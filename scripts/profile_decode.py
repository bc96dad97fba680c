"""On-chip decode profiling: where does the missing roofline half go?

Times the jitted decode_chunk in isolation (device-only, no engine host
loop) across batch x attn_len, plus ablations (no-head sampling, bigger
chunks), and compares against the engine's end-to-end loop.  Prints one
JSON line per measurement.  Run with the real TPU visible (no JAX_PLATFORMS
override).
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def bench_cfg():
    from areal_tpu.models.config import TransformerConfig

    return TransformerConfig(
        n_layers=24,
        hidden_dim=1024,
        n_q_heads=8,
        n_kv_heads=4,
        head_dim=128,
        intermediate_dim=5504,
        vocab_size=32768,
        max_position_embeddings=4096,
        use_attention_bias=True,
        dtype="bfloat16",
    )


def main():
    from functools import partial

    from areal_tpu.base.compile_cache import setup_compile_cache

    setup_compile_cache()

    from areal_tpu.engine.sampling import SamplingParams, sample_logits
    from areal_tpu.models import transformer
    from areal_tpu.models.transformer import KVCache, decode_chunk

    cfg = bench_cfg()
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        transformer.init_params(cfg, jax.random.PRNGKey(0)),
    )
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    sampling = SamplingParams()
    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "n_params": n_params}))

    def sample_fn(logits, rng):
        return sample_logits(logits, rng, sampling)

    def stop_fn(tok):
        return jnp.zeros_like(tok, dtype=bool)

    @partial(jax.jit, static_argnames=("B", "S", "chunk", "attn_len"))
    def run_chunk(params, cache_len_fill, rng, B, S, chunk, attn_len):
        cache = KVCache.zeros(cfg, B, S, dtype=jnp.bfloat16)
        cache = KVCache(
            k=cache.k, v=cache.v,
            lengths=jnp.full((B,), cache_len_fill, jnp.int32),
        )
        cur = jnp.ones((B,), jnp.int32)
        active = jnp.ones((B,), bool)
        budgets = jnp.full((B,), chunk + 1, jnp.int32)
        out = decode_chunk(
            params, cfg, cache, cur, active, budgets, rng, chunk,
            sample_fn, stop_fn, attn_len=attn_len,
        )
        return out[1]  # tokens [B, chunk]

    results = []
    for B in (16, 32, 64):
        for fill, attn_len in ((512, 1024), (1500, 2048)):
            for chunk in (128, 256):
                S = 4096
                rng = jax.random.PRNGKey(1)
                toks = run_chunk(params, fill, rng, B, S, chunk, attn_len)
                np.asarray(toks)  # compile + real host fetch
                t0 = time.perf_counter()
                n_rep = 3
                for i in range(n_rep):
                    toks = run_chunk(
                        params, fill, jax.random.PRNGKey(i), B, S, chunk,
                        attn_len,
                    )
                    np.asarray(toks)
                dt = (time.perf_counter() - t0) / n_rep
                tok_s = B * chunk / dt
                ms_per_step = dt / chunk * 1e3
                # bandwidth model: per step reads weights once + per-row KV
                # prefix attn_len (k+v, bf16)
                kv_bytes = (
                    2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim
                    * attn_len * 2 * B
                )
                w_bytes = n_params * 2
                bw_need = (kv_bytes + w_bytes) / (dt / chunk)
                r = {
                    "B": B, "fill": fill, "attn_len": attn_len,
                    "chunk": chunk,
                    "tok_s": round(tok_s, 1),
                    "ms_per_step": round(ms_per_step, 3),
                    "hbm_gbps_implied": round(bw_need / 1e9, 1),
                }
                results.append(r)
                print(json.dumps(r), flush=True)

    print(json.dumps({"summary": sorted(
        results, key=lambda r: -r["tok_s"])[:5]}))


if __name__ == "__main__":
    main()
