"""Sliding-window bounded-decode measurement at long context.

Compares chunked decode throughput on a mistral-flavor 0.5B config at an
~8k-token cache: the window-GATHER path (sliding_window=4096, per-row reads
bounded to the window) vs the dense full-prefix stream (sliding_window=None,
reads the whole 8k+ prefix every step — what windowed models previously did
with masking).  Same model dims, same cache fill; the delta is the KV bytes
streamed per step."""

import json
import time

import numpy as np


def main():
    from areal_tpu.base.compile_cache import setup_compile_cache

    setup_compile_cache()
    import jax
    import jax.numpy as jnp

    from areal_tpu.engine.inference_server import _decode_chunk
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.models import transformer
    from areal_tpu.models.config import TransformerConfig
    from areal_tpu.models.transformer import KVCache

    def cfg_for(window):
        return TransformerConfig(
            n_layers=24,
            hidden_dim=1024,
            n_q_heads=8,
            n_kv_heads=4,
            head_dim=128,
            intermediate_dim=5504,
            vocab_size=32768,
            max_position_embeddings=16384,
            use_attention_bias=True,
            dtype="bfloat16",
            sliding_window=window,
        )

    sampling = SamplingParams()
    B, S, fill, chunk = 8, 8576, 8000, 128  # fill + 4*chunk <= S: every
    # timed token really emits (capacity-deactivated rows would inflate tok/s)
    attn_len = 8576
    results = {}
    for name, window in (("window4096_gather", 4096), ("dense_full_prefix", None)):
        cfg = cfg_for(window)
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16),
            transformer.init_params(cfg, jax.random.PRNGKey(0)),
        )
        cache = KVCache.zeros(cfg, B, S, dtype=jnp.bfloat16)
        cache = KVCache(
            k=cache.k, v=cache.v,
            lengths=jnp.full((B,), fill, jnp.int32),
        )
        cur = jnp.ones((B,), jnp.int32)
        active = jnp.ones((B,), bool)
        budgets = jnp.full((B,), 10_000, jnp.int32)
        rng = jax.random.PRNGKey(1)
        out = _decode_chunk(
            params, cfg, cache, cur, active, budgets,
            jnp.zeros((B,), jnp.int32), rng, chunk, (),
            sampling, attn_len=attn_len,
        )
        cache, out_t, out_l, em, cur, active, budgets, rng = out
        jax.device_get((out_t, active))  # compile + settle
        t0 = time.perf_counter()
        n = 0
        N = 3
        for _ in range(N):
            out = _decode_chunk(
                params, cfg, cache, cur, active, budgets,
                jnp.zeros((B,), jnp.int32), rng, chunk, (),
                sampling, attn_len=attn_len,
            )
            cache, out_t, out_l, em, cur, active, budgets, rng = out
            # immediate fetch bounds live cache generations under lazy
            # execution (OOM guard); also counts what really emitted
            n += int(jax.device_get(em).sum())
        dt = time.perf_counter() - t0
        results[name] = round(n / dt, 1)
        print(json.dumps({name: results[name],
                          "ms_per_step": round(dt / N / chunk * 1e3, 3)}),
              flush=True)
        del params, cache
    results["speedup"] = round(
        results["window4096_gather"] / results["dense_full_prefix"], 3
    )
    print(json.dumps(results))


if __name__ == "__main__":
    main()
