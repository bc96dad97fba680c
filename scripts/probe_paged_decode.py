"""Probe: paged vs bucketed-dense decode throughput at long context
(Qwen2.5-1.5B architecture, random weights, synthetic KV).

Isolates the decode hot loop from the engine: fills a dense cache and a
paged pool with random KV at context L, then times W-token decode chunks.
Run on the real chip:  python scripts/probe_paged_decode.py [L ...]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from bench import qwen25_15b_config  # noqa: E402

from areal_tpu.models import paged, transformer  # noqa: E402
from areal_tpu.models.transformer import KVCache, decode_chunk  # noqa: E402

BS = int(__import__("os").environ.get("PROBE_BS", "256"))
W = 64


def greedy(logits, _rng):
    return (
        jnp.argmax(logits, -1).astype(jnp.int32),
        jnp.max(jax.nn.log_softmax(logits), -1),
    )


def no_stop(toks):
    return jnp.zeros_like(toks, bool)


def bucket(n):
    p = 256
    while p < n:
        p <<= 1
    return p


def run(cfg, params, L, B):
    S = bucket(L + 2 * W + 8)
    MB = S // BS
    NB = B * MB + 4
    Hkv, hd = cfg.n_kv_heads, cfg.head_dim
    key = jax.random.PRNGKey(0)
    kd = jax.random.normal(
        key, (cfg.n_layers, B, Hkv, S, hd), jnp.bfloat16
    ) * 0.05
    lengths = jnp.full((B,), L, jnp.int32)
    cache = KVCache(k=kd, v=kd + 0.0, lengths=lengths)  # no alias: donated
    cur = jnp.full((B,), 7, jnp.int32)
    active = jnp.ones((B,), bool)

    dense_jit = jax.jit(
        decode_chunk,
        static_argnames=(
            "cfg", "chunk_size", "sample_fn", "stop_fn", "attn_len"
        ),
        donate_argnums=(2,),
    )

    def dense_round(cache, cur_in, budgets, rng):
        return dense_jit(
            params, cfg, cache, cur_in, active, budgets, rng,
            chunk_size=W, sample_fn=greedy, stop_fn=no_stop, attn_len=S,
        )

    rng = jax.random.PRNGKey(1)
    budgets = jnp.full((B,), 10_000, jnp.int32)
    times = []
    cur_h = cur
    for it in range(5):
        t0 = time.perf_counter()
        cache, out_t, out_l, em, cur2, act2, budgets, rng = dense_round(
            cache, cur_h, budgets, rng
        )
        # host fetch + feedback: route the sampled token back through
        # the host, exactly what the engine does
        cur_h = jnp.asarray(np.asarray(out_t[:, -1]))
        times.append(time.perf_counter() - t0)
    dense_times = [round(t, 3) for t in times]
    dense_tps = B * W / min(times[2:])
    del cache, kd
    # paged
    kp = jax.random.normal(
        key, (cfg.n_layers, NB, Hkv, BS, hd), jnp.bfloat16
    ) * 0.05
    # distinct buffer: paged_decode_chunk donates BOTH pools (an aliased
    # buffer donated twice is a runtime error)
    vp = kp + 0.0
    tables = jnp.arange(B * MB, dtype=jnp.int32).reshape(B, MB)
    lengths = jnp.full((B,), L, jnp.int32)
    budgets = jnp.full((B,), 10_000, jnp.int32)
    rng = jax.random.PRNGKey(1)
    times = []
    cur_h = cur
    for it in range(5):
        t0 = time.perf_counter()
        (kp, vp, lengths, out_t, out_l, em, cur2, act2, budgets, rng) = (
            paged.paged_decode_chunk(
                params, kp, vp, cfg, tables, lengths, cur_h, active,
                budgets, rng, W, greedy, no_stop,
                use_kernel=True, max_len=S,
            )
        )
        cur_h = jnp.asarray(np.asarray(out_t[:, -1]))
        times.append(time.perf_counter() - t0)
    paged_times = [round(t, 3) for t in times]
    paged_tps = B * W / min(times[2:])
    kv_per_tok = cfg.n_layers * Hkv * hd * 2 * 2
    roofline = 820e9 / (L * kv_per_tok) * B  # HBM-bound bound per chip
    print(
        f"L={L:6d} B={B:3d}: dense {dense_tps:7.1f} tok/s | paged "
        f"{paged_tps:7.1f} tok/s | ratio {paged_tps/dense_tps:5.2f} | "
        f"KV-roofline {roofline:7.0f}"
    )
    print(f"    dense times {dense_times}  paged times {paged_times}")
    del kp, vp
    return dense_tps, paged_tps


def main():
    cfg = qwen25_15b_config()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    cases = [(2048, 16), (8192, 16), (16384, 16), (32768, 8)]
    if len(sys.argv) > 1:
        want = {int(a) for a in sys.argv[1:]}
        cases = [c for c in cases if c[0] in want]
    for L, B in cases:
        run(cfg, params, L, B)


if __name__ == "__main__":
    main()
