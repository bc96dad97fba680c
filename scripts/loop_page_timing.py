"""Pages of 64 or of 128 tokens for a looped stack's cache (Ouro-2.6B: 192
cache layers of 16 KV heads of 128, ONE query head a KV head)?  The paged
decode kernel ALONE on the chip at the cell's shape: 16 rows (ISSUE 55's)
and 6 rows (the slots the cell runs) at contexts of 150-1,500 tokens scaled
to what the cell's pool holds, 192 calls back to back over a 192-layer
pool, as a decode step makes them, for each page size; the plan made once
before them, as a decode chunk makes it.

    chiprun --chips 1 --timeout 900 -- python3 scripts/loop_page_timing.py

Prints one JSON line a page size: microseconds a call, microseconds a token
attended and the GB/s of the bytes attended, and half a page of slack a
row as a share of the pool.  ``benchmark/traffic/rollout-full-loop.json``
takes 64 unless it is slower a token attended by more than the slack it
saves.  The same lines go to ``chiprun_out/loop_page_timing.jsonl``.
Without a TPU it refuses to run.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

HEADS, HD, LAYERS, CTX_MAX, POOL_TOKENS = 16, 128, 192, 1536, 4864


def measure(page: int, ROWS: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import paged

    rng = np.random.default_rng(seed)
    # contexts of 150-1,500 whose pages fit the pool a deployment holds
    lengths = rng.integers(150, 1501, ROWS)
    lengths = (lengths * min(1.0, 0.75 * POOL_TOKENS / lengths.sum())).astype(np.int64)
    held = -(-lengths // page)
    NB, MB = POOL_TOKENS // page, CTX_MAX // page
    assert held.sum() < NB, (held.sum(), NB)
    tables = np.zeros((ROWS, MB), np.int32)
    ids = rng.permutation(np.arange(1, NB))
    at = 0
    for b in range(ROWS):
        tables[b, : held[b]] = ids[at : at + held[b]]
        at += held[b]
    kp, kq = jax.random.split(jax.random.PRNGKey(seed))
    block = jax.random.normal(kp, (8, HEADS, page, HD), jnp.bfloat16)
    pool = jnp.take(block, jnp.arange(LAYERS * NB) % 8, axis=0).reshape(
        LAYERS, NB, HEADS, page, HD
    )
    vpool = pool + 1
    q = jax.random.normal(kq, (ROWS, 1, HEADS, HD), jnp.bfloat16)
    tb, ln = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)

    @jax.jit
    def step(q, pool, vpool, tb, ln):
        plan = paged._prefix_plan(1, HEADS, pool, tb, ln, True)

        def body(acc, layer):
            a, m, l = paged._prefix_partials(
                q, pool, vpool, tb, ln, layer, True, plan=plan
            )
            return acc + a.sum() + m.sum() + l.sum(), None

        return jax.lax.scan(body, jnp.float32(0), jnp.arange(LAYERS))[0]

    args = (q, pool, vpool, tb, ln)
    jax.block_until_ready(step(*args))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(4):
            out = step(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / 4)
    tokens = int(lengths.sum())
    call_us = 1e6 * best / LAYERS
    kv_bytes = tokens * 2 * HEADS * HD * 2
    return {
        "page": page, "rows": ROWS, "tokens_attended": tokens,
        "contexts": [int(lengths.min()), int(lengths.max())],
        "step_ms_192_calls": 1e3 * best, "call_us": call_us,
        "ns_a_token": 1e3 * call_us / tokens,
        "gb_per_s": kv_bytes / (best / LAYERS) / 1e9,
        "tile_tokens": paged.kernel_tile_tokens(pool),
        "slack_share_of_pool": ROWS * page / 2 / POOL_TOKENS,
    }


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print("loop_page_timing.py needs a TPU", file=sys.stderr)
        return 2
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "loop_page_timing.jsonl"), "w") as f:
        for page, rows in ((64, 16), (128, 16), (64, 6), (128, 6), (64, 6), (128, 6)):
            row = measure(page, rows, seed=20261055)
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
