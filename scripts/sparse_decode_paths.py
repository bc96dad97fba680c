"""Which way does a decode step of an indexed latent layer read its chosen
entries faster: GATHERED (``sparse_attention.select`` + ``sparse_latent_
partials``: two sorts, then ``index_topk`` rows a sequence out of the pool)
or MASKED (``sparse_attention.chosen_mask`` + the paged kernel's latent mode
under the selection, ``paged_flash_attention(mask=)`` at one query a row:
every cached position of the row multiplied, the chosen ones kept)?

Both paths ALONE on the chip, at the sparse cell's widths (128 heads, pages
of 512 x 640 bf16, values the first 512 columns, ``index_topk`` 2,048, 64
slots), at several table sizes and two counts of live rows, rows filled to
70% of the table; then the line through each path's times over the table
size and where the two cross, as a ratio ``table positions / index_topk``.
That ratio is the source of ``sparse_attention.MASKED_DECODE_MAX_RATIO``:

    chiprun --chips 1 --timeout 1500 -- python3 scripts/sparse_decode_paths.py

Prints one JSON line a (table, rows) pair with each part's milliseconds a
call (``sort``: ``select`` and what the step makes of its positions;
``gather``: ``sparse_latent_partials``; ``mask``: ``chosen_mask``;
``kernel``: the Mosaic call with the plan made before, as a decode chunk
makes it once for its 16 calls; ``plan``: that plan; ``kernel_bare``: the
same call without the selection), and a closing line with the fits.  The
same lines go to ``chiprun_out/sparse_decode_paths.jsonl``.  Without a TPU
it refuses to run: the interpreter's times are no source for the constant.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SLOTS, HEADS, PAGE, WIDTH, VALUE, TOPK, STEPS = 64, 128, 512, 640, 512, 2048, 8
LAYERS, SCALE, FILL = 2, 0.07, 0.7


def _ms(fn, args, calls: int) -> float:
    """Milliseconds a call of ``fn(*args)``: ``calls`` of them queued one
    behind the other, the last waited for (the best of three such trains)."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return 1e3 * best


def measure(pages: int, live_rows: int, calls: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import paged
    from areal_tpu.ops import sparse_attention as sparse

    rng = np.random.default_rng(seed)
    N = pages * PAGE
    # rows filled to 70% of the table, a page either way; the slots past
    # ``live_rows`` hold nothing (scattered among the live ones)
    lengths = np.zeros(SLOTS, np.int64)
    slots = rng.permutation(SLOTS)[:live_rows]
    lengths[slots] = np.clip(
        FILL * N + rng.integers(-PAGE, PAGE + 1, live_rows), 1, N
    )
    held = -(-lengths // PAGE)
    NB = int(held.sum()) + 1
    tables = np.zeros((SLOTS, pages), np.int32)
    ids = rng.permutation(np.arange(1, NB))
    at = 0
    for b in range(SLOTS):
        tables[b, : held[b]] = ids[at : at + held[b]]
        at += held[b]
    key = jax.random.PRNGKey(seed)
    kp, kq, ks = jax.random.split(key, 3)
    # (a block of random pages repeated: a draw of the whole pool at once
    # would hold it several times over in float32)
    block = jax.random.normal(kp, (min(NB, 256), 1, PAGE, WIDTH), jnp.bfloat16)
    pool = jnp.take(
        block, jnp.arange(LAYERS * NB) % block.shape[0], axis=0
    ).reshape(LAYERS, NB, 1, PAGE, WIDTH)
    q = jax.random.normal(kq, (SLOTS, 1, HEADS, WIDTH), jnp.bfloat16)
    pos = jnp.arange(N + STEPS)
    ln = jnp.asarray(lengths, jnp.int32)
    # a step's scores: the cached positions', then the chunk's own
    scores = jnp.where(
        (pos[None] < ln[:, None]) | ((pos[None] >= N) & (ln[:, None] > 0)),
        jax.random.normal(ks, (SLOTS, N + STEPS), jnp.float32), sparse.NEG,
    )
    tb, layer = jnp.asarray(tables), jnp.int32(1)

    @jax.jit
    def sort(scores, ln):
        idx, live = sparse.select(scores, TOPK)
        own = jnp.any(
            (idx[:, :, None] == N + jnp.arange(STEPS)) & live[:, :, None], axis=1
        )
        kept = jnp.where(live, jnp.where(idx < N, idx, idx - N + ln[:, None]), -1)
        return idx, live & (idx < N), own, kept

    @jax.jit
    def gather(q, pool, tb, idx, live, layer):
        return sparse.sparse_latent_partials(
            q, pool, layer, tb, idx, live, VALUE, SCALE
        )

    @jax.jit
    def mask(scores):
        chosen = sparse.chosen_mask(scores, TOPK)
        return chosen[:, None, :N], chosen[:, N:]

    @jax.jit
    def plan_of(pool, tb, ln):
        return paged._prefix_plan(1, HEADS, pool, tb, ln, True, masked=True)

    @jax.jit
    def kernel(q, pool, tb, ln, layer, plan, chosen):
        return paged._prefix_partials(
            q, pool, None, tb, ln, layer, True, plan=plan, scale=SCALE,
            value_dim=VALUE, mask=chosen,
        )

    @jax.jit
    def plan_bare_of(pool, tb, ln):
        return paged._prefix_plan(1, HEADS, pool, tb, ln, True)

    @jax.jit
    def kernel_bare(q, pool, tb, ln, layer, plan):
        return paged._prefix_partials(
            q, pool, None, tb, ln, layer, True, plan=plan, scale=SCALE,
            value_dim=VALUE,
        )

    idx, live, _, _ = sort(scores, ln)
    chosen, _ = mask(scores)
    plan = plan_of(pool, tb, ln)
    out = {
        "table_positions": N, "live_rows": live_rows, "slots": SLOTS,
        "cached_mean": float(lengths[slots].mean()),
        "ratio": N / TOPK,
        "sort_ms": _ms(sort, (scores, ln), calls),
        "gather_ms": _ms(gather, (q, pool, tb, idx, live, layer), calls),
        "mask_ms": _ms(mask, (scores,), calls),
        "kernel_ms": _ms(kernel, (q, pool, tb, ln, layer, plan, chosen), calls),
        "plan_ms": _ms(plan_of, (pool, tb, ln), calls),
        # the same call without the selection: what the operand costs
        "kernel_bare_ms": _ms(
            kernel_bare, (q, pool, tb, ln, layer, plan_bare_of(pool, tb, ln)), calls
        ),
    }
    out["gathered_ms"] = out["sort_ms"] + out["gather_ms"]
    out["masked_ms"] = out["mask_ms"] + out["kernel_ms"]
    # the two paths attend one set: their partials agree
    a = gather(q, pool, tb, idx, live, layer)
    b = kernel(q, pool, tb, ln, layer, plan, chosen)
    na, nb = (np.asarray(x[0] / jnp.maximum(x[2], 1e-30)[..., None]) for x in (a, b))
    out["paths_apart_max"] = float(np.abs(na - nb)[lengths > 0].max())
    return out


def crossing(rows: list) -> dict:
    """Least-squares lines ``ms = a + b x ratio`` through each path's times
    at one count of live rows, and the ratio at which they meet."""
    x = np.array([r["ratio"] for r in rows])
    fit = {}
    for path in ("gathered_ms", "masked_ms"):
        b, a = np.polyfit(x, np.array([r[path] for r in rows]), 1)
        fit[path] = {"at_0": float(a), "per_ratio": float(b)}
    g, m = fit["gathered_ms"], fit["masked_ms"]
    slope = m["per_ratio"] - g["per_ratio"]
    fit["crossing_ratio"] = (
        float((g["at_0"] - m["at_0"]) / slope) if slope > 0 else None
    )
    return fit


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--pages", default="36,72,108", help="table sizes, in pages of 512")
    p.add_argument("--rows", default="49,64", help="live rows of the 64 slots")
    p.add_argument("--calls", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        # off the chip the kernel runs in the interpreter: its times would
        # come out in the same form and set nothing
        sys.exit("scripts/sparse_decode_paths.py needs a TPU")
    lines, fits = [], {}
    for live_rows in (int(r) for r in args.rows.split(",")):
        rows = [
            measure(int(pg), live_rows, args.calls, args.seed)
            for pg in args.pages.split(",")
        ]
        for r in rows:
            print(json.dumps(r), flush=True)
        lines += rows
        fits[str(live_rows)] = crossing(rows)
    last = {"fits": fits, "device": jax.devices()[0].device_kind}
    print(json.dumps(last), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "sparse_decode_paths.jsonl"), "w") as f:
        for r in lines + [last]:
            f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
