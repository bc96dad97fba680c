#!/usr/bin/env python3
"""Hashes of the served stacks' fill and decode programs as lowered for a
DESCRIBED v5e (nothing attached, nothing compiled), for showing that a
change leaves a stack's programs alone:

    JAX_PLATFORMS=cpu python scripts/lowered_programs.py > change.txt     # in this tree
    (cd <a copy of the parent> && JAX_PLATFORMS=cpu PYTHONPATH=. python \\
        <this file> > parent.txt);  diff parent.txt change.txt

The hybrid, latent, window, shared and parallel cells' ``hybrid_fill_chunk`` /
``hybrid_decode_chunk`` and the dense cell's ``paged_fill_chunk`` /
``paged_decode_chunk`` at their cells' shapes (the shapes of
``tests/ops/test_tpu_compile.py``) and ``ops/ssm.ssm_state_update``'s
Mamba-2 and Mamba-1 calls.  A Mosaic kernel's body is embedded in the lowered text as
serialized MLIR WITH its source paths and line numbers, so two trees at
two paths never agree byte for byte: each body is parsed back and hashed
without its locations.  One process only (it loads the TPU compiler's
library)."""

import base64
import hashlib
import os
import re
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax._src.interpreters import mlir as jax_mlir  # noqa: E402
from jax._src.lib import tpu  # noqa: E402
from jax._src.lib.mlir import ir  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import tests.ops.test_tpu_compile as t  # noqa: E402
from areal_tpu.models import hybrid, paged  # noqa: E402
from areal_tpu.ops import ssm as ssm_ops  # noqa: E402

_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _without_locations(payload: str) -> str:
    ctx = jax_mlir.make_ir_context()
    tpu.register_dialect(ctx)
    with ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(base64.b64decode(payload))
        return module.operation.get_asm(enable_debug_info=False)


def digest(lowered) -> str:
    text = _BODY.sub(
        lambda m: m.group(1)
        + hashlib.sha1(_without_locations(m.group(2)).encode()).hexdigest()
        + m.group(3),
        lowered.as_text(),
    )
    return hashlib.sha1(text.encode()).hexdigest()


def main():
    paged.kernel_interpret = lambda: False
    desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(desc.devices[0])
    i32 = jnp.int32

    def decode(cfg, params, pools, ssm, conv, place, rows, table, chunk, max_len, **win):
        r = lambda d: place((rows,), d)
        return hybrid.hybrid_decode_chunk.lower(
            params, *pools, ssm, conv, cfg, table, r(i32), r(i32), r(jnp.bool_),
            r(i32), place((2,), jnp.uint32), chunk_size=chunk,
            sample_fn=t._keyed_greedy, stop_fn=t._never_stop, use_kernel=True,
            max_len=max_len, row_seeds=r(i32), **win,
        )

    def fill(cfg, params, pools, ssm, conv, place, F, C, table, **win):
        return hybrid.hybrid_fill_chunk.lower(
            params, *pools, ssm, conv, cfg, place((F, C), i32), place((F,), i32),
            place((F,), i32), table, place((F,), i32), use_kernel=True, **win,
        )

    out = {}
    cfg, params, pool, ssm, conv, place = t._hybrid_cell_args(one)
    args = (cfg, params, (pool, pool), ssm, conv, place)
    for F in (1, 2, 4):
        out[f"hybrid_fill_F{F}"] = fill(*args, F, t.HYBRID_FILL_C, place((F, t.MB), i32))
    out["hybrid_decode"] = decode(
        *args, t.HYBRID_SLOTS, place((t.HYBRID_SLOTS, t.MB), i32), t.DECODE_W,
        t.PAGE * t.MB,
    )
    S, N, HP = ssm.shape[1:]
    f32 = jnp.float32
    out["ssm_state_update_mamba2"] = ssm_ops.ssm_state_update.lower(
        ssm, place((), i32), place((S, HP), f32), place((S, HP), f32),
        place((S, N), f32), place((S, N), f32), place((S,), jnp.bool_),
    )
    cfg, params, pools, ssm, conv, place = t._window_cell_args(one, t.WINDOW_PAGE)
    args = (cfg, params, pools["global"], ssm, conv, place)
    for F, C in ((4, 1024), (1, 1024)):
        table = place((F, t.WINDOW_CTX // t.WINDOW_PAGE), i32)
        out[f"window_fill_F{F}"] = fill(
            *args, F, C, table, win_pools=pools["window"], win_tables=table
        )
    table = place((t.WINDOW_ROWS, t.WINDOW_CTX // t.WINDOW_PAGE), i32)
    out["window_decode"] = decode(
        *args, t.WINDOW_ROWS, table, t.WINDOW_CHUNK, t.WINDOW_CTX,
        win_pools=pools["window"], win_tables=table,
    )
    page = 512
    cfg, params, pools, ssm, conv, place = t._latent_cell_args(one, page)
    args = (cfg, params, pools, ssm, conv, place)
    for F, C in ((1, 1024), (4, 256)):
        out[f"latent_fill_F{F}"] = fill(
            *args, F, C, place((F, t.LATENT_CTX // page), i32)
        )
    out["latent_decode"] = decode(
        *args, t.LATENT_ROWS, place((t.LATENT_ROWS, t.LATENT_CTX // page), i32),
        t.LATENT_CHUNK, t.LATENT_CTX,
    )
    cfg, params, pools, ssm, conv, place = t._shared_cell_args(one)
    args = (cfg, params, pools["global"], ssm, conv, place)
    for F, C in ((2, 1024), (1, 1024)):
        table = place((F, t.SHARED_CTX // t.SHARED_PAGE), i32)
        out[f"shared_fill_F{F}"] = fill(
            *args, F, C, table, win_pools=pools["window"], win_tables=table
        )
    table = place((t.SHARED_ROWS, t.SHARED_CTX // t.SHARED_PAGE), i32)
    out["shared_decode"] = decode(
        *args, t.SHARED_ROWS, table, t.SHARED_CHUNK, t.SHARED_CTX,
        win_pools=pools["window"], win_tables=table,
    )
    S, N, HP = ssm.shape[1:]
    out["ssm_state_update_mamba1"] = ssm_ops.ssm_state_update.lower(
        ssm, place((), i32), place((S, HP), f32), place((S, HP), f32),
        place((S, N), f32), place((S, N), f32), place((S,), jnp.bool_),
        a=place((N, HP), f32),
    )
    _, place3 = t._place_on(desc, 1)
    cfg, params, pool, scales = t._serving_program_args(
        "qwen2.5-1.5b", 28, False, place3
    )
    out["dense_fill"] = paged.paged_fill_chunk.lower(
        params, pool, pool, cfg, place((t.FILL_F, t.FILL_C), i32),
        place((t.FILL_F,), i32), place((t.FILL_F,), i32),
        place((t.FILL_F, t.MB), i32), use_kernel=True, mesh=None,
        kv_axis=None, **scales,
    )
    r = lambda d: place((t.DECODE_B,), d)
    out["dense_decode"] = paged.paged_decode_chunk.lower(
        params, pool, pool, cfg, place((t.DECODE_B, t.MB), i32), r(i32),
        r(i32), r(jnp.bool_), r(i32), place((2,), jnp.uint32),
        chunk_size=t.DECODE_W, sample_fn=t._greedy, stop_fn=t._never_stop,
        use_kernel=True, max_len=t.PAGE * t.MB, mesh=None, kv_axis=None,
        **scales,
    )
    if hasattr(t, "_parallel_cell_args"):  # a tree from before the kind has none
        cfg, params, pools, ssm, conv, place = t._parallel_cell_args(one)
        args = (cfg, params, pools, ssm, conv, place)
        pages = t.PARALLEL_CTX // t.PARALLEL_PAGE
        for F, C in ((2, 1024), (1, 1024)):
            out[f"parallel_fill_F{F}"] = fill(*args, F, C, place((F, pages), i32))
        out["parallel_decode"] = decode(
            *args, t.PARALLEL_ROWS, place((t.PARALLEL_ROWS, pages), i32),
            t.PARALLEL_CHUNK, t.PARALLEL_CTX,
        )
    for name, lowered in out.items():
        print(name, digest(lowered), flush=True)


if __name__ == "__main__":
    main()
