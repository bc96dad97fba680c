"""Pallas flash-decode attention over a contiguous per-row KV cache.

In-house TPU kernel for the rollout engine's decode hot loop (the role the
reference delegates to SGLang/flashinfer paged decode kernels,
realhf/impl/model/backend/sglang.py:369).  One query token per row attends
over that row's cache prefix ``[0, length)``:

* grid ``(B, Hkv, S/block)`` — the minor block axis iterates sequentially on
  TPU, so online-softmax state (m/l/acc) lives in VMEM scratch across blocks
  and the normalized output is emitted at the last block;
* ``lengths`` rides scalar prefetch: the K/V index maps CLAMP the block
  index to the last valid block of each row.  The trailing blocks are
  later grid steps of the SAME operand, so they re-address the tile of
  the step before and the pipeline skips their HBM->VMEM copies: short
  rows stream only the KV they own.  (That holds for one operand walked
  along the grid's minor axis, as here; the paged kernel, whose pages
  lie anywhere in a pool, starts its own copies: ``ops/paged_attention``);
* GQA is grouped: the query head group ``r = Hq // Hkv`` shares one KV head
  per grid cell, so the cache is read once per KV head (never
  repeat-materialized).

Returns UN-normalized partials ``(acc, m, l)`` so the caller can
online-merge them with attention over KV that is not in the cache yet (the
decode chunk's in-flight window, models/transformer.py:decode_chunk).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK = 256
_NEG_INF = -1e30


def softmax_scratch_init(s_acc, s_m, s_l):
    """Reset the online-softmax VMEM scratch at the first grid block
    (shared with ops/paged_attention.py)."""
    s_acc[:] = jnp.zeros_like(s_acc)
    s_m[:] = jnp.full_like(s_m, _NEG_INF)
    s_l[:] = jnp.zeros_like(s_l)


def _split_bf16(x):
    """``x`` (float32) as three bf16 arrays that sum to it, largest
    first: 8 significant bits each hold all 24 of a float32, so the sum
    is exact."""
    parts = []
    for _ in range(3):
        hi = x.astype(jnp.bfloat16)
        parts.append(hi)
        x = x - hi.astype(jnp.float32)
    return parts


def _dot_f32(a, b, dims):
    """float32 x float32 on the MXU at HIGHEST precision (six bf16
    passes): the path of every operand that is not stored as bf16."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), (dims, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _dot_bf16(a, b, dims):
    """bf16 x bf16 in ONE MXU pass, accumulated in float32.  A product of
    two bf16 numbers has 16 significant bits, so it is exact in float32:
    this is the number HIGHEST gives for the same operands."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32
    )


def softmax_block_update(
    q, k, v, s_acc, s_m, s_l, *, base, length, scale, first=None, chosen=None
):
    """One KV block's online-softmax update over (rows, hd) queries —
    the SINGLE definition of the decode-attention numerics, used by both
    the contiguous (flash_decode) and paged kernels.  ``q``/``k``/``v``
    are already-loaded VMEM tiles: (rows, hd), (BS, hd), (BS, hd).

    Float32 accuracy on both dots, by the cheapest route the operands'
    dtypes allow (a plain float32 MXU dot rounds its operands to bf16:
    0.1 abs output error at 4k lengths against 6e-5):

    * q, K and V all bf16 (a bf16 pool under a bf16 model): ``q k^T``
      is one bf16 pass, which is exact; the float32 probabilities are
      split into three bf16 terms (exact) that ride ONE dot against the
      bf16 V tile, stacked along the row axis.  No operand is widened.
    * anything else (an int8 pool dequantized to float32, a float32
      pool, float32 queries): float32 operands at HIGHEST precision.

    ``first`` ((rows, 1) int32, or None): the first position each query
    row attends (a sliding window); positions before it are masked like
    those at and past ``length``, and their probabilities are set to 0
    (a block may then hold no position at all for a row, whose running
    maximum stays at its initial value: ``exp(s - m)`` of a masked entry
    would read 1 there).

    ``chosen`` ((rows, BS) bool, or None): a selection, which of the
    block's positions each query row attends at all (an indexer's choice);
    what is not chosen is masked and its probability set to 0 in the same
    way, for the same reason (a block may hold nothing a row chose).

    Decode is NOT so HBM-bound that HIGHEST's passes are free: widening
    every bf16 K and V tile to float32 and splitting it back into bf16
    terms held the paged kernel to 458 GB/s on a v5e with every page
    useful, against 731 with the bf16 operands (PERF.md section 6,
    PR 25).  The stacked dot loads each 128x128 tile of V into the MXU
    once for all three terms; that is worth 3%, the rest is the
    widening.
    """
    nt = ((1,), (1,))  # (rows, hd) x (BS, hd) -> (rows, BS)
    nn = ((1,), (0,))  # (rows, BS) x (BS, hd) -> (rows, hd)
    bf16 = all(x.dtype == jnp.bfloat16 for x in (q, k, v))
    s = (_dot_bf16 if bf16 else _dot_f32)(q, k, nt) * scale  # (rows, BS)
    pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < length
    if first is not None:
        valid &= pos >= first
    if chosen is not None:
        valid &= chosen
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = s_m[:, 0]  # (rows,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])  # (rows, BS)
    if first is not None or chosen is not None:
        p = jnp.where(valid, p, 0.0)
    if bf16:
        rows = p.shape[0]
        stacked = _dot_bf16(jnp.concatenate(_split_bf16(p), axis=0), v, nn)
        pv = stacked[:rows] + stacked[rows : 2 * rows] + stacked[2 * rows :]
    else:
        pv = _dot_f32(p, v, nn)  # (rows, hd)
    s_acc[:] = s_acc[:] * alpha[:, None] + pv
    s_l[:] = s_l[:] * alpha[:, None] + jnp.sum(p, axis=1)[:, None]
    s_m[:] = jnp.broadcast_to(m_cur[:, None], s_m.shape)


def softmax_emit(acc_ref, m_ref, l_ref, s_acc, s_m, s_l):
    """Write the scratch state out at the last grid block."""
    acc_ref[0, 0] = s_acc[:]
    m_ref[0, 0] = s_m[:]
    l_ref[0, 0] = s_l[:]


def _kernel(
    lengths_ref,  # scalar prefetch [B]
    q_ref,  # (1, 1, r, hd)
    k_ref,  # (1, 1, BS, hd)
    v_ref,  # (1, 1, BS, hd)
    acc_ref,  # out (1, 1, r, hd) f32
    m_ref,  # out (1, 1, r, 128) f32 (value replicated along lanes)
    l_ref,  # out (1, 1, r, 128) f32
    s_acc,  # scratch (r, hd) f32
    s_m,  # scratch (r, 128) f32
    s_l,  # scratch (r, 128) f32
    *,
    block_size: int,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        softmax_scratch_init(s_acc, s_m, s_l)

    length = lengths_ref[b]
    base = j * block_size

    @pl.when(base < length)
    def _block():
        softmax_block_update(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], s_acc, s_m, s_l,
            base=base, length=length, scale=scale,
        )

    @pl.when(j == nb - 1)
    def _emit():
        softmax_emit(acc_ref, m_ref, l_ref, s_acc, s_m, s_l)


def _clamped_kv_map(b, h, j, lengths_ref, *, block_size):
    # last block that holds any valid KV for row b (>= 0 so length-0 rows
    # still address a real tile; their compute is skipped in the kernel)
    last = jnp.maximum(
        (lengths_ref[b] + block_size - 1) // block_size - 1, 0
    )
    return (b, h, jnp.minimum(j, last), 0)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "interpret"),
)
def flash_decode(
    q: jax.Array,  # [B, Hq, hd]
    k: jax.Array,  # [B, Hkv, S, hd]
    v: jax.Array,  # [B, Hkv, S, hd]
    lengths: jax.Array,  # [B] int32 — valid cache prefix per row
    block_size: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Un-normalized online-softmax attention partials over the cache.

    Returns ``(acc [B,Hq,hd] f32, m [B,Hq] f32, l [B,Hq] f32)`` with
    ``out = acc / l`` the attention output when nothing else is merged.
    Rows with ``length == 0`` return ``acc=0, l=0, m=-inf``.
    """
    B, Hq, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    r = Hq // Hkv
    assert S % block_size == 0, (S, block_size)
    nb = S // block_size
    qg = q.reshape(B, Hkv, r, hd)

    grid = (B, Hkv, nb)
    kv_map = functools.partial(_clamped_kv_map, block_size=block_size)
    acc, m, l = pl.pallas_call(
        functools.partial(
            _kernel, block_size=block_size, scale=1.0 / np.sqrt(hd)
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, r, hd), lambda b, h, j, L: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_size, hd), kv_map),
                pl.BlockSpec((1, 1, block_size, hd), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, r, hd), lambda b, h, j, L: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, r, 128), lambda b, h, j, L: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, r, 128), lambda b, h, j, L: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((r, hd), jnp.float32),
                pltpu.VMEM((r, 128), jnp.float32),
                pltpu.VMEM((r, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, r, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, r, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, r, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return (
        acc.reshape(B, Hq, hd),
        m[..., 0].reshape(B, Hq),
        l[..., 0].reshape(B, Hq),
    )


def reference_decode_partials(q, k, v, lengths):
    """jnp reference for :func:`flash_decode` (same (acc, m, l) contract)."""
    B, Hq, hd = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    r = Hq // Hkv
    qg = q.reshape(B, Hkv, r, hd).astype(jnp.float32)
    s = jnp.einsum(
        "bkrd,bksd->bkrs", qg, k.astype(jnp.float32)
    ) / np.sqrt(hd)
    mask = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkrs,bksd->bkrd", p, v.astype(jnp.float32))
    return (
        acc.reshape(B, Hq, hd),
        m.reshape(B, Hq),
        l.reshape(B, Hq),
    )
