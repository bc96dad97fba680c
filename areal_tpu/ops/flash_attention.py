"""TPU flash attention for packed (segment-id) batches.

Replaces the reference's flash-attn varlen CUDA dependency
(reference: realhf/impl/model/modules/attn.py:24-289 using
``flash_attn_varlen_func``) with the TPU-idiomatic equivalent: Pallas
flash-attention kernels over padded ``[B, T]`` batches where packing is
expressed via segment ids.  Differentiable through a custom VJP that
saves only the output and the log-sum-exp, so training memory stays O(T)
per layer instead of the O(T^2) probs matrix.

The three kernels (forward, dq, dkv) are this repository's own: the
kernels shipped with JAX (``jax.experimental.pallas.ops.tpu
.flash_attention``) cut down to what the trainer uses (causal, segment
ids always present, one block size, no bias) and given one more test.  A
block pair runs only where it lies under the diagonal AND a q token and a
kv token of it can belong to one segment (:func:`block_ranges`); a pair
that is skipped costs a grid step and no copy, so attention's cost
follows the sequences in a packed row, not the row.  Under a WINDOW
(``window=``, static: a query attends ``0 <= i - j < window``) a pair runs
only where some ``(i, j)`` of it is inside the window too, and the grid's
minor axis walks the band's blocks alone (``1 + ceil((window - 1) /
block)`` of them, two at a window of 512): a window layer's cost follows
``min(T, window)`` and a 16k row costs no 1,024 grid steps a head.  With
``window=None`` the programs are what they were, bit for bit.  GQA is
handled by repeating KV heads.  Self-attention only (decode-time KV-cache
attention uses the cache path in the model).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK = 512
_LANES = 128
_SUBLANES = 8
_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
#: padding (id 0) is renumbered above every real id when block ranges are
#: taken, so that a block's (lowest, highest) pair stays tight where a row
#: ends in padding
_PAD_ID = np.iinfo(np.int32).max


def _block_of(T: int) -> int:
    """The block a row of ``T`` slots is tiled in."""
    return min(_BLOCK, T)


def supported(q_len: int, kv_len: int, sliding_window) -> bool:
    """Whether rows of ``q_len`` slots tile into the kernels' blocks (under
    any window, or none)."""
    if q_len != kv_len or q_len < 128:
        return False
    # whole blocks, each of whole lane tiles
    return q_len % _block_of(q_len) == 0 and q_len % _LANES == 0


def band_blocks(T: int, window) -> int:
    """Blocks of a row of ``T`` slots that a q block can meet under
    ``window``: its own and those before it that hold a position less than
    ``window`` back (the whole row's without a window)."""
    blk = _block_of(T)
    if window is None:
        return T // blk
    return min(T // blk, 1 + (window + blk - 2) // blk)


def block_ranges(seg_ids, blk: int, xp=jnp, window=None):
    """Which block pairs of a causal, segment-masked row have to run.

    ``seg_ids`` [B, T] (0 = padding) in blocks of ``blk`` slots; returns
    ``(kv_lo, q_hi)``, both [B, T // blk] int32: q block ``i`` visits the
    kv blocks ``kv_lo[i] .. i`` and kv block ``j`` is visited from the q
    blocks ``j .. q_hi[j]``.  Conservative for ANY ids: two blocks are
    kept apart only where their (lowest id, highest id) ranges do not
    overlap, so a pair that holds two equal ids is never dropped, and a
    block always meets itself.  For the trainer's layouts (contiguous runs
    numbered 1..k, padding at the row's end) the ranges are exact.
    Under ``window`` a pair is kept apart as well where its nearest two
    positions (the q block's first, the kv block's last) are ``window`` or
    more apart.  ``xp`` is ``numpy`` for the host's count of the same
    rule."""
    B, T = seg_ids.shape
    n = T // blk
    ids = xp.where(seg_ids == 0, _PAD_ID, seg_ids).reshape(B, n, blk)
    lo, hi = ids.min(-1), ids.max(-1)
    at = xp.arange(n)
    meet = (
        (lo[:, :, None] <= hi[:, None, :])
        & (lo[:, None, :] <= hi[:, :, None])
        & (at[:, None] >= at[None, :])
    )  # [B, q block, kv block], under the diagonal
    if window is not None:
        meet = meet & (at[:, None] - at[None, :] <= (window + blk - 2) // blk)
    kv_lo = xp.argmax(meet, axis=2)
    q_hi = n - 1 - xp.argmax(meet[:, ::-1], axis=1)
    return kv_lo.astype(xp.int32), q_hi.astype(xp.int32)


def blocks_run(seg_ids: np.ndarray, blk: int = 0, window=None):
    """``(run, causal)`` block-pair counts of a host layout [B, T] at the
    kernels' block size (or ``blk``): what they run (under ``window``), of
    what lies under the diagonal (``(0, 0)`` for a row length the kernels
    do not tile)."""
    B, T = seg_ids.shape
    blk = blk or _block_of(T)
    n = T // blk
    if T % blk:
        return 0, 0
    kv_lo, _ = block_ranges(seg_ids, blk, xp=np, window=window)
    return int((np.arange(n) - kv_lo + 1).sum()), B * n * (n + 1) // 2


def _visit(major, minor, edge, q_major: bool):
    """The block pair ``(i, j)`` a grid point stands on and whether it
    runs.  The major axis walks q blocks (``edge`` = ``kv_lo``) or kv
    blocks (``edge`` = ``q_hi``); a skipped point stands on the nearest
    pair that runs, whose blocks are resident or wanted next."""
    lo, hi = (edge, major) if q_major else (major, edge)
    runs = jnp.logical_and(minor >= lo, minor <= hi)
    other = jnp.clip(minor, lo, hi)
    return (runs, major, other) if q_major else (runs, other, major)


def _at_minor(major, minor, band, q_major: bool):
    """The block the minor grid axis stands on: itself, or under a window
    (``band`` = the axis' length) the ``minor``-th of the band that ends
    (q-major) or starts (kv-major) at the major axis' block; outside the
    row where the band passes its ends, where no pair runs."""
    if band is None:
        return minor
    return major - (band - 1) + minor if q_major else major + minor


def _runs(edge_ref, b, major, minor, n, band, q_major: bool):
    """:func:`_visit` of a kernel's grid point (``n``: the minor axis'
    length, the row's blocks without a window)."""
    n_blocks = n if band is None else pl.num_programs(2)
    return _visit(
        major, _at_minor(major, minor, band, q_major),
        edge_ref[b * n_blocks + major], q_major,
    )


def _lanes(x, width: int):
    """A lane-replicated [rows, 128] column statistic at ``width`` lanes."""
    if width < _LANES:
        return x[:, :width]
    return jnp.tile(x, (1, width // _LANES))


def _scores(q_ref, k_ref, qseg_ref, kseg_ref, i, j, scale, window=None):
    """Masked scores of block pair (i, j), [blk, blk] float32."""
    s = lax.dot_general(
        q_ref[0, 0], k_ref[0, 0], _NT, preferred_element_type=jnp.float32
    )
    s *= scale
    blk = s.shape[1]
    same = _lanes(qseg_ref[0], blk) == kseg_ref[0, :1]
    rows = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    causal = cols - rows <= (i - j) * blk  # all true under the diagonal
    keep = jnp.logical_and(same, causal)
    if window is not None:
        keep = jnp.logical_and(keep, (i - j) * blk + rows - cols < window)
    return jnp.where(keep, s, _MASK_VALUE)


def _fwd_kernel(
    kv_lo_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
    o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, window=None, band=None,
):
    b, major, minor = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n = pl.num_programs(3)

    @pl.when(minor == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    runs, i, j = _runs(kv_lo_ref, b, major, minor, n, band, True)

    @pl.when(runs)
    def _():
        s = _scores(q_ref, k_ref, qseg_ref, kseg_ref, i, j, scale, window)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, s.max(axis=1)[:, None])
        p = jnp.exp(s - _lanes(m_next, s.shape[1]))
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=1)[:, None]
        m_scr[...] = m_next
        v = v_ref[0, 0]
        acc_scr[...] = acc_scr[...] * _lanes(alpha, v.shape[1]) + lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    @pl.when(minor == n - 1)
    def _():
        # every row has met at least its own slot: l > 0
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / _lanes(l, acc_scr.shape[1])).astype(
            o_ref.dtype
        )
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _probs_and_dscores(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, lse_ref, do_ref, di_ref,
    i, j, scale, window,
):
    """``p`` and ``dL/ds`` (short of the softmax scale) of pair (i, j)."""
    s = _scores(q_ref, k_ref, qseg_ref, kseg_ref, i, j, scale, window)
    p = jnp.exp(s - _lanes(lse_ref[0, 0], s.shape[1]))
    dp = lax.dot_general(
        do_ref[0, 0], v_ref[0, 0], _NT, preferred_element_type=jnp.float32
    )
    return p, (dp - _lanes(di_ref[0, 0], s.shape[1])) * p


def _dq_kernel(
    kv_lo_ref, q_ref, k_ref, v_ref, lse_ref, do_ref, di_ref, qseg_ref,
    kseg_ref, dq_ref, dq_scr, *, scale, window=None, band=None,
):
    b, major, minor = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n = pl.num_programs(3)

    @pl.when(minor == 0)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    runs, i, j = _runs(kv_lo_ref, b, major, minor, n, band, True)

    @pl.when(runs)
    def _():
        _, ds = _probs_and_dscores(
            q_ref, k_ref, v_ref, qseg_ref, kseg_ref, lse_ref, do_ref,
            di_ref, i, j, scale, window,
        )
        k = k_ref[0, 0]
        dq_scr[...] += lax.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    @pl.when(minor == n - 1)
    def _():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_hi_ref, q_ref, k_ref, v_ref, lse_ref, do_ref, di_ref, qseg_ref,
    kseg_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, window=None,
    band=None,
):
    b, major, minor = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n = pl.num_programs(3)

    @pl.when(minor == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    runs, i, j = _runs(q_hi_ref, b, major, minor, n, band, False)

    @pl.when(runs)
    def _():
        p, ds = _probs_and_dscores(
            q_ref, k_ref, v_ref, qseg_ref, kseg_ref, lse_ref, do_ref,
            di_ref, i, j, scale, window,
        )
        do, q = do_ref[0, 0], q_ref[0, 0]
        dv_scr[...] += lax.dot(
            p.T.astype(do.dtype), do, preferred_element_type=jnp.float32
        )
        dk_scr[...] += lax.dot(
            ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32
        )

    @pl.when(minor == n - 1)
    def _():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _spec(
    kind: str, width: int = 0, *, n: int, blk: int, q_major: bool, band=None
):
    """BlockSpec of one operand: ``q`` / ``kv`` are [B, H, T, width] cut
    along T at the pair's q or kv block, ``qseg`` [B, T, 128] and ``kseg``
    [B, 8, T] the segment ids as a column and as a row."""

    def at(b, h, major, minor, edge_ref):
        _, i, j = _visit(
            major, _at_minor(major, minor, band, q_major),
            edge_ref[b * n + major], q_major,
        )
        return {
            "q": (b, h, i, 0), "kv": (b, h, j, 0),
            "qseg": (b, i, 0), "kseg": (b, 0, j),
        }[kind]

    shape = {
        "q": (1, 1, blk, width), "kv": (1, 1, blk, width),
        "qseg": (1, blk, _LANES), "kseg": (1, _SUBLANES, blk),
    }[kind]
    return pl.BlockSpec(shape, at)


def _call(
    kernel, name, q_major, edge, ins, outs, scratch, seg_ids, interpret,
    window=None,
):
    """One Mosaic call over the grid (B, H, blocks, blocks), or (B, H,
    blocks, the band's blocks) under ``window``; ``ins`` and ``outs`` are
    ``(kind, array or shape)`` pairs, the segment ids follow the inputs,
    and ``scratch`` gives the widths of the float32 accumulators [blk,
    width]."""
    B, H, T, hd = ins[0][1].shape
    blk = _block_of(T)
    n = T // blk
    spec = functools.partial(_spec, n=n, blk=blk, q_major=q_major)
    static = {}
    if window is not None:
        band = band_blocks(T, window)
        spec = functools.partial(spec, band=band)
        static = dict(window=window, band=band)
        name = name.replace("flash_attn", "flash_attn_window")
    qseg = lax.broadcast_in_dim(seg_ids, (B, T, _LANES), (0, 1))
    kseg = lax.broadcast_in_dim(seg_ids, (B, _SUBLANES, T), (0, 2))
    return pl.pallas_call(
        functools.partial(kernel, scale=1.0 / np.sqrt(hd), **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n, static.get("band", n)),
            in_specs=[spec(kind, a.shape[-1]) for kind, a in ins]
            + [spec("qseg"), spec("kseg")],
            out_specs=[spec(kind, s.shape[-1]) for kind, s in outs],
            scratch_shapes=[
                pltpu.VMEM((blk, w), jnp.float32) for w in scratch
            ],
        ),
        out_shape=[s for _, s in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
            )
        ),
        name=name,
        interpret=interpret,
    )(edge.reshape(-1), *[a for _, a in ins], qseg, kseg)


def _forward(q, k, v, seg_ids, interpret, window=None):
    """``(o, lse)``: [B, H, T, hd] and the rows' log-sum-exp [B, H, T]."""
    B, H, T, hd = q.shape
    kv_lo, _ = block_ranges(seg_ids, _block_of(T), window=window)
    o, lse = _call(
        _fwd_kernel, "flash_attn_fwd", True, kv_lo,
        [("q", q), ("kv", k), ("kv", v)],
        [
            ("q", jax.ShapeDtypeStruct(q.shape, q.dtype)),
            ("q", jax.ShapeDtypeStruct((B, H, T, _LANES), jnp.float32)),
        ],
        (_LANES, _LANES, hd), seg_ids, interpret, window,
    )
    return o, lse[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attend(q, k, v, seg_ids, interpret, window=None):
    return _forward(q, k, v, seg_ids, interpret, window)[0]


def _attend_fwd(q, k, v, seg_ids, interpret, window):
    o, lse = _forward(q, k, v, seg_ids, interpret, window)
    return o, (q, k, v, seg_ids, o, lse)


def _attend_bwd(interpret, window, residuals, do):
    q, k, v, seg_ids, o, lse = residuals
    kv_lo, q_hi = block_ranges(
        seg_ids, _block_of(q.shape[2]), window=window
    )
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    ins = [("q", q), ("kv", k), ("kv", v)] + [
        ("q", a)
        for a in (
            jnp.broadcast_to(lse[..., None], (*lse.shape, _LANES)),
            do,
            jnp.broadcast_to(di[..., None], (*di.shape, _LANES)),
        )
    ]
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    (dq,) = _call(
        _dq_kernel, "flash_attn_bwd_dq", True, kv_lo, ins,
        [("q", like(q))], (q.shape[-1],), seg_ids, interpret, window,
    )
    dk, dv = _call(
        _dkv_kernel, "flash_attn_bwd_dkv", False, q_hi, ins,
        [("kv", like(k)), ("kv", like(v))], (q.shape[-1],) * 2, seg_ids,
        interpret, window,
    )
    return dq, dk, dv, None


_attend.defvjp(_attend_fwd, _attend_bwd)


def flash_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k: jax.Array,  # [B, T, Hkv, hd]
    v: jax.Array,  # [B, T, Hkv, hd]
    seg_ids: jax.Array,  # [B, T] int32, 0 = padding
    interpret: bool = False,
    window=None,  # static: attend ``i - j < window`` (None: the whole row)
) -> jax.Array:
    """Causal, segment-masked flash attention. Returns [B, T, Hq, hd]."""
    rep = q.shape[2] // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [B, H, T, hd]
    out = _attend(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), seg_ids,
        interpret, window,
    )
    return out.swapaxes(1, 2)
