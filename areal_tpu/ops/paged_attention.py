"""Pallas paged flash attention over a block-pool KV cache.

In-house TPU kernel for the serving engine's paged KV cache (the role
SGLang/vLLM paged decode kernels play behind the reference's generation
server, reference: realhf/impl/model/backend/sglang.py:369 + SURVEY §2.8
"splash/paged attention kernels").  KV lives in a shared pool of
fixed-size blocks, PAGE-major ``[NB, Hkv, BS, hd]`` (one page = one
contiguous HBM extent); each batch row owns an ordered list of pool
block ids (its *block table*), so cache capacity is allocated in
BS-token pages instead of dense ``max_len`` rows — the difference
between a handful of 32k rows fitting one chip and dozens.

Kernel shape:

* grid ``(B, QB, ceil(MB/G))`` — MB is the static per-row block
  capacity, G pages stream per step (PAGE_GROUP), QB tiles the query
  axis so VMEM scratch stays bounded at prefill-chunk shapes; the minor
  axis iterates sequentially on TPU so online-softmax state (m/l/acc)
  lives in VMEM scratch across blocks;
* the K/V index maps ride TWO scalar-prefetch operands: ``lengths``
  clamps the block index to each row's last valid block (trailing grid
  steps re-address the same tile and the pipeline skips their HBM->VMEM
  copies — short rows stream only the KV they own), and ``tables``
  translates the clamped logical block index into a pool block id;
* queries are GQA-grouped AND chunk-grouped: ``q`` carries Q query
  tokens per row (Q=1 for decode; Q=chunk for chunked prefill's
  prefix attention) and every query row of a (b, qb) cell shares one
  streamed KV page — all KV heads of a page ride one contiguous DMA.

Returns UN-normalized partials ``(acc, m, l)`` so the caller online-merges
them with attention over KV not in the pool yet (the decode chunk's
in-flight window, or a prefill chunk's causal self-attention).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from areal_tpu.ops.decode_attention import (
    softmax_block_update,
    softmax_emit,
    softmax_scratch_init,
)

DEFAULT_BLOCK = 256
_NEG_INF = -1e30


#: logical pages streamed per grid step.  The kernel is DMA-LATENCY-bound
#: at one small page per step (~1us fixed cost per HBM->VMEM copy caps it
#: at ~200 GB/s on v5e); issuing G page copies per step overlaps their
#: latencies.  Measured on v5e at 8k ctx (1.5B arch, B=16, 256-token
#: pages): G=1 0.70x of the dense-einsum path, G=4 0.78x, and G=4 with
#: 1024-token pages 0.93x — G=8 regresses (0.83x), so 4 it is.
PAGE_GROUP = 4


#: cap on query rows (Q*r) per grid cell, so prefill-chunk shapes (Q up
#: to prefill_chunk_tokens) tile the query axis; :func:`_plan_tiles`
#: lowers it further when the shapes need more VMEM than the budget
MAX_Q_ROWS = 512

#: scoped-VMEM limit stated to Mosaic for both paged kernels.  The
#: compiler's default scope is 16 MiB, which four double-buffered
#: 1024-token pages of 4 bf16 KV heads (Qwen2.5-7B: 16 MiB of page
#: buffers alone) already exceed; v5e/v6e have 128 MiB of VMEM per core
#: and v5p 96 MiB, so 48 MiB is safe on each.
VMEM_LIMIT_BYTES = 48 << 20

#: what :func:`vmem_bytes_needed` may reach: the limit less a margin for
#: what the estimate cannot see (Mosaic's own spills and relayouts)
VMEM_BUDGET_BYTES = 40 << 20


def vmem_bytes_needed(
    Hkv: int, BS: int, hd: int, kv_itemsize: int, quantized: bool,
    page_group: int, q_rows: int,
) -> int:
    """VMEM one grid cell of :func:`paged_flash_attention` needs, from its
    shapes: double-buffered page/scale/q/output tiles, the f32 scratch,
    and the per-head temporaries of :func:`softmax_block_update` (scores
    and probabilities [q_rows, BS], the page's f32 copies, and the bf16
    splits HIGHEST precision makes of each dot operand)."""
    page = Hkv * BS * hd * kv_itemsize
    pages = 2 * page_group * 2 * page  # k+v, G streams, double-buffered
    if quantized:
        pages += 2 * page_group * 2 * Hkv * BS * 4  # scale tiles
        pages += 2 * Hkv * BS * hd * 4  # the dequantized page, f32
    q_tile = 2 * Hkv * q_rows * hd * 2
    state = Hkv * q_rows * (hd + 256) * 4  # acc + m + l
    outs_and_scratch = 3 * state  # double-buffered outs + scratch
    temps = 3 * q_rows * BS * 4 + 4 * BS * hd * 4 + 2 * q_rows * hd * 4
    return pages + q_tile + outs_and_scratch + temps


def _plan_tiles(
    Q: int, r: int, Hkv: int, BS: int, hd: int, kv_itemsize: int,
    quantized: bool, MB: int,
) -> Tuple[int, int]:
    """(page_group G, query tokens per cell QT) for these shapes: start
    from PAGE_GROUP pages and MAX_Q_ROWS rows and give up query rows,
    then pages, until :func:`vmem_bytes_needed` fits the budget.  Raises
    when even one page and one sublane tile of queries do not fit —
    the caller must not quietly take another path."""
    # QT*r must be a multiple of the 8-row sublane tile unless one cell
    # holds the whole (short) query axis
    step = 8 // np.gcd(8, r)
    G = max(1, min(PAGE_GROUP, MB))
    while True:
        QT = min(Q, MAX_Q_ROWS // r)
        if QT < Q:
            QT = max(step, QT // step * step)
        while True:
            if (
                vmem_bytes_needed(
                    Hkv, BS, hd, kv_itemsize, quantized, G, QT * r
                )
                <= VMEM_BUDGET_BYTES
            ):
                return G, QT
            if QT <= step:
                break
            QT = max(step, (QT // 2) // step * step)
        if G == 1:
            raise ValueError(
                "paged_flash_attention: one page of "
                f"[Hkv={Hkv}, page={BS}, head_dim={hd}] x {kv_itemsize} B "
                f"with {step * r} query rows needs "
                f"{vmem_bytes_needed(Hkv, BS, hd, kv_itemsize, quantized, 1, step * r)} "
                f"bytes of VMEM, over the {VMEM_BUDGET_BYTES}-byte budget; "
                "use a smaller page_size or shard kv heads over more chips"
            )
        G //= 2


def _kernel(
    lengths_ref,  # scalar prefetch [B]
    tables_ref,  # scalar prefetch [B, MB]
    layer_ref,  # scalar prefetch [1] (0 when the pool is per-layer)
    q_ref,  # (1, 1, Hkv, QR, hd)
    *refs,  # G k-page refs, G v-page refs, [2G scale refs], 3 outs, 3 scratch
    block_size: int,
    scale: float,
    n_kv_heads: int,
    page_group: int,
    quantized: bool = False,
):
    G = page_group
    k_refs = refs[:G]
    v_refs = refs[G : 2 * G]
    base_idx = 2 * G
    ks_refs = vs_refs = ()
    if quantized:
        ks_refs = refs[2 * G : 3 * G]
        vs_refs = refs[3 * G : 4 * G]
        base_idx = 4 * G
    acc_ref, m_ref, l_ref = refs[base_idx : base_idx + 3]
    s_acc, s_m, s_l = refs[base_idx + 3 :]
    b = pl.program_id(0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        softmax_scratch_init(s_acc, s_m, s_l)

    length = lengths_ref[b]
    hd = k_refs[0].shape[-1]
    for g in range(G):
        base = (j * G + g) * block_size

        @pl.when(base < length)
        def _block(g=g, base=base):
            # each page tile is one CONTIGUOUS (Hkv, BS, hd) copy; all
            # KV heads ride it together
            k_all = k_refs[g][...].reshape(n_kv_heads, block_size, hd)
            v_all = v_refs[g][...].reshape(n_kv_heads, block_size, hd)
            if quantized:
                # in-kernel dequant: multiply the int8 page by its
                # per-(head, slot) scales right after the gather, so the
                # attention dots below run in f32 like the fp path
                ks = ks_refs[g][...].reshape(n_kv_heads, block_size)
                vs = vs_refs[g][...].reshape(n_kv_heads, block_size)
                k_all = k_all.astype(jnp.float32) * ks[:, :, None]
                v_all = v_all.astype(jnp.float32) * vs[:, :, None]
            for h in range(n_kv_heads):
                softmax_block_update(
                    q_ref[0, 0, h], k_all[h], v_all[h],
                    s_acc.at[h], s_m.at[h], s_l.at[h],
                    base=base, length=length, scale=scale,
                )

    @pl.when(j == nb - 1)
    def _emit():
        acc_ref[0, 0] = s_acc[...]
        m_ref[0, 0] = s_m[...]
        l_ref[0, 0] = s_l[...]


def _paged_kv_map(b, qb, j, lengths_ref, tables_ref, layer_ref, *,
                  block_size, layered, group, offset):
    # page ``j * group + offset``, clamped to the last LOGICAL block
    # holding valid KV for row b (trailing steps re-address that tile and
    # the pipeline skips their copies), then translated through the row's
    # block table into a pool block id
    last = jnp.maximum(
        (lengths_ref[b] + block_size - 1) // block_size - 1, 0
    )
    pid = tables_ref[b, jnp.minimum(j * group + offset, last)]
    if layered:
        return (layer_ref[0], pid, 0, 0, 0)
    return (pid, 0, 0, 0)



def _group_queries(q, Hkv, r, QT):
    """Pad + regroup [B, Q, Hq, hd] queries into per-(kv-head) row tiles
    [B, QB, Hkv, QT*r, hd] of ``QT`` query tokens each; returns
    (qg, QB)."""
    B, Q, Hq, hd = q.shape
    QB = -(-Q // QT)
    Qp = QB * QT
    q_pad = (
        jnp.pad(q, ((0, 0), (0, Qp - Q), (0, 0), (0, 0)))
        if Qp != Q
        else q
    )
    qg = (
        q_pad.reshape(B, QB, QT, Hkv, r, hd)
        .transpose(0, 1, 3, 2, 4, 5)
        .reshape(B, QB, Hkv, QT * r, hd)
    )
    return qg, QB


def _ungroup_outputs(acc, m, l, B, QB, QT, Hkv, r, Q, Hq, hd):
    """Invert :func:`_group_queries` on the kernel's (acc, m, l)."""

    def unravel(x, lanes):
        return (
            x.reshape(B, QB, Hkv, QT, r, lanes)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(B, QB * QT, Hq, lanes)[:, :Q]
        )

    return (
        unravel(acc, hd),
        unravel(m, 128)[..., 0],
        unravel(l, 128)[..., 0],
    )


def _layer_scalar(layer):
    return (
        jnp.zeros((1,), jnp.int32)
        if layer is None
        else jnp.asarray(layer, jnp.int32).reshape(1)
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_attention(
    q: jax.Array,  # [B, Q, Hq, hd]
    k_pool: jax.Array,  # [NB, Hkv, BS, hd] or [L, NB, Hkv, BS, hd]
    v_pool: jax.Array,
    tables: jax.Array,  # [B, MB] int32 — pool block id per logical block
    lengths: jax.Array,  # [B] int32 — valid cache prefix per row
    layer: jax.Array | None = None,  # [] or [1] int32, for stacked pools
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # [(L,) NB, Hkv, BS] int8-pool scales
    v_scale: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Un-normalized online-softmax attention partials over paged KV.

    Every query token attends the FULL prefix ``[0, length)`` of its row
    (decode queries by definition; prefill-chunk queries because the
    prefix precedes the whole chunk — in-chunk causality is the caller's
    self-attention term).  Returns ``(acc [B,Q,Hq,hd] f32, m [B,Q,Hq],
    l [B,Q,Hq])``; rows with ``length == 0`` return ``acc=0, l=0, m=-inf``.

    Pool layout is PAGE-major ``[NB, Hkv, BS, hd]`` so one page's tile is
    one contiguous (Hkv, BS, hd) HBM read, and the grid streams
    ``PAGE_GROUP`` pages per step (their DMAs overlap — see PAGE_GROUP).

    A 5-D ``k_pool``/``v_pool`` is the FULL layer-stacked pool; ``layer``
    (traced scalar) selects the layer inside the kernel's index map, so a
    layer scan never materializes a per-layer pool slice (that slice is
    pool_bytes/L of pure copy traffic per layer — the whole pool per
    forward).

    ``k_scale``/``v_scale`` mark an int8-quantized pool: each page's
    scale tile streams beside its KV tile through the same index map and
    the kernel dequantizes in VMEM right after the gather (the
    storage-only quantization contract).
    """
    B, Q, Hq, hd = q.shape
    layered = k_pool.ndim == 5
    NB, Hkv, BS, _ = k_pool.shape[-4:]
    MB = tables.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    if layered:
        assert layer is not None, "layer index required for a stacked pool"
    r = Hq // Hkv
    quantized = k_scale is not None
    # tile the query axis (QT tokens per grid cell, QT*r rows of scratch)
    # and pick the page group from the VMEM these shapes need
    G, QT = _plan_tiles(
        Q, r, Hkv, BS, hd, jnp.dtype(k_pool.dtype).itemsize, quantized, MB
    )
    qg, QB = _group_queries(q, Hkv, r, QT)
    layer_arr = _layer_scalar(layer)

    grid = (B, QB, -(-MB // G))
    kv_block = (1, 1, Hkv, BS, hd) if layered else (1, Hkv, BS, hd)
    kv_specs = [
        pl.BlockSpec(
            kv_block,
            functools.partial(
                _paged_kv_map,
                block_size=BS,
                layered=layered,
                group=G,
                offset=g,
            ),
        )
        for g in range(G)
    ]
    # int8 pools: each page's scale tile (one f32 per head x slot) rides
    # the same clamped index map as its KV tile
    scale_block = (1, 1, Hkv, BS) if layered else (1, Hkv, BS)
    scale_specs = [
        pl.BlockSpec(
            scale_block,
            functools.partial(
                _paged_scale_map,
                block_size=BS,
                layered=layered,
                group=G,
                offset=g,
            ),
        )
        for g in range(G)
    ]
    acc, m, l = pl.pallas_call(
        functools.partial(
            _kernel,
            block_size=BS,
            scale=1.0 / np.sqrt(hd),
            n_kv_heads=Hkv,
            page_group=G,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=(
                [
                    pl.BlockSpec(
                        (1, 1, Hkv, QT * r, hd),
                        lambda b, qb, j, L, T, Y: (b, qb, 0, 0, 0),
                    )
                ]
                + kv_specs  # G k-page streams
                + kv_specs  # G v-page streams (same maps, v operands)
                + (scale_specs + scale_specs if quantized else [])
            ),
            out_specs=[
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, hd),
                    lambda b, qb, j, L, T, Y: (b, qb, 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, 128),
                    lambda b, qb, j, L, T, Y: (b, qb, 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, 128),
                    lambda b, qb, j, L, T, Y: (b, qb, 0, 0, 0),
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((Hkv, QT * r, hd), jnp.float32),
                pltpu.VMEM((Hkv, QT * r, 128), jnp.float32),
                pltpu.VMEM((Hkv, QT * r, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        # the trace names the Mosaic call after this: one query row a
        # sequence is a decode step, a query tile a prefill chunk
        name="paged_attn_decode" if Q == 1 else "paged_attn_fill",
    )(
        lengths.astype(jnp.int32),
        tables.astype(jnp.int32),
        layer_arr,
        qg,
        *([k_pool] * G),
        *([v_pool] * G),
        *(([k_scale] * G + [v_scale] * G) if quantized else []),
    )

    return _ungroup_outputs(acc, m, l, B, QB, QT, Hkv, r, Q, Hq, hd)


def _paged_scale_map(b, qb, j, lengths_ref, tables_ref, layer_ref, *,
                     block_size, layered, group, offset):
    """Scale-pool twin of :func:`_paged_kv_map` (one fewer trailing dim)."""
    last = jnp.maximum(
        (lengths_ref[b] + block_size - 1) // block_size - 1, 0
    )
    pid = tables_ref[b, jnp.minimum(j * group + offset, last)]
    if layered:
        return (layer_ref[0], pid, 0, 0)
    return (pid, 0, 0)


#: in-flight page DMAs of the deep-pipelined kernel (see
#: paged_flash_attention_deep); 8 x ~0.5 MB tiles keep the HBM stream
#: saturated where the BlockSpec pipeline's 1-deep lookahead cannot
DEEP_BUFFERS = 8


def _deep_kernel(
    lengths_ref,  # scalar prefetch [B]
    tables_ref,  # scalar prefetch [B, MB]
    layer_ref,  # scalar prefetch [1]
    q_ref,  # (1, 1, Hkv, QR, hd) VMEM
    *refs,  # k_hbm, v_hbm, [ks_hbm, vs_hbm], 3 outs, bufs, scratch, sems
    block_size: int,
    scale: float,
    n_kv_heads: int,
    layered: bool,
    max_blocks: int,
    n_buffers: int,
    quantized: bool = False,
):
    if quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, acc_ref, m_ref, l_ref,
         kbuf, vbuf, ksbuf, vsbuf, s_acc, s_m, s_l,
         k_sems, v_sems, ks_sems, vs_sems) = refs
    else:
        (k_hbm, v_hbm, acc_ref, m_ref, l_ref, kbuf, vbuf,
         s_acc, s_m, s_l, k_sems, v_sems) = refs
    NBUF = n_buffers
    b = pl.program_id(0)
    length = lengths_ref[b]
    n_blocks = jnp.minimum(
        jnp.maximum((length + block_size - 1) // block_size, 0), max_blocks
    )
    lay = layer_ref[0]

    softmax_scratch_init(s_acc, s_m, s_l)

    def src(j):
        pid = tables_ref[b, jnp.minimum(j, max_blocks - 1)]
        if layered:
            return lambda r: r.at[lay, pid]
        return lambda r: r.at[pid]

    def dma_group(j, slot):
        sel = src(j)
        copies = [
            pltpu.make_async_copy(sel(k_hbm), kbuf.at[slot], k_sems.at[slot]),
            pltpu.make_async_copy(sel(v_hbm), vbuf.at[slot], v_sems.at[slot]),
        ]
        if quantized:
            # the page's scale tiles ride the same DMA ring slot — the
            # in-kernel-dequant half of the int8 storage format
            copies.append(
                pltpu.make_async_copy(
                    sel(ks_hbm), ksbuf.at[slot], ks_sems.at[slot]
                )
            )
            copies.append(
                pltpu.make_async_copy(
                    sel(vs_hbm), vsbuf.at[slot], vs_sems.at[slot]
                )
            )
        return copies

    # warm-up: fill the buffer ring
    def warm(j, _):
        @pl.when(j < n_blocks)
        def _():
            for c in dma_group(j, j % NBUF):
                c.start()
        return 0

    jax.lax.fori_loop(0, NBUF, warm, 0)

    def body(j, _):
        slot = j % NBUF
        for c in dma_group(j, slot):
            c.wait()
        k_all = kbuf[slot]
        v_all = vbuf[slot]
        if quantized:
            k_all = k_all.astype(jnp.float32) * ksbuf[slot][:, :, None]
            v_all = v_all.astype(jnp.float32) * vsbuf[slot][:, :, None]
        for h in range(n_kv_heads):
            softmax_block_update(
                q_ref[0, 0, h], k_all[h], v_all[h],
                s_acc.at[h], s_m.at[h], s_l.at[h],
                base=j * block_size, length=length, scale=scale,
            )
        # refill this slot with the page NBUF ahead
        nxt = j + NBUF

        @pl.when(nxt < n_blocks)
        def _():
            for c in dma_group(nxt, slot):
                c.start()
        return 0

    jax.lax.fori_loop(0, n_blocks, body, 0)

    acc_ref[0, 0] = s_acc[...]
    m_ref[0, 0] = s_m[...]
    l_ref[0, 0] = s_l[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_attention_deep(
    q: jax.Array,  # [B, Q, Hq, hd]
    k_pool: jax.Array,  # [NB, Hkv, BS, hd] or [L, NB, Hkv, BS, hd]
    v_pool: jax.Array,
    tables: jax.Array,  # [B, MB]
    lengths: jax.Array,  # [B]
    layer: jax.Array | None = None,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # [(L,) NB, Hkv, BS] int8-pool scales
    v_scale: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Deep-pipelined variant of :func:`paged_flash_attention`: the pool
    stays in HBM and the kernel issues its own page DMAs with a
    ``DEEP_BUFFERS``-deep ring, so up to 8 page copies are in flight —
    the BlockSpec pipeline's single-step lookahead is what caps the
    default kernel at ~350 GB/s on v5e (DMA-latency-bound).  Same
    (acc, m, l) contract; rows stream only their valid pages.

    EXPERIMENTAL: numerics are parity-tested (interpret mode + TPU), but
    until it is measured FASTER on hardware the engine keeps the default
    kernel (bench.py's decode A/B reports both).
    """
    B, Q, Hq, hd = q.shape
    layered = k_pool.ndim == 5
    NB, Hkv, BS, _ = k_pool.shape[-4:]
    MB = tables.shape[1]
    assert Hq % Hkv == 0
    if layered:
        assert layer is not None
    r = Hq // Hkv
    quantized = k_scale is not None
    # same VMEM plan as the default kernel: its G double-buffered page
    # streams cost what a ring of 2G pages costs here
    G, QT = _plan_tiles(
        Q, r, Hkv, BS, hd, jnp.dtype(k_pool.dtype).itemsize, quantized,
        DEEP_BUFFERS // 2,
    )
    nbuf = 2 * G
    qg, QB = _group_queries(q, Hkv, r, QT)
    layer_arr = _layer_scalar(layer)
    grid = (B, QB)
    scratch = [
        pltpu.VMEM((nbuf, Hkv, BS, hd), k_pool.dtype),
        pltpu.VMEM((nbuf, Hkv, BS, hd), v_pool.dtype),
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((nbuf, Hkv, BS), jnp.float32),
            pltpu.VMEM((nbuf, Hkv, BS), jnp.float32),
        ]
    scratch += [
        pltpu.VMEM((Hkv, QT * r, hd), jnp.float32),
        pltpu.VMEM((Hkv, QT * r, 128), jnp.float32),
        pltpu.VMEM((Hkv, QT * r, 128), jnp.float32),
    ]
    scratch += [pltpu.SemaphoreType.DMA((nbuf,))] * (4 if quantized else 2)
    acc, m, l = pl.pallas_call(
        functools.partial(
            _deep_kernel,
            block_size=BS,
            scale=1.0 / np.sqrt(hd),
            n_kv_heads=Hkv,
            layered=layered,
            max_blocks=MB,
            n_buffers=nbuf,
            quantized=quantized,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, hd),
                    lambda b, qb, L, T, Y: (b, qb, 0, 0, 0),
                ),
            ]
            + [pl.BlockSpec(memory_space=pl.ANY)]
            * (4 if quantized else 2),
            out_specs=[
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, hd),
                    lambda b, qb, L, T, Y: (b, qb, 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, 128),
                    lambda b, qb, L, T, Y: (b, qb, 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, 128),
                    lambda b, qb, L, T, Y: (b, qb, 0, 0, 0),
                ),
            ],
            scratch_shapes=scratch,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="paged_attn_deep",
    )(
        lengths.astype(jnp.int32),
        tables.astype(jnp.int32),
        layer_arr,
        qg,
        k_pool,
        v_pool,
        *((k_scale, v_scale) if quantized else ()),
    )

    return _ungroup_outputs(acc, m, l, B, QB, QT, Hkv, r, Q, Hq, hd)


def gather_paged_kv(
    k_pool: jax.Array,  # [NB, Hkv, BS, hd] (or [L, NB, Hkv, BS, hd])
    v_pool: jax.Array,
    tables: jax.Array,  # [B, MB]
) -> Tuple[jax.Array, jax.Array]:
    """Materialize per-row dense KV ``[..., B, Hkv, MB*BS, hd]`` from the
    pool (jnp reference/CPU path; the kernel never does this)."""

    def g(pool):
        gathered = jnp.take(pool, tables, axis=-4)  # [..,B,MB,Hkv,BS,hd]
        gathered = jnp.moveaxis(gathered, -3, -4)  # [..,B,Hkv,MB,BS,hd]
        s = gathered.shape
        return gathered.reshape(*s[:-3], s[-3] * s[-2], s[-1])

    return g(k_pool), g(v_pool)


def reference_paged_partials(
    q, k_pool, v_pool, tables, lengths, k_scale=None, v_scale=None
):
    """jnp reference for :func:`paged_flash_attention` (same contract).

    ``k_scale``/``v_scale`` ([NB, Hkv, BS]) mark an int8 pool: the
    gathered pages are multiplied by their per-(head, slot) scales right
    after the block gather — dequant-on-read, storage-only error."""
    B, Q, Hq, hd = q.shape
    NB, Hkv, BS, _ = k_pool.shape
    r = Hq // Hkv
    k, v = gather_paged_kv(k_pool, v_pool, tables)  # [B,Hkv,S,hd]
    if k_scale is not None:
        ks, vs = gather_paged_kv(
            k_scale[..., None], v_scale[..., None], tables
        )  # [B,Hkv,S,1]
        k = k.astype(jnp.float32) * ks
        v = v.astype(jnp.float32) * vs
    S = k.shape[2]
    qg = q.reshape(B, Q, Hkv, r, hd).astype(jnp.float32)
    s = jnp.einsum(
        "bqkrd,bksd->bqkrs", qg, k.astype(jnp.float32)
    ) / np.sqrt(hd)
    mask = (
        jnp.arange(S)[None, None, None, None, :]
        < lengths[:, None, None, None, None]
    )
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bqkrs,bksd->bqkrd", p, v.astype(jnp.float32))
    return (
        acc.reshape(B, Q, Hq, hd),
        m.reshape(B, Q, Hq),
        l.reshape(B, Q, Hq),
    )
