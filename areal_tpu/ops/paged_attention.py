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
* each of the G page streams is an OPERAND of its own, and the pipeline
  skips an operand's HBM->VMEM copy only when its block index is the one
  of the grid step before.  So the K/V index maps read a scalar-prefetch
  table of page ids made outside the kernel (:func:`stream_page_ids`):
  a stream's own page where that page holds valid KV, and otherwise the
  page the stream fetched last.  Each valid page is copied once; dead
  rows and the pages past a short row's length start no copy.  (Clamping
  a stream to its row's LAST valid page instead re-reads that page once
  a stream and row: 256 MiB a call for 69 MiB of valid pages at 64 slots
  x 4 pages, PERF.md section 6);
* the grid visits the rows in falling order of their page counts
  (:func:`visit_order`; q and the outputs are addressed through it), so
  that the copies of the next row overlap the dots of this one;
* queries are GQA-grouped AND chunk-grouped: ``q`` carries Q query
  tokens per row (Q=1 for decode; Q=chunk for chunked prefill's
  prefix attention) and every query row of a (b, qb) cell shares one
  streamed KV page — all KV heads of a page ride one contiguous DMA.

Returns UN-normalized partials ``(acc, m, l)`` so the caller online-merges
them with attention over KV not in the pool yet (the decode chunk's
in-flight window, or a prefill chunk's causal self-attention).

**Latent pages** (``v_pool=None``, ``value_dim``): a pool of ONE "head"
whose page ``[BS, kv_latent_dim]`` holds each token's ``[c_kv | k_rope]``
(latent attention in its absorbed form).  The page is fetched ONCE and
serves as the keys (all ``kv_latent_dim`` columns) and as the values
(its first ``value_dim`` columns), so the kernel has G operand streams
where the K/V form has 2 G, its accumulator is ``value_dim`` wide, and
every query head shares the one stream (``r = Hq``).  It is the same
kernel, grid, page plan and softmax update; the K/V form's programs are
what they were (the mode is a static branch), and the Mosaic call is
named ``paged_mla_decode`` / ``paged_mla_fill``.  Why one kernel and not
two: everything that was hard to get right here (which page a stream
addresses when it has nothing to fetch, the visiting order, the bf16
routes of the two dots) is the latent form's too, and a second kernel
would have had to copy it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from areal_tpu.ops.decode_attention import (
    softmax_block_update,
    softmax_scratch_init,
)

_NEG_INF = -1e30


#: logical pages streamed per grid step, each as an operand stream of its
#: own.  A row of up to G pages is one grid step: its 2 G tile copies are
#: in flight together while the row before it computes.  G was chosen
#: against the dense-einsum path on v5e at 8k context (1.5B heads, B=16):
#: G=1 0.70x and G=4 0.78x with 256-token pages, G=4 0.93x and G=8 0.83x
#: with 1024-token pages.  What G overlaps is whole rows' copies with
#: whole rows' dots, not copy latencies: with 1024-token pages and every
#: copy useful the pipeline reaches 733 GB/s of 819 (PERF.md, PR 25).
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
    lengths_ref,  # scalar prefetch [B], in the order the grid visits
    ids_ref,  # scalar prefetch [B, MB']: stream_page_ids, for the maps
    layer_ref,  # scalar prefetch [1] (0 when the pool is per-layer)
    order_ref,  # scalar prefetch [B]: visit_order, for the maps
    q_ref,  # (1, 1, Hkv, QR, hd)
    *refs,  # G k-page refs, G v-page refs, [2G scale refs], 3 outs, 3 scratch
    block_size: int,
    scale: float,
    n_kv_heads: int,
    page_group: int,
    quantized: bool = False,
    value_dim: Optional[int] = None,  # latent pages: no v-page refs
):
    G = page_group
    k_refs = refs[:G]
    v_refs = refs[G : 2 * G] if value_dim is None else None
    base_idx = 2 * G if value_dim is None else G
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
            if value_dim is None:
                v_all = v_refs[g][...].reshape(n_kv_heads, block_size, hd)
            else:  # the values are the page's first columns
                v_all = k_all[:, :, :value_dim]
            if quantized:
                # in-kernel dequant: multiply the int8 page by its
                # per-(head, slot) scales right after the gather; the
                # dots below then take float32 operands (HIGHEST)
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


def stream_page_ids(tables, lengths, block_size: int, group: int):
    """Pool page id that stream ``g`` of the kernel addresses at grid
    step ``(b, ., j)``, as ``[B, ceil(MB/group) * group]`` int32 indexed
    ``[b, j * group + g]``: the row's own page where that page holds
    valid KV (``(j * group + g) * block_size < lengths[b]``), and
    otherwise the page the stream fetched LAST, in the order the grid
    visits its steps (a forward fill of the valid ids down each of the
    ``group`` columns).

    The BlockSpec pipeline skips an operand's HBM->VMEM copy when its
    block index is the one of the step before, so a stream that has
    nothing to fetch must repeat ITS OWN last index.  Every row visits
    the same ``j`` sequence under each ``qb``, and a column with no
    valid page in a row is constant over that row, so the fill over
    ``(b, j)`` holds for any number of query tiles.  Rows that share a
    page (siblings of one prompt) in consecutive slots skip its copy
    too.
    """
    B, MB = tables.shape
    n_j = -(-MB // group)
    width = n_j * group
    tables = tables.astype(jnp.int32)
    if width != MB:
        tables = jnp.pad(tables, ((0, 0), (0, width - MB)))
    page = jnp.arange(width, dtype=jnp.int32)
    valid = page[None, :] * block_size < lengths.astype(jnp.int32)[:, None]
    # flatten (b, j) into the order the grid visits: one line a step,
    # one column a stream
    step = jnp.arange(B * n_j, dtype=jnp.int32)[:, None]
    last_valid_step = jax.lax.cummax(
        jnp.where(valid.reshape(B * n_j, group), step, 0), axis=0
    )
    ids = jnp.take_along_axis(
        tables.reshape(B * n_j, group), last_valid_step, axis=0
    )
    return ids.reshape(B, width)


def visit_order(lengths, block_size: int):
    """The batch row each grid step ``b`` works on: rows in falling order
    of the valid pages they hold, dead rows last.  The pipeline fetches
    step ``b + 1``'s pages while step ``b`` computes, one step ahead and
    no further, so a step lasts as long as the LONGER of this row's dots
    and the next row's copies: rows of equal page counts side by side
    keep both busy, and dead rows in a block at the end start no copy
    between two live rows."""
    pages = -(-lengths.astype(jnp.int32) // block_size)
    return jnp.argsort(-pages, stable=True).astype(jnp.int32)


class PagePlan(NamedTuple):
    """What the kernel's index maps read, made from one (tables,
    lengths) pair for one page group."""

    lengths: jax.Array  # [B] valid prefix per row, in visiting order
    page_ids: jax.Array  # [B, MB'] stream_page_ids, in visiting order
    order: jax.Array  # [B] visit_order


def plan_pages(tables, lengths, block_size: int, group: int) -> PagePlan:
    """The plan :func:`paged_flash_attention` makes for itself unless it
    is handed one.  A caller that runs the kernel many times over the
    same rows (every layer of every step of a decode chunk) makes it
    once, with the ``group`` :func:`page_group` names for its shapes:
    XLA does not hoist the sort and the scan out of those loops (about
    9 us a call on a v5e, beside a kernel of 80-120)."""
    order = visit_order(lengths, block_size)
    lengths = lengths.astype(jnp.int32)[order]
    ids = stream_page_ids(tables[order], lengths, block_size, group)
    return PagePlan(lengths, ids, order)


def page_group(
    n_queries: int, n_q_heads: int, pool_shape, kv_dtype, quantized: bool,
    max_blocks: int,
) -> int:
    """Pages a grid step streams for a call of these shapes (``pool_shape``
    as the kernel sees it: one shard's, under a TP mesh)."""
    Hkv, BS, hd = pool_shape[-3:]
    return _plan_tiles(
        n_queries, n_q_heads // Hkv, Hkv, BS, hd,
        jnp.dtype(kv_dtype).itemsize, quantized, max_blocks,
    )[0]


def _row_map(b, qb, j, lengths_ref, ids_ref, layer_ref, order_ref):
    """Query and output tiles of step (b, qb, .): those of the row this
    step works on."""
    return (order_ref[b], qb, 0, 0, 0)


def _paged_page_map(b, qb, j, lengths_ref, ids_ref, layer_ref, order_ref, *,
                    layered, group, offset, rank):
    """Block index (of ``rank`` axes: a KV page's or its int8 scales') of
    stream ``offset`` at step (b, qb, j): the page :func:`stream_page_ids`
    assigned it, whole."""
    pid = ids_ref[b, j * group + offset]
    head = (layer_ref[0], pid) if layered else (pid,)
    return head + (0,) * (rank - len(head))


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


@functools.partial(
    jax.jit, static_argnames=("interpret", "scale", "value_dim")
)
def paged_flash_attention(
    q: jax.Array,  # [B, Q, Hq, hd]
    k_pool: jax.Array,  # [NB, Hkv, BS, hd] or [L, NB, Hkv, BS, hd]
    v_pool: Optional[jax.Array],  # None: latent pages (see ``value_dim``)
    tables: jax.Array,  # [B, MB] int32 — pool block id per logical block
    lengths: jax.Array,  # [B] int32 — valid cache prefix per row
    layer: jax.Array | None = None,  # [] or [1] int32, for stacked pools
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # [(L,) NB, Hkv, BS] int8-pool scales
    v_scale: jax.Array | None = None,
    plan: Optional[PagePlan] = None,  # plan_pages(tables, lengths, ...)
    scale: Optional[float] = None,  # softmax scale; None = 1/sqrt(hd)
    value_dim: Optional[int] = None,  # latent pages: values = k[..., :value_dim]
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

    ``plan``: what :func:`plan_pages` made of these ``tables`` and
    ``lengths`` (which are then not read), for a caller that makes many
    calls over the same rows.

    ``v_pool=None`` with ``value_dim``: latent pages (module docstring);
    ``acc`` is then ``[B, Q, Hq, value_dim]``.
    """
    B, Q, Hq, hd = q.shape
    latent = v_pool is None
    assert latent == (value_dim is not None), (latent, value_dim)
    vd = value_dim if latent else hd
    layered = k_pool.ndim == 5
    NB, Hkv, BS, _ = k_pool.shape[-4:]
    MB = tables.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    if layered:
        assert layer is not None, "layer index required for a stacked pool"
    r = Hq // Hkv
    quantized = k_scale is not None
    assert not (latent and quantized), "int8 latent pages are not written"
    # tile the query axis (QT tokens per grid cell, QT*r rows of scratch)
    # and pick the page group from the VMEM these shapes need
    G, QT = _plan_tiles(
        Q, r, Hkv, BS, hd, jnp.dtype(k_pool.dtype).itemsize, quantized, MB
    )
    qg, QB = _group_queries(q, Hkv, r, QT)
    layer_arr = _layer_scalar(layer)
    grid = (B, QB, -(-MB // G))
    # lengths and page ids in the order the grid visits the rows; q and
    # the outputs stay where they are and are addressed through the order
    if plan is None:
        plan = plan_pages(tables, lengths, BS, G)
    assert plan.page_ids.shape == (B, grid[2] * G), (
        plan.page_ids.shape, B, MB, G,
    )
    kv_block = (1, 1, Hkv, BS, hd) if layered else (1, Hkv, BS, hd)
    # int8 pools: each page's scale tile (one f32 per head x slot) rides
    # the same page ids as its KV tile
    scale_block = (1, 1, Hkv, BS) if layered else (1, Hkv, BS)

    def page_specs(block):
        return [
            pl.BlockSpec(
                block,
                functools.partial(
                    _paged_page_map, layered=layered, group=G, offset=g,
                    rank=len(block),
                ),
            )
            for g in range(G)
        ]

    kv_specs = page_specs(kv_block)
    scale_specs = page_specs(scale_block) if quantized else []
    acc, m, l = pl.pallas_call(
        functools.partial(
            _kernel,
            block_size=BS,
            scale=1.0 / np.sqrt(hd) if scale is None else scale,
            n_kv_heads=Hkv,
            page_group=G,
            quantized=quantized,
            value_dim=value_dim,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=(
                [
                    pl.BlockSpec(
                        (1, 1, Hkv, QT * r, hd),
                        _row_map,
                    )
                ]
                + kv_specs  # G k-page streams
                # G v-page streams (same maps, v operands); none for
                # latent pages, whose values ride the k-page stream
                + ([] if latent else kv_specs)
                + scale_specs  # int8 pools: G k-scale streams,
                + scale_specs  # G v-scale streams
            ),
            out_specs=[
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, vd),
                    _row_map,
                ),
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, 128),
                    _row_map,
                ),
                pl.BlockSpec(
                    (1, 1, Hkv, QT * r, 128),
                    _row_map,
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((Hkv, QT * r, vd), jnp.float32),
                pltpu.VMEM((Hkv, QT * r, 128), jnp.float32),
                pltpu.VMEM((Hkv, QT * r, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, vd), jnp.float32),
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
        name=("paged_mla_" if latent else "paged_attn_")
        + ("decode" if Q == 1 else "fill"),
    )(
        plan.lengths,
        plan.page_ids,
        layer_arr,
        plan.order,
        qg,
        *([k_pool] * G),
        *([] if latent else [v_pool] * G),
        *(([k_scale] * G + [v_scale] * G) if quantized else []),
    )

    return _ungroup_outputs(acc, m, l, B, QB, QT, Hkv, r, Q, Hq, vd)


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
    q, k_pool, v_pool, tables, lengths, k_scale=None, v_scale=None,
    scale=None, value_dim=None,
):
    """jnp reference for :func:`paged_flash_attention` (same contract;
    ``v_pool=None`` with ``value_dim``: latent pages).

    ``k_scale``/``v_scale`` ([NB, Hkv, BS]) mark an int8 pool: the
    gathered pages are multiplied by their per-(head, slot) scales right
    after the block gather — dequant-on-read, storage-only error."""
    B, Q, Hq, hd = q.shape
    NB, Hkv, BS, _ = k_pool.shape
    r = Hq // Hkv
    if v_pool is None:
        k, _ = gather_paged_kv(k_pool, k_pool, tables)
        v = k[..., :value_dim]
    else:
        k, v = gather_paged_kv(k_pool, v_pool, tables)  # [B,Hkv,S,hd]
    if k_scale is not None:
        ks, vs = gather_paged_kv(
            k_scale[..., None], v_scale[..., None], tables
        )  # [B,Hkv,S,1]
        k = k.astype(jnp.float32) * ks
        v = v.astype(jnp.float32) * vs
    S = k.shape[2]
    qg = q.reshape(B, Q, Hkv, r, hd).astype(jnp.float32)
    s = jnp.einsum("bqkrd,bksd->bqkrs", qg, k.astype(jnp.float32))
    s = s / np.sqrt(hd) if scale is None else s * scale
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
        acc.reshape(B, Q, Hq, v.shape[-1]),
        m.reshape(B, Q, Hq),
        l.reshape(B, Q, Hq),
    )
