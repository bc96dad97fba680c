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

* grid ``(B, QB, ceil(MB/G))`` (a decode call's: ``(live rows, 1,
  ceil(MB/G))``) — MB is the static per-row block
  capacity, G pages stream per step (PAGE_GROUP), QB tiles the query
  axis so VMEM scratch stays bounded at prefill-chunk shapes; the steps
  run in order on one core, so online-softmax state (m/l/acc) lives in
  VMEM scratch across a row's page steps and a copy started at one step
  lands at the next;
* the pools stay in HBM and the kernel copies pages ITSELF: each of the
  G streams has two page buffers, and a grid step first queues the NEXT
  step's copies (into the buffers this step does not read), then waits
  for its own, then multiplies — the one-step look-ahead of the BlockSpec
  pipeline it replaces, without that pipeline's bookkeeping for pages no
  row holds (eight operands' index maps at every step, dead rows' too:
  half of a call at 22 live rows of 64, PERF.md PR 37);
* a page is copied only AS FAR AS IT IS FILLED, in tiles
  (:func:`_tile_tokens`): one descriptor of ``s`` tiles for each ``s`` a
  page can hold, chosen by a branch on the row's length.  Each valid
  page is copied once; dead rows and the pages past a row's length start
  no copy; a stream that already holds the page as far (a query tile
  that visits the row again, a sibling that shares it) starts none
  either (:func:`stream_page`, :func:`page_fetched`).  It is MULTIPLIED
  whole, in one update a head, with what lies past the row's length
  masked as ever: an update's cost on a v5e is a third of a microsecond
  a head almost whatever its length, so tiles as units of their own
  (operand streams of tiles, or a dot a tile) lost at every shape
  (PERF.md PR 37).  The buffers' value side is zeroed at a call's first
  step, so that what a bounded copy leaves behind it is finite;
* a step's streams are a LOOP (``each_stream``), for their copies and
  for their dots: the kernel is traced, lowered and compiled at a
  quarter of its unrolled size, which is what a program's first call
  pays, in every process (the unrolled form doubled the benchmark's
  warm set-up: PERF.md PR 37);
* the grid visits the rows in falling order of their page counts
  (:func:`visit_order`; q and the outputs are addressed through it), so
  that the copies of the next row overlap the dots of this one and dead
  rows come last, where a step costs one branch; a DECODE call (one query
  a row) does not visit them at all: its grid's first extent is the number
  of rows that hold a page (:class:`LiveRows`, a traced scalar made with
  the plan), and what the unvisited rows return is selected after the
  call (a dead slot cost 0.14 us a layer and step, 6 us of a 58 us call
  at 22 live rows of 64; a traced extent costs what a static one of the
  same size does: PERF.md PR 52);
* queries are GQA-grouped AND chunk-grouped: ``q`` carries Q query
  tokens per row (Q=1 for decode; Q=chunk for chunked prefill's
  prefix attention) and every query row of a (b, qb) cell shares one
  copied KV page — all KV heads of a page ride one copy.

**A window** (``window=W``): every query attends the last ``W - 1``
cached positions before it and not the whole prefix (query ``t`` of a
row of ``length`` cached positions stands at position ``length + t`` and
attends ``j`` with ``length + t - j < W``; what lies inside its own
chunk is the caller's).  The grid visits a row from the page that holds
its first such position (``PagePlan.firsts``): pages wholly before it
are neither copied nor multiplied, whoever still holds them, and the
grid is as long as a window's pages, not a table's.  That first page is
copied whole, like every page but a row's last, and masked by position
(per query row: a fill chunk's later tokens start later).  The Mosaic
call is named ``paged_window_decode`` / ``paged_window_fill``.

Returns UN-normalized partials ``(acc, m, l)`` so the caller online-merges
them with attention over KV not in the pool yet (the decode chunk's
in-flight window, or a prefill chunk's causal self-attention).

**Latent pages** (``v_pool=None``, ``value_dim``): a pool of ONE "head"
whose page ``[BS, kv_latent_dim]`` holds each token's ``[c_kv | k_rope]``
(latent attention in its absorbed form).  The page is fetched ONCE and
serves as the keys (all ``kv_latent_dim`` columns) and as the values
(its first ``value_dim`` columns), so the kernel has G page streams
where the K/V form has 2 G, its accumulator is ``value_dim`` wide, and
every query head shares the one stream (``r = Hq``).  It is the same
kernel, grid, page plan and softmax update; the K/V form's programs are
what they were (the mode is a static branch), and the Mosaic call is
named ``paged_mla_decode`` / ``paged_mla_fill`` (under a window, the two
modes together: ``paged_mla_window_decode`` / ``paged_mla_window_fill``).
Why one kernel and not
two: everything that was hard to get right here (which page a stream
addresses when it has nothing to fetch, the visiting order, the bf16
routes of the two dots) is the latent form's too, and a second kernel
would have had to copy it.

**A selection** (``mask`` ``[B, Q, MB * BS]``): query ``t`` of row ``b``
attends cached position ``s`` iff ``mask[b, t, s]`` (and ``s < length``):
an indexed latent layer's fill, and its decode step over a table short
enough (``ops/sparse_attention.decode_reads_masked``), whose indexer chose
``index_topk`` positions a query (``ops/sparse_attention.chosen_mask``).  One more
operand, one row a query TOKEN: it is laid out ``[B, QB, pages, QT, BS]``
in 32-bit words (:func:`_group_selection`), a grid step's block is its G
pages of the row's query tile, ``(1, 1, G, QT, BS)``, through the pipeline
as ``q``'s is (past a row's last page its last block again, which is not
fetched anew), and a page's ``[QT, BS]`` is repeated down the sublanes
over the ``r`` query heads of each token and joins the length mask in
:func:`softmax_block_update` (``chosen``).  A page's scores of a query
tile, ``[QT * r, BS]`` float32, never leave VMEM; the XLA page loop this
replaced wrote them out and read them back several times a page.  A
static branch on the operand's presence: a call without it is the program
it was.  The Mosaic call is named ``paged_mla_masked_fill``; at ONE query
a row ``paged_mla_masked_decode``: the block is one token's, ``(1, 1, G,
1, BS)``, the grid the live rows' (the selection is addressed through the
visiting order, as ``q`` is), and every cached position of a row is
multiplied whatever the selection keeps: 4.4-4.5 us a 1,024 positions and
live row at 128 heads on a v5e (PERF.md PR 54), which beats a gather of the
chosen rows while the table is short.
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


#: logical pages streamed per grid step, each a stream of its own (two
#: page buffers, K and V).  A row of up to G pages is one grid step: its
#: copies are in flight together while the row before it computes.  G
#: was chosen against the dense-einsum path on v5e at 8k context (1.5B
#: heads, B=16) when the BlockSpec pipeline made the copies: G=1 0.70x
#: and G=4 0.78x with 256-token pages, G=4 0.93x and G=8 0.83x with
#: 1024-token pages; not timed again since the kernel copies for itself
#: (PR 37).  What G overlaps is whole rows' copies with whole rows' dots,
#: not copy latencies: with 1024-token pages and every page full the
#: kernel reaches 734 GB/s of 819 (PERF.md, PR 37; 733 in PR 25).
PAGE_GROUP = 4


#: the lane tile: a page tile is a whole number of these
LANES = 128

#: the unit a page is copied in (:func:`_tile_tokens`): a row's LAST page
#: is copied as far as the last tile that holds a cached position, not
#: whole.  At least this many tokens and at least this many bytes of one
#: pool's page: the kernel holds a copy descriptor a stream for every
#: number of filled tiles, and the scalar core walks their branches at
#: every grid step (kernel alone at tiles of 1024 / 512 / 256 tokens:
#: PERF.md, PR 37).
MIN_TILE_TOKENS = 256
MIN_TILE_BYTES = 256 << 10

#: tiles a page is cut into at most
MAX_PAGE_TILES = 4



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
    page_group: int, q_rows: int, selected_tokens: int = 0,
) -> int:
    """VMEM one grid cell of :func:`paged_flash_attention` needs, from its
    shapes: the two page/scale buffers of each of the G streams,
    double-buffered q/output tiles, the f32 scratch, and the per-head
    temporaries of :func:`softmax_block_update` (scores and
    probabilities [q_rows, BS], the page's f32 copies, and the bf16
    splits HIGHEST precision makes of each dot operand).  A page copied
    as far as it is filled needs the buffers of a whole one.  Under a
    selection (``selected_tokens``: the query tokens of a cell) its
    double-buffered block of G pages, a sublane tile of tokens at least,
    and a page's selection laid out by query row, as words and as a mask."""
    page = Hkv * BS * hd * kv_itemsize
    pages = 2 * page_group * 2 * page  # k+v, G streams, two buffers each
    if quantized:
        pages += 2 * page_group * 2 * Hkv * BS * 4  # scale tiles
        pages += 2 * Hkv * BS * hd * 4  # the dequantized page, f32
    q_tile = 2 * Hkv * q_rows * hd * 2
    state = Hkv * q_rows * (hd + 256) * 4  # acc + m + l
    outs_and_scratch = 3 * state  # double-buffered outs + scratch
    temps = 3 * q_rows * BS * 4 + 4 * BS * hd * 4 + 2 * q_rows * hd * 4
    if selected_tokens:
        temps += 2 * page_group * max(selected_tokens, 8) * BS * 4
        temps += 2 * q_rows * BS * 4
    return pages + q_tile + outs_and_scratch + temps


def _tile_tokens(Hkv: int, BS: int, hd: int, kv_itemsize: int) -> int:
    """Tokens of the unit a page is copied in: the smallest divisor of
    the page that is a whole number of lane tiles, at least
    MIN_TILE_TOKENS, MIN_TILE_BYTES of one pool (all kv heads) and a
    MAX_PAGE_TILES-th of the page; the page itself where it has no such
    divisor."""
    for tile in range(LANES, BS, LANES):
        if (
            BS % tile == 0
            and tile >= MIN_TILE_TOKENS
            and Hkv * tile * hd * kv_itemsize >= MIN_TILE_BYTES
            and tile * MAX_PAGE_TILES >= BS
        ):
            return tile
    return BS


def _plan_tiles(
    Q: int, r: int, Hkv: int, BS: int, hd: int, kv_itemsize: int,
    quantized: bool, MB: int, masked: bool = False,
) -> Tuple[int, int, int]:
    """(page_group G, query tokens per cell QT, tokens a tile) for these
    shapes (``masked``: with a selection's blocks beside them): start
    from PAGE_GROUP pages and MAX_Q_ROWS rows and give up
    query rows, then pages, until :func:`vmem_bytes_needed` fits the
    budget; the tile is :func:`_tile_tokens`'s.  Raises when even one
    page and one sublane tile of queries do not fit — the caller must
    not quietly take another path."""
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
                    Hkv, BS, hd, kv_itemsize, quantized, G, QT * r,
                    QT if masked else 0,
                )
                <= VMEM_BUDGET_BYTES
            ):
                return G, QT, _tile_tokens(Hkv, BS, hd, kv_itemsize)
            if QT <= step:
                break
            QT = max(step, (QT // 2) // step * step)
        if G == 1:
            need = vmem_bytes_needed(
                Hkv, BS, hd, kv_itemsize, quantized, 1, step * r,
                step if masked else 0,
            )
            raise ValueError(
                "paged_flash_attention: one page of "
                f"[Hkv={Hkv}, page={BS}, head_dim={hd}] x {kv_itemsize} B "
                f"with {step * r} query rows needs {need} "
                f"bytes of VMEM, over the {VMEM_BUDGET_BYTES}-byte budget; "
                "use a smaller page_size or shard kv heads over more chips"
            )
        G //= 2


def stream_page(
    lengths, page_ids, b, j, g, group: int, block_size: int, tile: int,
    firsts=None,
):
    """``(pool page id, tiles that hold cached positions)`` of stream ``g``
    at the grid step of row ``b`` (in visiting order) and page step ``j``:
    what the kernel copies there, from a plan's lengths, page ids and
    (under a window) first pages (an id past a row's length is never
    read: it holds no tile)."""
    col = stream_col(b, j, g, group, firsts)
    held = (lengths[b] - col * block_size + tile - 1) // tile
    if firsts is not None:  # a window's last steps may lie past the table
        col = jnp.minimum(col, page_ids.shape[1] - 1)
    return page_ids[b, col], jnp.clip(held, 0, block_size // tile)


def stream_col(b, j, g, group: int, firsts=None):
    """The number, in its row, of the page :func:`stream_page` names."""
    col = j * group + g
    return col if firsts is None else col + firsts[b]


def page_fetched(this, before, no_step_before):
    """Whether a stream starts a copy for a grid step where its
    :func:`stream_page` is ``this``, after a step where it was
    ``before``: it holds cached positions there, and not already the same
    page as far (a query tile that visits the row again, a sibling that
    shares the page)."""
    (pid, n), (pid0, n0) = this, before
    return (n > 0) & (no_step_before | (pid != pid0) | (n > n0))


def _kernel(
    lengths_ref,  # scalar prefetch [B], in the order the grid visits
    ids_ref,  # scalar prefetch [B, MB']: the rows' tables
    layer_ref,  # scalar prefetch [1] (0 when the pool is per-layer); under
    # a window [2]: the layer, and how far the queries stand past ``length``
    order_ref,  # scalar prefetch [B]: visit_order, for the maps
    *refs,  # [firsts (scalar prefetch [B]) under a window,] q (1, 1, Hkv,
    # QR, hd), [the selection (1, 1, G, QT, BS),] pools in HBM, 3 outs,
    # page buffers, 3 scratch, slots, semaphores
    block_size: int,
    tile: int,
    scale: float,
    n_kv_heads: int,
    page_group: int,
    layered: bool,
    quantized: bool = False,
    value_dim: Optional[int] = None,  # latent pages: no v pool
    window: Optional[int] = None,
    q_per_kv: int = 1,  # query heads a kv head: a q tile's row t*r + i
    masked: bool = False,  # a selection rides in behind q
):
    G, S = page_group, block_size // tile
    firsts_ref = selection_ref = None
    if window is not None:
        firsts_ref, refs = refs[0], refs[1:]
    q_ref, refs = refs[0], refs[1:]
    if masked:
        selection_ref, refs = refs[0], refs[1:]
    # the arrays a page is made of: K, V (none for latent pages, whose
    # values ride the K page) and an int8 pool's two scale arrays
    n_arrays = (1 if value_dim is not None else 2) * (2 if quantized else 1)
    pools = refs[:n_arrays]
    acc_ref, m_ref, l_ref = refs[n_arrays : n_arrays + 3]
    bufs = refs[n_arrays + 3 : 2 * n_arrays + 3]
    s_acc, s_m, s_l, slot_ref, coming_ref, landing_ref = refs[
        2 * n_arrays + 3 : 2 * n_arrays + 9
    ]
    sems = refs[2 * n_arrays + 9 :]
    # what the values are made of: V (the K page's first columns for
    # latent pages) and an int8 pool's V scales
    value_bufs = [bufs[0 if value_dim is not None else 1]] + (
        [bufs[3]] if quantized else []
    )
    b, qb, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nB, nQ, nJ = pl.num_programs(0), pl.num_programs(1), pl.num_programs(2)
    first = (b == 0) & (qb == 0) & (j == 0)
    last = (b == nB - 1) & (qb == nQ - 1) & (j == nJ - 1)
    # the grid step after this one (the last step stays where it is)
    row_ends = j == nJ - 1
    b_next = jnp.where(row_ends & (qb == nQ - 1) & (b < nB - 1), b + 1, b)
    j_next = jnp.where(row_ends, 0, j + 1)

    def page(bb, jj, g):
        return stream_page(
            lengths_ref, ids_ref, bb, jj, g, G, block_size, tile, firsts_ref
        )

    def copies(g, slot, pid, tiles):
        """One descriptor an array for the first ``tiles`` tiles of pool
        page ``pid`` into buffer ``slot`` of stream ``g``."""
        n = tiles * tile
        at = (layer_ref[0], pid) if layered else (pid,)
        out = []
        for pool, buf, sem in zip(pools, bufs, sems):
            tail = (slice(None), pl.ds(0, n)) + (slice(None),) * (
                len(pool.shape) - len(at) - 2
            )
            out.append(
                pltpu.make_async_copy(
                    pool.at[at + tail], buf.at[(slot, g) + tail],
                    sem.at[slot, g],
                )
            )
        return out

    def for_tiles(n, fn, also=True):
        """``fn(s)`` for the static ``s`` in 1..S that ``n`` equals."""
        for s in range(1, S + 1):
            pl.when(also & (n == s))(functools.partial(fn, s))

    def each_stream(fn):
        """``fn(g)`` for every stream, as a loop: a stream's copies and
        dots are traced, lowered and held by the core ONCE (a step's
        streams run one after the other either way, each behind its own
        branch)."""
        jax.lax.fori_loop(0, G, lambda g, _: fn(g) or 0, 0)

    def start_copies_for(bb, jj, b_before, j_before, no_step_before):
        """The copies grid step ``(bb, jj)`` reads, each into the buffer
        its stream is NOT reading from, with word of it for that step."""

        def _stream(g):
            pid, n = page(bb, jj, g)
            wanted = page_fetched(
                (pid, n), page(b_before, j_before, g), no_step_before
            )

            def _start(s):
                for c in copies(g, 1 - slot_ref[g], pid, s):
                    c.start()
                coming_ref[g] = s

            for_tiles(n, _start, wanted)

        each_stream(_stream)

    @pl.when(first)
    def _first_step_fetches_for_itself():
        if S > 1:
            # a page is multiplied whole and copied as far as it is
            # filled: what lies behind holds probability 0, which only a
            # FINITE value keeps out of the sum, and a buffer no copy has
            # reached yet holds whatever the kernel before left there
            for buf in value_bufs:
                buf[...] = jnp.zeros(buf.shape, buf.dtype)

        def _reset(g):
            slot_ref[g] = 0
            coming_ref[g] = 0

        each_stream(_reset)
        start_copies_for(b, j, b, j, True)

    # a stream whose copy is on its way reads from the other buffer from
    # now on, which leaves the one it read from free for the next step's
    def _turn(g):
        landing_ref[g] = coming_ref[g]
        slot_ref[g] = jnp.where(coming_ref[g] > 0, 1 - slot_ref[g], slot_ref[g])
        coming_ref[g] = 0

    each_stream(_turn)

    # the next step's copies are queued behind this step's before this
    # step waits for its own: the copy engine never idles between them.
    # Stream 0 holds a step's lowest positions: where it has none (a
    # dead row: they come last), no stream has
    @pl.when(jnp.logical_not(last) & (page(b_next, j_next, 0)[1] > 0))
    def _fetch_for_the_next_step():
        start_copies_for(b_next, j_next, b, j, False)

    @pl.when(j == 0)
    def _init():
        softmax_scratch_init(s_acc, s_m, s_l)

    length = lengths_ref[b]
    first = None
    if window is not None:
        # query token t of this tile stands at position length + t
        rows = q_ref.shape[3]
        t = qb * (rows // q_per_kv) + (
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // q_per_kv
        )
        first = length + layer_ref[1] + t - (window - 1)

    @pl.when(page(b, j, 0)[1] > 0)
    def _row_has_pages_here():
        def _stream(g):
            # what this step reads was started one step ago and has to be
            # here now (the wait needs the copy's size, not its source)
            def _wait(s):
                for c in copies(g, slot_ref[g], 0, s):
                    c.wait()

            for_tiles(landing_ref[g], _wait)

            @pl.when(page(b, j, g)[1] > 0)
            def _block():
                # all KV heads of the page rode one copy
                slot = slot_ref[g]
                k_all = bufs[0][slot, g]
                if value_dim is None:
                    v_all = bufs[1][slot, g]
                else:  # the values are the page's first columns
                    v_all = k_all[:, :, :value_dim]
                if quantized:
                    # in-kernel dequant: multiply the int8 page by its
                    # per-(head, slot) scales right after the gather;
                    # the dots below then take float32 operands (HIGHEST)
                    ks, vs = bufs[2][slot, g], bufs[3][slot, g]
                    k_all = k_all.astype(jnp.float32) * ks[:, :, None]
                    v_all = v_all.astype(jnp.float32) * vs[:, :, None]
                chosen = None
                if masked:
                    # this page's selection, a row a query TOKEN: every
                    # query head of the token takes it (q tile row t*r + i)
                    chosen = jnp.repeat(selection_ref[0, 0, g], q_per_kv, 0) != 0
                for h in range(n_kv_heads):
                    softmax_block_update(
                        q_ref[0, 0, h], k_all[h], v_all[h],
                        s_acc.at[h], s_m.at[h], s_l.at[h],
                        base=stream_col(b, j, g, G, firsts_ref) * block_size,
                        length=length,
                        scale=scale, first=first, chosen=chosen,
                    )

        each_stream(_stream)

    @pl.when(j == nJ - 1)
    def _emit():
        acc_ref[0, 0] = s_acc[...]
        m_ref[0, 0] = s_m[...]
        l_ref[0, 0] = s_l[...]


def window_first_pages(lengths, block_size: int, window: int):
    """The number, in its row, of the page that holds the first position a
    row's FIRST query attends under ``window`` (position ``length - window
    + 1``; 0 while the row is shorter than the window)."""
    start = jnp.maximum(lengths.astype(jnp.int32) - (window - 1), 0)
    return start // block_size


def window_span_pages(block_size: int, window: int) -> int:
    """Pages the ``window - 1`` positions a query attends before itself
    can touch, at the worst alignment."""
    return max(window - 2, 0) // block_size + 2


def visit_order(lengths, block_size: int, firsts=None):
    """The batch row each grid step ``b`` works on: rows in falling order
    of the valid pages they hold (from ``firsts`` on, under a window),
    dead rows last.  The pipeline fetches
    step ``b + 1``'s pages while step ``b`` computes, one step ahead and
    no further, so a step lasts as long as the LONGER of this row's dots
    and the next row's copies: rows of equal page counts side by side
    keep both busy, and dead rows in a block at the end start no copy
    between two live rows."""
    pages = pages_to_visit(lengths, block_size, firsts)
    return jnp.argsort(-pages, stable=True).astype(jnp.int32)


def pages_to_visit(lengths, block_size: int, firsts=None):
    """The pages the grid visits of each row: those that hold a cached
    position, from ``firsts`` on under a window.  0: a dead row (or one
    whose window reaches no cached position)."""
    pages = -(-lengths.astype(jnp.int32) // block_size)
    return pages if firsts is None else pages - firsts


class LiveRows(NamedTuple):
    """The rows of a DECODE call that hold a page to visit
    (:func:`pages_to_visit`): its grid runs over them and over no other."""

    count: jax.Array  # [] int32: how many, at least 1 (the grid's extent)
    mask: jax.Array  # [B] bool, in the rows' OWN order (not the grid's)


class PagePlan(NamedTuple):
    """What the kernel reads of one (tables, lengths) pair, in the order
    its grid visits the rows."""

    lengths: jax.Array  # [B] valid prefix per row
    page_ids: jax.Array  # [B, MB'] the rows' tables, a whole number of steps wide
    order: jax.Array  # [B] visit_order
    live: Optional[LiveRows] = None  # a decode call's (``decode=True``)


class WindowPagePlan(NamedTuple):
    """A :class:`PagePlan` of ``[length - window + 1, length)`` of each
    row."""

    lengths: jax.Array
    page_ids: jax.Array
    order: jax.Array
    firsts: jax.Array  # [B] window_first_pages, in visiting order
    live: Optional[LiveRows] = None


def plan_pages(
    tables, lengths, block_size: int, group: int, window: Optional[int] = None,
    decode: bool = False,
):
    """The plan :func:`paged_flash_attention` makes for itself unless it
    is handed one.  A caller that runs the kernel many times over the
    same rows (every layer of every step of a decode chunk) makes it
    once, with the ``group`` :func:`page_group` names for its shapes:
    XLA does not hoist the sort out of those loops.  Under ``window`` the
    plan is of ``[length - window + 1, length)`` of each row.  ``decode``:
    the plan of calls with ONE query a row, whose grid holds the live rows
    only (:class:`LiveRows`); a fill's plan is what it was."""
    firsts = None
    if window is not None:
        firsts = window_first_pages(lengths, block_size, window)
    order = visit_order(lengths, block_size, firsts)
    live = None
    if decode:
        # a grid of no step at all is not one Mosaic is asked for: where
        # every row is dead the one step is a dead row's
        held = pages_to_visit(lengths, block_size, firsts) > 0
        live = LiveRows(jnp.maximum(jnp.sum(held, dtype=jnp.int32), 1), held)
    tables = tables.astype(jnp.int32)[order]
    short = -tables.shape[1] % group
    if short:  # the steps past a row's table hold no cached position
        tables = jnp.pad(tables, ((0, 0), (0, short)))
    lengths = lengths.astype(jnp.int32)[order]
    if firsts is None:
        return PagePlan(lengths, tables, order, live)
    return WindowPagePlan(lengths, tables, order, firsts[order], live)


def page_group(
    n_queries: int, n_q_heads: int, pool_shape, kv_dtype, quantized: bool,
    max_blocks: int, masked: bool = False,
) -> int:
    """Pages a grid step streams for a call of these shapes (``pool_shape``
    as the kernel sees it: one shard's, under a TP mesh; ``masked``: a
    call with a selection)."""
    Hkv, BS, hd = pool_shape[-3:]
    return _plan_tiles(
        n_queries, n_q_heads // Hkv, Hkv, BS, hd,
        jnp.dtype(kv_dtype).itemsize, quantized, max_blocks, masked,
    )[0]


def page_tile(pool_shape, kv_dtype) -> int:
    """Tokens of the unit the kernel copies a page of this pool in
    (``pool_shape`` as the kernel sees it: one shard's, under a TP mesh):
    of a row of ``n`` cached positions it reads ``ceil(n / tile)``
    tiles."""
    Hkv, BS, hd = pool_shape[-3:]
    return _tile_tokens(Hkv, BS, hd, jnp.dtype(kv_dtype).itemsize)


def _row_map(b, qb, j, lengths_ref, ids_ref, layer_ref, order_ref, *firsts):
    """Query and output tiles of step (b, qb, .): those of the row this
    step works on."""
    return (order_ref[b], qb, 0, 0, 0)


def _selection_map(
    b, qb, j, lengths_ref, ids_ref, layer_ref, order_ref, *, span: int
):
    """The selection's block of step (b, qb, j): that of the step's G
    pages (``span`` positions) for the row's query tile; at the steps past
    the row's last page its last block again, which the pipeline does not
    fetch anew."""
    last = jnp.maximum((lengths_ref[b] + span - 1) // span - 1, 0)
    return (order_ref[b], qb, jnp.minimum(j, last), 0, 0)


def _group_selection(mask, QT: int, QB: int, pages: int, BS: int):
    """A selection ``[B, Q, MB * BS]`` as the grid reads it: ``[B, QB,
    pages, QT, BS]`` int32 (a block's last two dimensions whole: the query
    tokens of a tile by the positions of ONE page; 32-bit words, a row of
    which the kernel lays down the sublanes of a token's query heads), 0
    where the query axis and the table were padded."""
    B, Q, S = mask.shape
    mask = jnp.pad(
        mask.astype(jnp.int32), ((0, 0), (0, QB * QT - Q), (0, pages * BS - S))
    )
    return mask.reshape(B, QB, QT, pages, BS).transpose(0, 1, 3, 2, 4)


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


def _layer_scalar(layer, window_shift=None):
    layer = (
        jnp.zeros((1,), jnp.int32)
        if layer is None
        else jnp.asarray(layer, jnp.int32).reshape(1)
    )
    if window_shift is None:
        return layer
    return jnp.concatenate([layer, jnp.asarray(window_shift, jnp.int32).reshape(1)])


@functools.partial(
    jax.jit, static_argnames=("interpret", "scale", "value_dim", "window")
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
    plan=None,  # plan_pages(tables, lengths, ..., window)
    scale: Optional[float] = None,  # softmax scale; None = 1/sqrt(hd)
    value_dim: Optional[int] = None,  # latent pages: values = k[..., :value_dim]
    window: Optional[int] = None,  # attend i - j < window only
    window_shift: jax.Array | None = None,  # [] int32: see below
    mask: jax.Array | None = None,  # [B, Q, MB * BS] bool: a selection
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Un-normalized online-softmax attention partials over paged KV.

    Every query token attends the FULL prefix ``[0, length)`` of its row
    (decode queries by definition; prefill-chunk queries because the
    prefix precedes the whole chunk — in-chunk causality is the caller's
    self-attention term); under ``window``, query ``t`` attends ``[length
    + t - window + 1, length)`` of it (module docstring).
    ``window_shift`` (traced, 0 if None) moves every query that many
    positions on: step ``i`` of a decode chunk, whose queries stand ``i``
    past the cached prefix the chunk started from, is ``window_shift=i``
    over the plan made once for the chunk.  Returns ``(acc [B,Q,Hq,hd] f32, m [B,Q,Hq],
    l [B,Q,Hq])``; rows with ``length == 0`` return ``acc=0, l=0, m=-inf``.

    Pool layout is PAGE-major ``[NB, Hkv, BS, hd]`` so a whole page is one
    contiguous (Hkv, BS, hd) HBM read (a partly filled one ``Hkv``
    pieces), and the grid streams ``PAGE_GROUP`` pages per step (their
    copies overlap — see PAGE_GROUP).

    A 5-D ``k_pool``/``v_pool`` is the FULL layer-stacked pool; ``layer``
    (traced scalar) selects the layer inside the kernel's index map, so a
    layer scan never materializes a per-layer pool slice (that slice is
    pool_bytes/L of pure copy traffic per layer — the whole pool per
    forward).

    ``k_scale``/``v_scale`` mark an int8-quantized pool: each page's
    scales are copied beside it, as far, and the kernel dequantizes in
    VMEM right after the gather (the storage-only quantization
    contract).

    ``plan``: what :func:`plan_pages` made of these ``tables`` and
    ``lengths`` (which are then not read) under this ``window``, for a
    caller that makes many calls over the same rows.

    ``v_pool=None`` with ``value_dim``: latent pages (module docstring);
    ``acc`` is then ``[B, Q, Hq, value_dim]``.

    ``mask``: a SELECTION (module docstring): query ``t`` of row ``b``
    attends cached position ``s`` iff ``s < length`` and ``mask[b, t,
    s]``; a query that chose nothing returns ``l=0``.  Not under a window.
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
    masked = mask is not None
    assert not (masked and window is not None), "a selection under a window"
    # tile the query axis (QT tokens per grid cell, QT*r rows of scratch)
    # and pick the page group from the VMEM these shapes need
    G, QT, tile = _plan_tiles(
        Q, r, Hkv, BS, hd, jnp.dtype(k_pool.dtype).itemsize, quantized, MB,
        masked,
    )
    qg, QB = _group_queries(q, Hkv, r, QT)
    if window is not None and window_shift is None:
        window_shift = 0
    layer_arr = _layer_scalar(layer, window_shift if window is not None else None)
    # a row's page steps: its table's, or no more than a window can touch
    pages = MB if window is None else min(MB, window_span_pages(BS, window))
    # lengths and page ids in the order the grid visits the rows; q and
    # the outputs stay where they are and are addressed through the order
    decode = Q == 1
    if plan is None:
        plan = plan_pages(tables, lengths, BS, G, window, decode)
    assert plan.page_ids.shape == (B, -(-MB // G) * G), (
        plan.page_ids.shape, B, MB, G,
    )
    assert isinstance(plan, WindowPagePlan) == (window is not None), window
    assert (plan.live is not None) == decode, (Q, plan.live)
    # a decode call's grid holds the rows that have pages to visit and no
    # other (they come first in visiting order, and the kernel reads its
    # extent from the grid): a slot that does not decode costs no step.
    # Mosaic and both interpreters take the traced extent
    grid = (plan.live.count if decode else B, QB, -(-pages // G))
    prefetch = [plan.lengths, plan.page_ids, layer_arr, plan.order]
    if window is not None:
        prefetch.append(plan.firsts)
    # the pools stay in HBM: the kernel copies each stream's page into one
    # of its two buffers itself, as far as the page is filled.  int8
    # pools: a page's scales (one f32 per head x slot) ride beside it
    pools = [k_pool] + ([] if latent else [v_pool])
    if quantized:
        pools += [k_scale, v_scale]
    page_buffers = [
        pltpu.VMEM((2, G) + p.shape[k_pool.ndim - 3:], p.dtype)  # a page
        for p in pools
    ]
    # the selection arrives as q does, a block a step through the pipeline
    selection, selection_spec = [], []
    if masked:
        assert mask.shape == (B, Q, MB * BS), (mask.shape, B, Q, MB, BS)
        selection = [_group_selection(mask, QT, QB, grid[2] * G, BS)]
        selection_spec = [
            pl.BlockSpec(
                (1, 1, G, QT, BS),
                functools.partial(_selection_map, span=G * BS),
            )
        ]
    acc, m, l = pl.pallas_call(
        functools.partial(
            _kernel,
            block_size=BS,
            tile=tile,
            scale=1.0 / np.sqrt(hd) if scale is None else scale,
            n_kv_heads=Hkv,
            page_group=G,
            layered=layered,
            quantized=quantized,
            value_dim=value_dim,
            window=window,
            q_per_kv=r,
            masked=masked,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[pl.BlockSpec((1, 1, Hkv, QT * r, hd), _row_map)]
            + selection_spec
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=[
                pl.BlockSpec((1, 1, Hkv, QT * r, vd), _row_map),
                pl.BlockSpec((1, 1, Hkv, QT * r, 128), _row_map),
                pl.BlockSpec((1, 1, Hkv, QT * r, 128), _row_map),
            ],
            scratch_shapes=page_buffers
            + [
                pltpu.VMEM((Hkv, QT * r, vd), jnp.float32),
                pltpu.VMEM((Hkv, QT * r, 128), jnp.float32),
                pltpu.VMEM((Hkv, QT * r, 128), jnp.float32),
                # which of its two buffers each stream reads from, the
                # tiles on their way into the other one for the next
                # step, and those this step waits for
                pltpu.SMEM((G,), jnp.int32),
                pltpu.SMEM((G,), jnp.int32),
                pltpu.SMEM((G,), jnp.int32),
            ]
            + [pltpu.SemaphoreType.DMA((2, G))] * len(pools),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, vd), jnp.float32),
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, QB, Hkv, QT * r, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # a copy started at one grid step lands at the next: the
            # steps run in order, on one core
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        # the trace names the Mosaic call after this: one query row a
        # sequence is a decode step, a query tile a prefill chunk; a
        # windowed call apart from one over the whole prefix, and one
        # under a selection apart from both
        name=(
            ("paged_mla_" if window is None else "paged_mla_window_")
            if latent
            else "paged_attn_" if window is None else "paged_window_"
        )
        + ("masked_" if masked else "")
        + ("decode" if Q == 1 else "fill"),
    )(*prefetch, qg, *selection, *pools)

    acc, m, l = _ungroup_outputs(acc, m, l, B, QB, QT, Hkv, r, Q, Hq, vd)
    if decode:
        # the blocks of the rows the grid left out were never written:
        # whatever they hold, a row without pages reads as the contract says
        keep = plan.live.mask[:, None, None]
        acc = jnp.where(keep[..., None], acc, 0.0)
        m = jnp.where(keep, m, _NEG_INF)
        l = jnp.where(keep, l, 0.0)
    return acc, m, l


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
    scale=None, value_dim=None, window=None, window_shift=0, mask=None,
):
    """jnp reference for :func:`paged_flash_attention` (same contract;
    ``v_pool=None`` with ``value_dim``: latent pages; ``window``: query
    ``t`` attends ``[length + t - window + 1, length)``; ``mask`` [B, Q,
    MB * BS]: query ``t`` attends ``s`` only where ``mask[b, t, s]``).

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
    pos = jnp.arange(S)[None, None, None, None, :]
    valid = pos < lengths[:, None, None, None, None]
    if window is not None:
        first = (
            lengths[:, None] + window_shift + jnp.arange(Q)[None, :]
        ) - (window - 1)
        valid = valid & (pos >= first[:, :, None, None, None])
    if mask is not None:
        valid = valid & mask[:, :, None, None, :]
    s = jnp.where(valid, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(valid, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bqkrs,bksd->bqkrd", p, v.astype(jnp.float32))
    return (
        acc.reshape(B, Q, Hq, v.shape[-1]),
        m.reshape(B, Q, Hq),
        l.reshape(B, Q, Hq),
    )
