"""Attention over a CHOSEN set of cached latent entries: a learned indexer's
scores over a row's pages, the exact choice of the ``k`` largest, and
latent attention over the chosen entries alone (DeepSeek-V3.2-Exp's
sparse attention, as ``dots3_note``'s full layers state it).

Three steps a full layer.  The first two are XLA in every program; the
third is the paged kernel's (``ops/paged_attention.py`` under a selection)
in a fill and in a decode step over a table of up to
:data:`MASKED_DECODE_MAX_RATIO` times ``k`` positions, XLA's in a decode
step over a longer one, and this module has no Mosaic call of its own:

* :func:`paged_index_scores`: ``I(t, s) = sum_j w_j(t) relu(q_j(t) .
  k(s))`` of every cached position ``s`` of a row, from the pool of index
  keys (ONE key of ``index_head_dim`` a token, the V side of the pool of
  whole-context pages: ``models/paged.pool_shapes``): 256 B and ``2 x
  heads x width`` = 16,384 FLOP a position at the published sizes, 64
  FLOP/B, far under a v5e's ridge of 240: bound by the bytes.  The row's
  pages are gathered first, so the keys are read twice (0.3 GB a layer
  and step at the cell's sizes); a Mosaic kernel that scored them page by
  page where they lie was written and taken out again before it was
  served (PR 49: PERF.md section 7 has its design and why).
* the choice, EXACT (no ``approx_max_k``), in one of two forms:
  :func:`chosen_mask` states the set as a mask over the scores (the
  ``k``-th largest found bit by bit, no sort; ties at it fall as ``top_k``
  lets them: the lower position first) for a path that attends under a
  mask; :func:`select` (``lax.top_k``: two sorts) states it as positions
  for the path that gathers.  Fewer valid positions than ``k`` choose them
  all.
* the attention over the chosen entries, ONE algorithm in the form that is
  cheaper for how much of the table the set is (:func:`decode_reads_masked`,
  from the table's shape when a program is traced): UNDER THE MASK in the
  paged kernel's latent mode, the mask one more operand
  (``paged_flash_attention(mask=)``: every cached position of the row is
  multiplied and the chosen ones kept; a page's scores of a query tile stay
  in VMEM).  That is every fill chunk (Mosaic ``paged_mla_masked_fill``;
  the XLA page loop that stood here took 58 ms a layer and chunk where the
  kernel takes 33, PERF.md PR 50) and, since PR 54, a decode step whose
  table is short (``paged_mla_masked_decode``: the cell's 18,432 = 9 x
  ``k``).  Over a LONG table a decode step READS THE CHOSEN ENTRIES and not
  the context (:func:`sparse_latent_partials`, XLA: a gather of ``k`` rows
  a sequence from the pool as it lies, then the absorbed products over
  them: a cost that does not grow with the context).  Both return the
  un-normalised ``(acc, m, l)`` that ``paged.window_attention`` /
  ``chunk_attention`` merge with the chunk's own tokens.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

#: a decode step attends its chosen set UNDER A MASK in the paged kernel
#: (every cached position of the row multiplied) while its table holds at
#: most this many times ``index_topk`` positions, and GATHERS the chosen
#: rows beyond.  Both paths alone on a v5e at the sparse cell's widths (128
#: heads, pages of 512 x 640, ``index_topk`` 2,048, 64 slots, rows filled
#: to 70% of the table; ``scripts/sparse_decode_paths.py``, my chip run,
#: PR 54), milliseconds a layer and step, mask + kernel | sorts + gather:
#:
#:   table    ratio   49 live rows                  64 live rows
#:   18,432     9     0.40 + 2.82 | 1.23 + 4.38     0.38 + 3.66 | 1.23 + 4.15
#:   36,864    18     0.40 + 5.43 | 2.67 + 4.29     0.41 + 7.06 | 2.69 + 4.07
#:   55,296    27     0.41 + 8.04 | 3.57 + 4.29     0.39 + 10.47 | 3.59 + 4.07
#:
#: The masked path is 0.6 ms + 0.29 (49 rows) or 0.38 (64) a unit of the
#: ratio (4.4-4.5 us a 1,024 cached positions and live row), the gathering
#: one 4.3-4.6 ms + 0.125 whatever the rows (the gather is the same
#: ``index_topk`` rows of every slot; the sorts grow with the table): they
#: cross at 23.9 with 49 rows live and at 14.6 with all 64.  A program
#: knows its slots and not how many will be live, so the constant sits by
#: the full engine's crossing: at 16 the masked path is 5% behind with
#: every slot live and 20% ahead with three quarters.
MASKED_DECODE_MAX_RATIO = 16


def decode_reads_masked(table_positions: int, k: int) -> bool:
    """Whether a decode step over tables of ``table_positions`` (``MB *
    BS``, static) attends its ``k`` chosen positions under a mask in the
    paged kernel, or gathers them (module docstring)."""
    return table_positions <= MASKED_DECODE_MAX_RATIO * k


def index_scores(q, w, keys):
    """``sum_j w[.., t, j] relu(q[.., t, j] . keys[.., s])`` [.., T, S]
    float32 of keys at hand (``q`` [.., T, Hi, di], ``w`` [.., T, Hi],
    ``keys`` [.., S, di])."""
    s = jnp.einsum(
        "...thd,...sd->...ths", q.astype(keys.dtype), keys,
        preferred_element_type=F32,
    )
    return jnp.einsum(
        "...ths,...th->...ts", jnp.maximum(s, 0.0), w.astype(F32),
        precision=HIGHEST,
    )


#: query tokens :func:`paged_index_scores` scores at a time (its per-head
#: products are [this, Hi, MB * BS] float32)
INDEX_QUERY_BLOCK = 32


def paged_index_scores(q, w, index_pool, tables, lengths, layer):
    """``[B, T, MB * BS]`` float32: :func:`index_scores` of every cached
    position ``s`` of row ``b`` (``q`` [B, T, Hi, di] roped index queries,
    ``w`` [B, T, Hi] head weights with every scale folded in,
    ``index_pool`` [L, NB, 1, BS, di]), ``NEG`` from ``lengths[b]`` on.
    Every query scores the whole cached prefix (what lies inside a query's
    own chunk is the caller's).  XLA: the row's pages of keys are gathered
    from the pool (``MB * BS * di`` a row and layer), then scored a block
    of queries at a time."""
    B, T = q.shape[:2]
    di = index_pool.shape[-1]
    flat = index_pool.reshape((-1,) + index_pool.shape[2:])  # [L * NB, 1, BS, di]
    keys = flat[layer * index_pool.shape[1] + tables, 0].reshape(B, -1, di)
    Q = min(T, INDEX_QUERY_BLOCK)
    pad = -T % Q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
    n = (T + pad) // Q
    out = jax.lax.map(
        lambda qw: index_scores(qw[0], qw[1], keys),
        (
            q.reshape((B, n, Q) + q.shape[2:]).swapaxes(0, 1),
            w.reshape(B, n, Q, -1).swapaxes(0, 1),
        ),
    )  # [n, B, Q, S]
    out = out.swapaxes(0, 1).reshape(B, n * Q, -1)[:, :T]
    pos = jnp.arange(keys.shape[1])
    return jnp.where(pos[None, None, :] < lengths[:, None, None], out, NEG)


def select(scores: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """The ``k`` positions of largest score, EXACTLY: ``(idx [.., k]
    int32, chosen [.., k] bool)``.  ``scores`` [.., N] holds ``NEG`` at
    what a query may not attend; ``chosen`` is False where ``top_k`` ran
    out of real positions (a context shorter than ``k`` chooses all of
    it).  Ties fall to the lower position (``lax.top_k``)."""
    k = min(k, scores.shape[-1])
    vals, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32), vals > NEG / 2


def _ordered_bits(x):
    """float32 as uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def chosen_mask(scores: jax.Array, k: int) -> jax.Array:
    """:func:`select`'s set as a mask over ``scores`` [.., N]: the
    positions above the ``k``-th largest score, and of those AT it the
    lowest positions, as many as ``top_k`` takes.  The ``k``-th largest
    is found bit by bit (32 counts over the scores, then one count a bit
    of a position for the ties): 1.1 ms where ``top_k`` over a fill
    chunk's ``[1024, 19456]`` scores takes 22.7 (my chip run, PR 49)."""
    N = scores.shape[-1]
    valid = scores > NEG / 2
    if N <= k:
        return valid
    u = _ordered_bits(scores)
    lead = scores.shape[:-1]

    def value_bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, t)

    kth = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros(lead, jnp.uint32))
    above, tied = u > kth[..., None], u == kth[..., None]
    need = k - jnp.sum(above, axis=-1)  # of the tied, the lowest positions
    pos = jnp.arange(N, dtype=jnp.int32)
    bits = max(N - 1, 1).bit_length()

    def position_bit(i, q):
        cand = q | (jnp.int32(1) << (bits - 1 - i))
        few = jnp.sum(tied & (pos < cand[..., None]), axis=-1) < need
        return jnp.where(few, cand, q)

    # the largest q with fewer than ``need`` tied positions before it: the
    # ``need``-th tied position itself
    last = jax.lax.fori_loop(0, bits, position_bit, jnp.zeros(lead, jnp.int32))
    return (above | (tied & (pos <= last[..., None]))) & valid


def packed_mask(mask: jax.Array) -> jax.Array:
    """``mask`` [.., N] bool as ``[.., ceil(N / 32)]`` uint32, position
    ``s`` bit ``s % 32`` of word ``s // 32``: what a decode step hands the
    host of its selection (:func:`positions_of_packed` reads it)."""
    N = mask.shape[-1]
    words = -(-N // 32)
    bits = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, words * 32 - N)])
    bits = bits.reshape(mask.shape[:-1] + (words, 32)).astype(jnp.uint32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1, dtype=jnp.uint32)


def positions_of_packed(words: np.ndarray) -> np.ndarray:
    """The set positions of ONE :func:`packed_mask` row, ascending (on the
    host)."""
    bits = np.unpackbits(
        np.ascontiguousarray(words, "<u4").view(np.uint8), bitorder="little"
    )
    return np.flatnonzero(bits).astype(np.int32)


def row_positions(cols: np.ndarray, table: int, own: int) -> np.ndarray:
    """Columns of a mask over ``[the table's positions | a chunk's own
    tokens]`` as positions of the row: the chunk's first token stands at
    position ``own`` (on the host)."""
    return np.where(cols < table, cols, cols - table + own)


def kept_positions(kept: np.ndarray, table: int, own: int, k: int) -> np.ndarray:
    """ONE decode step's chosen set of one layer as positions of the row,
    int32 (-1: none), from what the decode program handed out
    (``hybrid_decode_chunk(keep_chosen=True)``), whichever it was: uint32
    is a step that attended under its mask (:func:`packed_mask` words over
    the table's ``table`` positions and then its chunk's own tokens, the
    first at position ``own``: ``[k]`` comes back); int32 is a step that
    gathered, the positions themselves.  The one place that knows both
    forms (on the host)."""
    if kept.dtype != np.uint32:
        assert kept.dtype == np.int32, kept.dtype
        return kept
    cols = positions_of_packed(kept)
    out = np.full(k, -1, np.int32)
    out[: len(cols)] = row_positions(cols, table, own)
    return out


def _flat_entries(pool, layer, tables, positions):
    """Rows of ``pool`` [L, NB, 1, BS, width] seen as ``[L * NB * BS,
    width]`` (a bitcast: the pool is not moved) that hold cached position
    ``positions[b, i]`` of row ``b`` in layer ``layer``."""
    _, NB, _, BS, _ = pool.shape
    page = jnp.take_along_axis(
        tables, jnp.clip(positions // BS, 0, tables.shape[1] - 1), axis=1
    )
    return (layer * NB + page) * BS + positions % BS


def sparse_latent_partials(
    q, pool, layer, tables, idx, live, value_dim: int, scale: float
):
    """Absorbed latent attention of ONE query a row over the cached
    entries at positions ``idx`` [B, K] where ``live`` [B, K]: the K rows
    are gathered from the pool as it lies (K x width bytes a row, not the
    context's) and multiplied as the paged kernel multiplies a page.
    ``q`` [B, 1, H, width].  Returns ``(acc [B, 1, H, value_dim], m [B,
    1, H], l [B, 1, H])`` float32, un-normalised."""
    width = pool.shape[-1]
    rows = _flat_entries(pool, layer, tables, jnp.where(live, idx, 0))
    ent = jnp.take(pool.reshape(-1, width), rows, axis=0)  # [B, K, width]
    s = jnp.einsum(
        "bhd,bkd->bhk", q[:, 0].astype(ent.dtype), ent,
        preferred_element_type=F32,
    ) * scale
    keep = live[:, None, :]
    s = jnp.where(keep, s, NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.where(keep, jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum(
        "bhk,bkv->bhv", p.astype(ent.dtype), ent[..., :value_dim],
        preferred_element_type=F32,
    )
    return acc[:, None], m[:, None], jnp.sum(p, axis=-1)[:, None]
