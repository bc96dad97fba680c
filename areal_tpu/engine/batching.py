"""Packed SequenceSample <-> device-batch conversion.

The data plane moves packed varlen numpy (areal_tpu/api/data.py); XLA wants
static shapes.  This module is the boundary, with two layouts:

* :func:`pad_batch` — one sequence per row of a ``[B, T]`` batch with
  bucketed T (limiting recompilation) and B padded to a multiple of the
  mesh's dp shard count.
* :func:`pack_batch` — MULTIPLE sequences per row: FFD bin packing
  (base/datapack.py, native fast path) lays segments side by side, so a
  long-tail length distribution no longer pads every row to the global
  max.  Per-row ``seg_ids`` are numbered 1..k and ``positions`` restart at
  0 per segment, so the transformer's same-segment-causal mask and RoPE
  are correct by construction.

:func:`plan_minibatch` chooses the ONE ``[rows, T]`` of a train step's
micro-batches: the minibatch packed once, micro-batches cut as whole rows.

Both produce the same :class:`PaddedBatch` dataclass, and both carry a
**segment table** (``seg_rows``/``seg_starts``/``seg_lens``, flat ``[S]``
arrays in ORIGINAL sequence order) so jitted code can gather per-segment
quantities (last-token values, pair signs) without assuming
one-sequence-per-row.  :func:`unpack_per_token` is the inverse, restoring
the packed-1D order of per-token outputs.

(The reference keeps 1-D packing all the way into flash-attn varlen
kernels, realhf/api/core/data_api.py + realhf/impl/model/utils/padding.py;
on TPU the segment-packed padded layout is the idiomatic equivalent — the
Pallas flash kernel, the reference attention mask, and the MoE stat
masking all consume ``seg_ids`` natively.)
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from areal_tpu.api.data import _SCALAR_KEYS, SequenceSample
from areal_tpu.base import datapack

DEFAULT_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768)


def bucket_len(n: int, buckets: Sequence[int] = DEFAULT_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"sequence length {n} exceeds largest bucket")


def pad_rows(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@dataclasses.dataclass
class PaddedBatch:
    """Device-ready arrays; one OR MORE sequences (segments) per row.

    ``tokens``/``positions``/``seg_ids``: [B, T]; ``seq_lens``: [B] (real
    tokens per row, 0 for padding rows).  ``extras`` holds per-key aligned
    arrays:
      - full-length keys -> [B, T] at each segment's columns
      - transition keys (len L-1) -> [B, T] with entry t = transition
        t->t+1 (each segment's LAST column is always 0)
      - scalar keys -> [n_real] (padded-mode, one segment per row) or
        [S] segment-aligned (packed mode)

    The segment table maps original sequence order to the layout:
    segment ``s`` (the s-th flattened sequence of the sample) occupies
    ``tokens[seg_rows[s], seg_starts[s] : seg_starts[s] + seg_lens[s]]``.
    Arrays are sized [S] (``n_segs`` real entries, zero-padded) so jitted
    consumers see a static shape; padding entries have ``seg_lens == 0``
    and must be masked (they alias row 0 / column 0).
    """

    tokens: np.ndarray
    positions: np.ndarray
    seg_ids: np.ndarray
    seq_lens: np.ndarray
    extras: Dict[str, np.ndarray]
    n_real: int  # number of real rows
    seg_rows: np.ndarray  # [S] int32
    seg_starts: np.ndarray  # [S] int32
    seg_lens: np.ndarray  # [S] int32 (0 = padding segment)
    n_segs: int  # number of real segments

    @property
    def shape(self):
        return self.tokens.shape

    @property
    def padded_slots(self) -> int:
        """Total [B, T] slots this batch occupies on device."""
        return int(self.tokens.size)


def _extra_layout(key: str, lens: List[int], tok_lens: List[int]) -> str:
    """Classify an extra key as ``full`` / ``transition`` / ``scalar`` by
    comparing its per-sequence lengths to the token key's.

    The registry of known scalar keys wins first: ``rewards`` et al. stay
    scalars even in a degenerate batch of length-1 sequences.  For unknown
    keys, FULL-length wins over scalar when every sequence has length 1 —
    the old ``all(l == 1)`` heuristic silently laid a genuine per-token
    key out as [B] whenever the batch happened to be all length-1.
    """
    if key in _SCALAR_KEYS:
        if not all(l == 1 for l in lens):
            raise ValueError(
                f"scalar key {key!r} has non-unit lengths {lens[:8]}"
            )
        return "scalar"
    if lens == tok_lens:
        return "full"
    if lens == [l - 1 for l in tok_lens]:
        return "transition"
    if all(l == 1 for l in lens):
        return "scalar"
    raise ValueError(
        f"key {key!r} lengths match neither the token key ({tok_lens[:4]}...)"
        f", its transitions, nor a scalar layout: {lens[:4]}..."
    )


def _layout_batch(
    sample: SequenceSample,
    token_key: str,
    seqlens: List[int],
    placement: List[Tuple[int, int]],  # per-seq (row, start col)
    B: int,
    T: int,
    S: int,
    scalar_per_segment: bool,
) -> PaddedBatch:
    """Shared layout engine for pad_batch/pack_batch: place sequence ``s``
    at ``placement[s]``, build the segment table, and align extras."""
    n = len(seqlens)
    tokens = np.zeros((B, T), np.int32)
    positions = np.zeros((B, T), np.int32)
    seg_ids = np.zeros((B, T), np.int32)
    seq_lens = np.zeros((B,), np.int32)
    seg_rows = np.zeros((S,), np.int32)
    seg_starts = np.zeros((S,), np.int32)
    seg_lens = np.zeros((S,), np.int32)

    offsets = np.concatenate([[0], np.cumsum(seqlens)])
    data = sample.data[token_key]
    next_seg = np.zeros((B,), np.int32)  # per-row running segment number
    for s, L in enumerate(seqlens):
        r, c = placement[s]
        tokens[r, c : c + L] = data[offsets[s] : offsets[s + 1]]
        positions[r, c : c + L] = np.arange(L)
        next_seg[r] += 1
        seg_ids[r, c : c + L] = next_seg[r]
        seq_lens[r] += L
        seg_rows[s], seg_starts[s], seg_lens[s] = r, c, L

    extras: Dict[str, np.ndarray] = {}
    for key in sample.keys:
        if key == token_key or sample.data.get(key) is None:
            continue
        lens = [l for ls in sample.seqlens[key] for l in ls]
        if len(lens) != len(seqlens):
            # a key not aligned per member sequence (e.g. one scalar per
            # GROUP id alongside multi-sequence groups) would land on the
            # wrong segments after flattening — refuse rather than guess
            raise ValueError(
                f"key {key!r} has {len(lens)} sequences but {token_key!r} "
                f"has {len(seqlens)}; per-group keys cannot align with "
                "multi-sequence ids"
            )
        arr = sample.data[key]
        offs = np.concatenate([[0], np.cumsum(lens)])
        layout = _extra_layout(key, lens, seqlens)
        if layout == "scalar":
            out = np.zeros((S if scalar_per_segment else B,), arr.dtype)
            out[:n] = arr[:n]
        else:
            out = np.zeros((B, T), arr.dtype)
            for s in range(n):
                r, c = placement[s]
                Lk = lens[s]  # == seqlens[s], or seqlens[s]-1 (transition):
                # a transition key fills only its L-1 columns, so each
                # segment's last column stays 0 by construction
                out[r, c : c + Lk] = arr[offs[s] : offs[s + 1]]
        extras[key] = out
    return PaddedBatch(
        tokens=tokens,
        positions=positions,
        seg_ids=seg_ids,
        seq_lens=seq_lens,
        extras=extras,
        n_real=int(max((r for r, _ in placement), default=-1)) + 1,
        seg_rows=seg_rows,
        seg_starts=seg_starts,
        seg_lens=seg_lens,
        n_segs=n,
    )


def pad_batch(
    sample: SequenceSample,
    token_key: str = "packed_input_ids",
    buckets: Sequence[int] = DEFAULT_BUCKETS,
    row_multiple: int = 1,
    min_rows: int = 1,
    fixed_rows: int = 0,
    fixed_len: int = 0,
) -> PaddedBatch:
    """One sequence per row, right padding; extras aligned per class.

    ``fixed_rows``/``fixed_len`` force the output shape (so several
    micro-batches can share one compiled step / be stacked for a scan).
    The segment table is the trivial one (segment s = row s, start 0),
    sized [B] so per-segment gathers line up with per-row [B] arrays.

    Ids holding SEQUENCE GROUPS (e.g. the paired preference dataset packs
    [chosen, rejected, ...] under one id) flatten to one row per member
    sequence, in packed order."""
    seqlens = [l for ls in sample.seqlens[token_key] for l in ls]
    B = max(pad_rows(max(len(seqlens), min_rows), row_multiple), min_rows)
    T = bucket_len(max(seqlens), buckets)
    if fixed_rows:
        assert len(seqlens) <= fixed_rows
        B = fixed_rows
    if fixed_len:
        assert max(seqlens) <= fixed_len
        T = fixed_len
    placement = [(i, 0) for i in range(len(seqlens))]
    return _layout_batch(
        sample, token_key, seqlens, placement, B, T, S=B,
        scalar_per_segment=False,
    )


def pack_batch(
    sample: SequenceSample,
    token_key: str = "packed_input_ids",
    row_multiple: int = 1,
    min_rows: int = 1,
    fixed_rows: int = 0,
    fixed_len: int = 0,
    fixed_segs: int = 0,
    bins: Optional[List[List[int]]] = None,
) -> PaddedBatch:
    """FFD-bin sequences into multi-segment rows.

    Row width T is ``bucket_len(longest sequence)`` (or ``fixed_len``);
    :func:`datapack.bin_pack_ffd` (native fast path) packs sequences into
    rows of at most T tokens, so the padded-slot count tracks the TOTAL
    token count instead of ``n_seqs x max_len``.  Within a row, segments
    are laid out in ascending original-sequence order with ``seg_ids``
    1..k and per-segment positions — attention masking and RoPE need no
    layout-specific handling downstream.

    ``fixed_segs`` forces the segment-table capacity S (default: the
    next power of two of the sequence count, bounding compile variety).
    ``bins`` passes the rows a caller has already chosen (the engine
    plans a whole minibatch first, :func:`plan_minibatch`).
    """
    seqlens = [l for ls in sample.seqlens[token_key] for l in ls]
    max_len = max(seqlens)
    T = fixed_len or bucket_len(max_len)
    assert max_len <= T, (max_len, T)
    if bins is None:
        bins = datapack.bin_pack_ffd(seqlens, T)
    # deterministic layout: rows ordered by their smallest member index,
    # members within a row in ascending original order
    bins = sorted((sorted(b) for b in bins), key=lambda b: b[0])
    n_rows = len(bins)
    B = max(pad_rows(max(n_rows, min_rows), row_multiple), min_rows)
    if fixed_rows:
        assert n_rows <= fixed_rows, (n_rows, fixed_rows)
        B = fixed_rows
    S = fixed_segs or next_pow2(len(seqlens))
    assert len(seqlens) <= S, (len(seqlens), S)

    placement: List[Optional[Tuple[int, int]]] = [None] * len(seqlens)
    for r, members in enumerate(bins):
        col = 0
        for s in members:
            placement[s] = (r, col)
            col += seqlens[s]
        assert col <= T
    return _layout_batch(
        sample, token_key, seqlens, placement, B, T, S=S,
        scalar_per_segment=True,
    )


#: row lengths above this are whole multiples of it (the flash kernel's
#: block, ``ops/flash_attention._BLOCK``); below it the power-of-two
#: buckets stand, which are the lengths a short row may have there
ROW_LEN_STEP = 512


def row_len(n: int) -> int:
    """Shortest row the trainer lays out for ``n`` tokens."""
    if n <= ROW_LEN_STEP:
        return bucket_len(n)
    return pad_rows(n, ROW_LEN_STEP)


@dataclasses.dataclass
class MinibatchPlan:
    """How one minibatch becomes micro-batches of one ``[rows, row_len]``:
    micro-batch ``m`` holds the ids ``groups[m]`` (ascending indices into
    the sample) and, counting its sequences from 0 in that order, its row
    ``r`` holds the sequences ``bins[m][r]``."""

    row_len: int
    rows: int
    groups: List[List[int]]
    bins: List[List[List[int]]]

    @property
    def n_stacked(self) -> int:
        """Micro-batches on the device: the count bucketed to a power of
        two, so that a data-dependent count meets a bounded set of
        compiled steps; the extra ones are all zero."""
        return next_pow2(len(self.groups))

    @property
    def slots(self) -> int:
        return self.n_stacked * self.rows * self.row_len


def _row_units(
    id_lens: List[List[int]], T: int, pack: bool, min_units: int
) -> List[Tuple[List[int], List[List[Tuple[int, int]]]]]:
    """Rows of length ``T`` in units that a micro-batch takes whole:
    ``(ids, rows)``, a row a list of ``(id, k)`` for the id's k-th
    sequence.  Packed: FFD over the IDS, an id's sequences side by side
    in one row (a preference pair never straddles micro-batches); an id
    longer than a row has rows of its own.  Not packed: a row a sequence.
    At least ``min_units`` units (the caller has as many ids)."""
    if not pack:
        return [
            ([i], [[(i, k)] for k in range(len(ls))])
            for i, ls in enumerate(id_lens)
        ]
    totals = [sum(ls) for ls in id_lens]
    units = [
        ([i], [[(i, k) for k in b] for b in datapack.bin_pack_ffd(ls, T)])
        for i, ls in enumerate(id_lens)
        if totals[i] > T
    ]
    small = [i for i, t in enumerate(totals) if t <= T]
    shared = [
        [small[j] for j in b]
        for b in datapack.bin_pack_ffd([totals[i] for i in small], T)
    ]
    while len(units) + len(shared) < min_units:
        fullest = max(shared, key=len)
        shared.remove(fullest)
        shared += [fullest[::2], fullest[1::2]]
    for ids in map(sorted, shared):
        units.append(
            (ids, [[(i, k) for i in ids for k in range(len(id_lens[i]))]])
        )
    return sorted(units, key=lambda u: u[0][0])


def plan_minibatch(
    seqlens: Sequence[Sequence[int]],
    row_cost: Callable[[int], float],
    max_slots_per_mb: int,
    min_mbs: int = 1,
    row_quantum: int = 1,
    pack: bool = True,
    grow: Callable[[int], bool] = lambda T: True,
) -> MinibatchPlan:
    """Lay a minibatch (``seqlens[i]``: the sequence lengths of id i) out
    by slots: pack it ONCE into rows of one length T, then cut
    micro-batches as groups of whole rows, each group at most
    ``max_slots_per_mb`` slots (or one unit of rows, or one
    ``row_quantum`` of them, where that is more), at least ``min_mbs``
    groups, all padded to the largest group's rows.

    T runs from the longest sequence's :func:`row_len` up the same
    ladder, no further than one ``row_quantum`` of rows fits the slot
    budget, and the T whose stacked rows cost least by ``row_cost(T)`` a
    row is taken, the shorter on a tie.  T stays at the first step where
    ``grow(T)`` is false there (the caller's attention holds [T, T]
    scores), as it does with ``pack=False``."""
    id_lens = [list(ls) for ls in seqlens]
    if min_mbs > len(id_lens):
        raise ValueError(
            f"cannot cut {len(id_lens)} ids into {min_mbs} micro-batches"
        )
    total = sum(map(sum, id_lens))
    T = row_len(max(map(max, id_lens)))
    T_end = T
    if pack and grow(T):
        fit = max_slots_per_mb // row_quantum // ROW_LEN_STEP * ROW_LEN_STEP
        T_end = max(T, min(row_len(total), fit))
    best = None
    while T <= T_end:
        if best is not None and total / T * row_cost(T) >= best[0]:
            break  # not even without padding: a row's cost a slot only grows
        units = _row_units(id_lens, T, pack, min_mbs)
        n_rows = [len(rows) for _, rows in units]
        cap = max(max_slots_per_mb // T // row_quantum, 1) * row_quantum
        cuts = datapack.partition_by_budget(n_rows, cap, min_groups=min_mbs)
        rows = pad_rows(
            max(sum(n_rows[u] for u in cut) for cut in cuts), row_quantum
        )
        c = next_pow2(len(cuts)) * rows * row_cost(T)
        if best is None or c < best[0]:
            best = (c, T, rows, units, cuts)
        T = row_len(T + 1)
    _, T, rows, units, cuts = best
    groups, bins = [], []
    for cut in cuts:
        ids = sorted(i for u in cut for i in units[u][0])
        first = dict(
            zip(ids, np.cumsum([0] + [len(id_lens[i]) for i in ids]))
        )
        groups.append(ids)
        bins.append(
            [
                [int(first[i]) + k for i, k in row]
                for u in cut
                for row in units[u][1]
            ]
        )
    return MinibatchPlan(row_len=T, rows=rows, groups=groups, bins=bins)


def unpad_per_token(
    out: np.ndarray,  # [B, T] per-token outputs (full-length alignment)
    seq_lens: np.ndarray,
    n_real: int,
    shift: int = 0,  # 1 for transition-aligned outputs (length L-1)
) -> np.ndarray:
    """Back to packed 1-D concat over real rows (one-sequence-per-row
    layout only; for packed batches use :func:`unpack_per_token`)."""
    parts: List[np.ndarray] = []
    for i in range(n_real):
        L = int(seq_lens[i]) - shift
        parts.append(out[i, :L])
    return np.concatenate(parts, axis=0)


def unpack_per_token(
    out: np.ndarray,  # [B, T] per-token outputs
    pb: PaddedBatch,
    shift: int = 0,  # 1 for transition-aligned outputs (length L-1)
) -> np.ndarray:
    """Segment-table inverse of pad_batch/pack_batch: gather per-token
    outputs back into the packed 1-D concat in ORIGINAL sequence order."""
    parts: List[np.ndarray] = []
    for s in range(pb.n_segs):
        r = int(pb.seg_rows[s])
        c = int(pb.seg_starts[s])
        L = int(pb.seg_lens[s]) - shift
        parts.append(out[r, c : c + L])
    return np.concatenate(parts, axis=0)
