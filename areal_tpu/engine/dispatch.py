"""Measured dispatch table for the serving engine's KV-cache paths.

The engine has two ways to run decode attention — the bucketed dense
cache (XLA einsum at roofline for short uniform rows) and the paged
block-pool kernel (ops/paged_attention.paged_flash_attention).  Which one
wins is a *hardware measurement*, not a constant: the crossover moved
every time the kernels changed (G=1 0.70x dense -> G=4 + 1k pages 0.93x
on v5e), yet ``cache_mode="auto"`` shipped for two rounds on a hardcoded
>=2k cutoff.  (A third, deep DMA-ring paged kernel lost to the BlockSpec
kernel at every shape measured and went in PR 25: PERF.md section 6.)

This module makes the dispatch decision data-driven:

* :class:`PagedDispatchTable` — the threshold ``auto`` mode consults
  (dense->paged by ``kv_cache_len``), plus a ``source`` tag so a scrape
  or a bench blob can tell a measured table from the builtin fallback;
* :func:`derive_dispatch_table` — turns bench.py's decode A/B (dense /
  paged tok/s by context length) into the threshold; bench.py emits the
  result in its summary so the recipe configs can pin what the hardware
  actually measured;
* :func:`resolve_dispatch_table` — config plumbing: an explicit override
  wins, an unset field keeps the default below.

The default reproduces the pre-table behavior (paged at >=2k) so an
unconfigured engine changes nothing.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional

#: dense rows beat the block machinery below this cache length (short
#: prefixes amortize no paging; measured crossover on v5e — see the
#: bench.py decode A/B this default came from)
DEFAULT_PAGED_MIN_CACHE_LEN = 2048

#: speculative decoding's per-row spec-on/spec-off threshold: the
#: acceptance-rate EMA below which a row's drafts are judged not worth
#: verifying and the row falls back to plain chunked decode.  Like the
#: other thresholds in this module it should come from a measurement —
#: bench.py's ``spec_decode_ab`` derives the break-even rate from its
#: own off/on A/B (:func:`spec_break_even_accept_rate`) — and this
#: builtin default is deliberately conservative: at k=8 drafts it only
#: ejects rows whose windows verify ~2 tokens or fewer per pass.
DEFAULT_SPEC_MIN_ACCEPT_RATE = 0.2

#: measured cost of one speculative verify pass, in plain-decode-step
#: units (``c`` in :func:`spec_break_even_accept_rate`).  The per-step
#: batch vote dispatches a verify instead of a decode chunk only when
#: the EMA-expected emitted tokens per pass exceed ``c x live rows`` —
#: i.e. the pass out-emits the decode steps it displaces.  A window
#: runs at prefill arithmetic intensity, so on TPU ``c`` sits near 1-2;
#: bench.py's ``spec_decode_ab`` reports the measured value per chip so
#: recipe configs can pin it.
DEFAULT_SPEC_VERIFY_COST = 2.0


@dataclasses.dataclass(frozen=True)
class PagedDispatchTable:
    """Context-length thresholds ``cache_mode="auto"`` dispatches on."""

    #: dense cache below, paged block pool at/above (by ``kv_cache_len``)
    paged_min_cache_len: int = DEFAULT_PAGED_MIN_CACHE_LEN
    #: provenance: "builtin-default" | "config" | "bench(...)"
    source: str = "builtin-default"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


#: a paged column within this fraction of dense counts as a win — decode
#: A/B cells carry a few percent of run-to-run noise, and at parity the
#: paged path's capacity/mixed-length advantages break the tie
PARITY_MARGIN = 0.95


def resolve_dispatch_table(
    paged_min_cache_len: Optional[int] = None,
) -> PagedDispatchTable:
    """Build the engine's table from the config field; ``None`` keeps
    the builtin default (so configs only pin what they measured)."""
    if paged_min_cache_len is None:
        return PagedDispatchTable()
    return PagedDispatchTable(
        paged_min_cache_len=int(paged_min_cache_len), source="config"
    )


def spec_break_even_accept_rate(
    verify_cost_over_decode_step: float, max_draft_tokens: int
) -> float:
    """Acceptance rate at which speculative decoding stops paying.

    A verify pass over a ``k+1``-token window emits ``a*k + 1`` tokens
    in expectation (``a`` = acceptance rate) and costs ``c`` plain
    decode steps' worth of device time (``c`` is a hardware measurement:
    the window runs at prefill arithmetic intensity, so ``c`` is near 1
    when decode is weight-read-bound and grows where it is not).  Spec
    wins iff ``(a*k + 1) / c > 1``, i.e. ``a > (c - 1) / k`` — the
    threshold the per-row EMA fallback should sit at.  bench.py's
    ``spec_decode_ab`` reports the measured ``c`` and this derived rate
    so recipe configs can pin ``spec_decode.min_accept_rate`` to what
    the chip actually showed.
    """
    k = max(int(max_draft_tokens), 1)
    rate = (float(verify_cost_over_decode_step) - 1.0) / k
    return min(max(rate, 0.0), 1.0)


def derive_dispatch_table(
    rows: Mapping[int, Mapping[str, Optional[float]]],
) -> PagedDispatchTable:
    """Derive the threshold from a measured decode A/B.

    ``rows`` maps context length -> ``{"dense": tok/s, "paged": tok/s}``
    with ``None`` for cells that could not run (OOM).  The threshold is
    the smallest measured context from which paged wins at EVERY larger
    measured context too (one noisy mid-table cell must not carve a
    dense island out of the paged range).  A dense OOM counts as a paged
    win — capacity is the point.  If paged never wins, the threshold is
    pushed past the measured range (2x the largest context: beyond what
    was measured, capacity arguments take over).
    """
    ctxs = sorted(int(c) for c in rows)
    if not ctxs:
        return PagedDispatchTable(source="bench(empty)")

    def cell(ctx, key):
        v = rows[ctx].get(key)
        return float(v) if isinstance(v, (int, float)) else None

    def paged_wins(ctx):
        dense, paged = cell(ctx, "dense"), cell(ctx, "paged")
        if dense is None:
            return True  # dense OOM: paged is the only option
        if paged is None:
            return False
        return paged >= PARITY_MARGIN * dense

    # smallest ctx such that paged wins at it and at all larger
    paged_thr = None
    for ctx in reversed(ctxs):
        if not paged_wins(ctx):
            break
        paged_thr = ctx
    return PagedDispatchTable(
        paged_min_cache_len=(
            paged_thr if paged_thr is not None else 2 * ctxs[-1]
        ),
        source=f"bench({ctxs[0]}..{ctxs[-1]})",
    )
