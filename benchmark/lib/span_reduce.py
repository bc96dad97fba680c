"""From the traced slice's xplane to what the program's own phase spans
say: self time by span, counts, and which span owns each device idle gap.

The program marks what its threads are doing with
``jax.profiler.TraceAnnotation``s under the prefix ``areal.``
(``areal_tpu/observability/tracing.phase``; names and counts in
``docs/observability.md``, "Phase spans").  They land in the ``/host:CPU``
plane of the same xplane as the device operations, so both share one clock
(``trace_reduce.py``).  That file's ``load()`` keeps device operations and
``bench.`` annotations only; this one reads the file again and keeps the
``areal.`` events PER LINE (one line a thread; go by the line, not by its
name: in a sandbox trace every line is called ``python``) with each
event's ``stats``, which are the span's counts.

Only spans that lie wholly inside the profiler session are in the file: one
that was open when the session started or stopped is not recorded, though
its children are.  An engine step lasts 1.2-1.7 s, nearly all of it one or
two blocked waits, and the slice 3 s: it holds one whole
``areal.gserver.poll``, or none, and both its edges fall inside a wait.
The engine therefore marks where each of its phases begins and where it
has ended with two spans of no length (``areal.phase.begin``,
``areal.phase.end``, each naming the phase), which survive.  ``load()``
puts the part of a cut phase that lies inside the trace back from them
(``with_cut_phases``), so every second a reader books to a wait has a
recorded event behind it, and none is booked where the program marks none.

Readers get ``None`` where there is no xplane (a context without
``work_dir``, a run without ``--trace 1``) or no such span (a program from
before the spans): the metric is then left out of the line.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmark.lib import trace_reduce
from benchmark.lib.trace_reduce import (
    DEVICE_PLANE,
    HOST_PLANE,
    OPS_LINE,
    short_name,
    union_seconds,
)

#: the program's spans, and the benchmark's own annotations (kept only to
#: name an idle gap that no span of the program covers)
PREFIX = ("areal.", "bench.")
POLL = "areal.gserver.poll"
STEP = "areal.engine.step"
BATCH = "areal.train.batch"
#: the engine thread's host bookkeeping: every engine span that is not a
#: blocked wait (``fill.first_token_wait``, ``harvest.wait``) or a transfer
#: (``harvest.fetch``); a weight swap is not part of a step's routine
BOOKKEEPING = (
    STEP,
    "areal.engine.admit",
    "areal.engine.fill.dispatch",
    "areal.engine.fill.activate",
    "areal.engine.ensure_blocks",
    "areal.engine.decode.dispatch",
    "areal.engine.harvest.fold",
)
FIRST_TOKEN_WAIT = "areal.engine.fill.first_token_wait"
#: the generation server's thread: a poll, its parts, the engine's step
SERVER = ("areal.gserver.", "areal.engine.")
#: a poll's parts outside the engine's step
POLL_PARTS = (
    "areal.gserver.serve_api",
    "areal.gserver.apply_commands",
    "areal.gserver.reply",
    "areal.gserver.export_metrics",
)
#: where a phase of the engine begins and where it has ended: spans of no
#: length that carry the phase's name as ``of`` (``tracing.PhaseClock``)
PHASE_BEGIN = "areal.phase.begin"
PHASE_END = "areal.phase.end"
#: threads whose spans say why the DEVICE waits: the ones that feed it
DRIVING = ("areal.engine.", "areal.train.")
NO_OWNER = "none"


class Span(NamedTuple):
    start: float
    end: float
    name: str
    counts: Dict[str, float]


def xplane_of(ctx) -> Optional[str]:
    """The traced slice's file: ``run.py`` keeps it beside the work
    directory (``<out>/work`` -> ``<out>/trace``)."""
    work_dir = getattr(ctx, "work_dir", None)
    if not work_dir:
        return None
    return trace_reduce.find_xplane(
        os.path.join(os.path.dirname(os.path.abspath(work_dir)), "trace")
    )


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """{"lines": [[Span, ...] per host line that holds ``areal.`` (or
    ``bench.``) spans, sorted by start], "devices": {plane: [(start_s,
    end_s, op name)]}}."""
    from jax.profiler import ProfileData

    lines: List[List[Span]] = []
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == HOST_PLANE:
            for ln in plane.lines:
                spans = []
                for e in ln.events:
                    name = e.name
                    if not name.startswith(PREFIX):
                        continue
                    s = float(e.start_ns) * 1e-9
                    spans.append(
                        Span(s, s + float(e.duration_ns) * 1e-9, name,
                             {k: v for k, v in e.stats})
                    )
                if spans:
                    lines.append(sorted(spans, key=lambda s: s[:3]))
        elif plane.name.startswith(DEVICE_PLANE):
            for ln in plane.lines:
                if ln.name != OPS_LINE:
                    continue
                ops = []
                for e in ln.events:
                    s = float(e.start_ns) * 1e-9
                    ops.append(
                        (s, s + float(e.duration_ns) * 1e-9, short_name(e.name))
                    )
                devices[plane.name] = ops
    t0, t1 = extent({"lines": lines, "devices": devices})
    restored = [with_cut_phases(ln, t0, t1) for ln in lines]
    return {
        "lines": [ln for ln, _ in restored],
        "devices": devices,
        "cut_phases": [c for _, cuts in restored for c in cuts],
    }


def with_cut_phases(
    spans: Sequence[Span], t0: float, t1: float
) -> Tuple[List[Span], List[Span]]:
    """(One line's spans without the phases' marks and with a span for
    each phase that an edge of the trace cut, those added spans alone).
    Marks nest as the phases do.  An ``areal.phase.end`` that closes no
    ``areal.phase.begin`` before it: the phase was open when the session
    started, and lasted from ``t0`` (or from ``seconds`` before the mark,
    if that is later) to the mark.  A ``begin`` that no ``end`` closes:
    open when the session stopped, from the mark to ``t1``.  A phase with
    both was recorded whole."""
    kept: List[Span] = []
    cuts: List[Span] = []
    begun: List[Span] = []
    for s in spans:
        if s.name == PHASE_BEGIN:
            begun.append(s)
        elif s.name == PHASE_END:
            if begun and begun[-1].counts["of"] == s.counts["of"]:
                begun.pop()
            else:
                lasted = s.counts.get("seconds")
                start = t0 if lasted is None else max(t0, s.start - lasted)
                cuts.append(Span(start, s.start, s.counts["of"], {}))
        else:
            kept.append(s)
    cuts += [Span(b.end, t1, b.counts["of"], {}) for b in begun]
    return sorted(kept + cuts, key=lambda s: s[:3]), cuts


def spans_of(ctx) -> Optional[dict]:
    path = xplane_of(ctx)
    if path is None:
        return None
    t = load(path)
    if not t["lines"]:
        return None
    report_idle_once(path, t)
    return t


# -- spans ---------------------------------------------------------------


def named(t: dict, name: str) -> List[Span]:
    return [s for ln in t["lines"] for s in ln if s.name == name]


def line_of(t: dict, name: str) -> List[Span]:
    """The line (thread) that holds spans called ``name``; [] if none."""
    for ln in t["lines"]:
        if any(s.name == name for s in ln):
            return ln
    return []


def inside(spans: Sequence[Span], parents: Sequence[Span]) -> List[Span]:
    """The spans that lie wholly inside one of ``parents`` (themselves
    included)."""
    return [
        s for s in spans
        if any(p.start <= s.start and s.end <= p.end for p in parents)
    ]


def self_seconds(spans: Sequence[Span]) -> Dict[str, float]:
    """Seconds by span name on ONE line, a span's children subtracted."""
    return trace_reduce.self_seconds_by_name([s[:3] for s in spans])


def extent(t: dict) -> Tuple[float, float]:
    """From the first to the last thing the trace saw (a device operation
    or an ``areal.`` span): the profiler's session, nearly."""
    edges = [x for ops in t["devices"].values() for o in ops for x in o[:2]]
    edges += [x for ln in t["lines"] for s in ln for x in (s.start, s.end)]
    return min(edges), max(edges)


def engine_thread(t: dict) -> Optional[Tuple[float, Dict[str, float]]]:
    """(seconds of the slice, self seconds by span name) of the generation
    server's thread (of each, where there are several): its
    ``areal.gserver.*`` and ``areal.engine.*`` spans, the engine's cut
    phases as ``load()`` restored them, a server's span whose poll an edge
    cut as a root.  The first is the trace's extent, times the threads.
    What the self seconds leave of it is a cut poll's own time and the
    worker's loop between polls."""
    by_name: Dict[str, float] = {}
    threads = 0
    for ln in t["lines"]:
        spans = [s for s in ln if s.name.startswith(SERVER)]
        if not spans:
            continue
        threads += 1
        for name, sec in self_seconds(spans).items():
            by_name[name] = by_name.get(name, 0.0) + sec
    if not threads:
        return None
    t0, t1 = extent(t)
    return threads * (t1 - t0), by_name


def share_of_engine_thread(ctx, names: Sequence[str]) -> Optional[float]:
    t = spans_of(ctx)
    got = engine_thread(t) if t else None
    if not got or got[0] <= 0:
        return None
    base, by_name = got
    return 100.0 * sum(by_name.get(n, 0.0) for n in names) / base


def mean_ms(ctx, name: str) -> Optional[float]:
    t = spans_of(ctx)
    spans = named(t, name) if t else []
    if not spans:
        return None
    return 1e3 * sum(s.end - s.start for s in spans) / len(spans)


def sum_of_mean_ms(ctx, names: Sequence[str]) -> Optional[float]:
    """The sum over ``names`` of each one's mean span length."""
    means = [mean_ms(ctx, n) for n in names]
    if all(m is None for m in means):
        return None
    return sum(m for m in means if m is not None)


def mean_ms_of_children(ctx, parent: str, children: Sequence[str]) -> Optional[float]:
    """Mean over the ``parent`` spans of the ``children`` inside them."""
    t = spans_of(ctx)
    line = line_of(t, parent) if t else []
    parents = [s for s in line if s.name == parent]
    kids = inside([s for s in line if s.name in children], parents)
    if not parents or not kids:
        return None
    return 1e3 * sum(s.end - s.start for s in kids) / len(parents)


# -- device operations ---------------------------------------------------


def kernel_calls(t: dict, prefix: str) -> Tuple[int, float, int]:
    """(executions, device seconds, chips) of the operations whose name
    starts with ``prefix``, over every chip of the trace."""
    n, sec = 0, 0.0
    for ops in t["devices"].values():
        for s, e, name in ops:
            if name.startswith(prefix):
                n += 1
                sec += e - s
    return n, sec, len(t["devices"])


# -- idle gaps -------------------------------------------------------------


def idle_gaps(t: dict) -> List[Tuple[float, float, float]]:
    """(seconds, start, end) of every interval in which a chip ran no
    operation, between the first and the last thing the trace saw."""
    if not t["devices"]:
        return []
    t0, t1 = extent(t)
    gaps = []
    for ops in t["devices"].values():
        _, merged = union_seconds(ops)
        cuts = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [
            (cuts[i + 1] - cuts[i], cuts[i], cuts[i + 1])
            for i in range(0, len(cuts), 2)
            if cuts[i + 1] > cuts[i]
        ]
    return sorted(gaps, reverse=True)


def gap_owner(t: dict, g0: float, g1: float) -> str:
    """The innermost span over the gap's middle on the threads that feed
    the device (on any thread, in a trace that shows no such thread), else
    ``none``: a manager that polls all the time is over every gap and
    explains none.  A benchmark's ``bench.`` annotation owns a gap only
    where no span of the program is inside it."""
    mid = 0.5 * (g0 + g1)
    feeding = [
        ln for ln in t["lines"] if any(s.name.startswith(DRIVING) for s in ln)
    ]
    over = [
        s for ln in feeding or t["lines"] for s in ln if s.start <= mid <= s.end
    ]
    if not over:
        return NO_OWNER
    return min(over, key=lambda s: s.end - s.start).name


def idle_by_span(t: dict, top: int = 10) -> dict:
    gaps = idle_gaps(t)
    by_owner: Dict[str, float] = {}
    owners = []
    for sec, g0, g1 in gaps:
        owner = gap_owner(t, g0, g1)
        owners.append(owner)
        by_owner[owner] = by_owner.get(owner, 0.0) + sec
    return {
        "idle_s": dict(sorted(by_owner.items(), key=lambda kv: -kv[1])),
        "gaps": len(gaps),
        "longest": [[o, g[0]] for o, g in zip(owners[:top], gaps[:top])],
    }


_reported = set()


def report_idle_once(path: str, t: dict):
    """One ``idle_by_span`` line a traced run, from whichever reader runs
    first, with the engine thread's split beside it and how much of it
    are phases that an edge of the trace cut."""
    if path in _reported:
        return
    _reported.add(path)
    line = {"event": "idle_by_span", **idle_by_span(t)}
    thread = engine_thread(t)
    if thread:
        line["engine_thread_s"], line["engine_thread_self_s"] = thread
        line["cut_phases"] = [[c.name, c.end - c.start] for c in t["cut_phases"]]
    print(json.dumps(line), flush=True)
