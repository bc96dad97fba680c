"""The program's own account of a window: a record a step, read whole.

A traced slice holds one or two engine steps, so what its spans say is the
slice's place in the schedule.  The program therefore keeps one record a
LAP of each thread's ``PhaseClock`` (``areal_tpu/observability/tracing.py``;
``docs/observability.md``, "Step records"): an engine step, a trainer's
batch, each with its ends on ``time.perf_counter()``, its self seconds by
phase and its counts, always, traced or not.  This file reads them.

Beside ``lib/program.py`` it is the only file of the benchmark that imports
the program: ``records_in_process`` takes the records from
``tracing.step_logs()`` (the drivers free engine and trainer before the
readers run; the clocks stay readable).  A program from before the records
has no such function, and every reader returns ``None``.

**On the trace's clock.**  Each ``areal.phase.begin`` / ``areal.phase.end``
mark in a capture carries ``t``, the host clock at the mark: its start on
the profiler's clock gives the offset, and the capture's first and last
event (``span_reduce.extent``) a place on the host's clock.

**The stretch.**  The slice sits in the window's middle, so the records
from ``(seconds - trace_seconds) / 2 - STARTUP_S`` before the capture's
first event to as long after its last lie inside the window however late
(up to ``STARTUP_S``) the profiler started: 46 s of a 50 s window.  A lap
counts for the part of it inside the stretch.  Fewer than ``MIN_LAPS`` laps
there, no capture or no mark with ``t``: ``None``.

By hand, for any cell (the six whose metric sets accepted tests hold fixed)
and any run, from the file the generation server writes when it exits:

    python3 -m benchmark.lib.step_log <steps.jsonl> [<xplane.pb>] \\
        [--seconds 50] [--trace-seconds 3]
"""

from __future__ import annotations

import functools
import json
import statistics
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmark.lib import span_reduce, trace_reduce

MARKS = (span_reduce.PHASE_BEGIN, span_reduce.PHASE_END)
#: the profiler's start-up the stretch leaves room for, at either end
STARTUP_S = 2.0
MIN_LAPS = 8
QUEUE_EMPTY = "queue_empty"
#: the decode program, whatever the stack (``jit_paged_decode_chunk``,
#: ``jit_hybrid_decode_chunk``, the dense ``jit__decode_chunk``)
DECODE_PROGRAM = "decode_chunk"


class Capture(NamedTuple):
    """A capture's first and last event on the HOST's clock, and what was
    added to the profiler's clock to get there."""

    t0: float
    t1: float
    offset: float
    marks: int


# -- the capture's place on the host clock --------------------------------


@functools.lru_cache(maxsize=2)
def capture_of(xplane: str) -> Optional[Capture]:
    """None where no mark of the capture carries ``t`` (a program from
    before PR 51, or a capture no ``PhaseClock`` thread ran in)."""
    from jax.profiler import ProfileData

    offsets = []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name in MARKS:
                    t = dict(e.stats).get("t")
                    if isinstance(t, float):
                        offsets.append(t - float(e.start_ns) * 1e-9)
    if not offsets:
        return None
    offset = statistics.median(offsets)
    e0, e1 = span_reduce.extent(span_reduce.load(xplane))
    return Capture(e0 + offset, e1 + offset, offset, len(offsets))


def stretch_of(
    capture: Capture, seconds: float, trace_seconds: float
) -> Tuple[float, float]:
    pad = max(0.0, (seconds - trace_seconds) / 2 - STARTUP_S)
    return capture.t0 - pad, capture.t1 + pad


def decode_program_s(xplane: str) -> Optional[Tuple[int, float]]:
    """(executions, device seconds a chip) of the decode program in the
    capture (``XLA Modules``)."""
    devices = trace_reduce.load(xplane)["devices"].values()
    runs = [
        e - s
        for dev in devices
        for s, e, name in dev["modules"]
        if trace_reduce.strip_hash(name).endswith(DECODE_PROGRAM)
    ]
    return (len(runs), sum(runs) / len(devices)) if runs else None


# -- the records ------------------------------------------------------------


def records_in_process(log: str) -> Optional[Tuple[dict, List[dict]]]:
    """(header, records) of the newest clock whose log name is ``log`` or
    starts with ``log + "."``; None where the program keeps none."""
    from areal_tpu.observability import tracing

    step_logs = getattr(tracing, "step_logs", None)
    if step_logs is None:
        return None
    for name, clock in step_logs().items():
        if name == log or name.startswith(log + "."):
            return clock.header(), clock.records()
    return None


def read_file(path: str) -> Tuple[dict, List[dict]]:
    with open(path) as f:
        header, *records = [json.loads(ln) for ln in f if ln.strip()]
    return header, records


def cut(
    records: Sequence[dict], a: float, b: float
) -> List[Tuple[float, dict]]:
    """(weight, record) of the laps that overlap ``[a, b]``: the share of
    the lap, ``t0`` to ``t1``, that lies inside."""
    out = []
    for r in records:
        inside = min(r["t1"], b) - max(r["t0"], a)
        if inside > 0:
            out.append((inside / (r["t1"] - r["t0"]), r))
    return out


def _largest_phase(r: dict) -> List:
    if not r["self_s"]:
        return ["none", 0.0]
    name = max(r["self_s"], key=r["self_s"].get)
    return [name, r["self_s"][name]]


def _common(laps: List[Tuple[float, dict]], a: float, b: float) -> dict:
    by_phase: Dict[str, float] = {}
    for w, r in laps:
        for name, sec in r["self_s"].items():
            by_phase[name] = by_phase.get(name, 0.0) + w * sec
    longest = sorted(
        laps, key=lambda wr: wr[1]["t1"] - wr[1]["t0"], reverse=True
    )[:10]
    return {
        "stretch_s": b - a,
        "laps": len(laps),
        # laps in which nothing moved, folded into those records
        "quiet_laps": sum(r.get("quiet_laps", 0) for _, r in laps),
        "lap_s_covered": sum(w * (r["t1"] - r["t0"]) for w, r in laps),
        "self_s_by_phase": dict(sorted(by_phase.items(), key=lambda kv: -kv[1])),
        "compiled": [
            [r["seq"], r["compiles"], r["compile_s"]]
            for _, r in laps if r["compiles"]
        ],
        "longest_laps": [
            [r["seq"], r["t1"] - r["t0"], *_largest_phase(r)] for _, r in longest
        ],
    }


def engine_account(
    header: dict, records: Sequence[dict], a: float, b: float,
    decode: Optional[Tuple[int, float]] = None,
    trace: Optional[dict] = None,
) -> Optional[dict]:
    """The engine's records over ``[a, b]``; ``decode`` and ``trace`` are
    the capture's (``decode_program_s``, ``trace_reduce.reduce_trace``),
    for the two figures of the device below.  The four ``slots_*`` shares
    are over the steps that dispatched a decode chunk, a chunk each: of the
    ``max_batch`` slots a chunk computes, those decoding, those whose
    prompt was still prefilling, and those without a live row (empty, or
    held by a PARKED row, which gives its slot to any admission): nobody
    asked for them where the step's admission ended on an empty queue, a
    queued request could not take them where it stopped on anything else.

    ``engine_thread_busy_share``: the self seconds of the phases
    ``engine_bookkeeping_share`` sums over a slice
    (``span_reduce.BOOKKEEPING``, ONE list), over the stretch's seconds.
    It is the time NOT IN A NAMED WAIT, not the host's own work: a phase
    of that list that blocks behind the device (a dispatch into a full
    queue, a fold that touches a device array) keeps the block, and
    ``engine_thread_busy_share_long_laps`` is the part of it in laps whose
    busy seconds are over twice the stretch's median lap's.

    The device's time that is not decode chunks, fills first, two ways and
    neither a metric: ``fill_busy_share_slice`` is the capture's alone, 1 -
    the decode program's device seconds / the slice's busy seconds (a
    slice: its place in the schedule); ``fill_busy_share_estimate``
    stretches it over ``[a, b]`` by ASSUMING that a decode execution
    outside the slice takes the slice's mean and that the device is as
    busy outside as inside, 1 - ``decode_chunks`` x that mean / (``b - a``
    x the slice's busy share): any idle outside the slice is booked to the
    fills (it read 8-10 where the slice's programs said 4.3: PERF.md,
    section 7)."""
    laps = cut(records, a, b)
    if len(laps) < MIN_LAPS:
        return None
    out = _common(laps, a, b)
    slots = float(header["max_batch"])
    chunks = sum(w * r["decode_chunks"] for w, r in laps)

    def share(of) -> Optional[float]:
        if chunks <= 0:
            return None
        return 100.0 * sum(
            w * r["decode_chunks"] * of(r) for w, r in laps
        ) / (chunks * slots)

    def idle(r):
        return r["slots_empty"] + r["slots_parked"]

    def unasked(r):
        return idle(r) if r["admit_stopped_by"] == QUEUE_EMPTY else 0

    by_stop: Dict[str, List[float]] = {}
    for w, r in laps:
        if r["decode_chunks"]:
            row = by_stop.setdefault(r["admit_stopped_by"], [0.0] * 5)
            n = w * r["decode_chunks"]
            for i, key in enumerate(
                ("slots_decoding", "slots_filling", "slots_parked", "slots_empty")
            ):
                row[i + 1] += n * r[key]
            row[0] += n

    def busy_s(r):
        return sum(r["self_s"].get(n, 0.0) for n in span_reduce.BOOKKEEPING)

    busy_median = statistics.median(busy_s(r) for _, r in laps)
    decode_mean_s = decode[1] / decode[0] if decode else None
    traced = decode and trace and trace.get("busy_s") and trace.get("window_s")
    total = {
        key: sum(w * r[key] for w, r in laps)
        for key in (
            "tokens_emitted", "rows_admitted", "rows_finished",
            "rows_preempted", "decode_rows", "fill_programs", "fill_tokens",
            "fill_slots", "late_joins",
        )
    }
    out.update(
        decode_chunks=chunks,
        slots_decoding_share=share(lambda r: r["slots_decoding"]),
        slots_filling_share=share(lambda r: r["slots_filling"]),
        slots_unrequested_share=share(unasked),
        slots_blocked_share=share(lambda r: idle(r) - unasked(r)),
        # [chunks, then the mean slots decoding, filling, parked, empty]
        slots_by_admit_stopped_by={
            stop: [row[0]] + [x / row[0] for x in row[1:]]
            for stop, row in sorted(by_stop.items())
        },
        engine_thread_busy_share=100.0 * sum(
            w * busy_s(r) for w, r in laps
        ) / (b - a),
        engine_thread_busy_share_long_laps=100.0 * sum(
            w * busy_s(r) for w, r in laps if busy_s(r) > 2 * busy_median
        ) / (b - a),
        decode_rows_dispatched_mean=(
            total["decode_rows"] / chunks if chunks > 0 else None
        ),
        fill_busy_share_slice=(
            100.0 * (1.0 - decode[1] / trace["busy_s"]) if traced else None
        ),
        fill_busy_share_estimate=(
            100.0 * (1.0 - chunks * decode_mean_s * trace["window_s"]
                     / ((b - a) * trace["busy_s"]))
            if traced else None
        ),
        decode_program_mean_s=decode_mean_s,
        versions=sorted({r["version"] for _, r in laps}),
        **total,
    )
    return out


def train_account(
    header: dict, records: Sequence[dict], a: float, b: float
) -> Optional[dict]:
    """The trainer's records over ``[a, b]``.  ``between``: a batch's ``t0``
    less the one before's ``t1``, both inside.  A lap here runs from a
    batch's begin to the next one's; ``stalled`` are those longer than
    twice the stretch's median, for the part of them inside."""
    laps = cut(records, a, b)
    if len(laps) < MIN_LAPS:
        return None
    out = _common(laps, a, b)
    rs = [r for _, r in laps]
    # a batch and the next one, wherever a part of begin-to-begin is inside
    pairs = [
        (p, r) for p, r in zip(records, records[1:])
        if r["seq"] == p["seq"] + 1 and p["t0"] < b and r["t0"] > a
    ]
    gaps = [r["t0"] - p["t1"] for p, r in pairs if a <= p["t1"] and r["t0"] <= b]
    whole = [(r["t0"] - p["t0"], p, r) for p, r in pairs]
    median = statistics.median(d for d, _, _ in whole)
    stalled = [x for x in whole if x[0] > 2 * median]
    out.update(
        batches=len(rs),
        between_batches_ms=1e3 * sum(gaps) / len(gaps) if gaps else None,
        between_batches_max_ms=1e3 * max(gaps) if gaps else None,
        lap_median_s=median,
        step_stall_share=100.0 * sum(
            max(0.0, min(r["t0"], b) - max(p["t0"], a)) for _, p, r in stalled
        ) / (b - a),
        # [seq, seconds begin to next begin, its largest phase or the time
        # after it, that one's seconds]
        stalled=[
            [p["seq"], d, *max(
                _largest_phase(p), ["between", r["t0"] - p["t1"]],
                key=lambda kv: kv[1],
            )]
            for d, p, r in stalled
        ],
        real_tokens=sum(w * r["real_tokens"] for w, r in laps),
        padded_slots=sum(w * r["padded_slots"] for w, r in laps),
        versions=[rs[0]["version"], rs[-1]["version"]],
    )
    return out


# -- for the readers under layer_metrics/ -----------------------------------

_accounts: Dict[Tuple[str, str], Optional[dict]] = {}


def account(ctx, log: str) -> Optional[dict]:
    """The account of this run's stretch from the records of the clock
    ``log`` (``engine`` or ``train``), or None; one ``step_log`` line a run
    with all of it, the stretch's ends beside the capture's on the host's
    clock (``time.perf_counter()``: CLOCK_MONOTONIC on Linux, one clock
    for every process of a machine)."""
    xplane = span_reduce.xplane_of(ctx)
    if xplane is None:
        return None
    if (xplane, log) in _accounts:
        return _accounts[xplane, log]
    out = _accounts[xplane, log] = _account(ctx, xplane, log)
    if out is not None:
        print(json.dumps({"event": "step_log", "log": log, **out}), flush=True)
    return out


def _account(ctx, xplane: str, log: str) -> Optional[dict]:
    got = records_in_process(log)
    capture = capture_of(xplane) if got else None
    if capture is None:
        return None
    header, records = got
    seconds = float(ctx.seconds)
    length = min(float(ctx.traffic["trace_seconds"]), seconds / 2)
    a, b = stretch_of(capture, seconds, length)
    if log == "engine":
        out = engine_account(
            header, records, a, b, decode_program_s(xplane),
            getattr(ctx, "trace", None),
        )
    else:
        out = train_account(header, records, a, b)
    if out is None:
        return None
    out.update(
        stretch=[a, b], capture=[capture.t0, capture.t1],
        marks=capture.marks, dropped=header["dropped"],
    )
    return out


def metric(ctx, log: str, key: str) -> Optional[float]:
    got = account(ctx, log)
    return None if got is None else got.get(key)


# -- by hand ------------------------------------------------------------------


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("steps", help="steps.<worker>.jsonl of a run")
    p.add_argument("xplane", nargs="?", help="the run's capture (*.xplane.pb)")
    p.add_argument("--seconds", type=float, default=50.0)
    p.add_argument("--trace-seconds", type=float, default=3.0)
    args = p.parse_args(argv)
    header, records = read_file(args.steps)
    if not records:
        print(json.dumps({"header": header, "records": 0}))
        return 1
    a, b = records[0]["t0"], records[-1]["t1"]
    decode = trace = capture = None
    if args.xplane:
        capture = capture_of(args.xplane)
        if capture is None:
            print("no mark with a host clock in the capture", file=sys.stderr)
            return 1
        a, b = stretch_of(capture, args.seconds, args.trace_seconds)
        decode = decode_program_s(args.xplane)
        trace = trace_reduce.reduce_trace(args.xplane)
    if header["lap"].startswith("areal.train."):
        out = train_account(header, records, a, b)
    else:
        out = engine_account(header, records, a, b, decode, trace)
    print(json.dumps({"header": header}))
    print(json.dumps({
        "stretch": [a, b],
        "capture": list(capture[:2]) if capture else None,
        "records": [records[0]["t0"], records[-1]["t1"]],
    }))
    if out is None:
        print(f"fewer than {MIN_LAPS} laps in the stretch", file=sys.stderr)
        return 1
    for key, value in out.items():
        print(json.dumps({key: value}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
