"""From the traced slice's xplane to the DEVICE's time by region of the
step programs and by pass.

The program names the parts of its step programs with
``jax.named_scope``s under the prefix ``areal.``
(``areal_tpu/observability/tracing.region``; names in
``docs/observability.md``, "Device regions").  A scope is metadata of the
lowered program: the compiler carries each operation's scope path through
to the executable, and the profiler writes it into the trace as the stat
``tf_op`` of the operation's event METADATA
(``jit(train_step)/transpose(jvp(areal.mlp))/.../dot_general:``), beside
the compiler's own ``flops`` and ``bytes_accessed`` of the operation and
its ``program_id``.  A fusion carries its root's path; an operation the
compiler made (a copy, a loop it rebuilt) carries none.

The file is read twice.  The events and their times with
``jax.profiler.ProfileData``, as ``trace_reduce.load`` does (planes
``/device:TPU:<n>``, lines ``XLA Ops`` and ``XLA Modules``).
``ProfileData`` does not hand the metadata's stats out, so those come from
a reader of the protobuf wire format below, which walks only each device
plane's ``event_metadata`` and ``stat_metadata`` maps and steps over the
lines by their length: its cost follows the number of distinct
instructions, not of events.  The two are joined by (plane, event name =
the instruction's whole HLO text).

What is reduced: SELF seconds (an enclosing ``while`` counts only what its
body does not cover: ``trace_reduce``'s rule) by (program, region, pass).
The region is the innermost ``areal.`` component of the path; the pass is
read from what jax writes around it (``transpose(`` = backward,
``rematted_computation`` = recomputed forward, else forward).  Two bins
have no region and are kept apart: ``no_op_name`` (no ``tf_op`` at all:
the compiler's own copies and loops) and ``unscoped`` (a ``tf_op`` with no
``areal.`` component: a hole in the program's regions).

    python3 -m benchmark.lib.region_reduce <file.xplane.pb>

prints the table for any capture (a benchmark's ``out/trace``, a
worker's ``GET /profile?seconds=N``).

Readers get ``None`` where there is no xplane (a context without
``work_dir``, a run without ``--trace 1``) or where most of the device's
time carries no region (a program from before the regions).
"""

from __future__ import annotations

import bisect
import functools
import json
import re
import struct
import sys
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from benchmark.lib import span_reduce
from benchmark.lib.trace_reduce import (
    DEVICE_PLANE,
    MODULES_LINE,
    OPS_LINE,
    short_name,
    strip_hash,
    union_seconds,
)

NO_OP_NAME = "no_op_name"
UNSCOPED = "unscoped"
UNNAMED = (NO_OP_NAME, UNSCOPED)
FORWARD, BACKWARD, REMAT = "forward", "backward", "rematted_computation"
#: the operation's stats this file reads from its event metadata
STATS = ("tf_op", "flops", "bytes_accessed", "program_id")

_REGION = re.compile(r"areal(?:\.[a-z0-9_]+)+")


def region_of(tf_op: Optional[str]) -> str:
    """The innermost ``areal.`` component of a scope path (it may stand
    inside ``jvp(...)`` / ``transpose(jvp(...))``)."""
    if not tf_op:
        return NO_OP_NAME
    found = _REGION.findall(tf_op)
    return found[-1] if found else UNSCOPED


def pass_of(tf_op: Optional[str]) -> str:
    if not tf_op:
        return FORWARD
    if REMAT in tf_op:
        return REMAT
    return BACKWARD if "transpose(" in tf_op else FORWARD


# -- the protobuf wire format, as far as an XSpace needs it ------------------
#
# XSpace {1: planes}; XPlane {2: name, 3: lines, 4: event_metadata map,
# 5: stat_metadata map}; a map entry {1: key, 2: value}; XEventMetadata
# {1: id, 2: name, 5: stats}; XStatMetadata {1: id, 2: name}; XStat
# {1: metadata_id, 2: double, 3: uint64, 4: int64, 5: str, 6: bytes,
# 7: ref (a stat metadata's id, whose NAME is the value)}.


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, int, int, int]]:
    """``(field number, wire type, a, b)`` of one message's fields: a
    varint's value in ``a``; a length-delimited field's extent ``[a, b)``
    (its bytes are not touched); a fixed field's offset in ``a``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, wire, v, 0
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, wire, i, i + n
            i += n
        elif wire == 1:
            yield key >> 3, wire, i, 0
            i += 8
        elif wire == 5:
            yield key >> 3, wire, i, 0
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _map_value(buf: bytes, a: int, b: int) -> Tuple[int, int]:
    """The extent of a map entry's value (field 2)."""
    for no, wire, x, y in _fields(buf, a, b):
        if no == 2 and wire == 2:
            return x, y
    return a, a


def _stat(buf: bytes, a: int, b: int, stat_names: Dict[int, str]):
    """``(the stat's name, its value)`` of one XStat."""
    name, value = None, None
    for no, wire, x, y in _fields(buf, a, b):
        if no == 1:
            name = stat_names.get(x)
        elif no == 2:
            value = struct.unpack_from("<d", buf, x)[0]
        elif no == 3:
            value = x
        elif no == 4:
            value = x - (1 << 64) if x >> 63 else x
        elif no in (5, 6):
            value = buf[x:y].decode("utf-8", "replace")
        elif no == 7:
            value = stat_names.get(x, "")
    return name, value


def read_op_metadata(path: str) -> Dict[str, Dict[str, dict]]:
    """{device plane: {event name (the instruction's whole HLO text):
    {stat: value} of :data:`STATS`}}."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, Dict[str, dict]] = {}
    for no, wire, p0, p1 in _fields(buf, 0, len(buf)):
        if no != 1 or wire != 2:
            continue
        name, events, stats = "", [], []
        for fno, fwire, a, b in _fields(buf, p0, p1):
            if fno == 2:
                name = buf[a:b].decode()
            elif fno == 4:
                events.append((a, b))
            elif fno == 5:
                stats.append((a, b))
        if not name.startswith(DEVICE_PLANE):
            continue
        stat_names: Dict[int, str] = {}
        for a, b in stats:
            sid, sname = 0, ""
            for fno, _, x, y in _fields(buf, *_map_value(buf, a, b)):
                if fno == 1:
                    sid = x
                elif fno == 2:
                    sname = buf[x:y].decode("utf-8", "replace")
            stat_names[sid] = sname
        ops: Dict[str, dict] = {}
        for a, b in events:
            ename, found = "", {}
            for fno, _, x, y in _fields(buf, *_map_value(buf, a, b)):
                if fno == 2:
                    ename = buf[x:y].decode("utf-8", "replace")
                elif fno == 5:
                    k, v = _stat(buf, x, y, stat_names)
                    if k in STATS:
                        found[k] = v
            ops[ename] = found
        out[name] = ops
    return out


# -- the reduction -------------------------------------------------------------


def _line_events(line) -> List[Tuple[int, int, str]]:
    """``(start, end, name)`` in WHOLE nanoseconds, as ``ProfileData``
    hands them out: an operation that starts where the one before it ended
    must not look nested in it, which in float seconds it does one time in
    forty (``s + d`` rounds above the next ``s``), and then an enclosing
    ``while`` keeps that operation's time as its own."""
    out = []
    for e in line.events:
        s = int(e.start_ns)
        out.append((s, s + int(e.duration_ns), e.name))
    return out


def _self_time(events: Sequence[Tuple[int, int, str]]):
    """``(name, self time, whether nothing ran inside it)`` of every
    event, in the events' own unit: ``trace_reduce.self_seconds_by_name``'s
    rule, an event at a time."""
    out, stack = [], []  # stack: [end, name, self_s, leaf]

    def close(upto: float):
        while stack and stack[-1][0] <= upto:
            _, name, self_s, leaf = stack.pop()
            out.append((name, max(self_s, 0), leaf))

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
            stack[-1][3] = False
        stack.append([e, name, e - s, True])
    close(float("inf"))
    return out


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """{"seconds": {(program, region, pass): self seconds, mean over the
    chips}, "unnamed": {(bin, operation): [seconds, tf_op]}, "flops" /
    "bytes": {region: the compiler's count summed over the executions of
    its innermost operations}, "self_s": all of ``seconds``, "busy_s": the
    union of the operations' intervals, "cost": what reading cost}."""
    from jax.profiler import ProfileData

    t_start = time.perf_counter()
    meta = read_op_metadata(path)
    t_meta = time.perf_counter()
    seconds: Dict[Tuple[str, str, str], float] = {}
    unnamed: Dict[Tuple[str, str], list] = {}
    flops: Dict[str, float] = {}
    nbytes: Dict[str, float] = {}
    busy, events_n = [], 0
    planes = [
        p for p in ProfileData.from_file(path).planes
        if p.name.startswith(DEVICE_PLANE)
    ]
    chips = 0
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        chips += 1
        ops = _line_events(lines[OPS_LINE])
        modules = sorted(
            _line_events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        )
        starts = [m[0] for m in modules]
        events_n += len(ops)
        busy.append(union_seconds(ops)[0] * 1e-9)
        of_op = meta.get(plane.name, {})
        keyed = []
        for s, e, name in ops:
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < modules[i][1]
            program = strip_hash(modules[i][2]) if inside else "none"
            keyed.append((s, e, (program, name)))
        for (program, name), self_ns, leaf in _self_time(keyed):
            m = of_op.get(name, {})
            tf_op = m.get("tf_op") or None
            self_s = self_ns * 1e-9
            region = region_of(tf_op)
            key = (program, region, pass_of(tf_op))
            seconds[key] = seconds.get(key, 0.0) + self_s
            if region in UNNAMED:
                op = unnamed.setdefault(
                    (region, short_name(name)), [0.0, tf_op or ""]
                )
                op[0] += self_s
            if leaf:
                flops[region] = flops.get(region, 0.0) + (m.get("flops") or 0)
                nbytes[region] = nbytes.get(region, 0.0) + (
                    m.get("bytes_accessed") or 0
                )
    n = max(chips, 1)
    t_end = time.perf_counter()
    return {
        "seconds": {k: v / n for k, v in seconds.items()},
        "unnamed": {k: [v[0] / n, v[1]] for k, v in unnamed.items()},
        "flops": {k: v / n for k, v in flops.items()},
        "bytes": {k: v / n for k, v in nbytes.items()},
        "self_s": sum(seconds.values()) / n,
        "busy_s": sum(busy) / n if busy else 0.0,
        "chips": chips,
        "cost": {
            "metadata_s": t_meta - t_start,
            "events_s": t_end - t_meta,
            "events": events_n,
            "instructions": sum(len(v) for v in meta.values()),
        },
    }


def _matches(value: str, wanted: Optional[Sequence[str]]) -> bool:
    """Whether ``value`` is one of ``wanted`` or lies under one
    (``areal.moe`` takes ``areal.moe.route``); everything, for None."""
    return wanted is None or any(
        value == w or value.startswith(w + ".") for w in wanted
    )


def seconds_of(t: dict, regions=None, passes=None, programs=None) -> float:
    return sum(
        s for (program, region, pas), s in t["seconds"].items()
        if _matches(region, regions)
        and (passes is None or pas in passes)
        and (programs is None or program in programs)
    )


def has_regions(t: dict) -> bool:
    """Whether the trace is of programs that name their regions: MOST of
    the device's time lies in one.  Not "any of it": the compile cache
    keys a program without its metadata, so a run of a program from before
    the regions can be handed a small helper compiled after them (the
    parent's `_sample_rows` was, PR 38), and its shares would describe the
    cache."""
    named = seconds_of(t) - seconds_of(t, regions=UNNAMED)
    return named > seconds_of(t, regions=UNNAMED)


def regions_of(ctx) -> Optional[dict]:
    """The traced run's reduction, or None: no xplane, or programs from
    before the regions (:func:`has_regions`)."""
    path = span_reduce.xplane_of(ctx)
    if path is None:
        return None
    t = load(path)
    if not has_regions(t):
        return None
    report_once(path, t)
    return t


def share(ctx, regions=None, passes=None, programs=None) -> Optional[float]:
    """% of the device's busy time (``ctx.trace["busy_s"]``) that the
    chosen regions, passes and programs take."""
    t = regions_of(ctx)
    busy = (getattr(ctx, "trace", None) or {}).get("busy_s", 0.0)
    if t is None or busy <= 0:
        return None
    return 100.0 * seconds_of(t, regions, passes, programs) / busy


def table(t: dict, top: int = 5) -> dict:
    """What the ``device_by_region`` line carries."""
    by_program: Dict[str, Dict[str, Dict[str, float]]] = {}
    by_region: Dict[str, float] = {}
    for (program, region, pas), s in sorted(t["seconds"].items()):
        by_program.setdefault(program, {}).setdefault(region, {})[pas] = s
        by_region[region] = by_region.get(region, 0.0) + s
    largest = {}
    for which in UNNAMED:
        ops = sorted(
            ((op, s, tf_op) for (b, op), (s, tf_op) in t["unnamed"].items()
             if b == which),
            key=lambda x: -x[1],
        )
        largest[which] = [[op, s, tf_op] for op, s, tf_op in ops[:top]]
    return {
        "busy_s": t["busy_s"],
        "self_s": t["self_s"],
        "chips": t["chips"],
        "seconds": by_program,
        "region_s": dict(sorted(by_region.items(), key=lambda kv: -kv[1])),
        "largest_unnamed": largest,
        "compiler_flops": t["flops"],
        "compiler_bytes": t["bytes"],
        "cost": t["cost"],
    }


_reported = set()


def report_once(path: str, t: dict):
    """One ``device_by_region`` line a traced run, from whichever reader
    runs first (as ``span_reduce.report_idle_once``)."""
    if path in _reported:
        return
    _reported.add(path)
    print(json.dumps({"event": "device_by_region", **table(t)}), flush=True)


def format_table(t: dict) -> str:
    """The table as text: a row a (program, region), a column a pass, in
    seconds and % of busy time."""
    tb = table(t)
    busy = tb["busy_s"] or 1.0
    rows = [
        f"busy {tb['busy_s']:.6f} s, self {tb['self_s']:.6f} s, "
        f"{tb['chips']} chip(s); reading took "
        f"{tb['cost']['metadata_s'] + tb['cost']['events_s']:.3f} s "
        f"({tb['cost']['instructions']} instructions, "
        f"{tb['cost']['events']} events)",
        f"{'program':34} {'region':18} {'forward':>10} {'backward':>10} "
        f"{'remat':>10} {'% busy':>7}",
    ]
    for program, regions in tb["seconds"].items():
        for region, passes in sorted(
            regions.items(), key=lambda kv: -sum(kv[1].values())
        ):
            cells = [passes.get(p, 0.0) for p in (FORWARD, BACKWARD, REMAT)]
            rows.append(
                f"{program[:34]:34} {region:18} "
                + " ".join(f"{c:10.6f}" for c in cells)
                + f" {100.0 * sum(cells) / busy:7.2f}"
            )
    rows.append("by region, % of busy time, compiler TFLOP/s and GB/s:")
    for region, s in tb["region_s"].items():
        fl = tb["compiler_flops"].get(region, 0.0)
        by = tb["compiler_bytes"].get(region, 0.0)
        rate = f"{fl / s / 1e12:8.2f} {by / s / 1e9:8.1f}" if s > 0 else ""
        rows.append(f"  {region:18} {s:10.6f} {100.0 * s / busy:7.2f} {rate}")
    for which, ops in tb["largest_unnamed"].items():
        for op, s, tf_op in ops:
            rows.append(f"  {which}: {op} {s:.6f} s {tf_op}")
    return "\n".join(rows)


if __name__ == "__main__":
    for arg in sys.argv[1:]:
        print(format_table(load(arg)))
