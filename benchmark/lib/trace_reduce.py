"""From a profiler trace (``*.xplane.pb``) to the few numbers the output
needs: seconds in which an operation ran on the device, seconds by
operation name, and the longest idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a v5e trace
looks like (looked at by hand, PR 23): one plane per chip named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
operation, nested where an operation contains others (a ``while`` spans
its body's operations); an event's name is the instruction's whole HLO
text, which starts with its name (``%fusion.252 = ...``; a Mosaic kernel
is a custom call named after the kernel function,
``%paged_flash_attention.7 = ...``); line ``XLA Modules`` holds one event
per executed program (``jit_paged_decode_chunk(<hash>)``).  Host
threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` is an event there under its own name.  All
planes share one clock, in nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[float, float, str]  # start_s, end_s, name


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return found[-1] if found else None


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO text (``%fusion.12 =
    bf16[...] fusion(...)``): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        s = float(e.start_ns) * 1e-9
        out.append((s, s + float(e.duration_ns) * 1e-9, short_name(e.name)))
    return out


def load(path: str, annotation_prefix: str = "bench.") -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "annotations": [...]} — only what the reduction reads."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    annotations: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices[plane.name] = {
                    "ops": _events(lines[OPS_LINE]),
                    "modules": _events(lines[MODULES_LINE])
                    if MODULES_LINE in lines
                    else [],
                }
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                annotations += [
                    ev for ev in _events(ln) if ev[2].startswith(annotation_prefix)
                ]
    return {"devices": devices, "annotations": sorted(annotations)}


def union_seconds(events: Sequence[Event]) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of the events' intervals, and the merged
    intervals themselves."""
    merged: List[List[float]] = []
    for s, e, _ in sorted(events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def self_seconds_by_name(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds by event name, an enclosing event (a ``while`` around its
    body) counted only for the time none of its children covers."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, self_seconds]

    def close(upto: float):
        while stack and stack[-1][0] <= upto:
            end, name, self_s = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_s, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


_HASH = re.compile(r"\(\d+\)$")


def strip_hash(name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``: the hash changes with every
    change to the program, the function's name does not."""
    return _HASH.sub("", name)


def reduce_trace(path: str, default_gap_owner: str = "host", top: int = 10) -> dict:
    """The traced slice reduced.  ``window_s`` runs from the first to the
    last thing the trace saw (a device operation or a benchmark
    annotation); ``busy_s`` is the mean over the chips of the union of
    their operations' intervals."""
    t = load(path)
    devs = t["devices"]
    if not devs:
        return {}
    all_ev = [ev for d in devs.values() for ev in d["ops"]] + t["annotations"]
    if not all_ev:
        return {}
    t0 = min(ev[0] for ev in all_ev)
    t1 = max(ev[1] for ev in all_ev)
    busy, by_name, by_module, gaps = [], {}, {}, []
    for d in devs.values():
        b, merged = union_seconds(d["ops"])
        busy.append(b)
        for name, s in self_seconds_by_name(d["ops"]).items():
            by_name[name] = by_name.get(name, 0.0) + s / len(devs)
        for s, e, name in d["modules"]:
            name = strip_hash(name)
            by_module[name] = by_module.get(name, 0.0) + (e - s) / len(devs)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [
            (edges[i + 1] - edges[i], edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
    gaps.sort(reverse=True)

    def owner(g0: float, g1: float) -> str:
        mid = 0.5 * (g0 + g1)
        inner = [a for a in t["annotations"] if a[0] <= mid <= a[1]]
        # the innermost annotation around the gap's middle
        return min(inner, key=lambda a: a[1] - a[0])[2] if inner else default_gap_owner

    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "window_s": t1 - t0,
        "busy_s": sum(busy) / len(busy),
        "chips": len(devs),
        "op_seconds": by_name,
        "module_seconds": by_module,
        "device_ops": [[n, s] for n, s in ranked[:top]],
        "idle_gaps": [[owner(g0, g1), g] for g, g0, g1 in gaps[:top]],
    }


def seconds_matching(op_seconds: Dict[str, float], pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(s for n, s in op_seconds.items() if rx.search(n))
