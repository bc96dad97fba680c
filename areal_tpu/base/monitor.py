"""Runtime monitoring: device-memory/host-utilization sampling + time marks.

TPU-native rebuild of the reference's monitor
(reference: realhf/base/monitor.py — ``gpu_utilization_monitor`` :266
NVML-sampling thread, ``time_mark``/``parse_time_mark_*`` :43-118 wall-clock
event marks dumped to logs, RolloutStat :37).  Differences by design: TPUs
expose ``device.memory_stats()`` instead of NVML, so the sampler records
HBM bytes-in-use/peak + host RSS/load; kernel-level time attribution comes
from ``jax.profiler.trace`` (wired per-MFC in model_worker) rather than a
trace-file parser, so the CUDAKernelTimeStat machinery has no counterpart.

Time marks are in-memory and exported as plain dicts — the stats tracker /
MetricsLogger fan them out — instead of being grepped back out of logfiles.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional

from areal_tpu.base import logging_

logger = logging_.getLogger("monitor")


@dataclasses.dataclass
class RolloutStat:
    """Rollout accounting (reference: monitor.py:37)."""

    submitted: int = 0
    accepted: int = 0
    running: int = 0

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Time marks
# ---------------------------------------------------------------------------

_marks_lock = threading.Lock()
_marks: Dict[str, List[Dict]] = defaultdict(list)


class time_mark:
    """Context manager recording a named wall-clock interval.

    ``with time_mark("actor_train", rank, step): ...`` — the reference logs
    start/end lines and greps them back (monitor.py:48-116); we keep the
    events in memory and export on demand.
    """

    def __init__(self, name: str, identifier: str = "", step: int = 0):
        self.name = name
        self.identifier = str(identifier)
        self.step = step

    def __enter__(self):
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        with _marks_lock:
            _marks[self.name].append(
                {
                    "identifier": self.identifier,
                    "step": self.step,
                    "start": self._t0,
                    "end": t1,
                    "duration": t1 - self._t0,
                }
            )
        # scrape-side mirror: one histogram series per mark name, so the
        # marks show up at /metrics instead of living log-only
        try:
            from areal_tpu.observability import get_registry

            get_registry().histogram("areal_time_mark_seconds").observe(
                t1 - self._t0, mark=self.name
            )
        except Exception:  # noqa: BLE001 - marks must never raise
            pass
        return False


def get_time_marks(name: Optional[str] = None) -> Dict[str, List[Dict]]:
    with _marks_lock:
        if name is not None:
            return {name: list(_marks.get(name, []))}
        return {k: list(v) for k, v in _marks.items()}


def summary_time_marks() -> Dict[str, float]:
    """Flat {mark/total_s, mark/count, mark/mean_s} gauges for metrics."""
    out: Dict[str, float] = {}
    with _marks_lock:
        for name, events in _marks.items():
            total = sum(e["duration"] for e in events)
            out[f"time_marks/{name}/total_s"] = total
            out[f"time_marks/{name}/count"] = float(len(events))
            out[f"time_marks/{name}/mean_s"] = total / max(1, len(events))
    return out


def clear_time_marks():
    with _marks_lock:
        _marks.clear()


# ---------------------------------------------------------------------------
# Device/host utilization sampling
# ---------------------------------------------------------------------------

#: dense bf16 peak TFLOP/s per chip, keyed by substrings of
#: ``device.device_kind`` ("TPU v5 lite" is how a v5e reports itself).
#: THE table for every MFU denominator in the program, read through
#: :func:`device_peak_flops` (``benchmark/lib/peaks.py`` keeps the
#: yardstick's own copy).  Source: Google Cloud TPU
#: documentation, "System architecture" page of each generation
#: (cloud.google.com/tpu/docs/v5e: 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s; .../v4: 275; .../v5p: 459; .../v6e: 918; .../v3: 123).
PEAK_TFLOPS_BF16 = {
    "v3": 123,
    "v4": 275,
    "v5e": 197,
    "v5 lite": 197,
    "v5p": 459,
    "v6e": 918,
    "v6 lite": 918,
    "trillium": 918,
}


def device_peak_flops(device) -> float:
    """Peak bf16 FLOP/s of one device.  A non-TPU device (the CPU backend
    of the tests) has no published peak: 0.0, and MFU gauges are skipped
    rather than reporting nonsense.  A TPU whose ``device_kind`` is not in
    the table RAISES — an unknown chip is an error, never a default."""
    if getattr(device, "platform", None) != "tpu":
        return 0.0
    kind = getattr(device, "device_kind", "").lower()
    for name, tf in PEAK_TFLOPS_BF16.items():
        if name in kind:
            return tf * 1e12
    raise ValueError(
        f"no published bf16 peak for TPU device_kind {device.device_kind!r}; "
        "add it (with its source) to areal_tpu.base.monitor.PEAK_TFLOPS_BF16"
    )


def _host_stats() -> Dict[str, float]:
    out: Dict[str, float] = {}
    try:
        la1, la5, _ = os.getloadavg()
        out["host/load1"] = la1
        out["host/load5"] = la5
    except OSError:
        pass
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    out["host/rss_gb"] = float(line.split()[1]) / 1e6
                    break
    except OSError:
        pass
    return out


def device_memory_stats() -> Dict[str, float]:
    """Per-device HBM gauges from ``memory_stats()`` (absent on some
    backends — returns {} then)."""
    import jax

    out: Dict[str, float] = {}
    for d in jax.local_devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 - backend-dependent
            pass
        if not stats:
            continue
        key = f"device{d.id}"
        if "bytes_in_use" in stats:
            out[f"{key}/hbm_in_use_gb"] = stats["bytes_in_use"] / 1e9
        if "peak_bytes_in_use" in stats:
            out[f"{key}/hbm_peak_gb"] = stats["peak_bytes_in_use"] / 1e9
        if "bytes_limit" in stats:
            out[f"{key}/hbm_limit_gb"] = stats["bytes_limit"] / 1e9
    return out


class UtilizationMonitor:
    """Background sampler (reference: gpu_utilization_monitor thread :266).

    Samples device + host gauges every ``interval`` seconds into a ring of
    the last ``keep`` snapshots; ``export()`` returns the latest gauges for
    the metrics fan-out."""

    def __init__(self, interval: float = 10.0, keep: int = 360, registry=None):
        self.interval = interval
        self.keep = keep
        self._registry = registry
        self._snapshots: List[Dict[str, float]] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self):
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, name="util-monitor", daemon=True
        )
        self._thread.start()

    def _sample(self):
        snap = {"ts": time.time(), **_host_stats(), **device_memory_stats()}
        with self._lock:
            self._snapshots.append(snap)
            if len(self._snapshots) > self.keep:
                self._snapshots.pop(0)
        self._publish(snap)

    def _publish(self, snap: Dict[str, float]):
        """Mirror the latest sample into the scrape registry (instead of the
        log-only output the sampler used to be).  Metric names are literal
        at the call sites so scripts/check_metric_names.py can audit them."""
        try:
            from areal_tpu.observability import get_registry

            reg = self._registry or get_registry()
            if "host/load1" in snap:
                reg.gauge("areal_host_load1").set(snap["host/load1"])
            if "host/load5" in snap:
                reg.gauge("areal_host_load5").set(snap["host/load5"])
            if "host/rss_gb" in snap:
                reg.gauge("areal_host_rss_gb").set(snap["host/rss_gb"])
            for k, v in snap.items():
                if not k.startswith("device") or "/" not in k:
                    continue
                dev, field = k.split("/", 1)
                if field == "hbm_in_use_gb":
                    reg.gauge("areal_device_hbm_in_use_gb").set(v, device=dev)
                elif field == "hbm_peak_gb":
                    reg.gauge("areal_device_hbm_peak_gb").set(v, device=dev)
                elif field == "hbm_limit_gb":
                    reg.gauge("areal_device_hbm_limit_gb").set(v, device=dev)
            # HBM-ledger reconciliation: the subsystem attributions must
            # sum to <= the allocator's own in-use bytes; the excess
            # publishes as areal_hbm_ledger_drift_gb (0 when honest).
            # Backends without memory_stats (CPU) reconcile vacuously.
            from areal_tpu.observability.hbm_ledger import get_ledger

            in_use_gb = [
                v for k, v in snap.items()
                if k.startswith("device") and k.endswith("/hbm_in_use_gb")
            ]
            get_ledger().reconcile(
                reg,
                int(sum(in_use_gb) * 1e9) if in_use_gb else None,
            )
        except Exception:  # noqa: BLE001 - monitoring must not kill work
            logger.exception("metric registry publish failed")

    def _run(self):
        while not self._stop.wait(self.interval):
            try:
                self._sample()
            except Exception:  # noqa: BLE001 - monitoring must not kill work
                logger.exception("utilization sample failed")

    def export(self) -> Dict[str, float]:
        with self._lock:
            if not self._snapshots:
                return {}
            latest = dict(self._snapshots[-1])
        latest.pop("ts", None)
        return latest

    def history(self) -> List[Dict[str, float]]:
        with self._lock:
            return list(self._snapshots)

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
