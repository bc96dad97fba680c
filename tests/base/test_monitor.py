"""Monitor: time marks, utilization sampling, rollout stat (reference:
realhf/base/monitor.py time_mark/parse_time_mark_* and the NVML sampler)."""

import time

from areal_tpu.base.monitor import (
    RolloutStat,
    UtilizationMonitor,
    clear_time_marks,
    device_memory_stats,
    get_time_marks,
    summary_time_marks,
    time_mark,
)


def test_time_marks_record_and_summarize():
    clear_time_marks()
    with time_mark("actor_train", identifier="w0", step=1):
        time.sleep(0.01)
    with time_mark("actor_train", identifier="w0", step=2):
        time.sleep(0.01)
    with time_mark("ref_inf", identifier="w1", step=1):
        pass

    marks = get_time_marks("actor_train")["actor_train"]
    assert len(marks) == 2
    assert marks[0]["duration"] >= 0.01
    assert marks[0]["step"] == 1

    summary = summary_time_marks()
    assert summary["time_marks/actor_train/count"] == 2
    assert summary["time_marks/actor_train/total_s"] >= 0.02
    assert "time_marks/ref_inf/mean_s" in summary
    clear_time_marks()
    assert summary_time_marks() == {}


def test_utilization_monitor_samples():
    mon = UtilizationMonitor(interval=0.01)
    mon.start()
    deadline = time.monotonic() + 5.0
    while not mon.history() and time.monotonic() < deadline:
        time.sleep(0.02)
    mon.stop()
    hist = mon.history()
    assert hist, "no samples collected"
    # host gauges always present on linux; device gauges backend-dependent
    assert "host/load1" in hist[-1] or "host/rss_gb" in hist[-1]
    export = mon.export()
    assert "ts" not in export


def test_device_memory_stats_shape():
    # CPU backend may expose no stats; the call must still be total
    stats = device_memory_stats()
    for k, v in stats.items():
        assert isinstance(v, float)
        assert "/" in k


def test_rollout_stat():
    rs = RolloutStat()
    rs.submitted += 2
    rs.running += 2
    rs.accepted += 1
    rs.running -= 1
    assert rs.as_dict() == {"submitted": 2, "accepted": 1, "running": 1}


def test_time_marks_publish_histogram_to_registry():
    """Marks are no longer log-only: each interval lands in the
    areal_time_mark_seconds histogram (one series per mark name)."""
    from areal_tpu.observability import get_registry

    clear_time_marks()
    with time_mark("publish_check", identifier="w0", step=1):
        time.sleep(0.005)
    with time_mark("publish_check", identifier="w0", step=2):
        pass
    h = get_registry().histogram("areal_time_mark_seconds")
    total, count = h.snapshot(mark="publish_check")
    assert count == 2
    assert total >= 0.005
    clear_time_marks()


def test_utilization_monitor_publishes_gauges():
    """The HBM/host sampler exports into the registry instead of staying
    log-only (satellite of the observability plane)."""
    from areal_tpu.observability.registry import MetricsRegistry

    reg = MetricsRegistry()
    mon = UtilizationMonitor(interval=1000, registry=reg)
    mon._sample()  # one synchronous sample, no thread needed
    names = reg.names()
    # host gauges always present on linux
    assert "areal_host_load1" in names or "areal_host_rss_gb" in names
    # device gauges appear iff the backend reports memory_stats
    if device_memory_stats():
        assert "areal_device_hbm_in_use_gb" in names


def test_device_peak_flops_table():
    import pytest

    from areal_tpu.base.monitor import device_peak_flops

    class _D:
        platform = "tpu"
        device_kind = "TPU v5 lite"  # how a v5e reports itself

    assert device_peak_flops(_D()) == 197e12

    class _C:
        platform = "cpu"
        device_kind = "cpu"

    # no published peak off-TPU: MFU is skipped, not invented
    assert device_peak_flops(_C()) == 0.0
    assert device_peak_flops(object()) == 0.0

    class _Unknown:
        platform = "tpu"
        device_kind = "TPU v9 mega"

    # an unknown chip is an error, never a default peak
    with pytest.raises(ValueError, match="v9 mega"):
        device_peak_flops(_Unknown())
