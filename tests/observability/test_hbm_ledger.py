"""HbmLedger unit contract: handle lifecycle, clamping, watermarks,
publish/reconcile export, leak audit, the disabled no-op mode, and the
tag catalogue's agreement with the metric-label docs."""

import threading

import numpy as np
import pytest

from areal_tpu.observability.hbm_ledger import (
    DEVICE_SUBSYSTEMS,
    SUBSYSTEMS,
    HbmLedger,
    get_ledger,
    set_ledger,
    tree_nbytes,
)
from areal_tpu.observability.registry import MetricsRegistry


def test_register_resize_release_roundtrip():
    led = HbmLedger()
    h = led.register("kv_pool", nbytes=100, name="pool")
    assert led.snapshot()["kv_pool"] == 100
    h.resize(40)
    assert led.snapshot()["kv_pool"] == 40
    assert led.watermarks()["kv_pool"] == 100  # peak survives the shrink
    h.release()
    assert led.snapshot()["kv_pool"] == 0
    h.resize(999)  # no-op after release
    assert led.snapshot()["kv_pool"] == 0
    h.release()  # idempotent


def test_unknown_tag_rejected():
    with pytest.raises(ValueError, match="unknown ledger subsystem"):
        HbmLedger().register("gpu_vram")


def test_two_handles_same_tag_sum_and_negative_clamps():
    led = HbmLedger()
    a = led.register("weights", nbytes=10)
    b = led.register("weights", nbytes=5)
    assert led.snapshot()["weights"] == 15
    a.resize(-50)  # negative coerces to 0, never below
    assert a.bytes == 0
    assert led.snapshot()["weights"] == 5
    b.release()
    assert led.snapshot()["weights"] == 0


def test_device_bytes_excludes_host_tags():
    led = HbmLedger()
    led.register("kv_pool", nbytes=1000)
    led.register("prefix_spill_host", nbytes=7777)
    assert led.device_bytes() == 1000
    assert set(DEVICE_SUBSYSTEMS) < set(SUBSYSTEMS)


def test_leaks_against_baseline():
    led = HbmLedger()
    h = led.register("handoff_staging", nbytes=64)
    base = led.snapshot()
    assert led.leaks(base) == {}
    h.resize(96)
    assert led.leaks(base) == {"handoff_staging": 32}
    assert led.leaks() == {"handoff_staging": 96}  # vs empty ledger
    h.release()
    assert led.leaks(base) == {"handoff_staging": -64}


def test_publish_exports_every_tag_including_zeros():
    led = HbmLedger()
    led.register("kv_scales", nbytes=256)
    reg = MetricsRegistry()
    led.publish(reg)
    g = reg.gauge("areal_hbm_ledger_bytes")
    assert g.value(subsystem="kv_scales") == 256.0
    assert g.value(subsystem="stream_buffers") == 0.0  # no holes
    assert (
        reg.gauge("areal_hbm_ledger_peak_bytes").value(subsystem="kv_scales")
        == 256.0
    )


def test_reconcile_within_tolerance_and_drift():
    led = HbmLedger()
    led.register("weights", nbytes=1 << 30)
    reg = MetricsRegistry()
    # device reports MORE in use than the ledger: fine (untagged scratch)
    r = led.reconcile(reg, 2 << 30)
    assert r["ok"] and not r["vacuous"] and r["drift_gb"] == 0.0
    # ledger claims 1 GiB the device says it doesn't hold -> drift
    r = led.reconcile(reg, 0, tolerance_bytes=0)
    assert not r["ok"]
    assert r["drift_gb"] == pytest.approx(1.0)
    assert reg.gauge("areal_hbm_ledger_drift_gb").value() == pytest.approx(
        1.0
    )


def test_reconcile_vacuous_without_device_stats():
    led = HbmLedger()
    led.register("kv_pool", nbytes=123456)
    reg = MetricsRegistry()
    r = led.reconcile(reg, None)  # CPU jax: no memory_stats
    assert r["ok"] and r["vacuous"] and r["drift_gb"] == 0.0
    assert reg.gauge("areal_hbm_ledger_drift_gb").value() == 0.0


def test_disabled_ledger_is_a_noop():
    led = HbmLedger(enabled=False)
    h = led.register("weights", nbytes=100)
    h.resize(500)
    assert led.snapshot()["weights"] == 0
    assert led.leaks() == {}


def test_global_ledger_roundtrip():
    old = get_ledger()
    try:
        mine = HbmLedger()
        set_ledger(mine)
        assert get_ledger() is mine
    finally:
        set_ledger(old)


def test_tree_nbytes_counts_array_leaves_only():
    tree = {
        "w": np.zeros((4, 4), dtype=np.float32),
        "meta": {"step": 7, "b": np.ones(3, dtype=np.int8)},
    }
    assert tree_nbytes(tree) == 64 + 3
    assert tree_nbytes(None) == 0


def test_concurrent_resizes_stay_consistent():
    led = HbmLedger()
    handles = [led.register("stream_buffers") for _ in range(8)]

    def hammer(h):
        for i in range(200):
            h.resize(i)
        h.resize(13)

    ts = [threading.Thread(target=hammer, args=(h,)) for h in handles]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert led.snapshot()["stream_buffers"] == 8 * 13


def test_catalogue_matches_metric_label_docs():
    """Every canonical tag renders into the published gauge exactly once
    — the docs table in observability.md is generated from this
    vocabulary, and the fleet merge keys on it."""
    led = HbmLedger()
    reg = MetricsRegistry()
    led.publish(reg)
    fam = reg.render()
    for tag in SUBSYSTEMS:
        assert f'subsystem="{tag}"' in fam
