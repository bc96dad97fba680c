"""MetricsLogger sinks: JSONL always, tensorboard event file when available
(reference observability fan-out: realhf/system/master_worker.py:291-350)."""

import glob
import json
import os


def test_metrics_logger_jsonl_and_tensorboard(tmp_path):
    from areal_tpu.base.metrics import MetricsLogger

    m = MetricsLogger(str(tmp_path), "exp", "trial")
    m.log({"loss": 1.5, "grad_norm": 0.3, "note": "skipme"}, step=0)
    m.log({"loss": 1.2, "grad_norm": 0.2, "n_mbs": 4}, step=1)
    m.close()

    lines = [
        json.loads(l)
        for l in open(tmp_path / "stats.jsonl").read().splitlines()
    ]
    assert [l["step"] for l in lines] == [0, 1]
    assert lines[0]["loss"] == 1.5
    assert "note" not in lines[0]  # non-scalars dropped
    assert lines[1]["n_mbs"] == 4

    events = glob.glob(
        os.path.join(tmp_path, "tensorboard", "events.out.tfevents.*")
    )
    assert events, "tensorboard event file missing"
    # the file is tensorboard's own format: records of 8 bytes of length,
    # 4 of checksum, the Event, 4 of checksum; the first names the version
    import struct

    from tensorboard.compat.proto.event_pb2 import Event

    raw, read = open(events[0], "rb").read(), []
    while raw:
        (n,) = struct.unpack("<Q", raw[:8])
        read.append(Event.FromString(raw[12 : 12 + n]))
        raw = raw[12 + n + 4 :]
    assert read[0].file_version == "brain.Event:2"
    scalars = [
        (e.step, v.tag, v.simple_value) for e in read for v in e.summary.value
    ]
    assert (0, "loss", 1.5) in scalars and (1, "n_mbs", 4.0) in scalars
    assert len(scalars) == 5


def test_flops_counter_relations():
    from areal_tpu.models.config import tiny_config
    from areal_tpu.system import flops_counter as fc

    cfg = tiny_config()
    fwd = fc.forward_flops(cfg, [64, 32])
    assert fc.train_flops(cfg, [64, 32]) == 3 * fwd
    assert fwd > fc.forward_flops(cfg, [64], with_head=True)

    gen = fc.generate_flops(cfg, [16, 16], [8, 8])
    assert gen > fc.forward_flops(cfg, [16, 16], with_head=False)
    assert gen == fc.mfc_flops("generate", cfg, [24, 24], [16, 16])

    # MoE activates n_experts_per_tok experts, not all
    moe = tiny_config(n_experts=8, n_experts_per_tok=2)
    dense_like = tiny_config()
    assert fc.matmul_params_per_layer(moe) > fc.matmul_params_per_layer(
        dense_like
    ) * 0  # sanity: positive
    full_moe = tiny_config(n_experts=8, n_experts_per_tok=8)
    assert fc.matmul_params_per_layer(moe) < fc.matmul_params_per_layer(
        full_moe
    )


def test_worker_heartbeat():
    from areal_tpu.base import constants, name_resolve, names
    from areal_tpu.system import worker_base

    name_resolve.reconfigure("memory")
    constants.set_experiment_trial_names("hbexp", "t0")
    server = worker_base.make_server("w0", "hbexp", "t0")
    panel = worker_base.WorkerControlPanel("hbexp", "t0")
    age = panel.get_heartbeat_age("w0")
    assert age is not None and age < 5.0
    assert panel.find_stale_workers(["w0"], timeout=60.0) == []

    # a worker whose beat value stopped changing counts as stale; staleness
    # is reader-side (panel's monotonic clock since last observed CHANGE),
    # so a synthetic worker is observed once, then its observation time is
    # backdated to simulate 120s with no new beat
    name_resolve.add(
        names.worker_heartbeat("hbexp", "t0", "w1"),
        "12345.0",
        replace=True,
    )
    name_resolve.add(
        names.worker_status("hbexp", "t0", "w1"),
        worker_base.WorkerServerStatus.RUNNING.value,
        replace=True,
    )
    assert panel.find_stale_workers(["w1"], timeout=60.0) == []  # first obs
    val, seen = panel._hb_seen["w1"]
    panel._hb_seen["w1"] = (val, seen - 120)
    assert panel.find_stale_workers(["w1"], timeout=60.0) == ["w1"]
    # a NEW beat value resets staleness
    name_resolve.add(
        names.worker_heartbeat("hbexp", "t0", "w1"), "12346.0", replace=True
    )
    assert panel.find_stale_workers(["w1"], timeout=60.0) == []
    # terminal workers are never stale, even with an old observation
    val, seen = panel._hb_seen["w1"]
    panel._hb_seen["w1"] = (val, seen - 120)
    name_resolve.add(
        names.worker_status("hbexp", "t0", "w1"),
        worker_base.WorkerServerStatus.COMPLETED.value,
        replace=True,
    )
    assert panel.find_stale_workers(["w1"], timeout=60.0) == []
    # unknown worker: no heartbeat yet -> not declared stale
    assert panel.find_stale_workers(["nope"], timeout=60.0) == []
    server.close()
    panel.close()
