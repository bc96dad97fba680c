"""One process per chip: the process launcher's per-worker device env,
and a generation server that is placed past the last device."""

from types import SimpleNamespace as NS

import pytest

from areal_tpu.apps import main as launcher
from areal_tpu.base.topology import MeshSpec


def _cfg(gen_device_idx=(1,), gen_tp=1, train_world=1, n_model_workers=1):
    return NS(
        master=NS(worker_name="master"),
        model_workers=[
            NS(
                worker_name=f"model_worker_{i}",
                shards=[NS(mesh_spec=MeshSpec(fsdp=train_world))],
            )
            for i in range(n_model_workers)
        ],
        gen_servers=[
            NS(
                worker_name=f"gen_server_{i}",
                mesh_spec=MeshSpec(model=gen_tp),
                device_idx=idx,
            )
            for i, idx in enumerate(gen_device_idx)
        ],
        gserver_manager=NS(worker_name="gserver_manager"),
        rollout_workers=[NS(worker_name="rollout_worker_0")],
        gateway=NS(worker_name="gateway"),
    )


def test_chipless_workers_carry_the_cpu_pin():
    cfg = _cfg()
    specs = launcher._worker_specs(cfg)
    env = launcher.worker_device_env(cfg, specs, {})
    for name in ("master", "gserver_manager", "rollout_worker_0", "gateway"):
        assert env[name]["AREAL_JAX_PLATFORM"] == "cpu", name
        assert env[name]["JAX_PLATFORMS"] == "cpu", name
    # ... also under slurm, and when the launch env already pins a platform
    for kw in (dict(mode="slurm"), dict(base_env={"AREAL_JAX_PLATFORM": "cpu"})):
        e = launcher.worker_device_env(
            cfg, specs, kw.get("base_env", {}), mode=kw.get("mode", "local")
        )
        assert e["master"]["AREAL_JAX_PLATFORM"] == "cpu"
        assert "model_worker_0" not in e and "gen_server_0" not in e


def test_chip_owners_on_a_shared_host_get_disjoint_visible_chips():
    cfg = _cfg(gen_device_idx=(1, 2))
    env = launcher.worker_device_env(cfg, launcher._worker_specs(cfg), {})
    seen = [
        env[w]["TPU_VISIBLE_DEVICES"]
        for w in ("model_worker_0", "gen_server_0", "gen_server_1")
    ]
    assert seen == ["0", "1", "2"]
    assert env["gen_server_1"]["AREAL_DEVICE_BASE"] == "2"
    assert "AREAL_JAX_PLATFORM" not in env["gen_server_0"]


def test_a_single_chip_owner_inherits_the_whole_host():
    cfg = _cfg(gen_device_idx=())
    env = launcher.worker_device_env(cfg, launcher._worker_specs(cfg), {})
    assert "model_worker_0" not in env


@pytest.mark.parametrize(
    "kwargs,match",
    [
        (dict(gen_device_idx=(None,)), "no device_idx"),
        (dict(gen_device_idx=(0,)), "both placed on chip 0"),
        (dict(gen_device_idx=(2,), gen_tp=2), "spans 2 chips"),
        (dict(gen_device_idx=(2,), train_world=2), "spans 2 chips"),
        (dict(gen_device_idx=(2,), n_model_workers=2), "2 model workers"),
    ],
)
def test_launcher_refuses_what_it_cannot_arrange(kwargs, match):
    """... with a message, instead of letting a child hang on the lock."""
    cfg = _cfg(**kwargs)
    with pytest.raises(ValueError, match=match) as e:
        launcher.worker_device_env(cfg, launcher._worker_specs(cfg), {})
    assert "threaded runner" in str(e.value)


def test_gen_server_device_idx_past_last_device_raises():
    import jax

    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.system_api import GenServerConfig
    from areal_tpu.base import constants
    from areal_tpu.system.generation_server import GenerationServerWorker

    constants.set_experiment_trial_names("placement", "t0")
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"device {n + 3} but only {n} exist"):
        GenerationServerWorker().configure(
            GenServerConfig(
                worker_name="gen_server_0",
                model=ModelAbstraction("random", {"vocab_size": 64}),
                max_concurrent_batch=2,
                kv_cache_len=64,
                device_idx=n + 3,  # e.g. the shipped gen_device_start: 4
            )
        )
