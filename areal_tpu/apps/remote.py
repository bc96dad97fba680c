"""Per-process worker entry.

Rebuild of the reference's remote worker entry (reference:
realhf/apps/remote.py ``main_worker``/``main_controller`` — the process the
scheduler actually launches; it re-registers the experiment from an on-disk
cache and runs one worker).  The launcher (areal_tpu/apps/main.py) dumps the
fully-resolved ``ExperimentConfig`` to the cluster cache dir; every worker
process loads it and picks its own slice, so no controller push-channel is
needed for configuration — name_resolve (NFS backend by default) is the only
cross-process dependency.

Usage::

    python -m areal_tpu.apps.remote --experiment_name E --trial_name T \
        --worker_type model_worker --worker_index 0
"""

from __future__ import annotations

import argparse
import os
import pickle
import sys


def config_cache_path(experiment_name: str, trial_name: str) -> str:
    from areal_tpu.base import constants

    return os.path.join(
        constants.get_cache_path(),
        f"{experiment_name}-{trial_name}-config.pkl",
    )


def dump_experiment_config(cfg) -> str:
    path = config_cache_path(cfg.experiment_name, cfg.trial_name)
    with open(path + ".tmp", "wb") as f:
        pickle.dump(cfg, f)
    os.replace(path + ".tmp", path)
    return path


def load_experiment_config(experiment_name: str, trial_name: str):
    with open(config_cache_path(experiment_name, trial_name), "rb") as f:
        return pickle.load(f)


def _maybe_init_jax_distributed():
    """Join the jax.distributed cluster when the launcher exported the
    coordination env (multi-host SPMD over DCN; reference analogue: the NCCL
    group bootstrap realhf/impl/model/comm/global_comm.py:48)."""
    coord = os.environ.get("AREAL_JAX_COORDINATOR")
    if not coord:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["AREAL_JAX_NUM_PROCESSES"]),
        process_id=int(os.environ["AREAL_JAX_PROCESS_ID"]),
    )


def run_worker(
    experiment_name: str,
    trial_name: str,
    worker_type: str,
    worker_index: int,
) -> str:
    """Run one worker to completion in this process; returns final status."""
    from areal_tpu.apps.local_runner import register_impls
    from areal_tpu.base import constants, name_resolve
    from areal_tpu.system.worker_base import AsyncWorker, make_server

    # platform pinning for workers that own no chip (the process launcher
    # sets AREAL_JAX_PLATFORM=cpu for them) and for CPU-mesh runs
    from areal_tpu.base.compile_cache import setup_compile_cache

    setup_compile_cache()
    platform = os.environ.get("AREAL_JAX_PLATFORM")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)

    name_resolve.reconfigure(
        os.environ.get("AREAL_NAME_RESOLVE", "nfs"),
    )
    constants.set_experiment_trial_names(experiment_name, trial_name)
    register_impls()
    _maybe_init_jax_distributed()
    cfg = load_experiment_config(experiment_name, trial_name)

    if worker_type == "master":
        from areal_tpu.system.master_worker import MasterWorker

        cls, wcfg = MasterWorker, cfg.master
    elif worker_type == "model_worker":
        from areal_tpu.system.model_worker import ModelWorker

        cls, wcfg = ModelWorker, cfg.model_workers[worker_index]
    elif worker_type == "rollout_worker":
        from areal_tpu.system.rollout_worker import RolloutWorker

        cls, wcfg = RolloutWorker, cfg.rollout_workers[worker_index]
    elif worker_type == "gen_server":
        from areal_tpu.system.generation_server import GenerationServerWorker

        cls, wcfg = GenerationServerWorker, cfg.gen_servers[worker_index]
        base = os.environ.get("AREAL_DEVICE_BASE")
        if base and wcfg.device_idx is not None:
            # the launcher gave this process its own visible chip(s),
            # which jax numbers from 0 (apps/main.worker_device_env)
            wcfg.device_idx -= int(base)
    elif worker_type == "gserver_manager":
        from areal_tpu.system.gserver_manager import GserverManager

        cls, wcfg = GserverManager, cfg.gserver_manager
    elif worker_type == "gateway":
        from areal_tpu.gateway.worker import GatewayWorker

        cls, wcfg = GatewayWorker, cfg.gateway
    else:
        raise ValueError(f"unknown worker type {worker_type!r}")

    server = make_server(wcfg.worker_name)
    worker = cls(server)
    if isinstance(worker, AsyncWorker):
        status = worker.run_async(wcfg)
    else:
        status = worker.run(wcfg)
    return str(status.value if hasattr(status, "value") else status)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="areal_tpu remote worker entry")
    p.add_argument("--experiment_name", required=True)
    p.add_argument("--trial_name", required=True)
    p.add_argument("--worker_type", required=True)
    p.add_argument("--worker_index", type=int, default=0)
    args = p.parse_args(argv)
    status = run_worker(
        args.experiment_name,
        args.trial_name,
        args.worker_type,
        args.worker_index,
    )
    return 0 if status in ("COMPLETED", "PAUSED") else 1


if __name__ == "__main__":
    sys.exit(main())
