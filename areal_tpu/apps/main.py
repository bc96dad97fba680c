"""Experiment launcher: one process per worker, monitored, restartable.

Rebuild of the reference's classic launch path (reference:
realhf/apps/main.py:78 ``main_start`` with the recover-restart loop
:108-288, plus the controller's configure/monitor/panic role,
realhf/system/controller.py:98).  Differences by design: workers read their
config slice from the dumped ``ExperimentConfig`` cache instead of a
controller push channel, and on TPU the launch unit is one process per HOST
(each process drives its local chips; jax.distributed joins them into one
SPMD world when ``AREAL_JAX_COORDINATOR`` is exported).
"""

from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Optional, Tuple

from areal_tpu.api import system_api
from areal_tpu.apps import remote
from areal_tpu.base import constants, logging_, name_resolve, names
from areal_tpu.scheduler.client import (
    JobException,
    JobState,
    make_scheduler,
)
from areal_tpu.system.worker_base import (
    WorkerControlPanel,
    WorkerServerStatus,
)

logger = logging_.getLogger("launcher")

TERMINAL_STATUSES = (
    WorkerServerStatus.COMPLETED,
    WorkerServerStatus.ERROR,
    WorkerServerStatus.LOST,
)


def _worker_specs(cfg: system_api.ExperimentConfig) -> List[Tuple[str, int, str]]:
    """[(worker_type, index, worker_name)] for every worker process."""
    specs = [("master", 0, cfg.master.worker_name)]
    for i, w in enumerate(cfg.model_workers):
        specs.append(("model_worker", i, w.worker_name))
    for i, w in enumerate(cfg.gen_servers):
        specs.append(("gen_server", i, w.worker_name))
    if cfg.gserver_manager is not None:
        specs.append(("gserver_manager", 0, cfg.gserver_manager.worker_name))
    for i, w in enumerate(cfg.rollout_workers):
        specs.append(("rollout_worker", i, w.worker_name))
    if getattr(cfg, "gateway", None) is not None:
        specs.append(("gateway", 0, cfg.gateway.worker_name))
    return specs


#: workers that never run a model.  Started with the CPU pin so they never
#: open (and so hold) a chip: a TPU belongs to ONE process at a time, and
#: the first process to touch jax would take every chip of the host.
CHIPLESS_WORKERS = ("master", "gserver_manager", "rollout_worker", "gateway")
CPU_PIN = {"AREAL_JAX_PLATFORM": "cpu", "JAX_PLATFORMS": "cpu"}


def worker_device_env(
    cfg: system_api.ExperimentConfig,
    specs: List[Tuple[str, int, str]],
    base_env: Dict[str, str],
    mode: str = "local",
) -> Dict[str, Dict[str, str]]:
    """{worker_name: extra env} giving every worker process its devices.

    Chipless workers always get the CPU pin.  Device-owning workers
    (model workers, generation servers) inherit the platform — unless
    several of them share this host (``local`` mode), where each gets its
    own chip through ``TPU_VISIBLE_DEVICES`` (the way
    ``scheduler/evaluator.py`` places the evaluator), one chip per worker,
    with ``AREAL_DEVICE_BASE`` telling the child which global device index
    its first visible chip has.  What cannot be arranged that way is
    REFUSED here with a message — a child must never be left to fail or
    hang on the chip's lock.  A launch env that already pins a platform
    (``AREAL_JAX_PLATFORM``, e.g. ``cpu`` for CPU-mesh runs) is honoured
    as is: no chip is involved."""
    out: Dict[str, Dict[str, str]] = {
        wname: dict(CPU_PIN)
        for wtype, _, wname in specs
        if wtype in CHIPLESS_WORKERS
    }
    owners = [s for s in specs if s[0] not in CHIPLESS_WORKERS]
    if base_env.get("AREAL_JAX_PLATFORM") or mode != "local" or len(owners) < 2:
        # pinned by the caller / one process per host under slurm / a
        # single device owner that takes the whole host
        return out
    hint = (
        "run the one-host case in ONE process instead (the threaded "
        "runner: training/main_*.py, or quickstart --mode threads), or "
        "export AREAL_JAX_PLATFORM=cpu for a CPU-mesh run"
    )
    taken: Dict[int, str] = {}
    for wtype, idx, wname in owners:
        if wtype == "model_worker":
            if len(cfg.model_workers) > 1:
                raise ValueError(
                    f"--mode processes cannot place {len(cfg.model_workers)} "
                    f"model workers on one host; {hint}"
                )
            world = max(
                (
                    s.mesh_spec.world_size
                    for s in cfg.model_workers[idx].shards
                ),
                default=1,
            )
            start = 0
        else:
            wcfg = cfg.gen_servers[idx]
            world = wcfg.mesh_spec.world_size
            start = wcfg.device_idx
            if start is None:
                raise ValueError(
                    f"{wname} has no device_idx (gen_device_start unset): "
                    "it would open the same chips as the trainer and one "
                    f"of them would fail on the chip's lock; {hint}"
                )
        if world != 1:
            raise ValueError(
                f"{wname} spans {world} chips: the process launcher only "
                "knows how to give a worker ONE visible chip on a shared "
                f"host; {hint}"
            )
        if start in taken:
            raise ValueError(
                f"{wname} and {taken[start]} are both placed on chip "
                f"{start}: two processes cannot share a chip; {hint}"
            )
        taken[start] = wname
        out[wname] = {
            "TPU_VISIBLE_DEVICES": str(start),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "AREAL_DEVICE_BASE": str(start),
        }
    return out


def launch_experiment(
    cfg: system_api.ExperimentConfig,
    mode: str = "local",
    recover_retries: int = 0,
    timeout: Optional[float] = None,
    env: Optional[Dict[str, str]] = None,
) -> None:
    """Launch every worker as its own process; monitor to completion.

    Restarts the whole experiment up to ``recover_retries`` times when a
    worker fails (the reference's experiment-level recovery policy,
    realhf/apps/main.py:108-288; recover ckpt loading happens inside the
    workers)."""
    trials = recover_retries + 1
    last_exc: Optional[BaseException] = None
    for attempt in range(trials):
        if attempt > 0:
            logger.warning(
                "restarting experiment (recover attempt %d/%d)",
                attempt,
                recover_retries,
            )
        try:
            _launch_once(cfg, mode=mode, timeout=timeout, env=env, recover=attempt > 0)
            return
        except (JobException, TimeoutError) as e:
            last_exc = e
            if attempt == trials - 1:
                raise
    if last_exc:
        raise last_exc


def _launch_once(
    cfg: system_api.ExperimentConfig,
    mode: str,
    timeout: Optional[float],
    env: Optional[Dict[str, str]],
    recover: bool = False,
) -> None:
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)
    backend = os.environ.get("AREAL_NAME_RESOLVE", "nfs")
    name_resolve.reconfigure(backend)
    name_resolve.clear_subtree(
        names.trial_root(cfg.experiment_name, cfg.trial_name)
    )
    remote.dump_experiment_config(cfg)

    sched = make_scheduler(mode, cfg.experiment_name, cfg.trial_name)
    wenv = {
        "AREAL_NAME_RESOLVE": backend,
        # the server backend resolves its endpoint from this var — workers
        # need it propagated just like the backend selector itself
        **(
            {"AREAL_NAME_RESOLVE_ADDR": os.environ["AREAL_NAME_RESOLVE_ADDR"]}
            if os.environ.get("AREAL_NAME_RESOLVE_ADDR")
            else {}
        ),
        **({"AREAL_RECOVER": "1"} if recover else {}),
        **(env or {}),
    }
    log_dir = constants.get_log_path()
    specs = _worker_specs(cfg)
    # observability plane: with AREAL_METRICS_PORT_BASE set, every worker's
    # /metrics endpoint gets a deterministic port (base + launch index) so
    # ops tooling/firewalls can pre-open them; unset, each worker binds a
    # random free port and publishes it via name_resolve either way.
    # Local mode only: the slurm client exports env at CONSTRUCTION, not
    # per-submit, and cross-host port pinning belongs in the sbatch prolog.
    metrics_base = None
    raw_base = os.environ.get("AREAL_METRICS_PORT_BASE")
    if raw_base and mode == "local":
        try:
            metrics_base = int(raw_base)
        except ValueError:
            logger.warning(
                "ignoring non-numeric AREAL_METRICS_PORT_BASE=%r", raw_base
            )
    device_env = worker_device_env(cfg, specs, wenv, mode=mode)
    for seq, (wtype, idx, wname) in enumerate(specs):
        worker_env = {**wenv, **device_env.get(wname, {})}
        if metrics_base is not None:
            worker_env["AREAL_METRICS_PORT"] = str(metrics_base + seq)
        sched.submit(
            wtype,
            [
                sys.executable,
                "-m",
                "areal_tpu.apps.remote",
                "--experiment_name",
                cfg.experiment_name,
                "--trial_name",
                cfg.trial_name,
                "--worker_type",
                wtype,
                "--worker_index",
                str(idx),
            ],
            env=worker_env,
            log_path=os.path.join(log_dir, f"{wname}.log"),
        )
    try:
        _monitor(sched, cfg, specs, timeout, mode=mode)
    except BaseException:
        sched.stop_all()
        raise


def _make_evaluator(cfg: system_api.ExperimentConfig, mode: str = "local"):
    """Checkpoint-watching evaluator driven by the controller loop
    (reference: realhf/apps/main.py:96-154 builds the AutomaticEvaluator and
    steps it while monitoring).  Eval jobs submit through the same
    scheduler layer as workers, so slurm experiments get slurm evals."""
    from areal_tpu.scheduler.evaluator import make_evaluator

    return make_evaluator(cfg, scheduler_mode=mode)


def _monitor(
    sched,
    cfg: system_api.ExperimentConfig,
    specs: List[Tuple[str, int, str]],
    timeout: Optional[float],
    mode: str = "local",
) -> None:
    """Controller role: watch job + worker statuses; panic on failure; when
    the master completes, gracefully exit the remaining workers."""
    deadline = time.monotonic() + timeout if timeout else None
    master_name = cfg.master.worker_name
    status_key = names.worker_status(
        cfg.experiment_name, cfg.trial_name, master_name
    )
    all_names = [w for _, _, w in specs]
    # beats come from a daemon thread, so this is a process-liveness bound
    # (not an MFC-duration bound); the scheduler catches clean process death
    # faster, heartbeats catch hosts that vanish without reaping
    hb_timeout = float(os.environ.get("AREAL_HEARTBEAT_TIMEOUT", "60"))
    panel = WorkerControlPanel(cfg.experiment_name, cfg.trial_name)
    evaluator = _make_evaluator(cfg, mode)
    last_eval_step = time.monotonic()
    completed = False
    try:
        _monitor_loop(
            sched,
            cfg,
            deadline,
            status_key,
            master_name,
            panel,
            all_names,
            hb_timeout,
            evaluator,
            last_eval_step,
        )
        completed = True
    finally:
        # every exit path (worker failure, timeout, Ctrl-C) must reap the
        # detached eval subprocess or a restart would race the orphan
        if evaluator is not None:
            evaluator._harvest()
            evaluator.shutdown()
        if not completed:
            panel.close()

    _shutdown_workers(sched, cfg, specs, panel, master_name)


def _monitor_loop(
    sched,
    cfg,
    deadline,
    status_key,
    master_name,
    panel,
    all_names,
    hb_timeout,
    evaluator,
    last_eval_step,
):
    last_hb_check = time.monotonic()
    while True:
        for job in sched.find_all():
            if job.state == JobState.FAILED:
                raise JobException(
                    sched.run_name, job.name, job.host, job.state
                )
        try:
            master_status = name_resolve.get(status_key)
        except name_resolve.NameEntryNotFoundError:
            master_status = None
        if master_status == WorkerServerStatus.COMPLETED.value:
            break
        if master_status == WorkerServerStatus.ERROR.value:
            raise JobException(
                sched.run_name, master_name, "?", JobState.FAILED
            )
        if time.monotonic() - last_hb_check > 10.0:
            last_hb_check = time.monotonic()
            stale = panel.find_stale_workers(all_names, timeout=hb_timeout)
            if stale:
                for w in stale:
                    logger.error(
                        "worker %s heartbeat stale > %.0fs; declaring LOST",
                        w,
                        hb_timeout,
                    )
                raise JobException(
                    sched.run_name, stale[0], "?", JobState.FAILED
                )
        if evaluator is not None and (
            time.monotonic() - last_eval_step > cfg.evaluator.interval
        ):
            last_eval_step = time.monotonic()
            evaluator.step()
        if deadline and time.monotonic() > deadline:
            raise TimeoutError("experiment timed out")
        time.sleep(0.5)


def _shutdown_workers(sched, cfg, specs, panel, master_name):
    # master done: ask everyone else to exit, then reap
    others = [w for t, i, w in specs if w != master_name]
    try:
        panel.connect(others, timeout=10)
        for w in others:
            try:
                panel.request(w, "exit", timeout=10)
            except Exception:  # noqa: BLE001 - best-effort shutdown
                logger.warning("worker %s did not ack exit", w)
    except Exception:  # noqa: BLE001
        logger.warning("could not connect control panel for shutdown")
    finally:
        panel.close()
    try:
        sched.wait(
            timeout=30,
            check_status=(JobState.FAILED,),
            remove_status=(JobState.COMPLETED, JobState.CANCELLED),
        )
    except TimeoutError:
        logger.warning(
            "workers still running after master exit; killing %s",
            [j.name for j in sched.find_all() if j.state == JobState.RUNNING],
        )
    finally:
        sched.stop_all()


def main_stop(experiment_name: str, trial_name: str, mode: str = "local"):
    """Best-effort stop of a running trial (reference main.py ``main_stop``)."""
    constants.set_experiment_trial_names(experiment_name, trial_name)
    name_resolve.reconfigure(os.environ.get("AREAL_NAME_RESOLVE", "nfs"))
    panel = WorkerControlPanel(experiment_name, trial_name)
    root = names.worker_root(experiment_name, trial_name)
    try:
        workers = [k.rsplit("/", 1)[-1] for k in name_resolve.find_subtree(root)]
        panel.connect(workers, timeout=5)
        for w in workers:
            try:
                panel.request(w, "exit", timeout=5)
            except Exception:  # noqa: BLE001
                pass
    finally:
        panel.close()


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description="areal_tpu experiment launcher")
    p.add_argument("command", choices=["stop"])
    p.add_argument("--experiment_name", required=True)
    p.add_argument("--trial_name", required=True)
    args = p.parse_args(argv)
    if args.command == "stop":
        main_stop(args.experiment_name, args.trial_name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
