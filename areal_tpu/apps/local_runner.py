"""In-process experiment runner: master + workers as threads.

Rebuild of the reference's local launch path (reference:
realhf/apps/main.py ``main_start`` + realhf/system/controller.py; the
threaded mode mirrors the CPU e2e test harness
tests/experiments/utils.py:52 ``run_test_exp``).  Suitable for single-host
experiments — which on TPU covers a whole slice, since one process drives
all local chips.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

from areal_tpu.api import system_api
from areal_tpu.base import constants, logging_, name_resolve
from areal_tpu.system.master_worker import MasterWorker
from areal_tpu.system.model_worker import ModelWorker
from areal_tpu.system.worker_base import WorkerServerStatus

logger = logging_.getLogger("local_runner")


def register_impls():
    """Import all implementation modules so their registries populate
    (reference: realhf/apps/remote.py ``_patch_external_impl``)."""
    import areal_tpu.data.math_code_dataset  # noqa: F401
    import areal_tpu.data.prompt_answer_dataset  # noqa: F401
    import areal_tpu.data.prompt_dataset  # noqa: F401
    import areal_tpu.data.rw_paired_dataset  # noqa: F401
    import areal_tpu.agents.math_multi_turn_agent  # noqa: F401
    import areal_tpu.agents.math_single_step_agent  # noqa: F401
    import areal_tpu.engine.backend  # noqa: F401
    import areal_tpu.envs.math_code_single_step_env  # noqa: F401
    import areal_tpu.experiments.async_ppo_exp  # noqa: F401
    import areal_tpu.experiments.dpo_exp  # noqa: F401
    import areal_tpu.experiments.null_exp  # noqa: F401
    import areal_tpu.experiments.ppo_math_exp  # noqa: F401
    import areal_tpu.experiments.rm_exp  # noqa: F401
    import areal_tpu.experiments.sft_exp  # noqa: F401
    import areal_tpu.interfaces.dpo_interface  # noqa: F401
    import areal_tpu.interfaces.fused_interface  # noqa: F401
    import areal_tpu.interfaces.ppo_interface  # noqa: F401
    import areal_tpu.interfaces.rw_interface  # noqa: F401
    import areal_tpu.interfaces.sft_interface  # noqa: F401

    # pre-resolve transformers' lazy attributes in the main thread: its lazy
    # module loader is not thread-safe, and worker threads load tokenizers
    # concurrently at configure time
    from transformers import AutoConfig, AutoTokenizer  # noqa: F401

    # the checkpointing library here too: a model worker's first save
    # imports it, and as a thread among the other workers' threads, all
    # taking turns at the interpreter, that import takes 14 s against 2
    import orbax.checkpoint  # noqa: F401


def run_experiment_local(
    cfg: system_api.ExperimentConfig,
    timeout: Optional[float] = None,
    before_exit=None,
) -> MasterWorker:
    """Run to completion in this process; returns the master (stats inside).

    ONE process owns every chip of the host and every worker is a thread
    of it — the one-host way to run (a chip belongs to one process at a
    time).  ``before_exit(workers)``, when given, is called once the
    master has finished and before any worker is told to exit, with every
    worker object the runner started (model workers, generation servers,
    manager, rollout workers) — engines are still live and placed."""
    register_impls()
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)

    workers: List[ModelWorker] = []
    threads: List[threading.Thread] = []
    errors: List[BaseException] = []

    def _run_worker(w, wcfg):
        try:
            w.run(wcfg)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    for wcfg in cfg.model_workers:
        w = ModelWorker()
        workers.append(w)
        t = threading.Thread(
            target=_run_worker, args=(w, wcfg), daemon=True,
            name=wcfg.worker_name,
        )
        t.start()
        threads.append(t)

    # rollout stack (async experiments)
    aux_threads, aux_workers = _start_rollout_stack(cfg, errors)

    # automatic evaluator (same component the process launcher drives;
    # reference: apps/main.py builds it alongside the monitor)
    evaluator = None
    eval_stop = threading.Event()
    if cfg.evaluator is not None:
        from areal_tpu.scheduler.evaluator import (
            make_evaluator,
            run_evaluator_loop,
        )

        evaluator = make_evaluator(cfg)
        et = threading.Thread(
            target=run_evaluator_loop,
            args=(evaluator, eval_stop, cfg.evaluator.interval),
            daemon=True,
            name="evaluator",
        )
        et.start()
        aux_threads.append(et)

    master = MasterWorker()
    master_err: List[BaseException] = []

    def _run_master():
        try:
            master.run_async(cfg.master)
        except BaseException as e:  # noqa: BLE001
            master_err.append(e)

    mt = threading.Thread(target=_run_master, daemon=True, name="master")
    mt.start()
    deadline = time.monotonic() + timeout if timeout else None
    try:
        while mt.is_alive():
            mt.join(timeout=0.5)
            if errors:
                for w in workers:
                    w.exit()
                raise RuntimeError("worker failed") from errors[0]
            if deadline and time.monotonic() > deadline:
                raise TimeoutError("experiment timed out")
        if master_err:
            raise RuntimeError("master failed") from master_err[0]
    finally:
        # stop the evaluator on every exit path (its subprocess is detached)
        eval_stop.set()
        if evaluator is not None:
            evaluator.shutdown()
    if before_exit is not None:
        before_exit(workers + aux_workers)
    for w in workers + aux_workers:
        w.exit()
    for t in threads + aux_threads:
        t.join(timeout=10)
    return master


def _start_rollout_stack(cfg: system_api.ExperimentConfig, errors):
    threads = []
    aux = []
    if cfg.gen_servers:
        from areal_tpu.system.generation_server import GenerationServerWorker

        for gcfg in cfg.gen_servers:
            aux.append((GenerationServerWorker(), gcfg))
    if cfg.gserver_manager is not None:
        from areal_tpu.system.gserver_manager import GserverManager

        aux.append((GserverManager(), cfg.gserver_manager))
    if cfg.rollout_workers:
        from areal_tpu.system.rollout_worker import RolloutWorker

        for rcfg in cfg.rollout_workers:
            aux.append((RolloutWorker(), rcfg))
    if getattr(cfg, "gateway", None) is not None:
        from areal_tpu.gateway.worker import GatewayWorker

        aux.append((GatewayWorker(), cfg.gateway))

    from areal_tpu.system.worker_base import AsyncWorker

    def _run(w, wcfg):
        try:
            if isinstance(w, AsyncWorker):
                w.run_async(wcfg)
            else:
                w.run(wcfg)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    worker_objs = []
    for w, wcfg in aux:
        worker_objs.append(w)
        t = threading.Thread(
            target=_run, args=(w, wcfg), daemon=True,
            name=getattr(wcfg, "worker_name", "aux"),
        )
        t.start()
        threads.append(t)
    return threads, worker_objs
