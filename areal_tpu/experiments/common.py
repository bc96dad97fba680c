"""Shared experiment-building helpers
(reference: realhf/experiments/common/common.py ``CommonExperimentConfig``
:72 — allocation parsing, worker-config building, sanity checks)."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from areal_tpu.api import system_api
from areal_tpu.api.config import DatasetAbstraction, ModelAbstraction
from areal_tpu.api.system_api import (
    ExperimentConfig,
    ExperimentSaveEvalControl,
    MasterWorkerConfig,
    ModelWorkerConfig,
)
from areal_tpu.base.topology import MeshSpec


def model_config_from_abstraction(model: Optional[ModelAbstraction]):
    """TransformerConfig for a model abstraction ('hf' reads config.json
    only, 'random' builds from args), or None when underivable.  Used by
    the heuristic allocation hooks."""
    if model is None:
        return None
    if model.type_ == "hf":
        from areal_tpu.models.hf.registry import load_hf_config

        _, cfg, _ = load_hf_config(model.args["path"])
        return cfg
    if model.type_ == "random":
        from areal_tpu.models.config import TransformerConfig, tiny_config

        args = dict(model.args)
        args.pop("seed", None)
        conf = args.pop("config", None)
        if isinstance(conf, TransformerConfig):
            return conf
        if conf is not None:
            return TransformerConfig(**conf)
        return tiny_config(**args)
    return None


@dataclasses.dataclass
class CommonExperimentConfig(system_api.Experiment):
    """Base options shared by quickstart experiments."""

    experiment_name: str = "test-exp"
    trial_name: str = "test-trial"
    seed: int = 1
    # number of model-worker processes (hosts); each drives its local chips
    n_model_workers: int = 1
    mesh_spec: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    exp_ctrl: ExperimentSaveEvalControl = dataclasses.field(
        default_factory=ExperimentSaveEvalControl
    )
    tokenizer_path: Optional[str] = None
    # compact allocation string ("d2f2m2", "gen.d2m1+d4f2m1", "heuristic")
    # overriding mesh_spec / the gen-device split (reference:
    # CommonExperimentConfig.allocation_mode, experiments/common/common.py:189)
    allocation_mode: str = ""
    # run on N virtual CPU devices instead of the accelerator (debug/CI mode,
    # mirrors the reference's CPU test harness realhf/base/testing.py)
    force_cpu_devices: Optional[int] = None
    # automatic checkpoint evaluator (reference: exp_cfg.evaluator driven by
    # apps/main.py); consumed by the process launcher's monitor loop
    evaluator: Optional[system_api.EvaluatorConfig] = None

    def resolve_allocation(self):
        """Apply ``allocation_mode`` to mesh_spec; returns the parsed mode
        (or None).  Decoupled gen placement is applied by the async
        experiment, which owns the gen-server configs."""
        if not self.allocation_mode:
            return None
        from areal_tpu.api.allocation import AllocationMode, AllocationType

        am = AllocationMode.from_str(self.allocation_mode)
        if am.type_ == AllocationType.HEURISTIC:
            am = self._solve_heuristic_allocation()
        if am.type_ != AllocationType.MANUAL:
            self.mesh_spec = am.train_spec()
        return am

    # -- heuristic allocation hooks (overridden by concrete experiments) ----

    def _main_model(self) -> Optional[ModelAbstraction]:
        """The trained model's abstraction (drives heuristic allocation and
        tokenizer defaulting); None when the experiment has no single one."""
        return None

    def prepare_common(self):
        """Shared initial_setup preamble: resolve the allocation string and
        default the tokenizer to the main model's HF path."""
        self.resolve_allocation()
        main = self._main_model()
        if (
            self.tokenizer_path is None
            and main is not None
            and main.type_ == "hf"
        ):
            self.tokenizer_path = main.args["path"]

    def _heuristic_model_config(self):
        """TransformerConfig of the trained model, or None when the
        experiment cannot derive one."""
        return model_config_from_abstraction(self._main_model())

    def _heuristic_tokens_per_step(self) -> int:
        return 32768

    def _heuristic_gen_fraction(self) -> Optional[float]:
        """Fraction of devices carved out for generation (async RL)."""
        return None

    def _solve_heuristic_allocation(self):
        cfg = self._heuristic_model_config()
        if cfg is None:
            raise ValueError(
                "allocation_mode='heuristic' is not supported by "
                f"{type(self).__name__} (no model footprint); pass an "
                "explicit strategy string like 'd2f2m1'"
            )
        import jax

        from areal_tpu.api.allocation import (
            ModelFootprint,
            search_allocation,
        )

        stats = {}
        try:
            stats = jax.local_devices()[0].memory_stats() or {}
        except Exception:  # noqa: BLE001 - backend-dependent
            pass
        hbm = float(stats.get("bytes_limit", 16e9))
        return search_allocation(
            len(jax.devices()),
            ModelFootprint.from_config(cfg),
            self._heuristic_tokens_per_step(),
            hbm_bytes=hbm,
            decoupled_gen_fraction=self._heuristic_gen_fraction(),
        )

    def apply_device_overrides(self):
        """``force_cpu_devices=N``: run on N virtual CPU devices.  Must be
        called before the first use of jax (the device count is fixed at
        backend init)."""
        if self.force_cpu_devices:
            import jax

            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_num_cpu_devices", self.force_cpu_devices)

    def model_worker_names(self) -> List[str]:
        return [f"model_worker_{i}" for i in range(self.n_model_workers)]

    def build_model_workers(
        self,
        shards: List[system_api.ModelShard],
        interfaces: Dict,
        datasets: List[DatasetAbstraction],
    ) -> List[ModelWorkerConfig]:
        names = self.model_worker_names()
        return [
            ModelWorkerConfig(
                worker_name=name,
                shards=shards,
                interfaces=interfaces,
                datasets=datasets,
                tokenizer_path=self.tokenizer_path,
                dataset_seed=self.seed,
                dataset_shard=(i, len(names)),
                seed=self.seed,
            )
            for i, name in enumerate(names)
        ]

    def make_config(self, rpcs, model_workers) -> ExperimentConfig:
        return ExperimentConfig(
            experiment_name=self.experiment_name,
            trial_name=self.trial_name,
            master=MasterWorkerConfig(
                model_rpcs=rpcs,
                exp_ctrl=self.exp_ctrl,
                seed=self.seed,
            ),
            model_workers=model_workers,
            evaluator=self.evaluator,
        ).lazy_init()
