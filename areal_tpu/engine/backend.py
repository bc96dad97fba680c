"""Model factory + train/inference backends.

Rebuild of the reference's backend layer (reference:
realhf/impl/model/backend/megatron.py ``MegatronTrainBackend`` :561,
realhf/impl/model/backend/inference.py ``PipelinableInferenceEngine`` :230,
realhf/api/core/model_api.py ``make_model`` :928): a backend turns a raw
(config, params) bundle into an engine with train_batch/forward_batch; on
TPU both are the sharded ``TrainEngine`` (the inference variant simply has
no optimizer state).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np

from areal_tpu.api import model_api
from areal_tpu.api.config import ModelAbstraction, ModelName
from areal_tpu.base import logging_
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.engine.train_engine import TrainEngine
from areal_tpu.models.config import TransformerConfig, tiny_config

logger = logging_.getLogger("backend")


def host_device():
    """The host-memory (CPU backend) device model weights are BUILT on.

    A freshly loaded or randomly initialized tree is whole and float32;
    built on the default accelerator it would sit entire on chip 0 before
    any mesh, ``device_idx`` or serving dtype is applied (a trainer and a
    generation server sharing one chip would each do so at once).  Built
    here, each engine's own placement (``tree_put_global`` /
    ``device_put`` onto its shardings) is the first time the weights
    touch an accelerator, already cut to that engine's shards."""
    return jax.local_devices(backend="cpu")[0]


def cast_floating(params, dtype):
    """Cast a host-resident param tree's floating leaves to ``dtype``
    without leaving the host (a serving copy is placed at model dtype,
    never as float32 masters)."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    with jax.default_device(host_device()):
        return jax.tree.map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dtype
            else x,
            params,
        )


def refuse_unserved(model_cfg: TransformerConfig):
    """Raise, by name, for a stack no generation server is written for."""
    if model_cfg.is_hybrid and model_cfg.window_has_own_widths:
        raise NotImplementedError(
            "a generation server cannot serve a stack whose window layers "
            f"have {model_cfg.swa_n_q_heads} query heads and a rope rule of "
            f"their own beside full layers of {model_cfg.n_q_heads}: the "
            "paged programs read both kinds from one parameter stack at one "
            "head count (such a stack is TRAINED, on the `train` backend; "
            "ROADMAP R9 has what serving it needs)"
        )


def make_model(
    cfg: ModelAbstraction,
    name: ModelName,
    mesh,
    tokenizer=None,
) -> model_api.Model:
    """Build an uninitialized Model bundle.

    Abstraction types:
      - ``hf``: args {path, is_critic?, dtype?, plus any TransformerConfig
        field (remat, remat_policy, pipe_microbatches, cp_impl, ...) as a
        post-load override} — HF checkpoint dir; unknown keys raise
      - ``random``: args {config: dict | TransformerConfig kwargs, seed?} —
        random init (tests / from-scratch)
    """
    if cfg.type_ == "null":
        # engine-less bundle for rule-based interfaces (e.g. the math reward
        # verifier needs only the tokenizer)
        model = model_api.Model(
            name=name, engine=None, tokenizer=tokenizer, mesh=mesh
        )
        model.model_cfg = tiny_config()
        return model
    if cfg.type_ == "hf":
        from areal_tpu.models.hf.registry import load_hf_config, load_hf_model

        # every TransformerConfig field is a post-load override (remat,
        # remat_policy, pipe_microbatches, cp_impl, ...); unknown keys are
        # typos and must fail BEFORE the multi-GB checkpoint read
        cfg_fields = {f.name for f in dataclasses.fields(TransformerConfig)}
        unknown = set(cfg.args) - cfg_fields - {"path", "is_critic", "dtype"}
        if unknown:
            raise ValueError(
                f"unknown hf model args {sorted(unknown)}; valid: path, "
                f"is_critic, dtype, or any TransformerConfig field"
            )
        load_overrides = {
            k: v for k, v in cfg.args.items() if k in ("is_critic", "dtype")
        }
        with jax.default_device(host_device()):
            model_cfg, params = load_hf_model(
                cfg.args["path"], **load_overrides
            )
        post = {
            k: v
            for k, v in cfg.args.items()
            if k in cfg_fields and k not in load_overrides
        }
        if post:
            model_cfg = dataclasses.replace(model_cfg, **post)
        family, _, _ = load_hf_config(cfg.args["path"])
        backend_name = family.name
    elif cfg.type_ == "random":
        args = dict(cfg.args)
        seed = args.pop("seed", 0)
        conf = args.pop("config", None)
        if isinstance(conf, TransformerConfig):
            model_cfg = conf
        elif conf is not None:
            model_cfg = TransformerConfig(**conf)
        else:
            model_cfg = tiny_config(**args)
        if model_cfg.is_hybrid:
            # a stack stated by kind: made in the model's dtype where
            # jax's default device is (a server's chip), kind by kind; a
            # float32 host copy of a 5 B-parameter share is 20 GB
            from areal_tpu.models import hybrid

            params = hybrid.init_params(model_cfg, jax.random.PRNGKey(seed))
            backend_name = (
                "dots3_note" if model_cfg.is_latent_window
                or model_cfg.is_indexed
                else "deepseek_v3" if model_cfg.is_latent
                else "phi4flash" if model_cfg.is_mamba1
                else "falcon_h1" if model_cfg.n_parallel_layers
                else "laguna" if model_cfg.window_has_own_widths
                else "smallthinker" if model_cfg.n_window_layers
                else "granitemoehybrid"
            )
        elif model_cfg.sandwich_norm:
            # a looped dense stack (``ouro``): made in the model's dtype
            # where jax's default device is, as a stack stated by kind
            from areal_tpu.models.transformer import init_params_in_dtype

            params = init_params_in_dtype(model_cfg, jax.random.PRNGKey(seed))
            backend_name = "ouro"
        else:
            from areal_tpu.models.transformer import init_params

            with jax.default_device(host_device()):
                params = init_params(model_cfg, jax.random.PRNGKey(seed))
            backend_name = "llama"
    else:
        raise ValueError(f"unknown model abstraction {cfg.type_}")

    model = model_api.Model(
        name=name,
        engine=None,
        tokenizer=tokenizer,
        mesh=mesh,
        backend_name=backend_name,
    )
    model.model_cfg = model_cfg
    model.init_params = params
    return model


@dataclasses.dataclass
class TrainBackend(model_api.ModelBackend):
    """Sharded train engine with optimizer (reference: megatron.py:561)."""

    optimizer: OptimizerConfig = dataclasses.field(
        default_factory=OptimizerConfig
    )
    #: FFD segment packing of train/forward micro-batches (multi-segment
    #: rows; see docs/parallelism.md "Training batch layout").  On by
    #: default.
    pack_sequences: bool = True

    def _initialize(self, model, spec):
        model.engine = TrainEngine(
            model.model_cfg,
            model.mesh,
            model.init_params,
            optimizer_cfg=self.optimizer,
            total_train_steps=max(1, spec.total_train_steps),
            name=str(model.name) if model.name else "",
            pack_sequences=self.pack_sequences,
        )
        model.init_params = None
        return model

    def save(self, model, save_dir: str):
        import os

        model.engine.save_train_state(os.path.join(save_dir, "train_state"))

    def load(self, model, load_dir: str):
        import os

        model.engine.load_train_state(os.path.join(load_dir, "train_state"))


@dataclasses.dataclass
class InferenceBackend(model_api.ModelBackend):
    """Engine without optimizer state (reference: inference.py:230)."""

    pack_sequences: bool = True

    def _initialize(self, model, spec):
        model.engine = TrainEngine(
            model.model_cfg,
            model.mesh,
            model.init_params,
            optimizer_cfg=None,
            name=str(model.name) if model.name else "",
            pack_sequences=self.pack_sequences,
        )
        model.init_params = None
        return model


@dataclasses.dataclass
class NullBackend(model_api.ModelBackend):
    """No-op backend for engine-less roles (rule-based reward)."""

    def _initialize(self, model, spec):
        return model


model_api.register_backend("train", TrainBackend)
model_api.register_backend("inference", InferenceBackend)
model_api.register_backend("null", NullBackend)
