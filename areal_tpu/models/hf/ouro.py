"""The ``ouro`` HF adapter (``OuroForCausalLM``: ByteDance's Ouro-1.4B /
Ouro-2.6B looped language models, arXiv 2510.25741).

A llama-like dense decoder (no biases, RoPE over the whole head, an untied
head) whose ``num_hidden_layers`` layers are run ``total_ut_steps`` times
with the same weights (``TransformerConfig.loop_steps``), with SANDWICH
norms: beside ``input_layernorm`` and ``post_attention_layernorm`` on each
branch's input, ``input_layernorm_2`` and ``post_attention_layernorm_2``
on its output before the residual add.  ``model.norm`` follows every pass,
and ``model.early_exit_gate`` (a ``Linear(hidden, 1)`` with bias) reads
each pass's normed output; at the published ``early_exit_threshold`` 1 no
token leaves before the last pass, so the gate's weights are held and
converted and never evaluated (any other threshold is refused:
``TransformerConfig.__post_init__``).  The parameter names are the
family's modelling code's (``modeling_ouro.py``), as this builder knows
them; the config carries no key for the norms, the gate or the per-pass
cache.
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.hf.llama_like import (
    _llama_like_config_from_hf,
    _llama_like_config_to_hf,
    _params_from_hf,
    _params_to_hf,
)
from areal_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    register_hf_family,
    stack_layers,
    to_np,
)

#: ours -> theirs, the two norms on a branch's OUTPUT
_POST_NORMS = (
    ("attn_post_norm", "input_layernorm_2"),
    ("mlp_post_norm", "post_attention_layernorm_2"),
)


def _config_from_hf(hf: Dict[str, Any]) -> TransformerConfig:
    if hf.get("rope_scaling"):
        raise NotImplementedError(
            f"ouro with rope_scaling {hf['rope_scaling']!r}: the published "
            "config has none"
        )
    return _llama_like_config_from_hf(
        hf,
        loop_steps=int(hf.get("total_ut_steps", 1)),
        sandwich_norm=True,
        loop_exit_gate=True,
        loop_exit_threshold=float(hf.get("early_exit_threshold", 1.0)),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    return _llama_like_config_to_hf(
        cfg, "ouro", "OuroForCausalLM",
        total_ut_steps=cfg.loop_steps,
        early_exit_threshold=cfg.loop_exit_threshold,
        use_sliding_window=False, sliding_window=None, rope_scaling=None,
    )


def _from_hf(state: StateDict, cfg: TransformerConfig) -> Dict[str, Any]:
    params = _params_from_hf(state, cfg)
    for ours, theirs in _POST_NORMS:
        params["layers"][ours] = {
            "scale": jnp.asarray(
                stack_layers(
                    [
                        to_np(state[f"model.layers.{i}.{theirs}.weight"])
                        for i in range(cfg.n_layers)
                    ]
                )
            )
        }
    params["exit_gate"] = {
        # torch [out = 1, in] -> ours [in, 1]
        "w": jnp.asarray(to_np(state["model.early_exit_gate.weight"]).T),
        "b": jnp.asarray(to_np(state["model.early_exit_gate.bias"])),
    }
    return params


def _to_hf(params: Dict[str, Any], cfg: TransformerConfig) -> StateDict:
    out = _params_to_hf(params, cfg)
    np_ = lambda x: np.asarray(x, dtype=np.float32)
    for ours, theirs in _POST_NORMS:
        for i in range(cfg.n_layers):
            out[f"model.layers.{i}.{theirs}.weight"] = np_(
                params["layers"][ours]["scale"][i]
            )
    out["model.early_exit_gate.weight"] = np_(params["exit_gate"]["w"]).T
    out["model.early_exit_gate.bias"] = np_(params["exit_gate"]["b"])
    return out


register_hf_family(
    HFFamily(
        name="ouro",
        hf_architecture="OuroForCausalLM",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_from_hf,
        params_to_hf=_to_hf,
    )
)
