"""Laguna HF adapter (``LagunaForCausalLM``; Laguna-XS.2): a stack stated
by kind in which ``layer_types[l]`` makes layer ``l`` a full-attention or
a WINDOW layer (``sliding_attention``: ``i - j < sliding_window``) and
``num_attention_heads_per_layer[l]`` gives its query heads, ONE count a
kind (48 full, 64 window on the same 8 KV heads of 128), so the two kinds
have a parameter stack each (``params["attn"]``, ``params["window"]``).
``rope_parameters`` states a rope rule a kind: the full layers rotate the
leading ``partial_rotary_factor`` of a head under YaRN, the window layers
the whole head, plain.  ``gating: true`` is read as the gated-attention
rule: one sigmoid gate a head from the layer's normed input, on the head's
output before ``o_proj``.  ``mlp_layer_types`` puts a dense MLP on the
leading layers and experts on the rest: ``num_experts`` routed by sigmoid
scores (top k of score + a choice bias, weights the scores renormalised
times ``moe_routed_scaling_factor``; one group) beside a shared expert.
The model code is ``areal_tpu/models/hybrid.py``; such a stack is TRAINED
(one chip's share) and not served.

HF names -> ours (``i`` the layer, ``j`` its number among its kind, ``e``
among the expert layers; the names are the family's convention, the
catalog row carries none):

    model.layers.{i}.input_layernorm            layers.attn_norm[i]
    model.layers.{i}.post_attention_layernorm   layers.mlp_norm[i]
    ...self_attn.{q,k,v,o}_proj [out, in]       {attn,window}.{q,k,v,o}.w[j]  (transposed)
    ...self_attn.g_proj [H, D]                  {attn,window}.gate.w[j] [D, H]
    ...mlp.{gate,up,down}_proj (dense layers)   dense.{gate,up,down}.w
    ...mlp.gate.weight [E, D]                   layers.mlp.router.w[e] [D, E]
    ...mlp.gate.e_score_correction_bias [E]     layers.mlp.router.bias[e]
    ...mlp.experts.{x}.{gate,up}_proj [F, D]    layers.mlp.experts.{gate,up}[e, x]
    ...mlp.experts.{x}.down_proj [D, F]         layers.mlp.experts.down[e, x] [F, D]
    ...mlp.shared_experts.{gate,up,down}_proj   layers.mlp.shared.{gate,up,down}.w[e]

A config that holds a share of the experts (``moe_held_experts``) imports
its own experts' rows of a full checkpoint and cannot export one.
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    register_hf_family,
    to_np,
)

KINDS = {"full_attention": "attention", "sliding_attention": "window"}


def _one(values, what: str):
    values = sorted(set(values))
    if len(values) != 1:
        raise NotImplementedError(f"laguna: {what} differ inside a kind: {values}")
    return values[0]


def _config_from_hf(hf: Dict[str, Any]) -> TransformerConfig:
    if hf.get("attention_bias"):
        raise NotImplementedError("laguna with attention biases")
    if hf.get("moe_apply_router_weight_on_input"):
        raise NotImplementedError(
            "laguna with the router's weights on the experts' INPUT: the "
            "expert layer weights the outputs"
        )
    L = hf["num_hidden_layers"]
    kinds = [KINDS[t] for t in hf["layer_types"]]
    heads = list(hf["num_attention_heads_per_layer"])
    mlps = list(hf["mlp_layer_types"])
    assert len(kinds) == L == len(heads) == len(mlps), (L, len(kinds))
    n_dense = mlps.index("sparse") if "sparse" in mlps else L
    if "dense" in mlps[n_dense:]:
        raise NotImplementedError("laguna: a dense MLP after an expert layer")
    full = _one([h for h, k in zip(heads, kinds) if k == "attention"], "heads")
    swa = _one([h for h, k in zip(heads, kinds) if k == "window"], "heads")
    rope = hf["rope_parameters"]
    rf, rw = rope["full_attention"], rope["sliding_attention"]
    if rw.get("rope_type", "default") != "default" or float(
        rw.get("partial_rotary_factor", 1)
    ) != 1.0:
        raise NotImplementedError(f"laguna: window layers' rope {rw}")
    hd = hf["head_dim"]
    yarn = {}
    if rf.get("rope_type") == "yarn":
        factor = float(rf["factor"])
        # cos and sin times ``attention_factor``: 0.1 mscale ln(factor) + 1
        m = (float(rf.get("attention_factor", 0.1 * np.log(factor) + 1.0)) - 1.0) / (
            0.1 * np.log(factor)
        )
        yarn = dict(
            rope_yarn_factor=factor,
            rope_yarn_original_max=int(rf["original_max_position_embeddings"]),
            rope_yarn_beta_fast=float(rf.get("beta_fast", 32)),
            rope_yarn_beta_slow=float(rf.get("beta_slow", 1)),
            rope_yarn_mscale=float(m),
        )
    elif rf.get("rope_type", "default") != "default":
        raise NotImplementedError(f"laguna: full layers' rope {rf}")
    partial = int(round(float(rf.get("partial_rotary_factor", 1)) * hd))
    gate = "headwise" if hf.get("gating") else None
    return TransformerConfig(
        n_layers=L,
        hidden_dim=hf["hidden_size"],
        n_q_heads=full,
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hd,
        intermediate_dim=hf["intermediate_size"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        shared_expert_dim=hf.get("shared_expert_intermediate_size", 0),
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 262144),
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        tied_embedding=hf.get("tie_word_embeddings", False),
        n_experts=hf["num_experts"],
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_router="sigmoid_group",
        moe_routed_scale=float(hf.get("moe_routed_scaling_factor", 1.0)),
        n_dense_layers=n_dense,
        sliding_window=hf["sliding_window"],
        layer_types=tuple(kinds),
        rotary_base=float(rf["rope_theta"]),
        rope_partial_dim=0 if partial == hd else partial,
        swa_n_q_heads=swa,
        swa_rotary_base=float(rw["rope_theta"]),
        attention_gate=gate,
        swa_attention_gate=gate,
        **yarn,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    wcfg = cfg.window_plain()
    hd = cfg.head_dim
    full = {"rope_type": "default", "rope_theta": cfg.rotary_base}
    if cfg.rope_yarn_factor:
        full = {
            "rope_theta": cfg.rotary_base, "rope_type": "yarn",
            "factor": cfg.rope_yarn_factor,
            "original_max_position_embeddings": cfg.rope_yarn_original_max,
            "beta_slow": cfg.rope_yarn_beta_slow,
            "beta_fast": cfg.rope_yarn_beta_fast,
            "attention_factor": 0.1 * cfg.rope_yarn_mscale
            * float(np.log(cfg.rope_yarn_factor)) + 1.0,
        }
    full["partial_rotary_factor"] = (cfg.rope_partial_dim or hd) / hd
    return dict(
        architectures=["LagunaForCausalLM"],
        model_type="laguna",
        hidden_size=cfg.hidden_dim,
        intermediate_size=cfg.intermediate_dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=hd,
        num_attention_heads_per_layer=[
            wcfg.n_q_heads if t == "window" else cfg.n_q_heads
            for t in cfg.layer_types
        ],
        layer_types=[
            "sliding_attention" if t == "window" else "full_attention"
            for t in cfg.layer_types
        ],
        mlp_layer_types=["dense"] * cfg.n_dense_layers
        + ["sparse"] * cfg.n_expert_layers,
        num_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_dim,
        shared_expert_intermediate_size=cfg.shared_expert_dim,
        moe_routed_scaling_factor=cfg.moe_routed_scale,
        moe_apply_router_weight_on_input=False,
        gating=cfg.attention_gate == "headwise",
        sliding_window=cfg.sliding_window,
        rope_parameters={
            "full_attention": full,
            "sliding_attention": {
                "rope_type": "default", "rope_theta": wcfg.rotary_base,
                "partial_rotary_factor": 1,
            },
        },
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.norm_eps,
        attention_bias=False,
        tie_word_embeddings=cfg.tied_embedding,
        torch_dtype="bfloat16",
    )


_ATTN = ("q", "k", "v", "o")
_MLP3 = ("gate", "up", "down")


def _layers_of(cfg: TransformerConfig, kind: str):
    return [l for l, t in enumerate(cfg.layer_types) if t == kind]


def _params_from_hf(state: StateDict, cfg: TransformerConfig) -> Dict[str, Any]:
    g = lambda n: to_np(state[n])
    T = lambda m: m.T
    pre = "model.layers.{i}."
    held = range(cfg.moe_first_expert, cfg.moe_first_expert + cfg.n_held_experts)

    def stack(layers, name, fn=lambda m: m):
        return jnp.asarray(
            np.stack([fn(g((pre + name).format(i=i))) for i in layers])
        )

    every = range(cfg.n_layers)
    dense, sparse = range(cfg.n_dense_layers), range(cfg.n_dense_layers, cfg.n_layers)

    def mixers(kind):
        layers = _layers_of(cfg, kind)
        out = {
            n: {"w": stack(layers, f"self_attn.{n}_proj.weight", T)} for n in _ATTN
        }
        if cfg.attention_gate:
            out["gate"] = {"w": stack(layers, "self_attn.g_proj.weight", T)}
        return out

    def experts(name, fn=lambda m: m):  # -> [Le, E_held, F, D]
        fmt = pre + "mlp.experts.{e}." + name + "_proj.weight"
        return jnp.asarray(
            np.stack(
                [
                    np.stack([fn(g(fmt.format(i=i, e=e))) for e in held])
                    for i in sparse
                ]
            )
        )

    params: Dict[str, Any] = {
        "embed": {"weight": jnp.asarray(g("model.embed_tokens.weight"))},
        "layers": {
            "attn_norm": {"scale": stack(every, "input_layernorm.weight")},
            "mlp_norm": {"scale": stack(every, "post_attention_layernorm.weight")},
            "mlp": {
                "router": {
                    "w": stack(sparse, "mlp.gate.weight", T),
                    "bias": stack(sparse, "mlp.gate.e_score_correction_bias"),
                },
                "experts": {
                    "gate": experts("gate"), "up": experts("up"),
                    "down": experts("down", T),
                },
                "shared": {
                    n: {"w": stack(sparse, f"mlp.shared_experts.{n}_proj.weight", T)}
                    for n in _MLP3
                },
            },
        },
        "attn": mixers("attention"),
        "window": mixers("window"),
        "dense": {
            n: {"w": stack(dense, f"mlp.{n}_proj.weight", T)} for n in _MLP3
        },
        "final_norm": {"scale": jnp.asarray(g("model.norm.weight"))},
    }
    if not cfg.tied_embedding:
        params["lm_head"] = {"w": jnp.asarray(g("lm_head.weight").T)}
    return params


def _params_to_hf(params: Dict[str, Any], cfg: TransformerConfig) -> StateDict:
    if cfg.n_held_experts != cfg.n_experts:
        raise ValueError(
            f"this tree holds {cfg.n_held_experts} of {cfg.n_experts} "
            "experts a layer: a share of a deployment cannot be exported "
            "as a checkpoint"
        )
    np_ = lambda x: np.asarray(x, np.float32)
    out: StateDict = {
        "model.embed_tokens.weight": np_(params["embed"]["weight"]),
        "model.norm.weight": np_(params["final_norm"]["scale"]),
    }
    if not cfg.tied_embedding:
        out["lm_head.weight"] = np_(params["lm_head"]["w"]).T
    lay, mlp = params["layers"], params["layers"]["mlp"]
    at = {"attention": 0, "window": 0}
    for i, kind in enumerate(cfg.layer_types):
        pre = f"model.layers.{i}."
        out[pre + "input_layernorm.weight"] = np_(lay["attn_norm"]["scale"][i])
        out[pre + "post_attention_layernorm.weight"] = np_(
            lay["mlp_norm"]["scale"][i]
        )
        ap, j = params["attn" if kind == "attention" else "window"], at[kind]
        at[kind] += 1
        for n in _ATTN:
            out[pre + f"self_attn.{n}_proj.weight"] = np_(ap[n]["w"][j]).T
        if "gate" in ap:
            out[pre + "self_attn.g_proj.weight"] = np_(ap["gate"]["w"][j]).T
        if i < cfg.n_dense_layers:
            for n in _MLP3:
                out[pre + f"mlp.{n}_proj.weight"] = np_(params["dense"][n]["w"][i]).T
            continue
        e = i - cfg.n_dense_layers
        out[pre + "mlp.gate.weight"] = np_(mlp["router"]["w"][e]).T
        out[pre + "mlp.gate.e_score_correction_bias"] = np_(mlp["router"]["bias"][e])
        for n in _MLP3:
            out[pre + f"mlp.shared_experts.{n}_proj.weight"] = np_(
                mlp["shared"][n]["w"][e]
            ).T
        for x in range(cfg.n_experts):
            ex = pre + f"mlp.experts.{x}."
            out[ex + "gate_proj.weight"] = np_(mlp["experts"]["gate"][e, x])
            out[ex + "up_proj.weight"] = np_(mlp["experts"]["up"][e, x])
            out[ex + "down_proj.weight"] = np_(mlp["experts"]["down"][e, x]).T
    return out


register_hf_family(
    HFFamily(
        name="laguna",
        hf_architecture="LagunaForCausalLM",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    )
)
