"""SmallThinker HF adapter (``SmallThinkerForCausalLM``;
SmallThinker-21BA3B / 4BA0.6B): a stack stated by kind in which
``sliding_window_layout[l] == 1`` makes layer ``l`` a WINDOW layer
(``i - j < sliding_window_size``) and 0 a global one, ``rope_layout[l]``
says whether the layer ropes q and k (the global layers have no position
term), every layer has ``moe_num_primary_experts`` ReLU-gated experts with
``moe_num_active_primary_experts`` a token and no shared expert, and the
ROUTER reads the attention's input.  The model code is
``areal_tpu/models/hybrid.py``; the attention and window layers share one
parameter stack there (``params["attn"]``, in layer order).

HF names -> ours (``i`` the layer):

    model.layers.{i}.input_layernorm            layers.attn_norm[i]
    model.layers.{i}.post_attention_layernorm   layers.mlp_norm[i]
    ...self_attn.{q,k,v,o}_proj [out, in]       attn.{q,k,v,o}.w[i]  (transposed)
    ...block_sparse_moe.primary_router [E, D]   layers.mlp.router.w[i] [D, E]
    ...block_sparse_moe.experts.{e}.{gate,up} [F, D]   layers.mlp.experts.{gate,up}[i, e]
    ...block_sparse_moe.experts.{e}.down [D, F]        layers.mlp.experts.down[i, e] [F, D]

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


def _config_from_hf(hf: Dict[str, Any]) -> TransformerConfig:
    if hf.get("rope_scaling"):
        raise NotImplementedError("smallthinker with rope_scaling")
    if not hf.get("moe_primary_router_apply_softmax", True) or not hf.get(
        "norm_topk_prob", True
    ):
        raise NotImplementedError(
            "smallthinker routes by softmax over the experts, top k, "
            "renormalised (the softmax of the top k logits); a sigmoid or "
            "un-normalised router is not written"
        )
    if hf.get("attention_bias"):
        raise NotImplementedError("smallthinker with attention biases")
    n_heads, L = hf["num_attention_heads"], hf["num_hidden_layers"]
    window_layout = list(hf["sliding_window_layout"])
    rope_layout = list(hf["rope_layout"])
    assert len(window_layout) == L == len(rope_layout), (L, window_layout)
    return TransformerConfig(
        n_layers=L,
        hidden_dim=hf["hidden_size"],
        n_q_heads=n_heads,
        n_kv_heads=hf.get("num_key_value_heads", n_heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // n_heads,
        intermediate_dim=hf["moe_ffn_hidden_size"],
        moe_intermediate_dim=hf["moe_ffn_hidden_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 16384),
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        rotary_base=hf.get("rope_theta", 10000.0),
        tied_embedding=hf.get("tie_word_embeddings", False),
        activation="relu",
        n_experts=hf["moe_num_primary_experts"],
        n_experts_per_tok=hf["moe_num_active_primary_experts"],
        moe_router="topk_softmax",
        moe_router_input="attn",
        sliding_window=hf["sliding_window_size"],
        layer_types=tuple(
            "window" if w else "attention" for w in window_layout
        ),
        rope_layers=tuple(bool(r) for r in rope_layout),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    return dict(
        architectures=["SmallThinkerForCausalLM"],
        model_type="smallthinker",
        hidden_size=cfg.hidden_dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        moe_ffn_hidden_size=cfg.moe_intermediate_dim,
        moe_num_primary_experts=cfg.n_experts,
        moe_num_active_primary_experts=cfg.n_experts_per_tok,
        moe_primary_router_apply_softmax=True,
        norm_topk_prob=True,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rotary_base,
        rope_scaling=None,
        rope_layout=[int(cfg.layer_ropes(l)) for l in range(cfg.n_layers)],
        sliding_window_layout=[int(t == "window") for t in cfg.layer_types],
        sliding_window_size=cfg.sliding_window,
        tie_word_embeddings=cfg.tied_embedding,
        torch_dtype="bfloat16",
    )


_MOE = "block_sparse_moe."


def _params_from_hf(state: StateDict, cfg: TransformerConfig) -> Dict[str, Any]:
    g = lambda n: to_np(state[n])
    every = range(cfg.n_layers)
    held = range(cfg.moe_first_expert, cfg.moe_first_expert + cfg.n_held_experts)
    pre = "model.layers.{i}."

    def stack(name, fn=lambda m: m):
        return jnp.asarray(
            np.stack([fn(g((pre + name).format(i=i))) for i in every])
        )

    def experts(name, fn=lambda m: m):  # -> [L, E_held, F, D]
        fmt = pre + _MOE + "experts.{e}." + name + ".weight"
        return jnp.asarray(
            np.stack(
                [
                    np.stack([fn(g(fmt.format(i=i, e=e))) for e in held])
                    for i in every
                ]
            )
        )

    T = lambda m: m.T
    params: Dict[str, Any] = {
        "embed": {"weight": jnp.asarray(g("model.embed_tokens.weight"))},
        "layers": {
            "attn_norm": {"scale": stack("input_layernorm.weight")},
            "mlp_norm": {"scale": stack("post_attention_layernorm.weight")},
            "mlp": {
                "router": {"w": stack(_MOE + "primary_router.weight", T)},
                "experts": {
                    "gate": experts("gate"),
                    "up": experts("up"),
                    "down": experts("down", T),
                },
            },
        },
        "attn": {
            ours: {"w": stack(f"self_attn.{ours}_proj.weight", T)}
            for ours in ("q", "k", "v", "o")
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
    for i in range(cfg.n_layers):
        pre = f"model.layers.{i}."
        out[pre + "input_layernorm.weight"] = np_(lay["attn_norm"]["scale"][i])
        out[pre + "post_attention_layernorm.weight"] = np_(
            lay["mlp_norm"]["scale"][i]
        )
        for ours in ("q", "k", "v", "o"):
            out[pre + f"self_attn.{ours}_proj.weight"] = np_(
                params["attn"][ours]["w"][i]
            ).T
        out[pre + _MOE + "primary_router.weight"] = np_(mlp["router"]["w"][i]).T
        for e in range(cfg.n_experts):
            ex = pre + _MOE + f"experts.{e}."
            out[ex + "gate.weight"] = np_(mlp["experts"]["gate"][i, e])
            out[ex + "up.weight"] = np_(mlp["experts"]["up"][i, e])
            out[ex + "down.weight"] = np_(mlp["experts"]["down"][i, e]).T
    return out


register_hf_family(
    HFFamily(
        name="smallthinker",
        hf_architecture="SmallThinkerForCausalLM",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    )
)
