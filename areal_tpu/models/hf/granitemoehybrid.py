"""GraniteMoeHybrid HF adapter (``GraniteMoeHybridForCausalLM``;
granite-4.0-h): a stack stated by kind (``layer_types``: "mamba" |
"attention"), every layer followed by routed experts and a shared expert,
four multipliers, no position term.  The model code is
``areal_tpu/models/hybrid.py``; parameters stack BY KIND there.

HF names -> ours (``i`` the layer, ``j`` its number among its kind):

    model.layers.{i}.input_layernorm          layers.attn_norm[i]
    model.layers.{i}.post_attention_layernorm layers.mlp_norm[i]
    ...mamba.in_proj   [z | xBC | dt]         mamba.in_proj.w[j]   (transposed)
    ...mamba.conv1d.weight [cd, 1, K], .bias  mamba.conv.w[j] [K, cd], .b[j]
    ...mamba.A_log / D / dt_bias / norm       mamba.A_log[j] / D / dt_bias / norm.scale
    ...mamba.out_proj                         mamba.out_proj.w[j]  (transposed)
    ...self_attn.{q,k,v,o}_proj               attn.{q,k,v,o}.w[j]  (transposed)
    ...block_sparse_moe.router.layer [E, D]   layers.mlp.router.w[i] [D, E]
    ...block_sparse_moe.input_linear [E, 2F, D]   layers.mlp.experts.{gate,up}[i] [E, F, D]
    ...block_sparse_moe.output_linear [E, D, F]   layers.mlp.experts.down[i] [E, F, D]
    ...shared_mlp.input_linear [2Fs, D]       layers.mlp.shared.{gate,up}.w[i]
    ...shared_mlp.output_linear [D, Fs]       layers.mlp.shared.down.w[i]

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
    if hf.get("position_embedding_type", "nope") != "nope":
        raise NotImplementedError(
            "granitemoehybrid with a position term "
            f"({hf['position_embedding_type']!r}) is not supported: the "
            "hybrid stack's attention is written without one"
        )
    if hf.get("mamba_n_groups", 1) != 1:
        raise NotImplementedError(
            "granitemoehybrid with mamba_n_groups != 1 is not supported"
        )
    if hf.get("attention_bias") or hf.get("mamba_proj_bias"):
        raise NotImplementedError(
            "granitemoehybrid with projection biases is not supported: the "
            "adapter would silently drop them"
        )
    if not hf.get("mamba_conv_bias", True):
        raise NotImplementedError("granitemoehybrid without a conv bias")
    n_heads = hf["num_attention_heads"]
    return TransformerConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=n_heads,
        n_kv_heads=hf.get("num_key_value_heads", n_heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // n_heads,
        intermediate_dim=hf["intermediate_size"],
        moe_intermediate_dim=hf["intermediate_size"],
        shared_expert_dim=hf.get("shared_intermediate_size", 0),
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 131072),
        norm_eps=hf.get("rms_norm_eps", 1e-5),
        tied_embedding=hf.get("tie_word_embeddings", True),
        n_experts=hf["num_local_experts"],
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_router="topk_softmax",
        layer_types=tuple(hf["layer_types"]),
        mamba_n_heads=hf["mamba_n_heads"],
        mamba_head_dim=hf["mamba_d_head"],
        mamba_d_state=hf["mamba_d_state"],
        mamba_n_groups=hf.get("mamba_n_groups", 1),
        mamba_d_conv=hf["mamba_d_conv"],
        mamba_chunk_size=hf.get("mamba_chunk_size", 256),
        embed_scale=hf.get("embedding_multiplier"),
        attention_scale=hf.get("attention_multiplier"),
        residual_scale=hf.get("residual_multiplier"),
        logits_divisor=hf.get("logits_scaling"),
        use_rope=False,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    return dict(
        architectures=["GraniteMoeHybridForCausalLM"],
        model_type="granitemoehybrid",
        hidden_act="silu",
        hidden_size=cfg.hidden_dim,
        intermediate_size=cfg.moe_intermediate_dim,
        shared_intermediate_size=cfg.shared_expert_dim,
        num_hidden_layers=cfg.n_layers,
        layer_types=list(cfg.layer_types),
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.norm_eps,
        tie_word_embeddings=cfg.tied_embedding,
        num_local_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        mamba_n_heads=cfg.mamba_n_heads,
        mamba_d_head=cfg.mamba_head_dim,
        mamba_d_state=cfg.mamba_d_state,
        mamba_n_groups=cfg.mamba_n_groups,
        mamba_d_conv=cfg.mamba_d_conv,
        mamba_chunk_size=cfg.mamba_chunk_size,
        mamba_expand=cfg.mamba_d_inner // cfg.hidden_dim,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
        attention_bias=False,
        embedding_multiplier=cfg.embed_scale,
        attention_multiplier=cfg.attention_scale,
        residual_multiplier=cfg.residual_scale,
        logits_scaling=cfg.logits_divisor,
        position_embedding_type="nope",
        normalization_function="rmsnorm",
        torch_dtype="bfloat16",
    )


def _kind_layers(cfg: TransformerConfig, kind: str):
    return [i for i, t in enumerate(cfg.layer_types) if t == kind]


def _params_from_hf(state: StateDict, cfg: TransformerConfig) -> Dict[str, Any]:
    g = lambda n: to_np(state[n])
    L = cfg.n_layers
    Fe, Fs = cfg.moe_intermediate_dim, cfg.shared_expert_dim
    e0, e1 = cfg.moe_first_expert, cfg.moe_first_expert + cfg.n_held_experts
    pre = "model.layers.{i}."

    def stack(layers, name, fn=lambda m: m):
        return jnp.asarray(
            np.stack([fn(g((pre + name).format(i=i))) for i in layers])
        )

    every, T = range(L), (lambda m: m.T)
    moe_in = "block_sparse_moe.input_linear.weight"  # [E, 2F, D]
    mlp: Dict[str, Any] = {
        "router": {"w": stack(every, "block_sparse_moe.router.layer.weight", T)},
        "experts": {
            "gate": stack(every, moe_in, lambda m: m[e0:e1, :Fe]),
            "up": stack(every, moe_in, lambda m: m[e0:e1, Fe:]),
            "down": stack(
                every, "block_sparse_moe.output_linear.weight",
                lambda m: m[e0:e1].transpose(0, 2, 1),
            ),
        },
    }
    if Fs:
        sh_in = "shared_mlp.input_linear.weight"  # [2Fs, D]
        mlp["shared"] = {
            "gate": {"w": stack(every, sh_in, lambda m: m[:Fs].T)},
            "up": {"w": stack(every, sh_in, lambda m: m[Fs:].T)},
            "down": {"w": stack(every, "shared_mlp.output_linear.weight", T)},
        }
    mam, att = _kind_layers(cfg, "mamba"), _kind_layers(cfg, "attention")
    return {
        "embed": {"weight": jnp.asarray(g("model.embed_tokens.weight"))},
        "layers": {
            "attn_norm": {"scale": stack(every, "input_layernorm.weight")},
            "mlp_norm": {
                "scale": stack(every, "post_attention_layernorm.weight")
            },
            "mlp": mlp,
        },
        "mamba": {
            "in_proj": {"w": stack(mam, "mamba.in_proj.weight", T)},
            "conv": {
                "w": stack(mam, "mamba.conv1d.weight", lambda m: m[:, 0].T),
                "b": stack(mam, "mamba.conv1d.bias"),
            },
            "dt_bias": stack(mam, "mamba.dt_bias"),
            "A_log": stack(mam, "mamba.A_log"),
            "D": stack(mam, "mamba.D"),
            "norm": {"scale": stack(mam, "mamba.norm.weight")},
            "out_proj": {"w": stack(mam, "mamba.out_proj.weight", T)},
        },
        "attn": {
            ours: {"w": stack(att, f"self_attn.{ours}_proj.weight", T)}
            for ours in ("q", "k", "v", "o")
        },
        "final_norm": {"scale": jnp.asarray(g("model.norm.weight"))},
    }


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
    lay, mlp = params["layers"], params["layers"]["mlp"]
    for i in range(cfg.n_layers):
        pre = f"model.layers.{i}."
        out[pre + "input_layernorm.weight"] = np_(lay["attn_norm"]["scale"][i])
        out[pre + "post_attention_layernorm.weight"] = np_(
            lay["mlp_norm"]["scale"][i]
        )
        out[pre + "block_sparse_moe.router.layer.weight"] = np_(
            mlp["router"]["w"][i]
        ).T
        ex = mlp["experts"]
        out[pre + "block_sparse_moe.input_linear.weight"] = np.concatenate(
            [np_(ex["gate"][i]), np_(ex["up"][i])], axis=1
        )
        out[pre + "block_sparse_moe.output_linear.weight"] = np_(
            ex["down"][i]
        ).transpose(0, 2, 1)
        if "shared" in mlp:
            sh = mlp["shared"]
            out[pre + "shared_mlp.input_linear.weight"] = np.concatenate(
                [np_(sh["gate"]["w"][i]), np_(sh["up"]["w"][i])], axis=1
            ).T
            out[pre + "shared_mlp.output_linear.weight"] = np_(
                sh["down"]["w"][i]
            ).T
    m = params["mamba"]
    for j, i in enumerate(_kind_layers(cfg, "mamba")):
        pre = f"model.layers.{i}.mamba."
        out[pre + "in_proj.weight"] = np_(m["in_proj"]["w"][j]).T
        out[pre + "conv1d.weight"] = np_(m["conv"]["w"][j]).T[:, None, :]
        out[pre + "conv1d.bias"] = np_(m["conv"]["b"][j])
        out[pre + "dt_bias"] = np_(m["dt_bias"][j])
        out[pre + "A_log"] = np_(m["A_log"][j])
        out[pre + "D"] = np_(m["D"][j])
        out[pre + "norm.weight"] = np_(m["norm"]["scale"][j])
        out[pre + "out_proj.weight"] = np_(m["out_proj"]["w"][j]).T
    for j, i in enumerate(_kind_layers(cfg, "attention")):
        for ours in ("q", "k", "v", "o"):
            out[f"model.layers.{i}.self_attn.{ours}_proj.weight"] = np_(
                params["attn"][ours]["w"][j]
            ).T
    return out


register_hf_family(
    HFFamily(
        name="granitemoehybrid",
        hf_architecture="GraniteMoeHybridForCausalLM",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    )
)
