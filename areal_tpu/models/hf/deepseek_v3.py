"""DeepSeek-V3 HF adapter (``DeepseekV3ForCausalLM``, ``model_type``
deepseek_v3: DeepSeek-V3/R1, GigaChat3): latent attention (MLA) on every
layer, ``first_k_dense_replace`` leading layers with a dense MLP and then
sigmoid-routed, group-limited experts beside a shared expert, RoPE with
YaRN on the rope dims, an untied head.  The model code is
``areal_tpu/models/hybrid.py`` (mixer kind ``latent``); parameters stack
BY KIND there.

HF names -> ours (``i`` the layer, ``e`` its number among the expert
layers, ``d`` among the dense ones; every matrix transposed to [in, out]
unless noted):

    model.layers.{i}.input_layernorm            layers.attn_norm[i]
    model.layers.{i}.post_attention_layernorm   layers.mlp_norm[i]
    ...self_attn.q_a_proj / q_a_layernorm       latent.q_a.w[i] / q_a_norm.scale
    ...self_attn.q_b_proj                       latent.q_b.w[i]      (rope columns de-interleaved)
    ...self_attn.kv_a_proj_with_mqa             latent.kv_a.w[i]     (rope columns de-interleaved)
    ...self_attn.kv_a_layernorm                 latent.kv_a_norm.scale[i]
    ...self_attn.kv_b_proj [H (nope + v), r]    latent.k_b.w[i] [r, H nope], latent.v_b.w[i] [r, H v]
    ...self_attn.o_proj                         latent.o.w[i]
    ...mlp.{gate,up,down}_proj  (dense layers)  dense.{gate,up,down}.w[d]
    ...mlp.gate.weight [E, D]                   layers.mlp.router.w[e] [D, E]
    ...mlp.gate.e_score_correction_bias [E]     layers.mlp.router.bias[e]
    ...mlp.experts.{n}.{gate,up}_proj [F, D]    layers.mlp.experts.{gate,up}[e, n] (as they are)
    ...mlp.experts.{n}.down_proj [D, F]         layers.mlp.experts.down[e, n] [F, D]
    ...mlp.shared_experts.{gate,up,down}_proj   layers.mlp.shared.{gate,up,down}.w[e]
    model.layers.{num_hidden_layers + m}.*      SKIPPED by name (below)

**The rotary pair convention.**  The published weights pair the rope dims
(2j, 2j+1) and ``apply_rotary_pos_emb`` de-interleaves q and k at run
time; the program rotates halves (j, j + d/2).  The adapter applies the
de-interleave ONCE, to the rope columns of ``q_b_proj`` (per head) and of
``kv_a_proj_with_mqa``, on load, and its inverse on export.

**Multi-token prediction is not served.**  A checkpoint's
``num_nextn_predict_layers`` modules (``model.layers.{num_hidden_layers}``
onwards: ``enorm``, ``hnorm``, ``eh_proj``, one more layer, the shared
embedding and head) take no part in the main model's logits; they are a
drafting head, whose use needs verification that is exact under sampling
and a step that yields more than one token (ROADMAP, Reach).  Their
weights are skipped BY NAME with a log line, and an exported checkpoint
carries none (``transformers`` ignores them on load as well).

A config that holds a share of the experts (``moe_held_experts``) imports
its own experts of a full checkpoint and cannot export one.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    logger,
    register_hf_family,
    to_np,
)


def _config_from_hf(hf: Dict[str, Any]) -> TransformerConfig:
    refused = {
        "scoring_func other than sigmoid": hf.get("scoring_func") != "sigmoid",
        "topk_method other than noaux_tc": hf.get("topk_method") != "noaux_tc",
        "moe_layer_freq other than 1": hf.get("moe_layer_freq", 1) != 1,
        "attention_bias": bool(hf.get("attention_bias")),
        "queries without their low-rank bottleneck": not hf.get("q_lora_rank"),
    }
    for what, asked in refused.items():
        if asked:
            raise NotImplementedError(f"deepseek_v3 with {what} is not supported")
    rs = hf.get("rope_scaling") or {}
    kind = rs.get("rope_type", rs.get("type"))
    if rs and kind != "yarn":
        raise NotImplementedError(f"deepseek_v3 with rope_scaling {kind!r}")
    n_heads, L = hf["num_attention_heads"], hf["num_hidden_layers"]
    yarn = {}
    if rs:
        yarn = dict(
            rope_yarn_factor=float(rs["factor"]),
            rope_yarn_original_max=rs["original_max_position_embeddings"],
            rope_yarn_beta_fast=float(rs.get("beta_fast", 32)),
            rope_yarn_beta_slow=float(rs.get("beta_slow", 1)),
            rope_yarn_mscale=float(rs.get("mscale", 1)),
            rope_yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
        )
    return TransformerConfig(
        n_layers=L,
        hidden_dim=hf["hidden_size"],
        n_q_heads=n_heads,
        n_kv_heads=hf.get("num_key_value_heads", n_heads),
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        intermediate_dim=hf["intermediate_size"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        shared_expert_dim=hf.get("n_shared_experts", 0)
        * hf["moe_intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        rotary_base=float(hf.get("rope_theta", 10000.0)),
        tied_embedding=hf.get("tie_word_embeddings", False),
        n_experts=hf["n_routed_experts"],
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_norm_topk_prob=hf.get("norm_topk_prob", True),
        moe_router="sigmoid_group",
        moe_n_groups=hf["n_group"],
        moe_topk_groups=hf["topk_group"],
        moe_routed_scale=float(hf["routed_scaling_factor"]),
        layer_types=("latent",) * L,
        n_dense_layers=hf.get("first_k_dense_replace", 0),
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        n_mtp_modules=hf.get("num_nextn_predict_layers", 0),
        **yarn,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    rs = None
    if cfg.rope_yarn_factor:
        rs = dict(
            beta_fast=_num(cfg.rope_yarn_beta_fast),
            beta_slow=_num(cfg.rope_yarn_beta_slow),
            factor=_num(cfg.rope_yarn_factor),
            mscale=_num(cfg.rope_yarn_mscale),
            mscale_all_dim=_num(cfg.rope_yarn_mscale_all_dim),
            original_max_position_embeddings=cfg.rope_yarn_original_max,
            rope_type="yarn",
        )
    return dict(
        architectures=["DeepseekV3ForCausalLM"],
        model_type="deepseek_v3",
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        hidden_size=cfg.hidden_dim,
        intermediate_size=cfg.intermediate_dim,
        moe_intermediate_size=cfg.moe_intermediate_dim,
        num_hidden_layers=cfg.n_layers,
        num_nextn_predict_layers=cfg.n_mtp_modules,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        n_shared_experts=cfg.shared_expert_dim // cfg.moe_intermediate_dim,
        n_routed_experts=cfg.n_experts,
        ep_size=1,
        routed_scaling_factor=_num(cfg.moe_routed_scale),
        kv_lora_rank=cfg.kv_lora_rank,
        q_lora_rank=cfg.q_lora_rank,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        topk_method="noaux_tc",
        n_group=cfg.moe_n_groups,
        topk_group=cfg.moe_topk_groups,
        num_experts_per_tok=cfg.n_experts_per_tok,
        moe_layer_freq=1,
        first_k_dense_replace=cfg.n_dense_layers,
        norm_topk_prob=cfg.moe_norm_topk_prob,
        scoring_func="sigmoid",
        hidden_act="silu",
        rms_norm_eps=cfg.norm_eps,
        rope_theta=_num(cfg.rotary_base),
        rope_scaling=rs,
        attention_bias=False,
        tie_word_embeddings=cfg.tied_embedding,
        torch_dtype="bfloat16",
    )


def _num(x: float):
    """A whole number as the int a ``config.json`` writes it as."""
    return int(x) if float(x).is_integer() else x


def _deinterleave(n: int) -> np.ndarray:
    """Positions (0, 2, 4, ..., 1, 3, 5, ...) of ``n`` rope columns: the
    published pairs (2j, 2j+1) as the halves (j, j + n/2)."""
    return np.concatenate([np.arange(0, n, 2), np.arange(1, n, 2)])


def mtp_weight_names(state_names, cfg: TransformerConfig) -> List[str]:
    """The names of a checkpoint's multi-token-prediction modules: every
    ``model.layers.{i}.*`` with ``i >= num_hidden_layers``."""
    out = []
    for name in state_names:
        parts = name.split(".")
        if parts[:2] == ["model", "layers"] and int(parts[2]) >= cfg.n_layers:
            out.append(name)
    return sorted(out)


def _params_from_hf(state: StateDict, cfg: TransformerConfig) -> Dict[str, Any]:
    skipped = mtp_weight_names(state, cfg)
    if skipped:
        logger.info(
            "deepseek_v3: skipping %d weights of the multi-token-prediction "
            "module(s) model.layers.%d onwards (not served): %s ...",
            len(skipped), cfg.n_layers, skipped[:3],
        )
    g = lambda n: to_np(state[n])
    L, Ld, H = cfg.n_layers, cfg.n_dense_layers, cfg.n_q_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    e0, e1 = cfg.moe_first_expert, cfg.moe_first_expert + cfg.n_held_experts
    pre = "model.layers.{i}."
    T = lambda m: m.T

    def stack(layers, name, fn=lambda m: m):
        return jnp.asarray(
            np.stack([fn(g((pre + name).format(i=i))) for i in layers])
        )

    perm = _deinterleave(rope)

    def q_b(m):  # [H (nope + rope), rq] -> [rq, H (nope + rope)]
        m = m.T.reshape(-1, H, nope + rope)
        m = np.concatenate([m[..., :nope], m[..., nope:][..., perm]], -1)
        return m.reshape(-1, H * (nope + rope))

    def kv_a(m):  # [r + rope, D] -> [D, r + rope]
        m = m.T
        return np.concatenate([m[:, :r], m[:, r:][:, perm]], -1)

    def kv_b(part):  # [H (nope + vd), r] -> [r, H nope] or [r, H vd]
        def fn(m):
            m = m.T.reshape(r, H, nope + vd)
            m = m[..., :nope] if part == "k" else m[..., nope:]
            return m.reshape(r, -1)

        return fn

    every, dense, moe = range(L), range(Ld), range(Ld, L)
    att = "self_attn."

    def experts(name, fn=lambda m: m):
        return jnp.asarray(
            np.stack(
                [
                    np.stack(
                        [
                            fn(g(f"model.layers.{i}.mlp.experts.{n}.{name}.weight"))
                            for n in range(e0, e1)
                        ]
                    )
                    for i in moe
                ]
            )
        )

    mlp: Dict[str, Any] = {
        "router": {
            "w": stack(moe, "mlp.gate.weight", T),
            "bias": stack(moe, "mlp.gate.e_score_correction_bias"),
        },
        "experts": {
            "gate": experts("gate_proj"),
            "up": experts("up_proj"),
            "down": experts("down_proj", T),
        },
    }
    if cfg.shared_expert_dim:
        mlp["shared"] = {
            k: {"w": stack(moe, f"mlp.shared_experts.{k}_proj.weight", T)}
            for k in ("gate", "up", "down")
        }
    params: Dict[str, Any] = {
        "embed": {"weight": jnp.asarray(g("model.embed_tokens.weight"))},
        "layers": {
            "attn_norm": {"scale": stack(every, "input_layernorm.weight")},
            "mlp_norm": {
                "scale": stack(every, "post_attention_layernorm.weight")
            },
            "mlp": mlp,
        },
        "latent": {
            "q_a": {"w": stack(every, att + "q_a_proj.weight", T)},
            "q_a_norm": {"scale": stack(every, att + "q_a_layernorm.weight")},
            "q_b": {"w": stack(every, att + "q_b_proj.weight", q_b)},
            "kv_a": {"w": stack(every, att + "kv_a_proj_with_mqa.weight", kv_a)},
            "kv_a_norm": {"scale": stack(every, att + "kv_a_layernorm.weight")},
            "k_b": {"w": stack(every, att + "kv_b_proj.weight", kv_b("k"))},
            "v_b": {"w": stack(every, att + "kv_b_proj.weight", kv_b("v"))},
            "o": {"w": stack(every, att + "o_proj.weight", T)},
        },
        "final_norm": {"scale": jnp.asarray(g("model.norm.weight"))},
    }
    if Ld:
        params["dense"] = {
            k: {"w": stack(dense, f"mlp.{k}_proj.weight", T)}
            for k in ("gate", "up", "down")
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
    H, Ld = cfg.n_q_heads, cfg.n_dense_layers
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    inv = np.argsort(_deinterleave(rope))
    out: StateDict = {
        "model.embed_tokens.weight": np_(params["embed"]["weight"]),
        "model.norm.weight": np_(params["final_norm"]["scale"]),
    }
    if not cfg.tied_embedding:
        out["lm_head.weight"] = np_(params["lm_head"]["w"]).T
    lay, lat = params["layers"], params["latent"]
    for i in range(cfg.n_layers):
        pre = f"model.layers.{i}."
        out[pre + "input_layernorm.weight"] = np_(lay["attn_norm"]["scale"][i])
        out[pre + "post_attention_layernorm.weight"] = np_(
            lay["mlp_norm"]["scale"][i]
        )
        att = pre + "self_attn."
        out[att + "q_a_proj.weight"] = np_(lat["q_a"]["w"][i]).T
        out[att + "q_a_layernorm.weight"] = np_(lat["q_a_norm"]["scale"][i])
        q = np_(lat["q_b"]["w"][i]).reshape(-1, H, nope + rope)
        q = np.concatenate([q[..., :nope], q[..., nope:][..., inv]], -1)
        out[att + "q_b_proj.weight"] = q.reshape(-1, H * (nope + rope)).T
        kv = np_(lat["kv_a"]["w"][i])
        out[att + "kv_a_proj_with_mqa.weight"] = np.concatenate(
            [kv[:, :r], kv[:, r:][:, inv]], -1
        ).T
        out[att + "kv_a_layernorm.weight"] = np_(lat["kv_a_norm"]["scale"][i])
        out[att + "kv_b_proj.weight"] = np.concatenate(
            [
                np_(lat["k_b"]["w"][i]).reshape(r, H, nope),
                np_(lat["v_b"]["w"][i]).reshape(r, H, vd),
            ],
            -1,
        ).reshape(r, -1).T
        out[att + "o_proj.weight"] = np_(lat["o"]["w"][i]).T
        if i < Ld:
            for k in ("gate", "up", "down"):
                out[pre + f"mlp.{k}_proj.weight"] = np_(
                    params["dense"][k]["w"][i]
                ).T
            continue
        e, mlp = i - Ld, lay["mlp"]
        out[pre + "mlp.gate.weight"] = np_(mlp["router"]["w"][e]).T
        out[pre + "mlp.gate.e_score_correction_bias"] = np_(
            mlp["router"]["bias"][e]
        )
        for n in range(cfg.n_experts):
            ex = pre + f"mlp.experts.{n}."
            out[ex + "gate_proj.weight"] = np_(mlp["experts"]["gate"][e, n])
            out[ex + "up_proj.weight"] = np_(mlp["experts"]["up"][e, n])
            out[ex + "down_proj.weight"] = np_(mlp["experts"]["down"][e, n]).T
        if "shared" in mlp:
            for k in ("gate", "up", "down"):
                out[pre + f"mlp.shared_experts.{k}_proj.weight"] = np_(
                    mlp["shared"][k]["w"][e]
                ).T
    return out


register_hf_family(
    HFFamily(
        name="deepseek_v3",
        hf_architecture="DeepseekV3ForCausalLM",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    )
)
