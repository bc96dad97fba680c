"""Falcon-H1 HF adapter (``FalconH1ForCausalLM``, ``model_type``
``falcon_h1``): EVERY layer runs an attention mixer and a Mamba-2 mixer
side by side on one normed input and adds both to the residual stream
(kind ``"parallel"``), then a SiLU-gated dense MLP; muP multipliers on the
embedding, on each branch's input and output, on the keys, on the five
segments of the Mamba-2 in-projection, on the MLP's gate and output and on
the logits; rotary attention, an untied head.  The model code is
``areal_tpu/models/hybrid.py``.

HF names -> ours (``i`` the layer: every layer is of the one kind, so its
number among the attention mixers and among the Mamba mixers is ``i``):

    model.layers.{i}.input_layernorm.weight    layers.attn_norm.scale[i]
    model.layers.{i}.pre_ff_layernorm.weight   layers.mlp_norm.scale[i]
    ...self_attn.{q,k,v,o}_proj.weight         attn.{q,k,v,o}.w[i]        (transposed)
    ...mamba.in_proj.weight [z | x B C | dt]   mamba.in_proj.w[i]         (transposed)
    ...mamba.conv1d.weight [cd, 1, K], .bias   mamba.conv.w[i] [K, cd], .b[i]
    ...mamba.A_log / D / dt_bias / norm.weight mamba.A_log[i] / D / dt_bias / norm.scale
    ...mamba.out_proj.weight                   mamba.out_proj.w[i]        (transposed)
    ...feed_forward.{gate,up,down}_proj.weight dense.{gate,up,down}.w[i]  (transposed)
    model.final_layernorm.weight               final_norm.scale
    model.embed_tokens.weight, lm_head.weight  embed.weight, lm_head.w    (transposed)

``lm_head_multiplier`` is kept as its reciprocal (``logits_divisor``; a
power of two as published, so the two are the same number).  A multiplier
of 1 is kept as None: the program then has no product for it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    register_hf_family,
    to_np,
)


def _m(value) -> Optional[float]:
    """A published multiplier as the config keeps it."""
    return None if value is None or float(value) == 1.0 else float(value)


def _config_from_hf(hf: Dict[str, Any]) -> TransformerConfig:
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "projectors_bias"):
        if hf.get(key):
            raise NotImplementedError(
                f"falcon_h1 with {key} is not supported: the adapter would "
                "silently drop the biases"
            )
    if not hf.get("mamba_conv_bias", True):
        raise NotImplementedError("falcon_h1 without a conv bias")
    if not hf.get("mamba_rms_norm", True) or hf.get("mamba_norm_before_gate"):
        raise NotImplementedError(
            "falcon_h1 without the gated RMS norm, or with the norm before "
            "the gate: the Mamba-2 mixer is written with the gate first"
        )
    if hf.get("rope_scaling"):
        raise NotImplementedError("falcon_h1 with rope_scaling")
    n_heads = hf["num_attention_heads"]
    H, P = hf["mamba_n_heads"], hf["mamba_d_head"]
    if hf.get("mamba_d_ssm") not in (None, H * P):
        raise NotImplementedError(
            f"falcon_h1 mamba_d_ssm {hf['mamba_d_ssm']} is not "
            f"mamba_n_heads x mamba_d_head = {H * P}"
        )
    L = hf["num_hidden_layers"]
    head = hf.get("lm_head_multiplier")
    mlp = hf.get("mlp_multipliers")
    return TransformerConfig(
        n_layers=L,
        hidden_dim=hf["hidden_size"],
        n_q_heads=n_heads,
        n_kv_heads=hf.get("num_key_value_heads", n_heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // n_heads,
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 262144),
        norm_eps=hf.get("rms_norm_eps", 1e-5),
        rotary_base=float(hf.get("rope_theta", 1e11)),
        tied_embedding=hf.get("tie_word_embeddings", False),
        layer_types=("parallel",) * L,
        n_dense_layers=L,
        mamba_n_heads=H,
        mamba_head_dim=P,
        mamba_d_state=hf["mamba_d_state"],
        mamba_n_groups=hf.get("mamba_n_groups", 1),
        mamba_d_conv=hf["mamba_d_conv"],
        mamba_chunk_size=hf.get("mamba_chunk_size", 128),
        embed_scale=_m(hf.get("embedding_multiplier")),
        logits_divisor=None if _m(head) is None else 1.0 / float(head),
        attn_in_scale=_m(hf.get("attention_in_multiplier")),
        attn_out_scale=_m(hf.get("attention_out_multiplier")),
        key_scale=_m(hf.get("key_multiplier")),
        ssm_in_scale=_m(hf.get("ssm_in_multiplier")),
        ssm_out_scale=_m(hf.get("ssm_out_multiplier")),
        ssm_scales=hf.get("ssm_multipliers"),
        mlp_scales=None if mlp is None else tuple(mlp),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    one = lambda m: 1.0 if m is None else m
    return dict(
        architectures=["FalconH1ForCausalLM"],
        model_type="falcon_h1",
        hidden_act="silu",
        hidden_size=cfg.hidden_dim,
        intermediate_size=cfg.intermediate_dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rotary_base,
        rope_scaling=None,
        tie_word_embeddings=cfg.tied_embedding,
        mamba_n_heads=cfg.mamba_n_heads,
        mamba_d_head=cfg.mamba_head_dim,
        mamba_d_ssm=cfg.mamba_d_inner,
        mamba_d_state=cfg.mamba_d_state,
        mamba_n_groups=cfg.mamba_n_groups,
        mamba_d_conv=cfg.mamba_d_conv,
        mamba_chunk_size=cfg.mamba_chunk_size,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
        mamba_rms_norm=True,
        mamba_norm_before_gate=False,
        attention_bias=False,
        mlp_bias=False,
        projectors_bias=False,
        embedding_multiplier=one(cfg.embed_scale),
        lm_head_multiplier=1.0 / one(cfg.logits_divisor),
        attention_in_multiplier=one(cfg.attn_in_scale),
        attention_out_multiplier=one(cfg.attn_out_scale),
        key_multiplier=one(cfg.key_scale),
        ssm_in_multiplier=one(cfg.ssm_in_scale),
        ssm_out_multiplier=one(cfg.ssm_out_scale),
        ssm_multipliers=list(cfg.ssm_scales or (1.0,) * 5),
        mlp_multipliers=list(cfg.mlp_scales or (1.0, 1.0)),
        torch_dtype="bfloat16",
    )


def _check(cfg: TransformerConfig):
    if set(cfg.layer_types or ()) != {"parallel"}:
        raise ValueError(
            f"falcon_h1 is a stack of parallel layers, not {cfg.layer_types}"
        )


#: (HF name under ``model.layers.{i}.``, our kind, our path, how the HF
#: tensor becomes ours; the inverse is the same function: each is its own)
_T = lambda m: m.T
_LAYER = (
    ("input_layernorm.weight", ("layers", "attn_norm", "scale"), None),
    ("pre_ff_layernorm.weight", ("layers", "mlp_norm", "scale"), None),
    *(
        (f"self_attn.{n}_proj.weight", ("attn", n, "w"), _T)
        for n in ("q", "k", "v", "o")
    ),
    ("mamba.in_proj.weight", ("mamba", "in_proj", "w"), _T),
    ("mamba.conv1d.bias", ("mamba", "conv", "b"), None),
    ("mamba.dt_bias", ("mamba", "dt_bias"), None),
    ("mamba.A_log", ("mamba", "A_log"), None),
    ("mamba.D", ("mamba", "D"), None),
    ("mamba.norm.weight", ("mamba", "norm", "scale"), None),
    ("mamba.out_proj.weight", ("mamba", "out_proj", "w"), _T),
    *(
        (f"feed_forward.{n}_proj.weight", ("dense", n, "w"), _T)
        for n in ("gate", "up", "down")
    ),
)
_CONV = "mamba.conv1d.weight"  # [cd, 1, K] there, [K, cd] here


def _put(tree: Dict[str, Any], path, value):
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = value


def _get(tree: Dict[str, Any], path):
    for key in path:
        tree = tree[key]
    return tree


def _params_from_hf(state: StateDict, cfg: TransformerConfig) -> Dict[str, Any]:
    _check(cfg)
    g = lambda n: to_np(state[n])
    V = cfg.vocab_size  # a sliced vocabulary takes the rows it holds

    def stack(name, fn):
        return jnp.asarray(
            np.stack(
                [
                    (fn or (lambda m: m))(g(f"model.layers.{i}.{name}"))
                    for i in range(cfg.n_layers)
                ]
            )
        )

    params: Dict[str, Any] = {
        "embed": {"weight": jnp.asarray(g("model.embed_tokens.weight")[:V])},
        "final_norm": {"scale": jnp.asarray(g("model.final_layernorm.weight"))},
    }
    if not cfg.tied_embedding:
        params["lm_head"] = {"w": jnp.asarray(g("lm_head.weight")[:V].T)}
    for name, path, fn in _LAYER:
        _put(params, path, stack(name, fn))
    _put(params, ("mamba", "conv", "w"), stack(_CONV, lambda m: m[:, 0].T))
    return params


def _params_to_hf(params: Dict[str, Any], cfg: TransformerConfig) -> StateDict:
    _check(cfg)
    np_ = lambda x: np.asarray(x, np.float32)
    out: StateDict = {
        "model.embed_tokens.weight": np_(params["embed"]["weight"]),
        "model.final_layernorm.weight": np_(params["final_norm"]["scale"]),
    }
    if not cfg.tied_embedding:
        out["lm_head.weight"] = np_(params["lm_head"]["w"]).T
    for i in range(cfg.n_layers):
        pre = f"model.layers.{i}."
        for name, path, fn in _LAYER:
            m = np_(_get(params, path)[i])
            out[pre + name] = m if fn is None else fn(m)
        out[pre + _CONV] = np_(params["mamba"]["conv"]["w"][i]).T[:, None, :]
    return out


register_hf_family(
    HFFamily(
        name="falcon_h1",
        hf_architecture="FalconH1ForCausalLM",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    )
)
