"""Phi-4-mini-flash HF adapter (``Phi4FlashForCausalLM``, ``model_type``
``phi4flash``): the SambaY decoder-hybrid-decoder stack.  With ``L =
num_hidden_layers`` and ``mb_per_layer`` 2, even layers are Mamba-1 mixers
up to layer ``L/2`` and gated memory units after it; odd layers are
attention under ``sliding_window`` below ``L/2 + 1``, attention over the
whole context AT ``L/2 + 1`` (the one layer whose K and V are cached for
good), and cross-attention over that layer's K and V after it.  Heads are
differential, norms are LayerNorms with bias, the MLP is SiLU-gated and
dense on every layer, the head is the embedding, and there is no position
term.  The model code is ``areal_tpu/models/hybrid.py``.

HF names -> ours (``i`` the layer, ``j`` its number among its kind; every
layer's mixer module is called ``attn`` there):

    model.layers.{i}.input_layernorm.{weight,bias}           layers.attn_norm.{scale,bias}[i]
    model.layers.{i}.post_attention_layernorm.{weight,bias}  layers.mlp_norm.{scale,bias}[i]
    ...mlp.fc1.weight [2F, D] (gate rows, then up rows)      dense.{gate,up}.w[i]  (transposed)
    ...mlp.fc2.weight [D, F]                                 dense.down.w[i]       (transposed)
    attention, window:  ...attn.Wqkv.{weight,bias} [q | k | v rows]   attn.{q,k,v}.{w,b}[j]
                        ...attn.out_proj.{weight,bias}                attn.o.{w,b}[j]
                        ...attn.inner_cross_attn.lambda_{q1,k1,q2,k2}  attn.lambda_*[j]
                        ...attn.inner_cross_attn.subln.weight         attn.subln.scale[j]
    cross:              the same names, Wqkv holding the q rows alone  cross.*[j]
    Mamba-1:  ...attn.in_proj.weight [2 d_inner, D]          mamba1.in_proj.w[j]  (transposed)
              ...attn.conv1d.{weight [d_inner, 1, K], bias}  mamba1.conv.{w [K, d_inner], b}[j]
              ...attn.x_proj.weight, dt_proj.{weight,bias}   mamba1.{x_proj.w, dt_proj.{w,b}}[j]
              ...attn.A_log [d_inner, N], attn.D             mamba1.{A_log [N, d_inner], D}[j]
              ...attn.out_proj.weight                        mamba1.out_proj.w[j]
    GMU:      ...attn.in_proj.weight [d_inner, D], out_proj  gmu.{in_proj,out_proj}.w[j]
    model.final_layernorm.{weight,bias}                      final_norm.{scale,bias}

The Mamba sizes are the family's class defaults (``d_state`` 16, ``d_conv``
4, ``expand`` 2, ``dt_rank`` ceil(hidden / 16)); the published config has
no key for them.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax.numpy as jnp
import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    register_hf_family,
    to_np,
)

D_STATE, D_CONV, EXPAND = 16, 4, 2


def layer_types(n_layers: int, mb_per_layer: int) -> Tuple[str, ...]:
    """The stack by kind (module docstring)."""
    full = n_layers // 2 + 1
    kinds = []
    for l in range(n_layers):
        if l % mb_per_layer == 0:
            kinds.append("mamba1" if l < full else "gmu")
        elif l < full:
            kinds.append("window")
        else:
            kinds.append("attention" if l == full else "cross")
    return tuple(kinds)


def _config_from_hf(hf: Dict[str, Any]) -> TransformerConfig:
    if hf.get("mlp_bias") or hf.get("lm_head_bias"):
        raise NotImplementedError("phi4flash with an MLP or head bias")
    if not hf.get("tie_word_embeddings", True):
        raise NotImplementedError("phi4flash with an untied head")
    D, L = hf["hidden_size"], hf["num_hidden_layers"]
    n_heads = hf["num_attention_heads"]
    return TransformerConfig(
        n_layers=L,
        hidden_dim=D,
        n_q_heads=n_heads,
        n_kv_heads=hf.get("num_key_value_heads", n_heads),
        head_dim=D // n_heads,
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 262144),
        activation=hf.get("hidden_act", "silu"),
        norm_type="layer",
        norm_eps=hf.get("layer_norm_eps", 1e-5),
        use_attention_bias=True,
        tied_embedding=True,
        use_rope=False,
        sliding_window=hf["sliding_window"],
        layer_types=layer_types(L, hf.get("mb_per_layer", 2)),
        diff_attention=True,
        n_dense_layers=L,
        mamba_n_heads=EXPAND * D,
        mamba_head_dim=1,
        mamba_d_state=D_STATE,
        mamba_d_conv=D_CONV,
        mamba_dt_rank=-(-D // 16),
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    return dict(
        architectures=["Phi4FlashForCausalLM"],
        model_type="phi4flash",
        hidden_size=cfg.hidden_dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_q_heads,
        num_key_value_heads=cfg.n_kv_heads,
        intermediate_size=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        max_position_embeddings=cfg.max_position_embeddings,
        hidden_act=cfg.activation,
        layer_norm_eps=cfg.norm_eps,
        sliding_window=cfg.sliding_window,
        mb_per_layer=2,
        tie_word_embeddings=True,
        mlp_bias=False,
        lm_head_bias=False,
        torch_dtype="bfloat16",
    )


_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
_INNER = "attn.inner_cross_attn."


def _params_from_hf(state: StateDict, cfg: TransformerConfig) -> Dict[str, Any]:
    g = lambda n: to_np(state[n])
    pre = "model.layers.{i}."
    T = lambda m: m.T
    of = {
        kind: [l for l, t in enumerate(cfg.layer_types) if t == kind]
        for kind in set(cfg.layer_types)
    }
    of["attn"] = sorted(of.get("attention", []) + of.get("window", []))

    def stack(layers, name, fn=lambda m: m):
        return jnp.asarray(
            np.stack([fn(g((pre + name).format(i=i))) for i in layers])
        )

    every = range(cfg.n_layers)
    F, qd, kd = cfg.intermediate_dim, cfg.q_dim, cfg.kv_dim
    rows = {"q": slice(0, qd), "k": slice(qd, qd + kd), "v": slice(qd + kd, None)}

    def heads(layers, names):
        out = {
            n: {
                "w": stack(layers, "attn.Wqkv.weight", lambda m, n=n: m[rows[n]].T),
                "b": stack(layers, "attn.Wqkv.bias", lambda m, n=n: m[rows[n]]),
            }
            for n in names
        }
        out["o"] = {
            "w": stack(layers, "attn.out_proj.weight", T),
            "b": stack(layers, "attn.out_proj.bias"),
        }
        out.update({n: stack(layers, _INNER + n) for n in _LAMBDAS})
        out["subln"] = {"scale": stack(layers, _INNER + "subln.weight")}
        return out

    m1 = of["mamba1"]
    return {
        "embed": {"weight": jnp.asarray(g("model.embed_tokens.weight"))},
        "layers": {
            "attn_norm": {
                "scale": stack(every, "input_layernorm.weight"),
                "bias": stack(every, "input_layernorm.bias"),
            },
            "mlp_norm": {
                "scale": stack(every, "post_attention_layernorm.weight"),
                "bias": stack(every, "post_attention_layernorm.bias"),
            },
        },
        "dense": {
            "gate": {"w": stack(every, "mlp.fc1.weight", lambda m: m[:F].T)},
            "up": {"w": stack(every, "mlp.fc1.weight", lambda m: m[F:].T)},
            "down": {"w": stack(every, "mlp.fc2.weight", T)},
        },
        "attn": heads(of["attn"], ("q", "k", "v")),
        "cross": heads(of["cross"], ("q",)),
        "gmu": {
            "in_proj": {"w": stack(of["gmu"], "attn.in_proj.weight", T)},
            "out_proj": {"w": stack(of["gmu"], "attn.out_proj.weight", T)},
        },
        "mamba1": {
            "in_proj": {"w": stack(m1, "attn.in_proj.weight", T)},
            "conv": {
                "w": stack(m1, "attn.conv1d.weight", lambda m: m[:, 0, :].T),
                "b": stack(m1, "attn.conv1d.bias"),
            },
            "x_proj": {"w": stack(m1, "attn.x_proj.weight", T)},
            "dt_proj": {
                "w": stack(m1, "attn.dt_proj.weight", T),
                "b": stack(m1, "attn.dt_proj.bias"),
            },
            "A_log": stack(m1, "attn.A_log", T),
            "D": stack(m1, "attn.D"),
            "out_proj": {"w": stack(m1, "attn.out_proj.weight", T)},
        },
        "final_norm": {
            "scale": jnp.asarray(g("model.final_layernorm.weight")),
            "bias": jnp.asarray(g("model.final_layernorm.bias")),
        },
    }


def _params_to_hf(params: Dict[str, Any], cfg: TransformerConfig) -> StateDict:
    np_ = lambda x: np.asarray(x, np.float32)
    out: StateDict = {
        "model.embed_tokens.weight": np_(params["embed"]["weight"]),
        "model.final_layernorm.weight": np_(params["final_norm"]["scale"]),
        "model.final_layernorm.bias": np_(params["final_norm"]["bias"]),
    }
    lay, dense = params["layers"], params["dense"]
    seen: Dict[str, int] = {}
    for i, kind in enumerate(cfg.layer_types):
        pre = f"model.layers.{i}."
        for hf_name, ours in (
            ("input_layernorm", "attn_norm"),
            ("post_attention_layernorm", "mlp_norm"),
        ):
            out[pre + hf_name + ".weight"] = np_(lay[ours]["scale"][i])
            out[pre + hf_name + ".bias"] = np_(lay[ours]["bias"][i])
        out[pre + "mlp.fc1.weight"] = np.concatenate(
            [np_(dense["gate"]["w"][i]).T, np_(dense["up"]["w"][i]).T]
        )
        out[pre + "mlp.fc2.weight"] = np_(dense["down"]["w"][i]).T
        stack = {"window": "attn", "attention": "attn"}.get(kind, kind)
        j = seen.get(stack, 0)
        seen[stack] = j + 1
        p = params[stack]
        if stack in ("attn", "cross"):
            names = ("q", "k", "v") if stack == "attn" else ("q",)
            out[pre + "attn.Wqkv.weight"] = np.concatenate(
                [np_(p[n]["w"][j]).T for n in names]
            )
            out[pre + "attn.Wqkv.bias"] = np.concatenate(
                [np_(p[n]["b"][j]) for n in names]
            )
            out[pre + "attn.out_proj.weight"] = np_(p["o"]["w"][j]).T
            out[pre + "attn.out_proj.bias"] = np_(p["o"]["b"][j])
            for n in _LAMBDAS:
                out[pre + _INNER + n] = np_(p[n][j])
            out[pre + _INNER + "subln.weight"] = np_(p["subln"]["scale"][j])
        elif stack == "gmu":
            out[pre + "attn.in_proj.weight"] = np_(p["in_proj"]["w"][j]).T
            out[pre + "attn.out_proj.weight"] = np_(p["out_proj"]["w"][j]).T
        else:
            out[pre + "attn.in_proj.weight"] = np_(p["in_proj"]["w"][j]).T
            out[pre + "attn.conv1d.weight"] = np_(p["conv"]["w"][j]).T[:, None, :]
            out[pre + "attn.conv1d.bias"] = np_(p["conv"]["b"][j])
            out[pre + "attn.x_proj.weight"] = np_(p["x_proj"]["w"][j]).T
            out[pre + "attn.dt_proj.weight"] = np_(p["dt_proj"]["w"][j]).T
            out[pre + "attn.dt_proj.bias"] = np_(p["dt_proj"]["b"][j])
            out[pre + "attn.A_log"] = np_(p["A_log"][j]).T
            out[pre + "attn.D"] = np_(p["D"][j])
            out[pre + "attn.out_proj.weight"] = np_(p["out_proj"]["w"][j]).T
    return out


register_hf_family(
    HFFamily(
        name="phi4flash",
        hf_architecture="Phi4FlashForCausalLM",
        config_from_hf=_config_from_hf,
        config_to_hf=_config_to_hf,
        params_from_hf=_params_from_hf,
        params_to_hf=_params_to_hf,
    )
)
