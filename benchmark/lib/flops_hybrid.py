"""Bytes a stack stated by kind (granitemoehybrid) has to move, from its
published sizes: what one decode step must read of the weights this chip
holds, what one Mamba layer's recurrent state costs a live row a step, and
the K and V of one cached position in the attention layers.  Keyed on the
HuggingFace ``config.json`` names the configuration files hold, like
``flops.py``, so the program can change and the yardstick cannot.
"""

from __future__ import annotations

from typing import Sequence


def _mamba_dims(hf: dict):
    d_inner = hf["mamba_n_heads"] * hf["mamba_d_head"]
    conv_dim = d_inner + 2 * hf["mamba_n_groups"] * hf["mamba_d_state"]
    return d_inner, conv_dim


def mixer_params(hf: dict, kind: str) -> int:
    """Weight-matrix parameters of one layer's mixer (norms, biases and
    the per-head scalars left out: under 0.1%)."""
    d = hf["hidden_size"]
    if kind == "mamba":
        d_inner, conv_dim = _mamba_dims(hf)
        return (
            d * (d_inner + conv_dim + hf["mamba_n_heads"])  # in_proj
            + d_inner * d  # out_proj
            + conv_dim * hf["mamba_d_conv"]
        )
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or d // heads
    return d * hd * (2 * heads + 2 * hf["num_key_value_heads"])  # q, o, k, v


def expert_block_params(hf: dict, held_experts: int) -> int:
    """Router, the held routed experts and the shared expert of a layer."""
    d = hf["hidden_size"]
    return (
        d * hf["num_local_experts"]  # the router keeps its published width
        + held_experts * 3 * d * hf["intermediate_size"]
        + 3 * d * hf.get("shared_intermediate_size", 0)
    )


def held_param_count(
    hf: dict, layer_types: Sequence[str], held_experts: int
) -> int:
    """Matrix parameters this chip holds: its layers and the embedding
    (tied: the head is the same table)."""
    layers = sum(
        mixer_params(hf, kind) + expert_block_params(hf, held_experts)
        for kind in layer_types
    )
    return layers + hf["vocab_size"] * hf["hidden_size"]


def weight_bytes(
    hf: dict, layer_types: Sequence[str], held_experts: int,
    bytes_per_param: int = 2,
) -> int:
    """Bytes one decode step has to read of the weights: every layer's
    matrices, every held expert's (at a decode batch of tens of rows and
    top-10 routing every held expert is hit at every step), and the tied
    head read whole."""
    return held_param_count(hf, layer_types, held_experts) * bytes_per_param


def ssm_state_bytes(hf: dict) -> int:
    """One sequence's SSM state in one Mamba layer, float32."""
    d_inner, _ = _mamba_dims(hf)
    return 4 * hf["mamba_d_state"] * d_inner


def ssm_update_min_bytes(hf: dict, live_rows: float) -> float:
    """Least bytes of ONE execution of the decode step's state update
    (one layer, one step): each live row's state read and written."""
    return 2.0 * ssm_state_bytes(hf) * live_rows


def state_bytes_per_row_step(hf: dict, layer_types: Sequence[str]) -> int:
    """What the recurrent state costs one live row one decode step over
    all Mamba layers: state and conv tail (bf16) read and written."""
    _, conv_dim = _mamba_dims(hf)
    tail = 2 * conv_dim * (hf["mamba_d_conv"] - 1)
    n_mamba = sum(kind == "mamba" for kind in layer_types)
    return n_mamba * 2 * (ssm_state_bytes(hf) + tail)


def kv_bytes_per_token(
    hf: dict, layer_types: Sequence[str], bytes_per_el: int = 2
) -> int:
    """K and V of one cached position over the attention layers."""
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // heads
    n_attn = sum(kind == "attention" for kind in layer_types)
    return 2 * hf["num_key_value_heads"] * hd * n_attn * bytes_per_el


def decode_min_seconds(
    hf: dict,
    layer_types: Sequence[str],
    held_experts: int,
    decode_steps: float,
    row_steps: float,
    context_token_reads: float,
    hbm_bytes_per_s: float,
) -> float:
    """Least time by bandwidth for ``decode_steps`` batched decode steps
    in which ``row_steps`` (row, step) pairs were live and which together
    attended ``context_token_reads`` cached positions."""
    total = (
        decode_steps * weight_bytes(hf, layer_types, held_experts)
        + row_steps * state_bytes_per_row_step(hf, layer_types)
        + context_token_reads * kv_bytes_per_token(hf, layer_types)
    )
    return total / hbm_bytes_per_s
