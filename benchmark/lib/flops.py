"""Operations and bytes a model needs, from its published sizes.

The arithmetic is a copy of ``areal_tpu/system/flops_counter.py`` (one
MAC = 2 FLOPs; causal attention costs ``2 * q_dim * t^2`` per layer over a
sequence of t tokens; backward = 2x forward, recompute not counted),
re-keyed on the HuggingFace ``config.json`` names the configuration files
hold, so the program can change and the yardstick cannot.
"""

from __future__ import annotations

from typing import Sequence


def _dims(hf: dict):
    heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    q_dim = heads * head_dim
    kv_dim = hf.get("num_key_value_heads", heads) * head_dim
    return hf["hidden_size"], hf["intermediate_size"], q_dim, kv_dim


def matmul_params_per_layer(hf: dict) -> int:
    """Weight-matrix parameters one token touches in one (dense, gated)
    layer: q, k, v, o and gate, up, down."""
    d, f, q_dim, kv_dim = _dims(hf)
    return d * (q_dim + 2 * kv_dim) + q_dim * d + 3 * d * f


def param_count(hf: dict, n_layers: int) -> int:
    """Every parameter (weights, qkv biases, norms, embedding, head)."""
    d, f, q_dim, kv_dim = _dims(hf)
    per_layer = matmul_params_per_layer(hf) + 2 * d
    if hf.get("attention_bias", hf.get("model_type") == "qwen2"):
        per_layer += q_dim + 2 * kv_dim
    embed = hf["vocab_size"] * d
    tied = hf.get("tie_word_embeddings", False)
    return embed * (1 if tied else 2) + n_layers * per_layer + d


def forward_flops(
    hf: dict, n_layers: int, seqlens: Sequence[int], with_head: bool = True
) -> int:
    d, _, q_dim, _ = _dims(hf)
    tokens = sum(seqlens)
    flops = 2 * matmul_params_per_layer(hf) * n_layers * tokens
    for t in seqlens:
        flops += 2 * n_layers * q_dim * t * t
    if with_head:
        flops += 2 * d * hf["vocab_size"] * tokens
    return flops


def train_flops(hf: dict, n_layers: int, seqlens: Sequence[int]) -> int:
    """Forward + backward (2x forward); rematerialised work not counted."""
    return 3 * forward_flops(hf, n_layers, seqlens)


def weight_bytes(hf: dict, n_layers: int, bytes_per_param: int = 2) -> int:
    """Bytes one decode step has to read of the weights: every layer's
    matrices and the output head (the embedding table is gathered, not
    read; a tied head is the same table read whole)."""
    d = hf["hidden_size"]
    head = d * hf["vocab_size"]
    return (matmul_params_per_layer(hf) * n_layers + head) * bytes_per_param


def kv_bytes_per_token(hf: dict, n_layers: int, bytes_per_el: int = 2) -> int:
    """K and V of one cached position over all layers."""
    _, _, _, kv_dim = _dims(hf)
    return 2 * kv_dim * n_layers * bytes_per_el


def decode_min_seconds(
    hf: dict,
    n_layers: int,
    decode_steps: int,
    context_token_reads: int,
    hbm_bytes_per_s: float,
) -> float:
    """Least time by bandwidth for ``decode_steps`` batched decode steps:
    each reads the weights once, and the steps together read
    ``context_token_reads`` cached positions (the sum over emitted tokens
    of the context each attended to)."""
    total = decode_steps * weight_bytes(hf, n_layers) + (
        context_token_reads * kv_bytes_per_token(hf, n_layers)
    )
    return total / hbm_bytes_per_s
