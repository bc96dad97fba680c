"""Bytes a stack of PARALLEL layers (``falcon_h1``: attention and a Mamba-2
mixer side by side in EVERY layer, a dense gated MLP, an untied head) has
to move, from its published sizes: what one decode step must read of the
weights this chip holds, what one layer's recurrent state costs a live row
a step, the K and V of one cached position, and the least time these
leave.  Keyed on the HuggingFace ``config.json`` names the configuration
file holds, like ``flops.py``, so the program can change and the yardstick
cannot.

Every layer holds BOTH caches: a cached position costs ``2 x
num_key_value_heads x head_dim`` values in each of the layers (2,048 B in
bf16 at 4 heads of 128), and a live row's state ``mamba_d_state x
mamba_n_heads x mamba_d_head`` float32 values in each of them (4 MiB at
256 x 4,096), read AND written every step.
"""

from __future__ import annotations

# the Mamba-2 keys are the hybrid stack's: one arithmetic for one state
from benchmark.lib.flops_hybrid import _mamba_dims, ssm_state_bytes


def as_run(config: dict) -> dict:
    """The published ``config.json`` keys of a configuration file at the
    depth and the vocabulary slice its serving role runs."""
    serve = config["roles"]["serve"]
    return dict(
        config["hf_config"], num_hidden_layers=serve["num_hidden_layers"],
        vocab_size=serve["model_overrides"]["vocab_size"],
    )


def attention_params(hf: dict) -> int:
    d, hd = hf["hidden_size"], hf["head_dim"]
    return d * hd * (2 * hf["num_attention_heads"] + 2 * hf["num_key_value_heads"])


def mamba_params(hf: dict) -> int:
    """in_proj ``[z | x B C | dt]``, out_proj and the depthwise conv."""
    d = hf["hidden_size"]
    d_inner, conv_dim = _mamba_dims(hf)
    return (
        d * (d_inner + conv_dim + hf["mamba_n_heads"]) + d_inner * d
        + conv_dim * hf["mamba_d_conv"]
    )


def mlp_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def layer_params(hf: dict) -> int:
    """Weight-matrix parameters of one layer (norms, the conv's bias and
    the per-head scalars left out: under 0.01%)."""
    return attention_params(hf) + mamba_params(hf) + mlp_params(hf)


def held_param_count(hf: dict) -> int:
    """Matrix parameters this chip holds: its layers, the embedding and
    the untied head."""
    table = hf["vocab_size"] * hf["hidden_size"]
    tables = 1 if hf.get("tie_word_embeddings") else 2
    return hf["num_hidden_layers"] * layer_params(hf) + tables * table


def weight_bytes(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes one decode step has to read of the weights: every layer's
    matrices and the head (the embedding's table is gathered, a row a
    token, not read)."""
    table = hf["vocab_size"] * hf["hidden_size"]
    return (hf["num_hidden_layers"] * layer_params(hf) + table) * bytes_per_param


def state_bytes_per_row_layer(hf: dict) -> int:
    """What the recurrent state costs one live row one decode step in ONE
    layer: state (float32) and conv tail (bf16) read and written."""
    _, conv_dim = _mamba_dims(hf)
    tail = 2 * conv_dim * (hf["mamba_d_conv"] - 1)
    return 2 * (ssm_state_bytes(hf) + tail)


def kv_bytes_per_token_layer(hf: dict, bytes_per_el: int = 2) -> int:
    """K and V of one cached position in ONE layer."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * bytes_per_el


def decode_min_seconds(
    hf: dict, decode_steps: float, row_steps: float,
    context_token_reads: float, hbm_bytes_per_s: float,
) -> float:
    """Least time by bandwidth for ``decode_steps`` batched decode steps
    in which ``row_steps`` (row, step) pairs were live and which together
    attended ``context_token_reads`` cached positions, every layer holding
    both caches."""
    layers = hf["num_hidden_layers"]
    total = (
        decode_steps * weight_bytes(hf)
        + row_steps * layers * state_bytes_per_row_layer(hf)
        + context_token_reads * layers * kv_bytes_per_token_layer(hf)
    )
    return total / hbm_bytes_per_s


def cache_bytes(hf: dict, state_rows: float, ctx_tokens: float) -> dict:
    """Bytes ONE decode step must move of each cache: ``state_rows`` (row,
    layer) pairs' state, and ``ctx_tokens`` cached positions in every
    layer."""
    return {
        "state": state_rows * state_bytes_per_row_layer(hf),
        "pages": ctx_tokens * hf["num_hidden_layers"] * kv_bytes_per_token_layer(hf),
    }
