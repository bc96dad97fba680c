"""Bytes and operations of a stack of window and global attention layers
with ReLU-gated experts (``smallthinker``), from its published sizes: what
one decode step must read of the weights this chip holds, what one cached
position costs the paged kernel to read and to multiply BY LAYER KIND, and
the least time both bounds leave.  Keyed on the HuggingFace ``config.json``
names the configuration files hold, like ``flops.py``, so the program can
change and the yardstick cannot.

One cached position of one layer is K and V of ``num_key_value_heads x
head_dim`` values each (4 x 128 x 2 x 2 B = 2,048 B in bf16).  A GLOBAL
layer's decode step reads every cached position of its row; a WINDOW
layer's at most the ``sliding_window_size - 1`` before the query (the
query itself and the chunk's own tokens are not in the pool yet).  Every
query head multiplies its query with the key and its probability with the
value: ``heads x head_dim x 2 x 2`` FLOP a position, 14,336 at 28 heads:
7 FLOP/B against a ridge of 240 on a v5e, so the bytes decide.
"""

from __future__ import annotations


def as_run(config: dict) -> dict:
    """The published ``config.json`` keys of a configuration file at the
    depth and the layouts its cut names (``reduced``): what the cell runs."""
    return dict(config["hf_config"], **{k: config[k] for k in config["reduced"]})


def kv_bytes_per_token(hf: dict, layers: int = 1, bytes_per_el: int = 2) -> int:
    """K and V of one cached position over ``layers`` layers."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * bytes_per_el * layers


def attn_flops_per_token(hf: dict, layers: int = 1) -> int:
    """What the kernel multiplies for ONE query row of every head against
    one cached position: a score and a value update."""
    return hf["num_attention_heads"] * hf["head_dim"] * 2 * 2 * layers


def layer_kinds(hf: dict) -> tuple:
    """``(global layers, window layers)`` of the layout AS RUN."""
    n_window = sum(1 for w in hf["sliding_window_layout"] if w)
    return len(hf["sliding_window_layout"]) - n_window, n_window


def window_reads(hf: dict, context: int) -> int:
    """Cached positions a window layer's decode query at cached length
    ``context`` reads."""
    return min(context, hf["sliding_window_size"] - 1)


def attn_params(hf: dict) -> int:
    """Weight-matrix parameters of one layer's mixer (norms left out)."""
    d, hd = hf["hidden_size"], hf["head_dim"]
    return d * hd * 2 * (hf["num_attention_heads"] + hf["num_key_value_heads"])


def expert_block_params(hf: dict, held_experts: int) -> int:
    """Router (its published width) and the held experts' three matrices."""
    d, f = hf["hidden_size"], hf["moe_ffn_hidden_size"]
    return d * hf["moe_num_primary_experts"] + held_experts * 3 * d * f


def held_param_count(hf: dict, n_layers: int, held_experts: int, vocab_rows: int) -> int:
    """Matrix parameters this chip holds: its layers, and the embedding
    and the untied head at the vocabulary rows it keeps."""
    per_layer = attn_params(hf) + expert_block_params(hf, held_experts)
    return n_layers * per_layer + 2 * vocab_rows * hf["hidden_size"]


def weight_bytes(
    hf: dict, n_layers: int, held_experts: int, vocab_rows: int,
    bytes_per_param: int = 2,
) -> int:
    """Bytes one decode step has to read of the weights: every layer's
    matrices and every held expert's (computed for every row at every
    step, ``moe.dense_expert_compute``; at 55 rows x 6 pairs over 64
    experts every expert is touched by some row besides), and the head.
    The embedding's table is left out: a step reads only its tokens' rows."""
    embedding = vocab_rows * hf["hidden_size"]
    return (
        held_param_count(hf, n_layers, held_experts, vocab_rows) - embedding
    ) * bytes_per_param


def decode_min_seconds(
    hf: dict, held_experts: int, vocab_rows: int, decode_steps: float,
    context_token_reads: float, window_token_reads: float,
    hbm_bytes_per_s: float,
) -> float:
    """Least time by bandwidth for ``decode_steps`` batched decode steps
    whose queries together had ``context_token_reads`` cached positions
    before them, of which a window layer reads ``window_token_reads``
    (:func:`window_reads` summed likewise); each in every layer of its
    kind."""
    n_global, n_window = layer_kinds(hf)
    kv = kv_bytes_per_token(hf)
    total = decode_steps * weight_bytes(
        hf, n_global + n_window, held_experts, vocab_rows
    ) + kv * (n_global * context_token_reads + n_window * window_token_reads)
    return total / hbm_bytes_per_s


def window_kernel_min_seconds(hf: dict, window_tokens: float, peaks: dict) -> float:
    """Least time of ONE execution of the windowed decode kernel (one
    window layer of one decode step) whose rows read ``window_tokens``
    cached positions in all: the larger of its bytes over the bandwidth
    and its operations over the peak."""
    return max(
        window_tokens * kv_bytes_per_token(hf) / peaks["hbm_bytes_per_s"],
        window_tokens * attn_flops_per_token(hf) / peaks["bf16_flops"],
    )
