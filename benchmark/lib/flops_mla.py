"""Bytes and operations of a latent-attention (MLA) stack with leading
dense layers and group-routed experts (``deepseek_v3``), from its
published sizes: what one decode step must read of the weights this chip
holds, what one cached position costs the latent kernel to read and to
multiply, and the least time both bounds leave.  Keyed on the HuggingFace
``config.json`` names the configuration files hold, like ``flops.py``, so
the program can change and the yardstick cannot.

One cached position of one layer is ``[c_kv | k_rope]``: ``kv_lora_rank +
qk_rope_head_dim`` values (512 + 64 = 576, 1,152 B in bf16), read ONCE for
all heads.  (The program stores rows of 640 columns, the next lane tile;
the 128 B of padding a position are the program's cost, not the
model's, and are not counted here: a share of this floor reads lower for
them.)  Every head multiplies its absorbed query with all 576 columns and
its probabilities with the first 512: ``heads x (576 + 512) x 2`` FLOP a
position, 139,264 at 64 heads: 121 FLOP/B, against a ridge of 240 on a
v5e.
"""

from __future__ import annotations


def latent_dim(hf: dict) -> int:
    return hf["kv_lora_rank"] + hf["qk_rope_head_dim"]


def latent_bytes_per_token(hf: dict, layers: int = 1, bytes_per_el: int = 2) -> int:
    """The cached entry of one position over ``layers`` layers."""
    return latent_dim(hf) * bytes_per_el * layers


def mla_flops_per_token(hf: dict, layers: int = 1) -> int:
    """What the absorbed kernel multiplies for ONE query row of every head
    against one cached position: scores over the whole entry, values over
    its latent part."""
    return (
        hf["num_attention_heads"] * (latent_dim(hf) + hf["kv_lora_rank"]) * 2 * layers
    )


def mla_params(hf: dict) -> int:
    """Weight-matrix parameters of one layer's mixer (norms left out)."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    qk = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
    return (
        d * hf["q_lora_rank"]  # q_a
        + hf["q_lora_rank"] * h * qk  # q_b
        + d * latent_dim(hf)  # kv_a
        + hf["kv_lora_rank"] * h * (hf["qk_nope_head_dim"] + hf["v_head_dim"])  # kv_b
        + h * hf["v_head_dim"] * d  # o
    )


def dense_mlp_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def expert_block_params(hf: dict, n_router_outputs: int, held_experts: int) -> int:
    """Router (its published width), the held routed experts, the shared
    expert(s)."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    return d * n_router_outputs + (held_experts + hf.get("n_shared_experts", 0)) * 3 * d * f


def held_param_count(
    hf: dict, n_layers: int, n_dense: int, n_router_outputs: int,
    held_experts: int, vocab_rows: int,
) -> int:
    """Matrix parameters this chip holds: its layers, and the embedding
    and the untied head at the vocabulary rows it keeps."""
    layers = n_layers * mla_params(hf) + n_dense * dense_mlp_params(hf)
    layers += (n_layers - n_dense) * expert_block_params(
        hf, n_router_outputs, held_experts
    )
    return layers + 2 * vocab_rows * hf["hidden_size"]


def weight_bytes(hf: dict, *shape, bytes_per_param: int = 2) -> int:
    """Bytes one decode step has to read of the weights (``shape``: the
    arguments of :func:`held_param_count`): every layer's matrices and
    every held expert's (computed for every row at every step:
    ``moe.dense_expert_compute``), and the head.  The embedding's table is
    left out: a step reads only the rows of its tokens (0.23 GB of the
    8.58 GB held are not read)."""
    embedding = shape[-1] * hf["hidden_size"]
    return (held_param_count(hf, *shape) - embedding) * bytes_per_param


def decode_min_seconds(
    hf: dict, shape, decode_steps: float, context_token_reads: float,
    hbm_bytes_per_s: float,
) -> float:
    """Least time by bandwidth for ``decode_steps`` batched decode steps
    which together attended ``context_token_reads`` cached positions
    (each in every layer)."""
    n_layers = shape[0]
    total = decode_steps * weight_bytes(hf, *shape) + (
        context_token_reads * latent_bytes_per_token(hf, n_layers)
    )
    return total / hbm_bytes_per_s


def mla_kernel_min_seconds(hf: dict, ctx_tokens: float, peaks: dict) -> float:
    """Least time of ONE execution of the latent decode kernel (one layer
    of one decode step) whose rows attend ``ctx_tokens`` positions in all:
    the larger of its bytes over the bandwidth and its operations over the
    peak."""
    return max(
        ctx_tokens * latent_bytes_per_token(hf) / peaks["hbm_bytes_per_s"],
        ctx_tokens * mla_flops_per_token(hf) / peaks["bf16_flops"],
    )
