"""Model FLOPs of a ``laguna`` train step and the operations and bytes of
the windowed flash kernels, from the published ``config.json`` keys and the
sequence lengths: forward + backward (3 x forward) of the REAL tokens, no
rematerialised work, no padding.

A layer of kind ``layer_types[l]`` at ``num_attention_heads_per_layer[l]``
heads: the four projections and the headwise gate; scores and values over
each query's context, ``min(i + 1, sliding_window)`` positions in a
sliding layer; the dense MLP on ``mlp_layer_types[l] == "dense"``, else the
router's 256 outputs, the shared expert and the (token, k) pairs the HELD
experts actually took (a count of the program's, not an expectation: a
pair routed to an expert held elsewhere costs nothing here).
"""

from __future__ import annotations

from typing import Sequence

from benchmark.lib.reference_laguna import kinds_of


def causal_pairs(L: int) -> int:
    """(query, key) pairs of one sequence under the causal mask."""
    return L * (L + 1) // 2


def window_pairs(L: int, window: int) -> int:
    """... under ``i - j < window`` too: ``sum_i min(i + 1, window)``."""
    w = min(L, window)
    return w * (w + 1) // 2 + (L - w) * w


def forward_flops(
    hf: dict, n_layers: int, seqlens: Sequence[int], held_pairs: float,
    vocab_size: int,
) -> float:
    """One forward pass over ``seqlens``; ``held_pairs``: the (token, k)
    pairs the held experts took, summed over the expert layers."""
    D, hd, Hkv = hf["hidden_size"], hf["head_dim"], hf["num_key_value_heads"]
    tokens = sum(seqlens)
    flops = 0.0
    for kind, heads, mlp_kind in kinds_of(hf, n_layers):
        mats = D * (heads * hd + 2 * Hkv * hd) + heads * hd * D
        if hf.get("gating"):
            mats += D * heads
        if mlp_kind == "dense":
            mats += 3 * D * hf["intermediate_size"]
        else:
            mats += D * hf["num_experts"] + 3 * D * hf.get(
                "shared_expert_intermediate_size", 0
            )
        flops += 2 * mats * tokens
        pairs = sum(
            window_pairs(L, hf["sliding_window"])
            if kind == "sliding_attention" else causal_pairs(L)
            for L in seqlens
        )
        flops += 4 * heads * hd * pairs  # q.k and p.v, 2 FLOPs a MAC
    flops += 2 * 3 * D * hf["moe_intermediate_size"] * held_pairs
    flops += 2 * D * vocab_size * tokens
    return flops


def train_flops(hf, n_layers, seqlens, held_pairs, vocab_size) -> float:
    """Forward + backward (2 x forward)."""
    return 3.0 * forward_flops(hf, n_layers, seqlens, held_pairs, vocab_size)


#: matmul passes over a block pair in each windowed flash kernel: forward
#: q.k and p.v; dq: q.k, do.v, ds.k; dkv: q.k, do.v, p.do, ds.q
KERNEL_PRODUCTS = {
    "flash_attn_window_fwd": 2,
    "flash_attn_window_bwd_dq": 3,
    "flash_attn_window_bwd_dkv": 4,
}


def window_kernel_flops(hf: dict, kernel: str, pairs: float) -> float:
    """Operations of ONE call of ``kernel`` (one sliding layer, one
    micro-batch) whose rows hold ``pairs`` (query, key) pairs inside
    window, segment and causal mask, at the sliding layers' head count."""
    heads = max(hf["num_attention_heads_per_layer"])
    return 2.0 * KERNEL_PRODUCTS[kernel] * heads * hf["head_dim"] * pairs


def window_kernel_bytes(hf: dict, kernel: str, slots: float) -> float:
    """Bytes ONE call moves at least, over ``slots`` row slots in bf16,
    ``q``, ``o``, ``do``, ``dq`` at the sliding layers' query heads and
    ``k``, ``v``, ``dk``, ``dv`` at the KV heads (what the program repeats
    to the query heads before the kernel is its own doing, not the
    model's): q, k, v in and o out of the forward; q, k, v, do in and dq
    out of dq; q, k, v, do in and dk, dv out of dkv.  The log-sum-exp and
    ``di`` (a float a head and slot) are left out."""
    heads, hd = max(hf["num_attention_heads_per_layer"]), hf["head_dim"]
    at_q, at_kv = {
        "flash_attn_window_fwd": (2, 2),
        "flash_attn_window_bwd_dq": (3, 2),
        "flash_attn_window_bwd_dkv": (2, 4),
    }[kernel]
    row = (at_q * heads + at_kv * hf["num_key_value_heads"]) * hd * 2
    return float(row * slots)


def window_kernel_min_seconds(hf, kernel, pairs, slots, peaks) -> float:
    return max(
        window_kernel_flops(hf, kernel, pairs) / peaks["bf16_flops"],
        window_kernel_bytes(hf, kernel, slots) / peaks["hbm_bytes_per_s"],
    )
