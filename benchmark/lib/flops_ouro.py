"""Operations and bytes of a LOOPED dense stack (``ouro``: Ouro-2.6B), from
its published sizes: the ``num_hidden_layers`` weight layers are run
``total_ut_steps`` times before a token's logits exist, and every pass keeps
keys and values of its own, so the cache has ``num_hidden_layers x
total_ut_steps`` layers over ``num_hidden_layers`` weight layers.

One MAC = 2 FLOPs, as ``lib/flops.py``; a decode step reads the layers'
matrices ONCE A PASS (the same bytes ``total_ut_steps`` times: nothing
keeps 4.9 GB of weights on the chip between passes), the untied head once
(the embedding's table is gathered, not read), and K and V of every
position each emitted token attended to, in every CACHE layer.
"""

from __future__ import annotations

from benchmark.lib import flops


def passes(hf: dict) -> int:
    return int(hf["total_ut_steps"])


def cache_layers(hf: dict) -> int:
    """(pass, layer) pairs, each with a cache of its own."""
    return hf["num_hidden_layers"] * passes(hf)


def layer_params(hf: dict) -> int:
    """One weight layer: q, k, v, o, gate, up, down and its FOUR norms
    (sandwich: one on each branch's input and one on its output)."""
    return flops.matmul_params_per_layer(hf) + 4 * hf["hidden_size"]


def param_count(hf: dict) -> int:
    """Every parameter held: the layers once (their weights are the same
    in every pass), the embedding, the untied head, the norm after each
    pass and the exit gate ([hidden -> 1] and its bias)."""
    d = hf["hidden_size"]
    return (
        hf["num_hidden_layers"] * layer_params(hf)
        + 2 * hf["vocab_size"] * d + d + (d + 1)
    )


def kv_bytes_per_token(hf: dict, bytes_per_el: int = 2) -> int:
    """K and V of one cached position over ALL cache layers."""
    return flops.kv_bytes_per_token(hf, cache_layers(hf), bytes_per_el)


def paged_call_bytes(hf: dict, context_tokens: float, bytes_per_el: int = 2) -> float:
    """Least bytes ONE call of the paged decode kernel reads: K and V of
    the positions its rows attend to, in one cache layer (one query head
    a KV head: nothing is read twice for a group)."""
    return context_tokens * flops.kv_bytes_per_token(hf, 1, bytes_per_el)


def weight_bytes_per_step(hf: dict, bytes_per_param: int = 2) -> int:
    """Bytes a decode step reads of the weights: the layers' matrices once
    a pass, the head once."""
    mats = flops.matmul_params_per_layer(hf) * hf["num_hidden_layers"]
    head = hf["hidden_size"] * hf["vocab_size"]
    return (mats * passes(hf) + head) * bytes_per_param


def decode_min_seconds(
    hf: dict, decode_steps: float, context_token_reads: float,
    hbm_bytes_per_s: float,
) -> float:
    """Least time by bandwidth for ``decode_steps`` batched decode steps
    that together read ``context_token_reads`` cached positions."""
    total = decode_steps * weight_bytes_per_step(hf) + (
        context_token_reads * kv_bytes_per_token(hf)
    )
    return total / hbm_bytes_per_s


def forward_flops_per_token(hf: dict, context: float = 0.0) -> float:
    """FLOPs of one token's forward at ``context`` cached positions:
    ``total_ut_steps`` passes of the layers' matrices and of attention
    over the context, and ONE head."""
    d = hf["hidden_size"]
    q_dim = hf["num_attention_heads"] * (
        hf.get("head_dim") or d // hf["num_attention_heads"]
    )
    layers = cache_layers(hf)
    return (
        2 * flops.matmul_params_per_layer(hf) * layers
        + 4 * layers * q_dim * context
        + 2 * d * hf["vocab_size"]
    )
