"""Analytic FLOPs accounting per model function call.

Rebuild of the reference's FLOPs counter (reference:
realhf/system/flops_counter.py — per-MFC llama FLOPs used by the master's
throughput logging, surfaced via master_worker._log_training_stats :497).
Ours computes from :class:`TransformerConfig` directly (no hardcoded llama
shape assumptions), counts GQA and MoE correctly, and runs worker-side where
the exact packed seqlens are known; the master only aggregates.

Conventions: one MAC = 2 FLOPs; causal attention scores/values cost
``2 * 2 * T_kv/2`` per query token on average (the causal triangle); the
backward pass is 2x forward (grads wrt inputs and weights).
"""

from __future__ import annotations

from typing import Sequence

from areal_tpu.models.config import PLAIN_ATTENTION_KINDS, TransformerConfig


def matmul_params_per_layer(cfg: TransformerConfig) -> int:
    """Weight-matrix parameters touched per token per layer (excludes
    norms/embeddings; MoE counts only the activated experts)."""
    attn = cfg.hidden_dim * (cfg.q_dim + 2 * cfg.kv_dim) + cfg.q_dim * cfg.hidden_dim
    if cfg.is_moe:
        inter = cfg.moe_intermediate_dim or cfg.intermediate_dim
        n_mats = 3 if cfg.gated_mlp else 2
        mlp = cfg.n_experts_per_tok * n_mats * cfg.hidden_dim * inter
        router = cfg.hidden_dim * cfg.n_experts
        return attn + mlp + router
    n_mats = 3 if cfg.gated_mlp else 2
    return attn + n_mats * cfg.hidden_dim * cfg.intermediate_dim


def layer_passes(cfg: TransformerConfig) -> int:
    """Layers a token goes through before its logits exist: a looped
    stack's ``n_layers``, ``loop_steps`` times over (and ONE head)."""
    return cfg.n_layers * cfg.loop_steps


def _forward_flops_by_kind(
    cfg: TransformerConfig, seqlens: Sequence[int], with_head: bool
) -> int:
    """:func:`forward_flops` of a stack of plain attention kinds
    (``layer_types`` of "attention" and "window"): each layer at its
    kind's own head count, a window layer's scores and values over
    ``min(t, window)`` positions a query, a leading dense MLP or the
    activated experts and the shared one."""
    total_tokens = sum(seqlens)
    D, flops = cfg.hidden_dim, 0
    wcfg = cfg.window_plain()
    for l, kind in enumerate(cfg.layer_types):
        k = wcfg if kind == "window" else cfg
        mats = D * (k.q_dim + 2 * k.kv_dim) + k.q_dim * D
        if k.attention_gate:
            mats += D * k.n_q_heads
        if l < cfg.n_dense_layers or not cfg.is_moe:
            mats += 3 * D * cfg.intermediate_dim
        else:
            inter = cfg.moe_intermediate_dim or cfg.intermediate_dim
            mats += 3 * D * (
                cfg.n_experts_per_tok * inter + cfg.shared_expert_dim
            ) + D * cfg.n_experts
        flops += 2 * mats * total_tokens
        for t in seqlens:
            w = min(t, cfg.sliding_window) if kind == "window" else t
            # sum over queries of 4 q_dim min(i, w): the triangle, then
            # the band
            flops += 2 * k.q_dim * (w * w + 2 * w * (t - w))
    if with_head:
        flops += 2 * D * cfg.vocab_size * total_tokens
    return flops


def forward_flops(
    cfg: TransformerConfig,
    seqlens: Sequence[int],
    with_head: bool = True,
) -> int:
    """FLOPs of one forward pass over packed sequences.

    Per token: 2 * (matmul params) for the projections, plus causal
    attention ~ 2 * 2 * (t/2) * q_dim accumulated over each sequence of
    length t, plus the output head."""
    if cfg.is_hybrid and set(cfg.layer_types) <= set(PLAIN_ATTENTION_KINDS):
        return _forward_flops_by_kind(cfg, seqlens, with_head)
    total_tokens = sum(seqlens)
    passes = layer_passes(cfg)
    flops = 2 * matmul_params_per_layer(cfg) * passes * total_tokens
    # causal attention: sum_t 4 * q_dim * t/2 = q_dim * t*(t+1) ~= q_dim*t^2
    for t in seqlens:
        flops += 2 * passes * cfg.q_dim * t * t
    if with_head:
        out_dim = 1 if cfg.is_critic else cfg.vocab_size
        flops += 2 * cfg.hidden_dim * out_dim * total_tokens
    return flops


def train_flops(cfg: TransformerConfig, seqlens: Sequence[int]) -> int:
    """Forward + backward (2x forward)."""
    return 3 * forward_flops(cfg, seqlens)


def generate_flops(
    cfg: TransformerConfig,
    prompt_lens: Sequence[int],
    gen_lens: Sequence[int],
) -> int:
    """Prefill of each prompt + per-token decode over the growing cache."""
    flops = forward_flops(cfg, prompt_lens, with_head=False)
    passes = layer_passes(cfg)
    per_tok_mats = 2 * matmul_params_per_layer(cfg) * passes
    out_dim = 1 if cfg.is_critic else cfg.vocab_size
    head = 2 * cfg.hidden_dim * out_dim
    for p, g in zip(prompt_lens, gen_lens):
        # decode token i attends to p+i cached positions
        avg_ctx = p + g / 2.0
        flops += int(
            g * (per_tok_mats + head + 4 * passes * cfg.q_dim * avg_ctx)
        )
    return flops


def mfc_flops(
    handle: str,
    cfg: TransformerConfig,
    seqlens: Sequence[int],
    prompt_lens: Sequence[int] | None = None,
) -> int:
    """FLOPs for one MFC given the handle kind and the *output* seqlens.

    For ``generate``, ``seqlens`` are the full prompt+response lengths and
    ``prompt_lens`` the prompt parts."""
    if handle == "train_step":
        return train_flops(cfg, seqlens)
    if handle == "generate" and prompt_lens is not None:
        gen_lens = [s - p for s, p in zip(seqlens, prompt_lens)]
        return generate_flops(cfg, prompt_lens, gen_lens)
    return forward_flops(cfg, seqlens)
