"""Mixture-of-Experts layer (mixtral-style top-k routing).

Rebuild of the reference's MoE stack (reference:
realhf/impl/model/modules/moe/router.py ``TopKRouter`` with aux/z losses,
moe/experts.py:21-131 grouped GEMM experts, moe/token_dispatcher.py
permute/unpermute) the TPU way: tokens are sorted by expert and the expert
matmuls run as a single ``jax.lax.ragged_dot`` — the MXU-native equivalent of
the CUDA ``grouped_gemm`` dependency.  Expert parallelism shards the [E, ...]
expert-weight dimension over the ``expert`` mesh axis (transformer.param_pspecs;
SURVEY §2.9 EP — a capability beyond the reference's local-only MoE).

Two EP regimes:

* Training leaves the partitioning to XLA's SPMD partitioner over the
  pspecs (the engine jits over the whole mesh and the partitioner keeps
  the [E, D, F] weights sharded through the backward pass).
* SERVING passes ``mesh`` explicitly: the expert compute runs under a
  fully-manual ``shard_map`` over the ``expert`` axis — each shard
  computes only the (token, k) pairs routed to ITS local experts from
  its local ``[E/ep, D, F]`` weight shard and a ``psum`` combines the
  partial outputs.  The router stays replicated (it is [D, E]-small);
  non-local pairs contribute exact zeros (their inputs are masked to
  zero, so silu(0)·0 → 0 flows through the down projection), which
  keeps the combine bitwise-faithful to the replicated layout for the
  usual K <= 2.  This is what lets a qwen3-moe-style model whose expert
  weights don't fit one chip SERVE at all: per-chip expert residency is
  E/ep, not E (the role Megatron's expert parallelism plays for the
  reference's training side, here on the decode/prefill hot path).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models import quantize
from areal_tpu.models.config import TransformerConfig


def init_moe_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    L, D, E = cfg.n_layers, cfg.hidden_dim, cfg.n_experts
    F = cfg.moe_intermediate_dim or cfg.intermediate_dim
    ks = jax.random.split(key, 4)

    def init(k, shape, fan_in):
        scale = 1.0 / np.sqrt(fan_in)
        return jax.random.uniform(
            k, shape, minval=-scale, maxval=scale, dtype=jnp.float32
        )

    return {
        "router": {"w": init(ks[0], (L, D, E), D)},
        "experts": {
            "gate": init(ks[1], (L, E, D, F), D),
            "up": init(ks[2], (L, E, D, F), D),
            "down": init(ks[3], (L, E, F, D), F),
        },
    }


def ep_axis_size(mesh) -> int:
    """Expert-parallel degree of a (possibly None) mesh."""
    if mesh is None:
        return 1
    return int(mesh.shape.get("expert", 1))


def _ep_expert_compute(
    cfg: TransformerConfig,
    mesh,
    x: jax.Array,  # [N, D] (compute dtype)
    topk_idx: jax.Array,  # [N, K] global expert ids
    gate_w: jax.Array,  # [E, D, F] sharded P("expert", None, None)
    up_w: jax.Array,
    down_w: jax.Array,  # [E, F, D]
) -> jax.Array:
    """Expert-parallel grouped compute: returns ``expert_out`` [N*K, D]
    in canonical (token, k) order, identical to the replicated path's
    unsorted output.

    Runs as a fully-manual ``shard_map`` over the serving mesh (the same
    pattern as the TP paged-attention kernel in
    ``models/paged._prefix_partials``): activations and routing are
    replicated in, expert weights arrive pre-sharded over ``expert``
    (the engine's serving pspecs shard the E axis ONLY, so no weight
    gather happens here), and each shard sorts its LOCAL (token, k)
    pairs by local expert id for one ragged_dot per projection.
    Non-local pairs are clamped into group 0 with their inputs zeroed —
    they flow exact zeros through silu/mul/down — and the final ``psum``
    over ``expert`` reassembles every pair from the one shard that owns
    its expert."""
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    ep = ep_axis_size(mesh)
    assert E % ep == 0, (
        f"n_experts {E} not divisible by expert-parallel degree {ep}"
    )
    act_kind = cfg.activation
    from jax.sharding import PartitionSpec as P

    def local_fn(x, topk_idx, gate_w, up_w, down_w):
        e_local = gate_w.shape[0]  # E / ep
        e0 = jax.lax.axis_index("expert") * e_local
        flat = topk_idx.reshape(-1) - e0  # [N*K] local expert ids
        is_local = (flat >= 0) & (flat < e_local)
        key = jnp.where(is_local, flat, 0)
        order = jnp.argsort(key)
        inv_order = jnp.argsort(order)
        xs = jnp.repeat(x, K, axis=0)[order]
        # zeroed non-local rows ride group 0: their gate/up are exact
        # zeros, so the whole pair contributes 0 to the psum below
        xs = jnp.where(is_local[order][:, None], xs, 0)
        group_sizes = jnp.bincount(key, length=e_local).astype(jnp.int32)
        gate = jax.lax.ragged_dot(xs, gate_w, group_sizes)
        up = jax.lax.ragged_dot(xs, up_w, group_sizes)
        act = (
            jax.nn.silu(gate) if act_kind == "silu" else jax.nn.gelu(gate)
        )
        out = jax.lax.ragged_dot(act * up, down_w, group_sizes)
        return jax.lax.psum(out[inv_order], "expert")

    w_spec = P("expert", None, None)
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, None), P(None, None), w_spec, w_spec, w_spec),
        out_specs=P(None, None),
        check_vma=False,
    )
    return fn(x, topk_idx, gate_w, up_w, down_w)


def moe_mlp(
    cfg: TransformerConfig,
    h: jax.Array,
    p: Dict[str, Any],
    valid: jax.Array = None,
    mesh=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """h: [B, T, D] (per-layer params, no leading L).  Returns (out, aux)
    where aux carries the load-balancing and z losses
    (reference: realhf/impl/model/modules/moe/router.py aux-loss/z-loss).

    ``valid`` [B, T] bool masks padding out of the aux statistics — the
    reference router sees packed pad-free tokens, so including pads here
    would distort the load-balancing objective toward pad-token routing.

    ``mesh`` (serving only): a mesh whose ``expert`` axis is > 1 routes
    the expert compute through the explicit EP shard_map
    (:func:`_ep_expert_compute`) over locally-resident [E/ep, D, F]
    weight shards; None (training) leaves sharding to XLA's partitioner
    over the pspecs."""
    B, T, D = h.shape
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    x = h.reshape(-1, D)
    N = x.shape[0]

    router_logits = (x.astype(jnp.float32)) @ p["router"]["w"].astype(
        jnp.float32
    )  # [N, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    topk_probs, topk_idx = jax.lax.top_k(probs, K)  # [N, K]
    if cfg.moe_norm_topk_prob:
        topk_probs = topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)

    # aux losses over VALID tokens only
    if valid is None:
        vmask = jnp.ones((N,), jnp.float32)
    else:
        vmask = valid.reshape(-1).astype(jnp.float32)
    n_valid = jnp.maximum(jnp.sum(vmask), 1.0)
    me = jnp.sum(probs * vmask[:, None], axis=0) / n_valid  # [E]
    ce = (
        jnp.sum(
            jax.nn.one_hot(topk_idx, E).sum(axis=1) * vmask[:, None], axis=0
        )
        / n_valid
    )  # fraction routed per expert * K
    aux_loss = cfg.moe_aux_loss_coef * E * jnp.sum(me * ce) / K
    z_loss = cfg.moe_z_loss_coef * jnp.sum(
        jax.nn.logsumexp(router_logits, axis=-1) ** 2 * vmask
    ) / n_valid

    # leaf_weight serves both formats: plain arrays and the int8 serving
    # format's {"qw", "scale"} leaves.  Dequant happens at use, OUTSIDE
    # the EP shard_map: the qw/scale leaves are sharded over the same
    # ``expert`` axis (transformer.serving_param_pspecs), so the
    # partitioner dequantizes each shard's resident [E/ep, ...] slice
    # locally and the shard_map's in_specs see the layout they expect —
    # no gather, and per-chip residency stays E/ep at int8 bytes.
    gate_w = quantize.leaf_weight(p["experts"]["gate"], h.dtype)
    up_w = quantize.leaf_weight(p["experts"]["up"], h.dtype)
    down_w = quantize.leaf_weight(p["experts"]["down"], h.dtype)

    xd = x.astype(h.dtype)
    if ep_axis_size(mesh) > 1:
        # serving EP: explicit shard_map over the expert axis (already in
        # canonical (token, k) order — no global unsort needed)
        expert_out = _ep_expert_compute(
            cfg, mesh, xd, topk_idx, gate_w, up_w, down_w
        ).reshape(N, K, D)
    else:
        # dispatch: sort token-expert pairs by expert id
        flat_expert = topk_idx.reshape(-1)  # [N*K]
        order = jnp.argsort(flat_expert)
        inv_order = jnp.argsort(order)
        xs = jnp.repeat(xd, K, axis=0)[order]  # [N*K, D] grouped by expert
        group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)

        gate = jax.lax.ragged_dot(xs, gate_w, group_sizes)
        up = jax.lax.ragged_dot(xs, up_w, group_sizes)
        act = (
            jax.nn.silu(gate)
            if cfg.activation == "silu"
            else jax.nn.gelu(gate)
        )
        expert_out = jax.lax.ragged_dot(
            act * up, down_w, group_sizes
        )  # [N*K, D]
        # combine: unsort, weight, sum over K
        expert_out = expert_out[inv_order].reshape(N, K, D)
    out = jnp.sum(expert_out * topk_probs[..., None].astype(h.dtype), axis=1)
    aux = {"moe_aux_loss": aux_loss, "moe_z_loss": z_loss}
    return out.reshape(B, T, D), aux
