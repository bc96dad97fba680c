"""Mixture-of-Experts layer (mixtral-style top-k routing).

Rebuild of the reference's MoE stack (reference:
realhf/impl/model/modules/moe/router.py ``TopKRouter`` with aux/z losses,
moe/experts.py:21-131 grouped GEMM experts, moe/token_dispatcher.py
permute/unpermute) the TPU way: tokens are sorted by expert and the expert
matmuls run as a single ``jax.lax.ragged_dot`` — the MXU-native equivalent of
the CUDA ``grouped_gemm`` dependency.  Expert parallelism shards the [E, ...]
expert-weight dimension over the ``expert`` mesh axis (transformer.param_pspecs;
SURVEY §2.9 EP — a capability beyond the reference's local-only MoE).

The held experts' grouped product (:func:`grouped_expert_compute`: rounds
of plain batched dots over the routed pairs, a ``while_loop``) has a form
of its own for the trainer, with its derivative
(:func:`grouped_expert_train`: the held pairs in tiles, expert after
expert, none dropped, and one gather a call).

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

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.models import quantize
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import _activation
from areal_tpu.observability.tracing import region


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


def local_expert_compute(
    x: jax.Array,  # [N, D] (compute dtype)
    topk_idx: jax.Array,  # [N, K] global expert ids
    gate_w: jax.Array,  # [E_held, D, F]: experts first .. first + E_held
    up_w: jax.Array,
    down_w: jax.Array,  # [E_held, F, D]
    first,  # global id of the first held expert (python int or traced)
    act_kind: str,
) -> jax.Array:
    """What the experts ``[first, first + E_held)`` give for the (token,
    k) pairs routed to them: ``[N*K, D]`` in canonical (token, k) order,
    exact zeros for every pair routed elsewhere.  The local part of
    expert parallelism: each shard of :func:`_ep_expert_compute` calls it
    with its own ``first`` and sums the shards; one chip's share of a
    stated deployment (:func:`held_moe_mlp`) calls it alone, with no
    exchange and nothing standing in for the absent chips.

    Pairs are sorted by local expert id for one ``ragged_dot`` a
    projection; pairs of absent experts ride group 0 with their inputs
    zeroed, so they flow exact zeros through silu, product and down."""
    e_held = gate_w.shape[0]
    K = topk_idx.shape[1]
    flat = topk_idx.reshape(-1) - first  # [N*K] local expert ids
    is_local = (flat >= 0) & (flat < e_held)
    key = jnp.where(is_local, flat, 0)
    order = jnp.argsort(key)
    inv_order = jnp.argsort(order)
    xs = jnp.repeat(x, K, axis=0)[order]
    xs = jnp.where(is_local[order][:, None], xs, 0)
    group_sizes = jnp.bincount(key, length=e_held).astype(jnp.int32)
    gate = jax.lax.ragged_dot(xs, gate_w, group_sizes)
    up = jax.lax.ragged_dot(xs, up_w, group_sizes)
    out = jax.lax.ragged_dot(_activation(gate, act_kind) * up, down_w, group_sizes)
    return out[inv_order]


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
    pairs through :func:`local_expert_compute`; the final ``psum`` over
    ``expert`` reassembles every pair from the one shard that owns its
    expert."""
    E = cfg.n_experts
    ep = ep_axis_size(mesh)
    assert E % ep == 0, (
        f"n_experts {E} not divisible by expert-parallel degree {ep}"
    )
    act_kind = cfg.activation
    from jax.sharding import PartitionSpec as P

    def local_fn(x, topk_idx, gate_w, up_w, down_w):
        e0 = jax.lax.axis_index("expert") * gate_w.shape[0]
        out = local_expert_compute(
            x, topk_idx, gate_w, up_w, down_w, e0, act_kind
        )
        return jax.lax.psum(out, "expert")

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
    x = h.reshape(-1, D)
    topk_probs, topk_idx, aux = _route_with_losses(cfg, x, p["router"], valid)
    out = _routed_experts(cfg, h.dtype, x, topk_probs, topk_idx, p, mesh)
    return out.reshape(B, T, D), aux


@region("areal.moe.route")
def _route_with_losses(cfg: TransformerConfig, x, router, valid):
    """``(weights [N, K], expert ids [N, K], the router's losses)`` of the
    trainer's softmax top-k router over the tokens ``x`` [N, D]."""
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    N = x.shape[0]
    router_logits = (x.astype(jnp.float32)) @ router["w"].astype(
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
    return (
        topk_probs, topk_idx,
        {"moe_aux_loss": aux_loss, "moe_z_loss": z_loss},
    )


@region("areal.moe.experts")
def _routed_experts(cfg, dtype, x, topk_probs, topk_idx, p, mesh):
    """``sum_k weight * expert(x)`` [N, D] over each token's routed
    experts."""
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    N, D = x.shape
    # leaf_weight serves both formats: plain arrays and the int8 serving
    # format's {"qw", "scale"} leaves.  Dequant happens at use, OUTSIDE
    # the EP shard_map: the qw/scale leaves are sharded over the same
    # ``expert`` axis (transformer.serving_param_pspecs), so the
    # partitioner dequantizes each shard's resident [E/ep, ...] slice
    # locally and the shard_map's in_specs see the layout they expect —
    # no gather, and per-chip residency stays E/ep at int8 bytes.
    gate_w = quantize.leaf_weight(p["experts"]["gate"], dtype)
    up_w = quantize.leaf_weight(p["experts"]["up"], dtype)
    down_w = quantize.leaf_weight(p["experts"]["down"], dtype)

    xd = x.astype(dtype)
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
        expert_out = jax.lax.ragged_dot(
            _activation(gate, cfg.activation) * up, down_w, group_sizes
        )  # [N*K, D]
        # combine: unsort, weight, sum over K
        expert_out = expert_out[inv_order].reshape(N, K, D)
    return jnp.sum(expert_out * topk_probs[..., None].astype(dtype), axis=1)


#: the call length from which :func:`held_moe_mlp` multiplies the routed
#: pairs and not every held expert for every token (:func:`group_rows`
#: has the timing): the served calls are a decode step's 64 rows and
#: fills of ``[1, 1024]``, ``[4, 256]``, ``[4, 1024]`` in the latent and
#: window cells, ``[1..4, 256]`` in the hybrid cell; longer are whole-
#: sequence forwards.  A longer call that may not take the grouped form
#: is cut into pieces of this many tokens, so that the ``[E_held, N, F]``
#: temporaries of the product over every held expert do not grow with it
DENSE_EXPERTS_CALL_TOKENS = 1024

#: rows of one group of the grouped product: the ridge of a v5e (197
#: TFLOP/s over 819 GB/s = 240 FLOP a byte, about 240 rows a bf16 weight
#: read).  Under it a held expert's product is bound by reading its
#: weights, so a smaller group saves little (2.20 ms a layer at 128 rows,
#: 2.76 at 256, latent widths) where a second round costs a second read of
#: them (+2.0 ms, and the combine's gathers again); my chip runs, PR 41
GROUP_ROWS = 256


@region("areal.moe.experts")
def dense_expert_compute(x, w_tok, gate_w, up_w, down_w, act_kind: str):
    """``sum_e w_tok[n, e] * expert_e(x[n])`` over the held experts with
    every expert computed for every token: ``x`` [N, D], ``w_tok`` [N,
    E_held] (0 where token n was not routed to e), all three weights
    ``[E_held, F, D]`` (the hidden width minor in each: given gate and up
    as ``[E, D, F]`` the TPU compiler transposes the whole layer stack
    first, 2.1 GB a projection at 10 x 36 experts).  Returns [N, D].

    The form of a call with FEW tokens a held expert (a decode step's 64
    rows): there each expert's product is bound by reading its weights,
    and the products nobody routed cost no time.  A fill's 1,024 tokens
    are past that knee and take :func:`grouped_expert_compute`.

    Why not pairs sorted by expert for ``ragged_dot``
    (:func:`local_expert_compute`, the expert-parallel path's), in either
    form: that is a custom call on the TPU, for which a layer's weights,
    sliced from the layer stack, are COPIED (1.36 GB written and read
    again a layer at 36 experts of 4096 x 768 x 3, by a described-v5e
    compile, PR 31), where a plain dot reads the slice in place."""
    g = jnp.einsum("nd,efd->enf", x, gate_w)
    u = jnp.einsum("nd,efd->enf", x, up_w)
    hid = _activation(g, act_kind) * u * w_tok.T[:, :, None].astype(x.dtype)
    return jnp.einsum("enf,efd->nd", hid, down_w)


def group_rows(cfg: TransformerConfig, n_tokens: int) -> int:
    """Rows of one held expert's group in a call of ``n_tokens``
    (``GROUP_ROWS``), or 0 where the call takes the product over every
    held expert (:func:`dense_expert_compute`).  From the call's shape and
    the configuration's facts alone.

    Grouped from ``DENSE_EXPERTS_CALL_TOKENS`` tokens on: four times past
    the knee where a held expert's product stops being bound by its weight
    read, and four times the rows of a group, so the grouped form's own
    cost (ranks, a gather a pair to lay a round out, a float32 gather a
    pair to combine it: 0.5-1.4 ms a layer at 1,024 tokens) is a quarter
    of the products it saves.  The crossover as timed on a v5e, ms a layer
    at the window / latent / hybrid cell's widths, every held expert
    against groups of 256 rows (my chip runs, PR 41): 64 tokens 1.03 /
    1.94 / 0.93 against 1.18 / 2.12 / 1.07 at 128 rows (the smallest
    timed), 256 tokens 1.12 / 2.10 / 1.03 against 1.84 / 2.76 / 1.65, 512
    tokens 2.06 / 3.99 / 1.86 against 1.90 / 2.88 / 1.76 (within 8% but
    for the latent widths), 1,024 tokens 4.09 / 7.88 / 3.74 against 2.17
    / 4.79 / 2.38, 4,096 tokens 19.2 / 38.1 / 17.9 against 7.1 / 20.9 /
    16.8 (two to four rounds a layer).

    NEVER in a stack with recurrent state (a Mamba layer), at any length,
    and not for its timing: on the chip the hybrid cell's served rows came
    back NON-FINITE when its fill programs took the grouped form (three
    runs, 89-127 of ~188 sequences, a row's first bad token right after a
    fill of OTHER rows; my chip runs, PR 41), though the same programs
    alone on the chip left every other state slot bit-equal and agreed
    with this form.  The cause is not known (``PERF.md`` section 7 has
    what was ruled out), so such a stack keeps the parent's product at
    every shape until a ``bring_up`` PR finds it;
    ``tests/model/test_moe_grouped.py`` and ``tests/ops/
    test_tpu_compile.py`` hold that."""
    if not cfg.n_held_experts or cfg.n_mamba_layers:
        return 0
    return GROUP_ROWS if n_tokens >= DENSE_EXPERTS_CALL_TOKENS else 0


@region("areal.moe.experts")
def grouped_expert_compute(
    x: jax.Array,  # [N, D]
    local: jax.Array,  # [N, K] expert numbers among the held ones
    w: jax.Array,  # [N, K] float32 router weights
    valid: Optional[jax.Array],  # [N] bool
    weights,  # round -> (gate, up, down), each [E_held, F, D]
    held: int,
    act_kind: str,
    cap: int,
) -> Tuple[jax.Array, jax.Array]:
    """``sum_k w[n, k] * expert_{local[n, k]}(x[n])`` over the pairs whose
    expert is held (``local`` in ``[0, E_held)``) and whose token is
    ``valid``, computed for THOSE pairs only: ``(out [N, D], extra rounds
    int32)``.  What :func:`dense_expert_compute` gives, less the products
    whose weight is 0.

    Each held expert's pairs are ranked in token order; a ROUND lays the
    pairs of ranks ``[r cap, (r + 1) cap)`` out as ``[E_held, cap, D]`` (a
    gather of ``x``'s rows), multiplies them by plain batched dots, which
    read a layer's ``[E_held, F, D]`` slices where they lie in the stack,
    and every pair GATHERS its row of the result (a scatter-add of
    ``[E_held x cap, D]`` rows is a serial loop on the TPU); the down
    products come out in float32 and a token's pairs are weighted and
    summed there, rounded once (the dense form weights the hidden rows
    in the compute dtype and rounds its one contraction over ``e`` and
    ``f`` once).  ``weights(r)`` gives round ``r`` its three ``[E_held,
    F, D]`` arrays (:func:`held_moe_mlp` says why a function and not the
    arrays).  ``cap`` is a tile size, not
    a limit: the rounds go on until the busiest expert's last pair is
    computed (usually one: ``extra rounds`` counts the others), so no pair
    is dropped at any routing.  Pairs of experts held elsewhere and of
    padding tokens take no room in any group.

    The rounds are a loop and not a dense product under ``lax.cond``: one
    body in the program, where the other keeps both forms in every fill
    program, and a routing whose busiest expert is 2.4 times the mean (the
    latent cell's choice bias) would fall back in most calls."""
    N, D = x.shape
    K = local.shape[1]
    is_held, cum, rank, rounds, first_slot = _pair_ranks(local, valid, held, cap)
    wf = w.astype(jnp.float32)

    def one_round(carry):
        r, acc = carry
        j = r * cap + jnp.arange(cap)  # the round's ranks
        # the token that holds rank j of expert e: the first whose count
        # passes j (N where the expert has no such pair: any row will do,
        # nobody gathers its result)
        tok = jnp.sum(cum[:, :, None] <= j[None, None, :], axis=0)  # [E, cap]
        xg = x[jnp.minimum(tok, N - 1)]  # [E_held, cap, D]
        gate_w, up_w, down_w = weights(r)
        g = jnp.einsum("ecd,efd->ecf", xg, gate_w)
        u = jnp.einsum("ecd,efd->ecf", xg, up_w)
        y = jnp.einsum(
            "ecf,efd->ecd", _activation(g, act_kind) * u, down_w,
            preferred_element_type=jnp.float32,
        ).reshape(held * cap, D)
        here = (rank >= r * cap) & (rank < (r + 1) * cap)  # [N, K]
        slot = jnp.where(here, first_slot + rank - r * cap, 0)
        for k in range(K):
            acc = acc + jnp.where(
                here[:, k, None], y[slot[:, k]] * wf[:, k, None], 0.0
            )
        return r + 1, acc

    _, acc = jax.lax.while_loop(
        lambda carry: carry[0] < rounds,
        one_round,
        (jnp.int32(0), jnp.zeros((N, D), jnp.float32)),
    )
    return acc.astype(x.dtype), jnp.maximum(rounds - 1, 0).astype(jnp.int32)


def _pair_ranks(local, valid, held: int, cap: int):
    """The grouped product's layout of the pairs ``local`` [N, K]:
    ``(is_held [N, K], cum [N, E_held], rank [N, K], rounds, first_slot
    [N, K])``: which pairs take part (a held expert's, of a valid token),
    the tokens of expert e up to and with token n, each pair's rank among
    its expert's pairs in token order, the rounds of ``cap`` ranks the
    busiest expert needs, and ``cap`` times a pair's expert."""
    is_held = (local >= 0) & (local < held)
    if valid is not None:
        is_held = is_held & valid[:, None]
    hit = is_held[:, :, None] & (
        local[:, :, None] == jnp.arange(held)[None, None, :]
    )  # [N, K, E_held]; top-k names an expert once a token
    cum = jnp.cumsum(jnp.any(hit, axis=1).astype(jnp.int32), axis=0)
    rank = jnp.sum(jnp.where(hit, cum[:, None, :], 0), axis=2) - 1  # [N, K]
    rounds = (jnp.max(cum[-1]) + cap - 1) // cap
    first_slot = jnp.where(is_held, local, 0) * cap  # [N, K]
    return is_held, cum, rank, rounds, first_slot


#: tiles of one pass of the trainer's loop over the held pairs
#: (:func:`grouped_expert_train`): a pass multiplies ``TRAIN_TILES x cap``
#: rows, so the passes a call takes follow its held pairs in steps of
#: 2,048 rows at the ``GROUP_ROWS`` it is called with
TRAIN_TILES = 8


def _tile_layout(cum, cap: int):
    """The trainer's layout of the held pairs in TILES of ``cap`` rows,
    expert after expert: ``(count [E_held], first tile [E_held], tile
    after the last [E_held])``.  An expert with no pair takes no tile and
    the others round up to whole tiles, so the rows laid out are the held
    pairs and at most ``cap - 1`` more an expert, whatever the busiest
    expert took."""
    count = cum[-1]
    ends = jnp.cumsum((count + cap - 1) // cap)
    return count, ends - (count + cap - 1) // cap, ends


def _tile_rows(N: int, K: int, held: int, cap: int) -> int:
    """Rows of the buffer that holds every tile of a call at ANY routing:
    ``N K`` pairs and a ragged end an expert, in whole passes."""
    tiles = -(-N * K // cap) + held
    return -(-tiles // TRAIN_TILES) * TRAIN_TILES * cap


def _pass_tiles(c, cap: int, count, starts, ends, cum_t):
    """Pass ``c`` of the tile layout: ``(expert [G], live [G, cap], token
    [G, cap])`` of its ``G = TRAIN_TILES`` tiles: which expert a tile
    belongs to, which of its rows hold a pair, and the token of each (the
    first whose count passes the row's rank; any token where no pair
    is)."""
    held, N = cum_t.shape
    t = c * TRAIN_TILES + jnp.arange(TRAIN_TILES)
    e = jnp.minimum(jnp.sum(ends[None, :] <= t[:, None], axis=1), held - 1)
    j = (t - starts[e])[:, None] * cap + jnp.arange(cap)[None, :]  # ranks
    live = (t < ends[-1])[:, None] & (j < count[e][:, None])
    tok = jnp.sum(cum_t[e][:, :, None] <= j[:, None, :], axis=1)
    return e, live, jnp.minimum(tok, N - 1)


def _pair_rows(local, is_held, rank, starts, cap: int):
    """``[N, K]``: the row of the tile layout that holds each pair (0
    where the pair is not held: masked by the caller)."""
    first = starts[jnp.where(is_held, local, 0)] * cap
    return jnp.where(is_held, first + rank, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def grouped_expert_train(
    x, local, w, valid, gate_w, up_w, down_w, held: int, act_kind: str,
    cap: int,
):
    """What :func:`grouped_expert_compute` gives, over ONE layer's
    ``[E_held, F, D]`` arrays in hand (a trainer's masters, cast to
    ``x``'s dtype here), in the form a TRAINER's long calls take, WITH a
    derivative: ``(out [N, D], the busiest expert's groups of cap past
    the first)``.

    The held pairs are laid out in tiles of ``cap`` rows, expert after
    expert (:func:`_tile_layout`); a loop multiplies ``TRAIN_TILES`` tiles
    a pass by plain batched dots over the tiles' experts' weights and
    writes the products into ONE buffer of rows (in ``x``'s dtype: 0.57 GB
    at 16,384 tokens x 8 in bfloat16), and after the loop every pair
    gathers its row ONCE, weights it and sums a token's in float32.  So a call costs its held pairs (in passes
    of ``TRAIN_TILES x cap`` rows) and one gather of ``N x K`` rows,
    whatever the busiest expert took: the rounds of the serving form cost
    that gather EACH, and their count follows the busiest expert, which
    under a choice bias drawn by seed swung a 16,384-token step's time by
    a hundredth from seed to seed (my chip runs, PR 53).  No pair is
    dropped at any routing: the loop runs until the last tile is done.
    The loop is a ``while_loop`` (no reverse mode), so the backward is
    written out (:func:`_grouped_bwd`)."""
    N, D = x.shape
    K = local.shape[1]
    dt = x.dtype
    f32 = jnp.float32
    is_held, cum, rank, rounds, _ = _pair_ranks(local, valid, held, cap)
    count, starts, ends = _tile_layout(cum, cap)
    cum_t = cum.T
    gate_w, up_w, down_w = (a.astype(dt) for a in (gate_w, up_w, down_w))
    rows = TRAIN_TILES * cap

    def one_pass(c, ys):
        e, _, tok = _pass_tiles(c, cap, count, starts, ends, cum_t)
        xg = x[tok]  # [G, cap, D]
        g = jnp.einsum("gcd,gfd->gcf", xg, gate_w[e])
        u = jnp.einsum("gcd,gfd->gcf", xg, up_w[e])
        y = jnp.einsum(
            "gcf,gfd->gcd", _activation(g, act_kind) * u, down_w[e],
            preferred_element_type=f32,
        )
        return jax.lax.dynamic_update_slice(
            ys, y.reshape(rows, D).astype(dt), (c * rows, 0)
        )

    with region("areal.moe.experts"):
        ys = jax.lax.fori_loop(
            0, (ends[-1] + TRAIN_TILES - 1) // TRAIN_TILES, one_pass,
            jnp.zeros((_tile_rows(N, K, held, cap), D), dt),
        )
        row = _pair_rows(local, is_held, rank, starts, cap)
        wf = w.astype(f32)
        acc = jnp.zeros((N, D), f32)
        for k in range(K):
            acc = acc + jnp.where(
                is_held[:, k, None], ys[row[:, k]].astype(f32) * wf[:, k, None], 0.0
            )
    return acc.astype(dt), jnp.maximum(rounds - 1, 0).astype(jnp.int32)


def _grouped_fwd(x, local, w, valid, gate_w, up_w, down_w, held, act_kind, cap):
    out = grouped_expert_train(
        x, local, w, valid, gate_w, up_w, down_w, held, act_kind, cap
    )
    return out, (x, local, w, valid, gate_w, up_w, down_w)


@region("areal.moe.experts")
def _grouped_bwd(held: int, act_kind: str, cap: int, kept, cts):
    """:func:`grouped_expert_train`'s backward, for the held pairs only
    and without dropping any: the forward's passes again, each computing
    by plain batched dots its tiles' share of the three ``dW`` (added in
    float32 to the tiles' experts, handed back in the masters' dtype) and
    writing its rows of ``dx`` and of ``dw`` (a pair's ``dout[n] . y``,
    ``y`` the pair's unweighted float32 down product, computed again) into
    a buffer each, from which every pair gathers its row once after the
    loop.  A row past its expert's last pair carries a weight of 0 and so
    adds nothing to any ``dW``.  The routing (``local``, ``valid``) is
    integers and carries no gradient."""
    x, local, w, valid, gate_m, up_m, down_m = kept
    f32 = jnp.float32
    dt = x.dtype
    N, D = x.shape
    K = local.shape[1]
    dout = cts[0].astype(f32)
    gate_w, up_w, down_w = (a.astype(dt) for a in (gate_m, up_m, down_m))
    is_held, cum, rank, _, _ = _pair_ranks(local, valid, held, cap)
    count, starts, ends = _tile_layout(cum, cap)
    cum_t = cum.T
    rows = TRAIN_TILES * cap
    # each token's weight for each held expert, 0 where not routed
    w_tok = jnp.sum(
        jnp.where(
            is_held[:, :, None]
            & (local[:, :, None] == jnp.arange(held)[None, None, :]),
            w.astype(f32)[:, :, None], 0.0,
        ),
        axis=1,
    )  # [N, E_held]

    def one_pass(c, carry):
        dxs, dws, d_gate, d_up, d_down = carry
        e, live, tok = _pass_tiles(c, cap, count, starts, ends, cum_t)
        ge, ue, de = gate_w[e], up_w[e], down_w[e]
        xg = x[tok]
        g = jnp.einsum("gcd,gfd->gcf", xg, ge)
        u = jnp.einsum("gcd,gfd->gcf", xg, ue)
        hid, act_vjp = jax.vjp(lambda g, u: _activation(g, act_kind) * u, g, u)
        do = dout[tok]  # [G, cap, D] float32
        dy = (do * jnp.where(live, w_tok[tok, e[:, None]], 0.0)[..., None]).astype(dt)
        y = jnp.einsum("gcf,gfd->gcd", hid, de, preferred_element_type=f32)
        d_down = d_down.at[e].add(
            jnp.einsum("gcf,gcd->gfd", hid, dy, preferred_element_type=f32)
        )
        dg, du = act_vjp(jnp.einsum("gcd,gfd->gcf", dy, de))
        d_gate = d_gate.at[e].add(
            jnp.einsum("gcf,gcd->gfd", dg, xg, preferred_element_type=f32)
        )
        d_up = d_up.at[e].add(
            jnp.einsum("gcf,gcd->gfd", du, xg, preferred_element_type=f32)
        )
        dxg = jnp.einsum(
            "gcf,gfd->gcd", dg, ge, preferred_element_type=f32
        ) + jnp.einsum("gcf,gfd->gcd", du, ue, preferred_element_type=f32)
        dxs = jax.lax.dynamic_update_slice(
            dxs, dxg.reshape(rows, D).astype(dt), (c * rows, 0)
        )
        dws = jax.lax.dynamic_update_slice(
            dws, jnp.sum(do * y, axis=-1).reshape(rows), (c * rows,)
        )
        return dxs, dws, d_gate, d_up, d_down

    zeros = lambda a: jnp.zeros(a.shape, f32)
    n_rows = _tile_rows(N, K, held, cap)
    dxs, dws, d_gate, d_up, d_down = jax.lax.fori_loop(
        0, (ends[-1] + TRAIN_TILES - 1) // TRAIN_TILES, one_pass,
        (
            jnp.zeros((n_rows, D), dt), jnp.zeros((n_rows,), f32),
            zeros(gate_m), zeros(up_m), zeros(down_m),
        ),
    )
    row = _pair_rows(local, is_held, rank, starts, cap)
    dx = jnp.zeros((N, D), f32)
    for k in range(K):
        dx = dx + jnp.where(is_held[:, k, None], dxs[row[:, k]].astype(f32), 0.0)
    dw = jnp.where(is_held, dws[row], 0.0)
    return (
        dx.astype(dt), None, dw.astype(w.dtype), None,
        d_gate.astype(gate_m.dtype), d_up.astype(up_m.dtype),
        d_down.astype(down_m.dtype),
    )


grouped_expert_train.defvjp(_grouped_fwd, _grouped_bwd)


def group_limited_choice(cfg: TransformerConfig, choice: jax.Array):
    """``(expert ids [N, K], chosen groups [N, G] bool)`` of the
    ``sigmoid_group`` router from its choice scores ``choice`` [N, E]
    (score + bias): a group (``E / G`` consecutive experts) scores the
    sum of its two largest entries, the ``moe_topk_groups`` best groups
    are kept, and the top k are taken of the scores with every other
    group's set to 0 (as the published ``masked_fill(..., 0.0)``)."""
    N, E = choice.shape
    G = cfg.moe_n_groups
    per_group = choice.reshape(N, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(per_group, 2)[0], axis=-1)  # [N, G]
    _, best = jax.lax.top_k(group_score, cfg.moe_topk_groups)
    chosen = jnp.any(best[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
    masked = jnp.where(chosen[:, :, None], per_group, 0.0).reshape(N, E)
    _, idx = jax.lax.top_k(masked, cfg.n_experts_per_tok)
    return idx, chosen


@region("areal.moe.route")
def route(cfg: TransformerConfig, x: jax.Array, router: Dict[str, Any]):
    """``(weights [N, K] f32, expert ids [N, K], logits [N, E] f32,
    chosen groups [N, G] bool or None)`` of the tokens ``x`` [N, D], by
    ``cfg.moe_router`` over the router's ``{"w"[, "bias"]}``: the router
    always has its published ``n_experts`` outputs and takes its
    published k, however many experts this program holds."""
    K = cfg.n_experts_per_tok
    logits = x.astype(jnp.float32) @ router["w"].astype(jnp.float32)  # [N, E]
    if cfg.moe_router == "sigmoid_group":
        # the bias takes part in the CHOICE only; the weights are the
        # unbiased scores of the chosen, renormalised and scaled
        scores = jax.nn.sigmoid(logits)
        idx, groups = group_limited_choice(
            cfg, scores + router["bias"].astype(jnp.float32)
        )
        top = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.moe_norm_topk_prob:
            top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        return top * cfg.moe_routed_scale, idx, logits, groups
    if cfg.moe_router == "topk_softmax":
        top, idx = jax.lax.top_k(logits, K)
        return jax.nn.softmax(top, axis=-1), idx, logits, None
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, K)
    if cfg.moe_norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top, idx, logits, None


def n_pair_counts(cfg: TransformerConfig) -> int:
    """Length of :func:`held_moe_mlp`'s ``pairs``."""
    return cfg.n_held_experts + 1 + (cfg.moe_router == "sigmoid_group")


def layer_of(tree, layer):
    """Layer ``layer`` of a stacked tree (a dynamic slice inside a scan:
    what ``lax.scan`` over the stack itself reads)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        tree,
    )


def held_moe_mlp(
    cfg: TransformerConfig,
    h: jax.Array,  # [B, T, D]
    p: Dict[str, Any],  # one layer's {"router", "experts"[, "shared"]}
    valid: Optional[jax.Array] = None,  # [B, T] bool
    router_input: Optional[jax.Array] = None,  # [B, T, D]; None: ``h``
    layer: Optional[jax.Array] = None,  # ``p`` is the layer STACK: which
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[jax.Array]]:
    """The expert layer of a program that is TOLD which experts it holds
    (``cfg.moe_first_expert``, ``cfg.n_held_experts``; ``p["experts"]``
    holds exactly those, gate, up and down each ``[E_held, F, D]``):
    routes over all ``n_experts``, computes its own
    experts' part of the result, adds the shared expert (every token,
    weight 1).  The ROUTER reads ``router_input`` where one is given (a
    model whose router stands before attention routes on the mixer's
    input) while the experts read ``h``.  Returns ``(out [B, T, D], pairs [n_pair_counts] int32,
    expert ids [B, T, K] int32, extra rounds)``: the valid (token, k) pairs each held
    expert took and, after them, those routed to experts held elsewhere
    (a group-limited router appends the (token, chosen group) pairs
    whose group has an expert held HERE);
    each token's routed experts, by their published numbers, for a
    caller that hands the routing out (a routing-replay trainer, a
    parity check that follows the server's choices); and the rounds past
    the first that the grouped product took (int32), None where the
    call took the product over every held expert.

    Which of the two products a call takes follows from its shape and
    the stack's kinds alone (:func:`group_rows`): a decode step's 64 rows
    multiply every held expert (:func:`dense_expert_compute`), a fill's
    1,024 tokens the pairs their router chose
    (:func:`grouped_expert_compute`); a stack with recurrent state takes
    the first at every length, a long call in pieces.

    A caller inside a scan over layers hands over the STACK of every
    layer's parameters and ``layer``, and not the layer's slice: the
    grouped product's rounds are a loop, a slice taken outside it is the
    loop's operand, and the compiler then COPIES a layer's expert weights
    out of the stack (0.755 GB a layer at 64 experts of 768 x 2560 x 3, by
    a described-v5e compile, PR 41) where a dot that slices the stack
    itself reads them in place."""
    B, T, D = h.shape
    cap = group_rows(cfg, B * T)
    experts = p["experts"]
    if layer is not None and cap:
        # the rounds slice the experts' stack themselves: weights() below
        p = layer_of({k: v for k, v in p.items() if k != "experts"}, layer)
    elif layer is not None:
        p = layer_of(p, layer)
        experts, layer = p["experts"], None
    x = h.reshape(-1, D)
    routed_on = x if router_input is None else router_input.reshape(-1, D)
    w, idx, _, groups = route(cfg, routed_on, p["router"])
    first, held = cfg.moe_first_expert, cfg.n_held_experts
    local = idx - first
    rounds = None

    def weights(r=None):
        """gate, up, down ``[E_held, F, D]``; inside round ``r`` of the
        grouped product the stack's slice, tied to ``r``: a slice that
        depends on nothing the loop changes is lifted out of it (loop-
        invariant code motion), which makes it the copy described
        above."""
        ex = experts
        if layer is not None:
            ex = layer_of(ex, jax.lax.optimization_barrier((layer, r))[0])
        return tuple(
            quantize.leaf_weight(ex[k], h.dtype) for k in ("gate", "up", "down")
        )

    flat_valid = None if valid is None else valid.reshape(-1)
    if cap and layer is None and not isinstance(experts["gate"], dict):
        # one layer's arrays in hand (a trainer scans the stack itself):
        # the held pairs in tiles, with their derivative
        out, rounds = grouped_expert_train(
            x, local, w, flat_valid, experts["gate"], experts["up"],
            experts["down"], held, cfg.activation, cap,
        )
    elif cap:
        out, rounds = grouped_expert_compute(
            x, local, w, flat_valid, weights, held, cfg.activation, cap,
        )
    else:
        with region("areal.moe.route"):
            # each token's weight for each held expert, 0 where not routed
            w_tok = jnp.sum(
                jnp.where(
                    local[:, :, None] == jnp.arange(held)[None, None, :],
                    w[:, :, None], 0.0,
                ),
                axis=1,
            )
        with region("areal.moe.experts"):
            N, C = x.shape[0], DENSE_EXPERTS_CALL_TOKENS
            ws = weights()
            if N <= C:
                out = dense_expert_compute(x, w_tok, *ws, cfg.activation)
            else:
                # a long call of a stack that may not group: pieces of C
                pad = (-N) % C
                out = jax.lax.map(
                    lambda xw: dense_expert_compute(*xw, *ws, cfg.activation),
                    (
                        jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, C, D),
                        jnp.pad(w_tok, ((0, pad), (0, 0))).reshape(-1, C, held),
                    ),
                ).reshape(-1, D)[:N]
    if "shared" in p:
        with region("areal.moe.shared"):
            sh = p["shared"]
            g = x @ quantize.leaf_weight(sh["gate"], h.dtype)
            u = x @ quantize.leaf_weight(sh["up"], h.dtype)
            out = out + (_activation(g, cfg.activation) * u) @ quantize.leaf_weight(
                sh["down"], h.dtype
            )
    return (
        out.reshape(B, T, D),
        _held_pairs(cfg, local, groups, valid),
        idx.reshape(B, T, -1).astype(jnp.int32),
        rounds,
    )


@region("areal.moe.route")
def _held_pairs(cfg: TransformerConfig, local, groups, valid):
    """:func:`held_moe_mlp`'s ``pairs`` from the routed experts' numbers
    among the held ones (``local`` [N, K]: outside ``[0, held)`` where
    held elsewhere) and the router's chosen groups."""
    first, held = cfg.moe_first_expert, cfg.n_held_experts
    is_held = (local >= 0) & (local < held)
    slot = jnp.where(is_held, local, held)  # [N, K]
    if valid is not None:
        slot = jnp.where(valid.reshape(-1)[:, None], slot, held + 1)
    pairs = jnp.bincount(slot.reshape(-1), length=held + 2)[: held + 1]
    if groups is not None:
        per = cfg.n_experts // cfg.moe_n_groups
        g = jnp.arange(cfg.moe_n_groups)
        here = (g * per < first + held) & ((g + 1) * per > first)
        hit = groups & here[None, :]
        if valid is not None:
            hit = hit & valid.reshape(-1)[:, None]
        pairs = jnp.concatenate([pairs, jnp.sum(hit, dtype=pairs.dtype)[None]])
    return pairs.astype(jnp.int32)
