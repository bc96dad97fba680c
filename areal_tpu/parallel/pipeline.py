"""Pipeline parallelism over the ``pipe`` mesh axis.

TPU-native replacement for the reference's pipeline-instruction VM
(reference: realhf/impl/model/backend/pipe_runner.py — 1F1B/inference
schedules executed by a Python interpreter issuing NCCL p2p send/recvs;
reference: realhf/impl/model/backend/static_schedule.py:159-323).  On TPU
none of that machinery survives: the schedule is expressed *inside* one
jitted program as a ``lax.scan`` over pipeline steps within a
``jax.shard_map`` that is manual over only the ``pipe`` axis —

* each stage holds a contiguous slice of the stacked ``[L, ...]`` layer
  params (the mesh shards the leading layer axis over ``pipe``);
* micro-batch activations rotate stage-to-stage via ``lax.ppermute``
  (XLA lowers this to ICI neighbour transfers — the p2p send/recv pairs
  of the reference's VM, scheduled by the compiler instead of Python);
* every other mesh axis (``data``/``fsdp``/``model``/``expert``) stays
  *auto*: XLA keeps inserting the FSDP all-gathers and TP collectives
  inside each stage exactly as in the unpipelined path.

The backward schedule needs no hand-built 1F1B program: differentiating
through the scan-of-ppermute gives a GPipe schedule (all forwards, then
all backwards, with reverse-direction ppermutes), and per-layer
rematerialisation keeps the stored state to layer-boundary activations —
the same memory class as the unpipelined remat path.

Composition limits: ``pipe`` composes with data/fsdp/model/expert.
``pipe × seq`` (context parallelism inside a pipeline stage) would nest
two manual shard_maps and is rejected with an explicit error.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


Aux = Any
# stage_fn(local_stacked_params, {"x": [B,T,D], **side_inputs}) -> (y, aux)
StageFn = Callable[[Any, Dict[str, jax.Array]], Tuple[jax.Array, Aux]]


def pick_microbatches(n_rows: int, pipe: int, requested: int = 0) -> int:
    """Number of pipeline micro-batches.

    ``requested=0`` -> auto: ``2 * pipe`` (bubble fraction
    ``(p-1)/(m+p-1)`` ≈ 1/3) capped by the row count; always >= 1.
    """
    m = requested if requested > 0 else 2 * pipe
    return max(1, min(m, n_rows))


def _wavefront(stage, t, m):
    """Forward-wavefront indexing shared by every schedule: the
    micro-batch at ``stage`` on step ``t`` entered the pipeline ``stage``
    steps ago.  Returns (mb_idx clamped for bubble steps, valid)."""
    mb_idx = jnp.clip(t - stage, 0, m - 1)
    valid = (t - stage >= 0) & (t - stage < m)
    return mb_idx, valid


def _take_mb(xs, sides, mb_idx):
    """Slice micro-batch ``mb_idx`` out of stacked inputs + side inputs."""
    mb_x = jax.lax.dynamic_index_in_dim(xs, mb_idx, axis=0, keepdims=False)
    mb_sides = {
        k: jax.lax.dynamic_index_in_dim(v, mb_idx, axis=0, keepdims=False)
        for k, v in sides.items()
    }
    return mb_x, mb_sides


def _bank(outs, mb_idx, out, cond):
    """Store ``out`` at ``outs[mb_idx]`` when ``cond`` (else keep)."""
    prev = jax.lax.dynamic_index_in_dim(outs, mb_idx, axis=0, keepdims=False)
    return jax.lax.dynamic_update_index_in_dim(
        outs, jnp.where(cond, out, prev), mb_idx, 0
    )


def pipeline_apply(
    mesh,
    stacked_params: Any,
    stage_fn: StageFn,
    x: jax.Array,
    side_inputs: Dict[str, jax.Array],
    n_mbs: int,
    aux_zero: Optional[Aux] = None,
):
    """Run ``stage_fn`` over ``pipe`` stages with micro-batch rotation.

    Args:
      mesh: the engine mesh; ``mesh.shape["pipe"] > 1``.
      stacked_params: pytree whose every leaf has leading dim ``L``
        (sharded over ``pipe`` by the caller's NamedSharding; inside the
        shard_map each stage sees its local ``[L/p, ...]`` slice).
      stage_fn: applies one stage's layers to one micro-batch.  Called
        under the shard_map with *auto* data/model axes — it may use
        sharded matmuls freely but must not touch the ``pipe`` axis.
      x: ``[B, T, D]`` hidden states entering the first stage.
      side_inputs: per-row arrays (``[B, ...]``) consumed by every stage
        alongside its current micro-batch (positions, seg_ids, ...).
      n_mbs: micro-batch count ``m``; must divide ``B``.
      aux_zero: zero-valued pytree matching stage_fn's aux output
        (None = no aux).

    Returns ``(y [B, T, D], aux_total)`` where aux_total sums stage_fn's
    aux over all layers and micro-batches (psum over ``pipe``).
    """
    p = mesh.shape["pipe"]
    assert p > 1, "pipeline_apply called without a pipe axis"
    if mesh.shape.get("seq", 1) > 1:
        raise NotImplementedError(
            "pipe x seq (context parallelism inside pipeline stages) nests "
            "two manual shard_maps; shard long sequences with seq OR pipe"
        )
    B = x.shape[0]
    m = n_mbs
    assert B % m == 0, f"rows {B} not divisible by pipeline micro-batches {m}"

    def split(a):
        return a.reshape((m, B // m) + a.shape[1:])

    xs = split(x)
    sides = {k: split(v) for k, v in side_inputs.items()}
    has_aux = aux_zero is not None

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            jax.sharding.PartitionSpec("pipe"),
            jax.sharding.PartitionSpec(),
            jax.sharding.PartitionSpec(),
        ),
        out_specs=(
            jax.sharding.PartitionSpec("pipe"),
            jax.sharding.PartitionSpec(),
        ),
        axis_names={"pipe"},
        check_vma=False,
    )
    def run(local_params, xs, sides):
        stage = jax.lax.axis_index("pipe")
        perm = [(i, (i + 1) % p) for i in range(p)]
        n_steps = m + p - 1

        def step(carry, t):
            recv, outs, aux_acc = carry
            mb_idx, valid = _wavefront(stage, t, m)
            mb_x, mb_sides = _take_mb(xs, sides, mb_idx)
            inp = jnp.where(stage == 0, mb_x, recv)
            out, aux = stage_fn(local_params, {"x": inp, **mb_sides})
            if has_aux:
                aux_acc = jax.tree.map(
                    lambda acc, a: acc + jnp.where(valid, a, 0), aux_acc, aux
                )
            # the last stage banks its finished micro-batch
            outs = _bank(outs, mb_idx, out, (stage == p - 1) & valid)
            recv = jax.lax.ppermute(out, "pipe", perm)
            return (recv, outs, aux_acc), None

        aux0 = (
            jax.tree.map(lambda a: jnp.asarray(a), aux_zero)
            if has_aux
            else jnp.zeros((), jnp.float32)
        )
        (recv, outs, aux_acc), _ = jax.lax.scan(
            step,
            (jnp.zeros_like(xs[0]), jnp.zeros_like(xs), aux0),
            jnp.arange(n_steps),
        )
        aux_total = jax.lax.psum(aux_acc, "pipe")
        return outs, aux_total

    outs, aux_total = run(stacked_params, xs, sides)
    # outs is the per-stage banks concatenated over ``pipe`` -> [p*m, ...];
    # only the last stage's block holds real outputs
    y = outs[(p - 1) * m :].reshape((B,) + x.shape[1:])
    return y, (aux_total if has_aux else None)


def pipeline_apply_1f1b(
    mesh,
    stacked_params: Any,
    stage_fn: StageFn,
    x: jax.Array,
    side_inputs: Dict[str, jax.Array],
    n_mbs: int,
):
    """Memory-bounded pipeline schedule (the reference's 1F1B,
    realhf/impl/model/backend/static_schedule.py:323, re-expressed as a
    custom-VJP pair of shard_map scans instead of a p2p instruction VM).

    Differentiating :func:`pipeline_apply`'s scan gives GPipe: every
    step's stage input is saved for the backward, so per-device live
    activations are ~(m+p-1) micro-batches.  Here the FORWARD saves
    NOTHING (custom_vjp residuals = the pipeline's own inputs); the
    BACKWARD re-runs the forward pipeline and consumes each recomputed
    stage input as soon as its cotangent arrives — the 1F1B dependence
    pattern — holding only a ``2p-1``-slot ring of micro-batch inputs.
    Live activations therefore scale with ``p``, not ``m`` (verified by
    compiled memory analysis in tests/parallel/test_pipeline.py).

    Schedule (backward pass, step t, stage s, R = 2p-1):
      * recompute-forward of micro-batch ``t - s`` (same wavefront as the
        forward pass), stage input ring-buffered at slot ``mb mod R``;
      * backward of micro-batch ``t - 2(p-1) + s`` via ``jax.vjp`` on the
        ring-buffered input (one extra stage recompute — full-remat
        semantics, the policy the engine already runs);
      * activations rotate forward via ppermute, cotangents rotate
        backward; stage 0 banks input cotangents, every stage
        accumulates its local param grads.

    Cost: one extra forward sweep vs GPipe-with-remat.  ``stage_fn``'s
    aux output is NOT differentiated here (MoE router losses need grads
    — MoE models keep the GPipe schedule; transformer._run_layers_pipelined
    enforces this).

    Returns ``y [B, T, D]`` (no aux).
    """
    p = mesh.shape["pipe"]
    assert p > 1, "pipeline_apply_1f1b called without a pipe axis"
    if mesh.shape.get("seq", 1) > 1:
        raise NotImplementedError("pipe x seq is rejected (see module doc)")
    B = x.shape[0]
    m = n_mbs
    assert B % m == 0, f"rows {B} not divisible by micro-batches {m}"
    P = jax.sharding.PartitionSpec

    def split(a):
        return a.reshape((m, B // m) + a.shape[1:])

    xs = split(x)
    sides = {k: split(v) for k, v in side_inputs.items()}
    perm_fwd = [(i, (i + 1) % p) for i in range(p)]
    perm_bwd = [((i + 1) % p, i) for i in range(p)]

    def stage_call(local_params, mb_x, mb_sides):
        out, _aux = stage_fn(local_params, {"x": mb_x, **mb_sides})
        return out

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("pipe"), P(), P()),
        out_specs=P("pipe"),
        axis_names={"pipe"},
        check_vma=False,
    )
    def run_fwd(local_params, xs, sides):
        stage = jax.lax.axis_index("pipe")
        n_steps = m + p - 1

        def step(carry, t):
            recv, outs = carry
            mb_idx, valid = _wavefront(stage, t, m)
            mb_x, mb_sides = _take_mb(xs, sides, mb_idx)
            inp = jnp.where(stage == 0, mb_x, recv)
            out = stage_call(local_params, inp, mb_sides)
            outs = _bank(outs, mb_idx, out, (stage == p - 1) & valid)
            recv = jax.lax.ppermute(out, "pipe", perm_fwd)
            return (recv, outs), None

        (recv, outs), _ = jax.lax.scan(
            step,
            (jnp.zeros_like(xs[0]), jnp.zeros_like(xs)),
            jnp.arange(n_steps),
        )
        return outs

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("pipe"), P(), P(), P()),
        # dxs banks live ONLY on stage 0 — concatenate over pipe and let
        # the caller slice stage 0's block (a replicated out_spec on a
        # stage-varying value is undefined)
        out_specs=(P("pipe"), P("pipe")),
        axis_names={"pipe"},
        check_vma=False,
    )
    def run_bwd(local_params, xs, sides, dys):
        stage = jax.lax.axis_index("pipe")
        R = 2 * p - 1
        n_steps = 2 * (p - 1) + m
        g_params0 = jax.tree.map(jnp.zeros_like, local_params)
        ring0 = jnp.zeros((R,) + xs.shape[1:], xs.dtype)

        def sides_at(i):
            return {
                k: jax.lax.dynamic_index_in_dim(v, i, 0, False)
                for k, v in sides.items()
            }

        def step(carry, t):
            recv, cot_recv, ring, dxs, g_params = carry
            # ---- recompute-forward wavefront (same as the fwd pass) ----
            f_idx, f_valid = _wavefront(stage, t, m)
            mb_x = jax.lax.dynamic_index_in_dim(xs, f_idx, 0, False)
            inp = jnp.where(stage == 0, mb_x, recv)
            out = stage_call(local_params, inp, sides_at(f_idx))
            # ring-buffer this stage's input for its (later) backward;
            # invalid wavefront steps overwrite nothing that is still live
            slot_f = jnp.where(f_valid, f_idx % R, R - 1)
            keep = jax.lax.dynamic_index_in_dim(ring, slot_f, 0, False)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, jnp.where(f_valid, inp, keep), slot_f, 0
            )
            # ---- backward of the micro-batch whose cotangent arrived ----
            b_i = t - 2 * (p - 1) + stage
            b_idx = jnp.clip(b_i, 0, m - 1)
            b_valid = (b_i >= 0) & (b_i < m)
            dy_mb = jax.lax.dynamic_index_in_dim(dys, b_idx, 0, False)
            cot_in = jnp.where(stage == p - 1, dy_mb, cot_recv)
            saved = jax.lax.dynamic_index_in_dim(
                ring, b_idx % R, 0, False
            )
            _, vjp_fn = jax.vjp(
                lambda pp, xx: stage_call(pp, xx, sides_at(b_idx)),
                local_params,
                saved,
            )
            g_p, g_x = vjp_fn(cot_in)
            g_params = jax.tree.map(
                lambda acc, g: acc + jnp.where(b_valid, g, 0).astype(
                    acc.dtype
                ),
                g_params,
                g_p,
            )
            # stage 0 banks input cotangents (grads wrt xs)
            dxs = _bank(
                dxs, b_idx, g_x.astype(dxs.dtype), (stage == 0) & b_valid
            )
            recv = jax.lax.ppermute(out, "pipe", perm_fwd)
            cot_recv = jax.lax.ppermute(g_x, "pipe", perm_bwd)
            return (recv, cot_recv, ring, dxs, g_params), None

        carry0 = (
            jnp.zeros_like(xs[0]),
            jnp.zeros_like(xs[0]),
            ring0,
            jnp.zeros_like(xs),
            g_params0,
        )
        (recv, cot_recv, ring, dxs, g_params), _ = jax.lax.scan(
            step, carry0, jnp.arange(n_steps)
        )
        return g_params, dxs

    @jax.custom_vjp
    def _pipeline(stacked_params, xs, sides):
        outs = run_fwd(stacked_params, xs, sides)
        return outs[(p - 1) * m :]

    def _fwd(stacked_params, xs, sides):
        # residuals = the pipeline's own inputs; NOTHING per-step is saved
        return _pipeline(stacked_params, xs, sides), (
            stacked_params, xs, sides,
        )

    def _bwd(res, dy):
        stacked_params, xs, sides = res
        g_params, dxs_all = run_bwd(stacked_params, xs, sides, dy)
        dxs = dxs_all[:m]  # stage 0's bank
        g_sides = jax.tree.map(jnp.zeros_like, sides)
        return g_params, dxs, g_sides

    _pipeline.defvjp(_fwd, _bwd)
    ys = _pipeline(stacked_params, xs, sides)
    return ys.reshape((B,) + x.shape[1:])
