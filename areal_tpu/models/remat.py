"""Graduated rematerialisation policies for the layer scan.

Round 1-5 shipped an all-or-nothing choice: full remat ("none") or
``qkv_attn`` (save q/k/v projections + attention output), and the latter
OOMs v5e at the bench batch (measured 17.0G peak temp vs 15.75G HBM, r4).
This module replaces the two hardcoded branches in
``models/transformer._scan_layers`` with a POLICY TABLE built from the
``checkpoint_name`` tags the forward already plants (q_proj/k_proj/v_proj/
attn_out/mlp_out), graduated by per-layer saved bytes so a config can buy
back backward-recompute FLOPs in steps instead of one 4x jump:

  name         saves per layer (per token)          role
  ----------   ----------------------------------   -------------------------
  none         nothing                              full recompute (max mem headroom)
  attn_out     attn_out                [D]          skips the whole attention-block
                                                    recompute for the o-proj/residual
                                                    backward at 1 activation/layer
  mlp          attn_out + mlp_out      [2D]         both block boundaries saved:
                                                    backward recomputes only INSIDE
                                                    a block, never across it
  qkv_attn     q,k,v,attn_out          [~4D]        also skips qkv-projection
                                                    recompute (the v5p policy)
  offload_qkv  q,k,v,attn_out -> HOST  [0 on HBM]   qkv_attn's FLOP savings at
                                                    none's device footprint, paying
                                                    d2h/h2d DMA instead
  dots         every matmul output                  cheapest backward, most memory

This is the JAX-native equivalent of Megatron's
``--recompute-granularity/--recompute-method/--recompute-num-layers`` knobs
the reference drives through its ``MegatronConfig`` (AReaL leans on them for
exactly this memory/throughput trade; realhf/api/cli_args.py).

``compile_train_step`` AOT-compiles one full train step (grad + optimizer
update) WITHOUT materializing params, so "fits v5e at the bench batch" is a
checkable property of every (policy, moment-dtype) cell via XLA's
``memory_analysis`` — asserted at tiny shapes in
``tests/model/test_remat_policies.py``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

# tensor-name tags planted by models/transformer.py (_attn_qkv / _layer)
QKV_NAMES = ("q_proj", "k_proj", "v_proj")
ATTN_OUT = "attn_out"
MLP_OUT = "mlp_out"


def _none() -> None:
    return None  # plain jax.checkpoint: save nothing, recompute everything


def _attn_out():
    import jax

    return jax.checkpoint_policies.save_only_these_names(ATTN_OUT)


def _mlp():
    import jax

    return jax.checkpoint_policies.save_only_these_names(ATTN_OUT, MLP_OUT)


def _qkv_attn():
    import jax

    return jax.checkpoint_policies.save_only_these_names(*QKV_NAMES, ATTN_OUT)


def _offload_qkv():
    import jax

    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=[*QKV_NAMES, ATTN_OUT],
        offload_src="device",
        offload_dst="pinned_host",
    )


def _dots():
    import jax

    return jax.checkpoint_policies.dots_with_no_batch_dims_saveable


# ordered roughly by device-activation footprint, smallest first
POLICIES: Dict[str, Callable[[], Any]] = {
    "none": _none,
    "offload_qkv": _offload_qkv,
    "attn_out": _attn_out,
    "mlp": _mlp,
    "qkv_attn": _qkv_attn,
    "dots": _dots,
}

POLICY_NAMES: Tuple[str, ...] = tuple(POLICIES)


def policy_for(name: str):
    """The jax.checkpoint policy for a preset name (None = save nothing)."""
    try:
        return POLICIES[name]()
    except KeyError:
        raise ValueError(
            f"unknown remat_policy {name!r} (valid: {POLICY_NAMES})"
        ) from None


# ---------------------------------------------------------------------------
# AOT train-step compilation + memory analysis
# ---------------------------------------------------------------------------


def compile_train_step(
    cfg,
    optimizer_cfg=None,
    n_seqs: int = 16,
    seq_len: int = 2048,
    total_train_steps: int = 100,
    donate: bool = True,
):
    """AOT-compile one SFT train step (value_and_grad + clip + adamw apply)
    at batch [n_seqs, seq_len] and return ``(compiled, abstract_state)``.

    Compilation is from ``jax.ShapeDtypeStruct``s only — no params are
    materialized, so a 0.5B cell costs compile time, not HBM.  The returned
    ``compiled`` executable IS callable (``compiled(params, opt_state,
    batch)``) and donates params/opt_state like the engine's fused step;
    ``abstract_state`` is ``{"params", "opt_state", "batch"}`` shape trees
    for building concrete inputs.  ``compiled.memory_analysis()`` gives the
    XLA peak-temp/argument/output byte accounting per cell.
    """
    import jax
    import jax.numpy as jnp

    from areal_tpu.engine.optimizer import OptimizerConfig, make_optimizer
    from areal_tpu.interfaces.sft_interface import sft_loss_fn
    from areal_tpu.models import transformer

    optimizer_cfg = optimizer_cfg or OptimizerConfig()
    tx = make_optimizer(optimizer_cfg, total_train_steps)

    def step(params, opt_state, batch):
        def scalar_loss(p):
            loss_sum, denom, _stats = sft_loss_fn(p, cfg, batch)
            return loss_sum, denom

        (loss_sum, denom), grads = jax.value_and_grad(
            scalar_loss, has_aux=True
        )(params)
        grads = jax.tree.map(
            lambda g: g / jnp.maximum(denom, 1e-8).astype(g.dtype), grads
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree.map(
            lambda p, u: p + u.astype(p.dtype), params, updates
        )
        return params, opt_state, loss_sum / jnp.maximum(denom, 1e-8)

    params_s = jax.eval_shape(
        lambda k: transformer.init_params(cfg, k), jax.random.PRNGKey(0)
    )
    opt_s = jax.eval_shape(tx.init, params_s)
    batch_s = {
        "tokens": jax.ShapeDtypeStruct((n_seqs, seq_len), jnp.int32),
        "positions": jax.ShapeDtypeStruct((n_seqs, seq_len), jnp.int32),
        "seg_ids": jax.ShapeDtypeStruct((n_seqs, seq_len), jnp.int32),
        "prompt_mask": jax.ShapeDtypeStruct((n_seqs, seq_len), jnp.bool_),
    }
    jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    compiled = jitted.lower(params_s, opt_s, batch_s).compile()
    return compiled, {"params": params_s, "opt_state": opt_s, "batch": batch_s}


def memory_summary(compiled) -> Optional[Dict[str, float]]:
    """{peak_temp_gb, argument_gb, output_gb, host_temp_gb} from an AOT
    executable's XLA memory analysis; None when the backend reports none."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - backend-dependent surface
        return None
    if ma is None:
        return None
    gb = float(2**30)
    try:
        return {
            "peak_temp_gb": ma.temp_size_in_bytes / gb,
            "argument_gb": ma.argument_size_in_bytes / gb,
            "output_gb": ma.output_size_in_bytes / gb,
            "host_temp_gb": getattr(ma, "host_temp_size_in_bytes", 0) / gb,
        }
    except AttributeError:
        return None
