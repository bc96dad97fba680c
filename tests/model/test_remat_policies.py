"""Graduated remat presets (areal_tpu/models/remat.py): every policy must
preserve the training math exactly (rematerialisation changes WHAT is
recomputed, never the result), and the AOT memory-analysis harness that
the v5e fits-HBM assertion rides on must cover every preset end-to-end on
CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.interfaces.sft_interface import sft_loss_fn
from areal_tpu.models import remat, transformer
from areal_tpu.models.config import tiny_config


def _batch(cfg, B=2, T=16, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "tokens": jnp.asarray(
            rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32
        ),
        "positions": jnp.tile(jnp.arange(T, dtype=jnp.int32), (B, 1)),
        "seg_ids": jnp.ones((B, T), jnp.int32),
        "prompt_mask": jnp.zeros((B, T), bool),
    }


def _grad(cfg, params, batch):
    def loss(p):
        loss_sum, denom, _ = sft_loss_fn(p, cfg, batch)
        return loss_sum / denom

    return jax.jit(jax.grad(loss))(params)


@pytest.mark.parametrize("policy", remat.POLICY_NAMES)
def test_policy_gradient_parity_with_no_remat(policy):
    cfg0 = tiny_config(vocab_size=64)
    params = transformer.init_params(cfg0, jax.random.PRNGKey(0))
    batch = _batch(cfg0)
    g_ref = _grad(dataclasses.replace(cfg0, remat=False), params, batch)
    g_pol = _grad(
        dataclasses.replace(cfg0, remat=True, remat_policy=policy),
        params,
        batch,
    )
    for a, b in zip(jax.tree.leaves(g_pol), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4
        )


def test_policy_table_is_graduated_and_complete():
    # the table's whole point: presets between "save nothing" and the
    # qkv_attn policy that OOMed v5e — and every name resolves to a policy
    assert remat.POLICY_NAMES[0] == "none"
    assert {"attn_out", "mlp", "offload_qkv"} < set(remat.POLICY_NAMES)
    for name in remat.POLICY_NAMES:
        if name == "none":
            assert remat.policy_for(name) is None
        else:
            assert callable(remat.policy_for(name))
    with pytest.raises(ValueError):
        remat.policy_for("bogus")


def test_config_rejects_unknown_policy():
    with pytest.raises(AssertionError):
        tiny_config(remat_policy="save_everything_twice")


def test_compile_train_step_memory_analysis_every_preset():
    """The fits-HBM property is checked through compile_train_step +
    memory_summary; every preset must compile AOT (no params materialized)
    and report a positive peak-temp figure on this backend."""
    opt = OptimizerConfig(lr=1e-3)
    for name in remat.POLICY_NAMES:
        cfg = dataclasses.replace(
            tiny_config(vocab_size=64), remat=True, remat_policy=name
        )
        compiled, abstract = remat.compile_train_step(
            cfg, opt, n_seqs=2, seq_len=16
        )
        ms = remat.memory_summary(compiled)
        assert ms is not None and ms["peak_temp_gb"] > 0, (name, ms)
        assert set(abstract) == {"params", "opt_state", "batch"}


def test_compiled_step_trains():
    """The AOT executable must be directly callable and actually descend
    the loss."""
    cfg = dataclasses.replace(
        tiny_config(vocab_size=64), remat=True, remat_policy="attn_out"
    )
    opt_cfg = OptimizerConfig(
        lr=1e-2, lr_scheduler_type="constant", warmup_steps_proportion=0.0
    )
    compiled, _ = remat.compile_train_step(
        cfg, opt_cfg, n_seqs=2, seq_len=16
    )
    from areal_tpu.engine.optimizer import make_optimizer

    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = jax.jit(make_optimizer(opt_cfg, 100).init)(params)
    batch = _batch(cfg)
    p, o = params, opt_state
    losses = []
    for _ in range(6):
        p, o, loss = compiled(p, o, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
