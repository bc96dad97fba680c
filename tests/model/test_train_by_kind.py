"""A stack stated by kind on the TRAINER (``hybrid.hidden_states`` as
``transformer.hidden_states`` hands it over): the flash kernels by kind
against the dense form, the refusals by name, the layout's cost by kind and
the trainer's record."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hybrid, transformer
from areal_tpu.models.config import TransformerConfig
from areal_tpu.ops import flash_attention as fa
from areal_tpu.system import flops_counter

KINDS = ("attention", "window", "window", "window", "attention")


def _cfg(**kw):
    base = dict(
        n_layers=5, hidden_dim=64, n_q_heads=6, n_kv_heads=2, head_dim=16,
        intermediate_dim=128, vocab_size=128, layer_types=KINDS,
        sliding_window=200, swa_n_q_heads=8, swa_rotary_base=10000.0,
        rotary_base=500000.0, rope_partial_dim=8, rope_yarn_factor=4.0,
        rope_yarn_original_max=64, rope_yarn_beta_fast=8.0,
        attention_gate="headwise", swa_attention_gate="headwise",
        n_dense_layers=1, n_experts=16, n_experts_per_tok=4,
        moe_intermediate_dim=32, shared_expert_dim=32,
        moe_router="sigmoid_group", moe_routed_scale=2.5, moe_held_experts=8,
        dtype="float32",
    )
    return TransformerConfig(**{**base, **kw})


def _packed_row(T, lens, seed=0):
    rng = np.random.default_rng(seed)
    tokens, positions, seg = (np.zeros((1, T), np.int32) for _ in range(3))
    at = 0
    for n, L in enumerate(lens):
        tokens[0, at : at + L] = rng.integers(3, 128, L)
        positions[0, at : at + L] = np.arange(L)
        seg[0, at : at + L] = n + 1
        at += L
    return jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(seg)


def _loss_of(cfg, batch, ct):
    def loss(p):
        x = transformer.hidden_states(p, cfg, *batch)
        return jnp.sum(x * ct * (batch[2] != 0)[..., None])

    return loss


@pytest.fixture(scope="module")
def dense_form():
    """The float32 ``[T, T]`` form's value and gradients, once for both
    cases below (rematerialising a half changes no number)."""
    cfg = _cfg()
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    batch = _packed_row(1024, (300, 450, 200))
    ct = jax.random.normal(jax.random.PRNGKey(1), (1, 1024, 64))
    with jax.default_matmul_precision("highest"):
        assert not transformer.takes_flash(cfg, 1024, None)  # the CPU's path
        want, want_g = jax.value_and_grad(_loss_of(cfg, batch, ct))(params)
    return params, batch, ct, want, want_g


@pytest.mark.parametrize("remat", [False, True])
def test_the_flash_kernels_by_kind_give_the_dense_forms_states_and_gradients(
    monkeypatch, dense_form, remat
):
    """Packed rows of three segments across block edges: window layers
    (a window of 200 positions, inside a block's band) and full layers through the flash kernels (interpret mode)
    against the float32 ``[T, T]`` form, states and every parameter's
    gradient, with and without rematerialised halves."""
    params, batch, ct, want, want_g = dense_form
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(transformer, "takes_flash", lambda *a: True)
        monkeypatch.setattr(
            fa, "flash_attention",
            functools.partial(fa.flash_attention, interpret=True),
        )
        got, got_g = jax.value_and_grad(_loss_of(_cfg(remat=remat), batch, ct))(
            params
        )
    assert abs(float(got - want)) < 1e-3 * abs(float(want)) + 1e-3
    for (path, g), w in zip(
        jax.tree_util.tree_leaves_with_path(got_g), jax.tree.leaves(want_g)
    ):
        scale = float(jnp.abs(w).max()) + 1e-9
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale + 1e-7, path


def test_a_stack_takes_flash_when_every_attention_kind_of_it_does(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert transformer.takes_flash(_cfg(), 1024, None)
    assert not transformer.takes_flash(_cfg(), 640, None)  # not whole blocks
    assert not transformer.takes_flash(_cfg(attention_scale=0.1), 1024, None)
    mamba = _cfg(
        layer_types=("mamba",) + KINDS[1:], mamba_n_heads=2, mamba_head_dim=8,
        mamba_d_state=8,
    )
    assert not transformer.takes_flash(mamba, 1024, None)
    # a dense stack under ONE window takes the kernels too
    dense = dataclasses.replace(_cfg(), layer_types=None, n_dense_layers=0)
    assert transformer.takes_flash(dense, 1024, None)


def test_the_trainer_refuses_a_state_kind_and_the_server_a_window_of_its_own_widths():
    from areal_tpu.engine.backend import refuse_unserved

    mamba = _cfg(
        layer_types=("mamba",) + KINDS[1:], mamba_n_heads=2, mamba_head_dim=8,
        mamba_d_state=8,
    )
    batch = _packed_row(128, (50, 60))
    with pytest.raises(NotImplementedError, match=r"layer kinds \['mamba'\]"):
        transformer.hidden_states({}, mamba, *batch)
    with pytest.raises(NotImplementedError, match="8 query heads"):
        refuse_unserved(_cfg())
    # one head count for both kinds: served as before
    refuse_unserved(_cfg(swa_n_q_heads=0, swa_attention_gate=None))


def test_the_layouts_cost_counts_a_window_layer_by_min_T_window():
    cfg = _cfg(sliding_window=512)
    short = flops_counter.forward_flops(cfg, [512], with_head=False)
    long = flops_counter.forward_flops(cfg, [4096], with_head=False)
    full = dataclasses.replace(cfg, layer_types=("attention",) * 5, swa_n_q_heads=0)
    # per token a window layer's attention stops growing past the window
    # (from w / 2 positions a query at t = w towards w), a full layer's not
    per_tok_w = (long / 4096 - short / 512)
    per_tok_f = (
        flops_counter.forward_flops(full, [4096], with_head=False) / 4096
        - flops_counter.forward_flops(full, [512], with_head=False) / 512
    )
    assert 0 < per_tok_w < 0.6 * per_tok_f
    # by hand: a window layer of 8 heads x 16 over t = 4096, w = 512
    pairs = 512 * 512 + 2 * 512 * (4096 - 512)
    assert (
        flops_counter.forward_flops(cfg, [4096], with_head=False)
        - flops_counter.forward_flops(
            dataclasses.replace(cfg, sliding_window=4096), [4096], with_head=False
        )
    ) == 3 * 2 * 128 * (pairs - 4096 * 4096)


def test_grad_groups_cover_the_tree():
    cfg = _cfg()
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    groups = {
        hybrid.grad_group(tuple(k.key for k in path))
        for path, _ in jax.tree_util.tree_leaves_with_path(shapes)
    }
    assert groups == {
        "attention", "window", "gate", "router", "experts", "shared", "dense",
        "embed", "head", "norms",
    }
    assert shapes["window"]["q"]["w"].shape == (3, 64, 8 * 16)
    assert shapes["attn"]["q"]["w"].shape == (2, 64, 6 * 16)
    assert shapes["attn"]["gate"]["w"].shape == (2, 64, 6)
