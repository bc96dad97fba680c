"""``ops/loss.py``: the head-and-loss of a sum over tokens
(``token_sum_loss``: a chunk's gradient taken while its logits are alive)
against plain autodiff of ``per_token_logprobs_entropy`` + the same loss,
through the two interfaces that call it (``_actor_loss_of_hidden``,
``sft_loss_fn``'s ``masked_cross_entropy``).  CPU, tiny widths, more than one
chunk of 1,024 tokens and ``N`` no multiple of it."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.interfaces import ppo_functional
from areal_tpu.interfaces.ppo_interface import (
    PPOActorInterface,
    _actor_loss_of_hidden,
)
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import head_weight
from areal_tpu.ops import loss as loss_ops

B, T, D, V = 2, 601, 16, 48  # N = 1,200 transitions: chunks of 1,024 + 176


def _params(dtype, tied):
    w = 0.4 * jax.random.normal(jax.random.PRNGKey(1), (D, V), jnp.float32)
    w = w.astype(dtype)
    return {"embed": {"weight": w.T}} if tied else {"lm_head": {"w": w}}


def _batch(zero_rows=False, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    mask = (jax.random.uniform(k[0], (B, T)) > 0.3).astype(jnp.float32)
    mask = mask.at[:, -1].set(0.0)
    if zero_rows:
        mask = mask.at[0].set(0.0)
    return {
        "tokens": jax.random.randint(k[1], (B, T), 0, V),
        "ppo_loss_mask": mask,
        "packed_logprobs": -3.5 + 0.7 * jax.random.normal(k[2], (B, T)),
        "prox_logp": -3.5 + 0.7 * jax.random.normal(k[3], (B, T)),
        "advantages": jax.random.normal(k[4], (B, T)),
    }


def _hidden(dtype):
    h = jax.random.normal(jax.random.PRNGKey(2), (B, T, D), jnp.float32)
    return h.astype(dtype)


def _plain_actor_loss(params, cfg, batch, iface, hidden):
    """The loss as plain autodiff sees it: every chunk's log-probability
    first, the PPO loss over the whole micro-batch after."""
    w = head_weight(params, cfg).astype(hidden.dtype) / iface.temperature
    new_logp, entropy = loss_ops.per_token_logprobs_entropy(
        hidden[:, :-1].reshape(-1, D), w, batch["tokens"][:, 1:].reshape(-1)
    )
    new_logp = jnp.pad(new_logp.reshape(B, T - 1), ((0, 0), (0, 1)))
    entropy = jnp.pad(entropy.reshape(B, T - 1), ((0, 0), (0, 1)))
    mask = batch["ppo_loss_mask"]
    loss, stat = ppo_functional.actor_loss_fn(
        new_logp,
        batch["packed_logprobs"],
        batch["advantages"],
        iface.eps_clip,
        mask,
        c_clip=iface.c_clip,
        proximal_logprobs=(
            batch["prox_logp"] if iface.use_decoupled_loss else None
        ),
        behav_imp_weight_cap=iface.behav_imp_weight_cap,
    )
    count = jnp.maximum(jnp.sum(mask), 1.0)
    stats = {
        "clip_count_sum": jnp.sum(stat["clip_mask"]),
        "approx_kl_sum": jnp.sum(stat["approx_kl"]),
        "entropy_sum": jnp.sum(entropy * mask),
    }
    return loss * count, (count, stats)


def _shipped_actor_loss(params, cfg, batch, iface, hidden):
    loss_sum, count, stats = _actor_loss_of_hidden(
        params, cfg, batch, iface, hidden, None
    )
    return loss_sum, (count, stats)


def _close(got, want, dtype, what):
    """float32: 1e-5 relative.  bfloat16: within one bf16 rounding (2^-7
    of the array's scale) of plain autodiff's gradient, which rounds the
    same products to bfloat16 and adds the chunks in the other order."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got)), what
    scale = np.abs(want).max()
    tol = 1e-5 if dtype == jnp.float32 else 2.0**-7
    np.testing.assert_allclose(
        got, want, rtol=tol, atol=tol * scale, err_msg=what
    )


ACTOR_CASES = {
    "plain": dict(),
    "decoupled": dict(use_decoupled_loss=True),
    "dual_clip": dict(c_clip=3.0),
    "capped": dict(use_decoupled_loss=True, behav_imp_weight_cap=1.5),
    "temperature": dict(temperature=0.7),
    "all_zero_rows": dict(use_decoupled_loss=True, c_clip=3.0),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
@pytest.mark.parametrize("case", sorted(ACTOR_CASES))
def test_actor_loss_and_its_gradients_equal_plain_autodiff(case, dtype):
    iface = PPOActorInterface(**ACTOR_CASES[case])
    cfg = tiny_config(vocab_size=V, hidden_dim=D)
    params, hidden = _params(dtype, tied=False), _hidden(dtype)
    batch = _batch(zero_rows=case == "all_zero_rows")

    def run(loss):
        return jax.jit(
            jax.value_and_grad(
                lambda p, h: loss(p, cfg, batch, iface, h),
                argnums=(0, 1),
                has_aux=True,
            )
        )(params, hidden)

    (got, (count, stats)), (d_params, d_hidden) = run(_shipped_actor_loss)
    (want, (count_w, stats_w)), (d_params_w, d_hidden_w) = run(
        _plain_actor_loss
    )
    assert float(count) == float(count_w) > 100
    _close(got, want, jnp.float32, "loss_sum")
    for k, v in stats_w.items():
        _close(stats[k], v, jnp.float32, k)
    if case == "capped":
        assert float(stats["clip_count_sum"]) > 0
    _close(d_hidden, d_hidden_w, dtype, "d hidden")
    _close(d_params["lm_head"]["w"], d_params_w["lm_head"]["w"], dtype, "d head")
    assert d_hidden.dtype == hidden.dtype
    assert float(jnp.abs(d_hidden_w).max()) > 1e-3
    # an all-masked row takes no gradient at all
    if case == "all_zero_rows":
        assert not np.asarray(d_hidden[0], np.float32).any()


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_actor_loss_gradient_reaches_a_tied_and_an_untied_head(tied):
    iface = PPOActorInterface(use_decoupled_loss=True)
    cfg = tiny_config(vocab_size=V, hidden_dim=D, tied_embedding=tied)
    params, hidden, batch = _params(jnp.float32, tied), _hidden(jnp.float32), _batch()
    grads = [
        jax.grad(lambda p: loss(p, cfg, batch, iface, hidden)[0])(params)
        for loss in (_shipped_actor_loss, _plain_actor_loss)
    ]
    leaf = ("embed", "weight") if tied else ("lm_head", "w")
    got, want = (g[leaf[0]][leaf[1]] for g in grads)
    assert got.shape == ((V, D) if tied else (D, V))
    _close(got, want, jnp.float32, "d head")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_masked_cross_entropy_and_its_gradients_equal_plain_autodiff(dtype):
    """The SFT loss, ``-sum_t mask_t logp_t``: no entropy pass."""
    hidden = _hidden(dtype)[:, :-1].reshape(-1, D)
    w = _params(dtype, tied=False)["lm_head"]["w"]
    batch = _batch()
    labels = batch["tokens"][:, 1:].reshape(-1)
    mask = batch["ppo_loss_mask"][:, :-1].reshape(-1) > 0

    def plain(h, w):
        logp, _ = loss_ops.per_token_logprobs_entropy(
            h, w, labels, with_entropy=False
        )
        return -jnp.sum(logp * mask)

    def shipped(h, w):
        nll, count = loss_ops.masked_cross_entropy(h, w, labels, mask)
        return nll

    got, d = jax.jit(jax.value_and_grad(shipped, (0, 1)))(hidden, w)
    want, d_w = jax.jit(jax.value_and_grad(plain, (0, 1)))(hidden, w)
    _close(got, want, jnp.float32, "nll_sum")
    _close(d[0], d_w[0], dtype, "d hidden")
    _close(d[1], d_w[1], dtype, "d head")
    # without differentiation: the forward scan alone, the same sum
    nll, count = jax.jit(loss_ops.masked_cross_entropy)(hidden, w, labels, mask)
    _close(nll, want, jnp.float32, "nll_sum, not differentiated")
    assert float(count) == float(mask.sum())


def _toy(n=37):
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    return (
        jax.random.normal(k[0], (n, D)),
        0.4 * jax.random.normal(k[1], (D, V)),
        jax.random.randint(k[2], (n,), 0, V),
    )


def test_returned_logp_and_entropy_are_the_chunks_own_and_carry_no_gradient():
    h, w, labels = _toy()
    logp_w, ent_w = loss_ops.per_token_logprobs_entropy(h, w, labels, 8)

    def f(h, w):
        loss_sum, logp, ent = loss_ops.token_sum_loss(
            h, w, labels, lambda logp, ent: -logp, chunk_size=8
        )
        return loss_sum + 5.0 * jnp.sum(logp) + 7.0 * jnp.sum(ent), (logp, ent)

    (_, (logp, ent)), grads = jax.value_and_grad(f, (0, 1), has_aux=True)(h, w)
    np.testing.assert_allclose(logp, logp_w, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(ent, ent_w, rtol=1e-6, atol=1e-6)
    # the gradient is loss_sum's alone
    want = jax.grad(
        lambda h, w: -jnp.sum(
            loss_ops.per_token_logprobs_entropy(h, w, labels, 8)[0]
        ),
        (0, 1),
    )(h, w)
    for g, g_w in zip(grads, want):
        _close(g, g_w, jnp.float32, "gradient through loss_sum alone")
    # with_entropy=False: zeros, as per_token_logprobs_entropy gives
    _, _, none = loss_ops.token_sum_loss(
        h, w, labels, lambda logp, ent: -logp, chunk_size=8, with_entropy=False
    )
    assert not np.asarray(none).any()


def test_a_term_inside_the_token_loss_takes_its_gradient_through_entropy():
    """An entropy bonus belongs inside ``token_loss``: the chunk's
    ``value_and_grad`` sees it there, and per-token arguments of any
    trailing shape reach it sliced like the tokens."""
    h, w, labels = _toy()
    coef = jnp.stack([jnp.linspace(0.1, 0.9, 37), jnp.ones(37)], axis=1)

    def token_loss(logp, ent, coef):
        return -coef[:, 1] * logp - coef[:, 0] * ent

    def shipped(h, w):
        return loss_ops.token_sum_loss(
            h, w, labels, token_loss, (coef,), chunk_size=8
        )[0]

    def plain(h, w):
        logp, ent = loss_ops.per_token_logprobs_entropy(h, w, labels, 8)
        return jnp.sum(token_loss(logp, ent, coef))

    got, d = jax.value_and_grad(shipped, (0, 1))(h, w)
    want, d_w = jax.value_and_grad(plain, (0, 1))(h, w)
    _close(got, want, jnp.float32, "loss_sum")
    _close(d[0], d_w[0], jnp.float32, "d hidden")
    _close(d[1], d_w[1], jnp.float32, "d head")
    # and it is not the gradient without the bonus
    assert float(jnp.abs(d[1] - jax.grad(
        lambda w: -jnp.sum(loss_ops.per_token_logprobs_entropy(h, w, labels, 8)[0])
    )(w)).max()) > 1e-3


def test_head_products_traced_says_which_way_a_differentiated_loss_went():
    h, w, labels = _toy()

    def token_sum(h):
        return loss_ops.token_sum_loss(
            h, w, labels, lambda logp, ent: -logp, chunk_size=8
        )[0]

    def per_token(h):
        return -jnp.sum(loss_ops.per_token_logprobs_entropy(h, w, labels, 8)[0])

    with loss_ops.head_products_traced() as seen:
        jax.make_jaxpr(jax.grad(token_sum))(h)
        assert seen == [3]
        with loss_ops.head_products_traced() as inner:
            jax.make_jaxpr(jax.grad(per_token))(h)
        assert inner == [4] and seen == [3]
        # not differentiated, the token sum is the forward scan: no count
        jax.make_jaxpr(token_sum)(h)
        assert seen == [3]
    jax.make_jaxpr(jax.grad(per_token))(h)  # nobody listens: nothing kept
    assert seen == [3] and inner == [4]


# -- the lowered train step ------------------------------------------------------


def _dot_generals(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(
                tuple(v.aval.shape for v in (*eqn.invars, *eqn.outvars))
            )
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _dot_generals(sub, out)
    return out


def test_lowered_train_step_holds_three_head_products_a_chunk_body():
    """The PPO train step's program holds exactly three products of the
    ``[C, D] x [D, V]`` class (logits, ``d hidden``, ``d head``), all of
    them in the ONE chunk body the loss scans, and no recomputed pass under
    ``areal.loss``; the layers keep theirs."""
    from benchmark.lib.region_reduce import region_of
    from tests.observability.test_regions import _train_step

    vocab, chunk = 96, 1024  # a width no other product of the model has
    engines = []
    step, args, _ = _train_step(engines, vocab_size=vocab)
    engine = engines[0]
    traced = jax.make_jaxpr(step.__wrapped__)(*args)
    products = [
        s for s in _dot_generals(traced.jaxpr, []) if any(vocab in x for x in s)
    ]
    hidden = engine.model_cfg.hidden_dim
    assert sorted(products) == sorted(
        [
            ((chunk, hidden), (hidden, vocab), (chunk, vocab)),  # logits
            ((chunk, vocab), (hidden, vocab), (chunk, hidden)),  # d hidden
            ((chunk, vocab), (chunk, hidden), (vocab, hidden)),  # d head
        ]
    ), products
    assert engine._loss_head_products == {
        next(iter(engine._train_step_cache)): 3
    }
    paths = set(
        re.findall(r'op_name="([^"]*)"', step.lower(*args).compile().as_text())
    )
    of_loss = [p for p in paths if region_of(p) == "areal.loss"]
    assert any(p.endswith("/dot_general") for p in of_loss)
    assert not [p for p in of_loss if "rematted_computation" in p]
    assert any(
        "rematted_computation" in p for p in paths
        if region_of(p) == "areal.mlp"
    )
