"""Memory-lean head losses.

For long contexts the [tokens, vocab] logits tensor dominates memory; these
helpers compute cross-entropy / per-token logprobs / entropy in chunks of
tokens and keep no chunk's logits between the passes (replaces the
reference's vocab-parallel cross entropy,
realhf/impl/model/parallelism/tensor_parallel/modules.py:1060, whose purpose
on GPU was the same memory saving).  Two ways, chosen by the loss's
mathematics, which a caller states by the function it calls:

* :func:`token_sum_loss` for a loss that is a SUM OVER TOKENS (PPO actor,
  SFT): a chunk's gradient is taken while its logits are alive, three head
  products a token (logits, ``d hidden``, ``d head``);
* :func:`per_token_logprobs_entropy` for everything else (DPO's sigmoid of
  per-sequence sums, forward-only scoring, a user's loss): under ``grad``
  the chunks run under ``jax.checkpoint`` and the backward pass makes each
  chunk's logits a second time, four products a token.
"""

from __future__ import annotations

import contextlib
import threading
from functools import partial
from typing import Any, Callable, Iterator, List, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.observability.tracing import region


def _chunk_logp_ent(h, w, labels):
    """h [C, D], labels [C] -> (logp [C], entropy [C])."""
    logits = (h @ w).astype(jnp.float32)  # [C, V]
    lse = jax.nn.logsumexp(logits, axis=-1)
    logp_all = logits - lse[:, None]
    p = jnp.exp(logp_all)
    entropy = -jnp.sum(p * logp_all, axis=-1)
    logp = jnp.take_along_axis(logp_all, labels[:, None], axis=-1)[:, 0]
    return logp, entropy


def _chunk_logp(h, w, labels):
    """Logprob only — skips the full-vocab entropy passes (saves several
    f32 [C, V] HBM round-trips when the caller discards entropy)."""
    logits = (h @ w).astype(jnp.float32)  # [C, V]
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    logp = tgt - lse
    return logp, jnp.zeros_like(logp)


# -- what a differentiated program makes of the head ---------------------------

_tracing = threading.local()


@contextlib.contextmanager
def head_products_traced() -> Iterator[List[int]]:
    """``with head_products_traced() as seen:`` around the TRACING of a
    program that takes a gradient: every head loss traced inside appends the
    ``[C, D] x [D, V]``-class products a token it costs there, 3
    (:func:`token_sum_loss`) or 4 (:func:`per_token_logprobs_entropy`:
    forward, recomputed, two backward).  The train engine puts the largest
    on the span ``areal.train.batch`` as ``loss_head_products``."""
    seen: List[int] = []
    outer = getattr(_tracing, "seen", None)
    _tracing.seen = seen
    try:
        yield seen
    finally:
        _tracing.seen = outer


def _note_head_products(n: int) -> None:
    seen = getattr(_tracing, "seen", None)
    if seen is not None:
        seen.append(n)


@region("areal.loss")
def per_token_logprobs_entropy(
    hidden: jax.Array,  # [N, D] hidden states (pre final-head)
    head_w: jax.Array,  # [D, V]
    labels: jax.Array,  # [N]
    chunk_size: int = 1024,
    with_entropy: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Chunk-scanned (logprob, entropy) per token; differentiable w.r.t.
    ``hidden`` and ``head_w`` with chunk-local logits rematerialized in the
    backward pass."""
    _note_head_products(4)
    N, D = hidden.shape
    pad = (-N) % chunk_size
    h = jnp.pad(hidden, ((0, pad), (0, 0)))
    lab = jnp.pad(labels, (0, pad))
    n_chunks = h.shape[0] // chunk_size
    h = h.reshape(n_chunks, chunk_size, D)
    lab = lab.reshape(n_chunks, chunk_size)

    f = jax.checkpoint(_chunk_logp_ent if with_entropy else _chunk_logp)

    def body(_, xs):
        hc, lc = xs
        return None, f(hc, head_w, lc)

    _, (logps, ents) = jax.lax.scan(body, None, (h, lab))
    return logps.reshape(-1)[:N], ents.reshape(-1)[:N]


# -- a loss that is a sum over tokens: the gradient taken chunk by chunk ---------


def _chunk_loss(h, w, labels, valid, args, token_loss, with_entropy):
    """One chunk: ``(sum of its tokens' losses, (logp [C], entropy [C]))``."""
    logp, entropy = (_chunk_logp_ent if with_entropy else _chunk_logp)(
        h, w, labels
    )
    per_token = token_loss(logp, entropy, *args).astype(jnp.float32)
    return jnp.sum(jnp.where(valid, per_token, 0.0)), (logp, entropy)


def _in_chunks(x: jax.Array, chunk_size: int) -> jax.Array:
    """[N, ...] -> [ceil(N / chunk_size), chunk_size, ...], zeros behind."""
    pad = (-x.shape[0]) % chunk_size
    x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((-1, chunk_size) + x.shape[1:])


def _chunked(hidden, labels, token_args, chunk_size):
    """The scan's inputs: ``(hidden, labels, valid, token_args)`` by chunk,
    ``valid`` false on the padding behind the last token."""
    valid = jnp.ones(hidden.shape[:1], bool)
    return jax.tree.map(
        lambda a: _in_chunks(a, chunk_size),
        (hidden, labels, valid, token_args),
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 5, 6))
def _token_sum_loss(
    hidden, head_w, labels, token_loss, token_args, chunk_size, with_entropy
):
    def body(loss_sum, xs):
        hc, lc, valid, args = xs
        loss, out = _chunk_loss(
            hc, head_w, lc, valid, args, token_loss, with_entropy
        )
        return loss_sum + loss, out

    loss_sum, (logp, entropy) = jax.lax.scan(
        body,
        jnp.zeros((), jnp.float32),
        _chunked(hidden, labels, token_args, chunk_size),
    )
    N = hidden.shape[0]
    return loss_sum, logp.reshape(-1)[:N], entropy.reshape(-1)[:N]


def _token_sum_loss_fwd(
    hidden, head_w, labels, token_loss, token_args, chunk_size, with_entropy
):
    """The forward scan with each chunk's gradient taken in its body: the
    chunk's ``d hidden`` goes out, its ``d head`` is added into the carry
    (in the head's dtype, as the transposed scan adds its cotangents)."""
    _note_head_products(3)
    grad_of_chunk = jax.value_and_grad(_chunk_loss, argnums=(0, 1), has_aux=True)

    def body(carry, xs):
        loss_sum, d_head = carry
        hc, lc, valid, args = xs
        (loss, out), (d_hc, d_w) = grad_of_chunk(
            hc, head_w, lc, valid, args, token_loss, with_entropy
        )
        return (loss_sum + loss, d_head + d_w), (out, d_hc)

    (loss_sum, d_head), ((logp, entropy), d_hidden) = jax.lax.scan(
        body,
        (jnp.zeros((), jnp.float32), jnp.zeros_like(head_w)),
        _chunked(hidden, labels, token_args, chunk_size),
    )
    N, D = hidden.shape
    out = loss_sum, logp.reshape(-1)[:N], entropy.reshape(-1)[:N]
    return out, (d_hidden.reshape(-1, D)[:N], d_head)


def _token_sum_loss_bwd(token_loss, chunk_size, with_entropy, kept, cts):
    d_hidden, d_head = kept
    g = cts[0]  # logp and entropy carry no gradient
    return (
        (g * d_hidden).astype(d_hidden.dtype),
        (g * d_head).astype(d_head.dtype),
        None,
        None,
    )


_token_sum_loss.defvjp(_token_sum_loss_fwd, _token_sum_loss_bwd)


@region("areal.loss")
def token_sum_loss(
    hidden: jax.Array,  # [N, D] hidden states (pre final-head)
    head_w: jax.Array,  # [D, V]
    labels: jax.Array,  # [N]
    token_loss: Callable[..., jax.Array],
    token_args: Any = (),  # arrays [N, ...] the loss reads token by token
    chunk_size: int = 1024,
    with_entropy: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Head and loss in one, for a loss that is a sum over tokens:
    ``(loss_sum, logp [N], entropy [N])`` with ``loss_sum = sum_t
    token_loss(logp, entropy, *token_args)[t]``.

    ``token_loss`` gets one chunk at a time (``logp`` and ``entropy`` of
    ``[C]`` tokens and the same ``[C]`` slices of ``token_args``) and returns
    their ``[C]`` losses; it must be a function of each token alone and must
    not close over traced arrays (pass them in ``token_args``).  Under
    differentiation each chunk's ``value_and_grad`` runs while its logits
    are alive: one logits product and the two gradient products a token,
    nothing recomputed and no logits kept between the passes.  The gradient
    reaches ``hidden`` and ``head_w`` through ``loss_sum`` ALONE: the
    returned ``logp`` and ``entropy`` are for statistics and carry none, so
    a term that needs a gradient through either (an entropy bonus) belongs
    INSIDE ``token_loss``.  With ``with_entropy=False`` the entropy passes
    are skipped and ``entropy`` is zeros."""
    return _token_sum_loss(
        hidden, head_w, labels, token_loss, token_args, chunk_size,
        with_entropy,
    )


def _masked_nll(logp, _entropy, mask):
    return -logp * mask


@region("areal.loss")
def masked_cross_entropy(
    hidden: jax.Array,  # [N, D]
    head_w: jax.Array,  # [D, V]
    labels: jax.Array,  # [N]
    mask: jax.Array,  # [N] float/bool
    chunk_size: int = 1024,
) -> Tuple[jax.Array, jax.Array]:
    """(summed NLL over masked tokens, token count).  Mean = sum/count."""
    mask = mask.astype(jnp.float32)
    nll_sum, _, _ = token_sum_loss(
        hidden, head_w, labels, _masked_nll, (mask,), chunk_size,
        with_entropy=False,
    )
    return nll_sum, jnp.sum(mask)
