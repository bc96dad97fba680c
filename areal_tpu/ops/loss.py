"""Memory-lean head losses.

For long contexts the [tokens, vocab] logits tensor dominates memory; these
helpers compute cross-entropy / per-token logprobs / entropy in vocab chunks
under ``jax.checkpoint`` so the backward pass recomputes chunk logits instead
of keeping them alive (replaces the reference's vocab-parallel cross entropy,
realhf/impl/model/parallelism/tensor_parallel/modules.py:1060, whose purpose
on GPU was the same memory saving).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

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


@region("areal.loss")
def masked_cross_entropy(
    hidden: jax.Array,  # [N, D]
    head_w: jax.Array,  # [D, V]
    labels: jax.Array,  # [N]
    mask: jax.Array,  # [N] float/bool
    chunk_size: int = 1024,
) -> Tuple[jax.Array, jax.Array]:
    """(summed NLL over masked tokens, token count).  Mean = sum/count."""
    logp, _ = per_token_logprobs_entropy(
        hidden, head_w, labels, chunk_size, with_entropy=False
    )
    mask = mask.astype(jnp.float32)
    return -jnp.sum(logp * mask), jnp.sum(mask)
