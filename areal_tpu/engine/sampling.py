"""Token sampling (temperature / top-k / top-p) in jit
(reference: realhf/impl/model/utils/logits_warper.py + the genstep sampling in
realhf/impl/model/nn/real_llm_generate.py:30).

Two samplers share the filtering/logprob math:

* :func:`sample_logits` — one PRNG key per CALL (the original contract).
  The key is whatever the caller split off its chain, so the random
  stream depends on HOW MANY sampling calls preceded this one — fine for
  the static-batch generator, a hazard for the serving engine where the
  number of dispatches producing a position varies (pipeline depth,
  chunked continuations).
* :func:`sample_logits_keyed` — the key for each row is derived from
  ``(base_key, row, absolute_position)`` by ``fold_in``, so the draw for
  "row r's token at position p" is a pure function of the seed: the
  stream is invariant to chunk size and pipeline depth.  Sampling uses
  the Gumbel-max trick over the same filtered logits ``sample_logits``
  samples from (``categorical`` is Gumbel-max internally), so the two
  samplers draw from identical distributions.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Tuple

import jax
import jax.numpy as jnp

from areal_tpu.observability.tracing import region


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Static (compile-time) sampling configuration."""

    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 or >= vocab disables
    greedy: bool = False


def _filtered_logits(
    logits: jax.Array,  # [B, V] post-temperature
    params: SamplingParams,
    ban_mask: jax.Array = None,
) -> jax.Array:
    """Apply ban + top-k + top-p filters (-inf out the filtered entries)."""
    sample_from = logits
    if ban_mask is not None:
        sample_from = jnp.where(ban_mask, -jnp.inf, sample_from)
    if params.greedy:
        return sample_from
    filtered = sample_from
    V = logits.shape[-1]
    if params.top_k and params.top_k < V:
        kth = jnp.sort(filtered, axis=-1)[:, V - params.top_k][:, None]
        filtered = jnp.where(filtered < kth, -jnp.inf, filtered)
    if params.top_p < 1.0:
        sorted_logits = jnp.sort(filtered, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep smallest prefix with cum >= top_p (always keep first)
        cutoff_mask = cum - probs >= params.top_p
        cutoff_logit = jnp.min(
            jnp.where(cutoff_mask, jnp.inf, sorted_logits), axis=-1
        )[:, None]
        filtered = jnp.where(filtered < cutoff_logit, -jnp.inf, filtered)
    return filtered


@region("areal.sample")
def sample_logits(
    logits: jax.Array,  # [B, V] float32
    rng: jax.Array,
    params: SamplingParams,
    ban_mask: jax.Array = None,  # [B, V] or [V] bool: True = never sample
) -> Tuple[jax.Array, jax.Array]:
    """Returns (tokens [B], logprob-of-sampled-token [B]).

    The reported logprob is from the *post-temperature* distribution without
    top-k/p filtering or bans — matching what inference servers report and
    what PPO treats as the behavioral logprob (the trainer's recompute knows
    nothing about sampling-time filters, so parity requires excluding them).
    """
    # Scale even in greedy mode: argmax is temperature-invariant but the
    # reported behavioral logprob must match the trainer's recompute, which
    # always applies temperature.
    if params.temperature != 1.0:
        logits = logits / max(params.temperature, 1e-5)
    base_logprobs = jax.nn.log_softmax(logits, axis=-1)
    filtered = _filtered_logits(logits, params, ban_mask)

    if params.greedy:
        tokens = jnp.argmax(filtered, axis=-1)
    else:
        tokens = jax.random.categorical(rng, filtered, axis=-1)

    logp = jnp.take_along_axis(base_logprobs, tokens[:, None], axis=-1)[:, 0]
    return tokens.astype(jnp.int32), logp


@region("areal.sample")
def sample_logits_keyed(
    logits: jax.Array,  # [B, V] float32
    base_rng: jax.Array,  # ONE fixed key per engine/run, never split
    rows: jax.Array,  # [B] per-ROW key identity.  The serving engine
    # passes a per-REQUEST seed (crc32 of the qid): a cache-row index
    # would hand a freed-and-reused slot the SAME keys, so two
    # same-prompt requests through one slot (a GRPO group member
    # landing where a sibling just finished) would draw token-identical
    # trajectories and silently collapse group sample diversity
    positions: jax.Array,  # [B] absolute position of the SAMPLED token
    params: SamplingParams,
    ban_mask: jax.Array = None,
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """Position-keyed sampling: identity r's draw at position p depends
    only on ``(base_rng, r, p)`` — never on how many prior sampling
    calls the run happened to make.  This is what makes the serving
    engine's random stream invariant to chunk size / pipeline depth
    (the split-sequence hazard the engine docstring used to carry).
    Same distribution as
    :func:`sample_logits` (Gumbel-max over the identically filtered
    logits).

    Invariance caveat: the draws are exactly reproducible, but chunk
    layout still perturbs LOGITS at the float32 reduction-order level
    (~1e-7), so a stream can differ at a near-tie — essentially never
    under pure temperature sampling, but top-p/top-k cutoffs sit on
    sorted-probability cliffs where a tie can flip the filtered set.

    ``mesh`` (serving meshes only): the gumbel generation runs inside a
    fully-replicated manual ``shard_map`` region.  jax 0.4.x's legacy
    (non-partitionable) threefry can generate DIFFERENT bits when XLA's
    auto-partitioner shards the counter computation — measured on a
    4-chip d/e/m mesh, the same (key, shape) drew different tokens than
    the single-device engine, silently breaking sharded-vs-replicated
    stream parity.  Inside the manual region every device computes the
    full [B, V] gumbel locally with the exact single-device lowering,
    so the bits are bitwise-identical to ``mesh=None``."""
    if params.temperature != 1.0:
        logits = logits / max(params.temperature, 1e-5)
    base_logprobs = jax.nn.log_softmax(logits, axis=-1)
    filtered = _filtered_logits(logits, params, ban_mask)

    if params.greedy:
        tokens = jnp.argmax(filtered, axis=-1)
    else:
        V = logits.shape[-1]

        def row_gumbel(r, p):
            key = jax.random.fold_in(
                jax.random.fold_in(base_rng, r.astype(jnp.uint32)),
                p.astype(jnp.uint32),
            )
            return jax.random.gumbel(key, (V,), jnp.float32)

        def gen_gumbel(rows_, positions_):
            return jax.vmap(row_gumbel)(rows_, positions_)

        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            gen_gumbel = jax.shard_map(
                gen_gumbel,
                mesh=mesh,
                in_specs=(P(None), P(None)),
                out_specs=P(None, None),
                check_vma=False,
            )
        g = gen_gumbel(rows, positions)  # [B, V]
        tokens = jnp.argmax(filtered + g, axis=-1)

    logp = jnp.take_along_axis(base_logprobs, tokens[:, None], axis=-1)[:, 0]
    return tokens.astype(jnp.int32), logp


def call_sample_fn(sample_fn, logits, rng, positions, row_seeds=None):
    """Invoke a decode-loop sampling callback with whichever contract it
    declares: the legacy 2-arg ``(logits, rng)``, the position-aware
    3-arg ``(logits, rng, positions)``, or the fully keyed 4-arg
    ``(logits, rng, positions, row_seeds)`` (``positions`` [B] = the
    absolute position each row's sampled token will occupy;
    ``row_seeds`` [B] = the per-request key identity).  Resolved at
    trace time (``sample_fn`` is a static jit argument), so existing
    2-arg callers — bench loops, profiling scripts, tests — keep
    working unchanged while the engine opts into position-keyed
    streams."""
    try:
        n = len(inspect.signature(sample_fn).parameters)
    except (TypeError, ValueError):
        n = 2
    if n >= 4:
        return sample_fn(logits, rng, positions, row_seeds)
    if n == 3:
        return sample_fn(logits, rng, positions)
    return sample_fn(logits, rng)


@region("areal.sample")
def sample_and_advance(
    sample_fn,
    stop_fn,
    logits: jax.Array,  # [B, V] of the step's pending tokens
    rng: jax.Array,
    i,  # the step's number in its chunk
    lengths: jax.Array,  # [B] positions cached before this step
    active: jax.Array,  # [B] bool
    budgets: jax.Array,  # [B] tokens a row may still emit
    out_t: jax.Array,  # [B, W] the chunk's tokens so far
    out_l: jax.Array,  # [B, W] their log-probabilities
    emitted: jax.Array,  # [B, W] bool
    max_len: int,
    row_seeds=None,
):
    """The end of one decode step, the same in every decode program:
    sample each live row's next token (position-aware samplers get the
    position it will occupy, ``lengths + 1``), record it in column ``i``
    of the chunk's outputs, and retire the rows that stopped, ran out of
    budget or reached ``max_len``.  Returns ``(lengths, tokens, active,
    budgets, out_t, out_l, emitted, rng)``."""
    rng, sub = jax.random.split(rng)
    tok, logp = call_sample_fn(
        sample_fn, logits.astype(jnp.float32), sub, lengths + 1, row_seeds
    )
    tok = jnp.where(active, tok, 0)
    out_t = out_t.at[:, i].set(tok)
    out_l = out_l.at[:, i].set(jnp.where(active, logp, 0.0))
    emitted = emitted.at[:, i].set(active)
    lengths = lengths + active.astype(jnp.int32)
    budgets = budgets - active.astype(jnp.int32)
    active = active & ~stop_fn(tok) & (budgets > 0) & (lengths < max_len)
    return lengths, tok, active, budgets, out_t, out_l, emitted, rng
