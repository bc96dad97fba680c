"""Batched autoregressive generation on the model mesh.

Rebuild of the reference's in-house generation engine
(reference: realhf/impl/model/nn/real_llm_generate.py — ``genstep`` :30,
``generate`` :256 with CUDA-graphed decode :218).  On TPU the whole decode
loop runs device-side as a ``lax.while_loop`` inside one jit (the XLA
equivalent of CUDA-graph capture: no host round-trip per token), with early
exit when every row finishes.

This static-batch path serves sync-PPO's ``actor_gen`` MFC; the continuous
batching server for async rollout builds on the same prefill/decode steps
(areal_tpu/engine/inference_server.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api import model_api
from areal_tpu.api.data import SequenceSample
from areal_tpu.base import jax_compat, logging_
from areal_tpu.engine.batching import bucket_len
from areal_tpu.engine.sampling import SamplingParams, sample_logits_keyed
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import KVCache, decode_step, prefill

logger = logging_.getLogger("generation")


@dataclasses.dataclass
class GenState:
    cache: KVCache
    cur_tokens: jax.Array  # [B]
    active: jax.Array  # [B] bool
    out_tokens: jax.Array  # [B, max_new]
    out_logps: jax.Array  # [B, max_new]
    n_generated: jax.Array  # [B]
    step: jax.Array  # scalar
    rng: jax.Array


jax.tree_util.register_dataclass(
    GenState,
    data_fields=[
        "cache",
        "cur_tokens",
        "active",
        "out_tokens",
        "out_logps",
        "n_generated",
        "step",
        "rng",
    ],
    meta_fields=[],
)


@partial(
    jax.jit,
    static_argnames=(
        "cfg",
        "max_new_tokens",
        "min_new_tokens",
        "stop_tokens",
        "sampling",
        "cache_len",
    ),
)
def generate_loop(
    params,
    cfg: TransformerConfig,
    prompt_tokens: jax.Array,  # [B, T] right-padded
    prompt_lens: jax.Array,  # [B]
    rng: jax.Array,
    max_new_tokens: int,
    min_new_tokens: int,
    stop_tokens: Tuple[int, ...],
    sampling: SamplingParams,
    cache_len: int,
):
    """Prefill + device-side decode loop.  Returns (out_tokens [B, max_new],
    out_logps, n_generated [B], no_eos [B])."""
    B, T = prompt_tokens.shape
    cache = KVCache.zeros(cfg, B, cache_len, dtype=jnp.dtype(cfg.dtype))
    positions = jnp.tile(jnp.arange(T, dtype=jnp.int32), (B, 1))
    seg_ids = (
        positions < prompt_lens[:, None]
    ).astype(jnp.int32)
    logits, cache = prefill(
        params, cfg, prompt_tokens, positions, seg_ids, cache
    )
    last_idx = jnp.maximum(prompt_lens - 1, 0)
    last_logits = jnp.take_along_axis(
        logits, last_idx[:, None, None], axis=1
    )[:, 0]  # [B, V]

    def is_stop(tok):
        stop = jnp.zeros_like(tok, dtype=bool)
        for s in stop_tokens:
            stop |= tok == s
        return stop

    def stop_ban_mask(n_prev):
        """[B, V] True where stop tokens are banned from *sampling* (not from
        the reported logprob) until min_new_tokens are generated (reference:
        genstep's min-length logit ban, real_llm_generate.py:30)."""
        if min_new_tokens <= 0 or not stop_tokens:
            return None
        allow = (n_prev + 1 >= min_new_tokens)[:, None]  # [B,1]
        banned = np.zeros((cfg.vocab_size,), bool)
        for s in stop_tokens:
            banned[s] = True
        return ~allow & jnp.asarray(banned)[None, :]

    # sampling is keyed on (row, absolute position of the sampled token):
    # the random stream is a pure function of (rng, row, position), never
    # of how many sampling calls preceded it — the same contract as the
    # serving engine's, so chunking cannot perturb streams
    rows = jnp.arange(B, dtype=jnp.int32)
    n_prev0 = jnp.zeros((B,), jnp.int32)
    first_tok, first_logp = sample_logits_keyed(
        last_logits, rng, rows, prompt_lens, sampling,
        ban_mask=stop_ban_mask(n_prev0),
    )

    out_tokens = jnp.zeros((B, max_new_tokens), jnp.int32)
    out_logps = jnp.zeros((B, max_new_tokens), jnp.float32)
    out_tokens = out_tokens.at[:, 0].set(first_tok)
    out_logps = out_logps.at[:, 0].set(first_logp)
    n_gen0 = jnp.ones((B,), jnp.int32)
    active0 = ~is_stop(first_tok)
    # empty rows (batch padding) are never active — otherwise the early exit
    # below would never fire
    active0 &= prompt_lens > 0
    # capacity guard: the next decode step writes the current token's KV at
    # slot ``lengths``, so continuing requires lengths < cache_len
    active0 &= cache.lengths < cache_len

    state = GenState(
        cache=cache,
        cur_tokens=first_tok,
        active=active0,
        out_tokens=out_tokens,
        out_logps=out_logps,
        n_generated=n_gen0,
        step=jnp.asarray(1, jnp.int32),
        rng=rng,
    )

    def cond(s: GenState):
        return (s.step < max_new_tokens) & jnp.any(s.active)

    def body(s: GenState) -> GenState:
        logits, cache = decode_step(
            params, cfg, s.cur_tokens, s.cache, active=s.active
        )
        rng = s.rng
        # post-step cache.lengths IS the sampled token's absolute position
        tok, logp = sample_logits_keyed(
            logits.astype(jnp.float32),
            rng,
            rows,
            cache.lengths,
            sampling,
            ban_mask=stop_ban_mask(s.n_generated),
        )
        tok = jnp.where(s.active, tok, 0)
        n_gen = s.n_generated + s.active.astype(jnp.int32)
        out_tokens = s.out_tokens.at[:, s.step].set(tok)
        out_logps = s.out_logps.at[:, s.step].set(
            jnp.where(s.active, logp, 0.0)
        )
        active = s.active & ~is_stop(tok)
        active &= cache.lengths < cache_len
        return GenState(
            cache=cache,
            cur_tokens=tok,
            active=active,
            out_tokens=out_tokens,
            out_logps=out_logps,
            n_generated=n_gen,
            step=s.step + 1,
            rng=rng,
        )

    final = jax.lax.while_loop(cond, body, state)
    no_eos = final.active  # still active == ran out of budget
    return final.out_tokens, final.out_logps, final.n_generated, no_eos


def generate_tokens(
    params,
    cfg: TransformerConfig,
    prompts: Sequence[Sequence[int]],
    gconfig: model_api.GenerationHyperparameters,
    eos_token_id: Optional[int],
    rng: jax.Array,
    pad_rows_to: int = 1,
) -> List[Dict]:
    """Host wrapper: group-expand prompts (gconfig.n), bucket shapes, run the
    jitted loop, trim outputs.  Returns one dict per (prompt, group member):
    {output_ids, output_logprobs, no_eos}."""
    expanded: List[Sequence[int]] = []
    for p in prompts:
        expanded.extend([p] * gconfig.n)
    B = len(expanded)
    Bp = ((B + pad_rows_to - 1) // pad_rows_to) * pad_rows_to
    T = bucket_len(max(len(p) for p in expanded))
    toks = np.zeros((Bp, T), np.int32)
    lens = np.zeros((Bp,), np.int32)
    for i, p in enumerate(expanded):
        toks[i, : len(p)] = p
        lens[i] = len(p)

    stop = tuple(
        sorted(
            set(
                ([] if eos_token_id is None else [eos_token_id])
                + list(gconfig.stop_token_ids)
            )
        )
    )
    sampling = SamplingParams(
        temperature=gconfig.temperature,
        top_p=gconfig.top_p,
        top_k=(gconfig.top_k if gconfig.top_k < cfg.vocab_size else 0),
        greedy=gconfig.greedy,
    )
    max_new = gconfig.max_new_tokens
    cache_len = bucket_len(T + max_new)
    out_tokens, out_logps, n_gen, no_eos = generate_loop(
        params,
        cfg,
        jnp.asarray(toks),
        jnp.asarray(lens),
        rng,
        max_new_tokens=max_new,
        min_new_tokens=gconfig.min_new_tokens,
        stop_tokens=stop,
        sampling=sampling,
        cache_len=cache_len,
    )
    # start all four device->host copies before the first blocking
    # conversion: sequential np.asarray calls would each pay a full
    # PCIe round-trip, serialized
    jax_compat.start_host_copies((out_tokens, out_logps, n_gen, no_eos))
    out_tokens = np.asarray(out_tokens)
    out_logps = np.asarray(out_logps)
    n_gen = np.asarray(n_gen)
    no_eos = np.asarray(no_eos)
    results = []
    for i in range(B):
        n = int(n_gen[i])
        results.append(
            dict(
                output_ids=out_tokens[i, :n].tolist(),
                output_logprobs=out_logps[i, :n].tolist(),
                no_eos=bool(no_eos[i]),
            )
        )
    return results


def generate_for_sample(
    model: model_api.Model,
    data: SequenceSample,
    gconfig: model_api.GenerationHyperparameters,
) -> SequenceSample:
    """sync-PPO ``actor_gen``: prompts in, PPO training keys out
    (reference: PPOActorInterface.generate building the packed output sample,
    realhf/impl/model/interface/ppo_interface.py:301)."""
    engine = model.engine
    prompt_lens = [l[0] for l in data.seqlens["packed_prompts"]]
    offsets = np.concatenate([[0], np.cumsum(prompt_lens)])
    prompts = [
        data.data["packed_prompts"][offsets[i] : offsets[i + 1]].tolist()
        for i in range(data.bs)
    ]
    eos = model.tokenizer.eos_token_id if model.tokenizer else None
    rng = jax.random.PRNGKey(
        (model.version.global_step * 2654435761) % (2**31)
    )
    results = generate_tokens(
        engine.params,
        engine.model_cfg,
        prompts,
        gconfig,
        eos,
        rng,
        pad_rows_to=engine.dp_size,
    )

    seqs, logps, prompt_mask, no_eos, seqlens = [], [], [], [], []
    ids = []
    for i in range(data.bs):
        for j in range(gconfig.n):
            r = results[i * gconfig.n + j]
            p = prompts[i]
            seq = list(p) + r["output_ids"]
            seqs.append(np.array(seq, np.int32))
            lp = [0.0] * (len(p) - 1) + r["output_logprobs"]
            logps.append(np.array(lp, np.float32))
            pm = np.zeros(len(seq), bool)
            pm[: len(p)] = True
            prompt_mask.append(pm)
            no_eos.append(r["no_eos"])
            seqlens.append(len(seq))
            ids.append(f"{data.ids[i]}-{j}" if gconfig.n > 1 else data.ids[i])

    return SequenceSample.from_default(
        seqlens,
        ids,
        {
            "packed_input_ids": np.concatenate(seqs),
            "packed_logprobs": np.concatenate(logps),
            "prompt_mask": np.concatenate(prompt_mask),
            "seq_no_eos_mask": np.array(no_eos, np.float32),
        },
    )
