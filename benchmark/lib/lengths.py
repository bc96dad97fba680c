"""The one general traffic generator: requests and train batches from the
numbers in a traffic file and ``--seed``.

Lengths come from the traffic file's ``length_seed``: every run of a cell
holds the same work in the same order, whatever ``--seed`` is.  (A closed
loop's window holds some twenty prompt groups; over draws, the rows alive
per slot, which the rate follows, spread by 6%, more than the changes the
bounds have to catch: PERF.md, section 6.  Another draw is another traffic
file.)  Token ids, weights, log-probabilities and rewards come from
``--seed``.
"""

from __future__ import annotations

import math

import numpy as np


def _rng(*key) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(k) for k in key]))


def _lognormal_len(rng, spec: dict, size=None):
    """Log-normal lengths with the given median and sigma, clipped."""
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _uniform_len(rng, spec: dict, size=None):
    return rng.integers(spec["min"], spec["max"] + 1, size)


def rollout_prompt(traffic: dict, seed: int, vocab_size: int, k: int) -> dict:
    """The k-th prompt of a rollout mix: its token ids and the number of new
    tokens each of its samples asks for."""
    lr = _rng(traffic["length_seed"], 1, k)
    plen = int(_uniform_len(lr, traffic["prompt_len"]))
    new = _lognormal_len(lr, traffic["output_len"], traffic["samples_per_prompt"])
    ids = _rng(seed, 2, k).integers(3, vocab_size, plen)
    return {
        "k": k,
        "prompt_ids": ids.tolist(),
        "max_new_tokens": [int(n) for n in new],
    }


def train_batch(traffic: dict, seed: int, vocab_size: int, k: int) -> dict:
    """The k-th packed PPO batch of a train mix: whole sequences (prompt +
    output) until ``tokens_per_step`` real tokens, the last one cut to fit.
    Returns flat arrays in the program's packed layout: per-token keys have
    sum(seqlens) entries, per-transition keys sum(seqlens - 1), per-sequence
    keys one each."""
    budget = int(traffic["tokens_per_step"])
    lr = _rng(traffic["length_seed"], 3, k)
    plens, seqlens = [], []
    while sum(seqlens) < budget:
        p = int(_uniform_len(lr, traffic["prompt_len"]))
        n = int(_lognormal_len(lr, traffic["output_len"]))
        room = budget - sum(seqlens)
        if p + n > room:
            if room < p + traffic["output_len"]["min"]:
                # too little room for a whole prompt and a shortest output:
                # give the remainder to the previous sequence's output
                seqlens[-1] += room
                break
            n = room - p
        plens.append(p)
        seqlens.append(p + n)
    total = sum(seqlens)
    assert total == budget, (total, budget)
    r = _rng(seed, 4, k)
    n_trans = total - len(seqlens)
    prompt_mask = np.zeros(total, bool)
    off = 0
    for p, s in zip(plens, seqlens):
        prompt_mask[off : off + p] = True
        off += s
    uniform_logp = -math.log(vocab_size)
    return {
        "seqlens": seqlens,
        "prompt_lens": plens,
        "packed_input_ids": r.integers(3, vocab_size, total).astype(np.int64),
        "prompt_mask": prompt_mask,
        # a random model is near uniform over the vocabulary: behaviour and
        # proximal log-probabilities sit around -ln(V), so importance
        # ratios are of order 1 and some are clipped, as in a real step
        "packed_logprobs": (
            uniform_logp + traffic["logprob_noise"] * r.standard_normal(n_trans)
        ).astype(np.float32),
        "prox_logp": (
            uniform_logp + traffic["logprob_noise"] * r.standard_normal(n_trans)
        ).astype(np.float32),
        "rewards": (r.random(len(seqlens)) < traffic["reward_rate"]).astype(
            np.float32
        ),
        "seq_no_eos_mask": np.zeros(len(seqlens), np.float32),
    }
