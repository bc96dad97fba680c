#!/usr/bin/env python3
"""What each planted fault of ``benchmark/lib/reference_laguna.py`` does to
the PPO gradient at ``laguna-xs.2``'s published widths, against the limits of
``benchmark/traffic/train-long-expert.json``:

    chiprun --chips 1 --timeout 1500 -- python3 scripts/laguna_faults.py [--seed N] [--sequences 2]

The plain reference's gradient over the first sequences of the cell's first
batch (weights from ``--seed``, float32, "highest"), then the same with a
missing window mask, the gate left out, a dropped pair, rope on the wrong
half and the experts' weights on their input: one JSON line a fault, each
group's cosine and relative L2 distance to the plain gradient and whether the
cell's limits would refuse it.  A tool for ``PERF.md``; no test runs it."""

import argparse
import dataclasses
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from areal_tpu.models import hybrid  # noqa: E402
from benchmark.lib import lengths  # noqa: E402
from benchmark.lib import reference_laguna as ref  # noqa: E402
from benchmark.lib.program import model_config  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=2147483801)
    p.add_argument("--sequences", type=int, default=2)
    args = p.parse_args()
    with open(os.path.join(ROOT, "benchmark/configs/laguna-xs.2.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic/train-long-expert.json")) as f:
        t = json.load(f)
    hf, it = config["hf_config"], t["interface"]
    cfg = model_config(config, "train")
    params = hybrid.init_params(
        dataclasses.replace(cfg, dtype="float32"),
        jax.random.PRNGKey(args.seed % (2**31 - 1)),
    )
    b = lengths.train_batch(t, args.seed, cfg.vocab_size, 0)
    # every sequence rewarded: a zero advantage has no gradient
    seqs = [
        {k: v for k, v in s.items() if k != "len"}
        for s in ref.ppo_sequences(
            b, range(args.sequences), it, t["reference_pad_to"],
            advantage=lambda score: abs(score) + 0.5,
        ).values()
    ]
    count = float(sum(s["mask"].sum() for s in seqs))

    def gradient(wrong):
        fn = ref.make_loss_and_grad(hf, it, cfg.moe_first_expert, wrong=wrong)
        acc, total = jax.tree.map(jnp.zeros_like, params), 0.0
        with jax.default_matmul_precision("highest"):
            for s in seqs:
                acc, loss_sum, _, _ = fn(acc, params, s)
                total += float(loss_sum)
        return jax.device_get(acc), total / count

    @jax.jit
    def sums(a, b):
        return jnp.stack(
            [jnp.sum(a * b), jnp.sum(a * a), jnp.sum(b * b), jnp.sum(jnp.square(a - b))]
        )

    want, want_loss = gradient(None)
    for wrong in [w for w in ref.WRONG if w]:
        got, loss = gradient(wrong)
        out, refused = {}, False
        for group in ref.GROUPS:
            tot = np.zeros(4, np.float64)
            for a, c in zip(ref.group_leaves(got, group), ref.group_leaves(want, group)):
                tot += np.asarray(sums(a, c), np.float64)
            ab, aa, bb, dd = tot
            cos = ab / max(math.sqrt(aa * bb), 1e-30)
            rel = math.sqrt(dd / max(bb, 1e-30))
            norm = math.sqrt(aa / max(bb, 1e-30))
            out[group] = {"cosine": cos, "rel_l2": rel, "norm_ratio": norm}
            refused |= (
                cos < t["grad_cosine_min"] or rel > t["grad_rel_l2_max"]
                or abs(norm - 1.0) > t["grad_norm_rel_tolerance"]
            )
        print(
            json.dumps(
                {
                    "fault": wrong, "refused": bool(refused),
                    "loss": loss, "plain_loss": want_loss,
                    "loss_refuses": abs(loss - want_loss) > t["first_loss_abs_tolerance"],
                    "groups": out,
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
