"""Driver ``train_steps``: the trainer alone.  Each step is one PPO actor
``train_step`` as the model worker calls it (``ppo_actor`` interface on a
``train`` backend: advantages, a split into ``n_minibatches``, and per
minibatch a packed, grad-accumulated, fused optimizer step), on batches
made from the seed before the window opens.  Packing is inside the step,
where a deployment pays it.

The weights are made ON THE DEVICE in one jitted call from the seed (the
trainer takes a tree; float32 masters, as it trains them).
"""

from __future__ import annotations

import gc
import json
import math
import time

import numpy as np

from benchmark.lib import lengths, reference
from benchmark.lib.program import model_config, point_roots_at

PER_TOKEN = ("packed_input_ids", "prompt_mask")
PER_TRANSITION = ("packed_logprobs", "prox_logp")
PER_SEQUENCE = ("rewards", "seq_no_eos_mask")


class Driver:
    def __init__(self, ctx):
        import jax

        import areal_tpu.engine.backend  # noqa: F401 - registers "train"
        import areal_tpu.interfaces.ppo_interface  # noqa: F401 - "ppo_actor"
        from areal_tpu.api import model_api
        from areal_tpu.api.config import (
            ModelBackendAbstraction,
            ModelInterfaceAbstraction,
            ModelName,
        )
        from areal_tpu.api.data import MicroBatchSpec
        from areal_tpu.base.topology import MeshSpec
        from areal_tpu.engine.optimizer import OptimizerConfig
        from areal_tpu.models import transformer

        self.ctx, self.jax = ctx, jax
        self.traffic = t = ctx.traffic
        self.hf = ctx.config["hf_config"]
        self.n_layers = ctx.config["roles"]["train"]["num_hidden_layers"]
        point_roots_at(ctx.work_dir)
        self.cfg = model_config(ctx.config, "train")
        self._transformer = transformer
        self._warned_before = set(transformer._warned_dense)
        t0 = time.perf_counter()
        params = self._init_params()
        mesh = MeshSpec().make_mesh(jax.devices()[:1])
        model = model_api.Model(
            name=ModelName("actor"), engine=None, tokenizer=None, mesh=mesh,
            backend_name="llama",
        )
        model.model_cfg, model.init_params = self.cfg, params
        backend = model_api.make_backend(
            ModelBackendAbstraction(
                "train", {"optimizer": OptimizerConfig(**t["optimizer"])}
            )
        )
        self.model = backend.initialize(
            model, model_api.FinetuneSpec(1, 10**6, 1)
        )
        del params, model
        self.iface = model_api.make_interface(
            ModelInterfaceAbstraction("ppo_actor", dict(t["interface"]))
        )
        self.mb_spec = MicroBatchSpec(max_tokens_per_mb=t["max_tokens_per_mb"])
        self.batches = [
            lengths.train_batch(t, ctx.seed, self.hf["vocab_size"], k)
            for k in range(t["distinct_batches"])
        ]
        self.first_stats = None
        self.steps = []  # per window step: dict
        print(
            json.dumps(
                {
                    "event": "trainer_ready",
                    "seconds": time.perf_counter() - t0,
                    "layers": self.n_layers,
                }
            ),
            flush=True,
        )

    def _init_params(self):
        """The whole float32 tree in ONE jitted call on the device."""
        jax = self.jax
        from areal_tpu.models.transformer import init_params

        key = jax.random.PRNGKey(self.ctx.seed % (2**31 - 1))
        return jax.jit(lambda k: init_params(self.cfg, k))(key)

    def _sample(self, batch: dict):
        from areal_tpu.api.data import SequenceSample

        keys = PER_TOKEN + PER_TRANSITION + PER_SEQUENCE
        return SequenceSample.from_default(
            batch["seqlens"],
            [f"s{i}" for i in range(len(batch["seqlens"]))],
            {k: batch[k] for k in keys},
        )

    def _step(self, batch: dict) -> dict:
        jax = self.jax
        with jax.profiler.TraceAnnotation("bench.make_sample"):
            sample = self._sample(batch)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            stats = self.iface.train_step(self.model, sample, self.mb_spec)
        with jax.profiler.TraceAnnotation("bench.block"):
            jax.block_until_ready(self.model.engine.params)
        return {
            "loss": float(stats["loss"]),
            "grad_norm": float(stats["grad_norm"]),
            "pad_frac_last_minibatch": float(self.model.engine.last_padding_frac),
        }

    def warm(self):
        """One step on each distinct batch: every shape the window uses."""
        for k, b in enumerate(self.batches):
            tik = time.perf_counter()
            s = self._step(b)
            if self.first_stats is None:
                self.first_stats = s
            print(
                json.dumps(
                    {
                        "event": "warm_step", "batch": k,
                        "seconds": time.perf_counter() - tik,
                        "loss": s["loss"], "grad_norm": s["grad_norm"],
                    }
                ),
                flush=True,
            )

    def measure(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        k = 0
        while time.perf_counter() - t0 < seconds:
            b = self.batches[k % len(self.batches)]
            tik = time.perf_counter()
            s = self._step(b)
            s.update(seconds=time.perf_counter() - tik, batch=k % len(self.batches))
            self.steps.append(s)
            k += 1
        window_s = time.perf_counter() - t0
        tokens = sum(
            sum(self.batches[s["batch"]]["seqlens"]) for s in self.steps
        )
        flops = sum(
            self._train_flops(self.batches[s["batch"]]) for s in self.steps
        )
        bad = sum(not math.isfinite(s["loss"]) for s in self.steps)
        step_s = sorted(s["seconds"] for s in self.steps)
        return {
            "attempted": len(self.steps),
            "failed": bad,
            "end_to_end": {"train_tok_per_s": tokens / window_s},
            "counters": {
                "window_s": window_s,
                "steps": len(self.steps),
                "real_tokens": tokens,
                "train_flops": flops,
                "pad_frac_mean": float(
                    np.mean([s["pad_frac_last_minibatch"] for s in self.steps])
                ),
                "n_layers": self.n_layers,
            },
            "gap_owner": "host",
            "notes": {
                "steps": len(self.steps),
                "window_s": window_s,
                "step_seconds_median": step_s[len(step_s) // 2],
                "step_seconds_max": step_s[-1],
            },
        }

    def _train_flops(self, batch: dict) -> int:
        from benchmark.lib import flops

        return flops.train_flops(self.hf, self.n_layers, batch["seqlens"])

    # -- correctness, outside the window -----------------------------------

    def reference_first_loss(self) -> float:
        """The first batch's PPO loss by the plain reference, on the weights
        the seed gives (made again: the trainer has moved its own)."""
        it = self.traffic["interface"]
        b = self.batches[0]
        params = self._init_params()
        fn = reference.make_token_logps(self.hf)
        score = np.clip(
            b["rewards"] * it["reward_scaling"] - it["reward_bias"],
            -it["max_reward_clip"], it["max_reward_clip"],
        )
        new, adv, mask = [], [], []
        tok = 0
        for s, p, sc in zip(b["seqlens"], b["prompt_lens"], score):
            seq = b["packed_input_ids"][tok : tok + s]
            tok += s
            new.append(reference.sequence_logps(fn, params, seq) / it["temperature"])
            # no critic, no KL, discount 1: every response transition's
            # advantage is its sequence's score
            adv.append(np.full(s - 1, sc))
            mask.append(np.arange(s - 1) >= p - 1)
        return reference.ppo_actor_loss(
            np.concatenate(new), b["packed_logprobs"], b["prox_logp"],
            np.concatenate(adv), np.concatenate(mask),
            it["eps_clip"], it.get("behav_imp_weight_cap"),
        )

    def check(self):
        new_warnings = sorted(
            str(k) for k in self._transformer._warned_dense - self._warned_before
        )
        losses = [s["loss"] for s in self.steps]
        grads = [s["grad_norm"] for s in self.steps]
        first = self.first_stats["loss"]
        # free the trainer before the reference makes its own tree
        self.model.engine = None
        self.model = None
        gc.collect()
        ref = self.reference_first_loss()
        tol = self.traffic["first_loss_abs_tolerance"]
        details = {
            "first_step_loss": first,
            "reference_loss": ref,
            "abs_diff": abs(first - ref),
            "tolerance_abs": tol,
            "tolerance_why": (
                "the trainer computes in bf16 over fp32 masters, the reference "
                "in fp32: log-probabilities differ by ~0.002 (PR 21), so "
                "ratios by ~0.2% and a loss of order 0.3 by ~0.001; a wrong "
                "mask, packing or advantage moves it by 0.05 and more.  The "
                "step's loss is the token-weighted mean over 4 minibatches, "
                "the later ones after updates at lr 1e-6, which move it by "
                "less than 1e-5"
            ),
            "loss_min": min(losses) if losses else None,
            "loss_max": max(losses) if losses else None,
            "grad_norm_min": min(grads) if grads else None,
            "dense_attention_fallbacks": new_warnings,
        }
        ok = (
            bool(losses)
            and all(math.isfinite(x) for x in losses)
            and all(g > 0 for g in grads)
            and self.first_stats["grad_norm"] > 0
            and abs(first - ref) <= tol
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and not new_warnings  # the flash kernel, not dense
        return bool(ok), details

    def close(self):
        self.model = None
        gc.collect()


def build(ctx) -> Driver:
    return Driver(ctx)
