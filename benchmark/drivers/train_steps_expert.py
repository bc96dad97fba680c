"""Driver ``train_steps_expert``: ``train_steps`` for a stack stated by
kind with held experts (``laguna``): the same PPO actor ``train_step``s on
a ``train`` backend, with

* float32 masters made on the device by ``hybrid.init_params`` (the
  configuration's dtype is the COMPUTE dtype), with the router's choice
  bias by :func:`choice_bias`: from the seed, and every seed's held
  experts under the same load;
* the step's counters read from the trainer's own records (``TrainEngine``'s
  record a batch: held pairs, the busiest expert's, extra rounds, block
  pairs by kind, the gradient's norm by group), and ``train_flops`` from
  ``lib/flops_laguna.py`` with the pairs the held experts actually took;
* ``correct`` on the weights the seed gives, against
  ``lib/reference_laguna.py``: (i) the first step's loss, (ii) the first
  MINIBATCH's gradient norms as the timed path's own record holds them,
  (iii) the gradient's DIRECTION by group, read out of the STEP PROGRAM
  itself: after the window the engine steps once more on the first
  minibatch from the seed's weights and a fresh optimizer state, and the
  first moment that step leaves is ``(1 - beta1)`` times the gradient it
  applied, (iv) the PARAMETERS' CHANGE over the next step on the same
  minibatch against the reference's own Adam step (a state left
  unchanged reads 1), (v) the router's CHOICE: the share of tokens whose
  routing lies further than a margin outside the reference's own top
  eight by its float32 choice scores.  Inside the margin the reference
  FOLLOWS the trainer's routing (the choice carries no gradient; two
  near-tied experts changing places is no fault of either side).
"""

from __future__ import annotations

import gc
import json
import math
import time

import numpy as np

from benchmark.drivers.train_steps import Driver as TrainDriver
from benchmark.lib import flops_laguna
from benchmark.lib import reference
from benchmark.lib import reference_laguna as ref

#: what each limit of the check is, and why it stands where it does
#: (my chip runs, PR 53; PERF.md section 6 has the readings)
TOLERANCES_WHY = {
    "first_loss_abs_tolerance": (
        "bf16 compute over fp32 masters against the float32 reference: a "
        "loss of order 0.4 read 1e-6 to 2e-5 apart on nine seeds (the "
        "precision hardly moves it: the reference with float8 weights "
        "read 2e-5), so the limit is the accepted train cell's 0.01; a "
        "wrong mask, packing or advantage moves it by 0.05 and more.  The "
        "step's loss is the token-weighted mean over 2 minibatches, the "
        "second after an update at lr 1e-6 (under 1e-5)"
    ),
    "grad_norm_rel_tolerance": (
        "a group's gradient norm as the timed step's OWN record holds it "
        "against the reference's: bf16 read within 0.1% on nine seeds "
        "(float8 weights 0.13%: this limit refuses faults, not a "
        "precision); a gate left out reads 3.3 times, a dropped pair "
        "0.52, weights on the input 0.29 of the norm"
    ),
    "grad_cosine_min / grad_rel_l2_max": (
        "each group's gradient AS THE STEP PROGRAM APPLIED IT (its first "
        "moment after one step from a fresh state, over 1 - beta1) against "
        "the reference's, element by element: the limits stand between "
        "what bf16 reads in its worst group and what the reference with "
        "float8 weights reads in its worst (PERF.md section 6 has both); "
        "each planted fault reads 0.57-2.7 in its worst group and over "
        "0.096 in its least moved"
    ),
    "param_change_rel_max": (
        "the parameters' change over ONE step of the engine's step program "
        "(the second on the first minibatch from the seed's weights: the "
        "schedule's first update has lr 0, so both see the same gradient "
        "and Adam's corrected moments are g and g^2) against the "
        "reference's own AdamW step -lr (g / (|g| + eps) + wd p) of ITS "
        "gradient, as |change - reference's| / |reference's| a group and "
        "in the worst leaf: an update left out reads exactly 1; the limit "
        "stands between the first readings and 1, nearer 1"
    ),
    "router_choice_margin / router_choice_outside_max": (
        "a token's gap: the best choice score (sigmoid + bias, the "
        "reference's float32) its routing left out less the worst it took, "
        "<= 0 where the routing IS the reference's top eight; bf16 moves a "
        "score by a few thousandths, so near-tied experts change places "
        "and the gap stays within the margin, where the reference follows "
        "the trainer; a router without the bias (uniform +-0.15) or with "
        "another k stands outside it on most tokens (the same gap by the "
        "scores without the bias is the control, and must read outside)"
    ),
}


class Driver(TrainDriver):
    def __init__(self, ctx):
        super().__init__(ctx)
        cfg = self.cfg
        self.vocab = cfg.vocab_size
        self.first_expert = cfg.moe_first_expert
        # token ids (and the uniform log-probability) over the SLICE of the
        # vocabulary this chip holds, not the published one
        from benchmark.lib import lengths

        self.batches = [
            lengths.train_batch(ctx.traffic, ctx.seed, self.vocab, k)
            for k in range(ctx.traffic["distinct_batches"])
        ]
        self.first_record = None  # the trainer's record of batch 1
        self._records_seen = 0

    def _init_params(self):
        """The whole tree in float32 (the trainer's masters), made on the
        device kind by kind, all of it from ``--seed``; the router's choice
        bias by :func:`choice_bias`, so that every seed holds the same
        load."""
        import dataclasses

        jax = self.jax
        from areal_tpu.models import hybrid

        cfg = self.cfg
        seed = self.ctx.seed % (2**31 - 1)
        params = hybrid.init_params(
            dataclasses.replace(cfg, dtype="float32"), jax.random.PRNGKey(seed)
        )
        router = params["layers"]["mlp"]["router"]
        router["bias"] = jax.numpy.asarray(
            choice_bias(
                seed, cfg.n_expert_layers, cfg.n_experts,
                cfg.moe_first_expert, cfg.n_held_experts,
            )
        )
        return params

    # -- the trainer's records ------------------------------------------------

    def _new_records(self):
        records = self.model.engine._phases.records()
        new = [r for r in records if r["batch"] > self._records_seen]
        if new:
            self._records_seen = new[-1]["batch"]
        return new

    def _step(self, batch: dict) -> dict:
        s = super()._step(batch)
        recs = self._new_records()
        if self.first_record is None:
            self.first_record = recs[0]
        for k in (
            "moe_held_pairs", "moe_busiest_pairs", "moe_extra_rounds",
            "attn_blocks_run", "attn_blocks_causal", "attn_window_blocks_run",
            "n_mbs", "padded_slots",
        ):
            s[k] = float(sum(r.get(k, 0.0) for r in recs))
        return s

    def warm(self):
        """One step on each distinct batch, then the first batch once
        more: the first call of a step program takes an optimizer state
        fresh from ``init`` (its count has another type than the one a step
        returns), so the program it compiled serves no later call, and the
        first minibatch's shape may be no other's."""
        super().warm()
        tik = time.perf_counter()
        s = self._step(self.batches[0])
        print(
            json.dumps(
                {
                    "event": "warm_step", "batch": 0, "again": True,
                    "seconds": time.perf_counter() - tik, "loss": s["loss"],
                }
            ),
            flush=True,
        )

    def _train_flops_of(self, step: dict) -> float:
        return flops_laguna.train_flops(
            self.hf, self.n_layers, self.batches[step["batch"]]["seqlens"],
            step["moe_held_pairs"], self.vocab,
        )

    def measure(self, seconds: float) -> dict:
        out = super().measure(seconds)
        steps = self.steps
        total = lambda k: float(sum(s[k] for s in steps))
        held = self.cfg.n_held_experts
        W = self.hf["sliding_window"]
        c = out["counters"]
        c.update(
            train_flops=float(sum(self._train_flops_of(s) for s in steps)),
            moe_held_pairs=total("moe_held_pairs"),
            moe_busiest_pairs=total("moe_busiest_pairs"),
            moe_extra_rounds=total("moe_extra_rounds"),
            held_experts=held,
            attn_blocks_run=total("attn_blocks_run"),
            attn_blocks_causal=total("attn_blocks_causal"),
            attn_window_blocks_run=total("attn_window_blocks_run"),
            microbatches=total("n_mbs"),
            window_pairs=float(
                sum(
                    flops_laguna.window_pairs(L, W)
                    for s in steps
                    for L in self.batches[s["batch"]]["seqlens"]
                )
            ),
            # the slots the layouts stacked, all micro-batches
            row_slots=total("padded_slots"),
        )
        pairs = max(c["moe_held_pairs"], 1.0)
        out["notes"].update(
            moe_load_max_over_mean=c["moe_busiest_pairs"] * held / pairs,
            moe_extra_rounds_a_step=c["moe_extra_rounds"] / max(len(steps), 1),
            attn_blocks_run_share=c["attn_blocks_run"]
            / max(c["attn_blocks_causal"], 1.0),
            attn_window_blocks_run_share=c["attn_window_blocks_run"]
            / max(c["attn_blocks_causal"], 1.0),
        )
        return out

    def _train_flops(self, batch: dict) -> int:
        return 0  # the parent's sum; measure() above counts with the pairs

    # -- correctness, outside the window -----------------------------------

    def _sequences(self, batch: dict, which):
        return ref.ppo_sequences(
            batch, which, self.traffic["interface"],
            int(self.traffic["reference_pad_to"]),
        )

    def _routing_of(self, out, stacked, seqs):
        """Each sequence's routing ``[expert layers, pad_to, K]`` out of
        the gradient program's per-micro-batch ids, by the layout's
        segment table (a segment is matched to its sequence by its
        tokens)."""
        routed = np.asarray(out["per_microbatch"]["routed_experts"])
        pad_to = int(self.traffic["reference_pad_to"])
        n_mbs, Le, _, _, K = routed.shape
        given = {}
        for m in range(n_mbs):
            for row, start, n in zip(
                stacked["seg_rows"][m], stacked["seg_starts"][m],
                stacked["seg_lens"][m],
            ):
                if n == 0:
                    continue
                toks = stacked["tokens"][m, row, start : start + n]
                (i,) = [
                    i for i, s in seqs.items()
                    if s["len"] == n and np.array_equal(s["tokens"][:n], toks)
                ]
                g = np.zeros((Le, pad_to, K), np.int32)
                g[:, :n] = routed[m, :, row, start : start + n]
                given[i] = g
        assert sorted(given) == sorted(seqs), (sorted(given), sorted(seqs))
        return given

    def _reference_gradient(self, fn, params, seqs, given):
        """``(gradient tree on the host, {i: loss_sum}, the choice's
        account over the real tokens)`` of the sequences ``seqs`` by ``fn``
        (``reference_laguna.make_loss_and_grad``), undivided."""
        jax = self.jax
        import jax.numpy as jnp

        acc = jax.tree.map(jnp.zeros_like, params)
        sums = {}
        about = {"flipped": [], "gap": [], "gap_no_bias": []}
        with jax.default_matmul_precision("highest"):
            for i, s in seqs.items():
                seq = {k: v for k, v in s.items() if k != "len"}
                seq["given"] = given[i]
                acc, loss_sum, _, one = fn(acc, params, seq)
                sums[i] = float(loss_sum)
                for k in about:
                    about[k].append(np.asarray(one[k])[: s["len"]])
        return (
            jax.device_get(acc), sums,
            {k: np.concatenate(v) for k, v in about.items()},
        )

    def _compare(self, got, want):
        """``{group: {cosine, rel_l2, norm, norm_reference}}`` of two host
        gradient trees, leaf by leaf on the device."""
        jax = self.jax
        import jax.numpy as jnp

        @jax.jit
        def sums(a, b):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            return jnp.stack(
                [jnp.sum(a * b), jnp.sum(a * a), jnp.sum(b * b),
                 jnp.sum(jnp.square(a - b))]
            )

        out = {}
        for group in ref.GROUPS:
            t = np.zeros(4, np.float64)
            for a, b in zip(ref.group_leaves(got, group), ref.group_leaves(want, group)):
                t += np.asarray(sums(a, b), np.float64)
            ab, aa, bb, dd = t
            out[group] = {
                "cosine": ab / max(math.sqrt(aa * bb), 1e-30),
                "rel_l2": math.sqrt(dd / max(bb, 1e-30)),
                "norm": math.sqrt(aa),
                "norm_reference": math.sqrt(bb),
            }
        return out

    def _adam(self):
        """The optimizer's numbers for the reference's own step: the
        traffic file's over ``OptimizerConfig``'s defaults, and the
        schedule's rate at update 1 (a linear warm-up from 0 over
        ``max(1, proportion x 10**6 steps)`` updates, then constant)."""
        from areal_tpu.engine.optimizer import OptimizerConfig

        o = OptimizerConfig(**self.traffic["optimizer"])
        assert o.type == "adam" and o.lr_scheduler_type == "constant", o
        warm = max(1, int(o.warmup_steps_proportion * 10**6))
        return o, o.lr * min(1.0, 1.0 / warm)

    def _param_change(self, after, params, want, grad_norm):
        """``{group: |change - reference's| / |reference's|}`` and the worst
        leaf's: ``after`` (host) less ``params`` (the seed's, on the device)
        against ``-lr (u / (|u| + eps) + wd p)`` with ``u`` the reference's
        gradient ``want`` (host) clipped at its own global norm."""
        jax = self.jax
        import jax.numpy as jnp

        o, lr = self._adam()
        scale = 1.0
        if o.gradient_clipping:
            scale = min(1.0, o.gradient_clipping / max(grad_norm, 1e-30))

        @jax.jit
        def sums(new, p, g):
            u = g.astype(jnp.float32) * scale
            want_d = -lr * (u / (jnp.abs(u) + o.eps) + o.weight_decay * p)
            d = new - p
            return jnp.stack([jnp.sum(want_d**2), jnp.sum((d - want_d) ** 2)])

        out, worst = {}, 0.0
        for group in ref.GROUPS:
            t = np.zeros(2, np.float64)
            for new, p, g in zip(
                *(ref.group_leaves(tree, group) for tree in (after, params, want))
            ):
                one = np.asarray(sums(new, p, g), np.float64)
                worst = max(worst, math.sqrt(one[1] / max(one[0], 1e-60)))
                t += one
            out[group] = math.sqrt(t[1] / max(t[0], 1e-60))
        return out, worst, lr

    def check(self):
        jax = self.jax
        from areal_tpu.api.data import MicroBatchSpec

        t, it = self.traffic, self.traffic["interface"]
        new_warnings = sorted(
            str(k) for k in self._transformer._warned_dense - self._warned_before
        )
        losses = [s["loss"] for s in self.steps]
        grads = [s["grad_norm"] for s in self.steps]
        first = self.first_stats["loss"]
        record = self.first_record
        # -- at the seed's weights, on the minibatch the first train_batch
        # took; the trainer's own weights and moments go first
        engine = self.model.engine
        engine.params = engine.opt_state = None
        gc.collect()
        tik = time.perf_counter()
        params = self._init_params()
        b = self.batches[0]
        sample = self._sample(b)
        self.iface._prepare_batch(sample)
        mbs, *_ = sample.split(MicroBatchSpec(n_mbs=it["n_minibatches"]))
        first_ids = [int(i[1:]) for i in mbs[0].ids]
        # the ROUTING the engine's micro-batch function took (the step
        # program drops it); its gradient is not read
        _, out, stacked = engine.grad_batch(
            mbs[0], self.iface._loss_fn, self.mb_spec, params=params
        )
        count = float(out["denom"])
        trainer_loss_mb0 = float(out["loss_sum"]) / count
        # the STEP PROGRAM, twice on that minibatch from a fresh state:
        # the first update (lr 0 by the schedule) leaves the gradient it
        # applied in its first moment, the second moves the parameters
        adam, _ = self._adam()
        engine.params, engine.opt_state = params, jax.jit(engine.tx.init)(params)
        del params
        engine.train_batch(mbs[0], self.iface._loss_fn, self.mb_spec)
        got = jax.tree.map(
            lambda m: np.asarray(m) / (1.0 - adam.beta1),
            jax.device_get(_adam_state(engine.opt_state).mu),
        )
        engine.train_batch(mbs[0], self.iface._loss_fn, self.mb_spec)
        after = jax.device_get(engine.params)
        self.model.engine = engine = None
        self.model = None
        gc.collect()
        seconds = {"trainer_steps": time.perf_counter() - tik}
        # -- the reference: gradient of the first minibatch, loss of the
        # whole first batch
        tik = time.perf_counter()
        params = self._init_params()
        seqs = self._sequences(b, first_ids)
        given = self._routing_of(out, stacked, seqs)
        margin = t["router_choice_margin"]
        fn = ref.make_loss_and_grad(self.hf, it, self.first_expert, margin=margin)
        want, sums, about = self._reference_gradient(fn, params, seqs, given)
        want = jax.tree.map(lambda g: g / count, want)
        rest = self._sequences(
            b, [i for i in range(len(b["seqlens"])) if i not in first_ids]
        )
        logps = ref.make_token_logps(self.hf, self.first_expert)
        rest_sum, rest_count = 0.0, 0
        with jax.default_matmul_precision("highest"):
            for s in rest.values():
                lp, _ = logps(params, s["tokens"], None)
                n = s["len"] - 1
                rest_count += int(s["mask"][:n].sum())
                rest_sum += sequence_loss_sum(
                    np.asarray(lp)[:n], s, n, it
                )
        ref_loss = (sum(sums.values()) + rest_sum) / (count + rest_count)
        seconds["reference"] = time.perf_counter() - tik
        by_group = self._compare(got, want)
        del got
        ref_norms = {g: v["norm_reference"] for g, v in by_group.items()}
        ref_global = math.sqrt(
            sum(
                float(np.sum(np.square(np.asarray(x, np.float64))))
                for x in jax.tree.leaves(want)
            )
        )
        change, change_worst, lr = self._param_change(after, params, want, ref_global)
        del after
        # -- the control: the same reference with every matrix in float8,
        # which has to fall outside a limit
        tik = time.perf_counter()
        low_params = ref.float8_weights(params)
        del params
        low, low_sums, _ = self._reference_gradient(fn, low_params, seqs, given)
        low = jax.tree.map(lambda g: g / count, low)
        del low_params
        control = self._compare(low, want)
        low_loss_mb0 = sum(low_sums.values()) / count
        seconds["control"] = time.perf_counter() - tik

        norm_tol = t["grad_norm_rel_tolerance"]
        step_norms = dict(record["grad_norms"])
        norms_ok = abs(record["grad_norm"] / ref_global - 1.0) <= norm_tol and all(
            abs(step_norms[g] / max(ref_norms[g], 1e-30) - 1.0) <= norm_tol
            for g in ref.GROUPS
        )

        def within(cmp):
            return all(
                v["cosine"] >= t["grad_cosine_min"]
                and v["rel_l2"] <= t["grad_rel_l2_max"]
                for v in cmp.values()
            )

        outside = lambda gap: float(np.mean(gap > margin))
        choice_max = t["router_choice_outside_max"]
        change_max = t["param_change_rel_max"]
        change_ok = change_worst <= change_max and all(
            v <= change_max for v in change.values()
        )
        loss_tol = t["first_loss_abs_tolerance"]
        details = {
            "first_step_loss": first,
            "reference_loss": ref_loss,
            "abs_diff": abs(first - ref_loss),
            "tolerance_abs": loss_tol,
            "first_minibatch_loss": trainer_loss_mb0,
            "first_minibatch_reference_loss": sum(sums.values()) / count,
            "step_grad_norm": record["grad_norm"],
            "reference_grad_norm": ref_global,
            "step_grad_norms": step_norms,
            "reference_grad_norms": ref_norms,
            "grad_norm_rel_tolerance": norm_tol,
            "grad_norms_within": norms_ok,
            "direction_from": "the step program's first moment",
            "direction": by_group,
            "grad_cosine_min": t["grad_cosine_min"],
            "grad_rel_l2_max": t["grad_rel_l2_max"],
            "direction_within": within(by_group),
            "param_change_rel": change,
            "param_change_rel_worst_leaf": change_worst,
            "param_change_rel_max": change_max,
            "param_change_lr": lr,
            "param_change_within": change_ok,
            "control_float8": {
                "direction": control,
                "first_minibatch_loss": low_loss_mb0,
                "within": within(control)
                and abs(low_loss_mb0 - sum(sums.values()) / count) <= loss_tol,
            },
            "router_choice_margin": margin,
            "router_choice_outside_share": outside(about["gap"]),
            "router_choice_outside_max": choice_max,
            "router_choice_gap_shares_over": {
                str(x): float(np.mean(about["gap"] > x)) for x in GAP_STEPS
            },
            "router_choice_gap_max": float(about["gap"].max()),
            "control_no_bias": {
                "outside_share": outside(about["gap_no_bias"]),
                "gap_min": float(about["gap_no_bias"].min()),
                "gap_first_percentile": float(
                    np.quantile(about["gap_no_bias"], 0.01)
                ),
                "within": outside(about["gap_no_bias"]) <= choice_max,
            },
            "router_flips_share": float(np.mean(about["flipped"])),
            "first_minibatch_sequences": first_ids,
            "check_seconds": seconds,
            "tolerances_why": TOLERANCES_WHY,
            "loss_min": min(losses) if losses else None,
            "loss_max": max(losses) if losses else None,
            "grad_norm_min": min(grads) if grads else None,
            "dense_attention_fallbacks": new_warnings,
        }
        ok = (
            bool(losses)
            and all(math.isfinite(x) for x in losses)
            and all(g > 0 for g in grads)
            and abs(first - ref_loss) <= loss_tol
            and norms_ok
            and within(by_group)
            and change_ok
            and outside(about["gap"]) <= choice_max
            and not details["control_float8"]["within"]
            and not details["control_no_bias"]["within"]
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and not new_warnings  # the flash kernels, not dense
        return bool(ok), details


#: half the width of the choice bias's range (``hybrid.init_params``' own)
BIAS_HALF_RANGE = 0.15


def choice_bias(seed: int, layers: int, experts: int, first: int, held: int):
    """The router's choice bias ``[layers, experts]`` float32, from the
    seed, with EVERY SEED'S LOAD THE SAME: in each layer the held experts
    take the ``held`` midpoints of an even division of ``+-0.15`` and the
    others the ``experts - held`` midpoints of another, each set in an
    order the seed draws.  Every bias is still uniform over the range and
    which expert has which is the seed's, but how much of the range's top
    falls to the held experts is not: under 256 independent draws the 32
    held experts' pairs, which a tenth of a step's time follows, lay 7%
    apart from seed to seed and the cell's rate 1.3-1.4% (the driver's
    check of PR 53 refused the cell for it)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 5]))

    def midpoints(n):
        return ((np.arange(n) + 0.5) / n * 2.0 - 1.0) * BIAS_HALF_RANGE

    out = np.empty((layers, experts), np.float32)
    inside = np.zeros(experts, bool)
    inside[first : first + held] = True
    for row in out:
        row[inside] = rng.permutation(midpoints(held))
        row[~inside] = rng.permutation(midpoints(experts - held))
    return out


#: the gaps whose shares of tokens the check prints, for its margin
GAP_STEPS = (0.0, 0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1)


def _adam_state(state):
    """The ``ScaleByAdamState`` inside an optax chain's state."""
    if hasattr(state, "mu") and hasattr(state, "nu"):
        return state
    if isinstance(state, (tuple, list)):
        for sub in state:
            found = _adam_state(sub)
            if found is not None:
                return found
    return None


def sequence_loss_sum(logp, s, n, it) -> float:
    """The masked SUM of one sequence's per-transition PPO losses by the
    plain numpy loss (``reference.ppo_actor_loss`` is their mean)."""
    mask = s["mask"][:n]
    return reference.ppo_actor_loss(
        logp / it["temperature"], s["old"][:n], s["prox"][:n], s["adv"][:n],
        mask, it["eps_clip"], it.get("behav_imp_weight_cap"),
    ) * max(int(mask.sum()), 1)


def build(ctx) -> Driver:
    return Driver(ctx)
