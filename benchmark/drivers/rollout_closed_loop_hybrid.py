"""Driver ``rollout_closed_loop_hybrid``: ``rollout_closed_loop`` for a
stack stated by kind (granitemoehybrid), with the server kept FULL.  The
server, the client side, the warm-up rounds, the window and the token
count are the parent driver's, line for line.  What differs:

* **the loop**: ``prompts_in_flight x samples_per_prompt`` SAMPLES are out
  at any time, and each one that comes back is replaced at once by the
  next sample of the stream (prompt 0's samples, then prompt 1's, ...).
  The parent sends a prompt when ALL samples of one are back, so a group
  waits for its longest sample and most of its rows stand empty.  With
  more samples out than the server has slots a queue always stands, and
  a freed slot is taken at the engine's next step;
* **``check``**: the plain reference (``lib/reference_granitemoehybrid``)
  FOLLOWS the server's routing (``keep_routed_experts``: the engine keeps
  every layer's routed experts of the requests it finished), so that the
  two are apart by rounding alone; the written tolerances; and a control,
  the same reference with every matrix in float8, which the same
  comparison has to refuse;
* a few counters of the second cache kind and of the held experts in the
  window record, for the readers that came with the configuration.
"""

from __future__ import annotations

import asyncio

import numpy as np

from benchmark.drivers.rollout_closed_loop import Driver as ClosedLoopDriver
from benchmark.lib import lengths
from benchmark.lib import reference_granitemoehybrid as reference

#: |server logprob - plain reference| over the generated tokens of the
#: three picked sequences, the reference taking the server's routed
#: experts at every (position, layer).  The server runs bf16 weights and
#: bf16 activations, float32 recurrent state and decay, the chunked SSD
#: form for the prompt and the kernel for every decode step; the
#: reference runs the same bf16 weights with float32 activations, a
#: sequential scan, every held expert for every token, "highest"
#: precision.  What separates them is bf16 rounding of activations over
#: ten layers.  Readings on a v5e (PERF.md section 6, PR 31, review
#: round): the server 0.0053-0.0085 max / 0.0018-0.0020 mean (six runs,
#: 18 sequences of 301-621 new tokens); the CONTROL 0.0296-0.0416 /
#: 0.0069-0.0077: the same reference, following the same routing, with
#: every matrix rounded to float8 (e4m3), the nearest precision below the
#: bfloat16 the configuration states.  The limits lie between: 2.4 and
#: 2.0 times above the server's largest, 1.5 and 1.7 times below the
#: control's smallest.  ``check`` runs the control through the same
#: comparison in every run and it has to come out NOT correct.  A wrong
#: page, mask, position, state slot or weight shows as 0.1-1.
#:
#: Two things these limits had hidden while they stood at 0.05 / 0.012
#: (the first round's, 1.9 times the server's 0.026 / 0.008 of then):
#: the reference routed for itself, and takes another tenth expert of 72
#: in 5% of (position, layer) pairs (``router_flips_share``); and, the
#: larger term by far, the server's head gave its logits out in bfloat16
#: (``models/hybrid._head_logits``), which alone read 0.024-0.027 /
#: 0.0065-0.0071 with the routing followed: as far from float32 as the
#: float8 control.
#:
#: A recurrent state CARRIED in bfloat16 is not refused by a limit on
#: log-probabilities: the same reference so computed reads 0.00009-
#: 0.00016 / 0.00002 against itself (``bf16_state`` of the check line),
#: a fiftieth of the server's own bf16-activation rounding, over
#: sequences of 0.4-1.1k tokens.  ``state_dtype`` holds the stated type.
LOGP_MAX_ABS = 0.02
LOGP_MEAN_ABS = 0.004

CONTROL = ("weights", "float8_e4m3fn")


def compare(got, want) -> dict:
    """The comparison that decides ``correct``, for the server's
    log-probabilities and for the control's alike."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    diff = np.abs(got - want)
    bad = np.flatnonzero(~np.isfinite(got))
    row = {
        # a log-probability that is no number fails the limits below;
        # say where it began
        "nonfinite": int(bad.size),
        "first_nonfinite": int(bad[0]) if bad.size else -1,
        "max_abs_diff": float(diff.max()),
        "mean_abs_diff": float(diff.mean()),
    }
    row["within"] = bool(
        row["max_abs_diff"] <= LOGP_MAX_ABS
        and row["mean_abs_diff"] <= LOGP_MEAN_ABS
    )
    return row


class Driver(ClosedLoopDriver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self._snaps = []
        self._group = None  # the prompt whose samples are being sent

    # -- the loop: every sample that comes back is replaced at once --------

    def _next_sample(self):
        """The stream's next ``(k, i, prompt ids, new tokens)``: the
        samples of prompt 0, then of prompt 1, ...  (Called on the client
        loop's one thread.)"""
        g = self._group
        if g is None or g["i"] == len(g["max_new_tokens"]):
            k = self.next_prompt
            self.next_prompt += 1
            g = self._group = dict(
                lengths.rollout_prompt(
                    self.traffic, self.ctx.seed, self.hf["vocab_size"], k
                ),
                k=k, i=0,
            )
        g["i"] += 1
        return g["k"], g["i"] - 1, g["prompt_ids"], g["max_new_tokens"][g["i"] - 1]

    async def _slot(self):
        """``samples_per_prompt`` workers (the parent starts
        ``prompts_in_flight`` of these), each with ONE sample out: when
        it is back, the stream's next."""

        async def worker():
            while not self.stopping:
                self.done.append(await self._sample(*self._next_sample()))

        await asyncio.gather(
            *(worker() for _ in range(self.traffic["samples_per_prompt"]))
        )

    def _fill_sweep(self):
        """The parent's sweep, then ``warm.mixed_rounds``: rounds of
        prompts of DIFFERENT lengths ``[[lengths], samples of each]``.
        Siblings that arrive one by one make fills that end while the
        batch's last prompt goes on, so first tokens are sampled for fewer
        rows than the batch's width allows: shapes the parent's rounds of
        equal prompts never meet."""
        rounds = super()._fill_sweep()
        rng = np.random.default_rng(self.ctx.seed % (2**32) + 1)
        for lens, siblings in self.traffic["warm"].get("mixed_rounds", []):
            self._round(f"m{rounds}", lens, siblings, rng)
            rounds += 1
        return rounds

    # -- the window's record, with the counters the new readers take -------

    def _counters(self):
        c = super()._counters()
        eng = self.engine
        c.update(
            state_copies=float(eng.state_copies_total),
            state_reprefills=float(eng.state_reprefills_total),
            moe_pairs_held=float(eng.moe_pairs_held_total),
            moe_pairs_routed=float(eng.moe_pairs_routed_total),
            moe_expert_pairs=[float(n) for n in eng.moe_expert_pairs],
            requests_queued=eng.n_pending,
        )
        self._snaps.append(c)
        return c

    def measure(self, seconds: float) -> dict:
        record = super().measure(seconds)
        c0, c1 = self._snaps[-2], self._snaps[-1]
        counters = record["counters"]
        for key in (
            "state_copies", "state_reprefills", "moe_pairs_held",
            "moe_pairs_routed",
        ):
            counters[key] = c1[key] - c0[key]
        counters["moe_expert_pairs"] = [
            b - a for a, b in zip(c0["moe_expert_pairs"], c1["moe_expert_pairs"])
        ]
        serve = self.ctx.config["roles"]["serve"]
        counters["layer_types"] = list(serve["model_overrides"]["layer_types"])
        counters["held_experts"] = serve["model_overrides"]["moe_held_experts"]
        # ISSUE 31 leaves the tail out of this cell (with a standing queue
        # it swings with the smallest change)
        record["end_to_end"].pop("seq_p90_s", None)
        record["notes"].update(
            state_copies=counters["state_copies"],
            state_reprefills=counters["state_reprefills"],
            # with more samples out than slots a queue stands: at the
            # window's first and last instant
            requests_queued=[c0["requests_queued"], c1["requests_queued"]],
        )
        return record

    # -- correctness, outside the window -----------------------------------

    def check(self):
        from areal_tpu.models import paged

        eng = self.engine
        details = {
            "paged": bool(eng.paged),
            "use_paged_kernel": bool(getattr(eng, "_use_paged_kernel", False)),
            "kernel_interpret": bool(paged.kernel_interpret()),
            "weight_dtype": str(self._jax.tree.leaves(eng.params)[0].dtype),
            "state_dtype": str(eng.ssm_state.dtype),
        }
        win = self.window_done
        if not win:
            return False, dict(details, reason="no sequence completed in the window")
        # the longest prompt (two fill chunks, the state carried between
        # them), the shortest, and between them one whose sibling also
        # completed (a state copied from the fill's slot, or a late
        # sibling's own prefill)
        by_plen = sorted(win, key=lambda d: (d.prompt_len, d.k, d.i))
        middle = by_plen[1:-1] or by_plen
        shared = next(
            (d for d in middle if any(o.k == d.k and o.i != d.i for o in win)),
            middle[len(middle) // 2],
        )
        picks = [by_plen[-1], shared, by_plen[0]]
        # what the engine routed each of them to (a sample's request is
        # the one member of its group: ``generate_group`` names it so)
        routed = [eng.routed_experts(f"p{d.k}s{d.i}-0") for d in picks]
        params = eng.params
        del eng  # the last reference to pool and state slots, once stopped
        self._stop_server()  # frees them before the reference runs
        if any(r is None for r in routed):
            return False, dict(details, reason="the engine kept no routing of a pick")
        overrides = self.ctx.config["roles"]["serve"]["model_overrides"]
        hf = dict(self.hf, layer_types=list(overrides["layer_types"]))
        first = overrides.get("moe_first_expert", 0)
        fn = reference.make_token_logps(hf, first)
        rows, refs = [], []
        for d, r in zip(picks, routed):
            ref, margin, flips = reference.sequence_logps(fn, params, d.seq, r)
            new = slice(d.prompt_len - 1, None)
            refs.append(ref[new])
            rows.append(
                dict(
                    compare(d.logprobs[new], ref[new]),
                    prompt_len=d.prompt_len, new_tokens=d.new_tokens,
                    tokens_distinct=len(set(d.seq[d.prompt_len :])),
                    mean_logp=float(ref[new].mean()),
                    router_margin_min=float(margin[new].min()),
                    # (position, layer) pairs in which the reference, left
                    # to itself, would have routed otherwise
                    router_flips_share=float(flips.mean() / len(hf["layer_types"])),
                )
            )
        details["reference"] = rows
        # every sequence of the window, not the picks alone: a state or a
        # page gone bad shows as a log-probability that is no number
        details["sequences_nonfinite"] = sum(
            not np.isfinite(
                np.asarray(d.logprobs, np.float32)[d.prompt_len - 1 :]
            ).all()
            for d in win
        )
        details["tolerance"] = {"max_abs": LOGP_MAX_ABS, "mean_abs": LOGP_MEAN_ABS}
        # the control: the SAME reference, following the same routing,
        # computed in the nearest precision below the stated one, on the
        # pick with most decode steps; its log-probabilities go through
        # the same comparison as the server's and have to be refused.
        # Beside it, for the record, the same with the recurrent state
        # carried in bfloat16 (PERF.md says why that one is not refused)
        j = max(range(len(picks)), key=lambda i: picks[i].new_tokens)
        new = slice(picks[j].prompt_len - 1, None)
        for name, low in (("control", CONTROL), ("bf16_state", ("state", "bfloat16"))):
            low_fn = reference.make_token_logps(hf, first, low=low)
            got, _, _ = reference.sequence_logps(
                low_fn, params, picks[j].seq, routed[j]
            )
            details[name] = dict(
                compare(got[new], refs[j]), what=f"{low[0]} in {low[1]}",
                new_tokens=picks[j].new_tokens,
            )
        ok = (
            details["sequences_nonfinite"] == 0
            and all(r["within"] for r in rows)
            and not details["control"]["within"]
            and details["paged"]
            and details["state_dtype"] == "float32"
            and all(d.new_tokens == d.asked for d in win)
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and details["use_paged_kernel"] and not details["kernel_interpret"]
        return bool(ok), details


def build(ctx) -> Driver:
    return Driver(ctx)
