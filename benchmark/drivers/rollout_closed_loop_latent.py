"""Driver ``rollout_closed_loop_latent``: ``rollout_closed_loop_hybrid`` for
a stack of latent-attention layers with leading dense layers and
group-routed experts (``deepseek_v3``: GigaChat3.1-702B-A36B).  The server,
the client side, the loop that keeps the server FULL, the warm-up rounds,
the window and the token count are that driver's, line for line.  What
differs:

* **``check``**: the plain reference is ``lib/reference_deepseek_v3``
  (unabsorbed attention over the whole sequence, no cache), following the
  server's routing of every EXPERT layer; this model's tolerances; its
  control (every matrix in float8); no recurrent state to look at;
* the counters the latent readers take: the shape of the share this chip
  holds (for ``lib/flops_mla``) and the chosen groups that hit it.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers.rollout_closed_loop_hybrid import Driver as FullServerDriver
from benchmark.lib import reference_deepseek_v3 as reference

#: |server logprob - plain reference| over the generated tokens of the
#: three picked sequences, the reference taking the server's routed
#: experts at every (position, expert layer).  The server runs bf16
#: weights and bf16 activations, the ABSORBED form of attention over
#: latent pages for every decode step and for a fill chunk's prefix
#: (queries through W_UK and the accumulator through W_UV, each rounded
#: to bfloat16 on the way), the expanded form inside a chunk; the
#: reference runs the same bf16 weights with float32 activations,
#: UNABSORBED attention over the whole sequence, every held expert for
#: every token, "highest" precision.  What separates them is bf16
#: rounding of activations over five layers, landing on logits of
#: deviation 0.6.  The CONTROL is the same reference, following the same
#: routing, with every matrix rounded to float8 (e4m3), the nearest
#: precision below the bfloat16 the configuration states.  ``check`` runs
#: the control through the same comparison in every run and it has to
#: come out NOT correct.  Readings on a v5e (my chip runs, PR 33:
#: fourteen runs, thirteen seeds, three sequences each; PERF.md section
#: 6): the server 0.0088-0.0150 max / 0.0026-0.0030 mean, the control
#: 0.082-0.110 max / 0.0199-0.0213 mean; the limits are granite's and
#: lie between the two on both counts (1.33 times the server's largest,
#: a quarter and a fifth of the control's smallest).  A wrong page,
#: mask, position or weight shows as 0.1-1.
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


class Driver(FullServerDriver):
    def __init__(self, ctx):
        super().__init__(ctx)
        # a sliced vocabulary is a smaller vocabulary: prompts draw their
        # ids from the rows this chip holds
        over = ctx.config["roles"]["serve"]["model_overrides"]
        self.hf = dict(self.hf, vocab_size=over["vocab_size"])

    def _counters(self):
        c = super()._counters()
        c["moe_groups_hit"] = float(self.engine.moe_groups_hit_total)
        return c

    def measure(self, seconds: float) -> dict:
        record = super().measure(seconds)
        c0, c1 = self._snaps[-2], self._snaps[-1]
        counters = record["counters"]
        counters["moe_groups_hit"] = c1["moe_groups_hit"] - c0["moe_groups_hit"]
        over = self.ctx.config["roles"]["serve"]["model_overrides"]
        # (layers, leading dense ones, router outputs, held experts,
        # vocabulary rows) of this chip's share: lib/flops_mla's ``shape``
        counters["latent_shape"] = [
            self.n_layers, over["n_dense_layers"], self.hf["n_routed_experts"],
            over["moe_held_experts"], over["vocab_size"],
        ]
        # the fill stage: with rows decoding the engine runs ONE batch of
        # at most ``prefill_chunk_tokens`` an engine step (a step a decode
        # chunk), so that many tokens a step are its capacity; the traffic
        # file holds the cell under 70% of it
        budget = self.traffic["engine"]["prefill_chunk_tokens"]
        record["notes"].update(
            prefill_tokens=counters["prefill_tokens"],
            decode_chunks=counters["decode_chunks"],
            fill_stage_share=counters["prefill_tokens"]
            / max(counters["decode_chunks"] * budget, 1.0),
            moe_pairs_held=counters["moe_pairs_held"],
            moe_pairs_routed=counters["moe_pairs_routed"],
            moe_groups_hit=counters["moe_groups_hit"],
        )
        return record

    def check(self):
        from areal_tpu.models import paged

        eng = self.engine
        details = {
            "paged": bool(eng.paged),
            "use_paged_kernel": bool(getattr(eng, "_use_paged_kernel", False)),
            "kernel_interpret": bool(paged.kernel_interpret()),
            "weight_dtype": str(self._jax.tree.leaves(eng.params)[0].dtype),
            "pool_shape": list(eng.k_pool.shape),
            "pool_dtype": str(eng.k_pool.dtype),
            "v_pool_bytes": int(eng.v_pool.nbytes),
            "prefix_cache": eng.prefix_cache_stats(),
        }
        win = self.window_done
        if not win:
            return False, dict(details, reason="no sequence completed in the window")
        # the longest prompt (three fill chunks: the absorbed prefix part
        # twice), the shortest, and between them one whose sibling also
        # completed (pages shared among the siblings of a fill, or taken
        # from the prefix cache by a sibling that came later)
        by_plen = sorted(win, key=lambda d: (d.prompt_len, d.k, d.i))
        middle = by_plen[1:-1] or by_plen
        shared = next(
            (d for d in middle if any(o.k == d.k and o.i != d.i for o in win)),
            middle[len(middle) // 2],
        )
        picks = [by_plen[-1], shared, by_plen[0]]
        details["token_id_max"] = max(max(d.seq) for d in win)
        details["vocab_rows"] = int(eng.params["lm_head"]["w"].shape[1])
        routed = [eng.routed_experts(f"p{d.k}s{d.i}-0") for d in picks]
        params = eng.params
        del eng  # the last reference to the pool, once stopped
        self._stop_server()  # frees it before the reference runs
        if any(r is None for r in routed):
            return False, dict(details, reason="the engine kept no routing of a pick")
        over = self.ctx.config["roles"]["serve"]["model_overrides"]
        hf = dict(
            self.hf, num_hidden_layers=self.n_layers,
            first_k_dense_replace=over["n_dense_layers"],
        )
        n_expert_layers = self.n_layers - over["n_dense_layers"]
        first = over.get("moe_first_expert", 0)
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
                    # (position, expert layer) pairs in which the
                    # reference, left to itself, would have routed otherwise
                    router_flips_share=float(flips.mean() / n_expert_layers),
                )
            )
        details["reference"] = rows
        # every sequence of the window, not the picks alone: a page gone
        # bad shows as a log-probability that is no number
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
        # the same comparison as the server's and have to be refused
        j = max(range(len(picks)), key=lambda i: picks[i].new_tokens)
        new = slice(picks[j].prompt_len - 1, None)
        low_fn = reference.make_token_logps(hf, first, low=CONTROL)
        got, _, _ = reference.sequence_logps(low_fn, params, picks[j].seq, routed[j])
        details["control"] = dict(
            compare(got[new], refs[j]), what=f"{CONTROL[0]} in {CONTROL[1]}",
            new_tokens=picks[j].new_tokens,
        )
        ok = (
            details["sequences_nonfinite"] == 0
            and all(r["within"] for r in rows)
            and not details["control"]["within"]
            and details["paged"]
            and details["v_pool_bytes"] == 0
            and details["token_id_max"] < details["vocab_rows"]
            and all(d.new_tokens == d.asked for d in win)
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and details["use_paged_kernel"] and not details["kernel_interpret"]
        return bool(ok), details


def build(ctx) -> Driver:
    return Driver(ctx)
