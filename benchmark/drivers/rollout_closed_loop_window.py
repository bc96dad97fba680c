"""Driver ``rollout_closed_loop_window``: ``rollout_closed_loop_hybrid`` for
a stack of window and global attention layers with ReLU-gated experts
(``smallthinker``: SmallThinker-21BA3B-Instruct).  The server, the client
side, the loop that keeps the server FULL, the warm-up rounds, the window
and the token count are that driver's, line for line.  What differs:

* **``check``**: the plain reference is ``lib/reference_smallthinker``
  (whole-sequence attention under the window's mask, no cache, no pages),
  following the server's routing of every layer; the rollout cells'
  tolerances; THREE controls that the same comparison has to refuse: every
  matrix in float8 (the nearest precision below the stated bfloat16), and
  the reference making each of two mistakes (the window left off, the
  router fed the experts' input); and, for the record, a third mistake
  that no limit on log-probabilities can refuse at random weights (RoPE on
  the global layers: ``ON_RECORD`` below);
* the counters the window readers take: the page rule's (pages the window
  layers allocated, let go behind a window, hold; prefixes refused for a
  window tail gone) and the cached positions a decode step reads by layer
  kind (for ``lib/flops_window``).
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers.rollout_closed_loop_hybrid import Driver as FullServerDriver
from benchmark.lib import flops_window
from benchmark.lib import reference_smallthinker as reference

#: |server logprob - plain reference| over the generated tokens of the
#: three picked sequences (each longer than the window of 4,096, so each
#: crosses it), the reference taking the server's routed experts at every
#: (position, layer).  The server runs bf16 weights and bf16 activations,
#: paged KV in two pools, the windowed kernel from the page that holds a
#: window's first position; the reference runs the same bf16 weights with
#: float32 activations, whole-sequence attention under the mask, every
#: held expert for every token, "highest" precision.  What separates them
#: is bf16 rounding of activations over eight layers, landing on logits of
#: deviation 0.6.  The limits are the rollout cells' (ISSUE 40); PERF.md
#: section 6 (PR 40) has the server's readings and each control's.
LOGP_MAX_ABS = 0.02
LOGP_MEAN_ABS = 0.005

#: what each control changes, by name on the check line: each has to come
#: out NOT within the limits
CONTROLS = {
    "control": dict(low=("weights", "float8_e4m3fn")),
    "window_off": dict(wrong="window_off"),
    "router_reads_m": dict(wrong="router_reads_m"),
}

#: on the check line and refused by nothing: RoPE on the global layers
#: moves the log-probabilities of 2,048 new tokens by 0.016 max / 0.0036
#: mean at these random weights (my chip run, PR 40), as much as the
#: server's own bfloat16 rounding (0.015 / 0.0032): attention over 4-10k
#: positions of random keys is close to a plain mean of the values, which
#: a rotation of q and k does not move.  No limit between the server's
#: reading and the float8 control's separates the two; the CPU tests hold
#: the mistake at float32 (tests/model/test_window.py: 0.24 against 2e-5)
ON_RECORD = {"rope_on_global": dict(wrong="rope_on_global")}

#: the reference pads a sequence to a multiple of this (4.1k-10.2k tokens
#: make 6,144 / 8,192 / 10,240: three shapes to compile a layer kind)
PAD_TO = 2048


def compare(got, want) -> dict:
    """The comparison that decides ``correct``, for the server's
    log-probabilities and for the controls' alike."""
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
    def _counters(self):
        c = super()._counters()
        eng = self.engine
        c.update(
            window_pages_allocated=float(eng._win.allocated_total),
            window_pages_released=float(eng.window_pages_released),
            window_pages_freed_behind=float(eng._win.freed_behind_total),
            prefix_refused_window=float(eng.prefix_refused_window),
            window_pages_live=eng.window_pages_live,
            global_pages_live=eng.global_pages_live,
        )
        return c

    def measure(self, seconds: float) -> dict:
        record = super().measure(seconds)
        c0, c1 = self._snaps[-2], self._snaps[-1]
        counters = record["counters"]
        for key in (
            "window_pages_allocated", "window_pages_released",
            "window_pages_freed_behind", "prefix_refused_window",
        ):
            counters[key] = c1[key] - c0[key]
        for key in ("window_pages_live", "global_pages_live"):
            counters[key] = c1[key]  # at the window's last instant
        hf = flops_window.as_run(self.ctx.config)
        # (held experts, vocabulary rows) of this chip: all of both
        counters["window_shape"] = [
            hf["moe_num_primary_experts"], hf["vocab_size"],
        ]
        # sum over the window's new tokens of the cached positions a
        # WINDOW layer read for each, scaled like context_token_reads
        win = self.window_done
        done = sum(d.new_tokens for d in win)
        scale = counters["tokens_emitted"] / done if done else 0.0
        counters["window_token_reads"] = scale * sum(
            flops_window.window_reads(hf, d.prompt_len + t)
            for d in win for t in range(d.new_tokens)
        )
        budget = self.traffic["engine"]["prefill_chunk_tokens"]
        record["notes"].update(
            prefill_tokens=counters["prefill_tokens"],
            decode_chunks=counters["decode_chunks"],
            # the fill stage: ONE batch of at most prefill_chunk_tokens an
            # engine step (a step a decode chunk); the traffic file holds
            # the cell under 70% of that capacity
            fill_stage_share=counters["prefill_tokens"]
            / max(counters["decode_chunks"] * budget, 1.0),
            window_pages_released=counters["window_pages_released"],
            window_pages_live=counters["window_pages_live"],
            global_pages_live=counters["global_pages_live"],
            prefix_refused_window=counters["prefix_refused_window"],
            window_row_pages_max=self.engine._win.row_pages_max,
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
            "pool_shapes": [list(eng.k_pool.shape), list(eng.win_k_pool.shape)],
            "prefix_cache": eng.prefix_cache_stats(),
            "window_pages_released_total": eng.window_pages_released,
            "window_row_pages_max": eng._win.row_pages_max,
        }
        win = self.window_done
        if not win:
            return False, dict(details, reason="no sequence completed in the window")
        # the longest prompt (eight fill chunks), the shortest, and between
        # them one whose sibling also completed (pages shared among the
        # siblings of a fill, or taken from the prefix cache by a sibling
        # that came later)
        by_plen = sorted(win, key=lambda d: (d.prompt_len, d.k, d.i))
        middle = by_plen[1:-1] or by_plen
        shared = next(
            (d for d in middle if any(o.k == d.k and o.i != d.i for o in win)),
            middle[len(middle) // 2],
        )
        picks = [by_plen[-1], shared, by_plen[0]]
        routed = [eng.routed_experts(f"p{d.k}s{d.i}-0") for d in picks]
        params = eng.params
        del eng  # the last reference to the pools, once stopped
        self._stop_server()  # frees them before the reference runs
        if any(r is None for r in routed):
            return False, dict(details, reason="the engine kept no routing of a pick")
        hf = flops_window.as_run(self.ctx.config)
        window = hf["sliding_window_size"]
        fn = reference.make_token_logps(hf)
        rows, refs = [], []
        for d, r in zip(picks, routed):
            ref, margin, flips = reference.sequence_logps(
                fn, params, d.seq, r, pad_to=PAD_TO
            )
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
                    router_flips_share=float(
                        flips.mean() / hf["num_hidden_layers"]
                    ),
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
        # the controls: the SAME reference, following the same routing, in
        # float8 and with each mistake, on the pick with most decode steps;
        # each goes through the same comparison as the server's
        # log-probabilities, and those of CONTROLS have to be refused
        j = max(range(len(picks)), key=lambda i: picks[i].new_tokens)
        new = slice(picks[j].prompt_len - 1, None)
        for name, how in {**CONTROLS, **ON_RECORD}.items():
            low_fn = reference.make_token_logps(hf, **how)
            got, _, _ = reference.sequence_logps(
                low_fn, params, picks[j].seq, routed[j], pad_to=PAD_TO
            )
            details[name] = dict(
                compare(got[new], refs[j]), new_tokens=picks[j].new_tokens
            )
        ok = (
            details["sequences_nonfinite"] == 0
            and all(r["within"] for r in rows)
            and not any(details[name]["within"] for name in CONTROLS)
            and details["paged"]
            and all(d.prompt_len > window for d in picks)
            and details["window_pages_released_total"] > 0
            and all(d.new_tokens == d.asked for d in win)
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and details["use_paged_kernel"] and not details["kernel_interpret"]
        return bool(ok), details


def build(ctx) -> Driver:
    return Driver(ctx)
