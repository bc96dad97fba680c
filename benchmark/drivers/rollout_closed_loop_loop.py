"""Driver ``rollout_closed_loop_loop``: the full-server closed loop for a
LOOPED dense stack (``ouro``: Ouro-2.6B, whose 48 layers are run four times
with the same weights, 192 cache layers over 48 weight layers), on the
DENSE path (``models/transformer.py`` + ``models/paged.py``), as
``rollout_closed_loop`` drives ``qwen2.5-1.5b``.  The server, the client
side, the warm-up rounds, the window, the token count and the window record
are the dense driver's, line for line; the loop that keeps the server FULL
is ``rollout_closed_loop_hybrid``'s (every sample that comes back is
replaced by the stream's next), sent IN THE STREAM'S ORDER to a paused
server as ``rollout_closed_loop_shared`` sends it: the engine admits first
come first served and decides by steps, so with the arrivals in order
which siblings share a fill, who waits for pages and who is preempted are
the same in every run.  What differs:

* **``check``**: the plain reference is ``lib/reference_ouro`` (every pass
  over the whole sequence with its own keys and values, sandwich norms, the
  norm after every pass; no cache, no pages).  The stack has no router, so
  the two are apart by rounding alone.  The control that the same
  comparison has to refuse: every matrix in float8, the nearest precision
  below the stated bfloat16;
* the counters the readers take (``lib/flops_ouro``): the steps admission
  waited for PAGES with a slot free, the rows preempted, the pages live.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers.rollout_closed_loop import Driver as ClosedLoopDriver
from benchmark.drivers.rollout_closed_loop_shared import Driver as InOrderDriver
from benchmark.lib import flops_ouro
from benchmark.lib import reference_ouro as reference

#: |server logprob - plain reference| over the generated tokens of the
#: three picked sequences (the longest, one whose sibling also completed,
#: the shortest prompt).  The server runs bf16 weights and bf16
#: activations through 192 layer passes (48 layers four times over, six
#: times the deepest stack served before this one), the paged kernel at one
#: query head a KV head and a cache of its own for every (pass, layer); the
#: reference runs the same bf16 weights with float32 activations,
#: whole-sequence attention, "highest" precision.  What separates them is
#: bf16 rounding of activations, 192 times over: a branch's output is
#: normed to rms ~1 and added to a residual stream that grows to rms ~10
#: within a pass (a bfloat16 add there rounds at 0.04) before the pass's
#: norm brings it back to ~1, under logits of deviation 0.58.  The CONTROL is the same
#: reference with every matrix rounded to float8 (e4m3), the nearest
#: precision below the bfloat16 the configuration states; ``check`` runs it
#: through the same comparison in every run and it has to come out NOT
#: correct.  Readings on a v5e (my chip runs, PR 55; PERF.md section 6 has
#: every run's) are SERVER_READINGS and CONTROL_READINGS below, smallest
#: and largest over the three picks of nineteen runs on nineteen seeds (57
#: sequences of 77-1,024 new tokens at up to 1,348 of context; the control
#: on each run's longest): the distance is some 150 times the dense cell's
#: 28 layers' (0.0021 / 0.0005) and 3 times the shared cell's 32 layers',
#: and the float8 control stands 3-9 times further out.  Each limit is the
#: geometric middle of the server's largest and the control's smallest: a
#: factor of 1.6-1.8 of room on either side.  A wrong page, pass, cache
#: layer or norm shows as 2-8 (the CPU tests' ``wrong="shared_cache"``
#: reads 2.0 in float32).
SERVER_READINGS = {"max_abs": (0.262, 0.627), "mean_abs": (0.0696, 0.1396)}
CONTROL_READINGS = {"max_abs": (1.707, 2.346), "mean_abs": (0.4492, 0.5568)}
LOGP_MAX_ABS = 1.03
LOGP_MEAN_ABS = 0.25

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


class Driver(InOrderDriver):
    """``_slot`` and ``_send_next`` (the stream's order) are the shared
    cell's, ``_next_sample`` the hybrid cell's; counters and window record
    are the DENSE driver's with this stack's beside them; the check is
    this stack's."""

    def _counters(self):
        c = ClosedLoopDriver._counters(self)
        eng = self.engine
        c.update(
            rows_preempted=float(eng.preempted_total),
            # (an engine from before the count has none: the reader of
            # page_wait_share then has nothing to read)
            admission_page_waits=getattr(eng, "admission_page_waits_total", None),
            engine_steps=float(eng._step_seq),
            pages_live=eng.pages_live,
            requests_queued=eng.n_pending,
        )
        self._snaps.append(c)
        return c

    def measure(self, seconds: float) -> dict:
        record = ClosedLoopDriver.measure(self, seconds)
        c0, c1 = self._snaps[-2], self._snaps[-1]
        counters = record["counters"]
        for key in ("rows_preempted", "engine_steps"):
            counters[key] = c1[key] - c0[key]
        if c1["admission_page_waits"] is not None:
            counters["admission_page_waits"] = float(
                c1["admission_page_waits"] - c0["admission_page_waits"]
            )
        counters["pages_live"] = c1["pages_live"]  # at the window's last instant
        # what tells a reader that the record is this stack's: (weight
        # layers, passes, cache layers, bytes a cached token)
        hf = self.ctx.config["hf_config"]
        counters["loop_shape"] = [
            hf["num_hidden_layers"], flops_ouro.passes(hf),
            flops_ouro.cache_layers(hf), flops_ouro.kv_bytes_per_token(hf),
        ]
        # above the knee by construction: the tail swings with the
        # smallest change and is not this cell's to judge
        record["end_to_end"].pop("seq_p90_s", None)
        budget = self.traffic["engine"]["prefill_chunk_tokens"]
        record["notes"].update(
            prefill_tokens=counters["prefill_tokens"],
            decode_chunks=counters["decode_chunks"],
            # the fill stage: ONE batch of at most prefill_chunk_tokens an
            # engine step (a step a decode chunk); the traffic file holds
            # the cell under 70% of that capacity
            fill_stage_share=counters["prefill_tokens"]
            / max(counters["decode_chunks"] * budget, 1.0),
            pages_live=counters["pages_live"],
            pages_total=self.engine.pages_total,
            rows_preempted=counters["rows_preempted"],
            admission_page_waits=counters.get("admission_page_waits"),
            engine_steps=counters["engine_steps"],
            requests_queued=[c0["requests_queued"], c1["requests_queued"]],
            # the schedule is the same in every run, so these tell a slow
            # machine from a schedule that came out otherwise
            engine_wait_s=counters["device_s"],
            engine_host_s=counters["host_s"],
        )
        return record

    def check(self):
        from areal_tpu.models import paged

        eng = self.engine
        hf = self.ctx.config["hf_config"]
        details = {
            "paged": bool(eng.paged),
            "use_paged_kernel": bool(getattr(eng, "_use_paged_kernel", False)),
            "kernel_interpret": bool(paged.kernel_interpret()),
            "weight_dtype": str(self._jax.tree.leaves(eng.params)[0].dtype),
            # [cache layers, pages, kv heads, page, head]: a layer for
            # every (pass, layer)
            "pool_shape": list(eng.k_pool.shape),
            "loop_counts": dict(getattr(eng, "loop_counts", {})),
            # the fill shapes (F_pad, C) and distributions (fills ended
            # together, targets) that ran, with their counts: what the
            # traffic file's warm lists are trimmed by
            "fill_shapes_run": sorted(
                [list(k), n] for k, n in eng.fill_shapes_run.items()
            ),
            "distributions_run": sorted(
                [list(k), n] for k, n in eng.distributions_run.items()
            ),
            "rows_preempted_total": eng.preempted_total,
            "admission_page_waits_total": getattr(
                eng, "admission_page_waits_total", None
            ),
        }
        win = self.window_done
        if not win:
            return False, dict(details, reason="no sequence completed in the window")
        # the longest sequence (the longest context a decode step reads),
        # the shortest prompt, and between them one whose sibling also
        # completed (a prompt's pages shared across all 192 cache layers,
        # its tail page copied)
        by_plen = sorted(win, key=lambda d: (d.prompt_len, d.k, d.i))
        longest = max(win, key=lambda d: (len(d.seq), d.k, d.i))
        middle = [d for d in by_plen[1:] if d is not longest] or by_plen
        shared = next(
            (d for d in middle if any(o.k == d.k and o.i != d.i for o in win)),
            middle[len(middle) // 2],
        )
        picks = [longest, shared, by_plen[0]]
        params = eng.params
        del eng  # the last reference to the pool, once stopped
        self._stop_server()  # frees it before the reference runs
        fn = reference.make_token_logps(hf)
        # every sequence padded to the engine's longest: ONE shape to
        # compile the layer, the pass's end and the head
        pad_to = self.traffic["engine"]["kv_cache_len"]
        rows, refs = [], []
        for d in picks:
            ref = reference.sequence_logps(fn, params, d.seq, pad_to=pad_to)
            new = slice(d.prompt_len - 1, None)
            refs.append(ref[new])
            rows.append(
                dict(
                    compare(d.logprobs[new], ref[new]),
                    prompt_len=d.prompt_len, new_tokens=d.new_tokens,
                    tokens_distinct=len(set(d.seq[d.prompt_len :])),
                    mean_logp=float(ref[new].mean()),
                )
            )
        details["reference"] = rows
        details["context_max"] = max(len(d.seq) for d in picks)
        # every sequence of the window, not the picks alone: a page gone
        # bad shows as a log-probability that is no number
        details["sequences_nonfinite"] = sum(
            not np.isfinite(
                np.asarray(d.logprobs, np.float32)[d.prompt_len - 1 :]
            ).all()
            for d in win
        )
        details["tolerance"] = {"max_abs": LOGP_MAX_ABS, "mean_abs": LOGP_MEAN_ABS}
        # the control: the SAME reference computed in the nearest
        # precision below the stated one, on the pick with most decode
        # steps, through the same comparison: it has to be refused
        j = max(range(len(picks)), key=lambda i: picks[i].new_tokens)
        new = slice(picks[j].prompt_len - 1, None)
        low_fn = reference.make_token_logps(hf, low=CONTROL)
        got = reference.sequence_logps(low_fn, params, picks[j].seq, pad_to=pad_to)
        details["control"] = dict(
            compare(got[new], refs[j]), what=f"{CONTROL[0]} in {CONTROL[1]}",
            new_tokens=picks[j].new_tokens,
        )
        ok = (
            details["sequences_nonfinite"] == 0
            and all(r["within"] for r in rows)
            and not details["control"]["within"]
            and details["paged"]
            and details["pool_shape"][0] == flops_ouro.cache_layers(hf)
            and all(d.new_tokens == d.asked for d in win)
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and details["use_paged_kernel"] and not details["kernel_interpret"]
        return bool(ok), details


def build(ctx) -> Driver:
    return Driver(ctx)
