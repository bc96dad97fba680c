"""Driver ``rollout_closed_loop_parallel``: the full-server closed loop for a
stack of PARALLEL layers (``falcon_h1``: Falcon-H1-34B-Instruct): attention
AND a Mamba-2 mixer side by side in every layer, so every layer holds pages
in the pool of whole-context pages and a recurrent-state slot.  The
server, the client side, the warm-up rounds, the window and the token
count are the parent closed-loop driver's, line for line; the loop that
keeps the server FULL is ``rollout_closed_loop_hybrid``'s, sent IN THE
STREAM'S ORDER as ``rollout_closed_loop_shared`` sends it (this stack keeps
a recurrent state, so there is no prefix cache and a sibling that misses its
prompt's fill prefills 1-3k tokens again: with the arrivals in order the
schedule is the same in every run).  What differs:

* **``check``**: the plain reference is ``lib/reference_falcon_h1`` (the
  recurrence as a scan over positions, whole-sequence attention, no cache,
  no pages, no slots, every multiplier where the equations have it).  This
  stack has no router, so nothing is followed: the two are apart by
  rounding alone.  The control that the same comparison has to refuse:
  every matrix in float8, the nearest precision below the stated
  bfloat16.  For the record, the same reference with its state carried in
  bfloat16 (``bf16_state``);
* a sliced vocabulary: prompts draw their ids from the rows this chip
  holds;
* the counters the readers take (``lib/flops_parallel``), and the fill
  stage's share and the late siblings' prefills in the window record.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers.rollout_closed_loop_hybrid import Driver as FullServerDriver
from benchmark.drivers.rollout_closed_loop_shared import Driver as InOrderDriver
from benchmark.lib import flops_parallel
from benchmark.lib import reference_falcon_h1 as reference

#: |server logprob - plain reference| over the generated tokens of the
#: three picked sequences (the longest, one whose sibling also completed,
#: the shortest prompt).  The server runs bf16 weights and bf16
#: activations, float32 recurrent state and decay, the chunked SSD form
#: with two B/C groups for the prompt (one to three fill chunks from a
#: carried state, conv tail and paged prefix) and the two kernels for
#: every decode step; the reference runs the same bf16 weights with
#: float32 activations, a sequential scan, whole-sequence attention,
#: "highest" precision.  What separates them is bf16 rounding of
#: activations over eight layers whose Mamba-2 branch adds 0.58 rms a
#: layer to a residual stream of 0.5-1.8 (a third to a half of it out of
#: the STATE), under logits of deviation 0.58.  The CONTROL is the same
#: reference with every matrix rounded to float8 (e4m3), the nearest
#: precision below the bfloat16 the configuration states; ``check`` runs
#: it through the same comparison in every run and it has to come out
#: NOT correct.  Readings on a v5e (my chip runs, PR 46; PERF.md section
#: 6 has every run's): the server 0.0247-0.0371 max and 0.00605-0.00647
#: mean over the three picks of thirteen runs on thirteen seeds; the
#: float8 control 0.244-0.295 max and 0.0566-0.0596 mean.  Each limit is
#: the geometric middle of its two readings, a factor of 2.4 to 3 of room
#: on both sides.
#:
#: A recurrent state CARRIED in bfloat16 reads 0.0014-0.017 max from the
#: float32 reference: under the server's own rounding, so it is NOT
#: refused (as in the hybrid cell); it is on the check line for the
#: record (``bf16_state``), and ``state_dtype`` holds the stated type.
LOGP_MAX_ABS = 0.09
LOGP_MEAN_ABS = 0.019

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
    cell's; counters, window record and check are this stack's."""

    def __init__(self, ctx):
        super().__init__(ctx)
        # a sliced vocabulary is a smaller vocabulary: prompts draw their
        # ids from the rows this chip holds
        self.run_hf = flops_parallel.as_run(ctx.config)
        self.hf = dict(self.hf, vocab_size=self.run_hf["vocab_size"])

    def _counters(self):
        c = FullServerDriver._counters(self)
        eng = self.engine
        c.update(
            rows_preempted=float(eng.preempted_total),
            pages_live=eng.pages_live,
            state_slots_live=eng.state_slots_live,
        )
        return c

    def measure(self, seconds: float) -> dict:
        record = FullServerDriver.measure(self, seconds)
        c0, c1 = self._snaps[-2], self._snaps[-1]
        counters = record["counters"]
        counters["rows_preempted"] = c1["rows_preempted"] - c0["rows_preempted"]
        for key in ("pages_live", "state_slots_live"):
            counters[key] = c1[key]  # at the window's last instant
        # what tells a reader that the record is this stack's: (layers,
        # B/C groups, state size, vocabulary rows)
        hf = self.run_hf
        counters["parallel_shape"] = [
            hf["num_hidden_layers"], hf["mamba_n_groups"], hf["mamba_d_state"],
            hf["vocab_size"],
        ]
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
            state_slots_live=counters["state_slots_live"],
            rows_preempted=counters["rows_preempted"],
            # the schedule is the same in every run, so these tell a slow
            # machine from a schedule that came out otherwise
            engine_wait_s=counters["device_s"],
            engine_host_s=counters["host_s"],
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
            "state_dtype": str(eng.ssm_state.dtype),
            # [layers, pages, kv heads, page, head]; [layers, slots, N, H P]:
            # every layer in BOTH
            "pool_shape": list(eng.k_pool.shape),
            "state_shape": list(eng.ssm_state.shape),
            "state_copies_total": eng.state_copies_total,
            "state_reprefills_total": eng.state_reprefills_total,
            "rows_preempted_total": eng.preempted_total,
        }
        win = self.window_done
        if not win:
            return False, dict(details, reason="no sequence completed in the window")
        # the longest sequence (the most fill chunks and the longest
        # context a decode step reads), the shortest prompt, and between
        # them one whose sibling also completed (pages shared and a state
        # copied among the siblings of a fill, or a late sibling's own
        # prefill)
        by_plen = sorted(win, key=lambda d: (d.prompt_len, d.k, d.i))
        longest = max(win, key=lambda d: (len(d.seq), d.k, d.i))
        middle = [d for d in by_plen[1:] if d is not longest] or by_plen
        shared = next(
            (d for d in middle if any(o.k == d.k and o.i != d.i for o in win)),
            middle[len(middle) // 2],
        )
        picks = [longest, shared, by_plen[0]]
        details["token_id_max"] = max(max(d.seq) for d in win)
        details["vocab_rows"] = int(eng.params["lm_head"]["w"].shape[1])
        params = eng.params
        del eng  # the last reference to pool and state slots, once stopped
        self._stop_server()  # frees them before the reference runs
        hf = self.run_hf
        fn = reference.make_token_logps(hf)
        # every sequence padded to the engine's longest: ONE shape to
        # compile the layer and the head (and again for each control)
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
        # every sequence of the window, not the picks alone: a state or a
        # page gone bad shows as a log-probability that is no number
        details["sequences_nonfinite"] = sum(
            not np.isfinite(
                np.asarray(d.logprobs, np.float32)[d.prompt_len - 1 :]
            ).all()
            for d in win
        )
        details["tolerance"] = {"max_abs": LOGP_MAX_ABS, "mean_abs": LOGP_MEAN_ABS}
        # the control: the SAME reference computed in the nearest
        # precision below the stated one, on the pick with most decode
        # steps, through the same comparison: it has to be refused.
        # Beside it, for the record, the recurrent state carried in bf16
        j = max(range(len(picks)), key=lambda i: picks[i].new_tokens)
        new = slice(picks[j].prompt_len - 1, None)
        for name, low in (("control", CONTROL), ("bf16_state", ("state", "bfloat16"))):
            low_fn = reference.make_token_logps(hf, low=low)
            got = reference.sequence_logps(low_fn, params, picks[j].seq, pad_to=pad_to)
            details[name] = dict(
                compare(got[new], refs[j]), what=f"{low[0]} in {low[1]}",
                new_tokens=picks[j].new_tokens,
            )
        layers = hf["num_hidden_layers"]
        ok = (
            details["sequences_nonfinite"] == 0
            and all(r["within"] for r in rows)
            and not details["control"]["within"]
            and details["paged"]
            and details["state_dtype"] == "float32"
            and details["pool_shape"][0] == details["state_shape"][0] == layers
            and details["token_id_max"] < details["vocab_rows"]
            and all(d.new_tokens == d.asked for d in win)
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and details["use_paged_kernel"] and not details["kernel_interpret"]
        return bool(ok), details


def build(ctx) -> Driver:
    return Driver(ctx)
