"""Driver ``rollout_closed_loop_shared``: ``rollout_closed_loop_hybrid`` for
a decoder-hybrid-decoder stack (``phi4flash``: Phi-4-mini-flash-reasoning):
Mamba-1 state slots, a window pool, and a pool of whole-context pages with
ONE layer that eight layers read.  The server, the client side, the
warm-up rounds, the window and the token count are that driver's, line for
line.  What differs:

* **the loop sends in the stream's order**: the same 96 samples out, each
  replaced when it is back by the stream's next, but a sample is sent only
  once the engine holds the one before it, so the engine's queue IS the
  stream (prompt 0's samples, then prompt 1's, ...) in every run.  The
  parent's workers send side by side, each over a connection of its own,
  and the server reads what arrived during an engine step in whatever
  order its socket hands it over: the first 96 come in scrambled, so WHICH
  siblings stand together in the queue, and so share a fill, is drawn anew
  in every run.  That costs the other full cells little (prompts of 512
  tokens, or a prefix cache); here a sibling that misses its prompt's fill
  prefills 2-8k tokens again, 0.2-0.8 s of a 50 s window each, and the
  runs' prefill totals spread by 4% (PERF.md section 6, PR 42).  The
  engine admits first come first served and decides by steps, never by the
  clock, and a queue of ~30 always stands, so with the arrivals in order
  the schedule is the same in every run and for every ``--seed``, as the
  traffic file's one draw of lengths means it to be;
* **``check``**: the plain reference is ``lib/reference_phi4flash`` (the
  recurrence as a scan over tokens, whole-sequence attention under the
  masks with the two softmax maps written out pair by pair, no cache, no
  pages, no slots).  This stack has no router, so nothing is followed: the
  two are apart by rounding alone.  FIVE controls that the same comparison
  has to refuse: every matrix in float8 (the nearest precision below the
  stated bfloat16), and the reference making each of four mistakes (the
  window left off, a gated memory unit fed its own input, a cross layer
  attending K and V of its own input, the second softmax map's weight at
  0); nothing is on the line "for the record" only (``ON_RECORD`` says
  why);
* the counters the new readers take: the page rule's, the state slots',
  and the cached positions a decode step reads by layer kind (for
  ``lib/flops_sambay``).
"""

from __future__ import annotations

import asyncio

import numpy as np

from benchmark.drivers.rollout_closed_loop_hybrid import Driver as FullServerDriver
from benchmark.lib import flops_sambay
from benchmark.lib import reference_phi4flash as reference

#: |server logprob - plain reference| over the generated tokens of the
#: three picked sequences (each longer than the window of 512, so each
#: crosses it; the longest past 8k).  The server runs bf16 weights and bf16
#: activations, float32 recurrent state, paged KV in two pools with a
#: differential pair as one head of 128, the Mamba-1 kernel for every
#: decode step; the reference runs the same bf16 weights with float32
#: activations, a scan over tokens, whole-sequence attention under the
#: masks, "highest" precision.  What separates them is bf16 rounding of
#: activations over 32 layers (the other rollout cells have 5-10), landing
#: on logits of deviation ~2.5 (theirs: 0.6; a sampled token's mean
#: log-probability is -6 where uniform would be -12.2): the other rollout
#: cells' 0.02 / 0.005 do not carry over.  Readings on a v5e (PERF.md
#: section 6, PR 42): the server 0.091-0.181 max / 0.0199-0.0322 mean
#: (fifteen runs, 45 sequences of 272-1,338 new tokens at 2.4k-9.1k of
#: context); the float8 CONTROL 0.73-1.32 / 0.143-0.233: the same
#: reference with every matrix rounded to float8 (e4m3), the nearest
#: precision below the bfloat16 the configuration states.  The limits
#: lie between: 1.9 and 1.9 times above the server's largest, 2.1 and
#: 2.4 times below the control's smallest.  ``check``
#: runs the control through the same comparison in every run and it has
#: to come out NOT correct.  A wrong page, mask, state slot, memory or
#: shared layer shows as 2-8 (the four mistakes below).
LOGP_MAX_ABS = 0.35
LOGP_MEAN_ABS = 0.06

#: what each control changes, by name on the check line: each has to come
#: out NOT within the limits
CONTROLS = {
    "control": dict(low=("weights", "float8_e4m3fn")),
    "window_off": dict(wrong="window_off"),
    "gmu_own_input": dict(wrong="gmu_own_input"),
    "cross_own_kv": dict(wrong="cross_own_kv"),
    "lam_zero": dict(wrong="lam_zero"),
}

#: mistakes on the check line that no limit on log-probabilities refuses
#: at random weights: NONE in this cell.  ISSUE 42 expected the second
#: softmax map's weight at 0 (``lam_zero``) to be one (over thousands of
#: random keys both maps would be near a plain mean of the values, and the
#: mistake a rescaling that the pair's RMS norm removes); on the chip it
#: moves the log-probabilities by 6.7 max / 1.4 mean (my chip run, PR 42),
#: twenty times the limits, so it is a control like the others
ON_RECORD = {}


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
    def __init__(self, ctx):
        super().__init__(ctx)
        # what the engine has been handed, by request id: ``submit`` is
        # called by the server's thread once a poll has read the request
        submit = self.engine.submit
        self.arrived = arrived = set()

        def noted_submit(req):
            qid = submit(req)
            arrived.add(req.qid)
            return qid

        self.engine.submit = noted_submit
        self._in_order = None  # the loop's lock: one sample is sent at a time
        # the samples that go out before the server runs: all that are out
        self._opening = (
            self.traffic["prompts_in_flight"] * self.traffic["samples_per_prompt"]
        )

    # -- the loop: the stream's order is the engine's queue's order --------

    async def _send_next(self):
        """The stream's next sample, sent, and held by the engine before
        this returns (the server reads its socket once an engine step, so
        that is up to a step's time: with ~30 requests queued nothing
        waits for it).  The first 96 go to a PAUSED server, which polls
        every 10 ms and admits nothing, so they stand in the queue in the
        stream's order when it resumes, as one wave."""
        k, i, ids, n = self._next_sample()
        task = asyncio.ensure_future(self._sample(k, i, ids, n))
        qid = f"p{k}s{i}-0"  # generate_group's name for its one member
        while qid not in self.arrived and not task.done():
            await asyncio.sleep(0.002)
        if self._opening:
            self._opening -= 1
            if not self._opening:
                await asyncio.to_thread(self._server_rpc, "resume")
        return task

    async def _slot(self):
        if self._in_order is None:  # the first of the slots opens the loop
            self._in_order = asyncio.Lock()
            async with self._in_order:
                await asyncio.to_thread(self._server_rpc, "pause")

        async def worker():
            while not self.stopping:
                async with self._in_order:  # waiters are served in turn
                    task = await self._send_next()
                self.done.append(await task)

        await asyncio.gather(
            *(worker() for _ in range(self.traffic["samples_per_prompt"]))
        )

    def _counters(self):
        c = super()._counters()
        eng = self.engine
        c.update(
            window_pages_allocated=float(eng._win.allocated_total),
            window_pages_released=float(eng.window_pages_released),
            window_pages_freed_behind=float(eng._win.freed_behind_total),
            rows_preempted=float(eng.preempted_total),
            window_pages_live=eng.window_pages_live,
            global_pages_live=eng.global_pages_live,
            state_slots_live=eng.state_slots_live,
        )
        return c

    def measure(self, seconds: float) -> dict:
        record = super().measure(seconds)
        c0, c1 = self._snaps[-2], self._snaps[-1]
        counters = record["counters"]
        for key in (
            "window_pages_allocated", "window_pages_released",
            "window_pages_freed_behind", "rows_preempted",
        ):
            counters[key] = c1[key] - c0[key]
        for key in ("window_pages_live", "global_pages_live", "state_slots_live"):
            counters[key] = c1[key]  # at the window's last instant
        hf = flops_sambay.as_run(self.ctx.config)
        # what tells a reader that the record is this stack's
        counters["shared_shape"] = [
            flops_sambay.global_readers(hf), flops_sambay.counts(hf)["window"],
        ]
        # sum over the window's new tokens of the cached positions a
        # WINDOW layer read for each, scaled like context_token_reads
        win = self.window_done
        done = sum(d.new_tokens for d in win)
        scale = counters["tokens_emitted"] / done if done else 0.0
        counters["window_token_reads"] = scale * sum(
            flops_sambay.window_reads(hf, d.prompt_len + t)
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
            state_slots_live=counters["state_slots_live"],
            rows_preempted=counters["rows_preempted"],
            window_row_pages_max=self.engine._win.row_pages_max,
            # the schedule is the same in every run, so these tell a slow
            # machine (fewer chunks, more seconds waited a chunk) from a
            # schedule that came out otherwise (other prefill_tokens)
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
            # [layers, pages, heads, page, width]: ONE layer of
            # whole-context pages, which global_readers layers read
            "pool_shapes": [list(eng.k_pool.shape), list(eng.win_k_pool.shape)],
            "global_readers": int(eng.cfg.n_global_readers),
            "window_pages_released_total": eng.window_pages_released,
            "window_row_pages_max": eng._win.row_pages_max,
            "state_copies_total": eng.state_copies_total,
            "state_reprefills_total": eng.state_reprefills_total,
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
        params = eng.params
        window = eng.cfg.sliding_window
        del eng  # the last reference to pools and state slots, once stopped
        self._stop_server()  # frees them before the reference runs
        hf = flops_sambay.as_run(self.ctx.config)
        fn = reference.make_token_logps(hf)
        # every sequence padded to the engine's longest: ONE shape to
        # compile a layer kind (five kinds and the head, and again for each
        # kind a control changes)
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
        # the controls: the SAME reference in float8 and with each mistake,
        # on the pick with most decode steps; each goes through the same
        # comparison as the server's log-probabilities, and those of
        # CONTROLS have to be refused
        j = max(range(len(picks)), key=lambda i: picks[i].new_tokens)
        new = slice(picks[j].prompt_len - 1, None)
        for name, how in {**CONTROLS, **ON_RECORD}.items():
            low_fn = reference.make_token_logps(hf, **how)
            got = reference.sequence_logps(low_fn, params, picks[j].seq, pad_to=pad_to)
            details[name] = dict(
                compare(got[new], refs[j]), new_tokens=picks[j].new_tokens
            )
        details["on_record"] = sorted(ON_RECORD)
        ok = (
            details["sequences_nonfinite"] == 0
            and all(r["within"] for r in rows)
            and not any(details[name]["within"] for name in CONTROLS)
            and details["paged"]
            and details["state_dtype"] == "float32"
            and details["pool_shapes"][0][0] == 1
            and all(d.prompt_len > window for d in picks)
            and details["window_pages_released_total"] > 0
            and all(d.new_tokens == d.asked for d in win)
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and details["use_paged_kernel"] and not details["kernel_interpret"]
        return bool(ok), details


def build(ctx) -> Driver:
    return Driver(ctx)
