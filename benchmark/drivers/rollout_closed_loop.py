"""Driver ``rollout_closed_loop``: a rollout fleet's traffic against ONE
generation server, reached the way a rollout worker reaches it
(GenerationServerWorker <- GserverManager <- PartialRolloutManager), after
``chip_smoke.phase_serve`` (PR 21).

Closed loop: ``prompts_in_flight`` prompts are out at any time, each
sampled ``samples_per_prompt`` times with its own number of new tokens;
when every sample of a prompt is back, the next prompt is sent.
``PartialRolloutManager`` holds one ``gconfig``, so each sample goes
through a manager of its own (``generate_group(..., 1)``) over one shared
``GserverManagerClient``; the server has no tokenizer, hence no stop token,
so a sequence's length is exactly what its request asks for.

The window lasts exactly ``--seconds``.  ``seq_p90_s`` is the CLIENT's:
submit -> complete of the sequences whose reply arrived inside it.
``rollout_tok_per_s`` is the tokens GENERATED inside it: the benchmark
wraps the engine's ``step()`` (which returns the tokens it emitted) and
keeps a running total with the host clock's reading at each step that
emitted some.  A step hands over a whole decode chunk of every live row at
once, about a second's work, so the rate is taken from the first to the
last such step inside the window: all the tokens between them over all the
time between them, at most one chunk short of the window at either end.
The warm-up's paused rounds, whose token counts are known, settle whether
``step()`` counts a sequence's first token (today it does not: the prefill
makes it); where it does not, the client's count of the sequences submitted
between those two steps is added.  (Counted from the replies alone, a
window swings by a whole 2,048-token sequence with a reply that lands a
moment before or after its end, and holds tokens made before it opened.)
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
import gc
import json
import math
import threading
import time

import numpy as np

from benchmark.lib import lengths, reference
from benchmark.lib.program import model_config, point_roots_at

#: |server logprob - plain reference| over the generated tokens.  The
#: server runs bf16 weights AND bf16 activations through the paged kernel
#: and the KV cache; the reference runs the same bf16 weights with float32
#: activations, dense attention, no cache, "highest" precision.  What
#: separates them is bf16 rounding of activations over the layers, landing
#: on near-uniform logits: 0.0021 max / 0.0005 mean on a v5e (PR 21).  The
#: bounds sit ~10x above; a wrong page, mask, position or weight shows as
#: errors of 0.1-1, and serving in a lower precision than stated fails.
LOGP_MAX_ABS = 0.02
LOGP_MEAN_ABS = 0.005


@dataclasses.dataclass
class Done:
    k: int
    i: int
    prompt_len: int
    asked: int
    new_tokens: int
    t_submit: float
    t_done: float
    seq: list
    logprobs: list


class Driver:
    def __init__(self, ctx):
        import jax

        from areal_tpu.api.config import ModelAbstraction
        from areal_tpu.api.system_api import GenServerConfig, GserverManagerConfig
        from areal_tpu.base import constants, name_resolve, names
        from areal_tpu.system.generation_server import GenerationServerWorker
        from areal_tpu.system.gserver_manager import (
            GserverManager,
            GserverManagerClient,
        )

        self.ctx = ctx
        self.traffic = t = ctx.traffic
        self.hf = ctx.config["hf_config"]
        self.n_layers = ctx.config["roles"]["serve"]["num_hidden_layers"]
        t0 = time.perf_counter()
        point_roots_at(ctx.work_dir)
        expr, trial = "benchmark", f"rollout-{ctx.seed}"
        constants.set_experiment_trial_names(expr, trial)
        self._name_resolve = name_resolve

        self.errors = []

        def run(worker, cfg):
            try:
                worker.run(cfg)
            except BaseException as e:  # noqa: BLE001 - raised by _raise()
                self.errors.append(e)

        self.server = GenerationServerWorker()
        cfg = model_config(ctx.config, "serve")
        self.server_thread = threading.Thread(
            target=run,
            args=(
                self.server,
                GenServerConfig(
                    worker_name="gen_server_0",
                    model=ModelAbstraction(
                        "random", {"config": cfg, "seed": ctx.seed % (2**31 - 1)}
                    ),
                    tokenizer_path=None,
                    cache_mode="auto",
                    device_idx=0,
                    temperature=t["temperature"],
                    **t["engine"],
                ),
            ),
            daemon=True,
            name="gen_server_0",
        )
        self.server_thread.start()
        self.manager = GserverManager()
        self.manager_thread = threading.Thread(
            target=run,
            args=(self.manager, GserverManagerConfig(n_servers=1)),
            daemon=True,
            name="gserver_manager",
        )
        self._wait_key(names.gen_server(expr, trial, "gen_server_0"), "gen server")
        self.manager_thread.start()
        self._wait_key(names.gen_server_manager(expr, trial), "gserver manager")
        self.engine = self.server.engine
        self._count_steps()
        print(
            json.dumps(
                {
                    "event": "server_ready",
                    "seconds": time.perf_counter() - t0,
                    "layers": self.n_layers,
                }
            ),
            flush=True,
        )

        self.schedule_waits = []  # (perf_counter, seconds) of routing RPCs
        waits = self.schedule_waits

        class TimedManagerClient(GserverManagerClient):
            """The benchmark's span around the routing RPC."""

            def call(self, cmd, payload):
                tik = time.perf_counter()
                try:
                    return super().call(cmd, payload)
                finally:
                    tok = time.perf_counter()
                    if cmd == "schedule_request":
                        waits.append((tok, tok - tik))

        self.client = TimedManagerClient(expr, trial)
        self._server_addr = self.server.addr

        # the client side: one event loop in a thread of its own; every
        # blocking RPC of a sample runs on this pool, so it has to hold
        # every sequence in flight (asyncio's default pool holds
        # min(32, cores + 4) and would cap the batch from the client side)
        max_in_flight = t["prompts_in_flight"] * t["samples_per_prompt"]
        self.loop = asyncio.new_event_loop()
        self.loop.set_default_executor(
            concurrent.futures.ThreadPoolExecutor(
                max_workers=2 * max_in_flight + 8,
                thread_name_prefix="rollout-client",
            )
        )
        self.loop_thread = threading.Thread(
            target=self.loop.run_forever, daemon=True, name="client-loop"
        )
        self.loop_thread.start()
        self.done = []  # Done records, appended by the loop thread
        self.submits = []  # host clock of every sample's submission
        self.live_managers = set()
        self.next_prompt = 0
        self.stopping = False
        self.slot_futures = []
        self._jax = jax

    # -- plumbing ----------------------------------------------------------

    def _count_steps(self):
        """The benchmark's counter of tokens generated: ``engine.step()``
        returns the tokens it emitted; every step that emitted some leaves
        ``(host clock, running total)`` in ``self.harvests``."""
        step = self.engine.step
        self.harvests = harvests = []
        total = 0

        def counted_step():
            nonlocal total
            n = step()
            if n:
                total += n
                harvests.append((time.perf_counter(), total))
            return n

        self.engine.step = counted_step

    def _raise(self):
        if self.errors:
            raise RuntimeError("a serving worker failed") from self.errors[0]

    def _wait_key(self, key, what):
        deadline = time.monotonic() + 1100
        while time.monotonic() < deadline:
            if self.errors:
                raise RuntimeError(f"{what} failed to start") from self.errors[0]
            try:
                return self._name_resolve.wait(key, timeout=1)
            except TimeoutError:
                continue
        raise TimeoutError(f"{what} did not register")

    def _wait(self, futs, timeout):
        """Results of futures of the client loop; a failed worker is raised
        at once, not after every RPC of a dead server has timed out."""
        deadline = time.monotonic() + timeout
        pending = set(futs)
        while pending:
            self._raise()
            if time.monotonic() > deadline:
                raise TimeoutError(f"{len(pending)} requests did not come back")
            _, pending = concurrent.futures.wait(pending, timeout=0.25)
        return [fu.result() for fu in futs]

    async def _sample(self, k, i, prompt_ids, n_new, qid=None):
        from areal_tpu.api.model_api import GenerationHyperparameters
        from areal_tpu.system.partial_rollout import PartialRolloutManager

        prm = PartialRolloutManager(
            self.client,
            GenerationHyperparameters(
                max_new_tokens=n_new, min_new_tokens=n_new,
                temperature=self.traffic["temperature"],
            ),
            request_timeout=self.traffic["request_timeout"],
            max_rpc_retries=1,  # a lost reply is a failure here, not a retry
        )
        self.live_managers.add(prm)
        t_submit = time.perf_counter()
        self.submits.append(t_submit)
        try:
            out = await prm.generate_group(qid or f"p{k}s{i}", prompt_ids, 1)
        finally:
            self.live_managers.discard(prm)
            prm.close()
        return Done(
            k=k, i=i, prompt_len=len(prompt_ids), asked=n_new,
            new_tokens=len(out.seqs[0]) - len(prompt_ids),
            t_submit=t_submit, t_done=time.perf_counter(),
            seq=out.seqs[0], logprobs=out.logprobs[0],
        )

    async def _slot(self):
        vocab = self.hf["vocab_size"]
        while not self.stopping:
            k = self.next_prompt
            self.next_prompt += 1
            p = lengths.rollout_prompt(self.traffic, self.ctx.seed, vocab, k)

            async def one(i, n):
                self.done.append(await self._sample(k, i, p["prompt_ids"], n))

            await asyncio.gather(
                *(one(i, n) for i, n in enumerate(p["max_new_tokens"]))
            )

    def _server_rpc(self, cmd):
        from areal_tpu.system.generation_server import GenServerClient

        c = GenServerClient(self._server_addr, timeout=60)
        try:
            return c.call(cmd, {})
        finally:
            c.close()

    # -- warm-up -----------------------------------------------------------

    def _round(self, tag, prompt_lens, siblings, rng, new_tokens=2):
        """One warm-up round: pause the server, queue ``siblings`` samples
        of one prompt per length, resume.  The engine admits them in one
        step, so they prefill in ONE batch.  With one new token a row ends
        at its first sampled token; with two it is activated and decodes."""
        vocab = self.hf["vocab_size"]
        self._server_rpc("pause")
        futs = []
        for j, n in enumerate(prompt_lens):
            ids = rng.integers(3, vocab, n).tolist()
            for s in range(siblings):
                futs.append(
                    asyncio.run_coroutine_threadsafe(
                        self._sample(
                            -1, s, ids, new_tokens, qid=f"w{tag}p{j}s{s}"
                        ),
                        self.loop,
                    )
                )
        deadline = time.monotonic() + 60
        while self.engine.n_pending < len(futs) and time.monotonic() < deadline:
            self._raise()
            time.sleep(0.002)
        self._server_rpc("resume")
        self._wait(futs, 600)
        return len(futs), len(futs) * new_tokens

    def _fill_sweep(self):
        """Every small program the loop can meet, met once before it.

        A prefill batch compiles per ``(F_pad, C)``: C the bucket
        (32..prefill_chunk_tokens) of its longest piece, F_pad the next
        power of two of the prompts in it.  ``fill_shapes`` lists
        ``[prompts, C]`` pairs: the longest prompt gives C, the others share
        what is left of the chunk budget.  First-token sampling, the copy
        of a shared prompt's last page and the rows' activation compile per
        number of prompts and samples finishing their prefill together:
        ``sibling_rounds`` lists ``[prompts, samples of each]``."""
        warm = self.traffic["warm"]
        budget = self.traffic["engine"]["prefill_chunk_tokens"]
        rng = np.random.default_rng(self.ctx.seed % (2**32))
        sent = []  # (sequences, tokens asked for) of each round
        for f, c in warm["fill_shapes"]:
            longest = c if c < budget else (3 * budget) // 4
            rest = min(longest, (budget - longest) // (f - 1)) if f > 1 else 0
            sent.append(
                self._round(
                    len(sent), [longest] + [rest] * (f - 1), 1, rng, new_tokens=1
                )
            )
        for f, siblings in warm["sibling_rounds"]:
            sent.append(
                self._round(len(sent), [warm["sibling_prompt_len"]] * f, siblings, rng)
            )
        # every reply is back, so the counter at ``engine.step()`` holds
        # these rounds' tokens: all of them, or all but each sequence's first
        seqs, asked = (sum(x) for x in zip(*sent))
        counted = self.harvests[-1][1] if self.harvests else 0
        if counted not in (asked, asked - seqs):
            raise RuntimeError(
                f"engine.step() reported {counted} tokens for warm-up rounds "
                f"of {seqs} sequences and {asked} tokens: the benchmark's "
                "token counter cannot be trusted"
            )
        self.step_counts_first_token = counted == asked
        return len(sent)

    def warm(self):
        t0 = time.perf_counter()
        snap = self.ctx.clock.between(0, t0)
        rounds = self._fill_sweep()
        t1 = time.perf_counter()
        sweep = self.ctx.clock.between(t0, t1)
        # then the loop itself, until a fixed number of sequences is back:
        # the window opens on rows at mixed depths, at the same point of
        # the schedule in every run (a count and not a time, so that a run
        # that compiles here reaches the same point)
        self.slot_futures = [
            asyncio.run_coroutine_threadsafe(self._slot(), self.loop)
            for _ in range(self.traffic["prompts_in_flight"])
        ]
        want = self.traffic["warm"]["completions"]
        while len(self.done) < want:
            self._raise()
            for fu in self.slot_futures:
                if fu.done():
                    fu.result()  # a slot never ends by itself: raise its error
            time.sleep(0.05)
        t2 = time.perf_counter()
        loop = self.ctx.clock.between(t1, t2)
        print(
            json.dumps(
                {
                    "event": "warm", "programs_before": snap["compiles"],
                    "sweep_rounds": rounds, "sweep_s": t1 - t0,
                    "sweep_programs": sweep["compiles"],
                    "sweep_cache_hits": sweep["cache_hits"],
                    "loop_s": t2 - t1, "loop_programs": loop["compiles"],
                    "loop_compiled": loop["compiled"],
                    "loop_completions": len(self.done),
                    "step_counts_first_token": self.step_counts_first_token,
                }
            ),
            flush=True,
        )

    # -- the window --------------------------------------------------------

    def _counters(self):
        eng = self.engine
        split = eng.timing_split()
        digest = eng.slo_digests()["admission_wait_s"]
        return {
            **{k: float(v) for k, v in split.items()},
            "prefill_tokens": float(eng.prefill_tokens_total),
            "admission_counts": list(digest["counts"]),
            "admission_lo": digest["lo"],
            "admission_ratio": digest["ratio"],
        }

    def measure(self, seconds: float) -> dict:
        """The window opens now and closes ``seconds`` later, whatever is in
        flight."""
        c0 = self._counters()
        t0 = time.perf_counter()
        t1 = t0 + seconds
        while True:
            left = t1 - time.perf_counter()
            if left <= 0:
                break
            self._raise()
            time.sleep(min(left, 0.25))
        c1 = self._counters()
        in_flight = self.engine.n_inflight
        harvests = [h for h in list(self.harvests) if t0 <= h[0] <= t1]
        self._stop_traffic()
        if len(harvests) < 2:
            raise RuntimeError(
                f"the engine emitted tokens in {len(harvests)} steps of a "
                f"{seconds} s window: no rate can be taken"
            )
        (h0, n0), (h1, n1) = harvests[0], harvests[-1]
        emitted = n1 - n0
        if not self.step_counts_first_token:
            emitted += sum(h0 < t <= h1 for t in list(self.submits))
        win = [d for d in self.done if t0 <= d.t_done <= t1]
        self.window_done = win
        completed = sum(d.new_tokens for d in win)
        lat = sorted(d.t_done - d.t_submit for d in win)
        p90 = lat[max(0, math.ceil(0.9 * len(lat)) - 1)] if lat else float("nan")
        early = sum(d.new_tokens < d.asked for d in win)
        waits = [s for t, s in self.schedule_waits if t0 <= t <= t1]
        counts = [b - a for a, b in zip(c0["admission_counts"], c1["admission_counts"])]
        # the engine's counters cover the whole window, the token count
        # the time between its first and last emitting step
        whole = seconds / (h1 - h0)
        counters = {
            "window_s": seconds,
            "tokens_emitted": emitted * whole,
            "tokens_completed": completed,
            "sequences_completed": len(win),
            # sum over the window's new tokens of the context each attended
            # to (prompt + the tokens before it): known for the sequences
            # that completed in the window, and scaled from their tokens to
            # the tokens emitted in it
            "context_token_reads": (
                sum(
                    d.new_tokens * d.prompt_len
                    + d.new_tokens * (d.new_tokens - 1) // 2
                    for d in win
                )
                * (emitted * whole / completed if completed else 0.0)
            ),
            "decode_chunks": c1["chunks"] - c0["chunks"],
            "chunk_size": self.traffic["engine"]["chunk_size"],
            "host_s": c1["host_s"] - c0["host_s"],
            "device_s": c1["device_s"] - c0["device_s"],
            "fetch_s": c1["fetch_s"] - c0["fetch_s"],
            "prefill_tokens": c1["prefill_tokens"] - c0["prefill_tokens"],
            "schedule_wait_mean_s": sum(waits) / len(waits) if waits else None,
            "admission_counts": counts,
            "admission_lo": c1["admission_lo"],
            "admission_ratio": c1["admission_ratio"],
            "n_layers": self.n_layers,
        }
        return {
            "attempted": len(win),
            "failed": early,
            "end_to_end": {
                "rollout_tok_per_s": emitted / (h1 - h0),
                "seq_p90_s": p90,
            },
            "counters": counters,
            "gap_owner": "engine.step",
            "notes": {
                "sequences_completed": len(win),
                "seq_p90_samples_beyond": len(lat) - math.ceil(0.9 * len(lat)),
                "early_stops": early,
                "rows_in_flight_at_close": in_flight,
                "window_s": seconds,
                "emitting_steps": len(harvests),
                "rate_taken_over_s": h1 - h0,
                "tokens_emitted": emitted,
                "tokens_of_sequences_completed": completed,
            },
        }

    def _stop_traffic(self):
        """Sequences in flight at the window's end are cancelled: they are
        neither attempted nor failed."""
        self.stopping = True
        for fu in self.slot_futures:
            fu.cancel()
        for prm in list(self.live_managers):
            prm.close()

    # -- correctness, outside the window -----------------------------------

    def check(self):
        from areal_tpu.models import paged

        eng = self.engine
        details = {
            "paged": bool(eng.paged),
            "use_paged_kernel": bool(getattr(eng, "_use_paged_kernel", False)),
            "kernel_interpret": bool(paged.kernel_interpret()),
            "weight_dtype": str(self._jax.tree.leaves(eng.params)[0].dtype),
        }
        params = eng.params
        self._stop_server()  # frees the KV pool before the reference runs
        win = self.window_done
        if not win:
            return False, dict(details, reason="no sequence completed in the window")
        # the longest prompt (chunked prefill), the shortest, and between
        # them one whose sibling also completed (a prompt whose pages were
        # shared while both decoded)
        by_plen = sorted(win, key=lambda d: (d.prompt_len, d.k, d.i))
        middle = by_plen[1:-1] or by_plen
        shared = next(
            (d for d in middle if any(o.k == d.k and o.i != d.i for o in win)),
            middle[len(middle) // 2],
        )
        picks = [by_plen[-1], shared, by_plen[0]]
        fn = reference.make_token_logps(self.hf)
        rows = []
        for d in picks:
            ref = reference.sequence_logps(fn, params, d.seq)
            got = np.asarray(d.logprobs, np.float32)[d.prompt_len - 1 :]
            want = ref[d.prompt_len - 1 :]
            diff = np.abs(got - want)
            rows.append(
                {
                    "prompt_len": d.prompt_len, "new_tokens": d.new_tokens,
                    "max_abs_diff": float(diff.max()),
                    "mean_abs_diff": float(diff.mean()),
                    "mean_logp": float(want.mean()),
                }
            )
        details["reference"] = rows
        details["tolerance"] = {"max_abs": LOGP_MAX_ABS, "mean_abs": LOGP_MEAN_ABS}
        ok = (
            all(r["max_abs_diff"] <= LOGP_MAX_ABS for r in rows)
            and all(r["mean_abs_diff"] <= LOGP_MEAN_ABS for r in rows)
            and details["paged"]
            and all(d.new_tokens == d.asked for d in win)
        )
        if self.ctx.device_kind != "cpu":
            ok = ok and details["use_paged_kernel"] and not details["kernel_interpret"]
        return bool(ok), details

    def _stop_server(self):
        if self.server is None:
            return
        self.server.exit()
        self.manager.exit()
        self.server_thread.join(timeout=60)
        self.manager_thread.join(timeout=60)
        self.server.engine = None
        self.engine = None
        self.server = None
        gc.collect()

    def close(self):
        if not self.stopping:
            self._stop_traffic()
        self._stop_server()
        self.client.close()
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._name_resolve.reset()


def build(ctx) -> Driver:
    return Driver(ctx)
