"""Benchmark driver: one JSON line with the headline metric.

Requires a TPU: ``main()`` exits non-zero on any other backend, a section
that fails or times out makes the exit code non-zero after the summary is
printed, and a multi-chip section on too few chips is reported as not
run.  The section functions stay callable at tiny shapes from the CPU
tests (tests/engine/test_bench_sweep.py); nothing they print there is a
device number.  One process owns the chip: bench.py starts no children.

Headline: **effective RL throughput per peak-TFLOP** — trained tokens per
second of a full generate->train step on one chip, normalized by the chip's
peak bf16 TFLOP/s, against a baseline DERIVED from the reference system's
published end-to-end numbers (not an assumed constant):

    reference async 1.5B run: 1000 PPO steps in 14.8 h on 16 nodes x 8 H800
    (reference: blog/AReaL_v0_3.md:109-113), batch 512 prompts x 16 answers
    = 8192 sequences/step (reference: benchmark/verl_.../README.md:40-46).
    Mean total sequence length is not published; assumed 8000 tokens
    (~1k prompt + ~7k response, consistent with the 31k cap and <5%
    truncation, reference: blog/AReaL_v0_2.md:88).  That gives
    8192*8000 / 53.28 s / 128 GPUs / 989 TFLOP/s = 9.72 tok/s per TFLOP/s.

Components also measured (in `detail`): train-step MFU (param-only and
attention-corrected, plus an 8k-context row — hardware efficiency holds
~0.40-0.43 attn-corrected from 2k to 8k on v5e), decode/prefill
throughput at 0.5B (batch 32 and 64) and at the Qwen2.5-1.5B architecture,
interruptible-vs-drain weight-update throughput (the reference's +12-17%
mechanism, blog/AReaL_v0_3.md:125), and publish block/commit latency
(reference budget <3 s, blog/AReaL_v0_2.md:52-54).

Round 5 moved the headline to the RECIPE REGIME: the effective row runs
~8k-token sequences (prompt 7.5k + 512 generated) through the PAGED
serving engine, so the baseline's assumed 8000-token mean cancels instead
of flattering a short-sequence number; `detail` adds the paged-vs-dense
decode A/B at 2k-32k context (1.5B arch) with the 16x16k capacity row,
and the chunked-prefill decode-stall A/B.

Round 6 adds the train-MFU lever sweep: `train_remat_moment_sweep` runs
{remat_policy x optimizer-moment dtype} cells at the bench batch (graduated
remat presets from models/remat.py x bf16/factored Adam moments from
OptimizerConfig), reporting per cell tok/s/TFLOP and XLA's peak-temp
allocation, with would-OOM cells reported from the memory analysis instead
of crashed.

Round 7 measures the deep-pipelined serving hot path: the generation
section reports `engine_over_jit` (engine decode vs the isolated
decode_chunk jit loop at the same shapes — the 0.78x gap VERDICT r5 #5
flagged), a `ring_ab` sub-row sweeping the engine's `pipeline_depth`
(K in-flight chunks + dispatch-time async output fetch) with the
host/device/fetch split per K, and a `prefill_ab` section attributing the
round-5 prefill regression (jit ceiling vs engine dense admit vs paged
chunked admit, repeated so run-to-run variance is visible as spread).  The
decode A/B additionally derives a `PagedDispatchTable` (engine/dispatch.py)
from its own dense / paged rows, and the whole round's diffable numbers are
duplicated into a compact top-level `summary` object so BENCH_rNN.json's
`parsed` field carries them even when `detail` is huge.

Round 8 measures the cross-request radix prefix cache
(engine/prefix_cache.py): a `prefix_cache_ab` section replays multi-turn
conversations — every turn re-sends the WHOLE growing conversation under a
fresh qid, the shape of the reference's multi-turn agent loops over
SGLang's radix cache — with the cache on vs off, reporting the
cached-token fraction (prompt tokens served from cache instead of
re-prefilled), suffix-only prefill work, and end-to-end replay tok/s.

Caveats stated where measured: ONE chip, sync gen+train (the reference's
number is 128-GPU async); 1.5B uses the true Qwen2.5-1.5B architecture
with random weights (zero-egress image has no checkpoint; the HF importer
is parity-tested separately); the 1.5B fp32-adam train state (21 GB)
exceeds one v5e, so the effective row keeps the 0.5B model (the recipe
trains 1.5B on an 8-chip FSDP mesh — dryrun-validated).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

# ---- derived reference baseline (see module docstring) --------------------
REF_SEQS_PER_STEP = 512 * 16
REF_MEAN_SEQ_LEN_ASSUMED = 8000
REF_STEP_SECONDS = 14.8 * 3600 / 1000
REF_N_GPUS = 16 * 8
REF_GPU_PEAK_TFLOPS = 989  # H800 dense bf16
REF_TOK_PER_SEC_PER_TFLOP = (
    REF_SEQS_PER_STEP
    * REF_MEAN_SEQ_LEN_ASSUMED
    / REF_STEP_SECONDS
    / REF_N_GPUS
    / REF_GPU_PEAK_TFLOPS
)

def peak_flops(device) -> float:
    """Peak bf16 FLOP/s of one chip — the ONE table lives in
    ``areal_tpu.base.monitor`` (with its source); an unknown TPU kind
    raises there, and a non-TPU device has no peak (0.0)."""
    from areal_tpu.base.monitor import device_peak_flops

    return device_peak_flops(device)


def _per_tflop(toks_per_sec: float, peak_tf: float):
    """tok/s per peak TFLOP/s, or None where the device has no published
    peak (the section functions also run at tiny shapes on the CPU
    tests; a CPU rate is never written under a device metric)."""
    return round(toks_per_sec / peak_tf, 3) if peak_tf else None


def param_count(params) -> int:
    import jax

    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


def bench_gen_cache_len(prompt_len, max_new):
    """Smallest 128-multiple covering the bench sequences.  Round-up to a
    power of two looked harmless but was measured catastrophic: a 2048-slot
    cache for 1032-token rows put B=64 under memory pressure (lazy
    execution keeps >1 donated cache generation alive) and decode fell to
    2.3k tok/s; right-sized 1152 slots reach 7.2k on the same chip."""
    n = prompt_len + max_new + 8
    return -(-n // 128) * 128


def make_engine(cfg, params, n_reqs, prompt_len, max_new, chunk=128, **kw):
    from areal_tpu.engine.inference_server import ContinuousBatchingEngine

    return ContinuousBatchingEngine(
        cfg,
        params,
        max_batch=n_reqs,
        kv_cache_len=bench_gen_cache_len(prompt_len, max_new),
        chunk_size=chunk,
        **kw,
    )


def submit_wave(
    eng, cfg, n_reqs, prompt_len, max_new, tag, lens=None, greedy=False
):
    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )

    import zlib

    # crc32, not hash(): str hashes are salted per interpreter launch and
    # would make the prompt stream differ across bench runs
    rng = np.random.default_rng(zlib.crc32(tag.encode()))
    qids = []
    for i in range(n_reqs):
        ids = rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
        mn = int(lens[i]) if lens is not None else max_new
        qid = f"{tag}{i}"
        qids.append(qid)
        eng.submit(
            APIGenerateInput(
                qid=qid,
                prompt_ids=ids,
                input_ids=ids,
                gconfig=GenerationHyperparameters(
                    max_new_tokens=mn,
                    **({"greedy": True} if greedy
                       else {"temperature": 1.0}),
                ),
            )
        )
    return qids


def lcp_divergence(ref_streams, got_streams):
    """Greedy divergence between two {qid: tokens} stream maps:
    ``1 - (longest-common-prefix tokens / reference tokens)`` — one
    early flip charges the whole tail (the conservative definition).
    Returns ``(rate, diverged_request_count)``.  THE quality-gate
    statistic of ``bench_kv_quant_ab``; the tier-1 divergence pin
    (tests/engine/test_kv_quant.py) imports this same function so the
    asserted bar can never drift from what the bench reports."""
    total = matched = diverged = 0
    for qid, ref in ref_streams.items():
        got = got_streams[qid]
        lcp = 0
        for a, b in zip(ref, got):
            if a != b:
                break
            lcp += 1
        total += len(ref)
        matched += lcp
        diverged += int(lcp < max(len(ref), len(got)))
    return round(1.0 - matched / max(total, 1), 4), diverged


def drain(eng):
    n = 0
    while eng.has_work:
        n += eng.step()
    eng.drain_results()
    return n


def _split_fracs(split):
    attributed = max(
        split["host_s"] + split["device_s"] + split["fetch_s"], 1e-9
    )
    return {
        "host_s": round(split["host_s"], 4),
        "device_s": round(split["device_s"], 4),
        "fetch_s": round(split["fetch_s"], 4),
        "chunks": int(split["chunks"]),
        "host_frac": round(split["host_s"] / attributed, 3),
        "device_frac": round(split["device_s"] / attributed, 3),
        "fetch_frac": round(split["fetch_s"] / attributed, 3),
    }


def _jit_decode_rate(cfg, params, B, L, S, W=128):
    """Isolated ``decode_chunk`` jit-loop throughput (tok/s) at the
    engine's exact shapes and sampling — the engine-overhead-free ceiling
    that ``engine_over_jit`` divides by (VERDICT r5 #5: the engine ran at
    ~0.78x of this and nobody could say which overhead ate the rest)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.engine.sampling import SamplingParams, sample_logits
    from areal_tpu.models.transformer import KVCache, decode_chunk

    sp = SamplingParams()  # the engine's default sampler

    def sample(logits, rng):
        return sample_logits(logits, rng, sp)

    def no_stop(toks):
        return jnp.zeros_like(toks, bool)

    dense_jit = jax.jit(
        decode_chunk,
        static_argnames=(
            "cfg", "chunk_size", "sample_fn", "stop_fn", "attn_len"
        ),
        donate_argnums=(2,),
    )
    key = jax.random.PRNGKey(0)
    kd = jax.random.normal(
        key,
        (cfg.n_layers, B, cfg.n_kv_heads, S, cfg.head_dim),
        jnp.bfloat16,
    ) * 0.05
    cache = KVCache(k=kd, v=kd + 0.0, lengths=jnp.full((B,), L, jnp.int32))
    cur = jnp.full((B,), 7, jnp.int32)
    active = jnp.ones((B,), bool)
    budgets = jnp.full((B,), 10_000, jnp.int32)
    rng = jax.random.PRNGKey(1)
    times, cur_h = [], cur
    for _ in range(4):
        t0 = time.perf_counter()
        cache, out_t, _, _, _, _, budgets, rng = dense_jit(
            params, cfg, cache, cur_h, active, budgets, rng,
            chunk_size=W, sample_fn=sample, stop_fn=no_stop, attn_len=S,
        )
        # route the sampled tokens through the host like the engine does
        cur_h = jnp.asarray(np.asarray(out_t[:, -1]))
        times.append(time.perf_counter() - t0)
    del cache, kd
    return B * W / min(times[2:])


def bench_generation(
    cfg, params, n_reqs, prompt_len=512, max_new=512,
    pipeline_depth=2, ring_ab=(), jit_ratio=False,
):
    """Continuous-batching throughput on one chip: batched prefill tok/s
    and sustained decode tok/s under a ``pipeline_depth``-deep in-flight
    chunk ring.  ``jit_ratio`` adds the isolated decode_chunk loop at the
    same shapes and the engine/jit ratio; ``ring_ab`` sweeps pipeline
    depths (shorter waves, compiles shared) reporting tok/s + the
    host/device/fetch split per K — the fetch_frac column is the direct
    readout of whether the dispatch-time async output copy is hiding the
    device->host (PCIe) fetch.  The engine is dropped before returning so its KV cache
    (and its reference to ``params``) frees promptly."""
    eng = make_engine(
        cfg, params, n_reqs, prompt_len, max_new,
        pipeline_depth=pipeline_depth,
    )
    # warmup compiles every attention bucket the timed run touches
    submit_wave(eng, cfg, n_reqs, prompt_len, max_new, "w")
    drain(eng)
    submit_wave(eng, cfg, n_reqs, prompt_len, max_new, "t")
    t0 = time.perf_counter()
    eng._admit()
    int(np.asarray(eng.cache.lengths)[0])  # force prefill completion
    t_prefill = time.perf_counter() - t0
    # zero the timing counters so the split covers ONLY the timed decode
    # phase (warmup compiles + admission would otherwise dominate host_s)
    eng._phases.reset()
    eng.chunks_total = 0
    t0 = time.perf_counter()
    n_decoded = drain(eng)
    t_decode = time.perf_counter() - t0
    split = eng.timing_split()
    fetch_overlap = {
        "async_fetches": int(eng.async_fetches_total),
        "ready_at_harvest": int(eng.fetch_ready_total),
    }
    del eng
    out = {
        "prefill_toks_per_sec": round(n_reqs * prompt_len / t_prefill, 1),
        "decode_toks_per_sec": round(n_decoded / t_decode, 1),
        "batch": n_reqs,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new,
        "pipeline_depth": pipeline_depth,
        # decode-loop time attribution (engine-vs-jit gap): host
        # bookkeeping vs blocked-on-device vs output fetch (PCIe)
        "decode_split": _split_fracs(split),
        "fetch_overlap": fetch_overlap,
    }
    if jit_ratio:
        S = bench_gen_cache_len(prompt_len, max_new)
        jit_rate = _jit_decode_rate(cfg, params, n_reqs, prompt_len, S)
        out["jit_decode_toks_per_sec"] = round(jit_rate, 1)
        out["engine_over_jit"] = round(
            out["decode_toks_per_sec"] / max(jit_rate, 1e-9), 3
        )
    for K in ring_ab:
        # shorter waves; every attention bucket is already compiled by
        # the main run (lengths pass through the same power-of-two
        # buckets while growing), so each K pays only its own decode
        eng = make_engine(
            cfg, params, n_reqs, prompt_len, max_new, pipeline_depth=K
        )
        ab_new = max_new // 2
        submit_wave(eng, cfg, n_reqs, prompt_len, ab_new, f"rk{K}")
        eng._admit()
        int(np.asarray(eng.cache.lengths)[0])
        eng._phases.reset()
        eng.chunks_total = 0
        t0 = time.perf_counter()
        n = drain(eng)
        dt = time.perf_counter() - t0
        ksplit = _split_fracs(eng.timing_split())
        out.setdefault("ring_ab", {})[f"k{K}"] = {
            "decode_toks_per_sec": round(n / dt, 1),
            "host_frac": ksplit["host_frac"],
            "device_frac": ksplit["device_frac"],
            "fetch_frac": ksplit["fetch_frac"],
            "fetch_ready_frac": round(
                eng.fetch_ready_total / max(eng.chunks_total, 1), 3
            ),
        }
        del eng
    return out


def bench_trace_overhead_ab(
    cfg, params, n_reqs=32, prompt_len=256, max_new=256, repeats=2,
):
    """Flight-recorder overhead A/B: sustained decode tok/s with tracing
    off / sampled (the production default rate) / always-on.  The claim
    the acceptance bar tracks is "sampled tracing costs < 2% decode
    tok/s vs tracing-off" — measured here, machine-parseable, and
    diffable across rounds like the other A/Bs.  Each arm rebuilds the
    engine under a fresh tracer (the engine binds the process tracer at
    construction); the warmup wave pre-compiles every attention bucket,
    and each arm reports the best of ``repeats`` timed waves (decode is
    deterministic; the variance is host noise)."""
    from areal_tpu.observability import tracing

    arms = {
        "off": tracing.TraceConfig(enabled=False),
        "sampled": tracing.TraceConfig(),  # the production default rate
        "always": tracing.TraceConfig(sample_rate=1.0),
    }
    prev = tracing.get_tracer()
    out = {}
    try:
        for arm, tcfg in arms.items():
            tracing.set_tracer(
                tracing.Tracer(tcfg, worker=f"bench-{arm}")
            )
            eng = make_engine(cfg, params, n_reqs, prompt_len, max_new)
            submit_wave(eng, cfg, n_reqs, prompt_len, max_new, f"tow{arm}")
            drain(eng)  # warm: compiles shared across arms' shapes
            best = 0.0
            for r in range(repeats):
                submit_wave(
                    eng, cfg, n_reqs, prompt_len, max_new, f"tot{arm}{r}"
                )
                eng._admit()
                int(np.asarray(eng.cache.lengths)[0])  # prefill done
                t0 = time.perf_counter()
                n = drain(eng)
                best = max(best, n / (time.perf_counter() - t0))
            out[arm] = {
                "decode_toks_per_sec": round(best, 1),
                "sample_rate": (
                    0.0 if not tcfg.enabled else tcfg.sample_rate
                ),
            }
            del eng
    finally:
        tracing.set_tracer(prev)
    off = out["off"]["decode_toks_per_sec"]
    for arm in ("sampled", "always"):
        out[arm]["overhead_frac_vs_off"] = round(
            1.0 - out[arm]["decode_toks_per_sec"] / max(off, 1e-9), 4
        )
    return out


def bench_obs_ledger_report(
    cfg, params, n_reqs=32, prompt_len=256, max_new=256, repeats=2,
):
    """HBM-ledger + recompile-sentinel report: the observability
    acceptance numbers in one diffable dict.

    * ledger-on vs ledger-off decode tok/s (same warmup-wave +
      best-of-repeats protocol as ``bench_trace_overhead_ab``) with the
      <2% overhead bar tracked as ``overhead_frac_vs_off``;
    * per-subsystem ledger bytes + peaks under the live decode wave,
      and the reconciliation verdict against the allocator's own
      in-use bytes (vacuous on backends without memory_stats — the
      CPU smoke still proves the plumbing);
    * steady-state sentinel: the armed guard sees ZERO fresh compiles
      across the timed steady-shape decode waves, then >=1 attributed
      fire after a FORCED cache-bucket change (a second engine with a
      different KV bucket against the same module-level jits);
    * leak audit: ``engine.close()`` returns no leaks and the ledger
      reads back to the zero baseline."""
    from areal_tpu.base.monitor import device_memory_stats
    from areal_tpu.engine import inference_server as eng_mod
    from areal_tpu.observability.compile_watch import CompileWatch
    from areal_tpu.observability.hbm_ledger import HbmLedger
    from areal_tpu.observability.registry import MetricsRegistry

    out = {"overhead_bar_frac": 0.02}
    for arm in ("off", "on"):
        led = HbmLedger(enabled=(arm == "on"))
        eng = make_engine(
            cfg, params, n_reqs, prompt_len, max_new, hbm_ledger=led
        )
        watch = reg = None
        if arm == "on":
            reg = MetricsRegistry()
            watch = CompileWatch(
                registry=reg, quiet_after_steps=1, monitoring=False
            )
            sig = (
                f"cache_len={eng.kv_cache_len},chunk={eng.chunk_size},"
                f"batch={eng.max_batch}"
            )
            for fn_name, fn in (
                ("decode_chunk", eng_mod._decode_chunk),
                ("admit_rows", eng_mod._admit_rows),
                ("sample_rows", eng_mod._sample_rows),
            ):
                watch.watch(fn_name, fn, signature=lambda s=sig: s)
        submit_wave(eng, cfg, n_reqs, prompt_len, max_new, f"olw{arm}")
        drain(eng)  # warm: every bucket this arm will touch is compiled
        if watch is not None:
            watch.poll()  # absorb the warmup compiles, then declare
            watch.note_step(1)  # the loop steady — the guard is armed
        best = 0.0
        for r in range(repeats):
            submit_wave(
                eng, cfg, n_reqs, prompt_len, max_new, f"olt{arm}{r}"
            )
            eng._admit()
            int(np.asarray(eng.cache.lengths)[0])  # prefill done
            t0 = time.perf_counter()
            n = drain(eng)
            best = max(best, n / (time.perf_counter() - t0))
        out[arm] = {"decode_toks_per_sec": round(best, 1)}
        if arm == "on":
            # steady decode over warmed shapes: the armed sentinel must
            # stay silent (any count here is an acceptance failure)
            steady = watch.poll()
            out[arm]["steady_compiles"] = int(sum(steady.values()))
            # ledger attribution while the engine is live, + the
            # reconcile verdict against the allocator's own number
            snap = led.snapshot()
            out[arm]["hbm_bytes"] = {
                k: int(v) for k, v in snap.items() if v
            }
            out[arm]["hbm_peak_bytes"] = {
                k: int(v) for k, v in led.watermarks().items() if v
            }
            gauges = device_memory_stats()
            in_use = [
                v for k, v in gauges.items()
                if k.endswith("/hbm_in_use_gb")
            ]
            rec = led.reconcile(
                reg, int(sum(in_use) * 1e9) if in_use else None
            )
            out[arm]["reconcile"] = {
                "ok": rec["ok"],
                "vacuous": rec["vacuous"],
                "drift_gb": rec["drift_gb"],
            }
            # forced bucket change: a second engine with a DIFFERENT
            # KV bucket drives fresh compiles of the same module-level
            # jits -> the armed sentinel must fire (>=1) and attribute
            forced = make_engine(
                cfg, params, n_reqs, prompt_len + 128, 8,
                hbm_ledger=HbmLedger(enabled=False),
            )
            submit_wave(forced, cfg, n_reqs, prompt_len + 128, 8, "olf")
            drain(forced)
            burst = watch.poll()
            out[arm]["sentinel"] = {
                "forced_compiles": int(sum(burst.values())),
                "fires_total": int(
                    watch.stats()["xla_sentinel_fires_total"]
                ),
                "stall_counter_recompile": float(
                    reg.counter("areal_trace_stall_total").value(
                        kind="recompile"
                    )
                ),
            }
            forced.close()
            # leak audit: clean shutdown returns the ledger to baseline
            out[arm]["close_leaks"] = {
                k: int(v) for k, v in eng.close().items()
            }
            out[arm]["ledger_zero_after_close"] = all(
                v == 0 for v in led.snapshot().values()
            )
        else:
            eng.close()
        del eng
    off_tps = out["off"]["decode_toks_per_sec"]
    out["on"]["overhead_frac_vs_off"] = round(
        1.0 - out["on"]["decode_toks_per_sec"] / max(off_tps, 1e-9), 4
    )
    return out


def bench_prefix_reuse(cfg, params, n_reqs=32, group_size=8, prompt_len=512):
    """Group-prompt KV dedup at admission (the radix-cache role of the
    reference's patched SGLang, realhf/impl/model/backend/sglang.py:369):
    time the admission prefill of ``n_reqs`` rows over ``n_reqs/group_size``
    unique prompts (a sampling group's n copies each) vs all-unique."""
    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )

    rng = np.random.default_rng(11)

    def submit(eng, n_unique, tag):
        prompts = [
            rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
            for _ in range(n_unique)
        ]
        for i in range(n_reqs):
            eng.submit(
                APIGenerateInput(
                    qid=f"{tag}{i // (n_reqs // n_unique)}-{i}",
                    prompt_ids=prompts[i // (n_reqs // n_unique)],
                    input_ids=prompts[i // (n_reqs // n_unique)],
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=4, temperature=1.0
                    ),
                )
            )

    def admit_time(n_unique, tag):
        # engine shapes match bench_generation's b32 run (same cache bucket
        # and chunk), so every decode/prefill jit EXCEPT the m-unique
        # admission bucket is already compiled — keeps bench wall time flat
        eng = make_engine(cfg, params, n_reqs, prompt_len, 512, chunk=128)
        submit(eng, n_unique, f"w{tag}")  # warmup: compile this m-bucket
        drain(eng)
        base_toks = eng.prefill_tokens_total
        submit(eng, n_unique, tag)
        t0 = time.perf_counter()
        eng._admit()
        int(np.asarray(eng.cache.lengths)[0])  # force prefill completion
        dt = time.perf_counter() - t0
        toks = eng.prefill_tokens_total - base_toks
        del eng
        return dt, toks

    t_unique, toks_unique = admit_time(n_reqs, "u")
    t_grouped, toks_grouped = admit_time(n_reqs // group_size, "g")
    return {
        "batch": n_reqs,
        "group_size": group_size,
        "prompt_len": prompt_len,
        "admit_s_unique_prompts": round(t_unique, 4),
        "admit_s_grouped_prompts": round(t_grouped, 4),
        # wall speedup carries the fixed per-wave fetch cost; the token
        # ratio is the exact compute reduction (one prefill per group)
        "admit_wall_speedup": round(t_unique / max(t_grouped, 1e-9), 2),
        "prefill_tokens_unique": int(toks_unique),
        "prefill_tokens_grouped": int(toks_grouped),
        "prefill_work_reduction": round(
            toks_unique / max(toks_grouped, 1), 2
        ),
    }


def bench_prefix_cache_ab(
    cfg,
    params,
    n_sessions=8,
    turns=4,
    prompt_len=512,
    user_len=64,
    max_new=64,
    page=256,
    chunk=128,
):
    """Multi-turn conversation replay over the cross-request radix prefix
    cache (engine/prefix_cache.py), cache on vs off.  Every turn re-sends
    the WHOLE growing conversation under a FRESH qid — the reference's
    multi-turn agent shape (realhf/system/partial_rollout.py over SGLang's
    radix cache), where same-qid continuation parking cannot help and only
    the cross-request cache saves the prefix re-prefill.  ``n_sessions``
    conversations replay in lockstep (one submit wave per turn, drained
    before the next), so the decode batch matches between arms and the A/B
    isolates the admission/prefill savings.

    Reported per arm: end-to-end replay tok/s (generated tokens / wall),
    ``cached_token_frac`` (prompt tokens served from cache / prompt tokens
    submitted — 0 by construction with the cache off), and the suffix
    prefill token count the cache arm actually paid."""
    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )

    import zlib

    # longest prompt the replay submits + its generation
    final_prompt = prompt_len + (turns - 1) * (max_new + user_len)

    def replay(eng, tag):
        """Returns (wall_s, generated_tokens, prompt_tokens_submitted)."""
        rngs = [
            np.random.default_rng(zlib.crc32(f"{tag}s{s}".encode()))
            for s in range(n_sessions)
        ]
        convs = [
            rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
            for rng in rngs
        ]
        gen_toks = 0
        prompt_toks = 0
        t0 = time.perf_counter()
        for j in range(turns):
            for s, conv in enumerate(convs):
                prompt_toks += len(conv)
                eng.submit(
                    APIGenerateInput(
                        qid=f"{tag}s{s}@t{j}",
                        prompt_ids=conv,
                        input_ids=conv,
                        gconfig=GenerationHyperparameters(
                            max_new_tokens=max_new, temperature=1.0
                        ),
                    )
                )
            while eng.has_work:
                eng.step()
            outs = eng.drain_results()
            for s, rng in enumerate(rngs):
                out = outs[f"{tag}s{s}@t{j}"]
                gen_toks += len(out.output_ids)
                convs[s] = (
                    convs[s]
                    + list(out.output_ids)
                    + rng.integers(0, cfg.vocab_size, (user_len,)).tolist()
                )
        return time.perf_counter() - t0, gen_toks, prompt_toks

    def arm(enabled, tag):
        eng = make_engine(
            cfg, params, n_sessions, final_prompt, max_new, chunk=chunk,
            cache_mode="paged",
            page_size=page,
            # headroom so capacity trims don't dominate the A/B: the cache
            # may keep earlier turns resident beyond the live rows' pool
            kv_pool_tokens=2 * n_sessions
            * bench_gen_cache_len(final_prompt, max_new),
            prefix_cache=enabled,
        )
        replay(eng, f"w{tag}")  # warmup: compile every turn's buckets
        s0 = eng.prefix_cache_stats()
        p0 = eng.prefill_tokens_total
        wall, gen_toks, prompt_toks = replay(eng, tag)
        st = eng.prefix_cache_stats()
        row = {
            "replay_s": round(wall, 3),
            "toks_per_sec": round(gen_toks / max(wall, 1e-9), 1),
            "generated_tokens": int(gen_toks),
            "prompt_tokens_submitted": int(prompt_toks),
            "cached_token_frac": round(
                (st["cached_tokens_total"] - s0["cached_tokens_total"])
                / max(prompt_toks, 1),
                3,
            ),
            "prefill_tokens": int(eng.prefill_tokens_total - p0),
            "cache_hits": int(st["hits_total"] - s0["hits_total"]),
            "cache_evictions": int(
                st["evictions_total"] - s0["evictions_total"]
            ),
        }
        del eng
        return row

    on = arm(True, "on")
    off = arm(False, "off")
    return {
        "sessions": n_sessions,
        "turns": turns,
        "prompt_len": prompt_len,
        "user_len": user_len,
        "max_new": max_new,
        "page_size": page,
        "cache_on": on,
        "cache_off": off,
        "replay_wall_speedup": round(
            off["replay_s"] / max(on["replay_s"], 1e-9), 2
        ),
        "prefill_work_reduction": round(
            off["prefill_tokens"] / max(on["prefill_tokens"], 1), 2
        ),
    }


def bench_prefix_cache_hier(
    cfg,
    params,
    counts=(2, 8),
    turns=2,
    prompt_len=128,
    user_len=24,
    max_new=24,
    page=32,
    chunk=32,
    capacity_frac=0.2,
    pool_rows=4,
    host_bytes=1 << 30,
):
    """Hierarchical prefix cache: cached-token-frac vs CONVERSATION COUNT
    curves, host spill tier on vs off (engine/prefix_cache.py host tier).

    The HBM radix cache is capped (``capacity_frac`` of a FIXED pool
    sized for ``pool_rows`` rows), so as the conversation count grows
    the working set of sessions evicts itself — exactly the chat-scale
    failure the host tier exists for.  Sessions replay round-robin, one
    at a time (pressure comes from the CACHE working set, not batch
    concurrency), every turn re-sending the whole conversation under a
    fresh qid.  With the tier OFF, overflowed prefixes die and returning
    sessions re-prefill; ON, they spill to host and swap back in, so
    ``cached_token_frac`` stays high as the count crosses the HBM
    capacity — the curve pair IS the win.

    Sub-arms are never silently capped: a (count, arm) cell that raises
    is recorded as ``{"error": ...}`` and named in ``dropped``; parity
    for that count is then reported as unverified, not assumed."""
    import zlib

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.sampling import SamplingParams

    final_prompt = prompt_len + (turns - 1) * (max_new + user_len)
    pool_tokens = pool_rows * bench_gen_cache_len(final_prompt, max_new)

    def replay(eng, n_conv, tag):
        """Round-robin conversation replay; returns (streams, row)."""
        rngs = [
            np.random.default_rng(zlib.crc32(f"{tag}s{s}".encode()))
            for s in range(n_conv)
        ]
        convs = [
            rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
            for rng in rngs
        ]
        streams = {}
        prompt_toks = 0
        gen_toks = 0
        t0 = time.perf_counter()
        for j in range(turns):
            for s in range(n_conv):
                qid = f"{tag}s{s}t{j}"
                prompt_toks += len(convs[s])
                eng.submit(
                    APIGenerateInput(
                        qid=qid,
                        prompt_ids=convs[s],
                        input_ids=convs[s],
                        gconfig=GenerationHyperparameters(
                            max_new_tokens=max_new, greedy=True
                        ),
                    )
                )
                while eng.has_work:
                    eng.step()
                out = eng.drain_results()[qid]
                streams[(s, j)] = list(out.output_ids)
                gen_toks += len(out.output_ids)
                convs[s] = (
                    convs[s]
                    + list(out.output_ids)
                    + rngs[s].integers(
                        0, cfg.vocab_size, (user_len,)
                    ).tolist()
                )
        return streams, {
            "replay_s": round(time.perf_counter() - t0, 3),
            "generated_tokens": int(gen_toks),
            "prompt_tokens_submitted": int(prompt_toks),
        }

    def arm(n_conv, tier_bytes, tag):
        eng = make_engine(
            cfg, params, 2, final_prompt, max_new, chunk=chunk,
            cache_mode="paged",
            page_size=page,
            kv_pool_tokens=pool_tokens,
            prefix_cache=True,
            prefix_cache_capacity_frac=capacity_frac,
            prefix_cache_host_bytes=tier_bytes,
            sampling=SamplingParams(greedy=True),
        )
        # parked rows would mask cache pressure (fresh-qid turns never
        # resume them); TTL 0 releases a row the step after it parks
        eng.park_ttl_steps = 0
        streams, row = replay(eng, n_conv, tag)
        st = eng.prefix_cache_stats()
        row.update(
            cached_token_frac=round(
                st["cached_tokens_total"]
                / max(row["prompt_tokens_submitted"], 1),
                3,
            ),
            prefill_tokens=int(eng.prefill_tokens_total),
            spilled_blocks=int(st["spilled_blocks_total"]),
            restored_blocks=int(st["restored_blocks_total"]),
            host_dropped_blocks=int(st["host_dropped_blocks_total"]),
            evictions=int(st["evictions_total"]),
        )
        # leak audit: drain parked rows, flush both tiers, and require
        # the pool pristine + zero host bytes (tier-1 asserts this)
        eng.step()
        eng.step()
        eng._prefix_cache.flush()
        st = eng.prefix_cache_stats()
        row["leak_free"] = bool(
            eng.free_pool_blocks == eng.n_blocks
            and st["host_bytes_held"] == 0
            and st["host_blocks_held"] == 0
        )
        cap = eng._prefix_cache.capacity_blocks
        del eng
        return streams, row, cap

    out = {
        "counts": list(counts),
        "turns": turns,
        "prompt_len": prompt_len,
        "user_len": user_len,
        "max_new": max_new,
        "page_size": page,
        "capacity_frac": capacity_frac,
        "pool_tokens": pool_tokens,
        "host_bytes": host_bytes,
        "sweep": {},
        "dropped": [],
    }
    for n_conv in counts:
        cell = {}
        arms = {}
        for name, tier_bytes in (("host_on", host_bytes), ("host_off", 0)):
            try:
                streams, row, cap = arm(n_conv, tier_bytes, f"c{n_conv}")
                arms[name] = streams
                cell[name] = row
                out["capacity_blocks"] = cap
            except Exception as e:  # noqa: BLE001 - a cell is data
                cell[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
                out["dropped"].append(f"c{n_conv}/{name}")
        if len(arms) == 2:
            cell["token_parity"] = arms["host_on"] == arms["host_off"]
            cell["cached_token_frac_gain"] = round(
                cell["host_on"]["cached_token_frac"]
                - cell["host_off"]["cached_token_frac"],
                3,
            )
        else:
            cell["token_parity"] = None  # unverified, not assumed
        out["sweep"][f"c{n_conv}"] = cell
    return out


def bench_kv_fabric_ab(
    cfg,
    params,
    counts=(2, 8),
    turns=3,
    prompt_len=128,
    user_len=24,
    max_new=24,
    page=32,
    chunk=32,
):
    """Fleet-wide KV fabric A/B: session-migration replay on a 2-server
    in-process fleet, cross-server prefix pull on vs off.

    Every session runs turn 0 on the OWNER server, then migrates to the
    TARGET for all later turns — the poster-child workload for the
    fabric (cache-aware routing just lost, e.g. on a rebalance or a
    server death).  Fabric ON, the target is handed ``kv_source`` and
    pulls the owner's cached prefix over the segment transport
    (export_prefix -> import_prefix_segment, the worker's pump driven
    in-process); OFF, it re-prefills the whole conversation.  The
    diffable wins: FLEET ``cached_token_frac`` (both servers' radix
    hits over all prompt tokens submitted anywhere) and the target's
    re-prefill token count — the acceptance bar is a strictly higher
    fleet frac and a >=2x re-prefill reduction, with greedy streams
    token-identical across arms (the fabric buys FLOPs, never tokens)
    and both pools pristine after a flush.

    Sub-arms are never silently capped: a (count, arm) cell that raises
    is recorded as ``{"error": ...}`` and named in ``dropped``; parity
    for that count is then reported as unverified, not assumed."""
    import zlib

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.sampling import SamplingParams

    final_prompt = prompt_len + (turns - 1) * (max_new + user_len)
    cache_len = bench_gen_cache_len(final_prompt, max_new)

    def submit(eng, qid, ids, source=None):
        eng.submit(
            APIGenerateInput(
                qid=qid,
                prompt_ids=ids,
                input_ids=ids,
                gconfig=GenerationHyperparameters(
                    max_new_tokens=max_new, greedy=True
                ),
            )
        )
        if source is not None:
            # the schedule response's kv_source hint, as partial_rollout
            # attaches it (request metadata on the queued admission)
            with eng._lock:
                eng._pending[-1].metadata = {"kv_source": source}

    def pump(target, owner, max_steps=6000):
        """Step the target while servicing its pull intents from the
        owner — the generation-server worker's pull pump in-process."""
        for _ in range(max_steps):
            if not target.has_work:
                return
            target.step()
            for preq in target.drain_prefix_pull_requests():
                segs = owner.export_prefix(preq["qid"], preq["tokens"])
                if not segs:
                    target.prefix_pull_failed(preq["qid"], "miss")
                    continue
                for seg in segs:
                    ok, _ = target.import_prefix_segment(seg)
                    if not ok:
                        break
        raise RuntimeError("kv_fabric replay did not drain")

    def pristine(eng):
        eng.step()
        eng.step()
        if eng._prefix_cache is not None:
            eng._prefix_cache.flush()
        return bool(
            eng.free_pool_blocks == eng.n_blocks
            and (np.asarray(eng._block_ref) == 0).all()
        )

    def arm(n_conv, fabric, tag):
        servers = {}
        for role in ("owner", "target"):
            eng = make_engine(
                cfg, params, 2, final_prompt, max_new, chunk=chunk,
                cache_mode="paged",
                page_size=page,
                # roomy pool: the owner keeps every session's turn-0
                # prefix radix-resident for the later pulls
                kv_pool_tokens=(n_conv + 2) * cache_len,
                prefix_cache=True,
                prefix_pull_min_tokens=page,
                sampling=SamplingParams(greedy=True),
            )
            eng.park_ttl_steps = 0  # fresh-qid turns never resume rows
            servers[role] = eng
        owner, target = servers["owner"], servers["target"]
        rngs = [
            np.random.default_rng(zlib.crc32(f"{tag}s{s}".encode()))
            for s in range(n_conv)
        ]
        convs = [
            rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
            for rng in rngs
        ]
        streams = {}
        prompt_toks = 0
        migrated_toks = 0
        gen_toks = 0
        t0 = time.perf_counter()
        for j in range(turns):
            for s in range(n_conv):
                qid = f"{tag}s{s}t{j}"
                prompt_toks += len(convs[s])
                if j == 0:  # warm turn on the owner
                    submit(owner, qid, convs[s])
                    while owner.has_work:
                        owner.step()
                    out = owner.drain_results()[qid]
                else:  # the session migrated: later turns on the target
                    migrated_toks += len(convs[s])
                    submit(
                        target, qid, convs[s],
                        source="owner" if fabric else None,
                    )
                    pump(target, owner)
                    out = target.drain_results()[qid]
                streams[(s, j)] = list(out.output_ids)
                gen_toks += len(out.output_ids)
                convs[s] = (
                    convs[s]
                    + list(out.output_ids)
                    + rngs[s].integers(
                        0, cfg.vocab_size, (user_len,)
                    ).tolist()
                )
        fleet_cached = sum(
            e.prefix_cache_stats()["cached_tokens_total"]
            for e in servers.values()
        )
        pst = target.prefix_peer_stats()
        row = {
            "replay_s": round(time.perf_counter() - t0, 3),
            "generated_tokens": int(gen_toks),
            "prompt_tokens_submitted": int(prompt_toks),
            "migrated_prompt_tokens": int(migrated_toks),
            "fleet_cached_token_frac": round(
                fleet_cached / max(prompt_toks, 1), 3
            ),
            "target_prefill_tokens": int(target.prefill_tokens_total),
            "pulls_total": int(pst["pulls_total"]),
            "pull_bytes_total": int(pst["pull_bytes_total"]),
            "pull_rejects": dict(pst["pull_rejects"]),
            # leak audit: drain parked rows, flush the radix tiers, and
            # require both pools pristine (tier-1 asserts this)
            "leak_free": pristine(owner) and pristine(target),
        }
        del owner, target, servers
        return streams, row

    out = {
        "counts": list(counts),
        "turns": turns,
        "prompt_len": prompt_len,
        "user_len": user_len,
        "max_new": max_new,
        "page_size": page,
        "sweep": {},
        "dropped": [],
    }
    for n_conv in counts:
        cell = {}
        arms = {}
        for name, fabric in (("fabric_on", True), ("fabric_off", False)):
            try:
                streams, row = arm(n_conv, fabric, f"c{n_conv}")
                arms[name] = streams
                cell[name] = row
            except Exception as e:  # noqa: BLE001 - a cell is data
                cell[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
                out["dropped"].append(f"c{n_conv}/{name}")
        if len(arms) == 2:
            cell["token_parity"] = arms["fabric_on"] == arms["fabric_off"]
            cell["cached_token_frac_gain"] = round(
                cell["fabric_on"]["fleet_cached_token_frac"]
                - cell["fabric_off"]["fleet_cached_token_frac"],
                3,
            )
            cell["reprefill_token_reduction"] = round(
                cell["fabric_off"]["target_prefill_tokens"]
                / max(cell["fabric_on"]["target_prefill_tokens"], 1),
                2,
            )
        else:
            cell["token_parity"] = None  # unverified, not assumed
        out["sweep"][f"c{n_conv}"] = cell
    return out


def bench_kv_quant_ab(
    cfg,
    params,
    n_reqs=8,
    prompt_len=256,
    max_new=64,
    page=256,
    chunk=32,
    turns=3,
    sessions=4,
    user_len=24,
    capacity_frac=0.5,
    divergence_bar=0.35,
):
    """Quantized KV cache A/B (``GenServerConfig.kv_cache_dtype``):
    fp ("auto") vs int8 per-block-quantized pools on the paged serving
    path, at EQUAL pool budgets.

    Reported, all MEASURED on the arms actually run:

    * ``blocks_per_hbm_byte_gain`` — bytes per pool block from the
      allocated arrays' true itemsize (int8 data + f32 scales vs model
      dtype), i.e. how many more paged blocks one HBM byte buys;
    * ``max_concurrent_rows`` — full-context rows a FIXED byte budget
      (the fp arm's pool) holds per arm;
    * ``decode`` — greedy decode tok/s per arm on an identical wave,
      plus the int8 arm's greedy divergence rate vs the fp arm
      (per-request longest-common-prefix, so one early flip counts the
      whole tail — the conservative definition);
    * ``prefix_equal_hbm`` — the multi-turn replay with the radix cache
      capped at the SAME HBM bytes per arm: the int8 arm's pool holds
      ~2x the blocks, so ``cached_token_frac`` rises at equal memory;
    * ``auto_token_parity`` — the "auto" arm against a DENSE engine on
      the same wave: the quantization plumbing must leave the
      unquantized path token-identical (pinned in tier-1).

    The ``quality_ok`` gate asserts the decode-wave divergence rate
    under ``divergence_bar``; the int8 engine (the arm under test)
    folds the check into its ``areal_inference_kv_quant_*`` divergence
    counters.
    Sub-arms never silently cap: a cell that raises is recorded as
    ``{"error": ...}`` and named in ``dropped``."""
    import zlib

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.sampling import SamplingParams

    out = {
        "batch": n_reqs,
        "prompt_len": prompt_len,
        "max_new": max_new,
        "page_size": page,
        "divergence_bar": divergence_bar,
        "dropped": [],
    }

    def decode_arm(kv_dtype):
        eng = make_engine(
            cfg, params, n_reqs, prompt_len, max_new, chunk=chunk,
            cache_mode="paged", page_size=page,
            kv_cache_dtype=kv_dtype,
            sampling=SamplingParams(greedy=True),
        )
        # IDENTICAL tags (= identical prompt streams and qids) across
        # arms: the divergence comparison is token-by-token per qid
        submit_wave(
            eng, cfg, n_reqs, prompt_len, max_new, "kvwarm", greedy=True
        )
        drain(eng)  # warmup: compile this arm's buckets
        qids = submit_wave(
            eng, cfg, n_reqs, prompt_len, max_new, "kvwave", greedy=True
        )
        t0 = time.perf_counter()
        while eng.has_work:
            eng.step()
        dt = time.perf_counter() - t0
        outs = eng.drain_results()
        streams = {q: list(outs[q].output_ids) for q in qids}
        n_tok = sum(len(s) for s in streams.values())
        row = {
            "decode_toks_per_sec": round(n_tok / max(dt, 1e-9), 1),
            "generated_tokens": int(n_tok),
            "bytes_per_block": int(eng._pool_block_bytes()),
            "pool_blocks": int(eng.n_blocks),
            "storage_bits": eng.kv_quant_stats()["storage_bits"],
        }
        return eng, streams, row

    # -- decode wave + storage-density numbers (equal pool budget) ---------
    try:
        eng_fp, fp_streams, fp_row = decode_arm("auto")
        eng_q, q_streams, q_row = decode_arm("int8")
        div_rate, n_div = lcp_divergence(fp_streams, q_streams)
        # the measured check lands on the INT8 arm's quality counters
        # (the areal_inference_kv_quant_divergence_* series) — it is
        # the arm whose storage is under test; the fp arm is the
        # reference and its counters stay zero
        eng_q.note_kv_divergence_check(len(fp_streams), n_div)
        gain = fp_row["bytes_per_block"] / max(q_row["bytes_per_block"], 1)
        budget = fp_row["bytes_per_block"] * fp_row["pool_blocks"]
        bpr = eng_fp.blocks_per_row
        out["bytes_per_block"] = {
            "auto": fp_row["bytes_per_block"],
            "int8": q_row["bytes_per_block"],
        }
        out["blocks_per_hbm_byte_gain"] = round(gain, 3)
        out["max_concurrent_rows"] = {
            "budget_bytes": int(budget),
            "auto": int(fp_row["pool_blocks"] // bpr),
            "int8": int(
                (budget // q_row["bytes_per_block"]) // bpr
            ),
        }
        out["decode"] = {
            "auto": fp_row,
            "int8": q_row,
            "divergence_rate": div_rate,
            "diverged_requests": int(n_div),
            "quality_ok": bool(div_rate <= divergence_bar),
        }
        del eng_q
    except Exception as e:  # noqa: BLE001 - a cell is data
        out["decode"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        out["dropped"].append("decode")
        eng_fp = None
        fp_streams = {}

    # -- "auto" arm parity pin: the unquantized path must be untouched -----
    try:
        if eng_fp is None:
            raise RuntimeError("decode arm dropped")
        dense = make_engine(
            cfg, params, n_reqs, prompt_len, max_new, chunk=chunk,
            cache_mode="dense",
            sampling=SamplingParams(greedy=True),
        )
        qids = submit_wave(
            dense, cfg, n_reqs, prompt_len, max_new, "kvwave", greedy=True
        )
        drain_outs = {}
        while dense.has_work:
            dense.step()
        for q, o in dense.drain_results().items():
            drain_outs[q] = list(o.output_ids)
        out["auto_token_parity"] = bool(
            all(drain_outs[q] == fp_streams[q] for q in qids)
        )
        del dense
    except Exception as e:  # noqa: BLE001
        out["auto_token_parity"] = None
        out["dropped"].append(f"auto_parity: {type(e).__name__}: {e}"[:120])
    finally:
        del eng_fp

    # -- prefix cache at equal HBM: int8 pools hold ~2x the blocks ---------
    final_prompt = prompt_len + (turns - 1) * (max_new + user_len)
    fp_pool_tokens = sessions * bench_gen_cache_len(final_prompt, max_new)

    def replay_arm(kv_dtype, pool_tokens, tag):
        eng = make_engine(
            cfg, params, 2, final_prompt, max_new, chunk=chunk,
            cache_mode="paged", page_size=page,
            kv_pool_tokens=pool_tokens,
            kv_cache_dtype=kv_dtype,
            prefix_cache_capacity_frac=capacity_frac,
            sampling=SamplingParams(greedy=True),
        )
        eng.park_ttl_steps = 0  # fresh-qid turns never resume parks
        rngs = [
            np.random.default_rng(zlib.crc32(f"{tag}s{s}".encode()))
            for s in range(sessions)
        ]
        convs = [
            rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
            for rng in rngs
        ]
        streams = {}
        prompt_toks = 0
        for j in range(turns):
            for s in range(sessions):
                qid = f"{tag}s{s}t{j}"
                prompt_toks += len(convs[s])
                eng.submit(
                    APIGenerateInput(
                        qid=qid,
                        prompt_ids=convs[s],
                        input_ids=convs[s],
                        gconfig=GenerationHyperparameters(
                            max_new_tokens=max_new, greedy=True
                        ),
                    )
                )
                while eng.has_work:
                    eng.step()
                o = eng.drain_results()[qid]
                streams[qid] = list(o.output_ids)
                convs[s] = (
                    convs[s]
                    + list(o.output_ids)
                    + rngs[s].integers(
                        0, cfg.vocab_size, (user_len,)
                    ).tolist()
                )
        st = eng.prefix_cache_stats()
        row = {
            "pool_tokens": int(pool_tokens),
            "pool_blocks": int(eng.n_blocks),
            "pool_bytes": int(
                eng._pool_block_bytes() * eng.n_blocks
            ),
            "capacity_blocks": int(st["capacity_blocks"]),
            "cached_token_frac": round(
                st["cached_tokens_total"] / max(prompt_toks, 1), 3
            ),
            "prefill_tokens": int(eng.prefill_tokens_total),
        }
        del eng
        return streams, row

    try:
        fp_rep_streams, fp_rep = replay_arm("auto", fp_pool_tokens, "r")
        # equal HBM: scale the int8 arm's pool tokens by the measured
        # per-block byte ratio so both arms' pools cost the same bytes
        bb = out.get("bytes_per_block")
        ratio = (
            bb["auto"] / bb["int8"]
            if isinstance(bb, dict)
            else 2.0
        )
        q_pool_tokens = int(fp_pool_tokens * ratio)
        q_rep_streams, q_rep = replay_arm("int8", q_pool_tokens, "r")
        rep_div, rep_n_div = lcp_divergence(fp_rep_streams, q_rep_streams)
        out["prefix_equal_hbm"] = {
            "auto": fp_rep,
            "int8": q_rep,
            "divergence_rate": rep_div,
            "diverged_requests": int(rep_n_div),
            "cached_token_frac_gain": round(
                q_rep["cached_token_frac"] - fp_rep["cached_token_frac"],
                3,
            ),
        }
    except Exception as e:  # noqa: BLE001
        out["prefix_equal_hbm"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        out["dropped"].append("prefix_equal_hbm")
    return out


def bench_weight_quant_ab(
    cfg,
    params,
    n_reqs=8,
    prompt_len=256,
    max_new=64,
    page=256,
    chunk=32,
    turns=3,
    sessions=4,
    user_len=24,
    divergence_bar=0.35,
    stage_bytes_bar=1.8,
):
    """Quantized serving weights A/B (``GenServerConfig.
    serving_weight_dtype``): the model-dtype param tree ("auto") vs the
    int8 + per-output-channel-scale serving format on the same engine
    paths.

    Reported, all MEASURED on the arms actually run:

    * ``param_hbm`` — the resident serving tree's byte footprint per
      arm (the HBM a quantized fleet frees for paged blocks / prefix
      cache) and the reduction ratio;
    * ``staged_swap`` — a staged weight swap per arm against a
      published snapshot pair (full tree + the ``v*-int8`` sibling the
      manifest advertises): bytes actually restored, stage seconds
      (decode running), commit pause ms — the ``bytes_ratio`` >=
      ``stage_bytes_bar`` gate is the "half-byte staged swaps" claim;
    * ``decode`` — greedy decode tok/s per arm on an identical paged
      wave, plus the int8 arm's divergence rate vs the full-precision
      arm (per-request longest common prefix — one early flip charges
      the whole tail);
    * ``replay`` — the multi-turn replay (paged + radix prefix cache)
      divergence rate: THE ``quality_ok`` gate's workload, folded into
      the int8 engine's ``areal_inference_weight_quant_*`` counters;
    * ``max_concurrent_rows`` — full-context rows a FIXED HBM budget
      (full weights + the fp pool) holds when weight-int8 frees weight
      bytes into pool blocks, with and without kv int8 COMPOSED (the
      PR-12 format) — the capacity story the two quantizations buy
      together;
    * ``auto_token_parity`` — the "auto" arm against a dense engine on
      the same wave: the weight-quant plumbing must leave the
      unquantized path token-identical (pinned in tier-1).

    Sub-arms never silently cap: a cell that raises is recorded as
    ``{"error": ...}`` and named in ``dropped``."""
    import shutil
    import tempfile
    import threading
    import zlib

    import jax

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine import checkpoint
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.models import quantize

    out = {
        "batch": n_reqs,
        "prompt_len": prompt_len,
        "max_new": max_new,
        "page_size": page,
        "divergence_bar": divergence_bar,
        "stage_bytes_bar": stage_bytes_bar,
        "dropped": [],
    }

    def decode_arm(swd, kv_dtype="auto"):
        eng = make_engine(
            cfg, params, n_reqs, prompt_len, max_new, chunk=chunk,
            cache_mode="paged", page_size=page,
            serving_weight_dtype=swd, kv_cache_dtype=kv_dtype,
            sampling=SamplingParams(greedy=True),
        )
        # IDENTICAL tags (= identical prompt streams and qids) across
        # arms: the divergence comparison is token-by-token per qid
        submit_wave(
            eng, cfg, n_reqs, prompt_len, max_new, "wqwarm", greedy=True
        )
        drain(eng)  # warmup: compile this arm's buckets
        qids = submit_wave(
            eng, cfg, n_reqs, prompt_len, max_new, "wqwave", greedy=True
        )
        t0 = time.perf_counter()
        while eng.has_work:
            eng.step()
        dt = time.perf_counter() - t0
        outs = eng.drain_results()
        streams = {q: list(outs[q].output_ids) for q in qids}
        n_tok = sum(len(s) for s in streams.values())
        st = eng.weight_quant_stats()
        row = {
            "decode_toks_per_sec": round(n_tok / max(dt, 1e-9), 1),
            "generated_tokens": int(n_tok),
            "param_bytes": int(st["param_bytes"]),
            "storage_bits": int(st["storage_bits"]),
            "quantized_leaves": int(st["quantized_leaves"]),
            "pool_block_bytes": int(eng._pool_block_bytes()),
        }
        return eng, streams, row

    # -- decode wave + param-HBM numbers -----------------------------------
    try:
        eng_fp, fp_streams, fp_row = decode_arm("auto")
        eng_q, q_streams, q_row = decode_arm("int8")
        div_rate, n_div = lcp_divergence(fp_streams, q_streams)
        out["param_hbm"] = {
            "auto_bytes": fp_row["param_bytes"],
            "int8_bytes": q_row["param_bytes"],
            "reduction": round(
                fp_row["param_bytes"] / max(q_row["param_bytes"], 1), 3
            ),
        }
        out["decode"] = {
            "auto": fp_row,
            "int8": q_row,
            "divergence_rate": div_rate,
            "diverged_requests": int(n_div),
        }
        # -- max concurrent rows at a FIXED HBM budget (weights + pool),
        # composing kv int8 (PR 12): freed weight bytes buy pool blocks
        budget = fp_row["param_bytes"] + (
            fp_row["pool_block_bytes"] * eng_fp.n_blocks
        )
        bpr = eng_fp.blocks_per_row
        cells = {}
        kv_bb = {"auto": fp_row["pool_block_bytes"]}
        try:
            eng_kv, _, kv_row = decode_arm("auto", kv_dtype="int8")
            kv_bb["int8"] = kv_row["pool_block_bytes"]
            del eng_kv
        except Exception as e:  # noqa: BLE001
            out["dropped"].append(
                f"kv_int8_block_bytes: {type(e).__name__}: {e}"[:120]
            )
        for warm, wbytes in (
            ("auto", fp_row["param_bytes"]),
            ("int8", q_row["param_bytes"]),
        ):
            for kvarm, bb in kv_bb.items():
                cells[f"w_{warm}+kv_{kvarm}"] = int(
                    max(budget - wbytes, 0) // bb // bpr
                )
        out["max_concurrent_rows"] = {
            "budget_bytes": int(budget), **cells
        }
        del eng_q
    except Exception as e:  # noqa: BLE001 - a cell is data
        out["decode"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        out["dropped"].append("decode")
        eng_fp = None
        fp_streams = {}

    # -- "auto" arm parity pin: the unquantized path must be untouched -----
    try:
        if eng_fp is None:
            raise RuntimeError("decode arm dropped")
        dense = make_engine(
            cfg, params, n_reqs, prompt_len, max_new, chunk=chunk,
            cache_mode="dense",
            sampling=SamplingParams(greedy=True),
        )
        qids = submit_wave(
            dense, cfg, n_reqs, prompt_len, max_new, "wqwave", greedy=True
        )
        while dense.has_work:
            dense.step()
        dense_streams = {
            q: list(o.output_ids) for q, o in dense.drain_results().items()
        }
        out["auto_token_parity"] = bool(
            all(dense_streams[q] == fp_streams[q] for q in qids)
        )
        del dense
    except Exception as e:  # noqa: BLE001
        out["auto_token_parity"] = None
        out["dropped"].append(f"auto_parity: {type(e).__name__}: {e}"[:120])
    finally:
        del eng_fp

    # -- staged swap A/B: bytes restored + stage/commit time per format ----
    pub = tempfile.mkdtemp(prefix="areal-wquant-")
    try:
        snap = os.path.join(pub, "v1")
        checkpoint.save_params(params, snap)
        qpath = checkpoint.quant_snapshot_path(snap)
        qavals = checkpoint.save_quantized_params(params, qpath)
        checkpoint.write_manifest(
            jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), params
            ),
            snap,
            version=1,
            serving_quant={
                "int8": checkpoint.quant_manifest_entry(qavals, qpath)
            },
        )

        def staged_arm(swd):
            eng = make_engine(
                cfg, params, n_reqs, prompt_len, max_new, chunk=chunk,
                cache_mode="paged", page_size=page,
                serving_weight_dtype=swd,
                sampling=SamplingParams(greedy=True),
            )
            submit_wave(
                eng, cfg, n_reqs, prompt_len, max_new, f"wqsw{swd}",
                greedy=True,
            )
            tok = 0
            while eng.has_work and tok < n_reqs * chunk:
                tok += eng.step()
            # the negotiation the generation server runs: int8 engines
            # restore the advertised sibling tree, auto the full one
            restore_path = qpath if swd == "int8" else snap
            template = eng.weight_restore_template(
                "int8" if swd == "int8" else "full"
            )
            box = {}

            def _stage():
                try:
                    p = checkpoint.load_params_staged(
                        template, restore_path, chunk_bytes=1 << 20
                    )
                    box["bytes"] = quantize.tree_bytes(p)
                    eng.stage_weights(eng.prepare_weights(p), 1)
                except Exception as e:  # noqa: BLE001 - reported
                    box["error"] = repr(e)

            th = threading.Thread(target=_stage, daemon=True)
            t_st = time.perf_counter()
            th.start()
            while th.is_alive():
                eng.step()  # decode CONTINUES during staging
            th.join()
            if "error" in box:
                raise RuntimeError(box["error"])
            stage_s = time.perf_counter() - t_st
            t0 = time.perf_counter()
            eng.pause()
            eng.step()
            eng.commit_staged(expected_version=1)
            eng.resume()
            while eng.version != 1:
                eng.step()
            pause_s = time.perf_counter() - t0
            drain(eng)
            del eng
            return {
                "staged_bytes": int(box["bytes"]),
                "stage_ms": round(stage_s * 1e3, 1),
                "commit_pause_ms": round(pause_s * 1e3, 1),
            }

        fp_sw = staged_arm("auto")
        q_sw = staged_arm("int8")
        ratio = fp_sw["staged_bytes"] / max(q_sw["staged_bytes"], 1)
        out["staged_swap"] = {
            "auto": fp_sw,
            "int8": q_sw,
            "bytes_ratio": round(ratio, 3),
            "bytes_ok": bool(ratio >= stage_bytes_bar),
        }
    except Exception as e:  # noqa: BLE001
        out["staged_swap"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        out["dropped"].append("staged_swap")
    finally:
        shutil.rmtree(pub, ignore_errors=True)

    # -- multi-turn replay (paged + prefix cache): THE quality gate --------
    def replay_arm(swd, tag):
        eng = make_engine(
            cfg, params, 2,
            prompt_len + (turns - 1) * (max_new + user_len), max_new,
            chunk=chunk, cache_mode="paged", page_size=page,
            serving_weight_dtype=swd,
            sampling=SamplingParams(greedy=True),
        )
        eng.park_ttl_steps = 0  # fresh-qid turns never resume parks
        rngs = [
            np.random.default_rng(zlib.crc32(f"{tag}s{s}".encode()))
            for s in range(sessions)
        ]
        convs = [
            rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
            for rng in rngs
        ]
        streams = {}
        for j in range(turns):
            for s in range(sessions):
                qid = f"{tag}s{s}t{j}"
                eng.submit(
                    APIGenerateInput(
                        qid=qid,
                        prompt_ids=convs[s],
                        input_ids=convs[s],
                        gconfig=GenerationHyperparameters(
                            max_new_tokens=max_new, greedy=True
                        ),
                    )
                )
                while eng.has_work:
                    eng.step()
                o = eng.drain_results()[qid]
                streams[qid] = list(o.output_ids)
                convs[s] = (
                    convs[s]
                    + list(o.output_ids)
                    + rngs[s].integers(
                        0, cfg.vocab_size, (user_len,)
                    ).tolist()
                )
        return eng, streams

    try:
        eng_rf, fp_rep = replay_arm("auto", "wqr")
        del eng_rf
        eng_rq, q_rep = replay_arm("int8", "wqr")
        rep_div, rep_n_div = lcp_divergence(fp_rep, q_rep)
        # the measured check lands on the INT8 arm's quality counters
        # (the areal_inference_weight_quant_divergence_* series) — it is
        # the arm whose storage is under test
        eng_rq.note_weight_divergence_check(len(fp_rep), rep_n_div)
        out["replay"] = {
            "requests": len(fp_rep),
            "divergence_rate": rep_div,
            "diverged_requests": int(rep_n_div),
            "quality_ok": bool(rep_div <= divergence_bar),
        }
        del eng_rq
    except Exception as e:  # noqa: BLE001
        out["replay"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        out["dropped"].append("replay")
    return out


def bench_slo_report(
    cfg,
    params,
    n_sessions=6,
    turns=3,
    prompt_len=192,
    user_len=32,
    max_new=48,
    page=256,
    chunk=32,
    overhead_reqs=32,
    overhead_prompt=256,
    overhead_new=256,
    overhead_repeats=2,
):
    """Request-level SLO report (observability/latency.py):

    * **multi_turn** — the multi-turn replay workload split across TWO
      engines posing as separate servers; each engine's TTFT/TPOT
      digests are FLEET-MERGED (exact: fixed log buckets) and reported
      as p50/p95/p99 alongside per-server p99 — the same merge the
      master's aggregator performs over scraped pages.
    * **spec_decode** — the repetitive-trace workload with speculative
      decoding ON (greedy + paged), so the report covers the serving
      mode whose TTFT/TPOT shape differs most from plain decode.
    * **overhead_ab** — sustained decode tok/s with SLO tracking on vs
      off; the tracked acceptance bar is on < 2% overhead vs off (same
      bar as the flight recorder's).

    ``merge_within_bound`` cross-checks the merged p50/p95/p99 against
    the pooled raw records' inverted-CDF quantiles — the documented
    digest error bound, asserted in tier-1 by
    tests/engine/test_bench_sweep.py."""
    import zlib

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.engine.spec_decode import SpecDecodeParams
    from areal_tpu.observability.latency import (
        SLO_REL_ERROR_BOUND,
        LatencyDigest,
    )

    def _pct(digest):
        p = digest.percentiles()
        return {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in p.items()
        }

    def _fleet(engines, records):
        """Fleet-merge the engines' digests + cross-check vs raw
        records (the pooled inverted-CDF quantiles must sit within the
        documented bound of the merged digest's)."""
        fleet = {"ttft_s": LatencyDigest(), "tpot_s": LatencyDigest()}
        servers = {}
        for eng in engines:
            digs = {
                k: LatencyDigest.from_dict(v)
                for k, v in eng.slo_digests().items()
            }
            for k in fleet:
                fleet[k].merge(digs[k])
            servers[eng.server_name] = {
                "ttft_p99": digs["ttft_s"].quantile(0.99),
                "tpot_p99": digs["tpot_s"].quantile(0.99),
                "records": eng.slo_records_total,
            }
        checks = []
        for field, dig in fleet.items():
            raw = sorted(
                r.ttft_s if field == "ttft_s" else r.tpot_s
                for r in records
                if (field == "ttft_s" or r.tpot_s is not None)
            )
            for q in (0.50, 0.95, 0.99):
                if not raw:
                    continue
                # inverted-CDF: the ceil(q*n)-th smallest raw value
                emp = raw[
                    min(len(raw) - 1, max(0, int(np.ceil(q * len(raw))) - 1))
                ]
                got = dig.quantile(q)
                if emp > 0 and got is not None:
                    checks.append(abs(got - emp) / emp)
        return {
            "fleet": {k: _pct(d) for k, d in fleet.items()},
            "servers": servers,
            "merge_max_rel_err": round(max(checks), 4) if checks else None,
            "merge_within_bound": bool(
                not checks or max(checks) <= SLO_REL_ERROR_BOUND + 1e-12
            ),
        }

    def multi_turn():
        engines = [
            make_engine(
                cfg, params, n_sessions,
                prompt_len + (turns - 1) * (max_new + user_len), max_new,
                chunk=chunk, cache_mode="paged", page_size=page,
                server_name=f"srv{j}",
            )
            for j in range(2)
        ]
        records = []
        rngs = [
            np.random.default_rng(zlib.crc32(f"slo-s{s}".encode()))
            for s in range(n_sessions)
        ]
        convs = [
            rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()
            for rng in rngs
        ]
        for j in range(turns):
            for s, conv in enumerate(convs):
                eng = engines[s % 2]  # session -> "server" routing
                eng.submit(
                    APIGenerateInput(
                        qid=f"slo-s{s}@t{j}",
                        prompt_ids=conv,
                        input_ids=conv,
                        gconfig=GenerationHyperparameters(
                            max_new_tokens=max_new, temperature=1.0
                        ),
                        metadata={"slo_schedule_wait_s": 0.0},
                    )
                )
            for eng in engines:
                drain(eng)
            for s, rng in enumerate(rngs):
                convs[s] = convs[s] + rng.integers(
                    0, cfg.vocab_size, (max_new + user_len,)
                ).tolist()
        for eng in engines:
            records.extend(eng.drain_slo_records())
        out = _fleet(engines, records)
        out["records"] = len(records)
        engines.clear()  # free both engines' KV/params before the next arm
        return out

    def spec_workload():
        eng = make_engine(
            cfg, params, n_sessions, prompt_len, max_new, chunk=chunk,
            cache_mode="paged", page_size=page,
            sampling=SamplingParams(greedy=True),
            spec_decode_params=SpecDecodeParams(
                enabled=True, max_draft_tokens=7
            ),
            server_name="srv-spec",
        )
        for i in range(n_sessions):
            rng = np.random.default_rng(zlib.crc32(f"slor{i}".encode()))
            motif = rng.integers(0, 2, (12,)).tolist()
            ids = (motif * (prompt_len // 12 + 1))[:prompt_len]
            eng.submit(
                APIGenerateInput(
                    qid=f"slosp{i}",
                    prompt_ids=ids,
                    input_ids=ids,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=max_new, greedy=True
                    ),
                )
            )
        drain(eng)
        records = eng.drain_slo_records()
        out = _fleet([eng], records)
        out["records"] = len(records)
        del eng
        return out

    def overhead_ab():
        rows = {}
        for arm, on in (("off", False), ("on", True)):
            eng = make_engine(
                cfg, params, overhead_reqs, overhead_prompt,
                overhead_new, slo_tracking=on,
            )
            submit_wave(
                eng, cfg, overhead_reqs, overhead_prompt, overhead_new,
                f"slow{arm}",
            )
            drain(eng)  # warmup: compiles shared across arms
            best = 0.0
            for r in range(overhead_repeats):
                submit_wave(
                    eng, cfg, overhead_reqs, overhead_prompt,
                    overhead_new, f"slot{arm}{r}",
                )
                eng._admit()
                int(np.asarray(eng.cache.lengths)[0])  # prefill done
                t0 = time.perf_counter()
                n = drain(eng)
                best = max(best, n / (time.perf_counter() - t0))
            rows[arm] = round(best, 1)
            del eng
        return {
            "slo_off_toks_per_sec": rows["off"],
            "slo_on_toks_per_sec": rows["on"],
            "overhead_frac_vs_off": round(
                1.0 - rows["on"] / max(rows["off"], 1e-9), 4
            ),
        }

    return {
        "error_bound": round(SLO_REL_ERROR_BOUND, 4),
        "multi_turn": multi_turn(),
        "spec_decode": spec_workload(),
        "overhead_ab": overhead_ab(),
    }


def bench_pd_disagg_ab(
    cfg,
    params,
    n_interactive=8,
    interactive_prompt=48,
    interactive_new=12,
    turns=2,
    n_wave=5,
    wave_prompt=640,
    wave_new=4,
    page=64,
    chunk=8,
    prefill_chunk=128,
    arms=("unified", "disagg", "disagg_streamed"),
    prefill_mesh=None,
):
    """Disaggregated prefill/decode A/B under MIXED load (ROADMAP item 2)
    + the streamed-vs-monolithic handoff A/B (ISSUE 15).

    Workload: ``n_interactive`` chat sessions decoding short turns (the
    latency-sensitive stream) while a concurrent wave of ``n_wave``
    long-prompt requests prefills (the throughput batch that, on a
    unified fleet, steals a fill chunk out of every decode step).  All
    arms get the SAME two engines' worth of hardware:

    * **unified** — two unified engines, sessions and wave spread across
      both; every engine interleaves wave fill chunks with interactive
      decode, so interactive TTFT absorbs the wave.
    * **disagg** — one prefill engine + one decode engine with the
      PR-13 MONOLITHIC handoff: the whole unit (gather + wire + scatter
      of every block) moves serially AFTER prefill completes.
    * **disagg_streamed** — same split, but each fill chunk's finalized
      blocks stream into D as numbered segments WHILE the rest of the
      prompt still fills (import_handoff_segment's engine half), so at
      prefill-done only the final tail+metadata segment remains.

    Reported per (arm, workload): fleet-merged TTFT/TPOT p50/p99 from
    per-request LatencyRecords folded into the SLO plane's
    ``LatencyDigest``, handoff count/bytes/latency, greedy stream parity
    across ALL arms as DATA, and the headline ``stream_ab`` row: the
    RESUME GAP (prefill-done -> decode-resume, measured on the
    long-prompt wave) monolithic vs streamed, with the >=2x-reduction
    and p99-TTFT-no-worse verdicts the acceptance bar names.  Asserted
    as a CPU smoke in tests/system/test_pd_disagg.py.

    ``prefill_mesh`` runs the PREFILL engine on a device mesh (the
    heterogeneous big-mesh-prefill / small-mesh-decode deployment) —
    the hetero sub-arm's driver (see :func:`bench_pd_disagg_hetero`).
    """
    import zlib

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.observability.latency import LatencyDigest

    total_interactive = interactive_new * (1 + turns)

    def mk(name, streaming=False, mesh=None):
        eng = make_engine(
            cfg, params, n_interactive + n_wave, wave_prompt,
            total_interactive, chunk=chunk, cache_mode="paged",
            page_size=page, prefill_chunk_tokens=prefill_chunk,
            sampling=SamplingParams(greedy=True), server_name=name,
            handoff_streaming=streaming, mesh=mesh,
        )
        # sessions park through the whole wave phase; the default TTL
        # (512 steps) could evict a quiet session mid-measurement
        eng.park_ttl_steps = 1 << 20
        return eng

    def req(qid, ids, mn, workload, handoff=False):
        md = {"workload": workload, "slo_schedule_wait_s": 0.0}
        if handoff:
            # the manager's two-stage routing sets this in production;
            # the bench drives the engine halves directly
            md["handoff_to"] = "peer"
        return APIGenerateInput(
            qid=qid, prompt_ids=ids, input_ids=ids,
            gconfig=GenerationHyperparameters(
                max_new_tokens=mn, greedy=True
            ),
            metadata=md,
        )

    iconvs = [
        np.random.default_rng(zlib.crc32(f"pdi{s}".encode()))
        .integers(0, cfg.vocab_size, (interactive_prompt,)).tolist()
        for s in range(n_interactive)
    ]
    wconvs = [
        np.random.default_rng(zlib.crc32(f"pdw{i}".encode()))
        .integers(0, cfg.vocab_size, (wave_prompt,)).tolist()
        for i in range(n_wave)
    ]

    def run_arm(mode):
        """Chunked-generation driver over two interleaved engines —
        each request behaves like a partial_rollout client: submit a
        chunk, collect, submit the continuation.  Disagg arms put the
        first chunk on P with the handoff flag; the driver moves the KV
        P->D exactly like the generation-server worker (monolithic:
        whole unit when the prefill result lands; streamed: segments
        pumped into D as P's fill chunks emit them, final segment at
        the result)."""
        disagg = mode != "unified"
        streamed = mode == "disagg_streamed"
        if disagg:
            P = mk("pd-P", streaming=streamed, mesh=prefill_mesh)
            D = mk("pd-D")
            engines = [P, D]
        else:
            engines = [mk("uni-0"), mk("uni-1")]
        handoff_ms = []
        resume_gap_ms = []
        handoff_fail = [0]
        seg_fail = [0]

        recs = {}

        def pump_segments():
            if not streamed:
                return
            for seg in P.drain_handoff_segments():
                ok, _ = D.import_handoff_segment(seg)
                if not ok and not seg.get("abort"):
                    seg_fail[0] += 1

        def start(qid, ids, total, per, workload, uni_idx):
            recs[qid] = dict(
                ids=list(ids), left=total, per=per, workload=workload,
                uni=engines[uni_idx % len(engines)], first=True,
                stream=[], waiting=False, cur=None, done=False,
            )

        def submit_next(r, qid):
            mn = min(r["per"], r["left"])
            if disagg:
                eng = P if r["first"] else D
                eng.submit(
                    req(qid, r["ids"], mn, r["workload"],
                        handoff=r["first"])
                )
            else:
                eng = r["uni"]
                eng.submit(req(qid, r["ids"], mn, r["workload"]))
            r["cur"], r["waiting"] = eng, True

        def fold_chunk(r, out):
            r["stream"].extend(out.output_ids)
            r["ids"].extend(out.output_ids)
            r["left"] -= len(out.output_ids)
            r["done"] = (
                r["left"] <= 0
                or not out.output_ids
                or not out.no_eos
            )

        def finish_handoff(qid, r, out):
            """Prefill-stage result landed: move the REMAINING KV and
            time prefill-done -> decode-resume (the resume gap).  The
            monolithic arm pays gather + import of EVERY block here;
            the streamed arm only drains the final segment (everything
            else already scattered under D's decode chunks)."""
            t0 = time.perf_counter()
            if streamed:
                pump_segments()  # the final (tail + metadata) segment
            else:
                unit = P.export_handoff(qid)
                ok = False
                if unit is not None:
                    ok, _ = D.import_handoff(unit)
                if not ok:
                    handoff_fail[0] += 1
            r["first"] = False
            fold_chunk(r, out)
            if r["done"]:
                handoff_ms.append((time.perf_counter() - t0) * 1e3)
                return
            submit_next(r, qid)
            # step D until the continuation is RESUMED (decoding, not
            # filling): the wall clock from prefill-done to here is the
            # bubble streaming exists to shrink
            for _ in range(50_000):
                if any(
                    row is not None and row.req.qid == qid
                    and not row.parked and not row.filling
                    for row in D.rows
                ):
                    break
                D.step()
            dt = (time.perf_counter() - t0) * 1e3
            handoff_ms.append(dt)
            if qid.startswith("pdw"):
                resume_gap_ms.append(dt)

        def pump(max_steps=200_000):
            for _ in range(max_steps):
                live = False
                for eng in engines:
                    if eng.has_work:
                        eng.step()
                        live = True
                # streamed: export segments ride into D while P's later
                # fill chunks are still running — THE overlap
                pump_segments()
                for qid, r in recs.items():
                    if not r["waiting"]:
                        continue
                    out = r["cur"].try_get_result(qid)
                    if out is None:
                        continue
                    r["waiting"] = False
                    if disagg and r["first"] and out.output_ids:
                        finish_handoff(qid, r, out)
                        live = True
                        continue
                    r["first"] = False
                    fold_chunk(r, out)
                    if not r["done"]:
                        submit_next(r, qid)
                        live = True
                if not live and all(
                    r["done"] or not r["waiting"] for r in recs.values()
                ):
                    if all(r["done"] for r in recs.values()):
                        return
                    # nothing in flight but requests remain: submit them
                    for qid, r in recs.items():
                        if not r["done"] and not r["waiting"]:
                            submit_next(r, qid)
            raise RuntimeError("pd_disagg driver did not converge")

        # -- setup: establish every session's first turn, pre-wave
        for s, conv in enumerate(iconvs):
            start(f"pds{s}", conv, total_interactive, interactive_new,
                  "interactive", s)
        # sessions stop after turn 0 (budget throttled by `left` vs the
        # measured turns below): cap left to one turn for the setup pump
        for r in recs.values():
            r["_left_total"] = r["left"]
            r["left"] = interactive_new
        for qid, r in recs.items():
            submit_next(r, qid)
        pump()
        for eng in engines:
            eng.drain_slo_records()  # setup latencies: not measured
        # -- measured window: the wave prefills while sessions keep
        # decoding turns
        for r in recs.values():
            r["left"] = r["_left_total"] - (
                len(r["stream"])
            )
            r["done"] = r["left"] <= 0
        for i, conv in enumerate(wconvs):
            start(f"pdw{i}", conv, wave_new, wave_new, "wave",
                  i)
        for qid, r in recs.items():
            if not r["done"] and not r["waiting"]:
                submit_next(r, qid)
        pump()
        records = []
        for eng in engines:
            records.extend(eng.drain_slo_records())
        digs: Dict[str, Dict[str, LatencyDigest]] = {}
        for rec in records:
            d = digs.setdefault(
                rec.workload,
                {"ttft_s": LatencyDigest(), "tpot_s": LatencyDigest()},
            )
            d["ttft_s"].observe(rec.ttft_s)
            if rec.tpot_s is not None:
                d["tpot_s"].observe(rec.tpot_s)
        out = {}
        for wl, d in sorted(digs.items()):
            out[wl] = {
                "records": d["ttft_s"].count,
                "ttft_p50_ms": _q_ms(d["ttft_s"], 0.50),
                "ttft_p99_ms": _q_ms(d["ttft_s"], 0.99),
                "tpot_p50_ms": _q_ms(d["tpot_s"], 0.50),
                "tpot_p99_ms": _q_ms(d["tpot_s"], 0.99),
            }
        if disagg:
            hs = [P.handoff_stats(), D.handoff_stats()]
            out["handoff"] = {
                "count": hs[1]["imports_total"],
                "exports": hs[0]["exports_total"],
                "segments": hs[0]["segment_exports_total"],
                "segment_imports": hs[1]["segment_imports_total"],
                "failed": handoff_fail[0] + seg_fail[0],
                "bytes_total": hs[0]["bytes_total"],
                "mean_ms": round(float(np.mean(handoff_ms)), 2)
                if handoff_ms else None,
                "max_ms": round(float(np.max(handoff_ms)), 2)
                if handoff_ms else None,
                "resume_gap_wave_ms": {
                    "n": len(resume_gap_ms),
                    "mean": round(float(np.mean(resume_gap_ms)), 3)
                    if resume_gap_ms else None,
                    "max": round(float(np.max(resume_gap_ms)), 3)
                    if resume_gap_ms else None,
                },
                "import_rejects": hs[1]["import_rejects"],
            }
            if prefill_mesh is not None:
                out["prefill_mesh_devices"] = int(
                    prefill_mesh.devices.size
                )
        streams = {qid: list(r["stream"]) for qid, r in recs.items()}
        engines.clear()
        return out, streams

    def _q_ms(dig, q):
        v = dig.quantile(q)
        return round(v * 1e3, 3) if v is not None else None

    out: Dict[str, object] = {}
    streams = {}
    for arm in arms:
        try:
            out[arm], streams[arm] = run_arm(arm)
        except Exception as e:  # noqa: BLE001 - dropped sub-arm is data
            import traceback

            traceback.print_exc()
            out[arm] = {"error": f"{type(e).__name__}: {e}"[:300]}

    def _ok(a):
        return isinstance(out.get(a), dict) and "error" not in out[a]

    good = [a for a in arms if _ok(a)]
    if "unified" in good and len(good) > 1:
        out["parity_ok"] = all(
            streams[a] == streams["unified"] for a in good
            if a != "unified"
        )
        u = out["unified"].get("interactive", {}).get("ttft_p99_ms")
        best = out[good[1]].get("interactive", {}).get("ttft_p99_ms")
        out["interactive_ttft_p99_improved"] = (
            u is not None and best is not None and best < u
        )
    if _ok("disagg") and _ok("disagg_streamed"):
        # the streamed-vs-monolithic headline: resume gap on the wave
        # (>=2x bar) + interactive p99 TTFT no worse than monolithic
        # (1.2x slack: both are wall-clock over few records, and the
        # streamed path must merely not regress)
        mono = out["disagg"]["handoff"]["resume_gap_wave_ms"]["mean"]
        strm = out["disagg_streamed"]["handoff"]["resume_gap_wave_ms"][
            "mean"
        ]
        mono_p99 = out["disagg"].get("interactive", {}).get("ttft_p99_ms")
        strm_p99 = out["disagg_streamed"].get("interactive", {}).get(
            "ttft_p99_ms"
        )
        out["stream_ab"] = {
            "resume_gap_mono_ms": mono,
            "resume_gap_streamed_ms": strm,
            "resume_gap_ratio": (
                round(mono / strm, 2)
                if mono is not None and strm not in (None, 0)
                else None
            ),
            "resume_gap_improved_2x": (
                mono is not None
                and strm not in (None, 0)
                and mono / strm >= 2.0
            ),
            "mono_interactive_ttft_p99_ms": mono_p99,
            "streamed_interactive_ttft_p99_ms": strm_p99,
            "streamed_ttft_no_worse": (
                mono_p99 is not None
                and strm_p99 is not None
                and strm_p99 <= 1.2 * mono_p99
            ),
        }
    return out


def _too_few_chips(n_chips: int) -> dict:
    """A multi-chip section on a host with fewer chips is reported as NOT
    RUN: it never measures something else (a CPU mesh in a child process)
    under the section's name."""
    import jax

    return {
        "not_run": (
            f"needs {n_chips} chips, this host has {len(jax.devices())}"
        )
    }


def bench_pd_disagg_hetero(
    n_chips=2, n_sessions=2, interactive_prompt=24, interactive_new=6,
    n_wave=2, wave_prompt=96, wave_new=3, page=16, chunk=4,
    prefill_chunk=32,
):
    """Heterogeneous-mesh P/D sub-arm (ROADMAP item 2 called it
    "routable but unmeasured"): a BIG-mesh prefill engine (dense TP over
    ``n_chips``) streams KV handoffs into a SMALL single-chip decode
    engine — parity + TTFT rows recorded as data through the same
    mixed-load driver.  Needs ``n_chips`` devices in THIS process;
    reported as not run otherwise."""
    import jax

    if len(jax.devices()) < n_chips:
        return _too_few_chips(n_chips)
    return _pd_hetero_measure(
        n_chips=n_chips, n_sessions=n_sessions,
        interactive_prompt=interactive_prompt,
        interactive_new=interactive_new, n_wave=n_wave,
        wave_prompt=wave_prompt, wave_new=wave_new, page=page,
        chunk=chunk, prefill_chunk=prefill_chunk,
    )


def _pd_hetero_measure(
    n_chips=2, n_sessions=2, interactive_prompt=24, interactive_new=6,
    n_wave=2, wave_prompt=96, wave_new=3, page=16, chunk=4,
    prefill_chunk=32,
):
    """In-process half of the hetero sub-arm: n_chips-TP prefill mesh,
    single-chip decode, streamed handoff — rides the pd_disagg driver
    with ``prefill_mesh`` set, unified arm as the parity reference."""
    import jax

    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.models import transformer

    dense_cfg, _ = _sharded_serving_cfgs(jax.default_backend() == "tpu")
    params = transformer.init_params(dense_cfg, jax.random.PRNGKey(0))
    mesh = MeshSpec(model=n_chips).make_mesh(jax.devices()[:n_chips])
    res = bench_pd_disagg_ab(
        dense_cfg, params,
        n_interactive=n_sessions, interactive_prompt=interactive_prompt,
        interactive_new=interactive_new, turns=1, n_wave=n_wave,
        wave_prompt=wave_prompt, wave_new=wave_new, page=page,
        chunk=chunk, prefill_chunk=prefill_chunk,
        arms=("unified", "disagg_streamed"), prefill_mesh=mesh,
    )
    res["prefill_mesh"] = f"m{n_chips}"
    res["decode_mesh_devices"] = 1
    return res


def bench_spec_decode_ab(
    cfg,
    params,
    batches=(32, 64),
    prompt_len=512,
    max_new=256,
    motif_len=12,
    motif_alphabet=2,
    page=256,
    chunk=64,
    max_draft=7,
):
    """Self-speculative decoding A/B on a REPETITIVE-trace workload
    (engine/spec_decode.py): decode tok/s with n-gram draft + batched
    paged verify ON vs OFF, per batch size, under GREEDY sampling (the
    mode speculative decode is exact in).  Prompts tile a per-row
    random motif over a SMALL token alphabet: greedy decode from such
    low-entropy context settles into near-periodic output even for the
    bench's random-weight models — the synthetic proxy for what trained
    models do on real math/code traces, which is the regime n-gram
    drafting feeds on (the reported ``accept_rate`` makes the regime
    explicit).  Both arms submit identical prompts; the timed phase
    starts after admission/prefill completes, so the ratio isolates
    decode.

    Reported per batch: decode tok/s per arm, ``spec_over_off`` (the
    acceptance bar tracks >= 1.3x here), the measured acceptance rate,
    ``accepted_tokens_per_step`` (tokens emitted per verify pass), and
    ``derived_min_accept_rate`` — the break-even EMA threshold implied
    by the measured verify-vs-decode cost, the number recipe configs pin
    into ``GenServerConfig.spec_decode.min_accept_rate``
    (engine/dispatch.spec_break_even_accept_rate)."""
    import zlib

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.dispatch import spec_break_even_accept_rate
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.engine.spec_decode import SpecDecodeParams

    def submit_repetitive(eng, B, tag):
        for i in range(B):
            # motif seeded by ROW ONLY: warmup and timed waves (and both
            # arms) decode identical traces, so every window bucket the
            # timed wave touches is compiled by the warmup
            rng = np.random.default_rng(zlib.crc32(f"row{i}".encode()))
            alpha = min(motif_alphabet, cfg.vocab_size)
            motif = rng.integers(0, alpha, (motif_len,)).tolist()
            ids = (motif * (prompt_len // motif_len + 1))[:prompt_len]
            eng.submit(
                APIGenerateInput(
                    qid=f"{tag}{i}",
                    prompt_ids=ids,
                    input_ids=ids,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=max_new, greedy=True
                    ),
                )
            )

    def decode_timed(eng):
        """(tokens, seconds) of the post-admission decode phase."""
        while eng.has_work and (eng.n_pending > 0 or eng._filling):
            eng.step()
        t0 = time.perf_counter()
        n = 0
        while eng.has_work:
            n += eng.step()
        eng.drain_results()
        return n, time.perf_counter() - t0

    def arm(B, spec_on, tag):
        eng = make_engine(
            cfg, params, B, prompt_len, max_new, chunk=chunk,
            cache_mode="paged",
            page_size=page,
            sampling=SamplingParams(greedy=True),
            spec_decode_params=(
                SpecDecodeParams(enabled=True, max_draft_tokens=max_draft)
                if spec_on
                else None
            ),
        )
        submit_repetitive(eng, B, f"w{tag}")  # warmup: compiles
        drain(eng)
        submit_repetitive(eng, B, tag)
        n, dt = decode_timed(eng)
        row = {
            "decode_toks_per_sec": round(n / max(dt, 1e-9), 1),
            "decode_tokens": int(n),
        }
        if spec_on:
            s = eng.spec_stats()
            row["accept_rate"] = round(
                s["accepted_total"] / max(s["drafted_total"], 1), 3
            )
            # PER-ROW tokens emitted per verify pass (1 correction +
            # accepted drafts), the quantity dispatch.py's a*k+1 model
            # describes — a verify chunk batches many rows, so dividing
            # by chunks would overstate this by ~the batch size
            row["accepted_tokens_per_step"] = round(
                (s["accepted_total"] + s["draft_row_passes_total"])
                / max(s["draft_row_passes_total"], 1),
                2,
            )
            row["verify_chunks"] = int(s["verify_chunks_total"])
            row["fallback_rows"] = int(s["fallback_rows_total"])
        del eng
        return row

    out = {
        "prompt_len": prompt_len,
        "max_new": max_new,
        "motif_len": motif_len,
        "motif_alphabet": motif_alphabet,
        "max_draft_tokens": max_draft,
        "workload": (
            "repetitive-trace (tiled per-row small-alphabet motif), "
            "greedy"
        ),
    }
    for B in batches:
        off = arm(B, False, f"so{B}_")
        on = arm(B, True, f"sn{B}_")
        ratio = round(
            on["decode_toks_per_sec"]
            / max(off["decode_toks_per_sec"], 1e-9),
            3,
        )
        a = on.get("accept_rate", 0.0)
        tokens_per_pass = 1.0 + a * max_draft
        # measured verify cost in plain-decode-step units, backed out of
        # the A/B itself: on/off = tokens_per_pass / c
        c = tokens_per_pass / max(ratio, 1e-9)
        out[f"b{B}"] = {
            "spec_off": off,
            "spec_on": on,
            "spec_over_off": ratio,
            "verify_cost_over_decode_step": round(c, 3),
            "derived_min_accept_rate": round(
                spec_break_even_accept_rate(c, max_draft), 3
            ),
        }
    return out


def bench_prefill_ab(cfg, params, n_reqs=32, prompt_len=512, repeats=3):
    """Admission-path prefill A/B (VERDICT r5 #2: the in-round bench saw
    prefill fall 35.8k -> 23.8k tok/s at b32/512/0.5B between rounds with
    no attribution).  Three columns, each repeated ``repeats`` times:

    * ``jit``: one batched ``prefill`` call at [n_reqs, prompt_len] —
      the compute ceiling, no engine anywhere (r4 and r5 share this
      code, so if THIS column moved, the delta is the chip or its host, not
      the admission path);
    * ``engine_dense``: the engine's ``_admit`` wave (group dedup,
      shape bucketing, host bookkeeping, one completion fetch) with
      max_new=1 so every row finishes at admission and the wave repeats
      on a drained engine — the r4-equivalent admission path;
    * ``engine_paged_chunked``: the identical wave admitted through the
      paged fill queue in ``prefill_chunk_tokens`` chunks — the round-5
      addition, now issuing a wave's chunks back-to-back with no host
      round-trip between them when nothing is decoding.

    Per-repeat values are reported, not just a mean: a one-chip machine
    shares its host's cores, a single wave can swing run-to-run, and the
    jit column swings with it — ``spread`` vs the column DELTAS is what
    separates host noise from a real admission-path regression."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.engine.batching import bucket_len
    from areal_tpu.engine.inference_server import ContinuousBatchingEngine
    from areal_tpu.models.transformer import KVCache, prefill

    B, P = n_reqs, prompt_len
    T = bucket_len(P)
    rng = np.random.default_rng(13)
    toks = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, T)).astype(np.int32)
    )
    lens = jnp.full((B,), P, jnp.int32)
    positions = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (B, 1))
    seg = (positions < lens[:, None]).astype(jnp.int32)

    @jax.jit
    def jit_prefill(p, toks, positions, seg, lens):
        cache = KVCache.zeros(cfg, B, T, dtype=jnp.bfloat16)
        logits, _ = prefill(
            p, cfg, toks, positions, seg, cache,
            last_pos=jnp.maximum(lens - 1, 0),
        )
        return jnp.sum(logits)  # scalar fetch forces the whole call

    def time_jit():
        t0 = time.perf_counter()
        float(jit_prefill(params, toks, positions, seg, lens))
        return B * P / (time.perf_counter() - t0)

    float(jit_prefill(params, toks, positions, seg, lens))  # compile
    jit_rates = [round(time_jit(), 1) for _ in range(repeats)]

    def engine_rates(mode):
        kw = dict(cache_mode=mode)
        if mode == "paged":
            kw.update(page_size=1024, prefill_chunk_tokens=1024)
        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=B,
            kv_cache_len=bench_gen_cache_len(P, 4), chunk_size=128, **kw
        )

        def wave(tag):
            # max_new=1: rows sample their first token and finish AT
            # admission, so the wave repeats on a fully drained engine
            submit_wave(eng, cfg, B, P, 1, tag)
            t0 = time.perf_counter()
            while eng.has_work:
                eng.step()
            dt = time.perf_counter() - t0
            eng.drain_results()
            return B * P / dt

        wave(f"w{mode}")  # compile this mode's admission path
        rates = [round(wave(f"t{mode}{i}"), 1) for i in range(repeats)]
        del eng
        return rates

    dense_rates = engine_rates("dense")
    paged_rates = engine_rates("paged")
    return {
        "batch": B,
        "prompt_len": P,
        "jit_toks_per_sec": jit_rates,
        "engine_dense_toks_per_sec": dense_rates,
        "engine_paged_chunked_toks_per_sec": paged_rates,
        "best": {
            "jit": max(jit_rates),
            "engine_dense": max(dense_rates),
            "engine_paged_chunked": max(paged_rates),
        },
        "engine_dense_over_jit": round(
            max(dense_rates) / max(max(jit_rates), 1e-9), 3
        ),
    }


def bench_interruption(cfg, params, n_reqs=32, prompt_len=256):
    """Interruptible vs drain-before-update weight swaps under a
    heterogeneous-length workload (the reference ablates this mechanism at
    +12-17% throughput, blog/AReaL_v0_3.md:125).

    Both modes process the same requests and apply the same number of
    weight updates; 'interrupt' applies them mid-flight (in-flight KV
    recomputed under new weights), 'drain' holds each update until every
    in-flight row finishes (the non-interruptible server's behavior —
    the long tail stalls the swap and admissions behind it)."""
    lens = np.linspace(64, 768, n_reqs).astype(int)
    np.random.default_rng(7).shuffle(lens)  # interleave short/long rows
    total_updates = 3

    def run(mode):
        eng = make_engine(cfg, params, 16, prompt_len, int(lens.max()))
        submit_wave(eng, cfg, n_reqs, prompt_len, None, "w", lens=lens)
        # warmup must also compile the WEIGHT-SWAP path (batched re-prefill
        # of in-flight rows hits shape buckets the plain drain never sees)
        warm_updates = 0
        warm_tok = 0
        while eng.has_work:
            warm_tok += eng.step()
            if warm_updates < total_updates and warm_tok > (
                (warm_updates + 1) * n_reqs * 100
            ):
                eng.update_weights(params, version=warm_updates + 1)
                warm_updates += 1
        eng.drain_results()
        eng.version = 0
        submit_wave(eng, cfg, n_reqs, prompt_len, None, mode, lens=lens)
        updates_done = 0
        n_tok = 0
        t0 = time.perf_counter()
        visible_lat = []
        while eng.has_work:
            n_tok += eng.step()
            want_update = (
                updates_done < total_updates
                and n_tok > (updates_done + 1) * n_reqs * 100
            )
            if want_update:
                if mode == "drain":
                    # non-interruptible: hold admissions and wait for every
                    # in-flight row (the long tail stalls the swap)
                    eng.hold_admissions = True
                    while eng.n_inflight > 0 or eng.inflight_chunks > 0:
                        n_tok += eng.step()
                tu = time.perf_counter()
                eng.update_weights(params, version=updates_done + 1)
                # update applies at the next step; measure visibility
                while eng.version != updates_done + 1:
                    n_tok += eng.step()
                visible_lat.append(time.perf_counter() - tu)
                eng.hold_admissions = False
                updates_done += 1
        dt = time.perf_counter() - t0
        eng.drain_results()
        del eng
        return n_tok / dt, visible_lat

    tput_int, lat_int = run("interrupt")
    tput_drain, _ = run("drain")
    return {
        "interrupt_toks_per_sec": round(tput_int, 1),
        "drain_toks_per_sec": round(tput_drain, 1),
        "interrupt_gain": round(tput_int / max(tput_drain, 1e-9), 4),
        "update_visible_latency_s": round(float(np.mean(lat_int)), 3),
        "n_updates": total_updates,
    }


def _weight_swap_cfg():
    """Tiny greedy-decode model for the swap A/B: the mechanism under
    test — restore off the paused critical path vs on it — is
    model-size-independent, and the tiny tree keeps the CPU-smoke arm
    honest (both paths restore the SAME snapshot)."""
    from areal_tpu.models.config import TransformerConfig

    return TransformerConfig(
        n_layers=2, hidden_dim=64, n_q_heads=4, n_kv_heads=2,
        head_dim=32, intermediate_dim=128, vocab_size=512,
        max_position_embeddings=512, dtype="float32",
    )


def _weight_swap_measure_arm(
    arm, n_reqs=4, prompt_len=32, max_new=48, page=32, chunk=8,
    repeats=2, n_chips=2,
):
    """One ``weight_swap_ab`` arm (dense | paged_prefix | mesh): the
    FULL-reload swap (pause covers restore + transfer + flip) vs the
    STAGED swap (restore while decode continues; pause covers only
    ring-drain + pointer flip) on the same mid-generation workload, plus
    post-swap token parity against a fresh engine running the new
    weights.  Mirrors the engine half of the fleet protocol exactly
    (generation_server's stage thread + commit barrier drive the same
    engine calls)."""
    import shutil
    import tempfile
    import threading

    import jax

    from areal_tpu.engine import checkpoint
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.models import transformer

    cfg = _weight_swap_cfg()
    params0 = transformer.init_params(cfg, jax.random.PRNGKey(0))
    params1 = transformer.init_params(cfg, jax.random.PRNGKey(42))
    kw = dict(sampling=SamplingParams(greedy=True))
    if arm == "dense":
        kw.update(cache_mode="dense")
    elif arm == "paged_prefix":
        kw.update(
            cache_mode="paged", page_size=page,
            prefill_chunk_tokens=max(page, 64), prefix_cache=True,
        )
    elif arm == "mesh":
        from areal_tpu.base.topology import MeshSpec

        kw.update(
            cache_mode="paged", page_size=page,
            prefill_chunk_tokens=max(page, 64),
            mesh=MeshSpec(model=n_chips).make_mesh(
                jax.devices()[:n_chips]
            ),
        )
    else:
        raise ValueError(arm)
    pub = tempfile.mkdtemp(prefix="areal-swapab-")
    try:
        # two snapshots: ``same`` re-publishes the CURRENT weights (the
        # timed swaps are token-neutral, so the full and staged arms run
        # on byte-identical decode workloads), ``new`` carries genuinely
        # new weights for the final flip whose post-swap stream the
        # fresh-engine replay must reproduce
        snap_same = os.path.join(pub, "v_same")
        snap_new = os.path.join(pub, "v_new")
        checkpoint.save_params(params0, snap_same)
        checkpoint.write_manifest(params0, snap_same, version=0)
        checkpoint.save_params(params1, snap_new)
        checkpoint.write_manifest(params1, snap_new, version=1)
        # ONE engine per arm, seeded from a RESTORED tree: every tree the
        # engine ever holds (initial, full-swapped, staged) then shares
        # one committed-sharding jit variant, and the warm-up swap below
        # pays the re-prefill shape-bucket compiles — so the timed
        # windows measure the swap mechanism, not first-use compiles (a
        # long-lived server is past both after its first swap)
        eng = make_engine(
            cfg,
            checkpoint.load_params_like(params0, snap_same),
            n_reqs, prompt_len, max_new, chunk=chunk, **kw,
        )
        trigger = n_reqs * max_new // 4
        wave_n = [0]

        def wave(tag=None):
            wave_n[0] += 1
            submit_wave(
                eng, cfg, n_reqs, prompt_len, max_new,
                tag or f"w{wave_n[0]}{arm}",
            )

        def run_to_trigger():
            tok = 0
            while eng.has_work and tok < trigger:
                tok += eng.step()

        version = [0]

        def full_swap():
            version[0] += 1
            t0 = time.perf_counter()
            eng.pause()
            eng.step()  # quiesce the in-flight ring
            # the legacy path's restore happens INSIDE the pause
            p = checkpoint.load_params_like(eng.params, snap_same)
            eng.update_weights(p, version=version[0])
            eng.resume()
            while eng.version != version[0]:
                eng.step()
            return time.perf_counter() - t0

        def staged_swap(snap):
            version[0] += 1
            v, box = version[0], {}

            def _stage():
                try:
                    p = checkpoint.load_params_staged(
                        eng.params, snap, chunk_bytes=1 << 20
                    )
                    eng.stage_weights(p, v)
                except Exception as e:  # noqa: BLE001 - reported
                    box["error"] = repr(e)

            th = threading.Thread(target=_stage, daemon=True)
            t_st, tok = time.perf_counter(), 0
            th.start()
            while th.is_alive():
                tok += eng.step()  # decode CONTINUES during staging
            th.join()
            if "error" in box:
                raise RuntimeError(box["error"])
            stage_s = time.perf_counter() - t_st
            t0 = time.perf_counter()
            eng.pause()
            eng.step()
            eng.commit_staged(expected_version=v)
            eng.resume()
            while eng.version != v:
                eng.step()
            return (
                time.perf_counter() - t0,
                stage_s,
                tok / max(stage_s, 1e-9),
            )

        # warm-up swap: compiles the ring-drain/re-prefill buckets once
        wave()
        run_to_trigger()
        full_swap()
        drain(eng)
        fulls, stageds, stage_ss, stage_tps, before_tps = [], [], [], [], []
        for _ in range(repeats):
            wave()
            run_to_trigger()
            t_b, tok_b = time.perf_counter(), 0
            while eng.has_work and tok_b < n_reqs * chunk:
                tok_b += eng.step()
            before_tps.append(tok_b / max(time.perf_counter() - t_b, 1e-9))
            fulls.append(full_swap())
            drain(eng)
            wave()
            run_to_trigger()
            p_s, s_s, s_tps = staged_swap(snap_same)
            stageds.append(p_s)
            stage_ss.append(s_s)
            stage_tps.append(s_tps)
            drain(eng)
        # the REAL flip: staged swap to the NEW weights mid-wave, then a
        # post-swap wave whose greedy stream a fresh engine running the
        # new weights must reproduce token-for-token
        wave()
        run_to_trigger()
        staged_swap(snap_new)
        drain(eng)
        eng.drain_results()
        submit_wave(eng, cfg, n_reqs, prompt_len, max_new, f"p{arm}")
        while eng.has_work:
            eng.step()
        post = {
            q: list(o.output_ids) for q, o in eng.drain_results().items()
        }
        del eng
        fresh = make_engine(
            cfg,
            checkpoint.load_params_like(params1, snap_new),
            n_reqs, prompt_len, max_new, chunk=chunk, **kw,
        )
        submit_wave(fresh, cfg, n_reqs, prompt_len, max_new, f"p{arm}")
        while fresh.has_work:
            fresh.step()
        ref = {
            q: list(o.output_ids)
            for q, o in fresh.drain_results().items()
        }
        del fresh
        full_pause = min(fulls)
        staged_pause = min(stageds)
        return {
            "full_pause_ms": round(full_pause * 1e3, 1),
            "staged_pause_ms": round(staged_pause * 1e3, 1),
            "staged_stage_ms": round(min(stage_ss) * 1e3, 1),
            "pause_ratio": round(staged_pause / max(full_pause, 1e-9), 4),
            "staged_below_full": bool(staged_pause < full_pause),
            "decode_tps_before": round(float(np.mean(before_tps)), 1),
            "decode_tps_during_stage": round(float(np.mean(stage_tps)), 1),
            "post_swap_parity": bool(post == ref),
        }
    finally:
        shutil.rmtree(pub, ignore_errors=True)


def bench_weight_swap_ab(
    n_reqs=4, prompt_len=32, max_new=48, page=32, chunk=8, repeats=2,
    mesh_chips=2,
):
    """Zero-downtime weight sync A/B (ISSUE 8's acceptance bench): the
    staged (stage-while-decoding -> pointer-flip commit) swap against
    the legacy full reload, per serving arm — pause-ms, decode tok/s
    around the swap, and post-swap fresh-replay token parity.  The mesh
    arm needs ``mesh_chips`` devices in this process and is reported as
    not run otherwise."""
    import jax

    shape = dict(
        n_reqs=n_reqs, prompt_len=prompt_len, max_new=max_new,
        page=page, chunk=chunk, repeats=repeats,
    )
    out = {"backend": jax.default_backend()}
    for arm in ("dense", "paged_prefix"):
        try:
            out[arm] = _weight_swap_measure_arm(arm, **shape)
        except Exception as e:  # noqa: BLE001 - an arm failure is data
            out[arm] = {"error": f"{type(e).__name__}: {e}"[:300]}
    if len(jax.devices()) >= mesh_chips:
        try:
            out["mesh"] = _weight_swap_measure_arm(
                "mesh", n_chips=mesh_chips, **shape
            )
        except Exception as e:  # noqa: BLE001
            out["mesh"] = {"error": f"{type(e).__name__}: {e}"[:300]}
    else:
        out["mesh"] = _too_few_chips(mesh_chips)
    arms_ok = [
        v for k, v in out.items()
        if isinstance(v, dict) and "staged_below_full" in v
    ]
    out["staged_below_full_all"] = bool(arms_ok) and all(
        v["staged_below_full"] for v in arms_ok
    )
    out["post_swap_parity_all"] = bool(arms_ok) and all(
        v.get("post_swap_parity") for v in arms_ok
    )
    return out



def _sharded_serving_cfgs(on_tpu: bool):
    """(dense_cfg, moe_cfg) for the sharded-serving A/B.  Small even on
    TPU: the section measures the SCALING of the sharded engine (mesh
    collectives + EP dispatch on the hot path), not peak model tok/s —
    the other generation sections own that."""
    import dataclasses

    from areal_tpu.models.config import TransformerConfig

    if on_tpu:
        dense = TransformerConfig(
            n_layers=8, hidden_dim=1024, n_q_heads=8, n_kv_heads=4,
            head_dim=128, intermediate_dim=2816, vocab_size=32768,
            max_position_embeddings=4096, dtype="bfloat16",
        )
    else:
        dense = TransformerConfig(
            n_layers=2, hidden_dim=64, n_q_heads=4, n_kv_heads=2,
            head_dim=32, intermediate_dim=128, vocab_size=512,
            max_position_embeddings=512, dtype="float32",
        )
    moe = dataclasses.replace(
        dense,
        intermediate_dim=dense.intermediate_dim // 2,
        moe_intermediate_dim=dense.intermediate_dim // 2,
        n_experts=4,
        n_experts_per_tok=2,
        moe_aux_loss_coef=0.01,
        moe_z_loss_coef=0.001,
    )
    return dense, moe


def _sharded_serving_measure(
    n_chips=2, n_reqs=4, prompt_len=32, max_new=32, page=32, chunk=8
):
    """Decode tok/s at 1 vs ``n_chips`` chips for a dense-TP arm and a
    moe-EP arm, with token parity between the two engines asserted as
    data (greedy decode: the sharded engine must reproduce the
    single-chip stream exactly)."""
    import jax

    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.models import transformer

    on_tpu = jax.default_backend() == "tpu"
    dense_cfg, moe_cfg = _sharded_serving_cfgs(on_tpu)
    out = {"n_chips": n_chips, "backend": jax.default_backend()}

    def run(eng, cfg, tag, parity_tag):
        submit_wave(eng, cfg, n_reqs, prompt_len, max_new, f"w{tag}")
        drain(eng)  # warm: compiles included here, not in the timing
        submit_wave(eng, cfg, n_reqs, prompt_len, max_new, f"t{tag}")
        t0 = time.perf_counter()
        n = drain(eng)
        dt = time.perf_counter() - t0
        # parity wave: SAME tag (= same prompts/qids) on both engines so
        # the sharded stream is compared token-for-token
        submit_wave(eng, cfg, n_reqs, prompt_len, max_new, parity_tag)
        while eng.has_work:
            eng.step()
        outs = eng.drain_results()
        return n / max(dt, 1e-9), {
            q: list(o.output_ids) for q, o in outs.items()
        }

    for arm, cfg, spec in (
        ("dense_tp", dense_cfg, MeshSpec(model=n_chips)),
        ("moe_ep", moe_cfg, MeshSpec(expert=n_chips)),
    ):
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        kw = dict(
            sampling=SamplingParams(greedy=True),
            cache_mode="paged", page_size=page,
            prefill_chunk_tokens=max(page, 64),
        )
        e1 = make_engine(
            cfg, params, n_reqs, prompt_len, max_new, chunk=chunk, **kw
        )
        tps1, toks1 = run(e1, cfg, f"{arm}1", f"p{arm}")
        del e1
        mesh = spec.make_mesh(jax.devices()[:n_chips])
        eN = make_engine(
            cfg, params, n_reqs, prompt_len, max_new, chunk=chunk,
            mesh=mesh, **kw,
        )
        row = {
            "chips1_decode_toks_per_sec": round(tps1, 1),
        }
        if arm == "moe_ep":
            w = eN.params["layers"]["mlp"]["experts"]["gate"]
            # sharded for real, never silently replicated (acceptance
            # criterion: shard_shape != shape)
            row["expert_shard_ok"] = bool(
                w.sharding.shard_shape(w.shape) != w.shape
            )
        tpsN, toksN = run(eN, cfg, f"{arm}N", f"p{arm}")
        del eN
        row[f"chips{n_chips}_decode_toks_per_sec"] = round(tpsN, 1)
        row["scaling_x"] = round(tpsN / max(tps1, 1e-9), 3)
        row["token_parity"] = toks1 == toksN
        out[arm] = row
    return out


def bench_sharded_serving(
    n_chips=2, n_reqs=4, prompt_len=32, max_new=32, page=32, chunk=8
):
    """Sharded-serving scaling A/B (ROADMAP item 1's bench): decode tok/s
    at 1 vs N chips, dense-TP and moe-EP arms.

    Needs ``n_chips`` devices in this process; reported as not run
    otherwise."""
    import jax

    if len(jax.devices()) < n_chips:
        return _too_few_chips(n_chips)
    return _sharded_serving_measure(
        n_chips=n_chips, n_reqs=n_reqs, prompt_len=prompt_len,
        max_new=max_new, page=page, chunk=chunk,
    )


def bench_gateway_ab(
    cfg,
    params,
    n_bulk=8,
    n_interactive=8,
    prompt_len=128,
    bulk_new=256,
    inter_new=16,
    page=32,
    chunk=16,
    max_batch=4,
    max_steps=6000,
):
    """Serving-gateway A/B: an interactive SSE burst landing on a
    2-engine fleet mid bulk-rollout storm, tenant admission ON vs OFF.

    The load shape is the gateway's worst case: ``n_bulk`` long
    bulk-tenant generations claim the fleet's cache rows first, then
    ``n_interactive`` short interactive streams burst in.  Admission
    OFF, every bulk request admits and the burst queues behind the
    storm (TTFT ~ the bulk generation length).  Admission ON, the bulk
    tenant's token bucket caps the storm at half the fleet's rows
    (typed ``rate_limited`` rejects for the rest — the 429s a real
    client would retry) and stamps priority classes, so the burst finds
    free rows immediately.  The diffable win is interactive p99 TTFT
    (steps is the deterministic unit; wall seconds reported alongside);
    the acceptance bar is STRICTLY better p99 with admission on, plus
    SSE-stream/rollout-path token parity and a zero-leak block audit on
    every engine of both arms."""
    import zlib

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.gateway.admission import AdmissionPlane, TenantPolicy
    from areal_tpu.gateway.server import (
        EngineBackend,
        estimate_tokens,
        run_request,
    )

    cache_len = bench_gen_cache_len(prompt_len, bulk_new)
    bulk_est = estimate_tokens(prompt_len, bulk_new)
    inter_est = estimate_tokens(prompt_len, inter_new)

    def prompt_ids(tag):
        rng = np.random.default_rng(zlib.crc32(tag.encode()))
        return rng.integers(0, cfg.vocab_size, (prompt_len,)).tolist()

    def ginp(qid, ids, max_new):
        return APIGenerateInput(
            qid=qid,
            prompt_ids=list(ids),
            input_ids=list(ids),
            gconfig=GenerationHyperparameters(
                max_new_tokens=max_new, greedy=True
            ),
        )

    def pristine(eng):
        eng.step()
        eng.step()
        if eng._prefix_cache is not None:
            eng._prefix_cache.flush()
        return bool(
            eng.free_pool_blocks == eng.n_blocks
            and (np.asarray(eng._block_ref) == 0).all()
        )

    def mk_fleet():
        engines = {}
        for name in ("srv0", "srv1"):
            eng = make_engine(
                cfg, params, max_batch, prompt_len, bulk_new, chunk=chunk,
                cache_mode="paged",
                page_size=page,
                kv_pool_tokens=(max_batch + 1) * cache_len,
                sampling=SamplingParams(greedy=True),
            )
            eng.park_ttl_steps = 0  # fresh qids never resume: no parked rows
            engines[name] = eng
        return engines

    def _pct(vals, q):
        return round(float(np.percentile(np.asarray(vals, float), q)), 4)

    def arm(admission, tag):
        engines = mk_fleet()
        plane = None
        if admission:
            plane = AdmissionPlane([
                # the storm's cap: a bucket holding half the storm up
                # front, refilling too slowly to matter inside the bench
                TenantPolicy(
                    "bulk_load",
                    priority="bulk",
                    rate_tokens_per_s=1e-6,
                    burst_tokens=(n_bulk // 2) * bulk_est,
                ),
                TenantPolicy("interactive", priority="interactive"),
            ])
        backend = EngineBackend(engines, plane=plane)

        # warm the prefill/decode jits out of the TTFT measurement
        for name in engines:
            backend.submit(
                ginp(f"{tag}-warm-{name}", prompt_ids(f"{tag}w{name}"), 2),
                "interactive", "", False,
            )
        for _ in range(max_steps):
            backend.pump_once()
            if not backend.has_work():
                break
        for eng in engines.values():
            eng.drain_results()

        # the bulk storm claims rows first
        bulk_admitted = 0
        bulk_rejects = {}
        for i in range(n_bulk):
            dec = backend.admit("bulk_load", bulk_est)
            if dec["ok"]:
                bulk_admitted += 1
                backend.submit(
                    ginp(f"{tag}-bulk{i}", prompt_ids(f"{tag}b{i}"),
                         bulk_new),
                    "bulk_load", dec.get("priority", ""), False,
                )
            else:
                bulk_rejects[dec["reason"]] = (
                    bulk_rejects.get(dec["reason"], 0) + 1
                )
        for _ in range(3):  # storm settles into its cache rows
            backend.pump_once()

        # the interactive burst: SSE-style streamed requests, TTFT = the
        # first drained stream chunk
        handles = {}
        t_submit = {}
        for i in range(n_interactive):
            qid = f"{tag}-int{i}"
            dec = backend.admit("interactive", inter_est)
            assert dec["ok"], dec
            t_submit[qid] = time.perf_counter()
            handles[qid] = backend.submit(
                ginp(qid, prompt_ids(f"{tag}i{i}"), inter_new),
                "interactive", dec.get("priority", ""), True,
            )
        ttft_steps = {}
        ttft_s = {}
        streams = {qid: [] for qid in handles}
        done = set()
        for step in range(1, max_steps + 1):
            backend.pump_once()
            for qid, h in handles.items():
                if qid in done:
                    continue
                r = backend.poll(h)
                toks = r.get("tokens") or []
                if toks and qid not in ttft_steps:
                    ttft_steps[qid] = step
                    ttft_s[qid] = time.perf_counter() - t_submit[qid]
                streams[qid].extend(toks)
                if r.get("done"):
                    done.add(qid)
                    backend.finish(
                        h, len(streams[qid]) + prompt_len, inter_est
                    )
            if len(done) == n_interactive:
                break
        else:
            raise RuntimeError("interactive burst did not drain")
        # drain the surviving storm, then audit for leaks
        for _ in range(max_steps):
            if not backend.has_work():
                break
            backend.pump_once()
        for eng in engines.values():
            eng.drain_results()
        row = {
            "bulk_admitted": int(bulk_admitted),
            "bulk_rejects": bulk_rejects,
            "interactive_ttft_steps": {
                "p50": _pct(list(ttft_steps.values()), 50),
                "p99": _pct(list(ttft_steps.values()), 99),
                "max": max(ttft_steps.values()),
            },
            "interactive_ttft_s": {
                "p50": _pct(list(ttft_s.values()), 50),
                "p99": _pct(list(ttft_s.values()), 99),
            },
            "interactive_tokens": int(sum(len(s) for s in streams.values())),
            "leak_free": all(pristine(e) for e in engines.values()),
        }
        if plane is not None:
            row["tenants"] = plane.stats()
        return row

    def parity():
        """Greedy token identity across the three read paths: the SSE
        stream's chunk concat, the request's final result, and a plain
        rollout-style submission of the same prompt."""
        eng = make_engine(
            cfg, params, 2, prompt_len, inter_new, chunk=chunk,
            cache_mode="paged", page_size=page,
            kv_pool_tokens=4 * bench_gen_cache_len(prompt_len, inter_new),
            # no prefix cache: a radix hit would prefill only the suffix,
            # and the changed reduction order can flip near-tied argmax
            # on tiny models — parity wants bit-identical prefills
            prefix_cache=False,
            sampling=SamplingParams(greedy=True),
        )
        eng.park_ttl_steps = 0
        backend = EngineBackend({"srv": eng})
        ids = prompt_ids("parity")
        chunks = []
        out = run_request(
            backend, ginp("par-gw", ids, inter_new),
            "interactive", "interactive",
            stream=True, on_chunk=chunks.append,
            pump=backend.pump_once,
        )
        concat = [t for c in chunks for t in c]
        eng.submit(ginp("par-rollout", ids, inter_new))
        while eng.has_work:
            eng.step()
        rollout = eng.drain_results()["par-rollout"]
        return {
            "stream_concat_matches_result": bool(
                concat == list(out["result"]["output_ids"])
            ),
            "gateway_matches_rollout": bool(
                list(out["result"]["output_ids"])
                == list(rollout.output_ids)
            ),
            "leak_free": pristine(eng),
        }

    def two_gateways(n_requests=12, cap=5):
        """ROADMAP item 1(c) nibble: TWO gateway front doors (two
        ``FleetBackend``s, each with its own manager connection — the
        two-``GatewayWorker`` deployment shape) share ONE real
        manager's admission plane over the combined ``gateway_submit``
        RPC.  The capped tenant's bucket holds exactly ``cap``
        requests up front and refills too slowly to matter inside the
        bench, so with both gateways racing from their own threads the
        plane must admit EXACTLY ``cap`` across the pair — one
        over-admit means a decision escaped the plane's lock.  Pure
        control plane: no engines; admitted requests dispatch to
        null gen-server clients."""
        import threading

        from areal_tpu.api.system_api import GserverManagerConfig
        from areal_tpu.base import logging_ as logging_mod
        from areal_tpu.base.monitor import RolloutStat
        from areal_tpu.gateway.server import FleetBackend
        from areal_tpu.system.gserver_manager import (
            GserverManager,
            GserverManagerClient,
        )

        est = float(estimate_tokens(prompt_len, inter_new))
        m = GserverManager.__new__(GserverManager)
        m.config = GserverManagerConfig(
            schedule_policy="least_requests",
            n_servers=4,
            serve_mode="router",
            tenants=[
                dict(
                    name="capped",
                    priority="bulk",
                    rate_tokens_per_s=1e-6,
                    burst_tokens=cap * est,
                ),
                dict(name="interactive", priority="interactive"),
            ],
        )
        m.server_addrs = [f"2gw-fs{i}" for i in range(4)]
        m.logger = logging_mod.getLogger("bench-2gw")
        m._round_robin = 0
        m._qid_server = {}
        m._server_load = {a: 0 for a in m.server_addrs}
        m._server_tokens = {a: 0.0 for a in m.server_addrs}
        m._server_devices = {a: 1 for a in m.server_addrs}
        m._server_mesh = {a: "" for a in m.server_addrs}
        m._qid_tokens = {}
        m._group_server = {}
        m._group_prefix = {}
        m._group_tokens = {}
        m.rollout_stat = RolloutStat()
        m._model_version = 0
        m._expr, m._trial = "bench-2gw", "t0"
        m._clients = {}
        m._init_metrics()
        import zmq as _zmq

        m._serve_mode = "router"
        m._ctx = _zmq.Context.instance()
        m._sock = m._ctx.socket(_zmq.ROUTER)
        port = m._sock.bind_to_random_port("tcp://127.0.0.1")
        m.addr = f"127.0.0.1:{port}"

        stop = threading.Event()

        def serve():
            while not stop.is_set():
                if m._sock.poll(timeout=10):
                    m._serve()

        st = threading.Thread(target=serve, daemon=True,
                              name="2gw-serve")
        st.start()

        class _NullGenClient:
            """Admitted requests have nowhere real to go — the arm
            measures the admission plane, not generation."""

            def call(self, cmd, payload, timeout=None):
                return {}

            def close(self):
                pass

        results = {}
        errors = []
        lock = threading.Lock()
        barrier = threading.Barrier(3)

        def gateway(gname):
            client = GserverManagerClient(addr=m.addr, timeout=60.0)
            backend = FleetBackend(
                client, client_factory=lambda addr: _NullGenClient()
            )
            admitted = rejected = inter_ok = 0
            try:
                barrier.wait()
                for i in range(n_requests):
                    dec, handle = backend.admit_and_submit(
                        ginp(f"{gname}-cap{i}",
                             prompt_ids(f"{gname}c{i}"), inter_new),
                        "capped", est, False,
                    )
                    if dec.get("ok"):
                        admitted += 1
                        assert handle and handle["url"], handle
                    else:
                        rejected += 1
                        assert dec.get("reason") == "rate_limited", dec
                    # the uncapped tenant proves this front door stays
                    # live even after its capped traffic is throttled
                    dec2, h2 = backend.admit_and_submit(
                        ginp(f"{gname}-int{i}",
                             prompt_ids(f"{gname}n{i}"), inter_new),
                        "interactive", est, False,
                    )
                    if dec2.get("ok") and h2:
                        inter_ok += 1
            except Exception as e:  # noqa: BLE001 - becomes arm data
                with lock:
                    errors.append(
                        f"{gname}: {type(e).__name__}: {e}"[:200]
                    )
            finally:
                client.close()
            with lock:
                results[gname] = {
                    "capped_admitted": admitted,
                    "capped_rejected": rejected,
                    "interactive_admitted": inter_ok,
                }

        threads = [
            threading.Thread(target=gateway, args=(g,), daemon=True,
                             name=f"2gw-{g}")
            for g in ("gw0", "gw1")
        ]
        for t in threads:
            t.start()
        barrier.wait()
        for t in threads:
            t.join(timeout=120.0)
        stop.set()
        st.join(timeout=5.0)
        m._sock.close(linger=0)

        total = sum(r["capped_admitted"] for r in results.values())
        row = {
            "n_requests_per_gateway": n_requests,
            "capped_tenant_slots": cap,
            "per_gateway": results,
            "total_capped_admitted": int(total),
            # THE acceptance bar: admission stayed atomic across the
            # two front doors — the shared bucket filled exactly, never
            # over
            "no_tenant_over_admit": bool(total == cap and not errors),
            "both_gateways_served": bool(
                len(results) == 2
                and all(
                    r["interactive_admitted"] == n_requests
                    for r in results.values()
                )
            ),
            "plane_tenants": m._admission.stats(),
        }
        if errors:
            row["errors"] = errors[:3]
        return row

    out = {
        "n_bulk": n_bulk,
        "n_interactive": n_interactive,
        "prompt_len": prompt_len,
        "bulk_new": bulk_new,
        "inter_new": inter_new,
        "max_batch_per_engine": max_batch,
        "admission_on": arm(True, "on"),
        "admission_off": arm(False, "off"),
        "parity": parity(),
        "two_gateways": two_gateways(),
    }
    on_p99 = out["admission_on"]["interactive_ttft_steps"]["p99"]
    off_p99 = out["admission_off"]["interactive_ttft_steps"]["p99"]
    out["p99_ttft_steps_improvement"] = round(off_p99 / max(on_p99, 1), 2)
    out["interactive_p99_ttft_better_with_admission"] = bool(
        on_p99 < off_p99
    )
    out["leak_free"] = bool(
        out["admission_on"]["leak_free"]
        and out["admission_off"]["leak_free"]
        and out["parity"]["leak_free"]
    )
    out["no_tenant_over_admit"] = bool(
        out["two_gateways"]["no_tenant_over_admit"]
    )
    return out


def bench_control_plane_ab(
    n_servers=64,
    n_groups=48,
    group_size=16,
    n_gateway=96,
    n_threads=16,
    prompt_len=128,
    new_tokens=64,
    update_rpc_s=0.05,
):
    """Manager control-plane A/B: schedules/sec and p99 schedule wait
    under a mixed rollout+gateway storm at ``n_servers`` registered
    fake servers, across the two serve loops (strict-lockstep REP vs
    batched ROUTER) and the two pick paths (O(N) scan vs O(log N)
    incremental indexes).  Pure CPU — no engine, no real gen servers:
    the managers are hand-built with fake addresses but serve over
    REAL ZMQ sockets with real threaded ``GserverManagerClient``s, so
    the arms measure the actual wire + serve-loop + scheduling stack.

    Storm shape: ``n_groups`` rollout groups of ``group_size`` siblings
    plus ``n_gateway`` interactive requests, spread over ``n_threads``
    client threads.  The baseline arms issue one RPC per sibling and
    an admit+schedule RPC pair per gateway request (the pre-batching
    client protocol); the fully-optimized arm issues one
    ``schedule_batch`` per group and one combined ``gateway_submit``
    per gateway request.  Every arm also gets the SAME mid-storm
    weight-update publication (real ``_flush_and_update`` fan-out over
    fake per-server clients whose RPCs sleep ``update_rpc_s``): the
    rep arms pay it INLINE on the serve thread — the pre-ROUTER
    behavior — while the router arms run it on the update pool, so
    scheduling never stalls.  The acceptance bar is >= 5x
    schedules/sec for router+indexed+batched vs rep+scan+unbatched;
    ``parity`` reports scan-vs-indexed pick identity over a
    deterministic mixed trace for all three policies (the exhaustive
    version is a tier-1 property test)."""
    import queue as queue_mod
    import threading

    from areal_tpu.api.system_api import GserverManagerConfig
    from areal_tpu.base import logging_
    from areal_tpu.base.monitor import RolloutStat
    from areal_tpu.system.gserver_manager import (
        GserverManager,
        GserverManagerClient,
    )

    class _FakeGenClient:
        """Stands in for a GenServerClient during the weight-update
        fan-out: every RPC just sleeps the configured latency."""

        def call(self, cmd, payload, timeout=None):
            time.sleep(update_rpc_s)
            if cmd == "update_weights":
                return {"num_interrupted": 0}
            return {}

    def mk_manager(serve_mode, indexed, policy="least_requests",
                   bind=True):
        m = GserverManager.__new__(GserverManager)
        m.config = GserverManagerConfig(
            schedule_policy=policy,
            n_servers=n_servers,
            serve_mode=serve_mode,
            routing_index=indexed,
        )
        m.server_addrs = [f"fs{i}" for i in range(n_servers)]
        m.logger = logging_.getLogger("bench-cp")
        m._round_robin = 0
        m._qid_server = {}
        m._server_load = {a: 0 for a in m.server_addrs}
        m._server_tokens = {a: 0.0 for a in m.server_addrs}
        m._server_devices = {a: 1 for a in m.server_addrs}
        m._server_mesh = {a: "" for a in m.server_addrs}
        m._qid_tokens = {}
        m._group_server = {}
        m._group_prefix = {}
        m._group_tokens = {}
        m.rollout_stat = RolloutStat()
        m._model_version = 0
        m._expr, m._trial = "bench-cp", f"{serve_mode}-{int(indexed)}"
        m._clients = {a: _FakeGenClient() for a in m.server_addrs}
        m._init_metrics()
        if bind:
            import zmq as _zmq

            m._serve_mode = serve_mode
            m._ctx = _zmq.Context.instance()
            m._sock = m._ctx.socket(
                _zmq.ROUTER if serve_mode == "router" else _zmq.REP
            )
            port = m._sock.bind_to_random_port("tcp://127.0.0.1")
            m.addr = f"127.0.0.1:{port}"
        return m

    def _pct(vals, q):
        return round(float(np.percentile(np.asarray(vals, float), q)), 6)

    est_tokens = float(prompt_len + new_tokens)
    n_schedules = n_groups * group_size + n_gateway

    def run_arm(serve_mode, indexed, batched):
        m = mk_manager(serve_mode, indexed)
        stop = threading.Event()
        fire_update = threading.Event()
        update_info = {"version": 1, "path": "bench-ckpt-v1",
                       "format": "hf"}

        def serve():
            # the worker's _poll loop, minus the scrapes: serve, then
            # kick a published weight update when one appears.  Blocking
            # on the socket (instead of NOBLOCK-spinning) keeps the GIL
            # free for the in-process client threads — in deployment
            # the manager is its own process and never shares one.
            fired = False
            while not stop.is_set():
                if m._sock.poll(timeout=10):
                    m._serve()
                if fire_update.is_set() and not fired:
                    fired = True
                    # rep mode: runs INLINE right here, stalling every
                    # queued schedule; router mode: hops to the update
                    # pool and this loop keeps serving
                    m._start_weight_update(update_info)

        st = threading.Thread(target=serve, daemon=True,
                              name=f"cp-serve-{serve_mode}")
        st.start()

        jobs = queue_mod.Queue()
        for g in range(n_groups):
            jobs.put(("rollout", g))
        for i in range(n_gateway):
            jobs.put(("gateway", i))
        waits = []  # one entry per LOGICAL schedule decision
        rpcs = [0]
        lock = threading.Lock()
        errors = []
        barrier = threading.Barrier(n_threads + 1)

        def worker():
            client = GserverManagerClient(addr=m.addr, timeout=60.0)
            try:
                barrier.wait()
                while True:
                    try:
                        kind, i = jobs.get_nowait()
                    except queue_mod.Empty:
                        return
                    local, n_rpc = [], 0
                    if kind == "rollout":
                        qids = [f"r{i}-{j}" for j in range(group_size)]
                        if batched:
                            t0 = time.perf_counter()
                            out = client.call("schedule_batch", {
                                "qids": qids,
                                "prompt_len": prompt_len,
                                "new_token_budget": new_tokens,
                            })
                            dt = time.perf_counter() - t0
                            n_rpc += 1
                            assert len(out["responses"]) == group_size
                            local = [dt] * group_size
                        else:
                            for q in qids:
                                t0 = time.perf_counter()
                                client.call("schedule_request", {
                                    "qid": q,
                                    "prompt_len": prompt_len,
                                    "new_token_budget": new_tokens,
                                })
                                local.append(time.perf_counter() - t0)
                                n_rpc += 1
                    else:
                        qid = f"gw{i}"
                        t0 = time.perf_counter()
                        if batched:
                            resp = client.call("gateway_submit", {
                                "tenant": "interactive",
                                "tokens": est_tokens,
                                "qid": qid,
                                "prompt_len": prompt_len,
                                "new_token_budget": new_tokens,
                            })
                            n_rpc += 1
                            assert resp["ok"] and resp["schedule"]["url"]
                        else:
                            dec = client.call("gateway_admit", {
                                "tenant": "interactive",
                                "tokens": est_tokens,
                            })
                            assert dec["ok"]
                            client.call("schedule_request", {
                                "qid": qid,
                                "prompt_len": prompt_len,
                                "new_token_budget": new_tokens,
                            })
                            n_rpc += 2
                        local = [time.perf_counter() - t0]
                    with lock:
                        waits.extend(local)
                        rpcs[0] += n_rpc
            except Exception as e:  # noqa: BLE001 - becomes arm data
                with lock:
                    errors.append(f"{type(e).__name__}: {e}"[:200])
            finally:
                client.close()

        threads = [
            threading.Thread(target=worker, daemon=True,
                             name=f"cp-client-{t}")
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        batch_sum0, batch_cnt0 = m._m_ctl_batch.snapshot()
        barrier.wait()
        t0 = time.perf_counter()
        fire_update.set()  # the update publishes as the storm lands
        for t in threads:
            t.join(timeout=120.0)
        wall = time.perf_counter() - t0
        # router arms: let the async update finish before teardown so
        # both arms end at the bumped version (proves it really ran)
        deadline = time.monotonic() + 60.0
        while (
            getattr(m, "_weight_update_fut", None) is not None
            and not m._weight_update_fut.done()
            and time.monotonic() < deadline
        ):
            time.sleep(0.005)
        m._harvest_weight_update()
        stop.set()
        st.join(timeout=5.0)
        batch_sum1, batch_cnt1 = m._m_ctl_batch.snapshot()
        pool = getattr(m, "_update_pool", None)
        if pool is not None:
            pool.shutdown(wait=False)
        m._sock.close(linger=0)
        row = {
            "schedules_per_sec": round(n_schedules / max(wall, 1e-9), 1),
            "wall_s": round(wall, 4),
            "rpcs": int(rpcs[0]),
            "schedule_wait_s": {
                "p50": _pct(waits, 50),
                "p99": _pct(waits, 99),
            } if waits else None,
            "scheduled": len(waits),
            "model_version_after": int(m._model_version),
        }
        if serve_mode == "router" and batch_cnt1 > batch_cnt0:
            row["mean_serve_batch"] = round(
                (batch_sum1 - batch_sum0) / (batch_cnt1 - batch_cnt0), 2
            )
        if errors:
            row["errors"] = errors[:3]
        return row

    def parity():
        """Scan-vs-indexed pick identity over one deterministic mixed
        trace (schedules with group collisions, releases, direct
        load/token writes) per policy."""
        import random

        out = {}
        for policy in ("least_requests", "least_token_usage",
                       "round_robin"):
            seqs = []
            for indexed in (False, True):
                m = mk_manager("rep", indexed, policy=policy, bind=False)
                rng = random.Random(1234)
                seq, live = [], []
                for step in range(400):
                    op = rng.random()
                    if op < 0.6 or not live:
                        g = rng.randrange(120)
                        qid = f"g{g}-{rng.randrange(group_size)}"
                        r = m._schedule_request(
                            qid, rng.randrange(1, 256),
                            rng.randrange(1, 128),
                        )
                        seq.append(r["url"])
                        live.append(qid)
                    elif op < 0.85:
                        m._release_scheduled(
                            live.pop(rng.randrange(len(live)))
                        )
                    else:
                        # direct operator/test-style map writes: the
                        # observed dicts must keep the index honest
                        a = m.server_addrs[
                            rng.randrange(len(m.server_addrs))
                        ]
                        m._server_tokens[a] = (
                            m._server_tokens[a] + 48.0
                        )
                        m._server_load[a] = m._server_load[a] + 1
                seqs.append(seq)
            out[policy] = bool(seqs[0] == seqs[1])
        return out

    arms = {
        "rep_scan": run_arm("rep", indexed=False, batched=False),
        "rep_indexed": run_arm("rep", indexed=True, batched=False),
        "router_scan": run_arm("router", indexed=False, batched=False),
        "router_indexed": run_arm("router", indexed=True, batched=True),
    }
    par = parity()
    base = arms["rep_scan"]["schedules_per_sec"]
    opt = arms["router_indexed"]["schedules_per_sec"]
    return {
        "n_servers": n_servers,
        "n_groups": n_groups,
        "group_size": group_size,
        "n_gateway": n_gateway,
        "n_threads": n_threads,
        "n_schedules": n_schedules,
        **arms,
        "speedup": round(opt / max(base, 1e-9), 2),
        "meets_5x": bool(opt >= 5.0 * base),
        "parity": par,
        "routing_parity": bool(all(par.values())),
    }


#: per-section outcomes for the machine-parseable summary:
#: {name: {"status": "ok"|"error"|"timeout", "seconds": wall}}.  A round
#: that loses sections still reports WHICH ones and why.
_SECTION_STATUS = {}

#: one line per section that failed or timed out (named or not); the
#: summary is still printed, then ``main`` exits non-zero if any
_SECTION_FAILURES = []

#: default per-section time limit; generous because a cold section may
#: pay multiple fresh XLA compiles
SECTION_TIMEOUT_S = 900.0


def _section(fn, *args, name=None, timeout_s=None, **kw):
    """Run one bench section.  A failure becomes DATA (an error string in
    the summary) so the other sections' numbers are still printed — and
    is recorded in ``_SECTION_FAILURES`` so the run exits non-zero after
    the summary.  With ``name`` the section runs under a time limit
    (``areal_tpu.base.watchdog.run_bounded``) and its outcome lands in
    the summary's per-section ``status`` table."""
    import traceback

    label = name or getattr(fn, "__name__", "section")
    if name is None:
        try:
            return fn(*args, **kw)
        except Exception as e:  # noqa: BLE001 - reported, then rc != 0
            traceback.print_exc()
            err = f"{type(e).__name__}: {e}"[:300]
            _SECTION_FAILURES.append(f"{label}: {err}")
            return {"error": err}

    from areal_tpu.base.watchdog import run_bounded

    budget = timeout_s if timeout_s is not None else SECTION_TIMEOUT_S
    out = run_bounded(
        fn, *args, name=f"bench-{name}", timeout_s=budget, **kw
    )
    _SECTION_STATUS[name] = {
        "status": out["status"], "seconds": out["seconds"]
    }
    if out["status"] == "timeout":
        err = f"section {name!r} still running after {budget:.0f}s"
        _SECTION_FAILURES.append(f"{label}: {err}")
        return {"error": err, "status": "timeout"}
    if out["status"] == "error":
        _SECTION_FAILURES.append(f"{label}: {out['error']}")
        return {"error": out["error"]}
    return out["result"]


def _errors_in(node, path=""):
    """Paths of every ``{"error": ...}`` row inside a result tree: arms
    and cells report failures as data too, not only whole sections."""
    if isinstance(node, dict):
        if "error" in node:
            yield f"{path or '.'}: {str(node['error'])[:200]}"
        for k, v in node.items():
            yield from _errors_in(v, f"{path}/{k}")
    elif isinstance(node, (list, tuple)):
        for i, v in enumerate(node):
            yield from _errors_in(v, f"{path}[{i}]")


def _exit_code(record=None) -> int:
    """0 when every section, arm and cell ran; 1 otherwise, with the
    failures listed on stderr AFTER the summary went to stdout."""
    import sys

    # a failed section is in both lists (its error row is in the tree);
    # arms and cells that failed inside a section only in the second
    failures = _SECTION_FAILURES + list(_errors_in(record))
    if not failures:
        return 0
    print(
        f"bench: {len(failures)} failure(s):\n  " + "\n  ".join(failures),
        file=sys.stderr,
    )
    return 1


#: the machine-parseable summary's contract: these keys are ALWAYS
#: present (value None when a section didn't run), so round-over-round
#: diffs and the capture harness's `parsed` field never KeyError.
#: Guarded by a tier-1 schema test (tests/engine/test_bench_sweep.py).
SUMMARY_REQUIRED_KEYS = (
    "pipeline_depth",
    "decode",
    "ring_ab",
    "prefill_ab",
    "prefix_cache_ab",
    "prefix_cache_hier",
    "kv_fabric_ab",
    "kv_quant_ab",
    "weight_quant_ab",
    "trace_overhead_ab",
    "obs_ledger_report",
    "spec_decode_ab",
    "slo_report",
    "pd_disagg_ab",
    "gateway_ab",
    "control_plane_ab",
    "sharded_serving",
    "weight_swap_ab",
    "train_packing_ab",
    "paged_decode_ab",
    "dispatch_table",
    "sections",
)


def build_summary(
    gen,
    prefill_ab=None,
    prefix_cache_ab=None,
    prefix_cache_hier=None,
    kv_fabric_ab=None,
    kv_quant_ab=None,
    weight_quant_ab=None,
    trace_overhead_ab=None,
    obs_ledger_report=None,
    spec_decode_ab=None,
    slo_report=None,
    pd_disagg_ab=None,
    gateway_ab=None,
    control_plane_ab=None,
    sharded_serving=None,
    weight_swap_ab=None,
    train_packing_ab=None,
    decode_ab=None,
    pipeline_depth=2,
):
    """Compact machine-parseable summary: the round's DIFFABLE numbers
    (decode split + ring A/B, prefill A/B, the paged 3-column table and
    the dispatch thresholds it derives, the spec-decode off/on A/B, and
    each section's run status) duplicated out of `detail` so the capture
    harness's `parsed` field carries them even when the full detail blob
    is huge or the tail is truncated.  Always emits every key in
    ``SUMMARY_REQUIRED_KEYS`` and always round-trips ``json.dumps`` —
    the tier-1 schema test pins both."""

    def _gen_summary(g):
        if not isinstance(g, dict):
            return None
        return {
            "prefill_toks_per_sec": g.get("prefill_toks_per_sec"),
            "decode_toks_per_sec": g.get("decode_toks_per_sec"),
            "engine_over_jit": g.get("engine_over_jit"),
            "decode_split": g.get("decode_split"),
        }

    return {
        "pipeline_depth": pipeline_depth,
        "decode": {k: _gen_summary(v) for k, v in (gen or {}).items()},
        "ring_ab": (gen.get("b32") or {}).get("ring_ab")
        if isinstance((gen or {}).get("b32"), dict)
        else None,
        "prefill_ab": prefill_ab,
        "prefix_cache_ab": prefix_cache_ab,
        "prefix_cache_hier": prefix_cache_hier,
        "kv_fabric_ab": kv_fabric_ab,
        "kv_quant_ab": kv_quant_ab,
        "weight_quant_ab": weight_quant_ab,
        "trace_overhead_ab": trace_overhead_ab,
        "obs_ledger_report": obs_ledger_report,
        "spec_decode_ab": spec_decode_ab,
        "slo_report": slo_report,
        "pd_disagg_ab": pd_disagg_ab,
        "gateway_ab": gateway_ab,
        "control_plane_ab": control_plane_ab,
        "sharded_serving": sharded_serving,
        "weight_swap_ab": weight_swap_ab,
        "train_packing_ab": train_packing_ab,
        "paged_decode_ab": (
            {
                k: [
                    row.get("dense_toks_per_sec"),
                    row.get("paged_toks_per_sec"),
                ]
                for k, row in decode_ab.items()
                if isinstance(row, dict) and k.startswith("ctx")
            }
            if isinstance(decode_ab, dict)
            else None
        ),
        "dispatch_table": (
            decode_ab.get("derived_dispatch_table")
            if isinstance(decode_ab, dict)
            else None
        ),
        "sections": dict(_SECTION_STATUS),
    }


def bench_decode_ab(cfg15, params15, cases=None, page=1024, chunk=64,
                    capacity_case=True):
    """Paged vs bucketed-dense decode at the recipe's context regime
    (2k/8k/16k/32k, Qwen2.5-1.5B architecture) — chunk-level A/B of the
    exact jitted functions the serving engine dispatches, over synthetic
    KV (decode throughput does not depend on KV values).  Each timed
    chunk routes its sampled tokens through the host (the engine's real
    pattern).

    Also reports the CAPACITY row: at the reference recipe's 31k max gen
    len, a dense cache must reserve kv_cache_len per row
    (16 rows x 32k x 28 KB/token = 14.7 GB — over v5e HBM before the
    3.1 GB of weights), while the paged pool allocates only the tokens
    rows actually hold: 16 concurrent 16k-token rows run here."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import paged
    from areal_tpu.models.transformer import KVCache, decode_chunk

    W = chunk
    BS = page

    def greedy(logits, _rng):
        return (
            jnp.argmax(logits, -1).astype(jnp.int32),
            jnp.max(jax.nn.log_softmax(logits), -1),
        )

    def no_stop(toks):
        return jnp.zeros_like(toks, bool)

    def bucket(n):
        p = 256
        while p < n:
            p <<= 1
        return p

    dense_jit = jax.jit(
        decode_chunk,
        static_argnames=(
            "cfg", "chunk_size", "sample_fn", "stop_fn", "attn_len"
        ),
        donate_argnums=(2,),
    )
    Hkv, hd = cfg15.n_kv_heads, cfg15.head_dim
    kv_bytes_per_tok = cfg15.n_layers * Hkv * hd * 2 * 2

    def run_dense(L, B):
        # the ENGINE right-sizes its cache to the workload
        # (bench_gen_cache_len), so dense reads L + slack, not pow2(L)
        S = -(-(L + 2 * W + 8) // 256) * 256
        key = jax.random.PRNGKey(0)
        kd = jax.random.normal(
            key, (cfg15.n_layers, B, Hkv, S, hd), jnp.bfloat16
        ) * 0.05
        cache = KVCache(
            k=kd, v=kd + 0.0, lengths=jnp.full((B,), L, jnp.int32)
        )
        cur = jnp.full((B,), 7, jnp.int32)
        active = jnp.ones((B,), bool)
        budgets = jnp.full((B,), 10_000, jnp.int32)
        rng = jax.random.PRNGKey(1)
        times, cur_h = [], cur
        for _ in range(4):
            t0 = time.perf_counter()
            cache, out_t, _, _, _, _, budgets, rng = dense_jit(
                params15, cfg15, cache, cur_h, active,
                budgets, rng, chunk_size=W, sample_fn=greedy,
                stop_fn=no_stop, attn_len=S,
            )
            cur_h = jnp.asarray(np.asarray(out_t[:, -1]))
            times.append(time.perf_counter() - t0)
        del cache, kd
        return B * W / min(times[2:])

    def run_paged(L, B, kv_cache_len=None):
        S = bucket(L + 2 * W + 8)
        MB = -(-(kv_cache_len or S) // BS)
        used = -(-(L + 2 * W + 8) // BS)
        NB = B * used + 2  # pool sized by ACTUAL tokens, not reservation
        key = jax.random.PRNGKey(0)
        kp = jax.random.normal(
            key, (cfg15.n_layers, NB, Hkv, BS, hd), jnp.bfloat16
        ) * 0.05
        vp = kp + 0.0
        tables = np.zeros((B, MB), np.int32)
        for b in range(B):
            tables[b, :used] = np.arange(b * used, (b + 1) * used)
        tables = jnp.asarray(tables)
        lengths = jnp.full((B,), L, jnp.int32)
        cur = jnp.full((B,), 7, jnp.int32)
        active = jnp.ones((B,), bool)
        budgets = jnp.full((B,), 10_000, jnp.int32)
        rng = jax.random.PRNGKey(1)
        times, cur_h = [], cur
        for _ in range(4):
            t0 = time.perf_counter()
            (kp, vp, lengths, out_t, _, _, _, active, budgets, rng) = (
                paged.paged_decode_chunk(
                    params15, kp, vp, cfg15, tables, lengths, cur_h,
                    active, budgets, rng, W, greedy, no_stop,
                    use_kernel=True, max_len=(kv_cache_len or S),
                )
            )
            cur_h = jnp.asarray(np.asarray(out_t[:, -1]))
            times.append(time.perf_counter() - t0)
        del kp, vp
        return B * W / min(times[2:])

    def safe(fn, *a, **kw):
        try:
            return fn(*a, **kw)
        except Exception as e:  # noqa: BLE001 - OOM rows are DATA here
            if "memory" in str(e).lower() or "hbm" in str(e).lower():
                return None
            raise

    rows = {}
    measured = {}
    for L, B in (cases or ((2048, 16), (8192, 16), (16384, 16), (32768, 8))):
        d = safe(run_dense, L, B)
        p = safe(run_paged, L, B)
        row = {
            "dense_toks_per_sec": round(d, 1) if d else "OOM",
            "paged_toks_per_sec": round(p, 1) if p else "OOM",
            "paged_over_dense": round(p / d, 3) if (p and d) else None,
        }
        rows[f"ctx{L}_b{B}"] = row
        measured[L] = {"dense": d, "paged": p}
    # turn the A/B into the threshold cache_mode="auto" should dispatch
    # on; recipe configs pin it once a hardware round fills it in
    # (GenServerConfig.paged_min_cache_len)
    from areal_tpu.engine.dispatch import derive_dispatch_table

    rows["derived_dispatch_table"] = derive_dispatch_table(
        measured
    ).as_dict()
    if capacity_case:
        # CAPACITY: the recipe regime — kv_cache_len 32768 (31k max gen
        # len), 16 concurrent rows actually holding 16k tokens.  Dense
        # must reserve B x kv_cache_len; paged allocates B x actual.
        dense_reserved_gb = 16 * 32768 * kv_bytes_per_tok / 2**30
        p_cap = safe(run_paged, 16384, 16, kv_cache_len=32768)
        rows["capacity_16x16k_at_32k_reservation"] = {
            "paged_toks_per_sec": round(p_cap, 1) if p_cap else "OOM",
            "paged_pool_gb": round(
                16 * (16384 + 136) * kv_bytes_per_tok / 2**30, 2
            ),
            "dense_reserved_gb": round(dense_reserved_gb, 2),
            "dense_fits_v5e": dense_reserved_gb + 3.1 < 15.75,
        }
    return rows


def bench_chunked_prefill(
    cfg, gen_params, long_len=15 * 1024, kv_len=16384,
    prefill_chunk=1024, page=1024, short_new=3000, short_prompt=128,
):
    """Decode-stall A/B during a LONG-prompt admission (round-4 verdict
    #2): 8 short rows decode continuously; a 15k-token prompt arrives.
    The dense engine prefills the whole wave in one call (decode stalls
    for its duration); the paged engine admits it in
    ``prefill_chunk_tokens`` chunks interleaved with decode chunks, so
    the longest decode gap is ~one chunk's prefill.  Reported: the max
    inter-step wall gap observed by the short rows after the long
    admission, per mode."""
    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.inference_server import ContinuousBatchingEngine

    rng = np.random.default_rng(5)
    long_prompt = rng.integers(0, cfg.vocab_size, (long_len,)).tolist()

    def run(mode):
        eng = ContinuousBatchingEngine(
            cfg,
            gen_params,
            max_batch=10,
            kv_cache_len=kv_len,
            chunk_size=64,
            cache_mode=mode,
            page_size=page,
            prefill_chunk_tokens=prefill_chunk,
        )
        for i in range(8):
            ids = rng.integers(0, cfg.vocab_size, (short_prompt,)).tolist()
            eng.submit(
                APIGenerateInput(
                    qid=f"s{mode}{i}", prompt_ids=ids, input_ids=ids,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=short_new, temperature=1.0
                    ),
                )
            )
        # warm the decode path, then the LONG admission path (compile)
        for _ in range(4):
            eng.step()
        warm = rng.integers(0, cfg.vocab_size, (long_len,)).tolist()
        eng.submit(
            APIGenerateInput(
                qid=f"w{mode}", prompt_ids=warm, input_ids=warm,
                gconfig=GenerationHyperparameters(
                    max_new_tokens=4, temperature=1.0
                ),
            )
        )
        while eng.try_get_result(f"w{mode}") is None:
            eng.step()
        for _ in range(3):
            eng.step()
        # timed: submit the long prompt, watch per-step gaps until it
        # finishes admission + its first tokens
        eng.submit(
            APIGenerateInput(
                qid=f"L{mode}", prompt_ids=long_prompt,
                input_ids=long_prompt,
                gconfig=GenerationHyperparameters(
                    max_new_tokens=4, temperature=1.0
                ),
            )
        )
        gaps = []
        t_prev = time.perf_counter()
        for _ in range(400):
            eng.step()
            now = time.perf_counter()
            gaps.append(now - t_prev)
            t_prev = now
            if eng.try_get_result(f"L{mode}") is not None:
                break
        eng.pause()
        eng.drain_results()
        return max(gaps)

    stall_paged = run("paged")
    stall_dense = run("dense")
    return {
        "long_prompt_tokens": long_len,
        "decode_stall_dense_s": round(stall_dense, 3),
        "decode_stall_paged_chunked_s": round(stall_paged, 3),
        "stall_reduction": round(stall_dense / max(stall_paged, 1e-9), 2),
    }


# {remat_policy x moment-dtype} sweep cells (the train-MFU levers).
# Moment presets map to OptimizerConfig fields; policies are the graduated
# remat presets (areal_tpu/models/remat.py).
MOMENT_PRESETS = {
    "fp32": {},
    "bf16_mu": {"mu_dtype": "bfloat16"},
    "bf16_mu_nu": {"mu_dtype": "bfloat16", "nu_dtype": "bfloat16"},
    "factored": {"mu_dtype": "bfloat16", "factored_second_moment": True},
}

DEFAULT_SWEEP_CELLS = (
    ("none", "fp32"),  # rounds 1-5 baseline configuration
    ("none", "bf16_mu"),
    ("offload_qkv", "bf16_mu"),
    ("attn_out", "bf16_mu"),
    ("mlp", "bf16_mu"),
    ("qkv_attn", "bf16_mu"),
    ("attn_out", "bf16_mu_nu"),
    ("attn_out", "factored"),
)


def bench_train_packing_ab(
    cfg,
    n_seqs=64,
    len_range=(64, 8192),
    sigma=1.0,
    max_tokens_per_mb=16384,
    timed_steps=2,
    seed=0,
    lr=1e-5,
):
    """Sequence-packing A/B on a long-tail RL-shaped workload: per-row
    padded vs FFD segment-packed train steps (engine ``pack_sequences``).

    RL response lengths are long-tail by nature — one 8k reasoning trace
    in a batch of mostly-short rows pads the whole padded [n, B, T] stack
    to T=8192.  Lengths are lognormal (median ~4x the floor) clipped to
    ``len_range``; both arms run the SAME sample and token budget through
    TrainEngine.train_batch (sft loss), so the reported padded-slot count,
    padding fraction, tok/s, and MFU isolate the batch layout.  The two
    arms' first-step losses must agree (same objective, different layout)
    — reported as ``loss_parity_abs``.  CPU-smoke capable at tiny shapes;
    tok/s and MFU are data for the TPU re-run."""
    import gc

    import jax

    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.engine.train_engine import TrainEngine
    from areal_tpu.interfaces.sft_interface import sft_loss_fn
    from areal_tpu.models import transformer

    lmin, lmax = len_range
    rng = np.random.default_rng(seed)
    lens = np.clip(
        np.round(np.exp(rng.normal(np.log(lmin * 4.0), sigma, n_seqs))),
        lmin,
        lmax,
    ).astype(int)
    total_tokens = int(lens.sum())
    sample = SequenceSample.from_default(
        seqlens=lens.tolist(),
        ids=[f"p{i}" for i in range(n_seqs)],
        data={
            "packed_input_ids": rng.integers(
                0, cfg.vocab_size, (total_tokens,)
            ).astype(np.int64),
            "prompt_mask": np.zeros((total_tokens,), bool),
        },
    )
    mb_spec = MicroBatchSpec(max_tokens_per_mb=max_tokens_per_mb)
    peak_tf = peak_flops(jax.devices()[0]) / 1e12

    def run_arm(pack):
        # arms run SEQUENTIALLY and free their engine: two resident
        # 0.5B fp32-adam states would not share a v5e with the other
        # sections' remnants
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        mesh = MeshSpec().make_mesh(jax.devices()[:1])
        eng = TrainEngine(
            cfg,
            mesh,
            params,
            optimizer_cfg=OptimizerConfig(lr=lr),
            total_train_steps=100,
            pack_sequences=pack,
        )
        first = eng.train_batch(sample, sft_loss_fn, mb_spec)  # compile
        eng.train_batch(sample, sft_loss_fn, mb_spec)  # donation settles
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            eng.train_batch(sample, sft_loss_fn, mb_spec)
        dt = (time.perf_counter() - t0) / timed_steps
        tps = total_tokens / dt
        row = {
            "padded_slots": eng.last_padded_slots,
            "padding_frac": round(eng.last_padding_frac, 4),
            "toks_per_sec": round(tps, 1),
            "tok_per_sec_per_tflop": _per_tflop(tps, peak_tf),
            "first_step_loss": round(float(first["loss"]), 6),
            "n_mbs": first["n_mbs"],
        }
        if eng.last_mfu > 0:
            row["mfu"] = round(eng.last_mfu, 4)
        del eng, params
        gc.collect()
        return row

    padded = run_arm(False)
    packed = run_arm(True)
    return {
        "workload": {
            "n_seqs": n_seqs,
            "total_tokens": total_tokens,
            "len_min": int(lens.min()),
            "len_p50": int(np.median(lens)),
            "len_max": int(lens.max()),
            "max_tokens_per_mb": max_tokens_per_mb,
        },
        "padded": padded,
        "packed": packed,
        "padded_slots_ratio": round(
            padded["padded_slots"] / max(packed["padded_slots"], 1), 2
        ),
        "toks_per_sec_speedup": round(
            packed["toks_per_sec"] / max(padded["toks_per_sec"], 1e-9), 3
        ),
        "loss_parity_abs": round(
            abs(padded["first_step_loss"] - packed["first_step_loss"]), 6
        ),
    }


def bench_train_sweep(
    cfg_base,
    seq_len,
    n_seqs,
    dev,
    timed_steps=2,
    cells=DEFAULT_SWEEP_CELLS,
    hbm_gb=None,
    lr=1e-5,
    progress=None,
):
    """Train-step sweep over {remat_policy x moment dtype} at the standard
    bench batch: per cell, AOT-compile the full fused step (grad + clip +
    adamw apply; areal_tpu/models/remat.py ``compile_train_step``), read
    XLA's memory analysis, and — when the accounting says it fits — run
    timed steps.  Reported per cell: tok/s, tok/s/TFLOP, peak temp
    allocation, argument bytes, optimizer-state bytes, and ``fits_hbm``.

    This turns "fits v5e at the bench batch" into a MEASURED property per
    preset instead of an OOM crash (``qkv_attn`` at fp32 moments measured
    17.0G vs 15.75G in r4): cells whose memory analysis exceeds the budget
    are reported with their numbers and skipped for timing, so the sweep
    always completes.  CPU-validatable at tiny shapes
    (tests/engine/test_bench_sweep.py)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from areal_tpu.engine.optimizer import (
        OptimizerConfig,
        make_optimizer,
        opt_state_bytes,
    )
    from areal_tpu.models import remat, transformer

    if hbm_gb is None:
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # noqa: BLE001 - CPU/older runtimes have none
            stats = {}
        hbm_gb = stats.get("bytes_limit", 0) / 2**30 or None
    peak_tf = peak_flops(dev) / 1e12
    rng = np.random.default_rng(3)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg_base.vocab_size, (n_seqs, seq_len)),
            jnp.int32,
        ),
        "positions": jnp.tile(
            jnp.arange(seq_len, dtype=jnp.int32), (n_seqs, 1)
        ),
        "seg_ids": jnp.ones((n_seqs, seq_len), jnp.int32),
        "prompt_mask": jnp.zeros((n_seqs, seq_len), bool),
    }
    tokens_per_step = n_seqs * seq_len

    def run_cell(policy, moment):
        cfg = dataclasses.replace(cfg_base, remat=True, remat_policy=policy)
        ocfg = OptimizerConfig(lr=lr, **MOMENT_PRESETS[moment])
        compiled, _ = remat.compile_train_step(
            cfg, ocfg, n_seqs=n_seqs, seq_len=seq_len
        )
        mem = remat.memory_summary(compiled) or {}
        row = {k: round(v, 6) for k, v in mem.items()}
        need_gb = mem.get("peak_temp_gb", 0.0) + mem.get("argument_gb", 0.0)
        # no analysis -> fitness UNKNOWN (None), never a measured-looking
        # True; the cell still runs, guarded by the caller's _section
        fits = (
            None
            if hbm_gb is None or not mem
            else bool(need_gb < hbm_gb)
        )
        row["fits_hbm"] = fits
        if fits is False:
            # the memory analysis IS the result: report why this cell
            # cannot run instead of crashing the chip on it
            row["skipped"] = f"needs {need_gb:.2f} GB of {hbm_gb:.2f}"
            return row
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        tx = make_optimizer(ocfg, 100)
        opt_state = jax.jit(tx.init)(params)
        row["opt_state_mb"] = round(opt_state_bytes(opt_state) / 2**20, 3)
        p, o = params, opt_state
        p, o, loss = compiled(p, o, batch)  # warmup (donation settles)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            p, o, loss = compiled(p, o, batch)
        final_loss = float(loss)  # forces the whole timed chain
        dt = (time.perf_counter() - t0) / timed_steps
        tps = tokens_per_step / dt
        row["toks_per_sec"] = round(tps, 1)
        row["tok_per_sec_per_tflop"] = _per_tflop(tps, peak_tf)
        row["loss"] = round(final_loss, 4)
        del p, o, params, opt_state
        return row

    out = {"seq_len": seq_len, "n_seqs": n_seqs, "hbm_gb": hbm_gb}
    for policy, moment in cells:
        if progress:
            progress(f"train sweep: {policy} x {moment}")
        out[f"{policy}|{moment}"] = _section(run_cell, policy, moment)
    return out


def qwen25_15b_config():
    """The true Qwen2.5-1.5B architecture (hidden 1536, 28 layers, GQA
    12q/2kv, head 128, inter 8960, vocab 151936, tied embedding) — random
    weights; the HF importer is logit-parity-tested separately."""
    from areal_tpu.models.config import TransformerConfig

    return TransformerConfig(
        n_layers=28,
        hidden_dim=1536,
        n_q_heads=12,
        n_kv_heads=2,
        head_dim=128,
        intermediate_dim=8960,
        vocab_size=151936,
        max_position_embeddings=32768,
        use_attention_bias=True,
        tied_embedding=True,
        dtype="bfloat16",
    )


def main():
    import sys

    import jax
    import jax.numpy as jnp

    _t0 = time.perf_counter()

    def mark(msg):
        print(f"[bench {time.perf_counter() - _t0:5.0f}s] {msg}",
              file=sys.stderr, flush=True)

    from areal_tpu.api.data import MicroBatchSpec, SequenceSample
    from areal_tpu.base.compile_cache import setup_compile_cache
    from areal_tpu.base.topology import MeshSpec
    from areal_tpu.engine.optimizer import OptimizerConfig
    from areal_tpu.engine.train_engine import TrainEngine
    from areal_tpu.interfaces.sft_interface import sft_loss_fn
    from areal_tpu.models import transformer
    from areal_tpu.models.config import TransformerConfig

    setup_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        # a measurement path that finds no chip fails; it never falls
        # back to the CPU under the same metric names
        print(
            f"bench.py needs a TPU: jax.devices()[0] is {dev.platform!r} "
            f"({dev.device_kind}); run it on the chip machine",
            file=sys.stderr,
        )
        return 2

    # ~0.5B dense model (largest that fits v5e 16G with fp32 adam
    # states).  head_dim=128 fills the TPU's 128-lane tiles.
    cfg = TransformerConfig(
        n_layers=24,
        hidden_dim=1024,
        n_q_heads=8,
        n_kv_heads=4,
        head_dim=128,
        intermediate_dim=5504,
        vocab_size=32768,
        max_position_embeddings=4096,
        use_attention_bias=True,
        dtype="bfloat16",
        remat=True,
    )
    seq_len, n_seqs, timed_steps = 2048, 16, 3
    # b64 is back (dropped in r6 for wall budget): the round-7
    # acceptance bar is engine decode >= 0.9x the isolated jit loop
    # AT B=64, so both batches report engine_over_jit
    gen_batches = (32, 64)

    # fp32 master weights; the model casts to bf16 at use (MXU compute),
    # adam states stay fp32.
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    n_params = param_count(params)
    # independent bf16 copy for generation (train engine donates its params)
    gen_params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    mesh = MeshSpec().make_mesh(jax.devices()[:1])
    engine = TrainEngine(
        cfg,
        mesh,
        params,
        optimizer_cfg=OptimizerConfig(lr=1e-5),
        total_train_steps=100,
    )

    rng = np.random.default_rng(0)
    tokens_per_step = n_seqs * seq_len
    sample = SequenceSample.from_default(
        seqlens=[seq_len] * n_seqs,
        ids=list(range(n_seqs)),
        data={
            "packed_input_ids": rng.integers(
                0, cfg.vocab_size, (tokens_per_step,)
            ).astype(np.int64),
            "prompt_mask": np.zeros((tokens_per_step,), bool),
        },
    )
    mb_spec = MicroBatchSpec(n_mbs=1)

    def time_train(s, toks):
        """tok/s of engine.train_batch on sample ``s`` (two warmups: first
        compiles, second lets buffer donation settle)."""
        engine.train_batch(s, sft_loss_fn, mb_spec)
        engine.train_batch(s, sft_loss_fn, mb_spec)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            engine.train_batch(s, sft_loss_fn, mb_spec)
        return toks / ((time.perf_counter() - t0) / timed_steps)

    def mfu_attn(tps, T):
        # attention-corrected MFU; causal self-attention fwd+bwd adds
        # 12 * L * Hq * hd * (T/2) FLOPs/token to the 6N param term
        attn = 12 * cfg.n_layers * cfg.n_q_heads * cfg.head_dim * (T / 2)
        return tps * (6 * n_params + attn) / peak_flops(dev)

    mark("train 2k")
    train_toks_per_sec = time_train(sample, tokens_per_step)
    mfu = train_toks_per_sec * 6 * n_params / peak_flops(dev)

    # long-context train step (the reference's recipe runs 32k ctx;
    # attention-CORRECTED MFU is the honest long-T efficiency number —
    # param-only MFU mechanically decays as the quadratic term grows)
    mark("train 8k")
    T_long, n_long = 8192, 4
    s_long = SequenceSample.from_default(
        seqlens=[T_long] * n_long,
        ids=list(range(n_long)),
        data={
            "packed_input_ids": rng.integers(
                0, cfg.vocab_size, (T_long * n_long,)
            ).astype(np.int64),
            "prompt_mask": np.zeros((T_long * n_long,), bool),
        },
    )
    tps_long = time_train(s_long, T_long * n_long)
    train_long = {
        "seq_len": T_long,
        "n_seqs": n_long,
        "toks_per_sec": round(tps_long, 1),
        "mfu_attn_corrected": round(mfu_attn(tps_long, T_long), 4),
    }

    # generation throughput at 0.5B, batch sweep.  The b32 row carries
    # the pipeline-depth A/B (K=1 unpipelined baseline / K=2 default /
    # K=4 deep queue).
    mark("gen 0.5B")
    gen = {}
    for B in gen_batches:
        gen[f"b{B}"] = _section(
            bench_generation, cfg, gen_params, n_reqs=B,
            ring_ab=(1, 2, 4) if B == 32 else (),
            jit_ratio=True,
            name=f"generation_b{B}",
        )

    # admission-prefill A/B: jit ceiling vs dense-engine admit vs paged
    # chunked admit (roots the r5 prefill regression — VERDICT #2)
    mark("prefill A/B")
    prefill_ab = _section(
        bench_prefill_ab, cfg, gen_params, name="prefill_ab"
    )

    # interruption A/B + update-visibility latency
    mark("interruption")
    interruption = _section(
        bench_interruption, cfg, gen_params, name="interruption"
    )

    # group-prompt KV dedup at admission (prefix-reuse A/B)
    mark("prefix reuse")
    prefix_reuse = _section(
        bench_prefix_reuse, cfg, gen_params, name="prefix_reuse"
    )

    # flight-recorder overhead A/B (off / sampled / always-on decode
    # tok/s).
    mark("trace overhead A/B")
    trace_overhead_ab = _section(
        bench_trace_overhead_ab,
        cfg,
        gen_params,
        name="trace_overhead_ab",
    )

    # HBM-ledger + recompile-sentinel report: per-subsystem device-byte
    # attribution, reconciliation verdict, steady-sentinel silence +
    # forced-recompile fire, leak-free close, ledger-on-vs-off tok/s
    # with the <2% overhead bar.
    mark("obs ledger report")
    obs_ledger_report = _section(
        bench_obs_ledger_report,
        cfg,
        gen_params,
        name="obs_ledger_report",
    )

    # cross-request radix prefix cache: multi-turn conversation replay,
    # cache on vs off (cached-token fraction + replay tok/s).
    mark("prefix cache A/B")
    prefix_cache_ab = _section(
        bench_prefix_cache_ab,
        cfg,
        gen_params,
        name="prefix_cache_ab",
    )

    # hierarchical prefix cache: cached-token-frac vs conversation-count
    # curves with the host spill tier on vs off, on a sweep that
    # overflows the HBM cache.
    mark("prefix cache hier")
    prefix_cache_hier = _section(
        bench_prefix_cache_hier,
        cfg,
        gen_params,
        name="prefix_cache_hier",
    )

    # fleet-wide KV fabric A/B: session-migration replay on a 2-server
    # in-process fleet, cross-server prefix pull on vs off — fleet
    # cached_token_frac, target re-prefill tokens (>=2x reduction bar),
    # pull bytes, greedy parity as data.
    mark("kv fabric A/B")
    kv_fabric_ab = _section(
        bench_kv_fabric_ab,
        cfg,
        gen_params,
        name="kv_fabric_ab",
    )

    # quantized KV cache A/B: fp vs int8 paged pools at equal budgets —
    # blocks-per-HBM-byte gain, decode tok/s, max rows at a fixed byte
    # budget, prefix-cache cached_token_frac at equal HBM, and the
    # MEASURED greedy divergence rate per workload (the quality gate).
    # The summary carries the
    # >=1.8x density + quality-bar acceptance numbers.
    mark("kv quant A/B")
    kv_quant_ab = _section(
        bench_kv_quant_ab,
        cfg,
        gen_params,
        name="kv_quant_ab",
    )

    # quantized serving weights A/B: model-dtype vs int8 + scales param
    # trees — param-HBM reduction, staged-swap bytes/time per format,
    # decode tok/s, fixed-budget capacity with kv-int8 composed, and
    # the MEASURED greedy divergence rate per workload (quality gate).
    # The summary carries
    # the >=1.8x staged-bytes + quality-bar acceptance numbers.
    mark("weight quant A/B")
    weight_quant_ab = _section(
        bench_weight_quant_ab,
        cfg,
        gen_params,
        name="weight_quant_ab",
    )

    # request-level SLO report: fleet-merged TTFT/TPOT percentiles under
    # the multi-turn replay + spec-decode workloads, digest-merge
    # cross-check, and the SLO-tracking on/off overhead A/B (<2% bar).
    mark("slo report")
    slo_report = _section(
        bench_slo_report,
        cfg,
        gen_params,
        name="slo_report",
    )

    # disaggregated prefill/decode A/B: interactive decode stream + long-
    # prompt prefill wave on unified vs 1P+1D split fleets (same hardware
    # both arms) — fleet-merged p99 TTFT/TPOT per workload, handoff
    # count/bytes/latency, greedy parity as data.
    mark("pd disagg A/B")
    pd_disagg_ab = _section(
        bench_pd_disagg_ab,
        cfg,
        gen_params,
        name="pd_disagg_ab",
    )
    # heterogeneous-mesh sub-arm: big-mesh (TP) prefill streaming into a
    # single-chip decode engine — parity + TTFT rows as data
    if isinstance(pd_disagg_ab, dict):
        mark("pd disagg hetero sub-arm")
        pd_disagg_ab["hetero"] = _section(
            bench_pd_disagg_hetero, name="pd_disagg_hetero",
        )

    # serving gateway A/B: interactive SSE burst vs bulk-rollout storm on
    # a 2-engine fleet, tenant admission on vs off — interactive p99 TTFT
    # (strictly-better bar), typed bulk rejects, SSE/rollout token
    # parity, zero-leak audit.
    mark("gateway A/B")
    gateway_ab = _section(
        bench_gateway_ab,
        cfg,
        gen_params,
        name="gateway_ab",
    )

    # control-plane A/B: the manager's batched ROUTER serve loop +
    # O(log N) routing indexes + batched client RPCs vs the strict REP
    # + O(N)-scan + per-request baseline, at 64 registered fake servers
    # under a mixed rollout+gateway storm.  Pure CPU (real ZMQ, no
    # engine), so the summary always carries the >=5x schedules/sec
    # acceptance verdict and the scan-vs-indexed parity bool.
    mark("control plane A/B")
    control_plane_ab = _section(
        bench_control_plane_ab,
        name="control_plane_ab",
    )

    # self-speculative decoding A/B: n-gram draft + batched paged verify
    # on vs off, on a repetitive-trace workload (decode tok/s + accepted
    # tokens per verify step); the
    # summary always carries the >=1.3x acceptance bar's number.
    mark("spec decode A/B")
    spec_decode_ab = _section(
        bench_spec_decode_ab,
        cfg,
        gen_params,
        name="spec_decode_ab",
    )

    # zero-downtime weight sync A/B: staged (stage-while-decoding ->
    # pointer-flip commit) vs legacy full-reload swap — pause-ms, decode
    # dip around the swap, post-swap fresh-replay parity.
    mark("weight swap A/B")
    weight_swap_ab = _section(
        bench_weight_swap_ab,
        name="weight_swap_ab",
    )

    # sharded-serving scaling: decode tok/s at 1 vs N chips, dense-TP +
    # moe-EP arms (ROADMAP item 1).
    mark("sharded serving")
    sharded_n = min(4, len(devs))
    sharded_serving = _section(
        bench_sharded_serving,
        n_chips=max(2, sharded_n),
        name="sharded_serving",
    )

    # train->generation weight publish (sharded raw-param checkpoint,
    # inference dtype; reference budget <3 s)
    mark("publish")
    import shutil
    import tempfile

    from areal_tpu.engine.checkpoint import save_params, wait_for_saves

    # memory-backed dir when available: the CO-HOSTED publish path is a
    # direct device transfer with no disk at all (model_worker._param_realloc),
    # and the reference's <3 s figure is NCCL+GDRDMA, also diskless — this
    # host's ~80 MB/s scratch disk would measure the wrong thing.  The
    # detail still reports it as "commit" (serialize + durable write).
    pub_root = "/dev/shm" if os.path.isdir("/dev/shm") else None
    pub_dir = tempfile.mkdtemp(prefix="areal-bench-pub-", dir=pub_root)
    try:
        save_params(gen_params, pub_dir + "/v0", cast_dtype="bfloat16")
        t0 = time.perf_counter()
        save_params(
            gen_params, pub_dir + "/v1", cast_dtype="bfloat16", wait=False
        )
        publish_block_s = time.perf_counter() - t0  # trainer stall
        wait_for_saves()
        publish_commit_s = time.perf_counter() - t0  # durable + advertised
    finally:
        shutil.rmtree(pub_dir, ignore_errors=True)

    # device->host SINGLE-STREAM link bandwidth (a local TPU host fetches
    # over PCIe/DMA at GB/s): says whether the commit time above is
    # fetch-bound or disk-bound.  Orbax fetches leaves concurrently, so
    # commit throughput ~ n_streams x this.
    big = jax.device_put(  # 64 MiB of incompressible bytes: an all-zeros
        # payload would let transport compression serve the fetch for free
        np.random.default_rng(7)
        .standard_normal((32, 1024, 1024))
        .astype(np.float16)
    )
    scale = jax.jit(lambda x, c: x * c)
    np.asarray(scale(big, jnp.float16(2)))  # compile + warm the path
    t0 = time.perf_counter()
    # same compiled fn, FRESH output buffer: the timed fetch pays only
    # exec + transfer (a repeat fetch of one buffer can hit a host-side
    # cache)
    np.asarray(scale(big, jnp.float16(3)))
    d2h_gbps = (64 / 1024) / max(time.perf_counter() - t0, 1e-9)

    # effective RL step on one chip AT THE RECIPE REGIME: ~8k-token
    # sequences (prompt 7.5k + 512 generated), gen + train sharing the
    # chip.  The reference baseline below was derived ASSUMING a mean
    # sequence of 8000 tokens — at 8k our sequences match the assumption
    # instead of flattering it (round-4 verdict #3; the old 1k-token row
    # divided by an 8k-denominated baseline).  The 1.5B-arch train state
    # (fp32 adam, 21 GB) exceeds one v5e; the recipe trains it on an
    # 8-chip FSDP mesh (dryrun-validated) — this row keeps the 0.5B
    # model, whose tok/s/TFLOP normalization is size-comparable.
    mark("effective 8k")
    B_eff, new_eff = 8, 512
    prompt_eff = 7680
    eng = make_engine(cfg, gen_params, B_eff, prompt_eff, new_eff)
    submit_wave(eng, cfg, B_eff, prompt_eff, new_eff, "we")
    drain(eng)  # warm
    submit_wave(eng, cfg, B_eff, prompt_eff, new_eff, "te")
    t0 = time.perf_counter()
    drain(eng)
    t_gen = time.perf_counter() - t0
    eff_seq = prompt_eff + new_eff
    eff_tokens = B_eff * eff_seq
    eff_sample = SequenceSample.from_default(
        seqlens=[eff_seq] * B_eff,
        ids=list(range(B_eff)),
        data={
            "packed_input_ids": rng.integers(
                0, cfg.vocab_size, (eff_tokens,)
            ).astype(np.int64),
            "prompt_mask": np.zeros((eff_tokens,), bool),
        },
    )
    engine.train_batch(eff_sample, sft_loss_fn, mb_spec)  # compile
    t0 = time.perf_counter()
    engine.train_batch(eff_sample, sft_loss_fn, mb_spec)
    t_train = time.perf_counter() - t0
    effective_tok_s = eff_tokens / (t_gen + t_train)
    ours_per_tflop = effective_tok_s / (peak_flops(dev) / 1e12)
    del eng, engine, params  # free HBM before the 1.5B section

    # sequence-packing A/B: padded vs FFD segment-packed train steps on a
    # long-tail (lognormal) RL-shaped length distribution — padded-slot
    # count, padding fraction, tok/s, MFU per arm.
    mark("train packing A/B")
    train_packing_ab = _section(
        bench_train_packing_ab,
        cfg,
        name="train_packing_ab",
    )

    # chunked-prefill decode-stall A/B (0.5B; the mechanism under test is
    # the engine's admission scheduling, not model-size-dependent)
    mark("chunked prefill")
    chunked_prefill = _section(
        bench_chunked_prefill, cfg, gen_params, name="chunked_prefill"
    )

    # 1.5B architecture (the reference's smallest published scale): the
    # recipe-regime decode A/B (paged vs bucketed-dense at 2k-32k ctx)
    # plus the capacity row.  Init on the HOST CPU and ship straight as
    # bf16 — a device-side fp32 init would spike ~6 GB of HBM next to the
    # other benches' remnants.
    mark("1.5B section")
    import ml_dtypes

    del gen_params
    cfg15 = qwen25_15b_config()
    shapes = jax.eval_shape(
        lambda k: transformer.init_params(cfg15, k),
        jax.random.PRNGKey(1),
    )
    host_rng = np.random.default_rng(1)
    params15 = jax.tree.map(
        lambda s: jax.device_put(
            (0.02 * host_rng.standard_normal(s.shape, dtype=np.float32))
            .astype(ml_dtypes.bfloat16)
        ),
        shapes,
    )
    g15 = _section(
        bench_generation, cfg15, params15, n_reqs=32,
        name="generation_1p5b",
    )
    gen_15b = {**g15, "n_params": param_count(params15)}
    mark("decode A/B")
    decode_ab = _section(
        bench_decode_ab, cfg15, params15, name="decode_ab"
    )
    del params15

    # {remat_policy x moment dtype} train sweep at the bench batch — the
    # MFU-plateau lever set (low-precision optimizer states + graduated
    # remat presets).  Runs LAST: every cell inits fresh 0.5B params +
    # opt state, so it needs the HBM the other sections have released.
    mark("train sweep")
    sweep_cells = DEFAULT_SWEEP_CELLS
    train_sweep = _section(
        bench_train_sweep,
        cfg,
        seq_len,
        n_seqs,
        dev,
        cells=sweep_cells,
        progress=mark,
        name="train_sweep",
        timeout_s=1800.0,  # many per-cell compiles
    )
    mark("done")

    summary = build_summary(
        gen,
        prefill_ab=prefill_ab,
        prefix_cache_ab=prefix_cache_ab,
        prefix_cache_hier=prefix_cache_hier,
        kv_fabric_ab=kv_fabric_ab,
        kv_quant_ab=kv_quant_ab,
        weight_quant_ab=weight_quant_ab,
        trace_overhead_ab=trace_overhead_ab,
        obs_ledger_report=obs_ledger_report,
        spec_decode_ab=spec_decode_ab,
        slo_report=slo_report,
        pd_disagg_ab=pd_disagg_ab,
        gateway_ab=gateway_ab,
        control_plane_ab=control_plane_ab,
        sharded_serving=sharded_serving,
        weight_swap_ab=weight_swap_ab,
        train_packing_ab=train_packing_ab,
        decode_ab=decode_ab,
    )

    record = {
            "metric": "effective_rl_toks_per_sec_per_tflop",
            "value": round(ours_per_tflop, 4),
            "unit": "tok/s per bf16-TFLOP/s (1 chip, sync gen+train)",
            "vs_baseline": round(
                ours_per_tflop / REF_TOK_PER_SEC_PER_TFLOP, 4
            ),
            "summary": summary,
            "detail": {
                "device": getattr(dev, "device_kind", dev.platform),
                "baseline_derivation": {
                    "ref_tok_per_sec_per_tflop": round(
                        REF_TOK_PER_SEC_PER_TFLOP, 4
                    ),
                    "ref_seqs_per_step": REF_SEQS_PER_STEP,
                    "ref_mean_seq_len_ASSUMED": REF_MEAN_SEQ_LEN_ASSUMED,
                    "ref_step_seconds": round(REF_STEP_SECONDS, 2),
                    "ref_n_gpus": REF_N_GPUS,
                    "ref_gpu_peak_tflops": REF_GPU_PEAK_TFLOPS,
                    "caveat": "ours: 8k-token seqs (matching the assumed ref mean) on 1 chip sync; ref: 128-GPU async",
                },
                "effective": {
                    "toks_per_sec": round(effective_tok_s, 1),
                    "gen_s": round(t_gen, 3),
                    "train_s": round(t_train, 3),
                    "batch": B_eff,
                    "seq_len": eff_seq,
                    "cache_mode": "paged",
                },
                "train_step_mfu": round(mfu, 4),
                "train_mfu_attn_corrected": round(
                    mfu_attn(train_toks_per_sec, seq_len), 4
                ),
                "train_long_ctx": train_long,
                "train_packing_ab": train_packing_ab,
                "train_remat_moment_sweep": train_sweep,
                "train_toks_per_sec": round(train_toks_per_sec, 1),
                "n_params": n_params,
                "weight_publish_block_s": round(publish_block_s, 4),
                "weight_publish_commit_s": round(publish_commit_s, 3),
                "d2h_stream_gb_per_s": round(d2h_gbps, 3),
                "generation_0p5b": gen,
                "generation_qwen25_1p5b_arch": gen_15b,
                "decode_paged_vs_dense_1p5b": decode_ab,
                "prefill_ab": prefill_ab,
                "chunked_prefill": chunked_prefill,
                "interruption": interruption,
                "prefix_reuse": prefix_reuse,
                "prefix_cache_ab": prefix_cache_ab,
                "prefix_cache_hier": prefix_cache_hier,
                "kv_fabric_ab": kv_fabric_ab,
                "kv_quant_ab": kv_quant_ab,
                "weight_quant_ab": weight_quant_ab,
                "trace_overhead_ab": trace_overhead_ab,
                "spec_decode_ab": spec_decode_ab,
                "slo_report": slo_report,
                "pd_disagg_ab": pd_disagg_ab,
                "gateway_ab": gateway_ab,
                "control_plane_ab": control_plane_ab,
                "sharded_serving": sharded_serving,
            },
        }
    print(json.dumps(record))
    return _exit_code(record)


if __name__ == "__main__":
    import sys as _sys

    _sys.exit(main())
