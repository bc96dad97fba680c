"""Tier-1 CPU smoke of bench.py's sections (the bench_decode_ab
pattern from 9ab0b16: size-parametrized helpers validated end-to-end at
tiny shapes so bench logic breakage is caught BEFORE a hardware round).

Covers the {remat_policy x moment dtype} train sweep, what hides no
device (``main()`` refuses a non-TPU backend; a failed or timed-out
section makes the run's exit code non-zero after the summary; a
multi-chip section on too few chips is reported as not run), the
per-section time limit, the speculative-decoding off/on A/B, and the
machine-parseable summary's schema contract (always json-round-trips,
always carries every SUMMARY_REQUIRED_KEYS entry)."""

import json
import time

import numpy as np
import pytest

import bench


@pytest.fixture(scope="module")
def tiny_cfg():
    from areal_tpu.models.config import tiny_config

    return tiny_config(vocab_size=64)


def test_train_sweep_runs_end_to_end_at_tiny_shapes(tiny_cfg):
    import jax

    out = bench.bench_train_sweep(
        tiny_cfg,
        seq_len=16,
        n_seqs=2,
        dev=jax.devices()[0],
        timed_steps=1,
        cells=(
            ("none", "fp32"),
            ("attn_out", "bf16_mu"),
            ("offload_qkv", "bf16_mu"),
            ("attn_out", "factored"),
        ),
    )
    assert out["seq_len"] == 16 and out["n_seqs"] == 2
    cells = {k: v for k, v in out.items() if "|" in k}
    assert set(cells) == {
        "none|fp32",
        "attn_out|bf16_mu",
        "offload_qkv|bf16_mu",
        "attn_out|factored",
    }
    for key, row in cells.items():
        assert "error" not in row, (key, row)
        # per-cell report: throughput + the memory-analysis numbers the
        # fits-v5e assertion reads on hardware
        assert row["toks_per_sec"] > 0, (key, row)
        # the CPU has no published peak: a CPU rate is never written
        # under the per-TFLOP device metric
        assert row["tok_per_sec_per_tflop"] is None, (key, row)
        assert row["peak_temp_gb"] > 0, (key, row)
        assert row["opt_state_mb"] > 0, (key, row)
        assert np.isfinite(row["loss"]), (key, row)
    # bf16 moments must actually shrink the optimizer state
    assert (
        cells["attn_out|bf16_mu"]["opt_state_mb"]
        < cells["none|fp32"]["opt_state_mb"]
    )


def test_train_sweep_reports_would_oom_cells_as_data(tiny_cfg):
    """A cell over the HBM budget is reported from the memory analysis and
    skipped for timing — never a crash (the qkv_attn r4 OOM, as data)."""
    import jax

    out = bench.bench_train_sweep(
        tiny_cfg,
        seq_len=16,
        n_seqs=2,
        dev=jax.devices()[0],
        cells=(("qkv_attn", "fp32"),),
        hbm_gb=1e-9,  # nothing fits
    )
    row = out["qkv_attn|fp32"]
    assert row["fits_hbm"] is False
    assert "skipped" in row and "toks_per_sec" not in row


def test_main_refuses_a_cpu_backend(capsys):
    """bench.main() requires the TPU: non-zero exit, no metric line, and
    no toy CPU run under the chip run's metric names."""
    rc = bench.main()
    out = capsys.readouterr()
    assert rc != 0
    assert "needs a TPU" in out.err
    assert "metric" not in out.out


def test_failed_section_makes_the_exit_code_nonzero(capsys):
    bench._SECTION_FAILURES.clear()
    assert bench._exit_code({"detail": {"a": {"ok": 1}}}) == 0

    def boom():
        raise RuntimeError("kernel refused")

    row = bench._section(boom, name="demo_fail")
    assert "kernel refused" in row["error"]  # still data for the summary
    assert bench._exit_code({"detail": {"demo_fail": row}}) == 1
    assert "demo_fail" in capsys.readouterr().err
    bench._SECTION_FAILURES.clear()
    # an arm that failed INSIDE a section (error row in the tree) counts too
    rec = {"detail": {"weight_swap_ab": {"mesh": {"error": "boom"}}}}
    assert bench._exit_code(rec) == 1
    # ... while a section that was NOT RUN for lack of chips does not
    rec = {"detail": {"sharded_serving": {"not_run": "needs 4 chips"}}}
    assert bench._exit_code(rec) == 0


def test_multichip_sections_report_not_run_on_too_few_chips():
    """With fewer devices than a section wants it is reported as not run
    — no CPU child process is started to fill the row."""
    import subprocess
    from unittest import mock

    with mock.patch.object(subprocess, "run") as run:
        out = bench.bench_sharded_serving(n_chips=64)
        assert "not_run" in out and "64" in out["not_run"]
        assert "not_run" in bench.bench_pd_disagg_hetero(n_chips=64)
        run.assert_not_called()
    assert not hasattr(bench, "_probe_devices")
    assert not hasattr(bench, "_sharded_serving_child")


# -- per-section fail-safe isolation ------------------------------------------


def test_section_records_ok_status_and_result():
    bench._SECTION_STATUS.pop("demo_ok", None)
    out = bench._section(lambda x: {"v": x + 1}, 1, name="demo_ok")
    assert out == {"v": 2}
    assert bench._SECTION_STATUS["demo_ok"]["status"] == "ok"


def test_section_turns_exception_into_data_with_status():
    def boom():
        raise RuntimeError("backend exploded")

    out = bench._section(boom, name="demo_err")
    assert "backend exploded" in out["error"]
    assert bench._SECTION_STATUS["demo_err"]["status"] == "error"


def test_section_bounds_a_hung_section():
    """A section that runs past its time limit forfeits only its own
    numbers (bounded join, timeout status, the run continues to its
    summary) — and fails the run's exit code."""

    def hang():
        time.sleep(5)
        return {"never": True}

    t0 = time.perf_counter()
    out = bench._section(hang, name="demo_hang", timeout_s=0.2)
    assert time.perf_counter() - t0 < 2.0
    assert out["status"] == "timeout" and "error" in out
    assert bench._SECTION_STATUS["demo_hang"]["status"] == "timeout"
    assert any("demo_hang" in f for f in bench._SECTION_FAILURES)
    bench._SECTION_FAILURES.clear()


def test_unnamed_section_keeps_legacy_inline_behavior():
    assert bench._section(lambda: 7) == 7
    assert "error" in bench._section(
        lambda: (_ for _ in ()).throw(ValueError("x"))
    )


# -- spec-decode A/B + summary schema -----------------------------------------


@pytest.fixture(scope="module")
def spec_ab(tiny_cfg):
    """One tiny spec_decode_ab run shared by the section + schema tests
    (greedy + paged, repetitive-trace workload)."""
    import jax

    from areal_tpu.models import transformer

    params = transformer.init_params(tiny_cfg, jax.random.PRNGKey(0))
    return bench.bench_spec_decode_ab(
        tiny_cfg, params, batches=(2,), prompt_len=32, max_new=48,
        motif_len=8, page=16, chunk=8, max_draft=3,
    )


@pytest.fixture(scope="module")
def slo_report(tiny_cfg):
    """One tiny bench_slo_report run shared by the section + schema
    tests (multi-turn replay across two 'servers', spec-decode arm,
    SLO-tracking on/off overhead A/B)."""
    import jax

    from areal_tpu.models import transformer

    params = transformer.init_params(tiny_cfg, jax.random.PRNGKey(2))
    return bench.bench_slo_report(
        tiny_cfg, params, n_sessions=2, turns=2, prompt_len=32,
        user_len=8, max_new=12, page=16, chunk=4, overhead_reqs=2,
        overhead_prompt=32, overhead_new=16, overhead_repeats=1,
    )


def test_slo_report_fleet_merged_percentiles_within_bound(slo_report):
    """The acceptance criterion: fleet-merged TTFT/TPOT p50/p95/p99
    present for both workloads, and the digest-merge cross-check against
    the pooled raw records sits inside the documented error bound."""
    from areal_tpu.observability.latency import SLO_REL_ERROR_BOUND

    assert slo_report["error_bound"] == pytest.approx(
        SLO_REL_ERROR_BOUND, abs=1e-4
    )
    for workload in ("multi_turn", "spec_decode"):
        row = slo_report[workload]
        assert row["records"] > 0, (workload, row)
        for fam in ("ttft_s", "tpot_s"):
            pct = row["fleet"][fam]
            for k in ("p50", "p95", "p99"):
                assert pct[k] is not None and pct[k] > 0, (workload, fam, k)
            assert pct["p50"] <= pct["p95"] <= pct["p99"]
            assert pct["count"] > 0
        # THE error-bound assertion: merged digest vs pooled raw records
        assert row["merge_within_bound"] is True, row
    # two servers in the multi-turn arm, each attributable
    assert sorted(slo_report["multi_turn"]["servers"]) == ["srv0", "srv1"]
    for srow in slo_report["multi_turn"]["servers"].values():
        assert srow["records"] > 0 and srow["ttft_p99"] > 0


def test_slo_report_overhead_ab_reports_both_arms(slo_report):
    """The on/off A/B carries both arms + the overhead fraction (the
    <2% bar is asserted on TPU bench rounds; CPU smoke asserts shape
    and sanity, not the noisy CPU ratio)."""
    ab = slo_report["overhead_ab"]
    assert ab["slo_on_toks_per_sec"] > 0
    assert ab["slo_off_toks_per_sec"] > 0
    assert -1.0 < ab["overhead_frac_vs_off"] < 1.0


def test_spec_decode_ab_reports_required_fields(spec_ab):
    row = spec_ab["b2"]
    for arm in ("spec_off", "spec_on"):
        assert row[arm]["decode_toks_per_sec"] > 0
    on = row["spec_on"]
    assert on["verify_chunks"] > 0  # spec genuinely engaged
    assert 0.0 <= on["accept_rate"] <= 1.0
    assert on["accepted_tokens_per_step"] >= 1.0
    assert row["spec_over_off"] > 0
    assert 0.0 <= row["derived_min_accept_rate"] <= 1.0


@pytest.mark.slow  # ~19s: four engine builds; the >=2x slot-reduction
# claim itself stays tier-1 via test_packed_training.py's dense arm
def test_train_packing_ab_smoke(tiny_cfg):
    """The packing A/B's acceptance bar at tiny CPU shapes: >= 2x fewer
    padded slots on the long-tail workload, first-step loss parity
    between the arms, and every reported field present for the TPU
    re-run's diff."""
    out = bench.bench_train_packing_ab(
        tiny_cfg,
        n_seqs=16,
        len_range=(8, 96),
        max_tokens_per_mb=256,
        timed_steps=1,
    )
    assert out["padded_slots_ratio"] >= 2.0, out
    assert out["packed"]["padding_frac"] < out["padded"]["padding_frac"]
    assert out["loss_parity_abs"] < 1e-4, out
    for arm in ("padded", "packed"):
        assert out[arm]["toks_per_sec"] > 0
        assert out[arm]["padded_slots"] > 0
    assert out["workload"]["len_max"] <= 96
    json.dumps(out)  # wire-format safe


def test_gateway_ab_cpu_smoke(tiny_cfg):
    """The gateway A/B at tiny CPU shapes (the acceptance criterion's
    smoke): interactive p99 TTFT strictly better with admission on
    under the bulk storm, SSE-concat == non-streaming token parity,
    greedy gateway output token-identical to the rollout path, and
    zero leaked blocks across every arm."""
    import jax

    from areal_tpu.models import transformer

    params = transformer.init_params(tiny_cfg, jax.random.PRNGKey(0))
    out = bench.bench_gateway_ab(
        tiny_cfg, params, n_bulk=4, n_interactive=4, prompt_len=32,
        bulk_new=96, inter_new=8, page=16, chunk=8, max_batch=2,
    )
    on, off = out["admission_on"], out["admission_off"]
    # the bulk storm was genuinely throttled on the on-arm only
    assert sum(on["bulk_rejects"].values()) > 0
    assert off["bulk_rejects"] == {}
    assert off["bulk_admitted"] == 4
    # every interactive request streamed its full token budget
    for arm in (on, off):
        assert arm["interactive_tokens"] == 4 * 8
        assert arm["leak_free"] is True
    # THE acceptance bar: interactive p99 TTFT (deterministic,
    # step-counted) strictly better with admission on
    assert out["p99_ttft_steps_improvement"] > 1.0
    assert out["interactive_p99_ttft_better_with_admission"] is True
    par = out["parity"]
    assert par["stream_concat_matches_result"] is True
    assert par["gateway_matches_rollout"] is True
    assert out["leak_free"] is True
    # N=2-gateways arm: two front doors racing one manager's admission
    # plane over gateway_submit — the shared capped bucket filled
    # EXACTLY (atomic, no over-admit) and both gateways stayed live
    two = out["two_gateways"]
    assert two["no_tenant_over_admit"] is True, two
    assert (
        two["total_capped_admitted"] == two["capped_tenant_slots"]
    ), two
    assert two["both_gateways_served"] is True, two
    assert "errors" not in two, two
    assert out["no_tenant_over_admit"] is True
    json.dumps(out)  # wire-format safe


def test_obs_ledger_report_cpu_smoke(tiny_cfg):
    """The observability acceptance smoke: per-subsystem attribution
    present under live decode, the reconcile verdict clean (vacuous on
    backends without memory_stats), ZERO steady sentinel compiles over
    the timed same-shape waves, >=1 attributed fire after the forced
    KV-bucket change, and a leak-free close back to the zero ledger
    baseline."""
    import jax

    from areal_tpu.models import transformer

    params = transformer.init_params(tiny_cfg, jax.random.PRNGKey(0))
    out = bench.bench_obs_ledger_report(
        tiny_cfg, params, n_reqs=2, prompt_len=32, max_new=16, repeats=1,
    )
    on = out["on"]
    assert on["hbm_bytes"]["weights"] > 0
    assert on["hbm_bytes"]["kv_pool"] > 0
    assert on["hbm_peak_bytes"]["kv_pool"] >= on["hbm_bytes"]["kv_pool"]
    assert on["reconcile"]["ok"] is True
    assert on["reconcile"]["drift_gb"] == 0.0
    # armed sentinel silent across steady decode, fires on the forced
    # bucket change with the compile burst attributed
    assert on["steady_compiles"] == 0
    assert on["sentinel"]["forced_compiles"] >= 1
    assert on["sentinel"]["fires_total"] >= 1
    assert on["sentinel"]["stall_counter_recompile"] >= 1.0
    # leak audit: clean close returns the ledger to baseline
    assert on["close_leaks"] == {}
    assert on["ledger_zero_after_close"] is True
    # both arms produced a throughput number and the overhead stat +
    # bar ride along (the <2% assertion itself is a hardware-round bar
    # — CPU tiny-shape noise swamps it)
    assert out["off"]["decode_toks_per_sec"] > 0
    assert on["decode_toks_per_sec"] > 0
    assert isinstance(on["overhead_frac_vs_off"], float)
    assert out["overhead_bar_frac"] == 0.02
    json.dumps(out)  # wire-format safe


def test_control_plane_ab_cpu_smoke():
    """The control-plane A/B end to end on CPU (the acceptance
    criterion's smoke): real ZMQ sockets, threaded clients, a mid-storm
    weight update in every arm — router+indexed+batched must clear 5x
    schedules/sec over rep+scan+unbatched at 64 fake servers, with
    scan-vs-indexed pick parity across all three policies.  The update
    RPC latency is raised above the bench default so the rep arms'
    inline stall dominates scheduler noise under CI load."""
    out = bench.bench_control_plane_ab(update_rpc_s=0.1)
    assert out["meets_5x"] is True, out
    assert out["routing_parity"] is True, out["parity"]
    for arm in ("rep_scan", "rep_indexed", "router_scan",
                "router_indexed"):
        row = out[arm]
        assert "errors" not in row, (arm, row)
        # every logical schedule landed exactly once
        assert row["scheduled"] == out["n_schedules"], (arm, row)
        # the mid-storm weight update really completed in every arm
        assert row["model_version_after"] == 1, (arm, row)
    # the batched arm collapsed round trips: one RPC per group + one
    # per gateway request vs one per sibling + two per gateway request
    assert out["router_indexed"]["rpcs"] < out["rep_scan"]["rpcs"]
    json.dumps(out)  # wire-format safe


def test_summary_schema_round_trips_with_required_keys(spec_ab):
    """The machine-parseable summary contract: json round-trip + every
    SUMMARY_REQUIRED_KEYS entry present (None for sections that did not
    run) — including the new spec_decode_ab section and the per-section
    status table."""
    gen = {"b2": {"prefill_toks_per_sec": 1.0,
                  "decode_toks_per_sec": 2.0,
                  "decode_split": {"host_frac": 1.0}},
           "b4": {"error": "section died"}}
    summary = bench.build_summary(
        gen,
        prefill_ab=None,
        prefix_cache_ab={"replay_wall_speedup": 1.5},
        prefix_cache_hier={
            "sweep": {
                "c8": {
                    "host_on": {"cached_token_frac": 0.61},
                    "host_off": {"cached_token_frac": 0.22},
                    "token_parity": True,
                    "cached_token_frac_gain": 0.39,
                }
            },
            "dropped": [],
        },
        kv_fabric_ab={
            "sweep": {
                "c8": {
                    "fabric_on": {
                        "fleet_cached_token_frac": 0.58,
                        "target_prefill_tokens": 900,
                    },
                    "fabric_off": {
                        "fleet_cached_token_frac": 0.21,
                        "target_prefill_tokens": 4100,
                    },
                    "token_parity": True,
                    "reprefill_token_reduction": 4.56,
                }
            },
            "dropped": [],
        },
        trace_overhead_ab=None,
        spec_decode_ab=spec_ab,
        train_packing_ab={
            "padded_slots_ratio": 3.3,
            "padded": {"padding_frac": 0.8},
            "packed": {"padding_frac": 0.38},
        },
        slo_report={
            "error_bound": 0.0905,
            "multi_turn": {"fleet": {"ttft_s": {"p99": 0.5}}},
            "overhead_ab": {"overhead_frac_vs_off": 0.01},
        },
        sharded_serving={
            "n_chips": 2,
            "dense_tp": {"scaling_x": 1.7, "token_parity": True},
            "moe_ep": {"scaling_x": 1.5, "expert_shard_ok": True},
        },
        weight_swap_ab={
            "dense": {
                "full_pause_ms": 20.0, "staged_pause_ms": 8.0,
                "staged_below_full": True, "post_swap_parity": True,
            },
            "staged_below_full_all": True,
            "post_swap_parity_all": True,
        },
        decode_ab={
            "ctx2048_b16": {"dense_toks_per_sec": 1.0,
                            "paged_toks_per_sec": 2.0},
            "derived_dispatch_table": {"paged_min_cache_len": 2048},
        },
        gateway_ab={
            "admission_on": {"interactive_ttft_steps": {"p99": 3}},
            "admission_off": {"interactive_ttft_steps": {"p99": 11}},
            "p99_ttft_steps_improvement": 3.67,
            "interactive_p99_ttft_better_with_admission": True,
            "parity": {"stream_concat_matches_result": True,
                       "gateway_matches_rollout": True},
            "leak_free": True,
        },
        control_plane_ab={
            "rep_scan": {"schedules_per_sec": 2000.0},
            "router_indexed": {"schedules_per_sec": 18000.0},
            "speedup": 9.0,
            "meets_5x": True,
            "routing_parity": True,
        },
    )
    blob = json.loads(json.dumps(summary))
    for key in bench.SUMMARY_REQUIRED_KEYS:
        assert key in blob, key
    assert "gateway_ab" in bench.SUMMARY_REQUIRED_KEYS
    assert "control_plane_ab" in bench.SUMMARY_REQUIRED_KEYS
    assert "obs_ledger_report" in bench.SUMMARY_REQUIRED_KEYS
    cp = blob["control_plane_ab"]
    assert cp["meets_5x"] is True
    assert cp["routing_parity"] is True
    assert cp["speedup"] == 9.0
    gw = blob["gateway_ab"]
    assert gw["interactive_p99_ttft_better_with_admission"] is True
    assert gw["p99_ttft_steps_improvement"] == 3.67
    assert gw["parity"]["gateway_matches_rollout"] is True
    assert blob["spec_decode_ab"]["b2"]["spec_on"]["verify_chunks"] > 0
    assert blob["decode"]["b2"]["decode_toks_per_sec"] == 2.0
    assert blob["decode"]["b4"]["decode_toks_per_sec"] is None
    assert blob["paged_decode_ab"]["ctx2048_b16"] == [1.0, 2.0]
    assert blob["dispatch_table"] == {"paged_min_cache_len": 2048}
    assert blob["sharded_serving"]["moe_ep"]["expert_shard_ok"] is True
    assert blob["slo_report"]["multi_turn"]["fleet"]["ttft_s"]["p99"] == 0.5
    assert blob["slo_report"]["overhead_ab"]["overhead_frac_vs_off"] == 0.01
    assert blob["weight_swap_ab"]["staged_below_full_all"] is True
    assert blob["train_packing_ab"]["padded_slots_ratio"] == 3.3
    hier = blob["prefix_cache_hier"]["sweep"]["c8"]
    assert hier["token_parity"] is True
    assert (
        hier["host_on"]["cached_token_frac"]
        > hier["host_off"]["cached_token_frac"]
    )
    assert blob["prefix_cache_hier"]["dropped"] == []
    fab = blob["kv_fabric_ab"]["sweep"]["c8"]
    assert fab["token_parity"] is True
    assert (
        fab["fabric_on"]["fleet_cached_token_frac"]
        > fab["fabric_off"]["fleet_cached_token_frac"]
    )
    assert fab["reprefill_token_reduction"] >= 2.0
    assert blob["kv_fabric_ab"]["dropped"] == []
    assert blob["weight_swap_ab"]["dense"]["staged_pause_ms"] < (
        blob["weight_swap_ab"]["dense"]["full_pause_ms"]
    )
    assert isinstance(blob["sections"], dict)
    # every recorded section row carries a status field
    for row in blob["sections"].values():
        assert row["status"] in ("ok", "error", "timeout")


@pytest.mark.slow
def test_sharded_serving_section_runs_inline_on_a_cpu_mesh():
    """With enough local devices (the test harness's 8-device virtual
    CPU mesh) the section measures INLINE — both arms report 1-vs-N
    decode tok/s, greedy token parity holds, and the moe arm's expert
    weights are genuinely sharded."""
    out = bench.bench_sharded_serving(
        n_chips=2, n_reqs=2, prompt_len=16, max_new=12, page=16, chunk=4
    )
    assert out["n_chips"] == 2
    for arm in ("dense_tp", "moe_ep"):
        row = out[arm]
        assert row["chips1_decode_toks_per_sec"] > 0
        assert row["chips2_decode_toks_per_sec"] > 0
        assert row["token_parity"] is True, row
    assert out["moe_ep"]["expert_shard_ok"] is True


@pytest.mark.slow
def test_weight_swap_ab_paged_arm_staged_beats_full():
    """The weight_swap_ab measure on the paged+prefix-cache arm: the
    staged pause must come in strictly below the full-reload pause and
    the post-swap stream must match the fresh-engine replay (ISSUE 8
    acceptance, inline CPU-smoke arm)."""
    row = bench._weight_swap_measure_arm(
        "paged_prefix", n_reqs=2, prompt_len=24, max_new=32, page=16,
        chunk=4, repeats=1,
    )
    assert row["staged_below_full"] is True, row
    assert row["post_swap_parity"] is True, row
    assert row["staged_pause_ms"] < row["full_pause_ms"]
    assert row["decode_tps_during_stage"] > 0  # decode never stopped
