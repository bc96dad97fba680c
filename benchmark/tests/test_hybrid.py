"""python -m pytest benchmark/tests -q   (CPU, toy sizes)

What came with the granite-4.0-h-small configuration: its reference, its
driver, its byte arithmetic and its readers."""

import contextlib
import io
import json
import os
import sys
import types

import numpy as np
import pytest

from benchmark.tests.test_benchmark import BENCH, ROOT, _json, _load

CELL = "rollout-full-hybrid.granite-4.0-h-small"


@pytest.fixture(scope="module")
def run():
    return sys.modules.get("benchmark_run_under_test") or _load(
        os.path.join(BENCH, "run.py"), "benchmark_run_under_test"
    )


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-small.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def hybrid_result(run, spec):
    """The toy hybrid cell through run.py's own functions: the result,
    and the lines printed before it by their ``event``."""
    import jax

    cell = dict(next(w for w in spec["workloads"] if w["name"] == CELL), chips=1)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = run.execute(
            spec, cell, _json("tiny-hybrid.json"),
            _json("tiny-rollout-hybrid.json"), seed=2**31 + 7, seconds=5.0,
            traced=False, dev=jax.devices()[0],
            peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        )
    notes = {}
    for line in out.getvalue().splitlines():
        if line.startswith("{"):
            note = json.loads(line)
            notes[note.get("event")] = note
    return result, notes


def test_hybrid_driver_end_to_end(hybrid_result):
    r, _ = hybrid_result
    json.dumps(r)
    # ISSUE 31 keeps the tail out of this cell
    assert set(r["metrics"]) == {"rollout_tok_per_s", "setup_s"}
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["metrics"]["rollout_tok_per_s"]["value"] > 0


def test_hybrid_check_is_against_the_plain_reference(hybrid_result):
    _, notes = hybrid_result
    c = notes["check"]
    # (the check's own verdict: the toy's short warm-up does not meet every
    # shape its loop meets, and run.py holds a compile in the window
    # against the result)
    assert c["correct"]
    # float32 toy on the CPU: prefill in chunks, then decode through state
    # slots and pages, IS the reference's full forward, and the routing the
    # engine kept is the reference's own
    assert c["state_dtype"] == "float32" and c["paged"]
    assert len(c["reference"]) == 3
    for row in c["reference"]:
        assert row["within"] and row["max_abs_diff"] < 1e-4, row
        assert row["router_margin_min"] >= 0
        assert row["router_flips_share"] == 0.0
    # every sequence of the window is looked at, not the picks alone
    assert c["sequences_nonfinite"] == 0


def test_the_control_is_refused_by_the_comparison_that_passes_the_server(
    hybrid_result,
):
    """The same reference with every matrix in float8, following the same
    routing, goes through ``compare`` like the server's log-probabilities
    and comes out NOT correct, by the cell's own limits; the state carried
    in bfloat16 is reported beside it."""
    from benchmark.drivers import rollout_closed_loop_hybrid as drv

    _, notes = hybrid_result
    c = notes["check"]
    worst = max(r["max_abs_diff"] for r in c["reference"])
    assert c["tolerance"] == {
        "max_abs": drv.LOGP_MAX_ABS, "mean_abs": drv.LOGP_MEAN_ABS
    }
    assert c["control"]["what"] == "weights in float8_e4m3fn"
    assert not c["control"]["within"]
    assert (
        c["control"]["max_abs_diff"] > drv.LOGP_MAX_ABS
        or c["control"]["mean_abs_diff"] > drv.LOGP_MEAN_ABS
    )
    assert c["control"]["max_abs_diff"] > c["bf16_state"]["max_abs_diff"] > worst


@pytest.mark.parametrize(
    "max_off,mean_off,within",
    [(0.5, 0.5, True), (1.5, 0.5, False), (0.9, 1.5, False)],
)
def test_compare_holds_both_limits(max_off, mean_off, within):
    from benchmark.drivers import rollout_closed_loop_hybrid as drv

    want = np.zeros(100, np.float32)
    got = np.full(100, mean_off * drv.LOGP_MEAN_ABS, np.float32)
    got[0] = max(got[0], max_off * drv.LOGP_MAX_ABS)
    row = drv.compare(got, want)
    assert row["within"] is within and row["nonfinite"] == 0
    got[3] = np.nan
    row = drv.compare(got, want)
    assert not row["within"] and row["first_nonfinite"] == 3


def test_float8_rounding_of_the_reference_is_e4m3():
    """The reading 'every matrix in float8' rounds in plain float32
    arithmetic, so that it runs on any backend: it is e4m3 to the bit."""
    import jax.numpy as jnp

    from benchmark.lib import reference_granitemoehybrid as ref

    x = np.random.RandomState(0).standard_normal((64, 96)).astype(np.float32)
    x[0, :4] = [0.0, 1e-6, -3.0, x.max() * 2]
    got = np.asarray(ref._fp8_weights({"w": jnp.asarray(x), "b": jnp.ones(3)})["w"])
    s = np.abs(x).max() / 448.0
    want = np.asarray(
        (jnp.asarray(x) / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    )
    np.testing.assert_array_equal(got, want)


def test_hybrid_window_record_counts_the_second_cache_kind(hybrid_result):
    _, notes = hybrid_result
    w = notes["window_closed"]
    # 3 x 2 samples out against 4 slots, each replaced when it is back:
    # siblings queued together share a fill and get a copy of its end
    # state, a sibling that comes late prefills again
    assert w["state_copies"] >= 1 and w["state_reprefills"] >= 0
    assert len(w["requests_queued"]) == 2


def test_configuration_file_states_the_cut(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == config["name"])
    assert entry["file"] == "benchmark/configs/granite-4.0-h-small.json"
    assert set(entry["reduced"]) == set(config["reduced"])
    pub = config["hf_config"]
    # no width differs from the source: every top-level number is the
    # published one but the keys the cut names
    for key, value in pub.items():
        if key in config["reduced"] or key in ("architectures", "torch_dtype"):
            continue
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 10 and pub["num_hidden_layers"] == 40
    assert config["num_local_experts"] == 36 and pub["num_local_experts"] == 72
    assert config["layer_types"] == pub["layer_types"][:10]
    over = config["roles"]["serve"]["model_overrides"]
    assert over["layer_types"] == config["layer_types"]
    assert over["moe_held_experts"] == config["num_local_experts"]
    for key in ("source", "deployment", "assumed", "resident"):
        assert config[key]


def test_program_reads_the_configuration_as_the_cell_runs_it(config):
    from benchmark.lib.program import model_config

    cfg = model_config(config, "serve")
    assert cfg.n_layers == 10 and cfg.n_mamba_layers == 9
    assert cfg.n_experts == 72 and cfg.n_held_experts == 36
    assert cfg.n_experts_per_tok == 10 and cfg.shared_expert_dim == 1536
    assert (cfg.hidden_dim, cfg.mamba_d_inner, cfg.mamba_conv_dim) == (4096, 8192, 8448)
    assert cfg.dtype == "bfloat16" and not cfg.use_rope


def test_held_parameter_count_is_the_configurations_arithmetic(config):
    """4.96 B parameters here (the file's ``resident``), and the
    published 32 B whole."""
    import jax

    from areal_tpu.models import hybrid
    from benchmark.lib import flops_hybrid
    from benchmark.lib.program import model_config

    hf = config["hf_config"]
    held = flops_hybrid.held_param_count(hf, config["layer_types"], 36)
    assert abs(held / 1e9 - 4.96) < 0.01
    whole = flops_hybrid.held_param_count(hf, hf["layer_types"], 72)
    assert abs(whole / 1e9 - 32.2) < 0.3
    # and it is the program's tree, less norms, biases and per-head scalars
    cfg = model_config(config, "serve")
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 0 <= n - held < 1e-3 * held


def test_byte_arithmetic_on_a_case_worked_by_hand(config):
    from benchmark.lib import flops_hybrid

    hf, kinds = config["hf_config"], config["layer_types"]
    # one sequence's state in one layer: 128 x 8192 float32 = 4 MiB
    assert flops_hybrid.ssm_state_bytes(hf) == 4 << 20
    assert flops_hybrid.ssm_update_min_bytes(hf, 60) == 60 * 2 * (4 << 20)
    # a live row a step: 9 layers x 2 x (4 MiB + 3 x 8448 bf16)
    assert flops_hybrid.state_bytes_per_row_step(hf, kinds) == 9 * 2 * (
        (4 << 20) + 2 * 3 * 8448
    )
    # K and V of one position: ONE attention layer, 8 heads of 128, bf16
    assert flops_hybrid.kv_bytes_per_token(hf, kinds) == 2 * 8 * 128 * 2
    assert abs(flops_hybrid.weight_bytes(hf, kinds, 36) / 1e9 - 9.93) < 0.02
    least = flops_hybrid.decode_min_seconds(
        hf, kinds, 36, decode_steps=1, row_steps=64, context_token_reads=0,
        hbm_bytes_per_s=819e9,
    )
    # the issue's sizing: 12.1 ms of weights + 5.9 ms of state at 64 rows
    assert 0.0175 < least < 0.0185


def _ctx(config, counters, op_seconds=None):
    return types.SimpleNamespace(
        config=config, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        n_devices=1, memory_peak_bytes=13_300_000_000,
        trace={"busy_s": 2.0, "window_s": 4.0, "op_seconds": op_seconds or {}},
        window={"counters": counters},
    )


def test_new_readers_on_a_made_up_run(run, config):
    counters = {
        "window_s": 10.0, "tokens_emitted": 64.0 * 320, "decode_chunks": 5,
        "chunk_size": 64, "context_token_reads": 0.0,
        "layer_types": config["layer_types"], "held_experts": 36,
        "moe_expert_pairs": [10.0] * 35 + [45.0],
    }
    ctx = _ctx(
        config, counters,
        {"ssm_state_update.3": 0.3, "ssm_state_rows.1": 0.1, "fusion.1": 1.6},
    )
    value = lambda name: run.load_reader(name).value(ctx)
    # 320 steps x (9.92 GB of weights + 64 rows x 75.9 MB of state) / 819
    # GB/s = 18.09 ms each, over 5 s of busy time
    assert value("decode_hbm_share.hybrid") == pytest.approx(
        100 * 320 * 0.01809 / 5.0, rel=2e-3
    )
    assert value("ssm_time_share") == pytest.approx(20.0)
    # busiest held expert over the mean: 45 / (395 / 36)
    assert value("moe_expert_load_max_over_mean") == pytest.approx(45 / (395 / 36))
    # no xplane in a made-up run: the span readers leave their metric out
    assert value("ssm_update_hbm_share") is None
    assert value("state_slots_live_share") is None


@pytest.mark.parametrize("folded,rows", [([64 * 40, 64 * 48], 44.0), ([], 64.0)])
def test_state_update_roofline_counts_the_rows_of_the_slice(
    run, config, monkeypatch, folded, rows
):
    """Live rows an execution: from the chunks folded inside the slice
    (here 40 and 48 rows where the window's mean is 64), else the
    window's mean."""
    from benchmark.lib import flops_hybrid, span_reduce

    t = {
        "lines": [[
            span_reduce.Span(i, i + 0.1, "areal.engine.harvest.fold", {"tokens": n})
            for i, n in enumerate(folded)
        ] + [span_reduce.Span(9.0, 9.1, "areal.engine.step", {})]],
        "devices": {"tpu0": [(0.0, 0.001, "ssm_state_update.3")] * 90},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    ctx = _ctx(config, {
        "tokens_emitted": 64.0 * 320, "decode_chunks": 5, "chunk_size": 64,
        "layer_types": config["layer_types"],
    })
    least = 90 * flops_hybrid.ssm_update_min_bytes(config["hf_config"], rows) / 819e9
    assert run.load_reader("ssm_update_hbm_share").value(ctx) == pytest.approx(
        100 * least / 0.09
    )


def test_new_readers_find_nothing_on_a_program_without_the_second_cache_kind(
    run, spec
):
    """The parent commit has no such spans and counters, and the dense
    cells' window records no such keys: every new reader returns None."""
    with open(os.path.join(BENCH, "configs", "qwen2.5-1.5b.json")) as f:
        qwen = json.load(f)
    ctx = _ctx(qwen, {
        "window_s": 10.0, "tokens_emitted": 6400.0, "decode_chunks": 5,
        "chunk_size": 64, "context_token_reads": 6400.0 * 1000, "n_layers": 28,
    }, {"fusion.1": 1.5})
    new = [m for m in spec["per_layer"] if m["workloads"] == [CELL]]
    assert len(new) == 5
    for m in new:
        assert run.load_reader(m["name"]).value(ctx) is None, m["name"]


def test_the_cell_reports_what_the_issue_lists(spec):
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    e2e = {m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"rollout_tok_per_s", "setup_s"}
    per_layer = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert per_layer == {
        "schedule_wait_ms", "engine_host_share", "decode_rows_mean",
        "paged_attn_time_share", "hbm_peak_gb.rollout",
        "engine_bookkeeping_share", "server_poll_overhead_ms",
        "kv_pages_live_share", "decode_hbm_share.hybrid",
        "ssm_update_hbm_share", "ssm_time_share", "state_slots_live_share",
        "moe_expert_load_max_over_mean",
    }
    traffic = _json("../../traffic/rollout-full-hybrid.json")
    assert traffic["driver"] == "rollout_closed_loop_hybrid"
    assert traffic["prompts_in_flight"] * traffic["samples_per_prompt"] == 96
    # three warm-up prompts end their prefill in one chunk
    assert 3 * traffic["warm"]["sibling_prompt_len"] <= 256
    # the mixed rounds' prompts do NOT, in whatever order they arrive: any
    # two of a round fit a chunk less the last, which ends a step later
    for lens, _ in traffic["warm"]["mixed_rounds"]:
        assert sum(lens) > 256 and all(
            sum(lens) - n < 256 for n in lens
        ), lens
    # the engine keeps the routing of more requests than a window completes
    assert traffic["engine"]["keep_routed_experts"] >= 384
    assert traffic["engine"]["max_concurrent_batch"] == 64
    assert traffic["engine"]["prefill_chunk_tokens"] == 256
