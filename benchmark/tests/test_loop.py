"""python -m pytest benchmark/tests -q   (CPU, toy sizes)

What came with the ouro-2.6b configuration: its reference, its driver, its
byte arithmetic and its readers."""

import contextlib
import io
import json
import os
import sys
import types

import numpy as np
import pytest

from benchmark.tests.test_benchmark import BENCH, ROOT, _json, _load

CELL = "rollout-full-loop.ouro-2.6b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = [
    "decode_hbm_share.loop", "paged_attn_hbm_share.loop", "loop_time_share",
    "page_wait_share",
]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
TOY_LIMITS = (0.02, 0.005)


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
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "traffic", "rollout-full-loop.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def loop_result(run, spec, tmp_path_factory):
    """The toy cell through run.py's own functions: the result, and the
    lines printed before it by their ``event``.  The cell's limits are set
    for 192 layer passes in bfloat16 (0.9 / 0.22: a toy's float8 control,
    0.10 / 0.03 over its 6 passes, stands inside them), so the toy runs
    under ``TOY_LIMITS``, between its own two readings: the float32 toy
    reads 1e-6 under them and its float8 control has to fail them."""
    import jax

    cell = dict(next(w for w in spec["workloads"] if w["name"] == CELL), chips=1)
    out = io.StringIO()
    load_module = run.load_module

    def with_toy_limits(kind, name):
        mod = load_module(kind, name)
        if (kind, name) == ("drivers", "rollout_closed_loop_loop"):
            mod.LOGP_MAX_ABS, mod.LOGP_MEAN_ABS = TOY_LIMITS
        return mod

    with pytest.MonkeyPatch.context() as patch, contextlib.redirect_stdout(out):
        patch.setattr(run, "OUT_DIR", str(tmp_path_factory.mktemp("out")))
        patch.setattr(run, "load_module", with_toy_limits)
        result = run.execute(
            spec, cell, _json("tiny-loop.json"), _json("tiny-rollout-loop.json"),
            seed=2**31 + 17, seconds=5.0, traced=False, dev=jax.devices()[0],
            peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        )
    notes = {}
    for line in out.getvalue().splitlines():
        if line.startswith("{"):
            note = json.loads(line)
            notes[note.get("event")] = note
    return result, notes


def test_loop_driver_end_to_end(loop_result):
    r, _ = loop_result
    json.dumps(r)
    assert set(r["metrics"]) == {"rollout_tok_per_s", "setup_s"}
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["metrics"]["rollout_tok_per_s"]["value"] > 0


def test_loop_check_is_against_the_plain_reference(loop_result):
    _, notes = loop_result
    c = notes["check"]
    assert c["correct"]
    # float32 toy on the CPU: prefill in chunks over a paged prefix, then
    # decode through a cache layer of its own for every (pass, layer), IS
    # the reference's whole-sequence forward of three passes
    assert c["paged"] and c["pool_shape"][0] == 6  # 2 layers x 3 passes
    assert c["loop_counts"] == {
        "loop_steps": 3, "cache_layers": 6, "kv_bytes_per_token": 6 * 2 * 4 * 8 * 4,
    }
    assert len(c["reference"]) == 3
    for row in c["reference"]:
        assert row["within"] and row["max_abs_diff"] < 1e-4, row
    assert c["sequences_nonfinite"] == 0
    assert c["tolerance"] == {"max_abs": TOY_LIMITS[0], "mean_abs": TOY_LIMITS[1]}
    # the control is refused by the comparison that passes the server
    assert not c["control"]["within"] and c["control"]["nonfinite"] == 0
    assert c["control"]["what"] == "weights in float8_e4m3fn"


def test_the_limits_lie_between_the_chips_readings(run):
    driver = run.load_module("drivers", "rollout_closed_loop_loop")
    for limit, key in ((driver.LOGP_MAX_ABS, "max_abs"), (driver.LOGP_MEAN_ABS, "mean_abs")):
        server, control = driver.SERVER_READINGS[key], driver.CONTROL_READINGS[key]
        assert 0 < server[0] <= server[1] < limit < control[0] <= control[1], key
        # room on both sides: a factor of 1.5 at least
        assert limit / server[1] > 1.5 and control[0] / limit > 1.5, key


@pytest.mark.parametrize("which", ["max", "mean"])
def test_compare_holds_both_limits(run, which):
    driver = run.load_module("drivers", "rollout_closed_loop_loop")
    hi, lo = driver.LOGP_MAX_ABS, driver.LOGP_MEAN_ABS
    want = np.zeros(100, np.float32)
    got = np.full(100, 0.5 * lo, np.float32)
    assert driver.compare(got, want)["within"]
    if which == "max":
        got[0] = 1.01 * hi
    else:
        got[:] = 1.01 * lo
    assert not driver.compare(got, want)["within"]
    got = np.full(100, 0.5 * lo, np.float32)
    got[3] = np.nan
    assert not driver.compare(got, want)["within"]


def test_the_driver_takes_the_dense_record_and_the_streams_order(run):
    driver = run.load_module("drivers", "rollout_closed_loop_loop")
    shared = sys.modules[driver.InOrderDriver.__module__]
    dense = sys.modules[driver.ClosedLoopDriver.__module__]
    assert driver.Driver._slot is shared.Driver._slot
    assert driver.Driver._send_next is shared.Driver._send_next
    assert driver.ClosedLoopDriver is dense.Driver
    for name in ("_counters", "measure", "check"):
        assert getattr(driver.Driver, name) is not getattr(shared.Driver, name)


def test_window_record_counts_pages_and_waits(loop_result):
    _, notes = loop_result
    w = notes["window_closed"]
    assert len(w["requests_queued"]) == 2
    assert 0 < w["pages_live"] <= w["pages_total"]
    assert w["rows_preempted"] == 0 and w["admission_page_waits"] >= 0
    assert w["engine_steps"] > 0 and 0 < w["fill_stage_share"]


def test_configuration_file_keeps_the_catalog_row_whole(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == config["name"])
    assert entry["file"] == "benchmark/configs/ouro-2.6b.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    )
    assert entry["reduced"] == config["reduced"] == [] and len(entry["why"]) <= 200
    pub = config["hf_config"]
    assert (pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"], pub["intermediate_size"], pub["num_hidden_layers"],
            pub["vocab_size"], pub["rope_theta"], pub["total_ut_steps"],
            pub["early_exit_threshold"], pub["rms_norm_eps"]) == (
        2048, 16, 16, 128, 5632, 48, 49152, 1000000, 4, 1, 1e-6)
    assert pub["architectures"] == ["OuroForCausalLM"]
    assert pub["torch_dtype"] == "bfloat16" and not pub["tie_word_embeddings"]
    for key, value in pub.items():
        if key not in ("architectures", "torch_dtype"):
            assert config[key] == value, key
    if os.path.isfile(CATALOG):  # the row the driver drew, key for key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert pub[key] == value and config[key] == value, key
    assert config["roles"] == {"serve": {"num_hidden_layers": 48}}  # no train role
    for key in ("deployment", "assumed", "resident"):
        assert config[key]
    assert "ONE v5e chip holding the model whole" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("2510.25741", "OuroForCausalLM", "bfloat16", "sandwich",
                 "EVERY pass", "early_exit_gate", "192 layers", "rotate-half",
                 "refused by name", "SHARING"):
        assert said in assumed, said


def test_program_reads_the_configuration_as_the_cell_runs_it(config):
    from areal_tpu.models import paged
    from benchmark.lib.program import model_config

    cfg = model_config(config, "serve")
    assert not cfg.is_hybrid and not cfg.is_moe
    assert (cfg.n_layers, cfg.loop_steps, cfg.n_attn_layers) == (48, 4, 192)
    assert cfg.sandwich_norm and cfg.loop_exit_gate and cfg.loop_exit_threshold == 1.0
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate_dim, cfg.rotary_base, cfg.vocab_size) == (
        2048, 16, 16, 128, 5632, 1000000, 49152)
    assert cfg.dtype == "bfloat16" and not cfg.tied_embedding
    assert cfg.sliding_window is None and not cfg.use_attention_bias
    # 1,572,864 B a cached token: 8,192 B in each of 192 cache layers
    assert paged.pool_shapes(cfg, 38, 128)[0] == (192, 38, 16, 128, 128)
    assert paged.kv_pool_layout_bytes(cfg, 1, 1) == (1_572_864, 0)


def test_parameter_count_is_the_issues_arithmetic(config):
    """51.38 M a layer, 48 of them 2.466 B, embedding and head 201.3 M:
    2.668 B, 5.34 GB: against ``jax.eval_shape`` of the program's tree."""
    import jax

    from areal_tpu.models import transformer
    from benchmark.lib import flops, flops_ouro
    from benchmark.lib.program import model_config

    hf = config["hf_config"]
    assert abs(flops.matmul_params_per_layer(hf) / 1e6 - 51.38) < 0.01
    n_ref = flops_ouro.param_count(hf)
    assert abs(n_ref / 1e9 - 2.668) < 0.001
    cfg = model_config(config, "serve")
    shapes = jax.eval_shape(
        lambda: transformer.init_params_in_dtype(cfg, jax.random.PRNGKey(0))
    )
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == n_ref
    assert {str(x.dtype) for x in jax.tree.leaves(shapes)} == {"bfloat16"}
    assert abs(2 * n / 1e9 - 5.336) < 0.001


def test_byte_arithmetic_on_the_issues_step_worked_by_hand(config):
    from benchmark.lib import flops_ouro

    hf = config["hf_config"]
    assert (flops_ouro.passes(hf), flops_ouro.cache_layers(hf)) == (4, 192)
    assert flops_ouro.kv_bytes_per_token(hf) == 192 * 2 * 16 * 128 * 2 == 1_572_864
    assert flops_ouro.paged_call_bytes(hf, 1000) == 1000 * 8192
    # 4 x 4.93 GB of layers + 0.2 GB of head = 19.9 GB a step
    assert abs(flops_ouro.weight_bytes_per_step(hf) / 1e9 - 19.93) < 0.01
    # ISSUE 55's step: the weights four times (24 ms) and 5,400 tokens of
    # the pool once (10.4 ms) at 819 GB/s
    sec = flops_ouro.decode_min_seconds(
        hf, decode_steps=1, context_token_reads=5400, hbm_bytes_per_s=819e9
    )
    assert sec == pytest.approx((19.93e9 + 5400 * 1_572_864) / 819e9, rel=1e-3)
    assert 0.034 < sec < 0.036
    # a token at no context: 4 passes of 48 layers' matrices and one head
    per_tok = flops_ouro.forward_flops_per_token(hf)
    assert per_tok == 2 * 51_380_224 * 192 + 2 * 2048 * 49152
    assert flops_ouro.forward_flops_per_token(hf, 1000) - per_tok == 4 * 192 * 2048 * 1000


def _ctx(config, counters, op_seconds=None):
    return types.SimpleNamespace(
        config=config, peaks=PEAKS, n_devices=1, memory_peak_bytes=14_300_000_000,
        trace={"busy_s": 2.0, "window_s": 4.0, "op_seconds": op_seconds or {}},
        window={"counters": counters},
    )


def test_new_readers_on_a_made_up_run(run, config, monkeypatch):
    from benchmark.lib import region_reduce, span_reduce

    counters = {
        "window_s": 10.0, "tokens_emitted": 14.0 * 120, "decode_chunks": 15,
        "chunk_size": 8, "context_token_reads": 14.0 * 120 * 600,
        "loop_shape": [48, 4, 192, 1_572_864],
        "admission_page_waits": 30.0, "engine_steps": 40.0,
    }
    ctx = _ctx(config, counters, {"paged_attn_decode.2": 0.4, "fusion.1": 1.6})
    value = lambda name: run.load_reader(name).value(ctx)
    # 120 steps x 19.93 GB + 1,680 tokens x 600 positions x 1.57 MB, over
    # 819 GB/s, of 5 busy seconds of the window
    least = (120 * 19.93e9 + 1680 * 600 * 1_572_864) / 819e9
    assert value("decode_hbm_share.loop") == pytest.approx(100 * least / 5.0, rel=1e-3)
    assert 0 < value("decode_hbm_share.loop") < 100
    assert value("page_wait_share") == pytest.approx(75.0)
    # no xplane in a made-up run: the trace's readers leave theirs out
    assert value("paged_attn_hbm_share.loop") is None
    assert value("loop_time_share") is None
    # 1,536 executions (8 steps x 192 cache layers) of 12 us over 8,000
    # positions x 8,192 B / 819 GB/s = 80 us... of 100 us
    t = {
        "lines": [[
            span_reduce.Span(i, i + 0.1, "areal.engine.decode.dispatch",
                             {"ctx_tokens_sum": n, "rows": 14})
            for i, n in enumerate([7_500, 8_500])
        ]],
        "devices": {"tpu0": [(0.0, 0.0001, "paged_attn_decode.2")] * 1536
                    + [(0.0, 0.5, "paged_attn_fill.7")]},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    got = value("paged_attn_hbm_share.loop")
    assert got == pytest.approx(100 * (8_000 * 8192 / 819e9) / 0.0001)
    assert 0 < got < 100
    # the regions' seconds: the loop's own 0.05 s and its norm's 0.03 s of
    # 2 busy seconds; the layers' work inside the loop is not the loop's
    table = {"seconds": {
        ("jit_paged_decode_chunk", "areal.loop", "forward"): 0.05,
        ("jit_paged_decode_chunk", "areal.loop.norm", "forward"): 0.03,
        ("jit_paged_decode_chunk", "areal.attn", "forward"): 1.2,
        ("jit_paged_decode_chunk", "areal.layers", "forward"): 0.3,
    }}
    monkeypatch.setattr(region_reduce, "regions_of", lambda ctx: table)
    assert value("loop_time_share") == pytest.approx(100 * 0.08 / 2.0)


def test_new_readers_find_nothing_on_a_program_without_their_counts(
    run, spec, config, monkeypatch
):
    """The parent commit names no ``areal.loop`` region and keeps no
    ``admission_page_waits``; another cell's window record has no
    ``loop_shape``: the new readers return None there and raise nothing."""
    from benchmark.lib import region_reduce, span_reduce

    new = [m for m in spec["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW
    qwen = _json("../../configs/qwen2.5-1.5b.json")
    ctx = _ctx(qwen, {
        "window_s": 10.0, "tokens_emitted": 6400.0, "decode_chunks": 5,
        "chunk_size": 64, "context_token_reads": 6400.0 * 1000, "n_layers": 28,
    }, {"fusion.1": 1.5, "paged_attn_decode.1": 0.5})
    t = {
        "lines": [[span_reduce.Span(0, 0.1, "areal.engine.decode.dispatch",
                                    {"ctx_tokens_sum": 300_000})]],
        "devices": {"tpu0": [(0.0, 0.002, "paged_attn_decode.2")] * 30},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    table = {"seconds": {("jit_paged_decode_chunk", "areal.attn", "forward"): 1.2}}
    monkeypatch.setattr(region_reduce, "regions_of", lambda ctx: table)
    for name in NEW:
        assert run.load_reader(name).value(ctx) is None, name
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_the_traffic_file_holds_the_issues_parameters(traffic):
    from benchmark.lib import lengths

    assert traffic["driver"] == "rollout_closed_loop_loop"
    assert (traffic["prompts_in_flight"], traffic["samples_per_prompt"]) == (3, 8)
    assert traffic["prompt_len"] == {"min": 128, "max": 512}
    assert traffic["output_len"] == {"median": 256, "sigma": 1.0, "min": 16, "max": 1024}
    assert traffic["temperature"] == 1.0
    eng = traffic["engine"]
    assert eng["page_size"] in (64, 128) and eng["kv_cache_len"] == 1536
    assert eng["prefill_chunk_tokens"] == 512
    # ISSUE 55's 16 slots preempt rows: the largest count that does not,
    # by its rule, with the reason in the file
    assert eng["max_concurrent_batch"] == 5 and eng["page_size"] == 128
    assert "fewer than 8" in traffic["engine_why"]["max_concurrent_batch"]
    assert 3 * 8 >= 1.5 * eng["max_concurrent_batch"]  # a queue always stands
    assert eng["kv_pool_tokens"] % eng["page_size"] == 0
    # the pool beside 5.34 GB of weights on a chip of 15.75 GB
    assert 6.0e9 < eng["kv_pool_tokens"] * 1_572_864 < 9.0e9
    for key in ("page_size", "kv_pool_tokens", "max_concurrent_batch",
                "prefill_chunk_tokens and chunk_size", "kv_cache_len"):
        assert "TO BE SETTLED" not in traffic["engine_why"][key], key
    assert "ASSUMED" in " ".join(traffic["assumed"])
    a = [lengths.rollout_prompt(traffic, 7, 49152, k) for k in range(40)]
    b = [lengths.rollout_prompt(traffic, 2**31 + 5, 49152, k) for k in range(40)]
    assert [len(p["prompt_ids"]) for p in a] == [len(p["prompt_ids"]) for p in b]
    assert [p["max_new_tokens"] for p in a] == [p["max_new_tokens"] for p in b]
    assert a[0]["prompt_ids"] != b[0]["prompt_ids"]  # --seed gives the ids
    plens = [len(p["prompt_ids"]) for p in a]
    assert 128 <= min(plens) and max(plens) <= 512
    news = [n for p in a for n in p["max_new_tokens"]]
    assert 16 <= min(news) and max(news) == 1024
    assert 300 < np.mean(news) < 440
    # every warmed fill batch stacks under the engine's GiB of keys and values
    for f, c in traffic["warm"]["fill_shapes"]:
        assert f * c * 1_572_864 <= 1 << 30, (f, c)


def test_the_cell_reports_what_the_issue_lists(spec):
    # membership, never position: later cells are appended after this one
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "rollout-full-loop" and cell["config"] == "ouro-2.6b"
    e2e = {m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"rollout_tok_per_s", "setup_s"}
    per_layer = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert per_layer == {
        "schedule_wait_ms", "engine_host_share", "decode_rows_mean",
        "paged_attn_time_share", "hbm_peak_gb.rollout",
        "engine_bookkeeping_share", "server_poll_overhead_ms",
        "kv_pages_live_share", *NEW,
    }
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert by_name["decode_hbm_share.loop"]["layer"] == "model step, serving"
    assert by_name["paged_attn_hbm_share.loop"]["layer"] == "paged attention"
    assert by_name["loop_time_share"]["layer"] == "model step, serving"
    assert by_name["page_wait_share"]["source"] == "program_counter"
    assert all(by_name[n]["moves"] == "rollout_tok_per_s" for n in NEW)
