"""python -m pytest benchmark/tests -q   (CPU, toy sizes)

What came with the falcon-h1-34b-instruct configuration: its reference,
its driver, its byte arithmetic and its readers."""

import contextlib
import io
import json
import os
import sys
import types

import numpy as np
import pytest

from benchmark.tests.test_benchmark import BENCH, ROOT, _json, _load

CELL = "rollout-full-parallel.falcon-h1-34b-instruct"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = [
    "decode_hbm_share.parallel", "ssm_update_hbm_share.parallel",
    "paged_attn_hbm_share.parallel", "ssm_time_share.parallel",
    "state_slots_live_share.parallel", "cache_bytes_state_share",
]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


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
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b-instruct.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def parallel_result(run, spec, tmp_path_factory):
    """The toy cell through run.py's own functions: the result, and the
    lines printed before it by their ``event``.  The driver's limits are
    the cell's own (the other rollout cells' 0.02 / 0.004): the float32 toy
    reads 1e-6 under them and its float8 control has to fail them."""
    import jax

    cell = dict(next(w for w in spec["workloads"] if w["name"] == CELL), chips=1)
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as patch, contextlib.redirect_stdout(out):
        patch.setattr(run, "OUT_DIR", str(tmp_path_factory.mktemp("out")))
        result = run.execute(
            spec, cell, _json("tiny-parallel.json"),
            _json("tiny-rollout-parallel.json"), seed=2**31 + 13, seconds=5.0,
            traced=False, dev=jax.devices()[0],
            peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        )
    notes = {}
    for line in out.getvalue().splitlines():
        if line.startswith("{"):
            note = json.loads(line)
            notes[note.get("event")] = note
    return result, notes


def test_parallel_driver_end_to_end(parallel_result):
    r, _ = parallel_result
    json.dumps(r)
    assert set(r["metrics"]) == {"rollout_tok_per_s", "setup_s"}
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["metrics"]["rollout_tok_per_s"]["value"] > 0


def test_parallel_check_is_against_the_plain_reference(parallel_result):
    _, notes = parallel_result
    c = notes["check"]
    assert c["correct"]
    # float32 toy on the CPU: prefill in chunks from a carried state, conv
    # tail and paged prefix, then decode through pages and state slots in
    # EVERY layer, IS the reference's whole-sequence forward
    assert c["paged"] and c["state_dtype"] == "float32"
    assert c["pool_shape"][0] == c["state_shape"][0] == 3
    assert c["state_shape"][1:] == [4, 12, 32]  # slots, N, H P
    assert len(c["reference"]) == 3
    for row in c["reference"]:
        assert row["within"] and row["max_abs_diff"] < 1e-4, row
    assert c["sequences_nonfinite"] == 0
    assert c["state_copies_total"] > 0  # siblings took a fill's end state
    # an eighth of the toy's 512 rows: ids, logits, log-probabilities
    assert c["vocab_rows"] == 64 and c["token_id_max"] < 64
    assert c["tolerance"] == {"max_abs": 0.09, "mean_abs": 0.019}


def test_the_control_is_refused_by_the_comparison_that_passes_the_server(
    parallel_result,
):
    _, notes = parallel_result
    c = notes["check"]
    assert not c["control"]["within"] and c["control"]["nonfinite"] == 0
    assert c["control"]["what"] == "weights in float8_e4m3fn"
    # for the record: a state carried in bfloat16 is NOT refused
    assert c["bf16_state"]["what"] == "state in bfloat16"
    assert c["bf16_state"]["max_abs_diff"] < c["control"]["max_abs_diff"]


@pytest.mark.parametrize(
    "max_off, mean_off, within",
    [(0.089, 0.0189, True), (0.091, 0.01, False), (0.05, 0.0191, False)],
)
def test_compare_holds_both_limits(run, max_off, mean_off, within):
    driver = run.load_module("drivers", "rollout_closed_loop_parallel")
    want = np.zeros(100, np.float32)
    got = np.full(100, (100 * mean_off - max_off) / 99, np.float32)
    got[0] = max_off
    row = driver.compare(got, want)
    assert row["within"] is within, row
    got[3] = np.nan
    assert not driver.compare(got, want)["within"]


def test_the_driver_takes_the_loop_that_sends_in_the_streams_order(run):
    driver = run.load_module("drivers", "rollout_closed_loop_parallel")
    shared = sys.modules[driver.InOrderDriver.__module__]
    assert driver.Driver._slot is shared.Driver._slot
    assert driver.Driver._send_next is shared.Driver._send_next
    # counters, record and check are this stack's own
    for name in ("_counters", "measure", "check"):
        assert getattr(driver.Driver, name) is not getattr(shared.Driver, name)


def test_window_record_counts_both_caches(parallel_result):
    _, notes = parallel_result
    w = notes["window_closed"]
    assert len(w["requests_queued"]) == 2
    assert w["state_copies"] >= 0 and w["state_reprefills"] >= 0
    assert 0 < w["pages_live"] <= w["pages_total"] and w["rows_preempted"] == 0
    assert 0 < w["state_slots_live"] <= 4
    assert 0 < w["fill_stage_share"]


def test_configuration_file_keeps_the_catalog_row_whole(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == config["name"])
    assert entry["file"] == "benchmark/configs/falcon-h1-34b-instruct.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json"
    )
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "vocab_size"]
    pub = config["hf_config"]
    assert (pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"], pub["intermediate_size"], pub["num_hidden_layers"],
            pub["vocab_size"], pub["rope_theta"]) == (
        5120, 20, 4, 128, 21504, 72, 261120, 100000000000)
    assert (pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_ssm"],
            pub["mamba_d_state"], pub["mamba_n_groups"], pub["mamba_d_conv"],
            pub["mamba_chunk_size"]) == (32, 128, 4096, 256, 2, 4, 128)
    assert config["published"] == {"num_hidden_layers": 72, "vocab_size": 261120}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (8, 32640)
    for key, value in pub.items():
        if key in ("architectures", "torch_dtype") or key in config["reduced"]:
            continue
        assert config[key] == value, key
    if os.path.isfile(CATALOG):  # the row the driver drew, key for key
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f)
                if r["name"] == "Falcon-H1-34B-Instruct"
            )
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert pub[key] == value, key
            if key not in config["reduced"]:
                assert config[key] == value, key
    serve = config["roles"]["serve"]
    assert serve["num_hidden_layers"] == 8
    assert serve["model_overrides"] == {
        "layer_types": ["parallel"] * 8, "n_dense_layers": 8,
        "vocab_size": 32640, "moe_held_experts": 0,
    }
    for key in ("deployment", "assumed", "resident", "reduced_how"):
        assert config[key]
    assert "9 pipeline stages" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("2507.22448", "float32", "bfloat16", "key_multiplier",
                 "(z, x, B, C, dt)", "(gate, down)", "2,048 channels",
                 "heads 0-15", "time_step_limit", "rotate-half", "final_layernorm"):
        assert said in assumed, said


def test_program_reads_the_configuration_as_the_cell_runs_it(config):
    from areal_tpu.models import hybrid, paged
    from benchmark.lib.program import model_config

    cfg = model_config(config, "serve")
    assert cfg.layer_types == ("parallel",) * 8
    assert (cfg.n_layers, cfg.n_attn_layers, cfg.n_mamba_layers,
            cfg.n_parallel_layers, cfg.n_dense_layers) == (8, 8, 8, 8, 8)
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate_dim, cfg.rotary_base) == (5120, 20, 4, 128, 21504, 1e11)
    assert (cfg.mamba_n_heads, cfg.mamba_head_dim, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_chunk_size,
            cfg.mamba_conv_dim) == (32, 128, 256, 2, 4, 128, 5120)
    assert cfg.vocab_size == 32640 and cfg.dtype == "bfloat16"
    assert not cfg.tied_embedding and not cfg.is_moe
    # every multiplier as published; the head's as its reciprocal, 1 as None
    pub = config["hf_config"]
    assert (cfg.embed_scale, cfg.logits_divisor, cfg.attn_in_scale) == (
        pub["embedding_multiplier"], 128.0, None)
    assert (cfg.attn_out_scale, cfg.key_scale, cfg.ssm_in_scale, cfg.ssm_out_scale) == (
        pub["attention_out_multiplier"], pub["key_multiplier"],
        pub["ssm_in_multiplier"], pub["ssm_out_multiplier"])
    assert list(cfg.ssm_scales) == pub["ssm_multipliers"]
    assert list(cfg.mlp_scales) == pub["mlp_multipliers"]
    # 2,048 B a token a layer, in EVERY layer; 4 MiB + 30,720 B a slot a layer
    assert paged.pool_shapes(cfg, 384, 512)[0] == (8, 384, 4, 512, 128)
    assert paged.kv_pool_layout_bytes(cfg, 196608 // 512, 512) == (196608 * 16384, 0)
    assert hybrid.state_layout_bytes(cfg, 64) == 64 * 8 * (4 * 2**20 + 30720)
    assert [[(r.kind, r.count) for r in p] for p in hybrid.plan_periods(cfg)] == [
        [("parallel", 8)]
    ]


def test_parameter_count_is_the_issues_arithmetic(config):
    """430.1 M a layer (attention 31.46 M, Mamba-2 68.35 M, MLP 330.30 M),
    3.775 B on this chip: against ``jax.eval_shape`` of the program's
    tree."""
    import jax

    from areal_tpu.models import hybrid
    from benchmark.lib import flops_parallel
    from benchmark.lib.program import model_config

    hf = flops_parallel.as_run(config)
    assert (hf["num_hidden_layers"], hf["vocab_size"]) == (8, 32640)
    assert abs(flops_parallel.attention_params(hf) / 1e6 - 31.46) < 0.01
    assert abs(flops_parallel.mamba_params(hf) / 1e6 - 68.35) < 0.01
    assert abs(flops_parallel.mlp_params(hf) / 1e6 - 330.30) < 0.01
    assert abs(flops_parallel.layer_params(hf) / 1e6 - 430.1) < 0.05
    n_ref = flops_parallel.held_param_count(hf)
    assert abs(n_ref / 1e9 - 3.775) < 0.001
    cfg = model_config(config, "serve")
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 0 <= n - n_ref < 1e-4 * n_ref  # norms, conv bias, per-head scalars
    # the whole model at its published depth and vocabulary: 33.6 B
    whole = flops_parallel.held_param_count(config["hf_config"])
    assert abs(whole / 1e9 - 33.64) < 0.01


def test_byte_arithmetic_on_the_issues_step_worked_by_hand(config):
    from benchmark.lib import flops, flops_hybrid, flops_parallel

    hf = flops_parallel.as_run(config)
    assert flops_parallel.kv_bytes_per_token_layer(hf) == 2048
    assert flops_parallel.ssm_state_bytes(hf) == 4 * 2**20
    # what the two accepted readers this cell borrows take from the
    # published keys (ssm_update_hbm_share, paged_attn_hbm_share)
    assert flops_hybrid.ssm_update_min_bytes(config["hf_config"], 61) == 61 * 8 * 2**20
    assert flops.kv_bytes_per_token(config["hf_config"], 1) == 2048
    assert flops_parallel.state_bytes_per_row_layer(hf) == 2 * (4 * 2**20 + 30720)
    # the head is read, the embedding's table gathered: 7.55 - 0.33 GB
    assert abs(flops_parallel.weight_bytes(hf) / 1e9 - 7.216) < 0.001
    # ISSUE 46's step: 64 rows at 2.6k of context
    b = flops_parallel.cache_bytes(hf, 64 * 8, 64 * 2600)
    assert abs(b["state"] / 1e9 - 4.33) < 0.01 and abs(b["pages"] / 1e9 - 2.73) < 0.01
    sec = flops_parallel.decode_min_seconds(
        hf, decode_steps=1, row_steps=64, context_token_reads=64 * 2600,
        hbm_bytes_per_s=819e9,
    )
    assert sec == pytest.approx(
        (flops_parallel.weight_bytes(hf) + b["state"] + b["pages"]) / 819e9
    )
    assert 0.0172 < sec < 0.0176  # "about 17 ms a step"


def _ctx(config, counters, op_seconds=None):
    return types.SimpleNamespace(
        config=config, peaks=PEAKS, n_devices=1, memory_peak_bytes=13_300_000_000,
        trace={"busy_s": 2.0, "window_s": 4.0, "op_seconds": op_seconds or {}},
        window={"counters": counters},
    )


def test_new_readers_on_a_made_up_run(run, config, monkeypatch):
    from benchmark.lib import span_reduce

    counters = {
        "window_s": 10.0, "tokens_emitted": 60.0 * 320, "decode_chunks": 40,
        "chunk_size": 8, "context_token_reads": 60.0 * 320 * 2600,
        "parallel_shape": [8, 2, 256, 32640], "layer_types": ["parallel"] * 8,
    }
    ctx = _ctx(
        config, counters,
        {"paged_attn_decode.2": 0.4, "ssm_state_update.4": 0.5,
         "ssm_state_rows.5": 0.1, "fusion.1": 1.0},
    )
    value = lambda name: run.load_reader(name).value(ctx)
    # 320 steps x (7.216 + 60 x 8 x 8.45 MB + 60 x 2600 x 16 KiB) / 819 GB/s
    step = (7.2158e9 + 60 * 8 * 2 * (4 * 2**20 + 30720) + 60 * 2600 * 16384) / 819e9
    assert value("decode_hbm_share.parallel") == pytest.approx(
        100 * 320 * step / 5.0, rel=1e-3
    )
    assert value("ssm_time_share.parallel") == pytest.approx(30.0)
    assert value("paged_attn_time_share") == pytest.approx(20.0)
    # no xplane in a made-up run: the span readers leave theirs out
    for name in ("ssm_update_hbm_share.parallel", "paged_attn_hbm_share.parallel",
                 "state_slots_live_share.parallel", "cache_bytes_state_share"):
        assert value(name) is None, name
    # 240 executions of each kernel (30 steps x 8 layers): the state's of
    # 700 us over 60 live rows (60 x 8 MiB / 819 GB/s = 615 us), the paged
    # one's of 500 us over 160,000 positions x 2,048 B / 819 GB/s = 400 us
    t = {
        "lines": [[
            span_reduce.Span(i, i + 0.1, "areal.engine.decode.dispatch",
                             {"ctx_tokens_sum": n, "state_rows": 60 * 8,
                              "parallel_layers": 8, "rows": 60})
            for i, n in enumerate([150_000, 170_000])
        ] + [
            span_reduce.Span(5 + i, 5.1 + i, "areal.engine.harvest.fold",
                             {"tokens": 60 * 8})
            for i in range(2)
        ] + [
            span_reduce.Span(8 + i, 8.1 + i, "areal.engine.ensure_blocks",
                             {"state_slots_live": 63 + i, "state_slots_total": 64})
            for i in range(2)
        ]],
        "devices": {"tpu0": [(0.0, 0.0005, "paged_attn_decode.2")] * 240
                    + [(0.0, 0.0007, "ssm_state_update.4")] * 240
                    + [(0.0, 0.001, "ssm_state_rows.5")] * 16
                    + [(0.0, 0.5, "paged_attn_fill.7")]},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    got = value("ssm_update_hbm_share.parallel")
    assert got == pytest.approx(100 * (60 * 8 * 2**20 / 819e9) / 0.0007)
    assert 0 < got < 100
    got = value("paged_attn_hbm_share.parallel")
    assert got == pytest.approx(100 * (160_000 * 2048 / 819e9) / 0.0005)
    assert 0 < got < 100
    assert value("state_slots_live_share.parallel") == pytest.approx(100 * 63.5 / 64)
    # 480 (row, layer) states of 8.45 MB against 160,000 positions of 16 KiB
    state, pages = 480 * 2 * (4 * 2**20 + 30720), 160_000 * 16384
    assert value("cache_bytes_state_share") == pytest.approx(
        100 * state / (state + pages)
    )
    assert 55 < value("cache_bytes_state_share") < 65


def test_new_readers_find_nothing_on_a_program_without_their_counts(
    run, spec, config, monkeypatch
):
    """The parent commit has no ``state_rows`` on its dispatch spans and
    the other cells' window records no ``parallel_shape``: the two NEW
    readers return None there and raise nothing."""
    from benchmark.lib import span_reduce

    new = [m for m in spec["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in new] == NEW
    ctx = _ctx(config, {
        "window_s": 10.0, "tokens_emitted": 6400.0, "decode_chunks": 5,
        "chunk_size": 64, "context_token_reads": 6400.0 * 1000, "n_layers": 28,
    }, {"fusion.1": 1.5, "paged_attn_decode.1": 0.5})
    t = {
        "lines": [[span_reduce.Span(0, 0.1, "areal.engine.decode.dispatch",
                                    {"ctx_tokens_sum": 300_000}),
                   span_reduce.Span(1, 1.1, "areal.engine.ensure_blocks", {"pages_live": 5})]],
        "devices": {"tpu0": [(0.0, 0.002, "paged_attn_decode.2")] * 30},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    for name in ("decode_hbm_share.parallel", "cache_bytes_state_share",
                 "ssm_update_hbm_share.parallel", "ssm_time_share.parallel",
                 "state_slots_live_share.parallel"):
        assert run.load_reader(name).value(ctx) is None, name
    # four names are read by the file named before the last dot: no new code
    for name in NEW[1:5]:
        assert not os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in (NEW[0], NEW[5]):
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_the_traffic_files_draw_is_the_same_for_two_seeds():
    from benchmark.lib import lengths

    traffic = _json("../../traffic/rollout-full-parallel.json")
    a = [lengths.rollout_prompt(traffic, 7, 32640, k) for k in range(40)]
    b = [lengths.rollout_prompt(traffic, 2**31 + 5, 32640, k) for k in range(40)]
    assert [len(p["prompt_ids"]) for p in a] == [len(p["prompt_ids"]) for p in b]
    assert [p["max_new_tokens"] for p in a] == [p["max_new_tokens"] for p in b]
    assert a[0]["prompt_ids"] != b[0]["prompt_ids"]  # --seed gives the ids
    plens = [len(p["prompt_ids"]) for p in a]
    assert 1024 <= min(plens) and max(plens) <= 3072
    # ids from the slice this chip holds, all of it
    ids = [t for p in a for t in p["prompt_ids"]]
    assert 30_000 < max(ids) < 32640
    news = [n for p in a for n in p["max_new_tokens"]]
    assert 16 <= min(news) and max(news) <= 2048


def test_the_cell_reports_what_the_issue_lists(spec):
    # membership, never position: later cells are appended after this one
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "rollout-full-parallel"
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert len(config["why"]) <= 200
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
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            assert m["moves"] == "rollout_tok_per_s" and m["unit"] == "%", m
            assert m["workloads"] == [CELL]
    traffic = _json("../../traffic/rollout-full-parallel.json")
    assert traffic["driver"] == "rollout_closed_loop_parallel"
    assert (traffic["prompts_in_flight"], traffic["samples_per_prompt"]) == (12, 8)
    assert traffic["prompt_len"] == {"min": 1024, "max": 3072}
    assert traffic["output_len"] == {"median": 384, "sigma": 1.0, "min": 16, "max": 2048}
    assert traffic["temperature"] == 1.0
    others = [
        _json(f"../../traffic/{w['traffic']}.json")["length_seed"]
        for w in spec["workloads"] if w["name"] != CELL
        and "length_seed" in _json(f"../../traffic/{w['traffic']}.json")
    ]
    assert traffic["length_seed"] not in others  # a draw of its own
    eng = traffic["engine"]
    assert (eng["max_concurrent_batch"], eng["prefill_chunk_tokens"]) == (64, 1024)
    assert "keep_routed_experts" not in eng  # no router, nothing to follow
    assert eng["kv_cache_len"] == 3072 + 2048
    assert eng["kv_pool_tokens"] % eng["page_size"] == 0
