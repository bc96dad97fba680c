"""python -m pytest benchmark/tests -q   (CPU, toy sizes)

What came with the smallthinker-21b-a3b configuration: its reference, its
driver, its byte and operation arithmetic and its readers."""

import contextlib
import io
import json
import os
import sys
import types

import numpy as np
import pytest

from benchmark.tests.test_benchmark import BENCH, ROOT, _json, _load

CELL = "rollout-full-window.smallthinker-21b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
    with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def window_result(run, spec, tmp_path_factory):
    """The toy window cell through run.py's own functions: the result, and
    the lines printed before it by their ``event``."""
    import jax

    cell = dict(next(w for w in spec["workloads"] if w["name"] == CELL), chips=1)
    out = io.StringIO()
    # an output directory of its own: run.py empties ``out/work`` when a
    # run starts, and the other cells' tests run beside this one
    with pytest.MonkeyPatch.context() as patch, contextlib.redirect_stdout(out):
        patch.setattr(run, "OUT_DIR", str(tmp_path_factory.mktemp("out")))
        result = run.execute(
            spec, cell, _json("tiny-window.json"),
            _json("tiny-rollout-window.json"), seed=2**31 + 13, seconds=5.0,
            traced=False, dev=jax.devices()[0],
            peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        )
    notes = {}
    for line in out.getvalue().splitlines():
        if line.startswith("{"):
            note = json.loads(line)
            notes[note.get("event")] = note
    return result, notes


def test_window_driver_end_to_end(window_result):
    r, _ = window_result
    json.dumps(r)
    # ISSUE 40 keeps the tail out of this cell
    assert set(r["metrics"]) == {"rollout_tok_per_s", "setup_s"}
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["metrics"]["rollout_tok_per_s"]["value"] > 0


def test_window_check_is_against_the_plain_reference(window_result):
    _, notes = window_result
    c = notes["check"]
    assert c["correct"]
    # float32 toy on the CPU: prefill in chunks, then decode through the
    # two pools while pages go behind the window, IS the reference's
    # whole-sequence forward under the mask, and the routing the engine
    # kept is the reference's own
    assert c["paged"] and c["window_pages_released_total"] > 0
    assert [s[0] for s in c["pool_shapes"]] == [2, 6]  # global, window layers
    assert len(c["reference"]) == 3
    for row in c["reference"]:
        assert row["within"] and row["max_abs_diff"] < 1e-4, row
        assert row["prompt_len"] > 24  # each crosses the toy's window
        assert row["router_margin_min"] >= 0
        assert row["router_flips_share"] == 0.0
    assert c["sequences_nonfinite"] == 0
    # a decoding row never held more than ceil((W + chunks in flight) /
    # page) + 1 window-layer pages: (24 + 2 x 8) / 16 -> 3 + 1
    assert 0 < c["window_row_pages_max"] <= 4


def test_every_control_is_refused_by_the_comparison_that_passes_the_server(
    window_result,
):
    from benchmark.drivers import rollout_closed_loop_window as drv

    _, notes = window_result
    c = notes["check"]
    assert c["tolerance"] == {
        "max_abs": drv.LOGP_MAX_ABS, "mean_abs": drv.LOGP_MEAN_ABS
    }
    assert set(drv.CONTROLS) == {"control", "window_off", "router_reads_m"}
    # RoPE on the global layers is on the line too; at float32 the toy
    # refuses it like the others (on the chip, at bfloat16 and random
    # weights, no limit on log-probabilities can: the driver says why)
    assert set(drv.ON_RECORD) == {"rope_on_global"}
    worst = max(r["max_abs_diff"] for r in c["reference"])
    for name in {**drv.CONTROLS, **drv.ON_RECORD}:
        assert not c[name]["within"], name
        assert (
            c[name]["max_abs_diff"] > drv.LOGP_MAX_ABS
            or c[name]["mean_abs_diff"] > drv.LOGP_MEAN_ABS
        ), name
        assert c[name]["max_abs_diff"] > worst, name


@pytest.mark.parametrize(
    "max_off,mean_off,within",
    [(0.5, 0.5, True), (1.5, 0.5, False), (0.9, 1.5, False)],
)
def test_compare_holds_both_limits(max_off, mean_off, within):
    from benchmark.drivers import rollout_closed_loop_window as drv

    want = np.zeros(100, np.float32)
    got = np.full(100, mean_off * drv.LOGP_MEAN_ABS, np.float32)
    got[0] = max(got[0], max_off * drv.LOGP_MAX_ABS)
    row = drv.compare(got, want)
    assert row["within"] is within and row["nonfinite"] == 0
    got[3] = np.nan
    row = drv.compare(got, want)
    assert not row["within"] and row["first_nonfinite"] == 3


def test_window_record_counts_what_the_window_readers_take(window_result):
    _, notes = window_result
    w = notes["window_closed"]
    assert len(w["requests_queued"]) == 2
    assert w["state_copies"] == 0 and w["state_reprefills"] == 0
    assert w["window_pages_released"] > 0 and w["prefix_refused_window"] >= 0
    # what the window layers hold is less than what the global layers hold
    assert 0 < w["window_pages_live"] < w["global_pages_live"]
    assert 0 < w["fill_stage_share"]


def test_configuration_file_keeps_the_catalog_row_and_states_the_cut(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == config["name"])
    assert entry["file"] == "benchmark/configs/smallthinker-21b-a3b.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"
    )
    reduced = ["num_hidden_layers", "sliding_window_layout", "rope_layout"]
    assert entry["reduced"] == config["reduced"] == reduced
    pub = config["hf_config"]
    # every width of the catalog row, unchanged
    assert (pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["head_dim"], pub["moe_ffn_hidden_size"], pub["moe_num_primary_experts"],
            pub["moe_num_active_primary_experts"], pub["sliding_window_size"],
            pub["vocab_size"], pub["rope_theta"], pub["max_position_embeddings"]) == (
        2560, 28, 4, 128, 768, 64, 6, 4096, 151936, 1500000, 16384)
    assert pub["num_hidden_layers"] == 52 and not pub["tie_word_embeddings"]
    assert pub["sliding_window_layout"] == pub["rope_layout"] == [0, 1, 1, 1] * 13
    assert config["published"] == {k: pub[k] for k in reduced}
    # no width differs from the source: every top-level key is the
    # published one but the keys the cut names
    for key, value in pub.items():
        if key in reduced or key in ("architectures", "model_type", "torch_dtype"):
            continue
        assert config[key] == value, key
    assert [config[k] for k in reduced] == [8, [0, 1, 1, 1] * 2, [0, 1, 1, 1] * 2]
    if os.path.isfile(CATALOG):  # the row the driver drew, key for key
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f)
                if r["name"] == "SmallThinker-21BA3B-Instruct"
            )
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert pub[key] == value, key
    over = config["roles"]["serve"]["model_overrides"]
    assert over == {
        "layer_types": ["attention", "window", "window", "window"] * 2,
        "rope_layers": [False, True, True, True] * 2,
        "moe_first_expert": 0, "moe_held_experts": 64,
    }
    for key in ("deployment", "assumed", "resident", "reduced_how"):
        assert config[key]
    assert "7 one-chip stages" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("secondary", "ATTENTION's input", "ReLU", "bfloat16", "i - j <"):
        assert said in assumed, said


def test_program_reads_the_configuration_as_the_cell_runs_it(config):
    from areal_tpu.models import paged
    from benchmark.lib.program import model_config

    cfg = model_config(config, "serve")
    assert cfg.n_layers == 8 and cfg.n_window_layers == 6 and not cfg.is_latent
    assert cfg.layer_types == ("attention", "window", "window", "window") * 2
    assert cfg.rope_layers == (False, True, True, True) * 2
    assert (cfg.n_experts, cfg.n_held_experts, cfg.n_experts_per_tok,
            cfg.moe_router, cfg.moe_router_input, cfg.activation) == (
        64, 64, 6, "topk_softmax", "attn", "relu")
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.moe_intermediate_dim, cfg.sliding_window, cfg.rotary_base) == (
        2560, 28, 4, 128, 768, 4096, 1.5e6)
    assert cfg.vocab_size == 151936 and cfg.dtype == "bfloat16"
    assert not cfg.tied_embedding
    # the pools: 262,144 tokens x 2 global layers and 196,608 x 6 window
    # layers, 2,048 B a token a layer
    assert paged.kv_pool_layout_bytes(cfg, 262144 // 512, 512) == (1_073_741_824, 0)
    assert paged.kv_pool_layout_bytes(
        cfg, 196608 // 512, 512, layers=cfg.n_window_layers
    ) == (2_415_919_104, 0)


def test_held_parameter_count_is_the_configurations_arithmetic(config):
    """3.97 B parameters here (the file's ``resident``: 7.94 GB), and the
    published 21 B whole."""
    import jax

    from areal_tpu.models import hybrid
    from benchmark.lib import flops_window
    from benchmark.lib.program import model_config

    hf = flops_window.as_run(config)
    assert abs(flops_window.attn_params(hf) / 1e6 - 20.97) < 0.01
    assert abs(flops_window.expert_block_params(hf, 64) / 1e6 - (0.16 + 377.49)) < 0.01
    held = flops_window.held_param_count(hf, 8, 64, 151936)
    assert abs(held * 2 / 1e9 - 7.94) < 0.01
    whole = flops_window.held_param_count(hf, 52, 64, 151936)
    assert abs(whole / 1e9 - 21.5) < 0.1
    # and it is the program's tree, less norms
    cfg = model_config(config, "serve")
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 0 <= n - held < 1e-4 * held


def test_byte_and_operation_arithmetic_on_a_case_worked_by_hand(config):
    from benchmark.lib import flops_window

    hf = flops_window.as_run(config)
    # one cached position of one layer: K and V of 4 heads x 128 x 2 B
    assert flops_window.kv_bytes_per_token(hf) == 2048
    assert flops_window.kv_bytes_per_token(hf, layers=6) == 12288
    assert flops_window.layer_kinds(hf) == (2, 6)
    # 28 heads x 128 x (a score + a value) x 2 FLOP over 2,048 B: 7 FLOP/B,
    # far under a v5e's ridge of 197e12 / 819e9 = 240
    assert flops_window.attn_flops_per_token(hf) == 28 * 128 * 4 == 14336
    assert flops_window.attn_flops_per_token(hf) / 2048 == 7.0
    # a window layer's query reads the 4,095 before it, or all it has
    assert flops_window.window_reads(hf, 10_000) == 4095
    assert flops_window.window_reads(hf, 300) == 300
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops_window.window_kernel_min_seconds(hf, 55 * 4095, peaks)
    assert least == pytest.approx(55 * 4095 * 2048 / 819e9)  # the bytes decide
    # a decode step reads the 7.94 GB held less the embedding's table
    # (0.78 GB: only its tokens' rows are read): 7.16 GB; and at 55 rows of
    # 6.7k context 55 x (2 x 6,700 + 6 x 4,095) x 2,048 B = 4.28 GB where an
    # unwindowed stack would read 6.04: 11.4 GB, 14 ms a step
    assert flops_window.weight_bytes(hf, 8, 64, 151936) == (
        flops_window.held_param_count(hf, 8, 64, 151936) - 151936 * 2560
    ) * 2
    assert abs(flops_window.weight_bytes(hf, 8, 64, 151936) / 1e9 - 7.16) < 0.01
    step = flops_window.decode_min_seconds(
        hf, 64, 151936, decode_steps=1, context_token_reads=55 * 6700,
        window_token_reads=55 * 4095, hbm_bytes_per_s=819e9,
    )
    assert step == pytest.approx((7.16e9 + 4.28e9) / 819e9, rel=2e-3)
    assert 0.0139 < step < 0.0141


def _ctx(config, counters, op_seconds=None):
    return types.SimpleNamespace(
        config=config, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        n_devices=1, memory_peak_bytes=12_500_000_000,
        trace={"busy_s": 2.0, "window_s": 4.0, "op_seconds": op_seconds or {}},
        window={"counters": counters},
    )


def test_new_readers_on_a_made_up_run(run, config, monkeypatch):
    from benchmark.lib import region_reduce, span_reduce

    counters = {
        "window_s": 10.0, "tokens_emitted": 55.0 * 320, "decode_chunks": 40,
        "chunk_size": 8, "context_token_reads": 55.0 * 320 * 6700,
        "window_token_reads": 55.0 * 320 * 4095, "window_shape": [64, 151936],
        "window_pages_allocated": 400.0, "window_pages_freed_behind": 150.0,
        "moe_expert_pairs": [30.0] * 63 + [60.0],
    }
    ctx = _ctx(
        config, counters,
        {"paged_window_decode.3": 0.3, "paged_attn_decode.2": 0.2, "fusion.1": 1.5},
    )
    value = lambda name: run.load_reader(name).value(ctx)
    # 320 steps x 11.44 GB / 819 GB/s = 13.97 ms each, over 5 s of busy time
    assert value("decode_hbm_share.window") == pytest.approx(
        100 * 320 * 0.013968 / 5.0, rel=2e-3
    )
    assert value("window_pages_released_share") == pytest.approx(37.5)
    # the busiest expert took 60 pairs where the mean took 30.47
    assert value("moe_expert_load_max_over_mean.window") == pytest.approx(
        60 / (1950 / 64)
    )
    # the accepted reader matches both kernels' calls by the name they carry
    assert value("paged_attn_time_share") == pytest.approx(25.0)
    # no xplane in a made-up run: the span and region readers leave theirs out
    assert value("paged_window_roofline_share") is None
    assert value("window_attn_time_share") is None
    # 90 executions of 0.8 ms, each over 225,000 positions (the mean of the
    # slice's dispatch spans): 225,000 x 2,048 B / 819 GB/s = 0.563 ms
    t = {
        "lines": [[
            span_reduce.Span(i, i + 0.1, "areal.engine.decode.dispatch",
                             {"window_tokens_sum": n, "ctx_tokens_sum": 2 * n})
            for i, n in enumerate([220_000, 230_000])
        ]],
        "devices": {"tpu0": [(0.0, 0.0008, "paged_window_decode.3")] * 90
                    + [(0.0, 0.002, "paged_attn_decode.2")] * 30
                    + [(0.0, 0.5, "paged_window_fill.7")]},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    got = value("paged_window_roofline_share")
    assert got == pytest.approx(100 * (225_000 * 2048 / 819e9) / 0.0008)
    assert 0 < got < 100
    # a quarter of the busy time in the window layers' region
    monkeypatch.setattr(
        region_reduce, "regions_of",
        lambda ctx: {"seconds": {
            ("jit_hybrid_decode_chunk", "areal.attn.window", "forward"): 0.4,
            ("jit_hybrid_fill_chunk", "areal.attn.window", "forward"): 0.1,
            ("jit_hybrid_decode_chunk", "areal.attn", "forward"): 0.3,
            ("jit_hybrid_decode_chunk", "areal.moe.experts", "forward"): 1.2,
        }},
    )
    assert value("window_attn_time_share") == pytest.approx(25.0)


def test_new_readers_find_nothing_on_a_program_without_window_layers(run, spec):
    """The parent commit has no such spans, regions and counters, and the
    other cells' window records no such keys: every new reader returns
    None."""
    with open(os.path.join(BENCH, "configs", "qwen2.5-1.5b.json")) as f:
        qwen = json.load(f)
    ctx = _ctx(qwen, {
        "window_s": 10.0, "tokens_emitted": 6400.0, "decode_chunks": 5,
        "chunk_size": 64, "context_token_reads": 6400.0 * 1000, "n_layers": 28,
    }, {"fusion.1": 1.5, "paged_attn_decode.1": 0.5})
    new = [m for m in spec["per_layer"] if m["workloads"] == [CELL]]
    assert sorted(m["name"] for m in new) == [
        "decode_hbm_share.window", "moe_expert_load_max_over_mean.window",
        "paged_window_roofline_share", "window_attn_time_share",
        "window_pages_released_share",
    ]
    for m in new:
        assert run.load_reader(m["name"]).value(ctx) is None, m["name"]
    # a name's reader is the file named before the last dot: no new code
    assert not os.path.isfile(os.path.join(
        BENCH, "layer_metrics", "moe_expert_load_max_over_mean.window.py"
    ))


def test_the_traffic_files_draw_is_the_same_for_two_seeds():
    from benchmark.lib import lengths

    traffic = _json("../../traffic/rollout-full-window.json")
    a = [lengths.rollout_prompt(traffic, 7, 151936, k) for k in range(40)]
    b = [lengths.rollout_prompt(traffic, 2**31 + 5, 151936, k) for k in range(40)]
    assert [len(p["prompt_ids"]) for p in a] == [len(p["prompt_ids"]) for p in b]
    assert [p["max_new_tokens"] for p in a] == [p["max_new_tokens"] for p in b]
    assert a[0]["prompt_ids"] != b[0]["prompt_ids"]  # --seed gives the ids
    plens = [len(p["prompt_ids"]) for p in a]
    assert 4096 <= min(plens) and max(plens) <= 8192  # each past the window
    assert max(max(p["prompt_ids"]) for p in a) > 100_000  # the whole vocabulary
    news = [n for p in a for n in p["max_new_tokens"]]
    assert 16 <= min(news) and max(news) <= 2048


def test_the_cell_reports_what_the_issue_lists(spec):
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "rollout-full-window"
    assert spec["workloads"][-1] == cell and spec["configs"][-1]["name"] == cell["config"]
    assert len(spec["configs"][-1]["why"]) <= 200
    e2e = {m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"rollout_tok_per_s", "setup_s"}
    per_layer = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert per_layer == {
        "schedule_wait_ms", "engine_host_share", "decode_rows_mean",
        "paged_attn_time_share", "hbm_peak_gb.rollout",
        "engine_bookkeeping_share", "server_poll_overhead_ms",
        "kv_pages_live_share", "decode_hbm_share.window",
        "paged_window_roofline_share", "window_attn_time_share",
        "window_pages_released_share", "moe_expert_load_max_over_mean.window",
    }
    # the five it brings stand at the end of the list, in the issue's order
    assert [m["name"] for m in spec["per_layer"][-5:]] == [
        "decode_hbm_share.window", "paged_window_roofline_share",
        "window_attn_time_share", "window_pages_released_share",
        "moe_expert_load_max_over_mean.window",
    ]
    traffic = _json("../../traffic/rollout-full-window.json")
    assert traffic["driver"] == "rollout_closed_loop_window"
    assert traffic["prompts_in_flight"] * traffic["samples_per_prompt"] == 96
    assert traffic["prompt_len"] == {"min": 4096, "max": 8192}
    assert traffic["output_len"] == {"median": 384, "sigma": 1.0, "min": 16, "max": 2048}
    assert traffic["temperature"] == 1.0 and traffic["length_seed"] == 20261004
    eng = traffic["engine"]
    assert (eng["max_concurrent_batch"], eng["kv_pool_tokens"],
            eng["kv_window_pool_tokens"], eng["keep_routed_experts"]) == (
        64, 262144, 196608, 512)
    assert eng["kv_cache_len"] >= 8192 + 2048
    budget = eng["prefill_chunk_tokens"]
    assert eng["chunk_size"] < 4096 and eng["page_size"] % 128 == 0
    # eight warm-up prompts end their prefill in one chunk
    assert 8 * traffic["warm"]["sibling_prompt_len"] <= budget
    # the mixed rounds' prompts do NOT, in whatever order they arrive
    for lens, _ in traffic["warm"]["mixed_rounds"]:
        assert sum(lens) > budget and all(sum(lens) - n < budget for n in lens), lens
    # every fill shape warmed is one the engine's batch rule lets through
    for f, c in traffic["warm"]["fill_shapes"]:
        f_pad = 1 << (f - 1).bit_length()
        assert f_pad * c <= 4 * budget, (f, c)
