"""python -m pytest benchmark/tests -q   (CPU, toy sizes)

What came with the phi-4-mini-flash-reasoning configuration: its reference,
its driver, its byte and operation arithmetic and its readers."""

import contextlib
import io
import json
import os
import sys
import types

import numpy as np
import pytest

from benchmark.tests.test_benchmark import BENCH, ROOT, _json, _load

CELL = "rollout-full-shared.phi-4-mini-flash-reasoning"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOY_LIMITS = (0.02, 0.005)
NEW = [
    "decode_hbm_share.shared", "shared_kv_roofline_share",
    "cross_attn_time_share", "ssm_time_share.shared", "ssm1_update_hbm_share",
    "gmu_time_share", "state_slots_live_share.shared",
]


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
    with open(os.path.join(BENCH, "configs", "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def shared_result(run, spec, tmp_path_factory):
    """The toy cell through run.py's own functions: the result, and the
    lines printed before it by their ``event``."""
    import jax

    cell = dict(next(w for w in spec["workloads"] if w["name"] == CELL), chips=1)
    out = io.StringIO()
    # the driver's limits are set from the chip's readings at the published
    # widths (logits of deviation 2.5 after 32 bfloat16 layers); the float32
    # toy's logits are a fifth of that, and it is held to the other rollout
    # cells' limits, which its float8 control has to fail
    driver = run.load_module("drivers", "rollout_closed_loop_shared")
    load = run.load_module
    with pytest.MonkeyPatch.context() as patch, contextlib.redirect_stdout(out):
        patch.setattr(driver, "LOGP_MAX_ABS", TOY_LIMITS[0])
        patch.setattr(driver, "LOGP_MEAN_ABS", TOY_LIMITS[1])
        patch.setattr(
            run, "load_module",
            lambda kind, name: driver if (kind, name) == (
                "drivers", "rollout_closed_loop_shared"
            ) else load(kind, name),
        )
        patch.setattr(run, "OUT_DIR", str(tmp_path_factory.mktemp("out")))
        result = run.execute(
            spec, cell, _json("tiny-shared.json"),
            _json("tiny-rollout-shared.json"), seed=2**31 + 13, seconds=5.0,
            traced=False, dev=jax.devices()[0],
            peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        )
    notes = {}
    for line in out.getvalue().splitlines():
        if line.startswith("{"):
            note = json.loads(line)
            notes[note.get("event")] = note
    return result, notes


def test_shared_driver_end_to_end(shared_result):
    r, _ = shared_result
    json.dumps(r)
    assert set(r["metrics"]) == {"rollout_tok_per_s", "setup_s"}
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["metrics"]["rollout_tok_per_s"]["value"] > 0


def test_shared_check_is_against_the_plain_reference(shared_result):
    _, notes = shared_result
    c = notes["check"]
    assert c["correct"]
    # float32 toy on the CPU: prefill in chunks, then decode through state
    # slots, the window pool and the ONE-layer pool that three layers read,
    # IS the reference's whole-sequence forward
    assert c["paged"] and c["window_pages_released_total"] > 0
    assert [s[0] for s in c["pool_shapes"]] == [1, 3]  # global, window layers
    assert c["global_readers"] == 3 and c["state_dtype"] == "float32"
    assert len(c["reference"]) == 3
    for row in c["reference"]:
        assert row["within"] and row["max_abs_diff"] < 1e-4, row
        assert row["prompt_len"] > 24  # each crosses the toy's window
    assert c["sequences_nonfinite"] == 0
    assert c["state_copies_total"] > 0  # siblings took a fill's end state
    assert 0 < c["window_row_pages_max"] <= 4


def test_the_loop_sends_in_the_streams_order(run):
    """A sample leaves only once the engine holds the one before it, the
    first wave goes to a paused server, and the server resumes when the
    wave stands in its queue: so the engine's queue is the stream in every
    run, whatever the connections' timing."""
    import asyncio
    import random

    driver = run.load_module("drivers", "rollout_closed_loop_shared")
    d = driver.Driver.__new__(driver.Driver)
    d.traffic = {"prompts_in_flight": 3, "samples_per_prompt": 4}
    d.arrived, d.done, d.stopping = set(), [], False
    d._in_order, d._opening = None, 12
    stream = ((k, i, [k], 1) for k in range(10) for i in range(4))
    d._next_sample = lambda: next(stream)
    log, rng = [], random.Random(7)

    async def sample(k, i, ids, n):
        log.append(("sent", k, i))
        await asyncio.sleep(rng.random() * 0.004)  # the connection's own time
        log.append(("held", k, i))
        d.arrived.add(f"p{k}s{i}-0")
        await asyncio.sleep(rng.random() * 0.004)  # the sample's decode
        if len(d.done) >= 24:
            d.stopping = True
        return (k, i)

    d._sample = sample
    d._server_rpc = lambda cmd: log.append((cmd,))

    async def loop():
        await asyncio.gather(*(d._slot() for _ in range(3)))

    asyncio.run(loop())
    sends = [e[1:] for e in log if e[0] == "sent"]
    assert sends == [(k, i) for k in range(10) for i in range(4)][: len(sends)]
    assert len(sends) >= 24
    for n, e in enumerate(log):  # nothing is sent before the last is held
        if e[0] == "sent" and n:
            assert log[n - 1][0] in ("held", "pause", "resume"), log[n - 3 : n + 1]
    assert log[0] == ("pause",)
    # the server runs again when the twelfth sample of the wave is held
    at = log.index(("resume",))
    assert log[at - 1] == ("held", 2, 3) and log.count(("resume",)) == 1


def test_every_control_is_refused_by_the_comparison_that_passes_the_server(
    shared_result,
):
    from benchmark.drivers import rollout_closed_loop_shared as drv

    _, notes = shared_result
    c = notes["check"]
    assert c["tolerance"] == {"max_abs": TOY_LIMITS[0], "mean_abs": TOY_LIMITS[1]}
    # the cell's own limits: between the server's readings and the float8
    # control's on the chip (the driver's comment has both)
    assert (drv.LOGP_MAX_ABS, drv.LOGP_MEAN_ABS) == (0.35, 0.06)
    assert set(drv.CONTROLS) == {
        "control", "window_off", "gmu_own_input", "cross_own_kv", "lam_zero",
    }
    # nothing is on the line for the record only: the chip refuses the
    # second map's weight at 0 like the others (the driver says so)
    assert drv.ON_RECORD == {} and c["on_record"] == []
    worst = max(r["max_abs_diff"] for r in c["reference"])
    for name in drv.CONTROLS:
        assert not c[name]["within"], name
        assert c[name]["max_abs_diff"] > worst, name


@pytest.mark.parametrize(
    "max_off,mean_off,within",
    [(0.5, 0.5, True), (1.5, 0.5, False), (0.9, 1.5, False)],
)
def test_compare_holds_both_limits(max_off, mean_off, within):
    from benchmark.drivers import rollout_closed_loop_shared as drv

    want = np.zeros(100, np.float32)
    got = np.full(100, mean_off * drv.LOGP_MEAN_ABS, np.float32)
    got[0] = max(got[0], max_off * drv.LOGP_MAX_ABS)
    row = drv.compare(got, want)
    assert row["within"] is within and row["nonfinite"] == 0
    got[3] = np.nan
    row = drv.compare(got, want)
    assert not row["within"] and row["first_nonfinite"] == 3


def test_window_record_counts_the_three_cache_kinds(shared_result):
    _, notes = shared_result
    w = notes["window_closed"]
    assert len(w["requests_queued"]) == 2
    assert w["state_copies"] >= 0 and w["state_reprefills"] >= 0
    assert w["window_pages_released"] > 0 and w["rows_preempted"] == 0
    assert 0 < w["window_pages_live"] < w["global_pages_live"]
    assert 0 < w["state_slots_live"] <= 4
    assert 0 < w["fill_stage_share"]


def test_configuration_file_keeps_the_catalog_row_whole(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == config["name"])
    assert entry["file"] == "benchmark/configs/phi-4-mini-flash-reasoning.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    )
    assert entry["reduced"] == config["reduced"] == []  # nothing is cut
    pub = config["hf_config"]
    assert (pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["intermediate_size"], pub["num_hidden_layers"], pub["sliding_window"],
            pub["mb_per_layer"], pub["vocab_size"], pub["max_position_embeddings"]) == (
        2560, 40, 20, 10240, 32, 512, 2, 200064, 262144)
    assert pub["tie_word_embeddings"] and pub["model_type"] == "phi4flash"
    for key, value in pub.items():
        if key in ("architectures", "torch_dtype"):
            continue
        assert config[key] == value, key
    if os.path.isfile(CATALOG):  # the row the driver drew, key for key
        with open(CATALOG) as f:
            row = next(
                r for r in map(json.loads, f)
                if r["name"] == "Phi-4-mini-flash-reasoning"
            )
        assert row["source_url"] == config["source"]
        for key, value in row["config"].items():
            assert pub[key] == value and config[key] == value, key
    assert config["assumed_sizes"] == {
        "d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 160,
    }
    over = config["roles"]["serve"]["model_overrides"]
    assert over["layer_types"] == (
        ["mamba1", "window"] * 8 + ["mamba1", "attention"] + ["gmu", "cross"] * 7
    )
    assert config["roles"]["serve"]["num_hidden_layers"] == 32
    for key in ("deployment", "assumed", "resident"):
        assert config[key]
    assert "WHOLE" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for said in ("2507.06607", "2410.05258", "2312.00752", "layer 17", "dt_rank",
                 "ADJACENT", "NoPE", "bfloat16", "i - j <", "BEFORE the gate"):
        assert said in assumed, said


def test_program_reads_the_configuration_as_the_cell_runs_it(config):
    from areal_tpu.models import hybrid, paged
    from benchmark.lib import flops_sambay
    from benchmark.lib.program import model_config

    cfg = model_config(config, "serve")
    over = config["roles"]["serve"]["model_overrides"]
    # the adapter derives the stack; the file's list is the same one
    assert list(cfg.layer_types) == over["layer_types"] == flops_sambay.layer_kinds(
        flops_sambay.as_run(config)
    )
    assert (cfg.n_layers, cfg.n_window_layers, cfg.n_mamba_layers,
            cfg.n_cross_layers, cfg.n_gmu_layers, cfg.n_attn_layers) == (32, 8, 9, 7, 7, 9)
    assert (cfg.kv_shared_layer, cfg.memory_layer, cfg.n_global_readers) == (17, 16, 8)
    assert (cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.intermediate_dim, cfg.sliding_window) == (2560, 40, 20, 64, 10240, 512)
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_dt_rank) == (5120, 16, 4, 160)
    assert cfg.vocab_size == 200064 and cfg.dtype == "bfloat16" and cfg.tied_embedding
    assert not cfg.is_moe and cfg.n_dense_layers == 32 and cfg.diff_attention
    # a pair is one cached head of 128: no byte of padding in a page
    assert paged.pool_shapes(cfg, 576, 512)[0] == (1, 576, 10, 512, 128)
    # 294,912 tokens x ONE layer and 114,688 x 8 window layers, 5,120 B each
    assert paged.kv_pool_layout_bytes(cfg, 294912 // 512, 512) == (1_509_949_440, 0)
    assert paged.kv_pool_layout_bytes(
        cfg, 114688 // 512, 512, layers=cfg.n_window_layers
    ) == (4_697_620_480, 0)
    assert hybrid.state_layout_bytes(cfg, 64) == 64 * 9 * (16 * 5120 * 4 + 3 * 5120 * 2)
    # the plan folds the alternating stretches into periods
    assert [[(r.kind, r.count) for r in p] for p in hybrid.plan_periods(cfg)] == [
        [("mamba1", 8), ("window", 8)], [("mamba1", 1)], [("attention", 1)],
        [("gmu", 7), ("cross", 7)],
    ]


def test_parameter_count_is_the_configurations_arithmetic(config):
    """3.85 B parameters (the file's ``resident``: 7.71 GB), the published
    3.8 B."""
    import jax

    from areal_tpu.models import hybrid
    from benchmark.lib import flops_sambay
    from benchmark.lib.program import model_config

    hf = flops_sambay.as_run(config)
    assert flops_sambay.counts(hf) == {
        "mamba1": 9, "window": 8, "attention": 1, "gmu": 7, "cross": 7,
    }
    assert abs(flops_sambay.mixer_params(hf, "mamba1") / 1e6 - 41.2) < 0.1
    assert abs(flops_sambay.mixer_params(hf, "window") / 1e6 - 19.66) < 0.01
    assert abs(flops_sambay.mixer_params(hf, "cross") / 1e6 - 13.11) < 0.01
    assert abs(flops_sambay.mixer_params(hf, "gmu") / 1e6 - 26.21) < 0.01
    n_ref = flops_sambay.param_count(hf)
    assert abs(n_ref / 1e9 - 3.85) < 0.01
    cfg = model_config(config, "serve")
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 0 <= n - n_ref < 2e-4 * n_ref  # norms, biases, lambdas, skips


def test_byte_and_operation_arithmetic_on_a_case_worked_by_hand(config):
    from benchmark.lib import flops_sambay

    hf = flops_sambay.as_run(config)
    # one cached position of one layer: K and V of 20 heads x 64 x 2 B
    assert flops_sambay.kv_bytes_per_token(hf) == 5120
    assert flops_sambay.global_readers(hf) == 8
    # 40 heads x (a score of 64 + a pair's value of 128) x 2 FLOP over
    # 5,120 B: 3 FLOP/B, far under a v5e's ridge of 240
    assert flops_sambay.attn_flops_per_token(hf) == 40 * 64 * 6 == 15360
    assert flops_sambay.window_reads(hf, 10_000) == 511
    assert flops_sambay.window_reads(hf, 300) == 300
    assert flops_sambay.ssm_state_bytes(hf) == 327_680
    assert flops_sambay.ssm_update_min_bytes(hf, 61) == 61 * 2 * 327_680
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops_sambay.shared_kernel_min_seconds(hf, 61 * 5600, peaks)
    assert least == pytest.approx(61 * 5600 * 5120 / 819e9)  # the bytes decide
    # ISSUE 42's step: 61 rows at 5.6k of context read layer 17's pages 8
    # times (14.0 GB), the weights once (7.70 GB), the window layers'
    # 8 x 511 (1.28 GB) and the state with its conv tails twice (0.36 +
    # 0.03 GB): 23.4 GB, 28.5 ms
    step = flops_sambay.decode_step_bytes(hf, 61, 5600)
    assert abs(step["shared_kv"] / 1e9 - 14.0) < 0.05
    assert abs(step["weights"] / 1e9 - 7.70) < 0.01
    assert abs(step["window_kv"] / 1e9 - 1.28) < 0.01
    assert abs(step["state"] / 1e9 - 0.39) < 0.01
    sec = flops_sambay.decode_min_seconds(
        hf, decode_steps=1, row_steps=61, context_token_reads=61 * 5600,
        window_token_reads=61 * 511, hbm_bytes_per_s=819e9,
    )
    assert sec == pytest.approx(sum(step.values()) / 819e9)
    assert 0.0283 < sec < 0.0287


def _ctx(config, counters, op_seconds=None):
    return types.SimpleNamespace(
        config=config, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        n_devices=1, memory_peak_bytes=13_300_000_000,
        trace={"busy_s": 2.0, "window_s": 4.0, "op_seconds": op_seconds or {}},
        window={"counters": counters},
    )


def test_new_readers_on_a_made_up_run(run, config, monkeypatch):
    from benchmark.lib import region_reduce, span_reduce

    counters = {
        "window_s": 10.0, "tokens_emitted": 61.0 * 320, "decode_chunks": 40,
        "chunk_size": 8, "context_token_reads": 61.0 * 320 * 5600,
        "window_token_reads": 61.0 * 320 * 511, "shared_shape": [8, 8],
    }
    ctx = _ctx(
        config, counters,
        {"paged_window_decode.3": 0.1, "paged_attn_decode.2": 0.9,
         "ssm_state_update_m1.4": 0.06, "ssm_state_rows.5": 0.04, "fusion.1": 0.9},
    )
    value = lambda name: run.load_reader(name).value(ctx)
    # 320 steps x 23.37 GB / 819 GB/s = 28.5 ms each, over 5 s of busy time
    assert value("decode_hbm_share.shared") == pytest.approx(
        100 * 320 * 0.02853 / 5.0, rel=2e-3
    )
    # the accepted readers match the kernels' calls by the name they carry
    assert value("paged_attn_time_share") == pytest.approx(50.0)
    assert value("ssm_time_share.shared") == pytest.approx(5.0)
    # no xplane in a made-up run: the span and region readers leave theirs out
    for name in ("shared_kv_roofline_share", "ssm1_update_hbm_share",
                 "cross_attn_time_share", "gmu_time_share",
                 "state_slots_live_share.shared"):
        assert value(name) is None, name
    # 240 executions (30 steps x 8 readers) of 3 ms, each over 340,000
    # positions (the mean of the slice's dispatch spans): 340,000 x 5,120 B
    # / 819 GB/s = 2.13 ms; 270 of the state kernel (30 steps x 9 layers)
    # of 60 us over 60 live rows: 60 x 655,360 B / 819 GB/s = 48 us
    t = {
        "lines": [[
            span_reduce.Span(i, i + 0.1, "areal.engine.decode.dispatch",
                             {"ctx_tokens_sum": n, "global_readers": 8,
                              "window_tokens_sum": 61 * 511})
            for i, n in enumerate([330_000, 350_000])
        ] + [
            span_reduce.Span(5 + i, 5.1 + i, "areal.engine.harvest.fold",
                             {"tokens": 60 * 8})
            for i in range(2)
        ] + [
            span_reduce.Span(8 + i, 8.1 + i, "areal.engine.ensure_blocks",
                             {"state_slots_live": 60 + i, "state_slots_total": 64})
            for i in range(2)
        ]],
        "devices": {"tpu0": [(0.0, 0.003, "paged_attn_decode.2")] * 240
                    + [(0.0, 0.0002, "paged_window_decode.3")] * 240
                    + [(0.0, 0.00006, "ssm_state_update_m1.4")] * 270
                    + [(0.0, 0.5, "paged_attn_fill.7")]},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    got = value("shared_kv_roofline_share")
    assert got == pytest.approx(100 * (340_000 * 5120 / 819e9) / 0.003)
    assert 0 < got < 100
    got = value("ssm1_update_hbm_share")
    assert got == pytest.approx(100 * (60 * 655_360 / 819e9) / 0.00006)
    assert 0 < got < 100
    assert value("state_slots_live_share.shared") == pytest.approx(100 * 60.5 / 64)
    # the regions: 0.3 + 0.5 s of 2 s busy in the layers that read the
    # shared pages (the window layers' 0.2 s lie under areal.attn by name
    # and are taken off), 0.1 s in the gated memory units
    monkeypatch.setattr(
        region_reduce, "regions_of",
        lambda ctx: {"seconds": {
            ("jit_hybrid_decode_chunk", "areal.attn.window", "forward"): 0.2,
            ("jit_hybrid_decode_chunk", "areal.attn", "forward"): 0.25,
            ("jit_hybrid_fill_chunk", "areal.attn", "forward"): 0.05,
            ("jit_hybrid_decode_chunk", "areal.attn.cross", "forward"): 0.5,
            ("jit_hybrid_decode_chunk", "areal.gmu", "forward"): 0.1,
            ("jit_hybrid_decode_chunk", "areal.mlp", "forward"): 0.9,
        }},
    )
    assert value("cross_attn_time_share") == pytest.approx(40.0)
    assert value("gmu_time_share") == pytest.approx(5.0)


def test_new_readers_find_nothing_on_a_program_without_their_regions(
    run, spec, monkeypatch
):
    """The parent commit has no such spans, regions and counters, and the
    other cells' window records no such keys: every new reader returns
    None, on a made-up run and on a trace of a program of another stack."""
    from benchmark.lib import region_reduce, span_reduce

    with open(os.path.join(BENCH, "configs", "qwen2.5-1.5b.json")) as f:
        qwen = json.load(f)
    ctx = _ctx(qwen, {
        "window_s": 10.0, "tokens_emitted": 6400.0, "decode_chunks": 5,
        "chunk_size": 64, "context_token_reads": 6400.0 * 1000, "n_layers": 28,
    }, {"fusion.1": 1.5, "paged_attn_decode.1": 0.5})
    new = [m for m in spec["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in new] == NEW
    for m in new:
        assert run.load_reader(m["name"]).value(ctx) is None, m["name"]
    # a trace of the window cell's programs: spans without global_readers,
    # regions without areal.attn.cross or areal.gmu, no ssm_ operation
    t = {
        "lines": [[span_reduce.Span(0, 0.1, "areal.engine.decode.dispatch",
                                    {"ctx_tokens_sum": 300_000, "window_tokens_sum": 200_000}),
                   span_reduce.Span(1, 1.1, "areal.engine.ensure_blocks", {"pages_live": 5})]],
        "devices": {"tpu0": [(0.0, 0.002, "paged_attn_decode.2")] * 30},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    monkeypatch.setattr(
        region_reduce, "regions_of",
        lambda ctx: {"seconds": {
            ("jit_hybrid_decode_chunk", "areal.attn.window", "forward"): 0.4,
            ("jit_hybrid_decode_chunk", "areal.attn", "forward"): 0.3,
        }},
    )
    for m in new:
        assert run.load_reader(m["name"]).value(ctx) is None, m["name"]
    # two names are read by the file named before the last dot: no new code
    for name in ("ssm_time_share.shared", "state_slots_live_share.shared"):
        assert not os.path.isfile(os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_the_traffic_files_draw_is_the_same_for_two_seeds():
    from benchmark.lib import lengths

    traffic = _json("../../traffic/rollout-full-shared.json")
    a = [lengths.rollout_prompt(traffic, 7, 200064, k) for k in range(40)]
    b = [lengths.rollout_prompt(traffic, 2**31 + 5, 200064, k) for k in range(40)]
    assert [len(p["prompt_ids"]) for p in a] == [len(p["prompt_ids"]) for p in b]
    assert [p["max_new_tokens"] for p in a] == [p["max_new_tokens"] for p in b]
    assert a[0]["prompt_ids"] != b[0]["prompt_ids"]  # --seed gives the ids
    plens = [len(p["prompt_ids"]) for p in a]
    assert 2048 <= min(plens) and max(plens) <= 8192  # each past the window
    assert max(plens) > 8000  # and a context past 8k among them
    assert max(max(p["prompt_ids"]) for p in a) > 150_000  # the whole vocabulary
    news = [n for p in a for n in p["max_new_tokens"]]
    assert 16 <= min(news) and max(news) <= 2048


def test_the_cell_reports_what_the_issue_lists(spec):
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "rollout-full-shared"
    # membership, not position: a later cell is appended after this one
    config = next(c for c in spec["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == [] and len(config["why"]) <= 200
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
    traffic = _json("../../traffic/rollout-full-shared.json")
    assert traffic["driver"] == "rollout_closed_loop_shared"
    assert traffic["prompts_in_flight"] * traffic["samples_per_prompt"] == 96
    assert traffic["prompt_len"] == {"min": 2048, "max": 8192}
    assert traffic["output_len"] == {"median": 384, "sigma": 1.0, "min": 16, "max": 2048}
    assert traffic["temperature"] == 1.0 and traffic["length_seed"] == 20261042
    eng = traffic["engine"]
    assert (eng["max_concurrent_batch"], eng["kv_pool_tokens"],
            eng["kv_window_pool_tokens"], eng["prefill_chunk_tokens"]) == (
        64, 294912, 114688, 1024)
    assert "keep_routed_experts" not in eng  # no router, nothing to follow
    assert eng["kv_cache_len"] == 8192 + 2048
    budget = eng["prefill_chunk_tokens"]
    # a decode chunk's own tokens lie inside every window
    assert eng["chunk_size"] < 512 and eng["page_size"] % 128 == 0
    assert traffic["warm"]["sibling_prompt_len"] <= budget
    # every fill shape warmed is one the engine's batch rule lets through
    for f, c in traffic["warm"]["fill_shapes"]:
        f_pad = 1 << (f - 1).bit_length()
        assert f_pad * c <= 4 * budget, (f, c)
    for key in ("page_size", "prefill_chunk_tokens and chunk_size", "pools"):
        assert "TO BE WRITTEN" not in traffic["engine_why"][key], key
