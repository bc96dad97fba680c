"""python -m pytest benchmark/tests -q   (CPU, toy sizes)

What came with the gigachat3.1-702b-a36b configuration: its reference, its
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

CELL = "rollout-full-latent.gigachat3.1-702b-a36b"
SHAPE = [5, 1, 256, 16, 16032]  # layers, dense, router outputs, held, vocabulary


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
    with open(os.path.join(BENCH, "configs", "gigachat3.1-702b-a36b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def latent_result(run, spec, tmp_path_factory):
    """The toy latent cell through run.py's own functions: the result,
    and the lines printed before it by their ``event``."""
    import jax

    cell = dict(next(w for w in spec["workloads"] if w["name"] == CELL), chips=1)
    out = io.StringIO()
    # an output directory of its own: run.py empties ``out/work`` when a
    # run starts, and the other cells' tests run beside this one
    with pytest.MonkeyPatch.context() as patch, contextlib.redirect_stdout(out):
        patch.setattr(run, "OUT_DIR", str(tmp_path_factory.mktemp("out")))
        result = run.execute(
            spec, cell, _json("tiny-latent.json"),
            _json("tiny-rollout-latent.json"), seed=2**31 + 11, seconds=5.0,
            traced=False, dev=jax.devices()[0],
            peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        )
    notes = {}
    for line in out.getvalue().splitlines():
        if line.startswith("{"):
            note = json.loads(line)
            notes[note.get("event")] = note
    return result, notes


def test_latent_driver_end_to_end(latent_result):
    r, _ = latent_result
    json.dumps(r)
    # ISSUE 33 keeps the tail out of this cell
    assert set(r["metrics"]) == {"rollout_tok_per_s", "setup_s"}
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["metrics"]["rollout_tok_per_s"]["value"] > 0


def test_latent_check_is_against_the_plain_reference(latent_result):
    _, notes = latent_result
    c = notes["check"]
    assert c["correct"]
    # float32 toy on the CPU: prefill in chunks (the prefix absorbed),
    # then decode through latent pages, IS the reference's unabsorbed
    # full forward, and the routing the engine kept is the reference's own
    assert c["paged"] and c["v_pool_bytes"] == 0
    # token ids from the vocabulary rows this chip holds (256 of 512)
    assert c["token_id_max"] < c["vocab_rows"] == 256
    assert c["pool_shape"][2:] == [1, 32, 128]  # one "head", rows of a lane tile
    assert len(c["reference"]) == 3
    for row in c["reference"]:
        assert row["within"] and row["max_abs_diff"] < 1e-4, row
        assert row["router_margin_min"] >= 0
        assert row["router_flips_share"] == 0.0
    assert c["sequences_nonfinite"] == 0
    # the prefix cache's counts are on the line (whether a sibling came
    # after its prompt's fill in a toy window of 5 s is a matter of
    # timing: tests/engine/test_latent_pages.py holds that path)
    assert c["prefix_cache"]["cached_tokens_total"] >= 0


def test_the_control_is_refused_by_the_comparison_that_passes_the_server(
    latent_result,
):
    from benchmark.drivers import rollout_closed_loop_latent as drv

    _, notes = latent_result
    c = notes["check"]
    assert c["tolerance"] == {
        "max_abs": drv.LOGP_MAX_ABS, "mean_abs": drv.LOGP_MEAN_ABS
    }
    assert c["control"]["what"] == "weights in float8_e4m3fn"
    assert not c["control"]["within"]
    assert (
        c["control"]["max_abs_diff"] > drv.LOGP_MAX_ABS
        or c["control"]["mean_abs_diff"] > drv.LOGP_MEAN_ABS
    )
    assert c["control"]["max_abs_diff"] > max(
        r["max_abs_diff"] for r in c["reference"]
    )


@pytest.mark.parametrize(
    "max_off,mean_off,within",
    [(0.5, 0.5, True), (1.5, 0.5, False), (0.9, 1.5, False)],
)
def test_compare_holds_both_limits(max_off, mean_off, within):
    from benchmark.drivers import rollout_closed_loop_latent as drv

    want = np.zeros(100, np.float32)
    got = np.full(100, mean_off * drv.LOGP_MEAN_ABS, np.float32)
    got[0] = max(got[0], max_off * drv.LOGP_MAX_ABS)
    row = drv.compare(got, want)
    assert row["within"] is within and row["nonfinite"] == 0
    got[3] = np.nan
    row = drv.compare(got, want)
    assert not row["within"] and row["first_nonfinite"] == 3


def test_float8_rounding_of_the_reference_is_e4m3():
    import jax.numpy as jnp

    from benchmark.lib import reference_deepseek_v3 as ref

    x = np.random.RandomState(0).standard_normal((64, 96)).astype(np.float32)
    x[0, :4] = [0.0, 1e-6, -3.0, x.max() * 2]
    got = np.asarray(ref._fp8_weights({"w": jnp.asarray(x), "b": jnp.ones(3)})["w"])
    s = np.abs(x).max() / 448.0
    want = np.asarray(
        (jnp.asarray(x) / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    )
    np.testing.assert_array_equal(got, want)
    # an expert's matrices rounded one at a time under the stack's scale
    # are the stack rounded whole
    part = np.asarray(ref._fp8_round(jnp.asarray(x[:7]), ref._fp8_scale(jnp.asarray(x))))
    np.testing.assert_array_equal(part, want[:7])


def test_window_record_counts_what_the_latent_readers_take(latent_result):
    _, notes = latent_result
    w = notes["window_closed"]
    assert len(w["requests_queued"]) == 2
    # no recurrent state: nothing to copy, nobody prefills a whole prompt again
    assert w["state_copies"] == 0 and w["state_reprefills"] == 0


def test_configuration_file_keeps_the_catalog_row_and_states_the_cut(config, spec):
    entry = next(c for c in spec["configs"] if c["name"] == config["name"])
    assert entry["file"] == "benchmark/configs/gigachat3.1-702b-a36b.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ai-sage/GigaChat3.1-702B-A36B/blob/main/config.json"
    )
    reduced = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
               "vocab_size", "num_nextn_predict_layers"]
    assert entry["reduced"] == config["reduced"] == reduced
    pub = config["hf_config"]
    # every key of the catalog row, unchanged under hf_config: its widths
    assert (pub["hidden_size"], pub["q_lora_rank"], pub["kv_lora_rank"],
            pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"],
            pub["intermediate_size"], pub["moe_intermediate_size"]) == (
        7168, 1536, 512, 128, 64, 192, 18432, 2048)
    assert (pub["n_routed_experts"], pub["n_group"], pub["topk_group"],
            pub["num_experts_per_tok"], pub["routed_scaling_factor"]) == (256, 8, 4, 8, 2.5)
    assert (pub["num_hidden_layers"], pub["first_k_dense_replace"],
            pub["vocab_size"], pub["num_nextn_predict_layers"]) == (64, 3, 128256, 1)
    assert pub["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "rope_type": "yarn",
    }
    assert config["published"] == {k: pub[k] for k in reduced}
    # no width differs from the source: every top-level key is the
    # published one but the keys the cut names
    for key, value in pub.items():
        if key in reduced or key in ("architectures", "torch_dtype"):
            continue
        assert config[key] == value, key
    assert [config[k] for k in reduced] == [5, 1, 16, 16032, 0]
    over = config["roles"]["serve"]["model_overrides"]
    assert over == {
        "layer_types": ["latent"] * 5, "n_dense_layers": 1, "moe_first_expert": 0,
        "moe_held_experts": 16, "vocab_size": 16032, "n_mtp_modules": 0,
    }
    for key in ("deployment", "assumed", "resident", "reduced_how"):
        assert config[key]
    assert "16 chips" in config["deployment"] and "12 pipeline stages" in config["deployment"]


def test_program_reads_the_configuration_as_the_cell_runs_it(config):
    from areal_tpu.models import paged
    from benchmark.lib.program import model_config

    cfg = model_config(config, "serve")
    assert cfg.n_layers == 5 and cfg.n_dense_layers == 1 and cfg.is_latent
    assert cfg.n_experts == 256 and cfg.n_held_experts == 16
    assert (cfg.moe_router, cfg.moe_n_groups, cfg.moe_topk_groups,
            cfg.n_experts_per_tok, cfg.moe_routed_scale) == ("sigmoid_group", 8, 4, 8, 2.5)
    assert (cfg.hidden_dim, cfg.head_dim, cfg.v_head_dim, cfg.kv_latent_dim,
            cfg.q_lora_rank) == (7168, 192, 192, 576, 1536)
    assert cfg.vocab_size == 16032 and cfg.dtype == "bfloat16"
    assert cfg.n_mtp_modules == 0 and not cfg.tied_embedding
    # the pool: 327,680 tokens x 5 layers x 640 columns x 2 B, no V bytes
    assert paged.kv_pool_layout_bytes(cfg, 327680 // 512, 512) == (2_097_152_000, 0)


def test_held_parameter_count_is_the_configurations_arithmetic(config):
    """4.29 B parameters here (the file's ``resident``: 8.58 GB), and the
    published 702 B whole."""
    import jax

    from areal_tpu.models import hybrid
    from benchmark.lib import flops_mla
    from benchmark.lib.program import model_config

    hf = config["hf_config"]
    assert abs(flops_mla.mla_params(hf) / 1e6 - 132.6) < 0.1
    assert abs((flops_mla.mla_params(hf) + flops_mla.dense_mlp_params(hf)) / 1e6 - 528.9) < 0.1
    assert abs(flops_mla.expert_block_params(hf, 256, 16) / 1e6 - (883.1 - 132.6)) < 0.1
    held = flops_mla.held_param_count(hf, *SHAPE)
    assert abs(held * 2 / 1e9 - 8.58) < 0.01
    whole = flops_mla.held_param_count(hf, 64, 3, 256, 256, 128256)
    assert abs(whole / 1e9 - 702) < 16  # less the MTP module's 11.5 B
    # and it is the program's tree, less norms and the router's bias
    cfg = model_config(config, "serve")
    shapes = jax.eval_shape(lambda: hybrid.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 0 <= n - held < 1e-4 * held


def test_byte_and_operation_arithmetic_on_a_case_worked_by_hand(config):
    from benchmark.lib import flops_mla

    hf = config["hf_config"]
    # one cached position of one layer: (512 + 64) x 2 B
    assert flops_mla.latent_bytes_per_token(hf) == 1152
    assert flops_mla.latent_bytes_per_token(hf, layers=5) == 5760
    # 64 heads x (576 + 512) x 2 FLOP over 1,152 B: 121 FLOP/B, under
    # a v5e's ridge of 197e12 / 819e9 = 240
    assert flops_mla.mla_flops_per_token(hf) == 64 * 1088 * 2 == 139264
    assert round(flops_mla.mla_flops_per_token(hf) / 1152) == 121
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = flops_mla.mla_kernel_min_seconds(hf, 135_000, peaks)
    assert least == pytest.approx(135_000 * 1152 / 819e9)  # the bytes decide
    # per-head K and V of the same model: 42.7 times the latent entry
    assert round(64 * (192 + 192) * 2 / 1152, 1) == 42.7
    # a decode step reads the 8.58 GB held less the embedding's table
    # (0.23 GB: only its tokens' rows are read), 10.2 ms, and 0.75 GB of
    # cache at 52 rows of 2.5k context
    assert flops_mla.weight_bytes(hf, *SHAPE) == (
        flops_mla.held_param_count(hf, *SHAPE) - 16032 * 7168
    ) * 2
    step = flops_mla.decode_min_seconds(
        hf, SHAPE, decode_steps=1, context_token_reads=52 * 2500,
        hbm_bytes_per_s=819e9,
    )
    assert 0.0109 < step < 0.0113


def _ctx(config, counters, op_seconds=None):
    return types.SimpleNamespace(
        config=config, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        n_devices=1, memory_peak_bytes=11_500_000_000,
        trace={"busy_s": 2.0, "window_s": 4.0, "op_seconds": op_seconds or {}},
        window={"counters": counters},
    )


def test_new_readers_on_a_made_up_run(run, config, monkeypatch):
    from benchmark.lib import span_reduce

    counters = {
        "window_s": 10.0, "tokens_emitted": 52.0 * 320, "decode_chunks": 10,
        "chunk_size": 32, "context_token_reads": 52.0 * 320 * 2500,
        "latent_shape": SHAPE, "moe_pairs_held": 700.0, "moe_pairs_routed": 10000.0,
    }
    ctx = _ctx(config, counters, {"paged_mla_decode.3": 0.4, "fusion.1": 1.6})
    value = lambda name: run.load_reader(name).value(ctx)
    # 320 steps x (8.35 GB of weights + 52 x 2500 x 5,760 B) / 819 GB/s =
    # 11.11 ms each, over 5 s of busy time
    assert value("decode_hbm_share.latent") == pytest.approx(
        100 * 320 * 0.011113 / 5.0, rel=2e-3
    )
    # 7% of the pairs on one of 16 chips: 1.12 of an even share
    assert value("moe_group_local_share") == pytest.approx(112.0)
    # the accepted reader matches the new kernels by the name they carry
    assert value("paged_attn_time_share") == pytest.approx(20.0)
    # no xplane in a made-up run: the span reader leaves its metric out
    assert value("paged_mla_roofline_share") is None
    # 90 executions of 1 ms, each over 130,000 positions (the mean of the
    # slice's dispatch spans): 130,000 x 1,152 B / 819 GB/s = 0.183 ms
    t = {
        "lines": [[
            span_reduce.Span(i, i + 0.1, "areal.engine.decode.dispatch",
                             {"latent_ctx_tokens_sum": n, "latent_pages_attended": 300})
            for i, n in enumerate([120_000, 140_000])
        ]],
        "devices": {"tpu0": [(0.0, 0.001, "paged_mla_decode.3")] * 90
                    + [(0.0, 0.5, "paged_mla_fill.7")]},
    }
    monkeypatch.setattr(span_reduce, "spans_of", lambda ctx: t)
    got = value("paged_mla_roofline_share")
    assert got == pytest.approx(100 * (130_000 * 1152 / 819e9) / 0.001)
    assert 0 < got < 100


def test_new_readers_find_nothing_on_a_program_without_latent_pages(run, spec):
    """The parent commit has no such spans and counters, and the other
    cells' window records no such keys: every new reader returns None."""
    with open(os.path.join(BENCH, "configs", "qwen2.5-1.5b.json")) as f:
        qwen = json.load(f)
    ctx = _ctx(qwen, {
        "window_s": 10.0, "tokens_emitted": 6400.0, "decode_chunks": 5,
        "chunk_size": 64, "context_token_reads": 6400.0 * 1000, "n_layers": 28,
    }, {"fusion.1": 1.5})
    new = [m for m in spec["per_layer"] if m["workloads"] == [CELL]]
    assert sorted(m["name"] for m in new) == [
        "decode_hbm_share.latent", "moe_group_local_share", "paged_mla_roofline_share",
    ]
    for m in new:
        assert run.load_reader(m["name"]).value(ctx) is None, m["name"]


def test_the_traffic_files_draw_is_the_same_for_two_seeds():
    from benchmark.lib import lengths

    traffic = _json("../../traffic/rollout-full-latent.json")
    a = [lengths.rollout_prompt(traffic, 7, 16032, k) for k in range(40)]
    b = [lengths.rollout_prompt(traffic, 2**31 + 5, 16032, k) for k in range(40)]
    assert [len(p["prompt_ids"]) for p in a] == [len(p["prompt_ids"]) for p in b]
    assert [p["max_new_tokens"] for p in a] == [p["max_new_tokens"] for p in b]
    assert a[0]["prompt_ids"] != b[0]["prompt_ids"]  # --seed gives the ids
    plens = [len(p["prompt_ids"]) for p in a]
    assert 1024 <= min(plens) and max(plens) <= 3072
    assert max(max(p["prompt_ids"]) for p in a) < 16032  # ids from the slice
    news = [n for p in a for n in p["max_new_tokens"]]
    assert 16 <= min(news) and max(news) <= 2048


def test_the_cell_reports_what_the_issue_lists(spec):
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "rollout-full-latent"
    e2e = {m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or CELL in m["workloads"]}
    assert e2e == {"rollout_tok_per_s", "setup_s"}
    per_layer = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert per_layer == {
        "schedule_wait_ms", "engine_host_share", "decode_rows_mean",
        "paged_attn_time_share", "hbm_peak_gb.rollout",
        "engine_bookkeeping_share", "server_poll_overhead_ms",
        "kv_pages_live_share", "decode_hbm_share.latent",
        "paged_mla_roofline_share", "moe_group_local_share",
    }
    # (ISSUE 33 lists ``moe_expert_load_max_over_mean`` for this cell too;
    # the accepted ``benchmark/tests/test_hybrid.py`` picks the hybrid
    # cell's own metrics by ``workloads == [its cell]`` and counts five, and
    # no file the benchmark has may be edited here: left out, CHANGES.md)
    load = next(m for m in spec["per_layer"] if m["name"] == "moe_expert_load_max_over_mean")
    assert CELL not in load["workloads"]
    traffic = _json("../../traffic/rollout-full-latent.json")
    assert traffic["driver"] == "rollout_closed_loop_latent"
    assert traffic["prompts_in_flight"] * traffic["samples_per_prompt"] == 96
    assert traffic["prompt_len"] == {"min": 1024, "max": 3072}
    assert traffic["output_len"] == {"median": 384, "sigma": 1.0, "min": 16, "max": 2048}
    assert traffic["temperature"] == 1.0 and traffic["length_seed"] == 20261001
    eng = traffic["engine"]
    assert (eng["max_concurrent_batch"], eng["kv_cache_len"], eng["kv_pool_tokens"],
            eng["keep_routed_experts"]) == (64, 5120, 327680, 512)
    budget = eng["prefill_chunk_tokens"]
    # eight warm-up prompts end their prefill in one chunk
    assert 8 * traffic["warm"]["sibling_prompt_len"] <= budget
    # the mixed rounds' prompts do NOT, in whatever order they arrive
    for lens, _ in traffic["warm"]["mixed_rounds"]:
        assert sum(lens) > budget and all(sum(lens) - n < budget for n in lens), lens
    # every fill shape warmed is one the engine's batch rule lets through
    for f, c in traffic["warm"]["fill_shapes"]:
        f_pad = 1 << (f - 1).bit_length()
        assert f_pad * c <= 4 * budget, (f, c)
