"""python -m pytest benchmark/tests -q   (CPU, toy sizes; not part of tier-1)"""

import importlib.util
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run():
    return _load(os.path.join(BENCH, "run.py"), "benchmark_run_under_test")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def _execute(run, spec, traffic_file, cell_index, seconds):
    import jax

    cell = dict(spec["workloads"][cell_index], chips=1)
    return run.execute(
        spec, cell, _json("tiny.json"), _json(traffic_file), seed=2**31 + 7,
        seconds=seconds, traced=False, dev=jax.devices()[0], peaks=FAKE_PEAKS,
    )


# -- both drivers, end to end through run.py's own functions ----------------


@pytest.fixture(scope="module")
def train_result(run, spec):
    return _execute(run, spec, "tiny-train.json", 1, 2.0)


@pytest.fixture(scope="module")
def rollout_result(run, spec):
    """The result, and the lines printed before it by their ``event``."""
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = _execute(run, spec, "tiny-rollout.json", 0, 5.0)
    notes = {}
    for line in out.getvalue().splitlines():
        if line.startswith("{"):
            note = json.loads(line)
            notes[note.get("event")] = note
    return result, notes


def test_train_driver_end_to_end(train_result):
    r = train_result
    json.dumps(r)
    assert set(r) == {"correct", "attempted", "failed", "metrics", "device"}
    # float32 toy on the CPU: the trainer's first loss IS the plain
    # reference's (tolerance 1e-4 in the toy traffic file), nothing
    # compiled inside the window, every loss finite
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 2
    assert set(r["metrics"]) == {"train_tok_per_s", "setup_s"}
    assert r["metrics"]["train_tok_per_s"]["unit"] == "tokens/s"
    assert r["device"]["platform"] == "cpu"  # every result names its device


def test_rollout_driver_end_to_end(rollout_result):
    r, notes = rollout_result
    json.dumps(r)
    assert set(r["metrics"]) == {"rollout_tok_per_s", "seq_p90_s", "setup_s"}
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert r["metrics"]["seq_p90_s"]["value"] > 0
    # `correct` may be false here: the toy warm-up covers two prefill
    # shapes, and any program first met inside the window fails a run


def test_rollout_window_is_the_seconds_asked_and_counts_tokens_generated(
    rollout_result,
):
    r, notes = rollout_result
    # the warm-up's paused rounds (5 sequences, 7 tokens in the toy file)
    # settled what engine.step() counts: today all but first tokens
    assert notes["warm"]["step_counts_first_token"] is False
    w = notes["window_closed"]
    assert w["window_s"] == 5.0
    # the rate: tokens between the first and the last emitting step inside
    # the window over the time between them
    assert 0 < w["rate_taken_over_s"] <= 5.0 and w["emitting_steps"] >= 2
    assert r["metrics"]["rollout_tok_per_s"]["value"] == pytest.approx(
        w["tokens_emitted"] / w["rate_taken_over_s"]
    )
    # it is not the tokens of the sequences that completed in the window,
    # though in a loop this short the two are of one size
    assert 0.3 < w["tokens_emitted"] / w["tokens_of_sequences_completed"] < 3


# -- the traffic generator ---------------------------------------------------


def test_lengths_same_seed_same_requests_other_seed_others():
    from benchmark.lib import lengths

    t = _json("tiny-rollout.json")
    a = [lengths.rollout_prompt(t, 11, 512, k) for k in range(5)]
    b = [lengths.rollout_prompt(t, 11, 512, k) for k in range(5)]
    c = [lengths.rollout_prompt(t, 12, 512, k) for k in range(5)]
    assert a == b
    assert [p["prompt_ids"] for p in a] != [p["prompt_ids"] for p in c]
    # the traffic file's length_seed: every seed holds the same work in the
    # same order, and another draw is another traffic file
    assert [p["max_new_tokens"] for p in a] == [p["max_new_tokens"] for p in c]
    other = dict(t, length_seed=t["length_seed"] + 1)
    d = [lengths.rollout_prompt(other, 11, 512, k)["max_new_tokens"] for k in range(5)]
    assert d != [p["max_new_tokens"] for p in a]
    for p in a:
        assert t["prompt_len"]["min"] <= len(p["prompt_ids"]) <= t["prompt_len"]["max"]
        assert all(4 <= n <= 24 for n in p["max_new_tokens"])


def test_train_batches_hold_exactly_the_token_budget():
    from benchmark.lib import lengths

    t = _json("tiny-train.json")
    for k in range(4):
        b = lengths.train_batch(t, 3, 512, k)
        n = sum(b["seqlens"])
        assert n == t["tokens_per_step"] == len(b["packed_input_ids"])
        assert len(b["packed_logprobs"]) == n - len(b["seqlens"])
        assert len(b["rewards"]) == len(b["seqlens"])
        assert all(p < s for p, s in zip(b["prompt_lens"], b["seqlens"]))
    again = lengths.train_batch(t, 3, 512, 0)
    assert np.array_equal(again["packed_input_ids"],
                          lengths.train_batch(t, 3, 512, 0)["packed_input_ids"])
    assert not np.array_equal(again["packed_input_ids"],
                              lengths.train_batch(t, 4, 512, 0)["packed_input_ids"])


# -- the yardstick's arithmetic ---------------------------------------------


@pytest.mark.parametrize("name", ["qwen2.5-1.5b"])
def test_flops_agree_with_the_programs_counter(name):
    from areal_tpu.system import flops_counter
    from benchmark.lib import flops
    from benchmark.lib.program import model_config

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    cfg = model_config(config, "serve")
    hf, L = config["hf_config"], cfg.n_layers
    seqlens = [700, 3072, 33]
    assert flops.forward_flops(hf, L, seqlens) == flops_counter.forward_flops(cfg, seqlens)
    assert flops.train_flops(hf, L, seqlens) == flops_counter.train_flops(cfg, seqlens)
    assert flops.matmul_params_per_layer(hf) == flops_counter.matmul_params_per_layer(cfg)


@pytest.mark.parametrize("name,layers,billions", [
    ("qwen2.5-1.5b", 28, 1.54),
])
def test_param_count_is_the_published_size(name, layers, billions):
    from benchmark.lib import flops

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        hf = json.load(f)["hf_config"]
    assert abs(flops.param_count(hf, layers) / 1e9 - billions) < 0.01


def test_param_count_matches_the_programs_tree():
    import jax

    from areal_tpu.models.transformer import init_params
    from benchmark.lib import flops
    from benchmark.lib.program import model_config

    config = _json("tiny.json")
    cfg = model_config(config, "serve")
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert flops.param_count(config["hf_config"], cfg.n_layers) == n


def test_reference_forward_agrees_with_the_programs_forward():
    """The plain reference and the program's model code are two
    implementations of one architecture: float32, CPU, toy size."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import transformer
    from benchmark.lib import reference
    from benchmark.lib.program import model_config

    config = _json("tiny.json")
    cfg = model_config(config, "serve")
    params = transformer.init_params(cfg, jax.random.PRNGKey(1))
    seq = np.random.default_rng(0).integers(3, 512, 77)
    got = reference.sequence_logps(
        reference.make_token_logps(config["hf_config"]), params, seq, pad_to=32
    )
    tokens = jnp.asarray(seq[None], jnp.int32)
    pos = jnp.arange(len(seq), dtype=jnp.int32)[None]
    logits = transformer.forward(params, cfg, tokens, pos, jnp.ones_like(tokens))
    lp = jax.nn.log_softmax(logits[0, :-1], -1)
    want = np.asarray(jnp.take_along_axis(lp, tokens[0, 1:, None], -1)[:, 0])
    assert np.abs(got - want).max() < 1e-4


def test_ppo_loss_reference_on_a_case_worked_by_hand():
    from benchmark.lib.reference import ppo_actor_loss

    # ratio e^0.5 = 1.649 clipped to 1.2 with a negative advantage keeps the
    # unclipped (larger) loss; behaviour weight e^0 = 1; one masked-out entry
    loss = ppo_actor_loss(
        new_logp=[-1.0, -1.0], old_logp=[-1.5, -9.0], prox_logp=[-1.5, -1.0],
        advantages=[-0.5, 3.0], mask=[True, False], eps_clip=0.2, behav_cap=5.0,
    )
    assert loss == pytest.approx(0.5 * np.exp(0.5))


# -- the trace reduction -----------------------------------------------------


def test_trace_union_and_self_time_on_a_made_up_line():
    from benchmark.lib import trace_reduce as tr

    ev = [(0.0, 10.0, "while"), (1.0, 4.0, "fusion.1"), (4.0, 6.0, "paged_kernel"),
          (12.0, 13.0, "fusion.1")]
    busy, merged = tr.union_seconds(ev)
    assert busy == 11.0 and merged == [(0.0, 10.0), (12.0, 13.0)]
    by = tr.self_seconds_by_name(ev)
    assert by == {"while": 5.0, "fusion.1": 4.0, "paged_kernel": 2.0}
    assert tr.seconds_matching(by, r"paged") == 2.0
    assert tr.strip_hash("jit_step(123456)") == "jit_step"


def test_trace_reduction_of_the_recorded_v5e_trace():
    """``data/small_v5e.xplane.pb``: three calls of a jitted scan of three
    256x256 matmuls on one TPU v5 lite, each under a ``bench.train_step``
    annotation and followed by a 2 ms ``bench.make_sample`` sleep (recorded
    in PR 23).  The numbers below were read off the raw events by hand: the
    three programs took 2293 + 2306 + 2057 ns, the matmul fusion ran 9
    times for 286-287 ns, and the device sat idle between the programs."""
    from benchmark.lib import trace_reduce as tr

    r = tr.reduce_trace(os.path.join(DATA, "small_v5e.xplane.pb"), "host")
    assert r["chips"] == 1
    assert r["module_seconds"] == {"jit_small_step": pytest.approx(6.656e-6)}
    assert r["busy_s"] == pytest.approx(6.614e-6, rel=1e-3)
    assert r["window_s"] == pytest.approx(0.010457329, rel=1e-6)
    assert 100 * r["busy_s"] / r["window_s"] == pytest.approx(0.06325, rel=1e-3)
    ops = r["op_seconds"]
    assert ops["convolution_tanh_fusion.2"] == pytest.approx(2.580e-6, rel=1e-3)
    # the while's own time is what its body does not cover
    assert ops["while"] == pytest.approx(7.4e-8, rel=1e-2)
    assert r["device_ops"][0][0] == "convolution_tanh_fusion.2"
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    owner, longest = r["idle_gaps"][0]
    assert owner == "bench.make_sample"
    assert longest == pytest.approx(0.003855543, rel=1e-6)
    assert tr.seconds_matching(ops, r"tanh") == pytest.approx(2.580e-6, rel=1e-3)


def test_layer_metric_readers_on_a_made_up_run(spec):
    """Every reader the benchmark names loads, and reads what the drivers
    and the reduction hand it."""
    import types

    run = sys.modules.get("benchmark_run_under_test") or _load(
        os.path.join(BENCH, "run.py"), "benchmark_run_under_test"
    )
    with open(os.path.join(BENCH, "configs", "qwen2.5-1.5b.json")) as f:
        config = json.load(f)
    counts = [0] * 99
    counts[20], counts[40] = 90, 10  # 90 waits of ~3 ms, 10 of ~100 ms
    ctx = types.SimpleNamespace(
        config=config, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        n_devices=1, memory_peak_bytes=8_000_000_000,
        trace={"busy_s": 2.0, "window_s": 4.0,
               "op_seconds": {"paged_flash_attention.7": 0.5, "fusion.1": 1.5,
                              "flash_fwd": 0.2}},
        window={"counters": {
            "window_s": 10.0, "schedule_wait_mean_s": 0.002, "host_s": 1.0,
            "device_s": 2.0, "fetch_s": 1.0, "admission_counts": counts,
            "admission_lo": 1e-4, "admission_ratio": 2 ** 0.25,
            "tokens_emitted": 6400.0, "decode_chunks": 5, "chunk_size": 64,
            "context_token_reads": 6400 * 1000, "n_layers": 28,
            "pad_frac_mean": 0.125, "train_flops": 197e12, "steps": 3,
        }},
    )
    got = {
        m["name"]: run.load_reader(m["name"]).value(ctx)
        for m in spec["per_layer"]
    }
    assert got["schedule_wait_ms"] == pytest.approx(2.0)
    assert got["engine_host_share"] == pytest.approx(25.0)
    # the 90th of 100 waits is in bucket 20: (1e-4 * r^19, 1e-4 * r^20]
    assert got["admission_wait_p90_ms"] == pytest.approx(0.1 * 2 ** (19.5 / 4))
    assert got["decode_rows_mean"] == pytest.approx(20.0)
    assert got["paged_attn_time_share"] == pytest.approx(25.0)
    assert got["flash_attn_time_share"] == pytest.approx(10.0)
    assert got["train_pad_share"] == pytest.approx(12.5)
    assert got["train_mfu"] == pytest.approx(10.0)
    # one quantity split by the metric it moves: one reader, hbm_peak_gb.py
    assert got["hbm_peak_gb.train"] == got["hbm_peak_gb.rollout"] == 8.0
    with pytest.raises(FileNotFoundError):
        run.load_reader("no_such_metric.train")
    # 320 decode steps read 3.09 GB of weights each, the tokens 6.4 M cached
    # positions of 28 KiB: 1.42 s at 819 GB/s, over 5 busy seconds
    from benchmark.lib import flops

    hf = config["hf_config"]
    least = (320 * flops.weight_bytes(hf, 28) + 6.4e6 * 28672) / 819e9
    assert flops.weight_bytes(hf, 28) == pytest.approx(3.09e9, rel=0.01)
    assert got["decode_hbm_share"] == pytest.approx(100 * least / 5.0)


# -- run.py: refusals, and no names -----------------------------------------


def test_run_py_refuses_a_cpu_backend(tmp_path, spec):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=180, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_run_py_refuses_a_chip_that_is_not_in_the_peak_table(run, monkeypatch):
    import jax

    from benchmark.lib.peaks import peaks_for

    class Fake:
        platform, device_kind = "tpu", "TPU v99 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    dev, peaks, refusal = run.check_device({"name": "x", "chips": 1})
    assert dev is None and "not in benchmark/lib/peaks.py" in refusal
    with pytest.raises(KeyError):
        peaks_for("TPU v99 imaginary")
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12

    class V5e:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [V5e(), V5e()])
    dev, peaks, refusal = run.check_device({"name": "x", "chips": 1})
    assert dev is None and "needs 1 chip" in refusal


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_is_whole_and_everything_it_names_exists(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for c in configs.values():
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"] and len(c["source"]) <= 200
        assert body["reduced"] == c["reduced"]
        assert "assumed" in body and "deployment" in body
    for w in cells.values():
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in configs
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(BENCH, "drivers", driver + ".py"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert any(
            os.path.isfile(os.path.join(BENCH, "layer_metrics", n + ".py"))
            for n in (m["name"], m["name"].rpartition(".")[0])
        )
        moved = e2e[m["moves"]]
        # the metric it moves is reported in every cell where this one is
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    for w in cells:
        assert any(w in m.get("workloads", cells) for m in spec["per_layer"])
        assert any(w in m.get("workloads", cells) and m["name"] != "setup_s"
                   for m in spec["end_to_end"])


def test_run_py_holds_no_name_of_a_cell_a_configuration_or_a_metric(spec):
    with open(os.path.join(BENCH, "run.py")) as f:
        src = f.read()
    names = [w["name"] for w in spec["workloads"]]
    names += [c["name"] for c in spec["configs"]]
    names += [w["traffic"] for w in spec["workloads"]]
    names += [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    for name in set(names) - {"setup_s"}:  # the contract's one fixed metric
        assert name not in src, name
