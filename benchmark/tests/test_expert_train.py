"""The ``laguna`` train cell's pieces at a toy size on the CPU: the driver
end to end (loss, gradient norms and direction against the plain
reference), the trainer's log-probabilities, each planted fault against the
limits, the share test, the adapter, the FLOPs file and the readers."""

import dataclasses
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")

from areal_tpu.models import hybrid  # noqa: E402
from benchmark.lib import flops_laguna  # noqa: E402
from benchmark.lib import reference_laguna as ref  # noqa: E402
from benchmark.lib.program import model_config  # noqa: E402

CELL = "train-long-expert.laguna-xs.2"
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def _json(name, root=DATA):
    with open(os.path.join(root, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return _json("BENCHMARK.json", ROOT)


@pytest.fixture(scope="module")
def run():
    s = importlib.util.spec_from_file_location(
        "benchmark_run_expert", os.path.join(BENCH, "run.py")
    )
    mod = importlib.util.module_from_spec(s)
    sys.modules[s.name] = mod
    s.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def result(run, spec):
    """The driver through run.py's own ``execute``, and the lines printed
    before the result by their ``event``."""
    import contextlib
    import io

    cell = dict(next(w for w in spec["workloads"] if w["name"] == CELL), chips=1)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        r = run.execute(
            spec, cell, _json("tiny-laguna.json"), _json("tiny-train-expert.json"),
            seed=2**31 + 7, seconds=2.0, traced=False, dev=jax.devices()[0],
            peaks=FAKE_PEAKS,
        )
    notes = {}
    for line in out.getvalue().splitlines():
        if line.startswith("{"):
            note = json.loads(line)
            notes[note.get("event")] = note
    return r, notes


def test_driver_end_to_end_loss_and_gradient_against_the_reference(result):
    r, notes = result
    json.dumps(r)
    check = notes["check"]
    # float32 toy: the trainer's loss, each group's gradient norm (from the
    # first minibatch's own record) and direction ARE the reference's, the
    # float8 control is outside, nothing compiled inside the window
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"train_tok_per_s", "setup_s"}
    assert check["abs_diff"] < 1e-5
    assert check["grad_norms_within"] and check["direction_within"]
    assert set(check["direction"]) == set(ref.GROUPS)
    for group, v in check["direction"].items():
        assert v["cosine"] > 0.99999 and v["rel_l2"] < 1e-4, (group, v)
        assert v["norm_reference"] > 0
    assert check["control_float8"]["within"] is False
    # the direction is the step program's own (its first moment), and the
    # step after it moved the parameters as the reference's AdamW step does
    assert check["param_change_within"] and check["param_change_lr"] == 1e-6
    assert set(check["param_change_rel"]) == set(ref.GROUPS)
    assert check["param_change_rel_worst_leaf"] < 0.05
    # the routing is the reference's own top 4 but for near ties, and a
    # router without the bias would stand outside the margin
    assert check["router_flips_share"] < 0.01
    assert check["router_choice_outside_share"] <= 0.001
    assert check["control_no_bias"]["within"] is False
    assert check["control_no_bias"]["outside_share"] > 0.5
    assert notes["window_closed"]["compiles"] == 0


@pytest.mark.parametrize("first,held", [(0, 32), (64, 32), (0, 8)])
def test_every_seeds_choice_bias_holds_the_same_load(first, held):
    """The held experts' biases are one set of values for every seed (the
    midpoints of an even division of +-0.15), and so are the others'; the
    seed draws which expert has which, layer by layer."""
    from benchmark.drivers.train_steps_expert import choice_bias

    E = 256 if held == 32 else 16
    a, b = (choice_bias(seed, 4, E, first, held) for seed in (2**31 + 5, 11))
    assert a.shape == (4, E) and a.dtype == np.float32
    assert np.abs(a).max() < 0.15 and not np.array_equal(a, b)
    inside = np.zeros(E, bool)
    inside[first : first + held] = True
    for part in (inside, ~inside):
        want = ((np.arange(part.sum()) + 0.5) / part.sum() * 2 - 1) * 0.15
        for layer in range(4):
            np.testing.assert_allclose(np.sort(a[layer, part]), want, rtol=1e-6)
            np.testing.assert_allclose(np.sort(b[layer, part]), want, rtol=1e-6)
    assert not np.array_equal(a[0], a[1])  # a layer has an order of its own
    np.testing.assert_array_equal(a, choice_bias(2**31 + 5, 4, E, first, held))


def test_the_window_counts_what_the_readers_read(result, run, spec):
    r, notes = result
    closed = notes["window_closed"]
    assert closed["moe_load_max_over_mean"] >= 1.0
    assert 0 < closed["attn_window_blocks_run_share"] <= closed["attn_blocks_run_share"] <= 1.0
    ours = [m for m in spec["per_layer"] if CELL in m.get("workloads", [])]
    assert {m["moves"] for m in ours} == {"train_tok_per_s"}
    for m in ours:
        run.load_reader(m["name"])  # every metric of the cell has a reader


def _toy(seed=0, **overrides):
    config = _json("tiny-laguna.json")
    cfg = dataclasses.replace(model_config(config, "train"), **overrides)
    params = hybrid.init_params(cfg, jax.random.PRNGKey(seed))
    return config["hf_config"], cfg, params


def _program_logps(cfg, params, seqs, T):
    """The program's whole-row form on ONE packed row of ``seqs``."""
    tokens = np.zeros((1, T), np.int32)
    positions = np.zeros((1, T), np.int32)
    seg = np.zeros((1, T), np.int32)
    at = 0
    for n, s in enumerate(seqs):
        tokens[0, at : at + len(s)] = s
        positions[0, at : at + len(s)] = np.arange(len(s))
        seg[0, at : at + len(s)] = n + 1
        at += len(s)
    lp = hybrid.logprobs_of_labels(
        params, cfg, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(seg)
    )
    out, at = [], 0
    for s in seqs:
        out.append(np.asarray(lp[0, at : at + len(s) - 1]))
        at += len(s)
    return out


def test_packed_rows_log_probabilities_are_the_references():
    """Three sequences in ONE row (several segments: no kind of this stack
    carries a state) against the reference one sequence at a time: window
    and full layers at 8 / 6 heads, the rope rule by kind, the gate, 8 of
    16 experts held with a shared one."""
    hf, cfg, params = _toy()
    rng = np.random.default_rng(3)
    seqs = [rng.integers(3, 128, n).astype(np.int32) for n in (40, 70, 25)]
    with jax.default_matmul_precision("highest"):
        got = _program_logps(cfg, params, seqs, 160)
        for s, g in zip(seqs, got):
            want, _ = ref.token_logps(hf, params, jnp.asarray(ref.pad_sequence(s, 128)))
            np.testing.assert_allclose(g, np.asarray(want)[: len(s) - 1], atol=2e-5)


@pytest.mark.parametrize("wrong", [w for w in ref.WRONG if w])
def test_each_planted_fault_moves_the_reference_past_the_toy_limits(wrong):
    """A missing window mask, a gate left out, a dropped pair, rope on the
    wrong half and the experts' weights on the input each move the loss'
    gradient by far more than the toy traffic file's limits allow."""
    hf, cfg, params = _toy()
    t = _json("tiny-train-expert.json")
    it = t["interface"]
    rng = np.random.default_rng(5)
    L, T = 100, 128
    seq = {
        "tokens": jnp.asarray(ref.pad_sequence(rng.integers(3, 128, L).astype(np.int32), T)),
        "old": jnp.asarray(ref.pad_sequence((-4.85 + 0.1 * rng.standard_normal(L - 1)).astype(np.float32), T - 1)),
        "prox": jnp.asarray(ref.pad_sequence((-4.85 + 0.1 * rng.standard_normal(L - 1)).astype(np.float32), T - 1)),
        "adv": jnp.asarray(ref.pad_sequence(np.full(L - 1, 0.5, np.float32), T - 1)),
        "mask": jnp.asarray(ref.pad_sequence(np.arange(L - 1) >= 10, T - 1)),
    }
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    with jax.default_matmul_precision("highest"):
        want, *_ = ref.make_loss_and_grad(hf, it)(zeros(), params, seq)
        got, *_ = ref.make_loss_and_grad(hf, it, wrong=wrong)(zeros(), params, seq)
    worst = 0.0
    for group in ref.GROUPS:
        a = np.concatenate([np.ravel(x) for x in ref.group_leaves(got, group)])
        b = np.concatenate([np.ravel(x) for x in ref.group_leaves(want, group)])
        worst = max(worst, float(np.linalg.norm(a - b) / np.linalg.norm(b)))
    assert worst > 10 * t["grad_rel_l2_max"], (wrong, worst)


def test_the_eight_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """Section 4's share test for this router: 16 experts over 2 chips of
    8; the two shares' routed parts plus the shared expert ONCE equal the
    layer that holds all 16."""
    hf, cfg, _ = _toy()
    whole = dataclasses.replace(cfg, moe_held_experts=16)
    params = hybrid.init_params(whole, jax.random.PRNGKey(1))
    mlp = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    m = jax.random.normal(jax.random.PRNGKey(2), (256, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        full, _ = ref._experts(hf, m, mlp, 0, None)
        shared = ref._gated(m, mlp["shared"])
        parts = 0.0
        for first in (0, 8):
            share = dict(
                mlp, experts=jax.tree.map(lambda a: a[first : first + 8], mlp["experts"])
            )
            out, _ = ref._experts(hf, m, share, first, None)
            parts = parts + (out - shared)
        np.testing.assert_allclose(parts + shared, full, atol=1e-5)
        # and the program's layer gives each share's part
        for first in (0, 8):
            c = dataclasses.replace(cfg, moe_first_expert=first, moe_held_experts=8)
            share = dict(
                mlp, experts=jax.tree.map(lambda a: a[first : first + 8], mlp["experts"])
            )
            got, *_ = hybrid.held_moe_mlp(c, m[None], share)
            want, _ = ref._experts(hf, m, share, first, None)
            np.testing.assert_allclose(got[0], want, atol=1e-5)


def test_laguna_adapter_names_shapes_and_round_trip():
    from areal_tpu.models.hf.registry import family_from_architecture

    fam = family_from_architecture("LagunaForCausalLM")
    hf = _json("tiny-laguna.json")["hf_config"]
    cfg = dataclasses.replace(fam.config_from_hf(hf), dtype="float32")
    assert cfg.layer_types == ("attention", "window", "window", "window", "attention")
    assert (cfg.n_q_heads, cfg.window_plain().n_q_heads, cfg.n_kv_heads) == (6, 8, 2)
    assert cfg.rope_partial_dim == 8 and cfg.window_plain().rope_partial_dim == 0
    assert cfg.rope_yarn_factor == 4 and cfg.window_plain().rope_yarn_factor is None
    assert cfg.n_dense_layers == 1 and cfg.moe_router == "sigmoid_group"
    assert cfg.attention_gate == cfg.swa_attention_gate == "headwise"
    params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
    state = fam.params_to_hf(params, cfg)
    assert state["model.layers.1.self_attn.q_proj.weight"].shape == (8 * 16, 64)
    assert state["model.layers.0.self_attn.q_proj.weight"].shape == (6 * 16, 64)
    assert state["model.layers.4.self_attn.g_proj.weight"].shape == (6, 64)
    assert state["model.layers.0.mlp.gate_proj.weight"].shape == (128, 64)
    assert state["model.layers.2.mlp.gate.weight"].shape == (16, 64)
    assert state["model.layers.2.mlp.gate.e_score_correction_bias"].shape == (16,)
    assert state["model.layers.3.mlp.experts.15.down_proj.weight"].shape == (64, 32)
    assert state["model.layers.3.mlp.shared_experts.up_proj.weight"].shape == (32, 64)
    back = fam.params_from_hf(state, cfg)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params), jax.tree.leaves(back)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))
    again = fam.config_from_hf(fam.config_to_hf(cfg))
    assert dataclasses.replace(again, dtype="float32") == cfg


def test_the_benchmarks_configuration_is_the_catalogs_widths():
    c = _json("laguna-xs.2.json", os.path.join(BENCH, "configs"))
    cfg = model_config(c, "train")
    assert (cfg.hidden_dim, cfg.head_dim, cfg.n_kv_heads) == (2048, 128, 8)
    assert (cfg.n_q_heads, cfg.window_plain().n_q_heads) == (48, 64)
    assert (cfg.intermediate_dim, cfg.moe_intermediate_dim, cfg.shared_expert_dim) == (8192, 512, 512)
    assert (cfg.n_experts, cfg.n_held_experts, cfg.n_experts_per_tok) == (256, 32, 8)
    assert cfg.sliding_window == 512 and cfg.vocab_size == 12544 and cfg.remat
    assert cfg.rope_partial_dim == 64 and cfg.rotary_base == 500000.0
    assert abs(hybrid.yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale) - 1.4158883083359672) < 1e-9
    shapes = jax.eval_shape(
        lambda k: hybrid.init_params(cfg, k), jax.random.PRNGKey(0)
    )
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    # the issue's arithmetic: 691.6 M parameters (and 5 x 2 norms, a final
    # norm, 4 x 256 biases)
    assert abs(n - 691.6e6) < 0.1e6, n
    for k in c["reduced"]:
        assert c[k] != c["published"][k] == c["hf_config"][k]
    assert c["layer_types"] == c["hf_config"]["layer_types"] and len(c["layer_types"]) == 40


def test_flops_of_a_step_by_hand():
    hf = _json("laguna-xs.2.json", os.path.join(BENCH, "configs"))["hf_config"]
    assert flops_laguna.window_pairs(512, 512) == flops_laguna.causal_pairs(512)
    assert flops_laguna.window_pairs(1000, 512) == 512 * 513 // 2 + 488 * 512
    # one token a layer, by the issue's arithmetic (MFLOP): window 75.5 of
    # projections + gate, full 58.7, dense MLP 100.7
    one = flops_laguna.forward_flops(hf, 5, [1], 0.0, 12544)
    attn = 3 * 2 * (2048 * (8192 + 2048) + 8192 * 2048 + 2048 * 64) + 2 * 2 * (
        2048 * (6144 + 2048) + 6144 * 2048 + 2048 * 48
    )
    mlp = 2 * 3 * 2048 * 8192 + 4 * 2 * (2048 * 256 + 3 * 2048 * 512)
    scores = 4 * 128 * (3 * 64 + 2 * 48)
    assert one == attn + mlp + scores + 2 * 2048 * 12544
    # a pair costs one expert's three products
    more = flops_laguna.forward_flops(hf, 5, [1], 7.0, 12544)
    assert more - one == 7 * 2 * 3 * 2048 * 512
    assert flops_laguna.train_flops(hf, 5, [1], 7.0, 12544) == 3 * more
    # the windowed kernels: 2, 3, 4 products a pair at 64 heads of 128
    assert flops_laguna.window_kernel_flops(hf, "flash_attn_window_bwd_dkv", 10) == 2 * 4 * 64 * 128 * 10
