"""Packed (FFD multi-segment rows) vs per-row padded training parity.

The acceptance bar for the packing path: identical token denominators
EXACTLY, loss within fp tolerance, and the same optimizer update — across
dense, MoE, and sliding-window attention arms — plus the padded-slot
reduction that is the point of the feature."""

import jax
import numpy as np
import pytest

from areal_tpu.api.data import MicroBatchSpec, SequenceSample
from areal_tpu.base.topology import MeshSpec
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.engine import batching
from areal_tpu.engine.train_engine import TrainEngine, plan_layout
from areal_tpu.interfaces.sft_interface import sft_loss_fn
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import init_params

#: long-tail-ish lengths: one long trace among short rows — the padded
#: layout pads every row to bucket(33)=64, packing does not
LENS = (33, 5, 9, 4, 12, 7, 6, 10)


def make_sample(cfg, seqlens=LENS, seed=0):
    rng = np.random.RandomState(seed)
    total = sum(seqlens)
    prompt_mask = np.zeros(total, dtype=bool)
    off = 0
    for L in seqlens:
        prompt_mask[off : off + max(1, L // 3)] = True
        off += L
    return SequenceSample.from_default(
        list(seqlens),
        [f"s{i}" for i in range(len(seqlens))],
        {
            "packed_input_ids": rng.randint(1, cfg.vocab_size, size=total)
            .astype(np.int32),
            "prompt_mask": prompt_mask,
        },
    )


def _engine(cfg, pack, seed=0, mesh=None):
    mesh = mesh or MeshSpec(data=1, fsdp=1, model=1).make_mesh(
        jax.devices()[:1]
    )
    return TrainEngine(
        cfg,
        mesh,
        init_params(cfg, jax.random.PRNGKey(seed)),
        optimizer_cfg=OptimizerConfig(
            lr=1e-2, lr_scheduler_type="constant", warmup_steps_proportion=0.0
        ),
        total_train_steps=10,
        pack_sequences=pack,
    )


def _parity_arm(
    cfg, mb_spec=None, loss_tol=1e-5, param_tol=2e-5, seqlens=LENS,
    packed_mesh=None,
):
    """One train step padded vs packed on identical init: exact token
    denominator, fp-tolerance loss, gradient norm and per-token statistics,
    fp-tolerance resulting params."""
    mb_spec = mb_spec or MicroBatchSpec()
    sample = make_sample(cfg, seqlens)
    stats, engines = {}, {}
    for name, pack in (("padded", False), ("packed", True)):
        e = _engine(cfg, pack, mesh=packed_mesh if pack else None)
        stats[name] = e.train_batch(sample, sft_loss_fn, mb_spec)
        engines[name] = e
    # token denominator: EXACTLY equal (same transition set by mask
    # construction — packing must not leak/drop a single token)
    assert stats["padded"]["n_tokens"] == stats["packed"]["n_tokens"]
    assert np.isclose(
        stats["padded"]["loss"], stats["packed"]["loss"], atol=loss_tol
    ), (stats["padded"]["loss"], stats["packed"]["loss"])
    for k in set(stats["padded"]) - {"n_mbs", "tokens_per_sec", "mfu"}:
        assert np.isclose(
            stats["padded"][k], stats["packed"][k], rtol=1e-4, atol=loss_tol
        ), (k, stats["padded"][k], stats["packed"][k])
    for p1, p2 in zip(
        jax.tree.leaves(engines["padded"].params),
        jax.tree.leaves(engines["packed"].params),
    ):
        np.testing.assert_allclose(
            np.asarray(p1), np.asarray(p2), atol=param_tol
        )
    return stats, engines


def test_dense_packed_parity_and_padding_reduction():
    cfg = tiny_config(vocab_size=64)
    stats, engines = _parity_arm(cfg)
    # the point of the feature: the long-tail batch wastes >= 2x fewer
    # padded slots when packed
    assert engines["padded"].last_padded_slots >= (
        2 * engines["packed"].last_padded_slots
    ), (
        engines["padded"].last_padded_slots,
        engines["packed"].last_padded_slots,
    )
    assert engines["packed"].last_padding_frac < engines["padded"].last_padding_frac


def test_dense_packed_parity_with_microbatches():
    cfg = tiny_config(vocab_size=64)
    _parity_arm(cfg, mb_spec=MicroBatchSpec(n_mbs=2))


def test_moe_packed_parity():
    cfg = tiny_config(
        vocab_size=64,
        n_experts=4,
        n_experts_per_tok=2,
        moe_aux_loss_coef=0.01,
        moe_z_loss_coef=0.001,
    )
    # MoE router stats are masked on seg_ids != 0 and the aux losses are
    # means over REAL tokens, so the packed layout must reproduce them
    _parity_arm(cfg, loss_tol=2e-5)


def test_sliding_window_packed_parity():
    # window smaller than the longest sequence: per-segment positions
    # must keep the window mask identical in the packed layout
    cfg = tiny_config(vocab_size=64, sliding_window=8)
    _parity_arm(cfg)


def test_forward_batch_packed_parity():
    """forward_batch per-token outputs restore the ORIGINAL packed-1D
    order identically under both layouts (the overlap-dispatch loop must
    not reorder micro-batch outputs)."""
    from areal_tpu.interfaces.ppo_interface import model_logprobs_fwd

    cfg = tiny_config(vocab_size=64)
    sample = make_sample(cfg, seed=3)
    outs = {}
    for name, pack in (("padded", False), ("packed", True)):
        e = _engine(cfg, pack, seed=1)
        outs[name] = e.forward_batch(
            sample,
            model_logprobs_fwd(1.0),
            MicroBatchSpec(n_mbs=2),
            output_shift=1,
        )
    expected_len = sum(l - 1 for l in LENS)
    assert outs["padded"].shape == outs["packed"].shape == (expected_len,)
    np.testing.assert_allclose(
        outs["padded"], outs["packed"], atol=1e-5, rtol=1e-5
    )


def test_packed_scan_padding_batches_are_inert():
    """The all-zero scan-padding micro-batch invariant survives the
    layout by slots: a pow2-bucketed mb count (3 real -> 4 stacked)
    contributes zero loss/denom/grads for the padding slot."""
    cfg = tiny_config(vocab_size=64)
    sample = make_sample(cfg, seed=5)
    e1 = _engine(cfg, True)
    s1 = e1.train_batch(sample, sft_loss_fn, MicroBatchSpec(n_mbs=3))
    assert s1["n_mbs"] == 3
    # three micro-batches of one 64-slot row each, and the fourth of zeros
    assert e1.last_padded_slots == 4 * 1 * 64
    e2 = _engine(cfg, True)
    s2 = e2.train_batch(sample, sft_loss_fn, MicroBatchSpec(n_mbs=1))
    assert s2["n_mbs"] == 1
    assert s1["n_tokens"] == s2["n_tokens"]
    assert np.isclose(s1["loss"], s2["loss"], atol=1e-5)


#: heavy-tailed minibatches at a slot budget of 2,048: 2,190 tokens are 142
#: OVER it (the benchmark's 8,387 at 8,192), 2,040 are under
OVER = (900, 400, 300, 200, 150, 100, 80, 60)
UNDER = (900, 400, 300, 200, 100, 80, 60)
BUDGET = MicroBatchSpec(max_tokens_per_mb=2048)
#: a model whose projections outweigh its attention, as a real one's do
#: (at tiny_config's own widths a slot of a 1,024-token row costs three
#: times as much in attention as in everything else)
WIDE = dict(vocab_size=64, hidden_dim=64, intermediate_dim=1024)


@pytest.fixture
def layout_as_on_the_chip(monkeypatch):
    """The layout rule sees the chip's attention dispatch, under which the
    flash kernel takes long rows; the model's own dispatch stays this
    backend's (the jnp path: same numbers)."""
    from areal_tpu.engine import train_engine
    from areal_tpu.models import transformer

    def takes_flash(cfg, T, mesh):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            return transformer.takes_flash(cfg, T, mesh)

    monkeypatch.setattr(train_engine, "takes_flash", takes_flash)


@pytest.mark.parametrize(
    "seqlens,shape",
    [
        # the leftover rides in whole rows: 3,072 slots, where a cut by
        # tokens laid a full piece and a 142-token piece out at [2, 2, 1024]
        (OVER, (2, 1, 1536)),
        (UNDER, (1, 1, 2048)),
    ],
)
def test_layout_by_slots_equals_one_sequence_a_row(
    seqlens, shape, layout_as_on_the_chip
):
    cfg = tiny_config(**WIDE)
    plan = plan_layout(cfg, [[l] for l in seqlens], BUDGET)
    assert (plan.n_stacked, plan.rows, plan.row_len) == shape
    stats, engines = _parity_arm(cfg, mb_spec=BUDGET, seqlens=seqlens)
    assert engines["packed"].last_padded_slots == np.prod(shape)
    # the reference: a sequence a row, two rows of 1,024 a micro-batch
    assert engines["padded"].last_padded_slots == 4 * 2 * 1024
    assert stats["padded"]["n_mbs"] == 4
    assert stats["packed"]["n_mbs"] == shape[0]


def test_layout_by_slots_on_a_four_shard_mesh(layout_as_on_the_chip):
    """Rows come in multiples of the DP shards (``row_quantum`` 4), so they
    are shorter and more; the update equals the single-device reference's."""
    cfg = tiny_config(**WIDE)
    mesh = MeshSpec(data=2, fsdp=2, model=1).make_mesh(jax.devices()[:4])
    _, engines = _parity_arm(
        cfg, mb_spec=BUDGET, seqlens=OVER, packed_mesh=mesh
    )
    e = engines["packed"]
    assert e.row_quantum == 4
    plan = plan_layout(
        cfg, [[l] for l in OVER], BUDGET, mesh=mesh, row_quantum=4
    )
    assert (plan.n_stacked, plan.rows, plan.row_len) == (1, 4, 1024)
    # what was uploaded is what was planned
    assert e.last_padded_slots == plan.slots == 4096


def test_layout_by_slots_holds_the_row_length_under_a_sliding_window(
    layout_as_on_the_chip, monkeypatch,
):
    """The jnp attention path holds [T, T] scores: no longer rows there,
    whatever the padding costs.  A sliding window is no such path since
    the flash kernels take one (``flash_attention(window=)``): its rows
    grow as the unwindowed model's do; off the chip they hold."""
    cfg = tiny_config(sliding_window=8, **WIDE)
    plan = plan_layout(cfg, [[l] for l in OVER], BUDGET)
    assert plan.row_len == 1536 == plan_layout(
        tiny_config(**WIDE), [[l] for l in OVER], BUDGET
    ).row_len
    from areal_tpu.engine import train_engine
    from areal_tpu.models import transformer

    with monkeypatch.context() as m:  # this backend's own dispatch: the CPU's
        m.setattr(train_engine, "takes_flash", transformer.takes_flash)
        held = plan_layout(cfg, [[l] for l in OVER], BUDGET)
    assert held.row_len == batching.row_len(max(OVER)) == 1024
    assert (held.n_stacked, held.rows) == (2, 2)
    # nor on a mesh with a ``seq`` axis, which splits T
    seq = MeshSpec(seq=2).make_mesh(jax.devices()[:2])
    assert plan_layout(
        tiny_config(**WIDE), [[l] for l in OVER], BUDGET, mesh=seq
    ).row_len == 1024
    # nor where the model itself makes long rows dear: at tiny_config's
    # widths attention is most of a slot's cost
    narrow = plan_layout(
        tiny_config(vocab_size=64), [[l] for l in OVER], BUDGET
    )
    assert (narrow.n_stacked, narrow.rows, narrow.row_len) == (2, 2, 1024)
    _parity_arm(cfg, mb_spec=BUDGET, seqlens=OVER)


def test_layout_holds_the_row_length_off_the_chip():
    """The planner asks the model's own dispatch predicate: on this
    backend attention is the jnp path, so rows stay at the longest
    sequence's step, in the plan and in what the engine uploads."""
    from areal_tpu.models.transformer import takes_flash

    cfg = tiny_config(**WIDE)
    assert not takes_flash(cfg, 1024, None)
    plan = plan_layout(cfg, [[l] for l in OVER], BUDGET)
    assert (plan.n_stacked, plan.rows, plan.row_len) == (2, 2, 1024)
    e = _engine(cfg, True)
    e.train_batch(make_sample(cfg, OVER), sft_loss_fn, BUDGET)
    assert e.last_padded_slots == plan.slots == 4096


def test_layout_of_the_benchmarks_train_cell(layout_as_on_the_chip):
    """``benchmark/traffic/train-packed.json``'s three batches through the
    engine's layout function, as the PPO actor splits them: counts, no
    speeds.  The parent laid a step out in 58,027 slots and six shapes, its
    largest micro-batch 12,288 slots in a program of 14.1 of 15.75 GB."""
    import json
    import os

    from benchmark.lib import lengths
    from benchmark.lib.program import model_config

    root = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark")
    with open(os.path.join(root, "traffic", "train-packed.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(root, "configs", "qwen2.5-1.5b.json")) as f:
        cfg = model_config(json.load(f), "train")
    mb_spec = MicroBatchSpec(max_tokens_per_mb=traffic["max_tokens_per_mb"])
    slots, shapes = [], set()
    for k in range(traffic["distinct_batches"]):
        lens = lengths.train_batch(traffic, 1, 1000, k)["seqlens"]
        sample = SequenceSample.from_default(
            lens,
            [f"s{i}" for i in range(len(lens))],
            {"packed_input_ids": np.zeros(sum(lens), np.int32)},
        )
        n = traffic["interface"]["n_minibatches"]
        mbs, *_ = sample.split(MicroBatchSpec(n_mbs=n))
        assert len(mbs) == n
        step = 0
        for mb in mbs:
            plan = plan_layout(cfg, mb.seqlens["packed_input_ids"], mb_spec)
            shapes.add((plan.n_stacked, plan.rows, plan.row_len))
            assert plan.rows * plan.row_len <= 12288
            step += plan.slots
        slots.append(step)
    assert max(slots) <= 38000, slots
    assert sum(slots) / len(slots) <= 36000, slots
    assert len(shapes) <= 6, shapes
