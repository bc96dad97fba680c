"""batching.pad_batch / pack_batch unit tests: segment-table invariants,
pack/unpack round trips, transition-key boundary zeroing, and the extras
classification fix (per-token keys in an all-length-1 batch)."""

import numpy as np
import pytest

from areal_tpu.api.data import SequenceSample, SequenceSplitSpec
from areal_tpu.engine import batching


def make_sample(seqlens, vocab=100, seed=0, extra_keys=()):
    rng = np.random.RandomState(seed)
    total = sum(seqlens)
    data = {
        "packed_input_ids": rng.randint(1, vocab, size=total).astype(np.int32)
    }
    if "prompt_mask" in extra_keys:  # full-length
        data["prompt_mask"] = rng.rand(total) < 0.3
    if "packed_logprobs" in extra_keys:  # transition (L-1)
        data["packed_logprobs"] = -rng.rand(
            total - len(seqlens)
        ).astype(np.float32)
    if "rewards" in extra_keys:  # scalar
        data["rewards"] = rng.rand(len(seqlens)).astype(np.float32)
    return SequenceSample.from_default(
        seqlens, [f"s{i}" for i in range(len(seqlens))], data
    )


LENS = [12, 9, 30, 4, 17, 8, 25, 6]


def test_pack_batch_segment_invariants():
    sample = make_sample(LENS, seed=1)
    pb = batching.pack_batch(sample)
    B, T = pb.shape
    assert T == 32
    # every original sequence appears verbatim at its table slot
    offs = np.concatenate([[0], np.cumsum(LENS)])
    packed = sample.data["packed_input_ids"]
    assert pb.n_segs == len(LENS)
    for s, L in enumerate(LENS):
        r, c = int(pb.seg_rows[s]), int(pb.seg_starts[s])
        assert int(pb.seg_lens[s]) == L
        np.testing.assert_array_equal(
            pb.tokens[r, c : c + L], packed[offs[s] : offs[s + 1]]
        )
        # positions restart at 0 per segment (RoPE correct by construction)
        np.testing.assert_array_equal(
            pb.positions[r, c : c + L], np.arange(L)
        )
        # one seg id covers the whole segment, nonzero
        ids = pb.seg_ids[r, c : c + L]
        assert ids.min() == ids.max() > 0
    for r in range(pb.n_real):
        row_ids = pb.seg_ids[r][pb.seg_ids[r] != 0]
        ks = np.unique(row_ids)
        # seg ids numbered 1..k per row
        np.testing.assert_array_equal(ks, np.arange(1, len(ks) + 1))
        # capacity respected
        assert int(pb.seq_lens[r]) == (pb.seg_ids[r] != 0).sum() <= T
    # packing actually packs: fewer rows than sequences
    assert pb.n_real < len(LENS)
    # slots shrink vs one-sequence-per-row at the same bucket
    padded = batching.pad_batch(sample)
    assert pb.padded_slots < padded.padded_slots


def test_pad_batch_trivial_segment_table():
    sample = make_sample(LENS, seed=2)
    pb = batching.pad_batch(sample, row_multiple=4)
    B = pb.shape[0]
    assert pb.seg_rows.shape == (B,)  # [S] == [B]: per-row arrays line up
    np.testing.assert_array_equal(pb.seg_rows[: len(LENS)], np.arange(len(LENS)))
    np.testing.assert_array_equal(pb.seg_starts, np.zeros(B, np.int32))
    np.testing.assert_array_equal(pb.seg_lens, pb.seq_lens)


@pytest.mark.parametrize("packer", ["pad", "pack"])
def test_pack_unpack_round_trip_original_order(packer):
    sample = make_sample(
        LENS, seed=3,
        extra_keys=("prompt_mask", "packed_logprobs", "rewards"),
    )
    if packer == "pack":
        pb = batching.pack_batch(sample, row_multiple=4)
    else:
        pb = batching.pad_batch(sample, row_multiple=4)
    # full-length round trip
    got = batching.unpack_per_token(pb.tokens, pb)
    np.testing.assert_array_equal(got, sample.data["packed_input_ids"])
    got = batching.unpack_per_token(pb.extras["prompt_mask"], pb)
    np.testing.assert_array_equal(got, sample.data["prompt_mask"])
    # transition-aligned round trip (shift=1)
    got = batching.unpack_per_token(pb.extras["packed_logprobs"], pb, shift=1)
    np.testing.assert_array_equal(got, sample.data["packed_logprobs"])


def test_transition_key_zero_at_segment_boundaries():
    sample = make_sample(LENS, seed=4, extra_keys=("packed_logprobs",))
    pb = batching.pack_batch(sample, fixed_len=64)
    lp = pb.extras["packed_logprobs"]
    for s in range(pb.n_segs):
        r, c, L = (
            int(pb.seg_rows[s]),
            int(pb.seg_starts[s]),
            int(pb.seg_lens[s]),
        )
        # the segment's LAST column carries no transition value — packed
        # next to another segment or not
        assert lp[r, c + L - 1] == 0.0
    # everything outside real segments is zero too
    mask = np.zeros_like(lp, bool)
    for s in range(pb.n_segs):
        r, c, L = (
            int(pb.seg_rows[s]),
            int(pb.seg_starts[s]),
            int(pb.seg_lens[s]),
        )
        mask[r, c : c + L - 1] = True
    assert np.all(lp[~mask] == 0.0)


def test_scalar_extras_per_segment_in_pack_mode():
    sample = make_sample(LENS, seed=5, extra_keys=("rewards",))
    pb = batching.pack_batch(sample)
    r = pb.extras["rewards"]
    assert r.ndim == 1 and r.shape[0] == pb.seg_rows.shape[0]
    np.testing.assert_array_equal(
        r[: pb.n_segs], sample.data["rewards"]
    )


def test_all_length_one_batch_keeps_per_token_keys_per_token():
    """The old ``all(l == 1)`` heuristic silently laid a genuine
    per-token key out as [B] when every sequence had length 1; the
    classifier now compares against the token key's lengths."""
    n = 5
    sample = SequenceSample.from_default(
        [1] * n,
        [f"s{i}" for i in range(n)],
        {
            "packed_input_ids": np.arange(1, n + 1, dtype=np.int32),
            # per-token key (lens == token lens == all ones)
            "prompt_mask": np.ones(n, bool),
            # registered scalar key: stays [B] even in this degenerate batch
            "rewards": np.arange(n, dtype=np.float32),
        },
    )
    pb = batching.pad_batch(sample)
    assert pb.extras["prompt_mask"].shape == pb.tokens.shape  # [B, T], not [B]
    np.testing.assert_array_equal(
        pb.extras["prompt_mask"][:n, 0], np.ones(n, bool)
    )
    assert pb.extras["rewards"].shape == (pb.shape[0],)


def test_length_two_transition_key_not_misread_as_scalar():
    """L-1 == 1 transition keys in an all-length-2 batch were scalar
    under the old heuristic; they must lay out [B, T] with column 1
    zeroed."""
    n = 4
    sample = SequenceSample.from_default(
        [2] * n,
        [f"s{i}" for i in range(n)],
        {
            "packed_input_ids": np.arange(1, 2 * n + 1, dtype=np.int32),
            "packed_logprobs": -np.arange(1, n + 1, dtype=np.float32),
        },
    )
    pb = batching.pad_batch(sample)
    lp = pb.extras["packed_logprobs"]
    assert lp.shape == pb.tokens.shape
    np.testing.assert_array_equal(lp[:n, 0], -np.arange(1, n + 1))
    assert np.all(lp[:, 1:] == 0.0)


def test_pack_batch_fixed_shapes_and_row_padding():
    """The shape a plan fixes: its rows, its row length (no power of two
    above the flash block), its segment capacity; what it does not fill is
    zero."""
    sample = make_sample(LENS, seed=6)
    bins = [[2, 0, 3], [6, 4, 1], [5, 7]]
    pb = batching.pack_batch(
        sample, fixed_rows=8, fixed_len=1536, fixed_segs=16, bins=bins
    )
    assert pb.shape == (8, 1536)
    assert pb.seg_rows.shape == (16,)
    assert np.all(pb.seg_lens[pb.n_segs :] == 0)
    # rows in the order of their first member, members ascending
    assert pb.seg_rows[:8].tolist() == [0, 1, 0, 0, 1, 2, 1, 2]
    assert pb.seq_lens[:3].tolist() == [12 + 30 + 4, 9 + 17 + 25, 8 + 6]
    # padding rows are all-zero
    assert pb.n_real == 3
    assert np.all(pb.tokens[pb.n_real :] == 0)
    assert np.all(pb.seg_ids[pb.n_real :] == 0)


def _flat(seqlens):
    return [[l] for l in seqlens]


def _check_plan(plan, id_lens, max_slots, row_quantum=1):
    """What every plan holds: each id in exactly one micro-batch, each of
    its sequences whole in exactly one row, no row over its length, no
    micro-batch over its rows, the rows a multiple of the quantum."""
    assert sorted(i for g in plan.groups for i in g) == list(
        range(len(id_lens))
    )
    assert plan.rows % row_quantum == 0
    for ids, rows in zip(plan.groups, plan.bins):
        assert ids == sorted(ids)
        lens = [l for i in ids for l in id_lens[i]]
        assert sorted(s for r in rows for s in r) == list(range(len(lens)))
        assert len(rows) <= plan.rows
        for r in rows:
            assert sum(lens[s] for s in r) <= plan.row_len
    one_unit = plan.rows == row_quantum or max(
        len(ls) for ls in id_lens
    ) > 1
    assert plan.rows * plan.row_len <= max_slots or one_unit


@pytest.mark.parametrize(
    "seqlens,want",
    [
        # the longest sequence's step, not its power of two
        ([2322, 1700, 1800, 1300, 900], (1, 2, 4096)),
        ([40, 3, 3], (1, 1, 64)),  # under the block: the old buckets
        ([513, 100], (1, 1, 1024)),
        ([3000, 700, 600], (1, 1, 4608)),
        # 8,387 tokens at 8,192: the leftover rides in whole rows
        ([2534, 1900, 1700, 1200, 600, 453], (2, 1, 4608)),
    ],
)
def test_plan_row_length_is_on_the_block_ladder(seqlens, want):
    from areal_tpu.ops import flash_attention

    assert batching.ROW_LEN_STEP == flash_attention._BLOCK
    assert flash_attention.supported(want[2], want[2], None) == (want[2] >= 128)
    plan = batching.plan_minibatch(_flat(seqlens), lambda T: T, 8192)
    assert (plan.n_stacked, plan.rows, plan.row_len) == want
    _check_plan(plan, _flat(seqlens), 8192)
    assert plan.slots == want[0] * want[1] * want[2]


def test_plan_holds_the_row_at_the_first_step_when_told_not_to_grow():
    seqlens = _flat([700, 650, 600, 600, 300, 200])
    free = batching.plan_minibatch(seqlens, lambda T: T, 4096)
    held = batching.plan_minibatch(
        seqlens, lambda T: T, 4096, grow=lambda T: False
    )
    assert (free.rows, free.row_len) == (1, 3072)
    assert (held.n_stacked, held.rows, held.row_len) == (1, 4, 1024)
    # and a cost that grows with T^2 holds it by itself
    quad = batching.plan_minibatch(seqlens, lambda T: T * T, 4096)
    assert (quad.rows, quad.row_len) == (4, 1024)
    for p in (free, held, quad):
        _check_plan(p, seqlens, 4096)


@pytest.mark.parametrize("pack", [True, False])
@pytest.mark.parametrize("quantum", [1, 4])
def test_plan_rows_are_a_multiple_of_the_quantum(pack, quantum):
    rng = np.random.RandomState(3)
    seqlens = _flat(rng.randint(20, 900, size=23).tolist())
    plan = batching.plan_minibatch(
        seqlens, lambda T: T, 4096, row_quantum=quantum, pack=pack
    )
    _check_plan(plan, seqlens, 4096, quantum)
    if not pack:
        assert plan.row_len == 1024
        assert all(len(r) == 1 for b in plan.bins for r in b)
    # no micro-batch over the slot budget: one quantum of the shortest
    # rows fits it
    assert quantum * batching.row_len(max(map(max, seqlens))) <= 4096
    assert plan.rows * plan.row_len <= 4096


@pytest.mark.parametrize("cost", ["slots", "quadratic"])
def test_plan_row_length_stops_where_a_quantum_of_rows_fills_the_budget(cost):
    """On a DP mesh a micro-batch holds at least ``row_quantum`` rows, so T
    climbs to budget / quantum and no further (the recipe's FSDP-6 at a
    budget of 32,768: no row over 5,120); only a sequence longer than that
    makes a longer row, and then a micro-batch is one quantum of them."""
    rng = np.random.RandomState(7)
    row_cost = (lambda T: T) if cost == "slots" else (lambda T: T + T * T / 4096)
    seqlens = _flat(rng.randint(300, 3000, size=128).tolist())
    plan = batching.plan_minibatch(seqlens, row_cost, 32768, row_quantum=6)
    _check_plan(plan, seqlens, 32768, 6)
    assert 3072 <= plan.row_len <= 5120
    assert plan.rows * plan.row_len <= 32768
    seqlens[5] = [9000]
    plan = batching.plan_minibatch(seqlens, row_cost, 32768, row_quantum=6)
    _check_plan(plan, seqlens, 32768, 6)
    assert (plan.rows, plan.row_len) == (6, 9216)


@pytest.mark.parametrize("pack", [True, False])
def test_plan_keeps_an_ids_sequences_in_one_micro_batch(pack):
    """A preference pair [chosen, rejected] under one id never straddles
    micro-batches, packed (one row, or rows of its own when it is longer
    than a row) or not (a row a sequence)."""
    rng = np.random.RandomState(5)
    id_lens = [rng.randint(30, 500, size=2).tolist() for _ in range(12)]
    id_lens[4] = [400, 390, 410, 380]  # longer than the rows, 512 or 1024
    plan = batching.plan_minibatch(id_lens, lambda T: T, 2048, pack=pack)
    _check_plan(plan, id_lens, 2048)
    assert len(plan.groups) > 1
    assert plan.row_len in (512, 1024)
    if pack:
        # an id that fits a row lies in ONE row
        for ids, rows in zip(plan.groups, plan.bins):
            start = 0
            for i in ids:
                mine = set(range(start, start + len(id_lens[i])))
                start += len(id_lens[i])
                holding = [r for r in rows if mine & set(r)]
                assert len(holding) == 1 or i == 4


def test_plan_honours_a_minimum_count_and_refuses_an_impossible_one():
    seqlens = _flat([33, 5, 9, 4, 12, 7, 6, 10])
    plan = batching.plan_minibatch(seqlens, lambda T: T, 10**12, min_mbs=3)
    assert len(plan.groups) == 3 and plan.n_stacked == 4
    _check_plan(plan, seqlens, 10**12)
    with pytest.raises(ValueError):
        batching.plan_minibatch(seqlens, lambda T: T, 10**12, min_mbs=9)


def test_planned_layout_round_trips_in_original_order():
    """Every sequence lands whole in one row, and per-token outputs come
    back in the sample's own order through the plan's groups."""
    rng = np.random.RandomState(11)
    seqlens = rng.randint(8, 700, size=19).tolist()
    sample = make_sample(seqlens, seed=2)
    plan = batching.plan_minibatch(_flat(seqlens), lambda T: T, 2048)
    assert len(plan.groups) > 1
    order = [i for g in plan.groups for i in g]
    mbs = SequenceSample.reorder(sample, order).split_with_spec(
        SequenceSplitSpec(sizes=[len(g) for g in plan.groups])
    )
    parts = {}
    for ids, mb, bins in zip(plan.groups, mbs, plan.bins):
        pb = batching.pack_batch(
            mb, fixed_rows=plan.rows, fixed_len=plan.row_len, bins=bins
        )
        assert pb.shape == (plan.rows, plan.row_len)
        got = batching.unpack_per_token(pb.tokens, pb)
        for i, piece in zip(ids, np.split(got, np.cumsum(pb.seg_lens[: len(ids)])[:-1])):
            parts[i] = piece
    np.testing.assert_array_equal(
        np.concatenate([parts[i] for i in range(len(seqlens))]),
        sample.data["packed_input_ids"],
    )
