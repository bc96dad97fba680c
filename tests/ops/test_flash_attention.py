"""The trainer's flash kernels (interpret mode on CPU) against
``transformer.reference_attention`` under the same-segment causal mask:
outputs, the gradients of q, k and v, and the rule that says which block
pairs run (``block_ranges``), which may never drop a pair that holds two
equal ids."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.transformer import reference_attention
from areal_tpu.ops import flash_attention as fa


def _row(T, *lens, ids=None):
    """One row of ``T`` slots: runs of ``lens`` slots numbered 1..k (or
    ``ids``), the rest padding."""
    row = np.zeros(T, np.int32)
    at = 0
    for n, L in enumerate(lens):
        row[at : at + L] = ids[n] if ids else n + 1
        at += L
    assert at <= T
    return row


def _compare(seg, Hq=4, Hkv=2, hd=128, dtype=jnp.float32, seed=0, window=None):
    """Kernel and reference on one draw: ``(out, grads)`` of each, float32
    (under ``window``: both attend ``i - j < window``)."""
    seg = jnp.asarray(seg, jnp.int32)
    B, T = seg.shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, w = (
        jax.random.normal(kk, (B, T, Hq, hd), jnp.float32) for kk in ks[:2]
    )
    k, v = (
        jax.random.normal(kk, (B, T, Hkv, hd), jnp.float32) for kk in ks[2:]
    )
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    at = jnp.arange(T)
    mask = (seg[:, :, None] == seg[:, None, :]) & (
        at[:, None] >= at[None, :]
    )
    if window is not None:
        mask = mask & (at[:, None] - at[None, :] < window)

    def both(attend):
        def loss(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return (out * w).sum(), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            q, k, v
        )
        return out, [g.astype(jnp.float32) for g in grads]

    got = both(
        lambda q, k, v: fa.flash_attention(
            q, k, v, seg, interpret=True, window=window
        )
    )
    want = both(lambda q, k, v: reference_attention(q, k, v, mask))
    return got, want


def _assert_close(got, want, tol):
    (out, grads), (out_w, grads_w) = got, want
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, out_w, atol=tol, rtol=0)
    for g, g_w in zip(grads, grads_w):
        assert np.isfinite(np.asarray(g)).all()
        # a gradient's entries are sums over up to T slots: scale by its size
        np.testing.assert_allclose(
            g, g_w, atol=tol * float(jnp.abs(g_w).max()), rtol=0
        )


LAYOUTS = {
    # three sequences whose ends fall inside blocks, 148 slots of padding
    "packed_across_block_edges": [_row(2048, 700, 600, 600)],
    "one_sequence": [_row(2048, 2048)],
    # 1,748 slots of padding: three whole blocks of it
    "tail_padding_longer_than_a_block": [_row(2048, 300)],
    "two_rows_of_different_layouts": [
        _row(1024, 100, 500, 300), _row(1024, 1024),
    ],
    "one_block": [_row(512, 200, 250)],
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_matches_the_reference_forward_and_backward(layout):
    seg = np.stack(LAYOUTS[layout])
    _assert_close(*_compare(seg), tol=2e-5)


def test_matches_the_reference_at_12_q_heads_on_2_kv_heads_in_bfloat16():
    """Qwen2.5-1.5B's grouping at the trainer's dtype; the tolerance is
    bfloat16's (8 bits of mantissa in p, ds and the operands)."""
    seg = np.stack([_row(1024, 300, 600)])
    _assert_close(*_compare(seg, Hq=12, Hkv=2, dtype=jnp.bfloat16), tol=3e-2)


def test_padding_slots_and_their_gradients_stay_finite():
    """Padding meets padding (0 == 0), so no softmax row is empty: every
    slot of a row that is mostly padding is a number, forward and back."""
    seg = np.stack(LAYOUTS["tail_padding_longer_than_a_block"])
    (out, grads), _ = _compare(seg)
    pad = np.asarray(seg[0] == 0)
    assert pad.sum() > 3 * 512
    assert np.isfinite(np.asarray(out)[0, pad]).all()
    for g in grads:
        assert np.isfinite(np.asarray(g)[0, pad]).all()


def test_a_row_of_one_sequence_runs_every_pair_under_the_diagonal():
    seg = np.stack(LAYOUTS["one_sequence"])
    kv_lo, q_hi = fa.block_ranges(seg, 512, xp=np)
    assert kv_lo.tolist() == [[0, 0, 0, 0]]
    assert q_hi.tolist() == [[3, 3, 3, 3]]
    assert fa.blocks_run(seg) == (10, 10)


def test_packed_rows_skip_the_pairs_whose_segments_do_not_meet():
    seg = np.stack(LAYOUTS["packed_across_block_edges"])
    # blocks hold ids {1}, {1, 2}, {2, 3}, {3, padding}
    kv_lo, q_hi = fa.block_ranges(seg, 512, xp=np)
    assert kv_lo.tolist() == [[0, 0, 1, 2]]
    assert q_hi.tolist() == [[1, 2, 3, 3]]
    assert fa.blocks_run(seg) == (7, 10)
    # the device's ranges are the host's
    dev = fa.block_ranges(jnp.asarray(seg), 512)
    np.testing.assert_array_equal(dev[0], kv_lo)
    np.testing.assert_array_equal(dev[1], q_hi)


def _arbitrary_ids(seed, T=1024, blk=128):
    """Runs of random length with ids in no order, repeated ids far apart
    and padding in the middle of the row."""
    rng = np.random.default_rng(seed)
    row, at = np.zeros(T, np.int32), 0
    while at < T:
        L = int(rng.integers(1, 3 * blk))
        row[at : at + L] = rng.choice([0, 3, 7, 7, 11, 2, 40])
        at += L
    return row[None]


@pytest.mark.parametrize("seed", range(6))
def test_ids_in_any_order_never_lose_a_meeting_pair(seed, monkeypatch):
    """The skip test is conservative for ANY ids: with unsorted, repeated
    and interleaved ids the result still equals the reference's, and every
    block pair that holds two equal ids lies inside the ranges."""
    monkeypatch.setattr(fa, "_BLOCK", 128)  # 8 blocks a row, 36 pairs
    seg = _arbitrary_ids(seed)
    _assert_close(*_compare(seg, Hq=2, Hkv=1, seed=seed), tol=2e-5)
    kv_lo, q_hi = fa.block_ranges(seg, 128, xp=np)
    meets = pairs_that_meet(seg[0], 128)
    for i, j in meets:
        assert kv_lo[0, i] <= j <= i <= q_hi[0, j], (i, j)
    assert fa.blocks_run(seg, 128)[0] >= len(meets)


def pairs_that_meet(row, blk):
    """By brute force: the block pairs (i, j), j <= i, in which some q slot
    and some kv slot at or before it carry the same id."""
    n = len(row) // blk
    blocks = [set(row[b * blk : (b + 1) * blk].tolist()) for b in range(n)]
    return [
        (i, j) for i in range(n) for j in range(i + 1)
        if blocks[i] & blocks[j]
    ]


@pytest.mark.parametrize("T", [1024, 2048, 4096])
@pytest.mark.parametrize("mode", ["pack", "pad"])
def test_the_count_equals_thepairs_that_meet_on_the_trainers_layouts(mode, T):
    """For ``pack_batch`` / ``pad_batch`` layouts (contiguous runs 1..k,
    padding at the end) the ranges are exact: ``blocks_run`` counts the
    pairs that meet by brute force, of ``n (n + 1) / 2`` a row."""
    from areal_tpu.engine import batching
    from tests.engine.test_batching import make_sample

    rng = np.random.default_rng(T)
    sample = make_sample(rng.integers(40, T // 2, size=9).tolist(), seed=T)
    lay = batching.pack_batch if mode == "pack" else batching.pad_batch
    seg = lay(sample, fixed_len=T).seg_ids
    B, T = seg.shape
    n = T // 512
    run, causal = fa.blocks_run(seg)
    assert causal == B * n * (n + 1) // 2
    assert run == sum(len(pairs_that_meet(row, 512)) for row in seg)
    assert run <= causal
    if T >= 2048:  # sequences of under T / 2 slots: some pair is skipped,
        assert run < causal  # be it padding's with a sequence it follows


#: windows smaller than, equal to and larger than a block of 512 (and one
#: past the row): (window, the band's blocks at T = 2048)
WINDOWS = {100: 2, 512: 2, 513: 2, 514: 3, 700: 3, 1537: 4, 4096: 4}


@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_windowed_matches_the_masked_reference_forward_dq_dkv(window):
    """Forward, dq and dkv under ``i - j < window`` beside the segment
    rule, on a packed row whose sequences' ends fall inside blocks (a
    window then crosses a segment edge in most blocks) and on one
    sequence a row."""
    assert fa.band_blocks(2048, window) == WINDOWS[window]
    seg = np.stack(
        LAYOUTS["packed_across_block_edges"] + LAYOUTS["one_sequence"]
    )
    _assert_close(*_compare(seg, window=window), tol=2e-5)


def test_windowed_at_64_q_heads_on_8_kv_heads_in_bfloat16():
    """A window layer's grouping (64 on 8 heads of 128, window 512) at the
    trainer's dtype."""
    seg = np.stack([_row(1024, 300, 600)])
    _assert_close(
        *_compare(seg, Hq=64, Hkv=8, dtype=jnp.bfloat16, window=512), tol=3e-2
    )


def test_a_window_keeps_the_band_and_the_segments_rule():
    seg = np.stack(LAYOUTS["one_sequence"])
    # window 512 at blocks of 512: a q block meets itself and the one before
    kv_lo, q_hi = fa.block_ranges(seg, 512, xp=np, window=512)
    assert kv_lo.tolist() == [[0, 0, 1, 2]]
    assert q_hi.tolist() == [[1, 2, 3, 3]]
    assert fa.blocks_run(seg, window=512) == (7, 10)
    # a q block's first position reaches the last of the block two back
    # from a window of 514 on
    assert fa.blocks_run(seg, window=513) == (7, 10)
    assert fa.blocks_run(seg, window=514) == (9, 10)
    assert fa.blocks_run(seg, window=1) == (4, 10)
    # the segments' rule still holds under the band: blocks {1}, {1, 2},
    # {2, 3}, {3, padding}
    packed = np.stack(LAYOUTS["packed_across_block_edges"])
    assert fa.blocks_run(packed, window=2048) == (7, 10)
    assert fa.blocks_run(packed, window=512) == (7, 10)
    dev = fa.block_ranges(jnp.asarray(seg), 512, window=512)
    np.testing.assert_array_equal(dev[0], kv_lo)
    np.testing.assert_array_equal(dev[1], q_hi)


def test_unsupported_row_lengths_fall_to_the_dense_path():
    assert fa.supported(8192, 8192, None) and fa.supported(384, 384, None)
    assert not fa.supported(640, 640, None)  # not whole blocks of 512
    assert not fa.supported(200, 200, None)  # not whole lane tiles
    assert not fa.supported(64, 64, None)
    assert fa.supported(1024, 1024, 256)  # a window runs in the kernels
    assert fa.blocks_run(np.ones((2, 640), np.int32)) == (0, 0)
