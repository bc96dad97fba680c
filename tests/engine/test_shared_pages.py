"""The engine over a decoder-hybrid-decoder stack: THREE cache kinds at
once (recurrent state slots, the window layers' pool and table under the
page rule, and a pool of whole-context pages that has ONE layer, written by
the stack's one full-attention layer and read by it and by every cross
layer).  Every sequence the engine completes has the log-probabilities of
the benchmark's plain reference (no cache, no pages, no slots), at
contexts that cross the window of 12 several times, through admission,
sibling copies of pages and states, a late sibling's second prefill,
window-page release, a recompute-preemption and a weight swap."""

import jax
import numpy as np
import pytest

from areal_tpu.engine.inference_server import (
    ContinuousBatchingEngine,
    StatefulModelUnsupported,
)
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import hybrid
from benchmark.lib import reference_phi4flash as ref
from tests.engine.test_window_pages import (
    BS, CHUNK, _prompts, _req, check_page_rule, fill_spans, run_until_done,
)
from tests.model.test_sambay import HF, make_cfg


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def make_engine(model, **kw):
    cfg, params = model
    defaults = dict(
        max_batch=4, kv_cache_len=96, chunk_size=CHUNK,
        sampling=SamplingParams(temperature=1.0), cache_mode="paged",
        page_size=BS, prefill_chunk_tokens=8,
    )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params, **defaults)


def assert_reference(params, results, tol=2e-5):
    fn = ref.make_token_logps(HF)
    for qid, out in sorted(results.items()):
        seq = list(out.prompt_ids) + list(out.output_ids)
        want = ref.sequence_logps(fn, params, seq, pad_to=32)
        got = np.asarray(out.output_logprobs)
        diff = np.abs(got - want[-len(got):]).max()
        assert diff < tol, (qid, diff)


def assert_nothing_leaked(eng):
    """Every page of both pools is free again and no slot is held."""
    for row_id in range(eng.max_batch):
        if eng.rows[row_id] is not None:
            eng._release_row(row_id)
    assert eng._prefix_cache is None  # a recurrent state rules it out
    while eng._kept.evict("pages"):  # (and what was kept for late siblings)
        pass
    assert eng._win.free_blocks == eng._win.n_blocks
    assert eng.free_pool_blocks == eng.n_blocks
    assert eng.state_slots_live == 0


def test_the_engine_holds_three_cache_kinds_and_one_written_pool_layer(model):
    cfg, _ = model
    eng = make_engine(model)
    assert eng._stateful and eng._win is not None and eng._by_kind
    # ONE layer of whole-context pages for the four layers that read it
    # (layer 7 and the cross layers 9, 11 here), a pair's heads as one
    assert eng.k_pool.shape == (1, eng.n_blocks, 2, BS, 8)
    assert eng.win_k_pool.shape == (3, eng._win.n_blocks, 2, BS, 8)
    assert eng.ssm_state.shape == (4, 4, 16, 64)
    assert eng.conv_state.shape == (4, 3, 4, 64)
    assert cfg.n_global_readers == 3


def test_siblings_share_pages_and_copy_states_and_a_late_one_prefills_again(model):
    """Three samples of one prompt of 37 tokens: two are admitted together
    (one fill; the second takes the full pages by reference, a copy of the
    tail page of each pool and a copy of every Mamba layer's state and conv
    tail), the third arrives when they decode and prefills the prompt
    again: an engine of four rows has ONE snapshot slot, and the second
    prompt's fill, which ended later, took it (tests/engine/
    test_kept_fills.py has the late sibling that joins).  A second prompt
    runs beside."""
    eng = make_engine(model)
    p1, p2 = _prompts(1, 37, 21)
    eng.submit(_req("a0", p1, 22))
    eng.submit(_req("a1", p1, 17))
    eng.submit(_req("b0", p2, 30))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 3:
            eng.step()
            check_page_rule(eng)
        eng.submit(_req("a2", p1, 9))
    run_until_done(eng)
    assert eng.state_copies_total >= 1
    assert eng.state_reprefills_total >= 1
    assert eng.window_pages_released > 0
    out = eng.drain_results()
    assert sorted(out) == ["a0", "a1", "a2", "b0"]
    assert_reference(model[1], out)
    assert_nothing_leaked(eng)


def test_the_dispatch_span_counts_the_layers_that_read_the_global_pool(model):
    eng = make_engine(model)
    eng.submit(_req("c0", _prompts(2, 19)[0], 8))
    seen = []
    count = eng._count_dispatch

    def spy(span, snapshot, chunk_size):
        class Span:
            def is_enabled(self):
                return True

            def set_metadata(self, **counts):
                seen.append(counts)

        count(Span(), snapshot, chunk_size)

    eng._count_dispatch = spy
    run_until_done(eng)
    assert seen and all(c["global_readers"] == 3 for c in seen)
    assert all(
        c["window_tokens_sum"] <= c["ctx_tokens_sum"] and c["rows"] == 1
        for c in seen
    )


def test_the_fill_span_counts_the_tail_layers_and_the_positions_they_skip(model):
    """Layers 8-11 here (``[gmu, cross] x 2``) keep nothing: a fill runs
    them on each row's last position; its span says how many layers, and
    the engine's running total how many (layer, position) pairs that left
    out."""
    eng = make_engine(model, prefill_chunk_tokens=16)
    assert eng.fill_tail_layers == 4
    fills = fill_spans(eng)
    for i, p in enumerate(_prompts(4, 19, 7, 30)):
        eng.submit(_req(f"d{i}", p, 4))
    run_until_done(eng)
    assert len(eng.drain_results()) == 3
    assert len(fills) >= 3 and all(c["tail_layers"] == 4 for c in fills)
    # (the engine's attribute; no span and no record carries the total)
    assert all("fill_tail_positions_saved" not in c for c in fills)
    total = sum(4 * (c["f_pad"] * c["c"] - c["f_pad"]) for c in fills)
    assert eng.fill_tail_positions_saved_total == total > 0
    assert sum(
        r["fill_programs"] for r in eng._phases.records()
    ) == len(fills)


def test_a_preempted_row_is_computed_again_through_the_fill_queue(model):
    """The window layers hold a steady few pages a row, so it is the pool
    of whole-context pages that runs out.  The youngest row gives up its
    pages of both pools and its slot's state and comes back through the
    fill queue, which makes all three again."""
    eng = make_engine(
        model, max_batch=3, kv_cache_len=64, kv_pool_tokens=128,
    )
    assert eng.n_blocks == 16 and eng._win.n_blocks == 16
    for i, p in enumerate(_prompts(3, 30, 27, 25)):
        eng.submit(_req(f"d{i}", p, 24))
    run_until_done(eng)
    assert eng.preempted_total >= 1
    out = eng.drain_results()
    assert len(out) == 3
    assert_reference(model[1], out)
    assert_nothing_leaked(eng)


def test_a_weight_swap_computes_pages_and_states_again_under_the_new_weights(model):
    cfg, _ = model
    eng = make_engine(model, max_batch=3)
    p1, p2 = _prompts(4, 28, 41)
    eng.submit(_req("e0", p1, 30))
    eng.submit(_req("e1", p1, 26))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 2:
            eng.step()
        for _ in range(2):
            eng.step()
        eng.submit(_req("e2", p2, 6))
        eng.step()  # e2 is mid-fill when the weights change
        new = hybrid.init_params(cfg, jax.random.PRNGKey(7))
        eng.update_weights(new, version=1)
    run_until_done(eng)
    assert eng.version == 1 and eng.swap_recomputed_rows_total == 2
    out = eng.drain_results()
    assert sorted(out) == ["e0", "e1", "e2"]
    fn = ref.make_token_logps(HF)
    # a sequence that started after the swap is the NEW weights' all
    # through; the two that straddle it end under the new weights, over
    # pages AND states computed again under them
    seq = list(out["e2"].prompt_ids) + list(out["e2"].output_ids)
    got = np.asarray(out["e2"].output_logprobs)
    want = ref.sequence_logps(fn, new, seq, pad_to=32)
    assert np.abs(got - want[-len(got):]).max() < 2e-5
    for qid in ("e0", "e1"):
        o = out[qid]
        seq = list(o.prompt_ids) + list(o.output_ids)
        want = ref.sequence_logps(fn, new, seq, pad_to=32)
        got = np.asarray(o.output_logprobs)
        assert np.abs(got[-3:] - want[-3:]).max() < 2e-4, qid
    assert_nothing_leaked(eng)


def test_what_assumes_per_token_blocks_is_refused_and_says_which_kind(model):
    eng = make_engine(model)
    for call in (
        lambda: eng.export_handoff("q"),
        lambda: eng.import_handoff({}),
        lambda: eng.export_prefix("q", [1, 2, 3]),
    ):
        with pytest.raises(StatefulModelUnsupported) as err:
            call()
        # all three kinds named, and the one that refuses
        msg = str(err.value)
        assert "recurrent state slots, a window pool, a pool of whole-context pages" in msg
        assert "the state slots refuse it" in msg
    with pytest.raises(ValueError, match="expert layers"):
        make_engine(model, keep_routed_experts=4)  # no router, no routing
    for kw in (
        dict(kv_cache_dtype="int8"), dict(serving_weight_dtype="int8"),
        dict(prefix_cache_host_bytes=1 << 20),
    ):
        with pytest.raises(StatefulModelUnsupported, match="state slots refuse"):
            make_engine(model, **kw)
