"""The engine over a stack of PARALLEL layers (falcon_h1: attention and a
Mamba-2 mixer side by side in EVERY layer): every layer holds pages in the
pool of whole-context pages AND a recurrent-state slot.  Every sequence the
engine completes has the log-probabilities of the benchmark's plain
reference (no cache, no pages, no slots), through admission, sibling
copies of the tail page AND the state, a late sibling's second prefill, a
recompute-preemption and a weight swap; what a stateful model is refused
stays refused by name."""

import jax
import numpy as np
import pytest

from areal_tpu.engine import kv_pages
from areal_tpu.engine.inference_server import (
    ContinuousBatchingEngine,
    StatefulModelUnsupported,
)
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import hybrid
from benchmark.lib import reference_falcon_h1 as ref
from tests.engine.test_window_pages import BS, CHUNK, _prompts, _req
from tests.engine.test_window_pages import run_until_done as _run
from tests.model.test_parallel import HF, _lively, make_cfg


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, _lively(hybrid.init_params(cfg, jax.random.PRNGKey(0)))


def make_engine(model, **kw):
    cfg, params = model
    defaults = dict(
        max_batch=4, kv_cache_len=96, chunk_size=CHUNK,
        sampling=SamplingParams(temperature=1.0), cache_mode="paged",
        page_size=BS, prefill_chunk_tokens=8,
    )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params, **defaults)


def run_until_done(eng):
    _run(eng, each=lambda eng: None)  # no window pool, no page rule


def assert_reference(params, results, tol=2e-5):
    fn = ref.make_token_logps(HF)
    for qid, out in sorted(results.items()):
        seq = list(out.prompt_ids) + list(out.output_ids)
        want = ref.sequence_logps(fn, params, seq, pad_to=32)
        got = np.asarray(out.output_logprobs)
        diff = np.abs(got - want[-len(got):]).max()
        assert diff < tol, (qid, diff)


def assert_nothing_leaked(eng):
    for row_id in range(eng.max_batch):
        if eng.rows[row_id] is not None:
            eng._release_row(row_id)
    assert eng._prefix_cache is None  # a recurrent state rules it out
    while eng._kept.evict("pages"):  # (and what was kept for late siblings)
        pass
    assert eng.free_pool_blocks == eng.n_blocks
    assert eng.state_slots_live == 0


def test_every_layer_holds_pages_and_a_state_slot(model):
    cfg, _ = model
    eng = make_engine(model)
    assert eng._stateful and eng._by_kind and eng._win is None
    assert eng.k_pool.shape == (3, eng.n_blocks, 2, BS, 8)
    assert eng.ssm_state.shape == (3, 4, 12, 32)
    assert eng.conv_state.shape == (3, 3, 4, 80)
    assert eng.state_slots_total == 4
    assert kv_pages.kinds_held(cfg)[kv_pages.STATE_SLOTS] == (
        "a model with recurrent state slots, a pool of whole-context pages"
    )


def test_siblings_get_the_tail_page_and_the_state_and_a_late_one_prefills_again(model):
    """Three samples of one prompt of 37 tokens (four full pages and a
    tail of 5): two are admitted together (one fill; the second takes the
    full pages by reference, a copy of the tail page and a copy of every
    layer's state and conv tail), the third arrives when they decode and
    prefills the prompt again (the one snapshot slot of an engine of four
    rows went to the second prompt's fill, which ended later).  A second
    prompt runs beside."""
    eng = make_engine(model)
    p1, p2 = _prompts(1, 37, 21)
    eng.submit(_req("a0", p1, 22))
    eng.submit(_req("a1", p1, 17))
    eng.submit(_req("b0", p2, 30))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 3:
            eng.step()
        eng.submit(_req("a2", p1, 9))
    run_until_done(eng)
    assert eng.state_copies_total >= 1
    assert eng.state_reprefills_total >= 1
    out = eng.drain_results()
    assert sorted(out) == ["a0", "a1", "a2", "b0"]
    assert_reference(model[1], out)
    assert_nothing_leaked(eng)


def test_the_dispatch_span_counts_both_caches_of_a_step(model):
    eng = make_engine(model)
    for i, p in enumerate(_prompts(2, 19, 11)):
        eng.submit(_req(f"c{i}", p, 8))
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
    assert seen and all(c["parallel_layers"] == 3 for c in seen)
    # a state in each of three layers for every dispatched row
    assert all(c["state_rows"] == 3 * c["rows"] for c in seen)
    assert any(c["rows"] == 2 for c in seen)
    assert all("global_readers" not in c for c in seen)


def test_a_preempted_row_is_computed_again_through_the_fill_queue(model):
    """The pool runs out while three rows decode: the youngest gives up
    its pages and its slot's state and comes back through the fill queue,
    which makes both again; its log-probabilities are the reference's."""
    eng = make_engine(model, max_batch=3, kv_cache_len=64, kv_pool_tokens=128)
    assert eng.n_blocks == 16
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
        new = _lively(hybrid.init_params(cfg, jax.random.PRNGKey(7)))
        eng.update_weights(new, version=1)
    run_until_done(eng)
    assert eng.version == 1 and eng.swap_recomputed_rows_total == 2
    out = eng.drain_results()
    assert sorted(out) == ["e0", "e1", "e2"]
    fn = ref.make_token_logps(HF)
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


@pytest.mark.parametrize(
    "how",
    [
        "export_handoff", "import_handoff", "export_prefix",
        "kv_cache_dtype", "serving_weight_dtype", "prefix_cache_host_bytes",
    ],
)
def test_what_a_stateful_model_is_refused_stays_refused_by_name(model, how):
    calls = {
        "export_handoff": lambda eng: eng.export_handoff("q"),
        "import_handoff": lambda eng: eng.import_handoff({}),
        "export_prefix": lambda eng: eng.export_prefix("q", [1, 2, 3]),
    }
    with pytest.raises(StatefulModelUnsupported) as err:
        if how in calls:
            calls[how](make_engine(model))
        else:
            make_engine(
                model, **{how: 1 << 20 if how.endswith("bytes") else "int8"}
            )
    msg = str(err.value)
    assert "recurrent state slots, a pool of whole-context pages" in msg
    assert "the state slots refuse it" in msg
