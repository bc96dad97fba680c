"""The engine's second cache kind: a recurrent-state slot a batch row
(SSM state + conv tail per Mamba layer) beside the attention layers'
pages.  Every sequence the engine completes, whatever its slot went
through, has the log-probabilities of the benchmark's plain reference
(which has no cache, no slots and no chunks)."""

import re

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import APIGenerateInput, GenerationHyperparameters
from areal_tpu.engine.inference_server import (
    ContinuousBatchingEngine,
    StatefulModelUnsupported,
)
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import hybrid, moe
from benchmark.lib import reference_granitemoehybrid as ref
from tests.model.test_hybrid import HF, make_cfg
from tests.engine.test_window_pages import (
    assert_no_fill_leaves_a_tail_position_out,
)

# one chip of two that share each layer's 8 experts: experts 2-5 here
FIRST, HELD = 2, 4


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg(moe_first_expert=FIRST, moe_held_experts=HELD)
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def make_engine(model, params=None, **kw):
    cfg, own = model
    defaults = dict(
        max_batch=4, kv_cache_len=64, chunk_size=4,
        sampling=SamplingParams(temperature=1.0), cache_mode="paged",
        page_size=8, prefill_chunk_tokens=8,
    )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params or own, **defaults)


def _req(qid, prompt, n, **meta):
    return APIGenerateInput(
        qid=qid, prompt_ids=list(prompt), input_ids=list(prompt),
        gconfig=GenerationHyperparameters(
            max_new_tokens=n, min_new_tokens=n, temperature=1.0
        ),
        metadata=meta or None,
    )


def run_until_done(eng, max_steps=400):
    with jax.default_matmul_precision("highest"):
        for _ in range(max_steps):
            if not eng.has_work:
                return
            eng.step()
    raise AssertionError("engine did not drain")


def assert_reference(params, results, tol=2e-5, eng=None):
    """``eng``: an engine that kept its routing; the reference follows it
    and finds its own choices the same (both compute in float32 here)."""
    fn = ref.make_token_logps(HF, first_expert=FIRST)
    for qid, out in sorted(results.items()):
        seq = list(out.prompt_ids) + list(out.output_ids)
        routed = eng.routed_experts(qid) if eng is not None else None
        if eng is not None:
            assert routed.shape == (len(seq) - 1, 8, 3), (qid, routed.shape)
        want, _, flips = ref.sequence_logps(
            fn, params, seq, routed=routed, pad_to=32
        )
        assert int(flips.sum()) == 0
        got = np.asarray(out.output_logprobs)
        diff = np.abs(got - want[-len(got):]).max()
        assert diff < tol, (qid, diff)


def _prompts(seed, *lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 64, n).tolist() for n in lens]


def test_siblings_of_one_fill_decode_as_if_each_had_prefilled_alone(model):
    eng = make_engine(model, keep_routed_experts=2)
    (p,) = _prompts(0, 13)  # two fill chunks of 8: state carried across
    for i in range(3):
        eng.submit(_req(f"a{i}", p, 6 + i))
    run_until_done(eng)
    out = eng.drain_results()
    assert len(out) == 3
    # ONE prefill of the prompt, its end state copied to the two siblings
    assert eng.prefill_tokens_total == 13 and eng.state_copies_total == 2
    assert eng.state_reprefills_total == 0
    # the routing is kept by ROOM (two sequences of the cache's 64
    # positions): three of 18-20 positions are all there
    assert_reference(model[1], out, eng=eng)
    assert_reference(model[1], out)
    # a row never parks: its slot is free when it finishes
    assert eng.n_parked == 0 and eng.state_slots_live == 0


def test_routing_is_kept_in_the_room_of_that_many_whole_sequences(model):
    """``keep_routed_experts`` = 2 with a cache of 64 positions: room for
    128 positions of routing, the oldest request out first.  At least the
    last two are always there; of shorter sequences, as many as fit (a
    count alone lost the oldest of a reader's requests when the engine
    got faster: the benchmark's toy windows, PR 48)."""
    eng = make_engine(model, keep_routed_experts=2)

    def finish(qid, n):
        eng._keep_routing(qid, np.zeros((n, 2, 2), np.int16))

    for i in range(6):
        finish(f"s{i}", 20)
    assert list(eng._routed_done) == [f"s{i}" for i in range(6)]  # 120
    finish("s6", 20)  # 140 positions: the oldest goes
    assert eng.routed_experts("s0") is None
    assert eng.routed_experts("s1") is not None
    finish("s1", 8)  # the same request again: its new routing, at the end
    assert len(eng.routed_experts("s1")) == 8
    finish("long0", 64)
    finish("long1", 64)  # two whole sequences: everything else has gone
    assert list(eng._routed_done) == ["long0", "long1"]
    assert eng._routed_done_positions == 128


def test_a_reused_slot_starts_from_zero_and_a_late_sibling_reprefills(model):
    eng = make_engine(model, max_batch=2, keep_routed_experts=8)
    p1, p2, p3 = _prompts(1, 13, 5, 20)
    eng.submit(_req("a0", p1, 30))
    eng.submit(_req("b0", p2, 3))
    with jax.default_matmul_precision("highest"):
        while eng.try_get_result("b0") is None:
            eng.step()
    # b0's slot is free and dirty; a0 decodes on.  Its sibling comes late:
    # the prompt's end state sat in a0's slot and has moved on, and the one
    # snapshot slot of an engine of two rows went to b0's fill
    assert eng.state_slots_live == 1
    assert all(float(abs(np.asarray(eng.ssm_state[:, i])).max()) > 0 for i in (0, 1))
    eng.submit(_req("a1", p1, 5))
    eng.submit(_req("c0", p3, 6))  # waits for a slot, then takes a used one
    run_until_done(eng)
    out = eng.drain_results()
    assert sorted(out) == ["a0", "a1", "c0"]
    assert eng.state_reprefills_total == 1 and eng.state_copies_total == 0
    # the late sibling prefilled its whole prompt again
    assert eng.prefill_tokens_total == 13 + 5 + 13 + 20
    assert_reference(model[1], out, eng=eng)


def test_more_requests_than_slots_queue_and_every_one_is_the_reference(model):
    eng = make_engine(model, max_batch=2)
    prompts = _prompts(2, 9, 17, 4, 11, 6)
    for i, p in enumerate(prompts):
        eng.submit(_req(f"q{i}", p, 5 + i))
    run_until_done(eng)
    out = eng.drain_results()
    assert len(out) == 5
    assert_reference(model[1], out)
    # decode chunks counted their routed pairs on the device
    assert eng.moe_pairs_routed_total > eng.moe_pairs_held_total > 0
    assert int(eng.moe_expert_pairs.sum()) == eng.moe_pairs_held_total
    assert eng.moe_expert_pairs.shape == (HELD,)


def test_no_fill_of_a_stack_with_state_takes_the_grouped_product(model, monkeypatch):
    """With the rule's threshold down at a fill chunk's 8 slots (where a
    stack without state groups: tests/engine/test_latent_pages.py) this
    stack's fills still multiply every held expert, a fill batch of 16
    slots in pieces of 8: on the chip its rows came back non-finite
    beside grouped fills (``moe.group_rows``)."""
    monkeypatch.setattr(moe, "DENSE_EXPERTS_CALL_TOKENS", 8)
    assert moe.group_rows(model[0], 8) == moe.group_rows(model[0], 4096) == 0
    jax.clear_caches()  # programs traced under the real threshold
    try:
        eng = make_engine(model, max_batch=2, prefill_chunk_tokens=16)
        prompts = _prompts(3, 9, 17, 4)
        for i, p in enumerate(prompts):
            eng.submit(_req(f"q{i}", p, 5 + i))
        run_until_done(eng)
        out = eng.drain_results()
        assert len(out) == 3
        assert_reference(model[1], out)
        assert eng.moe_fill_tokens_grouped_total == 0 < eng.moe_fill_tokens_total
        assert eng.moe_fill_extra_rounds_total == 0
    finally:
        jax.clear_caches()


def test_the_fill_span_of_a_stack_without_a_tail_says_zero(model):
    """Mamba layers keep a state and attention layers pages, and the
    expert layers report their routing: every layer of a fill runs on
    every position."""
    assert_no_fill_leaves_a_tail_position_out(
        make_engine(model), _req("t0", _prompts(5, 11)[0], 3), run_until_done
    )


def test_recompute_preemption_goes_through_the_fill_path(model):
    """A pool too small for the rows it admits: the youngest is preempted
    and re-admitted by re-prefilling prompt + generated from position 0,
    which rebuilds its state in whatever slot it is given."""
    eng = make_engine(
        model, max_batch=3, kv_cache_len=64, kv_pool_tokens=96,
        prefix_cache=False,
    )
    for i, p in enumerate(_prompts(3, 10, 12, 14)):
        eng.submit(_req(f"q{i}", p, 30))
    run_until_done(eng, max_steps=2000)
    out = eng.drain_results()
    assert eng.preempted_total >= 1
    assert all(len(o.output_ids) == 30 for o in out.values())
    assert_reference(model[1], out, tol=5e-5)


def test_weight_swap_recomputes_state_and_pages_under_the_new_weights(model):
    cfg, params = model
    new = hybrid.init_params(cfg, jax.random.PRNGKey(9))
    eng = make_engine(model)
    p1, p2 = _prompts(4, 13, 6)
    eng.submit(_req("a", p1, 30))
    eng.submit(_req("b", p2, 30))
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            eng.step()
        eng.update_weights(new, version=1)
    run_until_done(eng)
    out = eng.drain_results()
    assert eng.swap_recomputed_rows_total >= 1 and eng.version == 1
    fn = ref.make_token_logps(HF, first_expert=FIRST)
    for qid, o in out.items():
        # the tokens generated AFTER the swap follow the new weights over
        # the whole sequence so far (state and KV recomputed), those
        # before it the old ones
        seq = list(o.prompt_ids) + list(o.output_ids)
        got = np.asarray(o.output_logprobs)
        old_lp = ref.sequence_logps(fn, params, seq, pad_to=32)[0][-len(got):]
        new_lp = ref.sequence_logps(fn, new, seq, pad_to=32)[0][-len(got):]
        by_old = np.abs(got - old_lp) < 2e-5
        by_new = np.abs(got - new_lp) < 2e-5
        assert (by_old | by_new).all(), qid
        k = int(by_old.sum())
        assert 0 < k < len(got) and by_new[k:].all() and by_old[:k].all(), qid


REFUSED_AT_CONSTRUCTION = {
    "prefix-cache host spill": dict(prefix_cache_host_bytes=1 << 20),
    "int8 KV storage": dict(kv_cache_dtype="int8"),
    "int8 serving weights": dict(serving_weight_dtype="int8"),
    "the dense (unpaged) KV cache": dict(cache_mode="dense"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED_AT_CONSTRUCTION))
def test_a_feature_that_assumes_per_token_blocks_refuses_where_its_option_is_set(
    model, feature
):
    with pytest.raises(StatefulModelUnsupported, match=re.escape(feature)) as e:
        make_engine(model, **REFUSED_AT_CONSTRUCTION[feature])
    assert e.value.feature == feature


REFUSED_WHEN_ASKED = {
    "export_handoff": ("P/D handoff", lambda eng: eng.export_handoff("q")),
    "import_handoff": ("P/D handoff", lambda eng: eng.import_handoff({})),
    "import_handoff_segment": (
        "P/D handoff", lambda eng: eng.import_handoff_segment({})
    ),
    "handoff_to": (
        "P/D handoff",
        lambda eng: eng.submit(_req("q", [5, 6, 7], 2, handoff_to="peer")),
    ),
    "export_prefix": ("prefix pulls", lambda eng: eng.export_prefix("q", [5, 6])),
    "import_prefix_segment": (
        "prefix pulls", lambda eng: eng.import_prefix_segment({})
    ),
    "kv_source": (
        "prefix pulls",
        lambda eng: eng.submit(_req("q", [5, 6, 7], 2, kv_source="peer")),
    ),
}


@pytest.fixture(scope="module")
def idle_engine(model):
    return make_engine(model)


@pytest.mark.parametrize("entry", sorted(REFUSED_WHEN_ASKED))
def test_a_feature_without_an_option_refuses_by_name_when_it_is_asked_for(
    idle_engine, entry
):
    feature, ask = REFUSED_WHEN_ASKED[entry]
    with pytest.raises(StatefulModelUnsupported, match=feature):
        ask(idle_engine)


def test_a_continuation_of_a_finished_row_reprefills_instead_of_resuming(model):
    """Parked-row resume works by re-prefill: the row's slot was freed
    when it finished, the continuation (same qid, prompt + output) is a
    new admission whose pages match and whose state is rebuilt."""
    eng = make_engine(model)
    (p,) = _prompts(5, 9)
    eng.submit(_req("turn", p, 6))
    run_until_done(eng)
    first = eng.drain_results()["turn"]
    assert eng.n_parked == 0
    more = list(p) + list(first.output_ids)
    eng.submit(_req("turn", more, 5))
    run_until_done(eng)
    second = eng.drain_results()["turn"]
    # (no late sibling either: nothing alive carried that prompt)
    assert eng.resumed_total == 0 and eng.state_reprefills_total == 0
    assert eng.prefill_tokens_total == 9 + len(more)
    assert_reference(model[1], {"turn": second})


def test_a_dense_stack_has_no_state_slots():
    from tests.engine.test_paged_pool import make_engine as dense_engine

    eng, *_ = dense_engine()
    assert eng.ssm_state is None and eng.state_slots_live == 0
    assert eng.k_pool.shape[0] == eng.cfg.n_layers
    with pytest.raises(ValueError, match="keep_routed_experts"):
        dense_engine(keep_routed_experts=4)
