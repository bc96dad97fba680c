"""The engine over LATENT pages (one ``[c_kv | k_rope]`` entry a token
and layer, no V pool): a stack of latent-attention layers has no recurrent
state, so it keeps what the dense model has (siblings share a prompt's
pages, tail pages are copied, cached prefixes are reused, rows park) and
goes through the hybrid stack's two programs.  Every sequence the engine
completes has the log-probabilities of the benchmark's plain reference,
which has no cache, no pages and no absorbed form."""

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import APIGenerateInput, GenerationHyperparameters
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import hybrid, moe
from benchmark.lib import reference_deepseek_v3 as ref
from tests.model.test_latent import HF, make_cfg
from tests.engine.test_window_pages import (
    assert_no_fill_leaves_a_tail_position_out,
)

# one chip of four that share each layer's 16 experts: experts 4-7 here,
# a whole routing group
FIRST, HELD = 4, 4


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg(moe_first_expert=FIRST, moe_held_experts=HELD)
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def make_engine(model, **kw):
    cfg, params = model
    defaults = dict(
        max_batch=4, kv_cache_len=64, chunk_size=4,
        sampling=SamplingParams(temperature=1.0), cache_mode="paged",
        page_size=8, prefill_chunk_tokens=8, prefix_cache_min_tokens=8,
    )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params, **defaults)


def _req(qid, prompt, n):
    return APIGenerateInput(
        qid=qid, prompt_ids=list(prompt), input_ids=list(prompt),
        gconfig=GenerationHyperparameters(
            max_new_tokens=n, min_new_tokens=n, temperature=1.0
        ),
    )


def run_until_done(eng, max_steps=400):
    with jax.default_matmul_precision("highest"):
        for _ in range(max_steps):
            if not eng.has_work:
                return
            eng.step()
    raise AssertionError("engine did not drain")


def assert_reference(params, results, eng=None, tol=2e-5):
    fn = ref.make_token_logps(HF, first_expert=FIRST)
    for qid, out in sorted(results.items()):
        seq = list(out.prompt_ids) + list(out.output_ids)
        routed = eng.routed_experts(qid) if eng is not None else None
        if eng is not None:
            # every position READ, every EXPERT layer (3 of the 4), top 3
            assert routed.shape == (len(seq) - 1, 3, 3), (qid, routed.shape)
        want, _, flips = ref.sequence_logps(fn, params, seq, routed=routed, pad_to=32)
        assert int(flips.sum()) == 0
        got = np.asarray(out.output_logprobs)
        diff = np.abs(got - want[-len(got):]).max()
        assert diff < tol, (qid, diff)


def _prompts(seed, *lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 64, n).tolist() for n in lens]


def test_siblings_of_one_fill_share_its_pages_and_copy_its_tail(model):
    eng = make_engine(model, keep_routed_experts=8)
    assert eng.v_pool.size == 0 and eng.k_pool.shape[2:] == (1, 8, 128)
    assert eng.state_slots_live == 0 and eng.ssm_state.size == 0
    (p,) = _prompts(0, 13)  # two fill chunks of 8; one full page, a tail of 5
    for i in range(3):
        eng.submit(_req(f"a{i}", p, 6 + i))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 3:
            eng.step()
    # ONE prefill of the prompt; its full page is the SAME block in the
    # three rows, its tail page a copy of their own each
    assert eng.prefill_tokens_total == 13
    rows = [eng._pages.rows[i] for i in range(4) if eng._pages.rows[i]]
    assert len(rows) == 3 and len({r[0] for r in rows}) == 1
    assert len({r[1] for r in rows}) == 3
    run_until_done(eng)
    out = eng.drain_results()
    assert len(out) == 3
    assert_reference(model[1], out, eng=eng)
    assert_reference(model[1], out)
    # a finished row PARKS (its pages stay for a continuation): nothing
    # here is a recurrent state that ends where the sequence does
    assert eng.n_parked == 3
    # decode chunks counted their routed pairs and chosen groups
    assert eng.moe_pairs_routed_total > eng.moe_pairs_held_total > 0
    assert int(eng.moe_expert_pairs.sum()) == eng.moe_pairs_held_total
    assert 0 < eng.moe_groups_hit_total <= eng.moe_pairs_routed_total // 3


def test_a_late_sibling_reuses_cached_pages_and_keeps_their_routing(model):
    eng = make_engine(model, max_batch=2, keep_routed_experts=8)
    p1, p2 = _prompts(1, 21, 5)  # two full pages and a tail of 5
    eng.submit(_req("a0", p1, 12))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 1:
            eng.step()
    assert eng.prefill_tokens_total == 21
    # its sibling comes when the fill is over: the prompt's pages are in
    # the prefix cache, and only what lies past them is prefilled
    eng.submit(_req("a1", p1, 5))
    eng.submit(_req("b0", p2, 6))  # waits for a row, then takes a parked one
    run_until_done(eng)
    out = eng.drain_results()
    assert sorted(out) == ["a0", "a1", "b0"]
    assert eng.prefill_tokens_total < 21 + 21 + 5
    assert eng.prefix_cache_stats()["cached_tokens_total"] >= 16
    # the routing handed out covers the reused positions too: the
    # reference follows it over the whole sequence
    assert_reference(model[1], out, eng=eng)


def test_more_requests_than_rows_queue_and_every_one_is_the_reference(model):
    eng = make_engine(model, max_batch=2)
    prompts = _prompts(2, 9, 17, 4, 11, 6)
    for i, p in enumerate(prompts):
        eng.submit(_req(f"q{i}", p, 5 + i))
    run_until_done(eng)
    out = eng.drain_results()
    assert len(out) == 5
    assert_reference(model[1], out)


@pytest.mark.parametrize(
    "feature,kw",
    [
        ("int8 KV storage", dict(kv_cache_dtype="int8")),
        ("int8 serving weights", dict(serving_weight_dtype="int8")),
        ("the dense (unpaged) KV cache", dict(cache_mode="dense")),
    ],
)
def test_what_cannot_hold_for_latent_pages_refuses_by_name(model, feature, kw):
    with pytest.raises(NotImplementedError, match="latent") as e:
        make_engine(model, **kw)
    assert feature in str(e.value)


def test_int8_latent_pages_refuse_by_name_in_the_allocator(model):
    from areal_tpu.models import paged

    with pytest.raises(NotImplementedError, match="latent pages"):
        paged.alloc_kv_pool(model[0], 4, 8, kv_cache_dtype="int8")


def test_dispatch_span_counts_the_latent_context(model):
    """``latent_ctx_tokens_sum`` / ``latent_pages_attended`` on the decode
    dispatch span: what the latent readers take."""
    eng = make_engine(model)
    counts = {}

    class Span:
        def is_enabled(self):
            return True

        def set_metadata(self, **kw):
            counts.update(kw)

    (p,) = _prompts(3, 13)
    eng.submit(_req("a", p, 4))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 1:
            eng.step()
    snapshot = [(i, r.epoch) for i, r in enumerate(eng.rows) if r is not None]
    eng._count_dispatch(Span(), snapshot, 4)
    assert counts["latent_ctx_tokens_sum"] == counts["ctx_tokens_sum"] >= 13
    assert counts["latent_pages_attended"] == counts["pages_attended"] == 2
    assert "state_rows_sum" not in counts


def test_the_fill_span_of_a_stack_without_a_tail_says_zero(model):
    """Latent layers keep their entries and the expert layers report
    their routing: every layer of a fill runs on every position."""
    assert_no_fill_leaves_a_tail_position_out(
        make_engine(model), _req("t0", _prompts(5, 11)[0], 3), run_until_done
    )


def test_fills_that_take_the_grouped_product_are_the_reference(model, monkeypatch):
    """The sizes here never reach ``moe.group_rows``' 1,024 tokens, so the
    rule is set to groups of 2 rows from a fill chunk's 8 slots on: every
    fill then multiplies its routed pairs in ROUNDS that slice the layer
    stack themselves, as the cells' fill programs do, beside decode chunks
    that multiply every held expert; siblings, a queue and reused rows."""
    monkeypatch.setattr(
        moe, "group_rows", lambda cfg, n: 2 if n >= 8 and cfg.n_held_experts else 0
    )
    jax.clear_caches()  # programs traced under the real rule
    try:
        eng = make_engine(model, max_batch=2, keep_routed_experts=8)
        prompts = _prompts(2, 9, 17, 4, 11, 6)
        for i, p in enumerate(prompts):
            eng.submit(_req(f"q{i}", p, 5 + i))
        eng.submit(_req("q0b", prompts[0], 7))
        run_until_done(eng)
        out = eng.drain_results()
        assert len(out) == 6
        assert_reference(model[1], out, eng=eng)
        assert eng.moe_fill_tokens_grouped_total == eng.moe_fill_tokens_total > 0
        eng._add_fill_rounds_that_arrived()
        assert eng.moe_fill_extra_rounds_total > 0
    finally:
        jax.clear_caches()
