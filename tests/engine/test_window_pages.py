"""The engine over a stack of window and global attention layers: two pools
with a table each and a page rule per layer kind (engine/kv_pages.py).
A window layer's page goes once every holder's window has passed it; a
global layer's page lives as long as its row.  Every sequence the engine
completes has the log-probabilities of the benchmark's plain reference
(no cache, no pages), at contexts that cross the window of 12 several
times, through sibling sharing, a late sibling's prefix reuse, parking, a
recompute-preemption and a weight swap."""

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import APIGenerateInput, GenerationHyperparameters
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.engine.kv_pages import GONE
from areal_tpu.models import hybrid, moe
from benchmark.lib import reference_smallthinker as ref
from tests.model.test_window import HF, WINDOW, make_cfg

BS, CHUNK = 8, 4


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


def make_engine(model, **kw):
    cfg, params = model
    defaults = dict(
        max_batch=4, kv_cache_len=96, chunk_size=CHUNK,
        sampling=SamplingParams(temperature=1.0), cache_mode="paged",
        page_size=BS, prefill_chunk_tokens=8, prefix_cache_min_tokens=8,
        keep_routed_experts=16,
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


def _prompts(seed, *lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 64, n).tolist() for n in lens]


def check_page_rule(eng):
    """What must hold after every step.  A decoding row: the pages its
    next query reads are held (from ``cached - W + 1`` on); those wholly
    before ``cached - W`` are gone, as of the tokens the host knew when it
    last looked (the step's harvest may have added a chunk or two since);
    it never holds more than ``ceil((W + chunks in flight) / page) + 1``.
    A filling row: none wholly before its fill position less ``W``."""
    win = eng._win
    lag = (eng.pipeline_depth + 1) * CHUNK
    for rid, row in enumerate(eng.rows):
        if row is None or row.parked:
            continue
        pages = win.rows[rid]
        if row.filling:
            fill = next(
                f for f in eng._filling
                if any(t.row_id == rid for t in f.targets)
            )
            if pages is not fill.wblocks:
                assert pages == []  # a sibling waiting for the fill
                continue
            n, lag_here = fill.fill_pos, 0
        else:
            n, lag_here = len(row.prompt) + len(row.generated) - 1, lag
            assert win.held(pages) <= -(-(WINDOW + lag) // BS) + 1
        assert all(b == GONE for b in pages[: win.first_kept(n - lag_here)]), (
            rid, n, pages,
        )
        assert all(b != GONE for b in pages[win.first_read(n) :]), (rid, n, pages)


def run_until_done(eng, max_steps=600, each=check_page_rule):
    with jax.default_matmul_precision("highest"):
        for _ in range(max_steps):
            if not eng.has_work:
                return
            eng.step()
            each(eng)
    raise AssertionError("engine did not drain")


def fill_spans(eng):
    """The counts of every ``areal.engine.fill.dispatch`` span the engine
    opens from now on, in the list returned."""
    seen, phase = [], eng._phases.phase

    def spy(name, **counts):
        if name == "areal.engine.fill.dispatch":
            seen.append(counts)
        return phase(name, **counts)

    eng._phases.phase = spy
    return seen


def assert_no_fill_leaves_a_tail_position_out(eng, req, run):
    """A stack without a keep-nothing tail, serving ``req`` to its
    end (``run(eng)``): every layer of a fill runs on every position, and
    the span says so."""
    fills = fill_spans(eng)
    eng.submit(req)
    run(eng)
    assert len(eng.drain_results()) == 1
    assert fills and eng.fill_tail_layers == 0
    assert all(c["tail_layers"] == 0 for c in fills)
    # (the running total is the engine's attribute alone)
    assert all("fill_tail_positions_saved" not in c for c in fills)
    assert eng.fill_tail_positions_saved_total == 0


def assert_reference(params, results, eng, tol=2e-5):
    fn = ref.make_token_logps(HF)
    for qid, out in sorted(results.items()):
        seq = list(out.prompt_ids) + list(out.output_ids)
        routed = eng.routed_experts(qid)
        assert routed.shape == (len(seq) - 1, 8, 3), (qid, routed.shape)
        want, _, flips = ref.sequence_logps(fn, params, seq, routed=routed, pad_to=32)
        assert int(flips.sum()) == 0
        got = np.asarray(out.output_logprobs)
        diff = np.abs(got - want[-len(got):]).max()
        assert diff < tol, (qid, diff)


def assert_nothing_leaked(eng):
    """Every page of both pools is free or held by the prefix cache."""
    for row_id in range(eng.max_batch):
        if eng.rows[row_id] is not None:
            eng._release_row(row_id)
    if eng._prefix_cache is not None:
        eng._prefix_cache.flush()
    assert eng._win.cached == {} and eng._win.cache_refs == {}
    assert eng._win.free_blocks == eng._win.n_blocks
    assert eng.free_pool_blocks == eng.n_blocks


def test_a_long_prompt_fills_under_the_rule_and_siblings_share_its_window(model):
    eng = make_engine(model)
    assert eng.k_pool.shape[0] == 2 and eng.win_k_pool.shape[0] == 6
    (p,) = _prompts(0, 37)  # five fill chunks; crosses the window 3 times
    for i in range(3):
        eng.submit(_req(f"a{i}", p, 14 + 3 * i))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 3:
            eng.step()
            check_page_rule(eng)
    # ONE prefill of the prompt.  Of its five pages the window layers hold
    # [37 - 12, 37): pages 3 and 4; page 3 is the SAME block in the three
    # rows, the tail page a copy of their own each; the global layers'
    # table holds all five
    assert eng.prefill_tokens_total == 37
    rows = [r for r in eng._win.rows if r]
    assert len(rows) == 3
    assert all(r[:3] == [GONE] * 3 for r in rows)
    assert len({r[3] for r in rows}) == 1 and len({r[4] for r in rows}) == 3
    assert all(len(b) >= 5 and GONE not in b for b in eng._pages.rows if b)
    assert eng.window_pages_released == 3
    # (the shared page, three tails, and a page each for the next chunk)
    assert eng.window_pages_live == 1 + 3 + 3
    run_until_done(eng)
    out = eng.drain_results()
    assert len(out) == 3
    assert_reference(model[1], out, eng)
    # the shared page went when the LAST sibling's window had passed it
    assert eng.window_pages_released >= 3 + 1 + 3
    assert eng._win.row_pages_max <= -(-(WINDOW + 2 * CHUNK) // BS) + 1
    assert_nothing_leaked(eng)


def test_the_fill_span_of_a_stack_without_a_tail_says_zero(model):
    """Window and global layers with experts keep pages and report their
    routing: no layer of this stack runs on the last position alone."""
    assert_no_fill_leaves_a_tail_position_out(
        make_engine(model), _req("t0", _prompts(5, 11)[0], 3), run_until_done
    )


def test_a_late_sibling_reuses_the_prefix_while_its_window_tail_is_held(model):
    eng = make_engine(model, max_batch=2)
    p1, p2 = _prompts(1, 29, 21)
    eng.submit(_req("a0", p1, 30))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 1:
            eng.step()
    assert eng.prefill_tokens_total == 29
    # its sibling comes when the fill is over: the cache holds the prompt's
    # pages AND the window layers' pages of its last window, so only what
    # lies past the match (28 of 29 tokens) is prefilled
    eng.submit(_req("a1", p1, 9))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 2:
            eng.step()
            check_page_rule(eng)
    assert eng.prefill_tokens_total == 29 + 1
    assert eng.prefix_refused_window == 0
    run_until_done(eng)
    # a request that shares only the FIRST page of a cached sequence whose
    # window has moved on finds global pages but no window pages there:
    # with 8 tokens matched its window is [0, 8), page 0, long released
    assert eng.n_parked == 2
    eng.submit(_req("b0", p1[:8] + p2, 5))
    run_until_done(eng)
    assert eng.prefix_refused_window == 1
    assert eng.prefill_tokens_total == 29 + 1 + 29  # all of it prefilled
    out = eng.drain_results()
    assert sorted(out) == ["a0", "a1", "b0"]
    assert_reference(model[1], out, eng)
    assert_nothing_leaked(eng)


def test_a_cached_window_page_stays_pinned_while_its_fill_allocates(model):
    """A fill that reuses a cached prefix pins its pages in BOTH pools
    before it allocates in either: the whole-context pool's allocation
    evicts cache entries, this very prefix's tail among them, and the
    window-layer page cached with it must not go back to the free stack
    (and out again as one of the fill's own) under the fill."""
    eng = make_engine(model, max_batch=2)
    p, c = _prompts(3, 21, 17)
    for qid, prompt, n in (("c", c, 3), ("a", p, 6)):
        eng.submit(_req(qid, prompt, n))
        run_until_done(eng)
    out = eng.drain_results()
    while eng._evict_parked() is not None:
        pass  # the cache alone holds the two sequences' pages now
    taken = eng._pages.alloc(eng.free_pool_blocks)  # other holders, say
    seq = list(out["a"].prompt_ids) + list(out["a"].output_ids)
    new_fill, starts = eng._new_fill, []

    def checked(*a, **kw):
        fill = new_fill(*a, **kw)
        starts.append(fill.fill_pos)
        for pool, held in eng._pages_of(fill):
            held = [b for b in held if b != GONE]
            assert len(set(held)) == len(held) and not set(held) & set(pool._free)
            assert all(pool._ref[b] >= 1 for b in held)
        return fill

    eng._new_fill = checked
    eng.submit(_req("b", seq + _prompts(4, 9)[0], 5))
    with jax.default_matmul_precision("highest"):
        eng.step()
    # 26 tokens reused; to make room the cache let go of the other
    # sequence's two pages and of this one's own tail entry
    assert starts == [26]
    assert eng.prefix_cache_stats()["evictions_total"] == 3
    eng._pages.free(taken)
    run_until_done(eng)
    assert_reference(model[1], out, eng)
    # (nobody kept the routing behind the 26 reused tokens, which were
    # another request's prompt AND output: the reference routes for itself)
    b = eng.drain_results()["b"]
    want, _, _ = ref.sequence_logps(
        ref.make_token_logps(HF), model[1],
        list(b.prompt_ids) + list(b.output_ids), pad_to=32,
    )
    assert np.abs(np.asarray(b.output_logprobs) - want[-5:]).max() < 2e-5
    assert_nothing_leaked(eng)


def test_a_parked_row_resumes_inside_its_window(model):
    eng = make_engine(model, max_batch=2)
    (p,) = _prompts(2, 26)
    eng.submit(_req("c", p, 11))
    run_until_done(eng)
    first = eng.drain_results()["c"]
    assert eng.n_parked == 1
    parked = next(i for i, r in enumerate(eng.rows) if r is not None)
    n = 26 + 11 - 1
    assert eng._win.rows[parked][: max(n - WINDOW, 0) // BS] == [GONE] * 3
    # the continuation resumes over the pages the row still holds
    cont = list(first.prompt_ids) + list(first.output_ids)
    eng.submit(_req("c", cont, 10))
    run_until_done(eng)
    assert eng.resumed_total == 1 and eng.prefill_tokens_total == 26
    second = eng.drain_results()["c"]
    fn = ref.make_token_logps(HF)
    seq = cont + list(second.output_ids)
    want = ref.sequence_logps(fn, model[1], seq, pad_to=32)[0]
    got = np.asarray(second.output_logprobs)
    assert np.abs(got - want[-len(got):]).max() < 2e-4  # (routes for itself)
    assert_nothing_leaked(eng)


def test_a_preempted_row_is_computed_again_through_the_fill_queue(model):
    """Rows under the window rule hold a steady three or four window pages
    however long they grow, so it is the GLOBAL layers' pool that runs
    out: 16 pages for three rows that grow to 7 each.  The youngest row
    gives up the pages of both pools and comes back through the fill
    queue, which provisions both again."""
    eng = make_engine(
        model, max_batch=3, kv_cache_len=64, kv_pool_tokens=128,
        prefix_cache=False,
    )
    assert eng.n_blocks == 16 and eng._win.n_blocks == 16
    ps = _prompts(3, 30, 27, 25)
    for i, p in enumerate(ps):
        eng.submit(_req(f"d{i}", p, 24))
    run_until_done(eng)
    assert eng.preempted_total >= 1
    out = eng.drain_results()
    assert len(out) == 3
    assert_reference(model[1], out, eng)
    assert_nothing_leaked(eng)


def test_a_weight_swap_sends_decoding_rows_back_through_the_fill_queue(model):
    cfg, params = model
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
        assert any(r is not None and r.filling for r in eng.rows)
        new = hybrid.init_params(cfg, jax.random.PRNGKey(7))
        eng.update_weights(new, version=1)
    run_until_done(eng)
    assert eng.version == 1 and eng.swap_recomputed_rows_total == 2
    out = eng.drain_results()
    assert sorted(out) == ["e0", "e1", "e2"]
    # a sequence that started after the swap is the NEW weights' all
    # through; the two that straddle it end under the new weights, over KV
    # computed again under them
    fn = ref.make_token_logps(HF)
    seq = list(out["e2"].prompt_ids) + list(out["e2"].output_ids)
    want = ref.sequence_logps(
        fn, new, seq, routed=eng.routed_experts("e2"), pad_to=32
    )[0]
    got = np.asarray(out["e2"].output_logprobs)
    assert np.abs(got - want[-len(got):]).max() < 2e-5
    for qid in ("e0", "e1"):
        o = out[qid]
        seq = list(o.prompt_ids) + list(o.output_ids)
        want = ref.sequence_logps(fn, new, seq, pad_to=32)[0]
        got = np.asarray(o.output_logprobs)
        assert np.abs(got[-3:] - want[-3:]).max() < 2e-4, qid
    assert_nothing_leaked(eng)


def test_what_moves_whole_rows_between_servers_is_refused_by_name(model):
    eng = make_engine(model)
    for call in (
        lambda: eng.export_handoff("q"),
        lambda: eng.import_handoff({}),
        lambda: eng.export_prefix("q", [1, 2, 3]),
    ):
        with pytest.raises(NotImplementedError, match="window layers"):
            call()
    with pytest.raises(NotImplementedError, match="host spill"):
        make_engine(model, prefix_cache_host_bytes=1 << 20)


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
        eng = make_engine(model, max_batch=2)
        prompts = _prompts(2, 9, 17, 4, 37, 6)
        for i, p in enumerate(prompts):
            eng.submit(_req(f"q{i}", p, 5 + i))
        eng.submit(_req("q0b", prompts[0], 7))
        run_until_done(eng)
        out = eng.drain_results()
        assert len(out) == 6
        assert_reference(model[1], out, eng)
        assert eng.moe_fill_tokens_grouped_total == eng.moe_fill_tokens_total > 0
        eng._add_fill_rounds_that_arrived()
        assert eng.moe_fill_extra_rounds_total > 0
    finally:
        jax.clear_caches()
