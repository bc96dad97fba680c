"""The engine over dots3_note's stack: a pool of latent entries with a pool
of INDEX KEYS riding its block table (one page id names both), and a
window pool of latent entries of another width under ``PagePool(window=5)``.
Every sequence the engine completes has the log-probabilities of the
benchmark's plain reference (no cache, no pages, its own selection of 6
positions a query), at contexts of 4-9 times ``index_topk``, through
sibling sharing, a tail copy, a late sibling's prefix-cache hit, parking
and the window layers' release; and the positions the engine's decode
steps attended are the reference's chosen sets.

TWO engines for the whole file and the reproduction script's own (an engine
costs a test process 450-550 memory mappings a program set: PERF.md
section 7)."""

import os

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import APIGenerateInput, GenerationHyperparameters
from areal_tpu.engine import kv_pages
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.kv_pages import GONE
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import hybrid
from benchmark.lib import reference_dots3_note as ref
from tests.model.test_sparse import HF, make_cfg

BS, CHUNK, WINDOW, TOPK = 8, 4, 5, 6


@pytest.fixture(scope="module")
def model():
    cfg = make_cfg()
    return cfg, hybrid.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def eng(model):
    cfg, params = model
    return ContinuousBatchingEngine(
        cfg, params, max_batch=4, kv_cache_len=96, chunk_size=CHUNK,
        sampling=SamplingParams(temperature=1.0), cache_mode="paged",
        page_size=BS, prefill_chunk_tokens=8, prefix_cache_min_tokens=8,
        keep_routed_experts=16, keep_chosen_sets=5,
    )


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


def run_until_done(eng, max_steps=600):
    with jax.default_matmul_precision("highest"):
        for _ in range(max_steps):
            if not eng.has_work:
                return
            eng.step()
    raise AssertionError("engine did not drain")


def assert_reference(params, results, eng, tol=2e-5):
    """Log-probabilities against the reference following the engine's
    routing (2e-5 at float32: the model tests' tolerance), and the kept
    chosen sets against the reference's OWN selection, exactly."""
    fn = ref.make_token_logps(HF)
    for qid, out in sorted(results.items()):
        seq = list(out.prompt_ids) + list(out.output_ids)
        routed = eng.routed_experts(qid)
        assert routed.shape == (len(seq) - 1, 4, 3), (qid, routed.shape)
        at, sets = eng.chosen_sets(qid)
        want, _, flips, scores = ref.sequence_logps(
            fn, params, seq, routed=routed, pad_to=32, keep_at=at
        )
        assert int(flips.sum()) == 0
        got = np.asarray(out.output_logprobs)
        assert np.abs(got - want[-len(got):]).max() < tol, qid
        # the prompt's last positions (from the FILL's mask: as many as
        # its last chunk held, five at most), then the last decode steps
        n_decode = min(5, len(out.output_ids) - 1)
        n_prompt = len(at) - n_decode
        plen = len(out.prompt_ids)
        assert 1 <= n_prompt <= 5 and sets.shape == (len(at), 2, TOPK)
        assert at[:n_prompt].tolist() == list(range(plen - n_prompt, plen))
        assert at[n_prompt:].tolist() == list(range(len(seq) - 1 - n_decode, len(seq) - 1))
        for i, t in enumerate(at):
            for f in range(2):
                row = ref.selection_agreement(scores[f, i], sets[i, f], int(t), TOPK, 0.0)
                assert row["agree"] == 1.0 and row["within"], (qid, t, f, row)


def assert_nothing_leaked(eng):
    for row_id in range(eng.max_batch):
        if eng.rows[row_id] is not None:
            eng._release_row(row_id)
    if eng._prefix_cache is not None:
        eng._prefix_cache.flush()
    assert eng._win.cached == {} and eng._win.cache_refs == {}
    assert eng._win.free_blocks == eng._win.n_blocks
    assert eng.free_pool_blocks == eng.n_blocks


def test_the_pools_are_two_latent_formats_and_an_index_pool_beside_one(eng):
    assert eng.k_pool.shape == (2, eng.n_blocks, 1, BS, 128)
    assert eng.v_pool.shape == (2, eng.n_blocks, 1, BS, 12)  # index keys
    assert eng.win_k_pool.shape == (3, eng._win.n_blocks, 1, BS, 128)
    assert eng.win_v_pool.size == 0 and eng._win.window == WINDOW
    assert eng.index_pages_live == 0 == eng.pages_live


def test_siblings_share_a_fill_with_the_index_pages_riding_along(model, eng):
    (p,) = _prompts(0, 37)  # five fill chunks; six windows; 6 x top-k
    for i in range(3):
        eng.submit(_req(f"a{i}", p, 9 + 3 * i))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 3:
            eng.step()
    # ONE prefill.  The global table holds all five pages (the first four
    # shared, the tail page a copy each: latent entries AND index keys);
    # the window table holds the last page alone ([37 - 5, 37))
    assert eng.prefill_tokens_total == 37
    rows = [r for r in eng._pages.rows if r]
    assert len(rows) == 3 and len({tuple(r[:4]) for r in rows}) == 1
    tails = [r[4] for r in rows]
    assert len(set(tails)) == 3
    v = np.asarray(eng.v_pool)
    assert np.abs(v[:, tails[0], 0, :5]).min() > 0  # 37 = 4 x 8 + 5 keys
    assert (v[:, tails[0], 0, :5] == v[:, tails[1], 0, :5]).all()
    wrows = [r for r in eng._win.rows if r]
    assert all(r[:4] == [GONE] * 4 for r in wrows)
    assert eng.index_pages_live == eng.pages_live > 0
    run_until_done(eng)
    out = eng.drain_results()
    assert len(out) == 3
    assert_reference(model[1], out, eng)
    assert eng.window_pages_released >= 4


def test_a_late_sibling_takes_latent_and_index_pages_from_the_prefix_cache(model, eng):
    before = eng.prefill_tokens_total
    (p,) = _prompts(1, 29)
    eng.submit(_req("b0", p, 12))
    with jax.default_matmul_precision("highest"):
        while eng.n_decoding < 1:
            eng.step()
    eng.submit(_req("b1", p, 7))
    run_until_done(eng)
    # the late sibling prefilled the ONE token past the cached prefix:
    # its indexer scored index keys that another row's fill had written
    assert eng.prefill_tokens_total == before + 29 + 1
    assert eng.prefix_refused_window == 0
    out = eng.drain_results()
    assert sorted(out) == ["b0", "b1"]
    assert_reference(model[1], out, eng)


def test_the_dispatch_span_counts_what_the_indexed_layers_score_and_attend(eng):
    seen, phase = [], eng._phases.phase

    class Span:
        def __init__(self, inner):
            self.inner = inner

        def __enter__(self):
            self.inner.__enter__()
            return self

        def __exit__(self, *a):
            return self.inner.__exit__(*a)

        def is_enabled(self):
            return True

        def set_metadata(self, **counts):
            seen.append(counts)

    def spy(name, **counts):
        inner = phase(name, **counts)
        watched = ("areal.engine.decode.dispatch", "areal.engine.ensure_blocks")
        return Span(inner) if name in watched else inner

    eng._phases.phase = spy
    scored0 = eng.index_positions_scored_total
    attended0 = eng.index_positions_attended_total
    try:
        eng.submit(_req("c0", _prompts(2, 19)[0], 6))
        run_until_done(eng)
    finally:
        eng._phases.phase = phase
    eng.drain_results()
    dispatch = [c for c in seen if "ctx_tokens_sum" in c]
    blocks = [c for c in seen if "pages_live" in c]
    assert dispatch and blocks
    for c in dispatch:
        assert c["index_ctx_tokens_sum"] == c["ctx_tokens_sum"] >= 19
        assert c["sparse_tokens_sum"] == TOPK * c["rows"]
        assert c["latent_window_tokens_sum"] == c["window_tokens_sum"] == WINDOW * c["rows"]
        assert "latent_ctx_tokens_sum" not in c  # no layer reads the whole context
    assert all(c["index_pages_live"] == c["pages_live"] for c in blocks)
    scored = eng.index_positions_scored_total - scored0
    attended = eng.index_positions_attended_total - attended0
    assert scored == CHUNK * sum(c["ctx_tokens_sum"] for c in dispatch)
    assert attended == CHUNK * TOPK * len(dispatch) and attended < scored / 3


def test_a_parked_row_resumes_over_all_three_pools(model, eng):
    before = eng.prefill_tokens_total
    (p,) = _prompts(3, 26)
    eng.submit(_req("d", p, 11))
    run_until_done(eng)
    first = eng.drain_results()["d"]
    cont = list(first.prompt_ids) + list(first.output_ids)
    resumed = eng.resumed_total
    eng.submit(_req("d", cont, 10))
    run_until_done(eng)
    assert eng.resumed_total == resumed + 1
    assert eng.prefill_tokens_total == before + 26
    second = eng.drain_results()["d"]
    seq = cont + list(second.output_ids)
    want = ref.sequence_logps(ref.make_token_logps(HF), model[1], seq, pad_to=32)[0]
    got = np.asarray(second.output_logprobs)
    assert np.abs(got - want[-len(got):]).max() < 2e-4  # (routes for itself)
    assert_nothing_leaked(eng)


@pytest.mark.parametrize(
    "feature,option",
    [
        ("int8 KV storage", dict(kv_cache_dtype="int8")),
        ("prefix-cache host spill", dict(prefix_cache_host_bytes=1 << 20)),
    ],
)
def test_what_the_index_pool_refuses_is_refused_by_name(model, feature, option):
    cfg, params = model
    held = kv_pages.kinds_held(cfg)
    assert held[kv_pages.INDEX_POOL] == "a stack whose latent layers keep index keys"
    assert held[kv_pages.WINDOW_POOL] == "a stack with latent window layers"
    with pytest.raises(kv_pages.CacheKindRefuses) as err:
        ContinuousBatchingEngine(
            cfg, params, max_batch=2, kv_cache_len=32, cache_mode="paged",
            page_size=BS, **option,
        )
    assert feature in str(err.value) and "index pool" in str(err.value)
    for feature in ("a tensor- or expert-parallel serving mesh", "P/D handoff", "prefix pulls"):
        with pytest.raises(kv_pages.CacheKindRefuses) as err:
            kv_pages.refuse(feature, held)
        assert "index pool" in str(err.value)


def test_the_decode_path_follows_the_tables_ratio_to_the_chosen_set(model, eng):
    """The engines above hold 96 positions a row, 16 x ``index_topk``: their
    decode steps attend under the mask (every test above), and say so in
    the step records' header.  One of 104 gathers, its kept sets the
    positions the program hands out, and its completions the reference's
    as well."""
    cfg, params = model
    assert eng.sparse_decode_path == "masked"
    assert eng._phases.header()["sparse_decode_path"] == "masked"
    longer = ContinuousBatchingEngine(
        cfg, params, max_batch=2, kv_cache_len=104, chunk_size=CHUNK,
        sampling=SamplingParams(temperature=1.0), cache_mode="paged",
        page_size=BS, prefill_chunk_tokens=8, keep_routed_experts=4,
        keep_chosen_sets=5,
    )
    assert longer.sparse_decode_path == "gather"
    assert longer._phases.header()["sparse_decode_path"] == "gather"
    longer.submit(_req("g", _prompts(5, 21)[0], 9))
    run_until_done(longer)
    assert_reference(params, longer.drain_results(), longer)


def test_keeping_chosen_sets_needs_an_indexer_and_the_kept_routing(model):
    cfg, params = model
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(
            cfg, params, max_batch=2, kv_cache_len=32, cache_mode="paged",
            page_size=BS, keep_chosen_sets=4,
        )


def test_the_stand_alone_reproduction_runs_at_the_toy_size(capsys):
    """``scripts/sparse_rows_finite.py`` (a full engine, fills that end
    together, every row's log-probabilities and every pool finite: what a
    chip run of it asserts at the cell's widths) on the benchmark's toy
    configuration: its arguments, its engine and its closing line."""
    import importlib.util
    import json

    path = os.path.join(
        os.path.dirname(__file__), "..", "..", "scripts", "sparse_rows_finite.py"
    )
    spec = importlib.util.spec_from_file_location("sparse_rows_finite", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    rc = script.main([
        "--config", "benchmark/tests/data/tiny-sparse.json",
        "--traffic", "benchmark/tests/data/tiny-rollout-sparse.json",
        "--rounds", "2",
    ])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["ok"] and last["rows"] == 2 * last["slots"] == 8
    assert last["nonfinite_rows"] == 0 and last["pools_nonfinite"] == [0, 0, 0]
