"""Self-speculative decoding: exactness, bookkeeping, and subsystem
interplay (tests the engine/spec_decode.py tentpole).

The contract under test: with spec decode ON, the paged engine's greedy
output is TOKEN-IDENTICAL to spec-off decode — drafting/verification may
only change how fast tokens appear, never which tokens.  Around that
core, the file pins the acceptance bookkeeping (full accept / first-
token reject / mid-window reject via a forced drafter), the EMA fallback
that bounds the worst case, the paged-pool hygiene (no block leaks from
rejected drafts, no garbage served through the radix prefix cache), the
pause/weight-swap quiesce of in-flight verify windows, and the
position-keyed RNG satellite (same seed + different chunking/pipelining
=> identical sampled streams, the split-sequence hazard fix).
"""

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine import spec_decode
from areal_tpu.engine.batching import spec_window_bucket
from areal_tpu.engine.generation import generate_tokens
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.engine.spec_decode import SpecDecodeParams, SpecRowState
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config

EOS = 5
VOCAB = 64

_cfg = tiny_config(vocab_size=VOCAB, max_position_embeddings=256)
_params = transformer.init_params(_cfg, jax.random.PRNGKey(0))


def make_engine(spec=None, mode="paged", **kw):
    defaults = dict(
        max_batch=4,
        kv_cache_len=128,
        chunk_size=8,
        sampling=SamplingParams(greedy=True),
        stop_tokens=(EOS,),
    )
    if mode == "paged":
        defaults.update(
            cache_mode="paged", page_size=16, prefill_chunk_tokens=32
        )
    else:
        defaults.update(cache_mode="dense")
    defaults.update(kw)
    return ContinuousBatchingEngine(
        _cfg, _params, spec_decode_params=spec, **defaults
    )


def run_wave(eng, prompts, budgets, tag="q", max_steps=600):
    qids = []
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        qids.append(
            eng.submit(
                APIGenerateInput(
                    qid=f"{tag}{i}", prompt_ids=p, input_ids=p,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=b, greedy=True
                    ),
                )
            )
        )
    for _ in range(max_steps):
        if not eng.has_work:
            break
        eng.step()
    assert not eng.has_work, "engine did not drain"
    return [eng.wait_result(q, timeout=5) for q in qids]


# repetitive motifs (n-gram drafting engages) + irregular prompts
MOTIF = [7, 8, 9, 10]
PROMPTS = [
    MOTIF * 5,
    [10, 11, 12, 13, 14],
    [3, 2] * 6,
    [21, 22, 23, 24],
]
BUDGETS = [25, 9, 23, 12]

_REF_CACHE = {}


def ref_ids(prompt, budget, params=None):
    key = (tuple(prompt), budget, id(params))
    if key not in _REF_CACHE:
        _REF_CACHE[key] = generate_tokens(
            params if params is not None else _params, _cfg, [prompt],
            GenerationHyperparameters(max_new_tokens=budget, greedy=True),
            EOS, jax.random.PRNGKey(1),
        )[0]["output_ids"]
    return _REF_CACHE[key]


SPEC = SpecDecodeParams(enabled=True, max_draft_tokens=7)


# -- exactness ----------------------------------------------------------------


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_greedy_token_parity_spec_on_vs_off_paged(prefix_cache):
    """The tentpole contract: spec-on greedy output is token-identical
    to spec-off (and to the static-batch reference), with verify chunks
    genuinely dispatched."""
    on = make_engine(spec=SPEC, prefix_cache=prefix_cache)
    off = make_engine(prefix_cache=prefix_cache)
    outs_on = run_wave(on, PROMPTS, BUDGETS)
    outs_off = run_wave(off, PROMPTS, BUDGETS)
    assert on.spec_verify_chunks_total > 0  # the test is not vacuous
    assert on.spec_accepted_total > 0  # drafts genuinely accepted
    for p, b, a, o in zip(PROMPTS, BUDGETS, outs_on, outs_off):
        assert a.output_ids == o.output_ids == ref_ids(p, b)
        # logprobs agree to float32 reduction-order noise (verify runs
        # prefill-style attention; decode runs the windowed step)
        np.testing.assert_allclose(
            a.output_logprobs, o.output_logprobs, atol=1e-4
        )


def test_spec_requested_on_dense_engine_is_disabled_noop():
    eng = make_engine(spec=SPEC, mode="dense")
    assert eng._spec is None  # paged-only feature, silently off
    outs = run_wave(eng, PROMPTS, BUDGETS)
    assert eng.spec_verify_chunks_total == 0
    for p, b, o in zip(PROMPTS, BUDGETS, outs):
        assert o.output_ids == ref_ids(p, b)


def test_spec_requested_with_nongreedy_sampling_is_disabled():
    eng = make_engine(
        spec=SPEC, sampling=SamplingParams(temperature=1.0)
    )
    assert eng._spec is None  # verification is exact under greedy only


# -- acceptance bookkeeping (forced drafter) ----------------------------------


def _forced_drafter(refs, mutate):
    """A SpecRowState.draft replacement proposing ``mutate``-d slices of
    the known greedy reference streams (prompt-matched)."""

    def draft(self, history, params):
        for prompt, ref in refs.items():
            if tuple(history[: len(prompt)]) == prompt:
                pos = len(history) - len(prompt)
                cont = ref[pos : pos + params.max_draft_tokens]
                return mutate(list(cont))
        return []

    return draft


def _bookkeeping_wave(monkeypatch, mutate, prompts=None, budgets=None):
    prompts = prompts or PROMPTS[:2]
    budgets = budgets or BUDGETS[:2]
    refs = {
        tuple(p): ref_ids(p, b) for p, b in zip(prompts, budgets)
    }
    monkeypatch.setattr(
        SpecRowState, "draft", _forced_drafter(refs, mutate)
    )
    eng = make_engine(spec=SPEC)
    outs = run_wave(eng, prompts, budgets)
    for p, b, o in zip(prompts, budgets, outs):
        assert o.output_ids == ref_ids(p, b)  # parity regardless of drafts
    return eng


def test_full_accept_bookkeeping(monkeypatch):
    """Drafts equal to the true greedy continuation: every draft within
    budget is accepted (rejections only where the budget truncates the
    window)."""
    eng = _bookkeeping_wave(monkeypatch, lambda c: c)
    assert eng.spec_verify_chunks_total > 0
    assert eng.spec_accepted_total > 0
    # every non-accepted draft must be a budget/stop truncation, never a
    # mismatch: with <=7-token windows against 9-25 token budgets the
    # overwhelming majority of drafts verify
    assert eng.spec_accepted_total >= 0.7 * eng.spec_drafted_total


def test_first_token_reject_bookkeeping_and_fallback(monkeypatch):
    """Always-wrong drafts: zero acceptance, exact parity (the verifier's
    correction token IS the greedy token), and the EMA fallback trips —
    the bounded worst case."""
    eng = _bookkeeping_wave(
        monkeypatch, lambda c: [(t + 1) % VOCAB for t in c]
    )
    assert eng.spec_verify_chunks_total > 0
    assert eng.spec_accepted_total == 0
    assert eng.spec_rejected_total > 0
    assert eng.spec_fallback_rows_total >= 1


def test_mid_window_reject_bookkeeping(monkeypatch):
    """Drafts correct for two positions then wrong: acceptance truncates
    at the first divergence (longest-accepted-prefix), never beyond."""

    def mutate(c):
        return c[:2] + [(t + 1) % VOCAB for t in c[2:]]

    # single row so each verify chunk carries exactly one window and the
    # per-verify acceptance bound below is exact
    eng = _bookkeeping_wave(
        monkeypatch, mutate, prompts=PROMPTS[:1], budgets=BUDGETS[:1]
    )
    assert eng.spec_verify_chunks_total > 0
    assert 0 < eng.spec_accepted_total < eng.spec_drafted_total
    # no verify may accept past the forced divergence: accepted tokens
    # per verify <= 2
    assert eng.spec_accepted_total <= 2 * eng.spec_verify_chunks_total


# -- paged-pool + prefix-cache hygiene ----------------------------------------


def test_no_block_leak_after_rejected_drafts(monkeypatch):
    """Rejected drafts scatter garbage KV beyond the valid length; none
    of it may leak blocks: after releasing every row and flushing the
    radix cache the pool is pristine."""
    eng = _bookkeeping_wave(
        monkeypatch, lambda c: [(t + 1) % VOCAB for t in c]
    )
    for rid, row in enumerate(eng.rows):
        if row is not None:
            eng._release_row(rid)
    if eng._prefix_cache is not None:
        eng._prefix_cache.flush(new_version=99)
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()


def test_rejected_drafts_never_poison_the_prefix_cache(monkeypatch):
    """Turn 2 of a conversation whose turn 1 decoded with ALWAYS-WRONG
    drafts must reuse the cached prefix AND still match a spec-off
    replay token-for-token — rejected-draft garbage beyond the valid
    length is unreachable through the radix cache."""
    p0 = MOTIF * 5
    refs = {tuple(p0): ref_ids(p0, 20)}
    monkeypatch.setattr(
        SpecRowState, "draft",
        _forced_drafter(refs, lambda c: [(t + 1) % VOCAB for t in c]),
    )
    eng = make_engine(spec=SPEC, prefix_cache=True)
    (t1,) = run_wave(eng, [p0], [20], tag="turn1_")
    assert eng.spec_rejected_total > 0
    conv = p0 + list(t1.output_ids) + [11, 12]
    h0 = eng.prefix_cache_stats()["cached_tokens_total"]
    (t2,) = run_wave(eng, [conv], [8], tag="turn2_")
    assert eng.prefix_cache_stats()["cached_tokens_total"] > h0
    fresh = make_engine()  # spec-off, cold cache
    (t2_ref,) = run_wave(fresh, [conv], [8], tag="fresh_")
    assert t2.output_ids == t2_ref.output_ids


# -- quiesce: pause / weight swap ---------------------------------------------


def test_pause_quiesces_inflight_verify_chunks():
    eng = make_engine(spec=SPEC)
    eng.submit(APIGenerateInput(
        qid="q0", prompt_ids=MOTIF * 5, input_ids=MOTIF * 5,
        gconfig=GenerationHyperparameters(max_new_tokens=30, greedy=True),
    ))
    for _ in range(30):
        eng.step()
        if eng.spec_verify_chunks_total > 0 and eng.inflight_chunks:
            break
    assert eng.inflight_chunks >= 1
    eng.pause()
    eng.step()
    assert eng.inflight_chunks == 0  # verify windows drain like chunks
    eng.resume()
    for _ in range(300):
        if not eng.has_work:
            break
        eng.step()
    out = eng.wait_result("q0", timeout=5)
    assert out.output_ids == ref_ids(MOTIF * 5, 30)


def test_weight_swap_mid_verify_emits_nothing_stale():
    """Swap weights while a verify window is in flight: the window folds
    in under v0, the continuation decodes under v1 — the output splits
    cleanly into a v0-greedy prefix and a v1-greedy tail."""
    eng = make_engine(spec=SPEC)
    prompt = MOTIF * 5
    qid = eng.submit(APIGenerateInput(
        qid="q0", prompt_ids=prompt, input_ids=prompt,
        gconfig=GenerationHyperparameters(max_new_tokens=24, greedy=True),
    ))
    for _ in range(30):
        eng.step()
        if eng.spec_verify_chunks_total > 0 and eng.inflight_chunks:
            break
    assert eng.inflight_chunks >= 1
    params2 = transformer.init_params(_cfg, jax.random.PRNGKey(42))
    assert eng.update_weights(params2, version=1) == 1
    for _ in range(400):
        if not eng.has_work:
            break
        eng.step()
    out = eng.wait_result(qid, timeout=5)
    assert out.version_start == 0 and out.version_end == 1
    v0 = ref_ids(prompt, 24)
    got = list(out.output_ids)
    split = None
    for k in range(len(got) + 1):
        if got[:k] != v0[:k]:
            break
        tail = generate_tokens(
            params2, _cfg, [prompt + got[:k]],
            GenerationHyperparameters(
                max_new_tokens=max(len(got) - k, 1), greedy=True
            ),
            EOS, jax.random.PRNGKey(2),
        )[0]["output_ids"]
        if got[k:] == tail[: len(got) - k]:
            split = k
            break
    assert split is not None, (got, v0)
    assert 0 < split < len(got)


# -- position-keyed RNG (satellite: the split-sequence hazard fix) ------------


# temperature-only: top-p/top-k cutoffs sit on sorted-prob cliffs where
# the ~1e-7 reduction-order noise between chunk layouts can flip the
# FILTERED SET at a near-tie; the position-keyed draws themselves are
# chunking-invariant, and without cliffs so is the sampled stream
TEMP_SAMPLING = SamplingParams(temperature=0.8)


def _temp_wave(mode, chunk_size, pipeline_depth, seed=3):
    eng = make_engine(
        spec=None, mode=mode, chunk_size=chunk_size,
        pipeline_depth=pipeline_depth, sampling=TEMP_SAMPLING, seed=seed,
    )
    outs = run_wave(eng, PROMPTS, [12, 9, 11, 10], tag=f"t{mode}_")
    return [o.output_ids for o in outs]


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_rng_stream_invariant_to_chunk_size(mode):
    """Same seed, different chunking => identical sampled tokens: the
    draw for (row, position) is keyed on exactly that, never on how many
    chunk dispatches produced the position."""
    assert _temp_wave(mode, 4, 2) == _temp_wave(mode, 8, 2)


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_rng_stream_invariant_to_pipeline_depth(mode):
    assert _temp_wave(mode, 4, 1) == _temp_wave(mode, 4, 3)


def test_rng_streams_differ_across_seeds_and_rows():
    """Sanity: position-keying must not collapse randomness — different
    seeds give different streams, and group rows at identical positions
    draw independently."""
    a = _temp_wave("paged", 4, 2, seed=3)
    b = _temp_wave("paged", 4, 2, seed=4)
    assert a != b
    eng = make_engine(spec=None, sampling=TEMP_SAMPLING)
    outs = run_wave(
        eng, [PROMPTS[0], PROMPTS[0]], [12, 12], tag="grp"
    )
    assert outs[0].output_ids != outs[1].output_ids


def test_rng_slot_reuse_does_not_duplicate_same_prompt_streams():
    """Draws are keyed per REQUEST, not per cache-row slot: a 1-row
    engine serving the same prompt twice (the second request lands in
    the slot the first just freed — a GRPO sibling's shape) must draw an
    independent stream, while re-running the SAME request id reproduces
    its stream exactly."""
    p = PROMPTS[0]
    eng = make_engine(
        spec=None, mode="dense", max_batch=1, sampling=TEMP_SAMPLING
    )
    (a,) = run_wave(eng, [p], [12], tag="reqA_")
    (b,) = run_wave(eng, [p], [12], tag="reqB_")
    assert a.output_ids != b.output_ids  # slot reuse, fresh randomness
    fresh = make_engine(
        spec=None, mode="dense", max_batch=1, sampling=TEMP_SAMPLING
    )
    (a2,) = run_wave(fresh, [p], [12], tag="reqA_")
    assert a2.output_ids == a.output_ids  # same request id, same stream


# -- drafter / dispatch units -------------------------------------------------


def test_ngram_drafter_chains_through_periodic_history():
    st = SpecRowState()
    hist = [1, 2, 3, 4] * 6  # period 4
    d = st.draft(hist, SPEC)
    # the chained lookup walks the cycle to the full window, not just to
    # the most recent occurrence's (1-token) tail gap
    assert d == ([1, 2, 3, 4] * 2)[: SPEC.max_draft_tokens]


def test_ngram_drafter_no_repeat_returns_empty_and_cools_down():
    st = SpecRowState()
    d = st.draft(list(range(20)), SPEC)  # no n-gram recurs
    assert d == []
    st.note_draft_result(False, step_seq=10)
    st.note_draft_result(False, step_seq=11)
    assert not st.wants_draft(11)  # exponential draft-miss backoff
    assert st.wants_draft(11 + 65)  # cooldown is bounded


def test_vote_losing_drafter_cools_down_and_keeps_the_pipeline():
    """A row whose drafts keep HITTING while the batch vote keeps
    picking plain decode must back off like a draft-miss row — else it
    would force the ring quiesce (pipeline depth 1 + a host sync) every
    single step for zero verify chunks."""
    eng = make_engine(
        spec=SpecDecodeParams(
            enabled=True, max_draft_tokens=7,
            verify_cost_over_decode_step=100.0,  # vote can never win
        )
    )
    qids = []
    for i, (p, b) in enumerate(zip(PROMPTS, BUDGETS)):
        qids.append(eng.submit(APIGenerateInput(
            qid=f"vl{i}", prompt_ids=p, input_ids=p,
            gconfig=GenerationHyperparameters(
                max_new_tokens=b, greedy=True
            ),
        )))
    for _ in range(10):  # mid-wave: rows still live
        eng.step()
    states = [
        r.spec for r in eng.rows
        if r is not None and r.spec is not None
    ]
    assert states
    assert any(s.cooldown_until > 0 for s in states)  # backed off
    for _ in range(600):
        if not eng.has_work:
            break
        eng.step()
    assert eng.spec_verify_chunks_total == 0  # plain decode throughout
    for qid, p, b in zip(qids, PROMPTS, BUDGETS):
        assert eng.wait_result(qid, timeout=5).output_ids == ref_ids(p, b)


def test_ngram_drafter_index_is_incremental():
    st = SpecRowState()
    hist = [1, 2, 3, 1, 2]
    assert st.draft(hist, SPEC)[:1] == [3]  # bigram (1,2) -> 3
    hist2 = hist + [3, 9, 9, 1, 2]
    d = st.draft(hist2, SPEC)
    assert d[:1] == [3]  # extended history, most recent occurrence wins


def test_ema_observe_and_fallback_threshold():
    p = SpecDecodeParams(
        enabled=True, min_accept_rate=0.5, ema_decay=0.5,
        warmup_verifies=2,
    )
    st = SpecRowState()
    assert not st.observe(0, 4, p)  # warmup: cannot trip yet
    tripped = st.observe(0, 4, p)  # ema = 0.25 < 0.5, verifies = 2
    assert tripped and st.fallback
    assert not st.observe(0, 4, p)  # counted once only


def test_spec_window_bucket_and_break_even():
    assert spec_window_bucket(2) == 2
    assert spec_window_bucket(3) == 4
    assert spec_window_bucket(8) == 8
    assert spec_window_bucket(9) == 16


def test_resolve_spec_params_defaults_and_disable():
    from areal_tpu.api.system_api import SpecDecodeConfig
    from areal_tpu.engine.spec_decode import (
        DEFAULT_SPEC_MIN_ACCEPT_RATE,
        DEFAULT_SPEC_VERIFY_COST,
    )

    assert spec_decode.resolve_spec_params(None) is None
    assert spec_decode.resolve_spec_params(SpecDecodeConfig()) is None
    p = spec_decode.resolve_spec_params(SpecDecodeConfig(enabled=True))
    assert p.enabled and p.max_draft_tokens == 7
    assert p.min_accept_rate == DEFAULT_SPEC_MIN_ACCEPT_RATE
    assert p.verify_cost_over_decode_step == DEFAULT_SPEC_VERIFY_COST
    p2 = spec_decode.resolve_spec_params(
        SpecDecodeConfig(enabled=True, min_accept_rate=0.4)
    )
    assert p2.min_accept_rate == 0.4
