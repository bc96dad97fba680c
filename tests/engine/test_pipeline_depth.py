"""K-deep in-flight decode ring: correctness across pipeline depths.

The serving engine dispatches up to ``pipeline_depth`` decode chunks
before harvesting the oldest, with every chunk's output fetch started
async at dispatch time.  TPU benches measure whether that hides the
fetch RTT; THIS file is the CPU tier-1 gate that the ring cannot buy
throughput with correctness:

* K=1 (unpipelined, trivially correct) and K>=2 must be token-for-token
  identical under greedy sampling — ring ordering + harvest identity;
* pause() must quiesce the WHOLE ring, not one chunk;
* a weight swap mid-ring must fold every in-flight chunk in under the
  old weights and emit nothing stale after the swap;
* rows admitted while the ring is full must still be dispatched (the
  generalized ``_worth_dispatching`` epoch-count logic);
* cache_mode="auto" goes paged at ``PAGED_MIN_CACHE_LEN``.
"""

import jax
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine.generation import generate_tokens
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config

EOS = 5


def make_engine(mode="dense", pipeline_depth=2, params=None, **kw):
    cfg = tiny_config(vocab_size=64, max_position_embeddings=256)
    if params is None:
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_batch=4,
        kv_cache_len=128,
        chunk_size=4,
        sampling=SamplingParams(greedy=True),
        stop_tokens=(EOS,),
        pipeline_depth=pipeline_depth,
    )
    if mode == "paged":
        defaults.update(
            cache_mode="paged", page_size=16, prefill_chunk_tokens=16
        )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params, **defaults), cfg, params


def run_until_done(eng, max_steps=400):
    for _ in range(max_steps):
        if not eng.has_work:
            return
        eng.step()
    raise AssertionError("engine did not drain")


PROMPTS = [[7, 8, 9], [10, 11, 12, 13, 14], [3, 2], [21, 22, 23, 24]]
BUDGETS = [17, 9, 23, 5]  # staggered so rows finish mid-ring

# waves and reference streams are deterministic (greedy, fixed seeds), so
# tests comparing across (mode, K) pairs share one run each instead of
# re-decoding — keeps the tier-1 wall cost of the K sweep flat
_WAVE_CACHE = {}
_REF_CACHE = {}


def _ref_ids(params, cfg, prompt, budget):
    key = (tuple(prompt), budget)
    if key not in _REF_CACHE:
        _REF_CACHE[key] = generate_tokens(
            params, cfg, [prompt],
            GenerationHyperparameters(max_new_tokens=budget, greedy=True),
            EOS, jax.random.PRNGKey(1),
        )[0]
    return _REF_CACHE[key]


def _run_wave(mode, K):
    if (mode, K) in _WAVE_CACHE:
        return _WAVE_CACHE[(mode, K)]
    eng, cfg, params = make_engine(mode=mode, pipeline_depth=K)
    qids = []
    for i, (p, b) in enumerate(zip(PROMPTS, BUDGETS)):
        qids.append(
            eng.submit(
                APIGenerateInput(
                    qid=f"q{i}", prompt_ids=p, input_ids=p,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=b, greedy=True
                    ),
                )
            )
        )
    max_seen = 0
    for _ in range(400):
        if not eng.has_work:
            break
        eng.step()
        max_seen = max(max_seen, eng.inflight_chunks)
        assert eng.inflight_chunks <= K  # ring bounded by pipeline_depth
    assert not eng.has_work
    outs = [eng.wait_result(q, timeout=5) for q in qids]
    _WAVE_CACHE[(mode, K)] = (eng, cfg, params, outs, max_seen)
    return _WAVE_CACHE[(mode, K)]


@pytest.mark.parametrize("mode", ["dense", "paged"])
@pytest.mark.parametrize("K", [1, 2, 3])
def test_ring_token_parity_with_reference(mode, K):
    """Every pipeline depth must emit exactly the unpipelined reference
    stream, in sequence order, across rows finishing at different times
    (ring ordering + (row_id, epoch) harvest identity)."""
    eng, cfg, params, outs, max_seen = _run_wave(mode, K)
    if K > 1:
        # between steps the ring carries K-1 in-flight chunks (the K-th
        # slot exists only transiently inside a step, between dispatch
        # and the harvest of the oldest)
        assert max_seen >= K - 1
    for p, b, out in zip(PROMPTS, BUDGETS, outs):
        assert out.output_ids == _ref_ids(params, cfg, p, b)["output_ids"], (
            p, b,
        )


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_k1_vs_k2_exact_parity(mode):
    """The satellite contract: K=1 and K=2 token-for-token identical."""
    outs1 = _run_wave(mode, 1)[3]
    outs2 = _run_wave(mode, 2)[3]
    for o1, o2 in zip(outs1, outs2):
        assert o1.output_ids == o2.output_ids
        assert o1.output_logprobs == o2.output_logprobs


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_pause_drains_whole_ring(mode):
    eng, cfg, params = make_engine(mode=mode, pipeline_depth=3)
    eng.submit(
        APIGenerateInput(
            qid="q0", prompt_ids=[7, 8, 9], input_ids=[7, 8, 9],
            gconfig=GenerationHyperparameters(
                max_new_tokens=40, greedy=True
            ),
        )
    )
    for _ in range(20):
        eng.step()
        if eng.inflight_chunks >= 2:
            break
    assert eng.inflight_chunks >= 2  # ring genuinely occupied
    eng.pause()
    eng.step()
    # one paused step quiesces EVERY dispatched chunk, not just one
    assert eng.inflight_chunks == 0
    eng.resume()
    run_until_done(eng)
    out = eng.wait_result("q0", timeout=5)
    ref = generate_tokens(
        params, cfg, [[7, 8, 9]],
        GenerationHyperparameters(max_new_tokens=40, greedy=True),
        EOS, jax.random.PRNGKey(1),
    )[0]
    assert out.output_ids == ref["output_ids"]


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_weight_swap_mid_ring_emits_nothing_stale(mode):
    """Swap weights while the ring holds multiple in-flight chunks: all
    of them fold in (computed under v0), then the continuation decodes
    under v1 — the whole output must split cleanly into a v0-greedy
    prefix and a v1-greedy tail, with no stale chunk emitted after the
    swap point."""
    eng, cfg, params = make_engine(mode=mode, pipeline_depth=3, chunk_size=2)
    prompt = [7, 8, 9]
    qid = eng.submit(
        APIGenerateInput(
            qid="q0", prompt_ids=prompt, input_ids=prompt,
            gconfig=GenerationHyperparameters(
                max_new_tokens=24, greedy=True
            ),
        )
    )
    for _ in range(20):
        eng.step()
        if eng.inflight_chunks >= 2:
            break
    assert eng.inflight_chunks >= 2
    params2 = transformer.init_params(cfg, jax.random.PRNGKey(42))
    assert eng.update_weights(params2, version=1) == 1
    run_until_done(eng)
    out = eng.wait_result(qid, timeout=5)
    assert out.version_start == 0 and out.version_end == 1

    ref_v0 = generate_tokens(
        params, cfg, [prompt],
        GenerationHyperparameters(max_new_tokens=24, greedy=True),
        EOS, jax.random.PRNGKey(1),
    )[0]["output_ids"]
    got = list(out.output_ids)
    # find the swap point: the longest v0-greedy prefix, whose v1-greedy
    # continuation reproduces the tail exactly
    split = None
    for k in range(len(got) + 1):
        if got[:k] != ref_v0[:k]:
            break
        tail = generate_tokens(
            params2, cfg, [prompt + got[:k]],
            GenerationHyperparameters(
                max_new_tokens=max(len(got) - k, 1), greedy=True
            ),
            EOS, jax.random.PRNGKey(2),
        )[0]["output_ids"]
        if got[k:] == tail[: len(got) - k]:
            split = k
            break
    assert split is not None, (got, ref_v0)
    # chunks were genuinely in flight at the swap, so v0 emitted some
    # tokens before it; and the v1 tail is non-empty (work continued)
    assert 0 < split < len(got)


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_admit_mid_ring_gets_dispatched(mode):
    """A request admitted while the ring is full of chunks that predate
    it has (row_id, epoch) in NO snapshot; the generalized
    _worth_dispatching must count it alive and keep dispatching until it
    finishes with the correct greedy stream."""
    eng, cfg, params = make_engine(mode=mode, max_batch=2, pipeline_depth=3)
    long_p, short_p = [11, 12, 13], [7, 8]
    eng.submit(APIGenerateInput(
        qid="long", prompt_ids=long_p, input_ids=long_p,
        gconfig=GenerationHyperparameters(max_new_tokens=40, greedy=True),
    ))
    for _ in range(10):
        eng.step()
        if eng.inflight_chunks == 2:
            break
    assert eng.inflight_chunks == 2  # ring full between steps (K-1)
    eng.submit(APIGenerateInput(
        qid="short", prompt_ids=short_p, input_ids=short_p,
        gconfig=GenerationHyperparameters(max_new_tokens=6, greedy=True),
    ))
    run_until_done(eng)
    for qid, p, b in (("long", long_p, 40), ("short", short_p, 6)):
        out = eng.wait_result(qid, timeout=5)
        ref = generate_tokens(
            params, cfg, [p],
            GenerationHyperparameters(max_new_tokens=b, greedy=True),
            EOS, jax.random.PRNGKey(1),
        )[0]
        assert out.output_ids == ref["output_ids"], qid


@pytest.mark.parametrize("mode", ["dense", "paged"])
def test_async_fetch_counters(mode):
    eng, cfg, params = make_engine(mode=mode, pipeline_depth=2)
    eng.submit(APIGenerateInput(
        qid="q0", prompt_ids=[7, 8, 9], input_ids=[7, 8, 9],
        gconfig=GenerationHyperparameters(max_new_tokens=20, greedy=True),
    ))
    run_until_done(eng)
    # every dispatched chunk started an async output copy and was
    # harvested exactly once; readiness hits are bounded by harvests
    assert eng.chunks_total > 0
    assert eng.async_fetches_total == eng.chunks_total
    assert 0 <= eng.fetch_ready_total <= eng.chunks_total
    assert eng.inflight_chunks == 0


# -- cache_mode="auto" --------------------------------------------------------


@pytest.mark.parametrize(
    "kv_cache_len, paged", [(2047, False), (2048, True)]
)
def test_auto_mode_goes_paged_at_the_constant(kv_cache_len, paged):
    cfg = tiny_config(vocab_size=64, max_position_embeddings=4096)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(
        cfg, params, cache_mode="auto", max_batch=1,
        kv_cache_len=kv_cache_len, chunk_size=4,
    )
    assert eng.paged is paged
