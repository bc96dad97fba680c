"""Engine-side gateway plumbing: per-request stream buffers fed at
chunk-fold time (bounded, drop-accounted), the cancel lifecycle across
every state a request can be in (pending / mid-decode / finished), the
stale-stream backstop, and priority-aware pool-pressure preemption
(bulk evicted before interactive, with stream continuity across the
eviction)."""

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config


def make_engine(**kw):
    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_batch=2,
        kv_cache_len=128,
        chunk_size=4,
        sampling=SamplingParams(greedy=True),
        cache_mode="paged",
        page_size=16,
    )
    defaults.update(kw)
    eng = ContinuousBatchingEngine(cfg, params, **defaults)
    eng.park_ttl_steps = 0
    return eng


def _req(qid, prompt, max_new, **metadata):
    return APIGenerateInput(
        qid=qid, prompt_ids=list(prompt), input_ids=list(prompt),
        gconfig=GenerationHyperparameters(
            max_new_tokens=max_new, greedy=True
        ),
        metadata=metadata or None,
    )


def run_until_done(eng, drain_into=None, qid=None, max_steps=500):
    for _ in range(max_steps):
        if not eng.has_work:
            return
        eng.step()
        if drain_into is not None:
            drain_into.extend(eng.drain_stream(qid) or [])
    raise AssertionError("engine did not drain")


def assert_pool_pristine(eng):
    eng.step()
    eng.step()  # TTL eviction of parked rows
    if getattr(eng, "_prefix_cache", None) is not None:
        eng._prefix_cache.flush()
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()


def test_stream_delivers_every_token_exactly_once():
    eng = make_engine()
    eng.submit(_req("s1", [7, 8, 9], 16, stream=True))
    eng.submit(_req("plain", [3, 4, 5], 8))  # no stream opened
    assert eng.stream_stats()["opened_total"] == 1
    acc = []
    run_until_done(eng, drain_into=acc, qid="s1")
    acc.extend(eng.drain_stream("s1") or [])
    out = eng.drain_results()
    # interleaved drains reassemble the exact output, no drop, no dup
    assert acc == list(out["s1"].output_ids)
    # a non-streaming request never grew a buffer
    assert eng.drain_stream("plain") is None
    # close tears the buffer down; later drains report unknown
    eng.stream_close("s1")
    assert eng.drain_stream("s1") is None
    assert eng.stream_stats()["open_streams"] == 0


def test_stream_buffer_is_bounded_with_drop_accounting():
    eng = make_engine()
    eng.stream_buffer_cap = 4  # read at submit: deque(maxlen=cap)
    eng.submit(_req("s1", [7, 8, 9], 16, stream=True))
    run_until_done(eng)  # nobody drains: the buffer overflows
    tail = eng.drain_stream("s1")  # before drain_results prunes it
    out = eng.drain_results()["s1"]
    # undrained stream kept the LAST cap tokens and counted the rest
    assert tail == list(out.output_ids)[-4:]
    st = eng.stream_stats()
    assert st["dropped_tokens_total"] == len(out.output_ids) - 4


def test_cancel_releases_blocks_in_every_lifecycle_state():
    eng = make_engine(max_batch=4)
    # pending: cancelled before any step touches the device
    eng.submit(_req("pend", [11, 12, 13], 8, stream=True))
    assert eng.cancel("pend") is True
    # mid-decode: cancelled while actively holding pool blocks
    eng.submit(_req("mid", [7, 8, 9], 64, stream=True))
    eng.step()
    eng.step()
    assert eng.cancel("mid") is True
    # finished-but-uncollected: result + stream swept
    eng.submit(_req("done", [3, 4, 5], 4))
    run_until_done(eng)
    assert eng.cancel("done") is True
    assert eng.try_get_result("done") is None
    # unknown qid is a no-op, not an error
    assert eng.cancel("never-existed") is False
    assert eng.cancelled_total == 3
    assert eng.stream_stats()["open_streams"] == 0
    # the audit the gateway's disconnect path rides on: nothing leaked
    assert_pool_pristine(eng)
    # and the engine still serves fresh traffic afterwards
    eng.submit(_req("after", [21, 22], 4))
    run_until_done(eng)
    assert len(eng.drain_results()["after"].output_ids) == 4


@pytest.mark.slow  # dedicated engine build for the stale-clock arm
def test_stale_stream_backstop_names_undrained_streams():
    eng = make_engine()
    eng.stream_stale_steps = 2
    eng.submit(_req("ghost", [7, 8, 9], 64, stream=True))
    for _ in range(5):
        eng.step()
    # nobody drained for > stream_stale_steps engine steps: the leader
    # turns this into a cancel command (dead-gateway-client backstop)
    assert "ghost" in eng.stale_stream_qids()
    assert eng.cancel("ghost") is True
    assert eng.stale_stream_qids() == []
    assert_pool_pristine(eng)
    # a drained stream never goes stale
    eng.submit(_req("live", [3, 4, 5], 32, stream=True))
    for _ in range(5):
        eng.step()
        eng.drain_stream("live")
    assert eng.stale_stream_qids() == []


@pytest.mark.slow  # pool-pressure preemption needs a long decode
def test_priority_aware_preemption_evicts_bulk_before_interactive():
    # 6 blocks: either row alone fits (prompt+48 new <= 96 pool
    # tokens), both together do not — admitting the interactive row
    # forces exactly the preemption decision under test
    eng = make_engine(
        kv_cache_len=96, kv_pool_tokens=96, page_size=16, chunk_size=4
    )
    eng.submit(_req(
        "gw-bulk", list(range(6, 30)), 48,
        workload="rollout", priority_class="bulk",
    ))
    eng.step()
    eng.submit(_req(
        "gw-int", [7, 8, 9, 10, 11, 12], 48,
        workload="chat", priority_class="interactive", stream=True,
    ))
    acc = []
    run_until_done(eng, drain_into=acc, qid="gw-int", max_steps=2000)
    acc.extend(eng.drain_stream("gw-int") or [])
    out = eng.drain_results()
    # the victim choice: bulk yielded, interactive never evicted
    assert eng.preempted_by_class.get("bulk", 0) >= 1
    assert eng.preempted_by_class.get("interactive", 0) == 0
    # both still complete (the bulk row resumed after the eviction)
    assert len(out["gw-bulk"].output_ids) == 48
    # stream continuity across pool pressure: the interactive stream
    # saw every token exactly once
    assert acc == list(out["gw-int"].output_ids)
    assert_pool_pristine(eng)
