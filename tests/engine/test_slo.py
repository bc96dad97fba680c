"""Engine-side SLO instrumentation: per-request latency records across
the dense and paged paths, swap-stall attribution, the park/resume
continuation shape, the off switch, and the swap.commit/swap.stage
flight-recorder spans (ISSUE 9)."""

import math

import jax
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config
from areal_tpu.observability import tracing
from areal_tpu.observability.latency import (
    SLO_BUCKET_LO,
    SLO_BUCKET_RATIO,
    SLO_REL_ERROR_BOUND,
    LatencyDigest,
)

EOS = 5


@pytest.fixture(params=["dense", "paged"])
def mode(request):
    return request.param


def make_engine(mode="dense", **kw):
    cfg = tiny_config(vocab_size=64, max_position_embeddings=256)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_batch=4,
        kv_cache_len=128,
        chunk_size=8,
        sampling=SamplingParams(greedy=True),
        stop_tokens=(EOS,),
        server_name="gs-test",
    )
    if mode == "paged":
        defaults.update(
            cache_mode="paged", page_size=16, prefill_chunk_tokens=16
        )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params, **defaults), cfg, params


def submit(eng, qid, max_new=12, prompt=(7, 8, 9), metadata=None):
    eng.submit(
        APIGenerateInput(
            qid=qid,
            prompt_ids=list(prompt),
            input_ids=list(prompt),
            gconfig=GenerationHyperparameters(
                max_new_tokens=max_new, greedy=True
            ),
            metadata=metadata or {},
        )
    )


def drain(eng, max_steps=400):
    for _ in range(max_steps):
        if not eng.has_work:
            return
        eng.step()
    raise AssertionError("engine did not drain")


def test_finished_request_yields_a_complete_record(mode):
    eng, _, _ = make_engine(mode=mode)
    submit(
        eng, "s0-0", max_new=12,
        metadata={"slo_schedule_wait_s": 0.003, "workload": "chat"},
    )
    drain(eng)
    eng.drain_results()
    recs = eng.drain_slo_records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec.qid == "s0-0"
    assert rec.workload == "chat"
    assert rec.server == "gs-test" and rec.mesh_devices == 1
    assert rec.schedule_wait_s == 0.003
    assert rec.admission_wait_s >= 0.0
    assert rec.ttft_s > 0.0
    assert rec.tokens >= 2 and rec.tpot_s is not None and rec.tpot_s >= 0
    assert rec.ttft_s >= rec.admission_wait_s  # TTFT includes the queue
    assert rec.stall_s == 0.0  # no swap/preemption happened
    assert rec.complete()
    # records drained once: the deque is consumed
    assert eng.drain_slo_records() == []
    # digests observed exactly one request per family
    stats = eng.slo_stats()
    assert stats["records_total"] == 1
    for fam in ("ttft_s", "tpot_s", "admission_wait_s", "stall_s"):
        assert stats[fam]["count"] == 1, fam


def test_mid_decode_weight_swap_attributes_stall(mode):
    eng, _, params = make_engine(mode=mode)
    submit(eng, "sw0-0", max_new=64)
    for _ in range(2):
        eng.step()
    assert eng.n_inflight > 0 and eng.n_decoding > 0
    eng.update_weights(params, version=1)
    drain(eng)
    rec = eng.drain_slo_records()[0]
    assert rec.stall_s > 0.0, rec.as_dict()
    assert rec.ttft_s > 0.0 and rec.tokens >= 2


def test_slo_tracking_off_records_nothing(mode):
    eng, _, _ = make_engine(mode=mode, slo_tracking=False)
    submit(eng, "off0-0", max_new=8)
    drain(eng)
    assert eng.drain_slo_records() == []
    assert eng.slo_stats()["records_total"] == 0
    assert eng.slo_stats()["ttft_s"]["p99"] is None


def test_parked_continuation_gets_its_own_record(mode):
    """A chunked rollout: each chunk is a completed request from the
    client's view, so each produces its own record (the continuation's
    TTFT restarts at ITS submit — park-resume makes it small)."""
    eng, _, _ = make_engine(mode=mode)
    submit(eng, "pk0-0", max_new=6, prompt=(7, 8, 9))
    drain(eng)
    out = eng.drain_results()["pk0-0"]
    assert out.no_eos  # budget-exhausted: row parked for continuation
    first = eng.drain_slo_records()
    assert len(first) == 1 and first[0].tokens >= 2
    cont = list((7, 8, 9)) + list(out.output_ids)
    submit(eng, "pk0-0", max_new=6, prompt=tuple(cont))
    drain(eng)
    eng.drain_results()
    second = eng.drain_slo_records()
    assert len(second) == 1
    assert second[0].tokens >= 1
    assert second[0].ttft_s > 0.0


def test_single_token_request_has_no_tpot(mode):
    eng, _, _ = make_engine(mode=mode)
    submit(eng, "one0-0", max_new=1)
    drain(eng)
    eng.drain_results()
    recs = eng.drain_slo_records()
    assert len(recs) == 1
    assert recs[0].tokens == 1
    assert recs[0].tpot_s is None  # no inter-token gap exists
    assert eng.slo_stats()["tpot_s"]["count"] == 0
    assert eng.slo_stats()["ttft_s"]["count"] == 1


def test_group_members_each_get_a_record(mode):
    eng, _, _ = make_engine(mode=mode)
    for i in range(3):
        submit(eng, f"g0-{i}", max_new=8, prompt=(11, 12, 13, 14))
    drain(eng)
    eng.drain_results()
    recs = eng.drain_slo_records()
    assert sorted(r.qid for r in recs) == ["g0-0", "g0-1", "g0-2"]
    assert all(r.ttft_s > 0 for r in recs)


def test_weight_swap_emits_swap_commit_span(mode):
    tracer = tracing.Tracer(
        tracing.TraceConfig(sample_rate=0.0), worker="slo-test"
    )
    tracing.set_tracer(tracer)
    try:
        eng, _, params = make_engine(mode=mode)
        submit(eng, "sp0-0", max_new=64)
        for _ in range(2):
            eng.step()
        eng.update_weights(params, version=3)
        drain(eng)
    finally:
        tracing.set_tracer(None)
    spans = [
        e for e in tracer.snapshot(0)["events"]
        if e["name"] == "swap.commit"
    ]
    # sample_rate=0: only the FORCED swap root records — swaps are fleet
    # events and must never sample out
    assert len(spans) == 1
    s = spans[0]
    assert s["ph"] == "X" and s["root"] == "swap-v3"
    assert s["attrs"]["version"] == 3
    assert s["attrs"]["pre_sharded"] is False


def test_preemption_window_counts_as_stall():
    """Paged pool pressure: the preempted row's out-of-service window
    lands in its stall_s once it is re-admitted and finishes."""
    eng, _, _ = make_engine(
        mode="paged", max_batch=3, kv_cache_len=64, page_size=16,
        kv_pool_tokens=96, chunk_size=4,
    )
    for i in range(3):
        submit(eng, f"pp0-{i}", max_new=24, prompt=tuple(range(7, 19)))
    drain(eng, max_steps=2000)
    eng.drain_results()
    assert eng.preempted_total > 0, "workload did not trigger preemption"
    recs = eng.drain_slo_records()
    assert any(r.stall_s > 0 for r in recs), [r.as_dict() for r in recs]


def test_two_engines_digests_merge_within_bound_of_pooled_records(mode):
    """Fleet percentiles: the digests two engines export, merged, sit
    within the documented bound of the inverted-CDF quantiles of the
    pooled raw records, and every record names its own server."""
    engines = [
        make_engine(mode=mode, server_name=f"srv{j}")[0] for j in range(2)
    ]
    for j, eng in enumerate(engines):
        for i in range(3):
            submit(
                eng, f"s{j}-{i}", max_new=6 + 3 * i,
                prompt=(7 + i, 8, 9, 10 + j),
            )
        drain(eng)
    records = [r for eng in engines for r in eng.drain_slo_records()]
    assert sorted(r.server for r in records) == ["srv0"] * 3 + ["srv1"] * 3
    for field in ("ttft_s", "tpot_s"):
        fleet = LatencyDigest()
        for eng in engines:
            fleet.merge(LatencyDigest.from_dict(eng.slo_digests()[field]))
        raw = sorted(
            getattr(r, field)
            for r in records
            if getattr(r, field) is not None
        )
        assert fleet.count == len(raw) > 0
        for q in (0.5, 0.95, 0.99):
            emp = raw[max(0, math.ceil(q * len(raw)) - 1)]
            if emp < SLO_BUCKET_LO / SLO_BUCKET_RATIO:
                # under the covered range (a tiny engine's 28 us between
                # two tokens) a value clamps into the lowest bucket: what
                # observability/latency.py documents there
                assert fleet.quantile(q) <= SLO_BUCKET_LO, (field, q)
                continue
            assert abs(fleet.quantile(q) - emp) <= (
                SLO_REL_ERROR_BOUND * emp + 1e-12
            ), (field, q)
