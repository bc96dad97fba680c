"""The HTTP/SSE front door, end to end over a real socket: OpenAI-dialect
framing conformance (``data:`` frames, final usage block, ``[DONE]``
sentinel), token-stream parity between the streaming and non-streaming
paths, structured 429/403 admission rejects, and the disconnect /
mid-stream-weight-swap lifecycle guarantees (zero leaked blocks, no
dropped or duplicated tokens).

Tier-1 keeps one streaming smoke and one reject smoke (ISSUE budget
discipline); the disconnect-leak and weight-swap arms are ``slow``."""

import http.client
import json
import socket
import time

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.gateway import sse
from areal_tpu.gateway.admission import AdmissionPlane, TenantPolicy
from areal_tpu.gateway.server import (
    EngineBackend,
    GatewayServer,
    run_request,
)
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config

PROMPT = [7, 8, 9, 10]


def make_engine(**kw):
    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_batch=2,
        kv_cache_len=128,
        chunk_size=4,
        # greedy is ENGINE-level (per-request gconfig.greedy is not a
        # sampler input) — required for stream-vs-sync token parity
        sampling=SamplingParams(greedy=True),
        cache_mode="paged",
        page_size=16,
        prefix_cache=False,  # bit-identical prefills for parity checks
    )
    defaults.update(kw)
    eng = ContinuousBatchingEngine(cfg, params, **defaults)
    eng.park_ttl_steps = 0  # parked rows would hold blocks past finish
    return eng, cfg, params


def assert_pool_pristine(eng):
    eng.step()
    eng.step()  # TTL eviction of parked rows
    if getattr(eng, "_prefix_cache", None) is not None:
        eng._prefix_cache.flush()
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()


# -- SSE framing conformance (pure) ------------------------------------------


def test_sse_frames_round_trip_through_the_parser():
    import io

    payloads = [{"a": 1}, {"choices": [{"token_ids": [1, 2]}]}]
    wire = b"".join(sse.sse_frame(p) for p in payloads) + sse.sse_done()
    got = list(sse.iter_sse_events(io.BytesIO(wire)))
    assert got == payloads + [sse.DONE_SENTINEL]
    # each frame is data:-prefixed and blank-line terminated
    assert wire.startswith(b"data: ") and wire.endswith(b"\n\n")
    assert sse.sse_done() == b"data: [DONE]\n\n"


def test_byte_codec_round_trips_text():
    ids = sse.encode_text("hello, gaéway", vocab_size=256)
    assert sse.decode_tokens(ids) == "hello, gaéway"
    # out-of-range ids render as placeholders, never raise
    assert sse.decode_tokens([300]) == "<300>"
    assert sse.usage_block(3, 5) == {
        "prompt_tokens": 3, "completion_tokens": 5, "total_tokens": 8,
    }


# -- HTTP smoke (tier-1) ------------------------------------------------------


@pytest.fixture(scope="module")
def gateway():
    eng, cfg, params = make_engine()
    plane = AdmissionPlane([
        # reject-smoke tenants: "limited" trips the bucket on its 2nd
        # request, "capped" can never afford one request
        TenantPolicy(name="limited", priority="interactive",
                     rate_tokens_per_s=1e-6, burst_tokens=16.0),
        TenantPolicy(name="capped", priority="interactive",
                     token_budget=5.0),
    ])
    backend = EngineBackend({"eng0": eng}, plane=plane)
    backend.start_pump()
    gw = GatewayServer(backend, port=0, vocab_size=cfg.vocab_size)
    gw.start()
    host, port = gw.address.split(":")
    yield {"gw": gw, "backend": backend, "eng": eng,
           "host": host, "port": int(port), "params": params}
    gw.shutdown()
    backend.stop_pump()


def _post(g, path, body, headers=()):
    conn = http.client.HTTPConnection(g["host"], g["port"], timeout=60)
    conn.request(
        "POST", path, json.dumps(body),
        {"Content-Type": "application/json", **dict(headers or {})},
    )
    return conn, conn.getresponse()


def test_sse_stream_conforms_and_matches_non_streaming(gateway):
    body = {"prompt": PROMPT, "max_tokens": 8, "stream": True}
    conn, resp = _post(gateway, "/v1/completions", body)
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    events = list(sse.iter_sse_events(resp))
    conn.close()
    assert events[-1] == sse.DONE_SENTINEL
    frames = events[:-1]
    # every content frame carries incremental token_ids; only the FINAL
    # frame carries finish_reason + usage
    streamed = []
    for f in frames[:-1]:
        c = f["choices"][0]
        assert c["finish_reason"] is None
        assert c["token_ids"]
        streamed.extend(c["token_ids"])
    last = frames[-1]
    assert last["choices"][0]["finish_reason"] in ("stop", "length")
    assert last["usage"] == sse.usage_block(len(PROMPT), len(streamed))
    assert len(streamed) >= 1

    # token-stream parity: the SSE concat equals the non-streaming
    # response for the same prompt (greedy engine, prefix cache off)
    conn2, resp2 = _post(
        gateway, "/v1/completions",
        {"prompt": PROMPT, "max_tokens": 8},
    )
    assert resp2.status == 200
    sync = json.loads(resp2.read())
    conn2.close()
    assert sync["object"] == "text_completion"
    assert sync["choices"][0]["token_ids"] == streamed
    assert sync["usage"]["completion_tokens"] == len(streamed)

    # chat dialect: same engine path, message-shaped response
    conn3, resp3 = _post(
        gateway, "/v1/chat/completions",
        {"messages": [{"role": "user", "content": PROMPT}],
         "max_tokens": 8},
    )
    assert resp3.status == 200
    chat = json.loads(resp3.read())
    conn3.close()
    assert chat["choices"][0]["message"]["role"] == "assistant"
    assert chat["choices"][0]["token_ids"] == streamed


def test_admission_rejects_surface_as_structured_429_and_403(gateway):
    body = {"prompt": PROMPT, "max_tokens": 8}  # 12-token estimate
    # first request fits the 16-token burst...
    conn, resp = _post(gateway, "/v1/completions", body,
                       {"x-tenant": "limited"})
    assert resp.status == 200
    resp.read()
    conn.close()
    # ...the second trips the bucket: 429 + Retry-After + typed body
    conn, resp = _post(gateway, "/v1/completions", body,
                       {"x-tenant": "limited"})
    assert resp.status == 429
    assert int(resp.getheader("Retry-After")) >= 1
    err = json.loads(resp.read())["error"]
    conn.close()
    assert err["type"] == "rate_limited"
    assert err["retry_after_s"] > 0
    # budget exhaustion: structured 403, no Retry-After
    conn, resp = _post(gateway, "/v1/completions", body,
                       {"x-tenant": "capped"})
    assert resp.status == 403
    assert resp.getheader("Retry-After") is None
    err = json.loads(resp.read())["error"]
    conn.close()
    assert err["type"] == "budget_exhausted"
    # malformed input stays a 400, never a 500
    conn = http.client.HTTPConnection(gateway["host"], gateway["port"],
                                      timeout=60)
    conn.request("POST", "/v1/completions", "{not json",
                 {"Content-Type": "application/json"})
    assert conn.getresponse().status == 400
    conn.close()


# -- lifecycle arms (slow) ----------------------------------------------------


@pytest.mark.slow  # dedicated engine build + socket teardown timing
def test_client_disconnect_cancels_row_with_zero_leaked_blocks():
    eng, cfg, _ = make_engine(kv_cache_len=256)
    backend = EngineBackend({"eng0": eng})
    backend.start_pump()
    gw = GatewayServer(backend, port=0, vocab_size=cfg.vocab_size)
    gw.start()
    host, port = gw.address.split(":")
    try:
        raw = socket.create_connection((host, int(port)), timeout=60)
        body = json.dumps({
            "prompt": PROMPT, "max_tokens": 192, "stream": True,
        }).encode()
        raw.sendall(
            b"POST /v1/completions HTTP/1.0\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body
        )
        raw.recv(256)  # stream is live (headers + first bytes arrived)
        raw.close()  # client walks away mid-stream
        deadline = time.monotonic() + 60
        while eng.cancelled_total == 0:
            assert time.monotonic() < deadline, "disconnect never cancelled"
            time.sleep(0.02)
    finally:
        gw.shutdown()
        backend.stop_pump()
    assert eng.cancelled_total >= 1
    assert eng.stream_stats()["open_streams"] == 0
    # the leak audit: the cancelled row released every block it pinned
    assert_pool_pristine(eng)


@pytest.mark.slow  # dedicated engine build
def test_mid_stream_weight_swap_never_drops_or_duplicates_a_token():
    eng, _, params = make_engine()
    backend = EngineBackend({"eng0": eng})
    swapped = []
    chunks = []

    def on_chunk(toks):
        chunks.append(list(toks))
        if not swapped:
            # same tree under a bumped version: the swap machinery runs
            # (pause, KV recompute) without perturbing greedy tokens
            eng.update_weights(params, version=eng.version + 1)
            swapped.append(True)

    inp = APIGenerateInput(
        qid="swap-stream", prompt_ids=PROMPT, input_ids=PROMPT,
        gconfig=GenerationHyperparameters(max_new_tokens=32, greedy=True),
    )
    out = run_request(
        backend, inp, "chat", "interactive", stream=True,
        on_chunk=on_chunk, pump=backend.pump_once,
    )
    assert swapped, "weight swap never fired"
    streamed = [t for c in chunks for t in c]
    # the whole point: stream concat == final result, exactly once each
    assert streamed == out["result"]["output_ids"]
    assert out["result"]["version_end"] == eng.version
    assert_pool_pristine(eng)


# -- admission under a bulk storm, and the three read paths -------------------


def _ginp(qid, ids, max_new):
    return APIGenerateInput(
        qid=qid, prompt_ids=list(ids), input_ids=list(ids),
        gconfig=GenerationHyperparameters(
            max_new_tokens=max_new, greedy=True
        ),
    )


PROMPT_LEN, BULK_NEW, INTER_NEW, N_BULK, N_INTER = 16, 64, 8, 4, 4


def _storm_then_burst(plane):
    """A bulk storm claims a 2-engine fleet's rows, then an interactive
    burst streams in.  Returns the bulk admissions and rejects, each
    interactive stream's first-token step (counted in ``pump_once``
    rounds after the burst: steps, never seconds), the streams and the
    engines."""
    engines = {f"srv{j}": make_engine()[0] for j in range(2)}
    backend = EngineBackend(engines, plane=plane)
    rng = np.random.default_rng(0)

    def prompt():
        return rng.integers(6, 60, (PROMPT_LEN,)).tolist()

    admitted, rejects = 0, {}
    for i in range(N_BULK):
        dec, _ = backend.admit_and_submit(
            _ginp(f"bulk{i}", prompt(), BULK_NEW), "bulk_load",
            float(PROMPT_LEN + BULK_NEW), False,
        )
        if dec["ok"]:
            admitted += 1
        else:
            rejects[dec["reason"]] = rejects.get(dec["reason"], 0) + 1
    for _ in range(3):  # the storm settles into its rows
        backend.pump_once()
    inter_est = float(PROMPT_LEN + INTER_NEW)
    handles = {}
    for i in range(N_INTER):
        dec, h = backend.admit_and_submit(
            _ginp(f"int{i}", prompt(), INTER_NEW), "interactive",
            inter_est, True,
        )
        assert dec["ok"], dec
        handles[f"int{i}"] = h
    first_step, streams, done = {}, {q: [] for q in handles}, set()
    for step in range(1, 2000):
        backend.pump_once()
        for qid, h in handles.items():
            if qid in done:
                continue
            r = backend.poll(h)
            if r["tokens"]:
                first_step.setdefault(qid, step)
                streams[qid].extend(r["tokens"])
            if r["done"]:
                done.add(qid)
                backend.finish(
                    h, PROMPT_LEN + len(streams[qid]), inter_est
                )
        if len(done) == N_INTER:
            break
    assert len(done) == N_INTER
    while backend.has_work():
        backend.pump_once()
    for eng in engines.values():
        eng.drain_results()
    return {
        "admitted": admitted, "rejects": rejects,
        "first_step": first_step, "streams": streams, "engines": engines,
    }


def test_admission_caps_the_bulk_storm_and_interactive_ttft_in_steps():
    """Admission ON, the bulk tenant's bucket admits half the storm and
    rejects the rest as ``rate_limited``, so the interactive burst finds
    free rows: its worst first-token step is strictly earlier than with
    admission OFF, where every bulk request admits.  Every interactive
    stream is whole in both arms and no engine leaks a block."""
    plane = AdmissionPlane([
        TenantPolicy("bulk_load", priority="bulk", rate_tokens_per_s=1e-6,
                     burst_tokens=2.0 * (PROMPT_LEN + BULK_NEW)),
        TenantPolicy("interactive", priority="interactive"),
    ])
    on = _storm_then_burst(plane)
    off = _storm_then_burst(None)
    assert on["admitted"] == 2 and on["rejects"] == {"rate_limited": 2}
    assert off["admitted"] == N_BULK and off["rejects"] == {}
    assert max(on["first_step"].values()) < max(
        off["first_step"].values()
    ), (on["first_step"], off["first_step"])
    for arm in (on, off):
        assert sum(len(s) for s in arm["streams"].values()) == (
            N_INTER * INTER_NEW
        )
        for eng in arm["engines"].values():
            assert_pool_pristine(eng)


def test_stream_chunks_final_result_and_rollout_path_agree():
    """Greedy token identity across the three read paths of one engine:
    the streamed chunks' concatenation, the request's final result, and
    a plain rollout-style submission of the same prompt."""
    eng, *_ = make_engine()
    backend = EngineBackend({"srv": eng})
    ids = list(np.random.default_rng(1).integers(6, 60, (16,)))
    chunks = []
    out = run_request(
        backend, _ginp("par-gw", ids, 8), "interactive", "interactive",
        stream=True, on_chunk=chunks.append, pump=backend.pump_once,
    )
    concat = [t for c in chunks for t in c]
    assert len(chunks) > 1 and concat == list(out["result"]["output_ids"])
    eng.submit(_ginp("par-rollout", ids, 8))
    while eng.has_work:
        eng.step()
    rollout = eng.drain_results()["par-rollout"]
    assert list(rollout.output_ids) == concat
    assert_pool_pristine(eng)
