"""OpenAI-style HTTP/SSE front door over the engine fleet.

``GatewayServer`` is a stdlib ``ThreadingHTTPServer`` (the same shape
as ``observability/server.py``'s metrics server) exposing
``/v1/completions`` + ``/v1/chat/completions`` with token streaming:
each decode-ring harvest's chunk surfaces as one SSE frame, so a
client's time-to-first-byte is the engine's TTFT, not the full
generation wall.  Requests are admitted through the per-tenant
admission plane (``admission.py``) — typed rejects surface as
structured HTTP 429/403 bodies with ``Retry-After``, never generic
500s — and every request stamps its tenant into the SLO plane's
``workload`` label plus a ``priority_class`` the engine's preemption
honors (interactive rows outlive bulk rollout rows under pool
pressure).

Two backends speak the same five-call protocol (admit / submit / poll
/ cancel / finish):

* :class:`EngineBackend` — in-process engines, used by tests and the
  dryrun's gateway phase.  The
  caller (or :meth:`EngineBackend.start_pump`) steps the engines;
  cancels queue and apply on the stepping thread (the engine's cancel
  rewrites pool state and must never race a step).
* :class:`FleetBackend` — the deployment path: schedules through the
  ``GserverManager`` (session-sticky, cache-aware, P/D two-stage
  routing all for free), generates via the gen servers'
  ``generate_stream``/``stream_poll``/``stream_cancel`` commands, and
  settles tenant budgets back through the manager.

A client disconnect mid-stream cancels the engine row and releases its
blocks (leak-audited in tests).
"""

from __future__ import annotations

import json
import math
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional

from areal_tpu.api import model_api
from areal_tpu.base import logging_
from areal_tpu.gateway import sse
from areal_tpu.gateway.admission import (
    PRIORITY_INTERACTIVE,
    AdmissionPlane,
)

logger = logging_.getLogger("gateway")


def estimate_tokens(prompt_len: int, max_new_tokens: int) -> float:
    """The admission plane's charge for one request: its worst-case
    token footprint (budgets true up via ``settle`` on finish)."""
    return float(prompt_len + max_new_tokens)


class ClientDisconnected(Exception):
    """The SSE consumer went away mid-stream (write failed)."""


# -- backends ---------------------------------------------------------------


class EngineBackend:
    """In-process fleet: round-robin over named engines + a local
    admission plane.  ``pump_once``/``start_pump`` own every
    state-mutating engine call (step + cancel); ``submit``/``poll`` are
    safe from HTTP handler threads (the engine's client API locks)."""

    def __init__(
        self,
        engines: Dict[str, Any],
        plane: Optional[AdmissionPlane] = None,
        pick: Optional[Callable[[str], str]] = None,
    ):
        self.engines = dict(engines)
        self.plane = plane
        self._names = list(self.engines)
        self._rr = 0
        self._pick = pick
        self._lock = threading.Lock()
        self._cancels: List[Dict[str, str]] = []
        self._pump_thread: Optional[threading.Thread] = None
        self._pump_stop = threading.Event()

    def admit(self, tenant: str, est_tokens: float) -> Dict[str, Any]:
        if self.plane is None:
            # admission plane off: every request admitted, no priority
            # class stamped
            return {"ok": True, "tenant": tenant, "priority": ""}
        return self.plane.admit(tenant, est_tokens, time.monotonic()).as_dict()

    def admit_and_submit(
        self,
        inp: model_api.APIGenerateInput,
        tenant: str,
        est_tokens: float,
        stream: bool,
    ):
        """Admission + placement in one step (in-process both are local
        calls, so this is just the protocol's combined form).  Returns
        ``(decision, handle)``; handle is ``None`` on reject."""
        dec = self.admit(tenant, est_tokens)
        if not dec.get("ok"):
            return dec, None
        return dec, self.submit(inp, tenant, dec.get("priority", ""), stream)

    def submit(
        self,
        inp: model_api.APIGenerateInput,
        tenant: str,
        priority: str,
        stream: bool,
    ) -> Dict[str, str]:
        with self._lock:
            if self._pick is not None:
                name = self._pick(inp.qid)
            else:
                name = self._names[self._rr % len(self._names)]
                self._rr += 1
        md = dict(inp.metadata or {})
        md["workload"] = tenant
        if priority:
            md["priority_class"] = priority
        if stream:
            md["stream"] = True
        inp.metadata = md
        self.engines[name].submit(inp)
        return {"engine": name, "qid": inp.qid, "tenant": tenant}

    def poll(self, handle: Dict[str, str]) -> Dict[str, Any]:
        eng, qid = self.engines[handle["engine"]], handle["qid"]
        toks = eng.drain_stream(qid) or []
        out = eng.try_get_result(qid)
        if out is not None:
            toks += eng.drain_stream(qid) or []
            eng.stream_close(qid)
            return {
                "tokens": toks,
                "done": True,
                "result": {
                    "output_ids": list(out.output_ids),
                    "no_eos": bool(out.no_eos),
                    "version_start": out.version_start,
                    "version_end": out.version_end,
                },
            }
        return {"tokens": toks, "done": False, "result": None}

    def cancel(self, handle: Dict[str, str]):
        with self._lock:
            self._cancels.append(dict(handle))

    def finish(self, handle: Dict[str, str], used_tokens: float,
               reserved_tokens: float):
        if self.plane is not None:
            self.plane.settle(
                handle["tenant"], reserved_tokens, used_tokens
            )

    # -- pumping (the stepping thread owns all engine mutation) ---------

    def pump_once(self) -> int:
        """Apply queued cancels, then step every engine once.  Returns
        total tokens harvested this round."""
        with self._lock:
            cancels, self._cancels = self._cancels, []
        for h in cancels:
            self.engines[h["engine"]].cancel(h["qid"])
        n = 0
        for eng in self.engines.values():
            n += eng.step()
        return n

    def has_work(self) -> bool:
        return any(e.has_work for e in self.engines.values())

    def start_pump(self, interval_s: float = 0.0):
        assert self._pump_thread is None

        def loop():
            while not self._pump_stop.is_set():
                if self.pump_once() == 0 and not self.has_work():
                    time.sleep(max(interval_s, 0.002))

        self._pump_thread = threading.Thread(
            target=loop, name="gateway-pump", daemon=True
        )
        self._pump_thread.start()

    def stop_pump(self):
        if self._pump_thread is not None:
            self._pump_stop.set()
            self._pump_thread.join(timeout=10.0)
            self._pump_thread = None
            self._pump_stop.clear()


class FleetBackend:
    """ZMQ fleet: manager-scheduled, gen-server-streamed (deployment
    path; exercised end-to-end by the launcher, not tier-1)."""

    def __init__(self, manager_client, client_factory=None,
                 request_timeout: float = 600.0):
        from areal_tpu.system.generation_server import GenServerClient

        self.manager = manager_client
        self._timeout = request_timeout
        self._factory = client_factory or (
            lambda addr: GenServerClient(addr, timeout=request_timeout)
        )
        self._clients: Dict[str, Any] = {}
        # flipped off (permanently) the first time the manager rejects
        # the combined gateway_submit command — an older manager speaks
        # only the two-call admit + schedule protocol
        self._combined_ok = True

    def _client(self, addr: str):
        if addr not in self._clients:
            self._clients[addr] = self._factory(addr)
        return self._clients[addr]

    def admit(self, tenant: str, est_tokens: float) -> Dict[str, Any]:
        return self.manager.call(
            "gateway_admit", {"tenant": tenant, "tokens": est_tokens}
        )

    def _dispatch(
        self,
        inp: model_api.APIGenerateInput,
        tenant: str,
        priority: str,
        stream: bool,
        sched: Dict[str, Any],
        sched_wait_s: float,
    ) -> Dict[str, str]:
        """Stamp routing metadata from a schedule decision and hand the
        request to the scheduled gen server."""
        md = dict(inp.metadata or {})
        md["workload"] = tenant
        if priority:
            md["priority_class"] = priority
        if stream:
            md["stream"] = True
        md["slo_schedule_wait_s"] = sched_wait_s
        for key in ("handoff_to", "pd_shed", "kv_source"):
            if sched.get(key):
                md[key] = sched[key]
        inp.metadata = md
        self._client(sched["url"]).call(
            "generate_stream" if stream else "generate", inp,
            timeout=self._timeout,
        )
        return {"url": sched["url"], "qid": inp.qid, "tenant": tenant}

    def submit(
        self,
        inp: model_api.APIGenerateInput,
        tenant: str,
        priority: str,
        stream: bool,
    ) -> Dict[str, str]:
        t0 = time.monotonic()
        sched = self.manager.call(
            "schedule_request",
            {
                "qid": inp.qid,
                "prompt_len": len(inp.input_ids or inp.prompt_ids),
                "new_token_budget": inp.gconfig.max_new_tokens,
            },
        )
        return self._dispatch(
            inp, tenant, priority, stream, sched,
            sched_wait_s=time.monotonic() - t0,
        )

    def admit_and_submit(
        self,
        inp: model_api.APIGenerateInput,
        tenant: str,
        est_tokens: float,
        stream: bool,
    ):
        """One manager round trip instead of two: ``gateway_submit``
        returns the admission decision and — when admitted — the
        schedule for ``inp`` in the same reply.  Falls back (for good)
        to the legacy admit + schedule_request pair against managers
        that predate the combined command.  Returns ``(decision,
        handle)``; handle is ``None`` on reject."""
        if self._combined_ok:
            t0 = time.monotonic()
            try:
                dec = self.manager.call(
                    "gateway_submit",
                    {
                        "tenant": tenant,
                        "tokens": est_tokens,
                        "qid": inp.qid,
                        "prompt_len": len(inp.input_ids or inp.prompt_ids),
                        "new_token_budget": inp.gconfig.max_new_tokens,
                    },
                )
            except RuntimeError:
                # the manager replied {"error": "unknown command ..."}:
                # an older control plane — use the two-call protocol
                # from here on
                self._combined_ok = False
                logger.warning(
                    "manager does not speak gateway_submit; falling "
                    "back to admit + schedule round trips"
                )
            else:
                if not dec.get("ok"):
                    return dec, None
                sched = dec.get("schedule")
                if sched is not None:
                    handle = self._dispatch(
                        inp, tenant, dec.get("priority", ""), stream,
                        sched, sched_wait_s=time.monotonic() - t0,
                    )
                    return dec, handle
                # admitted but no schedule attached (defensive):
                # schedule separately below
                return dec, self.submit(
                    inp, tenant, dec.get("priority", ""), stream
                )
        dec = self.admit(tenant, est_tokens)
        if not dec.get("ok"):
            return dec, None
        return dec, self.submit(inp, tenant, dec.get("priority", ""), stream)

    def poll(self, handle: Dict[str, str]) -> Dict[str, Any]:
        return self._client(handle["url"]).call(
            "stream_poll", {"qid": handle["qid"]}, timeout=self._timeout
        )

    def cancel(self, handle: Dict[str, str]):
        self._client(handle["url"]).call(
            "stream_cancel", {"qid": handle["qid"]}, timeout=self._timeout
        )

    def finish(self, handle: Dict[str, str], used_tokens: float,
               reserved_tokens: float):
        self.manager.call(
            "gateway_finish",
            {
                "qid": handle["qid"],
                "tenant": handle["tenant"],
                "reserved_tokens": reserved_tokens,
                "used_tokens": used_tokens,
            },
        )


# -- request lifecycle (transport-agnostic) ---------------------------------


def run_request(
    backend,
    inp: model_api.APIGenerateInput,
    tenant: str,
    priority: str,
    *,
    stream: bool,
    on_chunk: Optional[Callable[[List[int]], None]] = None,
    poll_interval_s: float = 0.002,
    timeout_s: float = 600.0,
    pump: Optional[Callable[[], Any]] = None,
    handle: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    """Submit one admitted request and drive it to completion, invoking
    ``on_chunk`` with each incremental token batch (streaming mode).
    ``pump`` lets a single-threaded caller (dryrun, tests) step the
    in-process engines between polls.  A pre-made ``handle`` (from
    ``admit_and_submit``'s combined round trip) skips the submit.  A
    ``ClientDisconnected`` raised by ``on_chunk`` cancels the engine
    row and settles the tenant's budget for the tokens actually
    produced."""
    prompt_len = len(inp.input_ids or inp.prompt_ids)
    reserved = estimate_tokens(prompt_len, inp.gconfig.max_new_tokens)
    if handle is None:
        handle = backend.submit(inp, tenant, priority, stream)
    collected: List[int] = []
    deadline = time.monotonic() + timeout_s
    try:
        while True:
            if pump is not None:
                pump()
            r = backend.poll(handle)
            toks = r.get("tokens") or []
            if toks:
                collected.extend(toks)
                if on_chunk is not None:
                    on_chunk(toks)
            if r.get("done"):
                backend.finish(
                    handle, float(len(collected)) + prompt_len, reserved
                )
                return {
                    "token_ids": collected,
                    "result": r.get("result") or {},
                    "prompt_tokens": prompt_len,
                }
            if time.monotonic() > deadline:
                backend.cancel(handle)
                backend.finish(
                    handle, float(len(collected)) + prompt_len, reserved
                )
                raise TimeoutError(f"gateway request {inp.qid} timed out")
            if pump is None and poll_interval_s:
                time.sleep(poll_interval_s)
    except ClientDisconnected:
        backend.cancel(handle)
        backend.finish(
            handle, float(len(collected)) + prompt_len, reserved
        )
        raise


# -- HTTP server ------------------------------------------------------------


class GatewayServer:
    """The HTTP/SSE front door.  ``port=0`` binds an ephemeral port
    (tests); ``serve_forever`` runs on a daemon thread like the metrics
    server."""

    def __init__(
        self,
        backend,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        default_tenant: str = "anonymous",
        vocab_size: int = 256,
        max_new_tokens_cap: int = 1024,
        model_name: str = "areal-tpu",
        poll_interval_s: float = 0.002,
        request_timeout_s: float = 600.0,
        tokenizer: Optional[Any] = None,
    ):
        self.backend = backend
        self.default_tenant = default_tenant
        self.vocab_size = vocab_size
        # a real (HF-style) tokenizer makes string prompts first-class;
        # without one the byte-level codec in ``sse`` round-trips text
        self.tokenizer = tokenizer
        self.max_new_tokens_cap = max_new_tokens_cap
        self.model_name = model_name
        self.poll_interval_s = poll_interval_s
        self.request_timeout_s = request_timeout_s
        self._seq_lock = threading.Lock()
        self._seq = 0
        self._active_streams = 0
        self._init_metrics()
        gw = self

        class Handler(BaseHTTPRequestHandler):
            # HTTP/1.0: the SSE body ends at connection close (no
            # chunked framing), matching curl/openai-client behavior
            protocol_version = "HTTP/1.0"

            def log_message(self, fmt, *args):  # noqa: N802
                logger.debug("gateway http: " + fmt, *args)

            def do_GET(self):  # noqa: N802
                if self.path == "/healthz":
                    body = json.dumps({"ok": True}).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self.send_error(404)

            def do_POST(self):  # noqa: N802
                if self.path == "/v1/completions":
                    gw._handle_completion(self, chat=False)
                elif self.path == "/v1/chat/completions":
                    gw._handle_completion(self, chat=True)
                else:
                    self.send_error(404)

        self.httpd = ThreadingHTTPServer((host, port), Handler)
        self.httpd.daemon_threads = True
        self.address = (
            f"{self.httpd.server_address[0]}:{self.httpd.server_address[1]}"
        )
        self._thread: Optional[threading.Thread] = None

    def _init_metrics(self):
        from areal_tpu.observability import get_registry

        reg = get_registry()
        self._m_requests = reg.counter("areal_gateway_requests_total")
        self._m_streams = reg.counter("areal_gateway_streams_total")
        self._m_rejects = reg.counter(
            "areal_gateway_admission_rejects_total"
        )
        self._m_active = reg.gauge("areal_gateway_active_streams")

    def start(self):
        assert self._thread is None
        self._thread = threading.Thread(
            target=self.httpd.serve_forever,
            name="gateway-http",
            daemon=True,
        )
        self._thread.start()
        logger.info("gateway listening on %s", self.address)

    def shutdown(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    # -- request handling ----------------------------------------------

    def _next_id(self) -> str:
        with self._seq_lock:
            self._seq += 1
            return f"{self._seq}"

    def _encode_text(self, text: str) -> List[int]:
        if self.tokenizer is not None:
            return [int(t) for t in self.tokenizer.encode(text)]
        return sse.encode_text(text, self.vocab_size)

    def _decode_tokens(self, toks: List[int]) -> str:
        if self.tokenizer is not None:
            return self.tokenizer.decode(toks)
        return sse.decode_tokens(toks)

    def _parse_prompt(self, body: Dict[str, Any], chat: bool) -> List[int]:
        if chat:
            ids: List[int] = []
            for msg in body.get("messages") or []:
                content = msg.get("content", "")
                if isinstance(content, list):
                    ids.extend(int(t) for t in content)
                else:
                    ids.extend(self._encode_text(str(content)))
            return ids
        prompt = body.get("prompt", [])
        if isinstance(prompt, str):
            return self._encode_text(prompt)
        return [int(t) for t in prompt]

    def _send_json(self, handler, status: int, obj: Dict[str, Any],
                   headers: Dict[str, str] = ()):
        body = json.dumps(obj).encode()
        handler.send_response(status)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(body)))
        for k, v in dict(headers or {}).items():
            handler.send_header(k, v)
        handler.end_headers()
        handler.wfile.write(body)

    def _handle_completion(self, handler, chat: bool):
        try:
            n = int(handler.headers.get("Content-Length") or 0)
            body = json.loads(handler.rfile.read(n) or b"{}")
        except Exception:  # noqa: BLE001
            self._send_json(
                handler, 400,
                {"error": {"message": "invalid JSON body",
                           "type": "invalid_request_error"}},
            )
            return
        self._m_requests.inc()
        tenant = str(
            handler.headers.get("x-tenant")
            or body.get("user")
            or self.default_tenant
        )
        prompt = self._parse_prompt(body, chat)
        if not prompt:
            self._send_json(
                handler, 400,
                {"error": {"message": "empty prompt",
                           "type": "invalid_request_error"}},
            )
            return
        max_new = min(
            int(body.get("max_tokens") or 16), self.max_new_tokens_cap
        )
        stream = bool(body.get("stream"))
        temperature = body.get("temperature")
        greedy = temperature is None or float(temperature) <= 0.0
        # request object built BEFORE admission: a backend with the
        # combined admit_and_submit entry point collapses the admit and
        # schedule round trips into one manager call
        qid = str(body.get("qid") or f"gw-{self._next_id()}")
        gconfig = model_api.GenerationHyperparameters(
            max_new_tokens=max_new,
            greedy=greedy,
            temperature=float(temperature) if not greedy else 1.0,
            n=1,
        )
        inp = model_api.APIGenerateInput(
            qid=qid, prompt_ids=prompt, input_ids=prompt, gconfig=gconfig
        )
        handle = None
        try:
            if hasattr(self.backend, "admit_and_submit"):
                dec, handle = self.backend.admit_and_submit(
                    inp, tenant, estimate_tokens(len(prompt), max_new),
                    stream,
                )
            else:
                # stub/minimal backends speak the five-call protocol only
                dec = self.backend.admit(
                    tenant, estimate_tokens(len(prompt), max_new)
                )
        except Exception as e:  # noqa: BLE001 - manager/gen-server down
            logger.exception("admit/submit for %s failed", qid)
            self._send_json(
                handler, 502,
                {"error": {"message": repr(e), "type": "bad_gateway"}},
            )
            return
        if not dec.get("ok"):
            reason = dec.get("reason", "rejected")
            self._m_rejects.inc(reason=reason)
            headers = {}
            retry_after = dec.get("retry_after_s") or 0.0
            if dec.get("http_status") == 429:
                headers["Retry-After"] = str(
                    max(1, int(math.ceil(retry_after)))
                )
            self._send_json(
                handler,
                int(dec.get("http_status") or 429),
                {"error": {
                    "message": (
                        f"tenant {tenant!r} rejected: {reason}"
                    ),
                    "type": reason,
                    "retry_after_s": retry_after,
                }},
                headers,
            )
            return
        rid = f"cmpl-{qid}"
        obj = "chat.completion.chunk" if chat else "text_completion"
        if stream:
            self._m_streams.inc()
            self._stream_response(
                handler, inp, tenant, dec.get("priority", ""), rid, obj,
                chat, handle=handle,
            )
        else:
            self._sync_response(
                handler, inp, tenant, dec.get("priority", ""), rid, chat,
                handle=handle,
            )

    def _choice(self, toks: List[int], chat: bool,
                finish_reason: Optional[str]) -> Dict[str, Any]:
        text = self._decode_tokens(toks)
        if chat:
            delta = {"role": "assistant", "content": text}
            return {"index": 0, "delta": delta, "token_ids": toks,
                    "finish_reason": finish_reason}
        return {"index": 0, "text": text, "token_ids": toks,
                "finish_reason": finish_reason}

    def _stream_response(self, handler, inp, tenant, priority, rid, obj,
                         chat, handle=None):
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-cache")
        handler.end_headers()
        with self._seq_lock:
            self._active_streams += 1
            self._m_active.set(self._active_streams)

        def write_frame(payload):
            try:
                handler.wfile.write(sse.sse_frame(payload))
                handler.wfile.flush()
            except (BrokenPipeError, ConnectionResetError, OSError) as e:
                raise ClientDisconnected(str(e)) from e

        def on_chunk(toks: List[int]):
            write_frame({
                "id": rid, "object": obj, "model": self.model_name,
                "choices": [self._choice(toks, chat, None)],
            })

        try:
            out = run_request(
                self.backend, inp, tenant, priority,
                stream=True, on_chunk=on_chunk,
                poll_interval_s=self.poll_interval_s,
                timeout_s=self.request_timeout_s,
                handle=handle,
            )
            result = out["result"]
            finish = "length" if result.get("no_eos") else "stop"
            write_frame({
                "id": rid, "object": obj, "model": self.model_name,
                "choices": [self._choice([], chat, finish)],
                "usage": sse.usage_block(
                    out["prompt_tokens"], len(out["token_ids"])
                ),
            })
            write_frame(sse.DONE_SENTINEL)
        except ClientDisconnected:
            logger.info("client disconnected mid-stream (%s)", inp.qid)
        except Exception as e:  # noqa: BLE001
            logger.exception("stream %s failed", inp.qid)
            try:
                write_frame({"error": {"message": repr(e)}})
            except ClientDisconnected:
                pass
        finally:
            with self._seq_lock:
                self._active_streams -= 1
                self._m_active.set(self._active_streams)

    def _sync_response(self, handler, inp, tenant, priority, rid, chat,
                       handle=None):
        try:
            out = run_request(
                self.backend, inp, tenant, priority, stream=False,
                poll_interval_s=self.poll_interval_s,
                timeout_s=self.request_timeout_s,
                handle=handle,
            )
        except TimeoutError as e:
            self._send_json(
                handler, 504,
                {"error": {"message": str(e), "type": "timeout"}},
            )
            return
        result = out["result"]
        toks = result.get("output_ids") or out["token_ids"]
        finish = "length" if result.get("no_eos") else "stop"
        choice = self._choice(toks, chat, finish)
        if chat:
            choice = {
                "index": 0,
                "message": {
                    "role": "assistant",
                    "content": self._decode_tokens(toks),
                },
                "token_ids": toks,
                "finish_reason": finish,
            }
        self._send_json(handler, 200, {
            "id": rid,
            "object": "chat.completion" if chat else "text_completion",
            "model": self.model_name,
            "choices": [choice],
            "usage": sse.usage_block(out["prompt_tokens"], len(toks)),
        })
