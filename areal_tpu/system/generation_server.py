"""Generation server worker: hosts the continuous-batching engine.

Rebuild of the reference's generation server (reference:
realhf/system/generation_server.py :120 — launches patched SGLang
subprocesses and registers URLs; here the TPU engine runs in-process).

API is a ZMQ ROUTER socket (replacing SGLang's HTTP):
  ("generate", APIGenerateInput)          -> APIGenerateOutput (async reply)
  ("update_weights", {path | version})    -> {"num_interrupted": n}
  ("pause"/"resume"/"metrics", {})        -> ack / metrics dict
"""

from __future__ import annotations

import os
import pickle
import threading
import time
from collections import deque
from typing import Dict, Optional, Tuple

import numpy as np
import zmq

from areal_tpu.api import dataset_api, system_api
from areal_tpu.base import constants, logging_, name_resolve, names, network
from areal_tpu.observability.tracing import phase
from areal_tpu.system import worker_base

logger = logging_.getLogger("generation_server")


#: serving roles a generation server may register under.  ``prefill``
#: servers run chunked prefill and hand finished rows' KV blocks to a
#: ``decode`` peer (P/D disaggregation); ``unified`` (the default, and
#: what every legacy registration parses as) does both.
SERVER_ROLES = ("prefill", "decode", "unified")

#: segment transports a generation server may register: the wire
#: mechanics a streamed KV segment (P/D handoff pushes, fleet prefix
#: pulls) travels over.  ``host-numpy`` (the default, and what every
#: legacy registration parses as) materializes payloads on host and
#: ships numpy over the peer ZMQ RPC.  ``tpu-d2d`` is a RESERVED
#: capability token for the device-to-device ICI/DMA window — it
#: parses (so a mixed fleet negotiates cleanly) but has no backend in
#: this build; see :func:`make_segment_transport`.
SEGMENT_TRANSPORTS = ("host-numpy", "tpu-d2d")


def format_server_registration(
    addr: str, mesh_spec, role: str = "unified",
    transport: str = "host-numpy",
) -> str:
    """Registration value for the gen_servers name-resolve subtree:
    ``addr|mesh_devices|mesh_spec[|role][|transport]``.  One "server" =
    one mesh: the gserver manager scales capacity accounting and
    routing weights by the chip count, so a 4-chip TP/EP server absorbs
    4x the load of a single-chip one instead of being treated as an
    equal peer.  ``role`` opts the server into the manager's two-stage
    prefill/decode routing; ``transport`` advertises the segment
    transport the server's KV fabric speaks (the manager only routes
    segment traffic — handoffs, prefix pulls — between servers on the
    same transport).  Both are capability TOKENS appended only when
    they differ from the defaults (``unified`` / ``host-numpy``), so
    legacy-shaped registrations stay byte-stable across versions."""
    base = f"{addr}|{mesh_spec.world_size}|{mesh_spec}"
    if role and role != "unified":
        if role not in SERVER_ROLES:
            raise ValueError(f"unknown server role {role!r}")
        base += f"|{role}"
    if transport and transport != "host-numpy":
        if transport not in SEGMENT_TRANSPORTS:
            raise ValueError(f"unknown segment transport {transport!r}")
        base += f"|{transport}"
    return base


def parse_server_registration(
    value: str,
) -> Tuple[str, int, str, str, str]:
    """``(addr, mesh_devices, mesh spec string, role, transport)`` from a
    registration value; bare-address values (older registrations) parse
    as one device, registrations without a role field parse as
    ``unified``, and ones without a transport capability parse as
    ``host-numpy``.  The trailing fields are capability TOKENS, not
    positions: everything past the mesh spec is matched against the
    known role and transport vocabularies, so ``addr|d|spec|tpu-d2d``
    (a unified server on a d2d fabric) and ``addr|d|spec|decode|tpu-d2d``
    both parse, and an unknown token from a newer peer degrades to the
    defaults instead of failing the whole fleet discovery."""
    parts = value.split("|")
    addr = parts[0]
    devices = int(parts[1]) if len(parts) > 1 and parts[1] else 1
    spec = parts[2] if len(parts) > 2 else ""
    role, transport = "unified", "host-numpy"
    for token in parts[3:]:
        if token in SERVER_ROLES:
            role = token
        elif token in SEGMENT_TRANSPORTS:
            transport = token
    return addr, max(1, devices), spec, role, transport

# ctrl-stream high-water mark (messages, each ~100s of bytes): bounds the
# leader's buffer at ~10s of MB if a follower wedges, yet is ~100x deeper
# than any observed leader/follower skew, so in practice nothing is dropped
_CTRL_HWM = 1 << 17


class SegmentTransport:
    """Wire mechanics for ONE streamed KV segment.

    The segment PROTOCOL — numbering, per-segment version checks, TTL
    sweeps, abort markers, fail-closed rejects — lives above this
    interface (engine + worker); a transport only moves a segment's
    bytes to a peer.  ``submit`` runs off the engine thread and returns
    a future resolving to ``bool`` ok (False = the peer rejected or the
    push died — the protocol layer drops the stream's remainder and the
    decode side re-prefills).  The negotiated transport name rides the
    server registration (see :func:`format_server_registration`), so a
    TPU device-to-device backend slots in here without touching the
    protocol logic."""

    name = "abstract"

    def __init__(self, worker: "GenerationServerWorker"):
        self._worker = worker

    def submit(self, qid: str, dest: str, seg: Dict):
        """Push ``seg`` (one numbered segment, device or host payload)
        to ``dest``; returns a Future[bool]."""
        raise NotImplementedError


class HostNumpyTransport(SegmentTransport):
    """The default transport: materialize the payload on host
    (``jax.device_get`` on the push thread, so the engine thread never
    blocks on the copy-out — the gather it dispatched rides under later
    fill and decode chunks) and ship numpy over the peer's ZMQ RPC."""

    name = "host-numpy"

    def submit(self, qid: str, dest: str, seg: Dict):
        worker = self._worker
        client = worker._peer_client(dest)
        log = worker.logger
        timeout = worker.config.handoff_request_timeout

        def push() -> bool:
            try:
                import jax

                wire = dict(seg)
                wire.pop("dest", None)
                payload = wire.get("payload")
                if payload:
                    wire["payload"] = tuple(
                        np.asarray(a) for a in jax.device_get(payload)
                    )
                resp = client.call(
                    "import_handoff_segment",
                    {"segment": wire},
                    timeout=timeout,
                )
                if isinstance(resp, dict) and resp.get("imported"):
                    return True
                log.warning(
                    "handoff segment %s/%s rejected by %s (%s); the "
                    "decode server re-prefills",
                    qid, seg.get("seq"), dest,
                    (resp or {}).get("reason")
                    if isinstance(resp, dict)
                    else resp,
                )
            except Exception as e:  # noqa: BLE001 - fail closed
                log.warning(
                    "handoff segment %s/%s to %s failed (%r); the decode "
                    "server re-prefills",
                    qid, seg.get("seq"), dest, e,
                )
            return False

        return worker._pool().submit(push)


def make_segment_transport(
    name: str, worker: "GenerationServerWorker"
) -> SegmentTransport:
    """Instantiate the segment transport ``name`` for ``worker``.
    ``tpu-d2d`` is a recognized capability with no backend in this
    build (the ICI/DMA path stays open for the TPU window — ROADMAP
    item 2 remainder), so asking for it is a configuration error, not a
    silent host-numpy fallback that would lie to the fleet directory."""
    if name == "host-numpy":
        return HostNumpyTransport(worker)
    if name in SEGMENT_TRANSPORTS:
        raise ValueError(
            f"segment transport {name!r} has no backend in this build"
        )
    raise ValueError(
        f"unknown segment transport {name!r}; expected one of "
        f"{SEGMENT_TRANSPORTS}"
    )


class GenerationServerWorker(worker_base.Worker):
    def _configure(self, config: system_api.GenServerConfig):
        self.config = config
        self.worker_name = config.worker_name
        self.logger = logging_.getLogger(self.worker_name)

        from areal_tpu.engine.backend import (
            cast_floating,
            make_model,
            refuse_unserved,
        )
        from areal_tpu.engine.inference_server import ContinuousBatchingEngine
        from areal_tpu.engine.sampling import SamplingParams
        from areal_tpu.observability import tracing

        # configure BEFORE the engine is built: the engine binds the
        # process tracer at construction
        tracing.configure(config.trace, worker=config.worker_name)

        tokenizer = None
        if config.tokenizer_path:
            tokenizer = dataset_api.load_hf_tokenizer(config.tokenizer_path)
        import jax

        # multi-host SPMD serving: join the jax.distributed cluster first so
        # jax.devices() below is the GLOBAL device list and the TP mesh can
        # span hosts (the reference's multi-node SGLang server role)
        self._n_procs = max(1, config.num_processes)
        self._is_leader = config.process_id == 0
        # P/D disaggregation: the serving role this server registers
        # under (routing hint for the manager; the handoff mechanics are
        # driven per-request by the ``handoff_to`` metadata the client
        # copies from its schedule response, so a unified fleet never
        # pays anything for the feature existing)
        self._role = config.role or "unified"
        if self._role not in SERVER_ROLES:
            raise ValueError(
                f"unknown server role {self._role!r}; expected "
                "prefill | decode | unified"
            )
        if self._role != "unified" and self._n_procs > 1:
            # the handoff unit is a full (unsharded) host copy of the
            # row's blocks; a multi-controller SPMD server only
            # addresses its local kv-head shard, so P/D roles are
            # single-process servers for now (cross-host MESHES decode
            # fine as unified)
            raise ValueError(
                "prefill/decode roles need a single-process server; "
                "multi-host SPMD servers must register as unified"
            )
        # fleet KV fabric: the segment transport this server registers
        # (negotiated through the registration value — the manager only
        # routes segment traffic between servers on the same transport)
        self._transport_name = config.segment_transport or "host-numpy"
        self._segment_transport = make_segment_transport(
            self._transport_name, self
        )
        if self._n_procs > 1:
            from areal_tpu.parallel import distributed as dist

            if not config.coordinator:
                raise ValueError(
                    "multi-host gen server needs config.coordinator"
                )
            dist.initialize(
                config.coordinator, self._n_procs, config.process_id
            )

        device = mesh = None
        world = config.mesh_spec.world_size
        if world > 1:
            # tensor-parallel engine over a contiguous device span starting
            # at device_idx (single-host) or over the global device list
            # (multi-host; every controller builds the identical mesh)
            start = config.device_idx or 0
            n = len(jax.devices())
            if start + world > n:
                raise ValueError(
                    f"gen server {config.worker_name} needs devices "
                    f"[{start}, {start + world}) but only {n} exist — "
                    "the allocation oversubscribes the host"
                )
            devices = jax.devices()[start : start + world]
            mesh = config.mesh_spec.make_mesh(devices)
        else:
            idx = config.device_idx or 0
            if idx >= len(jax.devices()):
                raise ValueError(
                    f"gen server {config.worker_name} is placed on device "
                    f"{idx} but only {len(jax.devices())} exist — set "
                    "gen_device_start/device_idx to a chip this host has"
                )
            device = jax.devices()[idx]
        # weights are built on the host (engine/backend.host_device) and
        # cast there to the serving dtype, so the engine's placement is
        # the first and only copy on its chip(s)
        model = make_model(config.model, None, None, tokenizer=tokenizer)
        refuse_unserved(model.model_cfg)
        model.init_params = cast_floating(
            model.init_params, model.model_cfg.dtype
        )
        sampling = SamplingParams(
            temperature=config.temperature, greedy=config.greedy
        )
        self.engine = ContinuousBatchingEngine(
            model.model_cfg,
            model.init_params,
            tokenizer=tokenizer,
            max_batch=config.max_concurrent_batch,
            kv_cache_len=config.kv_cache_len,
            chunk_size=config.chunk_size,
            sampling=sampling,
            device=device,
            mesh=mesh,
            cache_mode=config.cache_mode,
            page_size=config.page_size,
            kv_pool_tokens=config.kv_pool_tokens,
            kv_window_pool_tokens=config.kv_window_pool_tokens,
            kv_cache_dtype=config.kv_cache_dtype,
            serving_weight_dtype=config.serving_weight_dtype,
            prefill_chunk_tokens=config.prefill_chunk_tokens,
            pipeline_depth=config.pipeline_depth,
            prefix_cache=config.prefix_cache,
            prefix_cache_capacity_frac=config.prefix_cache_capacity_frac,
            prefix_cache_min_tokens=config.prefix_cache_min_match_tokens,
            prefix_cache_host_bytes=config.prefix_cache_host_bytes,
            slo_tracking=config.slo_tracking,
            server_name=config.worker_name,
            handoff_streaming=config.handoff_streaming,
            prefix_pull_min_tokens=config.prefix_pull_min_tokens,
            keep_routed_experts=config.keep_routed_experts,
            keep_chosen_sets=config.keep_chosen_sets,
        )
        if self.engine.sparse_decode_path:
            # (static: the decode program's path follows the table's shape)
            self.logger.info(
                "indexed layers at decode: sparse_decode_path=%s",
                self.engine.sparse_decode_path,
            )

        self._ctx = zmq.Context.instance()
        self._sock = None
        self._ctrl_pub = self._ctrl_sub = None
        self._ctrl_seq = 0
        expr, tr = constants.experiment_name(), constants.trial_name()
        base_key = names.gen_server(expr, tr, config.worker_name)
        # control keys live OUTSIDE the gen_servers/ subtree: the gserver
        # manager scans that subtree for server addresses and must not see
        # ctrl/readiness entries (code-review r3 finding)
        ctrl_key = names.gen_server_spmd(
            expr, tr, config.worker_name, "ctrl"
        )
        if self._is_leader:
            self._sock = self._ctx.socket(zmq.ROUTER)
            port = self._sock.bind_to_random_port("tcp://*")
            self.addr = f"{network.gethostip()}:{port}"
            # registration carries the mesh shape + serving role: the
            # manager weights this server's capacity/routing by its chip
            # count and slots it into the prefill/decode pools
            name_resolve.add(
                base_key,
                format_server_registration(
                    self.addr, config.mesh_spec, role=self._role,
                    transport=self._transport_name,
                ),
                replace=True,
            )
            if self._n_procs > 1:
                # command-stream broadcast to follower controllers.
                # HWM: the default (1000) silently DROPS messages under a
                # sustained leader/follower rate mismatch; unbounded (0)
                # instead buffers without limit and can OOM the leader when
                # a follower stalls (code-review r4+r5 findings).  A large
                # FINITE HWM bounds memory while making drops so rare that
                # one only happens when a follower is truly wedged — and a
                # drop is LOUD: the follower's seq-gap check kills the
                # server rather than desyncing the lockstep stream.
                self._ctrl_pub = self._ctx.socket(zmq.PUB)
                self._ctrl_pub.setsockopt(zmq.SNDHWM, _CTRL_HWM)
                cport = self._ctrl_pub.bind_to_random_port("tcp://*")
                name_resolve.add(
                    ctrl_key,
                    f"{network.gethostip()}:{cport}",
                    replace=True,
                )
                # slow-joiner barrier: publish nothing until every follower
                # has connected its SUB and said so
                for pid in range(1, self._n_procs):
                    name_resolve.wait(
                        names.gen_server_spmd(
                            expr, tr, config.worker_name, f"ready/{pid}"
                        ),
                        timeout=120,
                    )
                time.sleep(0.3)  # let late SUB handshakes settle
        else:
            ctrl_addr = name_resolve.wait(ctrl_key, timeout=120)
            self._ctrl_sub = self._ctx.socket(zmq.SUB)
            self._ctrl_sub.setsockopt(zmq.RCVHWM, _CTRL_HWM)  # see PUB note
            self._ctrl_sub.connect(f"tcp://{ctrl_addr}")
            self._ctrl_sub.setsockopt(zmq.SUBSCRIBE, b"")
            name_resolve.add(
                names.gen_server_spmd(
                    expr, tr, config.worker_name,
                    f"ready/{config.process_id}",
                ),
                "1",
                replace=True,
            )
        # qid -> ROUTER identity awaiting the result (leader only)
        self._waiting: Dict[str, bytes] = {}
        # gateway streams opened but possibly not yet applied to the
        # engine (a stream_poll can race the generate_stream's command
        # batch by one poll cycle); leader-local bookkeeping only
        self._open_streams: set = set()
        self._update_reply_idents = []  # clients awaiting update_weights
        self._import_reply_idents = []  # clients awaiting import_handoff
        # P/D handoff plumbing: destination decode server per in-flight
        # handoff-flagged request, lazily created peer clients, and the
        # in-flight pushes — the peer RPC runs on a small thread pool so
        # a slow or dead decode peer can never stall this server's poll
        # loop (the client reply is deferred until the push settles; the
        # RPC's own timeout bounds the deferral)
        self._handoff_dest: Dict[str, str] = {}
        self._peer_clients: Dict[str, "GenServerClient"] = {}
        self._handoff_pool = None
        self._handoff_futs: Dict[str, object] = {}
        self._handoff_out: Dict[str, object] = {}
        # STREAMED handoff (handoff_streaming, default on): the engine
        # queues numbered export segments as fill chunks complete; the
        # worker pushes them per-stream IN ORDER (one in-flight push per
        # qid, next submitted when the previous lands) over the
        # import_handoff_segment RPC while later chunks still fill.  The
        # client reply for a handoff-flagged request is gated on its
        # FINAL segment settling, so the continuation always finds the
        # row parked on the decode server.  A failed/rejected push marks
        # the stream dead (remaining segments dropped — the decode
        # side's TTL sweep releases its partial blocks; the continuation
        # re-prefills there).
        self._handoff_streaming = bool(config.handoff_streaming)
        self._segment_reply_idents = []  # clients awaiting segment import
        self._stream_push: Dict[str, Dict] = {}
        # fleet KV fabric: in-flight peer prefix pulls.  Each pull runs
        # the owner's export_prefix RPC on the handoff pool (a dead or
        # slow owner never stalls the poll loop); the returned segments
        # (numpy payloads, the segment wire format) are injected into
        # the lockstep command batch as import_prefix_segment commands,
        # so SPMD followers replay the identical import stream.
        self._pull_futs: Dict[str, object] = {}
        # in-flight staged weight restore (update_weights mode="stage"):
        # a background thread restores the snapshot into a device-resident
        # staging tree while decode continues; the RPC reply is deferred
        # until the tree is resident (the manager's pre-pause barrier)
        self._staging: Optional[Dict] = None
        self._start_time = time.monotonic()

        # recompile sentinel (observability/compile_watch.py): count
        # compiles per jitted decode/fill entry and — once the loop is
        # declared steady — alarm on ANY fresh compile, force-sampling
        # every in-flight row's trace root so the stalled episode is
        # inspectable end to end
        from areal_tpu.observability.compile_watch import CompileWatch
        from areal_tpu.observability.tracing import member_root

        def _force_inflight_roots(fns):
            trc = tracing.get_tracer()
            for row in self.engine.rows:
                if row is not None:
                    trc.force(member_root(row.req.qid))

        eng = self.engine
        self._compile_watch = CompileWatch(
            quiet_after_steps=config.compile_quiet_after_steps,
            on_steady_compile=_force_inflight_roots,
        )
        if eng.paged:
            from areal_tpu.models import paged as paged_mod

            def _paged_sig():
                return (
                    f"page={eng.page_size},chunk={eng.chunk_size},"
                    f"n_blocks={eng.n_blocks},batch={eng.max_batch}"
                )

            self._compile_watch.watch(
                "paged_fill_chunk", paged_mod.paged_fill_chunk,
                signature=_paged_sig,
            )
            self._compile_watch.watch(
                "paged_decode_chunk", paged_mod.paged_decode_chunk,
                signature=_paged_sig,
            )
        else:
            from areal_tpu.engine import inference_server as eng_mod

            def _dense_sig():
                return (
                    f"cache_len={eng.kv_cache_len},"
                    f"chunk={eng.chunk_size},batch={eng.max_batch}"
                )

            self._compile_watch.watch(
                "decode_chunk", eng_mod._decode_chunk,
                signature=_dense_sig,
            )
            self._compile_watch.watch(
                "admit_rows", eng_mod._admit_rows, signature=_dense_sig
            )
            self._compile_watch.watch(
                "sample_rows", eng_mod._sample_rows, signature=_dense_sig
            )

        # observability: the engine keeps plain cumulative floats (no
        # registry dependency in the hot loop); the worker mirrors them
        # into the scrape registry as counter deltas + gauges per poll
        from areal_tpu.observability import get_registry

        reg = get_registry()
        self._registry = reg
        self._obs = {
            "chunks": reg.counter("areal_inference_chunks_total"),
            "host": reg.counter("areal_inference_host_seconds_total"),
            "device": reg.counter("areal_inference_device_seconds_total"),
            "fetch": reg.counter("areal_inference_fetch_seconds_total"),
            "gen_tokens": reg.counter("areal_inference_generated_tokens_total"),
            "prefill_tokens": reg.counter("areal_inference_prefill_tokens_total"),
            "async_fetches": reg.counter(
                "areal_inference_async_fetches_total"
            ),
            "fetch_ready": reg.counter("areal_inference_fetch_ready_total"),
            "prefix_hits": reg.counter(
                "areal_inference_prefix_cache_hits_total"
            ),
            "prefix_misses": reg.counter(
                "areal_inference_prefix_cache_misses_total"
            ),
            "prefix_cached_tokens": reg.counter(
                "areal_inference_prefix_cached_tokens_total"
            ),
            "prefix_evictions": reg.counter(
                "areal_inference_prefix_cache_evictions_total"
            ),
            "prefix_host_spilled": reg.counter(
                "areal_inference_prefix_host_spilled_blocks_total"
            ),
            "prefix_host_restored": reg.counter(
                "areal_inference_prefix_host_restored_blocks_total"
            ),
            "prefix_host_dropped": reg.counter(
                "areal_inference_prefix_host_dropped_blocks_total"
            ),
            "kv_quant_checks": reg.counter(
                "areal_inference_kv_quant_divergence_checks_total"
            ),
            "kv_quant_diverged": reg.counter(
                "areal_inference_kv_quant_divergence_diverged_total"
            ),
            "weight_quant_checks": reg.counter(
                "areal_inference_weight_quant_divergence_checks_total"
            ),
            "weight_quant_diverged": reg.counter(
                "areal_inference_weight_quant_divergence_diverged_total"
            ),
            "handoff_exports": reg.counter(
                "areal_inference_handoff_exports_total"
            ),
            "handoff_imports": reg.counter(
                "areal_inference_handoff_imports_total"
            ),
            "handoff_bytes": reg.counter(
                "areal_inference_handoff_bytes_total"
            ),
            "handoff_seconds": reg.counter(
                "areal_inference_handoff_seconds_total"
            ),
            "handoff_segment_exports": reg.counter(
                "areal_inference_handoff_segment_exports_total"
            ),
            "handoff_segment_imports": reg.counter(
                "areal_inference_handoff_segment_imports_total"
            ),
            "handoff_segment_aborts": reg.counter(
                "areal_inference_handoff_segment_aborts_total"
            ),
            "prefix_peer_pulls": reg.counter(
                "areal_inference_prefix_peer_pulls_total"
            ),
            "prefix_peer_pull_bytes": reg.counter(
                "areal_inference_prefix_peer_pull_bytes_total"
            ),
            "swap_stage": reg.counter(
                "areal_inference_swap_stage_seconds_total"
            ),
            "swap_pause": reg.counter(
                "areal_inference_swap_pause_seconds_total"
            ),
            "swaps": reg.counter("areal_inference_weight_swaps_total"),
            "swaps_staged": reg.counter(
                "areal_inference_weight_swaps_staged_total"
            ),
            "inflight": reg.gauge("areal_inference_inflight_rows"),
            "pages_live": reg.gauge("areal_inference_kv_pages_live"),
            "pages_total": reg.gauge("areal_inference_kv_pages_total"),
            "state_slots_live": reg.gauge(
                "areal_inference_state_slots_live"
            ),
            "state_slots_total": reg.gauge(
                "areal_inference_state_slots_total"
            ),
            "moe_expert_pairs": reg.gauge("areal_inference_moe_expert_pairs"),
            "moe_groups_hit": reg.gauge("areal_inference_moe_groups_hit"),
            "moe_fill_tokens": reg.gauge("areal_inference_moe_fill_tokens"),
            "moe_fill_tokens_grouped": reg.gauge(
                "areal_inference_moe_fill_tokens_grouped"
            ),
            "moe_fill_extra_rounds": reg.gauge(
                "areal_inference_moe_fill_extra_rounds"
            ),
            "pending": reg.gauge("areal_inference_pending_requests"),
            "version": reg.gauge("areal_inference_weight_version"),
            "ring_depth": reg.gauge("areal_inference_ring_depth"),
            "inflight_chunks": reg.gauge("areal_inference_inflight_chunks"),
            "prefix_blocks": reg.gauge("areal_inference_prefix_cache_blocks"),
            "prefix_host_bytes": reg.gauge(
                "areal_inference_prefix_host_bytes"
            ),
            "prefix_host_blocks": reg.gauge(
                "areal_inference_prefix_host_blocks"
            ),
            "kv_quant_bits": reg.gauge(
                "areal_inference_kv_quant_storage_bits"
            ),
            "kv_quant_blocks": reg.gauge("areal_inference_kv_quant_blocks"),
            "weight_quant_bits": reg.gauge(
                "areal_inference_weight_quant_storage_bits"
            ),
            "weight_quant_leaves": reg.gauge(
                "areal_inference_weight_quant_leaves"
            ),
            "mesh_devices": reg.gauge("areal_inference_mesh_devices"),
        }
        # the engine thread's self seconds by phase span, mirrored as
        # per-phase counter deltas
        self._obs_phase = reg.counter("areal_inference_phase_seconds_total")
        self._obs_phase_last: Dict[str, float] = {}
        # handoff import rejects carry a reason label (version skew vs
        # layout vs capacity); mirrored as per-reason counter deltas
        self._obs_handoff_rejects = reg.counter(
            "areal_inference_handoff_import_rejects_total"
        )
        self._obs_handoff_rejects_last: Dict[str, int] = {}
        # fleet prefix pulls that failed closed, by reason (rpc failure,
        # version skew, expired TTL, ...); same delta-mirroring shape
        self._obs_pull_rejects = reg.counter(
            "areal_inference_prefix_peer_pull_rejects_total"
        )
        self._obs_pull_rejects_last: Dict[str, int] = {}
        # pool-pressure preemptions split by the victim's priority class
        # (the gateway admission plane's interactive/bulk split); same
        # per-label delta-mirroring shape as the reject counters
        self._obs_preempt_class = reg.counter(
            "areal_gateway_preemptions_total"
        )
        self._obs_preempt_class_last: Dict[str, int] = {}
        # request-level SLO digests: each family is a histogram over the
        # FIXED log buckets (latency.SLO_BUCKETS), so the master-side
        # aggregator can rebuild and EXACTLY merge per-worker digests
        # into fleet percentiles (observability/latency.py)
        from areal_tpu.observability.latency import SLO_BUCKETS

        self._obs_slo = {
            "admission_wait_s": reg.histogram(
                "areal_slo_admission_wait_seconds", buckets=SLO_BUCKETS
            ),
            "ttft_s": reg.histogram(
                "areal_slo_ttft_seconds", buckets=SLO_BUCKETS
            ),
            "tpot_s": reg.histogram(
                "areal_slo_tpot_seconds", buckets=SLO_BUCKETS
            ),
            "stall_s": reg.histogram(
                "areal_slo_stall_seconds", buckets=SLO_BUCKETS
            ),
        }
        self._obs_last: Dict[str, float] = {}

    def _export_engine_metrics(self):
        eng = self.engine
        pstats = eng.prefix_cache_stats()
        qstats = eng.kv_quant_stats()
        wstats = eng.weight_quant_stats()
        hstats = eng.handoff_stats()
        fstats = eng.prefix_peer_stats()
        split = eng.timing_split()
        totals = {
            "chunks": float(eng.chunks_total),
            "host": split["host_s"],
            "device": split["device_s"],
            "fetch": split["fetch_s"],
            "gen_tokens": float(eng.tokens_emitted_total),
            "prefill_tokens": float(eng.prefill_tokens_total),
            "async_fetches": float(eng.async_fetches_total),
            "fetch_ready": float(eng.fetch_ready_total),
            "prefix_hits": float(pstats["hits_total"]),
            "prefix_misses": float(pstats["misses_total"]),
            "prefix_cached_tokens": float(pstats["cached_tokens_total"]),
            "prefix_evictions": float(pstats["evictions_total"]),
            "prefix_host_spilled": float(pstats["spilled_blocks_total"]),
            "prefix_host_restored": float(pstats["restored_blocks_total"]),
            "prefix_host_dropped": float(
                pstats["host_dropped_blocks_total"]
            ),
            "kv_quant_checks": float(qstats["divergence_checks_total"]),
            "kv_quant_diverged": float(
                qstats["divergence_diverged_total"]
            ),
            "weight_quant_checks": float(
                wstats["divergence_checks_total"]
            ),
            "weight_quant_diverged": float(
                wstats["divergence_diverged_total"]
            ),
            "handoff_exports": float(hstats["exports_total"]),
            "handoff_imports": float(hstats["imports_total"]),
            "handoff_bytes": float(hstats["bytes_total"]),
            "handoff_seconds": float(hstats["seconds_total"]),
            "handoff_segment_exports": float(
                hstats["segment_exports_total"]
            ),
            "handoff_segment_imports": float(
                hstats["segment_imports_total"]
            ),
            "handoff_segment_aborts": float(
                hstats["segment_aborts_total"]
            ),
            "prefix_peer_pulls": float(fstats["pulls_total"]),
            "prefix_peer_pull_bytes": float(fstats["pull_bytes_total"]),
            "swap_stage": eng.swap_stage_s,
            "swap_pause": eng.swap_pause_s,
            "swaps": float(eng.swaps_total),
            "swaps_staged": float(eng.swaps_staged_total),
        }
        for key, total in totals.items():
            delta = total - self._obs_last.get(key, 0.0)
            if delta > 0:
                self._obs[key].inc(delta)
                self._obs_last[key] = total
        for name, total in eng.phase_seconds().items():
            delta = total - self._obs_phase_last.get(name, 0.0)
            if delta > 0:
                self._obs_phase.inc(delta, phase=name)
                self._obs_phase_last[name] = total
        for reason, total in hstats["import_rejects"].items():
            delta = total - self._obs_handoff_rejects_last.get(reason, 0)
            if delta > 0:
                self._obs_handoff_rejects.inc(delta, reason=reason)
                self._obs_handoff_rejects_last[reason] = total
        for reason, total in fstats["pull_rejects"].items():
            delta = total - self._obs_pull_rejects_last.get(reason, 0)
            if delta > 0:
                self._obs_pull_rejects.inc(delta, reason=reason)
                self._obs_pull_rejects_last[reason] = total
        for cls, total in eng.preempted_by_class.items():
            delta = total - self._obs_preempt_class_last.get(cls, 0)
            if delta > 0:
                # "class" is a Python keyword: pass the label via **
                self._obs_preempt_class.inc(delta, **{"class": cls})
                self._obs_preempt_class_last[cls] = total
        for rec in eng.drain_slo_records():
            w = rec.workload
            self._obs_slo["admission_wait_s"].observe(
                rec.admission_wait_s, workload=w
            )
            self._obs_slo["ttft_s"].observe(rec.ttft_s, workload=w)
            self._obs_slo["stall_s"].observe(rec.stall_s, workload=w)
            if rec.tpot_s is not None:
                self._obs_slo["tpot_s"].observe(rec.tpot_s, workload=w)
        self._obs["inflight"].set(eng.n_inflight)
        self._obs["pages_live"].set(eng.pages_live)
        self._obs["pages_total"].set(eng.pages_total)
        self._obs["state_slots_live"].set(eng.state_slots_live)
        self._obs["state_slots_total"].set(eng.state_slots_total)
        for e, n in enumerate(eng.moe_expert_pairs.tolist()):
            self._obs["moe_expert_pairs"].set(n, expert=str(e))
        self._obs["moe_groups_hit"].set(eng.moe_groups_hit_total)
        self._obs["moe_fill_tokens"].set(eng.moe_fill_tokens_total)
        self._obs["moe_fill_tokens_grouped"].set(
            eng.moe_fill_tokens_grouped_total
        )
        self._obs["moe_fill_extra_rounds"].set(
            eng.moe_fill_extra_rounds_total
        )
        self._obs["pending"].set(eng.n_pending)
        self._obs["version"].set(eng.version)
        self._obs["ring_depth"].set(eng.pipeline_depth)
        self._obs["inflight_chunks"].set(eng.inflight_chunks)
        self._obs["prefix_blocks"].set(pstats["blocks_held"])
        self._obs["prefix_host_bytes"].set(pstats["host_bytes_held"])
        self._obs["prefix_host_blocks"].set(pstats["host_blocks_held"])
        self._obs["kv_quant_bits"].set(qstats["storage_bits"])
        self._obs["kv_quant_blocks"].set(qstats["quantized_blocks_held"])
        self._obs["weight_quant_bits"].set(wstats["storage_bits"])
        self._obs["weight_quant_leaves"].set(wstats["quantized_leaves"])
        self._obs["mesh_devices"].set(eng.mesh_devices)
        # HBM ledger: per-subsystem attribution gauges (current + peak)
        eng.hbm_ledger.publish(self._registry)
        # recompile sentinel: arm the steady-state guard off the engine's
        # own step clock, then diff the jitted caches (the poll counts
        # compiles, records xla.compile spans, and fires the stall
        # sentinel when armed)
        watch = getattr(self, "_compile_watch", None)
        if watch is not None:
            watch.note_step(eng._step_seq)
            watch.poll()

    # -- API ---------------------------------------------------------------

    def _serve_api(self):
        """Drain client requests into an ordered command batch (leader).
        Read-only queries are answered immediately; state-mutating commands
        are returned for (broadcast +) lockstep application so every SPMD
        controller sees the identical stream."""
        batch = []
        for _ in range(64):
            try:
                ident, _, msg = self._sock.recv_multipart(flags=zmq.NOBLOCK)
            except zmq.ZMQError:
                break
            try:
                cmd, payload = pickle.loads(msg)
                if cmd == "generate":
                    self._waiting[payload.qid] = ident
                    dest = (payload.metadata or {}).get("handoff_to")
                    if dest:
                        # prefill-stage request: after the fill parks the
                        # row, export its KV to this decode peer BEFORE
                        # the client reply goes out (_reply_finished)
                        self._handoff_dest[payload.qid] = dest
                    batch.append((cmd, payload))
                    continue  # reply when the result is ready
                elif cmd == "generate_stream":
                    # gateway streaming generate: ack immediately; the
                    # submit rides the lockstep batch with the stream
                    # flag set (every controller opens the buffer, only
                    # the leader drains it).  NO _waiting entry — the
                    # final result stays parked for stream_poll to
                    # collect instead of _reply_finished pushing it.
                    md = dict(payload.metadata or {})
                    md["stream"] = True
                    payload.metadata = md
                    self._open_streams.add(payload.qid)
                    batch.append(("generate", payload))
                    resp = {"ok": True, "qid": payload.qid}
                elif cmd == "stream_poll":
                    # read-only leader query (like ``metrics``): drain
                    # buffered tokens + the final result when done
                    resp = self._stream_poll(payload)
                elif cmd == "stream_cancel":
                    # state-mutating (releases the row's pool blocks):
                    # rides the lockstep batch; ack immediately
                    self._open_streams.discard(payload["qid"])
                    batch.append((cmd, payload))
                    resp = {"ok": True}
                elif cmd == "import_handoff":
                    # state-mutating (a pool scatter): rides the lockstep
                    # batch like generate/update; reply after the apply
                    self._import_reply_idents.append(ident)
                    batch.append((cmd, payload))
                    continue
                elif cmd == "import_handoff_segment":
                    # one segment of a streamed handoff: state-mutating
                    # (seg-0 block allocation + an async pool scatter),
                    # so it rides the lockstep batch too
                    self._segment_reply_idents.append(ident)
                    batch.append((cmd, payload))
                    continue
                elif cmd == "update_weights":
                    self._update_reply_idents.append(ident)
                    batch.append((cmd, payload))
                    continue  # reply after the (lockstep) apply
                elif cmd == "pause":
                    batch.append((cmd, payload))
                    resp = "paused"
                elif cmd == "resume":
                    batch.append((cmd, payload))
                    resp = "resumed"
                elif cmd == "metrics":
                    resp = self.metrics()
                elif cmd == "export_prefix":
                    # fleet KV fabric, owner side: a read-only gather
                    # (device blocks -> host numpy), answered on the
                    # leader like ``metrics`` — nothing in engine state
                    # mutates, so it never rides the lockstep batch
                    resp = self._export_prefix(payload)
                else:
                    resp = {"error": f"unknown command {cmd}"}
            except Exception as e:  # noqa: BLE001
                self.logger.exception("api request failed")
                resp = {"error": repr(e)}
            self._sock.send_multipart([ident, b"", pickle.dumps(resp)])
        return batch

    def _apply_commands(self, batch):
        """Apply one command batch to the local engine (every controller
        runs this with the identical batch, in the identical step)."""
        for cmd, payload in batch:
            if cmd == "generate":
                self.engine.submit(payload)
            elif cmd == "update_weights":
                if (payload.get("mode") or "full") == "stage":
                    # deferred reply: the stage RPC answers only once the
                    # staged tree is device-resident (see _reply_staged)
                    self._begin_stage(payload)
                    continue
                commit_failed = None
                try:
                    if (payload.get("mode") or "full") == "commit":
                        n = self._commit_staged(payload)
                    else:
                        n = self._update_weights(payload)
                    resp = {
                        "num_interrupted": n,
                        "version": self.engine.version,
                    }
                except Exception as e:  # noqa: BLE001
                    self.logger.exception("weight update failed")
                    commit_failed = e
                    resp = {"error": repr(e)}
                if self._is_leader and self._update_reply_idents:
                    ident = self._update_reply_idents.pop(0)
                    self._sock.send_multipart(
                        [ident, b"", pickle.dumps(resp)]
                    )
                if (
                    commit_failed is not None
                    and self._n_procs > 1
                    and (payload.get("mode") or "full") == "commit"
                ):
                    # multi-host lockstep: a commit that fails on ONE
                    # controller while peers flip would leave shards of
                    # one SPMD computation serving different weight
                    # versions — silently corrupted tokens.  Die loudly
                    # instead (same policy as a ctrl-stream seq gap).
                    raise RuntimeError(
                        "staged weight commit failed on one SPMD "
                        "controller — versions would diverge across "
                        "the lockstep mesh"
                    ) from commit_failed
            elif cmd == "import_handoff":
                try:
                    ok, reason = self.engine.import_handoff(payload["unit"])
                    resp = {"imported": ok, "reason": reason}
                except Exception as e:  # noqa: BLE001 - peer re-prefills
                    self.logger.exception("handoff import failed")
                    resp = {"error": repr(e)}
                if self._is_leader and self._import_reply_idents:
                    ident = self._import_reply_idents.pop(0)
                    self._sock.send_multipart(
                        [ident, b"", pickle.dumps(resp)]
                    )
            elif cmd == "import_handoff_segment":
                try:
                    ok, reason = self.engine.import_handoff_segment(
                        payload["segment"]
                    )
                    resp = {"imported": ok, "reason": reason}
                except Exception as e:  # noqa: BLE001 - peer re-prefills
                    self.logger.exception("handoff segment import failed")
                    resp = {"error": repr(e)}
                if self._is_leader and self._segment_reply_idents:
                    ident = self._segment_reply_idents.pop(0)
                    self._sock.send_multipart(
                        [ident, b"", pickle.dumps(resp)]
                    )
            elif cmd == "import_prefix_segment":
                # fleet KV fabric, puller side: one pulled segment —
                # injected by the leader's pull driver, replayed by
                # followers (the engine rejects fail-closed on any skew
                # and the admission falls back to a plain re-prefill)
                try:
                    self.engine.import_prefix_segment(payload["segment"])
                except Exception:  # noqa: BLE001 - fail closed
                    self.logger.exception("prefix segment import failed")
            elif cmd == "prefix_pull_failed":
                self.engine.prefix_pull_failed(payload["qid"])
            elif cmd == "stream_cancel":
                # gateway client went away (disconnect or staleness):
                # cancel the row wherever it lives, freeing its blocks
                self.engine.cancel(payload["qid"])
            elif cmd == "pause":
                self.engine.pause()
            elif cmd == "resume":
                self.engine.resume()

    def _reply_finished(self):
        # settle in-flight handoff pushes first: a finished push frees
        # its request's deferred client reply
        for qid in list(self._handoff_futs):
            fut = self._handoff_futs[qid]
            if not fut.done():
                continue
            del self._handoff_futs[qid]
            out = self._handoff_out.pop(qid)
            ident = self._waiting.pop(qid)
            self._sock.send_multipart([ident, b"", pickle.dumps(out)])
        if not self._waiting:
            return
        for qid in list(self._waiting):
            if qid in self._handoff_futs:
                continue  # reply deferred until the push settles
            st = self._stream_push.get(qid)
            if st is not None and st.get("gate"):
                # streamed handoff: the final segment is queued or in
                # flight — the reply waits until it settles (success or
                # failure) so the continuation's schedule can't race
                # the decode-side park
                continue
            out = self.engine.try_get_result(qid)
            if out is not None:
                dest = self._handoff_dest.pop(qid, None)
                if (
                    dest is not None
                    and not self._handoff_streaming
                    and out.no_eos
                    and out.output_ids
                ):
                    # the handoff COMPLETES before the client reply: the
                    # continuation the client schedules next must find
                    # the imported row already parked on the decode
                    # server (an EOS'd or empty result has nothing to
                    # continue, so nothing moves).  The export (a local
                    # device gather) runs here on the engine's thread;
                    # the peer RPC runs pooled so the poll loop never
                    # blocks on a slow or dead peer.
                    if self._begin_handoff(qid, dest, out):
                        continue
                ident = self._waiting.pop(qid)
                self._sock.send_multipart([ident, b"", pickle.dumps(out)])

    def _begin_handoff(self, qid: str, dest: str, out) -> bool:
        """Export the parked prefill row's KV blocks (on this thread —
        the engine is single-threaded) and start the ``import_handoff``
        push to the decode peer on the handoff thread pool.  Returns
        True iff a push is in flight (the caller defers the client
        reply until it settles).  Every failure is non-fatal and
        FAIL-CLOSED: the peer rejects skewed or unplaceable units, a
        dead peer times out at ``handoff_request_timeout``, and in all
        cases the continuation simply re-prefills on the decode server
        under its own weights — stale KV is never decoded."""
        unit = self.engine.export_handoff(qid)
        if unit is None:
            return False  # row already evicted (swap/TTL): re-prefill
        client = self._peer_client(dest)

        def push():
            try:
                resp = client.call(
                    "import_handoff",
                    {"unit": unit},
                    timeout=self.config.handoff_request_timeout,
                )
                if not (isinstance(resp, dict) and resp.get("imported")):
                    self.logger.warning(
                        "handoff of %s rejected by %s (%s); the decode "
                        "server re-prefills",
                        qid, dest,
                        (resp or {}).get("reason")
                        if isinstance(resp, dict)
                        else resp,
                    )
            except Exception as e:  # noqa: BLE001 - fail closed
                self.logger.warning(
                    "handoff of %s to %s failed (%r); the decode server "
                    "re-prefills", qid, dest, e,
                )

        self._handoff_out[qid] = out
        self._handoff_futs[qid] = self._pool().submit(push)
        return True

    # -- streamed handoff: ordered per-stream segment pushes -----------------

    def _pool(self):
        if self._handoff_pool is None:
            import concurrent.futures as cf

            self._handoff_pool = cf.ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="kv-handoff"
            )
        return self._handoff_pool

    def _peer_client(self, dest: str) -> "GenServerClient":
        """Lazily-created RPC client for a peer server (handoff pushes,
        fleet prefix pulls)."""
        if dest not in self._peer_clients:
            self._peer_clients[dest] = GenServerClient(
                dest, timeout=self.config.handoff_request_timeout
            )
        return self._peer_clients[dest]

    def _submit_segment_push(self, qid: str, st: Dict, seg: Dict):
        """Push ONE segment to the decode peer over the negotiated
        segment transport (host-numpy unless configured otherwise —
        see :class:`SegmentTransport`).  Returns the future (resolves
        to bool ok)."""
        dest = seg.get("dest") or st["dest"]
        return self._segment_transport.submit(qid, dest, seg)

    # -- fleet KV fabric: cross-server prefix pulls --------------------------

    def _export_prefix(self, payload: Dict) -> Dict:
        """Owner side of a fleet prefix pull: the longest resident
        full-block prefix of the peer's tokens as numbered wire
        segments (numpy payloads — host-spilled blocks ARE the wire
        format already; device runs pay one gather).  Sharded SPMD
        export stays open for the TPU window: a multi-process server
        only addresses its local kv-head shard, so it refuses and the
        puller re-prefills (fail closed, like every fabric path)."""
        if self._n_procs > 1:
            return {"segments": [], "reason": "spmd"}
        try:
            segs = self.engine.export_prefix(
                payload.get("qid", "?"), payload.get("tokens") or []
            )
        except Exception as e:  # noqa: BLE001 - puller re-prefills
            self.logger.exception("prefix export failed")
            return {"segments": [], "reason": repr(e)}
        if not segs:
            return {"segments": [], "reason": "miss"}
        return {"segments": segs}

    def _pump_prefix_pulls(self):
        """Start one owner-side ``export_prefix`` RPC per pull intent
        the engine registered.  The RPC runs on the handoff thread pool
        — a dead or slow owner never stalls the poll loop, and the
        engine's step-keyed TTL sweep bounds how long the requeued
        admission waits — and resolves to the owner's segment list, or
        None on any failure (the pull fails closed to a re-prefill)."""
        for req in self.engine.drain_prefix_pull_requests():
            qid, source = req["qid"], req["source"]
            client = self._peer_client(source)
            timeout = self.config.handoff_request_timeout
            tokens = req["tokens"]
            log = self.logger

            def pull(qid=qid, source=source, tokens=tokens, client=client):
                try:
                    resp = client.call(
                        "export_prefix",
                        {"qid": qid, "tokens": tokens},
                        timeout=timeout,
                    )
                    segs = (
                        resp.get("segments")
                        if isinstance(resp, dict)
                        else None
                    )
                    if segs:
                        return segs
                    log.info(
                        "prefix pull %s from %s returned nothing (%s); "
                        "re-prefilling locally",
                        qid, source,
                        (resp or {}).get("reason")
                        if isinstance(resp, dict)
                        else resp,
                    )
                except Exception as e:  # noqa: BLE001 - fail closed
                    log.warning(
                        "prefix pull %s from %s failed (%r); "
                        "re-prefilling locally", qid, source, e,
                    )
                return None

            self._pull_futs[qid] = self._pool().submit(pull)

    def _drain_pull_commands(self):
        """Finished pulls -> lockstep commands: the owner's segments in
        seq order, or one failure marker.  Appended to the leader's
        command batch BEFORE the publish, so followers replay the
        identical imports at the identical step."""
        cmds = []
        for qid in list(self._pull_futs):
            fut = self._pull_futs[qid]
            if not fut.done():
                continue
            del self._pull_futs[qid]
            segs = fut.result()
            if segs:
                for seg in segs:
                    cmds.append(
                        ("import_prefix_segment", {"segment": seg})
                    )
            else:
                cmds.append(("prefix_pull_failed", {"qid": qid}))
        return cmds

    def _pump_handoff_streams(self):
        """Each poll: drain the engine's new export segments into their
        per-stream queues, settle finished pushes, and keep exactly one
        push in flight per stream (segments must arrive in seq order; a
        failure drops the stream's remainder — the decode side's TTL
        sweep releases its partial blocks and the continuation simply
        re-prefills there)."""
        for seg in self.engine.drain_handoff_segments():
            qid = seg["qid"]
            st = self._stream_push.get(qid)
            if st is None:
                st = {
                    "queue": deque(),
                    "fut": None,
                    "failed": False,
                    "gate": False,
                    "dest": seg.get("dest"),
                }
                self._stream_push[qid] = st
            if seg.get("final"):
                st["gate"] = True  # the client reply waits on this one
            if st["failed"]:
                continue  # peer dead/rejecting: drop the remainder
            st["queue"].append(seg)
        for qid in list(self._stream_push):
            st = self._stream_push[qid]
            fut = st["fut"]
            if fut is not None:
                if not fut.done():
                    continue
                st["fut"] = None
                if not fut.result():
                    st["failed"] = True
                    st["queue"].clear()
            if st["queue"]:
                st["fut"] = self._submit_segment_push(
                    qid, st, st["queue"].popleft()
                )
            elif st["fut"] is None:
                # drained (or failed): drop the record — this releases
                # the reply gate, and a still-filling stream's next
                # segment recreates it
                del self._stream_push[qid]

    def _update_weights(self, payload: Dict) -> int:
        """Load new weights (from the trainer's realloc dir) and hot-swap.

        ``format == "params"`` is the fast path: a sharded raw-param orbax
        tree restored straight onto this engine's shardings/dtypes (no HF
        conversion, resharding handled by orbax).  Plain HF checkpoint dirs
        remain accepted for cross-job swaps.

        This is the LEGACY full-reload path: the restore runs on the poll
        thread, so a paused fleet waits out disk + transfer here.  The
        staged protocol (``mode="stage"`` then ``mode="commit"``) moves
        everything but the pointer flip off that critical path."""
        params = self._load_update_params(payload, staged=False)
        return self.engine.update_weights(
            params, version=payload.get("version")
        )

    # -- staged weight sync (stage -> commit) --------------------------------

    def _negotiate_weight_format(
        self, path: str, manifest: Optional[Dict]
    ) -> Tuple[str, str, Optional[Dict]]:
        """Pick the snapshot tree this server restores: ``(format,
        restore_path, quant_leaves)`` with format "int8" | "full".

        A server configured ``serving_weight_dtype="int8"`` prefers the
        quantized sibling tree the publisher ADVERTISED in the manifest
        (half the staged bytes); a publisher that wrote none — or an
        old manifest-less snapshot — falls back to the full-precision
        tree with one readable log line (the server quantizes on
        arrival, so serving stays int8 either way).  An "auto" server
        ignores quantized advertisements entirely: today's behavior,
        bit for bit.  No publisher/server combination crashes on
        format grounds."""
        import os as _os

        want = self.config.serving_weight_dtype
        if want != "int8":
            return "full", path, None
        qinfo = ((manifest or {}).get("serving_quant") or {}).get("int8")
        if not (isinstance(qinfo, dict) and qinfo.get("dir")):
            self.logger.info(
                "serving_weight_dtype='int8' but snapshot %s advertises "
                "no quantized serving tree%s — restoring the "
                "full-precision tree and quantizing on arrival",
                path,
                "" if manifest is not None else " (no manifest)",
            )
            return "full", path, None
        qpath = _os.path.join(
            _os.path.dirname(_os.path.abspath(path)), qinfo["dir"]
        )
        if not _os.path.isdir(qpath):
            self.logger.info(
                "advertised quantized serving tree %s is gone (GC "
                "race?) — restoring the full-precision tree and "
                "quantizing on arrival",
                qpath,
            )
            return "full", path, None
        return "int8", qpath, qinfo.get("leaves")

    def _load_update_params(self, payload: Dict, staged: bool):
        """Restore the snapshot named by an update payload.  The staged
        path restores layer-chunked straight onto the engine's serving
        shardings (each chip reads only its own shard ranges; transient
        restore buffers bounded by ``stage_chunk_bytes``) and pre-checks
        the publisher's layout manifest so an arch mismatch fails as one
        readable error instead of an orbax stack trace.

        The tree FORMAT is negotiated through the manifest first
        (:meth:`_negotiate_weight_format`): int8 servers restore the
        publisher's quantized sibling tree when advertised — ~half the
        bytes per stage — and fall back to full precision (quantized on
        arrival) otherwise.  Either way the returned tree is in the
        engine's resident format, so the pointer-flip commit and
        version checks downstream are untouched."""
        path = payload.get("path")
        if payload.get("format") == "params":
            from areal_tpu.engine import checkpoint

            manifest = checkpoint.read_manifest(path)
            fmt, restore_path, quant_leaves = self._negotiate_weight_format(
                path, manifest
            )
            template = self.engine.weight_restore_template(fmt)
            if staged:
                # arch pre-check BEFORE any tensorstore open (and before
                # the fleet's pause window): the negotiated tree's own
                # leaves entry for int8, the manifest's for full
                check_leaves = (
                    quant_leaves
                    if fmt == "int8"
                    else (manifest or {}).get("leaves")
                )
                if check_leaves:
                    problems = checkpoint.validate_manifest(
                        template, {"leaves": check_leaves}
                    )
                    if problems:
                        raise RuntimeError(
                            "published snapshot does not match this "
                            f"engine's layout: {problems[:3]}"
                        )
                restored = checkpoint.load_params_staged(
                    template,
                    restore_path,
                    chunk_bytes=self.config.stage_chunk_bytes,
                    # staged_weights attribution grows chunk by chunk —
                    # the mid-restore footprint is visible, not just the
                    # final stage_weights total
                    ledger_handle=self.engine._led_staged,
                )
            else:
                restored = checkpoint.load_params_like(
                    template, restore_path
                )
            return self.engine.prepare_weights(restored)
        from areal_tpu.models.hf.registry import load_hf_model

        _, params = load_hf_model(path)
        return self.engine.prepare_weights(params)

    def _begin_stage(self, payload: Dict):
        """Start restoring ``payload``'s snapshot into a device-resident
        staging tree on a background thread — decode continues.  The RPC
        reply is sent by :meth:`_reply_staged` once the tree is resident
        (or the restore failed), which is the manager's pre-pause
        barrier."""
        ident = None
        if self._is_leader and self._update_reply_idents:
            ident = self._update_reply_idents.pop(0)
        if self._staging is not None and not self._staging["done"].is_set():
            # a concurrent round is still restoring: the manager is
            # retrying after a timeout — reply fail-fast (it re-polls the
            # published version; by then this staging has settled)
            if ident is not None:
                self._sock.send_multipart([
                    ident, b"",
                    pickle.dumps({"error": "weight staging in progress"}),
                ])
            return
        # an aborted round may have left an uncommitted tree: drop it so
        # the commit barrier can never flip a stale version
        self.engine.discard_staged()
        rec: Dict = {
            "done": threading.Event(),
            "result": None,
            "ident": ident,
            "replied": False,
            "version": payload.get("version"),
            "t0": time.monotonic(),
        }
        rec["thread"] = threading.Thread(
            target=self._stage_worker,
            args=(payload, rec),
            daemon=True,
            name=f"weight-stage-v{payload.get('version')}",
        )
        self._staging = rec
        rec["thread"].start()

    def _stage_worker(self, payload: Dict, rec: Dict):
        # the staged restore as a flight-recorder span: it runs WHILE
        # decode continues, and the Perfetto lane ("swap-v{n}") makes
        # the overlap with the decode chunks visible instead of only
        # counted.  Force-sampled: swaps are fleet events, not rollouts.
        swap_root = f"swap-v{payload.get('version')}"
        tracer = self.engine.tracer
        tracer.force(swap_root)
        tracer.span_begin(
            swap_root, "swap.stage", root=swap_root,
            version=payload.get("version"),
        )
        try:
            params = self._load_update_params(payload, staged=True)
            # device_put onto the serving shardings (no-op when the
            # restore already placed them there) + block_until_ready:
            # the commit's pointer flip pays zero transfer
            self.engine.stage_weights(params, payload.get("version"))
            rec["result"] = {
                "staged": payload.get("version"),
                "stage_seconds": round(time.monotonic() - rec["t0"], 4),
            }
            tracer.span_end(
                swap_root, "swap.stage", root=swap_root, ok=True,
            )
        except Exception as e:  # noqa: BLE001 - reported to the manager
            self.logger.exception("weight staging failed")
            rec["result"] = {"error": repr(e)}
            tracer.span_end(
                swap_root, "swap.stage", root=swap_root, ok=False,
                error=repr(e),
            )
        finally:
            rec["done"].set()

    def _reply_staged(self):
        """Answer a finished stage RPC (leader poll loop; followers have
        no ident and just let the record sit until commit)."""
        rec = self._staging
        if rec is None or rec["replied"] or not rec["done"].is_set():
            return
        rec["replied"] = True
        if rec["ident"] is not None:
            self._sock.send_multipart(
                [rec["ident"], b"", pickle.dumps(rec["result"])]
            )

    def _commit_staged(self, payload: Dict) -> int:
        """Version-consistent commit barrier: wait out any still-running
        local staging (SPMD followers can lag the leader), surface a
        failed restore, then pointer-flip the staged tree into the
        engine.  The fleet pause covers exactly this call plus the
        engine's next-step ring drain."""
        rec = self._staging
        version = payload.get("version")
        if rec is not None:
            if not rec["done"].wait(
                timeout=float(payload.get("commit_timeout", 60.0))
            ):
                raise RuntimeError("staged restore still running at commit")
            self._reply_staged()  # never leave a stage RPC unanswered
            self._staging = None
            result = rec["result"]
            if isinstance(result, dict) and "error" in result:
                raise RuntimeError(
                    f"staged restore failed: {result['error']}"
                )
        if self.engine.staged_version is None and version is not None and (
            self.engine.version == version
            or self.engine.pending_version == version
        ):
            # idempotent retry ack: the first commit flipped (or queued)
            # this exact version but its reply was lost in flight — the
            # manager's timeout-retry must not turn a completed round
            # into a failed one (the legacy full reload was idempotent
            # under the same retry loop)
            self.logger.info(
                "commit v%s retried after a lost reply: already applied",
                version,
            )
            return 0
        return self.engine.commit_staged(expected_version=version)

    def _stream_poll(self, payload: Dict) -> Dict:
        """One gateway poll: buffered tokens since the last poll, plus
        the final result (and stream teardown) once the row finished.
        Read-only from the SPMD view — answered on the leader without
        riding the command batch, exactly like ``metrics``."""
        qid = payload["qid"]
        toks = self.engine.drain_stream(qid)
        out = self.engine.try_get_result(qid)
        if out is not None:
            extra = self.engine.drain_stream(qid)
            if extra:
                toks = (toks or []) + extra
            self.engine.stream_close(qid)
            self._open_streams.discard(qid)
            return {
                "tokens": toks or [],
                "done": True,
                "result": {
                    "output_ids": list(out.output_ids),
                    "no_eos": bool(out.no_eos),
                    "version_start": out.version_start,
                    "version_end": out.version_end,
                },
            }
        if toks is None:
            if qid in self._open_streams:
                # the generate_stream's command batch has not applied
                # yet (one-poll race); nothing buffered, keep polling
                return {"tokens": [], "done": False, "result": None}
            return {"error": f"unknown stream {qid}"}
        return {"tokens": toks, "done": False, "result": None}

    def metrics(self) -> Dict:
        return {
            "n_inflight": self.engine.n_inflight,
            "n_pending": self.engine.n_pending,
            "gen_tokens_total": self.engine.gen_tokens_total,
            "version": self.engine.version,
            "uptime": time.monotonic() - self._start_time,
            # one server = one mesh: chips this engine's forward spans
            "mesh_devices": self.engine.mesh_devices,
            "mesh_spec": str(self.config.mesh_spec),
            # decode-pipeline ring state + async-fetch overlap counters
            "ring_depth": self.engine.pipeline_depth,
            "inflight_chunks": self.engine.inflight_chunks,
            "async_fetches_total": self.engine.async_fetches_total,
            "fetch_ready_total": self.engine.fetch_ready_total,
            # radix prefix cache: hit rate / cached-token volume /
            # eviction pressure / resident footprint
            **{
                f"prefix_cache_{k}": v
                for k, v in self.engine.prefix_cache_stats().items()
            },
            # quantized KV storage: dtype bits, quantized block
            # residency, measured divergence-check counters
            **{
                f"kv_quant_{k}": v
                for k, v in self.engine.kv_quant_stats().items()
            },
            # quantized serving weights: resident format, storage bits,
            # leaf count, param-tree HBM bytes, divergence counters
            **{
                f"weight_quant_{k}": v
                for k, v in self.engine.weight_quant_stats().items()
            },
            # P/D disaggregation: this server's role + KV-handoff volume
            # + the prefill-token backlog the manager's load-aware
            # admission routes on (tokens admitted/queued but not yet
            # filled; falls as fills complete or rows fail/evict)
            "role": self._role,
            "prefill_backlog_tokens": self.engine.prefill_backlog_tokens(),
            **{
                f"handoff_{k}": v
                for k, v in self.engine.handoff_stats().items()
            },
            # fleet KV fabric: the negotiated segment transport and the
            # puller-side counters (the manager's directory scrape also
            # reads prefix_cache_flushes_total above for its flush-epoch
            # coherence — see gserver_manager._refresh_fabric_epochs)
            "segment_transport": self._transport_name,
            **{
                f"prefix_peer_{k}": v
                for k, v in self.engine.prefix_peer_stats().items()
            },
            # decode-loop host/device/fetch attribution (cumulative s)
            **{
                f"time_{k}": v
                for k, v in self.engine.timing_split().items()
            },
            # weight-swap attribution: staging time (off the paused
            # critical path) vs pause time (what actually interrupted
            # decode), plus staged-vs-full swap counts
            **{
                f"swap_{k}": v
                for k, v in self.engine.swap_stats().items()
            },
            # request-level SLO plane: per-stage percentile summaries
            # (records_total + TTFT/TPOT/admission/stall p50-p99) and the
            # raw mergeable digest state for external consumers
            "slo": self.engine.slo_stats(),
            "slo_digests": self.engine.slo_digests(),
            # gateway token streams + priority-aware preemption split
            "streams": self.engine.stream_stats(),
            "cancelled_total": self.engine.cancelled_total,
            "preempted_total": self.engine.preempted_total,
            "preempted_by_class": dict(self.engine.preempted_by_class),
            # HBM ledger: per-subsystem byte attribution + watermarks
            # (the aggregator's merge_hbm folds these into fleet rows)
            "hbm_ledger": self.engine.hbm_ledger.snapshot(),
            "hbm_ledger_peak": self.engine.hbm_ledger.watermarks(),
            # recompile sentinel: per-entry compile counts + steady-state
            # fire totals
            **(
                self._compile_watch.stats()
                if getattr(self, "_compile_watch", None) is not None
                else {}
            ),
        }

    # -- poll ---------------------------------------------------------------

    def _poll(self) -> worker_base.PollResult:
        if self._is_leader:
            with phase("areal.gserver.poll"):
                return self._poll_leader()
        # follower: lockstep replay of the leader's command stream — one
        # engine.step() per published message, so chunk dispatches pair up
        if not self._ctrl_sub.poll(timeout=100):
            return worker_base.PollResult(sample_count=0)
        seq, batch = pickle.loads(self._ctrl_sub.recv())
        if seq != self._ctrl_seq + 1:
            raise RuntimeError(
                f"gen-server control stream gap: got seq {seq}, expected "
                f"{self._ctrl_seq + 1} — SPMD controllers have diverged"
            )
        self._ctrl_seq = seq
        self._apply_commands(batch)
        n = self.engine.step()
        self.engine.drain_results()  # leader owns client replies
        self._reply_staged()  # followers: just mark the record settled
        self._export_engine_metrics()
        return worker_base.PollResult(sample_count=n)

    def _poll_leader(self) -> worker_base.PollResult:
        with phase("areal.gserver.serve_api") as span:
            batch = self._serve_api()
            # dead-gateway-client backstop: a stream nobody drained for
            # stream_stale_steps engine steps auto-cancels — the cancel
            # rides THIS batch so followers release the row in lockstep
            for qid in self.engine.stale_stream_qids():
                self.logger.warning(
                    "auto-cancelling stale gateway stream %s", qid
                )
                self._open_streams.discard(qid)
                batch.append(("stream_cancel", {"qid": qid}))
            # fleet KV fabric: start owner RPCs for new pull intents and
            # append finished pulls' segments (or failure markers) to
            # THIS batch — they ride the publish below, so follower
            # controllers replay the identical import stream
            self._pump_prefix_pulls()
            batch.extend(self._drain_pull_commands())
            span.set_metadata(commands=len(batch))
        with phase("areal.gserver.apply_commands", commands=len(batch)):
            if self._ctrl_pub is not None:
                # publish BEFORE applying: followers must dispatch their
                # part of this step's device programs (TP collectives span
                # all controllers) while the leader runs its own
                self._ctrl_seq += 1
                self._ctrl_pub.send(pickle.dumps((self._ctrl_seq, batch)))
            self._apply_commands(batch)
        n = self.engine.step()
        with phase("areal.gserver.reply") as span:
            waiting = len(self._waiting)
            # streamed handoff: new export segments must enter their
            # queues (and gate their replies) BEFORE _reply_finished
            # looks at this step's results
            self._pump_handoff_streams()
            self._reply_finished()
            self._reply_staged()
            span.set_metadata(replies=waiting - len(self._waiting))
        with phase("areal.gserver.export_metrics"):
            self._export_engine_metrics()
        return worker_base.PollResult(sample_count=n)

    def _exit_hook(self):
        eng = getattr(self, "engine", None)
        if eng is not None:
            # in every run's output, traced or not: a slow run can be laid
            # beside a normal one of the same schedule, phase by phase
            self.logger.info(
                "engine phases after %d steps (self seconds): %s",
                eng._step_seq,
                ", ".join(
                    f"{name}={sec:.3f}"
                    for name, sec in eng.phase_seconds().items()
                ),
            )
            # ... and step by step: a header and a record a step
            # (docs/observability.md, "Step records";
            # python3 -m benchmark.lib.step_log reads it)
            try:
                log_dir = constants.get_log_path()
                os.makedirs(log_dir, exist_ok=True)
                path = os.path.join(
                    log_dir, f"steps.{self.worker_name}.jsonl"
                )
                eng._phases.dump(path)
                self.logger.info("step records: %s", path)
            except Exception as e:  # noqa: BLE001 - never keeps a worker up
                self.logger.warning("step records not written: %r", e)
            slo = eng.slo_stats()
            self.logger.info(
                "first tokens: first_tokens_deferred=%d, "
                "first_tokens_blocking=%d, ttft_s=%s, tpot_s=%s",
                eng.first_tokens_deferred_total,
                eng.first_tokens_blocking_total,
                slo["ttft_s"], slo["tpot_s"],
            )
            if eng.sparse_decode_path:
                self.logger.info(
                    "indexed layers at decode: sparse_decode_path=%s",
                    eng.sparse_decode_path,
                )
            if eng.moe_fill_tokens_total:
                self.logger.info(
                    "expert layers at fill: moe_fill_tokens=%d, "
                    "moe_fill_tokens_grouped=%d, moe_fill_extra_rounds=%d",
                    eng.moe_fill_tokens_total,
                    eng.moe_fill_tokens_grouped_total,
                    eng.moe_fill_extra_rounds_total,
                )
            if eng._by_kind:
                self.logger.info(
                    "keep-nothing tail at fill: tail_layers=%d, "
                    "fill_tail_positions_saved=%d",
                    eng.fill_tail_layers,
                    eng.fill_tail_positions_saved_total,
                )
            if eng.cfg.loop_steps > 1:
                self.logger.info(
                    "looped stack: loop_steps=%d, cache_layers=%d, "
                    "kv_bytes_per_token=%d, admission_page_waits=%d",
                    *eng.loop_counts.values(),
                    eng.admission_page_waits_total,
                )
            if eng._stateful:
                self.logger.info(
                    "kept fills: state_late_joins=%d, state_reprefills=%d, "
                    "state_fills_kept=%d, state_fills_evicted=%s",
                    eng.state_late_joins_total,
                    eng.state_reprefills_total,
                    eng.state_fills_kept_total,
                    eng.state_fills_evicted,
                )
            # releases the ledger attributions (and logs the leak audit:
            # a quiesced server returns the process ledger to baseline)
            eng.close()
        for client in getattr(self, "_peer_clients", {}).values():
            client.close()  # aborts any in-flight pooled push promptly
        pool = getattr(self, "_handoff_pool", None)
        if pool is not None:
            pool.shutdown(wait=False)
        for name in ("_sock", "_ctrl_pub", "_ctrl_sub"):
            sock = getattr(self, name, None)
            if sock is not None:
                sock.close(linger=0)


class GenServerClient:
    """Blocking client for the server API (used via asyncio.to_thread from
    rollout workers — replaces the reference's aiohttp SGLangAPIClient,
    realhf/impl/model/backend/sglang.py:62)."""

    def __init__(self, addr: str, timeout: float = 600.0):
        self.addr = addr
        self.timeout = timeout
        self._ctx = zmq.Context.instance()
        self._local = threading.local()
        self._abort = threading.Event()

    def _sock(self) -> zmq.Socket:
        # one DEALER per thread: safe concurrent requests over one client
        if not hasattr(self._local, "sock"):
            s = self._ctx.socket(zmq.DEALER)
            s.connect(f"tcp://{self.addr}")
            self._local.sock = s
        return self._local.sock

    def call(self, cmd: str, payload, timeout: Optional[float] = None) -> object:
        sock = self._sock()
        sock.send_multipart([b"", pickle.dumps((cmd, payload))])
        # sliced poll with an abort check: these calls run on asyncio's
        # default-executor threads, and a thread stuck in a 600s poll
        # after worker exit stalls asyncio.run's shutdown for its full
        # 300s join timeout (round-4 verdict weak #8)
        if not _poll_abortable(
            sock, self.timeout if timeout is None else timeout, self._abort
        ):
            # discard the socket so a late reply can't be read by (and
            # mismatched with) the next request on this thread
            sock.close(linger=0)
            del self._local.sock
            if self._abort.is_set():
                raise TimeoutError(f"{cmd} to {self.addr}: client closed")
            raise TimeoutError(f"{cmd} to {self.addr} timed out")
        _, msg = sock.recv_multipart()
        resp = pickle.loads(msg)
        if isinstance(resp, dict) and "error" in resp:
            raise RuntimeError(f"server error: {resp['error']}")
        return resp

    def generate(self, inp) -> object:
        return self.call("generate", inp)

    def close(self):
        self._abort.set()  # unblock every in-flight thread within ~0.5s
        if hasattr(self._local, "sock"):
            self._local.sock.close(linger=0)


def _poll_abortable(
    sock: zmq.Socket, timeout_s: float, abort: threading.Event
) -> bool:
    """Poll in 0.5s slices until data, timeout, or abort; True iff data."""
    deadline = time.monotonic() + timeout_s
    while not abort.is_set():
        left = deadline - time.monotonic()
        if left <= 0:
            return False
        if sock.poll(timeout=int(min(left, 0.5) * 1000)):
            return True
    return False
