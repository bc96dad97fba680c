"""Experiment/worker configuration dataclasses.

Rebuild of the reference's system API (reference:
realhf/api/core/system_api.py — ``ModelWorker`` :95, ``GenerationServer``
:124, ``GserverManager`` :134, ``RolloutWorker`` :146, ``MasterWorker``
:159, ``ExperimentConfig`` :190 with DFG lazy-init, ``Experiment`` ABC +
registry :457-488).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from areal_tpu.api.config import (
    AgentAbstraction,
    DatasetAbstraction,
    EnvServiceAbstraction,
    ModelAbstraction,
    ModelBackendAbstraction,
    ModelInterfaceAbstraction,
    ModelName,
)
from areal_tpu.api.dfg import MFCDef, build_graph
from areal_tpu.api.model_api import GenerationHyperparameters
from areal_tpu.base.topology import MeshSpec
from areal_tpu.observability.tracing import TraceConfig


@dataclasses.dataclass
class ExperimentSaveEvalControl:
    """Frequency control for save/eval/recover-ckpt
    (reference: realhf/api/cli_args.py:702)."""

    total_train_epochs: int = 1
    save_freq_epochs: Optional[int] = None
    save_freq_steps: Optional[int] = None
    save_freq_secs: Optional[int] = None
    ckpt_freq_epochs: Optional[int] = None
    ckpt_freq_steps: Optional[int] = None
    ckpt_freq_secs: Optional[int] = None
    eval_freq_epochs: Optional[int] = None
    eval_freq_steps: Optional[int] = None
    eval_freq_secs: Optional[int] = None
    benchmark_steps: Optional[int] = None  # early exit for profiling runs


@dataclasses.dataclass
class ModelShard:
    """One model role hosted by a model worker (reference: system_api.py
    ``StandaloneModelShard``)."""

    model_name: ModelName
    model: ModelAbstraction
    backend: ModelBackendAbstraction
    mesh_spec: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    eval_dataset: Optional[DatasetAbstraction] = None


@dataclasses.dataclass
class ModelWorkerConfig:
    worker_name: str
    shards: List[ModelShard] = dataclasses.field(default_factory=list)
    # interfaces per MFC name (the worker instantiates them lazily)
    interfaces: Dict[str, ModelInterfaceAbstraction] = dataclasses.field(
        default_factory=dict
    )
    datasets: List[DatasetAbstraction] = dataclasses.field(
        default_factory=list
    )
    tokenizer_path: Optional[str] = None
    dataset_seed: int = 1
    # which DP shard of the dataset this worker loads (dp_rank, dp_size)
    dataset_shard: Tuple[int, int] = (0, 1)
    use_stream_dataset: bool = False  # async mode: data arrives by push
    stream_group_size: int = 1  # trajectories per prompt (epoch accounting)
    # publish an int8 serving tree (matmul weights quantized to int8 +
    # per-output-channel f32 scales, sibling v{N}-int8 snapshot dir)
    # next to every full-precision weight publish and advertise it in
    # the manifest.  Servers that set serving_weight_dtype="int8"
    # negotiate onto it (half the staged-swap bytes, half the serving
    # weight HBM); everyone else ignores it.  Costs ~50% extra publish
    # IO — turn off for trainers whose fleet never serves quantized.
    publish_quantized_int8: bool = True
    seed: int = 1
    # flight-recorder knobs (None = ambient process defaults)
    trace: Optional[TraceConfig] = None


@dataclasses.dataclass
class MasterWorkerConfig:
    worker_name: str = "master"
    model_rpcs: List[MFCDef] = dataclasses.field(default_factory=list)
    model_worker_names: List[str] = dataclasses.field(default_factory=list)
    # worker names hosting each model role (requests broadcast to the group)
    model_groups: Dict[str, List[str]] = dataclasses.field(
        default_factory=dict
    )
    exp_ctrl: ExperimentSaveEvalControl = dataclasses.field(
        default_factory=ExperimentSaveEvalControl
    )
    # the MFC whose n_seqs defines one train iteration
    train_rpc_name: str = ""
    seed: int = 1
    trace: Optional[TraceConfig] = None


@dataclasses.dataclass
class RolloutWorkerConfig:
    worker_name: str
    agent: AgentAbstraction = None
    env: EnvServiceAbstraction = None
    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters
    )
    datasets: List[DatasetAbstraction] = dataclasses.field(
        default_factory=list
    )
    tokenizer_path: Optional[str] = None
    dataset_shard: Tuple[int, int] = (0, 1)
    dataset_seed: int = 1
    rollout_request_timeout: float = 600.0
    new_tokens_per_chunk: int = 1 << 30  # interruptible-generation chunking
    # schedule all group siblings' first chunks in ONE manager RPC
    # (affinity co-locates them anyway); falls back per-qid against an
    # old manager that does not know the batched command
    batch_schedule: bool = True
    # SLO/tenant label this worker's traffic carries end-to-end: it
    # lands in LatencyRecord.workload (fleet-merged per-workload
    # percentile rows) and charges the matching admission-plane tenant.
    # Default: the bulk rollout tenant.
    workload: str = "rollout"
    trace: Optional[TraceConfig] = None


@dataclasses.dataclass
class GenServerConfig:
    worker_name: str
    model: ModelAbstraction = None
    mesh_spec: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    tokenizer_path: Optional[str] = None
    max_concurrent_batch: int = 64
    kv_cache_len: int = 32768
    # tokens generated fully device-side between host syncs; larger chunks
    # amortize dispatch (measured on v5e: 3.7k tok/s @64 -> 3.9k @128 for
    # the 0.5B bench model) at the cost of coarser interrupt/admission
    # granularity
    chunk_size: int = 64
    temperature: float = 1.0
    # greedy (argmax) decoding server-wide (eval servers, deterministic
    # replay)
    greedy: bool = False
    # KV layout: "auto" uses the paged block pool at kv_cache_len >= 2k
    # (global-attention models), dense per-row cache below; see
    # engine/inference_server.py.  kv_pool_tokens sizes the paged pool
    # (None = dense-equivalent max_batch * kv_cache_len — set smaller to
    # serve 32k contexts a dense cache could never reserve);
    # prefill_chunk_tokens bounds the per-step admission prefill so long
    # prompts never stall decode for a whole wave (chunked prefill)
    cache_mode: str = "auto"
    page_size: int = 1024
    kv_pool_tokens: Optional[int] = None
    # the pool of a stack's WINDOW layers, whose pages are released once
    # every holder's window has passed them (engine/kv_pages.py);
    # None = as many tokens as kv_pool_tokens.  Read only where the model
    # has such layers
    kv_window_pool_tokens: Optional[int] = None
    # paged KV storage dtype (the SGLang/vLLM --kv-cache-dtype knob):
    # "auto" stores blocks at model dtype (bit-for-bit today's
    # behavior); "int8" stores quantized pools with per-(block, head,
    # slot) f32 scales alongside — ~half the HBM per cached token (~2x
    # live rows / prefix-cache capacity / half-cost host spills at the
    # same budget), reads dequantize inline so the error is
    # storage-only.  Quality is pinned, not assumed:
    # tests/engine/test_kv_quant.py holds the greedy divergence rate on
    # a multi-turn replay under a bar, and the fleet exports the
    # areal_inference_kv_quant_* series.
    kv_cache_dtype: str = "auto"
    # serving WEIGHT storage dtype (the SGLang --quantization / vLLM
    # quantized-weight-loading knob): "auto" serves the model-dtype
    # param tree (bit-for-bit today's behavior — quantized snapshots a
    # publisher advertises are simply ignored); "int8" holds matmul
    # weights as int8 + per-output-channel f32 absmax scales
    # (models/quantize.py) — ~half the weight HBM (freed for paged
    # blocks / prefix cache) and ~half the bytes a staged weight swap
    # restores.  The format is NEGOTIATED through the publish manifest:
    # a publisher that wrote the v{N}-int8 sibling tree serves it to
    # int8 servers; one that didn't triggers a logged fall-back to the
    # full-precision tree (restored full, quantized on arrival), never
    # a crash.  Dequantization happens at use inside each projection,
    # so matmul math stays model dtype and the error is storage-only —
    # pinned, not assumed: tests/engine/test_weight_quant.py holds the
    # greedy divergence rate on a replay under a bar, and the fleet
    # exports the areal_inference_weight_quant_* series.
    serving_weight_dtype: str = "auto"
    prefill_chunk_tokens: int = 1024
    # cross-request radix prefix cache over the paged pool (default on
    # for paged mode; engine/prefix_cache.py): finished/parked sequences'
    # blocks stay indexed by token prefix so multi-turn continuations,
    # retries, and late group members prefill only their new suffix.
    # capacity_frac bounds the pool fraction the cache may hold
    # references to; min_match_tokens suppresses matches too short to
    # pay for their pin + tail copy — a tail match costs a full
    # page_size-block COW device copy, so reusing a handful of tokens
    # (every prompt shares a BOS/template head) costs more than the
    # prefill it saves.  64 keeps multi-turn/retry reuse (hundreds+ of
    # tokens) while rejecting the degenerate matches.
    prefix_cache: bool = True
    prefix_cache_capacity_frac: float = 0.5
    prefix_cache_min_match_tokens: int = 64
    # host spill tier below the HBM radix cache (the SGLang
    # hierarchical-cache / HiCache direction): evicted full-block
    # entries copy their KV into host buffers (batched device_get per
    # reclamation round) instead of dying, and a match on a spilled
    # prefix swaps the blocks back in on an async dispatch riding the
    # decode ring's overlap (admission requeued until the step after
    # dispatch — SPMD-deterministic).  Bytes-budgeted: effective cache
    # capacity multiplies by roughly host-RAM/HBM.  0 = off (default);
    # weight swaps always flush both tiers.  Single-process engines
    # only (multi-host SPMD serving auto-disables with a warning).
    prefix_cache_host_bytes: int = 0
    # P/D disaggregation: the serving role this server registers under
    # (the SGLang/vLLM prefill/decode-disaggregation deployment knob).
    # "unified" (default) serves both stages exactly as before.  With
    # both "prefill" and "decode" servers registered, the gserver
    # manager routes every NEW request to a prefill server, which runs
    # chunked prefill + first token, exports the row's paged KV blocks
    # as a handoff unit, and pushes them to the decode server that owns
    # the request; continuations sticky-route to the decode server and
    # resume with zero prefill.  Version skew across a weight swap
    # fails the handoff closed (the decode server re-prefills — stale
    # KV is never decoded).  Single-process servers only.
    role: str = "unified"
    # per-handoff timeout for the import_handoff RPC to the decode peer
    # (a dead peer must not wedge the prefill server's poll loop; on
    # timeout the continuation re-prefills on the decode server)
    handoff_request_timeout: float = 60.0
    # STREAMED handoff (default on): export each fill chunk's finalized
    # blocks as a numbered segment the moment the chunk lands — one
    # coalesced buffer per segment over the import_handoff_segment RPC,
    # pushed while later chunks still fill — and the decode server
    # pre-allocates the row's blocks on segment 0 and async-scatters
    # each segment under its own decode chunks, so the decode-side
    # resume gap is O(one chunk) instead of O(prompt).  Every segment
    # is version-checked fail-closed (skew, sequence gaps, aborts, and
    # dead peers all release the partial blocks; the continuation
    # re-prefills).  False = the PR-13 monolithic handoff unit.
    handoff_streaming: bool = True
    # how KV segments travel between servers (streamed handoffs AND
    # fleet prefix pulls).  "host-numpy" (the default and the only
    # backend in this build) materializes segment payloads on host and
    # ships them over the worker ZMQ RPC; "tpu-d2d" is the reserved
    # capability token for a device-to-device ICI/DMA backend (a server
    # registering it today fails at startup — the token exists so the
    # registration protocol and mixed-fleet negotiation are already
    # wire-stable).  The manager reads each server's token from its
    # registration value and only fabric-routes between servers whose
    # transports match.
    segment_transport: str = "host-numpy"
    # fleet KV fabric, pull side: a kv_source schedule hint triggers a
    # peer prefix pull only when the pull would cover at least this
    # many tokens beyond the local radix match (an RPC + scatter round
    # trip costs more than re-prefilling a short suffix).  The
    # manager's kv_fabric_min_prefix_tokens gates the hint fleet-side;
    # this is the engine's own floor.
    prefix_pull_min_tokens: int = 256
    # request-level SLO plane (observability/latency.py): per-request
    # latency decomposition (schedule/admission wait, TTFT, TPOT,
    # swap/preempt stall) streamed into mergeable percentile digests and
    # exported as the areal_slo_* families.  Overhead is a few clock
    # stamps per request.
    slo_tracking: bool = True
    # decode-pipeline depth: max chunks dispatched-but-unharvested (the
    # engine's in-flight ring).  2 overlaps each chunk's output fetch
    # with the next chunk's device time; raise it when the fetch RTT
    # exceeds a chunk's device time.  1 =
    # unpipelined baseline.
    pipeline_depth: int = 2
    # keep every layer's routed experts of the last finished requests
    # on the engine (``ContinuousBatchingEngine.routed_experts(qid)``),
    # in the room that N sequences of ``kv_cache_len`` positions take: at
    # least the last N, and more where they are shorter.  What a
    # routing-replay trainer or a parity check follows.  Only a
    # stack whose programs hand their routing out takes it (the hybrid
    # one); 0 keeps nothing and fetches nothing
    keep_routed_experts: int = 0
    # with ``keep_routed_experts`` on a stack with an indexer: keep, beside
    # each finished request's routing, the positions its indexed layers
    # attended at the last N positions of its prompt (the fill's mask) and
    # at its last N decode steps
    # (``ContinuousBatchingEngine.chosen_sets(qid)``); 0 fetches nothing
    keep_chosen_sets: int = 0
    # recompile sentinel (observability/compile_watch.py): engine steps
    # after which the serving loop is declared steady-state — any
    # decode/fill-path XLA compile from then on fires
    # areal_trace_stall_total{kind="recompile"} once per episode and
    # force-samples the in-flight trace roots.  0 disables the sentinel
    # (compile COUNTING always runs); size it past the bucket-ladder
    # warm-up for the deployment's longest prompts.
    compile_quiet_after_steps: int = 0
    # staged weight sync: transient HBM headroom knob for the staged
    # restore (update_weights mode="stage").  The snapshot restores in
    # layer chunks of at most this many bytes, placed directly at the
    # engine's serving shardings, so peak footprint during a stage is
    # old tree + staged-so-far + ONE chunk of restore buffers — not old
    # tree + a full host copy + a full device copy like the legacy
    # full-reload path.  None = one-shot restore (small models).
    stage_chunk_bytes: Optional[int] = 256 * 1024 * 1024
    # which local device hosts this server's engine (trainer/generation
    # device split on one host; None = default device)
    device_idx: Optional[int] = None
    # multi-host serving: when num_processes > 1 this worker is one SPMD
    # controller of a TP mesh spanning jax.distributed processes (the role
    # of the reference's multi-node SGLang servers).  Process 0 is the
    # leader: it owns the client-facing socket and broadcasts the command
    # stream; followers replay it in lockstep so every controller issues
    # identical device programs.
    coordinator: str = ""  # jax.distributed coordinator host:port
    num_processes: int = 1
    process_id: int = 0
    trace: Optional[TraceConfig] = None


@dataclasses.dataclass
class GserverManagerConfig:
    worker_name: str = "gserver_manager"
    n_servers: int = 1
    schedule_policy: str = "round_robin"
    # control-plane serve loop: "router" (default) drains a batch of
    # pending requests per tick off a ZMQ ROUTER socket, processes them
    # under one lock pass, and replies out of order — a gateway storm
    # never queues behind rollout traffic, and slow work (weight-update
    # fan-out) runs off the serve thread.  "rep" restores the legacy
    # strict-lockstep REP loop.  Wire format is identical either way:
    # legacy REQ clients speak to both.
    serve_mode: str = "router"
    # max requests drained per ROUTER serve tick (bounds the time one
    # lock pass can hold the scheduling state)
    serve_batch_max: int = 256
    # O(log N) routing: per-chip load/token min-heaps maintained
    # incrementally on the deltas scheduling already applies, plus a
    # precomputed weighted round-robin cycle rebuilt only when pool
    # membership or mesh shapes change.  False = the O(N) scans
    # (pick-for-pick identical; kept for A/B and paranoia).
    routing_index: bool = True
    max_head_offpolicyness: int = 0
    train_batch_size: int = 1  # in sequences (train_bs_n_seqs)
    group_size: int = 1  # sequences per rollout (staleness unit conversion)
    max_concurrent_rollouts: Optional[int] = None
    flush_request_timeout: float = 120.0
    # cache-aware routing: a session's turns follow the server whose
    # prefix cache is hottest for it (longest prefix served so far),
    # UNLESS that server's estimated resident tokens exceed the least-
    # loaded server's by more than imbalance_factor x + slack — then the
    # affinity breaks (the new server re-prefills; latency beats a hot
    # cache on an overloaded box).  False = the pre-cache behavior
    # (unconditional group affinity + the configured schedule_policy).
    cache_aware_routing: bool = True
    affinity_imbalance_factor: float = 1.5
    affinity_imbalance_slack_tokens: float = 4096.0
    # per-server update_weights retries before the round is declared
    # failed (one flaky server must not block the fleet's version bump)
    update_weights_retries: int = 3
    update_weights_retry_backoff_s: float = 0.5
    # zero-downtime weight sync (default on for published sharded
    # snapshots): servers restore the new snapshot into a device-resident
    # STAGING tree while decode continues (update_weights mode="stage",
    # issued to the whole fleet concurrently), then the fleet pauses only
    # for the pointer-flip commit — pause becomes max(commit) instead of
    # sum(load + transfer + apply).  A server whose stage fails falls
    # back to the legacy full reload inside the pause window, so the
    # fleet always converges on one version.  False = legacy full
    # reloads (still fanned out concurrently).
    staged_weight_updates: bool = True
    # per-server timeout for the stage RPC — generous, because staging
    # runs OFF the paused critical path (decode continues throughout)
    stage_request_timeout: float = 600.0
    # load-aware prefill admission (two-stage P/D fleets): prefill
    # servers report their in-flight prefill-token backlog through the
    # metrics RPC (scraped at most every prefill_backlog_refresh_s,
    # with optimistic local increments between scrapes) and a NEW
    # request's prefill stage goes to the least-backlog-per-chip server
    # instead of the load-blind chip-weighted rotation.  When EVERY
    # prefill server's backlog-per-chip exceeds
    # prefill_saturation_tokens_per_chip, the request is SHED: it
    # routes straight to its decode owner and serves unified-style
    # there (prefill + decode on one server) — admission pressure never
    # queues unboundedly on a saturated prefill pool.  0 disables
    # shedding; prefill_load_aware=False restores the PR-13 rotation.
    prefill_load_aware: bool = True
    prefill_backlog_refresh_s: float = 0.5
    prefill_saturation_tokens_per_chip: int = 65536
    # fleet KV fabric (cross-server prefix reuse): the manager's
    # per-session hot-prefix map doubles as a fleet prefix DIRECTORY —
    # when a session's request routes to a server other than its
    # longest-prefix owner, the schedule response carries a kv_source
    # hint and the routed engine peer-pulls the cached prefix instead
    # of re-prefilling it.  Directory entries are stamped with the
    # owner's (model version, cache flush epoch) and invalidated on
    # weight updates, server cache flushes (reported through the
    # existing metrics scrape), and server death — the directory never
    # advertises dropped prefixes.  Hints only pair servers whose
    # segment transports match.  False = hot-prefix tracking behaves
    # exactly as before (affinity only, no hints).
    kv_fabric: bool = True
    # minimum advertised prefix length (tokens) worth a pull hint — the
    # fleet-side floor mirroring the engine's prefix_pull_min_tokens
    kv_fabric_min_prefix_tokens: int = 256
    # per-tenant admission policies (gateway/admission.TenantPolicy rows
    # or plain dicts of their fields): priority class, token-bucket rate
    # limit, cumulative token budget.  Unknown tenants run under the
    # permissive interactive default; rollout traffic charges the
    # "rollout" tenant.  Empty = admit everything.
    tenants: List = dataclasses.field(default_factory=list)
    trace: Optional[TraceConfig] = None


@dataclasses.dataclass
class GatewayConfig:
    """The OpenAI-style HTTP/SSE serving gateway (gateway/server.py):
    one front-door worker per fleet, scheduling through the gserver
    manager and streaming tokens off the gen servers' harvest
    streams."""

    worker_name: str = "gateway"
    host: str = "0.0.0.0"
    port: int = 8081
    # tenant attributed to requests carrying neither an x-tenant header
    # nor a body ``user`` field
    default_tenant: str = "anonymous"
    # byte-codec vocab for string prompts (see gateway/sse.py); set to
    # the serving model's vocab size
    vocab_size: int = 256
    # real tokenizer for string prompts/completions: a HF tokenizer path
    # loaded via dataset_api.load_hf_tokenizer.  Empty = the byte-level
    # codec (token-id prompts are native either way).
    tokenizer_path: str = ""
    max_new_tokens_cap: int = 1024
    request_timeout_s: float = 600.0
    poll_interval_s: float = 0.002
    # manager RPC timeout for the gateway's admission/schedule calls
    manager_timeout_s: float = 60.0
    trace: Optional[TraceConfig] = None


@dataclasses.dataclass
class EvaluatorConfig:
    """Automatic-evaluator knobs (reference: cli_args AutomaticEvaluator —
    ours points the watcher at the saved-checkpoint tree and an eval
    dataset instead of a slurm image)."""

    dataset_path: str
    model_name: str = "actor"
    max_prompts: int = 64
    max_new_tokens: int = 256
    interval: float = 5.0
    # JAX platform policy for the eval subprocess (scheduler/evaluator.py
    # resolve_eval_env).  "auto" (default): run ON a spare local
    # accelerator whenever the experiment's workers leave one free
    # (pinned via TPU_VISIBLE_DEVICES — the reference's dedicated eval
    # partition, realhf/scheduler/evaluator.py:34), falling back to CPU
    # only when every chip is claimed.  A platform string forces it;
    # "" inherits the host platform unconditionally.
    device: str = "auto"


@dataclasses.dataclass
class ExperimentConfig:
    experiment_name: str
    trial_name: str
    master: MasterWorkerConfig
    model_workers: List[ModelWorkerConfig] = dataclasses.field(
        default_factory=list
    )
    rollout_workers: List[RolloutWorkerConfig] = dataclasses.field(
        default_factory=list
    )
    gen_servers: List[GenServerConfig] = dataclasses.field(
        default_factory=list
    )
    gserver_manager: Optional[GserverManagerConfig] = None
    gateway: Optional[GatewayConfig] = None
    evaluator: Optional[EvaluatorConfig] = None
    # experiment-wide flight-recorder config, propagated to every worker
    # that does not set its own (None = leave workers on ambient defaults)
    trace: Optional[TraceConfig] = None

    def lazy_init(self):
        """Build the MFC graph and sanity-check worker wiring
        (reference: system_api.py ExperimentConfig.lazy_init :190)."""
        build_graph(self.master.model_rpcs)
        if self.trace is not None:
            workers = [self.master, self.gserver_manager, self.gateway]
            workers += self.model_workers + self.rollout_workers
            workers += self.gen_servers
            for w in workers:
                if w is not None and w.trace is None:
                    w.trace = self.trace
        if self.gateway is not None and self.gserver_manager is None:
            raise ValueError(
                "gateway worker requires a gserver_manager (it schedules "
                "and admits through the manager's control plane)"
            )
        self.master.model_worker_names = [
            w.worker_name for w in self.model_workers
        ]
        if not self.master.model_groups:
            groups: Dict[str, List[str]] = {}
            for w in self.model_workers:
                for s in w.shards:
                    groups.setdefault(str(s.model_name), []).append(
                        w.worker_name
                    )
            self.master.model_groups = groups
        for rpc in self.master.model_rpcs:
            if str(rpc.model_name) not in self.master.model_groups:
                raise ValueError(
                    f"MFC {rpc.name}: no worker hosts {rpc.model_name}"
                )
        if not self.master.train_rpc_name:
            from areal_tpu.api.dfg import ModelInterfaceType

            trains = [
                r
                for r in self.master.model_rpcs
                if r.interface_type == ModelInterfaceType.TRAIN_STEP
            ]
            if trains:
                self.master.train_rpc_name = trains[0].name
        return self


# ---------------------------------------------------------------------------
# Experiment registry (reference :457-488)
# ---------------------------------------------------------------------------


class Experiment:
    """User-facing experiment: produces an ExperimentConfig."""

    def initial_setup(self) -> ExperimentConfig:
        raise NotImplementedError()


_EXPERIMENTS: Dict[str, Callable[[], Experiment]] = {}


def register_experiment(name: str, cls: Callable[[], Experiment]):
    if name in _EXPERIMENTS:
        raise KeyError(f"experiment {name} already registered")
    _EXPERIMENTS[name] = cls


def make_experiment(name: str, *args, **kwargs) -> Experiment:
    return _EXPERIMENTS[name](*args, **kwargs)


def experiment_cls(name: str) -> Callable[[], Experiment]:
    if name not in _EXPERIMENTS:
        raise KeyError(
            f"unknown experiment {name!r}; registered: {sorted(_EXPERIMENTS)}"
        )
    return _EXPERIMENTS[name]


def list_experiments() -> List[str]:
    return sorted(_EXPERIMENTS)
