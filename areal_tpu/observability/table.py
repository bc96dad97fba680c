"""Canonical metric AND trace name tables.

Single source of truth for every Prometheus series the system emits and
every flight-recorder span/event name it records.  The registry resolves
HELP text from here, ``docs/observability.md`` renders from here, and
``scripts/check_metric_names.py`` (run in tier-1) asserts that every name
emitted anywhere in the codebase appears EXACTLY once in its table — so a
typo'd or renamed metric/span fails CI instead of silently forking a
series (or leaving an undocumented trace name nobody can query for).

The tables are *lists* (not dicts) precisely so an accidental duplicate
entry is representable and the lint can catch it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    type: str  # "counter" | "gauge" | "histogram"
    help: str
    labels: Tuple[str, ...] = ()


METRIC_TABLE = [
    # -- worker substrate (system/worker_base.py) ---------------------------
    MetricSpec(
        "areal_worker_info",
        "gauge",
        "Constant 1 per live worker; labels identify it",
        ("worker", "group"),
    ),
    MetricSpec(
        "areal_worker_uptime_seconds",
        "gauge",
        "Seconds since the worker's server started",
    ),
    # -- inference engine (engine/inference_server.py) ----------------------
    MetricSpec(
        "areal_inference_chunks_total",
        "counter",
        "Decode chunks harvested by the continuous-batching engine",
    ),
    MetricSpec(
        "areal_inference_host_seconds_total",
        "counter",
        "Engine-loop time spent on host bookkeeping (admit/schedule/park)",
    ),
    MetricSpec(
        "areal_inference_device_seconds_total",
        "counter",
        "Engine-loop time blocked waiting for device compute to finish",
    ),
    MetricSpec(
        "areal_inference_fetch_seconds_total",
        "counter",
        "Engine-loop time fetching chunk outputs to host (PCIe)",
    ),
    MetricSpec(
        "areal_inference_generated_tokens_total",
        "counter",
        "New tokens handed to rows, first tokens included; moves as "
        "tokens are emitted (the engine's tokens_emitted_total), not "
        "when a row finishes",
    ),
    MetricSpec(
        "areal_inference_phase_seconds_total",
        "counter",
        "Engine-thread self seconds by phase span (the areal.engine.* "
        "names of the trace table): they add up to the wall time of the "
        "engine's steps",
        ("phase",),
    ),
    MetricSpec(
        "areal_inference_kv_pages_live",
        "gauge",
        "Pool blocks referenced by rows that are decoding or filling, "
        "each block once however many siblings share it; parked rows "
        "and prefix-cache holdings are not live",
    ),
    MetricSpec(
        "areal_inference_kv_pages_total",
        "gauge",
        "Blocks of the paged KV pool (0 on a dense-cache engine)",
    ),
    MetricSpec(
        "areal_inference_state_slots_live",
        "gauge",
        "Recurrent-state slots (SSM state + conv tail per Mamba layer, "
        "one a batch row) held by rows that decode or fill; 0 for a "
        "model without such state",
    ),
    MetricSpec(
        "areal_inference_state_slots_total",
        "gauge",
        "Recurrent-state slots the engine holds (one a batch row; 0 for "
        "a model without such state)",
    ),
    MetricSpec(
        "areal_inference_moe_expert_pairs",
        "gauge",
        "(token, k) pairs decode chunks have routed to each expert this "
        "server holds, since it started (a stack stated by kind only)",
        ("expert",),
    ),
    MetricSpec(
        "areal_inference_moe_fill_tokens",
        "gauge",
        "Prompt tokens that went through the expert layers of a fill "
        "batch since the server started (a stack stated by kind only)",
    ),
    MetricSpec(
        "areal_inference_moe_fill_tokens_grouped",
        "gauge",
        "Those of areal_inference_moe_fill_tokens in a batch whose shape "
        "takes the grouped product over the held experts (the routed "
        "pairs only: moe.group_rows), not the product over every held "
        "expert for every token",
    ),
    MetricSpec(
        "areal_inference_moe_fill_extra_rounds",
        "gauge",
        "Rounds past the first that the fills' grouped products took, "
        "summed over their expert layers (a round holds moe.group_rows "
        "pairs an expert; the busiest expert's pairs decide how many a "
        "layer takes); the fills still on the device are not in it yet",
    ),
    MetricSpec(
        "areal_inference_moe_groups_hit",
        "gauge",
        "(token, chosen group) pairs of a group-limited router whose "
        "group has an expert this server holds, over decode chunks since "
        "it started (0 for any other router)",
    ),
    MetricSpec(
        "areal_inference_prefill_tokens_total",
        "counter",
        "Unique-prompt tokens actually prefilled (post group-dedup)",
    ),
    MetricSpec(
        "areal_inference_async_fetches_total",
        "counter",
        "Decode chunks whose outputs started an async device-to-host "
        "copy at dispatch time (the fetch-overlap half of the pipeline)",
    ),
    MetricSpec(
        "areal_inference_fetch_ready_total",
        "counter",
        "Harvests that found the oldest in-flight chunk already complete "
        "(its output fetch fully overlapped by newer chunks' device time)",
    ),
    MetricSpec(
        "areal_inference_prefix_cache_hits_total",
        "counter",
        "Admissions whose prompt matched a cached prefix in the "
        "cross-request radix cache (suffix-only prefill)",
    ),
    MetricSpec(
        "areal_inference_prefix_cache_misses_total",
        "counter",
        "Admissions that found no usable cached prefix",
    ),
    MetricSpec(
        "areal_inference_prefix_cached_tokens_total",
        "counter",
        "Prompt tokens served from the radix prefix cache instead of "
        "being re-prefilled",
    ),
    MetricSpec(
        "areal_inference_prefix_cache_evictions_total",
        "counter",
        "Radix-cache entries dropped (LRU capacity trims + pool-pressure "
        "reclamation yielding blocks to live rows)",
    ),
    MetricSpec(
        "areal_inference_prefix_cache_blocks",
        "gauge",
        "Pool blocks currently referenced by the radix prefix cache",
    ),
    MetricSpec(
        "areal_inference_prefix_host_spilled_blocks_total",
        "counter",
        "Radix-cache blocks spilled from HBM into the host tier instead "
        "of dying on eviction (batched device-to-host gather per "
        "reclamation round)",
    ),
    MetricSpec(
        "areal_inference_prefix_host_restored_blocks_total",
        "counter",
        "Host-tier blocks swapped back into freshly allocated pool "
        "blocks after a prefix match landed on a spilled entry (async "
        "dispatch riding the decode ring's overlap)",
    ),
    MetricSpec(
        "areal_inference_prefix_host_dropped_blocks_total",
        "counter",
        "Host-tier entries dropped outright (byte-budget LRU trims, "
        "orphaned spilled subtrees, weight-swap flushes)",
    ),
    MetricSpec(
        "areal_inference_prefix_host_bytes",
        "gauge",
        "Host memory currently held by spilled prefix-cache blocks "
        "(bounded by prefix_cache_host_bytes)",
    ),
    MetricSpec(
        "areal_inference_prefix_host_blocks",
        "gauge",
        "Prefix-cache blocks currently resident in the host tier",
    ),
    MetricSpec(
        "areal_inference_kv_quant_storage_bits",
        "gauge",
        "Bits per stored KV element in the serving cache (8 = int8 "
        "quantized pools with per-(block, head, slot) scales; 16/32 = "
        "model-dtype storage, kv_cache_dtype='auto')",
    ),
    MetricSpec(
        "areal_inference_kv_quant_blocks",
        "gauge",
        "Pool blocks currently held in quantized (int8) storage — live "
        "rows, prefix-cache references, and in-flight fills together; 0 "
        "on an unquantized engine",
    ),
    MetricSpec(
        "areal_inference_kv_quant_divergence_checks_total",
        "counter",
        "Greedy-divergence checks folded into the engine by quality "
        "harnesses (parity tests comparing the int8 arm against an fp arm "
        "token by token)",
    ),
    MetricSpec(
        "areal_inference_kv_quant_divergence_diverged_total",
        "counter",
        "Checked requests whose int8-arm greedy stream diverged from "
        "the fp arm's (the measured token-quality delta the quantized "
        "serving rollout is gated on)",
    ),
    MetricSpec(
        "areal_inference_weight_quant_storage_bits",
        "gauge",
        "Bits per stored element of the serving param tree's matmul "
        "weights (8 = int8 + per-output-channel scales, "
        "serving_weight_dtype='int8'; 16/32 = model-dtype storage)",
    ),
    MetricSpec(
        "areal_inference_weight_quant_leaves",
        "gauge",
        "Projection leaves of the RESIDENT serving tree held in "
        "quantized {int8 weight, f32 scale} form — 0 on a "
        "full-precision engine",
    ),
    MetricSpec(
        "areal_inference_weight_quant_divergence_checks_total",
        "counter",
        "Greedy-divergence checks folded into the engine by quality "
        "harnesses (parity tests comparing the int8-weight arm against a "
        "full-precision arm token by token)",
    ),
    MetricSpec(
        "areal_inference_weight_quant_divergence_diverged_total",
        "counter",
        "Checked requests whose int8-weight greedy stream diverged "
        "from the full-precision arm's (the measured token-quality "
        "delta the quantized-weight serving rollout is gated on)",
    ),
    MetricSpec(
        "areal_inference_handoff_exports_total",
        "counter",
        "Paged-block KV handoff units exported by a prefill-role server "
        "(one per request handed to a decode peer)",
    ),
    MetricSpec(
        "areal_inference_handoff_imports_total",
        "counter",
        "Handoff units imported and parked by a decode-role server "
        "(the continuation resumes over them with zero prefill)",
    ),
    MetricSpec(
        "areal_inference_handoff_import_rejects_total",
        "counter",
        "Handoff imports rejected fail-closed, by reason (version = "
        "weight-swap skew; layout | dense | capacity | pool | empty | "
        "scatter; streamed handoffs add stream = sequence gap/unknown "
        "stream, abort = exporter cut the stream short, expired = the "
        "dead-peer TTL released a half-received stream); the "
        "continuation re-prefills on the decode server",
        ("reason",),
    ),
    MetricSpec(
        "areal_inference_handoff_segment_exports_total",
        "counter",
        "Streamed-handoff segments exported by a prefill-role server "
        "(one per fill-chunk boundary of a handoff-flagged row, plus "
        "the final tail+metadata segment)",
    ),
    MetricSpec(
        "areal_inference_handoff_segment_imports_total",
        "counter",
        "Streamed-handoff segments imported and scattered by a "
        "decode-role server (the scatters ride under its decode chunks "
        "while the prefill side is still filling)",
    ),
    MetricSpec(
        "areal_inference_handoff_segment_aborts_total",
        "counter",
        "Export streams cut short by the prefill server (EOS at the "
        "first token, a weight swap restarting the fill) — the decode "
        "peer releases its partial blocks",
    ),
    MetricSpec(
        "areal_inference_handoff_bytes_total",
        "counter",
        "Host bytes moved by KV handoffs (export gathers + import "
        "scatters; int8 pools move quantized bytes + scales)",
    ),
    MetricSpec(
        "areal_inference_handoff_seconds_total",
        "counter",
        "Time spent in KV-handoff device<->host block copies (export "
        "gather on the prefill side + import scatter dispatch on the "
        "decode side)",
    ),
    MetricSpec(
        "areal_inference_prefix_peer_pulls_total",
        "counter",
        "Fleet KV-fabric prefix pulls COMPLETED by this engine (a peer's "
        "cached prefix imported segment by segment and radix-inserted; "
        "the admission's re-prefill shrank to the un-pulled suffix)",
    ),
    MetricSpec(
        "areal_inference_prefix_peer_pull_bytes_total",
        "counter",
        "Host bytes imported by completed fleet prefix pulls (int8 "
        "pools move quantized bytes + scales)",
    ),
    MetricSpec(
        "areal_inference_prefix_peer_pull_rejects_total",
        "counter",
        "Fleet prefix pulls failed closed, by reason (version = weight-"
        "swap skew mid-pull; layout | dense | pool | scatter | stream "
        "mirror the handoff-segment rules; miss = the owner no longer "
        "held the prefix; rpc = the export call to the owner died; "
        "spmd = a multi-controller owner refused the export; expired = "
        "the dead-owner TTL) — the admission re-prefills plainly",
        ("reason",),
    ),
    MetricSpec(
        "areal_inference_inflight_rows",
        "gauge",
        "Rows currently decoding or chunk-filling",
    ),
    MetricSpec(
        "areal_inference_ring_depth",
        "gauge",
        "Configured decode-pipeline depth (max in-flight decode chunks)",
    ),
    MetricSpec(
        "areal_inference_inflight_chunks",
        "gauge",
        "Decode chunks currently dispatched but not yet harvested "
        "(pipeline-ring occupancy; bounded by areal_inference_ring_depth)",
    ),
    MetricSpec(
        "areal_inference_pending_requests",
        "gauge",
        "Requests queued for admission",
    ),
    MetricSpec(
        "areal_inference_mesh_devices",
        "gauge",
        "Chips this engine's sharded forward spans (one server = one "
        "mesh; 1 for a single-chip engine)",
    ),
    MetricSpec(
        "areal_inference_weight_version",
        "gauge",
        "Weight version the engine currently serves",
    ),
    MetricSpec(
        "areal_inference_swap_stage_seconds_total",
        "counter",
        "Time spent restoring/transferring staged weight trees while "
        "decode continued (the off-critical-path half of a staged swap)",
    ),
    MetricSpec(
        "areal_inference_swap_pause_seconds_total",
        "counter",
        "Time weight swaps actually interrupted decode (ring drain + "
        "pointer flip or full reload + prefix flush + in-flight "
        "recompute)",
    ),
    MetricSpec(
        "areal_inference_weight_swaps_total",
        "counter",
        "Weight swaps applied by the engine (staged pointer-flips + "
        "legacy full reloads)",
    ),
    MetricSpec(
        "areal_inference_weight_swaps_staged_total",
        "counter",
        "Weight swaps applied as staged pointer-flips (pre-restored, "
        "zero transfer inside the pause)",
    ),
    # -- request-level SLO plane (observability/latency.py consumers) --------
    # Each family is a histogram over the FIXED log-bucket boundaries
    # latency.SLO_BUCKETS, so the master can rebuild + exactly merge
    # per-worker digests into fleet percentiles (the lint asserts this
    # vocabulary matches latency.SLO_FAMILIES both ways).
    MetricSpec(
        "areal_slo_schedule_wait_seconds",
        "histogram",
        "Time a rollout waited at the gserver manager's admission gate "
        "(first rejected allocate to the eventual ok; 0 when admitted "
        "immediately) — SLO digest, fixed log buckets",
        ("workload",),
    ),
    MetricSpec(
        "areal_slo_admission_wait_seconds",
        "histogram",
        "Time a request queued at the engine between submit and cache-"
        "row admission — SLO digest, fixed log buckets",
        ("workload",),
    ),
    MetricSpec(
        "areal_slo_ttft_seconds",
        "histogram",
        "Time to first token: engine submit to the first generated "
        "token (queue + prefill) — SLO digest, fixed log buckets",
        ("workload",),
    ),
    MetricSpec(
        "areal_slo_tpot_seconds",
        "histogram",
        "Per-token time: mean inter-token gap after the first token, "
        "one observation per finished request — SLO digest, fixed log "
        "buckets",
        ("workload",),
    ),
    MetricSpec(
        "areal_slo_stall_seconds",
        "histogram",
        "Time a request spent quiesced by weight swaps or parked by "
        "preemption while in flight — SLO digest, fixed log buckets",
        ("workload",),
    ),
    # -- gserver manager (system/gserver_manager.py) -------------------------
    MetricSpec(
        "areal_gserver_alloc_rejections_total",
        "counter",
        "Rollout allocations rejected, by reason (staled | capacity)",
        ("reason",),
    ),
    MetricSpec(
        "areal_gserver_running_rollouts",
        "gauge",
        "Rollouts currently in flight (queue depth of the staleness gate)",
    ),
    MetricSpec(
        "areal_gserver_accepted_rollouts_total",
        "counter",
        "Rollouts finished and accepted",
    ),
    MetricSpec(
        "areal_gserver_model_version",
        "gauge",
        "Latest weight version pushed to the generation servers",
    ),
    MetricSpec(
        "areal_gserver_version_lag",
        "gauge",
        "expected_version - model_version (staleness headroom consumed)",
    ),
    MetricSpec(
        "areal_gserver_server_requests",
        "gauge",
        "Sticky requests resident per generation server",
        ("server",),
    ),
    MetricSpec(
        "areal_gserver_server_tokens",
        "gauge",
        "Estimated resident tokens per generation server",
        ("server",),
    ),
    MetricSpec(
        "areal_gserver_server_mesh_devices",
        "gauge",
        "Chips behind each generation server's mesh (registration-"
        "derived; routing and capacity weights scale with it)",
        ("server",),
    ),
    MetricSpec(
        "areal_gserver_affinity_escapes_total",
        "counter",
        "Sessions re-routed away from their prefix-hot server because "
        "the load-imbalance escape hatch fired",
    ),
    MetricSpec(
        "areal_gserver_pd_role_servers",
        "gauge",
        "Registered generation servers per serving role (prefill | "
        "decode | unified); two-stage P/D routing is active iff both "
        "prefill and decode are nonzero",
        ("role",),
    ),
    MetricSpec(
        "areal_gserver_pd_handoff_routes_total",
        "counter",
        "New requests routed through the two-stage prefill->handoff->"
        "decode path (continuations sticky-route and are not counted)",
    ),
    MetricSpec(
        "areal_gserver_prefill_backlog_tokens",
        "gauge",
        "Estimated in-flight prefill-token backlog per prefill server "
        "(metrics-RPC scrape + optimistic local increments) — the load "
        "signal least-backlog prefill admission routes on",
        ("server",),
    ),
    MetricSpec(
        "areal_gserver_prefill_sheds_total",
        "counter",
        "New requests shed to unified-style serving on their decode "
        "owner because every prefill server's backlog-per-chip "
        "exceeded prefill_saturation_tokens_per_chip",
    ),
    MetricSpec(
        "areal_gserver_kv_fabric_directory_entries",
        "gauge",
        "Live entries in the manager's fleet prefix directory (version-"
        "and-flush-epoch-stamped hot-prefix records a kv_source pull "
        "hint may cite)",
    ),
    MetricSpec(
        "areal_gserver_kv_fabric_pull_routes_total",
        "counter",
        "Schedule responses that carried a kv_source hint (the routed "
        "engine peer-pulls the named owner's cached prefix instead of "
        "re-prefilling it)",
    ),
    MetricSpec(
        "areal_gserver_kv_fabric_invalidations_total",
        "counter",
        "Fleet prefix-directory entries dropped, by reason "
        "(weight_update = fleet-wide flush on a version bump; flush = "
        "the owner's scraped prefix_cache_flushes_total moved; death = "
        "consecutive failed epoch scrapes declared the owner dead)",
        ("reason",),
    ),
    MetricSpec(
        "areal_gserver_weight_update_pause_seconds",
        "gauge",
        "Fleet pause of the most recent weight update (pause RPCs to "
        "resume RPCs) — staged rounds pay max(commit), legacy rounds "
        "pay the full reload",
    ),
    MetricSpec(
        "areal_gserver_weight_updates_total",
        "counter",
        "Fleet weight-update rounds attempted, by protocol "
        "(staged | full)",
        ("mode",),
    ),
    MetricSpec(
        "areal_gserver_control_serve_batch_size",
        "histogram",
        "Requests drained per ROUTER serve tick (batch size; the "
        "strict-lockstep rep mode never batches, so this family only "
        "moves under serve_mode=router)",
    ),
    MetricSpec(
        "areal_gserver_control_queue_depth",
        "gauge",
        "Control-plane requests pending at the start of the most "
        "recent serve tick (drained backlog on the ROUTER socket)",
    ),
    MetricSpec(
        "areal_gserver_control_requests_total",
        "counter",
        "Control-plane commands handled, by command name "
        "(schedule_request | schedule_batch | gateway_submit | ...)",
        ("cmd",),
    ),
    MetricSpec(
        "areal_gserver_control_handler_seconds_total",
        "counter",
        "Cumulative seconds spent inside control-plane command "
        "handlers, by command name — divide by requests_total for "
        "mean handler latency",
        ("cmd",),
    ),
    # -- serving gateway (gateway/server.py + admission plane) ---------------
    MetricSpec(
        "areal_gateway_requests_total",
        "counter",
        "HTTP requests received at the gateway front door "
        "(/v1/completions + /v1/chat/completions, streaming or not)",
    ),
    MetricSpec(
        "areal_gateway_streams_total",
        "counter",
        "SSE streaming responses started at the gateway",
    ),
    MetricSpec(
        "areal_gateway_active_streams",
        "gauge",
        "SSE streams currently open at the gateway",
    ),
    MetricSpec(
        "areal_gateway_admission_rejects_total",
        "counter",
        "Tenant admission-plane rejects, by typed reason "
        "(rate_limited | budget_exhausted | request_too_large) — "
        "incremented at the gateway front door (HTTP 429/403) and at "
        "the gserver manager's gateway_admit command",
        ("reason",),
    ),
    MetricSpec(
        "areal_gateway_preemptions_total",
        "counter",
        "Pool-pressure row preemptions by the victim's priority class "
        "(interactive | bulk) — priority-aware eviction picks bulk "
        "rollout rows before interactive gateway rows",
        ("class",),
    ),
    # -- master buffer (system/buffer.py) ------------------------------------
    MetricSpec(
        "areal_buffer_size",
        "gauge",
        "Sequences resident in the master's sequence buffer",
    ),
    MetricSpec(
        "areal_buffer_oldest_sample_age_seconds",
        "gauge",
        "Age of the oldest buffered sequence (birth-time to now)",
    ),
    # -- train engine (engine/train_engine.py) -------------------------------
    MetricSpec(
        "areal_train_step_seconds",
        "histogram",
        "Wall time of one train_batch call (pad + dispatch + host sync)",
        ("model",),
    ),
    MetricSpec(
        "areal_train_tokens_total",
        "counter",
        "Real (non-padding) tokens consumed by train steps",
        ("model",),
    ),
    MetricSpec(
        "areal_train_tokens_per_second",
        "gauge",
        "Token throughput of the most recent train step",
        ("model",),
    ),
    MetricSpec(
        "areal_train_mfu",
        "gauge",
        "Model FLOPs utilization of the most recent train step (0-1)",
        ("model",),
    ),
    MetricSpec(
        "areal_train_padding_frac",
        "gauge",
        "Fraction of the most recent train step's stacked [n, B, T] "
        "device slots that held padding (incl. all-zero bucketing "
        "micro-batches) — the waste sequence packing exists to shrink",
        ("model",),
    ),
    MetricSpec(
        "areal_train_version",
        "gauge",
        "Optimizer-step count of this engine (published weight version)",
        ("model",),
    ),
    # -- rollout worker (system/rollout_worker.py) ---------------------------
    MetricSpec(
        "areal_rollout_episodes_total",
        "counter",
        "Rollout episodes finished (accepted or not)",
    ),
    MetricSpec(
        "areal_rollout_pushed_total",
        "counter",
        "Trajectories pushed to the training stream",
    ),
    MetricSpec(
        "areal_rollout_alloc_rejected_total",
        "counter",
        "allocate_rollout denials observed, by reason",
        ("reason",),
    ),
    # -- host/device monitor (base/monitor.py) -------------------------------
    MetricSpec("areal_host_load1", "gauge", "Host 1-minute load average"),
    MetricSpec("areal_host_load5", "gauge", "Host 5-minute load average"),
    MetricSpec("areal_host_rss_gb", "gauge", "Worker process RSS in GB"),
    MetricSpec(
        "areal_device_hbm_in_use_gb",
        "gauge",
        "HBM bytes in use per local device, in GB",
        ("device",),
    ),
    MetricSpec(
        "areal_device_hbm_peak_gb",
        "gauge",
        "Peak HBM bytes in use per local device, in GB",
        ("device",),
    ),
    MetricSpec(
        "areal_device_hbm_limit_gb",
        "gauge",
        "HBM capacity per local device, in GB",
        ("device",),
    ),
    MetricSpec(
        "areal_time_mark_seconds",
        "histogram",
        "Named wall-clock intervals recorded via monitor.time_mark",
        ("mark",),
    ),
    # -- HBM ledger (observability/hbm_ledger.py) ----------------------------
    MetricSpec(
        "areal_hbm_ledger_bytes",
        "gauge",
        "Bytes currently attributed to each subsystem by the device-"
        "memory ledger (see hbm_ledger.SUBSYSTEMS for the tag catalogue; "
        "host-side tags carry host bytes)",
        ("subsystem",),
    ),
    MetricSpec(
        "areal_hbm_ledger_peak_bytes",
        "gauge",
        "High-watermark bytes each ledger subsystem ever held (resets "
        "with the process; the capacity-planning ceiling)",
        ("subsystem",),
    ),
    MetricSpec(
        "areal_hbm_ledger_drift_gb",
        "gauge",
        "Excess of the ledger's device-tag sum over the device's "
        "reported HBM in-use bytes, in GB (0 while sum(ledger) <= "
        "in_use + tolerance; nonzero = the ledger double-counts or a "
        "release was missed)",
    ),
    # -- recompile sentinel (observability/compile_watch.py) -----------------
    MetricSpec(
        "areal_xla_compiles_total",
        "counter",
        "XLA compiles observed per watched entry point (jitted-cache "
        "growth) plus the process-wide backend_compile events under "
        "fn=backend",
        ("fn",),
    ),
    MetricSpec(
        "areal_xla_compile_seconds",
        "histogram",
        "Backend-compile durations reported by jax.monitoring "
        "(process-wide; per-fn attribution rides "
        "areal_xla_compiles_total)",
    ),
    # -- master / stats fan-in (system/master_worker.py) ---------------------
    MetricSpec(
        "areal_master_step_seconds",
        "histogram",
        "End-to-end wall time of one master step (full MFC graph)",
    ),
    MetricSpec(
        "areal_stats",
        "gauge",
        "Scalar stats exported from the hierarchical stats tracker",
        ("key",),
    ),
    # -- aggregator self-metrics (observability/aggregator.py) ---------------
    MetricSpec(
        "areal_aggregator_scrape_errors_total",
        "counter",
        "Failed /metrics scrapes, by endpoint key",
        ("endpoint",),
    ),
    # -- flight recorder (observability/tracing.py + trace_collector.py) -----
    MetricSpec(
        "areal_trace_stall_total",
        "counter",
        "Stall-watchdog flags, by kind (the STALL_KINDS vocabulary "
        "below); each stalled span / breach episode counts once",
        ("kind",),
    ),
    MetricSpec(
        "areal_trace_harvest_errors_total",
        "counter",
        "Failed /trace harvests, by endpoint key (skip-and-count: a dead "
        "or garbage endpoint never fails a master step)",
        ("endpoint",),
    ),
    MetricSpec(
        "areal_trace_events_total",
        "counter",
        "Flight-recorder events harvested into traces.jsonl",
    ),
    MetricSpec(
        "areal_train_sample_staleness",
        "histogram",
        "Per-trained-sample weight-version lag: current version minus "
        "the version the sample finished generating under",
        ("model",),
    ),
]


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """One canonical trace name.  ``kind`` is "span" (the flight
    recorder's span_begin/span_end/span — a duration of one SAMPLE),
    "event" (instant), "phase" (``tracing.phase`` — what a THREAD is
    doing, a ``jax.profiler.TraceAnnotation`` in the profiler's own
    trace; the name starts with ``areal.`` and the help text names the
    counts the span carries) or "region" (``tracing.region`` — which part
    of a step program a DEVICE operation belongs to, a
    ``jax.named_scope``; same prefix)."""

    name: str
    kind: str  # "span" | "event" | "phase" | "region"
    help: str


TRACE_TABLE = [
    # -- rollout worker / partial rollout ------------------------------------
    TraceSpec(
        "rollout.episode",
        "span",
        "One rollout episode on the rollout worker: allocate -> agent/env "
        "loop -> push -> finish (attrs: accepted, pushed)",
    ),
    TraceSpec(
        "rollout.alloc_reject",
        "event",
        "allocate_rollout denial observed worker-side (attrs: reason)",
    ),
    TraceSpec(
        "rollout.generate",
        "span",
        "One group member's full generation across all chunked "
        "continuations (attrs: chunks, retries, version_start/end)",
    ),
    TraceSpec(
        "rollout.chunk",
        "span",
        "One schedule+generate chunk attempt from the partial-rollout "
        "client (attrs: attempt, gen_qid, server)",
    ),
    TraceSpec(
        "rollout.retry",
        "event",
        "Transient RPC failure during schedule/generate; the trace root "
        "is force-sampled from here on (attrs: stage, attempt, error)",
    ),
    # -- gserver manager -----------------------------------------------------
    TraceSpec(
        "gserver.allocate",
        "event",
        "Staleness/capacity gate decision for a rollout (attrs: ok, "
        "reason, version_lag)",
    ),
    TraceSpec(
        "gserver.schedule",
        "event",
        "Routing decision for a request (attrs: server, sticky, "
        "prompt_len, version)",
    ),
    TraceSpec(
        "gserver.handoff_route",
        "event",
        "New request routed through the two-stage P/D path (attrs: "
        "prefill = the server filling the blocks, decode = the server "
        "owning the request after the handoff)",
    ),
    TraceSpec(
        "gserver.kv_fabric_route",
        "event",
        "Schedule response carried a kv_source pull hint (attrs: "
        "target = the routed server, source = the prefix owner, "
        "prompt_len)",
    ),
    TraceSpec(
        "gserver.finish",
        "event",
        "Rollout slot released at the manager (attrs: accepted)",
    ),
    TraceSpec(
        "gserver.gateway_admit",
        "event",
        "Tenant admission-plane decision for a gateway request "
        "(attrs: tenant, ok, reason)",
    ),
    # -- generation engine ---------------------------------------------------
    TraceSpec(
        "engine.admit",
        "event",
        "Request admitted into a cache row (attrs: row, cached_tokens "
        "from the radix prefix cache, prompt_len)",
    ),
    TraceSpec(
        "engine.resume",
        "event",
        "Parked row resumed for a chunked continuation with zero "
        "prefill (attrs: row)",
    ),
    TraceSpec(
        "engine.fill_chunk",
        "event",
        "One chunked-prefill batch advanced this request's fill "
        "(attrs: tokens, fill_pos)",
    ),
    TraceSpec(
        "engine.chunk",
        "event",
        "One harvested decode chunk's tokens folded into this row "
        "(attrs: row, epoch, n_tokens, step)",
    ),
    TraceSpec(
        "swap.stage",
        "span",
        "Staged weight restore on the generation server: snapshot "
        "restore -> device-resident staging tree, while decode "
        "continues (attrs: version; root swap-v{n}, force-sampled)",
    ),
    TraceSpec(
        "swap.commit",
        "span",
        "The weight-swap apply window that actually interrupts decode: "
        "ring drain -> pointer flip (or legacy full reload) -> prefix "
        "flush -> in-flight recompute (attrs: version, pre_sharded, "
        "interrupted)",
    ),
    TraceSpec(
        "engine.handoff_export",
        "event",
        "Parked prefill row's KV blocks gathered to host and exported "
        "as a handoff unit (attrs: row, blocks, bytes, version)",
    ),
    TraceSpec(
        "engine.handoff_import",
        "event",
        "Handoff unit imported (scattered into fresh pool blocks and "
        "parked for resume) or rejected fail-closed (attrs: ok, reason "
        "on reject, row, blocks, bytes, version; streamed=True when the "
        "final segment of a streamed handoff parked the row)",
    ),
    TraceSpec(
        "engine.handoff_segment",
        "event",
        "One streamed-handoff segment exported at a fill-chunk boundary "
        "(attrs: seq, blocks, bytes, final, version; abort=True with a "
        "reason when the exporter cut the stream short)",
    ),
    TraceSpec(
        "engine.handoff_segment_import",
        "event",
        "One streamed-handoff segment scattered into the decode "
        "server's pre-allocated blocks (attrs: seq, blocks, bytes, "
        "final, version)",
    ),
    TraceSpec(
        "engine.prefix_export",
        "event",
        "Owner side of a fleet prefix pull: the cached run covering the "
        "peer's tokens gathered into wire segments (attrs: blocks, "
        "tokens, segments, version)",
    ),
    TraceSpec(
        "engine.prefix_pull",
        "event",
        "Puller side of a fleet prefix pull: intent registered (attrs: "
        "source, prompt_len, resident), completed (ok=True, blocks, "
        "tokens, bytes), or failed closed (ok=False, reason)",
    ),
    TraceSpec(
        "engine.finish",
        "event",
        "Row finished or parked; the request's result is ready "
        "(attrs: park, n_tokens, version_start, version_end)",
    ),
    TraceSpec(
        "engine.preempt",
        "event",
        "Row preempted under pool pressure (recompute-on-readmit; "
        "attrs: row, cached_tokens)",
    ),
    TraceSpec(
        "engine.cancel",
        "event",
        "Request cancelled (gateway client disconnect or stale-stream "
        "backstop); the row's pool blocks are released (attrs: step)",
    ),
    TraceSpec(
        "engine.recompute",
        "event",
        "In-flight row's KV re-prefilled under freshly swapped weights "
        "(attrs: version)",
    ),
    # -- master buffer / train -----------------------------------------------
    TraceSpec(
        "buffer.resident",
        "span",
        "Sample resident in the master sequence buffer, push to final "
        "consumption (attrs: version = version_end at push)",
    ),
    TraceSpec(
        "buffer.consume",
        "event",
        "Sample handed to an MFC from the buffer (attrs: rpc)",
    ),
    TraceSpec(
        "train.consume",
        "event",
        "Sample consumed by a train step (attrs: step, staleness, model)",
    ),
    # -- recompile sentinel --------------------------------------------------
    TraceSpec(
        "xla.compile",
        "span",
        "One detected XLA compile of a watched entry point (attrs: fn, "
        "n new cache entries, the caller-provided shape/dtype "
        "signature, secs when jax.monitoring reported a duration)",
    ),
    # -- phase spans: generation server thread --------------------------------
    TraceSpec(
        "areal.gserver.poll",
        "phase",
        "One poll of the leading generation server: commands in, one "
        "engine step, replies and metrics out",
    ),
    TraceSpec(
        "areal.gserver.serve_api",
        "phase",
        "Client requests drained into this poll's command batch, with "
        "the stale-stream and prefix-pull pumps (counts: commands)",
    ),
    TraceSpec(
        "areal.gserver.apply_commands",
        "phase",
        "The command batch applied to the engine (counts: commands)",
    ),
    TraceSpec(
        "areal.gserver.reply",
        "phase",
        "Finished and staged results sent back to their clients, "
        "handoff streams pumped (counts: replies)",
    ),
    TraceSpec(
        "areal.gserver.export_metrics",
        "phase",
        "The engine's counters copied into the metrics registry, the "
        "HBM ledger published, the jitted caches diffed",
    ),
    # -- phase spans: the engine's step, on the same thread -------------------
    TraceSpec(
        "areal.engine.step",
        "phase",
        "One engine step, the paused branch's sleep excluded (counts at "
        "its end: step, rows_decoding, rows_filling, pending, ring, "
        "tokens_emitted_total); one lap of the engine's PhaseClock, "
        "whose record (ENGINE_STEP_RECORD) holds the same numbers and "
        "more, traced or not",
    ),
    TraceSpec(
        "areal.engine.swap",
        "phase",
        "A pending weight version applied: ring drain, flip or reload, "
        "prefix flush, in-flight rows recomputed (counts: version, "
        "rows_recomputed)",
    ),
    TraceSpec(
        "areal.engine.admit",
        "phase",
        "Admission: parked rows expired, preempted rows re-admitted, "
        "pending requests given a row and a fill (counts: "
        "rows_admitted, prefix_hits)",
    ),
    TraceSpec(
        "areal.engine.fill.dispatch",
        "phase",
        "One batched prefill chunk built on the host and dispatched "
        "(counts: prompts, f_pad, c, tokens; for a model with "
        "recurrent state also the running totals the drivers' window "
        "records read, state_copies = copies of a fill's end state to "
        "the siblings queued on it and state_reprefills = late siblings "
        "whose prompt a live row carries and whose kept fill was gone, "
        "so that they prefilled it again; for a stack stated by kind "
        "tail_layers = layers of its keep-nothing tail, which a fill "
        "runs on each row's last position alone; for a LOOPED dense "
        "stack loop_steps, cache_layers = n_layers x loop_steps and "
        "kv_bytes_per_token over all of them, each set once, and the "
        "running total admission_page_waits = engine steps that ended "
        "with a request queued, a slot free and no page for it).  The "
        "other running totals a fill moves are engine attributes, "
        "logged once when the server exits",
    ),
    TraceSpec(
        "areal.engine.fill.first_token_wait",
        "phase",
        "The pick-up of a distribution's sampled first tokens where the "
        "host folds them: at the harvest of the first chunk that "
        "decoded their rows, after its wait for the sample program and "
        "before its wait for the chunk, so next to nothing; a wait for the "
        "fill program only where they are settled early (fetched at "
        "once, a ring drain, a step that dispatched nothing) and at the "
        "non-paged admission (counts: rows)",
    ),
    TraceSpec(
        "areal.engine.fill.activate",
        "phase",
        "A completed fill handed to its rows: blocks shared, tail pages "
        "copied, sampling and the one activation program dispatched; "
        "and, once the first tokens have reached the host, their fold "
        "into the rows",
    ),
    TraceSpec(
        "areal.engine.ensure_blocks",
        "phase",
        "Every decoding row's table extended to cover the next chunk "
        "(counts: blocks_allocated, rows_preempted, and pages_live and "
        "pages_total after it; for a model with recurrent state also "
        "state_slots_live, state_slots_total; with window layers also "
        "window_pages_live, window_pages_total, window_pages_released "
        "and prefix_refused_window; with an indexer also index_pages_live "
        "= the pages of index keys, which ride the whole-context pages' "
        "table)",
    ),
    TraceSpec(
        "areal.engine.decode.dispatch",
        "phase",
        "One decode chunk dispatched (counts: rows, "
        "rows_planned = the rows that hold a cached position, which the "
        "paged kernel's decode grid visits, "
        "ctx_tokens_sum = prompt + generated known to the host over the "
        "dispatched rows, chunk_size, pages_attended, page_slots = "
        "batch slots x pages a slot's table holds, tiles_attended = "
        "sum of ceil(context / tile_tokens), tile_tokens = the unit the "
        "paged kernel copies a page in; for latent "
        "pages latent_ctx_tokens_sum and latent_pages_attended = the "
        "same context and pages, ONE entry a position and layer; with "
        "window layers window_tokens_sum = sum of min(context, window); "
        "with recurrent state state_rows = rows x state layers, the "
        "states a step updates; with parallel layers parallel_layers; "
        "with an indexer index_ctx_tokens_sum = the positions an indexed "
        "layer's step SCORES and sparse_tokens_sum = sum of min(context, "
        "index_topk), those it attends, in place of the latent counts; "
        "with latent window layers latent_window_tokens_sum = "
        "window_tokens_sum, ONE entry a position of the window)",
    ),
    TraceSpec(
        "areal.engine.harvest.wait",
        "phase",
        "Blocked until the oldest dispatched chunk's outputs are "
        "computed (timing_split's device_s)",
    ),
    TraceSpec(
        "areal.engine.harvest.fetch",
        "phase",
        "The oldest chunk's outputs copied to the host (timing_split's "
        "fetch_s)",
    ),
    TraceSpec(
        "areal.engine.harvest.fold",
        "phase",
        "The fetched chunk folded into the host rows, finished rows "
        "parked or released (counts: tokens; for a model that holds a "
        "share of the experts also moe_pairs_held, moe_pairs_routed, "
        "moe_expert_pairs_max of the chunk, and under a group-limited "
        "router moe_groups_hit)",
    ),
    # -- phase spans: what the profiler would drop ----------------------------
    TraceSpec(
        "areal.phase.begin",
        "phase",
        "No length: a phase of a PhaseClock (the areal.engine.* and "
        "areal.train.batch.. spans) begins on this thread.  The profiler "
        "drops a span still open when its session stops; this one "
        "survives (counts: of = the phase's name, t = "
        "time.perf_counter() at the mark, seq = the lap's, its record's)",
    ),
    TraceSpec(
        "areal.phase.end",
        "phase",
        "No length: a phase of a PhaseClock has ended on this thread; "
        "survives where the phase began before the session (counts: of "
        "= the phase's name, seconds = how long it lasted, t and seq as "
        "at its begin)",
    ),
    # -- phase spans: gserver manager thread ----------------------------------
    TraceSpec(
        "areal.manager.schedule",
        "phase",
        "One routing decision (the schedule RPC's time inside the "
        "manager, against what its client waits)",
    ),
    # -- phase spans: trainer thread ------------------------------------------
    TraceSpec(
        "areal.train.step",
        "phase",
        "One PPO train_step of an interface: batch prepared, "
        "minibatches trained, statistics gathered (counts: step, "
        "n_minibatches)",
    ),
    TraceSpec(
        "areal.train.batch",
        "phase",
        "One TrainEngine.train_batch call (counts: real_tokens, "
        "padded_slots, n_mbs, rows, row_len, attn_blocks_run, "
        "attn_blocks_causal, loss_head_products); one lap of the "
        "trainer's PhaseClock, whose record (TRAIN_BATCH_RECORD) holds "
        "the same counts, traced or not",
    ),
    TraceSpec(
        "areal.train.pack",
        "phase",
        "The sample split into micro-batches and laid out as stacked "
        "numpy arrays (a phase of the trainer's PhaseClock, like the "
        "three below: self seconds always, marks in a capture)",
    ),
    TraceSpec(
        "areal.train.upload",
        "phase",
        "The stacked arrays placed on the devices",
    ),
    TraceSpec(
        "areal.train.dispatch",
        "phase",
        "The jitted train step called (asynchronous: returns when the "
        "program is enqueued)",
    ),
    TraceSpec(
        "areal.train.sync",
        "phase",
        "The step's one device_get: blocks until the program has run",
    ),
    # -- regions: which part of a step program a device operation is in ------
    TraceSpec(
        "areal.embed",
        "region",
        "Token (and position) embedding",
    ),
    TraceSpec(
        "areal.attn",
        "region",
        "A layer's attention half: the norm before it, q/k/v (latent: "
        "the low-rank projections and the absorbed query), rope, the "
        "attention call (paged and flash kernels, chunk and cache "
        "attention), the output projection and its residual add",
    ),
    TraceSpec(
        "areal.attn.window",
        "region",
        "The same half of a WINDOW layer of a stack stated by kind: its "
        "paged kernel reads its own pools from the window's first page "
        "(paged_window_decode / paged_window_fill; a LATENT window "
        "layer's paged_mla_window_decode / paged_mla_window_fill)",
    ),
    TraceSpec(
        "areal.attn.index",
        "region",
        "Inside an INDEXED latent layer's half: the indexer's queries, "
        "key and head weights of the positions at hand and its scores "
        "over the row's index pages (sparse_attention.paged_index_scores, "
        "XLA) and over the chunk's own keys",
    ),
    TraceSpec(
        "areal.attn.select",
        "region",
        "Inside an INDEXED latent layer's half: the exact choice of the "
        "index_topk positions of largest score (lax.top_k in a decode "
        "step, the same set as a mask in a fill and over whole rows)",
    ),
    TraceSpec(
        "areal.attn.sparse",
        "region",
        "Inside an INDEXED latent layer's half: attention over the "
        "chosen cached entries alone (a decode step gathers them from "
        "the pool; a fill attends its paged prefix under the mask in "
        "the paged kernel, Mosaic paged_mla_masked_fill) and, in a "
        "decode step, its merge with the chunk's own tokens",
    ),
    TraceSpec(
        "areal.attn.cross",
        "region",
        "The same half of a CROSS layer of a stack stated by kind: queries "
        "only, over the pages and the chunk's own K and V of the stack's "
        "one full-attention layer (paged_attn_decode / paged_attn_fill "
        "over the pool's ONE layer)",
    ),
    TraceSpec(
        "areal.parallel",
        "region",
        "What the two mixers of a PARALLEL layer share (attention and "
        "Mamba-2 side by side on one input): the norm before them, the "
        "sum of their outputs and the residual add; the branches "
        "themselves lie in areal.attn and areal.ssm inside it",
    ),
    TraceSpec(
        "areal.gmu",
        "region",
        "A gated memory unit's half: norm, the gate's projection, the "
        "product with the last Mamba-1 layer's scan output, output "
        "projection, residual add",
    ),
    TraceSpec(
        "areal.kv_write",
        "region",
        "Keys and values put where later steps read them: "
        "write_kv_runs, a decode step's window write, copy_blocks",
    ),
    TraceSpec(
        "areal.ssm",
        "region",
        "A Mamba layer's mixer half: norm, in-projection, conv, state "
        "update (ssm_state_update / ssd_chunked), gate and norm, "
        "out-projection, residual; the state rows' and conv tails' get "
        "and put, copy_state_slots",
    ),
    TraceSpec(
        "areal.layers",
        "region",
        "The layer loop itself: a layer's weights sliced out of the "
        "stack, what the layers leave stacked (a fill's keys and values, "
        "the weights' gradients); what a layer's halves name keeps "
        "their region",
    ),
    TraceSpec(
        "areal.loop",
        "region",
        "A looped stack's passes (loop_steps > 1): the scan over the "
        "passes around the layer loop, i.e. the cache layers' index and "
        "a dense cache's keys and values sliced a pass, the carry, what "
        "the passes leave restacked over the cache layers; what the "
        "layer loop and a layer's halves name keeps their region",
    ),
    TraceSpec(
        "areal.loop.norm",
        "region",
        "The final norm BETWEEN passes of a looped stack (after every "
        "pass but the last, whose norm is areal.head's)",
    ),
    TraceSpec(
        "areal.mlp",
        "region",
        "A layer's MLP half: norm, dense MLP, residual (around an "
        "expert block: the norm and the residual)",
    ),
    TraceSpec(
        "areal.moe.route",
        "region",
        "The router: logits, choice, weights, the pair histogram and "
        "the router losses",
    ),
    TraceSpec(
        "areal.moe.experts",
        "region",
        "The routed experts' three projections and their combination",
    ),
    TraceSpec(
        "areal.moe.shared",
        "region",
        "The shared expert",
    ),
    TraceSpec(
        "areal.head",
        "region",
        "Final norm and the logits product on the serving path (the "
        "trainer's final norm; its head product is in areal.loss)",
    ),
    TraceSpec(
        "areal.sample",
        "region",
        "The sampler, the sampled token's log-probability, the stop "
        "rule and a decode step's row bookkeeping",
    ),
    TraceSpec(
        "areal.loss",
        "region",
        "The chunked head product with log-probability and entropy, "
        "and the loss arithmetic over them (a token-sum loss takes each "
        "chunk's gradient in the forward scan: no recomputed pass)",
    ),
    TraceSpec(
        "areal.optimizer",
        "region",
        "Gradient accumulation and scaling, norm and clip, the "
        "optimizer's update, the parameters' update",
    ),
]

#: names of the trainer thread's phases (``TrainEngine``'s clock): a lap
#: is one ``areal.train.batch``
TRAIN_PHASES = tuple(
    s.name
    for s in TRACE_TABLE
    if s.kind == "phase"
    and s.name.startswith("areal.train.")
    and s.name != "areal.train.step"  # (the interface's, around the batches)
)


@dataclasses.dataclass(frozen=True)
class AdmitStopSpec:
    """One value of a step record's ``admit_stopped_by``."""

    name: str
    help: str


#: why an engine step's admission left the queue standing: the ONE
#: reason it stopped on (``_admit_paged``, ``_admit``, the paused
#: branch).  Linted both ways like the stall kinds: every literal goes
#: through :func:`admit_stop` at its site.
ADMIT_STOP_TABLE = [
    AdmitStopSpec("queue_empty", "Nothing was queued, or all of it was admitted"),
    AdmitStopSpec(
        "no_slot",
        "Every slot holds a row and no parked row could be evicted",
    ),
    AdmitStopSpec(
        "no_pages",
        "A slot was free but the pool (or the snapshot slots) had no "
        "room for the prompt's fill (_new_fill gave None)",
    ),
    AdmitStopSpec(
        "held",
        "Admissions are held (hold_admissions: a drain before a weight "
        "update), or the engine is paused with requests queued",
    ),
    AdmitStopSpec(
        "late_join_cap",
        "The next request is a late sibling of a kept fill and this "
        "step's distribution is full (LATE_JOINS_A_STEP)",
    ),
    AdmitStopSpec(
        "prefix_pull",
        "The next request waits for a prefix being pulled from a peer",
    ),
]

ADMIT_STOPS = tuple(s.name for s in ADMIT_STOP_TABLE)


def admit_stop(reason: str) -> str:
    """Validate-and-return an ``admit_stopped_by`` value (the marker the
    lint collects, as :func:`stall_kind`)."""
    if reason not in ADMIT_STOPS:
        raise ValueError(
            f"unknown admit stop {reason!r}; add it to "
            "table.ADMIT_STOP_TABLE (and docs) first"
        )
    return reason


#: a step record's counts that are the movement of a running total over
#: the step, in the order of ``ContinuousBatchingEngine._step_totals``
STEP_DELTAS = (
    "tokens_emitted", "rows_admitted", "rows_finished", "rows_preempted",
    "decode_chunks", "decode_rows", "rows_planned", "fill_programs",
    "fill_tokens", "fill_slots", "late_joins", "admission_page_waits",
)

#: what every PhaseClock record holds, whoever owns the clock
LAP_RECORD = {
    "seq": "The record's number, from 1 (a mark's seq names it)",
    "t0": "time.perf_counter() when the lap's phase was entered",
    "t1": "... and when it was left",
    "self_s": "Self seconds by phase since the last lap's end (phases at "
    "0 left out): a run's records sum to phase_seconds()",
    "compiles": "The PROCESS's backend compiles since the last lap's end "
    "(jax.monitoring's event, which wraps a load from the persistent "
    "cache too): which step compiled, or waited for one",
    "compile_s": "... and their seconds",
    "quiet_laps": "Only on a record of laps in which nothing moved (an "
    "idle engine's polls, a pause): how many were folded into it; t1, "
    "self_s and the counts are up to the last of them",
}

#: the engine's record a step (``ContinuousBatchingEngine._count_step``)
ENGINE_STEP_RECORD = {
    "step": "The engine's step number (its deterministic clock)",
    "slots_decoding": "Slots whose row decodes at the step's end; the "
    "four slots_* sum to max_batch",
    "slots_filling": "Slots held by a row whose prompt is still prefilling",
    "slots_parked": "Slots held by a parked row (KV resident, no request)",
    "slots_empty": "Slots without a row",
    "pending": "Requests queued at the step's end",
    "admit_stopped_by": "Why admission left the queue standing "
    "(ADMIT_STOP_TABLE)",
    "ring": "Chunks in flight at the step's end",
    "chunk_size": "Decode steps a chunk runs",
    "version": "The weights' version at the step's end",
    "tokens_emitted": "Tokens handed to rows since the last record "
    "(step()'s return and the first tokens folded): sums to "
    "tokens_emitted_total",
    "rows_admitted": "Rows given a slot",
    "rows_finished": "Requests finished (parked or released)",
    "rows_preempted": "Rows preempted under pool pressure",
    "decode_chunks": "Decode chunks dispatched: 0 or 1 a step, more where "
    "a drain re-dispatches",
    "decode_rows": "Rows in those chunks' snapshots",
    "rows_planned": "... of which hold a cached position: the rows the "
    "paged kernel's decode grid visits (1 - rows_planned / (decode_chunks "
    "x max_batch): the share of grid steps spared)",
    "fill_programs": "Prefill programs dispatched",
    "fill_tokens": "Real prompt tokens in them",
    "fill_slots": "f_pad x c positions they computed",
    "late_joins": "Late siblings served from a kept fill",
    "admission_page_waits": "1 where the step ended with a request "
    "queued, a slot free and no page for it (admit_stopped_by no_pages): "
    "sums to admission_page_waits_total, what page_wait_share reads",
}

#: the trainer's record a batch (``TrainEngine.train_batch``)
TRAIN_BATCH_RECORD = {
    "batch": "The engine's train_batch number, from 1",
    "real_tokens": "Tokens of the sample",
    "padded_slots": "n_mbs x rows x row_len the program ran",
    "rows": "Rows of a micro-batch",
    "row_len": "Their length",
    "n_mbs": "Micro-batches stacked (the bucketed count)",
    "attn_blocks_run": "Flash-attention block pairs the layout runs",
    "attn_blocks_causal": "... of those under the diagonal",
    "version": "The weights' version after the batch",
}


#: what a stack stated by kind adds to a batch's record (its expert
#: layers' counts summed over layers and micro-batches, its gradient by
#: group, before clipping)
TRAIN_BATCH_RECORD_BY_KIND = {
    "attn_window_blocks_run": "Flash-attention block pairs a WINDOW layer "
    "runs on the layout, of attn_blocks_causal",
    "moe_held_pairs": "Valid (token, k) pairs the held experts took",
    "moe_busiest_pairs": "The busiest held expert's pairs, summed over "
    "layers (over moe_held_pairs / held experts: max over mean)",
    "moe_extra_rounds": "Groups of moe.GROUP_ROWS pairs the busiest held "
    "expert takes past a layer's first (the rounds the serving form would "
    "add: the trainer's tiles cost the held pairs, not these)",
    "grad_norm": "The batch's global gradient norm",
    "grad_norms": "... by group (hybrid.grad_group)",
}


#: names of the engine thread's phases: ``engine.phase_seconds()`` and
#: ``areal_inference_phase_seconds_total{phase=}`` carry exactly these
ENGINE_PHASES = tuple(
    s.name
    for s in TRACE_TABLE
    if s.kind == "phase" and s.name.startswith("areal.engine.")
)


@dataclasses.dataclass(frozen=True)
class StallKindSpec:
    """One canonical stall-watchdog ``kind`` label value (the vocabulary
    of ``areal_trace_stall_total``)."""

    name: str
    help: str


#: every value the ``kind`` label of ``areal_trace_stall_total`` may
#: carry.  ``scripts/check_metric_names.py`` lints this table against
#: every emission site BOTH WAYS (an unlisted literal at an emission
#: site fails, and a listed kind nothing emits is dead vocabulary) —
#: route every new fire through :func:`stall_kind` or a literal
#: ``kind="..."`` keyword so the lint can see it.
STALL_KIND_TABLE = [
    StallKindSpec(
        "span_deadline",
        "An open trace span outlived the per-span wall-clock deadline "
        "(a wedged rollout/request)",
    ),
    StallKindSpec(
        "buffer_age",
        "A buffered sample sat unconsumed across too many weight "
        "versions (train side starving or rollout side flooding)",
    ),
    StallKindSpec(
        "slo",
        "The fleet TTFT p99 breached its objective for N consecutive "
        "scrapes (fires once per breach episode, re-arms on recovery)",
    ),
    StallKindSpec(
        "recompile",
        "An XLA compile landed on a watched decode/fill entry point "
        "after the engine reached steady state (fires once per compile "
        "episode, re-arms after a quiet poll)",
    ),
]

STALL_KINDS = tuple(s.name for s in STALL_KIND_TABLE)


def stall_kind(kind: str) -> str:
    """Validate-and-return a stall ``kind``.  Emission sites that pick a
    kind dynamically wrap each candidate literal in this (identity at
    runtime, plus a membership check), which is exactly the marker the
    stall-kind lint collects."""
    if kind not in STALL_KINDS:
        raise ValueError(
            f"unknown stall kind {kind!r}; add it to "
            "table.STALL_KIND_TABLE (and docs) first"
        )
    return kind


def trace_table_index() -> Dict[str, TraceSpec]:
    out: Dict[str, TraceSpec] = {}
    for spec in TRACE_TABLE:
        if spec.name in out:
            raise ValueError(f"duplicate trace table entry: {spec.name}")
        out[spec.name] = spec
    return out


def table_index() -> Dict[str, MetricSpec]:
    """name -> spec.  Raises if the table itself holds duplicates (the
    lint reports this as a table error rather than crashing)."""
    out: Dict[str, MetricSpec] = {}
    for spec in METRIC_TABLE:
        if spec.name in out:
            raise ValueError(f"duplicate metric table entry: {spec.name}")
        out[spec.name] = spec
    return out
