"""Continuous-batching TPU inference engine with interruptible weight update.

This is the TPU-native replacement for the reference's patched SGLang server
(reference: realhf/impl/model/backend/sglang.py + patch/sglang/
v0.4.6.post2.patch — the ``interrupt_all_requests`` + ``allow_interrupt``
weight-update mechanism, and realhf/impl/model/nn/real_llm_generate.py:670
``InflightBatchingGenerator``).

Design:
* ``max_batch`` independent rows whose cache is held BY KIND: whole-context
  pages, a window pool, recurrent state slots, latent pages (last item); the
  dense ``KVCache`` rows (auto under 2k context) are the parity reference.
* The host loop alternates: admit pending requests into free rows ->
  dispatch a ``decode_chunk`` (``chunk_size`` tokens fully device-side)
  into a ``pipeline_depth``-deep in-flight ring -> harvest the OLDEST
  dispatched chunk once the ring is full.  Up to K chunks are queued on
  the device at once and every chunk's outputs start an async
  device->host copy at dispatch time, so the fetch round-trip of chunk N
  overlaps the device time of chunks N+1..N+K — host<->device sync is
  one *overlapped* fetch per chunk, the XLA analogue of the reference's
  CUDA-graphed decode behind a deep submission queue.  All harvest
  decisions are dispatch-count-based (never wall-clock or readiness
  probes): multi-host SPMD controllers replay the same command stream
  and must take identical branches.
* ``update_weights(params)`` interrupts between chunks: the current chunk
  finishes, weights swap, and every in-flight row's KV is recomputed by
  re-prefilling its tokens under the new weights (the patch's
  pause -> load -> resume semantics).  ``version_start``/``version_end``
  record the weight versions a request sampled under (decoupled PPO's
  staleness bookkeeping).
* Sampling randomness is keyed on (request seed, absolute position) from
  a fixed base key, so chunking / row placement / pipelining can never
  perturb sampled streams.
* ``cache_mode="paged"`` (auto at >= 2k context, always for a stack stated
  by kind): a shared BLOCK POOL + per-row block tables (models/paged.py —
  the paged/radix-cache role of the reference's SGLang server).  Pages are
  allocated as rows grow, a group's prompt is shared by block REFERENCE
  (one fill, refcounted full pages, per-member tail-page copy), pressure
  evicts cache entries and parked rows, then preempts the youngest rows,
  long prompts prefill in ``prefill_chunk_tokens`` chunks between decodes.
  The pools' host side (allocator, tables, the window layers' page rule,
  what a cache kind refuses) is engine/kv_pages.py; a stack stated by kind
  takes models/hybrid.py's fill and decode programs, any other paged.py's.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import uuid
import zlib
from collections import deque
from functools import lru_cache, partial
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api import model_api
from areal_tpu.base import jax_compat, logging_
from areal_tpu.engine.batching import bucket_len
from areal_tpu.engine.kv_pages import (  # noqa: F401 - callers name it here
    GONE,
    KeptFills,
    PagePool,
    StatefulModelUnsupported,
    kinds_held,
    refuse,
)
from areal_tpu.engine.prefix_cache import PrefixMatch, RadixPrefixCache
from areal_tpu.engine.sampling import SamplingParams, sample_logits_keyed
from areal_tpu.models import hybrid, moe, paged, quantize
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import KVCache, decode_step, prefill
from areal_tpu.observability.hbm_ledger import (
    HbmLedger,
    get_ledger,
    tree_nbytes,
)
from areal_tpu.observability.latency import LatencyDigest, LatencyRecord
from areal_tpu.observability.table import (
    ENGINE_PHASES,
    STEP_DELTAS,
    admit_stop,
)
from areal_tpu.observability.tracing import PhaseClock, get_tracer
from areal_tpu.ops import sparse_attention

#: ``cache_mode="auto"``: dense rows below this ``kv_cache_len`` (short
#: prefixes amortize no paging), the paged block pool at and above it
PAGED_MIN_CACHE_LEN = 2048


@partial(jax.jit, static_argnames=("sampling", "mesh"))
def _sample_rows(
    logits: jax.Array,  # [F, V]
    src: jax.Array,  # [n] which logits row each target samples from
    seeds: jax.Array,  # [n] per-REQUEST sampler key identity
    positions: jax.Array,  # [n] absolute position of the sampled token
    rng: jax.Array,  # the engine's FIXED sampling base key
    sampling: SamplingParams,
    mesh=None,
):
    """First-token sampling for fill targets (each group member draws its
    own independent token from the shared prompt's final logits).  Keyed
    on (request seed, position) so the draw matches what a decode step
    for the same request at the same position would have drawn —
    chunking- and placement-invariant streams."""
    tok, logp = sample_logits_keyed(
        logits[src].astype(jnp.float32), rng, seeds, positions, sampling,
        mesh=mesh,
    )
    return tok, logp


@partial(jax.jit, donate_argnums=(0,))
def _keep_logits_row(kept: jax.Array, logits: jax.Array, row, slot):
    """Row ``row`` of a fill's last logits ``[F, V]`` into row ``slot`` of
    the kept fills' ``[slots, V]`` float32 (exact from any logits dtype:
    ``_sample_rows`` samples in float32), in place.  One program a fill
    batch's ``F``: ``row`` and ``slot`` are numbers on the device."""
    return kept.at[slot].set(logits[row].astype(kept.dtype))


def _sample_base_rng(seed: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed), 1)


@lru_cache(maxsize=None)
def _sample_and_stop_fns(sampling, stop_tokens, seed: int, mesh):
    """The sampler and the stop rule that a paged decode program is given.
    The program's jit is cached on THEIR identity, so engines of one
    sampling, stop tokens, seed and mesh get the same two functions and
    share their decode programs (a process of many engines, as a test's,
    compiled one each before: every compiled program costs it memory
    mappings that nothing gives back)."""
    base_rng = _sample_base_rng(seed)

    def _sample(logits, _sub, positions, seeds):
        # position-keyed: the draw for (request seed, position) is a
        # pure function of the engine seed (see sample_logits_keyed)
        return sample_logits_keyed(
            logits, base_rng, seeds, positions, sampling, mesh=mesh
        )

    def _stop(tok):
        stop = jnp.zeros_like(tok, dtype=bool)
        for s in stop_tokens:
            stop |= tok == s
        return stop

    return _sample, _stop


@partial(jax.jit, static_argnames=("stop_tokens",))
def _activate_rows(
    cur_tokens: jax.Array,  # [B] the engine's five row arrays
    active: jax.Array,
    budgets: jax.Array,
    kv_lengths: jax.Array,
    row_seeds: jax.Array,
    sampled: jax.Array,  # [n] first tokens, as ``_sample_rows`` left them
    entries: jax.Array,  # [6, n] int32: the rows, and what the host knows
    stop_tokens: Tuple[int, ...],
):
    """Rows that a distribution hands over start decoding, in ONE program
    and without the host seeing a token: entry ``i`` is ``(row id, fresh,
    cur, budget, length, seed)``.  A FRESH target takes ``sampled[i]`` as
    its pending token and is active unless that token is a stop token or
    its budget is 0 (the host finds the same when the token reaches it);
    a resumed row brings the pending token it was preempted with and is
    active.  A row id past the batch is padding and writes nothing."""
    ids, fresh, curs, buds, lens, seeds = entries
    fresh = fresh > 0
    tok = jnp.where(fresh, sampled, curs)
    stop = jnp.zeros_like(fresh)
    for s in stop_tokens:
        stop |= tok == s
    alive = ~fresh | (~stop & (buds > 0))
    return tuple(
        arr.at[ids].set(val, mode="drop")
        for arr, val in (
            (cur_tokens, tok), (active, alive), (budgets, buds),
            (kv_lengths, lens), (row_seeds, seeds),
        )
    )


#: late siblings that join kept fills in ONE engine step (the next waits a
#: step): what a distribution copies, samples and activates then stays
#: among the padded counts (powers of two up to 8) that one fill's eight
#: samples meet
LATE_JOINS_A_STEP = 8


logger = logging_.getLogger("inference_server")


def _qid_seed(qid: str) -> int:
    """Per-request sampler-key identity: deterministic across processes
    (SPMD controllers replay identical streams) and unique per request,
    so a freed-and-reused cache row never hands a later same-prompt
    request its predecessor's random draws."""
    return zlib.crc32(qid.encode()) & 0x7FFFFFFF


class _nullctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


@dataclasses.dataclass
class _Row:
    """Host-side state of one in-flight request."""

    req: model_api.APIGenerateInput
    prompt: List[int]
    generated: List[int]
    logprobs: List[float]
    version_start: int
    no_eos: bool = False
    cur_token: int = -1  # pending token (KV not yet in cache)
    budget_left: int = 0  # host-side view of remaining new-token budget
    # paged mode: row reserved while its prompt prefills chunk-by-chunk
    # (chunked prefill); not decoding yet
    filling: bool = False
    # a PARKED row finished a chunk without EOS and keeps its KV resident so
    # the sticky-routed continuation resumes decoding instead of re-prefilling
    # the whole prefix (the radix-cache role of the reference's SGLang server,
    # reference: patch/sglang/v0.4.6.post2.patch +
    # realhf/impl/model/backend/sglang.py:369).  The parking clock counts
    # engine STEPS, not wall time: multi-host SPMD serving replays the same
    # command stream on every controller, and step counts agree where
    # wall-clocks never would (eviction must be deterministic).
    parked: bool = False
    park_step: int = 0
    # monotone stamp, bumped on every admit AND resume: a pipelined chunk's
    # harvest must only touch the occupant the dispatch snapshotted — a row
    # freed-and-reused between dispatch and harvest (park->resume, or
    # finish->new admission) carries a different epoch and is skipped
    epoch: int = 0
    # SLO latency decomposition (monotonic-clock stamps; telemetry only —
    # never read by dispatch decisions, so SPMD lockstep is untouched):
    # submit -> admit = admission wait, submit -> first token = TTFT,
    # (last - first) / (tokens - 1) = TPOT; stall_s accumulates weight-
    # swap pause + preempted-out-of-service time while in flight
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_last: float = 0.0
    slo_stall_s: float = 0.0
    t_preempt: float = 0.0
    #: ``keep_routed_experts``: pieces ``[n, L, K]`` int16 of every
    #: layer's routed experts, one entry a position the model has READ:
    #: the prompt's from its fill, then each decode chunk's emitted steps
    routed: Optional[List[np.ndarray]] = None
    #: ``keep_chosen_sets``: what every indexed layer attended at the
    #: row's last decode steps, a step an entry ``(as the decode program
    #: hands it out, the row's cached length when its chunk began)``
    #: (``_decode_chosen_sets`` makes positions of them when asked), and
    #: at its prompt's last positions (``_fill_chosen_sets``)
    chosen: Optional[collections.deque] = None
    chosen_fill: Optional[Tuple[np.ndarray, np.ndarray]] = None
    #: the row decodes and its FIRST token has not reached the host yet
    #: (``_FirstTokens``): ``generated`` lacks it and ``cur_token`` is not
    #: known, until the harvest that brings it
    first_on_its_way: bool = False

    @property
    def n_tokens(self) -> int:
        """Prompt and generated tokens, a first token on its way among
        them: one more than the positions the row has cached."""
        return len(self.prompt) + len(self.generated) + self.first_on_its_way


@dataclasses.dataclass
class _FillTarget:
    """One cache consumer of an in-progress prompt fill: a fresh request
    (sample its first token on completion) or a preempted row resuming
    after its re-prefill (``resume`` carries the full host state)."""

    row_id: int
    req: Optional[model_api.APIGenerateInput]
    max_new: int
    resume: Optional[_Row] = None


@dataclasses.dataclass
class _Fill:
    """An in-progress chunked prefill of ONE unique token sequence.

    ``blocks`` are the canonical pool blocks receiving the KV; requests
    arriving with an identical prompt while the fill is in flight are
    appended as extra ``targets`` and share the blocks on completion
    (group-prompt dedup as block-reference sharing — the radix-cache role
    of the reference's SGLang server, reference:
    realhf/impl/model/backend/sglang.py:369)."""

    key: Tuple[int, ...]
    tokens: List[int]
    blocks: List[int]
    targets: List[_FillTarget]
    fill_pos: int = 0
    #: stateful models: the row slot whose recurrent state this fill
    #: advances chunk by chunk (its first target's); siblings get a copy
    state_slot: int = -1
    #: a stack with window layers: their pages by number (``GONE`` where
    #: released behind the fill: engine/kv_pages.py)
    wblocks: Optional[List[int]] = None
    #: ``keep_routed_experts``: ``(routed [L, F, C, K] on the device, this
    #: fill's row in it, its valid tokens)`` of each chunk so far
    routed: List[Tuple[Any, int, int]] = dataclasses.field(
        default_factory=list
    )
    #: a stateful stack KEEPS an ended fill for its prompt's late siblings
    #: (``kv_pages.KeptFills``): the snapshot slot that holds the prompt's
    #: end state (the row of the kept logits too), -1 while it fills and
    #: once it is let go; ``blocks`` / ``wblocks`` are then the kept
    #: references, ``targets`` the late siblings joining in this step
    snap: int = -1
    #: ``keep_routed_experts``: a kept fill's routing ``[tokens, L, K]``
    routing: Optional[np.ndarray] = None
    #: ``keep_chosen_sets``: the fill's LAST chunk's ``(kept masks on the
    #: device, this fill's row in them, the chunk's start, its tokens)``
    chosen_last: Optional[Tuple[Any, int, int, int]] = None


@dataclasses.dataclass
class _FirstTokens:
    """The first tokens of ONE distribution (fills that ended, or late
    siblings joining kept ones), sampled on the device and handed to
    their rows there (``_activate_rows``): what the host still owes those
    rows once the tokens reach it.  ``arrays`` are ``_sample_rows``'
    ``(tokens, logps)`` with their copy to the host started, entry ``i``
    for ``targets[i]``; ``fills`` names each fill with the targets it had
    then (its routing goes to them)."""

    targets: List[Tuple[_FillTarget, _Row]]
    arrays: Optional[Tuple[Any, Any]]
    fills: List[Tuple[_Fill, List[_FillTarget]]]


@dataclasses.dataclass
class _InflightChunk:
    """One dispatched-but-unharvested decode chunk in the pipeline ring.

    ``arrs`` holds the chunk's device outputs ``(out_t, out_l, emitted,
    active, cur)`` — already swapped for the local replica on multi-host
    meshes, with an async device->host copy started at dispatch time so
    the transfer rides under the device time of the chunks queued behind
    it.  ``snapshot`` is the dispatch-time ``(row_id, epoch)`` occupancy:
    the harvest folds outputs ONLY into rows whose epoch still matches
    (a slot freed-and-reused mid-ring carries a different epoch and is
    skipped — the harvest-identity invariant)."""

    arrs: Tuple[Any, ...]
    snapshot: List[Tuple[int, int]]
    #: first tokens of the rows that this chunk is the first to decode:
    #: folded at its harvest, before its own outputs are waited for
    first_tokens: List[_FirstTokens] = dataclasses.field(default_factory=list)


#: the most a fill batch's stacked keys and values may take
#: (``_run_fill_batch``)
FILL_KV_TEMP_BYTES = 1 << 30


@partial(
    jax.jit,
    static_argnames=("cfg", "sampling", "mesh"),
    donate_argnums=(2,),
)
def _admit_rows(
    params,
    cfg: TransformerConfig,
    cache: KVCache,
    tokens: jax.Array,  # [m, T] right-padded UNIQUE prompts
    lengths: jax.Array,  # [m]
    rows: jax.Array,  # [n] target cache rows; >= B entries are dropped
    src: jax.Array,  # [n] which unique prompt each target row copies
    seeds: jax.Array,  # [n] per-request sampler key identity
    rng: jax.Array,
    sampling: SamplingParams,
    mesh=None,
) -> Tuple[KVCache, jax.Array, jax.Array]:
    """Batched prefill: run ``m`` unique prompts through the model ONCE and
    scatter each prompt's KV into every target row that shares it (``src``
    maps target row -> unique prompt).  A group of ``n`` samples over one
    prompt therefore pays ONE prefill, not ``n`` (the prompt-KV sharing the
    reference gets from SGLang's radix cache,
    reference: realhf/impl/model/backend/sglang.py:369); each target row
    still samples its own independent first token."""
    m, T = tokens.shape
    positions = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (m, 1))
    seg = (positions < lengths[:, None]).astype(jnp.int32)
    mini = KVCache.zeros(cfg, m, T, dtype=cache.k.dtype)
    # last_pos: only each prompt's final logits are computed — full [m,T,V]
    # logits at a 152k vocab would be multiple GB of HBM
    logits, mini = prefill(
        params, cfg, tokens, positions, seg, mini,
        last_pos=jnp.maximum(lengths - 1, 0), mesh=mesh,
    )
    k = cache.k.at[:, rows, :, :T].set(mini.k[:, src], mode="drop")
    v = cache.v.at[:, rows, :, :T].set(mini.v[:, src], mode="drop")
    new_lengths = cache.lengths.at[rows].set(lengths[src], mode="drop")
    last = logits[:, 0]  # [m, V]
    # keyed on (request seed, prompt length): the first generated
    # token's draw is a pure function of the engine seed and the
    # request's (identity, position), like every later token's —
    # admission batching cannot perturb streams
    tok, logp = sample_logits_keyed(
        last[src].astype(jnp.float32), rng, seeds, lengths[src], sampling,
        mesh=mesh,
    )
    return KVCache(k=k, v=v, lengths=new_lengths), tok, logp


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk_size", "stop_tokens", "sampling", "attn_len", "mesh",
    ),
    donate_argnums=(2,),
)
def _decode_chunk(
    params,
    cfg: TransformerConfig,
    cache: KVCache,
    cur_tokens: jax.Array,  # [B]
    active: jax.Array,  # [B] bool
    budgets: jax.Array,  # [B] remaining new tokens (incl. pending cur)
    row_seeds: jax.Array,  # [B] per-request sampler key identity
    rng: jax.Array,
    chunk_size: int,
    stop_tokens: Tuple[int, ...],
    sampling: SamplingParams,
    attn_len: Optional[int] = None,
    mesh=None,
):
    """Generate up to ``chunk_size`` tokens for all active rows device-side.

    Dispatches to the windowed :func:`transformer.decode_chunk` (one cache
    scatter per chunk), including sliding-window models whenever
    ``chunk_size <= sliding_window``; only pathological window/chunk combos
    fall back to the step-wise loop.  Returns (cache, out_tokens [B,K],
    out_logps [B,K], emitted [B,K] bool, cur_tokens, active, budgets, rng).
    """
    B = cur_tokens.shape[0]
    S = cache.max_len

    def is_stop(tok):
        stop = jnp.zeros_like(tok, dtype=bool)
        for s in stop_tokens:
            stop |= tok == s
        return stop

    # position-keyed sampling: ``rng`` is the engine's FIXED base key and
    # each draw is keyed on (request seed, absolute position), so the
    # random stream never depends on how many chunk dispatches produced
    # a position (pipeline depth / chunk size)
    # nor on which cache row the request landed in
    def keyed_sample(logits, _sub, positions, seeds):
        return sample_logits_keyed(
            logits, rng, seeds, positions, sampling, mesh=mesh
        )

    if cfg.sliding_window is None or chunk_size <= cfg.sliding_window:
        from areal_tpu.models.transformer import decode_chunk

        return decode_chunk(
            params,
            cfg,
            cache,
            cur_tokens,
            active,
            budgets,
            rng,
            chunk_size,
            keyed_sample,
            is_stop,
            attn_len=attn_len,
            row_seeds=row_seeds,
            mesh=mesh,
        )

    def body(i, state):
        cache, cur, active, budgets, out_t, out_l, emitted, rng = state
        logits, new_cache = decode_step(
            params, cfg, cur, cache, active=active, mesh=mesh
        )
        rng, sub = jax.random.split(rng)
        # post-step lengths IS the sampled token's absolute position
        tok, logp = keyed_sample(
            logits.astype(jnp.float32), sub, new_cache.lengths, row_seeds
        )
        tok = jnp.where(active, tok, 0)
        out_t = out_t.at[:, i].set(tok)
        out_l = out_l.at[:, i].set(jnp.where(active, logp, 0.0))
        emitted = emitted.at[:, i].set(active)
        budgets = budgets - active.astype(jnp.int32)
        active = active & ~is_stop(tok) & (budgets > 0)
        active &= new_cache.lengths < S
        return (new_cache, tok, active, budgets, out_t, out_l, emitted, rng)

    out_t = jnp.zeros((B, chunk_size), jnp.int32)
    out_l = jnp.zeros((B, chunk_size), jnp.float32)
    emitted = jnp.zeros((B, chunk_size), bool)
    state = (cache, cur_tokens, active, budgets, out_t, out_l, emitted, rng)
    cache, cur, active, budgets, out_t, out_l, emitted, rng = jax.lax.fori_loop(
        0, chunk_size, body, state
    )
    return cache, out_t, out_l, emitted, cur, active, budgets, rng


_warned_paged_reference = set()


def _warn_paged_reference(head_dim: int):
    """One warning per head_dim when a paged engine on a TPU serves from
    the jnp reference instead of the Pallas kernel (the trainer's twin is
    ``transformer._warn_dense_fallback``)."""
    if head_dim in _warned_paged_reference:
        return
    _warned_paged_reference.add(head_dim)
    logger.warning(
        "paged attention is taking the jnp REFERENCE path on a TPU: "
        "head_dim %d is not a multiple of the 128-lane tile the Pallas "
        "kernel's scratch slices need; expect gather-bound decode",
        head_dim,
    )


class ContinuousBatchingEngine:
    """Thread-safe continuous-batching generation over one model mesh."""

    def __init__(
        self,
        cfg: TransformerConfig,
        params,
        tokenizer=None,
        max_batch: int = 32,
        kv_cache_len: int = 4096,
        chunk_size: int = 16,
        sampling: Optional[SamplingParams] = None,
        stop_tokens: Sequence[int] = (),
        seed: int = 0,
        device=None,
        mesh=None,
        cache_mode: str = "auto",
        page_size: int = 1024,
        kv_pool_tokens: Optional[int] = None,
        kv_cache_dtype: str = "auto",
        serving_weight_dtype: str = "auto",
        prefill_chunk_tokens: int = 1024,
        kv_window_pool_tokens: Optional[int] = None,
        pipeline_depth: int = 2,
        prefix_cache: bool = True,
        prefix_cache_capacity_frac: float = 0.5,
        prefix_cache_min_tokens: int = 1,
        prefix_cache_host_bytes: int = 0,
        slo_tracking: bool = True,
        server_name: str = "",
        handoff_streaming: bool = False,
        prefix_pull_min_tokens: int = 256,
        hbm_ledger: Optional[HbmLedger] = None,
        keep_routed_experts: int = 0,
        keep_chosen_sets: int = 0,
    ):
        """``mesh``: a (small) jax Mesh for tensor-parallel serving — params
        shard via ``transformer.param_pspecs`` (TP over ``model``), the KV
        cache shards its kv-head axis, and the jitted admit/decode paths run
        SPMD (the role TP SGLang servers play for big models in the
        reference's decoupled mode).  Mutually exclusive with ``device``.

        ``cache_mode``: "dense" keeps per-row ``[max_batch, kv_cache_len]``
        KV; "paged" uses a shared block pool + block tables (capacity in
        ``page_size``-token pages, chunked prefill, block-shared group
        prompts); "auto" is paged at ``kv_cache_len >=
        PAGED_MIN_CACHE_LEN`` for global-attention models.

        ``pipeline_depth``: max decode chunks dispatched-but-unharvested
        (the in-flight ring).  K=1 is the unpipelined baseline (dispatch
        then immediately block — parity reference); K=2 overlaps one
        chunk's fetch with the next chunk's device time; K>=3 keeps the
        device fed even when the output-fetch RTT exceeds a chunk's own
        device time (short chunks, a loaded host).  Token streams are identical
        across K under ANY sampling mode: every draw is keyed on
        (request seed, absolute position) from a fixed base key
        (sampling.py
        ``sample_logits_keyed``), so the stream is a pure function of
        the seed — how many chunk dispatches produced a position cannot
        perturb it.

        ``kv_pool_tokens`` sizes the paged pool (default: dense-equivalent
        ``max_batch * kv_cache_len``; set smaller to serve long contexts a
        dense cache could never reserve).  ``kv_window_pool_tokens`` sizes
        the pool of a stack's WINDOW layers (default: as many tokens):
        their pages follow a rule of their own (engine/kv_pages.py).
        ``prefill_chunk_tokens`` bounds
        the prompt tokens prefetched per engine step — the decode stall
        during a long-prompt admission is one chunk, not the whole wave.

        ``prefix_cache`` (paged mode only; default on) keeps a radix index
        over finished/parked sequences' blocks so ANY new request — a
        multi-turn continuation under a fresh qid, a retried request, a
        group member landing late — pins the longest cached prefix and
        prefills only its suffix (the cross-request radix-cache role of
        the reference's SGLang server).  ``prefix_cache_capacity_frac``
        bounds the pool fraction the cache may hold references to;
        ``prefix_cache_min_tokens`` suppresses matches too short to pay
        for their pin + tail copy.  Cache eviction yields to live rows
        (it is the first reclamation tier, before parked-row eviction and
        preemption) and the whole cache flushes on ``update_weights`` —
        KV computed under old weights is never reused after a swap.

        ``kv_cache_dtype`` ("auto" | "int8", paged mode only): "auto"
        stores KV blocks at model dtype (today's behavior, bit-for-bit);
        "int8" stores the pools quantized with per-(block, head, slot)
        float32 scales alongside (models/paged.py) — roughly half the
        HBM per cached token, so ~2x live rows / prefix-cache capacity
        at the same pool budget, at the cost of storage-rounding error
        (reads dequantize inline; attention math stays in model dtype).
        Every pool path carries the scales: fill/decode writes
        quantize at the scatter, COW tail copies, host-tier spills, and
        swap-ins move int8 bytes + scales together.
        tests/engine/test_kv_quant.py pins the token-quality delta;
        dense mode ignores the knob with a warning.

        ``serving_weight_dtype`` ("auto" | "int8"): "auto" serves the
        param tree exactly as passed (bit-for-bit today's behavior);
        "int8" quantizes every matmul weight to int8 + per-output-
        channel f32 absmax scales at construction (models/quantize.py)
        and dequantizes AT USE inside each projection — ~half the
        weight HBM (freed for paged blocks / prefix cache) and ~half
        the bytes a staged weight swap restores, at the cost of
        storage-rounding error (matmul math stays at activation dtype;
        tests/engine/test_weight_quant.py pins the token-quality
        delta).  Works on every path — dense, paged, TP/EP meshes —
        because the forward reads weights through one format-agnostic
        accessor.  Incoming swap trees must arrive in the engine's
        resident format; the generation server's manifest negotiation
        guarantees that (quantizing on arrival when the publisher only
        wrote full precision).

        ``prefix_cache_host_bytes`` > 0 adds the HOST SPILL TIER below
        the HBM cache (the SGLang hierarchical/HiCache direction):
        evicted full-block entries copy their KV to host buffers (one
        batched device_get per reclamation round) instead of dying, and
        a match on a spilled prefix swaps the blocks back in on an
        async dispatch that rides the decode ring's overlap — the
        admission requeues until the step after the swap-in dispatch
        (step-keyed, never a readiness probe, so SPMD lockstep holds).
        Effective cache capacity multiplies by roughly host-RAM/HBM;
        weight swaps flush both tiers.  Single-process engines only
        (multi-process SPMD serving disables the tier with a warning —
        host buffers would cover just the local pool shard).

        ``handoff_streaming`` (paged mode): stream a handoff-flagged
        row's KV to the decode peer INCREMENTALLY — as each fill chunk
        completes, the now-final full pool blocks are gathered (one
        coalesced buffer per segment) and queued for export
        (:meth:`drain_handoff_segments`; the worker pushes them over the
        ``import_handoff_segment`` RPC while later chunks still fill),
        and the FINAL segment carries the tail block plus the first
        token + host metadata — so the decode-side resume gap is O(one
        chunk) instead of O(prompt).  Off (default) keeps the PR-13
        monolithic ``export_handoff``/``import_handoff`` unit.
        """
        self.cfg = cfg
        self.device = device
        self.mesh = mesh
        assert cache_mode in ("auto", "dense", "paged"), cache_mode
        assert pipeline_depth >= 1, pipeline_depth
        self.pipeline_depth = pipeline_depth
        self._prefix_cache: Optional[RadixPrefixCache] = None
        self._prefix_cache_enabled = bool(prefix_cache)
        self._prefix_cache_capacity_frac = prefix_cache_capacity_frac
        self._prefix_cache_min_tokens = prefix_cache_min_tokens
        self._prefix_cache_host_bytes = max(0, int(prefix_cache_host_bytes))
        # (a looped stack's rows are paged at any length: dense rows hold
        # ``max_batch x kv_cache_len`` positions of EVERY pass's cache,
        # 38.7 GB for 16 rows of 1,536 at 192 cache layers of 16 heads)
        self.paged = cache_mode == "paged" or (
            cache_mode == "auto"
            and (kv_cache_len >= PAGED_MIN_CACHE_LEN or cfg.loop_steps > 1)
            and cfg.sliding_window is None
        )
        # the second cache kind: one recurrent-state slot a batch row (SSM
        # state + conv tail per Mamba layer) beside the attention layers'
        # pages.  Zeroed by a fill's first chunk, carried by its later
        # ones, copied to the siblings that share the fill, free when the
        # row is.  What assumes per-token blocks refuses by name here,
        # where its option is set, or where it is asked for.
        # (A stack stated by kind WITHOUT recurrent layers, such as one
        # of latent-attention layers, keeps what the dense model has:
        # siblings share a prompt's pages, tail pages are copied, prefixes
        # are reused, rows park.)
        self._by_kind = cfg.is_hybrid  # models/hybrid.py runs the stack
        self._stateful = cfg.n_mamba_layers > 0
        from areal_tpu.engine.backend import refuse_unserved

        refuse_unserved(cfg)
        # the routing of the last finished requests, at least
        # ``keep_routed_experts`` of them (:meth:`routed_experts`,
        # ``_keep_routing``): what a routing-replay trainer or a parity
        # check follows.  The hybrid stack's programs hand it out; nothing
        # else does yet
        if keep_routed_experts and not (self._by_kind and cfg.n_experts):
            raise ValueError(
                "keep_routed_experts: only the hybrid stack's programs "
                "hand their routing out, and only a stack with expert "
                "layers has any"
            )
        #: the host side of the device pools (engine/kv_pages.py): the
        #: whole-context pages' and, where the stack has window layers,
        #: theirs (pools, a table and a page rule of their own); both in
        #: ``_pools``.  ``_kinds``: the cache kinds that rule something out
        self._pages: Optional[PagePool] = None
        self._win: Optional[PagePool] = None
        self._pools: List[PagePool] = []
        self._kinds = kinds_held(cfg)
        self._kv_window_pool_tokens = kv_window_pool_tokens
        self.win_k_pool = self.win_v_pool = None
        #: cached prefixes a request could not reuse because the window
        #: layers no longer held the pages before its first position
        self.prefix_refused_window = 0
        #: how often each fill batch shape ``(F_pad, C)`` and each
        #: distribution ``(fills that ended together, their targets)`` ran:
        #: the programs a warm-up has to have met (a fill program a shape;
        #: first-token sampling, tail-page copies and activation a count)
        self.fill_shapes_run: collections.Counter = collections.Counter()
        self.distributions_run: collections.Counter = collections.Counter()
        self._keep_routed = int(keep_routed_experts)
        # the positions an indexer chose at the last ``keep_chosen_sets``
        # positions of each finished request's prompt and at as many of
        # its last decode steps (:meth:`chosen_sets`), kept
        # for as many requests as the routing: what a parity check lays
        # beside a reference's own selection
        if keep_chosen_sets and not (cfg.is_indexed and keep_routed_experts):
            raise ValueError(
                "keep_chosen_sets: only a stack with an indexer chooses "
                "positions, and the sets ride the kept routing "
                "(keep_routed_experts)"
            )
        self._keep_chosen = int(keep_chosen_sets)
        #: qid -> (positions read, the fill's sets, the last decode steps
        #: as the program handed them out), oldest first
        self._chosen_done: Dict[str, Tuple[int, Any, list]] = {}
        #: positions the indexed layers' decode steps scored and, of
        #: those, attended (a layer each; the host's context at dispatch)
        self.index_positions_scored_total = 0
        self.index_positions_attended_total = 0
        self._routed_done: Dict[str, np.ndarray] = {}  # oldest first
        self._routed_done_positions = 0  # (what they hold: ``_keep_routing``)
        #: the routing of the last prompts filled, by their tokens: a later
        #: request that reuses such a prompt's cached pages prefills its
        #: tail alone and takes the rest of its routing from here
        self._routed_prompts: Dict[Tuple[int, ...], np.ndarray] = {}
        # what an option asks for that one of the model's cache kinds rules
        # out refuses by name here, where the option is set
        asked = {
            "the dense (unpaged) KV cache": cache_mode == "dense",
            "a tensor- or expert-parallel serving mesh": mesh is not None,
            "int8 KV storage": kv_cache_dtype == "int8",
            "int8 serving weights": serving_weight_dtype == "int8",
            "prefix-cache host spill": prefix_cache_host_bytes > 0,
        }
        for feature in (f for f, yes in asked.items() if yes):
            refuse(feature, self._kinds)
        if self._by_kind:
            self.paged = True
        #: sibling copies of a fill's end state; fills built for a prompt
        #: that a live row already carries (a late sibling whose prompt's
        #: kept fill was let go before it came, so it prefills again);
        #: (token, k) pairs decode chunks routed to held experts and to
        #: absent ones
        self.state_copies_total = 0
        self.state_reprefills_total = 0
        #: the ended fills kept for their prompts' late siblings (none but
        #: for a stateful stack: a stateless one reuses prefixes of any
        #: length through the radix cache), and those that requests
        #: admitted in this step are joining
        self._kept = KeptFills(0, [])
        self._joining: List[_Fill] = []
        self.moe_pairs_held_total = 0
        self.moe_pairs_routed_total = 0
        self.moe_groups_hit_total = 0
        self.moe_expert_pairs = np.zeros(
            (cfg.n_held_experts if self._by_kind else 0,), np.int64
        )
        #: prompt tokens that went through the expert layers of a fill
        #: batch; those of them in a batch whose shape takes the grouped
        #: product (``moe.group_rows``); the rounds past the first that
        #: those products took, summed over a fill's expert layers on the
        #: device (each fill's count rides to the host on its own and is
        #: added here when it has arrived: no fetch waits for it)
        self.moe_fill_tokens_total = 0
        self.moe_fill_tokens_grouped_total = 0
        self.moe_fill_extra_rounds_total = 0
        self._fill_rounds_on_the_way: Deque[jax.Array] = deque()
        #: layers of the stack's keep-nothing tail, which a fill runs on
        #: each row's last position alone (``hybrid.keep_nothing_tail``:
        #: 0 for most stacks), and the (layer, position) pairs the fills
        #: left out for it: ``tail_layers x (F_pad x C - F_pad)`` a batch
        self.fill_tail_layers = (
            hybrid.keep_nothing_tail_layers(cfg) if self._by_kind else 0
        )
        self.fill_tail_positions_saved_total = 0
        assert kv_cache_dtype in ("auto", "int8"), kv_cache_dtype
        if kv_cache_dtype == "int8" and not self.paged:
            logger.warning(
                "kv_cache_dtype='int8' requested but cache_mode resolved "
                "to dense; quantized KV storage lives on the paged path "
                "only — serving at model dtype"
            )
            kv_cache_dtype = "auto"
        self.kv_cache_dtype = kv_cache_dtype
        self._kv_quant = kv_cache_dtype == "int8"
        #: what a looped stack holds a token, set once (on the span
        #: ``areal.engine.fill.dispatch``; logged when the server exits)
        self.loop_counts = dict(
            loop_steps=cfg.loop_steps, cache_layers=cfg.n_attn_layers,
            kv_bytes_per_token=sum(
                paged.kv_pool_layout_bytes(
                    cfg, 1, 1, kv_cache_dtype=kv_cache_dtype
                )
            ),
        )
        assert serving_weight_dtype in ("auto", "int8"), serving_weight_dtype
        self.serving_weight_dtype = serving_weight_dtype
        self._weight_quant = serving_weight_dtype == "int8"
        # quantized-serving-weight quality counters (the
        # areal_inference_weight_quant_* divergence series): external
        # parity harnesses fold their measured greedy-divergence checks
        # in here
        self.weight_quant_divergence_checks_total = 0
        self.weight_quant_divergence_diverged_total = 0
        # abstract full-precision tree template (int8 engines only):
        # the restore target when a publisher did NOT write the
        # quantized format and the negotiation falls back to the
        # full-precision snapshot (the server quantizes on arrival, so
        # the engine's resident format never changes)
        self._full_weight_template = None
        if self._weight_quant:
            self._full_weight_template = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    jnp.shape(x), jnp.result_type(x)
                ),
                params,
            )
            # the engine holds int8 + scales from step 0: ~half the
            # weight HBM, and every staged swap restores ~half the bytes
            params = quantize.quantize_param_tree(params)
        # scale pools exist only for int8 paged storage; None everywhere
        # else so every pool call site can pass them unconditionally
        self.k_scale: Optional[jax.Array] = None
        self.v_scale: Optional[jax.Array] = None
        # recurrent-state slots exist only for a stateful model
        self.ssm_state: Optional[jax.Array] = None
        self.conv_state: Optional[jax.Array] = None
        # quantized-serving quality counters: external parity harnesses
        # fold their greedy divergence checks in here so the fleet's
        # metrics carry measured quality, not assumptions
        self.kv_quant_divergence_checks_total = 0
        self.kv_quant_divergence_diverged_total = 0
        if self.paged and cfg.sliding_window is not None and not self._by_kind:
            raise ValueError(
                "paged cache serves global-attention models; sliding-window "
                "models use the dense window-gather path"
            )
        self._param_shardings = None
        self._cache_sharding = None
        self._pool_sharding = None
        self._pool_scale_sharding = None
        if mesh is not None:
            assert device is None, "pass mesh OR device, not both"
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from areal_tpu.models.transformer import (
                param_pspecs,
                serving_param_pspecs,
            )

            ep = mesh.shape.get("expert", 1)
            if cfg.is_moe and ep > 1 and cfg.n_experts % ep != 0:
                raise ValueError(
                    f"n_experts {cfg.n_experts} not divisible by the "
                    f"mesh's expert axis ({ep}); expert parallelism "
                    "needs an even split"
                )
            if not cfg.is_moe and ep > 1:
                raise ValueError(
                    "mesh has an expert axis > 1 but the model is dense; "
                    "use the model/data axes for dense serving"
                )
            # EP serving shards experts over the expert axis ONLY (the
            # explicit shard_map in models/moe.py consumes exactly the
            # local [E/ep, D, F] shard; see serving_param_pspecs).  On an
            # expert-less mesh the training pspecs apply unchanged —
            # experts keep their model/fsdp matmul-dim sharding, so a
            # MoE model under plain TP serving never pays full expert
            # replication (code-review finding)
            if cfg.is_moe and ep > 1:
                pspecs = serving_param_pspecs(cfg, params)
            else:
                pspecs = param_pspecs(cfg, params)
            self._param_shardings = jax.tree.map(
                lambda ps: NamedSharding(mesh, ps), pspecs
            )
            params = jax.device_put(params, self._param_shardings)
            if self._full_weight_template is not None:
                # the fallback restore target places full-precision
                # leaves at the SAME mesh's full-tree shardings (then
                # quantizes on arrival) — never a one-chip transient
                fspecs = (
                    serving_param_pspecs(cfg, self._full_weight_template)
                    if (cfg.is_moe and ep > 1)
                    else param_pspecs(cfg, self._full_weight_template)
                )
                self._full_weight_template = jax.tree.map(
                    lambda t, ps: jax.ShapeDtypeStruct(
                        t.shape, t.dtype, sharding=NamedSharding(mesh, ps)
                    ),
                    self._full_weight_template,
                    fspecs,
                )
            tp = mesh.shape.get("model", 1)
            kv_axis = "model" if cfg.n_kv_heads % max(tp, 1) == 0 else None
            self._kv_axis = kv_axis
            self._cache_sharding = KVCache(
                k=NamedSharding(mesh, P(None, None, kv_axis, None, None)),
                v=NamedSharding(mesh, P(None, None, kv_axis, None, None)),
                lengths=NamedSharding(mesh, P(None)),
            )
            # paged pool [L, NB, Hkv, BS, hd]: shard the kv-head axis too
            self._pool_sharding = NamedSharding(
                mesh, P(None, None, kv_axis, None, None)
            )
            # int8 scale pools [L, NB, Hkv, BS] shard the same head axis
            self._pool_scale_sharding = NamedSharding(
                mesh, P(None, None, kv_axis, None)
            )
        elif device is not None:
            params = jax.device_put(params, device)
        #: chips this engine's forward spans (1 off-mesh) — the fleet
        #: manager scales capacity/routing weights by it
        self.mesh_devices = int(mesh.devices.size) if mesh is not None else 1
        self.params = params
        # HBM ledger (observability/hbm_ledger.py): per-subsystem byte
        # attribution.  Every seam below holds one handle; close()
        # leak-audits the set and releases them.  Handles no-op on a
        # disabled ledger, so the hot paths never need a guard.
        self.hbm_ledger = hbm_ledger if hbm_ledger is not None else get_ledger()
        led = self.hbm_ledger
        self._led_weights = led.register(
            "weights", tree_nbytes(params), name="engine.params"
        )
        self._led_staged = led.register(
            "staged_weights", name="engine.staged_params"
        )
        self._led_kv_pool = led.register("kv_pool", name="engine.kv_pool")
        self._led_kv_scales = led.register(
            "kv_scales", name="engine.kv_scales"
        )
        self._led_spill = led.register(
            "prefix_spill_host", name="engine.prefix_spill"
        )
        self._led_streams = led.register(
            "stream_buffers", name="engine.streams"
        )
        self._led_handoff = led.register(
            "handoff_staging", name="engine.handoff"
        )
        self.tokenizer = tokenizer
        self.max_batch = max_batch
        self.kv_cache_len = kv_cache_len
        self.chunk_size = chunk_size
        self.sampling = sampling or SamplingParams()
        stop = set(stop_tokens)
        if tokenizer is not None and tokenizer.eos_token_id is not None:
            stop.add(int(tokenizer.eos_token_id))
        self.stop_tokens = tuple(sorted(stop))
        self.version = 0

        #: how a decode step under an indexer attends its chosen set, which
        #: the program decides from the table's shape when it is traced
        #: (``sparse_attention.decode_reads_masked``): "masked" in the
        #: paged kernel, "gather" from the pool; None without an indexer.
        #: For the log line and the step records' header only: what the
        #: program hands out of a step says its form itself
        self.sparse_decode_path: Optional[str] = None
        with jax.default_device(device) if device is not None else _nullctx():
            # ONE fixed base key for every sampling draw: draws are keyed
            # on (request seed, position) from it, so streams are
            # invariant to chunking / pipeline depth
            self._seed = seed
            self._sample_base_rng = _sample_base_rng(seed)
            if self.paged:
                self._init_paged_state(
                    page_size, kv_pool_tokens, prefill_chunk_tokens
                )
            elif self._cache_sharding is not None and mesh is not None:
                # allocate directly sharded: a transient full-size cache on
                # one chip would OOM exactly the models TP serving exists for
                self.cache = jax.jit(
                    lambda: KVCache.zeros(cfg, max_batch, kv_cache_len),
                    out_shardings=self._cache_sharding,
                )()
            else:
                self.cache = KVCache.zeros(cfg, max_batch, kv_cache_len)
            if not self.paged:
                # dense KV cache bytes land under the same kv_pool tag —
                # the attribution question ("who owns the bytes") does
                # not care which cache layout answered it
                self._led_kv_pool.set(tree_nbytes(self.cache))
            self.cur_tokens = jnp.zeros((max_batch,), jnp.int32)
            self.active = jnp.zeros((max_batch,), bool)
            self.budgets = jnp.zeros((max_batch,), jnp.int32)
            # per-request sampler key identity of each row's occupant
            # (crc32 of the qid, set at admit/resume/fill-activation)
            self.row_seeds = jnp.zeros((max_batch,), jnp.int32)
            # legacy split-chain key: no sampler reads it anymore (every
            # draw is position-keyed off _sample_base_rng), kept only so
            # external probes of engine state keep working
            self.rng = jax.random.PRNGKey(seed)
        #: first tokens that reached their rows on the device before the
        #: host (folded at a later harvest), and those it fetched at once
        self.first_tokens_deferred_total = 0
        self.first_tokens_blocking_total = 0
        # distributions whose first tokens no dispatched chunk has taken up
        self._first_tokens: List[_FirstTokens] = []
        if self.paged:
            # (outside ``default_device``: a program's cache key holds the
            # context it was first called in, and the steps run outside)
            self._warm_activation()
        if self._stateful:
            self._warm_kept_fill_programs()

        # flight recorder: per-request lifecycle events (admit/resume/
        # fill/chunk/park/preempt/recompute) under the request's trace
        # root.  The tracer no-ops for unsampled roots (one memoized
        # dict lookup), keeping the decode hot loop unburdened.
        self.tracer = get_tracer()
        self.rows: List[Optional[_Row]] = [None] * max_batch
        self._pending: List[model_api.APIGenerateInput] = []
        self._results: Dict[str, model_api.APIGenerateOutput] = {}
        self._result_events: Dict[str, threading.Event] = {}
        self._lock = threading.Lock()
        # pending swap: (params, target_version|None, pre_sharded) — one
        # atomic cell so a racing second update can never mix its version
        # with an earlier request's tree.  pre_sharded marks a STAGED
        # tree (already device-resident under this engine's shardings):
        # the apply skips the device_put and becomes a pointer flip.
        self._new_params: Optional[Tuple[Any, Optional[int], bool]] = None
        self._staged_params = None
        self._staged_version: Optional[int] = None
        self._paused = threading.Event()
        self.gen_tokens_total = 0
        self.prefill_tokens_total = 0  # unique-prompt tokens actually run
        self.prefill_calls = 0
        self.resumed_total = 0  # continuations resumed with zero prefill
        # host-tier rounds: batched spill gathers (one device_get each)
        # and batched swap-in dispatches (one async scatter each)
        self.host_spill_rounds_total = 0
        self.host_restore_rounds_total = 0
        # prefill/decode disaggregation: paged-block KV handoff counters
        # (exports on the prefill role, imports/rejects on the decode
        # role; bytes/seconds cover the host round trip on both sides)
        self.handoff_exports_total = 0
        self.handoff_imports_total = 0
        self.handoff_bytes_total = 0
        self.handoff_seconds_total = 0.0
        self.handoff_import_rejects: Dict[str, int] = {}
        # streamed (segmented) handoff: per-chunk segment export/import
        # counters plus exporter-side aborts (a stream cut short by EOS
        # at the first token, a weight swap restarting the fill, or an
        # explicit cancel — the decode peer releases its partial blocks)
        self._handoff_streaming = (
            bool(handoff_streaming) and not cfg.n_window_layers
        )
        self.handoff_segment_exports_total = 0
        self.handoff_segment_imports_total = 0
        self.handoff_segment_aborts_total = 0
        #: outbound segment queue (engine thread appends; the worker
        #: drains each poll and pushes per-stream IN ORDER)
        self._handoff_segments: List[Dict[str, Any]] = []
        #: export-side stream state per handoff-flagged qid
        self._handoff_streams: Dict[str, Dict[str, Any]] = {}
        #: import-side partially-received streams: qid -> {blocks,
        #: next_seq, received, version, step, total}.  Blocks are owned
        #: by the record until the final segment parks the row or a
        #: failure releases them — never evictable, so the TTL below
        #: bounds how long a dead peer's half-stream can pin pool space.
        self._handoff_pending: Dict[str, Dict[str, Any]] = {}
        self.handoff_pending_ttl_steps = 512
        # fleet KV fabric (cross-server prefix pull): puller-side state.
        # ``_prefix_pulls`` holds one record per pull qid (state machine
        # requested -> pulling -> done|failed); intents queue in
        # ``_prefix_pull_requests`` until the worker drains them and
        # runs the owner's export_prefix RPC.  Pulled segments re-enter
        # through :meth:`import_prefix_segment` under the SAME
        # numbered-segment rules as the streamed handoff: per-segment
        # version checks, the step-keyed TTL sweep, and zero-leak block
        # release on any reject.  ``prefix_pull_min_tokens`` is the
        # minimum token gap (advertised prefix beyond the local
        # resident match) worth an RPC + scatter instead of a local
        # re-prefill.
        self.prefix_pull_min_tokens = max(1, int(prefix_pull_min_tokens))
        self._prefix_pulls: Dict[str, Dict[str, Any]] = {}
        self._prefix_pull_requests: List[Dict[str, Any]] = []
        self.prefix_peer_pulls_total = 0
        self.prefix_peer_pull_bytes_total = 0
        self.prefix_peer_pull_rejects: Dict[str, int] = {}
        # what the engine's thread is doing: every part of a step is a
        # phase span (observability/tracing.phase: in the profiler's
        # trace when one is being taken) whose self seconds also add up
        # here, always.  ``timing_split()`` reads its host/device/fetch
        # split off these totals.
        # And a record a step, always (``tracing.PhaseClock``'s laps):
        # ``_count_step`` notes the step's counts; ``tracing.step_logs()``
        # and the server's ``steps.<worker>.jsonl`` give the window-long
        # account that a traced slice of a step or two cannot.
        self._phases = PhaseClock(ENGINE_PHASES, log="engine")
        self._phases.about = dict(
            max_batch=self.max_batch, chunk_size=self.chunk_size,
            pipeline_depth=self.pipeline_depth,
        )
        if self.sparse_decode_path:
            self._phases.about["sparse_decode_path"] = self.sparse_decode_path
        #: why the last admission left the queue standing
        #: (``table.ADMIT_STOPS``)
        self._admit_stopped_by = admit_stop("queue_empty")
        #: engine steps that ended with a request queued, a slot free and
        #: no page for it (``admit_stopped_by`` "no_pages"): where PAGES
        #: bound the batch and not slots, as under a looped stack's cache
        self.admission_page_waits_total = 0
        # running totals a step's record differences (``_step_totals``)
        self.rows_admitted_total = 0
        self.rows_finished_total = 0
        self.chunks_dispatched_total = 0
        self.decode_rows_dispatched_total = 0
        self.decode_rows_planned_total = 0
        self.fill_slots_total = 0  # f_pad x c of every fill program
        self.chunks_total = 0
        #: every token handed to a row, first tokens included, counted
        #: where it is handed over (``gen_tokens_total`` moves only when
        #: a row finishes)
        self.tokens_emitted_total = 0
        # async-fetch accounting: chunks whose outputs started a
        # device->host copy at dispatch, and harvests that found the
        # oldest chunk already complete (its fetch fully overlapped)
        self.async_fetches_total = 0
        self.fetch_ready_total = 0
        # weight-swap time attribution (cumulative seconds): stage =
        # restoring/transferring a staged tree while decode continued
        # (off the paused critical path); pause = the swap work that DOES
        # interrupt decode (_apply_pending_weights: ring drain + pointer
        # flip or device_put + prefix-cache flush + in-flight recompute)
        self.swap_stage_s = 0.0
        self.swap_pause_s = 0.0
        self.preempted_total = 0  # paged-pool preemptions (0 when dense)
        self.swaps_total = 0
        self.swaps_staged_total = 0
        # in-flight rows whose KV a swap recomputed under the new weights
        # (0 for a swap that landed on an idle engine)
        self.swap_recomputed_rows_total = 0
        # True while _apply_pending_weights runs: ``version`` flips in the
        # middle of it, the counters above only at its end
        self.swap_applying = False
        self.park_ttl_steps = 512  # engine steps a parked row may idle
        # True = decode only, admit nothing (drain-before-update servers)
        self.hold_admissions = False
        self._step_seq = 0  # deterministic clock (one tick per step())
        self._counted = self._step_totals()  # at the last step's record
        self._epoch_counter = 0  # admission/resume stamp source
        # lifetime tokens folded in by harvests; step() reports its own
        # delta of this so tokens harvested by MID-STEP ring drains
        # (weight swaps, preemption flushes) are never lost from the
        # step's return value
        self._tokens_harvested_total = 0
        # the in-flight chunk ring: dispatched-but-unharvested decode
        # chunks, FIFO, at most ``pipeline_depth`` deep
        self._ring: Deque[_InflightChunk] = deque()
        # request-level SLO plane (observability/latency.py): per-request
        # LatencyRecords + streaming percentile digests over the fixed
        # log buckets.  Host-side telemetry only — a few monotonic-clock
        # stamps per request lifecycle event, nothing on the per-token
        # path and nothing dispatch decisions read (SPMD-safe).
        self._slo_enabled = bool(slo_tracking)
        self.server_name = server_name
        self.slo_records_total = 0
        self._submit_ts: Dict[str, float] = {}
        self._slo_records: Deque[LatencyRecord] = deque(maxlen=4096)
        self._slo_digests: Dict[str, LatencyDigest] = {
            "admission_wait_s": LatencyDigest(),
            "ttft_s": LatencyDigest(),
            "tpot_s": LatencyDigest(),
            "stall_s": LatencyDigest(),
        }
        # gateway token streams: per-qid incremental harvest queues,
        # fed at chunk-fold time (_harvest_oldest) plus the two
        # first-token sites (dense admit, paged fill distribution) and
        # drained by the gen-server worker into SSE frames.  The deque
        # is the ISSUE's bounded queue: SPMD follower controllers open
        # streams too (submit rides the command batch) but never drain
        # them, so their buffers cap out harmlessly — dropped tokens on
        # a follower are never read; the leader drains promptly.
        self._streams: Dict[str, Dict[str, Any]] = {}
        self.stream_buffer_cap = 4096
        # step-keyed staleness (never wall clock — SPMD determinism):
        # a stream nobody polled for this many steps is auto-cancelled
        # by the leader (dead gateway client backstop)
        self.stream_stale_steps = 2048
        self.streams_opened_total = 0
        self.stream_dropped_total = 0
        self.cancelled_total = 0
        # pool-pressure evictions split by the victim's priority class
        # (interactive vs bulk — the admission plane's classes)
        self.preempted_by_class: Dict[str, int] = {}
        # cancels that arrived while the target row was mid-fill (its
        # blocks belong to the fill machinery); retried each step after
        # _advance_fill
        self._cancel_wanted: set = set()

    # -- paged-cache state --------------------------------------------------

    def _init_paged_state(
        self,
        page_size: int,
        kv_pool_tokens: Optional[int],
        prefill_chunk_tokens: int,
    ):
        cfg, max_batch = self.cfg, self.max_batch
        BS = page_size
        self.page_size = BS
        self.blocks_per_row = -(-self.kv_cache_len // BS)  # MB
        if cfg.is_indexed:
            self.sparse_decode_path = (
                "masked" if sparse_attention.decode_reads_masked(
                    self.blocks_per_row * BS, cfg.index_topk
                ) else "gather"
            )
        pool_tokens = kv_pool_tokens or max_batch * self.kv_cache_len
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # TPU: the Pallas kernel (shard_mapped over the kv-head axis under
        # a TP mesh); elsewhere: the vectorized jnp reference (the kernel
        # would only run in slow interpret mode).  Tests force the kernel
        # path in interpret mode explicitly (tests/engine/test_paged_pool).
        # head_dim must be lane-aligned (128) for Mosaic's scratch-slice
        # tiling — a misaligned model on a TPU takes the reference path,
        # and says so once
        on_tpu = jax.default_backend() == "tpu"
        # (a latent page's row is a whole number of lane tiles by
        # construction: paged.latent_page_width)
        # (a page's head is ``pool_head_dim`` wide: a differential pair's
        # two heads of 64 are one of 128)
        self._use_paged_kernel = on_tpu and (
            cfg.is_latent or cfg.pool_head_dim % 128 == 0
        )
        if on_tpu and not self._use_paged_kernel:
            _warn_paged_reference(cfg.pool_head_dim)
        kv_dtype = self.kv_cache_dtype
        # COMMITTED to the device from the start: a program's cache key
        # holds whether each argument is, so the first fill (fresh,
        # uncommitted zeros) and a later one of the same shape (a
        # program's outputs) were two programs, and the second was built
        # inside a benchmark's window
        commit = self._by_kind and self.device is not None

        def alloc(n: int, layers: Optional[int]):
            def make():
                return paged.alloc_kv_pool(
                    cfg, n, BS, kv_cache_dtype=kv_dtype, layers=layers
                )

            if self._pool_sharding is None:
                arrays = make()
                if commit:
                    arrays = jax.device_put(arrays, self.device)
                return arrays
            shardings = (self._pool_sharding, self._pool_sharding)
            if self._kv_quant:
                shardings += (
                    self._pool_scale_sharding, self._pool_scale_sharding
                )
            else:
                shardings += (None, None)  # None leaves: no sharding slot
            return jax.jit(make, out_shardings=shardings)()

        # the stack's kinds of pages, each a device pool with its host side
        # (engine/kv_pages.py): the layers that attend the whole context,
        # then the window layers, whose pages go once every holder's
        # window has passed them.  (layers of its own, tokens, window)
        kinds = [(None, pool_tokens, None)]
        if cfg.n_window_layers:
            kinds.append((
                cfg.n_window_layers,
                self._kv_window_pool_tokens or pool_tokens,
                cfg.sliding_window,
            ))
        pool_b = scale_b = 0
        for layers, tokens, window in kinds:
            # (one full-length row always fits)
            n = max(-(-tokens // BS), self.blocks_per_row)
            arrays = alloc(n, layers)
            # ledger attribution: the alloc itself may run under jit
            # (sharded path), so sizes come from the pure layout math,
            # which matches the allocated arrays' nbytes exactly
            b = paged.kv_pool_layout_bytes(
                cfg, n, BS, kv_cache_dtype=kv_dtype, layers=layers
            )
            pool_b, scale_b = pool_b + b[0], scale_b + b[1]
            # host allocator: LIFO free stack + refcounts (shared prompt
            # blocks); all decisions host-deterministic for SPMD lockstep
            pool = PagePool(n, BS, max_batch, self.blocks_per_row, window)
            self._pools.append(pool)
            if window is None:
                self._pages, self.n_blocks = pool, n  # NB
                self.k_pool, self.v_pool, self.k_scale, self.v_scale = arrays
            else:
                self._win = pool
                self.win_k_pool, self.win_v_pool = arrays[:2]
        if self._by_kind:
            # the recurrent state slots, a slot a batch row (no byte where
            # no layer is recurrent)
            self.ssm_state, self.conv_state = hybrid.state_zeros(
                cfg, max_batch
            )
            pool_b += hybrid.state_layout_bytes(cfg, max_batch)
            if commit:
                self.ssm_state, self.conv_state = jax.device_put(
                    (self.ssm_state, self.conv_state), self.device
                )
        if self._stateful:
            # the snapshot slots of the fills kept for late siblings, in
            # arrays of their own (the step programs see the rows' arrays
            # at the shape they had, and no decode step reads a slot that
            # no row owns), and the kept fills' last logits rows
            n_snap = max(1, max_batch // 8)
            self._kept = KeptFills(n_snap, self._pools)
            self.snap_ssm, self.snap_conv = hybrid.state_zeros(cfg, n_snap)
            self._kept_logits = jnp.zeros(
                (n_snap, cfg.vocab_size), jnp.float32
            )
            pool_b += hybrid.state_layout_bytes(cfg, n_snap)
            pool_b += int(self._kept_logits.nbytes)
            if commit:
                self.snap_ssm, self.snap_conv, self._kept_logits = (
                    jax.device_put(
                        (self.snap_ssm, self.snap_conv, self._kept_logits),
                        self.device,
                    )
                )
        self._led_kv_pool.set(pool_b)
        self._led_kv_scales.set(scale_b)
        self.kv_lengths = jnp.zeros((max_batch,), jnp.int32)
        self._filling: List[_Fill] = []
        self._preempted: List[_Row] = []
        # cross-request radix prefix cache: trie nodes hold refcounted
        # pool blocks (the cache speaks to the allocator only through
        # incref/decref, so its evictions can never recycle a block a
        # live row still pins)
        # (none for a stateful model: cached pages hold a prefix's KV and
        # not the recurrent state at its end, so a match inside a prompt
        # could skip nothing; the one point where every fill's state is
        # saved is its END, and ``KeptFills`` reuses that)
        if self._prefix_cache_enabled and not self._stateful:
            host_bytes = self._prefix_cache_host_bytes
            if host_bytes > 0 and jax.process_count() > 1:
                logger.warning(
                    "prefix-cache host tier disabled: spill buffers are "
                    "per-process host memory, but this engine's pool is "
                    "sharded across %d SPMD processes (a local gather "
                    "would cover only this process's kv-head shard)",
                    jax.process_count(),
                )
                host_bytes = 0
            # one full block's k+v footprint — the host budget's unit.
            # Derived from the POOL ARRAYS' actual itemsize (not the
            # model dtype): an int8 pool's block is half the bytes and
            # carries its f32 scale slices, so spilled prefixes cost
            # their true host RAM and the budget admits ~2x the blocks.
            block_bytes = self._pool_block_bytes()
            self._prefix_cache = RadixPrefixCache(
                page_size=BS,
                capacity_blocks=int(
                    self._prefix_cache_capacity_frac * self.n_blocks
                ),
                acquire=self._cache_acquire,
                release=self._cache_release,
                min_match_tokens=self._prefix_cache_min_tokens,
                host_bytes_budget=host_bytes,
                block_bytes=block_bytes,
                spill_fetch=self._spill_gather if host_bytes > 0 else None,
                ledger_handle=self._led_spill,
            )
            # the effective knobs, logged once: the config default for
            # min_match_tokens (64) and the engine default (1) differ,
            # and a caller bypassing GenServerConfig silently gets the
            # engine's — make the value a fleet actually runs visible
            logger.info(
                "radix prefix cache: capacity=%d/%d pool blocks "
                "(frac=%.2f), min_match_tokens=%d (effective), host "
                "tier=%s",
                self._prefix_cache.capacity_blocks,
                self.n_blocks,
                self._prefix_cache_capacity_frac,
                self._prefix_cache.min_match_tokens,
                (
                    f"{host_bytes} bytes (~{host_bytes // block_bytes} "
                    "blocks)"
                    if host_bytes > 0
                    else "off"
                ),
            )
        self._paged_sample_fn, self._paged_stop_fn = _sample_and_stop_fns(
            self.sampling, self.stop_tokens, self._seed, self.mesh
        )

    # -- quantized KV storage helpers ---------------------------------------

    def _pool_arrays(self) -> List[jax.Array]:
        """The paged pool's storage arrays: (k, v) plus the scale pools
        when the storage is int8-quantized."""
        arrs = [self.k_pool, self.v_pool]
        if self.k_scale is not None:
            arrs += [self.k_scale, self.v_scale]
        return arrs

    def _pool_block_bytes(self) -> int:
        """One pool block's true byte footprint, derived from the
        allocated arrays' itemsize (int8 data + f32 scales for quantized
        pools, model dtype otherwise) — the unit every byte account
        (host spill budget, capacity math) must use."""
        return sum(int(a.nbytes) for a in self._pool_arrays()) // max(
            self.n_blocks, 1
        )

    def _copy_pages(self, pool: PagePool, src: List[int], dst: List[int]):
        """COW page copies inside ``pool``'s device arrays (group tails,
        prefix-cache tail matches), in power-of-two counts; int8 pools
        carry the scale slices with the bytes."""
        n_pad = 1 << (len(src) - 1).bit_length()
        s = np.zeros((n_pad,), np.int32)
        d = np.full((n_pad,), pool.n_blocks, np.int32)  # pad -> drop
        s[: len(src)], d[: len(dst)] = src, dst
        s, d = jnp.asarray(s), jnp.asarray(d)
        if pool is self._win:
            self.win_k_pool, self.win_v_pool = paged.copy_blocks(
                self.win_k_pool, self.win_v_pool, s, d
            )
            return
        out = paged.copy_blocks(
            self.k_pool, self.v_pool, s, d,
            k_scale=self.k_scale, v_scale=self.v_scale,
        )
        if self._kv_quant:
            self.k_pool, self.v_pool, self.k_scale, self.v_scale = out
        else:
            self.k_pool, self.v_pool = out

    def note_kv_divergence_check(self, checked: int, diverged: int):
        """Fold a measured greedy-divergence check (a parity harness
        compares an int8 arm against an fp arm token by token) into the
        engine's cumulative quality counters — the
        ``areal_inference_kv_quant_*`` divergence series."""
        self.kv_quant_divergence_checks_total += int(checked)
        self.kv_quant_divergence_diverged_total += int(diverged)

    def kv_quant_stats(self) -> Dict[str, int]:
        """Quantized-KV storage counters (worker scrape + metrics RPC)."""
        if self.paged:
            bits = int(jnp.dtype(self.k_pool.dtype).itemsize) * 8
            held = (
                self.n_blocks - self._pages.free_blocks
                if self._kv_quant
                else 0
            )
        else:
            bits = int(jnp.dtype(self.cache.k.dtype).itemsize) * 8
            held = 0
        return {
            "quantized": int(self._kv_quant),
            "storage_bits": bits,
            "quantized_blocks_held": int(held),
            "divergence_checks_total": self.kv_quant_divergence_checks_total,
            "divergence_diverged_total": (
                self.kv_quant_divergence_diverged_total
            ),
        }

    def note_weight_divergence_check(self, checked: int, diverged: int):
        """Fold a measured greedy-divergence check (a parity harness
        compares an int8-weight arm against a full-precision arm token
        by token) into the engine's cumulative
        quality counters — the ``areal_inference_weight_quant_*``
        divergence series."""
        self.weight_quant_divergence_checks_total += int(checked)
        self.weight_quant_divergence_diverged_total += int(diverged)

    def weight_quant_stats(self) -> Dict[str, int]:
        """Quantized-serving-weight counters (worker scrape + metrics
        RPC): resident format, storage bits, quantized-leaf
        count, the param tree's HBM byte footprint, and the measured
        divergence-check counters."""
        quantized = quantize.is_quantized_tree(self.params)
        if quantized:
            bits = quantize.STORAGE_BITS
        else:
            # the embedding is in every family's tree, at the weights' dtype
            w = self.params["embed"]["weight"]
            bits = int(jnp.dtype(w.dtype).itemsize) * 8
        return {
            "quantized": int(quantized),
            "storage_bits": bits,
            "quantized_leaves": quantize.quantized_leaf_count(self.params),
            "param_bytes": quantize.tree_bytes(self.params),
            "divergence_checks_total": (
                self.weight_quant_divergence_checks_total
            ),
            "divergence_diverged_total": (
                self.weight_quant_divergence_diverged_total
            ),
        }

    def weight_restore_template(self, fmt: str):
        """The restore/placement template for an incoming published
        tree in ``fmt`` ("full" | "int8").  The engine's resident params
        ARE the template when the formats agree (live arrays carry the
        serving shardings); an int8 engine negotiating a FULL-precision
        snapshot (publisher wrote no quantized tree) gets the abstract
        full template captured at construction — the server restores
        onto it, then quantizes on arrival so the engine's resident
        format never changes."""
        resident = (
            "int8" if quantize.is_quantized_tree(self.params) else "full"
        )
        if fmt == resident:
            return self.params
        if fmt == "full" and self._full_weight_template is not None:
            return self._full_weight_template
        if fmt == "int8":
            # an auto engine never negotiates int8; cover it anyway so a
            # direct caller gets a usable (unsharded) template
            return quantize.quant_tree_struct(self.params)
        raise ValueError(f"unknown weight format {fmt!r}")

    def prepare_weights(self, params):
        """Convert an incoming tree to the engine's RESIDENT format
        (quantize on arrival for an int8 engine handed a full-precision
        tree — the negotiation fallback; pass-through otherwise)."""
        if self._weight_quant and not quantize.is_quantized_tree(params):
            return quantize.quantize_param_tree(params)
        return params

    # -- the fills kept for late siblings (engine/kv_pages.KeptFills) --------

    def _slot_pairs(self, src: List[int], dst: List[int]):
        """``(src, dst, n)`` of ``hybrid.copy_state_slots_between``: one
        length whatever the count, so one program a direction."""
        pairs = np.zeros((2, self.max_batch), np.int32)
        pairs[0, : len(src)], pairs[1, : len(dst)] = src, dst
        return jnp.asarray(pairs[0]), jnp.asarray(pairs[1]), jnp.int32(len(src))

    def _row_arrays(self):
        return (
            self.cur_tokens, self.active, self.budgets, self.kv_lengths,
            self.row_seeds,
        )

    def _on_device(self, x):
        """COMMITTED where a step program's small outputs are (the
        engine's device; every device of its mesh, whole): a program's
        cache key holds each argument's placement."""
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            return jax.device_put(x, NamedSharding(self.mesh, P()))
        return jnp.asarray(x) if self.device is None else jax.device_put(
            x, self.device
        )

    def _warm_activation(self):
        """Build ``_activate_rows`` at every padded count a distribution
        can have, when the engine starts: which counts a warm-up's rounds
        meet is the schedule's, and a program first met under load is
        compiled there.  Every entry is padding: nothing is written.  The
        rows' arrays are committed from here on, like the outputs that
        take their place."""
        (self.cur_tokens, self.active, self.budgets, self.kv_lengths,
         self.row_seeds) = map(self._on_device, self._row_arrays())
        top = (self.max_batch - 1).bit_length()  # (2**top >= max_batch)
        counts = {LATE_JOINS_A_STEP} | {1 << k for k in range(top + 1)}
        for n in sorted(counts):
            self._start_rows(self._on_device(np.zeros((n,), np.int32)), [])

    def _warm_kept_fill_programs(self):
        """Build the small programs that keep a fill and hand it to late
        siblings, when the engine starts: no round of a warm-up meets
        them in every shape (WHICH fill batch ends a prompt, and whether a
        sibling comes late, is the schedule's), and a program first met
        under load is compiled there.  Nothing is copied (no pairs) but a
        row of zeros onto zeros."""
        none = self._slot_pairs([], [])
        self.snap_ssm, self.snap_conv = hybrid.copy_state_slots_between(
            self.ssm_state, self.conv_state, self.snap_ssm, self.snap_conv,
            *none,
        )
        self.ssm_state, self.conv_state = hybrid.copy_state_slots_between(
            self.snap_ssm, self.snap_conv, self.ssm_state, self.conv_state,
            *none,
        )
        zero = jnp.int32(0)
        f_pad = 1
        while f_pad < 2 * self.max_batch:  # every F_pad a fill batch can have
            logits = np.zeros(
                (f_pad, self.cfg.vocab_size), jnp.dtype(self.cfg.logits_dtype)
            )
            # (committed where a fill program's output is)
            logits = jax.device_put(logits, self.device)
            self._kept_logits = _keep_logits_row(
                self._kept_logits, logits, zero, zero
            )
            f_pad *= 2
        ids = jnp.asarray(np.zeros((LATE_JOINS_A_STEP,), np.int32))
        _sample_rows(
            self._kept_logits, ids, ids, ids, self._sample_base_rng,
            self.sampling, mesh=self.mesh,
        )

    def _keep_fills(self, fills: List[_Fill], idxs, logits):
        """Keep the fills that just ended (``_share_fill_blocks`` has
        handed their pages out): the end state from the fill's slot to a
        snapshot slot, the last logits row, a reference on every page."""
        src, dst = [], []
        for f, li in zip(fills, idxs):
            if f.targets[0].resume is not None:
                continue  # a preempted row's sequence: no request brings it
            if not self._kept.keep(f):
                continue
            src.append(f.state_slot)
            dst.append(f.snap)
            self._kept_logits = _keep_logits_row(
                self._kept_logits, logits, jnp.int32(li), jnp.int32(f.snap)
            )
        if src:
            self.snap_ssm, self.snap_conv = hybrid.copy_state_slots_between(
                self.ssm_state, self.conv_state, self.snap_ssm,
                self.snap_conv, *self._slot_pairs(src, dst),
            )

    @property
    def state_late_joins_total(self) -> int:
        """Requests served from a kept fill: no fill program ran for them."""
        return self._kept.late_joins_total

    @property
    def state_fills_kept_total(self) -> int:
        return self._kept.kept_total

    @property
    def state_fills_evicted(self) -> Dict[str, int]:
        """Kept fills let go, by cause: ``slots`` (a newer fill took the
        snapshot slot), ``pages`` (a live row needed a page), ``swap``
        (computed under the weights before a swap)."""
        return dict(self._kept.evicted)

    def _set_fill_row(self, row_id: int, fill: _Fill):
        """A fill's canonical pages live in its first target's tables
        (the same lists: what the fill releases behind itself, the row
        has released)."""
        for pool, held in self._pages_of(fill):
            pool.set_row(row_id, held)

    def _pages_of(self, fill: _Fill):
        """``fill``'s pages, pool by pool: (pool, its list)."""
        return zip(self._pools, (fill.blocks, fill.wblocks))

    def _release_row(self, row_id: int):
        """Single exit point for a row slot: frees its pages."""
        self.rows[row_id] = None
        for pool in self._pools:
            pool.release_row(row_id)

    # -- what the prefix cache holds (engine/kv_pages.py) -------------------

    def _cache_acquire(self, blocks: List[int]):
        self._pages.incref(blocks)
        if self._win is not None:
            self._win.cache_hold(blocks)

    def _cache_release(self, blocks: List[int]):
        if self._win is not None:
            self._win.cache_drop(blocks)
        self._pages.free(blocks)

    # -- allocation under pressure ------------------------------------------

    def _reclaim_one(
        self, keep_qids=(), preempt_but: Optional[int] = None,
        cache_blocks: int = 1, protect_step: Optional[int] = None,
    ):
        """One step of reclamation for an allocation that failed:
        ``cache_blocks`` prefix-cache entries (pure recompute insurance —
        the cache always yields to live rows; with the host tier on,
        "yield" means spill, not die), else the longest parked row, else
        (``preempt_but`` given: the row that must stay, or -1) the
        youngest decoding row.  A stateful engine has its kept fills where
        the cache is (``KeptFills``: the same insurance, one a step).
        Returns what went: "kept", "cache", "parked", "preempted" or
        None."""
        if self._kept.evict("pages"):
            return "kept"
        if self._prefix_cache is not None and self._prefix_cache.evict(
            cache_blocks, protect_step=protect_step
        ):
            return "cache"
        if self._evict_parked(keep_qids=keep_qids) is not None:
            return "parked"
        if preempt_but is None:
            return None
        victim = self._pick_preemption_victim(exclude=preempt_but)
        if victim is None:
            return None
        self._preempt_row(victim)
        return "preempted"

    def _alloc_reclaiming(
        self, pool: PagePool, n: int, keep_qids=(),
        preempt_but: Optional[int] = None,
        protect_step: Optional[int] = None,
    ) -> Optional[List[int]]:
        """``n`` pages of ``pool``, reclaiming as :meth:`_reclaim_one`
        until it has them; None when every tier the caller allows is
        exhausted (it may then preempt or requeue).  ``protect_step``
        spares cache nodes touched at that step — the swap-in path
        allocates while the nodes it is restoring sit freshly matched."""
        blocks = pool.alloc(n)
        while blocks is None:
            # the whole-context pool asks the cache for its whole deficit,
            # so that a host-tier round stays ONE batched gather; a cached
            # block's pair in the window pool may already be gone, so that
            # pool asks entry by entry
            ask = n - pool.free_blocks if pool is self._pages else 1
            went = self._reclaim_one(keep_qids, preempt_but, ask, protect_step)
            if went is None:
                return None
            blocks = pool.alloc(n)
        return blocks

    def _window_args(self, win_tables=None) -> Dict[str, Any]:
        """What the stack's two programs take besides, where it has window
        layers: their pools (donated) and ``win_tables`` (the rows' own
        where none is given)."""
        if self._win is None:
            return {}
        if win_tables is None:
            win_tables = self._win.upload()
        return dict(
            win_pools=(self.win_k_pool, self.win_v_pool),
            win_tables=jnp.asarray(win_tables),
        )

    # -- counts -------------------------------------------------------------

    def _live_rows(self):
        """The rows that decode or fill.  Parked rows and what the prefix
        cache holds are not live: ``free_pool_blocks`` counts both as
        held."""
        return (
            row_id for row_id, row in enumerate(self.rows)
            if row is not None and not row.parked
        )

    @property
    def window_pages_live(self) -> int:
        """Window-layer pages held by live rows, each once (0 for a stack
        without window layers)."""
        if self._win is None:
            return 0
        return self._win.live(self._live_rows())

    @property
    def index_pages_live(self) -> int:
        """Pages of index keys held by live rows (0 without an indexer):
        the whole-context pages', whose table they ride."""
        return self.pages_live if self.cfg.is_indexed else 0

    @property
    def window_pages_released(self) -> int:
        """Window-layer pages let go behind a holder's window so far."""
        return self._win.released_total if self._win is not None else 0

    @property
    def free_pool_blocks(self) -> int:
        return self._pages.free_blocks

    @property
    def pages_total(self) -> int:
        """Blocks of the paged KV pool (0 with the dense cache)."""
        return self.n_blocks if self.paged else 0

    @property
    def pages_live(self) -> int:
        """Pool blocks referenced by live rows, each block once however
        many siblings share it (0 with the dense cache)."""
        return self._pages.live(self._live_rows()) if self.paged else 0

    #: the pages of the layers that attend the whole context (all of a
    #: stack's pages where no layer has a window)
    global_pages_live = pages_live

    @property
    def state_slots_live(self) -> int:
        """Recurrent-state slots held by rows that decode or fill (0 for
        a model without such state; a slot is its row's)."""
        if not self._stateful:
            return 0
        return sum(1 for _ in self._live_rows())

    @property
    def state_slots_total(self) -> int:
        """Recurrent-state slots the engine holds: one a batch row."""
        return self.max_batch if self._stateful else 0

    def _cache_counts(self) -> Dict[str, int]:
        """What each cache kind the engine holds has live, of how much:
        the counts of the ``areal.engine.ensure_blocks`` span."""
        counts = dict(pages_live=self.pages_live, pages_total=self.pages_total)
        if self.cfg.is_indexed:
            # the index keys ride the whole-context pages' table: a live
            # page is a latent page AND its index page
            counts["index_pages_live"] = self.index_pages_live
        if self._win is not None:
            counts.update(
                window_pages_live=self.window_pages_live,
                window_pages_total=self._win.n_blocks,
                window_pages_released=self.window_pages_released,
                prefix_refused_window=self.prefix_refused_window,
            )
        if self._stateful:
            counts.update(
                state_slots_live=self.state_slots_live,
                state_slots_total=self.state_slots_total,
            )
        return counts

    # -- cross-request prefix cache ----------------------------------------

    def _spill_gather(self, blocks: List[int]):
        """Batched device->host gather of whole pool blocks (the cache's
        ``spill_fetch``), via the shared :func:`paged.gather_blocks_host`
        helper — int8 pools spill the quantized bytes plus their scale
        slices, half or less the host RAM of a model-dtype spill."""
        out = paged.gather_blocks_host(
            self.k_pool, self.v_pool, blocks,
            k_scale=self.k_scale, v_scale=self.v_scale,
        )
        self.host_spill_rounds_total += 1
        return out

    def _scatter_host_payloads(self, payloads, blocks: List[int]):
        """Dispatch ONE batched async scatter of host block payloads
        (per-block component tuples, as produced by the shared gather
        helper) into ``blocks`` — the device half of a host-tier swap-in
        AND of a handoff import.  The transfer rides under whatever
        decode chunks are queued behind it in the in-flight ring."""
        out = paged.restore_blocks_from_host(
            self.k_pool, self.v_pool, payloads, blocks,
            k_scale=self.k_scale, v_scale=self.v_scale,
        )
        if self._kv_quant:
            (self.k_pool, self.v_pool, self.k_scale, self.v_scale) = out
        else:
            self.k_pool, self.v_pool = out

    def _restore_spilled(self, nodes, keep_qids=()) -> bool:
        """Swap spilled prefix blocks back into the pool: allocate fresh
        blocks (reclamation protected from eating the nodes being
        restored), dispatch ONE batched async scatter of the host
        payloads (paged.restore_blocks — the transfer rides under the
        decode chunks queued in the in-flight ring), and mark the nodes
        usable from the NEXT engine step.  The triggering admission
        requeues meanwhile; its re-match next step lands resident.
        False when the pool cannot provide the blocks — the caller falls
        back to the resident-only prefix."""
        n = len(nodes)
        blocks = self._alloc_reclaiming(
            self._pages, n, keep_qids=keep_qids, protect_step=self._step_seq
        )
        if blocks is None:
            return False
        payloads = self._prefix_cache.begin_restore(nodes)
        self._scatter_host_payloads(payloads, blocks)
        self._prefix_cache.complete_restore(
            nodes, blocks, ready_step=self._step_seq + 1
        )
        self.host_restore_rounds_total += 1
        return True

    def _cache_insert(
        self, seq: List[int], blocks: List[int],
        wblocks: Optional[List[int]] = None,
    ):
        """Register ``seq``'s KV-bearing blocks in the radix cache (full
        blocks by reference, the partial tail by value).  ``wblocks``: the
        window layers' pages of the same sequence; the cache holds those
        of the sequence's last window with the blocks it took, so that a
        request that reuses the whole prefix finds them
        (``PagePool.cached_tail``)."""
        if self._prefix_cache is None or not seq or not blocks:
            return
        self._prefix_cache.insert(
            seq, blocks, step=self._step_seq, version=self.version
        )
        if self._win is not None:
            n_pages = min(-(-len(seq) // self.page_size), len(blocks))
            for i in range(self._win.first_kept(len(seq)), n_pages):
                self._win.cache_pair(blocks[i], wblocks[i])

    def _match_prefix(self, seq: List[int]) -> PrefixMatch:
        # record=False: a requeued admission re-matches every engine step
        # until the pool can serve it — hit/cached-token stats are counted
        # in _new_fill, once, when the fill is actually built
        if self._prefix_cache is None or len(seq) < 2:
            return PrefixMatch()
        return self._prefix_cache.match(
            seq, step=self._step_seq, record=False
        )

    def _new_fill(self, seq: List[int], keep_qids=()) -> Optional[_Fill]:
        """Build a ``_Fill`` for ``seq``, reusing the longest cached
        prefix: matched full blocks are PINNED (shared by reference), a
        matched partial tail is copied into an owned block (copy-on-write
        — the donor row may still be appending to it), and ``fill_pos``
        starts past the reused prefix so only the suffix is prefilled.
        Returns None when the pool cannot provide the non-cached blocks
        even after reclamation (caller requeues), or when the match
        landed on host-spilled blocks — their swap-in is dispatched (or
        already riding the ring) and the requeued admission re-matches
        into a resident prefix at the next engine step."""
        n_blocks = max(1, -(-len(seq) // self.page_size))
        m = self._match_prefix(seq)
        if m.restore_nodes or m.pending:
            restored = False
            if m.restore_nodes:
                restored = self._restore_spilled(
                    m.restore_nodes, keep_qids=keep_qids
                )
            if restored or m.pending:
                return None  # requeue: resident next step (step-keyed)
            # the pool couldn't serve the swap-in: fall back to the
            # resident-only prefix this match already carries (its tail
            # scan was skipped — correctness unaffected, just a shorter
            # reuse).  The match's floor gate passed on resident +
            # spilled tokens together; the resident part alone must
            # re-clear min_match_tokens or the fallback would pin a
            # reuse below the configured floor and count it as a hit
            if m.n_tokens < self._prefix_cache.min_match_tokens:
                m = PrefixMatch()
        pinned = list(m.blocks)
        if m.tail_block is not None:
            pinned.append(m.tail_block)
        wheld: Optional[List[int]] = []
        if self._win is not None and m.n_tokens:
            wheld = self._win.cached_tail(pinned, m.n_tokens)
            if wheld is None:
                # the window layers no longer hold [n - W + 1, n) of this
                # prefix: nothing of it can be skipped
                self.prefix_refused_window += 1
                m, pinned, wheld = PrefixMatch(), [], []
        # pool by pool: what it holds of the prefix (by reference; a cached
        # tail page last) and the rest of the fill's pages, its own.  All
        # of the first is pinned BEFORE anything is allocated: an
        # allocation in either pool may evict cache entries, and an
        # unpinned matched page could be recycled into our own allocation
        held, own = [pinned, wheld][: len(self._pools)], []
        for pool, h in zip(self._pools, held):
            pool.incref(h)
        for pool in self._pools:
            got = self._alloc_reclaiming(
                pool, n_blocks - len(m.blocks), keep_qids=keep_qids
            )
            if got is None:
                for had, h, o in zip(self._pools, held, own + [[], []]):
                    had.free(h + o)
                return None
            own.append(got)
        if self._prefix_cache is not None and len(seq) >= 2:
            self._prefix_cache.record(m)
        if self._stateful and any(
            r is not None and r.prompt == seq for r in self.rows
        ):
            # a late sibling: its prompt's end state sits in a live row's
            # slot, already moved on; this fill computes it again
            self.state_reprefills_total += 1
        if m.tail_block is not None:
            # COW: the partial tail's first tail_tokens are valid; copy
            # the whole block (append-only writes beyond that point are
            # the donor's garbage and our suffix fill overwrites them)
            for pool, h, o in zip(self._pools, held, own):
                self._copy_pages(pool, [h[-1]], [o[0]])
                pool.free([h.pop()])  # copy taken: unpin
        pages = [h + o for h, o in zip(held, own)] + [None]
        return _Fill(
            key=tuple(seq),
            tokens=list(seq),
            blocks=pages[0],
            targets=[],
            fill_pos=m.n_tokens,
            wblocks=pages[1],
        )

    def prefix_cache_stats(self) -> Dict[str, int]:
        if self._prefix_cache is None:
            return RadixPrefixCache.zero_stats()
        return self._prefix_cache.stats()

    # -- prefill/decode disaggregation: paged-block KV handoff ---------------

    def export_handoff(self, qid: str) -> Optional[Dict[str, Any]]:
        """Export a PARKED row's cache state as a handoff unit: the host
        request state plus every pool block's KV gathered to host numpy
        (the shared :func:`paged.gather_blocks_host` — int8 pools export
        quantized bytes + scales, bit-identical on restore).  The row is
        released; its blocks stay resident only through the radix
        cache's own references (the park already inserted them), so a
        sibling landing here later still reuses the prefix.

        Returns None when no parked row carries ``qid`` (already evicted
        by a weight swap or TTL — the decode side re-prefills) or on a
        dense engine.  This is the prefill role's half of the
        P/D-disaggregated serving path."""
        refuse("P/D handoff", self._kinds)
        if not self.paged:
            return None
        for row_id, row in enumerate(self.rows):
            if row is None or not row.parked or row.req.qid != qid:
                continue
            blocks = list(self._pages.rows[row_id])
            if not blocks:
                return None
            tik = time.perf_counter()
            payload = paged.gather_blocks_host(
                self.k_pool, self.v_pool, blocks,
                k_scale=self.k_scale, v_scale=self.v_scale,
            )
            unit = {
                "qid": qid,
                "req": row.req,
                "prompt": list(row.prompt),
                "generated": list(row.generated),
                "logprobs": list(row.logprobs),
                # the weight version this KV was computed under: the
                # importer must match it exactly or fail closed
                "version": self.version,
                "page_size": self.page_size,
                "kv_cache_dtype": self.kv_cache_dtype,
                "payload": payload,
            }
            self._release_row(row_id)
            n_bytes = int(sum(a.nbytes for a in payload))
            self.handoff_exports_total += 1
            self.handoff_bytes_total += n_bytes
            self.handoff_seconds_total += time.perf_counter() - tik
            self.tracer.event(
                qid, "engine.handoff_export",
                row=row_id, blocks=len(blocks), bytes=n_bytes,
                version=self.version,
            )
            return unit
        return None

    def _reject_handoff(self, qid: str, reason: str) -> Tuple[bool, str]:
        self.handoff_import_rejects[reason] = (
            self.handoff_import_rejects.get(reason, 0) + 1
        )
        self.tracer.event(
            qid, "engine.handoff_import", ok=False, reason=reason
        )
        logger.info("handoff import of %s rejected: %s", qid, reason)
        return False, reason

    def import_handoff(self, unit: Dict[str, Any]) -> Tuple[bool, str]:
        """Import a handoff unit exported by a prefill-role peer: scatter
        the host KV payload into freshly allocated pool blocks (one
        batched async dispatch riding under the decode ring) and park
        the row, so the continuation request — sticky-routed here by the
        manager — resumes through the ordinary ``_try_resume`` path with
        ZERO prefill.  The handed-off prefix also enters this engine's
        radix cache.

        Fails CLOSED on any skew: a unit whose weight ``version``
        differs from this engine's (a swap raced the handoff) is
        REJECTED — stale KV is never decoded; the continuation simply
        re-prefills under the current weights.  Layout mismatches
        (page size, kv dtype, context length) and pool/row exhaustion
        reject the same way.  Returns ``(ok, reason)``."""
        refuse("P/D handoff", self._kinds)
        t0 = time.perf_counter()
        qid = unit.get("qid", "?")
        if not self.paged:
            return self._reject_handoff(qid, "dense")
        if (
            unit.get("page_size") != self.page_size
            or unit.get("kv_cache_dtype") != self.kv_cache_dtype
        ):
            return self._reject_handoff(qid, "layout")
        if unit.get("version") != self.version:
            return self._reject_handoff(qid, "version")
        prompt = list(unit["prompt"])
        generated = list(unit["generated"])
        if not generated:
            return self._reject_handoff(qid, "empty")
        payload = unit["payload"]
        n = len(payload[0])
        # per-block payload geometry must match THIS pool exactly —
        # [L, Hkv, BS, hd] (scales [L, Hkv, BS]) — or the scatter would
        # raise mid-dispatch; a peer built from a different model config
        # rejects here instead
        pool_block_shape = self.k_pool.shape[:1] + self.k_pool.shape[2:]
        if (
            n > self.blocks_per_row
            or len(prompt) + len(generated) + 1 >= self.kv_cache_len
            or tuple(payload[0].shape[1:]) != pool_block_shape
            or len(payload) != len(self._pool_arrays())
        ):
            return self._reject_handoff(qid, "layout")
        rid = next(
            (i for i, r in enumerate(self.rows) if r is None), None
        )
        # never evict live work for an import (the fallback is a plain
        # re-prefill, not a correctness problem), and — like every other
        # eviction site — spare parked rows whose own continuation is
        # already queued: trading their zero-prefill resume for this
        # import's would just move the re-prefill cost around
        with self._lock:
            queued = {r.qid for r in self._pending}
        if rid is None:
            rid = self._evict_parked(keep_qids=queued)
        if rid is None:
            rid = self._evict_parked()  # unprotected last resort
        if rid is None:
            return self._reject_handoff(qid, "capacity")
        blocks = self._alloc_reclaiming(self._pages, n, keep_qids=queued)
        if blocks is None:
            return self._reject_handoff(qid, "pool")
        payloads = [tuple(a[i] for a in payload) for i in range(n)]
        try:
            self._scatter_host_payloads(payloads, blocks)
        except Exception:  # noqa: BLE001 - free the blocks, fail closed
            self._pages.free(blocks)
            logger.exception("handoff import scatter failed for %s", qid)
            return self._reject_handoff(qid, "scatter")
        row = _Row(
            req=unit["req"],
            prompt=prompt,
            generated=generated,
            logprobs=list(unit["logprobs"]),
            version_start=self.version,
            no_eos=True,
            cur_token=int(generated[-1]),
            parked=True,
            park_step=self._step_seq,
        )
        self._epoch_counter += 1
        row.epoch = self._epoch_counter
        self.rows[rid] = row
        self._pages.set_row(rid, blocks)
        # cached KV covers everything but the pending cur token
        n_kv = len(prompt) + len(generated) - 1
        self.kv_lengths = self.kv_lengths.at[
            np.array([rid], np.int32)
        ].set(n_kv)
        self._cache_insert((prompt + generated)[:-1], blocks)
        n_bytes = int(sum(a.nbytes for a in payload))
        self.handoff_imports_total += 1
        self.handoff_bytes_total += n_bytes
        self.handoff_seconds_total += time.perf_counter() - t0
        self.tracer.event(
            qid, "engine.handoff_import",
            ok=True, row=rid, blocks=n, bytes=n_bytes,
            version=self.version,
        )
        return True, ""

    # -- streamed (segmented) handoff: chunk-overlapped export/import --------
    #
    # The monolithic unit above ships gather + wire + scatter of the
    # WHOLE prompt after prefill completes — a serial bubble the size of
    # the prompt on the decode-resume path.  With ``handoff_streaming``
    # the prefill engine exports each fill chunk's now-FINAL full blocks
    # as a numbered segment the moment the chunk lands (one coalesced
    # buffer per segment, riding the same gather helper), the worker
    # pushes segments while later chunks still fill, and the decode
    # engine pre-allocates the row's blocks on segment 0 and
    # async-scatters each segment under its own decode chunks — so when
    # the final segment (tail block + first token + metadata) arrives,
    # the remaining resume gap is O(one chunk), not O(prompt).  Every
    # segment carries the exporter's weight version and is checked
    # fail-closed: any skew, sequence gap, abort, or dead-peer timeout
    # releases the partial blocks and the continuation re-prefills —
    # stale or incomplete KV is never decoded.

    def _gather_blocks_device(self, blocks: List[int]) -> Tuple[Any, ...]:
        """Dispatch ONE async whole-block gather (no device_get): the
        returned device arrays are materialized later — by the worker's
        push thread, off the engine thread — so the copy-out rides under
        the fill/decode chunks dispatched after it."""
        n = len(blocks)
        n_pad = 1 << (n - 1).bit_length()
        idx = np.zeros((n_pad,), np.int32)
        idx[:n] = blocks
        out = paged.gather_blocks(
            self.k_pool, self.v_pool, jnp.asarray(idx),
            k_scale=self.k_scale, v_scale=self.v_scale,
        )
        return tuple(a[:n] for a in out)

    def _queue_handoff_segment(
        self, qid: str, st: Dict[str, Any], blocks: List[int],
        total: int, final: bool, row: Optional[_Row] = None,
    ):
        """Gather ``blocks`` (may be empty on a final segment of a
        page-aligned prompt) and append one numbered segment to the
        outbound queue."""
        tik = time.perf_counter()
        payload = self._gather_blocks_device(blocks) if blocks else ()
        seg: Dict[str, Any] = {
            "qid": qid,
            "dest": st["dest"],
            "seq": st["seq"],
            "block_start": st["exported"],
            "n_blocks": len(blocks),
            "total_blocks": total,
            "version": self.version,
            "page_size": self.page_size,
            "kv_cache_dtype": self.kv_cache_dtype,
            "final": final,
            "payload": payload,
        }
        if final:
            assert row is not None
            seg["req"] = row.req
            seg["prompt"] = list(row.prompt)
            seg["generated"] = list(row.generated)
            seg["logprobs"] = list(row.logprobs)
        self._handoff_segments.append(seg)
        n_bytes = int(sum(a.nbytes for a in payload))
        self.handoff_segment_exports_total += 1
        self.handoff_bytes_total += n_bytes
        self.handoff_seconds_total += time.perf_counter() - tik
        if final:
            self.handoff_exports_total += 1
        self.tracer.event(
            qid, "engine.handoff_segment",
            seq=st["seq"], blocks=len(blocks), bytes=n_bytes,
            final=final, version=self.version,
        )
        st["seq"] += 1
        st["exported"] += len(blocks)

    def _emit_handoff_segments(self, f: _Fill):
        """Export the blocks a fill chunk just FINALIZED for every
        handoff-flagged target: full blocks strictly below ``fill_pos``
        never receive another write (the partial tail keeps appending
        until the fill completes and travels with the final segment)."""
        if not f.targets:
            return
        full_final = min(
            min(f.fill_pos, len(f.tokens)) // self.page_size,
            len(f.blocks),
        )
        if full_final <= 0:
            return
        for tgt in f.targets:
            if tgt.resume is not None:
                continue
            dest = (tgt.req.metadata or {}).get("handoff_to")
            if not dest:
                continue
            qid = tgt.req.qid
            st = self._handoff_streams.get(qid)
            if st is None:
                st = {"dest": dest, "seq": 0, "exported": 0}
                self._handoff_streams[qid] = st
            if st["exported"] >= full_final:
                continue
            self._queue_handoff_segment(
                qid, st, f.blocks[st["exported"] : full_final],
                total=len(f.blocks), final=False,
            )

    def _emit_final_handoff_segment(self, rid: int, row: _Row):
        """The stream's last segment: the tail block(s) not yet exported
        plus the first generated token and the host request state.  The
        row is then RELEASED — like the monolithic export, the radix
        cache's own references (inserted at fill completion) keep the
        prefix alive for sibling reuse on this server."""
        qid = row.req.qid
        dest = (row.req.metadata or {}).get("handoff_to")
        st = self._handoff_streams.pop(qid, None)
        if st is None:
            # no chunk boundary ever emitted (short prompt): the whole
            # handoff is this one final segment
            st = {"dest": dest, "seq": 0, "exported": 0}
        row_blocks = self._pages.rows[rid]
        self._queue_handoff_segment(
            qid, st, row_blocks[st["exported"] :],
            total=len(row_blocks), final=True, row=row,
        )
        self._release_row(rid)

    def _abort_handoff_stream(self, qid: str, reason: str = ""):
        """Cut an export stream short (EOS at the first token, a weight
        swap restarting the fill): queue an abort marker so the decode
        peer releases its partial blocks promptly (its TTL sweep is the
        dead-sender backstop)."""
        st = self._handoff_streams.pop(qid, None)
        if st is None or st["seq"] == 0:
            return  # nothing ever left this server: nothing to clean up
        self._handoff_segments.append({
            "qid": qid,
            "dest": st["dest"],
            "seq": st["seq"],
            "abort": True,
            "version": self.version,
        })
        self.handoff_segment_aborts_total += 1
        self.tracer.event(
            qid, "engine.handoff_segment",
            seq=st["seq"], abort=True, reason=reason,
        )

    def drain_handoff_segments(self) -> List[Dict[str, Any]]:
        """Pop the outbound export segments (worker poll loop; in-process
        drivers — dryrun, tests — pump them straight into the
        decode engine).  Payloads are still device arrays; the pusher
        materializes them (``jax.device_get``) off the engine thread."""
        out = self._handoff_segments
        self._handoff_segments = []
        return out

    def _scatter_stacked(self, components, blocks: List[int]):
        """One async scatter of a segment's coalesced payload into
        ``blocks`` — rides under whatever decode chunks are queued."""
        out = paged.restore_blocks_host_stacked(
            self.k_pool, self.v_pool, components, blocks,
            k_scale=self.k_scale, v_scale=self.v_scale,
        )
        if self._kv_quant:
            (self.k_pool, self.v_pool, self.k_scale, self.v_scale) = out
        else:
            self.k_pool, self.v_pool = out

    def _release_pending_handoff(self, qid: str, reason: str = ""):
        """Free a partially-imported stream's blocks (fail-closed: the
        continuation re-prefills).  ``reason`` counts a reject; empty
        means a benign replace (a fresh segment 0 restarting a stream)."""
        pend = self._handoff_pending.pop(qid, None)
        if pend is None:
            return
        self._pages.free(pend["blocks"])
        if reason:
            self._reject_handoff(qid, reason)

    def import_handoff_segment(self, seg: Dict[str, Any]) -> Tuple[bool, str]:
        """Import ONE segment of a streamed handoff.  Segment 0
        pre-allocates ALL ``total_blocks`` of the row (so later segments
        never wait on the allocator); every segment's coalesced payload
        is scattered with one async dispatch riding under the decode
        chunks; the final segment validates completeness, parks the row,
        stamps its device-side length, and radix-inserts the prefix —
        the continuation resumes through the ordinary ``_try_resume``
        with zero prefill.

        Fails CLOSED per segment: version skew (a weight swap on either
        side mid-stream), a sequence gap or unknown stream
        (``"stream"``), layout/geometry mismatches, pool/row exhaustion,
        and exporter aborts all release the partial blocks; reasons
        extend the monolithic set with ``stream`` | ``abort`` |
        ``expired`` (the TTL sweep for dead peers).  Stale or incomplete
        KV is never decoded."""
        refuse("P/D handoff", self._kinds)
        t0 = time.perf_counter()
        qid = seg.get("qid", "?")
        if seg.get("abort"):
            if qid in self._handoff_pending:
                self._release_pending_handoff(qid, reason="abort")
            return True, ""  # an abort for an unknown stream is a no-op
        if not self.paged:
            return self._reject_handoff(qid, "dense")
        if (
            seg.get("page_size") != self.page_size
            or seg.get("kv_cache_dtype") != self.kv_cache_dtype
        ):
            self._release_pending_handoff(qid)
            return self._reject_handoff(qid, "layout")
        if seg.get("version") != self.version:
            # per-segment version rule: EVERY segment must match the
            # current weights — a swap mid-stream invalidates whatever
            # was already scattered
            self._release_pending_handoff(qid)
            return self._reject_handoff(qid, "version")
        seq = int(seg.get("seq", -1))
        payload = seg.get("payload") or ()
        n = int(seg.get("n_blocks", 0))
        pend = self._handoff_pending.get(qid)
        if seq == 0:
            if pend is not None:
                # a restarted stream (exporter-side fill restart)
                # replaces the old half-stream — benign, not a reject
                self._release_pending_handoff(qid)
            total = int(seg.get("total_blocks", 0))
            if not 0 < total <= self.blocks_per_row:
                return self._reject_handoff(qid, "layout")
            with self._lock:
                queued = {r.qid for r in self._pending}
            blocks = self._alloc_reclaiming(
                self._pages, total, keep_qids=queued
            )
            if blocks is None:
                return self._reject_handoff(qid, "pool")
            pend = {
                "blocks": blocks,
                "next_seq": 0,
                "received": 0,
                "version": seg.get("version"),
                "step": self._step_seq,
                "total": total,
            }
            self._handoff_pending[qid] = pend
        elif (
            pend is None
            or pend["next_seq"] != seq
            or pend["version"] != seg.get("version")
            or pend["total"] != int(seg.get("total_blocks", -1))
        ):
            self._release_pending_handoff(qid)
            return self._reject_handoff(qid, "stream")
        start = int(seg.get("block_start", -1))
        if start != pend["received"] or start + n > pend["total"]:
            self._release_pending_handoff(qid)
            return self._reject_handoff(qid, "stream")
        if n:
            # per-segment geometry check — a peer built from a different
            # model config rejects BEFORE the scatter can raise
            pool_block_shape = (
                self.k_pool.shape[:1] + self.k_pool.shape[2:]
            )
            if (
                len(payload) != len(self._pool_arrays())
                or payload[0].shape[0] != n
                or tuple(payload[0].shape[1:]) != pool_block_shape
            ):
                self._release_pending_handoff(qid)
                return self._reject_handoff(qid, "layout")
            try:
                self._scatter_stacked(
                    payload, pend["blocks"][start : start + n]
                )
            except Exception:  # noqa: BLE001 - free and fail closed
                logger.exception(
                    "handoff segment scatter failed for %s", qid
                )
                self._release_pending_handoff(qid)
                return self._reject_handoff(qid, "scatter")
        pend["received"] += n
        pend["next_seq"] = seq + 1
        pend["step"] = self._step_seq
        n_bytes = int(sum(a.nbytes for a in payload))
        final = bool(seg.get("final"))

        def _count_segment():
            # counted only once the segment is ACCEPTED: a final segment
            # rejected below must not let the export/import segment
            # counters read as balanced while the stream actually failed
            self.handoff_segment_imports_total += 1
            self.handoff_bytes_total += n_bytes
            self.tracer.event(
                qid, "engine.handoff_segment_import",
                seq=seq, blocks=n, bytes=n_bytes, final=final,
                version=self.version,
            )

        if not final:
            _count_segment()
            self.handoff_seconds_total += time.perf_counter() - t0
            return True, ""
        # final segment: completeness + host state, then park for resume
        if pend["received"] != pend["total"]:
            self._release_pending_handoff(qid)
            return self._reject_handoff(qid, "stream")
        prompt = list(seg.get("prompt") or [])
        generated = list(seg.get("generated") or [])
        if not generated:
            self._release_pending_handoff(qid)
            return self._reject_handoff(qid, "empty")
        n_kv = len(prompt) + len(generated) - 1
        if (
            len(prompt) + len(generated) + 1 >= self.kv_cache_len
            or -(-n_kv // self.page_size) > pend["total"]
        ):
            self._release_pending_handoff(qid)
            return self._reject_handoff(qid, "layout")
        rid = next(
            (i for i, r in enumerate(self.rows) if r is None), None
        )
        with self._lock:
            queued = {r.qid for r in self._pending}
        if rid is None:
            rid = self._evict_parked(keep_qids=queued)
        if rid is None:
            rid = self._evict_parked()  # unprotected last resort
        if rid is None:
            self._release_pending_handoff(qid)
            return self._reject_handoff(qid, "capacity")
        blocks = pend["blocks"]
        del self._handoff_pending[qid]  # ownership moves to the row
        row = _Row(
            req=seg["req"],
            prompt=prompt,
            generated=generated,
            logprobs=list(seg.get("logprobs") or []),
            version_start=self.version,
            no_eos=True,
            cur_token=int(generated[-1]),
            parked=True,
            park_step=self._step_seq,
        )
        self._epoch_counter += 1
        row.epoch = self._epoch_counter
        self.rows[rid] = row
        self._pages.set_row(rid, blocks)
        self.kv_lengths = self.kv_lengths.at[
            np.array([rid], np.int32)
        ].set(n_kv)
        self._cache_insert((prompt + generated)[:-1], blocks)
        _count_segment()
        self.handoff_imports_total += 1
        self.handoff_seconds_total += time.perf_counter() - t0
        self.tracer.event(
            qid, "engine.handoff_import",
            ok=True, row=rid, blocks=pend["total"], streamed=True,
            version=self.version,
        )
        return True, ""

    def prefill_backlog_tokens(self) -> int:
        """In-flight prefill-token backlog: prompt tokens admitted to the
        fill queue but not yet filled, plus the queued prompts waiting
        for admission.  Computed fresh from the live structures, so a
        completed handoff, a finished fill, and a failed/evicted row all
        decrement it by construction — the load signal the gserver
        manager's least-backlog prefill admission routes on."""
        backlog = 0
        if self.paged:
            for f in self._filling:
                backlog += max(0, len(f.tokens) - f.fill_pos)
        with self._lock:
            for r in self._pending:
                backlog += len(r.input_ids or r.prompt_ids)
        return backlog

    def handoff_stats(self) -> Dict[str, Any]:
        """Cumulative KV-handoff counters (worker scrape + metrics RPC)."""
        return {
            "exports_total": self.handoff_exports_total,
            "imports_total": self.handoff_imports_total,
            "bytes_total": self.handoff_bytes_total,
            "seconds_total": self.handoff_seconds_total,
            "import_rejects": dict(self.handoff_import_rejects),
            "segment_exports_total": self.handoff_segment_exports_total,
            "segment_imports_total": self.handoff_segment_imports_total,
            "segment_aborts_total": self.handoff_segment_aborts_total,
            "pending_streams": len(self._handoff_pending),
        }

    # -- fleet KV fabric: cross-server prefix pull ---------------------------
    #
    # The radix cache above makes cached prefixes a PER-SERVER resource;
    # the fabric makes them a FLEET one.  When the gserver manager's
    # schedule response names a peer that owns a longer hot prefix for a
    # session (``kv_source`` metadata — the manager's directory tracks
    # per-session longest-prefix owners), the admission registers a pull
    # intent instead of re-prefilling, and requeues step-keyed.  The
    # worker runs the owner's export_prefix RPC off-thread and replays
    # the returned numbered segments through import_prefix_segment as
    # lockstep commands; the final segment radix-inserts the pulled
    # blocks, so the requeued admission's next match lands on them and
    # only the un-pulled suffix prefills.  Every reject — version skew,
    # geometry, pool pressure, dead owner, TTL — releases the partial
    # blocks and falls back to a plain re-prefill: the fabric is an
    # optimization, never a correctness dependency.

    def export_prefix(self, qid: str, tokens: List[int]):
        """Owner side: the longest cached full-block run covering
        ``tokens`` as numbered wire segments (numpy payloads in
        :func:`paged.restore_blocks_host_stacked`'s stacked component
        format — the streamed-handoff segment format minus the row
        state).  Device-resident blocks pay ONE batched gather
        (:func:`paged.gather_blocks_host`); host-spilled blocks ship
        their spill payloads directly — the spill buffer already IS the
        wire format.  Returns ``[]`` when nothing exportable is cached
        (the puller re-prefills)."""
        refuse("prefix pulls", self._kinds)
        if not self.paged or self._prefix_cache is None or len(tokens) < 2:
            return []
        entries = self._prefix_cache.export_walk(
            tokens, step=self._step_seq
        )
        if not entries:
            return []
        dev_ids = [v for kind, v in entries if kind == "device"]
        dev = (
            paged.gather_blocks_host(
                self.k_pool, self.v_pool, dev_ids,
                k_scale=self.k_scale, v_scale=self.v_scale,
            )
            if dev_ids
            else None
        )
        per_block = []
        di = 0
        for kind, v in entries:
            if kind == "device":
                per_block.append(tuple(np.asarray(a[di]) for a in dev))
                di += 1
            else:
                per_block.append(v)
        total = len(per_block)
        n_tokens = total * self.page_size
        # segment at fill-chunk granularity — the same unit the
        # streamed handoff exports, so segment sizes (and the import
        # side's scatter batches) look identical on the wire
        seg_blocks = max(1, self.prefill_chunk_tokens // self.page_size)
        segs = []
        start = 0
        while start < total:
            n = min(seg_blocks, total - start)
            final = start + n == total
            seg = {
                "qid": qid,
                "seq": len(segs),
                "block_start": start,
                "n_blocks": n,
                "total_blocks": total,
                "version": self.version,
                "page_size": self.page_size,
                "kv_cache_dtype": self.kv_cache_dtype,
                "final": final,
                "payload": paged.stack_host_payloads(
                    per_block[start : start + n]
                ),
            }
            if final:
                seg["n_tokens"] = n_tokens
            segs.append(seg)
            start += n
        self.tracer.event(
            qid, "engine.prefix_export",
            blocks=total, tokens=n_tokens, segments=len(segs),
            version=self.version,
        )
        return segs

    def _reject_prefix_pull(self, qid: str, reason: str) -> Tuple[bool, str]:
        """Fail ONE pull closed: release any partially-imported blocks
        (zero-leak — the radix insert never saw them) and mark the
        record failed so the requeued admission falls back to a plain
        re-prefill at its next step."""
        rec = self._prefix_pulls.get(qid)
        if rec is not None:
            blocks = rec.get("blocks")
            if blocks:
                self._pages.free(blocks)
                rec["blocks"] = []
            rec["state"] = "failed"
            rec["step"] = self._step_seq
        self.prefix_pull_rejects_inc(reason)
        self.tracer.event(
            qid, "engine.prefix_pull", ok=False, reason=reason
        )
        logger.info("prefix pull for %s rejected: %s", qid, reason)
        return False, reason

    def prefix_pull_rejects_inc(self, reason: str):
        self.prefix_peer_pull_rejects[reason] = (
            self.prefix_peer_pull_rejects.get(reason, 0) + 1
        )

    def prefix_pull_failed(self, qid: str, reason: str = "rpc"):
        """The worker's pull RPC died or the owner had nothing (a
        lockstep command, so every controller fails the record at the
        identical step)."""
        if qid in self._prefix_pulls:
            self._reject_prefix_pull(qid, reason)

    def drain_prefix_pull_requests(self) -> List[Dict[str, Any]]:
        """Pop the queued pull intents (worker poll loop; in-process
        drivers pump them straight into the owner engine's
        export_prefix)."""
        out = self._prefix_pull_requests
        self._prefix_pull_requests = []
        for req in out:
            rec = self._prefix_pulls.get(req["qid"])
            if rec is not None and rec["state"] == "requested":
                rec["state"] = "pulling"
        return out

    def _maybe_pull_prefix(self, req, prompt: List[int]) -> bool:
        """Admission-side fabric gate: when the schedule response named
        a peer owning a longer hot prefix (``kv_source`` metadata) and
        the local radix match is short, register a pull intent and tell
        the caller to requeue step-keyed (never a readiness probe —
        SPMD lockstep).  Returns True while the pull is in flight;
        False once it landed (the next radix walk hits the pulled
        blocks), failed closed, or was never worth the RPC."""
        meta = req.metadata or {}
        source = meta.get("kv_source")
        if not source or not self.paged or self._prefix_cache is None:
            return False
        qid = req.qid
        rec = self._prefix_pulls.get(qid)
        if rec is not None:
            if rec["state"] in ("requested", "pulling"):
                return True
            # done or failed: consume the hint so pool churn can never
            # re-trigger the same pull in a loop
            del self._prefix_pulls[qid]
            meta.pop("kv_source", None)
            return False
        want = len(prompt) - 1
        resident = self._match_prefix(prompt).n_tokens
        if want - resident < max(
            self.page_size, self.prefix_pull_min_tokens
        ):
            meta.pop("kv_source", None)
            return False
        self._prefix_pulls[qid] = {
            "state": "requested",
            "step": self._step_seq,
            "source": source,
            "tokens": list(prompt),
            "blocks": [],
            "bytes": 0,
        }
        self._prefix_pull_requests.append(
            {"qid": qid, "source": source, "tokens": list(prompt)}
        )
        self.tracer.event(
            qid, "engine.prefix_pull", source=source,
            prompt_len=len(prompt), resident=resident,
        )
        return True

    def import_prefix_segment(self, seg: Dict[str, Any]) -> Tuple[bool, str]:
        """Import ONE segment of a fleet prefix pull — the pull-side
        twin of :meth:`import_handoff_segment`, same fail-closed rules:
        segment 0 pre-allocates ALL ``total_blocks``; every segment's
        version must match the current weights; sequence gaps, geometry
        mismatches, pool exhaustion, and scatter failures release the
        partial blocks (zero-leak) and the admission re-prefills.  The
        final segment radix-inserts the pulled prefix — the cache takes
        its own references and the pull's are dropped, so ownership
        rules are identical to a locally-computed prefix."""
        refuse("prefix pulls", self._kinds)
        t0 = time.perf_counter()
        qid = seg.get("qid", "?")
        if not self.paged:
            return self._reject_prefix_pull(qid, "dense")
        rec = self._prefix_pulls.get(qid)
        if rec is None or rec["state"] not in ("requested", "pulling"):
            # a late segment for a pull the TTL/weight sweep already
            # settled: count it, nothing to release
            return self._reject_prefix_pull(qid, "stream")
        if (
            seg.get("page_size") != self.page_size
            or seg.get("kv_cache_dtype") != self.kv_cache_dtype
        ):
            return self._reject_prefix_pull(qid, "layout")
        if seg.get("version") != self.version:
            # per-segment version rule: a swap on either side mid-pull
            # invalidates whatever was already scattered
            return self._reject_prefix_pull(qid, "version")
        seq = int(seg.get("seq", -1))
        payload = seg.get("payload") or ()
        n = int(seg.get("n_blocks", 0))
        if seq == 0:
            if rec.get("blocks"):
                # one RPC per pull — a duplicate segment 0 is skew
                return self._reject_prefix_pull(qid, "stream")
            total = int(seg.get("total_blocks", 0))
            if not 0 < total <= self.blocks_per_row:
                return self._reject_prefix_pull(qid, "layout")
            with self._lock:
                queued = {r.qid for r in self._pending}
            blocks = self._alloc_reclaiming(
                self._pages, total, keep_qids=queued
            )
            if blocks is None:
                return self._reject_prefix_pull(qid, "pool")
            rec.update(
                blocks=blocks, next_seq=0, received=0,
                version=seg.get("version"), total=total,
            )
        elif (
            not rec.get("blocks")
            or rec.get("next_seq") != seq
            or rec.get("version") != seg.get("version")
            or rec.get("total") != int(seg.get("total_blocks", -1))
        ):
            return self._reject_prefix_pull(qid, "stream")
        start = int(seg.get("block_start", -1))
        if start != rec["received"] or start + n > rec["total"]:
            return self._reject_prefix_pull(qid, "stream")
        if n:
            pool_block_shape = (
                self.k_pool.shape[:1] + self.k_pool.shape[2:]
            )
            if (
                len(payload) != len(self._pool_arrays())
                or payload[0].shape[0] != n
                or tuple(payload[0].shape[1:]) != pool_block_shape
            ):
                return self._reject_prefix_pull(qid, "layout")
            try:
                self._scatter_stacked(
                    payload, rec["blocks"][start : start + n]
                )
            except Exception:  # noqa: BLE001 - free and fail closed
                logger.exception(
                    "prefix pull scatter failed for %s", qid
                )
                return self._reject_prefix_pull(qid, "scatter")
        rec["received"] += n
        rec["next_seq"] = seq + 1
        rec["step"] = self._step_seq
        rec["bytes"] += int(sum(a.nbytes for a in payload))
        if not seg.get("final"):
            self.handoff_seconds_total += time.perf_counter() - t0
            return True, ""
        if rec["received"] != rec["total"]:
            return self._reject_prefix_pull(qid, "stream")
        n_tokens = int(
            seg.get("n_tokens") or rec["total"] * self.page_size
        )
        key = list(rec["tokens"][:n_tokens])
        blocks = rec["blocks"]
        rec["blocks"] = []
        # the radix insert takes its OWN references; the pull's are
        # dropped right after, so the cache is the sole owner — exactly
        # the ownership a locally-filled prefix ends up with, and the
        # zero-leak invariant holds even if a raced flush drops the
        # insert (refs then hit zero and the blocks recycle)
        self._cache_insert(key, blocks)
        self._pages.free(blocks)
        rec["state"] = "done"
        rec["step"] = self._step_seq
        self.prefix_peer_pulls_total += 1
        self.prefix_peer_pull_bytes_total += rec["bytes"]
        self.handoff_seconds_total += time.perf_counter() - t0
        self.tracer.event(
            qid, "engine.prefix_pull", ok=True,
            blocks=rec["total"], tokens=len(key), bytes=rec["bytes"],
            version=self.version,
        )
        return True, ""

    def prefix_peer_stats(self) -> Dict[str, Any]:
        """Cumulative fleet-fabric pull counters (worker scrape +
        metrics RPC)."""
        return {
            "pulls_total": self.prefix_peer_pulls_total,
            "pull_bytes_total": self.prefix_peer_pull_bytes_total,
            "pull_rejects": dict(self.prefix_peer_pull_rejects),
            "pending_pulls": len(self._prefix_pulls),
        }

    # -- client API (any thread) -------------------------------------------

    def submit(self, req: model_api.APIGenerateInput) -> str:
        if self._kinds:
            meta = req.metadata or {}
            if meta.get("handoff_to"):
                refuse("P/D handoff", self._kinds)
            if meta.get("kv_source"):
                refuse("prefix pulls", self._kinds)
        with self._lock:
            self._pending.append(req)
            ev = threading.Event()
            self._result_events[req.qid] = ev
            if self._slo_enabled:
                self._submit_ts[req.qid] = time.monotonic()
            if (req.metadata or {}).get("stream"):
                self._streams[req.qid] = {
                    "toks": deque(maxlen=self.stream_buffer_cap),
                    "drain_step": self._step_seq,
                    "dropped": 0,
                }
                self.streams_opened_total += 1
        return req.qid

    # -- request-level SLO plane ---------------------------------------------

    def _slo_admitted(self, row: _Row, now: Optional[float] = None):
        """Stamp a row's submit/admit times (admission-wait starts the
        TTFT decomposition).  Called once wherever a request binds to a
        cache row: dense admit, paged fill admission, park-resume."""
        if not self._slo_enabled:
            return
        now = time.monotonic() if now is None else now
        with self._lock:
            t0 = self._submit_ts.pop(row.req.qid, now)
        row.t_submit = t0
        row.t_admit = now
        row.t_first = row.t_last = 0.0
        row.slo_stall_s = 0.0
        row.t_preempt = 0.0

    def _slo_first_token(self, row: _Row, now: Optional[float] = None):
        if not self._slo_enabled or row.t_first:
            return
        row.t_first = row.t_last = (
            time.monotonic() if now is None else now
        )

    def _slo_finish(self, row: _Row):
        """Fold a finished (or parked — each chunk is a completed request
        from the client's view) row into the records deque + digests."""
        if not self._slo_enabled:
            return
        with self._lock:
            self._submit_ts.pop(row.req.qid, None)
        tokens = len(row.generated)
        if row.t_admit == 0.0 or row.t_first == 0.0 or tokens == 0:
            return  # never admitted / produced nothing: no decomposition
        md = row.req.metadata or {}
        ttft = max(0.0, row.t_first - row.t_submit)
        tpot = (
            max(0.0, row.t_last - row.t_first) / (tokens - 1)
            if tokens >= 2
            else None
        )
        sched = md.get("slo_schedule_wait_s")
        rec = LatencyRecord(
            qid=row.req.qid,
            workload=str(md.get("workload", "rollout")),
            server=self.server_name,
            mesh_devices=self.mesh_devices,
            schedule_wait_s=(
                float(sched) if isinstance(sched, (int, float)) else None
            ),
            admission_wait_s=max(0.0, row.t_admit - row.t_submit),
            ttft_s=ttft,
            tpot_s=tpot,
            stall_s=row.slo_stall_s,
            tokens=tokens,
        )
        self._slo_records.append(rec)
        self.slo_records_total += 1
        d = self._slo_digests
        d["admission_wait_s"].observe(rec.admission_wait_s)
        d["ttft_s"].observe(ttft)
        d["stall_s"].observe(rec.stall_s)
        if tpot is not None:
            d["tpot_s"].observe(tpot)

    def drain_slo_records(self) -> List[LatencyRecord]:
        """Pop the recent per-request latency records (the worker feeds
        them into the ``areal_slo_*`` registry histograms)."""
        out = list(self._slo_records)
        self._slo_records.clear()
        return out

    def slo_stats(self) -> Dict[str, Any]:
        """Percentile summary of the engine-local digests (metrics RPC);
        ``digests`` carries the mergeable raw state."""
        return {
            "records_total": self.slo_records_total,
            **{k: d.percentiles() for k, d in self._slo_digests.items()},
        }

    def slo_digests(self) -> Dict[str, Dict[str, Any]]:
        return {k: d.to_dict() for k, d in self._slo_digests.items()}

    def wait_result(
        self, qid: str, timeout: float = 600.0
    ) -> model_api.APIGenerateOutput:
        ev = self._result_events.get(qid)
        assert ev is not None, f"unknown qid {qid}"
        if not ev.wait(timeout):
            raise TimeoutError(f"generation {qid} timed out")
        with self._lock:
            self._result_events.pop(qid, None)
            return self._results.pop(qid)

    def try_get_result(self, qid: str) -> Optional[model_api.APIGenerateOutput]:
        """Non-blocking result fetch (server loop polls this)."""
        with self._lock:
            if qid in self._results:
                self._result_events.pop(qid, None)
                return self._results.pop(qid)
        return None

    def drain_results(self) -> Dict[str, model_api.APIGenerateOutput]:
        """Pop every finished result (SPMD follower controllers discard
        theirs — the leader owns client replies)."""
        with self._lock:
            out = dict(self._results)
            self._results.clear()
            for qid in out:
                self._result_events.pop(qid, None)
                # follower controllers never poll streams: prune each
                # finished request's buffer with its discarded result
                self._streams.pop(qid, None)
        return out

    # -- gateway token streams + cancel --------------------------------------

    def _stream_push(self, row: _Row, toks: List[int]):
        """Feed a row's freshly-folded tokens into its gateway stream
        (no-op for non-streaming requests — one dict miss)."""
        if not toks:
            return
        with self._lock:
            st = self._streams.get(row.req.qid)
            if st is None:
                return
            q = st["toks"]
            before = len(q)
            q.extend(int(t) for t in toks)
            dropped = before + len(toks) - len(q)
            if dropped > 0:  # bounded buffer overflowed (undrained)
                st["dropped"] += dropped
                self.stream_dropped_total += dropped

    def drain_stream(self, qid: str) -> Optional[List[int]]:
        """Pop a stream's buffered tokens (None = unknown/closed stream).
        Read-only from the SPMD view — safe on the leader off the
        command batch, like metrics."""
        with self._lock:
            st = self._streams.get(qid)
            if st is None:
                return None
            st["drain_step"] = self._step_seq
            out = list(st["toks"])
            st["toks"].clear()
            return out

    def stream_close(self, qid: str):
        with self._lock:
            self._streams.pop(qid, None)

    def stale_stream_qids(self) -> List[str]:
        """Streams nobody drained for ``stream_stale_steps`` engine steps
        (step-keyed, never wall clock): the leader turns these into
        cancel commands — the dead-gateway-client backstop."""
        with self._lock:
            return [
                qid for qid, st in self._streams.items()
                if self._step_seq - st["drain_step"]
                > self.stream_stale_steps
            ]

    def stream_stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "open_streams": len(self._streams),
                "opened_total": self.streams_opened_total,
                "dropped_tokens_total": self.stream_dropped_total,
                "cancelled_total": self.cancelled_total,
            }

    def _finalize_cancel(self, qid: str):
        with self._lock:
            self._results.pop(qid, None)
            self._result_events.pop(qid, None)
            self._submit_ts.pop(qid, None)
            self._streams.pop(qid, None)
        self._cancel_wanted.discard(qid)
        self.cancelled_total += 1
        self.tracer.event(qid, "engine.cancel", step=self._step_seq)

    def cancel(self, qid: str) -> bool:
        """Cancel a request wherever it lives — pending, preempted,
        decoding, parked, or finished-but-uncollected — releasing every
        block it pins (the disconnect leak audit rides on this).

        MUST be called from the engine-stepping thread: cancelling an
        active row rewrites the pool, so under SPMD it rides the
        command batch like submit (every controller replays it at the
        same step).  A mid-fill row defers into ``_cancel_wanted`` and
        is retried after ``_advance_fill`` each step."""
        # pending: never admitted, nothing on device
        with self._lock:
            for i, req in enumerate(self._pending):
                if req.qid == qid:
                    self._pending.pop(i)
                    break
            else:
                req = None
        if req is not None:
            self._finalize_cancel(qid)
            return True
        # preempted: host-side row awaiting re-admission
        if self.paged:
            for i, row in enumerate(self._preempted):
                if row.req.qid == qid:
                    self._preempted.pop(i)
                    self._finalize_cancel(qid)
                    return True
        for row_id, row in enumerate(self.rows):
            if row is None or row.req.qid != qid:
                continue
            if row.filling:
                # the fill machinery owns this row's blocks mid-prefill;
                # retried next step once the fill completes or dies
                self._cancel_wanted.add(qid)
                return True
            if not row.parked:
                # fold every in-flight chunk first: the ring snapshots
                # reference this row (same flush as preemption)
                self._drain_ring()
                row = self.rows[row_id]
                if row is None or row.req.qid != qid:
                    # finished (or slot reused) during the drain
                    self._finalize_cancel(qid)
                    return True
                if row.filling:
                    self._cancel_wanted.add(qid)
                    return True
            if not row.parked:
                self.active = self.active.at[row_id].set(False)
            self._release_row(row_id)
            self._finalize_cancel(qid)
            return True
        # already finished (result awaiting pickup) or residual state
        with self._lock:
            known = (
                qid in self._results
                or qid in self._result_events
                or qid in self._streams
            )
        if known:
            self._finalize_cancel(qid)
            return True
        return False

    def _process_deferred_cancels(self):
        if not self._cancel_wanted:
            return
        for qid in list(self._cancel_wanted):
            self._cancel_wanted.discard(qid)
            self.cancel(qid)  # re-defers itself if still mid-fill

    def update_weights(
        self,
        params,
        version: Optional[int] = None,
        pre_sharded: bool = False,
    ) -> int:
        """Swap weights between chunks; in-flight rows' KV is recomputed under
        the new weights on the next loop iteration.  Returns the number of
        interrupted (in-flight) requests — the patch's return contract.

        ``pre_sharded``: the tree is already device-resident under this
        engine's shardings (a staged tree); the apply becomes a pure
        pointer flip with no transfer on the paused critical path."""
        with self._lock:
            self._new_params = (params, version, pre_sharded)
            return self.n_inflight

    # -- staged (zero-downtime) weight sync ---------------------------------

    def stage_weights(self, params, version: int) -> int:
        """Prepare ``params`` as a device-resident STAGED tree while decode
        continues: shard onto this engine's param shardings (a no-op when
        the caller restored directly onto them) and block until every
        buffer is materialized — so the later :meth:`commit_staged` pays
        zero transfer inside the fleet pause.  Safe to call from a
        non-engine thread; only the staged slot is touched."""
        tik = time.perf_counter()
        if self._param_shardings is not None:
            params = jax.device_put(params, self._param_shardings)
        elif self.device is not None:
            params = jax.device_put(params, self.device)
        jax.block_until_ready(params)
        with self._lock:
            if version is not None and version <= self.version:
                # stale stage: a same-or-newer tree already serves (the
                # round fell back to a full reload while this restore
                # was still running).  Parking the tree anyway would pin
                # a whole extra model copy in HBM until the next round.
                self.swap_stage_s += time.perf_counter() - tik
                logger.info(
                    "discarding stale staged weights v%s (engine already "
                    "at v%d)", version, self.version,
                )
                return version
            self._staged_params = params
            self._staged_version = version
            self._ledger_sync_staged_locked()
        self.swap_stage_s += time.perf_counter() - tik
        logger.info(
            "staged weights v%d in %.3fs (decode uninterrupted)",
            version, time.perf_counter() - tik,
        )
        return version

    def _ledger_sync_staged_locked(self):
        """Re-derive the ``staged_weights`` attribution from the two
        slots that can hold a device-resident swap tree: the staged slot
        and a committed-but-unapplied PRE-SHARDED pending tree (a
        non-pre-sharded pending tree is a host tree — not device bytes
        yet).  Caller holds ``self._lock``."""
        nbytes = tree_nbytes(self._staged_params)
        if self._new_params is not None and self._new_params[2]:
            nbytes += tree_nbytes(self._new_params[0])
        self._led_staged.set(nbytes)

    def _ledger_sync_host_buffers(self):
        """Recompute the ``stream_buffers`` / ``handoff_staging``
        host-byte attributions from the actual queues, once per engine
        step — these queues mutate at a dozen sites, and a recomputed
        total can never drift the way incremental deltas would."""
        if not self.hbm_ledger.enabled:
            return
        with self._lock:
            # undrained gateway tokens: int32 ids (logical bytes — the
            # wire/payload size, not CPython object overhead)
            stream_b = 4 * sum(
                len(st["toks"]) for st in self._streams.values()
            )
        self._led_streams.set(stream_b)
        handoff_b = sum(
            int(a.nbytes)
            for seg in self._handoff_segments
            for a in seg.get("payload", ())
        )
        self._led_handoff.set(handoff_b)

    @property
    def staged_version(self) -> Optional[int]:
        """Version of the currently staged (uncommitted) tree, if any."""
        return self._staged_version

    @property
    def pending_version(self) -> Optional[int]:
        """Target version of a committed-but-not-yet-applied swap (the
        engine applies it at its next unpaused step).  Lets a commit
        RETRY whose first reply was lost be acknowledged idempotently
        instead of failing the fleet round."""
        with self._lock:
            return self._new_params[1] if self._new_params else None

    def commit_staged(self, expected_version: Optional[int] = None) -> int:
        """Pointer-flip commit of the staged tree: the next engine step
        drains the ring and swaps by reference — no load, no transfer.
        ``expected_version`` guards the fleet's version-consistent commit
        barrier (a manager must never commit a different version than it
        staged).  Returns the interrupted-request count, like
        :meth:`update_weights`."""
        with self._lock:
            if self._staged_params is None:
                raise RuntimeError("no staged weights to commit")
            if (
                expected_version is not None
                and self._staged_version != expected_version
            ):
                raise RuntimeError(
                    f"staged weights are v{self._staged_version}, commit "
                    f"asked for v{expected_version}"
                )
            self._new_params = (
                self._staged_params, self._staged_version, True
            )
            self._staged_params = None
            self._staged_version = None
            self._ledger_sync_staged_locked()
            return self.n_inflight

    def discard_staged(self):
        """Drop an uncommitted staged tree (an aborted fleet round)."""
        with self._lock:
            self._staged_params = None
            self._staged_version = None
            self._ledger_sync_staged_locked()

    def swap_stats(self) -> Dict[str, float]:
        """Cumulative weight-swap counters (worker scrape)."""
        return {
            "stage_s": self.swap_stage_s,
            "pause_s": self.swap_pause_s,
            "swaps_total": self.swaps_total,
            "swaps_staged_total": self.swaps_staged_total,
        }

    def pause(self):
        self._paused.set()

    def resume(self):
        self._paused.clear()

    def close(self) -> Dict[str, int]:
        """Tear down this engine's ledger attributions and return the
        LEAK AUDIT: the host/staging tags that were still non-zero —
        ``staged_weights`` (an undiscarded swap tree), ``prefix_spill_host``
        (an unflushed spill tier), ``stream_buffers`` (undrained gateway
        streams), ``handoff_staging`` (unexported segments).  A quiesced
        engine returns ``{}``.  The by-design resident tags (weights,
        kv_pool, kv_scales) release silently — holding them WAS the
        engine's job.  After close the process ledger is back to its
        pre-construction baseline.  Idempotent."""
        # refresh the accounting-derived tags so the audit reads actuals,
        # not a stale per-step snapshot
        self._ledger_sync_host_buffers()
        with self._lock:
            self._ledger_sync_staged_locked()
        leaked: Dict[str, int] = {}
        for h in (
            self._led_staged, self._led_spill,
            self._led_streams, self._led_handoff,
        ):
            if h.bytes:
                leaked[h.subsystem] = leaked.get(h.subsystem, 0) + h.bytes
        if leaked:
            logger.warning("engine close leak audit: %s", leaked)
        for h in (
            self._led_weights, self._led_staged,
            self._led_kv_pool, self._led_kv_scales,
            self._led_spill, self._led_streams, self._led_handoff,
        ):
            h.release()
        return leaked

    @property
    def n_inflight(self) -> int:
        """In-flight rows: decoding or chunk-filling (parked rows are
        idle KV residents)."""
        return sum(r is not None and not r.parked for r in self.rows)

    @property
    def n_decoding(self) -> int:
        """Rows with a pending token to decode (excludes filling rows)."""
        return sum(
            r is not None and not r.parked and not r.filling
            for r in self.rows
        )

    @property
    def n_parked(self) -> int:
        return sum(r is not None and r.parked for r in self.rows)

    @property
    def n_pending(self) -> int:
        return len(self._pending)

    @property
    def inflight_chunks(self) -> int:
        """Decode chunks dispatched but not yet harvested (ring depth in
        use; bounded by ``pipeline_depth``)."""
        return len(self._ring)

    @property
    def has_work(self) -> bool:
        # host-side bookkeeping only — no device fetch; parked rows are
        # idle and do not keep the loop hot
        return (
            self.n_pending > 0
            or self.n_inflight > 0
            or bool(self._ring)
            or (self.paged and bool(self._filling or self._preempted))
        )

    # -- engine loop (owner thread) ----------------------------------------

    def _apply_pending_weights(self):
        with self._lock:
            if self._new_params is None:
                return
            peek_version = self._new_params[1]
        # the apply window as a flight-recorder span: staged syncs show
        # up in Perfetto NEXT TO the decode chunks they interrupt (the
        # counters alone can't show the overlap).  Swap roots are
        # synthetic ("swap-v{n}") and force-sampled — a weight swap is
        # fleet-wide, never a per-rollout event the hash slice covers.
        with self._phases.phase("areal.engine.swap") as span:
            self._apply_weights(peek_version, span)

    def _apply_weights(self, peek_version, span):
        swap_root = f"swap-v{peek_version}" if peek_version is not None \
            else f"swap-v{self.version + 1}"
        self.tracer.force(swap_root)
        self.tracer.span_begin(
            swap_root, "swap.commit", root=swap_root, version=peek_version,
        )
        tik = time.perf_counter()
        # the host row state must be exact before re-prefilling in-flight
        # rows: quiesce the WHOLE pipeline ring first (every dispatched
        # chunk was computed under the old weights and must be folded in
        # before the swap — none may be emitted after it as if new)
        self._drain_ring()
        with self._lock:
            pending = self._new_params
            self._new_params = None
        if pending is None:
            self.tracer.span_end(
                swap_root, "swap.commit", root=swap_root, aborted=True,
            )
            return
        new_params, target_version, pre_sharded = pending
        self.swap_applying = True
        if not pre_sharded:
            # legacy full path: the transfer happens HERE, on the paused
            # critical path.  A staged tree already sits sharded on the
            # devices (stage_weights block_until_ready'd it), so the swap
            # below is a pure pointer flip.
            if self._param_shardings is not None:
                new_params = jax.device_put(new_params, self._param_shardings)
            elif self.device is not None:
                new_params = jax.device_put(new_params, self.device)
        self.params = new_params
        self._led_weights.set(tree_nbytes(new_params))
        self.version = (
            target_version if target_version is not None else self.version + 1
        )
        with self._lock:
            # an uncommitted staged tree at or below the version we just
            # applied is dead weight (a stage-fallback round's leftover):
            # free its HBM now instead of at the next round's stage
            if (
                self._staged_version is not None
                and self._staged_version <= self.version
            ):
                logger.info(
                    "dropping stale staged weights v%d (applied v%d)",
                    self._staged_version, self.version,
                )
                self._staged_params = None
                self._staged_version = None
            self._ledger_sync_staged_locked()
        # parked rows hold KV computed under the OLD weights; resuming over
        # it would mix weight versions in attention.  Evict them — their
        # continuation re-prefills under the new weights, which is exactly
        # the reference's refresh-after-update semantics.
        n_evicted = 0
        for row_id, row in enumerate(self.rows):
            if row is not None and row.parked:
                self._release_row(row_id)
                n_evicted += 1
        if n_evicted:
            logger.info("weight update evicted %d parked rows", n_evicted)
        # recompute in-flight KV under the new weights (pause -> reload ->
        # resume; reference patch interrupts and re-prefills continuations).
        # The pending cur_token (last generated) must stay OUT of the cache —
        # the next decode_step writes its KV; re-prefill the rest, in ONE
        # batched call for all in-flight rows.
        if self.paged:
            # the radix cache holds KV computed under the OLD weights:
            # reusing any of it after the swap would silently mix weight
            # versions in attention.  Flush drops every cached reference
            # and version-tags the cache so a racing insert of pre-swap
            # KV is rejected.
            if self._prefix_cache is not None:
                self._prefix_cache.flush(new_version=self.version)
            # (the kept fills likewise: state, pages and logits computed
            # under the old weights must not meet a row of the new version)
            while self._kept.evict("swap"):
                pass
            # streamed-handoff state is version-bound on BOTH sides:
            # export streams restart with their fills below (segments
            # re-emit from block 0 under the new version; the abort
            # tells the peer to drop the dead half-stream promptly),
            # and partially-IMPORTED streams hold KV computed under the
            # old weights — released fail-closed, the continuation
            # re-prefills (same rule as the monolithic version reject)
            for qid in list(self._handoff_streams):
                self._abort_handoff_stream(qid, reason="weight_swap")
            for qid in list(self._handoff_pending):
                self._release_pending_handoff(qid, reason="version")
            # in-flight fleet prefix pulls hold (or are about to hold)
            # old-version KV: fail them closed too — the requeued
            # admission re-prefills under the new weights
            for qid, rec in list(self._prefix_pulls.items()):
                if rec["state"] in ("requested", "pulling"):
                    self._reject_prefix_pull(qid, "version")
            # chunk-filling rows hold KV computed under the OLD weights:
            # restart their fills from scratch (their rows/blocks stay;
            # a cache-matched fill_pos also resets — its prefix blocks
            # are rewritten under the new weights like any others)
            for f in self._filling:
                f.fill_pos = 0
            entries = [
                (row_id, (row.prompt + row.generated)[:-1])
                for row_id, row in enumerate(self.rows)
                if row is not None and not row.filling
            ]
            for rid, _ in entries:
                self.tracer.event(
                    self.rows[rid].req.qid, "engine.recompute",
                    version=self.version,
                )
            if self._win is not None:
                # a window layer no longer holds what lies behind a row's
                # window, and computing its last window again needs all
                # of it: the rows go back through the fill queue (oldest
                # first), which holds a whole prompt's window pages only
                # while it fills; a restarted fill gets back the pages it
                # had released behind itself
                for rid, _ in sorted(
                    entries, key=lambda e: self.rows[e[0]].epoch
                ):
                    self._requeue_row(rid, self.rows[rid])
                for f in self._filling:
                    gone = [i for i, b in enumerate(f.wblocks) if b == GONE]
                    again = self._alloc_reclaiming(self._win, len(gone))
                    if again is None:
                        raise RuntimeError(
                            "window pool too small to restart a fill of "
                            f"{len(f.tokens)} tokens after a weight swap"
                        )
                    for i, b in zip(gone, again):
                        f.wblocks[i] = b
                    if self._win.rows[f.state_slot] is f.wblocks:
                        self._win.sync_row(f.state_slot)
            elif entries:
                # existing blocks are overwritten in place; the pending
                # cur_tokens are untouched (no resampling to discard)
                self._refill_rows_paged(entries)
        else:
            entries = [
                (row_id, (row.prompt + row.generated)[:-1])
                for row_id, row in enumerate(self.rows)
                if row is not None
            ]
            for rid, _ in entries:
                self.tracer.event(
                    self.rows[rid].req.qid, "engine.recompute",
                    version=self.version,
                )
            if entries:
                self._prefill_rows(entries)
                # keep the already-sampled pending tokens, discard the
                # resamples
                ids = np.array([rid for rid, _ in entries], np.int32)
                curs = np.array(
                    [self.rows[rid].cur_token for rid, _ in entries],
                    np.int32,
                )
                self.cur_tokens = self.cur_tokens.at[ids].set(curs)
        dt = time.perf_counter() - tik
        self.swap_pause_s += dt
        self.swaps_total += 1
        self.swap_recomputed_rows_total += len(entries)
        self.swap_applying = False
        span.set_metadata(version=self.version, rows_recomputed=len(entries))
        if pre_sharded:
            self.swaps_staged_total += 1
        if self._slo_enabled:
            # the pause quiesced every in-flight request: attribute the
            # whole window to each one's stall time (they all waited it
            # out — drain, flip/reload, recompute).  Rows mid
            # preemption-readmit (t_preempt still set) are skipped: their
            # out-of-service window, added at re-activation, already
            # spans this pause — adding dt here would double-count it.
            for row in self.rows:
                if row is not None and not row.parked and not row.t_preempt:
                    row.slo_stall_s += dt
        self.tracer.span_end(
            swap_root, "swap.commit", root=swap_root,
            version=self.version, pre_sharded=pre_sharded,
            interrupted=self.n_inflight,
        )
        logger.info(
            "weights updated to v%d (%d in-flight recomputed, %s, %.3fs "
            "interrupted)",
            self.version,
            self.n_inflight,
            "pointer-flip" if pre_sharded else "full reload",
            dt,
        )

    def _prefill_rows(
        self,
        entries: List[Tuple[int, List[int]]],
        seeds: Optional[List[int]] = None,
    ):
        """Batched prefill of ``(row_id, token_seq)`` entries; returns the
        per-entry sampled next token and its logprob (np arrays).

        Entries sharing an identical token sequence (a sampling group's n
        copies of one prompt) are deduplicated: the model runs each unique
        sequence once and the KV is scattered to every target row.

        ``seeds`` are the per-entry request sampler keys; None derives
        them from the resident rows (the weight-swap re-prefill, whose
        resamples are discarded anyway)."""
        n = len(entries)
        if seeds is None:
            seeds = [
                _qid_seed(self.rows[rid].req.qid) for rid, _ in entries
            ]
        uniq: Dict[Tuple[int, ...], int] = {}
        src_idx = []
        for _, seq in entries:
            key = tuple(seq)
            if key not in uniq:
                uniq[key] = len(uniq)
            src_idx.append(uniq[key])
        m = len(uniq)
        m_pad = 1 << (m - 1).bit_length()  # bucket: fewer recompiles
        n_pad = 1 << (n - 1).bit_length()
        T = bucket_len(max(max(len(seq) for _, seq in entries), 1))
        toks = np.zeros((m_pad, T), np.int32)
        lens = np.ones((m_pad,), np.int32)
        for key, i in uniq.items():
            toks[i, : len(key)] = key
            lens[i] = len(key)
        rows = np.full((n_pad,), self.max_batch, np.int32)  # OOB -> dropped
        src = np.zeros((n_pad,), np.int32)
        seed_arr = np.zeros((n_pad,), np.int32)
        for i, (rid, _) in enumerate(entries):
            rows[i] = rid
            src[i] = src_idx[i]
            seed_arr[i] = seeds[i]
        self.cache, tok, logp = _admit_rows(
            self.params,
            self.cfg,
            self.cache,
            jnp.asarray(toks),
            jnp.asarray(lens),
            jnp.asarray(rows),
            jnp.asarray(src),
            jnp.asarray(seed_arr),
            self._sample_base_rng,
            self.sampling,
            mesh=self.mesh,
        )
        self.prefill_calls += 1
        self.prefill_tokens_total += int(lens[:m].sum())
        with self._phases.phase(
            "areal.engine.fill.first_token_wait", rows=n
        ):
            return np.asarray(tok)[:n], np.asarray(logp)[:n]

    def _try_resume(self, req: model_api.APIGenerateInput) -> bool:
        """Resume a parked row whose resident KV matches this continuation:
        same qid AND identical token prefix (token-exact, so a client that
        edited the context falls through to a fresh prefill)."""
        prompt = list(req.input_ids or req.prompt_ids)
        for row_id, row in enumerate(self.rows):
            if (
                row is None
                or not row.parked
                or row.req.qid != req.qid
                or row.prompt + row.generated != prompt
            ):
                continue
            if len(prompt) + 1 >= self.kv_cache_len:
                # no room to continue: report empty so the client stops
                self._release_row(row_id)
                done = _Row(
                    req=req, prompt=prompt, generated=[], logprobs=[],
                    version_start=self.version, no_eos=True,
                )
                self._finish(-1, done, started=False)
                return True
            max_new = req.gconfig.max_new_tokens
            if len(prompt) + max_new > self.kv_cache_len:
                max_new = max(1, self.kv_cache_len - len(prompt))
            # cache already holds KV for prompt[:-1]; prompt[-1] is the
            # pending cur_token, so decoding picks up exactly where the
            # previous chunk stopped — zero prefill FLOPs.
            row.req = req
            row.prompt = prompt
            row.generated = []
            row.logprobs = []
            row.version_start = self.version
            row.no_eos = False
            row.parked = False
            row.budget_left = max_new
            self._slo_admitted(row)
            self._epoch_counter += 1
            row.epoch = self._epoch_counter
            rid = np.array([row_id], np.int32)
            self.cur_tokens = self.cur_tokens.at[rid].set(row.cur_token)
            self.active = self.active.at[rid].set(True)
            self.budgets = self.budgets.at[rid].set(max_new)
            self.row_seeds = self.row_seeds.at[rid].set(
                _qid_seed(req.qid)
            )
            self.resumed_total += 1
            self.tracer.event(req.qid, "engine.resume", row=row_id)
            return True
        return False

    def _evict_parked(self, keep_qids=()) -> Optional[int]:
        """Free the longest-parked row (its continuation will re-prefill).
        Oldest-by-(park_step, row_id): fully deterministic under SPMD."""
        oldest, oldest_id = None, None
        for row_id, row in enumerate(self.rows):
            if row is not None and row.parked and row.req.qid not in keep_qids:
                if oldest is None or row.park_step < oldest:
                    oldest, oldest_id = row.park_step, row_id
        if oldest_id is not None:
            self._release_row(oldest_id)
        return oldest_id

    # -- paged-mode engine internals ---------------------------------------

    def _run_fill_batch(self, fills: List[_Fill], budget: int):
        """Run ONE batched prefill chunk over ``fills`` (FIFO, total
        tokens <= budget).  Advances fill_pos; returns
        (completed_fills, their_logits_indices, logits_device)."""
        batch: List[Tuple[_Fill, int]] = []
        left = budget
        for f in fills:
            rem = len(f.tokens) - f.fill_pos
            if rem <= 0:
                continue
            take = min(rem, left)
            if take <= 0:
                break
            # the batch is a dense [F_pad, C] array and the model runs all
            # of it: one more row may double it.  Four budgets of padded
            # positions at most (what four rows of a whole chunk each
            # come to); the fill goes into the next batch
            # ... and every layer's keys and values of all of it leave
            # the program's layer loop stacked, a temporary: a GiB at most
            # (no stack but a looped one comes near: 192 cache layers of
            # 16 heads are 1.57 MB a position, 0.8 GB for 512)
            width = bucket_len(max([take] + [t for _, t in batch]))
            slots = (1 << len(batch).bit_length()) * width
            if batch and (
                slots > 4 * budget
                or slots * self.loop_counts["kv_bytes_per_token"]
                > FILL_KV_TEMP_BYTES
            ):
                break
            batch.append((f, take))
            left -= take
            if left <= 0:
                break
        if not batch:
            return [], [], None
        C = bucket_len(max(take for _, take in batch))
        F_pad = 1 << (len(batch) - 1).bit_length()
        self.fill_shapes_run[F_pad, C] += 1
        self.fill_slots_total += F_pad * C
        # the counts of THIS event (the running totals a fill moves that
        # no reader of the trace reads are engine attributes, logged once
        # when the server exits)
        counts = dict(
            prompts=len(batch), f_pad=F_pad, c=C,
            tokens=sum(take for _, take in batch),
        )
        if self._stateful:
            # running totals the drivers' window records read: sibling
            # copies of a fill's end state, and late siblings that
            # prefilled their prompt again
            counts.update(
                state_copies=self.state_copies_total,
                state_reprefills=self.state_reprefills_total,
            )
        if self.cfg.loop_steps > 1:
            # a looped stack: what it holds a token (set once), and the
            # running total of the steps admission waited for PAGES
            counts.update(
                self.loop_counts,
                admission_page_waits=self.admission_page_waits_total,
            )
        grouped = False
        if self._by_kind and self.cfg.n_experts:
            # the host knows from the batch's shape which product its
            # experts take
            grouped = bool(moe.group_rows(self.cfg, F_pad * C))
            self.moe_fill_tokens_total += counts["tokens"]
            if grouped:
                self.moe_fill_tokens_grouped_total += counts["tokens"]
            self._add_fill_rounds_that_arrived()
        if self._by_kind:
            self.fill_tail_positions_saved_total += (
                self.fill_tail_layers * (F_pad * C - F_pad)
            )
            counts.update(tail_layers=self.fill_tail_layers)
        with self._phases.phase("areal.engine.fill.dispatch", **counts):
            toks = np.zeros((F_pad, C), np.int32)
            starts = np.zeros((F_pad,), np.int32)
            cls = np.zeros((F_pad,), np.int32)
            tables = np.zeros((F_pad, self.blocks_per_row), np.int32)
            wtables = np.zeros_like(tables)
            slots = np.zeros((F_pad,), np.int32)
            for i, (f, take) in enumerate(batch):
                toks[i, :take] = f.tokens[f.fill_pos : f.fill_pos + take]
                starts[i] = f.fill_pos
                cls[i] = take
                slots[i] = f.state_slot
                for (pool, held), t in zip(
                    self._pages_of(f), (tables, wtables)
                ):
                    pool.table_of(held, t[i])
            if self._by_kind:
                win = self._window_args(wtables)
                (logits, self.k_pool, self.v_pool, self.ssm_state,
                 self.conv_state, _, routed, rounds,
                 *win_out) = hybrid.hybrid_fill_chunk(
                    self.params, self.k_pool, self.v_pool, self.ssm_state,
                    self.conv_state, self.cfg, jnp.asarray(toks),
                    jnp.asarray(starts), jnp.asarray(cls),
                    jnp.asarray(tables), jnp.asarray(slots),
                    use_kernel=self._use_paged_kernel, **win,
                    **(
                        {"keep_chosen": self._keep_chosen}
                        if self._keep_chosen else {}
                    ),
                )
                if self._keep_chosen:
                    # (like the routing: read when the fill hands it out)
                    chosen_last = win_out.pop()
                    jax_compat.start_host_copies((chosen_last,))
                    for i, (f, take) in enumerate(batch):
                        if f.targets and f.fill_pos + take == len(f.tokens):
                            f.chosen_last = (chosen_last, i, f.fill_pos, take)
                if win_out:
                    self.win_k_pool, self.win_v_pool = win_out[0]
                out = (logits, self.k_pool, self.v_pool)
                if grouped:
                    jax_compat.start_host_copies((rounds,))
                    self._fill_rounds_on_the_way.append(rounds)
                if self._keep_routed:
                    # on its way to the host while the rows decode: the
                    # row that finishes reads it without a round trip
                    jax_compat.start_host_copies((routed,))
                    for i, (f, take) in enumerate(batch):
                        if f.targets:
                            f.routed.append((routed, i, take))
            else:
                out = paged.paged_fill_chunk(
                    self.params,
                    self.k_pool,
                    self.v_pool,
                    self.cfg,
                    jnp.asarray(toks),
                    jnp.asarray(starts),
                    jnp.asarray(cls),
                    jnp.asarray(tables),
                    use_kernel=self._use_paged_kernel,
                    mesh=self.mesh,
                    kv_axis=getattr(self, "_kv_axis", None),
                    k_scale=self.k_scale,
                    v_scale=self.v_scale,
                )
        if self._kv_quant:
            (logits, self.k_pool, self.v_pool, self.k_scale,
             self.v_scale) = out
        else:
            logits, self.k_pool, self.v_pool = out
        self.prefill_calls += 1
        self.prefill_tokens_total += int(cls.sum())
        completed, idxs = [], []
        for i, (f, take) in enumerate(batch):
            f.fill_pos += take
            if self._win is not None:
                # (the chunk just dispatched reads them first: the device
                # runs the programs in the order they were dispatched)
                self._win.release_behind(f.wblocks, f.fill_pos, f.state_slot)
            if f.targets:  # weight-swap refills (no targets) trace as
                # engine.recompute, not per-chunk fill events
                self.tracer.event(
                    f.targets[0].req.qid, "engine.fill_chunk",
                    tokens=take, fill_pos=f.fill_pos,
                )
                if self._handoff_streaming:
                    # streamed handoff: the chunk just finalized some
                    # full blocks — export them NOW, while the rest of
                    # the prompt still fills (the overlap that shrinks
                    # the decode-side resume gap to O(one chunk))
                    self._emit_handoff_segments(f)
            if f.fill_pos == len(f.tokens):
                completed.append(f)
                idxs.append(i)
        return completed, idxs, logits

    def _add_fill_rounds_that_arrived(self):
        """Add to ``moe_fill_extra_rounds_total`` the counts of the fills
        whose programs have run (their copies to the host were started at
        dispatch): never a wait."""
        on_the_way = self._fill_rounds_on_the_way
        while on_the_way and on_the_way[0].is_ready():
            self.moe_fill_extra_rounds_total += int(on_the_way.popleft())

    def _refill_rows_paged(self, entries: List[Tuple[int, List[int]]]):
        """Synchronously recompute rows' cached KV into their EXISTING
        blocks (weight update re-prefill; no sampling — the pending
        cur_token is preserved).  Shared group-prompt blocks are written
        once per sharer with identical values (same tokens, same new
        weights), which is scatter-deterministic."""
        fills = [
            _Fill(
                key=(), tokens=seq, blocks=self._pages.rows[rid], targets=[],
                state_slot=rid,
            )
            for rid, seq in entries
            if len(seq) > 0
        ]
        pending = [f for f in fills if f.fill_pos < len(f.tokens)]
        while pending:
            self._run_fill_batch(pending, self.prefill_chunk_tokens)
            pending = [f for f in pending if f.fill_pos < len(f.tokens)]

    def _advance_fill(self):
        """Advance in-flight chunked prefills.

        With rows decoding, ONE ``prefill_chunk_tokens`` batch per engine
        step bounds the decode stall at a single chunk (the chunked-
        prefill interleave).  With NOTHING decoding there is no stall to
        bound, so the whole admission wave's chunks are dispatched
        back-to-back in this one call — each ``paged_fill_chunk`` is an
        async jit dispatch chaining on the donated pool, so a 16k prompt
        issues its 16 chunks with no host round-trip between them
        instead of paying one engine-step (admit/harvest bookkeeping +
        fetch) per chunk.

        First the late siblings that this step's admission found a kept
        fill for: one distribution for all of them, by the code that
        serves siblings queued on a fill in time, from the snapshot slots
        and the kept logits rows where those read the fill's own."""
        if self._joining:
            joining, self._joining = self._joining, []
            self._distribute_fills(
                joining, [f.snap for f in joining], self._kept_logits
            )
        while self._filling:
            completed, idxs, logits = self._run_fill_batch(
                self._filling, self.prefill_chunk_tokens
            )
            if completed:
                for f in completed:
                    self._filling.remove(f)
                self._distribute_fills(completed, idxs, logits)
            elif logits is None:
                return  # nothing advanced: no fill has tokens left
            if self.n_decoding > 0:
                return

    def _distribute_fills(self, fills: List[_Fill], idxs, logits):
        """Hand a completed fill's blocks to its targets: target 0 owns
        the canonical blocks; later targets share the FULL blocks
        (refcount) and receive a COPY of the partial tail block (their
        generated tokens diverge inside it).  Fresh targets sample their
        first token from the shared final logits; preempted targets
        restore their saved decode state with zero sampling.

        The first tokens stay on the device: ``_activate_rows`` hands them
        to their rows there, and the host learns them at the harvest of
        the next chunk dispatched (``_fold_first_tokens``), so nothing
        here waits for the fill program.  Fetched at once only where the
        token is needed at once (``_first_tokens_at_once``)."""
        with self._phases.phase("areal.engine.fill.activate"):
            targets, resumed, sampled = self._share_fill_blocks(
                fills, idxs, logits
            )
            activated = self._activate_filled_rows(targets, resumed, sampled)
            self._first_tokens.append(
                _FirstTokens(
                    targets=activated, arrays=sampled,
                    fills=[(f, f.targets) for f in fills],
                )
            )
            for f in fills:
                f.targets = []  # (a kept fill's next are its late siblings)
        if self._first_tokens_at_once(targets):
            self.first_tokens_blocking_total += len(targets)
            self._settle_first_tokens()
        else:
            self.first_tokens_deferred_total += len(targets)

    def _first_tokens_at_once(self, targets) -> bool:
        """Whether a distribution's first tokens are fetched before
        anything else is dispatched: a request that is handed off parks
        (and is exported) on its first token."""
        return any(
            (tgt.req.metadata or {}).get("handoff_to") for _, tgt, _ in targets
        )

    def _settle_first_tokens(self):
        """Fold every distribution's first tokens that the host has not
        seen, oldest first (the ring's chunks', then those no chunk has
        taken up), with a BLOCKING fetch: before anything that reads or
        rewrites a decoding row's tokens (wherever the ring is drained),
        and at the end of a step that dispatched nothing to carry them."""
        records = [r for ch in self._ring for r in ch.first_tokens]
        records += self._first_tokens
        for ch in self._ring:
            ch.first_tokens = []
        self._first_tokens = []
        for record in records:
            self._fold_first_tokens(record)

    def _fold_first_tokens(self, record: _FirstTokens):
        """A distribution's first tokens have reached the host (or are
        waited for here, in ``areal.engine.fill.first_token_wait``): each
        goes to its row's list, its stream and the stop rule.  A row that
        its first token ended (a stop token, a budget of one) stopped on
        the device when it was activated; the host ends it here."""
        n = len(record.targets)
        toks = logps = np.zeros((0,))
        if n:
            with self._phases.phase(
                "areal.engine.fill.first_token_wait", rows=n
            ):
                toks = np.asarray(record.arrays[0])[:n]
                logps = np.asarray(record.arrays[1])[:n]
            self.tokens_emitted_total += n
        with self._phases.phase("areal.engine.fill.activate"):
            for f, targets in record.fills:
                self._hand_out_routing(f, targets)
            t_first = time.monotonic()  # fill's first tokens on host
            for (tgt, row), tok_i, logp in zip(
                record.targets, toks.tolist(), logps.tolist()
            ):
                assert self.rows[tgt.row_id] is row and row.first_on_its_way
                row.first_on_its_way = False
                row.generated = [int(tok_i)]
                row.logprobs = [float(logp)]
                self._slo_first_token(row, now=t_first)
                self._stream_push(row, [int(tok_i)])
                if tok_i in self.stop_tokens or tgt.max_new <= 1:
                    row.no_eos = tok_i not in self.stop_tokens
                    self._finish(tgt.row_id, row, started=False)
                    self._release_row(tgt.row_id)
                    if self._handoff_streaming:
                        # the request ends HERE (EOS / 1-token budget):
                        # any segments already streamed have no final —
                        # tell the decode peer to release them
                        self._abort_handoff_stream(
                            tgt.req.qid, reason="eos"
                        )
                    continue
                row.cur_token = int(tok_i)
                if (row.req.metadata or {}).get("handoff_to"):
                    # prefill-role handoff: park RIGHT AFTER the fill +
                    # first token instead of decoding — the worker
                    # exports the parked row's blocks to the decode
                    # server and the continuation resumes THERE (the
                    # activation stamped the device-side row length; a
                    # normal park inherits it from its decode chunks)
                    row.no_eos = True
                    self._finish(tgt.row_id, row, park=True)
                    if self._handoff_streaming:
                        # streamed mode: the final segment (tail block +
                        # first token + host state) replaces the
                        # monolithic export — emitted now, row released
                        self._emit_final_handoff_segment(tgt.row_id, row)

    def _hand_out_routing(self, f: _Fill, targets: List[_FillTarget]):
        """``keep_routed_experts``: a completed fill's routing to the
        ``targets`` it had (a resumed row's was computed again, with
        everything else), as ``[tokens, L, K]`` pieces on the host.  Its
        program has run by now (its first tokens have arrived) and the
        copy started at dispatch, so nothing waits here and the device
        arrays go."""
        if f.routed:
            pieces = [
                np.asarray(r)[:, i, :take].swapaxes(0, 1).astype(np.int16)
                for r, i, take in f.routed
            ]
            f.routed = []
            n_reused = len(f.tokens) - sum(len(p) for p in pieces)
            if n_reused:
                # pages reused from the cache hold KV that an earlier fill
                # of this prompt computed, under the routing it handed out
                # (by now: the distributions' records are folded oldest
                # first, and that fill's may have been on its way when
                # this one was admitted)
                reused = self._routed_prompts.get(f.key, ())[:n_reused]
                if len(reused) != n_reused:
                    return  # pages of a prompt whose routing nobody kept
                pieces.insert(0, reused)
            f.routing = np.concatenate(pieces)
            # (a recurrent state rules page reuse out: the routing stays
            # with the fill, for as long as that is kept)
            if not self._stateful:
                self._routed_prompts.pop(f.key, None)
                while len(self._routed_prompts) >= self._keep_routed:
                    del self._routed_prompts[next(iter(self._routed_prompts))]
                self._routed_prompts[f.key] = f.routing
        routing = f.routing
        if routing is None:
            return
        chosen = self._fill_chosen_sets(f)
        for tgt in targets:
            row = tgt.resume or self.rows[tgt.row_id]
            if row is not None:
                row.routed = [routing]
                row.chosen_fill = chosen

    def _fill_chosen_sets(self, f: _Fill):
        """``keep_chosen_sets``: ``(query positions [n], sets [n, L_indexed,
        K])`` of the prompt's last positions, from the masks the fill's
        last chunk kept (``hybrid_fill_chunk``'s ``keep_chosen``: cached
        positions, then the chunk's own tokens), in :meth:`chosen_sets`'
        form.  None where nothing was kept."""
        if f.chosen_last is None:
            return None
        masks, i, start, take = f.chosen_last
        f.chosen_last = None
        masks = np.asarray(masks)[:, i]  # [L_indexed, keep, cached + C]
        n = min(masks.shape[1], take)
        table = self.blocks_per_row * self.page_size
        sets = np.full((n, masks.shape[0], self.cfg.index_topk), -1, np.int32)
        for layer, rows in enumerate(masks[:, masks.shape[1] - n :]):
            for q, row in enumerate(rows):
                cols = np.flatnonzero(row)
                sets[q, layer, : len(cols)] = sparse_attention.row_positions(
                    cols, table, start
                )
        return np.arange(start + take - n, start + take, dtype=np.int32), sets

    def _share_fill_blocks(self, fills: List[_Fill], idxs, logits):
        """Pages, states and the sampler's draws of ``_distribute_fills``.
        Returns (fresh targets sampled for, resumed rows to activate as
        they are, the sampled tokens and log-probabilities on the device,
        their copy to the host started).

        ``late``: the fills are KEPT ones and their targets the late
        siblings that join them.  Every target is then a sibling (none
        owns the kept pages: full pages by reference, the tail by copy,
        which holds what the fill's first target has appended since, past
        the prompt, where the joiner writes before anything reads), the
        end states come from the snapshot slots and ``logits`` is the
        kept rows'."""
        late = fills[0].snap >= 0  # (a fill is kept after it is shared)
        self.distributions_run[
            len(fills), sum(len(f.targets) for f in fills)
        ] += 1
        copies = [([], []) for _ in self._pools]  # (from, to) a pool
        state_src, state_dst = [], []
        sample_targets: List[Tuple[_Fill, _FillTarget, int]] = []
        activation: List[Tuple[int, int, int, int]] = []  # rid,cur,budget,len
        for f, li in zip(fills, idxs):
            plen = len(f.tokens)
            n_full = plen // self.page_size
            has_tail = plen % self.page_size != 0
            # the completed prompt's KV enters the radix cache NOW (a
            # retried or sibling request arriving next step already hits)
            self._cache_insert(f.tokens, f.blocks, f.wblocks)
            for t_i, tgt in enumerate(f.targets):
                if late:
                    state_src.append(f.snap)
                    state_dst.append(tgt.row_id)
                elif self._stateful and tgt.row_id != f.state_slot:
                    # the prompt's end state, which the fill left in its
                    # own slot, for a sibling that shares the fill
                    state_src.append(f.state_slot)
                    state_dst.append(tgt.row_id)
                for (pool, held), (src, dst) in zip(self._pages_of(f), copies):
                    if t_i == 0 and not late:
                        pool.set_row(tgt.row_id, list(held))
                        continue
                    # the prompt's full pages by reference (in a window
                    # pool those of its last window: what lies before is
                    # GONE), the tail by copy
                    own = list(held[:n_full])
                    pool.incref(own)
                    if has_tail:
                        tail = self._alloc_reclaiming(pool, 1, preempt_but=-1)
                        if tail is None:
                            raise RuntimeError(
                                ("window pool" if pool.window else "pool")
                                + " exhausted distributing a group fill"
                            )
                        src.append(held[n_full])
                        dst.append(tail[0])
                        own += tail
                    pool.set_row(tgt.row_id, own)
                if tgt.resume is not None:
                    row = tgt.resume
                    if self._slo_enabled and row.t_preempt:
                        # back in service: the preempted window was stall
                        row.slo_stall_s += (
                            time.monotonic() - row.t_preempt
                        )
                        row.t_preempt = 0.0
                    self._epoch_counter += 1
                    row.epoch = self._epoch_counter
                    row.filling = False
                    self.rows[tgt.row_id] = row
                    activation.append(
                        (tgt.row_id, row.cur_token, row.budget_left, plen,
                         row)
                    )
                else:
                    sample_targets.append((f, tgt, li))
        for pool, (src, dst) in zip(self._pools, copies):
            if src:
                self._copy_pages(pool, src, dst)
        if late:
            self.ssm_state, self.conv_state = hybrid.copy_state_slots_between(
                self.snap_ssm, self.snap_conv, self.ssm_state,
                self.conv_state, *self._slot_pairs(state_src, state_dst),
            )
        elif state_src:
            n_pad = 1 << (len(state_src) - 1).bit_length()
            src = np.zeros((n_pad,), np.int32)
            dst = np.full((n_pad,), self.max_batch, np.int32)  # pad -> skip
            src[: len(state_src)] = state_src
            dst[: len(state_dst)] = state_dst
            self.ssm_state, self.conv_state = hybrid.copy_state_slots(
                self.ssm_state, self.conv_state, jnp.asarray(src),
                jnp.asarray(dst),
            )
            self.state_copies_total += len(state_src)
        if self._stateful and not late:
            self._keep_fills(fills, idxs, logits)
        sampled = None
        if sample_targets:
            # padded to a power of two (late siblings: to the one count
            # that was built at the start)
            n = len(sample_targets)
            n_pad = LATE_JOINS_A_STEP if late else 1 << (n - 1).bit_length()
            src_idx = np.zeros((n_pad,), np.int32)
            tgt_seeds = np.zeros((n_pad,), np.int32)
            tgt_pos = np.zeros((n_pad,), np.int32)
            for i, (f_i, tgt_i, li) in enumerate(sample_targets):
                src_idx[i] = li
                tgt_seeds[i] = _qid_seed(tgt_i.req.qid)
                tgt_pos[i] = len(f_i.tokens)
            sampled = _sample_rows(
                logits,
                jnp.asarray(src_idx),
                jnp.asarray(tgt_seeds),
                jnp.asarray(tgt_pos),
                self._sample_base_rng,
                self.sampling,
                mesh=self.mesh,
            )
            # on their way to the host from now: whoever folds them finds
            # them there once the program has run
            jax_compat.start_host_copies(sampled)
        return sample_targets, activation, sampled

    def _activate_filled_rows(self, sample_targets, resumed, sampled):
        """The rows of a distribution start decoding, on the device
        (``_activate_rows``): the fresh targets in ONE program with the
        tokens sampled for them, which the host has not seen
        (``first_on_its_way`` until ``_fold_first_tokens``); resumed rows,
        where a preemption left any, in a call of their own with what they
        were preempted with (so ``_sample_rows`` and ``_activate_rows``
        meet the counts they were built at, whatever resumes beside the
        fresh ones).  Returns the fresh targets with their rows."""
        targets, entries = [], []
        for f, tgt, _ in sample_targets:
            row = self.rows[tgt.row_id]
            assert row is not None and row.filling
            row.filling = False
            row.first_on_its_way = True
            row.budget_left = tgt.max_new - 1
            self._epoch_counter += 1
            row.epoch = self._epoch_counter
            entries.append(
                (tgt.row_id, 1, 0, tgt.max_new - 1, len(f.tokens),
                 _qid_seed(tgt.req.qid))
            )
            targets.append((tgt, row))
        if entries:
            self._start_rows(sampled[0], entries)
        # a resume target activated EARLIER in the distribution is the
        # youngest active row, so a LATER target's tail-block allocation
        # may have preempted it (rows[rid] is None again, its table
        # zeroed): activating its slot anyway would scatter KV into pool
        # block 0 and corrupt another row (code-review r5 #1) — apply only
        # entries whose row object still occupies its slot
        entries = [
            (rid, 0, cur, budget, plen, _qid_seed(row.req.qid))
            for rid, cur, budget, plen, row in resumed
            if self.rows[rid] is row
        ]
        if entries:
            n_pad = 1 << (len(entries) - 1).bit_length()
            self._start_rows(
                self._on_device(np.zeros((n_pad,), np.int32)), entries
            )
        return targets

    def _start_rows(self, tokens, entries):
        """``_activate_rows`` over ``entries`` (row id, fresh, cur, budget,
        length, seed), padded to ``tokens``' count: padding names a row
        past the batch and writes nothing."""
        columns = np.zeros((6, tokens.shape[0]), np.int32)
        columns[0] = self.max_batch
        columns[:, : len(entries)] = np.reshape(entries, (-1, 6)).T
        (self.cur_tokens, self.active, self.budgets, self.kv_lengths,
         self.row_seeds) = _activate_rows(
            *self._row_arrays(), tokens, jnp.asarray(columns),
            stop_tokens=self.stop_tokens,
        )

    def _admit_paged(self) -> Tuple[int, int]:
        """Returns (rows admitted, those whose fill starts behind a
        cached prefix)."""
        if self.hold_admissions:
            self._admit_stopped_by = admit_stop("held")
            return 0, 0
        admitted = prefix_hits = 0
        # (what stopped the preempted rows' queue stands unless the
        # requests' queue stops on something too)
        stopped_by = admit_stop("queue_empty")
        for row_id, row in enumerate(self.rows):
            if row is not None and row.parked and (
                self._step_seq - row.park_step > self.park_ttl_steps
            ):
                self._release_row(row_id)
        # dead-peer backstop for streamed imports: a half-received
        # stream whose sender died mid-push would pin its pre-allocated
        # blocks forever — release it fail-closed after the TTL (the
        # continuation re-prefills; zero leaked blocks)
        for qid, pend in list(self._handoff_pending.items()):
            if self._step_seq - pend["step"] > self.handoff_pending_ttl_steps:
                self._release_pending_handoff(qid, reason="expired")
        # same backstop for fleet prefix pulls: a dead owner (or a pull
        # whose requester was aborted before re-admission) must not pin
        # blocks or intent records forever
        for qid, rec in list(self._prefix_pulls.items()):
            if self._step_seq - rec["step"] > self.handoff_pending_ttl_steps:
                if rec["state"] in ("requested", "pulling"):
                    self._reject_prefix_pull(qid, "expired")
                else:  # settled but never collected by an admission
                    del self._prefix_pulls[qid]
        free = [i for i, r in enumerate(self.rows) if r is None]

        def take_row():
            if free:
                return free.pop(0)
            with self._lock:
                queued = {r.qid for r in self._pending}
            evicted = self._evict_parked(keep_qids=queued)
            return evicted

        # preempted rows first (their pool reservation was stolen mid-
        # decode; FIFO so none starves).  The re-prefill walks the radix
        # cache like any admission — a preempted row whose prefix is
        # still cached recomputes only the un-cached suffix.
        while self._preempted:
            row = self._preempted[0]
            seq = (row.prompt + row.generated)[:-1]
            rid = take_row()
            if rid is None:
                stopped_by = admit_stop("no_slot")
                break
            with self._lock:
                queued = {r.qid for r in self._pending}
            fill = self._new_fill(seq, keep_qids=queued)
            if fill is None:
                free.insert(0, rid)
                stopped_by = admit_stop("no_pages")
                break
            self._preempted.pop(0)
            fill.state_slot = rid
            self._set_fill_row(rid, fill)
            row.filling = True
            self.rows[rid] = row
            admitted += 1
            prefix_hits += fill.fill_pos > 0
            self.tracer.event(
                row.req.qid, "engine.admit", row=rid,
                prompt_len=len(seq), cached_tokens=fill.fill_pos,
                shared=False, preempt_readmit=True,
            )
            fill.targets.append(
                _FillTarget(
                    row_id=rid, req=row.req,
                    max_new=row.budget_left, resume=row,
                )
            )
            self._filling.append(fill)
        while True:
            with self._lock:
                if not self._pending:
                    break
                req = self._pending.pop(0)
            if self._try_resume(req):
                continue
            prompt = list(req.input_ids or req.prompt_ids)
            if len(prompt) + 1 >= self.kv_cache_len:
                row = _Row(
                    req=req, prompt=prompt, generated=[], logprobs=[],
                    version_start=self.version, no_eos=True,
                )
                self._finish(-1, row, started=False)
                continue
            max_new = req.gconfig.max_new_tokens
            if len(prompt) + max_new > self.kv_cache_len:
                max_new = max(1, self.kv_cache_len - len(prompt))
            key = tuple(prompt)
            fill = next(
                (f for f in self._filling if f.key == key), None
            )
            if (
                fill is None
                and self._kept.peek(key) is not None
                and sum(len(f.targets) for f in self._joining)
                >= LATE_JOINS_A_STEP
            ):
                # (a late sibling more than one distribution serves: it
                # joins its prompt's kept fill at the next step)
                with self._lock:
                    self._pending.insert(0, req)
                stopped_by = admit_stop("late_join_cap")
                break
            if fill is None and self._maybe_pull_prefix(req, prompt):
                # fleet pull in flight: requeue step-keyed until the
                # imported prefix lands in the radix cache (or the pull
                # fails closed and the next pass re-prefills plainly)
                with self._lock:
                    self._pending.insert(0, req)
                stopped_by = admit_stop("prefix_pull")
                break
            rid = take_row()
            if rid is None:
                with self._lock:
                    self._pending.insert(0, req)
                stopped_by = admit_stop("no_slot")
                break
            if fill is None:
                # a late sibling of a stateful stack's prompt: the fill
                # has ended and was kept, and is joined like one in flight
                # (handed out in this step's ``_advance_fill``)
                fill = self._kept.join(key)
                if fill is not None and fill not in self._joining:
                    self._joining.append(fill)
            if fill is None:
                # radix walk first: a cached prefix (an earlier turn of
                # this conversation, a retried request, a sibling's
                # prompt) is pinned and skipped; only the suffix enters
                # the fill queue.  Reclamation spares parked rows whose
                # own continuation is still queued behind this request
                # (evicting one trades this alloc for that row's full
                # re-prefill — the dense path's guard, same reason)
                with self._lock:
                    queued = {r.qid for r in self._pending}
                fill = self._new_fill(prompt, keep_qids=queued)
                if fill is None:
                    free.insert(0, rid)
                    with self._lock:
                        self._pending.insert(0, req)
                    stopped_by = admit_stop("no_pages")
                    break
                self._filling.append(fill)
                fill.state_slot = rid
                self._set_fill_row(rid, fill)
                prefix_hits += fill.fill_pos > 0
                # canonical blocks live in target 0's table; refcount
                # stays 1 until extra targets share them
                self.tracer.event(
                    req.qid, "engine.admit", row=rid,
                    prompt_len=len(prompt), cached_tokens=fill.fill_pos,
                    shared=False,
                )
            else:
                # group member joins the in-flight (or kept) fill: ZERO
                # extra prefill work (block-reference prompt sharing)
                self.tracer.event(
                    req.qid, "engine.admit", row=rid,
                    prompt_len=len(prompt), cached_tokens=fill.fill_pos,
                    shared=True,
                )
            fill.targets.append(
                _FillTarget(row_id=rid, req=req, max_new=max_new)
            )
            row = _Row(
                req=req, prompt=prompt, generated=[], logprobs=[],
                version_start=self.version, filling=True,
            )
            self._slo_admitted(row)
            self.rows[rid] = row
            admitted += 1
        self._admit_stopped_by = stopped_by
        return admitted, prefix_hits

    def _ensure_decode_blocks(self) -> Tuple[int, int]:
        """Every ACTIVE row's table must cover ``length + chunk`` slots
        before a decode dispatch (the chunk allocates nothing device-side).
        Under pool pressure: evict parked rows, then PREEMPT the youngest
        active rows (recompute-on-readmit, the deterministic analogue of
        vLLM's recompute preemption).  Returns (blocks allocated, rows
        preempted)."""
        W = self.chunk_size
        # every un-harvested chunk that snapshot a row may advance it by
        # up to W more tokens the host has not folded in yet (row_id
        # match only: the device does not know epochs — any chunk
        # dispatched while the slot was active moves its length).  One
        # pass over the ring, not one per row: this is the decode hot
        # loop whose host_s share the split exists to minimize.
        pend_counts: Dict[int, int] = {}
        for ch in self._ring:
            for rid, _ in ch.snapshot:
                pend_counts[rid] = pend_counts.get(rid, 0) + 1
        allocated0 = self._pages.allocated_total
        preempted0 = self.preempted_total
        for row_id in range(self.max_batch):
            row = self.rows[row_id]
            if row is None or row.parked or row.filling:
                continue
            n_pend = pend_counts.get(row_id, 0)
            host_len = row.n_tokens + 1 + n_pend * W
            need = -(-(host_len + W) // self.page_size)
            need = min(need, self.blocks_per_row)
            while True:
                # the first pool whose table is short of it (the
                # whole-context pages', then the window layers')
                pool = next(
                    (p for p in self._pools if len(p.rows[row_id]) < need),
                    None,
                )
                if pool is None:
                    break
                blocks = pool.alloc(need - len(pool.rows[row_id]))
                if blocks is not None:
                    pool.extend_row(row_id, blocks)
                    continue
                # reclamation tiers: prefix-cache entries (recompute
                # insurance only — always yield to a live row), then
                # parked rows, then preemption
                went = self._reclaim_one(preempt_but=row_id)
                if went is None:
                    # only this row left: it must fit by construction
                    raise RuntimeError(
                        "KV pool exhausted with no evictable rows; "
                        f"pool={self.n_blocks} blocks is too small for "
                        f"kv_cache_len={self.kv_cache_len}"
                    )
                if went != "preempted":
                    continue
                # the preemption DRAINED the ring: pending chunks are now
                # folded into every row's generated, so the counts taken
                # above would double-charge them — recompute this row's
                # demand and zero the counts for the rows that follow
                pend_counts.clear()
                if (
                    self.rows[row_id] is None
                    or self.rows[row_id] is not row
                    or row.parked
                ):
                    break  # this very row finished/parked during the drain
                host_len = row.n_tokens + 1
                need = min(
                    -(-(host_len + W) // self.page_size),
                    self.blocks_per_row,
                )
            if self._win is not None and self.rows[row_id] is row:
                # what the row is KNOWN to have cached (chunks still in the
                # ring have only added to it): its window has passed the
                # pages before that, whoever else still holds them
                wrow = self._win.rows[row_id]
                self._win.release_behind(wrow, row.n_tokens - 1, row_id)
                self._win.row_pages_max = max(
                    self._win.row_pages_max, self._win.held(wrow)
                )
        return (
            self._pages.allocated_total - allocated0,
            self.preempted_total - preempted0,
        )

    def _row_priority(self, row: _Row) -> str:
        """The admission plane's priority class, stamped into request
        metadata by the gateway/manager; unlabeled traffic is bulk.
        Metadata rides the SPMD command batch, so every controller
        computes the same class."""
        return str((row.req.metadata or {}).get("priority_class", "bulk"))

    def _pick_preemption_victim(self, exclude: int) -> Optional[int]:
        """Priority-aware: the youngest (highest-epoch) BULK row first —
        bulk rollout rows yield to interactive chat rows under pool
        pressure; an interactive row is evicted only when no bulk
        candidate exists.  Within a class the youngest has the least
        cached work to throw away.  Deterministic (epochs + metadata
        are identical on every SPMD controller)."""
        best, best_key = None, (-1, -1)
        for row_id, row in enumerate(self.rows):
            if (
                row is None or row.parked or row.filling
                or row_id == exclude
            ):
                continue
            is_bulk = 0 if self._row_priority(row) == "interactive" else 1
            key = (is_bulk, row.epoch)
            if key > best_key:
                best, best_key = row_id, key
        return best

    def _requeue_row(self, row_id: int, row: _Row):
        """Take a decoding row's pages and put it back in the fill queue
        (it re-admits with its KV computed again)."""
        self.active = self.active.at[row_id].set(False)
        self._release_row(row_id)
        if self._slo_enabled:
            row.t_preempt = time.monotonic()  # stall until re-activation
        self._preempted.append(row)

    def _preempt_row(self, row_id: int):
        """Stop decoding a row and reclaim its blocks; it re-admits
        through the fill queue (prefix recompute) when space frees up."""
        # every in-flight chunk must be folded in first: preemption
        # rewrites the row set the harvest snapshots refer to (a full
        # pipeline flush — preemption is rare, correctness is not)
        self._drain_ring()
        row = self.rows[row_id]
        if row is None or row.parked or row.filling:
            return  # the drain finished or parked the victim: done
        self._requeue_row(row_id, row)
        self.preempted_total += 1
        cls = self._row_priority(row)
        self.preempted_by_class[cls] = (
            self.preempted_by_class.get(cls, 0) + 1
        )
        self.tracer.event(
            row.req.qid, "engine.preempt", row=row_id,
            cached_tokens=row.n_tokens,
        )
        logger.info(
            "preempted row %d (qid=%s, %d cached tokens) under pool "
            "pressure",
            row_id, row.req.qid, row.n_tokens,
        )

    def _count_dispatch(self, span, snapshot, chunk_size: int):
        """The counts of a decode dispatch's span: the context the host
        knows the dispatched rows to have (prompt + generated so far;
        chunks still in the ring are not in it, so it is a floor), the
        pages that context spans, the page slots of the whole batch
        (every slot's whole table: what a kernel that followed capacity
        would stream; 1 - pages_attended / page_slots is the share the
        paged kernel skips), and the tiles the paged kernel copies of
        those pages (a row's last page only as far as it is filled:
        ctx_tokens_sum / (tiles_attended x tile_tokens) is the share of
        what the kernel reads that is attended); ``rows_planned``
        (:meth:`_rows_planned`).  Read only while a profiler session
        records them."""
        if not span.is_enabled():
            return
        ctx = [self.rows[i].n_tokens for i, _ in snapshot]
        page = self.page_size if self.paged else self.kv_cache_len
        # the unit is the kernel's to name, from the shapes it sees
        tile = (
            paged.kernel_tile_tokens(
                self.k_pool, self.mesh, getattr(self, "_kv_axis", None)
            )
            if self.paged else page
        )
        counts = dict(
            rows=len(snapshot),
            rows_planned=self._rows_planned(snapshot),
            ctx_tokens_sum=sum(ctx),
            chunk_size=chunk_size,
            pages_attended=sum(-(-c // page) for c in ctx),
            page_slots=len(self.rows)
            * (self.blocks_per_row if self.paged else 1),
            tiles_attended=sum(-(-c // tile) for c in ctx),
            tile_tokens=tile,
        )
        if self._win is not None:
            # what a window layer's kernel reads of those contexts
            counts["window_tokens_sum"] = sum(
                min(c, self.cfg.sliding_window) for c in ctx
            )
        if self._stateful:
            # (row, state layer) pairs each of the chunk's steps updates:
            # a state read and written for each
            counts["state_rows"] = len(snapshot) * self.cfg.n_mamba_layers
        if self._by_kind and self.cfg.n_parallel_layers:
            # layers that hold pages AND a state slot (both caches are
            # read in one layer: ctx_tokens_sum and state_rows count them)
            counts["parallel_layers"] = self.cfg.n_parallel_layers
        if self._by_kind and self.cfg.n_cross_layers:
            # layers that read the pool of whole-context pages each step
            # (ctx_tokens_sum times this is what a step reads of it)
            counts["global_readers"] = self.cfg.n_global_readers
        if self.cfg.is_latent and not self.cfg.is_indexed:
            # what the latent kernel reads: ONE entry a position and layer
            # whatever the head count (the same floor as ctx_tokens_sum)
            counts["latent_ctx_tokens_sum"] = counts["ctx_tokens_sum"]
            counts["latent_pages_attended"] = counts["pages_attended"]
        if self.cfg.is_indexed:
            # an indexed layer's step SCORES every cached position (one
            # index key each) and attends the chosen ones alone
            counts["index_ctx_tokens_sum"] = counts["ctx_tokens_sum"]
            counts["sparse_tokens_sum"] = sum(
                min(c, self.cfg.index_topk) for c in ctx
            )
        if self.cfg.is_latent_window:
            # what a latent window layer's kernel reads: ONE entry a
            # position of the window
            counts["latent_window_tokens_sum"] = counts["window_tokens_sum"]
        span.set_metadata(**counts)

    def _rows_planned(self, snapshot) -> int:
        """The rows of a chunk's snapshot that hold a cached position:
        those the paged kernel's decode grid visits (its plan leaves out a
        row without pages, ``ops/paged_attention.LiveRows``), so that 1 -
        rows_planned / slots is the share of grid steps the chunk was
        spared.  From the host's own rows, no device sync: a row that a
        chunk still in the ring has ended counts until its harvest."""
        return sum(self.rows[i].n_tokens > 1 for i, _ in snapshot)

    def _dispatch_chunk_paged(self):
        snapshot = [
            (i, r.epoch) for i, r in enumerate(self.rows)
            if r is not None and not r.parked and not r.filling
        ]
        if self.cfg.is_indexed:
            ctx = [self.rows[i].n_tokens for i, _ in snapshot]
            self.index_positions_scored_total += self.chunk_size * sum(ctx)
            self.index_positions_attended_total += self.chunk_size * sum(
                min(c, self.cfg.index_topk) for c in ctx
            )
        with self._phases.phase("areal.engine.decode.dispatch") as span:
            self._count_dispatch(span, snapshot, self.chunk_size)
            self._dispatch_paged(snapshot)

    def _dispatch_paged(self, snapshot):
        tables = self._pages.upload()
        if self._by_kind:
            win = self._window_args()
            (
                self.k_pool, self.v_pool, self.ssm_state, self.conv_state,
                self.kv_lengths, out_t, out_l, emitted, self.cur_tokens,
                self.active, self.budgets, _, pairs, routed, *more,
            ) = hybrid.hybrid_decode_chunk(
                self.params, self.k_pool, self.v_pool, self.ssm_state,
                self.conv_state, self.cfg, tables, self.kv_lengths,
                self.cur_tokens, self.active, self.budgets,
                self._sample_base_rng, self.chunk_size,
                self._paged_sample_fn, self._paged_stop_fn,
                use_kernel=self._use_paged_kernel,
                max_len=self.kv_cache_len, row_seeds=self.row_seeds, **win,
                **({"keep_chosen": True} if self._keep_chosen else {}),
            )
            # (the kept chosen positions come before the pools)
            chosen = more.pop(0) if self._keep_chosen else None
            if more:
                self.win_k_pool, self.win_v_pool = more[0]
            # (a stack without expert layers has no pairs to count)
            extra = () if not self.cfg.n_experts else (
                (pairs, routed) if self._keep_routed else (pairs,)
            )
            if self._keep_chosen:
                extra += (chosen,)
            self._enqueue_chunk(
                out_t, out_l, emitted, self.active, self.cur_tokens,
                snapshot, extra=extra,
            )
            return
        out = paged.paged_decode_chunk(
            self.params,
            self.k_pool,
            self.v_pool,
            self.cfg,
            tables,
            self.kv_lengths,
            self.cur_tokens,
            self.active,
            self.budgets,
            # FIXED base key: the engine's sampler keys each draw on
            # (request seed, position) from it — dispatch-count invariant
            self._sample_base_rng,
            self.chunk_size,
            self._paged_sample_fn,
            self._paged_stop_fn,
            use_kernel=self._use_paged_kernel,
            max_len=self.kv_cache_len,
            mesh=self.mesh,
            kv_axis=getattr(self, "_kv_axis", None),
            row_seeds=self.row_seeds,
            k_scale=self.k_scale,
            v_scale=self.v_scale,
        )
        if self._kv_quant:
            self.k_scale, self.v_scale = out[10], out[11]
        (
            self.k_pool,
            self.v_pool,
            self.kv_lengths,
            out_t,
            out_l,
            emitted,
            cur,
            self.active,
            self.budgets,
            _,
        ) = out[:10]
        self.cur_tokens = cur
        self._enqueue_chunk(
            out_t, out_l, emitted, self.active, self.cur_tokens, snapshot
        )

    def _admit(self) -> int:
        """Returns the rows admitted."""
        if self.hold_admissions:
            self._admit_stopped_by = admit_stop("held")
            return 0
        self._admit_stopped_by = admit_stop("queue_empty")
        # expired parked rows first: a row parked past the TTL is likely
        # abandoned (rollout dropped, or the group finished elsewhere)
        for row_id, row in enumerate(self.rows):
            if row is not None and row.parked and (
                self._step_seq - row.park_step > self.park_ttl_steps
            ):
                self._release_row(row_id)
        free = [i for i, r in enumerate(self.rows) if r is None]
        to_admit: List[Tuple[int, model_api.APIGenerateInput, List[int], int]] = []
        while True:
            with self._lock:
                if not self._pending:
                    break
                req = self._pending.pop(0)
            if self._try_resume(req):
                continue
            if not free:
                # make room by evicting a parked row — but never one whose
                # own continuation is already queued (evicting it would
                # trade this request's prefill for that one's)
                with self._lock:
                    queued_qids = {r.qid for r in self._pending}
                evicted = self._evict_parked(keep_qids=queued_qids)
                if evicted is None:
                    with self._lock:
                        self._pending.insert(0, req)
                    self._admit_stopped_by = admit_stop("no_slot")
                    break
                free.append(evicted)
            # input_ids = prompt + previously generated tokens (chunked
            # continuation); falls back to the bare prompt
            prompt = list(req.input_ids or req.prompt_ids)
            if len(prompt) + 1 >= self.kv_cache_len:
                # context exhausted: finish immediately with no output so the
                # chunked-rollout client stops resubmitting continuations
                row = _Row(
                    req=req,
                    prompt=prompt,
                    generated=[],
                    logprobs=[],
                    version_start=self.version,
                    no_eos=True,
                )
                self._finish(-1, row, started=False)
                continue
            max_new = req.gconfig.max_new_tokens
            if len(prompt) + max_new > self.kv_cache_len:
                max_new = max(1, self.kv_cache_len - len(prompt))
            to_admit.append((free.pop(0), req, prompt, max_new))
        if not to_admit:
            return 0
        for rid, req, prompt, _ in to_admit:
            self.tracer.event(
                req.qid, "engine.admit", row=rid,
                prompt_len=len(prompt), cached_tokens=0, shared=False,
            )
        t_admit = time.monotonic()  # admission decided; prefill follows
        toks, logps = self._prefill_rows(
            [(rid, prompt) for rid, _, prompt, _ in to_admit],
            seeds=[_qid_seed(req.qid) for _, req, _, _ in to_admit],
        )
        t_first = time.monotonic()  # first tokens materialized on host
        self.tokens_emitted_total += len(to_admit)
        self.first_tokens_blocking_total += len(to_admit)
        started_ids, started_curs, started_budgets = [], [], []
        started_seeds = []
        for (row_id, req, prompt, max_new), tok_i, logp in zip(
            to_admit, toks.tolist(), logps.tolist()
        ):
            row = _Row(
                req=req,
                prompt=prompt,
                generated=[tok_i],
                logprobs=[float(logp)],
                version_start=self.version,
            )
            self._slo_admitted(row, now=t_admit)
            self._slo_first_token(row, now=t_first)
            self._stream_push(row, [int(tok_i)])
            if tok_i in self.stop_tokens or max_new <= 1:
                row.no_eos = tok_i not in self.stop_tokens
                self._finish(row_id, row, started=False)
                continue
            row.cur_token = tok_i
            row.budget_left = max_new - 1
            self._epoch_counter += 1
            row.epoch = self._epoch_counter
            self.rows[row_id] = row
            started_ids.append(row_id)
            started_curs.append(tok_i)
            started_budgets.append(max_new - 1)
            started_seeds.append(_qid_seed(req.qid))
        if started_ids:
            ids = np.array(started_ids, np.int32)
            self.cur_tokens = self.cur_tokens.at[ids].set(
                np.array(started_curs, np.int32)
            )
            self.active = self.active.at[ids].set(True)
            self.budgets = self.budgets.at[ids].set(
                np.array(started_budgets, np.int32)
            )
            self.row_seeds = self.row_seeds.at[ids].set(
                np.array(started_seeds, np.int32)
            )
        return len(to_admit)

    def _finish(
        self, row_id: int, row: _Row, started: bool = True, park: bool = False
    ):
        self._slo_finish(row)
        self.rows_finished_total += 1
        out = model_api.APIGenerateOutput.from_input(row.req)
        out.output_ids = list(row.generated)
        out.output_logprobs = list(row.logprobs)
        out.no_eos = row.no_eos
        out.version_start = row.version_start
        out.version_end = self.version
        self.gen_tokens_total += len(row.generated)
        if self._keep_routed and row.routed is not None:
            self._keep_routing(row.req.qid, np.concatenate(row.routed))
        if row.chosen:
            kept = self._chosen_done
            kept.pop(row.req.qid, None)
            while len(kept) >= self._keep_routed:
                del kept[next(iter(kept))]
            # as the row leaves them (:meth:`chosen_sets` makes positions
            # of them for whoever asks): the last steps read the tokens up
            # to position prompt + generated - 1
            kept[row.req.qid] = (
                len(row.prompt) + len(row.generated) - 1, row.chosen_fill,
                list(row.chosen),
            )
        if started and self.paged and row_id >= 0:
            # cached KV covers prompt + generated[:-1] (the final token is
            # the pending cur; its KV was never written).  Inserting on
            # BOTH park and release is what makes the next turn of a
            # multi-turn conversation — arriving under a fresh qid, on
            # any schedule — prefill only its new suffix.
            cached = (row.prompt + row.generated)[:-1]
            wrow = None
            if self._win is not None:
                # what a parked row keeps, and the cache with it, is its
                # LAST window
                wrow = self._win.rows[row_id]
                self._win.release_behind(wrow, len(cached), row_id)
            self._cache_insert(cached, self._pages.rows[row_id], wrow)
        if started and park:
            # keep KV resident; the last generated token is the pending
            # cur_token (its KV was never written — see decode_chunk)
            row.parked = True
            row.park_step = self._step_seq
            row.cur_token = row.generated[-1]
            self.active = self.active.at[row_id].set(False)
        elif started:
            self._release_row(row_id)
            self.active = self.active.at[row_id].set(False)
        self.tracer.event(
            row.req.qid, "engine.finish",
            park=bool(started and park), n_tokens=len(row.generated),
            version_start=row.version_start, version_end=self.version,
        )
        with self._lock:
            self._results[row.req.qid] = out
            ev = self._result_events.get(row.req.qid)
        if ev:
            ev.set()

    def _decode_chosen_sets(self, kept) -> np.ndarray:
        """``[n, L_indexed, K]`` int32 positions (-1: none) of a row's kept
        decode steps, in :meth:`chosen_sets`' form.  A step left ``(what the
        decode program handed out of it [L_indexed, .], the row's cached
        length when its chunk began)``; ``sparse_attention.kept_positions``
        reads either form the program hands out, by what it is."""
        table = self.blocks_per_row * self.page_size
        return np.stack([
            np.stack([
                sparse_attention.kept_positions(
                    row, table, cached, self.cfg.index_topk
                )
                for row in step
            ])
            for step, cached in kept
        ])

    def _keep_routing(self, qid: str, routing: np.ndarray):
        """A finished request's routing, the oldest out first.  The room
        is memory's: ``keep_routed_experts`` sequences as long as the
        cache holds (``kv_cache_len`` positions each), so at least the
        last that many requests are kept, and more where they are
        shorter.  (Kept by COUNT, an engine that finishes its requests
        sooner lost the routing of a reader's oldest ones: PR 48.)"""
        kept = self._routed_done
        self._routed_done_positions += len(routing) - len(kept.pop(qid, ()))
        kept[qid] = routing
        while self._routed_done_positions > self._keep_routed * self.kv_cache_len:
            self._routed_done_positions -= len(kept.pop(next(iter(kept))))

    def chosen_sets(self, qid: str) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """``(at [n], sets [n, L_indexed, K])`` int32 of a finished
        request: the positions each indexed layer ATTENDED for the query
        at position ``at[i]`` (-1: fewer than K were there to choose), at
        the last ``keep_chosen_sets`` positions of its prompt (from the
        fill's mask; fewer where the prompt's last chunk was shorter, none
        where no fill of this engine's handed them out) and then at its
        last ``keep_chosen_sets`` decode steps (which read the tokens up
        to the one before the last generated).  None unless kept."""
        kept = self._chosen_done.get(qid)
        if kept is None:
            return None
        read, fill, steps = kept
        # step i of the last n read the token at position read - n + i
        at = np.arange(read - len(steps), read, dtype=np.int32)
        sets = self._decode_chosen_sets(steps)
        if fill is not None:
            at, sets = np.concatenate([fill[0], at]), np.concatenate([fill[1], sets])
        return at, sets

    def routed_experts(self, qid: str) -> Optional[np.ndarray]:
        """``[prompt + generated - 1, L, K]`` int16: every layer's routed
        experts (published numbers) at each position request ``qid`` READ,
        in order: entry ``t`` stands behind the log-probability of token
        ``t + 1``.  None unless ``keep_routed_experts`` still holds it."""
        return self._routed_done.get(qid)

    def _attn_bucket(self, extra: int = 0) -> int:
        """Static attention prefix for the next chunk, as a power-of-two
        bucket of the longest CACHED row (few recompiles, halved-or-better
        KV streaming early in generation).  In-chunk tokens never need it
        larger: their KV lives in the decode window, cache attention reads
        only the frozen base_lens prefix, and the end-of-chunk scatter
        targets the full unsliced cache.  ``extra`` covers lengths the host
        has not harvested yet (one chunk_size per in-flight pipelined
        chunk)."""
        longest = 0
        for row in self.rows:
            if row is not None and not row.parked:
                longest = max(
                    longest, len(row.prompt) + len(row.generated) + 1
                )
        need = min(longest + extra, self.kv_cache_len)
        p = 256
        while p < need:
            p <<= 1
        return min(p, self.kv_cache_len)

    def _dispatch_chunk(self):
        """Enqueue one decode chunk on the device (async) and record its
        output futures + the in-flight row snapshot for a later harvest."""
        snapshot = [
            (i, r.epoch) for i, r in enumerate(self.rows)
            if r is not None and not r.parked
        ]
        with self._phases.phase("areal.engine.decode.dispatch") as span:
            self._count_dispatch(span, snapshot, self.chunk_size)
            self._dispatch_dense(snapshot)

    def _dispatch_dense(self, snapshot):
        (
            self.cache,
            out_t,
            out_l,
            emitted,
            self.cur_tokens,
            self.active,
            self.budgets,
            _,
        ) = _decode_chunk(
            self.params,
            self.cfg,
            self.cache,
            self.cur_tokens,
            self.active,
            self.budgets,
            self.row_seeds,
            # the FIXED base key: draws are keyed on (request seed,
            # position) inside — dispatch-count invariant
            self._sample_base_rng,
            self.chunk_size,
            self.stop_tokens,
            self.sampling,
            attn_len=self._attn_bucket(
                extra=len(self._ring) * self.chunk_size
            ),
            mesh=self.mesh,
        )
        self._enqueue_chunk(
            out_t, out_l, emitted, self.active, self.cur_tokens, snapshot
        )

    def _enqueue_chunk(
        self, out_t, out_l, emitted, active_dev, cur_dev, snapshot, extra=(),
    ):
        """Append a dispatched chunk to the in-flight ring and START its
        device->host output copy.  The copy rides under the device time
        of the chunks queued behind it, so by the time the harvest blocks
        on this chunk the fetch round-trip is (partly or fully) paid —
        the async-fetch half of the deep pipeline.  Multi-host meshes:
        outputs are replicated but not fully addressable from one
        process, so the local replica is swapped in before the copy."""
        arrs = tuple(
            x.addressable_data(0)
            if isinstance(x, jax.Array) and not x.is_fully_addressable
            else x
            for x in (out_t, out_l, emitted, active_dev, cur_dev, *extra)
        )
        if jax_compat.start_host_copies(arrs):
            self.async_fetches_total += 1
        self.chunks_dispatched_total += 1
        self.decode_rows_dispatched_total += len(snapshot)
        self.decode_rows_planned_total += self._rows_planned(snapshot)
        # the first chunk to decode the rows activated since the last one:
        # their first tokens are folded at its harvest, ahead of its own
        self._ring.append(
            _InflightChunk(
                arrs=arrs, snapshot=snapshot,
                first_tokens=self._first_tokens,
            )
        )
        self._first_tokens = []

    def _drain_ring(self) -> int:
        """Harvest EVERY in-flight chunk, oldest first, and settle the
        first tokens on their way (pipeline flush: pause, weight swap,
        preemption, cancel — host rows exact afterwards)."""
        n = 0
        while self._ring:
            n += self._harvest_oldest()
        self._settle_first_tokens()  # (those no chunk had taken up)
        return n

    def _harvest_oldest(self) -> int:
        """Fetch the OLDEST dispatched chunk's outputs and fold them into
        the host rows.  FIFO order is the ring-ordering invariant: a row's
        tokens append in dispatch sequence.  Only rows in the dispatch-time
        snapshot (matching epoch) are touched — rows admitted/resumed
        after the dispatch emitted nothing in this chunk."""
        if not self._ring:
            return 0
        chunk = self._ring.popleft()
        if chunk.first_tokens:
            # rows this chunk was the first to decode: their first tokens,
            # sampled by programs queued before it, are there before its
            # own outputs and go to their rows as soon as they are
            with self._phases.phase("areal.engine.harvest.wait"):
                for record in chunk.first_tokens:
                    for x in record.arrays or ():
                        x.block_until_ready()
            for record in chunk.first_tokens:
                self._fold_first_tokens(record)
        arrs = chunk.arrs
        # time attribution: block_until_ready isolates the wait for device
        # compute from the device_get transfer that follows (the transfer
        # is the PCIe cost the async dispatch-time copy hides)
        with self._phases.phase("areal.engine.harvest.wait"):
            try:
                ready = all(
                    x.is_ready() for x in arrs if isinstance(x, jax.Array)
                )
            except Exception:  # noqa: BLE001 - readiness probe is telemetry
                ready = False  # only; never load-bearing (SPMD determinism)
            if ready:
                self.fetch_ready_total += 1
            for x in arrs:
                if isinstance(x, jax.Array):
                    x.block_until_ready()
        with self._phases.phase("areal.engine.harvest.fetch"):
            fetched = jax.device_get(arrs)
        self.chunks_total += 1
        with self._phases.phase("areal.engine.harvest.fold") as span:
            n_tokens = self._fold_chunk(chunk, fetched)
            self._add_fill_rounds_that_arrived()
            counts = {"tokens": n_tokens}
            if len(fetched) > 5:
                # the chunk's (token, k) pairs by held expert, and last
                # those routed to experts held elsewhere
                pairs = np.asarray(fetched[5], np.int64)
                if self.cfg.moe_router == "sigmoid_group":
                    # a group-limited router's last count: (token, chosen
                    # group) pairs whose group has an expert held here
                    pairs, hit = pairs[:-1], int(pairs[-1])
                    self.moe_groups_hit_total += hit
                    counts["moe_groups_hit"] = hit
                self.moe_expert_pairs += pairs[:-1]
                self.moe_pairs_held_total += int(pairs[:-1].sum())
                self.moe_pairs_routed_total += int(pairs.sum())
                counts.update(
                    moe_pairs_held=int(pairs[:-1].sum()),
                    moe_pairs_routed=int(pairs.sum()),
                    moe_expert_pairs_max=int(pairs[:-1].max()),
                )
            span.set_metadata(**counts)
        return n_tokens

    def _fold_chunk(self, chunk: _InflightChunk, fetched) -> int:
        """Fold a fetched chunk's outputs into the host rows; returns the
        tokens it handed them."""
        out_t, out_l, emitted, active, cur = fetched[:5]
        snapshot = chunk.snapshot
        n_tokens = 0
        t_harvest = time.monotonic()  # chunk's tokens reach the host NOW
        for row_id, epoch in snapshot:
            row = self.rows[row_id]
            # skip freed-and-reused slots: the dispatch-time occupant is
            # gone and this chunk says nothing about the new one
            if row is None or row.parked or row.epoch != epoch:
                continue
            cols = emitted[row_id]
            toks = out_t[row_id][cols].tolist()
            lps = out_l[row_id][cols].tolist()
            if len(fetched) > 7:
                # [W, L_indexed, B, .] -> this row's emitted steps, the
                # last ``keep_chosen_sets`` of its life
                if row.chosen is None:
                    row.chosen = collections.deque(maxlen=self._keep_chosen)
                # (with the row's cached length now, where the chunk's
                # first token stands: a step's mask counts its chunk's own
                # tokens from there)
                cached = len(row.prompt) + len(row.generated) - 1
                row.chosen.extend(
                    (step, cached) for step in fetched[7][cols, :, row_id]
                )
            row.generated.extend(toks)
            row.logprobs.extend(lps)
            if len(fetched) > 6 and row.routed is not None:
                # [W, L, K, B] -> this row's emitted steps as [n, L, K]
                row.routed.append(
                    fetched[6][cols, :, :, row_id].astype(np.int16)
                )
            row.budget_left -= len(toks)
            n_tokens += len(toks)
            if toks and self._slo_enabled:
                self._slo_first_token(row, now=t_harvest)
                row.t_last = t_harvest
            if toks:
                self._stream_push(row, toks)
                self.tracer.event(
                    row.req.qid, "engine.chunk", row=row_id,
                    epoch=epoch, n_tokens=len(toks), step=self._step_seq,
                )
            if not active[row_id]:
                last = row.generated[-1] if row.generated else -1
                row.no_eos = last not in self.stop_tokens
                # budget-exhausted rows with cache headroom stay resident so
                # the chunked continuation resumes without re-prefill
                # (a stateful model's rows never park: their slot is
                # free at once and the continuation re-prefills)
                park = (
                    row.no_eos
                    and not self._stateful
                    and len(row.prompt) + len(row.generated) + 1
                    < self.kv_cache_len
                )
                self._finish(row_id, row, park=park)
            else:
                row.cur_token = int(cur[row_id])
        self._tokens_harvested_total += n_tokens
        self.tokens_emitted_total += n_tokens
        return n_tokens

    def _worth_dispatching(self) -> bool:
        """Skip a dispatch that could only decode rows the un-harvested
        ring is certain to finish (budget exhaustion is deterministic;
        EOS is not, so an occasional wasted tail chunk remains).

        A row appearing in ``c`` ring snapshots may consume up to
        ``c * chunk_size`` more budget the host has not folded in yet; it
        is certainly alive only if its budget exceeds that.  Counting
        occurrences per (row_id, epoch) — not "is it in the one pending
        snapshot" — is what makes this correct for rows admitted or
        resumed MID-RING: their epoch appears in no snapshot (c=0), so
        their full budget counts and they always earn the dispatch."""
        if not self._ring:
            return True
        counts: Dict[Tuple[int, int], int] = {}
        for ch in self._ring:
            for key in ch.snapshot:
                counts[key] = counts.get(key, 0) + 1
        for row_id, row in enumerate(self.rows):
            if row is None or row.parked or row.filling:
                continue
            c = counts.get((row_id, row.epoch), 0)
            if row.budget_left > c * self.chunk_size:
                return True
        return False

    def phase_seconds(self) -> Dict[str, float]:
        """Cumulative self seconds of the engine thread by phase span
        (``table.ENGINE_PHASES``): they add up to the wall time of the
        steps, the paused branch's sleep excluded."""
        return dict(self._phases.seconds)

    def timing_split(self) -> Dict[str, float]:
        """Cumulative decode-loop time attribution: device = blocked
        waiting for a chunk's compute (``harvest.wait``), fetch = its
        outputs' device->host transfer (``harvest.fetch``), host = every
        other phase (admit, bookkeeping, dispatch-enqueue — and any
        blocked sync outside the harvest, which ``phase_seconds()`` tells
        apart)."""
        sec = self.phase_seconds()
        device_s = sec.pop("areal.engine.harvest.wait")
        fetch_s = sec.pop("areal.engine.harvest.fetch")
        return {
            "host_s": sum(sec.values()),
            "device_s": device_s,
            "fetch_s": fetch_s,
            "chunks": self.chunks_total,
        }

    def step(self) -> int:
        """One engine iteration, DEEP-PIPELINED: weight swap (if
        requested), admit, dispatch chunk N+K-1, then harvest chunk N —
        the oldest of up to ``pipeline_depth`` in-flight chunks.  Keeping
        K chunks queued (with their output fetches started at dispatch)
        keeps the device busy even when the fetch round-trip exceeds a
        chunk's own device time (on a local chip a fetch is a PCIe
        copy; which K pays is for a chip measurement to say).  Harvest
        policy is dispatch-count-based only (ring full, or nothing left
        to dispatch) — never readiness probes, so SPMD follower
        controllers replaying the command stream take identical branches.
        Returns the number of tokens emitted — every token any harvest
        folded in during this step, including mid-step ring drains
        (weight swaps, preemption flushes); 0 on ring-filling warm-up
        steps."""
        self._step_seq += 1
        h0 = self._tokens_harvested_total
        if self._paused.is_set():
            # drain the whole ring so pause means quiesced (the idle-pause
            # sleep is outside the span: it would read as host overhead)
            with self._phases.phase("areal.engine.step") as span:
                n = self._drain_ring()
                # (a pause holds admissions too)
                self._admit_stopped_by = (
                    admit_stop("held") if self._pending
                    else admit_stop("queue_empty")
                )
                self._count_step(span)
            if n == 0:
                time.sleep(0.01)
            return n
        # the step span's SELF time is what no child span covers: host
        # bookkeeping, like every child but the harvest's wait and fetch
        with self._phases.phase("areal.engine.step") as span:
            try:
                self._apply_pending_weights()
                if self.paged:
                    with self._phases.phase("areal.engine.admit") as sp:
                        admitted, hits = self._admit_paged()
                        self.rows_admitted_total += admitted
                        sp.set_metadata(
                            rows_admitted=admitted, prefix_hits=hits
                        )
                    self._advance_fill()
                    self._process_deferred_cancels()
                    with self._phases.phase(
                        "areal.engine.ensure_blocks"
                    ) as sp:
                        allocated, preempted = self._ensure_decode_blocks()
                        if sp.is_enabled():
                            sp.set_metadata(
                                blocks_allocated=allocated,
                                rows_preempted=preempted,
                                **self._cache_counts(),
                            )
                    dispatched = False
                    if (
                        self.n_decoding > 0
                        and len(self._ring) < self.pipeline_depth
                        and self._worth_dispatching()
                    ):
                        self._dispatch_chunk_paged()
                        dispatched = True
                else:
                    with self._phases.phase("areal.engine.admit") as sp:
                        admitted = self._admit()
                        self.rows_admitted_total += admitted
                        sp.set_metadata(rows_admitted=admitted, prefix_hits=0)
                    dispatched = False
                    if (
                        self.n_decoding > 0
                        and len(self._ring) < self.pipeline_depth
                        and self._worth_dispatching()
                    ):
                        self._dispatch_chunk()
                        dispatched = True
                if len(self._ring) >= self.pipeline_depth or (
                    not dispatched and self._ring
                ):
                    self._harvest_oldest()
                if self._first_tokens:
                    # no chunk took them up (nothing was worth
                    # dispatching): the device has little else to do
                    self._settle_first_tokens()
                return self._tokens_harvested_total - h0
            finally:
                self._ledger_sync_host_buffers()
                self._count_step(span)

    def _step_totals(self) -> Tuple[int, ...]:
        """The running totals whose movement over a step is in its record,
        in ``STEP_DELTAS``' order."""
        return (
            self.tokens_emitted_total, self.rows_admitted_total,
            self.rows_finished_total, self.preempted_total,
            self.chunks_dispatched_total, self.decode_rows_dispatched_total,
            self.decode_rows_planned_total, self.prefill_calls,
            self.prefill_tokens_total,
            self.fill_slots_total, self._kept.late_joins_total,
            self.admission_page_waits_total,
        )

    def _count_step(self, span):
        """The one place that counts a step, traced or not: the engine's
        state at the step's end and what moved since the last step's
        count (the totals that move between two steps, a cancel's drain,
        are in the later one's), as the step span's counts and as the
        notes of the clock's record (``table.ENGINE_STEP_RECORD``).  One
        pass over the rows; nothing per token or per page, nothing from
        the device."""
        decoding = filling = parked = empty = 0
        for r in self.rows:
            if r is None:
                empty += 1
            elif r.parked:
                parked += 1
            elif r.filling:
                filling += 1
            else:
                decoding += 1
        pending, ring = len(self._pending), len(self._ring)
        if pending and self._admit_stopped_by == "no_pages":
            self.admission_page_waits_total += 1
        span.set_metadata(
            step=self._step_seq, rows_decoding=decoding,
            rows_filling=filling, pending=pending, ring=ring,
            tokens_emitted_total=self.tokens_emitted_total,
        )
        totals = self._step_totals()
        self._phases.note(
            step=self._step_seq, slots_decoding=decoding,
            slots_filling=filling, slots_parked=parked, slots_empty=empty,
            pending=pending, admit_stopped_by=self._admit_stopped_by,
            ring=ring, chunk_size=self.chunk_size, version=self.version,
            **{
                name: now - before for name, now, before
                in zip(STEP_DELTAS, totals, self._counted)
            },
        )
        if (
            totals == self._counted
            and not (decoding or filling or ring)
        ):
            # nothing moved and nothing is in flight: an idle engine's
            # poll, a pause; one record for the whole stretch of them
            self._phases.quiet()
        self._counted = totals
