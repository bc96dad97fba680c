"""Self-speculative decoding for the paged serving engine.

Decode is the rollout bottleneck: a July run on a 0.5B model measured
~6.4k decode tok/s against ~38k prefill tok/s at b64 on one v5e — the
engine's prefill machinery sits ~6x faster than the loop that actually
produces tokens.
Speculative decoding converts that prefill-rate surplus into decode
throughput, and RL math/code traces are repetitive enough that no draft
model is needed: each row DRAFTS its own continuation by n-gram /
prompt-lookup over its prompt+output token history (the self-drafting
family: prompt-lookup decoding / SGLang's ngram speculative mode /
vLLM's ``method="ngram"``), then a single batched VERIFY pass — a paged
prefill of the draft window over the row's cached prefix, riding the
same :func:`areal_tpu.models.paged.paged_window_forward` core as chunked
prefill — scores every draft position at prefill cost.

Exactness contract: verification is longest-accepted-prefix under
GREEDY decode.  Window position j's logits yield the greedy target
``t_j``; draft ``d_{j+1}`` is accepted iff it equals ``t_j`` and every
earlier draft was accepted; the first divergence emits the verifier's
own token instead (the "correction"), so every verify step emits
between 1 (total rejection — plain-decode progress, the bounded worst
case) and ``max_draft_tokens + 1`` tokens and the emitted stream is
token-identical to non-speculative greedy decode.  KV for the window is
scattered into the row's own pool blocks; rejected positions leave
garbage only BEYOND the row's valid length, which the next decode/
verify/fill write overwrites and which neither attention (reads
``[0, length)``) nor the radix prefix cache (indexes only the valid
prefix) can ever observe.

Per-row acceptance is tracked as an EMA; rows whose drafts keep missing
fall back to the plain chunked-decode path (threshold default in
``engine/dispatch.py`` — measured, like the other dispatch decisions),
so a non-repetitive workload pays only the warmup verifies.

Everything host-side here is deterministic (dict insertion order, no
wall-clock): multi-host SPMD controllers replaying the same command
stream draft identically and take identical spec/plain branches.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from areal_tpu.engine.sampling import SamplingParams, sample_logits
from areal_tpu.models import paged
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import _head

#: acceptance-rate EMA below which a row's drafts are judged not worth
#: verifying and the row falls back to plain chunked decode.  Conservative
#: on purpose: at 7 drafts it only ejects rows whose windows verify about
#: 2 tokens or fewer a pass.
DEFAULT_SPEC_MIN_ACCEPT_RATE = 0.2

#: cost of one verify pass in plain-decode-step units.  The per-step batch
#: vote dispatches a verify instead of a decode chunk only when the
#: EMA-expected emitted tokens a pass exceed this times the live rows (the
#: pass out-emits the decode steps it displaces).  A window runs at
#: prefill arithmetic intensity, so on TPU it sits near 1-2; no chip run
#: has measured it.
DEFAULT_SPEC_VERIFY_COST = 2.0


@dataclasses.dataclass(frozen=True)
class SpecDecodeParams:
    """Engine-level speculative-decoding knobs (resolved from
    ``GenServerConfig.spec_decode``; see :func:`resolve_spec_params`)."""

    enabled: bool = False
    #: max draft tokens proposed per verify step (window = this + 1, the
    #: pending token; the verify emits at most this + 1 tokens per
    #: step).  Keep it at a power of two MINUS ONE: windows bucket to
    #: powers of two (batching.spec_window_bucket), so e.g. 8 drafts
    #: would pad every window to 16 positions and double the verify
    #: compute for nothing.
    max_draft_tokens: int = 7
    #: n-gram sizes tried for the history lookup, longest first (a longer
    #: matched context predicts the continuation more reliably)
    ngram_max: int = 3
    ngram_min: int = 1
    #: acceptance-rate EMA below which a row falls back to plain decode
    min_accept_rate: float = DEFAULT_SPEC_MIN_ACCEPT_RATE
    ema_decay: float = 0.9
    #: verifies before the fallback threshold may fire (one unlucky
    #: first window must not disable a row for its whole generation)
    warmup_verifies: int = 4
    #: verify-pass cost in plain-decode-step units; the batch vote
    #: dispatches a verify only when the EMA-expected emission beats
    #: this per live row
    verify_cost_over_decode_step: float = DEFAULT_SPEC_VERIFY_COST


def resolve_spec_params(cfg_block) -> Optional[SpecDecodeParams]:
    """Map a ``GenServerConfig.spec_decode`` block (or None) to engine
    params; a ``min_accept_rate`` or ``verify_cost_over_decode_step`` of
    None keeps this module's default."""
    if cfg_block is None or not getattr(cfg_block, "enabled", False):
        return None
    thr = getattr(cfg_block, "min_accept_rate", None)
    cost = getattr(cfg_block, "verify_cost_over_decode_step", None)
    return SpecDecodeParams(
        enabled=True,
        max_draft_tokens=int(cfg_block.max_draft_tokens),
        ngram_max=int(cfg_block.ngram_max),
        ngram_min=int(cfg_block.ngram_min),
        min_accept_rate=(
            DEFAULT_SPEC_MIN_ACCEPT_RATE if thr is None else float(thr)
        ),
        ema_decay=float(cfg_block.ema_decay),
        warmup_verifies=int(cfg_block.warmup_verifies),
        verify_cost_over_decode_step=(
            DEFAULT_SPEC_VERIFY_COST if cost is None else float(cost)
        ),
    )


class SpecRowState:
    """Per-row drafting state: an incremental n-gram index over the
    row's prompt+output history, plus acceptance bookkeeping.

    The index maps each n-gram (for n in [ngram_min, ngram_max]) to the
    most recent position it ENDS at, maintained incrementally as the
    history grows — O(appended tokens) per draft call, not O(history).
    Indexing always stops one position short of the history tail, so the
    lookup of the tail n-gram finds a strictly EARLIER occurrence.  The
    state survives park/resume, preemption/readmit, and weight swaps
    unchanged: none of those rewrite past tokens."""

    __slots__ = (
        "ema", "verifies", "fallback", "miss_streak", "cooldown_until",
        "_index", "_indexed_upto",
    )

    def __init__(self):
        self.ema = 1.0  # optimistic start: every row earns its warmup
        self.verifies = 0
        self.fallback = False
        # draft-miss backoff: a row whose history holds no recurring
        # n-gram skips draft attempts for exponentially growing step
        # windows, so a non-repetitive wave never pays per-step drafting
        # (or the ring quiesce drafting needs) — the spec-off worst case
        self.miss_streak = 0
        self.cooldown_until = 0  # engine step_seq gate
        self._index: Dict[int, Dict[Tuple[int, ...], int]] = {}
        self._indexed_upto = 0

    def wants_draft(self, step_seq: int) -> bool:
        return not self.fallback and step_seq >= self.cooldown_until

    def note_draft_result(self, productive: bool, step_seq: int):
        """``productive`` = this draft attempt actually led to a verify
        (a hit AND the batch vote picked spec).  A lookup miss and a
        vote loss back off identically: both mean the row paid draft
        cost (and forced a ring quiesce) for nothing, and a row whose
        n-grams keep hitting while the batch keeps voting plain would
        otherwise drain the pipeline to depth 1 every single step."""
        if productive:
            self.miss_streak = 0
            return
        self.miss_streak += 1
        if self.miss_streak >= 2:
            self.cooldown_until = step_seq + min(
                1 << (self.miss_streak - 2), 64
            )

    def draft(self, history: List[int], params: SpecDecodeParams) -> List[int]:
        """Propose up to ``max_draft_tokens`` continuation tokens for
        ``history`` (prompt + generated, INCLUDING the pending token) by
        longest-n-gram lookup; [] when no n-gram recurs.

        The lookup CHAINS: after each predicted token, the (virtual)
        tail n-gram is looked up again.  A plain copy-forward from the
        matched position would usually yield a single token on exactly
        the traces self-drafting feeds on — a near-periodic sequence's
        most recent n-gram occurrence sits right at the tail — while the
        chained lookup walks the cycle and fills the whole window."""
        n_hist = len(history)
        hi = n_hist - 1  # never index the tail position before lookup
        for pos in range(self._indexed_upto, hi):
            for n in range(params.ngram_min, params.ngram_max + 1):
                if pos + 1 >= n:
                    self._index.setdefault(n, {})[
                        tuple(history[pos - n + 1 : pos + 1])
                    ] = pos
        self._indexed_upto = max(self._indexed_upto, hi)
        virt = None  # history + drafts so far, built only on first hit
        drafts: List[int] = []
        while len(drafts) < params.max_draft_tokens:
            src = virt if virt is not None else history
            nxt = None
            for n in range(params.ngram_max, params.ngram_min - 1, -1):
                if len(src) < n:
                    continue
                j = self._index.get(n, {}).get(tuple(src[len(src) - n :]))
                if j is not None:
                    nxt = history[j + 1]
                    break
            if nxt is None:
                break
            if virt is None:
                virt = list(history)
            virt.append(nxt)
            drafts.append(nxt)
        return drafts

    def observe(
        self, accepted: int, drafted: int, params: SpecDecodeParams
    ) -> bool:
        """Fold one verify outcome into the EMA; returns True when this
        observation tripped the fallback (caller counts it once)."""
        self.verifies += 1
        frac = accepted / max(drafted, 1)
        d = params.ema_decay
        self.ema = d * self.ema + (1.0 - d) * frac
        if (
            not self.fallback
            and self.verifies >= params.warmup_verifies
            and self.ema < params.min_accept_rate
        ):
            self.fallback = True
            return True
        return False


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "max_draft", "stop_tokens", "sampling", "use_kernel",
        "max_len", "mesh", "kv_axis",
    ),
    donate_argnums=(1, 2),
    donate_argnames=("k_scale", "v_scale"),
)
def paged_verify_chunk(
    params,
    k_pool: jax.Array,  # [L, NB, Hkv, BS, hd]
    v_pool: jax.Array,
    cfg: TransformerConfig,
    tables: jax.Array,  # [B, MB]
    lengths: jax.Array,  # [B] valid cache prefix per row
    cur_tokens: jax.Array,  # [B] pending token per row (KV not yet cached)
    draft_tokens: jax.Array,  # [B, max_draft] right-padded host drafts
    draft_lens: jax.Array,  # [B] valid drafts per row
    participants: jax.Array,  # [B] bool: rows verifying this step
    active: jax.Array,  # [B] bool
    budgets: jax.Array,  # [B] remaining new tokens (incl. pending cur)
    max_draft: int,
    stop_tokens: Tuple[int, ...],
    sampling: SamplingParams,
    use_kernel: bool,
    max_len: int,
    mesh=None,
    kv_axis=None,
    k_scale=None,  # [L, NB, Hkv, BS] int8-pool scales (None = fp pool)
    v_scale=None,
):
    """Batched draft verification: ONE paged-prefill pass over each
    participating row's window ``[cur, d_1..d_k]`` with greedy targets,
    acceptance bookkeeping, and state advance all device-side, so a
    verify chunk chains through the engine's in-flight ring exactly like
    a decode chunk (same output signature/semantics: ``out_t``/``out_l``
    /``emitted`` columns are the emitted tokens in order, ``cur``/
    ``active``/``budgets``/``lengths`` advance for the next dispatch;
    ``(k_scale, v_scale)`` append on a quantized pool).

    Non-participant rows pass through untouched.  Window KV scatters
    into the rows' own pre-covered blocks (quantized at the scatter on
    an int8 pool, like any fill); positions at/beyond ``max_len`` are
    masked (never clipped into a foreign block).
    """
    if cfg.loop_steps > 1:
        raise NotImplementedError(
            f"speculative verify with loop_steps {cfg.loop_steps}: refused "
            "(engine/kv_pages.py, LOOPED)"
        )
    B = cur_tokens.shape[0]
    C = max_draft + 1
    window = jnp.concatenate([cur_tokens[:, None], draft_tokens], axis=1)
    act = active & participants
    iot = jnp.arange(C, dtype=jnp.int32)
    valid = (
        act[:, None]
        & (iot[None, :] <= draft_lens[:, None])
        & ((lengths[:, None] + iot[None, :]) < max_len)
    )  # [B, C] positions forwarded + scattered
    x, k_pool, v_pool, k_scale, v_scale = paged.paged_window_forward(
        params, k_pool, v_pool, cfg, window, lengths, valid, tables,
        use_kernel=use_kernel, mesh=mesh, kv_axis=kv_axis,
        k_scale=k_scale, v_scale=v_scale,
    )

    # greedy targets + behavioral logprobs per window position, scanned
    # so the [B, V] logits transient never becomes [B, C, V] (a 152k
    # vocab at C=9 would be hundreds of MB)
    dummy = jax.random.PRNGKey(0)  # greedy sampling reads no randomness

    def head_step(_, xj):  # xj [B, D]
        logits = _head(params, cfg, xj[:, None])[:, 0]
        t, lp = sample_logits(logits.astype(jnp.float32), dummy, sampling)
        return None, (t, lp)

    _, (tgt, logp) = jax.lax.scan(head_step, None, x.swapaxes(0, 1))
    tgt = tgt.T  # [B, C]
    logp = logp.T

    def is_stop(tok):
        stop = jnp.zeros_like(tok, dtype=bool)
        for s in stop_tokens:
            stop |= tok == s
        return stop

    # acceptance chain: draft j+1 is confirmed iff it equals target j
    match = (window[:, 1:] == tgt[:, :-1]) & valid[:, 1:]  # [B, C-1]
    chain = jnp.concatenate(
        [
            jnp.ones((B, 1), bool),
            jnp.cumprod(match.astype(jnp.int32), axis=1).astype(bool),
        ],
        axis=1,
    )  # [B, C]: position j emits only if drafts 1..j all matched
    stop_t = is_stop(tgt)
    no_stop_prefix = jnp.concatenate(
        [
            jnp.ones((B, 1), bool),
            jnp.cumprod(
                (~stop_t[:, :-1]).astype(jnp.int32), axis=1
            ).astype(bool),
        ],
        axis=1,
    )  # a stop target ends emission AFTER itself
    emitted = (
        valid
        & chain
        & (iot[None, :] < budgets[:, None])
        & no_stop_prefix
    )  # prefix-contiguous by construction (every factor is monotone)
    m = emitted.sum(axis=1).astype(jnp.int32)  # [B] tokens emitted (>=1
    # for every live participant: position 0 always passes the chain)
    new_lengths = lengths + m
    last_tok = jnp.take_along_axis(
        tgt, jnp.maximum(m - 1, 0)[:, None], axis=1
    )[:, 0]
    new_cur = jnp.where(act & (m > 0), last_tok, cur_tokens)
    new_budgets = budgets - m
    cont = (
        act
        & ~is_stop(last_tok)
        & (new_budgets > 0)
        & (new_lengths < max_len)
    )
    new_active = jnp.where(participants, cont, active)
    out_t = jnp.where(emitted, tgt, 0)
    out_l = jnp.where(emitted, logp, 0.0)
    base = (
        k_pool, v_pool, new_lengths, out_t, out_l, emitted, new_cur,
        new_active, new_budgets,
    )
    if k_scale is None:
        return base
    return base + (k_scale, v_scale)
