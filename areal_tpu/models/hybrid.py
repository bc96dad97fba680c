"""A stack stated by kind: each layer a MIXER (Mamba-2 or Mamba-1 with a
recurrent state, attention with paged per-head KV over the whole context
or over a window of it, latent attention with paged latent entries,
CROSS attention over another layer's pages, a gated memory unit over
another layer's scan output, or attention AND Mamba-2 side by side on one
input) and an MLP (a dense one on the leading ``cfg.n_dense_layers``
layers, else the expert block this program's share of the experts gives)
(granitemoehybrid, deepseek_v3, smallthinker, phi4flash, falcon_h1,
dots3_note, laguna).

    h0 = embed_scale * embed[tokens]
    per layer:  h += r * mixer(norm(h));  m = norm(h)
                h += r * (experts(m) + shared(m))   or   r * dense(m)
    logits = norm(h) @ head / logits_divisor     (head: embed^T if tied)

(``norm``: ``cfg.norm_type``, an RMS norm or a LayerNorm with bias.)

**The layer plan** (:func:`layer_plan`): the published ``layer_types`` cut
into runs of one (mixer, MLP) pair, in order; a run is one ``lax.scan``
over its layers, and a stretch of single layers that repeats a pattern
(``[mamba1, window] x 8``) is one scan over the pattern's repetitions
(:func:`plan_periods`).  Parameters are stacked BY KIND: ``params["mamba"]``
over the Mamba mixers, ``params["attn"]`` over the attention mixers,
``params["latent"]`` over the latent ones, ``params["dense"]`` over the
dense MLPs, ``params["layers"]["mlp"]`` over the expert blocks and the two
norms of ``params["layers"]`` over all layers (``params["mamba1"]``,
``params["cross"]`` and ``params["gmu"]`` likewise); a run's body indexes them
by the layer's number and by its numbers among its kinds.  The dense stack
of ``transformer.py`` / ``paged.py`` does not go through this module, and
this module calls their functions where they fit (``_norm``, ``_embed``,
``_attn_qkv``, the paged kernels and ``write_kv_runs``).

**Window layers** (kind ``"window"``: attention with ``i - j <
cfg.sliding_window``) share the attention mixers' parameter stack
(``params["attn"]``, numbered with them in layer order) and have pools
and a block table of THEIR OWN (``win_pools``, ``win_tables``): the
engine releases a window layer's page once every holder's window has
passed it, while a global layer's page lives as long as its row, so the
two kinds cannot share one table.  The paged kernel visits a window
layer's pages from the one that holds the window's first position
(``ops/paged_attention``, ``window=``).  A layer ropes q and k or not by
``cfg.layer_ropes`` (smallthinker's global layers have no position term),
and an expert layer's router reads the mixer's input where
``cfg.moe_router_input == "attn"``.

A stack may state its window layers' widths apart
(``cfg.window_has_own_widths``: laguna's 64 query heads against the full
layers' 48 on the same KV heads, plain RoPE over the whole head against
YaRN over half of it, ``cfg.window_plain()``): they then have a parameter
stack of their own (``params["window"]``) and each kind its rope tables
(:func:`plain_rope_tables`); either kind may carry a headwise gate.  Such
a stack is TRAINED and not served (``engine/backend.refuse_unserved``).

**The trainer's form** is :func:`hidden_states` itself: packed rows for a
stack of attention kinds alone, the flash kernels by kind (a window layer's
under ``window=``), each run scanning its layers' own parameters, a layer's
halves rematerialised under ``cfg.remat``, the grouped product over the
held experts with its derivative (``moe.grouped_expert_train``).
``transformer.hidden_states`` hands a configuration with ``layer_types``
here and refuses by name the kinds whose backward does not exist
(:func:`refuse_untrainable`).

**Parallel layers** (kind ``"parallel"``: falcon_h1): ``h += attention(a) +
mamba2(a)`` with ONE ``a = norm(h)``.  Such a layer is an attention mixer
and a Mamba-2 mixer as the other kinds run them, each with its number in
its own parameter stack (``Run.first_of_kind`` among ``params["attn"]``,
``Run.first_of_state`` among ``params["mamba"]``), a place in the pool of
whole-context pages AND a state slot; the three programs run both on the
same ``a`` and add the two outputs (region ``areal.parallel`` around the
norm and the sum, ``areal.attn`` and ``areal.ssm`` inside it).  The
published multipliers are facts of the config that every mixer of their
kind obeys (``_scaled``): a branch's input and output factors, the keys'
(``transformer._attn_qkv``), the five of the Mamba-2 in-projection's
segments, the dense MLP's two.

**Differential heads** (``cfg.diff_attention``): adjacent heads pair up;
a pair ``j`` makes two softmax maps ``P1 = softmax(q1 k1^T s)``, ``P2 =
softmax(q2 k2^T s)`` over ONE value ``[v1 | v2]`` and gives
``rmsnorm((P1 - lam P2) V) (1 - lam0)``, ``lam = exp(lq1 . lk1) - exp(lq2
. lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)`` at layer ``l``.  A pair
is ONE cached head of ``2 head_dim`` (``[k1 | k2]``, ``[v1 | v2]``: a
whole lane tile where a head of 64 is half of one), and its two maps are
two QUERY heads of that width, ``[q1 | 0]`` and ``[0 | q2]``: the paged
kernel, ``chunk_attention`` and ``window_attention`` then return ``P1 V``
and ``P2 V`` as they return any two heads' outputs (:func:`_diff_qkv`),
and the weight, the norm and ``W_o`` follow outside (:func:`_diff_out`).
The zeros double the score products and no byte of the cache.

**One written cache layer, many readers** (kinds ``"attention"`` +
``"cross"``): the ONE attention layer of such a stack writes the pool of
whole-context pages (one layer); each cross layer makes queries only and
attends that layer's pages, and in a fill or a decode chunk that layer's
K and V of the chunk's own tokens, which are in hand once: no copy of
either per reading layer.  **The gated memory unit** (kind ``"gmu"``)
caches nothing: ``out = (silu(a W_1) * m) W_2`` with ``m`` the scan output
``y`` of ``cfg.memory_layer`` (the last Mamba-1 layer, before ITS gate),
which every program hands from that layer to the last of them.

**The Mamba-1 mixer** (kind ``"mamba1"``) has three forms over one set of
equations (``[x | z] = a W_in``; ``x = silu(causal depthwise conv)``;
``[d | B | C] = x W_x``; ``dt = softplus(d W_dt + b_dt)``; ``S_t =
exp(dt_t (x) A) S_{t-1} + (dt_t x_t) (x) B_t``, the decay differing by
channel AND by state index; ``y_t = S_t C_t + D x_t``; ``out = (y *
silu(z)) W_out``): whole sequence / fill chunk (:func:`mamba1_chunk`: the
recurrence as a scan over the chunk's positions from a given state and
conv tail) and one decode step over the engine's slots
(:func:`mamba1_step`, by ``ops/ssm.ssm_state_update`` with ``a=``).  Its
state is ``[N, d_inner]`` a sequence and layer like Mamba-2's, its conv
tail holds ``x`` alone.

**The latent mixer** (MLA) has three forms over one set of equations
(``c_q = rmsnorm(a W_qa)``, ``[q_nope | q_rope]_i = c_q W_qb``; ``[c_kv |
k_r] = a W_kva``, ``c_kv = rmsnorm(c_kv)``, ``k_rope = rope(k_r)``, ONE
for all heads; ``k_nope_i = c_kv W_UK,i``, ``v_i = c_kv W_UV,i``; ``s_i =
scale (q_nope_i . k_nope_i + rope(q_rope_i) . k_rope)``; ``out =
concat_i(softmax(s_i) v_i) W_o``).  What is cached is a token's ``[c_kv |
k_rope]`` and nothing a head:

* whole sequence (:func:`hidden_states`): keys and values expanded;
* one decode step: ABSORBED, ``q~_i = q_nope_i W_UK,i^T``, ``s_i = scale
  ([q~_i | q_rope_i] . [c_kv | k_rope])``, ``o_i = (sum p_i c_kv) W_UV,i``:
  the paged kernel reads latent pages as keys and as values, all heads
  sharing the one stream;
* a fill chunk: its own tokens expanded, its paged prefix absorbed, merged
  by the online-softmax partials (the prefix's accumulator goes through
  ``W_UV`` first, which is linear).

The three are the same mathematics (``tests/model/test_latent.py``).

**Under an indexer** (``cfg.is_indexed``: dots3_note's full layers) a query
of a ``"latent"`` layer attends the ``cfg.index_topk`` positions of its
context that ``sum_j w_j relu(q^I_j . k^I)`` scores highest, EXACTLY, and
nothing else, in all three forms (``ops/sparse_attention.py``: a fill and
a decode step over a short table apply the set as a mask in the paged
kernel, a decode step over a long one gathers the chosen entries alone);
each token's index key is cached beside its latent entry (the V side of
the whole-context pool).  **A ``"latent_window"`` layer** is the same
mixer at widths OF ITS OWN (``cfg.window_latent()``: heads, ranks, head
sizes, RoPE base) under ``i - j < cfg.sliding_window``, with a parameter
stack of its own and latent pages in the window pool.  A headwise gate
(:func:`latent_out`) and the latents' rescale (:func:`_lora_rescale`) are
facts of the config (``tests/model/test_sparse.py``).

**The Mamba-2 mixer** has three forms over one set of equations
(``[z | xBC | dt] = a W_in``; ``xBC = silu(causal depthwise conv)``;
``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``; per head ``S_t =
exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``out
= (rmsnorm(y * silu(z)) * w) W_out``; with ``cfg.mamba_n_groups`` = G > 1,
B and C are ``[G, N]``, heads ``[g H/G, (g + 1) H/G)`` read group ``g``'s,
and the norm is over each group's ``d_inner / G`` channels):

* whole sequence / fill chunk (:func:`mamba_chunk`): the chunked SSD
  algorithm in plain ``jnp`` (products inside a chunk of
  ``mamba_chunk_size``, a state pass between chunks), from a given state
  and conv tail to the state and tail after the last valid token, so a
  prompt split into fill chunks carries both across them;
* one decode step (:func:`mamba_step`): the recurrence itself, over the
  engine's state slots, by ``ops/ssm.ssm_state_update``.

State and decay are float32 in every form.  One sequence's state in one
layer is ``[N, H*P]`` (state size x inner width, the inner width along
the lanes: ``ops/ssm.py``) and its conv tail the last ``d_conv - 1``
inputs of the conv.  The engine holds ``ssm [Lm, slots, N, H*P]`` float32
and ``conv [Lm, d_conv - 1, slots, conv_dim]`` in the model's dtype (the
slot axis next to the channels: a ``[.., conv_dim, 3]`` array would pad
its last axis to a lane tile, 43 times its size).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from areal_tpu.engine.sampling import sample_and_advance
from areal_tpu.models import paged
from areal_tpu.models.config import PLAIN_ATTENTION_KINDS, TransformerConfig
from areal_tpu.models import quantize
from areal_tpu.models import moe
from areal_tpu.models import remat as remat_names
from areal_tpu.models.moe import held_moe_mlp, n_pair_counts
from areal_tpu.models.moe import layer_of as _at
from areal_tpu.models.transformer import (
    Params,
    _activation,
    _attn_qkv,
    _embed,
    _final_norm,
    _norm,
    _proj,
    make_attention_mask,
    rope_apply,
    scan_layers,
    uniform_stack as _uniform_stack,
    window_put,
)
from areal_tpu.observability.tracing import region
from areal_tpu.ops import sparse_attention as sparse
from areal_tpu.ops import ssm as ssm_ops

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


class Run(NamedTuple):
    kind: str  # the mixer: one of ``config.LAYER_KINDS``
    mlp: str  # "dense" | "experts"
    first_layer: int  # number of the run's first layer in the stack
    #: its number in its mixer's parameter stack ("attention" and
    #: "window" layers share ``params["attn"]``, in layer order)
    first_of_kind: int
    first_of_mlp: int  # its number among the layers of its MLP kind
    count: int
    rope: bool = True  # whether its attention mixers rope q and k
    #: its number among the layers of its POOL (a window layer's among the
    #: window layers, an attention layer's among those; else first_of_kind)
    first_in_pool: int = 0
    #: inside a PERIOD (:func:`plan_periods`) a run holds every
    #: ``every``-th layer from its first, and its numbers among its kind,
    #: its MLP kind and its pool advance by ``strides`` a layer
    every: int = 1
    strides: Tuple[int, int, int, int] = (1, 1, 1, 1)
    #: a "parallel" layer's number among the MAMBA mixers (its
    #: ``first_of_kind`` is its number among the attention mixers); the
    #: last of ``strides`` is this number's
    first_of_state: int = 0


def _param_kind(kind: str, cfg: TransformerConfig) -> str:
    """Whose parameter stack a layer's mixer lies in ("latent_window"
    layers have one of their own: their widths differ, and so have
    "window" layers at a head count of their own)."""
    if kind == "window" and cfg.window_has_own_widths:
        return kind
    return "attention" if kind in ("window", "parallel") else kind


#: the parameter stack of each mixer kind that has one of its own
PARAM_STACKS = {"attention": "attn"}


def _stack_of(kind: str, cfg: TransformerConfig) -> str:
    """The key of ``params`` under which a kind's mixers are stacked."""
    kind = _param_kind(kind, cfg)
    return PARAM_STACKS.get(kind, kind)


#: the layer kinds whose pages live in the window pool
WINDOW_KINDS = ("window", "latent_window")


def _pool_kind(kind: str) -> str:
    """Whose pool a layer's pages live in (a parallel layer's among the
    attention layers')."""
    return "attention" if kind == "parallel" else kind


def layer_plan(cfg: TransformerConfig) -> Tuple[Run, ...]:
    """``cfg.layer_types`` as runs of one (mixer, MLP, rope) triple, in
    the published order; the first ``cfg.n_dense_layers`` layers have the
    dense MLP."""
    runs, seen = [], {}
    for l, kind in enumerate(cfg.layer_types):
        mlp = "dense" if l < cfg.n_dense_layers else "experts"
        rope = cfg.layer_ropes(l)
        last = runs[-1] if runs else None
        if last and (last.kind, last.mlp, last.rope) == (kind, mlp, rope):
            runs[-1] = last._replace(count=last.count + 1)
        else:
            runs.append(
                Run(
                    kind, mlp, l, seen.get(_param_kind(kind, cfg), 0),
                    seen.get(mlp, 0), 1, rope,
                    seen.get("pool:" + _pool_kind(kind), 0),
                    first_of_state=seen.get("mamba", 0)
                    if kind == "parallel" else 0,
                )
            )
        for name in (_param_kind(kind, cfg), "pool:" + _pool_kind(kind), mlp) + (
            ("mamba",) if kind == "parallel" else ()
        ):
            seen[name] = seen.get(name, 0) + 1
    return tuple(runs)


#: the longest pattern of single layers :func:`plan_periods` looks for
MAX_PERIOD = 4


def plan_periods(cfg: TransformerConfig) -> Tuple[Tuple[Run, ...], ...]:
    """:func:`layer_plan` with each stretch of SINGLE layers that repeats
    a pattern (``[mamba1, window] x 8``: sixteen runs of one layer) folded
    into one PERIOD: a tuple of runs, one a position of the pattern, each
    holding every ``every``-th layer.  A period is one ``lax.scan`` whose
    trip runs one layer of each of its runs in turn, so the program holds
    the pattern once and not once a repetition (a stack that alternates
    its kinds would otherwise be unrolled whole: 32 layer bodies to
    compile, in every fill shape).  Every other run is a period of its
    own, and its scan is what it was."""
    runs = layer_plan(cfg)

    def same(a: Run, b: Run):
        return (a.kind, a.mlp, a.rope, a.count) == (b.kind, b.mlp, b.rope, 1)

    out, i = [], 0
    while i < len(runs):
        for p in range(2, MAX_PERIOD + 1):
            n = 1
            while i + (n + 1) * p <= len(runs) and all(
                same(runs[i + k], runs[i + n * p + k]) for k in range(p)
            ):
                n += 1
            if n > 1 and all(r.count == 1 for r in runs[i : i + p]):
                out.append(
                    tuple(
                        a._replace(
                            count=n, every=p,
                            strides=(
                                b.first_of_kind - a.first_of_kind,
                                b.first_of_mlp - a.first_of_mlp,
                                b.first_in_pool - a.first_in_pool,
                                b.first_of_state - a.first_of_state,
                            ),
                        )
                        for a, b in zip(runs[i : i + p], runs[i + p : i + 2 * p])
                    )
                )
                i += n * p
                break
        else:
            out.append((runs[i],))
            i += 1
    return tuple(out)


#: the mixers that leave nothing for a later POSITION to read: no K or V
#: for a pool, no state slot, no conv tail
KEEP_NOTHING_KINDS = ("cross", "gmu")


def keep_nothing_tail(cfg: TransformerConfig) -> Tuple[Tuple[Run, ...], ...]:
    """The stack's KEEP-NOTHING TAIL: the longest run of trailing periods
    of :func:`plan_periods` every one of whose runs has a mixer of
    ``KEEP_NOTHING_KINDS`` and the dense MLP (an expert layer reports
    every position's routed experts and pair counts).  No later position
    reads what these layers make of a position and the engine keeps none
    of it, so a fill runs them on each row's LAST position alone, the one
    the head reads (``hybrid_fill_chunk``; YOCO's prefill saving, arXiv
    2405.05254).  phi4flash: the cross-decoder, ``[gmu, cross] x 7``;
    empty for a stack that ends in any other kind."""
    periods = plan_periods(cfg)
    n = 0
    for period in reversed(periods):
        if not all(
            run.kind in KEEP_NOTHING_KINDS and run.mlp == "dense"
            for run in period
        ):
            break
        n += 1
    return periods[len(periods) - n :]


def keep_nothing_tail_layers(cfg: TransformerConfig) -> int:
    """Layers in :func:`keep_nothing_tail`."""
    return sum(run.count for period in keep_nothing_tail(cfg) for run in period)


def pool_layer_numbers(cfg: TransformerConfig, kind: str) -> np.ndarray:
    """The numbers, in ``params["attn"]``'s stack, of the layers whose
    pages live in ``kind``'s pool, in the pool's order."""
    return np.array(
        [
            j for run in layer_plan(cfg) if _pool_kind(run.kind) == kind
            for j in range(run.first_of_kind, run.first_of_kind + run.count)
        ],
        np.int32,
    )


def _run_indices(run: Run):
    """``(layer numbers, numbers in the mixer's parameter stack, among
    the MLP kind, in the mixer's pool)`` of a run's layers; of a parallel
    run's also their numbers among the Mamba mixers."""
    firsts = (
        run.first_layer, run.first_of_kind, run.first_of_mlp,
        run.first_in_pool,
    )
    if run.kind == "parallel":
        firsts += (run.first_of_state,)
    if run.every == 1:
        return tuple(jnp.arange(first, first + run.count) for first in firsts)
    return tuple(
        first + stride * jnp.arange(run.count)
        for first, stride in zip(firsts, (run.every,) + run.strides)
    )


def _of_kind(run: Run) -> slice:
    """A run's layers in its mixer's parameter stack."""
    return slice(
        run.first_of_kind, run.first_of_kind + run.count * run.strides[0],
        run.strides[0],
    )


def _of_state(run: Run) -> slice:
    """A run's layers in the Mamba mixers' stack (and among the conv
    tails and state slots' layers)."""
    if run.kind != "parallel":
        return _of_kind(run)
    return slice(
        run.first_of_state, run.first_of_state + run.count * run.strides[3],
        run.strides[3],
    )


def _scan_period(step, carry, period: Tuple[Run, ...], xs_of):
    """``lax.scan`` over a period's repetitions: a trip runs
    ``step(carry, xs, run) -> (carry, ys)`` for one layer of each of the
    period's runs in turn (``xs_of(run)``: that run's scanned inputs).
    Returns ``(carry, [a run's stacked ys, ...])``."""
    if len(period) == 1:
        (run,) = period
        carry, ys = scan_layers(
            lambda c, xs: step(c, xs, run), carry, xs_of(run)
        )
        return carry, [ys]

    def trip(c, xs):
        ys = []
        for run, x in zip(period, xs):
            c, y = step(c, x, run)
            ys.append(y)
        return c, tuple(ys)

    carry, ys = scan_layers(trip, carry, tuple(xs_of(run) for run in period))
    return carry, list(ys)


def _mixer_region(run: Run):
    """The region of a layer's first half (norm, mixer, residual add)."""
    if run.kind in ("mamba", "mamba1"):
        return region("areal.ssm")
    if run.kind in WINDOW_KINDS:
        return region("areal.attn.window")
    if run.kind == "cross":
        return region("areal.attn.cross")
    if run.kind == "gmu":
        return region("areal.gmu")
    if run.kind == "parallel":
        # the shared norm, the two outputs' sum and the residual add; the
        # branches lie in ``areal.attn`` and ``areal.ssm`` inside it
        return region("areal.parallel")
    return region("areal.attn")


def _place_in(run: Run, layer: Optional[int]) -> Optional[int]:
    """Where among a run's layers layer ``layer`` stands, or None."""
    if layer is None:
        return None
    at, left = divmod(layer - run.first_layer, run.every)
    return at if not left and 0 <= at < run.count else None


def _held(run: Run, layer: Optional[int], ys):
    """Layer ``layer``'s entry of what a run's scan stacked (``ys``, a
    tree), or None where the run does not hold that layer."""
    at = _place_in(run, layer)
    return None if at is None else jax.tree.map(lambda a: a[at], ys)


def _scaled(x, m: Optional[float]):
    """``m x`` for a published multiplier ``m`` (None: there is none)."""
    return x if m is None else x * jnp.asarray(m, x.dtype)


def _rope_cfg(cfg: TransformerConfig, run: Run) -> TransformerConfig:
    """``cfg`` as ``_attn_qkv`` is to read it for a run's layers."""
    if cfg.use_rope == run.rope:
        return cfg
    return dataclasses.replace(cfg, use_rope=run.rope)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


#: rms of the random embedding of a TIED head (see :func:`init_params`)
EMBED_RMS = 0.05


def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    """Seeded random weights in ``cfg.dtype``, made where jax's default
    device is (for a server: its chip), kind by kind: a float32 host copy
    of a 5 B-parameter share is 20 GB and most of a minute.

    Matrices are uniform in ``+-1/sqrt(fan_in)``; ``A`` in (-16, -1),
    ``dt_bias`` so that ``dt`` falls in (0.001, 0.1) (the Mamba-2
    initialisation); scales and skips around 1; a group-limited router's
    choice bias uniform in +-0.15, the spread of its sigmoid scores (at
    zero the bias would take no part in any choice).  The embedding of
    a TIED head has rms ``EMBED_RMS``: with a tied head the logit of the
    token a position HOLDS is ``D x rms x (the embedding's share of the
    hidden state) / logits_divisor``, about 8 at granite's sizes against
    0.2 for every other token, so that token repeats with a probability
    of a few percent and a log-probability says something about the
    hidden state.  (At rms 0.29 the repeat took probability 1 - 1e-6 and
    every log-probability read 0 to five places, my chip run, PR 31; at
    the dense family's 1/sqrt(D) the logits are uniform to 0.01.)  An
    untied head is one more matrix (logits of deviation 0.58 over a
    final norm of rms 1), beside an embedding of rms 0.5.  A matrix whose
    product a PUBLISHED MULTIPLIER ``m`` scales (falcon_h1's muP factors:
    ``_wider``) is drawn ``1/m`` wider, so that ``m`` times the product
    has the deviation the product has in a stack without multipliers: a
    trained muP model's matrices are larger by as much, and at the usual
    width its factors (0.0078 on the logits, 0.011 on the MLP's output,
    0.0375 and 0.088 on the branches') leave logits uniform to 0.005 and
    eight layers that move the residual stream by under a hundredth, so
    that no log-probability says anything about a page or a state.  The
    program and the reference apply every multiplier as published either
    way.  A LayerNorm's bias is uniform in +-0.1; the newer kinds' pieces
    are in :func:`_init_newer_kinds`."""
    assert cfg.is_hybrid and (cfg.is_moe or cfg.n_dense_layers == cfg.n_layers)
    dt = jnp.dtype(cfg.dtype)
    L, Le, Ld = cfg.n_layers, cfg.n_expert_layers, cfg.n_dense_layers
    Lm = 0 if cfg.is_mamba1 else cfg.n_mamba_layers
    Ll = cfg.layer_types.count("latent")
    # attention and window layers: one stack, but for window layers at
    # widths of their own (``_init_plain_kinds``)
    La = cfg.n_attn_layers - Ll - cfg.n_latent_window_layers
    if cfg.window_has_own_widths:
        La -= cfg.n_window_layers
    D, E, Eh = cfg.hidden_dim, cfg.n_experts, cfg.n_held_experts
    Fe, Fs = cfg.moe_intermediate_dim, cfg.shared_expert_dim
    Hq, Hkv, hd = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    H, di, cd = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    K = cfg.mamba_d_conv
    keys = iter(jax.random.split(key, 40))
    # the kinds that came after the first keep their own stream, so that
    # a seed's weights of the older kinds are what they were
    more = iter(jax.random.split(jax.random.fold_in(key, 1), 40))

    def mat(n, shape, fan_in, keys=keys):
        return _uniform_stack(next(keys), n, shape, 1.0 / np.sqrt(fan_in), dt)

    def ones(*shape, keys=keys):
        # scales and skips around 1, not AT 1: a scale read from the
        # wrong layer, or left out, has to show against the reference
        return jax.random.uniform(next(keys), shape, F32, 0.75, 1.25).astype(dt)

    mlp: Params = {
        "router": {"w": mat(Le, (D, E), D)},
        "experts": {
            # all three [E_held, F, D]: see moe.dense_expert_compute
            "gate": mat(Le, (Eh, Fe, D), D),
            "up": mat(Le, (Eh, Fe, D), D),
            "down": mat(Le, (Eh, Fe, D), Fe),
        },
    } if Le else {}
    if Fs:
        mlp["shared"] = {
            "gate": {"w": mat(Le, (D, Fs), D)},
            "up": {"w": mat(Le, (D, Fs), D)},
            "down": {"w": mat(Le, (Fs, D), Fs)},
        }
    if cfg.moe_router == "sigmoid_group":
        mlp["router"]["bias"] = jax.random.uniform(
            next(more), (Le, E), F32, -0.15, 0.15
        )
    if Lm:
        u = jax.random.uniform(next(keys), (Lm, H), F32)
        dt0 = jnp.exp(u * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
    embed_rms = (
        EMBED_RMS if cfg.tied_embedding else 0.5 / (cfg.embed_scale or 1.0)
    )
    params: Params = {
        "embed": {
            "weight": _uniform_stack(
                next(keys), 1, (cfg.vocab_size, D), embed_rms * np.sqrt(3.0), dt
            )[0]
        },
        "layers": {
            "attn_norm": {"scale": ones(L, D)},
            "mlp_norm": {"scale": ones(L, D)},
            **({"mlp": mlp} if Le else {}),
        },
    }
    if Lm:
        params["mamba"] = {
            "in_proj": {
                "w": _segments_wider(cfg, mat(
                    Lm, (D, di + cd + H), D * _wider(cfg.ssm_in_scale)
                ))
            },
            "conv": {"w": mat(Lm, (K, cd), K), "b": mat(Lm, (cd,), 16)},
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt),
            "A_log": jnp.log(
                jax.random.uniform(next(keys), (Lm, H), F32, 1.0, 16.0)
            ).astype(dt),
            "D": ones(Lm, H),
            "norm": {"scale": ones(Lm, di)},
            "out_proj": {"w": mat(Lm, (di, D), di * _wider(cfg.ssm_out_scale))},
        }
    if La:
        d_in = D * _wider(cfg.attn_in_scale)
        params["attn"] = {
            "q": {"w": mat(La, (D, Hq * hd), d_in)},
            "k": {"w": mat(La, (D, Hkv * hd), d_in * _wider(cfg.key_scale))},
            "v": {"w": mat(La, (D, Hkv * hd), d_in)},
            "o": {
                "w": mat(La, (Hq * hd, D), Hq * hd * _wider(cfg.attn_out_scale))
            },
        }
    params["final_norm"] = {"scale": ones(D)}
    if Ll:
        params["latent"] = _latent_stack(
            cfg, Ll, partial(mat, keys=more), partial(ones, keys=more)
        )
    if Ld:
        Fd = cfg.intermediate_dim
        m_gate, m_down = cfg.mlp_scales or (None, None)
        params["dense"] = {
            "gate": {"w": mat(Ld, (D, Fd), D * _wider(m_gate), keys=more)},
            "up": {"w": mat(Ld, (D, Fd), D, keys=more)},
            "down": {"w": mat(Ld, (Fd, D), Fd * _wider(m_down), keys=more)},
        }
    if not cfg.tied_embedding:
        params["lm_head"] = {
            "w": _uniform_stack(
                next(more), 1, (D, cfg.vocab_size),
                (cfg.logits_divisor or 1.0) / np.sqrt(D), dt,
            )[0]
        }
    _init_newer_kinds(cfg, params, jax.random.fold_in(key, 2))
    _init_sparse_kinds(cfg, params, jax.random.fold_in(key, 3))
    _init_plain_kinds(cfg, params, jax.random.fold_in(key, 4))
    return params


def _wider(m: Optional[float]) -> float:
    """The factor of a matrix's fan-in under which it is drawn ``1/m``
    wider (:func:`init_params`; 1.0, the same draw, without a multiplier)."""
    return 1.0 if m is None else float(m) ** 2


def _segments_wider(cfg: TransformerConfig, w):
    """A Mamba-2 in-projection ``[.., D, z | x | B | C | dt]`` with each
    segment's columns ``1/m`` wider for the segment's own multiplier."""
    if cfg.ssm_scales is None:
        return w
    return w * jnp.asarray(1.0 / _segment_factors(cfg), w.dtype)


def _init_newer_kinds(cfg: TransformerConfig, params: Params, key):
    """What ``"mamba1"``, ``"cross"`` and ``"gmu"`` layers, differential
    heads, attention biases and a LayerNorm add to :func:`init_params`'
    tree, from a stream of their own (a seed's weights of the older kinds
    stay what they were).  ``A`` uniform in (-16, -1) per (state index,
    channel) and ``dt`` in (0.001, 0.1), as the Mamba-2 mixer's; the
    pairs' four lambda vectors uniform in +-0.3, so that ``lam - lam0``
    spreads by a few tenths."""
    dt = jnp.dtype(cfg.dtype)
    D, di, N = cfg.hidden_dim, cfg.mamba_d_inner, cfg.mamba_d_state
    K, R = cfg.mamba_d_conv, cfg.mamba_dt_rank
    Hq, hd = cfg.n_q_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 64))

    def mat(n, shape, fan_in):
        return _uniform_stack(next(keys), n, shape, 1.0 / np.sqrt(fan_in), dt)

    def around(n, shape, lo, hi):
        return jax.random.uniform(next(keys), (n,) + shape, F32, lo, hi).astype(dt)

    def diff_parts(n):
        return {
            **{
                name: around(n, (hd,), -0.3, 0.3)
                for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
            },
            "subln": {"scale": around(n, (2 * hd,), 0.75, 1.25)},
        }

    if cfg.norm_type == "layer":
        for norm, n in (
            (params["layers"]["attn_norm"], (cfg.n_layers, D)),
            (params["layers"]["mlp_norm"], (cfg.n_layers, D)),
            (params["final_norm"], (D,)),
        ):
            norm["bias"] = around(n[0], n[1:], -0.1, 0.1)
    attn = params.get("attn")
    if attn is not None:
        La = attn["q"]["w"].shape[0]
        if cfg.use_attention_bias:
            for name in ("q", "k", "v", "o"):
                attn[name]["b"] = mat(La, attn[name]["w"].shape[-1:], 64)
        if cfg.diff_attention:
            attn.update(diff_parts(La))
    Lc, Lg = cfg.n_cross_layers, cfg.n_gmu_layers
    if Lc:
        params["cross"] = {
            "q": {"w": mat(Lc, (D, Hq * hd), D)},
            "o": {"w": mat(Lc, (Hq * hd, D), Hq * hd)},
        }
        if cfg.use_attention_bias:
            params["cross"]["q"]["b"] = mat(Lc, (Hq * hd,), 64)
            params["cross"]["o"]["b"] = mat(Lc, (D,), 64)
        if cfg.diff_attention:
            params["cross"].update(diff_parts(Lc))
    if Lg:
        params["gmu"] = {
            "in_proj": {"w": mat(Lg, (D, di), D)},
            "out_proj": {"w": mat(Lg, (di, D), di)},
        }
    if cfg.is_mamba1:
        Lm = cfg.n_mamba_layers
        u = jax.random.uniform(next(keys), (Lm, di), F32)
        dt0 = jnp.exp(u * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
        params["mamba1"] = {
            "in_proj": {"w": mat(Lm, (D, 2 * di), D)},
            "conv": {"w": mat(Lm, (K, di), K), "b": mat(Lm, (di,), 16)},
            "x_proj": {"w": mat(Lm, (di, R + 2 * N), di)},
            # the bias is added in float32, before the softplus
            "dt_proj": {
                "w": mat(Lm, (R, di), R),
                "b": dt0 + jnp.log(-jnp.expm1(-dt0)),
            },
            # [N, d_inner], the state's own layout (the published
            # ``A_log`` is its transpose)
            "A_log": jnp.log(
                jax.random.uniform(next(keys), (Lm, N, di), F32, 1.0, 16.0)
            ),
            "D": around(Lm, (di,), 0.75, 1.25),
            "out_proj": {"w": mat(Lm, (di, D), di)},
        }


def _rescale_wider(cfg: TransformerConfig, rank: int) -> float:
    """The factor of the fan-in of a matrix that reads a RESCALED latent
    (``cfg.mla_lora_rescale``: the latent times ``m = sqrt(hidden /
    rank)``): drawn ``1/m`` wider, as under any published multiplier
    (:func:`init_params`), its product has the deviation it has in a
    stack without the rescale.  (That is the rule's own premise: such a
    matrix is initialised by the HIDDEN size, fan-in ``rank x m^2``.  At
    the plain draw a full layer's scores come out ``sqrt(5 x 10)`` = 7
    times as wide, its softmax sits on a handful of positions, and the
    served log-probabilities stood 0.055-0.077 from the float32
    reference's where the latent cell's stand 0.015: my chip run, PR 49.)"""
    return _wider(np.sqrt(cfg.hidden_dim / rank) if cfg.mla_lora_rescale else None)


def _latent_stack(cfg: TransformerConfig, n: int, mat, ones) -> Params:
    """The latent mixer's matrices over ``n`` layers at ``cfg``'s widths
    (``kv_b`` as its key columns and its value columns apart: the
    absorbed form reads each alone)."""
    D, H, rq, rkv = cfg.hidden_dim, cfg.n_q_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    in_q, in_kv = rq * _rescale_wider(cfg, rq), rkv * _rescale_wider(cfg, rkv)
    return {
        "q_a": {"w": mat(n, (D, rq), D)},
        "q_a_norm": {"scale": ones(n, rq)},
        "q_b": {"w": mat(n, (rq, H * cfg.head_dim), in_q)},
        "kv_a": {"w": mat(n, (D, cfg.kv_latent_dim), D)},
        "kv_a_norm": {"scale": ones(n, rkv)},
        "k_b": {"w": mat(n, (rkv, H * cfg.qk_nope_head_dim), in_kv)},
        "v_b": {"w": mat(n, (rkv, H * cfg.v_head_dim), in_kv)},
        "o": {"w": mat(n, (H * cfg.v_head_dim, D), H * cfg.v_head_dim)},
    }


def _init_sparse_kinds(cfg: TransformerConfig, params: Params, key):
    """What a headwise gate, an indexer and "latent_window" layers add to
    :func:`init_params`' tree, from a stream of their own (a seed's
    weights of the older kinds stay what they were).  The index key's
    LayerNorm has a scale around 1 and a bias in +-0.1."""
    dt = jnp.dtype(cfg.dtype)
    D = cfg.hidden_dim
    keys = iter(jax.random.split(key, 32))

    def mat(n, shape, fan_in):
        return _uniform_stack(next(keys), n, shape, 1.0 / np.sqrt(fan_in), dt)

    def around(n, shape, lo, hi):
        return jax.random.uniform(next(keys), (n,) + shape, F32, lo, hi).astype(dt)

    def ones(n, width):
        return around(n, (width,), 0.75, 1.25)

    Ll, Lw = cfg.layer_types.count("latent"), cfg.n_latent_window_layers
    if Lw:
        wcfg = cfg.window_latent()
        params["latent_window"] = _latent_stack(wcfg, Lw, mat, ones)
        if wcfg.attention_gate:
            params["latent_window"]["gate"] = {
                "w": mat(Lw, (D, wcfg.n_q_heads), D)
            }
    if Ll and cfg.attention_gate:
        params["latent"]["gate"] = {"w": mat(Ll, (D, cfg.n_q_heads), D)}
    if Ll and cfg.is_indexed:
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        params["latent"].update(
            index_q={
                "w": mat(
                    Ll, (cfg.q_lora_rank, Hi * di),
                    cfg.q_lora_rank * _rescale_wider(cfg, cfg.q_lora_rank),
                )
            },
            index_k={"w": mat(Ll, (D, di), D)},
            index_k_norm={
                "scale": ones(Ll, di), "bias": around(Ll, (di,), -0.1, 0.1),
            },
            index_w={"w": mat(Ll, (D, Hi), D)},
        )


def _path_keys(path) -> Tuple[str, ...]:
    """A tree path (``jax.tree_util``'s) as its dictionary keys."""
    return tuple(k.key if hasattr(k, "key") else str(k) for k in path)


def param_pspecs(cfg: TransformerConfig, params: Params) -> Params:
    """PartitionSpecs of :func:`init_params`' tree for the trainer's mesh:
    the held experts' axis over ``expert`` where it divides, every other
    leaf whole on every chip.  A stack stated by kind trains on ONE chip's
    share of a stated deployment (the chips that share a layer hold other
    experts and their own batches; ROADMAP R9 has experts across chips)."""
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf):
        if "experts" in _path_keys(path) and leaf.ndim == 4:
            return P(None, "expert")
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def grad_group(keys: Tuple[str, ...]) -> str:
    """The group a leaf of :func:`init_params`' tree (or of its gradient)
    is counted in, from its path: the trainer's record and the benchmark's
    check read a gradient's norm by these."""
    if keys[0] == "embed":
        return "embed"
    if keys[0] == "lm_head":
        return "head"
    if "gate" in keys and keys[0] in ("attn", "window", "latent", "latent_window"):
        return "gate"
    if keys[0] == "attn":
        return "attention"
    if keys[0] == "layers" and "mlp" in keys:
        return next(k for k in ("router", "experts", "shared") if k in keys)
    if keys[0] == "layers" or keys[0] == "final_norm" or "norm" in keys[-2]:
        return "norms"
    return keys[0]  # "window", "dense", "mamba", "latent", ...


def grad_norms_by_group(grads: Params):
    """``{group: l2 norm}`` of a gradient tree, by :func:`grad_group`."""
    squares = {}
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        group = grad_group(_path_keys(path))
        squares[group] = squares.get(group, 0.0) + jnp.sum(
            jnp.square(g.astype(F32))
        )
    return {group: jnp.sqrt(s) for group, s in squares.items()}


def _init_plain_kinds(cfg: TransformerConfig, params: Params, key):
    """What plain "window" layers at widths of their own and a headwise
    gate on plain attention add to :func:`init_params`' tree, from a
    stream of their own (a seed's weights of the older kinds stay what
    they were)."""
    dt = jnp.dtype(cfg.dtype)
    D, Hkv, hd = cfg.hidden_dim, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 8))

    def mat(n, shape, fan_in):
        return _uniform_stack(next(keys), n, shape, 1.0 / np.sqrt(fan_in), dt)

    if cfg.window_has_own_widths:
        wcfg, Lw = cfg.window_plain(), cfg.n_window_layers
        Hq = wcfg.n_q_heads
        params["window"] = {
            "q": {"w": mat(Lw, (D, Hq * hd), D)},
            "k": {"w": mat(Lw, (D, Hkv * hd), D)},
            "v": {"w": mat(Lw, (D, Hkv * hd), D)},
            "o": {"w": mat(Lw, (Hq * hd, D), Hq * hd)},
        }
        if wcfg.attention_gate:
            params["window"]["gate"] = {"w": mat(Lw, (D, Hq), D)}
    if "attn" in params and cfg.attention_gate:
        La = params["attn"]["q"]["w"].shape[0]
        params["attn"]["gate"] = {"w": mat(La, (D, cfg.n_q_heads), D)}


def state_zeros(cfg: TransformerConfig, slots: int):
    """``(ssm [Lm, slots, N, H*P] float32, conv [Lm, d_conv - 1, slots,
    conv_dim] model dtype)``: the second cache kind, one slot a row."""
    Lm = cfg.n_mamba_layers
    return (
        jnp.zeros((Lm, slots, cfg.mamba_d_state, cfg.mamba_d_inner), F32),
        jnp.zeros(
            (Lm, cfg.mamba_d_conv - 1, slots, cfg.mamba_conv_dim),
            jnp.dtype(cfg.dtype),
        ),
    )


def state_layout_bytes(cfg: TransformerConfig, slots: int) -> int:
    """Bytes :func:`state_zeros` allocates (pure arithmetic)."""
    Lm = cfg.n_mamba_layers
    ssm = Lm * slots * cfg.mamba_d_state * cfg.mamba_d_inner * 4
    conv = (
        Lm * (cfg.mamba_d_conv - 1) * slots * cfg.mamba_conv_dim
        * jnp.dtype(cfg.dtype).itemsize
    )
    return ssm + conv


@partial(jax.jit, donate_argnums=(0, 1))
@region("areal.ssm")
def copy_state_slots(ssm, conv, src: jax.Array, dst: jax.Array):
    """Copy slot ``src[i]`` to slot ``dst[i]`` (every Mamba layer's state
    and conv tail) for each ``i`` with ``dst[i] < slots``: a fill's
    siblings get the prompt's end state, beside ``paged.copy_blocks``.
    One slot's pieces at a time, by ``dynamic_update_slice``: in place,
    no state-sized temporary."""
    Lm, S, N, HP = ssm.shape
    Km1, cd = conv.shape[1], conv.shape[3]

    def put(i, st):
        ssm, conv = st
        s = src[i]
        d = jnp.minimum(dst[i], S - 1)
        keep = dst[i] >= S  # padding: write the target back as it is
        s = jnp.where(keep, d, s)
        a = jax.lax.dynamic_slice(ssm, (0, s, 0, 0), (Lm, 1, N, HP))
        b = jax.lax.dynamic_slice(conv, (0, 0, s, 0), (Lm, Km1, 1, cd))
        return (
            jax.lax.dynamic_update_slice(ssm, a, (0, d, 0, 0)),
            jax.lax.dynamic_update_slice(conv, b, (0, 0, d, 0)),
        )

    return jax.lax.fori_loop(0, src.shape[0], put, (ssm, conv))


@partial(jax.jit, donate_argnums=(2, 3))
@region("areal.ssm")
def copy_state_slots_between(
    ssm_from, conv_from, ssm, conv, src: jax.Array, dst: jax.Array, n
):
    """Copy slot ``src[i]`` of ``(ssm_from, conv_from)`` to slot ``dst[i]``
    of ``(ssm, conv)`` for ``i < n``: between the rows' slots and the
    snapshot slots that keep a finished fill's end state, which are arrays
    of their own (the same layout, fewer slots), so that the step programs
    see the rows' arrays at the shape they had.  As
    :func:`copy_state_slots`: one slot's pieces at a time, in place, no
    state-sized temporary; ``n`` is a number on the device, so one program
    serves every count up to ``src``'s length and copies nothing for the
    rest."""
    Lm, _, N, HP = ssm.shape
    Km1, cd = conv.shape[1], conv.shape[3]

    def put(i, st):
        ssm, conv = st
        s = src[i]
        a = jax.lax.dynamic_slice(ssm_from, (0, s, 0, 0), (Lm, 1, N, HP))
        b = jax.lax.dynamic_slice(conv_from, (0, 0, s, 0), (Lm, Km1, 1, cd))
        return (
            jax.lax.dynamic_update_slice(ssm, a, (0, dst[i], 0, 0)),
            jax.lax.dynamic_update_slice(conv, b, (0, 0, dst[i], 0)),
        )

    return jax.lax.fori_loop(0, n, put, (ssm, conv))


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------


def _split_in_proj(cfg: TransformerConfig, mp: Params, h):
    """``(z [.., d_inner], xBC [.., conv_dim], dt_raw [.., H])``."""
    di, cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    zxd = _proj(mp["in_proj"], _scaled(h, cfg.ssm_in_scale))
    if cfg.ssm_scales is not None:
        zxd = zxd * jnp.asarray(_segment_factors(cfg), zxd.dtype)
    return zxd[..., :di], zxd[..., di : di + cd], zxd[..., di + cd :]


def _segment_factors(cfg: TransformerConfig) -> np.ndarray:
    """``cfg.ssm_scales`` a column of the Mamba-2 in-projection's output
    ``[z | x | B | C | dt]``: one factor a segment."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_inner, cfg.mamba_d_inner, gn, gn, cfg.mamba_n_heads)
    return np.repeat(np.asarray(cfg.ssm_scales, np.float32), widths)


def _split_conv_out(cfg: TransformerConfig, xbc):
    """``(x [.., d_inner], B [.., N], C [.., N])`` with one group (B and
    C are shared by all heads), else B and C ``[.., G, N]``."""
    di, N, G = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_n_groups
    if G == 1:
        return xbc[..., :di], xbc[..., di : di + N], xbc[..., di + N :]
    bc = xbc[..., di:].reshape(xbc.shape[:-1] + (2, G, N))
    return xbc[..., :di], bc[..., 0, :, :], bc[..., 1, :, :]


def _dt_and_a(mp: Params, dt_raw):
    dt = jax.nn.softplus(dt_raw.astype(F32) + mp["dt_bias"].astype(F32))
    return dt, -jnp.exp(mp["A_log"].astype(F32))


def _mamba_out(cfg: TransformerConfig, mp: Params, y, x, z):
    """``y`` [.., d_inner] float32 (without the skip) -> the mixer's
    output: skip ``D x``, gate BEFORE the norm, norm over all of
    ``d_inner`` (over each group's channels where there are groups),
    output projection."""
    P, G = cfg.mamba_head_dim, cfg.mamba_n_groups
    y = y + jnp.repeat(mp["D"].astype(F32), P) * x.astype(F32)
    y = y * jax.nn.silu(z.astype(F32))
    if G == 1:
        y = _norm(y, mp["norm"], cfg).astype(z.dtype)
    else:
        grouped = {"scale": mp["norm"]["scale"].reshape(G, -1)}
        y = _norm(y.reshape(y.shape[:-1] + (G, -1)), grouped, cfg).reshape(
            y.shape
        ).astype(z.dtype)
    return _scaled(_proj(mp["out_proj"], y), cfg.ssm_out_scale)


def causal_conv(xbc, tail, w, b, n_valid):
    """Depthwise causal conv of width ``K`` over ``xbc`` [B, T, cd], the
    ``K - 1`` inputs before it being ``tail`` [B, K-1, cd]; silu.
    Returns ``(out [B, T, cd], new tail)``: the last ``K - 1`` inputs up
    to each row's ``n_valid``-th."""
    K = w.shape[0]
    T = xbc.shape[1]
    xp = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    acc = b.astype(F32)
    for k in range(K):
        acc = acc + w[k].astype(F32) * xp[:, k : k + T].astype(F32)
    new_tail = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, K - 1, axis=0)
    )(xp, n_valid)
    return jax.nn.silu(acc).astype(xbc.dtype), new_tail


def ssd_chunked(x, dt, a_neg, bm, cm, s0, chunk: int):
    """The SSD recurrence over a whole window, chunk by chunk.

    ``x`` [B, T, H, P], ``dt`` [B, T, H] (0 where a position is not
    valid: no decay, no input), ``a_neg`` [H] (< 0), ``bm`` / ``cm``
    [B, T, N] (or [B, T, G, N]: heads ``[g H/G, (g + 1) H/G)`` read group
    ``g``'s), ``s0`` [B, N, H, P]; all float32.  Returns ``(y [B, T, H,
    P], state after the last position)``.  Inside a chunk of ``chunk``
    positions: ``y_i = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j
    x_j`` with ``cum`` the running sum of ``dt A``; between chunks the
    state decays by the chunk's total and takes the chunk's inputs."""
    B, T, H, P = x.shape
    Q = min(chunk, T)
    pad = (-T) % Q
    if pad:
        x, dt, bm, cm = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, bm, cm)
        )
    nc = (T + pad) // Q
    x = x.reshape(B, nc, Q, H, P)
    dt = dt.reshape(B, nc, Q, H)
    if bm.ndim == 4:
        return _ssd_grouped(
            x, dt, a_neg, bm.reshape((B, nc, Q) + bm.shape[2:]),
            cm.reshape((B, nc, Q) + cm.shape[2:]), s0, T,
        )
    bm = bm.reshape(B, nc, Q, -1)
    cm = cm.reshape(B, nc, Q, -1)
    cum = jnp.cumsum(dt * a_neg, axis=2)  # [B, nc, Q, H], inclusive
    dtx = dt[..., None] * x  # [B, nc, Q, H, P]
    ein = partial(jnp.einsum, precision=HIGHEST)
    # inside a chunk
    g = ein("bcin,bcjn->bcij", cm, bm)  # [B, nc, Qi, Qj]
    cum_h = cum.swapaxes(2, 3)  # [B, nc, H, Q]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(
        jnp.where(causal, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf)
    )  # [B, nc, H, Qi, Qj]
    y = ein("bchij,bcjhp->bcihp", g[:, :, None] * decay, dtx)
    # what each chunk's inputs leave at its end, and its total decay
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [B, nc, Q, H]
    s_in = ein("bcjn,bcjhp->bcnhp", bm, to_end[..., None] * dtx)
    total = jnp.exp(cum[:, :, -1, :])  # [B, nc, H]

    def step(s, inp):
        s_c, dec = inp
        return s * dec[:, None, :, None] + s_c, s

    s_end, s_before = jax.lax.scan(
        step, s0, (s_in.swapaxes(0, 1), total.swapaxes(0, 1))
    )  # s_before [nc, B, N, H, P]: the state each chunk starts from
    y = y + ein(
        "bcin,cbnhp->bcihp", cm, s_before
    ) * jnp.exp(cum)[..., None]
    return y.reshape(B, nc * Q, H, P)[:, :T], s_end


def _ssd_grouped(x, dt, a_neg, bm, cm, s0, T: int):
    """:func:`ssd_chunked`'s products with B and C by GROUP, on its
    chunked operands: ``x`` [B, nc, Q, H, P], ``dt`` [B, nc, Q, H], ``bm``
    / ``cm`` [B, nc, Q, G, N], ``s0`` [B, N, H, P].  A head axis is split
    ``[G, H/G]`` where it meets B or C, and nothing else differs."""
    B, nc, Q, H, P = x.shape
    G, N = bm.shape[3:]
    cum = jnp.cumsum(dt * a_neg, axis=2)  # [B, nc, Q, H], inclusive
    dtx = dt[..., None] * x  # [B, nc, Q, H, P]
    ein = partial(jnp.einsum, precision=HIGHEST)
    grouped = (B, nc, Q, G, H // G, P)
    # inside a chunk
    g = ein("bcign,bcjgn->bcgij", cm, bm)  # [B, nc, G, Qi, Qj]
    cum_h = cum.swapaxes(2, 3)  # [B, nc, H, Q]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(
        jnp.where(causal, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf)
    )  # [B, nc, H, Qi, Qj]
    y = ein(
        "bchij,bcjhp->bcihp", jnp.repeat(g, H // G, axis=2) * decay, dtx
    )
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # [B, nc, Q, H]
    s_in = ein(
        "bcjgn,bcjghp->bcnghp", bm, (to_end[..., None] * dtx).reshape(grouped)
    ).reshape(B, nc, N, H, P)
    total = jnp.exp(cum[:, :, -1, :])  # [B, nc, H]

    def step(s, inp):
        s_c, dec = inp
        return s * dec[:, None, :, None] + s_c, s

    s_end, s_before = jax.lax.scan(
        step, s0, (s_in.swapaxes(0, 1), total.swapaxes(0, 1))
    )  # s_before [nc, B, N, H, P]: the state each chunk starts from
    y = y + ein(
        "bcign,cbnghp->bcighp", cm, s_before.reshape(nc, B, N, G, H // G, P)
    ).reshape(B, nc, Q, H, P) * jnp.exp(cum)[..., None]
    return y.reshape(B, nc * Q, H, P)[:, :T], s_end


def mamba_chunk(cfg: TransformerConfig, mp: Params, h, n_valid, s0, tail0):
    """The mixer over a window ``h`` [B, T, D] whose first ``n_valid[b]``
    positions are real, from state ``s0`` [B, N, H*P] float32 and conv
    tail ``tail0`` [B, K-1, conv_dim].  Returns ``(out [B, T, D], state,
    tail)`` after each row's last real position."""
    B, T, _ = h.shape
    H, P, N = cfg.mamba_n_heads, cfg.mamba_head_dim, cfg.mamba_d_state
    z, xbc, dt_raw = _split_in_proj(cfg, mp, h)
    xbc, tail = causal_conv(
        xbc, tail0, mp["conv"]["w"], mp["conv"]["b"], n_valid
    )
    x, bm, cm = _split_conv_out(cfg, xbc)
    dt, a_neg = _dt_and_a(mp, dt_raw)
    valid = jnp.arange(T)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    y, s = ssd_chunked(
        x.astype(F32).reshape(B, T, H, P), dt, a_neg,
        bm.astype(F32), cm.astype(F32), s0.reshape(B, N, H, P),
        cfg.mamba_chunk_size,
    )
    out = _mamba_out(cfg, mp, y.reshape(B, T, H * P), x, z)
    return out, s.reshape(B, N, H * P), tail


def _conv_step(mp: Params, new, conv, j, live):
    """One position of the causal conv over every slot: ``new`` [S, cd]
    behind layer ``j``'s tails of ``conv``.  Returns ``(the conv's output
    before its silu, float32; conv with the live slots' tails moved on)``."""
    tail = jax.lax.dynamic_index_in_dim(conv, j, 0, keepdims=False)
    xp = jnp.concatenate([tail, new[None].astype(tail.dtype)], axis=0)
    w = mp["conv"]["w"].astype(F32)  # [K, cd]
    acc = mp["conv"]["b"].astype(F32) + jnp.sum(
        w[:, None, :] * xp.astype(F32), axis=0
    )
    conv = jax.lax.dynamic_update_index_in_dim(
        conv, jnp.where(live[None, :, None], xp[1:], tail), j, 0
    )
    return acc, conv


def mamba_step(
    cfg: TransformerConfig, mp: Params, h, ssm, conv, j, live, use_kernel
):
    """The mixer for ONE new position of every slot: ``h`` [S, 1, D],
    ``ssm`` / ``conv`` the engine's stacked state, ``j`` the layer's
    number among the Mamba layers, ``live`` [S] the slots that take the
    step (the others keep state and tail).  Returns ``(out [S, 1, D],
    ssm, conv)``."""
    P = cfg.mamba_head_dim
    z, xbc, dt_raw = _split_in_proj(cfg, mp, h[:, 0])
    acc, conv = _conv_step(mp, xbc, conv, j, live)
    x, bm, cm = _split_conv_out(cfg, jax.nn.silu(acc).astype(h.dtype))
    dt, a_neg = _dt_and_a(mp, dt_raw)  # [S, H]
    decay = jnp.repeat(jnp.exp(dt * a_neg), P, axis=-1)
    dtx = jnp.repeat(dt, P, axis=-1) * x.astype(F32)
    args = (ssm, j, decay, dtx, bm.astype(F32), cm.astype(F32), live)
    if use_kernel:
        y, ssm = ssm_ops.ssm_state_update(
            *args, interpret=paged.kernel_interpret()
        )
        y = jnp.where(live[:, None], y, 0.0)  # a dead slot's is not written
    else:
        y, ssm = ssm_ops.ssm_state_update_reference(*args)
    return _mamba_out(cfg, mp, y, x, z)[:, None], ssm, conv


# ---------------------------------------------------------------------------
# the Mamba-1 mixer
# ---------------------------------------------------------------------------


def _m1_in(cfg: TransformerConfig, mp: Params, h):
    """``(x [.., d_inner], z [.., d_inner])``."""
    xz = _proj(mp["in_proj"], h)
    return xz[..., : cfg.mamba_d_inner], xz[..., cfg.mamba_d_inner :]


def _m1_dt_b_c(cfg: TransformerConfig, mp: Params, x):
    """``(dt [.., d_inner], B [.., N], C [.., N])`` of the conv's output
    ``x``, float32; ``dt`` after its bias and the softplus."""
    R, N = cfg.mamba_dt_rank, cfg.mamba_d_state
    dbc = _proj(mp["x_proj"], x)
    dt_raw = dbc[..., :R] @ quantize.leaf_weight(mp["dt_proj"], x.dtype)
    dt = jax.nn.softplus(dt_raw.astype(F32) + mp["dt_proj"]["b"].astype(F32))
    return dt, dbc[..., R : R + N].astype(F32), dbc[..., R + N :].astype(F32)


def _m1_out(mp: Params, y, x, z):
    """``y`` [.., d_inner] float32 (without the skip) -> ``(the mixer's
    output, y + D x in the model's dtype)``: the second is what a gated
    memory unit reads, the scan's output before this layer's gate."""
    y = (y + mp["D"].astype(F32) * x.astype(F32)).astype(z.dtype)
    gated = y.astype(F32) * jax.nn.silu(z.astype(F32))
    return _proj(mp["out_proj"], gated.astype(z.dtype)), y


#: positions a trip of the fill scan's loop takes (``lax.scan``'s unroll)
M1_SCAN_UNROLL = 4


def selective_scan(x, dt, a_neg, bm, cm, s0):
    """The Mamba-1 recurrence over a window, position by position.

    ``x`` / ``dt`` [B, T, C] (``dt`` 0 where a position is not valid: no
    decay, no input), ``a_neg`` [N, C] (< 0), ``bm`` / ``cm`` [B, T, N],
    ``s0`` [B, N, C]; all float32.  Returns ``(y [B, T, C], state after
    the last position)``.  The decay ``exp(dt_t[c] a[n, c])`` is a tile a
    position, so there is no product form over a chunk as Mamba-2's SSD
    has; the loop carries one row's ``[N, C]`` state and nothing a
    position wide."""

    def step(s, inp):
        dt_t, dtx_t, b_t, c_t = inp
        s = s * jnp.exp(dt_t[:, None, :] * a_neg) + (
            b_t[:, :, None] * dtx_t[:, None, :]
        )
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    s, y = jax.lax.scan(
        step, s0,
        tuple(t.swapaxes(0, 1) for t in (dt, dt * x, bm, cm)),
        unroll=M1_SCAN_UNROLL,
    )
    return y.swapaxes(0, 1), s


def mamba1_chunk(cfg: TransformerConfig, mp: Params, h, n_valid, s0, tail0):
    """The Mamba-1 mixer over a window ``h`` [B, T, D] whose first
    ``n_valid[b]`` positions are real, from state ``s0`` [B, N, d_inner]
    float32 and conv tail ``tail0`` [B, K-1, d_inner].  Returns ``(out [B,
    T, D], state, tail, y [B, T, d_inner])`` after each row's last real
    position (``y``: see :func:`_m1_out`)."""
    T = h.shape[1]
    x, z = _m1_in(cfg, mp, h)
    x, tail = causal_conv(x, tail0, mp["conv"]["w"], mp["conv"]["b"], n_valid)
    dt, bm, cm = _m1_dt_b_c(cfg, mp, x)
    valid = jnp.arange(T)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    y, s = selective_scan(
        x.astype(F32), dt, -jnp.exp(mp["A_log"].astype(F32)), bm, cm, s0
    )
    out, y = _m1_out(mp, y, x, z)
    return out, s, tail, y


def mamba1_step(
    cfg: TransformerConfig, mp: Params, h, ssm, conv, j, live, use_kernel
):
    """The Mamba-1 mixer for ONE new position of every slot
    (:func:`mamba_step`'s contract).  Returns ``(out [S, 1, D], ssm, conv,
    y [S, 1, d_inner])``."""
    x, z = _m1_in(cfg, mp, h[:, 0])
    acc, conv = _conv_step(mp, x, conv, j, live)
    x = jax.nn.silu(acc).astype(h.dtype)
    dt, bm, cm = _m1_dt_b_c(cfg, mp, x)
    args = (ssm, j, dt, dt * x.astype(F32), bm, cm, live)
    a_neg = -jnp.exp(mp["A_log"].astype(F32))
    if use_kernel:
        y, ssm = ssm_ops.ssm_state_update(
            *args, a=a_neg, interpret=paged.kernel_interpret()
        )
        y = jnp.where(live[:, None], y, 0.0)  # a dead slot's is not written
    else:
        y, ssm = ssm_ops.ssm_state_update_reference(*args, a=a_neg)
    out, y = _m1_out(mp, y, x, z)
    return out[:, None], ssm, conv, y[:, None]


def gmu(gp: Params, a, mem):
    """The gated memory unit: ``(silu(a W_1) * mem) W_2``."""
    gate = jax.nn.silu(_proj(gp["in_proj"], a).astype(F32))
    return _proj(gp["out_proj"], (gate * mem.astype(F32)).astype(a.dtype))


# ---------------------------------------------------------------------------
# attention heads as the caches hold them: plain, or differential pairs
# ---------------------------------------------------------------------------


def _heads_q(cfg: TransformerConfig, ap: Params, h, positions, run: Run):
    """A layer's queries ``[B, T, Hq, pool_head_dim]``: a differential
    pair's two as ``[q1 | 0]`` and ``[0 | q2]`` (module docstring)."""
    B, T, _ = h.shape
    h = _scaled(h, cfg.attn_in_scale)
    if not cfg.diff_attention:
        return _attn_qkv(
            _rope_cfg(cfg, run), {"attn": ap}, h, positions, None
        )[0]
    assert not cfg.layer_ropes(run.first_layer), "roped differential heads"
    q = _proj(ap["q"], h).reshape(B, T, cfg.n_q_heads // 2, 2, cfg.head_dim)
    zeros = jnp.zeros_like(q[..., 0, :])
    return jnp.stack(
        [
            jnp.concatenate([q[..., 0, :], zeros], axis=-1),
            jnp.concatenate([zeros, q[..., 1, :]], axis=-1),
        ],
        axis=3,
    ).reshape(B, T, cfg.n_q_heads, 2 * cfg.head_dim)


@region("areal.attn")
def plain_rope_tables(cfg: TransformerConfig, positions):
    """``(cos, sin)`` [B, T, 1, rot / 2] float32 of a plain attention
    kind as ``cfg`` states it (``cfg.window_plain()`` for a window layer
    at widths of its own): over the leading ``cfg.rope_partial_dim``
    columns of a head (the whole head at 0), at :func:`rope_inv_freq`'s
    frequencies (YaRN's blend where the kind has a factor), cos and sin
    times YaRN's attention factor ``0.1 mscale ln(factor) + 1``."""
    rot = cfg.rope_partial_dim or cfg.head_dim
    angles = positions[..., None].astype(F32) * jnp.asarray(
        rope_inv_freq(cfg, rot)
    )
    m = 1.0
    if cfg.rope_yarn_factor:
        m = yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale)
    return (
        (jnp.cos(angles) * m)[:, :, None, :],
        (jnp.sin(angles) * m)[:, :, None, :],
    )


def _rope_leading(x, rope_cs):
    """``x`` [.., head_dim] with its leading ``2 x`` the tables' width
    columns rotated and the rest as they were."""
    rot = 2 * rope_cs[0].shape[-1]
    if rot == x.shape[-1]:
        return rope_apply(x, *rope_cs)
    return jnp.concatenate(
        [rope_apply(x[..., :rot], *rope_cs), x[..., rot:]], axis=-1
    )


def _heads_qkv(
    cfg: TransformerConfig, ap: Params, h, positions, run: Run, rope_cs=None
):
    """``(q, k, v)`` of an attention or window layer, k and v ``[B, T,
    pool_kv_heads, pool_head_dim]`` as a page holds them (a differential
    pair's ``[k1 | k2]`` and ``[v1 | v2]`` are adjacent heads' columns: a
    reshape).  ``rope_cs``: the kind's own tables
    (:func:`plain_rope_tables`, made once a program), where the stack
    states a rope rule by kind; ``cfg`` is then the kind's."""
    if rope_cs is not None:
        q, k, v = _attn_qkv(
            dataclasses.replace(cfg, use_rope=False), {"attn": ap},
            _scaled(h, cfg.attn_in_scale), positions, None,
        )
        if run.rope:
            q, k = _rope_leading(q, rope_cs), _rope_leading(k, rope_cs)
        return q, k, v
    if not cfg.diff_attention:
        return _attn_qkv(
            _rope_cfg(cfg, run), {"attn": ap}, _scaled(h, cfg.attn_in_scale),
            positions, None,
        )
    B, T, _ = h.shape
    shape = (B, T, cfg.pool_kv_heads, cfg.pool_head_dim)
    hs = _scaled(h, cfg.attn_in_scale)
    return (
        _heads_q(cfg, ap, h, positions, run),
        _proj(ap["k"], hs).reshape(shape),
        _proj(ap["v"], hs).reshape(shape),
    )


def _attn_dtype(cfg: TransformerConfig, dtype):
    """What the attention functions hand to :func:`_heads_out`: a pair's
    two outputs are subtracted, so they stay float32 until they are."""
    return F32 if cfg.diff_attention else dtype


def _heads_out(cfg: TransformerConfig, ap: Params, l, attn, dtype, a=None):
    """Attention's output ``[B, T, Hq * pool_head_dim]`` through the
    pairs' difference, weight and norm (differential heads), or the
    headwise gate of the layer's normed input ``a`` where the kind has
    one (:func:`latent_out`'s rule), and ``W_o``."""
    if not cfg.diff_attention:
        if "gate" in ap:
            return _scaled(latent_out(cfg, ap, a, attn), cfg.attn_out_scale)
        return _scaled(_proj(ap["o"], attn), cfg.attn_out_scale)
    B, T, _ = attn.shape
    o = attn.reshape(B, T, cfg.n_q_heads // 2, 2, 2 * cfg.head_dim).astype(F32)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, F32))

    def dot(a, b):
        return jnp.sum(ap[a].astype(F32) * ap[b].astype(F32))

    lam = (
        jnp.exp(dot("lambda_q1", "lambda_k1"))
        - jnp.exp(dot("lambda_q2", "lambda_k2"))
        + lam0
    )
    d = o[..., 0, :] - lam * o[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + cfg.norm_eps)
    d = d * ap["subln"]["scale"].astype(F32) * (1.0 - lam0)
    return _scaled(
        _proj(ap["o"], d.astype(dtype).reshape(B, T, -1)), cfg.attn_out_scale
    )


# ---------------------------------------------------------------------------
# residual, softmax scale, rotary tables
# ---------------------------------------------------------------------------


def _res(cfg: TransformerConfig, x, branch):
    if cfg.residual_scale is None:
        return x + branch
    return x + jnp.asarray(cfg.residual_scale, x.dtype) * branch


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def _attn_scale(cfg: TransformerConfig) -> float:
    """The softmax scale: the stated one, else ``1/sqrt(head_dim)``, times
    the square of YaRN's ``mscale_all_dim`` factor where that is set (as
    the published ``DeepseekV3Attention``: 192^-0.5 x 1.4159^2 = 0.1447
    at factor 64)."""
    if cfg.attention_scale is not None:
        return cfg.attention_scale
    scale = 1.0 / np.sqrt(cfg.head_dim)
    if cfg.rope_yarn_factor and cfg.rope_yarn_mscale_all_dim:
        m = yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale_all_dim)
        scale = scale * m * m
    return float(scale)


def rope_inv_freq(cfg: TransformerConfig, dim: int) -> np.ndarray:
    """``[dim / 2]`` float32 rotary frequencies: ``base^(-2j/dim)``, and
    under YaRN (as ``DeepseekV3YarnRotaryEmbedding``) a blend of those and
    the same divided by ``factor``, by a linear ramp between the
    correction dims of ``beta_fast`` and ``beta_slow`` at the original
    context: dims that turn fast keep their frequency, slow ones are
    stretched."""
    j = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / cfg.rotary_base**j
    if not cfg.rope_yarn_factor:
        return extra.astype(np.float32)
    inter = extra / cfg.rope_yarn_factor

    def correction_dim(n_rot):
        return (
            dim * np.log(cfg.rope_yarn_original_max / (n_rot * 2 * np.pi))
        ) / (2 * np.log(cfg.rotary_base))

    low = max(int(np.floor(correction_dim(cfg.rope_yarn_beta_fast))), 0)
    high = min(int(np.ceil(correction_dim(cfg.rope_yarn_beta_slow))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


@region("areal.attn")
def latent_rope_tables(cfg: TransformerConfig, positions):
    """``(cos, sin)`` [B, T, 1, rope/2] float32 for the rope parts of a
    latent layer's queries and key, times YaRN's ``mscale /
    mscale_all_dim`` ratio (1 where both are set alike)."""
    freqs = jnp.asarray(rope_inv_freq(cfg, cfg.qk_rope_head_dim))
    angles = positions[..., None].astype(F32) * freqs
    m = 1.0
    if cfg.rope_yarn_factor:
        m = yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscale) / yarn_mscale(
            cfg.rope_yarn_factor, cfg.rope_yarn_mscale_all_dim
        )
    return (
        (jnp.cos(angles) * m)[:, :, None, :],
        (jnp.sin(angles) * m)[:, :, None, :],
    )


# ---------------------------------------------------------------------------
# the latent mixer's pieces
# ---------------------------------------------------------------------------


def _w3(p, dtype, heads: int):
    """A projection ``[rank, heads * width]`` as ``[rank, heads, width]``."""
    w = quantize.leaf_weight(p, dtype)
    return w.reshape(w.shape[0], heads, -1)


def _lora_rescale(cfg: TransformerConfig, x, rank: int):
    """A normed latent times ``sqrt(hidden / rank)`` where the config
    says so (``cfg.mla_lora_rescale``)."""
    if not cfg.mla_lora_rescale:
        return x
    return x * jnp.asarray(np.sqrt(cfg.hidden_dim / rank), x.dtype)


def latent_cq(cfg: TransformerConfig, ap: Params, h):
    """The query latent ``c_q`` [B, T, q_lora_rank] (normed, rescaled)."""
    return _lora_rescale(
        cfg, _norm(_proj(ap["q_a"], h), ap["q_a_norm"], cfg), cfg.q_lora_rank
    )


def latent_q(cfg: TransformerConfig, ap: Params, h, rope_cs, c_q=None):
    """``(q_nope [B, T, H, nope], q_rope [B, T, H, rope])``, the rope part
    roped (``c_q``: :func:`latent_cq` of ``h`` where the caller has it)."""
    B, T, _ = h.shape
    if c_q is None:
        c_q = latent_cq(cfg, ap, h)
    q = _proj(ap["q_b"], c_q).reshape(B, T, cfg.n_q_heads, cfg.head_dim)
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], rope_apply(q[..., nope:], *rope_cs)


def latent_kv(cfg: TransformerConfig, ap: Params, h, rope_cs):
    """What a token leaves in the cache: ``(c_kv [B, T, rank]`` after its
    norm, ``k_rope [B, T, rope]`` roped, ONE for all heads)``."""
    ckr = _proj(ap["kv_a"], h)
    r = cfg.kv_lora_rank
    c_kv = _lora_rescale(cfg, _norm(ckr[..., :r], ap["kv_a_norm"], cfg), r)
    k_rope = rope_apply(ckr[..., None, r:], *rope_cs)[..., 0, :]
    return c_kv, k_rope


def latent_out(cfg: TransformerConfig, ap: Params, a, attn):
    """Attention's output ``[B, T, H * v_head_dim]`` through the headwise
    gate (``sigmoid(a W_g)``, one a head, where the config has it) and
    ``W_o``."""
    if cfg.attention_gate:
        B, T, _ = attn.shape
        g = jax.nn.sigmoid(_proj(ap["gate"], a).astype(F32))
        attn = (
            attn.reshape(B, T, cfg.n_q_heads, -1).astype(F32) * g[..., None]
        ).astype(attn.dtype).reshape(B, T, -1)
    return _proj(ap["o"], attn)


def index_qkw(cfg: TransformerConfig, ap: Params, c_q, a, rope_cs):
    """The indexer's pieces of positions at hand: ``(q [B, T, Hi, di]``
    from the query latent, ``k [B, T, di]`` = LayerNorm of a projection of
    the layer's input, ONE a token and what the index pool keeps, both
    roped on their first ``qk_rope_head_dim`` columns; ``w [B, T, Hi]``
    float32, the heads' weights times ``di^-0.5 Hi^-0.5)``."""
    B, T, _ = a.shape
    Hi, di, r = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = _proj(ap["index_q"], c_q).reshape(B, T, Hi, di)
    q = jnp.concatenate([rope_apply(q[..., :r], *rope_cs), q[..., r:]], axis=-1)
    k = _proj(ap["index_k"], a)
    k32 = k.astype(F32)
    k32 = k32 - jnp.mean(k32, axis=-1, keepdims=True)
    k32 = k32 * jax.lax.rsqrt(
        jnp.mean(k32 * k32, axis=-1, keepdims=True) + cfg.norm_eps
    )
    k = (
        k32 * ap["index_k_norm"]["scale"].astype(F32)
        + ap["index_k_norm"]["bias"].astype(F32)
    ).astype(k.dtype)[:, :, None, :]
    k = jnp.concatenate(
        [rope_apply(k[..., :r], *rope_cs), k[..., r:]], axis=-1
    )[:, :, 0]
    w = _proj(ap["index_w"], a).astype(F32) * float(di**-0.5 * Hi**-0.5)
    return q, k, w


def latent_entry(cfg: TransformerConfig, c_kv, k_rope):
    """``[c_kv | k_rope | 0]``, a page's row (``paged.latent_page_width``)."""
    pad = paged.latent_page_width(cfg) - cfg.kv_latent_dim
    zeros = jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)
    return jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype), zeros], axis=-1)


def latent_expand(cfg: TransformerConfig, ap: Params, c_kv, k_rope):
    """Per-head keys ``[k_nope_i | k_rope]`` [B, T, H, head_dim] and values
    [B, T, H, v_head_dim] of tokens whose latent is at hand."""
    H = cfg.n_q_heads
    k_nope = jnp.einsum("btc,chn->bthn", c_kv, _w3(ap["k_b"], c_kv.dtype, H))
    v = jnp.einsum("btc,chv->bthv", c_kv, _w3(ap["v_b"], c_kv.dtype, H))
    k_r = jnp.broadcast_to(
        k_rope[:, :, None, :].astype(k_nope.dtype),
        k_nope.shape[:-1] + k_rope.shape[-1:],
    )
    return jnp.concatenate([k_nope, k_r], axis=-1), v


def latent_absorbed_q(cfg: TransformerConfig, ap: Params, q_nope, q_rope):
    """``[q~_i | q_rope_i | 0]`` [B, T, H, page width] with ``q~_i =
    q_nope_i W_UK,i^T``: a query against latent entries themselves."""
    q_lat = jnp.einsum(
        "bthn,chn->bthc", q_nope, _w3(ap["k_b"], q_nope.dtype, cfg.n_q_heads)
    )
    return latent_entry(cfg, q_lat, q_rope)


def latent_values_out(cfg: TransformerConfig, ap: Params, o_lat, dtype=None):
    """``o~_i W_UV,i`` [.., H, v_head_dim] of ``o_lat`` [.., H, rank]
    (attention's output, or an unnormalised accumulator, in latent
    space: the map is linear).  Comes out in ``dtype`` (the input's)."""
    w = _w3(ap["v_b"], o_lat.dtype, cfg.n_q_heads)
    return jnp.einsum(
        "...hc,chv->...hv", o_lat, w, preferred_element_type=dtype
    )


# ---------------------------------------------------------------------------
# shared pieces of the three programs
# ---------------------------------------------------------------------------


@region("areal.mlp")
def _mlp_half(
    cfg: TransformerConfig, params: Params, run: Run, l, e, x, valid, a=None,
    lp: Optional[Params] = None,
):
    """The second half of layer ``l`` (number ``e`` among its MLP kind);
    ``a``: the mixer's input, which the router reads where
    ``cfg.moe_router_input == "attn"``; ``lp``: the layer's OWN parameters
    where the caller scans them (``{"mlp_norm", "mlp"}``: see
    :func:`_run_params`), else they are cut out of ``params``' stacks
    here.  Returns ``(x, pairs, routed [B, T, K], extra rounds)``, the
    last three None after a dense MLP and the last one wherever the
    experts took the product over every held one: see
    ``moe.held_moe_mlp``."""
    h = _norm(
        x, lp["mlp_norm"] if lp else _at(params["layers"]["mlp_norm"], l), cfg
    )
    if run.mlp == "dense":
        dp = lp["mlp"] if lp else _at(params["dense"], e)
        m_gate, m_down = cfg.mlp_scales or (None, None)
        hid = _activation(
            _scaled(_proj(dp["gate"], h), m_gate), cfg.activation
        ) * _proj(dp["up"], h)
        out = _scaled(_proj(dp["down"], hid), m_down)
        if lp:  # the trainer's form: a remat preset may keep it
            out = checkpoint_name(out, remat_names.MLP_OUT)
        return _res(cfg, x, out), None, None, None
    out, pairs, routed, rounds = held_moe_mlp(
        cfg, h, lp["mlp"] if lp else params["layers"]["mlp"], valid=valid,
        router_input=a if cfg.moe_router_input == "attn" else None,
        layer=None if lp else e,
    )
    if lp:
        out = checkpoint_name(out, remat_names.MLP_OUT)
    return _res(cfg, x, out), pairs, routed, rounds


@region("areal.head")
def _head_logits(params: Params, cfg: TransformerConfig, x):
    """Logits of final-norm hidden states ``x``: the head's products
    come OUT in float32.  A ``bfloat16 @ bfloat16`` product comes out in
    bfloat16 whatever it accumulates in, and granite's logits are
    not small (tens to a hundred before ``logits_divisor``):
    rounded to 8 bits they moved the server's log-probabilities by
    0.024-0.027 at most and 0.0065-0.0071 on average, as much as serving
    every matrix in float8 (my chip runs, PR 31: PERF.md section 6)."""
    assert not cfg.is_critic
    if cfg.tied_embedding:
        w = params["embed"]["weight"].astype(x.dtype).T
    else:
        w = quantize.leaf_weight(params["lm_head"], x.dtype)
    logits = jnp.matmul(x, w, preferred_element_type=F32)
    logits = logits.astype(jnp.dtype(cfg.logits_dtype))
    if cfg.logits_divisor is not None:
        logits = logits / cfg.logits_divisor
    return logits


def _logits(params: Params, cfg: TransformerConfig, x):
    return _head_logits(params, cfg, _final_norm(params, cfg, x))


def _pairs_zero(cfg: TransformerConfig):
    return jnp.zeros((n_pair_counts(cfg),), jnp.int32)


def _add_pairs(pairs, p):
    return pairs if p is None else pairs + p


# ---------------------------------------------------------------------------
# whole sequences, no cache
# ---------------------------------------------------------------------------


#: the mixer kinds whose backward exists: the flash kernels' (windowed or
#: not) and the dense form's; a scan's, latent and sparse attention's do not
TRAINABLE_KINDS = PLAIN_ATTENTION_KINDS


def refuse_untrainable(cfg: TransformerConfig):
    """Raise, by name, for a stack the trainer cannot take."""
    kinds = sorted(set(cfg.layer_types) - set(TRAINABLE_KINDS))
    if kinds or cfg.diff_attention:
        raise NotImplementedError(
            f"the trainer cannot run a stack with layer kinds {kinds}"
            f"{' and differential heads' if cfg.diff_attention else ''}: "
            f"the backward exists for {TRAINABLE_KINDS} (packed rows, the "
            "flash kernels, the grouped product over held experts); a "
            "recurrent state has no packing and its scans, latent and sparse "
            "attention have no backward here (ROADMAP R9)"
        )


def _run_params(params: Params, cfg: TransformerConfig, run: Run) -> Params:
    """A run's layers cut out of the stacks, to be SCANNED: ``{"attn_norm",
    "mlp_norm", "mixer", "mlp"[, "mamba"]}``, each leaf ``[run.count,
    ...]``.  A scan over the layers' own parameters stacks their gradients
    as it goes; a body that indexes the whole stacks by a scanned number
    would add a stack-sized cotangent a layer."""

    def take(tree, first, stride):
        return jax.tree.map(
            lambda a: a[first : first + run.count * stride : stride], tree
        )

    layers = params["layers"]
    lp = {
        "attn_norm": take(layers["attn_norm"], run.first_layer, run.every),
        "mlp_norm": take(layers["mlp_norm"], run.first_layer, run.every),
        "mixer": take(
            params[_stack_of(run.kind, cfg)], run.first_of_kind, run.strides[0]
        ),
        "mlp": take(
            params["dense"] if run.mlp == "dense" else layers["mlp"],
            run.first_of_mlp, run.strides[1],
        ),
    }
    if run.kind == "parallel":
        lp["mamba"] = take(params["mamba"], run.first_of_state, run.strides[3])
    return lp


def _stats_zero():
    """The expert layers' counts of :func:`hidden_states`, at nothing."""
    z = jnp.zeros((), F32)
    return {
        "moe_held_pairs_sum": z, "moe_busiest_pairs_sum": z,
        "moe_extra_rounds_sum": z,
    }


def hidden_states(
    params: Params, cfg: TransformerConfig, tokens, positions, seg_ids,
    with_stats: bool = False,
):
    """Final-norm hidden states [B, T, D] of whole rows, and the form the
    TRAINER differentiates (``transformer.hidden_states`` hands a stack
    stated by kind here): every run scans its layers' own parameters
    (:func:`_run_params`), under ``cfg.remat`` a layer is rematerialised
    by the presets of ``models/remat.py``, and attention runs in the flash
    kernels, windowed or not, wherever ``transformer.takes_flash`` holds
    (float32 ``[T, T]`` scores under the mask elsewhere: the parity tests'
    form).  A stack of attention kinds alone takes PACKED rows (several
    segments a row, ``seg_ids`` 1..k, 0 padding); a stack with a recurrent
    state takes ONE segment a row (``seg_ids`` 1 on a prefix): a state has
    no packing.  ``with_stats``: also the expert layers' counts summed
    over layers (float32 scalars: the valid pairs the held experts took,
    the busiest held expert's pairs a layer, the grouped product's rounds
    past the first) and ``"routed_experts"`` [expert layers, B, T, K], each
    token's routed experts by their published numbers, for a check that
    follows the trainer's choices."""
    from areal_tpu.models import transformer

    B, T = tokens.shape
    n_valid = jnp.sum(seg_ids != 0, axis=1, dtype=jnp.int32)
    valid = seg_ids != 0
    x = _embed(params, cfg, tokens, positions)
    wcfg = cfg.window_plain()
    flash = transformer.takes_flash(cfg, T, transformer._AMBIENT_MESH)
    mask = mask_window = None
    if not flash:
        mask = make_attention_mask(seg_ids, positions, seg_ids, positions)
        mask_window = mask
        if cfg.n_window_layers:
            mask_window = make_attention_mask(
                seg_ids, positions, seg_ids, positions, cfg.sliding_window
            )
    s0 = jnp.zeros((B, cfg.mamba_d_state, cfg.mamba_d_inner), F32)
    tail0 = jnp.zeros((B, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim), x.dtype)
    scale = _attn_scale(cfg)
    rope_cs = latent_rope_tables(cfg, positions) if cfg.is_latent else None
    if cfg.is_latent_window:
        lwcfg = cfg.window_latent()
        rope_cs_win = latent_rope_tables(lwcfg, positions)
    # a rope rule by kind: the kinds' own tables, made once
    plain_cs = {}
    if cfg.window_has_own_widths or cfg.rope_partial_dim or (
        cfg.rope_yarn_factor and not cfg.is_latent
    ):
        plain_cs = {
            "attention": plain_rope_tables(cfg, positions),
            "window": plain_rope_tables(wcfg, positions),
        }

    def attend(q, k, v, mask, scale=scale):
        """Causal attention of whole rows: q [B, T, Hq, hd], k [B, T,
        Hkv, hd], v [B, T, Hkv, vd] -> [B, T, Hq * vd]."""
        Hkv = k.shape[2]
        s = jnp.einsum(
            "bikrd,bjkd->bkrij",
            q.reshape(B, T, Hkv, q.shape[2] // Hkv, -1).astype(F32),
            k.astype(F32),
        ) * scale
        s = jnp.where(mask[:, None, None], s, -1e30)
        o = jnp.einsum(
            "bkrij,bjkd->bikrd", jax.nn.softmax(s, axis=-1), v.astype(F32)
        )
        return o.reshape(B, T, -1).astype(_attn_dtype(cfg, x.dtype))

    def attend_plain(kcfg, q, k, v, window):
        """A plain attention kind's rows: the flash kernels under the
        kind's window, or :func:`attend` under its mask."""
        if not flash:
            return attend(q, k, v, mask if window is None else mask_window)
        out = transformer._flash_attention(q, k, v, seg_ids, kcfg, window)
        return out.reshape(B, T, -1)

    def keeps_memory(run: Run):
        return _place_in(run, cfg.memory_layer) is not None

    def mixer(run: Run, h, l, ap):
        """``(the mixer's output, what later layers read of it)``: the K
        and V the cross layers attend, the scan output the gated memory
        units gate, else None."""
        if run.kind == "mamba":
            out, _, _ = mamba_chunk(cfg, ap, h, n_valid, s0, tail0)
            return out, None
        if run.kind == "mamba1":
            out, _, _, y = mamba1_chunk(cfg, ap, h, n_valid, s0, tail0)
            return out, y if keeps_memory(run) else None
        if run.kind == "gmu":
            return gmu(ap, h, shared["memory"]), None
        if run.kind == "cross":
            q = _heads_q(cfg, ap, h, positions, run)
            attn = attend(q, *shared["kv"], mask)
            return _heads_out(cfg, ap, l, attn, h.dtype), None
        if run.kind in ("attention", "window"):
            kcfg = wcfg if run.kind == "window" else cfg
            q, k, v = _heads_qkv(
                kcfg, ap, h, positions, run, plain_cs.get(run.kind)
            )
            window = cfg.sliding_window if run.kind == "window" else None
            attn = checkpoint_name(
                attend_plain(kcfg, q, k, v, window), remat_names.ATTN_OUT
            )
            out = _heads_out(kcfg, ap, l, attn, h.dtype, h)
            return out, (k, v) if cfg.n_cross_layers else None
        if run.kind == "latent_window":
            return latent_whole(lwcfg, ap, h, rope_cs_win, mask_window), None
        return latent_whole(cfg, ap, h, rope_cs, mask), None

    def latent_whole(lcfg, ap, h, cs, mask):
        """The latent mixer over whole rows, keys and values expanded;
        under an indexer a query attends its chosen positions alone."""
        c_q = latent_cq(lcfg, ap, h)
        q_nope, q_rope = latent_q(lcfg, ap, h, cs, c_q)
        k, v = latent_expand(lcfg, ap, *latent_kv(lcfg, ap, h, cs))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if lcfg.is_indexed:
            with region("areal.attn.index"):
                qi, ki, w = index_qkw(lcfg, ap, c_q, h, cs)
                scores = jnp.where(
                    mask, sparse.index_scores(qi, w, ki), sparse.NEG
                )
            with region("areal.attn.select"):
                mask = sparse.chosen_mask(scores, lcfg.index_topk)
        return latent_out(
            lcfg, ap, h, attend(q, k, v, mask, _attn_scale(lcfg))
        )

    shared = {}  # what one layer leaves for later ones to read

    def parallel_mixer(run: Run, h, l, lp):
        """Both mixers on the one normed input, summed."""
        with region("areal.attn"):
            ap = lp["mixer"]
            q, k, v = _heads_qkv(cfg, ap, h, positions, run)
            out = _heads_out(cfg, ap, l, attend(q, k, v, mask), h.dtype)
        with region("areal.ssm"):
            out_m, _, _ = mamba_chunk(cfg, lp["mamba"], h, n_valid, s0, tail0)
        return out + out_m

    def mixer_half(x, idx, lp, run):
        """The layer's first half: ``(x, the mixer's input, what later
        layers read of the mixer)``."""
        with _mixer_region(run):
            a = _norm(x, lp["attn_norm"], cfg)
            if run.kind == "parallel":
                out, left = parallel_mixer(run, a, idx[0], lp), None
            else:
                out, left = mixer(run, a, idx[0], lp["mixer"])
            return _res(cfg, x, out), a, left

    def mlp_half(x, a, idx, lp, run):
        return _mlp_half(cfg, params, run, idx[0], idx[2], x, valid, a, lp=lp)

    def body(carry, xs, run):
        x, stats = carry
        idx, lp = xs
        halves = partial(mixer_half, run=run), partial(mlp_half, run=run)
        if cfg.remat:
            # each half rematerialised on its own: the backward of one
            # holds that half's intermediates alone
            policy = remat_names.policy_for(cfg.remat_policy)
            halves = [jax.checkpoint(h, policy=policy) for h in halves]
        x, a, left = halves[0](x, idx, lp)
        reads_a = cfg.moe_router_input == "attn" and run.mlp == "experts"
        x, pairs, routed, rounds = halves[1](x, a if reads_a else None, idx, lp)
        if pairs is not None:
            held = pairs[: cfg.n_held_experts].astype(F32)
            stats = {
                "moe_held_pairs_sum": stats["moe_held_pairs_sum"] + held.sum(),
                "moe_busiest_pairs_sum": stats["moe_busiest_pairs_sum"]
                + held.max(),
                "moe_extra_rounds_sum": stats["moe_extra_rounds_sum"]
                + (0.0 if rounds is None else rounds.astype(F32)),
            }
        return (x, stats), (left, routed if with_stats else None)

    def xs_of(run: Run):
        return _run_indices(run), _run_params(params, cfg, run)

    carry = (x, _stats_zero())
    routed = {}  # an expert run's first layer -> its layers' [n, B, T, K]
    for period in plan_periods(cfg):
        carry, kept = _scan_period(body, carry, period, xs_of)
        for run, (left, ids) in zip(period, kept):
            _keep_shared(cfg, run, shared, left)
            if ids is not None:
                routed[run.first_of_mlp] = (run, ids)
    x, stats = carry
    x = _final_norm(params, cfg, x)
    if not with_stats:
        return x
    if routed:
        # in the expert layers' order (a period's runs interleave)
        Le = cfg.n_expert_layers
        ids = jnp.zeros((Le,) + tokens.shape + (cfg.n_experts_per_tok,), jnp.int32)
        for run, got in routed.values():
            ids = ids.at[
                run.first_of_mlp : run.first_of_mlp + run.count * run.strides[1]
                : run.strides[1]
            ].set(got)
        stats["routed_experts"] = ids
    return x, stats


def _keep_shared(cfg: TransformerConfig, run: Run, shared: dict, left):
    """What a run's layers left (stacked over them) into ``shared``: the
    K and V of ``cfg.kv_shared_layer`` under ``"kv"``, the scan output of
    ``cfg.memory_layer`` under ``"memory"``."""
    if run.kind == "attention" and cfg.n_cross_layers:
        shared["kv"] = _held(run, cfg.kv_shared_layer, left)
    if _place_in(run, cfg.memory_layer) is not None:
        shared["memory"] = _held(run, cfg.memory_layer, left)


def forward(params: Params, cfg: TransformerConfig, tokens, positions, seg_ids):
    """Logits [B, T, V] of whole sequences (see :func:`hidden_states`)."""
    return _head_logits(
        params, cfg, hidden_states(params, cfg, tokens, positions, seg_ids)
    )


def logprobs_of_labels(
    params: Params, cfg: TransformerConfig, tokens, positions, seg_ids
):
    """log p(tokens[t+1] | tokens[<=t]), shape [B, T-1]."""
    logits = forward(params, cfg, tokens, positions, seg_ids)[:, :-1]
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


# ---------------------------------------------------------------------------
# the engine's two programs
# ---------------------------------------------------------------------------


def _get_state_rows(ssm, j, slots):
    """``ssm[j, slots]`` as ``[F, N, H*P]``: one ``dynamic_slice`` a row
    (F is 1, 2 or 4).  A gather of rows of the stacked state is lowered
    on the TPU through lane-block slices of the WHOLE operand: a
    state-sized copy a layer.  (Off the chip; on it the rows are read by
    ``ops/ssm.ssm_state_rows``.)"""
    piece = (1, 1) + ssm.shape[2:]
    return jnp.concatenate(
        [
            jax.lax.dynamic_slice(ssm, (j, slots[i], 0, 0), piece)[0]
            for i in range(slots.shape[0])
        ]
    )


def _put_state_rows(ssm, j, slots, rows, keep):
    """``ssm[j, slots[i]] = rows[i]`` for each ``i`` with ``keep[i]``,
    one row at a time by ``dynamic_update_slice`` (in place; a scatter
    makes XLA copy the operand)."""
    piece = (1, 1) + ssm.shape[2:]

    def put(i, ssm):
        at = (j, slots[i], 0, 0)
        old = jax.lax.dynamic_slice(ssm, at, piece)
        new = jnp.where(keep[i], rows[i].reshape(piece), old)
        return jax.lax.dynamic_update_slice(ssm, new, at)

    return jax.lax.fori_loop(0, slots.shape[0], put, ssm)


@region("areal.ssm")
def _get_conv_tails(conv, slots):
    """``conv[:, :, slots]`` as ``[Lm, F, K-1, conv_dim]``: every Mamba
    layer's tail of each filling row, one ``dynamic_slice`` a row."""
    Lm, Km1, _, cd = conv.shape
    rows = [
        jax.lax.dynamic_slice(conv, (0, 0, slots[i], 0), (Lm, Km1, 1, cd))
        for i in range(slots.shape[0])
    ]
    return jnp.concatenate(rows, axis=2).swapaxes(1, 2)


@region("areal.ssm")
def _put_conv_tails(conv, slots, tails, keep):
    """``conv[:, :, slots[i]] = tails[:, i]`` for each ``i`` with
    ``keep[i]``, one row after the other (F is 1, 2 or 4: written out,
    not a loop, so that nothing carries ``conv``), in place."""
    Lm, Km1, _, cd = conv.shape
    for i in range(slots.shape[0]):
        at = (0, 0, slots[i], 0)
        old = jax.lax.dynamic_slice(conv, at, (Lm, Km1, 1, cd))
        new = jnp.where(
            keep[i], tails[:, i, :, None].astype(conv.dtype), old
        )
        conv = jax.lax.dynamic_update_slice(conv, new, at)
    return conv


@partial(
    jax.jit,
    static_argnames=("cfg", "use_kernel", "keep_chosen"),
    donate_argnums=(1, 2, 3, 4),
    donate_argnames=("win_pools",),
)
def hybrid_fill_chunk(
    params: Params,
    k_pool: jax.Array,  # [La, NB, Hkv, BS, hd] (paged.pool_shapes)
    v_pool: jax.Array,
    ssm: jax.Array,  # [Lm, slots, N, H*P] float32
    conv: jax.Array,  # [Lm, K-1, slots, conv_dim]
    cfg: TransformerConfig,
    tokens: jax.Array,  # [F, C] this chunk's tokens (right-padded)
    starts: jax.Array,  # [F] tokens already filled per row
    chunk_lens: jax.Array,  # [F] valid tokens in this chunk (0: padding row)
    tables: jax.Array,  # [F, MB] pool block ids
    slots: jax.Array,  # [F] state slot of each row
    use_kernel: bool,
    win_pools: Optional[Tuple[jax.Array, jax.Array]] = None,  # [Lw, NBw, ..]
    win_tables: Optional[jax.Array] = None,  # [F, MB] window-pool block ids
    keep_chosen: int = 0,  # keep each row's last queries' chosen sets
):
    """One prefill chunk for F filling rows of a hybrid stack: the
    hybrid twin of ``paged.paged_fill_chunk``.  An attention layer attends
    the chunk and the row's paged prefix and leaves its KV for ONE pool
    write after the stack; a window layer likewise under ``i - j <
    cfg.sliding_window``, over its own pools and table (``win_pools``,
    ``win_tables``: the pages before the window's first position are not
    read, and need not be held); a Mamba layer starts from the row's slot
    (from zero where ``starts`` is 0: a slot is never cleared by a pass
    of its own) and leaves the state after the chunk's last valid token
    there.  The conv tails are read before the stack and written after
    it, like the KV: carried through the layer loops, the TPU compiler
    moved the whole ``conv`` array into its fast memory for the loops'
    duration, where part of it came back overwritten (three layers' tails
    of slots 25-63 in one fill in twenty, my chip runs, PR 31: PERF.md
    section 6).  A latent layer attends the chunk with keys and values
    expanded and the paged prefix in the absorbed form, and leaves its
    latent entries for the same one write.  Returns ``(last_logits [F,
    V], k_pool, v_pool, ssm, conv, pairs [moe.n_pair_counts], routed [Le,
    F, C, K], extra rounds)`` and, given ``win_pools``, those last:
    ``routed`` is every EXPERT layer's routed experts of every position
    and ``extra rounds`` the rounds past the first that the expert layers'
    grouped products took, summed over them (``moe.held_moe_mlp``; None
    where the batch's shape takes the product over every held expert).
    ``keep_chosen`` (a stack with an indexer): one output more, the last:
    ``[L_indexed, F, keep_chosen, MB * BS + C]`` bool, what each indexed
    layer's mask let the LAST ``keep_chosen`` valid queries of each row
    attend (cached positions, then the chunk's own tokens; query ``i`` of
    them is token ``chunk_len - keep_chosen + i`` of the chunk, nothing
    where that is negative)."""
    C = tokens.shape[1]
    valid = jnp.arange(C)[None, :] < chunk_lens[:, None]  # [F, C]
    row_valid = chunk_lens > 0
    fresh = starts == 0
    positions = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    read_lens = jnp.where(row_valid, starts, 0)
    x = _embed(params, cfg, tokens, positions)
    iot = jnp.arange(C)
    mask_chunk = (
        valid[:, None, :] & valid[:, :, None] & (iot[:, None] >= iot[None, :])
    )
    scale = _attn_scale(cfg)
    plan = paged._prefix_plan(
        C, cfg.n_q_heads, k_pool, tables, read_lens, use_kernel,
        masked=cfg.is_indexed,
    )
    window = cfg.sliding_window if cfg.n_window_layers else None
    # a latent window layer's widths are its own (``cfg.window_latent``)
    wcfg = cfg.window_latent() if cfg.is_latent_window else cfg
    if window:
        mask_chunk_win = mask_chunk & (iot[:, None] - iot[None, :] < window)
        plan_win = paged._prefix_plan(
            C, wcfg.n_q_heads, win_pools[0], win_tables, read_lens,
            use_kernel, window=window,
        )

    latent = cfg.is_latent
    # the arrays of the whole-context pool that the stack writes: a latent
    # pool's V side holds the index keys, or nothing
    n_global = 1 if latent and not cfg.is_indexed else 2
    rope_cs = latent_rope_tables(cfg, positions) if latent else None
    if cfg.is_latent_window:
        rope_cs_win = latent_rope_tables(wcfg, positions)
    if cfg.n_mamba_layers:
        with region("areal.ssm"):
            tails0 = jnp.where(
                fresh[None, :, None, None], 0, _get_conv_tails(conv, slots)
            )  # [Lm, F, K-1, conv_dim]

    def mamba_mixer(run, h, ssm, j, tail0):
        mp = _at(params["mamba1" if run.kind == "mamba1" else "mamba"], j)
        if use_kernel:
            s0 = ssm_ops.ssm_state_rows(
                ssm, j, slots, interpret=paged.kernel_interpret()
            )
        else:
            s0 = _get_state_rows(ssm, j, slots)
        s0 = jnp.where(fresh[:, None, None], 0.0, s0)
        if run.kind == "mamba1":
            out, s1, tail1, y = mamba1_chunk(cfg, mp, h, chunk_lens, s0, tail0)
            if _place_in(run, cfg.memory_layer) is not None:
                tail1 = (tail1, y)
        else:
            out, s1, tail1 = mamba_chunk(cfg, mp, h, chunk_lens, s0, tail0)
        return out, _put_state_rows(ssm, j, slots, s1, row_valid), tail1

    def attn_mixer(run, h, l, j, p):
        ap = _at(params["attn"], j)
        q, k, v = _heads_qkv(cfg, ap, h, positions, run)
        if run.kind == "window":
            prefix = paged._prefix_partials(
                q, *win_pools, win_tables, read_lens, p, use_kernel,
                plan=plan_win, scale=scale, window=window,
            )
            mask = mask_chunk_win
        else:
            prefix = paged._prefix_partials(
                q, k_pool, v_pool, tables, read_lens, p, use_kernel,
                plan=plan, scale=scale,
            )
            mask = mask_chunk
        attn = paged.chunk_attention(
            q, k, v, prefix, mask, scale, _attn_dtype(cfg, h.dtype)
        )
        return _heads_out(cfg, ap, l, attn, h.dtype), (
            k.astype(k_pool.dtype), v.astype(v_pool.dtype)
        )

    def cross_mixer(run, h, l, j, last=False):
        """Queries only, over the shared layer's pages (the pool's one
        layer) and its K and V of this chunk's tokens.  ``last``: ``h``
        holds each row's last valid position alone (at ``positions_last``,
        its calls' page plan ``plan_last``), which attends every valid
        position of its chunk."""
        ap = _at(params["cross"], j)
        if last:
            q = _heads_q(cfg, ap, h, positions_last, run)
            # a query tile of TWO rows, the second zero: the kernel names
            # a call of one query row a sequence a decode step
            # (``paged_attn_decode``), and the decode kernel's share of
            # its roofline counts every execution of that name
            prefix = tuple(
                t[:, :1]
                for t in paged._prefix_partials(
                    jnp.pad(q, ((0, 0), (0, 1), (0, 0), (0, 0))), k_pool,
                    v_pool, tables, read_lens, 0, use_kernel,
                    plan=plan_last, scale=scale,
                )
            )
            mask = valid[:, None, :]
        else:
            q = _heads_q(cfg, ap, h, positions, run)
            prefix = paged._prefix_partials(
                q, k_pool, v_pool, tables, read_lens, 0, use_kernel,
                plan=plan, scale=scale,
            )
            mask = mask_chunk
        attn = paged.chunk_attention(
            q, *shared["kv"], prefix, mask, scale, _attn_dtype(cfg, h.dtype)
        )
        return _heads_out(cfg, ap, l, attn, h.dtype)

    def latent_mixer(run, h, j, p):
        """A latent layer over the chunk: its own tokens expanded, its
        paged prefix absorbed (a window layer's under the window, from
        the window pool; an indexed layer's under the indexer's choice)."""
        if run.kind == "latent_window":
            lcfg, ap, cs = wcfg, _at(params["latent_window"], j), rope_cs_win
        else:
            lcfg, ap, cs = cfg, _at(params["latent"], j), rope_cs
        lscale = _attn_scale(lcfg)
        c_q = latent_cq(lcfg, ap, h) if lcfg.is_indexed else None
        q_nope, q_rope = latent_q(lcfg, ap, h, cs, c_q)
        c_kv, k_rope = latent_kv(lcfg, ap, h, cs)
        k, v = latent_expand(lcfg, ap, c_kv, k_rope)
        q_abs = latent_absorbed_q(lcfg, ap, q_nope, q_rope)
        mask, kept_index = mask_chunk, ()
        if run.kind == "latent_window":
            acc, m, lsum = paged._prefix_partials(
                q_abs, win_pools[0], None, win_tables, read_lens, p,
                use_kernel, plan=plan_win, scale=lscale,
                value_dim=lcfg.kv_lora_rank, window=window,
            )
            mask = mask_chunk_win
        elif lcfg.is_indexed:
            with region("areal.attn.index"):
                qi, ki, w = index_qkw(lcfg, ap, c_q, h, cs)
                before = sparse.paged_index_scores(
                    qi, w, v_pool, tables, read_lens, j
                )  # [F, C, MB * BS]
                own = jnp.where(
                    mask_chunk, sparse.index_scores(qi, w, ki), sparse.NEG
                )
            with region("areal.attn.select"):
                chosen = sparse.chosen_mask(
                    jnp.concatenate([before, own], axis=-1), lcfg.index_topk
                )
                cached = before.shape[-1]
            with region("areal.attn.sparse"):
                acc, m, lsum = paged._prefix_partials(
                    q_abs, k_pool, None, tables, read_lens, j, use_kernel,
                    plan=plan, scale=lscale, value_dim=lcfg.kv_lora_rank,
                    mask=chosen[..., :cached],
                )
            mask = chosen[..., cached:]
            kept_index = (ki[:, :, None, :].astype(v_pool.dtype),)
            if keep_chosen:
                last = chunk_lens[:, None] - keep_chosen + jnp.arange(keep_chosen)
                kept_index += (
                    jnp.take_along_axis(
                        chosen, jnp.maximum(last, 0)[:, :, None], axis=1
                    ) & (last >= 0)[:, :, None],
                )
        else:
            acc, m, lsum = paged._prefix_partials(
                q_abs, k_pool, None,
                tables, read_lens, j, use_kernel, plan=plan, scale=lscale,
                value_dim=lcfg.kv_lora_rank,
            )
        attn = paged.chunk_attention(
            jnp.concatenate([q_nope, q_rope], axis=-1), k, v,
            (latent_values_out(lcfg, ap, acc), m, lsum),
            mask, lscale, h.dtype,
        )
        entry = latent_entry(lcfg, c_kv, k_rope)[:, :, None, :]
        return latent_out(lcfg, ap, h, attn), (
            entry.astype(k_pool.dtype),
        ) + kept_index

    # the rounds' count rides the layer loops only in a program whose
    # experts take the grouped product: any other is the program it was,
    # to the letter (one scalar more through the hybrid cell's loops, and
    # 4 of 187 served sequences came back non-finite in one run of four:
    # my chip runs, PR 41, ``moe.group_rows``)
    grouped = moe.group_rows(cfg, tokens.size)
    carry = (
        x, ssm, _pairs_zero(cfg), jnp.zeros((), jnp.int32) if grouped else None,
    )
    chunk_kv, chunk_kv_win, tails1, routed = [], [], [], []
    shared = {}  # what one layer leaves for later ones to read

    def xs_of(run):
        xs = _run_indices(run)
        if run.kind in ("mamba", "mamba1", "parallel"):
            xs += (tails0[_of_state(run)],)
        return xs

    def body(carry, inp, run):
        x, ssm, pairs, rounds = carry
        l, j, e, p = inp[:4]
        with _mixer_region(run):
            a = _norm(x, _at(params["layers"]["attn_norm"], l), cfg)
            if run.kind == "parallel":
                # both mixers on the one normed input, summed; kept: the
                # chunk's (K, V) and the conv tail after it
                with region("areal.attn"):
                    out, kv = attn_mixer(run, a, l, j, p)
                with region("areal.ssm"):
                    out_m, ssm, tail1 = mamba_mixer(run, a, ssm, inp[4], inp[5])
                out, kept = out + out_m, (kv, tail1)
            elif run.kind in ("mamba", "mamba1"):
                out, ssm, kept = mamba_mixer(run, a, ssm, j, inp[4])
            elif run.kind in ("latent", "latent_window"):
                out, kept = latent_mixer(run, a, j, p)
            elif run.kind == "gmu":
                out, kept = gmu(_at(params["gmu"], j), a, shared["memory"]), None
            elif run.kind == "cross":
                out, kept = cross_mixer(run, a, l, j), None
            else:
                out, kept = attn_mixer(run, a, l, j, p)
            x = _res(cfg, x, out)
        x, n, r, m = _mlp_half(cfg, params, run, l, e, x, valid, a)
        return (x, ssm, _add_pairs(pairs, n), _add_pairs(rounds, m)), (kept, r)

    periods, tail = plan_periods(cfg), keep_nothing_tail(cfg)
    for period in periods[: len(periods) - len(tail)]:
        carry, left = _scan_period(body, carry, period, xs_of)
        for run, (kept, r) in zip(period, left):
            if run.kind == "parallel":
                chunk_kv.append(kept[0])
                tails1.append(kept[1])
            elif run.kind in ("mamba", "mamba1"):
                if _place_in(run, cfg.memory_layer) is not None:
                    kept, shared["memory"] = kept[0], _held(
                        run, cfg.memory_layer, kept[1]
                    )
                tails1.append(kept)
            elif run.kind in WINDOW_KINDS:
                chunk_kv_win.append(kept)
            elif kept is not None:
                chunk_kv.append(kept)
                _keep_shared(cfg, run, shared, kept)
            if r is not None:
                routed.append(r)
    x, ssm, pairs, rounds = carry

    def tail_body(x, idx, run):
        l, j, e, _ = idx
        with _mixer_region(run):
            a = _norm(x, _at(params["layers"]["attn_norm"], l), cfg)
            if run.kind == "gmu":
                out = gmu(_at(params["gmu"], j), a, memory_last)
            else:
                out = cross_mixer(run, a, l, j, last=True)
            x = _res(cfg, x, out)
        return _mlp_half(cfg, params, run, l, e, x, row_valid[:, None])[0], None

    if tail:
        # the keep-nothing tail, on [F, 1, D]: each row's last valid
        # position (a padding row's position 0, masked as it was)
        x = paged.last_valid(x, chunk_lens)
        if "memory" in shared:
            memory_last = paged.last_valid(shared["memory"], chunk_lens)
        positions_last = (starts + jnp.maximum(chunk_lens - 1, 0))[:, None]
        plan_last = paged._prefix_plan(
            2, cfg.n_q_heads, k_pool, tables, read_lens, use_kernel
        )
        for period in tail:
            x, _ = _scan_period(tail_body, x, period, _run_indices)
    if tails1:
        conv = _put_conv_tails(
            conv, slots, jnp.concatenate(tails1, axis=0), row_valid
        )
    pools = (k_pool, v_pool)[:n_global]
    vals = tuple(jnp.concatenate(t, axis=0) for t in zip(*chunk_kv))
    if keep_chosen:
        assert cfg.is_indexed, "keep_chosen: a stack with an indexer"
        vals, chosen_last = vals[:-1], vals[-1]
    vals_win = tuple(jnp.concatenate(t, axis=0) for t in zip(*chunk_kv_win))
    # the pools are written only after every layer has read them: without
    # the barrier a run of ONE layer is inlined, the kernel reads the
    # donated pool while the write loop wants it in place, and XLA
    # settles that with two copies of each pool
    x, pools, vals, win_pools, vals_win = jax.lax.optimization_barrier(
        (x, pools, vals, win_pools, vals_win)
    )
    if vals:
        pools = paged.write_kv_runs(pools, vals, tables, starts, chunk_lens)
    if vals_win:
        # (a pool of latent entries has no second array to write)
        win_pools = paged.write_kv_runs(
            win_pools[: len(vals_win)], vals_win, win_tables, starts,
            chunk_lens,
        ) + tuple(win_pools[len(vals_win) :])
    k_pool, v_pool = (pools + (v_pool,))[:2]
    if not tail:
        x = paged.last_valid(x, chunk_lens)
    logits = _logits(params, cfg, x)[:, 0]
    out = (
        logits, k_pool, v_pool, ssm, conv, pairs,
        jnp.concatenate(routed, axis=0) if routed else None, rounds,
    )
    if win_pools is not None:
        out += (win_pools,)
    return out + (chosen_last,) if keep_chosen else out


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk_size", "use_kernel", "max_len", "sample_fn", "stop_fn",
        "keep_chosen",
    ),
    donate_argnums=(1, 2, 3, 4),
    donate_argnames=("win_pools",),
)
def hybrid_decode_chunk(
    params: Params,
    k_pool: jax.Array,  # [La, NB, Hkv, BS, hd]
    v_pool: jax.Array,
    ssm: jax.Array,  # [Lm, B, N, H*P] float32: slot b is row b's
    conv: jax.Array,  # [Lm, K-1, B, conv_dim]
    cfg: TransformerConfig,
    tables: jax.Array,  # [B, MB]
    lengths: jax.Array,  # [B] valid cache prefix per row
    cur_tokens: jax.Array,  # [B] pending token per row
    active: jax.Array,  # [B] bool
    budgets: jax.Array,  # [B]
    rng: jax.Array,
    chunk_size: int,
    sample_fn,
    stop_fn,
    use_kernel: bool,
    max_len: int,
    row_seeds: Optional[jax.Array] = None,
    win_pools: Optional[Tuple[jax.Array, jax.Array]] = None,  # [Lw, NBw, ..]
    win_tables: Optional[jax.Array] = None,  # [B, MB] window-pool block ids
    keep_chosen: bool = False,
):
    """Up to ``chunk_size`` tokens for all active rows of a hybrid stack:
    the hybrid twin of ``paged.paged_decode_chunk`` (same window design
    for the attention layers' KV, same outputs), with every Mamba layer's
    state advanced in place for the rows live at each step.  A window
    layer reads its own pools through its own table (``win_pools``,
    ``win_tables``) from the page that holds ``length - sliding_window +
    1`` on; the chunk's own tokens lie inside every window (``chunk_size
    < sliding_window``).  Returns ``(k_pool, v_pool, ssm, conv, lengths,
    out_t, out_l, emitted, cur, active, budgets, rng, pairs
    [moe.n_pair_counts], routed [W, Le, K, B])`` and, given
    ``win_pools``, those last: ``routed`` is every EXPERT layer's routed
    experts at each step, for the position
    the step READ (row b's entry of step i means something where
    ``emitted[b, i]``; the row axis last, so that the array pads little
    on the chip).  ``keep_chosen`` (a stack with an indexer): one output
    more, before ``win_pools``: every indexed layer's chosen set at each
    step, ``[W, L_indexed, B, .]``: where the steps attend under a mask
    (``sparse_attention.decode_reads_masked`` of the table's shape) the
    mask over the table's ``MB * BS`` positions and then the chunk's ``W``,
    as ``sparse_attention.packed_mask`` words; where they gather, the
    ``index_topk`` positions of the row (int32, -1: none)."""
    B = cur_tokens.shape[0]
    W = chunk_size
    _, _, Hkv, _, hd = k_pool.shape
    latent, indexed = cfg.is_latent, cfg.is_indexed
    lat_win = cfg.is_latent_window
    # a latent window layer's widths are its own (``cfg.window_latent``)
    wcfg = cfg.window_latent() if lat_win else cfg
    # attention, window or latent: the chunk's KV (latent window layers'
    # entries, of another width, have a buffer of their own: ``ww``)
    La = cfg.n_attn_layers - cfg.n_latent_window_layers
    base_lens = lengths
    read_lens = jnp.where(active, base_lens, 0)
    scale = _attn_scale(cfg)
    # how a step under an indexer attends its chosen set, from the table's
    # shape: under a mask in the paged kernel, or gathered (a long table)
    masked = indexed and sparse.decode_reads_masked(
        tables.shape[1] * k_pool.shape[3], cfg.index_topk
    )
    plan = paged._prefix_plan(
        1, cfg.n_q_heads, k_pool, tables, read_lens, use_kernel, masked=masked
    )
    window = cfg.sliding_window if cfg.n_window_layers else None
    if window:
        assert W < window, (W, window)
        plan_win = paged._prefix_plan(
            1, wcfg.n_q_heads, win_pools[0], win_tables, read_lens,
            use_kernel, window=window,
        )
    # the chunk's own KV (latent layers: its latent entries, which are
    # keys and values both, and under an indexer its index keys on the V
    # side), one pool write after the chunk
    wk = jnp.zeros((La, W, B, Hkv, hd), k_pool.dtype)
    wv = jnp.zeros(
        (0 if latent and not indexed else La, W, B, Hkv,
         v_pool.shape[-1] if indexed else hd),
        k_pool.dtype,
    )
    ww = chosen = None
    if lat_win:
        ww = jnp.zeros(
            (cfg.n_latent_window_layers, W, B, 1, win_pools[0].shape[-1]),
            k_pool.dtype,
        )
    if keep_chosen:
        # every indexed layer's chosen set at each step: the step's mask
        # over the table's positions and then the chunk's own, 32 to a
        # word (the host makes positions of the rows it keeps), or on the
        # gathering path the positions themselves (-1: none)
        assert indexed, "keep_chosen: a stack with an indexer"
        scored = tables.shape[1] * k_pool.shape[3] + W
        chosen = (
            jnp.zeros((W, La, B, -(-scored // 32)), jnp.uint32) if masked
            else jnp.full((W, La, B, min(cfg.index_topk, scored)), -1, jnp.int32)
        )
    if cfg.n_cross_layers:
        # the shared layer's number in the chunk's own KV (the attention
        # and window layers' parameter stack)
        shared_j = int(pool_layer_numbers(cfg, "attention")[0])

    def step(i, st):
        (lengths_, cur, active, budgets, wk, wv, wvalid, ssm, conv, out_t,
         out_l, emitted, rng, pairs, routed, ww, chosen) = st
        positions = lengths_[:, None]
        x = _embed(params, cfg, cur[:, None], positions)
        wvalid = wvalid.at[i].set(active)
        mask_win = wvalid.T[:, None, None, None, :]  # [B,1,1,1,W]
        live = active[:, None]
        rope_cs = latent_rope_tables(cfg, positions) if latent else None
        if lat_win:
            rope_cs_win = latent_rope_tables(wcfg, positions)

        def attn_mixer(run, h, wk, wv, l, j, p):
            ap = _at(params["attn"], j)
            q, k, v = _heads_qkv(cfg, ap, h, positions, run)
            wk, wv = window_put(wk, k, j, i), window_put(wv, v, j, i)
            if run.kind == "window":
                # the plan is of the chunk's start; this step's queries
                # stand i positions past it
                prefix = paged._prefix_partials(
                    q, *win_pools, win_tables, read_lens, p, use_kernel,
                    plan=plan_win, scale=scale, window=window,
                    window_shift=i,
                )
            else:
                prefix = paged._prefix_partials(
                    q, k_pool, v_pool, tables, read_lens, p, use_kernel,
                    plan=plan, scale=scale,
                )
            attn = paged.window_attention(
                q,
                jax.lax.dynamic_index_in_dim(wk, j, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(wv, j, 0, keepdims=False),
                prefix, mask_win, scale, _attn_dtype(cfg, h.dtype),
            )
            return _heads_out(cfg, ap, l, attn, h.dtype), wk, wv

        def cross_mixer(run, h, wk, wv, l, j):
            """Queries only, over the shared layer's pages (the pool's one
            layer) and its K and V of this chunk's steps so far."""
            ap = _at(params["cross"], j)
            q = _heads_q(cfg, ap, h, positions, run)
            prefix = paged._prefix_partials(
                q, k_pool, v_pool, tables, read_lens, 0, use_kernel,
                plan=plan, scale=scale,
            )
            attn = paged.window_attention(
                q, wk[shared_j], wv[shared_j], prefix, mask_win, scale,
                _attn_dtype(cfg, h.dtype),
            )
            return _heads_out(cfg, ap, l, attn, h.dtype)

        def latent_start(lcfg, ap, h, cs, buf, at, c_q=None):
            """A latent layer's absorbed query of the step's token, and the
            chunk's buffer ``buf`` with that token's entry at layer ``at``."""
            q_nope, q_rope = latent_q(lcfg, ap, h, cs, c_q)
            c_kv, k_rope = latent_kv(lcfg, ap, h, cs)
            buf = window_put(
                buf, latent_entry(lcfg, c_kv, k_rope)[:, :, None, :], at, i
            )
            return latent_absorbed_q(lcfg, ap, q_nope, q_rope), buf

        def latent_finish(lcfg, ap, h, q, buf, at, prefix, mask):
            """The partials over cached entries merged with the chunk's own
            (``buf``'s layer ``at``, attended where ``mask``), through
            ``W_UV``, the gate and ``W_o``."""
            own = jax.lax.dynamic_index_in_dim(buf, at, 0, keepdims=False)
            o_lat = paged.window_attention(
                q, own, own[..., : lcfg.kv_lora_rank], prefix, mask,
                _attn_scale(lcfg), h.dtype,
            ).reshape(B, 1, lcfg.n_q_heads, lcfg.kv_lora_rank)
            attn = latent_values_out(lcfg, ap, o_lat).reshape(B, 1, -1)
            return latent_out(lcfg, ap, h, attn)

        def latent_mixer(h, wk, j):
            ap = _at(params["latent"], j)
            q, wk = latent_start(cfg, ap, h, rope_cs, wk, j)
            prefix = paged._prefix_partials(
                q, k_pool, None, tables, read_lens, j, use_kernel,
                plan=plan, scale=scale, value_dim=cfg.kv_lora_rank,
            )
            return latent_finish(cfg, ap, h, q, wk, j, prefix, mask_win), wk

        def latent_window_mixer(h, ww, j, p):
            """A latent layer under the window: its own pools and table,
            its own widths; the chunk's tokens lie inside every window."""
            ap = _at(params["latent_window"], j)
            q, ww = latent_start(wcfg, ap, h, rope_cs_win, ww, p)
            prefix = paged._prefix_partials(
                q, win_pools[0], None, win_tables, read_lens, p, use_kernel,
                plan=plan_win, scale=_attn_scale(wcfg),
                value_dim=wcfg.kv_lora_rank, window=window, window_shift=i,
            )
            return latent_finish(wcfg, ap, h, q, ww, p, prefix, mask_win), ww

        def indexed_mixer(h, wk, wv, chosen, j):
            """A latent layer under its indexer: scores over the row's
            index pages and the chunk's own keys, the exact choice, and
            the absorbed products over the CHOSEN entries alone: the
            cached prefix attended under the choice's mask in the paged
            kernel, or over a long table the chosen entries gathered from
            the pool and not the context read (``masked``)."""
            ap = _at(params["latent"], j)
            c_q = latent_cq(cfg, ap, h)
            q, wk = latent_start(cfg, ap, h, rope_cs, wk, j, c_q)
            with region("areal.attn.index"):
                qi, ki, w = index_qkw(cfg, ap, c_q, h, rope_cs)
                wv = window_put(wv, ki[:, :, None, :], j, i)
                before = sparse.paged_index_scores(
                    qi, w, v_pool, tables, read_lens, j
                )  # [B, 1, MB * BS]
                wv_j = jax.lax.dynamic_index_in_dim(wv, j, 0, keepdims=False)
                own = jnp.where(
                    wvalid.T[:, None, :],
                    sparse.index_scores(qi, w, wv_j[:, :, 0].swapaxes(0, 1)),
                    sparse.NEG,
                )  # [B, 1, W]
            with region("areal.attn.select"):
                cached = before.shape[-1]
                scores = jnp.concatenate([before, own], axis=-1)[:, 0]
                if masked:
                    picked = sparse.chosen_mask(scores, cfg.index_topk)
                    own_chosen = picked[:, cached:]  # [B, W]
                    kept = sparse.packed_mask(picked) if keep_chosen else None
                else:
                    idx, live = sparse.select(scores, cfg.index_topk)  # [B, K]
                    own_chosen = jnp.any(
                        (idx[:, :, None] == cached + jnp.arange(W))
                        & live[:, :, None],
                        axis=1,
                    )
                    # kept as positions of the row: a step of the chunk
                    # stands at the cached length + its number
                    at = jnp.where(
                        idx < cached, idx, idx - cached + base_lens[:, None]
                    )
                    kept = jnp.where(live, at, -1)
                if keep_chosen:
                    chosen = jax.lax.dynamic_update_slice(
                        chosen, kept[None, None], (i, j, 0, 0)
                    )
            with region("areal.attn.sparse"):
                if masked:
                    prefix = paged._prefix_partials(
                        q, k_pool, None, tables, read_lens, j, use_kernel,
                        plan=plan, scale=scale, value_dim=cfg.kv_lora_rank,
                        mask=picked[:, None, :cached],
                    )
                else:
                    prefix = sparse.sparse_latent_partials(
                        q, k_pool, j, tables, idx, live & (idx < cached),
                        cfg.kv_lora_rank, scale,
                    )
                out = latent_finish(
                    cfg, ap, h, q, wk, j, prefix,
                    mask_win & own_chosen[:, None, None, None, :],
                )
            return out, wk, wv, chosen

        carry, step_routed = (x, wk, wv, ssm, conv, pairs, ww, chosen), []
        shared = {}  # what one layer leaves for later ones to read

        def body(carry, idx, run):
            x, wk, wv, ssm, conv, pairs, ww, chosen = carry
            l, j, e, p = idx[:4]
            left = None
            with _mixer_region(run):
                a = _norm(x, _at(params["layers"]["attn_norm"], l), cfg)
                if run.kind == "parallel":
                    # both mixers on the one normed input, summed
                    with region("areal.attn"):
                        out, wk, wv = attn_mixer(run, a, wk, wv, l, j, p)
                    with region("areal.ssm"):
                        out_m, ssm, conv = mamba_step(
                            cfg, _at(params["mamba"], idx[4]), a,
                            ssm, conv, idx[4], active, use_kernel,
                        )
                    out = out + out_m
                elif run.kind == "mamba":
                    out, ssm, conv = mamba_step(
                        cfg, _at(params["mamba"], j), a,
                        ssm, conv, j, active, use_kernel,
                    )
                elif run.kind == "mamba1":
                    out, ssm, conv, y = mamba1_step(
                        cfg, _at(params["mamba1"], j), a,
                        ssm, conv, j, active, use_kernel,
                    )
                    if _place_in(run, cfg.memory_layer) is not None:
                        left = y
                elif run.kind == "gmu":
                    out = gmu(_at(params["gmu"], j), a, shared["memory"])
                elif run.kind == "cross":
                    out = cross_mixer(run, a, wk, wv, l, j)
                elif run.kind == "latent_window":
                    out, ww = latent_window_mixer(a, ww, j, p)
                elif run.kind == "latent" and indexed:
                    out, wk, wv, chosen = indexed_mixer(a, wk, wv, chosen, j)
                elif run.kind == "latent":
                    out, wk = latent_mixer(a, wk, j)
                else:
                    out, wk, wv = attn_mixer(run, a, wk, wv, l, j, p)
                x = _res(cfg, x, out)
            x, n, r, _ = _mlp_half(cfg, params, run, l, e, x, live, a)
            return (x, wk, wv, ssm, conv, _add_pairs(pairs, n), ww, chosen), (
                None if r is None else r[:, 0].T, left,
            )

        for period in plan_periods(cfg):
            carry, lefts = _scan_period(body, carry, period, _run_indices)
            for run, (r, left) in zip(period, lefts):
                _keep_shared(cfg, run, shared, left)
                if r is not None:
                    step_routed.append(r)  # [run.count, K, B]
        x, wk, wv, ssm, conv, pairs, ww, chosen = carry
        if step_routed:
            with region("areal.moe.route"):
                routed = jax.lax.dynamic_update_slice(
                    routed, jnp.concatenate(step_routed, axis=0)[None],
                    (i, 0, 0, 0),
                )
        logits = _logits(params, cfg, x)[:, 0]
        (new_lengths, tok, active, budgets, out_t, out_l, emitted,
         rng) = sample_and_advance(
            sample_fn, stop_fn, logits, rng, i, lengths_, active, budgets,
            out_t, out_l, emitted, max_len, row_seeds,
        )
        return (new_lengths, tok, active, budgets, wk, wv, wvalid, ssm, conv,
                out_t, out_l, emitted, rng, pairs, routed, ww, chosen)

    st = (
        base_lens, cur_tokens, active, budgets, wk, wv,
        jnp.zeros((W, B), bool), ssm, conv,
        jnp.zeros((B, W), jnp.int32), jnp.zeros((B, W), F32),
        jnp.zeros((B, W), bool), rng, _pairs_zero(cfg),
        jnp.zeros(
            (W, cfg.n_expert_layers, cfg.n_experts_per_tok, B), jnp.int32
        ),
        ww, chosen,
    )
    (lengths_, cur, active, budgets, wk, wv, _, ssm, conv, out_t, out_l,
     emitted, rng, pairs, routed, ww, chosen) = jax.lax.fori_loop(
        0, W, step, st
    )
    pools, vals = (k_pool, v_pool), (wk.swapaxes(1, 2), wv.swapaxes(1, 2))
    if latent and not indexed:
        pools, vals = pools[:1], vals[:1]
    counts = lengths_ - base_lens
    if lat_win:
        # (the global layers' entries are the whole of ``wk``)
        win_pools = paged.write_kv_runs(
            win_pools[:1], (ww.swapaxes(1, 2),), win_tables, base_lens, counts
        ) + tuple(win_pools[1:])
    elif window:
        # the chunk's KV holds both kinds' layers, in the order of their
        # parameter stack: each pool takes its own
        of_win = pool_layer_numbers(cfg, "window")
        win_pools = paged.write_kv_runs(
            win_pools, tuple(v[of_win] for v in vals), win_tables,
            base_lens, counts,
        )
        of_global = pool_layer_numbers(cfg, "attention")
        vals = tuple(v[of_global] for v in vals)
    pools = paged.write_kv_runs(pools, vals, tables, base_lens, counts)
    k_pool, v_pool = (pools + (v_pool,))[:2]
    out = (k_pool, v_pool, ssm, conv, lengths_, out_t, out_l, emitted, cur,
           active, budgets, rng, pairs, routed)
    if keep_chosen:
        out += (chosen,)
    return out if win_pools is None else out + (win_pools,)
