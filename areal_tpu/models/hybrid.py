"""A stack stated by kind: each layer a MIXER (Mamba-2 with a recurrent
state, attention with paged per-head KV over the whole context or over a
window of it, latent attention with paged latent entries) and an MLP (a
dense one on the leading ``cfg.n_dense_layers`` layers, else the expert
block this program's share of the experts gives) (granitemoehybrid,
deepseek_v3, smallthinker).

    h0 = embed_scale * embed[tokens]
    per layer:  h += r * mixer(rmsnorm(h));  m = rmsnorm(h)
                h += r * (experts(m) + shared(m))   or   r * dense(m)
    logits = rmsnorm(h) @ head / logits_divisor     (head: embed^T if tied)

**The layer plan** (:func:`layer_plan`): the published ``layer_types`` cut
into runs of one (mixer, MLP) pair, in order; a run is one ``lax.scan``
over its layers.  Parameters are stacked BY KIND: ``params["mamba"]``
over the Mamba mixers, ``params["attn"]`` over the attention mixers,
``params["latent"]`` over the latent ones, ``params["dense"]`` over the
dense MLPs, ``params["layers"]["mlp"]`` over the expert blocks and the two
norms of ``params["layers"]`` over all layers; a run's body indexes them
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

**The Mamba-2 mixer** has three forms over one set of equations
(``[z | xBC | dt] = a W_in``; ``xBC = silu(causal depthwise conv)``;
``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``; per head ``S_t =
exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``; ``out
= (rmsnorm(y * silu(z)) * w) W_out``):

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

from areal_tpu.engine.sampling import sample_and_advance
from areal_tpu.models import paged
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models import quantize
from areal_tpu.models import moe
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
    window_put,
)
from areal_tpu.observability.tracing import region
from areal_tpu.ops import ssm as ssm_ops

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


class Run(NamedTuple):
    kind: str  # the mixer: "attention" | "window" | "mamba" | "latent"
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


def _param_kind(kind: str) -> str:
    return "attention" if kind == "window" else kind


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
                    kind, mlp, l, seen.get(_param_kind(kind), 0),
                    seen.get(mlp, 0), 1, rope, seen.get("pool:" + kind, 0),
                )
            )
        seen[_param_kind(kind)] = seen.get(_param_kind(kind), 0) + 1
        seen["pool:" + kind] = seen.get("pool:" + kind, 0) + 1
        seen[mlp] = seen.get(mlp, 0) + 1
    return tuple(runs)


def pool_layer_numbers(cfg: TransformerConfig, kind: str) -> np.ndarray:
    """The numbers, in ``params["attn"]``'s stack, of the layers whose
    pages live in ``kind``'s pool, in the pool's order."""
    return np.array(
        [
            j for run in layer_plan(cfg) if run.kind == kind
            for j in range(run.first_of_kind, run.first_of_kind + run.count)
        ],
        np.int32,
    )


def _run_indices(run: Run):
    """``(layer numbers, numbers in the mixer's parameter stack, among
    the MLP kind, in the mixer's pool)`` of a run's layers."""
    return tuple(
        jnp.arange(first, first + run.count)
        for first in (
            run.first_layer, run.first_of_kind, run.first_of_mlp,
            run.first_in_pool,
        )
    )


def _mixer_region(run: Run):
    """The region of a layer's first half (norm, mixer, residual add)."""
    if run.kind == "mamba":
        return region("areal.ssm")
    if run.kind == "window":
        return region("areal.attn.window")
    return region("areal.attn")


def _rope_cfg(cfg: TransformerConfig, run: Run) -> TransformerConfig:
    """``cfg`` as ``_attn_qkv`` is to read it for a run's layers."""
    if cfg.use_rope == run.rope:
        return cfg
    return dataclasses.replace(cfg, use_rope=run.rope)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _uniform_stack(key, n: int, shape, bound: float, dtype):
    """``[n, *shape]`` uniform in ``(-bound, bound)``, made one layer at a
    time in float32 and kept in ``dtype``: the float32 transient is one
    layer's, not the stack's."""

    @jax.jit
    def make(keys):
        return jax.lax.map(
            lambda k: jax.random.uniform(
                k, shape, F32, -bound, bound
            ).astype(dtype),
            keys,
        )

    return make(jax.random.split(key, n))


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
    final norm of rms 1), beside an embedding of rms 0.5."""
    assert cfg.is_hybrid and cfg.is_moe
    dt = jnp.dtype(cfg.dtype)
    L, Le, Ld = cfg.n_layers, cfg.n_expert_layers, cfg.n_dense_layers
    Lm = cfg.n_mamba_layers
    Ll = L - Lm if cfg.is_latent else 0
    La = L - Lm - Ll  # attention and window layers: one stack
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
    }
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
    embed_rms = EMBED_RMS if cfg.tied_embedding else 0.5
    params: Params = {
        "embed": {
            "weight": _uniform_stack(
                next(keys), 1, (cfg.vocab_size, D), embed_rms * np.sqrt(3.0), dt
            )[0]
        },
        "layers": {
            "attn_norm": {"scale": ones(L, D)},
            "mlp_norm": {"scale": ones(L, D)},
            "mlp": mlp,
        },
    }
    if Lm:
        params["mamba"] = {
            "in_proj": {"w": mat(Lm, (D, di + cd + H), D)},
            "conv": {"w": mat(Lm, (K, cd), K), "b": mat(Lm, (cd,), 16)},
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt),
            "A_log": jnp.log(
                jax.random.uniform(next(keys), (Lm, H), F32, 1.0, 16.0)
            ).astype(dt),
            "D": ones(Lm, H),
            "norm": {"scale": ones(Lm, di)},
            "out_proj": {"w": mat(Lm, (di, D), di)},
        }
    if La:
        params["attn"] = {
            "q": {"w": mat(La, (D, Hq * hd), D)},
            "k": {"w": mat(La, (D, Hkv * hd), D)},
            "v": {"w": mat(La, (D, Hkv * hd), D)},
            "o": {"w": mat(La, (Hq * hd, D), Hq * hd)},
        }
    params["final_norm"] = {"scale": ones(D)}
    if Ll:
        rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
        nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
        m = partial(mat, keys=more)
        params["latent"] = {
            "q_a": {"w": m(Ll, (D, rq), D)},
            "q_a_norm": {"scale": ones(Ll, rq, keys=more)},
            "q_b": {"w": m(Ll, (rq, Hq * hd), rq)},
            "kv_a": {"w": m(Ll, (D, cfg.kv_latent_dim), D)},
            "kv_a_norm": {"scale": ones(Ll, rkv, keys=more)},
            # the published kv_b, its key columns and its value columns
            # apart: the absorbed form reads each alone
            "k_b": {"w": m(Ll, (rkv, Hq * nope), rkv)},
            "v_b": {"w": m(Ll, (rkv, Hq * vd), rkv)},
            "o": {"w": m(Ll, (Hq * vd, D), Hq * vd)},
        }
    if Ld:
        Fd = cfg.intermediate_dim
        params["dense"] = {
            "gate": {"w": mat(Ld, (D, Fd), D, keys=more)},
            "up": {"w": mat(Ld, (D, Fd), D, keys=more)},
            "down": {"w": mat(Ld, (Fd, D), Fd, keys=more)},
        }
    if not cfg.tied_embedding:
        params["lm_head"] = {
            "w": _uniform_stack(
                next(more), 1, (D, cfg.vocab_size), 1.0 / np.sqrt(D), dt
            )[0]
        }
    return params


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


# ---------------------------------------------------------------------------
# the Mamba-2 mixer
# ---------------------------------------------------------------------------


def _split_in_proj(cfg: TransformerConfig, mp: Params, h):
    """``(z [.., d_inner], xBC [.., conv_dim], dt_raw [.., H])``."""
    di, cd = cfg.mamba_d_inner, cfg.mamba_conv_dim
    zxd = _proj(mp["in_proj"], h)
    return zxd[..., :di], zxd[..., di : di + cd], zxd[..., di + cd :]


def _split_conv_out(cfg: TransformerConfig, xbc):
    """``(x [.., d_inner], B [.., N], C [.., N])`` (one group: B and C are
    shared by all heads)."""
    assert cfg.mamba_n_groups == 1, "one B/C group is what is written here"
    di, N = cfg.mamba_d_inner, cfg.mamba_d_state
    return xbc[..., :di], xbc[..., di : di + N], xbc[..., di + N :]


def _dt_and_a(mp: Params, dt_raw):
    dt = jax.nn.softplus(dt_raw.astype(F32) + mp["dt_bias"].astype(F32))
    return dt, -jnp.exp(mp["A_log"].astype(F32))


def _mamba_out(cfg: TransformerConfig, mp: Params, y, x, z):
    """``y`` [.., d_inner] float32 (without the skip) -> the mixer's
    output: skip ``D x``, gate BEFORE the norm, norm over all of
    ``d_inner``, output projection."""
    P = cfg.mamba_head_dim
    y = y + jnp.repeat(mp["D"].astype(F32), P) * x.astype(F32)
    y = y * jax.nn.silu(z.astype(F32))
    y = _norm(y, mp["norm"], cfg).astype(z.dtype)
    return _proj(mp["out_proj"], y)


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
    [B, T, N], ``s0`` [B, N, H, P]; all float32.  Returns ``(y [B, T, H,
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
    tail = jax.lax.dynamic_index_in_dim(conv, j, 0, keepdims=False)
    xp = jnp.concatenate([tail, xbc[None].astype(tail.dtype)], axis=0)
    w = mp["conv"]["w"].astype(F32)  # [K, cd]
    acc = mp["conv"]["b"].astype(F32) + jnp.sum(
        w[:, None, :] * xp.astype(F32), axis=0
    )
    conv = jax.lax.dynamic_update_index_in_dim(
        conv, jnp.where(live[None, :, None], xp[1:], tail), j, 0
    )
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


def latent_q(cfg: TransformerConfig, ap: Params, h, rope_cs):
    """``(q_nope [B, T, H, nope], q_rope [B, T, H, rope])``, the rope part
    roped."""
    B, T, _ = h.shape
    c_q = _norm(_proj(ap["q_a"], h), ap["q_a_norm"], cfg)
    q = _proj(ap["q_b"], c_q).reshape(B, T, cfg.n_q_heads, cfg.head_dim)
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], rope_apply(q[..., nope:], *rope_cs)


def latent_kv(cfg: TransformerConfig, ap: Params, h, rope_cs):
    """What a token leaves in the cache: ``(c_kv [B, T, rank]`` after its
    norm, ``k_rope [B, T, rope]`` roped, ONE for all heads)``."""
    ckr = _proj(ap["kv_a"], h)
    r = cfg.kv_lora_rank
    c_kv = _norm(ckr[..., :r], ap["kv_a_norm"], cfg)
    k_rope = rope_apply(ckr[..., None, r:], *rope_cs)[..., 0, :]
    return c_kv, k_rope


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
    cfg: TransformerConfig, params: Params, run: Run, l, e, x, valid, a=None
):
    """The second half of layer ``l`` (number ``e`` among its MLP kind);
    ``a``: the mixer's input, which the router reads where
    ``cfg.moe_router_input == "attn"``.  Returns ``(x, pairs, routed [B,
    T, K], extra rounds)``, the last three None after a dense MLP and the
    last one wherever the experts took the product over every held one:
    see ``moe.held_moe_mlp``."""
    h = _norm(x, _at(params["layers"]["mlp_norm"], l), cfg)
    if run.mlp == "dense":
        dp = _at(params["dense"], e)
        hid = _activation(_proj(dp["gate"], h), cfg.activation) * _proj(
            dp["up"], h
        )
        return _res(cfg, x, _proj(dp["down"], hid)), None, None, None
    out, pairs, routed, rounds = held_moe_mlp(
        cfg, h, params["layers"]["mlp"], valid=valid,
        router_input=a if cfg.moe_router_input == "attn" else None, layer=e,
    )
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


def hidden_states(
    params: Params, cfg: TransformerConfig, tokens, positions, seg_ids
):
    """Final-norm hidden states [B, T, D] of ONE segment a row
    (``seg_ids`` 1 on a prefix, 0 on the padding after it): a recurrent
    state has no packing of several sequences in a row."""
    B, T = tokens.shape
    n_valid = jnp.sum(seg_ids != 0, axis=1, dtype=jnp.int32)
    valid = seg_ids != 0
    x = _embed(params, cfg, tokens, positions)
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

    def attend(q, k, v, mask):
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
        return o.reshape(B, T, -1).astype(x.dtype)

    def mixer(run: Run, h, j):
        if run.kind == "mamba":
            out, _, _ = mamba_chunk(
                cfg, _at(params["mamba"], j), h, n_valid, s0, tail0
            )
            return out
        if run.kind in ("attention", "window"):
            ap = _at(params["attn"], j)
            q, k, v = _attn_qkv(
                _rope_cfg(cfg, run), {"attn": ap}, h, positions, None
            )
            m = mask_window if run.kind == "window" else mask
            return _proj(ap["o"], attend(q, k, v, m))
        ap = _at(params["latent"], j)
        q_nope, q_rope = latent_q(cfg, ap, h, rope_cs)
        k, v = latent_expand(cfg, ap, *latent_kv(cfg, ap, h, rope_cs))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        return _proj(ap["o"], attend(q, k, v, mask))

    for run in layer_plan(cfg):

        def body(x, idx, run=run):
            l, j, e, _ = idx
            with _mixer_region(run):
                a = _norm(x, _at(params["layers"]["attn_norm"], l), cfg)
                x = _res(cfg, x, mixer(run, a, j))
            x, _, _, _ = _mlp_half(cfg, params, run, l, e, x, valid, a)
            return x, None

        x, _ = scan_layers(body, x, _run_indices(run))
    return _final_norm(params, cfg, x)


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
    static_argnames=("cfg", "use_kernel"),
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
    where the batch's shape takes the product over every held expert)."""
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
        C, cfg.n_q_heads, k_pool, tables, read_lens, use_kernel
    )
    window = cfg.sliding_window if cfg.n_window_layers else None
    if window:
        mask_chunk_win = mask_chunk & (iot[:, None] - iot[None, :] < window)
        plan_win = paged._prefix_plan(
            C, cfg.n_q_heads, win_pools[0], win_tables, read_lens,
            use_kernel, window=window,
        )

    latent = cfg.is_latent
    rope_cs = latent_rope_tables(cfg, positions) if latent else None
    if cfg.n_mamba_layers:
        with region("areal.ssm"):
            tails0 = jnp.where(
                fresh[None, :, None, None], 0, _get_conv_tails(conv, slots)
            )  # [Lm, F, K-1, conv_dim]

    def mamba_mixer(h, ssm, j, tail0):
        mp = _at(params["mamba"], j)
        if use_kernel:
            s0 = ssm_ops.ssm_state_rows(
                ssm, j, slots, interpret=paged.kernel_interpret()
            )
        else:
            s0 = _get_state_rows(ssm, j, slots)
        s0 = jnp.where(fresh[:, None, None], 0.0, s0)
        out, s1, tail1 = mamba_chunk(cfg, mp, h, chunk_lens, s0, tail0)
        return out, _put_state_rows(ssm, j, slots, s1, row_valid), tail1

    def attn_mixer(run, h, j, p):
        ap = _at(params["attn"], j)
        q, k, v = _attn_qkv(
            _rope_cfg(cfg, run), {"attn": ap}, h, positions, None
        )
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
        attn = paged.chunk_attention(q, k, v, prefix, mask, scale, h.dtype)
        return _proj(ap["o"], attn), (
            k.astype(k_pool.dtype), v.astype(v_pool.dtype)
        )

    def latent_mixer(h, j):
        ap = _at(params["latent"], j)
        q_nope, q_rope = latent_q(cfg, ap, h, rope_cs)
        c_kv, k_rope = latent_kv(cfg, ap, h, rope_cs)
        k, v = latent_expand(cfg, ap, c_kv, k_rope)
        acc, m, lsum = paged._prefix_partials(
            latent_absorbed_q(cfg, ap, q_nope, q_rope), k_pool, None,
            tables, read_lens, j, use_kernel, plan=plan, scale=scale,
            value_dim=cfg.kv_lora_rank,
        )
        attn = paged.chunk_attention(
            jnp.concatenate([q_nope, q_rope], axis=-1), k, v,
            (latent_values_out(cfg, ap, acc), m, lsum),
            mask_chunk, scale, h.dtype,
        )
        entry = latent_entry(cfg, c_kv, k_rope)[:, :, None, :]
        return _proj(ap["o"], attn), (entry.astype(k_pool.dtype),)

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
    for run in layer_plan(cfg):
        l_idx, j_idx, e_idx, p_idx = _run_indices(run)

        def body(carry, inp, run=run):
            x, ssm, pairs, rounds = carry
            l, j, e, p = inp[:4]
            with _mixer_region(run):
                a = _norm(x, _at(params["layers"]["attn_norm"], l), cfg)
                if run.kind == "mamba":
                    out, ssm, kept = mamba_mixer(a, ssm, j, inp[4])
                elif run.kind == "latent":
                    out, kept = latent_mixer(a, j)
                else:
                    out, kept = attn_mixer(run, a, j, p)
                x = _res(cfg, x, out)
            x, n, r, m = _mlp_half(cfg, params, run, l, e, x, valid, a)
            return (x, ssm, _add_pairs(pairs, n), _add_pairs(rounds, m)), (kept, r)

        xs = (l_idx, j_idx, e_idx, p_idx)
        if run.kind == "mamba":
            of_kind = slice(run.first_of_kind, run.first_of_kind + run.count)
            xs += (tails0[of_kind],)
        carry, (kept, r) = scan_layers(body, carry, xs)
        if run.kind == "mamba":
            tails1.append(kept)
        elif run.kind == "window":
            chunk_kv_win.append(kept)
        else:
            chunk_kv.append(kept)
        if r is not None:
            routed.append(r)
    x, ssm, pairs, rounds = carry
    if tails1:
        conv = _put_conv_tails(
            conv, slots, jnp.concatenate(tails1, axis=0), row_valid
        )
    pools = (k_pool,) if latent else (k_pool, v_pool)
    vals = tuple(jnp.concatenate(t, axis=0) for t in zip(*chunk_kv))
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
        win_pools = paged.write_kv_runs(
            win_pools, vals_win, win_tables, starts, chunk_lens
        )
    k_pool, v_pool = pools + ((v_pool,) if latent else ())
    logits = _logits(params, cfg, paged.last_valid(x, chunk_lens))[:, 0]
    out = (
        logits, k_pool, v_pool, ssm, conv, pairs,
        jnp.concatenate(routed, axis=0), rounds,
    )
    return out if win_pools is None else out + (win_pools,)


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk_size", "use_kernel", "max_len", "sample_fn", "stop_fn",
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
    on the chip)."""
    B = cur_tokens.shape[0]
    W = chunk_size
    _, _, Hkv, _, hd = k_pool.shape
    La = cfg.n_attn_layers  # attention, window or latent: the chunk's KV
    latent = cfg.is_latent
    base_lens = lengths
    read_lens = jnp.where(active, base_lens, 0)
    scale = _attn_scale(cfg)
    plan = paged._prefix_plan(
        1, cfg.n_q_heads, k_pool, tables, read_lens, use_kernel
    )
    window = cfg.sliding_window if cfg.n_window_layers else None
    if window:
        assert W < window, (W, window)
        plan_win = paged._prefix_plan(
            1, cfg.n_q_heads, win_pools[0], win_tables, read_lens,
            use_kernel, window=window,
        )
    # the chunk's own KV (latent layers: its latent entries, which are
    # keys and values both), one pool write after the chunk
    wk = jnp.zeros((La, W, B, Hkv, hd), k_pool.dtype)
    wv = jnp.zeros((0 if latent else La, W, B, Hkv, hd), k_pool.dtype)

    def step(i, st):
        (lengths_, cur, active, budgets, wk, wv, wvalid, ssm, conv, out_t,
         out_l, emitted, rng, pairs, routed) = st
        positions = lengths_[:, None]
        x = _embed(params, cfg, cur[:, None], positions)
        wvalid = wvalid.at[i].set(active)
        mask_win = wvalid.T[:, None, None, None, :]  # [B,1,1,1,W]
        live = active[:, None]
        rope_cs = latent_rope_tables(cfg, positions) if latent else None

        def attn_mixer(run, h, wk, wv, j, p):
            ap = _at(params["attn"], j)
            q, k, v = _attn_qkv(
                _rope_cfg(cfg, run), {"attn": ap}, h, positions, None
            )
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
                prefix, mask_win, scale, h.dtype,
            )
            return _proj(ap["o"], attn), wk, wv

        def latent_mixer(h, wk, j):
            ap = _at(params["latent"], j)
            q_nope, q_rope = latent_q(cfg, ap, h, rope_cs)
            c_kv, k_rope = latent_kv(cfg, ap, h, rope_cs)
            wk = window_put(
                wk, latent_entry(cfg, c_kv, k_rope)[:, :, None, :], j, i
            )
            q = latent_absorbed_q(cfg, ap, q_nope, q_rope)
            prefix = paged._prefix_partials(
                q, k_pool, None, tables, read_lens, j, use_kernel,
                plan=plan, scale=scale, value_dim=cfg.kv_lora_rank,
            )
            wk_j = jax.lax.dynamic_index_in_dim(wk, j, 0, keepdims=False)
            o_lat = paged.window_attention(
                q, wk_j, wk_j[..., : cfg.kv_lora_rank], prefix, mask_win,
                scale, h.dtype,
            ).reshape(B, 1, cfg.n_q_heads, cfg.kv_lora_rank)
            attn = latent_values_out(cfg, ap, o_lat).reshape(B, 1, -1)
            return _proj(ap["o"], attn), wk

        carry, step_routed = (x, wk, wv, ssm, conv, pairs), []
        for run in layer_plan(cfg):

            def body(carry, idx, run=run):
                x, wk, wv, ssm, conv, pairs = carry
                l, j, e, p = idx
                with _mixer_region(run):
                    a = _norm(x, _at(params["layers"]["attn_norm"], l), cfg)
                    if run.kind == "mamba":
                        out, ssm, conv = mamba_step(
                            cfg, _at(params["mamba"], j), a,
                            ssm, conv, j, active, use_kernel,
                        )
                    elif run.kind == "latent":
                        out, wk = latent_mixer(a, wk, j)
                    else:
                        out, wk, wv = attn_mixer(run, a, wk, wv, j, p)
                    x = _res(cfg, x, out)
                x, n, r, _ = _mlp_half(cfg, params, run, l, e, x, live, a)
                return (x, wk, wv, ssm, conv, _add_pairs(pairs, n)), (
                    None if r is None else r[:, 0].T
                )

            carry, r = scan_layers(body, carry, _run_indices(run))
            if r is not None:
                step_routed.append(r)  # [run.count, K, B]
        x, wk, wv, ssm, conv, pairs = carry
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
                out_t, out_l, emitted, rng, pairs, routed)

    st = (
        base_lens, cur_tokens, active, budgets, wk, wv,
        jnp.zeros((W, B), bool), ssm, conv,
        jnp.zeros((B, W), jnp.int32), jnp.zeros((B, W), F32),
        jnp.zeros((B, W), bool), rng, _pairs_zero(cfg),
        jnp.zeros(
            (W, cfg.n_expert_layers, cfg.n_experts_per_tok, B), jnp.int32
        ),
    )
    (lengths_, cur, active, budgets, wk, wv, _, ssm, conv, out_t, out_l,
     emitted, rng, pairs, routed) = jax.lax.fori_loop(0, W, step, st)
    pools, vals = (k_pool, v_pool), (wk.swapaxes(1, 2), wv.swapaxes(1, 2))
    if latent:
        pools, vals = pools[:1], vals[:1]
    counts = lengths_ - base_lens
    if window:
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
    k_pool, v_pool = pools + ((v_pool,) if latent else ())
    out = (k_pool, v_pool, ssm, conv, lengths_, out_t, out_l, emitted, cur,
           active, budgets, rng, pairs, routed)
    return out if win_pools is None else out + (win_pools,)
