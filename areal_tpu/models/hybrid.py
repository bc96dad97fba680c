"""A stack stated by kind: Mamba-2 layers with a recurrent state beside
attention layers with paged KV, every layer followed by the expert block
this program's share of the experts gives (granitemoehybrid).

    h0 = embed_scale * embed[tokens]
    per layer:  h += r * mixer(rmsnorm(h));  m = rmsnorm(h)
                h += r * (experts(m) + shared(m))
    logits = rmsnorm(h) @ embed^T / logits_divisor

**The layer plan** (:func:`layer_plan`): the published ``layer_types`` cut
into runs of one kind, in order; a run is one ``lax.scan`` over its
layers.  Parameters are stacked BY KIND: ``params["mamba"]`` over the
Mamba mixers, ``params["attn"]`` over the attention mixers,
``params["layers"]`` (the two norms and the expert block) over all
layers; a run's body indexes them by the layer's number and by its number
among its kind.  The dense stack of ``transformer.py`` / ``paged.py`` does
not go through this module, and this module calls their functions where
they fit (``_norm``, ``_embed``, ``_attn_qkv``, the paged kernels and
``write_kv_runs``).

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

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.engine.sampling import call_sample_fn
from areal_tpu.models import paged
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.moe import held_moe_mlp
from areal_tpu.models.transformer import (
    Params,
    _attn_qkv,
    _embed,
    _norm,
    _proj,
    make_attention_mask,
)
from areal_tpu.ops import ssm as ssm_ops

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


class Run(NamedTuple):
    kind: str  # "attention" | "mamba"
    first_layer: int  # number of the run's first layer in the stack
    first_of_kind: int  # its number among the layers of its kind
    count: int


def layer_plan(cfg: TransformerConfig) -> Tuple[Run, ...]:
    """``cfg.layer_types`` as runs of one kind, in the published order."""
    runs, seen = [], {"attention": 0, "mamba": 0}
    for l, kind in enumerate(cfg.layer_types):
        if runs and runs[-1].kind == kind:
            runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
        else:
            runs.append(Run(kind, l, seen[kind], 1))
        seen[kind] += 1
    return tuple(runs)


def _run_indices(run: Run):
    return (
        jnp.arange(run.first_layer, run.first_layer + run.count),
        jnp.arange(run.first_of_kind, run.first_of_kind + run.count),
    )


def _at(tree, i):
    """Layer ``i`` of a stacked tree (a dynamic slice inside a scan: what
    ``lax.scan`` over the stack itself reads)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree
    )


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


#: rms of the random embedding (see :func:`init_params`)
EMBED_RMS = 0.05


def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    """Seeded random weights in ``cfg.dtype``, made where jax's default
    device is (for a server: its chip), kind by kind: a float32 host copy
    of a 5 B-parameter share is 20 GB and most of a minute.

    Matrices are uniform in ``+-1/sqrt(fan_in)``; ``A`` in (-16, -1),
    ``dt_bias`` so that ``dt`` falls in (0.001, 0.1) (the Mamba-2
    initialisation); scales and skips around 1.  The embedding (and tied
    head) has rms ``EMBED_RMS``: with a tied head the logit of the token
    a position HOLDS is ``D x rms x (the embedding's share of the hidden
    state) / logits_divisor``, about 8 at granite's sizes against 0.2
    for every other token, so that token repeats with a probability of a
    few percent and a log-probability says something about the hidden
    state.  (At rms 0.29 the repeat took probability 1 - 1e-6 and every
    log-probability read 0 to five places, my chip run, PR 31; at the
    dense family's 1/sqrt(D) the logits are uniform to 0.01.)"""
    assert cfg.is_hybrid and cfg.is_moe and cfg.tied_embedding
    dt = jnp.dtype(cfg.dtype)
    L, La, Lm = cfg.n_layers, cfg.n_attn_layers, cfg.n_mamba_layers
    D, E, Eh = cfg.hidden_dim, cfg.n_experts, cfg.n_held_experts
    Fe, Fs = cfg.moe_intermediate_dim, cfg.shared_expert_dim
    Hq, Hkv, hd = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    H, di, cd = cfg.mamba_n_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    K = cfg.mamba_d_conv
    keys = iter(jax.random.split(key, 40))

    def mat(n, shape, fan_in):
        return _uniform_stack(next(keys), n, shape, 1.0 / np.sqrt(fan_in), dt)

    def ones(*shape):
        # scales and skips around 1, not AT 1: a scale read from the
        # wrong layer, or left out, has to show against the reference
        return jax.random.uniform(next(keys), shape, F32, 0.75, 1.25).astype(dt)

    mlp: Params = {
        "router": {"w": mat(L, (D, E), D)},
        "experts": {
            # all three [E_held, F, D]: see moe.dense_expert_compute
            "gate": mat(L, (Eh, Fe, D), D),
            "up": mat(L, (Eh, Fe, D), D),
            "down": mat(L, (Eh, Fe, D), Fe),
        },
    }
    if Fs:
        mlp["shared"] = {
            "gate": {"w": mat(L, (D, Fs), D)},
            "up": {"w": mat(L, (D, Fs), D)},
            "down": {"w": mat(L, (Fs, D), Fs)},
        }
    u = jax.random.uniform(next(keys), (Lm, H), F32)
    dt0 = jnp.exp(u * (np.log(0.1) - np.log(0.001)) + np.log(0.001))
    params: Params = {
        "embed": {
            "weight": _uniform_stack(
                next(keys), 1, (cfg.vocab_size, D), EMBED_RMS * np.sqrt(3.0), dt
            )[0]
        },
        "layers": {
            "attn_norm": {"scale": ones(L, D)},
            "mlp_norm": {"scale": ones(L, D)},
            "mlp": mlp,
        },
        "mamba": {
            "in_proj": {"w": mat(Lm, (D, di + cd + H), D)},
            "conv": {"w": mat(Lm, (K, cd), K), "b": mat(Lm, (cd,), 16)},
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dt),
            "A_log": jnp.log(
                jax.random.uniform(next(keys), (Lm, H), F32, 1.0, 16.0)
            ).astype(dt),
            "D": ones(Lm, H),
            "norm": {"scale": ones(Lm, di)},
            "out_proj": {"w": mat(Lm, (di, D), di)},
        },
        "attn": {
            "q": {"w": mat(La, (D, Hq * hd), D)},
            "k": {"w": mat(La, (D, Hkv * hd), D)},
            "v": {"w": mat(La, (D, Hkv * hd), D)},
            "o": {"w": mat(La, (Hq * hd, D), Hq * hd)},
        },
        "final_norm": {"scale": ones(D)},
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
# shared pieces of the three programs
# ---------------------------------------------------------------------------


def _res(cfg: TransformerConfig, x, branch):
    if cfg.residual_scale is None:
        return x + branch
    return x + jnp.asarray(cfg.residual_scale, x.dtype) * branch


def _attn_scale(cfg: TransformerConfig) -> float:
    if cfg.attention_scale is None:
        return 1.0 / np.sqrt(cfg.head_dim)
    return cfg.attention_scale


def _expert_block(cfg: TransformerConfig, lp: Params, x, valid):
    """The second half of every layer; returns ``(x, pairs, routed [B,
    T, K])``: see ``moe.held_moe_mlp``."""
    out, pairs, routed = held_moe_mlp(
        cfg, _norm(x, lp["mlp_norm"], cfg), lp["mlp"], valid=valid
    )
    return _res(cfg, x, out), pairs, routed


def _head_logits(params: Params, cfg: TransformerConfig, x):
    """Logits of final-norm hidden states ``x``: the tied head's products
    come OUT in float32.  A ``bfloat16 @ bfloat16`` product comes out in
    bfloat16 whatever it accumulates in, and this family's logits are
    not small (tens to a hundred before ``logits_divisor``):
    rounded to 8 bits they moved the server's log-probabilities by
    0.024-0.027 at most and 0.0065-0.0071 on average, as much as serving
    every matrix in float8 (my chip runs, PR 31: PERF.md section 6)."""
    assert cfg.tied_embedding and not cfg.is_critic
    w = params["embed"]["weight"].astype(x.dtype).T
    logits = jnp.matmul(x, w, preferred_element_type=F32)
    logits = logits.astype(jnp.dtype(cfg.logits_dtype))
    if cfg.logits_divisor is not None:
        logits = logits / cfg.logits_divisor
    return logits


def _logits(params: Params, cfg: TransformerConfig, x):
    return _head_logits(params, cfg, _norm(x, params["final_norm"], cfg))


def _pairs_zero(cfg: TransformerConfig):
    return jnp.zeros((cfg.n_held_experts + 1,), jnp.int32)


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
    s0 = jnp.zeros((B, cfg.mamba_d_state, cfg.mamba_d_inner), F32)
    tail0 = jnp.zeros((B, cfg.mamba_d_conv - 1, cfg.mamba_conv_dim), x.dtype)
    scale = _attn_scale(cfg)

    def mamba_body(x, idx):
        l, j = idx
        lp, mp = _at(params["layers"], l), _at(params["mamba"], j)
        out, _, _ = mamba_chunk(
            cfg, mp, _norm(x, lp["attn_norm"], cfg), n_valid, s0, tail0
        )
        x, _, _ = _expert_block(cfg, lp, _res(cfg, x, out), valid)
        return x, None

    def attn_body(x, idx):
        l, j = idx
        lp, ap = _at(params["layers"], l), _at(params["attn"], j)
        h = _norm(x, lp["attn_norm"], cfg)
        q, k, v = _attn_qkv(cfg, {"attn": ap}, h, positions, None)
        Hkv, r = cfg.n_kv_heads, cfg.n_q_heads // cfg.n_kv_heads
        s = jnp.einsum(
            "bikrd,bjkd->bkrij",
            q.reshape(B, T, Hkv, r, -1).astype(F32), k.astype(F32),
        ) * scale
        s = jnp.where(mask[:, None, None], s, -1e30)
        o = jnp.einsum(
            "bkrij,bjkd->bikrd", jax.nn.softmax(s, axis=-1), v.astype(F32)
        ).reshape(B, T, -1).astype(x.dtype)
        x, _, _ = _expert_block(
            cfg, lp, _res(cfg, x, _proj(ap["o"], o)), valid
        )
        return x, None

    for run in layer_plan(cfg):
        body = mamba_body if run.kind == "mamba" else attn_body
        x, _ = jax.lax.scan(body, x, _run_indices(run))
    return _norm(x, params["final_norm"], cfg)


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


def _get_conv_tails(conv, slots):
    """``conv[:, :, slots]`` as ``[Lm, F, K-1, conv_dim]``: every Mamba
    layer's tail of each filling row, one ``dynamic_slice`` a row."""
    Lm, Km1, _, cd = conv.shape
    rows = [
        jax.lax.dynamic_slice(conv, (0, 0, slots[i], 0), (Lm, Km1, 1, cd))
        for i in range(slots.shape[0])
    ]
    return jnp.concatenate(rows, axis=2).swapaxes(1, 2)


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
)
def hybrid_fill_chunk(
    params: Params,
    k_pool: jax.Array,  # [La, NB, Hkv, BS, hd]
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
):
    """One prefill chunk for F filling rows of a hybrid stack: the
    hybrid twin of ``paged.paged_fill_chunk``.  An attention layer attends
    the chunk and the row's paged prefix and leaves its KV for ONE pool
    write after the stack; a Mamba layer starts from the row's slot
    (from zero where ``starts`` is 0: a slot is never cleared by a pass
    of its own) and leaves the state after the chunk's last valid token
    there.  The conv tails are read before the stack and written after
    it, like the KV: carried through the layer loops, the TPU compiler
    moved the whole ``conv`` array into its fast memory for the loops'
    duration, where part of it came back overwritten (three layers' tails
    of slots 25-63 in one fill in twenty, my chip runs, PR 31: PERF.md
    section 6).  Returns ``(last_logits [F, V], k_pool, v_pool, ssm, conv,
    pairs [E_held + 1], routed [L, F, C, K])``: the last is every layer's
    routed experts of every position (``moe.held_moe_mlp``)."""
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

    tails0 = jnp.where(
        fresh[None, :, None, None], 0, _get_conv_tails(conv, slots)
    )  # [Lm, F, K-1, conv_dim]

    def mamba_body(carry, inp):
        x, ssm, pairs = carry
        l, j, tail0 = inp
        lp, mp = _at(params["layers"], l), _at(params["mamba"], j)
        if use_kernel:
            s0 = ssm_ops.ssm_state_rows(
                ssm, j, slots, interpret=paged.kernel_interpret()
            )
        else:
            s0 = _get_state_rows(ssm, j, slots)
        s0 = jnp.where(fresh[:, None, None], 0.0, s0)
        out, s1, tail1 = mamba_chunk(
            cfg, mp, _norm(x, lp["attn_norm"], cfg), chunk_lens, s0, tail0
        )
        ssm = _put_state_rows(ssm, j, slots, s1, row_valid)
        x, p, routed = _expert_block(cfg, lp, _res(cfg, x, out), valid)
        return (x, ssm, pairs + p), (tail1, routed)

    def attn_body(carry, idx):
        x, ssm, pairs = carry
        l, j = idx
        lp, ap = _at(params["layers"], l), _at(params["attn"], j)
        h = _norm(x, lp["attn_norm"], cfg)
        q, k, v = _attn_qkv(cfg, {"attn": ap}, h, positions, None)
        prefix = paged._prefix_partials(
            q, k_pool, v_pool, tables, read_lens, j, use_kernel,
            plan=plan, scale=scale,
        )
        attn = paged.chunk_attention(
            q, k, v, prefix, mask_chunk, scale, x.dtype
        )
        x, p, routed = _expert_block(
            cfg, lp, _res(cfg, x, _proj(ap["o"], attn)), valid
        )
        return (x, ssm, pairs + p), (
            k.astype(k_pool.dtype), v.astype(v_pool.dtype), routed
        )

    carry = (x, ssm, _pairs_zero(cfg))
    window_kv, tails1, routed = [], [], []
    for run in layer_plan(cfg):
        l_idx, j_idx = _run_indices(run)
        if run.kind == "mamba":
            of_kind = slice(run.first_of_kind, run.first_of_kind + run.count)
            carry, (tails, r) = jax.lax.scan(
                mamba_body, carry, (l_idx, j_idx, tails0[of_kind])
            )
            tails1.append(tails)
        else:
            carry, (k, v, r) = jax.lax.scan(attn_body, carry, (l_idx, j_idx))
            window_kv.append((k, v))
        routed.append(r)
    x, ssm, pairs = carry
    if tails1:
        conv = _put_conv_tails(
            conv, slots, jnp.concatenate(tails1, axis=0), row_valid
        )
    if window_kv:
        ks, vs = (jnp.concatenate(t, axis=0) for t in zip(*window_kv))
        # the pool is written only after every layer has read it: without
        # the barrier a run of ONE layer is inlined, the kernel reads the
        # donated pool while the write loop wants it in place, and XLA
        # settles that with two copies of each pool
        x, k_pool, v_pool, ks, vs = jax.lax.optimization_barrier(
            (x, k_pool, v_pool, ks, vs)
        )
        k_pool, v_pool = paged.write_kv_runs(
            (k_pool, v_pool), (ks, vs), tables, starts, chunk_lens
        )
    last_idx = jnp.maximum(chunk_lens - 1, 0)
    x_last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
    logits = _logits(params, cfg, x_last)[:, 0]
    return (
        logits, k_pool, v_pool, ssm, conv, pairs,
        jnp.concatenate(routed, axis=0),
    )


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk_size", "use_kernel", "max_len", "sample_fn", "stop_fn",
    ),
    donate_argnums=(1, 2, 3, 4),
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
):
    """Up to ``chunk_size`` tokens for all active rows of a hybrid stack:
    the hybrid twin of ``paged.paged_decode_chunk`` (same window design
    for the attention layers' KV, same outputs), with every Mamba layer's
    state advanced in place for the rows live at each step.  Returns
    ``(k_pool, v_pool, ssm, conv, lengths, out_t, out_l, emitted, cur,
    active, budgets, rng, pairs [E_held + 1], routed [W, L, K, B])``: the
    last is every layer's routed experts at each step, for the position
    the step READ (row b's entry of step i means something where
    ``emitted[b, i]``; the row axis last, so that the array pads little
    on the chip)."""
    B = cur_tokens.shape[0]
    W = chunk_size
    La, _, Hkv, _, hd = k_pool.shape
    base_lens = lengths
    read_lens = jnp.where(active, base_lens, 0)
    scale = _attn_scale(cfg)
    plan = paged._prefix_plan(
        1, cfg.n_q_heads, k_pool, tables, read_lens, use_kernel
    )
    wk = jnp.zeros((La, W, B, Hkv, hd), k_pool.dtype)
    wv = jnp.zeros((La, W, B, Hkv, hd), k_pool.dtype)

    def step(i, st):
        (lengths_, cur, active, budgets, wk, wv, wvalid, ssm, conv, out_t,
         out_l, emitted, rng, pairs, routed) = st
        positions = lengths_[:, None]
        x = _embed(params, cfg, cur[:, None], positions)
        wvalid = wvalid.at[i].set(active)
        mask_win = wvalid.T[:, None, None, None, :]  # [B,1,1,1,W]
        live = active[:, None]

        def mamba_body(carry, idx):
            x, wk, wv, ssm, conv, pairs = carry
            l, j = idx
            lp, mp = _at(params["layers"], l), _at(params["mamba"], j)
            out, ssm, conv = mamba_step(
                cfg, mp, _norm(x, lp["attn_norm"], cfg), ssm, conv, j,
                active, use_kernel,
            )
            x, p, r = _expert_block(cfg, lp, _res(cfg, x, out), live)
            return (x, wk, wv, ssm, conv, pairs + p), r[:, 0].T

        def attn_body(carry, idx):
            x, wk, wv, ssm, conv, pairs = carry
            l, j = idx
            lp, ap = _at(params["layers"], l), _at(params["attn"], j)
            h = _norm(x, lp["attn_norm"], cfg)
            q, k, v = _attn_qkv(cfg, {"attn": ap}, h, positions, None)
            wk = jax.lax.dynamic_update_slice(
                wk, k.swapaxes(0, 1)[None].astype(wk.dtype), (j, i, 0, 0, 0)
            )
            wv = jax.lax.dynamic_update_slice(
                wv, v.swapaxes(0, 1)[None].astype(wv.dtype), (j, i, 0, 0, 0)
            )
            prefix = paged._prefix_partials(
                q, k_pool, v_pool, tables, read_lens, j, use_kernel,
                plan=plan, scale=scale,
            )
            attn = paged.window_attention(
                q,
                jax.lax.dynamic_index_in_dim(wk, j, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(wv, j, 0, keepdims=False),
                prefix, mask_win, scale, x.dtype,
            )
            x, p, r = _expert_block(
                cfg, lp, _res(cfg, x, _proj(ap["o"], attn)), live
            )
            return (x, wk, wv, ssm, conv, pairs + p), r[:, 0].T

        carry, step_routed = (x, wk, wv, ssm, conv, pairs), []
        for run in layer_plan(cfg):
            body = mamba_body if run.kind == "mamba" else attn_body
            carry, r = jax.lax.scan(body, carry, _run_indices(run))
            step_routed.append(r)  # [run.count, K, B]
        x, wk, wv, ssm, conv, pairs = carry
        routed = jax.lax.dynamic_update_slice(
            routed, jnp.concatenate(step_routed, axis=0)[None], (i, 0, 0, 0)
        )
        logits = _logits(params, cfg, x)[:, 0]
        rng, sub = jax.random.split(rng)
        tok, logp = call_sample_fn(
            sample_fn, logits.astype(F32), sub, lengths_ + 1, row_seeds
        )
        tok = jnp.where(active, tok, 0)
        out_t = out_t.at[:, i].set(tok)
        out_l = out_l.at[:, i].set(jnp.where(active, logp, 0.0))
        emitted = emitted.at[:, i].set(active)
        new_lengths = lengths_ + active.astype(jnp.int32)
        budgets = budgets - active.astype(jnp.int32)
        active = (
            active & ~stop_fn(tok) & (budgets > 0) & (new_lengths < max_len)
        )
        return (new_lengths, tok, active, budgets, wk, wv, wvalid, ssm, conv,
                out_t, out_l, emitted, rng, pairs, routed)

    st = (
        base_lens, cur_tokens, active, budgets, wk, wv,
        jnp.zeros((W, B), bool), ssm, conv,
        jnp.zeros((B, W), jnp.int32), jnp.zeros((B, W), F32),
        jnp.zeros((B, W), bool), rng, _pairs_zero(cfg),
        jnp.zeros((W, cfg.n_layers, cfg.n_experts_per_tok, B), jnp.int32),
    )
    (lengths_, cur, active, budgets, wk, wv, _, ssm, conv, out_t, out_l,
     emitted, rng, pairs, routed) = jax.lax.fori_loop(0, W, step, st)
    k_pool, v_pool = paged.write_kv_runs(
        (k_pool, v_pool), (wk.swapaxes(1, 2), wv.swapaxes(1, 2)),
        tables, base_lens, lengths_ - base_lens,
    )
    return (k_pool, v_pool, ssm, conv, lengths_, out_t, out_l, emitted, cur,
            active, budgets, rng, pairs, routed)
