"""The plain reference of the ``falcon_h1`` family (Falcon-H1-34B-Instruct):
the forward pass as the model's ``config.json`` and the Falcon-H1 report
(arXiv 2507.22448) describe it, in straightforward ``jax.numpy`` and
float32: no kernel, no cache, no chunking, one sequence at a time,
"highest" matmul precision, the recurrence a sequential ``lax.scan`` over
positions.  Every multiplier is applied where it is written here; nothing
is folded into a weight.

    h = embedding_multiplier * embed[tokens]
    per layer:
        a = rmsnorm(h) * w_in                                   (input_layernorm)
        h = h + A(a) + M(a)          both branches read the SAME a
        m = rmsnorm(h) * w_ff                                   (pre_ff_layernorm)
        h = h + mlp_multipliers[1] * ((silu(mlp_multipliers[0] * (m W_gate)) * (m W_up)) W_down)
    logits = lm_head_multiplier * (rmsnorm(h) * w_final) W_head      (head untied)

* **A**, the attention branch: ``u = attention_in_multiplier * a``; ``q =
  rope(u W_q)``, ``k = rope(key_multiplier * (u W_k))``, ``v = u W_v``
  (no bias; RoPE of ``rope_theta`` over all of ``head_dim``, rotate-half:
  dims ``j`` and ``j + head_dim / 2`` turn together); grouped-query causal
  softmax of ``q k^T / sqrt(head_dim)``; ``A = attention_out_multiplier *
  (att W_o)``.
* **M**, the Mamba-2 branch: ``[z | x | B | C | dt] = (ssm_in_multiplier *
  a) W_in``, each segment times its entry of ``ssm_multipliers`` (widths
  ``d_ssm | d_ssm | G N | G N | H``); ``[x | B | C] = silu(causal depthwise
  conv of width mamba_d_conv over [x | B | C], with bias)``; ``dt =
  softplus(dt + dt_bias)``, ``A_h = -exp(A_log_h)``; per head ``h`` of
  group ``g = h // (H / G)``, state ``S`` in R^{d_head x d_state}: ``S_t =
  exp(dt_t A_h) S_{t-1} + dt_t x_t B_{g,t}^T``, ``y_t = S_t C_{g,t} + D_h
  x_t``; ``y = y * silu(z)``; an RMS norm over EACH group's ``d_ssm / G``
  channels, times ``w_norm`` (``mamba_rms_norm``, ``mamba_norm_before_gate``
  false); ``M = ssm_out_multiplier * (y W_out)``.

Departures from the published model, each the configuration's:

* **a sliced vocabulary**: the embedding and the head hold the rows
  ``[0, V')`` of the published ``vocab_size`` that the weight tree holds;
  ids, logits and log-probabilities are over the slice;
* no clamp of ``dt``: the published config gives no ``time_step_limit``.

What the config leaves open is the configuration file's ``assumed`` list
(the order of the two multipliers of a branch, the grouped norm, which of
``mlp_multipliers`` scales the gate, the weight names).

It reads the published ``config.json`` keys and the weight tree the system
under test serves (``embed.weight``, ``lm_head.w``, ``final_norm.scale``;
``layers.{attn_norm, mlp_norm}.scale``, ``attn.{q,k,v,o}.w``, ``mamba.
{in_proj.w, conv.{w [K, cd], b}, dt_bias, A_log, D, norm.scale,
out_proj.w}`` and ``dense.{gate,up,down}.w``, each stacked over the
layers); it calls no model code of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: queries a block of the attention scores and of the head's logits: the
#: scores of 5,120 positions over 20 heads are 2.1 GB in float32 whole
QUERY_BLOCK = 512


def _rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _w(p):
    return p["w"].astype(F32)


def _fp8_weights(tree):
    """Every matrix of ``tree`` as a server holding float8 (e4m3: four
    significant bits, smallest step 2^-9, largest value 448; one scale a
    matrix, its largest magnitude -> 448) would read it; vectors (norm
    scales, biases, ``A_log``, ``D``, ``dt_bias``) as they are.  The
    rounding is written out in float32, so it runs wherever this file
    does."""

    def one(w):
        if w.ndim < 2:
            return w
        w32 = w.astype(F32)
        s = jnp.max(jnp.abs(w32)) / 448.0
        x = w32 / s
        _, e = jnp.frexp(x)  # |x| in [2^(e-1), 2^e)
        step = jnp.exp2((jnp.maximum(e, -5) - 4).astype(F32))
        return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0) * s

    return jax.tree.map(one, tree)


def _rope(x, theta: float):
    """x [T, heads, hd] at positions 0..T-1, rotate-half."""
    T, _, hd = x.shape
    half = hd // 2
    # float(): the published 100000000000 is an integer beyond 32 bits
    inv = 1.0 / float(theta) ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]  # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(hf, a, ap):
    """a [T, D] -> [T, D] (the out multiplier applied)."""
    T = a.shape[0]
    n_q, n_kv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    u = hf["attention_in_multiplier"] * a
    q = _rope((u @ _w(ap["q"])).reshape(T, n_q, hd), hf["rope_theta"])
    k = _rope(
        (hf["key_multiplier"] * (u @ _w(ap["k"]))).reshape(T, n_kv, hd),
        hf["rope_theta"],
    )
    v = (u @ _w(ap["v"])).reshape(T, n_kv, hd)
    q = q.reshape(T, n_kv, n_q // n_kv, hd)
    Q = min(QUERY_BLOCK, T)
    assert T % Q == 0, (T, Q)

    def block(args):
        qb, t0 = args  # [Q, n_kv, g, hd], the block's first position
        s = jnp.einsum("tkgd,skd->kgts", qb, k) / np.sqrt(hd)
        causal = (t0 + jnp.arange(Q))[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        return jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(
        block, (q.reshape(T // Q, Q, n_kv, n_q // n_kv, hd), jnp.arange(0, T, Q))
    )
    return hf["attention_out_multiplier"] * (o.reshape(T, n_q * hd) @ _w(ap["o"]))


def _mamba(hf, a, mp, low=None):
    """a [T, D] -> [T, D] (the out multiplier applied).  ``low`` is None:
    the recurrence in float32; ``("state", bfloat16)``: the state carried
    in bfloat16 between float32 steps (what the cell's check reads for the
    record)."""
    T = a.shape[0]
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    K, G = hf["mamba_d_conv"], hf["mamba_n_groups"]
    di = H * P
    assert di == hf["mamba_d_ssm"], (di, hf["mamba_d_ssm"])
    cd = di + 2 * G * N
    proj = (hf["ssm_in_multiplier"] * a) @ _w(mp["in_proj"])
    m_z, m_x, m_b, m_c, m_dt = hf["ssm_multipliers"]
    z = m_z * proj[:, :di]
    xbc = jnp.concatenate(
        [
            m_x * proj[:, di : 2 * di],
            m_b * proj[:, 2 * di : 2 * di + G * N],
            m_c * proj[:, 2 * di + G * N : di + cd],
        ],
        -1,
    )
    dt = m_dt * proj[:, di + cd :]
    xp = jnp.concatenate([jnp.zeros((K - 1, cd), F32), xbc], 0)
    cw = mp["conv"]["w"].astype(F32)  # [K, cd]: tap K-1 is the current input
    xbc = mp["conv"]["b"].astype(F32) + sum(cw[k] * xp[k : k + T] for k in range(K))
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :di].reshape(T, G, H // G, P)
    bm = xbc[:, di : di + G * N].reshape(T, G, N)
    cm = xbc[:, di + G * N :].reshape(T, G, N)
    dt = jax.nn.softplus(dt + mp["dt_bias"].astype(F32)).reshape(T, G, H // G)
    a_neg = -jnp.exp(mp["A_log"].astype(F32)).reshape(G, H // G)
    kept = low[1] if low is not None and low[0] == "state" else F32

    def step(s, inp):  # s [G, H/G, P, N]
        x_t, b_t, c_t, dt_t = inp
        decay = jnp.exp(dt_t * a_neg)
        s = s.astype(F32) * decay[:, :, None, None] + (
            (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        )
        s = s.astype(kept)
        return s, jnp.einsum("ghpn,gn->ghp", s.astype(F32), c_t)

    _, y = jax.lax.scan(
        step, jnp.zeros((G, H // G, P, N), kept), (x, bm, cm, dt)
    )
    y = y + mp["D"].astype(F32).reshape(G, H // G)[:, :, None] * x
    y = y.reshape(T, di) * jax.nn.silu(z)
    # the gated norm, over each group's channels
    yg = y.reshape(T, G, di // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + hf["rms_norm_eps"])
    y = yg.reshape(T, di) * mp["norm"]["scale"].astype(F32)
    return hf["ssm_out_multiplier"] * (y @ _w(mp["out_proj"]))


def _layer(hf, low, h, norms, attn, mamba, mlp):
    """One layer: h [T, D] -> h."""
    eps = hf["rms_norm_eps"]
    if low is not None and low[0] == "weights":
        attn, mamba, mlp = _fp8_weights((attn, mamba, mlp))
    a = _rmsnorm(h, norms["attn_norm"]["scale"], eps)
    h = h + _attention(hf, a, attn) + _mamba(hf, a, mamba, low)
    m = _rmsnorm(h, norms["mlp_norm"]["scale"], eps)
    m_gate, m_down = hf["mlp_multipliers"]
    hid = jax.nn.silu(m_gate * (m @ _w(mlp["gate"]))) * (m @ _w(mlp["up"]))
    return h + m_down * (hid @ _w(mlp["down"]))


def _head_logits(hf, head_w, norm_scale, h):
    x = _rmsnorm(h, norm_scale, hf["rms_norm_eps"])
    return hf["lm_head_multiplier"] * (x @ head_w.astype(F32))


def _head_logps(hf, head_w, norm_scale, h, tokens):
    """log p(tokens[t+1] | tokens[:t+1]) for t < T-1, shape [T-1]; a block
    of positions at a time."""
    T = h.shape[0]
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])  # the last is dropped
    Q = min(QUERY_BLOCK, T)
    assert T % Q == 0, (T, Q)

    def block(args):
        hb, tb = args
        logits = _head_logits(hf, head_w, norm_scale, hb)  # [Q, V]
        tgt = jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
        return tgt - jax.nn.logsumexp(logits, -1)

    out = jax.lax.map(block, (h.reshape(T // Q, Q, -1), nxt.reshape(T // Q, Q)))
    return out.reshape(T)[:-1]


def _walk(hf, layer, params, tokens, embed):
    """``embed[tokens]`` through every layer, each with its own weights
    out of the stacks."""
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    h = hf["embedding_multiplier"] * embed[tokens].astype(F32)
    norms = {k: params["layers"][k] for k in ("attn_norm", "mlp_norm")}
    for l in range(hf["num_hidden_layers"]):
        h = layer(
            h, at(norms, l), at(params["attn"], l), at(params["mamba"], l),
            at(params["dense"], l),
        )
    return h


def make_token_logps(hf: dict, low=None):
    """``fn(params, tokens) -> logps [T-1]``.  One jitted program for a
    layer and one for the head, called layer by layer with that layer's
    weights as arguments: the whole stack in one program keeps every
    layer's float32 weight copies alive at once (13.8 GB at the published
    widths and eight layers).  ``low``: ``("weights", "float8_e4m3fn")``,
    every matrix rounded to float8 first (the control of the cell's
    comparison), or ``("state", "bfloat16")`` (:func:`_mamba`)."""
    if low is not None:
        low = (low[0], jnp.dtype(low[1]))
    fp8 = low is not None and low[0] == "weights"
    layer = jax.jit(partial(_layer, hf, low))
    head = jax.jit(partial(_head_logps, hf))
    rounded = jax.jit(lambda w: _fp8_weights(w).astype(w.dtype))

    def fn(params, tokens):
        embed, head_w = params["embed"]["weight"], params["lm_head"]["w"]
        if fp8:
            embed, head_w = rounded(embed), rounded(head_w)
        h = _walk(hf, layer, params, tokens, embed)
        return head(head_w, params["final_norm"]["scale"], h, tokens)

    return fn


def sequence_logps(fn, params, seq, pad_to=QUERY_BLOCK):
    """Per-transition log-probabilities of one sequence, right-padded to a
    multiple of ``pad_to`` so few shapes compile; causal layers make the
    padding invisible to the real positions."""
    T = -(-len(seq) // pad_to) * pad_to
    tokens = jnp.asarray(list(seq) + [0] * (T - len(seq)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logps = fn(params, tokens)
    return np.asarray(logps)[: len(seq) - 1]


def forward_logits(hf: dict, params, tokens):
    """Logits [T, V] of one sequence: what the CPU tests compare the
    program's logits with."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _walk(hf, partial(_layer, hf, None), params, tokens, params["embed"]["weight"])
        return _head_logits(
            hf, params["lm_head"]["w"], params["final_norm"]["scale"], h
        )
