"""The plain reference of the ``ouro`` family (Ouro-2.6B, a looped language
model: arXiv 2510.25741, "Scaling Latent Reasoning via Looped Language
Models"): the forward pass as the model's ``config.json``, the paper's
architecture section and the family's modelling code describe it, in
straightforward ``jax.numpy`` and float32: no kernel, no cache, no batching,
one sequence at a time, "highest" matmul precision.  With ``N(x; g)`` an RMS
norm of ``rms_norm_eps``:

    h = E[x_t]                                              (no embedding scale)
    for r in 1..R:                        (R = total_ut_steps; the SAME weights in every pass)
      for l in 1..L:
        a = N(h; g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l   (no biases; rope of rope_theta over the
                                                             whole head, by the token's position,
                                                             the same position in every pass)
        o = softmax(q K^T / sqrt(head_dim), causal) V       (K, V: THIS pass's, of this layer)
        h = h + N(o Wo_l; g2_l)                             (sandwich: the branch's OUTPUT is normed)
        m = N(h; g3_l);  u = (silu(m Wg_l) * (m Wu_l)) Wd_l
        h = h + N(u; g4_l)
      h = N(h; g_final)                                     (after EVERY pass)
      lam_r = sigmoid(h . w_exit + b_exit)                  (the exit gate, [hidden -> 1])
    logits = h W_head                                       (of pass R; the head is untied)

The exit rule (:func:`exit_pass`): ``p_r = lam_r prod_{j<r} (1 - lam_j)`` for
``r < R`` and ``p_R`` the rest; a token leaves at the first ``r`` whose
cumulated ``p`` reaches ``early_exit_threshold``.  At the published 1 that is
pass R for every token: ``lam_r`` moves no logit.

A whole-sequence forward keeps every pass's keys and values apart by
construction (pass r's attention reads the keys pass r made).  The mistake
a serving system can make is ONE cache a layer that every pass overwrites:
pass r of token t then attends the keys the LAST pass left for the earlier
tokens.  ``wrong="shared_cache"`` computes that, token by token, for the
test that has to tell the two apart (the paper offers it as a deliberate
approximation at decode time; it is another result, not this one).

It reads the published ``config.json`` keys and the weight tree the system
under test serves (``embed.weight``, ``lm_head.w``, ``final_norm.scale``,
``exit_gate.{w, b}``; ``layers.{attn_norm, attn_post_norm, mlp_norm,
mlp_post_norm}.scale``, ``layers.attn.{q,k,v,o}.w`` and ``layers.mlp.{gate,
up,down}.w``, each stacked over the 48 weight layers); it calls no model
code of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: queries a block of the attention scores and of the head's logits
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
    scales, the gate's bias) as they are.  The rounding is written out in
    float32, so it runs wherever this file does."""

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


def _rope(x, positions, theta):
    """x [T, heads, hd] at ``positions`` [T], rotate-half (dims ``j`` and
    ``j + hd / 2`` turn together)."""
    half = x.shape[-1] // 2
    inv = 1.0 / float(theta) ** (jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _sizes(hf):
    n_q, n_kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    return n_q, n_kv, hf.get("head_dim") or hf["hidden_size"] // n_q


def _qkv(hf, lp, h, positions):
    T = h.shape[0]
    n_q, n_kv, hd = _sizes(hf)
    a = _rmsnorm(h, lp["attn_norm"]["scale"], hf["rms_norm_eps"])
    q = _rope((a @ _w(lp["attn"]["q"])).reshape(T, n_q, hd), positions, hf["rope_theta"])
    k = _rope((a @ _w(lp["attn"]["k"])).reshape(T, n_kv, hd), positions, hf["rope_theta"])
    v = (a @ _w(lp["attn"]["v"])).reshape(T, n_kv, hd)
    return q, k, v


def _after_attention(hf, lp, h, o):
    """The rest of a layer once ``o`` [T, n_q * hd] has been attended."""
    eps = hf["rms_norm_eps"]
    h = h + _rmsnorm(o @ _w(lp["attn"]["o"]), lp["attn_post_norm"]["scale"], eps)
    m = _rmsnorm(h, lp["mlp_norm"]["scale"], eps)
    mlp = lp["mlp"]
    u = (jax.nn.silu(m @ _w(mlp["gate"])) * (m @ _w(mlp["up"]))) @ _w(mlp["down"])
    return h + _rmsnorm(u, lp["mlp_post_norm"]["scale"], eps)


def _layer(hf, low, h, lp):
    """One layer of one pass over a whole sequence: h [T, D] -> h."""
    if low is not None:
        lp = _fp8_weights(lp)
    T = h.shape[0]
    n_q, n_kv, hd = _sizes(hf)
    q, k, v = _qkv(hf, lp, h, jnp.arange(T))
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
    return _after_attention(hf, lp, h, o.reshape(T, n_q * hd))


def _pass_end(hf, h, norm_scale, gate):
    """The norm after a pass and the exit gate's ``lam`` [T] on it."""
    h = _rmsnorm(h, norm_scale, hf["rms_norm_eps"])
    lam = jax.nn.sigmoid(h @ gate["w"].astype(F32)[:, 0] + gate["b"].astype(F32)[0])
    return h, lam


def _head_logps(head_w, h, tokens):
    """log p(tokens[t+1] | tokens[:t+1]) for t < T-1, shape [T-1], of the
    last pass's NORMED hidden states; a block of positions at a time."""
    T = h.shape[0]
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])  # the last is dropped
    Q = min(QUERY_BLOCK, T)
    assert T % Q == 0, (T, Q)

    def block(args):
        hb, tb = args
        logits = hb @ head_w.astype(F32)  # [Q, V]
        tgt = jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
        return tgt - jax.nn.logsumexp(logits, -1)

    out = jax.lax.map(block, (h.reshape(T // Q, Q, -1), nxt.reshape(T // Q, Q)))
    return out.reshape(T)[:-1]


def _walk(hf, layer, pass_end, params, tokens, embed):
    """``embed[tokens]`` through the stack ``total_ut_steps`` times, each
    layer with its own weights out of the stacks.  Returns the last
    pass's normed hidden states and every pass's ``lam`` [R, T]."""
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    h = embed[tokens].astype(F32)
    lams = []
    for _ in range(hf["total_ut_steps"]):
        for l in range(hf["num_hidden_layers"]):
            h = layer(h, at(params["layers"], l))
        h, lam = pass_end(h, params["final_norm"]["scale"], params["exit_gate"])
        lams.append(lam)
    return h, jnp.stack(lams)


def make_token_logps(hf: dict, low=None):
    """``fn(params, tokens) -> logps [T-1]``.  One jitted program for a
    layer, one for a pass's end and one for the head, called layer by
    layer with that layer's weights as arguments (the whole stack in one
    program would keep every layer's float32 copies alive at once).
    ``low=("weights", "float8_e4m3fn")``: every matrix rounded to float8
    first, the control of the cell's comparison."""
    assert low is None or low[0] == "weights", low
    layer = jax.jit(partial(_layer, hf, low))
    pass_end = jax.jit(partial(_pass_end, hf))
    head = jax.jit(_head_logps)
    rounded = jax.jit(lambda w: _fp8_weights(w).astype(w.dtype))

    def fn(params, tokens):
        embed, head_w = params["embed"]["weight"], params["lm_head"]["w"]
        if low is not None:
            embed, head_w = rounded(embed), rounded(head_w)
        h, _ = _walk(hf, layer, pass_end, params, tokens, embed)
        return head(head_w, h, tokens)

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


def _shared_cache_hidden(hf, params, tokens):
    """The MISTAKE: one cache a layer, which every pass overwrites, so
    pass r of token t attends what the LAST pass left for the tokens
    before it.  Token by token (the tests' sizes only)."""
    n_q, n_kv, hd = _sizes(hf)
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    L, R = hf["num_hidden_layers"], hf["total_ut_steps"]
    cache = [([], []) for _ in range(L)]
    out, lams = [], []
    for t, tok in enumerate(np.asarray(tokens)):
        h = params["embed"]["weight"][tok].astype(F32)[None]
        lam_t = []
        for _ in range(R):
            for l in range(L):
                lp = at(params["layers"], l)
                q, k, v = _qkv(hf, lp, h, jnp.asarray([t]))
                ks, vs = cache[l]
                del ks[t:], vs[t:]  # this token's entry of the pass before
                ks.append(k[0]), vs.append(v[0])
                K, V = jnp.stack(ks), jnp.stack(vs)  # [t + 1, n_kv, hd]
                qg = q.reshape(n_kv, n_q // n_kv, hd)
                s = jnp.einsum("kgd,skd->kgs", qg, K) / np.sqrt(hd)
                o = jnp.einsum("kgs,skd->kgd", jax.nn.softmax(s, -1), V)
                h = _after_attention(hf, lp, h, o.reshape(1, n_q * hd))
            h, lam = _pass_end(hf, h, params["final_norm"]["scale"], params["exit_gate"])
            lam_t.append(lam[0])
        out.append(h[0])
        lams.append(jnp.stack(lam_t))
    return jnp.stack(out), jnp.stack(lams, 1)


def forward_logits(hf: dict, params, tokens, wrong=None):
    """``(logits [T, V], lam [R, T])`` of one sequence: what the CPU tests
    compare the program's logits with, and the exit gate's value after
    every pass.  ``wrong="shared_cache"``: :func:`_shared_cache_hidden`."""
    assert wrong in (None, "shared_cache"), wrong
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        if wrong:
            h, lam = _shared_cache_hidden(hf, params, tokens)
        else:
            h, lam = _walk(
                hf, partial(_layer, hf, None), partial(_pass_end, hf),
                params, tokens, params["embed"]["weight"],
            )
        return h @ params["lm_head"]["w"].astype(F32), lam


def exit_pass(lam, threshold: float) -> np.ndarray:
    """The pass (1..R) at which each token leaves, from ``lam`` [R, T]:
    ``p_r = lam_r prod_{j<r} (1 - lam_j)`` for ``r < R``, ``p_R`` the rest;
    the first ``r`` whose cumulated ``p`` reaches ``threshold``."""
    lam = np.asarray(lam, np.float64)
    R = lam.shape[0]
    stay = np.cumprod(1.0 - lam, axis=0)  # prod_{j<=r} (1 - lam_j)
    p = lam * np.concatenate([np.ones_like(stay[:1]), stay[:-1]])
    p[-1] = 1.0 - p[:-1].sum(0)
    reached = np.cumsum(p, axis=0) >= threshold
    reached[-1] = True
    return reached.argmax(0) + 1


def mean_logp_grad(hf: dict, params, tokens):
    """Gradient, with respect to ``params``, of the mean log-probability
    of ``tokens[1:]``: what a test lays beside ``jax.grad`` of the
    program's ``logprobs_of_labels`` (a tied weight's gradient is the sum
    over the passes, which ``jax.grad`` of the loop above gives)."""
    tokens = jnp.asarray(tokens, jnp.int32)

    def loss(p):
        h, _ = _walk(
            hf, partial(_layer, hf, None), partial(_pass_end, hf), p, tokens,
            p["embed"]["weight"],
        )
        return jnp.mean(_head_logps(p["lm_head"]["w"], h, tokens))

    with jax.default_matmul_precision("highest"):
        return jax.grad(loss)(params)
