"""The plain reference of the ``phi4flash`` family
(Phi-4-mini-flash-reasoning): the forward pass as the model's
``config.json``, its paper (SambaY, arXiv 2507.06607) and the papers of its
parts (differential attention, arXiv 2410.05258; Mamba, arXiv 2312.00752)
state it, in straightforward ``jax.numpy`` and float32: no kernel, no
cache, no pages, no state slots, one sequence at a time, "highest" matmul
precision.  With ``L = num_hidden_layers`` (32), ``W = sliding_window``:

    layers 0 .. L/2+1   even l: Mamba-1      odd l < L/2+1: attention under i - j < W
                                             l = L/2+1: attention over the whole context
    layers L/2+2 .. L-1 even l: gated memory unit     odd l: cross-attention over
                                                      layer L/2+1's K and V

    every layer:  a = LN1(h);  h = h + mixer_l(a);  u = LN2(h)
                  h = h + W_down (silu(W_gate u) * W_up u)
    logits = LN_f(h_L) E^T                  (E the embedding; no position term)

    Mamba-1:  [x | z] = a W_in;  x = silu(conv4(x) + b_c);  [d | B | C] = x W_x
              D_t = softplus(d_t W_dt + b_dt)
              S_t = exp(D_t (x) A) * S_{t-1} + (D_t * x_t) (x) B_t,   A = -exp(A_log)
              y_t = S_t C_t + D_skip * x_t;   out = (y * silu(z)) W_out
              (layer L/2 keeps m := y for the gated memory units)
    GMU:      out = (silu(a W_1) * m) W_2
    attention, differential: adjacent heads pair up; for query pair j with
              KV pair j // (query pairs / KV pairs):
              P1 = softmax(q1 k1^T / sqrt(hd) + mask), P2 = softmax(q2 k2^T / sqrt(hd) + mask)
              lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l),  lam0(l) = 0.8 - 0.6 exp(-0.3 l)
              o_j = rmsnorm((P1 - lam P2) [v1 | v2]; w_sub) (1 - lam0(l));  out = concat_j(o_j) W_o + b_o
              a cross layer computes q only and takes k, v as layer L/2+1 made them

The two softmax maps are written out pair by pair (the server may run a
zero-padded form over heads of twice the width: that is ITS business).
The recurrence is a plain scan over tokens; attention runs a block of
queries at a time against all keys, so that a 10k-token sequence at the
published widths (2 maps x 20 pairs x 256 x 10,240 float32 scores = 0.4 GB
a block) fits beside the weights.

Departures from the published description, each the configuration's
(``benchmark/configs/phi-4-mini-flash-reasoning.json``, ``assumed``):

* the catalog row's config has no key for the split of the stack, the
  Mamba sizes, the differential heads or the projections' biases: they
  are the ``assumed`` block's, each with its paper;
* ``A_log`` is held ``[d_state, d_inner]``, the transpose of the published
  module's parameter (the state's own layout);
* the sub-norm's eps is ``layer_norm_eps``; the window's edge is ``i - j <
  sliding_window`` (a query sees itself and the ``W - 1`` before it).

``wrong=`` makes one deliberate mistake, for the controls that show the
comparison's limits refuse it: ``"window_off"`` (window layers attend the
whole prefix), ``"gmu_own_input"`` (a gated memory unit's memory made of
ITS OWN input, ``a W_1``, instead of layer L/2's ``y``), ``"cross_own_kv"``
(a cross layer attends K and V made of its OWN input by the shared
layer's ``W_k``, ``W_v``), ``"lam_zero"`` (the second map's weight 0).

It reads the published ``config.json`` keys (and ``assumed_sizes`` beside
them) and the weight tree the system under test serves; it calls no model
code of the program.
"""

from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.reference_deepseek_v3 import _fp8_round, _fp8_scale, _w

F32 = jnp.float32

#: query positions attended at once
QUERY_BLOCK = 256

WRONG = (None, "window_off", "gmu_own_input", "cross_own_kv", "lam_zero")


def layer_kinds(hf: dict) -> list:
    """The stack, by kind, from ``num_hidden_layers`` and ``mb_per_layer``
    (module docstring)."""
    L, every = hf["num_hidden_layers"], hf["mb_per_layer"]
    full = L // 2 + 1
    kinds = []
    for l in range(L):
        if l % every == 0:
            kinds.append("mamba1" if l < full else "gmu")
        elif l < full:
            kinds.append("window")
        else:
            kinds.append("attention" if l == full else "cross")
    return kinds


def _fp8_tree(tree):
    """Every matrix of ``tree`` rounded to float8 e4m3 under one scale a
    matrix; vectors and ``A_log`` (a float32 parameter of the recurrence,
    not a matrix a product reads) as they are."""

    def one(path, w):
        if w.ndim < 2 or any(getattr(k, "key", None) == "A_log" for k in path):
            return w
        return _fp8_round(w, _fp8_scale(w))

    return jax.tree_util.tree_map_with_path(one, tree)


def _layernorm(x, p, eps):
    x = x.astype(F32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32) + p[
        "bias"
    ].astype(F32)


def _lin(p, x):
    y = x @ _w(p)
    return y + p["b"].astype(F32) if "b" in p else y


def _mamba1(hf: dict, a, mp):
    """a [T, D] -> (out [T, D], y [T, d_inner]): the selective scan, one
    token after the other."""
    sizes = hf["assumed_sizes"]
    N, R, K = sizes["d_state"], sizes["dt_rank"], sizes["d_conv"]
    di = sizes["expand"] * hf["hidden_size"]
    T = a.shape[0]
    xz = a @ _w(mp["in_proj"])
    x, z = xz[:, :di], xz[:, di:]
    w = mp["conv"]["w"].astype(F32)  # [K, di]: tap k multiplies x_{t-K+1+k}
    xp = jnp.concatenate([jnp.zeros((K - 1, di), F32), x])
    x = jax.nn.silu(
        sum(w[k] * xp[k : k + T] for k in range(K)) + mp["conv"]["b"].astype(F32)
    )
    dbc = x @ _w(mp["x_proj"])
    dt = jax.nn.softplus(
        dbc[:, :R] @ _w(mp["dt_proj"]) + mp["dt_proj"]["b"].astype(F32)
    )
    bm, cm = dbc[:, R : R + N], dbc[:, R + N :]
    a_neg = -jnp.exp(mp["A_log"].astype(F32))  # [N, di]

    def step(s, t):
        dt_t, x_t, b_t, c_t = t
        s = jnp.exp(dt_t[None, :] * a_neg) * s + b_t[:, None] * (dt_t * x_t)[None, :]
        return s, c_t @ s

    _, y = jax.lax.scan(step, jnp.zeros((N, di), F32), (dt, x, bm, cm))
    y = y + mp["D"].astype(F32) * x
    return (y * jax.nn.silu(z)) @ _w(mp["out_proj"]), y


def _lam0(l):
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(l, F32))


def _diff_attention(hf: dict, l, windowed: bool, wrong, a, ap, kv=None):
    """a [T, D] -> (out [T, D], (k, v)): differential attention of layer
    ``l`` (its number: a traced scalar will do), causal, under the window
    where ``windowed``; ``kv``: another layer's K and V (a cross layer),
    else this layer's own."""
    T = a.shape[0]
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf["hidden_size"] // H
    Hp, Kp = H // 2, Hkv // 2  # query pairs, KV pairs
    r, W = Hp // Kp, hf["sliding_window"]
    pos = jnp.arange(T)
    q = _lin(ap["q"], a).reshape(T, Kp, r, 2, hd)
    if kv is None:
        kv = (
            _lin(ap["k"], a).reshape(T, Kp, 2, hd),
            _lin(ap["v"], a).reshape(T, Kp, 2 * hd),
        )
    k, v = kv
    lam0 = _lam0(l)
    vec = lambda n: ap[n].astype(F32)
    lam = (
        jnp.exp(jnp.dot(vec("lambda_q1"), vec("lambda_k1")))
        - jnp.exp(jnp.dot(vec("lambda_q2"), vec("lambda_k2")))
        + lam0
    )
    if wrong == "lam_zero":
        lam = 0.0
    scale = hd**-0.5
    Q = min(QUERY_BLOCK, T)
    assert T % Q == 0, (T, Q)

    def block(args):
        qb, t0 = args  # [Q, Kp, r, 2, hd], first position
        i = (t0 + jnp.arange(Q))[:, None]
        seen = i >= pos[None, :]
        if windowed:
            seen &= i - pos[None, :] < W
        maps = []
        for m in (0, 1):
            s = scale * jnp.einsum("tgrd,ugd->grtu", qb[:, :, :, m], k[:, :, m])
            maps.append(jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1))
        o = jnp.einsum("grtu,ugd->tgrd", maps[0] - lam * maps[1], v)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + hf["layer_norm_eps"])
        return (o * ap["subln"]["scale"].astype(F32) * (1.0 - lam0)).reshape(Q, -1)

    o = jax.lax.map(
        block, (q.reshape(T // Q, Q, Kp, r, 2, hd), jnp.arange(0, T, Q))
    )
    return _lin(ap["o"], o.reshape(T, Hp * 2 * hd)), kv


def _layer(hf, kind, low, wrong, l, h, norms, mixer, mlp, shared=None):
    """Layer ``l`` (its number, traced: one program serves every layer of
    a kind): h [T, D] -> (h, what later layers read of it: a Mamba-1
    layer's ``y``, an attention layer's ``(k, v)``, else None).
    ``shared``: what THIS layer reads of an earlier one (a GMU: ``y``; a
    cross layer: ``(k, v)``, and under ``cross_own_kv`` the shared
    layer's ``W_k``, ``W_v`` as a third entry)."""
    eps = hf["layer_norm_eps"]
    if low is not None:
        mixer, mlp = _fp8_tree((mixer, mlp))
    a = _layernorm(h, norms["attn_norm"], eps)
    left = None
    if kind == "mamba1":
        out, left = _mamba1(hf, a, mixer)
    elif kind == "gmu":
        gate = a @ _w(mixer["in_proj"])
        mem = gate if wrong == "gmu_own_input" else shared
        out = (jax.nn.silu(gate) * mem) @ _w(mixer["out_proj"])
    elif kind == "cross":
        kv = shared[:2]
        if wrong == "cross_own_kv":
            T, (k, v) = a.shape[0], kv
            wk, wv = shared[2]
            if low is not None:
                wk, wv = _fp8_tree((wk, wv))
            kv = (_lin(wk, a).reshape(k.shape), _lin(wv, a).reshape(v.shape))
        out, _ = _diff_attention(hf, l, False, wrong, a, mixer, kv)
    else:
        windowed = kind == "window" and wrong != "window_off"
        out, left = _diff_attention(hf, l, windowed, wrong, a, mixer)
    h = h + out
    u = _layernorm(h, norms["mlp_norm"], eps)
    h = h + (jax.nn.silu(u @ _w(mlp["gate"])) * (u @ _w(mlp["up"]))) @ _w(mlp["down"])
    return h, left


def _head_logps(hf, embed, norm, h, tokens):
    """log p(tokens[t+1] | tokens[:t+1]) for t < T-1, shape [T-1]; a block
    of positions at a time (the logits of 10,240 positions over 200,064
    rows are 8.2 GB in float32)."""
    T = h.shape[0]
    x = _layernorm(h, norm, hf["layer_norm_eps"])
    w = embed.astype(F32)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])  # the last is dropped
    Q = min(QUERY_BLOCK, T)
    assert T % Q == 0, (T, Q)

    def block(args):
        xb, tb = args
        logits = xb @ w.T  # [Q, V]
        tgt = jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
        return tgt - jax.nn.logsumexp(logits, -1)

    out = jax.lax.map(block, (x.reshape(T // Q, Q, -1), nxt.reshape(T // Q, Q)))
    return out.reshape(T)[:-1]


def _stack_walk(hf, layer_fn, params, h):
    """``h`` through every layer: ``layer_fn(kind)(l, h, norms, mixer,
    mlp, shared)``, each with its own weights out of the stacks by kind."""
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    lay = params["layers"]
    norms = {k: lay[k] for k in ("attn_norm", "mlp_norm")}
    stacks = {"mamba1": "mamba1", "window": "attn", "attention": "attn",
              "gmu": "gmu", "cross": "cross"}
    seen = {}
    memory = kv = own = None
    for l, kind in enumerate(layer_kinds(hf)):
        stack = stacks[kind]
        j = seen.get(stack, 0)
        seen[stack] = j + 1
        mixer = at(params[stack], j)
        shared = memory if kind == "gmu" else None
        if kind == "cross":
            shared = kv + (own,)
        h, left = layer_fn(kind)(
            jnp.asarray(l, jnp.int32), h, at(norms, l), mixer,
            at(params["dense"], l), shared,
        )
        if kind == "mamba1":
            memory = left  # the last one's stands when the GMUs begin
        if kind == "attention":
            kv, own = left, (mixer["k"], mixer["v"])
    return h


#: the layer kinds whose program a mistake changes
TOUCHES = {
    None: (), "window_off": ("window",), "gmu_own_input": ("gmu",),
    "cross_own_kv": ("cross",), "lam_zero": ("window", "attention", "cross"),
}

_PROGRAMS = {}


def _program(hf: dict, kind: str, low, wrong):
    """The jitted program of one layer kind (or of the head) under
    ``low`` and ``wrong``, one for as long as the process lives (a layer
    of 10k positions takes seconds to compile, and a check runs the stack
    nine times)."""
    key = (json.dumps(hf, sort_keys=True), kind, low, wrong)
    if key not in _PROGRAMS:
        fn = _head_logps if kind == "head" else partial(_layer, hf, kind, low, wrong)
        _PROGRAMS[key] = jax.jit(partial(fn, hf) if kind == "head" else fn)
    return _PROGRAMS[key]


def make_token_logps(hf: dict, low=None, wrong=None):
    """``fn(params, tokens, routed=None) -> (logps [T-1], None, None)``
    (the signature ``reference_deepseek_v3.sequence_logps`` drives; this
    stack has no router).  One jitted program a (kind, layer) and one for
    the head, called layer by layer with that layer's weights as
    arguments.  ``low = ("weights", "float8_e4m3fn")``: every matrix
    rounded to float8 first (the control of the cell's comparison).
    ``wrong``: one deliberate mistake (module docstring).  A program is
    kept by what it depends on (:func:`_program`): a control that changes
    one kind of layer compiles that kind alone."""
    assert low is None or tuple(low) == ("weights", "float8_e4m3fn"), low
    assert wrong in WRONG, wrong
    low = None if low is None else tuple(low)

    def layer_fn(kind):
        return _program(hf, kind, low, wrong if kind in TOUCHES[wrong] else None)

    head = _program(hf, "head", None, None)
    rounded = jax.jit(_fp8_tree)

    def fn(params, tokens, routed=None):
        embed = params["embed"]["weight"]
        if low is not None:
            embed = rounded(embed)
        h = _stack_walk(hf, layer_fn, params, embed[tokens].astype(F32))
        return head(embed, params["final_norm"], h, tokens), None, None

    return fn


def sequence_logps(fn, params, seq, pad_to=QUERY_BLOCK):
    """Per-transition log-probabilities of one sequence, right-padded to a
    multiple of ``pad_to`` so few shapes compile; causal layers make the
    padding invisible to the real positions."""
    T = -(-len(seq) // pad_to) * pad_to
    tokens = jnp.asarray(list(seq) + [0] * (T - len(seq)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logps, _, _ = fn(params, tokens)
    return np.asarray(logps)[: len(seq) - 1]


def forward_logits(hf: dict, params, tokens, wrong=None):
    """Logits [T, V] of one sequence: what the CPU tests compare the
    program's logits with."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _stack_walk(
            hf, lambda kind: partial(_layer, hf, kind, None, wrong),
            params, params["embed"]["weight"][tokens].astype(F32),
        )
        x = _layernorm(h, params["final_norm"], hf["layer_norm_eps"])
        return x @ params["embed"]["weight"].astype(F32).T
