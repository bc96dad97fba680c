"""The plain reference of the ``smallthinker`` family
(SmallThinker-21BA3B-Instruct): the forward pass as the model's
``config.json`` and its published description state it, in straightforward
``jax.numpy`` and float32: no kernel, no cache, no pages, one sequence at
a time, "highest" matmul precision.

    h = embed[tokens]
    per layer l, with g_l = sliding_window_layout[l], r_l = rope_layout[l]:
        a      = rmsnorm(h; w1)
        z      = a W_router                 (64 logits, from the ATTENTION's input)
        top,id = top_k(z);  w = softmax(top)
        q,k,v  = a W_q, a W_k, a W_v;  q,k = rope(q,k) where r_l = 1
        s_ij   = q_i . k_j / sqrt(head_dim)   for j <= i and, where g_l = 1, i - j < W
        h      = h + (softmax(s) v) W_o
        m      = rmsnorm(h; w2)
        h      = h + sum_{e in id} w_e W_down,e (relu(W_gate,e m) * W_up,e m)
    logits = rmsnorm(h_L; w_f) W_head                           (untied head)

Every held expert is computed for every token and the routed ones taken.
Attention runs a block of queries at a time against all keys, so that a
10k-token sequence at the published widths (28 heads x 512 x 10,240
float32 scores = 0.6 GB a block) fits beside the weights.

Departures from the published description, each the configuration's
(``benchmark/configs/smallthinker-21b-a3b.json``, ``assumed``):

* "primary+secondary experts" of the family's description: the published
  ``config.json`` has primary keys only; no secondary experts are here;
* ``softmax`` router with ``norm_topk_prob``: softmax over all experts,
  the top k, renormalised, which IS the softmax of the top k logits;
* the window's edge is ``i - j < sliding_window_size`` (a query sees
  itself and the ``W - 1`` positions before it);
* rope rotates halves ``(j, j + head_dim / 2)``, the HuggingFace
  convention for this family's ``rotate_half``;
* **the share of a deployment**: the weight tree holds the experts
  ``[first, first + held)`` of the router's outputs; a pair routed to an
  expert held elsewhere adds nothing (the program does the same).  The
  benchmark's configuration holds all 64.

``wrong=`` makes one deliberate mistake, for the controls that show the
comparison's limits refuse it: ``"window_off"`` (window layers attend the
whole prefix), ``"rope_on_global"`` (every layer ropes), ``"router_reads_m"``
(the router reads the experts' input ``m``).

It reads the published ``config.json`` keys and the weight tree the system
under test serves (``embed.weight``, ``lm_head.w``, ``final_norm``;
``layers.{attn_norm, mlp_norm}`` and ``attn.{q, k, v, o}`` over all
layers; ``layers.mlp.{router.w, experts.{gate, up, down} (each [E_held, F,
D])}``); it calls no model code of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.lib.reference_deepseek_v3 import (
    _fp8_round,
    _fp8_scale,
    _fp8_weights,
    _rmsnorm,
    _w,
    sequence_logps,  # noqa: F401 - the same padding and routing plumbing
)

F32 = jnp.float32

#: query positions attended at once: scores are [heads, this, T] float32
QUERY_BLOCK = 512

WRONG = (None, "window_off", "rope_on_global", "router_reads_m")


def _rope(hf: dict, x, positions):
    """x [T, H, hd] rotated by halves (j, j + hd/2) at ``rope_theta``."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (
        float(hf["rope_theta"]) ** (jnp.arange(0, half, dtype=F32) / half)
    )
    ang = positions.astype(F32)[:, None] * freqs  # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(hf: dict, windowed: bool, roped: bool, a, ap):
    """a [T, D] -> [T, D]: grouped-query attention, causal, under the
    window where ``windowed``; a block of queries at a time."""
    T = a.shape[0]
    H, Hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    r, W = H // Hkv, hf["sliding_window_size"]
    pos = jnp.arange(T)
    q = (a @ _w(ap["q"])).reshape(T, H, hd)
    k = (a @ _w(ap["k"])).reshape(T, Hkv, hd)
    v = (a @ _w(ap["v"])).reshape(T, Hkv, hd)
    if roped:
        q, k = _rope(hf, q, pos), _rope(hf, k, pos)
    scale = hd**-0.5
    Q = min(QUERY_BLOCK, T)
    assert T % Q == 0, (T, Q)

    def block(args):
        qb, t0 = args  # [Q, H, hd], first position
        s = scale * jnp.einsum("tgrd,ugd->grtu", qb.reshape(Q, Hkv, r, hd), k)
        i = (t0 + jnp.arange(Q))[:, None]
        seen = i >= pos[None, :]
        if windowed:
            seen &= i - pos[None, :] < W
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("grtu,ugd->tgrd", p, v).reshape(Q, H * hd)

    o = jax.lax.map(block, (q.reshape(T // Q, Q, H, hd), jnp.arange(0, T, Q)))
    return o.reshape(T, H * hd) @ _w(ap["o"])


def _experts(hf: dict, routed_on, m, mlp, first, given=None, low=None):
    """m [T, D] -> [T, D]: this share's part of the routed experts' sum,
    the router reading ``routed_on`` [T, D].  Also, per token, how close
    the k-th logit was to the one after it, and whether this router's own
    k differ from ``given`` [T, k] (the experts the system under test
    routed each token to: the logits and the weights are this reference's
    own, only WHICH k is taken from the system, so that what separates
    the two is rounding and not a near-tie that fell the other way).
    ``low``: the held experts' matrices rounded to float8, one expert at
    a time under its stack's one scale (the router comes rounded:
    :func:`_layer`)."""
    k = hf["moe_num_active_primary_experts"]
    z = routed_on @ _w(mlp["router"])  # [T, E]
    top, idx = jax.lax.top_k(z, k + 1)
    margin = top[:, k - 1] - top[:, k]
    idx = idx[:, :k]
    flipped = jnp.zeros(m.shape[:1], bool)
    if given is not None:
        flipped = jnp.any(jnp.sort(given, -1) != jnp.sort(idx, -1), -1)
        idx = given
    w = jax.nn.softmax(jnp.take_along_axis(z, idx, -1), -1)
    ex = mlp["experts"]  # gate, up, down: each [held, F, D]
    held = ex["gate"].shape[0]
    local = idx - first
    wts = jnp.sum(
        jnp.where(
            local[:, :, None] == jnp.arange(held)[None, None, :], w[:, :, None], 0.0
        ),
        axis=1,
    )  # [T, held]: 0 where an expert was not picked
    names = ("gate", "up", "down")
    scales = [_fp8_scale(ex[n]) for n in names] if low is not None else None

    def one(acc, e):
        g, u, d, w_e = e
        if scales is not None:
            g, u, d = (_fp8_round(w, s) for w, s in zip((g, u, d), scales))
        y = (jax.nn.relu(m @ g.astype(F32).T) * (m @ u.astype(F32).T)) @ d.astype(F32)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(m), (ex["gate"], ex["up"], ex["down"], wts.T)
    )
    return out, margin, flipped


def _layer(hf, windowed, roped, first, low, wrong, h, norms, mixer, mlp, given=None):
    """One layer: h [T, D] -> (h, router margin [T], own routing differs
    from ``given`` [T])."""
    eps = hf["rms_norm_eps"]
    if low is not None:
        # (the held experts' stacks inside _experts, an expert at a time)
        held = {k: v for k, v in mlp.items() if k == "experts"}
        rest = {k: v for k, v in mlp.items() if k != "experts"}
        mixer, rest = _fp8_weights((mixer, rest))
        mlp = dict(rest, **held)
    a = _rmsnorm(h, norms["attn_norm"]["scale"], eps)
    h = h + _attention(
        hf, windowed and wrong != "window_off",
        roped or wrong == "rope_on_global", a, mixer,
    )
    m = _rmsnorm(h, norms["mlp_norm"]["scale"], eps)
    routed_on = m if wrong == "router_reads_m" else a
    out, margin, flipped = _experts(hf, routed_on, m, mlp, first, given, low)
    return h + out, margin, flipped


def _head_logps(hf, head, norm_scale, h, tokens):
    """log p(tokens[t+1] | tokens[:t+1]) for t < T-1, shape [T-1]; a block
    of positions at a time (the logits of 10,240 positions over 151,936
    rows are 6.2 GB in float32)."""
    T = h.shape[0]
    x = _rmsnorm(h, norm_scale, hf["rms_norm_eps"])
    w = head.astype(F32)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])  # the last is dropped
    Q = min(QUERY_BLOCK, T)
    assert T % Q == 0, (T, Q)

    def block(args):
        xb, tb = args
        logits = xb @ w  # [Q, V]
        tgt = jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
        return tgt - jax.nn.logsumexp(logits, -1)

    out = jax.lax.map(block, (x.reshape(T // Q, Q, -1), nxt.reshape(T // Q, Q)))
    return out.reshape(T)[:-1]


def _layer_fns(hf: dict, first_expert: int, low, wrong):
    """One jitted program a (windowed, roped) pair the layouts hold."""
    assert wrong in WRONG, wrong
    kinds = set(zip(hf["sliding_window_layout"], hf["rope_layout"]))
    return {
        (g, r): jax.jit(
            partial(_layer, hf, bool(g), bool(r), first_expert, low, wrong)
        )
        for g, r in kinds
    }


def make_token_logps(hf: dict, first_expert: int = 0, low=None, wrong=None):
    """``fn(params, tokens, routed=None) -> (logps [T-1], smallest router
    margin over the layers [T-1], layers whose own routing differs from
    the given [T-1])``; ``routed`` [T, L, k]: the system's routed experts
    of every layer, which the reference then follows (:func:`_experts`).
    ``hf`` is the configuration AS RUN (``num_hidden_layers`` and the two
    layouts of the cut).  One jitted program a layer kind and one for the
    head, called layer by layer with that layer's weights as arguments:
    the whole stack in one program would keep every layer's float32
    weight copies alive at once.  ``low = ("weights", "float8_e4m3fn")``:
    every matrix rounded to float8 first (the control of the cell's
    comparison).  ``wrong``: one deliberate mistake (module docstring)."""
    assert low is None or tuple(low) == ("weights", "float8_e4m3fn"), low
    layer = _layer_fns(hf, first_expert, low, wrong)
    head = jax.jit(partial(_head_logps, hf))
    rounded = jax.jit(_fp8_weights)
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)

    def fn(params, tokens, routed=None):
        embed, lm_head = params["embed"]["weight"], params["lm_head"]["w"]
        if low is not None:
            embed, lm_head = rounded(embed), rounded(lm_head)
        h = embed[tokens].astype(F32)
        lay = params["layers"]
        norms = {k: lay[k] for k in ("attn_norm", "mlp_norm")}
        margin, flips = jnp.full(tokens.shape, jnp.inf, F32), 0
        for l in range(hf["num_hidden_layers"]):
            kind = (hf["sliding_window_layout"][l], hf["rope_layout"][l])
            given = None if routed is None else routed[:, l]
            h, m, f = layer[kind](
                h, at(norms, l), at(params["attn"], l), at(lay["mlp"], l), given
            )
            margin = jnp.minimum(margin, m)
            flips = flips + f.astype(jnp.int32)
        logps = head(lm_head, params["final_norm"]["scale"], h, tokens)
        return logps, margin[:-1], flips[:-1]

    return fn


def forward_logits(hf: dict, params, tokens, first_expert: int = 0, wrong=None):
    """Logits [T, V] of one sequence, routing for itself: what the CPU
    tests compare the program's logits with."""
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["weight"][tokens].astype(F32)
        lay = params["layers"]
        norms = {k: lay[k] for k in ("attn_norm", "mlp_norm")}
        for l in range(hf["num_hidden_layers"]):
            h, _, _ = _layer(
                hf, bool(hf["sliding_window_layout"][l]), bool(hf["rope_layout"][l]),
                first_expert, None, wrong, h, at(norms, l),
                at(params["attn"], l), at(lay["mlp"], l),
            )
        x = _rmsnorm(h, params["final_norm"]["scale"], hf["rms_norm_eps"])
        return x @ params["lm_head"]["w"].astype(F32)
