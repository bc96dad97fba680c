"""The plain reference of the ``granitemoehybrid`` family
(granite-4.0-h-small): the forward pass as ``modeling_granitemoehybrid.py``
and the model's ``config.json`` describe it, in straightforward
``jax.numpy`` and float32: no kernel, no cache, no chunking, one sequence
at a time, "highest" matmul precision.

    h = embedding_multiplier * embed[tokens]
    per layer, r = residual_multiplier:
        h += r * mixer(rmsnorm(h));  m = rmsnorm(h)
        h += r * (experts(m) + shared(m))
    logits = rmsnorm(h) embed^T / logits_scaling

* **mamba** mixer (Mamba-2): ``[z | xBC | dt] = a W_in``; ``xBC =
  silu(causal depthwise conv of width mamba_d_conv, with bias)``; ``[x |
  B | C] = xBC`` (one group: B and C are shared by all heads); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head; per head, state
  ``S`` in R^{d_head x d_state}: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T``, ``y_t = S_t C_t + D x_t``; ``out = (rmsnorm(y * silu(z)) * w)
  W_out`` (the gate BEFORE the norm, the norm over all of d_inner).  The
  recurrence is a sequential ``lax.scan`` over positions.
* **attention** mixer: q/k/v/o without bias, grouped-query, NO position
  term (``position_embedding_type`` "nope"), causal softmax of
  ``attention_multiplier * q k^T``.
* **experts**: router logits ``m W_r`` over all ``n_router`` experts; the
  top ``num_experts_per_tok`` LOGITS, softmax over those; expert e gives
  ``(silu(u1) * u2) W_out,e`` with ``[u1 | u2] = m W_in,e``.  EVERY held
  expert is computed for every token and the routed ones taken.  The
  shared expert has the same form at ``shared_intermediate_size``, for
  every token, weight 1.

Departures from the published model, each the configuration's:

* **the share of a deployment**: the weight tree holds the experts
  ``[first, first + held)`` of the router's ``n_router``; a pair routed to
  an expert held elsewhere adds nothing (the program does the same);
* HF's ``time_step_limit`` clamp of ``dt`` is (0, inf) in the published
  config: no clamp is written.

It reads the published ``config.json`` keys and the weight tree the system
under test serves (``embed.weight``; ``layers.{attn_norm, mlp_norm,
mlp.{router, experts.{gate, up, down} (each [E_held, F, D]), shared}}``
stacked over all layers;
``mamba.*`` stacked over the Mamba layers; ``attn.*`` over the attention
layers; ``final_norm``); it calls no model code of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _w(p):
    return (p["w"] if isinstance(p, dict) else p).astype(F32)


def _fp8_weights(tree):
    """Every matrix of ``tree`` as a server holding float8 (e4m3: four
    significant bits, smallest step 2^-9, largest value 448; one scale a
    stacked matrix, its largest magnitude -> 448) would read it; vectors
    (norm scales, biases, ``A_log``, ``D``, ``dt_bias``) as they are.
    The rounding is written out in float32, so it runs wherever this
    file does."""

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


def _mamba(hf, a, mp, low=None):
    """a [T, D] -> [T, D].  ``low`` is None: the recurrence in float32.
    The readings the cell's limits are set against (PERF.md, section 6,
    PR 31) pass ``("state", bfloat16)``, the state carried in bfloat16
    between float32 steps; ``("recurrence", bfloat16)``, decay, input and
    products in bfloat16 too; or ``("weights", float8_e4m3fn)``, which
    does nothing here (:func:`_layer` rounds the matrices)."""
    T = a.shape[0]
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    K, G = hf["mamba_d_conv"], hf["mamba_n_groups"]
    assert G == 1, "one B/C group is what is written here"
    di = H * P
    cd = di + 2 * G * N
    zxd = a @ _w(mp["in_proj"])
    z, xbc, dt = zxd[:, :di], zxd[:, di : di + cd], zxd[:, di + cd :]
    xp = jnp.concatenate([jnp.zeros((K - 1, cd), F32), xbc], 0)
    cw = mp["conv"]["w"].astype(F32)  # [K, cd]: tap K-1 is the current input
    xbc = mp["conv"]["b"].astype(F32) + sum(cw[k] * xp[k : k + T] for k in range(K))
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :di].reshape(T, H, P)
    bm, cm = xbc[:, di : di + N], xbc[:, di + N :]
    dt = jax.nn.softplus(dt + mp["dt_bias"].astype(F32))  # [T, H]
    a_neg = -jnp.exp(mp["A_log"].astype(F32))  # [H]
    in_state = low is not None and low[0] in ("state", "recurrence")
    kept = low[1] if in_state else F32  # what the state is carried in
    work = low[1] if low and low[0] == "recurrence" else F32

    def step(s, inp):
        x_t, b_t, c_t, dt_t = (t.astype(work) for t in inp)
        decay = jnp.exp(dt_t * a_neg.astype(work))
        s = s.astype(work) * decay[:, None, None] + (
            dt_t[:, None] * x_t
        )[:, :, None] * b_t[None, None, :]
        s = s.astype(kept)
        y = jnp.einsum("hpn,n->hp", s.astype(work), c_t)
        return s, y.astype(F32)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), kept), (x, bm, cm, dt))
    y = y + mp["D"].astype(F32)[:, None] * x
    y = y.reshape(T, di) * jax.nn.silu(z)
    y = _rmsnorm(y, mp["norm"]["scale"], hf["rms_norm_eps"])
    return y @ _w(mp["out_proj"])


def _attention(hf, a, ap):
    T = a.shape[0]
    n_q, n_kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // n_q
    q = (a @ _w(ap["q"])).reshape(T, n_kv, n_q // n_kv, hd)
    k = (a @ _w(ap["k"])).reshape(T, n_kv, hd)
    v = (a @ _w(ap["v"])).reshape(T, n_kv, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k) * hf["attention_multiplier"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
    return o.reshape(T, n_q * hd) @ _w(ap["o"])


def _experts(hf, m, mlp, first, given=None):
    """m [T, D] -> [T, D]: this share's part of the routed experts' sum,
    plus the shared expert.  Also, per token, how close the k-th pick was
    to the one after it (a flip of the k-th expert on rounding is the
    largest term of a comparison with a system that routes for itself),
    and whether this router's own k differ from ``given``.

    ``given`` [T, k]: the experts the system under test routed each token
    to.  The router's logits are this reference's own and so is the
    softmax over the k; only WHICH k is taken from the system, so that
    what separates the two is rounding, not a near-tie that fell the
    other way (top 10 of 72 flip in one (token, layer) in five under
    bf16 activations, my chip runs, PR 31, and a recurrent state carries
    every flip forward)."""
    k = hf["num_experts_per_tok"]
    logits = m @ _w(mlp["router"])  # [T, n_router]
    top, idx = jax.lax.top_k(logits, k + 1)
    margin = top[:, k - 1] - top[:, k]
    top, idx = top[:, :k], idx[:, :k]
    flipped = jnp.zeros(m.shape[:1], bool)
    if given is not None:
        flipped = jnp.any(jnp.sort(given, -1) != jnp.sort(idx, -1), -1)
        idx = given
        top = jnp.take_along_axis(logits, idx, -1)
    gate = jax.nn.softmax(top, -1)  # [T, k]
    ex = mlp["experts"]  # gate, up, down: each [held, F, D]
    held = ex["gate"].shape[0]
    # weight of each HELD expert for each token (0 where it was not picked)
    local = idx - first
    wts = jnp.sum(
        jnp.where(
            local[:, :, None] == jnp.arange(held)[None, None, :],
            gate[:, :, None], 0.0,
        ),
        axis=1,
    )  # [T, held]

    def one(acc, e):
        g, u, d, w_e = e
        y = (jax.nn.silu(m @ g.astype(F32).T) * (m @ u.astype(F32).T)) @ d.astype(F32)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(m), (ex["gate"], ex["up"], ex["down"], wts.T)
    )
    if "shared" in mlp:
        sh = mlp["shared"]
        out = out + (jax.nn.silu(m @ _w(sh["gate"])) * (m @ _w(sh["up"]))) @ _w(sh["down"])
    return out, margin, flipped


def _layer(hf, kind, first, low, h, lp, mixer, given=None):
    """One layer: h [T, D] -> (h, router margin [T], own routing differs
    from ``given`` [T])."""
    eps, r = hf["rms_norm_eps"], hf["residual_multiplier"]
    if low is not None and low[0] == "weights":
        lp, mixer = _fp8_weights((lp, mixer))
    a = _rmsnorm(h, lp["attn_norm"]["scale"], eps)
    if kind == "mamba":
        h = h + r * _mamba(hf, a, mixer, low)
    else:
        h = h + r * _attention(hf, a, mixer)
    m = _rmsnorm(h, lp["mlp_norm"]["scale"], eps)
    out, margin, flipped = _experts(hf, m, lp["mlp"], first, given)
    return h + r * out, margin, flipped


def _head_logps(hf, embed, norm_scale, h, tokens, vocab_block=16384):
    """log p(tokens[t+1] | tokens[:t+1]) for t < T-1, shape [T-1].  The
    log-sum-exp over the vocabulary is taken in blocks."""
    x = _rmsnorm(h, norm_scale, hf["rms_norm_eps"])[:-1]
    div = hf["logits_scaling"]
    lse = jnp.full((x.shape[0],), -jnp.inf, F32)
    for v0 in range(0, embed.shape[0], vocab_block):  # tied head: [V, D]
        blk = (x @ embed[v0 : v0 + vocab_block].astype(F32).T) / div
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(blk, -1))
    tgt = jnp.sum(x * embed[tokens[1:]].astype(F32), -1) / div
    return tgt - lse


def make_token_logps(hf: dict, first_expert: int = 0, low=None):
    """``fn(params, tokens, routed=None) -> (logps [T-1], smallest
    router margin over the layers [T-1], layers whose own routing differs
    from the given [T-1])``; ``routed`` [T, L, k]: the system's routed
    experts, which the reference then follows (:func:`_experts`).  One
    jitted program a layer KIND and one for the
    head, called layer by layer with that layer's weights as arguments:
    the whole stack in one program keeps every layer's float32 weight
    copies alive at once (6.4 GB at the published widths, beside 9.9 GB of
    weights on a 16 GB chip).  ``low``: see :func:`_mamba`."""
    if low is not None:  # ("state" | "recurrence" | "weights", a dtype)
        low = (low[0], jnp.dtype(low[1]))
    fp8 = low is not None and low[0] == "weights"
    layer = {
        kind: jax.jit(partial(_layer, hf, kind, first_expert, low))
        for kind in ("mamba", "attention")
    }
    head = jax.jit(partial(_head_logps, hf))
    round_embed = jax.jit(lambda w: _fp8_weights(w).astype(w.dtype))
    key = {"mamba": "mamba", "attention": "attn"}

    def fn(params, tokens, routed=None):
        embed = params["embed"]["weight"]
        if fp8:
            embed = round_embed(embed)
        h = hf["embedding_multiplier"] * embed[tokens].astype(F32)
        seen = {"mamba": 0, "attention": 0}
        margin, flips = None, 0
        for l, kind in enumerate(hf["layer_types"]):
            lp = jax.tree.map(lambda t: t[l], params["layers"])
            mixer = jax.tree.map(lambda t: t[seen[kind]], params[key[kind]])
            seen[kind] += 1
            given = None if routed is None else routed[:, l]
            h, m, f = layer[kind](h, lp, mixer, given)
            margin = m if margin is None else jnp.minimum(margin, m)
            flips = flips + f.astype(jnp.int32)
        logps = head(embed, params["final_norm"]["scale"], h, tokens)
        return logps, margin[:-1], flips[:-1]

    return fn


def sequence_logps(fn, params, seq, routed=None, pad_to=512):
    """Per-transition log-probabilities of one sequence, the smallest
    router margin behind each, and in how many layers the reference's own
    routing differs from ``routed`` [len(seq) - 1, L, k] (the system's
    routed experts of every position but the last, which it never read).
    Right-padded to a multiple of ``pad_to`` so few shapes compile;
    causal layers make the padding invisible to the real positions."""
    T = -(-len(seq) // pad_to) * pad_to
    tokens = jnp.asarray(list(seq) + [0] * (T - len(seq)), jnp.int32)
    if routed is not None:
        routed = np.asarray(routed, np.int32)
        assert routed.shape[0] == len(seq) - 1, (routed.shape, len(seq))
        routed = jnp.asarray(
            np.concatenate(
                [routed, np.zeros((T - len(routed),) + routed.shape[1:], np.int32)]
            )
        )
    with jax.default_matmul_precision("highest"):
        logps, margins, flips = fn(params, tokens, routed)
    n = len(seq) - 1
    return np.asarray(logps)[:n], np.asarray(margins)[:n], np.asarray(flips)[:n]
