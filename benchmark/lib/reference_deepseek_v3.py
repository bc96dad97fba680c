"""The plain reference of the ``deepseek_v3`` family (GigaChat3.1-702B-A36B):
the forward pass as ``modeling_deepseek_v3.py`` and the model's
``config.json`` describe it, in straightforward ``jax.numpy`` and float32:
no kernel, no cache, no absorbed form, one sequence at a time, "highest"
matmul precision.

    h = embed[tokens]
    per layer:  h += mla(rmsnorm(h));  h += mlp(rmsnorm(h))
    logits = rmsnorm(h) W_head                      (untied head)

* **mla** (latent attention, UNABSORBED): ``c_q = rmsnorm(a W_qa)``;
  ``[q_nope | q_rope]_i = c_q W_qb`` per head; ``[c_kv | k_r] = a W_kva``,
  ``c_kv = rmsnorm(c_kv)``, ``k_rope = rope(k_r)``, ONE for all heads;
  ``k_nope_i = c_kv W_UK,i``, ``v_i = c_kv W_UV,i``; ``s_i(t, u) = scale
  (q_nope_i(t) . k_nope_i(u) + rope(q_rope_i(t)) . k_rope(u))``, causal
  softmax, ``out = concat_i(sum_u p_i v_i) W_o``.  ``scale =
  (nope + rope)^-0.5 m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``
  under YaRN.
* **rope** with YaRN on the rope dims: ``inv_freq_j`` blends ``theta^(-2j
  / d)`` and the same over ``factor`` by a linear ramp between the
  correction dims of ``beta_fast`` and ``beta_slow`` at
  ``original_max_position_embeddings``; cos and sin times ``mscale(factor,
  mscale) / mscale(factor, mscale_all_dim)``.
* **dense mlp** (the first ``first_k_dense_replace`` layers): ``(silu(a
  W_g) * a W_u) W_d``.
* **experts** (``noaux_tc``): ``s = sigmoid(a W_r)``; ``c = s + b``
  (``e_score_correction_bias``), for the CHOICE only; a group (``n_routed
  / n_group`` consecutive experts) scores the sum of its two largest
  ``c``; the ``topk_group`` best groups are kept, every other group's ``c``
  set to 0, and the top ``num_experts_per_tok`` taken; ``w = s[chosen] /
  sum(s[chosen]) * routed_scaling_factor``.  EVERY held expert is computed
  for every token and the routed ones taken.  The shared expert has the
  same gated form at ``n_shared_experts * moe_intermediate_size``, for
  every token, weight 1.

Departures from the published model, each the configuration's or the
weight tree's:

* **the share of a deployment**: the weight tree holds the experts
  ``[first, first + held)`` of the router's ``n_routed_experts``; a pair
  routed to an expert held elsewhere adds nothing (the program does the
  same); the vocabulary is the rows the tree holds;
* **the rotary pair convention**: the published checkpoint pairs rope
  dims (2j, 2j+1) and de-interleaves q and k at run time; this reference
  takes the tree the program serves, whose ``q_b`` / ``kv_a`` rope
  columns the adapter de-interleaved ONCE on load, and rotates halves
  (j, j + d/2).  The two are the same function of the published weights;
* ``kv_b_proj`` arrives as its key columns ``k_b`` and its value columns
  ``v_b`` (the adapter splits it per head);
* the multi-token-prediction module (``num_nextn_predict_layers``) takes
  no part in the main model's logits and is not here.

It reads the published ``config.json`` keys and the weight tree the system
under test serves (``embed.weight``, ``lm_head.w``, ``final_norm``;
``layers.{attn_norm, mlp_norm}`` over all layers; ``layers.mlp.{router.{w,
bias}, experts.{gate, up, down} (each [E_held, F, D]), shared}`` over the
expert layers; ``dense.{gate, up, down}`` over the dense layers;
``latent.{q_a, q_a_norm, q_b, kv_a, kv_a_norm, k_b, v_b, o}`` over all
layers); it calls no model code of the program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: query positions attended at once: scores are [heads, this, T] float32
QUERY_BLOCK = 512


def _rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _w(p):
    return (p["w"] if isinstance(p, dict) else p).astype(F32)


def _fp8_weights(tree):
    """Every matrix of ``tree`` as a server holding float8 (e4m3: four
    significant bits, smallest step 2^-9, largest value 448; one scale a
    stacked matrix, its largest magnitude -> 448) would read it; vectors
    (norm scales, the router's bias) as they are.  The rounding is
    written out in float32, so it runs wherever this file does."""

    def one(w):
        return w if w.ndim < 2 else _fp8_round(w, _fp8_scale(w))

    return jax.tree.map(one, tree)


def _fp8_scale(w):
    return jnp.max(jnp.abs(w.astype(F32))) / 448.0


def _fp8_round(w, s):
    """``w`` (or a part of the matrix whose scale ``s`` is) as float8
    e4m3 under that scale, in float32."""
    x = w.astype(F32) / s
    _, e = jnp.frexp(x)  # |x| in [2^(e-1), 2^e)
    step = jnp.exp2((jnp.maximum(e, -5) - 4).astype(F32))
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0) * s


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(hf: dict) -> float:
    scale = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    rs = hf.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        scale *= m * m
    return scale


def rope_inv_freq(hf: dict) -> np.ndarray:
    """[rope / 2] rotary frequencies (module docstring)."""
    d, base = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    rs = hf.get("rope_scaling")
    if not rs:
        return extra.astype(np.float32)
    inter = extra / rs["factor"]
    orig = rs["original_max_position_embeddings"]

    def dim_of(n_rot):
        return d * math.log(orig / (n_rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * (1.0 - mask) + extra * mask).astype(np.float32)


def _rope(hf, x, positions):
    """x [T, ..., rope] rotated by halves (j, j + rope/2)."""
    ang = positions.astype(F32)[:, None] * jnp.asarray(rope_inv_freq(hf))
    rs = hf.get("rope_scaling")
    m = 1.0
    if rs:
        m = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
            rs["factor"], rs["mscale_all_dim"]
        )
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), (jnp.sin(ang) * m).reshape(shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(hf, a, ap):
    """a [T, D] -> [T, D], unabsorbed, a block of queries at a time."""
    T = a.shape[0]
    H, eps = hf["num_attention_heads"], hf["rms_norm_eps"]
    nope, rope, vd = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    r = hf["kv_lora_rank"]
    pos = jnp.arange(T)
    c_q = _rmsnorm(a @ _w(ap["q_a"]), ap["q_a_norm"]["scale"], eps)
    q = (c_q @ _w(ap["q_b"])).reshape(T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(hf, q[..., nope:], pos)
    ckr = a @ _w(ap["kv_a"])
    c_kv = _rmsnorm(ckr[:, :r], ap["kv_a_norm"]["scale"], eps)
    k_rope = _rope(hf, ckr[:, r:], pos)  # [T, rope], one for all heads
    k_nope = (c_kv @ _w(ap["k_b"])).reshape(T, H, nope)
    v = (c_kv @ _w(ap["v_b"])).reshape(T, H, vd)
    scale = softmax_scale(hf)
    Q = min(QUERY_BLOCK, T)
    assert T % Q == 0, (T, Q)

    def block(args):
        qn, qr, t0 = args  # [Q, H, nope], [Q, H, rope], first position
        s = scale * (
            jnp.einsum("thn,uhn->htu", qn, k_nope)
            + jnp.einsum("thr,ur->htu", qr, k_rope)
        )
        causal = (t0 + jnp.arange(Q))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        return jnp.einsum("htu,uhv->thv", p, v)

    o = jax.lax.map(
        block,
        (
            q_nope.reshape(T // Q, Q, H, nope),
            q_rope.reshape(T // Q, Q, H, rope),
            jnp.arange(0, T, Q),
        ),
    )
    return o.reshape(T, H * vd) @ _w(ap["o"])


def route(hf, scores, bias):
    """The published choice: ``scores`` [T, E] (sigmoid), ``bias`` [E] ->
    expert ids [T, k + 1] (the k chosen, and the one that came next) and
    the choice scores of those."""
    T, E = scores.shape
    G, k = hf["n_group"], hf["num_experts_per_tok"]
    choice = scores + bias.astype(F32)
    grouped = choice.reshape(T, G, E // G)
    group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)  # [T, G]
    _, group_idx = jax.lax.top_k(group_scores, hf["topk_group"])
    group_mask = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], group_idx].set(True)
    masked = jnp.where(group_mask[:, :, None], grouped, 0.0).reshape(T, E)
    top, idx = jax.lax.top_k(masked, k + 1)
    return idx, top


def _experts(hf, m, mlp, first, given=None, low=None):
    """m [T, D] -> [T, D]: this share's part of the routed experts' sum,
    plus the shared expert.  Also, per token, how close the k-th pick's
    choice score was to the one after it, and whether this router's own
    k differ from ``given`` [T, k] (the experts the system under test
    routed each token to: the scores and the weights are this reference's
    own, only WHICH k is taken from the system, so that what separates
    the two is rounding and not a near-tie that fell the other way).
    ``low``: the held experts' matrices rounded to float8, one expert
    at a time under its stack's one scale (router and shared expert come
    rounded: :func:`_layer`)."""
    k = hf["num_experts_per_tok"]
    scores = jax.nn.sigmoid(m @ _w(mlp["router"]))  # [T, n_routed]
    idx, top = route(hf, scores, mlp["router"]["bias"])
    margin = top[:, k - 1] - top[:, k]
    idx = idx[:, :k]
    flipped = jnp.zeros(m.shape[:1], bool)
    if given is not None:
        flipped = jnp.any(jnp.sort(given, -1) != jnp.sort(idx, -1), -1)
        idx = given
    w = jnp.take_along_axis(scores, idx, -1)
    if hf.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * hf["routed_scaling_factor"]
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
        y = (jax.nn.silu(m @ g.astype(F32).T) * (m @ u.astype(F32).T)) @ d.astype(F32)
        return acc + w_e[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(m), (ex["gate"], ex["up"], ex["down"], wts.T)
    )
    if "shared" in mlp:
        out = out + _gated(m, mlp["shared"])
    return out, margin, flipped


def _gated(m, p):
    return (jax.nn.silu(m @ _w(p["gate"])) * (m @ _w(p["up"]))) @ _w(p["down"])


def _layer(hf, mlp_kind, first, low, h, norms, mixer, mlp, given=None):
    """One layer: h [T, D] -> (h, router margin [T], own routing differs
    from ``given`` [T]); the last two are +inf and False after a dense
    MLP."""
    eps = hf["rms_norm_eps"]
    if low is not None:
        # (the held experts' stacks inside _experts, an expert at a time:
        # a float32 copy of a layer's 16 is 2.8 GB)
        held = {k: v for k, v in mlp.items() if k == "experts"}
        rest = {k: v for k, v in mlp.items() if k != "experts"}
        mixer, rest = _fp8_weights((mixer, rest))
        mlp = dict(rest, **held)
    h = h + _mla(hf, _rmsnorm(h, norms["attn_norm"]["scale"], eps), mixer)
    m = _rmsnorm(h, norms["mlp_norm"]["scale"], eps)
    if mlp_kind == "dense":
        T = h.shape[0]
        return h + _gated(m, mlp), jnp.full((T,), jnp.inf, F32), jnp.zeros((T,), bool)
    out, margin, flipped = _experts(hf, m, mlp, first, given, low)
    return h + out, margin, flipped


def _head_logps(hf, head, norm_scale, h, tokens):
    """log p(tokens[t+1] | tokens[:t+1]) for t < T-1, shape [T-1]."""
    x = _rmsnorm(h, norm_scale, hf["rms_norm_eps"])[:-1]
    logits = x @ head.astype(F32)  # [T-1, V]
    tgt = jnp.take_along_axis(logits, tokens[1:, None], -1)[:, 0]
    return tgt - jax.nn.logsumexp(logits, -1)


def make_token_logps(hf: dict, first_expert: int = 0, low=None):
    """``fn(params, tokens, routed=None) -> (logps [T-1], smallest router
    margin over the expert layers [T-1], expert layers whose own routing
    differs from the given [T-1])``; ``routed`` [T, Le, k]: the system's
    routed experts of every EXPERT layer, which the reference then
    follows (:func:`_experts`).  ``hf`` is the configuration AS RUN
    (``num_hidden_layers`` and ``first_k_dense_replace`` of the cut).
    One jitted program a layer kind and one for the head, called layer
    by layer with that layer's weights as arguments: the whole stack in
    one program would keep every layer's float32 weight copies alive at
    once.  ``low = ("weights", "float8_e4m3fn")``: every matrix rounded
    to float8 first (the control of the cell's comparison)."""
    assert low is None or tuple(low) == ("weights", "float8_e4m3fn"), low
    layer = {
        kind: jax.jit(partial(_layer, hf, kind, first_expert, low))
        for kind in ("dense", "experts")
    }
    head = jax.jit(partial(_head_logps, hf))
    rounded = jax.jit(_fp8_weights)
    n_dense = hf["first_k_dense_replace"]
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
            mixer = at(params["latent"], l)
            if l < n_dense:
                h, m, f = layer["dense"](h, at(norms, l), mixer, at(params["dense"], l))
            else:
                e = l - n_dense
                given = None if routed is None else routed[:, e]
                h, m, f = layer["experts"](
                    h, at(norms, l), mixer, at(lay["mlp"], e), given
                )
            margin = jnp.minimum(margin, m)
            flips = flips + f.astype(jnp.int32)
        logps = head(lm_head, params["final_norm"]["scale"], h, tokens)
        return logps, margin[:-1], flips[:-1]

    return fn


def sequence_logps(fn, params, seq, routed=None, pad_to=QUERY_BLOCK):
    """Per-transition log-probabilities of one sequence, the smallest
    router margin behind each, and in how many expert layers the
    reference's own routing differs from ``routed`` [len(seq) - 1, Le, k]
    (the system's routed experts of every position but the last, which it
    never read).  Right-padded to a multiple of ``pad_to`` so few shapes
    compile; causal layers make the padding invisible to the real
    positions."""
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


def forward_logits(hf: dict, params, tokens, first_expert: int = 0):
    """Logits [T, V] of one sequence, routing for itself: what the CPU
    tests compare the program's logits with."""
    n_dense = hf["first_k_dense_replace"]
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = params["embed"]["weight"][tokens].astype(F32)
        lay = params["layers"]
        norms = {k: lay[k] for k in ("attn_norm", "mlp_norm")}
        for l in range(hf["num_hidden_layers"]):
            kind = "dense" if l < n_dense else "experts"
            mlp = params["dense"] if l < n_dense else lay["mlp"]
            h, _, _ = _layer(
                hf, kind, first_expert, None, h, at(norms, l),
                at(params["latent"], l), at(mlp, l - (0 if l < n_dense else n_dense)),
            )
        x = _rmsnorm(h, params["final_norm"]["scale"], hf["rms_norm_eps"])
        return x @ params["lm_head"]["w"].astype(F32)
