"""The plain reference of the ``laguna`` family (Laguna-XS.2) ON THE
TRAINER: the forward pass of the cut model one sequence at a time, the PPO
actor loss of ``reference.ppo_actor_loss`` and its GRADIENT by ``jax.grad``
of that plain forward, in straightforward ``jax.numpy`` and float32 under
"highest" matmul precision: no kernel, no packing, no grouping.

    h = embed[tokens]
    per layer l of kind t_l = layer_types[l], H_l = num_attention_heads_per_layer[l]
    (48 full, 64 sliding), 8 KV heads, head 128:
        a       = rmsnorm(h; w1)
        q, k, v = a W_q, a W_k, a W_v                      (H_l, 8, 8 heads)
        full:    q, k = rope_yarn(first 64 of a head's 128 dims): YaRN's
                 blend of theta-500,000 frequencies and the same / 64 by
                 the ramp between the correction dims of beta_fast 64 and
                 beta_slow 1 at 4,096 positions, cos and sin times
                 attention_factor 1.41589; the other 64 dims as they are
        sliding: q, k = rope(all 128 dims, theta 10,000)
        s_ij    = q_i . k_j / sqrt(128)   for j <= i and, sliding, i - j < 512
        o       = softmax(s) v;  o_head *= sigmoid(a W_g)_head   (gating)
        h       = h + o W_o
        m       = rmsnorm(h; w2)
        layer 0 (mlp_layer_types "dense"):
            h   = h + W_down (silu(W_gate m) * W_up m)              (8,192)
        layers 1.. ("sparse"):
            z   = sigmoid(m W_router)                           (256 scores)
            id  = top_8(z + bias);  w = 2.5 z_id / sum(z_id)
            h   = h + sum_{e in id} w_e E_e(m) + E_shared(m)    (SwiGLU, 512)
    logits = rmsnorm(h_L; w_f) W_head                          (untied head)

Every HELD expert is applied to every token and weighted, zero where not
chosen; a pair routed to an expert held elsewhere (32-255 of the benchmark's
share) adds nothing, as in the program.  Attention runs a block of queries
at a time and the experts a block of tokens at a time, each block and each
layer under ``jax.checkpoint``: they change no number and let a 16,384-token
sequence at the published widths fit beside the float32 weights and their
gradient.

What the config does not settle, each the configuration's
(``benchmark/configs/laguna-xs.2.json``, ``assumed``):

* ``gating: true`` READ AS the gated-attention rule: one sigmoid gate a
  head from the layer's normed input, on the head's output before ``W_o``;
* no ``scoring_func`` / ``norm_topk_prob`` / ``n_group`` key: sigmoid
  scores, the choice by score + a bias that carries no gradient, weights
  the chosen scores renormalised times ``moe_routed_scaling_factor`` (2.5
  is that family's factor), no groups, no auxiliary loss, no q/k norm;
* ``moe_apply_router_weight_on_input`` false: weights on the OUTPUTS;
* rope rotates halves ``(j, j + rot / 2)`` of the rotated columns (the
  HuggingFace ``rotate_half`` convention), the rotated columns first;
* the window's edge is ``i - j < sliding_window``.

``given`` [expert layers, T, 8]: the routing the system under test took for
this sequence.  The reference HOLDS it to its own choice scores: a token's
GAP is the best score (with the bias) it left out less the worst it took,
0 or less where the given eight ARE the top eight, and as large as the
scores it disagrees by otherwise.  Where the gap is within ``margin`` (two
near-tied experts changing places under bfloat16 rounding: the caller's
limit says how few tokens may lie outside) the reference FOLLOWS the given
choice (weights from its own scores of the given experts); outside it
takes its own.  The choice carries no gradient either way, so the
gradient with the choice given is the gradient of the function the trainer
differentiated.  ``gap_no_bias`` is the same gap by the scores WITHOUT the
choice bias: what a router that left the bias out would be held to, for
the control.

``wrong=`` makes one deliberate mistake, for the controls that show the
comparison's limits refuse it: ``"window_off"`` (sliding layers attend the
whole prefix), ``"gate_off"`` (no gate), ``"pair_dropped"`` (each token's
last held pair adds nothing), ``"rope_other_half"`` (the full layers rotate
the LAST 64 dims), ``"weights_on_input"`` (the router's weights scale the
experts' inputs).  :func:`float8_weights` rounds every matrix to float8
e4m3, for the control in the nearest precision below.

It reads the published ``config.json`` keys and the weight tree the system
under test trains (``embed.weight``, ``lm_head.w``, ``final_norm``;
``layers.{attn_norm, mlp_norm}`` over all layers; ``attn`` / ``window``
``.{q, k, v, o, gate}`` over the layers of each kind; ``dense.{gate, up,
down}``; ``layers.mlp.{router.{w, bias}, experts.{gate, up, down} (each
[E_held, F, D]), shared}`` over the expert layers); it calls no model code
of the program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.reference_deepseek_v3 import (
    _fp8_round,
    _fp8_scale,
    _rmsnorm,
    _w,
)

F32 = jnp.float32

#: query positions attended at once: scores are [heads, this, T] float32
#: (0.5 GB at 64 heads x 16,384 keys; at 256 the gradient's program stood
#: within a gigabyte of the chip's memory, my chip run, PR 53)
QUERY_BLOCK = 128
#: tokens whose held experts are applied at once: [this, E_held, F] float32
TOKEN_BLOCK = 1024

WRONG = (
    None, "window_off", "gate_off", "pair_dropped", "rope_other_half",
    "weights_on_input",
)

#: parameter groups of the gradient's comparison -> paths in the tree
GROUPS = {
    "attention": [("attn", n) for n in "qkvo"],
    "window": [("window", n) for n in "qkvo"],
    "gate": [("attn", "gate"), ("window", "gate")],
    "router": [("layers", "mlp", "router", "w")],
    "experts": [("layers", "mlp", "experts")],
    "shared": [("layers", "mlp", "shared")],
    "dense": [("dense",)],
    "embed": [("embed",)],
    "head": [("lm_head",)],
}


def group_leaves(tree, group: str):
    """The leaves of ``tree`` (a weight or gradient tree) in ``group``."""
    out = []
    for path in GROUPS[group]:
        sub = tree
        for k in path:
            sub = sub[k]
        out += jax.tree.leaves(sub)
    return out


def kinds_of(hf: dict, n_layers: int):
    """``(kind, heads, mlp kind)`` of the first ``n_layers`` layers."""
    return list(
        zip(
            hf["layer_types"][:n_layers],
            hf["num_attention_heads_per_layer"][:n_layers],
            hf["mlp_layer_types"][:n_layers],
        )
    )


def yarn_inv_freq(rp: dict, dim: int):
    """``[dim / 2]`` frequencies of a ``rope_parameters`` entry over
    ``dim`` rotated columns: plain, or YaRN's blend."""
    base = float(rp["rope_theta"])
    pos = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", "default") != "yarn":
        return (1.0 / pos).astype(np.float32)
    factor, orig = float(rp["factor"]), rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return ((1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)).astype(
        np.float32
    )


def _rope(hf: dict, kind: str, x, wrong):
    """x [T, H, hd] under the kind's rope rule."""
    rp = hf["rope_parameters"][kind]
    hd = x.shape[-1]
    rot = int(round(float(rp.get("partial_rotary_factor", 1)) * hd))
    at = hd - rot if (wrong == "rope_other_half" and rot < hd) else 0
    angles = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(
        yarn_inv_freq(rp, rot)
    )
    m = float(rp.get("attention_factor", 1.0)) if rp.get("rope_type") == "yarn" else 1.0
    cos, sin = (jnp.cos(angles) * m)[:, None, :], (jnp.sin(angles) * m)[:, None, :]
    x1, x2 = x[..., at : at + rot // 2], x[..., at + rot // 2 : at + rot]
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([x[..., :at], turned, x[..., at + rot :]], axis=-1)


def _attention(hf: dict, kind: str, heads: int, a, ap, wrong):
    T = a.shape[0]
    Hkv, hd = hf["num_key_value_heads"], hf["head_dim"]
    q = _rope(hf, kind, (a @ _w(ap["q"])).reshape(T, heads, hd), wrong)
    k = _rope(hf, kind, (a @ _w(ap["k"])).reshape(T, Hkv, hd), wrong)
    v = (a @ _w(ap["v"])).reshape(T, Hkv, hd)
    windowed = kind == "sliding_attention" and wrong != "window_off"
    W = hf["sliding_window"]
    at = jnp.arange(T)

    qb = min(QUERY_BLOCK, T)

    @jax.checkpoint
    def block(i0):
        qi = jax.lax.dynamic_slice_in_dim(q, i0, qb, 0)
        qi = qi.reshape(qb, Hkv, heads // Hkv, hd)
        s = jnp.einsum("ikrd,jkd->krij", qi, k) / math.sqrt(hd)
        i = i0 + jnp.arange(qb)
        keep = at[None, :] <= i[:, None]
        if windowed:
            keep = keep & (i[:, None] - at[None, :] < W)
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -1e30), axis=-1)
        return jnp.einsum("krij,jkd->ikrd", p, v).reshape(qb, heads, hd)

    o = jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, heads, hd)
    if hf.get("gating") and wrong != "gate_off":
        o = o * jax.nn.sigmoid(a @ _w(ap["gate"]))[:, :, None]
    return o.reshape(T, heads * hd) @ _w(ap["o"])


def _gated(m, p):
    return (jax.nn.silu(m @ _w(p["gate"])) * (m @ _w(p["up"]))) @ _w(p["down"])


def _choice_gap(scores, idx):
    """``[T]``: the best of ``scores`` [T, E] outside ``idx`` [T, K] less
    the worst inside."""
    inside = jnp.any(
        idx[:, :, None] == jnp.arange(scores.shape[1])[None, None, :], axis=1
    )
    return jnp.max(jnp.where(inside, -jnp.inf, scores), -1) - jnp.min(
        jnp.where(inside, scores, jnp.inf), -1
    )


def _experts(hf: dict, m, mlp, first: int, given, margin=math.inf, wrong=None):
    """``(sum_k w_k E_k(m) + E_shared(m), {"flipped", "gap", "gap_no_bias"}
    each [T])`` over the held experts ``[first, first + E_held)``: the
    tokens whose own choice differs from ``given``, and the given choice's
    gap by the reference's choice scores with and without the bias."""
    T, D = m.shape
    K = hf["num_experts_per_tok"]
    z = jax.nn.sigmoid(m @ _w(mlp["router"]))  # [T, E]
    plain = jax.lax.stop_gradient(z)
    choice = plain + mlp["router"]["bias"].astype(F32)
    _, own = jax.lax.top_k(choice, K)
    if given is None:
        idx = own
        about = {k: jnp.zeros((T,), F32) for k in ("gap", "gap_no_bias")}
    else:
        about = {
            "gap": _choice_gap(choice, given),
            "gap_no_bias": _choice_gap(plain, given),
        }
        idx = jnp.where((about["gap"] <= margin)[:, None], given, own)
    about["flipped"] = jnp.any(
        jnp.sort(own, -1) != jnp.sort(own if given is None else given, -1),
        axis=-1,
    )
    top = jnp.take_along_axis(z, idx, axis=-1)
    w = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    w = w * float(hf.get("moe_routed_scaling_factor", 1.0))
    ex = mlp["experts"]
    held = ex["gate"].shape[0]
    local = idx - first
    if wrong == "pair_dropped":
        # each token's LAST held pair (in the router's order) adds nothing
        is_held = (local >= 0) & (local < held)
        last = K - 1 - jnp.argmax(is_held[:, ::-1], axis=-1)
        local = jnp.where(
            (jnp.arange(K)[None, :] == last[:, None]) & is_held, -1, local
        )
    w_tok = jnp.sum(
        jnp.where(
            local[:, :, None] == jnp.arange(held)[None, None, :],
            w[:, :, None], 0.0,
        ),
        axis=1,
    )  # [T, E_held], 0 where not chosen

    @jax.checkpoint
    def block(xs):
        mb, wb = xs  # [C, D], [C, E_held]
        if wrong == "weights_on_input":
            xin = mb[:, None, :] * wb[:, :, None]  # [C, E, D]
            g = jnp.einsum("ced,efd->cef", xin, ex["gate"].astype(F32))
            u = jnp.einsum("ced,efd->cef", xin, ex["up"].astype(F32))
            hid = jax.nn.silu(g) * u * (wb != 0)[:, :, None]
        else:
            g = jnp.einsum("cd,efd->cef", mb, ex["gate"].astype(F32))
            u = jnp.einsum("cd,efd->cef", mb, ex["up"].astype(F32))
            hid = jax.nn.silu(g) * u * wb[:, :, None]
        return jnp.einsum("cef,efd->cd", hid, ex["down"].astype(F32))

    tb = min(TOKEN_BLOCK, T)
    out = jax.lax.map(
        block, (m.reshape(-1, tb, D), w_tok.reshape(-1, tb, held))
    ).reshape(T, D)
    return out + _gated(m, mlp["shared"]), about


def _layer(
    hf, kind, heads, mlp_kind, first, margin, wrong, h, norms, mixer, mlp, given
):
    eps = hf["rms_norm_eps"]
    a = _rmsnorm(h, norms["attn_norm"]["scale"], eps)
    h = h + _attention(hf, kind, heads, a, mixer, wrong)
    m = _rmsnorm(h, norms["mlp_norm"]["scale"], eps)
    if mlp_kind == "dense":
        return h + _gated(m, mlp), None
    out, about = _experts(hf, m, mlp, first, given, margin, wrong)
    return h + out, about


def token_logps(
    hf: dict, params, tokens, given=None, first_expert=0, wrong=None,
    margin=math.inf,
):
    """``(log p(tokens[t+1] | tokens[:t+1]) [T - 1], {"flipped" [T] bool,
    "gap", "gap_no_bias" [T] float32: the largest over the expert
    layers})`` of ONE sequence ``tokens`` [T] (T a multiple of the blocks;
    padding at the end is invisible to the real positions).  ``margin``:
    how far outside the reference's own top eight a ``given`` choice is
    still followed (every given choice by default)."""
    assert wrong in WRONG, wrong
    at = lambda tree, i: jax.tree.map(lambda t: t[i], tree)
    lay = params["layers"]
    n_layers = lay["attn_norm"]["scale"].shape[0]
    h = params["embed"]["weight"][tokens].astype(F32)
    about = {
        "flipped": jnp.zeros(tokens.shape, bool),
        "gap": jnp.full(tokens.shape, -jnp.inf, F32),
        "gap_no_bias": jnp.full(tokens.shape, -jnp.inf, F32),
    }
    seen = {"full_attention": 0, "sliding_attention": 0, "dense": 0, "sparse": 0}
    for l, (kind, heads, mlp_kind) in enumerate(kinds_of(hf, n_layers)):
        j, e = seen[kind], seen[mlp_kind]
        seen[kind] += 1
        seen[mlp_kind] += 1
        stack = params["attn" if kind == "full_attention" else "window"]
        norms = {k: at(lay[k], l) for k in ("attn_norm", "mlp_norm")}
        mlp = at(params["dense"], e) if mlp_kind == "dense" else at(lay["mlp"], e)
        g = None if given is None or mlp_kind == "dense" else given[e]
        fn = jax.checkpoint(
            partial(_layer, hf, kind, heads, mlp_kind, first_expert, margin, wrong)
        )
        h, layer_about = fn(h, norms, at(stack, j), mlp, g)
        if layer_about is not None:
            about = {
                "flipped": about["flipped"] | layer_about["flipped"],
                **{
                    k: jnp.maximum(about[k], layer_about[k])
                    for k in ("gap", "gap_no_bias")
                },
            }
    x = _rmsnorm(h, params["final_norm"]["scale"], hf["rms_norm_eps"])[:-1]
    logits = x @ params["lm_head"]["w"].astype(F32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return tgt - lse, about


def token_losses(new_logp, old_logp, prox_logp, advantages, eps_clip, behav_cap):
    """``reference.ppo_actor_loss``'s per-transition terms in ``jax.numpy``
    (the decoupled PPO-clip loss: the ratio against the proximal policy,
    weighted by the behaviour importance weight, dropped past its cap)."""
    ratio = jnp.exp(new_logp - prox_logp)
    clipped = jnp.clip(ratio, 1.0 - eps_clip, 1.0 + eps_clip)
    pg = jnp.maximum(-advantages * ratio, -advantages * clipped)
    w = jnp.exp(prox_logp - old_logp)
    if behav_cap is not None:
        w = jnp.where(w <= behav_cap, w, 0.0)
    return pg * w


def make_loss_and_grad(
    hf: dict, iface: dict, first_expert=0, wrong=None, margin=math.inf
):
    """``fn(acc, params, seq) -> (acc + d loss_sum / d params, loss_sum,
    logps [T - 1], :func:`token_logps`' account of the choice)`` of one
    padded sequence ``seq`` =
    ``{tokens [T], old, prox, adv, mask [T - 1], given}``: ``loss_sum`` is
    the masked SUM of the sequence's per-transition losses (the caller
    divides by the batch's count), the accumulator is donated, and the
    weights are an ARGUMENT (closing a jit over a multi-GB tree copies it
    into the host's memory).  For the float8 control the caller rounds the
    tree once (:func:`float8_weights`)."""

    def loss(params, seq):
        logp, about = token_logps(
            hf, params, seq["tokens"], seq.get("given"), first_expert, wrong,
            margin,
        )
        per = token_losses(
            logp / iface["temperature"], seq["old"], seq["prox"], seq["adv"],
            iface["eps_clip"], iface.get("behav_imp_weight_cap"),
        )
        return jnp.sum(jnp.where(seq["mask"], per, 0.0)), (logp, about)

    def step(acc, params, seq):
        (loss_sum, (logp, about)), g = jax.value_and_grad(loss, has_aux=True)(
            params, seq
        )
        return jax.tree.map(jnp.add, acc, g), loss_sum, logp, about

    return jax.jit(step, donate_argnums=(0,))


def make_token_logps(hf: dict, first_expert=0, wrong=None):
    """jit of :func:`token_logps` (weights an argument)."""
    return jax.jit(
        lambda params, tokens, given: token_logps(
            hf, params, tokens, given, first_expert, wrong
        )
    )


@jax.jit
def float8_weights(tree):
    """Every matrix of the tree as float8 e4m3 would hold it (one scale a
    stacked matrix), in float32; norm scales and the router's bias as they
    are."""

    def one(path, w):
        keys = [k.key if hasattr(k, "key") else str(k) for k in path]
        if "bias" in keys or any("norm" in k for k in keys):
            return w
        return _fp8_round(w, _fp8_scale(w))

    return jax.tree_util.tree_map_with_path(one, tree)


def ppo_sequences(batch: dict, which, iface: dict, pad_to: int, advantage=None):
    """``{i: sequence}`` of ``batch``'s sequences ``which``
    (``lengths.train_batch``'s packed layout) as :func:`make_loss_and_grad`
    takes them, each padded to ``pad_to``: tokens, behaviour and proximal
    log-probabilities, the advantage (no critic, no KL, discount 1: every
    response transition's is its sequence's clipped score, or
    ``advantage(score)``) and the response mask; ``"len"`` its length."""
    score = np.clip(
        batch["rewards"] * iface["reward_scaling"] - iface["reward_bias"],
        -iface["max_reward_clip"], iface["max_reward_clip"],
    )
    starts = np.concatenate([[0], np.cumsum(batch["seqlens"])])
    tstarts = np.concatenate([[0], np.cumsum(np.asarray(batch["seqlens"]) - 1)])
    out = {}
    for i in which:
        s, p = batch["seqlens"][i], batch["prompt_lens"][i]
        tr = slice(tstarts[i], tstarts[i] + s - 1)
        adv = score[i] if advantage is None else advantage(score[i])
        pad = lambda a, dt: pad_sequence(np.asarray(a, dt), pad_to - 1)
        out[i] = {
            "len": s,
            "tokens": pad_sequence(
                batch["packed_input_ids"][starts[i] : starts[i] + s].astype(
                    np.int32
                ),
                pad_to,
            ),
            "old": pad(batch["packed_logprobs"][tr], np.float32),
            "prox": pad(batch["prox_logp"][tr], np.float32),
            "adv": pad(np.full(s - 1, adv), np.float32),
            "mask": pad(np.arange(s - 1) >= p - 1, bool),
        }
    return out


def pad_sequence(seq, pad_to: int):
    """``seq`` right-padded with zeros to ``pad_to`` entries."""
    seq = np.asarray(seq)
    out = np.zeros((pad_to,) + seq.shape[1:], seq.dtype)
    out[: len(seq)] = seq
    return out
