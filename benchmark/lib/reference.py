"""The plain reference of the Qwen2 family (Qwen2.5-1.5B, Qwen2.5-7B):
the forward pass as the model card and ``modeling_qwen2.py`` describe it,
in straightforward ``jax.numpy`` and float32 — no kernel, no cache, no
packing, one sequence at a time, "highest" matmul precision.

    h = embed[tokens]
    per layer:  a = rmsnorm(h);  q, k, v = a Wq + bq, a Wk + bk, a Wv + bv
                rope on q and k (half-rotation, base rope_theta)
                grouped-query causal softmax attention;  h += attn Wo
                m = rmsnorm(h);  h += (silu(m Wg) * (m Wu)) Wd
    logits = rmsnorm(h) Whead        (Whead = embed^T when tied)

It reads the published ``config.json`` keys and the weight tree the system
under test serves or trains (``embed.weight``, ``layers.*`` stacked over
the layer axis, ``final_norm``, ``lm_head.w``); it calls no model code of
the program.  Also here: the PPO actor loss the train cell is held to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, theta):
    """x [T, H, hd]; positions 0..T-1; rotate halves (HF convention)."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _lin(p, x):
    y = x @ p["w"].astype(F32)
    return y + p["b"].astype(F32) if "b" in p else y


def _layer(hf, h, lp):
    T = h.shape[0]
    n_q = hf["num_attention_heads"]
    n_kv = hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // n_q
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    a = _rmsnorm(h, lp["attn_norm"]["scale"], eps)
    q = _rope(_lin(lp["attn"]["q"], a).reshape(T, n_q, hd), theta)
    k = _rope(_lin(lp["attn"]["k"], a).reshape(T, n_kv, hd), theta)
    v = _lin(lp["attn"]["v"], a).reshape(T, n_kv, hd)
    g = n_q // n_kv
    q = q.reshape(T, n_kv, g, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
    h = h + _lin(lp["attn"]["o"], o.reshape(T, n_q * hd))
    m = _rmsnorm(h, lp["mlp_norm"]["scale"], eps)
    mlp = lp["mlp"]
    return h + _lin(mlp["down"], jax.nn.silu(_lin(mlp["gate"], m)) * _lin(mlp["up"], m))


def _final_hidden(hf, params, tokens):
    h = params["embed"]["weight"][tokens].astype(F32)

    def body(h, lp):
        return _layer(hf, h, lp), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    return _rmsnorm(h, params["final_norm"]["scale"], hf["rms_norm_eps"])


def _head_t(hf, params):
    """Output head as [V, D] rows (the embedding itself when tied)."""
    if hf.get("tie_word_embeddings", False):
        return params["embed"]["weight"]
    return params["lm_head"]["w"].T


def _token_logps(hf, params, tokens, vocab_block=16384):
    """log p(tokens[t+1] | tokens[:t+1]) for t < T-1, shape [T-1].  The
    log-sum-exp over the vocabulary is taken in blocks, so a 152k-word head
    never has to stand whole in float32."""
    x = _final_hidden(hf, params, tokens)[:-1]
    w = _head_t(hf, params)
    V = w.shape[0]
    lse = jnp.full((x.shape[0],), -jnp.inf, F32)
    for v0 in range(0, V, vocab_block):
        blk = x @ w[v0 : v0 + vocab_block].astype(F32).T
        lse = jnp.logaddexp(lse, jax.nn.logsumexp(blk, -1))
    tgt = jnp.sum(x * w[tokens[1:]].astype(F32), -1)
    return tgt - lse


def make_token_logps(hf: dict):
    """jit of :func:`_token_logps`; the weights are an ARGUMENT (closing a
    jit over a multi-GB tree copies it into the host's memory)."""
    return jax.jit(lambda params, tokens: _token_logps(hf, params, tokens))


def sequence_logps(fn, params, seq, pad_to=512) -> np.ndarray:
    """Per-transition log-probabilities of one sequence.  Right-padded to
    a multiple of ``pad_to`` so few shapes compile; causal attention makes
    the padding invisible to the real positions."""
    T = -(-len(seq) // pad_to) * pad_to
    tokens = jnp.asarray(list(seq) + [0] * (T - len(seq)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(params, tokens))[: len(seq) - 1]


def ppo_actor_loss(
    new_logp, old_logp, prox_logp, advantages, mask, eps_clip, behav_cap
) -> float:
    """Decoupled PPO-clip loss (AReaL, "boba^2"): the clip ratio is taken
    against the proximal policy, the clipped loss is weighted by the
    behaviour importance weight exp(prox - old), dropped where that weight
    exceeds the cap; mean over the masked (response) transitions.  Plain
    numpy float64."""
    new_logp, old_logp, prox_logp, advantages = (
        np.asarray(a, np.float64)
        for a in (new_logp, old_logp, prox_logp, advantages)
    )
    mask = np.asarray(mask, bool)
    ratio = np.exp(new_logp - prox_logp)
    clipped = np.clip(ratio, 1.0 - eps_clip, 1.0 + eps_clip)
    pg = np.maximum(-advantages * ratio, -advantages * clipped)
    w = np.exp(prox_logp - old_logp)
    if behav_cap is not None:
        w = np.where(w <= behav_cap, w, 0.0)
    return float(np.sum(np.where(mask, pg * w, 0.0)) / max(mask.sum(), 1))
