"""Ring attention: context parallelism over the ``seq`` mesh axis.

The reference has NO context parallelism (SURVEY §2.9: long context handled
by packed batches + token-budget micro-batching); this module provides the
TPU-idiomatic long-context answer the rebuild is expected to add: activations
sharded along the sequence dimension over the ICI ring, with KV blocks
rotated via ``lax.ppermute`` while each device accumulates its queries'
attention in online-softmax form (blockwise attention; see RingAttention,
Liu et al. 2023 — public technique).

Pure-jnp blockwise math (autodiff-friendly; XLA fuses the per-block matmuls
onto the MXU), usable standalone inside ``shard_map`` or through
:func:`ring_attention` which wraps the shard_map plumbing.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _block_attn(
    q,  # [B, Tq, H, hd]
    k,  # [B, Tk, H, hd]  (already head-repeated to H = n_q_heads)
    v,  # [B, Tk, H, hd]
    mask,  # [B, Tq, Tk] bool
) -> Tuple[jax.Array, jax.Array]:
    """Unnormalized block attention: returns (weighted values [B,Tq,H,hd],
    row logsumexp [B,H,Tq])."""
    hd = q.shape[-1]
    scores = jnp.einsum(
        "bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(hd)
    scores = jnp.where(mask[:, None, :, :], scores, _NEG_INF)
    lse = jax.nn.logsumexp(scores, axis=-1)  # [B,H,Tq]
    probs = jnp.exp(scores - lse[..., None])
    # rows with no valid key: lse == -inf-ish; zero their probs
    valid_row = lse > _NEG_INF / 2
    probs = jnp.where(valid_row[..., None], probs, 0.0)
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out, lse


def _combine(out_a, lse_a, out_b, lse_b):
    """Merge two partial attention results in online-softmax form."""
    lse = jnp.logaddexp(lse_a, lse_b)
    wa = jnp.exp(lse_a - lse)[..., None].swapaxes(1, 2)  # [B,Tq,H,1]
    wb = jnp.exp(lse_b - lse)[..., None].swapaxes(1, 2)
    return out_a * wa + out_b * wb, lse


def ring_attention_local(
    q: jax.Array,  # [B, T_local, Hq, hd]
    k: jax.Array,  # [B, T_local, Hkv, hd]
    v: jax.Array,
    seg: jax.Array,  # [B, T_local] int32 (0 = padding)
    pos: jax.Array,  # [B, T_local] int32 within-segment positions
    axis_name: str,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Per-device body (call inside shard_map over ``axis_name``).

    Each rotation step r: this device attends its local queries against the
    KV block originally owned by device (i - r) mod n, received over the
    ring.  Packing semantics (same-segment + causal by positions) work
    across blocks because segment ids are globally unique per row.
    """
    n = jax.lax.psum(1, axis_name)
    Hq, Hkv = q.shape[2], k.shape[2]
    rep = Hq // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def mask_for(seg_kv, pos_kv):
        m = (
            (seg[:, :, None] == seg_kv[:, None, :])
            & (pos[:, :, None] >= pos_kv[:, None, :])
            & (seg[:, :, None] != 0)
            & (seg_kv[:, None, :] != 0)
        )
        if sliding_window is not None:
            m &= pos[:, :, None] - pos_kv[:, None, :] < sliding_window
        return m

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, _):
        out, lse, kv_k, kv_v, kv_seg, kv_pos = carry
        o_i, lse_i = _block_attn(q, kv_k, kv_v, mask_for(kv_seg, kv_pos))
        out, lse = _combine(out, lse, o_i, lse_i)
        kv_k = jax.lax.ppermute(kv_k, axis_name, perm)
        kv_v = jax.lax.ppermute(kv_v, axis_name, perm)
        kv_seg = jax.lax.ppermute(kv_seg, axis_name, perm)
        kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
        return (out, lse, kv_k, kv_v, kv_seg, kv_pos), None

    B, T, H, hd = q.shape
    out0 = jnp.zeros((B, T, H, hd), jnp.float32)
    lse0 = jnp.full((B, H, T), _NEG_INF, jnp.float32)
    (out, lse, *_), _ = jax.lax.scan(
        body, (out0, lse0, k, v, seg, pos), None, length=n
    )
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [B, T, Hq, hd] — T sharded over ``axis``
    k: jax.Array,
    v: jax.Array,
    seg: jax.Array,  # [B, T]
    pos: jax.Array,  # [B, T]
    mesh,
    axis: str = "seq",
    batch_axes: Tuple[str, ...] = ("data", "fsdp"),
    head_axis: Optional[str] = "model",
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """shard_map wrapper: batch over ``batch_axes``, sequence over ``axis``,
    heads over ``head_axis``; XLA only moves KV blocks over the ring."""
    bspec = P(batch_axes)
    qkv_spec = P(batch_axes, axis, head_axis, None)
    tok_spec = P(batch_axes, axis)
    fn = partial(
        ring_attention_local,
        axis_name=axis,
        sliding_window=sliding_window,
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, tok_spec, tok_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, seg, pos)
