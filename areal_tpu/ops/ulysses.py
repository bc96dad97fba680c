"""Ulysses-style context parallelism: all-to-all sequence<->head exchange.

The second first-class long-context strategy next to ring attention
(areal_tpu/ops/ring_attention.py).  Where the ring rotates KV blocks around
the ICI with n permute steps, Ulysses (DeepSpeed-Ulysses, Jacobs et al.
2023 — public technique) pays exactly TWO all-to-alls: sequence-sharded
QKV are exchanged into head-sharded full-sequence tensors, each device runs
ordinary full attention over its head subset, and the output is exchanged
back.  Preferable when the head count comfortably exceeds the CP degree
and the interconnect's all-to-all is fast (TPU ICI); the ring wins at very
long sequences where the full [T, T] mask/score blocks no longer fit.

Packing semantics match the rest of the stack: same-segment + causal by
within-segment positions, optional sliding window.  The reference system
has NO context parallelism at all (SURVEY §2.9).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _full_attention(q, k, v, seg, pos, sliding_window):
    """Dense masked attention over the FULL sequence (q/k/v: [B,T,H,hd],
    same head count — kv already repeated)."""
    hd = q.shape[-1]
    scores = jnp.einsum(
        "bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / np.sqrt(hd)
    mask = (
        (seg[:, :, None] == seg[:, None, :])
        & (pos[:, :, None] >= pos[:, None, :])
        & (seg[:, :, None] != 0)
        & (seg[:, None, :] != 0)
    )
    if sliding_window is not None:
        mask &= pos[:, :, None] - pos[:, None, :] < sliding_window
    scores = jnp.where(mask[:, None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # fully-masked rows (padding queries) produce uniform probs; zero them
    any_valid = mask.any(axis=-1)[:, None, :, None]
    probs = jnp.where(any_valid, probs, 0.0)
    return jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))


def ulysses_attention_local(
    q: jax.Array,  # [B, T_local, Hq, hd]
    k: jax.Array,  # [B, T_local, Hkv, hd]
    v: jax.Array,
    seg: jax.Array,  # [B, T_local]
    pos: jax.Array,  # [B, T_local]
    axis_name: str,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Per-device body (inside shard_map over ``axis_name``).

    all-to-all #1: [B, T/n, H, hd] -> [B, T, H/n, hd]; full attention on
    the head subset; all-to-all #2 back.  Requires Hq % n == 0; KV heads
    are exchanged directly when Hkv % n == 0 (then repeated locally — the
    contiguous q-head group g owns exactly kv-head group g) and repeated
    BEFORE the exchange otherwise.
    """
    n = jax.lax.psum(1, axis_name)
    B, Tl, Hq, hd = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv

    q_full = jax.lax.all_to_all(
        q, axis_name, split_axis=2, concat_axis=1, tiled=True
    )  # [B, T, Hq/n, hd]
    if rep > 1 and Hkv % n != 0:
        # GQA narrower than the CP degree: replicate kv heads up to Hq
        # before the exchange so every q-head group gets its kv twin
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        rep = 1
    k_full = jax.lax.all_to_all(
        k, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    v_full = jax.lax.all_to_all(
        v, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    if rep > 1:
        # contiguous head groups: q group g = q heads [g*Hq/n, (g+1)*Hq/n),
        # whose kv twins are exactly kv group g when (Hq/n) % rep == 0
        k_full = jnp.repeat(k_full, rep, axis=2)
        v_full = jnp.repeat(v_full, rep, axis=2)
    seg_full = jax.lax.all_gather(seg, axis_name, axis=1, tiled=True)
    pos_full = jax.lax.all_gather(pos, axis_name, axis=1, tiled=True)

    out = _full_attention(
        q_full, k_full, v_full, seg_full, pos_full, sliding_window
    )  # [B, T, Hq/n, hd] f32
    out = jax.lax.all_to_all(
        out.astype(q.dtype), axis_name, split_axis=1, concat_axis=2,
        tiled=True,
    )  # [B, T/n, Hq, hd]
    return out


def ulysses_attention(
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
    """shard_map wrapper mirroring :func:`ring_attention.ring_attention`."""
    n = mesh.shape.get(axis, 1)
    tp = mesh.shape.get(head_axis, 1) if head_axis else 1
    local_hq = q.shape[2] // max(tp, 1)
    if local_hq % n != 0:
        raise ValueError(
            f"ulysses CP needs per-device q heads ({local_hq}) divisible "
            f"by the seq-parallel degree ({n}); use ring attention instead"
        )
    qkv_spec = P(batch_axes, axis, head_axis, None)
    tok_spec = P(batch_axes, axis)
    fn = partial(
        ulysses_attention_local,
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
