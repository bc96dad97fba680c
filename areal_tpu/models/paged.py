"""Paged-KV forward paths: chunked prefill + chunked decode over a block
pool.

TPU-native replacement for the paged/radix KV machinery the reference gets
from SGLang (reference: realhf/impl/model/backend/sglang.py:369 and the
server patched by patch/sglang/v0.4.6.post2.patch; SURVEY §2.8 names
"splash/paged attention kernels" as the TPU equivalent).  The serving
engine (areal_tpu/engine/inference_server.py) owns the host-side block
allocator; this module owns the device-side compute:

* the KV pool is ``[L, NB, Hkv, BS, hd]`` (PAGE-major: one page is one
  contiguous HBM extent) — NB fixed-size blocks shared by all rows; a
  row's cache is the ordered block list in its table row ``[MB]`` (pool
  block id per logical block);
  ``L`` counts CACHE layers (``cfg.n_attn_layers``): a looped stack's
  every pass writes a layer of its own, ``r * n_layers + l``, and both
  programs run their layers through ``transformer.loop_layers``, which
  is the plain layer scan where nothing loops;
* :func:`paged_fill_chunk` runs ONE chunk of prompt prefill for a batch of
  filling rows: in-chunk causal self-attention merged online with
  paged-kernel partials over each row's already-cached prefix — so a 16k
  prompt admits as 16 × 1k chunks interleaved with decode steps instead of
  one decode-stalling wave (chunked prefill, the round-4 verdict's #1/#2);
* :func:`paged_decode_chunk` mirrors ``transformer.decode_chunk``'s
  window design (in-chunk KV in a small contiguous window, ONE pool
  write per chunk) with the paged kernel streaming each row's valid
  blocks — cost scales with the row's true length, not a padded bucket;
* ONE pool write per chunk holds for the fill too: inside both programs'
  layer loops the pool is a read-only operand of the kernel, in the
  layout it is stored in, and the chunk's KV (every layer's, stacked)
  reaches it afterwards through :func:`write_kv_runs`, which keeps that
  layout.  A scatter in the loop, or a ``(pid, off)`` scatter after it,
  makes XLA convert the whole pool to the scatter's layout and back.

Every function threads the pool through donated jit args; the layered
kernel entry reads blocks straight from the stacked pool so no per-layer
pool slice is ever materialized.

**Quantized KV storage** (``kv_cache_dtype="int8"``): the k/v pools
store int8 with a float32 scale pool ``[L, NB, Hkv, BS]`` alongside —
one absmax scale per (block, head, page slot).  The slot axis is what
makes append-only pages exact: a single per-(block, head) scale would
need a read-modify-write requantization of the whole block every time
decode appends one token to the tail page, while per-slot scales let
every write path quantize just the values it writes.  Writes quantize
at insert (:func:`quantize_kv` before the pool write in
:func:`paged_window_forward` / :func:`paged_decode_chunk`'s chunk-end
merge); reads dequantize inline right after the block gather (the jnp
reference path and both Pallas kernels multiply by scales before the
attention dots), so attention math stays in model dtype and the
accuracy loss is storage-only.  Every function below accepts optional
``k_scale``/``v_scale`` operands (None = unquantized, today's
behavior) and returns them updated whenever it returns the pools.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.engine.sampling import sample_and_advance
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import (
    Params,
    _attn_half,
    _embed,
    _head,
    _mlp_half,
    loop_layers,
    rope_tables,
    window_put,
)
from areal_tpu.observability.tracing import region
from areal_tpu.ops.paged_attention import (
    page_group,
    page_tile,
    paged_flash_attention,
    plan_pages,
    reference_paged_partials,
)

_NEG_INF = -1e30


#: the lane tile: a pool's minor axis is a whole number of these
LANES = 128


def latent_page_width(cfg: TransformerConfig) -> int:
    """Columns of a latent page's row: ``[c_kv | k_rope]``
    (``cfg.kv_latent_dim``, 576 at the published MLA sizes) and zeros up
    to the next lane tile (640).  Stored at its own width the pool is an
    argument whose layout the Mosaic call does not take: the TPU
    compiler then copies the WHOLE pool to a 640-column layout before
    every call (2.1 GB of temporaries for a 1.9 GB pool, by its own
    count for a described v5e, PR 33); the padding is written out so
    that the bytes the ledger counts are the bytes the device holds."""
    return -(-cfg.kv_latent_dim // LANES) * LANES


def pool_shapes(
    cfg: TransformerConfig, n_blocks: int, block_size: int,
    layers: Optional[int] = None,
) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Shapes of the (k, v) block pools over ``layers`` layers (None: the
    layers that keep per-token KV for as long as their row lives, i.e.
    all but a stack's "window" layers, which have a pool of their own:
    ``layers=cfg.n_window_layers``).  Per-head pages: both ``[L, NB,
    Hkv, BS, hd]``.  LATENT pages (``cfg.is_latent``): ONE pool ``[L,
    NB, 1, BS, latent_page_width]`` whose row is a token's ``[c_kv |
    k_rope | 0]``, read as keys and (its first ``kv_lora_rank`` columns)
    as values; the V pool has width 0, so that everything that moves
    pages moves a latent pool as it moves any other and the V side
    holds no byte.  Under an INDEXER (``cfg.is_indexed``) the V side of
    the whole-context pool holds each token's index key, ``[L, NB, 1, BS,
    index_head_dim]``: one page id then names a latent page and its
    index page, and whatever shares, copies, caches, parks or releases
    the one does so to the other.  A window pool of "latent_window"
    layers holds entries of THEIR widths (``cfg.window_latent()``) and
    no index keys."""
    window_pool = layers is not None
    if layers is None:
        layers = cfg.n_attn_layers - cfg.n_window_layers
    head = (layers, n_blocks)
    if cfg.is_latent_window if window_pool else cfg.is_latent:
        of = cfg.window_latent() if window_pool else cfg
        return (
            head + (1, block_size, latent_page_width(of)),
            head + (1, block_size, of.index_head_dim if of.is_indexed else 0),
        )
    shape = head + (cfg.pool_kv_heads, block_size, cfg.pool_head_dim)
    return shape, shape


def pool_zeros(
    cfg: TransformerConfig, n_blocks: int, block_size: int, dtype=None,
    layers: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Allocate the (k, v) block pools (:func:`pool_shapes`) —
    PAGE-major so one page is one contiguous HBM extent (the kernel reads
    a page's every head in a single DMA)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    k_shape, v_shape = pool_shapes(cfg, n_blocks, block_size, layers)
    return jnp.zeros(k_shape, dtype), jnp.zeros(v_shape, dtype)


#: int8 symmetric absmax range (one sign bit + 7 magnitude bits; -128 is
#: never produced so quantize/dequantize round-trips are symmetric)
KV_QUANT_MAX = 127.0


def alloc_kv_pool(
    cfg: TransformerConfig,
    n_blocks: int,
    block_size: int,
    kv_cache_dtype: str = "auto",
    dtype=None,
    layers: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """Allocate the paged KV storage: ``(k_pool, v_pool, k_scale,
    v_scale)``.

    ``kv_cache_dtype="auto"`` keeps today's model-dtype pools (scales are
    None); ``"int8"`` allocates int8 pools plus float32 scale pools
    ``[L, NB, Hkv, BS]`` — one absmax scale per (block, head, page slot),
    so the storage cost per cached token-head drops from ``2 * hd *
    itemsize(model dtype)`` to ``2 * (hd + 4)`` bytes."""
    if kv_cache_dtype == "auto":
        k, v = pool_zeros(cfg, n_blocks, block_size, dtype=dtype, layers=layers)
        return k, v, None, None
    if kv_cache_dtype != "int8":
        raise ValueError(
            f"kv_cache_dtype must be 'auto' or 'int8', got {kv_cache_dtype!r}"
        )
    if cfg.is_latent:
        raise NotImplementedError(
            "int8 KV storage is not supported for latent pages: one "
            "scale a (block, head, slot) would cover a token's whole "
            "[c_kv | k_rope] row, whose two parts differ in scale"
        )
    shape, _ = pool_shapes(cfg, n_blocks, block_size, layers)
    sshape = shape[:-1]
    return (
        jnp.zeros(shape, jnp.int8),
        jnp.zeros(shape, jnp.int8),
        jnp.zeros(sshape, jnp.float32),
        jnp.zeros(sshape, jnp.float32),
    )


def kv_pool_layout_bytes(
    cfg: TransformerConfig,
    n_blocks: int,
    block_size: int,
    kv_cache_dtype: str = "auto",
    dtype=None,
    layers: Optional[int] = None,
) -> Tuple[int, int]:
    """``(pool_bytes, scale_bytes)`` that :func:`alloc_kv_pool` with the
    same arguments will allocate — pure arithmetic, no device memory.
    The HBM ledger sizes its ``kv_pool``/``kv_scales`` attributions from
    this (the allocation itself runs under jit, where a host-side ledger
    call cannot live); ``scale_bytes`` is 0 for fp pools."""
    k_shape, v_shape = pool_shapes(cfg, n_blocks, block_size, layers)
    itemsize = jnp.dtype(dtype or cfg.dtype).itemsize
    if cfg.is_latent:
        return (int(np.prod(k_shape)) + int(np.prod(v_shape))) * itemsize, 0
    n = int(np.prod(k_shape))
    if kv_cache_dtype == "int8":
        # k + v int8 data, k + v float32 scale pools [L, NB, Hkv, BS]
        return 2 * n, 2 * (n // cfg.pool_head_dim) * 4
    return 2 * n * itemsize, 0


def quantize_kv(vals: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric absmax int8 quantization over the trailing head_dim
    axis: returns ``(int8 values, float32 scales)`` with scales shaped
    like ``vals`` minus its last axis.  All-zero vectors quantize to
    zeros with scale 0 (the dequant multiply reproduces them exactly)."""
    v32 = vals.astype(jnp.float32)
    scale = jnp.max(jnp.abs(v32), axis=-1) / KV_QUANT_MAX
    q = v32 / jnp.maximum(scale, 1e-30)[..., None]
    q = jnp.clip(
        jnp.round(q), -KV_QUANT_MAX, KV_QUANT_MAX
    ).astype(jnp.int8)
    return q, scale


def kernel_interpret() -> bool:
    """Whether the Pallas paged kernels run in interpret mode: compiled
    on a TPU backend, interpreted (tests only) anywhere else."""
    return jax.default_backend() != "tpu"


def _shard_pool_shape(k_pool, mesh=None, kv_axis=None):
    """(shards, pool shape ``(Hkv, BS, hd)``) as ONE call of the paged
    kernel sees them: one shard's, where the kv heads split over
    ``kv_axis``."""
    shards = (
        mesh.shape[kv_axis] if mesh is not None and kv_axis is not None
        else 1
    )
    Hkv, BS, hd = k_pool.shape[-3:]
    return shards, (Hkv // shards, BS, hd)


def _prefix_plan(
    n_queries, n_q_heads, k_pool, tables, lengths, use_kernel,
    mesh=None, kv_axis=None, quantized=False, window=None, masked=False,
):
    """The paged kernel's page plan for every :func:`_prefix_partials`
    call over these ``tables`` and ``lengths`` (under this ``window``;
    ``masked``: calls that bring a selection, ``mask``):
    made ONCE, before the layer scan (and the decode chunk's step loop),
    because XLA leaves it inside them otherwise.  A decode call's plan
    (one query a row) says which rows hold pages: the kernel's grid holds
    those only.  None without the kernel."""
    if not use_kernel:
        return None
    shards, shard_shape = _shard_pool_shape(k_pool, mesh, kv_axis)
    group = page_group(
        n_queries, n_q_heads // shards, shard_shape, k_pool.dtype,
        quantized, tables.shape[1], masked,
    )
    return plan_pages(
        tables, lengths, shard_shape[1], group, window, decode=n_queries == 1
    )


def kernel_tile_tokens(k_pool, mesh=None, kv_axis=None) -> int:
    """Tokens of the unit the paged kernel copies a page of ``k_pool``
    in: a row of ``n`` cached positions costs it ``ceil(n / tile)`` tiles
    (for the engine's counts)."""
    return page_tile(_shard_pool_shape(k_pool, mesh, kv_axis)[1], k_pool.dtype)


def _prefix_partials(
    q, k_pool, v_pool, tables, lengths, layer, use_kernel,
    mesh=None, kv_axis=None, k_scale=None, v_scale=None, plan=None,
    scale=None, value_dim=None, window=None, window_shift=None, mask=None,
):
    """Paged-attention partials over each row's cached prefix.  ``q`` is
    [B, Q, Hq, hd]; returns (acc, m, l) with Q query tokens per row.
    ``plan`` is :func:`_prefix_plan` of the same arguments; ``scale`` the
    model's softmax scale where it is not ``1/sqrt(hd)`` (None).
    ``value_dim``: LATENT pages, whose first ``value_dim`` columns are
    the values (``v_pool`` is then not read; ``acc`` is that wide).
    ``window``: query ``t`` of a row attends the cached positions ``j``
    with ``length + window_shift + t - j < window`` only (``window_shift``:
    a decode chunk's step, over the plan made at its start).
    ``mask`` [B, Q, MB * BS]: a selection, query ``t`` attends cached
    position ``s`` of its row only where ``mask[b, t, s]`` (an indexed
    latent layer's fill: the kernel takes it as one more operand).

    ``k_scale``/``v_scale`` mark an int8-quantized pool: both the kernel
    and the jnp reference dequantize (multiply by the per-(block, head,
    slot) scales) right after the block gather, so attention math is
    identical to the unquantized path up to storage rounding.

    On a TP serving mesh the Pallas kernel has no SPMD partitioning rule,
    so it runs under an explicit ``shard_map``: the pool's kv-head axis
    and q's head axis split over ``kv_axis`` (or fully replicated when
    the head count doesn't divide), each shard streaming only its own
    heads' pages (code-review r5 #2); tables, lengths and the plan are
    replicated."""
    if use_kernel:
        interp = kernel_interpret()
        if mesh is None:
            return paged_flash_attention(
                q, k_pool, None if value_dim else v_pool, tables, lengths,
                layer=layer, interpret=interp, k_scale=k_scale,
                v_scale=v_scale, plan=plan, scale=scale,
                value_dim=value_dim, window=window,
                window_shift=window_shift, mask=mask,
            )
        assert value_dim is None, "latent pages under a serving mesh"
        assert window is None, "a windowed call under a serving mesh"
        from jax.sharding import PartitionSpec as P

        layered = k_pool.ndim == 5
        pool_spec = (
            P(None, None, kv_axis, None, None)
            if layered
            else P(None, kv_axis, None, None)
        )
        scale_spec = (
            P(None, None, kv_axis, None)
            if layered
            else P(None, kv_axis, None)
        )
        scales = () if k_scale is None else (k_scale, v_scale)

        def kern(qq, kk, vv, tb, ln, ly, pp, *sc):
            ks, vs = sc if sc else (None, None)
            return paged_flash_attention(
                qq, kk, vv, tb, ln, layer=ly, interpret=interp,
                k_scale=ks, v_scale=vs, plan=pp, scale=scale,
            )

        fn = jax.shard_map(
            kern,
            mesh=mesh,
            in_specs=(
                P(None, None, kv_axis, None),
                pool_spec,
                pool_spec,
                P(None, None),
                P(None),
                P(None),
                P(),  # the plan's arrays (or None): replicated
            )
            + (scale_spec,) * len(scales),
            out_specs=(
                P(None, None, kv_axis, None),
                P(None, None, kv_axis),
                P(None, None, kv_axis),
            ),
            check_vma=False,
        )
        return fn(
            q, k_pool, v_pool, tables, lengths,
            jnp.asarray(layer, jnp.int32).reshape(1), plan, *scales,
        )
    kl = jax.lax.dynamic_index_in_dim(k_pool, layer, 0, keepdims=False)
    if value_dim:
        return reference_paged_partials(
            q, kl, None, tables, lengths, scale=scale, value_dim=value_dim,
            window=window,
            window_shift=0 if window_shift is None else window_shift,
            mask=mask,
        )
    vl = jax.lax.dynamic_index_in_dim(v_pool, layer, 0, keepdims=False)
    ksl = vsl = None
    if k_scale is not None:
        ksl = jax.lax.dynamic_index_in_dim(k_scale, layer, 0, keepdims=False)
        vsl = jax.lax.dynamic_index_in_dim(v_scale, layer, 0, keepdims=False)
    return reference_paged_partials(
        q, kl, vl, tables, lengths, k_scale=ksl, v_scale=vsl, scale=scale,
        window=window, window_shift=0 if window_shift is None else window_shift,
    )


@region("areal.kv_write")
def write_kv_runs(
    pools: Sequence[jax.Array],  # each [L, NB, Hkv, BS, ...]
    values: Sequence[jax.Array],  # one per pool, [L, R, T, Hkv, ...]
    tables: jax.Array,  # [R, MB] pool block ids
    starts: jax.Array,  # [R] cache position of each row's first value
    counts: jax.Array,  # [R] values to write per row (the first ones)
) -> Tuple[jax.Array, ...]:
    """Write row r's ``values[:, r, :counts[r]]`` to cache positions
    ``starts[r] ...`` of its table row, in the pool's OWN layout — the
    one pool write of a prefill chunk (:func:`paged_window_forward`) and
    of a decode chunk (:func:`paged_decode_chunk`).

    A row's run is consecutive slots of at most ``ceil(T / BS) + 1``
    pages, so it goes in as one piece per page touched: ``min(T, BS)``
    slots read from the page, merged with the run's part of them, and
    put back by ``dynamic_update_slice``, which keeps the operand's
    layout.  Pieces that hold nothing (short runs, rows with ``counts``
    0, pages past the table) are never visited: the loop's trip count is
    the number of live pieces.  Slots outside a run keep their bits.

    Why not a scatter: ``pool.at[:, pid, :, off].set(...)`` makes XLA
    convert the whole pool to the scatter's preferred layout and back
    (two pool-sized copies a pool, 70 ms for both pools at the
    benchmark's shape), and a scatter of single ``hd`` rows, which keeps
    the layout, spends as long on a fill's 459k row updates; the pieces
    take 1.1-1.7 ms for a fill of 8 x 1,024 tokens and 1.2-3.0 ms for a
    decode chunk of 64 rows (my chip runs, PR 28: PERF.md section 6)."""
    L, _, Hkv, BS = pools[0].shape[:4]
    T = values[0].shape[2]
    MB = tables.shape[1]
    S = min(T, BS)  # slots in a piece
    P = -(-T // BS) + 1  # pages a run can touch
    lp = (starts // BS)[:, None] + jnp.arange(P, dtype=jnp.int32)  # [R, P]
    lo = jnp.maximum(starts[:, None], lp * BS)
    hi = jnp.minimum((starts + counts)[:, None], (lp + 1) * BS)
    live = ((hi > lo) & (lp < MB)).reshape(-1)
    # the piece's first slot in its page: the run's, pulled back so that
    # S slots fit; and the run index that slot holds (negative: before it)
    s0 = jnp.clip(lo - lp * BS, 0, BS - S)
    c0 = (lp * BS + s0 - starts[:, None]).reshape(-1)
    s0 = s0.reshape(-1)
    pid = jnp.take_along_axis(
        tables, jnp.clip(lp, 0, MB - 1), axis=1
    ).reshape(-1)
    order = jnp.argsort(~live, stable=True)  # live pieces first
    iot = jnp.arange(S, dtype=jnp.int32)

    def put(i, pools):
        j = order[i]
        r = j // P
        c = c0[j] + iot
        keep = (c >= 0) & (c < counts[r])  # [S]
        out = []
        for pool, val in zip(pools, values):
            tail = pool.shape[4:]
            z = (0,) * len(tail)
            at = (0, pid[j], 0, s0[j]) + z
            old = jax.lax.dynamic_slice(pool, at, (L, 1, Hkv, S) + tail)
            row = jax.lax.dynamic_index_in_dim(val, r, 1)  # [L,1,T,Hkv,..]
            # the run shifted to the piece's frame: entry i is run index
            # c0 + i wherever ``keep`` holds (a roll; the wrap is masked)
            new = jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([row, row], axis=2), c0[j] % T, S, axis=2
            ).swapaxes(2, 3)
            m = keep.reshape((1, 1, 1, S) + (1,) * len(tail))
            out.append(
                jax.lax.dynamic_update_slice(
                    pool, jnp.where(m, new, old), at
                )
            )
        return tuple(out)

    return jax.lax.fori_loop(
        0, jnp.sum(live, dtype=jnp.int32), put, tuple(pools)
    )


def chunk_attention(q, k, v, prefix, mask_chunk, scale, dtype):
    """Attention of a chunk's queries ``q`` [F, C, Hq, hd] over the chunk
    itself (``k`` [F, C, Hkv, hd], ``v`` [F, C, Hkv, vd], causal by
    ``mask_chunk`` [F, Cq, Ckv]) merged online with the paged partials
    ``prefix`` = ``(acc [F, C, Hq, vd], m, l)`` over each row's cached
    prefix.  Returns [F, C, Hq * vd] in ``dtype``."""
    F, C, Hq, hd = q.shape
    Hkv = k.shape[2]
    r = Hq // Hkv
    acc_p, m_p, l_p = prefix
    # in-chunk causal scores (C <= ~1k keeps [F,Hq,C,C] small)
    qg = q.reshape(F, C, Hkv, r, hd)
    s_c = (
        jnp.einsum(
            "fikrd,fjkd->fkrij",
            qg.astype(jnp.float32),
            k.astype(jnp.float32),
        )
        * scale
    )  # [F, Hkv, r, Cq, Ckv]
    s_c = jnp.where(mask_chunk[:, None, None, :, :], s_c, _NEG_INF)
    accp = acc_p.reshape(F, C, Hkv, r, -1).transpose(0, 2, 3, 1, 4)
    mp = m_p.reshape(F, C, Hkv, r).transpose(0, 2, 3, 1)
    lpp = l_p.reshape(F, C, Hkv, r).transpose(0, 2, 3, 1)
    # online merge of prefix partials with the in-chunk scores
    m_tot = jnp.maximum(mp, jnp.max(s_c, axis=-1))
    p_c = jnp.exp(s_c - m_tot[..., None])
    alpha = jnp.exp(mp - m_tot)
    num = accp * alpha[..., None] + jnp.einsum(
        "fkrij,fjkd->fkrid", p_c, v.astype(jnp.float32)
    )
    den = lpp * alpha + jnp.sum(p_c, axis=-1)
    attn = (num / jnp.maximum(den, 1e-30)[..., None]).astype(dtype)
    return attn.transpose(0, 3, 1, 2, 4).reshape(F, C, -1)


def window_attention(q, wk_l, wv_l, prefix, mask_win, scale, dtype):
    """Attention of one decode step's queries ``q`` [B, 1, Hq, hd] over
    the chunk's window so far (``wk_l`` [W, B, Hkv, hd], ``wv_l`` [W, B,
    Hkv, vd], valid by ``mask_win`` [B, 1, 1, 1, W]) merged online with
    the paged partials ``prefix`` over each row's cached prefix.  Returns
    [B, 1, Hq * vd] in ``dtype``."""
    B, _, Hq, hd = q.shape
    Hkv = wk_l.shape[2]
    r = Hq // Hkv
    qg = q.reshape(B, 1, Hkv, r, hd)
    s_win = (
        jnp.einsum(
            "btkrd,wbkd->bkrtw", qg, wk_l.astype(qg.dtype),
            preferred_element_type=jnp.float32,
        )
        * scale
    )
    s_win = jnp.where(mask_win, s_win, _NEG_INF)  # [B,Hkv,r,1,W]
    acc, m_main, l_main = prefix
    acc = acc.reshape(B, Hkv, r, -1)
    m_main = m_main.reshape(B, Hkv, r)
    l_main = l_main.reshape(B, Hkv, r)
    sw = s_win[:, :, :, 0, :]  # [B,Hkv,r,W]
    m_tot = jnp.maximum(m_main, jnp.max(sw, axis=-1))
    p_win = jnp.exp(sw - m_tot[..., None])
    alpha = jnp.exp(m_main - m_tot)
    num = acc * alpha[..., None] + jnp.einsum(
        "bkrw,wbkd->bkrd", p_win, wv_l.astype(jnp.float32)
    )
    den = l_main * alpha + jnp.sum(p_win, axis=-1)
    attn = (num / jnp.maximum(den, 1e-30)[..., None]).astype(dtype)
    return attn.reshape(B, 1, -1)


def paged_window_forward(
    params: Params,
    k_pool: jax.Array,  # [L, NB, Hkv, BS, hd]
    v_pool: jax.Array,
    cfg: TransformerConfig,
    tokens: jax.Array,  # [F, C] window tokens (right-padded)
    starts: jax.Array,  # [F] tokens already cached per row (window offset)
    valid: jax.Array,  # [F, C] bool: positions to compute + write
    tables: jax.Array,  # [F, MB] pool block ids
    use_kernel: bool,
    mesh=None,
    kv_axis=None,
    k_scale: Optional[jax.Array] = None,  # [L, NB, Hkv, BS] (int8 pool)
    v_scale: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[jax.Array],
           Optional[jax.Array]]:
    """Forward a short token WINDOW for F rows over their cached paged
    prefixes: in-window causal self-attention merged online with the
    paged kernel's partials over ``[0, start)``, window KV written into
    the rows' pool blocks (invalid positions dropped).  The core of
    chunked prefill (:func:`paged_fill_chunk`).  Returns ``(x [F, C, D],
    k_pool, v_pool, k_scale, v_scale)`` with ``x`` the final hidden
    states (pre-head); the scales pass through as None on unquantized
    pools.

    ``valid`` is a PREFIX mask: row f's first ``valid[f].sum()`` window
    positions, which is what the caller builds.

    The pools are read-only operands of the layer scan, in the layout
    the kernel reads them in: no layer reads what this window writes
    (the kernel attends ``[0, start)``, the window attends itself from
    registers), so every layer's window KV leaves the scan as its output
    and reaches the pool in ONE :func:`write_kv_runs` after it (why:
    the module docstring).

    On an int8 pool the window KV is computed in model dtype and
    quantized per (token, head) inside the scan; values and scales go
    through the same write.

    Callers jit this (it is not jitted itself); the pools thread through
    donated args of the enclosing jit."""
    C = tokens.shape[1]
    L, _, _, _, hd = k_pool.shape
    positions = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    # masked rows must stream zero prefix blocks (their ``starts`` may be
    # any live length)
    read_lens = jnp.where(valid[:, 0], starts, 0)
    x = _embed(params, cfg, tokens, positions)
    rope_cs = (
        None
        if cfg.abs_position_embedding
        else rope_tables(positions, cfg.rotary_base, cfg.head_dim)
    )
    iot = jnp.arange(C)
    mask_chunk = (
        valid[:, None, :]
        & valid[:, :, None]
        & (iot[:, None] >= iot[None, :])
    )  # [F, Cq, Ckv] causal
    seg_ids = valid.astype(jnp.int32)
    scale = 1.0 / np.sqrt(hd)
    plan = _prefix_plan(
        C, cfg.n_q_heads, k_pool, tables, read_lens, use_kernel,
        mesh=mesh, kv_axis=kv_axis, quantized=k_scale is not None,
    )

    def body(x, xs):
        lp, l = xs

        def attend(q, k, v):
            prefix = _prefix_partials(
                q, k_pool, v_pool, tables, read_lens, l, use_kernel,
                mesh=mesh, kv_axis=kv_axis, k_scale=k_scale,
                v_scale=v_scale, plan=plan,
            )
            attn = chunk_attention(
                q, k, v, prefix, mask_chunk, scale, x.dtype
            )
            # this layer's window KV, as the pool stores it: [F, C, Hkv,
            # hd] (and [F, C, Hkv] scales)
            with region("areal.kv_write"):
                if k_scale is not None:
                    kq, ks = quantize_kv(k)
                    vq, vs = quantize_kv(v)
                    return attn, (kq, vq, ks, vs)
                return attn, (k.astype(k_pool.dtype), v.astype(v_pool.dtype))

        x, kept = _attn_half(cfg, lp, x, positions, rope_cs, attend)
        x, _ = _mlp_half(cfg, lp, x, seg_ids=seg_ids, mesh=mesh)
        return x, kept

    x, window_kv = loop_layers(
        params, cfg, body, x, (params["layers"], jnp.arange(L))
    )
    pools = (k_pool, v_pool)
    if k_scale is not None:
        pools += (k_scale, v_scale)
    pools = write_kv_runs(
        pools, window_kv, tables, starts,
        jnp.sum(valid, axis=1, dtype=jnp.int32),
    )
    return (x, *pools) if k_scale is not None else (x, *pools, None, None)


@region("areal.head")
def last_valid(x, chunk_lens):
    """``x`` [F, C, D] at each row's last valid position: [F, 1, D]."""
    last_idx = jnp.maximum(chunk_lens - 1, 0)
    return jnp.take_along_axis(x, last_idx[:, None, None], axis=1)


@partial(
    jax.jit,
    static_argnames=("cfg", "use_kernel", "mesh", "kv_axis"),
    donate_argnums=(1, 2),
    donate_argnames=("k_scale", "v_scale"),
)
def paged_fill_chunk(
    params: Params,
    k_pool: jax.Array,  # [L, NB, Hkv, BS, hd]
    v_pool: jax.Array,
    cfg: TransformerConfig,
    tokens: jax.Array,  # [F, C] this chunk's tokens (right-padded)
    starts: jax.Array,  # [F] tokens already cached per row (chunk offset)
    chunk_lens: jax.Array,  # [F] valid tokens in this chunk
    tables: jax.Array,  # [F, MB] pool block ids
    use_kernel: bool,
    mesh=None,
    kv_axis=None,
    k_scale: Optional[jax.Array] = None,  # [L, NB, Hkv, BS] (int8 pool)
    v_scale: Optional[jax.Array] = None,
):
    """One prefill chunk for F filling rows.

    Each row's chunk tokens attend causally within the chunk AND over the
    row's already-cached prefix ``[0, start)`` via paged partials — an
    exact continuation of the row's prefill no matter how the prompt was
    split into chunks.  Chunk KV is written into the rows' pool blocks
    (the engine pre-allocated blocks covering ``start + chunk_len``);
    int8 pools quantize at the write and land scales alongside.

    Returns ``(last_logits [F, V], k_pool, v_pool)`` — plus ``(k_scale,
    v_scale)`` when the pool is quantized — logits at each row's LAST
    valid chunk position (only meaningful on a row's final chunk, where
    the engine samples the first generated token).
    """
    C = tokens.shape[1]
    valid = jnp.arange(C)[None, :] < chunk_lens[:, None]  # [F, C]
    x, k_pool, v_pool, k_scale, v_scale = paged_window_forward(
        params, k_pool, v_pool, cfg, tokens, starts, valid, tables,
        use_kernel=use_kernel, mesh=mesh, kv_axis=kv_axis,
        k_scale=k_scale, v_scale=v_scale,
    )
    logits = _head(params, cfg, last_valid(x, chunk_lens))[:, 0]  # [F, V]
    if k_scale is None:
        return logits, k_pool, v_pool
    return logits, k_pool, v_pool, k_scale, v_scale


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "chunk_size", "use_kernel", "max_len", "sample_fn",
        "stop_fn", "mesh", "kv_axis",
    ),
    donate_argnums=(1, 2),
    donate_argnames=("k_scale", "v_scale"),
)
def paged_decode_chunk(
    params: Params,
    k_pool: jax.Array,  # [L, NB, Hkv, BS, hd]
    v_pool: jax.Array,
    cfg: TransformerConfig,
    tables: jax.Array,  # [B, MB]
    lengths: jax.Array,  # [B] valid cache prefix per row
    cur_tokens: jax.Array,  # [B] pending token per row (KV not yet cached)
    active: jax.Array,  # [B] bool
    budgets: jax.Array,  # [B] remaining new tokens (incl. pending cur)
    rng: jax.Array,
    chunk_size: int,
    sample_fn,  # (logits_f32 [B,V], rng[, positions[, row_seeds]])
    stop_fn,  # (tokens [B]) -> [B] bool
    use_kernel: bool,
    max_len: int,
    mesh=None,
    kv_axis=None,
    row_seeds: Optional[jax.Array] = None,  # [B] per-request sampler keys
    k_scale: Optional[jax.Array] = None,  # [L, NB, Hkv, BS] (int8 pool)
    v_scale: Optional[jax.Array] = None,
):
    """Generate up to ``chunk_size`` tokens for all active rows device-side
    over the paged pool (the paged twin of ``transformer.decode_chunk``).

    In-chunk KV goes to a [L, W, B, Hkv, hd] window written at scalar
    offsets — always in MODEL dtype, even over an int8 pool, so in-chunk
    attention pays zero quantization error; prefix attention streams each
    row's valid blocks through the paged kernel (inactive rows read ZERO
    blocks — their read length is masked, unlike the dense path whose
    cost scaled with the padded bucket); the window merges into pool
    blocks ONCE per chunk through the block tables (int8 pools quantize
    at that merge, scales landing through the same coordinates).  The
    engine guarantees every active row's table covers ``length +
    chunk_size`` slots before dispatch.

    Returns (k_pool, v_pool, lengths, out_t [B,W], out_l [B,W],
    emitted [B,W], cur_tokens, active, budgets, rng) — with
    ``(k_scale, v_scale)`` appended when the pool is quantized.
    """
    assert cfg.sliding_window is None, (
        "paged decode serves global-attention models; sliding-window "
        "models use the dense window-gather path"
    )
    B = cur_tokens.shape[0]
    W = chunk_size
    L, _, Hkv, _, hd = k_pool.shape
    base_lens = lengths  # frozen: pool-resident prefix per row
    # dead rows stream nothing (parked/freed rows keep their lengths)
    read_lens = jnp.where(active, base_lens, 0)
    scale = 1.0 / np.sqrt(hd)
    plan = _prefix_plan(
        1, cfg.n_q_heads, k_pool, tables, read_lens, use_kernel,
        mesh=mesh, kv_axis=kv_axis, quantized=k_scale is not None,
    )

    win_dtype = (
        jnp.dtype(cfg.dtype) if k_scale is not None else k_pool.dtype
    )
    wk = jnp.zeros((L, W, B, Hkv, hd), win_dtype)
    wv = jnp.zeros((L, W, B, Hkv, hd), win_dtype)
    wvalid0 = jnp.zeros((W, B), bool)

    def step(i, st):
        (lengths_, cur, active, budgets, wk, wv, wvalid, out_t, out_l,
         emitted, rng) = st
        positions = lengths_[:, None]
        x = _embed(params, cfg, cur[:, None], positions)
        rope_cs = (
            None
            if cfg.abs_position_embedding
            else rope_tables(positions, cfg.rotary_base, cfg.head_dim)
        )
        wvalid = wvalid.at[i].set(active)
        mask_win = wvalid.T[:, None, None, None, :]  # [B,1,1,1,W]

        def body(carry, xs):
            x, wk, wv = carry
            lp, l = xs

            def attend(q, k, v):
                win = window_put(wk, k, l, i), window_put(wv, v, l, i)
                wk_l, wv_l = (
                    jax.lax.dynamic_index_in_dim(w, l, 0, keepdims=False)
                    for w in win
                )
                prefix = _prefix_partials(
                    q, k_pool, v_pool, tables, read_lens, l, use_kernel,
                    mesh=mesh, kv_axis=kv_axis,
                    k_scale=k_scale, v_scale=v_scale, plan=plan,
                )
                attn = window_attention(
                    q, wk_l, wv_l, prefix, mask_win, scale, x.dtype
                )
                return attn, win

            x, (wk, wv) = _attn_half(cfg, lp, x, positions, rope_cs, attend)
            x, _ = _mlp_half(cfg, lp, x, mesh=mesh)
            return (x, wk, wv), None

        (x, wk, wv), _ = loop_layers(
            params, cfg, body, (x, wk, wv),
            (params["layers"], jnp.arange(L)),
        )
        logits = _head(params, cfg, x)[:, 0]
        (new_lengths, tok, active, budgets, out_t, out_l, emitted,
         rng) = sample_and_advance(
            sample_fn, stop_fn, logits, rng, i, lengths_, active, budgets,
            out_t, out_l, emitted, max_len, row_seeds,
        )
        return (new_lengths, tok, active, budgets, wk, wv, wvalid, out_t,
                out_l, emitted, rng)

    out_t = jnp.zeros((B, W), jnp.int32)
    out_l = jnp.zeros((B, W), jnp.float32)
    emitted = jnp.zeros((B, W), bool)
    st = (base_lens, cur_tokens, active, budgets, wk, wv, wvalid0, out_t,
          out_l, emitted, rng)
    (lengths_, cur, active, budgets, wk, wv, _, out_t, out_l, emitted,
     rng) = jax.lax.fori_loop(0, W, step, st)

    # merge the window into pool blocks: ONE write per chunk.  A row is
    # live for its first ``lengths_ - base_lens`` steps (``active`` only
    # ever falls), so its window entries are one run from ``base_lens``
    pools = (k_pool, v_pool)
    vals = (wk.swapaxes(1, 2), wv.swapaxes(1, 2))  # [L, B, W, Hkv, hd]
    if k_scale is not None:
        with region("areal.kv_write"):
            (kq, ks), (vq, vs) = quantize_kv(vals[0]), quantize_kv(vals[1])
        pools, vals = pools + (k_scale, v_scale), (kq, vq, ks, vs)
    k_pool, v_pool, *scales = write_kv_runs(
        pools, vals, tables, base_lens, lengths_ - base_lens
    )
    return (k_pool, v_pool, lengths_, out_t, out_l, emitted, cur, active,
            budgets, rng, *scales)


@jax.jit
def gather_blocks(
    k_pool: jax.Array,
    v_pool: jax.Array,
    src: jax.Array,  # [n] pool block ids to gather (pad with any valid id)
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
):
    """Gather whole blocks out of the pool as ``[n, L, Hkv, BS, hd]``
    pairs — the device half of a host-tier SPILL (the engine
    ``device_get``s the result into host buffers, one batched fetch per
    reclamation round).  Quantized pools also gather the blocks' scale
    slices ``[n, L, Hkv, BS]`` (appended to the returned tuple), so a
    spilled prefix costs its true int8+scale bytes in host RAM — half
    or less of the model-dtype footprint.  NOT donated: the pool stays
    live."""
    src = jnp.clip(src, 0, k_pool.shape[1] - 1)
    out = (
        jnp.take(k_pool, src, axis=1).swapaxes(0, 1),
        jnp.take(v_pool, src, axis=1).swapaxes(0, 1),
    )
    if k_scale is None:
        return out
    return out + (
        jnp.take(k_scale, src, axis=1).swapaxes(0, 1),
        jnp.take(v_scale, src, axis=1).swapaxes(0, 1),
    )


@partial(
    jax.jit, donate_argnums=(0, 1), donate_argnames=("k_scale", "v_scale")
)
def restore_blocks(
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_host: jax.Array,  # [n, L, Hkv, BS, hd] spilled payloads (host-built)
    v_host: jax.Array,
    dst: jax.Array,  # [n] destination pool block ids (NB entries drop)
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    k_scale_host: Optional[jax.Array] = None,  # [n, L, Hkv, BS]
    v_scale_host: Optional[jax.Array] = None,
):
    """Scatter host-spilled block KV back into freshly allocated pool
    blocks — the device half of a host-tier swap-in.  Quantized pools
    restore the spilled int8 bytes AND their scales bit-identically (no
    requantization round trip).  Dispatched async like every pool op:
    the host->device transfer and scatter ride under the decode chunks
    queued behind it in the in-flight ring, and any later op consuming
    the (donated) pool is sequenced after it by data dependence."""
    k_pool = k_pool.at[:, dst].set(
        k_host.swapaxes(0, 1).astype(k_pool.dtype), mode="drop"
    )
    v_pool = v_pool.at[:, dst].set(
        v_host.swapaxes(0, 1).astype(v_pool.dtype), mode="drop"
    )
    if k_scale is None:
        return k_pool, v_pool
    k_scale = k_scale.at[:, dst].set(
        k_scale_host.swapaxes(0, 1).astype(k_scale.dtype), mode="drop"
    )
    v_scale = v_scale.at[:, dst].set(
        v_scale_host.swapaxes(0, 1).astype(v_scale.dtype), mode="drop"
    )
    return k_pool, v_pool, k_scale, v_scale


def gather_blocks_host(
    k_pool: jax.Array,
    v_pool: jax.Array,
    blocks: Sequence[int],
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> Tuple[np.ndarray, ...]:
    """Batched device->host copy of whole pool blocks: one jitted
    :func:`gather_blocks` + one blocking ``device_get``, power-of-two
    padded so repeated calls reuse a handful of compiled shapes.
    Returns host numpy components indexed ``[i] -> blocks[i]`` —
    ``(k, v)`` for model-dtype pools, ``(k, v, k_scale, v_scale)`` for
    int8 pools (the quantized bytes and their scales travel together,
    so a round trip through :func:`restore_blocks_from_host` is
    bit-identical, no requantization).

    The ONE host-copy implementation for every whole-block exporter:
    the prefix cache's host spill tier and the P/D-disaggregation
    handoff unit both ride it."""
    n = len(blocks)
    n_pad = 1 << (n - 1).bit_length()
    idx = np.zeros((n_pad,), np.int32)
    idx[:n] = blocks
    out = gather_blocks(
        k_pool, v_pool, jnp.asarray(idx), k_scale=k_scale, v_scale=v_scale
    )
    out = jax.device_get(out)
    return tuple(np.asarray(a)[:n] for a in out)


def restore_blocks_from_host(
    k_pool: jax.Array,
    v_pool: jax.Array,
    payloads: Sequence[Tuple[np.ndarray, ...]],
    dst: Sequence[int],
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
):
    """Batched host->device scatter of per-block payload tuples (each as
    produced by :func:`gather_blocks_host`, one tuple per destination
    block): stacks the components into one padded transfer buffer and
    dispatches ONE async :func:`restore_blocks` — the copy rides under
    whatever decode chunks are queued behind it, and any later op
    consuming the (donated) pools is sequenced after it by data
    dependence.  Returns the updated pools: ``(k_pool, v_pool)`` or
    ``(k_pool, v_pool, k_scale, v_scale)`` matching the pool format.

    Component shapes/dtypes come from the payloads themselves, so int8
    + scale spills restore bit-identically on quantized pools."""
    n = len(payloads)
    assert n == len(dst) and n > 0
    n_pad = 1 << (n - 1).bit_length()
    # fill the padded transfer buffers directly (one pass per component)
    stacked = []
    for c, proto in enumerate(payloads[0]):
        buf = np.zeros((n_pad,) + proto.shape, proto.dtype)
        for i, payload in enumerate(payloads):
            buf[i] = payload[c]
        stacked.append(jnp.asarray(buf))
    return _restore_padded(
        k_pool, v_pool, stacked, n, dst,
        k_scale=k_scale, v_scale=v_scale,
    )


def stack_host_payloads(
    payloads: Sequence[Tuple[np.ndarray, ...]],
) -> Tuple[np.ndarray, ...]:
    """Stack per-block payload tuples (each :func:`gather_blocks_host`
    output indexed ``[i]``, e.g. host-spill entries) into the ONE
    contiguous buffer per component that
    :func:`restore_blocks_host_stacked` scatters — the segmented-handoff
    wire format.  Lets an exporter mix batch-gathered device blocks and
    already-host spill payloads into one segment."""
    assert payloads
    return tuple(
        np.stack([np.asarray(p[c]) for p in payloads], axis=0)
        for c in range(len(payloads[0]))
    )


def restore_blocks_host_stacked(
    k_pool: jax.Array,
    v_pool: jax.Array,
    components: Sequence[np.ndarray],
    dst: Sequence[int],
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
):
    """Like :func:`restore_blocks_from_host`, but the payload arrives as
    ONE contiguous buffer per pool component — ``(k [n, L, Hkv, BS, hd],
    v, [k_scale [n, L, Hkv, BS], v_scale])`` indexed ``[i] -> dst[i]``,
    exactly :func:`gather_blocks_host`'s output shape.  This is the
    segmented KV-handoff wire format: a streamed segment ships its
    blocks coalesced and scatters them without a per-block
    split/re-stack round trip.  Pads to a power of two and dispatches
    ONE async :func:`restore_blocks`; returns the updated pools."""
    n = len(dst)
    assert n > 0
    n_pad = 1 << (n - 1).bit_length()
    stacked = []
    for c in components:
        c = np.asarray(c)
        assert c.shape[0] == n, (c.shape, n)
        if n_pad == n:
            buf = c
        else:
            buf = np.zeros((n_pad,) + c.shape[1:], c.dtype)
            buf[:n] = c
        stacked.append(jnp.asarray(buf))
    return _restore_padded(
        k_pool, v_pool, stacked, n, dst,
        k_scale=k_scale, v_scale=v_scale,
    )


def _restore_padded(
    k_pool, v_pool, stacked, n, dst, k_scale=None, v_scale=None
):
    """Shared dispatch tail of the two host-restore entry points:
    ``stacked`` components are already power-of-two padded device-ready
    buffers covering ``dst[:n]``."""
    n_pad = stacked[0].shape[0]
    # pad destinations point one past the pool: mode="drop" discards them
    dst_arr = np.full((n_pad,), k_pool.shape[1], np.int32)
    dst_arr[:n] = dst
    if k_scale is not None:
        kh, vh, ksh, vsh = stacked
        return restore_blocks(
            k_pool, v_pool, kh, vh, jnp.asarray(dst_arr),
            k_scale=k_scale, v_scale=v_scale,
            k_scale_host=ksh, v_scale_host=vsh,
        )
    kh, vh = stacked
    return restore_blocks(k_pool, v_pool, kh, vh, jnp.asarray(dst_arr))


@partial(
    jax.jit, donate_argnums=(0, 1), donate_argnames=("k_scale", "v_scale")
)
@region("areal.kv_write")
def copy_blocks(
    k_pool: jax.Array,
    v_pool: jax.Array,
    src: jax.Array,  # [n] pool block ids to copy from
    dst: jax.Array,  # [n] pool block ids to copy into (NB entries drop)
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
):
    """Copy whole blocks inside the pool (group-prompt tail blocks: the
    full blocks of a shared prompt are REFERENCED by every group member,
    but the partially-filled last block must be copied per member since
    their generated tokens diverge inside it).  Quantized pools copy the
    scale slices with the int8 bytes — a COW tail carries its donor's
    exact quantization."""
    src = jnp.clip(src, 0, k_pool.shape[1] - 1)  # pad entries gather blk 0
    k_pool = k_pool.at[:, dst].set(k_pool[:, src], mode="drop")
    v_pool = v_pool.at[:, dst].set(v_pool[:, src], mode="drop")
    if k_scale is None:
        return k_pool, v_pool
    k_scale = k_scale.at[:, dst].set(k_scale[:, src], mode="drop")
    v_scale = v_scale.at[:, dst].set(v_scale[:, src], mode="drop")
    return k_pool, v_pool, k_scale, v_scale
