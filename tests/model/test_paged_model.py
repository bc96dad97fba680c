"""Paged fill/decode chunks vs the proven dense prefill/decode paths.

The paged pool + block tables must be a pure re-layout: identical logits
and identical greedy decode to the dense per-row cache, regardless of how
the prompt is split into fill chunks or how blocks are scattered in the
pool."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import paged
from areal_tpu.models.config import tiny_config
from areal_tpu.models.transformer import (
    KVCache,
    _head,
    decode_chunk,
    init_params,
    prefill,
)

BS = 16  # small block size so prompts span several blocks


@pytest.fixture(scope="module")
def cfg():
    return tiny_config()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def _dense_prefill_logits(cfg, params, prompts):
    B = len(prompts)
    T = max(len(p) for p in prompts)
    toks = np.zeros((B, T), np.int32)
    lens = np.zeros((B,), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
        lens[i] = len(p)
    pos = np.tile(np.arange(T, dtype=np.int32)[None], (B, 1))
    seg = (pos < lens[:, None]).astype(np.int32)
    cache = KVCache.zeros(cfg, B, 64)
    logits, cache = prefill(
        params, cfg, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(seg),
        cache, last_pos=jnp.asarray(lens - 1),
    )
    return np.asarray(logits[:, 0]), cache, lens


def _paged_fill(cfg, params, prompts, chunk, scramble_seed=0):
    """Fill via paged chunks of size ``chunk``; returns (logits, pools,
    tables, lengths)."""
    B = len(prompts)
    MB = 8
    NB = B * MB + 4
    kp, vp = paged.pool_zeros(cfg, NB, BS)
    rng = np.random.RandomState(scramble_seed)
    perm = rng.permutation(NB)[: B * MB]
    tables = jnp.asarray(perm.reshape(B, MB), jnp.int32)
    lens = np.array([len(p) for p in prompts], np.int32)
    last = np.zeros((B, cfg.vocab_size), np.float32)
    filled = np.zeros((B,), np.int32)
    while (filled < lens).any():
        cl = np.minimum(lens - filled, chunk)
        toks = np.zeros((B, chunk), np.int32)
        for i, p in enumerate(prompts):
            got = p[filled[i] : filled[i] + cl[i]]
            toks[i, : len(got)] = got
        logits, kp, vp = paged.paged_fill_chunk(
            params, kp, vp, cfg,
            jnp.asarray(toks), jnp.asarray(filled), jnp.asarray(cl),
            tables, use_kernel=False,
        )
        new_filled = filled + cl
        # a row's last-logits are valid only on ITS final chunk
        done_now = (cl > 0) & (new_filled == lens)
        last[done_now] = np.asarray(logits)[done_now]
        filled = new_filled
    return last, kp, vp, tables, jnp.asarray(lens)


@pytest.mark.parametrize("chunk", [64, 7, 16])
def test_fill_chunks_match_dense_prefill(cfg, params, chunk):
    rng = np.random.RandomState(1)
    prompts = [
        list(rng.randint(0, cfg.vocab_size, n)) for n in (5, 23, 40, 17)
    ]
    dense_logits, _, _ = _dense_prefill_logits(cfg, params, prompts)
    paged_logits, *_ = _paged_fill(cfg, params, prompts, chunk)
    np.testing.assert_allclose(
        paged_logits, dense_logits, rtol=2e-4, atol=2e-4
    )


def test_paged_decode_matches_dense_decode(cfg, params):
    rng = np.random.RandomState(2)
    prompts = [
        list(rng.randint(0, cfg.vocab_size, n)) for n in (9, 30, 21)
    ]
    W = 8
    dense_logits, dense_cache, lens = _dense_prefill_logits(
        cfg, params, prompts
    )
    paged_logits, kp, vp, tables, plens = _paged_fill(
        cfg, params, prompts, chunk=16
    )
    greedy = lambda logits, _rng: (
        jnp.argmax(logits, -1).astype(jnp.int32),
        jnp.max(jax.nn.log_softmax(logits), -1),
    )
    stop = lambda toks: jnp.zeros_like(toks, bool)
    cur = jnp.argmax(jnp.asarray(dense_logits), -1).astype(jnp.int32)
    B = cur.shape[0]
    active = jnp.ones((B,), bool)
    budgets = jnp.full((B,), W + 1, jnp.int32)
    key = jax.random.PRNGKey(0)

    (dc, d_t, d_l, d_em, d_cur, d_act, d_bud, _) = decode_chunk(
        params, cfg, dense_cache, cur, active, budgets, key, W,
        greedy, stop,
    )
    (kp, vp, p_lens, p_t, p_l, p_em, p_cur, p_act, p_bud, _) = (
        paged.paged_decode_chunk(
            params, kp, vp, cfg, tables, plens, cur, active, budgets,
            key, W, greedy, stop, use_kernel=False, max_len=BS * 8,
        )
    )
    np.testing.assert_array_equal(np.asarray(d_t), np.asarray(p_t))
    np.testing.assert_allclose(
        np.asarray(d_l), np.asarray(p_l), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_array_equal(np.asarray(d_em), np.asarray(p_em))
    np.testing.assert_array_equal(
        np.asarray(dc.lengths), np.asarray(p_lens)
    )
    # a SECOND chunk continues exactly (window was merged into the pool)
    (dc, d_t2, *_rest) = decode_chunk(
        params, cfg, dc, d_cur, d_act, d_bud, key, W, greedy, stop,
    )
    (kp, vp, p_lens, p_t2, *_rest2) = paged.paged_decode_chunk(
        params, kp, vp, cfg, tables, p_lens, p_cur, p_act, p_bud,
        key, W, greedy, stop, use_kernel=False, max_len=BS * 8,
    )
    np.testing.assert_array_equal(np.asarray(d_t2), np.asarray(p_t2))


def test_copy_blocks_and_shared_prefix(cfg, params):
    # simulate group sharing: row 1 references row 0's FULL blocks and a
    # COPIED tail block; decode over both rows must match two full fills
    rng = np.random.RandomState(3)
    prompt = list(rng.randint(0, cfg.vocab_size, 21))  # 21 = 16 + 5 (tail)
    _, kp, vp, tables, plens = _paged_fill(cfg, params, [prompt], chunk=64)
    MB = tables.shape[1]
    # build a 2-row view: row 1 shares block 0, owns a copy of block 1;
    # the copy target must be a real UNUSED pool block (an OOB id would
    # gather jnp's NaN fill in the reference path)
    NB = kp.shape[1]
    free_blk = min(set(range(NB)) - set(np.asarray(tables).ravel()))
    kp, vp = paged.copy_blocks(
        kp, vp, jnp.asarray([int(tables[0, 1])]), jnp.asarray([free_blk])
    )
    t2 = np.zeros((2, MB), np.int32)
    t2[0] = np.asarray(tables[0])
    t2[1] = np.asarray(tables[0])
    t2[1, 1] = free_blk
    tables2 = jnp.asarray(t2)
    lens2 = jnp.asarray([21, 21], jnp.int32)
    q = jax.random.normal(
        jax.random.PRNGKey(5), (1, 1, cfg.n_q_heads, cfg.head_dim)
    )
    q = jnp.concatenate([q, q])  # identical query -> identical output
    from areal_tpu.ops.paged_attention import reference_paged_partials

    for l in range(cfg.n_layers):
        acc, m, lden = reference_paged_partials(
            q, kp[l], vp[l], tables2, lens2
        )
        np.testing.assert_allclose(
            np.asarray(acc[0]), np.asarray(acc[1]), rtol=1e-6, atol=1e-6
        )


# --- the moved pool write (PR 28): one write after the layer scan --------


def _scatter_in_scan_forward(
    params, k_pool, v_pool, cfg, tokens, starts, valid, tables,
    k_scale=None, v_scale=None,
):
    """``paged.paged_window_forward`` as it was before PR 28: the pools in
    the layer scan's carry and a ``(pid, off)`` scatter in every layer.
    Kept here as the reference the moved write is held to, bit for bit."""
    from areal_tpu.models.transformer import (
        _attn_qkv, _embed, _mlp_block, _norm, _proj, rope_tables,
    )

    F, C = tokens.shape
    L, NB, Hkv, BS, hd = k_pool.shape
    r = cfg.n_q_heads // Hkv
    positions = starts[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    read_lens = jnp.where(valid[:, 0], starts, 0)
    x = _embed(params, cfg, tokens, positions)
    rope_cs = rope_tables(positions, cfg.rotary_base, cfg.head_dim)
    iot = jnp.arange(C)
    mask_chunk = (
        valid[:, None, :] & valid[:, :, None] & (iot[:, None] >= iot[None, :])
    )
    pid_log = jnp.clip(positions // BS, 0, tables.shape[1] - 1)
    pid = jnp.take_along_axis(tables, pid_log, axis=1)
    pid = jnp.where(valid, pid, NB)  # invalid -> OOB -> dropped
    off = positions % BS
    seg_ids = valid.astype(jnp.int32)
    scale = 1.0 / np.sqrt(hd)

    def body(carry, xs):
        x, k_pool, v_pool, k_scale, v_scale = carry
        lp, l = xs
        h = _norm(x, lp["attn_norm"], cfg)
        q, k, v = _attn_qkv(cfg, lp, h, positions, rope_cs)
        acc_p, m_p, l_p = paged._prefix_partials(
            q, k_pool, v_pool, tables, read_lens, l, False,
            k_scale=k_scale, v_scale=v_scale,
        )
        qg = q.reshape(F, C, Hkv, r, hd)
        s_c = jnp.einsum(
            "fikrd,fjkd->fkrij",
            qg.astype(jnp.float32), k.astype(jnp.float32),
        ) * scale
        s_c = jnp.where(mask_chunk[:, None, None, :, :], s_c, paged._NEG_INF)
        accp = acc_p.reshape(F, C, Hkv, r, hd).transpose(0, 2, 3, 1, 4)
        mp = m_p.reshape(F, C, Hkv, r).transpose(0, 2, 3, 1)
        lpp = l_p.reshape(F, C, Hkv, r).transpose(0, 2, 3, 1)
        m_tot = jnp.maximum(mp, jnp.max(s_c, axis=-1))
        p_c = jnp.exp(s_c - m_tot[..., None])
        alpha = jnp.exp(mp - m_tot)
        num = accp * alpha[..., None] + jnp.einsum(
            "fkrij,fjkd->fkrid", p_c, v.astype(jnp.float32)
        )
        den = lpp * alpha + jnp.sum(p_c, axis=-1)
        attn = (num / jnp.maximum(den, 1e-30)[..., None]).astype(x.dtype)
        attn = attn.transpose(0, 3, 1, 2, 4).reshape(F, C, cfg.n_q_heads * hd)
        x = x + _proj(lp["attn"]["o"], attn)
        h2 = _norm(x, lp["mlp_norm"], cfg)
        mlp_out, _ = _mlp_block(cfg, lp, h2, seg_ids=seg_ids)
        x = x + mlp_out
        if k_scale is not None:
            kq, ks = paged.quantize_kv(k)
            vq, vs = paged.quantize_kv(v)
            k_pool = k_pool.at[l, pid, :, off].set(kq, mode="drop")
            v_pool = v_pool.at[l, pid, :, off].set(vq, mode="drop")
            k_scale = k_scale.at[l, pid, :, off].set(ks, mode="drop")
            v_scale = v_scale.at[l, pid, :, off].set(vs, mode="drop")
        else:
            k_pool = k_pool.at[l, pid, :, off].set(
                k.astype(k_pool.dtype), mode="drop"
            )
            v_pool = v_pool.at[l, pid, :, off].set(
                v.astype(v_pool.dtype), mode="drop"
            )
        return (x, k_pool, v_pool, k_scale, v_scale), None

    (x, k_pool, v_pool, k_scale, v_scale), _ = jax.lax.scan(
        body,
        (x, k_pool, v_pool, k_scale, v_scale),
        (params["layers"], jnp.arange(L)),
    )
    return x, k_pool, v_pool, k_scale, v_scale


def _marked_pools(cfg, NB, quantized, seed):
    """Pools (and scale pools), as host arrays, that hold a mark in every
    slot, so that a slot the write should have left alone shows."""
    rng = np.random.RandomState(seed)
    shape = (cfg.n_layers, NB, cfg.n_kv_heads, BS, cfg.head_dim)
    if quantized:
        draw = lambda: rng.randint(-127, 128, shape).astype(np.int8)
        scales = [
            (rng.rand(*shape[:-1]) + 0.5).astype(np.float32) for _ in range(2)
        ]
    else:
        draw = lambda: rng.randn(*shape).astype(np.float32)
        scales = [None, None]
    return [draw(), draw()] + scales


def _on_device(pools):
    """Fresh device copies (the fill donates its pools)."""
    return [None if p is None else jnp.array(p) for p in pools]


def _assert_pools_equal(got, want):
    for g, w in zip(got, want):
        if w is not None:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("n_chunks", [1, 2, 4])
def test_fill_write_after_the_scan_matches_scatter_in_scan(
    cfg, params, n_chunks, quantized
):
    """A prompt batch filled as 1, 2 and 4 chunks: the pools (values and
    int8 scales) are bit-equal to the in-scan scatter's everywhere, the
    last logits too, and every slot outside the rows' prompts still
    holds its mark: dropped positions (a row that has run out, the
    padding of a short last chunk) are really dropped."""
    rng = np.random.RandomState(11)
    lens = np.array([5, 23, 40, 17, 0], np.int32)  # the last row is padding
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in lens]
    B, MB_ = len(prompts), 4
    NB = B * MB_ + 3
    chunk = -(-int(lens.max()) // n_chunks)  # 40, 20, 10: 10 splits pages
    tables = jnp.asarray(
        np.random.RandomState(5).permutation(NB)[: B * MB_].reshape(B, MB_),
        jnp.int32,
    )
    marks = _marked_pools(cfg, NB, quantized, seed=7)
    new, old = _on_device(marks), _on_device(marks)

    @partial(jax.jit, static_argnums=3)
    def reference(params, k_pool, v_pool, cfg, toks, starts, cl, **scales):
        valid = jnp.arange(toks.shape[1])[None, :] < cl[:, None]
        x, *pools = _scatter_in_scan_forward(
            params, k_pool, v_pool, cfg, toks, starts, valid, tables,
            **scales,
        )
        last = jnp.maximum(cl - 1, 0)[:, None, None]
        x_last = jnp.take_along_axis(x, last, axis=1)
        return _head(params, cfg, x_last)[:, 0], pools

    filled = np.zeros((B,), np.int32)
    while (filled < lens).any():
        cl = np.minimum(lens - filled, chunk)
        toks = np.zeros((B, chunk), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : cl[i]] = p[filled[i] : filled[i] + cl[i]]
        args = (cfg, jnp.asarray(toks), jnp.asarray(filled))
        out = paged.paged_fill_chunk(
            params, new[0], new[1], *args, jnp.asarray(cl), tables,
            use_kernel=False, k_scale=new[2], v_scale=new[3],
        )
        logits, new = out[0], list(out[1:]) + [None] * (5 - len(out))
        want_logits, old = reference(
            params, old[0], old[1], *args, jnp.asarray(cl),
            k_scale=old[2], v_scale=old[3],
        )
        _assert_pools_equal(new, old)
        np.testing.assert_array_equal(
            np.asarray(logits), np.asarray(want_logits)
        )
        filled = filled + cl
    # outside the prompts' slots nothing moved
    written = np.zeros((NB, BS), bool)
    for i, n in enumerate(lens):
        for p in range(n):
            written[int(tables[i, p // BS]), p % BS] = True
    for got, mark in zip(new, marks):
        if mark is not None:
            keep = ~written[None, :, None, :]
            got, mark = np.asarray(got), np.asarray(mark)
            if got.ndim == 5:
                keep = keep[..., None]
            np.testing.assert_array_equal(
                np.where(keep, got, 0), np.where(keep, mark, 0)
            )
    assert written.sum() == lens.sum()


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_window_over_live_prefixes_matches_scatter_in_scan(
    cfg, params, quantized
):
    """A short window over live prefixes: rows that take part with
    windows of several lengths, one whose window crosses a page, one cut
    by ``max_len``, one that is left out: hidden states and pools
    bit-equal to the in-scan scatter's."""
    rng = np.random.RandomState(13)
    lens = np.array([14, 30, 9, 62, 21], np.int32)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in lens]
    B, MB_, C, max_len = len(prompts), 4, 5, 64
    NB = B * MB_ + 2
    tables = jnp.asarray(
        np.random.RandomState(6).permutation(NB)[: B * MB_].reshape(B, MB_),
        jnp.int32,
    )
    pools = _on_device(_marked_pools(cfg, NB, quantized, seed=8))
    toks = np.zeros((B, 64), np.int32)
    for i, p in enumerate(prompts):
        toks[i, : len(p)] = p
    out = paged.paged_fill_chunk(
        params, pools[0], pools[1], cfg, jnp.asarray(toks),
        jnp.zeros((B,), jnp.int32), jnp.asarray(lens), tables,
        use_kernel=False, k_scale=pools[2], v_scale=pools[3],
    )
    pools = list(out[1:]) + [None] * (5 - len(out))
    window = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, C)), jnp.int32)
    last = jnp.asarray([4, 2, 0, 4, 3], jnp.int32)
    takes_part = jnp.asarray([True, True, True, True, False])
    iot = jnp.arange(C, dtype=jnp.int32)
    starts = jnp.asarray(lens)
    valid = (
        takes_part[:, None]
        & (iot[None, :] <= last[:, None])
        & ((starts[:, None] + iot[None, :]) < max_len)
    )
    assert [int(n) for n in valid.sum(1)] == [5, 3, 1, 2, 0]
    args = (cfg, window, starts, valid, tables)
    fwd = jax.jit(
        paged.paged_window_forward, static_argnums=3,
        static_argnames=("use_kernel",),
    )
    x_new, *new = fwd(
        params, pools[0], pools[1], *args, use_kernel=False,
        k_scale=pools[2], v_scale=pools[3],
    )
    x_old, *old = jax.jit(_scatter_in_scan_forward, static_argnums=3)(
        params, pools[0], pools[1], *args,
        k_scale=pools[2], v_scale=pools[3],
    )
    np.testing.assert_array_equal(np.asarray(x_new), np.asarray(x_old))
    _assert_pools_equal(new, old)
    assert not np.array_equal(np.asarray(new[0]), np.asarray(pools[0]))


@pytest.mark.parametrize("T,page", [(8, 16), (16, 16), (40, 16), (5, 4)])
def test_write_kv_runs_is_the_coordinate_scatter(T, page):
    """The one pool write, alone, against ``pool.at[:, pid, :, off]``:
    runs shorter than, equal to and longer than a page, starting
    anywhere, rows that write nothing, and a run whose end would pass
    its table: those pages are dropped."""
    rng = np.random.RandomState(T)
    L, R, MB_, Hkv, hd = 2, 4, 4, 2, 8
    NB = R * MB_ + 1
    for trial in range(12):
        starts = rng.randint(0, MB_ * page - T // 2, R).astype(np.int32)
        counts = rng.randint(0, T + 1, R).astype(np.int32)
        counts[trial % R] = 0
        tables = rng.permutation(NB)[: R * MB_].reshape(R, MB_)
        pool = jnp.asarray(rng.randn(L, NB, Hkv, page, hd), jnp.float32)
        spool = jnp.asarray(rng.randn(L, NB, Hkv, page), jnp.float32)
        vals = jnp.asarray(rng.randn(L, R, T, Hkv, hd), jnp.float32)
        svals = jnp.asarray(rng.randn(L, R, T, Hkv), jnp.float32)
        pos = starts[:, None] + np.arange(T)[None]
        ok = (np.arange(T)[None] < counts[:, None]) & (pos < MB_ * page)
        pid = np.where(
            ok, np.take_along_axis(tables, np.minimum(pos // page, MB_ - 1), 1),
            NB,
        )
        want = pool.at[:, pid, :, pos % page].set(
            vals.transpose(1, 2, 0, 3, 4), mode="drop"
        )
        swant = spool.at[:, pid, :, pos % page].set(
            svals.transpose(1, 2, 0, 3), mode="drop"
        )
        got, sgot = paged.write_kv_runs(
            (pool, spool), (vals, svals), jnp.asarray(tables, jnp.int32),
            jnp.asarray(starts), jnp.asarray(counts),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(sgot), np.asarray(swant))
