"""Pallas paged-attention kernel vs jnp reference (interpret mode on CPU;
the same kernel compiles for TPU under the serving engine's paged KV)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.paged import quantize_kv
from areal_tpu.ops.paged_attention import (
    PAGE_GROUP,
    gather_paged_kv,
    page_group,
    page_tile,
    paged_flash_attention,
    page_fetched,
    plan_pages,
    reference_paged_partials,
    stream_page,
    visit_order,
)

BS = 128
#: a page of TWO tiles (the kernel copies and multiplies a row's last page
#: as far as the last tile that holds a cached position), and its tile
BS2, TILE = 512, 256


def _setup(B=4, Q=1, Hq=8, Hkv=4, MB=4, NB=32, hd=128, seed=0,
           lengths=None, dtype=jnp.bfloat16, q_dtype=jnp.float32,
           engine_tables=False, BS=BS):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Q, Hq, hd), jnp.float32).astype(q_dtype)
    k_pool = jax.random.normal(
        ks[1], (NB, Hkv, BS, hd), jnp.float32
    ).astype(dtype)
    v_pool = jax.random.normal(
        ks[2], (NB, Hkv, BS, hd), jnp.float32
    ).astype(dtype)
    # a scrambled table: logical order != pool order, no duplicates
    perm = jax.random.permutation(ks[3], NB)[: B * MB]
    tables = perm.reshape(B, MB).astype(jnp.int32)
    if lengths is None:
        lengths = [MB * BS] * B
    lens = jnp.asarray(lengths, jnp.int32)
    if engine_tables:
        # as the engine writes them: zeros past a row's blocks, a dead
        # row's table all zeros
        held = jnp.arange(MB)[None, :] * BS < lens[:, None]
        tables = jnp.where(held, tables, 0)
    return q, k_pool, v_pool, tables, lens


def _kv_heads(page, dtype):
    """KV heads that make a tile of TILE tokens 256 KiB of a pool of
    ``dtype``, so that a page of BS2 IS read in two tiles (the rule wants
    that much a copy); 2 heads on the one-tile page."""
    return 2 if page == BS else 2048 // TILE // jnp.dtype(dtype).itemsize


def _assert_tiled(pool, page):
    """The kernel copies ``pool``'s pages of BS2 in tiles of TILE."""
    assert page_tile(pool.shape, pool.dtype) == (TILE if page == BS2 else BS)


def _assert_matches_reference(got, want, lens, tol=3e-3):
    (acc, m, l), (acc_r, m_r, l_r) = got, want
    valid = np.asarray(lens) > 0
    np.testing.assert_allclose(
        np.asarray(m)[valid], np.asarray(m_r)[valid], rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(l)[valid], np.asarray(l_r)[valid], rtol=2e-3, atol=2e-3
    )
    out = np.asarray(acc)[valid] / np.asarray(l)[valid][..., None]
    out_r = np.asarray(acc_r)[valid] / np.asarray(l_r)[valid][..., None]
    np.testing.assert_allclose(out, out_r, rtol=tol, atol=tol)
    # dead rows: nothing attended, nothing accumulated
    assert (np.asarray(l)[~valid] == 0).all()
    assert (np.asarray(acc)[~valid] == 0).all()


#: what a decode batch looks like in the middle of a rollout: dead slots
#: between live ones, rows of one page beside rows of four, a length on a
#: page's edge, a dead first slot, a dead tail
RAGGED = {
    "dead_between_live": [300, 0, 0, 512, 0, 77, 0, 130],
    "one_page_beside_four": [100, 512, 128, 400, 1, 511, 90, 385],
    "on_a_page_edge": [128, 256, 384, 512, 129, 257, 0, 127],
    "dead_first_and_last": [0, 0, 512, 1, 0, 200, 0, 0],
}
#: the same on pages of two tiles: lengths one under, on and one over a
#: tile's edge, at a page's edge and one past it, dead rows between
TILED = {
    "on_a_tile_edge": [255, 256, 257, 0, 511, 512, 513, 1],
    "pages_and_tiles": [767, 768, 769, 0, 1025, 2048, 1281, 1024],
}
#: case -> (tokens a page, lengths)
CASES = {
    **{name: (BS, lengths) for name, lengths in RAGGED.items()},
    **{name: (BS2, lengths) for name, lengths in TILED.items()},
}


@pytest.mark.parametrize("pool", ["bf16", "float32", "int8"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_batch_matches_reference(case, pool):
    """The copy rule (each valid page once and as far as it is filled,
    nothing for dead rows or past a row's blocks) changes no number:
    engine-written tables (zeros past a row's blocks) over every pool
    format, bf16 queries as the model hands them (so the bf16 pool takes
    the bf16-operand dots and the other two the float32 path)."""
    page, lengths = CASES[case]
    dtype = {"float32": jnp.float32, "int8": jnp.int8}.get(pool, jnp.bfloat16)
    Hkv = _kv_heads(page, dtype)
    q, kp, vp, tables, lens = _setup(
        B=len(lengths), Hq=2 * Hkv, Hkv=Hkv, NB=40, lengths=lengths, seed=21,
        dtype=jnp.float32 if pool == "float32" else jnp.bfloat16,
        q_dtype=jnp.bfloat16, engine_tables=True, BS=page,
    )
    scales = {}
    if pool == "int8":
        kp, ks = quantize_kv(kp)
        vp, vs = quantize_kv(vp)
        scales = dict(k_scale=ks, v_scale=vs)
    _assert_tiled(kp, page)
    got = paged_flash_attention(
        q, kp, vp, tables, lens, interpret=True, **scales
    )
    want = reference_paged_partials(q, kp, vp, tables, lens, **scales)
    _assert_matches_reference(got, want, lens)


def test_bf16_operand_dots_equal_float32_highest_at_4k():
    """bf16 q, K and V straight to the MXU (one exact pass for q k^T,
    the probabilities split into three bf16 terms for p v) against the
    float32 HIGHEST dots on the SAME bf16 values, at 4k of context."""
    MB = 4096 // BS
    q, kp, vp, tables, lens = _setup(
        B=2, Hq=4, Hkv=2, MB=MB, NB=2 * MB, lengths=[4096, 4096 - 37],
        seed=31, q_dtype=jnp.bfloat16,
    )
    acc, m, l = paged_flash_attention(q, kp, vp, tables, lens, interpret=True)
    # the same numbers as float32 operands: the float32 path
    acc_f, m_f, l_f = paged_flash_attention(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32), tables, lens, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_f), rtol=1e-6)
    out = np.asarray(acc) / np.asarray(l)[..., None]
    out_f = np.asarray(acc_f) / np.asarray(l_f)[..., None]
    assert np.max(np.abs(out - out_f)) <= 1e-5 * np.max(np.abs(out_f))


def _held_table(lengths, page, MB=4):
    """Distinct page ids where a row holds a page, zeros past it (as the
    engine writes tables), and the mask of held pages."""
    B = len(lengths)
    tables = np.arange(1, B * MB + 1, dtype=np.int32).reshape(B, MB)
    held = np.arange(MB)[None, :] * page < np.asarray(lengths)[:, None]
    return np.where(held, tables, 0), held


def _walk_the_grid(lengths, page, tile, group, query_tiles=1, MB=4, tables=None,
                   window=None, decode=False):
    """What the kernel copies over a whole grid, by the functions the
    kernel itself calls (``stream_page``, ``page_fetched``) on the plan
    ``plan_pages`` makes: ``{(row in slot order, page id): [tiles copied,
    one entry a copy]}``.  ``decode``: the grid of a decode call, which
    stops at the plan's live rows; ``window``: the windowed plan, a row's
    steps as many as a window's pages."""
    from areal_tpu.ops.paged_attention import window_span_pages

    lengths = np.asarray(lengths)
    if tables is None:
        tables, _ = _held_table(lengths, page, MB)
    plan = plan_pages(
        jnp.asarray(tables), jnp.asarray(lengths), page, group, window, decode
    )
    lens, ids, order = (np.asarray(x) for x in plan[:3])
    firsts = None if window is None else np.asarray(plan.firsts)
    assert ids.shape == (len(lens), -(-MB // group) * group)
    if window is None:
        held = np.arange(ids.shape[1])[None, :] * page < lens[:, None]
        np.testing.assert_array_equal(  # every held page under its own id
            ids[held],
            np.asarray(tables)[order][np.arange(MB)[None, :] * page < lens[:, None]],
        )
    pages = MB if window is None else min(MB, window_span_pages(page, window))
    copied, before = {}, None
    for b in range(int(plan.live.count) if decode else len(lens)):
        for _qb in range(query_tiles):
            for j in range(-(-pages // group)):
                here = [
                    stream_page(lens, ids, b, j, g, group, page, tile, firsts)
                    for g in range(group)
                ]
                for this, was in zip(here, before or here):
                    if page_fetched(this, was, before is None):
                        pid, n = this
                        copied.setdefault((order[b], int(pid)), []).append(int(n))
                before = here
    return tables, copied


@pytest.mark.parametrize("query_tiles", [1, 3])
@pytest.mark.parametrize("group", [1, 2, PAGE_GROUP])
@pytest.mark.parametrize("case", sorted(CASES))
def test_every_filled_tile_is_copied_once_and_no_other(case, group, query_tiles):
    """In visiting order every page a row holds is copied as far as its
    last tile with a cached position, exactly once while the grid stays
    on it (once a query tile where a row takes several page steps, as
    the BlockSpec pipeline did); no tile past a row's length and nothing
    of a dead row is ever copied: the bytes fetched are ``sum(ceil(len /
    tile)) x tile`` entries."""
    page, lengths = CASES[case]
    tile = page_tile((2, page, 128), jnp.float32)
    tables, copied = _walk_the_grid(lengths, page, tile, group, query_tiles)
    visits = 1 if group == PAGE_GROUP else query_tiles  # page steps a row: 1
    for b, n in enumerate(lengths):
        for p in range(tables.shape[1]):
            filled = min(max(n - p * page, 0), page)
            got = copied.pop((b, int(tables[b, p])), []) if filled else []
            assert got == [-(-filled // tile)] * visits if filled else not got
    assert not copied  # no page of a dead row, none past a row's length
    _, copied = _walk_the_grid(lengths, page, tile, group)
    assert sum(sum(v) for v in copied.values()) * tile == sum(
        -(-n // tile) * tile for n in lengths
    )


@pytest.mark.parametrize(
    "shape,dtype,tile",
    [
        ((2, 16, 128), jnp.bfloat16, 16),  # a page of at most 256 tokens
        ((2, 128, 128), jnp.bfloat16, 128),  # is its own tile
        ((2, 256, 128), jnp.float32, 256),
        ((2, 384, 128), jnp.float32, 384),  # no divisor of 256 or more
        ((2, 512, 128), jnp.float32, 256),  # these tests' two-tile page
        ((2, 1024, 128), jnp.bfloat16, 512),  # Qwen2.5-1.5B: 256 KiB a tile
        ((2, 1024, 128), jnp.int8, 1024),
        ((4, 1024, 128), jnp.bfloat16, 256),  # Qwen2.5-7B
        ((8, 1024, 128), jnp.bfloat16, 256),  # granite-4.0-h-small
        ((5, 64, 1, 512, 640), jnp.bfloat16, 256),  # latent pages
        ((2, 4096, 128), jnp.bfloat16, 1024),  # four tiles at most
    ],
)
def test_tile_is_a_function_of_the_pools_shape(shape, dtype, tile):
    """A page of at most 256 tokens is its own tile (one copy descriptor
    a page); a longer one is cut into at most four tiles of at least 256
    tokens and 256 KiB of one pool, each a whole number of lane tiles,
    whatever the queries."""
    from areal_tpu.ops import paged_attention as pa

    Hkv, page, hd = shape[-3:]
    assert page_tile(shape, dtype) == tile
    itemsize = jnp.dtype(dtype).itemsize
    for Q, r in [(1, 4), (256, 4), (1, 64)]:
        assert pa._plan_tiles(Q, r, Hkv, page, hd, itemsize, False, 4)[2] == tile


def test_visit_order_puts_long_rows_first_and_dead_rows_last():
    lens = jnp.asarray([0, 130, 512, 0, 1, 129, 384, 128])
    order = np.asarray(visit_order(lens, BS))
    # 4, 3, 2, 2, 1, 1 pages, then the dead rows; ties keep slot order
    np.testing.assert_array_equal(order, [2, 6, 1, 5, 4, 7, 0, 3])


def test_handed_plan_equals_own_plan():
    """A caller that makes the plan once (models/paged._prefix_plan) gets
    the numbers of a call that makes its own."""
    lengths = RAGGED["one_page_beside_four"]
    q, kp, vp, tables, lens = _setup(
        B=len(lengths), Hq=4, Hkv=2, NB=40, lengths=lengths, seed=23,
        q_dtype=jnp.bfloat16, engine_tables=True,
    )
    group = page_group(1, 4, kp.shape, kp.dtype, False, tables.shape[1])
    assert group == PAGE_GROUP
    plan = plan_pages(tables, lens, BS, group, decode=True)
    own = paged_flash_attention(q, kp, vp, tables, lens, interpret=True)
    handed = paged_flash_attention(
        q, kp, vp, tables, lens, interpret=True, plan=plan
    )
    for a, b in zip(own, handed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_pads_a_ragged_table_width():
    # MB not a multiple of the group: the step past a row's table holds
    # no cached position, whatever id stands there
    tables = jnp.asarray([[8, 0, 0], [5, 6, 7]], jnp.int32)
    plan = plan_pages(tables, jnp.asarray([10, 3 * BS]), BS, 2)
    np.testing.assert_array_equal(plan.order, [1, 0])
    np.testing.assert_array_equal(plan.lengths, [3 * BS, 10])
    np.testing.assert_array_equal(plan.page_ids, [[5, 6, 7, 0], [8, 0, 0, 0]])
    assert [
        int(stream_page(*plan[:2], 0, 1, g, 2, BS, BS)[1]) for g in range(2)
    ] == [1, 0]


@pytest.mark.parametrize("seed", [0, 4])
@pytest.mark.parametrize(
    "lengths",
    [[512, 512, 512, 512], [1, 130, 256, 511], [0, 512, 37, 300]],
)
def test_paged_attention_matches_reference(lengths, seed):
    q, kp, vp, tables, lens = _setup(lengths=lengths, seed=seed)
    got = paged_flash_attention(q, kp, vp, tables, lens, interpret=True)
    want = reference_paged_partials(q, kp, vp, tables, lens)
    _assert_matches_reference(got, want, lens)


@pytest.mark.parametrize(
    "page,lengths", [(BS, [300, 77]), (BS2, [TILE + 1, 3 * TILE])]
)
def test_paged_attention_multi_query_chunk(page, lengths):
    # Q=16 queries per row (the chunked-prefill prefix-attention shape):
    # every query sees the same full prefix
    Hkv = _kv_heads(page, jnp.bfloat16)  # 16 x 2 = 32 query rows a cell
    q, kp, vp, tables, lens = _setup(
        B=2, Q=16, Hq=2 * Hkv, Hkv=Hkv, MB=3, NB=8, lengths=lengths, seed=2,
        BS=page,
    )
    _assert_tiled(kp, page)
    acc, m, l = paged_flash_attention(q, kp, vp, tables, lens, interpret=True)
    acc_r, m_r, l_r = reference_paged_partials(q, kp, vp, tables, lens)
    out = np.asarray(acc) / np.asarray(l)[..., None]
    out_r = np.asarray(acc_r) / np.asarray(l_r)[..., None]
    np.testing.assert_allclose(out, out_r, rtol=3e-3, atol=3e-3)


def test_paged_matches_dense_flash_decode():
    # paged over a scrambled table == dense flash decode over the
    # materialized rows (ties the new kernel to the proven one)
    from areal_tpu.ops.decode_attention import flash_decode

    q, kp, vp, tables, lens = _setup(lengths=[512, 100, 1, 256], seed=5)
    acc_p, m_p, l_p = paged_flash_attention(
        q, kp, vp, tables, lens, interpret=True
    )
    k_dense, v_dense = gather_paged_kv(kp, vp, tables)
    acc_d, m_d, l_d = flash_decode(
        q[:, 0], k_dense, v_dense, lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(acc_p[:, 0]), np.asarray(acc_d), rtol=2e-3, atol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(l_p[:, 0]), np.asarray(l_d), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize(
    "seed,L,page,lengths",
    [(11, 3, BS, [200, 77]), (12, 2, BS, [200, 77]),
     (13, 2, BS2, [TILE - 1, BS2 + TILE])],
)
def test_layered_pool_matches_per_layer_slice(seed, L, page, lengths):
    # the 5-D stacked-pool entry with a layer scalar must equal slicing
    # the layer out and calling the 4-D form
    Hkv = _kv_heads(page, jnp.bfloat16)
    q, kp, vp, tables, lens = _setup(B=2, Hq=2 * Hkv, Hkv=Hkv, MB=2, NB=8,
                                     lengths=lengths, seed=seed, BS=page)
    _assert_tiled(kp, page)
    kps = jnp.stack([kp + i for i in range(L)])
    vps = jnp.stack([vp - i for i in range(L)])
    for layer in range(L):
        acc_l, m_l, l_l = paged_flash_attention(
            q, kps, vps, tables, lens,
            layer=jnp.int32(layer), interpret=True,
        )
        acc_s, m_s, l_s = paged_flash_attention(
            q, kps[layer], vps[layer], tables, lens, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(acc_l), np.asarray(acc_s), rtol=1e-6, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(l_l), np.asarray(l_s), rtol=1e-6, atol=1e-6
        )


@pytest.mark.parametrize("page,pages", [(BS, 16), (BS2, 6)])
def test_rows_longer_than_one_grid_step(page, pages):
    """Rows of MORE pages than one grid step streams (16, or 6 of two
    tiles, against PAGE_GROUP): the page axis of the grid is longer than
    1, a stream walks several pages of one row, its two buffers take
    turns within a row, and the forward fill runs across rows of all, 2
    and 0 pages and one that ends a tile into its last page."""
    MB = pages
    lengths = [MB * page, 2 * page - 37, 0, MB * page - page // 2 - 37]
    Hkv = _kv_heads(page, jnp.bfloat16)
    q, kp, vp, tables, lens = _setup(
        B=4, Hq=2 * Hkv, Hkv=Hkv, MB=MB, NB=4 * MB + 4, lengths=lengths,
        seed=13, q_dtype=jnp.bfloat16, engine_tables=True, BS=page,
    )
    _assert_tiled(kp, page)
    got = paged_flash_attention(q, kp, vp, tables, lens, interpret=True)
    want = reference_paged_partials(q, kp, vp, tables, lens)
    _assert_matches_reference(got, want, lens)


def test_shared_blocks_between_rows():
    # two rows pointing at the SAME pool blocks (group prompt sharing)
    # read identical KV
    q, kp, vp, tables, lens = _setup(B=2, lengths=[256, 256], seed=7)
    q = q.at[1].set(q[0])
    tables = tables.at[1].set(tables[0])
    acc, m, l = paged_flash_attention(q, kp, vp, tables, lens, interpret=True)
    np.testing.assert_allclose(
        np.asarray(acc[0]), np.asarray(acc[1]), rtol=1e-6, atol=1e-6
    )


# -- the model's own softmax scale, and 8 kv heads x 4 q (neither Qwen shape) --


@pytest.mark.parametrize("Q", [1, 24])
@pytest.mark.parametrize("scale", [None, 0.0078125, 0.2])
def test_softmax_scale_is_an_argument_with_the_old_default(scale, Q):
    """``scale=None`` is 1/sqrt(hd) as before; a model that states its
    own (granite's ``attention_multiplier``) hands it in.  At 32 q / 8 kv
    heads the kernel's tile plan groups 4 query heads a kv head."""
    lengths = [300, 0, 512, 77]
    q, kp, vp, tables, lens = _setup(
        B=4, Q=Q, Hq=32, Hkv=8, NB=24, lengths=lengths, seed=7,
        q_dtype=jnp.bfloat16, engine_tables=True,
    )
    got = paged_flash_attention(
        q, kp, vp, tables, lens, interpret=True, scale=scale
    )
    want = reference_paged_partials(q, kp, vp, tables, lens, scale=scale)
    _assert_matches_reference(got, want, lens)
    # the scale is in the scores, not folded away: another scale is
    # another softmax
    hd = q.shape[-1]
    if scale not in (None, 1.0 / np.sqrt(hd)):
        default = reference_paged_partials(q, kp, vp, tables, lens)
        assert not np.allclose(np.asarray(default[1]), np.asarray(want[1]))
    else:
        explicit = reference_paged_partials(
            q, kp, vp, tables, lens, scale=1.0 / np.sqrt(hd)
        )
        np.testing.assert_allclose(
            np.asarray(explicit[0]), np.asarray(want[0]), rtol=1e-4, atol=1e-4
        )


@pytest.mark.parametrize("Q", [1, 64, 256])
def test_tile_plan_for_eight_kv_heads_of_four_queries(Q):
    """The published granite head layout at the engine's page of 1,024:
    the plan keeps within the VMEM budget with whole sublane tiles."""
    from areal_tpu.ops import paged_attention as pa

    G, QT, tile = pa._plan_tiles(Q, 4, 8, 1024, 128, 2, False, 4)
    assert 1 <= G <= pa.PAGE_GROUP and 1 <= QT <= Q
    assert QT == Q or (QT * 4) % 8 == 0
    assert tile == 256
    assert (
        pa.vmem_bytes_needed(8, 1024, 128, 2, False, G, QT * 4)
        <= pa.VMEM_BUDGET_BYTES
    )


# -- latent pages: one "head", the page is keys AND (first columns) values --


def _latent_setup(B, Q, lengths, MB=5, NB=32, H=16, width=256, L=None, seed=0,
                  BS=BS):
    """A latent pool ``[NB, 1, BS, width]`` (a token's ``[c_kv | k_rope |
    0]``) and queries ``[B, Q, H, width]``, as the absorbed form makes
    them; the table as the engine writes it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (NB, 1, BS, width) if L is None else (L, NB, 1, BS, width)
    pool = (jax.random.normal(ks[0], shape) * 0.5).astype(jnp.bfloat16)
    q = (jax.random.normal(ks[1], (B, Q, H, width)) * 0.5).astype(jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    tables = jax.random.permutation(ks[2], NB)[: B * MB].reshape(B, MB)
    held = jnp.arange(MB)[None, :] * BS < lens[:, None]
    return q, pool, jnp.where(held, tables, 0).astype(jnp.int32), lens


# rows of 1, 2 and 5 pages, a dead row between them and one at the end
LATENT_ROWS = [BS - 28, 0, 2 * BS, 5 * BS - 3, BS + 1, 0]
# the same on pages of two tiles: a tile and one more, a page and a tile
LATENT_ROWS2 = [TILE + 1, 0, BS2 + TILE, 5 * BS2 - 3, TILE, 0]


@pytest.mark.parametrize("page,rows", [(BS, LATENT_ROWS), (BS2, LATENT_ROWS2)])
@pytest.mark.parametrize("Q", [1, 24])
def test_latent_pages_match_the_plain_partials(Q, page, rows):
    """``paged_mla_decode`` (Q 1) / ``paged_mla_fill``: one fetch of a page
    serves the scores (every column) and the values (the first
    ``value_dim``); the accumulator is ``value_dim`` wide."""
    # a row of 512 columns makes a tile of TILE tokens 256 KiB
    q, pool, tables, lens = _latent_setup(
        6, Q, rows, BS=page, width=256 if page == BS else 512
    )
    _assert_tiled(pool, page)
    got = paged_flash_attention(
        q, pool, None, tables, lens, interpret=True, scale=0.1447, value_dim=128
    )
    want = reference_paged_partials(
        q, pool, None, tables, lens, scale=0.1447, value_dim=128
    )
    assert got[0].shape == (6, Q, 16, 128) and got[1].shape == (6, Q, 16)
    _assert_matches_reference(got, want, lens)
    # the values ARE the keys' first columns: the same partials as a K/V
    # pool whose V holds them
    kv = reference_paged_partials(
        q, pool, pool[..., :128], tables, lens, scale=0.1447
    )
    np.testing.assert_allclose(np.asarray(want[2]), np.asarray(kv[2]), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(want[0]), np.asarray(kv[0])[..., :128], rtol=1e-5, atol=1e-5
    )


def test_latent_pages_of_a_layer_stacked_pool_and_a_handed_plan():
    from areal_tpu.ops.paged_attention import page_group, plan_pages

    q, pool, tables, lens = _latent_setup(6, 1, LATENT_ROWS, L=3, seed=3)
    G = page_group(1, 16, pool.shape, pool.dtype, False, tables.shape[1])
    plan = plan_pages(tables, lens, BS, G, decode=True)
    for layer in (0, 2):
        got = paged_flash_attention(
            q, pool, None, tables, lens, layer=jnp.int32(layer), interpret=True,
            scale=0.2, value_dim=128, plan=plan,
        )
        want = reference_paged_partials(
            q, pool[layer], None, tables, lens, scale=0.2, value_dim=128
        )
        _assert_matches_reference(got, want, lens)


def test_latent_mode_wants_its_value_width_and_no_v_pool():
    q, pool, tables, lens = _latent_setup(2, 1, [BS, 3])
    with pytest.raises(AssertionError):
        paged_flash_attention(q, pool, None, tables, lens, interpret=True)
    with pytest.raises(AssertionError):
        paged_flash_attention(
            q, pool, pool, tables, lens, interpret=True, value_dim=128
        )


#: a decode batch at its emptiest and its fullest, and the shapes between
#: (ISSUE 52): name -> (lengths, window)
LIVE_ROWS = {
    "every_row_dead": ([0] * 8, None),
    "one_live_row_of_64": ([0] * 37 + [300] + [0] * 26, None),
    "every_row_live": (RAGGED["one_page_beside_four"], None),
    "dead_between_live": (RAGGED["dead_between_live"], None),
    "dead_first_and_last": (RAGGED["dead_first_and_last"], None),
    # a window of ONE position holds no cached position at all: rows 0 and
    # 3 end ON a page's edge and their window starts on the page after
    # their last; rows 1 and 4 keep the page their window starts inside
    "a_window_that_leaves_a_row_no_page": ([256, 300, 0, 128, 1], 1),
    "every_row_dead_under_a_window": ([0] * 4, 200),
    "dead_rows_under_a_window": ([0, 700, 0, 0, 130, 384, 0], 260),
}


def _decode_call(case, pages, seed=41):
    """``(args, kwargs, lens, window)`` of a decode call over ``case``'s
    rows: K/V or latent pages."""
    lengths, window = LIVE_ROWS[case]
    B = len(lengths)
    kw = {} if window is None else dict(window=window)
    if pages == "latent":
        q, pool, tables, lens = _latent_setup(
            B, 1, lengths, MB=6, NB=6 * B + 2, H=8, seed=seed
        )
        return (q, pool, None, tables, lens), dict(kw, scale=0.2, value_dim=128), lens
    q, kp, vp, tables, lens = _setup(
        B=B, Hq=4, Hkv=2, MB=6, NB=6 * B + 2, lengths=lengths, seed=seed,
        q_dtype=jnp.bfloat16, engine_tables=True,
    )
    return (q, kp, vp, tables, lens), kw, lens


def _assert_dead_rows_read_the_constants(got, dead):
    acc, m, l = (np.asarray(x) for x in got)
    assert (acc[dead] == 0).all() and (l[dead] == 0).all()
    assert (m[dead] == np.float32(-1e30)).all()


@pytest.mark.parametrize("pages", ["kv", "latent"])
@pytest.mark.parametrize("case", list(LIVE_ROWS))
def test_a_decode_grid_holds_the_rows_that_have_pages(case, pages):
    """A DECODE call's plan counts the rows that hold a page to visit (1
    at least), the grid that stops there copies every filled tile of
    theirs once and nothing of any other row, and every row the grid left
    out returns what the contract names: ``acc = 0, l = 0, m = -1e30``."""
    from areal_tpu.ops import paged_attention as pa

    args, kw, lens = _decode_call(case, pages)
    lengths, window = LIVE_ROWS[case]
    tables = args[3]
    G = page_group(1, args[0].shape[2], args[1].shape, args[1].dtype, False, 6)
    plan = plan_pages(tables, lens, BS, G, window, decode=True)
    firsts = None if window is None else np.asarray(
        pa.window_first_pages(lens, BS, window)
    )
    held = np.asarray(pa.pages_to_visit(lens, BS, firsts))
    assert int(plan.live.count) == max((held > 0).sum(), 1)
    np.testing.assert_array_equal(np.asarray(plan.live.mask), held > 0)
    # the rows the grid visits are the live ones, and they come first
    visited = np.asarray(plan.order)[: int(plan.live.count)]
    assert set(visited[held[visited] > 0]) == set(np.flatnonzero(held > 0))
    assert plan_pages(tables, lens, BS, G, window).live is None  # a fill's
    # the bounded walk, by the kernel's own page arithmetic: every page
    # of a live row from its window's first on, whole, once; no other
    _, copied = _walk_the_grid(
        lengths, BS, BS, G, MB=6, tables=tables, window=window, decode=True
    )
    assert copied == {
        (r, int(tables[r, c])): [1]
        for r, n in enumerate(lengths)
        for c in range(0 if firsts is None else firsts[r], -(-n // BS))
    }
    got = paged_flash_attention(*args, interpret=True, plan=plan, **kw)
    _assert_dead_rows_read_the_constants(got, held == 0)
    if (held > 0).any():
        want = reference_paged_partials(*args, **kw)
        live = np.asarray(want[2])[:, 0, 0] > 0
        _assert_matches_reference(
            tuple(np.asarray(x)[live] for x in got),
            tuple(np.asarray(x)[live] for x in want), np.asarray(lens)[live],
        )
        # a live row whose window holds nothing: summed nothing
        assert (np.asarray(got[2])[~live] == 0).all()


@pytest.mark.parametrize(
    "case,pages",
    [
        ("dead_between_live", "kv"),
        ("dead_first_and_last", "latent"),
        ("every_row_dead", "kv"),
        ("a_window_that_leaves_a_row_no_page", "kv"),
        ("dead_rows_under_a_window", "latent"),
    ],
)
def test_an_unvisited_rows_blocks_never_reach_the_caller(case, pages):
    """The traced extent under Pallas' TPU interpreter with everything the
    kernel has not written made NaN, the output blocks of the rows the
    grid never visits among it: they return ``0, -1e30, 0`` all the same,
    the live rows are the plain interpreter's to the last bit, and a pool
    whose every page outside the live rows' is NaN (the pages a dead
    row's stale table names too) leaves no NaN anywhere."""
    from jax.experimental.pallas import tpu as pltpu

    pltpu.reset_tpu_interpret_mode_state()
    args, kw, lens = _decode_call(case, pages)
    lengths, window = LIVE_ROWS[case]
    q, kp, vp, tables, _ = args
    # NaN wherever no live row holds a page; stale ids in dead rows' tables
    keep = np.zeros(kp.shape[0], bool)
    for r, n in enumerate(lengths):
        keep[np.asarray(tables)[r, : -(-n // BS)]] = True
    stale = np.flatnonzero(~keep)
    tables = jnp.where(
        (jnp.arange(6)[None, :] * BS < lens[:, None]), tables, int(stale[0])
    )
    nan = lambda p: None if p is None else jnp.where(
        jnp.asarray(keep).reshape((-1,) + (1,) * (p.ndim - 1)), p, jnp.nan
    )
    got = paged_flash_attention(
        q, nan(kp), nan(vp), tables, lens,
        interpret=pltpu.InterpretParams(
            uninitialized_memory="nan", detect_races=True,
            dma_execution_mode="on_wait",
        ),
        **kw,
    )
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    jax.block_until_ready(got)  # (the interpreter's state is the process's)
    assert not interpret_pallas_call.races.races_found
    want = paged_flash_attention(q, kp, vp, tables, lens, interpret=True, **kw)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    _assert_dead_rows_read_the_constants(got, np.asarray(lengths) == 0)
