"""ops/sparse_attention.py: the index scores over a row's pages against
the scores of the same keys at hand, the exact choice against
``lax.top_k``, the gather of chosen entries against dense masked
attention; the paged kernel's latent mode UNDER A SELECTION (a fill's
masked prefix and a decode step's) and UNDER THE WINDOW (the two static branches together)
against their reference, each once more with every byte it must not read
NaN.  Tolerances: 2e-5 at float32
(sums in another order); the choice is compared as a SET, exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops import paged_attention as pa
from areal_tpu.ops import sparse_attention as sa


def _pool(key, L, NB, BS, width):
    return jax.random.normal(key, (L, NB, 1, BS, width), jnp.float32)


def _tables(B, MB, NB, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.permutation(NB)[: B * MB].reshape(B, MB), jnp.int32)


@pytest.mark.parametrize("T", [1, 5, 33, 70])
def test_paged_index_scores_are_the_scores_of_the_rows_pages(T, monkeypatch):
    monkeypatch.setattr(sa, "INDEX_QUERY_BLOCK", 32)  # (33, 70: blocks and padding)
    B, Hi, di, L, NB, BS, MB = 3, 4, 16, 2, 16, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    q = jax.random.normal(ks[0], (B, T, Hi, di))
    w = jax.random.normal(ks[1], (B, T, Hi))
    pool = _pool(ks[2], L, NB, BS, di)
    tables, lengths = _tables(B, MB, NB), jnp.asarray([0, 13, 32], jnp.int32)
    got = sa.paged_index_scores(q, w, pool, tables, lengths, jnp.int32(1))
    keys = pool[1][tables, 0].reshape(B, MB * BS, di)  # layer 1's pages, row by row
    want = sa.index_scores(q, w, keys)
    assert got.shape == (B, T, MB * BS)
    held = np.arange(MB * BS)[None, None, :] < np.asarray(lengths)[:, None, None]
    assert np.abs(np.asarray(got) - np.asarray(want))[np.broadcast_to(held, got.shape)].max() < 2e-5
    # past a row's length nothing scores, and a negative weight is not
    # lost to the relu (it weighs the relu's output)
    assert float(got[1, :, 13:].max()) < sa.NEG / 2 and float(got[0].max()) < sa.NEG / 2
    assert float(got[2].min()) < 0 < float(got[2].max())


def test_index_scores_are_the_published_sum():
    q = jnp.asarray([[[1.0, 0.0], [0.0, 2.0]]])  # one query, two heads
    keys = jnp.asarray([[1.0, 1.0], [-1.0, 1.0], [1.0, -1.0]])
    w = jnp.asarray([[0.5, -2.0]])
    # head 0: relu(1, -1, 1) = (1, 0, 1); head 1: relu(2, 2, -2) = (2, 2, 0)
    want = [0.5 * 1 - 2 * 2, -2.0 * 2, 0.5]
    assert np.allclose(np.asarray(sa.index_scores(q, w, keys))[0], want)


@pytest.mark.parametrize("k", [1, 6, 33, 70, 200])
def test_the_mask_form_is_top_k_with_its_ties(k):
    s = jnp.round(jax.random.normal(jax.random.PRNGKey(0), (7, 90)) * 3) / 3
    s = s.at[3].set(0.25).at[:, 70:].set(sa.NEG)  # ties everywhere
    idx, live = sa.select(s, k)
    assert idx.shape == (7, min(k, 90))
    want = np.zeros(s.shape, bool)
    for b in range(7):
        want[b, np.asarray(idx[b])[np.asarray(live[b])]] = True
    got = np.asarray(sa.chosen_mask(s, k))
    assert (got == want).all() and got.sum(1).tolist() == [min(k, 70)] * 7
    # of a row of equal scores the LOWEST positions are taken
    assert got[3, : min(k, 70)].all()


def _dense_partials(q, entries, keep, value_dim, scale):
    """Masked absorbed attention over dense entries [B, S, width]."""
    s = jnp.einsum("bqhd,bsd->bqhs", q, entries) * scale
    s = jnp.where(keep[:, :, None, :], s, sa.NEG)
    m = s.max(-1)
    p = jnp.where(keep[:, :, None, :], jnp.exp(s - m[..., None]), 0.0)
    return jnp.einsum("bqhs,bsv->bqhv", p, entries[..., :value_dim]), m, p.sum(-1)


def _normalised(acc, m, l):
    return acc / jnp.maximum(l, 1e-30)[..., None]


def test_a_decode_step_reads_its_chosen_entries_and_nothing_else():
    B, H, width, vd, L, NB, BS, MB, K = 3, 5, 24, 16, 2, 16, 8, 4, 6
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    pool = _pool(ks[0], L, NB, BS, width)
    q = jax.random.normal(ks[1], (B, 1, H, width))
    tables = _tables(B, MB, NB, 1)
    idx = jnp.asarray([[0, 5, 9, 17, 30, 31], [3, 2, 1, 0, 0, 0], [8, 9, 10, 11, 12, 13]])
    live = jnp.asarray([[1] * 6, [1, 1, 1, 0, 0, 0], [0] * 6], bool)
    acc, m, l = sa.sparse_latent_partials(q, pool, jnp.int32(1), tables, idx, live, vd, 0.3)
    dense = pool[1][tables, 0].reshape(B, MB * BS, width)
    keep = np.zeros((B, 1, MB * BS), bool)
    for b in range(B):
        keep[b, 0, np.asarray(idx[b])[np.asarray(live[b])]] = True
    want = _dense_partials(q, dense, jnp.asarray(keep), vd, 0.3)
    assert float(jnp.abs(_normalised(acc, m, l)[:2] - _normalised(*want)[:2]).max()) < 2e-5
    assert float(jnp.abs(l[:2] * jnp.exp(m[:2]) - want[2][:2] * jnp.exp(want[1][:2])).max()) < 1e-4
    # a row that chose nothing: no mass, no value
    assert float(l[2].max()) == 0.0 and float(jnp.abs(acc[2]).max()) == 0.0
    # an entry that was NOT chosen moves nothing: change one, same result
    other = pool.at[1, tables[0, 0], 0, 1].add(100.0)
    again = sa.sparse_latent_partials(q, other, jnp.int32(1), tables, idx, live, vd, 0.3)
    assert float(jnp.abs(again[0] - acc).max()) == 0.0


#: a fill's masked prefix, by case: (chunk C, lengths, what the selection is)
MASKED_FILLS = {
    "C5": (5, [19, 0], "random"),
    "C300": (300, [19, 0], "random"),  # three query tiles of 128
    "C1024": (1024, [45, 19], "random"),  # the cell's chunk: eight tiles
    "prefix_0_and_a_dead_row": (70, [0, 0], "random"),
    "nothing_in_a_page": (9, [48, 33], "page_1_bare"),
    "shorter_than_k": (9, [21, 8], "all"),  # the mask is the length mask
    "siblings": (9, [29, 29, 29, 11], "random"),  # rows 0-2 share their pages
    "page_boundaries": (3, [7, 8, 9, 15, 16, 17, 47, 48], "random"),
}


@pytest.mark.parametrize("case", list(MASKED_FILLS))
def test_a_fill_attends_its_prefix_under_the_mask_in_the_paged_kernel(case):
    """``paged_flash_attention(mask=)``, the latent mode under a SELECTION
    (Mosaic ``paged_mla_masked_fill``; tests/ops/test_tpu_compile.py holds
    the name), against ``reference_paged_partials(mask=)``, the one
    definition of the masked prefix outside the kernel, and that against
    dense masked attention written out here."""
    C, lens, kind = MASKED_FILLS[case]
    F, H, width, vd, L, NB, BS, MB = len(lens), 4, 128, 96, 2, 48, 8, 6
    ks = jax.random.split(jax.random.PRNGKey(C + F), 3)
    pool = _pool(ks[0], L, NB, BS, width)
    q = jax.random.normal(ks[1], (F, C, H, width))
    tables, lengths = _tables(F, MB, NB, 2), jnp.asarray(lens, jnp.int32)
    if case == "siblings":
        tables = tables.at[1:3].set(tables[0])
    pos = jnp.arange(MB * BS)
    held = pos < lengths[:, None, None]
    mask = jax.random.bernoulli(ks[2], 0.4, (F, C, MB * BS))
    if kind == "page_1_bare":
        mask &= pos // BS != 1
    elif kind == "all":
        mask |= True
    mask = mask.at[0, 0].set(False)  # a query that chose nothing cached
    # the mask is handed over as it is: what it says past a row's length
    # is the kernel's and the reference's to leave out
    got = pa.paged_flash_attention(
        q, pool, None, tables, lengths, layer=jnp.int32(1), interpret=True,
        scale=0.3, value_dim=vd, mask=mask,
    )
    want = pa.reference_paged_partials(
        q, pool[1], None, tables, lengths, scale=0.3, value_dim=vd, mask=mask
    )
    dense = pool[1][tables, 0].reshape(F, MB * BS, width)
    plain = _dense_partials(q, dense, mask & held, vd, 0.3)
    assert got[0].shape == (F, C, H, vd)
    live = np.asarray(plain[2]) > 0
    assert (np.asarray(got[2]) > 0).tolist() == live.tolist()
    assert (np.asarray(want[2]) > 0).tolist() == live.tolist()
    assert not live[0, 0].any() and live.any() == (max(lens) > 0)
    for other in (want, plain):
        a, b = np.asarray(_normalised(*got)), np.asarray(_normalised(*other))
        assert np.abs(a - b)[live].max(initial=0.0) < 5e-5
        mass = [np.asarray(x[2] * jnp.exp(x[1]))[live] for x in (got, other)]
        assert np.abs(mass[0] / mass[1] - 1).max(initial=0.0) < 5e-5
    assert float(jnp.abs(got[0][0, 0]).max()) == 0.0
    if kind == "all":  # the call without the operand, to the bit
        bare = pa.paged_flash_attention(
            q[:, 1:], pool, None, tables, lengths, layer=jnp.int32(1),
            interpret=True, scale=0.3, value_dim=vd,
        )
        for g, w in zip(got, bare):
            assert (np.asarray(g[:, 1:]) == np.asarray(w)).all()


#: a decode step's masked prefix, by case: (lengths a slot, the selection)
MASKED_DECODES = {
    "dead_rows_in_between": ([0, 19, 0, 0, 45, 8, 0, 33], "random"),
    "shorter_than_k": ([21, 8, 0, 3], "all"),  # the mask is the length mask
    "nothing_in_a_page": ([48, 0, 33, 17], "page_1_bare"),
    "siblings": ([29, 29, 0, 29, 11], "random"),  # rows 0, 1, 3 share pages
    "every_row_dead": ([0, 0, 0], "random"),
    "page_boundaries": ([7, 8, 9, 15, 16, 17, 47, 48], "random"),
}


@pytest.mark.parametrize("case", list(MASKED_DECODES))
def test_a_decode_step_attends_its_prefix_under_the_mask_in_the_paged_kernel(case):
    """``paged_flash_attention(mask=)`` at ONE query a row (Mosaic
    ``paged_mla_masked_decode``): the selection's block is one token's, the
    grid holds the live rows only and addresses the selection through the
    visiting order as it does ``q``; a stacked pool with a TRACED layer;
    against ``reference_paged_partials(mask=)`` and dense masked attention."""
    lens, kind = MASKED_DECODES[case]
    B, H, width, vd, L, NB, BS, MB = len(lens), 4, 128, 96, 2, 64, 8, 6
    ks = jax.random.split(jax.random.PRNGKey(B), 3)
    pool = _pool(ks[0], L, NB, BS, width)
    q = jax.random.normal(ks[1], (B, 1, H, width))
    tables, lengths = _tables(B, MB, NB, 6), jnp.asarray(lens, jnp.int32)
    if case == "siblings":
        tables = tables.at[1].set(tables[0]).at[3].set(tables[0])
    pos = jnp.arange(MB * BS)
    held = pos < lengths[:, None, None]
    mask = jax.random.bernoulli(ks[2], 0.4, (B, 1, MB * BS))
    if kind == "page_1_bare":
        mask &= pos // BS != 1
    elif kind == "all":
        mask |= True
    chose_nothing = int(np.argmax(lens))
    mask = mask.at[chose_nothing].set(False)  # a LIVE row that chose nothing cached

    @jax.jit
    def step(layer, mask):
        return pa.paged_flash_attention(
            q, pool, None, tables, lengths, layer=layer, interpret=True,
            scale=0.3, value_dim=vd, mask=mask,
        )

    got = step(jnp.int32(1), mask)
    want = pa.reference_paged_partials(
        q, pool[1], None, tables, lengths, scale=0.3, value_dim=vd, mask=mask
    )
    dense = pool[1][tables, 0].reshape(B, MB * BS, width)
    plain = _dense_partials(q, dense, mask & held, vd, 0.3)
    assert got[0].shape == (B, 1, H, vd)
    live = np.asarray(plain[2]) > 0
    assert (np.asarray(got[2]) > 0).tolist() == live.tolist()
    assert live.any(axis=(1, 2)).tolist() == [
        n > 0 and b != chose_nothing for b, n in enumerate(lens)
    ]
    for other in (want, plain):
        a, b = np.asarray(_normalised(*got)), np.asarray(_normalised(*other))
        assert np.abs(a - b)[live].max(initial=0.0) < 5e-5
        mass = [np.asarray(x[2] * jnp.exp(x[1]))[live] for x in (got, other)]
        assert np.abs(mass[0] / mass[1] - 1).max(initial=0.0) < 5e-5
    # a row without pages, and one that chose nothing: acc 0, l 0
    for b in np.flatnonzero(~live.any(axis=(1, 2))):
        assert float(jnp.abs(got[0][b]).max()) == 0.0 == float(got[2][b].max())
    if kind == "all":  # the call without the operand, to the bit
        bare = pa.paged_flash_attention(
            q, pool, None, tables, lengths, layer=jnp.int32(1),
            interpret=True, scale=0.3, value_dim=vd,
        )
        rest = np.arange(B) != chose_nothing
        for g, w in zip(got, bare):
            assert (np.asarray(g)[rest] == np.asarray(w)[rest]).all()
    # the other layer's pages are another answer: the layer is read
    if max(lens):
        again = step(jnp.int32(0), mask)
        assert float(jnp.abs(again[0] - got[0]).max()) > 1e-3


@pytest.mark.parametrize("N", [1, 31, 32, 33, 100])
def test_a_mask_packed_32_positions_to_a_word_reads_back_as_its_positions(N):
    mask = jax.random.bernoulli(jax.random.PRNGKey(N), 0.3, (3, 2, N))
    words = np.asarray(sa.packed_mask(mask))
    assert words.shape == (3, 2, -(-N // 32)) and words.dtype == np.uint32
    for b in range(3):
        for r in range(2):
            got = sa.positions_of_packed(words[b, r])
            assert got.dtype == np.int32
            assert got.tolist() == np.flatnonzero(np.asarray(mask[b, r])).tolist()


@pytest.mark.parametrize("form", ["packed", "positions"])
def test_a_kept_step_reads_as_positions_of_the_row_by_its_own_form(form):
    """What a decode program hands out of a step says its form itself:
    packed words are a mask over the table's 40 positions and then the
    chunk's own tokens (the first at the row's cached length, 23 here);
    int32 are the positions already."""
    if form == "positions":
        kept = np.asarray([3, 24, 7, -1, -1], np.int32)
        assert sa.kept_positions(kept, 40, 23, 5) is kept
        return
    mask = np.zeros(44, bool)
    mask[[3, 7, 22, 41, 43]] = True  # 41, 43: the chunk's second and fourth
    words = np.asarray(sa.packed_mask(jnp.asarray(mask)))
    got = sa.kept_positions(words, 40, 23, 6)
    assert got.dtype == np.int32 and got.tolist() == [3, 7, 22, 24, 26, -1]
    assert sa.row_positions(np.asarray([0, 39, 40]), 40, 23).tolist() == [0, 39, 23]
    with pytest.raises(AssertionError):  # neither form: refused, not read
        sa.kept_positions(words.astype(np.int64), 40, 23, 6)


def test_the_decode_path_follows_the_tables_ratio_to_the_chosen_set():
    k, r = 2048, sa.MASKED_DECODE_MAX_RATIO
    assert sa.decode_reads_masked(18432, k)  # the sparse cell's table: 9 x
    assert sa.decode_reads_masked(r * k, k) and not sa.decode_reads_masked(r * k + 512, k)
    assert not sa.decode_reads_masked(131072, k)  # a 128k table gathers


def test_the_timing_scripts_crossing_is_where_its_two_lines_meet():
    """``scripts/sparse_decode_paths.py`` (the constant's source, a chip
    run): the ratio at which the line through the masked path's times
    meets the gathering path's, and None where the masked path's does not
    rise faster."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(__file__), "..", "..", "scripts", "sparse_decode_paths.py"
    )
    spec = importlib.util.spec_from_file_location("sparse_decode_paths", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    rows = [
        {"ratio": r, "gathered_ms": 4.5 + 0.125 * r, "masked_ms": 0.5 + 0.375 * r}
        for r in (9.0, 18.0, 27.0)
    ]
    fit = script.crossing(rows)
    assert abs(fit["crossing_ratio"] - 16.0) < 1e-6
    assert abs(fit["masked_ms"]["per_ratio"] - 0.375) < 1e-9
    flat = [dict(r, masked_ms=1.0) for r in rows]
    assert script.crossing(flat)["crossing_ratio"] is None


def test_a_selection_rides_beside_key_and_value_pools_too():
    """The operand is the kernel's, not the latent mode's: K and V pools of
    two kv heads, three query heads each (a tile's row ``t * 3 + i``)."""
    B, Q, Hq, Hkv, hd, NB, BS, MB = 2, 11, 6, 2, 128, 16, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    kp = jax.random.normal(ks[0], (NB, Hkv, BS, hd))
    vp = jax.random.normal(ks[1], (NB, Hkv, BS, hd))
    q = jax.random.normal(ks[2], (B, Q, Hq, hd))
    tables, lengths = _tables(B, MB, NB, 4), jnp.asarray([27, 5], jnp.int32)
    mask = jax.random.bernoulli(ks[3], 0.5, (B, Q, MB * BS))
    got = pa.paged_flash_attention(q, kp, vp, tables, lengths, interpret=True, mask=mask)
    want = pa.reference_paged_partials(q, kp, vp, tables, lengths, mask=mask)
    live = np.asarray(want[2]) > 0
    a, b = np.asarray(_normalised(*got)), np.asarray(_normalised(*want))
    assert live.mean() > 0.9 and np.abs(a - b)[live].max() < 5e-5
    with pytest.raises(AssertionError, match="window"):
        pa.paged_flash_attention(
            q, kp, vp, tables, lengths, interpret=True, mask=mask, window=9
        )


@pytest.mark.parametrize("Q,shift", [(1, 0), (1, 3), (6, 0)])
def test_the_paged_kernels_latent_mode_under_the_window_is_its_reference(Q, shift):
    """Both static branches of ``paged_flash_attention`` at once: ONE
    stream of latent pages read as keys and (their first ``value_dim``
    columns) as values, from the page that holds the window's first
    position (tests/ops/test_tpu_compile.py holds the Mosaic call's name)."""
    B, H, width, vd, NB, BS, MB, W = 3, 4, 128, 96, 24, 8, 6, 11
    ks = jax.random.split(jax.random.PRNGKey(Q), 2)
    pool = jax.random.normal(ks[0], (2, NB, 1, BS, width), jnp.float32)
    q = jax.random.normal(ks[1], (B, Q, H, width))
    tables, lengths = _tables(B, MB, NB, 3), jnp.asarray([41, 7, 0], jnp.int32)
    got = pa.paged_flash_attention(
        q, pool, None, tables, lengths, layer=jnp.int32(1), interpret=True,
        scale=0.2, value_dim=vd, window=W, window_shift=jnp.int32(shift),
    )
    want = pa.reference_paged_partials(
        q, pool[1], None, tables, lengths, scale=0.2, value_dim=vd, window=W,
        window_shift=shift,
    )
    assert got[0].shape == (B, Q, H, vd)
    live = np.asarray(want[2]) > 0
    a, b = np.asarray(_normalised(*got)), np.asarray(_normalised(*want))
    assert np.abs(a - b)[live].max() < 2e-5
    assert (np.asarray(got[2]) > 0).tolist() == live.tolist()
    # row 0's first query reads [41 + shift - 10, 41): not the whole prefix
    whole = pa.reference_paged_partials(
        q, pool[1], None, tables, lengths, scale=0.2, value_dim=vd
    )
    assert np.abs(np.asarray(_normalised(*whole))[0] - b[0]).max() > 1e-3


#: rows by case: a few prompts' siblings whose fills ended together; mixed
#: depths with a third of the rows dead; page and tile boundaries; anything
NAN_AUDIT_ROWS = {"siblings": 10, "dead_rows": 11, "boundaries": 12, "any": 13}

#: the interpreter as the audits run it: what the kernel has not written
#: reads NaN, a copy lands when it is WAITED for, races are looked for
NAN_AUDIT = dict(
    uninitialized_memory="nan", detect_races=True, dma_execution_mode="on_wait"
)


def _nan_pool_but_for(rng, lens, L, layer, BS, MB, width, tile, window=None):
    """``(pool, tables)``: a pool of NaN in which each row of ``lens``
    cached positions holds numbers in the tiles the kernel copies for it
    and nowhere else (from its window's first page on, as far as the last
    tile that holds a cached position), and tables whose other columns
    name pages at random (stale ids: NaN pages)."""
    B = len(lens)
    NB = 3 * B + 1
    pool = np.full((L, NB, 1, BS, width), np.nan, np.float32)
    tables = rng.integers(0, NB, (B, MB)).astype(np.int32)
    free = list(rng.permutation(NB))
    for b, n in enumerate(lens):
        first = 0 if window is None else max(n - (window - 1), 0) // BS
        for c in range(first, -(-n // BS)):
            tables[b, c] = pid = free.pop()
            upto = min(BS, -(-(n - c * BS) // tile) * tile)
            pool[layer, pid, 0, :upto] = rng.standard_normal((upto, width)) * 0.3
    return pool, jnp.asarray(tables)


def _assert_finite_equal_and_no_race(got, want, live, case):
    for g, w, tol in zip(got, want, (5e-5, 1e-6, 5e-4)):
        g, w = np.asarray(g)[live], np.asarray(w)[live]
        assert np.isfinite(g).all(), (case, int((~np.isfinite(g)).sum()))
        assert np.abs(g - w).max() < tol, (case, float(np.abs(g - w).max()))
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    assert not interpret_pallas_call.races.races_found


@pytest.mark.parametrize("case", list(NAN_AUDIT_ROWS))
def test_the_windowed_latent_kernel_reads_only_what_it_copied(case):
    """``paged_mla_window_decode`` at the cell's tile plan (pages of 512
    tokens copied in tiles of 256, so a row's last page is copied IN
    PART) under Pallas' TPU interpreter with everything the kernel may
    not read made NaN: VMEM and SMEM it has not written, every page of
    the pool no row holds, every page before a row's window and past its
    length, every tile of a held page past the last that holds a cached
    position (stale page ids in the tables' unused columns point at such
    pages); a copy lands when it is WAITED for, not when it is started,
    and the interpreter looks for races between the copies and the
    kernel's own loads and stores.  (Within a copied tile the positions
    past the row's length must be finite: they meet probability 0 in the
    product, the kernel's contract with its pool.)  PR 49's chip runs saw
    rows go NaN with this call beside a Mosaic index call that has left
    the tree; this is the audit that the fault is not a read of bytes the
    kernel never copied.  Each case has a row count of its own: the
    interpreter does not run one compiled call twice in a process."""
    pltpu.reset_tpu_interpret_mode_state()
    B, H, width, vd, BS, MB, W, L = NAN_AUDIT_ROWS[case], 8, 640, 512, 512, 8, 513, 3
    tile = pa.page_tile((L, 3 * B + 1, 1, BS, width), jnp.bfloat16)
    assert tile == 256
    rng = np.random.default_rng(B)
    if case == "siblings":
        lens = rng.integers(1, MB * BS - 8, 3)[rng.integers(0, 3, B)]
    elif case == "dead_rows":
        lens = rng.integers(1, MB * BS - 8, B) * (rng.random(B) > 0.33)
    elif case == "boundaries":
        lens = np.asarray([0, 1, 255, 256, 257, 511, 512, 513, 768, 1024, 1025, 2048])
    else:
        lens = rng.integers(0, 3, B) * BS + rng.integers(0, BS, B)
    layer, shift = int(rng.integers(0, L)), int(rng.integers(0, 8))
    pool, tables = _nan_pool_but_for(
        rng, lens.tolist(), L, layer, BS, MB, width, tile, window=W
    )
    q = jnp.asarray(rng.standard_normal((B, 1, H, width)) * 0.3, jnp.bfloat16)
    lengths = jnp.asarray(lens, jnp.int32)
    got = pa.paged_flash_attention(
        q, jnp.asarray(pool, jnp.bfloat16), None, tables, lengths,
        layer=jnp.int32(layer), scale=0.05, value_dim=vd, window=W,
        window_shift=jnp.int32(shift),
        interpret=pltpu.InterpretParams(**NAN_AUDIT),
    )
    want = pa.reference_paged_partials(
        q, jnp.asarray(np.nan_to_num(pool[layer]), jnp.bfloat16), None, tables,
        lengths, scale=0.05, value_dim=vd, window=W, window_shift=shift,
    )
    live = np.asarray(lens) > 0
    assert live.sum() >= 6
    _assert_finite_equal_and_no_race(got, want, live, case)


def test_the_masked_latent_fill_reads_only_what_it_copied():
    """The same audit of ``paged_mla_masked_fill``: the cell's pages and
    tiles, a chunk of three query tiles (the last one padded), rows that
    end on and beside a page and a tile boundary, two siblings on the same
    pages, a dead row FIRST and one in the middle, a row that needs a
    second page step.  The selection is a block a step through the
    pipeline: the steps past a row's last page repeat its last block, a
    dead row reads its first, and what either holds past the row's length
    (here: everything chosen) is not attended.  One query chose nothing."""
    pltpu.reset_tpu_interpret_mode_state()
    H, width, vd, BS, MB, L, C = 8, 640, 512, 512, 8, 2, 150
    lens = [0, 1, 256, 257, 512, 513, 0, 1100, 2304, 2304]
    B = len(lens)
    tile = pa.page_tile((L, 3 * B + 1, 1, BS, width), jnp.bfloat16)
    assert tile == 256 and pa._plan_tiles(C, H, 1, BS, width, 2, False, MB, True)[:2] == (4, 64)
    rng = np.random.default_rng(B)
    pool, tables = _nan_pool_but_for(rng, lens, L, 1, BS, MB, width, tile)
    tables = tables.at[9].set(tables[8])
    q = jnp.asarray(rng.standard_normal((B, C, H, width)) * 0.3, jnp.bfloat16)
    mask = jnp.asarray(rng.random((B, C, MB * BS)) < 0.3)
    mask = mask.at[:, :, 2400:].set(True).at[7, 5].set(False)
    lengths = jnp.asarray(lens, jnp.int32)
    got = pa.paged_flash_attention(
        q, jnp.asarray(pool, jnp.bfloat16), None, tables, lengths,
        layer=jnp.int32(1), scale=0.05, value_dim=vd, mask=mask,
        interpret=pltpu.InterpretParams(**NAN_AUDIT),
    )
    want = pa.reference_paged_partials(
        q, jnp.asarray(np.nan_to_num(pool[1]), jnp.bfloat16), None, tables,
        lengths, scale=0.05, value_dim=vd, mask=mask,
    )
    live = np.asarray(want[2]) > 0
    assert not live[7, 5].any() and not live[[0, 6]].any() and live[8:].all()
    assert ((np.asarray(got[2]) > 0) == live).all()
    # who chose nothing cached has no mass and no value, not a NaN
    assert all(np.isfinite(np.asarray(g)).all() for g in got)
    assert float(jnp.abs(got[0][7, 5]).max()) == 0.0
    _assert_finite_equal_and_no_race(got, want, live, "masked_fill")
