"""The paged kernel's WINDOWED mode (``window=``): a query attends the last
``window - 1`` cached positions before it, the grid starts at the page
that holds the first of them, and pages wholly before it are neither
copied nor multiplied.  Against ``reference_paged_partials`` under the same
window (interpret mode on the CPU; a few shapes), and the page plan by the
functions the kernel itself calls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops.paged_attention import (
    PagePlan,
    WindowPagePlan,
    page_fetched,
    page_group,
    paged_flash_attention,
    plan_pages,
    reference_paged_partials,
    stream_page,
    window_first_pages,
    window_span_pages,
)

BS = 128


def _setup(lengths, Q=1, Hq=8, Hkv=4, MB=6, NB=32, hd=128, page=BS, seed=0,
           dtype=jnp.bfloat16):
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, Q, Hq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (NB, Hkv, page, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (NB, Hkv, page, hd), jnp.float32).astype(dtype)
    tables = jax.random.permutation(ks[3], NB)[: B * MB].reshape(B, MB)
    return q, k, v, tables.astype(jnp.int32), jnp.asarray(lengths, jnp.int32)


def _assert_same(got, want):
    (acc, m, l), (acc_r, m_r, l_r) = (tuple(map(np.asarray, t)) for t in (got, want))
    live = l_r > 0
    np.testing.assert_allclose(m[live], m_r[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l, l_r, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        acc[live] / l[live][..., None], acc_r[live] / l_r[live][..., None],
        rtol=3e-3, atol=3e-3,
    )
    # a query with no cached position inside its window: nothing summed
    assert (l[~live] == 0).all() and (acc[~live] == 0).all()


CASES = {
    # window 300 over rows of 700 / 384 / 50 / 0 cached positions: the first
    # position read is 401 (INSIDE page 3 of 128) and 85 (inside page 0);
    # the window is LONGER than row 2; row 3 is dead
    "inside_a_page": dict(lengths=[700, 384, 50, 0], window=300),
    # 512 - 257 + 1 = 256 and 385 - 257 + 1 = 129: ON the edge of page 2,
    # and one position past the edge of page 1; row 2 lies inside the window
    "on_a_page_edge": dict(lengths=[512, 385, 130], window=257),
    # 7 query heads a kv head (SmallThinker's grouping), unequal rows
    "seven_to_one": dict(lengths=[640, 129], window=200, Hq=14, Hkv=2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_decode_query_attends_its_window_and_nothing_before(case):
    c = dict(CASES[case])
    window = c.pop("window")
    q, k, v, tables, lens = _setup(**c)
    got = paged_flash_attention(q, k, v, tables, lens, interpret=True, window=window)
    _assert_same(got, reference_paged_partials(q, k, v, tables, lens, window=window))
    # ... and it is NOT the whole prefix where a row is longer than the window
    full = reference_paged_partials(q, k, v, tables, lens)
    assert float(jnp.abs(got[2] - full[2])[0].max()) > 1.0


def test_a_decode_chunks_later_steps_shift_the_window_over_one_plan():
    """Step ``i`` of a decode chunk stands ``i`` positions past the plan's
    lengths (``window_shift``): the plan made at the chunk's start serves
    every step, and a page that step 0 still read may be wholly masked."""
    q, k, v, tables, lens = _setup([700, 384, 259], Hq=8, Hkv=4)
    G = page_group(1, 8, k.shape, k.dtype, False, tables.shape[1])
    plan = plan_pages(tables, lens, BS, G, window=260, decode=True)
    for shift in (0, 5, 130):
        got = paged_flash_attention(
            q, k, v, tables, lens, interpret=True, plan=plan, window=260,
            window_shift=jnp.int32(shift),
        )
        want = reference_paged_partials(
            q, k, v, tables, lens, window=260, window_shift=shift
        )
        _assert_same(got, want)


def test_a_fill_chunks_queries_start_later_one_by_one():
    """Query ``t`` of a chunk stands at ``length + t``: its window starts
    ``t`` later than the first query's, and a query far enough into the
    chunk has no cached position left (300 queries of 2 heads a kv head are
    two query tiles)."""
    q, k, v, tables, lens = _setup([700, 300], Q=300, Hq=4, Hkv=2, seed=2)
    got = paged_flash_attention(q, k, v, tables, lens, interpret=True, window=129)
    want = reference_paged_partials(q, k, v, tables, lens, window=129)
    assert float(want[2][0, 127].min()) > 0 and float(want[2][0, 128].max()) == 0
    _assert_same(got, want)


def test_a_page_of_two_tiles_is_masked_from_the_windows_first_position():
    """Pages of 512 tokens copied in tiles of 256: the first page of a
    window is copied whole and masked by position, the last as far as it
    is filled."""
    q, k, v, tables, lens = _setup(
        [1300, 700], Hq=4, Hkv=2, MB=4, NB=8, page=512, seed=3
    )
    got = paged_flash_attention(q, k, v, tables, lens, interpret=True, window=400)
    _assert_same(got, reference_paged_partials(q, k, v, tables, lens, window=400))


def test_the_plan_starts_at_the_windows_first_page_and_is_as_long_as_a_window():
    lens = jnp.asarray([700, 384, 50, 0, 1024])
    assert list(np.asarray(window_first_pages(lens, BS, 300))) == [3, 0, 0, 0, 5]
    # 299 positions before a query touch at most 4 pages of 128
    assert window_span_pages(BS, 300) == 4
    assert window_span_pages(BS, 130) == 3 and window_span_pages(BS, 129) == 2
    tables = jnp.arange(5 * 8, dtype=jnp.int32).reshape(5, 8)
    plan = plan_pages(tables, lens, BS, 4, window=300)
    assert isinstance(plan, WindowPagePlan)
    assert isinstance(plan_pages(tables, lens, BS, 4), PagePlan)
    # rows by the pages their WINDOW holds (3, 3, 3, 1, dead last), not by
    # their lengths
    order = list(np.asarray(plan.order))
    assert order[-1] == 3 and order[-2] == 2 and set(order[:3]) == {0, 1, 4}
    assert list(np.asarray(plan.firsts)) == [
        [3, 0, 0, 0, 5][r] for r in order
    ]


def test_pages_wholly_before_the_window_are_never_copied():
    """The copies of a whole grid, by the kernel's own ``stream_page`` and
    ``page_fetched``: every page that holds a position of a row's window,
    once, as far as it is filled, and no page before it."""
    lens, window, group, MB = np.asarray([700, 384, 50, 0, 1024]), 300, 2, 8
    tables = np.arange(5 * MB, dtype=np.int32).reshape(5, MB) + 1
    plan = plan_pages(jnp.asarray(tables), jnp.asarray(lens), BS, group, window)
    ln, ids, order, firsts = (np.asarray(x) for x in plan[:4])
    steps = -(-window_span_pages(BS, window) // group)
    copied, before = {}, None
    for b in range(len(ln)):
        for j in range(steps):
            here = [
                stream_page(ln, ids, b, j, g, group, BS, BS, firsts)
                for g in range(group)
            ]
            for this, was in zip(here, before or here):
                if page_fetched(this, was, before is None):
                    copied.setdefault(int(order[b]), []).append(int(this[0]))
            before = here
    want = {}
    for r, n in enumerate(lens):
        first = max(n - window + 1, 0) // BS
        pages = [int(tables[r, c]) for c in range(first, -(-n // BS))]
        if pages:
            want[r] = pages
    assert copied == want


@pytest.mark.parametrize("page, window", [(512, 512), (256, 200), (256, 256)])
def test_a_window_no_longer_than_a_page_reads_two_pages_at_most(page, window):
    """A window of 512 over pages of 512 (and shorter ones over pages of
    256): the grid is two page steps whatever the row's length
    (``window_span_pages``), a query whose window lies inside ONE page
    reads that page alone, and one whose window straddles an edge reads
    both; 4 query heads a KV head of 128 (a differential pair's plan)."""
    assert window_span_pages(page, window) == 2
    lengths = [3 * page + 7, 2 * page, 2 * page + window - 1, page // 2, 0]
    q, k, v, tables, lens = _setup(
        lengths, Hq=8, Hkv=2, MB=5, NB=32, page=page, seed=4
    )
    got = paged_flash_attention(q, k, v, tables, lens, interpret=True, window=window)
    _assert_same(got, reference_paged_partials(q, k, v, tables, lens, window=window))
    # later steps of a decode chunk over the plan made at its start
    G = page_group(1, 8, k.shape, k.dtype, False, tables.shape[1])
    plan = plan_pages(tables, lens, page, G, window=window, decode=True)
    assert list(np.asarray(plan.firsts)[np.argsort(np.asarray(plan.order))]) == [
        max(n - (window - 1), 0) // page for n in lengths
    ]
    for shift in (1, 3):
        got = paged_flash_attention(
            q, k, v, tables, lens, interpret=True, plan=plan, window=window,
            window_shift=jnp.int32(shift),
        )
        want = reference_paged_partials(
            q, k, v, tables, lens, window=window, window_shift=shift
        )
        _assert_same(got, want)
