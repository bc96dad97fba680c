"""The host side of a device page pool (engine/kv_pages.py): ONE allocator
for the pool of whole-context pages (``window=None``) and for a window
pool, and the one table of what a cache kind rules out.  The engine's own
tests hold both pools to the reference through whole requests
(test_paged_pool.py, test_window_pages.py); these hold the allocator
alone."""

import re

import numpy as np
import pytest

from areal_tpu.engine.kv_pages import (
    BY_KIND,
    GONE,
    REFUSED,
    STATE_SLOTS,
    WINDOW_POOL,
    CacheKindRefuses,
    PagePool,
    StatefulModelUnsupported,
    refuse,
)

WINDOWS = pytest.mark.parametrize("window", [None, 12], ids=["whole", "window"])


def make_pool(window, n_blocks=6):
    return PagePool(
        n_blocks=n_blocks, page_size=8, max_batch=2, blocks_per_row=6,
        window=window,
    )


@WINDOWS
def test_alloc_is_lifo_over_what_was_freed(window):
    pool = make_pool(window)
    assert pool.alloc(3) == [0, 1, 2]
    assert pool.alloc(7) is None and pool.free_blocks == 3  # all or nothing
    b = pool.alloc(2)
    assert b == [3, 4] and pool.allocated_total == 5
    pool.free([1])
    pool.free([3, 0])
    # the last page freed is the first handed out again
    assert pool.alloc(4) == [0, 3, 1, 5]
    assert pool.free_blocks == 0 and pool.alloc(1) is None


@WINDOWS
def test_a_shared_page_goes_with_its_last_holder(window):
    pool = make_pool(window)
    a = pool.alloc(4)
    b = list(a[:3]) + pool.alloc(1)  # a sibling: three pages shared, a tail of its own
    pool.incref(b[:3])
    pool.set_row(0, a)
    pool.set_row(1, b)
    assert pool.free_blocks == 1 and pool.live([0, 1]) == 5 and pool.live([1]) == 4
    if window is not None:
        # at 29 cached tokens a holder keeps [17, 29): pages 2 on (17 // 8)
        assert (pool.first_read(29), pool.first_kept(29)) == (2, 2)
        assert (pool.first_read(28), pool.first_kept(28)) == (2, 2)
        assert (pool.first_read(27), pool.first_kept(27)) == (2, 1)
        assert pool.release_behind(a, 29) == 2 and a[:2] == [GONE, GONE]
        assert pool.free_blocks == 1  # the sibling still holds them
        assert pool.release_behind(b, 21) == 1 and pool.free_blocks == 2  # its last holder
        assert pool.release_behind(b, 29) == 1 and pool.free_blocks == 3
        pool.sync_row(0)
        assert list(pool.tables_np[0][:4]) == [0, 0, a[2], a[3]]
        assert pool.live([0, 1]) == 3 and pool.held(a) == 2
    pool.release_row(0)
    assert pool.free_blocks == (4 if window else 2)  # the tail of its own, and
    # what the sibling had let go of before
    pool.release_row(1)
    assert pool.free_blocks == 6 and (pool._ref == 0).all()
    assert pool.released_total == (4 if window else 0)
    assert pool.freed_behind_total == (2 if window else 0)
    assert not pool.tables_np.any() and pool.rows == [[], []]


@WINDOWS
def test_a_double_free_asserts(window):
    pool = make_pool(window)
    a = pool.alloc(2)
    pool.free(a)
    with pytest.raises(AssertionError, match="double free"):
        pool.free([a[0]])
    pool.free([GONE])  # a page already let go is nobody's to free


@WINDOWS
def test_the_uploaded_table_is_a_copy(window):
    pool = make_pool(window)
    first = pool.upload()
    assert pool.upload() is first  # nothing changed: nothing is sent
    pool.set_row(1, pool.alloc(3))
    sent = pool.upload()
    assert sent is not first and np.asarray(sent)[1].tolist() == [0, 1, 2, 0, 0, 0]
    # the allocator goes on writing the host table in place; what a
    # dispatched chunk was given must not follow
    pool.extend_row(1, pool.alloc(1))
    pool.tables_np[0, 0] = 5
    assert np.asarray(sent)[1].tolist() == [0, 1, 2, 0, 0, 0]
    assert np.asarray(sent)[0, 0] == 0
    assert np.asarray(pool.upload())[1].tolist() == [0, 1, 2, 3, 0, 0]


@WINDOWS
def test_nothing_is_behind_a_holder_without_a_window(window):
    pool = make_pool(window)
    row = pool.alloc(5)
    pool.set_row(0, row)
    pool.upload()
    went = pool.release_behind(row, 40, 0)
    if window is None:
        assert went == 0 and GONE not in row and not pool.dirty
        assert (pool.first_read(40), pool.first_kept(40)) == (0, 0)
        assert pool.released_total == 0 and pool.free_blocks == 1
    else:
        # [28, 40) is kept: pages 0-2 go, and the row's table follows
        assert went == 3 and row[:3] == [GONE] * 3 and pool.dirty
        assert pool.tables_np[0].tolist() == [0, 0, 0, row[3], row[4], 0]
        assert pool.released_total == 3 and pool.free_blocks == 4


def test_the_cache_holds_a_window_page_with_its_global_block():
    pool = make_pool(12)
    w = pool.alloc(3)
    pool.cache_pair(7, w[0])  # the cache does not hold block 7: no pair
    assert pool.cached == {}
    pool.cache_hold([7, 8, 7])
    pool.cache_pair(7, w[1])
    pool.cache_pair(8, w[2])
    pool.cache_pair(8, GONE)
    assert pool.cached == {7: w[1], 8: w[2]}
    # a prefix of 20 tokens over global blocks [6, 7, 8]: its fill reads
    # [9, 20), pages 1 on
    assert pool.cached_tail([6, 7, 8], 20) == [GONE, w[1], w[2]]
    assert pool.cached_tail([6, 7, 9], 20) is None
    pool.free(w)  # the rows let go: the cache's pairs stay
    assert pool.free_blocks == 4
    pool.cache_drop([7])
    assert pool.cached == {7: w[1], 8: w[2]}  # one reference of two
    pool.cache_drop([7, 8])
    assert pool.cached == {} and pool.cache_refs == {} and pool.free_blocks == 6


HELD = {
    STATE_SLOTS: "a model with recurrent state slots, a pool of whole-context pages",
    WINDOW_POOL: "a stack with window layers",
    BY_KIND: "a stack stated by kind ['latent']",
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_a_feature_is_refused_by_the_first_kind_held_that_rules_it_out(feature):
    refuse(feature, {})  # a model whose cache is one pool of pages: nothing
    for kind in HELD:
        held = {kind: HELD[kind]}
        if kind not in REFUSED[feature]:
            refuse(feature, held)
            continue
        with pytest.raises(CacheKindRefuses, match=re.escape(feature)) as e:
            refuse(feature, held)
        assert e.value.feature == feature and HELD[kind] in str(e.value)
        assert isinstance(e.value, StatefulModelUnsupported) == (kind == STATE_SLOTS)
    # every kind held: the state slots refuse first, whatever else would
    with pytest.raises(StatefulModelUnsupported, match="the state slots refuse it"):
        refuse(feature, HELD)
