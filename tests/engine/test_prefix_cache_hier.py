"""Hierarchical prefix cache: host-RAM spill tier correctness gates.

The host tier may only ever buy prefill FLOPs — never change tokens.
This file pins, on CPU:

* the spill/restore state machine of the radix index itself (fake
  spill_fetch): spill-on-evict releases device refs and counts host
  bytes; a match landing on spilled nodes reports them for restore and
  gates the restored blocks on a STEP (never a readiness probe);
  the byte budget trims LRU-first ACROSS tiers; re-inserting a spilled
  prefix repatriates it for free; dropping a resident node with spilled
  children drops the orphaned subtree; flush() empties BOTH tiers;
* engine-level spill -> match -> swap-in replay is token-identical to a
  fresh engine, with spills and restores demonstrably happening and
  zero block / host-byte leaks after flush;
* weight swaps invalidate the host tier too (stale KV across a swap
  stays impossible, host copies included);
* on a replay that overflows the HBM cache, the tier ON serves strictly
  more prompt tokens from cache and prefills strictly fewer than OFF.
"""

import numpy as np
import pytest

from areal_tpu.engine.prefix_cache import RadixPrefixCache

from tests.engine.test_prefix_cache import (
    _req,
    make_engine,
    replay_conversation,
    run_until_done,
)

# -- radix-index spill/restore unit tests -------------------------------------


class _Alloc:
    def __init__(self):
        self.refs = {}

    def acquire(self, blocks):
        for b in blocks:
            self.refs[b] = self.refs.get(b, 0) + 1

    def release(self, blocks):
        for b in blocks:
            self.refs[b] -= 1
            assert self.refs[b] >= 0, f"double free of {b}"


class _HostFetch:
    """Fake batched device->host gather: payload = the block id, so a
    restore's identity is checkable."""

    def __init__(self):
        self.calls = 0

    def __call__(self, blocks):
        self.calls += 1
        ids = np.asarray(blocks, np.int32)
        return ids.copy(), -ids.copy()


def _cache(page=4, capacity=64, host_blocks=8, min_match=1):
    a, f = _Alloc(), _HostFetch()
    c = RadixPrefixCache(
        page_size=page,
        capacity_blocks=capacity,
        acquire=a.acquire,
        release=a.release,
        min_match_tokens=min_match,
        host_bytes_budget=host_blocks * 100,
        block_bytes=100,
        spill_fetch=f,
    )
    return c, a, f


def test_spill_on_evict_releases_device_and_counts_host():
    c, a, f = _cache(page=4)
    c.insert(list(range(8)), [7, 8], step=1, version=0)
    assert c.blocks_held == 2 and a.refs == {7: 1, 8: 1}
    # one reclamation round spills both (leaf first, then its parent once
    # every child is spilled) in ONE batched fetch
    assert c.evict(2) == 2
    assert f.calls == 1
    assert a.refs == {7: 0, 8: 0}  # device refs released
    assert c.blocks_held == 0
    assert c.host_blocks_held == 2 and c.host_bytes_held == 200
    assert c.spilled_blocks_total == 2 and c.evictions_total == 0


def test_match_on_spilled_restores_with_step_gate():
    c, a, _ = _cache(page=4)
    c.insert(list(range(8)), [7, 8], step=1, version=0)
    c.evict(2)
    m = c.match(list(range(8)) + [99], step=5)
    # blocked match: nothing resident, both nodes reported for restore
    assert m.blocks == [] and m.n_tokens == 0 and not m.pending
    assert len(m.restore_nodes) == 2 and m.restore_tokens == 8
    payloads = c.begin_restore(m.restore_nodes)
    assert [int(k) for k, _ in payloads] == [7, 8]  # identity preserved
    c.complete_restore(m.restore_nodes, [11, 12], ready_step=6)
    assert c.host_blocks_held == 0 and c.host_bytes_held == 0
    assert c.blocks_held == 2 and c.restored_blocks_total == 2
    # still step 5: the swap-in is riding the ring — pending, no restart
    m = c.match(list(range(8)) + [99], step=5)
    assert m.pending and not m.restore_nodes and m.blocks == []
    # the ready step arrives: fully resident, new blocks served
    m = c.match(list(range(8)) + [99], step=6)
    assert m.blocks == [11, 12] and m.n_tokens == 8 and not m.pending


def test_host_budget_trims_lru_across_tiers():
    c, a, _ = _cache(page=2, host_blocks=2)
    for i, tok in enumerate((1, 3, 5)):
        c.insert([tok, tok + 1], [10 + i], step=1 + i, version=0)
    # spill the two oldest leaves: budget exactly full
    assert c.evict(2, protect_step=3) == 2
    assert c.host_blocks_held == 2 and c.host_dropped_blocks_total == 0
    # the third (newest) spill displaces the LRU spilled entry
    assert c.evict(1) == 1
    assert c.host_blocks_held == 2
    assert c.host_dropped_blocks_total == 1
    # the survivor set is the two NEWEST: (3,4) and (5,6); (1,2) died
    assert not c.match([1, 2, 9], step=9, record=False).restore_nodes
    assert c.match([3, 4, 9], step=9, record=False).restore_nodes
    assert c.match([5, 6, 9], step=9, record=False).restore_nodes


def test_insert_readopts_spilled_prefix_for_free():
    c, a, _ = _cache(page=4)
    c.insert(list(range(8)), [7, 8], step=1, version=0)
    c.evict(2)
    assert c.host_blocks_held == 2
    # the same prefix re-finishes on device: repatriated, host copy dies
    c.insert(list(range(8)), [21, 22], step=3, version=0)
    assert c.host_blocks_held == 0 and c.host_bytes_held == 0
    assert a.refs[21] == 1 and a.refs[22] == 1
    m = c.match(list(range(8)) + [99], step=4)
    assert m.blocks == [21, 22] and not m.restore_nodes


def test_dropping_resident_parent_drops_spilled_subtree():
    c, a, _ = _cache(page=2, host_blocks=1)
    c.insert([1, 2, 3, 4, 5, 6], [10, 11, 12], step=1, version=0)
    # two rounds: the leaf chain spills bottom-up until the budget (1
    # block) forces drops; eventually evicting the resident parent of a
    # spilled child must cascade the orphaned host entries away
    c.evict(3)
    assert c.blocks_held == 0
    assert c.host_blocks_held <= 1  # budget respected
    assert c.host_dropped_blocks_total >= 1  # orphans/trims were dropped
    assert all(v == 0 for v in a.refs.values())


def test_flush_empties_both_tiers():
    c, a, _ = _cache(page=4)
    c.insert(list(range(8)), [7, 8], step=1, version=0)
    c.insert([9, 9, 9, 9, 2, 2, 2, 2], [5, 6], step=2, version=0)
    c.evict(2, protect_step=2)  # spill the older chain
    assert c.host_blocks_held == 2 and c.blocks_held == 2
    c.flush(new_version=7)
    assert c.blocks_held == 0
    assert c.host_blocks_held == 0 and c.host_bytes_held == 0
    assert all(v == 0 for v in a.refs.values())
    assert c.version == 7
    st = c.stats()
    assert st["host_dropped_blocks_total"] >= 2
    # effective config is part of the stats surface (metrics RPC carries
    # it so a mis-tuned fleet is diagnosable at runtime)
    assert st["min_match_tokens"] == 1
    assert st["host_bytes_budget"] == 800
    assert set(RadixPrefixCache.zero_stats()) == set(st)


# -- engine-level gates -------------------------------------------------------


def _pressure_engine(**kw):
    """Tiny paged engine whose HBM cache overflows fast: 32-block pool,
    8-block cache cap, ample host tier."""
    defaults = dict(
        kv_pool_tokens=160,
        prefix_cache_capacity_frac=0.25,
        prefix_cache_host_bytes=1 << 24,
    )
    defaults.update(kw)
    eng, cfg, params = make_engine(**defaults)
    eng.park_ttl_steps = 0
    return eng, cfg, params


def _replay(eng, n_sessions=3, turns=2, seed=0, max_new=8, user_len=6):
    """Round-robin multi-session replay under FRESH qids; returns the
    per-(session, turn) greedy streams."""
    rng = np.random.default_rng(seed)
    convs = [list(rng.integers(6, 60, (24,))) for _ in range(n_sessions)]
    streams = {}
    for t in range(turns):
        for s in range(n_sessions):
            qid = f"s{s}t{t}"
            eng.submit(_req(qid, convs[s], max_new))
            run_until_done(eng, max_steps=3000)
            out = eng.drain_results()[qid]
            streams[(s, t)] = list(out.output_ids)
            convs[s] = (
                convs[s]
                + list(out.output_ids)
                + list(rng.integers(6, 60, (6,)))
            )
    return streams


def test_spill_restore_replay_parity_and_no_leak():
    """The tentpole gate: a working set that overflows the HBM cache
    spills to host and swaps back in, token-identical to a fresh engine
    with no pressure at all — and a final flush returns the pool AND the
    host tier to pristine."""
    eng, *_ = _pressure_engine()
    streams = _replay(eng)
    st = eng.prefix_cache_stats()
    assert st["spilled_blocks_total"] > 0, st
    assert st["restored_blocks_total"] > 0, st
    assert eng.host_spill_rounds_total > 0
    assert eng.host_restore_rounds_total > 0

    # parity: an unpressured engine with the host tier OFF emits the
    # exact same greedy streams
    ref, *_ = make_engine(kv_pool_tokens=2048)
    ref.park_ttl_steps = 0
    assert _replay(ref) == streams

    # no leaks: both tiers drain to zero and the pool is pristine
    eng.step()
    eng.step()  # TTL-evict parked rows
    eng._prefix_cache.flush()
    st = eng.prefix_cache_stats()
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()
    assert st["host_bytes_held"] == 0 and st["host_blocks_held"] == 0


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_host_bytes_accounting_exact(kv_dtype):
    """``host_bytes_held`` must be EXACT for both storage formats: the
    budget unit (``block_bytes``) derives from the pool arrays' actual
    itemsize — int8 data + f32 scales for quantized pools, model dtype
    otherwise — and equals the true nbytes of every spilled payload.
    An int8 pool's spilled block costs well under half the fp one."""
    eng, *_ = _pressure_engine(kv_cache_dtype=kv_dtype)
    _replay(eng, n_sessions=3, turns=1)
    cache = eng._prefix_cache
    # block_bytes comes from the allocated arrays, not assumed dtype
    assert cache.block_bytes == eng._pool_block_bytes()
    expected = sum(int(a.nbytes) for a in eng._pool_arrays()) // eng.n_blocks
    assert cache.block_bytes == expected
    # force everything cached out to the host tier
    cache.evict(eng.prefix_cache_stats()["blocks_held"])
    st = eng.prefix_cache_stats()
    assert st["host_blocks_held"] > 0
    assert (
        st["host_bytes_held"]
        == st["host_blocks_held"] * cache.block_bytes
    )
    # every spilled payload's true host nbytes == the accounted unit
    # (scales included on the int8 arm: 4 components, not 2)
    stack = list(cache._root.children.values())
    n_checked = 0
    while stack:
        node = stack.pop()
        stack.extend(node.children.values())
        if node.spilled and node.host_kv is not None:
            assert (
                sum(int(a.nbytes) for a in node.host_kv)
                == cache.block_bytes
            )
            assert len(node.host_kv) == (4 if kv_dtype == "int8" else 2)
            n_checked += 1
    assert n_checked > 0
    if kv_dtype == "int8":
        fp_eng, *_ = _pressure_engine()
        assert cache.block_bytes < fp_eng._pool_block_bytes() / 1.8
    # flush drains the byte account to exactly zero
    cache.flush()
    st = eng.prefix_cache_stats()
    assert st["host_bytes_held"] == 0 and st["host_blocks_held"] == 0


def test_weight_swap_flushes_host_tier():
    """No token may ever come from pre-swap KV — including KV parked in
    HOST memory: after update_weights both tiers are empty and the next
    turn matches a fresh engine on the new weights."""
    import jax

    from areal_tpu.models import transformer

    eng, cfg, _ = _pressure_engine()
    _replay(eng, n_sessions=3, turns=1)
    # force the working set out of HBM so the host tier holds KV
    eng._prefix_cache.evict(eng.prefix_cache_stats()["blocks_held"])
    assert eng.prefix_cache_stats()["host_blocks_held"] > 0

    params1 = transformer.init_params(cfg, jax.random.PRNGKey(42))
    eng.update_weights(params1, version=1)
    eng.step()
    st = eng.prefix_cache_stats()
    assert st["blocks_held"] == 0
    assert st["host_bytes_held"] == 0 and st["host_blocks_held"] == 0

    conv = list(np.random.default_rng(3).integers(6, 60, (20,)))
    eng.submit(_req("post-swap", conv, 8))
    run_until_done(eng)
    got = eng.drain_results()["post-swap"]
    fresh, *_ = make_engine(params=params1)
    fresh.submit(_req("fresh", conv, 8))
    run_until_done(fresh)
    assert got.output_ids == fresh.drain_results()["fresh"].output_ids


def test_host_tier_serves_more_from_cache_and_prefills_less():
    """What the host tier is for: on the same replay that overflows the
    HBM cache, the tier ON serves strictly more prompt tokens from cache
    and prefills strictly fewer than the tier OFF, token-identical."""
    on, *_ = _pressure_engine()
    off, *_ = _pressure_engine(prefix_cache_host_bytes=0)
    assert _replay(on) == _replay(off)
    assert off.prefix_cache_stats()["spilled_blocks_total"] == 0
    assert (
        on.prefix_cache_stats()["cached_tokens_total"]
        > off.prefix_cache_stats()["cached_tokens_total"]
    )
    assert on.prefill_tokens_total < off.prefill_tokens_total


# -- HBM ledger attribution of the host spill tier ----------------------------


def test_ledger_tracks_host_spill_and_close_after_flush_is_leak_free():
    """prefix_spill_host mirrors the cache's exact host_bytes_held
    through the spill/restore churn; a flushed engine closes with an
    empty leak audit, and an UNflushed spill tier is named by it."""
    from areal_tpu.observability.hbm_ledger import HbmLedger

    led = HbmLedger()
    eng, *_ = _pressure_engine(hbm_ledger=led)
    _replay(eng)
    st = eng.prefix_cache_stats()
    assert st["spilled_blocks_total"] > 0
    # the ledger tag tracks the cache's own byte account exactly
    assert led.snapshot()["prefix_spill_host"] == st["host_bytes_held"]

    if st["host_bytes_held"] > 0:
        # closing with spill resident is a reported leak (audit bites)
        leaked_bytes = st["host_bytes_held"]
        eng2_leak = eng.close()
        assert eng2_leak == {"prefix_spill_host": leaked_bytes}
    else:
        assert eng.close() == {}
    assert all(v == 0 for v in led.snapshot().values())

    # a second engine that FLUSHES before close audits clean
    led2 = HbmLedger()
    eng2, *_ = _pressure_engine(hbm_ledger=led2)
    _replay(eng2, n_sessions=2, turns=2)
    eng2.step()
    eng2.step()  # TTL-evict parked rows
    eng2._prefix_cache.flush()
    assert led2.snapshot()["prefix_spill_host"] == 0
    assert eng2.close() == {}
