"""Cross-request radix prefix cache: correctness gates.

The cache may only ever buy prefill FLOPs — never change tokens.  This
file pins, on CPU:

* multi-turn conversation replay parity: cache-on, cache-off, and dense
  engines emit identical greedy streams while the cache demonstrably
  serves cached tokens (the affordable-multi-turn contract of the
  reference's SGLang radix cache);
* refcount/eviction invariants: evicting a cached prefix pinned by a
  live row can never recycle its blocks (eviction drops only the
  cache's own reference); a full admit/evict/flush cycle leaks nothing;
* weight-swap invalidation: no token is ever produced from pre-swap KV
  (stale-KV reuse across an update_weights would be a silent
  correctness bug);
* the radix index itself: block-granularity matching, partial-tail
  copy-on-write matches (including divergence inside the tail block),
  deterministic LRU eviction, capacity trims, version-gated inserts.
"""

import zlib

import jax
import numpy as np
import pytest

from areal_tpu.api.model_api import (
    APIGenerateInput,
    GenerationHyperparameters,
)
from areal_tpu.engine.inference_server import ContinuousBatchingEngine
from areal_tpu.engine.prefix_cache import RadixPrefixCache
from areal_tpu.engine.sampling import SamplingParams
from areal_tpu.models import transformer
from areal_tpu.models.config import tiny_config

EOS = 5


# -- radix index unit tests ---------------------------------------------------


class _Alloc:
    """Counting allocator double: the cache only increfs/decrefs."""

    def __init__(self):
        self.refs = {}

    def acquire(self, blocks):
        for b in blocks:
            self.refs[b] = self.refs.get(b, 0) + 1

    def release(self, blocks):
        for b in blocks:
            self.refs[b] -= 1
            assert self.refs[b] >= 0, f"double free of {b}"


def _cache(page=4, capacity=64, min_match=1):
    a = _Alloc()
    c = RadixPrefixCache(
        page_size=page,
        capacity_blocks=capacity,
        acquire=a.acquire,
        release=a.release,
        min_match_tokens=min_match,
    )
    return c, a


def test_match_full_blocks_and_cap():
    c, a = _cache(page=4)
    # 10 tokens over blocks [7, 8, 9]: two full + tail of 2
    c.insert(list(range(10)), [7, 8, 9], step=1, version=0)
    assert c.blocks_held == 3 and a.refs == {7: 1, 8: 1, 9: 1}
    m = c.match(list(range(10)) + [99], step=2)
    assert m.blocks == [7, 8] and m.tail_block == 9 and m.tail_tokens == 2
    assert m.n_tokens == 10
    # the match is capped at len(tokens)-1: at least one suffix token
    # must remain to prefill (its logits seed the first sampled token)
    m = c.match(list(range(8)), step=3)
    assert m.blocks == [7] and m.n_tokens == 4 + 3
    assert m.tail_block == 8 and m.tail_tokens == 3  # prefix of block 2
    m = c.match(list(range(4)), step=4)
    assert m.blocks == [] and m.tail_block == 7  # tail-of-node-0 style hit
    assert m.n_tokens == 3


def test_tail_divergence_matches_longest_common_prefix():
    c, _ = _cache(page=4)
    c.insert([1, 2, 3, 4, 9, 8], [5, 6], step=1, version=0)  # tail (9, 8)
    m = c.match([1, 2, 3, 4, 9, 7, 7, 7], step=2)
    # diverges inside the tail: only the common (9,) counts, COW makes
    # the overwrite of the divergent positions safe
    assert m.blocks == [5] and m.tail_block == 6 and m.tail_tokens == 1
    m = c.match([1, 2, 3, 4, 7, 7], step=3)
    assert m.tail_block is None and m.n_tokens == 4


def test_mismatch_and_min_match():
    c, _ = _cache(page=4, min_match=5)
    c.insert(list(range(8)), [1, 2], step=1, version=0)
    assert c.match([9, 9, 9, 9, 9, 9], step=2).n_tokens == 0
    # a 4-token match exists but is below the floor
    m = c.match(list(range(4)) + [77, 77], step=3)
    assert m.n_tokens == 0 and m.blocks == []
    assert c.misses_total == 2 and c.hits_total == 0
    # 8 cached tokens clear the floor
    m = c.match(list(range(8)) + [77], step=4)
    assert m.n_tokens == 8 and c.hits_total == 1


def test_lru_eviction_is_deterministic_and_leaf_first():
    c, a = _cache(page=2)
    c.insert([1, 2, 3, 4], [10, 11], step=1, version=0)  # chain 10 -> 11
    c.insert([5, 6], [12], step=2, version=0)
    # touch the deep chain so the lone (5,6) leaf is oldest
    c.match([1, 2, 3, 4, 9], step=3)
    assert c.evict(1) == 1
    assert a.refs[12] == 0  # LRU leaf went first
    # the chain evicts leaf-first (11 before 10): interior nodes must
    # not orphan their children
    assert c.evict(1) == 1 and a.refs[11] == 0 and a.refs[10] == 1
    assert c.evict(1) == 1 and a.refs[10] == 0
    assert c.evict(1) == 0  # empty


def test_concurrent_subpage_sessions_keep_distinct_tails():
    """Sub-``page_size`` conversations are ALL tail: one slot per node
    would let interleaved sessions thrash each other out (every insert
    replacing the other's), so tails coexist per first token up to
    TAILS_PER_NODE and each session keeps hitting."""
    c, a = _cache(page=16)
    s1, s2 = [1, 1, 1, 1, 1], [2, 2, 2, 2, 2]
    c.insert(s1, [10], step=1, version=0)
    c.insert(s2, [11], step=2, version=0)  # must NOT evict session 1
    assert c.blocks_held == 2
    m = c.match(s1 + [1, 1], step=3)
    assert m.tail_block == 10 and m.tail_tokens == 5
    m = c.match(s2 + [2, 2], step=4)
    assert m.tail_block == 11 and m.tail_tokens == 5
    # a LONGER donor with the same first token still replaces in place
    c.insert(s1 + [1, 1], [12], step=5, version=0)
    assert c.blocks_held == 2 and a.refs[10] == 0 and a.refs[12] == 1
    # the per-node tail set is bounded: a 5th distinct first token drops
    # the LRU tail (session 2, untouched since step 4)
    for i, tok in enumerate((3, 4, 5)):
        c.insert([tok] * 5, [20 + i], step=6 + i, version=0)
    assert c.blocks_held == 4
    assert a.refs[11] == 0  # LRU tail dropped, sessions 1/3/4/5 resident


def test_full_block_insert_subsumes_stale_tail():
    """A row's tail block later fills up and re-inserts as a FULL block:
    the stale tail entry must be dropped, or blocks_held double-counts
    the physical block and the dead entry squats in a tail slot."""
    c, a = _cache(page=4)
    c.insert([1, 2, 3], [7], step=1, version=0)  # partial: tail (1,2,3)
    assert c.blocks_held == 1 and a.refs[7] == 1
    # same sequence grew past the page boundary: block 7 is now full
    c.insert([1, 2, 3, 4, 9], [7, 8], step=2, version=0)
    assert c.blocks_held == 2  # node(7) + tail(8) — NOT 3
    assert a.refs == {7: 1, 8: 1}
    m = c.match([1, 2, 3, 4, 9, 9], step=3)
    assert m.blocks == [7] and m.tail_block == 8 and m.n_tokens == 5
    c.flush()
    assert a.refs == {7: 0, 8: 0}


def test_capacity_trim_and_version_gate():
    c, a = _cache(page=2, capacity=2)
    c.insert([1, 2, 3, 4], [10, 11], step=1, version=0)
    assert c.blocks_held == 2
    # over capacity: the OLD entries are trimmed, never this insert's
    c.insert([7, 8], [12], step=2, version=0)
    assert c.blocks_held <= 2 and a.refs[12] == 1
    # stale-version inserts are dropped (weight swap raced the caller)
    c.flush(new_version=3)
    assert c.blocks_held == 0
    assert c.insert([1, 2], [13], step=3, version=0) == 0
    assert c.insert([1, 2], [13], step=3, version=3) == 1


# -- engine-level gates -------------------------------------------------------


def make_engine(params=None, **kw):
    cfg = tiny_config(vocab_size=64, max_position_embeddings=512)
    if params is None:
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_batch=4,
        kv_cache_len=256,
        chunk_size=4,
        sampling=SamplingParams(greedy=True),
        stop_tokens=(EOS,),
        cache_mode="paged",
        page_size=8,
        prefill_chunk_tokens=16,
    )
    defaults.update(kw)
    return ContinuousBatchingEngine(cfg, params, **defaults), cfg, params


def run_until_done(eng, max_steps=800):
    for _ in range(max_steps):
        if not eng.has_work:
            return
        eng.step()
    raise AssertionError("engine did not drain")


def _req(qid, prompt, max_new):
    return APIGenerateInput(
        qid=qid, prompt_ids=prompt, input_ids=prompt,
        gconfig=GenerationHyperparameters(max_new_tokens=max_new, greedy=True),
    )


def replay_conversation(eng, tag, n_turns=3, user_len=9, max_new=7):
    """Multi-turn agent loop shape: every turn re-sends the WHOLE growing
    conversation under a FRESH qid ('{tag}@t{j}'), exactly how the
    multi-turn agent + partial-rollout client behave — same-qid parking
    cannot mask the cross-request cache here."""
    rng = np.random.default_rng(zlib.crc32(tag.encode()))
    conv = list(rng.integers(6, 60, (user_len,)))
    streams = []
    for j in range(n_turns):
        qid = f"{tag}@t{j}"
        eng.submit(_req(qid, conv, max_new))
        run_until_done(eng)
        out = eng.wait_result(qid, timeout=10)
        streams.append(list(out.output_ids))
        conv = conv + list(out.output_ids) + list(
            rng.integers(6, 60, (user_len,))
        )
    return streams


def test_multi_turn_replay_parity_on_off_dense():
    streams = {}
    for name, kw in (
        ("paged_on", dict(prefix_cache=True)),
        ("paged_off", dict(prefix_cache=False)),
        ("dense", dict(cache_mode="dense")),
    ):
        eng, *_ = make_engine(**kw)
        streams[name] = replay_conversation(eng, "conv")
        if name == "paged_on":
            stats = eng.prefix_cache_stats()
            # the cache actually served tokens (turns 2..n hit)
            assert stats["hits_total"] >= 2, stats
            assert stats["cached_tokens_total"] > 0, stats
            on_prefill = eng.prefill_tokens_total
        if name == "paged_off":
            assert eng.prefix_cache_stats()["hits_total"] == 0
            off_prefill = eng.prefill_tokens_total
    assert streams["paged_on"] == streams["paged_off"] == streams["dense"]
    # the whole point: strictly less prefill work with the cache on
    assert on_prefill < off_prefill


def test_retried_request_prefills_only_suffix():
    eng, *_ = make_engine()
    eng.park_ttl_steps = 0
    prompt = list(np.arange(20) % 40 + 6)
    eng.submit(_req("r0", prompt, 6))
    run_until_done(eng)
    first = eng.wait_result("r0", timeout=10)
    eng.step()  # TTL-evict the parked row: only the CACHE can help now
    base = eng.prefill_tokens_total
    eng.submit(_req("r0-retry", prompt, 6))
    run_until_done(eng)
    retry = eng.wait_result("r0-retry", timeout=10)
    assert retry.output_ids == first.output_ids
    # 20-token prompt, page 8: blocks 0-1 cached + tail prefix of block 2
    # via COW — the retry prefilled strictly less than the full prompt
    assert eng.prefill_tokens_total - base < len(prompt)
    assert eng.prefix_cache_stats()["hits_total"] >= 1


def test_evicting_pinned_prefix_is_impossible():
    """Cache eviction drops only the cache's own reference: a prefix a
    live row pinned keeps its blocks out of the free pool, and the row's
    tokens stay exact."""
    eng, *_ = make_engine()
    prompt = list(np.arange(17) % 40 + 6)
    eng.submit(_req("a", prompt, 8))
    run_until_done(eng)
    ref = eng.wait_result("a", timeout=10)

    conv = prompt + list(ref.output_ids) + [7, 8, 9]
    eng.submit(_req("b", conv, 12))
    # admit so the match pins cached blocks, then gut the cache mid-run
    eng.step()
    assert eng.prefix_cache_stats()["hits_total"] >= 1
    pinned = [
        b for r in range(eng.max_batch) for b in eng._pages.rows[r]
    ]
    while eng._prefix_cache.evict(1):
        pass
    assert eng.prefix_cache_stats()["blocks_held"] == 0
    # the live row's blocks survived every eviction
    for b in pinned:
        assert eng._pages._ref[b] >= 1
        assert b not in eng._pages._free
    run_until_done(eng)
    got = eng.wait_result("b", timeout=10)

    fresh, *_ = make_engine(prefix_cache=False)
    fresh.submit(_req("b2", conv, 12))
    run_until_done(fresh)
    assert got.output_ids == fresh.wait_result("b2", timeout=10).output_ids


def test_pool_pressure_evicts_cache_before_live_rows_and_never_leaks():
    """A pool too small for cache + live rows: the cache yields first
    (recompute insurance), every request completes exactly, and a final
    flush returns the pool to pristine — no block leaks across the full
    admit/evict cycle."""
    eng, cfg, params = make_engine(
        max_batch=4,
        kv_cache_len=128,
        kv_pool_tokens=160,  # 20 blocks of 8: pressure guaranteed
        page_size=8,
    )
    eng.park_ttl_steps = 0
    prompts = [list(np.arange(20) % 40 + 6 + i) for i in range(4)]
    for rep in range(2):  # second wave hits the first wave's cache
        for i, p in enumerate(prompts):
            eng.submit(_req(f"w{rep}-{i}", p, 16))
        run_until_done(eng, max_steps=2000)
    outs = eng.drain_results()
    assert len(outs) == 8
    # same-prompt waves decode identically whatever got evicted when
    for i in range(4):
        assert (
            outs[f"w0-{i}"].output_ids == outs[f"w1-{i}"].output_ids
        ), i
    assert eng.prefix_cache_stats()["evictions_total"] > 0
    eng.step()
    eng.step()  # TTL-evict parked rows
    eng._prefix_cache.flush()
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()


def test_weight_swap_invalidates_cache():
    """No token may ever come from pre-swap KV: after update_weights the
    next turn must match a FRESH engine running the new weights, and the
    cache must have been flushed."""
    eng, cfg, params0 = make_engine()
    streams = replay_conversation(eng, "swap", n_turns=1)
    conv_rng = np.random.default_rng(zlib.crc32(b"swap"))
    conv = list(conv_rng.integers(6, 60, (9,)))
    conv = conv + streams[0] + list(conv_rng.integers(6, 60, (9,)))

    assert eng.prefix_cache_stats()["blocks_held"] > 0
    params1 = transformer.init_params(cfg, jax.random.PRNGKey(42))
    eng.update_weights(params1, version=1)
    eng.step()  # swap applies between chunks
    assert eng.prefix_cache_stats()["flushes_total"] == 1
    assert eng.prefix_cache_stats()["blocks_held"] == 0

    eng.submit(_req("swap@t1", conv, 8))
    run_until_done(eng)
    got = eng.wait_result("swap@t1", timeout=10)

    fresh, *_ = make_engine(params=params1)
    fresh.submit(_req("f@t1", conv, 8))
    run_until_done(fresh)
    assert got.output_ids == fresh.wait_result("f@t1", timeout=10).output_ids

    # post-swap repopulation serves the NEW weights' KV: a further turn
    # hits the cache and still matches the fresh-engine stream
    conv2 = conv + list(got.output_ids) + [11, 12, 13]
    base_hits = eng.prefix_cache_stats()["hits_total"]
    eng.submit(_req("swap@t2", conv2, 8))
    run_until_done(eng)
    got2 = eng.wait_result("swap@t2", timeout=10)
    assert eng.prefix_cache_stats()["hits_total"] > base_hits
    fresh.submit(_req("f@t2", conv2, 8))
    run_until_done(fresh)
    assert (
        got2.output_ids == fresh.wait_result("f@t2", timeout=10).output_ids
    )


def test_group_fill_sharing_unchanged_with_cache_on():
    """The in-flight group dedup (n targets, one fill) still fires with
    the cache enabled; the cache adds cross-REQUEST reuse on top."""
    eng, *_ = make_engine()
    prompt = list(np.arange(33) % 50 + 6)
    for i in range(4):
        eng.submit(_req(f"g-{i}", prompt, 4))
    eng._admit_paged()
    assert len(eng._filling) == 1 and len(eng._filling[0].targets) == 4
    run_until_done(eng)
    eng.drain_results()
    assert eng.prefill_tokens_total == len(prompt)


def test_dense_mode_has_no_cache():
    eng, *_ = make_engine(cache_mode="dense")
    assert eng._prefix_cache is None
    stats = eng.prefix_cache_stats()
    assert stats["hits_total"] == 0 and stats["blocks_held"] == 0
