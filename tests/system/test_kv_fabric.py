"""Fleet-wide KV fabric: cross-server prefix pull correctness gates.

The fabric turns each server's radix prefix cache into a FLEET
resource: when the gserver manager's schedule response names a peer
owning a longer cached prefix for a session (``kv_source``), the
target engine pulls the prefix over the segment transport instead of
re-prefilling it.  The fabric may only ever buy prefill FLOPs — never
change tokens.  This file pins, on CPU, driving two in-process
engines exactly the way the generation-server worker drives the
export_prefix RPC + import_prefix_segment lockstep commands:

* **Parity**: a pulled-prefix decode is greedy token-identical to the
  local-hit decode on the owner AND to a fresh full re-prefill, on fp
  and int8(+scales) pools, with the pulled bytes landing bit-identical;
* **Fail-closed**: per-segment version skew, a weight swap racing the
  pull, a dead/empty owner, and a stalled stream (TTL) all release the
  partial blocks — ZERO leaked blocks on both sides — and the
  admission falls back to a plain re-prefill with the same stream;
* **Spilled tier**: a prefix the owner evicted to host RAM exports
  straight from the spill buffers (no device restore round-trip);
* **Thresholds**: a target already holding most of the prefix skips
  the RPC entirely (the hint is consumed, never looped on).
"""

import numpy as np
import pytest

from tests.engine.test_prefix_cache import (
    _req,
    make_engine,
    run_until_done,
)

PROMPT0 = list(np.arange(40) % 40 + 6)
EXTRA = [7, 9, 11, 13, 15, 17, 19, 21]


def _pump_pull(target, owner, fail=None, on_segment=None, max_steps=600):
    """Step the target to completion while servicing its pull intents
    from the owner — the worker's ``_pump_prefix_pulls`` in-process.
    ``fail(preq)`` replaces the owner RPC (dead-peer arms);
    ``on_segment(i, seg) -> bool`` may intercept a segment (return
    False to skip the default import)."""
    for _ in range(max_steps):
        if not target.has_work:
            return
        target.step()
        for preq in target.drain_prefix_pull_requests():
            if fail is not None:
                fail(preq)
                continue
            segs = owner.export_prefix(preq["qid"], preq["tokens"])
            if not segs:
                target.prefix_pull_failed(preq["qid"], "miss")
                continue
            for i, seg in enumerate(segs):
                if on_segment is not None and not on_segment(i, seg):
                    continue
                ok, _ = target.import_prefix_segment(seg)
                if not ok:
                    break
    raise AssertionError("target did not drain")


def _turn0(eng, qid="c@t0", max_new=8):
    eng.submit(_req(qid, PROMPT0, max_new))
    run_until_done(eng)
    return list(eng.wait_result(qid, timeout=10).output_ids)


def _submit_with_source(target, conv, qid="c@t1", max_new=8):
    target.submit(_req(qid, conv, max_new))
    with target._lock:
        target._pending[-1].metadata = {"kv_source": "OWNER"}


def _assert_pristine(eng):
    """Zero-leak gate: park-evict + cache flush returns the pool to
    fully free with every refcount at zero."""
    eng.step()
    eng.step()
    if eng._prefix_cache is not None:
        eng._prefix_cache.flush()
    assert eng.free_pool_blocks == eng.n_blocks
    assert (np.asarray(eng._pages._ref) == 0).all()


def _fabric_pair(params, **target_kw):
    owner, *_ = make_engine(params=params)
    target, *_ = make_engine(
        params=params, prefix_pull_min_tokens=8, **target_kw
    )
    owner.park_ttl_steps = 0
    target.park_ttl_steps = 0
    return owner, target


def test_peer_pull_parity_and_prefill_savings():
    """The tentpole gate (tier-1 smoke): the pulled-prefix decode is
    token-identical to the owner's local radix hit AND to a fresh full
    re-prefill, while the target demonstrably prefills only the
    un-pulled suffix."""
    uni, _, params = make_engine()
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    # local-hit reference: the same engine continues the conversation
    uni.submit(_req("c@t1", conv, 8))
    run_until_done(uni)
    ref_local = list(uni.wait_result("c@t1", timeout=10).output_ids)
    assert uni.prefix_cache_stats()["hits_total"] >= 1
    # fresh re-prefill reference
    fresh, *_ = make_engine(params=params)
    fresh.submit(_req("c@t1", conv, 8))
    run_until_done(fresh)
    ref_fresh = list(fresh.wait_result("c@t1", timeout=10).output_ids)
    assert ref_local == ref_fresh

    owner, target = _fabric_pair(params)
    assert _turn0(owner) == out0  # same weights: same warmup stream
    _submit_with_source(target, conv)
    _pump_pull(target, owner)
    got = list(target.wait_result("c@t1", timeout=10).output_ids)
    assert got == ref_local

    st = target.prefix_peer_stats()
    assert st["pulls_total"] == 1
    assert st["pull_bytes_total"] > 0
    assert st["pull_rejects"] == {}
    assert st["pending_pulls"] == 0  # settled record consumed
    # the whole point: the pulled prefix (>= 5 full pages of the
    # 40-token turn-0 prompt) never re-prefilled on the target
    assert target.prefill_tokens_total <= len(conv) - 40
    # ... at least twice less than the same turn costs without the fabric
    assert 2 * target.prefill_tokens_total <= fresh.prefill_tokens_total
    assert target.prefix_cache_stats()["hits_total"] >= 1
    _assert_pristine(target)
    _assert_pristine(owner)


def test_pull_bytes_bit_identical_through_import():
    """The pulled blocks' device bytes equal the exported segment
    payloads exactly (the shared gather/scatter helpers' bit-identity,
    asserted through the fabric path)."""
    from areal_tpu.models import paged

    uni, _, params = make_engine()
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    owner, target = _fabric_pair(params)
    _turn0(owner)
    segs = []

    def collect(i, seg):
        segs.append(seg)
        ok, reason = target.import_prefix_segment(seg)
        assert ok, reason
        return False

    _submit_with_source(target, conv)
    _pump_pull(target, owner, on_segment=collect)
    assert len(segs) >= 2  # 5 pulled pages at 16-token chunks
    m = target._prefix_cache.match(
        conv, step=target._step_seq, record=False
    )
    total = sum(s["n_blocks"] for s in segs)
    assert len(m.blocks) >= total  # pulled blocks all matched
    back = paged.gather_blocks_host(
        target.k_pool, target.v_pool, m.blocks[:total],
        k_scale=target.k_scale, v_scale=target.v_scale,
    )
    for c in range(len(back)):
        sent = np.concatenate(
            [np.asarray(s["payload"][c]) for s in segs]
        )
        np.testing.assert_array_equal(sent, np.asarray(back[c]))


def test_pull_segment_version_skew_fails_closed_zero_leak():
    """A segment stamped with a different weight version (the owner
    swapped mid-export) rejects, releases the partial blocks, and the
    admission re-prefills to the identical stream — zero leaks."""
    uni, _, params = make_engine()
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    fresh, *_ = make_engine(params=params)
    fresh.submit(_req("c@t1", conv, 8))
    run_until_done(fresh)
    ref = list(fresh.wait_result("c@t1", timeout=10).output_ids)

    owner, target = _fabric_pair(params)
    _turn0(owner)
    free0 = target.free_pool_blocks

    def skew_after_first(i, seg):
        if i == 0:
            ok, reason = target.import_prefix_segment(seg)
            assert ok, reason
            assert target.free_pool_blocks < free0  # seg-0 allocated
        elif i == 1:
            forged = dict(seg)
            forged["version"] = 99
            ok, reason = target.import_prefix_segment(forged)
            assert not ok and reason == "version", (ok, reason)
        # the real exporter stops pushing after a reject: drop the rest
        return False

    _submit_with_source(target, conv)
    _pump_pull(target, owner, on_segment=skew_after_first)
    got = list(target.wait_result("c@t1", timeout=10).output_ids)
    assert got == ref  # same stream, via the safe re-prefill path
    st = target.prefix_peer_stats()
    assert st["pulls_total"] == 0
    assert st["pull_rejects"].get("version") == 1
    assert st["pending_pulls"] == 0
    assert target.prefill_tokens_total >= len(conv) - 8  # re-prefilled
    _assert_pristine(target)


def test_pull_racing_weight_swap_fails_closed():
    """A weight swap landing on the TARGET mid-pull: the apply sweep
    fails the in-flight pull closed (reason=version), late segments
    bounce off the settled record, and the continuation re-prefills
    under the new weights — stale KV is never decoded."""
    uni, _, params = make_engine()
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    fresh, *_ = make_engine(params=params)
    fresh.submit(_req("c@t1", conv, 8))
    run_until_done(fresh)
    ref = list(fresh.wait_result("c@t1", timeout=10).output_ids)

    owner, target = _fabric_pair(params)
    _turn0(owner)

    def swap_after_first(i, seg):
        if i == 0:
            ok, reason = target.import_prefix_segment(seg)
            assert ok, reason
            # same tree, bumped version: the next step's apply sweep
            # must fail the in-flight pull closed
            target.update_weights(params, 1)
            target.step()
            assert (
                target.prefix_peer_pull_rejects.get("version") == 1
            )
            return False
        ok, reason = target.import_prefix_segment(seg)
        assert not ok, (ok, reason)  # settled record: late segment
        return False

    _submit_with_source(target, conv)
    _pump_pull(target, owner, on_segment=swap_after_first)
    got = list(target.wait_result("c@t1", timeout=10).output_ids)
    assert got == ref  # same weights tree -> same stream, re-prefilled
    assert target.prefix_peer_stats()["pulls_total"] == 0
    assert target.prefill_tokens_total >= len(conv) - 8
    _assert_pristine(target)


def test_pull_dead_owner_falls_back_to_plain_prefill():
    """The owner RPC dies (or it cached nothing): the lockstep failure
    command settles the pull and the very next admission re-prefills —
    no retry loop, no leak, same stream."""
    uni, _, params = make_engine()
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    fresh, *_ = make_engine(params=params)
    fresh.submit(_req("c@t1", conv, 8))
    run_until_done(fresh)
    ref = list(fresh.wait_result("c@t1", timeout=10).output_ids)

    owner, target = _fabric_pair(params)

    def dead(preq):
        target.prefix_pull_failed(preq["qid"], "rpc")

    _submit_with_source(target, conv)
    _pump_pull(target, owner, fail=dead)
    got = list(target.wait_result("c@t1", timeout=10).output_ids)
    assert got == ref
    st = target.prefix_peer_stats()
    assert st["pulls_total"] == 0
    assert st["pull_rejects"] == {"rpc": 1}
    assert st["pending_pulls"] == 0
    _assert_pristine(target)

    # an owner with an empty cache answers export_prefix with []: the
    # worker maps that to a "miss" failure — same fallback
    cold, target2 = _fabric_pair(params)
    _submit_with_source(target2, conv, qid="c@t1b")
    _pump_pull(target2, cold)  # export returns [] -> miss
    got2 = list(target2.wait_result("c@t1b", timeout=10).output_ids)
    assert got2 == ref
    assert target2.prefix_peer_stats()["pull_rejects"] == {"miss": 1}
    _assert_pristine(target2)


def test_pull_ttl_expires_stalled_stream_zero_leak():
    """Segments stop arriving mid-pull (sender died silently): the TTL
    sweep fails the pull closed (reason=expired), the pre-allocated
    blocks release, and the requeued admission re-prefills."""
    uni, _, params = make_engine()
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    fresh, *_ = make_engine(params=params)
    fresh.submit(_req("c@t1", conv, 8))
    run_until_done(fresh)
    ref = list(fresh.wait_result("c@t1", timeout=10).output_ids)

    owner, target = _fabric_pair(params)
    _turn0(owner)
    target.handoff_pending_ttl_steps = 3
    free0 = target.free_pool_blocks

    def only_seg0(i, seg):
        return i == 0  # the rest of the stream is lost

    _submit_with_source(target, conv)
    _pump_pull(target, owner, on_segment=only_seg0)
    got = list(target.wait_result("c@t1", timeout=10).output_ids)
    assert got == ref
    st = target.prefix_peer_stats()
    assert st["pulls_total"] == 0
    assert st["pull_rejects"].get("expired") == 1
    assert st["pending_pulls"] == 0
    assert target.free_pool_blocks >= free0 - len(conv) // 8 - 2
    _assert_pristine(target)


def test_pull_skipped_when_local_prefix_already_long():
    """A target already holding (most of) the prefix consumes the hint
    without the RPC: pulling would save less than a page — the radix
    hit serves it locally."""
    uni, _, params = make_engine()
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    owner, *_ = make_engine(params=params)
    # floor above the 16-token suffix the warmed target is missing:
    # pulling would save less than the RPC is worth
    target, *_ = make_engine(params=params, prefix_pull_min_tokens=32)
    owner.park_ttl_steps = target.park_ttl_steps = 0
    _turn0(owner)
    _turn0(target, qid="local@t0")  # target warmed the same turn 0
    _submit_with_source(target, conv)
    seen = []
    _pump_pull(target, owner, fail=lambda preq: seen.append(preq))
    got = list(target.wait_result("c@t1", timeout=10).output_ids)
    run_until_done(uni)
    assert seen == []  # below threshold: no pull intent ever queued
    st = target.prefix_peer_stats()
    assert st["pulls_total"] == 0 and st["pending_pulls"] == 0
    assert target.prefix_cache_stats()["hits_total"] >= 1
    uni.submit(_req("ref@t1", conv, 8))
    run_until_done(uni)
    assert got == list(uni.wait_result("ref@t1", timeout=10).output_ids)


def test_pull_from_spilled_tier():
    """A prefix the owner evicted to HOST RAM still exports: the spill
    payloads ship directly (the spill buffer already is the wire
    format) and the pulled decode stays token-identical."""
    uni, _, params = make_engine()
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    fresh, *_ = make_engine(params=params)
    fresh.submit(_req("c@t1", conv, 8))
    run_until_done(fresh)
    ref = list(fresh.wait_result("c@t1", timeout=10).output_ids)

    owner, *_ = make_engine(
        params=params, prefix_cache_host_bytes=1 << 24
    )
    owner.park_ttl_steps = 0
    _turn0(owner)
    owner.step()
    owner.step()  # TTL-evict the parked row
    owner._prefix_cache.evict(
        owner.prefix_cache_stats()["blocks_held"]
    )
    st = owner.prefix_cache_stats()
    assert st["host_blocks_held"] > 0  # the prefix lives on host now

    target, *_ = make_engine(params=params, prefix_pull_min_tokens=8)
    target.park_ttl_steps = 0
    _submit_with_source(target, conv)
    _pump_pull(target, owner)
    got = list(target.wait_result("c@t1", timeout=10).output_ids)
    assert got == ref
    tst = target.prefix_peer_stats()
    assert tst["pulls_total"] == 1 and tst["pull_rejects"] == {}
    assert target.prefill_tokens_total <= len(conv) - 40
    # the export served straight from host payloads: nothing restored
    # to the owner's device pool for the pull's sake
    assert owner.prefix_cache_stats()["restored_blocks_total"] == 0
    _assert_pristine(target)
    _assert_pristine(owner)


@pytest.mark.slow  # int8 arm: quant parity arms are slow-marked by policy
def test_peer_pull_int8_parity_and_bit_identity():
    """Int8(+scales) pools over the fabric: the pulled quantized bytes
    and scales land bit-identical (4 payload components, no requant)
    and the composite stream matches the int8 unified engine's."""
    from areal_tpu.models import paged

    uni, _, params = make_engine(kv_cache_dtype="int8")
    out0 = _turn0(uni)
    conv = PROMPT0 + out0 + EXTRA
    uni.submit(_req("c@t1", conv, 8))
    run_until_done(uni)
    ref = list(uni.wait_result("c@t1", timeout=10).output_ids)

    owner, *_ = make_engine(params=params, kv_cache_dtype="int8")
    target, *_ = make_engine(
        params=params, kv_cache_dtype="int8", prefix_pull_min_tokens=8
    )
    owner.park_ttl_steps = target.park_ttl_steps = 0
    _turn0(owner)
    segs = []

    def collect(i, seg):
        segs.append(seg)
        ok, reason = target.import_prefix_segment(seg)
        assert ok, reason
        return False

    _submit_with_source(target, conv)
    _pump_pull(target, owner, on_segment=collect)
    got = list(target.wait_result("c@t1", timeout=10).output_ids)
    assert got == ref
    assert target.prefix_peer_stats()["pulls_total"] == 1
    assert len(segs[0]["payload"]) == 4  # k, v, k_scale, v_scale
    m = target._prefix_cache.match(
        conv, step=target._step_seq, record=False
    )
    total = sum(s["n_blocks"] for s in segs)
    back = paged.gather_blocks_host(
        target.k_pool, target.v_pool, m.blocks[:total],
        k_scale=target.k_scale, v_scale=target.v_scale,
    )
    for c in range(len(back)):
        sent = np.concatenate(
            [np.asarray(s["payload"][c]) for s in segs]
        )
        np.testing.assert_array_equal(sent, np.asarray(back[c]))
    _assert_pristine(target)


@pytest.mark.slow  # fat arm: multi-session sweep over the fabric
def test_peer_pull_many_sessions_parity_and_zero_leak():
    """Session-migration replay at width: several conversations warmed
    on the owner all migrate to the target through pulls; every stream
    matches the fresh-engine reference and both pools end pristine."""
    _, _, params = make_engine()
    owner, target = _fabric_pair(params)
    fresh, *_ = make_engine(params=params)
    fresh.park_ttl_steps = 0
    rng = np.random.default_rng(7)
    refs, convs = {}, {}
    for s in range(3):
        conv0 = list(rng.integers(6, 60, (40,)))
        owner.submit(_req(f"m{s}@t0", conv0, 8))
        run_until_done(owner)
        out0 = list(owner.wait_result(f"m{s}@t0", timeout=10).output_ids)
        convs[s] = conv0 + out0 + list(rng.integers(6, 60, (8,)))
        fresh.submit(_req(f"m{s}@t1", convs[s], 8))
        run_until_done(fresh)
        refs[s] = list(fresh.wait_result(f"m{s}@t1", timeout=10).output_ids)
    for s in range(3):
        _submit_with_source(target, convs[s], qid=f"m{s}@t1")
    _pump_pull(target, owner, max_steps=2000)
    for s in range(3):
        got = list(target.wait_result(f"m{s}@t1", timeout=10).output_ids)
        assert got == refs[s], s
    st = target.prefix_peer_stats()
    assert st["pulls_total"] == 3 and st["pending_pulls"] == 0
    _assert_pristine(target)
    _assert_pristine(owner)
