"""Disaggregated prefill/decode serving gates (ISSUE 13 + the ISSUE-15
streamed handoff & load-aware admission, ROADMAP item 2).

What this file pins, on CPU:

* **Routing**: role-aware two-stage scheduling at the gserver manager —
  a new request in a P/D fleet routes to a prefill server with
  ``handoff_to`` naming the decode owner; continuations sticky-route to
  the decode server; sticky/token/affinity state never lands on a
  prefill server; unified fleets are byte-for-byte unaffected.
* **Load-aware prefill admission**: the prefill pick is least-backlog-
  per-chip over the scraped ``prefill_backlog_tokens`` signal (plus
  optimistic local increments), a saturated pool SHEDS to unified-style
  serving on the decode owner, and the engine-side backlog accounting
  decrements on fill completion AND on failed/evicted rows.
* **Handoff mechanics**: the engine's export/import halves are greedy
  TOKEN-IDENTICAL to the unified engine on the same workload, the
  decode side resumes with ZERO prefill, and the payload round-trips
  bit-identically (int8 pools: quantized bytes + scales, no requant).
* **Streamed handoff**: segments export at fill-chunk boundaries and
  scatter on the decode side while the prompt still fills; the
  composite stream is token-identical; per-segment version skew,
  exporter aborts, and dead peers (TTL) all fail closed with ZERO
  leaked blocks on both sides.
* **Fail-closed**: a handoff racing a weight swap — the swap landing
  either before the import (version-skew reject) or after it (parked-
  row eviction) — NEVER decodes stale KV; the continuation re-prefills
  and the stream stays correct.
* **Worker RPC path**: a real 1P+1D fleet (GenerationServerWorker x2 +
  GserverManager + PartialRolloutManager client) serves a chunked
  generation end to end through schedule -> prefill ->
  import_handoff_segment RPC stream -> resume, token-identical to a
  direct unified engine.
* **Mixed load**: short and long prompts in flight together on the
  prefill engine all hand off (monolithic and streamed), every handoff
  lands, and every stream equals the unified engine's.
"""

import threading

import numpy as np
import pytest

from tests.engine.test_prefix_cache import (
    _req,
    make_engine,
    run_until_done,
)
from tests.system.test_gserver_manager_unit import _manager

PROMPT = list(np.arange(24) % 40 + 6)


# -- two-stage routing at the manager -----------------------------------------


def _pd_manager(**kw):
    """Hand-built role-aware manager: s0 = prefill, s1/s2 = decode."""
    m = _manager(**kw)
    m._server_role = {"s0": "prefill", "s1": "decode", "s2": "decode"}
    m._prefill_addrs = ["s0"]
    m._decode_addrs = ["s1", "s2"]
    m._pd_enabled = True
    m._group_prefill = {}
    m._pd_rr = 0
    return m


def test_two_stage_routing_new_request_and_sticky_continuation():
    m = _pd_manager(policy="least_token_usage")
    r = m._schedule_request("q1-0", prompt_len=100, new_token_budget=50)
    assert r["url"] == "s0"  # new request: prefill stage first
    owner = r["handoff_to"]
    assert owner in ("s1", "s2")
    # the decode server OWNS the request: sticky + token accounting
    assert m._qid_server["q1-0"] == owner
    assert m._server_tokens["s0"] == 0.0
    assert m._server_tokens[owner] > 0.0
    # continuation: straight to the decode owner, no second handoff
    r2 = m._schedule_request("q1-0", prompt_len=120, new_token_budget=30)
    assert r2["url"] == owner and "handoff_to" not in r2


def test_group_members_share_prefill_server_and_decode_owner():
    """One rollout's members colocate at BOTH stages: the prefill server
    dedups the shared prompt fill, the decode owner shares the radix
    prefix."""
    m = _pd_manager(policy="round_robin")
    resps = [
        m._schedule_request(f"g1-{i}", prompt_len=64, new_token_budget=16)
        for i in range(4)
    ]
    assert {r["url"] for r in resps} == {"s0"}
    assert len({r["handoff_to"] for r in resps}) == 1
    m._finish_rollout("g1", accepted=True)
    assert "g1" not in m._group_prefill


def test_decode_pool_excludes_prefill_servers():
    """Sticky owners are always decode servers — across many rollouts,
    no request's resident state ever lands on the prefill server."""
    m = _pd_manager(policy="least_token_usage")
    for i in range(12):
        m._schedule_request(f"r{i}-0", prompt_len=32, new_token_budget=8)
    assert set(m._qid_server.values()) <= {"s1", "s2"}
    assert m._server_load["s0"] == 0


def test_unified_servers_excluded_from_pd_decode_pool():
    """A unified registration carries no single-process guarantee (it
    could be a multi-controller SPMD server that cannot import a
    handoff unit), so in a P/D fleet the decode-owner pool is decode-
    role servers ONLY — a unified bystander never becomes a handoff
    target."""
    m = _pd_manager(policy="least_token_usage")
    m._server_role["s2"] = "unified"
    m._decode_addrs = ["s1"]  # what _configure derives for this fleet
    for i in range(8):
        r = m._schedule_request(f"x{i}-0", prompt_len=32, new_token_budget=8)
        assert r["handoff_to"] == "s1", r
    assert m._server_load["s2"] == 0


def test_unified_fleet_unchanged_no_handoff_key():
    m = _manager(policy="least_requests")  # no roles registered
    r = m._schedule_request("u0-0", prompt_len=32, new_token_budget=8)
    assert "handoff_to" not in r
    assert r["url"] in m.server_addrs


def test_pd_routes_counter_increments_once_per_new_request():
    m = _pd_manager(policy="round_robin")
    base = m._m_pd_routes.value()
    m._schedule_request("c0-0", prompt_len=16, new_token_budget=4)
    m._schedule_request("c0-0", prompt_len=20, new_token_budget=4)  # sticky
    assert m._m_pd_routes.value() == base + 1


# -- load-aware prefill admission ---------------------------------------------


def _pd2_manager(**kw):
    """Two prefill servers (s0 1-chip, s3 2-chip) + one decode server."""
    m = _manager(**kw)
    m.server_addrs = ["s0", "s1", "s3"]
    m._server_role = {"s0": "prefill", "s1": "decode", "s3": "prefill"}
    m._server_devices = {"s0": 1, "s1": 1, "s3": 2}
    m._server_mesh = {a: "" for a in m.server_addrs}
    m._server_load = {a: 0 for a in m.server_addrs}
    m._server_tokens = {a: 0.0 for a in m.server_addrs}
    m._prefill_addrs = ["s0", "s3"]
    m._decode_addrs = ["s1"]
    m._pd_enabled = True
    m._group_prefill = {}
    m._pd_rr = 0
    return m


def test_prefill_pick_least_backlog_per_chip():
    """The pick is backlog PER CHIP: a 2-chip prefill mesh absorbs 2x
    the backlog of a 1-chip one before looking busier."""
    m = _pd2_manager(policy="least_token_usage")
    m._init_runtime_state()
    m._prefill_backlog.update({"s0": 1000.0, "s3": 1500.0})
    m._prefill_backlog_ts = 1e18  # freeze: no scrape (no clients)
    r = m._schedule_request("b0-0", prompt_len=64, new_token_budget=8)
    assert r["url"] == "s3", r  # 1500/2 = 750 < 1000/1
    # the routed prompt's tokens count immediately (optimistic local
    # increment), so a burst between scrapes spreads
    assert m._prefill_backlog_local["s3"] == 64.0


def test_prefill_local_increments_spread_a_burst():
    m = _pd2_manager(policy="least_token_usage")
    m._init_runtime_state()
    m._prefill_backlog_ts = 1e18
    picks = [
        m._schedule_request(f"b{i}-0", prompt_len=100, new_token_budget=4)[
            "url"
        ]
        for i in range(6)
    ]
    # zero scraped backlog everywhere: the local adds alone must route
    # ~1/3 of the prompts to the 1-chip server and ~2/3 to the 2-chip
    assert picks.count("s3") == 4 and picks.count("s0") == 2, picks


def test_prefill_saturation_sheds_to_decode_owner():
    """Every prefill server over the per-chip saturation bar: the
    request routes STRAIGHT to its decode owner (no handoff_to — it
    serves unified-style there) and the shed is counted."""
    m = _pd2_manager(
        policy="least_token_usage",
        prefill_saturation_tokens_per_chip=500,
    )
    m._init_runtime_state()
    m._prefill_backlog.update({"s0": 5000.0, "s3": 5000.0})
    m._prefill_backlog_ts = 1e18
    base = m._m_prefill_sheds.value()
    r = m._schedule_request("sh0-0", prompt_len=64, new_token_budget=8)
    assert r["url"] == "s1" and "handoff_to" not in r, r
    assert r.get("pd_shed") is True
    assert m._m_prefill_sheds.value() == base + 1
    # below the bar: two-stage routing resumes
    m._prefill_backlog.update({"s0": 100.0, "s3": 5000.0})
    r2 = m._schedule_request("sh1-0", prompt_len=64, new_token_budget=8)
    assert r2["url"] == "s0" and r2["handoff_to"] == "s1", r2


def test_prefill_rotation_restored_when_load_aware_off():
    m = _pd2_manager(
        policy="least_token_usage", prefill_load_aware=False
    )
    picks = [
        m._schedule_request(f"r{i}-0", prompt_len=32, new_token_budget=4)[
            "url"
        ]
        for i in range(3)
    ]
    # chip-weighted rotation: s0 once, s3 twice per cycle
    assert sorted(picks) == ["s0", "s3", "s3"], picks


def test_engine_prefill_backlog_accounting():
    """The engine-side backlog signal: rises on submit, falls as fills
    complete (handoff park included), and falls when a row FAILS
    (context-exhausted) — never a stale counter, because it is computed
    from the live fill/pending structures."""
    _, _, params = make_engine()
    P, *_ = make_engine(params=params)
    assert P.prefill_backlog_tokens() == 0
    P.submit(_req("bl0", PROMPT, 8))
    with P._lock:
        P._pending[-1].metadata = {"handoff_to": "D"}
    assert P.prefill_backlog_tokens() == len(PROMPT)  # queued
    run_until_done(P)  # fill + park + (monolithic) handoff wait
    assert P.prefill_backlog_tokens() == 0  # completed: decremented
    # a failed row (prompt too long for the cache) must ALSO decrement
    too_long = list(np.arange(300) % 40 + 6)
    P.submit(_req("bl1", too_long, 8))
    assert P.prefill_backlog_tokens() == len(too_long)
    run_until_done(P)
    out = P.wait_result("bl1", timeout=10)
    assert out.output_ids == []  # failed: no room
    assert P.prefill_backlog_tokens() == 0
    # an evicted mid-fill row: weight swap resets fills (backlog grows
    # back to the full prompt — honest accounting of the re-prefill),
    # then completion decrements again
    P2, *_ = make_engine(params=params)
    P2.submit(_req("bl2", PROMPT, 8))
    P2.update_weights(params, 1)
    run_until_done(P2)
    assert P2.prefill_backlog_tokens() == 0


# -- engine-level handoff: parity, zero-prefill resume, bit identity ----------


def _drive_disagg(P, D, prompt, max_new, qid="pd0", swap_before_import=None,
                  swap_after_import=None):
    """Run prefill-with-handoff on P, move the unit to D (exactly what
    the generation-server worker does before its client reply), then
    decode the continuation on D.  Optional weight swaps are injected at
    the named race points.  Returns (tokens, import_ok, reason)."""
    P.submit(_req(qid, prompt, max_new))
    # stamp the handoff flag the manager's schedule response carries
    with P._lock:
        P._pending[-1].metadata = {"handoff_to": "D"}
    run_until_done(P)
    first = P.wait_result(qid, timeout=10)
    assert len(first.output_ids) == 1 and first.no_eos
    unit = P.export_handoff(qid)
    assert unit is not None
    if swap_before_import is not None:
        D.update_weights(*swap_before_import)
        D.step()
    ok, reason = D.import_handoff(unit)
    if swap_after_import is not None:
        D.update_weights(*swap_after_import)
        D.step()
    cont = list(prompt) + list(first.output_ids)
    D.submit(_req(qid, cont, max_new - 1))
    run_until_done(D)
    rest = D.wait_result(qid, timeout=10)
    return list(first.output_ids) + list(rest.output_ids), ok, reason


def test_disagg_greedy_token_identical_to_unified():
    uni, _, params = make_engine()
    uni.submit(_req("pd0", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("pd0", timeout=10).output_ids)

    P, *_ = make_engine(params=params)
    D, *_ = make_engine(params=params)
    got, ok, _ = _drive_disagg(P, D, PROMPT, 10)
    assert ok
    assert got == ref
    # the whole point: ZERO suffix prefill on the decode side
    assert D.resumed_total == 1
    assert D.prefill_tokens_total == 0
    assert D.handoff_stats()["imports_total"] == 1
    assert P.handoff_stats()["exports_total"] == 1


def test_handoff_racing_weight_swap_fails_closed_before_import():
    """Swap lands on D between export and import: the unit's version no
    longer matches — the import is REJECTED (stale KV never decoded) and
    the continuation re-prefills, still token-correct."""
    uni, _, params = make_engine()
    uni.submit(_req("pd1", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("pd1", timeout=10).output_ids)

    P, *_ = make_engine(params=params)
    D, *_ = make_engine(params=params)
    got, ok, reason = _drive_disagg(
        P, D, PROMPT, 10, qid="pd1",
        swap_before_import=(params, 1),  # same tree, bumped version
    )
    assert not ok and reason == "version"
    assert D.handoff_stats()["import_rejects"] == {"version": 1}
    assert D.resumed_total == 0  # re-prefilled, never resumed stale KV
    assert D.prefill_tokens_total > 0
    assert got == ref  # same weights -> same stream, via the safe path


def test_handoff_racing_weight_swap_fails_closed_after_import():
    """Swap lands on D after the import but before the resume: the
    imported parked row is evicted with every other parked row — the
    continuation re-prefills under the new weights."""
    uni, _, params = make_engine()
    uni.submit(_req("pd2", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("pd2", timeout=10).output_ids)

    P, *_ = make_engine(params=params)
    D, *_ = make_engine(params=params)
    got, ok, _ = _drive_disagg(
        P, D, PROMPT, 10, qid="pd2",
        swap_after_import=(params, 1),
    )
    assert ok  # the import itself succeeded...
    assert D.resumed_total == 0  # ...but the swap evicted the parked row
    assert D.prefill_tokens_total > 0
    assert got == ref


def test_handoff_racing_quantized_weight_swap_fails_closed():
    """PR-13 x weight-quant interaction pin: when the swap that causes
    the version skew is a QUANTIZED-tree swap (int8 serving weights on
    both roles), the import still fails closed on version and the
    continuation re-prefills — same stream via the safe path, and the
    decode server's resident tree stays in the quantized format."""
    from areal_tpu.models import quantize

    uni, _, params = make_engine(serving_weight_dtype="int8")
    uni.submit(_req("pdq", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("pdq", timeout=10).output_ids)

    P, *_ = make_engine(params=params, serving_weight_dtype="int8")
    D, *_ = make_engine(params=params, serving_weight_dtype="int8")
    got, ok, reason = _drive_disagg(
        P, D, PROMPT, 10, qid="pdq",
        # same weights, bumped version — arriving in the engine's
        # resident (quantized) format, as the server negotiation does
        swap_before_import=(D.prepare_weights(params), 1),
    )
    assert not ok and reason == "version"
    assert D.handoff_stats()["import_rejects"] == {"version": 1}
    assert D.resumed_total == 0  # re-prefilled, never resumed stale KV
    assert D.prefill_tokens_total > 0
    assert got == ref
    assert quantize.is_quantized_tree(D.params)
    # the eviction path too: a quantized swap AFTER the import evicts
    # the parked row like any other swap
    P2, *_ = make_engine(params=params, serving_weight_dtype="int8")
    D2, *_ = make_engine(params=params, serving_weight_dtype="int8")
    got2, ok2, _ = _drive_disagg(
        P2, D2, PROMPT, 10, qid="pdq2",
        swap_after_import=(D2.prepare_weights(params), 1),
    )
    assert ok2 and D2.resumed_total == 0 and D2.prefill_tokens_total > 0
    assert got2 == ref


def test_import_rejects_dense_and_layout_mismatch():
    _, _, params = make_engine()
    P, *_ = make_engine(params=params)
    got_unit = {}

    P.submit(_req("pd3", PROMPT, 8))
    with P._lock:
        P._pending[-1].metadata = {"handoff_to": "D"}
    run_until_done(P)
    P.wait_result("pd3", timeout=10)
    got_unit = P.export_handoff("pd3")
    assert got_unit is not None

    dense, *_ = make_engine(params=params, cache_mode="dense")
    ok, reason = dense.import_handoff(dict(got_unit))
    assert not ok and reason == "dense"

    other_page, *_ = make_engine(params=params, page_size=16)
    ok, reason = other_page.import_handoff(dict(got_unit))
    assert not ok and reason == "layout"

    # a geometry-skewed payload (wrong per-block shape — e.g. a peer
    # built from a different model config) rejects BEFORE any blocks
    # are allocated, so nothing can leak off the free list
    bad = dict(got_unit)
    bad["payload"] = tuple(a[:, :1] for a in got_unit["payload"])
    victim, *_ = make_engine(params=params)
    free0 = victim.free_pool_blocks
    ok, reason = victim.import_handoff(bad)
    assert not ok and reason == "layout"
    assert victim.free_pool_blocks == free0  # no leak


def test_handoff_payload_bit_identical_through_import():
    """The imported blocks' device bytes equal the exported payload
    exactly (the shared gather/restore helpers' bit-identity, asserted
    through the engine path)."""
    from areal_tpu.models import paged

    _, _, params = make_engine()
    P, *_ = make_engine(params=params)
    D, *_ = make_engine(params=params)
    P.submit(_req("pd4", PROMPT, 8))
    with P._lock:
        P._pending[-1].metadata = {"handoff_to": "D"}
    run_until_done(P)
    P.wait_result("pd4", timeout=10)
    unit = P.export_handoff("pd4")
    ok, _ = D.import_handoff(unit)
    assert ok
    rid = next(
        i for i, r in enumerate(D.rows)
        if r is not None and r.req.qid == "pd4"
    )
    back = paged.gather_blocks_host(
        D.k_pool, D.v_pool, D._pages.rows[rid],
        k_scale=D.k_scale, v_scale=D.v_scale,
    )
    for a, b in zip(unit["payload"], back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- streamed (segmented) handoff ---------------------------------------------


def _drive_streamed(
    P, D, prompt, max_new, qid="st0", on_segment=None,
    submit_continuation=True,
):
    """Run prefill-with-handoff on P (streaming engine), pumping export
    segments into D as they emit — the worker's
    ``_pump_handoff_streams`` in-process — then decode the continuation
    on D.  ``on_segment(i, seg) -> bool`` may intercept a segment
    (return False to skip the default import: a dead-peer simulation,
    or a test importing with its own race injected).  Returns
    ``(tokens, segments)``."""
    P.submit(_req(qid, prompt, max_new))
    with P._lock:
        P._pending[-1].metadata = {"handoff_to": "D"}
    segs = []
    for _ in range(600):
        if not P.has_work:
            break
        P.step()
        for seg in P.drain_handoff_segments():
            i = len(segs)
            segs.append(seg)
            if on_segment is not None and not on_segment(i, seg):
                continue
            D.import_handoff_segment(seg)
    first = P.wait_result(qid, timeout=10)
    if (
        not submit_continuation
        or max_new <= 1
        or not (first.no_eos and first.output_ids)
    ):
        return list(first.output_ids), segs
    D.submit(_req(qid, list(prompt) + list(first.output_ids), max_new - 1))
    run_until_done(D)
    rest = D.wait_result(qid, timeout=10)
    return list(first.output_ids) + list(rest.output_ids), segs


def test_streamed_handoff_parity_and_chunk_boundary_export():
    """The composite streamed-handoff stream is token-identical to the
    unified engine's, the decode side resumes with ZERO prefill, and
    the export really is chunked: multiple numbered segments, the
    non-final ones emitted at fill-chunk boundaries (not one
    end-of-fill batch)."""
    uni, _, params = make_engine()
    uni.submit(_req("st0", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("st0", timeout=10).output_ids)

    P, *_ = make_engine(params=params, handoff_streaming=True)
    D, *_ = make_engine(params=params)
    got, segs = _drive_streamed(P, D, PROMPT, 10)
    assert got == ref
    assert D.resumed_total == 1 and D.prefill_tokens_total == 0
    data_segs = [s for s in segs if not s.get("abort")]
    assert len(data_segs) >= 3  # 24-tok prompt, 16-tok chunks, 8-tok pages
    assert [s["seq"] for s in data_segs] == list(range(len(data_segs)))
    assert data_segs[-1]["final"] and not data_segs[0]["final"]
    hp, hd = P.handoff_stats(), D.handoff_stats()
    assert hp["exports_total"] == 1 and hd["imports_total"] == 1
    assert hd["segment_imports_total"] == hp["segment_exports_total"]
    assert hd["pending_streams"] == 0 and hd["import_rejects"] == {}


def test_streamed_segment_version_skew_fails_closed_zero_leak():
    """ACCEPTANCE PIN: a weight swap landing on D mid-stream makes the
    NEXT segment's version check fail closed — the partial blocks are
    released (zero leaked on both sides), stale KV is never decoded,
    and the continuation re-prefills to the identical stream."""
    uni, _, params = make_engine()
    uni.submit(_req("sv0", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("sv0", timeout=10).output_ids)

    P, *_ = make_engine(params=params, handoff_streaming=True)
    D, *_ = make_engine(params=params)
    free0 = D.free_pool_blocks
    state = {"imported": 0}

    def swap_after_first(i, seg):
        if state["imported"] == 0:
            ok, reason = D.import_handoff_segment(seg)
            assert ok, reason
            # same tree, bumped version: every later segment is skewed
            D.update_weights(params, 1)
            D.step()
        else:
            ok, reason = D.import_handoff_segment(seg)
            assert not ok and reason == "version", (ok, reason)
        state["imported"] += 1
        return False  # we imported (or rejected) it ourselves

    got1, segs = _drive_streamed(
        P, D, PROMPT, 10, qid="sv0", on_segment=swap_after_first,
        submit_continuation=False,
    )
    assert len(segs) >= 3
    assert D.handoff_stats()["pending_streams"] == 0
    assert D.free_pool_blocks == free0  # ZERO leaked blocks on D
    # exporter side leaked nothing either: the stream state is gone and
    # the radix cache's references are the only remaining holders
    assert P.handoff_stats()["pending_streams"] == 0
    assert not P._handoff_streams
    # the continuation still produces the right stream — via re-prefill
    D.submit(_req("sv0", list(PROMPT) + got1, 9))
    run_until_done(D)
    rest = D.wait_result("sv0", timeout=10)
    assert D.resumed_total == 0 and D.prefill_tokens_total > 0
    assert got1 + list(rest.output_ids) == ref


def test_streamed_dead_peer_ttl_releases_blocks():
    """ACCEPTANCE PIN: a stream whose sender dies mid-push (segments
    simply stop arriving) may not pin its pre-allocated blocks forever —
    the TTL sweep releases them (reason="expired") with zero leaks."""
    _, _, params = make_engine()
    P, *_ = make_engine(params=params, handoff_streaming=True)
    D, *_ = make_engine(params=params)
    free0 = D.free_pool_blocks

    def only_seg0(i, seg):
        return i == 0  # every later segment is lost: the peer is dead

    _drive_streamed(
        P, D, PROMPT, 10, qid="dp0", on_segment=only_seg0,
        submit_continuation=False,
    )
    assert D.handoff_stats()["pending_streams"] == 1
    assert D.free_pool_blocks < free0  # seg-0 pre-allocated the row
    D.handoff_pending_ttl_steps = 3
    for _ in range(10):
        D.step()
    assert D.handoff_stats()["pending_streams"] == 0
    assert D.free_pool_blocks == free0  # ZERO leaked blocks
    assert D.handoff_stats()["import_rejects"].get("expired") == 1


def test_streamed_abort_on_one_token_budget_releases_peer_blocks():
    """A request that ENDS at its first token (1-token budget) after
    segments already streamed sends an ABORT; the peer releases its
    partial blocks immediately instead of waiting out the TTL."""
    _, _, params = make_engine()
    P, *_ = make_engine(params=params, handoff_streaming=True)
    D, *_ = make_engine(params=params)
    free0 = D.free_pool_blocks
    got, segs = _drive_streamed(P, D, PROMPT, 1, qid="ab0")
    assert len(got) == 1  # finished on P: nothing to hand off
    assert segs and segs[-1].get("abort")
    assert P.handoff_stats()["segment_aborts_total"] == 1
    assert D.handoff_stats()["pending_streams"] == 0
    assert D.free_pool_blocks == free0
    assert D.handoff_stats()["import_rejects"] == {"abort": 1}


def test_streamed_seg0_restart_replaces_pending_without_leak():
    """A restarted stream (exporter-side fill restart after a swap)
    re-sends seq 0; the decode side replaces the old half-stream —
    blocks swapped, never leaked, and the restart is not a reject."""
    _, _, params = make_engine()
    P, *_ = make_engine(params=params, handoff_streaming=True)
    D, *_ = make_engine(params=params)
    free0 = D.free_pool_blocks
    segs = []

    def collect(i, seg):
        segs.append(seg)
        return False

    _drive_streamed(
        P, D, PROMPT, 10, qid="rs0", on_segment=collect,
        submit_continuation=False,
    )
    seg0 = next(s for s in segs if s.get("seq") == 0)
    ok, _ = D.import_handoff_segment(seg0)
    assert ok
    held = free0 - D.free_pool_blocks
    assert held > 0
    ok, _ = D.import_handoff_segment(seg0)  # the restarted stream
    assert ok
    assert free0 - D.free_pool_blocks == held  # replaced, not doubled
    assert D.handoff_stats()["pending_streams"] == 1
    D._release_pending_handoff("rs0")
    assert D.free_pool_blocks == free0


@pytest.mark.slow  # int8 arm: quant parity arms are slow-marked by policy
def test_streamed_handoff_int8_segmented_bit_identity():
    """Streamed segments on int8 pools carry quantized bytes + scales
    bit-identically: the decode side's imported blocks equal the
    concatenated segment payloads exactly, and the composite stream
    matches the int8 unified engine's."""
    import jax

    from areal_tpu.models import paged

    uni, _, params = make_engine(kv_cache_dtype="int8")
    uni.submit(_req("si0", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("si0", timeout=10).output_ids)

    P, *_ = make_engine(params=params, kv_cache_dtype="int8",
                        handoff_streaming=True)
    D, *_ = make_engine(params=params, kv_cache_dtype="int8")
    # pump only (no continuation yet): the imported blocks must equal
    # the wire payloads BEFORE any decode appends to the tail page
    first, segs = _drive_streamed(
        P, D, PROMPT, 10, qid="si0", submit_continuation=False
    )
    rid = next(
        i for i, r in enumerate(D.rows)
        if r is not None and r.req.qid == "si0"
    )
    back = paged.gather_blocks_host(
        D.k_pool, D.v_pool, D._pages.rows[rid],
        k_scale=D.k_scale, v_scale=D.v_scale,
    )
    data_segs = [
        s for s in segs if not s.get("abort") and s["n_blocks"] > 0
    ]
    for c in range(len(back)):
        sent = np.concatenate(
            [np.asarray(jax.device_get(s["payload"][c]))
             for s in data_segs]
        )
        np.testing.assert_array_equal(sent, np.asarray(back[c]))
    D.submit(_req("si0", list(PROMPT) + first, 9))
    run_until_done(D)
    rest = D.wait_result("si0", timeout=10)
    assert first + list(rest.output_ids) == ref
    assert D.resumed_total == 1 and D.prefill_tokens_total == 0


@pytest.mark.slow  # hetero-mesh arm: a TP-2 prefill engine on the CPU mesh
def test_streamed_handoff_hetero_mesh():
    """Heterogeneous-mesh P/D (big-mesh prefill -> single-chip decode):
    a 2-device TP prefill engine streams its handoff to a one-device
    decode engine, token-identical to the unified engine."""
    import jax

    from areal_tpu.base.topology import MeshSpec

    uni, _, params = make_engine()
    uni.submit(_req("st0", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("st0", timeout=10).output_ids)

    P, *_ = make_engine(
        params=params, handoff_streaming=True,
        mesh=MeshSpec(model=2).make_mesh(jax.devices()[:2]),
    )
    D, *_ = make_engine(params=params)
    got, segs = _drive_streamed(P, D, PROMPT, 10)
    assert got == ref
    assert D.resumed_total == 1 and D.prefill_tokens_total == 0
    hp, hd = P.handoff_stats(), D.handoff_stats()
    assert hp["exports_total"] == hd["imports_total"] == 1
    assert hp["segment_exports_total"] > 1  # genuinely multi-segment
    assert hd["import_rejects"] == {}


@pytest.mark.slow  # int8 arm: quant parity arms are slow-marked by policy
def test_disagg_parity_int8_kv_cache():
    """Disaggregation composes with the quantized KV cache: int8+scale
    payloads hand off bit-identically, and the disaggregated stream
    matches the int8 unified engine's exactly."""
    uni, _, params = make_engine(kv_cache_dtype="int8")
    uni.submit(_req("pdq", PROMPT, 10))
    run_until_done(uni)
    ref = list(uni.wait_result("pdq", timeout=10).output_ids)

    P, *_ = make_engine(params=params, kv_cache_dtype="int8")
    D, *_ = make_engine(params=params, kv_cache_dtype="int8")
    got, ok, _ = _drive_disagg(P, D, PROMPT, 10, qid="pdq")
    assert ok and got == ref
    assert D.resumed_total == 1 and D.prefill_tokens_total == 0


# -- worker RPC path: a real 1P+1D fleet --------------------------------------


def test_pd_fleet_e2e_over_worker_rpc(monkeypatch, tmp_path):
    """Full-stack proof over the REAL wire: two GenerationServerWorkers
    registered prefill/decode, the GserverManager's two-stage schedule
    RPC, the partial-rollout client copying ``handoff_to`` into request
    metadata, the prefill worker pushing the unit through the
    ``import_handoff`` RPC before its client reply, and the continuation
    resuming on the decode server — token-identical to a direct unified
    engine with the same weights."""
    import asyncio

    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.model_api import GenerationHyperparameters
    from areal_tpu.api.system_api import (
        GenServerConfig,
        GserverManagerConfig,
    )
    from areal_tpu.base import constants, name_resolve, names
    from areal_tpu.engine.backend import make_model
    from areal_tpu.engine.inference_server import ContinuousBatchingEngine
    from areal_tpu.engine.sampling import SamplingParams
    from areal_tpu.system.generation_server import (
        GenerationServerWorker,
        GenServerClient,
    )
    from areal_tpu.system.gserver_manager import (
        GserverManager,
        GserverManagerClient,
    )
    from areal_tpu.system.partial_rollout import PartialRolloutManager

    monkeypatch.setenv("AREAL_SAVE_ROOT", str(tmp_path / "save"))
    monkeypatch.setenv("AREAL_LOG_ROOT", str(tmp_path / "logs"))
    name_resolve.reconfigure("memory")
    constants.set_experiment_trial_names("pdtest", "t0")
    expr, tr = "pdtest", "t0"

    model_abs = ModelAbstraction(
        "random", {"vocab_size": 64, "max_position_embeddings": 256}
    )
    common = dict(
        model=model_abs,
        max_concurrent_batch=2,
        kv_cache_len=128,
        chunk_size=4,
        greedy=True,
        cache_mode="paged",
        page_size=16,
        prefill_chunk_tokens=32,
    )
    workers = []
    for name, role in (("gen_server_0", "prefill"), ("gen_server_1", "decode")):
        w = GenerationServerWorker()
        threading.Thread(
            target=w.run,
            args=(GenServerConfig(worker_name=name, role=role, **common),),
            daemon=True,
        ).start()
        workers.append(w)
        name_resolve.wait(names.gen_server(expr, tr, name), timeout=30)

    manager = GserverManager()
    threading.Thread(
        target=manager.run,
        args=(
            GserverManagerConfig(worker_name="gserver_manager", n_servers=2),
        ),
        daemon=True,
    ).start()
    name_resolve.wait(names.gen_server_manager(expr, tr), timeout=30)

    prompt = list(np.arange(40) % 60 + 2)
    mgr_client = GserverManagerClient(expr, tr, timeout=30.0)
    prm = PartialRolloutManager(
        mgr_client,
        GenerationHyperparameters(max_new_tokens=12, greedy=True),
        new_tokens_per_chunk=6,
        request_timeout=60.0,
    )
    try:
        out = asyncio.run(prm._gen_one("pdr0-0", prompt))
        assert len(out.output_ids) == 12, out.output_ids

        # unified reference: a direct engine on the identical weights
        probe = make_model(model_abs, None, None)
        ref_eng = ContinuousBatchingEngine(
            probe.model_cfg,
            probe.init_params,
            max_batch=2,
            kv_cache_len=128,
            chunk_size=4,
            sampling=SamplingParams(greedy=True),
            cache_mode="paged",
            page_size=16,
            prefill_chunk_tokens=32,
        )
        ref_eng.submit(_req("ref0", prompt, 12))
        run_until_done(ref_eng)
        ref = ref_eng.wait_result("ref0", timeout=10)
        assert list(out.output_ids) == list(ref.output_ids)

        # the handoff ACTUALLY happened (not a silent unified fallback):
        # prefill server exported once, decode server imported once and
        # served every continuation
        reg = name_resolve.get(names.gen_server(expr, tr, "gen_server_0"))
        from areal_tpu.system.generation_server import (
            parse_server_registration,
        )

        p_addr, _, _, p_role, _ = parse_server_registration(reg)
        assert p_role == "prefill"
        p_metrics = GenServerClient(p_addr, timeout=10.0).call(
            "metrics", {}
        )
        assert p_metrics["role"] == "prefill"
        assert p_metrics["handoff_exports_total"] == 1, p_metrics
        reg_d = name_resolve.get(names.gen_server(expr, tr, "gen_server_1"))
        d_addr = parse_server_registration(reg_d)[0]
        d_metrics = GenServerClient(d_addr, timeout=10.0).call(
            "metrics", {}
        )
        assert d_metrics["role"] == "decode"
        assert d_metrics["handoff_imports_total"] == 1, d_metrics
        assert d_metrics["handoff_import_rejects"] == {}
        # the default path is STREAMED: the handoff crossed the wire as
        # multiple import_handoff_segment RPCs (40-token prompt,
        # 32-token fill chunks, 16-token pages), every one imported
        assert p_metrics["handoff_segment_exports_total"] >= 2, p_metrics
        assert (
            d_metrics["handoff_segment_imports_total"]
            == p_metrics["handoff_segment_exports_total"]
        ), (p_metrics, d_metrics)
        assert d_metrics["handoff_pending_streams"] == 0
        # load-aware admission: the prefill server's backlog signal is
        # scrapeable (drained back to zero once the fill completed)
        assert p_metrics["prefill_backlog_tokens"] == 0
        status = mgr_client.call("get_status", {})
        assert status["pd_enabled"] is True
        assert status["server_roles"][p_addr] == "prefill"
        assert p_addr in status["prefill_backlog_tokens"]
    finally:
        prm.close()
        mgr_client.close()
        manager.exit()
        for w in workers:
            w.exit()


# -- mixed load: every handoff lands ------------------------------------------


@pytest.mark.parametrize(
    "streamed", [False, True], ids=["monolithic", "streamed"]
)
def test_mixed_load_every_handoff_lands_with_parity(streamed):
    """Short and long prompts fill TOGETHER on the prefill engine (the
    shape the single-request gates above never take): every request
    hands off, every unit lands on the decode engine with no reject and
    no suffix prefill, and every stream equals the unified engine's."""
    prompts = {
        f"mx{i}": list((np.arange(n) * (i + 3)) % 40 + 6)
        for i, n in enumerate((12, 24, 40, 56))
    }
    uni, _, params = make_engine()
    for qid, prompt in prompts.items():
        uni.submit(_req(qid, prompt, 8))
    run_until_done(uni)
    ref = {
        qid: list(uni.wait_result(qid, timeout=10).output_ids)
        for qid in prompts
    }

    P, *_ = make_engine(params=params, handoff_streaming=streamed)
    D, *_ = make_engine(params=params)
    for qid, prompt in prompts.items():
        P.submit(_req(qid, prompt, 8))
        with P._lock:
            P._pending[-1].metadata = {"handoff_to": "D"}
    for _ in range(600):
        if not P.has_work:
            break
        P.step()
        for seg in P.drain_handoff_segments():
            ok, reason = D.import_handoff_segment(seg)
            assert ok, reason
    got, continued = {}, []
    for qid, prompt in prompts.items():
        first = P.wait_result(qid, timeout=10)
        got[qid] = list(first.output_ids)
        if not streamed:
            ok, reason = D.import_handoff(P.export_handoff(qid))
            assert ok, reason
        if first.no_eos:
            D.submit(_req(qid, prompt + got[qid], 7))
            continued.append(qid)
    assert continued
    run_until_done(D)
    for qid in continued:
        got[qid] += list(D.wait_result(qid, timeout=10).output_ids)
    assert got == ref

    hp, hd = P.handoff_stats(), D.handoff_stats()
    assert hp["exports_total"] == hd["imports_total"] == len(prompts)
    assert hp["bytes_total"] > 0
    assert hd["import_rejects"] == {} and hd["pending_streams"] == 0
    assert D.resumed_total == len(continued)
    assert D.prefill_tokens_total == 0
    if streamed:
        assert hp["segment_exports_total"] > len(prompts)
        assert hd["segment_imports_total"] == hp["segment_exports_total"]
