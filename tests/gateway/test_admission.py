"""Tenant admission plane unit tests: token-bucket refill math, the
typed reject catalogue (rate_limited / budget_exhausted /
request_too_large), budget terminality, the unknown-tenant default
policy, and settle-time refunds.  All pure host-side Python with an
explicit clock — no jax, no sockets."""

import pytest

from areal_tpu.gateway.admission import (
    DEFAULT_BULK_TENANT,
    PRIORITY_BULK,
    PRIORITY_INTERACTIVE,
    REJECT_BUDGET_EXHAUSTED,
    REJECT_HTTP_STATUS,
    REJECT_RATE_LIMITED,
    REJECT_REQUEST_TOO_LARGE,
    AdmissionPlane,
    TenantPolicy,
    TokenBucket,
)


# -- token bucket refill math -------------------------------------------------


def test_bucket_starts_full_then_rejects_with_exact_refill_wait():
    b = TokenBucket(rate_tokens_per_s=10.0, burst_tokens=20.0)
    ok, wait = b.take(20.0, now=0.0)  # burst allowance up front
    assert ok and wait == 0.0
    # empty bucket: the reject carries the EXACT deficit/rate wait
    ok, wait = b.take(10.0, now=0.0)
    assert not ok and wait == pytest.approx(1.0)
    # half the deficit refilled after 0.5s at rate 10
    ok, wait = b.take(10.0, now=0.5)
    assert not ok and wait == pytest.approx(0.5)
    # fully refilled for this request at 1.0s
    ok, wait = b.take(10.0, now=1.0)
    assert ok and wait == 0.0


def test_bucket_refill_caps_at_burst():
    b = TokenBucket(rate_tokens_per_s=5.0, burst_tokens=8.0)
    assert b.take(8.0, now=0.0)[0]
    # an hour idle refills to burst, not rate*3600
    assert b.peek(now=3600.0) == pytest.approx(8.0)
    ok, _ = b.take(8.0, now=3600.0)
    assert ok


def test_bucket_request_larger_than_burst_is_unservable():
    b = TokenBucket(rate_tokens_per_s=100.0, burst_tokens=10.0)
    ok, wait = b.take(11.0, now=0.0)
    assert not ok and wait == float("inf")
    # ...and stays unservable no matter how long the caller waits
    ok, wait = b.take(11.0, now=1e6)
    assert not ok and wait == float("inf")


def test_bucket_burst_defaults_to_one_second_of_rate():
    b = TokenBucket(rate_tokens_per_s=7.0)
    assert b.burst == pytest.approx(7.0)
    with pytest.raises(AssertionError):
        TokenBucket(rate_tokens_per_s=0.0)


# -- reject catalogue ----------------------------------------------------------


def _plane(**policy_kw):
    return AdmissionPlane([TenantPolicy(name="t", **policy_kw)])


def test_rate_limited_reject_is_429_with_retry_after():
    plane = _plane(rate_tokens_per_s=10.0, burst_tokens=20.0)
    assert plane.admit("t", 20.0, now=0.0).ok
    dec = plane.admit("t", 10.0, now=0.0)
    assert not dec.ok
    assert dec.reason == REJECT_RATE_LIMITED
    assert dec.http_status == 429
    assert dec.retry_after_s == pytest.approx(1.0)
    # the wire dict the gateway maps onto the HTTP response
    d = dec.as_dict()
    assert d["ok"] is False and d["http_status"] == 429
    assert d["retry_after_s"] > 0


def test_request_too_large_reject_is_403_not_retryable():
    plane = _plane(rate_tokens_per_s=100.0, burst_tokens=10.0)
    dec = plane.admit("t", 11.0, now=0.0)
    assert not dec.ok
    assert dec.reason == REJECT_REQUEST_TOO_LARGE
    assert dec.http_status == 403
    # the bucket's internal inf never reaches the wire (0.0 = "no
    # retry hint" — a 403 body stays JSON-serializable)
    assert dec.retry_after_s == 0.0
    assert not plane.admit("t", 11.0, now=1e6).ok  # waiting never helps


def test_budget_exhaustion_is_terminal_until_reset():
    plane = _plane(token_budget=100.0)
    assert plane.admit("t", 100.0, now=0.0).ok
    dec = plane.admit("t", 1.0, now=0.0)
    assert not dec.ok
    assert dec.reason == REJECT_BUDGET_EXHAUSTED
    assert dec.http_status == 403
    # TERMINAL: time passing never refills a cumulative budget
    assert not plane.admit("t", 1.0, now=1e9).ok
    # ...until an operator resets it
    plane.reset_budget("t")
    assert plane.admit("t", 1.0, now=1e9).ok


def test_settle_refunds_the_overestimate():
    plane = _plane(token_budget=100.0)
    assert plane.admit("t", 80.0, now=0.0).ok
    assert not plane.admit("t", 60.0, now=0.0).ok  # 80 + 60 > 100
    # the request actually used 30 of its 80-token reservation
    plane.settle("t", reserved=80.0, used=30.0)
    assert plane.stats()["t"]["spent_tokens"] == pytest.approx(30.0)
    assert plane.admit("t", 60.0, now=0.0).ok
    # a refund can never push spend below zero or above the reservation
    plane.settle("t", reserved=1e9, used=0.0)
    assert plane.stats()["t"]["spent_tokens"] == 0.0


def test_unknown_tenant_runs_under_permissive_interactive_default():
    plane = AdmissionPlane(
        [TenantPolicy(name="t", rate_tokens_per_s=1.0, burst_tokens=1.0)]
    )
    dec = plane.admit("stranger", 1e6, now=0.0)
    assert dec.ok and dec.priority == PRIORITY_INTERACTIVE
    # materialized: repeat requests share one accounting line
    st = plane.stats()["stranger"]
    assert st["admitted_total"] == 1
    assert st["priority"] == PRIORITY_INTERACTIVE


def test_reject_counters_and_stats_accumulate_per_reason():
    plane = _plane(rate_tokens_per_s=10.0, burst_tokens=10.0,
                   token_budget=50.0)
    assert plane.admit("t", 10.0, now=0.0).ok
    assert plane.admit("t", 5.0, now=0.0).reason == REJECT_RATE_LIMITED
    # budget is checked FIRST, so keep the oversized request affordable
    # (10 spent + 20 <= 50) to reach the bucket's too-large branch
    assert plane.admit("t", 20.0, now=10.0).reason == (
        REJECT_REQUEST_TOO_LARGE
    )
    assert plane.admit("t", 45.0, now=10.0).reason == (
        REJECT_BUDGET_EXHAUSTED
    )
    st = plane.stats()["t"]
    assert st["rejects"] == {
        REJECT_RATE_LIMITED: 1,
        REJECT_REQUEST_TOO_LARGE: 1,
        REJECT_BUDGET_EXHAUSTED: 1,
    }
    assert st["admitted_total"] == 1
    assert st["token_budget"] == 50.0


def test_http_status_map_covers_the_whole_catalogue():
    assert REJECT_HTTP_STATUS == {
        REJECT_RATE_LIMITED: 429,
        REJECT_BUDGET_EXHAUSTED: 403,
        REJECT_REQUEST_TOO_LARGE: 403,
    }


def test_from_config_accepts_dict_rows_and_priority_classes():
    plane = AdmissionPlane.from_config([
        {"name": "chat", "priority": PRIORITY_INTERACTIVE},
        TenantPolicy(name=DEFAULT_BULK_TENANT, priority=PRIORITY_BULK,
                     rate_tokens_per_s=100.0),
    ])
    assert plane.priority_of("chat") == PRIORITY_INTERACTIVE
    assert plane.priority_of(DEFAULT_BULK_TENANT) == PRIORITY_BULK
    dec = plane.admit(DEFAULT_BULK_TENANT, 10.0, now=0.0)
    assert dec.ok and dec.priority == PRIORITY_BULK
