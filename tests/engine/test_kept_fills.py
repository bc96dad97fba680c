"""A finished fill of a STATEFUL stack stays for its prompt's late
siblings (``engine/kv_pages.KeptFills``): the prompt's end state in a
snapshot slot, a reference on its pages in every pool, its last logits
row.  A sibling admitted after the fill has ended joins it where it
prefilled the whole prompt again, and is, token for token, what it is
when it is queued on the fill in time, and what the benchmark's plain
reference gives for its sequence.  Over the three stateful stacks' small
configs (state + one pool; state + two pools; a slot and pages in every
layer); one case says that a stateless stack keeps nothing and is served
by its prefix cache as before."""

import jax
import numpy as np
import pytest

from areal_tpu.models import hybrid
from tests.engine import test_parallel_pages as parallel
from tests.engine import test_shared_pages as shared
from tests.engine import test_state_slots as slots
from tests.engine import test_window_pages as window
from tests.engine.test_window_pages import _prompts, _req

#: 16 rows: two snapshot slots
ROWS = 16


def _weights(name, cfg, seed):
    params = hybrid.init_params(cfg, jax.random.PRNGKey(seed))
    return parallel._lively(params) if name == "parallel" else params


def _build(name):
    """(name, the stack's test module, cfg, params).  A stateful stack's
    programs are built here too, by one prompt with a sibling in time and
    one late: the cases then find them, and none of them pays a compile
    inside the time a tier-1 test may take."""
    mod = dict(
        hybrid=slots, shared=shared, parallel=parallel, stateless=window
    )[name]
    if name == "hybrid":
        cfg = slots.make_cfg(
            moe_first_expert=slots.FIRST, moe_held_experts=slots.HELD
        )
    else:
        cfg = mod.make_cfg()
    built = (name, mod, cfg, _weights(name, cfg, 0))
    if name != "stateless":
        (p,) = _prompts(10, 37)
        eng = _engine(built)
        eng.submit(_req("w0", p, 12))
        eng.submit(_req("w1", p, 6))
        _step_until(eng, lambda: eng.n_decoding == 2)
        eng.submit(_req("w2", p, 4))
        _run(eng)
    return built


@pytest.fixture(scope="module")
def stacks():
    return {}


@pytest.fixture
def stack(request, stacks):
    """Built once a stack, in the SETUP of the first case that asks."""
    name = request.param
    if name not in stacks:
        stacks[name] = _build(name)
    return stacks[name]


def _engine(stack, **kw):
    _, mod, cfg, params = stack
    kw = dict(max_batch=ROWS, kv_cache_len=96, **kw)
    return mod.make_engine((cfg, params), **kw)


def _step_until(eng, cond, max_steps=200):
    with jax.default_matmul_precision("highest"):
        for _ in range(max_steps):
            if cond():
                return
            eng.step()
    raise AssertionError("the engine never got there")


def _run(eng):
    _step_until(eng, lambda: not eng.has_work, 600)
    return eng.drain_results()


def _assert_reference(stack, results, params=None, eng=None):
    name, mod, _, own = stack
    if name == "stateless":
        mod.assert_reference(params or own, results, eng)
    else:
        mod.assert_reference(params or own, results)


def _assert_nothing_leaked(eng):
    """With the rows gone and the kept fills let go, every page of every
    pool is free again and every snapshot slot."""
    assert all(r is None for r in eng.rows)
    while eng._kept.evict("pages"):
        pass
    assert len(eng._kept) == 0
    assert sorted(eng._kept._free) == list(range(eng._kept.n_slots))
    for pool in eng._pools:
        assert pool.free_blocks == pool.n_blocks
        assert not pool._ref.any()


def _a_late_sibling_is_the_one_that_came_in_time(stack):
    (p,) = _prompts(11, 37)  # four full pages and a tail of 5; three windows
    late = _engine(stack)
    late.submit(_req("s0", p, 30))
    _step_until(late, lambda: late.n_decoding == 1)
    for _ in range(3):  # the first target has moved on, and its tail page
        late.step()
    assert late.state_fills_kept_total == 1
    calls = late.prefill_calls
    late.submit(_req("s1", p, 9))
    late.submit(_req("s2", p, 5))  # two late siblings, one distribution
    got = _run(late)
    # no fill program ran for them, not for one position
    assert late.prefill_calls == calls and late.prefill_tokens_total == 37
    assert late.state_late_joins_total == 2
    assert late.state_reprefills_total == 0 and late.state_copies_total == 0
    in_time = _engine(stack)
    for qid, n in (("s0", 30), ("s1", 9), ("s2", 5)):
        in_time.submit(_req(qid, p, n))
    want = _run(in_time)
    assert in_time.state_late_joins_total == 0
    assert in_time.state_copies_total == 2
    for qid in ("s0", "s1", "s2"):
        assert got[qid].output_ids == want[qid].output_ids, qid
        np.testing.assert_allclose(
            got[qid].output_logprobs, want[qid].output_logprobs,
            rtol=0, atol=1e-6, err_msg=qid,
        )
    _assert_reference(stack, got)
    _assert_nothing_leaked(late)


def _the_kept_pages_outlive_the_fills_first_target(stack):
    (p,) = _prompts(12, 37)
    eng = _engine(stack)
    eng.submit(_req("s0", p, 3))
    first = _run(eng)
    # the row is gone, and with it every reference but the kept fill's
    assert all(r is None for r in eng.rows) and len(eng._kept) == 1
    assert eng.free_pool_blocks == eng.n_blocks - 5
    if eng._win is not None:  # the prompt's last window (12) and its tail
        assert eng._win.n_blocks - eng._win.free_blocks == 37 // 8 + 1 - (
            eng._win.first_kept(37)
        )
    eng.submit(_req("s1", p, 14))
    out = {**first, **_run(eng)}
    assert eng.prefill_tokens_total == 37 and eng.state_late_joins_total == 1
    _assert_reference(stack, out)
    _assert_nothing_leaked(eng)


def _the_least_joined_goes_when_the_slots_run_out(stack):
    p1, p2, p3 = _prompts(13, 37, 21, 26)
    eng = _engine(stack)
    eng.submit(_req("a0", p1, 40))
    _step_until(eng, lambda: eng.n_decoding == 1)
    eng.submit(_req("b0", p2, 6))
    _step_until(eng, lambda: eng.state_fills_kept_total == 2)
    eng.submit(_req("a1", p1, 7))  # joins: p1's is now the most recent
    _step_until(eng, lambda: eng.state_late_joins_total == 1)
    eng.submit(_req("c0", p3, 6))  # a third prompt: p2's goes, not p1's
    _step_until(eng, lambda: eng.state_fills_kept_total == 3)
    assert eng.state_fills_evicted == dict(slots=1, pages=0, swap=0)
    assert eng._kept.peek(tuple(p2)) is None
    eng.submit(_req("a2", p1, 5))
    eng.submit(_req("b1", p2, 5))  # prefills again (and is kept again)
    out = _run(eng)
    assert sorted(out) == ["a0", "a1", "a2", "b0", "b1", "c0"]
    assert eng.state_late_joins_total == 2
    assert eng.prefill_tokens_total == 37 + 21 + 26 + 21
    assert eng.state_fills_evicted["slots"] == 2
    _assert_reference(stack, out)
    _assert_nothing_leaked(eng)


def _a_kept_fill_yields_its_pages_to_a_live_row(stack):
    """A pool of 16 pages: a kept fill's five and a row that grows to
    eleven do not both fit.  The kept fill goes (no row is preempted for
    it), and its prompt's next sample prefills again."""
    p1, p2 = _prompts(14, 37, 25)
    eng = _engine(stack, kv_pool_tokens=128)
    assert eng.n_blocks == 16
    eng.submit(_req("a0", p1, 3))
    out = _run(eng)
    assert len(eng._kept) == 1
    eng.submit(_req("b0", p2, 62))
    out.update(_run(eng))
    assert eng.state_fills_evicted["pages"] == 1 and eng.preempted_total == 0
    assert eng._kept.peek(tuple(p1)) is None
    eng.submit(_req("a1", p1, 4))
    out.update(_run(eng))
    assert eng.state_late_joins_total == 0
    assert eng.prefill_tokens_total == 37 + 25 + 37
    _assert_reference(stack, out)
    _assert_nothing_leaked(eng)


def _a_weight_swap_drops_the_kept_fills(stack):
    name, _, cfg, _ = stack
    (p,) = _prompts(15, 37)
    eng = _engine(stack)
    eng.submit(_req("s0", p, 24))
    _step_until(eng, lambda: eng.n_decoding == 1)
    assert len(eng._kept) == 1
    new = _weights(name, cfg, 7)
    eng.update_weights(new, version=1)
    _step_until(eng, lambda: eng.version == 1)  # (applied by the next step)
    assert len(eng._kept) == 0
    assert eng.state_fills_evicted == dict(slots=0, pages=0, swap=1)
    eng.submit(_req("s1", p, 8))
    out = _run(eng)
    # the late sibling prefilled under the new weights, and is theirs
    assert eng.state_late_joins_total == 0 and eng.state_reprefills_total == 1
    _assert_reference(stack, {"s1": out["s1"]}, params=new)
    _assert_nothing_leaked(eng)


def _keeping_and_joining_build_no_program_after_the_start(stack):
    """The engine builds the programs that keep a fill and hand it out
    when it starts, in the context its steps run in (its constructor
    works under ``jax.default_device``, which a program's cache key
    holds): a fill that ends and a sibling that joins late find them."""
    from areal_tpu.engine import inference_server

    (p,) = _prompts(17, 37)
    eng = _engine(stack, device=jax.devices()[0])
    programs = (
        hybrid.copy_state_slots_between, inference_server._keep_logits_row,
    )
    built = [f._cache_size() for f in programs]
    eng.submit(_req("s0", p, 12))
    for _ in range(200):  # (no ``default_matmul_precision``: a key too)
        if eng.n_decoding == 1:
            break
        eng.step()
    eng.submit(_req("s1", p, 4))
    while eng.has_work:
        eng.step()
    assert eng.state_fills_kept_total == 1 and eng.state_late_joins_total == 1
    assert [f._cache_size() for f in programs] == built


def _the_ninth_late_sibling_of_a_step_waits_for_the_next(stack):
    """One distribution serves ``LATE_JOINS_A_STEP`` late siblings: the
    step's record says what left the ninth queued, and the next step
    serves it."""
    from areal_tpu.engine.inference_server import LATE_JOINS_A_STEP

    (p,) = _prompts(18, 37)
    eng = _engine(stack)
    eng.submit(_req("s0", p, 40))
    _step_until(eng, lambda: eng.state_fills_kept_total == 1)
    for i in range(LATE_JOINS_A_STEP + 1):
        eng.submit(_req(f"late{i}", p, 3))
    with jax.default_matmul_precision("highest"):
        eng.step()
        first = eng._phases.records()[-1]
        eng.step()
        second = eng._phases.records()[-1]
    assert (first["admit_stopped_by"], first["pending"]) == ("late_join_cap", 1)
    assert first["late_joins"] == first["rows_admitted"] == LATE_JOINS_A_STEP
    assert (second["admit_stopped_by"], second["pending"]) == ("queue_empty", 0)
    assert second["late_joins"] == second["rows_admitted"] == 1
    assert eng.state_late_joins_total == LATE_JOINS_A_STEP + 1
    assert first["fill_programs"] == second["fill_programs"] == 0
    out = _run(eng)
    assert len(out) == LATE_JOINS_A_STEP + 2
    records = eng._phases.records()
    assert sum(r["late_joins"] for r in records) == eng.state_late_joins_total


def _a_stateless_stack_keeps_nothing(stack):
    """Its prefix cache serves the late sibling, as before: no snapshot
    slot, no kept fill, and the counters of the stateful path stay 0."""
    (p,) = _prompts(16, 37)
    eng = _engine(stack)
    assert not eng._stateful and eng._kept.n_slots == 0
    assert not hasattr(eng, "snap_ssm")
    eng.submit(_req("s0", p, 30))
    _step_until(eng, lambda: eng.n_decoding == 1)
    eng.submit(_req("s1", p, 9))
    out = _run(eng)
    assert len(eng._kept) == 0 and eng._joining == []
    assert eng.state_late_joins_total == eng.state_fills_kept_total == 0
    assert eng.state_reprefills_total == eng.state_copies_total == 0
    assert eng.state_fills_evicted == dict(slots=0, pages=0, swap=0)
    # the whole prompt but its last token came from the cache
    assert eng.prefix_cache_stats()["hits_total"] == 1
    assert eng.prefill_tokens_total == 37 + 1
    _assert_reference(stack, out, eng=eng)


STATEFUL = {
    "late_sibling": _a_late_sibling_is_the_one_that_came_in_time,
    "first_target_gone": _the_kept_pages_outlive_the_fills_first_target,
    "evicted_for_a_slot": _the_least_joined_goes_when_the_slots_run_out,
    "evicted_for_pages": _a_kept_fill_yields_its_pages_to_a_live_row,
    "weight_swap": _a_weight_swap_drops_the_kept_fills,
}
CASES = [
    (name, case, run)
    for name in ("hybrid", "shared", "parallel")
    for case, run in STATEFUL.items()
] + [
    # (nothing of these two is a stack's own: one stack each)
    ("parallel", "built_at_the_start",
     _keeping_and_joining_build_no_program_after_the_start),
    ("hybrid", "late_join_cap",
     _the_ninth_late_sibling_of_a_step_waits_for_the_next),
    ("stateless", "keeps_nothing", _a_stateless_stack_keeps_nothing),
]


@pytest.mark.parametrize(
    "stack,run",
    [pytest.param(n, r, id=f"{n}-{c}") for n, c, r in CASES],
    indirect=["stack"],
)
def test_a_finished_fill_stays_for_its_prompts_late_siblings(stack, run):
    run(stack)
