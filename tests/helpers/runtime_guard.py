"""Tier-1 runtime guards: one per test, one per session.

The tier-1 suite runs under a hard limit (ROADMAP.md, "Tier-1 verify",
says what the driver runs, its limit and the suite's time): past it the
failure mode is an opaque rc=124 instead of a named offender.  Two
guards, wired by ``tests/conftest.py``, make that fail LOUDLY:

* per test: a PASSING non-``slow`` test whose call phase exceeded
  :data:`TIER1_TEST_BUDGET_S` becomes a failure naming the test and its
  duration.  Tests that legitimately need longer belong behind the
  ``slow`` marker — they run outside the tier-1 budget
  (``pytest -m slow``).
* per session: a child process or a non-daemon thread still alive when
  a session ends (an xdist worker's or the controller's) is named, the
  children are killed, and the run's exit status becomes a failure.  A
  process left behind keeps the pipe the driver reads open, and a thread
  is joined at interpreter exit: either holds the run long after its
  summary line.

Both decisions are pure functions so they are themselves unit-tested
(tests/base/test_runtime_guard.py).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

#: per-test wall budget (seconds) for the call phase of non-slow tests.
#: Headroom check (2026-08): the slowest tier-1 test is ~35 s
#: (test_async_ppo_e2e), so 60 s flags regressions without flaking the
#: existing suite.
TIER1_TEST_BUDGET_S = 60.0


def over_budget_message(
    nodeid: str,
    duration_s: float,
    is_slow: bool,
    budget_s: float = TIER1_TEST_BUDGET_S,
) -> Optional[str]:
    """The guard decision: a failure message for a non-``slow`` test
    whose call phase ran past the budget, else None."""
    if is_slow or duration_s <= budget_s:
        return None
    return (
        f"tier-1 runtime guard: {nodeid} took {duration_s:.1f}s, over "
        f"the {budget_s:.0f}s per-test budget (the whole suite has a "
        "hard limit: ROADMAP.md, \"Tier-1 verify\").  Make the test "
        "faster, or mark it @pytest.mark.slow to move it out of tier-1."
    )


def leftovers_message(
    where: str,
    children: Sequence[Tuple[int, str, str]],
    threads: Sequence[Tuple[str, bool]],
) -> Optional[str]:
    """The session-end decision: a failure message naming what a test
    session left behind, else None.  ``children`` are the (pid, name,
    command) of child processes still alive; ``threads`` the (name,
    daemon) of live threads besides the main one.  A daemon thread is
    not held against the run: it cannot keep an interpreter from
    exiting, and a non-daemon one is joined at exit for as long as it
    likes."""
    lines = [
        f"  child process {pid} {name}: {command}"
        for pid, name, command in children
    ] + [
        f"  non-daemon thread {name}"
        for name, daemon in threads
        if not daemon
    ]
    if not lines:
        return None
    return "\n".join(
        [
            f"tier-1 session-end guard: {where} finished with these still "
            "alive (children are killed now; the test or the code that "
            "made each must end it):"
        ]
        + lines
    )
