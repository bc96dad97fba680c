"""Tier-1 runtime guards: one per test, one per session, one per worker.

The tier-1 suite runs under a hard limit (ROADMAP.md, "Tier-1 verify",
says what the driver runs, its limit and the suite's time): past it the
failure mode is an opaque rc=124 instead of a named offender.  Three
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
* per worker: every program XLA's CPU backend compiles costs its process
  memory mappings, and past ``vm.max_map_count`` the next compile
  segfaults the worker.  The conftest gives a file's programs back at
  the file's end and notes the counts; a worker that stood above half
  the limit at any file's end fails the run and names the files that
  added most (:func:`mappings_message`), and the summary prints where
  the mappings and the seconds went (:func:`mappings_tables`).  A worker
  that dies all the same takes its children with it
  (:func:`start_sentinel`): left alive they hold the worker's pipe open,
  the controller never learns that it went down, and the run waits for
  its limit.

The decisions are pure functions so they are themselves unit-tested
(tests/base/test_runtime_guard.py).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import (
    Iterable,
    List,
    Mapping,
    Optional,
    Sequence,
    Set,
    Tuple,
    TypedDict,
)

import psutil

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


class FileNote(TypedDict):
    """What the conftest notes for one test file."""

    worker: str
    file: str
    #: this process's mappings before the file's first test, after its
    #: last one, and after the release
    start: int
    end: int
    after: int
    seconds: float


#: how many files each of the summary's tables names
TABLE_ROWS = 15


def mappings_now() -> int:
    """This process's memory mappings (one line of ``/proc/self/maps``
    each)."""
    with open("/proc/self/maps", "rb") as f:
        return sum(1 for _ in f)


def mappings_limit() -> int:
    """What the kernel allows a process (``vm.max_map_count``)."""
    with open("/proc/sys/vm/max_map_count") as f:
        return int(f.read())


def _added(note: FileNote) -> int:
    return note["end"] - note["start"]


def _peaks(notes: Sequence[FileNote]) -> List[Tuple[FileNote, List[FileNote]]]:
    """By worker: the note of the file at whose end it stood highest,
    and all its notes."""
    found = []
    for worker in sorted({n["worker"] for n in notes}):
        own = [n for n in notes if n["worker"] == worker]
        found.append((max(own, key=lambda n: n["end"]), own))
    return found


def mappings_message(notes: Sequence[FileNote], limit: int) -> Optional[str]:
    """The per-worker decision: a failure message for every worker whose
    count of mappings stood above half of ``limit`` at any file's end,
    naming the five files that added most to it (highest first), else
    None."""
    lines: List[str] = []
    for peak, own in _peaks(notes):
        if peak["end"] * 2 <= limit:
            continue
        lines.append(
            f"  {peak['worker']} stood at {peak['end']} after "
            f"{peak['file']}; the files that added most:"
        )
        lines += [
            f"    +{_added(n)} {n['file']} (left {n['after']})"
            for n in sorted(own, key=_added, reverse=True)[:5]
        ]
    if not lines:
        return None
    return "\n".join(
        [
            f"tier-1 mappings guard: a worker stood above half of "
            f"vm.max_map_count ({limit}) at a file's end; past the limit "
            "the next compile segfaults it.  Make the files below compile "
            "less (one model a module, engines reused across cases), or "
            "find what keeps their programs past the file's end:"
        ]
        + lines
    )


def mappings_tables(notes: Sequence[FileNote]) -> List[str]:
    """The summary's three tables: each worker's highest count and the
    file at which it stood, the files that added most mappings, and the
    files with most seconds."""
    if not notes:
        return []
    lines = [
        "mappings and seconds by file (tests/helpers/runtime_guard.py)",
        "  worker: highest count of mappings, at the end of",
    ]
    for peak, own in _peaks(notes):
        lines.append(
            f"    {peak['worker']}: {peak['end']} {peak['file']} "
            f"({len(own)} files, {sum(n['seconds'] for n in own):.0f} s)"
        )
    lines.append("  mappings added (start -> end, after the release) by")
    for n in sorted(notes, key=_added, reverse=True)[:TABLE_ROWS]:
        lines.append(
            f"    +{_added(n)} ({n['start']} -> {n['end']}, {n['after']}) "
            f"{n['file']} [{n['worker']}]"
        )
    lines.append("  seconds in")
    by_seconds = sorted(notes, key=lambda n: n["seconds"], reverse=True)
    for n in by_seconds[:TABLE_ROWS]:
        lines.append(f"    {n['seconds']:.1f} {n['file']} [{n['worker']}]")
    return lines


#: (pid, creation time): a pid alone may be another process by now
Seen = Tuple[int, float]
#: seconds between two looks of a sentinel at its worker
SENTINEL_PERIOD_S = 0.5


def orphans_to_kill(
    remembered: Iterable[Seen],
    found: Mapping[int, Set[Seen]],
    spared: Iterable[int],
) -> Set[int]:
    """The sentinel's decision once its worker is gone.  ``remembered``
    are the worker's descendants as they were seen while it lived, and
    ``found`` maps a remembered pid to what is there now, the process
    and its descendants.  Killed are those still alive as the SAME
    processes, with what they have started since the last look, but for
    the ``spared`` (the sentinel itself)."""
    doomed: Set[int] = set()
    for child in remembered:
        there = found.get(child[0], set())
        if child in there:
            doomed |= {pid for pid, _ in there}
    return doomed - set(spared)


def _seen(pid: int) -> Set[Seen]:
    """The process ``pid`` as it is now and its descendants; nothing
    where it is gone (a zombie is gone: it runs nothing, and its parent
    reaps it only when its pipe closes)."""
    try:
        proc = psutil.Process(pid)
        if proc.status() == psutil.STATUS_ZOMBIE:
            return set()
        found = {(pid, proc.create_time())}
        children = proc.children(recursive=True)
    except psutil.NoSuchProcess:
        return set()
    for p in children:
        try:
            found.add((p.pid, p.create_time()))
        except psutil.NoSuchProcess:  # a child that left is not the worker
            pass
    return found


def watch_worker(pid: int) -> None:
    """A sentinel's whole life: remember the descendants of the worker
    ``pid`` twice a second; once the worker is gone, kill those still
    alive and leave."""
    try:
        worker = (pid, psutil.Process(pid).create_time())
    except psutil.NoSuchProcess:
        return
    remembered: Set[Seen] = set()
    while worker in (found := _seen(pid)):
        remembered |= found - {worker}
        time.sleep(SENTINEL_PERIOD_S)
    now = {child: _seen(child) for child, _ in remembered}
    for child in orphans_to_kill(remembered, now, [os.getpid()]):
        try:
            psutil.Process(child).kill()
        except psutil.NoSuchProcess:
            pass


def start_sentinel() -> subprocess.Popen:
    """Start this process's sentinel (:func:`watch_worker`): a process
    in a session of its own that holds none of this one's pipes, so that
    it outlives a segmentation fault here and keeps nothing open."""
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return subprocess.Popen(
        [
            sys.executable,
            "-c",
            "import sys; sys.path.insert(0, sys.argv[1]); "
            "from tests.helpers.runtime_guard import watch_worker; "
            "watch_worker(int(sys.argv[2]))",
            root,
            str(os.getpid()),
        ],
        stdin=subprocess.DEVNULL,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        close_fds=True,
        start_new_session=True,
        cwd="/",
    )
