"""Tier-1 runtime guards (tests/helpers/runtime_guard.py, wired by
tests/conftest.py).  Per test: no single non-``slow`` tier-1 test may
exceed the 60 s budget — creep toward the suite's hard limit must fail
loudly, naming its offender, not as an opaque rc=124.  Per session: a
child process or a non-daemon thread that outlives the session is named
and killed and the run fails, instead of holding the interpreter after
the summary line.  Per worker: a file's programs are given back at its
end, a worker that stood above half of ``vm.max_map_count`` fails the run
by the files that added most, and a worker that dies takes its children
with it."""

import os
import select
import signal
import subprocess
import sys
import time

import psutil
import pytest

from tests.helpers.runtime_guard import (
    TIER1_TEST_BUDGET_S,
    leftovers_message,
    mappings_message,
    mappings_tables,
    orphans_to_kill,
    over_budget_message,
)


def test_budget_is_sixty_seconds():
    # the number ISSUE 9 pins; headroom vs the measured slowest test
    # (~35 s) is part of the contract — change deliberately, not by diff
    assert TIER1_TEST_BUDGET_S == 60.0


def test_fast_tests_pass_the_guard():
    assert over_budget_message("tests/x.py::test_a", 0.5, False) is None
    assert (
        over_budget_message(
            "tests/x.py::test_a", TIER1_TEST_BUDGET_S, False
        )
        is None
    )


def test_slow_marked_tests_are_exempt():
    assert over_budget_message("tests/x.py::test_big", 500.0, True) is None


def test_over_budget_test_fails_with_an_attributing_message():
    msg = over_budget_message("tests/x.py::test_creep", 61.2, False)
    assert msg is not None
    assert "tests/x.py::test_creep" in msg  # names the offender
    assert "61.2s" in msg
    assert "slow" in msg  # tells the author the escape hatch


ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def test_conftest_wires_the_guard():
    """The hooks must actually consult the guards — a helper nobody calls
    guards nothing."""
    src = open(os.path.join(ROOT, "tests", "conftest.py")).read()
    assert "pytest_runtest_makereport" in src
    assert "over_budget_message" in src
    assert "pytest_sessionfinish" in src
    assert "leftovers_message" in src
    # the module-end release, the mappings guard, the sentinel, the tables
    assert 'fixture(scope="module", autouse=True)' in src
    assert "jax.clear_caches()" in src and "gc.collect()" in src
    assert "mappings_message" in src
    assert "pytest_configure" in src and "start_sentinel" in src
    assert "pytest_terminal_summary" in src and "mappings_tables" in src


@pytest.mark.parametrize(
    "children,threads,named,spared",
    [
        # a clean session is left alone
        ([], [], None, []),
        # a daemon thread is not held against the run
        ([], [("beat-w0", True), ("pydevd", True)], None, []),
        # a surviving child is named by pid, name and command
        (
            [(4242, "python", "python -m areal_tpu.apps.remote --x 1")],
            [("beat-w0", True)],
            ["4242", "python -m areal_tpu.apps.remote --x 1"],
            ["beat-w0"],
        ),
        # so is a thread that the interpreter would join at exit
        ([], [("Thread-1", False), ("d", True)], ["Thread-1"], ["  d"]),
    ],
)
def test_session_end_decision(children, threads, named, spared):
    msg = leftovers_message("gw3", children, threads)
    if named is None:
        assert msg is None
        return
    assert "gw3" in msg  # says whose session it was
    for piece in named:
        assert piece in msg
    for piece in spared:
        assert piece not in msg


# a test file for a session of its own: what it leaves behind is the case
_LEAVES = {
    "a_child": (
        "import subprocess, sys\n"
        "def test_leaves_a_child():\n"
        "    subprocess.Popen([sys.executable, '-c',"
        " 'import time; time.sleep(300)'])\n"
    ),
    "a_daemon_thread": (
        "import threading, time\n"
        "def test_leaves_a_daemon_thread():\n"
        "    threading.Thread(target=time.sleep, args=(300,),"
        " daemon=True, name='left-daemon').start()\n"
    ),
}


def _runs(pid):
    """Whether ``pid`` still runs anything (a zombie does not, and who
    reaps an orphan is the machine's business)."""
    try:
        return psutil.Process(pid).status() != psutil.STATUS_ZOMBIE
    except psutil.NoSuchProcess:
        return False


def _toy_session(tmp_path, files, workers, timeout=120):
    """A session of its own under this repo's conftest over ``files``
    (name -> source): its return code and what it printed."""
    import shutil

    tests = tmp_path / "tests"
    tests.mkdir()
    shutil.copy(os.path.join(ROOT, "tests", "conftest.py"), tests)
    (tests / "__init__.py").write_text("")
    for name, source in files.items():
        (tests / name).write_text(source)
    # the copied conftest imports ``tests.helpers`` of THIS checkout
    os.symlink(os.path.join(ROOT, "tests", "helpers"), tests / "helpers")
    os.symlink(os.path.join(ROOT, "areal_tpu"), tmp_path / "areal_tpu")
    cmd = [sys.executable, "-m", "pytest", "tests", "-q",
           "-p", "no:cacheprovider"]
    if workers:
        cmd += ["-p", "xdist", "-n", str(workers)]
    out = subprocess.run(
        cmd, cwd=tmp_path, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": str(tmp_path)},
    )
    return out.returncode, out.stdout + out.stderr


def _sleepers(tmp_path):
    """The ``time.sleep(300)`` children of a toy session still running."""
    found = []
    for p in psutil.process_iter(["cmdline"]):
        try:
            if (
                "time.sleep(300)" in " ".join(p.info["cmdline"] or [])
                and str(tmp_path) in p.cwd()
                and _runs(p.pid)
            ):
                found.append(p)
        except psutil.Error:
            pass
    return found


@pytest.mark.parametrize(
    "leaves,workers,rc,said",
    [
        ("a_child", 0, 1, "time.sleep(300)"),
        ("a_child", 2, 1, "time.sleep(300)"),
        ("a_daemon_thread", 0, 0, None),
    ],
)
def test_a_session_that_leaves_something_fails_and_says_what(
    tmp_path, leaves, workers, rc, said
):
    """The wired check, in a session of its own under this repo's
    conftest: alone (``workers`` 0) and through an xdist worker's last
    message to its controller.  All its tests pass; what is left behind
    decides the status, and the child is gone afterwards."""
    code, text = _toy_session(
        tmp_path, {"test_it.py": _LEAVES[leaves]}, workers
    )
    assert "1 passed" in text, text
    assert code == rc, text
    if said is None:
        assert "session-end guard" not in text
    else:
        assert "session-end guard" in text and said in text
    assert not _sleepers(tmp_path)


# forks a sleeper, as the verifier's pool is forked: the child holds every
# descriptor of its worker, the pipe to the controller among them
_SEGFAULTS = """
import os, signal, time
def test_forks_a_sleeper_and_segfaults():
    pid = os.fork()
    if pid == 0:
        time.sleep(300)
        os._exit(0)
    open("sleeper.pid", "w").write(str(pid))
    time.sleep(1.5)  # the sentinel looks twice a second
    os.kill(os.getpid(), signal.SIGSEGV)
def test_after_it():
    pass
"""


def test_a_worker_that_dies_costs_one_test_and_not_the_run(tmp_path):
    """The wired sentinel: the controller sees the worker go down, fails
    the test it was running by name, starts another worker and counts
    every other test; the sleeper is gone.  At the parent commit this
    session has no "node down" and waits for whoever kills it."""
    files = {
        "test_a.py": _SEGFAULTS,
        "test_b.py": "def test_one():\n    pass\ndef test_two():\n    pass\n",
    }
    began = time.monotonic()
    code, text = _toy_session(tmp_path, files, workers=2, timeout=60)
    assert time.monotonic() - began < 30, text
    assert code == 1, text
    assert "node down" in text and "crashed while running" in text
    assert "tests/test_a.py::test_forks_a_sleeper_and_segfaults" in text
    assert "1 failed, 3 passed" in text, text
    assert not _runs(int((tmp_path / "sleeper.pid").read_text()))


def _note(worker, file, start, end, after=None, seconds=1.0):
    return {
        "worker": worker, "file": file, "start": start, "end": end,
        "after": start if after is None else after, "seconds": seconds,
    }


@pytest.mark.parametrize(
    "notes,named",
    [
        # nothing ran
        ([], None),
        # at half the limit and under it: silent
        ([_note("gw0", "tests/a.py", 500, 5_000)], None),
        (
            [
                _note("gw0", "tests/a.py", 500, 4_000),
                _note("gw1", "tests/b.py", 900, 2_000),
            ],
            None,
        ),
        # over: the worker, then its files by what each added, highest
        # first; a worker that stayed under is not named
        (
            [
                _note("gw0", "tests/small.py", 500, 900, after=600),
                _note("gw0", "tests/large.py", 600, 5_200, after=700),
                _note("gw0", "tests/medium.py", 700, 5_001, after=4_900),
                _note("gw1", "tests/other.py", 500, 4_999),
            ],
            ["gw0 stood at 5200 after tests/large.py", "+4600 tests/large.py",
             "+4301 tests/medium.py (left 4900)", "+400 tests/small.py"],
        ),
    ],
)
def test_mappings_decision(notes, named):
    msg = mappings_message(notes, limit=10_000)
    if named is None:
        assert msg is None
        return
    assert "10000" in msg  # says what the limit is
    at = [msg.index(piece) for piece in named]  # each is there...
    assert at == sorted(at)  # ...in this order
    assert "gw1" not in msg and "tests/other.py" not in msg


def test_the_summary_has_three_tables():
    notes = [
        _note(f"gw{i % 2}", f"tests/f{i}.py", 500, 500 + 100 * i, seconds=20 - i)
        for i in range(20)
    ]
    lines = mappings_tables(notes)
    assert mappings_tables([]) == []
    text = "\n".join(lines)
    # each worker's highest count and the file at which it stood
    assert "gw0: 2300 tests/f18.py (10 files" in text
    assert "gw1: 2400 tests/f19.py (10 files" in text
    # fifteen files by mappings added, fifteen by seconds, highest first
    added = lines[lines.index("  mappings added (start -> end, after the release) by") + 1:]
    assert added[0].startswith("    +1900 (500 -> 2400, 500) tests/f19.py [gw1]")
    assert added[15] == "  seconds in"
    assert added[16] == "    20.0 tests/f0.py [gw0]"
    assert len(added) == 31 and added[-1].startswith("    6.0 tests/f14.py")


@pytest.mark.parametrize(
    "remembered,found,spared,doomed",
    [
        # nothing was ever started
        ([], {}, [7], set()),
        # a child still alive goes, with what it started since the last
        # look; one that left, or whose pid is another process by now, is
        # let be; the sentinel never kills itself
        (
            [(10, 1.0), (11, 1.5), (12, 2.0), (7, 0.5)],
            {
                10: {(10, 1.0), (20, 9.0)},
                11: set(),
                12: {(12, 8.0), (21, 9.0)},
                7: {(7, 0.5)},
            },
            [7],
            {10, 20},
        ),
    ],
)
def test_sentinel_decision(remembered, found, spared, doomed):
    assert orphans_to_kill(remembered, found, spared) == doomed


# a "worker": starts its sentinel, then `sleep 300` holding the write end
# of the test's pipe (argv[2]), says the sleeper's pid, and ends as asked
_WORKER = """
import os, signal, subprocess, sys, time
sys.path.insert(0, sys.argv[1])
from tests.helpers.runtime_guard import start_sentinel
sentinel = start_sentinel()
w = int(sys.argv[2])
sleeper = subprocess.Popen(["sleep", "300"], pass_fds=[w])
print(sleeper.pid, sentinel.pid, flush=True)
time.sleep(2.0)  # the sentinel looks twice a second
if sys.argv[3] == "segfault":
    os.kill(os.getpid(), signal.SIGSEGV)
sentinel.kill()
sentinel.wait()
"""


@pytest.mark.parametrize("ends", ["segfault", "normally"])
def test_a_worker_that_dies_takes_its_children_with_it(ends):
    """The sentinel alone: a worker that segfaults leaves nobody holding
    its pipe (the reader sees EOF, the child is gone); one that ends its
    sentinel and leaves normally has its child left alone."""
    r, w = os.pipe()
    worker = subprocess.Popen(
        [sys.executable, "-c", _WORKER, ROOT, str(w), ends],
        pass_fds=[w], stdout=subprocess.PIPE, text=True,
    )
    os.close(w)
    sleeper = None
    try:
        sleeper, sentinel = map(int, worker.stdout.readline().split())
        # the worker ends 2 s from here; dead, it stays a zombie until
        # somebody waits for it, as an xdist worker does until its
        # controller reads EOF
        at_eof, _, _ = select.select(
            [r], [], [], 7.0 if ends == "segfault" else 3.5
        )
        if ends == "segfault":
            assert at_eof and os.read(r, 1) == b""
            assert worker.wait(timeout=5) == -signal.SIGSEGV
            deadline = time.monotonic() + 3.0
            while _runs(sleeper) or _runs(sentinel):
                assert time.monotonic() < deadline
                time.sleep(0.05)
        else:
            assert not at_eof
            assert worker.wait(timeout=5) == 0
            assert _runs(sleeper) and not _runs(sentinel)
    finally:
        os.close(r)
        worker.kill()
        worker.wait()
        if sleeper is not None and _runs(sleeper):
            os.kill(sleeper, signal.SIGKILL)
