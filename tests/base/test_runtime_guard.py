"""Tier-1 runtime guards (tests/helpers/runtime_guard.py, wired by
tests/conftest.py).  Per test: no single non-``slow`` tier-1 test may
exceed the 60 s budget — creep toward the suite's hard limit must fail
loudly, naming its offender, not as an opaque rc=124.  Per session: a
child process or a non-daemon thread that outlives the session is named
and killed and the run fails, instead of holding the interpreter after
the summary line."""

import os
import subprocess
import sys
import threading

import pytest

from tests.helpers.runtime_guard import (
    TIER1_TEST_BUDGET_S,
    leftovers_message,
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
    import shutil

    import psutil

    tests = tmp_path / "tests"
    tests.mkdir()
    shutil.copy(os.path.join(ROOT, "tests", "conftest.py"), tests)
    (tests / "__init__.py").write_text("")
    (tests / "test_it.py").write_text(_LEAVES[leaves])
    # the copied conftest imports ``tests.helpers`` of THIS checkout
    os.symlink(os.path.join(ROOT, "tests", "helpers"), tests / "helpers")
    os.symlink(os.path.join(ROOT, "areal_tpu"), tmp_path / "areal_tpu")
    cmd = [sys.executable, "-m", "pytest", "tests/test_it.py", "-q",
           "-p", "no:cacheprovider"]
    if workers:
        cmd += ["-p", "xdist", "-n", str(workers)]
    out = subprocess.run(
        cmd, cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": str(tmp_path)},
    )
    text = out.stdout + out.stderr
    assert "1 passed" in text, text
    assert out.returncode == rc, text
    if said is None:
        assert "session-end guard" not in text
    else:
        assert "session-end guard" in text and said in text
    sleepers = [
        p for p in psutil.process_iter(["cmdline"])
        if "time.sleep(300)" in " ".join(p.info["cmdline"] or [])
        and str(tmp_path) in (p.cwd() if p.is_running() else "")
    ]
    assert not sleepers
