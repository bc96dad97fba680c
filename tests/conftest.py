"""Test configuration: run all tests on an 8-device virtual CPU mesh so that
every distributed feature is exercised without TPU hardware, mirroring the
reference's gloo/CPU multi-process harness (reference: realhf/base/testing.py).
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests run on the virtual 8-device CPU mesh and never open a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


#: an xdist worker's sentinel (tests/helpers/runtime_guard.py)
_SENTINEL = pytest.StashKey["subprocess.Popen"]()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests excluded from the tier-1 budget "
        "(run with `-m slow` or no marker filter)",
    )
    if hasattr(config, "workerinput"):
        # a worker that dies takes its children with it, so that its pipe
        # closes and the controller fails ONE test and goes on
        from tests.helpers.runtime_guard import start_sentinel

        config.stash[_SENTINEL] = start_sentinel()


#: what a whole run starts with, in this order
FIRST = ("tests/ops/test_tpu_compile.py", "tests/yardstick/")


def pytest_collection_modifyitems(items):
    """The longest file and the most threaded ones first.  Under ``--dist
    loadfile`` one worker holds a file whole: the described-TPU compiles
    take several times as long as any other file, and started in their
    alphabetical turn they are what the run waits for at its end, five
    workers idle.  The benchmark drivers' end-to-end tests (a minute of
    many threads each) came last by the alphabet, beside the experiments
    that run as five processes or threads (``tests/system``), which then
    took 2.6 times what they take alone and tripped the 60 s guard."""

    def turn(item):
        for n, prefix in enumerate(FIRST):
            if item.nodeid.startswith(prefix):
                return n
        return len(FIRST)

    items.sort(key=turn)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Tier-1 per-test runtime guard: a PASSING non-``slow`` test whose
    call phase ran past the per-test budget becomes a loud failure
    naming the offender, instead of silently pushing the suite toward
    its 870 s hard timeout (tests/helpers/runtime_guard.py)."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when != "call" or not rep.passed:
        return
    from tests.helpers.runtime_guard import over_budget_message

    msg = over_budget_message(
        item.nodeid, call.duration, is_slow="slow" in item.keywords
    )
    if msg is not None:
        rep.outcome = "failed"
        rep.longrepr = msg


#: seconds a child or a thread that is already on its way out gets
LEFTOVER_GRACE_S = 3.0
#: the controller's list of what its workers' sessions left behind
_LEFTOVERS = pytest.StashKey[list]()
#: what the files of this process noted, and on the controller its workers'
_FILE_NOTES = pytest.StashKey[list]()


@pytest.fixture(scope="module", autouse=True)
def _give_back_what_the_file_compiled(request):
    """After a file's last test, release what it compiled.  Every
    program XLA's CPU backend compiles costs the process memory
    mappings, and past ``vm.max_map_count`` the next compile segfaults
    the worker; ``jax.clear_caches()`` followed by a collection gives
    them back (only a ``Compiled`` that something still references keeps
    its own).  Under ``--dist loadfile`` a worker holds a file whole, so
    nothing inside a file compiles twice.  The counts and the seconds are
    noted for the summary and the mappings guard
    (tests/helpers/runtime_guard.py)."""
    import gc
    import time

    from tests.helpers.runtime_guard import mappings_now

    config = request.config
    note = {
        "worker": getattr(config, "workerinput", {}).get(
            "workerid", "the controller"
        ),
        "file": request.node.nodeid,
        "start": mappings_now(),
    }
    began = time.monotonic()
    yield
    note["seconds"] = round(time.monotonic() - began, 3)
    note["end"] = mappings_now()
    jax.clear_caches()
    gc.collect()
    note["after"] = mappings_now()
    config.stash.setdefault(_FILE_NOTES, []).append(note)


def _session_leftovers(where, sentinel):
    """What this process would take into its exit: (message or None)
    after killing the children it names.  The ``sentinel`` is ended and
    not counted."""
    import threading
    import time

    import psutil

    from tests.helpers.runtime_guard import leftovers_message

    deadline = time.monotonic() + LEFTOVER_GRACE_S
    if sentinel is not None:
        sentinel.kill()
        sentinel.wait()
    _, alive = psutil.wait_procs(
        psutil.Process().children(recursive=True), timeout=LEFTOVER_GRACE_S
    )
    children = []
    for p in alive:
        try:
            if p.status() != psutil.STATUS_ZOMBIE:
                children.append((p.pid, p.name(), " ".join(p.cmdline())))
                p.kill()
        except psutil.NoSuchProcess:
            pass
    others = [
        t for t in threading.enumerate() if t is not threading.main_thread()
    ]
    for t in others:
        if not t.daemon:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
    threads = [(t.name, t.daemon) for t in others if t.is_alive()]
    return leftovers_message(where, children, threads)


def pytest_testnodedown(node, error):
    """The controller's half: keep what a worker's session-end guard
    and its files' notes sent with its last message."""
    output = getattr(node, "workeroutput", {})
    if output.get("leftovers"):
        node.config.stash.setdefault(_LEFTOVERS, []).append(
            output["leftovers"]
        )
    node.config.stash.setdefault(_FILE_NOTES, []).extend(
        output.get("file_notes", [])
    )


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session, exitstatus):
    """Tier-1 session-end guard, the other half of the per-test one: a
    child process that outlives the session keeps the pipe the driver
    reads open (two idle workers of the verifier's forked pool did, for
    as long as the driver waited), and a non-daemon thread is joined at
    interpreter exit; either way the command returns long after its
    summary line, and the failure mode is an opaque rc=124.  Here each is
    named, the children are killed, and the run fails at once
    (tests/helpers/runtime_guard.py).  A worker sends its message to the
    controller; the controller looks last, when its workers are down,
    and there holds their files' notes to the mappings guard too: a
    worker that stood above half of ``vm.max_map_count`` fails the run
    by the files that added most."""
    from tests.helpers.runtime_guard import mappings_limit, mappings_message

    config = session.config
    worker_id = getattr(config, "workerinput", {}).get("workerid")
    msg = _session_leftovers(
        worker_id or "the controller", config.stash.get(_SENTINEL, None)
    )
    if worker_id is not None:
        if msg:
            config.workeroutput["leftovers"] = msg
        config.workeroutput["file_notes"] = config.stash.get(_FILE_NOTES, [])
        return
    found = config.stash.get(_LEFTOVERS, []) + ([msg] if msg else [])
    crept = mappings_message(
        config.stash.get(_FILE_NOTES, []), mappings_limit()
    )
    if crept:
        found.append(crept)
    if not found:
        return
    reporter = config.pluginmanager.get_plugin("terminalreporter")
    reporter.ensure_newline()
    for m in found:
        for line in m.splitlines():
            reporter.write_line(line, red=True)
    session.exitstatus = pytest.ExitCode.TESTS_FAILED


def pytest_terminal_summary(terminalreporter, config):
    """Where the mappings and the seconds went: what the next writer
    reads from the log instead of guessing."""
    from tests.helpers.runtime_guard import mappings_tables

    for line in mappings_tables(config.stash.get(_FILE_NOTES, [])):
        terminalreporter.write_line(line)


@pytest.fixture(autouse=True)
def _fresh_globals():
    """Reset process-global state between tests."""
    from areal_tpu.base import constants, name_resolve

    yield
    name_resolve.reset()
    constants.reset()
    from areal_tpu.models import transformer

    transformer.set_ambient_mesh(None)
    from areal_tpu.observability import set_registry

    set_registry(None)  # fresh metric series per test
    # what lives as long as its process does, which here is the whole
    # session, ends with the test: the verifier's forked pool, and the
    # checkpointers with their non-daemon threads
    math_verify = sys.modules.get("areal_tpu.verifiers.math_verify")
    if math_verify is not None:
        math_verify._shutdown_pool()
    checkpoint = sys.modules.get("areal_tpu.engine.checkpoint")
    if checkpoint is not None:
        for name in ("_checkpointer", "_quant_checkpointer"):
            if getattr(checkpoint, name) is not None:
                getattr(checkpoint, name).close()
                setattr(checkpoint, name, None)
