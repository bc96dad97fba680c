"""An AsyncWorker hears "exit" while its poll is parked: control requests
are served beside ``_poll_async`` on the same loop, not between two polls
(``worker_base.AsyncWorker._serve_control``).  The poll here sits in a
60 s call of a fake client, as the rollout worker's does in
``allocate_rollout`` to a manager that has gone; ``exit()`` closes the
client, which is what lets the poll return at all."""

import asyncio
import threading
import time

import pytest

from areal_tpu.base import constants, name_resolve
from areal_tpu.system import worker_base

EXPR, TRIAL = "asyncexit", "t0"
PARKED_S = 60.0


class _ParkingClient:
    """A blocking call that ends when its time is up or it is closed."""

    def __init__(self):
        self.entered = threading.Event()
        self._closed = threading.Event()

    def call(self):
        self.entered.set()
        if self._closed.wait(PARKED_S):
            raise TimeoutError("client closed")

    def close(self):
        self._closed.set()


class _ParkedWorker(worker_base.AsyncWorker):
    def _configure(self, config):
        self.client = config

    async def _poll_async(self):
        try:
            await asyncio.to_thread(self.client.call)
        except TimeoutError:
            if not self.exit_requested:
                raise
        return worker_base.PollResult()

    def exit(self, status=worker_base.WorkerServerStatus.COMPLETED):
        super().exit(status)
        self.client.close()


@pytest.fixture
def panel():
    name_resolve.reconfigure("memory")
    constants.set_experiment_trial_names(EXPR, TRIAL)
    p = worker_base.WorkerControlPanel(EXPR, TRIAL)
    yield p
    p.close()


def test_exit_is_acknowledged_while_the_poll_is_parked(panel):
    worker = _ParkedWorker(worker_base.make_server("parked/0", EXPR, TRIAL))
    client = _ParkingClient()
    done = []
    t = threading.Thread(
        target=lambda: done.append(worker.run_async(client)), daemon=True
    )
    t.start()
    try:
        assert client.entered.wait(10), "the poll never parked"
        panel.connect(["parked/0"], timeout=5)
        t0 = time.monotonic()
        assert panel.request("parked/0", "exit", timeout=10) == "exiting"
        acked = time.monotonic() - t0
        t.join(timeout=10)
        ended = time.monotonic() - t0
    finally:
        client.close()
        worker.exit()
        t.join(timeout=10)
    assert acked < 2.0, f"exit acknowledged after {acked:.1f}s"
    assert not t.is_alive() and ended < 2.0, f"worker ended after {ended:.1f}s"
    assert done == [worker_base.WorkerServerStatus.COMPLETED]
    assert (
        panel.get_worker_status("parked/0")
        == worker_base.WorkerServerStatus.COMPLETED
    )
