"""ROUTER serve-loop concurrency: N threaded REQ clients against a
live manager socket — every reply reaches exactly the client that
asked (no lost or cross-wired replies), legacy REQ wire compat holds
in both serve modes, a slow weight-update fan-out runs OFF the
serve thread so fast schedule RPCs never queue behind it, a mixed
rollout + gateway storm with a weight update in its middle lands every
schedule exactly once (the batched client in fewer RPCs, by the manager's
own count), and two gateways racing one manager never over-admit a
capped tenant."""

import pickle
import queue
import threading
import time

import pytest
import zmq

from areal_tpu.api.system_api import GserverManagerConfig
from areal_tpu.base import logging_
from areal_tpu.base.monitor import RolloutStat
from areal_tpu.system.gserver_manager import (
    GserverManager,
    GserverManagerClient,
)

N_SERVERS = 4


class _SlowGenClient:
    """Weight-update fan-out stand-in: every RPC sleeps."""

    def __init__(self, rpc_s):
        self.rpc_s = rpc_s

    def call(self, cmd, payload, timeout=None):
        time.sleep(self.rpc_s)
        if cmd == "update_weights":
            return {"num_interrupted": 0}
        return {}


def _manager(serve_mode, rpc_s=0.0, **cfg_kwargs):
    m = GserverManager.__new__(GserverManager)
    m.config = GserverManagerConfig(
        schedule_policy="least_requests",
        n_servers=N_SERVERS,
        serve_mode=serve_mode,
        **cfg_kwargs,
    )
    m.server_addrs = [f"s{i}" for i in range(N_SERVERS)]
    m.logger = logging_.getLogger("test-router")
    m._round_robin = 0
    m._qid_server = {}
    m._server_load = {a: 0 for a in m.server_addrs}
    m._server_tokens = {a: 0.0 for a in m.server_addrs}
    m._server_devices = {a: 1 for a in m.server_addrs}
    m._server_mesh = {a: "" for a in m.server_addrs}
    m._qid_tokens = {}
    m._group_server = {}
    m._group_prefix = {}
    m._group_tokens = {}
    m.rollout_stat = RolloutStat()
    m._model_version = 0
    m._expr, m._trial = "test-exp", "test-router"
    m._clients = {a: _SlowGenClient(rpc_s) for a in m.server_addrs}
    m._init_metrics()
    m._serve_mode = serve_mode
    m._ctx = zmq.Context.instance()
    m._sock = m._ctx.socket(
        zmq.ROUTER if serve_mode == "router" else zmq.REP
    )
    port = m._sock.bind_to_random_port("tcp://127.0.0.1")
    m.addr = f"127.0.0.1:{port}"
    return m


@pytest.fixture
def served():
    """Yield a factory that binds a manager and runs its serve loop on
    a thread (blocking poll, like the deployed worker); tears every
    started manager down after the test.  ``update_on``: a pair of
    events; once the first is set the serve thread itself publishes
    weight version 1, as the worker's ``_poll`` does (inline in rep
    mode, on the update pool in router mode), and sets the second."""
    started = []

    def start(serve_mode, update_on=None, **kwargs):
        m = _manager(serve_mode, **kwargs)
        stop = threading.Event()

        def loop():
            while not stop.is_set():
                if m._sock.poll(timeout=10):
                    m._serve()
                if update_on and update_on[0].is_set() and (
                    not update_on[1].is_set()
                ):
                    m._start_weight_update({
                        "version": 1, "path": "test-ckpt-v1",
                        "format": "hf",
                    })
                    update_on[1].set()

        t = threading.Thread(target=loop, daemon=True)
        t.start()
        started.append((m, stop, t))
        return m

    yield start
    for m, stop, t in started:
        stop.set()
        t.join(timeout=5.0)
        pool = getattr(m, "_update_pool", None)
        if pool is not None:
            pool.shutdown(wait=False)
        m._sock.close(linger=0)


def test_router_replies_reach_their_own_client(served):
    """Each of N concurrent clients issues schedule_batch calls with a
    DISTINCT batch size — a lost reply would hang that client's REQ
    (surfaced as its timeout) and a cross-wired reply would return the
    wrong response length.  All accounting must balance afterwards."""
    m = served("router")
    n_clients, rounds = 8, 20
    errors = []
    barrier = threading.Barrier(n_clients)

    def worker(t):
        size = t + 1  # unique per client: length mismatches catch
        client = GserverManagerClient(addr=m.addr, timeout=15.0)
        try:
            barrier.wait()
            for r in range(rounds):
                qids = [f"c{t}-r{r}-m{j}" for j in range(size)]
                out = client.call("schedule_batch", {
                    "qids": qids,
                    "prompt_len": 64,
                    "new_token_budget": 32,
                })
                if len(out["responses"]) != size:
                    errors.append(f"c{t}: got {len(out['responses'])}")
                    return
                for resp in out["responses"]:
                    if resp["url"] not in m.server_addrs:
                        errors.append(f"c{t}: bad url {resp['url']}")
                        return
        except Exception as e:  # noqa: BLE001 - surfaced via errors
            errors.append(f"c{t}: {type(e).__name__}: {e}")
        finally:
            client.close()

    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True)
        for t in range(n_clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not errors, errors
    total = sum((t + 1) * rounds for t in range(n_clients))
    assert len(m._qid_server) == total
    assert sum(m._server_load.values()) == total


@pytest.mark.parametrize("serve_mode", ["router", "rep"])
def test_legacy_req_wire_compat(served, serve_mode):
    """The raw pickled (cmd, payload) REQ protocol works unchanged
    against both serve loops — no client-side envelope handling."""
    m = served(serve_mode)
    sock = zmq.Context.instance().socket(zmq.REQ)
    sock.connect(f"tcp://{m.addr}")
    try:
        sock.send(pickle.dumps(("schedule_request", {
            "qid": "legacy-q0", "prompt_len": 8, "new_token_budget": 4,
        })))
        assert sock.poll(timeout=10_000)
        resp = pickle.loads(sock.recv())
        assert resp["url"] in m.server_addrs
        assert resp["version"] == 0
        # errors still round-trip as {"error": ...}
        sock.send(pickle.dumps(("no_such_cmd", {})))
        assert sock.poll(timeout=10_000)
        assert "error" in pickle.loads(sock.recv())
    finally:
        sock.close(linger=0)


def test_slow_weight_update_does_not_block_schedules(served):
    """Fire a weight update whose fan-out takes ~1s (slow per-server
    RPCs); schedule RPCs issued while it is in flight must complete
    promptly — the update runs on the async pool, not the serve
    thread — and the version bump lands once it finishes."""
    m = served("router", rpc_s=0.25)
    client = GserverManagerClient(addr=m.addr, timeout=15.0)
    try:
        info = {"version": 1, "path": "test-ckpt-v1", "format": "hf"}
        m._start_weight_update(info)
        fut = m._weight_update_fut
        assert fut is not None and not fut.done()
        overlapped = 0
        for i in range(10):
            t0 = time.perf_counter()
            resp = client.call("schedule_request", {
                "qid": f"fast-{i}", "prompt_len": 16,
                "new_token_budget": 8,
            })
            dt = time.perf_counter() - t0
            assert resp["url"] in m.server_addrs
            # each RPC is microseconds of handler work; anything near
            # the fan-out's wall means scheduling queued behind it
            assert dt < 2.0, dt
            if not fut.done():
                overlapped += 1
        assert overlapped > 0  # some schedules truly ran mid-update
        fut.result(timeout=30.0)  # surfaces a crashed fan-out
        m._harvest_weight_update()
        assert m._model_version == 1
        assert m._weight_update_fut is None
    finally:
        client.close()


def test_router_batches_drained_under_one_lock_pass(served):
    """The batch-size histogram must observe drains > 1 when requests
    pile up while a previous batch is being served."""
    m = served("router")
    n_clients = 6
    stop = threading.Event()
    barrier = threading.Barrier(n_clients + 1)

    def worker(t):
        client = GserverManagerClient(addr=m.addr, timeout=15.0)
        try:
            barrier.wait()
            i = 0
            while not stop.is_set():
                client.call("schedule_request", {
                    "qid": f"b{t}-{i}", "prompt_len": 8,
                    "new_token_budget": 4,
                })
                i += 1
        finally:
            client.close()

    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True)
        for t in range(n_clients)
    ]
    for t in threads:
        t.start()
    barrier.wait()
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join(timeout=15.0)
    batch_sum, batch_cnt = m._m_ctl_batch.snapshot()
    assert batch_cnt > 0
    assert batch_sum > batch_cnt  # at least one drain served > 1 req


@pytest.mark.parametrize(
    "serve_mode, batched",
    [("rep", False), ("router", False), ("router", True)],
)
def test_storm_with_mid_storm_update_lands_every_schedule_once(
    served, serve_mode, batched
):
    """Rollout groups and gateway requests from threaded clients, with a
    weight update published by the serve thread as the storm lands: every
    logical schedule is placed exactly once, the update completes, and
    the manager counts one RPC a group and one a gateway request from the
    batched client against one a sibling and two a gateway request."""
    n_groups, group_size, n_gateway, n_threads = 6, 4, 8, 4
    fire, fired = threading.Event(), threading.Event()
    m = served(serve_mode, rpc_s=0.02, update_on=(fire, fired))
    jobs = queue.Queue()
    for g in range(n_groups):
        jobs.put(("rollout", g))
    for i in range(n_gateway):
        jobs.put(("gateway", i))
    errors = []
    barrier = threading.Barrier(n_threads + 1)
    size = {"prompt_len": 64, "new_token_budget": 32}

    def worker():
        client = GserverManagerClient(addr=m.addr, timeout=30.0)
        try:
            barrier.wait()
            while True:
                try:
                    kind, i = jobs.get_nowait()
                except queue.Empty:
                    return
                if kind == "rollout":
                    qids = [f"r{i}-{j}" for j in range(group_size)]
                    if batched:
                        out = client.call(
                            "schedule_batch", {"qids": qids, **size}
                        )
                        assert len(out["responses"]) == group_size
                    else:
                        for q in qids:
                            client.call(
                                "schedule_request", {"qid": q, **size}
                            )
                elif batched:
                    resp = client.call("gateway_submit", {
                        "tenant": "interactive", "tokens": 96.0,
                        "qid": f"gw{i}", **size,
                    })
                    assert resp["ok"] and resp["schedule"]["url"]
                else:
                    assert client.call("gateway_admit", {
                        "tenant": "interactive", "tokens": 96.0,
                    })["ok"]
                    client.call(
                        "schedule_request", {"qid": f"gw{i}", **size}
                    )
        except Exception as e:  # noqa: BLE001 - surfaced via errors
            errors.append(f"{type(e).__name__}: {e}")
        finally:
            client.close()

    threads = [
        threading.Thread(target=worker, daemon=True)
        for _ in range(n_threads)
    ]
    for t in threads:
        t.start()
    barrier.wait()
    fire.set()
    for t in threads:
        t.join(timeout=60.0)
    assert not errors, errors
    assert fired.wait(timeout=30.0)
    fut = getattr(m, "_weight_update_fut", None)
    if fut is not None:
        fut.result(timeout=30.0)
        m._harvest_weight_update()
    assert m._model_version == 1

    want = {f"r{g}-{j}" for g in range(n_groups) for j in range(group_size)}
    want |= {f"gw{i}" for i in range(n_gateway)}
    assert set(m._qid_server) == want
    assert sum(m._server_load.values()) == len(want)
    rpcs = {
        cmd: m._m_ctl_requests.value(cmd=cmd)
        for cmd in ("schedule_request", "schedule_batch",
                    "gateway_admit", "gateway_submit")
    }
    if batched:
        assert rpcs == {
            "schedule_request": 0.0, "schedule_batch": n_groups,
            "gateway_admit": 0.0, "gateway_submit": n_gateway,
        }
    else:
        assert rpcs == {
            "schedule_request": len(want), "schedule_batch": 0.0,
            "gateway_admit": n_gateway, "gateway_submit": 0.0,
        }


def test_two_gateways_never_over_admit_a_capped_tenant(served):
    """Two front doors (a ``FleetBackend`` each, with its own manager
    connection) race one manager's admission plane over the combined
    ``gateway_submit`` RPC.  The capped tenant's bucket holds exactly
    ``cap`` requests and refills too slowly to matter, so the pair is
    admitted exactly ``cap`` between them (one more would mean a decision
    escaped the plane's lock) and both stay live for the open tenant."""
    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.gateway.server import FleetBackend

    n_requests, cap, est = 12, 5, 24.0
    m = served("router", tenants=[
        dict(name="capped", priority="bulk", rate_tokens_per_s=1e-6,
             burst_tokens=cap * est),
        dict(name="interactive", priority="interactive"),
    ])

    def inp(qid):
        ids = [7, 8, 9, 10]
        return APIGenerateInput(
            qid=qid, prompt_ids=ids, input_ids=ids,
            gconfig=GenerationHyperparameters(max_new_tokens=20),
        )

    results, errors = {}, []
    barrier = threading.Barrier(3)

    def gateway(name):
        client = GserverManagerClient(addr=m.addr, timeout=30.0)
        # admitted requests have nowhere real to go: this is the
        # admission plane's test, not generation's
        backend = FleetBackend(
            client, client_factory=lambda addr: _SlowGenClient(0.0)
        )
        admitted = rejected = open_ok = 0
        try:
            barrier.wait()
            for i in range(n_requests):
                dec, handle = backend.admit_and_submit(
                    inp(f"{name}-cap{i}"), "capped", est, False
                )
                if dec.get("ok"):
                    admitted += 1
                    assert handle["url"] in m.server_addrs
                else:
                    rejected += 1
                    assert dec["reason"] == "rate_limited", dec
                dec, handle = backend.admit_and_submit(
                    inp(f"{name}-int{i}"), "interactive", est, False
                )
                open_ok += bool(dec.get("ok") and handle)
        except Exception as e:  # noqa: BLE001 - surfaced via errors
            errors.append(f"{name}: {type(e).__name__}: {e}")
        finally:
            client.close()
        results[name] = (admitted, rejected, open_ok)

    threads = [
        threading.Thread(target=gateway, args=(g,), daemon=True)
        for g in ("gw0", "gw1")
    ]
    for t in threads:
        t.start()
    barrier.wait()
    for t in threads:
        t.join(timeout=60.0)
    assert not errors, errors
    assert sum(r[0] for r in results.values()) == cap, results
    assert sum(r[1] for r in results.values()) == 2 * n_requests - cap
    assert [r[2] for r in results.values()] == [n_requests] * 2
    st = m._admission.stats()["capped"]
    assert st["admitted_total"] == cap
    assert st["rejects"] == {"rate_limited": 2 * n_requests - cap}
