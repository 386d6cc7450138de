"""Elastic distributed membership (`mxtpu/_ps.py`, `docs/elastic.md`).

Fast, socket-level tests running scheduler/server/worker IN-PROCESS
(daemon threads) with sub-second heartbeat/dead timeouts: heartbeat
edge cases, dead-node declaration, scheduler-restart re-registration,
worker-death re-rank + stranded-round completion, server-death replica
failover, and the typed no-replica abort.  The full multi-PROCESS
SIGKILL gauntlet lives in `tools/check_elastic.py` (test_tools.py).
"""
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import _ps, profiler
from mxtpu.base import PSConnectError, ServerDiedError


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _start_scheduler(monkeypatch, nw, ns, hb="0.1", dead="0.5"):
    monkeypatch.setenv("MXTPU_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("MXTPU_NUM_WORKER", str(nw))
    monkeypatch.setenv("MXTPU_NUM_SERVER", str(ns))
    monkeypatch.setenv("MXTPU_PS_HEARTBEAT_INTERVAL", hb)
    monkeypatch.setenv("MXTPU_DEAD_TIMEOUT", dead)
    sched = _ps.Scheduler(port=0)
    monkeypatch.setenv("MXTPU_PS_ROOT_PORT", str(sched._port))
    t = threading.Thread(target=sched.run, daemon=True)
    t.start()
    return sched, t


def _start_server(**kw):
    srv = _ps.Server(**kw)
    threading.Thread(target=srv.run, daemon=True).start()
    return srv


def _start_servers(n):
    """Boot n servers CONCURRENTLY: registration blocks until the
    whole server group has rendezvoused at the scheduler."""
    out = [None] * n

    def boot(i):
        srv = _ps.Server()
        out[i] = srv
        srv.run()

    for i in range(n):
        threading.Thread(target=boot, args=(i,), daemon=True).start()
    deadline = time.time() + 10
    while time.time() < deadline and any(s is None for s in out):
        time.sleep(0.02)
    assert all(s is not None for s in out), "server group never formed"
    return sorted(out, key=lambda s: s.rank)


@pytest.fixture(autouse=True)
def _fresh_worker_singleton():
    _ps.Worker._singleton = None
    yield
    _ps.Worker._singleton = None


def test_client_connect_backoff_typed_error():
    """Satellite: _Client retries with exponential backoff under a
    wall-clock deadline and raises the TYPED PSConnectError — not a
    bare ConnectionError after a fixed-sleep spin."""
    port = _free_port()  # nothing listens here
    t0 = time.monotonic()
    with pytest.raises(PSConnectError):
        _ps._Client(("127.0.0.1", port), deadline=0.5)
    elapsed = time.monotonic() - t0
    assert elapsed < 5.0, "deadline not honored (%.1fs)" % elapsed
    # PSConnectError must stay catchable as ConnectionError (existing
    # transport-failure handling relies on it)
    assert issubclass(PSConnectError, ConnectionError)


def test_heartbeat_dropped_beat_and_dead_timeout(monkeypatch):
    """A single dropped beat never marks a node dead; only silence
    past MXTPU_DEAD_TIMEOUT does — and then the monitor DECLARES it
    (visible in dead_nodes even after its stale-beat entry is gone)."""
    sched, _ = _start_scheduler(monkeypatch, nw=1, ns=0, dead="0.6")
    c = _ps._Client(("127.0.0.1", sched._port))
    info = c.request({"op": "register", "role": "worker"})
    nid = info["node_id"]
    c.request({"op": "heartbeat", "node_id": nid})
    time.sleep(0.25)  # ~2 dropped beats at the 0.1s interval
    assert c.request({"op": "dead_nodes", "timeout": 0.6})["dead"] == []
    c.request({"op": "heartbeat", "node_id": nid})  # recovers
    assert c.request({"op": "dead_nodes", "timeout": 0.6})["dead"] == []
    # now go fully silent: the monitor DECLARES us dead after ~0.6s
    # (poll the declaration itself — a stale-beat query can report the
    # node a beat earlier than the declaration lands)
    deadline = time.time() + 5
    while time.time() < deadline:
        if nid in sched._dead:
            break
        time.sleep(0.1)
    else:
        pytest.fail("node never declared dead after MXTPU_DEAD_TIMEOUT")
    assert nid in c.request({"op": "dead_nodes", "timeout": 0.6})["dead"]
    # a declared corpse stays dead to a plain heartbeat (resurrection
    # requires an explicit reregister)
    c.request({"op": "heartbeat", "node_id": nid})
    assert nid in c.request({"op": "dead_nodes", "timeout": 0.6})["dead"]
    info = c.request({"op": "group_info"})
    assert info["num_workers"] == 0 and nid in info["dead"]
    c.close()
    sched._die()


def test_reregister_after_scheduler_restart(monkeypatch):
    """Satellite: a worker's heartbeat thread survives a scheduler
    restart — it reconnects with backoff and re-registers its saved
    identity, so the fresh scheduler rebuilds its membership tables."""
    monkeypatch.setenv("MXTPU_SCHED_RECONNECT", "20")
    monkeypatch.setenv("MXTPU_RETRY_BASE", "0.05")
    sched1, _ = _start_scheduler(monkeypatch, nw=1, ns=0, dead="30")
    reregistered = profiler.get_stat("elastic_sched_reregister")
    worker = _ps.Worker()
    assert worker.node_id in sched1._last_beat
    port = sched1._port
    # wait until the heartbeat thread's own connection is up, so the
    # crash below severs an ESTABLISHED heartbeat (the reconnect path
    # under test) rather than racing the initial connect
    deadline = time.time() + 5
    while time.time() < deadline and len(sched1._conns) < 2:
        time.sleep(0.05)
    assert len(sched1._conns) >= 2

    sched1._die()  # scheduler "crashes" (all its sockets sever)
    time.sleep(0.3)
    sched2 = _ps.Scheduler(port=port)  # restarted on the same address
    threading.Thread(target=sched2.run, daemon=True).start()

    # the scheduler's tables fill while it serves the request; the
    # heartbeat thread counts the re-registration once the reply is
    # back, so wait for both
    deadline = time.time() + 15
    while time.time() < deadline:
        if worker.node_id in sched2._last_beat and \
                worker.node_id in sched2._worker_order and \
                profiler.get_stat("elastic_sched_reregister") > \
                reregistered:
            break
        time.sleep(0.1)
    else:
        pytest.fail("worker never re-registered with the restarted "
                    "scheduler")
    # rank preserved across the restart
    assert sched2._rank_of(worker.node_id) == worker.rank == 0
    worker.close()
    sched2._die()


def test_worker_death_rerank_and_round_completion(monkeypatch):
    """Worker death mid-round: the scheduler declares it dead, bumps
    the generation, re-ranks survivors, and reconfigures the servers —
    the stranded sync round completes with an nw0/live rescale so
    averaging semantics stay exact; the survivor's next barrier
    reports the new generation/rank/live-count."""
    sched, _ = _start_scheduler(monkeypatch, nw=2, ns=1, dead="0.6")
    srv = _start_server()
    worker = _ps.Worker()  # rank 0, heartbeats
    # fake second worker: registers + pushes round 1, then goes silent
    c = _ps._Client(("127.0.0.1", sched._port))
    binfo = c.request({"op": "register", "role": "worker"})
    b_nid = binfo["node_id"]

    worker.init("w", np.zeros(4, np.float32))
    sub = ("w", 0)
    worker.push("w", np.ones(4, np.float32))          # A: round 1
    sc = _ps._Client(tuple(srv._addr))
    rep = sc.request({"op": "push", "key": sub,
                      "value": np.ones(4, np.float32) * 3.0,
                      "sync": True, "worker": b_nid, "round": 1})
    assert not rep.get("error")
    np.testing.assert_allclose(worker.pull("w"), np.full(4, 4.0))

    # round 2: only A pushes; B is dead (silent).  The pull blocks
    # until the monitor declares B dead and the server completes the
    # round with the nw0/live = 2x rescale.
    worker.push("w", np.ones(4, np.float32) * 5.0)
    t0 = time.monotonic()
    out = worker.pull("w")
    assert time.monotonic() - t0 < 10
    np.testing.assert_allclose(out, np.full(4, 10.0))  # 5 * (2/1)

    worker.barrier()  # survivors-only barrier releases immediately
    assert worker.gen >= 1
    assert worker.live_workers == 1
    assert worker.rank == 0
    assert b_nid in worker.num_dead_nodes()
    c.close()
    worker.close()
    sched._die()


def _failover_topology(monkeypatch, replication):
    monkeypatch.setenv("MXTPU_PS_REPLICATION", "1" if replication
                       else "0")
    sched, _ = _start_scheduler(monkeypatch, nw=1, ns=2, dead="0.4")
    servers = _start_servers(2)
    worker = _ps.Worker()
    return sched, servers, worker


def test_server_failover_to_replica(monkeypatch):
    """Tentpole: the shard's home server dies; the worker confirms
    death with the scheduler, promotes the chain replica on the
    successor, re-pushes anything the mirror missed, and transparently
    re-routes — values and versions survive."""
    sched, servers, worker = _failover_topology(monkeypatch, True)
    before = profiler.get_stat("elastic_failover")
    worker.init("w", np.zeros(6, np.float32))
    val = np.arange(6, dtype=np.float32)
    worker.push("w", val)
    np.testing.assert_allclose(worker.pull("w"), val)

    home = worker._chunks("w", 6)[0][0]
    servers[home]._die()
    # next op trips the failover protocol (possibly replaying round 1
    # from the retained payload if the mirror lagged)
    np.testing.assert_allclose(worker.pull("w"), val)
    assert profiler.get_stat("elastic_failover") == before + 1
    # the promoted replica now serves the shard: version advances there
    worker.push("w", val * 2)
    np.testing.assert_allclose(worker.pull("w"), val * 2)
    assert worker.key_version("w") == 2
    worker.close()
    sched._die()
    for s in servers:
        s._die()


def test_server_death_without_replication_is_typed(monkeypatch):
    """Acceptance: with MXTPU_PS_REPLICATION=0 a dead server aborts
    the run with the typed ServerDiedError — promptly, never a hang —
    and the resilience retry layer does NOT spin on it."""
    from mxtpu import resilience as res

    sched, servers, worker = _failover_topology(monkeypatch, False)
    worker.init("w", np.zeros(4, np.float32))
    worker.push("w", np.ones(4, np.float32))
    home = worker._chunks("w", 4)[0][0]
    servers[home]._die()
    t0 = time.monotonic()
    with pytest.raises(ServerDiedError):
        worker.pull("w")
    assert time.monotonic() - t0 < 15
    # ServerDiedError is permanent: guarded() must propagate, not retry
    assert not isinstance(ServerDiedError("x"), res.TRANSIENT_ERRORS)
    worker.close()
    sched._die()
    for s in servers:
        s._die()


def test_kvstore_dist_frontend_introspection(monkeypatch):
    """Satellite: KVStoreDist exposes live_workers / num_dead_node /
    rejoined / current_version (MXNet get_num_dead_node parity, backed
    by Worker.num_dead_nodes)."""
    sched, _ = _start_scheduler(monkeypatch, nw=1, ns=1, dead="30")
    _start_server()
    kv = mx.kv.create("dist_sync")
    try:
        assert kv.type == "dist_sync"
        assert kv.num_workers == 1
        assert kv.live_workers == 1
        assert kv.rejoined is False
        assert kv.num_dead_node() == 0
        assert kv.num_dead_node(node_id=2) == 0  # servers-only mask
        kv.init("x", mx.nd.zeros((3,)))
        assert kv.current_version("x") == 0
        kv.push("x", mx.nd.ones((3,)))
        out = mx.nd.empty((3,))
        kv.pull("x", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(3))
        assert kv.current_version("x") == 1
        # the non-dist base store answers the same surface
        local = mx.kv.create("local")
        assert local.live_workers == local.num_workers == 1
    finally:
        kv.close()
        sched._die()


def test_declared_dead_worker_is_fenced(monkeypatch):
    """A straggler the scheduler declared dead must not slip back into
    the group: its pushes are rejected typed (never silently completing
    a round in a live worker's place) and its barrier fails loudly."""
    sched, _ = _start_scheduler(monkeypatch, nw=2, ns=1, dead="0.5")
    srv = _start_server()
    worker = _ps.Worker()  # live, heartbeats
    c = _ps._Client(("127.0.0.1", sched._port))
    z_nid = c.request({"op": "register", "role": "worker"})["node_id"]
    worker.init("w", np.zeros(2, np.float32))
    # zombie goes silent until declared dead
    deadline = time.time() + 5
    while time.time() < deadline and z_nid not in sched._dead:
        time.sleep(0.1)
    assert z_nid in sched._dead
    time.sleep(0.3)  # let the reconfig reach the server
    sc = _ps._Client(tuple(srv._addr))
    rep = sc.request({"op": "push", "key": ("w", 0),
                      "value": np.ones(2, np.float32), "sync": True,
                      "worker": z_nid, "round": 1})
    assert rep.get("fenced") and "declared dead" in rep["error"]
    rep = c.request({"op": "barrier", "node_id": z_nid})
    assert "declared dead" in rep.get("error", "")
    # the live worker is unaffected: its solo round completes (2x
    # rescale) without the zombie's rejected contribution
    worker.push("w", np.ones(2, np.float32) * 3.0)
    np.testing.assert_allclose(worker.pull("w"), np.full(2, 6.0))
    c.close()
    sc.close()
    worker.close()
    sched._die()
    srv._die()


def test_sync_push_retry_is_idempotent(monkeypatch):
    """A retried sync push (lost reply) must not double-accumulate:
    the server dedups by (worker id, round) while pending and by round
    number once applied."""
    sched, _ = _start_scheduler(monkeypatch, nw=2, ns=1, dead="30")
    srv = _start_server()
    c = _ps._Client(tuple(srv._addr))
    sc = _ps._Client(("127.0.0.1", sched._port))
    a = sc.request({"op": "register", "role": "worker"})["node_id"]
    b = sc.request({"op": "register", "role": "worker"})["node_id"]
    c.request({"op": "init", "key": "k", "value": np.zeros(2)})
    push = {"op": "push", "key": "k", "value": np.ones(2),
            "sync": True, "worker": a, "round": 1}
    c.request(push)
    rep = c.request(push)           # in-round retry: dedup'd
    assert rep.get("duplicate")
    c.request({"op": "push", "key": "k", "value": np.ones(2),
               "sync": True, "worker": b, "round": 1})
    rep = c.request(push)           # post-apply retry: dedup'd
    assert rep.get("duplicate")
    rep = c.request({"op": "pull", "key": "k", "min_version": 1})
    np.testing.assert_allclose(rep["value"], np.full(2, 2.0))
    assert rep["version"] == 1
    c.close()
    sc.close()
    sched._die()
    srv._die()
