"""mx.checkpoint — fleet-consistent async checkpointing with
deterministic resume (mxtpu/checkpoint.py, docs/checkpoint.md).

Fast in-process coverage: the async double-buffered writer (drop-and-
count, flush), fleet-manifest completeness (partial fleets invisible
to resume), full-run-state round trips (RNG chain, DataLoader
position), bitwise trainer resume through a real on-disk fleet
checkpoint (dropout masks included — RNG restore), ZeRO-1 N→M replica
resharding through the fleet bundle path, the SIGTERM
checkpoint-then-drain boundary flush, and the scheduler's idempotent
fleet stamp + server shard snapshots over an in-process PS fleet.
The multi-PROCESS SIGKILL/auto-resume gauntlet lives in
`tools/check_checkpoint.py` (test_tools.py).
"""
import json
import os
import pickle
import signal
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import _ps, checkpoint as ck, profiler, resilience as _res
from mxtpu.base import MXNetError


# ---------------------------------------------------------------------------
# AsyncSnapshotter
# ---------------------------------------------------------------------------

def test_async_snapshotter_drops_instead_of_blocking(tmp_path, monkeypatch):
    """While a write is in flight a new capture returns False in
    bounded time and ticks ``ckpt_dropped`` — the step never waits on
    the disk."""
    monkeypatch.setenv("MXTPU_CKPT_WRITE_DELAY", "0.4")
    snap = ck.AsyncSnapshotter()
    prefix = str(tmp_path / "worker0")
    arrays = {"w": np.arange(4, dtype=np.float32)}
    pre = profiler.get_stat("ckpt_dropped")
    assert snap.capture(prefix, 1, arrays) is True
    t0 = time.monotonic()
    assert snap.capture(prefix, 2, arrays) is False
    assert time.monotonic() - t0 < 0.25
    assert profiler.get_stat("ckpt_dropped") == pre + 1
    assert snap.flush(timeout=10)
    snap.close()
    got = ck.load_worker_bundle(str(tmp_path), 0)
    assert got is not None
    loaded, states, man = got
    np.testing.assert_array_equal(loaded["w"], arrays["w"])
    assert states is None and man["epoch"] == 1


def test_async_snapshotter_wait_and_states_roundtrip(tmp_path):
    snap = ck.AsyncSnapshotter()
    prefix = str(tmp_path / "worker3")
    ok = snap.capture(prefix, 7, {"b": np.zeros(2, np.float32)},
                      states=b"opaque-bytes", extra={"step": 7},
                      wait=True)
    assert ok is True
    snap.close()
    arrays, states, man = ck.load_worker_bundle(str(tmp_path), 3)
    assert states == b"opaque-bytes"
    assert man["bundle"]["step"] == 7


# ---------------------------------------------------------------------------
# fleet manifest: partial fleets are invisible as a unit
# ---------------------------------------------------------------------------

def _land_worker(d, rank, rnd):
    snap = ck.AsyncSnapshotter()
    snap.capture(os.path.join(d, "worker%d" % rank), rnd,
                 {"w": np.full(2, float(rank), np.float32)}, wait=True)
    snap.close()


def test_fleet_commits_only_when_every_role_lands(tmp_path):
    stamp = {"id": "r000004_g000", "round": 4, "gen": 0,
             "num_workers": 2, "num_servers": 0, "workers": []}
    d = ck.fleet_dir(str(tmp_path), stamp["id"])
    os.makedirs(d)
    _land_worker(d, 0, 4)
    # worker1 missing: no fleet.json, invisible to resume
    assert ck._commit_fleet(d, stamp, timeout=0.3) is False
    assert ck.read_fleet_manifest(d) is None
    assert ck.fleet_complete(d) is None
    assert ck.find_resume(str(tmp_path)) is None
    _land_worker(d, 1, 4)
    assert ck._commit_fleet(d, stamp, timeout=10) is True
    path, man = ck.find_resume(str(tmp_path))
    assert path == d and man["id"] == stamp["id"] and man["round"] == 4


def test_find_resume_picks_newest_complete_and_gc_spares_it(tmp_path):
    base = str(tmp_path)
    for rnd in (2, 5, 9):
        stamp = {"id": "r%06d_g000" % rnd, "round": rnd, "gen": 0,
                 "num_workers": 1, "num_servers": 0, "workers": []}
        d = ck.fleet_dir(base, stamp["id"])
        os.makedirs(d)
        _land_worker(d, 0, rnd)
        assert ck._commit_fleet(d, stamp, timeout=10)
    # a TORN newer fleet (no manifest) must lose to the complete round-9
    torn = ck.fleet_dir(base, "r000011_g000")
    os.makedirs(torn)
    _land_worker(torn, 0, 11)
    path, man = ck.find_resume(base)
    assert man["round"] == 9
    ck._gc_old(base, keep=1, protect=path)
    left = sorted(n for n in os.listdir(base) if n.startswith("ckpt_"))
    # newest complete survives; the torn dir is never touched
    assert left == ["ckpt_r000009_g000", "ckpt_r000011_g000"]


# ---------------------------------------------------------------------------
# full-run state: RNG chain + loader positions
# ---------------------------------------------------------------------------

class _LoaderStub(object):
    def __init__(self, pos):
        self._pos = dict(pos)
        self.applied = None

    def state(self):
        return dict(self._pos)

    def set_state(self, st):
        self.applied = dict(st)


def test_run_state_roundtrip_is_jsonable_and_bitwise(tmp_path):
    mx.random.seed(1234)
    mx.nd.random.uniform(shape=(3,)).asnumpy()  # advance the chain
    ld = _LoaderStub({"epoch": 2, "batch": 17, "seed": 5})
    st = ck.collect_run_state(loaders={"train": ld})
    json.dumps(st)  # the bundle must survive the JSON manifest
    a = mx.nd.random.uniform(shape=(8,)).asnumpy()
    b = mx.nd.random.uniform(shape=(8,)).asnumpy()
    ld2 = _LoaderStub({})
    ck.apply_run_state(st, loaders={"train": ld2})
    np.testing.assert_array_equal(
        mx.nd.random.uniform(shape=(8,)).asnumpy(), a)
    np.testing.assert_array_equal(
        mx.nd.random.uniform(shape=(8,)).asnumpy(), b)
    assert ld2.applied == {"epoch": 2, "batch": 17, "seed": 5}


# ---------------------------------------------------------------------------
# DataLoader mid-epoch deterministic re-entry
# ---------------------------------------------------------------------------

class _IdxDataset(object):
    def __init__(self, n=20):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((2,), float(i), np.float32)


def _flat(batches):
    return [b.asnumpy().tolist() for b in batches]


def test_dataloader_mid_epoch_resume_identical_stream():
    from mxtpu.gluon.data.dataloader import DataLoader

    ds = _IdxDataset(20)
    ld = DataLoader(ds, batch_size=4, shuffle=True, seed=11)
    it = iter(ld)
    head = [next(it) for _ in range(3)]
    st = ld.state()
    assert st == {"epoch": 0, "batch": 3, "seed": 11}
    rest = list(it)
    assert len(rest) == 2

    ld2 = DataLoader(ds, batch_size=4, shuffle=True, seed=11)
    ld2.set_state(st)
    assert _flat(list(ld2)) == _flat(rest)
    # both loaders continue into an IDENTICAL epoch 1 that actually
    # reshuffled relative to epoch 0
    e1a, e1b = _flat(list(ld)), _flat(list(ld2))
    assert e1a == e1b
    ld3 = DataLoader(ds, batch_size=4, shuffle=True, seed=11)
    e0 = _flat(head) + _flat(rest)
    assert _flat(list(ld3)) == e0
    assert e1a != e0


def test_dataloader_seed_mismatch_refuses_resume():
    from mxtpu.gluon.data.dataloader import DataLoader

    ds = _IdxDataset(8)
    ld = DataLoader(ds, batch_size=4, shuffle=True, seed=3)
    with pytest.raises(MXNetError):
        ld.set_state({"epoch": 0, "batch": 1, "seed": 4})


# ---------------------------------------------------------------------------
# trainer fleet checkpoint -> bitwise resume (dropout included)
# ---------------------------------------------------------------------------

def _make_net_trainer(init_seed, lr=0.1, plan=None, n_ctx=1):
    from mxtpu import gluon
    from mxtpu.gluon import nn

    net = nn.HybridSequential(prefix="ck_")
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
        net.add(nn.Dropout(0.5))
        net.add(nn.Dense(1, in_units=8))
    mx.random.seed(init_seed)
    ctxs = [mx.cpu(i) for i in range(n_ctx)]
    net.initialize(ctx=ctxs)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": lr, "momentum": 0.9},
                       sharding_plan=plan)
    return net, tr, ctxs


def _train_steps(net, tr, batches):
    from mxtpu import autograd, gluon

    loss_fn = gluon.loss.L2Loss()
    losses = []
    for bx, by in batches:
        x, y = mx.nd.array(bx), mx.nd.array(by)
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(x.shape[0])
        losses.append(float(loss.mean().asnumpy()))
    return losses


def _batches(n, bs=8, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.rand(bs, 4).astype(np.float32),
             rng.rand(bs, 1).astype(np.float32)) for _ in range(n)]


def _params_np(tr):
    return {p.name: p.data().asnumpy() for p in tr._params}


def test_trainer_boundary_checkpoint_and_bitwise_resume(tmp_path):
    """End to end through the REAL surfaces: `arm()` +
    `Trainer.step`'s boundary hook checkpoints at step 4; a fresh
    differently-initialized trainer restored from the fleet dir
    replays steps 5..6 to BITWISE-identical params — momentum state,
    RNG chain (dropout masks) and step count all round-tripped."""
    batches = _batches(6)
    net, tr, _ = _make_net_trainer(init_seed=7)
    fc = ck.FleetCheckpointer(trainer=tr, directory=str(tmp_path),
                              every=4)
    pre = profiler.get_stat("ckpt_fleet_committed")
    ck.arm(fc)
    try:
        _train_steps(net, tr, batches)
    finally:
        ck.disarm()
    assert tr.step_count == 6
    assert fc.flush(timeout=10)
    assert profiler.get_stat("ckpt_fleet_committed") == pre + 1
    found = ck.find_resume(str(tmp_path))
    assert found is not None and found[1]["round"] == 4

    net2, tr2, _ = _make_net_trainer(init_seed=99)
    meta = ck.restore_worker(trainer=tr2, directory=found[0])
    assert meta["step"] == 4 and tr2.step_count == 4
    _train_steps(net2, tr2, batches[4:])
    pa, pb = _params_np(tr), _params_np(tr2)
    assert set(pa) == set(pb)
    for k in pa:
        np.testing.assert_array_equal(pa[k], pb[k], err_msg=k)


def test_bundle_with_the_parents_tune_key_restores(tmp_path, monkeypatch):
    """Bundles written before the autotuner was removed carry its
    provenance under ``run_state["tune"]``; the reader has nothing to
    do with the key and must restore such a bundle like any other."""
    collect = ck.collect_run_state

    def as_the_parent_wrote_it(loaders=None, extra=None):
        state = collect(loaders, extra)
        state["tune"] = "tune:key=ab12cd34,donate=0,passes=default"
        return state

    monkeypatch.setattr(ck, "collect_run_state", as_the_parent_wrote_it)
    batches = _batches(3)
    net, tr, _ = _make_net_trainer(init_seed=7)
    fc = ck.FleetCheckpointer(trainer=tr, directory=str(tmp_path))
    _train_steps(net, tr, batches[:2])
    assert fc.checkpoint(2, wait=True) and fc.flush(timeout=10)
    _train_steps(net, tr, batches[2:])
    found = ck.find_resume(str(tmp_path))
    assert found is not None
    _, _, man = ck.load_worker_bundle(found[0], 0, epoch=2)
    assert man["bundle"]["run_state"]["tune"].startswith("tune:key=")

    # the restore puts the RNG chain (the dropout masks) back too
    net2, tr2, _ = _make_net_trainer(init_seed=99)
    meta = ck.restore_worker(trainer=tr2, directory=found[0])
    assert meta["step"] == 2 and tr2.step_count == 2
    _train_steps(net2, tr2, batches[2:])
    pa, pb = _params_np(tr), _params_np(tr2)
    for k in pa:
        np.testing.assert_array_equal(pa[k], pb[k], err_msg=k)


def test_zero1_fleet_bundle_reshards_n_to_m(tmp_path):
    """A fleet bundle written by a 2-replica ZeRO-1 trainer restores
    into a 4-replica one through the SAME fleet-manifest path (the
    `get_states` wire format is gathered, replica-count independent —
    `set_states` re-shards under the new plan)."""
    from mxtpu.sharding import ShardingPlan

    def _mk(n_ctx):
        from mxtpu import gluon
        from mxtpu.gluon import nn

        net = nn.Dense(2, in_units=16, prefix="z_")
        mx.random.seed(5)
        ctxs = [mx.cpu(i) for i in range(n_ctx)]
        net.initialize(ctx=ctxs)
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01},
                           sharding_plan=ShardingPlan(min_shard_elems=1))
        return net, tr, ctxs

    from mxtpu import autograd, gluon

    net, tr, ctxs = _mk(2)
    rng = np.random.RandomState(2)
    loss_fn = gluon.loss.L2Loss()
    for _ in range(3):
        xs = [mx.nd.array(rng.rand(4, 16).astype(np.float32), ctx=c)
              for c in ctxs]
        ys = [mx.nd.array(rng.rand(4, 2).astype(np.float32), ctx=c)
              for c in ctxs]
        with autograd.record():
            losses = [loss_fn(net(x), y) for x, y in zip(xs, ys)]
        for l in losses:
            l.backward()
        tr.step(8)
    assert tr._zero1 is not None
    pre = profiler.get_stat("zero1_state_reshards")
    fc = ck.FleetCheckpointer(trainer=tr, directory=str(tmp_path),
                              every=0)
    assert fc.checkpoint(3, wait=True)
    path, man = ck.find_resume(str(tmp_path))

    net1, tr1, _ = _mk(4)
    ck.restore_worker(trainer=tr1, directory=path)
    assert profiler.get_stat("zero1_state_reshards") > pre
    assert tr1._zero1 is not None and tr1._zero1.n == 4
    for p0, p1 in zip(tr._params, tr1._params):
        np.testing.assert_array_equal(p0.data().asnumpy(),
                                      p1.data().asnumpy(), err_msg=p0.name)
    # gathered optimizer state equal across the replica-count change
    g0, g1 = tr._zero1._gather_full(), tr1._zero1._gather_full()
    assert set(g0) == set(g1)
    for idx in g0:
        if g0[idx] is None:
            continue
        for a, b in zip(g0[idx], g1[idx]):
            np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())


# ---------------------------------------------------------------------------
# SIGTERM preemption: checkpoint-then-drain at the next boundary
# ---------------------------------------------------------------------------

def test_preemption_flushes_final_fleet_snapshot(tmp_path):
    batches = _batches(3, seed=4)
    net, tr, _ = _make_net_trainer(init_seed=21)
    _train_steps(net, tr, batches[:2])
    fc = ck.FleetCheckpointer(trainer=tr, directory=str(tmp_path),
                              every=0)
    pre = profiler.get_stat("ckpt_preempt_flushed")
    ck.install_preemption(fc, exit_after=False)
    try:
        assert ck.active()
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.monotonic() + 5
        while not _res.preempted() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert _res.preempted()
        # the handler only set the flag; the boundary does the work
        _train_steps(net, tr, batches[2:])
    finally:
        ck.disarm()
        _res.remove_preemption_hook()
    assert profiler.get_stat("ckpt_preempt_flushed") == pre + 1
    path, man = ck.find_resume(str(tmp_path))
    assert man["round"] == 3  # flushed at the step-3 boundary
    arrays, _, bman = ck.load_worker_bundle(path, 0)
    assert bman["bundle"]["step"] == 3


# ---------------------------------------------------------------------------
# PS fleet: idempotent scheduler stamp + server shard snapshots
# ---------------------------------------------------------------------------

def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture
def _fleet(monkeypatch):
    monkeypatch.setenv("MXTPU_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("MXTPU_NUM_WORKER", "1")
    monkeypatch.setenv("MXTPU_NUM_SERVER", "1")
    monkeypatch.setenv("MXTPU_PS_HEARTBEAT_INTERVAL", "0.2")
    monkeypatch.setenv("MXTPU_DEAD_TIMEOUT", "30")
    _ps.Worker._singleton = None
    sched = _ps.Scheduler(port=0)
    monkeypatch.setenv("MXTPU_PS_ROOT_PORT", str(sched._port))
    threading.Thread(target=sched.run, daemon=True).start()
    srv = _ps.Server()
    threading.Thread(target=srv.run, daemon=True).start()
    yield sched, srv
    sched._die()
    srv._die()
    _ps.Worker._singleton = None


def test_fleet_stamp_idempotent_and_server_snapshot(tmp_path, _fleet):
    sched, srv = _fleet
    kv = mx.kv.create("dist_sync")
    try:
        kv.init("p", mx.nd.zeros((3,)))
        kv.push("p", mx.nd.ones((3,)))
        out = mx.nd.empty((3,))
        kv.pull("p", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.ones(3))

        s1 = kv.checkpoint_stamp(1)
        s2 = kv.checkpoint_stamp(1)
        # the stamp is the fleet barrier: every worker asking about
        # round 1 gets the SAME id/generation/live-set
        assert s1 == s2
        assert s1["round"] == 1 and s1["num_workers"] == 1 \
            and s1["num_servers"] == 1
        s3 = kv.checkpoint_stamp(2)
        assert s3["id"] != s1["id"]

        kv.server_checkpoint(str(tmp_path), s1)
        deadline = time.monotonic() + 10
        got = None
        while got is None and time.monotonic() < deadline:
            got = ck.load_server_snapshot(str(tmp_path), 0)
            if got is None:
                time.sleep(0.05)
        assert got is not None, "server snapshot never landed"
        blob, rnd = got
        assert rnd == 1
        shard = pickle.loads(blob)
        assert shard["versions"] and \
            max(shard["versions"].values()) >= 1
        assert any(np.allclose(np.asarray(v), 1.0)
                   for v in shard["store"].values())
    finally:
        kv.close()
