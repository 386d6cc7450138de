"""KVStore tests (modeled on `tests/python/unittest/test_kvstore.py` and
`tests/nightly/dist_sync_kvstore.py` of the reference)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxtpu as mx

SHAPE = (4, 4)
KEYS = [5, 7, 11]
STR_KEYS = ["b", "c", "d"]


def _init_kv(kv_type="local"):
    kv = mx.kv.create(kv_type)
    kv.init(3, mx.nd.zeros(SHAPE))
    kv.init(KEYS, [mx.nd.zeros(SHAPE)] * len(KEYS))
    return kv


def _check_diff_to_scalar(arr, x):
    np.testing.assert_allclose(arr.asnumpy(), np.full(SHAPE, x), rtol=1e-5)


@pytest.mark.parametrize("kv_type", ["local", "device", "tpu"])
def test_single_kv_pair(kv_type):
    kv = _init_kv(kv_type)
    kv.push(3, mx.nd.ones(SHAPE))
    out = mx.nd.empty(SHAPE)
    kv.pull(3, out=out)
    _check_diff_to_scalar(out, 1.0)


@pytest.mark.parametrize("kv_type", ["local", "device", "tpu"])
def test_list_kv_pair(kv_type):
    kv = _init_kv(kv_type)
    kv.push(KEYS, [mx.nd.ones(SHAPE) * 4] * len(KEYS))
    outs = [mx.nd.empty(SHAPE) for _ in KEYS]
    kv.pull(KEYS, out=outs)
    for o in outs:
        _check_diff_to_scalar(o, 4.0)


def test_aggregator_multi_device():
    """Push a list of per-device values -> reduced sum broadcast back
    (reference test_aggregator)."""
    num_devs = 4
    kv = _init_kv("device")
    vals = [mx.nd.ones(SHAPE) for _ in range(num_devs)]
    kv.push(3, vals)
    outs = [mx.nd.empty(SHAPE) for _ in range(num_devs)]
    kv.pull(3, out=outs)
    for o in outs:
        _check_diff_to_scalar(o, num_devs)


def test_tpu_allreduce_over_mesh():
    """'tpu' kvstore reduce = psum over the dp axis of the active mesh."""
    import jax

    import mxtpu.parallel as par

    n = 4
    mesh = par.create_mesh({"dp": n}, devices=jax.devices()[:n])
    with par.MeshContext(mesh):
        kv = mx.kv.create("tpu")
        kv.init(3, mx.nd.zeros(SHAPE))
        kv.push(3, [mx.nd.ones(SHAPE) * (i + 1) for i in range(n)])
        out = mx.nd.empty(SHAPE)
        kv.pull(3, out=out)
    _check_diff_to_scalar(out, sum(range(1, n + 1)))


def test_tpu_allreduce_over_the_replicas_own_devices():
    """What `Module(context=[...])` pushes with no mesh anywhere (the
    image-classification fit.py path): one value per device.  The
    reduce line is built from those devices and the sum is a psum; the
    merged value comes back on the first replica's device, where an
    updater can combine it with the stored weight."""
    n = 4
    kv = mx.kv.create("tpu")
    kv.init(3, mx.nd.ones(SHAPE))
    kv.set_updater(lambda key, recv, stored: stored.__iadd__(recv))
    vals = [mx.nd.ones(SHAPE, ctx=mx.cpu(i)) * (i + 1) for i in range(n)]
    assert len({next(iter(v._data.devices())) for v in vals}) == n
    kv.push(3, vals)
    assert kv.last_reduce_path == "psum"
    outs = [mx.nd.empty(SHAPE, ctx=mx.cpu(i)) for i in range(n)]
    kv.pull(3, out=outs)
    for o in outs:
        _check_diff_to_scalar(o, 1 + sum(range(1, n + 1)))
    # neither one device nor one device each: no line to reduce over
    with pytest.raises(mx.MXNetError, match="no line of devices"):
        kv.push(3, [vals[0], vals[0] * 2, vals[1]])


def test_updater():
    """Custom updater runs on push (reference test_updater)."""
    kv = _init_kv("device")
    kv.set_updater(lambda key, recv, stored: stored.__iadd__(recv * 2))
    kv.push(3, mx.nd.ones(SHAPE))
    out = mx.nd.empty(SHAPE)
    kv.pull(3, out=out)
    _check_diff_to_scalar(out, 2.0)
    # accumulate across pushes
    num_push = 3
    for _ in range(num_push):
        kv.push(3, mx.nd.ones(SHAPE))
    kv.pull(3, out=out)
    _check_diff_to_scalar(out, 2.0 * (num_push + 1))


def test_get_type_and_str_keys():
    kv = mx.kv.create("device")
    assert kv.type == "device"
    kv.init(STR_KEYS, [mx.nd.ones(SHAPE)] * len(STR_KEYS))
    outs = [mx.nd.empty(SHAPE) for _ in STR_KEYS]
    kv.pull(STR_KEYS, out=outs)
    for o in outs:
        _check_diff_to_scalar(o, 1.0)


def test_gradient_compression_exact():
    """2-bit quantization with error feedback matches the python model
    (reference computes expected values in
    `tests/nightly/dist_sync_kvstore.py` compute_expected_2bit_quantization)."""
    threshold = 0.5
    kv = mx.kv.create("local")
    kv.set_gradient_compression({"type": "2bit", "threshold": threshold})
    kv.init(3, mx.nd.zeros(SHAPE))

    rng = np.random.RandomState(0)
    grads = [rng.uniform(-1.2, 1.2, SHAPE).astype(np.float32)
             for _ in range(4)]
    residual = np.zeros(SHAPE, dtype=np.float32)
    for g in grads:
        kv.push(3, mx.nd.array(g))
        out = mx.nd.empty(SHAPE)
        kv.pull(3, out=out)
        x = g + residual
        expected = np.where(x > threshold, threshold,
                            np.where(x < -threshold, -threshold,
                                     0.0)).astype(np.float32)
        residual = x - expected
        np.testing.assert_allclose(out.asnumpy(), expected, rtol=1e-6)


def test_optimizer_on_kvstore():
    """set_optimizer routes pushes through the fused sgd update."""
    kv = _init_kv("device")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                         rescale_grad=1.0, wd=0.0))
    kv.push(3, mx.nd.ones(SHAPE))
    out = mx.nd.empty(SHAPE)
    kv.pull(3, out=out)
    _check_diff_to_scalar(out, -0.1)


def test_trainer_with_kvstore_device():
    """Trainer multi-replica aggregation through the kvstore."""
    from mxtpu import autograd, gluon

    net = gluon.nn.Dense(2, in_units=3)
    net.initialize(ctx=[mx.cpu(0), mx.cpu(1)])
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.0})
    x = mx.nd.ones((2, 3))
    with autograd.record():
        y = net(x)
        loss = (y * y).sum()
    loss.backward()
    trainer.step(2)  # smoke: aggregation + update runs


def test_dist_sync_kvstore_local_launcher():
    """Multi-process dist_sync over the local launcher (reference:
    `tools/launch.py -n 2 python dist_sync_kvstore.py`,
    `tests/nightly/test_all.sh:55`)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "tests", "dist_sync_kvstore.py")
    launcher = os.path.join(repo, "tools", "launch.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, launcher, "-n", "2", "-s", "2",
         sys.executable, script],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("DIST_SYNC_OK") == 2, res.stdout + res.stderr


def test_save_load_optimizer_states_roundtrip(tmp_path):
    """save_optimizer_states must persist the UPDATER's state buffers
    (momentum), not just the optimizer object (reference
    `python/mxnet/kvstore.py` saves `_updater.get_states()`)."""
    kv = _init_kv("local")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    for _ in range(3):
        kv.push(3, mx.nd.ones(SHAPE))
    fname = str(tmp_path / "opt.states")
    kv.save_optimizer_states(fname)

    kv2 = _init_kv("local")
    kv2.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    kv2.load_optimizer_states(fname)
    # momentum buffers must have survived the roundtrip
    st1 = kv._updater.states
    st2 = kv2._updater.states
    assert set(st1) == set(st2) and len(st1) > 0
    for k in st1:
        s1 = st1[k] if not isinstance(st1[k], (list, tuple)) else st1[k][0]
        s2 = st2[k] if not isinstance(st2[k], (list, tuple)) else st2[k][0]
        np.testing.assert_allclose(s1.asnumpy(), s2.asnumpy(), rtol=1e-6)


def test_ps_wire_codec_roundtrip():
    """The PS transport uses a restricted serializer (JSON + raw numpy
    buffers), never pickle, and HMAC-rejects tampered frames."""
    from mxtpu import _ps

    msg = {"op": "push", "key": ("weight", 2),
           "value": np.arange(12, dtype=np.float32).reshape(3, 4),
           "sync": True, "body": b"\x80\x05opaque", "extra": [1, 2.5, None]}
    out = _ps._decode(_ps._encode(msg))
    assert out["op"] == "push" and out["key"] == ("weight", 2)
    assert out["sync"] is True and out["body"] == b"\x80\x05opaque"
    assert out["extra"] == [1, 2.5, None]
    np.testing.assert_array_equal(out["value"], msg["value"])
    # pickle payloads must NOT execute: a malicious frame is just bytes
    evil = b"cos\nsystem\n(S'echo pwned'\ntR."
    dec = _ps._decode(_ps._encode({"body": evil}))
    assert dec["body"] == evil

    os.environ["MXTPU_PS_SECRET"] = "s3cret"
    try:
        import socket as _socket

        a, b = _socket.socketpair()
        _ps._send_msg(a, {"ok": True})
        assert _ps._recv_msg(b) == {"ok": True}
        # tampered frame fails HMAC
        payload = _ps._encode({"ok": True})
        import hashlib, hmac, struct

        mac = hmac.new(b"wrong", payload, hashlib.sha256).digest()
        framed = struct.pack("!Q", len(mac + payload)) + mac + payload
        a.sendall(framed)
        with pytest.raises(ConnectionError):
            _ps._recv_msg(b)
        a.close(); b.close()
    finally:
        del os.environ["MXTPU_PS_SECRET"]


def test_kvstore_tpu_psum_on_multi_axis_mesh():
    """kvstore=tpu must ride the XLA psum even on a MULTI-axis mesh
    (reduce along the dp line), and must say so via
    last_reduce_path rather than silently falling back."""
    import jax
    from jax.sharding import Mesh

    import mxtpu.parallel as par

    devs = np.array(jax.devices("cpu")[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("dp", "tp"))
    with par.MeshContext(mesh):
        kv = mx.kv.create("tpu")
        kv.init(1, mx.nd.zeros(SHAPE))
        vals = [mx.nd.ones(SHAPE) * (i + 1) for i in range(4)]
        kv.push(1, vals)
        assert kv.last_reduce_path == "psum", kv.last_reduce_path
        out = mx.nd.empty(SHAPE)
        kv.pull(1, out=out)
        np.testing.assert_allclose(out.asnumpy(), np.full(SHAPE, 10.0),
                                   rtol=1e-6)

    # 1-D mesh still takes the collective
    mesh1 = Mesh(np.array(jax.devices("cpu")[:4]), ("dp",))
    with par.MeshContext(mesh1):
        kv = mx.kv.create("tpu")
        kv.init(2, mx.nd.zeros(SHAPE))
        kv.push(2, [mx.nd.ones(SHAPE)] * 4)
        assert kv.last_reduce_path == "psum"

    # a count the mesh does not match, all values on one device: the
    # sum is local, and says so
    with par.MeshContext(mesh1):
        kv = mx.kv.create("tpu")
        kv.init(3, mx.nd.zeros(SHAPE))
        kv.push(3, [mx.nd.ones(SHAPE)] * 3)
        assert kv.last_reduce_path == "local"
        out = mx.nd.empty(SHAPE)
        kv.pull(3, out=out)
        np.testing.assert_allclose(out.asnumpy(), np.full(SHAPE, 3.0),
                                   rtol=1e-6)


def test_dist_async_kvstore_local_launcher():
    """Multi-process dist_async over the local launcher (reference
    `tests/nightly/dist_async_kvstore.py`): per-push async updates,
    non-divisible server shards, heartbeat dead-node detection."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "tests", "dist_async_kvstore.py")
    launcher = os.path.join(repo, "tools", "launch.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["MXTPU_KVSTORE_BIGARRAY_BOUND"] = "500000"  # force sharded big key
    res = subprocess.run(
        [sys.executable, launcher, "-n", "2", "-s", "2",
         sys.executable, script],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("DIST_ASYNC_OK") == 2, res.stdout + res.stderr


def test_dist_sync_kvstore_ssh_launcher(tmp_path):
    """The ssh launcher's whole pipeline — hostfile parse, round-robin
    role placement, env broadcast, remote command assembly, reaping —
    driven through a local `ssh` SHIM that executes the remote command
    via bash (the reference's dmlc-tracker ssh mode, tools/launch.py
    ssh.py; real multi-host needs only passwordless ssh)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shim_dir = tmp_path / "bin"
    shim_dir.mkdir()
    shim = shim_dir / "ssh"
    # drop the ssh options + hostname, run the remote command locally
    shim.write_text("#!/bin/bash\n"
                    "while [[ \"$1\" == -* ]]; do\n"
                    "  if [[ \"$1\" == -o ]]; then shift 2; "
                    "else shift; fi\n"
                    "done\n"
                    "host=\"$1\"; shift\n"
                    "exec bash -c \"$*\"\n")
    shim.chmod(0o755)
    hostfile = tmp_path / "hosts.txt"
    hostfile.write_text("127.0.0.1\n127.0.0.1\n")

    script = os.path.join(repo, "tests", "dist_sync_kvstore.py")
    launcher = os.path.join(repo, "tools", "launch.py")
    env = dict(os.environ)
    env["PATH"] = str(shim_dir) + os.pathsep + env["PATH"]
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, launcher, "-n", "2", "-s", "2",
         "--launcher", "ssh", "-H", str(hostfile),
         sys.executable, script],
        env=env, capture_output=True, text=True, timeout=300, cwd=repo)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("DIST_SYNC_OK") == 2, res.stdout + res.stderr


def test_server_controller_dispatches_app_commands():
    """The MXKVStoreRunServer controller hook: non-builtin command heads
    reach the controller; a raising controller returns an error reply
    instead of killing the server.  _command is exercised directly —
    Server.__init__ registers with a live scheduler, which the
    multi-process dist tests cover."""
    import threading

    from mxtpu import _ps

    got = []
    srv = _ps.Server.__new__(_ps.Server)
    srv._controller = lambda h, b: got.append((h, b))
    srv._local_only = True
    srv._lock = threading.Lock()
    srv._updater = None

    rep = srv._command({"head": "42", "body": b"payload"})
    assert rep == {"ok": True}
    assert got == [("42", b"payload")]

    def boom(h, b):
        raise RuntimeError("app bug")

    srv._controller = boom
    rep = srv._command({"head": "7", "body": b"x"})
    assert "error" in rep and "controller failed" in rep["error"]
