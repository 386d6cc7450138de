"""`mx.serve` (`mxtpu/serve.py`): continuous-batching model server —
micro-batcher packing parity, admission control, multi-model
isolation, SIGTERM drain, OOM degradation.  The multi-process chaos
contract (SIGKILL a replica mid-load, zero failed requests) lives in
`tools/check_serving.py`, wired into `tests/test_tools.py`."""
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import profiler, telemetry
from mxtpu.base import MemoryExhaustedError, RequestShedError
from mxtpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.initializer.Xavier(rnd_type="uniform"))
    net.hybridize()
    return net


@pytest.fixture
def server():
    srv = mx.serve.Server(max_batch=8, batch_wait_s=0.002)
    yield srv
    srv.close()


# -- micro-batcher packing parity ------------------------------------------

def _same_rows(out, exp):
    """Packing parity is to a few float32 ulps, not bitwise: XLA:CPU
    (jax 0.9.0) does not vectorize two batch sizes of one matmul
    identically, so a row computed inside a padded bucket can differ
    from the same row dispatched alone in its last bit."""
    return out.shape == exp.shape and np.allclose(out, exp, rtol=1e-6,
                                                  atol=1e-7)


def test_packing_parity(server):
    """Ragged requests packed into one bucketed program must return
    the rows a per-request dispatch returns — padding and batch
    position must be invisible (see `_same_rows` for the tolerance)."""
    net = _mlp()
    server.add_model("mlp", net, input_shape=(10,))
    server.start()
    rng = np.random.RandomState(0)
    xs = [rng.rand(n, 10).astype("float32") for n in (1, 3, 2, 5, 1, 4)]
    futs = [server.submit("mlp", x) for x in xs]
    outs = [f.result(30) for f in futs]
    for x, out in zip(xs, outs):
        exp = net(mx.nd.array(x)).asnumpy()
        assert _same_rows(out, exp), (out, exp)
    assert profiler.get_stat("serve_requests") >= len(xs)


def test_packing_parity_under_concurrency(server):
    """Many frontend threads, one batcher: every row still the same."""
    net = _mlp(seed=1)
    server.add_model("mlp", net, input_shape=(10,))
    server.start()
    failures = []

    def client(i):
        rng = np.random.RandomState(i)
        for _ in range(10):
            x = rng.rand(int(rng.randint(1, 6)), 10).astype("float32")
            out = server.infer("mlp", x)
            exp = net(mx.nd.array(x)).asnumpy()
            if not _same_rows(out, exp):
                failures.append(i)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures
    # continuous batching actually batched: fewer dispatches than
    # requests under concurrent load
    assert profiler.get_stat("serve_batches") > 0


def test_single_sample_promotion(server):
    """A bare (sample_shape) array is served as one row."""
    net = _mlp()
    server.add_model("mlp", net, input_shape=(10,))
    server.start()
    x = np.random.rand(10).astype("float32")
    out = server.infer("mlp", x)
    assert out.shape == (1, 4)


def test_unknown_model_and_bad_shape(server):
    server.add_model("mlp", _mlp(), input_shape=(10,))
    server.start()
    with pytest.raises(mx.MXNetError, match="unknown model"):
        server.submit("nope", np.zeros((1, 10), "float32"))
    with pytest.raises(mx.MXNetError, match="sample shape"):
        server.submit("mlp", np.zeros((1, 7), "float32"))


def test_submit_before_start_raises_typed(server):
    """submit() on a never-started server must raise, not admit work
    no batcher will ever pop (an orphaned future that times out
    opaquely instead of shedding)."""
    server.add_model("mlp", _mlp(), input_shape=(10,))
    with pytest.raises(mx.MXNetError, match="not started"):
        server.submit("mlp", np.ones((1, 10), "float32"))


def test_two_servers_share_the_metrics_provider():
    """A second live Server must not replace the first in
    metrics()["serve"], and closing one must not yank the survivor's
    gauges out of telemetry."""
    a = mx.serve.Server(max_batch=4, batch_wait_s=0.002)
    b = mx.serve.Server(max_batch=4, batch_wait_s=0.002)
    try:
        a.add_model("m_a", lambda x: x + 1.0, input_shape=(2,))
        b.add_model("m_b", lambda x: x * 2.0, input_shape=(2,))
        a.start(); b.start()
        a.infer("m_a", np.ones((1, 2), "float32"))
        b.infer("m_b", np.ones((1, 2), "float32"))
        sm = telemetry.metrics()["serve"]
        assert {"m_a", "m_b"} <= set(sm["models"])  # both visible
        b.close()
        sm = telemetry.metrics()["serve"]
        assert "m_a" in sm["models"]  # survivor still reporting
    finally:
        a.close()
        b.close()


def test_effective_cap_snaps_to_warmed_bucket():
    """A cap that is not itself a bucket of the policy snaps DOWN to
    the largest warmed bucket: dispatch can then only ever pad to a
    warmed signature — a cap of 20 under pow2 would otherwise clamp
    17-row batches to an unwarmed (20, ...) shape and compile on the
    serving hot path."""
    srv = mx.serve.Server(max_batch=20)
    try:
        srv.add_model("m", lambda x: x, input_shape=(3,))
        e = srv._entries["m"]
        assert e.buckets == [1, 2, 4, 8, 16]
        assert e.max_batch == 16
    finally:
        srv.close()


# -- admission control ------------------------------------------------------

def test_admission_control_sheds_per_tenant():
    """One tenant over its queued-row cap sheds typed (synchronously,
    at submit); an under-cap tenant on the SAME model is still
    admitted."""
    gate = threading.Event()
    started = threading.Event()

    def slow(x):
        started.set()
        gate.wait(10)
        return x * 2.0

    srv = mx.serve.Server(max_batch=2, queue_cap=4, batch_wait_s=0.0)
    srv.add_model("slow", slow, input_shape=(3,))
    srv.start()
    try:
        plug = srv.submit("slow", np.ones((2, 3), "float32"),
                          tenant="greedy")
        assert started.wait(10)  # the batcher is now WEDGED in-model
        futs = [srv.submit("slow", np.ones((2, 3), "float32"),
                           tenant="greedy") for _ in range(2)]
        # greedy's 4 queued rows hit the cap: the next row sheds NOW
        with pytest.raises(RequestShedError) as ei:
            srv.submit("slow", np.ones((1, 3), "float32"),
                       tenant="greedy")
        assert ei.value.reason == "queue_full"
        # the polite tenant is admitted despite greedy's full queue
        fut_polite = srv.submit("slow", np.ones((1, 3), "float32"),
                                tenant="polite")
        gate.set()
        for f in [plug] + futs:
            np.testing.assert_array_equal(f.result(30),
                                          2 * np.ones((2, 3), "f"))
        assert fut_polite.result(30).shape == (1, 3)
        assert profiler.get_stat("serve_shed::queue_full") >= 1
        shed_evs = [e for e in telemetry.events("serve")
                    if e.get("action") == "shed"]
        assert shed_evs and shed_evs[-1]["tenant"] == "greedy"
    finally:
        gate.set()
        srv.close()


def test_queue_timeout_sheds_typed():
    """A request whose deadline expires while QUEUED is shed with
    reason 'timeout', not left to hang."""
    gate = threading.Event()

    def slow(x):
        gate.wait(10)
        return x

    srv = mx.serve.Server(max_batch=2, batch_wait_s=0.0,
                          request_timeout_s=0.2)
    srv.add_model("slow", slow, input_shape=(1,))
    srv.start()
    try:
        first = srv.submit("slow", np.ones((1, 1), "float32"))
        stuck = srv.submit("slow", np.ones((2, 1), "float32"))
        time.sleep(0.4)  # let stuck's deadline lapse while queued
        gate.set()
        first.result(30)
        with pytest.raises(RequestShedError) as ei:
            stuck.result(30)
        assert ei.value.reason == "timeout"
    finally:
        gate.set()
        srv.close()


# -- multi-model / multi-tenant isolation ----------------------------------

def test_multi_model_isolation(server):
    """Two hosted models answer with THEIR weights; a model that
    raises fails only its own requests."""
    net_a = _mlp(seed=2)
    net_b = _mlp(seed=3)

    def broken(x):
        raise ValueError("broken model")

    server.add_model("a", net_a, input_shape=(10,))
    server.add_model("b", net_b, input_shape=(10,))
    server.add_model("broken", broken, input_shape=(10,))
    server.start()
    rng = np.random.RandomState(0)
    x = rng.rand(3, 10).astype("float32")
    fa = server.submit("a", x)
    fb = server.submit("b", x)
    fbad = server.submit("broken", x)
    assert np.array_equal(fa.result(30), net_a(mx.nd.array(x)).asnumpy())
    assert np.array_equal(fb.result(30), net_b(mx.nd.array(x)).asnumpy())
    with pytest.raises(ValueError, match="broken model"):
        fbad.result(30)
    # the broken model never poisons a healthy one
    assert np.array_equal(server.infer("a", x),
                          net_a(mx.nd.array(x)).asnumpy())
    assert profiler.get_stat("serve_errors") >= 1


# -- graceful degradation (OOM path) ---------------------------------------

def test_oom_shrinks_bucket_and_retries():
    """A typed MemoryExhaustedError on dispatch SHRINKS the model's
    bucket cap, requeues the batch, and every admitted request still
    completes — shed/shrink/retry, never a dead server loop."""
    calls = []

    def oomy(x):
        calls.append(x.shape[0])
        if x.shape[0] > 4:
            raise MemoryExhaustedError("injected HBM exhaustion")
        return x + 1.0

    srv = mx.serve.Server(max_batch=8, batch_wait_s=0.05)
    srv.add_model("oomy", oomy, input_shape=(2,))
    srv.start()
    try:
        futs = [srv.submit("oomy", np.full((n, 2), i, "float32"))
                for i, n in enumerate((3, 3, 2))]  # 8 rows -> bucket 8
        outs = [f.result(30) for f in futs]
        for i, (n, out) in enumerate(zip((3, 3, 2), outs)):
            np.testing.assert_array_equal(
                out, np.full((n, 2), i, "float32") + 1.0)
        assert max(calls) > 4          # the OOM really fired
        assert profiler.get_stat("serve_oom_shrink") >= 1
        entry = srv._entries["oomy"]
        assert entry.max_batch <= 4    # cap shrank
        evs = [e for e in telemetry.events("serve")
               if e.get("action") == "oom_shrink"]
        assert evs and evs[-1]["model"] == "oomy"
        # a single request wider than the shrunken cap can never fit:
        # typed failure, not an infinite requeue loop
        with pytest.raises(MemoryExhaustedError):
            srv.infer("oomy", np.ones((6, 2), "float32"))
    finally:
        srv.close()


def test_oom_at_floor_bucket_fails_typed_fast():
    """An OOM at the SMALLEST bucket has nowhere to shrink: the batch
    must fail with the original typed error immediately — not requeue
    into an OOM-redispatch busy loop that only ends when the queue
    deadline sheds it as an opaque timeout."""
    def always_oom(x):
        raise MemoryExhaustedError("injected HBM exhaustion")

    srv = mx.serve.Server(max_batch=8, batch_wait_s=0.002)
    srv.add_model("oom", always_oom, input_shape=(2,))
    srv.start()
    try:
        t0 = time.monotonic()
        with pytest.raises(MemoryExhaustedError):
            srv.infer("oom", np.ones((1, 2), "float32"))
        assert time.monotonic() - t0 < 10.0  # typed, not a 30s timeout
        # no smaller bucket existed, so the cap did not change
        assert srv._entries["oom"].max_batch == 8
    finally:
        srv.close()


def test_transient_fault_is_retried_and_chokepoint_armed():
    """The dispatch runs under the `serve` resilience chokepoint: a
    transient failure is retried with backoff (the request still
    succeeds), an ALWAYS-firing injected fault exhausts typed without
    killing the batcher loop, and the server keeps serving after the
    fault is cleared."""
    from mxtpu import resilience
    from mxtpu.resilience import RetryExhausted

    state = {"fails": 1}

    def flaky(x):
        if state["fails"]:
            state["fails"] -= 1
            raise OSError("transient wire wobble")
        return x * 3.0

    srv = mx.serve.Server(max_batch=4, batch_wait_s=0.0)
    srv.add_model("flaky", flaky, input_shape=(2,))
    srv.start()
    try:
        out = srv.infer("flaky", np.ones((2, 2), "float32"))
        np.testing.assert_array_equal(out, 3 * np.ones((2, 2), "f"))
        assert profiler.get_stat("retry_attempts::serve") >= 1
        assert profiler.get_stat("retry_recovered::serve") >= 1

        # arm the chokepoint itself: every attempt faults -> the
        # REQUEST fails typed, the serve loop survives
        resilience.inject("serve", prob=1.0, seed=5)
        try:
            with pytest.raises(RetryExhausted):
                srv.infer("flaky", np.ones((1, 2), "float32"),
                          timeout=30)
            assert profiler.get_stat("fault_injected::serve") >= 1
        finally:
            resilience.clear_faults("serve")
        out = srv.infer("flaky", np.ones((2, 2), "float32"))
        np.testing.assert_array_equal(out, 3 * np.ones((2, 2), "f"))
    finally:
        srv.close()


# -- drain ------------------------------------------------------------------

def test_drain_finishes_admitted_work_then_sheds():
    gate = threading.Event()

    def slow(x):
        gate.wait(10)
        return x

    srv = mx.serve.Server(max_batch=2, batch_wait_s=0.0)
    srv.add_model("slow", slow, input_shape=(1,))
    srv.start()
    admitted = [srv.submit("slow", np.ones((1, 1), "float32"))
                for _ in range(3)]
    drained = []
    t = threading.Thread(target=lambda: drained.append(srv.drain(30)))
    t.start()
    time.sleep(0.05)
    with pytest.raises(RequestShedError) as ei:
        srv.submit("slow", np.ones((1, 1), "float32"))
    assert ei.value.reason == "draining"
    gate.set()
    t.join(30)
    assert drained == [True]
    for f in admitted:  # admitted-before-drain work completed
        assert f.result(1).shape == (1, 1)
    srv.close()


@pytest.mark.skipif(sys.platform == "win32", reason="POSIX signals")
def test_sigterm_drains_replica(tmp_path):
    """serve_forever: SIGTERM = drain + flush + exit 0 (the launcher's
    serve-role contract).  Runs the real replica entrypoint in a
    subprocess and serves one request through HTTP first."""
    script = r"""
import os, sys
sys.path.insert(0, %r)
import numpy as np
import mxtpu as mx
from mxtpu.gluon import nn

def build(server):
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4))
    net.initialize(mx.initializer.Xavier(rnd_type="uniform"))
    net.hybridize()
    server.add_model("m", net, input_shape=(3,))

mx.serve.serve_forever(build, port=0, ready_file=%r)
print("drained-clean")
""" % (REPO, str(tmp_path / "port"))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_TELEMETRY_DIR"] = str(tmp_path / "tel")
    # output to a file, not a pipe: nothing reads the child before it
    # is ready, and a full pipe would block it (a warm compile cache
    # makes XLA:CPU log several KB per executable it loads)
    log = open(tmp_path / "replica.log", "w+")
    proc = subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=log, stderr=subprocess.STDOUT)
    try:
        deadline = time.time() + 120
        port = None
        while time.time() < deadline and port is None:
            try:
                port = int((tmp_path / "port").read_text())
            except (OSError, ValueError):
                time.sleep(0.1)
        assert port, "replica never became ready"
        ep = "127.0.0.1:%d" % port
        assert mx.serve.wait_ready([ep], 30, ["m"])
        out = mx.serve.Client([ep]).predict("m", np.ones((2, 3), "f"))
        assert out.shape == (2, 4)
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log.seek(0)
        stdout = log.read()
        log.close()
    assert proc.returncode == 0, stdout[-1500:]
    assert "drained-clean" in stdout
    # the replica flushed its final telemetry snapshot as role serve
    assert (tmp_path / "tel" / "telemetry_serve0.json").exists()


# -- failover client --------------------------------------------------------

def test_expired_head_cannot_overpack_past_cap():
    """An expired request shed at the queue HEAD mid-gather must not
    admit its unchecked successor: cap 8 with 6 rows gathered, an
    expired 1-row head and an 8-row request behind it packed 14 rows
    pre-fix — a raw dispatch at an unwarmed signature."""
    shapes = []
    gate = threading.Event()
    first_call = threading.Event()

    def model(x):
        shapes.append(x.shape[0])
        if not first_call.is_set():
            first_call.set()
            gate.wait(10)  # hold the batcher while the queue is staged
        return x

    srv = mx.serve.Server(max_batch=8, batch_wait_s=0.0)
    srv.add_model("m", model, input_shape=(1,))
    srv.start()
    try:
        plug = srv.submit("m", np.ones((1, 1), "float32"))
        assert first_call.wait(10)
        fa = srv.submit("m", np.ones((6, 1), "float32"))
        fb = srv.submit("m", np.ones((1, 1), "float32"), timeout=0.01)
        fc = srv.submit("m", np.ones((8, 1), "float32"))
        time.sleep(0.1)  # fb's deadline expires in-queue
        gate.set()
        assert plug.result(10).shape == (1, 1)
        assert fa.result(10).shape == (6, 1)
        with pytest.raises(RequestShedError):
            fb.result(10)
        assert fc.result(10).shape == (8, 1)
        assert max(shapes) <= 8, "batch packed past the cap: %s" % shapes
    finally:
        srv.close()


def test_client_fails_over_on_torn_response(server):
    """A replica dying mid-response sends valid headers then a
    truncated body: http.client raises IncompleteRead — an
    HTTPException, NOT an OSError — and the client must REPLAY on the
    next replica, not fail the request (the chaos guard caught this
    as intermittent failed requests when the SIGKILL landed between
    headers and body)."""
    import socket

    net = _mlp()
    server.add_model("mlp", net, input_shape=(10,))
    front = mx.serve.HttpFrontend(server, port=0).start()
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(4)
    torn_port = lsock.getsockname()[1]

    def torn_replica():  # headers + partial body, then a clean FIN
        import re

        while True:
            try:
                conn, _ = lsock.accept()
            except OSError:
                return
            with conn:
                # drain the WHOLE request first: closing with unread
                # inbound data sends an RST (ConnectionResetError — an
                # OSError, caught all along); a drained socket FINs,
                # and the short body surfaces as IncompleteRead
                conn.settimeout(0.5)
                buf = b""
                try:
                    while b"\r\n\r\n" not in buf or len(
                            buf.partition(b"\r\n\r\n")[2]) < int(
                            re.search(rb"(?i)content-length:\s*(\d+)",
                                      buf).group(1)):
                        d = conn.recv(65536)
                        if not d:
                            break
                        buf += d
                except (socket.timeout, AttributeError):
                    pass
                conn.sendall(b"HTTP/1.1 200 OK\r\n"
                             b"Content-Type: application/json\r\n"
                             b"Content-Length: 999\r\n\r\n{\"output")
                conn.shutdown(socket.SHUT_WR)
                time.sleep(0.1)

    threading.Thread(target=torn_replica, daemon=True).start()
    base = profiler.get_stat("serve_failover::serve0")
    try:
        client = mx.serve.Client(
            ["127.0.0.1:%d" % torn_port, "127.0.0.1:%d" % front.port],
            timeout=5)
        x = np.random.RandomState(3).rand(2, 10).astype("float32")
        out = client.predict("mlp", x)
        assert np.array_equal(out, net(mx.nd.array(x)).asnumpy())
        assert profiler.get_stat("serve_failover::serve0") == base + 1
    finally:
        lsock.close()
        front.close()


def test_client_does_not_fail_over_on_4xx(server):
    """A deterministic client error (404 unknown model) surfaces
    immediately: every replica would answer the same, so replaying it
    around the fleet would only burn rounds and tick bogus failover
    counters against live replicas."""
    import urllib.error

    server.add_model("mlp", _mlp(), input_shape=(10,))
    front = mx.serve.HttpFrontend(server, port=0).start()
    base = profiler.get_stat("serve_failover::serve0")
    try:
        client = mx.serve.Client(["127.0.0.1:%d" % front.port],
                                 timeout=5)
        with pytest.raises(urllib.error.HTTPError):
            client.predict("no_such_model", np.ones((1, 10), "f"))
        assert profiler.get_stat("serve_failover::serve0") == base
    finally:
        front.close()


# -- observability ----------------------------------------------------------

def test_serve_metrics_and_histograms(server):
    server.add_model("mlp", _mlp(), input_shape=(10,))
    server.start()
    for n in (1, 3, 5):
        server.infer("mlp", np.random.rand(n, 10).astype("float32"))
    m = telemetry.metrics()
    sm = m["serve"]
    assert sm["queue_depth"] == 0
    assert 0 < sm["batch_occupancy_pct"] <= 100
    assert sm["models"]["mlp"]["requests"] >= 3
    assert sm["models"]["mlp"]["latency_p99_s"] > 0
    assert sm["models"]["mlp"]["max_batch"] == 8
    h = m["histograms"]["serve_latency_s::mlp"]
    assert h["count"] >= 3 and h["p50"] <= h["p99"]
    # gauges land in profiler.stats() too (heartbeat/cluster rollups)
    stats = profiler.stats()
    for k in ("serve_batch_occupancy_pct", "serve_queue_depth",
              "serve_max_batch", "serve_inflight"):
        assert k in stats
        assert k in telemetry.GAUGE_STATS


def test_frontend_metrics_content_negotiation(server):
    """/metrics answers JSON by default (existing dashboards) and the
    mx.obs OpenMetrics text exposition when the Accept header asks for
    it (what a Prometheus scraper sends) — one scrape config covers
    serve replicas and training roles identically."""
    import json
    import urllib.request

    from mxtpu import obs

    server.add_model("mlp", _mlp(), input_shape=(10,))
    front = mx.serve.HttpFrontend(server, port=0).start()
    try:
        server.infer("mlp", np.random.rand(2, 10).astype("float32"))
        base = "http://127.0.0.1:%d/metrics" % front.port
        with urllib.request.urlopen(base, timeout=5) as r:
            assert "json" in r.headers.get("Content-Type")
            body = json.loads(r.read())
        assert "serve" in body and "steps" in body
        for accept in ("application/openmetrics-text; version=1.0.0",
                       "text/plain;version=0.0.4;q=0.5,*/*;q=0.1"):
            req = urllib.request.Request(base,
                                         headers={"Accept": accept})
            with urllib.request.urlopen(req, timeout=5) as r:
                assert "openmetrics-text" in r.headers["Content-Type"]
                text = r.read().decode()
        fams = obs.parse_openmetrics(text)  # strict parse
        # the serve SLO surface is in the exposition: the per-model
        # latency summary + the queue-depth gauge
        assert fams["mxtpu_serve_latency_s"]["type"] == "summary"
        keys = {lab.get("key") for _, lab, _
                in fams["mxtpu_serve_latency_s"]["samples"]}
        assert "mlp" in keys
        assert fams["mxtpu_serve_queue_depth"]["type"] == "gauge"
    finally:
        front.close()
