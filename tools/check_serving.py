#!/usr/bin/env python
"""Serving chaos guard: a replica SIGKILL mid-load must cost ZERO
failed requests.

Drives a REAL 2-replica `mx.serve` fleet (tools/launch.py
--serve-replicas 2: each replica a separate process hosting the same
deterministically-initialized MLP behind the HTTP frontend) under a
closed-loop load generator, then:

  1. mid-load, SIGKILLs replica 0 (the pid file the launcher wrote) —
     the failover `mx.serve.Client` must replay every affected
     request on replica 1: ZERO failed requests, and every output
     must match the locally-computed expected values (failover must
     not silently return garbage);
  2. the measured end-to-end p99 (client-side `telemetry.Histogram`)
     must stay within ``--p99-budget-ms`` ACROSS the kill;
  3. the surviving replica is SIGTERMed and must DRAIN (exit 0), so
     `launch.py --allow-serve-failures 1` exits 0 overall;
  4. the merged telemetry rollup (cluster.json) must NAME the
     failover: the client's ``serve_failover::serve0`` counter in the
     aggregate, plus serve throughput counters from the survivor.

Usage: python tools/check_serving.py [--duration S] [--p99-budget-ms N]
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# full-rate trace sampling: the client span per request must not evict
# the mid-run failover event from the telemetry ring before the
# end-of-load trace assertions read it
os.environ.setdefault("MXTPU_TELEMETRY_RING", "32768")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SEED = 7
SAMPLE = (10,)


def build_model():
    """The model every replica hosts — FIXED seed, so all replicas
    (and the parent's expected-value oracle) hold identical weights."""
    import mxtpu as mx
    from mxtpu.gluon import nn

    mx.random.seed(SEED)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.initializer.Xavier(rnd_type="uniform"))
    net.hybridize()
    return net


# ---------------------------------------------------------------------------
# child: one serving replica
# ---------------------------------------------------------------------------

def run_replica(args):
    import mxtpu as mx

    def build(server):
        server.add_model("mlp", build_model(), input_shape=SAMPLE)

    rank = int(os.environ.get("MXTPU_SERVE_RANK", "0"))
    ready = os.path.join(args.ready_dir, "ready-%d.port" % rank) \
        if args.ready_dir else None
    mx.serve.serve_forever(build, ready_file=ready)
    return 0


# ---------------------------------------------------------------------------
# parent: fleet + closed-loop load + kill + assertions
# ---------------------------------------------------------------------------

def _wait_ports(ready_dir, n, deadline_s=120):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        ports = {}
        for i in range(n):
            path = os.path.join(ready_dir, "ready-%d.port" % i)
            try:
                ports[i] = int(open(path).read())
            except (OSError, ValueError):
                break
        if len(ports) == n:
            return ports
        time.sleep(0.1)
    raise RuntimeError("replicas not ready within %ds" % deadline_s)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--child", default=None, choices=[None, "serve"])
    ap.add_argument("--ready-dir", default=None)
    ap.add_argument("--duration", type=float, default=8.0,
                    help="closed-loop load seconds")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--p99-budget-ms", type=float, default=2000.0)
    ap.add_argument("--kill-after", type=float, default=2.0,
                    help="SIGKILL replica 0 this many seconds in")
    args = ap.parse_args()
    if args.child == "serve":
        return run_replica(args)

    import numpy as np

    import mxtpu as mx
    from mxtpu import profiler, telemetry

    failures = []
    workdir = tempfile.mkdtemp(prefix="check_serving_")
    tdir = os.path.join(workdir, "telemetry")
    pid_dir = os.path.join(workdir, "pids")
    ready_dir = os.path.join(workdir, "ready")
    os.makedirs(ready_dir, exist_ok=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update({
        "JAX_PLATFORMS": "cpu",
        "MXTPU_SERVE_MAX_BATCH": "8",
        # a SIGKILL can land mid-persistent-cache-write; keep chaos
        # children off the shared suite cache (see check_elastic.py)
        "MXTPU_COMPILE_CACHE": "0",
    })
    cmd = [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
           "--serve-replicas", "2", "--allow-serve-failures", "1",
           "--trace-sample", "1",
           "--pid-dir", pid_dir, "--telemetry-dir", tdir,
           sys.executable, os.path.abspath(__file__),
           "--child", "serve", "--ready-dir", ready_dir]
    logf = open(os.path.join(workdir, "log"), "wb")
    launcher = subprocess.Popen(cmd, env=env, stdout=logf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    try:
        ports = _wait_ports(ready_dir, 2)
        endpoints = ["127.0.0.1:%d" % ports[i] for i in sorted(ports)]
        assert mx.serve.wait_ready(endpoints, 60, ["mlp"]), \
            "healthz never came up"
        print("check_serving: 2 replicas up on %s" % endpoints)

        telemetry.set_identity(role="client", rank=0)
        # head-sample every request: a failover replay must keep the
        # ORIGINAL trace id (one user request == one trace)
        from mxtpu import tracing
        tracing.set_sample_rate(1.0)
        client = mx.serve.Client(endpoints, timeout=10)
        hist = telemetry.histogram("client_latency_s")
        results = []   # (x, out) pairs for the oracle check
        errors = []
        res_lock = threading.Lock()
        stop = time.monotonic() + args.duration

        def load(worker_id):
            rng = np.random.RandomState(worker_id)
            while time.monotonic() < stop:
                x = rng.rand(int(rng.randint(1, 5)),
                             *SAMPLE).astype("float32")
                t0 = time.monotonic()
                try:
                    out = client.predict("mlp", x)
                except Exception as e:
                    with res_lock:
                        errors.append("%s: %s" % (type(e).__name__, e))
                    continue
                hist.record(time.monotonic() - t0)
                with res_lock:
                    results.append((x, out))

        threads = [threading.Thread(target=load, args=(i,))
                   for i in range(args.clients)]
        for t in threads:
            t.start()

        # the chaos moment: SIGKILL replica 0 mid-load
        time.sleep(args.kill_after)
        pre_kill = len(results)
        pid0 = int(open(os.path.join(pid_dir, "serve-0.pid")).read())
        os.kill(pid0, signal.SIGKILL)
        print("check_serving: SIGKILLed replica 0 (pid %d) after "
              "%d requests" % (pid0, pre_kill))
        for t in threads:
            t.join()

        n_ok, n_err = len(results), len(errors)
        print("check_serving: load done — %d ok, %d failed" % (n_ok,
                                                               n_err))
        if n_err:
            failures.append("%d FAILED requests across the kill "
                            "(first: %s)" % (n_err, errors[0]))
        if pre_kill < 1 or n_ok <= pre_kill:
            failures.append("load pattern did not straddle the kill "
                            "(%d before, %d total)" % (pre_kill, n_ok))
        fo = profiler.get_stat("serve_failover::serve0")
        if fo < 1:
            failures.append("client never recorded a failover off "
                            "replica 0")

        # tracing across the replay: the failover event must carry the
        # request's trace id, and that trace must have exactly ONE
        # client root span — the replay rides the original trace, it
        # does NOT mint a second request
        evs = telemetry.events()
        fo_traces = [e.get("trace") for e in evs
                     if e.get("kind") == "failover"
                     and e.get("site") == "serve" and e.get("trace")]
        if not fo_traces:
            failures.append("no failover event carries a trace id")
        else:
            tid = fo_traces[0]
            roots = [e for e in evs if e.get("kind") == "span"
                     and e.get("name") == "client"
                     and e.get("trace") == tid]
            if len(roots) != 1:
                failures.append(
                    "failover trace %s has %d client root spans "
                    "(want exactly 1: replay must not mint a new "
                    "trace)" % (tid, len(roots)))
            else:
                print("check_serving: failover replay kept trace %s "
                      "(1 client root span)" % tid)

        # oracle: every output must match the local model's
        oracle = build_model()
        bad = 0
        for x, out in results:
            exp = oracle(mx.nd.array(x)).asnumpy()
            if not np.allclose(out, exp, atol=1e-5):
                bad += 1
        if bad:
            failures.append("%d/%d outputs diverged from the oracle "
                            "after failover" % (bad, n_ok))
        else:
            print("check_serving: all %d outputs match the oracle"
                  % n_ok)

        snap = hist.snapshot()
        p99_ms = snap["p99"] * 1e3
        print("check_serving: client p50=%.1fms p95=%.1fms p99=%.1fms "
              "(budget %.0fms) over %d requests"
              % (snap["p50"] * 1e3, snap["p95"] * 1e3, p99_ms,
                 args.p99_budget_ms, snap["count"]))
        if p99_ms > args.p99_budget_ms:
            failures.append("p99 %.1fms blew the %.0fms budget"
                            % (p99_ms, args.p99_budget_ms))

        # flush the client's telemetry into the shared dir, then drain
        # the survivor so the launcher can merge and exit honestly
        telemetry.flush(tdir)
        pid1 = int(open(os.path.join(pid_dir, "serve-1.pid")).read())
        os.kill(pid1, signal.SIGTERM)
        rc = launcher.wait(timeout=120)
        if rc != 0:
            failures.append("launcher exited %d (survivor failed to "
                            "drain?)" % rc)

        cluster = json.load(open(os.path.join(tdir, "cluster.json")))
        agg = cluster.get("aggregate", {})
        if agg.get("serve_failover::serve0", 0) < 1:
            failures.append("telemetry rollup does not name the "
                            "serve0 failover")
        else:
            print("check_serving: rollup names the failover "
                  "(serve_failover::serve0=%d)"
                  % agg["serve_failover::serve0"])
        surv = cluster.get("roles", {}).get("serve1", {})
        if (surv.get("stats") or {}).get("serve_requests", 0) < 1:
            failures.append("survivor's telemetry shows no served "
                            "requests")
        m = telemetry.metrics()
        if "histograms" not in m or "client_latency_s" not in \
                m["histograms"]:
            failures.append("latency histogram missing from "
                            "telemetry.metrics()")
    finally:
        if launcher.poll() is None:
            try:
                os.killpg(launcher.pid, signal.SIGKILL)
            except OSError:
                launcher.kill()
            launcher.wait()
        logf.close()

    if failures:
        print("check_serving FAILED:")
        for f in failures:
            print("  - " + f)
        tail = open(os.path.join(workdir, "log"), "rb").read()[-2000:]
        print(tail.decode(errors="replace"))
        return 1
    print("check_serving OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
