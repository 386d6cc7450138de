"""mx.obs: live observability plane (mxtpu/obs.py).

Sampler cadence + read-only contract (a sample/scrape must never
compile or sync a device), ring bounds, disabled-mode dormancy, the
strict OpenMetrics round trip, the exporter HTTP surface, the run
ledger + compare tool, and the live aggregator's dead-rank marking.
"""
import collections
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import obs, profiler, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Every test starts dormant and leaves nothing running."""
    obs.stop(final_rows=False)
    obs.clear()
    obs.enable(True)
    with obs._lock:
        obs._STATE["run_id"] = None
    yield
    obs.stop(final_rows=False)
    obs.clear()
    obs.enable(True)
    with obs._lock:
        obs._STATE["run_id"] = None


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=5) as r:
        return r.getcode(), r.headers.get("Content-Type"), r.read()


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sample_row_schema():
    row = obs.sample()
    for key in ("kind", "ts", "seq", "run_id", "role", "rank", "pid",
                "steps", "step_time_ms", "examples_per_sec",
                "input_wait_frac", "anomalies", "retries", "failovers",
                "counters", "sample_wall_us"):
        assert key in row, key
    assert row["kind"] == "sample"
    assert obs.samples()[-1] is row
    json.dumps(row)  # JSON-safe by construction


def test_sample_and_scrape_are_readonly(monkeypatch):
    """The scrape-rule contract: building a sample row and rendering
    the OpenMetrics exposition must trigger ZERO compiles (inspect
    registry + retrace counters frozen) and ZERO device syncs
    (jax.block_until_ready is never reached)."""
    import jax

    # a real compiled program in the registry, so the MFU join has
    # something to (not) analyze
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    net.hybridize()
    net(mx.nd.array(np.ones((2, 3), "float32"))).asnumpy()

    before = profiler.stats()
    compile_keys = [k for k in before
                    if k.endswith(("_trace", "_wall_us"))
                    or k.startswith(("inspect_compile", "retrace"))
                    or k == "perf_sync_samples"]

    def _boom(*a, **k):
        raise AssertionError("a sample/scrape synced the device")

    monkeypatch.setattr(jax, "block_until_ready", _boom)
    for _ in range(5):
        assert obs.sample() is not None
        obs.parse_openmetrics(obs.openmetrics())
    monkeypatch.undo()
    after = profiler.stats()
    for k in compile_keys:
        assert after.get(k, 0) == before.get(k, 0), k


def test_sampler_cadence_and_seq(monkeypatch):
    """Drift-free cadence: tick k fires at t0 + k*interval, so the
    sample count tracks elapsed/interval and seq increments by one."""
    monkeypatch.setenv("MXTPU_OBS_SAMPLE_S", "0.1")
    port = obs.start(http_port=0)
    assert port and obs.started()
    time.sleep(0.65)
    obs.stop(final_rows=False)
    rows = obs.samples()
    assert 3 <= len(rows) <= 7, len(rows)
    seqs = [r["seq"] for r in rows]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))


def test_ring_bounds(monkeypatch):
    monkeypatch.setattr(obs, "_RING", collections.deque(maxlen=4))
    for _ in range(11):
        obs.sample()
    assert len(obs.samples()) == 4
    assert obs.samples()[-1]["seq"] > obs.samples()[0]["seq"]


def test_disabled_mode_is_dormant(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_RUN_DIR", str(tmp_path))
    obs.enable(False)
    assert obs.sample() is None
    assert obs.start(http_port=0) is None
    assert not obs.started()
    assert obs.port() is None
    assert obs.ledger_append({"kind": "x"}) is None
    assert list(tmp_path.iterdir()) == []


def test_histogram_interval_feeds_sample(monkeypatch):
    h = telemetry.histogram("obs_test_lat")
    h.reset()
    for v in (0.01, 0.01, 0.01):
        h.record(v)
    row1 = obs.sample()
    assert row1["hist_interval"]["obs_test_lat"]["count"] == 3
    for v in (1.0,):
        h.record(v)
    row2 = obs.sample()
    w = row2["hist_interval"]["obs_test_lat"]
    assert w["count"] == 1  # only the new window, not lifetime 4
    assert w["p50"] == pytest.approx(1.0, rel=0.15)


# ---------------------------------------------------------------------------
# OpenMetrics round trip + strict parser
# ---------------------------------------------------------------------------

def test_openmetrics_round_trip():
    profiler.inc_stat("obs_rt_demo")
    h = telemetry.histogram("obs_rt_lat::m1")
    h.record(0.004)
    text = obs.openmetrics()
    assert text.endswith("# EOF\n")
    fams = obs.parse_openmetrics(text)
    assert fams["mxtpu_obs"]["type"] == "info"
    fam = fams["mxtpu_obs_rt_demo"]
    assert fam["type"] == "counter"
    name, labels, value = fam["samples"][0]
    assert name == "mxtpu_obs_rt_demo_total"
    assert labels["role"] == telemetry.identity()["role"]
    assert "rank" in labels and value >= 1
    summ = fams["mxtpu_obs_rt_lat"]
    assert summ["type"] == "summary"
    quantiles = {lab.get("quantile") for _, lab, _ in summ["samples"]
                 if lab.get("quantile")}
    assert quantiles == {"0.5", "0.95", "0.99"}
    keys = {lab.get("key") for _, lab, _ in summ["samples"]}
    assert keys == {"m1"}


@pytest.mark.parametrize("bad,why", [
    ("# TYPE a counter\na_total 1\n", "no EOF"),
    ("a_total 1\n# EOF\n", "sample before TYPE"),
    ("# TYPE a counter\na 1\n# EOF\n", "counter without _total"),
    ("# TYPE a counter\na_total -1\n# EOF\n", "negative counter"),
    ("# TYPE a gauge\na 1\na 2\n# EOF\n", "duplicate sample"),
    ("# TYPE a gauge\na{x=y} 1\n# EOF\n", "unquoted label"),
    ("# TYPE 9bad gauge\n# EOF\n", "bad family name"),
    ("# TYPE a gauge\na one\n# EOF\n", "unparseable value"),
    ("# TYPE a gauge\n# TYPE a gauge\n# EOF\n", "duplicate TYPE"),
    ("# TYPE a wat\n# EOF\n", "unknown type"),
])
def test_openmetrics_parser_rejects(bad, why):
    with pytest.raises(ValueError):
        obs.parse_openmetrics(bad)
    assert why  # (documentation parameter)


# ---------------------------------------------------------------------------
# exporter HTTP surface
# ---------------------------------------------------------------------------

def test_exporter_http_surface(monkeypatch):
    monkeypatch.setenv("MXTPU_OBS_SAMPLE_S", "0.1")
    port = obs.start(http_port=0)
    base = "http://127.0.0.1:%d" % port
    code, ctype, body = _get(base + "/metrics")
    assert code == 200 and "openmetrics-text" in ctype
    obs.parse_openmetrics(body.decode())
    code, ctype, body = _get(base + "/metrics",
                             {"Accept": "application/json"})
    assert code == 200 and "json" in ctype
    assert "steps" in json.loads(body)
    _, _, body = _get(base + "/metrics.json")
    assert "steps" in json.loads(body)
    time.sleep(0.25)
    _, _, body = _get(base + "/samples.json")
    payload = json.loads(body)
    assert payload["run_id"] and len(payload["samples"]) >= 1
    _, _, body = _get(base + "/snapshot.json")
    snap = json.loads(body)
    assert "stats" in snap and "obs_samples" in snap
    _, _, body = _get(base + "/healthz")
    assert json.loads(body)["ok"] is True
    with pytest.raises(urllib.error.HTTPError):
        _get(base + "/nope")
    obs.stop(final_rows=False)


def test_exporter_port_autoincrement(monkeypatch):
    """Two processes sharing MXTPU_OBS_PORT must not collide: the
    second binds base+1 (here simulated with a blocking socket)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    taken = s.getsockname()[1]
    try:
        port = obs.start(http_port=taken)
        assert port != taken and port is not None
    finally:
        s.close()
        obs.stop(final_rows=False)


# ---------------------------------------------------------------------------
# run ledger + compare tool
# ---------------------------------------------------------------------------

def test_ledger_rows_and_summary(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_RUN_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_RUN_ID", "t_run")
    row = obs.sample()
    assert obs.ledger_append(row)
    summary = obs.summary_row()
    assert obs.ledger_append(summary)
    rows = obs.read_ledger(str(tmp_path / "t_run.jsonl"))
    assert [r["kind"] for r in rows] == ["sample", "summary"]
    s = rows[-1]
    assert s["schema"] == "mxtpu-bench-v1"
    assert s["run_id"] == "t_run"
    assert "MXTPU_RUN_DIR" in s["knobs"]
    assert isinstance(s["counters"], dict)
    for key in ("metric", "value", "unit", "throughput",
                "step_time_us", "mfu", "phases"):
        assert key in s, key


def test_stop_writes_final_rows_once(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_RUN_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_RUN_ID", "t_once")
    monkeypatch.setenv("MXTPU_OBS_SAMPLE_S", "30")
    obs.start(http_port=0)
    obs.stop()   # final sample + summary
    obs.stop()   # idempotent: no duplicate epilogue
    rows = obs.read_ledger(str(tmp_path / "t_once.jsonl"))
    kinds = [r["kind"] for r in rows]
    assert kinds == ["sample", "summary"]
    assert rows[0].get("final") is True


def test_read_ledger_tolerates_torn_tail(tmp_path):
    p = tmp_path / "torn.jsonl"
    p.write_text('{"kind": "sample", "seq": 1}\n{"kind": "sum')
    rows = obs.read_ledger(str(p))
    assert len(rows) == 1 and rows[0]["seq"] == 1


def test_compare_runs_reports_knob_and_metric_deltas(tmp_path):
    def mk(name, knobs, value, step_us, phases):
        rows = [
            {"kind": "sample", "run_id": name, "role": "worker",
             "rank": 0, "step_time_ms": step_us / 1e3, "mfu": 0.1},
            {"kind": "summary", "schema": "mxtpu-bench-v1",
             "run_id": name, "role": "worker", "rank": 0,
             "metric": "throughput", "value": value, "unit": "img/s",
             "throughput": value, "step_time_us": step_us,
             "mfu": 0.1, "phases": phases, "knobs": knobs},
        ]
        p = tmp_path / (name + ".jsonl")
        p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        return str(p)

    a = mk("ra", {"MXTPU_PASSES": "default"}, 1000.0, 900.0,
           {"host_dispatch": 120.0})
    b = mk("rb", {"MXTPU_PASSES": "off", "MXTPU_DONATE": "0"},
           1200.0, 750.0, {"host_dispatch": 80.0})
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    # B is FASTER, so the ratchet flag must stay quiet on this pass
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "compare_runs.py"), a, b,
         "--fail-on-slower", "5"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert "MXTPU_PASSES" in out and "default -> off" in out
    assert "MXTPU_DONATE" in out and "(unset) -> 0" in out
    assert "throughput" in out and "+20.0%" in out
    assert "host_dispatch" in out and "-33.3%" in out
    # reversed (A after B) the step-time ratchet must fire
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "compare_runs.py"), b, a,
         "--fail-on-slower", "5"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 1 and "REGRESSION" in r.stderr


# ---------------------------------------------------------------------------
# live aggregation + dash
# ---------------------------------------------------------------------------

def test_aggregate_once_marks_dead_rank(tmp_path):
    port = obs.start(http_port=0)
    disc = tmp_path / "obs_pid99.json"
    disc.write_text(json.dumps({"role": "worker", "rank": 7,
                                "pid": 99, "port": port,
                                "ts": time.time()}))
    state = {}
    c1 = obs.aggregate_once(str(tmp_path), state)
    assert "worker7" in c1["live"] and not c1["dead"]
    assert "worker7" in c1["roles"]
    assert (tmp_path / "cluster_live.json").exists()
    obs.stop(final_rows=False)  # endpoint goes silent, file remains
    c2 = obs.aggregate_once(str(tmp_path), state)
    assert c2["dead"] == ["worker7"]
    assert "worker7" not in c2["live"]
    assert "worker7" in c2["roles"]  # last known numbers retained
    assert c2["refreshes"] == 2
    on_disk = json.loads((tmp_path / "cluster_live.json").read_text())
    assert on_disk["dead"] == ["worker7"]


def test_dash_renders_dead_and_straggler(tmp_path):
    cluster = {
        "ts": time.time(), "refreshes": 9, "run_id": "r1",
        "live": ["worker0"], "dead": ["worker1"],
        "roles": {
            "worker0": {"steps": 50, "step_time_ms": 10.0,
                        "step_time_avg_ms": 11.0, "mfu": 0.4,
                        "dominant_phase": "device_compute",
                        "queue_depth": 0, "anomalies": 0,
                        "retries": 1, "failovers": 0},
            "worker1": {"steps": 20, "step_time_ms": 30.0,
                        "step_time_avg_ms": 29.0, "mfu": 0.1,
                        "dominant_phase": "host_dispatch",
                        "queue_depth": 0, "anomalies": 2,
                        "retries": 0, "failovers": 0},
        },
        "samples": {"worker0": [{"step_time_ms": v}
                                for v in (10, 11, 12, 10)]},
        "perf": {"mfu_spread": 0.3},
        "health": {"anomaly_total": 2,
                   "first_nonfinite": {"worker1": {"layer": "fc1",
                                                   "step": 19}}},
        "retry_total": 1, "failover_total": 0, "serve_queue_depth": 0,
    }
    p = tmp_path / "cluster_live.json"
    p.write_text(json.dumps(cluster))
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dash.py"),
         "--file", str(p), "--once"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "worker0" in r.stdout and "worker1" in r.stdout
    assert "DEAD" in r.stdout
    assert "device_compute" in r.stdout
    assert "nonfinite @ worker1" in r.stdout and "fc1" in r.stdout
    assert "MFU spread 0.300" in r.stdout


def test_armed_gating(monkeypatch):
    monkeypatch.delenv("MXTPU_OBS_PORT", raising=False)
    monkeypatch.delenv("MXTPU_RUN_DIR", raising=False)
    monkeypatch.delenv("MXTPU_TELEMETRY_DIR", raising=False)
    assert not obs.armed()
    assert obs.ensure_started() is None
    assert not obs.started()
    monkeypatch.setenv("MXTPU_RUN_DIR", "/tmp")
    assert obs.armed()
