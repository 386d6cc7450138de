"""Performance observatory tests (`mxtpu/perf.py`, `mx.perf`,
`docs/observability.md` §Performance): phase schema on all three
dispatch paths, sampled-sync cadence, MFU math, roofline
classification, disabled mode, metrics/histogram surface, and the
input-wait double-count fix."""
import os

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import autograd, perf, profiler, sym, telemetry
from mxtpu.gluon import nn, loss as gloss, Trainer
from mxtpu.io.io import DataBatch, DataIter


@pytest.fixture(autouse=True)
def _clean_perf():
    profiler.reset_stats()
    telemetry.clear()
    perf.reset()
    perf.enable(True)
    yield
    perf.reset()
    perf.enable(True)
    telemetry.clear()


def _gluon_net():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    return net


def _gluon_steps(n, bs=8):
    net = _gluon_net()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.05})
    l2 = gloss.L2Loss()
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(bs, 10).astype("float32"))
    y = mx.nd.array(rng.rand(bs, 4).astype("float32"))
    for _ in range(n):
        with autograd.record():
            loss = l2(net(x), y)
        loss.backward()
        trainer.step(bs)
    return net


def _mlp_module(batch=8, hidden=16):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.FullyConnected(data=data, num_hidden=hidden, name="fc1")
    x = sym.Activation(data=x, act_type="relu", name="relu1")
    x = sym.FullyConnected(data=x, num_hidden=4, name="fc2")
    out = sym.SoftmaxOutput(data=x, label=label, name="softmax")
    mod = mx.mod.Module(out, data_names=("data",),
                        label_names=("softmax_label",), context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 10))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05})
    return mod


def _module_steps(mod, n, batch=8):
    rng = np.random.RandomState(0)
    b = DataBatch(
        data=[mx.nd.array(rng.rand(batch, 10).astype("float32"))],
        label=[mx.nd.array(rng.randint(0, 4, (batch,))
                           .astype("float32"))])
    for _ in range(n):
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()


# ---------------------------------------------------------------------------
# Phase schema on the three dispatch paths
# ---------------------------------------------------------------------------

def test_executor_path_phase_schema(monkeypatch):
    """Module/Executor dispatch records host_dispatch every call,
    device_compute on the sampling cadence, and the host-side
    optimizer phase from Module.update."""
    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "4")
    mod = _mlp_module()
    _module_steps(mod, 10)
    progs = perf.programs()
    name = mod._exec_group.execs[0]._insp.name
    assert name in progs, sorted(progs)
    row = progs[name]
    assert row["site"] == "executor"
    assert row["calls"] == 10 and row["steps"] == 10
    assert row["host_dispatch_us_avg"] > 0
    assert row["sync_samples"] >= 2
    assert "device_compute_us_avg" in row
    assert row["dominant_phase"] in perf.PHASES
    ph = perf.phases()
    assert ph["optimizer"]["n"] == 10 and ph["optimizer"]["sum_us"] > 0
    # gauges landed in profiler.stats()
    st = profiler.stats()
    assert st.get("perf_host_dispatch_us_last", 0) > 0
    assert st.get("perf_optimizer_us_last", 0) > 0
    assert st.get("perf_sync_samples", 0) == row["sync_samples"]


def test_cachedop_path_phase_schema(monkeypatch):
    """gluon Trainer (CachedOp recording dispatch): phase rows +
    optimizer phase from Trainer._update."""
    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "3")
    _gluon_steps(8)
    rows = [r for r in perf.programs().values()
            if r["site"] == "cachedop"]
    assert rows, perf.programs()
    row = rows[0]
    assert row["calls"] == 8 and row["sync_samples"] >= 2
    assert row["host_dispatch_us_avg"] > 0
    assert perf.phases()["optimizer"]["n"] == 8


def test_fused_train_path_phase_schema(monkeypatch):
    """FusedTrainLoop: one dispatch advances K wall steps (steps ==
    calls * K) and the sampled device span covers the whole chunk."""
    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "2")
    mod = _mlp_module()
    loop = mx.FusedTrainLoop(mod, steps_per_program=3)
    rng = np.random.RandomState(0)
    for _ in range(6):
        batches = [DataBatch(
            data=[mx.nd.array(rng.rand(8, 10).astype("float32"))],
            label=[mx.nd.array(rng.randint(0, 4, (8,))
                               .astype("float32"))])
            for _ in range(3)]
        loop.run(batches)
    loop.finalize()
    rows = [r for r in perf.programs().values()
            if r["site"] == "fused_train"]
    assert rows, perf.programs()
    row = rows[0]
    assert row["calls"] == 6 and row["steps"] == 18
    assert row["sync_samples"] >= 2
    # per-STEP device span: the sampled chunk wall divided by K
    assert row["device_compute_us_avg"] >= 0


# ---------------------------------------------------------------------------
# Sampling cadence
# ---------------------------------------------------------------------------

def test_sampled_sync_cadence(monkeypatch):
    """Exactly one device sync per MXTPU_PERF_SYNC_EVERY calls (never
    the first, which pays the compile and also counts toward the
    cadence): 13 calls at cadence 4 = samples at calls 4, 8, 12."""
    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "4")
    mod = _mlp_module()
    _module_steps(mod, 13)
    name = mod._exec_group.execs[0]._insp.name
    row = perf.programs()[name]
    assert row["sync_samples"] == 3, row
    assert profiler.stats().get("perf_sync_samples") == 3
    # each sample emitted one telemetry "perf" event
    assert len(telemetry.events("perf")) == 3


def test_sync_zero_never_blocks(monkeypatch):
    """MXTPU_PERF_SYNC_EVERY=0: host phases keep flowing, but no
    per-step block_until_ready ever runs (zero samples, zero perf
    events)."""
    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "0")
    mod = _mlp_module()
    _module_steps(mod, 8)
    name = mod._exec_group.execs[0]._insp.name
    row = perf.programs()[name]
    assert row["sync_samples"] == 0
    assert "device_compute_us_avg" not in row
    assert profiler.stats().get("perf_sync_samples", 0) == 0
    assert telemetry.events("perf") == []
    assert row["host_dispatch_us_avg"] > 0  # always-on host view


# ---------------------------------------------------------------------------
# MFU + roofline
# ---------------------------------------------------------------------------

def test_mfu_math_against_hand_computed_mlp_flops(monkeypatch):
    """report()'s MFU must equal flops / (sampled_wall * peak) with
    the flops XLA reports, and that flops figure must agree with the
    hand-computed MLP count (2*B*d_in*d_h + 2*B*d_h*d_out matmul
    flops, x3 for fwd+bwd) within a small factor (XLA adds the
    softmax/loss tail)."""
    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "4")
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "1e9")  # pinned peak
    mod = _mlp_module(batch=8, hidden=16)
    _module_steps(mod, 12)
    name = mod._exec_group.execs[0]._insp.name
    rep = perf.report()
    row = rep["programs"][name]
    assert 0.0 < row["mfu"] <= 1.0
    # the exact MFU identity, recomputed from the same observables
    wall_s = row["wall_us_avg"] / 1e6
    expect = min(1.0, row["flops"] / (wall_s * 1e9))
    assert row["mfu"] == pytest.approx(expect, rel=0.01)
    # XLA's flops vs the analytic fwd+bwd matmul count
    hand_fwd = 2 * 8 * 10 * 16 + 2 * 8 * 16 * 4
    hand_train = 3 * hand_fwd  # fwd + ~2x in the backward
    assert hand_train / 4 <= row["flops"] <= hand_train * 4, \
        (row["flops"], hand_train)


def test_roofline_classification(monkeypatch):
    """Roofline math: intensity above the ridge = compute-bound,
    below = memory-bound, degenerate inputs = None."""
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXTPU_PEAK_BYTES", "1e10")  # ridge = 100
    rf = perf.roofline(flops=1e9, bytes_accessed=1e6)  # 1000 fl/B
    assert rf["bound"] == "compute"
    assert rf["ridge_flops_per_byte"] == pytest.approx(100.0)
    rf = perf.roofline(flops=1e6, bytes_accessed=1e6)  # 1 fl/B
    assert rf["bound"] == "memory"
    assert perf.roofline(0.0, 1e6) is None
    assert perf.roofline(1e6, 0.0) is None


def test_peak_table_env_overrides(monkeypatch):
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "123.0")
    monkeypatch.setenv("MXTPU_PEAK_BYTES", "7.0")
    assert perf.peak_flops() == 123.0
    assert perf.peak_bytes() == 7.0
    monkeypatch.delenv("MXTPU_PEAK_FLOPS")
    monkeypatch.delenv("MXTPU_PEAK_BYTES")
    assert perf.peak_flops() > 0 and perf.peak_bytes() > 0
    # mfu clamps into (0, 1]
    assert perf.mfu(1e30, 1.0) == 1.0
    assert perf.mfu(0.0, 1.0) is None


def test_peak_table_is_keyed_by_device_kind():
    """ONE table, keyed by the device_kind JAX reports: the v5e row
    carries the published figures, a CPU gets a flagged estimate, and
    an accelerator that is not in the table raises instead of
    borrowing another chip's peak."""
    v5e = perf.peaks_for("tpu", "TPU v5 lite")
    assert (v5e["flops"], v5e["bytes_per_s"]) == (197e12, 819e9)
    assert (v5e["int8_ops"], v5e["hbm_bytes"]) == (393e12, 16e9)
    assert v5e["estimate"] is False
    cpu = perf.device_peaks()           # this suite runs on a CPU
    assert cpu["estimate"] is True and cpu["flops"] > 0
    with pytest.raises(mx.MXNetError, match="TPU v9000"):
        perf.peaks_for("tpu", "TPU v9000")


# ---------------------------------------------------------------------------
# Disabled mode / metrics surface
# ---------------------------------------------------------------------------

def test_disabled_mode_zero_records(monkeypatch):
    """MXTPU_PERF=0 (runtime flip): no program rows, no phase sums,
    no perf events, no perf gauges — every hook is one bool check."""
    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "2")
    perf.enable(False)
    mod = _mlp_module()
    _module_steps(mod, 6)
    assert perf.programs() == {}
    assert all(v["n"] == 0 for v in perf.phases().values())
    assert telemetry.events("perf") == []
    assert telemetry.metrics()["perf"] == {"enabled": False}
    st = profiler.stats()
    assert "perf_host_dispatch_us_last" not in st
    assert "perf_optimizer_us_last" not in st


def test_metrics_surface_histograms_and_gauges(monkeypatch):
    """metrics()["perf"] carries the phase averages + program rows,
    and the per-phase histograms ride metrics()["histograms"]."""
    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "3")
    mod = _mlp_module()
    _module_steps(mod, 7)
    m = telemetry.metrics()
    blk = m["perf"]
    assert blk["enabled"] and blk["sync_every"] == 3
    assert set(blk["phases_us_per_step"]) == \
        {"input_wait", "optimizer", "collective"}
    assert blk["programs"]
    assert blk.get("dominant_phase") in perf.PHASES
    hists = m["histograms"]
    # 7 calls, but the FIRST (trace+compile) is excluded from the
    # steady-state histogram — its wall lives in first_call_us only
    assert hists["perf_phase_us::host_dispatch"]["count"] == 6
    assert hists["perf_phase_us::device_compute"]["count"] >= 1
    assert hists["perf_phase_us::optimizer"]["count"] == 7
    # gauge names are declared gauges (cluster aggregation takes MAX)
    for g in ("perf_host_dispatch_us_last",
              "perf_device_compute_us_last", "perf_optimizer_us_last"):
        assert g in telemetry.GAUGE_STATS


def test_speedometer_prints_mfu_and_phase(monkeypatch, caplog):
    """telemetry.Speedometer reads metrics()["perf"]: '-' while no
    MFU is known, the live figure once report() populated it."""
    import logging

    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "3")
    mod = _mlp_module()
    _module_steps(mod, 7)
    speedo = telemetry.Speedometer(frequent=1)
    with caplog.at_level(logging.INFO, logger="mxtpu.telemetry"):
        speedo()
    assert "MFU" in caplog.text and "phase" in caplog.text
    perf.report()  # forces the analysis -> MFU becomes available
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="mxtpu.telemetry"):
        speedo()
    blk = telemetry.metrics()["perf"]
    assert blk.get("mfu") is not None
    assert ("%.3f" % blk["mfu"]) in caplog.text or "MFU" in caplog.text


def test_speedometer_disabled_prints_dash(caplog):
    import logging

    perf.enable(False)
    telemetry.record_step(batch_size=4)
    speedo = telemetry.Speedometer(frequent=1)
    with caplog.at_level(logging.INFO, logger="mxtpu.telemetry"):
        speedo()
    assert "MFU -" in caplog.text and "phase -" in caplog.text


# ---------------------------------------------------------------------------
# input_wait: the double-count fix + phase fold
# ---------------------------------------------------------------------------

class _SlowIter(DataIter):
    """DataIter whose next() sleeps — a measurable inner wait."""

    def __init__(self, n=4, wait_s=0.004):
        super(_SlowIter, self).__init__(batch_size=2)
        self.n = n
        self.wait_s = wait_s
        self.i = 0

    def reset(self):
        self.i = 0

    def next(self):
        import time

        if self.i >= self.n:
            raise StopIteration
        self.i += 1
        time.sleep(self.wait_s)
        return DataBatch(data=[mx.nd.zeros((2, 3))], label=None)


def test_input_wait_not_double_counted_when_nested():
    """A wrapper driving an inner DataIter through the iterator
    protocol used to stamp the SAME wall-clock wait twice (inner
    __next__ + outer loop).  With the nesting guard only the
    outermost scope records: N waits, and a total close to the true
    wall time — not ~2x it."""
    inner = _SlowIter(n=4, wait_s=0.004)
    # outer layer wrapping the inner protocol hop, telemetry-scoped
    # exactly like DataLoader.__iter__ — the inner __next__ enters a
    # nested input_wait() of its own
    it = iter(inner)
    got = 0
    import time

    t0 = time.perf_counter()
    while True:
        try:
            with telemetry.input_wait():
                next(it)  # inner __next__ also enters input_wait()
        except StopIteration:
            break
        got += 1
    wall = time.perf_counter() - t0
    assert got == 4
    m = telemetry.metrics()
    # ONE recording per wall-clock wait (the pre-fix behavior stamped
    # each wait at BOTH layers: 8 records summing to ~2x wall)
    assert m["input_waits"] == 4, m["input_waits"]
    total = m["input_wait_avg_s"] * m["input_waits"]
    assert 4 * 0.004 * 0.9 <= total <= wall * 1.2, (total, wall)


def test_input_wait_feeds_perf_phase():
    """The PR 6 gauge folds into the mx.perf schema as input_wait."""
    inner = _SlowIter(n=3, wait_s=0.003)
    for _ in inner:
        pass
    ph = perf.phases()
    assert ph["input_wait"]["n"] == 3
    assert ph["input_wait"]["sum_us"] >= 3 * 3000 * 0.5
    assert profiler.stats().get("perf_input_wait_us_last", 0) > 0


def test_serve_path_records_phase_row():
    """The mx.serve batcher registers a serve:<model> perf row whose
    host_dispatch covers the (synchronous) predict wall."""
    import mxtpu.serve as serve

    srv = serve.Server(max_batch=8)
    srv.add_model("mlp", _gluon_net(), input_shape=(10,))
    srv.start()
    try:
        rng = np.random.RandomState(0)
        for _ in range(5):
            srv.infer("mlp", rng.rand(3, 10).astype("float32"))
        rows = perf.programs()
        assert "serve:mlp" in rows, sorted(rows)
        assert rows["serve:mlp"]["site"] == "serve"
        assert rows["serve:mlp"]["host_dispatch_us_avg"] > 0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Rollups
# ---------------------------------------------------------------------------

def test_perf_rollup_and_merge_dir(tmp_path, monkeypatch):
    """merge_dir's cluster.json carries the per-rank MFU + dominant
    phase, computes the worker MFU spread, and renders perf events as
    chrome counter tracks."""
    import json

    monkeypatch.setenv("MXTPU_PERF_SYNC_EVERY", "3")
    mod = _mlp_module()
    _module_steps(mod, 7)
    perf.report()  # populate MFU
    snap = telemetry.snapshot()
    for rank, mfu in ((0, 0.5), (1, 0.2)):
        s = json.loads(json.dumps(telemetry._json_safe(snap),
                                  default=str))
        s["role"], s["rank"] = "worker", rank
        s["metrics"]["perf"]["mfu"] = mfu
        with open(os.path.join(str(tmp_path),
                               "telemetry_worker%d.json" % rank),
                  "w") as f:
            json.dump(s, f)
    cluster = telemetry.merge_dir(str(tmp_path))
    p = cluster["perf"]
    assert p["per_rank_mfu"] == {"worker0": 0.5, "worker1": 0.2}
    assert p["mfu_spread"] == pytest.approx(0.3)
    assert p["per_rank_dominant_phase"]["worker0"] in perf.PHASES
    with open(os.path.join(str(tmp_path), "merged_trace.json")) as f:
        trace = json.load(f)
    counters = [e for e in trace["traceEvents"]
                if e.get("ph") == "C" and
                str(e.get("name", "")).startswith("perf/")]
    assert counters, "no perf counter tracks in the merged trace"
