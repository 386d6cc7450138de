"""Device-memory observatory (`mxtpu/hbm.py`): per-class static plan
decode on all three dispatch paths (Executor / CachedOp /
FusedTrainLoop) including donation-aliasing, the live census + planted
leak detector, headroom/capacity planning, and the consumer wiring
(telemetry metrics block, obs sample/OpenMetrics, health OOM
forensics, cluster rollup, dash cell, ZeRO-1 measured freed bytes).
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import autograd, hbm, obs, profiler, telemetry
from mxtpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(autouse=True)
def _clean_state():
    profiler.reset_stats()
    mx.inspect.reset()
    telemetry.clear()
    hbm.reset()
    hbm.enable(True)
    yield
    mx.inspect.reset()
    hbm.reset()
    hbm.enable(True)


def _mlp_sym():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(data=fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(data=act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(
        data=fc2, label=mx.sym.Variable("softmax_label"), name="softmax")


def _executor(train=True, batch=4):
    ex = _mlp_sym().simple_bind(mx.cpu(), data=(batch, 10),
                                softmax_label=(batch,))
    ex.forward(is_train=train, data=mx.nd.ones((batch, 10)))
    if train:
        ex.backward()
    return ex


def _hybrid_net(train=True, batch=4):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((batch, 10))
    net(x).wait_to_read()
    if train:
        with autograd.record():
            out = net(x)
        out.backward()
    return net


def _fused_loop(optimizer="adam"):
    from mxtpu.fused_train import FusedTrainLoop
    from mxtpu.io.io import DataBatch

    sym = _mlp_sym()
    mod = mx.mod.Module(sym, data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params={"learning_rate": 0.01})
    loop = FusedTrainLoop(mod, steps_per_program=2)
    rng = np.random.RandomState(0)
    batches = [DataBatch(
        data=[mx.nd.array(rng.rand(8, 10).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 4, 8).astype(np.float32))])
        for _ in range(2)]
    loop.run(batches)
    return loop


def _assert_reconciles(plan):
    assert "error" not in plan, plan
    peak = plan["peak_bytes"]
    assert peak > 0
    assert sum(plan["classes"].values()) == peak
    assert abs(plan["classes"]["unattributed"]) <= 0.10 * peak


# ---------------------------------------------------------------------------
# static plan decode: the three dispatch paths
# ---------------------------------------------------------------------------

def test_plan_executor_train_reconciles_and_layer_joins():
    ex = _executor(train=True)
    plan = hbm.plan(ex._insp, kind="train")
    _assert_reconciles(plan)
    c = plan["classes"]
    assert c["params"] > 0 and c["grads"] > 0 and c["data"] > 0
    assert "fc1" in plan["by_layer"] and "fc2" in plan["by_layer"]
    assert plan["batch"] == 4
    # the plan attaches to the record and rides inspect.report()
    assert ex._insp.memory_plan is plan
    rep = mx.inspect.report("executor:softmax", kind="train")
    assert rep["memory_plan"]["classes"] == c


def test_plan_cachedop_infer_and_train():
    net = _hybrid_net(train=True)
    rec = net._cached_op._insp
    infer = hbm.plan(rec, kind="infer")
    train = hbm.plan(rec, kind="train")
    _assert_reconciles(infer)
    _assert_reconciles(train)
    assert infer["classes"]["grads"] == 0
    assert train["classes"]["grads"] > 0
    assert train["peak_bytes"] > infer["peak_bytes"]


def test_plan_fused_donation_not_double_counted():
    loop = _fused_loop(optimizer="adam")
    plan = hbm.plan(loop._insp, kind="train")
    _assert_reconciles(plan)
    # params + adam state are donated into the K-step program: the
    # aliased bytes must be SEEN, named once, and excluded from the
    # class budget (the exact-sum assert proves no double-count)
    assert plan["alias_bytes"] > 0
    assert plan["donated_aliased_bytes"] == plan["alias_bytes"]
    c = plan["classes"]
    assert c["params"] > 0 and c["optimizer_state"] > 0
    # what-if pricing comes straight off the class budget
    wi = plan["what_if"]
    assert wi["zero1_optimizer_state_bytes"] == c["optimizer_state"]
    assert wi["zero3_parameter_bytes"] == c["params"]


def test_plan_unknown_program_errors():
    with pytest.raises(Exception):
        hbm.plan("no-such-program")


# ---------------------------------------------------------------------------
# live census + leak detector
# ---------------------------------------------------------------------------

def test_census_joins_live_buckets_to_programs():
    _executor(train=True)
    c = hbm.census(force=True)
    assert c["enabled"] and c["n_arrays"] > 0 and c["live_bytes"] > 0
    assert c["headroom_bytes"] >= 0
    owned = [r for r in c["top_buckets"] if r["program"]]
    assert owned, c["top_buckets"]
    assert any(r["layer"] == "fc1" and r["class"] == "params"
               for r in owned)


def test_planted_leak_named_by_program_layer_dtype(monkeypatch):
    """A cache growing by arrays shaped like fc1's weight must be
    named as a (program, layer, dtype) leak suspect within the
    detector window — BEFORE any OOM."""
    monkeypatch.setattr(hbm, "_SWEEP_S", 0.0)
    monkeypatch.setattr(hbm, "_GROWTH_BYTES", 2048)
    _executor(train=True)
    # in a full-suite process, earlier tests' dead device buffers can
    # be collected MID-LOOP, shrinking used_bytes between ticks and
    # masking the planted growth — drop them up front and settle the
    # baseline before the growth streak starts
    import gc
    gc.collect()
    for _ in range(2):
        hbm.census(force=True)
    cache = []
    fired = None
    for i in range(hbm._WINDOW * 6):
        for _ in range(4):   # 4 x (16, 10) float32 = 2560 B per tick
            cache.append(mx.nd.ones((16, 10)))
        cache[-1].wait_to_read()
        c = hbm.census(force=True)
        if c["leaks"]:
            fired = (i, c["leaks"])
            break
    assert fired is not None, "leak detector never fired"
    _i, leaks = fired
    leak = leaks[-1]
    assert leak["program"] == "executor:softmax"
    assert leak["layer"] == "fc1"
    assert leak["dtype"] == "float32"
    assert leak["growth_bytes"] >= 2048
    # ... and it rode telemetry as a memory_leak anomaly
    evs = [e for e in telemetry.events("anomaly")
           if e.get("atype") == "memory_leak"]
    assert evs and evs[-1]["layer"] == "fc1"
    assert profiler.get_stat("hbm_leak_events") >= 1
    # the census block flags it for every downstream surface
    blk = hbm.metrics_block()
    assert blk["leak"] and blk["last_leak"]["layer"] == "fc1"


def test_disabled_census_is_inert(monkeypatch):
    hbm.enable(False)
    assert hbm.census() == {"enabled": False}
    assert hbm.metrics_block() == {"enabled": False}
    hbm.observe_used(1 << 40)   # must not record anything
    hbm.enable(True)
    assert hbm.census(force=True)["peak_used_bytes"] < (1 << 40)


# ---------------------------------------------------------------------------
# headroom + capacity planning
# ---------------------------------------------------------------------------

def test_limit_env_override(monkeypatch):
    monkeypatch.setenv("MXTPU_HBM_LIMIT_BYTES", str(123 << 20))
    assert hbm.limit_bytes() == 123 << 20
    assert hbm.headroom() == max(0, (123 << 20) - hbm.used_bytes())


def test_max_batch_and_fits():
    net = _hybrid_net(train=False, batch=4)
    x = mx.nd.ones((8, 10))
    net(x).wait_to_read()   # second bucket -> a 2-point capacity fit
    rec = net._cached_op._insp
    cm = hbm.capacity_model(rec, kind="infer")
    assert cm["bytes_per_sample"] >= 1.0
    assert len(cm["points"]) == 2
    # plenty of headroom: prediction snaps DOWN onto the ladder
    big = hbm.max_batch(rec, headroom_bytes=1 << 30, kind="infer",
                        buckets=[4, 8])
    assert big == 8
    # no headroom: nothing fits
    assert hbm.max_batch(rec, headroom_bytes=0, kind="infer",
                         buckets=[4, 8]) == 0
    f = hbm.fits([rec], headroom_bytes=1 << 30)
    assert f["fits"] and f["per_model"][rec.name] > 0
    assert not hbm.fits([rec], headroom_bytes=1)["fits"]


def test_report_shape():
    ex = _executor(train=True)
    hbm.plan(ex._insp)   # report() only shows ANALYZED programs
    rep = hbm.report(top=3)
    assert rep["census"]["enabled"]
    assert rep["plans"] and rep["plans"][0]["classes"]
    assert rep["headroom_bytes"] >= 0


# ---------------------------------------------------------------------------
# consumer wiring
# ---------------------------------------------------------------------------

def test_metrics_obs_and_openmetrics_surfaces():
    _executor(train=True)
    blk = telemetry.metrics().get("hbm")
    assert blk and blk["enabled"] and blk["used_bytes"] > 0
    row = obs.sample()
    assert row["hbm"]["used_bytes"] > 0
    assert row["hbm"]["headroom_bytes"] >= 0
    om = obs.openmetrics()
    for fam in ("mxtpu_hbm_used_bytes", "mxtpu_hbm_peak_bytes",
                "mxtpu_hbm_headroom_bytes", "mxtpu_hbm_leak_suspect"):
        assert fam in om, fam
    obs.parse_openmetrics(om)   # strict parser accepts the gauges


def test_hbm_rollup_folds_ranks_and_leaks():
    snaps = {
        "worker0": {"metrics": {"hbm": {
            "enabled": True, "used_bytes": 100, "peak_used_bytes": 120,
            "headroom_bytes": 900, "leak": False}}},
        "worker1": {"metrics": {"hbm": {
            "enabled": True, "used_bytes": 500, "peak_used_bytes": 600,
            "headroom_bytes": 400, "leak": True,
            "last_leak": {"layer": "fc1"}}}},
        "server0": {"metrics": {}},         # no census: skipped
        "corrupt": "not-a-dict",            # tolerated
    }
    r = telemetry.hbm_rollup(snaps)
    assert set(r["per_rank"]) == {"worker0", "worker1"}
    assert r["min_headroom_bytes"] == 400
    assert r["peak_used_bytes"] == 600
    assert r["leak_ranks"] == ["worker1"]
    assert r["per_rank"]["worker1"]["last_leak"]["layer"] == "fc1"


def test_health_memory_report_rides_census():
    _executor(train=True)
    rep = mx.health.memory_report()
    assert "device_error" not in rep, rep
    assert rep["top_live_buffers"]
    row = rep["top_live_buffers"][0]
    assert {"shape", "dtype", "mbytes", "program", "layer",
            "class"} <= set(row)
    assert any(r["program"] == "executor:softmax"
               for r in rep["top_live_buffers"])
    assert rep["headroom_bytes"] >= 0
    assert rep["plan_vs_live"]["static_peak_bytes"] > 0
    assert rep["programs"][0]["plan_classes"]["params"] > 0


def test_dash_renders_hbm_cell():
    import dash

    cell = dash._fmt_hbm({"used_bytes": 3 << 30,
                          "headroom_bytes": 29 << 30, "leak": True})
    assert cell == "3.0G/29.0G!"
    assert dash._fmt_hbm(None) == "-"
    lines = dash.render({
        "ts": time.time(), "roles": {
            "worker0": {"steps": 1, "hbm": {"used_bytes": 1 << 20,
                                            "headroom_bytes": 1 << 30,
                                            "leak": False}}},
        "samples": {}, "hbm": {"min_headroom_bytes": 1 << 30,
                               "leak_ranks": ["worker3"]}})
    frame = "\n".join(lines)
    assert "hbm(u/free)" in frame
    assert "1.0M/1.0G" in frame
    assert "LEAK suspects: worker3" in frame


def test_zero1_measured_freed_bytes():
    from mxtpu import optimizer as opt_mod
    from mxtpu.sharding import ShardingPlan, ZeRO1Updater, hbm_report

    plan = ShardingPlan(num_shards=4, min_shard_elems=16)
    opt = opt_mod.create("adam", learning_rate=0.01)
    upd = ZeRO1Updater(opt, plan, idx2name={0: "w"})
    w = mx.nd.array(np.ones((8, 16), "float32"))
    g = mx.nd.array(np.full((8, 16), 0.5, "float32"))
    upd.update_replicas([(0, [g], [w])])
    freed = upd.hbm_freed_bytes()
    # adam keeps 2 state arrays: full = 2*8*16*4 bytes over 4 shards
    assert freed == upd.state_nbytes() - upd.per_replica_state_nbytes()
    assert freed > 0
    rep = hbm_report(upd)
    assert rep["hbm_freed_bytes"] == freed
    assert rep["n_shards"] == 4
    assert rep["state_bytes_full"] > rep["state_bytes_per_replica"]


def test_serve_add_model_records_capacity_advisory():
    net = nn.HybridSequential()
    net.add(nn.Dense(4))
    net.initialize()
    net.hybridize()
    srv = mx.serve.Server(max_batch=8, batch_wait_s=0.0)
    try:
        srv.add_model("m", net, input_shape=(10,))
        evs = [e for e in telemetry.events("serve")
               if e.get("action") == "hbm_capacity"]
        assert evs, "add_model recorded no hbm capacity advisory"
        assert evs[-1]["model"] == "m"
        assert evs[-1]["fit_max_batch"] >= 1
    finally:
        srv.close()
