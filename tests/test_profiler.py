"""Profiler + monitor + visualization tests (reference:
`tests/python/unittest/test_profiler.py`)."""
import json
import logging
import os
import tempfile
import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, sym, profiler


def test_profiler_chrome_trace_and_aggregate():
    with tempfile.TemporaryDirectory() as td:
        fname = os.path.join(td, "profile.json")
        profiler.set_config(filename=fname, profile_all=True)
        profiler.set_state("run")
        a = nd.ones((8, 8))
        for _ in range(3):
            b = nd.dot(a, a)
        b.wait_to_read()
        profiler.set_state("stop")
        profiler.dump()
        with open(fname) as f:
            trace = json.load(f)
        names = {e["name"] for e in trace["traceEvents"]}
        assert "dot" in names
        table = profiler.dumps(reset=True)
        assert "dot" in table and "Calls" in table


def test_profiler_pause_resume():
    profiler.set_config(profile_all=True)
    profiler.set_state("run")
    profiler.pause()
    x = nd.ones((4,)) * 2
    x.wait_to_read()
    profiler.resume()
    y = nd.ones((4,)).exp()
    y.wait_to_read()
    profiler.set_state("stop")
    table = profiler.dumps(reset=True)
    assert "exp" in table
    assert "_mul_scalar" not in table


def test_profiler_task_counter_marker():
    profiler.set_state("run")
    d = profiler.Domain("unit")
    t = profiler.Task(d, "work")
    t.start()
    t.stop()
    c = profiler.Counter(d, "ctr", 0)
    c.increment(5)
    m = profiler.Marker(d, "mark")
    m.mark()
    profiler.set_state("stop")
    assert "unit::work" in profiler.dumps(reset=True)


def test_profiler_pause_gates_spans_and_markers():
    """Satellite: the pause/resume gate applies to every recording
    surface — is_recording(), spans taken through the public span()
    helper, counters, and markers: NOTHING recorded during pause may
    appear in the dump."""
    profiler.set_config(profile_all=True)
    profiler.set_state("run")
    assert profiler.is_recording("imperative")
    profiler.pause()
    assert not profiler.is_recording("imperative")
    assert not profiler.is_recording("symbolic")
    profiler.Marker(None, "paused_mark").mark()
    with profiler.span("paused_span", "operator"):
        pass
    profiler.record_counter("paused_counter", 1.0)
    profiler.resume()
    assert profiler.is_recording("imperative")
    profiler.Marker(None, "live_mark").mark()
    with profiler.span("live_span", "operator"):
        pass
    with tempfile.TemporaryDirectory() as td:
        fname = os.path.join(td, "p.json")
        profiler.set_config(filename=fname)
        profiler.set_state("stop")
        profiler.dump()
        names = {e["name"] for e in
                 json.load(open(fname))["traceEvents"]}
    assert "live_mark" in names and "live_span" in names
    assert "paused_mark" not in names
    assert "paused_span" not in names
    assert "paused_counter" not in names
    profiler.dumps(reset=True)


def test_profiler_dumps_json_aggregation():
    profiler.set_config(profile_all=True)
    profiler.dumps(reset=True)
    profiler.set_state("run")
    a = nd.ones((8, 8))
    for _ in range(4):
        nd.dot(a, a).wait_to_read()
    profiler.set_state("stop")
    rows = json.loads(profiler.dumps(reset=True, format="json"))
    dot = next(r for r in rows if r["name"] == "dot")
    assert dot["count"] == 4
    assert dot["total_us"] >= dot["max_us"] >= dot["avg_us"] > 0
    assert dot["min_us"] <= dot["avg_us"]
    assert dot["total_us"] == pytest.approx(dot["avg_us"] * 4, rel=1e-6)


def test_inc_stat_concurrent_threads():
    """Satellite: inc_stat is lock-protected — concurrent bumps from
    many threads must not lose increments."""
    profiler.reset_stats()
    n_threads, n_incs = 8, 500

    def bump():
        for _ in range(n_incs):
            profiler.inc_stat("concurrency_probe")

    threads = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert profiler.get_stat("concurrency_probe") == n_threads * n_incs
    profiler.reset_stats()


def test_reset_stats_isolation():
    profiler.inc_stat("isolation_probe", 3)
    profiler.set_stat("isolation_gauge", 42)
    assert profiler.stats()["isolation_probe"] == 3
    profiler.reset_stats()
    assert profiler.get_stat("isolation_probe") == 0
    assert "isolation_probe" not in profiler.stats()
    assert "isolation_gauge" not in profiler.stats()


def test_set_and_max_stat_gauges():
    profiler.reset_stats()
    profiler.set_stat("gauge", 10)
    profiler.set_stat("gauge", 4)       # absolute: overwrites down
    assert profiler.get_stat("gauge") == 4
    profiler.max_stat("watermark", 5)
    profiler.max_stat("watermark", 3)   # watermark: never descends
    assert profiler.get_stat("watermark") == 5
    profiler.max_stat("watermark", 9)
    assert profiler.get_stat("watermark") == 9
    profiler.reset_stats()


def test_profiler_sync_is_dynamic(monkeypatch):
    """Satellite: MXTPU_PROFILER_SYNC is read per span, not latched at
    import — flipping the env mid-run changes behavior, and a span
    with attached device results blocks on exactly those."""
    monkeypatch.delenv("MXTPU_PROFILER_SYNC", raising=False)
    assert not profiler._sync_enabled()
    monkeypatch.setenv("MXTPU_PROFILER_SYNC", "1")
    assert profiler._sync_enabled()
    profiler.set_config(profile_all=True)
    profiler.set_state("run")
    with profiler.span("sync_probe", "operator") as sp:
        sp.result = nd.ones((16, 16))._data * 2  # block target
    profiler.set_state("stop")
    rows = json.loads(profiler.dumps(reset=True, format="json"))
    assert any(r["name"] == "sync_probe" for r in rows)


def _mlp():
    data = sym.Variable("data")
    fc1 = sym.FullyConnected(data=data, num_hidden=8, name="fc1")
    act = sym.Activation(data=fc1, act_type="relu", name="relu1")
    fc2 = sym.FullyConnected(data=act, num_hidden=3, name="fc2")
    return sym.SoftmaxOutput(data=fc2, label=sym.Variable("softmax_label"),
                             name="softmax")


def test_monitor_collects_stats():
    from mxtpu.monitor import Monitor

    net = _mlp()
    ex = net.simple_bind(ctx=mx.cpu(), data=(4, 10), softmax_label=(4,))
    mon = Monitor(interval=1)
    mon.install(ex)
    mon.tic()
    ex.forward(is_train=False, data=mx.nd.ones((4, 10)))
    res = mon.toc()
    assert res and any("softmax_output" in k for _, k, _v in res)


def test_monitor_interval_and_monitor_all():
    """Satellite: direct Monitor coverage — interval gating (only
    every Nth tic collects), monitor_all pulls args/aux too, and the
    pattern filter applies."""
    from mxtpu.monitor import Monitor

    net = _mlp()
    ex = net.simple_bind(ctx=mx.cpu(), data=(4, 10), softmax_label=(4,))
    mon = Monitor(interval=2, monitor_all=True)
    mon.install(ex)

    mon.tic()  # step 0: activated
    ex.forward(is_train=False, data=mx.nd.ones((4, 10)))
    res0 = mon.toc()
    names0 = {k for _, k, _ in res0}
    assert any("fc1_weight" in n for n in names0), names0  # args too

    mon.tic()  # step 1: NOT activated (interval=2)
    ex.forward(is_train=False, data=mx.nd.ones((4, 10)))
    assert mon.toc() == []

    mon.tic()  # step 2: activated again
    ex.forward(is_train=False, data=mx.nd.ones((4, 10)))
    assert mon.toc()


def test_monitor_pattern_and_sort():
    from mxtpu.monitor import Monitor

    net = _mlp()
    ex = net.simple_bind(ctx=mx.cpu(), data=(4, 10), softmax_label=(4,))
    mon = Monitor(interval=1, pattern=".*fc1.*", sort=True,
                  monitor_all=True)
    mon.install(ex)
    mon.tic()
    ex.forward(is_train=False, data=mx.nd.ones((4, 10)))
    res = mon.toc()
    assert res
    names = [k for _, k, _ in res]
    assert all("fc1" in n for n in names)
    assert names == sorted(names)


def test_monitor_custom_stat_and_toc_print(caplog):
    from mxtpu.monitor import Monitor

    net = _mlp()
    ex = net.simple_bind(ctx=mx.cpu(), data=(4, 10), softmax_label=(4,))
    mon = Monitor(interval=1, stat_func=lambda x: x.max())
    mon.install(ex)
    mon.tic()
    ex.forward(is_train=False, data=mx.nd.ones((4, 10)))
    with caplog.at_level(logging.INFO):
        mon.toc_print()
    assert any("softmax_output" in r.getMessage()
               for r in caplog.records)


def test_print_summary():
    out = mx.visualization.print_summary(
        _mlp(), shape={"data": (4, 10), "softmax_label": (4,)})
    assert "fc1" in out and "Total params" in out
    # 10*8+8 + 8*3+3 = 115
    assert "115" in out


# -- the one span primitive, and the loops under it (mx: vocabulary) --------

@pytest.fixture
def span_store():
    """An empty span store, un-armed before and after."""
    profiler.set_state("stop")
    profiler.spans(reset=True)
    yield
    profiler.set_state("stop")
    profiler.spans(reset=True)


def _fused_loop(k=2):
    from test_fused_train import _batches, _make_module

    mod = _make_module(5)
    return mx.FusedTrainLoop(mod, steps_per_program=k), _batches(3 * k)


def _xplane_events(trace_dir):
    """{event name: [its stats as a dict]} over the host planes of the
    one .xplane.pb under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("mx:"):
                    found.setdefault(e.name, []).append(dict(e.stats))
    return found


def test_span_unarmed_records_nothing_and_enters_no_annotation(
        span_store, monkeypatch):
    entered = []

    class Annotation(object):
        is_enabled = staticmethod(lambda: False)

        def __init__(self, *a, **kw):
            entered.append(a)

    monkeypatch.setattr(profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(profiler, "StepTraceAnnotation", Annotation)
    assert not profiler.armed()
    with profiler.span("mx:step", "loop", step=3, k=2) as sp:
        sp.result = object()            # taken and dropped
        with profiler.span("mx:host_args", "loop"):
            pass
    assert sp is profiler.span("anything")      # the shared no-op
    assert sp.result is None
    assert profiler.spans() == [] and entered == []
    loop, batches = _fused_loop()
    loop.run(batches[:2])
    assert profiler.spans() == []


@pytest.mark.parametrize("armed_by", ["set_state", "jax_trace"])
def test_fused_loop_leaves_one_step_span_per_call(span_store, armed_by,
                                                  tmp_path):
    """``run_stacked`` under the primitive: one ``mx:step`` per call with
    its children, ``parent`` and ``step`` set, the counters beside them;
    armed by a JAX profiler session the same names are in its
    .xplane.pb, ``mx:step`` with ``step_num``."""
    import jax

    k = 2
    loop, batches = _fused_loop(k)
    stacks = [loop.stack_batches(batches[i * k:(i + 1) * k])
              for i in range(3)]
    loop.run_stacked(stacks[0])         # compiles; un-armed: no rows
    assert profiler.spans() == []
    before = profiler.get_stat("fused_programs"), \
        profiler.get_stat("fused_steps")
    if armed_by == "set_state":
        profiler.set_state("run")
    else:
        jax.profiler.start_trace(str(tmp_path))
    try:
        assert profiler.armed()
        loop.run_stacked(stacks[1])
        loop.run_stacked(stacks[2])
    finally:
        if armed_by == "set_state":
            profiler.set_state("stop")
        else:
            jax.profiler.stop_trace()
    assert not profiler.armed()
    assert profiler.get_stat("fused_programs") - before[0] == 2
    assert profiler.get_stat("fused_steps") - before[1] == 2 * k
    rows = [r for r in profiler.spans() if r["cat"] == "loop"]
    steps = [r for r in rows if r["name"] == "mx:step"]
    assert [r["step"] for r in steps] == [k, 2 * k]
    assert all(r["parent"] is None and r["args"]["k"] == k
               and r["args"]["site"] == "fused_train" for r in steps)
    for st in steps:
        kids = [r for r in rows if r is not st and r["tid"] == st["tid"]
                and st["t0"] <= r["t0"] and r["t1"] <= st["t1"]]
        names = [r["name"] for r in kids]
        for want in ("mx:host_args", "mx:host_dispatch", "mx:publish",
                     "mx:device_wait", "mx:observe.inspect",
                     "mx:observe.perf", "mx:observe.telemetry",
                     "mx:observe.health", "mx:observe.checkpoint",
                     "mx:observe.xprof"):
            assert names.count(want) == 1, (want, names)
        assert all(r["parent"] == "mx:step" and r["step"] == st["step"]
                   for r in kids)
        (wait,) = [r for r in kids if r["name"] == "mx:device_wait"]
        assert wait["args"] == {"why": "health"}
        assert sum(r["t1"] - r["t0"] for r in kids) <= st["t1"] - st["t0"]
    if armed_by == "jax_trace":
        events = _xplane_events(str(tmp_path))
        assert {r["name"] for r in rows} <= set(events)
        assert sorted(s["step_num"] for s in events["mx:step"]) \
            == [k, 2 * k]
        assert {"why": "health"} in [
            {"why": s.get("why")} for s in events["mx:device_wait"]]


def test_span_store_drops_oldest_and_counts(span_store, monkeypatch):
    import collections

    monkeypatch.setattr(profiler, "_SPANS", collections.deque(maxlen=3))
    drops0 = profiler.get_stat("profiler_span_drops")
    profiler.set_state("run")
    for i in range(5):
        with profiler.span("s%d" % i, "loop"):
            pass
    profiler.set_state("stop")
    assert [r["name"] for r in profiler.spans()] == ["s2", "s3", "s4"]
    assert profiler.get_stat("profiler_span_drops") - drops0 == 2
    assert profiler.spans(reset=True) and profiler.spans() == []


def test_span_parent_and_step_are_per_thread(span_store):
    profiler.set_state("run")
    inner_done = threading.Event()

    def other():
        with profiler.span("other:root", "loop"):
            pass
        inner_done.set()

    with profiler.span("outer", "loop", step=7, site="t"):
        with profiler.span("mid", "loop"):
            with profiler.span("leaf", "loop", why="x"):
                t = threading.Thread(target=other)
                t.start()
                t.join(timeout=30)
        with profiler.span("own_step", "loop", step=9):
            pass
    profiler.set_state("stop")
    assert inner_done.is_set()
    rows = {r["name"]: r for r in profiler.spans()}
    assert rows["outer"]["parent"] is None and rows["outer"]["step"] == 7
    assert rows["mid"]["parent"] == "outer" and rows["mid"]["step"] == 7
    assert rows["leaf"]["parent"] == "mid" and rows["leaf"]["step"] == 7
    assert rows["leaf"]["args"] == {"why": "x"}
    assert rows["own_step"]["step"] == 9
    # another thread's span is no child of this thread's open spans
    assert rows["other:root"]["parent"] is None
    assert rows["other:root"]["step"] is None
    assert rows["other:root"]["tid"] != rows["outer"]["tid"]
    assert rows["outer"]["t0"] <= rows["mid"]["t0"] \
        <= rows["mid"]["t1"] <= rows["outer"]["t1"]


def test_dump_writes_the_span_rows(span_store, tmp_path):
    """The chrome trace is written from the same rows: one store."""
    fname = str(tmp_path / "p.json")
    profiler.set_config(filename=fname)
    profiler.set_state("run")
    t_before = time.perf_counter()
    with profiler.span("mx:step", "loop", step=1, k=4):
        with profiler.span("mx:host_dispatch", "loop"):
            pass
    profiler.set_state("stop")
    (step,) = [r for r in profiler.spans() if r["name"] == "mx:step"]
    assert t_before <= step["t0"] <= step["t1"] <= time.perf_counter()
    profiler.dump()
    events = {e["name"]: e for e in json.load(open(fname))["traceEvents"]
              if e.get("ph") == "X"}
    assert set(events) == {"mx:step", "mx:host_dispatch"}
    assert events["mx:step"]["args"] == {"k": 4}
    assert events["mx:step"]["dur"] == pytest.approx(
        (step["t1"] - step["t0"]) * 1e6)
    assert events["mx:step"]["ts"] <= events["mx:host_dispatch"]["ts"]
    assert profiler.spans() == []       # dump(finished=True) empties them
    profiler.dumps(reset=True)


def test_perf_sync_is_a_bracketed_device_wait(span_store, monkeypatch):
    """The one block inside ``perf.end`` is an ``mx:device_wait`` with
    ``why=perf_sync``, so an observer's span can be read without it."""
    from mxtpu import perf

    monkeypatch.setattr(perf, "sync_every", lambda: 1)
    monkeypatch.setattr(perf, "_ENABLED", True)
    out = nd.ones((4, 4))._data
    profiler.set_state("run")
    for _ in range(3):                  # the first call never samples
        with profiler.span("mx:observe.perf", "loop"):
            perf.end("probe_prog", "test", perf.begin(), outputs=out)
    profiler.set_state("stop")
    waits = [r for r in profiler.spans() if r["name"] == "mx:device_wait"]
    assert len(waits) == 2
    assert all(r["args"] == {"why": "perf_sync"}
               and r["parent"] == "mx:observe.perf" for r in waits)


@pytest.mark.parametrize("kvstore", [None, "local"])
def test_per_step_path_span_tree(span_store, kvstore):
    """``Module.forward`` / ``backward`` / ``update`` are ``mx:forward``,
    ``mx:backward``, ``mx:optimizer`` with ``step`` = the update count;
    the kvstore's push / pull is one ``mx:collective`` inside the
    update."""
    from test_fused_train import _batches

    ctxs = [mx.cpu(0), mx.cpu(1)] if kvstore else [mx.cpu(0)]
    mod = mx.mod.Module(_mlp(), context=ctxs)
    mod.bind(data_shapes=[("data", (8, 10))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(kvstore=kvstore,
                       optimizer_params={"learning_rate": 0.1})
    batches = _batches(2)
    profiler.set_state("run")
    for b in batches:
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()
    profiler.set_state("stop")
    rows = [r for r in profiler.spans() if r["cat"] == "loop"]
    top = [(r["name"], r["step"]) for r in rows if r["parent"] is None]
    assert top == [("mx:forward", 0), ("mx:backward", 0),
                   ("mx:optimizer", 0), ("mx:forward", 1),
                   ("mx:backward", 1), ("mx:optimizer", 1)]
    coll = [r for r in rows if r["name"] == "mx:collective"]
    if kvstore:
        assert [(r["parent"], r["step"]) for r in coll] \
            == [("mx:optimizer", 0), ("mx:optimizer", 1)]
    else:
        assert coll == []


def test_trainer_step_span_tree(span_store):
    from mxtpu import autograd, gluon

    net = gluon.nn.Dense(3)
    ctxs = [mx.cpu(0), mx.cpu(1)]
    net.initialize(ctx=ctxs)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="local")
    profiler.set_state("run")
    for _ in range(2):
        with autograd.record():
            losses = [net(nd.ones((2, 5), ctx=c)).sum() for c in ctxs]
        for l in losses:
            l.backward()
        trainer.step(4)
    profiler.set_state("stop")
    rows = [(r["name"], r["step"], r["args"]["site"])
            for r in profiler.spans() if r["cat"] == "loop"]
    assert rows == [("mx:collective", 0, "trainer"),
                    ("mx:optimizer", 0, "trainer"),
                    ("mx:collective", 1, "trainer"),
                    ("mx:optimizer", 1, "trainer")]


def test_xplane_wrappers_are_gone():
    """The program joins whatever ``jax.profiler`` session is open."""
    assert not hasattr(profiler, "start_xplane")
    assert not hasattr(profiler, "stop_xplane")
    assert "armed" in profiler.__all__ and "spans" in profiler.__all__
