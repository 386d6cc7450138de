"""Tests of the readers of the program's own spans (``program_spans.py``)
on hand-made rows, and of a whole rehearsal that prints their metrics.

    JAX_PLATFORMS=cpu python -m pytest benchmark/onchip/tests -q

Every timing in them is made up or a CPU's.
"""
import argparse
import io
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(ONCHIP))
for p in (ROOT, ONCHIP):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as run_mod  # noqa: E402

run_mod._environment(True)

import harness  # noqa: E402
import program_spans  # noqa: E402

NEW = ("loop_host_ms_per_step.fused", "loop_device_wait_ms_per_step.fused",
       "observer_ms_per_step.fused")


def _row(name, t0, t1, tid=1, parent="mx:step", step=0):
    return {"name": name, "cat": "loop", "t0": t0, "t1": t1, "tid": tid,
            "parent": parent, "step": step}


def _program(t, step):
    """One ``mx:step`` of 1.0 s at ``t``: 0.01 s of arguments, 0.02 s of
    dispatch, an observer of 0.10 s that waits 0.06 s inside it, a wait
    of 0.70 s and an observer of 0.03 s beside it."""
    return [
        _row("mx:step", t, t + 1.0, parent=None, step=step),
        _row("mx:host_args", t + 0.00, t + 0.01, step=step),
        _row("mx:host_dispatch", t + 0.01, t + 0.03, step=step),
        _row("mx:observe.perf", t + 0.03, t + 0.13, step=step),
        _row("mx:device_wait", t + 0.05, t + 0.11,
             parent="mx:observe.perf", step=step),
        _row("mx:device_wait", t + 0.20, t + 0.90, step=step),
        _row("mx:observe.health", t + 0.90, t + 0.93, step=step),
    ]


@pytest.fixture
def rows(monkeypatch):
    held = []
    monkeypatch.setattr(program_spans, "program_rows", lambda: held)
    return held


def _run(window, steps):
    return {"window": window, "steps": steps}


def test_self_time_is_duration_less_covered_children():
    rows = _program(10.0, 0)
    step = rows[0]
    wait = program_spans._is_wait
    assert program_spans.covered_seconds(step, rows, wait) \
        == pytest.approx(0.76)
    assert program_spans.self_seconds(step, rows, wait) \
        == pytest.approx(0.24)
    # overlapping and nested children count once; another thread's and
    # one that sticks out of the span count not at all
    rows += [_row("mx:device_wait", 10.25, 10.30),
             _row("mx:device_wait", 10.85, 10.95),
             _row("mx:device_wait", 10.40, 10.60, tid=2),
             _row("mx:device_wait", 10.95, 11.05)]
    assert program_spans.covered_seconds(step, rows, wait) \
        == pytest.approx(0.76 + 0.05)
    assert program_spans.self_seconds(rows[3], rows, wait) \
        == pytest.approx(0.04)


def test_readers_on_hand_made_rows(rows):
    rows += _program(10.0, 0) + _program(11.5, 4)
    run = _run((9.0, 13.0), steps=8)
    assert program_spans.loop_device_wait_ms_per_step(run) \
        == pytest.approx(2 * 760.0 / 8)
    assert program_spans.loop_host_ms_per_step(run) \
        == pytest.approx(2 * 240.0 / 8)
    # 0.10 less the 0.06 waited inside it, and 0.03
    assert program_spans.observer_ms_per_step(run) \
        == pytest.approx(2 * 70.0 / 8)
    # the inside accounts for the whole of the steps
    assert program_spans.loop_host_ms_per_step(run) \
        + program_spans.loop_device_wait_ms_per_step(run) \
        == pytest.approx(2 * 1000.0 / 8)


def test_rows_across_the_windows_edge_are_left_out(rows):
    rows += _program(10.0, 0) + _program(11.5, 4)
    # the window opens inside the first program and closes after the
    # second: only the second counts, and the first one's children that
    # lie inside the window belong to no step there
    run = _run((10.1, 13.0), steps=4)
    assert program_spans.loop_device_wait_ms_per_step(run) \
        == pytest.approx(760.0 / 4)
    assert program_spans.loop_host_ms_per_step(run) \
        == pytest.approx(240.0 / 4)
    assert program_spans.observer_ms_per_step(run) \
        == pytest.approx(70.0 / 4)
    assert [r["step"] for r in program_spans.in_window(rows, run["window"])
            if r["name"] == "mx:step"] == [4]


@pytest.mark.parametrize("metric", NEW)
def test_no_rows_reads_none(rows, metric):
    read = harness.load_module("metrics", metric).read
    assert read(_run((0.0, 100.0), steps=8)) is None
    rows += [_row("bench:call", 1.0, 2.0, parent=None)]    # no mx:step
    assert read(_run((0.0, 100.0), steps=8)) is None
    rows += _program(10.0, 0)
    assert read(_run((0.0, 100.0), steps=0)) is None
    assert read(_run((0.0, 100.0), steps=8)) > 0


def test_a_program_without_spans_reads_none(monkeypatch):
    """On the parent of the PR that brought the spans ``mxtpu.profiler``
    has no ``spans``: the readers find nothing and do not raise."""
    from mxtpu import profiler

    monkeypatch.delattr(profiler, "spans")
    assert program_spans.program_rows() == []
    assert program_spans.loop_host_ms_per_step(_run((0, 1), 8)) is None


def test_the_new_metrics_are_additions_for_the_fused_cell():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # present wherever they stand: later PRs append their own entries
    for name in NEW:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) \
            == ("ms", "lower", "program_span", "img_per_s")
        assert m["workloads"] == ["resnet50_fused_k16"]
    assert by_name[NEW[2]]["layer"] == "observers"
    assert by_name[NEW[0]]["layer"] == by_name[NEW[1]]["layer"] \
        == by_name["host_call_ms_per_step.fused"]["layer"]


def test_traced_rehearsal_prints_the_span_metrics():
    args = argparse.Namespace(workload="resnet50_fused_k16", seed=2 ** 31 + 5,
                              seconds=0.5, trace=1, rehearse=True)
    out, err = io.StringIO(), io.StringIO()
    assert run_mod.run_cell(args, out=out, err=err) == 0, \
        err.getvalue()[-2000:]
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(got)
    host, wait, observers = (got[n] for n in NEW)
    assert 0 < observers <= host
    # the inside accounts for the outside, and never for more
    outside = got["host_call_ms_per_step.fused"]
    assert 0.5 * outside < host + wait <= outside
    assert result["info"]["counters"]["fused_programs"] \
        == result["info"]["programs"]
    assert result["info"]["counters"]["fused_steps"] == result["attempted"]
