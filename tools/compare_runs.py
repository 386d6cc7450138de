#!/usr/bin/env python
"""Diff two `mx.obs` run-ledger files: knob deltas + metric shifts.

Each run (``MXTPU_RUN_DIR`` armed) leaves ``<run_id>.jsonl`` holding
timestamped sample rows and one final summary row per role
(throughput / step_time_us / mfu / phases / knobs).  This tool
answers: *what changed between these two runs, and what did it do to
the numbers?*

  * **knob deltas** — every ``MXTPU_*`` / ``JAX_PLATFORMS`` /
    ``XLA_FLAGS`` key that was added, removed or changed between the
    runs' recorded environments;
  * **metric deltas** — headline throughput, step time, MFU and the
    summary row's own metric, side by side with the relative change;
  * **phase shifts** — the per-step phase attribution
    (input_wait/host_dispatch/...) of run A vs run B, naming where
    the time moved;
  * **op-sink shifts** — when both runs' summary rows carry the
    `mx.xprof` ``op_profile`` breakdown (runs made with profiling),
    per-op-class device-time deltas plus the top-sink change: WHICH
    op class got slower, not just which phase;
  * **sample-series view** — per-run sample counts and averaged
    step-time/MFU over the time series (not just the final instant).

Usage::

    python tools/compare_runs.py A.jsonl B.jsonl
    python tools/compare_runs.py --run-dir /runs run1 run2
    python tools/compare_runs.py A.jsonl B.jsonl --json

Exit code 0; ``--fail-on-slower PCT`` exits 1 when run B's step time
regressed more than PCT percent vs run A (a ratchet hook).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KNOB_KEYS_SKIP = ("MXTPU_RUN_ID", "MXTPU_TELEMETRY_DIR",
                  "MXTPU_PS_ROOT_PORT", "MXTPU_SERVE_PORT",
                  "MXTPU_SERVE_PORTS", "MXTPU_SERVE_RANK")


def _read(path):
    from mxtpu import obs

    rows = obs.read_ledger(path)
    if not rows:
        raise SystemExit("compare_runs: %s holds no parseable rows"
                         % path)
    return rows


def _resolve(run_dir, name):
    if os.path.exists(name):
        return name
    if run_dir:
        p = os.path.join(run_dir, name)
        if os.path.exists(p):
            return p
        p += ".jsonl" if not p.endswith(".jsonl") else ""
        if os.path.exists(p):
            return p
    raise SystemExit("compare_runs: cannot resolve run %r" % name)


def primary_row(rows):
    """The run's headline record: the summary row of the busiest role
    (most steps — the trainer, not the scheduler)."""
    summaries = [r for r in rows if r.get("kind") == "summary"]
    if summaries:
        return max(summaries, key=lambda r: r.get("value") or 0)
    return rows[-1]


def series_view(rows):
    """Averages over the run's sample time series."""
    samples = [r for r in rows if r.get("kind") == "sample"]
    out = {"samples": len(samples)}
    for field, key in (("step_time_ms", "step_time_ms_avg"),
                       ("mfu", "mfu_avg"),
                       ("examples_per_sec", "examples_per_sec_avg")):
        vals = [float(r[field]) for r in samples
                if isinstance(r.get(field), (int, float)) and r[field]]
        if vals:
            out[key] = sum(vals) / len(vals)
    roles = sorted({"%s%s" % (r.get("role"), r.get("rank"))
                    for r in rows if r.get("role") is not None})
    out["roles"] = roles
    return out


def knob_deltas(a, b):
    ka = a.get("knobs") or {}
    kb = b.get("knobs") or {}
    deltas = []
    for k in sorted(set(ka) | set(kb)):
        if k in KNOB_KEYS_SKIP:
            continue
        va, vb = ka.get(k), kb.get(k)
        if va != vb:
            deltas.append((k, va, vb))
    return deltas


def _pct(a, b):
    try:
        a, b = float(a), float(b)
    except (TypeError, ValueError):
        return None
    if not a:
        return None
    return (b - a) / abs(a) * 100.0


def metric_deltas(a, b):
    rows = []
    for field in ("throughput", "step_time_us", "mfu", "value"):
        va, vb = a.get(field), b.get(field)
        if va is None and vb is None:
            continue
        label = field
        if field == "value":
            label = "%s (%s)" % (a.get("metric") or b.get("metric"),
                                 a.get("unit") or b.get("unit"))
        rows.append((label, va, vb, _pct(va, vb)))
    return rows


def phase_shifts(a, b):
    pa = a.get("phases") or {}
    pb = b.get("phases") or {}
    rows = []
    for k in sorted(set(pa) | set(pb)):
        va, vb = pa.get(k, 0.0), pb.get(k, 0.0)
        if va or vb:
            rows.append((k, va, vb, _pct(va, vb)))
    return rows


def _top_sink(row):
    top = ((row.get("op_profile") or {}).get("top") or [{}])[0]
    if not top.get("op"):
        return None
    return "%s [%s] %.0f%%" % (top.get("op"), top.get("op_class"),
                               100.0 * (top.get("share") or 0.0))


def op_sink_shifts(a, b):
    """Per-op-class device-time deltas (us) when BOTH runs carry the
    `mx.xprof` ``op_profile`` breakdown on their summary rows — this is
    the answer to WHICH op moved, one level below the phase shifts.
    Returns (class_rows, top_a, top_b) or None when either run lacks a
    profile."""
    pa = a.get("op_profile") or {}
    pb = b.get("op_profile") or {}
    ca, cb = pa.get("op_classes") or {}, pb.get("op_classes") or {}
    if not ca or not cb:
        return None
    rows = []
    for k in sorted(set(ca) | set(cb)):
        va, vb = ca.get(k, 0.0), cb.get(k, 0.0)
        if va or vb:
            rows.append((k, va, vb, _pct(va, vb)))
    # biggest mover first — the headline of the diff
    rows.sort(key=lambda r: -abs((r[2] or 0) - (r[1] or 0)))
    return rows, _top_sink(a), _top_sink(b)


def _fmt_num(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        return "%.4g" % v
    return str(v)


def report(path_a, path_b):
    rows_a, rows_b = _read(path_a), _read(path_b)
    a, b = primary_row(rows_a), primary_row(rows_b)
    out = {
        "run_a": {"path": path_a,
                  "run_id": a.get("run_id") or rows_a[0].get("run_id"),
                  "series": series_view(rows_a)},
        "run_b": {"path": path_b,
                  "run_id": b.get("run_id") or rows_b[0].get("run_id"),
                  "series": series_view(rows_b)},
        "knob_deltas": [{"knob": k, "a": va, "b": vb}
                        for k, va, vb in knob_deltas(a, b)],
        "metric_deltas": [{"metric": m, "a": va, "b": vb, "pct": p}
                          for m, va, vb, p in metric_deltas(a, b)],
        "phase_shifts": [{"phase": ph, "a_us": va, "b_us": vb,
                          "pct": p}
                         for ph, va, vb, p in phase_shifts(a, b)],
    }
    sinks = op_sink_shifts(a, b)
    if sinks is not None:
        class_rows, top_a, top_b = sinks
        out["op_sink_shifts"] = {
            "classes": [{"op_class": c, "a_us": va, "b_us": vb,
                         "pct": p}
                        for c, va, vb, p in class_rows],
            "top_sink_a": top_a, "top_sink_b": top_b,
        }
    return out


def print_report(rep):
    for tag in ("run_a", "run_b"):
        r = rep[tag]
        s = r["series"]
        print("%s: %s  (%d sample rows, roles %s)"
              % (tag[-1].upper(), r["run_id"], s["samples"],
                 ",".join(s.get("roles", []))))
        extra = "  ".join("%s=%s" % (k, _fmt_num(s[k]))
                          for k in ("step_time_ms_avg", "mfu_avg",
                                    "examples_per_sec_avg") if k in s)
        if extra:
            print("   series: %s" % extra)
    print()
    print("knob deltas (%d):" % len(rep["knob_deltas"]))
    for d in rep["knob_deltas"]:
        print("  %-28s %s -> %s" % (d["knob"],
                                    d["a"] if d["a"] is not None
                                    else "(unset)",
                                    d["b"] if d["b"] is not None
                                    else "(unset)"))
    if not rep["knob_deltas"]:
        print("  (none: identical recorded environments)")
    print()
    print("metric deltas:")
    for d in rep["metric_deltas"]:
        pct = ("  (%+.1f%%)" % d["pct"]) if d["pct"] is not None else ""
        print("  %-28s %10s -> %10s%s"
              % (d["metric"], _fmt_num(d["a"]), _fmt_num(d["b"]), pct))
    if not rep["metric_deltas"]:
        print("  (no comparable metrics)")
    if rep["phase_shifts"]:
        print()
        print("phase shifts (us/step):")
        for d in rep["phase_shifts"]:
            pct = ("  (%+.1f%%)" % d["pct"]) \
                if d["pct"] is not None else ""
            print("  %-28s %10s -> %10s%s"
                  % (d["phase"], _fmt_num(d["a_us"]),
                     _fmt_num(d["b_us"]), pct))
    sinks = rep.get("op_sink_shifts")
    if sinks:
        print()
        print("op-class device-time shifts (us, mx.xprof):")
        for d in sinks["classes"]:
            pct = ("  (%+.1f%%)" % d["pct"]) \
                if d["pct"] is not None else ""
            print("  %-28s %10s -> %10s%s"
                  % (d["op_class"], _fmt_num(d["a_us"]),
                     _fmt_num(d["b_us"]), pct))
        print("  top sink: %s -> %s"
              % (sinks.get("top_sink_a") or "-",
                 sinks.get("top_sink_b") or "-"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("run_a")
    ap.add_argument("run_b")
    ap.add_argument("--run-dir", default=os.environ.get("MXTPU_RUN_DIR"),
                    help="resolve bare run ids against this ledger dir")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--fail-on-slower", type=float, default=None,
                    metavar="PCT",
                    help="exit 1 when run B's step time regressed "
                         "more than PCT%% vs run A (ratchet hook)")
    args = ap.parse_args(argv)
    rep = report(_resolve(args.run_dir, args.run_a),
                 _resolve(args.run_dir, args.run_b))
    if args.json:
        print(json.dumps(rep, indent=2, default=str))
    else:
        print_report(rep)
    if args.fail_on_slower is not None:
        for d in rep["metric_deltas"]:
            if d["metric"] == "step_time_us" and d["pct"] is not None \
                    and d["pct"] > args.fail_on_slower:
                print("compare_runs: REGRESSION step_time_us %+.1f%% "
                      "> budget %.1f%%" % (d["pct"],
                                           args.fail_on_slower),
                      file=sys.stderr)
                return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
