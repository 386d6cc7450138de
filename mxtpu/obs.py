"""mx.obs — the live cluster observability plane.

Every observability layer before this one is instant-or-post-hoc:
`mxtpu/telemetry.py` gauges report the LAST value, chrome traces and
``cluster.json`` exist only after ``merge_dir`` runs at exit, and
nothing survives across runs.  This module adds the time axis and the
scrape surface a production fleet needs.  Four pieces:

  * **Sampler** — a per-role background thread
    (``MXTPU_OBS_SAMPLE_S``, default 5s; ``MXTPU_OBS=0`` opts out)
    that snapshots the existing surfaces — ``telemetry.metrics()``
    gauges, `mx.perf` phase/MFU rows, serve queue-depth/occupancy/SLO
    histograms, health anomaly counts, sharding collective byte
    counters — into a bounded timestamped ring
    (``MXTPU_OBS_RING``).  A sample is STRICTLY read-only over
    already-cached values: it must never compile a program or sync a
    device (the same contract as the PR 10 scrape rule, asserted by
    `tests/test_obs.py` and `tools/check_obs.py`).  Interval
    percentiles come from :meth:`telemetry.Histogram.interval`, so a
    sample row carries per-window p50/p95/p99, not lifetime values.

  * **OpenMetrics exporter** — one tiny threaded HTTP listener per
    role (trainer, PS worker/server/scheduler, serve replica) serving
    ``GET /metrics`` in OpenMetrics/Prometheus text (JSON via content
    negotiation), plus ``/samples.json`` (the ring), ``/snapshot.json``
    (the aggregation unit) and ``/healthz``.  ``MXTPU_OBS_PORT`` sets
    the base port (auto-incremented per process when taken); without
    it an ephemeral port is used and discovered through the
    ``obs_pid<pid>.json`` file each sampler tick rewrites into
    ``MXTPU_TELEMETRY_DIR`` — ONE scrape config covers the training
    and serving fleets identically.

  * **Live cluster aggregation** — ``tools/launch.py`` (all modes)
    runs :func:`aggregator_main` as a sidecar child that periodically
    scrapes every discovered role endpoint and atomically rewrites
    ``cluster_live.json`` DURING the run (per-rank step time / MFU /
    dominant phase, queue depths, anomaly + retry tickers, recent
    sample tails, and a ``dead`` list naming ranks whose endpoint
    stopped answering).  ``tools/dash.py`` renders it as a live
    terminal dashboard with sparklines.

  * **Run ledger** — with ``MXTPU_RUN_DIR`` set, every sample row plus
    one final summary row (headline metrics, knobs = the ``MXTPU_*``
    env) appends to ``MXTPU_RUN_DIR/<run_id>.jsonl``; ``MXTPU_RUN_ID``
    (set for the whole fleet by ``tools/launch.py``) makes one run =
    one file.  ``tools/compare_runs.py`` diffs two runs into a
    knob/metric delta report.

Cost discipline: disabled (``MXTPU_OBS=0``) means no thread, no
socket, no file; enabled, a sample is a handful of dict reads
(``obs_sample_wall_us_last`` gauges the measured cost; the
`tools/check_obs.py` budget is ``MXTPU_OBS_BUDGET_US``).  See
`docs/observability.md` §Live metrics.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .base import getenv, getenv_bool, getenv_int, getpid_cached
from . import tracing as _tracing

__all__ = [
    "enabled",
    "enable",
    "armed",
    "sample_interval",
    "sample",
    "samples",
    "start",
    "ensure_started",
    "stop",
    "started",
    "port",
    "openmetrics",
    "parse_openmetrics",
    "CONTENT_TYPE",
    "run_id",
    "ledger_path",
    "ledger_append",
    "summary_row",
    "read_ledger",
    "aggregate_once",
    "aggregator_main",
]

_ENABLED = getenv_bool("MXTPU_OBS", True)
_RING_SIZE = max(8, getenv_int("MXTPU_OBS_RING", 720))

#: the OpenMetrics content type `/metrics` replies with
CONTENT_TYPE = "application/openmetrics-text; version=1.0.0; " \
               "charset=utf-8"

_lock = threading.RLock()
_RING: collections.deque = collections.deque(maxlen=_RING_SIZE)

# sampler/exporter state (under _lock)
_STATE: Dict[str, Any] = {
    "thread": None, "stop": None, "httpd": None, "http_thread": None,
    "port": None, "seq": 0, "run_id": None, "ledger": None,
    "atexit": False, "hist_states": {}, "discovery": None,
    "final_done": False,
}


def enabled() -> bool:
    """Observability plane on?  ``MXTPU_OBS=0`` opts out at import."""
    return _ENABLED


def enable(on: bool = True) -> None:
    """Flip at runtime (tests / embedding).  Does not stop a running
    sampler — use :func:`stop`."""
    global _ENABLED
    _ENABLED = bool(on)


def sample_interval() -> float:
    """Seconds between sampler ticks (``MXTPU_OBS_SAMPLE_S``, default
    5).  Read per tick so a live process can be retuned."""
    try:
        return max(0.05, float(getenv("MXTPU_OBS_SAMPLE_S", "5") or 5))
    except ValueError:
        return 5.0


def armed() -> bool:
    """Should this process auto-start the plane?  True when enabled
    AND the process looks like a launched role: an explicit port
    (``MXTPU_OBS_PORT``), a run ledger (``MXTPU_RUN_DIR``) or a
    telemetry directory (``MXTPU_TELEMETRY_DIR``) is configured.  A
    bare in-process import (the tier-1 suite) stays dormant — zero
    threads, zero sockets."""
    return _ENABLED and bool(getenv("MXTPU_OBS_PORT")
                             or getenv("MXTPU_RUN_DIR")
                             or getenv("MXTPU_TELEMETRY_DIR"))


def run_id() -> str:
    """This run's ledger key: ``MXTPU_RUN_ID`` (set fleet-wide by
    ``tools/launch.py``) or a per-process ``<start>_<role><rank>``
    fallback."""
    with _lock:
        if _STATE["run_id"]:
            return _STATE["run_id"]
    rid = getenv("MXTPU_RUN_ID")
    if not rid:
        from . import telemetry as _tel

        ident = _tel.identity()
        rid = "run%d_%s%d" % (int(time.time()),
                              ident["role"], ident["rank"])
    with _lock:
        _STATE["run_id"] = rid
    return rid


# ---------------------------------------------------------------------------
# Sampling (strictly read-only: no compiles, no device syncs)
# ---------------------------------------------------------------------------

# additive profiler counters a sample row carries verbatim (small,
# stable subset — the ledger reconciliation keys `tools/check_obs.py`
# checks against the final telemetry snapshots)
_SAMPLE_COUNTERS = ("telemetry_steps", "serve_rows", "serve_requests",
                    "serve_shed", "flight_dumps", "inspect_compiles",
                    "inspect_recompiles", "obs_samples")

_COLLECTIVE_KEYS = ("allgather_bytes", "reduce_scatter_bytes",
                    "allreduce_bytes", "alltoall_bytes",
                    "ppermute_bytes", "reshard_bytes")


def sample() -> Optional[Dict[str, Any]]:
    """Build ONE timestamped sample row from the already-cached
    observability surfaces.  Read-only by contract: this never
    compiles (`mx.perf`'s metrics block uses cached analysis only) and
    never blocks on a device.  Returns the row (also appended to the
    ring), or None when disabled."""
    if not _ENABLED:
        return None
    from . import profiler as _prof
    from . import telemetry as _tel

    t0 = time.perf_counter()
    stats = _prof.stats()
    m = _tel.metrics()
    ident = _tel.identity()
    perf = m.get("perf") or {}
    serve = m.get("serve") or {}
    with _lock:
        _STATE["seq"] += 1
        seq = _STATE["seq"]
    row: Dict[str, Any] = {
        "kind": "sample",
        "ts": time.time(),
        "seq": seq,
        "run_id": run_id(),
        "role": ident["role"],
        "rank": ident["rank"],
        "pid": ident["pid"],
        "steps": m.get("steps", 0),
        "step_time_ms": round(m.get("step_time_last_s", 0.0) * 1e3, 3),
        "examples_per_sec": round(m.get("examples_per_sec", 0.0), 2),
        "input_wait_frac": round(m.get("input_wait_frac", 0.0), 4),
        "nonfinite_steps": m.get("nonfinite_steps", 0),
        "mem_watermark_bytes": m.get("device_mem_watermark_bytes", 0),
    }
    if perf.get("mfu") is not None:
        row["mfu"] = perf["mfu"]
    if perf.get("dominant_phase"):
        row["dominant_phase"] = perf["dominant_phase"]
    if perf.get("phases_us_per_step"):
        row["phases_us_per_step"] = perf["phases_us_per_step"]
    # the role's dominant critical-path segment (mx.tracing): which
    # named span segment owns the largest share of sampled span time
    tracing = m.get("tracing") or {}
    if tracing.get("dominant_segment"):
        row["critical_path"] = tracing["dominant_segment"]
    # the role's top device-time sink (mx.xprof): a dict lookup into
    # the latest attached OpProfile — sample() stays read-only
    try:
        from . import xprof as _xprof

        sink = _xprof.top_sink()
        if sink is not None:
            row["top_sink"] = "%s:%.0f%%" % (
                sink.get("op_class") or sink["op"],
                100.0 * (sink.get("share") or 0.0))
            row["top_sink_op"] = sink["op"]
    except Exception:
        pass
    # device-memory census (mx.hbm): the provider already ran inside
    # _tel.metrics() above — this is a dict reshape, still read-only
    hbm = m.get("hbm") or {}
    if hbm.get("enabled"):
        row["hbm"] = {
            "used_bytes": hbm.get("used_bytes", 0),
            "peak_used_bytes": hbm.get("peak_used_bytes", 0),
            "headroom_bytes": hbm.get("headroom_bytes", 0),
            "leak": bool(hbm.get("leak")),
        }
        if hbm.get("last_leak"):
            row["hbm"]["last_leak"] = hbm["last_leak"]
    if serve:
        row["serve"] = {
            "queue_depth": serve.get("queue_depth", 0),
            "inflight": serve.get("inflight", 0),
            "occupancy_pct": serve.get("batch_occupancy_pct", 0.0),
            "draining": bool(serve.get("draining")),
        }
    row.update(_tel.stat_rollup(stats))
    coll = {k: int(stats.get(k, 0)) for k in _COLLECTIVE_KEYS
            if stats.get(k)}
    if coll:
        row["collective_bytes"] = coll
    row["counters"] = {k: int(stats.get(k, 0))
                       for k in _SAMPLE_COUNTERS if k in stats}
    # per-window latency percentiles: each registered histogram's
    # delta vs the previous sample (telemetry.Histogram.interval), so
    # the time series answers "what was p99 in THIS window", not
    # "since process start".  The read-modify-write of the per-
    # histogram window state runs under _lock: the SIGTERM ledger
    # epilogue calls sample() on the main thread while the sampler
    # thread may be mid-tick, and an unguarded race would report the
    # same window twice (or drop one) in the closing ledger rows
    hist_rows = {}
    hists = _tel._registered_histograms()
    with _lock:
        hist_states = _STATE["hist_states"]
        for name, h in hists.items():
            snap, state = h.interval(hist_states.get(name))
            hist_states[name] = state
            if snap["count"]:
                hist_rows[name] = {"count": snap["count"],
                                   "p50": _r3(snap["p50"]),
                                   "p95": _r3(snap["p95"]),
                                   "p99": _r3(snap["p99"])}
    if hist_rows:
        row["hist_interval"] = hist_rows
    wall_us = (time.perf_counter() - t0) * 1e6
    row["sample_wall_us"] = round(wall_us, 1)
    with _lock:
        _RING.append(row)
    _prof.inc_stat("obs_samples")
    _prof.set_stat("obs_sample_wall_us_last", int(wall_us))
    return row


def _r3(x: float) -> float:
    return float("%.4g" % x)


def samples(last: Optional[int] = None) -> List[Dict[str, Any]]:
    """Ring snapshot (oldest first), optionally the last N rows.
    Taken under the lock: an HTTP scrape thread iterating the deque
    while the sampler appends would raise 'mutated during
    iteration' — and a torn /snapshot.json response reads as a DEAD
    rank to the live aggregator."""
    with _lock:
        rows = list(_RING)
    if last is not None and len(rows) > last:
        rows = rows[-last:]
    return rows


def clear() -> None:
    """Drop ring + sequence state (tests)."""
    with _lock:
        _RING.clear()
        _STATE["seq"] = 0
        _STATE["hist_states"] = {}


# ---------------------------------------------------------------------------
# OpenMetrics rendering
# ---------------------------------------------------------------------------

def _sanitize(name: str) -> str:
    s = "".join(ch if ch.isalnum() or ch == "_" else "_"
                for ch in name)
    if not s or s[0].isdigit():
        s = "_" + s
    return s


def _esc_label(v: str) -> str:
    return str(v).replace("\\", "\\\\").replace("\"", "\\\"") \
        .replace("\n", "\\n")


def _fmt_labels(labels: Dict[str, Any]) -> str:
    if not labels:
        return ""
    inner = ",".join('%s="%s"' % (k, _esc_label(v))
                     for k, v in sorted(labels.items()))
    return "{%s}" % inner


def _fmt_value(v: float) -> str:
    if isinstance(v, bool):
        return "1" if v else "0"
    if isinstance(v, int):
        return str(v)
    f = float(v)
    if f != f or f in (float("inf"), float("-inf")):
        return "0"  # the scrape surface is strict JSON-safe floats
    return repr(f)


def openmetrics() -> str:
    """This process's metrics in OpenMetrics text format (the
    ``/metrics`` body).  Families: every ``profiler.stats()`` key
    (counters get the spec's ``_total`` suffix; ``telemetry.
    GAUGE_STATS`` render as gauges; ``a::b`` keys become family ``a``
    with a ``key="b"`` label), the always-on step metrics, the
    `mx.perf` MFU/phase gauges, and every registered
    :class:`telemetry.Histogram` as a summary (p50/p95/p99 quantile
    samples + ``_count``/``_sum``).  Every sample carries
    ``role``/``rank`` labels so one scraper covers a mixed
    training+serving fleet.  Strictly read-only (never compiles, never
    syncs a device) — validated by :func:`parse_openmetrics`."""
    from . import profiler as _prof
    from . import telemetry as _tel

    ident = _tel.identity()
    base = {"role": ident["role"], "rank": ident["rank"]}
    stats = _prof.stats()
    m = _tel.metrics()

    # family -> (type, [(sample_name, labels, value)])
    fams: "collections.OrderedDict[str, Tuple[str, List]]" = \
        collections.OrderedDict()

    def add(fam: str, mtype: str, value: Any,
            labels: Optional[Dict[str, Any]] = None,
            suffix: str = "") -> None:
        ent = fams.get(fam)
        if ent is None:
            ent = fams[fam] = (mtype, [])
        lab = dict(base)
        if labels:
            lab.update(labels)
        ent[1].append((fam + suffix, lab, value))

    add("mxtpu_obs", "info", 1,
        {"pid": ident["pid"], "run_id": run_id(),
         "version": "1"}, suffix="_info")
    for key in sorted(stats):
        val = stats[key]
        if "::" in key:
            prefix, _, rest = key.partition("::")
            fam = "mxtpu_" + _sanitize(prefix)
            labels = {"key": rest}
        else:
            fam = "mxtpu_" + _sanitize(key)
            labels = None
        if key in _tel.GAUGE_STATS:
            add(fam, "gauge", val, labels)
        else:
            add(fam, "counter", max(0, int(val)), labels,
                suffix="_total")
    add("mxtpu_examples_per_second", "gauge",
        m.get("examples_per_sec", 0.0))
    add("mxtpu_input_wait_frac", "gauge", m.get("input_wait_frac", 0.0))
    add("mxtpu_step_time_avg_seconds", "gauge",
        m.get("step_time_avg_s", 0.0))
    perf = m.get("perf") or {}
    if perf.get("mfu") is not None:
        add("mxtpu_mfu", "gauge", perf["mfu"])
    for phase, us in sorted((perf.get("phases_us_per_step")
                             or {}).items()):
        add("mxtpu_perf_phase_us_per_step", "gauge", us,
            {"phase": phase})
    hbm = m.get("hbm") or {}
    if hbm.get("enabled"):
        add("mxtpu_hbm_used_bytes", "gauge", hbm.get("used_bytes", 0))
        add("mxtpu_hbm_peak_bytes", "gauge",
            hbm.get("peak_used_bytes", 0))
        add("mxtpu_hbm_headroom_bytes", "gauge",
            hbm.get("headroom_bytes", 0))
        add("mxtpu_hbm_leak_suspect", "gauge",
            1 if hbm.get("leak") else 0)
    serve = m.get("serve") or {}
    if serve:
        add("mxtpu_serve_draining", "gauge",
            1 if serve.get("draining") else 0)
    for name, snap in sorted(_tel.histograms().items()):
        if "::" in name:
            prefix, _, rest = name.partition("::")
            fam = "mxtpu_" + _sanitize(prefix)
            labels: Dict[str, Any] = {"key": rest}
        else:
            fam = "mxtpu_" + _sanitize(name)
            labels = {}
        ent = fams.get(fam)
        if ent is not None and ent[0] != "summary":
            # a stats counter already owns this family name: divert
            # the histogram to a sibling family rather than emit
            # mixed-type samples the strict parser would reject
            fam += "_hist"
            ent = fams.get(fam)
        if ent is None:
            ent = fams[fam] = ("summary", [])
        # mx.tracing exemplar: the slowest kept request's trace id
        # rides the p99 quantile sample (`# {trace_id="..."} value`
        # exemplar syntax) — p99 becomes clickable from Prometheus
        ex = _tracing.exemplar(name)
        for q, k in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
            lab = dict(base)
            lab.update(labels)
            lab["quantile"] = q
            if q == "0.99" and ex is not None:
                ent[1].append((fam, lab, snap[k], ex))
            else:
                ent[1].append((fam, lab, snap[k]))
        lab = dict(base)
        lab.update(labels)
        ent[1].append((fam + "_count", lab, snap["count"]))
        ent[1].append((fam + "_sum", lab, snap["sum"]))

    lines: List[str] = []
    for fam, (mtype, rows) in fams.items():
        lines.append("# TYPE %s %s" % (fam, mtype))
        for row in rows:
            name, labels, value = row[0], row[1], row[2]
            line = "%s%s %s" % (name, _fmt_labels(labels),
                                _fmt_value(value))
            if len(row) > 3:
                ex = row[3]
                line += ' # {trace_id="%s"} %s' % (
                    ex["trace_id"], _fmt_value(ex["value"]))
            lines.append(line)
    lines.append("# EOF")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# Strict OpenMetrics parser (tests + check tool + dash)
# ---------------------------------------------------------------------------

_TYPES = ("counter", "gauge", "summary", "histogram", "info",
          "unknown", "stateset")


def _valid_name(n: str) -> bool:
    if not n:
        return False
    if not (n[0].isalpha() or n[0] in "_:"):
        return False
    return all(c.isalnum() or c in "_:" for c in n)


def _parse_labels(text: str, lineno: int) -> Dict[str, str]:
    labels: Dict[str, str] = {}
    i = 0
    while i < len(text):
        j = text.find("=", i)
        if j < 0:
            raise ValueError("line %d: malformed labels %r"
                             % (lineno, text))
        key = text[i:j].strip(",").strip()
        if not _valid_name(key) or ":" in key:
            raise ValueError("line %d: bad label name %r"
                             % (lineno, key))
        if key in labels:
            raise ValueError("line %d: duplicate label %r"
                             % (lineno, key))
        if j + 1 >= len(text) or text[j + 1] != '"':
            raise ValueError("line %d: unquoted label value"
                             % lineno)
        k = j + 2
        val = []
        while k < len(text):
            c = text[k]
            if c == "\\":
                if k + 1 >= len(text):
                    raise ValueError("line %d: dangling escape"
                                     % lineno)
                nxt = text[k + 1]
                val.append({"\\": "\\", '"': '"', "n": "\n"}
                           .get(nxt, nxt))
                k += 2
                continue
            if c == '"':
                break
            val.append(c)
            k += 1
        else:
            raise ValueError("line %d: unterminated label value"
                             % lineno)
        labels[key] = "".join(val)
        i = k + 1
    return labels


def _family_of(sample_name: str, fams: Dict[str, Dict]) -> Optional[str]:
    """Which declared family does this sample name belong to (strict:
    suffix rules per metric type)."""
    for fam, info in fams.items():
        t = info["type"]
        if t == "counter" and sample_name in (fam + "_total",
                                              fam + "_created"):
            return fam
        if t in ("gauge", "unknown") and sample_name == fam:
            return fam
        if t == "summary" and sample_name in (fam, fam + "_count",
                                              fam + "_sum",
                                              fam + "_created"):
            return fam
        if t == "histogram" and sample_name in (
                fam + "_bucket", fam + "_count", fam + "_sum",
                fam + "_created"):
            return fam
        if t == "info" and sample_name == fam + "_info":
            return fam
        if t == "stateset" and sample_name == fam:
            return fam
    return None


def parse_openmetrics(text: str) -> Dict[str, Dict[str, Any]]:
    """STRICT OpenMetrics parser: validates the line grammar, metric
    and label names, escaping, the type-specific sample-name suffix
    rules (counter samples must be ``<family>_total``, summaries
    ``<family>{quantile=..}``/``_count``/``_sum``, info
    ``<family>_info``), TYPE-before-samples ordering, duplicate
    TYPE/sample detection, float-parseable values, non-negative
    counters, and the mandatory ``# EOF`` terminator.  Exemplars
    (`` # {trace_id="..."} value [ts]`` after a sample, the
    `mx.tracing` slowest-request annotation) are validated — label
    syntax, float value, ≤2 trailing tokens, 32-hex ``trace_id`` —
    and collected under the family's ``"exemplars"`` key.  Returns
    ``{family: {"type": ..., "samples": [(name, labels, value)]}}``.
    Raises ``ValueError`` naming the offending line on any
    violation."""
    if not text.endswith("\n"):
        raise ValueError("exposition must end with a newline")
    lines = text.split("\n")
    if lines[-1] == "":
        lines.pop()
    if not lines or lines[-1] != "# EOF":
        raise ValueError("missing the mandatory '# EOF' terminator")
    fams: "collections.OrderedDict[str, Dict[str, Any]]" = \
        collections.OrderedDict()
    seen_samples = set()
    for lineno, line in enumerate(lines[:-1], 1):
        if line == "# EOF":
            raise ValueError("line %d: '# EOF' before the end" % lineno)
        if line.startswith("#"):
            parts = line.split(" ", 3)
            if len(parts) < 3 or parts[0] != "#" or \
                    parts[1] not in ("TYPE", "HELP", "UNIT"):
                raise ValueError("line %d: malformed comment %r"
                                 % (lineno, line))
            name = parts[2]
            if not _valid_name(name):
                raise ValueError("line %d: bad family name %r"
                                 % (lineno, name))
            if parts[1] == "TYPE":
                mtype = parts[3] if len(parts) > 3 else ""
                if mtype not in _TYPES:
                    raise ValueError("line %d: unknown type %r"
                                     % (lineno, mtype))
                if name in fams:
                    raise ValueError("line %d: duplicate TYPE for %r"
                                     % (lineno, name))
                fams[name] = {"type": mtype, "samples": []}
            continue
        if not line.strip():
            raise ValueError("line %d: blank line not allowed" % lineno)
        # sample line: name[{labels}] value [ts] [# {exemplar} value]
        # — split the exemplar off FIRST: its closing brace would
        # otherwise be the rfind("}") the label parse anchors on
        exemplar = None
        if " # {" in line:
            line, exraw = line.split(" # ", 1)
            exemplar = _parse_exemplar(exraw, lineno)
        brace = line.find("{")
        if brace >= 0:
            name = line[:brace]
            close = line.rfind("}")
            if close < brace:
                raise ValueError("line %d: unbalanced braces" % lineno)
            labels = _parse_labels(line[brace + 1:close], lineno)
            rest = line[close + 1:].strip()
        else:
            fields = line.split(None, 1)
            if len(fields) != 2:
                raise ValueError("line %d: no value on sample line"
                                 % lineno)
            name, rest = fields[0], fields[1]
            labels = {}
        if not _valid_name(name):
            raise ValueError("line %d: bad metric name %r"
                             % (lineno, name))
        toks = rest.split()
        if not toks or len(toks) > 2:
            raise ValueError("line %d: bad value field %r"
                             % (lineno, rest))
        try:
            value = float(toks[0])
        except ValueError:
            raise ValueError("line %d: unparseable value %r"
                             % (lineno, toks[0]))
        fam = _family_of(name, fams)
        if fam is None:
            raise ValueError(
                "line %d: sample %r has no preceding TYPE family "
                "(or violates its suffix rules)" % (lineno, name))
        if fams[fam]["type"] == "counter" and value < 0:
            raise ValueError("line %d: negative counter %r"
                             % (lineno, name))
        sig = (name, tuple(sorted(labels.items())))
        if sig in seen_samples:
            raise ValueError("line %d: duplicate sample %r %r"
                             % (lineno, name, labels))
        seen_samples.add(sig)
        fams[fam]["samples"].append((name, labels, value))
        if exemplar is not None:
            # kept OFF the samples tuples so 3-tuple consumers of
            # ``"samples"`` never see a surprise 4th element
            fams[fam].setdefault("exemplars", []).append(
                (name, labels, exemplar))
    return dict(fams)


def _parse_exemplar(exraw: str, lineno: int) -> Dict[str, Any]:
    """Validate one `` # {labels} value [ts]`` exemplar tail."""
    exraw = exraw.strip()
    if not exraw.startswith("{"):
        raise ValueError("line %d: exemplar must start with '{', got "
                         "%r" % (lineno, exraw))
    close = exraw.rfind("}")
    if close < 0:
        raise ValueError("line %d: unbalanced exemplar braces" % lineno)
    exlabels = _parse_labels(exraw[1:close], lineno)
    tid = exlabels.get("trace_id")
    if tid is not None:
        if len(tid) != 32:
            raise ValueError("line %d: exemplar trace_id must be 32 "
                             "hex chars, got %r" % (lineno, tid))
        try:
            int(tid, 16)
        except ValueError:
            raise ValueError("line %d: exemplar trace_id %r is not "
                             "hex" % (lineno, tid))
    extoks = exraw[close + 1:].split()
    if not extoks or len(extoks) > 2:
        raise ValueError("line %d: exemplar needs a value (and at "
                         "most a timestamp), got %r"
                         % (lineno, exraw[close + 1:]))
    try:
        exval = float(extoks[0])
    except ValueError:
        raise ValueError("line %d: unparseable exemplar value %r"
                         % (lineno, extoks[0]))
    return {"labels": exlabels, "value": exval,
            "ts": float(extoks[1]) if len(extoks) == 2 else None}


# ---------------------------------------------------------------------------
# Run ledger
# ---------------------------------------------------------------------------

def ledger_path() -> Optional[str]:
    """``MXTPU_RUN_DIR/<run_id>.jsonl`` or None when no run dir is
    configured."""
    d = getenv("MXTPU_RUN_DIR")
    if not d:
        return None
    return os.path.join(d, "%s.jsonl" % run_id())


def ledger_append(row: Dict[str, Any]) -> Optional[str]:
    """Append one JSON row to the run ledger (no-op without
    ``MXTPU_RUN_DIR``).  One ``write()`` of one line — concurrent
    roles appending to the shared per-run file interleave at line
    granularity.  Never raises (a broken sink must not fail the
    run)."""
    path = ledger_path()
    if path is None or not _ENABLED:
        return None
    from . import telemetry as _tel

    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        line = json.dumps(_tel._json_safe(row), default=str,
                          allow_nan=False)
        with open(path, "a") as f:
            f.write(line + "\n")
    except (OSError, ValueError):
        return None
    return path


def summary_row() -> Dict[str, Any]:
    """The run's FINAL ledger row: one ``mxtpu-bench-v1`` record
    holding the headline throughput/step-time/MFU/phases, the full
    ``MXTPU_*`` knob environment, and the final counter snapshot the
    sample rows reconcile against."""
    from . import profiler as _prof
    from . import telemetry as _tel

    ident = _tel.identity()
    m = _tel.metrics()
    perf = m.get("perf") or {}
    knobs = {k: v for k, v in sorted(os.environ.items())
             if k.startswith("MXTPU_")
             or k in ("JAX_PLATFORMS", "XLA_FLAGS")}
    steps = m.get("steps", 0)
    return {
        "kind": "summary",
        "schema": "mxtpu-bench-v1",
        "bench": "obs",
        "ts": time.time(),
        "run_id": run_id(),
        "role": ident["role"],
        "rank": ident["rank"],
        "pid": ident["pid"],
        "metric": "steps",
        "value": float(steps),
        "unit": "steps",
        "vs_baseline": float(steps),
        "throughput": m.get("examples_per_sec"),
        "step_time_us": m.get("step_time_avg_s", 0.0) * 1e6
        if steps else None,
        "mfu": perf.get("mfu"),
        "phases": perf.get("phases_us_per_step"),
        "knobs": knobs,
        "counters": _prof.stats(),
        "extra": {"samples": len(_RING),
                  "nonfinite_steps": m.get("nonfinite_steps", 0)},
    } | _op_profile_block()


def _op_profile_block() -> Dict[str, Any]:
    """``{"op_profile": <compact breakdown>}`` when an `mx.xprof`
    profile was attached this run (else empty) — what makes ledger
    summary rows diffable per op class by ``tools/compare_runs.py``."""
    try:
        from . import xprof as _xprof

        opb = _xprof.bench_breakdown()
    except Exception:
        opb = None
    return {"op_profile": opb} if opb else {}


def read_ledger(path: str) -> List[Dict[str, Any]]:
    """Parse a ledger file, tolerating a truncated final line (the
    writer may have been SIGKILLed mid-append)."""
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except ValueError:
                continue  # torn tail line
    return rows


# ---------------------------------------------------------------------------
# The exporter + sampler threads
# ---------------------------------------------------------------------------

def _discovery_path() -> Optional[str]:
    d = getenv("MXTPU_TELEMETRY_DIR")
    if not d:
        return None
    return os.path.join(d, "obs_pid%d.json" % getpid_cached())


def _write_discovery() -> None:
    """Rewrite this role's endpoint-discovery file (tiny; every
    sampler tick, so an elastic re-rank self-corrects)."""
    path = _discovery_path()
    if path is None or _STATE["port"] is None:
        return
    from . import telemetry as _tel

    ident = _tel.identity()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        payload = {"role": ident["role"], "rank": ident["rank"],
                   "pid": ident["pid"], "port": _STATE["port"],
                   "ts": time.time(), "run_id": run_id()}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        _STATE["discovery"] = path
    except OSError:
        pass


def _make_httpd(port_base: Optional[int]):
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # quiet
            pass

        def _reply(self, code: int, body: bytes, ctype: str) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _reply_json(self, payload: Any) -> None:
            from . import telemetry as _tel

            self._reply(200, json.dumps(
                _tel._json_safe(payload), default=str,
                allow_nan=False).encode(), "application/json")

        def do_GET(self):
            from . import profiler as _prof
            from . import telemetry as _tel

            path = self.path.split("?", 1)[0]
            try:
                if path == "/metrics":
                    _prof.inc_stat("obs_scrapes")
                    accept = self.headers.get("Accept", "") or ""
                    if "application/json" in accept:
                        self._reply_json(_tel.metrics())
                    else:
                        self._reply(200, openmetrics().encode(),
                                    CONTENT_TYPE)
                elif path == "/metrics.json":
                    self._reply_json(_tel.metrics())
                elif path == "/samples.json":
                    self._reply_json({"run_id": run_id(),
                                      "samples": samples()})
                elif path == "/snapshot.json":
                    snap = _tel.snapshot(max_events=32)
                    snap["run_id"] = run_id()
                    snap["obs_samples"] = samples(last=32)
                    self._reply_json(snap)
                elif path == "/healthz":
                    ident = _tel.identity()
                    self._reply_json({"ok": True, "role": ident["role"],
                                      "rank": ident["rank"],
                                      "pid": ident["pid"]})
                else:
                    self._reply(404, b'{"error": "no such path"}',
                                "application/json")
            except (BrokenPipeError, ConnectionError):
                pass

    last_err: Optional[Exception] = None
    if port_base:
        # auto-increment: ranks of one fleet share a base port and
        # each process takes the first free successor
        for k in range(64):
            try:
                return ThreadingHTTPServer(("127.0.0.1",
                                            port_base + k), _Handler)
            except OSError as e:
                last_err = e
        raise last_err or OSError("no free obs port")
    return ThreadingHTTPServer(("127.0.0.1", 0), _Handler)


def _sampler_loop(stop_ev: threading.Event) -> None:
    # drift-free cadence: tick k fires at t0 + k*interval, so a slow
    # sample does not push every later tick (the exact-cadence
    # contract tests assert)
    t0 = time.monotonic()
    k = 0
    while not stop_ev.is_set():
        k += 1
        target = t0 + k * sample_interval()
        while True:
            delay = target - time.monotonic()
            if delay <= 0:
                break
            if stop_ev.wait(min(delay, 0.2)):
                return
        row = sample()
        if row is not None:
            ledger_append(row)
        _write_discovery()


def started() -> bool:
    with _lock:
        t = _STATE["thread"]
        return t is not None and t.is_alive()


def port() -> Optional[int]:
    """The exporter's bound port (None when not started)."""
    with _lock:
        return _STATE["port"]


def start(http_port: Optional[int] = None) -> Optional[int]:
    """Start the sampler thread + OpenMetrics listener.  ``http_port``
    overrides ``MXTPU_OBS_PORT`` (0 = ephemeral).  Idempotent; returns
    the bound port, or None when ``MXTPU_OBS=0``."""
    if not _ENABLED:
        return None
    with _lock:
        if started():
            return _STATE["port"]
        if http_port is None:
            http_port = getenv_int("MXTPU_OBS_PORT", 0)
        try:
            httpd = _make_httpd(http_port or None)
        except OSError:
            httpd = _make_httpd(None)  # base range exhausted: ephemeral
        httpd.daemon_threads = True
        _STATE["httpd"] = httpd
        _STATE["port"] = httpd.server_address[1]
        ht = threading.Thread(target=httpd.serve_forever,
                              name="mxobs-http", daemon=True)
        ht.start()
        _STATE["http_thread"] = ht
        stop_ev = threading.Event()
        _STATE["stop"] = stop_ev
        t = threading.Thread(target=_sampler_loop, args=(stop_ev,),
                             name="mxobs-sampler", daemon=True)
        t.start()
        _STATE["thread"] = t
        _STATE["final_done"] = False
        if not _STATE["atexit"]:
            import atexit

            atexit.register(_at_exit)
            _STATE["atexit"] = True
    _write_discovery()
    return _STATE["port"]


def ensure_started() -> Optional[int]:
    """:func:`start` iff :func:`armed` — what every role (PS
    scheduler/server/worker registration, `mx.serve` replicas, a
    launched trainer at import) calls; a bare library import stays
    dormant."""
    if not armed():
        return None
    try:
        return start()
    except Exception:
        return None


def stop(final_rows: bool = True) -> None:
    """Stop the sampler + listener.  ``final_rows`` appends one last
    sample and the summary row to the ledger (the normal exit path),
    so even a run shorter than one interval leaves a ledger trail."""
    with _lock:
        stop_ev = _STATE["stop"]
        t = _STATE["thread"]
        httpd = _STATE["httpd"]
        _STATE["thread"] = None
        _STATE["stop"] = None
        _STATE["httpd"] = None
        _STATE["http_thread"] = None
        _STATE["port"] = None
        # an explicit stop() followed by the atexit stop() must not
        # append the final sample + summary twice
        final_rows = final_rows and not _STATE["final_done"]
        if final_rows:
            _STATE["final_done"] = True
    if stop_ev is not None:
        stop_ev.set()
    if t is not None:
        t.join(2.0)
    if httpd is not None:
        try:
            httpd.shutdown()
            httpd.server_close()
        except Exception:
            pass
    if final_rows:
        _write_final_rows()
    disc = _STATE.get("discovery")
    if disc:
        try:
            os.unlink(disc)
        except OSError:
            pass
        _STATE["discovery"] = None


def _write_final_rows() -> None:
    if not _ENABLED or not ledger_path():
        return
    row = sample()
    if row is not None:
        row["final"] = True
        ledger_append(row)
    ledger_append(summary_row())


def _ledger_epilogue() -> None:
    """Append the final sample + summary WITHOUT tearing threads down
    — the SIGTERM path.  The flight recorder's signal handler calls
    this before chaining to the previous disposition (which terminates
    the process, skipping atexit): a role the launcher reaps with
    SIGTERM still leaves its ledger epilogue.  A summary row therefore
    means an ORDERLY exit (clean return or graceful SIGTERM); a
    SIGKILLed rank leaves none — the distinction `tools/check_obs.py`
    asserts.  Idempotent vs :func:`stop`/atexit via ``final_done``."""
    with _lock:
        if _STATE["final_done"]:
            return
        _STATE["final_done"] = True
    _write_final_rows()


def _at_exit() -> None:
    try:
        stop(final_rows=True)
    except Exception:
        pass


def _disarm_in_child() -> None:
    """fork-without-exec children (DataLoader pool workers) inherit
    the module state but not the threads: they are helpers, not roles
    — they must not write ledger/discovery rows under the parent's
    identity (same rationale as telemetry's fork disarm)."""
    with _lock:
        _STATE["thread"] = None
        _STATE["stop"] = None
        _STATE["httpd"] = None
        _STATE["http_thread"] = None
        _STATE["port"] = None
        _STATE["discovery"] = None
    global _ENABLED
    _ENABLED = False


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_disarm_in_child)


# ---------------------------------------------------------------------------
# Live cluster aggregation (the launch.py sidecar)
# ---------------------------------------------------------------------------

def _scrape(port_no: int, path: str, timeout: float = 2.0) -> Any:
    import urllib.request

    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port_no, path),
            timeout=timeout) as r:
        return json.loads(r.read())


def aggregate_once(directory: str,
                   state: Optional[Dict[str, Any]] = None,
                   out_name: str = "cluster_live.json"
                   ) -> Dict[str, Any]:
    """One live-aggregation pass: discover role endpoints via the
    ``obs_pid*.json`` files in ``directory``, scrape each
    ``/snapshot.json``, and atomically rewrite
    ``directory/cluster_live.json`` with the merged cluster view —
    per-rank step time / MFU / dominant phase, queue depths, anomaly +
    retry rollups, recent sample tails for sparklines, and a ``dead``
    list naming every role whose endpoint was seen alive earlier in
    THIS aggregation session but no longer answers (the SIGKILLed
    rank).  ``state`` carries the session memory between passes."""
    from . import telemetry as _tel

    state = state if state is not None else {}
    seen: Dict[str, Dict[str, Any]] = state.setdefault("seen", {})
    refreshes = state.get("refreshes", 0) + 1
    state["refreshes"] = refreshes

    discovered: Dict[str, Dict[str, Any]] = {}
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        names = []
    for name in names:
        if not (name.startswith("obs_pid") and name.endswith(".json")):
            continue
        try:
            with open(os.path.join(directory, name)) as f:
                d = json.load(f)
            key = "%s%d" % (d["role"], int(d["rank"]))
            discovered[key] = d
        except (OSError, ValueError, KeyError, TypeError):
            continue

    snaps: Dict[str, Dict[str, Any]] = {}
    tails: Dict[str, List[Dict[str, Any]]] = {}
    dead: List[str] = []
    for key, d in sorted(discovered.items()):
        try:
            snap = _scrape(int(d["port"]), "/snapshot.json")
            if not isinstance(snap, dict):
                raise ValueError("non-dict snapshot")
            snaps[key] = snap
            tails[key] = snap.get("obs_samples") or []
            seen[key] = {"snap": snap, "tail": tails[key],
                         "last_ok": time.time()}
        except Exception:
            if key in seen:
                # answered earlier this session, silent now: dead
                dead.append(key)
                snaps[key] = seen[key]["snap"]
                tails[key] = seen[key]["tail"]
            # never seen alive: not started yet — skip silently
    per_rank_step = {}
    per_rank_steps = {}
    roles: Dict[str, Dict[str, Any]] = {}
    for key, snap in snaps.items():
        m = snap.get("metrics") or {}
        m = m if isinstance(m, dict) else {}
        stats = snap.get("stats")
        stats = stats if isinstance(stats, dict) else {}
        perf = m.get("perf") or {}
        serve = m.get("serve") or {}
        if m.get("steps"):
            per_rank_step[key] = m.get("step_time_avg_s", 0.0)
            per_rank_steps[key] = m.get("steps", 0)
        # one compact derived row per role: everything tools/dash.py
        # renders without re-deriving from raw stats (tickers via the
        # ONE shared telemetry.stat_rollup definition)
        roles[key] = {
            "pid": snap.get("pid"),
            "steps": m.get("steps", 0),
            "step_time_ms": round(
                m.get("step_time_last_s", 0.0) * 1e3, 3),
            "step_time_avg_ms": round(
                m.get("step_time_avg_s", 0.0) * 1e3, 3),
            "examples_per_sec": round(
                m.get("examples_per_sec", 0.0), 1),
            "mfu": perf.get("mfu"),
            "dominant_phase": perf.get("dominant_phase"),
            # the role's dominant critical-path segment from its
            # mx.tracing sampled-span summary (the dash crit-path
            # column)
            "critical_path": (m.get("tracing") or {}).get(
                "dominant_segment"),
            # the rank's top device-time sink (mx.xprof op profile),
            # carried by the newest sample row that has one
            "top_sink": next(
                (s.get("top_sink")
                 for s in reversed(tails.get(key) or [])
                 if isinstance(s, dict) and s.get("top_sink")), None),
            "queue_depth": serve.get("queue_depth", 0)
            if isinstance(serve, dict) else 0,
        }
        # the rank's device-memory census (mx.hbm): used/peak/headroom
        # + leak flag, the dash HBM column — straight off the role's
        # metrics provider block, zero new wiring
        h = m.get("hbm")
        if isinstance(h, dict) and h.get("enabled"):
            roles[key]["hbm"] = {
                "used_bytes": h.get("used_bytes", 0),
                "peak_used_bytes": h.get("peak_used_bytes", 0),
                "headroom_bytes": h.get("headroom_bytes", 0),
                "leak": bool(h.get("leak")),
            }
        roles[key].update(_tel.stat_rollup(stats))
    aggregate = _tel.aggregate_stats(
        s.get("stats") for s in snaps.values()
        if isinstance(s.get("stats"), dict))
    cluster = {
        "ts": time.time(),
        "refreshes": refreshes,
        "run_id": next((s.get("run_id") for s in snaps.values()
                        if s.get("run_id")), None),
        "live": sorted(k for k in snaps if k not in dead),
        "dead": sorted(dead),
        "per_rank_step_time_s": per_rank_step,
        "per_rank_steps": per_rank_steps,
        "aggregate": aggregate,
        "perf": _tel.perf_rollup(snaps),
        "health": _tel.health_rollup(snaps),
        "hbm": _tel.hbm_rollup(snaps),
        "retry_total": sum(v for k, v in aggregate.items()
                           if k.startswith("retry_attempts::")),
        "failover_total": aggregate.get("elastic_failover", 0),
        "serve_queue_depth": aggregate.get("serve_queue_depth", 0),
        "samples": tails,
        "roles": roles,
    }
    _tel._write_json(os.path.join(directory, out_name), cluster)
    return cluster


def aggregator_main(directory: str,
                    interval: Optional[float] = None) -> int:
    """The ``tools/launch.py`` sidecar body: loop
    :func:`aggregate_once` over ``directory`` every ``interval``
    (default: min(2s, sample interval)) until SIGTERM/SIGINT.  Run
    with ``MXTPU_OBS=0`` + ``MXTPU_TELEMETRY=0`` so the aggregator is
    never a producer in the directory it aggregates."""
    import signal

    if interval is None:
        interval = min(2.0, sample_interval())
    stop_ev = threading.Event()

    def _stop(signum, frame):
        stop_ev.set()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    state: Dict[str, Any] = {}
    while not stop_ev.is_set():
        try:
            aggregate_once(directory, state)
        except Exception:
            pass  # diagnostics must never kill the sidecar
        stop_ev.wait(interval)
    # one final pass so the file reflects the end state
    try:
        aggregate_once(directory, state)
    except Exception:
        pass
    return 0


if armed():
    # a launched role (telemetry dir / obs port / run dir configured):
    # bring the plane up at import, like telemetry's flight recorder
    ensure_started()
