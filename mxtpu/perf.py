"""Performance observatory: step-phase attribution, live MFU, roofline.

`mxtpu/telemetry.py` answers "how fast is each rank stepping",
`mxtpu/inspect.py` answers "what did XLA build" — this module joins
the two so "img/s went down" becomes "which PHASE of which PROGRAM on
which rank ate the time".  Two pieces:

  * **Step-phase decomposition** — every dispatch path (Executor
    ``_jit_*``, CachedOp, FusedTrainLoop, the `mx.serve` batcher)
    records an always-on per-step phase breakdown:

      ===============  =====================================================
      ``input_wait``   host blocked waiting for the data pipeline (the
                       PR 6 gauge, folded into this schema — nested
                       loader/iter stacks record once, outermost wins)
      ``host_dispatch``  jit call → return (python arg staging + XLA
                       launch; on an async backend this EXCLUDES device
                       execution — a large value is dispatch overhead)
      ``device_compute`` jit return → ``jax.block_until_ready``,
                       SAMPLED every ``MXTPU_PERF_SYNC_EVERY`` (32)
                       calls per program so the async pipeline is
                       never serialized per step
      ``optimizer``    host-side parameter update (gluon Trainer /
                       Module.update; inside ``device_compute`` for
                       the fused K-step program)
      ``collective``   gradient allreduce (kvstore push/pull)
      ===============  =====================================================

    surfaced as ``perf_*_us_last`` gauges + ``perf_phase_us::*``
    :class:`telemetry.Histogram` s, with :func:`report` naming the
    dominant phase per program.

  * **Live MFU + roofline** — measured per-call wall (the sampled
    call→ready span) joined against the `mx.inspect` registry's
    ``cost_analysis`` FLOPs/bytes and the peak table keyed by
    ``device_kind`` (:data:`DEVICE_PEAKS`; ``MXTPU_PEAK_FLOPS`` /
    ``MXTPU_PEAK_BYTES`` override) gives per-program MFU and a compute- vs
    memory-bound roofline classification: operational intensity
    (flops/byte) above the machine's ridge point (peak_flops /
    peak_bytes) means the program is compute-bound — more FLOPs/s
    only come from a faster kernel; below it the program is
    memory-bound — layout/fusion (fewer bytes moved) is the lever.
    Exported in ``telemetry.metrics()["perf"]``, as chrome-trace
    counter tracks by ``telemetry.merge_dir``, as Speedometer columns,
    and rolled up per rank in ``launch.py --telemetry-dir``'s
    cluster.json (per-rank MFU spread = straggler signal).

Cost discipline: the unsampled per-call path is two
``time.perf_counter`` reads, one small locked dict update, one gauge
store and one histogram bump; what the hooks cost the fused loop is
read on the chip (``observer_ms_per_step.fused``, PERF.md §5).
``MXTPU_PERF=0`` turns every hook into one bool check.  MFU figures in :func:`metrics_block` use only analysis
the inspect registry has ALREADY cached (a heartbeat must never
trigger an XLA compile); :func:`report` forces the analysis.

See `docs/observability.md` §Performance.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from typing import Any, Dict, List, Optional

from .base import MXNetError, getenv, getenv_bool, getenv_int

__all__ = [
    "PHASES",
    "enabled",
    "enable",
    "sync_every",
    "begin",
    "end",
    "note_phase",
    "DEVICE_PEAKS",
    "peaks_for",
    "device_peaks",
    "peak_flops",
    "peak_bytes",
    "roofline",
    "mfu",
    "programs",
    "phases",
    "metrics_block",
    "report",
    "dominant_phase",
    "reset",
]

#: the phase vocabulary, in pipeline order
PHASES = ("input_wait", "host_dispatch", "device_compute", "optimizer",
          "collective")

_ENABLED = getenv_bool("MXTPU_PERF", True)

#: THE peak table: published per-chip peaks keyed by the
#: ``device_kind`` JAX reports.  Every MFU / roofline figure in the
#: tree (`mx.perf`, `mx.xprof`, `tools/hlo_report.py`)
#: reads this one table; an accelerator that is not in it is an error
#: (:func:`device_peaks`), never a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12,
                    "bytes_per_s": 819e9, "hbm_bytes": 16e9},
}
# a CPU has no published figure to key on: an ESTIMATE from the core
# count (~2.5 GHz x 8 f32 lanes x 2 (FMA) = 40 GFLOP/s per core, ~40
# GB/s for the socket), good for a relative signal in CPU tests only
_CPU_FLOPS_PER_CORE = 4e10
_CPU_BYTES = 4e10

_lock = threading.RLock()


def enabled() -> bool:
    """Observatory on?  ``MXTPU_PERF=0`` opts out at import."""
    return _ENABLED


def enable(on: bool = True) -> None:
    """Flip the observatory at runtime (tests / embedding)."""
    global _ENABLED
    _ENABLED = bool(on)


def sync_every() -> int:
    """Device-sync sampling cadence (``MXTPU_PERF_SYNC_EVERY``,
    default 32): every Nth call per program additionally blocks on the
    program's outputs to measure the true call→ready wall (the MFU
    denominator).  ``0`` never syncs — phases then carry only the
    host-side view.  Read from the environment per call (sub-us) so
    tests and embedders can retune a live process."""
    return max(0, getenv_int("MXTPU_PERF_SYNC_EVERY", 32))


# ---------------------------------------------------------------------------
# Per-program phase records
# ---------------------------------------------------------------------------

class _ProgPerf(object):
    """Always-on per-program accumulators.  Keyed by the program's
    `mx.inspect` registry name, so the MFU join (measured wall x
    registered cost analysis) is a dict lookup."""

    __slots__ = ("name", "site", "calls", "steps", "host_sum_us",
                 "host_last_us", "host_first_us", "n_first",
                 "sync_samples", "dev_span_sum_us", "dev_span_last_us",
                 "wall_sum_us", "wall_last_us", "since_sync", "n_last")

    def __init__(self, name: str, site: str):
        self.name = name
        self.site = site
        self.calls = 0
        self.steps = 0          # calls x steps-per-call (fused loop: K)
        self.host_sum_us = 0.0  # steady state: excludes the first call
        self.host_last_us = 0.0
        self.host_first_us = 0.0  # call 1 pays trace+compile — kept
        self.n_first = 0          # apart so averages stay steady-state
        self.sync_samples = 0
        self.dev_span_sum_us = 0.0   # jit return -> block_until_ready
        self.dev_span_last_us = 0.0
        self.wall_sum_us = 0.0       # call -> ready (sampled calls only)
        self.wall_last_us = 0.0
        self.since_sync = 0
        self.n_last = 1


_PROGS: "Dict[str, _ProgPerf]" = {}

# global per-step phase accumulators: [count, sum_us, last_us]
_PHASE_ACC: Dict[str, List[float]] = {
    p: [0, 0.0, 0.0] for p in ("input_wait", "optimizer", "collective")}


def _hist(name: str):
    from . import telemetry as _tel

    # us-valued: 0.1us .. 100s span, 8 bins/decade keeps it small
    return _tel.histogram(name, low=1e-1, high=1e8, bins_per_decade=8)


def begin() -> Optional[float]:
    """Stamp the start of a dispatch (or phase).  Returns an opaque
    token for :func:`end` / :func:`note_phase`, or None when the
    observatory is off (both then no-op)."""
    if not _ENABLED:
        return None
    return time.perf_counter()


def end(name: str, site: str, t0: Optional[float], outputs: Any = None,
        n: int = 1) -> None:
    """Account one program dispatch that STARTED at ``t0``
    (:func:`begin`).  Records ``host_dispatch`` (call→return, i.e.
    now - t0) always; every ``sync_every()``-th call per program —
    never the first, which pays the compile — additionally blocks on
    ``outputs`` (any jax pytree) and records ``device_compute``
    (return→ready) plus the full call→ready wall the MFU uses.  ``n``
    is the number of wall steps this one dispatch advanced (the fused
    loop's K)."""
    if t0 is None or not _ENABLED:
        return
    t1 = time.perf_counter()
    host_us = (t1 - t0) * 1e6
    se = sync_every()
    with _lock:
        rec = _PROGS.get(name)
        if rec is None:
            rec = _PROGS[name] = _ProgPerf(name, site)
        rec.calls += 1
        rec.steps += n
        rec.n_last = n
        first = rec.calls == 1
        if first:
            rec.host_first_us = host_us
            rec.n_first = n
        else:
            rec.host_sum_us += host_us
        rec.host_last_us = host_us
        rec.since_sync += 1
        sample = (outputs is not None and se > 0 and not first
                  and rec.since_sync >= se)
        if sample:
            rec.since_sync = 0
    from . import profiler as _prof

    if not first:
        # the first call pays trace + XLA compile: it lives in
        # first_call_us only — never in the steady-state gauge or
        # histogram, where a 1s compile would own vmax/p99 forever
        _prof.set_stat("perf_host_dispatch_us_last", int(host_us))
        _hist("perf_phase_us::host_dispatch").record(host_us / max(1, n))
    if not sample:
        return
    # sampled sync: the one deliberate serialization point — at most
    # once per sync_every() calls, so the async pipeline depth is
    # preserved between samples
    try:
        import jax

        # bracketed as the loop's device wait (mx.profiler, while armed)
        with _prof.span("mx:device_wait", "loop", why="perf_sync"):
            jax.block_until_ready(outputs)
    except Exception:
        return
    t2 = time.perf_counter()
    dev_us = (t2 - t1) * 1e6
    wall_us = (t2 - t0) * 1e6
    with _lock:
        rec.sync_samples += 1
        rec.dev_span_sum_us += dev_us
        rec.dev_span_last_us = dev_us
        rec.wall_sum_us += wall_us
        rec.wall_last_us = wall_us
    _prof.inc_stat("perf_sync_samples")
    _prof.set_stat("perf_device_compute_us_last", int(dev_us))
    _hist("perf_phase_us::device_compute").record(dev_us / max(1, n))
    from . import telemetry as _tel

    m = _cached_mfu(rec)
    _tel.record("perf", program=name, site=site, n=n,
                step=_tel.current_step(),
                host_us=round(host_us, 1), device_us=round(dev_us, 1),
                wall_us=round(wall_us, 1),
                mfu=_sig3(m) if m is not None else None)


def note_phase(phase: str, dur_s: float) -> None:
    """Account one host-side phase segment (``input_wait`` /
    ``optimizer`` / ``collective``) of ``dur_s`` seconds.  The gluon
    Trainer stamps its allreduce and update segments here; the
    telemetry input-wait gauge forwards here so the PR 6 signal lives
    in this schema as ``input_wait``."""
    if not _ENABLED:
        return
    us = dur_s * 1e6
    acc = _PHASE_ACC.get(phase)
    if acc is None:
        return
    with _lock:
        acc[0] += 1
        acc[1] += us
        acc[2] = us
    from . import profiler as _prof

    _prof.set_stat("perf_%s_us_last" % phase, int(us))
    _hist("perf_phase_us::%s" % phase).record(us)
    # when an mx.tracing context is ambient (a sampled trainer step),
    # the phase doubles as a causal span — phase names ARE the span
    # vocabulary, so spans and phase gauges reconcile by construction
    from . import tracing as _tracing

    trc = _tracing.current()
    if trc is not None:
        _tracing.record_span(trc, phase, dur_s)


def note_phase_since(phase: str, t0: Optional[float]) -> None:
    """:func:`note_phase` for a segment started with :func:`begin`."""
    if t0 is None or not _ENABLED:
        return
    note_phase(phase, time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Peak table + roofline
# ---------------------------------------------------------------------------

def peaks_for(platform: str, device_kind: str) -> Dict[str, Any]:
    """The :data:`DEVICE_PEAKS` row for one device, plus its
    ``device_kind`` and an ``estimate`` flag (true only for the CPU's
    core-count estimate).  An accelerator kind that is not in the table
    raises: a utilization against a guessed peak is worse than none."""
    if platform == "cpu":
        cores = os.cpu_count() or 1
        return {"device_kind": device_kind, "estimate": True,
                "flops": _CPU_FLOPS_PER_CORE * cores,
                "bytes_per_s": _CPU_BYTES}
    row = DEVICE_PEAKS.get(device_kind)
    if row is None:
        raise MXNetError(
            "no peak figures for %s device_kind %r: add its published "
            "peaks, with their source, to mxtpu.perf.DEVICE_PEAKS "
            "(known: %s)" % (platform, device_kind,
                             ", ".join(sorted(DEVICE_PEAKS))))
    return dict(row, device_kind=device_kind, estimate=False)


@functools.lru_cache(maxsize=1)
def device_peaks() -> Dict[str, Any]:
    """:func:`peaks_for` the process's first JAX device."""
    import jax

    d = jax.devices()[0]
    return peaks_for(d.platform, d.device_kind)


def peak_flops() -> float:
    """Peak device flops/s (bf16 on a TPU): ``MXTPU_PEAK_FLOPS``
    override, else the :data:`DEVICE_PEAKS` table."""
    env = getenv("MXTPU_PEAK_FLOPS")
    if env:
        return float(env)
    return device_peaks()["flops"]


def peak_bytes() -> float:
    """Peak memory bandwidth bytes/s: ``MXTPU_PEAK_BYTES`` override,
    else the :data:`DEVICE_PEAKS` table."""
    env = getenv("MXTPU_PEAK_BYTES")
    if env:
        return float(env)
    return device_peaks()["bytes_per_s"]


def mfu(flops: float, wall_s: float) -> Optional[float]:
    """Model-flops utilization of one program call: achieved flops/s
    over :func:`peak_flops`, clamped into (0, 1] (the CPU's estimated
    peak must not report a nonsense >1)."""
    if not flops or not wall_s or wall_s <= 0:
        return None
    return min(1.0, flops / (wall_s * peak_flops()))


def roofline(flops: float, bytes_accessed: float) -> Optional[Dict[str, Any]]:
    """Roofline classification of one program from its XLA cost
    analysis: operational intensity (flops/byte) vs the machine's
    ridge point (peak_flops / peak_bytes).  ``bound`` is ``compute``
    at or above the ridge (a faster kernel is the only lever) and
    ``memory`` below it (move fewer bytes: layout, fusion, dtype)."""
    if not flops or not bytes_accessed:
        return None
    intensity = flops / bytes_accessed
    ridge = peak_flops() / max(1.0, peak_bytes())
    return {"intensity_flops_per_byte": round(intensity, 3),
            "ridge_flops_per_byte": round(ridge, 3),
            "bound": "compute" if intensity >= ridge else "memory"}


# ---------------------------------------------------------------------------
# Joining against the inspect registry
# ---------------------------------------------------------------------------

def _analysis_for(name: str, force: bool = False) -> Optional[Dict[str, Any]]:
    """The inspect registry's cost/memory analysis for program
    ``name``.  ``force=False`` returns only what is ALREADY cached
    (never compiles — safe from metrics()/heartbeats); ``force=True``
    runs the lazy analysis (report()/tools only)."""
    try:
        from . import inspect as _insp

        rec = _insp.find(name)
        if rec is None:
            return None
        si = rec.latest_sig()
        if si is None:
            return None
        if si._analysis is None and not force:
            return None
        an = si.analyze()
        return an if "error" not in an else None
    except Exception:
        return None


def _cached_mfu(rec: _ProgPerf) -> Optional[float]:
    """MFU from already-cached analysis only (hot-path safe)."""
    if not rec.sync_samples:
        return None
    an = _analysis_for(rec.name, force=False)
    if an is None:
        return None
    wall_s = rec.wall_sum_us / rec.sync_samples / 1e6
    return mfu(an.get("flops", 0.0), wall_s)


def _sig3(x: float) -> float:
    """3 significant digits: a 1e-8 MFU on a toy model must survive
    serialization as nonzero (fixed-decimal rounding would zero it)."""
    return float("%.3g" % x)


def _program_row(rec: _ProgPerf, force: bool = False) -> Dict[str, Any]:
    # steady-state average: the first call (trace + XLA compile) is
    # reported ONLY as first_call_us — with a single call so far there
    # is no steady state yet, and folding the compile wall into the
    # average would misattribute it as dispatch overhead
    steady = max(1, rec.steps - rec.n_first)
    host_avg = (rec.host_sum_us / steady) if rec.calls > 1 else None
    row: Dict[str, Any] = {
        "site": rec.site,
        "calls": rec.calls,
        "steps": rec.steps,
        "host_dispatch_us_last": round(rec.host_last_us, 2),
        "first_call_us": round(rec.host_first_us, 1),
        "sync_samples": rec.sync_samples,
    }
    if host_avg is not None:
        row["host_dispatch_us_avg"] = round(host_avg, 2)
    dev_step_us = None
    if rec.sync_samples:
        per_call_n = max(1, rec.n_last)
        dev_step_us = rec.dev_span_sum_us / rec.sync_samples / per_call_n
        row["device_compute_us_avg"] = round(dev_step_us, 2)
        row["wall_us_avg"] = round(
            rec.wall_sum_us / rec.sync_samples / per_call_n, 2)
    an = _analysis_for(rec.name, force=force)
    if an is not None:
        row["flops"] = an.get("flops", 0.0)
        row["bytes_accessed"] = an.get("bytes_accessed", 0.0)
        rf = roofline(an.get("flops", 0.0), an.get("bytes_accessed", 0.0))
        if rf is not None:
            row["roofline"] = rf
        if rec.sync_samples:
            wall_s = rec.wall_sum_us / rec.sync_samples / 1e6
            m = mfu(an.get("flops", 0.0), wall_s)
            if m is not None:
                row["mfu"] = _sig3(m)
    # dominant phase of a step through THIS program: the program's own
    # host/device split plus the process-global per-step host phases
    cand = dict(_phase_avgs())
    if host_avg is not None:
        cand["host_dispatch"] = host_avg
    if dev_step_us is not None:
        cand["device_compute"] = dev_step_us
    if any(v > 0 for v in cand.values()):
        row["dominant_phase"] = max(cand, key=lambda k: cand[k])
    # all-zero (single call, nothing measured yet): no dominant phase
    # is named — a fabricated max() over zeros would send the reader
    # chasing a phase with no data behind it
    return row


def _phase_avgs() -> Dict[str, float]:
    """Process-global per-step host-phase averages (us): phase sums
    over the telemetry step count (phases are at most one segment per
    training step).  In a process that never trains (serve / pure
    inference: record_step never runs, current_step() stays 0) the
    denominator falls back to the phase's own event count, so the
    figure degrades to a bounded per-event average instead of an
    ever-growing cumulative sum."""
    from . import telemetry as _tel

    steps = _tel.current_step()
    with _lock:
        return {p: acc[1] / max(1, steps, acc[0])
                for p, acc in _PHASE_ACC.items()}


def programs(force: bool = False) -> Dict[str, Dict[str, Any]]:
    """Per-program phase/MFU rows, keyed by inspect registry name."""
    with _lock:
        recs = list(_PROGS.values())
    return {r.name: _program_row(r, force=force) for r in recs}


def phases() -> Dict[str, Dict[str, float]]:
    """The raw global phase accumulators (count/sum_us/last_us)."""
    with _lock:
        return {p: {"n": acc[0], "sum_us": round(acc[1], 1),
                    "last_us": round(acc[2], 1)}
                for p, acc in _PHASE_ACC.items()}


def dominant_phase(progs: Optional[Dict[str, Dict]] = None) -> Optional[str]:
    """The process-wide dominant phase: per-step averages of the host
    phases plus the busiest program's host/device split."""
    progs = programs(force=False) if progs is None else progs
    cand = dict(_phase_avgs())
    busiest = None
    for row in progs.values():
        if busiest is None or row["steps"] > busiest["steps"]:
            busiest = row
    if busiest is not None:
        if "host_dispatch_us_avg" in busiest:
            cand["host_dispatch"] = busiest["host_dispatch_us_avg"]
        if "device_compute_us_avg" in busiest:
            cand["device_compute"] = busiest["device_compute_us_avg"]
    if not cand or all(v == 0 for v in cand.values()):
        return None
    return max(cand, key=lambda k: cand[k])


def metrics_block(force: bool = False) -> Dict[str, Any]:
    """The ``telemetry.metrics()["perf"]`` block.  With
    ``force=False`` (the registered provider) MFU/roofline appear only
    for programs whose inspect analysis is already cached — a
    heartbeat or /metrics scrape must never trigger a compile; run
    :func:`report` (or ``MXTPU_INSPECT_EAGER=1``) to populate them."""
    if not _ENABLED:
        return {"enabled": False}
    progs = programs(force=force)
    out: Dict[str, Any] = {
        "enabled": True,
        "sync_every": sync_every(),
        "phases_us_per_step": {k: round(v, 2)
                               for k, v in _phase_avgs().items()},
        "programs": progs,
    }
    if progs:
        out["peak_flops"] = peak_flops()
        out["peak_bytes"] = peak_bytes()
        mfus = [r["mfu"] for r in progs.values() if "mfu" in r]
        if mfus:
            out["mfu"] = max(mfus)
        dp = dominant_phase(progs)
        if dp is not None:
            out["dominant_phase"] = dp
    return out


def report(force: bool = True) -> Dict[str, Any]:
    """Full observatory report: forces the inspect cost analysis (may
    compile — tool/notebook use, never a hot path) so every program
    row carries MFU + roofline, and names the dominant phase per
    program and process-wide.

    ::

        >>> mx.perf.report()["dominant_phase"]
        'device_compute'
    """
    return metrics_block(force=force)


def summary() -> str:
    """Printable one-line-per-program table (forces analysis)."""
    blk = report()
    lines = ["dominant phase: %s   phases us/step: %s"
             % (blk.get("dominant_phase"),
                blk.get("phases_us_per_step"))]
    lines.append("%-44s %6s %6s %10s %10s %7s %7s %s"
                 % ("program", "calls", "steps", "host(us)", "dev(us)",
                    "MFU", "bound", "dominant"))
    for name, r in blk.get("programs", {}).items():
        lines.append("%-44s %6d %6d %10s %10s %7s %7s %s" % (
            name[:44], r["calls"], r["steps"],
            "%.1f" % r["host_dispatch_us_avg"]
            if "host_dispatch_us_avg" in r else "-",
            "%.1f" % r["device_compute_us_avg"]
            if "device_compute_us_avg" in r else "-",
            "%.3f" % r["mfu"] if "mfu" in r else "-",
            (r.get("roofline") or {}).get("bound", "-"),
            r["dominant_phase"]))
    return "\n".join(lines)


def reset() -> None:
    """Drop all observatory state (tests)."""
    with _lock:
        _PROGS.clear()
        for acc in _PHASE_ACC.values():
            acc[0] = 0
            acc[1] = 0.0
            acc[2] = 0.0


# the "perf" block in telemetry.metrics(): registered at import so any
# consumer (Speedometer, heartbeats, /metrics, merge_dir rollups) sees
# it without this module being imported explicitly
from . import telemetry as _tel  # noqa: E402  (safe: telemetry has no
# top-level import back into perf; its producers import perf lazily)

_tel.register_metrics_provider("perf", metrics_block)
