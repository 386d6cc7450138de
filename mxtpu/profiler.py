"""Profiler — chrome://tracing dump + aggregate stats.

Reference: `src/profiler/profiler.h:256-304` (mode bitmask
kSymbolic|kImperative|kAPI|kMemory, DumpProfile), python surface
`python/mxnet/profiler.py:33-151` (set_config/set_state/pause/resume/
dump/dumps), aggregate tables `src/profiler/aggregate_stats.cc`, and the
engine's per-opr `ProfileOperator` wrap (`threaded_engine.h:336-347`).

TPU notes: host-side spans measure dispatch + (for jitted whole-graph
executors) device execution because the executor blocks on results it
returns lazily; set MXTPU_PROFILER_SYNC=1 to block after every op for
accurate per-op device times (the analog of the reference profiling
`NaiveEngine` mode).  The flag is read PER SPAN, so it can be flipped
mid-run; a span whose producer attached the op's results (``span.result``)
blocks on exactly those via ``jax.block_until_ready`` instead of the
global ``jax.effects_barrier``.

One span primitive: `span()` records while `armed()`, i.e. while
``set_state('run')`` is on OR a JAX profiler session is live
(``jax.profiler.start_trace``).  An armed span is one row of a bounded
in-memory buffer (`spans()`: raw ``time.perf_counter()`` seconds, the
thread, the enclosing span, the step) and, inside a JAX session, a
``TraceAnnotation`` / ``StepTraceAnnotation`` of the same name in the
session's ``.xplane.pb``, on the clock of the device's events.  The
loop-level sites (the ``mx:`` vocabulary, `docs/observability.md`) are
gated by `armed()` alone; the per-op sites keep `is_recording`.

Trace identity: every event is stamped with the REAL pid, `dump()`
emits chrome ``process_name``/``thread_name`` metadata rows (role+rank
from `mxtpu.telemetry`) and an ``otherData.epoch_origin_s`` wall-clock
origin, so per-role dumps from a distributed run merge into one
timeline via ``telemetry.merge_traces`` with clocks aligned.

Autostart: MXTPU_PROFILER_AUTOSTART=1 (reference
MXNET_PROFILER_AUTOSTART, `docs/faq/env_var.md:156`).
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .base import MXNetError, getpid_cached

__all__ = ["set_config", "set_state", "state", "pause", "resume", "dump",
           "dumps", "Domain", "Task", "Frame", "Counter", "Marker",
           "armed", "span", "spans",
           "inc_stat", "get_stat", "set_stat", "max_stat", "stats",
           "reset_stats"]

# RLock: the telemetry flight recorder's signal handler reads stats()
# on whatever thread the signal lands on — possibly one already inside
# inc_stat's critical section (re-entry only reads; see telemetry.py)
_lock = threading.RLock()
_RUNNING = False
_PAUSED = False
_CONFIG = {
    "filename": "profile.json",
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": True,
    "aggregate_stats": False,
    "continuous_dump": False,
}
_EVENTS: List[Dict[str, Any]] = []     # counter and marker events
_AGG: Dict[str, List[float]] = {}
# THE span store: one row per finished span, oldest dropped when full
# (drops counted in the `profiler_span_drops` stat); `dump()` writes its
# chrome-trace "X" events from these rows
_MAX_SPANS = 1 << 16
_SPANS: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=_MAX_SPANS)
_tls = threading.local()               # .stack: the thread's open spans
# the two origins are captured back-to-back: _START_TS anchors the
# relative event timestamps, _START_EPOCH records what wall-clock
# instant that zero corresponds to (the mergeable-trace contract)
_START_TS = time.perf_counter()
_START_EPOCH = time.time()


def _now_us() -> float:
    return (time.perf_counter() - _START_TS) * 1e6


def _sync_enabled() -> bool:
    """MXTPU_PROFILER_SYNC, read per-span (NOT latched at import) so a
    run can flip into accurate-device-timing mode on the fly."""
    return os.environ.get("MXTPU_PROFILER_SYNC", "0") == "1"


def set_config(**kwargs):
    """Configure (reference `profiler.py:33` set_config; accepts the
    reference's kwargs incl. profile_all)."""
    global _CONFIG
    if kwargs.pop("profile_all", False):
        for k in ("profile_symbolic", "profile_imperative",
                  "profile_memory", "profile_api"):
            _CONFIG[k] = True
    for k, v in kwargs.items():
        if k in _CONFIG:
            _CONFIG[k] = v
        elif k in ("profile_process", "aggregate_stats_filename"):
            pass
        else:
            raise MXNetError("unknown profiler config %r" % k)


def set_state(state_name: str = "stop"):
    global _RUNNING, _PAUSED
    if state_name not in ("run", "stop"):
        raise MXNetError("state must be 'run' or 'stop'")
    was = _RUNNING
    _RUNNING = state_name == "run"
    _PAUSED = False
    if was and not _RUNNING and _CONFIG["continuous_dump"]:
        dump()


def state() -> str:
    return "run" if _RUNNING else "stop"


def pause():
    global _PAUSED
    _PAUSED = True


def resume():
    global _PAUSED
    _PAUSED = False


def is_recording(kind: str = "imperative") -> bool:
    return _RUNNING and not _PAUSED and \
        _CONFIG.get("profile_" + kind, True)


def armed() -> bool:
    """True while a span would be kept: ``set_state('run')`` is on (and
    not paused) or a JAX profiler session is live.  The one check an
    un-armed loop-level site pays."""
    return (_RUNNING and not _PAUSED) or TraceAnnotation.is_enabled()


def record_span(name: str, cat: str, t0: float, t1: float,
                parent: Optional[str] = None, step: Optional[int] = None,
                args: Optional[Dict] = None):
    """Append one finished span (``t0`` / ``t1`` raw perf_counter
    seconds) to the span store."""
    row = {"name": name, "cat": cat, "t0": t0, "t1": t1,
           "tid": threading.get_ident(), "parent": parent, "step": step}
    if args:
        row["args"] = args
    with _lock:
        dropped = len(_SPANS) == _SPANS.maxlen
        _SPANS.append(row)
        _AGG.setdefault(name, []).append((t1 - t0) * 1e6)
    if dropped:
        inc_stat("profiler_span_drops")


def spans(reset: bool = False) -> List[Dict[str, Any]]:
    """The recorded span rows, oldest first: ``name``, ``cat``, ``t0``
    and ``t1`` (raw ``time.perf_counter()`` seconds), ``tid``,
    ``parent`` (the name of the span open on that thread when this one
    started, or None), ``step`` (its own or the enclosing span's) and
    ``args`` where it was given attributes.  ``reset`` empties the
    store."""
    with _lock:
        rows = list(_SPANS)
        if reset:
            _SPANS.clear()
    return rows


# -- always-on stats -------------------------------------------------------
# Counters (a dict bump, not gated on set_state) so hot-path
# regressions are observable without turning the event profiler on.
# The full counter-namespace catalog (compile-lifecycle *_trace/*_hit,
# resilience retry_*/fault_injected::<site>, elastic_*, telemetry_*)
# lives in `docs/observability.md`.

_STATS: Dict[str, int] = {}


def inc_stat(name: str, delta: int = 1) -> int:
    with _lock:
        val = _STATS.get(name, 0) + delta
        _STATS[name] = val
    if _RUNNING and delta:
        record_counter("stat::" + name, float(val))
    return val


def get_stat(name: str) -> int:
    return _STATS.get(name, 0)


def set_stat(name: str, value: int) -> None:
    """Set an absolute gauge value (e.g. ``step_time_us_last``) —
    counters use :func:`inc_stat`, gauges this."""
    with _lock:
        _STATS[name] = int(value)


def max_stat(name: str, value: int) -> None:
    """Raise a watermark gauge (e.g. ``device_mem_watermark_bytes``)
    to ``value`` if it is higher."""
    with _lock:
        if int(value) > _STATS.get(name, 0):
            _STATS[name] = int(value)


def stats() -> Dict[str, int]:
    """Snapshot of the compile-lifecycle counters."""
    with _lock:
        return dict(_STATS)


def reset_stats() -> None:
    with _lock:
        _STATS.clear()


def record_counter(name: str, value: float, ts_us: Optional[float] = None):
    if not _RUNNING or _PAUSED:
        return
    with _lock:
        _EVENTS.append({"name": name, "ph": "C",
                        "ts": ts_us if ts_us is not None else _now_us(),
                        "pid": getpid_cached(), "args": {name: value}})


class _Span(object):
    """Context manager measuring one span (engine ProfileOperator
    analog).  A producer may attach the span's device results via
    ``span.result = <jax arrays>``; under MXTPU_PROFILER_SYNC the exit
    then blocks on exactly those (``jax.block_until_ready``) for a
    true synchronous device timing, falling back to the global
    ``jax.effects_barrier`` when nothing was attached.  Loop-level
    spans (``cat='loop'``) never add a block of their own: their
    ``mx:device_wait`` children say where the host waits."""

    __slots__ = ("name", "cat", "step", "args", "t0", "result", "_parent",
                 "_ann", "_own_step")

    def __init__(self, name: str, cat: str, step: Optional[int],
                 args: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.step = step
        self._own_step = step is not None
        self.args = args
        self.result = None

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        if stack:
            self._parent = stack[-1].name
            if self.step is None:
                self.step = stack[-1].step
        else:
            self._parent = None
        stack.append(self)
        self._ann = None
        if TraceAnnotation.is_enabled():
            # the same span in the JAX session's .xplane.pb, on the
            # clock of the device's events
            if self._own_step:
                self._ann = StepTraceAnnotation(
                    self.name, step_num=self.step, **self.args)
            else:
                self._ann = TraceAnnotation(self.name, **self.args)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.cat != "loop" and _sync_enabled():
            try:
                import jax

                if self.result is not None:
                    jax.block_until_ready(self.result)
                else:
                    jax.effects_barrier()
            except Exception:
                pass
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _tls.stack.pop()
        record_span(self.name, self.cat, self.t0, t1, self._parent,
                    self.step, self.args)
        if _CONFIG["profile_memory"]:
            _sample_memory()
        return False


class _NullSpan(object):
    """What an un-armed `span()` returns: records nothing, enters no
    annotation, takes (and drops) ``result``."""

    __slots__ = ()
    result = property(lambda self: None, lambda self, value: None)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


_mem_counter = [0]


def _sample_memory():
    _mem_counter[0] += 1
    if _mem_counter[0] % 64:
        return
    try:
        import jax

        nbytes = sum(a.nbytes for a in jax.live_arrays())
        record_counter("device_mem_bytes", float(nbytes))
    except Exception:
        pass


def span(name: str, cat: str = "operator", step: Optional[int] = None,
         **args):
    """A context manager around one span; while not `armed()` the shared
    no-op.  ``step`` makes it (and, by inheritance, the spans inside it)
    a step's span, entered as a ``StepTraceAnnotation`` with
    ``step_num``.  Other keywords are the span's attributes (the
    annotation's stats, the row's ``args``)."""
    return _Span(name, cat, step, args) if armed() else _NULL_SPAN


# -- user-facing objects (reference profiler.py Domain/Task/Frame/...) ----

class Domain(object):
    def __init__(self, name: str):
        self.name = name


class _Timed(object):
    def __init__(self, domain: Optional[Domain], name: str):
        self.name = (domain.name + "::" if domain else "") + name
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        if self._t0 is None:
            raise MXNetError("stop() before start()")
        if armed():
            record_span(self.name, type(self).__name__.lower(), self._t0,
                        time.perf_counter())
        self._t0 = None


class Task(_Timed):
    def __init__(self, domain: Optional[Domain] = None, name: str = "task"):
        super().__init__(domain, name)


class Frame(_Timed):
    def __init__(self, domain: Optional[Domain] = None, name: str = "frame"):
        super().__init__(domain, name)


class Counter(object):
    def __init__(self, domain: Optional[Domain] = None,
                 name: str = "counter", value: float = 0):
        self.name = (domain.name + "::" if domain else "") + name
        self._value = value

    def set_value(self, value):
        self._value = value
        record_counter(self.name, float(value))

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)


class Marker(object):
    def __init__(self, domain: Optional[Domain] = None, name: str = "marker"):
        self.name = (domain.name + "::" if domain else "") + name

    def mark(self, scope: str = "process"):
        if not _RUNNING or _PAUSED:
            return
        with _lock:
            _EVENTS.append({"name": self.name, "ph": "i", "ts": _now_us(),
                            "pid": getpid_cached(), "tid": 0, "s": scope[0]})


# -- dumping ---------------------------------------------------------------

def dump(finished: bool = True, profile_process: str = "worker"):
    """Write accumulated events as chrome://tracing JSON (reference
    `DumpProfile`, `profiler.cc:166`).

    The dump is self-describing for cross-process merging: events
    carry the real pid, a ``process_name`` metadata row names this
    role+rank, and ``otherData.epoch_origin_s`` records the wall-clock
    instant of ts=0 so `mxtpu.telemetry.merge_traces` can align
    per-role dumps onto one timeline."""
    try:
        from . import telemetry as _tel

        ident = _tel.identity()
    except Exception:
        ident = {"role": "local", "rank": 0, "pid": os.getpid()}
    pid = os.getpid()
    meta = [
        {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
         "args": {"name": "%s%d (pid %d)"
                  % (ident["role"], ident["rank"], pid)}},
    ]
    # name the thread rows that actually hold events (spans record
    # tid = get_ident() % 1000, so label those, marking this thread —
    # the dumper, almost always the dispatch thread — as such)
    main_tid = threading.get_ident() % 1000
    with _lock:
        span_events = [
            {"name": r["name"], "cat": r["cat"], "ph": "X",
             "ts": (r["t0"] - _START_TS) * 1e6,
             "dur": (r["t1"] - r["t0"]) * 1e6, "pid": pid,
             "tid": r["tid"] % 1000,
             **({"args": r["args"]} if "args" in r else {})}
            for r in _SPANS]
        seen_tids = {e.get("tid", 0) for e in span_events}
        seen_tids.update(e.get("tid", 0) for e in _EVENTS)
        for tid in sorted(seen_tids):
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid,
                         "args": {"name": "dispatch" if tid == main_tid
                                  else "thread-%d" % tid}})
        payload = {"traceEvents": meta + span_events + list(_EVENTS),
                   "displayTimeUnit": "ms",
                   "otherData": {"epoch_origin_s": _START_EPOCH,
                                 "role": ident["role"],
                                 "rank": ident["rank"], "pid": pid}}
        if finished:
            _EVENTS.clear()
            _SPANS.clear()
    with open(_CONFIG["filename"], "w") as f:
        json.dump(payload, f)


def dumps(reset: bool = False, format: str = "table") -> str:
    """Aggregate stats table (reference MXAggregateProfileStatsPrint)."""
    with _lock:
        rows = []
        for name, durs in sorted(_AGG.items()):
            n = len(durs)
            total = sum(durs)
            rows.append((name, n, total, min(durs), max(durs), total / n))
        if reset:
            _AGG.clear()
    if format == "json":
        return json.dumps([{"name": r[0], "count": r[1], "total_us": r[2],
                            "min_us": r[3], "max_us": r[4], "avg_us": r[5]}
                           for r in rows])
    lines = ["%-48s %8s %12s %12s %12s %12s" %
             ("Name", "Calls", "Total(us)", "Min(us)", "Max(us)", "Avg(us)")]
    for r in rows:
        lines.append("%-48s %8d %12.1f %12.1f %12.1f %12.1f" % r)
    return "\n".join(lines)


if os.environ.get("MXTPU_PROFILER_AUTOSTART",
                  os.environ.get("MXNET_PROFILER_AUTOSTART", "0")) == "1":
    set_state("run")
