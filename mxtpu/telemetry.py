"""Unified telemetry: event ring, per-step metrics, flight recorder.

The profiler (`mxtpu/profiler.py`) answers "where did the time go in
THIS process while I was watching"; this module answers the production
questions around it: what was every role doing just before the job
wedged, how fast is each rank actually stepping, and what does the
WHOLE cluster look like from one place.  Three pieces, one identity
(role / rank / pid / wall-clock epoch timestamps) shared by all:

  * **Structured event log** — a bounded in-memory ring of typed
    records (:data:`EVENT_KINDS`): training steps, XLA compiles,
    kvstore rounds, retries, failovers, checkpoints, membership
    changes, monitor stats.  Producers live in ``executor.py``,
    ``cached_op.py``, ``fused_train.py``, ``gluon/trainer.py``,
    ``module/module.py``, ``kvstore.py``, ``_ps.py``,
    ``resilience.py``, ``compile_cache.py`` and ``monitor.py``.
    Every record carries epoch (``time.time()``) timestamps plus the
    step / kvstore-round correlation ids, so records from different
    processes merge on a common axis.

  * **Cross-process aggregation** — every PS role ships its counter
    snapshot + recent events to the scheduler on the existing
    heartbeat channel (`_ps._start_heartbeat`); ``kv.telemetry()``
    returns the scheduler's merged per-node view, and
    ``tools/launch.py --telemetry-dir`` makes each role write a final
    ``telemetry_<role><rank>.json`` which :func:`merge_dir` folds into
    ONE chrome trace (clocks aligned via the epoch timestamps) and a
    cluster counter view (per-rank step time, straggler spread,
    retry/failover totals).

  * **Flight recorder** — :func:`dump_flight` writes the ring + the
    counter snapshot + all-thread stacks as
    ``flight_<role><rank>.json``.  Triggers: SIGTERM/SIGQUIT
    (:func:`install_flight_recorder`), unhandled exceptions
    (sys/threading excepthook), a dist kvstore timeout
    (``MXTPU_KVSTORE_TIMEOUT`` expiry in ``_ps._Client``), and the
    ``MXTPU_MAX_BAD_STEPS`` abort.  A SIGKILLed node cannot dump its
    own corpse, so the scheduler writes a POSTHUMOUS flight file from
    the node's last heartbeat-shipped snapshot when it declares the
    node dead (:func:`dump_flight_for`) — a ``check_elastic``-style
    kill still leaves a diagnosable record naming the dead rank's
    last round.

Always-on and cheap: ``MXTPU_TELEMETRY=0`` opts out entirely (every
producer call is then one bool check); the ring is bounded
(``MXTPU_TELEMETRY_RING``, default 512) and the per-step path is a few
dict operations with NO device synchronization.  The device-memory
watermark samples ``jax.live_arrays()`` only every
``MXTPU_TELEMETRY_MEMSAMPLE`` (64) steps.  Measured overhead is <1%
on the training hot paths (`docs/observability.md`).

Event record schema (all values JSON-safe scalars)::

    {"kind": <EVENT_KINDS>, "ts": <epoch seconds>,
     "role": "worker", "rank": 0, "pid": 12345,
     "step": <step id>?, "round": <kvstore round>?, ...payload}

See `docs/observability.md` for the full per-kind payload catalog.
"""
from __future__ import annotations

import collections
import json
import os
import signal
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

from .base import getenv, getenv_bool, getpid_cached

__all__ = [
    "EVENT_KINDS",
    "GAUGE_STATS",
    "enabled",
    "enable",
    "set_identity",
    "identity",
    "record",
    "record_step",
    "record_input_wait",
    "input_wait",
    "current_step",
    "events",
    "stat_rollup",
    "health_rollup",
    "perf_rollup",
    "clear",
    "metrics",
    "snapshot",
    "hb_payload",
    "aggregate_stats",
    "dump_flight",
    "dump_flight_for",
    "install_flight_recorder",
    "uninstall_flight_recorder",
    "flush",
    "merge_dir",
    "merge_traces",
    "Speedometer",
    "Histogram",
    "histogram",
    "histograms",
    "register_metrics_provider",
    "unregister_metrics_provider",
]

#: The typed record vocabulary.  ``step`` = one (or K fused) training
#: steps; ``compile`` = a new XLA program is being built; ``kvstore`` =
#: a worker-side push/pull; ``kvstore_round`` = a server applied a
#: completed sync round; ``retry`` = a resilience chokepoint retried;
#: ``failover`` = elastic server failover; ``membership`` = group
#: change (death declared / re-rank / rejoin); ``checkpoint`` = a
#: manifest committed; ``monitor`` = a Monitor tensor stat; ``timeout``
#: = a dist kvstore exchange expired; ``flight`` = a flight dump fired.
#: (``anomaly`` = the training-health layer, `mxtpu/health.py`,
#: detected a non-finite step, loss/grad spike, step-time regression
#: or OOM; ``tensor_stats`` = one in-graph per-layer grad/param-norm
#: sample at the ``MXTPU_HEALTH_STATS_EVERY`` cadence, rendered as
#: chrome-trace counter tracks by :func:`merge_dir`.)
#: (``perf`` = an `mx.perf` sampled device-sync point: per-program
#: host_dispatch/device_compute/wall spans + MFU when known, rendered
#: as chrome-trace counter tracks by :func:`merge_dir`.)
#: (``span`` = one finished `mx.tracing` causal span: trace/span/parent
#: ids + name + ``dur_s``, ts = the span's END like ``step`` records;
#: :func:`merge_dir` renders them as X spans and has
#: ``tracing.stitch`` join cross-process traces with flow events.)
#: (``op_profile`` = one `mx.xprof` per-op attribution attached to a
#: program: acquisition source (xplane/replay), op count, per-step
#: device time, per-op-class rollup and the top sink's name/class/
#: share — how cluster.json and ``tools/dash.py`` name each rank's
#: dominant device-time sink.)
EVENT_KINDS = ("step", "compile", "kvstore", "kvstore_round", "retry",
               "failover", "membership", "checkpoint", "monitor",
               "timeout", "flight", "anomaly", "tensor_stats", "serve",
               "reshard", "perf", "span", "resume",
               "op_profile")

#: ``profiler.stats()`` keys that are point-in-time gauges, not
#: additive counters: cluster aggregation takes their MAX, and counter
#: reconciliation (`tools/check_telemetry.py`) excludes them from the
#: sum-of-roles check.
GAUGE_STATS = ("step_time_us_last", "device_mem_watermark_bytes",
               "kvstore_round_last", "input_wait_us_last",
               "serve_queue_depth", "serve_inflight",
               "serve_batch_occupancy_pct", "serve_max_batch",
               "perf_host_dispatch_us_last",
               "perf_device_compute_us_last", "perf_input_wait_us_last",
               "perf_optimizer_us_last", "perf_collective_us_last",
               "obs_sample_wall_us_last")

# RLock, NOT Lock: the flight recorder's signal handler snapshots
# state on whatever thread the signal lands on — if that thread was
# inside record_step()'s critical section, a non-reentrant lock would
# deadlock the handler against itself and turn a clean SIGTERM into a
# wedge.  Re-entry only ever READS, so mid-update values are safe.
_lock = threading.RLock()

_ENABLED = getenv_bool("MXTPU_TELEMETRY", True)
_RING_SIZE = max(16, int(getenv("MXTPU_TELEMETRY_RING", "512") or 512))
_MEM_SAMPLE_EVERY = max(1, int(getenv("MXTPU_TELEMETRY_MEMSAMPLE", "64")
                               or 64))
# the live_arrays fallback walks every device buffer (milliseconds on
# a big process): never more often than this many seconds
_MEM_MIN_INTERVAL = float(getenv("MXTPU_TELEMETRY_MEM_INTERVAL", "10")
                          or 10)
_HB_EVENTS = max(0, int(getenv("MXTPU_TELEMETRY_HB_EVENTS", "64") or 64))

_RING: collections.deque = collections.deque(maxlen=_RING_SIZE)

# anchor for telling THIS run's flight records apart from leftovers in
# a reused --telemetry-dir (files older than process start are stale)
_START_TIME = time.time()

_IDENTITY = {
    "role": getenv("MXTPU_ROLE", getenv("DMLC_ROLE", "local")) or "local",
    "rank": 0,
}

# per-step metric accumulators (under _lock)
_METRICS = {"steps": 0, "examples": 0.0, "dt_sum": 0.0, "dt_last": 0.0,
            "last_t": None, "nonfinite": 0, "mem_watermark": 0,
            "input_waits": 0, "input_wait_sum": 0.0,
            "input_wait_last": 0.0}


def enabled() -> bool:
    """Telemetry on?  ``MXTPU_TELEMETRY=0`` opts out at import."""
    return _ENABLED


def enable(on: bool = True) -> None:
    """Flip telemetry at runtime (tests / embedding)."""
    global _ENABLED
    _ENABLED = bool(on)


def set_identity(role: Optional[str] = None,
                 rank: Optional[int] = None) -> None:
    """Stamp this process's role/rank into every future record.  The
    PS layer calls this as soon as the scheduler assigns a rank (and
    again on elastic re-rank)."""
    with _lock:
        if role is not None:
            _IDENTITY["role"] = str(role)
        if rank is not None:
            _IDENTITY["rank"] = int(rank)


def identity() -> Dict[str, Any]:
    """``{"role", "rank", "pid"}`` of this process (the cached pid is
    refreshed on fork, so dataloader workers stamp their own)."""
    with _lock:
        return {"role": _IDENTITY["role"], "rank": _IDENTITY["rank"],
                "pid": getpid_cached()}


def record(kind: str, **fields) -> Optional[Dict[str, Any]]:
    """Append one typed record to the ring.  One bool check when
    telemetry is off; a dict build + deque append when on — safe on
    hot paths.  ``fields`` must be JSON-safe scalars.  Returns the
    record dict (held by reference in the ring) so a producer may
    BACKFILL scalar fields it created eagerly — `mx.inspect` fills
    ``flops``/``peak_bytes`` on ``compile`` events once its lazy
    analysis runs (assignment to pre-existing keys only, so a
    concurrent JSON dump never sees the dict change size)."""
    if not _ENABLED:
        return None
    ev = {"kind": kind, "ts": time.time(), "pid": getpid_cached(),
          "role": _IDENTITY["role"], "rank": _IDENTITY["rank"]}
    for k, v in fields.items():
        if v is not None:
            ev[k] = v
    _RING.append(ev)
    return ev


def record_step(batch_size: int = 0, n: int = 1,
                duration: Optional[float] = None,
                skipped: bool = False, site: str = "train",
                grad_norm: Optional[float] = None,
                skipped_n: Optional[int] = None) -> int:
    """Account one training step (or ``n`` fused steps) and emit a
    ``step`` record.  ``duration`` defaults to the wall time since the
    previous call — the full iteration time including data/forward/
    backward, measured with NO device sync.  ``skipped`` marks a
    non-finite-grad step the trainer dropped (``skipped_n`` = how many
    of the ``n`` fused steps were dropped); ``grad_norm`` attaches the
    global gradient norm when a producer already has it in hand (the
    health layer's one-program check), making skipped-step bursts
    diagnosable from the flight recorder.  Returns the step id (the
    correlation id monitor/kvstore records share)."""
    if not _ENABLED:
        return 0
    now = time.monotonic()
    with _lock:
        last = _METRICS["last_t"]
        _METRICS["last_t"] = now
        if duration is None:
            duration = (now - last) if last is not None else 0.0
        _METRICS["steps"] += n
        step_id = _METRICS["steps"]
        _METRICS["examples"] += float(batch_size) * n
        _METRICS["dt_sum"] += duration
        _METRICS["dt_last"] = duration / max(1, n)
        if skipped_n is None:
            skipped_n = n if skipped else 0
        if skipped_n:
            _METRICS["nonfinite"] += skipped_n
        dt_last = _METRICS["dt_last"]
    from . import profiler as _prof

    _prof.inc_stat("telemetry_steps", n)
    if batch_size:
        _prof.inc_stat("telemetry_examples", int(batch_size) * n)
    _prof.set_stat("step_time_us_last", int(dt_last * 1e6))
    record("step", step=step_id, n=n, batch=int(batch_size),
           dur_s=round(duration, 6), site=site,
           skipped=True if skipped_n else None,
           skipped_n=skipped_n if skipped_n and n > 1 else None,
           grad_norm=round(float(grad_norm), 6)
           if grad_norm is not None else None)
    # step-time straggler/regression watchdog (mxtpu/health.py): a
    # deque append + cached-median compare — stays on the <10us/step
    # always-on budget tools/check_health.py asserts
    from . import health as _health

    _health.observe_step(step_id, dt_last, site=site)
    if step_id == n or (step_id % _MEM_SAMPLE_EVERY) < n:
        _sample_device_mem()
    return step_id


def record_input_wait(dur_s: float) -> None:
    """Account one host-input wait: the wall time the training loop
    spent BLOCKED waiting for the data pipeline to hand over the next
    batch (DataLoader / DataIter ``__next__``).  Always-on gauge
    (``input_wait_us_last`` in `profiler.stats()`) + running totals in
    :func:`metrics` — this is what attributes an input-bound step-time
    gap to the pipeline instead of the device.  Producers that can NEST (a DataLoader
    whose fetch drives an inner DataIter — both used to stamp the same
    wait, double-counting it) should wrap the fetch in
    :func:`input_wait` instead, which records only at the outermost
    level.  Also feeds the `mx.perf` phase schema as ``input_wait``."""
    if not _ENABLED:
        return
    with _lock:
        _METRICS["input_waits"] += 1
        _METRICS["input_wait_sum"] += dur_s
        _METRICS["input_wait_last"] = dur_s
    from . import profiler as _prof

    _prof.set_stat("input_wait_us_last", int(dur_s * 1e6))
    from . import perf as _perf

    _perf.note_phase("input_wait", dur_s)


_INPUT_WAIT_TLS = threading.local()


class _InputWait(object):
    """Re-entrancy-guarded input-wait scope (see :func:`input_wait`).
    A plain class, not ``contextmanager``: this sits on the per-batch
    hot path and a generator frame per batch is measurable there."""

    __slots__ = ("_outer", "_t0")

    def __enter__(self):
        depth = getattr(_INPUT_WAIT_TLS, "depth", 0)
        _INPUT_WAIT_TLS.depth = depth + 1
        self._outer = depth == 0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        _INPUT_WAIT_TLS.depth = getattr(_INPUT_WAIT_TLS, "depth", 1) - 1
        # only the OUTERMOST scope on this thread records: a DataLoader
        # wrapping a DataIter (or any nested iterator stack) counts the
        # wait ONCE, at the layer the training loop actually blocked on
        if self._outer and exc[0] is None:
            record_input_wait(time.perf_counter() - self._t0)
        return False


def input_wait() -> _InputWait:
    """Context manager measuring one host-input wait with a
    thread-local nesting guard: nested scopes (outer ``DataLoader``
    fetch driving an inner ``DataIter.__next__``) record nothing —
    only the outermost records, so `input_wait_frac` can never
    double-count one wall-clock wait::

        with telemetry.input_wait():
            batch = next(source)
    """
    return _InputWait()


_last_mem_sample = [0.0]


def _sample_device_mem() -> None:
    """Device-memory watermark — sampled every
    ``MXTPU_TELEMETRY_MEMSAMPLE`` steps, never per step.  Prefers the
    runtime's O(1) ``device.memory_stats()`` (real allocator numbers
    on TPU); the ``jax.live_arrays()`` fallback walks every buffer
    (milliseconds on a large process), so it is additionally
    rate-limited to once per ``MXTPU_TELEMETRY_MEM_INTERVAL``
    seconds."""
    try:
        import jax

        nbytes = 0
        for dev in jax.local_devices():
            try:
                stats = getattr(dev, "memory_stats", lambda: None)()
            except Exception:
                stats = None  # unimplemented on some PJRT plugins:
                # treat like a None return so the fallback still runs
            if not stats:
                nbytes = 0
                break
            nbytes += int(stats.get("peak_bytes_in_use",
                                    stats.get("bytes_in_use", 0)))
        if not nbytes:
            now = time.monotonic()
            if now - _last_mem_sample[0] < _MEM_MIN_INTERVAL:
                return
            _last_mem_sample[0] = now
            nbytes = sum(int(a.nbytes) for a in jax.live_arrays())
    except Exception:
        return
    with _lock:
        if nbytes > _METRICS["mem_watermark"]:
            _METRICS["mem_watermark"] = nbytes
    from . import profiler as _prof

    _prof.max_stat("device_mem_watermark_bytes", nbytes)
    try:
        from . import hbm as _hbm

        _hbm.observe_used(nbytes)
    except Exception:
        pass


def current_step() -> int:
    """The latest COMPLETED step id (0 before any step).  Producers
    stamping in-flight work (a push, a compile) therefore tag it with
    the previous step's id — the documented join rule is "events of
    step N carry step == N-1" (`docs/observability.md`)."""
    with _lock:
        return _METRICS["steps"]


def events(kind: Optional[str] = None) -> List[Dict[str, Any]]:
    """Snapshot of the ring (oldest first), optionally one kind."""
    evs = list(_RING)
    if kind is not None:
        evs = [e for e in evs if e.get("kind") == kind]
    return evs


def clear() -> None:
    """Drop all ring records and reset the step metrics (tests).
    Registered histograms are reset in place (the registry itself —
    and any metrics providers — survive, matching counter behavior)."""
    _RING.clear()
    with _lock:
        _METRICS.update(steps=0, examples=0.0, dt_sum=0.0, dt_last=0.0,
                        last_t=None, nonfinite=0, mem_watermark=0,
                        input_waits=0, input_wait_sum=0.0,
                        input_wait_last=0.0)
        for h in _HISTOGRAMS.values():
            h.reset()


# ---------------------------------------------------------------------------
# Streaming percentile histograms
# ---------------------------------------------------------------------------

class Histogram(object):
    """Bounded streaming percentile histogram over log-spaced buckets.

    Fixed memory (one int per bucket, ~170 buckets at the defaults),
    O(1) :meth:`record`, thread-safe.  Buckets grow geometrically by
    ``10**(1/bins_per_decade)`` from ``low`` to ``high`` (values
    outside clamp into the under/overflow buckets), so any quantile is
    answered within ~``(growth-1)/2`` relative error — ±7% at the
    default 16 bins/decade, plenty for latency SLOs where the question
    is "is p99 under 200ms", not "is p99 198.3ms or 198.4ms".

    This is the serving SLO primitive: `mx.serve` keeps one per model
    for request latency (p50/p95/p99 surfaced via :func:`metrics`).

    Use the module-level :func:`histogram` get-or-create registry to
    have a histogram's :meth:`snapshot` ride along in
    :func:`metrics()["histograms"]` (and therefore in heartbeat
    snapshots and ``telemetry_*.json`` dumps) automatically.
    """

    def __init__(self, low: float = 1e-6, high: float = 1e4,
                 bins_per_decade: int = 16):
        import math

        if not (0 < low < high):
            raise ValueError("need 0 < low < high, got %r, %r"
                             % (low, high))
        self.low = float(low)
        self.high = float(high)
        self._log_growth = math.log(10.0) / max(1, int(bins_per_decade))
        # bucket 0 = underflow (<= low); last = overflow (>= high)
        self.nbins = int(math.ceil(
            math.log(high / low) / self._log_growth)) + 2
        self._counts = [0] * self.nbins
        # RLock for the same reason as the module _lock above: a
        # flight-recorder signal landing inside record() must be able
        # to snapshot() on the same thread (re-entry only reads, so a
        # mid-update count is an acceptable crash-dump approximation)
        self._hlock = threading.RLock()
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")

    def reset(self) -> None:
        with self._hlock:
            self._counts = [0] * self.nbins
            self.count = 0
            self.total = 0.0
            self.vmin = float("inf")
            self.vmax = float("-inf")

    def _index(self, v: float) -> int:
        import math

        if v <= self.low:
            return 0
        if math.isinf(v):  # int(log(inf)) would raise OverflowError
            return self.nbins - 1
        i = int(math.log(v / self.low) / self._log_growth) + 1
        return i if i < self.nbins else self.nbins - 1

    def record(self, value: float) -> None:
        v = float(value)
        if v != v:  # NaN would poison min/max and land nowhere sane
            return
        i = self._index(v)
        if v == float("inf"):
            v = self.high  # overflow bucket; keep total/vmax finite
        elif v == float("-inf"):
            v = self.low   # underflow bucket; keep total/vmin finite
        with self._hlock:
            self._counts[i] += 1
            self.count += 1
            self.total += v
            if v < self.vmin:
                self.vmin = v
            if v > self.vmax:
                self.vmax = v

    def merge(self, other: "Histogram") -> "Histogram":
        """Fold another histogram of the SAME bucket layout into this
        one (per-worker client histograms -> one run view)."""
        if (other.low, other._log_growth, other.nbins) != \
                (self.low, self._log_growth, self.nbins):
            raise ValueError("cannot merge histograms with different "
                             "bucket layouts")
        # canonical lock order: a.merge(b) racing b.merge(a) would
        # otherwise hold one lock each and deadlock waiting on the other
        first, second = (self, other) if id(self) <= id(other) \
            else (other, self)
        with first._hlock:
            with second._hlock:
                for i, c in enumerate(other._counts):
                    self._counts[i] += c
                self.count += other.count
                self.total += other.total
                self.vmin = min(self.vmin, other.vmin)
                self.vmax = max(self.vmax, other.vmax)
        return self

    def _quantile_of(self, counts, n: int, q: float,
                     vmin: Optional[float] = None,
                     vmax: Optional[float] = None) -> float:
        """q-quantile over an arbitrary bucket-count vector of THIS
        histogram's layout (shared by the cumulative :meth:`quantile`
        and the windowed :meth:`interval`): the geometric midpoint of
        the bucket holding the rank, clamped into [vmin, vmax] when
        given.  0.0 when the vector is empty."""
        import math

        if n <= 0:
            return 0.0
        rank = min(n - 1, max(0, int(math.ceil(q * n)) - 1))
        acc = 0
        idx = self.nbins - 1
        for i, c in enumerate(counts):
            acc += c
            if acc > rank:
                idx = i
                break
        if idx == 0:
            est = self.low
        else:
            # bucket idx spans [low*g^(idx-1), low*g^idx)
            est = self.low * math.exp(self._log_growth * (idx - 0.5))
        if vmin is not None:
            est = max(est, vmin)
        if vmax is not None:
            est = min(est, vmax)
        return est

    def quantile(self, q: float) -> float:
        """The q-quantile (0..1) as the geometric midpoint of the
        bucket holding that rank, clamped to the observed [min, max].
        0.0 when empty."""
        with self._hlock:
            counts = list(self._counts)
            n = self.count
            vmin, vmax = self.vmin, self.vmax
        return self._quantile_of(counts, n, q, vmin, vmax)

    def state(self) -> tuple:
        """Opaque cumulative state for :meth:`interval` — take one,
        hold it, and the next ``interval(prev_state)`` call answers
        "what were the percentiles BETWEEN the two samples"."""
        with self._hlock:
            return (tuple(self._counts), self.count, self.total)

    def interval(self, prev: Optional[tuple] = None):
        """WINDOWED snapshot: percentiles of only the values recorded
        since ``prev`` (a state returned by :meth:`state` or a prior
        ``interval`` call).  ``prev=None`` means "since the
        beginning".  Returns ``(snapshot_dict, new_state)`` where the
        dict carries per-window ``count/sum/avg/p50/p95/p99`` — the
        time-series row primitive (`mx.obs` sample rows show
        per-interval latency, not lifetime-cumulative values).  A
        :meth:`reset` inside the window (cumulative counts went
        backwards) degrades gracefully to "everything currently
        recorded".  Interval quantiles clamp to the bucket range, not
        a per-window min/max (not tracked per window)."""
        with self._hlock:
            cur = (tuple(self._counts), self.count, self.total)
        if (prev is None or len(prev) != 3
                or len(prev[0]) != len(cur[0])):
            prev = ((0,) * len(cur[0]), 0, 0.0)
        counts = [a - b for a, b in zip(cur[0], prev[0])]
        n = cur[1] - prev[1]
        tot = cur[2] - prev[2]
        if n < 0 or any(c < 0 for c in counts):
            counts, n, tot = list(cur[0]), cur[1], cur[2]
        snap = {"count": n, "sum": tot,
                "avg": tot / n if n else 0.0,
                "p50": self._quantile_of(counts, n, 0.50),
                "p95": self._quantile_of(counts, n, 0.95),
                "p99": self._quantile_of(counts, n, 0.99)}
        return snap, cur

    def percentiles(self) -> Dict[str, float]:
        return {"p50": self.quantile(0.50), "p95": self.quantile(0.95),
                "p99": self.quantile(0.99)}

    def snapshot(self) -> Dict[str, Any]:
        """JSON-safe summary: count/sum/avg/min/max + p50/p95/p99."""
        with self._hlock:
            n, tot = self.count, self.total
            vmin, vmax = self.vmin, self.vmax
        out = {"count": n, "sum": tot, "avg": tot / n if n else 0.0,
               "min": vmin if n else 0.0, "max": vmax if n else 0.0}
        out.update(self.percentiles())
        return out


_HISTOGRAMS: Dict[str, Histogram] = {}


def histogram(name: str, low: float = 1e-6, high: float = 1e4,
              bins_per_decade: int = 16) -> Histogram:
    """Get-or-create the registered histogram ``name``.  Registered
    histograms appear in :func:`metrics()["histograms"]` and reset
    with :func:`clear`."""
    with _lock:
        h = _HISTOGRAMS.get(name)
        if h is None:
            h = _HISTOGRAMS[name] = Histogram(low, high, bins_per_decade)
        return h


def histograms() -> Dict[str, Dict[str, Any]]:
    """Snapshots of every registered histogram, by name."""
    with _lock:
        hs = dict(_HISTOGRAMS)
    return {name: h.snapshot() for name, h in sorted(hs.items())}


def _registered_histograms() -> Dict[str, Histogram]:
    """The LIVE registered histogram objects (not snapshots) — the
    `mx.obs` sampler holds per-histogram interval states across
    ticks."""
    with _lock:
        return dict(_HISTOGRAMS)


# named callables merged into metrics() under their key — how a
# subsystem (mx.serve) surfaces its live gauges without telemetry
# importing it (the dependency points the other way)
_METRIC_PROVIDERS: Dict[str, Callable[[], Dict[str, Any]]] = {}


def register_metrics_provider(name: str,
                              fn: Callable[[], Dict[str, Any]]) -> None:
    """Merge ``fn()`` (a JSON-safe dict) into :func:`metrics` output
    under key ``name``.  A provider that raises is reported as
    ``{"error": ...}`` instead of breaking metrics()."""
    with _lock:
        _METRIC_PROVIDERS[name] = fn


def unregister_metrics_provider(name: str) -> None:
    with _lock:
        _METRIC_PROVIDERS.pop(name, None)


def _step_metrics() -> Dict[str, Any]:
    """Always-on per-step training metrics of THIS process: step
    count, latency (last/avg seconds), examples/sec over the run,
    non-finite steps skipped, device-memory watermark bytes."""
    with _lock:
        dt_sum = _METRICS["dt_sum"]
        return {
            "steps": _METRICS["steps"],
            "examples": _METRICS["examples"],
            "step_time_last_s": _METRICS["dt_last"],
            "step_time_avg_s": dt_sum / max(1, _METRICS["steps"]),
            "examples_per_sec": (_METRICS["examples"] / dt_sum)
            if dt_sum > 0 else 0.0,
            "nonfinite_steps": _METRICS["nonfinite"],
            "device_mem_watermark_bytes": _METRICS["mem_watermark"],
            "input_waits": _METRICS["input_waits"],
            "input_wait_last_s": _METRICS["input_wait_last"],
            "input_wait_avg_s": _METRICS["input_wait_sum"]
            / max(1, _METRICS["input_waits"]),
            # the attribution ratio ROADMAP item 3 wants: what share
            # of wall time went to WAITING on host input
            "input_wait_frac": (_METRICS["input_wait_sum"] / dt_sum)
            if dt_sum > 0 else 0.0,
        }


def metrics() -> Dict[str, Any]:
    """Always-on metrics of THIS process: the per-step training block
    (:func:`_step_metrics`), every registered :class:`Histogram`
    snapshot under ``"histograms"``, and each registered metrics
    provider's dict under its own key (`mx.serve` publishes its
    queue-depth / batch-occupancy / SLO gauges this way)."""
    out = _step_metrics()
    if _HISTOGRAMS:
        out["histograms"] = histograms()
    with _lock:
        providers = list(_METRIC_PROVIDERS.items())
    for name, fn in providers:
        try:
            out[name] = fn()
        except Exception as e:  # a broken provider must not take
            out[name] = {"error": str(e)}  # metrics() down with it
    return out


def snapshot(max_events: Optional[int] = None) -> Dict[str, Any]:
    """This process's full telemetry state: identity + wall-clock
    timestamp + ``profiler.stats()`` + :func:`metrics` + ring events.
    The unit that ships over the heartbeat and lands in the per-role
    ``telemetry_*.json`` files."""
    from . import profiler as _prof

    evs = events()
    if max_events is not None and len(evs) > max_events:
        evs = evs[-max_events:]
    snap = identity()
    snap["ts"] = time.time()
    snap["stats"] = _prof.stats()
    snap["metrics"] = metrics()
    snap["events"] = evs
    return snap


def hb_payload() -> Optional[Dict[str, Any]]:
    """Snapshot a role attaches to its scheduler heartbeat (capped at
    ``MXTPU_TELEMETRY_HB_EVENTS`` recent events); None when off."""
    if not _ENABLED:
        return None
    return snapshot(max_events=_HB_EVENTS)


def stat_rollup(stats) -> Dict[str, int]:
    """Derived per-node tickers from ONE ``profiler.stats()`` dict —
    the single definition shared by `mx.obs` sample rows, the live
    aggregator's per-role rows and :func:`health_rollup`, so the
    anomaly/retry/failover arithmetic cannot drift between surfaces.
    Tolerates a malformed dict (a dying role's last heartbeat)."""
    out = {"anomalies": 0, "retries": 0, "failovers": 0}
    if not isinstance(stats, dict):
        return out

    def _i(v) -> int:
        try:
            return int(v)
        except (TypeError, ValueError):
            return 0

    for k, v in stats.items():
        if k.startswith("health_anomaly::"):
            out["anomalies"] += _i(v)
        elif k.startswith("retry_attempts::"):
            out["retries"] += _i(v)
        elif k.startswith("serve_failover::"):
            out["failovers"] += _i(v)
    out["anomalies"] += _i(stats.get("health_nonfinite_steps", 0))
    out["anomalies"] += _i(stats.get("health_oom", 0))
    out["failovers"] += _i(stats.get("elastic_failover", 0))
    return out


def health_rollup(snaps: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """Fold per-node snapshots into the training-health cluster view:
    per-node anomaly counts (``health_*`` counters) and the FIRST
    non-finite blame each node reported (from its ``anomaly`` events).
    Shared by ``merge_dir``'s cluster.json and the scheduler's
    ``kv.telemetry()`` view."""
    per_node: Dict[str, int] = {}
    first_nonfinite: Dict[str, Dict[str, Any]] = {}
    for key, snap in snaps.items():
        if not isinstance(snap, dict):
            continue  # a corrupt heartbeat/merge source names the
            # gap upstream; the rollup folds the survivors
        n = stat_rollup(snap.get("stats"))["anomalies"]
        if n:
            per_node[key] = n
        evs = snap.get("events")
        for ev in (evs if isinstance(evs, list) else []):
            if not isinstance(ev, dict):
                continue
            if ev.get("kind") == "anomaly" and \
                    ev.get("atype") == "nonfinite" and ev.get("layer"):
                first_nonfinite[key] = {
                    "layer": ev.get("layer"), "step": ev.get("step"),
                    "origin": ev.get("origin"), "site": ev.get("site")}
                break
    return {"anomaly_total": sum(per_node.values()),
            "per_node_anomalies": per_node,
            "first_nonfinite": first_nonfinite}


def perf_rollup(snaps: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """Fold per-node snapshots into the performance cluster view:
    per-rank MFU, the worker MFU spread (straggler signal — max-min
    over ranks reporting one), and each rank's dominant phase.  Shared
    by ``merge_dir``'s cluster.json and the scheduler's
    ``kv.telemetry()`` view."""
    per_rank_mfu: Dict[str, float] = {}
    per_rank_phase: Dict[str, str] = {}
    for key, snap in snaps.items():
        if not isinstance(snap, dict):
            continue  # tolerate corrupt sources; fold the survivors
        m = snap.get("metrics")
        p = m.get("perf") if isinstance(m, dict) else None
        p = p if isinstance(p, dict) else {}
        try:
            if p.get("mfu") is not None:
                per_rank_mfu[key] = float(p["mfu"])
        except (TypeError, ValueError):
            pass
        if p.get("dominant_phase"):
            per_rank_phase[key] = str(p["dominant_phase"])
    worker_mfus = [v for k, v in per_rank_mfu.items()
                   if k.startswith("worker")] or list(per_rank_mfu.values())
    return {"per_rank_mfu": per_rank_mfu,
            "mfu_spread": (max(worker_mfus) - min(worker_mfus))
            if len(worker_mfus) >= 2 else 0.0,
            "per_rank_dominant_phase": per_rank_phase}


def hbm_rollup(snaps: Dict[str, Dict[str, Any]]) -> Dict[str, Any]:
    """Fold per-node snapshots into the device-memory cluster view:
    per-rank used/peak/headroom bytes plus a leak flag from each role's
    ``metrics()["hbm"]`` block (the `mx.hbm` census provider).  Shared
    by ``merge_dir``'s cluster.json and the scheduler's
    ``kv.telemetry()`` view."""
    per_rank: Dict[str, Dict[str, Any]] = {}
    leak_ranks: List[str] = []
    for key, snap in snaps.items():
        if not isinstance(snap, dict):
            continue  # tolerate corrupt sources; fold the survivors
        m = snap.get("metrics")
        h = m.get("hbm") if isinstance(m, dict) else None
        if not isinstance(h, dict) or not h.get("enabled"):
            continue
        per_rank[key] = {
            "used_bytes": int(h.get("used_bytes") or 0),
            "peak_used_bytes": int(h.get("peak_used_bytes") or 0),
            "headroom_bytes": int(h.get("headroom_bytes") or 0),
            "leak": bool(h.get("leak")),
        }
        if h.get("leak"):
            leak_ranks.append(key)
        if h.get("last_leak"):
            per_rank[key]["last_leak"] = h["last_leak"]
    headrooms = [r["headroom_bytes"] for r in per_rank.values()]
    return {"per_rank": per_rank,
            "min_headroom_bytes": min(headrooms) if headrooms else None,
            "peak_used_bytes": max(
                (r["peak_used_bytes"] for r in per_rank.values()),
                default=0),
            "leak_ranks": leak_ranks}


def aggregate_stats(stat_dicts) -> Dict[str, int]:
    """Fold per-node counter snapshots into one cluster view: additive
    counters sum, :data:`GAUGE_STATS` take the max."""
    out: Dict[str, int] = {}
    for stats in stat_dicts:
        if not isinstance(stats, dict):
            continue  # a SIGKILL-truncated role may leave a non-dict
        for k, v in stats.items():  # stats block; fold the survivors
            try:
                iv = int(v)
            except (TypeError, ValueError):
                continue
            if k in GAUGE_STATS:
                out[k] = max(out.get(k, 0), iv)
            else:
                out[k] = out.get(k, 0) + iv
    return out


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

_FLIGHT = {"dir": None, "signals_installed": False,
           "hooks_installed": False, "prev_handlers": {},
           "prev_excepthook": None, "prev_threadhook": None}


def _flight_dir() -> Optional[str]:
    return _FLIGHT["dir"] or getenv("MXTPU_TELEMETRY_DIR")


def _thread_stacks() -> Dict[str, List[str]]:
    """All-thread stack traces, formatted (the post-mortem hang
    answer: WHERE was every thread when the trigger fired)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for tid, frame in sys._current_frames().items():
        key = "%s-%d" % (names.get(tid, "thread"), tid)
        out[key] = traceback.format_stack(frame)
    return out


def _json_safe(obj):
    """Replace non-finite floats with strings so the written file is
    STRICT JSON.  Diverged runs stamp NaN/Inf grad norms into their
    step/anomaly/blame records — exactly the artifacts a post-mortem
    opens — and python's default ``json.dump`` would emit the bare
    ``NaN`` token, which chrome://tracing / ``JSON.parse`` reject
    wholesale."""
    if isinstance(obj, float):
        return obj if obj == obj and abs(obj) != float("inf") \
            else repr(obj)  # 'nan' / 'inf' / '-inf'
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    return obj


def _write_json(path: str, payload: Dict[str, Any]) -> Optional[str]:
    """Atomic write (temp + fsync + rename via resilience) so a crash
    mid-dump never leaves a truncated file a post-mortem tool would
    trust.  Returns None instead of raising — dump paths run inside
    signal handlers and excepthooks."""
    try:
        from .resilience import atomic_write

        with atomic_write(path, "w") as f:
            json.dump(_json_safe(payload), f, default=str,
                      allow_nan=False)
    except Exception:
        return None
    return path


def _flight_target(d: str, role: str, rank: int, pid: int) -> str:
    """Pick the path a flight dump lands at.  The base name is
    ``flight_<role><rank>.json`` — but a FRESH record there written by
    a DIFFERENT process (e.g. the posthumous corpse of the dead worker
    whose rank this survivor inherited after an elastic re-rank) must
    not be clobbered, so the dump diverts to a pid-suffixed sibling
    (still ``flight_*.json``, so the merge index picks both up).
    Records from a previous run (mtime before this process started)
    are stale and fair game."""
    base = os.path.join(d, "flight_%s%d.json" % (role, rank))
    try:
        if os.path.getmtime(base) < _START_TIME:
            return base  # leftover from an earlier run
        with open(base) as f:
            existing = json.load(f)
        if int(existing.get("pid", -1)) == pid:
            return base  # our own earlier dump: newer state wins
    except (OSError, ValueError):
        return base
    return os.path.join(d, "flight_%s%d_pid%d.json" % (role, rank, pid))


def dump_flight(reason: str, detail: str = "",
                directory: Optional[str] = None) -> Optional[str]:
    """Dump the flight record — ring events, counter snapshot, step
    metrics, all-thread stacks — as ``flight_<role><rank>.json`` in
    ``directory`` (default ``MXTPU_TELEMETRY_DIR``).  Returns the path
    or None (disabled / no directory / IO failure — never raises)."""
    d = directory or _flight_dir()
    if not _ENABLED or not d:
        return None
    try:
        os.makedirs(d, exist_ok=True)
        payload = snapshot()
        payload["reason"] = str(reason)
        if detail:
            payload["detail"] = str(detail)[:2000]
        payload["threads"] = _thread_stacks()
        record("flight", trigger=str(reason))
        path = _flight_target(d, payload["role"], payload["rank"],
                              payload["pid"])
        out = _write_json(path, payload)
    except Exception:
        return None
    if out:
        from . import profiler as _prof

        _prof.inc_stat("flight_dumps")
    return out


def dump_flight_for(snap: Dict[str, Any], reason: str,
                    directory: Optional[str] = None) -> Optional[str]:
    """POSTHUMOUS flight record: the scheduler writes the dead node's
    last heartbeat-shipped snapshot on its behalf when it declares the
    node dead — a SIGKILLed rank cannot dump its own corpse, but its
    last known step/round/counters are still on record."""
    d = directory or _flight_dir()
    if not _ENABLED or not d or not isinstance(snap, dict):
        return None
    try:
        os.makedirs(d, exist_ok=True)
        payload = dict(snap)
        payload["reason"] = str(reason)
        payload["posthumous"] = True
        payload["declared_ts"] = time.time()
        role = payload.get("role", "node")
        rank = int(payload.get("rank", 0))
        pid = int(payload.get("pid", 0))
        path = os.path.join(d, "flight_%s%d.json" % (role, rank))
        try:
            if os.path.getmtime(path) >= _START_TIME:
                # a fresh record already sits at the canonical name.
                # Same pid: the node dumped its OWN richer record (e.g.
                # SIGTERM then silence) — never clobber it with this
                # staler snapshot.  Different pid: a DIFFERENT
                # incarnation died there earlier this run (elastic
                # respawn at the same rank) — divert to a pid-suffixed
                # sibling so the second death still leaves its corpse.
                with open(path) as f:
                    if int(json.load(f).get("pid", -1)) == pid:
                        return None
                path = os.path.join(
                    d, "flight_%s%d_pid%d.json" % (role, rank, pid))
        except (OSError, ValueError):
            pass  # stale leftover / unreadable: the canonical name
        return _write_json(path, payload)
    except Exception:
        return None


def _flight_signal_handler(signum, frame):
    try:
        name = signal.Signals(signum).name
    except ValueError:
        name = str(signum)
    dump_flight("signal", name)
    try:
        # the chained previous disposition usually TERMINATES the
        # process (no atexit): let the mx.obs run ledger write its
        # final sample + summary first, so a role the launcher reaps
        # with SIGTERM still closes its trial record (idempotent; a
        # SIGKILL still leaves no summary — that asymmetry is the
        # orderly-vs-killed signal tools/check_obs.py asserts)
        from . import obs as _obs

        _obs._ledger_epilogue()
    except Exception:
        pass
    from .resilience import chain_prev_signal

    chain_prev_signal(_FLIGHT["prev_handlers"].get(signum),
                      signum, frame)


def _flight_excepthook(exc_type, exc, tb):
    dump_flight("exception", "%s: %s" % (exc_type.__name__, exc))
    prev = _FLIGHT["prev_excepthook"] or sys.__excepthook__
    prev(exc_type, exc, tb)


def _flight_threadhook(args):
    dump_flight("thread_exception", "%s: %s in %r"
                % (getattr(args.exc_type, "__name__", "?"),
                   args.exc_value, getattr(args.thread, "name", "?")))
    prev = _FLIGHT["prev_threadhook"]
    if prev is not None:
        prev(args)


def install_flight_recorder(directory: Optional[str] = None,
                            signals=(signal.SIGTERM, signal.SIGQUIT)
                            ) -> None:
    """Arm the flight recorder: set the dump directory (default
    ``MXTPU_TELEMETRY_DIR``), chain SIGTERM/SIGQUIT handlers (previous
    disposition still runs — the process dies as before, with a corpse
    on disk), and wrap sys/threading excepthooks so an unhandled
    exception dumps too.  Idempotent; signal install is skipped off
    the main thread (hooks still arm)."""
    if directory is not None:
        _FLIGHT["dir"] = os.path.abspath(directory)
    if not _FLIGHT["hooks_installed"]:
        _FLIGHT["prev_excepthook"] = sys.excepthook
        sys.excepthook = _flight_excepthook
        if hasattr(threading, "excepthook"):
            _FLIGHT["prev_threadhook"] = threading.excepthook
            threading.excepthook = _flight_threadhook
        _FLIGHT["hooks_installed"] = True
    if not _FLIGHT["signals_installed"]:
        try:
            for sig in signals:
                _FLIGHT["prev_handlers"][sig] = signal.signal(
                    sig, _flight_signal_handler)
            _FLIGHT["signals_installed"] = True
        except ValueError:
            pass  # not the main thread


def uninstall_flight_recorder() -> None:
    """Restore the previous signal handlers and excepthooks (tests)."""
    if _FLIGHT["signals_installed"]:
        for sig, prev in _FLIGHT["prev_handlers"].items():
            try:
                signal.signal(sig, prev if prev is not None
                              else signal.SIG_DFL)
            except (ValueError, TypeError):
                pass
        _FLIGHT["prev_handlers"].clear()
        _FLIGHT["signals_installed"] = False
    if _FLIGHT["hooks_installed"]:
        sys.excepthook = _FLIGHT["prev_excepthook"] or sys.__excepthook__
        if hasattr(threading, "excepthook") and \
                _FLIGHT["prev_threadhook"] is not None:
            threading.excepthook = _FLIGHT["prev_threadhook"]
        _FLIGHT["hooks_installed"] = False
    _FLIGHT["dir"] = None


def flush(directory: Optional[str] = None) -> Optional[str]:
    """Write this process's final snapshot as
    ``telemetry_<role><rank>.json`` (the per-role unit
    :func:`merge_dir` consumes).  Called at exit when
    ``MXTPU_TELEMETRY_DIR`` is set; server/scheduler roles call it
    explicitly before their hard ``os._exit``."""
    d = directory or _flight_dir()
    if not _ENABLED or not d:
        return None
    try:
        os.makedirs(d, exist_ok=True)
        snap = snapshot()
        path = os.path.join(d, "telemetry_%s%d.json"
                            % (snap["role"], snap["rank"]))
        return _write_json(path, snap)
    except Exception:
        return None


if getenv("MXTPU_TELEMETRY_DIR") and _ENABLED:
    # a launched role: arm the crash paths and flush a final snapshot
    # on clean interpreter exit (roles that hard-exit call flush()
    # themselves — see kvstore_server.init_module)
    import atexit

    install_flight_recorder()
    atexit.register(flush)

if hasattr(os, "register_at_fork"):
    # fork-without-exec children (DataLoader pool workers) are
    # HELPERS, not roles: they inherit the armed SIGTERM handler and
    # the parent's role/rank, so routine pool.terminate() would leave
    # crash-style flight corpses under the parent's name — and the
    # first one would claim flight_<role><rank>.json, blocking the
    # scheduler's posthumous record for the real worker.  Disarm in
    # the child; a process that execs (launch.py roles) re-imports and
    # re-arms itself.
    os.register_at_fork(after_in_child=uninstall_flight_recorder)


# ---------------------------------------------------------------------------
# Merging (per-role files -> one chrome trace + one cluster view)
# ---------------------------------------------------------------------------

def _role_key(snap: Dict[str, Any]) -> str:
    try:
        rank = int(snap.get("rank", 0))
    except (TypeError, ValueError):
        rank = 0
    return "%s%d" % (snap.get("role", "node"), rank)


def _load_snap(path: str) -> Dict[str, Any]:
    """Load one per-role JSON file STRICTLY: raises ``ValueError`` on
    torn/truncated/non-object content (a SIGKILLed role can leave any
    of those) so :func:`merge_dir` can merge the survivors and NAME
    the gap instead of crashing — or worse, silently dropping it."""
    with open(path) as f:
        snap = json.load(f)
    if not isinstance(snap, dict):
        raise ValueError("not a JSON object")
    # normalize the blocks every consumer indexes into
    if not isinstance(snap.get("stats"), dict):
        snap["stats"] = {}
    if not isinstance(snap.get("metrics"), dict):
        snap["metrics"] = {}
    evs = snap.get("events")
    snap["events"] = [e for e in evs if isinstance(e, dict)] \
        if isinstance(evs, list) else []
    return snap


def _events_to_chrome(snap: Dict[str, Any], t0: float) -> List[Dict]:
    """Telemetry ring records -> chrome trace events.  Records carry
    EPOCH timestamps, so alignment is just a shared origin ``t0``:
    ``ts_us = (ts - t0) * 1e6``.  ``step`` records with a duration
    render as complete (X) spans ending at their timestamp; everything
    else is an instant."""
    pid = int(snap.get("pid", 0))
    out = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "%s (pid %d)" % (_role_key(snap), pid)}}]
    for ev in snap.get("events", []):
        ts_us = (float(ev.get("ts", t0)) - t0) * 1e6
        args = {k: v for k, v in ev.items()
                if k not in ("kind", "ts", "pid", "role", "rank")}
        dur = ev.get("dur_s")
        if ev.get("kind") == "tensor_stats":
            # per-layer grad-norm counter tracks: one ph="C" series per
            # layer, so chrome://tracing plots the norm trajectories
            # next to the step spans
            for layer, st in sorted((ev.get("stats") or {}).items()):
                out.append({"name": "grad_norm/%s" % layer,
                            "cat": "health", "ph": "C", "ts": ts_us,
                            "pid": pid, "tid": 0,
                            "args": {"grad_norm":
                                     st.get("grad_norm", 0.0)}})
            continue
        if ev.get("kind") == "perf":
            # mx.perf sampled sync points: per-program counter tracks
            # (device span + MFU when known) next to the step spans
            prog = ev.get("program", "program")
            cargs = {"device_compute_us": ev.get("device_us", 0.0),
                     "host_dispatch_us": ev.get("host_us", 0.0)}
            out.append({"name": "perf/%s" % prog, "cat": "perf",
                        "ph": "C", "ts": ts_us, "pid": pid, "tid": 0,
                        "args": cargs})
            if ev.get("mfu") is not None:
                out.append({"name": "mfu/%s" % prog, "cat": "perf",
                            "ph": "C", "ts": ts_us, "pid": pid,
                            "tid": 0, "args": {"mfu": ev["mfu"]}})
            continue
        if ev.get("kind") == "span" and dur:
            # mx.tracing causal spans: same END-timestamp convention
            # as step records; the trace id stays in args so the flow
            # events tracing.stitch emits can be matched to these
            start = max(0.0, ts_us - float(dur) * 1e6)
            out.append({"name": ev.get("name", "span"), "cat": "trace",
                        "ph": "X", "ts": start, "dur": ts_us - start,
                        "pid": pid, "tid": 0, "args": args})
            continue
        if ev.get("kind") == "step" and dur:
            # the record's ts is the step's END; when the start would
            # fall before the merged origin, clip the DURATION too so
            # the span still ends at its true instant
            start = max(0.0, ts_us - float(dur) * 1e6)
            out.append({"name": "step", "cat": "telemetry", "ph": "X",
                        "ts": start, "dur": ts_us - start,
                        "pid": pid, "tid": 0, "args": args})
        else:
            out.append({"name": ev.get("kind", "event"),
                        "cat": "telemetry", "ph": "i", "ts": ts_us,
                        "pid": pid, "tid": 0, "s": "p", "args": args})
    return out


def merge_traces(paths, out_path: Optional[str] = None) -> Dict[str, Any]:
    """Merge per-role PROFILER chrome dumps into one trace.  Each dump
    written by ``profiler.dump()`` stamps real pids into its events
    and records ``otherData.epoch_origin_s`` — the wall-clock instant
    its relative timestamps count from — so this shifts every file
    onto the earliest origin and concatenates.  Returns the merged
    trace dict (and writes it to ``out_path`` when given)."""
    loaded = []
    for p in paths:
        try:
            with open(p) as f:
                trace = json.load(f)
        except (OSError, ValueError):
            continue
        origin = trace.get("otherData", {}).get("epoch_origin_s")
        if origin is None:
            # a foreign chrome trace with no epoch anchor cannot be
            # placed on the shared axis; anchoring it at 0 would shift
            # every OTHER file by ~50 years — fall back to the file's
            # mtime as a rough anchor instead
            try:
                origin = os.path.getmtime(p)
            except OSError:
                continue
        loaded.append((float(origin), trace))
    if not loaded:
        merged = {"traceEvents": [], "displayTimeUnit": "ms"}
    else:
        t0 = min(origin for origin, _ in loaded)
        evs: List[Dict] = []
        for origin, trace in loaded:
            shift_us = (origin - t0) * 1e6
            for ev in trace.get("traceEvents", []):
                ev = dict(ev)
                if ev.get("ph") != "M" and "ts" in ev:
                    ev["ts"] = float(ev["ts"]) + shift_us
                evs.append(ev)
        merged = {"traceEvents": evs, "displayTimeUnit": "ms",
                  "otherData": {"epoch_origin_s": t0}}
    if out_path:
        _write_json(out_path, merged)
    return merged


def merge_dir(directory: str, out_trace: str = "merged_trace.json",
              out_cluster: str = "cluster.json") -> Dict[str, Any]:
    """Fold a telemetry directory — ``telemetry_<role><rank>.json``
    final snapshots, ``flight_*.json`` corpses, and any
    ``trace_*.json`` profiler dumps — into:

      * ``merged_trace.json``: ONE chrome trace with a process row per
        role-rank and all clocks aligned on the earliest epoch
        timestamp seen;
      * ``cluster.json``: the merged counter view — per-role stats +
        step metrics, the cluster aggregate (:func:`aggregate_stats`),
        per-rank average step time, the straggler spread
        (slowest/fastest worker avg step time), retry + failover
        totals, and the flight-record index.

    Returns the cluster dict."""
    snaps: Dict[str, Dict[str, Any]] = {}
    flights: List[Dict[str, Any]] = []
    # files a SIGKILLed role left truncated/torn (or that vanished
    # between listdir and open) are MERGE GAPS: the merge folds the
    # survivors and names each gap in cluster.json instead of crashing
    gaps: List[Dict[str, str]] = []
    names = sorted(os.listdir(directory))
    for name in names:
        path = os.path.join(directory, name)
        if name.startswith("telemetry_") and name.endswith(".json"):
            try:
                snap = _load_snap(path)
            except (OSError, ValueError) as e:
                gaps.append({"file": name,
                             "error": str(e) or type(e).__name__})
                continue
            snaps[_role_key(snap)] = snap
        elif name.startswith("flight_") and name.endswith(".json"):
            try:
                fl = _load_snap(path)
            except (OSError, ValueError) as e:
                gaps.append({"file": name,
                             "error": str(e) or type(e).__name__})
                continue
            flights.append({
                "file": name,
                "role": fl.get("role"), "rank": fl.get("rank"),
                "reason": fl.get("reason"),
                "posthumous": bool(fl.get("posthumous")),
                "last_step": (fl.get("metrics") or {}).get("steps"),
                "last_round": (fl.get("stats") or {}).get(
                    "kvstore_round_last"),
            })
            # a corpse's events belong on the timeline too (dead nodes
            # wrote no final telemetry_ snapshot)
            key = _role_key(fl)
            if key not in snaps:
                snaps[key] = fl

    # per-role profiler chrome dumps (trace_*.json) join the timeline
    # too; the shared origin t0 must be the EARLIEST instant any
    # source knows about — telemetry records carry epoch timestamps
    # directly, profiler dumps carry an epoch origin for their ts=0
    prof_paths = [os.path.join(directory, n) for n in names
                  if n.startswith("trace_") and n.endswith(".json")]
    prof_merged = merge_traces(prof_paths) if prof_paths else None
    all_ts = [float(ev["ts"]) for s in snaps.values()
              for ev in s.get("events", []) if "ts" in ev]
    if prof_merged and prof_merged.get("traceEvents"):
        all_ts.append(float(prof_merged["otherData"]["epoch_origin_s"]))
    t0 = min(all_ts) if all_ts else time.time()
    trace_events: List[Dict] = []
    for snap in snaps.values():
        trace_events.extend(_events_to_chrome(snap, t0))
    if prof_merged and prof_merged.get("traceEvents"):
        shift_us = (float(prof_merged["otherData"]["epoch_origin_s"])
                    - t0) * 1e6
        for ev in prof_merged["traceEvents"]:
            if ev.get("ph") != "M" and "ts" in ev:
                ev["ts"] = float(ev["ts"]) + shift_us
            trace_events.append(ev)
    # mx.tracing: stitch the span records from every snapshot into
    # chrome-trace flow events by trace id (lazy import — tracing
    # imports telemetry at module level, not the other way around)
    span_evs = [ev for s in snaps.values()
                for ev in s.get("events", [])
                if ev.get("kind") == "span"]
    tracing_rollup = None
    if span_evs:
        from . import tracing as _tracing
        flows, tracing_rollup = _tracing.stitch(span_evs, t0)
        trace_events.extend(flows)
    merged = {"traceEvents": trace_events, "displayTimeUnit": "ms",
              "otherData": {"epoch_origin_s": t0}}
    _write_json(os.path.join(directory, out_trace), merged)

    per_rank_step = {}
    for key, snap in snaps.items():
        m = snap.get("metrics") or {}
        if m.get("steps"):
            per_rank_step[key] = m.get("step_time_avg_s", 0.0)
    worker_avgs = [v for k, v in per_rank_step.items()
                   if k.startswith("worker") and v > 0]
    aggregate = aggregate_stats(s.get("stats") for s in snaps.values())
    # compile rollup (mx.inspect counters): wall-clock seconds each
    # rank spent building XLA programs, and how many of those builds
    # were RE-compiles of an already-seen program (retrace blame)
    per_rank_compile = {
        k: round((s.get("stats") or {}).get("inspect_compile_wall_us", 0)
                 / 1e6, 3)
        for k, s in snaps.items()
        if (s.get("stats") or {}).get("inspect_compile_wall_us")}
    cluster = {
        "roles": {k: {"pid": s.get("pid"), "stats": s.get("stats", {}),
                      "metrics": s.get("metrics", {})}
                  for k, s in snaps.items()},
        "aggregate": aggregate,
        "gauge_stats": list(GAUGE_STATS),
        "per_rank_step_time_s": per_rank_step,
        "straggler_spread_s": (max(worker_avgs) - min(worker_avgs))
        if worker_avgs else 0.0,
        "retry_total": sum(v for k, v in aggregate.items()
                           if k.startswith("retry_attempts::")),
        "failover_total": aggregate.get("elastic_failover", 0),
        "per_rank_compile_s": per_rank_compile,
        "compile_total": aggregate.get("inspect_compiles", 0),
        "recompile_total": aggregate.get("inspect_recompiles", 0),
        # sharding rollup (mx.shard): cluster-wide per-collective
        # payload totals from the ZeRO-1 engine, the eager collectives
        # and reshard moves (docs/sharding.md byte conventions)
        "sharding": {k: aggregate.get(k, 0)
                     for k in ("allgather_bytes", "reduce_scatter_bytes",
                               "allreduce_bytes", "alltoall_bytes",
                               "ppermute_bytes", "reshard_bytes")},
        # training-health rollup (mx.health): per-rank anomaly counts
        # and the first non-finite blame, next to the compile/step rows
        "health": health_rollup(snaps),
        # performance rollup (mx.perf): per-rank MFU + dominant phase
        # from each role's metrics()["perf"] block; the worker MFU
        # spread is the straggler signal (one slow rank drags every
        # synchronous collective down to its speed)
        "perf": perf_rollup(snaps),
        # device-memory rollup (mx.hbm): per-rank used/peak/headroom
        # and which ranks have a live leak suspect — the fleet's
        # capacity picture next to its speed picture
        "hbm": hbm_rollup(snaps),
        # causal-tracing rollup (mx.tracing): trace/span totals, how
        # many traces crossed a process boundary, and the critical
        # path of the largest stitched traces
        "tracing": tracing_rollup,
        "flights": flights,
        # files that could not be merged (truncated by a SIGKILL,
        # torn, non-JSON): the survivors above are complete, and the
        # missing contribution is NAMED instead of silently absent
        "merge_gaps": gaps,
    }
    _write_json(os.path.join(directory, out_cluster), cluster)
    return cluster


# ---------------------------------------------------------------------------
# Speedometer-style callback (gluon loops)
# ---------------------------------------------------------------------------

class Speedometer(object):
    """Per-batch callable for gluon training loops that logs the LIVE
    telemetry metrics every ``frequent`` batches — the
    `mxtpu.callback.Speedometer` idiom, but fed by the always-on
    telemetry stream instead of its own clock, so the numbers it
    prints are the same ones ``kv.telemetry()`` aggregates::

        speedo = telemetry.Speedometer(frequent=50)
        for batch in loader:
            ...; trainer.step(bs)
            speedo()
    """

    def __init__(self, frequent: int = 50, logger=None):
        import logging

        self.frequent = max(1, int(frequent))
        self.logger = logger or logging.getLogger(__name__)
        self._count = 0

    def __call__(self, *_args) -> None:
        self._count += 1
        if self._count % self.frequent:
            return
        m = metrics()
        # mx.perf columns: MFU + dominant phase from metrics()["perf"]
        # — "-" when the observatory is disabled or has no sample yet
        p = m.get("perf") or {}
        mfu = p.get("mfu")
        self.logger.info(
            "telemetry: step %d\t%.1f samples/sec\tstep %.1f ms "
            "(avg %.1f ms)\tnonfinite %d\tmem watermark %.1f MB\t"
            "MFU %s\tphase %s",
            m["steps"], m["examples_per_sec"],
            m["step_time_last_s"] * 1e3, m["step_time_avg_s"] * 1e3,
            m["nonfinite_steps"],
            m["device_mem_watermark_bytes"] / 1e6,
            ("%.3f" % mfu) if mfu is not None else "-",
            p.get("dominant_phase") or "-")
