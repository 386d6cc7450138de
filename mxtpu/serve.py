"""mx.serve — continuous-batching model server over the compile-once stack.

The "millions of users" front end (ROADMAP open item 1): everything
below it already exists — shape-bucketed dispatch + AOT warmup
(`mxtpu/compile_cache.py`), typed OOM forensics (`mxtpu/health.py`),
resilience chokepoints (`mxtpu/resilience.py`), SLO telemetry
(`mxtpu/telemetry.py`) — and this module is what drives those pieces
under live traffic.  Four layers, smallest first:

  * **Request/future plumbing** — :meth:`Server.submit` enqueues a
    request (one or more rows of one model's input) and returns a
    future; :meth:`Server.infer` is the blocking convenience.

  * **Continuous micro-batcher** — one batcher thread per model pops
    the queue and packs ragged in-flight requests into the pow2 (or
    ``mult:N``/``fixed:...``) bucket set, dispatching ONE compiled
    program per batch.  New requests are admitted at every bucket
    boundary — the batcher never waits for a "full" batch; it lingers
    at most ``MXTPU_SERVE_BATCH_WAIT_US`` when the queue runs dry
    below the cap, so an idle server stays at ~one-request latency
    while a loaded server rides full buckets.  Every bucket size was
    AOT-warmed at :meth:`Server.add_model`, so the steady state
    compiles nothing.

  * **Admission control + graceful degradation** — per-(model, tenant)
    queued-row caps shed excess load with the typed
    :class:`~mxtpu.base.RequestShedError` (reason ``queue_full`` /
    ``draining`` / ``timeout``) instead of letting queues grow without
    bound; dispatch runs under the ``serve`` resilience chokepoint
    (fault injection + backoff retry), and a typed
    :class:`~mxtpu.base.MemoryExhaustedError` SHRINKS the model's
    bucket cap to the next smaller warmed bucket and requeues the
    batch rather than failing requests — shed, shrink, retry, never
    crash the serve loop (an OOM already at the smallest bucket fails
    typed: there is nothing left to shrink).

  * **Replica frontend + failover client** — :class:`HttpFrontend`
    serves a JSON-over-HTTP predict API per replica
    (``tools/launch.py --serve-replicas N`` spawns the fleet);
    :class:`Client` round-robins over replicas and FAILS OVER on
    connection errors, recording ``serve_failover::<replica>``
    counters + ``failover`` telemetry events so a SIGKILLed replica
    mid-load completes with zero failed requests and a named corpse
    (`tools/check_serving.py` is the chaos guard).

SLO surface: per-model request-latency histograms
(`telemetry.Histogram`, p50/p95/p99) plus queue-depth / in-flight /
batch-occupancy gauges, all visible in ``mx.telemetry.metrics()``
under ``"serve"`` and ``"histograms"``.

See `docs/serving.md` for the architecture and the chaos workflow.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .base import (MemoryExhaustedError, MXNetError, RequestShedError,
                   getenv, getenv_int)
from . import compile_cache as _cc
from . import perf as _perf
from . import tracing as _tracing

__all__ = [
    "Server",
    "HttpFrontend",
    "Client",
    "serve_forever",
    "wait_ready",
]


def _max_batch_default() -> int:
    return max(1, getenv_int("MXTPU_SERVE_MAX_BATCH", 32))


def _queue_cap_default() -> int:
    return max(1, getenv_int("MXTPU_SERVE_QUEUE_CAP", 1024))


def _batch_wait_default() -> float:
    return max(0.0, getenv_int("MXTPU_SERVE_BATCH_WAIT_US", 2000) / 1e6)


def _timeout_default() -> float:
    val = getenv("MXTPU_SERVE_TIMEOUT", "30")
    return float(val or 30)


# every live Server in the process; the ONE "serve" metrics provider
# folds them all, so a second Server (a canary next to the production
# one) can neither silently replace the first in metrics() nor yank
# the survivor's gauges out of telemetry when it closes
_SERVERS: "weakref.WeakSet" = weakref.WeakSet()


def _fleet_metrics() -> Dict[str, Any]:
    servers = list(_SERVERS)
    if not servers:
        return {}
    if len(servers) == 1:
        return servers[0]._metrics()
    out: Dict[str, Any] = {"queue_depth": 0, "inflight": 0,
                           "batch_occupancy_pct": 0.0,
                           "draining": False, "models": {}}
    for s in servers:
        m = s._metrics()
        out["queue_depth"] += m["queue_depth"]
        out["inflight"] += m["inflight"]
        out["batch_occupancy_pct"] = max(out["batch_occupancy_pct"],
                                         m["batch_occupancy_pct"])
        out["draining"] = out["draining"] or m["draining"]
        out["models"].update(m["models"])
    return out


class _Future(object):
    """Result slot for one submitted request."""

    __slots__ = ("_ev", "_val", "_exc")

    def __init__(self):
        self._ev = threading.Event()
        self._val = None
        self._exc: Optional[BaseException] = None

    def _set_result(self, val) -> None:
        self._val = val
        self._ev.set()

    def _set_exception(self, exc: BaseException) -> None:
        self._exc = exc
        self._ev.set()

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None):
        """Block for the output (np.ndarray, or a tuple for
        multi-output models).  Raises what the server raised — a
        :class:`RequestShedError` for shed requests."""
        if not self._ev.wait(timeout):
            raise TimeoutError("serve request still pending after %ss"
                               % timeout)
        if self._exc is not None:
            raise self._exc
        return self._val


class _Request(object):
    __slots__ = ("x", "n", "tenant", "future", "t_enq", "deadline",
                 "trace", "t_pop")

    def __init__(self, x: np.ndarray, tenant: str, deadline: float,
                 trace=None):
        self.x = x
        self.n = int(x.shape[0])
        self.tenant = tenant
        self.future = _Future()
        self.t_enq = time.monotonic()
        self.deadline = deadline
        # mx.tracing context from the frontend's traceparent header
        # (None when the caller is untraced); t_pop marks when the
        # batcher popped it — the queue_wait/batch_linger boundary
        self.trace = trace
        self.t_pop = 0.0


class _ModelEntry(object):
    """One hosted model: its predict callable, bucket policy, dynamic
    batch cap (OOM-shrinkable), queue, and latency histogram."""

    def __init__(self, name: str, predict: Callable[[np.ndarray], Any],
                 dtype: str, sample_shape: Optional[Tuple[int, ...]],
                 max_batch: int, bucket_spec: str, queue_cap: int):
        from . import telemetry as _tel

        self.name = name
        self.predict = predict
        self.dtype = np.dtype(dtype)
        self.sample_shape = tuple(sample_shape) if sample_shape else None
        # the warmed signature set; the EFFECTIVE cap is the largest
        # bucket <= the requested cap, so every dispatch pads to a
        # warmed bucket and steady state never compiles (a cap like 20
        # under pow2 would otherwise clamp 17-row batches to an
        # unwarmed (20, ...) signature)
        self.buckets = _cc.bucket_set(int(max_batch), bucket_spec)
        self.max_batch = self.buckets[-1]
        self.bucket_spec = bucket_spec
        self.queue_cap = int(queue_cap)
        self.queue: collections.deque = collections.deque()
        self.queued_rows = 0
        self.tenant_rows: Dict[str, int] = {}
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self.inflight_rows = 0
        # full request latency (enqueue -> result), seconds
        self.hist = _tel.histogram("serve_latency_s::%s" % name)
        self.thread: Optional[threading.Thread] = None
        # the model's mx.inspect record (set by add_model when the
        # model exposes one) — the handle the mx.hbm capacity consults
        # use at add time and on the OOM shrink path
        self.hbm_rec = None


class Server(object):
    """In-process continuous-batching model server.

    ::

        srv = mx.serve.Server()
        srv.add_model("mlp", net, input_shape=(10,))   # AOT-warms buckets
        srv.start()
        out = srv.infer("mlp", np.random.rand(3, 10))  # (3, ...) rows

    Thread-safe: :meth:`submit` may be called from any number of
    frontend threads; each model has ONE batcher thread, so per-model
    dispatch is serialized (outputs are deterministic) while distinct
    models run concurrently.
    """

    def __init__(self, max_batch: Optional[int] = None,
                 queue_cap: Optional[int] = None,
                 batch_wait_s: Optional[float] = None,
                 request_timeout_s: Optional[float] = None,
                 bucket_spec: Optional[str] = None):
        self.max_batch = max_batch or _max_batch_default()
        self.queue_cap = queue_cap or _queue_cap_default()
        self.batch_wait_s = _batch_wait_default() \
            if batch_wait_s is None else float(batch_wait_s)
        self.request_timeout_s = _timeout_default() \
            if request_timeout_s is None else float(request_timeout_s)
        self.bucket_spec = bucket_spec or _cc.get_bucket_policy() or "pow2"
        _cc._parse_policy(self.bucket_spec)  # validate eagerly
        self._entries: Dict[str, _ModelEntry] = {}
        # RLock: the flight recorder's signal handler serializes
        # metrics() — which calls our provider — on whatever thread the
        # signal lands on; if that thread held this lock, a plain Lock
        # would deadlock the dump (same rationale as telemetry._lock)
        self._lock = threading.RLock()
        self._started = False
        self._draining = False
        self._stopped = False
        self._last_occupancy = 0.0
        from . import telemetry as _tel

        _SERVERS.add(self)
        _tel.register_metrics_provider("serve", _fleet_metrics)

    # -- model hosting -----------------------------------------------------

    def add_model(self, name: str, model: Any,
                  input_shape: Optional[Sequence[int]] = None,
                  dtype: str = "float32",
                  max_batch: Optional[int] = None,
                  warmup: bool = True) -> None:
        """Host ``model`` under ``name``.

        ``model`` is a hybridized gluon block (anything with
        ``warmup``/``__call__``) or a plain callable
        ``fn(np.ndarray[batch, ...]) -> np.ndarray`` (batch-major
        outputs).  ``input_shape`` is ONE sample's shape (no batch
        dim); with a block it enables AOT warmup of the full bucket
        set (:func:`compile_cache.bucket_set`), so the replica's
        steady state compiles nothing.  Call before :meth:`start` or
        while running (multi-tenant hosting adds models live)."""
        if self._stopped:
            raise MXNetError("server is stopped")
        cap = int(max_batch or self.max_batch)
        predict = self._as_predict(model, dtype)
        entry = _ModelEntry(name, predict, dtype,
                            input_shape, cap, self.bucket_spec,
                            self.queue_cap)
        buckets = entry.buckets  # effective cap = buckets[-1] <= cap
        if warmup and input_shape is not None and \
                hasattr(model, "warmup"):
            model.warmup([[(b,) + tuple(input_shape)] for b in buckets],
                         dtype=dtype)
        from . import profiler as _prof
        from . import telemetry as _tel

        # mx.hbm capacity consult: warmup just compiled (and analyzed)
        # the whole bucket ladder, so the per-program capacity model is
        # a dict fit away.  The prediction always lands in telemetry as
        # an advisory; ``MXTPU_HBM_PRESHRINK=1`` additionally trims the
        # cap to the largest bucket predicted to fit live headroom.
        # Best-effort by contract — this never fails add_model.
        try:
            rec = getattr(getattr(model, "_cached_op", None),
                          "_insp", None)
            if rec is not None:
                from . import hbm as _hbm

                entry.hbm_rec = rec
                fit = _hbm.max_batch(rec, kind="infer",
                                     buckets=list(buckets),
                                     analyze=False)
                if fit is not None:
                    _tel.record("serve", action="hbm_capacity",
                                model=name, fit_max_batch=fit,
                                headroom_bytes=_hbm.headroom())
                    if getenv_int("MXTPU_HBM_PRESHRINK", 0) and \
                            0 < fit < entry.max_batch:
                        entry.max_batch = fit
                        _prof.inc_stat("serve_hbm_preshrink")
        except Exception:
            pass

        with self._lock:
            if name in self._entries:
                raise MXNetError("model %r already hosted" % name)
            self._entries[name] = entry
            if self._started:
                self._start_entry(entry)
        _prof.inc_stat("serve_models")
        _tel.record("serve", action="add_model", model=name,
                    buckets=",".join(str(b) for b in buckets),
                    max_batch=entry.max_batch)

    @staticmethod
    def _as_predict(model: Any, dtype: str) -> Callable[[np.ndarray], Any]:
        if not callable(model):
            raise MXNetError("model must be callable, got %r"
                             % type(model))
        if not hasattr(model, "hybridize") and \
                not hasattr(model, "warmup"):
            return model  # plain fn(np) -> np
        from . import ndarray as _nd

        def predict(x: np.ndarray):
            out = model(_nd.array(x, dtype=dtype))
            if isinstance(out, (list, tuple)):
                return tuple(o.asnumpy() for o in out)
            return out.asnumpy()
        return predict

    def models(self) -> List[str]:
        with self._lock:
            return sorted(self._entries)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "Server":
        with self._lock:
            if self._started:
                return self
            self._started = True
            for entry in self._entries.values():
                self._start_entry(entry)
        return self

    def _start_entry(self, entry: _ModelEntry) -> None:
        t = threading.Thread(target=self._batcher_loop, args=(entry,),
                             name="mxserve-%s" % entry.name, daemon=True)
        entry.thread = t
        t.start()

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful shutdown (the SIGTERM path): stop admitting —
        further :meth:`submit` sheds with reason ``draining`` — finish
        everything already queued/in flight, then stop the batcher
        threads.  Returns True when fully drained within ``timeout``.
        Idempotent."""
        from . import telemetry as _tel

        with self._lock:
            first = not self._draining
            self._draining = True
            entries = list(self._entries.values())
        if first:
            _tel.record("serve", action="drain")
        deadline = time.monotonic() + max(0.0, timeout)
        ok = True
        for entry in entries:
            with entry.cond:
                entry.cond.notify_all()
            t = entry.thread
            if t is not None:
                t.join(max(0.0, deadline - time.monotonic()))
                ok = ok and not t.is_alive()
        self._stopped = True
        return ok

    def close(self) -> None:
        """Drain (briefly); the "serve" metrics provider stays
        registered until the LAST live server closes."""
        from . import telemetry as _tel

        self.drain(timeout=5.0)
        _SERVERS.discard(self)
        if not _SERVERS:
            _tel.unregister_metrics_provider("serve")

    @property
    def draining(self) -> bool:
        return self._draining

    # -- submission / admission control ------------------------------------

    def submit(self, model: str, x, tenant: str = "default",
               timeout: Optional[float] = None, trace=None) -> _Future:
        """Enqueue rows for ``model`` and return the future.  ``x`` is
        one sample (``sample_shape``) or a batch of rows (leading
        batch dim).  Admission control runs HERE, on the caller's
        thread: a full per-tenant queue or a draining server RAISES
        the typed :class:`RequestShedError` synchronously (immediate
        backpressure — the caller never holds a future for work that
        was never admitted); by the time work reaches the batcher it
        is admitted, and only a deadline expiring in-queue sheds
        asynchronously through the future."""
        from . import profiler as _prof

        entry = self._entries.get(model)
        if entry is None:
            raise MXNetError("unknown model %r (hosted: %s)"
                             % (model, self.models()))
        if not self._started:
            # admitting with no batcher thread would orphan the
            # future: it hangs until its timeout instead of shedding
            raise MXNetError("server not started — call start() (or "
                             "HttpFrontend.start()) before submit()")
        x = np.ascontiguousarray(x, dtype=entry.dtype)
        if entry.sample_shape is not None and \
                x.shape == entry.sample_shape:
            x = x[None]  # one bare sample -> a 1-row batch
        if x.ndim == 0 or x.shape[0] < 1:
            raise MXNetError("request needs at least one row")
        if entry.sample_shape is not None and \
                tuple(x.shape[1:]) != entry.sample_shape:
            raise MXNetError(
                "model %r expects sample shape %s, got rows of %s"
                % (model, entry.sample_shape, tuple(x.shape[1:])))
        budget = self.request_timeout_s if timeout is None else timeout
        req = _Request(x, tenant, time.monotonic() + budget,
                       trace=trace)
        with entry.cond:
            # checked UNDER the batcher's cond: the batcher exits its
            # loop holding this lock (queue empty + draining), so a
            # check outside it could append after the last pop — an
            # orphaned future that times out instead of shedding typed
            if self._draining or self._stopped:
                raise self._shed(entry, req, "draining", deliver=False)
            have = entry.tenant_rows.get(tenant, 0)
            if have + req.n > entry.queue_cap:
                raise self._shed(entry, req, "queue_full",
                                 deliver=False)
            entry.queue.append(req)
            entry.queued_rows += req.n
            entry.tenant_rows[tenant] = have + req.n
            entry.cond.notify()
        _prof.inc_stat("serve_submitted")
        return req.future

    def infer(self, model: str, x, tenant: str = "default",
              timeout: Optional[float] = None, trace=None):
        """Blocking :meth:`submit` — returns the output rows."""
        budget = self.request_timeout_s if timeout is None else timeout
        # result() gets slack over the queue deadline: an admitted
        # request that expires in-queue is shed by the BATCHER with
        # the typed error, which beats an opaque client TimeoutError
        return self.submit(model, x, tenant, timeout, trace=trace) \
            .result(budget + 5.0)

    def _shed(self, entry: _ModelEntry, req: _Request, reason: str,
              deliver: bool = True) -> RequestShedError:
        """Account one shed.  ``deliver=True`` fails the future (the
        batcher's in-queue timeout path); ``deliver=False`` returns
        the error for the submitter to raise synchronously."""
        from . import profiler as _prof
        from . import telemetry as _tel

        _prof.inc_stat("serve_shed")
        _prof.inc_stat("serve_shed::%s" % reason)
        _tel.record("serve", action="shed", model=entry.name,
                    tenant=req.tenant, reason=reason, rows=req.n)
        err = RequestShedError(
            "request (%d rows, tenant %r, model %r) shed: %s"
            % (req.n, req.tenant, entry.name, reason), reason=reason)
        if deliver:
            req.future._set_exception(err)
        return err

    # -- the micro-batcher -------------------------------------------------

    def _pop_admitted(self, entry: _ModelEntry,
                      fit: Optional[int] = None) -> Optional[_Request]:
        """Pop the queue head (caller holds entry.lock), shedding
        requests whose deadline expired while queued.  With ``fit``,
        a LIVE head wider than ``fit`` rows is left in place (it
        starts the NEXT bucket) and None is returned: the fit check
        must run AFTER expiry sheds — a caller-side check on a head
        that then gets shed would admit its unchecked successor and
        pack the batch past the cap (an unwarmed raw dispatch)."""
        while entry.queue:
            req = entry.queue[0]
            expired = time.monotonic() > req.deadline
            if not expired and fit is not None and req.n > fit:
                return None
            entry.queue.popleft()
            entry.queued_rows -= req.n
            entry.tenant_rows[req.tenant] = \
                entry.tenant_rows.get(req.tenant, 0) - req.n
            if expired:
                self._shed(entry, req, "timeout")
                continue
            req.t_pop = time.monotonic()
            return req
        return None

    def _batcher_loop(self, entry: _ModelEntry) -> None:
        """One thread per model.  CONTINUOUS batching: re-admit from
        the queue at every bucket boundary; linger at most
        ``batch_wait_s`` when below the cap with an empty queue."""
        while True:
            with entry.cond:
                while not entry.queue and not self._draining:
                    entry.cond.wait(0.1)
                if not entry.queue and self._draining:
                    return
                first = self._pop_admitted(entry)
            if first is None:
                continue
            batch = [first]
            rows = first.n
            deadline = time.monotonic() + self.batch_wait_s
            while rows < entry.max_batch:
                with entry.cond:
                    if not entry.queue:
                        if self._draining:
                            break
                        wait = deadline - time.monotonic()
                        if wait <= 0:
                            break
                        entry.cond.wait(wait)
                        if not entry.queue:
                            continue  # re-check deadline
                    if entry.queue[0].n + rows > entry.max_batch:
                        break  # head starts the NEXT bucket
                    nxt = self._pop_admitted(
                        entry, fit=entry.max_batch - rows)
                if nxt is not None:
                    batch.append(nxt)
                    rows += nxt.n
                # nxt None: expiry sheds emptied the queue (loop waits)
                # or exposed a head too wide for the remaining fit
                # (the head-fits check above breaks next iteration)
            self._dispatch(entry, batch, rows)

    def _dispatch(self, entry: _ModelEntry, batch: List[_Request],
                  rows: int) -> None:
        """Pack -> pad to bucket -> ONE compiled program -> slice.
        Never raises: errors land in the request futures, OOM shrinks
        the bucket cap and requeues."""
        from . import profiler as _prof
        from . import resilience as _res
        from . import telemetry as _tel

        xs = batch[0].x if len(batch) == 1 else \
            np.concatenate([r.x for r in batch], axis=0)
        bucket = _cc.bucket_batch(rows, entry.bucket_spec)
        if bucket > entry.max_batch:
            # only reachable for a single overwide request (the
            # batcher never packs past the cap, and the cap is itself
            # a warmed bucket, so bucket_batch(rows<=cap) <= cap):
            # dispatch it raw at its own width
            bucket = entry.max_batch
        if bucket > rows:
            pad = np.zeros((bucket - rows,) + xs.shape[1:],
                           dtype=xs.dtype)
            xs = np.concatenate([xs, pad], axis=0)
        with entry.lock:
            entry.inflight_rows = rows
        _prof.set_stat("serve_inflight", self._inflight_rows())
        # phase attribution for the batcher: predict() is synchronous
        # (numpy out), so host_dispatch here IS the full dispatch wall;
        # the per-program device split comes from the CachedOp hook
        # underneath
        pt0 = _perf.begin()
        t_disp = time.monotonic()
        try:
            out = _res.guarded("serve", entry.predict, xs)
        except (MemoryExhaustedError, MemoryError) as e:
            self._degrade(entry, batch, bucket, e)
            return
        except BaseException as e:
            _prof.inc_stat("serve_errors")
            _tel.record("serve", action="error", model=entry.name,
                        error=type(e).__name__, detail=str(e)[:200])
            for req in batch:
                req.future._set_exception(e)
            return
        finally:
            with entry.lock:
                entry.inflight_rows = 0
            _prof.set_stat("serve_inflight", self._inflight_rows())
        _perf.end("serve:%s" % entry.name, "serve", pt0)
        self._fulfill(entry, batch, rows, bucket, out, t_disp)

    def _fulfill(self, entry: _ModelEntry, batch: List[_Request],
                 rows: int, bucket: int, out: Any,
                 t_disp: float = 0.0) -> None:
        from . import profiler as _prof

        outs = out if isinstance(out, tuple) else (out,)
        for o in outs:
            lead = getattr(o, "shape", (None,))[0]
            if lead not in (rows, bucket):
                err = MXNetError(
                    "model %r output leading dim %r is neither the "
                    "packed rows (%d) nor the bucket (%d) — serve "
                    "needs batch-major outputs" % (entry.name, lead,
                                                   rows, bucket))
                for req in batch:
                    req.future._set_exception(err)
                _prof.inc_stat("serve_errors")
                return
        now = time.monotonic()
        off = 0
        for req in batch:
            sliced = tuple(o[off:off + req.n] for o in outs)
            req.future._set_result(
                sliced if isinstance(out, tuple) else sliced[0])
            off += req.n
            lat = now - req.t_enq
            entry.hist.record(lat)
            # mx.tracing: the replica-side span tree — head-sampled,
            # or RETRO-kept when the request beat this model's rolling
            # p95 (the slow tail is always attributable); the segments
            # end at their true instants via `ago`
            if req.trace is not None and (
                    req.trace.sampled or _tracing.slow_keep(
                        "serve_latency_s::%s" % entry.name,
                        entry.hist, lat)):
                _tracing.note_exemplar(
                    "serve_latency_s::%s" % entry.name,
                    req.trace.trace_id, lat)
                t_pop = req.t_pop or now
                _tracing.record_span(
                    req.trace, "queue_wait",
                    max(0.0, t_pop - req.t_enq), ago=now - t_pop,
                    model=entry.name)
                if t_disp:
                    _tracing.record_span(
                        req.trace, "batch_linger",
                        max(0.0, t_disp - t_pop), ago=now - t_disp,
                        model=entry.name)
                    _tracing.record_span(
                        req.trace, "device", max(0.0, now - t_disp),
                        model=entry.name, rows=rows, bucket=bucket)
        # an overwide single request dispatches raw (rows > bucket):
        # its effective width is rows, not the cap — never report >100%
        occupancy = 100.0 * rows / max(1, bucket, rows)
        self._last_occupancy = occupancy
        _prof.inc_stat("serve_batches")
        _prof.inc_stat("serve_rows", rows)
        _prof.inc_stat("serve_requests", len(batch))
        _prof.set_stat("serve_batch_occupancy_pct", int(occupancy))
        _prof.set_stat("serve_queue_depth", self._queue_depth())
        _prof.set_stat("serve_max_batch", entry.max_batch)

    def _degrade(self, entry: _ModelEntry, batch: List[_Request],
                 bucket: int, exc: BaseException) -> None:
        """The OOM path: shrink the model's bucket cap to the next
        smaller WARMED bucket (the NEXT dispatch packs/pads smaller —
        and compiles nothing), requeue the batch at the front, and
        keep serving.  A request wider than the shrunken cap — or an
        OOM already at the smallest bucket, where no shrink exists —
        fails with the original typed error: requeueing it would just
        redispatch the same doomed batch in a busy loop until its
        queue deadline shed it as an opaque ``timeout``."""
        from . import profiler as _prof
        from . import telemetry as _tel

        smaller = [b for b in entry.buckets if b < bucket]
        target = smaller[-1] if smaller else 0
        # mx.hbm consult: when the census can predict what actually
        # fits the live headroom, jump straight to that bucket instead
        # of stepping one rung and OOMing again on the next dispatch.
        # Reactive path: analyze=False — never compiles here.
        if smaller and entry.hbm_rec is not None:
            try:
                from . import hbm as _hbm

                fit = _hbm.max_batch(entry.hbm_rec, kind="infer",
                                     buckets=smaller, analyze=False)
                if fit is not None and 0 < fit < target:
                    target = fit
            except Exception:
                pass
        with entry.cond:
            if smaller:
                entry.max_batch = min(entry.max_batch, target)
            requeue = []
            for req in batch:
                if not smaller or req.n > entry.max_batch:
                    req.future._set_exception(exc)
                    _prof.inc_stat("serve_oom_failed")
                else:
                    requeue.append(req)
            for req in reversed(requeue):
                entry.queue.appendleft(req)
                entry.queued_rows += req.n
                entry.tenant_rows[req.tenant] = \
                    entry.tenant_rows.get(req.tenant, 0) + req.n
            entry.cond.notify()
        if smaller:
            _prof.inc_stat("serve_oom_shrink")
            _tel.record("serve", action="oom_shrink", model=entry.name,
                        bucket=bucket, new_max_batch=entry.max_batch,
                        error=type(exc).__name__)
        else:
            # no shrink happened — counting this as one would read as
            # graceful degradation in the rollups when every request
            # in the batch in fact failed
            _tel.record("serve", action="oom_floor", model=entry.name,
                        bucket=bucket, error=type(exc).__name__)

    # -- observability -----------------------------------------------------

    def _queue_depth(self) -> int:
        with self._lock:
            entries = list(self._entries.values())
        return sum(e.queued_rows for e in entries)

    def _inflight_rows(self) -> int:
        with self._lock:
            entries = list(self._entries.values())
        return sum(e.inflight_rows for e in entries)

    def _metrics(self) -> Dict[str, Any]:
        """The ``metrics()["serve"]`` block (registered provider)."""
        with self._lock:
            entries = dict(self._entries)
        per_model = {}
        for name, e in entries.items():
            snap = e.hist.snapshot()
            per_model[name] = {
                "queued_rows": e.queued_rows,
                "inflight_rows": e.inflight_rows,
                "max_batch": e.max_batch,
                "latency_p50_s": snap["p50"],
                "latency_p95_s": snap["p95"],
                "latency_p99_s": snap["p99"],
                "requests": snap["count"],
            }
        return {
            "queue_depth": sum(e.queued_rows for e in entries.values()),
            "inflight": sum(e.inflight_rows for e in entries.values()),
            "batch_occupancy_pct": self._last_occupancy,
            "draining": self._draining,
            "models": per_model,
        }


# ---------------------------------------------------------------------------
# HTTP replica frontend
# ---------------------------------------------------------------------------

class HttpFrontend(object):
    """JSON-over-HTTP frontend for one :class:`Server` replica.

    Endpoints::

        POST /v1/<model>:predict   {"data": [[...]], "tenant": "t"}
          -> 200 {"output": [...], "replica": <rank>, "rows": n}
          -> 503 {"error": ..., "shed": true, "reason": ...}   (shed)
          -> 404 unknown model, 400 bad payload, 500 model error
        GET  /metrics   -> mx.telemetry.metrics() as JSON, or —
          content-negotiated via the Accept header
          (``application/openmetrics-text`` / ``text/plain``, what a
          Prometheus scraper sends) — the `mx.obs` OpenMetrics text
          exposition, so ONE scrape config covers serve replicas and
          training roles identically
        GET  /healthz   -> {"ok": true, "replica": <rank>, "models": [...]}

    ``port=0`` binds an ephemeral port (read it back from ``.port``).
    A threading HTTP server: one OS thread per in-flight request, all
    funneling into the server's per-model batcher — exactly the
    many-frontends-one-batcher shape the CachedOp thread-safety test
    covers.
    """

    def __init__(self, server: Server, port: Optional[int] = None,
                 host: str = "127.0.0.1"):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        self.server = server
        self.rank = getenv_int("MXTPU_SERVE_RANK", 0)
        if port is None:
            port = getenv_int("MXTPU_SERVE_PORT", 8080)
        srv = self.server
        rank = self.rank

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                pass

            def _reply(self, code: int, payload: Dict[str, Any]):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                from . import obs as _obs
                from . import telemetry as _tel

                if self.path == "/healthz":
                    self._reply(200, {"ok": not srv.draining,
                                      "replica": rank,
                                      "models": srv.models()})
                elif self.path == "/metrics":
                    # content negotiation: a Prometheus scraper asks
                    # for openmetrics-text/text-plain and gets the
                    # mx.obs exposition (same families as every
                    # training role's endpoint); the JSON default
                    # keeps the existing dashboards parsing
                    accept = self.headers.get("Accept", "") or ""
                    if "openmetrics" in accept or "text/plain" in accept:
                        body = _obs.openmetrics().encode()
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         _obs.CONTENT_TYPE)
                        self.send_header("Content-Length",
                                         str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                    else:
                        self._reply(200,
                                    _tel._json_safe(_tel.metrics()))
                else:
                    self._reply(404, {"error": "no such path"})

            def do_POST(self):
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    model = req.get("model")
                    if self.path.startswith("/v1/") and \
                            self.path.endswith(":predict"):
                        model = self.path[len("/v1/"):-len(":predict")]
                    if not model or model not in srv.models():
                        self._reply(404, {"error": "unknown model %r"
                                          % model})
                        return
                    data = np.asarray(req["data"])
                except Exception as e:
                    self._reply(400, {"error": "bad request: %s" % e})
                    return
                # mx.tracing: continue the caller's trace (W3C
                # traceparent header) through the batcher; malformed
                # or absent headers mean an untraced request
                trc = _tracing.parse(self.headers.get("traceparent"))
                try:
                    out = srv.infer(model, data,
                                    tenant=req.get("tenant", "default"),
                                    trace=trc)
                except RequestShedError as e:
                    self._reply(503, {"error": str(e), "shed": True,
                                      "reason": e.reason,
                                      "replica": rank})
                    return
                except Exception as e:
                    self._reply(500, {"error": "%s: %s"
                                      % (type(e).__name__, e)})
                    return
                outs = out if isinstance(out, tuple) else (out,)
                reply = {
                    "output": outs[0].tolist() if len(outs) == 1
                    else [o.tolist() for o in outs],
                    "replica": rank, "rows": int(outs[0].shape[0])}
                if trc is not None:
                    reply["trace"] = trc.trace_id
                self._reply(200, reply)

        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "HttpFrontend":
        self.server.start()
        t = threading.Thread(target=self._httpd.serve_forever,
                             name="mxserve-http", daemon=True)
        self._thread = t
        t.start()
        return self

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(5.0)


def serve_forever(build_models: Callable[[Server], None],
                  port: Optional[int] = None,
                  ready_file: Optional[str] = None) -> None:
    """Run ONE replica until SIGTERM, then drain and exit — the body a
    ``launch.py --serve-replicas N`` child runs.

    ``build_models(server)`` registers (and warms) the replica's
    models; the replica then serves HTTP on ``port`` (default
    ``MXTPU_SERVE_PORT``).  Identity: role ``serve``, rank
    ``MXTPU_SERVE_RANK`` — telemetry snapshots/flight records merge
    per replica.  SIGTERM stops admission (sheds with ``draining``),
    finishes queued work, flushes telemetry, exits 0; SIGKILL is the
    chaos case — the CLIENT's failover keeps the fleet's zero-failed
    contract (`tools/check_serving.py`)."""
    import signal

    from . import resilience as _res
    from . import telemetry as _tel

    rank = getenv_int("MXTPU_SERVE_RANK", 0)
    _tel.set_identity(role="serve", rank=rank)
    _tel.install_flight_recorder()
    from . import obs as _obs

    _obs.ensure_started()  # the replica's own OpenMetrics endpoint +
    # sampler (queue depth / occupancy / SLO time series), next to the
    # frontend's content-negotiated /metrics
    server = Server()
    build_models(server)
    front = HttpFrontend(server, port=port).start()
    done = threading.Event()
    # forward=False: SIGTERM means DRAIN, not die — the previous
    # disposition (flight dump + terminate) must not run, the replica
    # finishes admitted work and exits 0 below
    _res.install_preemption_hook(done.set, forward=False)
    signal.signal(signal.SIGINT, lambda *a: done.set())
    if ready_file:
        with open(ready_file, "w") as f:
            f.write(str(front.port))
    done.wait()
    server.drain()
    front.close()
    _tel.flush()


# ---------------------------------------------------------------------------
# Failover client
# ---------------------------------------------------------------------------

class Client(object):
    """Closed-loop HTTP client over a replica fleet with failover.

    Sticky round-robin: requests go to the current replica until it
    FAILS (connection refused/reset/timeout, a response torn mid-body
    by a dying replica, or a 5xx that is not a shed), then the client
    moves on to the next replica and REPLAYS
    the request — inference is pure, so replay is safe, and a SIGKILLed
    replica mid-request costs one retry, not one failed request.  Each
    failover ticks ``serve_failover::serve<rank>`` (naming the replica
    given up on) and records a ``failover`` telemetry event, which is
    how the chaos guard's telemetry rollup names the dead replica.

    A 503 shed is NOT a failover: the replica is alive and protecting
    its SLO — the typed :class:`RequestShedError` propagates so the
    caller can back off.
    """

    def __init__(self, endpoints: Sequence[str],
                 timeout: float = 30.0, rounds: int = 3):
        if not endpoints:
            raise MXNetError("need at least one endpoint")
        self.endpoints = ["http://" + e if "://" not in e else e
                          for e in endpoints]
        self.timeout = float(timeout)
        self.rounds = max(1, int(rounds))
        self._cur = 0
        self._lock = threading.Lock()

    def _post(self, url: str, payload: Dict[str, Any],
              trace=None) -> Dict[str, Any]:
        import urllib.request

        headers = {"Content-Type": "application/json"}
        if trace is not None:
            headers["traceparent"] = trace.traceparent()
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(), headers=headers)
        with urllib.request.urlopen(req, timeout=self.timeout) as r:
            return json.loads(r.read())

    def predict(self, model: str, x, tenant: str = "default"):
        """POST one request, failing over across replicas.  Returns
        the output rows as np.ndarray.  Raises
        :class:`RequestShedError` on a shed, ``ConnectionError`` only
        after every replica failed ``rounds`` times."""
        import http.client
        import urllib.error

        from . import profiler as _prof
        from . import telemetry as _tel

        payload = {"data": np.asarray(x).tolist(), "tenant": tenant}
        # mx.tracing: ONE context for the whole call — a failover
        # replay stamps the ORIGINAL trace id, so one user request is
        # one trace fleet-wide no matter how many replicas it crossed
        trc = _tracing.start_request()
        t_req = time.monotonic()
        with self._lock:
            start = self._cur
        n = len(self.endpoints)
        last_err: Optional[Exception] = None
        for attempt in range(self.rounds * n):
            idx = (start + attempt) % n
            url = "%s/v1/%s:predict" % (self.endpoints[idx], model)
            try:
                out = self._post(url, payload, trace=trc)
                with self._lock:
                    self._cur = idx  # stickiness: stay on a live one
                _tracing.finish_request(
                    trc, time.monotonic() - t_req, name="client",
                    model=model, replica=out.get("replica"))
                return np.asarray(out["output"])
            except urllib.error.HTTPError as e:
                detail = {}
                try:
                    detail = json.loads(e.read())
                except Exception:
                    pass
                if e.code == 503 and detail.get("shed"):
                    raise RequestShedError(
                        detail.get("error", "shed"),
                        reason=detail.get("reason", "overload"))
                if e.code < 500:
                    # deterministic client error (404 unknown model,
                    # 400 bad payload): every replica would answer the
                    # same — surface it, don't burn rounds of replays
                    # or tick failover counters against live replicas
                    raise
                last_err = e
            except (urllib.error.URLError, ConnectionError, OSError,
                    TimeoutError,
                    # a SIGKILL mid-response tears the body after the
                    # headers: http.client raises IncompleteRead (an
                    # HTTPException, NOT an OSError) — replay it too
                    http.client.HTTPException) as e:
                last_err = e
            # this replica failed us: name it and move on (the trace
            # id on the event ties the failover to the SAME trace the
            # replay continues)
            _prof.inc_stat("serve_failover::serve%d" % idx)
            _tel.record("failover", site="serve",
                        replica="serve%d" % idx,
                        to="serve%d" % ((idx + 1) % n),
                        error=type(last_err).__name__,
                        trace=trc.trace_id if trc is not None
                        else None)
            if attempt + 1 >= n:  # every replica seen at least once:
                time.sleep(0.05 * (attempt // n + 1))  # back off a bit
        raise ConnectionError(
            "all %d replica(s) failed %d rounds (last: %s)"
            % (n, self.rounds, last_err))


def wait_ready(endpoints: Sequence[str], timeout: float = 60.0,
               expect_models: Sequence[str] = ()) -> bool:
    """Poll every replica's ``/healthz`` until all are up (and host
    ``expect_models``) or ``timeout`` passes."""
    import urllib.request

    eps = ["http://" + e if "://" not in e else e for e in endpoints]
    deadline = time.monotonic() + timeout
    pending = set(eps)
    while pending and time.monotonic() < deadline:
        for ep in sorted(pending):
            try:
                with urllib.request.urlopen(ep + "/healthz",
                                            timeout=2) as r:
                    h = json.loads(r.read())
                if h.get("ok") and set(expect_models) <= \
                        set(h.get("models", [])):
                    pending.discard(ep)
            except Exception:
                pass
        if pending:
            time.sleep(0.1)
    return not pending
