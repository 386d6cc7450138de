"""DataLoader (reference: `python/mxnet/gluon/data/dataloader.py:26-111`).

The reference forks worker processes that decode samples and ship them
back through POSIX shared memory.  TPU-native design note: the heavy
per-sample work (image decode/augment) belongs on host CPU threads while
the chip runs ahead asynchronously, so this DataLoader defaults to a
thread pool (`num_workers`) + a prefetch queue; batches land as
committed host arrays ready for a single device transfer.  (The C++ IO
pipeline in `src/` takes over the decode path as it lands.)

`thread_pool=False` switches to FORKED WORKER PROCESSES (the
reference's model): right when the per-sample transform is
python-heavy (GIL-bound) rather than decode-heavy.  Workers batchify
to NUMPY (never touching jax/the device) and the parent does the
single host->device conversion.  Measured crossover on this host
(tests/test_gluon_data.py, crossover timing print):
a ~1 ms pure-python transform per sample is already ~2x faster with
2 processes than 2 threads; byte-decode workloads favor threads.
"""
from __future__ import annotations

import multiprocessing
import os
import queue
import threading
import time
import traceback
from typing import Any, Callable, List, Optional

import numpy as np

from ...base import MXNetError
from ... import resilience as _res
from ...ndarray.ndarray import NDArray, array as nd_array
from .dataset import Dataset
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (reference default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        from ...ndarray import stack

        return stack(*data, axis=0)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(list(i)) for i in data]
    arr = np.asarray(data)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    return nd_array(arr)


def _holds_ndarray(sample):
    if isinstance(sample, (tuple, list)):
        return any(_holds_ndarray(s) for s in sample)
    return isinstance(sample, NDArray)


_NDARRAY_SAMPLE_MSG = (
    "process workers (thread_pool=False) need datasets that return "
    "numpy/python samples — NDArray samples would pull the device "
    "runtime into the forked worker, and the device belongs to the "
    "parent (one process per chip); use thread_pool=True (default) or "
    "return numpy from __getitem__")


def _np_batchify(data):
    """Worker-side batchify: pure numpy (workers must never initialize
    jax — the device belongs to the parent)."""
    if isinstance(data[0], NDArray):
        raise MXNetError(_NDARRAY_SAMPLE_MSG)
    if isinstance(data[0], tuple):
        return tuple(_np_batchify(list(i)) for i in zip(*data))
    arr = np.asarray(data)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    return arr


_WORKER_DATASET = None


def _worker_init(dataset):
    global _WORKER_DATASET
    _WORKER_DATASET = dataset


#: Sentinel tag a forked worker returns instead of raising: exceptions
#: must cross the pickle boundary with their ORIGINAL traceback intact
#: (pickling arbitrary exception objects can itself fail, which the
#: reference dataloader turns into a deadlocked iterator).
_ERR_TAG = "__mxtpu_worker_error__"


class _WorkerLost(Exception):
    """A pool worker died (SIGKILL/segfault) while holding a batch —
    its result will never arrive."""


def _worker_fn(args):
    idx_batch, batchify = args
    try:
        _res.maybe_fault("dataloader")
        samples = [_WORKER_DATASET[i] for i in idx_batch]
        return batchify(samples)
    except Exception as e:
        return (_ERR_TAG, type(e).__name__, str(e),
                traceback.format_exc())


def _pool_pids(pool):
    return {p.pid for p in getattr(pool, "_pool", [])}


def _await_async(pool, res, submit_pids, poll: float = 0.2,
                 grace: float = 2.0):
    """``res.get()`` that cannot hang forever: a worker that dies
    (SIGKILL/segfault) is silently replaced by the pool's maintenance
    thread and the task it held is dropped — the naive ``.get()`` then
    blocks for good.  A death is detected by comparing the pool's pid
    SET against ``submit_pids``, the set captured when this batch was
    SUBMITTED (replacement swaps a pid, observable even if the death
    happened while the parent was off yielding earlier batches); if
    the result is still pending ``grace`` seconds after a death is
    seen, it is declared lost (:class:`_WorkerLost`) so the caller
    resubmits."""
    death_seen = None
    while True:
        try:
            return res.get(poll)
        except multiprocessing.TimeoutError:
            procs = list(getattr(pool, "_pool", []))
            cur = {p.pid for p in procs}
            if cur != submit_pids or any(not p.is_alive() for p in procs):
                if death_seen is None:
                    death_seen = time.monotonic()
            if death_seen is not None and \
                    time.monotonic() - death_seen >= grace:
                if res.ready():  # arrived at the last moment
                    return res.get(0)
                raise _WorkerLost()


def _to_nd(batch):
    if isinstance(batch, tuple):
        return [_to_nd(b) for b in batch]
    if isinstance(batch, np.ndarray):
        return nd_array(batch)
    return batch


class DataLoader(object):
    def __init__(self, dataset: Dataset, batch_size=None, shuffle=False,
                 sampler=None, last_batch=None, batch_sampler=None,
                 batchify_fn=None, num_workers=0, pin_memory=False,
                 prefetch=None, thread_pool=True, seed=None):
        self._dataset = dataset
        self._seed = seed
        if batch_sampler is None:
            if batch_size is None:
                raise MXNetError("batch_size is required when batch_sampler "
                                 "is not given")
            if sampler is None:
                sampler = RandomSampler(len(dataset), seed=seed) if shuffle \
                    else SequentialSampler(len(dataset))
            elif shuffle:
                raise MXNetError("shuffle must be False with custom sampler")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise MXNetError("batch_size/shuffle/sampler/last_batch must "
                             "not be set when batch_sampler is given")
        self._sampler = sampler if sampler is not None else \
            getattr(batch_sampler, "_sampler", None)
        self._batch_sampler = batch_sampler
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._num_workers = max(0, num_workers)
        self._thread_pool = thread_pool
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        # position bookkeeping for mx.checkpoint: (epoch, batches
        # handed to the consumer this epoch) — see state()/set_state()
        self._epoch = 0
        self._pos_epoch = 0
        self._pos_batch = 0
        self._resume = None

    # -- checkpointable position (docs/checkpoint.md) ---------------------
    def state(self):
        """Current position as a JSON-able dict: ``epoch``, ``batch``
        (batches already handed out this epoch — the index the NEXT
        batch would have), and the shuffle ``seed``.  With a seeded
        sampler, `set_state` on a fresh loader re-enters the identical
        batch stream mid-epoch."""
        return {"epoch": int(self._pos_epoch),
                "batch": int(self._pos_batch),
                "seed": self._seed}

    def set_state(self, state) -> None:
        """Arm deterministic re-entry at a `state()` position: the next
        `__iter__` shuffles for that epoch (seeded sampler) and skips
        the first ``batch`` index-batches WITHOUT touching the dataset."""
        if state is None:
            return
        saved_seed = state.get("seed")
        if saved_seed is not None and self._seed is not None and \
                saved_seed != self._seed:
            raise MXNetError(
                "DataLoader.set_state: shuffle seed mismatch (saved %r, "
                "this loader %r) — the restored position would replay a "
                "different batch stream" % (saved_seed, self._seed))
        self._resume = (int(state.get("epoch", 0)),
                        int(state.get("batch", 0)))

    def _make_batch(self, indices):
        _res.maybe_fault("dataloader")
        return self._batchify_fn([self._dataset[i] for i in indices])

    def __iter__(self):
        # input-wait gauge (mx.health / docs/observability.md): time
        # from the consumer ASKING for the next batch (this generator
        # resuming) to the batch being ready — the host-input wait that
        # separates "pipeline-bound" from "device-bound" step time
        from ... import telemetry as _tel

        if self._resume is not None:
            epoch, skip = self._resume
            self._resume = None
        else:
            epoch, skip = self._epoch, 0
        if getattr(self._sampler, "seed", None) is not None:
            # loader is authoritative over the shuffle epoch so an
            # abandoned iterator or a restore can't desync the stream
            self._sampler.set_epoch(epoch)
        self._epoch = epoch
        self._pos_epoch = epoch
        self._pos_batch = skip
        it = self._iter_impl(skip)
        # MXTPU_PREFETCH_DEVICE=N:
        # a lookahead thread pulls the NEXT batch and completes its
        # host->device transfer while the consumer computes on the
        # current one, so the input_wait gauge below measures only
        # what the pipeline could NOT hide
        depth = int(os.environ.get("MXTPU_PREFETCH_DEVICE", "0") or 0)
        if depth > 0:
            it = self._device_prefetch_iter(it, depth)
        while True:
            # nesting-guarded scope: when this fetch itself drives an
            # inner DataIter (dataset backed by one), only THIS
            # outermost layer records — no double count
            try:
                with _tel.input_wait():
                    batch = next(it)
            except StopIteration:
                self._epoch = epoch + 1
                return
            self._pos_batch += 1
            yield batch

    @staticmethod
    def _force_device(batch):
        """Complete a batch's host->device transfer (NDArray creation
        dispatches ``device_put`` asynchronously; blocking HERE, on
        the prefetch thread, is the whole point — the consumer thread
        receives a device-resident, ready batch)."""
        if isinstance(batch, (list, tuple)):
            for b in batch:
                DataLoader._force_device(b)
        elif isinstance(batch, NDArray):
            batch.wait_to_read()
        return batch

    def _device_prefetch_iter(self, it, depth: int):
        """Async host->device prefetch: a daemon thread runs ``depth``
        batches ahead, batchifying AND device-transferring each, with a
        bounded queue for backpressure.  Errors cross over and re-raise
        in the consumer; an abandoned consumer unblocks the worker via
        the stop event (the queue put polls it)."""
        from ... import profiler as _prof

        out_q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        stop = threading.Event()
        _DONE = object()

        def worker():
            try:
                for batch in it:
                    self._force_device(batch)
                    _prof.inc_stat("dataloader_device_prefetch")
                    while not stop.is_set():
                        try:
                            out_q.put((batch, None), timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        return
                out_q.put((_DONE, None))
            except BaseException as e:  # surface in the consumer
                # The sentinel put must survive a full queue: dropping
                # it (the old `except queue.Full: pass`) left the
                # consumer blocked forever on `out_q.get()` — the error
                # path retries against the stop event exactly like the
                # normal path (tests/test_gluon_data.py regression).
                while not stop.is_set():
                    try:
                        out_q.put((_DONE, e), timeout=0.1)
                        break
                    except queue.Full:
                        continue

        t = threading.Thread(target=worker, daemon=True,
                             name="mxtpu-device-prefetch")
        t.start()
        try:
            while True:
                batch, err = out_q.get()
                if batch is _DONE:
                    if err is not None:
                        raise err
                    return
                yield batch
        finally:
            stop.set()

    def _iter_impl(self, skip: int = 0):
        if self._num_workers == 0:
            it = iter(self._batch_sampler)
            for _ in range(skip):  # resume re-entry: index-only skip
                if next(it, None) is None:
                    return
            for indices in it:
                # inline path: full retry policy on transient faults
                yield _res.run_with_retry(
                    "dataloader", lambda idx=indices: self._make_batch(idx))
            return
        if self._thread_pool:
            yield from self._threaded_iter(skip)
        else:
            yield from self._process_iter(skip)

    def _process_iter(self, skip: int = 0):
        """Forked worker processes (reference dataloader.py:26-111
        model): per-sample transforms run GIL-free; workers ship numpy
        batches back (pickle), the parent converts once per batch.
        Custom `batchify_fn` runs IN the worker and must be picklable
        and numpy-only; the default numpy batchify is swapped in for
        the NDArray one automatically.

        Resilience: a worker EXCEPTION comes back as a tagged tuple
        carrying the original traceback (never a deadlock), the batch
        is retried once in a fresh worker, and a second failure raises
        with that traceback attached.  A worker DEATH (SIGKILL /
        segfault — the pool silently loses the batch and the naive
        ``.get()`` hangs forever) is detected by polling worker
        liveness; the lost batch is resubmitted once to the
        auto-replenished pool."""
        batchify = self._batchify_fn
        if batchify is default_batchify_fn:
            batchify = _np_batchify
        ctx = multiprocessing.get_context("fork")
        batches = list(self._batch_sampler)[skip:]
        # the forked children inherit a parent that may hold the chip,
        # and must never touch JAX: read one sample HERE, where that is
        # safe, and refuse a dataset that hands out device arrays
        # before any worker exists (whatever batchify_fn is in use)
        if batches and batches[0] and \
                _holds_ndarray(self._dataset[batches[0][0]]):
            raise MXNetError(_NDARRAY_SAMPLE_MSG)
        pool = ctx.Pool(min(self._num_workers, max(1, len(batches))),
                        initializer=_worker_init,
                        initargs=(self._dataset,))
        # windowed submission: same backpressure contract as the
        # threaded path — at most max(prefetch, num_workers) batches
        # decoded ahead of the consumer
        window = max(self._prefetch, self._num_workers)

        def _submit(indices):
            # the pid set at submit time anchors death detection for
            # this batch (a worker may die while the parent is off
            # yielding earlier batches)
            return (indices,
                    pool.apply_async(_worker_fn, ((indices, batchify),)),
                    _pool_pids(pool))

        try:
            pending = []  # (indices, AsyncResult, submit-time pids)
            submit = 0
            while submit < len(batches) and len(pending) < window:
                pending.append(_submit(batches[submit]))
                submit += 1
            while pending:
                indices, res, pids = pending.pop(0)
                out = self._resolve_pooled(pool, batchify, indices, res,
                                           pids)
                if submit < len(batches):
                    pending.append(_submit(batches[submit]))
                    submit += 1
                yield _to_nd(out)
        finally:
            pool.terminate()
            pool.join()

    def _resolve_pooled(self, pool, batchify, indices, res, pids,
                        attempt=0):
        from ... import profiler as _prof

        try:
            out = _await_async(pool, res, pids)
        except _WorkerLost:
            if attempt >= 1:
                raise MXNetError(
                    "DataLoader worker process died twice while decoding "
                    "the same batch (indices %r) — giving up" % (indices,))
            _prof.inc_stat("dataloader_worker_respawn")
            retry = pool.apply_async(_worker_fn, ((indices, batchify),))
            return self._resolve_pooled(pool, batchify, indices, retry,
                                        _pool_pids(pool), attempt + 1)
        if isinstance(out, tuple) and len(out) == 4 and out[0] == _ERR_TAG:
            _, etype, emsg, tb = out
            if attempt >= 1:
                # fresh worker failed too: last resort is the parent
                # computing the batch itself under the full retry
                # policy; only then surface the ORIGINAL traceback
                try:
                    return _res.run_with_retry(
                        "dataloader", lambda: self._make_batch(indices))
                except Exception:
                    raise MXNetError(
                        "DataLoader worker raised %s: %s (retried in a "
                        "fresh worker and in the parent)\n"
                        "--- original worker traceback ---\n%s"
                        % (etype, emsg, tb))
            _prof.inc_stat("dataloader_worker_retry")
            retry = pool.apply_async(_worker_fn, ((indices, batchify),))
            return self._resolve_pooled(pool, batchify, indices, retry,
                                        _pool_pids(pool), attempt + 1)
        return out

    def _threaded_iter(self, skip: int = 0):
        """Thread-pool pipeline with bounded in-order prefetch."""
        batches = list(self._batch_sampler)[skip:]
        results: "queue.Queue" = queue.Queue()
        lock = threading.Lock()
        next_submit = [0]
        stop = threading.Event()
        # bound how far workers run ahead of the consumer
        budget = threading.Semaphore(max(self._prefetch, self._num_workers))

        def worker():
            while True:
                budget.acquire()
                if stop.is_set():
                    return
                with lock:
                    i = next_submit[0]
                    if i >= len(batches):
                        budget.release()
                        return
                    next_submit[0] += 1
                try:
                    out = self._make_batch(batches[i])
                    results.put((i, out, None))
                except Exception as e:  # propagate to consumer
                    results.put((i, None, e))

        n_threads = min(self._num_workers, max(1, len(batches)))
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        try:
            want = 0
            stash = {}
            got = 0
            while got < len(batches):
                while want not in stash:
                    i, out, err = results.get()
                    stash[i] = (out, err)
                out, err = stash.pop(want)
                if err is not None:
                    # retry the failed batch inline under the FULL
                    # retry policy (a single bare retry would lose to a
                    # second transient fault); persistent failure
                    # surfaces with the original worker error chained
                    from ... import profiler as _prof

                    _prof.inc_stat("dataloader_worker_retry")
                    try:
                        out = _res.run_with_retry(
                            "dataloader",
                            lambda w=want: self._make_batch(batches[w]))
                    except Exception:
                        raise MXNetError(
                            "DataLoader batch %d failed twice; original "
                            "worker error: %r" % (want, err)) from err
                yield out
                budget.release()  # consumer consumed: allow another ahead
                want += 1
                got += 1
        finally:
            # wake any blocked workers so they exit even if the consumer
            # abandoned the generator early or a batch raised
            stop.set()
            for _ in threads:
                budget.release()

    def __len__(self):
        return len(self._batch_sampler)
