"""KVStore — key-value store for gradient aggregation / parameter sync.

TPU-native re-design of the reference KVStore stack
(`include/mxnet/kvstore.h:59-439`, `src/kvstore/kvstore.cc:40-72`,
`src/kvstore/kvstore_local.h:173-275`, `src/kvstore/comm.h`,
`src/kvstore/kvstore_nccl.h`, `src/kvstore/kvstore_dist.h`).

Backends:
  * ``local`` — reduce on host-ordered device merge (the analog of
    CommCPU, `comm.h:103`): values are summed into a merge buffer via one
    fused XLA executable.
  * ``device`` / ``nccl`` — on-device merge + broadcast (the analog of
    CommDevice GPU P2P merge `comm.h:451` and the NCCL ring
    `kvstore_nccl.h:62`): device-to-device transfers ride ICI, the sum is
    one jitted executable on the merge device.
  * ``tpu`` — the north-star backend (SURVEY.md): push is an XLA
    all-reduce (`jax.lax.psum` under shard_map) over the data axis of
    the active `mxtpu.parallel` mesh or, with none, over the devices
    the pushed values live on.
  * ``dist_sync`` / ``dist_device_sync`` / ``dist_async`` — multi-process
    parameter server over TCP (`mxtpu/_ps.py`), the analog of the ps-lite
    path (`kvstore_dist.h:44`, `kvstore_dist_server.h:155`).  Roles are
    read from MXTPU_ROLE / DMLC_ROLE env (bootstrapped by
    `tools/launch.py` like the reference's dmlc-tracker).

Semantics follow the reference exactly: ``push`` reduces a list of
per-device values into a merge buffer; with an updater set the updater
mutates the stored weight, otherwise the merged value replaces the
store; ``pull`` broadcasts the stored value into the outputs
(`kvstore_local.h:173-275`).
"""
from __future__ import annotations

import pickle
import time as _time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .base import KVStoreTimeoutError, MXNetError, getenv
from .ndarray.ndarray import NDArray, zeros
from . import resilience as _res
from . import tracing as _tracing

__all__ = ["KVStore", "KVStoreTimeoutError", "create"]


def _kvstore_timeout() -> Optional[float]:
    """MXTPU_KVSTORE_TIMEOUT: seconds a dist push/pull waits for the
    server before raising KVStoreTimeoutError (default 600; <= 0 waits
    forever — the pre-resilience behavior)."""
    val = getenv("MXTPU_KVSTORE_TIMEOUT")
    t = 600.0 if val in (None, "") else float(val)
    return t if t > 0 else None


def _wire_deadline() -> float:
    """Retry budget for dist wire ops: a SINGLE attempt may legitimately
    take MXTPU_KVSTORE_TIMEOUT, so the default MXTPU_RETRY_TIMEOUT (60 s)
    would expire before the first retry ever ran — give the guarded call
    room for at least two full waits plus backoff."""
    t = _kvstore_timeout()
    return 0.0 if t is None else max(2.5 * t, 60.0)


def _key_list(key):
    return key if isinstance(key, (list, tuple)) else [key]


def _val_list(val):
    if isinstance(val, NDArray):
        return [val]
    if isinstance(val, (list, tuple)) and val and isinstance(val[0], NDArray):
        return list(val)
    raise MXNetError("invalid value type %r" % type(val))


def _group_kv(key, vals):
    """Group (possibly list-of-list) values by key, reference
    `KVStoreLocal::GroupKVPairs` (`kvstore_local.h`)."""
    keys = _key_list(key)
    if len(keys) == 1:
        return keys, [_val_list(vals)]
    if not isinstance(vals, (list, tuple)) or len(vals) != len(keys):
        raise MXNetError("one value (or list) per key required")
    return keys, [_val_list(v) for v in vals]


# ---------------------------------------------------------------------------
# Fused reduce / broadcast executables (the Comm layer).
# One jitted executable per (n, shape, dtype) signature — the analog of
# CommDevice's merge-buffer kernel (`comm.h:503-598`).
# ---------------------------------------------------------------------------

_REDUCE_CACHE: Dict[Any, Any] = {}


def _fused_sum(jax_arrays):
    import jax

    if len(jax_arrays) == 1:
        return jax_arrays[0]
    key = (len(jax_arrays), tuple(jax_arrays[0].shape),
           str(jax_arrays[0].dtype))
    fn = _REDUCE_CACHE.get(key)
    if fn is None:
        def _sum(*xs):
            acc = xs[0]
            for x in xs[1:]:
                acc = acc + x
            return acc
        fn = jax.jit(_sum)
        _REDUCE_CACHE[key] = fn
    dev = jax_arrays[0].devices() if hasattr(jax_arrays[0], "devices") else None
    target = next(iter(dev)) if dev else None
    moved = [x if target is None or
             (hasattr(x, "devices") and target in x.devices())
             else jax.device_put(x, target) for x in jax_arrays]
    return fn(*moved)


# ---------------------------------------------------------------------------
# Gradient compression — 2-bit stochastic-threshold quantization with
# error-feedback residual (reference `src/kvstore/gradient_compression.h:
# 38-134`).  quantize(g + r): +threshold where > threshold, -threshold
# where < -threshold, else 0; the residual keeps what was dropped.
# ---------------------------------------------------------------------------

class GradientCompression(object):
    def __init__(self, type="2bit", threshold=0.5):
        if type != "2bit":
            raise MXNetError("unsupported compression type %r" % type)
        if float(threshold) <= 0:
            raise MXNetError("threshold must be positive")
        self.type = type
        self.threshold = float(threshold)
        self._residuals: Dict[Any, Any] = {}
        self._fn = None

    def _compiled(self):
        import jax
        import jax.numpy as jnp

        if self._fn is None:
            t = self.threshold

            def quant(g, r):
                x = g + r
                q = jnp.where(x > t, t, jnp.where(x < -t, -t, 0.0)
                              ).astype(g.dtype)
                return q, x - q
            self._fn = jax.jit(quant)
        return self._fn

    def compress(self, key, grad_jax):
        r = self._residuals.get(key)
        if r is None:
            import jax.numpy as jnp

            r = jnp.zeros(grad_jax.shape, grad_jax.dtype)
        q, r_new = self._compiled()(grad_jax, r)
        self._residuals[key] = r_new
        return q


# ---------------------------------------------------------------------------
# Base / local / device KVStore
# ---------------------------------------------------------------------------

class KVStore(object):
    """In-process KVStore (`local`); see module docstring."""

    def __init__(self):
        self._store: Dict[Any, NDArray] = {}
        self._updater: Optional[Callable] = None
        self._optimizer = None
        self._compression: Optional[GradientCompression] = None

    @property
    def type(self):
        return "local"

    # -- init ---------------------------------------------------------------
    def init(self, key, value):
        keys, values = _group_kv(key, value)
        for k, vals in zip(keys, values):
            if k in self._store:
                raise MXNetError("key %r already initialized" % (k,))
            if len(vals) != 1:
                raise MXNetError("init requires a single value per key")
            self._store[k] = vals[0].copy()

    # -- push/pull ----------------------------------------------------------
    def _reduce(self, k, vals: List[NDArray]) -> NDArray:
        raws = [v._data for v in vals]
        merged = _fused_sum(raws)
        if self._compression is not None:
            merged = self._compression.compress(k, merged)
        return NDArray(merged, ctx=vals[0].ctx, _committed=True)

    def push(self, key, value, priority=0):
        from .ndarray.sparse import (BaseSparseNDArray, RowSparseNDArray,
                                     add as _sp_add)

        keys, values = _group_kv(key, value)
        for k, vals in zip(keys, values):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            stored = self._store[k]
            if any(isinstance(v, BaseSparseNDArray) for v in vals):
                # row-sparse merge (reference KVStoreLocal sparse push):
                # rsp grads sum sparsely, and the updater sees the
                # MERGED sparse grad so lazy row updates stay lazy
                if not all(isinstance(v, RowSparseNDArray) for v in vals):
                    raise MXNetError(
                        "push of mixed sparse/dense values for key %r "
                        "is not supported" % (k,))
                merged = vals[0]
                for v in vals[1:]:
                    merged = _sp_add(merged, v)
                if self._updater is not None:
                    self._updater(k, merged, stored)
                else:
                    stored._set_jax(merged.todense()._data)
                continue
            # resilience chokepoint sits BEFORE the updater mutates the
            # stored weight, so a retried push never double-applies
            merged = _res.guarded("kvstore_push", self._reduce, k, vals)
            if self._updater is not None:
                self._updater(k, merged, stored)
            else:
                stored._set_jax(merged._data)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if out is None:
            raise MXNetError("pull requires out=")
        keys, outs = _group_kv(key, out)
        for k, dsts in zip(keys, outs):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            src = self._store[k]
            for d in dsts:
                if d.stype != "default":
                    raise MXNetError(
                        "pull into %s output: use row_sparse_pull"
                        % d.stype)
                # pull is idempotent: the whole copy is retry-safe
                _res.guarded("kvstore_pull", src.copyto, d)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority=priority)
        self.pull(key, out=out if out is not None else value,
                  priority=priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the rows in `row_ids` (reference
        `KVStoreLocal::PullRowSparseImpl`).  Dense store: gathers rows."""
        if out is None or row_ids is None:
            raise MXNetError("row_sparse_pull requires out= and row_ids=")
        keys, outs = _group_kv(key, out)
        rids = row_ids if isinstance(row_ids, (list, tuple)) else [row_ids]
        if len(rids) == 1 and len(outs[0]) > 1:
            rids = rids * len(outs[0])
        from .ndarray import sparse as _sp

        for k, dsts in zip(keys, outs):
            src = self._store[k]
            for d, rid in zip(dsts, rids):
                _sp.retain_rows_into(src, rid, d)

    # -- updater / optimizer ------------------------------------------------
    def set_updater(self, updater):
        self._updater = updater

    def _set_updater(self, updater):
        self.set_updater(updater)

    def set_optimizer(self, optimizer):
        from . import optimizer as opt_mod

        self._optimizer = optimizer
        self.set_updater(opt_mod.get_updater(optimizer))

    def set_gradient_compression(self, compression_params):
        params = dict(compression_params or {})
        self._compression = GradientCompression(
            type=params.get("type", "2bit"),
            threshold=params.get("threshold", 0.5))

    # -- distributed surface (degenerate single-process defaults) -----------
    @property
    def rank(self):
        return 0

    @property
    def num_workers(self):
        return 1

    @property
    def live_workers(self):
        """Workers currently alive in the group (elastic membership —
        see `docs/elastic.md`).  Equals :attr:`num_workers` for
        non-distributed stores."""
        return self.num_workers

    def barrier(self):
        pass

    def telemetry(self):
        """Merged telemetry view (`docs/observability.md`).  For
        non-distributed stores this is just the local process:
        ``{"nodes": {<id>: snapshot}, "aggregate": stats}``.
        `KVStoreDist` overrides with the scheduler's cluster view
        built from heartbeat-shipped per-node snapshots."""
        from . import telemetry as _tel

        snap = _tel.snapshot()
        return {"nodes": {"local": snap},
                "aggregate": dict(snap["stats"])}

    def send_command_to_servers(self, head, body):
        pass

    def save_optimizer_states(self, fname, dump_optimizer=False):
        """Persist the active updater's state buffers (momentum/Adam
        moments, update counters) — reference `python/mxnet/kvstore.py`
        saves `self._updater.get_states()`, not the optimizer object."""
        if self._updater is None:
            raise MXNetError(
                "load/save optimizer states is only supported when an "
                "updater is set (update_on_kvstore)")
        with _res.atomic_write(fname) as f:
            f.write(self._updater.get_states(dump_optimizer=dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError(
                "load/save optimizer states is only supported when an "
                "updater is set (update_on_kvstore)")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    def close(self):
        pass


class KVStoreDevice(KVStore):
    """On-device merge + broadcast (CommDevice / NCCL analog): identical
    host logic, but the merge is pinned to the first value's device so
    transfers ride device interconnect, never the host."""

    @property
    def type(self):
        return "device"


class KVStoreTPU(KVStoreDevice):
    """`tpu` backend: XLA all-reduce over the replicas' devices.

    The merge of n pushed per-device values is `jax.lax.psum` under
    shard_map (one compiled collective over ICI) along a line of n
    devices: the data axis of the live mesh when its size is n, else
    the n distinct devices the values already live on — which is what
    `Module(context=[tpu(0), ...])` pushes, with no mesh in sight.
    With neither, values that all sit on ONE device are summed there
    (nothing to reduce across) and anything in between raises.
    `last_reduce_path` names the path taken.  This is the BASELINE.json
    ``kvstore=tpu`` north star.

    Mesh and axis resolve through the sharding backbone: an explicit
    ctor arg wins, then the `MeshContext` stack, then the active
    `mx.shard.ShardingPlan` (mesh AND data-axis name) — the collective
    is chosen from the plan, not hand-wired per call site.
    """

    def __init__(self, mesh=None, axis=None):
        super().__init__()
        self._mesh = mesh
        self._axis = axis  # None = the active plan's data axis
        # "psum" | "local" (one value, or all on one device)
        self.last_reduce_path = None

    @property
    def type(self):
        return "tpu"

    def _resolve(self):
        """(mesh, axis) for this reduce, via the backbone order."""
        from .parallel.mesh import current_mesh
        from .sharding.plan import current_plan

        plan = current_plan()
        axis = self._axis or (plan.data_axis if plan is not None
                              else "dp")
        mesh = self._mesh or current_mesh() or \
            (plan.mesh if plan is not None else None)
        return mesh, axis

    def _dp_line_mesh(self, mesh, n, axis):
        """A 1-D sub-mesh over the `n` devices forming the reduce axis.
        For a 1-D (or effectively-1-D) mesh that is the mesh itself; for
        a multi-axis mesh (dp, tp, ...) it is the dp line at index 0 of
        every other axis — the n Module replicas map onto it in order."""
        if axis not in mesh.shape or mesh.shape[axis] != n:
            return None
        if len(mesh.devices.flat) == n:
            if len(mesh.axis_names) == 1:
                return mesh
            from jax.sharding import Mesh

            return Mesh(mesh.devices.reshape(n), (axis,))
        from jax.sharding import Mesh

        ai = list(mesh.axis_names).index(axis)
        line = np.moveaxis(mesh.devices, ai, 0).reshape(n, -1)[:, 0]
        return Mesh(line, (axis,))

    def _own_line_mesh(self, vals: List[NDArray], axis):
        """A 1-D mesh over the devices the pushed values live on, in
        push order, when those are len(vals) distinct devices."""
        devs = []
        for v in vals:
            ds = v._data.devices()
            if len(ds) != 1:
                return None
            devs.append(next(iter(ds)))
        if len(set(devs)) != len(devs):
            return None
        from jax.sharding import Mesh

        return Mesh(np.array(devs), (axis,))   # jax interns equal meshes

    def _reduce(self, k, vals: List[NDArray]) -> NDArray:
        n = len(vals)
        mesh, axis = self._resolve()
        line = self._dp_line_mesh(mesh, n, axis) \
            if mesh is not None and n > 1 else None
        if line is None and n > 1:
            line = self._own_line_mesh(vals, axis)
        if line is None:
            devices = set().union(*(v._data.devices() for v in vals))
            if len(devices) == 1:
                # one value, or every replica on one device and no
                # mesh to spread them over: nothing to reduce across
                self.last_reduce_path = "local"
                return super()._reduce(k, vals)
            raise MXNetError(
                "kvstore=tpu: %d pushed values span %d device(s) and no "
                "active mesh has a %r axis of size %d, so there is no "
                "line of devices to all-reduce over; push one value per "
                "device, or use kvstore='device' for an on-device merge"
                % (n, len(devices), axis, n))
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from .parallel import collectives

        # one shard per pushed value, placed on the reduce-line devices
        # in order — no host round-trip, replica i's gradient stays on
        # (or moves device-to-device to) line device i
        sharding = NamedSharding(line, PartitionSpec(axis))
        shape0 = vals[0].shape
        line_devs = list(line.devices.flat)
        shards = [jax.device_put(v._data.reshape((1,) + shape0), d)
                  for v, d in zip(vals, line_devs)]
        stacked = jax.make_array_from_single_device_arrays(
            (n,) + shape0, sharding, shards)
        summed = collectives.all_reduce(stacked, axis=axis, mesh=line)
        # every line device now holds the sum; hand back the copy on
        # the first value's own device (a single-device array, like the
        # values pushed — the updater mixes it with the stored weight)
        home = vals[0]._data.devices()
        shards = summed.addressable_shards
        merged = next((s for s in shards if s.device in home),
                      shards[0]).data[0]
        if self._compression is not None:
            merged = self._compression.compress(k, merged)
        self.last_reduce_path = "psum"
        return NDArray(merged, ctx=vals[0].ctx, _committed=True)


# ---------------------------------------------------------------------------
# Distributed KVStore (parameter server over TCP — `mxtpu/_ps.py`)
# ---------------------------------------------------------------------------

class KVStoreDist(KVStoreDevice):
    """Multi-process KVStore: local device merge, then push/pull against
    the server group (reference `KVStoreDist`, `kvstore_dist.h:44`).

    sync mode: the server accumulates pushes from all workers, then
    applies its updater once (`kvstore_dist_server.h:346-358`); async:
    the server applies each push immediately.
    """

    def __init__(self, type_name="dist_sync"):
        super().__init__()
        self._type = type_name
        from . import _ps

        self._worker = _ps.Worker.from_env()

    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        return self._worker.rank

    @property
    def num_workers(self):
        """CONFIGURED group size (nw0).  Deliberately static under
        elastic membership: gradient averaging stays scaled by nw0
        (Module/Trainer rescale_grad) while the server rescales short
        rounds by ``nw0/live`` — so `dist_sync` means "average over the
        live workers" at every group size.  See :attr:`live_workers`."""
        return self._worker.num_workers

    @property
    def live_workers(self):
        """Workers currently alive per the scheduler's dead-node
        detector (elastic membership, `docs/elastic.md`)."""
        try:
            return int(self._worker.group_info().get(
                "num_workers", self._worker.live_workers))
        except (ConnectionError, OSError):
            return self._worker.live_workers

    @property
    def rejoined(self):
        """True when this worker re-registered into a group that was
        already running (a respawned/late-joining elastic worker): it
        must pull current weights and resume at
        :meth:`current_version` instead of training from step 0."""
        return self._worker.rejoined

    def current_version(self, key):
        """Applied sync-round count of ``key`` on its servers — the
        group's current training step for elastic resume."""
        return self._worker.key_version(key)

    def init(self, key, value):
        keys, values = _group_kv(key, value)
        rejoined = self._worker.rejoined
        for k, vals in zip(keys, values):
            if k in self._store:
                raise MXNetError("key %r already initialized" % (k,))
            self._store[k] = vals[0].copy()
            if self._worker.rank == 0 and not rejoined:
                self._worker.init(k, vals[0].asnumpy())
            else:
                # non-root ranks AND rejoining workers must not reset
                # server state — the weights (and their round versions)
                # already live there
                self._worker.register_meta(k, vals[0].shape,
                                           vals[0].dtype)
        if not rejoined:
            # a rejoiner must not barrier: the running group is not at
            # a rendezvous point
            self._worker.barrier()

    def push(self, key, value, priority=0):
        from .ndarray.sparse import RowSparseNDArray, add as _sp_add

        keys, values = _group_kv(key, value)
        sync = self._type != "dist_async"
        for k, vals in zip(keys, values):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            if all(isinstance(v, RowSparseNDArray) for v in vals):
                # rows-only on the wire (reference kRowSparsePushPull)
                merged = vals[0]
                for v in vals[1:]:
                    merged = _sp_add(merged, v)
                rows = np.asarray(merged.indices.asnumpy(), np.int64)
                data = np.asarray(merged.data.asnumpy())
                valid = rows < merged.shape[0]  # drop OOB grad padding
                _res.guarded("kvstore_push", self._worker.push_rows, k,
                             rows[valid], data[valid], sync=sync,
                             timeout=_kvstore_timeout(),
                             _retry_deadline=_wire_deadline())
                continue
            merged = self._reduce(k, vals)
            # AT-LEAST-ONCE on retry: a reply lost after the server
            # applied the push means the resend double-applies (the
            # server dedups nothing yet — multi-host idempotency is
            # future work).  Injected faults fire before the send, so
            # injection replay stays exact.
            # mx.tracing: the wire round is one child span of the
            # ambient step trace; the CHILD context goes ambient so
            # the PS worker stamps ITS span id on the wire and the
            # server-side spans parent under this segment
            trc = _tracing.current()
            if trc is None:
                _res.guarded("kvstore_push", self._worker.push, k,
                             merged.asnumpy(), sync=sync,
                             timeout=_kvstore_timeout(),
                             _retry_deadline=_wire_deadline())
            else:
                kctx = trc.child()
                t0 = _time.perf_counter()
                try:
                    with _tracing.use(kctx):
                        _res.guarded("kvstore_push", self._worker.push,
                                     k, merged.asnumpy(), sync=sync,
                                     timeout=_kvstore_timeout(),
                                     _retry_deadline=_wire_deadline())
                finally:
                    _tracing.record_span(kctx, "kvstore_push",
                                         _time.perf_counter() - t0,
                                         root=True, key=str(k))

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if out is None:
            raise MXNetError("pull requires out=")
        keys, outs = _group_kv(key, out)
        for k, dsts in zip(keys, outs):
            if k not in self._store:
                raise MXNetError("key %r not initialized" % (k,))
            trc = _tracing.current()
            if trc is None:
                arr = _res.guarded("kvstore_pull", self._worker.pull,
                                   k, sync=self._type != "dist_async",
                                   timeout=_kvstore_timeout(),
                                   _retry_deadline=_wire_deadline())
            else:
                kctx = trc.child()
                t0 = _time.perf_counter()
                try:
                    with _tracing.use(kctx):
                        arr = _res.guarded(
                            "kvstore_pull", self._worker.pull, k,
                            sync=self._type != "dist_async",
                            timeout=_kvstore_timeout(),
                            _retry_deadline=_wire_deadline())
                finally:
                    _tracing.record_span(kctx, "kvstore_pull",
                                         _time.perf_counter() - t0,
                                         root=True, key=str(k))
            src = NDArray(np.asarray(arr), ctx=dsts[0].ctx)
            for d in dsts:
                if d.stype != "default":
                    raise MXNetError(
                        "pull into %s output: use row_sparse_pull"
                        % d.stype)
                src.copyto(d)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull ONLY the requested rows over the wire (reference
        `src/kvstore/kvstore_dist.h` PullRowSparse): the worker asks each
        server for the flat spans its chunk holds of those rows —
        traffic is O(rows * width), never the full value."""
        if out is None or row_ids is None:
            raise MXNetError("row_sparse_pull requires out= and row_ids=")
        keys, outs = _group_kv(key, out)
        rids = row_ids if isinstance(row_ids, (list, tuple)) else [row_ids]
        if len(rids) == 1 and len(outs[0]) > 1:
            rids = rids * len(outs[0])
        from .ndarray import sparse as _sp

        sync = self._type != "dist_async"
        for k, dsts in zip(keys, outs):
            for d, rid in zip(dsts, rids):
                rid_np = np.asarray(
                    rid.asnumpy() if isinstance(rid, NDArray) else rid
                ).reshape(-1)
                rows, data = _res.guarded(
                    "kvstore_pull", self._worker.pull_rows, k, rid_np,
                    sync=sync, timeout=_kvstore_timeout(),
                    _retry_deadline=_wire_deadline())
                _sp.set_rows_into(rows, data, d)

    def set_optimizer(self, optimizer):
        # reference: optimizer is serialized to the servers and runs there
        # (`python/mxnet/kvstore.py set_optimizer` → SendCommandToServers)
        self._optimizer = optimizer
        if self._worker.rejoined:
            return  # servers already run the updater; group isn't at a
            # rendezvous point, so neither command nor barrier
        if self._worker.rank == 0:
            self._worker.send_command("set_optimizer",
                                      pickle.dumps(optimizer))
        self._worker.barrier()

    def barrier(self):
        self._worker.barrier()

    def telemetry(self):
        """The scheduler's merged cluster view: per-node telemetry
        snapshots (shipped on the heartbeat channel) plus aggregated
        counter totals (`docs/observability.md`)."""
        return self._worker.telemetry()

    def send_command_to_servers(self, head, body):
        self._worker.send_command(head, body)

    # -- fleet checkpointing (mxtpu/checkpoint.py) ------------------------
    def checkpoint_stamp(self, rnd):
        """The scheduler's idempotent (round, generation,
        live-worker-set) fleet checkpoint stamp for round ``rnd`` —
        every worker asking at the same boundary gets the SAME id
        (docs/checkpoint.md)."""
        return self._worker.checkpoint_stamp(int(rnd))

    def server_checkpoint(self, directory, stamp):
        """Command every live server to snapshot its shard (store +
        version vector + updater state) into ``directory`` for the
        stamped round.  Servers capture under their lock and write on
        a background thread; rank 0's fleet-manifest commit polls for
        the resulting per-server manifests."""
        self._worker.send_command(
            "mxtpu_ckpt", {"dir": str(directory),
                           "id": stamp.get("id"),
                           "round": int(stamp["round"]),
                           "gen": int(stamp.get("gen", 0))})

    def resume_at_version(self, version):
        """Anchor push/pull round numbering at a restored checkpoint
        round R: the first post-resume push lands as round R+1 against
        the servers' restored version vectors, and sync pulls require
        ``>= R`` (see `_ps.Worker.resume_at_version`)."""
        self._worker.resume_at_version(int(version))

    def num_dead_node(self, node_id=6, timeout=None):
        """Count nodes with no heartbeat within `timeout` seconds
        (default ``MXTPU_DEAD_TIMEOUT``; reference
        `include/mxnet/kvstore.h:346-355` get_num_dead_node).
        `node_id` is the ps-lite group mask: 2 servers | 4 workers
        (default: both).  Nodes the scheduler has DECLARED dead (and
        re-ranked around) are always counted.  Scheduler liveness is
        not tracked — a dead scheduler surfaces as a ConnectionError
        from this very query."""
        count = 0
        for nid in self._worker.num_dead_nodes(timeout):
            group = 2 if nid % 2 == 0 else 4  # servers 8+2r, workers 9+2r
            if node_id & group:
                count += 1
        return count

    def close(self):
        self._worker.close()


# ---------------------------------------------------------------------------
# Factory (reference `src/kvstore/kvstore.cc:40-72`)
# ---------------------------------------------------------------------------

def create(name: str = "local", **kwargs) -> KVStore:
    name = (name or "local").lower()
    if name.startswith("dist"):
        return KVStoreDist(name)
    if name == "tpu":
        return KVStoreTPU(**kwargs)
    if name in ("device", "nccl"):
        return KVStoreDevice()
    if name == "local":
        return KVStore()
    raise MXNetError("unknown kvstore type %r" % name)
