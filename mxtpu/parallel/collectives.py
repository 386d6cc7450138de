"""XLA collectives over the device mesh.

These replace the reference's three comm backends behind KVStore
(CPU-OMP reduce `src/kvstore/comm.h:103`, GPU P2P merge `comm.h:451`,
NCCL ring `kvstore_nccl.h:62`) with the XLA collective set riding ICI:
all_reduce (psum), all_gather, reduce_scatter (psum_scatter),
all_to_all, collective_permute (ppermute).

Two call styles:
  * inside shard_map/pjit-traced code: use jax.lax.p* directly;
  * eager on NDArray (the KVStore 'tpu' backend path): the helpers here
    wrap shard_map so a host-level call is one compiled collective.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

from ..base import MXNetError
from .mesh import current_mesh

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "all_to_all",
           "collective_permute", "psum_scalar"]


def _resolve_mesh(mesh):
    """Explicit mesh > MeshContext > the active ShardingPlan's mesh —
    one resolution order for every collective (the plan is the
    backbone; call sites stop hand-wiring)."""
    m = mesh if mesh is not None else current_mesh()
    if m is None:
        from ..sharding.plan import current_plan

        plan = current_plan()
        m = plan.mesh if plan is not None else None
    if m is None:
        raise MXNetError("no mesh: pass mesh=, enter a MeshContext, or "
                         "activate a ShardingPlan with one")
    return m


def _resolve_axis(axis: Optional[str], fallback: str = "dp") -> str:
    """None -> the active plan's data axis (else ``fallback``) — so a
    plan that renames its replica axis re-points every collective."""
    if axis is not None:
        return axis
    from ..sharding.plan import current_plan

    plan = current_plan()
    return plan.data_axis if plan is not None else fallback


def _count_bytes(counter: str, x, factor: float,
                 stacked_over: int = 1) -> None:
    """Tick the per-collective payload counter in profiler.stats().

    Convention (docs/sharding.md): counters record the ring-algorithm
    per-replica payload for the LOGICAL VALUE B — ``factor`` * B.  For
    the wrappers whose input stacks n per-device contributions on the
    leading dim (all_reduce / reduce_scatter / all_to_all / ppermute),
    B is the input size divided by ``stacked_over`` = n, so a
    kvstore=tpu allreduce of a 4 MB gradient over dp=8 ticks
    2·(7/8)·4 MB — the SAME figure the ZeRO-1 engine books for the
    equivalent traffic, not the 8x-inflated stacked-buffer size."""
    import numpy as np

    from .. import profiler as _prof

    try:
        nbytes = int(x.nbytes) if hasattr(x, "nbytes") else \
            int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
    except Exception:
        return
    _prof.inc_stat(counter,
                   int(nbytes * factor / max(1, stacked_over)))


@functools.lru_cache(maxsize=256)
def _compiled_collective(kind, mesh, axis, perm_key):
    import jax
    from jax.sharding import PartitionSpec as P

    shard_map = jax.shard_map

    spec_in = P(axis)       # sharded along leading dim over `axis`
    spec_rep = P()          # fully replicated

    if kind == "all_reduce":
        def fn(x):
            return jax.lax.psum(x, axis)
        in_spec, out_spec = spec_in, spec_rep
        # caller passes per-shard values stacked on leading dim
    elif kind == "all_gather":
        # expressed as place-shard-into-zeros + psum so the result is
        # statically replicated (lax.all_gather output stays "varying"
        # under the vma checker and can't meet a replicated out spec)
        def fn(x):
            import jax.numpy as jnp

            n = mesh.shape[axis]
            idx = jax.lax.axis_index(axis)
            buf = jnp.zeros((n * x.shape[0],) + x.shape[1:], x.dtype)
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, x, idx * x.shape[0], 0)
            return jax.lax.psum(buf, axis)
        in_spec, out_spec = spec_in, spec_rep
    elif kind == "reduce_scatter":
        # same input convention as all_reduce: per-shard contributions
        # stacked on the leading dim; output = elementwise sum, left
        # distributed over `axis` (each device holds one tile)
        def fn(x):
            return jax.lax.psum_scatter(x, axis, tiled=True)
        in_spec, out_spec = spec_in, spec_in
    elif kind == "all_to_all":
        def fn(x):
            return jax.lax.all_to_all(x, axis, split_axis=1,
                                      concat_axis=0, tiled=True)
        in_spec, out_spec = spec_in, spec_in
    elif kind == "collective_permute":
        perm = list(perm_key)

        def fn(x):
            return jax.lax.ppermute(x, axis, perm)
        in_spec, out_spec = spec_in, spec_in
    else:  # pragma: no cover
        raise MXNetError("unknown collective %r" % kind)

    sm = shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                   out_specs=out_spec)
    return jax.jit(sm)


def _raw(x):
    from ..ndarray.ndarray import NDArray

    return x._data if isinstance(x, NDArray) else x


def _wrap(y, like):
    from ..ndarray.ndarray import NDArray

    if isinstance(like, NDArray):
        return NDArray(y, ctx=like.ctx, _committed=True)
    return y


def all_reduce(x, axis: Optional[str] = "dp", mesh=None):
    """Sum shards of `x` (leading dim = mesh axis size) over `axis`,
    returning the replicated sum.  Eager analog of `jax.lax.psum`.
    ``axis=None`` resolves from the active ShardingPlan."""
    mesh = _resolve_mesh(mesh)
    axis = _resolve_axis(axis)
    fn = _compiled_collective("all_reduce", mesh, axis, ())
    raw = _raw(x)
    n = mesh.shape[axis]
    _count_bytes("allreduce_bytes", raw, 2.0 * (n - 1) / max(n, 1),
                 stacked_over=n)
    return _wrap(fn(raw), x)


def all_gather(x, axis: Optional[str] = "dp", mesh=None):
    mesh = _resolve_mesh(mesh)
    axis = _resolve_axis(axis)
    fn = _compiled_collective("all_gather", mesh, axis, ())
    raw = _raw(x)
    n = mesh.shape[axis]
    _count_bytes("allgather_bytes", raw, float(n - 1) / max(n, 1))
    return _wrap(fn(raw), x)


def reduce_scatter(x, axis: Optional[str] = "dp", mesh=None):
    """Sum shards of `x` (leading dim = n stacked contributions, same
    convention as all_reduce); result is the elementwise sum with each
    device holding one tile (shape = x.shape[0] // n on the lead dim
    globally)."""
    mesh = _resolve_mesh(mesh)
    axis = _resolve_axis(axis)
    fn = _compiled_collective("reduce_scatter", mesh, axis, ())
    raw = _raw(x)
    n = mesh.shape[axis]
    _count_bytes("reduce_scatter_bytes", raw,
                 float(n - 1) / max(n, 1), stacked_over=n)
    return _wrap(fn(raw), x)


def all_to_all(x, axis: Optional[str] = "ep", mesh=None):
    mesh = _resolve_mesh(mesh)
    axis = _resolve_axis(axis, fallback="ep")
    fn = _compiled_collective("all_to_all", mesh, axis, ())
    raw = _raw(x)
    n = mesh.shape[axis]
    _count_bytes("alltoall_bytes", raw, float(n - 1) / max(n, 1),
                 stacked_over=n)
    return _wrap(fn(raw), x)


def collective_permute(x, perm: Sequence, axis: Optional[str] = "dp",
                       mesh=None):
    mesh = _resolve_mesh(mesh)
    axis = _resolve_axis(axis)
    fn = _compiled_collective("collective_permute", mesh, axis,
                              tuple(tuple(p) for p in perm))
    raw = _raw(x)
    _count_bytes("ppermute_bytes", raw, 1.0,
                 stacked_over=mesh.shape[axis])
    return _wrap(fn(raw), x)


def psum_scalar(value: float, axis: Optional[str] = "dp",
                mesh=None) -> float:
    """All-reduce a host scalar (metric aggregation across hosts)."""
    import numpy as np

    mesh = _resolve_mesh(mesh)
    axis = _resolve_axis(axis)
    n = mesh.shape[axis]
    arr = np.full((n,), float(value), dtype=np.float32)
    out = all_reduce(arr, axis=axis, mesh=mesh)
    import jax

    return float(jax.device_get(out)[0] if hasattr(out, "__len__")
                 else out)


def microbench(mesh=None, n_bytes: int = 1 << 20, reps: int = 5):
    """Per-axis collective microbenchmark + numeric self-check.

    For every mesh axis of size > 1, runs all_reduce / all_gather /
    reduce_scatter / all_to_all / ring collective_permute on an
    `n_bytes` float32 payload, VERIFIES the result (psum of ones ==
    axis size, gather reassembles, ring permute rotates) and times the
    steady state.  Returns {axis: {collective: {"gb_s", "ms", "ok"}}}.

    The algorithmic byte count follows the ring formulas the reference
    documents for its allreduce benchmarking (`tools/bandwidth`,
    2(n-1)/n for allreduce): on TPU hardware these numbers are the ICI
    utilisation; on the virtual CPU mesh they validate the code path
    that `tools/bandwidth/measure.py` runs on chip.
    """
    import time

    import numpy as np
    import jax

    mesh = _resolve_mesh(mesh)
    n_elem = max(n_bytes // 4, 8)
    results = {}
    for axis, size in mesh.shape.items():
        if size < 2:
            continue
        k = max(n_elem // size, size)
        k -= k % size                      # reduce_scatter tiling
        # per-shard-DISTINCT payload: an all-ones buffer cannot catch
        # ordering/wiring bugs (identity permute, wrong gather order)
        shard = np.arange(size * k, dtype=np.float32).reshape(size, k)
        flat = shard.reshape(-1)
        ka = max(k // size, 1)
        a2a = np.arange(size * size * ka,
                        dtype=np.float32).reshape(size, size, ka)
        ring = [(i, (i + 1) % size) for i in range(size)]
        cases = {
            # input conventions follow the eager wrappers (see
            # tests/test_parallel.py::TestCollectives)
            "all_reduce": (lambda: all_reduce(shard, axis=axis, mesh=mesh),
                           lambda out: np.allclose(np.asarray(out)[0],
                                                   shard.sum(0)),
                           2.0 * (size - 1) / size),
            "all_gather": (lambda: all_gather(flat, axis=axis, mesh=mesh),
                           lambda out: np.array_equal(np.asarray(out),
                                                      flat),
                           float(size - 1) / size),
            "reduce_scatter": (lambda: reduce_scatter(flat, axis=axis,
                                                      mesh=mesh),
                               lambda out: np.allclose(np.asarray(out),
                                                       shard.sum(0)),
                               float(size - 1) / size),
            # wrapper contract: (size, size, ka) -> (size*size, 1, ka),
            # row-major blocks of the [src, dst] transpose
            "all_to_all": (lambda: all_to_all(a2a, axis=axis, mesh=mesh),
                           lambda out: np.array_equal(
                               np.asarray(out).reshape(size, size, ka),
                               np.swapaxes(a2a, 0, 1)),
                           float(size - 1) / size),
            "ppermute": (lambda: collective_permute(
                shard, ring, axis=axis, mesh=mesh),
                lambda out: np.array_equal(np.asarray(out),
                                           np.roll(shard, 1, axis=0)),
                1.0),
        }
        axis_res = {}
        for name, (fn, check, factor) in cases.items():
            out = fn()                      # compile + warm
            ok = bool(check(jax.device_get(out)))
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn()
            jax.block_until_ready(
                out._data if hasattr(out, "_data") else out)
            dt = (time.perf_counter() - t0) / reps
            moved = factor * shard.nbytes
            axis_res[name] = {"ms": dt * 1e3,
                              "gb_s": moved / max(dt, 1e-9) / 1e9,
                              "ok": ok}
        results[axis] = axis_res
    return results
