"""Compilation lifecycle: persistent XLA cache, shape buckets, AOT warmup.

The reference amortizes graph setup cost with the NNVM graph cache
(`src/imperative/cached_op.cc`) but still pays full backend codegen on
every process start, and a new input shape means a new engine plan.  On
the XLA substrate both costs are explicit and much larger — a ResNet
bind is seconds of HLO compilation — so this module owns the three
levers that make "compile once, serve many" real:

  * **Persistent compile cache** — JAX's on-disk compilation cache,
    on by default: at ``JAX_COMPILATION_CACHE_DIR`` when whoever runs
    the program sets it, else at ``<checkout>/.jax_cache``
    (:func:`configure_persistent_cache`), with the thresholds dropped
    to zero so every program is eligible.  The second process start of
    the same model skips XLA entirely.

  * **Shape-bucketed dispatch** — serving traffic with ragged leading
    batch dims is padded up to a bounded bucket set (power-of-two by
    default; ``MXTPU_SHAPE_BUCKETS`` picks the policy) so the hot path
    runs a FIXED set of compiled programs instead of one per distinct
    batch size.  Outputs are sliced back; per-sample inference math is
    unaffected by pad rows.  Used by ``CachedOp.__call__`` and
    ``Executor.forward(is_train=False)``.

  * **AOT warmup** — ``Executor.warmup()`` / ``CachedOp.warmup()``
    build executables ahead of time via ``jit(...).lower().compile()``
    (the pattern proven by ``FusedTrainLoop.lower_stacked``) and the
    call paths dispatch straight to the stored executable, so the
    first request after warmup compiles NOTHING.

Retrace/hit accounting for all three levers flows through
``mxtpu.profiler`` stats (see ``profiler.stats()``), and
``tools/check_retrace.py`` turns that into a CI guard.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .base import MXNetError, getenv

__all__ = [
    "configure_persistent_cache",
    "persistent_cache_dir",
    "persistent_cache_bypassed",
    "set_bucket_policy",
    "get_bucket_policy",
    "bucket_batch",
    "bucket_set",
    "bucketing_enabled",
    "donation_enabled",
    "pad_leading",
    "sig_of",
    "aot_compile",
]

#: default cache location, ``<checkout>/.jax_cache``: derived from the
#: package's own path, so every process started from one checkout shares
#: it whatever its cwd (a directory that moves between runs never hits)
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_lock = threading.Lock()
_policy_override: Optional[str] = None


# ---------------------------------------------------------------------------
# Persistent on-disk compilation cache
# ---------------------------------------------------------------------------

def configure_persistent_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; ``import mxtpu`` calls
    this once, before anything can compile.  Returns the directory, or
    None when the cache is off.

    * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
      code sets no directory — whoever runs the program places the
      cache (a CI machine, the chip tool) and finds it again.
    * unset: ``<checkout>/.jax_cache`` (:data:`_DEFAULT_CACHE_DIR`).
    * ``MXTPU_COMPILE_CACHE=0``: no cache for this process (chaos
      harnesses that SIGKILL their children use it).

    The entry-size and compile-time thresholds are dropped so every
    executor/CachedOp program is eligible, not just the large ones — a
    serving fleet cold-starts hundreds of small bucket programs — and
    cache writes are made atomic (see :func:`_patch_atomic_cache_writes`).
    """
    import jax

    if getenv("MXTPU_COMPILE_CACHE", "1") in ("0", "false", "False", "off"):
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(_DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _patch_atomic_cache_writes()
    return persistent_cache_dir()


def persistent_cache_dir() -> Optional[str]:
    """The active on-disk cache directory, or None when disabled."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir


@contextlib.contextmanager
def persistent_cache_bypassed():
    """Scope in which compiles neither read nor write the persistent
    cache (diagnostic compiles whose HLO text must come from THIS
    lowering, see ``inspect._compile_uncached``).  Flips JAX's enable
    flag, never the directory, and resets the per-process latch JAX
    keeps on its cache decision both ways.  Process-global, so scopes
    are serialized; a normal compile on another thread during the
    scope just compiles uncached."""
    import jax
    from jax._src import compilation_cache as _jax_cc

    with _lock:
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        _jax_cc.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            _jax_cc.reset_cache()


def _patch_atomic_cache_writes() -> None:
    """Make JAX's on-disk cache writes ATOMIC (temp + ``os.replace``).

    jax 0.9.0's ``LRUCache.put`` still writes an entry with a bare
    ``path.write_bytes`` — no temp file, and no lock unless eviction is
    on.  Every process of a checkout shares one cache directory and the
    thresholds are zero, so a concurrent reader, or a SIGKILL landing
    mid-write, meets a TORN entry; since ``put`` never overwrites an
    existing key, a torn entry would stay a miss (plus a warning) for
    good.  ``os.replace`` is atomic on POSIX: readers see no entry or
    the whole entry, and an interrupted writer leaves only a ``.tmp``
    sibling no reader looks at.  Written against the 0.9.0 internals;
    if they moved, this raises instead of leaving writes unprotected."""
    import tempfile
    import time as _time
    import warnings

    from jax._src import lru_cache as _lru

    cls = _lru.LRUCache
    if getattr(cls.put, "_mxtpu_atomic", False):
        return
    cache_suffix = _lru._CACHE_SUFFIX
    atime_suffix = _lru._ATIME_SUFFIX

    def put(self, key, val):
        if not key:
            raise ValueError("key cannot be empty")
        if self.eviction_enabled and len(val) > self.max_size:
            warnings.warn(  # keep the stock diagnostic
                f"Cache value for key {key!r} of size {len(val)} "
                f"bytes exceeds the maximum cache size of "
                f"{self.max_size} bytes")
            return
        cache_path = self.path / f"{key}{cache_suffix}"
        if self.eviction_enabled:
            self.lock.acquire(timeout=self.lock_timeout_secs)
        try:
            if cache_path.exists():
                return
            self._evict_if_needed(additional_size=len(val))
            # mkstemp, not a fixed pid-derived name: two THREADS
            # putting the same key must not share one temp file (a
            # reopen+truncate race would atomically install a torn
            # entry — the exact corruption this patch kills)
            fd, tmp = tempfile.mkstemp(dir=str(self.path), suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(val)
                os.replace(tmp, cache_path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            if self.eviction_enabled:
                (self.path / f"{key}{atime_suffix}").write_bytes(
                    _time.time_ns().to_bytes(8, "little"))
        finally:
            if self.eviction_enabled:
                self.lock.release()

    put._mxtpu_atomic = True
    cls.put = put


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------

def set_bucket_policy(spec: Optional[str]) -> None:
    """Set the process-wide bucket policy, overriding the env knob.

    Specs: ``"pow2"`` (pad the leading batch dim up to the next power of
    two), ``"mult:N"`` (round up to a multiple of N), ``"fixed:a,b,c"``
    (smallest listed bucket that fits; larger batches run exact), or
    ``None``/``"off"`` to disable.
    """
    global _policy_override
    if spec is not None and spec not in ("off", "none", "0", "false",
                                         "False", "1", "true", "True"):
        _parse_policy(spec)  # validate eagerly
    _policy_override = spec


def get_bucket_policy() -> Optional[str]:
    """The active bucket policy spec, or None when bucketing is off.

    Resolution order: :func:`set_bucket_policy` override, then the
    ``MXTPU_SHAPE_BUCKETS`` env var (``1`` means ``pow2``).
    """
    spec = _policy_override
    if spec is None:
        spec = getenv("MXTPU_SHAPE_BUCKETS")
    if spec in (None, "", "0", "off", "false", "False", "none"):
        return None
    return "pow2" if spec in ("1", "true", "True") else spec


def bucketing_enabled() -> bool:
    return get_bucket_policy() is not None


@functools.lru_cache(maxsize=64)
def _parse_policy(spec: str):
    if spec == "pow2":
        return ("pow2",)
    if spec.startswith("mult:"):
        n = int(spec[5:])
        if n < 1:
            raise MXNetError("mult bucket step must be >= 1, got %d" % n)
        return ("mult", n)
    if spec.startswith("fixed:"):
        sizes = sorted(int(s) for s in spec[6:].split(",") if s)
        if not sizes:
            raise MXNetError("fixed bucket policy needs at least one size")
        return ("fixed", sizes)
    raise MXNetError(
        "bucket policy must be 'pow2', 'mult:N' or 'fixed:a,b,...' "
        "(got %r)" % (spec,))


def bucket_batch(n: int, spec: Optional[str] = None) -> int:
    """The padded leading dim for a ragged batch of ``n`` under the
    active (or given) policy.  Always >= n; returns n when bucketing is
    off or no bucket fits."""
    if spec is None:
        spec = get_bucket_policy()
    if spec is None or n < 1:
        return n
    policy = _parse_policy(spec)
    if policy[0] == "pow2":
        b = 1
        while b < n:
            b <<= 1
        return b
    if policy[0] == "mult":
        step = policy[1]
        return ((n + step - 1) // step) * step
    for size in policy[1]:
        if size >= n:
            return size
    return n


def bucket_set(cap: int, spec: Optional[str] = None) -> List[int]:
    """The FULL set of bucket sizes the policy can produce for batches
    of 1..cap, ascending — the signatures a serving replica AOT-warms
    so its steady state compiles nothing (``mx.serve`` warms exactly
    this set per model).  Under ``pow2`` and cap 32 that is
    [1, 2, 4, 8, 16, 32]; ``mult:N`` gives the multiples of N up to
    cap; ``fixed:...`` the listed sizes that fit."""
    if spec is None:
        spec = get_bucket_policy() or "pow2"
    cap = max(1, int(cap))
    sizes = sorted({bucket_batch(n, spec) for n in range(1, cap + 1)})
    return [s for s in sizes if s <= cap] or [cap]


def pad_leading(val, target: int):
    """Zero-pad a jax array's leading dim up to ``target`` rows."""
    import jax.numpy as jnp

    n = val.shape[0]
    if n == target:
        return val
    return jnp.pad(val, [(0, target - n)] + [(0, 0)] * (val.ndim - 1))


def batch_output_mask(symbol, arg_names: Sequence[str],
                      unpadded_shapes: Sequence[Tuple[int, ...]],
                      padded_shapes: Sequence[Tuple[int, ...]]):
    """Which graph outputs carry the (padded) batch dim, decided by
    shape inference rather than by guessing from the runtime shapes: an
    output whose leading dim coincidentally equals the bucket size
    (e.g. a transposed (features, B) head) must NOT be sliced.  Returns
    a per-output bool list (True = slice the pad rows off), or None
    when inference cannot decide (callers fall back to returning
    unsliced outputs and the exact-shape dispatch)."""
    try:
        _, outs_u, _ = symbol.infer_shape_partial(
            **dict(zip(arg_names, unpadded_shapes)))
        _, outs_p, _ = symbol.infer_shape_partial(
            **dict(zip(arg_names, padded_shapes)))
    except Exception:
        return None
    if outs_u is None or outs_p is None:
        return None
    mask = []
    for su, sp in zip(outs_u, outs_p):
        if su is None or sp is None:
            return None
        # batch-major <=> the leading dim tracked the padding
        mask.append(bool(su) and bool(sp) and su[0] != sp[0])
    return mask


# ---------------------------------------------------------------------------
# Donation + AOT helpers
# ---------------------------------------------------------------------------

def donation_enabled() -> bool:
    """Buffer donation on the executor/CachedOp training hot paths
    (``MXTPU_DONATE``, default on)."""
    return getenv("MXTPU_DONATE", "1") not in ("0", "false", "False")


def sig_of(vals: Sequence[Any]) -> Tuple:
    """Hashable shape/dtype signature of a flat list of arrays.

    The dtype OBJECT (np.dtype — hashable, interned per kind) is used
    rather than ``str(dtype)``: stringifying a dtype costs ~7 us and
    this runs per dispatch on the serving hot path (the whole
    signature build is ~6 us for a 5-array program; measured by
    ``tools/check_inspect.py --overhead-only``)."""
    return tuple((tuple(v.shape), v.dtype) for v in vals)


def aot_compile(jitfn, example_args, program=None, kind="aot"):
    """``jit(...).lower(*args).compile()``: build the executable without
    running it.  ``example_args`` may be arrays or ShapeDtypeStructs;
    the returned Compiled object is called with matching concrete
    arrays and NEVER touches the jit's trace/compile cache.

    ``program`` (a ``mx.inspect`` :class:`ProgramRecord`) registers
    the built executable in the program-inspector registry under
    ``kind`` — analysis is immediate and cheap because the Compiled
    object is already in hand.

    Runs under the ``compile`` fault-injection site + retry policy
    (mxtpu/resilience.py): a transient XLA/compile-cache failure is
    retried with backoff instead of killing the run."""
    import time as _time

    from . import resilience as _res
    from . import telemetry as _tel

    # zero-valued fields are backfilled IN PLACE by the inspector
    # (pre-created here so the ring-resident dict never grows)
    ev = _tel.record("compile", site="aot", step=_tel.current_step(),
                     program=program.name if program is not None else None,
                     variant=kind, flops=0.0, peak_bytes=0, compile_s=0.0)

    def body():
        _res.maybe_fault("compile", "aot_compile")
        return jitfn.lower(*example_args).compile()
    t0 = _time.perf_counter()
    compiled = _res.run_with_retry("compile", body)
    if program is not None:
        program.record_aot(kind, example_args, compiled,
                           _time.perf_counter() - t0, event=ev,
                           jitfn=jitfn)
    return compiled


def shape_struct(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)
