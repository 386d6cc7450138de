"""mx.checkpoint — fleet-consistent async checkpointing with
deterministic full-run resume (docs/checkpoint.md).

Three layers on top of the PR-2 atomic CRC-manifest machinery
(`mxtpu/resilience.py`):

* :class:`AsyncSnapshotter` — the per-role write path.  ``capture()``
  does the device→host copy into a double buffer and returns; a
  background writer thread lands the snapshot with temp+fsync+rename
  and a CRC manifest.  Steady-state checkpointing costs the copy,
  never the write: if the previous write is still in flight the new
  capture is DROPPED AND COUNTED (``ckpt_dropped``) instead of
  blocking the step.

* :class:`FleetCheckpointer` — fleet consistency over the PS round
  protocol.  The scheduler stamps an idempotent (round, generation,
  live-worker-set) checkpoint id; every worker snapshots at that exact
  round (params + optimizer state + full run state), rank 0 commands
  every server to snapshot its shard store + version vector, and rank
  0's writer thread commits ``fleet.json`` LAST — only after every
  role manifest validates.  A fleet with any missing/torn role bundle
  never gets a fleet manifest and is skipped as a unit at load.

* Resume — :func:`find_resume` picks the newest COMPLETE fleet
  checkpoint, :func:`restore_worker` restores params/optimizer/RNG/
  DataLoader position into a fresh process and anchors the kvstore
  round (``resume_at_version``) so the first post-resume push lands as
  round R+1 against the servers' restored version vectors.
  ``tools/launch.py --auto-resume`` wires this into whole-fleet
  auto-restart.

The per-role snapshot bundles the FULL run state: RNG stream
(`mx.random.get_state`), DataLoader/sampler position (epoch, batch
index, shuffle seed — `DataLoader.state()`) and trainer step count, so
a resumed run is trajectory-identical to the uninterrupted one
(`tools/check_checkpoint.py` enforces 1e-5).
"""
from __future__ import annotations

import json
import logging
import os
import pickle
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import profiler as _prof
from . import resilience as _res
from . import telemetry as _tel

__all__ = [
    "AsyncSnapshotter", "FleetCheckpointer", "collect_run_state",
    "apply_run_state", "restore_worker", "restore_dir", "find_resume",
    "fleet_dir", "fleet_manifest_path", "read_fleet_manifest",
    "fleet_complete", "load_worker_bundle", "write_server_snapshot",
    "load_server_snapshot", "ckpt_dir", "ckpt_every", "arm", "disarm",
    "install_preemption", "on_boundary", "active", "module_bundle",
    "trainer_bundle", "snapshotter",
]

log = logging.getLogger(__name__)

FLEET_MANIFEST = "fleet.json"
FLEET_FORMAT = 1


# ---------------------------------------------------------------------------
# knobs (docs/env_vars.md)
# ---------------------------------------------------------------------------

def ckpt_dir() -> Optional[str]:
    """Where fleet checkpoints live: ``MXTPU_CKPT_DIR``, defaulting to
    the run directory (``MXTPU_RUN_DIR``)."""
    return os.environ.get("MXTPU_CKPT_DIR") or \
        os.environ.get("MXTPU_RUN_DIR") or None


def ckpt_every() -> int:
    """``MXTPU_CKPT_EVERY``: checkpoint every N step/round boundaries
    (0 = only explicit/preemption checkpoints)."""
    try:
        return int(os.environ.get("MXTPU_CKPT_EVERY", "0") or 0)
    except ValueError:
        return 0


def restore_dir() -> Optional[str]:
    """``MXTPU_CKPT_RESTORE``: a complete fleet-checkpoint directory to
    restore from (set by ``launch.py --auto-resume``)."""
    return os.environ.get("MXTPU_CKPT_RESTORE") or None


def _keep() -> int:
    try:
        return max(1, int(os.environ.get("MXTPU_CKPT_KEEP", "3") or 3))
    except ValueError:
        return 3


def _maybe_write_delay() -> None:
    """Test hook: ``MXTPU_CKPT_WRITE_DELAY`` seconds of sleep before
    the writer thread touches disk — widens the torn-write window the
    mid-write-kill chaos phase of `tools/check_checkpoint.py` aims at."""
    try:
        delay = float(os.environ.get("MXTPU_CKPT_WRITE_DELAY", "0") or 0)
    except ValueError:
        return
    if delay > 0:
        time.sleep(delay)


def _fleet_timeout() -> float:
    try:
        return float(os.environ.get("MXTPU_CKPT_FLEET_TIMEOUT", "60")
                     or 60)
    except ValueError:
        return 60.0


# ---------------------------------------------------------------------------
# full-run state (RNG / DataLoader position)
# ---------------------------------------------------------------------------

def collect_run_state(loaders=None, extra: Optional[Dict] = None) -> Dict:
    """JSON-able bundle of everything outside params/optimizer that a
    deterministic resume needs: the threefry RNG chain and each named
    DataLoader's (epoch, batch, seed) position."""
    from . import random as _rnd

    key = _rnd.get_state()
    state: Dict[str, Any] = {
        "rng": None if key is None
        else np.asarray(key).astype(np.uint32).tolist(),
        "loaders": {},
    }
    for name, ld in dict(loaders or {}).items():
        if callable(getattr(ld, "state", None)):
            state["loaders"][str(name)] = ld.state()
    if extra:
        state["extra"] = extra
    return state


def apply_run_state(state, loaders=None) -> None:
    """Inverse of :func:`collect_run_state` — loaders are matched by
    the same names they were captured under."""
    if not state:
        return
    from . import random as _rnd

    if state.get("rng") is not None:
        _rnd.set_state(state["rng"])
    saved = state.get("loaders") or {}
    for name, ld in dict(loaders or {}).items():
        st = saved.get(str(name))
        if st is not None and callable(getattr(ld, "set_state", None)):
            ld.set_state(st)


# ---------------------------------------------------------------------------
# async double-buffered snapshot writer
# ---------------------------------------------------------------------------

def _to_host(v) -> np.ndarray:
    """Device→host copy (the only part of a capture that touches the
    device; `asnumpy` materializes a host array)."""
    if hasattr(v, "asnumpy"):
        return np.asarray(v.asnumpy())
    return np.asarray(v)


class AsyncSnapshotter(object):
    """Double-buffered background checkpoint writer (one per role).

    ``capture()`` copies arrays to host and hands the snapshot to a
    daemon writer thread; if a previous snapshot is still pending or
    being written the capture is dropped and ``ckpt_dropped`` ticks —
    the training step NEVER waits on the disk.  ``flush()`` drains for
    final/preemption snapshots."""

    def __init__(self):
        self._cv = threading.Condition()
        self._pending: Optional[Dict] = None
        self._inflight = False
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self.last_error: Optional[BaseException] = None

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="mxtpu-ckpt-writer")
            self._thread.start()

    def capture(self, prefix: str, epoch: int, arrays: Dict[str, Any],
                states: Optional[bytes] = None,
                extra: Optional[Dict] = None,
                post: Optional[Callable[[], None]] = None,
                wait: bool = False) -> bool:
        """Snapshot ``arrays`` (+ optional opaque ``states`` bytes +
        JSON ``extra`` recorded on the manifest) as epoch ``epoch``
        under ``prefix``.  Returns False when dropped because the
        previous write is still in flight (counted); ``wait=True``
        blocks for the writer instead (final flushes only).  ``post``
        runs on the writer thread after the manifest commits (rank 0
        hangs the fleet-manifest commit here — polling for the other
        roles happens entirely off the critical path)."""
        host = {k: _to_host(v) for k, v in arrays.items()}
        snap = {"prefix": prefix, "epoch": int(epoch), "arrays": host,
                "states": states, "extra": extra, "post": post}
        with self._cv:
            if self._pending is not None or self._inflight:
                if not wait:
                    _prof.inc_stat("ckpt_dropped")
                    return False
                while self._pending is not None or self._inflight:
                    self._cv.wait(0.1)
            self._pending = snap
            self._ensure_thread()
            self._cv.notify_all()
        _prof.inc_stat("ckpt_capture")
        if wait:
            self.flush()
            return self.last_error is None
        return True

    def _run(self) -> None:
        while True:
            with self._cv:
                while self._pending is None and not self._closed:
                    self._cv.wait(0.5)
                if self._pending is None:
                    return
                snap, self._pending = self._pending, None
                self._inflight = True
            try:
                self._write(snap)
                self.last_error = None
            except BaseException as e:
                self.last_error = e
                _prof.inc_stat("ckpt_write_failed")
                log.warning("async checkpoint write failed (%s-%04d): %s",
                            snap["prefix"], snap["epoch"], e)
            finally:
                with self._cv:
                    self._inflight = False
                    self._cv.notify_all()

    def _write(self, snap: Dict) -> None:
        _maybe_write_delay()
        prefix, epoch = snap["prefix"], snap["epoch"]
        w = _res.CheckpointWriter(prefix, epoch)
        base = "%s-%04d" % (prefix, epoch)
        with w.file(base + ".arrays.npz") as f:
            np.savez(f, **snap["arrays"])
        if snap["states"] is not None:
            with w.file(base + ".states.bin") as f:
                f.write(snap["states"])
        w.commit(extra={"bundle": snap["extra"] or {}})
        _prof.inc_stat("ckpt_async_write")
        if snap["post"] is not None:
            snap["post"]()

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Wait for any pending/in-flight write to land."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._pending is not None or self._inflight:
                if deadline is not None and time.monotonic() >= deadline:
                    return False
                self._cv.wait(0.1)
        return True

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()


_SNAPSHOTTER: Optional[AsyncSnapshotter] = None
_SNAP_LOCK = threading.Lock()


def snapshotter() -> AsyncSnapshotter:
    """The process-wide snapshotter (one writer thread per role)."""
    global _SNAPSHOTTER
    with _SNAP_LOCK:
        if _SNAPSHOTTER is None:
            _SNAPSHOTTER = AsyncSnapshotter()
        return _SNAPSHOTTER


# ---------------------------------------------------------------------------
# bundle load/save formats
# ---------------------------------------------------------------------------

def load_worker_bundle(d: str, rank: int,
                       epoch: Optional[int] = None):
    """Read a worker bundle: ``(arrays, states_bytes, manifest)`` or
    None when no valid bundle exists for this rank."""
    prefix = os.path.join(d, "worker%d" % rank)
    if epoch is None:
        epoch = _res.latest_valid_epoch(prefix)
    if epoch is None or not _res.validate_manifest(prefix, epoch):
        return None
    man = _res.read_manifest(prefix, epoch)
    base = "%s-%04d" % (prefix, epoch)
    with np.load(base + ".arrays.npz", allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    states = None
    if os.path.exists(base + ".states.bin"):
        with open(base + ".states.bin", "rb") as f:
            states = f.read()
    return arrays, states, man


def write_server_snapshot(d: str, rank: int, rnd: int,
                          blob: bytes) -> None:
    """Land one PS server's shard snapshot (store + version vector +
    updater state, already pickled by `_ps.Server`) under the fleet
    checkpoint directory with its own CRC manifest."""
    _maybe_write_delay()
    prefix = os.path.join(d, "server%d" % rank)
    w = _res.CheckpointWriter(prefix, rnd)
    with w.file("%s-%04d.shard.pkl" % (prefix, rnd)) as f:
        f.write(blob)
    w.commit(extra={"bundle": {"role": "server", "rank": int(rank),
                               "round": int(rnd)}})
    _prof.inc_stat("ckpt_server_write")


def load_server_snapshot(d: str, rank: int) -> Optional[Tuple[bytes, int]]:
    """``(blob, round)`` of a server's newest valid shard snapshot."""
    prefix = os.path.join(d, "server%d" % rank)
    epoch = _res.latest_valid_epoch(prefix)
    if epoch is None:
        return None
    path = "%s-%04d.shard.pkl" % (prefix, epoch)
    with open(path, "rb") as f:
        return f.read(), epoch


# ---------------------------------------------------------------------------
# fleet manifest
# ---------------------------------------------------------------------------

def fleet_dir(base_dir: str, ckpt_id: str) -> str:
    return os.path.join(base_dir, "ckpt_%s" % ckpt_id)


def fleet_manifest_path(d: str) -> str:
    return os.path.join(d, FLEET_MANIFEST)


def read_fleet_manifest(d: str) -> Optional[Dict]:
    try:
        with open(fleet_manifest_path(d)) as f:
            m = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(m, dict) or "round" not in m:
        return None
    return m


def _role_prefixes(m: Dict) -> List[str]:
    return (["worker%d" % r for r in range(int(m.get("num_workers", 0)))]
            + ["server%d" % s for s in range(int(m.get("num_servers", 0)))])


def fleet_complete(d: str) -> Optional[Dict]:
    """The fleet manifest iff this checkpoint is COMPLETE: fleet.json
    committed AND every per-role manifest it names validates (CRC) —
    partial/torn fleets return None and are skipped as a unit."""
    m = read_fleet_manifest(d)
    if m is None:
        return None
    rnd = int(m["round"])
    for p in _role_prefixes(m):
        if not _res.validate_manifest(os.path.join(d, p), rnd):
            return None
    return m


def find_resume(base_dir: Optional[str]) -> Optional[Tuple[str, Dict]]:
    """Newest complete fleet checkpoint under ``base_dir`` as
    ``(directory, fleet_manifest)``, or None."""
    if not base_dir or not os.path.isdir(base_dir):
        return None
    cands = []
    for name in sorted(os.listdir(base_dir)):
        if not name.startswith("ckpt_"):
            continue
        path = os.path.join(base_dir, name)
        m = fleet_complete(path)
        if m is not None:
            cands.append((int(m["round"]), float(m.get("ts", 0)), path, m))
    if not cands:
        return None
    cands.sort(key=lambda c: (c[0], c[1], c[2]))
    _, _, path, m = cands[-1]
    return path, m


def _commit_fleet(d: str, stamp: Dict,
                  timeout: Optional[float] = None) -> bool:
    """Rank 0's writer thread: poll until EVERY role manifest for the
    stamped round validates, then commit fleet.json atomically LAST.
    The polling is the fleet synchronization — it lives on the writer
    thread, never the step.  On timeout (a role dropped its capture or
    died) no fleet manifest is written: the partial fleet stays
    invisible to resume."""
    rnd = int(stamp["round"])
    need = _role_prefixes(stamp)
    deadline = time.monotonic() + (timeout if timeout is not None
                                   else _fleet_timeout())
    while True:
        missing = [p for p in need
                   if not _res.validate_manifest(os.path.join(d, p), rnd)]
        if not missing:
            break
        if time.monotonic() >= deadline:
            _prof.inc_stat("ckpt_fleet_incomplete")
            log.warning("fleet checkpoint %s incomplete after %.0fs "
                        "(missing %s) — left uncommitted",
                        stamp.get("id"), _fleet_timeout(), missing)
            return False
        time.sleep(0.05)
    payload = dict(stamp)
    payload["format"] = FLEET_FORMAT
    payload["ts"] = time.time()
    payload["roles"] = need
    with _res.atomic_write(fleet_manifest_path(d), "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    _prof.inc_stat("ckpt_fleet_committed")
    _tel.record("checkpoint", fleet=stamp.get("id"), round=rnd,
                roles=len(need), dir=d)
    _ledger({"event": "checkpoint", "ckpt": stamp.get("id"),
             "round": rnd, "dir": d, "roles": len(need)})
    return True


def _ledger(row: Dict) -> None:
    try:
        from . import obs as _obs

        _obs.ledger_append(row)
    except Exception:
        pass


def _gc_old(base_dir: str, keep: int, protect: str) -> None:
    """Drop the oldest COMPLETE fleet checkpoints beyond ``keep``.
    Incomplete dirs are left alone (late writers may still be landing
    files into them; they cost little and are skipped at load)."""
    try:
        complete = []
        for name in sorted(os.listdir(base_dir)):
            if not name.startswith("ckpt_"):
                continue
            path = os.path.join(base_dir, name)
            if os.path.abspath(path) == os.path.abspath(protect):
                m = read_fleet_manifest(path)
            else:
                m = fleet_complete(path)
            if m is not None:
                complete.append((int(m["round"]), path))
        complete.sort()
        for _, path in complete[:-keep]:
            if os.path.abspath(path) == os.path.abspath(protect):
                continue
            shutil.rmtree(path, ignore_errors=True)
            _prof.inc_stat("ckpt_gc_removed")
    except OSError:
        pass


# ---------------------------------------------------------------------------
# capture helpers for the two trainer surfaces
# ---------------------------------------------------------------------------

def module_bundle(module, save_optimizer_states: bool = True):
    """``(arrays, states_bytes)`` for a bound `mx.mod.Module` — params
    synced from devices; optimizer state via the kvstore updater / the
    ZeRO-1 gather wire format when initialized."""
    arg, aux = module.get_params()
    arrays = {}
    for k, v in arg.items():
        arrays["arg:%s" % k] = v
    for k, v in aux.items():
        arrays["aux:%s" % k] = v
    states = None
    if save_optimizer_states and module.optimizer_initialized:
        try:
            states = module._optimizer_state_bytes()
        except Exception as e:
            log.warning("checkpoint: optimizer state skipped: %s", e)
    return arrays, states


def trainer_bundle(trainer, save_optimizer_states: bool = True):
    """``(arrays, states_bytes)`` for a `gluon.Trainer` — parameter
    data plus the updater/ZeRO-1 gathered state (`get_states` wire
    format, replica-count independent)."""
    arrays = {}
    for p in trainer._params:
        arrays["param:%s" % p.name] = p.data()
    states = None
    if save_optimizer_states:
        upd = getattr(trainer, "_zero1", None)
        if upd is None:
            upds = getattr(trainer, "_updaters", None)
            upd = upds[0] if upds else None
        if upd is not None:
            try:
                states = upd.get_states(dump_optimizer=True)
            except Exception as e:
                log.warning("checkpoint: optimizer state skipped: %s", e)
    return arrays, states


def _apply_arrays_to_module(module, arrays: Dict[str, np.ndarray]) -> None:
    from .ndarray import array as nd_array

    arg = {k[len("arg:"):]: nd_array(v) for k, v in arrays.items()
           if k.startswith("arg:")}
    aux = {k[len("aux:"):]: nd_array(v) for k, v in arrays.items()
           if k.startswith("aux:")}
    module.init_params(initializer=None, arg_params=arg, aux_params=aux,
                       allow_missing=True, force_init=True,
                       allow_extra=True)


def _apply_arrays_to_trainer(trainer, arrays: Dict[str, np.ndarray]) -> None:
    from .ndarray import array as nd_array

    by_name = {p.name: p for p in trainer._params}
    for k, v in arrays.items():
        if not k.startswith("param:"):
            continue
        p = by_name.get(k[len("param:"):])
        if p is not None:
            p.set_data(nd_array(v))


# ---------------------------------------------------------------------------
# the fleet checkpointer
# ---------------------------------------------------------------------------

class FleetCheckpointer(object):
    """Periodic + on-demand fleet-consistent checkpoints.

    ``kv=None`` runs in single-process mode (no stamp RPC, no server
    command — the fleet is just this worker, and ``fleet.json`` commits
    right after the local bundle lands).  With a `dist*` kvstore the
    scheduler stamps the checkpoint id so every worker lands the SAME
    (round, generation, live-worker-set) snapshot."""

    def __init__(self, kv=None, module=None, trainer=None,
                 get_bundle: Optional[Callable[[], Tuple[Dict, Optional[bytes]]]] = None,
                 loaders=None, directory: Optional[str] = None,
                 every: Optional[int] = None,
                 keep: Optional[int] = None,
                 extra_meta: Optional[Dict] = None):
        if get_bundle is None:
            if module is not None:
                get_bundle = lambda m=module: module_bundle(m)  # noqa: E731
            elif trainer is not None:
                get_bundle = lambda t=trainer: trainer_bundle(t)  # noqa: E731
            else:
                raise ValueError(
                    "FleetCheckpointer needs module=, trainer= or "
                    "get_bundle=")
        self._kv = kv
        self._get_bundle = get_bundle
        self._loaders = dict(loaders or {})
        self._dir = directory or ckpt_dir()
        if not self._dir:
            raise ValueError(
                "no checkpoint directory: pass directory= or set "
                "MXTPU_CKPT_DIR / MXTPU_RUN_DIR")
        self._every = ckpt_every() if every is None else int(every)
        self._keep_n = _keep() if keep is None else int(keep)
        self._extra_meta = extra_meta
        self._snap = snapshotter()
        self.last_id: Optional[str] = None

    @property
    def rank(self) -> int:
        return int(getattr(self._kv, "rank", 0))

    @property
    def every(self) -> int:
        return self._every

    def maybe_checkpoint(self, step: int) -> bool:
        """The step/round-boundary hook: checkpoint when ``step`` hits
        the cadence; costs one modulo otherwise."""
        if self._every > 0 and step > 0 and step % self._every == 0:
            return self.checkpoint(step)
        return False

    def _stamp(self, rnd: int) -> Dict:
        if self._kv is not None:
            return self._kv.checkpoint_stamp(rnd)
        return {"id": "r%06d_g%03d" % (rnd, 0), "round": int(rnd),
                "gen": 0, "num_workers": 1, "num_servers": 0,
                "workers": []}

    def checkpoint(self, step: int, wait: bool = False) -> bool:
        """Snapshot this worker (and, from rank 0, command the servers
        + commit the fleet manifest) at round ``step``.  Non-blocking
        by default: returns False if dropped because the previous
        write is still in flight."""
        rnd = int(step)
        stamp = self._stamp(rnd)
        d = fleet_dir(self._dir, stamp["id"])
        try:
            os.makedirs(d, exist_ok=True)
        except OSError as e:
            log.warning("checkpoint dir %s: %s", d, e)
            return False
        rank = self.rank
        if self._kv is not None and rank == 0 and \
                int(stamp.get("num_servers", 0)) > 0:
            self._kv.server_checkpoint(d, stamp)
        arrays, states = self._get_bundle()
        meta = {"role": "worker", "rank": rank, "step": int(step),
                "stamp": stamp,
                "run_state": collect_run_state(self._loaders,
                                               extra=self._extra_meta)}
        post = None
        if rank == 0:
            base, keep_n = self._dir, self._keep_n

            def post(d=d, stamp=stamp, base=base, keep_n=keep_n):
                if _commit_fleet(d, stamp):
                    _gc_old(base, keep_n, protect=d)
        ok = self._snap.capture(
            prefix=os.path.join(d, "worker%d" % rank), epoch=rnd,
            arrays=arrays, states=states, extra=meta, post=post,
            wait=wait)
        if ok:
            self.last_id = stamp["id"]
        return ok

    def flush(self, timeout: Optional[float] = None) -> bool:
        return self._snap.flush(timeout)


# ---------------------------------------------------------------------------
# restore
# ---------------------------------------------------------------------------

def restore_worker(kv=None, module=None, trainer=None, loaders=None,
                   directory: Optional[str] = None,
                   apply_states: bool = True) -> Optional[Dict]:
    """Restore this worker from a complete fleet checkpoint.

    Reads ``directory`` (default ``MXTPU_CKPT_RESTORE``), loads the
    bundle for this worker's RANK (scheduler-assigned — whichever
    process gets rank r restores bundle r), applies params to the
    module/trainer, restores RNG + DataLoader positions, and anchors
    the kvstore push/pull round at the recorded round so the first
    post-resume push lands as round R+1 against the servers' restored
    version vectors.  Call it AFTER ``bind()``/``init_params()`` and
    BEFORE ``init_optimizer()`` (the kvstore init of a restored key is
    a server-side no-op and the first pull returns the restored
    authoritative values).

    Returns the bundle meta (``step``, ``stamp``...) or None when no
    restore is armed."""
    d = directory or restore_dir()
    if not d:
        return None
    fleet = read_fleet_manifest(d)
    rank = int(getattr(kv, "rank", 0))
    found = load_worker_bundle(d, rank,
                               epoch=None if fleet is None
                               else int(fleet["round"]))
    if found is None:
        raise _res_error("no valid worker%d bundle under %s" % (rank, d))
    arrays, states, man = found
    meta = man.get("bundle", {}) or {}
    if module is not None:
        _apply_arrays_to_module(module, arrays)
    if trainer is not None:
        _apply_arrays_to_trainer(trainer, arrays)
        if apply_states and states is not None:
            # force the updater topology into existence first (the
            # ZeRO-1 updater is built lazily at _init_kvstore) so the
            # states land in the updater the steps will actually use
            if not getattr(trainer, "_kv_initialized", True):
                trainer._init_kvstore()
            upd = getattr(trainer, "_zero1", None)
            if upd is None:
                upds = getattr(trainer, "_updaters", None)
                upd = upds[0] if upds else None
            if upd is not None:
                # ZeRO-1 set_states re-shards under the ACTIVE plan:
                # a bundle written at N replicas resumes at M
                upd.set_states(states)
        if hasattr(trainer, "_num_steps"):
            trainer._num_steps = int(meta.get("step", 0))
    stamp = meta.get("stamp", {}) or {}
    rnd = int(stamp.get("round", man.get("epoch", 0)))
    if kv is not None and hasattr(kv, "resume_at_version"):
        kv.resume_at_version(rnd)
    apply_run_state(meta.get("run_state"), loaders)
    out = {"dir": d, "rank": rank, "round": rnd,
           "step": int(meta.get("step", rnd)),
           "id": stamp.get("id"), "states": states}
    _prof.inc_stat("ckpt_restored")
    _tel.record("resume", ckpt=stamp.get("id"), round=rnd,
                step=out["step"], rank=rank, dir=d)
    _ledger({"event": "resume", "ckpt": stamp.get("id"), "round": rnd,
             "step": out["step"], "rank": rank, "dir": d})
    log.info("mx.checkpoint: restored rank %d from %s (round %d, "
             "step %d)", rank, d, rnd, out["step"])
    return out


def _res_error(msg):
    from .base import MXNetError

    return MXNetError(msg)


# ---------------------------------------------------------------------------
# boundary hook + preemption (SIGTERM -> checkpoint-then-drain)
# ---------------------------------------------------------------------------

_AUTO: Optional[FleetCheckpointer] = None
_PREEMPT: Optional[Tuple[FleetCheckpointer, bool, int]] = None
_PREEMPT_DONE = threading.Event()
_PREEMPT_REMOVE: Optional[Callable[[], None]] = None


def arm(fc: FleetCheckpointer) -> None:
    """Arm periodic boundary checkpointing: `gluon.Trainer.step` and
    `FusedTrainLoop` call :func:`on_boundary` at every step / K-step
    boundary, which delegates to ``fc.maybe_checkpoint``."""
    global _AUTO
    _AUTO = fc


def disarm() -> None:
    global _AUTO, _PREEMPT, _PREEMPT_REMOVE
    _AUTO = None
    _PREEMPT = None
    if _PREEMPT_REMOVE is not None:
        try:
            _PREEMPT_REMOVE()
        except Exception:
            pass
        _PREEMPT_REMOVE = None
    _PREEMPT_DONE.clear()


def active() -> bool:
    """Cheap per-step gate for the boundary hook."""
    return _AUTO is not None or _PREEMPT is not None


def install_preemption(fc: FleetCheckpointer, exit_after: bool = True,
                       exit_code: int = 0) -> None:
    """SIGTERM → checkpoint-then-drain: on preemption the NEXT step /
    K-step boundary flushes one final fleet snapshot synchronously
    (``wait=True`` — the writer is drained, rank 0 commits the fleet
    manifest) and then exits cleanly, so ``--auto-resume`` restarts
    from the exact boundary the signal landed on.  The handler itself
    only sets a flag (`resilience.preempted`); all real work happens
    at the boundary, never in signal context."""
    global _PREEMPT, _PREEMPT_REMOVE
    _PREEMPT = (fc, bool(exit_after), int(exit_code))
    _PREEMPT_DONE.clear()
    if _PREEMPT_REMOVE is None:
        _PREEMPT_REMOVE = _res.install_preemption_hook(
            lambda: None, forward=False)


def on_boundary(step: int) -> None:
    """Called by the training surfaces at every step/K-step boundary
    (guarded by :func:`active` so the unarmed cost is one global
    read)."""
    fc = _AUTO
    if fc is not None and not _res.preempted():
        try:
            fc.maybe_checkpoint(step)
        except Exception as e:
            _prof.inc_stat("ckpt_boundary_failed")
            log.warning("boundary checkpoint failed at step %d: %s",
                        step, e)
    if _PREEMPT is not None and _res.preempted() and \
            not _PREEMPT_DONE.is_set():
        _PREEMPT_DONE.set()
        pfc, exit_after, exit_code = _PREEMPT
        try:
            pfc.checkpoint(step, wait=True)
            _prof.inc_stat("ckpt_preempt_flushed")
            _tel.record("checkpoint", reason="preemption", step=step,
                        fleet=pfc.last_id)
            log.info("mx.checkpoint: preemption snapshot flushed at "
                     "step %d (%s)", step, pfc.last_id)
        except Exception as e:
            _prof.inc_stat("ckpt_preempt_failed")
            log.warning("preemption snapshot failed at step %d: %s",
                        step, e)
        if exit_after:
            raise SystemExit(exit_code)
