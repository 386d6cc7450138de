"""`mx.tune`: measured-trial autotuner over the framework's knob space.

Closes the loop the observability stack opened (TVM-style, arXiv
1802.04799): the repo's hand-picked performance knobs —
``steps_per_program``, shape buckets, ``MXTPU_PASSES`` subsets, remat
policy, donation, layout, the serve batcher — become a SEARCHED space
instead of documentation burden.  Three pieces:

  * :mod:`~mxtpu.tune.registry` — subsystems declare their tunables
    (name, domain, env var, apply hook); seeded with every knob in
    `docs/env_vars.md`.
  * :mod:`~mxtpu.tune.trial` + :mod:`~mxtpu.tune.search` — measured
    trials through ``bench_common``-speaking benches in subprocesses
    (one bench row per trial, appended to the ``MXTPU_RUN_DIR``
    ledger so `tools/compare_runs.py` and `mx.obs` see tuning
    history), driven by cost-model-seeded successive halving.
  * :mod:`~mxtpu.tune.db` — winning configs persisted per (graph
    fingerprint, backend, batch profile) with atomic writes, and
    **auto-applied** at ``Module.bind`` / ``hybridize`` /
    ``serve.add_model`` when ``MXTPU_TUNE=apply`` — with provenance
    on `mx.inspect` program records and a ``tuning`` telemetry event.

Auto-apply is OFF by default: every hook reduces to one cached check
(:func:`apply_enabled`).  Typical workflow::

    # search (one-off, writes the DB):
    result = mx.tune.tune(
        [sys.executable, "benchmark/python/bench_train_loop.py"],
        symbol=net, profile="b32", max_trials=12)

    # every later run (applies the DB at bind):
    MXTPU_TUNE=apply python train.py
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional, Sequence

from ..base import getenv
from . import db, registry, search as search_mod, trial as trial_mod
from .db import entry_key, lookup, make_entry, store
from .registry import (Knob, apply_config, current_config, declare,
                       defaults, env_for_config, get, knobs, names,
                       validate_config)
from .search import SearchResult, cost_model_priors, search
from .trial import Trial, TrialRunner, objective

__all__ = [
    "Knob", "declare", "get", "knobs", "names", "defaults",
    "current_config", "validate_config", "apply_config",
    "env_for_config",
    "Trial", "TrialRunner", "objective",
    "SearchResult", "search", "cost_model_priors",
    "lookup", "store", "make_entry", "entry_key",
    "mode", "enable", "apply_enabled", "maybe_apply",
    "current_applied", "tune", "fingerprint_of", "profile_of_shapes",
]

_lock = threading.Lock()
_MODE = (getenv("MXTPU_TUNE", "0") or "0").strip().lower()
#: provenance of the last auto-applied DB config in this process
#: (knobs are process-global env, so the ambient string is truthful
#: for every program built after the apply)
_APPLIED: Optional[str] = None
_APPLIED_KEYS: set = set()


def mode() -> str:
    """The tuner mode: ``"apply"`` (DB configs auto-apply at bind) or
    ``"off"``.  From ``MXTPU_TUNE`` at import (``apply``/``1``/``true``
    arm it); flip at runtime with :func:`enable`."""
    return "apply" if _MODE in ("apply", "1", "true") else "off"


def enable(on: Any = "apply") -> None:
    """Flip auto-apply at runtime (tests / embedding).  ``on`` may be
    a mode string or a bool."""
    global _MODE
    if isinstance(on, bool):
        _MODE = "apply" if on else "0"
    else:
        _MODE = str(on).strip().lower()


def apply_enabled() -> bool:
    """The ONE check every bind/hybridize/add_model hook pays when the
    tuner is off (the default)."""
    return _MODE in ("apply", "1", "true")


def current_applied() -> Optional[str]:
    """Provenance string of the auto-applied tuning config active in
    this process (e.g. ``"tune:key=ab12cd34,donate=0"``), or None.
    `mx.inspect.program` stamps this on every program record."""
    return _APPLIED


def fingerprint_of(symbol=None, name: Optional[str] = None) -> str:
    """The graph identity a DB entry is keyed on: the name-independent
    :func:`mxtpu.compile_cache.graph_fingerprint` when a symbol is in
    hand, else a literal ``name:...`` key (serve models are registered
    by name before any trace exists)."""
    if symbol is not None:
        from .. import compile_cache as _cc

        return _cc.graph_fingerprint(symbol)
    if name:
        return "name:%s" % name
    raise ValueError("fingerprint_of needs a symbol or a name")


def profile_of_shapes(shapes) -> str:
    """Canonical batch-profile string from bind-style data shapes
    (``[(name, shape), ...]`` pairs or DataDesc tuples):
    ``"data=32x64,label=32"``.  The profile half of the DB key."""
    parts = []
    for d in shapes or []:
        try:
            name, shape = d[0], tuple(d[1])
        except Exception:
            continue
        parts.append("%s=%s" % (name, "x".join(str(int(s))
                                               for s in shape)))
    return ",".join(parts)


def _backend() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:
        return "unknown"


def _require_parent_off_chip() -> None:
    """Trials are subprocesses and each needs the chip, which belongs
    to one process at a time: a parent that has already initialized an
    accelerator backend would make every trial fail or hang."""
    import jax
    from jax._src import xla_bridge as _xb

    if _xb.backends_are_initialized() and jax.default_backend() != "cpu":
        from ..base import MXNetError

        raise MXNetError(
            "mx.tune.tune: this process already holds the %s device, "
            "and a chip belongs to one process at a time, so the trial "
            "subprocesses could not reach it; start the tuning session "
            "from a process that has not run anything on the device "
            "(build Symbols only), or under JAX_PLATFORMS=cpu"
            % jax.default_backend())


def maybe_apply(symbol=None, name: Optional[str] = None,
                profile: str = "", site: str = "bind") -> Optional[str]:
    """Auto-apply hook: when ``MXTPU_TUNE=apply`` and the tuning DB
    holds an entry for this (graph, backend, profile), install its
    config and return the provenance string; otherwise None.

    Called from ``Module.bind``, ``HybridBlock._build_cache`` and
    ``serve.Server.add_model``.  Off (the default) this is one bool
    check.  A DB miss, an unreadable entry, or a config whose knobs
    have since narrowed their domains all degrade to "no apply" — the
    tuner must never take a bind down."""
    if not apply_enabled():
        return None
    global _APPLIED
    try:
        graph = fingerprint_of(symbol, name)
        backend = _backend()
        entry = db.lookup(graph, backend, profile)
        if entry is None and profile:
            entry = db.lookup(graph, backend, "*")
        if entry is None:
            return None
        key = entry["key"]
        with _lock:
            seen = key in _APPLIED_KEYS
            _APPLIED_KEYS.add(key)
        cfg = registry.apply_config(entry["config"])
        prov = "tune:key=%s,%s" % (
            key[:8], ",".join("%s=%s" % kv for kv in sorted(cfg.items())))
        _APPLIED = prov
        from .. import profiler as _prof
        from .. import telemetry as _tel

        _prof.inc_stat("tune_apply")
        if not seen:
            _tel.record("tuning", action="apply", site=site, key=key,
                        provenance=prov, profile=profile or None,
                        config=json.dumps(cfg, sort_keys=True))
        return prov
    except Exception:
        from .. import profiler as _prof

        _prof.inc_stat("tune_apply_errors")
        return None


def tune(bench_argv: Sequence[str],
         symbol=None, name: Optional[str] = None,
         profile: str = "",
         knob_names: Optional[Sequence[str]] = None,
         max_trials: int = 16,
         run_dir: Optional[str] = None,
         timeout_s: float = 300.0,
         db_dir: Optional[str] = None,
         seed: int = 0,
         store_db: bool = True) -> SearchResult:
    """One full tuning session: measure, search, persist the winner.

    ``bench_argv`` is a ``bench_common``-speaking benchmark command
    (its env decides what it measures — the trial runner injects each
    candidate config).  The winning config (never worse than the
    measured baseline) is stored in the tuning DB under
    (``symbol``/``name`` fingerprint, backend, ``profile``) so later
    processes with ``MXTPU_TUNE=apply`` pick it up at bind.

    The cost model is seeded from the program's ``inspect``
    cost-analysis when a symbol's program is registered, plus the
    baseline trial's phase attribution (see
    :func:`~mxtpu.tune.search.cost_model_priors`).
    """
    from .. import telemetry as _tel

    _require_parent_off_chip()
    analysis = None
    if symbol is not None:
        try:
            from .. import inspect as _inspect

            rec = _inspect.find_for_symbol(symbol)
            if rec is not None:
                si = rec.latest_sig()
                if si is not None:
                    analysis = si.analyze()
        except Exception:
            analysis = None
    runner = trial_mod.TrialRunner(bench_argv, run_dir=run_dir,
                                   timeout_s=timeout_s)
    result = search_mod.search(runner, knob_names=knob_names,
                               max_trials=max_trials, seed=seed,
                               analysis=analysis)
    entry_path = None
    if store_db:
        graph = fingerprint_of(symbol, name)
        entry = db.make_entry(
            graph, _backend(), profile, result.config,
            metric=result.score, baseline_metric=result.baseline_score,
            trials=len(result.trials), run_ids=result.run_ids)
        entry_path = db.store(entry, db_dir)
    _tel.record("tuning", action="session",
                trials=len(result.trials), score=result.score,
                baseline=result.baseline_score,
                improved=result.improved,
                config=json.dumps(result.config, sort_keys=True),
                db_path=entry_path)
    return result


def _metrics() -> Dict[str, Any]:
    from .. import profiler as _prof

    stats = _prof.stats()
    return {"mode": mode(), "applied": _APPLIED,
            "trials": stats.get("tune_trials", 0),
            "applies": stats.get("tune_apply", 0)}


def _register_provider() -> None:
    try:
        from .. import telemetry as _tel

        _tel.register_metrics_provider("tune", _metrics)
    except Exception:
        pass


_register_provider()
