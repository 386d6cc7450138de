"""Persistent tuning DB: graph identity + backend + batch profile -> config.

Keyed like the compile cache: a sha256 over a canonical JSON header
(``mxtpu-tune-v1`` schema, name-independent graph fingerprint from
:func:`mxtpu.compile_cache.graph_fingerprint`, the jax backend, and a
batch-profile string), so two processes that bind the same
architecture at the same batch geometry resolve the same entry file
even though gluon auto-uniquifies node names per process.

One entry per key, one JSON file per entry, written with
``resilience.atomic_write`` (temp + fsync + rename) so a reader never
observes a torn entry; garbage files are treated as cache misses, not
errors — a tuning DB must never take a training job down.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional

SCHEMA = "mxtpu-tune-v1"

__all__ = ["SCHEMA", "db_dir", "entry_key", "store", "lookup",
           "entries", "make_entry"]


def db_dir(path: Optional[str] = None) -> str:
    """Resolve the DB directory: explicit arg > ``MXTPU_TUNE_DB`` env
    > ``<checkout>/.tune_db`` (derived from the package path like the
    compile-cache default, so it travels with the checkout)."""
    return path or os.environ.get("MXTPU_TUNE_DB") \
        or os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".tune_db")


def entry_key(graph: str, backend: str, profile: str) -> str:
    """Stable content key: sha256 over the canonical key header."""
    header = json.dumps(
        {"schema": SCHEMA, "graph": graph, "backend": backend,
         "profile": profile},
        sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(header.encode("utf-8")).hexdigest()


def make_entry(graph: str, backend: str, profile: str,
               config: Dict[str, str],
               metric: Optional[float] = None,
               baseline_metric: Optional[float] = None,
               trials: int = 0,
               run_ids: Optional[List[str]] = None,
               extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    entry = {
        "schema": SCHEMA,
        "key": entry_key(graph, backend, profile),
        "graph": graph,
        "backend": backend,
        "profile": profile,
        "config": dict(config),
        "metric": metric,
        "baseline_metric": baseline_metric,
        "trials": trials,
        "run_ids": list(run_ids or []),
        "ts": time.time(),
    }
    if extra:
        entry["extra"] = dict(extra)
    return entry


def _entry_path(directory: str, key: str) -> str:
    return os.path.join(directory, key + ".json")


def store(entry: Dict[str, Any],
          directory: Optional[str] = None) -> str:
    """Atomically persist ``entry`` under its key; returns the path."""
    from ..resilience import atomic_write

    d = db_dir(directory)
    os.makedirs(d, exist_ok=True)
    path = _entry_path(d, entry["key"])
    data = json.dumps(entry, sort_keys=True, indent=1,
                      default=str).encode("utf-8")
    with atomic_write(path, mode="wb") as f:
        f.write(data)
    return path


def lookup(graph: str, backend: str, profile: str,
           directory: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The stored entry for this (graph, backend, profile), or None.

    Torn/garbage entry files read as a miss: the DB is advisory."""
    path = _entry_path(db_dir(directory),
                       entry_key(graph, backend, profile))
    try:
        with open(path, "r", encoding="utf-8") as f:
            entry = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(entry, dict) or entry.get("schema") != SCHEMA \
            or not isinstance(entry.get("config"), dict):
        return None
    return entry


def entries(directory: Optional[str] = None) -> List[Dict[str, Any]]:
    """Every readable entry in the DB (skipping garbage), newest first."""
    d = db_dir(directory)
    out = []
    try:
        names = [n for n in os.listdir(d) if n.endswith(".json")]
    except OSError:
        return out
    for name in names:
        try:
            with open(os.path.join(d, name), "r",
                      encoding="utf-8") as f:
                entry = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(entry, dict) and entry.get("schema") == SCHEMA:
            out.append(entry)
    out.sort(key=lambda e: e.get("ts") or 0, reverse=True)
    return out
