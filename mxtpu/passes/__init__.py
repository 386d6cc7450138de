"""mx.passes — symbol-level graph-rewrite pass framework.

The mid-level IR layer between Symbol construction and XLA tracing
(ROADMAP item 3, grounded in Relay's pass design — arXiv 1810.00952 —
and TVM's fusion/layout playbook — arXiv 1802.04799).  Every compile
path (Executor bind, CachedOp, FusedTrainLoop, control-flow subgraph
lowering) funnels through ``executor._build_graph_fn``, which calls
:func:`optimize_for_build` here, so graph-level decisions — folding,
fusion grouping — are composable passes instead of call-site hacks.

Built-in passes, in canonical execution order:

  ``dce``    identity elimination + reachability liveness
  ``fold``   constant folding (initializer-only subgraphs evaluated
             once at bind; ``MXTPU_FOLD_MAX_BYTES`` caps embeds)
  ``cse``    common-subexpression elimination (value-keyed for folded
             constants)
  ``fuse``   elementwise-chain fusion grouping (one node, one
             named_scope, one `mx.inspect` layer per chain)

Configuration — ``MXTPU_PASSES``:

  unset / ``1`` / ``default``   the default set above
  ``0`` / ``off`` / ``none``    disable the pipeline entirely
  ``dce,fold``                  exactly these passes
  ``default,-fuse``             the default set minus one

Spelling order never matters: the manager always executes in canonical
order.  :func:`scope` overrides the spec for a ``with`` block (tests,
A/B comparisons); `Symbol.optimize` applies a one-off spec.

Every pass is OUTPUT-IDENTICAL, bitwise (including RNG-consuming
graphs: ``ensure_rng_ids`` pins a stable per-node fold_in id so
rewrites cannot reseed dropout) — enforced in tier-1 by
``tools/check_passes.py``.  Optimized graphs are cached per (graph
identity, spec); provenance reports ride on `mx.inspect` program
records and telemetry ``compile`` events, and per-pass timings land in
``profiler.stats()``.
"""
from __future__ import annotations

import collections
import threading
import weakref
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from ..base import MXNetError, getenv
from ..symbol.symbol import Symbol
from .core import (GraphPass, PassManager, pass_names, register_pass,
                   _PASS_FACTORIES)
from .graph import (clone_graph, consumer_map, ensure_rng_ids,
                    make_const_node, node_count, op_node_count,
                    rewrite_entries, rng_id_of)
from .dce_cse import CSEPass, DeadNodePass
from .fold import ConstantFoldPass
from .fuse import ElemwiseFusionPass, FUSABLE_OPS
from .sharding import ShardingPass, shard_requested

__all__ = [
    "GraphPass", "PassManager", "register_pass", "pass_names",
    "DeadNodePass", "CSEPass", "ConstantFoldPass", "ElemwiseFusionPass",
    "ShardingPass", "optimize", "optimize_for_build",
    "provenance_for", "provenance_summary", "ensure_rng_ids",
    "rng_id_of", "scope", "current_spec", "FUSABLE_OPS",
]

# canonical order is registration order (see core.PassManager doc).
# shard runs LAST (annotation-only): its specs must land on the
# variables that SURVIVE dce/fold/cse and sit under the final fused
# graph — and it must never give the rewriting passes annotated nodes
# they'd have to preserve.
register_pass("dce", DeadNodePass)
register_pass("fold", ConstantFoldPass)
register_pass("cse", CSEPass)
register_pass("fuse", ElemwiseFusionPass)
register_pass("shard", ShardingPass)

_local = threading.local()
_cache_lock = threading.Lock()
_MAX_CACHE = 128
# graph-identity key -> {"refs", "spec", "sym", "report"}
_OPT_CACHE: "collections.OrderedDict[Tuple, Dict[str, Any]]" = \
    collections.OrderedDict()


# ---------------------------------------------------------------------------
# Spec parsing / config
# ---------------------------------------------------------------------------

def _default_names() -> List[str]:
    return [n for n in pass_names() if n != "shard" or shard_requested()]


def parse_spec(spec: Union[None, str, Sequence[str]]) -> Tuple[str, ...]:
    """Normalize a pass spec to the canonical-order tuple of names."""
    if spec is None:
        spec = getenv("MXTPU_PASSES") or "default"
    if not isinstance(spec, str):
        toks = list(spec)
    else:
        s = spec.strip().lower()
        if s in ("", "1", "on", "true", "default"):
            toks = ["default"]
        elif s in ("0", "off", "none", "false"):
            return ()
        else:
            toks = [t.strip() for t in spec.split(",") if t.strip()]
    names: set = set()
    for tok in toks:
        if tok in ("default", "all"):
            names |= set(_default_names() if tok == "default"
                         else pass_names())
            continue
        neg = tok.startswith("-")
        t = tok[1:] if neg else tok
        if t not in _PASS_FACTORIES:
            raise MXNetError(
                "unknown graph pass %r (known: %s; spec grammar: "
                "'default', 'off', 'dce,fold', 'default,-fuse')"
                % (t, ",".join(pass_names())))
        (names.discard if neg else names.add)(t)
    return tuple(n for n in pass_names() if n in names)


_SPEC_MEMO: Dict[Tuple, Tuple[str, ...]] = {}


def current_spec() -> Tuple[str, ...]:
    """The active pass set: a :func:`scope` override if one is live,
    else ``MXTPU_PASSES`` (re-read per call — flip it between binds).
    Parses are memoized by (raw string, shard request) — this runs on
    every graph build."""
    ov = getattr(_local, "spec", None)
    if ov is not None:
        return ov
    raw = getenv("MXTPU_PASSES") or "default"
    memo_key = (raw, shard_requested())
    spec = _SPEC_MEMO.get(memo_key)
    if spec is None:
        spec = parse_spec(raw)
        if len(_SPEC_MEMO) > 64:
            _SPEC_MEMO.clear()
        _SPEC_MEMO[memo_key] = spec
    return spec


class scope(object):
    """``with passes.scope("off"): ...`` — override the pass spec for
    graphs BUILT inside the block (bind/hybridize time, like amp).
    ``None`` resolves to the active ``MXTPU_PASSES`` configuration —
    the same convention as ``optimize(passes=None)``."""

    def __init__(self, spec: Union[None, str, Sequence[str]]):
        self._spec = parse_spec(spec)

    def __enter__(self):
        self._old = getattr(_local, "spec", None)
        _local.spec = self._spec
        return self

    def __exit__(self, *exc):
        _local.spec = self._old


# ---------------------------------------------------------------------------
# Optimize + cache + provenance
# ---------------------------------------------------------------------------

def _cache_key(symbol: Symbol) -> Tuple:
    return tuple((id(n), i) for n, i in symbol._outputs)


def _entry_alive(ent: Dict[str, Any]) -> bool:
    return all(r() is not None for r in ent["refs"])


def optimize(symbol: Symbol,
             passes: Union[None, str, Sequence[str]] = None
             ) -> Tuple[Symbol, Optional[Dict[str, Any]]]:
    """Run the pass pipeline over ``symbol`` (uncached, explicit spec).
    Returns ``(optimized symbol, report)`` — ``(symbol, None)`` when
    the spec resolves empty.  The input graph is never mutated beyond
    RNG-id stamping (which is semantics-preserving and idempotent)."""
    names = parse_spec(passes) if passes is not None else current_spec()
    if not names:
        return symbol, None
    ensure_rng_ids(symbol)
    mgr = PassManager([_PASS_FACTORIES[n]() for n in names])
    opt, report = mgr.run(symbol)
    report["spec"] = ",".join(names)
    return opt, report


def optimize_for_build(symbol: Symbol
                       ) -> Tuple[Symbol, Optional[Dict[str, Any]]]:
    """The `_build_graph_fn` entry point: :func:`optimize` under the
    active spec, memoized per (graph identity, spec) so an Executor's
    infer/train builds — and FusedTrainLoop rebuilding the same bound
    symbol — optimize once."""
    names = current_spec()
    if not names:
        return symbol, None
    key = _cache_key(symbol)
    from .. import amp as _amp

    # fold bakes values under the ACTIVE compute-dtype policy, so the
    # same graph bound under a different amp scope must re-optimize;
    # likewise shard stamps the ACTIVE plan's specs, so a plan change
    # (or deactivation) invalidates the memo
    spec = ",".join(names) + "|amp=%s" % _amp.get_compute_dtype()
    if "shard" in names:
        from ..sharding.plan import current_plan as _cur_plan

        plan = _cur_plan()
        spec += "|plan=%s" % (plan.describe() if plan is not None else "-")
    with _cache_lock:
        ent = _OPT_CACHE.get(key)
        if ent is not None and ent["spec"] == spec and _entry_alive(ent):
            _OPT_CACHE.move_to_end(key)
            return ent["sym"], ent["report"]
    opt, report = optimize(symbol, names)
    with _cache_lock:
        _OPT_CACHE[key] = {
            "refs": [weakref.ref(n) for n, _ in symbol._outputs],
            "spec": spec, "sym": opt, "report": report,
        }
        _OPT_CACHE.move_to_end(key)
        while len(_OPT_CACHE) > _MAX_CACHE:
            _OPT_CACHE.popitem(last=False)
    return opt, report


def provenance_for(symbol) -> Optional[Dict[str, Any]]:
    """The pass report of the most recent :func:`optimize_for_build`
    of this graph (any spec), or None — how `mx.inspect` attaches
    pass provenance to program records."""
    try:
        key = _cache_key(symbol)
    except Exception:
        return None
    with _cache_lock:
        ent = _OPT_CACHE.get(key)
        if ent is not None and _entry_alive(ent):
            return ent["report"]
    return None


def provenance_summary(report: Optional[Dict[str, Any]]) -> Optional[str]:
    """Compact provenance string for telemetry ``compile`` events,
    e.g. ``"dce,fold,cse,fuse:34->21"``."""
    if not report:
        return None
    return "%s:%d->%d" % (report.get("spec", "?"),
                          report.get("nodes_before", 0),
                          report.get("nodes_after", 0))


def reset_cache() -> None:
    """Drop memoized optimized graphs (tests)."""
    with _cache_lock:
        _OPT_CACHE.clear()
