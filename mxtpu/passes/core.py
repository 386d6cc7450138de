"""GraphPass base class and the PassManager.

A pass is a named graph-to-graph rewrite over the SymbolNode DAG that
must be OUTPUT-IDENTICAL: for any inputs (and RNG key), the rewritten
graph produces the same outputs as the original, bitwise (dce/fold/
cse/fuse never change the op sequence applied to any value).  The parity
contract is enforced by ``tools/check_passes.py`` (tier-1) across all
three dispatch paths.

The manager owns ordering: passes always execute in the canonical
order (``dce, fold, cse, fuse``) regardless of how the enabled
set was spelled, because the phases feed each other — identity
elimination exposes constants, folding creates value-keyed CSE
opportunities, and CSE lengthens single-consumer chains.  Per-pass
wall time and node deltas land in ``profiler.stats()`` as
``pass_runs::<name>`` / ``pass_wall_us::<name>`` /
``pass_nodes_removed::<name>``.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

from ..base import MXNetError
from ..symbol.symbol import Symbol
from .graph import clone_graph, node_count

__all__ = ["GraphPass", "PassManager", "register_pass", "pass_names"]


class GraphPass(object):
    """Base class: subclass, set ``name``, implement :meth:`run`.

    ``run`` mutates the (already cloned, private) graph in place and
    returns a stats dict merged into the pass report.  It must preserve
    output arity/order and the name->slot mapping of surviving
    variables (the executor maps variables positionally by name)."""

    name = "graph-pass"

    def run(self, symbol: Symbol) -> Dict[str, Any]:
        raise NotImplementedError

    def __repr__(self):
        return "<GraphPass %s>" % self.name


# name -> zero-arg factory, in canonical execution order
_PASS_FACTORIES: "Dict[str, Any]" = {}
_CANONICAL: List[str] = []


def register_pass(name: str, factory) -> None:
    """Register a pass factory under ``name``; registration order IS
    the canonical execution order."""
    if name in _PASS_FACTORIES:
        raise MXNetError("graph pass %r already registered" % name)
    _PASS_FACTORIES[name] = factory
    _CANONICAL.append(name)


def pass_names() -> List[str]:
    return list(_CANONICAL)


class PassManager(object):
    """Run a set of passes over a private clone of a Symbol graph."""

    def __init__(self, passes):
        self.passes = list(passes)

    def run(self, symbol: Symbol) -> Tuple[Symbol, Dict[str, Any]]:
        from .. import profiler as _prof

        work = clone_graph(symbol)
        n0 = node_count(work)
        records: List[Dict[str, Any]] = []
        for p in self.passes:
            nb = node_count(work)
            t0 = time.perf_counter()
            stats = p.run(work) or {}
            wall_us = int((time.perf_counter() - t0) * 1e6)
            na = node_count(work)
            _prof.inc_stat("pass_runs::%s" % p.name)
            _prof.inc_stat("pass_wall_us::%s" % p.name, wall_us)
            if nb > na:
                _prof.inc_stat("pass_nodes_removed::%s" % p.name, nb - na)
            rec = {"pass": p.name, "wall_us": wall_us,
                   "nodes_before": nb, "nodes_after": na}
            rec.update(stats)
            records.append(rec)
        report = {"nodes_before": n0, "nodes_after": node_count(work),
                  "passes": records}
        return work, report
