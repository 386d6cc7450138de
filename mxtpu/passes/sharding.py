"""Sharding pass: the partitioning decision as a graph annotation.

ROADMAP item 3a: item 1's `ShardingPlan` expressed as an `mx.passes`
rewrite instead of call-site pjit plumbing.  The pass stamps every
VARIABLE node of the graph with the spec the active plan assigns it —
``__shard_spec__`` (PartitionSpec string) plus ``__shard_state_dim__``
for params whose optimizer state the ZeRO-1 engine will chunk — and
reports the plan on the pass record, which is how the decision becomes
visible on `mx.inspect` program records and telemetry ``compile``
events (the acceptance contract of `tools/check_sharding.py`).

The pass is annotation-only: it never adds, removes or reorders nodes,
never touches ``__rng_id__``, and on a 1-shard plan (or none) it is a
strict no-op — so it is trivially bitwise output-identical and composes
with dce/fold/cse/fuse in any spelled order (canonical order places it
LAST, after fusion, so annotations land on the surviving variables of
the final graph).

It joins the default pass set only when requested: when a
`ShardingPlan` is active (`mx.shard.current_plan()`).
"""
from __future__ import annotations

from typing import Any, Dict

from ..symbol.symbol import Symbol, _topo_order
from .core import GraphPass

__all__ = ["ShardingPass", "shard_requested"]


def shard_requested() -> bool:
    """An active plan pulls ``shard`` into the default pass set — the
    ONE definition lives in `sharding.plan` (lazy import: the pass
    framework loads before the sharding package)."""
    from ..sharding.plan import shard_requested as _impl

    return _impl()


class ShardingPass(GraphPass):
    name = "shard"

    def run(self, symbol: Symbol) -> Dict[str, Any]:
        from ..sharding.plan import current_plan

        plan = current_plan()
        if plan is None or plan.num_shards <= 1:
            # 1-device mesh / no plan: strict no-op (bitwise trivially)
            return {"annotated": 0, "state_sharded": 0, "plan": None}
        annotated = state_sharded = 0
        for n in _topo_order(symbol._outputs):
            if not n.is_variable:
                continue
            shape = _known_shape(n)
            spec = plan.spec_for(n.name, shape)
            n.ext_attrs["__shard_spec__"] = str(spec)
            annotated += 1
            if shape and not n.is_aux and n.name not in plan.data_names:
                dim = plan.shard_dim(n.name, shape)
                if dim is not None:
                    n.ext_attrs["__shard_state_dim__"] = str(dim)
                    state_sharded += 1
        return {"annotated": annotated, "state_sharded": state_sharded,
                "plan": plan.describe()}


def _known_shape(node):
    """Static shape a variable declared at construction (`sym.Variable
    (shape=...)` stores ``__shape__`` in ext_attrs); () when unknown —
    spec_for treats it as replicated and shard_dim is skipped (the
    ZeRO-1 updater re-derives dims from the bound arrays anyway)."""
    shp = node.ext_attrs.get("__shape__")
    if not shp:
        return ()
    try:
        import ast

        val = ast.literal_eval(shp) if isinstance(shp, str) else shp
        return tuple(int(s) for s in val)
    except Exception:
        return ()
