"""mx.shard — the sharding-aware distributed backbone.

One `ShardingPlan` (data/model axes, per-param PartitionSpecs,
optimizer-state sharding ON by default) is chosen once — by
Trainer/Module, a `with plan.activate():` scope, or ``MXTPU_SHARD=zero1``
— and consumed everywhere:

  * `gluon.Trainer` / `Module` replace their N redundant per-replica
    updaters with ONE :class:`ZeRO1Updater` holding each param's Adam
    state in N disjoint chunks (arXiv 2004.13336): slice the merged
    grad (reduce-scatter), update the chunk, allgather the params.
  * `FusedTrainLoop` shards its scanned opt-state carry over the
    plan's mesh (GSPMD compiles the same reduce-scatter/allgather
    into the K-step program).
  * ``kvstore=tpu`` and `mxtpu.parallel` resolve their collective
    axis/mesh from the plan instead of hand-wired call sites.
  * the ``shard`` graph pass (`mxtpu/passes/sharding.py`) stamps the
    decision onto the Symbol graph — provenance on `mx.inspect`
    program records and telemetry ``compile`` events.
  * :func:`reshard` moves params/state between two plans' layouts
    (train<->serve, arXiv 2112.01075) in one device_put per leaf.

See `docs/sharding.md` for the workflow and `tools/check_sharding.py`
(tier-1) for the parity + memory contract.
"""
from __future__ import annotations

from .plan import (ShardingPlan, auto_plan, current_plan,
                   default_min_shard_elems, opt_state_sharding_default,
                   plan_scope, shard_requested)
from .zero1 import ZeRO1Updater, hbm_report, state_nbytes, tree_nbytes
from .reshard import reshard

__all__ = [
    "ShardingPlan", "ZeRO1Updater", "auto_plan", "current_plan",
    "default_min_shard_elems", "hbm_report",
    "opt_state_sharding_default", "plan_scope", "reshard",
    "shard_requested", "state_nbytes", "tree_nbytes",
]
