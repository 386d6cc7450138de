"""Device-mesh management.

The reference discovers GPU link topology (PCIe/NVLink) and builds
spanning-tree reduction schedules (`src/kvstore/gpu_topology.h`).  On TPU
the topology is the ICI torus and XLA owns the schedule, so the only job
here is choosing a logical `jax.sharding.Mesh` over the chips and keeping
a current-mesh stack (analogous to the reference's Context stack,
`python/mxnet/context.py`).

Axis vocabulary (canonical order, outermost first):
  dp — data parallel (batch dimension)
  pp — pipeline parallel (layer stages)
  tp — tensor parallel (weight matrices)
  sp — sequence/context parallel (ring attention)
  ep — expert parallel (MoE all_to_all)
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError

AXIS_DP = "dp"
AXIS_TP = "tp"
AXIS_PP = "pp"
AXIS_SP = "sp"
AXIS_EP = "ep"

_CANONICAL_ORDER = (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP, AXIS_EP)

_state = threading.local()


def default_mesh_shape(n_devices: int,
                       tp: int = 1, pp: int = 1, sp: int = 1,
                       ep: int = 1) -> Dict[str, int]:
    """Factor n_devices into a mesh shape; dp absorbs the remainder."""
    denom = tp * pp * sp * ep
    if denom <= 0 or n_devices % denom != 0:
        raise MXNetError(
            "cannot factor %d devices into tp=%d pp=%d sp=%d ep=%d"
            % (n_devices, tp, pp, sp, ep))
    return {AXIS_DP: n_devices // denom, AXIS_PP: pp, AXIS_TP: tp,
            AXIS_SP: sp, AXIS_EP: ep}


def create_mesh(shape: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence] = None,
                axis_order: Optional[Sequence[str]] = None):
    """Create a `jax.sharding.Mesh`.

    Axes of size 1 are kept in the mesh (so PartitionSpecs mentioning
    them always resolve); XLA elides collectives over singleton axes.
    Device order follows `jax.devices()`, which on TPU enumerates chips
    in torus-contiguous order so that the innermost (rightmost) mesh
    axes land on ICI neighbors — put sp/tp innermost, dp outermost, and
    ring ppermute rides nearest-neighbor links.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if shape is None:
        shape = default_mesh_shape(len(devices))
    order = list(axis_order) if axis_order is not None else \
        [a for a in _CANONICAL_ORDER if a in shape]
    for a in shape:
        if a not in order:
            order.append(a)
    sizes = [int(shape[a]) for a in order]
    total = int(np.prod(sizes)) if sizes else 1
    if total != len(devices):
        raise MXNetError("mesh shape %r needs %d devices, have %d"
                         % (shape, total, len(devices)))
    dev_array = np.array(devices, dtype=object).reshape(sizes)
    return jax.sharding.Mesh(dev_array, tuple(order))


def current_mesh():
    """Innermost active mesh (set with `MeshContext`), or None."""
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


class MeshContext(object):
    """`with MeshContext(mesh):` — like the reference's Context scope but
    for a whole device mesh.  It is this package's own stack, read back
    through :func:`current_mesh`; it enters no ambient JAX mesh
    (`jax.set_mesh`), because every consumer (`collectives`, the `tpu`
    kvstore, the sharding plan) hands the mesh explicitly to
    `jax.shard_map` / `NamedSharding` and none uses bare
    PartitionSpecs."""

    def __init__(self, mesh):
        self._mesh = mesh

    def __enter__(self):
        if not hasattr(_state, "stack"):
            _state.stack = []
        _state.stack.append(self._mesh)
        return self._mesh

    def __exit__(self, *exc):
        _state.stack.pop()
        return False
