"""Device contexts.

Re-design of the reference's `include/mxnet/base.h` ``Context`` and
`python/mxnet/context.py`.  A ``Context`` names a logical device
(``cpu(0)``, ``tpu(0)``...) and maps onto a concrete ``jax.Device``.
The reference's ``gpu(i)`` is accepted as an alias for ``tpu(i)`` so model
scripts written against the reference run with only a context swap (the
north-star requirement in BASELINE.json).

Unlike the reference there is no stream/device-ordinal plumbing below this:
placement is carried by committed jax Arrays, and XLA/PJRT owns streams.
``cpu_pinned``/``cpu_shared`` collapse onto the host CPU device (PJRT host
buffers are already DMA-visible).
"""
from __future__ import annotations

import threading
from typing import Optional

from .base import MXNetError, getenv

__all__ = [
    "Context",
    "cpu",
    "gpu",
    "tpu",
    "cpu_pinned",
    "cpu_shared",
    "current_context",
    "num_tpus",
    "num_gpus",
    "device_of",
]


class Context(object):
    """A logical device. Usable as a ``with`` scope, like the reference
    (`python/mxnet/context.py:93`)."""

    # type codes kept for API parity with the reference's Context enum
    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = int(device_id)
        self._old_ctx: Optional["Context"] = None

    @property
    def device_type(self) -> str:
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(self._default_ctx, "value"):
            self._default_ctx.value = Context("cpu", 0)
        self._old_ctx = self._default_ctx.value
        self._default_ctx.value = self
        return self

    def __exit__(self, *args):
        self._default_ctx.value = self._old_ctx

    # ---- jax mapping -----------------------------------------------------
    @property
    def jax_device(self):
        """The concrete jax.Device backing this context."""
        if self.device_typeid == 2:
            devs = _accelerator_devices()
            if not devs:
                raise MXNetError(
                    "no TPU/accelerator devices visible to JAX; "
                    "use mxtpu.cpu() or set JAX_PLATFORMS"
                )
        else:
            devs = _cpu_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                "%s requested but only %d %s device(s) present"
                % (self, len(devs), self.device_type))
        return devs[self.device_id]

    def empty_cache(self):  # parity no-op: PJRT owns the HBM pool
        pass


def _accelerator_devices():
    import jax

    return [d for d in jax.devices() if d.platform != "cpu"]


def _cpu_devices():
    import jax

    try:
        return jax.devices("cpu")
    except RuntimeError:
        return jax.devices()


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for :func:`tpu` — reference scripts using ``mx.gpu()`` run
    unchanged on the TPU backend."""
    return Context("tpu", device_id)


def num_tpus() -> int:
    return len(_accelerator_devices())


def num_gpus() -> int:
    """Parity alias (reference `mxnet.context.num_gpus`)."""
    return num_tpus()


def default_ctx() -> Context:
    if not hasattr(Context._default_ctx, "value"):
        dev = getenv("MXNET_DEFAULT_CONTEXT")
        if dev:
            name, _, idx = dev.partition(":")
            Context._default_ctx.value = Context(name, int(idx or 0))
        else:
            # TPU if one is attached, else CPU.
            Context._default_ctx.value = Context(
                "tpu" if _accelerator_devices() else "cpu", 0)
    return Context._default_ctx.value


def current_context() -> Context:
    return default_ctx()


def device_of(array) -> Context:
    """Context of an NDArray."""
    return array.ctx
