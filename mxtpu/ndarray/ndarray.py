"""NDArray: the imperative tensor.

TPU-native re-design of the reference's NDArray
(`include/mxnet/ndarray.h:82`, `python/mxnet/ndarray/ndarray.py:177`) and
of the imperative invoke path (`src/imperative/imperative.cc:38-119`,
`python/mxnet/_ctypes/ndarray.py:65-83`).

Design notes (vs the reference):
  * The reference NDArray owns a Storage handle + an engine variable; reads
    block via WaitToRead.  Here the payload is a committed `jax.Array`:
    PJRT is already an async, stream-ordered runtime, so the dependency
    engine's ordering job for pure compute is done by the runtime itself.
    `wait_to_read` maps to `block_until_ready`; `asnumpy` device-transfers.
  * Every operator call funnels through :func:`imperative_invoke` — the
    analog of `MXImperativeInvokeEx -> Imperative::Invoke` — which hits a
    per-(op, attrs) jitted executable (XLA recompiles per shape/dtype
    signature and caches, the reference's executable-cache discipline).
  * In-place mutation (`a[:] = x`, `+=`, optimizer updates) rebinds the
    wrapper's payload and bumps a version counter (the reference's
    engine-var version, `include/mxnet/engine.h:44-61`).
"""
from __future__ import annotations

import functools
import operator
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..base import MXNetError, _Null, np_dtype, shape2tuple
from ..context import Context, current_context
from ..ops import registry as _reg
from .. import autograd as _ag

__all__ = [
    "NDArray",
    "imperative_invoke",
    "array",
    "zeros",
    "ones",
    "full",
    "empty",
    "arange",
    "eye",
    "concat",
    "stack",
    "split",
    "moveaxis",
    "waitall",
    "save",
    "load",
    "from_numpy",
    "from_jax",
    "maximum",
    "minimum",
    "from_dlpack",
    "to_dlpack_for_read",
    "to_dlpack_for_write",
]


def _dev_of_ctx(ctx: Context):
    return ctx.jax_device


class NDArray(object):
    """A fixed-size multi-dimensional array on a device."""

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_marked", "_entry",
                 "_version", "__weakref__")

    def __init__(self, data, ctx: Optional[Context] = None, _committed: bool = False):
        import jax

        if ctx is None:
            ctx = current_context()
        if not _committed:
            data = jax.device_put(data, _dev_of_ctx(ctx))
        self._data = data
        self._ctx = ctx
        self._grad: Optional["NDArray"] = None
        self._grad_req = "write"
        self._marked = False
        self._entry = None  # (TapeNode, out_index) when produced under record
        self._version = 0

    # -- payload management -------------------------------------------------
    def _set_jax(self, data, bump: bool = True):
        """Rebind payload (in-place write semantics; bumps version like the
        reference's engine-var version on write)."""
        self._data = data
        if bump:
            self._version += 1
            self._entry = None  # an in-place write invalidates the tape link

    @property
    def dlpack(self):
        return self._data.__dlpack__()

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def ctx(self) -> Context:
        return self._ctx

    context = ctx

    @property
    def stype(self) -> str:
        return "default"

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    @property
    def T(self) -> "NDArray":
        if self.ndim < 2:
            return self
        return imperative_invoke("transpose", self)[0]

    # -- sync / host transfer ----------------------------------------------
    def wait_to_read(self):
        """Block until the value is computed (reference:
        `python/mxnet/ndarray/ndarray.py:1795`; async errors surface here
        like `threaded_engine.h:362-372`)."""
        try:
            self._data.block_until_ready()
        except Exception as e:  # deferred XLA error surfaces here
            raise MXNetError(str(e)) from e
        return self

    def asnumpy(self) -> np.ndarray:
        return np.asarray(self.wait_to_read()._data)

    def to_dlpack_for_read(self):
        """Zero-copy DLPack capsule over the device buffer (reference
        `MXNDArrayToDLPackForRead`, `include/mxnet/c_api.h`).  Works
        with any DLPack consumer, e.g.
        ``torch.utils.dlpack.from_dlpack``."""
        return self.wait_to_read()._data.__dlpack__()

    def to_dlpack_for_write(self):
        """Reference `MXNDArrayToDLPackForWrite`.  jax.Array buffers
        are immutable, so writable export cannot be honored — the
        reference's in-place-mutation contract would corrupt the XLA
        buffer cache.  Raises with the supported alternative."""
        raise MXNetError(
            "to_dlpack_for_write is not supported: XLA device buffers "
            "are immutable. Export with to_dlpack_for_read, mutate in "
            "the consumer framework, and re-import with nd.from_dlpack")

    def __dlpack__(self, **kwargs):
        return self.wait_to_read()._data.__dlpack__(**kwargs)

    def __dlpack_device__(self):
        return self.wait_to_read()._data.__dlpack_device__()

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("The truth value of an NDArray with multiple elements "
                         "is ambiguous")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        try:
            arr = self.asnumpy()
            body = str(arr)
        except Exception as e:  # pragma: no cover
            body = "<unrealized: %s>" % e
        return "%s\n<NDArray %s @%s>" % (body, "x".join(map(str, self.shape)), self._ctx)

    # -- conversion / movement ----------------------------------------------
    def astype(self, dtype, copy: bool = True) -> "NDArray":
        dt = np_dtype(dtype)
        if not copy and self.dtype == dt:
            return self
        return imperative_invoke("Cast", self, dtype=dt.name)[0]

    def copy(self) -> "NDArray":
        return imperative_invoke("_copy", self)[0]

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        import jax

        if isinstance(other, Context):
            out = NDArray(jax.device_put(self._data, _dev_of_ctx(other)),
                          ctx=other, _committed=True)
            return out
        if not isinstance(other, NDArray):
            raise TypeError("copyto target must be NDArray or Context")
        if other.stype != "default":
            from .sparse import cast_storage
            return cast_storage(self, other.stype).copyto(other)
        data = jax.device_put(self._data, _dev_of_ctx(other.ctx))
        if data.dtype != other._data.dtype:
            data = data.astype(other._data.dtype)
        if tuple(data.shape) != other.shape:
            raise MXNetError("copyto shape mismatch %s vs %s" % (self.shape, other.shape))
        other._set_jax(data)
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def as_in_ctx(self, ctx: Context) -> "NDArray":
        return self.as_in_context(ctx)

    def detach(self) -> "NDArray":
        out = NDArray(self._data, ctx=self._ctx, _committed=True)
        return out

    def tostype(self, stype: str) -> "NDArray":
        if stype != "default":
            from .sparse import cast_storage
            return cast_storage(self, stype)
        return self

    # -- autograd -----------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype: Optional[str] = None):
        """Attach a gradient buffer (reference:
        `python/mxnet/ndarray/ndarray.py` attach_grad → MXAutogradMarkVariables).
        ``stype='row_sparse'`` makes the buffer a RowSparseNDArray so
        embedding-style gradients stay sparse end to end."""
        import jax.numpy as jnp

        if stype == "row_sparse":
            from . import sparse as _sp

            grad = _sp.zeros("row_sparse", self.shape, ctx=self._ctx,
                             dtype=self._data.dtype)
        else:
            grad = NDArray(jnp.zeros(self.shape, dtype=self._data.dtype),
                           ctx=self._ctx)
        self._grad = grad
        self._grad_req = grad_req
        self._marked = grad_req != "null"
        self._entry = None

    def backward(self, out_grad: Optional["NDArray"] = None, retain_graph: bool = False,
                 train_mode: bool = True):
        _ag.backward([self], [out_grad], retain_graph=retain_graph,
                     train_mode=train_mode)

    # -- indexing -----------------------------------------------------------
    def _canon_index(self, key):
        if isinstance(key, NDArray):
            return key._data
        if isinstance(key, tuple):
            return tuple(k._data if isinstance(k, NDArray) else k for k in key)
        return key

    def __getitem__(self, key):
        key = self._canon_index(key)
        if isinstance(key, slice) and key.start is None and key.stop is None and key.step is None:
            return self
        # under record, indexing must tape (reference: slicing emits a
        # `slice`/`gather_nd` NNVM node) — otherwise downstream grads
        # silently vanish at the first subscript
        if _ag.is_recording() and (self._entry is not None or
                                   self._marked):
            outs, node = _ag._record_fn(
                "getitem", lambda d: (d[key],), [self], [self._data])
            out = NDArray(outs[0], ctx=self._ctx, _committed=True)
            if node is not None:
                out._entry = (node, 0)
            return out
        data = self._data[key]
        out = NDArray(data, ctx=self._ctx, _committed=True)
        return out

    def __setitem__(self, key, value):
        import jax.numpy as jnp

        key = self._canon_index(key)
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(key, slice) and key.start is None and key.stop is None and key.step is None:
            if hasattr(value, "shape") and tuple(np.broadcast_shapes(tuple(value.shape), self.shape)) != self.shape:
                raise MXNetError("shape mismatch in assignment")
            newdata = jnp.broadcast_to(jnp.asarray(value, dtype=self._data.dtype), self.shape)
        else:
            newdata = self._data.at[key].set(jnp.asarray(value, dtype=self._data.dtype))
        self._set_jax(newdata)

    # -- shape manipulation convenience (routes through registered ops) -----
    def reshape(self, *shape, **kwargs) -> "NDArray":
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if not shape:
            shape = kwargs.get("shape", ())
        return imperative_invoke("Reshape", self, shape=tuple(shape))[0]

    def reshape_like(self, other: "NDArray") -> "NDArray":
        return imperative_invoke("reshape_like", self, other)[0]

    def expand_dims(self, axis: int) -> "NDArray":
        return imperative_invoke("expand_dims", self, axis=axis)[0]

    def squeeze(self, axis=None) -> "NDArray":
        return imperative_invoke("squeeze", self, axis=axis)[0]

    def transpose(self, *axes) -> "NDArray":
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return imperative_invoke("transpose", self, axes=axes if axes else None)[0]

    def flatten(self) -> "NDArray":
        return imperative_invoke("Flatten", self)[0]

    def swapaxes(self, dim1: int, dim2: int) -> "NDArray":
        return imperative_invoke("SwapAxis", self, dim1=dim1, dim2=dim2)[0]

    def flip(self, axis) -> "NDArray":
        return imperative_invoke("reverse", self, axis=axis)[0]

    def broadcast_to(self, shape) -> "NDArray":
        return imperative_invoke("broadcast_to", self, shape=tuple(shape))[0]

    def broadcast_like(self, other: "NDArray") -> "NDArray":
        return imperative_invoke("broadcast_like", self, other)[0]

    def slice(self, begin, end, step=None) -> "NDArray":
        return imperative_invoke("slice", self, begin=tuple(begin), end=tuple(end),
                                 step=tuple(step) if step else None)[0]

    def slice_axis(self, axis: int, begin: int, end: Optional[int]) -> "NDArray":
        return imperative_invoke("slice_axis", self, axis=axis, begin=begin, end=end)[0]

    def take(self, indices: "NDArray", axis: int = 0, mode: str = "clip") -> "NDArray":
        return imperative_invoke("take", self, indices, axis=axis, mode=mode)[0]

    def one_hot(self, depth: int, on_value=1.0, off_value=0.0, dtype="float32") -> "NDArray":
        return imperative_invoke("one_hot", self, depth=depth, on_value=on_value,
                                 off_value=off_value, dtype=dtype)[0]

    def clip(self, a_min, a_max) -> "NDArray":
        return imperative_invoke("clip", self, a_min=a_min, a_max=a_max)[0]

    def abs(self) -> "NDArray":
        return imperative_invoke("abs", self)[0]

    def sign(self) -> "NDArray":
        return imperative_invoke("sign", self)[0]

    def sqrt(self) -> "NDArray":
        return imperative_invoke("sqrt", self)[0]

    def square(self) -> "NDArray":
        return imperative_invoke("square", self)[0]

    def exp(self) -> "NDArray":
        return imperative_invoke("exp", self)[0]

    def log(self) -> "NDArray":
        return imperative_invoke("log", self)[0]

    def relu(self) -> "NDArray":
        return imperative_invoke("relu", self)[0]

    def sigmoid(self) -> "NDArray":
        return imperative_invoke("sigmoid", self)[0]

    def tanh(self) -> "NDArray":
        return imperative_invoke("tanh", self)[0]

    def softmax(self, axis: int = -1) -> "NDArray":
        return imperative_invoke("softmax", self, axis=axis)[0]

    def log_softmax(self, axis: int = -1) -> "NDArray":
        return imperative_invoke("log_softmax", self, axis=axis)[0]

    # -- reductions ----------------------------------------------------------
    def _reduce(self, op: str, axis=None, keepdims=False, **kw) -> "NDArray":
        return imperative_invoke(op, self, axis=axis, keepdims=keepdims, **kw)[0]

    def sum(self, axis=None, keepdims=False) -> "NDArray":
        return self._reduce("sum", axis, keepdims)

    def mean(self, axis=None, keepdims=False) -> "NDArray":
        return self._reduce("mean", axis, keepdims)

    def prod(self, axis=None, keepdims=False) -> "NDArray":
        return self._reduce("prod", axis, keepdims)

    def max(self, axis=None, keepdims=False) -> "NDArray":
        return self._reduce("max", axis, keepdims)

    def min(self, axis=None, keepdims=False) -> "NDArray":
        return self._reduce("min", axis, keepdims)

    def norm(self, ord=2, axis=None, keepdims=False) -> "NDArray":
        return imperative_invoke("norm", self, ord=ord, axis=axis, keepdims=keepdims)[0]

    def argmax(self, axis=None, keepdims=False) -> "NDArray":
        return imperative_invoke("argmax", self, axis=axis, keepdims=keepdims)[0]

    def argmin(self, axis=None, keepdims=False) -> "NDArray":
        return imperative_invoke("argmin", self, axis=axis, keepdims=keepdims)[0]

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False) -> "NDArray":
        return imperative_invoke("topk", self, axis=axis, k=k, ret_typ=ret_typ,
                                 is_ascend=is_ascend)[0]

    def argsort(self, axis=-1, is_ascend=True) -> "NDArray":
        return imperative_invoke("argsort", self, axis=axis, is_ascend=is_ascend)[0]

    def sort(self, axis=-1, is_ascend=True) -> "NDArray":
        return imperative_invoke("sort", self, axis=axis, is_ascend=is_ascend)[0]

    def dot(self, other: "NDArray", **kw) -> "NDArray":
        return imperative_invoke("dot", self, other, **kw)[0]

    def pick(self, index: "NDArray", axis=-1, keepdims=False, mode="clip") -> "NDArray":
        return imperative_invoke("pick", self, index, axis=axis, keepdims=keepdims,
                                 mode=mode)[0]

    def zeros_like(self) -> "NDArray":
        return imperative_invoke("zeros_like", self)[0]

    def ones_like(self) -> "NDArray":
        return imperative_invoke("ones_like", self)[0]

    # -- arithmetic ----------------------------------------------------------
    _BROADCAST_NAME = {
        "elemwise_add": "broadcast_add", "elemwise_sub": "broadcast_sub",
        "elemwise_mul": "broadcast_mul", "elemwise_div": "broadcast_div",
        "_grad_add": "broadcast_add", "_mod": "broadcast_mod",
        "_power": "broadcast_power", "_maximum": "broadcast_maximum",
        "_minimum": "broadcast_minimum", "_hypot": "broadcast_hypot",
        "_equal": "broadcast_equal", "_not_equal": "broadcast_not_equal",
        "_greater": "broadcast_greater",
        "_greater_equal": "broadcast_greater_equal",
        "_lesser": "broadcast_lesser", "_lesser_equal": "broadcast_lesser_equal",
    }

    def _binary(self, other, op_ew: str, op_sc: str, reverse_sc: Optional[str] = None,
                swap: bool = False):
        if isinstance(other, NDArray):
            a, b = (other, self) if swap else (self, other)
            if a.shape == b.shape:
                return imperative_invoke(op_ew, a, b)[0]
            return imperative_invoke(self._BROADCAST_NAME[op_ew], a, b)[0]
        if isinstance(other, (int, float, np.generic)):
            name = reverse_sc if (swap and reverse_sc) else op_sc
            return imperative_invoke(name, self, scalar=float(other))[0]
        return NotImplemented

    def __add__(self, other):
        return self._binary(other, "elemwise_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elemwise_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binary(other, "elemwise_sub", "_minus_scalar", "_rminus_scalar",
                            swap=True)

    def __mul__(self, other):
        return self._binary(other, "elemwise_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elemwise_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binary(other, "elemwise_div", "_div_scalar", "_rdiv_scalar",
                            swap=True)

    def __mod__(self, other):
        return self._binary(other, "_mod", "_mod_scalar")

    def __rmod__(self, other):
        return self._binary(other, "_mod", "_mod_scalar", "_rmod_scalar", swap=True)

    def __pow__(self, other):
        return self._binary(other, "_power", "_power_scalar")

    def __rpow__(self, other):
        return self._binary(other, "_power", "_power_scalar", "_rpower_scalar",
                            swap=True)

    def __matmul__(self, other):
        return imperative_invoke("dot", self, other)[0]

    def __neg__(self):
        return imperative_invoke("negative", self)[0]

    def __abs__(self):
        return imperative_invoke("abs", self)[0]

    def _inplace_result(self, res):
        # keep the tape link when mutating in place under record()
        # (reference: in-place writes bump the var version but stay taped)
        self._set_jax(res._data)
        self._entry = getattr(res, "_entry", None)
        return self

    def __iadd__(self, other):
        return self._inplace_result(self.__add__(other))

    def __isub__(self, other):
        return self._inplace_result(self.__sub__(other))

    def __imul__(self, other):
        return self._inplace_result(self.__mul__(other))

    def __itruediv__(self, other):
        return self._inplace_result(self.__truediv__(other))

    def _compare(self, other, op_ew: str, op_sc: str):
        if isinstance(other, NDArray):
            if other.shape == self.shape:
                return imperative_invoke(op_ew, self, other)[0]
            return imperative_invoke("broadcast" + op_ew, self, other)[0]
        return imperative_invoke(op_sc, self, scalar=float(other))[0]

    def __eq__(self, other):
        if other is None:
            return False
        return self._compare(other, "_equal", "_equal_scalar")

    def __ne__(self, other):
        if other is None:
            return True
        return self._compare(other, "_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return self._compare(other, "_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._compare(other, "_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return self._compare(other, "_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._compare(other, "_lesser_equal", "_lesser_equal_scalar")

    __hash__ = object.__hash__


# ---------------------------------------------------------------------------
# Imperative invoke — the single funnel every op call goes through
# (reference: `Imperative::Invoke`, `src/imperative/imperative.cc:87-119`).
# ---------------------------------------------------------------------------

def imperative_invoke(op_name: str, *inputs, out=None,
                      _full_outputs: bool = False,
                      **attrs) -> Tuple[NDArray, ...]:
    from .. import profiler as _prof

    if _prof.is_recording("imperative"):
        with _prof.span(op_name, "operator"):
            return _imperative_invoke_impl(op_name, *inputs, out=out,
                                           _full_outputs=_full_outputs,
                                           **attrs)
    return _imperative_invoke_impl(op_name, *inputs, out=out,
                                   _full_outputs=_full_outputs, **attrs)


def _imperative_invoke_impl(op_name: str, *inputs, out=None,
                            _full_outputs: bool = False,
                            **attrs) -> Tuple[NDArray, ...]:
    opdef = _reg.get_op(op_name)

    # drop None/_Null attrs so they don't pollute the jit cache key
    attrs = {k: v for k, v in attrs.items() if v is not None and v is not _Null}
    if opdef.train_aware and "is_train" not in attrs:
        attrs["is_train"] = _ag.is_training()

    nd_inputs: List[NDArray] = []
    for x in inputs:
        if isinstance(x, NDArray):
            # storage-fallback dispatch (reference
            # `attach_op_execs_pass.cc:45`): ops without a sparse
            # formulation run on the densified array; sparse-native
            # kernels live in ndarray/sparse.py and bypass this funnel
            if x.stype != "default":
                x = x.todense()
            nd_inputs.append(x)
        elif isinstance(x, (int, float, np.generic, np.ndarray, list, tuple)):
            nd_inputs.append(array(x))
        else:
            nd_inputs.append(x)  # raw jax array (internal use)

    ctx = nd_inputs[0].ctx if nd_inputs and isinstance(nd_inputs[0], NDArray) \
        else attrs.pop("ctx", None) or current_context()
    if "ctx" in attrs:
        ctx = attrs.pop("ctx") or ctx
        if isinstance(ctx, str):
            name, _, idx = ctx.partition("(")
            ctx = Context(name, int(idx.rstrip(")") or 0))

    jax_inputs = [x._data if isinstance(x, NDArray) else x for x in nd_inputs]

    rng_key = None
    if opdef.needs_rng:
        from .. import random as _rnd
        rng_key = _rnd._next_key()

    node = None
    if _ag.is_recording() and opdef.differentiable:
        outs, node = _ag._record_op(opdef, nd_inputs, jax_inputs, attrs, rng_key)
    else:
        outs = _reg.invoke_jax(opdef, jax_inputs, attrs, rng_key)

    # init ops: place on requested ctx
    if not nd_inputs:
        import jax

        dev = _dev_of_ctx(ctx)
        outs = tuple(jax.device_put(o, dev) for o in outs)

    results = []
    for i, o in enumerate(outs):
        nd = NDArray(o, ctx=ctx, _committed=True)
        if node is not None:
            nd._entry = (node, i)
        results.append(nd)

    # hide non-visible outputs (reference NumVisibleOutputs — e.g.
    # BatchNorm's batch mean/var); internal callers pass _full_outputs
    if not _full_outputs:
        n_vis = opdef.n_visible_outputs(attrs)
        if n_vis < len(results):
            results = results[:n_vis]

    if out is not None:
        outs_list = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs_list, results):
            if dst.stype != "default":
                raise MXNetError(
                    "out= with %s storage is not supported for %s"
                    % (dst.stype, op_name))
            dst._set_jax(src._data)
        return tuple(outs_list)
    return tuple(results)


# ---------------------------------------------------------------------------
# Creation / utility functions (reference: `python/mxnet/ndarray/ndarray.py`
# zeros/ones/full/array/arange + `ndarray/utils.py` save/load)
# ---------------------------------------------------------------------------

def array(source_array, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    if isinstance(source_array, NDArray):
        res = source_array.copy() if ctx is None or ctx == source_array.ctx \
            else source_array.as_in_context(ctx)
        if dtype is not None and res.dtype != np_dtype(dtype):
            res = res.astype(dtype)
        return res
    # reference rule (`python/mxnet/ndarray/ndarray.py` array()): numpy
    # sources keep their dtype; python lists/scalars default to float32
    if dtype is None:
        dtype = source_array.dtype if isinstance(source_array, np.ndarray) \
            else np.float32
        if np.dtype(dtype) == np.float64:
            dtype = np.float32  # TPU-native default: fp64 is emulated on TPU
    arr = np.asarray(source_array).astype(np_dtype(dtype), copy=False)
    return NDArray(arr, ctx=ctx)


def from_numpy(a: np.ndarray, ctx=None) -> NDArray:
    return array(a, ctx=ctx)


def from_jax(a, ctx: Optional[Context] = None) -> NDArray:
    return NDArray(a, ctx=ctx or current_context(), _committed=True)


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs) -> NDArray:
    return imperative_invoke("_zeros", shape=shape2tuple(shape),
                             dtype=np_dtype(dtype).name, ctx=ctx)[0]


def ones(shape, ctx=None, dtype=None, **kwargs) -> NDArray:
    return imperative_invoke("_ones", shape=shape2tuple(shape),
                             dtype=np_dtype(dtype).name, ctx=ctx)[0]


def full(shape, val, ctx=None, dtype=None, **kwargs) -> NDArray:
    return imperative_invoke("_full", shape=shape2tuple(shape), value=float(val),
                             dtype=np_dtype(dtype).name, ctx=ctx)[0]


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None) -> NDArray:
    return imperative_invoke("_arange", start=float(start),
                             stop=float(stop) if stop is not None else None,
                             step=float(step), repeat=int(repeat),
                             dtype=np_dtype(dtype).name, ctx=ctx)[0]


def eye(N, M=0, k=0, ctx=None, dtype=None) -> NDArray:
    return imperative_invoke("_eye", N=int(N), M=int(M), k=int(k),
                             dtype=np_dtype(dtype).name, ctx=ctx)[0]


def concat(*arrays, dim: int = 1) -> NDArray:
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return imperative_invoke("Concat", *arrays, dim=dim)[0]


def stack(*arrays, axis: int = 0) -> NDArray:
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return imperative_invoke("stack", *arrays, axis=axis)[0]


def split(data, num_outputs, axis=1, squeeze_axis=False):
    outs = imperative_invoke("SliceChannel", data, num_outputs=num_outputs,
                             axis=axis, squeeze_axis=squeeze_axis)
    return list(outs) if len(outs) > 1 else outs[0]


def moveaxis(data, source, destination) -> NDArray:
    return imperative_invoke("moveaxis", data, source=source,
                             destination=destination)[0]


def waitall():
    """Block until all async work completes (reference:
    `python/mxnet/ndarray/ndarray.py:156` → Engine WaitForAll).

    Blocks on every live array.  A sentinel-program shortcut ("enqueue
    a trivial program last, wait for it") is NOT sound here: PJRT only
    orders programs that have data dependencies, so an independent
    sentinel can complete while earlier-enqueued work is still
    running.  `is_ready()` is a client-local check, so
    already-finished arrays cost nothing."""
    import jax

    try:
        jax.effects_barrier()
        pending = []
        for arr in jax.live_arrays():
            try:
                if not arr.is_ready():
                    pending.append(arr)
            except Exception:
                pending.append(arr)
        if pending:
            jax.block_until_ready(pending)
    except Exception as e:
        raise MXNetError(str(e)) from e


# -- serialization (reference: NDArray::Save/Load `src/ndarray/ndarray.cc`,
#    python `ndarray/utils.py:149-222`; format here is npz, not the
#    reference binary layout — same API, container swapped) ----------------

def save(fname, data):
    """`fname` may be a path or a writable binary file object (the C
    ABI's MXNDArraySaveRawBytes serializes through a BytesIO)."""
    if isinstance(data, NDArray):
        payload = {"0": data.asnumpy()}
        keys = None
    elif isinstance(data, (list, tuple)):
        payload = {str(i): d.asnumpy() for i, d in enumerate(data)}
        keys = None
    elif isinstance(data, dict):
        payload = {k: v.asnumpy() for k, v in data.items()}
        keys = list(data.keys())
    else:
        raise TypeError("unsupported data for save: %r" % type(data))
    kw = dict(__keys__=np.array(keys if keys is not None else [],
                                dtype=object), **payload)
    if hasattr(fname, "write"):
        np.savez(fname, **kw)
    else:
        # temp+fsync+rename: a crash mid-save never truncates an
        # existing params file (mxtpu/resilience.py)
        from ..resilience import atomic_write

        with atomic_write(fname) as f:
            np.savez(f, **kw)


def load(fname):
    """`fname` may be a path or a readable binary file object."""
    with np.load(fname, allow_pickle=True) as zf:
        keys = list(zf["__keys__"]) if "__keys__" in zf else []
        names = [k for k in zf.files if k != "__keys__"]
        if keys:
            return {str(k): array(zf[str(k)]) for k in keys}
        try:
            names_sorted = sorted(names, key=int)
            return [array(zf[n]) for n in names_sorted]
        except ValueError:
            return {n: array(zf[n]) for n in names}


def from_dlpack(ext_tensor) -> NDArray:
    """Construct an NDArray from any DLPack producer — a capsule from
    `to_dlpack_for_read`, or an object with `__dlpack__` (torch/numpy/
    cupy tensors).  Zero-copy when the producer lives on a compatible
    device (reference `MXNDArrayFromDLPack`)."""
    import jax.numpy as jnp

    return NDArray(jnp.from_dlpack(ext_tensor), _committed=True)


def to_dlpack_for_read(data: NDArray):
    """Module-level mirror of `NDArray.to_dlpack_for_read` (reference
    `mx.nd.to_dlpack_for_read`)."""
    return data.to_dlpack_for_read()


def to_dlpack_for_write(data: NDArray):
    """Module-level mirror of `NDArray.to_dlpack_for_write` — always
    raises; see the method docstring."""
    return data.to_dlpack_for_write()


def _commutative_binary(name, op_ew, op_sc, host_fn, host_ew):
    def fn(lhs, rhs):
        if not isinstance(lhs, NDArray) and not isinstance(rhs, NDArray):
            # elementwise for array-likes; Python max/min only handles
            # scalars (multi-element arrays raise ambiguous-truth-value)
            if isinstance(lhs, (int, float, np.generic)) and \
                    isinstance(rhs, (int, float, np.generic)):
                return host_fn(lhs, rhs)
            return host_ew(lhs, rhs)
        if isinstance(rhs, NDArray) and not isinstance(lhs, NDArray):
            lhs, rhs = rhs, lhs  # commutative: swap is free
        if not isinstance(rhs, (NDArray, int, float, np.generic)):
            try:
                rhs = array(rhs)  # lists/np arrays coerce (f32 default)
            except Exception:
                raise TypeError("%s: unsupported operand type %r"
                                % (name, type(rhs))) from None
        return lhs._binary(rhs, op_ew, op_sc)

    fn.__name__ = fn.__qualname__ = name
    fn.__doc__ = ("Elementwise %s of arrays or scalars (reference "
                  "`mx.nd.%s`); dispatch incl. broadcasting rides "
                  "NDArray._binary." % (name, name))
    return fn


maximum = _commutative_binary("maximum", "_maximum", "_maximum_scalar",
                              max, np.maximum)
minimum = _commutative_binary("minimum", "_minimum", "_minimum_scalar",
                              min, np.minimum)
