"""Operator registry.

TPU-native re-design of the reference's NNVM op registry
(`include/mxnet/op_attr_types.h:198-283`, `NNVM_REGISTER_OP` across
`src/operator/**`).  In the reference every op carries typed attributes
(FCompute kernels per device, FInferShape/Type, FGradient...).  Here an op
is a *pure JAX function*: XLA is the kernel library for every device, shape
and dtype inference fall out of `jax.eval_shape`, and the gradient comes
from `jax.vjp` — so the whole FCompute/FInferShape/FGradient attribute
bundle collapses into one callable plus a few flags.

Each op gets, for free:
  * an eager executable cached per (op, attrs) via `jax.jit` (XLA caches
    per input shape/dtype under that) — the analog of the reference's
    per-op kernel dispatch, but compiled;
  * a tape entry for autograd via `jax.vjp` (analog of FGradient);
  * a Symbol node type for whole-graph lowering (analog of the symbolic
    registry that drives `GraphExecutor`).

Ops are registered with plain-Python attrs; attrs are canonicalized to
hashable values so they can key the jit cache (the reference's analog is
the executable cache keyed by op signature).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..base import MXNetError

__all__ = ["OpDef", "register", "get_op", "list_ops", "invoke_jax", "canonical_attrs"]

_OP_REGISTRY: Dict[str, "OpDef"] = {}

# Called with (name, opdef) for every registration AFTER the hook was
# installed.  The nd/sym composer modules install one so ops registered
# late — e.g. a module whose import was triggered mid-way through
# ops/__init__, or a user registering at runtime — still get their
# nd.*/sym.* functions.
_POST_REGISTER_HOOKS: List[Callable[[str, "OpDef"], None]] = []


def add_post_register_hook(hook: Callable[[str, "OpDef"], None]):
    _POST_REGISTER_HOOKS.append(hook)


class OpDef(object):
    """A registered operator.

    Parameters
    ----------
    name : registered op name (reference names kept verbatim, e.g.
        ``elemwise_add``, ``FullyConnected``).
    fn : pure function ``fn(*arrays, **attrs) -> array | tuple(arrays)``.
        If ``needs_rng`` the first positional argument is a jax PRNG key.
    num_outputs : static output count (or a callable ``attrs -> int``).
    differentiable : if False the op is never taped (argmax, shape_array...).
    needs_rng : op consumes a PRNG key (dropout, samplers).
    mutate_inputs : indices of inputs updated in place (optimizer ops write
        weight/state — reference `src/operator/optimizer_op.cc`); the op
        must *return* the new values; the imperative layer writes them back.
    """

    def __init__(
        self,
        name: str,
        fn: Callable,
        num_outputs: Any = 1,
        differentiable: bool = True,
        needs_rng: bool = False,
        train_aware: bool = False,
        mutate_inputs: Sequence[int] = (),
        aliases: Sequence[str] = (),
        visible_outputs: Any = None,
        doc: Optional[str] = None,
    ):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs
        # reference analog: NumVisibleOutputs — BatchNorm computes
        # (out, mean, var) but only `out` is user-visible
        self.visible_outputs = visible_outputs
        self.differentiable = differentiable
        self.needs_rng = needs_rng
        # train_aware ops take an `is_train` attr injected from the autograd
        # scope (reference analog: OpContext::is_train threaded into FCompute)
        self.train_aware = train_aware
        self.mutate_inputs = tuple(mutate_inputs)
        self.aliases = tuple(aliases)
        self.doc = doc or (fn.__doc__ or "")

    def n_outputs(self, attrs: Dict[str, Any]) -> int:
        if callable(self.num_outputs):
            return self.num_outputs(attrs)
        return self.num_outputs

    def n_visible_outputs(self, attrs: Dict[str, Any]) -> int:
        if self.visible_outputs is None:
            return self.n_outputs(attrs)
        if callable(self.visible_outputs):
            return self.visible_outputs(attrs)
        return self.visible_outputs

    def __repr__(self):
        return "OpDef(%s)" % self.name


def register(
    name: str,
    num_outputs: Any = 1,
    differentiable: bool = True,
    needs_rng: bool = False,
    train_aware: bool = False,
    mutate_inputs: Sequence[int] = (),
    aliases: Sequence[str] = (),
    visible_outputs: Any = None,
):
    """Decorator registering a JAX function as a framework op."""

    def deco(fn):
        opdef = OpDef(
            name,
            fn,
            num_outputs=num_outputs,
            differentiable=differentiable,
            needs_rng=needs_rng,
            train_aware=train_aware,
            mutate_inputs=mutate_inputs,
            aliases=aliases,
            visible_outputs=visible_outputs,
        )
        if name in _OP_REGISTRY:
            raise MXNetError("op %r already registered" % name)
        _OP_REGISTRY[name] = opdef
        for a in aliases:
            if a in _OP_REGISTRY:
                raise MXNetError("op alias %r already registered" % a)
            _OP_REGISTRY[a] = opdef
        for hook in _POST_REGISTER_HOOKS:
            hook(name, opdef)
            for a in aliases:
                hook(a, opdef)
        return fn

    return deco


def get_op(name: str) -> OpDef:
    try:
        return _OP_REGISTRY[name]
    except KeyError:
        raise MXNetError("operator %r is not registered" % name) from None


def has_op(name: str) -> bool:
    return name in _OP_REGISTRY


def list_ops() -> List[str]:
    return sorted(_OP_REGISTRY.keys())


# ---------------------------------------------------------------------------
# attrs canonicalization — attrs key the jit cache, so they must be hashable
# and stable.
# ---------------------------------------------------------------------------

def _canon_value(v):
    if isinstance(v, (list, tuple)):
        return tuple(_canon_value(x) for x in v)
    if isinstance(v, np.generic):
        return v.item()
    if isinstance(v, np.dtype):
        return v.name
    return v


def canonical_attrs(attrs: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    return tuple(sorted((k, _canon_value(v)) for k, v in attrs.items() if v is not None))


# ---------------------------------------------------------------------------
# Executable cache.  Reference analog: per-op kernel dispatch + the
# CachedOp/executable caches keyed by (op, shape, dtype) — here jax.jit
# keys by shape/dtype itself, so we only cache the jitted callable per
# (op, attrs).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16384)
def _jitted(name: str, attrs_key: Tuple) -> Callable:
    import jax

    opdef = get_op(name)
    attrs = dict(attrs_key)
    fn = functools.partial(opdef.fn, **attrs)
    return jax.jit(fn)


def invoke_jax(opdef: OpDef, jax_inputs: Sequence, attrs: Dict[str, Any], rng_key=None):
    """Run an op on raw jax arrays through the per-op executable cache.

    Returns a tuple of jax arrays (always a tuple, even for 1 output).
    """
    attrs_key = canonical_attrs(attrs)
    fn = _jitted(opdef.name, attrs_key)
    if opdef.needs_rng:
        out = fn(rng_key, *jax_inputs)
    else:
        out = fn(*jax_inputs)
    if not isinstance(out, tuple):
        out = (out,)
    return out


def index_dtype():
    """Widest integer dtype actually available for emitted indices:
    int64 only under jax_enable_x64 (otherwise JAX truncates with a
    per-call warning) — shared by ops that mirror the reference's
    int64 index outputs (dgl samplers, unique_zipfian)."""
    import jax
    import jax.numpy as jnp

    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
