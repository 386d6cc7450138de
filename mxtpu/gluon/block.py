"""Block / HybridBlock / SymbolBlock (reference: `python/mxnet/gluon/
block.py:127,671,952`).

Same user model as the reference: Blocks compose imperatively; a
HybridBlock can `hybridize()`, which traces `hybrid_forward` with Symbol
proxies and compiles the whole graph into a CachedOp (`block.py:748-785`) —
here the CachedOp is a single jitted XLA module (see mxtpu/cached_op.py),
which is the TPU-native payoff: one compiled computation per network
instead of per-op dispatch.
"""
from __future__ import annotations

import copy
import re
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray
from .. import ndarray as nd_mod
from .. import symbol as sym_mod
from ..symbol.symbol import NameManager, Symbol
from ..cached_op import CachedOp
from .parameter import (Parameter, ParameterDict, DeferredInitializationError,
                        tensor_types)

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


def _flatten(args, fmt_hint="input"):
    """Flatten nested lists/tuples of arrays into a flat list + format tree
    (reference `block.py` _flatten)."""
    if isinstance(args, (NDArray, Symbol)):
        return [args], 0
    if isinstance(args, (list, tuple)):
        flat, fmts = [], []
        for a in args:
            f, fmt = _flatten(a, fmt_hint)
            flat.extend(f)
            fmts.append(fmt)
        return flat, fmts
    if args is None:
        return [], -1
    raise MXNetError("cannot flatten argument of type %s in %s"
                     % (type(args), fmt_hint))


def _regroup(flat, fmt):
    """Inverse of _flatten. Returns (structure, remaining_flat)."""
    if fmt == 0:
        return flat[0], flat[1:]
    if fmt == -1:
        return None, flat
    structure = []
    for f in fmt:
        item, flat = _regroup(flat, f)
        structure.append(item)
    return structure, flat


class _TraceNames(NameManager):
    """NameManager active while tracing one block's ``hybrid_forward``:
    anonymous ops get the block's ABSOLUTE prefix ("mlp_fc1_"), so the
    traced graph — and through `mx.inspect`'s per-node `named_scope`,
    the HLO op metadata and device traces — resolves to model layers
    instead of bare "fullyconnected2" counters.  Counters are shared
    with the enclosing manager (one dict per trace), so a
    weight-shared block called twice still yields unique node names.
    Explicit names pass through untouched (unlike `mx.name.Prefix`):
    Parameter.var() and user-named ops must keep their exact names or
    `_build_cache`'s arg mapping breaks."""

    def __init__(self, prefix):
        super().__init__()
        self._counter = NameManager.current()._counter
        self._prefix = prefix

    def get(self, name, hint):
        if name:
            return name
        return self._prefix + super().get(None, hint)


class _BlockScope(object):
    """Name scoping for blocks (reference `block.py:35`)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from ..symbol.symbol import NameManager

                prefix = NameManager.current().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = "%s%d_" % (hint, count)
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        from ..symbol.symbol import NameManager

        self._name_scope = NameManager()
        self._name_scope.__enter__()
        return self

    def __exit__(self, *args):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(*args)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


class Block(object):
    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: Dict[str, Parameter] = {}
        self._forward_hooks: List = []
        self._forward_pre_hooks: List = []

    def _alias(self):
        return self.__class__.__name__.lower()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(str(block), 2))
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        """Auto-register children and parameters; reassignment
        unregisters the previous Block/Parameter bound to the name
        (reference `block.py:218`)."""
        if hasattr(self, "_children"):
            if isinstance(value, Block):
                self._children[name] = value
            elif name in self._children:
                del self._children[name]
        if hasattr(self, "_reg_params"):
            if isinstance(value, Parameter):
                self._reg_params[name] = value
            elif name in self._reg_params:
                del self._reg_params[name]
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self) -> ParameterDict:
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret._params = OrderedDict(
                (name, value) for name, value in self.params.items()
                if pattern.match(name))
        for child in self._children.values():
            child_params = child.collect_params(select)
            ret.update(child_params)
        return ret

    def child_blocks(self):
        return list(self._children.values())

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer as _init_mod

        self.collect_params().initialize(init or _init_mod.Uniform(), ctx,
                                         verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    # -- persistence ------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        from ..ndarray import save as nd_save

        nd_save(filename, {k: v._reduce() if hasattr(v, "_reduce")
                           else v.data() for k, v in params.items()})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from ..ndarray import load as nd_load

        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError(
                        "Parameter %r is missing in file %r" % (name,
                                                                filename))
        for name in loaded:
            if name not in params:
                if not ignore_extra:
                    raise MXNetError(
                        "Parameter %r in file %r is not in this Block"
                        % (name, filename))
                continue
            param = params[name]
            if param._data is None and param._deferred_init == () and \
                    param._shape is None:
                param._shape = tuple(loaded[name].shape)
            if param._data is None and not param._deferred_init:
                param.initialize(ctx=ctx or [current_context()])
            param.set_data(loaded[name])

    save_params = save_parameters
    load_params = load_parameters

    def _collect_params_with_prefix(self, prefix="") -> Dict[str, Parameter]:
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- execution --------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary (reference block.summary)."""
        summary = []

        def walk(block, depth):
            pcount = sum(int(np.prod(p.shape)) for p in
                         block._reg_params.values()
                         if p.shape and all(s > 0 for s in p.shape))
            summary.append(("  " * depth + block.__class__.__name__, pcount))
            for c in block._children.values():
                walk(c, depth + 1)

        walk(self, 0)
        lines = ["%-40s %12d" % row for row in summary]
        total = sum(r[1] for r in summary)
        out = "\n".join(lines) + "\nTotal params: %d" % total
        print(out)
        return out


def _indent(s, num_spaces):
    lines = s.split("\n")
    first = lines.pop(0)
    return first + ("\n" + "\n".join(" " * num_spaces + line
                                     for line in lines) if lines else "")


class HybridBlock(Block):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op: Optional[CachedOp] = None
        self._cached_meta = None
        self._flags = []

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = list(kwargs.items())
        self._clear_cached_op()
        super().hybridize(active, **kwargs)

    def _clear_cached_op(self):
        self._cached_op = None
        self._cached_meta = None

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            raise MXNetError(
                "children of a HybridBlock must be HybridBlocks; got %s"
                % type(block))
        super().register_child(block, name)
        self._clear_cached_op()

    # -- tracing ----------------------------------------------------------
    def _trace_symbol(self, *args):
        """Trace hybrid_forward with Symbol proxies; returns
        (out_sym, out_fmt, in_fmt)."""
        flat, in_fmt = _flatten(list(args), "input")
        data_syms = [sym_mod.var("data%d" % i) for i in range(len(flat))]
        structured, _ = _regroup(list(data_syms), in_fmt)
        with _TraceNames(self.prefix):
            out = self._call_hybrid(sym_mod, structured, trace=True)
        out_flat, out_fmt = _flatten(out, "output")
        out_sym = out_flat[0] if len(out_flat) == 1 else \
            sym_mod.Group(out_flat)
        return out_sym, out_fmt, in_fmt

    def _build_cache(self, *args):
        """Trace hybrid_forward with Symbol proxies (reference
        `block.py:748`)."""
        out_sym, out_fmt, in_fmt = self._trace_symbol(*args)
        self._out_fmt = out_fmt
        self._in_fmt = in_fmt
        # "program_name" keys the mx.inspect registry record by THIS
        # block, so retraces across cache rebuilds stay one program
        self._cached_op = CachedOp(
            out_sym, list(self._flags) + [("program_name", self.name)])
        # map graph arguments to data slots / Parameters
        arg_names = self._cached_op._arg_names
        aux_names = self._cached_op._aux_names
        by_name = {p.name: p for p in self._collect_all_params()}
        self._cached_arg_map = []
        for name in arg_names:
            m = re.match(r"^data(\d+)$", name)
            if m:
                self._cached_arg_map.append(int(m.group(1)))
            else:
                if name not in by_name:
                    raise MXNetError("traced graph references unknown "
                                     "parameter %r" % name)
                self._cached_arg_map.append(by_name[name])
        self._cached_aux = [by_name[name] for name in aux_names]
        # the data slots are the bucketable (ragged-batch) args
        self._cached_op.set_data_indices(
            [pos for pos, slot in enumerate(self._cached_arg_map)
             if isinstance(slot, int)])

    def _collect_all_reg_params(self):
        out = dict(self._reg_params)
        for c in self._children.values():
            if isinstance(c, HybridBlock):
                out.update(c._collect_all_reg_params())
        return out

    def _collect_all_params(self) -> List[Parameter]:
        seen = []
        for p in self.collect_params().values():
            seen.append(p)
        return seen

    def _call_hybrid(self, F, inputs, trace=False):
        """Invoke hybrid_forward with this block's own params as kwargs."""
        if F is sym_mod:
            kwargs = {name: p.var() for name, p in self._reg_params.items()}
        else:
            # pick the parameter copy on the input's device (reference
            # HybridBlock.forward: `i.data(ctx)` per replica)
            ctx = None
            flat_in, _ = _flatten(list(inputs), "input")
            for a in flat_in:
                if isinstance(a, NDArray):
                    ctx = a.ctx
                    break
            try:
                kwargs = {name: p.data(ctx) for name, p in
                          self._reg_params.items()}
            except DeferredInitializationError:
                self._deferred_infer_shape(*inputs)
                for p in self._collect_all_reg_params().values():
                    p._finish_deferred_init()
                kwargs = {name: p.data(ctx) for name, p in
                          self._reg_params.items()}
        return self.hybrid_forward(F, *inputs, **kwargs)

    def _deferred_infer_shape(self, *args):
        """Infer deferred parameter shapes by tracing symbolically and
        running infer_shape with the data shapes (reference
        `block.py:_infer_attrs`)."""
        try:
            out_sym, _, _ = self._trace_symbol(*args)
            flat_args, _ = _flatten(list(args), "input")
            shape_kwargs = {"data%d" % i: a.shape
                            for i, a in enumerate(flat_args)}
            arg_shapes, _, aux_shapes = out_sym.infer_shape_partial(
                **shape_kwargs)
            all_params = {p.name: p for p in self._collect_all_params()}
            for name, shape in zip(out_sym.list_arguments(), arg_shapes):
                if name in all_params and shape is not None:
                    all_params[name].shape = shape
            for name, shape in zip(out_sym.list_auxiliary_states(),
                                   aux_shapes):
                if name in all_params and shape is not None:
                    all_params[name].shape = shape
        except DeferredInitializationError:
            raise
        except MXNetError as e:
            raise MXNetError("deferred shape inference failed: %s" % e) from e

    # -- execution --------------------------------------------------------
    def forward(self, x, *args):
        first = x
        while isinstance(first, (list, tuple)) and first:
            first = first[0]
        if isinstance(first, NDArray):
            if self._active:
                if self._cached_op is None:
                    # finish deferred param init first (needs shapes)
                    try:
                        for p in self._collect_all_reg_params().values():
                            p.data()
                    except (DeferredInitializationError, MXNetError):
                        self._deferred_infer_shape(x, *args)
                        for p in self._collect_all_params():
                            p._finish_deferred_init()
                    self._build_cache(x, *args)
                return self._run_cached(x, *args)
            return self._call_hybrid(nd_mod, [x] + list(args))
        if isinstance(first, Symbol):
            with _TraceNames(self.prefix):
                return self._call_hybrid(sym_mod, [x] + list(args))
        raise MXNetError("HybridBlock input must be NDArray or Symbol, got %s"
                         % type(first))

    def _run_cached(self, *args):
        flat_args, in_fmt = _flatten(list(args), "input")
        if in_fmt != self._in_fmt:
            self._build_cache(*args)  # input structure changed: retrace
            flat_args, _ = _flatten(list(args), "input")
        inputs = []
        for slot in self._cached_arg_map:
            if isinstance(slot, int):
                inputs.append(flat_args[slot])
            else:
                inputs.append(slot.data())
        aux = [p.data() for p in self._cached_aux]
        out = self._cached_op(inputs, aux)
        structured, _ = _regroup(list(out), self._out_fmt)
        return structured

    def forward_fused(self, x, *args):
        """Score K batches in ONE compiled program.

        Every input carries a leading K dimension over the traced batch
        shape (e.g. trace with (B, 3, H, W), call with (K, B, 3, H, W));
        returns outputs stacked the same way.  Amortizes per-dispatch
        latency exactly like FusedTrainLoop does for training — see
        CachedOp.call_fused.  The block must be hybridized; the cache is
        built from the first batch row if absent."""
        if not self._active:
            raise MXNetError("forward_fused requires hybridize()")
        if self._cached_op is None:
            # build the cache from batch row 0 of every input leaf —
            # sliced per LEAF (a top-level [x][0] would grab the first
            # structure element of a list input, not a batch row) and
            # under pause() so the warm-up forward can't record a tape
            # or write train-mode BN stats whatever scope the caller
            # is in (call_fused itself never touches aux)
            from .. import autograd as _ag

            flat0, fmt0 = _flatten([x] + list(args), "input")
            rows, _ = _regroup([a[0] for a in flat0], fmt0)
            with _ag.pause():
                self.forward(rows[0], *rows[1:])
        flat_args, in_fmt = _flatten([x] + list(args), "input")
        if in_fmt != self._in_fmt:
            raise MXNetError("forward_fused input structure does not "
                             "match the traced structure %r" % (self._in_fmt,))
        inputs = []
        stacked_idx = []
        for pos, slot in enumerate(self._cached_arg_map):
            if isinstance(slot, int):
                inputs.append(flat_args[slot])
                stacked_idx.append(pos)
            else:
                inputs.append(slot.data())
        aux = [p.data() for p in self._cached_aux]
        out = self._cached_op.call_fused(inputs, aux,
                                         stacked_idx=stacked_idx)
        structured, _ = _regroup(list(out), self._out_fmt)
        return structured

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        """Per-layer summary.  With example ``inputs`` (NDArrays or
        shape tuples) the block is traced symbolically and the call
        delegates to :func:`mxtpu.visualization.print_summary` — layer
        table with output shapes, param counts, and the XLA FLOPs
        column (plus the registry's whole-program figures when this
        block's compiled program exists in ``mx.inspect``).  Without
        inputs, falls back to the plain Block walk."""
        if not inputs:
            return super().summary()
        example = [nd_mod.zeros(tuple(a)) if isinstance(a, (tuple, list))
                   else a for a in inputs]
        try:
            for p in self._collect_all_reg_params().values():
                p.data()
        except (DeferredInitializationError, MXNetError):
            self._deferred_infer_shape(*example)
            for p in self._collect_all_params():
                p._finish_deferred_init()
        if self._cached_op is not None:
            # reuse the live cache's symbol: its graph head is what the
            # mx.inspect registry keys on, so the compiled-program
            # footer (whole-program FLOPs / peak memory) resolves
            out_sym = self._cached_op.symbol
        else:
            out_sym, _, _ = self._trace_symbol(*example)
        flat, _ = _flatten(list(example), "input")
        shapes = {"data%d" % i: tuple(a.shape) for i, a in enumerate(flat)}
        from .. import visualization

        return visualization.print_summary(out_sym, shape=shapes)

    # -- AOT warmup --------------------------------------------------------
    def warmup(self, input_shapes, dtype="float32"):
        """AOT-compile the hybridized graph for the given data input
        shapes WITHOUT running a batch (`CachedOp.warmup`, built on
        ``jit(...).lower().compile()``).

        ``input_shapes`` is one signature — a shape tuple per data
        input, e.g. ``[(8, 3, 224, 224)]`` — or a list of signatures,
        e.g. one per serving bucket.  Parameters must be initialized;
        the cache is traced from dummy zeros of the first signature if
        absent.  With the persistent compile cache warm
        (`docs/compile_cache.md`), warmup deserializes from disk
        instead of compiling."""
        if not self._active:
            raise MXNetError("warmup requires hybridize()")
        sigs = list(input_shapes)
        if not sigs:
            raise MXNetError("warmup needs at least one input shape")
        if isinstance(sigs[0][0], int):
            sigs = [sigs]  # a single signature was passed
        if self._cached_op is None:
            dummies = [nd_mod.zeros(tuple(s), dtype=dtype)
                       for s in sigs[0]]
            try:
                for p in self._collect_all_reg_params().values():
                    p.data()
            except (DeferredInitializationError, MXNetError):
                self._deferred_infer_shape(*dummies)
                for p in self._collect_all_params():
                    p._finish_deferred_init()
            self._build_cache(*dummies)
        aux_specs = [p.data() for p in self._cached_aux]
        for sig in sigs:
            arg_specs = []
            for slot in self._cached_arg_map:
                if isinstance(slot, int):
                    arg_specs.append((tuple(sig[slot]), dtype))
                else:
                    arg_specs.append(slot.data())
            self._cached_op.warmup(arg_specs, aux_specs, dtype=dtype)
        return self

    # -- export -----------------------------------------------------------
    def export(self, path, epoch=0):
        """Save symbol + params like the reference `block.py:868`
        (`path-symbol.json`, `path-%04d.params`)."""
        if self._cached_op is None:
            raise MXNetError("run forward at least once under hybridize() "
                             "before export")
        self._cached_op.symbol.save("%s-symbol.json" % path)
        arg_dict = {}
        for slot in self._cached_arg_map:
            if isinstance(slot, Parameter):
                arg_dict["arg:" + slot.name] = slot.data()
        for p in self._cached_aux:
            arg_dict["aux:" + p.name] = p.data()
        from ..ndarray import save as nd_save

        nd_save("%s-%04d.params" % (path, epoch), arg_dict)


class SymbolBlock(HybridBlock):
    """Wrap an arbitrary Symbol as a Block (reference `block.py:952`)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(list(outputs))
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        self._symbol = outputs
        self._input_names = [s.name for s in inputs]
        arg_names = outputs.list_arguments()
        aux_names = outputs.list_auxiliary_states()
        # register under the ORIGINAL graph names (no prefix): the symbol
        # owns the naming here, matching the reference's SymbolBlock
        for name in arg_names:
            if name not in self._input_names and \
                    name not in self.params._params:
                self.params._params[name] = Parameter(
                    name, allow_deferred_init=True)
        for name in aux_names:
            if name not in self.params._params:
                self.params._params[name] = Parameter(
                    name, grad_req="null", allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        symbol = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        block = SymbolBlock(symbol, inputs)
        if param_file is not None:
            from ..ndarray import load as nd_load

            loaded = nd_load(param_file)
            by_name = {}
            for k, v in loaded.items():
                by_name[k.replace("arg:", "").replace("aux:", "")] = v
            for name, p in block.params.items():
                if name in by_name:
                    p._shape = tuple(by_name[name].shape)
                    p.initialize(ctx=ctx or [current_context()])
                    p.set_data(by_name[name])
        return block

    def forward(self, x, *args):
        if not isinstance(x, NDArray):
            raise MXNetError("SymbolBlock input must be NDArray")
        if self._cached_op is None:
            self._build_symbol_cache(len(args) + 1)
        return self._run_cached(x, *args)

    def _build_symbol_cache(self, n_inputs):
        self._cached_op = CachedOp(self._symbol,
                                   (("program_name", self.name),))
        by_name = {p.name: p for p in self.params.values()}
        self._cached_arg_map = []
        for i, name in enumerate(self._cached_op._arg_names):
            if name in self._input_names:
                self._cached_arg_map.append(self._input_names.index(name))
            else:
                self._cached_arg_map.append(by_name[name])
        self._cached_aux = [by_name[n] for n in self._cached_op._aux_names]
        self._cached_op.set_data_indices(
            [pos for pos, slot in enumerate(self._cached_arg_map)
             if isinstance(slot, int)])
        n_out = len(self._symbol.list_outputs())
        self._out_fmt = 0 if n_out == 1 else [0] * n_out
        self._in_fmt = [0] * n_inputs

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise MXNetError("SymbolBlock has no hybrid_forward")
