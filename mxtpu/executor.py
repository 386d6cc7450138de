"""Executor: whole-graph XLA lowering of a bound Symbol.

TPU-native re-design of the reference's GraphExecutor
(`src/executor/graph_executor.cc`).  The reference binds a Symbol by
planning memory, attaching per-node engine ops, and pushing them one by
one (`RunOps`, graph_executor.cc:1317).  Here binding lowers the ENTIRE
graph to jitted XLA computations (the BASELINE.json north star):

  * inference: one XLA module  args, aux, key -> outputs
  * training:  one *fused* module  args, aux, key, ograds ->
               (outputs, grads, new_aux)   — forward + backward in a
               single compile, so XLA fuses across the boundary and no
               activation is recomputed.  `forward(is_train=True)` runs
               the fused step with default ones head-gradients (the
               reference seeds ograds with ones too — imperative.cc:302),
               and `backward()` publishes the cached grads.  Explicit
               `backward(out_grads)` flips the executor into a split
               fwd/vjp mode: forward returns outputs plus the vjp
               pullback (a jit-returnable pytree holding the residuals),
               and backward applies the cached closure — the forward is
               never recomputed.

Gradient bookkeeping (grad_req write/add/null per arg) matches
`python/mxnet/executor.py`; PlanMemory/inplace passes have no analog —
XLA buffer assignment owns memory.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .base import MXNetError, np_dtype
from .context import Context, current_context
from .ndarray.ndarray import NDArray
from .symbol.symbol import Symbol, _topo_order
from . import health as _health
from . import perf as _perf

__all__ = ["Executor"]

# reusable (stateless) HBM-forensics guards — one per dispatch surface,
# so the hot path pays one `with` and no allocation
_OOM_FWD = _health.oom_scope("executor")
_OOM_BWD = _health.oom_scope("executor:backward")

_BN_OPS = {"BatchNorm", "BatchNorm_v1", "_contrib_SyncBatchNorm"}

_REMAT_POLICIES = {
    # save matmul/conv outputs, recompute elementwise chains — the
    # TPU-idiomatic middle ground (FLOPs are cheap, HBM is not)
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
    # recompute EVERYTHING in backward (max memory savings)
    "full": None,
}

# The name (`jax.ad_checkpoint.checkpoint_name`) under which code that
# says for itself what "dots" keeps of it names a product's output.
REMAT_DOT = "dot"


def apply_remat(fn, policy_name, prevent_cse=True, named=False, keep=()):
    """Wrap fn in `jax.checkpoint` under the named policy.  The ONE
    remat vocabulary — the symbolic executor's mirror pass and the SPMD
    transformer's per-layer remat both route through here:

    * 'full': save nothing, recompute everything from fn's inputs;
    * 'dots_no_batch': save the outputs of products with no batch
      dimensions;
    * 'dots': save the products' outputs and recompute the elementwise
      chains between them, AND save the flash-attention kernel's two
      results, its merged output and its row log-sums (named `FLASH_OUT`
      / `FLASH_LSE` by `ops/pallas_attention.py`; a `pallas_call` is no
      product, so without the names the backward pass ran the whole
      forward kernel a second time to have them).  Which outputs count
      as products: every `dot_general` / convolution, for a fn that
      names nothing (the symbolic executor's graphs); with `named`, the
      values fn itself names `REMAT_DOT` and no other.  The LM's blocks
      (`parallel/transformer.py`) name each product they want kept and
      leave out the ONE that is cheapest to rebuild per byte from what
      is kept anyway, which pays for the kernel's output in the same
      layer: the out-projection `o @ wo` in `_attention` (the kept
      merged output is its operand: one [B*T, E] x [E, E] product, a
      ninth of the kernel call it saves at gpt2-medium's widths), the
      up-projection of q out of its latent `c_q @ wq_b` in `_mla`
      (as wide as the kept output, a tenth of the kernel call at
      GLM-4.7-Flash's widths; `o @ wo` is narrower and dearer there),
      the output gate's product `x @ w_gate` in `_gqa` (n_heads *
      head_dim wide, as q's is and the kept output: 8 KB a token at
      Trinity-Mini's widths where `o @ wo`'s is 4 and k's and v's 1
      each, all for the same [E, 4096] product or less; q's where the
      block has no gate).

    `keep` names values (`checkpoint_name`) kept besides what the policy
    keeps: the looped stack keeps `FLASH_OUT` / `FLASH_LSE` under "full"
    in every pass but the first (`transformer._looped_loss`).

    Pass prevent_cse=False when fn is a `lax.scan` body: the CSE
    barriers are unnecessary under scan (per the jax.checkpoint docs)
    and only cost backward throughput."""
    import jax

    if policy_name not in _REMAT_POLICIES:
        raise MXNetError("remat policy must be one of %s (got %r)"
                         % (sorted(_REMAT_POLICIES), policy_name))
    cp = jax.checkpoint_policies
    attr = _REMAT_POLICIES[policy_name]
    policy = getattr(cp, attr) if attr else None
    if policy_name == "dots":
        from .ops.pallas_attention import FLASH_OUT, FLASH_LSE

        policy = cp.save_only_these_names(REMAT_DOT, FLASH_OUT, FLASH_LSE) \
            if named else cp.save_from_both_policies(
                policy, cp.save_only_these_names(FLASH_OUT, FLASH_LSE))
    if keep:
        names = cp.save_only_these_names(*keep)
        policy = names if policy is None else \
            cp.save_from_both_policies(policy, names)
    return jax.checkpoint(fn, policy=policy, prevent_cse=prevent_cse)


def _maybe_remat(fn):
    """Gradient-checkpoint the whole-graph function when
    MXTPU_BACKWARD_DO_MIRROR / MXNET_BACKWARD_DO_MIRROR is set — the
    analog of the reference's mirror pass
    (`src/executor/graph_executor.cc:134-283`), built on `jax.checkpoint`
    so XLA rematerializes activations during the backward instead of
    holding them in HBM.  MXTPU_REMAT_POLICY picks what IS saved:
    'full' (default; save nothing), 'dots', or 'dots_no_batch'."""
    import os

    flag = os.environ.get("MXTPU_BACKWARD_DO_MIRROR",
                          os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0"))
    if flag not in ("1", "true", "True"):
        return fn
    return apply_remat(fn, os.environ.get("MXTPU_REMAT_POLICY", "full"))


def _build_graph_fn(symbol: Symbol, arg_names: List[str],
                    aux_names: List[str], is_train: bool):
    """Return fn(arg_vals, aux_vals, key) -> (outputs, new_aux_vals).

    The AMP compute-dtype policy (`mxtpu/amp.py`) is captured HERE, at
    graph-build time: per-op casts are baked into the traced function
    so XLA fuses them into neighboring kernels.

    The graph-rewrite pass pipeline (`mxtpu/passes`, MXTPU_PASSES) also
    runs HERE, ahead of tracing — this is the one choke point every
    compile path funnels through (Executor bind, CachedOp, the
    FusedTrainLoop scan body, control-flow subgraph lowering, health
    re-execution), so a pass-optimized graph is what XLA sees
    everywhere, uniformly.  RNG identity is pinned to the ORIGINAL
    graph first (ensure_rng_ids) so rewrites can never renumber the
    per-node fold_in keys of dropout-style ops."""
    import jax

    from . import amp as _amp
    from . import inspect as _insp
    from . import passes as _passes

    compute_dtype = _amp.get_compute_dtype()
    _passes.ensure_rng_ids(symbol)
    graph, _pass_report = _passes.optimize_for_build(symbol)
    nodes = _topo_order(graph._outputs)
    arg_pos = {n: i for i, n in enumerate(arg_names)}
    aux_pos = {n: i for i, n in enumerate(aux_names)}
    # stable RNG ids: assigned on the original graph in topo order (so
    # the unoptimized numbering is bitwise the legacy rng_i counter)
    # and carried through clones by ext_attrs
    rng_ids = {}
    rng_seq = 0
    for n in nodes:
        if not n.is_variable and n.op.needs_rng:
            rng_ids[id(n)] = _passes.rng_id_of(n, rng_seq)
            rng_seq += 1
    # layer attribution (MXTPU_INSPECT_SCOPES, default on): each node
    # executes under jax.named_scope(node name), so HLO op metadata
    # and jax.profiler device traces resolve back to model layers.
    # A pass-fused elementwise chain traces under its ONE (terminal)
    # name, so inspect attributes the whole region as one layer.
    # Trace-time only — zero runtime cost in the compiled program.
    if _insp.scopes_enabled():
        node_scope = {id(n): _insp.scope_name(n.name) for n in nodes
                      if not n.is_variable}
    else:
        node_scope = None

    def graph_fn_impl(arg_vals, aux_vals, key):
        env: Dict[Tuple[int, int], Any] = {}
        aux_new = list(aux_vals)
        # re-assert the captured policy for the duration of the trace so
        # nested graph builds (control-flow subgraphs constructed while
        # tracing) inherit it even if the thread-local changed since bind
        with _amp.scope(compute_dtype):
            for node in nodes:
                if node.is_variable:
                    if node.is_aux:
                        env[(id(node), 0)] = aux_vals[aux_pos[node.name]]
                    else:
                        env[(id(node), 0)] = arg_vals[arg_pos[node.name]]
                    continue
                invals = [env[(id(inode), idx)]
                          for inode, idx in node.inputs]
                # amp_inline ops (pass-fused chains) apply the per-op
                # cast policy member-wise inside their own fn
                if compute_dtype is not None \
                        and not getattr(node.op, "amp_inline", False):
                    invals = _amp.cast_op_inputs(node.op.name, invals,
                                                 compute_dtype)
                attrs = dict(node.attrs)
                if node.op.train_aware:
                    attrs["is_train"] = is_train
                scope = jax.named_scope(node_scope[id(node)]) \
                    if node_scope is not None else contextlib.nullcontext()
                if node.op.needs_rng:
                    sub = jax.random.fold_in(key, rng_ids[id(node)])
                    with scope:
                        out = node.op.fn(sub, *invals, **attrs)
                else:
                    with scope:
                        out = node.op.fn(*invals, **attrs)
                if not isinstance(out, tuple):
                    out = (out,)
                n_vis = node.op.n_outputs(node.attrs)
                # control-flow ops append their subgraph's updated aux
                # values after the visible outputs; write them back to
                # the matching outer aux slots by name
                if is_train and len(out) > n_vis \
                        and node.attrs.get("sub_aux"):
                    for name, val in zip(node.attrs["sub_aux"],
                                         out[n_vis:]):
                        if name in aux_pos:
                            aux_new[aux_pos[name]] = val
                    out = out[:n_vis]
                for i, o in enumerate(out):
                    env[(id(node), i)] = o
                # BatchNorm-family: fold the moving-stat update into the
                # graph (reference mutates aux NDArrays in-place during
                # forward)
                if is_train and node.op.name in _BN_OPS \
                        and not attrs.get("use_global_stats", False):
                    momentum = float(attrs.get("momentum", 0.9))
                    _, mean, var = out[0], out[1], out[2]
                    mm_node, mv_node = (node.inputs[3][0],
                                        node.inputs[4][0])
                    for aux_node, batch_stat in ((mm_node, mean),
                                                 (mv_node, var)):
                        if aux_node.is_variable and aux_node.is_aux:
                            p = aux_pos[aux_node.name]
                            aux_new[p] = momentum * aux_new[p] + \
                                (1.0 - momentum) * batch_stat
            outputs = [env[(id(n), i)] for n, i in graph._outputs]
        return outputs, aux_new

    # the mirror/remat hook lives HERE so every consumer of the training
    # graph fn (Executor, CachedOp, FusedTrainLoop) honors it uniformly
    return _maybe_remat(graph_fn_impl) if is_train else graph_fn_impl


class Executor(object):
    def __init__(self, symbol: Symbol, ctx: Context,
                 arg_arrays: List[NDArray],
                 grad_arrays: List[Optional[NDArray]],
                 grad_req: List[str],
                 aux_arrays: List[NDArray]):
        import jax

        self._symbol = symbol
        self._ctx = ctx or current_context()
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self.arg_arrays = arg_arrays
        self.grad_arrays = grad_arrays
        self._grad_req = grad_req
        self.aux_arrays = aux_arrays
        self.arg_dict = dict(zip(self._arg_names, arg_arrays))
        self.grad_dict = dict(zip(self._arg_names, grad_arrays))
        self.aux_dict = dict(zip(self._aux_names, aux_arrays))
        self.outputs: List[NDArray] = []
        self._monitor_callback = None

        self._diff_idx = [i for i, r in enumerate(grad_req) if r != "null"]
        self._has_rng = any((not n.is_variable) and n.op.needs_rng
                            for n in _topo_order(symbol._outputs))
        from . import amp as _amp

        # remembered so fused_train can rebuild the graph fn under the
        # SAME compute-dtype policy this executor was bound with
        self._amp_dtype = _amp.get_compute_dtype()

        infer_fn = _build_graph_fn(symbol, self._arg_names, self._aux_names,
                                   is_train=False)
        train_fn = _build_graph_fn(symbol, self._arg_names, self._aux_names,
                                   is_train=True)

        def fwd_infer(arg_vals, aux_vals, key):
            outs, _ = infer_fn(arg_vals, aux_vals, key)
            return outs

        diff_idx = self._diff_idx

        def fused_step(arg_vals, aux_vals, key, ograds):
            diff_vals = [arg_vals[i] for i in diff_idx]

            def f(dvals):
                full = list(arg_vals)
                for i, v in zip(diff_idx, dvals):
                    full[i] = v
                outs, aux_new = train_fn(full, aux_vals, key)
                return outs, aux_new

            (outs, aux_new), vjp = jax.vjp(f, diff_vals)
            zero_aux = [jax.numpy.zeros_like(a) for a in aux_new]
            (dgrads,) = vjp((list(ograds), zero_aux))
            return outs, dgrads, aux_new

        from . import compile_cache as _cc

        # donate the aux buffers (BN running stats) on the training hot
        # paths: forward writes fresh aux back every step anyway, so the
        # old buffers are dead the moment the program runs — donation
        # lets XLA update them in place instead of allocating new HBM
        # per step (fused_train.py and the optimizer kernels already do
        # this).  ograds are NOT donated: the default ones head-gradients
        # are a cached step-invariant buffer (see _forward_impl), and
        # donating would delete it after the first step, forcing a fresh
        # host->device ones transfer per step — strictly worse than the
        # copy donation saves.  MXTPU_DONATE=0 opts out.
        self._donate = _cc.donation_enabled()
        aux_dn = (1,) if self._donate else ()
        self._jit_fwd_infer = jax.jit(fwd_infer)
        self._jit_step = jax.jit(fused_step, donate_argnums=aux_dn)

        def fwd_train_only(arg_vals, aux_vals, key):
            return train_fn(arg_vals, aux_vals, key)

        self._jit_fwd_train = jax.jit(fwd_train_only, donate_argnums=aux_dn)
        self._cached_grads = None

        # explicit-ograd support: forward returns outputs PLUS the vjp
        # pullback (a jit-returnable pytree closing over the residuals),
        # so backward(out_grads) applies the cached closure instead of
        # re-running the whole fused step (2x compute).  Only engaged
        # once a caller actually passes out_grads — the default ones-
        # ograd path stays ONE fused dispatch per step.
        def fwd_vjp(arg_vals, aux_vals, key):
            diff_vals = [arg_vals[i] for i in diff_idx]

            def f(dvals):
                full = list(arg_vals)
                for i, v in zip(diff_idx, dvals):
                    full[i] = v
                return train_fn(full, aux_vals, key)

            (outs, aux_new), vjp = jax.vjp(f, diff_vals)
            return outs, aux_new, vjp

        def apply_vjp(vjp, ograds, aux_new):
            zero_aux = [jax.numpy.zeros_like(a) for a in aux_new]
            (dgrads,) = vjp((list(ograds), zero_aux))
            return dgrads

        self._jit_fwd_vjp = jax.jit(fwd_vjp, donate_argnums=aux_dn)
        self._jit_apply_vjp = jax.jit(apply_vjp)
        self._explicit_ograd_mode = False
        self._cached_vjp = None
        self._last_fwd_state = None

        # compile-lifecycle bookkeeping: AOT executables from warmup()
        # keyed by input signature, and the set of signatures this
        # executor has dispatched (drives the profiler retrace stats)
        self._aot_infer = None
        self._aot_step = None
        self._seen_sigs: set = set()
        self._pad_masks: Dict = {}
        # program-inspector registry record (mx.inspect): signatures,
        # compile wall times, retrace blame, lazy cost/HLO analysis
        from . import inspect as _insp

        self._insp = _insp.program("executor", symbol.name,
                                   arg_names=self._arg_names,
                                   symbol=symbol)
        # device-memory layout (mx.hbm): how this site's example-arg
        # tree (arg_vals, aux_vals, key[, ograds]) maps to the plan's
        # param/data/grad classes — diff args are params, the rest is
        # input data
        self._insp.mem_layout = {
            "layout": "executor",
            "arg_names": list(self._arg_names),
            "param_names": [self._arg_names[i] for i in self._diff_idx],
            "aux_names": list(self._aux_names),
        }

    # -- binding entry points --------------------------------------------
    @staticmethod
    def _normalize_grad_req(grad_req, arg_names: List[str]) -> List[str]:
        if isinstance(grad_req, str):
            return [grad_req] * len(arg_names)
        if isinstance(grad_req, (list, tuple)):
            return list(grad_req)
        if isinstance(grad_req, dict):
            return [grad_req.get(n, "null") for n in arg_names]
        raise MXNetError("bad grad_req %r" % (grad_req,))

    @staticmethod
    def _simple_bind(symbol: Symbol, ctx, grad_req, type_dict, shape_kwargs):
        import jax.numpy as jnp

        ctx = ctx or current_context()
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shape_kwargs)
        type_dict = type_dict or {}
        arg_arrays = []
        for name, shape in zip(arg_names, arg_shapes):
            dt = np_dtype(type_dict.get(name, np.float32))
            arg_arrays.append(NDArray(jnp.zeros(shape, dtype=dt), ctx=ctx))
        reqs = Executor._normalize_grad_req(grad_req, arg_names)
        # data/label inputs (the ones whose shapes the caller provided)
        # default to no gradient, like the reference's simple_bind
        for i, name in enumerate(arg_names):
            if name in shape_kwargs and isinstance(grad_req, str):
                reqs[i] = "null"
        grad_arrays = [
            NDArray(jnp.zeros(s, dtype=a.dtype), ctx=ctx)
            if r != "null" else None
            for s, a, r in zip(arg_shapes, arg_arrays, reqs)
        ]
        aux_arrays = [NDArray(jnp.zeros(s, dtype=np.float32), ctx=ctx)
                      for s in aux_shapes]
        return Executor(symbol, ctx, arg_arrays, grad_arrays, reqs, aux_arrays)

    @staticmethod
    def _bind(symbol: Symbol, ctx, args, args_grad, grad_req, aux_states):
        import jax.numpy as jnp

        ctx = ctx or current_context()
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, dict):
            arg_arrays = [args[n] for n in arg_names]
        else:
            arg_arrays = list(args or [])
        if len(arg_arrays) != len(arg_names):
            raise MXNetError("bind: expected %d args, got %d"
                             % (len(arg_names), len(arg_arrays)))
        reqs = Executor._normalize_grad_req(grad_req, arg_names)
        if args_grad is None:
            grad_arrays = [None] * len(arg_names)
            reqs = ["null"] * len(arg_names)
        elif isinstance(args_grad, dict):
            grad_arrays = [args_grad.get(n) for n in arg_names]
            reqs = [r if g is not None else "null"
                    for r, g in zip(reqs, grad_arrays)]
        else:
            grad_arrays = list(args_grad)
        if aux_states is None:
            aux_arrays = []
            if aux_names:
                _, _, aux_shapes = symbol.infer_shape(
                    **{n: a.shape for n, a in zip(arg_names, arg_arrays)})
                aux_arrays = [NDArray(jnp.zeros(s, dtype=np.float32), ctx=ctx)
                              for s in aux_shapes]
        elif isinstance(aux_states, dict):
            aux_arrays = [aux_states[n] for n in aux_names]
        else:
            aux_arrays = list(aux_states)
        return Executor(symbol, ctx, arg_arrays, grad_arrays, reqs, aux_arrays)

    # -- execution --------------------------------------------------------
    def _key(self):
        if self._has_rng:
            from . import random as _rnd

            return _rnd._next_key()
        import jax

        return jax.random.PRNGKey(0)

    def _arg_vals(self):
        return [a._data for a in self.arg_arrays]

    def _aux_vals(self):
        return [a._data for a in self.aux_arrays]

    def forward(self, is_train: bool = False, **kwargs):
        from . import profiler as _prof

        if _prof.is_recording("symbolic"):
            with _prof.span("Executor::forward(%s)"
                            % self._symbol.name, "symbolic") as sp:
                outs = self._forward_impl(is_train, **kwargs)
                # under MXTPU_PROFILER_SYNC the span blocks on exactly
                # these outputs for a true device timing
                sp.result = [o._data for o in outs]
                return outs
        return self._forward_impl(is_train, **kwargs)

    def _forward_impl(self, is_train: bool = False, **kwargs):
        # HBM forensics: a RESOURCE_EXHAUSTED escaping any dispatch
        # below re-raises as MemoryExhaustedError + attribution report
        with _OOM_FWD:
            return self._forward_dispatch(is_train, **kwargs)

    def _forward_dispatch(self, is_train: bool = False, **kwargs):
        from . import compile_cache as _cc
        from . import profiler as _prof

        # inference inputs whose leading batch dim differs from the
        # bound shape: routed through the bucketed dispatch below
        # instead of mutating the bound arrays (arg position -> value)
        ragged: Dict[int, Any] = {}
        for name, val in kwargs.items():
            if name not in self.arg_dict:
                raise MXNetError("unknown argument %r" % name)
            dst = self.arg_dict[name]
            src = val if isinstance(val, NDArray) else NDArray(val, ctx=self._ctx)
            if src.shape != dst.shape:
                if not is_train and len(src.shape) == len(dst.shape) \
                        and src.shape[1:] == dst.shape[1:] \
                        and _cc.bucketing_enabled():
                    ragged[self._arg_names.index(name)] = (
                        src._data.astype(dst.dtype)
                        if src.dtype != dst.dtype else src._data)
                    continue
                raise MXNetError("shape mismatch for %r: %s vs bound %s"
                                 % (name, src.shape, dst.shape))
            dst._set_jax(src._data.astype(dst.dtype)
                         if src.dtype != dst.dtype else src._data)
        key = self._key()
        if is_train and self._diff_idx and _health.want_context():
            # NaN-provenance context: the NDArray wrappers (not raw jax
            # buffers — aux donation would kill those) + this step's
            # RNG key, so a later non-finite detection can re-execute
            # THIS dispatch eagerly and blame the first offending
            # layer.  want_context() = enabled AND diagnosis budget
            # left, so spent processes stop paying for capture
            _health.register_context("executor", self._symbol,
                                     self._arg_names, self._aux_names,
                                     self.arg_arrays, self.aux_arrays,
                                     key, self._amp_dtype)
        self._last_key = key  # reused by explicit-ograd backward so the
        # gradients see the SAME dropout/random masks as these outputs
        # when donating, the pre-step aux buffers die inside the jit
        # call, so _last_fwd_state must not capture them — the explicit-
        # ograd fallback in backward() substitutes the (post-writeback)
        # current aux instead, which leaves gradients unchanged: in
        # train mode BatchNorm outputs use batch stats, so aux only
        # feeds the momentum update whose cotangent is zeroed
        saved_aux = None if self._donate else self._aux_vals()
        if is_train and self._diff_idx and self._explicit_ograd_mode:
            # split path: outputs + residual-closing vjp in one dispatch;
            # backward applies the cached pullback (no fwd recompute)
            tok = self._track_sig("train", self._arg_vals())
            self._last_fwd_state = (self._arg_vals(), saved_aux, key)
            pt0 = _perf.begin()
            outs, aux_new, vjp = self._jit_fwd_vjp(
                self._arg_vals(), self._aux_vals(), key)
            if tok is not None:
                tok.done(self._jit_fwd_vjp,
                         (self._arg_vals(), self._aux_vals(), key))
            _perf.end(self._insp.name, "executor", pt0, outputs=outs)
            self._cached_vjp = (vjp, aux_new)
            self._cached_grads = None
            self._write_aux(aux_new)
        elif is_train and self._diff_idx:
            import jax.numpy as jnp

            # the default ones head-gradients are step-invariant: build
            # them once (each jnp.ones is otherwise a tiny device
            # program per training step)
            ograds = getattr(self, "_ones_ograds", None)
            if ograds is None:
                ograds = [jnp.ones(s, dtype=d)
                          for s, d in self._out_avals()]
                self._ones_ograds = ograds
            # remembered so a FIRST explicit-ograd backward can build
            # the vjp for THIS step without semantic drift (jax arrays
            # are immutable; holding the refs is free)
            self._last_fwd_state = (self._arg_vals(), saved_aux, key)
            pt0 = _perf.begin()
            if self._aot_step is not None:
                _prof.inc_stat("executor_aot_hit")
                self._insp.hit()
                outs, grads, aux_new = self._aot_step(
                    self._arg_vals(), self._aux_vals(), key, ograds)
            else:
                tok = self._track_sig("train", self._arg_vals())
                outs, grads, aux_new = self._jit_step(
                    self._arg_vals(), self._aux_vals(), key, ograds)
                if tok is not None:
                    tok.done(self._jit_step,
                             (self._arg_vals(), self._aux_vals(), key,
                              ograds))
            # block target = outputs AND grads: the fused step's device
            # span must cover the backward half too
            _perf.end(self._insp.name, "executor", pt0,
                      outputs=(outs, grads))
            self._cached_grads = grads
            self._write_aux(aux_new)
        elif is_train:
            tok = self._track_sig("train", self._arg_vals())
            pt0 = _perf.begin()
            outs, aux_new = self._jit_fwd_train(
                self._arg_vals(), self._aux_vals(), key)
            if tok is not None:
                tok.done(self._jit_fwd_train,
                         (self._arg_vals(), self._aux_vals(), key))
            _perf.end(self._insp.name, "executor", pt0, outputs=outs)
            self._write_aux(aux_new)
        elif ragged:
            outs = self._forward_bucketed(ragged, key)
        else:
            pt0 = _perf.begin()
            if self._aot_infer is not None:
                _prof.inc_stat("executor_aot_hit")
                self._insp.hit()
                outs = self._aot_infer(self._arg_vals(), self._aux_vals(),
                                       key)
            else:
                tok = self._track_sig("infer", self._arg_vals())
                outs = self._jit_fwd_infer(self._arg_vals(),
                                           self._aux_vals(), key)
                if tok is not None:
                    tok.done(self._jit_fwd_infer,
                             (self._arg_vals(), self._aux_vals(), key))
            _perf.end(self._insp.name, "executor", pt0, outputs=outs)
        self.outputs = [NDArray(o, ctx=self._ctx, _committed=True)
                        for o in outs]
        return self.outputs

    def _forward_bucketed(self, ragged: Dict[int, Any], key):
        """Inference dispatch for inputs whose leading batch dim differs
        from the bound shape: pad up to the policy's bucket so a bounded
        set of compiled programs serves ALL ragged sizes, then slice the
        batch-carrying outputs back (which outputs those are comes from
        shape inference, cached — see compile_cache.batch_output_mask).
        Bound arg arrays are left untouched (only this dispatch sees the
        padded values).  Shapes whose outputs don't all track the batch
        dim run exact (unpadded) instead — correct, one compile per
        size."""
        from . import compile_cache as _cc
        from . import profiler as _prof

        sizes = {v.shape[0] for v in ragged.values()}
        if len(sizes) != 1:
            raise MXNetError("ragged inputs disagree on leading batch "
                             "dim: %s" % sorted(sizes))
        b = sizes.pop()
        bp = _cc.bucket_batch(b)
        mask = None
        if bp != b:
            mask = self._pad_mask(ragged, b, bp)
        call_vals = self._arg_vals()
        if mask is not None:
            for i, v in ragged.items():
                call_vals[i] = _cc.pad_leading(v, bp)
            _prof.inc_stat("executor_bucket_pad")
        else:
            for i, v in ragged.items():
                call_vals[i] = v
            if bp != b:
                _prof.inc_stat("executor_bucket_fallback")
        tok = self._track_sig("infer", call_vals)
        pt0 = _perf.begin()
        outs = self._jit_fwd_infer(call_vals, self._aux_vals(), key)
        if tok is not None:
            tok.done(self._jit_fwd_infer,
                     (call_vals, self._aux_vals(), key))
        _perf.end(self._insp.name, "executor", pt0, outputs=outs)
        if mask is not None:
            outs = [o[:b] if m else o for o, m in zip(outs, mask)]
        return outs

    def _pad_mask(self, ragged: Dict[int, Any], b: int, bp: int):
        """Per-output slice mask for padding b -> bp (cached); None when
        padding is unsafe (some output does not carry the batch dim)."""
        from . import compile_cache as _cc

        shapes_u = tuple((b,) + tuple(a.shape[1:])
                         if i in ragged else tuple(a.shape)
                         for i, a in enumerate(self.arg_arrays))
        key = (b, bp, shapes_u)
        if key in self._pad_masks:
            return self._pad_masks[key]
        shapes_p = tuple((bp,) + s[1:] if i in ragged else s
                         for i, s in enumerate(shapes_u))
        mask = _cc.batch_output_mask(self._symbol, self._arg_names,
                                     shapes_u, shapes_p)
        if mask is not None and not all(mask):
            mask = None
        self._pad_masks[key] = mask
        return mask

    def _track_sig(self, kind: str, vals):
        """Retrace accounting for one dispatch — see
        ``inspect.track_compile`` for the contract (None on hit,
        pending-compile token on a new signature)."""
        from . import compile_cache as _cc
        from . import inspect as _insp_mod

        return _insp_mod.track_compile(
            self._insp, self._seen_sigs, "executor_%s" % kind,
            "executor:%s" % kind, kind, _cc.sig_of(vals),
            arg_names=self._arg_names)

    def warmup(self, for_training: Optional[bool] = None):
        """AOT-compile this executor's programs via
        ``jit(...).lower().compile()`` (no execution) and dispatch
        subsequent calls straight to the stored executables, so the
        first real request after warmup compiles nothing.  With the
        persistent compile cache enabled the lower/compile here is a
        disk hit on warm process starts — together they make the
        serving cold-start a pure deserialization.  Compiles the
        inference program always and the fused train step when this
        executor has gradients (override with ``for_training``).
        Returns self."""
        import jax

        from . import compile_cache as _cc
        from . import profiler as _prof

        if for_training is None:
            for_training = bool(self._diff_idx)
        args = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                for a in self.arg_arrays]
        aux = [jax.ShapeDtypeStruct(a.shape, a.dtype)
               for a in self.aux_arrays]
        k = jax.random.PRNGKey(0)
        key = jax.ShapeDtypeStruct(k.shape, k.dtype)
        self._aot_infer = _cc.aot_compile(self._jit_fwd_infer,
                                          (args, aux, key),
                                          program=self._insp, kind="infer")
        _prof.inc_stat("executor_warmup")
        if for_training and self._diff_idx:
            ograds = [jax.ShapeDtypeStruct(s, d)
                      for s, d in self._out_avals()]
            self._aot_step = _cc.aot_compile(self._jit_step,
                                             (args, aux, key, ograds),
                                             program=self._insp,
                                             kind="train")
            _prof.inc_stat("executor_warmup")
        return self

    def backward(self, out_grads=None):
        with _OOM_BWD:
            return self._backward_impl(out_grads)

    def _backward_impl(self, out_grads=None):
        if not self._diff_idx:
            return
        if out_grads is None:
            if self._cached_vjp is not None:
                import jax.numpy as jnp

                ograds = getattr(self, "_ones_ograds", None)
                if ograds is None:
                    ograds = [jnp.ones(s, dtype=d)
                              for s, d in self._out_avals()]
                    self._ones_ograds = ograds
                vjp, aux_new = self._cached_vjp
                grads = self._jit_apply_vjp(vjp, ograds, aux_new)
                self._cached_vjp = None
            elif self._cached_grads is None:
                raise MXNetError("backward() before forward(is_train=True)")
            else:
                grads = self._cached_grads
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            ograds = [g._data for g in out_grads]
            if self._cached_vjp is not None:
                vjp, aux_new = self._cached_vjp
                grads = self._jit_apply_vjp(vjp, ograds, aux_new)
                self._cached_vjp = None
            else:
                # first explicit-ograd call: build the pullback from the
                # forward we already ran, then stay in split mode so
                # future steps never compute the forward twice
                self._explicit_ograd_mode = True
                if self._last_fwd_state is not None:
                    arg_vals, aux_vals, key = self._last_fwd_state
                else:
                    key = getattr(self, "_last_key", None) or self._key()
                    arg_vals, aux_vals = self._arg_vals(), None
                if aux_vals is None:
                    # donation mode never stores aux (the buffers were
                    # donated into the forward); the current post-update
                    # aux yields identical grads — see _forward_impl
                    aux_vals = self._aux_vals()
                if self._donate:
                    import jax.numpy as jnp

                    # _jit_fwd_vjp donates its aux argument, but here the
                    # executor's live aux arrays fill that slot and the
                    # recomputed aux_new is discarded (it was already
                    # applied by the forward) — feed copies so the live
                    # buffers survive this one-time mode switch
                    aux_vals = [jnp.copy(a) for a in aux_vals]
                _, aux_new, vjp = self._jit_fwd_vjp(arg_vals, aux_vals, key)
                grads = self._jit_apply_vjp(vjp, ograds, aux_new)
        for j, i in enumerate(self._diff_idx):
            garr = self.grad_arrays[i]
            if garr is None:
                continue
            if self._grad_req[i] == "add":
                garr._set_jax(garr._data + grads[j])
            else:
                garr._set_jax(grads[j])
        self._cached_grads = None

    def _out_avals(self):
        if getattr(self, "_out_avals_c", None) is None:
            import jax

            outs, _ = jax.eval_shape(self._jit_fwd_train, self._arg_vals(),
                                     self._aux_vals(), self._key())
            self._out_avals_c = [(tuple(o.shape), np.dtype(o.dtype))
                                 for o in outs]
        return self._out_avals_c

    def _write_aux(self, aux_new):
        for arr, val in zip(self.aux_arrays, aux_new):
            arr._set_jax(val)

    # -- utilities --------------------------------------------------------
    def copy_params_from(self, arg_params: Dict[str, NDArray],
                         aux_params: Optional[Dict[str, NDArray]] = None,
                         allow_extra_params: bool = False):
        for name, arr in (arg_params or {}).items():
            if name in self.arg_dict:
                arr.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise MXNetError("unknown arg param %r" % name)
        for name, arr in (aux_params or {}).items():
            if name in self.aux_dict:
                arr.copyto(self.aux_dict[name])
            elif not allow_extra_params:
                raise MXNetError("unknown aux param %r" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        shapes = {n: a.shape for n, a in self.arg_dict.items()}
        shapes.update(kwargs)
        new_exec = Executor._simple_bind(
            self._symbol, self._ctx,
            {n: r for n, r in zip(self._arg_names, self._grad_req)},
            None, shapes)
        for n, a in self.arg_dict.items():
            if new_exec.arg_dict[n].shape == a.shape:
                a.copyto(new_exec.arg_dict[n])
        for n, a in self.aux_dict.items():
            if new_exec.aux_dict[n].shape == a.shape:
                a.copyto(new_exec.aux_dict[n])
        return new_exec

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))
