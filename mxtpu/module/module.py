"""Module — symbolic training over one or more devices.

Reference: `python/mxnet/module/module.py` — `bind` (:364) builds the
DataParallelExecutorGroup, `init_optimizer` (:474) decides
kvstore/update_on_kvstore via `model._create_kvstore`, `update`
(:644-662) routes through the kvstore or per-device updaters.
"""
from __future__ import annotations

import contextlib
import logging
import os
from typing import Any, Dict, List, Optional

from ..base import MXNetError
from ..context import Context, current_context
from ..initializer import InitDesc, Uniform
from ..io.io import DataDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     load_latest as _load_latest_checkpoint,
                     save_checkpoint)
from .. import health as _health
from .. import perf as _perf
from .. import profiler as _prof
from .. import resilience as _res
from ..ndarray.ndarray import NDArray, zeros
from .. import optimizer as opt_mod
from .base_module import BaseModule
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None,
                 group2ctxs=None, compression_params=None):
        super().__init__(logger=logger)
        if context is None:
            context = [current_context()]
        if isinstance(context, Context):
            context = [context]
        self._context = list(context)
        self._monitor = None
        self._work_load_list = work_load_list
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._compression_params = compression_params

        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names + \
            list(state_names or [])
        self._param_names = [n for n in arg_names if n not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        self._arg_params: Optional[Dict[str, NDArray]] = None
        self._aux_params: Optional[Dict[str, NDArray]] = None
        self._params_dirty = False

        self._exec_group: Optional[DataParallelExecutorGroup] = None
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a Module from a checkpoint (reference `module.py:149`)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    @staticmethod
    def load_latest(prefix, load_optimizer_states=False, **kwargs):
        """Auto-resume: build a Module from the newest COMPLETE
        checkpoint under ``prefix`` (corrupt/partial ones are skipped
        via the CRC manifest — see `model.load_latest`).  Returns
        ``(module, epoch)``, or None when no restorable checkpoint
        exists (caller starts fresh)."""
        found = _load_latest_checkpoint(prefix)
        if found is None:
            return None
        sym, args, auxs, epoch = found
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        states = "%s-%04d.states" % (prefix, epoch)
        if load_optimizer_states and os.path.exists(states):
            mod._preload_opt_states = states
        return mod, epoch

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Atomic checkpoint (see `model.save_checkpoint`): params,
        symbol AND optimizer state land under one CRC manifest, so a
        crash mid-save never leaves a half-checkpoint that
        `load_latest` would trust."""
        self._sync_params_from_devices()
        states = self._optimizer_state_bytes() if save_optimizer_states \
            else None
        save_checkpoint(prefix, epoch, self.symbol, self._arg_params,
                        self._aux_params, states=states)

    def _optimizer_state_bytes(self):
        if not self.optimizer_initialized:
            raise MXNetError("init_optimizer() first")
        if self._update_on_kvstore:
            if self._kvstore._updater is None:
                raise MXNetError("kvstore has no updater to serialize")
            return self._kvstore._updater.get_states(dump_optimizer=False)
        return self._updater.get_states()

    # -- properties ---------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        if not self.binded:
            raise MXNetError("not bound")
        return self._exec_group.data_shapes

    @property
    def label_shapes(self):
        if not self.binded:
            raise MXNetError("not bound")
        return self._exec_group.label_shapes

    @property
    def output_shapes(self):
        if not self.binded:
            raise MXNetError("not bound")
        shapes = self.symbol.infer_shape(
            **{d.name: d.shape for d in self.data_shapes})[1]
        return list(zip(self._output_names, shapes))

    # -- params -------------------------------------------------------------
    def get_params(self):
        if not (self.binded and self.params_initialized):
            raise MXNetError("bind() and init_params() first")
        self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def _sync_params_from_devices(self):
        if self._params_dirty and self._exec_group is not None:
            self._exec_group.get_params(self._arg_params, self._aux_params)
            if self._kvstore is not None and self._update_on_kvstore:
                for name, arr in sorted(self._arg_params.items()):
                    try:
                        self._kvstore.pull(name, arr)
                    except MXNetError:
                        pass
            self._params_dirty = False

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            return
        if not self.binded:
            raise MXNetError("bind() first")
        if self._arg_params is None:
            self._arg_params = {
                name: zeros(arrs[0].shape, dtype=arrs[0].dtype)
                for name, arrs in zip(self._exec_group.param_names,
                                      self._exec_group.param_arrays)}
        if self._aux_params is None:
            self._aux_params = {
                name: zeros(arrs[0].shape, dtype=arrs[0].dtype)
                for name, arrs in zip(self._exec_group.aux_names,
                                      self._exec_group.aux_arrays)}

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache[name].copyto(arr)
            elif cache is not None and not allow_missing:
                raise MXNetError("%s not found in provided params" % name)
            elif initializer is not None:
                initializer(InitDesc(name, attrs=self.symbol.attr_dict()
                                     .get(name, {})), arr)

        attrs = {}
        for name, arr in sorted(self._arg_params.items()):
            _impl(name, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            _impl(name, arr, aux_params)
        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params,
                                    allow_extra=allow_extra)

    # -- bind ---------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._exec_group = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        shared_group = None
        if shared_module is not None:
            if not (shared_module.binded and
                    shared_module.params_initialized):
                raise MXNetError("shared_module must be bound+initialized")
            shared_group = shared_module._exec_group

        # mx.shard: Module is where the replica count becomes known, so
        # an ambient unpinned plan is resolved HERE — the shard pass
        # running under this bind stamps the real n onto the graph
        # (provenance shows "zero1:n=<replicas>", not a placeholder)
        from .. import sharding as _shard

        plan = _shard.current_plan()
        bind_scope = (
            _shard.plan_scope(plan.resolved(len(self._context)))
            if plan is not None and not plan.resolved_explicitly
            and len(self._context) > 1
            else contextlib.nullcontext())
        with bind_scope:
            self._exec_group = DataParallelExecutorGroup(
                self._symbol, self._context, self._work_load_list,
                data_shapes,
                label_shapes if for_training else (label_shapes or None),
                self._param_names, for_training, inputs_need_grad,
                shared_group, logger=self.logger,
                fixed_param_names=self._fixed_param_names,
                grad_req=grad_req)
        if shared_module is not None and shared_module.params_initialized:
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            self.params_initialized = True
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    # -- optimizer ----------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Install the optimizer, routing updates through the kvstore
        when one is configured (reference `module.py` init_optimizer).

        `dist_sync` scales ``rescale_grad`` by the CONFIGURED worker
        count (``kvstore.num_workers``) and deliberately keeps it there
        under elastic membership: when a worker dies, sync rounds
        completed by the survivors are rescaled server-side by
        ``nw0/live`` (`docs/elastic.md`), so gradient averaging stays
        exact without rebinding or touching the optimizer — and a
        rejoining worker (``kvstore.rejoined``) slots back in with the
        identical rescale."""
        if not (self.binded and self.params_initialized):
            raise MXNetError("bind() and init_params() first")
        if self.optimizer_initialized and not force_init:
            return
        if self._params_dirty:
            self._sync_params_from_devices()

        kvstore, update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), self._arg_params)
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and \
                "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        # mx.shard: an active ShardingPlan (or MXTPU_SHARD=zero1) with
        # multiple replica contexts engages the ZeRO-1 sharded updater
        # — one updater, state in 1/N chunks — instead of N full
        # per-device updaters.  The plan owns the update PLACEMENT
        # too: in-process kvstores (local/device/tpu) drop to
        # aggregation-only so the sharded update runs here (the dist
        # PS keeps its server-side updates — sharding those is the
        # recsys item, ROADMAP 4).  The shard pass stamped the same
        # plan on the graph at bind.
        from .. import sharding as _shard

        plan = _shard.current_plan()
        zero1_possible = (plan is not None and len(self._context) > 1
                          and plan.shard_optimizer_state
                          and self._zero1_ok(optimizer))
        if zero1_possible and update_on_kvstore \
                and kvstore is not None and "dist" not in kvstore.type:
            update_on_kvstore = False
        use_zero1 = zero1_possible and not update_on_kvstore
        if use_zero1:
            plan = plan.resolved(len(self._context))

        idx2name = {}
        if update_on_kvstore or use_zero1:
            idx2name.update(enumerate(self._exec_group.param_names))
        else:
            for k in range(len(self._context)):
                idx2name.update(
                    {i * len(self._context) + k: n
                     for i, n in enumerate(self._exec_group.param_names)})

        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params) if not \
                isinstance(optimizer_params, dict) else dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt_mod.create(optimizer,
                                       param_idx2name=idx2name,
                                       sym=self.symbol, **optimizer_params)
        else:
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but "
                    "rescale_grad != 1.0/batch_size (%s vs %s)",
                    optimizer.rescale_grad, rescale_grad)
            optimizer.idx2name = idx2name.copy()

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        self._sharding_plan = plan if use_zero1 else None
        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._exec_group.param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        elif use_zero1:
            from ..sharding.zero1 import ZeRO1Updater

            self.logger.info("mx.shard: ZeRO-1 optimizer-state sharding "
                             "engaged (%s) over %d replicas",
                             plan.describe(), len(self._context))
            self._updater = ZeRO1Updater(optimizer, plan,
                                         idx2name=dict(idx2name))
        else:
            self._updater = opt_mod.get_updater(optimizer)
        self.optimizer_initialized = True
        if hasattr(self, "_preload_opt_states"):
            self.load_optimizer_states(self._preload_opt_states)
            del self._preload_opt_states

    @staticmethod
    def _zero1_ok(optimizer) -> bool:
        """Whether the (possibly not-yet-created) optimizer supports
        the elementwise-slicing contract of ZeRO-1."""
        if isinstance(optimizer, str):
            klass = opt_mod.Optimizer.opt_registry.get(optimizer.lower())
            return bool(klass is not None
                        and getattr(klass, "zero1_compatible", True))
        return bool(getattr(optimizer, "zero1_compatible", True))

    def borrow_optimizer(self, shared_module):
        """Share optimizer/kvstore/updater with another Module bound to
        the same parameters (BucketingModule, reference `module.py:604`)."""
        if not shared_module.optimizer_initialized:
            raise MXNetError("shared module has no optimizer")
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self._sharding_plan = getattr(shared_module, "_sharding_plan",
                                      None)
        self.optimizer_initialized = True

    # -- execution ----------------------------------------------------------
    def _step_num(self):
        """The update count: the ``step`` of this iteration's
        ``mx:forward`` / ``mx:backward`` / ``mx:optimizer`` spans."""
        return self._optimizer.num_update if self.optimizer_initialized \
            else None

    def forward(self, data_batch, is_train=None):
        with _prof.span("mx:forward", "loop", step=self._step_num(),
                        site="module"):
            self._forward(data_batch, is_train)

    def _forward(self, data_batch, is_train):
        if not (self.binded and self.params_initialized):
            raise MXNetError("bind() and init_params() first")
        # re-bind on shape change (bucketing / last partial batch)
        curr_shapes = [d.shape for d in self._exec_group.data_shapes]
        new_shapes = [a.shape for a in data_batch.data]
        has_label = bool(getattr(data_batch, "label", None))
        # a labeled batch arriving while the bound exec group has no
        # label slots (e.g. after an unlabeled-batch rebind) must force
        # a rebind, or labels would silently never be copied in
        needs_label_rebind = (has_label and self.for_training
                              and not self._exec_group.label_shapes)
        effective_train = self.for_training if is_train is None else is_train
        if curr_shapes != new_shapes and not effective_train \
                and self._exec_group.can_forward_ragged(data_batch):
            # serving path: a ragged inference batch rides the
            # executor's shape-bucketed dispatch — the rebind below
            # would rebuild the executor and recompile per batch size.
            # A graph the bucketed dispatch can't serve (e.g. one that
            # combines a ragged input with a bound-shape arg the batch
            # didn't provide) falls through to the rebind path.
            try:
                self._exec_group.forward_ragged(data_batch)
                return
            except Exception:
                self.logger.debug("bucketed dispatch failed; rebinding",
                                  exc_info=True)
        if curr_shapes != new_shapes or needs_label_rebind:
            new_dshapes = [DataDesc(d.name, s) for d, s in
                           zip(self._exec_group.data_shapes, new_shapes)]
            new_lshapes = None
            if has_label:
                if self._exec_group.label_shapes:
                    new_lshapes = [DataDesc(l.name, a.shape) for l, a in
                                   zip(self._exec_group.label_shapes,
                                       data_batch.label)]
                else:
                    new_lshapes = [DataDesc(n, a.shape) for n, a in
                                   zip(self._label_names, data_batch.label)]
            elif self.for_training and self._exec_group.label_shapes:
                # unlabeled batch on a training module: keep the label
                # slots, scaled to the new batch size, so a later
                # labeled batch of this shape trains against fresh labels
                bs = new_shapes[0][0]
                new_lshapes = [DataDesc(l.name, (bs,) + tuple(l.shape[1:]))
                               for l in self._exec_group.label_shapes]
            self.reshape(new_dshapes, new_lshapes)
        self._exec_group.forward(data_batch, is_train)

    def reshape(self, data_shapes, label_shapes=None):
        # pull the freshest device weights into the host dicts first —
        # rebinding from stale host params would revert optimizer updates
        self._sync_params_from_devices()
        old_execs = set(map(id, self._exec_group.execs)) \
            if self._exec_group else set()
        arg_p, aux_p = self._arg_params, self._aux_params
        self.bind(data_shapes, label_shapes,
                  for_training=self.for_training,
                  inputs_need_grad=self.inputs_need_grad, force_rebind=True)
        if arg_p is not None:
            self._exec_group.set_params(arg_p, aux_p)
        if self._monitor is not None:
            # drop the discarded executors from the monitor before
            # installing the new group
            self._monitor.exes = [e for e in self._monitor.exes
                                  if id(e) not in old_execs]
            self._exec_group.install_monitor(self._monitor)

    def warmup(self):
        """AOT-compile the bound executors' programs
        (`Executor.warmup`): with the persistent compile cache enabled
        this turns the serving cold-start into cache deserialization,
        and the first real batch compiles nothing."""
        if not self.binded:
            raise MXNetError("bind() first")
        self._exec_group.warmup()
        return self

    def backward(self, out_grads=None):
        if not (self.binded and self.params_initialized):
            raise MXNetError("bind() and init_params() first")
        with _prof.span("mx:backward", "loop", step=self._step_num(),
                        site="module"):
            self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Apply optimizer using accumulated gradients (reference
        `module.py:644-662`)."""
        if not (self.binded and self.params_initialized and
                self.optimizer_initialized):
            raise MXNetError("init_optimizer() first")
        with _prof.span("mx:optimizer", "loop", step=self._step_num(),
                        site="module"):
            self._update()

    def _update(self):
        from .. import telemetry as _tel

        # deferred no-stall grad health on the Executor path; detection
        # re-executes the context the executor registered on its last
        # train dispatch.  Runs regardless of MXTPU_MAX_BAD_STEPS: the
        # Module path has no bad-step guard of its own (the Trainer /
        # FusedTrainLoop guards do not cover it), so arming the guard
        # must not silently turn monitoring OFF here.
        _health.monitor_grads("module", self._grad_vals)
        _health.maybe_stream_stats(self._stats_triple, site="module",
                                   scale=self._update_scale())
        self._params_dirty = True
        # perf phase attribution (mx.perf): the whole host-side update
        # segment — kvstore aggregation included — is the `optimizer`
        # phase of a Module step (the compiled fwd+bwd was accounted by
        # the Executor dispatch hook)
        pt0 = _perf.begin()
        if self._update_on_kvstore:
            _update_params_on_kvstore(self._exec_group.param_arrays,
                                      self._exec_group.grad_arrays,
                                      self._kvstore,
                                      self._exec_group.param_names)
        else:
            _update_params(self._exec_group.param_arrays,
                           self._exec_group.grad_arrays,
                           updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore,
                           param_names=self._exec_group.param_names)
        _perf.note_phase_since("optimizer", pt0)
        _tel.record_step(batch_size=self._exec_group.batch_size,
                         site="module")

    def _grad_vals(self):
        return [g._data
                for glist in self._exec_group.grad_arrays
                for g in glist if g is not None]

    def _update_scale(self) -> float:
        """lr x rescale_grad — makes the streamed update_ratio a real
        |Δw|/|w| estimate for plain SGD (best-effort; 1.0 when the
        optimizer hides its schedule)."""
        try:
            opt = self._optimizer
            lr = opt.lr if opt.lr_scheduler is None \
                else opt.lr_scheduler(opt.num_update)
            return abs(float(lr) * float(opt.rescale_grad))
        except Exception:
            return 1.0

    def _stats_triple(self):
        """(names, param vals, grad vals) for health stat streaming
        (first device replica)."""
        g = self._exec_group
        # param_arrays/grad_arrays skip param names absent from the
        # graph args — mirror that filter so the zip stays aligned
        pnames = [n for n in g.param_names if n in g.arg_names]
        names, ps, gs = [], [], []
        for name, parr, garr in zip(pnames, g.param_arrays,
                                    g.grad_arrays):
            if garr and garr[0] is not None:
                names.append(name)
                ps.append(parr[0]._data)
                gs.append(garr[0]._data)
        return names, ps, gs

    def get_outputs(self, merge_multi_context=True):
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        if not self.inputs_need_grad:
            raise MXNetError("bind with inputs_need_grad=True")
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        self._exec_group.update_metric(eval_metric, labels, pre_sliced)

    def install_monitor(self, mon):
        if not self.binded:
            raise MXNetError("bind() first")
        self._monitor = mon
        self._exec_group.install_monitor(mon)

    # -- optimizer state ------------------------------------------------------
    def save_optimizer_states(self, fname):
        if not self.optimizer_initialized:
            raise MXNetError("init_optimizer() first")
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with _res.atomic_write(fname) as f:
                f.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        if not self.optimizer_initialized:
            raise MXNetError("init_optimizer() first")
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())
