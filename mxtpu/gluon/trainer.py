"""Trainer (reference: `python/mxnet/gluon/trainer.py:27`).

Applies an Optimizer to a set of Parameters.  Reference flow
(`trainer.py:258`): `step()` -> `_allreduce_grads` (kvstore push/pull) ->
`_update` (fused optimizer ops per device).  Here single-device updates
run directly; multi-device/multi-chip gradient aggregation goes through
the kvstore ('device'/'tpu' = XLA collectives — see mxtpu/kvstore.py).
"""
from __future__ import annotations

import time as _time
from typing import Any, Dict, List, Optional

from ..base import MXNetError
from .. import checkpoint as _ckpt
from .. import health as _health
from .. import optimizer as opt_mod
from .. import perf as _perf
from .. import profiler as _prof
from .. import resilience as _res
from .. import telemetry as _tel
from .. import tracing as _tracing
from ..ndarray.ndarray import NDArray
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


class Trainer(object):
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None, sharding_plan=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a ParameterDict/list")
        self._params: List[Parameter] = []
        self._param2idx: Dict[str, int] = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError("invalid parameter %r" % p)
            self._param2idx[p.name] = i
            self._params.append(p)
        self._compression_params = compression_params
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_type = kvstore
        self._kvstore = None
        self._kv_initialized = False
        self._update_on_kvstore = update_on_kvstore
        self._params_to_init = []
        self._contexts = None
        self._bad_step_guard = None  # built lazily from MXTPU_MAX_BAD_STEPS
        # mx.shard: an explicit plan, or the ambient one at _init_kvstore
        # time, engages the ZeRO-1 sharded updater over the replicas
        self._sharding_plan = sharding_plan
        self._zero1 = None
        # steps applied so far — the round anchor mx.checkpoint stamps
        # fleet snapshots with (restored on resume)
        self._num_steps = 0

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            if optimizer_params and list(optimizer_params) != ["rescale_grad"]:
                raise MXNetError(
                    "optimizer_params must be None when optimizer is an "
                    "Optimizer instance")
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt_mod.create(optimizer,
                                             param_dict=param_dict,
                                             **optimizer_params)
        self._updaters = [opt_mod.get_updater(self._optimizer)]

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx()
            if contexts is not None and contexts != ctx:
                raise MXNetError("all Parameters must be on the same "
                                 "context set, got %s and %s"
                                 % (contexts, ctx))
            contexts = ctx
        return contexts or []

    def _init_kvstore(self):
        self._contexts = self._check_contexts()
        kv = self._kvstore_type
        if kv is None or (isinstance(kv, str) and kv in ("", "none")) or \
                len(self._contexts) <= 1 and kv in ("local", "device"):
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            from .. import kvstore as kv_mod

            self._kvstore = kv if not isinstance(kv, str) \
                else kv_mod.create(kv)
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            if self._update_on_kvstore is None:
                self._update_on_kvstore = False
            if self._update_on_kvstore:
                self._kvstore.set_optimizer(self._optimizer)
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.init(i, param.data())
        self._init_zero1()
        self._kv_initialized = True

    def _init_zero1(self):
        """Engage the ZeRO-1 sharded updater when a ShardingPlan is in
        force (ctor arg, active `mx.shard` scope, or MXTPU_SHARD env),
        there are multiple replica contexts, and the optimizer honors
        the elementwise-slicing contract.  One updater replaces the N
        per-replica full-state updaters (`docs/sharding.md`)."""
        from .. import sharding as _shard

        plan = self._sharding_plan if self._sharding_plan is not None \
            else _shard.current_plan()
        if (plan is None or self._update_on_kvstore
                or len(self._contexts) <= 1
                or not plan.shard_optimizer_state
                or not getattr(self._optimizer, "zero1_compatible", True)):
            self._zero1 = None
            return
        plan = plan.resolved(len(self._contexts))
        self._sharding_plan = plan
        idx2name = {i: p.name for i, p in enumerate(self._params)}
        self._zero1 = _shard.ZeRO1Updater(self._optimizer, plan,
                                          idx2name=idx2name)

    @property
    def live_workers(self):
        """Workers currently alive in the distributed group (elastic
        membership, `docs/elastic.md`); 1 without a kvstore.  The
        gradient-averaging contract needs NO adjustment when this
        drops: `dist_sync` rounds completed by fewer workers are
        rescaled server-side by ``nw0/live``, so the fixed
        ``rescale_grad = 1/batch`` here keeps averaging exact over the
        survivors."""
        if not self._kv_initialized:
            self._init_kvstore()
        return self._kvstore.live_workers if self._kvstore is not None \
            else 1

    @property
    def learning_rate(self):
        return self._optimizer.lr if self._optimizer.lr_scheduler is None \
            else self._optimizer.lr_scheduler(self._optimizer.num_update)

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce gradients then apply optimizer (reference
        `trainer.py:258`).

        Graceful degradation: with ``MXTPU_MAX_BAD_STEPS`` > 0 a step
        whose gradients contain NaN/Inf is SKIPPED (params and
        optimizer state untouched, `bad_steps_skipped` ticks in
        `profiler.stats()`), and only that many CONSECUTIVE bad steps
        abort the run (mxtpu/resilience.py BadStepGuard).  Default 0:
        no guard, no per-step device sync."""
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        if _res.max_bad_steps() > 0:
            # check BEFORE the allreduce: with update_on_kvstore the
            # push itself applies the update, so a post-allreduce check
            # would come too late to skip anything (and a non-finite
            # local grad makes the merged grad non-finite anyway).
            # ONE fused finiteness+norm program over the whole grad
            # tree (mx.health) replaces the old per-array sync loop.
            if self._bad_step_guard is None:
                self._bad_step_guard = _res.BadStepGuard(site="trainer")
            finite, gnorm = _health.grad_check(self._grad_vals())
            if not finite:
                # provenance first (the blame record + flight dump must
                # exist even if the guard aborts on this step)
                _health.on_nonfinite("trainer", gnorm=gnorm)
            if self._bad_step_guard.record(finite):
                # still a wall step: the telemetry stream records it as
                # skipped — with the grad norm and step id, so a burst
                # is diagnosable post-hoc from the flight recorder
                _tel.record_step(batch_size=batch_size, skipped=True,
                                 site="trainer", grad_norm=gnorm)
                return  # skip allreduce + update entirely
            _health.observe_grad_norm(gnorm)
        else:
            # guard off: deferred no-stall grad monitoring on the
            # MXTPU_HEALTH_CHECK_EVERY cadence
            _health.monitor_grads("trainer", self._grad_vals)
        # causal tracing (mx.tracing): head-sample this step; when
        # sampled, the ambient context makes the perf phase hooks and
        # the kvstore wire layer attach child spans (step ->
        # collective/optimizer -> kvstore round -> server apply).
        # step_trace() is one float compare when MXTPU_TRACE_SAMPLE=0.
        trc = _tracing.step_trace()
        if trc is not None:
            _tracing.set_current(trc)
            st0 = _time.perf_counter()
        # perf phase attribution (mx.perf): the two host-side segments
        # of a trainer step outside the compiled forward/backward —
        # gradient allreduce (collective) and the parameter update
        # (optimizer).  begin() is None when MXTPU_PERF=0.
        try:
            pt0 = _perf.begin()
            self._allreduce_grads()
            if self._kvstore is not None:
                _perf.note_phase_since("collective", pt0)
            # opt-in per-layer grad/param-norm streaming (before the
            # update so |Δw|/|w| pairs this step's grads with its
            # pre-step params)
            _health.maybe_stream_stats(
                self._stats_triple, site="trainer",
                scale=abs(self.learning_rate
                          * self._optimizer.rescale_grad))
            pt0 = _perf.begin()
            with _prof.span("mx:optimizer", "loop", step=self._num_steps,
                            site="trainer"):
                self._update(ignore_stale_grad)
            _perf.note_phase_since("optimizer", pt0)
        finally:
            if trc is not None:
                _tracing.set_current(None)
                _tracing.record_span(
                    trc, "step", _time.perf_counter() - st0, root=True,
                    step=_tel.current_step())
        _tel.record_step(batch_size=batch_size, site="trainer")
        self._num_steps += 1
        # mx.checkpoint step-boundary hook: periodic async fleet
        # snapshots and the SIGTERM checkpoint-then-drain flush both
        # fire HERE, at a consistent round boundary (one global read
        # when nothing is armed)
        if _ckpt.active():
            _ckpt.on_boundary(self._num_steps)

    @property
    def step_count(self):
        """Optimizer steps applied by this Trainer (checkpointed and
        restored by `mx.checkpoint` for deterministic re-entry)."""
        return self._num_steps

    def _grad_vals(self):
        vals = []
        for param in self._params:
            if param.grad_req != "null" and param._data is not None:
                vals.extend(g._data for g in param.list_grad())
        return vals

    def _stats_triple(self):
        """(names, param vals, grad vals) for health stat streaming
        (first device replica — the others hold the same values)."""
        names, ps, gs = [], [], []
        for param in self._params:
            if param.grad_req != "null" and param._data is not None:
                names.append(param.name)
                ps.append(param.list_data()[0]._data)
                gs.append(param.list_grad()[0]._data)
        return names, ps, gs

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        with _prof.span("mx:collective", "loop", step=self._num_steps,
                        site="trainer"):
            for i, param in enumerate(self._params):
                if param.grad_req != "null":
                    self._kvstore.push(i, param.list_grad(), priority=-i)
                    if not self._update_on_kvstore:
                        self._kvstore.pull(i, param.list_grad(),
                                           priority=-i, ignore_sparse=False)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError("update() not supported with "
                             "update_on_kvstore=True; call step()")
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._zero1 is not None:
            triples = []
            for i, param in enumerate(self._params):
                if param.grad_req == "null":
                    continue
                if param._data is None:
                    if not ignore_stale_grad:
                        raise MXNetError(
                            "Parameter %s has not been initialized"
                            % param.name)
                    continue
                triples.append((i, param.list_grad(), param.list_data()))
            self._zero1.update_replicas(
                triples, pre_reduced=self._kvstore is not None)
            return
        pending: Dict[int, list] = {}
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if param._data is None:
                if not ignore_stale_grad:
                    raise MXNetError(
                        "Parameter %s has not been initialized" % param.name)
                continue
            if self._update_on_kvstore and self._kvstore is not None:
                self._kvstore.pull(i, param.list_data(), priority=-i)
                continue
            # one updater per device replica: optimizer state (momentum,
            # Adam m/v, step count) must not be shared across copies
            # (reference keeps one updater per device too)
            n_dev = len(param.list_data())
            while len(self._updaters) < n_dev:
                self._updaters.append(
                    opt_mod.get_updater(self._optimizer))
            for k, (arr, grad) in enumerate(zip(param.list_data(),
                                                param.list_grad())):
                pending.setdefault(k, []).append((i, grad, arr))
        # apply queued updates, one fused call per device replica
        # (whole-tree update: a single XLA executable updates every
        # weight/state — the TPU answer to per-param kernel dispatch)
        for k, triples in pending.items():
            self._updaters[k].update_multi(triples)

    def save_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        upd = self._zero1 if self._zero1 is not None else self._updaters[0]
        with _res.atomic_write(fname) as f:
            f.write(upd.get_states(dump_optimizer=True))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        with open(fname, "rb") as f:
            states = f.read()
        if self._zero1 is not None:
            # re-shards under the active plan (replica count may differ
            # from the saver's)
            self._zero1.set_states(states)
            return
        for upd in self._updaters:
            upd.set_states(states)
            upd.optimizer = self._optimizer
