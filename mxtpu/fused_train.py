"""Fused multi-step training: K train steps per device dispatch.

TPU-native counterpart of the reference's engine-level op bulking
(`src/engine/threaded_engine.h:411-426` BulkStatus; executor bulk
segments `src/executor/graph_executor.cc:1186`).  The reference
amortizes per-op scheduling cost by fusing engine ops into segments;
on TPU the analogous overhead is per-PROGRAM dispatch: the per-step
path issues forward, backward and the optimizer update as separate
host dispatches.  So the TPU-first design lifts the bulking
one level higher: forward, backward AND the optimizer update for K
consecutive batches are traced into ONE XLA program (`lax.scan` over
the staged batches), with the parameter, optimizer-state and aux
buffers donated (`jax.jit(donate_argnums=...)`) so XLA updates them in
place instead of allocating fresh HBM each step.  What the fusion is
worth on a locally attached chip is not measured yet (PERF.md).

Semantics are EXACTLY the per-step path's: the optimizer's lr schedule
and bias-correction advance per step (effective lrs are precomputed
host-side for the K steps and fed through the scan), BatchNorm moving
stats update per step in the carry, and dropout keys fold per global
step index.  Equivalence is asserted by `tests/test_fused_train.py`.

Usage (single-device Module, local/absent kvstore)::

    loop = FusedTrainLoop(module, steps_per_program=8)
    for chunk in chunks_of(batches, 8):
        outputs = loop.run(chunk)          # ONE dispatch, 8 steps
    loop.finalize()  # publish params/opt state + drain the deferred
                     # health read (guard-off non-finite detection for
                     # the LAST chunk happens here — do call it)
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .base import MXNetError
from .executor import _build_graph_fn
from .ndarray.ndarray import NDArray
from . import checkpoint as _ckpt
from . import health as _health
from . import perf as _perf
from . import profiler as _prof
from . import resilience as _res
from . import xprof as _xprof

__all__ = ["FusedTrainLoop"]

_OOM_RUN = _health.oom_scope("fused_train")


class FusedTrainLoop(object):
    """Compile a Module's whole train step (fwd+bwd+optimizer) into one
    donated XLA program that scans over ``steps_per_program`` batches.

    Requirements: module is bound for training on ONE device with
    params initialized and a local (non-kvstore) optimizer whose type
    has a `make_scan_step` form (SGD / Adam), all grad_req in
    {write, null}.  Raises MXNetError otherwise.
    """

    def __init__(self, module, steps_per_program: Optional[int] = None,
                 collect_outputs: bool = True, unroll: Optional[int] = None):
        import os

        import jax

        if steps_per_program is None:
            # an explicit constructor arg always wins over the env
            steps_per_program = int(
                os.environ.get("MXTPU_STEPS_PER_PROGRAM", "8") or 8)
        if not (module.binded and module.params_initialized and
                module.optimizer_initialized):
            raise MXNetError("FusedTrainLoop: module must be bound, "
                             "initialized and have an optimizer")
        if len(module._context) != 1:
            raise MXNetError("FusedTrainLoop: single-device modules only "
                             "(use kvstore='tpu' data parallelism for "
                             "multi-device)")
        if module._update_on_kvstore or module._kvstore is not None:
            raise MXNetError("FusedTrainLoop: kvstore-backed updates not "
                             "supported; init_optimizer(kvstore=None)")
        self._module = module
        self._exec = module._exec_group.execs[0]
        self._K = int(steps_per_program)
        self._collect = collect_outputs
        if self._K < 1:
            raise MXNetError("steps_per_program must be >= 1")
        ex = self._exec
        if any(r not in ("write", "null") for r in ex._grad_req):
            raise MXNetError("FusedTrainLoop: grad_req 'add' not supported")

        self._arg_names = ex._arg_names
        self._diff_idx = list(ex._diff_idx)
        data_names = set(module._data_names) | set(module._label_names)
        self._data_idx = [i for i, n in enumerate(self._arg_names)
                          if i not in set(self._diff_idx)
                          and n in data_names]
        self._fixed_idx = [i for i in range(len(self._arg_names))
                           if i not in set(self._diff_idx)
                           and i not in set(self._data_idx)]

        # updater-index of each carried param (single device: index =
        # position in exec_group.param_names, matching idx2name)
        pname_pos = {n: i for i, n in
                     enumerate(module._exec_group.param_names)}
        self._opt_indices = [pname_pos[self._arg_names[i]]
                             for i in self._diff_idx]

        optimizer = module._optimizer
        weights = [ex.arg_arrays[i] for i in self._diff_idx]
        self._scan_step = optimizer.make_scan_step(self._opt_indices,
                                                   weights)
        if self._scan_step is None:
            raise MXNetError("FusedTrainLoop: optimizer %r has no scan "
                             "step form" % type(optimizer).__name__)
        self._optimizer = optimizer
        self._updater = module._updater

        # device-resident state tree, seeded from the updater's states
        # (created on demand) so switching per-step <-> fused mid-train
        # is seamless
        self._state_objs = []
        for idx, w in zip(self._opt_indices, weights):
            if idx not in self._updater.states:
                self._updater.states[idx] = \
                    optimizer.create_state_multi_precision(idx, w)
                self._updater.states_synced[idx] = True
            self._state_objs.append(self._updater.states[idx])
        if any(s is not None for s in self._state_objs):
            self._s_tree = self._scan_step.pack_states(self._state_objs)
        else:
            self._s_tree = self._scan_step.init_states(
                [w._data for w in weights])
        self._p_vals = [w._data for w in weights]
        self._aux_vals = [a._data for a in ex.aux_arrays]
        self._t = 0  # global step counter (dropout key folding)

        # XLA:CPU barely parallelizes inside while-loop bodies (a rolled
        # scan of convs runs ~70x slower than the same ops unrolled), so
        # on CPU the scan defaults to fully unrolled; on TPU the rolled
        # form compiles K x faster with identical runtime.  Override via
        # the arg or MXTPU_FUSED_UNROLL.
        if unroll is None:
            env = os.environ.get("MXTPU_FUSED_UNROLL")
            if env is not None:
                unroll = max(1, int(env))
            else:
                unroll = self._K if jax.default_backend() == "cpu" else 1
        self._unroll = min(self._K, max(1, int(unroll)))

        # graceful degradation (MXTPU_MAX_BAD_STEPS > 0): each scanned
        # step checks its gradients for NaN/Inf INSIDE the program and
        # keeps the previous params/opt-state/aux when they are not
        # finite; the per-step bad flags come back to the host, which
        # aborts after that many CONSECUTIVE skips.  Note the
        # optimizer's num_update still advances for skipped steps (the
        # lr schedule stays aligned with wall steps).
        # mx.shard: an active SPMD plan (mesh + ZeRO-1) shards the
        # scanned optimizer-state carry over the mesh's data axis —
        # params stay replicated, each device holds 1/N of every
        # moment, and GSPMD compiles the reduce-scatter/allgather into
        # the K-step program itself (arXiv 2004.13336 — this is the
        # "fused K-step loop composes with it" half of ROADMAP item 1)
        self._shard_plan = None
        self._carry_pin = None
        self._init_sharded_carry(weights)

        self._guard = _res.BadStepGuard(site="fused_train") \
            if _res.max_bad_steps() > 0 else None
        # health observatory (mx.health): even without the guard, the
        # scanned program carries per-step grad finiteness + the global
        # grad norm out (one fused reduction — the always-on cheap
        # mode).  Guard armed => flags are read synchronously (the
        # skip/abort contract needs them NOW); guard off => the flags
        # are read one chunk LATER so the loop never stalls on them.
        self._track_health = self._guard is not None or _health.enabled()
        self._stats_on = _health.enabled() and _health.stats_every() > 0
        self._stats_count = 0
        self._pending_health = None  # (t0, key, stack, bad_dev, gn_dev)

        self._jit_program = jax.jit(self._make_program(),
                                    donate_argnums=(0, 1, 2))

        # program-inspector registry record (mx.inspect): the fused
        # K-step program is a first-class compile site — signature =
        # the staged data stacks (params/opt-state shapes are fixed)
        from . import inspect as _insp

        self._insp = _insp.program(
            "fused_train", ex._symbol.name,
            arg_names=[self._arg_names[i] for i in self._data_idx],
            symbol=ex._symbol)
        # device-memory layout (mx.hbm): the program tree is (p_vals,
        # s_tree, aux_vals, fixed_vals, base_key, t0, data_stack,
        # lr_rows) — params/opt-state/aux are the donated carry, the
        # stacks are (K, B, ...) input data
        self._insp.mem_layout = {
            "layout": "fused_train",
            "param_names": [self._arg_names[i] for i in self._diff_idx],
            "aux_names": list(ex._aux_names),
            "fixed_names": [self._arg_names[i] for i in self._fixed_idx],
            "data_names": [self._arg_names[i] for i in self._data_idx],
        }
        self._seen_sigs: set = set()

    def _init_sharded_carry(self, weights) -> None:
        """Re-place the scan carry for an active SPMD ShardingPlan:
        optimizer state sharded per `plan.opt_state_spec`, params/aux
        replicated and PINNED so GSPMD cannot drift the forward into a
        partitioned (reassociated) computation.  No-op without a plan
        mesh."""
        import jax

        from . import sharding as _shard

        plan = _shard.current_plan()
        if plan is None or plan.mesh is None \
                or not plan.shard_optimizer_state \
                or int(np.prod(plan.mesh.devices.shape)) <= 1:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        tu = jax.tree_util
        mesh = plan.mesh
        rep = NamedSharding(mesh, P())
        names = [self._arg_names[i] for i in self._diff_idx]
        w_shardings = [
            NamedSharding(mesh, plan.opt_state_spec(n, w.shape))
            for n, w in zip(names, weights)]
        leaves, treedef = tu.tree_flatten(self._s_tree)
        k = len(weights)
        if k == 0 or not leaves or len(leaves) % k != 0:
            # no optimizer state (e.g. momentum-free SGD) = nothing to
            # shard, no collectives to account — stay unsharded
            return
        s_shard_leaves = w_shardings * (len(leaves) // k)
        s_shardings = tu.tree_unflatten(treedef, s_shard_leaves)
        self._p_vals = [jax.device_put(v, rep) for v in self._p_vals]
        self._aux_vals = [jax.device_put(v, rep) for v in self._aux_vals]
        self._s_tree = tu.tree_map(lambda v, sh: jax.device_put(v, sh),
                                   self._s_tree, s_shardings)
        self._shard_plan = plan
        self._rep_sharding = rep
        self._s_shardings = s_shardings
        # per-chunk collective payload estimate (ring convention, see
        # docs/sharding.md): params whose state spec actually shards
        n = plan.num_shards
        sharded_bytes = sum(
            int(np.prod(w.shape)) * w.dtype.itemsize
            for w, sh in zip(weights, w_shardings)
            if any(ax is not None for ax in sh.spec))
        self._collective_bytes_per_step = \
            int(sharded_bytes * (n - 1) / float(n)) if n > 1 else 0

        def pin(new_p, new_s, aux_new):
            wsc = jax.lax.with_sharding_constraint
            new_p = [wsc(a, rep) for a in new_p]
            new_s = tu.tree_map(lambda a, sh: wsc(a, sh), new_s,
                                s_shardings)
            aux_new = [wsc(a, rep) for a in aux_new]
            return new_p, new_s, aux_new

        self._carry_pin = pin

    def sharding_info(self) -> Optional[Dict[str, Any]]:
        """Live carry placement: plan, total state bytes, and the
        per-device state bytes (the ZeRO-1 1/N memory win, measurable
        on the virtual CPU mesh and on real chips alike).  None when
        the carry is unsharded."""
        if self._shard_plan is None:
            return None
        import jax

        leaves = [l for l in jax.tree_util.tree_leaves(self._s_tree)]
        total = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                    for l in leaves)
        per_dev: Dict[str, int] = {}
        for leaf in leaves:
            for sh in leaf.addressable_shards:
                key = str(sh.device.id)
                per_dev[key] = per_dev.get(key, 0) + int(
                    np.prod(sh.data.shape)) * leaf.dtype.itemsize
        return {"plan": self._shard_plan.describe(),
                "state_total_bytes": total,
                "state_bytes_per_device": per_dev}

    def _make_program(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from . import amp as _amp

        ex = self._exec
        n_args = len(self._arg_names)
        diff_idx, data_idx, fixed_idx = (self._diff_idx, self._data_idx,
                                         self._fixed_idx)
        with _amp.scope(ex._amp_dtype):
            train_fn = _build_graph_fn(ex._symbol, ex._arg_names,
                                       ex._aux_names, is_train=True)
        step = self._scan_step.step
        collect = self._collect
        guard_on = self._guard is not None
        track_health = self._track_health
        stats_on = self._stats_on
        carry_pin = self._carry_pin

        def program(p_vals, s_tree, aux_vals, fixed_vals, base_key, t0,
                    data_stack, lr_rows):
            def body(carry, xs):
                p, s, aux, t = carry
                data_vals, lr_row = xs
                key = jax.random.fold_in(base_key, t)

                def f(pv):
                    full = [None] * n_args
                    for j, i in enumerate(diff_idx):
                        full[i] = pv[j]
                    for j, i in enumerate(fixed_idx):
                        full[i] = fixed_vals[j]
                    for j, i in enumerate(data_idx):
                        full[i] = data_vals[j]
                    return train_fn(full, aux, key)

                (outs, aux_new), vjp = jax.vjp(f, p)
                ones = [jnp.ones_like(o) for o in outs]
                zaux = [jnp.zeros_like(a) for a in aux_new]
                (grads,) = vjp((ones, zaux))
                new_p, new_s = step(p, s, grads, lr_row)
                if track_health:
                    # in-graph grad health: finiteness + global l2 norm
                    # in the same fused reductions (a norm overflow is
                    # folded into the flag so isfinite(sq) can't mask a
                    # per-element NaN)
                    sq = jnp.float32(0.0)
                    ok = jnp.bool_(True)
                    if stats_on:
                        lnorms = []
                    for g in grads:
                        g32 = g.astype(jnp.float32)
                        gsq = jnp.sum(jnp.square(g32))
                        sq = sq + gsq
                        ok = ok & jnp.isfinite(g32).all()
                        if stats_on:
                            lnorms.append(jnp.sqrt(gsq))
                    ok = ok & jnp.isfinite(sq)
                    if guard_on:
                        # non-finite step: keep params, opt state AND
                        # aux (a blown-up forward poisons BN stats too)
                        new_p = [jnp.where(ok, a, b)
                                 for a, b in zip(new_p, p)]
                        new_s = jax.tree_util.tree_map(
                            lambda a, b: jnp.where(ok, a, b), new_s, s)
                        aux_new = [jnp.where(ok, a, b)
                                   for a, b in zip(aux_new, aux)]
                    ys = {"outs": tuple(outs) if collect else (),
                          "bad": ~ok, "gnorm": jnp.sqrt(sq)}
                    if stats_on:
                        ys["lnorms"] = tuple(lnorms)
                else:
                    ys = tuple(outs) if collect else ()
                if carry_pin is not None:
                    # sharded-carry mode: params/aux pinned replicated,
                    # opt state pinned to its ZeRO-1 placement, every
                    # scan iteration — GSPMD keeps the forward
                    # replicated and the update sharded
                    new_p, new_s, aux_new = carry_pin(new_p, new_s,
                                                      aux_new)
                return (new_p, new_s, aux_new, t + 1), ys

            (p, s, aux, _), outs = lax.scan(
                body, (p_vals, s_tree, aux_vals, t0),
                (data_stack, lr_rows), unroll=self._unroll)
            return p, s, aux, outs

        return program

    # -- data staging -----------------------------------------------------
    def stack_batches(self, batches: Sequence[Any]):
        """Stack K DataBatches into per-slot (K, ...) arrays on the
        module's device (host payloads: one transfer per batch)."""
        import jax
        import jax.numpy as jnp

        if len(batches) != self._K:
            raise MXNetError("expected %d batches, got %d"
                             % (self._K, len(batches)))
        mod = self._module
        dev = self._exec._ctx.jax_device
        stacks = []
        for j, i in enumerate(self._data_idx):
            name = self._arg_names[i]
            if name in mod._data_names:
                slot = mod._data_names.index(name)
                vals = [b.data[slot] for b in batches]
            else:
                slot = mod._label_names.index(name)
                vals = [b.label[slot] for b in batches]
            want = self._exec.arg_arrays[i].dtype
            parts = []
            for v in vals:
                arr = jax.device_put(
                    v._data if isinstance(v, NDArray) else v, dev)
                parts.append(arr.astype(want) if arr.dtype != want else arr)
            stacks.append(jnp.stack(parts))
        return stacks

    def _program_args(self, data_stack, base_key):
        """The full positional argument tuple `_jit_program` takes —
        single source of truth shared by run_stacked (execute) and
        lower_stacked (AOT analysis) so the two can't drift."""
        import jax.numpy as jnp

        lr_rows = self._scan_step.host_sched(self._K)
        fixed_vals = [self._exec.arg_arrays[i]._data
                      for i in self._fixed_idx]
        t0 = jnp.int32(self._t)
        lr_arr = jnp.asarray(lr_rows)
        if self._shard_plan is not None:
            # sharded-carry mode: every non-carry input rides the mesh
            # replicated (the carry was placed at init; jit propagates
            # from there)
            import jax

            rep = self._rep_sharding
            data_stack = [jax.device_put(d, rep) for d in data_stack]
            fixed_vals = [jax.device_put(v, rep) for v in fixed_vals]
            base_key = jax.device_put(base_key, rep)
            t0 = jax.device_put(t0, rep)
            lr_arr = jax.device_put(lr_arr, rep)
        return (self._p_vals, self._s_tree, self._aux_vals, fixed_vals,
                base_key, t0, data_stack, lr_arr)

    def lower_stacked(self, data_stack: List[Any]):
        """AOT-lower the fused K-step program for a staged stack
        (`jax.jit(...).lower`) without executing it.  `.compile()` the
        result for optimized-HLO text / cost / memory analysis — this
        is what `tools/hlo_report.py` uses for static attribution."""
        import jax

        return self._jit_program.lower(
            *self._program_args(data_stack, jax.random.PRNGKey(0)))

    # -- execution --------------------------------------------------------
    def run_stacked(self, data_stack: List[Any]):
        """Run K fused steps over pre-staged (K, ...) slot arrays.
        Returns stacked outputs (list of (K, ...) NDArrays) when
        collect_outputs, else None.

        While `mx.profiler.armed()` the call is one ``mx:step`` span
        whose children say what the host did (``mx:host_args``,
        ``mx:host_dispatch``, ``mx:publish``, ``mx:observe.<module>``)
        and where it waited for the device (``mx:device_wait``): see
        docs/observability.md."""
        _prof.inc_stat("fused_programs")
        _prof.inc_stat("fused_steps", self._K)
        with _prof.span("mx:step", "loop", step=self._t, k=self._K,
                        site="fused_train"):
            return self._run_stacked(data_stack)

    def _run_stacked(self, data_stack: List[Any]):
        import time as _time

        import jax

        from . import random as _rnd
        from . import telemetry as _tel

        from . import compile_cache as _cc
        from . import inspect as _insp_mod

        K = self._K
        t_base = self._t
        with _prof.span("mx:observe.inspect", "loop"):
            tok = _insp_mod.track_compile(
                self._insp, self._seen_sigs, "fused_train", "fused_train",
                "train", _cc.sig_of(data_stack),
                arg_names=[self._arg_names[i] for i in self._data_idx])
        with _prof.span("mx:host_args", "loop"):
            base_key = _rnd._next_key() if self._exec._has_rng \
                else jax.random.PRNGKey(0)
            prog_args = self._program_args(data_stack, base_key)
        t0 = _time.monotonic()
        pt0 = _perf.begin()
        with _prof.span("mx:host_dispatch", "loop"), _OOM_RUN:
            p, s, aux, outs = self._jit_program(*prog_args)
        if tok is not None:
            with _prof.span("mx:observe.inspect", "loop"):
                tok.done(self._jit_program, prog_args)
        # block target = the new params: produced LAST in the scanned
        # program, so call->ready spans the full K-step chunk
        with _prof.span("mx:observe.perf", "loop"):
            _perf.end(self._insp.name, "fused_train", pt0, outputs=p, n=K)
        bad_flags = gnorms = lnorms = prev_health = None
        if self._track_health:
            bad_dev, gn_dev = outs["bad"], outs["gnorm"]
            lnorms = outs.get("lnorms")
            outs = outs["outs"]
            if self._guard is not None:
                # guard armed: the skip/abort contract needs the flags
                # NOW (synchronous read — the PR 2 behavior)
                with _prof.span("mx:device_wait", "loop", why="guard"):
                    bad_flags = np.asarray(bad_dev)
                    gnorms = np.asarray(gn_dev)
            else:
                # guard off: defer the host read one chunk — by the
                # next run these scalars are long since materialized,
                # so the loop never stalls on its own health check.
                # The batch stacks are held ONLY while a diagnosis
                # could still run (bounded by MXTPU_HEALTH_MAX_DIAG).
                prev_health = self._pending_health
                self._pending_health = (
                    t_base, base_key,
                    data_stack if _health.want_context() else None,
                    bad_dev, gn_dev)
        with _prof.span("mx:publish", "loop"):
            self._p_vals, self._s_tree, self._aux_vals = p, s, aux
            self._t += K
            self._optimizer.commit_scan_steps(self._opt_indices, K)
            if self._shard_plan is not None \
                    and self._collective_bytes_per_step:
                # the ring-payload estimate of what GSPMD moved for the
                # K sharded updates (reduce-scatter grads in, allgather
                # params out) — same counters the eager ZeRO-1 engine
                # ticks
                _prof.inc_stat("reduce_scatter_bytes",
                               self._collective_bytes_per_step * K)
                _prof.inc_stat("allgather_bytes",
                               self._collective_bytes_per_step * K)
            self._publish()
        with _prof.span("mx:observe.telemetry", "loop"):
            # one record for the whole K-step program: per-step batch
            # size is the second dim of the staged (K, batch, ...) stacks
            batch = int(data_stack[0].shape[1]) \
                if data_stack and getattr(data_stack[0], "ndim", 0) > 1 \
                else 0
            skipped_n = int(bad_flags.sum()) if bad_flags is not None \
                else None
            _tel.record_step(batch_size=batch, n=K,
                             duration=_time.monotonic() - t0,
                             site="fused_train", skipped_n=skipped_n,
                             grad_norm=float(gnorms[-1])
                             if gnorms is not None else None)
        if self._stats_on and lnorms is not None:
            self._maybe_emit_stats(lnorms)
        if bad_flags is not None:
            # state is already published (skipped steps kept the old
            # buffers in-program); blame the FIRST bad step, then
            # account per-step health and abort on too many
            # CONSECUTIVE skips
            with _prof.span("mx:observe.health", "loop"):
                if bad_flags.any():
                    k = int(np.argmax(bad_flags))
                    _health.on_nonfinite(
                        "fused_train", gnorm=float(gnorms[k]),
                        ctx=self._diag_ctx(data_stack, base_key, t_base, k))
                for gn, bad in zip(gnorms, bad_flags):
                    if not bad:
                        _health.observe_grad_norm(float(gn))
                for bad in bad_flags:
                    self._guard.record(not bool(bad))
        elif prev_health is not None:
            self._check_pending(prev_health)
        # mx.checkpoint boundary: the end of a K-step chunk is the only
        # point where host copies of params/opt-state are coherent, so
        # periodic snapshots and SIGTERM flushes both anchor here
        with _prof.span("mx:observe.checkpoint", "loop"):
            if _ckpt.active():
                _ckpt.on_boundary(self._t)
        # mx.xprof auto-profile cadence (MXTPU_XPROF_EVERY, default
        # off): when disarmed this is two int/bool checks per chunk
        with _prof.span("mx:observe.xprof", "loop"):
            _xprof.maybe_autoprofile(self, data_stack)
        if self._collect:
            ctx = self._exec._ctx
            return [NDArray(o, ctx=ctx, _committed=True) for o in outs]
        return None

    # -- health hooks -----------------------------------------------------
    def _diag_ctx(self, data_stack, base_key, t_base: int, k: int):
        """Diagnosis context for scanned step ``k`` of a chunk: the
        exact batch slice and RNG key that step saw, with the CURRENT
        params/aux standing in for the mid-scan values (donation
        consumed those; with the guard on, skipped steps kept the
        pre-divergence buffers, so the stand-in is close)."""
        import jax

        ex = self._exec
        full = [None] * len(self._arg_names)
        for j, i in enumerate(self._diff_idx):
            full[i] = self._p_vals[j]
        for i in self._fixed_idx:
            full[i] = ex.arg_arrays[i]
        for j, i in enumerate(self._data_idx):
            full[i] = data_stack[j][k]
        key = jax.random.fold_in(base_key, t_base + k)
        return ("fused_train", ex._symbol, self._arg_names,
                ex._aux_names, full, list(ex.aux_arrays), key,
                ex._amp_dtype)

    def _check_pending(self, pending) -> None:
        """Read the PREVIOUS chunk's deferred health scalars (ready by
        now — their program finished before this chunk dispatched)."""
        t_base, base_key, stack, bad_dev, gn_dev = pending
        with _prof.span("mx:device_wait", "loop", why="health"):
            bad = np.asarray(bad_dev)
            gn = np.asarray(gn_dev)
        with _prof.span("mx:observe.health", "loop"):
            if bad.any():
                k = int(np.argmax(bad))
                ctx = self._diag_ctx(stack, base_key, t_base, k) \
                    if stack is not None else None
                _health.on_nonfinite("fused_train", gnorm=float(gn[k]),
                                     ctx=ctx)
            else:
                for v in gn:
                    _health.observe_grad_norm(float(v))

    def _maybe_emit_stats(self, lnorms) -> None:
        """Opt-in per-layer stat streaming on the
        ``MXTPU_HEALTH_STATS_EVERY`` cadence (counted in CHUNKS — each
        run is K wall steps): grad norms come from the scanned program
        (last step of the chunk), param norms from one fused reduction
        over the published params."""
        import jax

        n = _health.stats_every()
        if n <= 0:
            return
        self._stats_count += 1
        if self._stats_count % n:
            return
        names = [self._arg_names[i] for i in self._diff_idx]
        pn = _health.layer_norms(self._p_vals)
        with _prof.span("mx:device_wait", "loop", why="stats"):
            pn, gn = jax.device_get((pn, [l[-1] for l in lnorms]))
        with _prof.span("mx:observe.health", "loop"):
            try:
                opt = self._optimizer
                lr = opt.lr if opt.lr_scheduler is None \
                    else opt.lr_scheduler(opt.num_update)
                scale = abs(float(lr) * float(opt.rescale_grad))
            except Exception:
                scale = 1.0
            _health.emit_stats(names, pn, gn, scale=scale,
                               site="fused_train")

    def run(self, batches: Sequence[Any]):
        """Stage K DataBatches and run them as one program."""
        return self.run_stacked(self.stack_batches(batches))

    def _publish(self):
        """Point the executor/updater NDArrays at the freshest device
        buffers (host pointer swap — no transfer)."""
        ex = self._exec
        for j, i in enumerate(self._diff_idx):
            ex.arg_arrays[i]._set_jax(self._p_vals[j])
        for arr, val in zip(ex.aux_arrays, self._aux_vals):
            arr._set_jax(val)
        self._scan_step.writeback_states(self._state_objs, self._s_tree)
        self._module._params_dirty = True

    def finalize(self):
        """Alias kept for symmetry with reference Trainer APIs; state is
        already published after every run().  Also drains the deferred
        health read so the LAST chunk's non-finite steps still get
        blamed."""
        pending, self._pending_health = self._pending_health, None
        if pending is not None:
            self._check_pending(pending)
        self._publish()
