"""Model helpers: kvstore wiring + checkpointing + legacy FeedForward.

Reference: `python/mxnet/model.py` — `_create_kvstore` (:125),
`_update_params_on_kvstore` (:145), `save_checkpoint/load_checkpoint`
(:383,413), `BatchEndParam`, and the legacy `FeedForward` API.
"""
from __future__ import annotations

from collections import namedtuple
from typing import Dict, List, Optional, Tuple

import numpy as np

from .base import MXNetError
from .context import Context, cpu, current_context
from .ndarray import ndarray as nd_mod
from .ndarray.ndarray import NDArray
from . import kvstore as kvs
from . import profiler as _prof
from . import resilience as _res
from . import symbol as sym_mod

__all__ = ["BatchEndParam", "save_checkpoint", "load_checkpoint",
           "load_latest", "FeedForward"]

BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])


def _create_kvstore(kvstore, num_device: int, arg_params):
    """Decide kvstore + update_on_kvstore (reference `model.py:58-99`)."""
    update_on_kvstore = True
    if kvstore is None or kvstore == "":
        kv = None
    elif isinstance(kvstore, kvs.KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore and kvstore != "tpu":
            kv = None
        else:
            kv = kvs.create(kvstore)
            if kvstore == "local":
                # reference heuristic (`model.py:58-99`): models with a
                # big (>16M-element) param update per-device, not on the
                # single merge device
                max_size = max(int(np.prod(p.shape)) for p in
                               arg_params.values()) if arg_params else 0
                if max_size > 1024 * 1024 * 16:
                    update_on_kvstore = False
    else:
        raise MXNetError("bad kvstore %r" % (kvstore,))
    if kv is None:
        update_on_kvstore = False
    return kv, update_on_kvstore


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names,
                        update_on_kvstore):
    for idx, param_on_devs in enumerate(param_arrays):
        name = param_names[idx]
        kvstore.init(name, arg_params[name])
        if update_on_kvstore:
            kvstore.pull(name, param_on_devs, priority=-idx)


def _update_params_on_kvstore(param_arrays, grad_arrays, kvstore,
                              param_names):
    """Push grads / pull weights (reference `model.py:145`); priority
    -index so earlier-needed keys schedule first."""
    with _prof.span("mx:collective", "loop", site="module"):
        for index, pair in enumerate(zip(param_arrays, grad_arrays)):
            arg_list, grad_list = pair
            if grad_list[0] is None:
                continue
            name = param_names[index]
            kvstore.push(name, grad_list, priority=-index)
            kvstore.pull(name, arg_list, priority=-index)


def _update_params(param_arrays, grad_arrays, updater, num_device,
                   kvstore=None, param_names=None):
    """Aggregate via kvstore (push+pull grads) then run the updater per
    device (reference `model.py:165-201`).

    With a :class:`mxtpu.sharding.ZeRO1Updater` (Module engaged a
    `ShardingPlan`) the per-device update loop is replaced by ONE
    cross-replica sharded update: the updater slices the merged grad,
    applies the optimizer on each replica's 1/N state chunk, and
    allgathers the params back into every replica — no per-device
    state redundancy (`docs/sharding.md`)."""
    from .sharding.zero1 import ZeRO1Updater

    if kvstore:
        # every gradient's push + pull, as one span of the step
        with _prof.span("mx:collective", "loop", site="module"):
            for i, grad_list in enumerate(grad_arrays):
                if grad_list[0] is None:
                    continue
                kvstore.push(param_names[i], grad_list, priority=-i)
                kvstore.pull(param_names[i], grad_list, priority=-i)
    if isinstance(updater, ZeRO1Updater):
        triples = []
        for i, (arg_list, grad_list) in enumerate(zip(param_arrays,
                                                      grad_arrays)):
            if grad_list[0] is None:
                continue
            triples.append((i, grad_list, arg_list))
        updater.update_replicas(triples, pre_reduced=kvstore is not None)
        return
    updates: List[List[Tuple]] = [[] for _ in range(num_device)]
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg_list, grad_list = pair
        if grad_list[0] is None:
            continue
        for k, (w, g) in enumerate(zip(arg_list, grad_list)):
            updates[k].append((index * num_device + k, g, w))
    for dev_updates in updates:
        if hasattr(updater, "update_multi"):
            updater.update_multi(dev_updates)  # one fused XLA call
        else:
            for idx, g, w in dev_updates:
                updater(idx, g, w)


def save_checkpoint(prefix: str, epoch: int, symbol, arg_params,
                    aux_params, remove_amp_cast=True, states=None,
                    extra_meta=None):
    """Write `prefix-symbol.json` + `prefix-%04d.params` (reference
    `model.py:383`) — ATOMICALLY: every member lands via
    temp+fsync+rename and a CRC32 manifest
    (`prefix-%04d.manifest.json`) is committed LAST, so a crash (even
    SIGKILL) mid-save can never truncate the previous checkpoint and a
    checkpoint without a valid manifest is recognizably partial
    (`load_latest` skips it).  ``states`` optionally embeds serialized
    optimizer state as `prefix-%04d.states`.  All IO runs under the
    ``checkpoint`` fault-injection site + retry policy
    (mxtpu/resilience.py).  ``extra_meta`` (a JSON-serializable dict)
    rides in the manifest payload — `mx.checkpoint` uses it to stamp
    fleet ids and run state next to the tensors they describe."""
    writer = _res.CheckpointWriter(prefix, epoch)

    def _member(path, write_fn):
        def body():
            with writer.file(path) as f:
                write_fn(f)
        _res.run_with_retry("checkpoint", body)

    if symbol is not None:
        _member("%s-symbol.json" % prefix,
                lambda f: f.write(symbol.tojson().encode()))
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    _member("%s-%04d.params" % (prefix, epoch),
            lambda f: nd_mod.save(f, save_dict))
    if states is not None:
        _member("%s-%04d.states" % (prefix, epoch),
                lambda f: f.write(states))
    writer.commit(extra=extra_meta if extra_meta else None)


def read_checkpoint_meta(prefix: str, epoch: int):
    """The manifest payload of ``prefix``/``epoch`` as a dict (CRCs,
    file list, any ``extra_meta`` saved alongside) — or None when no
    manifest exists.  Cheap: reads only the JSON manifest, never the
    tensor members."""
    return _res.read_manifest(prefix, epoch)


def load_checkpoint(prefix: str, epoch: int):
    """Load (symbol, arg_params, aux_params) (reference `model.py:413`)."""
    def body():
        _res.maybe_fault("checkpoint", prefix)
        symbol = sym_mod.load("%s-symbol.json" % prefix)
        save_dict = nd_mod.load("%s-%04d.params" % (prefix, epoch))
        return symbol, save_dict
    symbol, save_dict = _res.run_with_retry("checkpoint", body)
    arg_params, aux_params = {}, {}
    for k, v in save_dict.items():
        tp, _, name = k.partition(":")
        if tp == "arg":
            arg_params[name] = v
        elif tp == "aux":
            aux_params[name] = v
    return symbol, arg_params, aux_params


def load_latest(prefix: str):
    """Auto-resume: load the NEWEST complete checkpoint for ``prefix``,
    skipping corrupt/partial ones (CRC-validated manifests, newest
    first).  Falls back to probing bare ``prefix-NNNN.params`` files for
    pre-manifest checkpoints.  Returns ``(symbol, arg_params,
    aux_params, epoch)`` or None when nothing restorable exists."""
    epoch = _res.latest_valid_epoch(prefix)
    if epoch is not None:
        sym, args, auxs = load_checkpoint(prefix, epoch)
        return sym, args, auxs, epoch
    # legacy checkpoints (saved before the manifest format existed)
    import glob

    from . import profiler as _prof

    covered = set(_res.list_manifest_epochs(prefix))
    for path in sorted(
            glob.glob("%s-[0-9][0-9][0-9][0-9].params" % prefix),
            reverse=True):
        ep = int(path[-len("0000.params"):-len(".params")])
        if ep in covered:  # manifest said corrupt; don't resurrect it
            continue
        try:
            sym, args, auxs = load_checkpoint(prefix, ep)
            return sym, args, auxs, ep
        except Exception:
            _prof.inc_stat("checkpoint_skipped_corrupt")
    return None


class FeedForward(object):
    """Legacy estimator-style API (reference `model.py` FeedForward;
    deprecated there in favor of Module — provided as a thin veneer over
    `mxtpu.module.Module` for API parity)."""

    def __init__(self, symbol, ctx=None, num_epoch=None, epoch_size=None,
                 optimizer="sgd", initializer=None, numpy_batch_size=128,
                 arg_params=None, aux_params=None, begin_epoch=0,
                 **kwargs):
        from .initializer import Uniform

        self.symbol = symbol
        self.ctx = ctx if isinstance(ctx, (list, tuple)) else \
            [ctx or current_context()]
        self.num_epoch = num_epoch
        self.epoch_size = epoch_size
        self.optimizer = optimizer
        self.initializer = initializer or Uniform(0.01)
        self.numpy_batch_size = numpy_batch_size
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.begin_epoch = begin_epoch
        self.kwargs = kwargs
        self._module = None

    def _make_module(self, data_names, label_names):
        from .module import Module

        return Module(self.symbol, data_names=data_names,
                      label_names=label_names, context=self.ctx)

    def fit(self, X, y=None, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            logger=None, optimizer_params=None):
        mod = self._make_module([d[0] for d in X.provide_data],
                                [l[0] for l in X.provide_label])
        mod.fit(X, eval_data=eval_data, eval_metric=eval_metric,
                epoch_end_callback=epoch_end_callback,
                batch_end_callback=batch_end_callback, kvstore=kvstore,
                optimizer=self.optimizer,
                optimizer_params=optimizer_params or
                {"learning_rate": 0.01},
                initializer=self.initializer,
                arg_params=self.arg_params, aux_params=self.aux_params,
                begin_epoch=self.begin_epoch,
                num_epoch=self.num_epoch or 1)
        self._module = mod
        self.arg_params, self.aux_params = mod.get_params()
        return self

    def predict(self, X, num_batch=None):
        if self._module is None:
            raise MXNetError("fit() first")
        outs = self._module.predict(X, num_batch=num_batch)
        return outs.asnumpy() if isinstance(outs, NDArray) else \
            [o.asnumpy() for o in outs]

    def score(self, X, eval_metric="acc", num_batch=None):
        if self._module is None:
            raise MXNetError("fit() first")
        res = self._module.score(X, eval_metric, num_batch=num_batch)
        return res[0][1]

    def save(self, prefix, epoch=None):
        save_checkpoint(prefix, epoch if epoch is not None else
                        (self.num_epoch or 0), self.symbol,
                        self.arg_params or {}, self.aux_params or {})

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(symbol, ctx=ctx, arg_params=arg_params,
                           aux_params=aux_params, begin_epoch=epoch,
                           **kwargs)
