"""mxtpu — a TPU-native deep-learning framework with the capability surface
of Apache MXNet (reference: /root/reference, mdespriee/incubator-mxnet 1.5).

Architecture (see SURVEY.md for the full blueprint):
  * compute substrate: JAX/XLA (per-op jitted executables imperatively;
    whole-graph StableHLO lowering for Symbol/CachedOp), Pallas kernels
    for hot custom ops;
  * parallelism: jax.sharding Mesh + pjit/shard_map with XLA collectives
    over ICI/DCN (replacing NCCL/ps-lite);
  * user surface: mx.nd / mx.sym / mx.autograd / mx.gluon / mx.mod /
    mx.kv / mx.io / mx.optimizer / mx.metric — the reference's Python API.

Typical use, identical to the reference apart from the context:

    import mxtpu as mx
    x = mx.nd.ones((2, 3), ctx=mx.tpu())
    with mx.autograd.record():
        y = (x * 2).sum()
    y.backward()
"""
__version__ = "0.1.0"

from .base import MXNetError, MXTPUError
from .context import (Context, cpu, gpu, tpu, cpu_pinned, cpu_shared,
                      current_context, num_tpus, num_gpus)
from . import compile_cache
from .compile_cache import set_bucket_policy

# place the persistent XLA compile cache before anything can trigger a
# first compilation (JAX latches the cache decision at first compile)
compile_cache.configure_persistent_cache()
from . import base
from . import context
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from .random import seed  # noqa: F401  (mx.random.seed also via mx.seed? keep parity minimal)

from .ndarray import NDArray

# Higher layers — import order matters: everything above is the core
# substrate.
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from .symbol import AttrScope                 # mx.AttrScope parity
from . import name                            # mx.name.Prefix parity
from . import log                             # mx.log.get_logger
from . import util                            # mx.util.makedirs
from . import libinfo                         # capability report
from .executor import Executor
from .cached_op import CachedOp
from . import subgraph
from . import passes
from . import amp
from . import control_flow
# reference API surface: mx.nd.contrib.foreach / mx.sym.contrib.foreach
# (`python/mxnet/{ndarray,symbol}/contrib.py`) — one dispatching impl here
for _ns in (ndarray.contrib, symbol.contrib):
    _ns.foreach = control_flow.foreach
    _ns.while_loop = control_flow.while_loop
    _ns.cond = control_flow.cond
del _ns
from . import initializer
from .initializer import init
from . import optimizer
from . import lr_scheduler
from . import metric
from . import recordio
from . import io
from . import test_utils
from . import kvstore
from . import kvstore as kv
from . import kvstore_server
from . import model
from . import operator
from . import callback
from . import profiler
from . import telemetry
from . import tracing
from . import inspect
from . import health
from . import perf
from . import xprof
from . import hbm
from . import resilience
from . import checkpoint
from . import monitor
from . import visualization
from . import sharding
from . import sharding as shard
from . import module
from . import module as mod
from . import rnn
from . import image
from . import gluon
from . import serve
from . import obs
from . import fused_train
from .fused_train import FusedTrainLoop
from . import contrib


def tpu_count():
    return num_tpus()
