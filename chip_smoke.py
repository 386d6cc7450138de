#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the train path starts on the chip.

Drives the system's main path once, through the entry points a user
calls, at the full width of the two models the repo ships, in ONE
process (a chip belongs to one process at a time):

  phase 0  device: versions, platform, ``mx.tpu()`` / ``mx.num_tpus()`` /
           ``mx.current_context()`` honest, one eager autograd gradient;
  phase 1  ResNet-50 bf16 bs128 through ``Module`` + ``FusedTrainLoop``;
  phase 2  the same module through forward / backward / update;
  phase 3  the Pallas flash kernel against its reference at four shapes,
           then the TransformerLM fused train step (Mosaic custom call
           in the lowered program, loss falls, two ways to close a
           timing window compared);
  phase 4  (>= 4 chips) ``Module`` over four contexts with
           ``kvstore="tpu"``, then the LM on dp=2 x tp=2 and dp=2 x sp=2.

Any failed assertion or exception ends the run non-zero with the phase
named; nothing is caught and carried past.  With no TPU visible to JAX
it fails in phase 0 within seconds and prints no result.  The last line
of stdout is the result, one JSON object with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The line before it, ``summary: {...}``, carries the per-phase record.
Its times are SET-UP INFORMATION (how long bring-up took, compile
included); they are not a benchmark and nothing may quote them as one —
its closing ``"claim": null`` says so.

``--rehearse`` is for a host without a chip (and for the tier-1 test
that keeps this script alive): every size shrinks, CPU devices stand in
and the kernel runs in Pallas interpret mode.  It checks the control
flow and says nothing about the chip.

    python chip_smoke.py                 # on the chip
    python chip_smoke.py --rehearse      # CPU, tiny sizes
"""
import argparse
import gc
import json
import os
import sys
import time
from importlib import metadata

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

import mxtpu as mx
from mxtpu import autograd, compile_cache, perf, profiler, sym
from mxtpu.gluon import nn
from mxtpu.gluon.model_zoo import vision
from mxtpu.io.io import DataBatch
from mxtpu.ops import pallas_attention as pa
from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP, AXIS_TP,
                                 AXIS_SP, AXIS_EP)

# JAX's own duration event around each executable build — a backend
# compile or, with a warm persistent cache, the read that replaces it
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _Sizes(object):
    """Every size the script uses; ``--rehearse`` swaps in the tiny set."""

    def __init__(self, rehearse):
        self.rehearse = rehearse
        if not rehearse:
            self.classes = 1000
            self.batch, self.image, self.fused_k = 128, 224, 4
            # (bh, T, d): the bench shape; bh=6, which once failed to
            # lower; T=640 (blocks fall to 128) at d=64; a long
            # sequence; d=256 at T=4096 (latent attention's q.k and v
            # width: two lane tiles a head); `gpt2m_fused_k8`'s own
            # shape (d=64 at 512 x 512 blocks: the causal walk's
            # sub-tiled diagonal at the width its claim rests on)
            self.attn_shapes = [(64, 1024, 128), (6, 1024, 128),
                                (2, 640, 64), (8, 4096, 128),
                                (4, 4096, 256), (128, 1024, 64)]
            self.attn_ragged, self.attn_ragged_block = (2, 200, 64), 128
            # ((bh, T, d), heads): the entry on the activations' layout
            # (`flash_attention_bthd`, [B, T, heads, d]) at the two LM
            # cells' own shapes, [8, 1024, 16, 64] (two heads to a lane
            # block) and [2, 4096, 20, 256] (a head a lane block), read
            # in place: a layout slip reads here before a cell's
            # `correct` does, and the kernels are timed at them
            self.attn_bthd = [((128, 1024, 64), 16), ((40, 4096, 256), 20)]
            # (batch, T, heads, q.k width, v width): latent attention
            # whose q.k is wider than v, padded to the kernels' one width
            self.attn_unequal = (2, 2048, 4, 192, 128)
            # (batch, T, q heads, kv heads, d, window): grouped kv heads
            # read in place under a window and under none, at
            # `trinitym_ep16_fused_k4`'s own shape (four windows a
            # sequence), against attention in blocks of query rows
            self.attn_grouped = (2, 8192, 32, 4, 128, 2048)
            # (batch, T, heads, d, chunk, re-basing): the chunked KDA
            # core against the recurrence, at the published head, with
            # the bounded gate re-based every 16 steps and with the
            # unbounded softplus gate (None: scored by halves)
            self.kda = [(1, 2048, 4, 128, 64, 16),
                        (1, 2048, 4, 128, 64, None)]
            self.lm = dict(vocab=8192, d_model=1024, n_heads=8,
                           n_layers=8, d_ff=4096, max_len=1024)
            self.lm_batch, self.lm_k = 8, 2
            self.lm4_layers = 2
        else:
            self.classes = 10
            self.batch, self.image, self.fused_k = 8, 16, 2
            self.attn_shapes = [(6, 128, 32), (2, 160, 16), (2, 128, 64)]
            self.attn_ragged, self.attn_ragged_block = (2, 50, 16), 32
            self.attn_bthd = [((4, 128, 64), 2), ((2, 128, 256), 2)]
            self.attn_unequal = (1, 128, 2, 24, 16)
            self.attn_grouped = (1, 256, 4, 2, 128, 100)
            self.kda = [(1, 40, 2, 16, 16, 4), (1, 40, 2, 16, 16, None)]
            self.lm = dict(vocab=64, d_model=32, n_heads=2, n_layers=2,
                           d_ff=64, max_len=64)
            self.lm_batch, self.lm_k = 4, 2
            self.lm4_layers = 1


class _CompileMeter(object):
    """Sums JAX's compile events so each phase can report its compile
    seconds and assert that a repeated program compiled nothing."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.count, self.cache_hits


def _require(cond, msg):
    """A check that survives ``python -O`` (assert does not)."""
    if not cond:
        raise AssertionError(msg)


def _ctx(sizes, i=0):
    return mx.cpu(i) if sizes.rehearse else mx.tpu(i)


def _on_default_platform(arr):
    """Every shard of `arr` sits on the platform JAX defaults to (the
    TPU on a chip host; phase 0 has checked which that is)."""
    return all(d.platform == jax.default_backend() for d in arr.devices())


def _xent(probs, labels):
    """Mean cross-entropy of softmax outputs against integer labels."""
    p = np.asarray(probs, dtype=np.float32)
    idx = np.asarray(labels).astype(np.int64)
    return float(-np.log(np.maximum(p[np.arange(len(idx)), idx],
                                    1e-30)).mean())


# ---------------------------------------------------------------------------
# phase 0: device
# ---------------------------------------------------------------------------

def phase0_device(sizes):
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {"python": sys.version.split()[0],
                "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": metadata.version("libtpu"),
                "numpy": np.__version__}
    print("versions: %s" % json.dumps(versions), flush=True)
    print("device: %s" % json.dumps(device), flush=True)
    if sizes.rehearse:
        _require(dev.platform == "cpu", "--rehearse is for a host with "
                 "no chip; found platform %r" % dev.platform)
    elif dev.platform != "tpu":
        sys.exit("chip_smoke: phase 0 FAILED: no TPU visible to JAX "
                 "(platform %r, device_kind %r); --rehearse runs the "
                 "control flow on a CPU" % (dev.platform, dev.device_kind))

    want = 0 if sizes.rehearse else jax.device_count()
    _require(mx.num_tpus() == want,
             "mx.num_tpus()=%d, expected %d" % (mx.num_tpus(), want))
    ctx = _ctx(sizes)
    _require(mx.current_context() == ctx,
             "default context %s, expected %s" % (mx.current_context(), ctx))
    ones = mx.nd.ones((2, 3), ctx=ctx)
    _require(_on_default_platform(ones._data),
             "nd.ones landed on %s" % ones._data.devices())
    x = mx.nd.array(np.array([0.5, -1.0, 2.0], np.float32), ctx=ctx)
    x.attach_grad()
    with autograd.record():
        y = mx.nd.sum(mx.nd.exp(x) * x)
    y.backward()
    xn = x.asnumpy()
    np.testing.assert_allclose(x.grad.asnumpy(), np.exp(xn) * (1 + xn),
                               rtol=1e-5)
    _require(_on_default_platform(x.grad._data), "grad off device")
    peaks = perf.device_peaks()     # an unknown chip raises here
    return {"peak_table_row": peaks["device_kind"], "_device": device,
            "_versions": versions}


# ---------------------------------------------------------------------------
# phases 1 and 2: ResNet-50 through Module, fused then per step
# ---------------------------------------------------------------------------

def _net(sizes):
    """ResNet-50 from the model zoo; the rehearsal's stand-in is one
    conv-BN-relu block with the same kinds of layer."""
    if not sizes.rehearse:
        return vision.resnet50_v1(classes=sizes.classes)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
                nn.Activation("relu"), nn.GlobalAvgPool2D(),
                nn.Dense(sizes.classes))
    return net


def _build_module(sizes, contexts, kvstore):
    """The bench's model and binding: the net traced to a Symbol plus
    SoftmaxOutput under the bf16 AMP scope, bound through Module, SGD
    with momentum."""
    shape = (sizes.batch, 3, sizes.image, sizes.image)
    with mx.amp.scope("bfloat16"):
        net = _net(sizes)
        net.initialize(ctx=contexts[0])
        trace_shape = (sizes.batch // len(contexts),) + shape[1:]
        out_sym, _, _ = net._trace_symbol(
            mx.nd.zeros(trace_shape, ctx=contexts[0]))
        softmax = sym.SoftmaxOutput(data=out_sym,
                                    label=sym.Variable("softmax_label"),
                                    name="softmax")
        mod = mx.mod.Module(softmax, data_names=("data0",),
                            label_names=("softmax_label",),
                            context=contexts)
        mod.bind(data_shapes=[("data0", shape)],
                 label_shapes=[("softmax_label", (sizes.batch,))])
    mx.random.seed(0)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore=kvstore, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9})
    return mod


def _synthetic_batch(sizes, ctx, seed=0):
    rng = np.random.RandomState(seed)
    data = rng.rand(sizes.batch, 3, sizes.image,
                    sizes.image).astype(np.float32)
    label = rng.randint(0, sizes.classes, (sizes.batch,)) \
        .astype(np.float32)
    batch = DataBatch(data=[mx.nd.array(data, ctx=ctx)],
                      label=[mx.nd.array(label, ctx=ctx)])
    return batch, label


def _param_probe(mod):
    """(name, host copy) of the module's last parameter."""
    name = mod._exec_group.param_names[-1]
    return name, mod._exec_group.param_arrays[-1][0].asnumpy().copy()


def _check_params(mod, before):
    name, old = before
    _, new = _param_probe(mod)
    _require(np.isfinite(new).all(), "param %s not finite" % name)
    _require(np.abs(new - old).max() > 0, "param %s did not change" % name)
    for pname, replicas in zip(mod._exec_group.param_names,
                               mod._exec_group.param_arrays):
        for r in replicas:
            _require(_on_default_platform(r._data),
                     "param %s sits on %s" % (pname, r._data.devices()))


def phase1_fused(sizes, meter, mod, batch, label):
    K = sizes.fused_k
    before = _param_probe(mod)
    loop = mx.FusedTrainLoop(mod, steps_per_program=K,
                             collect_outputs=True)
    stack = loop.stack_batches([batch] * K)
    jax.block_until_ready(stack)
    outs = loop.run_stacked(stack)
    first = [_xent(outs[0].asnumpy()[k], label) for k in range(K)]
    _, n0, _ = meter.snapshot()
    t0 = time.perf_counter()
    outs = loop.run_stacked(stack)
    mx.nd.waitall()
    step_ms = (time.perf_counter() - t0) / K * 1e3
    second = [_xent(outs[0].asnumpy()[k], label) for k in range(K)]
    loop.finalize()
    _require(np.isfinite(first + second).all(),
             "loss not finite: %s %s" % (first, second))
    _require(second[-1] < first[0],
             "loss did not fall: %s then %s" % (first, second))
    _require(meter.snapshot()[1] == n0,
             "the second program compiled %d executable(s)"
             % (meter.snapshot()[1] - n0))
    stats = profiler.stats()
    _require(stats.get("fused_train_trace") == 1
             and stats.get("fused_train_hit", 0) >= 1,
             "fused_train retrace counters: trace=%s hit=%s"
             % (stats.get("fused_train_trace"),
                stats.get("fused_train_hit")))
    _check_params(mod, before)
    return {"loss_first": round(first[0], 4),
            "loss_last": round(second[-1], 4),
            "steady_step_ms": round(step_ms, 2)}


def phase2_per_step(sizes, meter, mod):
    # a batch phase 1 has not already fitted, so the loss has room
    batch, label = _synthetic_batch(sizes, _ctx(sizes), seed=1)
    before = _param_probe(mod)
    losses = []
    for step in range(3):
        if step == 2:
            mx.nd.waitall()
            _, n0, _ = meter.snapshot()
            traces0 = profiler.stats().get("executor_trace", 0)
            t0 = time.perf_counter()
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()
        losses.append(mod.get_outputs()[0])
    mx.nd.waitall()
    step_ms = (time.perf_counter() - t0) * 1e3
    losses = [_xent(o.asnumpy(), label) for o in losses]
    _require(np.isfinite(losses).all(), "loss not finite: %s" % losses)
    _require(losses[-1] < losses[0], "loss did not fall: %s" % losses)
    _require(meter.snapshot()[1] == n0,
             "the third step compiled %d executable(s)"
             % (meter.snapshot()[1] - n0))
    _require(profiler.stats().get("executor_trace", 0) == traces0,
             "the third step retraced the executor")
    _check_params(mod, before)
    return {"loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "steady_step_ms": round(step_ms, 2)}


# ---------------------------------------------------------------------------
# phase 3: the Pallas kernel, then the TransformerLM train step
# ---------------------------------------------------------------------------

def _attention_check(shape, block=512, heads=None):
    """Kernel forward and gradients against the materializing
    reference at one (bh, T, d); returns the worst normalized error.
    With `heads`, through the entry on the activations' layout: the
    same arrays as [bh / heads, T, heads, d]."""
    bh, t, d = shape
    rng = np.random.RandomState(t + d)
    q, k, v, cot = (jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
                    for _ in range(4))
    scale = 1.0 / float(np.sqrt(d))

    # a random cotangent: under a plain sum every softmax row's dq and
    # dk vanish and the check would compare noise
    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                * cot.astype(jnp.float32)).sum()

    def flash(q, k, v):
        if heads is None:
            return pa.flash_attention(q, k, v, causal=True, block_q=block,
                                      block_k=block)
        b = bh // heads
        out = pa.flash_attention_bthd(
            *(x.reshape(b, heads, t, d).transpose(0, 2, 1, 3)
              for x in (q, k, v)), causal=True, block_q=block,
            block_k=block)
        return out.reshape(b, t, heads, d).transpose(0, 2, 1, 3) \
            .reshape(bh, t, d)

    def ref(q, k, v):
        return pa._reference_attention(q, k, v, scale, True)

    got = (jax.jit(flash)(q, k, v),) + \
        jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = (jax.jit(ref)(q, k, v),) + \
        jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    # bf16 keeps 8 bits: 2^-8 per rounding, a few roundings deep
    return _worst_error(got, want, ("out", "dq", "dk", "dv"),
                        "flash at (bh, T, d)=%s" % (shape,), 3e-2)


# ms a call of the kernels alone at `attn_bthd`'s shapes before they read
# the activations' layout in place (PERF.md section 5: [128, 1024, 64],
# PR 33's chip runs; [40, 4096, 256], PR 31's)
_PARENT_KERNEL_MS = {(128, 1024, 64): (0.794, 0.629, 0.759),
                     (40, 4096, 256): (3.98, 4.04, 4.43)}


def _ms_a_call(fn, args, calls):
    """ms a call of jitted `fn(*args)` over `calls` back-to-back calls
    after a warm one; None with `calls` 0 (the rehearsal: a CPU time is
    no kernel time), where it runs once."""
    fn = jax.jit(fn)
    r = jax.block_until_ready(fn(*args))
    if not calls:
        return None
    t0 = time.perf_counter()
    for _ in range(calls):
        r = fn(*args)
    jax.block_until_ready(r)
    return round((time.perf_counter() - t0) / calls * 1e3, 3)


def _kernel_times(shape, heads, calls=20):
    """ms a call of each flash kernel alone, on [B, T, heads * d] read
    in place as the LM's blocks hand it over, beside the parent's on
    (bh, T, d): {"fwd": [ms, parent's], ...}.  With `calls` 0 (the
    rehearsal: a CPU time is no kernel time) each kernel runs once and
    no time is taken.  Set-up information, as every time here."""
    bh, t, d = shape
    b = bh // heads
    per_block = pa._lane_plan(heads, d)     # in place at both shapes
    rng = np.random.RandomState(t + d)
    q, k, v, g, out = (jnp.asarray(rng.normal(0, 1, (b, t, heads * d)),
                                   jnp.bfloat16) for _ in range(5))
    lse = jnp.zeros((bh, t), jnp.float32) + 6.0
    args = (float(d) ** -0.5, True, min(512, t), min(512, t))
    lanes = (heads // per_block, per_block)

    def bwd(q, k, v):
        return pa._flash_backward_pallas(q, k, v, g, out, lse, *args, *lanes)

    kernels = {
        "fwd": lambda q, k, v: pa._flash_forward_pallas(
            q, k, v, *args, True, *lanes),
        "dq": lambda q, k, v: bwd(q, k, v)[0],
        "dkv": lambda q, k, v: bwd(q, k, v)[1:]}
    return {name: [_ms_a_call(fn, (q, k, v), calls), was]
            for (name, fn), was in zip(
                kernels.items(), _PARENT_KERNEL_MS.get(shape, (None,) * 3))}


def _grouped_band_check(shape, window, calls=20):
    """The three kernels on q [B, T, H, d] and k, v [B, T, Hkv, d] read
    in place, under `window` (None: the causal prefix): output and all
    three gradients against attention computed a head and a block of
    query rows at a time (a plain `repeat` of the kv heads, an index
    compare for the mask); returns (worst normalized error, ms a call
    of [fwd, dq, dkv] alone, or None with `calls` 0)."""
    b, t, h, hkv, d, _ = shape
    rng = np.random.RandomState(t + hkv)
    q, cot = (jnp.asarray(rng.normal(0, 1, (b, t, h, d)), jnp.bfloat16)
              for _ in range(2))
    k, v = (jnp.asarray(rng.normal(0, 1, (b, t, hkv, d)), jnp.bfloat16)
            for _ in range(2))
    rows = min(1024, t)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                * cot.astype(jnp.float32)).sum()

    def flash(q, k, v):
        return pa.flash_attention_bthd(q, k, v, causal=True,
                                       window=window).reshape(b, t, h, d)

    def ref(q, k, v):
        def head(args):                 # [B, T, d] each
            qh, kh, vh = (a.astype(jnp.float32) for a in args)

            def block(r):
                qr = jax.lax.dynamic_slice_in_dim(qh, r * rows, rows, 1)
                s = jnp.einsum("bqd,bkd->bqk", qr, kh) / np.sqrt(d)
                qi = r * rows + jnp.arange(rows)[:, None]
                ki = jnp.arange(t)[None]
                seen = ki <= qi
                if window is not None:
                    seen &= qi - ki < window
                p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
                return jnp.einsum("bqk,bkd->bqd", p, vh)

            o = jax.lax.map(jax.checkpoint(block), jnp.arange(t // rows))
            return o.transpose(1, 0, 2, 3).reshape(b, t, d)

        k, v = (jnp.repeat(a, h // hkv, axis=2) for a in (k, v))
        o = jax.lax.map(jax.checkpoint(head), tuple(
            a.transpose(2, 0, 1, 3) for a in (q, k, v)))
        return o.transpose(1, 2, 0, 3).astype(q.dtype)

    got = (jax.jit(flash)(q, k, v),) + \
        jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = (jax.jit(ref)(q, k, v),) + \
        jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    err = _worst_error(got, want, ("out", "dq", "dk", "dv"),
                       "flash at [B, T, H, Hkv, d, window]=%s under %s"
                       % (shape, window), 3e-2)
    if not calls:
        return err, None
    q3, g3, o3 = (a.reshape(b, t, h * d) for a in (q, cot, got[0]))
    k3, v3 = (a.reshape(b, t, hkv * d) for a in (k, v))
    lse = jnp.zeros((b * h, t), jnp.float32) + 6.0
    args = (float(d) ** -0.5, True, min(512, t), min(512, t))
    lanes = (h, 1, h // hkv, window)

    def bwd(q, k, v):
        return pa._flash_backward_pallas(q, k, v, g3, o3, lse, *args, *lanes)

    return err, [_ms_a_call(fn, (q3, k3, v3), calls) for fn in (
        lambda q, k, v: pa._flash_forward_pallas(q, k, v, *args, True,
                                                 *lanes),
        lambda q, k, v: bwd(q, k, v)[0],
        lambda q, k, v: bwd(q, k, v)[1:])]


def _on_one_device_mesh(fn, *args):
    """fn(*args) inside shard_map on a one-device mesh: the LM's layer
    functions name the mesh's axes."""
    from jax.sharding import PartitionSpec as P

    return jax.jit(jax.shard_map(
        fn, mesh=_mesh(), in_specs=tuple(P() for _ in args),
        out_specs=P(), check_vma=False))(*args)


def _worst_error(got, want, names, what, tol):
    worst = 0.0
    for name, a, b in zip(names, got, want):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        _require(np.isfinite(a).all(), "%s: %s not finite" % (what, name))
        err = float(np.abs(a - b).max() / (np.abs(b).max() + 1e-6))
        _require(err < tol, "%s: %s off the reference by %.3g of its range"
                 % (what, name, err))
        worst = max(worst, err)
    return worst


def _unequal_width_check(shape):
    """`transformer._padded_attention` (q.k wider than v: zero columns
    up to the kernels' one width) against the materializing reference
    at the widths as they are; forward and gradients."""
    b, t, h, dqk, dv = shape
    rng = np.random.RandomState(dqk + dv)
    q, k = (jnp.asarray(rng.normal(0, 1, (b, t, h, dqk)), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.normal(0, 1, (b, t, h, dv)), jnp.bfloat16)
    cot = jnp.asarray(rng.normal(0, 1, (b, t, h * dv)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * cot).sum()

    def ref(q, k, v):
        o = pa._reference_attention(
            *(a.transpose(0, 2, 1, 3).reshape(b * h, t, -1)
              for a in (q, k, v)), dqk ** -0.5, True)
        return o.reshape(b, h, t, dv).transpose(0, 2, 1, 3).reshape(
            b, t, h * dv)

    def both(fn):
        return lambda q, k, v: (fn(q, k, v),) + jax.grad(
            loss(fn), argnums=(0, 1, 2))(q, k, v)

    got = _on_one_device_mesh(both(tf._padded_attention), q, k, v)
    want = jax.jit(both(ref))(q, k, v)
    return _worst_error(got, want, ("out", "dq", "dk", "dv"),
                        "attention at widths %s" % (shape,), 3e-2)


def _kda_check(shape):
    """The chunked KDA core (`transformer._kda_chunked`, bfloat16
    operands in its products, float32 decays, solve and state) against
    the gated delta rule run one token at a time in float32; forward
    and every input's gradient.  Re-based (`shape`'s last entry a
    span): decays over the floor gate's range (-5, 0), some channels at
    the floor, beta in (0, 1).  Scored by halves (None): the softplus
    gate's unbounded decays, -exp(A) softplus(z) with A in (0, log 16),
    some channels at tens of nats a step, and beta in (0, 2).  Returns
    (worst error, the largest re-based span's or chunk's nats)."""
    b, t, h, d, chunk, rebase = shape
    rng = np.random.RandomState(d + t)
    q, k, v = (rng.normal(0, 1, (b, t, h, d)) for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    z = rng.normal(0, 1, (b, t, h, d))
    if rebase:      # decays over the gate's whole range, some at the floor
        g = -5.0 / (1.0 + np.exp(-4.0 * z))
        g[..., :2] = -5.0
        beta = 1.0 / (1.0 + np.exp(-rng.normal(0, 1, (b, t, h))))
    else:
        g = -np.exp(rng.uniform(0, np.log(16), (1, 1, h, 1))) \
            * np.logaddexp(2.0 * z - 2.0, 0.0)
        g[..., :2] *= 10.0
        beta = 2.0 / (1.0 + np.exp(-rng.normal(0, 1, (b, t, h))))
    args = tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))
    cot = jnp.asarray(rng.normal(0, 1, (b, t, h, d)), jnp.float32)
    hi = jax.lax.Precision.HIGHEST

    def recurrence(q, k, v, g, beta):
        def token(S, xs):
            qt, kt, vt, gt, bt = xs
            S = jnp.exp(gt)[..., None] * S
            u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt,
                                                 precision=hi))
            S = S + kt[..., :, None] * u[..., None, :]
            return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=hi)

        o = jax.lax.scan(token, jnp.zeros((b, h, d, d), jnp.float32),
                         tuple(jnp.moveaxis(a, 1, 0)
                               for a in (q, k, v, g, beta)))[1]
        return jnp.moveaxis(o, 0, 1)

    def chunked(*a):
        return tf._kda_chunked(*a, chunk, rebase, jnp.bfloat16)

    def both(fn):
        def run(*a):
            (_, aux), grads = jax.value_and_grad(
                lambda *a: (lambda o: ((o[0] * cot).sum(), o))(fn(*a)),
                argnums=(0, 1, 2, 3, 4), has_aux=True)(*a)
            return aux, grads
        return run

    (o, span), grads = _on_one_device_mesh(both(chunked), *args)
    (want_o, _), want = jax.jit(both(
        lambda *a: (recurrence(*a), 0.0)))(*args)
    # bf16 operands: 2^-8 a rounding, through a 64-row solve
    err = _worst_error((o,) + grads, (want_o,) + want,
                       ("out", "dq", "dk", "dv", "dg", "dbeta"),
                       "chunked KDA at %s" % (shape,), 5e-2)
    return err, float(span)


def _mesh(**axes):
    """A mesh over the first devices with the given axis sizes, every
    other axis 1."""
    shape = {AXIS_DP: 1, AXIS_PP: 1, AXIS_TP: 1, AXIS_SP: 1, AXIS_EP: 1}
    shape.update(axes)
    n = int(np.prod(list(shape.values())))
    return create_mesh(shape, devices=jax.devices()[:n])


def _value_fetch(params, loss):
    """Close a timing window by fetching values the program's last ops
    produce (the pre-round benches' workaround for an early
    block_until_ready; phase 3 compares the two)."""
    lv = float(loss)
    float(jnp.ravel(jax.tree_util.tree_leaves(params)[0])[0])
    return lv


def phase3_lm(sizes, meter):
    _require(pa._use_pallas(), "the Pallas kernel is not routed here")
    _require(pa._interpret() == sizes.rehearse,
             "interpret mode is %s" % pa._interpret())
    info = {}
    stats0 = dict(profiler.stats())
    errs = [_attention_check(s) for s in sizes.attn_shapes] + \
        [_attention_check(s, heads=h) for s, h in sizes.attn_bthd]
    info["attn_max_err"] = round(max(errs), 5)
    info["flash_ms_per_call_now_and_parent"] = {
        "x".join(map(str, s)): _kernel_times(s, h, 0 if sizes.rehearse
                                             else 20)
        for s, h in sizes.attn_bthd}
    print("flash kernels alone, ms a call [now, parent]: %s"
          % info["flash_ms_per_call_now_and_parent"], flush=True)
    info["attn_unequal_widths_err"] = round(
        _unequal_width_check(sizes.attn_unequal), 5)
    err, span = _kda_check(sizes.kda[0])
    info["kda_chunked_err"], info["kda_span_nats"] = round(err, 5), \
        round(span, 2)
    err, span = _kda_check(sizes.kda[1])
    info["kda_softplus_err"], info["kda_softplus_chunk_nats"] = \
        round(err, 5), round(span, 2)
    expanded = stats0.get("flash_kv_expanded", 0)
    for window in (sizes.attn_grouped[-1], None):
        err, ms = _grouped_band_check(sizes.attn_grouped, window,
                                      0 if sizes.rehearse else 20)
        name = "window_%d" % window if window else "causal"
        info["attn_grouped_%s_err" % name] = round(err, 5)
        info["attn_grouped_%s_ms_fwd_dq_dkv" % name] = ms
        print("grouped kv heads %s, %s: worst error %.2e, ms a call "
              "[fwd, dq, dkv] %s" % (sizes.attn_grouped[:5], name, err, ms),
              flush=True)
    _require(profiler.get_stat("flash_kv_expanded") == expanded
             and profiler.get_stat("flash_kv_group")
             == sizes.attn_grouped[2] // sizes.attn_grouped[3],
             "the grouped kv heads were not read in place: %s"
             % profiler.stats())
    stats1 = dict(profiler.stats())
    _require(stats1.get("flash_attention_pallas", 0)
             > stats0.get("flash_attention_pallas", 0)
             and stats1.get("flash_attention_reference", 0)
             == stats0.get("flash_attention_reference", 0),
             "kernel checks took the reference path: %s" % stats1)
    # the ragged rule: a K length the blocks do not divide takes the
    # reference, correctly, and the counter says so
    _attention_check(sizes.attn_ragged, block=sizes.attn_ragged_block)
    _require(profiler.stats().get("flash_attention_reference", 0)
             > stats1.get("flash_attention_reference", 0),
             "the ragged shape did not count a reference path")

    mesh = _mesh()
    cfg = tf.TransformerConfig(dtype="bfloat16", remat="dots", **sizes.lm)
    K, B, T = sizes.lm_k, sizes.lm_batch, cfg.max_len
    params = tf.init_params(cfg, mesh, seed=0)
    opt = tf.init_opt_state(cfg, mesh)
    step, sh = tf.make_fused_train_steps(cfg, mesh, K, lr=1e-3,
                                         optimizer="adam")
    rng = np.random.RandomState(0)
    one = rng.randint(0, cfg.vocab, (1, B, T)).astype(np.int32)
    lab = rng.randint(0, cfg.vocab, (1, B, T)).astype(np.int32)
    toks = jax.device_put(np.repeat(one, K, 0), sh["data"])
    labs = jax.device_put(np.repeat(lab, K, 0), sh["data"])

    ref0 = profiler.stats().get("flash_attention_reference", 0)
    lowered = step.lower(params, opt, toks, labs)
    _require(profiler.stats().get("flash_attention_reference", 0) == ref0,
             "the train step traced a reference attention path")
    if not sizes.rehearse:
        # the proof that the kernel, not the reference, is in the program
        _require("tpu_custom_call" in lowered.as_text(),
                 "no Mosaic custom call in the lowered train step")
    compiled = lowered.compile()

    params, opt, l1 = compiled(params, opt, toks, labs)
    _value_fetch(params, l1[-1])        # compiles the fetch's own ops
    l1 = np.asarray(l1)
    _, n0, _ = meter.snapshot()
    t0 = time.perf_counter()
    params, opt, l2 = compiled(params, opt, toks, labs)
    jax.block_until_ready((params, opt, l2))
    t_ready = time.perf_counter() - t0
    t0 = time.perf_counter()
    params, opt, l3 = compiled(params, opt, toks, labs)
    _value_fetch(params, l3[-1])
    t_fetch = time.perf_counter() - t0
    l3 = np.asarray(l3)
    _require(meter.snapshot()[1] == n0, "the steady programs compiled")
    _require(np.isfinite(np.concatenate([l1, l3])).all(),
             "loss not finite: %s %s" % (l1, l3))
    _require(l3[-1] < l1[0], "loss did not fall: %s then %s" % (l1, l3))
    leaf = jax.tree_util.tree_leaves(params)[0]
    _require(_on_default_platform(leaf), "params on %s" % leaf.devices())
    if not sizes.rehearse:
        # the window closed by block_until_ready must span the program:
        # if readiness fired early, every timing in the tree is void
        _require(abs(t_ready - t_fetch) < 0.10 * max(t_ready, t_fetch),
                 "block_until_ready window %.1f ms vs value fetch window "
                 "%.1f ms" % (t_ready * 1e3, t_fetch * 1e3))
    info.update({
        "loss_first": round(float(l1[0]), 4),
        "loss_last": round(float(l3[-1]), 4),
        "steady_step_ms": round(t_ready / K * 1e3, 2),
        "program_ms_block_until_ready": round(t_ready * 1e3, 2),
        "program_ms_value_fetch": round(t_fetch * 1e3, 2)})
    return info


# ---------------------------------------------------------------------------
# phase 4: four chips
# ---------------------------------------------------------------------------

def phase4a_module_kvstore(sizes):
    """The BASELINE path of examples/image-classification/common/fit.py:
    a Module over four contexts, updated through kvstore="tpu"."""
    contexts = [_ctx(sizes, i) for i in range(4)]
    kv = mx.kv.create("tpu")
    mod = _build_module(sizes, contexts, kv)
    batch, label = _synthetic_batch(sizes, contexts[0])
    before = _param_probe(mod)
    losses = []
    for _ in range(3):
        mod.forward_backward(batch)
        mod.update()
        outs = mod.get_outputs(merge_multi_context=True)[0]
        losses.append(_xent(outs.asnumpy(), label))
    mx.nd.waitall()
    _require(kv.last_reduce_path == "psum",
             "kvstore=tpu reduced by %r" % kv.last_reduce_path)
    _require(np.isfinite(losses).all() and losses[-1] < losses[0],
             "loss: %s" % losses)
    _check_params(mod, before)
    for name, replicas in zip(mod._exec_group.param_names,
                              mod._exec_group.param_arrays):
        homes = [next(iter(r._data.devices())) for r in replicas]
        _require(len(set(homes)) == 4,
                 "replicas of %s share devices: %s" % (name, homes))
        ref = replicas[0].asnumpy()
        for r in replicas[1:]:
            _require(np.array_equal(ref, r.asnumpy()),
                     "replicas of %s differ after the update" % name)
    if not sizes.rehearse:      # the CPU client reports no memory stats
        for d in jax.devices()[:4]:
            _require(d.memory_stats()["bytes_in_use"] > 0,
                     "nothing resident on %s" % d)
    return {"reduce_path": kv.last_reduce_path,
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4)}


def _check_inner_axis_neighbours(mesh):
    """`create_mesh` lays devices out in `jax.devices()` order and
    counts on that putting the innermost busy axis (tp / sp, the
    chattiest) on directly linked chips: check it against the chips'
    own coordinates.  CPU devices have none."""
    devs = mesh.devices
    inner = max(i for i, n in enumerate(devs.shape) if n > 1)
    lines = np.moveaxis(devs, inner, -1).reshape(-1, devs.shape[inner])
    for line in lines:
        for a, b in zip(line[:-1], line[1:]):
            hops = sum(abs(x - y) for x, y in zip(a.coords, b.coords))
            _require(hops == 1, "%s and %s are neighbours on mesh axis %r "
                     "but %d hops apart" % (a, b, mesh.axis_names[inner],
                                            hops))


def phase4b_lm_mesh(sizes):
    """First-step loss of the LM on dp=2 x tp=2 and dp=2 x sp=2 (ring
    attention's ppermute over real links) against one chip."""
    lm = dict(sizes.lm, n_layers=sizes.lm4_layers)
    cfg = tf.TransformerConfig(dtype="bfloat16", **lm)
    B, T = sizes.lm_batch, cfg.max_len
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab, (B, T)).astype(np.int32)
    labs = rng.randint(0, cfg.vocab, (B, T)).astype(np.int32)

    def first_loss(**axes):
        mesh = _mesh(**axes)
        n = mesh.devices.size
        if n > 1 and not sizes.rehearse:
            _check_inner_axis_neighbours(mesh)
        params = tf.init_params(cfg, mesh, seed=0)
        spans = max(len(p.devices()) for p in params.values())
        _require(spans == n, "param shardings span %d of %d devices"
                 % (spans, n))
        step, sh = tf.make_train_step(cfg, mesh, lr=1e-2)
        params, loss = step(params, jax.device_put(toks, sh["data"]),
                            jax.device_put(labs, sh["data"]))
        jax.block_until_ready(params)
        return float(loss)

    out = {"loss_1chip": first_loss()}
    for name, axes in (("loss_dp2_tp2", {AXIS_DP: 2, AXIS_TP: 2}),
                       ("loss_dp2_sp2", {AXIS_DP: 2, AXIS_SP: 2})):
        out[name] = first_loss(**axes)
        # bf16 matmuls split over tp / sp sum in another order
        _require(np.isclose(out[name], out["loss_1chip"], rtol=2e-3),
                 "%s=%.5f vs one chip %.5f"
                 % (name, out[name], out["loss_1chip"]))
    return {k: round(v, 5) for k, v in out.items()}


# ---------------------------------------------------------------------------

def _run_phase(name, phases, meter, fn, *args):
    """Run one phase, print its line, record wall and compile seconds.
    A failure is named and re-raised: the run ends non-zero.  Returns
    the phase's dict; its "_"-prefixed keys are hand-offs to later
    phases and stay out of the record."""
    s0, n0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    try:
        info = fn(*args)
    except BaseException as e:
        print("phase %s: FAILED after %.1fs: %s: %s"
              % (name, time.perf_counter() - t0, type(e).__name__, e),
              flush=True)
        raise

    s1, n1, h1 = meter.snapshot()
    row = {"wall_s": round(time.perf_counter() - t0, 2),
           "compile_s": round(s1 - s0, 2), "executables": n1 - n0,
           "cache_hits": h1 - h0}
    stats = jax.devices()[0].memory_stats()     # None on the CPU client
    if stats:
        row["hbm_peak_bytes_so_far"] = stats["peak_bytes_in_use"]
    row.update((k, v) for k, v in info.items() if not k.startswith("_"))
    phases[name] = row
    print("phase %s: ok %s" % (name, json.dumps(row)), flush=True)
    return info


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on CPU devices with the kernel in "
                         "interpret mode: checks the control flow, says "
                         "nothing about the chip")
    args = ap.parse_args(argv)
    sizes = _Sizes(args.rehearse)
    if sizes.rehearse:
        print("REHEARSAL (cpu) — not a chip result", flush=True)
        os.environ["MXTPU_PALLAS_INTERPRET"] = "1"
    t_start = time.perf_counter()
    phases = {}
    meter = _CompileMeter()

    p0 = _run_phase("0_device", phases, meter, phase0_device, sizes)
    device = p0["_device"]

    print("compile cache: %s (JAX_COMPILATION_CACHE_DIR %s)"
          % (compile_cache.persistent_cache_dir(),
             "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR")
             else "unset"), flush=True)

    def bind():
        ctx = _ctx(sizes)
        batch, label = _synthetic_batch(sizes, ctx)
        return {"_mod": _build_module(sizes, [ctx], None),
                "_batch": batch, "_label": label}

    b = _run_phase("1a_resnet_bind", phases, meter, bind)
    _run_phase("1_resnet_fused", phases, meter, phase1_fused, sizes, meter,
               b["_mod"], b["_batch"], b["_label"])
    _run_phase("2_resnet_per_step", phases, meter, phase2_per_step, sizes,
               meter, b["_mod"])
    del b
    gc.collect()
    _run_phase("3_lm_pallas", phases, meter, phase3_lm, sizes, meter)
    gc.collect()
    if jax.device_count() >= 4:
        _run_phase("4a_module_kvstore_tpu", phases, meter,
                   phase4a_module_kvstore, sizes)
        gc.collect()
        _run_phase("4b_lm_mesh", phases, meter, phase4b_lm_mesh, sizes)
    else:
        print("phase 4: skipped, %d device(s) (needs 4)"
              % jax.device_count(), flush=True)

    summary = {
        "device": device,
        "rehearsal": sizes.rehearse,
        "versions": p0["_versions"],
        "compile_cache_dir": compile_cache.persistent_cache_dir(),
        "total_wall_s": round(time.perf_counter() - t_start, 1),
        "total_compile_s": round(meter.seconds, 1),
        "phases": phases,
        "note": "set-up information, not a benchmark",
        "claim": None,
    }
    print("summary: %s" % json.dumps(summary), flush=True)
    # the result line: these keys and no others, the device as JAX reports it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
