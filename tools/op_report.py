#!/usr/bin/env python
"""Per-op device-time report for a fused conv-stack train bench —
the `mx.xprof` CLI.

Builds a Conv-BN-ReLU stack, trains it through `FusedTrainLoop` so the `mx.perf`
observatory measures the program wall, then prints the measured
top-K-sinks table: per-op wall, share, layer attribution
(``jvp(layer)`` / ``transpose(jvp(layer))`` HLO op_name metadata),
achieved GFLOP/s and GB/s against the ``MXTPU_PEAK_*`` roofline, and
the measured-vs-modeled discrepancy column.

Acquisition paths (see docs/observability.md §Op profiling):

  * default — timed eager replay of the NNVM graph, per-op walls
    CALIBRATED so their sum equals the sampled `mx.perf` program wall
    (relative shares are measured; absolute numbers inherit the
    fused-program wall).  Works on every backend.
  * ``--trace`` — additionally captures a real `mx.inspect.trace` and
    ingests the xplane protos in-tree (no TF dependency): device
    ground truth, HLO-op granularity.

Usage::

    python tools/op_report.py --trace --image 64 --batch 16   # the chip
    JAX_PLATFORMS=cpu python tools/op_report.py            # no chip
    python tools/op_report.py --json            # full OpProfile JSON
"""
import argparse
import json
import os
import sys
import time

# the report needs the program wall: force the perf observatory on and
# sample every chunk so even a short run measures it
os.environ.setdefault("MXTPU_PERF", "1")
os.environ.setdefault("MXTPU_PERF_SYNC_EVERY", "2")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def conv_stack(image_channels=3, num_filter=8, classes=10):
    """The fused conv-stack probe: Conv-BN-ReLU x2 + pool + FC head —
    conv/bn/wgrad/matmul op classes all present, every layer named so
    the layer join has real targets."""
    from mxtpu import sym

    d = sym.Variable("data")
    h = sym.Convolution(data=d, kernel=(3, 3), num_filter=num_filter,
                        pad=(1, 1), name="conv1")
    h = sym.BatchNorm(data=h, name="bn1")
    h = sym.Activation(data=h, act_type="relu", name="relu1")
    h = sym.Convolution(data=h, kernel=(3, 3), num_filter=num_filter,
                        pad=(1, 1), name="conv2")
    h = sym.BatchNorm(data=h, name="bn2")
    h = sym.Activation(data=h, act_type="relu", name="relu2")
    h = sym.Pooling(data=h, kernel=(2, 2), stride=(2, 2),
                    pool_type="max", name="pool1")
    h = sym.Flatten(h)
    h = sym.FullyConnected(data=h, num_hidden=32, name="fc1")
    h = sym.Activation(data=h, act_type="relu", name="relu3")
    out = sym.FullyConnected(data=h, num_hidden=classes, name="fc2")
    return sym.SoftmaxOutput(data=out,
                             label=sym.Variable("softmax_label"),
                             name="softmax")


def build_conv_loop(batch=8, image=16, spp=2, classes=10,
                    num_filter=8):
    """Bound + initialized FusedTrainLoop over the conv stack.
    Returns (loop, make_batches) — ``make_batches()`` yields one
    program's worth of DataBatches."""
    import mxtpu as mx
    from mxtpu.fused_train import FusedTrainLoop
    from mxtpu.io.io import DataBatch

    net = conv_stack(num_filter=num_filter, classes=classes)
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (batch, 3, image, image))],
             label_shapes=[("softmax_label", (batch,))])
    mx.random.seed(0)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    rng = np.random.RandomState(0)

    def make_batches():
        return [DataBatch(
            data=[mx.nd.array(rng.rand(batch, 3, image, image)
                              .astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, classes, batch)
                               .astype(np.float32))])
            for _ in range(spp)]

    return FusedTrainLoop(mod, steps_per_program=spp), make_batches


def run_bench(loop, make_batches, iters=6):
    """Train ``iters`` fused chunks so mx.perf samples the program
    wall; returns the last staged stack (profile input) and img/s."""
    import jax

    stacked = None
    t0 = time.perf_counter()
    n = 0
    for _ in range(iters):
        stacked = loop.stack_batches(make_batches())
        loop.run_stacked(stacked)
        n += loop._K
    jax.block_until_ready(loop._p_vals)
    return stacked, n / max(time.perf_counter() - t0, 1e-9)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image", type=int, default=16)
    ap.add_argument("--spp", type=int, default=2,
                    help="steps fused per program")
    ap.add_argument("--iters", type=int, default=6,
                    help="measured chunks before profiling")
    ap.add_argument("--top", type=int, default=5,
                    help="top-K sinks to print")
    ap.add_argument("--trace", action="store_true",
                    help="also capture + ingest a real xplane trace")
    ap.add_argument("--trace-dir", default="",
                    help="trace output dir (default: temp)")
    ap.add_argument("--json", action="store_true",
                    help="print the full OpProfile JSON instead of "
                         "the table")
    args = ap.parse_args(argv)

    import mxtpu as mx

    loop, make_batches = build_conv_loop(args.batch, args.image,
                                         args.spp)
    stacked, steps_per_s = run_bench(loop, make_batches, args.iters)

    # path (b): timed eager replay, calibrated to the perf wall
    prof = mx.xprof.profile(loop, data=[s[0] for s in stacked])
    if prof is None:
        print("op_report: MXTPU_XPROF=0 — profiling disabled",
              file=sys.stderr)
        return 1

    xplane = None
    if args.trace:
        import jax

        tdir = args.trace_dir or os.path.join(
            "/tmp", "mxtpu_op_report_%d" % os.getpid())
        with mx.inspect.trace(tdir):
            loop.run_stacked(loop.stack_batches(make_batches()))
            jax.block_until_ready(loop._p_vals)
        xplane = mx.xprof.ingest(tdir, program=loop._insp.name,
                                 kind="train", steps=args.spp)
    loop.finalize()

    if args.json:
        out = {"replay": prof, "steps_per_s": steps_per_s}
        if xplane is not None:
            out["xplane"] = xplane
        print(json.dumps(out, default=str))
        return 0
    print("conv-stack bench: batch=%d image=%d spp=%d  %.1f steps/s"
          % (args.batch, args.image, args.spp, steps_per_s))
    print()
    print(mx.xprof.format_report(prof, k=args.top))
    if xplane is not None:
        print()
        print(mx.xprof.format_report(xplane, k=args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
