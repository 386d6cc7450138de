#!/usr/bin/env python
"""Profile the production train loop on the current backend and
attribute the step time PER OP via `mx.xprof`.

For each configuration (dtype x conv layout x steps-per-program):

1. runs K fused steps through `FusedTrainLoop` so the `mx.perf`
   observatory measures the program wall (sampled call->ready);
2. builds the measured per-op attribution with BOTH `mx.xprof`
   acquisition paths: a timed eager replay (every backend), and —
   unless ``--no-trace`` — an xplane ingestion of a real
   ``mx.inspect.trace`` capture (device-ground-truth op events, layer-
   joined through the HLO op_name metadata);
3. prints the top-sink report plus one JSON line per config (the
   ``mxtpu-bench-v1``-style record now carries the ``op_profile``
   breakdown).

The old ad-hoc staging/execute stopwatch split is gone: staging shows
up as the `mx.perf` ``input_wait``/``host_dispatch`` phases and the
per-op report names what the device time is actually spent on.

Usage (on the chip):   python tools/profile_train.py --iters 6
CPU sanity run:        JAX_PLATFORMS=cpu python tools/profile_train.py \
                           --batch 8 --image 64 --iters 2 --no-trace
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# a profiling tool wants the measured program wall (MFU denominator +
# replay-calibration target): sample the device sync every other chunk
os.environ.setdefault("MXTPU_PERF", "1")
os.environ.setdefault("MXTPU_PERF_SYNC_EVERY", "2")

import numpy as np


def build_loop(batch, image, dtype, spp):
    import mxtpu as mx
    from mxtpu import sym
    from mxtpu.fused_train import FusedTrainLoop
    from mxtpu.gluon.model_zoo import vision

    ctx = mx.current_context()
    with mx.amp.scope(dtype if dtype != "float32" else None):
        net = vision.resnet50_v1(classes=1000)
        net.initialize(ctx=ctx)
        x_trace = mx.nd.zeros((batch, 3, image, image), ctx=ctx)
        out_sym, _, _ = net._trace_symbol(x_trace)
        softmax = sym.SoftmaxOutput(data=out_sym,
                                    label=sym.Variable("softmax_label"),
                                    name="softmax")
        mod = mx.mod.Module(softmax, data_names=("data0",),
                            label_names=("softmax_label",))
        mod.bind(data_shapes=[("data0", (batch, 3, image, image))],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.01,
                                             "momentum": 0.9})
    return FusedTrainLoop(mod, steps_per_program=spp), mx


def one_config(args, dtype, layout):
    os.environ["MXTPU_CONV_LAYOUT"] = layout
    import jax

    from mxtpu.ops.registry import clear_executable_cache

    clear_executable_cache()
    loop, mx = build_loop(args.batch, args.image, dtype, args.spp)
    from mxtpu.io.io import DataBatch

    rng = np.random.RandomState(0)

    def batches():
        return [DataBatch(
                    data=[mx.nd.array(
                        rng.rand(args.batch, 3, args.image, args.image)
                        .astype(np.float32))],
                    label=[mx.nd.array(
                        rng.randint(0, 1000, args.batch)
                        .astype(np.float32))])
                for _ in range(args.spp)]

    t0 = time.perf_counter()
    loop.run(batches())              # compile + first execute
    t_compile = time.perf_counter() - t0

    # measurement loop: mx.perf samples the program wall on its
    # MXTPU_PERF_SYNC_EVERY cadence — that wall is both the MFU
    # denominator and the replay-calibration target
    t0 = time.perf_counter()
    images = 0
    stacked = None
    for _ in range(args.iters):
        stacked = loop.stack_batches(batches())
        loop.run_stacked(stacked)
        images += args.batch * args.spp
    jax.block_until_ready(loop._p_vals)
    exec_s = time.perf_counter() - t0
    loop.finalize()

    # acquisition path (b): timed eager replay, calibrated to the
    # measured program wall — works on every backend
    replay = mx.xprof.profile(loop, data=[s[0] for s in stacked])
    if replay is not None:
        print(mx.xprof.format_report(replay, k=args.top))

    # acquisition path (a): a real device trace, ingested in-tree
    trace_dir = None
    xplane = None
    if args.trace_dir and dtype == args.trace_dtype and \
            layout == args.trace_layout:
        trace_dir = os.path.join(args.trace_dir,
                                 "%s_%s" % (dtype, layout or "nchw"))
        with mx.inspect.trace(trace_dir):
            loop.run_stacked(loop.stack_batches(batches()))
            jax.block_until_ready(loop._p_vals)
        xplane = mx.xprof.ingest(trace_dir, program=loop._insp.name,
                                 kind="train", steps=args.spp)
        print(mx.xprof.format_report(xplane, k=args.top))

    perf_row = mx.perf.report().get("programs", {}) \
        .get(loop._insp.name, {})
    prof = xplane or replay
    rec = {
        "dtype": dtype, "layout": layout or "NCHW", "spp": args.spp,
        "batch": args.batch, "image": args.image,
        "img_per_s": images / max(exec_s, 1e-9),
        "exec_ms_per_step": exec_s * 1e3 / (args.iters * args.spp),
        "compile_s": round(t_compile, 2),
        "mfu": perf_row.get("mfu"),
        "wall_us_avg": perf_row.get("wall_us_avg"),
        "phases": mx.perf.report().get("phases_us_per_step"),
        "op_profile": mx.xprof.bench_breakdown(prof) if prof else None,
        "trace": trace_dir,
    }
    print(json.dumps(rec))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--iters", type=int, default=6,
                    help="timed windows per config")
    ap.add_argument("--spp", type=int, default=8)
    ap.add_argument("--top", type=int, default=10,
                    help="top-K sinks to print per config")
    ap.add_argument("--configs", default="float32:,bfloat16:,"
                    "float32:NHWC,bfloat16:NHWC",
                    help="comma list of dtype:layout")
    ap.add_argument("--trace-dir", default="/tmp/mxtpu_trace")
    ap.add_argument("--no-trace", action="store_true")
    ap.add_argument("--trace-dtype", default="bfloat16",
                    help="config that gets the device trace")
    ap.add_argument("--trace-layout", default="")
    args = ap.parse_args()
    if args.no_trace:
        args.trace_dir = None

    for spec in args.configs.split(","):
        dtype, _, layout = spec.partition(":")
        try:
            one_config(args, dtype.strip(), layout.strip().upper())
        except Exception as e:  # keep later configs running
            print(json.dumps({"dtype": dtype, "layout": layout,
                              "error": str(e)[:500]}))


if __name__ == "__main__":
    main()
