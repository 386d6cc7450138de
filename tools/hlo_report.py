#!/usr/bin/env python
"""Static attribution of a compiled train program's optimized HLO.

Built on the `mx.inspect` program registry: the fused train program of
ANY Module/HybridBlock is AOT-lowered, compiled, registered, and
reported — convolution count/dtypes/shapes, explicit transpose/copy
ops that survived fusion, fusion-kind histogram, XLA's own FLOP
estimate (cost_analysis) and the peak memory analysis.  For the
ResNet models the report also compares against the 12.3 GFLOP/img
analytic number.  Use it to decide whether an MFU gap is layout
traffic (transposes/copies), dtype promotion (f32 convs under an amp
scope), or genuine kernel inefficiency vs the 128x128 MXU.

Models: any `gluon.model_zoo.vision` name (resnet50_v1, resnet18_v1,
mobilenet1.0, ...), the built-in ``mlp`` (2-layer,
``--in-dim``/``--hidden``), or ``--symbol-json FILE`` for a graph
exported by `HybridBlock.export` / `Symbol.save` (data shape from
``--batch``/``--data-shape``).

Graph-rewrite passes (`mxtpu.passes`) run for the build under
``--passes`` (default: the active MXTPU_PASSES config).  With
``--symbol-json`` the exported graph is ALSO analyzed pre-pass and the
report carries a ``pass_deltas`` section — node count and
HLO-histogram (transposes/fusions/copies) before vs after — plus the
full per-pass report, so "what did the pipeline buy on THIS graph" is
one command.  ``--passes off`` restores the raw analysis.

Usage:  python tools/hlo_report.py --batch 128 --dtype bfloat16 --spp 2
        JAX_PLATFORMS=cpu python tools/hlo_report.py --model mlp --batch 8
        JAX_PLATFORMS=cpu python tools/hlo_report.py \
            --symbol-json net-symbol.json --data-shape 4,3,32,32
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

TRAIN_GFLOP_PER_IMG_224 = 12.3


def _build_net(args):
    """The model's head symbol + data shape for one batch."""
    import mxtpu as mx
    from mxtpu import sym
    from mxtpu.gluon import nn

    if args.model == "mlp":
        net = nn.HybridSequential(prefix="mlp_")
        with net.name_scope():
            net.add(nn.Dense(args.hidden, activation="relu"),
                    nn.Dense(args.classes))
        data_shape = (args.batch, args.in_dim)
    else:
        from mxtpu.gluon.model_zoo import vision

        net = vision.get_model(args.model, classes=args.classes)
        data_shape = (args.batch, 3, args.image, args.image)
    ctx = mx.current_context()
    net.initialize(ctx=ctx)
    x_trace = mx.nd.zeros(data_shape, ctx=ctx)
    out_sym, _, _ = net._trace_symbol(x_trace)
    softmax = sym.SoftmaxOutput(data=out_sym,
                                label=sym.Variable("softmax_label"),
                                name="softmax")
    return softmax, data_shape


def _load_symbol(args):
    import mxtpu as mx
    from mxtpu import sym

    graph = mx.sym.load(args.symbol_json)
    shape = tuple(int(s) for s in args.data_shape.split(",") if s)
    if not shape:
        shape = (args.batch, args.in_dim)
    head = graph if "softmax" in graph.name.lower() else \
        sym.SoftmaxOutput(data=graph, label=sym.Variable("softmax_label"),
                          name="softmax")
    return head, shape


def build(args):
    """Bind the model's fused train program and register its compiled
    form in the mx.inspect registry (no training step runs)."""
    import mxtpu as mx
    from mxtpu.fused_train import FusedTrainLoop
    from mxtpu.io.io import DataBatch
    from mxtpu import amp

    with amp.scope(args.dtype if args.dtype != "float32" else None):
        if args.symbol_json:
            softmax, data_shape = _load_symbol(args)
        else:
            softmax, data_shape = _build_net(args)
        data_name = softmax.list_arguments()[0]
        mod = mx.mod.Module(softmax, data_names=(data_name,),
                            label_names=("softmax_label",))
        mod.bind(data_shapes=[(data_name, data_shape)],
                 label_shapes=[("softmax_label", (data_shape[0],))])
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.01,
                                             "momentum": 0.9})
    loop = FusedTrainLoop(mod, steps_per_program=args.spp)
    rng = np.random.RandomState(0)
    batches = [DataBatch(
        data=[mx.nd.array(rng.rand(*data_shape).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, args.classes, data_shape[0])
                           .astype(np.float32))])
        for _ in range(args.spp)]
    stacked = loop.stack_batches(batches)
    # AOT: lower + compile WITHOUT running, then hand the executable to
    # the registry (the same record run_stacked would populate)
    t0 = time.perf_counter()
    compiled = loop.lower_stacked(stacked).compile()
    loop._insp.record_aot("train", stacked, compiled,
                          time.perf_counter() - t0)
    return loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50_v1",
                    help="gluon model_zoo name, or 'mlp'")
    ap.add_argument("--symbol-json", default="",
                    help="report an exported symbol instead of --model")
    ap.add_argument("--data-shape", default="",
                    help="comma shape for --symbol-json data input")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--in-dim", type=int, default=64,
                    help="mlp input features")
    ap.add_argument("--hidden", type=int, default=32,
                    help="mlp hidden width")
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--spp", type=int, default=2)
    ap.add_argument("--passes", default=None,
                    help="graph-rewrite pass spec for the build "
                         "(default: active MXTPU_PASSES config; 'off' "
                         "disables; with --symbol-json the report adds "
                         "pre/post pass_deltas)")
    ap.add_argument("--dump", default="",
                    help="also write full optimized HLO text here")
    ap.add_argument("--roofline", action="store_true",
                    help="print per-program flops/bytes/operational "
                         "intensity + compute-vs-memory-bound "
                         "classification from the mx.inspect registry "
                         "(mx.perf peak table; MXTPU_PEAK_* override)")
    args = ap.parse_args()
    if args.model == "mlp" and args.classes == 1000:
        args.classes = 10

    import mxtpu as mx
    import mxtpu.passes as P

    # this tool IS the inspector's CLI: a disabled registry
    # (MXTPU_INSPECT=0 in the caller's env) would leave it nothing to
    # report on
    mx.inspect.enable(True)
    spec = P.parse_spec(args.passes) if args.passes is not None \
        else P.current_spec()
    pass_deltas = None
    if args.symbol_json and spec:
        # exported graphs route through Symbol.optimize: analyze the
        # RAW graph first, then the pass-optimized build below — the
        # deltas are the report's headline for --symbol-json
        with P.scope("off"):
            raw_loop = build(args)
        raw_report = mx.inspect.report(raw_loop._insp, kind="train")
        head, _ = _load_symbol(args)
        _, opt_report = head.optimize(passes=list(spec),
                                      return_report=True)
        pass_deltas = {"spec": ",".join(spec),
                       "nodes": [opt_report["nodes_before"],
                                 opt_report["nodes_after"]],
                       "per_pass": opt_report["passes"]}
    with P.scope(list(spec) if spec else "off"):
        loop = build(args)
    report = mx.inspect.report(loop._insp, kind="train")
    if pass_deltas is not None:
        for k in ("n_transposes_surviving", "n_fusions",
                  "n_copies_surviving", "n_convolutions"):
            pass_deltas[k] = [raw_report.get(k), report.get(k)]
        report["pass_deltas"] = pass_deltas
    report["config"] = {"model": args.symbol_json or args.model,
                        "batch": args.batch, "image": args.image,
                        "dtype": args.dtype, "spp": args.spp,
                        "passes": ",".join(spec) or "off"}
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(mx.inspect.hlo(loop._insp.name, kind="train"))

    if args.roofline:
        # per-program roofline rows over EVERY registered program (the
        # build above registers the fused train program; a caller that
        # imported more models sees them all)
        from mxtpu import perf as mxperf

        rows = {}
        for p in mx.inspect.programs(analyze=True):
            rf = mxperf.roofline(p.get("flops", 0.0),
                                 p.get("bytes_accessed", 0.0))
            rows[p["name"]] = {
                "flops": p.get("flops"),
                "bytes_accessed": p.get("bytes_accessed"),
                "peak_bytes": p.get("peak_bytes"),
                "roofline": rf,
            }
            # measured-time column: when an `mx.xprof` profile exists
            # for this program (this process ran profile()/ingest(), or
            # the registry record carries a compact op_profile), the
            # static roofline row gains the MEASURED side — device us,
            # achieved GFLOP/s vs the modeled bound, and the top sink
            prof = mx.xprof.get(p["name"]) or p.get("op_profile")
            if prof:
                flops = float(p.get("flops") or 0.0)
                dev_us = prof.get("device_us")
                rows[p["name"]]["measured"] = {
                    "source": prof.get("source"),
                    "device_us": dev_us,
                    "idle_us": prof.get("idle_us"),
                    "achieved_gflops": round(
                        flops / dev_us / 1e3, 2)
                    if flops and dev_us else None,
                    "pct_peak_flops": round(
                        flops / (dev_us * 1e-6)
                        / mxperf.peak_flops() * 100.0, 2)
                    if flops and dev_us else None,
                    "top_sink": [
                        {"op": o.get("op"),
                         "op_class": o.get("op_class"),
                         "layer": o.get("layer"),
                         "wall_us": o.get("wall_us"),
                         "share": o.get("share")}
                        for o in (prof.get("top") or [])[:3]],
                }
        report["roofline"] = {
            "peak_flops_per_s": mxperf.peak_flops(),
            "peak_bytes_per_s": mxperf.peak_bytes(),
            "ridge_flops_per_byte": round(
                mxperf.peak_flops() / mxperf.peak_bytes(), 3),
            "programs": rows,
        }

    flops = (report.get("cost") or {}).get("flops")
    if args.model.startswith("resnet") and not args.symbol_json:
        images = args.batch * args.spp
        analytic_gflop = images * TRAIN_GFLOP_PER_IMG_224 \
            * (args.image / 224.0) ** 2
        report["analytic_gflop_per_program"] = round(analytic_gflop, 1)
        if flops:
            report["xla_vs_analytic_flops"] = round(
                float(flops) / (analytic_gflop * 1e9), 3)
    print(json.dumps(report, indent=1, default=str))


if __name__ == "__main__":
    main()
