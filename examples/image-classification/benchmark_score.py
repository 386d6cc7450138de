"""Inference throughput across the model zoo.

Analog of the reference's
`example/image-classification/benchmark_score.py`: forward-only
images/sec for each zoo network at several batch sizes, via the
symbolic executor (one fused XLA program per (net, batch)).

Run:  python benchmark_score.py [--networks resnet18_v1,mobilenet1_0]
      [--batch-sizes 1,32] [--iters 20]
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))

import argparse
import logging
import time

import numpy as np

import mxtpu as mx
from mxtpu.gluon.model_zoo import vision


def score(name, batch, iters, ctx, dtype="float32", fused=0):
    """fused=K > 0 scores K batches per device program
    (HybridBlock.forward_fused), amortizing the per-dispatch host cost
    that weighs most on small-batch scoring."""
    amp_dtype = None if dtype == "float32" else dtype
    with mx.amp.scope(amp_dtype):
        net = getattr(vision, name)(classes=1000)
        net.initialize(ctx=ctx)
        x = mx.nd.array(np.random.uniform(size=(batch, 3, 224, 224))
                        .astype(np.float32), ctx=ctx)
        net(x)  # materialize deferred shapes
        net.hybridize()
        if fused:
            xs = mx.nd.array(np.random.uniform(
                size=(fused, batch, 3, 224, 224)).astype(np.float32),
                ctx=ctx)
            net.forward_fused(xs)[0].wait_to_read()  # compile
            tic = time.perf_counter()
            for _ in range(iters):
                out = net.forward_fused(xs)
            out[0].wait_to_read()
            dt = time.perf_counter() - tic
            return batch * fused * iters / dt
        net(x).wait_to_read()  # compile
        tic = time.perf_counter()
        for _ in range(iters):
            out = net(x)
        out.wait_to_read()
        dt = time.perf_counter() - tic
    return batch * iters / dt


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--networks",
                   default="resnet18_v1,resnet50_v1,mobilenet1_0")
    p.add_argument("--batch-sizes", default="1,32")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--dtype", default="float32",
                   help="float32 or bfloat16 (AMP compute policy — the "
                        "TPU analog of the reference's fp16 scoring "
                        "rows, docs/faq/perf.md:166-176)")
    p.add_argument("--fused", type=int, default=0,
                   help="score K batches per device program "
                        "(amortizes remote dispatch latency)")
    args = p.parse_args()
    logging.basicConfig(level=logging.INFO)

    ctx = mx.tpu() if mx.num_tpus() else mx.cpu()
    logging.info("device: %s", ctx)
    for name in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            ips = score(name.strip(), bs, args.iters, ctx,
                        dtype=args.dtype, fused=args.fused)
            logging.info("network %-16s batch %3d %s%s: %9.1f images/sec",
                         name, bs, args.dtype,
                         " fused=%d" % args.fused if args.fused else "",
                         ips)


if __name__ == "__main__":
    main()
