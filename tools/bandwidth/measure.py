#!/usr/bin/env python
"""Measure kvstore aggregation bandwidth.

The analog of the reference's `tools/bandwidth/measure.py` (README
reports ~11.1 GB/s/GPU for CommDevice on 2 GPUs): pushes ResNet-sized
gradient arrays through a kvstore and reports GB/s per device.  With
kvstore=tpu and a mesh, the reduce is one XLA allreduce over ICI.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kv-store", default="device")
    ap.add_argument("--num-devices", type=int, default=0,
                    help="0 = all available")
    ap.add_argument("--size-mb", type=float, default=100.0,
                    help="total bytes pushed per round")
    ap.add_argument("--num-keys", type=int, default=20)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cpu-mesh", type=int, default=0,
                    help="force a virtual N-device CPU mesh (testing)")
    args = ap.parse_args()

    if args.cpu_mesh:
        import os

        # jax is not imported yet, so the environment alone decides
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d"
            % args.cpu_mesh).strip()
    import jax

    import mxtpu as mx
    import mxtpu.parallel as par

    devices = jax.devices()
    n = args.num_devices or len(devices)
    ctxs = [mx.Context(devices[i].platform if devices[i].platform != "cpu"
                       else "cpu", i) for i in range(n)]

    elems_per_key = int(args.size_mb * 1e6 / 4 / args.num_keys)
    shape = (elems_per_key,)

    mesh_ctx = None
    if args.kv_store == "tpu" and n > 1:
        mesh_ctx = par.MeshContext(par.create_mesh({"dp": n},
                                                   devices=devices[:n]))
        mesh_ctx.__enter__()
    kv = mx.kv.create(args.kv_store)
    vals = {}
    for k in range(args.num_keys):
        kv.init(k, mx.nd.zeros(shape, ctx=ctxs[0]))
        vals[k] = [mx.nd.ones(shape, ctx=ctxs[i % len(ctxs)])
                   for i in range(n)]
    outs = {k: [mx.nd.empty(shape, ctx=ctxs[i % len(ctxs)])
                for i in range(n)] for k in range(args.num_keys)}

    # warmup
    for k in range(args.num_keys):
        kv.push(k, vals[k])
        kv.pull(k, out=outs[k])
    mx.nd.waitall()

    t0 = time.perf_counter()
    for _ in range(args.iters):
        for k in range(args.num_keys):
            kv.push(k, vals[k], priority=-k)
        for k in range(args.num_keys):
            kv.pull(k, out=outs[k], priority=-k)
    mx.nd.waitall()
    dt = time.perf_counter() - t0

    total_bytes = args.iters * args.num_keys * elems_per_key * 4
    # allreduce moves 2(n-1)/n of the data per device per round
    algo_bytes = total_bytes * 2 * (n - 1) / max(n, 1)
    print("kvstore=%s devices=%d keys=%d %.1f MB/round: "
          "%.3f s/round, %.2f GB/s algo bandwidth per device"
          % (args.kv_store, n, args.num_keys, args.size_mb,
             dt / args.iters, algo_bytes / dt / 1e9))
    if mesh_ctx:
        mesh_ctx.__exit__(None, None, None)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
