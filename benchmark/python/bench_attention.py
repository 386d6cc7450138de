#!/usr/bin/env python
"""Flash-attention kernel microbenchmark (Pallas vs fused-jnp reference).

The reference framework composes attention from batch_dot + softmax,
materializing the (T, T) score matrix (`src/operator/tensor/dot.cc` +
`softmax.cc` composition); this framework ships a Pallas flash kernel
(`mxtpu/ops/pallas_attention.py`) with online-softmax forward and
blocked-recompute backward. This benchmark times both paths on the
current backend over a sequence-length sweep, forward and
forward+backward, and prints one JSON line per (path, seq, mode).

Shapes start small and grow, and each config reports its own failure:
the materializing reference running out of memory at a long sequence
prints an error row and the sweep goes on, while a failure of the
kernel path also ends the run non-zero.

Usage:  python benchmark/python/bench_attention.py            # on chip
        JAX_PLATFORMS=cpu python benchmark/python/bench_attention.py \
            --seqs 256,512 --iters 2   # CPU smoke
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def attn_flops(b, h, t, d, causal, bwd):
    """2*T^2*d MACs for QK^T plus the same for PV -> 4*T^2*d FLOPs/head
    forward; backward recomputes scores and adds dq/dk/dv matmuls
    (~2.5x forward); causal halves the useful work."""
    f = 4.0 * b * h * t * t * d
    if causal:
        f *= 0.5
    return f * (3.5 if bwd else 1.0)


def run(fn, args, iters, min_window_s=0.5, max_iters=1000):
    """Differential timing: close a K-iteration and a 2K-iteration
    window with `jax.block_until_ready`; (t_2K - t_K)/K cancels any
    constant per-window overhead.  Device programs execute in dispatch
    order, so the last output's readiness drains the whole window
    (chip_smoke.py phase 3 checks on the chip that readiness and a
    value fetch close a window alike).

    K auto-scales from a pilot window so the differential stays well
    above host jitter — with fast kernels a fixed K makes
    (t_2K - t_K) - (t_K - t_0) pure noise."""
    import jax

    jax.block_until_ready(fn(*args))     # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    pilot = max(time.perf_counter() - t0, 1e-6 * iters) / iters
    k = int(min(max(iters, min_window_s / max(pilot, 1e-7)), max_iters))
    t0 = time.perf_counter()
    for _ in range(k):
        out = fn(*args)
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    for _ in range(2 * k):
        out = fn(*args)
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    diff = (t2 - t1) - (t1 - t0)
    if diff <= 0:
        # window smaller than the jitter even at max_iters: there is
        # no honest number here — report it as such rather than
        # flooring to an absurd TFLOPS row
        return float("nan")
    return diff / k


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--seqs", default="512,1024,2048,4096,8192")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--causal", action="store_true", default=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mxtpu.ops import pallas_attention as pa

    b, h, d = args.batch, args.heads, args.head_dim
    dt = jnp.dtype(args.dtype)
    kernel_failed = False

    for t in [int(s) for s in args.seqs.split(",") if s]:
        rng = np.random.RandomState(t)
        q = jnp.asarray(rng.randn(b * h, t, d), dtype=dt)
        k = jnp.asarray(rng.randn(b * h, t, d), dtype=dt)
        v = jnp.asarray(rng.randn(b * h, t, d), dtype=dt)
        sm = 1.0 / float(np.sqrt(d))

        paths = {}
        if pa._use_pallas():
            # flash_attention's routing is automatic on this backend
            paths["pallas_flash"] = functools.partial(
                pa.flash_attention, causal=args.causal)
        ref = functools.partial(pa._reference_attention,
                                sm_scale=sm, causal=args.causal)
        paths["jnp_materialized"] = lambda q, k, v: ref(q, k, v)

        for name, fn in paths.items():
            try:
                fwd = jax.jit(fn)

                def loss(q, k, v, _fn=fn):
                    return _fn(q, k, v).astype(jnp.float32).sum()

                fwdbwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                t_f = run(fwd, (q, k, v), args.iters)
                t_b = run(fwdbwd, (q, k, v), args.iters)
                for mode, tt in (("fwd", t_f), ("fwd+bwd", t_b)):
                    if tt != tt:       # NaN: noise-dominated window
                        print(json.dumps({
                            "path": name, "seq": t, "mode": mode,
                            "error": "window below host jitter even at "
                                     "max_iters; no honest number"}))
                        continue
                    fl = attn_flops(1, b * h, t, d, args.causal,
                                    mode != "fwd")
                    print(json.dumps({
                        "path": name, "seq": t, "mode": mode,
                        "dtype": args.dtype, "causal": args.causal,
                        "ms": round(tt * 1e3, 3),
                        "tflops": round(fl / tt / 1e12, 2),
                    }))
            except Exception as e:
                print(json.dumps({"path": name, "seq": t,
                                  "error": str(e)[:300]}))
                kernel_failed |= name == "pallas_flash"
    if kernel_failed:
        sys.exit("bench_attention: the Pallas kernel path failed")


if __name__ == "__main__":
    main()
