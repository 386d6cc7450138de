#!/usr/bin/env python
"""Long-context TransformerLM training: flash (Pallas) vs jnp attention.

The per-kernel sweep (`bench_attention.py`) times the flash kernel
against the materializing reference as T grows; this benchmark
measures the same effect at the FULL TRAINING STEP level — `mxtpu.parallel.transformer.make_train_step`
(fwd+bwd+Adam) at fixed tokens-per-batch while the sequence length
grows, with the attention path toggled via MXTPU_NO_PALLAS in a child
process (the routing is trace-time-static, so each config gets a fresh
interpreter; the child is this same script with --child, so the timing
loop exists exactly once).

The parent never touches JAX (a chip belongs to one process at a
time) and the children run in turn.  One JSON line per config; a
"flash" row whose child reports the kernel did not actually engage is
marked as an error instead of printing a misleading 0% comparison.

Usage: python benchmark/python/bench_long_context.py [--seqs 1024,2048,4096]
"""
import argparse
import json
import math
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_child(T, tokens, iters, remat):
    """One measured config in THIS process; prints one JSON line."""
    sys.path.insert(0, REPO)
    import time

    import numpy as np
    import jax

    from mxtpu.parallel import transformer as tf
    from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP,
                                     AXIS_TP, AXIS_SP, AXIS_EP)
    from mxtpu.ops.pallas_attention import _use_pallas

    B = max(1, tokens // T)
    mesh = create_mesh({AXIS_DP: 1, AXIS_PP: 1, AXIS_TP: 1, AXIS_SP: 1,
                        AXIS_EP: 1}, devices=jax.devices()[:1])
    cfg = tf.TransformerConfig(vocab=8192, d_model=1024, n_heads=8,
                               n_layers=8, d_ff=4096, max_len=T,
                               dtype="bfloat16", remat=remat)
    params = tf.init_params(cfg, mesh, seed=0)
    opt = tf.init_opt_state(cfg, mesh)
    step, sh = tf.make_train_step(cfg, mesh, lr=1e-3, optimizer="adam")
    rng = np.random.RandomState(0)
    toks = jax.device_put(
        rng.randint(0, cfg.vocab, (B, T)).astype(np.int32), sh["data"])
    labs = jax.device_put(
        rng.randint(0, cfg.vocab, (B, T)).astype(np.int32), sh["data"])

    for _ in range(2):
        params, opt, loss = step(params, opt, toks, labs)
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt, loss = step(params, opt, toks, labs)
    jax.block_until_ready((params, loss))
    dt = time.perf_counter() - t0
    lv = float(loss)
    if not math.isfinite(lv):
        raise RuntimeError("loss diverged: %r" % lv)
    print(json.dumps({"T": T, "B": B,
                      "tokens_per_sec": round(B * T * iters / dt, 1),
                      "loss": round(lv, 4), "remat": remat,
                      "pallas": bool(_use_pallas())}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="1024,2048,4096")
    ap.add_argument("--tokens", type=int, default=8192,
                    help="tokens per batch (B = tokens // T)")
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--remat", default="none",
                    choices=("none", "dots", "dots_no_batch", "full"),
                    help="per-layer rematerialization; 'full' is what "
                         "makes T>=8k fit on one chip")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-config child timeout (one hung config "
                         "must not hang the whole sweep)")
    ap.add_argument("--child", type=int, default=None,
                    help=argparse.SUPPRESS)  # internal: run one T
    args = ap.parse_args()

    if args.child is not None:
        run_child(args.child, args.tokens, args.iters, args.remat)
        return

    for t in [int(s) for s in args.seqs.split(",") if s]:
        for no_pallas in ("0", "1"):
            path = "jnp" if no_pallas == "1" else "flash"
            env = dict(os.environ)
            env["MXTPU_NO_PALLAS"] = no_pallas
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--child", str(t), "--tokens", str(args.tokens),
                     "--iters", str(args.iters),
                     "--remat", args.remat],
                    capture_output=True, text=True, env=env,
                    timeout=args.timeout)
            except subprocess.TimeoutExpired:
                print(json.dumps({"T": t, "path": path,
                                  "error": "child timeout (%.0fs)"
                                           % args.timeout}))
                continue
            line = (r.stdout.strip().splitlines() or [""])[-1]
            if r.returncode == 0 and line.startswith("{"):
                rec = json.loads(line)
                rec["path"] = path
                if path == "flash" and not rec.get("pallas"):
                    # both rows would silently measure the jnp path
                    rec = {"T": t, "path": path,
                           "error": "pallas kernel did not engage on "
                                    "this backend; comparison invalid"}
                print(json.dumps(rec))
            else:
                print(json.dumps({"T": t, "path": path,
                                  "error": (r.stderr
                                            or "no output")[-300:]}))


if __name__ == "__main__":
    main()
