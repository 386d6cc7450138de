#!/usr/bin/env python3
"""Record the small TPU trace the reducer's tests read (run on the chip):

    python3 benchmark/onchip/fixtures/record.py chiprun_out/fixture

A two-layer language model at toy width but the real attention shape
([bh, T, d] = [4, 1024, 128], so the three Pallas flash kernels are in
it), K=2 fused steps, three programs under the harness's own spans with a
host pause between two of them, Python tracing off.  Writes the
``*.xplane.pb`` and what trace_reduce.reduce() reads from it; copy both
to ``fixtures/lm_tiny_v5e.xplane.pb`` / ``.expect.json``.
"""
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(ONCHIP))
sys.path[:0] = [ROOT, ONCHIP]


def main(out):
    import jax
    import numpy as np

    import harness
    import trace_reduce
    from mxtpu.parallel import transformer as tf
    from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP, AXIS_TP,
                                     AXIS_SP, AXIS_EP)

    if jax.devices()[0].platform != "tpu":
        sys.exit("record.py: needs a TPU")
    mesh = create_mesh({AXIS_DP: 1, AXIS_PP: 1, AXIS_TP: 1, AXIS_SP: 1,
                        AXIS_EP: 1}, devices=jax.devices()[:1])
    cfg = tf.TransformerConfig(dtype="bfloat16", remat="dots", vocab=512,
                               d_model=256, n_heads=2, n_layers=2, d_ff=512,
                               max_len=1024)
    k, b, t = 2, 2, 1024
    params = tf.init_params(cfg, mesh, seed=0)
    opt = tf.init_opt_state(cfg, mesh)
    step, sh = tf.make_fused_train_steps(cfg, mesh, k, lr=1e-3,
                                         optimizer="adam")
    rng = np.random.RandomState(0)
    host = rng.randint(0, cfg.vocab, (k, b, t)).astype(np.int32)
    toks = jax.device_put(host, sh["data"])
    params, opt, loss = step(params, opt, toks, toks)
    jax.block_until_ready(loss)

    shutil.rmtree(out, ignore_errors=True)
    spans = harness.Spans(True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with spans.span("bench:window"):
        for i in range(3):
            with spans.span("bench:wait"):
                with spans.span("bench:stage"):
                    toks = jax.device_put(host, sh["data"])
                    jax.block_until_ready(toks)
            with spans.span("bench:call"):
                params, opt, loss = step(params, opt, toks, toks)
            if i == 1:
                time.sleep(0.02)        # an unmarked host pause
        with spans.span("bench:close"):
            jax.block_until_ready((params, loss))
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out)
    pd = trace_reduce.load(path)
    reduced = trace_reduce.reduce(pd)
    module_s = 0.0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    module_s = sum(e.duration_ns for e in line.events) * 1e-9
    reduced["module_s"] = module_s
    shutil.copy(path, os.path.join(out, "lm_tiny_v5e.xplane.pb"))
    with open(os.path.join(out, "lm_tiny_v5e.expect.json"), "w") as f:
        json.dump(reduced, f, indent=1)
    print(json.dumps(reduced)[:3000])
    print("bytes", os.path.getsize(path))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/fixture")
