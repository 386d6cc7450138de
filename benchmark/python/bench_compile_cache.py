"""Compile-lifecycle benchmark: cold vs warm bind, ragged-batch serving.

Measures the three levers of `mxtpu/compile_cache.py` on a gluon
model-zoo net:

  * **cold vs warm start** — a subprocess binds + warms up resnet18_v1
    through Module/Executor with `JAX_COMPILATION_CACHE_DIR` pointed at a
    fresh directory (cold: full XLA compile) and then again with the
    now-populated cache (warm: disk deserialization).  The headline
    metric is the warm-start speedup of the bind+warmup phase.

  * **ragged-batch inference** — batch sizes cycling over 1..MAX served
    through a hybridized net with shape bucketing OFF (one compiled
    program per distinct size) vs ON (<= log2 bucket programs), reporting
    wall time and program counts for each.

Emits ONE structured row via `bench_common.emit_result` (the shared
runner schema every seed and the `tools/check_perf.py` ratchet read);
metric "compile_cache_warm_bind_speedup", "baseline" is the cold
start, so vs_baseline == value.

Env knobs: MXTPU_BENCH_CC_NET (default resnet18_v1),
MXTPU_BENCH_CC_BATCH (default 4), MXTPU_BENCH_CC_HW (input H=W,
default 64 — resnet is global-pooled, so small inputs keep the CPU
fallback fast), MXTPU_BENCH_CC_MAXB (ragged sweep upper bound, 8).
"""
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NET = os.environ.get("MXTPU_BENCH_CC_NET", "resnet18_v1")
BATCH = int(os.environ.get("MXTPU_BENCH_CC_BATCH", "4"))
HW = int(os.environ.get("MXTPU_BENCH_CC_HW", "64"))
MAXB = int(os.environ.get("MXTPU_BENCH_CC_MAXB", "8"))

_BIND_SCRIPT = r"""
import os, sys, time
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[1]
import numpy as np
t0 = time.perf_counter()
import mxtpu as mx
from mxtpu.gluon.model_zoo import vision
net = getattr(vision, %(net)r)(classes=10)
net.initialize(ctx=mx.cpu())
net.hybridize()
t_import = time.perf_counter() - t0
t1 = time.perf_counter()
net.warmup([(%(batch)d, 3, %(hw)d, %(hw)d)])
t_warmup = time.perf_counter() - t1
# one real batch through the warmed executable (no compile)
t2 = time.perf_counter()
out = net(mx.nd.array(np.ones((%(batch)d, 3, %(hw)d, %(hw)d), "float32")))
out.wait_to_read()
t_first = time.perf_counter() - t2
assert net._cached_op._jit_infer._cache_size() == 0
print("BIND_JSON " + __import__("json").dumps(
    {"import_s": t_import, "warmup_s": t_warmup, "first_batch_s": t_first}))
"""


def _run_bind(cache_dir):
    code = _BIND_SCRIPT % {"net": NET, "batch": BATCH, "hw": HW}
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code, cache_dir],
                       capture_output=True, text=True, timeout=1200,
                       env=env, cwd=REPO)
    if r.returncode != 0:
        raise RuntimeError("bind subprocess failed: %s" % r.stderr[-2000:])
    for line in r.stdout.splitlines():
        if line.startswith("BIND_JSON "):
            return json.loads(line[len("BIND_JSON "):])
    raise RuntimeError("no BIND_JSON line in output")


def bench_cold_warm():
    with tempfile.TemporaryDirectory() as d:
        cache = os.path.join(d, "xla")
        cold = _run_bind(cache)
        warm = _run_bind(cache)
    return cold, warm


def bench_ragged():
    import numpy as np

    import mxtpu as mx
    from mxtpu.gluon.model_zoo import vision

    results = {}
    batches = [np.random.RandomState(b).rand(b, 3, HW, HW).astype("float32")
               for b in range(1, MAXB + 1)]
    for mode, policy in (("off", None), ("pow2", "pow2")):
        mx.set_bucket_policy(policy or "off")
        net = getattr(vision, NET)(classes=10)
        net.initialize(ctx=mx.cpu())
        net.hybridize()
        net(mx.nd.array(batches[-1])).wait_to_read()  # trace once at MAXB
        t0 = time.perf_counter()
        for arr in batches:
            net(mx.nd.array(arr)).wait_to_read()
        dt = time.perf_counter() - t0
        results[mode] = {
            "sweep_s": round(dt, 3),
            "programs": net._cached_op._jit_infer._cache_size(),
            "imgs_per_sec": round(sum(a.shape[0] for a in batches) / dt, 2),
        }
    mx.set_bucket_policy(None)
    return results


def main():
    extra = {"net": NET, "batch": BATCH, "hw": HW,
             "platform": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
             else os.environ.get("JAX_PLATFORMS", "auto")}
    cold, warm = bench_cold_warm()
    extra["cold_warmup_s"] = round(cold["warmup_s"], 3)
    extra["warm_warmup_s"] = round(warm["warmup_s"], 3)
    extra["cold_first_batch_s"] = round(cold["first_batch_s"], 4)
    extra["warm_first_batch_s"] = round(warm["first_batch_s"], 4)
    speedup = cold["warmup_s"] / max(warm["warmup_s"], 1e-9)
    try:
        extra["ragged"] = bench_ragged()
    except Exception as e:  # ragged sweep must not sink the record
        extra["ragged_error"] = str(e)[:300]
    import bench_common

    bench_common.emit_result(
        "compile_cache", "compile_cache_warm_bind_speedup",
        round(speedup, 2), "x",
        step_time_us=round(warm["warmup_s"] * 1e6, 1),
        extra=extra)


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
