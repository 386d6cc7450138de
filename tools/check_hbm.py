#!/usr/bin/env python
"""CI guard for `mx.hbm` — the device-memory observatory.

Four checks (any failure = rc 1; wired into tests/test_tools.py):

  1. **Plan reconciliation** — the per-class static memory plan must
     sum EXACTLY to the `memory_analysis` peak on all three dispatch
     paths (Executor, CachedOp infer+train, FusedTrainLoop), with the
     unplaced residual (``unattributed``) under 10% of peak — the
     acceptance tolerance.  On the fused path (all params/state
     donated) the donated-aliased bytes must equal the analysis alias
     bytes: donation is named once, never double-counted.
  2. **Scrape purity** — a 50x burst over every consumer surface
     (``telemetry.metrics()``, ``obs.sample()``, ``obs.openmetrics()``
     and a forced census sweep) must compile NOTHING and dispatch
     NOTHING: every ``*_trace``/``*_warmup`` profiler counter, the
     ``inspect_compiles`` stat and the registry signature count are
     frozen across the burst.
  3. **Disarmed budget** — with the census off (``MXTPU_HBM=0``
     semantics via ``hbm.enable(False)``) the step-path surfaces
     (``observe_used``/``census``/``metrics_block``) must cost
     < 10us/call (MIN over batches).
  4. **Capacity bracket** — in a CPU-memory-capped subprocess
     (RLIMIT_AS = VmSize + margin, set AFTER warming the bucket
     ladder), ``hbm.max_batch(headroom_bytes=margin)`` must bracket
     the REAL measured OOM boundary within one shape bucket — and the
     OOM must surface as the typed ``MemoryExhaustedError`` whose
     forensics ride the hbm census.

Usage: python tools/check_hbm.py [--probe]   (--probe is the internal
subprocess body of check 4)
"""
import argparse
import json
import os
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXTPU_TELEMETRY", "1")
os.environ.setdefault("MXTPU_HBM", "1")
TOOLS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS)
sys.path.insert(0, REPO)
sys.path.insert(0, TOOLS)

RECONCILE_TOL = 0.10      # the ISSUE's acceptance tolerance
HOOK_BUDGET_US = 10.0
PROBE_BUCKETS = [1, 2, 4, 8, 16, 32, 64]
PROBE_HIDDEN = 1 << 20    # ~4MB output per sample: the OOM boundary
PROBE_IN = 16             # lands inside the bucket ladder
PROBE_MARGIN = 160 << 20


# ---------------------------------------------------------------------------
# workload builders (one per dispatch path)
# ---------------------------------------------------------------------------

def _executor_program():
    import mxtpu as mx

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=data, num_hidden=32, name="fc1")
    act = mx.sym.Activation(data=fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(data=act, num_hidden=8, name="fc2")
    sym = mx.sym.SoftmaxOutput(
        data=fc2, label=mx.sym.Variable("softmax_label"), name="softmax")
    ex = sym.simple_bind(mx.cpu(), data=(8, 20), softmax_label=(8,))
    ex.forward(is_train=True, data=mx.nd.ones((8, 20)))
    ex.backward()
    return ex._insp


def _cachedop_program():
    import mxtpu as mx
    from mxtpu import autograd
    from mxtpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((8, 20))
    net(x).wait_to_read()
    with autograd.record():
        out = net(x)
    out.backward()
    return net._cached_op._insp


def _fused_program():
    import numpy as np
    import mxtpu as mx
    from mxtpu.fused_train import FusedTrainLoop
    from mxtpu.io.io import DataBatch

    sym_data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=sym_data, num_hidden=32, name="fc1")
    act = mx.sym.Activation(data=fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(data=act, num_hidden=8, name="fc2")
    sym = mx.sym.SoftmaxOutput(
        data=fc2, label=mx.sym.Variable("softmax_label"), name="softmax")
    mod = mx.mod.Module(sym, data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (8, 20))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    loop = FusedTrainLoop(mod, steps_per_program=2)
    rng = np.random.RandomState(0)
    batches = [DataBatch(
        data=[mx.nd.array(rng.rand(8, 20).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 8, 8).astype(np.float32))])
        for _ in range(2)]
    loop.run(batches)
    return loop._insp


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def check_plan_reconciliation(failures):
    from mxtpu import hbm

    cases = []
    ex_rec = _executor_program()
    cases.append(("executor/train", hbm.plan(ex_rec, kind="train")))
    co_rec = _cachedop_program()
    cases.append(("cachedop/infer", hbm.plan(co_rec, kind="infer")))
    cases.append(("cachedop/train", hbm.plan(co_rec, kind="train")))
    fu_rec = _fused_program()
    fu_plan = hbm.plan(fu_rec, kind="train")
    cases.append(("fused_train/train", fu_plan))

    for label, plan in cases:
        if "error" in plan:
            failures.append("plan %s failed: %s" % (label, plan["error"]))
            continue
        peak = plan["peak_bytes"]
        total = sum(plan["classes"].values())
        resid = abs(plan["classes"].get("unattributed", 0))
        print("  %-18s peak=%d placed_sum=%d residual=%d (%.1f%%)"
              % (label, peak, total, resid,
                 100.0 * resid / max(1, peak)))
        if total != peak:
            failures.append(
                "plan %s classes sum %d != peak %d (must reconcile "
                "exactly by construction)" % (label, total, peak))
        if resid > RECONCILE_TOL * max(1, peak):
            failures.append(
                "plan %s unattributed residual %d exceeds %d%% of "
                "peak %d" % (label, resid, int(RECONCILE_TOL * 100),
                             peak))
        if peak <= 0:
            failures.append("plan %s has non-positive peak" % label)

    # donation accounting on the fused path: params + opt state are
    # donated, so alias bytes must be named once and excluded from the
    # class budget (the exact-sum assert above already proves no
    # double-count; here we prove the donation was actually SEEN)
    if "error" not in fu_plan:
        if fu_plan["alias_bytes"] <= 0:
            failures.append("fused plan saw no donation (alias_bytes "
                            "= %d)" % fu_plan["alias_bytes"])
        if fu_plan["donated_aliased_bytes"] != fu_plan["alias_bytes"]:
            failures.append(
                "fused plan donated_aliased_bytes %d != analysis "
                "alias_bytes %d" % (fu_plan["donated_aliased_bytes"],
                                    fu_plan["alias_bytes"]))
        wi = fu_plan.get("what_if") or {}
        if not wi.get("zero1_optimizer_state_bytes"):
            failures.append("fused/adam plan prices no ZeRO-1 "
                            "optimizer state (what_if=%r)" % (wi,))
    return ex_rec


def check_scrape_purity(failures):
    from mxtpu import hbm, obs, profiler, telemetry
    import mxtpu as mx

    def frozen_counters():
        stats = profiler.stats()
        keys = {k: v for k, v in stats.items()
                if k.endswith("_trace") or k.endswith("_warmup")}
        keys["inspect_compiles"] = stats.get("inspect_compiles", 0)
        keys["_n_sigs"] = sum(p["n_sigs"] for p in
                              mx.inspect.programs(analyze=False))
        return keys

    before = frozen_counters()
    for _ in range(50):
        telemetry.metrics()
        obs.sample()
        obs.openmetrics()
        hbm.census(force=True)
        hbm.metrics_block()
        hbm.headroom()
    after = frozen_counters()
    if before != after:
        delta = {k: (before.get(k), after.get(k))
                 for k in set(before) | set(after)
                 if before.get(k) != after.get(k)}
        failures.append("scrape burst moved compile/dispatch counters "
                        "(census is not read-only): %r" % (delta,))
    else:
        print("  50x scrape burst: %d counters frozen, %d signatures "
              "untouched" % (len(before) - 1, before["_n_sigs"]))


def check_disarmed_budget(failures):
    from mxtpu import hbm

    hbm.enable(False)
    try:
        # MIN over batches: the budget is about the cheap path, not
        # scheduler noise
        best = float("inf")
        n = 3000
        for _batch in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                hbm.observe_used(123456)
                hbm.census()
                hbm.metrics_block()
            per_call_us = (time.perf_counter() - t0) * 1e6 / (3 * n)
            best = min(best, per_call_us)
        print("  disarmed hook: %.3f us/call (budget %.0f)"
              % (best, HOOK_BUDGET_US))
        if best >= HOOK_BUDGET_US:
            failures.append("disarmed hbm hook costs %.2f us/call "
                            "(budget %.0f)" % (best, HOOK_BUDGET_US))
    finally:
        hbm.enable(True)


def probe_main():
    """Subprocess body of check 4: warm the bucket ladder, cap
    RLIMIT_AS at VmSize + margin, then probe ascending buckets until
    the real OOM.  Emits one JSON line per event on stdout."""
    import resource

    import numpy as np
    import mxtpu as mx
    from mxtpu import hbm
    from mxtpu.gluon import nn
    from mxtpu.health import MemoryExhaustedError, oom_scope

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(PROBE_HIDDEN, activation="relu"))
    net.initialize()
    net.hybridize()
    # warm + analyze EVERY bucket first: compiles happen uncapped, so
    # the capped phase below measures pure execution footprint
    for b in PROBE_BUCKETS:
        x = mx.nd.array(np.random.rand(b, PROBE_IN).astype("float32"))
        net(x)[0].asnumpy()
    rec = net._cached_op._insp
    cm = hbm.capacity_model(rec, kind="infer")
    emit(ev="capacity", bytes_per_sample=cm.get("bytes_per_sample"),
         fixed_bytes=cm.get("fixed_bytes"),
         resident_bytes=cm.get("resident_bytes"))

    # typed-wrap self-test on the REAL wrapping path: an OOM-shaped
    # error escaping oom_scope must come back as MemoryExhaustedError
    # carrying census forensics.  Deterministic — the capped ladder
    # below can instead die to an uncatchable C++ bad_alloc abort
    # depending on which allocation hits the rlimit first.
    try:
        with oom_scope("hbm_probe_selftest"):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: synthetic OOM (wrap self-test)")
    except MemoryExhaustedError as e:
        rep = getattr(e, "report", None) or {}
        emit(ev="typed_wrap", typed=True,
             report_has_census=bool(rep.get("top_live_buffers")
                                    or rep.get("plan_vs_live")))
    except BaseException as e:
        emit(ev="typed_wrap", typed=False, type=type(e).__name__)

    with open("/proc/self/statm") as f:
        vm = int(f.read().split()[0]) * os.sysconf("SC_PAGE_SIZE")
    resource.setrlimit(resource.RLIMIT_AS,
                       (vm + PROBE_MARGIN, resource.RLIM_INFINITY))
    pred = hbm.max_batch(rec, headroom_bytes=PROBE_MARGIN,
                         kind="infer", buckets=PROBE_BUCKETS,
                         analyze=False)
    emit(ev="pred", max_batch=pred, vm_bytes=vm,
         limit_bytes=hbm.limit_bytes(), headroom=hbm.headroom())

    last_ok = boundary = None
    typed = False
    for b in PROBE_BUCKETS:
        try:
            x = mx.nd.array(
                np.random.rand(b, PROBE_IN).astype("float32"))
            with oom_scope("hbm_probe"):
                net(x)[0].asnumpy()
            last_ok = b
            emit(ev="ok", batch=b)
        except BaseException as e:
            boundary = b
            typed = isinstance(e, MemoryExhaustedError)
            rep = getattr(e, "report", None) or {}
            emit(ev="oom", batch=b, type=type(e).__name__,
                 typed=typed,
                 report_has_census=bool(rep.get("top_live_buffers")
                                        or rep.get("plan_vs_live")))
            break
    emit(ev="done", last_ok=last_ok, boundary=boundary, pred=pred)
    return 0


def check_capacity_bracket(failures):
    env = dict(os.environ)
    env.pop("MXTPU_HBM_LIMIT_BYTES", None)
    # the probe measures a SINGLE-device footprint against a
    # single-device plan; a harness-inherited
    # --xla_force_host_platform_device_count (pytest sets 8) would
    # multiply the backend's arenas and sink the real OOM boundary
    # below the per-device prediction
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    if flags:
        env["XLA_FLAGS"] = " ".join(flags)
    else:
        env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--probe"],
        capture_output=True, text=True, env=env, timeout=240)
    events = {}
    for line in r.stdout.splitlines():
        try:
            ev = json.loads(line)
            events[ev.pop("ev")] = ev
        except (ValueError, KeyError):
            continue
    killed = False
    if "done" in events and r.returncode == 0:
        done = events["done"]
        pred, last_ok, boundary = (done.get("pred"),
                                   done.get("last_ok"),
                                   done.get("boundary"))
    elif "pred" in events and "ok" in events:
        # the rlimit hit landed inside XLA's C++ threads: std::bad_alloc
        # terminates the process before Python sees anything.  The
        # death IS the OOM boundary — the last flushed "ok" line names
        # the last bucket that fit.
        killed = True
        pred = events["pred"].get("max_batch")
        last_ok = events["ok"].get("batch")
        nxt = PROBE_BUCKETS.index(last_ok) + 1
        boundary = PROBE_BUCKETS[nxt] if nxt < len(PROBE_BUCKETS) \
            else None
    else:
        failures.append("capacity probe subprocess failed (rc=%d): %s"
                        % (r.returncode, (r.stderr or r.stdout)[-400:]))
        return
    print("  probe: predicted max_batch=%s, measured last_ok=%s, "
          "first OOM at %s%s" % (pred, last_ok, boundary,
                                 " (C++ abort under rlimit)"
                                 if killed else ""))
    if boundary is None:
        failures.append("probe never hit the OOM boundary (ladder too "
                        "small for the margin)")
        return
    if last_ok is None or pred is None:
        failures.append("probe got no fit prediction or no successful "
                        "batch (pred=%r last_ok=%r)" % (pred, last_ok))
        return
    # the acceptance: the prediction brackets the measured boundary
    # within ONE shape bucket
    li, pi = PROBE_BUCKETS.index(last_ok), PROBE_BUCKETS.index(pred)
    if abs(pi - li) > 1:
        failures.append("max_batch prediction %d is %d buckets away "
                        "from the measured boundary (last_ok=%d, "
                        "oom_at=%d)" % (pred, abs(pi - li), last_ok,
                                        boundary))
    # the typed-forensics contract, proven on the real oom_scope
    # wrapping path by the probe's deterministic self-test...
    wrap = events.get("typed_wrap") or {}
    if not wrap.get("typed"):
        failures.append("oom_scope did not wrap an OOM-shaped error "
                        "as MemoryExhaustedError (got %s)"
                        % wrap.get("type"))
    elif not wrap.get("report_has_census"):
        failures.append("typed OOM report carries no hbm census "
                        "forensics")
    # ... and additionally on the real OOM when the OS let Python
    # catch it (a C++ bad_alloc abort yields no oom event)
    oom = events.get("oom")
    if oom is not None and not oom.get("typed"):
        failures.append("catchable probe OOM did not surface as the "
                        "typed MemoryExhaustedError (got %s)"
                        % oom.get("type"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--probe", action="store_true",
                    help="internal: run the RLIMIT_AS probe body")
    args = ap.parse_args(argv)
    if args.probe:
        return probe_main()

    failures = []
    import mxtpu as mx
    from mxtpu import hbm, obs, telemetry

    print("check 1: per-class plan reconciles with memory_analysis "
          "peak (3 dispatch paths)")
    check_plan_reconciliation(failures)

    # consumer wiring rides along: the census block must be on every
    # surface the docs promise before we prove it is pure
    m = telemetry.metrics().get("hbm") or {}
    if not m.get("enabled"):
        failures.append("metrics()['hbm'] missing or disabled")
    if "mxtpu_hbm_used_bytes" not in obs.openmetrics():
        failures.append("openmetrics lacks mxtpu_hbm_used_bytes gauge")
    rep = mx.inspect.report()
    if "memory_plan" not in rep:
        failures.append("inspect.report() lacks memory_plan")

    print("check 2: scrape burst compiles and dispatches nothing")
    check_scrape_purity(failures)

    print("check 3: disarmed hook budget")
    check_disarmed_budget(failures)

    print("check 4: capacity prediction brackets the real OOM "
          "boundary (RLIMIT_AS subprocess)")
    check_capacity_bracket(failures)

    print()
    if failures:
        for f in failures:
            print("FAIL: %s" % f)
        return 1
    print("check_hbm OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
