#!/usr/bin/env python
"""CI guard for `mx.xprof` — measured per-op device-time attribution.

Five checks on a real fused conv-stack train run (any failure = rc 1;
wired into tests/test_tools.py):

  1. **Wall reconciliation** — the calibrated per-op replay walls must
     SUM to the `mx.perf` sampled program wall within 15% (the
     acceptance tolerance), with the calibration record carrying the
     raw sum + scale it applied.
  2. **Layer attribution** — the top sinks must be layer-joined: every
     one of conv1/conv2/fc1 appears as some op's layer, and a wgrad
     row exists (backward conv attributed as weight-gradient work).
  3. **Cross-path top-sink consistency** — a real `mx.inspect.trace`
     capture ingested through the in-tree xplane decoder must agree
     with the replay path on where the time goes: the two paths' top
     sinks share at least one (op_class, layer) pair, and both name a
     conv-family class (conv/wgrad) among their leaders.
  4. **Zero retraces** — profiling must not dispatch the compiled
     program or trigger recompiles: the program's inspect compile
     count and every profiler ``*_trace`` counter are unchanged across
     both acquisition paths.
  5. **Disabled-mode budget** — with profiling off (``MXTPU_XPROF=0``
     semantics via ``xprof.enable(False)``), the per-chunk
     ``maybe_autoprofile`` hook must cost < 10us/step (MIN over
     batches).

Also asserts the consumer wiring: the profile lands on the program's
`mx.inspect` record (``op_profile``), emits the ``op_profile``
telemetry event, and surfaces through ``mx.xprof.top_sink()`` (what
`mx.obs`/dash show per rank).

Usage: python tools/check_xprof.py [--iters N]
"""
import argparse
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the reconciliation target is the sampled program wall: force the
# observatory on and sample every chunk so a short run measures it
os.environ["MXTPU_PERF"] = "1"
os.environ["MXTPU_PERF_SYNC_EVERY"] = "2"
os.environ.setdefault("MXTPU_TELEMETRY", "1")
TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TOOLS))
sys.path.insert(0, TOOLS)

RECONCILE_TOL = 0.15      # the ISSUE's acceptance tolerance
HOOK_BUDGET_US = 10.0


def _trace_counters():
    from mxtpu import profiler

    return {k: v for k, v in profiler.stats().items()
            if k.endswith("_trace")}


def check_reconciliation(mx, prof, failures):
    cal = prof.get("calibration")
    if not cal:
        failures.append("replay profile carries no calibration record "
                        "(perf wall was never sampled?)")
        return
    wall = cal.get("program_wall_us") or 0.0
    raw = cal.get("raw_sum_us") or 0.0
    scale = cal.get("scale") or 0.0
    opsum = sum(o["wall_us"] for o in prof["ops"])
    if wall <= 0 or raw <= 0 or scale <= 0:
        failures.append("calibration record incomplete: %r" % (cal,))
        return
    rel = abs(opsum - wall) / wall
    if rel > RECONCILE_TOL:
        failures.append(
            "per-op sum %.1fus vs program wall %.1fus: off by %.1f%% "
            "(> %.0f%%)" % (opsum, wall, rel * 100,
                            RECONCILE_TOL * 100))
    else:
        print("OK: per-op sum %.1fus reconciles with mx.perf program "
              "wall %.1fus (%.2f%% off; raw replay sum %.1fus, "
              "scale %.3f)" % (opsum, wall, rel * 100, raw, scale))


def check_layers(prof, failures):
    layers = {o.get("layer") for o in prof["ops"]}
    missing = {"conv1", "conv2", "fc1"} - layers
    if missing:
        failures.append("layer join lost layers %s (got %s)"
                        % (sorted(missing), sorted(filter(None,
                                                          layers))))
    else:
        print("OK: replay rows layer-joined (conv1/conv2/fc1 present)")
    wgrads = [o for o in prof["ops"] if o.get("op_class") == "wgrad"]
    if not wgrads:
        failures.append("no wgrad rows: backward conv/matmul not "
                        "attributed as weight-gradient work")
    else:
        print("OK: %d wgrad rows (e.g. %s @ %s)"
              % (len(wgrads), wgrads[0]["op"], wgrads[0].get("layer")))


def check_cross_path(mx, replay, xplane, failures):
    def sink_pairs(prof, k=8):
        return {(o.get("op_class"), o.get("layer"))
                for o in prof["ops"][:k] if o.get("layer")}

    common = sink_pairs(replay) & sink_pairs(xplane)
    if not common:
        failures.append(
            "replay and xplane top sinks share no (op_class, layer) "
            "pair: replay=%s xplane=%s"
            % (sorted(sink_pairs(replay)), sorted(sink_pairs(xplane))))
    else:
        print("OK: paths agree on top sinks %s" % sorted(common))
    for name, prof in (("replay", replay), ("xplane", xplane)):
        top_classes = {o.get("op_class") for o in prof["ops"][:8]}
        if not ({"conv", "wgrad"} & top_classes):
            failures.append("%s path: no conv-family class among the "
                            "top sinks (%s)" % (name,
                                                sorted(top_classes)))


def check_consumers(mx, loop, prof, failures):
    rec = mx.inspect.find(loop._insp.name)
    compact = getattr(rec, "op_profile", None)
    if not compact or not compact.get("top"):
        failures.append("inspect record carries no op_profile")
    else:
        print("OK: inspect record op_profile (top: %s)"
              % compact["top"][0]["op"])
    evs = mx.telemetry.events("op_profile")
    if not evs:
        failures.append("no op_profile telemetry event recorded")
    else:
        print("OK: op_profile telemetry event (top_class=%s)"
              % evs[-1].get("top_class"))
    sink = mx.xprof.top_sink()
    if not sink or not sink.get("op"):
        failures.append("mx.xprof.top_sink() empty after profiling")
    else:
        print("OK: top_sink() -> %s (%s) %.0f%%"
              % (sink["op"], sink.get("op_class"),
                 100 * (sink.get("share") or 0)))


def check_disabled_budget(mx, loop, stacked, failures):
    from mxtpu import xprof

    xprof.enable(False)
    try:
        best = float("inf")
        for _ in range(20):
            t0 = time.perf_counter()
            for _ in range(2000):
                xprof.maybe_autoprofile(loop, stacked)
            dt = (time.perf_counter() - t0) / 2000 * 1e6
            best = min(best, dt)
    finally:
        xprof.enable(True)
    if best > HOOK_BUDGET_US:
        failures.append("disabled maybe_autoprofile hook %.2fus/step "
                        "> %.0fus budget" % (best, HOOK_BUDGET_US))
    else:
        print("OK: disabled hook %.3fus/step (< %.0fus budget)"
              % (best, HOOK_BUDGET_US))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=6,
                    help="measured chunks before profiling")
    args = ap.parse_args(argv)

    import jax

    import mxtpu as mx
    from op_report import build_conv_loop

    mx.inspect.enable(True)
    failures = []

    loop, make_batches = build_conv_loop(batch=8, image=16, spp=2)
    stacked = None
    for _ in range(args.iters):
        stacked = loop.stack_batches(make_batches())
        loop.run_stacked(stacked)
    jax.block_until_ready(loop._p_vals)

    rec = mx.inspect.find(loop._insp.name)
    compiles_before = rec.compiles
    traces_before = _trace_counters()

    prof = mx.xprof.profile(loop, data=[s[0] for s in stacked])
    if prof is None:
        print("FAIL: xprof disabled (MXTPU_XPROF=0 in env?)",
              file=sys.stderr)
        return 1

    check_reconciliation(mx, prof, failures)
    check_layers(prof, failures)
    check_consumers(mx, loop, prof, failures)

    # path (a): a real trace through the in-tree xplane decoder
    tdir = "/tmp/mxtpu_check_xprof_%d" % os.getpid()
    with mx.inspect.trace(tdir):
        loop.run_stacked(loop.stack_batches(make_batches()))
        jax.block_until_ready(loop._p_vals)
    xplane = mx.xprof.ingest(tdir, program=loop._insp.name,
                             kind="train", steps=2)
    check_cross_path(mx, prof, xplane, failures)

    compiles_after = mx.inspect.find(loop._insp.name).compiles
    traces_after = _trace_counters()
    if compiles_after != compiles_before:
        failures.append("profiling recompiled the program: compiles "
                        "%d -> %d" % (compiles_before, compiles_after))
    grew = {k: (traces_before.get(k, 0), v)
            for k, v in traces_after.items()
            if v > traces_before.get(k, 0)}
    if grew:
        failures.append("profiling added retraces: %s" % grew)
    if compiles_after == compiles_before and not grew:
        print("OK: zero retraces / recompiles across both paths")

    check_disabled_budget(mx, loop, stacked, failures)
    loop.finalize()

    if failures:
        for f in failures:
            print("FAIL:", f, file=sys.stderr)
        return 1
    print("check_xprof OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
