#!/usr/bin/env python
"""Pass-parity CI guard for the mxtpu.passes graph-rewrite pipeline.

Three checks, any failure = rc 1 (wired into tests/test_tools.py, so a
semantics-changing pass cannot land silently):

  1. **Bitwise trajectory parity** — a real small-model train run
     (FullyConnected + BatchNorm aux write-back + Dropout RNG + an
     elementwise chain + a folded constant subgraph) executed with all
     default passes ON vs OFF, across all THREE dispatch paths
     (Executor bind / CachedOp under autograd / FusedTrainLoop): the
     per-step loss trajectories, final params, aux states and
     gradients must be bitwise equal.

  2. **Node reduction** — the default pipeline must strictly reduce
     the node count of that graph (DCE+fold+CSE+fuse all have work).

  3. **Time budget** — average per-pass wall time (profiler
     ``pass_wall_us::*`` / ``pass_runs::*``) must stay under
     ``--budget-ms`` (default 800 ms; the first fold pays a one-off
     cold jit for its eager evals).

Usage: python tools/check_passes.py [--steps N] [--budget-ms MS]
"""
import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _model():
    from mxtpu import sym

    x = sym.Variable("data")
    h = sym.FullyConnected(data=x, num_hidden=16, name="fc1")
    h = sym.BatchNorm(data=h, name="bn1")
    h = sym.Activation(data=h, act_type="relu", name="r1")
    h = sym.Dropout(data=h, p=0.25, name="do1")
    # elementwise chain (fuse) + duplicate subexpression (cse) +
    # constant subgraph (fold) + identity (dce)
    scale = sym.identity(sym._arange(start=1, stop=17, name="ar"),
                         name="idsc")
    h = sym.broadcast_mul(h, 0.05 * scale + 0.5)
    h = sym.tanh(h * 0.5) + sym.tanh(h * 0.5)
    out = sym.FullyConnected(data=h, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(data=out, label=sym.Variable("softmax_label"),
                             name="softmax")


def _batches(mx, np, steps, bs=8, feat=16):
    rng = np.random.RandomState(5)
    return [(rng.rand(bs, feat).astype("float32"),
             rng.randint(0, 4, bs).astype("float32"))
            for _ in range(steps)]


def _run_module(mx, np, P, spec, steps, fused):
    """Train `steps` steps; returns (losses, params, aux)."""
    from mxtpu.io.io import DataBatch

    with P.scope(spec):
        net = _model()
        mod = mx.mod.Module(net, data_names=("data",),
                            label_names=("softmax_label",))
        mod.bind(data_shapes=[("data", (8, 16))],
                 label_shapes=[("softmax_label", (8,))])
        mx.random.seed(11)
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        data = _batches(mx, np, steps)
        losses = []
        mx.random.seed(13)
        if fused:
            from mxtpu.fused_train import FusedTrainLoop

            loop = FusedTrainLoop(mod, steps_per_program=2)
            for i in range(0, steps, 2):
                outs = loop.run([DataBatch(data=[mx.nd.array(x)],
                                           label=[mx.nd.array(y)])
                                 for x, y in data[i:i + 2]])
                losses.extend(np.asarray(o) for o in outs[0].asnumpy())
            loop.finalize()
        else:
            for x, y in data:
                b = DataBatch(data=[mx.nd.array(x)],
                              label=[mx.nd.array(y)])
                mod.forward(b, is_train=True)
                losses.append(mod.get_outputs()[0].asnumpy())
                mod.backward()
                mod.update()
        p, a = mod.get_params()
        return (losses, {k: v.asnumpy() for k, v in sorted(p.items())},
                {k: v.asnumpy() for k, v in sorted(a.items())})


def _run_cachedop(mx, np, P, spec):
    """One recorded fwd/bwd through a CachedOp; returns out/aux/grad."""
    from mxtpu import autograd

    with P.scope(spec):
        net = _model()
        co = mx.CachedOp(net)
    args = net.list_arguments()
    shapes, _, aux_shapes = net.infer_shape(data=(8, 16),
                                            softmax_label=(8,))
    rng = np.random.RandomState(3)
    nd_in = [mx.nd.array(rng.rand(*s).astype("float32")) for s in shapes]
    for a in nd_in:
        a.attach_grad()
    aux_arr = [mx.nd.ones(s) for s in aux_shapes]
    mx.random.seed(7)
    with autograd.record():
        out = co(nd_in, aux_arr)[0]
    out.backward()
    gi = args.index("fc1_weight")
    return (out.asnumpy(), [a.asnumpy() for a in aux_arr],
            nd_in[gi].grad.asnumpy())


def _bitwise(np, a, b) -> bool:
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and \
            all(_bitwise(np, x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return set(a) == set(b) and \
            all(_bitwise(np, a[k], b[k]) for k in a)
    return np.array_equal(np.asarray(a), np.asarray(b))


def check_parity(mx, np, P, steps, failures):
    for path, runner in (
            ("executor", lambda s: _run_module(mx, np, P, s, steps,
                                               fused=False)),
            ("fused_train", lambda s: _run_module(mx, np, P, s, steps,
                                                  fused=True)),
            ("cachedop", lambda s: _run_cachedop(mx, np, P, s))):
        off = runner("off")
        on = runner("default")
        if _bitwise(np, off, on):
            print("OK: %s passes-on vs passes-off bitwise equal" % path)
        else:
            failures.append("%s: passes changed results" % path)


def check_reduction(mx, P, failures):
    net = _model()
    _, report = net.optimize(passes="default", return_report=True)
    nb, na = report["nodes_before"], report["nodes_after"]
    if na < nb:
        print("OK: node count %d -> %d (%s)"
              % (nb, na, report["spec"]))
    else:
        failures.append("node count not reduced: %d -> %d" % (nb, na))
    by_pass = {p["pass"]: p for p in report["passes"]}
    for name, key in (("dce", "identity_removed"), ("fold", "folded"),
                      ("cse", "cse_merged"), ("fuse", "chains")):
        if by_pass.get(name, {}).get(key, 0) < 1:
            failures.append("pass %r had no work on the probe graph "
                            "(%s=0) — probe and pass drifted apart"
                            % (name, key))


def check_budget(budget_ms, failures):
    from mxtpu import profiler

    stats = profiler.stats()
    for k, us in sorted(stats.items()):
        if not k.startswith("pass_wall_us::"):
            continue
        name = k.split("::", 1)[1]
        runs = max(1, stats.get("pass_runs::" + name, 1))
        avg_ms = us / runs / 1000.0
        if avg_ms > budget_ms:
            failures.append("pass %r avg %.1f ms/run exceeds budget "
                            "%d ms" % (name, avg_ms, budget_ms))
        else:
            print("OK: pass %-8s avg %.2f ms/run over %d runs"
                  % (name, avg_ms, runs))


def check_retrace_free(mx, failures):
    """Passes run pre-trace: dispatching the SAME shapes twice must
    not tick any *_trace counter on the second dispatch."""
    import numpy as np

    from mxtpu import profiler

    net = _model()
    ex = net.simple_bind(mx.cpu(), data=(8, 16), softmax_label=(8,))
    x = mx.nd.array(np.ones((8, 16), "float32"))
    ex.forward(is_train=False, data=x)
    before = {k: v for k, v in profiler.stats().items()
              if k.endswith("_trace")}
    ex.forward(is_train=False, data=x)
    after = {k: v for k, v in profiler.stats().items()
             if k.endswith("_trace")}
    grew = {k: (before.get(k, 0), v) for k, v in after.items()
            if v > before.get(k, 0)}
    if grew:
        failures.append("passes added retraces: %s" % grew)
    else:
        print("OK: zero extra retraces with passes on")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4,
                    help="train steps per parity run (even; default 4)")
    ap.add_argument("--budget-ms", type=int, default=800,
                    help="max avg wall ms per pass run")
    args = ap.parse_args()

    import numpy as np

    import mxtpu as mx
    import mxtpu.passes as P

    failures = []
    check_parity(mx, np, P, args.steps, failures)
    check_reduction(mx, P, failures)
    check_retrace_free(mx, failures)
    check_budget(args.budget_ms, failures)

    if failures:
        for f in failures:
            print("FAIL:", f, file=sys.stderr)
        return 1
    print("check_passes OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
