#!/usr/bin/env python
"""ZeRO-1 sharding CI guard for the mx.shard backbone (tier-1 via
tests/test_tools.py).

The acceptance contract of ROADMAP item 1 / the `mx.shard` subsystem,
on a >=4-device CPU mesh:

  1. **Loss parity** — `--steps` (default 50) training steps of a real
     small model under ZeRO-1 sharded optimizer state must match the
     replicated run's loss trajectory within ``--tol`` (default 1e-6;
     the host-replica engine is expected to be BITWISE — slicing an
     elementwise optimizer changes memory, not math).
  2. **State memory** — per-replica optimizer-state bytes under the
     plan must measure ~1/N of the full (replicated) state.
  3. **Pass provenance** — the sharding decision must be expressed as
     the `mx.passes` ``shard`` pass: the bound program's `mx.inspect`
     record carries the plan (``sharding`` field + shard entry in the
     pass report) and telemetry ``compile`` events carry it too.
  4. **Collective accounting** — ``allgather_bytes`` /
     ``reduce_scatter_bytes`` tick in ``profiler.stats()`` with the
     ring-payload magnitude the model predicts.
  5. (``--fused``) the FusedTrainLoop sharded scanned carry: GSPMD
     K-step program with state sharded over the mesh matches the
     unsharded loop and places ~1/N state bytes per device.  This one
     is to float tolerance (10x ``--tol``), not bitwise: GSPMD's
     sharded program sums in another order than the unsharded one on
     XLA:CPU (jax 0.9.0), and Adam's m/sqrt(v) turns a last-bit
     gradient difference into ~1e-6 of a weight within a few steps.

Usage: python tools/check_sharding.py [--steps N] [--replicas N]
                                      [--tol T] [--fused]
"""
import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _model(sym):
    x = sym.Variable("data")
    h = sym.FullyConnected(data=x, num_hidden=128, name="fc1")
    h = sym.Activation(data=h, act_type="relu", name="r1")
    h = sym.FullyConnected(data=h, num_hidden=64, name="fc2")
    h = sym.Activation(data=h, act_type="relu", name="r2")
    h = sym.FullyConnected(data=h, num_hidden=4, name="fc3")
    return sym.SoftmaxOutput(data=h, label=sym.Variable("softmax_label"),
                             name="softmax")


def _train(mx, np, plan, n_ctx, steps, batch=32, feat=64):
    """`steps` single-batch updates; returns (losses, params, module)."""
    import contextlib

    from mxtpu import sym
    from mxtpu.io.io import DataBatch
    from mxtpu.metric import CrossEntropy

    rng = np.random.RandomState(5)
    data = [(rng.rand(batch, feat).astype("float32"),
             rng.randint(0, 4, batch).astype("float32"))
            for _ in range(steps)]
    scope = plan.activate() if plan is not None \
        else contextlib.nullcontext()
    with scope:
        mod = mx.mod.Module(_model(sym),
                            context=[mx.cpu(i) for i in range(n_ctx)])
        mod.bind(data_shapes=[("data", (batch, feat))],
                 label_shapes=[("softmax_label", (batch,))])
        mx.random.seed(11)
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(kvstore="device", optimizer="adam",
                           optimizer_params={"learning_rate": 0.01})
        losses = []
        metric = CrossEntropy()
        for x, y in data:
            b = DataBatch(data=[mx.nd.array(x)],
                          label=[mx.nd.array(y)])
            mod.forward(b, is_train=True)
            metric.reset()
            mod.update_metric(metric, b.label)
            losses.append(metric.get()[1])
            mod.backward()
            mod.update()
        p, _ = mod.get_params()
        return (losses, {k: v.asnumpy() for k, v in sorted(p.items())},
                mod)


def check_parity_and_memory(mx, np, n, steps, tol, failures):
    from mxtpu.sharding import ShardingPlan, ZeRO1Updater, zero1 as z1

    losses_r, params_r, mod_r = _train(mx, np, None, n, steps)
    plan = ShardingPlan(min_shard_elems=256)
    losses_s, params_s, mod_s = _train(mx, np, plan, n, steps)

    dl = max(abs(a - b) for a, b in zip(losses_r, losses_s))
    if dl <= tol:
        print("OK: %d-step loss trajectory sharded-vs-replicated "
              "max |delta| = %.3g (tol %g)" % (steps, dl, tol))
    else:
        failures.append("loss trajectory diverged: max |delta| %.3g > "
                        "tol %g" % (dl, tol))
    dp = max(float(np.abs(params_r[k] - params_s[k]).max())
             for k in params_r)
    if dp <= tol:
        print("OK: final params max |delta| = %.3g" % dp)
    else:
        failures.append("final params diverged: %.3g > %g" % (dp, tol))

    upd = mod_s._updater
    if not isinstance(upd, ZeRO1Updater):
        failures.append("plan did not engage the ZeRO-1 updater "
                        "(got %r)" % type(upd).__name__)
        return mod_s, plan
    full = z1.tree_nbytes(upd._gather_full())
    per_replica = upd.per_replica_state_nbytes()
    frac = per_replica / float(full)
    # sharded weights dominate; biases below min_shard_elems stay
    # replicated, so allow up to 1.35x the ideal 1/N
    if 0.9 / n <= frac <= 1.35 / n:
        print("OK: per-replica optimizer state %.1f KiB = %.3f of "
              "full %.1f KiB (~1/%d)"
              % (per_replica / 1024.0, frac, full / 1024.0, n))
    else:
        failures.append("per-replica state fraction %.3f not ~1/%d"
                        % (frac, n))
    return mod_s, plan


def check_provenance(mx, mod_s, n, failures):
    from mxtpu import telemetry

    rec = mod_s._exec_group.execs[0]._insp
    want = "n=%d" % n
    if rec.sharding and want in rec.sharding:
        print("OK: inspect record carries sharding plan %r"
              % rec.sharding)
    else:
        failures.append("inspect record sharding %r does not name %s"
                        % (rec.sharding, want))
    entries = [p for p in (rec.pass_report or {}).get("passes", ())
               if p.get("pass") == "shard"]
    if entries and entries[0].get("annotated", 0) > 0 \
            and want in (entries[0].get("plan") or ""):
        print("OK: shard pass ran on the bound graph (%d vars "
              "annotated, plan %r)" % (entries[0]["annotated"],
                                       entries[0]["plan"]))
    else:
        failures.append("shard pass entry missing/empty on the bound "
                        "program's pass report: %r" % (entries,))
    evs = [e for e in telemetry.events("compile")
           if want in (e.get("sharding") or "")]
    if evs:
        print("OK: %d telemetry compile events carry the plan" % len(evs))
    else:
        failures.append("no telemetry compile event carries the plan")


def check_collective_bytes(mx, np, steps, n, failures):
    from mxtpu import profiler

    stats = profiler.stats()
    ag = stats.get("allgather_bytes", 0)
    rs = stats.get("reduce_scatter_bytes", 0)
    # the sharded run moved >= steps * ring payload of fc1_weight alone
    floor = steps * int(128 * 64 * 4 * (n - 1) / n)
    if ag >= floor and rs >= floor:
        print("OK: collective counters allgather=%.1f MiB "
              "reduce_scatter=%.1f MiB (>= %.1f MiB floor)"
              % (ag / 2**20, rs / 2**20, floor / 2**20))
    else:
        failures.append("collective byte counters too small: ag=%d "
                        "rs=%d < floor %d" % (ag, rs, floor))


def check_fused(mx, np, n, tol, failures):
    """FusedTrainLoop: sharded scanned carry vs plain, one mesh."""
    import contextlib

    import jax

    from mxtpu import parallel, sym
    from mxtpu.fused_train import FusedTrainLoop
    from mxtpu.io.io import DataBatch
    from mxtpu.sharding import ShardingPlan

    rng = np.random.RandomState(7)
    batches = [DataBatch(
        data=[mx.nd.array(rng.rand(16, 64).astype("float32"))],
        label=[mx.nd.array(rng.randint(0, 4, 16).astype("float32"))])
        for _ in range(6)]

    def run(plan):
        scope = plan.activate() if plan is not None \
            else contextlib.nullcontext()
        with scope:
            mod = mx.mod.Module(_model(sym),
                                data_names=("data",),
                                label_names=("softmax_label",))
            mod.bind(data_shapes=[("data", (16, 64))],
                     label_shapes=[("softmax_label", (16,))])
            mx.random.seed(3)
            mod.init_params(initializer=mx.init.Xavier())
            mod.init_optimizer(kvstore=None, optimizer="adam",
                               optimizer_params={"learning_rate": 0.01})
            loop = FusedTrainLoop(mod, steps_per_program=3)
            for i in (0, 3):
                loop.run(batches[i:i + 3])
            loop.finalize()
            p, _ = mod.get_params()
            return ({k: v.asnumpy() for k, v in sorted(p.items())},
                    loop.sharding_info())

    p_r, _ = run(None)
    mesh = parallel.create_mesh({"dp": n}, devices=jax.devices()[:n])
    p_s, info = run(ShardingPlan(mesh=mesh, min_shard_elems=256))
    d = max(float(np.abs(p_r[k] - p_s[k]).max()) for k in p_r)
    tol = 10 * tol      # float tolerance, see the module docstring
    if d <= tol:
        print("OK: fused sharded-carry params match plain loop "
              "(max |delta| %.3g)" % d)
    else:
        failures.append("fused sharded carry diverged: %.3g > %g"
                        % (d, tol))
    if info is None:
        failures.append("fused loop did not engage the sharded carry")
        return
    per_dev = list(info["state_bytes_per_device"].values())
    total = info["state_total_bytes"]
    if len(per_dev) == n and all(b <= total / n * 1.35 for b in per_dev):
        print("OK: fused carry places %.1f KiB/device of %.1f KiB "
              "state (~1/%d)" % (max(per_dev) / 1024.0,
                                 total / 1024.0, n))
    else:
        failures.append("fused carry per-device bytes %r not ~1/%d of "
                        "%d" % (per_dev, n, total))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--fused", action="store_true",
                    help="also check the FusedTrainLoop sharded carry")
    args = ap.parse_args()

    import numpy as np

    import jax

    import mxtpu as mx

    if jax.device_count() < args.replicas:
        print("check_sharding SKIP: need >= %d devices, have %d"
              % (args.replicas, jax.device_count()))
        return 0

    failures = []
    mod_s, _plan = check_parity_and_memory(mx, np, args.replicas,
                                           args.steps, args.tol,
                                           failures)
    check_provenance(mx, mod_s, args.replicas, failures)
    check_collective_bytes(mx, np, args.steps, args.replicas, failures)
    if args.fused:
        check_fused(mx, np, args.replicas, args.tol, failures)

    if failures:
        for f in failures:
            print("FAIL:", f, file=sys.stderr)
        return 1
    print("check_sharding OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
