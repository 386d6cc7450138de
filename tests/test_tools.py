"""tools/: im2rec packing, parse_log, diagnose (reference `tools/`)."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))



# guards whose assertions are structural (events present, within-run
# determinism/parity) run their fleets with HLO optimization passes
# skipped — measured 20-40% faster on the 1-core CI box with every
# gate intact.  NEVER apply this to check_sharding/check_xprof (both
# fail under the flag).
_DEOPT = {"JAX_DISABLE_MOST_OPTIMIZATIONS": "1"}


def _run(args, timeout=300, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(env_extra or {})
    r = subprocess.run([sys.executable] + args, capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=timeout)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    return r.stdout


def test_im2rec_list_pack_consume(tmp_path):
    PIL = pytest.importorskip("PIL.Image")
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = np.random.RandomState(i).randint(
                0, 255, (20, 24, 3), dtype=np.uint8)
            PIL.fromarray(arr).save(str(root / cls / ("%d.jpg" % i)))
    prefix = str(tmp_path / "data")
    out = _run(["tools/im2rec.py", "--list", prefix, str(root)])
    assert "6 entries" in out and os.path.exists(prefix + ".lst")
    _run(["tools/im2rec.py", prefix, str(root), "--resize", "16"])
    assert os.path.exists(prefix + ".rec")
    assert os.path.exists(prefix + ".idx")

    import mxtpu as mx

    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               path_imgidx=prefix + ".idx",
                               data_shape=(3, 16, 16), batch_size=6)
    batch = next(iter(it))
    assert batch.data[0].shape == (6, 3, 16, 16)
    labels = set(batch.label[0].asnumpy().tolist())
    assert labels == {0.0, 1.0}


def test_check_retrace_guard():
    """tools/check_retrace.py: the hot path must not retrace after
    step 1 — this is the CI guard for dispatch-overhead regressions
    (see mxtpu/compile_cache.py)."""
    out = _run(["tools/check_retrace.py", "--steps", "3"])
    assert out.startswith("OK")


def test_check_retrace_blame_on_churn():
    """tools/check_retrace.py --churn: a deliberate batch-size churn
    must FAIL the guard and the failure output must name the exact
    culprit argument from the mx.inspect retrace-blame registry."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, "tools/check_retrace.py", "--steps", "2",
         "--churn", "2"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert r.returncode == 1, (r.returncode, r.stdout, r.stderr)
    assert "retrace-blame" in r.stderr, r.stderr
    assert "data0" in r.stderr and "shape" in r.stderr, r.stderr


def test_check_inspect_guard():
    """tools/check_inspect.py: 5 training steps with a forced mid-run
    shape change must leave the program-inspector registry holding
    BOTH compiled programs, blame naming `data0` in the registry,
    profiler.stats() and the telemetry compile event, counter totals
    that reconcile with profiler.stats(), and a cache-hit bookkeeping
    path under 10us/call (see mxtpu/inspect.py,
    docs/observability.md)."""
    out = _run(["tools/check_inspect.py"])
    assert "check_inspect OK" in out


def test_check_passes_guard():
    """tools/check_passes.py: the graph-rewrite pipeline must be
    bitwise output-identical (passes on vs off) on a real small-model
    train run across all three dispatch paths, strictly reduce node
    count, add zero retraces and hold the per-pass time budget (see
    mxtpu/passes/, docs/passes.md)."""
    out = _run(["tools/check_passes.py"], timeout=420)
    assert "check_passes OK" in out


def test_check_sharding_guard():
    """tools/check_sharding.py: ZeRO-1 sharded training on a 4-replica
    CPU mesh must match replicated training's 20-step loss trajectory
    within 1e-6 (bitwise expected; 20 steps instead of the default 50
    keeps the tier-1 suite inside its 870s wall — parity and the
    step-scaled collective-byte floor hold at any length), measure
    ~1/N per-replica optimizer
    state bytes, carry the plan as `mx.passes` shard-pass provenance on
    the inspect record + telemetry compile events, tick the
    allgather/reduce_scatter byte counters, and the FusedTrainLoop
    sharded scanned carry must match the plain loop to float tolerance
    (see mxtpu/sharding/, docs/sharding.md)."""
    out = _run(["tools/check_sharding.py", "--fused", "--steps", "20"],
               timeout=420)
    assert "check_sharding OK" in out


def test_check_health_guard():
    """tools/check_health.py: a NaN injected at a named mid-model
    layer must be blamed to that layer in health.report(), the
    telemetry anomaly event AND the flight record; the injected steps
    skip with grad norms on their records; the always-on per-step
    health path must stay under its 10us budget."""
    out = _run(["tools/check_health.py"])
    assert "check_health OK" in out


def test_check_resilience_guard():
    """tools/check_resilience.py: a short fault-injected training run
    (compile-fail + kvstore-pull-fail + checkpoint-fail + SIGTERM +
    SIGKILL-mid-save) must recover via retries and auto-resume with
    zero lost checkpoints and fault-free-identical params (see
    mxtpu/resilience.py)."""
    out = _run(["tools/check_resilience.py", "--steps", "20"],
               timeout=420)
    assert "check_resilience OK" in out


def test_check_elastic_smoke_guard():
    """tools/check_elastic.py --smoke: a real multi-process dist_sync
    run survives a SIGKILLed worker — the scheduler re-ranks, the
    stranded sync round completes with the nw0/live rescale, rank 0's
    loss trajectory matches the fault-free run within 1e-5, and the
    launcher honestly exits nonzero for the dead child (see
    mxtpu/_ps.py, docs/elastic.md)."""
    out = _run(["tools/check_elastic.py", "--smoke"], timeout=420,
               env_extra=_DEOPT)  # measured 18s vs 22s, all gates intact
    assert "check_elastic OK" in out


def test_check_telemetry_guard():
    """tools/check_telemetry.py: a 2x2 dist_sync run with a SIGKILLed
    worker must stay observable — the merged chrome trace covers
    scheduler + servers + workers with epoch-aligned clocks, the
    scheduler writes a posthumous flight record naming the dead rank's
    last round, per-role counter sums reconcile with the cluster view,
    and kv.telemetry() serves the live scheduler view (see
    mxtpu/telemetry.py, docs/observability.md)."""
    out = _run(["tools/check_telemetry.py"], timeout=420,
               env_extra=_DEOPT)  # measured 14s vs 20s, all gates intact
    assert "check_telemetry OK" in out


def test_check_serving_guard():
    """tools/check_serving.py: a REAL 2-replica `mx.serve` fleet
    (launch.py --serve-replicas) under closed-loop load must survive a
    SIGKILL of one replica mid-load with ZERO failed requests (client
    failover replays them on the survivor), every output matching the
    deterministic oracle, client p99 within budget, a clean SIGTERM
    drain of the survivor, and a merged telemetry rollup that NAMES
    the failover (see mxtpu/serve.py, docs/serving.md)."""
    out = _run(["tools/check_serving.py", "--duration", "6"],
               timeout=420)
    assert "check_serving OK" in out


def test_check_trace_guard():
    """tools/check_trace.py: one head-sampled serve request against a
    REAL 2-replica fleet must stitch into ONE cross-process span tree
    (client -> queue_wait -> batch_linger -> device) whose segment sum
    reconciles with the measured client wall within 10% and whose
    critical path names a dominant segment; one 2x2 dist_sync training
    round with MXTPU_PS_REPLICATION=1 must stitch
    worker -> server_apply -> replicate across pids; and unsampled
    `mx.tracing.step_trace()` must stay under 10us/step with zero span
    records (see mxtpu/tracing.py, docs/observability.md §Causal
    tracing)."""
    out = _run(["tools/check_trace.py", "--steps", "4"], timeout=420)
    assert "check_trace OK" in out


def test_check_obs_guard():
    """tools/check_obs.py: a 2x2 dist_sync fleet with a SIGKILLed
    worker must keep its LIVE observability plane: every surviving
    role's OpenMetrics endpoint scrapes clean under the strict parser
    with provably read-only scrapes (compile + device-sync counters
    frozen across a scrape burst), cluster_live.json keeps refreshing
    and names the dead rank while the survivor stays live, the run
    ledger reconciles with the final telemetry counters, and the
    sampler holds its overhead budget (see mxtpu/obs.py,
    docs/observability.md §Live metrics)."""
    out = _run(["tools/check_obs.py"], timeout=420,
               env_extra=_DEOPT)  # measured 14s vs 16s, all gates intact
    assert "check_obs OK" in out


def test_check_checkpoint_smoke_guard():
    """tools/check_checkpoint.py --smoke: a real 2x2 dist_sync run
    with mx.checkpoint armed is SIGKILLed as a WHOLE fleet mid-epoch;
    a fresh ``launch.py --auto-resume`` relaunch must restore every
    role from the newest complete fleet manifest and finish with the
    clean run's loss trajectory within 1e-5 — and the armed/disarmed
    step-time comparison plus ckpt_async_write/ckpt_dropped counters
    must show snapshots landing off the step path (see
    mxtpu/checkpoint.py, docs/checkpoint.md)."""
    out = _run(["tools/check_checkpoint.py", "--smoke"], timeout=420)
    assert "check_checkpoint OK" in out


@pytest.mark.slow
def test_check_checkpoint_full_guard():
    """Full crash gauntlet: the whole-fleet SIGKILL phase plus a
    SIGKILL landing MID-CHECKPOINT-WRITE (MXTPU_CKPT_WRITE_DELAY
    widens the window): the launcher's in-run auto-restart must skip
    the torn fleet as a unit and resume from the PREVIOUS complete
    manifest, still matching the clean trajectory."""
    out = _run(["tools/check_checkpoint.py"], timeout=560)
    assert "check_checkpoint OK" in out


@pytest.mark.slow
def test_check_elastic_full_guard():
    """Full chaos gauntlet: SIGKILL one worker (respawned by
    launch.py --restart-workers -> rejoins and resumes at the group's
    round) AND one server (workers fail over to the chain replica)
    with MXTPU_PS_REPLICATION=1 — trajectory must match the clean run;
    with replication off the same kill must abort with the typed
    ServerDiedError, never a hang."""
    out = _run(["tools/check_elastic.py"], timeout=560)
    assert "check_elastic OK" in out


def test_check_xprof_guard():
    """tools/check_xprof.py: measured per-op attribution on a fused
    conv-stack train run — the calibrated replay per-op sum must
    reconcile with the mx.perf program wall within 15%, rows must be
    layer-joined (conv1/conv2/fc1, wgrad class on backward convs), the
    replay and in-tree-xplane paths must agree on a top (op_class,
    layer) sink, profiling must add zero retraces/recompiles, and the
    disabled-mode hook must stay under 10us/step (see mxtpu/xprof.py,
    docs/observability.md §Op profiling)."""
    out = _run(["tools/check_xprof.py"], timeout=420)
    assert "check_xprof OK" in out


def test_check_hbm_guard():
    """tools/check_hbm.py: the per-class static memory plan must sum
    exactly to the memory_analysis peak on Executor / CachedOp /
    FusedTrainLoop with < 10% unattributed residual (donation named
    once, never double-counted); a 50x scrape burst over every census
    surface must compile and dispatch nothing; the disarmed hook must
    cost < 10us/call; and in an RLIMIT_AS-capped subprocess
    hbm.max_batch must bracket the REAL measured OOM boundary within
    one shape bucket (an uncatchable C++ bad_alloc abort at the
    over-budget bucket counts as the boundary), with oom_scope's
    typed MemoryExhaustedError + census forensics proven on the same
    wrapping path (see mxtpu/hbm.py, docs/observability.md §Device
    memory)."""
    # no _DEOPT here: skipping HLO optimization inflates the REAL
    # temp-memory footprint, so the measured OOM boundary drops below
    # what the (deopt) plan predicts and the bracket check fails
    out = _run(["tools/check_hbm.py"], timeout=560)
    assert "check_hbm OK" in out


def test_launch_propagates_child_exit(tmp_path):
    """Satellite: a nonzero worker exit must surface as a nonzero
    launcher exit (silent child death looked like success before)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, "tools/launch.py", "-n", "1", "-s", "0",
         sys.executable, "-c", "import sys; sys.exit(7)"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 7, (r.returncode, r.stdout, r.stderr)


def test_launch_restart_workers(tmp_path):
    """Satellite: --restart-workers N respawns a dead worker; a worker
    that fails once and succeeds on the respawn makes the whole launch
    succeed."""
    marker = tmp_path / "attempted"
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        "p = %r\n"
        "if os.path.exists(p):\n"
        "    sys.exit(0)\n"
        "open(p, 'w').close()\n"
        "sys.exit(1)\n" % str(marker))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    base = [sys.executable, "tools/launch.py", "-n", "1", "-s", "0"]
    r = subprocess.run(base + ["--restart-workers", "1",
                               sys.executable, str(script)],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=120)
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert "respawning" in r.stderr
    # without the budget the same failure propagates
    marker.unlink()
    r = subprocess.run(base + [sys.executable, str(script)],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=120)
    assert r.returncode == 1


def test_parse_log(tmp_path):
    log = tmp_path / "train.log"
    log.write_text(
        "INFO:root:Epoch[0] Train-accuracy=0.5\n"
        "INFO:root:Epoch[0] Time cost=2.5\n"
        "INFO:root:Epoch[0] Validation-accuracy=0.4\n"
        "INFO:root:Epoch[1] Train-accuracy=0.8\n")
    out = _run(["tools/parse_log.py", str(log), "--format", "csv"])
    lines = out.strip().splitlines()
    assert lines[0] == "epoch,time,train-accuracy,validation-accuracy"
    assert lines[1] == "0,2.5,0.5,0.4"
    assert lines[2].startswith("1,nan,0.8")
    md = _run(["tools/parse_log.py", str(log)])
    assert "epoch" in md and "|" in md


def test_diagnose_runs():
    out = _run(["tools/diagnose.py", "--timeout", "5"], timeout=200)
    assert "registered ops:" in out
    assert "Accelerator" in out


def test_env_vars_doc_lists_what_the_code_reads():
    """docs/env_vars.md names exactly the MXTPU_* variables that appear
    in mxtpu/ and tools/: a variable nothing reads any more leaves the
    table with its reader, and a new one arrives documented."""
    import re

    name = re.compile(r"MXTPU_[A-Z0-9_]+")

    def names_in(text):
        # a trailing "_" is a family's prefix (`MXTPU_PEAK_*`,
        # `startswith("MXTPU_")`), not a variable
        return {n for n in name.findall(text) if not n.endswith("_")}

    in_code = set()
    for top in ("mxtpu", "tools"):
        for d, _, files in os.walk(os.path.join(REPO, top)):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(d, f)) as fh:
                        in_code |= names_in(fh.read())
    with open(os.path.join(REPO, "docs", "env_vars.md")) as fh:
        documented = names_in(fh.read())
    assert documented == in_code, (
        "undocumented: %s; documented but read nowhere: %s"
        % (sorted(in_code - documented), sorted(documented - in_code)))
