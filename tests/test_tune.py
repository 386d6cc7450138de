"""mx.tune: knob registry, tuning DB, trial runner, search loop
(mxtpu/tune/, docs/tuning.md, tools/check_tune.py)."""
import json
import os
import subprocess
import sys
import time

import pytest

import mxtpu as mx
from mxtpu import tune
from mxtpu.base import MXNetError
from mxtpu.tune import registry
from mxtpu.tune.trial import Trial

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmark", "python")


def _net(prefix=""):
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data=data, num_hidden=8,
                              name=prefix + "fc")
    h = mx.sym.Activation(data=h, act_type="relu", name=prefix + "act")
    return mx.sym.SoftmaxOutput(data=h, name=prefix + "sm")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_declare_apply_roundtrip():
    """A declared knob round-trips: env_for_config -> apply_config
    installs the env var and fires the in-process hook; UNSET deletes
    the var."""
    hook_calls = []
    registry.declare(registry.Knob(
        "t_test_knob", "tests", "MXTPU_T_TEST_KNOB",
        [registry.UNSET, "a", "b"], "a", "test-only",
        apply_hook=hook_calls.append))
    try:
        knob = registry.get("t_test_knob")
        assert knob.env_of("b") == {"MXTPU_T_TEST_KNOB": "b"}
        assert registry.env_for_config({"t_test_knob": "b"}) \
            == {"MXTPU_T_TEST_KNOB": "b"}
        cfg = registry.apply_config({"t_test_knob": "b"})
        assert cfg == {"t_test_knob": "b"}
        assert os.environ["MXTPU_T_TEST_KNOB"] == "b"
        assert knob.current() == "b"
        assert registry.current_config(["t_test_knob"]) \
            == {"t_test_knob": "b"}
        # UNSET deletes the var and the knob reads back its default
        registry.apply_config({"t_test_knob": registry.UNSET})
        assert "MXTPU_T_TEST_KNOB" not in os.environ
        assert knob.current() == "a"
        assert hook_calls == ["b", ""]
    finally:
        os.environ.pop("MXTPU_T_TEST_KNOB", None)
        registry._REGISTRY.pop("t_test_knob", None)


def test_registry_domain_validation():
    """Out-of-domain values are rejected everywhere: validate, config
    validation, candidate generation — the search can never propose an
    illegal value."""
    from mxtpu.tune.search import candidates_for

    knob = registry.get("donate")
    with pytest.raises(MXNetError):
        knob.validate("maybe")
    with pytest.raises(MXNetError):
        registry.validate_config({"donate": "2"})
    with pytest.raises(MXNetError):
        registry.validate_config({"no_such_knob": "1"})
    with pytest.raises(MXNetError):
        registry.Knob("bad", "tests", "MXTPU_BAD", ["a", "b"], "c")
    for cand in candidates_for(registry.defaults(["donate", "passes"]),
                               ["donate", "passes"]):
        registry.validate_config(cand)  # must not raise


def test_seed_knobs_cover_the_documented_space():
    """The issue's knob floor: steps_per_program, shape buckets,
    passes, remat, donate, layout, the serve batcher pair, and the
    DataLoader device prefetch are all declared."""
    have = set(registry.names())
    assert {"steps_per_program", "shape_buckets", "passes", "remat",
            "donate", "layout", "serve_batch_wait_us",
            "serve_max_batch", "prefetch_device"} <= have
    # remat is a multi-var knob: "off" must UNSET both carriers
    env = registry.get("remat").env_of("off")
    assert env == {"MXTPU_BACKWARD_DO_MIRROR": registry.UNSET,
                   "MXTPU_REMAT_POLICY": registry.UNSET}
    assert registry.get("remat").env_of("dots") \
        == {"MXTPU_BACKWARD_DO_MIRROR": "1", "MXTPU_REMAT_POLICY": "dots"}


# ---------------------------------------------------------------------------
# DB
# ---------------------------------------------------------------------------

def test_db_key_stable_across_names_and_processes(tmp_path):
    """The DB key must survive both gluon's per-process name
    uniquification (name-independent graph fingerprint) and process
    boundaries (pure content hash): a FRESH interpreter computing the
    key for the same architecture resolves the same entry file.
    Also: auto-apply is OFF by default in a fresh process."""
    fp_a = tune.fingerprint_of(_net("one_"))
    fp_b = tune.fingerprint_of(_net("two_"))
    assert fp_a == fp_b
    key = tune.entry_key(fp_a, "cpu", "data=4x8")

    code = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "import mxtpu as mx\n"
        "from mxtpu import tune\n"
        "data = mx.sym.Variable('data')\n"
        "h = mx.sym.FullyConnected(data=data, num_hidden=8,"
        " name='zz_fc')\n"
        "h = mx.sym.Activation(data=h, act_type='relu', name='zz_act')\n"
        "net = mx.sym.SoftmaxOutput(data=h, name='zz_sm')\n"
        "print(json.dumps({'fp': tune.fingerprint_of(net),\n"
        "                  'key': tune.entry_key(tune.fingerprint_of(net),"
        " 'cpu', 'data=4x8'),\n"
        "                  'mode': tune.mode()}))\n" % REPO)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MXTPU_TUNE", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-1500:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["fp"] == fp_a
    assert got["key"] == key
    assert got["mode"] == "off"


def test_db_store_lookup_and_torn_entry(tmp_path):
    """Entries round-trip through the atomic writer; a torn/garbage
    entry file reads as a MISS (never an exception), and a rewrite
    heals it."""
    from mxtpu.tune import db as tdb

    d = str(tmp_path / "db")
    entry = tune.make_entry("g" * 64, "cpu", "data=4x8",
                            {"donate": "0"}, metric=10.0,
                            baseline_metric=12.0, trials=3)
    path = tune.store(entry, d)
    assert os.path.basename(path) == entry["key"] + ".json"
    got = tune.lookup("g" * 64, "cpu", "data=4x8", d)
    assert got["config"] == {"donate": "0"}
    assert got["baseline_metric"] == 12.0
    # different profile/backend -> different key -> miss
    assert tune.lookup("g" * 64, "cpu", "data=8x8", d) is None
    assert tune.lookup("g" * 64, "tpu", "data=4x8", d) is None
    # torn entry (truncated JSON) and garbage read as misses
    with open(path, "w") as f:
        f.write('{"schema": "mxtpu-tune-v1", "config": {"don')
    assert tune.lookup("g" * 64, "cpu", "data=4x8", d) is None
    assert tdb.entries(d) == []
    with open(path, "w") as f:
        f.write('{"schema": "wrong-schema", "config": {}}')
    assert tune.lookup("g" * 64, "cpu", "data=4x8", d) is None
    tune.store(entry, d)
    assert tune.lookup("g" * 64, "cpu", "data=4x8", d)["config"] \
        == {"donate": "0"}


# ---------------------------------------------------------------------------
# auto-apply
# ---------------------------------------------------------------------------

def test_auto_apply_off_by_default_and_applies_when_armed(tmp_path,
                                                          monkeypatch):
    """Off (the default): maybe_apply is a no-op even with a DB hit
    sitting there.  Armed: the entry's config lands in the env, the
    provenance string is exposed, and mx.inspect stamps it on program
    records built afterwards."""
    d = str(tmp_path / "db")
    monkeypatch.setenv("MXTPU_TUNE_DB", d)
    net = _net("ap_")
    fp = tune.fingerprint_of(net)
    profile = tune.profile_of_shapes([("data", (4, 8))])
    tune.store(tune.make_entry(fp, "cpu", profile,
                               {"donate": "1", "passes": "default"}))
    saved_mode = tune._MODE
    saved_applied = tune._APPLIED
    try:
        tune.enable("0")
        assert not tune.apply_enabled()
        assert tune.maybe_apply(symbol=net, profile=profile) is None

        tune.enable("apply")
        assert tune.mode() == "apply"
        prov = tune.maybe_apply(symbol=net, profile=profile,
                                site="test")
        assert prov is not None and "donate=1" in prov
        assert prov.startswith("tune:key=")
        assert tune.current_applied() == prov
        assert os.environ["MXTPU_DONATE"] == "1"

        # a real bind now stamps provenance on the program record
        mod = mx.mod.Module(_net("ap2_"), data_names=("data",),
                            label_names=("ap2_sm_label",))
        mod.bind(data_shapes=[("data", (4, 8))],
                 label_shapes=[("ap2_sm_label", (4,))])
        mod.init_params()
        import numpy as np
        mod.forward(mx.io.DataBatch(
            data=[mx.nd.array(np.zeros((4, 8), dtype="float32"))]),
            is_train=False)
        stamped = [p for p in mx.inspect.programs(analyze=False)
                   if p.get("tuning") == prov]
        assert stamped, "no program record carries %r" % prov
    finally:
        tune._MODE = saved_mode
        tune._APPLIED = saved_applied
        tune._APPLIED_KEYS.clear()
        os.environ.pop("MXTPU_DONATE", None)
        os.environ.pop("MXTPU_PASSES", None)


# ---------------------------------------------------------------------------
# search (rigged runner: no subprocesses, planted optimum)
# ---------------------------------------------------------------------------

class _RiggedRunner(object):
    """In-process stand-in for TrialRunner: score = f(config)."""

    def __init__(self, time_of):
        self.time_of = time_of
        self.trials = []
        self._n = 0

    def run(self, config):
        config = registry.validate_config(config)
        tid = "rig_t%03d" % self._n
        self._n += 1
        us = float(self.time_of(config))
        row = {"schema": "mxtpu-bench-v1", "step_time_us": us,
               "knobs": {}, "extra": {}}
        t = Trial(tid, config, row, tid, 0, 0.0)
        self.trials.append(t)
        return t


def test_search_picks_planted_fastest_knob():
    """The search loop must find the planted optimum of a rigged
    objective: steps_per_program='2' is 10x faster than everything
    else."""
    runner = _RiggedRunner(
        lambda c: 100.0 if c.get("steps_per_program") == "2"
        else 1000.0)
    res = tune.search(runner, knob_names=["steps_per_program"],
                      max_trials=8, epsilon=0.0, seed=1)
    assert res.config["steps_per_program"] == "2"
    assert res.score == 100.0
    assert res.baseline_score == 1000.0
    assert res.improved
    assert len(res.trials) <= 8
    assert res.run_ids == [t.run_id for t in runner.trials]


def test_search_never_worse_than_baseline():
    """When every candidate measures SLOWER than the baseline the
    returned config is the baseline itself (the check_tune contract)."""
    base = registry.defaults(["donate"])

    def rigged(c):
        return 100.0 if c == base else 50000.0

    runner = _RiggedRunner(rigged)
    res = tune.search(runner, knob_names=["donate"], max_trials=6,
                      epsilon=0.0, seed=0)
    assert res.config == base
    assert res.score == 100.0
    assert not res.improved


def test_search_failed_trials_score_inf():
    """A config that crashes the bench loses to every config that
    finishes."""
    t = Trial("t0", {"donate": "1"}, None, "t0", 2, 0.1, "boom")
    assert t.score == float("inf")
    assert not t.ok
    assert tune.objective(None) == float("inf")
    assert tune.objective({"step_time_us": 5.0}) == 5.0
    assert tune.objective({"throughput": 1000.0}) == 1000.0
    assert tune.objective({"value": 7.0}) == 7.0


def test_cost_model_priors_order_the_queue():
    """Phase attribution steers the ranking: an input-bound baseline
    pushes prefetch_device ahead; a dispatch-bound one pushes
    steps_per_program; memory-bound cost analysis boosts remat."""
    from mxtpu.tune.search import cost_model_priors

    inp = cost_model_priors({"phases": {"input_wait": 900.0,
                                        "device_compute": 100.0}})
    assert inp["prefetch_device"] > inp["steps_per_program"]
    disp = cost_model_priors({"phases": {"host_dispatch": 900.0,
                                         "input_wait": 10.0}})
    assert disp["steps_per_program"] > disp["prefetch_device"]
    mem = cost_model_priors(None, {"flops": 100.0,
                                   "bytes_accessed": 100.0})
    assert mem["remat"] > mem["donate"]


# ---------------------------------------------------------------------------
# trial runner (real subprocesses over a featherweight bench)
# ---------------------------------------------------------------------------

def _planted_bench(tmp_path):
    """A bench_common-speaking bench whose step time IS the
    steps_per_program env value x100 — pure python, no framework
    import, so each trial costs ~100ms."""
    script = tmp_path / "planted_bench.py"
    script.write_text(
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "import bench_common\n"
        "v = float(os.environ.get('MXTPU_STEPS_PER_PROGRAM', '8') or 8)\n"
        "bench_common.emit_result('rigged', 'planted_us', v * 100.0,"
        " 'us', step_time_us=v * 100.0)\n" % BENCH_DIR)
    return str(script)


def test_trial_runner_rows_carry_knob_env(tmp_path):
    """Every trial's harvested row records the knob env the trial ran
    under (MXTPU_* knobs + the trial id), so ledger rows are
    reproducible and attributable."""
    runner = tune.TrialRunner([sys.executable, _planted_bench(tmp_path)],
                              run_dir=str(tmp_path), timeout_s=60)
    t = runner.run({"steps_per_program": "2"})
    assert t.ok, t.error
    assert t.score == 200.0
    knobs = t.row["knobs"]
    assert knobs["MXTPU_STEPS_PER_PROGRAM"] == "2"
    assert knobs["MXTPU_TUNE_TRIAL"] == t.trial_id
    assert knobs["MXTPU_TUNE"] == "0"  # trials never recursively apply
    assert t.row["extra"]["tune_trial"] == t.trial_id
    assert t.trial_id.endswith("_t000")


def test_search_over_real_subprocess_trials(tmp_path):
    """End-to-end search over REAL subprocess trials finds the planted
    fastest value ('1' -> 100us vs default '8' -> 800us)."""
    runner = tune.TrialRunner([sys.executable, _planted_bench(tmp_path)],
                              run_dir=str(tmp_path), timeout_s=60)
    res = tune.search(runner, knob_names=["steps_per_program"],
                      max_trials=7, epsilon=0.0, seed=0)
    assert res.config["steps_per_program"] == "1"
    assert res.score == pytest.approx(100.0)
    assert res.baseline_score == pytest.approx(800.0)
    assert res.improved


def test_trial_timeout_kills_wedged_bench(tmp_path, monkeypatch):
    """MXTPU_TUNE_TRIAL_TIMEOUT (mx.checkpoint PR satellite): a
    wedged bench — here sleeping far past the budget, in its own
    process group with a child of its own — is killed as a group,
    scores inf, and ticks ``tune_trial_timeouts``.  A sane config must
    still beat it in the search ordering."""
    from mxtpu import profiler

    sleeper = tmp_path / "sleeping_bench.py"
    sleeper.write_text(
        "import subprocess, sys, time\n"
        "# a grandchild holding the stdout pipe open — the case a\n"
        "# bare child-kill leaks\n"
        "subprocess.Popen([sys.executable, '-c', 'import time; "
        "time.sleep(600)'])\n"
        "time.sleep(600)\n")
    monkeypatch.setenv("MXTPU_TUNE_TRIAL_TIMEOUT", "1.5")
    assert tune.trial.default_trial_timeout() == 1.5
    runner = tune.TrialRunner([sys.executable, str(sleeper)],
                              run_dir=str(tmp_path))
    assert runner.timeout_s == 1.5
    pre = profiler.get_stat("tune_trial_timeouts")
    t0 = time.perf_counter()
    t = runner.run({"steps_per_program": "2"})
    assert time.perf_counter() - t0 < 30
    assert not t.ok
    assert t.returncode == -9
    assert t.score == float("inf")
    assert "timed out" in (t.error or "")
    assert profiler.get_stat("tune_trial_timeouts") == pre + 1


def test_tune_refuses_a_parent_that_holds_the_chip(monkeypatch):
    """Trials are subprocesses and a chip belongs to one process at a
    time: a tuning session started from a process that already runs on
    the accelerator must fail loudly, before the first trial hangs."""
    import jax

    jax.devices()                       # this process has a backend up
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(MXNetError, match="one process at a time"):
        tune.tune([sys.executable, "-c", "pass"], name="m", max_trials=1,
                  store_db=False)
