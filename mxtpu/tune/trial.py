"""Measured trials: run a bench under a knob config, harvest its row.

A trial is one subprocess execution of a ``bench_common``-speaking
benchmark (any ``benchmark/python/bench_*.py`` seed, or
``tools/check_tune.py --bench``) with the candidate config carried in
via env vars.  The subprocess emits one ``mxtpu-bench-v1`` row — the
LAST JSON line on stdout, also appended to ``MXTPU_BENCH_OUT`` — and,
when the session arms ``MXTPU_RUN_DIR``, the row lands in a per-trial
`mx.obs` run ledger (``tune_<session>_t<NNN>.jsonl``), so
``tools/compare_runs.py`` and the live cluster view see tuning
history with zero extra plumbing.

Lower objective is better: ``step_time_us`` when the row carries it,
else inverse throughput, else the raw metric value (assumed to be a
latency-like unit).  Failed/timed-out trials score ``inf`` — a config
that crashes the bench loses to every config that finishes.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence

from . import registry

__all__ = ["Trial", "TrialRunner", "objective", "default_trial_timeout"]


def default_trial_timeout() -> float:
    """Per-trial wall budget in seconds: ``MXTPU_TUNE_TRIAL_TIMEOUT``
    (default 300).  A wedged bench — a deadlocked collective, a child
    waiting for a chip another process holds — is killed as a whole
    process group when the budget expires and the trial scores
    ``inf``."""
    try:
        return float(os.environ.get("MXTPU_TUNE_TRIAL_TIMEOUT", "300"))
    except ValueError:
        return 300.0


def objective(row: Optional[Dict[str, Any]]) -> float:
    """Scalar score of a bench row; LOWER IS BETTER; inf on failure."""
    if not row:
        return float("inf")
    st = row.get("step_time_us")
    if isinstance(st, (int, float)) and st > 0:
        return float(st)
    tp = row.get("throughput")
    if isinstance(tp, (int, float)) and tp > 0:
        return 1e6 / float(tp)
    val = row.get("value")
    if isinstance(val, (int, float)) and val > 0:
        return float(val)
    return float("inf")


class Trial(object):
    """Outcome of one measured run of a config."""

    __slots__ = ("trial_id", "config", "row", "score", "run_id",
                 "returncode", "elapsed_s", "error")

    def __init__(self, trial_id: str, config: Dict[str, str],
                 row: Optional[Dict[str, Any]], run_id: str,
                 returncode: int, elapsed_s: float,
                 error: Optional[str] = None):
        self.trial_id = trial_id
        self.config = dict(config)
        self.row = row
        self.score = objective(row) if returncode == 0 else float("inf")
        self.run_id = run_id
        self.returncode = returncode
        self.elapsed_s = elapsed_s
        self.error = error

    @property
    def ok(self) -> bool:
        return self.returncode == 0 and self.row is not None

    def as_dict(self) -> Dict[str, Any]:
        return {"trial_id": self.trial_id, "config": self.config,
                "score": self.score, "run_id": self.run_id,
                "returncode": self.returncode,
                "elapsed_s": self.elapsed_s, "error": self.error,
                "row": self.row}


def _last_json_line(text: str) -> Optional[Dict[str, Any]]:
    for line in reversed(text.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            return obj
    return None


class TrialRunner(object):
    """Executes configs as bench subprocesses and scores the rows.

    ``bench_argv`` is the full command of a bench that ends in ONE
    ``bench_common.emit_result`` call (e.g. ``[sys.executable,
    "benchmark/python/bench_train_loop.py", "--steps", "30"]``).
    Each trial's environment is the parent env overlaid with:

      * the candidate config's knob env vars (``UNSET`` values deleted),
      * ``MXTPU_BENCH_OUT`` -> a per-trial temp file (row harvest),
      * ``MXTPU_RUN_ID`` -> ``tune_<session>_t<NNN>`` (per-trial
        ledger file under ``run_dir`` when set),
      * ``MXTPU_TUNE=0`` — a trial must measure the EXPLICIT config,
        never recursively auto-apply a stale DB entry,
      * ``MXTPU_TUNE_TRIAL`` -> the trial id, which
        ``bench_common.row`` records among the knobs so ledger rows
        are attributable to their trial.
    """

    def __init__(self, bench_argv: Sequence[str],
                 run_dir: Optional[str] = None,
                 timeout_s: Optional[float] = None,
                 session: Optional[str] = None,
                 extra_env: Optional[Dict[str, str]] = None):
        self.bench_argv = list(bench_argv)
        self.run_dir = run_dir if run_dir is not None \
            else os.environ.get("MXTPU_RUN_DIR")
        self.timeout_s = float(timeout_s) if timeout_s is not None \
            else default_trial_timeout()
        self.session = session or ("%08x" % (int(time.time() * 1e3)
                                             & 0xFFFFFFFF))
        self.extra_env = dict(extra_env or {})
        self.trials: List[Trial] = []
        self._next_id = 0

    # -- env assembly -----------------------------------------------------
    def _trial_env(self, trial_id: str,
                   config: Dict[str, str],
                   bench_out: str) -> Dict[str, str]:
        env = dict(os.environ)
        env.update(self.extra_env)
        for k, v in registry.env_for_config(config).items():
            if v == registry.UNSET:
                env.pop(k, None)
            else:
                env[k] = v
        env["MXTPU_BENCH_OUT"] = bench_out
        env["MXTPU_TUNE"] = "0"
        env["MXTPU_TUNE_TRIAL"] = trial_id
        env["MXTPU_RUN_ID"] = trial_id
        if self.run_dir:
            env["MXTPU_RUN_DIR"] = self.run_dir
        return env

    # -- execution --------------------------------------------------------
    def run(self, config: Dict[str, str]) -> Trial:
        """Measure one config; records and returns the Trial."""
        config = registry.validate_config(config)
        trial_id = "tune_%s_t%03d" % (self.session, self._next_id)
        self._next_id += 1
        fd, bench_out = tempfile.mkstemp(prefix="mxtpu_trial_",
                                         suffix=".jsonl")
        os.close(fd)
        row = None
        error = None
        t0 = time.perf_counter()
        try:
            # own session/process group so a WEDGED bench (hung
            # collective, deadlocked child it spawned) is killable as a
            # unit — subprocess.run's timeout only signals the direct
            # child and then blocks draining pipes grandchildren hold
            proc = subprocess.Popen(
                self.bench_argv,
                env=self._trial_env(trial_id, config, bench_out),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                start_new_session=True)
            try:
                out, err = proc.communicate(timeout=self.timeout_s)
                rc = proc.returncode
            except subprocess.TimeoutExpired:
                self._kill_group(proc)
                out, err = proc.communicate()
                rc = -9
                error = "trial timed out after %.0fs" % self.timeout_s
                from .. import profiler as _prof

                _prof.inc_stat("tune_trial_timeouts")
            if error is None:
                if rc == 0:
                    row = self._harvest(bench_out, out)
                    if row is None:
                        rc = -1
                        error = "bench emitted no mxtpu-bench-v1 row"
                else:
                    tail = err.decode("utf-8", "replace")[-2000:]
                    error = "bench exited %d: %s" % (rc, tail)
        finally:
            try:
                os.unlink(bench_out)
            except OSError:
                pass
        trial = Trial(trial_id, config, row, trial_id, rc,
                      time.perf_counter() - t0, error)
        self.trials.append(trial)
        self._record(trial)
        return trial

    @staticmethod
    def _kill_group(proc: "subprocess.Popen") -> None:
        """SIGKILL the trial's whole process group (best effort)."""
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except (OSError, ProcessLookupError):
            try:
                proc.kill()
            except OSError:
                pass

    def _harvest(self, bench_out: str,
                 stdout: bytes) -> Optional[Dict[str, Any]]:
        """The trial's bench row: last row of the JSONL sink when the
        bench wrote one, else the last JSON stdout line."""
        try:
            with open(bench_out, "r", encoding="utf-8") as f:
                text = f.read()
        except OSError:
            text = ""
        row = _last_json_line(text)
        if row is None:
            row = _last_json_line(stdout.decode("utf-8", "replace"))
        if row is not None and row.get("schema") and \
                row.get("schema") != "mxtpu-bench-v1":
            return None
        return row

    def _record(self, trial: Trial) -> None:
        from .. import profiler as _prof
        from .. import telemetry as _tel

        _prof.inc_stat("tune_trials")
        if not trial.ok:
            _prof.inc_stat("tune_trial_failures")
        _tel.record("tuning", action="trial", trial=trial.trial_id,
                    score=trial.score, ok=trial.ok,
                    config=json.dumps(trial.config, sort_keys=True))

    # -- views ------------------------------------------------------------
    def best(self) -> Optional[Trial]:
        done = [t for t in self.trials if t.ok]
        if not done:
            return None
        return min(done, key=lambda t: t.score)

    def history(self) -> List[Dict[str, Any]]:
        return [t.as_dict() for t in self.trials]
