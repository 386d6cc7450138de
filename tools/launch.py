#!/usr/bin/env python
"""Launcher for distributed KVStore jobs.

The analog of the reference's `tools/launch.py` → dmlc-tracker
(`tools/launch.py:71-111` drives ssh/mpi/sge/yarn): spawns 1 scheduler
+ S servers + W workers with the role environment set
(MXTPU_ROLE/MXTPU_PS_ROOT_URI/...), waits for the workers, then reaps
the rest.  Two launchers:

The local launcher is failure-honest: a nonzero child exit — worker,
server or scheduler — makes the launcher itself exit nonzero, so a
silently-dead role can never masquerade as success.  Elastic knobs:
``--restart-workers N`` respawns a dead worker up to N times (it
re-registers with the scheduler as a rejoin and resumes — see
`docs/elastic.md`); ``--allow-server-failures N`` tolerates N server
deaths when ``MXTPU_PS_REPLICATION=1`` failover is expected to absorb
them; ``--pid-dir DIR`` writes one ``<role>-<i>.pid`` file per child
so chaos harnesses (`tools/check_elastic.py`) can target a role.

A third mode, ``--serve-replicas N``, launches a SERVING fleet
instead of a PS training job: N identical role-``serve`` replicas of
the command, each with its own rank/port env
(``MXTPU_SERVE_RANK``/``MXTPU_SERVE_PORT``, fleet list in
``MXTPU_SERVE_PORTS``), failure-honest with an
``--allow-serve-failures`` chaos budget (see `docs/serving.md` and
`tools/check_serving.py`).

* ``local`` — all roles as local processes (development/tests);
* ``ssh``  — roles distributed round-robin over ``--hostfile`` hosts
  via passwordless ssh (the reference's ssh tracker): scheduler runs on
  the FIRST host, its address is broadcast through the role env, and
  `--sync-dst-dir` optionally rsyncs the working dir to each host
  first.  TPU-pod compute jobs use the coordination service
  (jax.distributed) instead — this bootstrap serves the PS/DCN path
  (dist_sync/dist_async kvstore).

Usage:  python tools/launch.py -n 2 [-s 1] python my_script.py args...
        python tools/launch.py -n 4 --launcher ssh -H hosts.txt \
               python train.py --kv-store dist_sync
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _arm_obs(base, tdir):
    """Arm the `mx.obs` live plane for the fleet: stamp ONE run id
    into every role (so a ``MXTPU_RUN_DIR`` ledger gets one file per
    run, all roles appending), and start the live-aggregation sidecar
    that scrapes each role's OpenMetrics endpoint and rewrites
    ``cluster_live.json`` DURING the run (`tools/dash.py` renders it).
    Returns the sidecar Popen or None.  The sidecar is a consumer
    only: telemetry + obs off, telemetry dir unset, so it never
    pollutes the directory it aggregates."""
    # EXACTLY base.getenv_bool's disabled spellings (the launcher
    # never imports the framework, so the rule is replicated): the
    # launcher and the roles must agree on whether the plane is off —
    # a divergent spelling would spawn an aggregator over roles that
    # never export, or roles that export with no aggregator/run id
    if base.get("MXTPU_OBS") in ("0", "false", "False", "FALSE"):
        return None
    base.setdefault("MXTPU_RUN_ID", "run%d" % int(time.time()))
    if not tdir:
        return None
    env = dict(base)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("MXTPU_TELEMETRY_DIR", None)
    env["MXTPU_TELEMETRY"] = "0"
    env["MXTPU_OBS"] = "0"
    try:
        return subprocess.Popen(
            [sys.executable, "-c",
             "import sys; from mxtpu import obs; "
             "raise SystemExit(obs.aggregator_main(sys.argv[1]))",
             tdir], env=env)
    except OSError as e:
        print("launch.py: obs aggregator failed to start: %s" % e,
              file=sys.stderr, flush=True)
        return None


def _stop_obs(agg):
    """Stop the aggregation sidecar (it writes one final pass)."""
    if agg is None:
        return
    try:
        agg.send_signal(signal.SIGTERM)
        agg.wait(timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        agg.kill()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, default=0)
    ap.add_argument("-s", "--num-servers", type=int, default=None)
    ap.add_argument("--launcher", choices=["local", "ssh"],
                    default="local")
    ap.add_argument("-H", "--hostfile", default=None,
                    help="one host per line (ssh launcher)")
    ap.add_argument("--sync-dst-dir", default=None,
                    help="rsync CWD to this dir on every host first")
    ap.add_argument("--restart-workers", type=int, default=0,
                    metavar="N",
                    help="respawn a dead (nonzero-exit) worker up to N "
                         "times total; it re-registers as an elastic "
                         "rejoin and resumes")
    ap.add_argument("--allow-server-failures", type=int, default=0,
                    metavar="N",
                    help="tolerate N nonzero server exits mid-run "
                         "(MXTPU_PS_REPLICATION failover absorbs them) "
                         "instead of failing the launch")
    ap.add_argument("--pid-dir", default=None,
                    help="write <role>-<i>.pid per child (chaos "
                         "harness hook)")
    ap.add_argument("--auto-resume", action="store_true",
                    help="fleet-level resume (docs/checkpoint.md): "
                         "before launching, scan the checkpoint dir "
                         "(MXTPU_CKPT_DIR, default MXTPU_RUN_DIR) for "
                         "the newest COMPLETE fleet checkpoint and "
                         "point every role at it via "
                         "MXTPU_CKPT_RESTORE; when the fleet FAILS "
                         "mid-run, kill the remainder, rescan, and "
                         "relaunch the WHOLE fleet from the newest "
                         "complete snapshot (up to "
                         "--max-fleet-restarts times)")
    ap.add_argument("--max-fleet-restarts", type=int, default=2,
                    metavar="N",
                    help="with --auto-resume: relaunch a failed fleet "
                         "at most N times (default 2) before giving "
                         "up with the last exit code")
    ap.add_argument("--serve-replicas", type=int, default=0,
                    metavar="N",
                    help="SERVING mode: spawn N replicas of the "
                         "command as role 'serve' (MXTPU_SERVE_RANK/"
                         "_PORT per replica, MXTPU_SERVE_PORTS = the "
                         "fleet) instead of a PS training job; see "
                         "docs/serving.md")
    ap.add_argument("--allow-serve-failures", type=int, default=0,
                    metavar="N",
                    help="tolerate N nonzero serve-replica exits "
                         "(client failover absorbs them — the chaos "
                         "contract tools/check_serving.py tests) "
                         "instead of failing the launch")
    ap.add_argument("--trace-sample", type=float, default=None,
                    metavar="RATE",
                    help="arm mx.tracing causal spans fleet-wide at "
                         "this head-sampling rate (sets "
                         "MXTPU_TRACE_SAMPLE in every role; 1 = every "
                         "request/step, 0 = off); merged spans land "
                         "in merged_trace.json + the cluster.json "
                         "tracing rollup — see docs/observability.md "
                         "§Tracing")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="unified telemetry (docs/observability.md): "
                         "every role dumps telemetry_<role><rank>.json "
                         "(and flight_* on crash/kill) into DIR, and "
                         "after the run the launcher merges them into "
                         "merged_trace.json (one chrome trace, clocks "
                         "aligned, mx.perf MFU/phase counter tracks) "
                         "+ cluster.json (per-rank step time, "
                         "straggler spread, counter totals, and the "
                         "mx.perf rollup: per-rank MFU + dominant "
                         "phase, worker MFU spread).  Also arms the "
                         "mx.obs LIVE plane: every role samples + "
                         "serves an OpenMetrics endpoint, a sidecar "
                         "rewrites cluster_live.json DURING the run "
                         "(tools/dash.py renders it), and "
                         "MXTPU_RUN_DIR appends a per-run ledger")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no command given")
    if args.serve_replicas > 0:
        return _launch_serve(args)
    if args.num_workers < 1:
        ap.error("need -n/--num-workers >= 1 (or --serve-replicas)")
    ns = args.num_servers if args.num_servers is not None else args.num_workers
    if args.launcher == "ssh":
        if not args.hostfile:
            ap.error("--launcher ssh requires -H/--hostfile")
        return _launch_ssh(args, ns)

    base = dict(os.environ)
    base.update({
        "MXTPU_PS_ROOT_URI": "127.0.0.1",
        "MXTPU_PS_ROOT_PORT": str(_free_port()),
        "MXTPU_NUM_WORKER": str(args.num_workers),
        "MXTPU_NUM_SERVER": str(ns),
    })
    if args.trace_sample is not None:
        base["MXTPU_TRACE_SAMPLE"] = repr(args.trace_sample)
    if args.pid_dir:
        os.makedirs(args.pid_dir, exist_ok=True)
    tdir = None
    if args.telemetry_dir:
        tdir = os.path.abspath(args.telemetry_dir)
        os.makedirs(tdir, exist_ok=True)
        base["MXTPU_TELEMETRY_DIR"] = tdir
    agg = _arm_obs(base, tdir)

    restarts_left = max(0, args.max_fleet_restarts) \
        if args.auto_resume else 0
    attempt = 0
    try:
        while True:
            if args.auto_resume:
                _arm_resume(base, attempt)
            rc = _run_fleet(args, ns, base)
            if rc == 0 or not args.auto_resume or restarts_left <= 0:
                break
            restarts_left -= 1
            attempt += 1
            print("launch.py: fleet failed (exit %d) — auto-resume "
                  "relaunch %d (%d restart(s) left)"
                  % (rc, attempt, restarts_left),
                  file=sys.stderr, flush=True)
            # a dead fleet can leave the old scheduler port in
            # TIME_WAIT / half-closed state — every relaunch gets a
            # fresh rendezvous port
            base["MXTPU_PS_ROOT_PORT"] = str(_free_port())
    finally:
        _stop_obs(agg)
    if args.telemetry_dir:
        _merge_telemetry(base, tdir)
    return rc


def _arm_resume(base, attempt):
    """Point the next fleet launch at the newest COMPLETE fleet
    checkpoint (or run fresh when none exists).  The scan runs in a
    framework child process — the launcher itself never imports mxtpu
    — and the decision lands as MXTPU_CKPT_RESTORE in every role's
    env plus one ``fleet_resume`` row in the run ledger."""
    ckpt_base = base.get("MXTPU_CKPT_DIR") or base.get("MXTPU_RUN_DIR")
    base.pop("MXTPU_CKPT_RESTORE", None)
    if not ckpt_base:
        return None
    env = dict(base)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("MXTPU_TELEMETRY_DIR", None)
    env["MXTPU_TELEMETRY"] = "0"
    env["MXTPU_OBS"] = "0"
    code = ("import sys, json\n"
            "from mxtpu import checkpoint as c\n"
            "r = c.find_resume(sys.argv[1])\n"
            "if r is not None:\n"
            "    print(json.dumps({'dir': r[0],\n"
            "                      'id': r[1].get('id'),\n"
            "                      'round': r[1].get('round')}))\n")
    try:
        r = subprocess.run([sys.executable, "-c", code, ckpt_base],
                           env=env, capture_output=True, text=True,
                           timeout=120)
        found = json.loads(r.stdout.strip()) if r.returncode == 0 \
            and r.stdout.strip() else None
    except (subprocess.TimeoutExpired, OSError, ValueError) as e:
        print("launch.py: auto-resume scan failed: %s" % e,
              file=sys.stderr, flush=True)
        return None
    row = {"event": "fleet_resume", "ts": time.time(),
           "attempt": attempt,
           "run": base.get("MXTPU_RUN_ID"),
           "ckpt_dir": found["dir"] if found else None,
           "ckpt_id": found["id"] if found else None,
           "round": found["round"] if found else None}
    if found:
        base["MXTPU_CKPT_RESTORE"] = found["dir"]
        print("launch.py: auto-resume from %s (id %s, round %s)"
              % (found["dir"], found["id"], found["round"]),
              file=sys.stderr, flush=True)
    else:
        print("launch.py: auto-resume armed, no complete fleet "
              "checkpoint under %s — starting fresh" % ckpt_base,
              file=sys.stderr, flush=True)
    run_dir = base.get("MXTPU_RUN_DIR")
    if run_dir and base.get("MXTPU_RUN_ID"):
        # same line-granularity jsonl the roles' obs ledger appends to
        try:
            os.makedirs(run_dir, exist_ok=True)
            with open(os.path.join(
                    run_dir, "%s.jsonl" % base["MXTPU_RUN_ID"]),
                    "a") as f:
                f.write(json.dumps(row) + "\n")
        except OSError:
            pass
    return found


def _run_fleet(args, ns, base):
    """ONE local fleet generation: spawn scheduler + servers +
    workers from ``base``, babysit to completion, reap.  Returns the
    fleet exit code (0 = all workers finished clean)."""
    procs = []

    def spawn(role, index, extra=None):
        env = dict(base)
        env["MXTPU_ROLE"] = role
        env.update(extra or {})
        if role in ("scheduler", "server"):
            # a chip belongs to one process at a time and these roles
            # only move host bytes: keep them off it, the workers'
            env["JAX_PLATFORMS"] = "cpu"
            cmd = [sys.executable, "-c",
                   "import mxtpu.kvstore_server as s; s.init_module()"]
        else:
            cmd = args.command
        p = subprocess.Popen(cmd, env=env)
        procs.append(p)
        if args.pid_dir:
            with open(os.path.join(args.pid_dir,
                                   "%s-%d.pid" % (role, index)), "w") as f:
                f.write(str(p.pid))
        return p

    infra = [("scheduler", spawn("scheduler", 0))]
    for i in range(ns):
        infra.append(("server", spawn("server", i)))
    workers = {}
    for i in range(args.num_workers):
        workers[i] = spawn("worker", i)

    rc = 0
    restarts_left = max(0, args.restart_workers)
    server_budget = max(0, args.allow_server_failures)
    infra_flagged = set()
    try:
        # poll loop instead of sequential wait(): it can respawn dead
        # workers (elastic restart) and catch SILENT scheduler/server
        # death while workers are still running — previously a dead
        # server could hang or fail the job with the launcher still
        # exiting 0
        while workers:
            time.sleep(0.2)
            for i, w in list(workers.items()):
                code = w.poll()
                if code is None:
                    continue
                del workers[i]
                if code == 0:
                    continue
                if restarts_left > 0:
                    restarts_left -= 1
                    print("launch.py: worker %d exited %d — respawning "
                          "(%d restart(s) left)" % (i, code,
                                                    restarts_left),
                          file=sys.stderr, flush=True)
                    workers[i] = spawn("worker", i)
                elif rc == 0:
                    rc = code if 0 < code < 256 else 1
            for role, p in infra:
                code = p.poll()
                if code in (None, 0) or p in infra_flagged:
                    continue
                infra_flagged.add(p)
                if role == "server" and server_budget > 0:
                    server_budget -= 1
                    print("launch.py: server died (exit %d) — tolerated "
                          "(%d allowed failure(s) left)"
                          % (code, server_budget),
                          file=sys.stderr, flush=True)
                elif rc == 0:
                    print("launch.py: %s died (exit %d) mid-run"
                          % (role, code), file=sys.stderr, flush=True)
                    rc = code if 0 < code < 256 else 1
    finally:
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    return rc


def _launch_serve(args):
    """SERVING launcher: N identical replicas of the command, each a
    role-``serve`` process with its own rank + port
    (``MXTPU_SERVE_RANK``/``MXTPU_SERVE_PORT``) and the whole fleet's
    port list in ``MXTPU_SERVE_PORTS`` — what a replica or a client
    needs to build the failover endpoint set.  Failure-honest like the
    PS launcher: a replica that dies nonzero fails the launch unless
    ``--allow-serve-failures`` budget absorbs it (the chaos harness
    SIGKILLs one on purpose).  SIGTERM to the launcher forwards to
    the replicas, which DRAIN and exit 0 (`mx.serve.serve_forever`)."""
    ports = [_free_port() for _ in range(args.serve_replicas)]
    base = dict(os.environ)
    base["MXTPU_SERVE_PORTS"] = ",".join(str(p) for p in ports)
    if args.trace_sample is not None:
        base["MXTPU_TRACE_SAMPLE"] = repr(args.trace_sample)
    if args.pid_dir:
        os.makedirs(args.pid_dir, exist_ok=True)
    tdir = None
    if args.telemetry_dir:
        tdir = os.path.abspath(args.telemetry_dir)
        os.makedirs(tdir, exist_ok=True)
        base["MXTPU_TELEMETRY_DIR"] = tdir
    agg = _arm_obs(base, tdir)

    procs = []
    for i in range(args.serve_replicas):
        env = dict(base)
        env["MXTPU_ROLE"] = "serve"
        env["MXTPU_SERVE_RANK"] = str(i)
        env["MXTPU_SERVE_PORT"] = str(ports[i])
        p = subprocess.Popen(args.command, env=env)
        procs.append(p)
        if args.pid_dir:
            with open(os.path.join(args.pid_dir,
                                   "serve-%d.pid" % i), "w") as f:
                f.write(str(p.pid))

    rc = 0
    budget = max(0, args.allow_serve_failures)

    # the docstring's contract: SIGTERM to the launcher forwards to
    # the replicas, which drain and exit 0.  Default disposition would
    # kill the launcher mid-wait WITHOUT running the finally below —
    # orphaned replicas, no telemetry merge.
    def _on_term(signum, frame):
        raise KeyboardInterrupt

    prev_term = signal.signal(signal.SIGTERM, _on_term)
    try:
        for p in procs:
            code = p.wait()
            if code == 0:
                continue
            if budget > 0:
                budget -= 1
                print("launch.py: serve replica died (exit %d) — "
                      "tolerated (%d allowed failure(s) left)"
                      % (code, budget), file=sys.stderr, flush=True)
            elif rc == 0:
                rc = code if 0 < code < 256 else 1
    except KeyboardInterrupt:
        print("launch.py: interrupted — draining serve replicas",
              file=sys.stderr, flush=True)
    finally:
        signal.signal(signal.SIGTERM, prev_term)
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        for p in procs:
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
        _stop_obs(agg)
    if args.telemetry_dir:
        _merge_telemetry(base, tdir)
    return rc


def _merge_telemetry(env, tdir):
    """Fold the per-role telemetry files into merged_trace.json +
    cluster.json (a child process: the launcher itself never imports
    the framework).  Diagnostics must not fail a finished launch —
    a merge failure is reported, not propagated."""
    env = dict(env)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # the merge helper must not be a telemetry OR obs PRODUCER: with
    # the dir armed its own atexit flush would drop a
    # telemetry_local0.json into the directory it just merged (and an
    # armed obs plane would append bogus local0 rows to the run
    # ledger), polluting later re-merges and run diffs
    env.pop("MXTPU_TELEMETRY_DIR", None)
    env["MXTPU_TELEMETRY"] = "0"
    env["MXTPU_OBS"] = "0"
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import sys; from mxtpu import telemetry; "
             "telemetry.merge_dir(sys.argv[1])", tdir],
            env=env, capture_output=True, text=True, timeout=120)
    except (subprocess.TimeoutExpired, OSError) as e:
        print("launch.py: telemetry merge failed: %s" % e,
              file=sys.stderr, flush=True)
        return
    if r.returncode != 0:
        print("launch.py: telemetry merge failed:\n%s" % r.stderr,
              file=sys.stderr, flush=True)
    else:
        print("launch.py: telemetry merged -> %s" %
              os.path.join(tdir, "merged_trace.json"),
              file=sys.stderr, flush=True)


def _launch_ssh(args, ns):
    """ssh launcher (reference dmlc-tracker ssh.py role): round-robin
    role placement over the hostfile, env passed on the remote command
    line, scheduler bound on the first host's address."""
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()
                 and not h.startswith("#")]
    if not hosts:
        raise SystemExit("empty hostfile %s" % args.hostfile)
    root = hosts[0]
    # NOTE: the port is probed on the LOCAL machine; the scheduler
    # binds it on hosts[0].  Collisions there surface as a scheduler
    # bind failure — pin MXTPU_PS_ROOT_PORT in the environment to
    # choose explicitly.
    root_port = int(os.environ.get("MXTPU_PS_ROOT_PORT", 0)) or \
        _free_port()
    cwd = args.sync_dst_dir or os.getcwd()

    if args.sync_dst_dir:
        for h in set(hosts):
            subprocess.run(["rsync", "-az", "--exclude", ".git",
                            os.getcwd() + "/",
                            "%s:%s/" % (h, args.sync_dst_dir)],
                           check=True)

    base_env = {
        "MXTPU_PS_ROOT_URI": root,
        "MXTPU_PS_ROOT_PORT": str(root_port),
        "MXTPU_NUM_WORKER": str(args.num_workers),
        "MXTPU_NUM_SERVER": str(ns),
    }
    # pass through the caller's python-visible config
    for k, v in os.environ.items():
        if (k == "PYTHONPATH" or
                k.startswith(("MXTPU_", "JAX_", "XLA_"))) and \
                k not in base_env:
            base_env[k] = v

    procs = []

    def spawn(role, host):
        env = dict(base_env)
        env["MXTPU_ROLE"] = role
        if role in ("scheduler", "server"):
            inner = ("%s -c 'import mxtpu.kvstore_server as s; "
                     "s.init_module()'" % sys.executable)
        else:
            import shlex

            inner = " ".join(shlex.quote(c) for c in args.command)
        import shlex

        envstr = " ".join("%s=%s" % (k, shlex.quote(v))
                          for k, v in sorted(env.items()))
        remote = "cd %s && env %s %s" % (shlex.quote(cwd), envstr, inner)
        # -tt forces a tty so dropping the ssh client (our SIGTERM on
        # cleanup) HUPs and kills the remote role instead of leaking it
        procs.append(subprocess.Popen(
            ["ssh", "-tt", "-o", "StrictHostKeyChecking=no", host,
             remote], stdin=subprocess.DEVNULL))

    spawn("scheduler", root)
    workers = []
    for i in range(ns):
        spawn("server", hosts[i % len(hosts)])
    for i in range(args.num_workers):
        spawn("worker", hosts[i % len(hosts)])
        workers.append(procs[-1])

    rc = 0
    try:
        for w in workers:
            code = w.wait()
            if code != 0 and rc == 0:
                rc = code if 0 < code < 256 else 1
    finally:
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
