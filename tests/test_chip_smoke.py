"""chip_smoke.py's contracts that a CPU host can check: the rehearsal
drives every phase's control flow at tiny sizes, and without a TPU the
script fails fast, names the missing device and prints no result — a
CPU run must never pass for a chip run."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, devices=1, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d"
               % devices)
    return subprocess.run([sys.executable, os.path.join(REPO, script)]
                          + list(args), capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=REPO)


def test_rehearsal_passes_on_cpu():
    """--rehearse: phases 0-3 at tiny sizes, kernel in interpret mode.
    One host device, so phase 4 is skipped: what it drives on a CPU is
    already tier-1 (`test_kvstore`'s own-devices psum, `test_examples`'
    8-device kvstore=tpu run, the mesh loss-parity sweeps)."""
    r = _run("chip_smoke.py", "--rehearse")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL (cpu)")
    # the result line: exactly these keys, the device as JAX reports it
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert result["device"]["count"] == 1
    # the line before it: the per-phase set-up record, claiming nothing
    assert lines[-2].startswith("summary: ")
    rec = json.loads(lines[-2][len("summary: "):])
    assert rec["rehearsal"] is True
    assert set(rec["phases"]) == {"0_device", "1a_resnet_bind",
                                  "1_resnet_fused", "2_resnet_per_step",
                                  "3_lm_pallas"}
    assert list(rec)[-1] == "claim" and rec["claim"] is None


def test_no_tpu_fails_fast_and_names_it():
    r = _run("chip_smoke.py", timeout=120)
    assert r.returncode != 0
    assert "no TPU visible to JAX" in r.stderr, r.stderr[-1500:]
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]
