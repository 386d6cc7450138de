"""Flat C ABI end-to-end: a real C program links libmxtpu_c.so and
exercises every function group — runtime, op list + imperative invoke,
NDArray create/copy/save/load, KVStore init/push/pull, CSVIter
(reference `include/mxnet/c_api.h`; the MXTPU analog is the core tier
documented in README.md §C API)."""
import os
import shutil
import subprocess

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "src", "build", "libmxtpu_c.so")


def _build_lib():
    if not os.path.exists(LIB):
        subprocess.run(["make", "-C", os.path.join(REPO, "src"), "capi"],
                       capture_output=True, text=True)
    return os.path.exists(LIB)


pytestmark = pytest.mark.skipif(
    not (shutil.which("gcc") and _build_lib()),
    reason="gcc or libmxtpu_c.so unavailable")


def test_c_api_all_groups(tmp_path):
    csv = tmp_path / "data.csv"
    rows = np.arange(12, dtype=np.float32).reshape(4, 3)
    np.savetxt(csv, rows, delimiter=",", fmt="%.1f")

    # symbol json for the symexec group
    from mxtpu import sym

    d = sym.Variable("data")
    fc = sym.FullyConnected(data=d, num_hidden=3, name="fc")
    sym_json = tmp_path / "fc.json"
    sym_json.write_text(fc.tojson())

    exe_path = str(tmp_path / "c_api_test")
    cc = subprocess.run(
        ["gcc", os.path.join(REPO, "tests", "c_api_test.c"),
         "-o", exe_path, "-L", os.path.dirname(LIB),
         "-Wl,-rpath," + os.path.dirname(LIB), "-lmxtpu_c"],
        capture_output=True, text=True)
    assert cc.returncode == 0, cc.stderr

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    profile_json = tmp_path / "profile.json"
    res = subprocess.run(
        [exe_path, str(csv), str(tmp_path / "weights.params"),
         str(sym_json), str(profile_json)],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    for group in ("runtime", "oplist", "ndarray", "invoke", "saveload",
                  "kvstore", "dataiter", "autograd", "symexec",
                  "profiler", "ndarray-views", "recordio",
                  "widening-misc", "widening-iter-gradex", "kv-updater", "ps-env"):
        assert ("group:%s ok" % group) in res.stdout, res.stdout
    assert "ALL-GROUPS-OK" in res.stdout, res.stdout
    assert profile_json.exists()  # chrome trace landed at the argv path
