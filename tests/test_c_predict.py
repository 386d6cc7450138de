"""C predict ABI end-to-end: a real C program links
libmxtpu_predict.so, loads a checkpoint, and must reproduce the Python
executor's outputs (reference `include/mxnet/c_predict_api.h` +
`example/image-classification/predict-cpp`)."""
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, sym

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "src", "build", "libmxtpu_predict.so")


def _build_lib():
    if not os.path.exists(LIB):
        subprocess.run(["make", "-C", os.path.join(REPO, "src")],
                       capture_output=True, text=True)
    return os.path.exists(LIB)


pytestmark = pytest.mark.skipif(
    not (shutil.which("gcc") and _build_lib()),
    reason="gcc or libmxtpu_predict.so unavailable")


def test_c_predict_matches_python(tmp_path):
    # a small MLP checkpoint
    data = sym.Variable("data")
    x = sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    x = sym.Activation(data=x, act_type="relu")
    x = sym.FullyConnected(data=x, num_hidden=4, name="fc2")
    out = sym.softmax(data=x, name="prob")

    rng = np.random.RandomState(0)
    args = {"fc1_weight": nd.array(rng.randn(16, 10).astype(np.float32)),
            "fc1_bias": nd.array(rng.randn(16).astype(np.float32)),
            "fc2_weight": nd.array(rng.randn(4, 16).astype(np.float32)),
            "fc2_bias": nd.array(rng.randn(4).astype(np.float32))}
    prefix = str(tmp_path / "model")
    mx.model.save_checkpoint(prefix, 1, out, args, {})

    xin = rng.rand(3, 10).astype(np.float32)
    with open(tmp_path / "input.bin", "wb") as f:
        f.write(xin.tobytes())

    # python-side gold through the same executor
    exe = out.simple_bind(ctx=mx.cpu(), grad_req="null", data=(3, 10))
    for k, v in args.items():
        v.copyto(exe.arg_dict[k])
    gold = exe.forward(is_train=False, data=nd.array(xin))[0].asnumpy()

    # compile + run the C consumer
    exe_path = str(tmp_path / "c_predict_test")
    cc = subprocess.run(
        ["gcc", os.path.join(REPO, "tests", "c_predict_test.c"),
         "-o", exe_path, "-L", os.path.dirname(LIB),
         "-Wl,-rpath," + os.path.dirname(LIB), "-lmxtpu_predict"],
        capture_output=True, text=True)
    assert cc.returncode == 0, cc.stderr

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [exe_path, prefix + "-symbol.json", prefix + "-0001.params",
         str(tmp_path / "input.bin"), "3"],
        capture_output=True, text=True, timeout=240, env=env)
    assert res.returncode == 0, res.stdout + res.stderr

    shape_m = re.search(r"shape:((?: \d+)+)", res.stdout)
    data_m = re.search(r"data:((?: -?[\d.]+(?:e-?\d+)?)+)", res.stdout)
    assert shape_m and data_m, res.stdout
    shape = tuple(int(t) for t in shape_m.group(1).split())
    vals = np.array([float(t) for t in data_m.group(1).split()],
                    np.float32).reshape(shape)
    assert shape == gold.shape
    np.testing.assert_allclose(vals, gold, rtol=1e-4, atol=1e-5)
