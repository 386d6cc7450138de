"""Environment diagnosis (reference `tools/diagnose.py`).

Prints platform, python, framework, accelerator, and build info for bug
reports.  The accelerator probe runs in a timeout-bounded subprocess,
so a device that cannot be reached must not hang the diagnosis itself.
This process never touches JAX devices: a chip belongs to one process
at a time, and the probe child needs it.

Usage: python tools/diagnose.py [--timeout 60]
"""
import argparse
import os
import platform
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def section(title):
    print("-" * 20)
    print(title)


def check_python():
    section("Python")
    print("version:", sys.version.replace("\n", " "))
    print("executable:", sys.executable)


def check_platform():
    section("Platform")
    print("system:", platform.platform())
    print("machine:", platform.machine())
    print("cpus:", os.cpu_count())


def check_deps():
    section("Dependencies")
    for mod in ("numpy", "jax", "jaxlib"):
        try:
            m = __import__(mod)
            print("%s: %s" % (mod, getattr(m, "__version__", "?")))
        except ImportError as e:
            print("%s: NOT AVAILABLE (%s)" % (mod, e))


def check_mxtpu():
    section("mxtpu")
    t0 = time.time()
    import mxtpu

    print("version:", getattr(mxtpu, "__version__", "dev"))
    print("location:", os.path.dirname(mxtpu.__file__))
    print("registered ops:", len(mxtpu.ops.list_ops()))
    print("import time: %.3fs" % (time.time() - t0))
    from mxtpu import _native

    lib = getattr(_native, "_LIB_PATH", None) or "not built"
    print("native runtime:", lib)


def check_accelerator(timeout):
    section("Accelerator")
    code = ("import jax, sys\n"
            "ds = jax.devices()\n"
            "print('devices:', ds)\n"
            "import jax.numpy as jnp\n"
            "jnp.ones((8, 8)).sum().block_until_ready()\n"
            "print('compute: ok')\n")
    try:
        t0 = time.time()
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout)
        out = (r.stdout + r.stderr).strip().splitlines()
        for line in out[-4:]:
            print(line)
        print("probe time: %.1fs rc=%d" % (time.time() - t0,
                                           r.returncode))
    except subprocess.TimeoutExpired:
        print("probe TIMED OUT after %ds — the chip is local here, so a "
              "hang at start-up most likely means another process "
              "holds it (one process per chip: look for a stale python "
              "with `ps`), else a runtime that cannot reach the device; "
              "a first compile alone takes seconds, not %ds"
              % (timeout, timeout))


def check_env():
    section("Environment variables")
    for k in sorted(os.environ):
        if k.startswith(("MXTPU_", "MXNET_", "JAX_", "XLA_", "DMLC_")):
            print("%s=%s" % (k, os.environ[k]))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--timeout", type=int, default=60,
                   help="accelerator probe timeout (seconds)")
    args = p.parse_args()
    check_python()
    check_platform()
    check_deps()
    check_env()
    check_mxtpu()
    check_accelerator(args.timeout)


if __name__ == "__main__":
    main()
