"""Test configuration.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §4: the reference's
CPU-vs-GPU consistency + single-host multi-device kvstore tests map to a
forced-CPU multi-device JAX platform here).  Must run before jax init.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# The persistent compile cache (mxtpu/compile_cache.py) is on for the
# suite as for every run, and child processes inherit its place through
# the environment, so the many tests that spawn subprocesses
# re-compiling the same tiny programs read them back instead.  Where
# JAX_COMPILATION_CACHE_DIR is set, that directory is used.  Where it
# is not, each suite run starts with an EMPTY directory of its own
# (removed at exit) rather than <checkout>/.jax_cache: entries from an
# earlier run are safe to load on jax 0.9.0 (a stale or torn entry is a
# warned miss, not the heap corruption of jaxlib 0.4.37), but XLA:CPU
# 0.9.0 logs ~10 KB of error-level noise per entry it loads
# (cpu_aot_loader.cc, "+prefer-no-scatter"), and with everything warm a
# child that logs into a pipe nobody drains yet blocks before it is
# ready (test_sigterm_drains_replica did).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import atexit
    import shutil
    import tempfile

    _cache_dir = tempfile.mkdtemp(prefix="mxtpu_test_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    atexit.register(shutil.rmtree, _cache_dir, True)
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-process guards excluded from tier-1 "
        "(-m 'not slow'), e.g. the full elastic chaos gauntlet")


@pytest.fixture(autouse=True)
def _seed_everything(request):
    """Per-test deterministic seeding (reference:
    `tests/python/unittest/common.py:113-169` with_seed())."""
    seed = int(os.environ.get("MXTPU_TEST_SEED",
                              os.environ.get("MXNET_TEST_SEED", "0")) or 0)
    if seed == 0:
        seed = abs(hash(request.node.nodeid)) % (2 ** 31 - 1)
    np.random.seed(seed)
    import random as _pyrandom

    _pyrandom.seed(seed)   # stdlib random: image augmenters draw here
    import mxtpu

    mxtpu.random.seed(seed)
    yield
