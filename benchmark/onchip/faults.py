"""Faults: each breaks the timed path underneath the harness, which then
has to report ``correct`` false.  The tests under ``tests/`` plant them
at rehearsal sizes, ``run.py --fault <name>`` at a cell's own size on the
chip (``limits/<cell>.json`` records those verdicts).  The benchmark's
own runs plant none.

``state_unchanged``  every call of the loop runs its program and then
                     puts the state back as it was before the call: the
                     steps trained nothing;
``half_batch``       the second half of every batch the program is fed
                     repeats the first, so its mean (and BatchNorm's
                     statistics) are those of half the batch.
"""
import numpy as np


def plant(fault, driver):
    if fault == "half_batch":
        put = driver.put

        def put_half(stack):
            half = stack["data"].shape[1] // 2
            broken = {}
            for key, arr in stack.items():
                arr = np.array(arr)
                arr[:, half:2 * half] = arr[:, :half]
                broken[key] = arr
            return put(broken)

        driver.put = put_half
    elif fault == "state_unchanged":
        import jax

        call = driver.call

        def call_and_put_back(staged):
            kept = driver.snapshot()
            out = jax.block_until_ready(call(staged))
            driver.restore(kept)
            return out

        driver.call = call_and_put_back
    else:
        raise ValueError("unknown fault %r" % (fault,))
