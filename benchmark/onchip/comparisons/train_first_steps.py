"""Comparison ``train_first_steps``: what decides ``correct`` for a
training cell.

Both sides give the same record of the first steps that the window's own
call made: every step's loss, the per-leaf norm of the optimizer's first
moment (after one step that is the first gradient as the optimizer got
it) and the per-leaf norm of the parameters' change.  Three numbers come
out, each held to a limit of its own:

``loss_gap``    the widest relative gap between a step's loss and the
                reference's;
``moment_gap``  by the worst leaf, the gap between the program's norm and
                the reference's (NOT the norm of their difference),
                measured against the reference's norm of that leaf or of
                the median leaf, whichever is larger;
``delta_gap``   the same for the parameters' change.

``moment_gap_median`` / ``delta_gap_median`` (the median leaf's gap where
the others take the worst leaf's) are the steadier readings of the same
records; a cell's limits file says which of the numbers it holds and why.

The program's record (a driver's ``observe``) holds the losses, the
moment's per-leaf norms, reduced on the device to scalars, and the
weights themselves as HOST arrays: their change is worked out here, once
the window has closed and the memory has been read, so that the check
puts no copy of the model on the device while the program is timed.

Leaves whose reference moment is under a thousandth of the median leaf's
(a bias that the BatchNorm after it cancels) have a gradient that is
nought to rounding: what a bf16 backward pass puts there is round-off,
and they move by round-off alone.  They are left out of both leaf
numbers, by that rule and not by name; ``nought`` in the record counts
them and gives the program's largest norm there against the median.
"""
import statistics

import jax
import jax.numpy as jnp

import traffic

NOUGHT = 1e-3       # of the median leaf's reference moment


def _worst_leaf(got, want, skip=()):
    names = [k for k in want if k not in skip]
    missing = [k for k in names if k not in got]
    if missing:
        raise KeyError("the program's record lacks leaves %s" % missing[:5])
    floor = statistics.median(want[k] for k in names)
    worst, where, gaps = 0.0, None, []
    for k in names:
        gap = abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
        gaps.append(gap)
        if not gap <= worst:            # catches NaN too
            worst, where = gap, k
    middle = statistics.median(gaps) if worst == worst else float("nan")
    return worst, where, middle


def numbers(observed, reference):
    """{number: value} plus {number: leaf or step it was worst at}."""
    if len(observed["losses"]) != len(reference["losses"]):
        raise ValueError("%d losses observed, %d in the reference"
                         % (len(observed["losses"]),
                            len(reference["losses"])))
    out, where = {}, {}
    gaps = [abs(a - b) / max(abs(b), 1e-30)
            for a, b in zip(observed["losses"], reference["losses"])]
    bad = [i for i, g in enumerate(gaps) if g != g]
    out["loss_gap"] = float("nan") if bad else max(gaps)
    where["loss_gap"] = "step %d" % (bad[0] if bad
                                     else gaps.index(out["loss_gap"]))
    med = statistics.median(reference["moment_norms"].values())
    nought = [k for k, v in reference["moment_norms"].items()
              if v < NOUGHT * med]
    for name in ("moment", "delta"):
        worst, leaf, middle = _worst_leaf(
            observed[name + "_norms"], reference[name + "_norms"],
            skip=nought)
        out[name + "_gap"], where[name + "_gap"] = worst, leaf
        out[name + "_gap_median"] = middle
    where["nought"] = {"leaves": len(nought), "program_moment_over_median":
                       max([observed["moment_norms"][k] for k in nought]
                           or [0.0]) / max(med, 1e-30)}
    return out, where


def follow(cell, ref, ring, seed, start=None, mode="f32", fault=None):
    """The plain reference's record of the first steps the stager serves
    from ``ring``; ``mode`` and ``fault`` are the control's and the
    planted fault's (calibrate.py, tests)."""
    if start is None:
        start = ref.init_params(cell.config, seed)
    return ref.train(
        cell.config, cell.config["optimizer"], start,
        traffic.step_feed(ring, int(cell.traffic["steps_per_program"])),
        int(cell.traffic["follow_steps"]), int(cell.traffic["moment_step"]),
        mode=mode, fault=fault)


@jax.jit
def _sub(a, b):
    return {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
            for k in b}


def settle(ref, observed, start):
    """The program's record with its weights (host arrays) turned into
    the per-leaf norms of their change from ``start``."""
    seen = dict(observed)
    seen["delta_norms"] = ref.leaf_norms(_sub(seen.pop("weights"), start))
    return seen


def check(cell, ref, observed, ring, seed):
    """(numbers, where) of the program's record against the reference
    run from the same seed.  Runs after the window, with the program's
    state released."""
    start = ref.init_params(cell.config, seed)
    return numbers(settle(ref, observed, start),
                   follow(cell, ref, ring, seed, start=start))
