"""Comparison ``train_first_steps_hybrid``: ``train_first_steps_moe``
(the optimizer's first moment, the parameters' change and the first
step's routed pairs against the plain reference) with the loss held at
the FIRST step only:

``loss_gap_first``  the relative gap between the program's loss and the
                reference's at the first step, where both sides hold the
                same weights: the forward pass alone, in the stated
                precision against float32.

``loss_gap`` (the widest gap over all the followed steps) stays in the
record and is held to nothing in a cell that names this comparison.  In
``ling3fvl_ep64_fused_k4`` a held expert sees ~64 tokens a step and Adam
moves the router by whole selections between steps, so from the second
step on the loss follows which experts were trained, not the precision:
on the chip the program reads 3.5e-4 to 1.1e-3, the reference in
bfloat16 1.1e-4 and 1.1e-3, and the reference in int8 4.3e-4 and 8.4e-4
(PERF.md, PR 34): no limit stands between them.  The per-step gaps are
in ``worst_at`` (``loss_gaps``).
"""
import harness

_moe = harness.load_module("comparisons", "train_first_steps_moe")
follow, settle = _moe.follow, _moe.settle


def numbers(observed, reference):
    out, where = _moe.numbers(observed, reference)
    gaps = [abs(a - b) / max(abs(b), 1e-30)
            for a, b in zip(observed["losses"], reference["losses"])]
    out["loss_gap_first"] = gaps[0]
    where["loss_gaps"] = gaps
    return out, where


def check(cell, ref, observed, ring, seed):
    start = ref.init_params(cell.config, seed)
    return numbers(settle(ref, observed, start),
                   follow(cell, ref, ring, seed, start=start))
