"""Comparison ``train_first_steps_moe``: ``train_first_steps`` (losses,
the optimizer's first moment and the parameters' change against the
plain reference) and one number more for a cell with routed experts:

``moe_pairs_gap``  at the first step, where both sides hold the same
                weights: the relative gap between the token-expert pairs
                the program's router put in the range of experts held
                here (its ``moe_pairs`` counter, over every expert layer
                of the step) and the pairs the plain reference counted
                for itself, in float32.  A selection that flips between
                bfloat16 and float32 activations moves it by flips into
                the range less flips out of it (3e-3 at most of ~20 000
                pairs on the chip); a router, a selection or a held
                range that is not the reference's moves it by a share of
                the pairs.

The later steps' pairs are in the record (``worst_at``) and are held to
nothing: once Adam has moved the router the count follows the rounding
(PERF.md, PR 30: program 30958 against the reference's 29120 at the
fourth step of one seed in thirteen, 6%, where the int8 control reads 4
to 10%).

It holds the ROUTING to the reference.  That every pair routed here was
also COMPUTED is not a count anyone can take from outside the grouped
product (the rows it was given are the pairs by construction): a pair
left out, or a row the product did not write, shows in ``loss_gap``,
``moment_gap`` and ``delta_gap`` (PERF.md, PR 30: 1.2e9 and 0.19 when
the TPU's grouped product left rows unwritten).
"""
import harness

_base = harness.load_module("comparisons", "train_first_steps")
follow, settle = _base.follow, _base.settle


def numbers(observed, reference):
    out, where = _base.numbers(observed, reference)
    got, want = observed["moe_pairs"], reference["moe_pairs"]
    if len(got) != len(want):
        raise ValueError("pairs of %d steps observed, of %d in the "
                         "reference" % (len(got), len(want)))
    out["moe_pairs_gap"] = abs(got[0] - want[0]) / max(want[0], 1.0)
    where["moe_pairs"] = {"program": list(got), "reference": list(want)}
    return out, where


def check(cell, ref, observed, ring, seed):
    start = ref.init_params(cell.config, seed)
    return numbers(settle(ref, observed, start),
                   follow(cell, ref, ring, seed, start=start))
