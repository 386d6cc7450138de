"""Readers of the per-layer metrics that a cell with routed experts
adds.  Each returns None where it finds nothing to read (a program
without the counters, a run without a device trace), and the metric is
then left out of the result's line.

The routed experts' counters are the program's: ``mx.profiler`` stats
``moe_pairs`` (token-expert pairs routed to the experts held here),
``moe_tokens`` (tokens x expert layers routed) and ``moe_load_max`` (the
fullest held expert's pairs in one layer of one step), which the driver
publishes from the arrays EVERY program returns beside its losses, read
after the first program and at the window's open and close: what the
readers see is the first program and the whole window.  The fourth,
``moe_rows_walked``, is the rows the dispatch walked (blocks x the rows
of a block, over the expert layers).
"""
import harness
import readers


def _stats():
    from mxtpu import profiler

    return profiler.stats()


def moe_pairs_per_token(run):
    """Token-expert pairs computed here per token routed: with uniform
    routing ``num_experts_per_tok * experts_held / n_routed_experts``."""
    s = _stats()
    if not s.get("moe_tokens"):
        return None
    return s["moe_pairs"] / float(s["moe_tokens"])


def moe_blocks_per_layer_step(run):
    """Blocks of the dispatch's walk per expert layer and step: the rows
    walked over the rows of a block (the program's own rule,
    ``_dispatch_block``), over the layer-steps routed.  A layer whose
    held experts got no pair in a step walks none, so under 1 is the
    share of layer-steps that held a pair at all; the cell's rate is a
    line in this number."""
    from mxtpu.parallel import transformer as tf

    s = _stats()
    cell = run["cell"]
    if not s.get("moe_tokens") or "moe_rows_walked" not in s:
        return None
    n_tok = int(cell.traffic["batch"]) * cell.config["input"]["length"]
    cfg = harness.load_module(
        "drivers", cell.traffic["driver"]).transformer_config(cell.config)
    _, block = tf._dispatch_block(cfg, n_tok)
    return s["moe_rows_walked"] / float(block) \
        / (s["moe_tokens"] / float(n_tok))


def mfu_pct(run):
    """``readers.mfu_pct`` with the routed experts' products counted at
    the pairs the program computed (``moe_pairs_per_token``) and not at
    the half a pair a token that uniform routing would give: the routed
    share follows the seed, and it drifts as the router trains."""
    cell, steps = run["cell"], readers._trace_steps(run)
    pairs = moe_pairs_per_token(run)
    if not steps or not run["peaks"] or pairs is None \
            or not run["trace"]["main"]["extent_s"]:
        return None
    family = harness.load_module("families", cell.config["family"])
    need = family.train_step_flops(cell.config, int(cell.traffic["batch"]),
                                   pairs_per_token=pairs) * steps
    return 100.0 * need / (run["trace"]["main"]["extent_s"] * cell.chips
                           * run["peaks"]["flops_bf16"])


def moe_load_max_over_mean(run):
    """The fullest held expert's pairs in one layer of one step, over
    the mean of a held expert's pairs per layer and step (1 is even)."""
    s = _stats()
    c = run["cell"].config
    if not s.get("moe_pairs") or not c.get("experts_held"):
        return None
    per_layer_step = int(run["cell"].traffic["batch"]) \
        * c["input"]["length"]
    mean = s["moe_pairs"] / (s["moe_tokens"] / float(per_layer_step)
                             * c["experts_held"])
    return s["moe_load_max"] / mean


def flash_attn_roofline(run):
    """``readers.flash_attn_roofline`` for a program that also runs
    XLA's grouped products.  On the TPU ``jax.lax.ragged_dot`` is itself
    a ``tpu_custom_call`` that returns ONE array, which
    ``trace_reduce.pallas_kind`` (it tells kernels by what they return)
    counts under ``dq``.  The forward kernel (two arrays, the second
    float32) and dk/dv (two arrays) are told apart soundly; dq's time is
    taken from the trace's op table under the kernel's own name
    (``mx_flash_dq``), its calls are dk/dv's (one of each per attention
    call) and its shape the forward kernel's; the arithmetic is the
    accepted reader's.  None where the op table's top rows do not hold
    ``mx_flash_dq``."""
    tr = run["trace"]
    k = (tr or {}).get("kernels") or {}
    took_dq = dict((tr or {}).get("device_ops") or ()).get("mx_flash_dq")
    if "fwd" not in k or "dkv" not in k or not took_dq:
        return None
    kernels = {"fwd": k["fwd"], "dkv": k["dkv"],
               "dq": {"seconds": took_dq, "calls": k["dkv"]["calls"],
                      "shape": k["fwd"]["shape"]}}
    return readers.flash_attn_roofline(
        dict(run, trace=dict(tr, kernels=kernels)))
