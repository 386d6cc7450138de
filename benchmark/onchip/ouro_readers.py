"""Readers of the per-layer metrics that the ``ouro26b_l8_fused_k4`` cell
adds.  Each returns None where it finds nothing to read (a program
without the counters), and the metric is then left out of the result's
line.

The counters are the program's (``mx.profiler`` stats, published by the
driver from the [K] arrays every program returns beside its losses,
read after the first program and at the window's open and close; what
the readers see is the first program and the whole window):
``loop_passes`` (passes run, summed over the steps), ``loop_tokens``
(tokens), ``loop_exit_expect`` (over the tokens, the pass each is
expected to exit at under the exit distribution) and ``loop_exit_last``
(the mass left to the last pass).
"""
from moe_readers import _stats


def loop_passes_per_step(run):
    """The passes the stack ran in a step: the steps are the tokens over
    a step's tokens.  The configuration's ``total_ut_steps``, 4."""
    s, cell = _stats(), run["cell"]
    if not s.get("loop_tokens") or "loop_passes" not in s:
        return None
    per_step = int(cell.traffic["batch"]) * cell.config["input"]["length"]
    return s["loop_passes"] / (s["loop_tokens"] / float(per_step))


def loop_exit_pass_mean(run):
    """The pass a token is expected to exit at, over the tokens: sum_t t
    p_t under the exit distribution, in 1 .. ``total_ut_steps`` (2.5 for
    an even spread over four passes, 4 with all the mass on the last)."""
    s = _stats()
    if not s.get("loop_tokens") or "loop_exit_expect" not in s:
        return None
    return s["loop_exit_expect"] / float(s["loop_tokens"])
