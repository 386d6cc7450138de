"""The arithmetic behind the per-layer metrics.  Each metric under
``metrics/`` is a reader of its own that names one of these; a reader
that finds nothing to read returns None and the metric is left out of
the result's line (never 0 for a share of a peak).

``run`` is what one traced run of a cell knows: ``cell``, ``steps`` and
``programs`` of the window as the host counted them (a metric whose
source is the device's trace takes its steps from the trace),
``elapsed_s``, host ``spans`` as (name, start, end), ``window`` (open,
close), ``window_compiles``, ``setup_compile_s``, ``trace``
(trace_reduce.reduce's record, or None), ``device`` (the result line's
record) and ``peaks``.
"""
import flops


def _span_ms_per_step(run, name):
    t0, t1 = run["window"]
    total = sum(e - s for n, s, e in run["spans"]
                if n == name and s >= t0 and e <= t1)
    return total * 1e3 / run["steps"] if run["steps"] else None


def input_wait_ms_per_step(run):
    return _span_ms_per_step(run, "bench:wait")


def host_call_ms_per_step(run):
    return _span_ms_per_step(run, "bench:call")


def _trace_steps(run):
    """Steps the device ran in the traced window, from the trace alone:
    runs of the program that took most of the device's time, times the
    steps one such program holds (the traffic file's)."""
    tr = run["trace"]
    if not tr or not tr.get("main"):
        return None
    return tr["main"]["runs"] * int(run["cell"].traffic["steps_per_program"])


def programs_per_step(run):
    steps = _trace_steps(run)
    return run["trace"]["programs"] / float(steps) if steps else None


def window_compiles(run):
    return float(run["window_compiles"])


def setup_compile_s(run):
    return run["setup_compile_s"]


def device_busy_ms_per_step(run):
    steps = _trace_steps(run)
    return run["trace"]["busy_s"] * 1e3 / steps if steps else None


def device_idle_share(run):
    tr = run["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu_pct(run):
    """The whole step's share of the chips' peak, from the trace alone:
    FLOPs the forward and backward passes need (the family's count) x
    the steps the device ran, over the extent from the first step
    program's start to the last one's end on the device's clock (not
    over the busy time), so it can never pass 100 and still bounds a
    gain once a kernel has left the path.  Nothing of the host's clock
    is in it."""
    import harness

    cell, steps = run["cell"], _trace_steps(run)
    if not steps or not run["peaks"] or not run["trace"]["main"]["extent_s"]:
        return None
    family = harness.load_module("families", cell.config["family"])
    need = family.train_step_flops(cell.config,
                                   int(cell.traffic["batch"])) * steps
    return 100.0 * need / (run["trace"]["main"]["extent_s"] * cell.chips
                           * run["peaks"]["flops_bf16"])


def flash_attn_roofline(run):
    """Least time the chip could take for the flash-attention calls the
    trace shows (the larger of FLOPs over peak and bytes over bandwidth,
    from their shapes, at causal work) over the time they took."""
    tr = run["trace"]
    if not tr or not tr["kernels"] or not run["peaks"]:
        return None
    least = took = 0.0
    for kind, k in tr["kernels"].items():
        bh, t, d = k["shape"]
        f, b = flops.attention_kernel(kind, bh, t, d)
        least += k["calls"] * max(f / run["peaks"]["flops_bf16"],
                                  b / run["peaks"]["hbm_bytes_per_s"])
        took += k["seconds"]
    return 100.0 * least / took if took else None


def hbm_peak_gib(run):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 2.0 ** 30 if peak else None
