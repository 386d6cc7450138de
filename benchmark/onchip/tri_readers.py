"""Readers of the per-layer metrics that the ``trinitym_ep16_fused_k4``
cell adds.  Each returns None where it finds nothing to read (a program
without the stat, a run without a device trace), and the metric is then
left out of the result's line.

``flash_tiles_total`` / ``flash_tiles_visited`` are the program's
trace-time stats (``mx.profiler``): per traced kernel call and head, the
score matrix's 128 x 128 tiles and those the kernels compute.
"""
import harness
from moe_readers import _stats


def flash_tiles_visited_share(run):
    """Of the score tiles of every traced flash kernel call, the share
    the kernels compute, in percent.  At T = 8192 under a window of 2048
    the band's least is 28.75 over four window layers and one full; a
    causal walk that computed the band's tiles and masked them reads
    50.8."""
    s = _stats()
    if not s.get("flash_tiles_total") or "flash_tiles_visited" not in s:
        return None
    return 100.0 * s["flash_tiles_visited"] / float(s["flash_tiles_total"])


def flash_attn_roofline(run):
    """Least time the chip could take for the work the flash kernels'
    calls NEED (the family's ``flash_kernel_useful``: each held layer's
    own pairs, q at 32 heads and k, v at 4), over the time the three
    kernels took.  Window and full layers' calls share a kernel's name,
    so a kernel's least is its calls over the held layers times the sum
    of the layers' least times.  The forward and dk/dv are told by what
    they return (``trace_reduce.pallas_kind``); dq's time is the op
    table's under ``mx_flash_dq`` (XLA's grouped products return one
    array too and fall under ``dq``), its calls dk/dv's.  None where
    the op table's rows do not hold ``mx_flash_dq``."""
    tr, cell = run["trace"], run["cell"]
    k = (tr or {}).get("kernels") or {}
    took_dq = dict((tr or {}).get("device_ops") or ()).get("mx_flash_dq")
    if "fwd" not in k or "dkv" not in k or not took_dq or not run["peaks"]:
        return None
    family = harness.load_module("families", cell.config["family"])
    least = 0.0
    for kind in ("fwd", "dq", "dkv"):
        layers = family.flash_kernel_useful(
            cell.config, int(cell.traffic["batch"]), kind)
        calls = k["dkv" if kind == "dq" else kind]["calls"]
        least += calls / float(len(layers)) * sum(
            max(f / run["peaks"]["flops_bf16"],
                b / run["peaks"]["hbm_bytes_per_s"]) for f, b in layers)
    took = k["fwd"]["seconds"] + k["dkv"]["seconds"] + took_dq
    return 100.0 * least / took if took else None
