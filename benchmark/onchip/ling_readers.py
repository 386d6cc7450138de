"""Readers of the per-layer metrics that the ``ling3fvl_ep64_fused_k4``
cell adds.  Each returns None where it finds nothing to read (a program
without the counter, a run without a device trace), and the metric is
then left out of the result's line.

The counters are the program's (``mx.profiler`` stats, published by the
driver from the [K] arrays every program returns beside its losses, read
after the first program and at the window's open and close):
``kda_decay_span_max`` (a watermark, whole nats to the nearest),
``moe_groups_kept_here`` and ``moe_tokens``.
"""
import harness
from moe_readers import _stats


def kda_decay_span_max(run):
    """The largest |cumulative log-decay| any re-based span of the
    chunked KDA reached, in nats: what ``exp`` was given.  The
    configuration bounds it by ``kda_rebase * -kda_lower_bound`` = 80;
    float32's cliff is 88.7."""
    return _stats().get("kda_decay_span_max")


def moe_group_hit_share(run):
    """Of the tokens routed (tokens x expert layers), the share whose
    kept groups include the group of the experts held here, in percent:
    ``topk_group / n_group`` under uniform routing."""
    s = _stats()
    if not s.get("moe_tokens") or "moe_groups_kept_here" not in s:
        return None
    return 100.0 * s["moe_groups_kept_here"] / float(s["moe_tokens"])


def flash_attn_roofline(run):
    """Least time the chip could take for the USEFUL work of the flash
    kernels' calls the trace shows, over the time they took.  Useful
    means the published widths (q.k 192, v 128: the family's
    ``flash_kernel_useful``), not the 256 the program pads both to.

    Over the forward and the dk/dv kernel only.  ``trace_reduce`` tells
    a ``tpu_custom_call`` by what it returns, which is sound for those
    two (two arrays, the second float32; two arrays of one type) and not
    for dq: XLA's own grouped products return one array too.  The glm
    cell's reader takes dq's time from the op table by its name; here
    one attention layer in seven puts no flash kernel among the table's
    ten largest classes, so a reader by name would find nothing."""
    tr, cell = run["trace"], run["cell"]
    k = (tr or {}).get("kernels") or {}
    if "fwd" not in k or "dkv" not in k or not run["peaks"]:
        return None
    family = harness.load_module("families", cell.config["family"])
    least = took = 0.0
    for kind in ("fwd", "dkv"):
        f, b = family.flash_kernel_useful(
            cell.config, int(cell.traffic["batch"]), kind)
        least += k[kind]["calls"] * max(
            f / run["peaks"]["flops_bf16"],
            b / run["peaks"]["hbm_bytes_per_s"])
        took += k[kind]["seconds"]
    return 100.0 * least / took if took else None
