"""From a profiler trace (``*.xplane.pb``) to numbers.  The same code
reads every PR's trace, so no PR that claims a gain can change how.

What a TPU trace looks like (looked at by hand, PR 25): one plane per
chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
program run) and ``XLA Ops`` (one event per HLO instruction run, named by
the instruction's whole text: ``%fusion.3 = bf16[..] fusion(..),
kind=kLoop, ..``); ``while`` / ``conditional`` / ``call`` events enclose
their bodies' events.  Host threads are lines of ``/host:CPU``; a
``TraceAnnotation`` is an event there under its own name.  The host's
and the device's clocks agree to about a millisecond.
"""
import glob
import os
import re

_CONTAINER = re.compile(r"[\)\}\]] (while|conditional|call)\(")
_KIND = re.compile(r"kind=(k\w+)")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError("no *.xplane.pb under %s" % trace_dir)
    return files[-1]


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def op_label(text):
    """``%convert_reduce_fusion.12 = .. fusion(..), kind=kOutput`` ->
    ``convert_reduce_fusion/kOutput``: the instruction's name without its
    number, with the fusion's kind where it has one."""
    name = text.split(" = ", 1)[0].lstrip("%")
    name = re.sub(r"(\.(clone|\d+))+$", "", name)
    kind = _KIND.search(text)
    return "%s/%s" % (name, kind.group(1)) if kind else name


def union_seconds(intervals):
    """Total length of the union of (start, end) intervals, and the
    merged intervals themselves."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def pallas_kind(text):
    """Which flash-attention kernel a ``tpu_custom_call`` event is, from
    what it returns (the calls carry no stable name yet): the forward
    returns the output and float32 row log-sums, dq one array, dkv two
    arrays of the inputs' type.  Also the [bh, t, d] it ran on."""
    if PALLAS_TARGET not in text:
        return None, None
    result = text.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    arrays = _ARRAY.findall(result)
    if not arrays:
        return None, None
    shape = tuple(int(x) for x in arrays[0][1].split(",") if x)
    if any(dt == "f32" for dt, _ in arrays[1:]) and len(arrays) == 2:
        kind = "fwd"
    elif len(arrays) == 1:
        kind = "dq"
    elif len(arrays) == 2:
        kind = "dkv"
    else:
        kind = None
    return kind, shape


def _host_spans(pd, prefix="bench:"):
    rows = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    rows.append((e.name, e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
    return rows


def reduce(pd, top=10):
    """The numbers the per-layer readers and the result's ``breakdown``
    take from one trace.  The window is the host's ``bench:window``
    span where there is one, else the extent of the device's events."""
    spans = _host_spans(pd)
    window = [s for s in spans if s[0] == "bench:window"]
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    per_chip = []
    op_seconds = {}
    kernels = {}
    programs = 0
    merged0, modules0 = [], []
    for plane in planes:
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules = [(e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name)
                           for e in line.events]
            elif line.name == "XLA Ops":
                for e in line.events:
                    text = e.name
                    if _CONTAINER.search(text):
                        continue
                    s = e.start_ns * 1e-9
                    d = e.duration_ns * 1e-9
                    ops.append((s, s + d))
                    label = op_label(text)
                    op_seconds[label] = op_seconds.get(label, 0.0) + d
                    kind, shape = pallas_kind(text)
                    if kind:
                        k = kernels.setdefault(
                            kind, {"seconds": 0.0, "calls": 0,
                                   "shape": shape})
                        k["seconds"] += d
                        k["calls"] += 1
        if not ops:
            continue
        if window:
            lo, hi = window[0][1] - 0.005, window[0][2] + 0.005
            ops = [(max(s, lo), min(e, hi)) for s, e in ops
                   if e > lo and s < hi]
            modules = [m for m in modules if m[1] > lo and m[0] < hi]
        busy, merged = union_seconds(ops)
        per_chip.append(busy)
        programs = max(programs, len(modules))
        if not merged0:
            merged0, modules0 = merged, modules
    if not per_chip:
        return None
    if window:
        w0, w1 = window[0][1], window[0][2]
    else:
        w0, w1 = merged0[0][0], merged0[-1][1]
    n = float(len(per_chip))
    out = {
        "busy_s": sum(per_chip) / n,
        "window_s": w1 - w0,
        "chips_traced": len(per_chip),
        "programs": programs,
        "main": _main_program(modules0),
        "device_ops": sorted(([k, v / n] for k, v in op_seconds.items()),
                             key=lambda kv: -kv[1])[:top],
        "kernels": kernels,
        "idle_gaps": _attribute_gaps(
            merged0, [m[:2] for m in modules0], spans, w0, w1, top),
    }
    return out


def _main_program(modules):
    """The program that took most of the device's time among the
    ``XLA Modules`` events (start, end, name): how often it ran, and the
    extent from its first start to its last end on the device's clock.
    A cell's steps in a trace are these runs times the steps one program
    holds; the small programs beside it (a stack's slices, a health
    flag) are not steps."""
    by_name = {}
    for s, e, name in modules:
        by_name.setdefault(name, []).append((s, e))
    if not by_name:
        return None
    name, runs = max(by_name.items(),
                     key=lambda kv: sum(e - s for s, e in kv[1]))
    return {"name": name, "runs": len(runs),
            "seconds": sum(e - s for s, e in runs),
            "extent_s": max(e for _, e in runs) - min(s for s, _ in runs)}


def _attribute_gaps(merged, modules, spans, w0, w1, top):
    """Idle seconds of the first chip by what the host's loop was doing:
    a gap inside a running program is the device's own; otherwise the
    harness span (``bench:call``, ``bench:wait``, ..) that covers most of
    it, or ``host:unmarked``.  The stager's ``bench:stage`` runs beside
    the loop all the time, so it names a gap only where the loop was
    waiting for it."""
    gaps = []
    edge = w0
    for s, e in merged:
        if s > edge:
            gaps.append((edge, min(s, w1)))
        edge = max(edge, e)
    if w1 > edge:
        gaps.append((edge, w1))
    loop = [s for s in spans
            if s[0] not in ("bench:window", "bench:stage")]
    totals = {"device:inside_program": 0.0, "host:unmarked": 0.0}
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        inside = sum(max(0.0, min(g1, m1) - max(g0, m0))
                     for m0, m1 in modules)
        totals["device:inside_program"] += inside
        rest = (g1 - g0) - inside
        if rest <= 0:
            continue
        best, best_cover = "host:unmarked", 0.0
        for name, s0, s1 in loop:
            cover = min(g1, s1) - max(g0, s0)
            if cover > best_cover:
                best, best_cover = name, cover
        if best == "bench:wait":
            best = "bench:stage"
        totals[best] = totals.get(best, 0.0) + rest
    return sorted(([k, v] for k, v in totals.items()),
                  key=lambda kv: -kv[1])[:top]
