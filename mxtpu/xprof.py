"""mx.xprof: measured per-op device-time attribution.

`mx.perf` (PR 10) attributes time at whole-PROGRAM granularity; this
module answers the next question — *which ops inside the program* —
with two acquisition paths feeding ONE schema:

* **Xplane ingestion** (:func:`ingest`): a minimal protobuf
  wire-format decoder (no TF/tsl dependency) for the XSpace files
  `mx.inspect.trace(dir)` / ``jax.profiler`` emit.  Device-line op
  events are extracted and joined back to model layers through the
  ``named_scope`` op_name metadata the graph builder plants in every
  HLO instruction (``jvp(layer)`` = forward, ``transpose(jvp(layer))``
  = backward/wgrad).  This is the ground-truth path: it reads what the
  device actually ran (post-fusion kernels), including idle gaps.

* **Timed eager replay** (:func:`profile`): the backend-portable
  fallback — the same NNVM topological walk `health.diagnose` runs
  (AMP casts and ``__rng_id__`` folding included) with
  ``block_until_ready`` per node.  Eager per-op dispatch is far slower
  than the fused compiled program, so the replay measures *relative*
  per-op shares and the absolute walls are CALIBRATED against the
  `mx.perf` sampled program wall (call→ready).  The calibrated sum
  therefore reconciles with the program wall by construction; what the
  guard (`tools/check_xprof.py`) proves is that the plumbing — perf
  wall, registry join, share math — stays consistent end to end.

Both paths land an ``OpProfile`` dict: per-op / per-layer /
per-op-class measured wall, joined against the `mx.inspect` registry's
cost analysis over the ``MXTPU_PEAK_*`` table → achieved
FLOPS/bandwidth, roofline placement, measured-vs-modeled discrepancy,
device-idle gaps, and a top-K-sinks report (:func:`report`,
``tools/op_report.py``).

Consumers: `mx.inspect` program records grow an ``op_profile`` field,
telemetry gets an ``op_profile`` event kind (cluster.json /
``tools/dash.py`` name each rank's top sink).

Env: ``MXTPU_XPROF`` (default 1) gates everything — disabled, every
entry point is one bool check; ``MXTPU_XPROF_EVERY=N`` auto-profiles
every Nth FusedTrainLoop chunk (default 0 = off);
``MXTPU_XPROF_TOPK`` sizes the top-sink list (default 10).
"""
from __future__ import annotations

import collections
import os
import re
import struct
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .base import MXNetError, getenv_bool

__all__ = [
    "enabled", "enable", "decode_xspace", "find_xplane_files",
    "ingest", "profile", "attach", "get", "last", "report",
    "format_report", "top_sink", "bench_breakdown", "classify",
    "maybe_autoprofile", "reset", "SCHEMA",
]

SCHEMA = "mxtpu-xprof-v1"

_ENABLED = getenv_bool("MXTPU_XPROF", True)
_AUTO_EVERY = int(os.environ.get("MXTPU_XPROF_EVERY", "0") or 0)
_TOP_K = max(1, int(os.environ.get("MXTPU_XPROF_TOPK", "10") or 10))

_lock = threading.Lock()
# latest OpProfile per inspect-registry program name + the most recent
_PROFILES: "collections.OrderedDict[str, Dict[str, Any]]" = \
    collections.OrderedDict()
_MAX_PROFILES = 32


def enabled() -> bool:
    return _ENABLED


def enable(on: bool = True) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def reset() -> None:
    with _lock:
        _PROFILES.clear()


# ---------------------------------------------------------------------------
# Protobuf wire-format decoder (XSpace subset, no TF/tsl dependency)
# ---------------------------------------------------------------------------
#
# Field numbers verified against jax 0.4.x profiler output:
#   XSpace.planes = 1
#   XPlane:  id=1 name=2 lines=3 event_metadata(map)=4
#            stat_metadata(map)=5 stats=6
#   XLine:   id=1 name=2 timestamp_ns=3 events=4 duration_ps=9
#   XEvent:  metadata_id=1 offset_ps=2 duration_ps=3 stats=4
#            num_occurrences=5
#   XStat:   metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6
#            ref=7
#   XEventMetadata: id=1 name=2 metadata=3 display_name=4
#   XStatMetadata:  id=1 name=2
#   proto map entries: key=1 value=2
#
# Torn/truncated files must read as PARTIAL, never crash: every
# container loop catches _Truncated and keeps what it already decoded.


class _Truncated(Exception):
    """Internal: the buffer ended (or was malformed) mid-field."""


def _varint(buf, pos: int, end: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        if pos >= end:
            raise _Truncated()
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not (b & 0x80):
            return result, pos
        shift += 7
        if shift > 63:
            raise _Truncated()


def _iter_fields(buf, pos: int, end: int):
    """Yield (field_no, wire_type, value) until ``end``.  Length-
    delimited values come back as (start, stop) spans into ``buf`` —
    no copies.  Raises _Truncated on overrun/unknown wire types."""
    while pos < end:
        tag, pos = _varint(buf, pos, end)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            val, pos = _varint(buf, pos, end)
        elif wt == 1:
            if pos + 8 > end:
                raise _Truncated()
            val = struct.unpack_from("<Q", buf, pos)[0]
            pos += 8
        elif wt == 2:
            ln, pos = _varint(buf, pos, end)
            if ln < 0 or pos + ln > end:
                raise _Truncated()
            val = (pos, pos + ln)
            pos += ln
        elif wt == 5:
            if pos + 4 > end:
                raise _Truncated()
            val = struct.unpack_from("<I", buf, pos)[0]
            pos += 4
        else:
            # groups (3/4) and anything newer: cannot be skipped
            # safely without schema knowledge — treat as torn
            raise _Truncated()
        yield fno, wt, val


def _text(buf, span) -> str:
    s, e = span
    return bytes(buf[s:e]).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= (1 << 63) else v


def _dec_stat(buf, span) -> Dict[str, Any]:
    st: Dict[str, Any] = {}
    try:
        for fno, wt, val in _iter_fields(buf, *span):
            if fno == 1 and wt == 0:
                st["metadata_id"] = val
            elif fno == 2 and wt == 1:
                st["value"] = struct.unpack("<d", struct.pack("<Q",
                                                              val))[0]
            elif fno == 3 and wt == 0:
                st["value"] = val
            elif fno == 4 and wt == 0:
                st["value"] = _signed(val)
            elif fno == 5 and wt == 2:
                st["value"] = _text(buf, val)
            elif fno == 6 and wt == 2:
                st["value"] = bytes(buf[val[0]:val[1]])
            elif fno == 7 and wt == 0:
                st["ref"] = val
    except _Truncated:
        pass
    return st


def _dec_event(buf, span) -> Dict[str, Any]:
    ev: Dict[str, Any] = {"metadata_id": 0, "offset_ps": 0,
                          "duration_ps": 0, "stats": []}
    try:
        for fno, wt, val in _iter_fields(buf, *span):
            if fno == 1 and wt == 0:
                ev["metadata_id"] = val
            elif fno == 2 and wt == 0:
                ev["offset_ps"] = _signed(val)
            elif fno == 3 and wt == 0:
                ev["duration_ps"] = val
            elif fno == 4 and wt == 2:
                ev["stats"].append(_dec_stat(buf, val))
            elif fno == 5 and wt == 0:
                ev["num_occurrences"] = val
    except _Truncated:
        pass
    return ev


def _dec_line(buf, span) -> Dict[str, Any]:
    ln: Dict[str, Any] = {"name": "", "timestamp_ns": 0, "events": []}
    try:
        for fno, wt, val in _iter_fields(buf, *span):
            if fno == 1 and wt == 0:
                ln["id"] = val
            elif fno == 2 and wt == 2:
                ln["name"] = _text(buf, val)
            elif fno == 3 and wt == 0:
                ln["timestamp_ns"] = _signed(val)
            elif fno == 4 and wt == 2:
                ln["events"].append(_dec_event(buf, val))
            elif fno == 9 and wt == 0:
                ln["duration_ps"] = val
    except _Truncated:
        pass
    return ln


def _dec_event_metadata(buf, span) -> Dict[str, Any]:
    md: Dict[str, Any] = {"id": 0, "name": ""}
    try:
        for fno, wt, val in _iter_fields(buf, *span):
            if fno == 1 and wt == 0:
                md["id"] = val
            elif fno == 2 and wt == 2:
                md["name"] = _text(buf, val)
            elif fno == 4 and wt == 2:
                md["display_name"] = _text(buf, val)
    except _Truncated:
        pass
    return md


def _dec_stat_metadata(buf, span) -> Dict[str, Any]:
    md: Dict[str, Any] = {"id": 0, "name": ""}
    try:
        for fno, wt, val in _iter_fields(buf, *span):
            if fno == 1 and wt == 0:
                md["id"] = val
            elif fno == 2 and wt == 2:
                md["name"] = _text(buf, val)
    except _Truncated:
        pass
    return md


def _dec_map_entry(buf, span, value_decoder):
    key = None
    value = None
    try:
        for fno, wt, val in _iter_fields(buf, *span):
            if fno == 1 and wt == 0:
                key = val
            elif fno == 2 and wt == 2:
                value = value_decoder(buf, val)
    except _Truncated:
        pass
    if value is not None and key is None:
        key = value.get("id")
    return key, value


def _dec_plane(buf, span) -> Dict[str, Any]:
    pl: Dict[str, Any] = {"name": "", "lines": [],
                          "event_metadata": {}, "stat_metadata": {}}
    try:
        for fno, wt, val in _iter_fields(buf, *span):
            if fno == 1 and wt == 0:
                pl["id"] = val
            elif fno == 2 and wt == 2:
                pl["name"] = _text(buf, val)
            elif fno == 3 and wt == 2:
                pl["lines"].append(_dec_line(buf, val))
            elif fno == 4 and wt == 2:
                k, v = _dec_map_entry(buf, val, _dec_event_metadata)
                if k is not None and v is not None:
                    pl["event_metadata"][k] = v
            elif fno == 5 and wt == 2:
                k, v = _dec_map_entry(buf, val, _dec_stat_metadata)
                if k is not None and v is not None:
                    pl["stat_metadata"][k] = v
            elif fno == 6 and wt == 2:
                pl.setdefault("stats", []).append(_dec_stat(buf, val))
    except _Truncated:
        pass
    return pl


def decode_xspace(data: bytes) -> Dict[str, Any]:
    """Decode a serialized XSpace (``*.xplane.pb``) into plain dicts.
    Truncated input decodes to whatever prefix is intact — a torn
    profile read mid-write yields a partial space, never an
    exception."""
    buf = memoryview(data)
    space: Dict[str, Any] = {"planes": []}
    try:
        for fno, wt, val in _iter_fields(buf, 0, len(buf)):
            if fno == 1 and wt == 2:
                space["planes"].append(_dec_plane(buf, val))
    except _Truncated:
        space["truncated"] = True
    return space


def find_xplane_files(logdir: str) -> List[str]:
    """All ``*.xplane.pb`` files under ``logdir`` (the jax profiler
    writes ``plugins/profile/<ts>/<host>.xplane.pb``)."""
    out = []
    for root, _dirs, files in os.walk(logdir):
        for f in files:
            if f.endswith(".xplane.pb"):
                out.append(os.path.join(root, f))
    return sorted(out)


# ---------------------------------------------------------------------------
# Op classification + layer join
# ---------------------------------------------------------------------------

#: the op-class vocabulary of the report (docs/observability.md):
#: conv / matmul / bn / wgrad / copy / collective / reduce /
#: elementwise / optimizer / other
_COLLECTIVE_PAT = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective", "all-to-all", "psum")
#: exact HLO control-flow wrapper instruction names (`while`,
#: `while.3`, `conditional`, `call.2`) — their trace events CONTAIN
#: the body ops' events, so `ingest` must skip them
_CONTROL_WRAPPER_RE = re.compile(
    r"^(while|conditional|call)(\.\d+)?$")
_COPY_PAT = ("copy", "transpose", "reshape", "bitcast", "pad", "slice",
             "concatenate", "gather", "dynamic-update", "broadcast",
             "prefetch", "tuple", "convert", "iota")


def classify(name: str, layer: Optional[str] = None,
             direction: Optional[str] = None) -> str:
    """Op class of one kernel/op name (HLO instruction name on the
    xplane path, mxtpu op name on the replay path).  ``direction``
    ('fwd'/'bwd', from the op_name layer join) turns backward conv /
    matmul into the ``wgrad`` class."""
    n = (name or "").lower()
    hay = n + " " + (layer or "").lower()
    if any(p in n for p in _COLLECTIVE_PAT):
        return "collective"
    if "conv" in hay:
        return "wgrad" if direction == "bwd" else "conv"
    if "batchnorm" in hay or "batch_norm" in hay or "-norm" in n:
        return "bn"
    if "dot" in n or "fullyconnected" in hay or "dense" in hay \
            or "matmul" in n or "einsum" in n:
        return "wgrad" if direction == "bwd" else "matmul"
    if any(p in n for p in _COPY_PAT):
        return "copy"
    if "sgd" in hay or "adam" in hay or "optimizer" in hay:
        return "optimizer"
    if "reduce" in n or "sum" in n or "argmax" in n:
        return "reduce"
    if "fusion" in n or "loop" in n or "elemwise" in n or "add" in n \
            or "multiply" in n or "activation" in hay or "relu" in n \
            or "pool" in hay or "softmax" in hay or "dropout" in hay \
            or "exp" in n or "log" in n:
        return "elementwise"
    return "other"


_HLO_OPNAME_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*[^\n]*?op_name=\"([^\"]+)\"")
_SCOPE_JVP_RE = re.compile(r"transpose\(jvp\(([^()]+)\)\)|jvp\(([^()]+)\)")


def _layer_map_from_hlo(hlo_text: str) -> Dict[str, str]:
    """instruction name -> op_name metadata path, parsed from optimized
    HLO text (the `named_scope` attribution the graph builder plants)."""
    return {m.group(1): m.group(2)
            for m in _HLO_OPNAME_RE.finditer(hlo_text or "")}


def _layer_of(path: str) -> Tuple[Optional[str], Optional[str]]:
    """(layer, direction) from an op_name scope path: the DEEPEST
    ``jvp(layer)`` ('fwd') / ``transpose(jvp(layer))`` ('bwd') frame;
    plain scope paths fall back to their deepest named segment."""
    if not path:
        return None, None
    last = None
    for last in _SCOPE_JVP_RE.finditer(path):
        pass
    if last is not None:
        if last.group(1):
            return last.group(1), "bwd"
        return last.group(2), "fwd"
    parts = [p for p in path.split("/") if p and not p.startswith("jit(")]
    return (parts[-1] if parts else None), None


def _registry_hlo(program: Optional[str],
                  kind: Optional[str] = None) -> Optional[str]:
    """Optimized HLO text of a registered program's latest signature
    (None when unavailable — the join then degrades to no layers)."""
    if not program:
        return None
    try:
        from . import inspect as _insp

        rec = _insp.find(program)
        if rec is None:
            return None
        si = rec.latest_sig(kind)
        if si is None:
            return None
        return si.hlo_text()
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Path (a): xplane ingestion
# ---------------------------------------------------------------------------

def _is_device_line(plane_name: str, line_name: str) -> bool:
    """Lines that carry per-HLO-op device events: TPU/GPU device
    planes' op lines, and the CPU client's per-module lines
    (``tf_XLATfrtCpuClient/<id>``)."""
    if plane_name.startswith("/device:"):
        return "step" not in line_name.lower()
    return "xla" in line_name.lower()


def ingest(logdir: str, program: Optional[str] = None,
           kind: Optional[str] = None, steps: int = 1,
           module_filter: Optional[str] = None,
           calibrate: bool = True) -> Dict[str, Any]:
    """Build an OpProfile from the xplane files under ``logdir`` (a
    `mx.inspect.trace` output dir, or one ``.xplane.pb`` path).

    Device-line events are aggregated by op name, joined to layers via
    ``program``'s registered HLO op_name metadata, and normalized to
    per-step microseconds by ``steps`` (how many wall steps ran inside
    the trace).  ``module_filter`` keeps only events whose
    ``hlo_module`` stat contains the substring.  Raises MXNetError
    when the dir holds no xplane file at all."""
    files = [logdir] if os.path.isfile(logdir) \
        else find_xplane_files(logdir)
    if not files:
        raise MXNetError(
            "xprof.ingest: no .xplane.pb under %r — was the trace "
            "empty? (see mx.inspect.trace / EmptyTraceError)" % logdir)
    agg: Dict[str, List[float]] = {}   # name -> [total_us, count]
    modules: collections.Counter = collections.Counter()
    idle_us = 0.0
    span_us = 0.0
    truncated = False
    for path in files:
        with open(path, "rb") as f:
            space = decode_xspace(f.read())
        truncated = truncated or bool(space.get("truncated"))
        for plane in space["planes"]:
            smd = plane["stat_metadata"]
            stat_names = {k: v.get("name", "") for k, v in smd.items()}
            for line in plane["lines"]:
                if not _is_device_line(plane["name"], line["name"]):
                    continue
                t_min = None
                t_max = None
                busy_ps = 0
                for ev in line["events"]:
                    emd = plane["event_metadata"].get(ev["metadata_id"])
                    name = (emd or {}).get("name") or "?"
                    if "::" in name:
                        # C++ runtime frames (ThunkExecutor::Execute,
                        # ...) wrap the real op events on CPU client
                        # lines — framework overhead, not device ops
                        continue
                    if _CONTROL_WRAPPER_RE.match(name):
                        # control-flow wrapper instructions (the fused
                        # scan's `while`, conditionals, calls): their
                        # duration is the SUM of the body ops' spans,
                        # which are emitted as their own events on the
                        # same line — counting both double-books every
                        # microsecond of the loop body
                        continue
                    mod = None
                    for st in ev["stats"]:
                        sname = stat_names.get(st.get("metadata_id"), "")
                        if sname == "hlo_module":
                            ref = st.get("ref", st.get("value"))
                            mod = stat_names.get(ref, str(ref)) \
                                if isinstance(ref, int) else str(ref)
                    if mod:
                        modules[mod] += 1
                    if module_filter and mod \
                            and module_filter not in mod:
                        continue
                    dur = ev.get("duration_ps", 0)
                    off = ev.get("offset_ps", 0)
                    busy_ps += dur
                    t_min = off if t_min is None else min(t_min, off)
                    t_max = off + dur if t_max is None \
                        else max(t_max, off + dur)
                    cell = agg.setdefault(name, [0.0, 0])
                    cell[0] += dur / 1e6
                    cell[1] += ev.get("num_occurrences", 0) or 1
                if t_min is not None and t_max > t_min:
                    line_span = (t_max - t_min) / 1e6
                    span_us += line_span
                    idle_us += max(0.0, line_span - busy_ps / 1e6)
    layer_map = _layer_map_from_hlo(_registry_hlo(program, kind))
    steps = max(1, int(steps))
    ops = []
    for name, (us, count) in agg.items():
        path = layer_map.get(name)
        layer, direction = _layer_of(path) if path else (None, None)
        ops.append({
            "op": name,
            "wall_us": us / steps,
            "count": count,
            "layer": layer,
            "direction": direction,
            "op_class": classify(name, layer, direction),
        })
    prof = _assemble(ops, source="xplane", program=program, kind=kind,
                     steps=steps, idle_us=idle_us / steps,
                     calibrate=calibrate)
    if truncated:
        prof["truncated"] = True
    if modules:
        prof["hlo_modules"] = dict(modules.most_common(8))
    if program:
        attach(program, prof)
    return prof


# ---------------------------------------------------------------------------
# Path (b): timed eager replay
# ---------------------------------------------------------------------------

def _nbytes(v) -> int:
    try:
        return int(v.size) * v.dtype.itemsize
    except Exception:
        return 0


def _replay_walk(symbol, arg_names: Sequence[str],
                 aux_names: Sequence[str], arg_vals, aux_vals, key,
                 amp_dtype=None, train: bool = False,
                 repeat: int = 2) -> List[Dict[str, Any]]:
    """The timed eager walk: `health.diagnose`'s exact NNVM traversal
    (same AMP casts, same ``__rng_id__`` folding) with a warmup pass
    and ``repeat`` timed re-executions per node, ``block_until_ready``
    bounding each measurement (MIN across repeats — the node's
    intrinsic cost, not scheduler noise).  Returns one op row per
    non-variable node."""
    import jax

    from . import amp as _amp
    from . import inspect as _insp
    from .passes.graph import ensure_rng_ids, rng_id_of
    from .symbol.symbol import _topo_order

    ensure_rng_ids(symbol)
    nodes = _topo_order(symbol._outputs)
    arg_pos = {n: i for i, n in enumerate(arg_names)}
    aux_pos = {n: i for i, n in enumerate(aux_names)}
    env: Dict[Tuple[int, int], Any] = {}
    rows: List[Dict[str, Any]] = []
    rng_i = 0
    with _amp.scope(amp_dtype):
        for node in nodes:
            if node.is_variable:
                if node.is_aux:
                    val = aux_vals[aux_pos[node.name]]
                else:
                    val = arg_vals[arg_pos[node.name]]
                env[(id(node), 0)] = getattr(val, "_data", val)
                continue
            invals = [env[(id(inode), idx)]
                      for inode, idx in node.inputs]
            if amp_dtype is not None:
                invals = _amp.cast_op_inputs(node.op.name, invals,
                                             amp_dtype)
            attrs = dict(node.attrs)
            if node.op.train_aware:
                attrs["is_train"] = train
            if node.op.needs_rng:
                sub = jax.random.fold_in(key, rng_id_of(node, rng_i))
                rng_i += 1
                call = (lambda fn=node.op.fn, k=sub, iv=invals, at=attrs:
                        fn(k, *iv, **at))
            else:
                call = (lambda fn=node.op.fn, iv=invals, at=attrs:
                        fn(*iv, **at))
            # warmup: compiles the eager kernel and materializes the
            # outputs the downstream nodes consume
            out = call()
            jax.block_until_ready(out)
            best = float("inf")
            for _ in range(max(1, repeat)):
                t0 = time.perf_counter()
                jax.block_until_ready(call())
                best = min(best, time.perf_counter() - t0)
            if not isinstance(out, tuple):
                out = (out,)
            n_vis = node.op.n_outputs(node.attrs)
            if len(out) > n_vis and node.attrs.get("sub_aux"):
                out = out[:n_vis]
            for i, o in enumerate(out):
                env[(id(node), i)] = o
            in_shapes = [tuple(v.shape) for v in invals]
            in_dtypes = [v.dtype for v in invals]
            flops = _insp.op_flops(node, in_shapes, in_dtypes)
            nbytes = sum(_nbytes(v) for v in invals) + \
                sum(_nbytes(o) for o in out)
            rows.append({
                "op": node.name,
                "kernel": node.op.name,
                "wall_us": best * 1e6,
                "count": 1,
                "layer": node.name,
                "direction": "fwd",
                "op_class": classify(node.op.name, node.name, "fwd"),
                "flops": flops,
                "bytes": nbytes or None,
            })
    return rows


_BWD_FACTOR = 2.0  # standard fwd:bwd FLOP ratio (one fwd, ~two mults)


def _add_backward_rows(rows: List[Dict[str, Any]]) -> List[Dict]:
    """Synthetic backward rows for a TRAIN replay: the eager walk times
    the forward only, so each grad-producing node gets a
    ``(backward)`` row at ``_BWD_FACTOR``x its forward wall (flagged
    ``estimated`` — calibration against the measured program wall then
    scales fwd and bwd shares together).  conv/matmul backward lands
    in the ``wgrad`` class, matching the xplane join's
    ``transpose(jvp(...))`` attribution."""
    out = list(rows)
    for r in rows:
        cls = r["op_class"]
        if cls in ("copy", "collective", "optimizer"):
            continue
        out.append({
            "op": r["op"] + " (backward)",
            "kernel": r.get("kernel"),
            "wall_us": r["wall_us"] * _BWD_FACTOR,
            "count": r["count"],
            "layer": r["layer"],
            "direction": "bwd",
            "op_class": "wgrad" if cls in ("conv", "matmul") else cls,
            "flops": (r.get("flops") or 0) * _BWD_FACTOR or None,
            "bytes": r.get("bytes"),
            "estimated": True,
        })
    return out


def _program_wall_us(name: Optional[str]) -> Optional[float]:
    """Per-step measured program wall from the `mx.perf` observatory
    (sampled call→ready), the calibration target."""
    if not name:
        return None
    try:
        from . import perf as _perf

        row = _perf.programs(force=False).get(name)
        if not row:
            return None
        return row.get("wall_us_avg") or \
            row.get("device_compute_us_avg") or \
            row.get("host_dispatch_us_avg")
    except Exception:
        return None


def profile(target, data=None, kind: Optional[str] = None,
            key=None, repeat: int = 2, calibrate: bool = True,
            attach_result: bool = True) -> Optional[Dict[str, Any]]:
    """Timed-eager-replay OpProfile of a dispatch-path object:

    * **Executor** — replays its bound symbol over the CURRENT
      arg/aux arrays (set data via ``arg_dict`` first); train replay
      when it has differentiable args.
    * **CachedOp** — ``data`` = the full args list (NDArrays/arrays in
      ``list_arguments()`` order), plus aux via the op's usual flow;
      pass ``kind='train'`` for a train-step replay.
    * **FusedTrainLoop** — ``data`` = one batch per data slot (a list
      matching the loop's data slots; pass a staged (K, ...) stack's
      ``[0]`` slices).  Train replay with synthetic backward rows.
    * **Module** — delegates to its first executor.

    Returns the OpProfile (and attaches it to the program's
    `mx.inspect` record + telemetry), or None when ``MXTPU_XPROF=0``.
    Replay never dispatches the compiled program: zero retraces."""
    if not _ENABLED:
        return None
    import jax

    if key is None:
        key = jax.random.PRNGKey(0)
    # -- FusedTrainLoop -----------------------------------------------------
    if hasattr(target, "_jit_program") and hasattr(target, "_exec"):
        loop = target
        ex = loop._exec
        if data is None:
            raise MXNetError("xprof.profile(FusedTrainLoop) needs "
                             "data=[per-slot batch arrays] (e.g. "
                             "[s[0] for s in stack_batches(batches)])")
        full = [None] * len(loop._arg_names)
        for j, i in enumerate(loop._diff_idx):
            full[i] = loop._p_vals[j]
        for i in loop._fixed_idx:
            full[i] = ex.arg_arrays[i]._data
        for j, i in enumerate(loop._data_idx):
            v = data[j]
            full[i] = getattr(v, "_data", v)
        rows = _replay_walk(ex._symbol, loop._arg_names, ex._aux_names,
                            full, list(loop._aux_vals), key,
                            amp_dtype=ex._amp_dtype, train=True,
                            repeat=repeat)
        rows = _add_backward_rows(rows)
        name, kind = loop._insp.name, kind or "train"
    # -- Executor -----------------------------------------------------------
    elif hasattr(target, "arg_arrays") and hasattr(target, "_symbol"):
        ex = target
        train = kind != "infer" and bool(ex._diff_idx)
        rows = _replay_walk(ex._symbol, ex._arg_names, ex._aux_names,
                            list(ex.arg_arrays), list(ex.aux_arrays),
                            key, amp_dtype=ex._amp_dtype, train=train,
                            repeat=repeat)
        if train:
            rows = _add_backward_rows(rows)
        name, kind = ex._insp.name, kind or ("train" if train
                                             else "infer")
    # -- CachedOp -----------------------------------------------------------
    elif hasattr(target, "_jit_infer") and hasattr(target, "_arg_names"):
        cop = target
        if data is None:
            raise MXNetError("xprof.profile(CachedOp) needs data="
                             "[args in list_arguments() order]")
        args = list(data)
        n = len(cop._arg_names)
        aux = args[n:] if len(args) > n else []
        train = kind == "train"
        rows = _replay_walk(cop._symbol, cop._arg_names,
                            cop._aux_names, args[:n], aux, key,
                            amp_dtype=cop._amp_dtype, train=train,
                            repeat=repeat)
        if train:
            rows = _add_backward_rows(rows)
        name, kind = cop._insp.name, kind or ("train" if train
                                              else "infer")
    # -- Module -------------------------------------------------------------
    elif hasattr(target, "_exec_group"):
        return profile(target._exec_group.execs[0], data=data,
                       kind=kind, key=key, repeat=repeat,
                       calibrate=calibrate,
                       attach_result=attach_result)
    else:
        raise MXNetError("xprof.profile: unsupported target %r — pass "
                         "an Executor, CachedOp, FusedTrainLoop or "
                         "Module" % type(target).__name__)
    prof = _assemble(rows, source="replay", program=name, kind=kind,
                     steps=1, calibrate=calibrate)
    if attach_result:
        attach(name, prof)
    return prof


# ---------------------------------------------------------------------------
# The one schema + enrichment
# ---------------------------------------------------------------------------

def _assemble(ops: List[Dict[str, Any]], source: str,
              program: Optional[str], kind: Optional[str],
              steps: int = 1, idle_us: Optional[float] = None,
              calibrate: bool = True) -> Dict[str, Any]:
    """Normalize op rows into the OpProfile schema: shares, per-layer /
    per-class rollups, roofline enrichment over the ``MXTPU_PEAK_*``
    table, calibration against the `mx.perf` program wall, top-K."""
    from . import perf as _perf

    ops = [dict(o) for o in ops if o.get("wall_us", 0) > 0]
    raw_sum = sum(o["wall_us"] for o in ops)
    wall_us = _program_wall_us(program)
    calibration = None
    if calibrate and wall_us and raw_sum > 0:
        scale = wall_us / raw_sum
        for o in ops:
            o["raw_wall_us"] = o["wall_us"]
            o["wall_us"] = o["wall_us"] * scale
        calibration = {"program_wall_us": round(wall_us, 2),
                       "raw_sum_us": round(raw_sum, 2),
                       "scale": round(scale, 6)}
    total = sum(o["wall_us"] for o in ops) or 1.0
    pkf, pkb = _perf.peak_flops(), _perf.peak_bytes()
    layers: Dict[str, float] = collections.defaultdict(float)
    classes: Dict[str, float] = collections.defaultdict(float)
    for o in ops:
        o["share"] = o["wall_us"] / total
        if o.get("layer"):
            layers[o["layer"]] += o["wall_us"]
        classes[o.get("op_class") or "other"] += o["wall_us"]
        wall_s = o["wall_us"] / 1e6
        flops = o.get("flops")
        nbytes = o.get("bytes")
        if flops and wall_s > 0:
            o["achieved_gflops"] = round(flops / wall_s / 1e9, 3)
            o["pct_peak_flops"] = round(
                100.0 * flops / (wall_s * pkf), 2)
        if nbytes and wall_s > 0:
            o["achieved_gbps"] = round(nbytes / wall_s / 1e9, 3)
            o["pct_peak_bytes"] = round(
                100.0 * nbytes / (wall_s * pkb), 2)
        if flops and nbytes:
            rf = _perf.roofline(flops, nbytes)
            if rf is not None:
                o["bound"] = rf["bound"]
                # fraction of the roofline this op achieves on its
                # binding resource
                o["roofline_frac"] = round(min(
                    flops / (wall_s * pkf) if rf["bound"] == "compute"
                    else nbytes / (wall_s * pkb), 1.0), 4) \
                    if wall_s > 0 else None
            modeled_us = max(flops / pkf, nbytes / pkb) * 1e6
            if modeled_us > 0:
                o["modeled_us"] = round(modeled_us, 3)
                # >1 = measured slower than the roofline floor says it
                # must be: the optimization headroom
                o["discrepancy"] = round(o["wall_us"] / modeled_us, 2)
        o["wall_us"] = round(o["wall_us"], 3)
        if "raw_wall_us" in o:
            o["raw_wall_us"] = round(o["raw_wall_us"], 3)
        o["share"] = round(o["share"], 4)
    ops.sort(key=lambda o: -o["wall_us"])
    prof: Dict[str, Any] = {
        "schema": SCHEMA,
        "source": source,
        "program": program,
        "kind": kind,
        "ts": time.time(),
        "steps": steps,
        "n_ops": len(ops),
        "device_us": round(total if ops else 0.0, 2),
        "ops": ops,
        "layers": {k: round(v, 2) for k, v in sorted(
            layers.items(), key=lambda kv: -kv[1])},
        "op_classes": {k: round(v, 2) for k, v in sorted(
            classes.items(), key=lambda kv: -kv[1])},
    }
    if wall_us is not None:
        prof["program_wall_us"] = round(wall_us, 2)
    if calibration is not None:
        prof["calibration"] = calibration
    if idle_us is not None:
        prof["idle_us"] = round(idle_us, 2)
    prof["top"] = ops[:_TOP_K]
    return prof


# ---------------------------------------------------------------------------
# Registry of latest profiles + consumer wiring
# ---------------------------------------------------------------------------

def attach(program: str, prof: Dict[str, Any]) -> None:
    """Record ``prof`` as the program's latest OpProfile: module
    registry (for :func:`report`/:func:`top_sink`), the program's
    `mx.inspect` record ``op_profile`` field (compact), and one
    telemetry ``op_profile`` event naming the top sink."""
    with _lock:
        _PROFILES[program] = prof
        _PROFILES.move_to_end(program)
        while len(_PROFILES) > _MAX_PROFILES:
            _PROFILES.popitem(last=False)
    try:
        from . import inspect as _insp

        rec = _insp.find(program)
        if rec is not None:
            rec.op_profile = _compact(prof)
    except Exception:
        pass
    try:
        from . import telemetry as _tel

        top = prof["ops"][0] if prof.get("ops") else None
        _tel.record("op_profile", program=program,
                    source=prof.get("source"),
                    step=_tel.current_step(),
                    n_ops=prof.get("n_ops"),
                    device_us=prof.get("device_us"),
                    idle_us=prof.get("idle_us"),
                    top_op=top and top["op"],
                    top_class=top and top.get("op_class"),
                    top_share=top and top.get("share"),
                    op_classes=prof.get("op_classes"))
    except Exception:
        pass


def _compact(prof: Dict[str, Any], k: int = 5) -> Dict[str, Any]:
    """The small form consumers embed (inspect records, ledger rows):
    totals + rollups + top-k ops, never the full op list."""
    return {key: prof.get(key) for key in
            ("schema", "source", "kind", "ts", "n_ops", "device_us",
             "program_wall_us", "idle_us", "op_classes")} | \
        {"top": [{f: o.get(f) for f in
                  ("op", "op_class", "layer", "wall_us", "share",
                   "bound", "discrepancy")}
                 for o in prof.get("top", [])[:k]]}


def get(program: str) -> Optional[Dict[str, Any]]:
    with _lock:
        return _PROFILES.get(program)


def last() -> Optional[Dict[str, Any]]:
    """The most recently attached OpProfile."""
    with _lock:
        return next(reversed(_PROFILES.values())) if _PROFILES else None


def top_sink() -> Optional[Dict[str, Any]]:
    """The top device-time sink of the latest profile — what
    `mx.obs`'s sampler/cluster view and ``tools/dash.py`` surface per
    rank.  Read-only: a dict lookup, never profiles."""
    prof = last()
    if not prof or not prof.get("ops"):
        return None
    t = prof["ops"][0]
    return {"program": prof.get("program"), "op": t["op"],
            "op_class": t.get("op_class"), "layer": t.get("layer"),
            "share": t.get("share"), "wall_us": t.get("wall_us")}


def bench_breakdown(prof: Optional[Dict[str, Any]] = None,
                    k: int = 5) -> Optional[Dict[str, Any]]:
    """The compact breakdown the run ledger's summary row carries:
    per-op-class us + top-k sinks (diffable by
    ``tools/compare_runs.py``)."""
    prof = prof or last()
    if not prof:
        return None
    return _compact(prof, k=k)


# ---------------------------------------------------------------------------
# Reports
# ---------------------------------------------------------------------------

def report(program: Optional[str] = None,
           k: Optional[int] = None) -> Dict[str, Any]:
    """The latest OpProfile (of ``program``, default most recent) with
    its top-``k`` sinks — raises when nothing was profiled yet."""
    prof = get(program) if program else last()
    if prof is None:
        raise MXNetError("xprof.report: no op profile recorded yet — "
                         "run mx.xprof.profile(...) or "
                         "mx.xprof.ingest(trace_dir)")
    if k:
        prof = dict(prof)
        prof["top"] = prof["ops"][:k]
    return prof


def format_report(prof: Dict[str, Any], k: int = 10) -> str:
    """Human-readable top-K-sinks table of one OpProfile."""
    lines = []
    cal = prof.get("calibration")
    lines.append(
        "op profile [%s] program=%s kind=%s  ops=%d  device=%.1fus%s%s"
        % (prof.get("source"), prof.get("program"), prof.get("kind"),
           prof.get("n_ops", 0), prof.get("device_us", 0.0),
           "  idle=%.1fus" % prof["idle_us"]
           if prof.get("idle_us") is not None else "",
           "  (calibrated to program wall %.1fus)"
           % cal["program_wall_us"] if cal else ""))
    classes = prof.get("op_classes") or {}
    total = sum(classes.values()) or 1.0
    lines.append("by class: " + "  ".join(
        "%s %.0f%%" % (c, 100.0 * v / total)
        for c, v in list(classes.items())[:6]))
    top = prof.get("ops", [])[:k]
    cum = 0.0
    lines.append("%-34s %-10s %-24s %9s %6s %6s %9s %9s %6s" % (
        "op", "class", "layer", "wall(us)", "share", "cum%",
        "GFLOP/s", "GB/s", "x-min"))
    for o in top:
        cum += o.get("share", 0.0)
        lines.append("%-34s %-10s %-24s %9.2f %5.1f%% %5.1f%% %9s %9s "
                     "%6s" % (
                         o["op"][:34], o.get("op_class", "-"),
                         (o.get("layer") or "-")[:24], o["wall_us"],
                         100.0 * o.get("share", 0.0), 100.0 * cum,
                         "%.2f" % o["achieved_gflops"]
                         if o.get("achieved_gflops") is not None
                         else "-",
                         "%.2f" % o["achieved_gbps"]
                         if o.get("achieved_gbps") is not None else "-",
                         "%.1f" % o["discrepancy"]
                         if o.get("discrepancy") is not None else "-"))
    if top:
        head = top[0]
        lines.append(
            "top sink: %s (%s%s) — %.1f%% of device time%s" % (
                head["op"], head.get("op_class"),
                ", %s" % head["layer"] if head.get("layer") else "",
                100.0 * head.get("share", 0.0),
                ", %s-bound at %.0f%% of roofline"
                % (head["bound"], 100.0 * head["roofline_frac"])
                if head.get("bound") and head.get("roofline_frac")
                is not None else ""))
    return "\n".join(lines)


def summary() -> str:
    prof = last()
    return format_report(prof) if prof else "no op profile recorded"


# ---------------------------------------------------------------------------
# FusedTrainLoop auto-profile hook
# ---------------------------------------------------------------------------

_auto_counts: Dict[int, int] = {}


def maybe_autoprofile(loop, data_stack) -> None:
    """Per-chunk hook `FusedTrainLoop.run_stacked` calls: every
    ``MXTPU_XPROF_EVERY`` chunks, replay-profile the loop on the first
    batch of the staged stack.  Default off; disabled/off mode is the
    two leading int/bool checks (<10us/step budget, asserted by
    ``tools/check_xprof.py``)."""
    if _AUTO_EVERY <= 0 or not _ENABLED:
        return
    key = id(loop)
    n = _auto_counts.get(key, 0) + 1
    _auto_counts[key] = n
    if n % _AUTO_EVERY:
        return
    try:
        profile(loop, data=[s[0] for s in data_stack])
    except Exception:
        pass
