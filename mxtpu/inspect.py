"""Program inspector: compiled-program registry, retrace blame, traces.

The compile-time mirror of `mxtpu/telemetry.py` (which watches the
*runtime*): every XLA program this framework builds — Executor
`_jit_*` dispatch, CachedOp, FusedTrainLoop, and `compile_cache.
aot_compile` warmups — registers here, so "retraces: 7" in
`profiler.stats()` becomes an actionable diagnosis.  Three pieces:

  * **Compiled-program registry** — one :class:`ProgramRecord` per
    logical program (keyed ``site:symbol-name``) holding every input
    signature it compiled, the compile wall time per signature, the
    cache-hit count, and — lazily, on first request — XLA's own
    ``cost_analysis()`` (FLOPs, bytes accessed) and
    ``memory_analysis()`` (argument/output/temp/peak bytes) plus the
    optimized HLO text.  Surfaced as :func:`programs` /
    :func:`summary` / :func:`report` / :func:`hlo`.

  * **Retrace blame** — when a program compiles a SECOND (third, ...)
    signature, the new signature is diffed against the cached ones and
    a human-readable culprit is produced ("arg `data0` shape
    (32, 3, 224, 224)→(33, 3, 224, 224): ... enable shape buckets").
    The culprit rides on the telemetry ``compile`` event (``blame``
    field), ticks a per-culprit ``retrace_blame::...`` counter in
    ``profiler.stats()``, and aggregates in :func:`blame_summary`.

  * **Layer attribution** — `executor._build_graph_fn` wraps every
    symbol-node invocation in ``jax.named_scope(node.name)`` (opt out:
    ``MXTPU_INSPECT_SCOPES=0``), so HLO op metadata (``op_name=...``
    in :func:`hlo` output) and `jax.profiler` device traces resolve to
    model layers.  :func:`trace` is the supported device-trace entry
    point (wraps ``jax.profiler.start_trace``/``stop_trace``).

Cost discipline: the cache-HIT path is one enabled-check plus one
unlocked integer bump (<10 us measured by ``tools/check_inspect.py``
--overhead; see `docs/observability.md`).  Cost/memory analysis needs
its own ``jit.lower().compile()`` (JAX exposes no handle to the
executable the dispatch cache built), so it runs LAZILY at inspect
time — never on the training path — and is cached per signature; with
the persistent compile cache armed the XLA part is a disk hit.
``MXTPU_INSPECT_EAGER=1`` moves the analysis to compile time (each new
program then pays one extra trace+compile) so telemetry ``compile``
events ship real ``flops``/``peak_bytes`` immediately; otherwise those
fields start at 0 and are backfilled in place once analysis runs.
``MXTPU_INSPECT=0`` opts out of all registry bookkeeping (the plain
telemetry ``compile`` records keep flowing).
"""
from __future__ import annotations

import collections
import contextlib
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .base import MXNetError, getenv, getenv_bool

__all__ = [
    "enabled",
    "enable",
    "scopes_enabled",
    "program",
    "programs",
    "summary",
    "find",
    "find_for_symbol",
    "hlo",
    "report",
    "hlo_histogram",
    "op_flops",
    "trace",
    "EmptyTraceError",
    "blame_summary",
    "analyze_all",
    "reset",
]

_ENABLED = getenv_bool("MXTPU_INSPECT", True)
_EAGER = getenv_bool("MXTPU_INSPECT_EAGER", False)
# bound both axes of registry growth: a long-lived process (or the
# test suite) creates thousands of executors, and each record pins its
# jit fn (and through it the compiled executable) for lazy analysis
_MAX_PROGRAMS = max(8, int(getenv("MXTPU_INSPECT_MAX", "512") or 512))
_MAX_SIGS = max(2, int(getenv("MXTPU_INSPECT_SIGS", "32") or 32))

_lock = threading.RLock()
# guards every compile site's seen-signature set on the dispatch hot
# path (track_compile): serving threads sharing one CachedOp must
# resolve a brand-new signature to exactly ONE compile token
_sig_lock = threading.Lock()
_REGISTRY: "collections.OrderedDict[str, ProgramRecord]" = \
    collections.OrderedDict()
_BLAME: "collections.Counter" = collections.Counter()


def enabled() -> bool:
    """Registry bookkeeping on?  ``MXTPU_INSPECT=0`` opts out."""
    return _ENABLED


def enable(on: bool = True) -> None:
    """Flip the inspector at runtime (tests / embedding)."""
    global _ENABLED
    _ENABLED = bool(on)


def scopes_enabled() -> bool:
    """Layer-attribution ``jax.named_scope`` wrapping in the graph
    builder (``MXTPU_INSPECT_SCOPES``, default on).  Read at graph
    BUILD time — flipping it after bind needs a rebind."""
    return _ENABLED and getenv_bool("MXTPU_INSPECT_SCOPES", True)


_SCOPE_RE = re.compile(r"[^\w.\-/]")


def scope_name(name: str) -> str:
    """A symbol-node name sanitized for ``jax.named_scope`` (the HLO
    metadata pipeline treats ``/`` as a scope separator)."""
    return _SCOPE_RE.sub("_", name) or "op"


# ---------------------------------------------------------------------------
# Signature helpers
# ---------------------------------------------------------------------------

def _sig_of_tree(example_args) -> Tuple:
    """Hashable (shape, dtype) signature over an arbitrary pytree of
    arrays / ShapeDtypeStructs (the aot_compile entry point)."""
    import jax

    leaves = jax.tree_util.tree_leaves(example_args)
    # dtype OBJECTS, matching compile_cache.sig_of
    return tuple((tuple(v.shape), v.dtype) for v in leaves
                 if hasattr(v, "shape") and hasattr(v, "dtype"))


def _to_structs(example_args):
    """Pytree of arrays -> ShapeDtypeStructs (metadata only — works on
    donated/deleted buffers too, whose avals survive the delete)."""
    import jax

    def leaf(v):
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            return jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
        return v

    return jax.tree_util.tree_map(leaf, example_args)


# ---------------------------------------------------------------------------
# Retrace blame
# ---------------------------------------------------------------------------

_BUCKET_HINT = ("enable shape buckets (MXTPU_SHAPE_BUCKETS=pow2 or "
                "hybridize(shape_buckets=...))")


def _arg_label(arg_names: Optional[Sequence[str]], i: int) -> str:
    if arg_names and i < len(arg_names):
        return arg_names[i]
    return "arg%d" % i


def _diff_sigs(arg_names, old_sig, new_sig) -> List[Tuple[str, str, str]]:
    """Per-argument diffs between two equal-length signatures:
    (arg name, field, human description)."""
    diffs = []
    for i, (o, n) in enumerate(zip(old_sig, new_sig)):
        if o == n:
            continue
        name = _arg_label(arg_names, i)
        (os_, od), (ns, nd) = o, n
        if os_ != ns:
            if len(os_) == len(ns) and os_[1:] == ns[1:]:
                hint = "leading (batch) dim churn — " + _BUCKET_HINT
            else:
                hint = ("pad or fix this dimension host-side (every "
                        "distinct shape compiles a new program)")
            diffs.append((name, "shape", "arg `%s` shape %s→%s: %s"
                          % (name, os_, ns, hint)))
        if od != nd:
            diffs.append((name, "dtype",
                          "arg `%s` dtype %s→%s: cast once at the input "
                          "boundary (the graph retraced for the new dtype)"
                          % (name, od, nd)))
    return diffs


def compute_blame(arg_names, prior_sigs: Sequence[Tuple],
                  new_sig: Tuple) -> Tuple[Optional[str], List[Tuple]]:
    """Diff ``new_sig`` against the cached signatures of the same
    program/kind and name the culprit.  Returns (human blame string or
    None, [(arg, field), ...] culprit keys)."""
    if not prior_sigs:
        return None, []
    same_len = [s for s in prior_sigs if len(s) == len(new_sig)]
    if not same_len:
        closest = prior_sigs[-1]
        msg = ("arg count %d→%d (graph inputs changed): input-structure "
               "churn retraces the whole program"
               % (len(closest), len(new_sig)))
        return msg, [("*", "arity")]
    best = min(same_len,
               key=lambda s: sum(a != b for a, b in zip(s, new_sig)))
    diffs = _diff_sigs(arg_names, best, new_sig)
    if not diffs:  # identical sig resubmitted as new (shouldn't happen)
        return None, []
    shown = [d[2] for d in diffs[:3]]
    if len(diffs) > 3:
        shown.append("(+%d more args changed)" % (len(diffs) - 3))
    return "; ".join(shown), [(d[0], d[1]) for d in diffs]


# ---------------------------------------------------------------------------
# Registry records
# ---------------------------------------------------------------------------

def _compile_uncached(lowered):
    """Diagnostic (inspect-time) compiles bypass the persistent
    compile cache: its key canonicalizes out op_name metadata, so an
    EQUIVALENT program compiled under different layer names in another
    run sharing the cache dir can satisfy the lookup — and
    ``hlo_text()`` would then show the twin's layer names, defeating
    attribution.  Cost/memory figures are name-independent, but the
    text must come from THIS program's lowering."""
    from . import compile_cache as _cc

    with _cc.persistent_cache_bypassed():
        return lowered.compile()


class _SigInfo(object):
    """One compiled signature of one program: compile wall time, the
    blame that triggered it, and the lazy analysis handle."""

    __slots__ = ("kind", "sig", "blame", "compile_wall_s", "aot", "ts",
                 "event", "_jitfn", "_structs", "_compiled", "_analysis",
                 "_hlo")

    def __init__(self, kind: str, sig: Tuple, blame: Optional[str],
                 event: Optional[dict]):
        self.kind = kind
        self.sig = sig
        self.blame = blame
        self.compile_wall_s = 0.0
        self.aot = False
        self.ts = time.time()
        self.event = event  # telemetry compile record (backfilled)
        self._jitfn = None
        self._structs = None
        self._compiled = None
        self._analysis = None
        self._hlo = None

    def set_lowerable(self, jitfn, example_args) -> None:
        try:
            self._structs = _to_structs(example_args)
            self._jitfn = jitfn
        except Exception:
            self._jitfn = self._structs = None

    def analyze(self) -> Dict[str, Any]:
        """XLA cost + memory analysis for this signature (cached).
        Needs its own ``lower().compile()`` when the record was not
        AOT-built — run at inspect time, never on the hot path."""
        if self._analysis is not None:
            return self._analysis
        out: Dict[str, Any] = {}
        try:
            compiled = self._compiled
            if compiled is None:
                if self._jitfn is None:
                    raise MXNetError("no lowerable handle recorded")
                lowered = self._jitfn.lower(*self._structs)
                compiled = _compile_uncached(lowered)
                self._compiled = compiled
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            out["flops"] = float(ca.get("flops", 0.0) or 0.0)
            out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0)
                                          or 0.0)
            out["transcendentals"] = float(ca.get("transcendentals", 0.0)
                                           or 0.0)
            ma = compiled.memory_analysis()
            arg = int(ma.argument_size_in_bytes)
            outb = int(ma.output_size_in_bytes)
            tmp = int(ma.temp_size_in_bytes)
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            out["argument_bytes"] = arg
            out["output_bytes"] = outb
            out["temp_bytes"] = tmp
            out["alias_bytes"] = alias
            # donated outputs alias argument buffers — don't double-count
            out["peak_bytes"] = arg + tmp + max(0, outb - alias)
        except Exception as e:  # analysis is best-effort diagnostics
            out.setdefault("flops", 0.0)
            out.setdefault("peak_bytes", 0)
            out["error"] = str(e)[:300]
        self._analysis = out
        ev = self.event
        if ev is not None:
            # the ring holds this dict by reference: filling the
            # pre-created keys in place (no size change) retroactively
            # enriches flight/telemetry dumps written later
            ev["flops"] = out.get("flops", 0.0)
            ev["peak_bytes"] = out.get("peak_bytes", 0)
        return out

    def hlo_text(self) -> str:
        """Optimized HLO text of this signature (compiles lazily)."""
        if self._hlo is None:
            self.analyze()
            if self._compiled is None:
                raise MXNetError("HLO unavailable: %s"
                                 % self._analysis.get("error", "no handle"))
            self._hlo = self._compiled.as_text()
        return self._hlo

    def as_dict(self) -> Dict[str, Any]:
        d = {"kind": self.kind, "signature": self.sig,
             "compile_wall_s": round(self.compile_wall_s, 6),
             "aot": self.aot, "ts": self.ts}
        if self.blame:
            d["blame"] = self.blame
        if self._analysis is not None:
            d.update(self._analysis)
        return d


class _Pending(object):
    """Token bridging ``begin_compile`` (before the jit dispatch) to
    the point right after it, where wall time and the lowerable handle
    become known."""

    __slots__ = ("prog", "si", "t0")

    def __init__(self, prog: "ProgramRecord", si: _SigInfo):
        self.prog = prog
        self.si = si
        self.t0 = time.perf_counter()

    def done(self, jitfn=None, example_args=None) -> None:
        from . import profiler as _prof

        wall = time.perf_counter() - self.t0
        si = self.si
        si.compile_wall_s = wall
        self.prog.compile_wall_s += wall
        _prof.inc_stat("inspect_compile_wall_us", int(wall * 1e6))
        if si.event is not None:
            si.event["compile_s"] = round(wall, 6)
        if jitfn is not None and example_args is not None:
            si.set_lowerable(jitfn, example_args)
        if _EAGER:
            si.analyze()


class ProgramRecord(object):
    """One logical compiled program (all its signatures)."""

    def __init__(self, site: str, name: str):
        self.site = site
        self.name = name
        self.created = time.time()
        self.arg_names: Optional[List[str]] = None
        # graph-rewrite provenance (mxtpu.passes report) of the symbol
        # this program lowered — set by program() when the pass
        # pipeline optimized the graph, so "this fusion created this
        # HLO region" is answerable from the registry
        self.pass_report: Optional[Dict[str, Any]] = None
        # sharding provenance (mx.shard): the plan the shard pass
        # stamped on this program's graph (or the plan active at
        # registration), e.g. "zero1:n=4,axis=dp" — rides every
        # telemetry ``compile`` event as the ``sharding`` field
        self.sharding: Optional[str] = None
        # latest measured per-op attribution (mx.xprof, compact form:
        # totals + per-class rollup + top sinks) — set by
        # xprof.attach() whenever this program is profiled
        self.op_profile: Optional[Dict[str, Any]] = None
        # device-memory layout hints (mx.hbm): how this site's flat
        # example-arg tree maps onto param/aux/data/opt-state slots —
        # set by the dispatch sites at registration, consumed by
        # hbm.plan()'s input-leaf classifier
        self.mem_layout: Optional[Dict[str, Any]] = None
        # latest decoded per-class/per-layer memory plan (mx.hbm.plan
        # attaches it; rides report() as "memory_plan")
        self.memory_plan: Optional[Dict[str, Any]] = None
        self.hits = 0          # unlocked bump: the <10us hot path
        self.compiles = 0      # dispatch-path compiles (ticks *_trace)
        self.aot_compiles = 0  # warmup/AOT builds (ticks *_warmup)
        self.compile_wall_s = 0.0
        self.sigs: "collections.OrderedDict[Tuple[str, Tuple], _SigInfo]" \
            = collections.OrderedDict()
        self._sym_head = None  # weakref to the symbol's head node

    # -- hot path ---------------------------------------------------------
    def hit(self) -> None:
        if _ENABLED:
            # under _sig_lock: a bare += from N serving threads loses
            # increments, and check_inspect RECONCILES these totals
            # against the (locked) profiler counters
            with _sig_lock:
                self.hits += 1

    # -- compile path -----------------------------------------------------
    def begin_compile(self, kind: str, sig: Tuple,
                      arg_names: Optional[Sequence[str]] = None,
                      site: Optional[str] = None) -> Optional[_Pending]:
        """Register a NEW signature about to compile.  Emits the
        telemetry ``compile`` event (with blame when this is a
        retrace), ticks the blame counters, and returns a token whose
        ``done()`` the call site invokes right after the jit dispatch.
        Returns None (after emitting the plain event) when the
        inspector is disabled."""
        from . import profiler as _prof
        from . import telemetry as _tel

        site = site or self.site
        blame = None
        if _ENABLED:
            names = list(arg_names) if arg_names is not None \
                else self.arg_names
            with _lock:
                # AOT sigs span the site's FULL example-arg tree (aux,
                # rng key, ...) while dispatch sigs cover only the
                # tracked args — different domains, so diffing across
                # them would fabricate arity blame
                prior = [s.sig for (k, _), s in self.sigs.items()
                         if k == kind and not s.aot]
                blame, culprits = compute_blame(names, prior, sig)
            if blame:
                _BLAME[blame] += 1
                _prof.inc_stat("inspect_recompiles")
                for arg, field in culprits:
                    _prof.inc_stat("retrace_blame::%s:%s:%s"
                                   % (self.name, arg, field))
        # flops/peak_bytes/compile_s are pre-created at 0 and later
        # BACKFILLED by assignment only: the dict is already in the
        # telemetry ring, and growing it there would race concurrent
        # heartbeat/flight serialization (dict-changed-size errors).
        # `passes` (graph-rewrite provenance, e.g. "dce,cse,fuse:34->21")
        # is complete at record time — never backfilled.
        pass_prov = None
        if self.pass_report is not None:
            from . import passes as _passes

            pass_prov = _passes.provenance_summary(self.pass_report)
        ev = _tel.record("compile", site=site, step=_tel.current_step(),
                         program=self.name, variant=kind, flops=0.0,
                         peak_bytes=0, compile_s=0.0, blame=blame,
                         passes=pass_prov, sharding=self.sharding)
        if not _ENABLED:
            return None
        _prof.inc_stat("inspect_compiles")
        si = _SigInfo(kind, sig, blame, ev)
        with _lock:
            self.compiles += 1
            if arg_names is not None:
                self.arg_names = list(arg_names)
            self.sigs[(kind, sig)] = si
            while len(self.sigs) > _MAX_SIGS:
                self.sigs.popitem(last=False)
        return _Pending(self, si)

    def record_aot(self, kind: str, example_args, compiled,
                   wall_s: float, event: Optional[dict] = None,
                   jitfn=None) -> None:
        """Register an AOT-built executable (`compile_cache.
        aot_compile`).  The real Compiled object is in hand, so
        analysis is cheap and runs immediately.  The example-arg
        structs (and the jit fn when the caller has one) are kept too,
        so hbm.plan()'s leaf classifier works on warmed programs."""
        if not _ENABLED:
            return
        from . import profiler as _prof

        sig = _sig_of_tree(example_args)
        si = _SigInfo(kind, sig, None, event)
        si.aot = True
        si.compile_wall_s = wall_s
        si._compiled = compiled
        try:
            si._structs = _to_structs(example_args)
            si._jitfn = jitfn
        except Exception:
            pass
        with _lock:
            self.aot_compiles += 1
            self.compile_wall_s += wall_s
            cur = self.sigs.setdefault((kind, sig), si)
            if cur is not si and cur._structs is None:
                cur._structs = si._structs
                cur._jitfn = jitfn
            while len(self.sigs) > _MAX_SIGS:
                self.sigs.popitem(last=False)
        _prof.inc_stat("inspect_compile_wall_us", int(wall_s * 1e6))
        if event is not None:
            event["compile_s"] = round(wall_s, 6)
        si.analyze()

    # -- inspection -------------------------------------------------------
    def latest_sig(self, kind: Optional[str] = None) -> Optional[_SigInfo]:
        with _lock:
            for (k, _), si in reversed(self.sigs.items()):
                if kind is None or k == kind:
                    return si
        return None

    def as_dict(self, analyze: bool = True) -> Dict[str, Any]:
        with _lock:
            sig_infos = list(self.sigs.values())
        d: Dict[str, Any] = {
            "name": self.name, "site": self.site,
            "n_sigs": len(sig_infos), "compiles": self.compiles,
            "aot_compiles": self.aot_compiles, "hits": self.hits,
            "compile_wall_s": round(self.compile_wall_s, 6),
            "kinds": sorted({s.kind for s in sig_infos}),
        }
        blames = [s.blame for s in sig_infos if s.blame]
        if blames:
            d["blame"] = blames
        if self.pass_report is not None:
            from . import passes as _passes

            d["passes"] = _passes.provenance_summary(self.pass_report)
        if self.sharding is not None:
            d["sharding"] = self.sharding
        if self.op_profile is not None:
            d["op_profile"] = self.op_profile
        if analyze and sig_infos:
            analysis = sig_infos[-1].analyze()
            d.update({k: v for k, v in analysis.items() if k != "error"})
            if "error" in analysis:
                d["analysis_error"] = analysis["error"]
        d["signatures"] = [s.as_dict() for s in sig_infos]
        return d


# ---------------------------------------------------------------------------
# Registration / lookup
# ---------------------------------------------------------------------------

def _head_ref(symbol):
    try:
        import weakref

        return weakref.ref(symbol._outputs[0][0])
    except Exception:
        return None


def program(site: str, name: str,
            arg_names: Optional[Sequence[str]] = None,
            symbol=None, reuse: bool = False) -> ProgramRecord:
    """Get-or-create the registry record for the logical program
    ``site:name``.

    ``reuse=True`` means the caller GUARANTEES ``name`` identifies one
    logical program (gluon block names are auto-uniquified per
    process): re-registration returns the same record, so a rebuilt
    CachedOp for the same block accumulates history — which is exactly
    what makes input-structure churn blameable.

    ``reuse=False`` (symbol-derived names like ``softmax``, which any
    number of unrelated graphs share) only merges onto an existing
    record when ``symbol`` is the SAME graph (head-node identity);
    otherwise the key is uniquified with a ``#N`` suffix — two Modules
    both headed by ``softmax`` must not fabricate retrace blame
    against each other."""
    key = "%s:%s" % (site, name)
    if not _ENABLED:
        # disabled: hand back a detached record (no-op bookkeeping)
        # without polluting the registry listing
        rec = ProgramRecord(site, key)
        if arg_names is not None:
            rec.arg_names = list(arg_names)
        return rec
    head = _head_ref(symbol) if symbol is not None else None
    with _lock:
        rec = _REGISTRY.get(key)
        if rec is not None and not reuse:
            same_graph = (head is not None and rec._sym_head is not None
                          and rec._sym_head() is head()
                          and head() is not None)
            if not same_graph:
                n = 2
                while True:
                    cand = "%s#%d" % (key, n)
                    other = _REGISTRY.get(cand)
                    if other is None:
                        key, rec = cand, None
                        break
                    if (head is not None and other._sym_head is not None
                            and other._sym_head() is head()
                            and head() is not None):
                        key, rec = cand, other
                        break
                    n += 1
        if rec is None:
            rec = ProgramRecord(site, key)
            _REGISTRY[key] = rec
            while len(_REGISTRY) > _MAX_PROGRAMS:
                _REGISTRY.popitem(last=False)
        else:
            _REGISTRY.move_to_end(key)
        if arg_names is not None:
            rec.arg_names = list(arg_names)
        if head is not None:
            rec._sym_head = head
    if symbol is not None:
        # pass provenance: the registering site just built its graph
        # fns through _build_graph_fn, so the optimizer cache holds the
        # report for exactly this graph (None when passes are off)
        try:
            from . import passes as _passes

            prov = _passes.provenance_for(symbol)
            if prov is not None:
                rec.pass_report = prov
        except Exception:
            pass
    # sharding provenance: prefer what the shard pass actually stamped
    # on this graph; fall back to the plan active at registration
    try:
        if rec.sharding is None:
            if rec.pass_report is not None:
                for p in rec.pass_report.get("passes", ()):
                    if p.get("pass") == "shard" and p.get("plan"):
                        rec.sharding = p["plan"]
                        break
            if rec.sharding is None:
                from .sharding.plan import current_plan as _cur_plan

                plan = _cur_plan()
                if plan is not None:
                    rec.sharding = plan.describe()
    except Exception:
        pass
    return rec


def track_compile(record: ProgramRecord, seen_sigs: set, counter: str,
                  site: str, kind: str, sig: Tuple,
                  arg_names: Optional[Sequence[str]] = None):
    """The ONE retrace-accounting step every compile site runs per
    dispatch (Executor._track_sig, CachedOp._track_sig, FusedTrainLoop
    .run_stacked are thin wrappers that only build ``sig``).

    On a seen signature: bumps ``<counter>_hit`` and the record's hit
    count, returns None.  On a NEW signature: crosses the ``compile``
    fault-injection chokepoint (an XLA build is about to happen; flaky-
    compile recovery rides the retry policy), bumps ``<counter>_trace``,
    and returns the pending-compile token — the call site invokes
    ``tok.done(jitfn, args)`` right after the jit call so compile wall
    time and the lazy-analysis handle land in the registry.

    This is the <10us/call hot path measured by tools/check_inspect.py;
    keep it allocation-light.

    Thread-safe: serving workers share one CachedOp, so two threads
    can race the SAME new signature here.  The membership check and
    the add are one atomic section under ``_sig_lock`` — exactly one
    thread gets the compile token (the loser books a hit and rides
    jax's own once-per-signature compile internally), so N concurrent
    callers never inflate the retrace counters the CI guard
    (`tools/check_retrace.py`) bounds."""
    from . import profiler as _prof

    keyed = (kind, sig)
    with _sig_lock:
        if keyed in seen_sigs:
            fresh = False
        else:
            seen_sigs.add(keyed)
            fresh = True
    if not fresh:
        _prof.inc_stat(counter + "_hit")
        record.hit()
        return None
    from . import resilience as _res

    try:
        _res.fault_barrier("compile", site)
    except BaseException:
        # the compile never happened: un-claim the signature so a
        # caller-level retry of the whole dispatch attempts it again
        with _sig_lock:
            seen_sigs.discard(keyed)
        raise
    _prof.inc_stat(counter + "_trace")
    return record.begin_compile(kind, sig, arg_names=arg_names, site=site)


def find(name: str) -> Optional[ProgramRecord]:
    """Look up a program by exact registry name or unique substring."""
    with _lock:
        if name in _REGISTRY:
            return _REGISTRY[name]
        matches = [r for k, r in _REGISTRY.items() if name in k]
    if len(matches) == 1:
        return matches[0]
    if len(matches) > 1:
        raise MXNetError("program name %r is ambiguous: %s"
                         % (name, sorted(r.name for r in matches)))
    return None


def find_for_symbol(symbol) -> Optional[ProgramRecord]:
    """The most recently registered program bound to this Symbol
    (matched by graph head-node identity)."""
    try:
        head = symbol._outputs[0][0]
    except Exception:
        return None
    with _lock:
        records = list(_REGISTRY.values())
    for rec in reversed(records):
        ref = rec._sym_head
        if ref is not None and ref() is head:
            return rec
    return None


def programs(analyze: bool = True) -> List[Dict[str, Any]]:
    """Snapshot of every registered program (registration order).
    ``analyze=True`` (default) runs the lazy cost/memory analysis for
    each program's latest signature — may compile (see module doc)."""
    with _lock:
        records = list(_REGISTRY.values())
    return [r.as_dict(analyze=analyze) for r in records]


def analyze_all() -> int:
    """Force analysis of EVERY recorded signature (not just the latest
    per program); returns how many were analyzed.  Useful right before
    a telemetry flush so all ``compile`` events ship real figures."""
    with _lock:
        infos = [si for r in _REGISTRY.values() for si in r.sigs.values()]
    n = 0
    for si in infos:
        si.analyze()
        n += 1
    return n


def blame_summary() -> "collections.Counter":
    """Aggregated retrace culprits: blame string -> occurrence count."""
    with _lock:
        return collections.Counter(_BLAME)


def reset() -> None:
    """Drop all registry state (tests)."""
    with _lock:
        _REGISTRY.clear()
        _BLAME.clear()


def summary(analyze: bool = True) -> str:
    """Printable one-line-per-program table."""
    rows = programs(analyze=analyze)
    lines = ["%-44s %5s %5s %7s %9s %10s %10s"
             % ("program", "sigs", "comp", "hits", "wall(s)",
                "GFLOP", "peak(MB)")]
    for r in rows:
        lines.append("%-44s %5d %5d %7d %9.3f %10.3f %10.1f" % (
            r["name"][:44], r["n_sigs"],
            r["compiles"] + r["aot_compiles"], r["hits"],
            r["compile_wall_s"], r.get("flops", 0.0) / 1e9,
            r.get("peak_bytes", 0) / 2**20))
    for r in rows:
        for b in r.get("blame", []):
            lines.append("  blame[%s]: %s" % (r["name"][:40], b))
    return "\n".join(lines)


def hlo(name: str, kind: Optional[str] = None) -> str:
    """Optimized HLO text of a program's latest signature."""
    rec = find(name)
    if rec is None:
        raise MXNetError("no registered program matches %r" % name)
    si = rec.latest_sig(kind)
    if si is None:
        raise MXNetError("program %r has no %s signature"
                         % (rec.name, kind or "compiled"))
    return si.hlo_text()


# ---------------------------------------------------------------------------
# HLO histograms + per-op FLOPs (tools/hlo_report.py backend)
# ---------------------------------------------------------------------------

_DT_SIZE = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
            "s32": 4, "u32": 4, "s16": 2, "u16": 2, "pred": 1, "s8": 1,
            "u8": 1}


_STABLEHLO_RE = re.compile(
    r"=\s+(?:stablehlo|mhlo|chlo)\.(\w+)")
_STABLEHLO_RESULT_RE = re.compile(
    r"->\s*tensor<((?:\d+x)*)(\w+)>\s*$")


def _stablehlo_histogram(text: str) -> Dict[str, Any]:
    """Histogram a LOWERED (pre-optimization) StableHLO dump — the
    graph-level truth before XLA fusion/cancellation runs.  This is
    what makes layout deltas CI-checkable on CPU, where the optimized
    HLO fuses every transpose away regardless of how many the graph
    emitted (the TPU backend materializes them; see ROADMAP item 2)."""
    ops: "collections.Counter" = collections.Counter()
    convs = []
    transposes = []
    copies = 0
    for line in text.splitlines():
        m = _STABLEHLO_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        ops[op] += 1
        rm = _STABLEHLO_RESULT_RE.search(line.strip())
        dtype = rm.group(2) if rm else "f32"
        shape = rm.group(1).rstrip("x").replace("x", ",") if rm else ""
        if op == "convolution":
            convs.append((dtype, shape, ""))
        elif op == "transpose":
            transposes.append((dtype, shape))
        elif op == "copy":
            copies += 1
    t_bytes = 0
    for d, shape in transposes:
        n = 1
        for dim in shape.split(","):
            if dim:
                n *= int(dim)
        t_bytes += n * _DT_SIZE.get(d, 4)
    return {
        "op_histogram_top": dict(ops.most_common(15)),
        "n_convolutions": len(convs),
        "conv_dtypes": dict(collections.Counter(d for d, _, _ in convs)),
        "convolutions": convs[:32],
        "n_transposes_surviving": len(transposes),
        "transpose_traffic_mb": round(t_bytes / 2**20, 2),
        "n_copies_surviving": copies,
        "n_fusions": 0,
        "dialect": "stablehlo",
    }


def hlo_histogram(hlo_text: str) -> Dict[str, Any]:
    """Histogram an optimized-HLO dump: op kinds, conv dtypes/shapes,
    transposes/copies that SURVIVED fusion (= materialized layout
    traffic).  Ops inside ``%fused_*`` computation bodies are excluded
    — a transpose folded into a fusion costs no extra HBM round trip;
    only top-level (entry / while-body / conditional) instructions
    materialize.

    Also accepts LOWERED StableHLO text (``jit(...).lower().as_text()``)
    and histograms the PRE-optimization graph instead — there
    ``n_transposes_surviving`` counts what the graph emitted, before
    XLA cancellation (the layout pass's graph-level feedback signal)."""
    if "stablehlo." in hlo_text or "mhlo." in hlo_text:
        return _stablehlo_histogram(hlo_text)
    ops: "collections.Counter" = collections.Counter()
    convs = []
    transposes = []
    copies = 0
    in_fusion_body = False
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and "(" in s:  # computation header
            cname = s.lstrip("%").split()[0]
            in_fusion_body = cname.startswith(("fused_", "%fused_")) \
                or ".fused" in cname
            continue
        if s == "}":
            in_fusion_body = False
            continue
        if in_fusion_body:
            continue
        m = re.match(r"\S+\s+=\s+(\w+)\[([\d,]*)\]\S*\s+(\S+?)\(", s)
        if not m:
            continue
        dtype, shape, op = m.group(1), m.group(2), m.group(3)
        ops[op] += 1
        if op == "convolution":
            convs.append((dtype, shape,
                          ("window=" + re.search(r"window={([^}]*)}", s)
                           .group(1)) if "window={" in s else ""))
        elif op == "transpose":
            transposes.append((dtype, shape))
        elif op == "copy":
            copies += 1
    t_bytes = 0
    for d, shape in transposes:
        n = 1
        for dim in shape.split(","):
            if dim:
                n *= int(dim)
        t_bytes += n * _DT_SIZE.get(d, 4)
    return {
        "op_histogram_top": dict(ops.most_common(15)),
        "n_convolutions": len(convs),
        "conv_dtypes": dict(collections.Counter(d for d, _, _ in convs)),
        "convolutions": convs[:32],
        "n_transposes_surviving": len(transposes),
        "transpose_traffic_mb": round(t_bytes / 2**20, 2),
        "n_copies_surviving": copies,
        "n_fusions": ops.get("fusion", 0),
    }


_OP_FLOPS_CACHE: Dict[Tuple, Optional[float]] = {}


def op_flops(node, in_shapes, in_dtypes) -> Optional[float]:
    """XLA's FLOP estimate for ONE symbol node (lower the op alone and
    read ``cost_analysis``).  Used by `visualization.print_summary`'s
    FLOPs column.  Returns None when the op cannot be lowered in
    isolation.  Memoized by (op, attrs, shapes, dtypes) — each lower
    costs ~10 ms and big models repeat the same op config dozens of
    times (a ResNet summary would otherwise stall for minutes)."""
    try:
        ck = (node.op.name, repr(sorted(node.attrs.items())),
              tuple(tuple(s) for s in in_shapes),
              tuple(str(d) for d in in_dtypes))
        if ck in _OP_FLOPS_CACHE:
            return _OP_FLOPS_CACHE[ck]
    except Exception:
        ck = None
    try:
        import functools

        import jax
        import numpy as np

        attrs = dict(node.attrs)
        if node.op.train_aware:
            attrs.setdefault("is_train", False)
        structs = [jax.ShapeDtypeStruct(tuple(s), np.dtype(d))
                   for s, d in zip(in_shapes, in_dtypes)]
        fn = functools.partial(node.op.fn, **attrs)
        if node.op.needs_rng:
            key = jax.ShapeDtypeStruct((2,), np.uint32)
            lowered = jax.jit(fn).lower(key, *structs)
        else:
            lowered = jax.jit(fn).lower(*structs)
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        out = float(ca.get("flops", 0.0) or 0.0)
    except Exception:
        out = None
    if ck is not None:
        if len(_OP_FLOPS_CACHE) > 4096:
            _OP_FLOPS_CACHE.clear()
        _OP_FLOPS_CACHE[ck] = out
    return out


def report(name_or_record=None, kind: Optional[str] = None) -> Dict[str, Any]:
    """Full inspection report for one program (default: the most
    recently registered): cost analysis, memory analysis, compile wall
    time, blame history, and the HLO op/conv/transpose/fusion
    histograms.  The backend of ``tools/hlo_report.py``."""
    if isinstance(name_or_record, ProgramRecord):
        rec = name_or_record
    elif name_or_record is None:
        with _lock:
            if not _REGISTRY:
                raise MXNetError("no programs registered yet")
            rec = next(reversed(_REGISTRY.values()))
    else:
        rec = find(name_or_record)
        if rec is None:
            raise MXNetError("no registered program matches %r"
                             % name_or_record)
    si = rec.latest_sig(kind)
    if si is None:
        raise MXNetError("program %r has no %s signature"
                         % (rec.name, kind or "compiled"))
    analysis = si.analyze()
    out: Dict[str, Any] = {
        "program": rec.name, "site": rec.site, "kind": si.kind,
        "n_sigs": len(rec.sigs), "compiles": rec.compiles,
        "aot_compiles": rec.aot_compiles, "hits": rec.hits,
        "compile_wall_s": round(si.compile_wall_s, 6),
        "signature": si.sig,
        "cost": {k: analysis.get(k) for k in
                 ("flops", "bytes_accessed", "transcendentals")},
        "memory": {k: analysis.get(k) for k in
                   ("argument_bytes", "output_bytes", "temp_bytes",
                    "alias_bytes", "peak_bytes")},
    }
    # per-class/per-layer decomposition of that peak (mx.hbm) — the
    # decode reuses the analysis just run, so this is cheap here
    try:
        from . import hbm as _hbm

        mp = _hbm.plan(rec, kind=kind)
        if "error" not in mp:
            out["memory_plan"] = mp
    except Exception:
        pass
    if "error" in analysis:
        out["analysis_error"] = analysis["error"]
    blames = [s.blame for s in rec.sigs.values() if s.blame]
    if blames:
        out["blame"] = blames
    if rec.pass_report is not None:
        out["pass_report"] = rec.pass_report
    if rec.op_profile is not None:
        out["op_profile"] = rec.op_profile
    try:
        out.update(hlo_histogram(si.hlo_text()))
    except Exception as e:
        out["hlo_error"] = str(e)[:200]
    return out


# ---------------------------------------------------------------------------
# Device traces
# ---------------------------------------------------------------------------

class EmptyTraceError(MXNetError):
    """`trace(dir)` finished but the profiler produced no xplane file
    under the dir — the trace silently captured nothing (profiler
    already active elsewhere, a crashed plugin, an unwritable dir).
    Raised at trace exit so the caller learns NOW, not when a much
    later `mx.xprof.ingest`/TensorBoard load finds the dir empty."""


@contextlib.contextmanager
def trace(logdir: str = "/tmp/mxtpu_trace", **kwargs):
    """The supported device-trace entry point: run a block under
    ``jax.profiler`` so kernel-level device timelines land in
    ``logdir`` (open with TensorBoard's profile plugin or Perfetto,
    or feed the dir to ``mx.xprof.ingest`` for the per-op report).
    With layer attribution on (the default), trace rows and HLO op
    metadata carry the gluon/Symbol layer names::

        with mx.inspect.trace("/tmp/tb"):
            mod.forward(batch, is_train=True)

    Raises :class:`EmptyTraceError` when the profiler stopped without
    writing an ``*.xplane.pb`` under ``logdir`` (the block itself
    failing takes precedence — its exception propagates unchanged).
    """
    import jax

    jax.profiler.start_trace(logdir, **kwargs)
    ok = False
    try:
        yield logdir
        ok = True
    finally:
        jax.profiler.stop_trace()
        if ok:
            from . import xprof as _xprof

            if not _xprof.find_xplane_files(logdir):
                raise EmptyTraceError(
                    "trace produced no .xplane.pb under %r — the "
                    "profiler captured nothing (already active in "
                    "another trace? unwritable dir?)" % logdir)
