"""The benchmark's core: one cell, one process, one window.

Everything that belongs to one cell is data the harness finds by name:
``BENCHMARK.json`` names the cell's configuration and traffic.  The
traffic file says which driver under ``drivers/`` feeds which loop of
the program and which comparison under ``comparisons/`` decides
``correct``; the configuration file names its plain reference under
``reference/``, its family under ``families/`` (FLOPs from shapes) and
its input kind under ``inputs/`` (what one batch is made of); each
per-layer metric is a reader under ``metrics/`` and the limits of
``correct`` are in ``limits/<cell>.json``.  Nothing here names a cell,
a family or an input kind.
"""
import gc
import importlib
import importlib.util
import json
import os
import queue
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """Import ``<kind>/<name>.py`` from the benchmark's directory; names
    may hold dots (``mfu_pct.fused``), so this goes by path."""
    if kind in ("drivers", "reference"):     # packages: relative imports
        return importlib.import_module("%s.%s" % (kind, name))
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError("no %s named %r (%s)" % (kind, name, path))
    modname = "onchip_%s_%s" % (kind, name.replace(".", "_").replace("-", "_"))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell(object):
    """One entry of ``workloads`` with its files read."""

    def __init__(self, bench, name, rehearse=False):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit("unknown workload %r; BENCHMARK.json has %s"
                             % (name, sorted(by_name)))
        self.name = name
        self.entry = by_name[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(ROOT, cfg_entry["file"])
        self.traffic = load_json(HERE, "traffic",
                                 self.entry["traffic"] + ".json")
        self.rehearse = rehearse
        if rehearse:
            # tiny sizes for a CPU walk-through; the files say which
            self.config = dict(self.config, **self.config["rehearse"])
            self.traffic = dict(self.traffic, **self.traffic["rehearse"])
        limits = os.path.join(HERE, "limits", name + ".json")
        self.limits = load_json(limits) if os.path.exists(limits) else None
        if rehearse and self.limits:
            # the CPU walk-through's own readings, at its own sizes
            self.limits = self.limits.get("rehearse")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


class CompileMeter(object):
    """JAX's own duration event around every executable build (a backend
    compile or, with a warm persistent cache, the read that replaces it)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


class Spans(object):
    """Host spans kept in memory: (name, start, end) on perf_counter.
    With tracing on each span is also a ``TraceAnnotation`` so that the
    device trace's idle gaps can be laid against them."""

    def __init__(self, traced):
        self.rows = []
        self.traced = traced
        self._lock = threading.Lock()

    def span(self, name):
        return _Span(self, name)


class _Span(object):
    def __init__(self, owner, name):
        self.owner, self.name, self.ann = owner, name, None

    def __enter__(self):
        if self.owner.traced:
            import jax

            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        with self.owner._lock:
            self.owner.rows.append((self.name, self.t0, t1))
        return False


class Stager(object):
    """The input layer of the harness: a thread that turns the host ring's
    stacks into device arrays and keeps ``ahead`` of them waiting, so a
    host hiccup shorter than ``ahead`` programs never reaches the device.

    ``put`` is the driver's function from one host stack to what its
    loop's call takes (device arrays, readied here, not in the loop)."""

    def __init__(self, ring, put, ahead, spans):
        self.ring, self.put, self.spans = ring, put, spans
        # one stack waits in the thread's hands while the queue is full
        self.q = queue.Queue(maxsize=max(1, ahead - 1))
        self.stop = threading.Event()
        self.error = None
        self.thread = threading.Thread(target=self._run, name="bench-stager",
                                       daemon=True)

    def start(self):
        self.thread.start()
        return self

    def _run(self):
        import jax

        i = 0
        try:
            while not self.stop.is_set():
                with self.spans.span("bench:stage"):
                    staged = self.put(self.ring[i % len(self.ring)])
                    jax.block_until_ready(staged)
                i += 1
                while not self.stop.is_set():
                    try:
                        self.q.put(staged, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                staged = None
        except BaseException as e:      # surfaced by get()
            self.error = e

    def get(self):
        with self.spans.span("bench:wait"):
            while True:
                if self.error is not None:
                    raise self.error
                try:
                    item = self.q.get(timeout=0.5)
                    break
                except queue.Empty:
                    continue
        return item

    def full(self):
        return self.q.full()

    def close(self):
        self.stop.set()
        while True:                      # unblock a waiting put
            try:
                self.q.get_nowait()
            except queue.Empty:
                break
        self.thread.join(timeout=30)
        if self.thread.is_alive():
            raise RuntimeError("the stager thread did not stop")


def wait_ready(tree, poll_s=0.0005):
    """Return once every array of ``tree`` is ready, by asking each
    ``is_ready()`` every half millisecond.  A blocking
    ``block_until_ready`` returned 1-2 s late on some hosts (PERF.md,
    Open questions); a poll's own error is under a millisecond."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        ready = getattr(leaf, "is_ready", None)
        if ready is None:
            continue
        while not ready():
            time.sleep(poll_s)


def run_window(driver, stager, seconds, spans):
    """Dispatch whole programs until ``seconds`` have passed, at most two
    in flight (one running, one queued behind it: the device never waits
    for the host, the host never runs away from the device), and close
    when the last program's outputs are ready (``wait_ready``, then the
    driver's own ``block_until_ready`` on its state).  Returns the
    programs run and the elapsed seconds; every sample of every program
    counts."""
    in_flight = []
    programs = 0
    t_open = time.perf_counter()
    while True:
        staged = stager.get()
        if len(in_flight) == 2:
            with spans.span("bench:backpressure"):
                wait_ready(in_flight.pop(0))
        with spans.span("bench:call"):
            out = driver.call(staged)
        staged = None
        in_flight.append(out)
        programs += 1
        if time.perf_counter() - t_open >= seconds:
            break
    with spans.span("bench:close"):
        wait_ready(in_flight)
        driver.sync()
    return programs, time.perf_counter() - t_open, t_open


def memory_now(chips):
    """What the allocator says now, on the fullest of the first
    ``chips`` devices: ``in_use`` and ``reserved`` bytes and the peak of
    each.  On the TPU the live arrays are ``bytes_in_use``; the scratch
    a loaded program needs for its temporaries is held apart, under
    ``bytes_reserved``, from the program's first run on (measured, PR
    25: the LM's 7.21 GiB there is the compiler's peak less its
    arguments), and stays while the program is loaded."""
    import jax

    rows = [(d.memory_stats() or {}) for d in jax.devices()[:chips]]
    return {key: max(int(r.get(name, 0)) for r in rows)
            for key, name in (("in_use", "bytes_in_use"),
                              ("peak_in_use", "peak_bytes_in_use"),
                              ("reserved", "bytes_reserved"),
                              ("peak_reserved", "peak_bytes_reserved"))}


def device_record(chips):
    """The result line's ``device``.  ``memory_peak_bytes`` is the peak
    of the live arrays plus the peak of the programs' reserved scratch:
    what the run needed of the chip's memory, temporaries included."""
    import jax

    now = memory_now(chips)
    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": now["peak_in_use"] + now["peak_reserved"]}


def free_device_memory():
    gc.collect()
    import jax

    jax.clear_caches()
    gc.collect()
