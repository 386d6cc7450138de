"""The arithmetic behind the per-layer metrics that read the PROGRAM's
own spans (``mxtpu.profiler.spans()``; the ``mx:`` vocabulary is in
docs/observability.md), beside ``readers.py``, which reads the
harness's.  The program records them while a JAX profiler session is
live, so the traced run's window holds them; a program that has no such
spans (the parent of the PR that brought them, a loop that is not
instrumented) gives no rows, the reader returns None and the metric is
left out of the result's line.

A row is a dict: ``name``, ``t0`` / ``t1`` (``time.perf_counter()``
seconds, the clock of ``run["window"]``), ``tid``, ``parent``, ``step``.
Only rows wholly inside the window count, and everything is divided by
the window's steps as the host counted them (``run["steps"]``).
"""
STEP = "mx:step"
WAIT = "mx:device_wait"
OBSERVE = "mx:observe."


def program_rows():
    """The program's span rows, or [] where it keeps none."""
    try:
        from mxtpu import profiler

        return profiler.spans()
    except (ImportError, AttributeError):
        return []


def in_window(rows, window):
    t0, t1 = window
    return [r for r in rows if r["t0"] >= t0 and r["t1"] <= t1]


def inside(row, rows):
    """The rows of ``row``'s own thread that lie inside it."""
    return [r for r in rows if r is not row and r["tid"] == row["tid"]
            and r["t0"] >= row["t0"] and r["t1"] <= row["t1"]]


def covered_seconds(row, rows, pick):
    """Seconds of ``row`` covered by the rows inside it that ``pick``
    takes (the union: nested or repeated rows count once)."""
    total, edge = 0.0, row["t0"]
    for s, e in sorted((r["t0"], r["t1"]) for r in inside(row, rows)
                       if pick(r)):
        if e > edge:
            total += e - max(s, edge)
            edge = e
    return total


def self_seconds(row, rows, pick):
    """A span's self time: its duration less what the picked rows inside
    it cover."""
    return (row["t1"] - row["t0"]) - covered_seconds(row, rows, pick)


def _is_wait(r):
    return r["name"] == WAIT


def _per_step(run, of):
    """The sum of ``of(step row, rows)`` seconds over the window's
    ``mx:step`` rows, as milliseconds per step of the window; None where
    the program recorded no ``mx:step`` there."""
    rows = in_window(program_rows(), run["window"])
    steps = [r for r in rows if r["name"] == STEP]
    if not run["steps"] or not steps:
        return None
    return sum(of(st, rows) for st in steps) * 1e3 / run["steps"]


def loop_host_ms_per_step(run):
    """What the host does itself inside the loop's call: each
    ``mx:step`` less the ``mx:device_wait`` spans inside it."""
    return _per_step(run, lambda st, rows: self_seconds(st, rows, _is_wait))


def loop_device_wait_ms_per_step(run):
    """Where the loop's call blocks on device results: the
    ``mx:device_wait`` spans inside each ``mx:step``."""
    return _per_step(run,
                     lambda st, rows: covered_seconds(st, rows, _is_wait))


def observer_ms_per_step(run):
    """The observers' hooks (``mx:observe.<module>``) inside each
    ``mx:step``, without the device wait inside any of them."""
    return _per_step(run, lambda st, rows: sum(
        self_seconds(r, rows, _is_wait) for r in inside(st, rows)
        if r["name"].startswith(OBSERVE)))
