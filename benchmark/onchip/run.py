#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark.

    python3 benchmark/onchip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process, the machine it is started on.  With no TPU visible to JAX,
or fewer chips than the cell asks for, it exits non-zero and prints no
result.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``), then ``compared``: each number that
``correct`` rests on beside its limit.  The same numbers are the last
lines of standard error.

``--rehearse`` walks the same code on CPU devices at the tiny sizes the
configuration and traffic files give under ``rehearse``, with the Pallas
kernel interpreted.  Its line names the device ``cpu``; it checks the
control flow and says nothing about the chip.
"""
import time

T_START = time.perf_counter()       # set-up counts from here

import argparse     # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU walk-through at tiny sizes; not a chip result")
    ap.add_argument("--fault", default=None,
                    help="break the timed path underneath (faults.py); "
                         "for the tests and for reading a fault on the "
                         "chip, never in a benchmark run")
    return ap.parse_args(argv)


def _environment(rehearse):
    """Before JAX is imported: where the compile cache lives, and for a
    rehearsal the CPU platform."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed path inside the checkout: the path is part of the key
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["MXTPU_PALLAS_INTERPRET"] = "1"
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)


def run_cell(args, fault=None, out=sys.stdout, err=sys.stderr):
    """Drive one cell to its result line.  ``fault`` is for the tests
    under ``tests/``: it breaks the timed path underneath the harness
    (see faults.py) so that ``correct`` can be seen to come out false;
    ``--fault`` does the same from the command line."""
    _environment(args.rehearse)
    import harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.Cell(bench, args.workload, rehearse=args.rehearse)

    import jax

    parts = {"import_jax": time.perf_counter() - T_START}
    dev0 = jax.devices()[0]
    parts["device_client"] = time.perf_counter() - T_START \
        - parts["import_jax"]
    want = "cpu" if args.rehearse else "tpu"
    if dev0.platform != want or len(jax.devices()) < cell.chips:
        print("run.py: needs %d %s device(s); JAX sees %d of platform %r"
              % (cell.chips, want, len(jax.devices()), dev0.platform),
              file=err)
        return 3
    if args.rehearse:
        print("REHEARSAL (cpu) -- not a chip result", file=err)

    import compare
    import traffic
    import peaks as peak_table

    fault = fault or getattr(args, "fault", None)

    meter = harness.CompileMeter()
    traced = bool(args.trace)
    spans = harness.Spans(traced)
    ref = harness.load_module("reference", cell.config["reference"])
    comparison = harness.load_module("comparisons",
                                     cell.traffic["comparison"])
    driver = harness.load_module(
        "drivers", cell.traffic["driver"]).Driver(cell, args.seed, ref)
    if fault is not None:
        import faults

        faults.plant(fault, driver)

    # ---- set-up: the program, its weights from the seed, the host ring,
    # the stager, and the first steps through the window's own call
    # (the ring is drawn beside the program's own set-up, not after it)
    parts["imports"] = time.perf_counter() - T_START    # all up to here
    with ThreadPoolExecutor(max_workers=1) as pool:
        t_part = time.perf_counter()
        drawing = pool.submit(traffic.host_ring, cell.config, cell.traffic,
                              args.seed)
        driver.setup()
        parts["program"] = time.perf_counter() - t_part
        ring = drawing.result()
        parts["program_and_ring"] = time.perf_counter() - t_part
    stager = harness.Stager(ring, driver.put, int(cell.traffic["ahead"]),
                            spans).start()
    memory = {}      # the allocator's readings along the run
    try:
        # the first program goes through the window's own call and feed;
        # what it left is read without a copy of the state on the device
        t_part = time.perf_counter()
        staged = stager.get()
        memory["set_up"] = harness.memory_now(cell.chips)
        first = driver.call(staged)
        harness.wait_ready(first)
        driver.sync()
        memory["first_program"] = harness.memory_now(cell.chips)
        observed = driver.observe(first, ring)
        memory["observed"] = harness.memory_now(cell.chips)
        del first, staged
        parts["first_steps"] = time.perf_counter() - t_part
        # what the program does only every so many calls (its deferred
        # health read, its first sampled sync) happens before the window
        for _ in range(int(cell.traffic.get("warm_calls", 0))):
            harness.wait_ready(driver.call(stager.get()))
        while not stager.full():        # the queue is whole at the open
            time.sleep(0.005)
        driver.sync()
        counters0 = driver.counters()
        compiles0, compile_s0 = meter.count, meter.seconds
        setup_s = time.perf_counter() - T_START

        # ---- the window
        seconds = args.seconds
        trace_dir = None
        if traced:
            seconds = min(seconds, float(cell.traffic["trace_seconds"]))
            trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with spans.span("bench:window"):
                programs, elapsed, t_open = harness.run_window(
                    driver, stager, seconds, spans)
        finally:
            if traced:
                jax.profiler.stop_trace()
        window_compiles = meter.count - compiles0
        counters1 = driver.counters()
        device = harness.device_record(cell.chips)
        memory["window_close"] = harness.memory_now(cell.chips)
    finally:
        stager.close()

    k = int(cell.traffic["steps_per_program"])
    steps = programs * k
    rate = programs * traffic.samples_per_program(cell.config,
                                                  cell.traffic) / elapsed
    values = {"setup_s": setup_s,
              cell.traffic["rate_metric"]: rate,
              "peak_hbm_gib": device["memory_peak_bytes"] / 2.0 ** 30}

    # ---- correct: the program's state goes, then the plain reference
    # follows the same first steps from the same seed
    driver.release()
    del driver, stager
    harness.free_device_memory()
    t_ref = time.perf_counter()
    nums, where = comparison.check(cell, ref, observed, ring, args.seed)
    correct, rows = compare.verdict(nums, cell.limits)
    if window_compiles:
        correct = False
    compared = {name: {"value": value, "limit": limit}
                for name, value, limit, _ in rows}
    compared["window_compiles"] = {"value": window_compiles, "limit": 0}
    reference_s = time.perf_counter() - t_ref

    result = {"correct": bool(correct), "attempted": steps, "failed": 0}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    if not traced:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    else:
        import trace_reduce

        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = {"cell": cell, "steps": steps, "programs": programs,
               "elapsed_s": elapsed, "spans": spans.rows,
               "window": (t_open, t_open + elapsed),
               "window_compiles": window_compiles,
               "setup_compile_s": compile_s0, "trace": reduced,
               "device": device,
               # a CPU has no row in the table: peak shares stay silent
               "peaks": None if args.rehearse
               else peak_table.of(device["kind"])}
        metrics = {}
        for m in cell.per_layer:
            value = harness.load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        result["metrics"] = metrics
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
    result["device"] = device
    t_close = t_open + elapsed
    span_s = {}
    for name, s0, s1 in spans.rows:
        if s0 >= t_open and s1 <= t_close and name != "bench:window":
            span_s[name] = span_s.get(name, 0.0) + (s1 - s0)
    result["info"] = {"programs": programs, "elapsed_s": elapsed,
                      "setup_s": setup_s, "setup_parts_s": parts,
                      "reference_s": reference_s, "memory_bytes": memory,
                      "span_s": span_s,
                      "worst_at": where, "rehearsal": bool(args.rehearse),
                      "counters": {key: counters1.get(key, 0)
                                   - counters0.get(key, 0)
                                   for key in counters1}}
    result["compared"] = compared       # last, as the contract asks

    out.flush()
    for name, c in compared.items():
        print("compared %s = %r (limit %r)%s"
              % (name, c["value"], c["limit"],
                 "" if c["limit"] is not None and c["value"] <= c["limit"]
                 else "  <-- FAILS"), file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0


def main(argv=None):
    args = _args(argv)
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
