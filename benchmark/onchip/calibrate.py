#!/usr/bin/env python3
"""Read the two ends that a cell's limits of ``correct`` stand between.

    python3 benchmark/onchip/calibrate.py --workload <name> \
        --seeds 101,102,... [--controls 3] [--out chiprun_out/x.jsonl]

One process (set-up is long, so the program's dozen seeds and the
control's are read together).  For every seed: the program's first steps
through the window's own call and feed, then the plain reference over the
same steps, and the comparison's numbers between them, each set beside
the verdict under the cell's committed limits: the LOWER reading is the
largest over the seeds.  For the first ``--controls``
seeds also, each put in the program's place and compared with the
reference in the same way:

* the control: the reference computed in the nearest precision below the
  configuration's bfloat16 (``int8`` operands).  The UPPER reading of a
  number is the smallest the control gives;
* ``bf16``: the reference with bfloat16 operands, what the configuration
  allows; for the record, it has to pass;
* the fault ``half_batch``: half of every batch left out, the mean taken
  over the rest.

The benchmark's own runs never call this; ``limits/<cell>.json`` records
what it read and the limits set from it.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import run as run_mod

    run_mod._environment(args.rehearse)
    import jax

    import compare
    import harness
    import traffic

    want = "cpu" if args.rehearse else "tpu"
    if jax.devices()[0].platform != want:
        print("calibrate.py: needs a %s device" % want, file=sys.stderr)
        return 3
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell = harness.Cell(bench, args.workload, rehearse=args.rehearse)
    cell.traffic = dict(cell.traffic, ring=max(
        1, -(-int(cell.traffic["follow_steps"])
             // int(cell.traffic["steps_per_program"]))))
    ref = harness.load_module("reference", cell.config["reference"])
    cmp = harness.load_module("comparisons", cell.traffic["comparison"])
    drivers = harness.load_module("drivers", cell.traffic["driver"])
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        spans = harness.Spans(False)
        driver = drivers.Driver(cell, seed, ref)
        driver.setup()
        ring = traffic.host_ring(cell.config, cell.traffic, seed)
        stager = harness.Stager(ring, driver.put, int(cell.traffic["ahead"]),
                                spans).start()
        try:
            first = driver.call(stager.get())
            harness.wait_ready(first)
            driver.sync()
            observed = driver.observe(first, ring)
            del first
        finally:
            stager.close()
        peak = harness.device_record(cell.chips)["memory_peak_bytes"]
        driver.release()
        del driver, stager
        harness.free_device_memory()
        t1 = time.perf_counter()

        start = ref.init_params(cell.config, seed)
        reference = cmp.follow(cell, ref, ring, seed, start=start)
        t2 = time.perf_counter()
        nums, where = cmp.numbers(cmp.settle(ref, observed, start),
                                  reference)

        def held(numbers):
            """The numbers beside the verdict under the cell's limits."""
            ok, rows = compare.verdict(numbers, cell.limits)
            return {"numbers": numbers, "correct": ok,
                    "fails": [r[0] for r in rows if not r[3]]}

        row = {"workload": cell.name, "seed": seed, "program": held(nums),
               "worst_at": where, "losses": observed["losses"][:4],
               "reference_losses": reference["losses"][:4],
               "program_s": t1 - t0, "reference_s": t2 - t1,
               "memory_peak_bytes": peak}
        if n < args.controls:
            for name, kw in (("control_int8", {"mode": "int8"}),
                             ("bf16", {"mode": "bf16"}),
                             ("fault_half_batch", {"fault": "half_batch"})):
                try:
                    row[name] = held(cmp.numbers(
                        cmp.follow(cell, ref, ring, seed, **kw),
                        reference)[0])
                except Exception as e:      # a control that crashes has
                    row[name] = {"error": repr(e)}   # failed; no upper end
            row["controls_s"] = time.perf_counter() - t2
        emit(row)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, HERE]
    sys.exit(main())
