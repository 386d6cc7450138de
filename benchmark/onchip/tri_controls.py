#!/usr/bin/env python3
"""``calibrate.py`` for a cell whose reference has controls of its own.

    python3 benchmark/onchip/tri_controls.py --workload <name> \
        --seeds 101,102,... [--controls 2] [--modes no_window,int8,bf16] \
        [--out chiprun_out/x.jsonl]

``calibrate.py`` reads three fixed controls (``int8``, ``bf16``, the
fault ``half_batch``) and cannot be edited by a PR that adds a cell.
``reference/trinity_mini.py`` has one more, ``no_window``: the reference
in float32 with plain causal masks on every layer, which the limits of
``trinitym_ep16_fused_k4`` have to refuse.  This script reads the same
rows as ``calibrate.py`` (the program's first steps through the window's
own call and feed, the plain reference over the same steps, the
comparison's numbers beside the verdict under the committed limits) and,
for the first ``--controls`` seeds, the reference run in each of
``--modes`` and under the fault ``half_batch``, each put in the
program's place.  The benchmark's own runs never call this.
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
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--modes", default="no_window,int8,bf16")
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
        print("tri_controls.py: needs a %s device" % want, file=sys.stderr)
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
    controls = [("control_" + m, {"mode": m})
                for m in args.modes.split(",") if m] \
        + [("fault_half_batch", {"fault": "half_batch"})]

    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver = drivers.Driver(cell, seed, ref)
        driver.setup()
        ring = traffic.host_ring(cell.config, cell.traffic, seed)
        stager = harness.Stager(ring, driver.put, int(cell.traffic["ahead"]),
                                harness.Spans(False)).start()
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
            for name, kw in controls:
                try:
                    row[name] = held(cmp.numbers(
                        cmp.follow(cell, ref, ring, seed, start=start, **kw),
                        reference)[0])
                except Exception as e:      # a control that crashes has
                    row[name] = {"error": repr(e)}   # failed; no upper end
            row["controls_s"] = time.perf_counter() - t2
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, HERE]
    sys.exit(main())
