#!/usr/bin/env python3
"""The third rehearsal of the on-chip guide: compile the cells' programs
at their REAL sizes for a described (not attached) TPU v5e chip, here on
the CPU host, before any chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmark/onchip/rehearse_compile.py

Compiles, per configuration in BENCHMARK.json: the reference's weight
maker and one training step of the reference (float32, as ``correct``
runs it), and for a decoder configuration the program's own fused K-step
program (``make_fused_train_steps`` on a mesh of the described chip, so
the Pallas kernels go through Mosaic).  Prints each program's memory
analysis.  The Module-based programs are not covered: ``Module.bind``
places real arrays on ``jax.devices()``, which here is the CPU; a compile
that passes is not a chip run and nothing it prints is a device metric.
"""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = harness.load_json(ROOT, "BENCHMARK.json")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    def report(name, lowered):
        t0 = time.perf_counter()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        print("%-46s compiled in %5.1fs  args %.2f GiB, out %.2f GiB, "
              "temp %.2f GiB" % (name, time.perf_counter() - t0,
                                 m.argument_size_in_bytes / 2 ** 30,
                                 m.output_size_in_bytes / 2 ** 30,
                                 m.temp_size_in_bytes / 2 ** 30), flush=True)
        return compiled

    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"])
        cfg, tr = cell.config, cell.traffic
        ref = harness.load_module("reference", cfg["reference"])
        b = int(tr["batch"])
        shapes = {name: sds(shape, jnp.float32)
                  for name, shape, _ in ref.layout(cfg)}
        if cfg["input"]["kind"] == "image":
            x = sds([b] + cfg["input"]["shape"], jnp.float32)
            y = sds([b], jnp.int32)
            fn = jax.jit(jax.value_and_grad(
                lambda p, x, y: ref.loss_fn(cfg, p, x, y, "f32")))
        else:
            x = y = sds([b, cfg["input"]["length"]], jnp.int32)
            fn = jax.jit(lambda p, x, y: ref._grad_of_mean(
                cfg, p, x, y, 2, "f32"))
        report("%s: reference loss+grad (f32)" % cell.name,
               fn.lower(shapes, x, y))
        if tr["driver"] == "lm_fused":
            from jax.sharding import Mesh, NamedSharding
            import numpy as np
            from mxtpu.ops import pallas_attention as pa
            from mxtpu.parallel import transformer as tf
            from mxtpu.parallel.mesh import (AXIS_DP, AXIS_PP, AXIS_TP,
                                             AXIS_SP, AXIS_EP)

            # the program asks jax.devices() whether it is on a TPU and
            # would take its CPU branch (materialized attention) here;
            # the rehearsal steers that in this script, not by an option
            pa._on_tpu = lambda: True
            mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1, 1, 1),
                        (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP, AXIS_EP))
            tcfg = tf.TransformerConfig(
                vocab=cfg["vocab_size"], d_model=cfg["n_embd"],
                n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
                d_ff=cfg["n_inner"], max_len=cfg["n_positions"],
                dtype=cfg["param_dtype"], remat=cfg["remat"])
            k = int(tr["steps_per_program"])
            step, sh = tf.make_fused_train_steps(
                tcfg, mesh, k, lr=cfg["optimizer"]["learning_rate"],
                optimizer="adam")
            pshapes = tf.param_shapes(tcfg, 1)
            params = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                              sharding=sh["params"][n])
                      for n, s in pshapes.items()}
            moments = {n: jax.ShapeDtypeStruct(
                s, jnp.float32, sharding=sh["opt_state"]["m"][n])
                for n, s in pshapes.items()}
            opt = {"m": moments, "v": dict(moments),
                   "t": jax.ShapeDtypeStruct((), jnp.float32,
                                             sharding=sh["opt_state"]["t"])}
            data = jax.ShapeDtypeStruct((k, b, cfg["input"]["length"]),
                                        jnp.int32, sharding=sh["data"])
            compiled = report("%s: the program's fused K=%d step"
                              % (cell.name, k),
                              step.lower(params, opt, data, data))
            n_pallas = compiled.as_text().count("tpu_custom_call")
            print("   tpu_custom_call sites in the compiled program: %d"
                  % n_pallas)
            if not n_pallas:
                raise SystemExit("no Mosaic kernel in the LM program")
    print("REHEARSAL ONLY: nothing above is a device metric")


if __name__ == "__main__":
    main()
