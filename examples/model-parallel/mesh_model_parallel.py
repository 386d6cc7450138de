"""Model parallelism on a device mesh.

The reference's `example/model-parallel/` places layer groups on
different GPUs via `group2ctx` (`graph_executor.cc:1594`).  That style
of per-node placement does not map to XLA's compilation model — this
framework raises on multi-device group2ctx (`symbol/symbol.py`) and
does model parallelism the TPU way instead: shard the weight matrices
over a `Mesh` axis and let XLA insert the collectives
(`mxtpu.parallel`, Megatron column/row split).

This script runs a 2-layer MLP whose hidden dimension is split over
the `tp` axis: layer 1 column-parallel (no comm), layer 2 row-parallel
(ONE psum), exactly the Megatron-LM pattern.  On a host with no TPUs it
builds a virtual 8-device CPU mesh so the sharding is still exercised.

Run:  python mesh_model_parallel.py [--tp 4]
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))

import argparse
import logging

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tp", type=int, default=4)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--steps", type=int, default=10)
    args = p.parse_args()
    logging.basicConfig(level=logging.INFO)

    # on a host with no chips a virtual CPU mesh stands in; the flag
    # only concerns the CPU platform and must land before its backend
    # starts
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=%d"
            % max(args.tp, 8)).strip()

    import jax

    if len(jax.devices()) < args.tp:
        raise SystemExit("need >= %d devices for tp=%d (got %d); run "
                         "with more chips or a larger CPU mesh"
                         % (args.tp, args.tp, len(jax.devices())))
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxtpu import parallel

    n_dev = len(jax.devices())
    mesh = parallel.create_mesh({"dp": n_dev // args.tp, "tp": args.tp})
    logging.info("mesh: %s", mesh)

    rng = np.random.RandomState(0)
    din, hidden, dout, batch = 64, args.hidden, 32, 128
    W1 = jnp.asarray(rng.normal(0, 0.05, (din, hidden)).astype(np.float32))
    W2 = jnp.asarray(rng.normal(0, 0.05, (hidden, dout)).astype(np.float32))
    Wt = jnp.asarray(rng.normal(0, 1.0, (din, dout)).astype(np.float32))
    X = jnp.asarray(rng.normal(0, 1, (batch, din)).astype(np.float32))
    Y = jnp.tanh(X @ Wt)

    # Megatron shardings: W1 column-split, W2 row-split over `tp`
    shard = {
        "W1": NamedSharding(mesh, P(None, "tp")),
        "W2": NamedSharding(mesh, P("tp", None)),
        "X": NamedSharding(mesh, P("dp", None)),
    }
    W1 = jax.device_put(W1, shard["W1"])
    W2 = jax.device_put(W2, shard["W2"])
    X = jax.device_put(X, shard["X"])

    def loss_fn(params, x, y):
        h = jnp.maximum(x @ params["W1"], 0)   # local: columns are split
        out = h @ params["W2"]                 # XLA inserts the psum here
        return jnp.mean((out - y) ** 2)

    @jax.jit
    def step(params, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        return loss, {k: params[k] - 0.1 * grads[k] for k in params}

    params = {"W1": W1, "W2": W2}
    first = None
    for i in range(args.steps):
        loss, params = step(params, X, Y)
        if first is None:
            first = float(loss)
    logging.info("loss %.4f -> %.4f over %d steps (tp=%d)", first,
                 float(loss), args.steps, args.tp)
    # the weights stayed sharded through every step
    assert params["W1"].sharding.spec == P(None, "tp")
    assert float(loss) < first
    logging.info("per-device W1 shard shape: %s",
                 params["W1"].addressable_shards[0].data.shape)


if __name__ == "__main__":
    main()
