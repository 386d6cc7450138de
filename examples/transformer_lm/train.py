"""Train a sharded TransformerLM on a character copy-task corpus.

The reference has NO transformer and no tensor/sequence/expert
parallelism (SURVEY.md §2.4); this example is the new-capability
counterpart of `example/rnn/word_lm` showing the framework's flagship
SPMD stack end-to-end as a USER would drive it:

  * `TransformerConfig` + `create_mesh` choose the parallel layout
    (dp × tp × sp here; add pp/ep the same way),
  * `make_train_step(..., optimizer="adam")` returns ONE jitted step —
    ZeRO-1 sharded Adam, ring attention over "sp", Megatron col/row
    sharding over "tp", gradient psum over "dp" — with the shardings to
    place the data,
  * the loop just feeds globally-shaped [B, T] token batches.

Run (any host — the mesh is virtual CPU devices unless real chips
exist):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python train.py --steps 60

The task is next-char prediction on sequences of the form
"abcabcabc..." with a random phase/alphabet per sample — a tiny
dataset the model must actually learn (loss drops from ~ln(V) to near
0), so the example doubles as a convergence check.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def make_batch(rng, batch, seqlen, vocab, period=3):
    """Periodic sequences with random phase + offset; label = next char."""
    offs = rng.randint(0, vocab - period, size=(batch, 1))
    phase = rng.randint(0, period, size=(batch, 1))
    pos = np.arange(seqlen + 1)[None, :] + phase
    toks = (pos % period) + offs
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seqlen", type=int, default=32)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--sp", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--remat", default="none",
                    choices=("none", "dots", "dots_no_batch", "full"),
                    help="per-layer gradient checkpointing; 'full' is "
                         "what makes very long sequences fit one chip")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    import jax

    from mxtpu.parallel import transformer as tf
    from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP,
                                     AXIS_TP, AXIS_SP, AXIS_EP)

    need = args.dp * args.tp * args.sp
    if len(jax.devices()) < need:
        raise SystemExit(
            "need %d devices (dp*tp*sp); run under JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=%d"
            % (need, need))

    cfg = tf.TransformerConfig(vocab=32, d_model=64, n_heads=4,
                               n_layers=2, d_ff=128,
                               max_len=args.seqlen, remat=args.remat)
    # size-1 axes stay in the mesh so every PartitionSpec resolves;
    # XLA elides collectives over singletons (grow pp/ep the same way)
    mesh = create_mesh({AXIS_DP: args.dp, AXIS_PP: 1, AXIS_TP: args.tp,
                        AXIS_SP: args.sp, AXIS_EP: 1})
    params = tf.init_params(cfg, mesh, seed=0)
    opt = tf.init_opt_state(cfg, mesh)
    step, shardings = tf.make_train_step(cfg, mesh, lr=args.lr,
                                         optimizer="adam")

    rng = np.random.RandomState(0)
    place = lambda x: jax.device_put(x, shardings["data"])
    first = last = None
    for it in range(args.steps):
        toks, labels = make_batch(rng, args.batch, args.seqlen,
                                  cfg.vocab)
        params, opt, loss = step(params, opt, place(toks), place(labels))
        loss = float(loss)
        first = loss if first is None else first
        last = loss
        if it % args.log_every == 0 or it == args.steps - 1:
            print("step %3d  loss %.4f" % (it, loss))
    print("first->last: %.4f -> %.4f" % (first, last))
    if last < first * 0.5:
        print("CONVERGED")
    else:
        raise SystemExit("did not converge")


if __name__ == "__main__":
    main()
