"""Drives the program ``mxtpu.parallel.transformer.make_fused_train_steps``
compiles: K steps (forward with the Pallas flash-attention kernel,
backward, Adam) of the decoder-only model on a one-device mesh."""
import jax

from mxtpu import profiler
from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP, AXIS_TP,
                                 AXIS_SP, AXIS_EP)

from . import common

_STACKED = ("wq", "wk", "wv", "wo", "ln1", "ln2", "w1", "w2")


class Driver(object):
    def __init__(self, cell, seed, ref):
        self.cell, self.seed, self.ref = cell, seed, ref
        self.k = int(cell.traffic["steps_per_program"])

    def setup(self):
        c = self.cell.config
        mesh = create_mesh({AXIS_DP: 1, AXIS_PP: 1, AXIS_TP: 1, AXIS_SP: 1,
                            AXIS_EP: 1}, devices=jax.devices()[:1])
        self.cfg = tf.TransformerConfig(
            vocab=c["vocab_size"], d_model=c["n_embd"], n_heads=c["n_head"],
            n_layers=c["n_layer"], d_ff=c["n_inner"],
            max_len=c["n_positions"], dtype=c["param_dtype"],
            remat=c["remat"])
        opt = c["optimizer"]
        if opt["name"] != "adam":
            raise ValueError("this driver runs Adam, not %r" % (opt["name"],))
        self.step, self.sh = tf.make_fused_train_steps(
            self.cfg, mesh, self.k, lr=opt["learning_rate"],
            optimizer="adam", betas=(opt["beta1"], opt["beta2"]),
            eps=opt["epsilon"])
        weights = self.ref.init_params(c, self.seed)
        self.params = {
            k: jax.device_put(v.reshape((1,) + v.shape) if k in _STACKED
                              else v, self.sh["params"][k])
            for k, v in weights.items()}
        del weights
        self.opt = tf.init_opt_state(self.cfg, mesh)

    def put(self, stack):
        return (jax.device_put(stack["data"], self.sh["data"]),
                jax.device_put(stack["label"], self.sh["data"]))

    def call(self, staged):
        self.params, self.opt, losses = self.step(self.params, self.opt,
                                                  staged[0], staged[1])
        return losses

    def sync(self):
        jax.block_until_ready((self.params, self.opt))

    def observe(self, losses, ring):
        """What the first program left behind: its losses, Adam's first
        moment as per-leaf norms, the weights as host arrays."""
        layers = self.cfg.n_layers
        shapes = {k: (v.shape[1:] if k in _STACKED else v.shape)
                  for k, v in self.params.items()}
        return {"losses": [float(x) for x in jax.device_get(losses)],
                "moment_norms": common.leaf_norms(self.opt["m"], _STACKED,
                                                  layers),
                "weights": common.to_host(self.params, shapes)}

    def snapshot(self):
        """A copy of the state, for the fault that puts it back."""
        return jax.tree_util.tree_map(jax.numpy.copy,
                                      (self.params, self.opt))

    def restore(self, state):
        self.params, self.opt = state

    def counters(self):
        return dict(profiler.stats())

    def release(self):
        self.params = self.opt = self.step = None
