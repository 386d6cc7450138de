"""Drives the program ``mxtpu.parallel.transformer.make_fused_train_steps``
compiles for Ouro's LoopLM (``ouro``: one stack of dense layers with
four norms a layer, run ``total_ut_steps`` times on the same weights,
a norm, an exit gate and a loss at every pass, combined by the exit
distribution): the entry point, mesh, optimizer and kernels ``lm_fused``
drives, with the configuration's published keys turned into
``TransformerConfig``'s fields, and the loop's counters read as
``lm_glm_fused`` reads the routed experts'."""
import jax

from mxtpu import profiler
from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP, AXIS_TP,
                                 AXIS_SP, AXIS_EP)

from . import common, lm_fused, lm_glm_fused


def transformer_config(c):
    """``TransformerConfig`` from the configuration file's keys.  Every
    published layer is a full-attention one with no window."""
    if set(c["layer_types"]) != {"full_attention"} or c["sliding_window"] \
            or c["use_sliding_window"]:
        raise ValueError("the program holds full-attention layers only")
    if c["early_exit_threshold"] != 1:
        raise ValueError("the program runs every pass (no early exit)")
    return tf.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"], dtype=c["param_dtype"],
        remat=c["remat"], norm_eps=c["rms_norm_eps"], attention="gqa",
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]), post_norms=True, ffn="swiglu",
        loop_passes=c["total_ut_steps"], exit_entropy=c["exit_entropy"])


class Driver(lm_fused.Driver):
    def setup(self):
        c = self.cell.config
        mesh = create_mesh({AXIS_DP: 1, AXIS_PP: 1, AXIS_TP: 1, AXIS_SP: 1,
                            AXIS_EP: 1}, devices=jax.devices()[:1])
        self.cfg = transformer_config(c)
        opt = c["optimizer"]
        if opt["name"] != "adam":
            raise ValueError("this driver runs Adam, not %r" % (opt["name"],))
        self.step, self.sh = tf.make_fused_train_steps(
            self.cfg, mesh, self.k, lr=opt["learning_rate"],
            optimizer="adam", betas=(opt["beta1"], opt["beta2"]),
            eps=opt["epsilon"])
        # {leaf: layers} of the leaves stacked over the held layers
        self.stacked = self.ref.stacked_leaves(c)
        weights = self.ref.init_params(c, self.seed)
        self.params = {
            k: jax.device_put(v.reshape((1,) + v.shape)
                              if k in self.stacked else v,
                              self.sh["params"][k])
            for k, v in weights.items()}
        del weights
        self.opt = tf.init_opt_state(self.cfg, mesh)
        self.loop = []      # the counters of programs not yet read

    def call(self, staged):
        self.params, self.opt, losses, loop = self.step(
            self.params, self.opt, staged[0], staged[1])
        self.loop.append(loop)      # four [K] device arrays: kept, not read
        return losses

    def _publish(self):
        """One host read of the counters of every program dispatched
        since the last one, added to `mx.profiler`'s stats."""
        unread, self.loop = jax.device_get(self.loop), []
        for loop in unread:
            tf.publish_moe_stats(loop)

    def observe(self, losses, ring):
        """What the first program left behind: its losses, Adam's first
        moment as per-leaf norms, the weights as host arrays; its
        counters go to the stats."""
        layers = self.cfg.n_layers
        names = tuple(self.stacked)
        norms = common.leaf_norms(
            {k: self.opt["m"][k] for k in names}, names, layers)
        norms.update(common.leaf_norms(
            {k: v for k, v in self.opt["m"].items() if k not in names}))
        shapes = {k: (v.shape[1:] if k in self.stacked else v.shape)
                  for k, v in self.params.items()}
        self._publish()
        return {"losses": [float(x) for x in jax.device_get(losses)],
                "moment_norms": norms,
                "weights": common.to_host(self.params, shapes)}

    # a second copy of the state on the device leaves no room to load
    # the program: the copy is the host's
    snapshot = lm_glm_fused.Driver.snapshot
    restore = lm_glm_fused.Driver.restore

    def counters(self):
        """The program's stats, with the loop's counters of every
        program dispatched since the last reading added first (one read
        of their [K] arrays, at the window's open and close, never
        inside it)."""
        self._publish()
        return dict(profiler.stats())

    def release(self):
        self.loop = []
        super(Driver, self).release()
