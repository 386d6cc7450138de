"""Drives the program ``mxtpu.parallel.transformer.make_fused_train_steps``
compiles for a ``glm4_moe_lite`` configuration (latent attention, a
leading dense layer, dropless top-k expert layers over the held experts
with a shared expert, the multi-token-prediction block): the entry
point, mesh, optimizer and kernels ``lm_fused`` drives, with the
configuration's published keys turned into ``TransformerConfig``'s
fields and nothing else of its own."""
import jax

from mxtpu import profiler
from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP, AXIS_TP,
                                 AXIS_SP, AXIS_EP)

from . import common, lm_fused


def transformer_config(c):
    """``TransformerConfig`` from the configuration file's keys."""
    return tf.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"], dtype=c["param_dtype"],
        remat=c["remat"], norm_eps=c["rms_norm_eps"], attention="mla",
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
        ffn="swiglu", n_dense_layers=c["first_k_dense_replace"],
        n_experts=c["n_routed_experts"], d_expert=c["moe_intermediate_size"],
        top_k=c["num_experts_per_tok"], moe_score="sigmoid",
        moe_select_bias=True, moe_norm_topk=c["norm_topk_prob"],
        moe_scale=c["routed_scaling_factor"],
        n_shared_experts=c["n_shared_experts"],
        expert_first=c["expert_first"], experts_held=c["experts_held"],
        mtp_depth=c["num_nextn_predict_layers"],
        mtp_weight=c["mtp_loss_weight"])


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
        # {leaf: layers} of the leaves stacked over a segment's layers
        self.stacked = self.ref.stacked_leaves(c)
        weights = self.ref.init_params(c, self.seed)
        self.params = {
            k: jax.device_put(v.reshape((1,) + v.shape)
                              if k in self.stacked else v,
                              self.sh["params"][k])
            for k, v in weights.items()}
        del weights
        self.opt = tf.init_opt_state(self.cfg, mesh)
        self.moe = []       # the counters of programs not yet read
        self.last_pairs = 0

    def call(self, staged):
        self.params, self.opt, losses, moe = self.step(
            self.params, self.opt, staged[0], staged[1])
        self.moe.append(moe)        # three [K] device arrays: kept, not read
        return losses

    def _publish(self):
        """One host read of the counters of every program dispatched
        since the last one, added to `mx.profiler`'s stats; returns the
        per-step pairs of those programs."""
        unread, self.moe = jax.device_get(self.moe), []
        for moe in unread:
            tf.publish_moe_stats(moe)
        return [float(n) for moe in unread for n in moe["moe_pairs"]]

    def observe(self, losses, ring):
        """What the first program left behind: its losses, Adam's first
        moment as per-leaf norms (a segment at a time: their depths
        differ), the weights as host arrays, and the pairs its router put
        in the held range at each of its K steps."""
        norms = {}
        for layers in sorted(set(self.stacked.values())):
            names = tuple(k for k, n in self.stacked.items() if n == layers)
            norms.update(common.leaf_norms(
                {k: self.opt["m"][k] for k in names}, names, layers))
        norms.update(common.leaf_norms(
            {k: v for k, v in self.opt["m"].items()
             if k not in self.stacked}))
        shapes = {k: (v.shape[1:] if k in self.stacked else v.shape)
                  for k, v in self.params.items()}
        return {"losses": [float(x) for x in jax.device_get(losses)],
                "moment_norms": norms,
                "weights": common.to_host(self.params, shapes),
                "moe_pairs": self._publish()}

    def snapshot(self):
        """A copy of the state for the fault that puts it back, on the
        HOST: a second 7 GB on the device leaves no room to load the
        program (my chip run, PR 30)."""
        return jax.device_get((self.params, self.opt))

    def restore(self, state):
        self.params = self.opt = None       # the device's copy goes first
        self.params = jax.device_put(state[0], self.sh["params"])
        self.opt = jax.device_put(state[1], self.sh["opt_state"])

    def counters(self):
        """The program's stats, with the routed experts' counters of
        every program dispatched since the last reading added first (one
        read of their [K] arrays, at the window's open and close, never
        inside it), so close less open, which the harness reports, is
        the WINDOW's pairs and tokens.  `moe_pairs_last_program` runs up
        by the last program's pairs at every reading, so its difference
        is the pairs of the window's LAST program, to hold against the
        first program's in `worst_at`: routing drifts as the router
        trains.  `moe_load_max` is a watermark, which has no such
        difference, so it is left to its reader."""
        self.last_pairs += sum(self._publish()[-self.k:])
        stats = dict(profiler.stats())
        stats.pop("moe_load_max", None)
        stats["moe_pairs_last_program"] = self.last_pairs
        return stats

    def release(self):
        self.moe = []
        super(Driver, self).release()
