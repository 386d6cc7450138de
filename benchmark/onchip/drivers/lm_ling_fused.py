"""Drives the program ``mxtpu.parallel.transformer.make_fused_train_steps``
compiles for Ling-3.0-flash's language model (a stack of two mixer
kinds: Kimi Delta Attention layers and one latent-attention layer a
period; a leading dense layer; dropless expert layers with group-limited
selection over the held experts and a shared expert): everything of
``lm_glm_fused``'s driver (entry point, mesh, optimizer, kernels, the
counters' reading) with the configuration's published keys turned into
``TransformerConfig``'s fields."""
import jax

from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP, AXIS_TP,
                                 AXIS_SP, AXIS_EP)

from . import lm_glm_fused


def transformer_config(c):
    """``TransformerConfig`` from the configuration file's keys."""
    return tf.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"], dtype=c["param_dtype"],
        remat=c["remat"], norm_eps=c["rms_norm_eps"], attention="mla",
        q_lora_rank=c["q_lora_rank"] or 0, kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
        qk_norm=c["use_qk_norm"], head_gate=True,
        kda_period=c["layer_group_size"], layer_ids=tuple(c["layers_held"]),
        kda_head_dim=c["head_dim"], kda_conv=c["short_conv_kernel_size"],
        kda_gate_floor=float(c["kda_lower_bound"]),
        kda_chunk=c["kda_chunk"], kda_rebase=c["kda_rebase"],
        ffn="swiglu", n_dense_layers=c["first_k_dense_replace"],
        n_experts=c["num_experts"], d_expert=c["moe_intermediate_size"],
        top_k=c["num_experts_per_tok"], moe_score=c["score_function"],
        moe_select_bias=c["moe_router_enable_expert_bias"],
        moe_norm_topk=c["norm_topk_prob"],
        moe_scale=c["routed_scaling_factor"],
        n_group=c["n_group"], topk_group=c["topk_group"],
        n_shared_experts=c["moe_shared_expert_intermediate_size"]
        // c["moe_intermediate_size"],
        expert_first=c["expert_first"], experts_held=c["experts_held"])


class Driver(lm_glm_fused.Driver):
    def setup(self):
        """`lm_glm_fused.Driver.setup` with this module's
        `transformer_config` (that one names its own)."""
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
        # {leaf: layers} of the leaves stacked over the layers of a kind
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

    def counters(self):
        """`lm_glm_fused.Driver.counters` less the two gauges this
        program adds (`kda_decay_span_max`, a watermark, and
        `mla_padded_width`): the harness reports close less open, which a
        gauge does not have; their readers take them from the stats."""
        stats = super(Driver, self).counters()
        stats.pop("kda_decay_span_max", None)
        stats.pop("mla_padded_width", None)
        return stats
