"""Drives the program ``mxtpu.parallel.transformer.make_fused_train_steps``
compiles for Solar Open 2 (``solar_open2``: a stack of two mixer kinds,
NoPE gated GQA as the first layer of each period and Kimi Delta Attention
with an unbounded softplus decay, low-rank gates and negative eigenvalues
in the other three; dropless sigmoid top-8 expert layers over the held
experts with a shared expert): everything of ``lm_ling_fused``'s driver
(entry point, mesh, optimizer, kernels, the counters' reading) with the
configuration's published keys turned into ``TransformerConfig``'s
fields."""
import jax

from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP, AXIS_TP,
                                 AXIS_SP, AXIS_EP)

from . import lm_ling_fused


def transformer_config(c):
    """``TransformerConfig`` from the configuration file's keys.  The
    program places its GQA layers by the period (``gqa_interval`` linear
    layers to one GQA layer); the published ``gqa_layers`` has to say the
    same of every layer, and one head count serves both mixers."""
    lin = c["linear_attn_config"]
    period = c["gqa_interval"] + 1
    at = c["gqa_layers"][0] % period
    layers = c.get("published", c)["num_hidden_layers"]
    if [i for i in range(layers) if i % period == at] != c["gqa_layers"]:
        raise ValueError("gqa_layers is not one GQA layer every %d" % period)
    if lin["num_heads"] != c["num_attention_heads"]:
        raise ValueError("the program holds one head count for both mixers")
    return tf.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"], dtype=c["param_dtype"],
        remat=c["remat"], norm_eps=c["rms_norm_eps"], attention="gqa",
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]), rope_full=c["use_rope"],
        out_gate=c["use_gqa_gate"], kda_period=period, kda_attn_at=at,
        layer_ids=tuple(c["layers_held"]), kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"], kda_gate="softplus",
        kda_gate_rank=0 if c["kda_use_full_proj"] else lin["head_dim"],
        kda_neg_eigval=c["kda_allow_neg_eigval"], kda_chunk=c["kda_chunk"],
        ffn="swiglu", n_dense_layers=c["first_k_dense_replace"],
        n_experts=c["n_routed_experts"], d_expert=c["moe_intermediate_size"],
        top_k=c["num_experts_per_tok"], moe_score=c["score_function"],
        moe_select_bias=True, moe_norm_topk=c["norm_topk_prob"],
        moe_scale=c["routed_scaling_factor"],
        n_shared_experts=c["n_shared_experts"],
        expert_first=c["expert_first"], experts_held=c["experts_held"])


class Driver(lm_ling_fused.Driver):
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
