"""Drives the program ``mxtpu.parallel.transformer.make_fused_train_steps``
compiles for Trinity-Mini (``afmoe``: grouped kv heads, window and full
attention layers mixed by a period with rotary positions on the window
layers only, per-head q / k norms, an elementwise output gate, four
norms a layer, a leading dense layer, dropless sigmoid top-8 expert
layers over the held experts with a shared expert): everything of
``lm_glm_fused``'s driver (entry point, mesh, optimizer, kernels, the
counters' reading) with the configuration's published keys turned into
``TransformerConfig``'s fields."""
import jax

from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import (create_mesh, AXIS_DP, AXIS_PP, AXIS_TP,
                                 AXIS_SP, AXIS_EP)

from . import lm_glm_fused


def transformer_config(c):
    """``TransformerConfig`` from the configuration file's keys.  The
    program places its full-attention layers by the period
    (``global_attn_every_n_layers``); the published ``layer_types`` has
    to say the same of every layer."""
    period = c["global_attn_every_n_layers"]
    by_period = ["full_attention" if (i + 1) % period == 0
                 else "sliding_attention"
                 for i in range(len(c["layer_types"]))]
    if by_period != list(c["layer_types"]):
        raise ValueError("layer_types is not full attention every %d "
                         "layers" % period)
    return tf.TransformerConfig(
        vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"], dtype=c["param_dtype"],
        remat=c["remat"], norm_eps=c["rms_norm_eps"], attention="gqa",
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]), qk_norm=True, out_gate=True,
        post_norms=True, rope_full=False, window=c["sliding_window"],
        full_period=period,
        embed_scale=c["hidden_size"] ** 0.5 if c["mup_enabled"] else 1.0,
        layer_ids=tuple(c["layers_held"]), ffn="swiglu",
        n_dense_layers=c["num_dense_layers"], n_experts=c["num_experts"],
        d_expert=c["moe_intermediate_size"], top_k=c["num_experts_per_tok"],
        moe_score=c["score_func"], moe_select_bias=True,
        moe_norm_topk=c["route_norm"], moe_scale=c["route_scale"],
        n_group=c["n_group"], topk_group=c["topk_group"],
        n_shared_experts=c["num_shared_experts"],
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
