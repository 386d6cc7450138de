"""Family ``glm_moe_lite``: GLM-4.7-Flash's blocks as ONE chip holds them
(latent attention, one leading dense layer, expert layers with a shared
expert over the held experts, the multi-token-prediction block).  FLOPs
of one training step from the configuration's sizes."""
from flops import attention_forward


def forward_flops_per_token(config, pairs_per_token=None):
    """Forward products of one token, by part, at two operations per
    multiply-add (the attention core is counted by the sequence, in
    ``train_step_flops``).  The routed experts are counted at
    ``pairs_per_token`` token-expert pairs on the held experts: the
    pairs a run really computed (the program's ``moe_pairs`` over
    ``moe_tokens``, which ``mfu_pct.glm`` passes), or where none is
    given the EXPECTED ``num_experts_per_tok * experts_held /
    n_routed_experts``, which is what uniform routing gives."""
    c = config
    e, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    mla = 2.0 * (e * c["q_lora_rank"] + c["q_lora_rank"] * h * (dn + dr)
                 + e * (c["kv_lora_rank"] + dr)
                 + c["kv_lora_rank"] * h * (dn + dv) + h * dv * e)
    fe = c["moe_intermediate_size"]
    pairs = pairs_per_token if pairs_per_token is not None else \
        c["num_experts_per_tok"] * c["experts_held"] \
        / float(c["n_routed_experts"])
    dense_layers = c["first_k_dense_replace"]
    expert_layers = c["num_hidden_layers"] - dense_layers \
        + c["num_nextn_predict_layers"]
    heads = 1 + c["num_nextn_predict_layers"]
    return {
        "mla_projections": (dense_layers + expert_layers) * mla,
        "dense_ffn": dense_layers * 2.0 * 3 * e * c["intermediate_size"],
        "router": expert_layers * 2.0 * e * c["n_routed_experts"],
        "shared_expert": expert_layers * 2.0 * 3 * e
        * c["n_shared_experts"] * fe,
        "routed_experts": expert_layers * 2.0 * 3 * e * fe * pairs,
        "heads": heads * 2.0 * e * c["vocab_size"],
        "mtp_projection": c["num_nextn_predict_layers"] * 2.0 * 2 * e * e,
    }


def train_step_flops(config, batch, pairs_per_token=None):
    """FLOPs of one training step on ``batch`` sequences of the
    configured length: three times the forward products of what this
    chip holds (the embedding is a lookup and counts nothing;
    recomputation is not counted), the routed experts at
    ``pairs_per_token`` (``forward_flops_per_token``).  The attention
    core is causal, at a q.k width of qk_nope + qk_rope = 256 and a v
    width of 256, once per layer, the MTP block's included."""
    c = config
    t = c["input"]["length"]
    layers = c["num_hidden_layers"] + c["num_nextn_predict_layers"]
    d = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    fwd = batch * t * sum(
        forward_flops_per_token(c, pairs_per_token).values()) \
        + layers * attention_forward(batch * c["num_attention_heads"], t, d)
    return 3.0 * fwd
