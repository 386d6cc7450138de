"""Family ``ling_hybrid``: Ling-3.0-flash's language model as ONE chip
holds it (Kimi Delta Attention layers and one latent-attention layer a
period, a leading dense layer, expert layers with a shared expert over
the held experts).  FLOPs of one training step from the configuration's
sizes, at two operations per multiply-add."""
from flops import attention_forward


def _mixers(config):
    """(KDA layers, latent-attention layers, dense layers, expert
    layers) of the layers held here."""
    mla = sum((i + 1) % config["layer_group_size"] == 0
              for i in config["layers_held"])
    layers = len(config["layers_held"])
    dense = config["first_k_dense_replace"]
    return layers - mla, mla, dense, layers - dense


def kda_core_flops_per_token(config):
    """Forward products of KDA's CHUNKED form for one token of one
    layer, all heads: with chunks of C = ``kda_chunk`` tokens and heads
    of d = ``head_dim`` (d_k = d_v), per token and head

    * the two score matrices A (k.k) and B (q.k) against the chunk's
      columns: 2 x 2 C d,
    * the unit lower triangular solve for [W | Ut] (d + d columns), at
      the substitution's C / 2 multiply-adds a column: 2 x (C / 2) x 2 d,
    * U = Ut - W S, the two output products (Qd S, B U) and the state's
      update K^T U: 2 d d + 2 d d + 2 C d + 2 d d,

    in all 6 C d + 6 d^2: 147 456 a head at C = 64, d = 128, 4.72 MFLOP a
    token-layer over 32 heads, beside 105 MFLOP of projections."""
    c, d = config["kda_chunk"], config["head_dim"]
    return config["num_attention_heads"] * (6.0 * c * d + 6.0 * d * d)


def forward_flops_per_token(config, pairs_per_token=None):
    """Forward products of one token, by part (the latent attention's
    core is counted by the sequence, in ``train_step_flops``).  The
    routed experts are counted at ``pairs_per_token`` token-expert pairs
    on the held experts: the pairs a run really computed (the program's
    ``moe_pairs`` over ``moe_tokens``), or where none is given the
    EXPECTED ``num_experts_per_tok * experts_held / num_experts``."""
    c = config
    e, h, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    kda, mla, dense, expert = _mixers(c)
    fe = c["moe_intermediate_size"]
    pairs = pairs_per_token if pairs_per_token is not None else \
        c["num_experts_per_tok"] * c["experts_held"] / float(c["num_experts"])
    return {
        # q, k, v, the decay gate, the out product; the two head gates'
        "kda_projections": kda * 2.0 * (5 * e * h * d + 2 * e * h),
        "kda_core": kda * kda_core_flops_per_token(c),
        "mla_projections": mla * 2.0 * (
            e * h * (dn + dr) + e * (c["kv_lora_rank"] + dr)
            + c["kv_lora_rank"] * h * (dn + dv) + h * dv * e + e * h),
        "dense_ffn": dense * 2.0 * 3 * e * c["intermediate_size"],
        "router": expert * 2.0 * e * c["num_experts"],
        "shared_expert": expert * 2.0 * 3 * e
        * c["moe_shared_expert_intermediate_size"],
        "routed_experts": expert * 2.0 * 3 * e * fe * pairs,
        "head": 2.0 * e * c["vocab_size"],
    }


def mla_core_forward_flops(config, batch):
    """The latent attention's two causal products of one call at the
    PUBLISHED widths (q.k qk_nope + qk_rope = 192, v 128), whatever
    width the kernels are handed."""
    c = config
    bh, t = batch * c["num_attention_heads"], c["input"]["length"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    # attention_forward counts two products of width d: one of each here
    return (attention_forward(bh, t, qk) + attention_forward(
        bh, t, c["v_head_dim"])) / 2.0


def train_step_flops(config, batch, pairs_per_token=None):
    """FLOPs of one training step on ``batch`` sequences of the
    configured length: three times the forward products of what this
    chip holds (the embedding is a lookup and counts nothing;
    recomputation is not counted), the routed experts at
    ``pairs_per_token``, KDA's core at its chunked form's products
    (``kda_core_flops_per_token``), the latent attention's core causal
    at the published 192 / 128."""
    c = config
    fwd = batch * c["input"]["length"] * sum(
        forward_flops_per_token(c, pairs_per_token).values()) \
        + _mixers(c)[1] * mla_core_forward_flops(c, batch)
    return 3.0 * fwd


def flash_kernel_useful(config, batch, kind):
    """(FLOPs, bytes) one flash kernel call of this configuration NEEDS:
    `flops.attention_kernel`'s count of products and arrays, with q and
    k at the published 192 and v, the output and its cotangent at 128
    (the zero columns the program pads to the kernels' one width are no
    work)."""
    c = config
    bh, t = batch * c["num_attention_heads"], c["input"]["length"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    dv = c["v_head_dim"]
    s = attention_forward(bh, t, qk) / 2.0      # one product over q.k
    p = attention_forward(bh, t, dv) / 2.0      # one product over v
    rows = float(bh * t * 4)
    a_qk, a_v = float(bh * t * qk * 2), float(bh * t * dv * 2)
    if kind == "fwd":       # S = QK^T, O = PV; reads q, k, v, writes o
        return s + p, 2 * a_qk + 2 * a_v + rows
    if kind == "dq":        # S, dP = dO V^T, dQ = dS K
        return 2 * s + p, 3 * a_qk + 2 * a_v + 2 * rows
    if kind == "dkv":       # S, dP, dV = P^T dO, dK = dS^T Q
        return 2 * s + 2 * p, 3 * a_qk + 3 * a_v + 2 * rows
    raise ValueError("unknown kernel kind %r" % (kind,))
