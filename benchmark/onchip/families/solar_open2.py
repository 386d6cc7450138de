"""Family ``solar_open2``: Solar Open 2's blocks as ONE chip holds them
(NoPE gated GQA as the first layer of each period and Kimi Delta
Attention with low-rank gates in the others; expert layers with a shared
expert over the held experts on every layer).  FLOPs of one training
step from the configuration's sizes, at two operations per multiply-add,
and what one flash kernel call of a held GQA layer NEEDS: the same
work whatever implements it."""


def _mixers(config):
    """[whether the layer is a GQA one] of the layers held here, in
    order."""
    return [i in config["gqa_layers"] for i in config["layers_held"]]


def kda_core_flops_per_token(config):
    """Forward products of KDA's CHUNKED form for one token of one
    layer, all heads: per token and head 6 C d + 6 d^2 (C = ``kda_chunk``,
    d the head's width; ``ling_hybrid``'s count: the two score matrices,
    the triangular solve at the substitution's C / 2 multiply-adds a
    column, and the four state products), 147 456 a head at C = 64, d =
    128."""
    lin = config["linear_attn_config"]
    c, d = config["kda_chunk"], lin["head_dim"]
    return lin["num_heads"] * (6.0 * c * d + 6.0 * d * d)


def forward_flops_per_token(config, pairs_per_token=None):
    """Forward products of one token, by part (the GQA core is counted
    by the sequence, in ``train_step_flops``).  The routed experts are
    counted at ``pairs_per_token`` token-expert pairs on the held
    experts: the pairs a run really computed (the program's
    ``moe_pairs`` over ``moe_tokens``), or where none is given the
    EXPECTED ``num_experts_per_tok * experts_held / n_routed_experts``."""
    c = config
    e, h, kv, d = (c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"])
    lin = c["linear_attn_config"]
    hl, dl = lin["num_heads"], lin["head_dim"]
    gqa = sum(_mixers(c))
    kda = len(c["layers_held"]) - gqa
    layers = len(c["layers_held"]) - c["first_k_dense_replace"]
    fe = c["moe_intermediate_size"]
    pairs = pairs_per_token if pairs_per_token is not None else \
        c["num_experts_per_tok"] * c["experts_held"] \
        / float(c["n_routed_experts"])
    return {
        # q, k, v and the out product; the decay's and the output gate's
        # rank-d bottlenecks, down and up; the write strength
        "kda_projections": kda * 2.0 * (
            4 * e * hl * dl + 2 * (e * dl + dl * hl * dl) + e * hl),
        "kda_core": kda * kda_core_flops_per_token(c),
        # q, the gate and the out product at h heads, k and v at kv
        "gqa_projections": gqa * 2.0 * e * d * (3 * h + 2 * kv),
        "router": layers * 2.0 * e * c["n_routed_experts"],
        "shared_expert": layers * 2.0 * 3 * e * c["n_shared_experts"] * fe,
        "routed_experts": layers * 2.0 * 3 * e * fe * pairs,
        "head": 2.0 * e * c["vocab_size"],
    }


def gqa_core_forward_flops(config, batch):
    """The two causal products of every held GQA layer's core: t (t + 1)
    / 2 pairs a head and sequence, of head_dim each."""
    c = config
    t = c["input"]["length"]
    return sum(_mixers(c)) * 2.0 * 2.0 * batch * c["num_attention_heads"] \
        * c["head_dim"] * t * (t + 1) / 2.0


def train_step_flops(config, batch, pairs_per_token=None):
    """FLOPs of one training step on ``batch`` sequences of the
    configured length: three times the forward products of what this
    chip holds (the embedding is a lookup and counts nothing;
    recomputation is not counted), the routed experts at
    ``pairs_per_token``, KDA's core at its chunked form's products, the
    GQA core causal."""
    c = config
    fwd = batch * c["input"]["length"] * sum(
        forward_flops_per_token(c, pairs_per_token).values()) \
        + gqa_core_forward_flops(c, batch)
    return 3.0 * fwd


def flash_kernel_useful(config, batch, kind):
    """(FLOPs, bytes) one flash kernel call of a held GQA layer NEEDS
    (every such layer is causal over the whole prefix at the same
    shapes): `flops.attention_kernel`'s count of products and arrays
    over the causal half-square with the diagonal, q, the output and
    their cotangents at ``num_attention_heads`` and k, v, dk, dv at
    ``num_key_value_heads``."""
    c = config
    t, d = c["input"]["length"], c["head_dim"]
    bh, bkv = batch * c["num_attention_heads"], \
        batch * c["num_key_value_heads"]
    a_q, a_kv = float(bh * t * d * 2), float(bkv * t * d * 2)
    rows = float(bh * t * 4)
    # products over the pairs; arrays of q's size, of k's, row vectors
    products, at_q, at_kv, n_rows = {
        "fwd": (2, 2, 2, 1),        # S, O; q, o; k, v; log-sums
        "dq": (3, 4, 2, 2),         # S, dP, dQ; q, o, do, dq; k, v
        "dkv": (4, 3, 4, 2),        # S, dP, dV, dK; q, o, do; k, v, dk, dv
    }[kind]
    return (products * 2.0 * bh * t * (t + 1) / 2.0 * d,
            at_q * a_q + at_kv * a_kv + n_rows * rows)
