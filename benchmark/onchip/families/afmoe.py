"""Family ``afmoe``: Trinity-Mini's blocks as ONE chip holds them (window
and full attention layers with grouped kv heads and an output gate, a
leading dense layer, expert layers with a shared expert over the held
experts).  FLOPs of one training step from the configuration's sizes, at
two operations per multiply-add, and what one flash kernel call of each
held layer NEEDS: the same work whatever implements it, nothing of a
kernel's own walk is in it."""


def _layers(config):
    """[whether the layer attends over the whole prefix] of the layers
    held here, in order."""
    return [config["layer_types"][i] == "full_attention"
            for i in config["layers_held"]]


def score_pairs(config, full):
    """(query, key) pairs one head scores over one sequence: the causal
    half-square with the diagonal on a full layer, the BAND's on a
    window layer: W (W + 1) / 2 for the first W queries, W each for the
    rest."""
    t, w = config["input"]["length"], config["sliding_window"]
    if full or w >= t:
        return t * (t + 1) / 2.0
    return w * (w + 1) / 2.0 + (t - w) * float(w)


def forward_flops_per_token(config, pairs_per_token=None):
    """Forward products of one token, by part (the attention cores are
    counted by the sequence, in ``train_step_flops``).  The routed
    experts are counted at ``pairs_per_token`` token-expert pairs on
    the held experts: the pairs a run really computed (the program's
    ``moe_pairs`` over ``moe_tokens``), or where none is given the
    EXPECTED ``num_experts_per_tok * experts_held / num_experts``."""
    c = config
    e, h, kv, d = (c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"])
    layers = len(c["layers_held"])
    dense = c["num_dense_layers"]
    fe = c["moe_intermediate_size"]
    pairs = pairs_per_token if pairs_per_token is not None else \
        c["num_experts_per_tok"] * c["experts_held"] / float(c["num_experts"])
    return {
        # q, the gate and the out product at 32 heads, k and v at 4
        "attention_projections": layers * 2.0 * e * d * (3 * h + 2 * kv),
        "dense_ffn": dense * 2.0 * 3 * e * c["intermediate_size"],
        "router": (layers - dense) * 2.0 * e * c["num_experts"],
        "shared_expert": (layers - dense) * 2.0 * 3 * e
        * c["num_shared_experts"] * fe,
        "routed_experts": (layers - dense) * 2.0 * 3 * e * fe * pairs,
        "head": 2.0 * e * c["vocab_size"],
    }


def attention_core_forward_flops(config, batch):
    """The two products of every held layer's attention core, at the
    pairs its mask leaves (``score_pairs``)."""
    c = config
    per_pair = 2.0 * 2.0 * batch * c["num_attention_heads"] * c["head_dim"]
    return sum(per_pair * score_pairs(c, full) for full in _layers(c))


def train_step_flops(config, batch, pairs_per_token=None):
    """FLOPs of one training step on ``batch`` sequences of the
    configured length: three times the forward products of what this
    chip holds (the embedding is a lookup and counts nothing;
    recomputation is not counted), the routed experts at
    ``pairs_per_token``, the score products at the band's pairs on the
    window layers and the causal pairs on the full ones."""
    c = config
    fwd = batch * c["input"]["length"] * sum(
        forward_flops_per_token(c, pairs_per_token).values()) \
        + attention_core_forward_flops(c, batch)
    return 3.0 * fwd


def flash_kernel_useful(config, batch, kind):
    """[(FLOPs, bytes)] one flash kernel call NEEDS, a held layer each:
    `flops.attention_kernel`'s count of products and arrays, with the
    layer's own pairs for the causal half-square, q, the output and
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
    return [(products * 2.0 * bh * score_pairs(c, full) * d,
             at_q * a_q + at_kv * a_kv + n_rows * rows)
            for full in _layers(c)]
