"""Family ``ouro``: Ouro's LoopLM as ONE pipeline stage holds it (dense
layers with full causal attention, run ``total_ut_steps`` times on the
same weights, a head and an exit gate at every pass).  FLOPs of one
training step from the configuration's sizes, at two operations per
multiply-add, and what one flash kernel call NEEDS: the same work
whatever implements it, nothing of a kernel's own walk is in it."""


def applications(config):
    """Layer applications a token goes through in one step: the held
    layers at every pass."""
    return config["total_ut_steps"] * config["num_hidden_layers"]


def forward_flops_per_token(config):
    """Forward products of one token, by part (the attention cores are
    counted by the sequence, in ``train_step_flops``)."""
    c = config
    e, h, kv, d = (c["hidden_size"], c["num_attention_heads"],
                   c["num_key_value_heads"], c["head_dim"])
    passes = c["total_ut_steps"]
    return {
        # q and the out product at h heads, k and v at kv heads
        "attention_projections": applications(c) * 2.0 * e * d
        * (2 * h + 2 * kv),
        "ffn": applications(c) * 2.0 * 3 * e * c["intermediate_size"],
        "heads": passes * 2.0 * e * c["vocab_size"],
        # the gate at every pass but the last, whose lambda is unused
        "exit_gates": (passes - 1) * 2.0 * e,
    }


def _pairs(config):
    t = config["input"]["length"]
    return t * (t + 1) / 2.0


def attention_core_forward_flops(config, batch):
    """The two products of every layer application's causal attention
    core."""
    c = config
    return applications(c) * 2.0 * 2.0 * batch * c["num_attention_heads"] \
        * c["head_dim"] * _pairs(c)


def train_step_flops(config, batch):
    """FLOPs of one training step on ``batch`` sequences of the
    configured length: three times the forward products of every pass
    (the embedding is a lookup and counts nothing; recomputation is not
    counted)."""
    c = config
    fwd = batch * c["input"]["length"] * sum(
        forward_flops_per_token(c).values()) \
        + attention_core_forward_flops(c, batch)
    return 3.0 * fwd


def flash_kernel_useful(config, batch, kind):
    """[(FLOPs, bytes)] one flash kernel call NEEDS, a layer application
    each (``total_ut_steps`` x ``num_hidden_layers`` launches of each
    kernel a step): `flops.attention_kernel`'s count of products and
    arrays over the causal half-square, q, the output and their
    cotangents at ``num_attention_heads`` and k, v, dk, dv at
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
    one = (products * 2.0 * bh * _pairs(c) * d,
           at_q * a_q + at_kv * a_kv + n_rows * rows)
    return [one] * applications(c)
