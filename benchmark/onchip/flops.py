"""Operations and bytes from shapes: what the algorithm needs, at two
operations per multiply-add, recomputation not counted.

A training step is counted as three times its forward products (forward,
gradient by the input, gradient by the weight), except the first layer,
whose input needs no gradient.  Elementwise work, normalizations, the
softmax and the optimizer are not counted: the model's FLOP/s utilization
is the share of the chip's matrix peak that the step's products fill.

Here are the counts of single operations; what a whole step of one model
family needs is ``families/<family>.py``, which a configuration names.
"""


def conv2d(n, cin, cout, h_out, w_out, kh, kw, groups=1):
    """Forward FLOPs of one convolution."""
    return 2.0 * n * h_out * w_out * cout * (cin // groups) * kh * kw


def attention_forward(bh, t, d, causal=True):
    """Forward FLOPs of one attention call over ``bh`` (batch x heads)
    sequences: the two products, at causal work (half the square plus
    the diagonal's half)."""
    pairs = t * (t + 1) / 2.0 if causal else float(t * t)
    return 2.0 * 2.0 * bh * pairs * d


def attention_kernel(kind, bh, t, d, itemsize=2, causal=True):
    """(FLOPs, bytes) one flash-attention kernel call needs.

    ``fwd``: QK^T and PV; reads q, k, v, writes o and the row log-sums.
    ``dq``: recomputes the scores, dP = dO V^T, dQ = dS K: three
    products; reads q, k, v, o/do, the row sums, writes dq.
    ``dkv``: scores, dP, dV = P^T dO, dK = dS^T Q: four products; reads
    the same, writes dk and dv.
    """
    one = attention_forward(bh, t, d, causal) / 2.0     # one product
    tensor = float(bh * t * d * itemsize)
    rows = float(bh * t * 4)
    if kind == "fwd":
        return 2 * one, 4 * tensor + rows
    if kind == "dq":
        return 3 * one, 6 * tensor + 2 * rows
    if kind == "dkv":
        return 4 * one, 7 * tensor + 2 * rows
    raise ValueError("unknown kernel kind %r" % (kind,))


def transformer_layer_forward(batch, t, e, f, heads, causal=True):
    """Forward FLOPs of one decoder layer: four E x E projections, the
    two feed-forward products and the attention call."""
    tokens = float(batch * t)
    return 2.0 * tokens * (4 * e * e + 2 * e * f) \
        + attention_forward(batch * heads, t, e // heads, causal)
