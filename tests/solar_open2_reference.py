"""Plain pieces of Solar Open 2's layers for `tests/test_solar_open2.py`,
each written as its equation reads, in numpy float64, sharing nothing
with `mxtpu.parallel.transformer`, with the flash kernels or with the
benchmark's reference (`benchmark/onchip/reference/solar_open2.py`, which
the whole-stack tests use): gated GQA with no positions a query at a
time, and the Kimi Delta Attention mixer with its softplus decay,
low-rank gates and write strengths in (0, 2), a token at a time with
the state as a matrix.  (The expert layer with EVERY expert present is
`ling_hybrid_reference.expert_layer_uncut`, the plain top-k router
`trinity_mini_reference.route_top_k`.)
"""
import numpy as np


def _sigmoid(a):
    return 1.0 / (1.0 + np.exp(-a))


def _softplus(a):
    return np.logaddexp(a, 0.0)


def gqa_block(x, lw, heads, kv_heads, d):
    """y = ((softmax over j <= t of q k / sqrt(d)) v * sigmoid(x
    W_gate)) W_o for ONE sequence x [T, E], a query at a time: q head h
    meets kv head h // (heads / kv_heads); no norms, no positions."""
    lw = {k: np.asarray(v, np.float64) for k, v in lw.items()}
    x = np.asarray(x, np.float64)
    t = x.shape[0]
    q = (x @ lw["wq"]).reshape(t, heads, d)
    k = (x @ lw["wk"]).reshape(t, kv_heads, d)
    v = (x @ lw["wv"]).reshape(t, kv_heads, d)
    o = np.zeros((t, heads, d))
    for h in range(heads):
        kv = h // (heads // kv_heads)
        for i in range(t):
            s = k[:i + 1, kv] @ q[i, h] / np.sqrt(d)
            p = np.exp(s - s.max())
            o[i, h] = (p / p.sum()) @ v[:i + 1, kv]
    gate = _sigmoid(x @ lw["w_gate"])
    return (o.reshape(t, heads * d) * gate) @ lw["wo"]


def kda_mixer(x, lw, heads, d, eps, neg_eigval=True):
    """Kimi Delta Attention for ONE sequence x [T, E], a token at a time:
    q, k, v = silu(conv4(x W)), the convolution causal and depthwise with
    its last tap on the current token; q, k L2-normed per head (q times d
    ** -0.5); g = -exp(A_log) softplus((x F_a) F_b + dt_bias); beta = 2
    sigmoid(x W_b) (sigmoid without `neg_eigval`); S_t = (I - beta k k^T)
    Diag(exp(g)) S_{t-1} + beta k v^T, o_t = S_t^T q_t; out W_o [RMS(o)
    o_norm * sigmoid((x G_a) G_b)].  Returns (y [T, E], g [T, heads, d],
    beta [T, heads])."""
    lw = {k: np.asarray(v, np.float64) for k, v in lw.items()}
    x = np.asarray(x, np.float64)
    t = x.shape[0]

    def conv(y, taps):
        yp = np.concatenate([np.zeros((len(taps) - 1, y.shape[1])), y])
        return sum(taps[j] * yp[j:j + t] for j in range(len(taps)))

    def silu(a):
        return a * _sigmoid(a)

    q, k, v = (silu(conv(x @ lw["w" + n], lw["conv_" + n])).reshape(
        t, heads, d) for n in ("q", "k", "v"))
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(d)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    g = -np.exp(lw["A_log"])[:, None] * _softplus(
        ((x @ lw["f_a"]) @ lw["f_b"] + lw["dt_bias"]).reshape(t, heads, d))
    beta = (2.0 if neg_eigval else 1.0) * _sigmoid(x @ lw["wb"])
    o = np.zeros((t, heads, d))
    for h in range(heads):
        S = np.zeros((d, d))
        for i in range(t):
            kk = k[i, h]
            S = (np.eye(d) - beta[i, h] * np.outer(kk, kk)) \
                @ (np.exp(g[i, h])[:, None] * S) \
                + beta[i, h] * np.outer(kk, v[i, h])
            o[i, h] = S.T @ q[i, h]
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + eps) * lw["o_norm"]
    o = o * _sigmoid(((x @ lw["g_a"]) @ lw["g_b"]).reshape(t, heads, d))
    return o.reshape(t, heads * d) @ lw["wo"], g, beta
