"""Plain pieces of Trinity-Mini's block for `tests/test_trinity_mini.py`,
each written as its equation reads, in numpy float64, sharing nothing
with `mxtpu.parallel.transformer`, with the flash kernels or with the
benchmark's reference (`benchmark/onchip/reference/trinity_mini.py`,
which the whole-stack tests use): attention with grouped kv heads a
query at a time, and the plain top-k router.  (The expert layer with
EVERY expert present is `ling_hybrid_reference.expert_layer_uncut`.)
"""
import numpy as np


def _rms(x, scale, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate_half(x, theta):
    """x [T, heads, d] at positions 0 .. T-1: dim j pairs with j + d/2."""
    t, _, d = x.shape
    half = d // 2
    ang = np.arange(t)[:, None] * theta ** (-np.arange(half) / half)[None]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_block(x, lw, heads, kv_heads, d, window=None, theta=None,
                    eps=1e-5):
    """y = ((softmax over the seen keys of q k / sqrt(d)) v * sigmoid(x
    W_gate)) W_o for ONE sequence x [T, E], a query at a time: q head h
    meets kv head h // (heads / kv_heads); a query at t sees key j iff j
    <= t and, with a `window`, t - j < window; q and k RMS-normed per
    head; rotary positions (base `theta`) on q and k, or none."""
    lw = {k: np.asarray(v, np.float64) for k, v in lw.items()}
    x = np.asarray(x, np.float64)
    t = x.shape[0]
    q = _rms((x @ lw["wq"]).reshape(t, heads, d), lw["q_norm"], eps)
    k = _rms((x @ lw["wk"]).reshape(t, kv_heads, d), lw["k_norm"], eps)
    v = (x @ lw["wv"]).reshape(t, kv_heads, d)
    if theta is not None:
        q, k = _rotate_half(q, theta), _rotate_half(k, theta)
    o = np.zeros((t, heads, d))
    for h in range(heads):
        kv = h // (heads // kv_heads)
        for i in range(t):
            lo = 0 if window is None else max(0, i - window + 1)
            s = k[lo:i + 1, kv] @ q[i, h] / np.sqrt(d)
            p = np.exp(s - s.max())
            o[i, h] = (p / p.sum()) @ v[lo:i + 1, kv]
    gate = 1.0 / (1.0 + np.exp(-(x @ lw["w_gate"])))
    return (o.reshape(t, heads * d) * gate) @ lw["wo"]


def route_top_k(scores, bias, top_k, scale):
    """The `top_k` largest of scores + bias a token (an earlier expert
    winning a tie), weights scale * score / (the selected scores' sum +
    1e-20).  scores: [n, NE].  Returns (ids [n, top_k], weights)."""
    scores = np.asarray(scores, np.float64)
    sel = scores + np.asarray(bias, np.float64)
    ids = np.stack([sorted(range(sel.shape[1]),
                           key=lambda e: (-sel[t, e], e))[:top_k]
                    for t in range(sel.shape[0])])
    picked = np.take_along_axis(scores, ids, axis=1)
    return ids, scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
