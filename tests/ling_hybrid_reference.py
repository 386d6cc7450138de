"""Plain pieces of Ling-3.0-flash's language model for
`tests/test_ling_hybrid.py`, each written as its equation reads and
sharing nothing with `mxtpu.parallel.transformer` or with the
benchmark's reference (`benchmark/onchip/reference/ling_3_0_flash.py`,
which the whole-stack tests use): the gated delta rule by matrices, one
token at a time; group-limited routing in numpy; an expert layer with
EVERY expert, dense; and the router as it was before groups existed.
"""
import numpy as np

import jax
import jax.numpy as jnp


def delta_rule_by_matrices(q, k, v, g, beta):
    """o_t = S_t^T q_t with S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
    S_{t-1} + beta_t k_t v_t^T, S_0 = 0, literally.  q, k, v, g: [B, T,
    H, d]; beta: [B, T, H].  float32, differentiable."""
    d = q.shape[-1]
    eye = jnp.eye(d, dtype=jnp.float32)

    def head(q, k, v, g, beta):                 # [T, d] .. [T]
        def token(S, xs):
            qt, kt, vt, gt, bt = xs
            S = (eye - bt * jnp.outer(kt, kt)) @ (jnp.exp(gt)[:, None] * S) \
                + bt * jnp.outer(kt, vt)
            return S, S.T @ qt

        return jax.lax.scan(token, jnp.zeros((d, d), jnp.float32),
                            (q, k, v, g, beta))[1]

    per_head = jax.vmap(head, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    return jax.vmap(per_head)(q, k, v, g, beta)


def route_by_groups(scores, bias, n_group, topk_group, top_k, scale):
    """Group-limited selection in numpy, a token at a time.  scores: [n,
    NE] (sigmoid outputs).  Returns (ids [n, top_k] in descending order
    of score + bias, weights [n, top_k], kept groups [n, n_group])."""
    scores = np.asarray(scores, np.float64)
    sel = scores + np.asarray(bias, np.float64)
    n, ne = sel.shape
    per = ne // n_group
    ids = np.zeros((n, top_k), np.int64)
    w = np.zeros((n, top_k))
    kept = np.zeros((n, n_group), bool)
    for t in range(n):
        group_score = [np.sort(sel[t, j * per:(j + 1) * per])[-2:].sum()
                       for j in range(n_group)]
        # the best groups, an earlier one winning a tie
        best = sorted(range(n_group),
                      key=lambda j: (-group_score[j], j))[:topk_group]
        kept[t, best] = True
        inside = [e for e in range(ne) if e // per in best]
        inside.sort(key=lambda e: (-sel[t, e], e))
        ids[t] = inside[:top_k]
        picked = scores[t, ids[t]]
        w[t] = scale * picked / (picked.sum() + 1e-20)
    return ids, w, kept


def expert_layer_uncut(z, lw, ids, w):
    """Shared(z) + sum over a token's selected experts of w_e Expert_e(z)
    with ALL the experts present.  z: [n, E]; lw: we_g / we_u [NE, E, F],
    we_d [NE, F, E], ws_*; ids, w: [n, k]."""
    z = np.asarray(z, np.float64)

    def silu(a):
        return a / (1.0 + np.exp(-a))

    def gated(x, g, u, d):
        return (silu(x @ g) * (x @ u)) @ d

    lw = {k: np.asarray(a, np.float64) for k, a in lw.items()}
    out = gated(z, lw["ws_g"], lw["ws_u"], lw["ws_d"])
    for t in range(z.shape[0]):
        for e, we in zip(ids[t], w[t]):
            out[t] += we * gated(z[t], lw["we_g"][e], lw["we_u"][e],
                                 lw["we_d"][e])
    return out


def route_before_groups(cfg, flat, router, bias=None):
    """`transformer._route` as it stood before group-limited selection
    (commit a09a2fe), for the jaxpr of `n_group = 1` to be held to; its
    `lax.top_k` is `_top_k_by_rounds` now, as in `_route`."""
    logits = jnp.einsum("ne,ex->nx", flat, router,
                        preferred_element_type=jnp.float32)
    from mxtpu.parallel.transformer import _kept, _top_k_by_rounds

    logits = _kept(logits)
    scores = jax.nn.softmax(logits, axis=-1) \
        if cfg.moe_score == "softmax" else jax.nn.sigmoid(logits)
    select = scores if bias is None else \
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, idx = _top_k_by_rounds(jax.lax.stop_gradient(select), cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg.moe_norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if cfg.moe_scale != 1.0:
        w = w * cfg.moe_scale
    return idx.astype(jnp.int32), w
