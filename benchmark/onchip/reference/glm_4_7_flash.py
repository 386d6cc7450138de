"""Plain reference of one chip's share of GLM-4.7-Flash
(``glm4_moe_lite``), float32.

The equations, from the published ``config.json`` and the family's
modelling code (every departure is listed in the configuration file under
``assumed``).  ``x`` is one sequence ``[T, hidden]``, ``RMS`` is RMSNorm
with ``rms_norm_eps``, ``R`` the rotary embedding with base
``rope_theta`` over all ``qk_rope_head_dim`` dims, rotate-half pairing.

* latent attention (MLA), decompressed form: ``c_q = RMS(x W_qa)``;
  ``[q_nope | q_pe] = c_q W_qb`` per head, ``q_pe <- R(q_pe)``;
  ``[c_kv | k_pe] = x W_kva``, ``c_kv <- RMS(c_kv)``, ``k_pe <- R(k_pe)``
  (one key for all heads); ``[k_nope | v] = c_kv W_kvb`` per head;
  ``o = softmax_causal([q_nope|q_pe] [k_nope|k_pe]^T / sqrt(192 + 64)) v``;
  ``attn = concat_heads(o) W_o``.
* dense layer: ``h = x + attn(RMS(x))``; ``y = h + W_d(silu(W_g z) *
  (W_u z))``, ``z = RMS(h)``.
* expert layer: ``s = sigmoid(z W_r)``; selection ``top_k(s + b)``;
  weights ``w_e = scale * s_e / (sum of s over ALL the selected +
  1e-20)``; ``f = Shared(z) + sum over e selected AND held of w_e *
  Expert_e(z)``.  What absent experts would add is left out (the chip's
  share of an expert-parallel layer).  ``b`` is a constant.
* multi-token prediction (depth 1, DeepSeek-V3's form): ``u_i = W_eh
  [RMS(Emb(t_{i+1})) | RMS(h_i)]`` for ``i < T-1``, one expert layer on
  ``u``, ``RMS``, the shared head; ``L = CE(main_i, label_i) + lambda *
  CE(mtp_i, label_{i+1})``, each a mean over its positions.

Straightforward ``jax.numpy``: attention materializes its [T, T] scores
(one head at a time, so that it fits beside 11 GB of float32 state),
every held expert runs over every token and is masked, nothing is
imported from the program under test, every product runs at
``precision`` ``HIGHEST``.  One layer's activations at a time are kept
(``jax.checkpoint``), the two heads' logits are made in blocks of rows.

``mode``: ``"f32"``; ``"bf16"`` rounds every product's operands, forward
and backward (the cotangent too), to bfloat16; ``"int8"`` rounds them to
8-bit integers with one scale per tensor, the control the limits have
to refuse.

Weights are STORED in bfloat16 with no float32 master copy: the
reference does its arithmetic in float32 and rounds the new weight to
bfloat16 once per Adam step.  Adam as the program states it:
``m = b1*m + (1-b1)*g``, ``v = b2*v + (1-b2)*g*g``,
``w = bf16(w - lr * (m/(1-b1^t)) / (sqrt(v/(1-b2^t)) + eps))``.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .precision import in_precision, seed_key, to_bf16

_HI = lax.Precision.HIGHEST
_LOGIT_ROWS = 2048      # rows of logits made at a time


def _sizes(cfg):
    return dict(
        E=cfg["hidden_size"], H=cfg["num_attention_heads"],
        ql=cfg["q_lora_rank"], kvl=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"], NE=cfg["n_routed_experts"],
        held=cfg["experts_held"], first=cfg.get("expert_first", 0),
        Fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        V=cfg["vocab_size"], dense=cfg["first_k_dense_replace"],
        L=cfg["num_hidden_layers"], mtp=cfg["num_nextn_predict_layers"])


def _segments(cfg):
    """[(prefix, kind, layers)] as the program names them: leading dense
    layers ``dense.``, the expert layers bare, the MTP block ``mtp.``."""
    z = _sizes(cfg)
    segs = [("dense.", "dense", z["dense"]), ("", "moe", z["L"] - z["dense"])]
    if z["mtp"]:
        segs.append(("mtp.", "moe", z["mtp"]))
    return segs


def _layer_layout(cfg, kind):
    z = _sizes(cfg)
    E, H = z["E"], z["H"]
    out = [("ln1", (E,), None), ("ln2", (E,), None),
           ("wq_a", (E, z["ql"]), E), ("q_norm", (z["ql"],), None),
           ("wq_b", (z["ql"], H * (z["dn"] + z["dr"])), z["ql"]),
           ("wkv_a", (E, z["kvl"] + z["dr"]), E),
           ("kv_norm", (z["kvl"],), None),
           ("wkv_b", (z["kvl"], H * (z["dn"] + z["dv"])), z["kvl"]),
           ("wo", (H * z["dv"], E), H * z["dv"])]
    if kind == "dense":
        F = z["F"]
        return out + [("wg", (E, F), E), ("wu", (E, F), E),
                      ("wd", (F, E), F)]
    Fe, Fs, held = z["Fe"], z["Fs"], z["held"]
    return out + [
        ("router", (E, z["NE"]), E),
        ("router_bias", (z["NE"],), 1e4),       # drawn small, constant
        ("we_g", (held, E, Fe), E), ("we_u", (held, E, Fe), E),
        ("we_d", (held, Fe, E), Fe),
        ("ws_g", (E, Fs), E), ("ws_u", (E, Fs), E), ("ws_d", (Fs, E), Fs)]


def layout(cfg):
    """Ordered (name, shape, fan_in or None); a layer leaf is stacked on
    a leading axis over its segment's layers."""
    z = _sizes(cfg)
    E, V = z["E"], z["V"]
    out = [("embed", (V, E), E), ("ln_f", (E,), None),
           ("unembed", (E, V), E)]
    for prefix, kind, n in _segments(cfg):
        out += [(prefix + name, (n,) + shape, fan_in)
                for name, shape, fan_in in _layer_layout(cfg, kind)]
    if z["mtp"]:
        out += [("mtp.eh", (2 * E, E), 2 * E), ("mtp.ln_e", (E,), None),
                ("mtp.ln_h", (E,), None), ("mtp.ln_f", (E,), None)]
    return out


def stacked_leaves(cfg):
    """{leaf: layers} of the leaves that hold one layer per leading
    index."""
    return {prefix + name: n for prefix, kind, n in _segments(cfg)
            for name, _, _ in _layer_layout(cfg, kind)}


def init_params(cfg, seed):
    """All weights in one jitted call from the seed, in bfloat16 (the
    type the program stores and trains them in): normal with variance
    1/fan_in, unit norm scales.  Returned as HOST arrays: the caller
    keeps the start through the whole comparison, and on the device its
    1.4 GB are what the int8 control lacks beside 11.3 GB of float32
    state (my chip run, PR 30: 50 MB short)."""
    leaves = layout(cfg)

    @jax.jit
    def make(key):
        p = {}
        for i, (name, shape, fan_in) in enumerate(leaves):
            if fan_in is None:
                p[name] = jnp.ones(shape, jnp.bfloat16)
            else:
                p[name] = (jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)
                           * (1.0 / fan_in) ** 0.5).astype(jnp.bfloat16)
        return p

    return jax.device_get(make(seed_key(seed)))


def _mm(spec, a, b, mode):
    return in_precision(
        lambda x, y: jnp.einsum(spec, x, y, precision=_HI), mode)(a, b)


def _rms(cfg, x, scale):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True)
                         + cfg["rms_norm_eps"]) * scale


def _rotary(cfg, x):
    """R(x) for x [..., T, dr] at positions 0..T-1, rotate-half."""
    T, d = x.shape[-2], x.shape[-1]
    half = d // 2
    inv = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla(cfg, x, lw, mode):
    z = _sizes(cfg)
    B, T, _ = x.shape
    H, dn, dr, dv, kvl = z["H"], z["dn"], z["dr"], z["dv"], z["kvl"]
    c_q = _rms(cfg, _mm("bte,ef->btf", x, lw["wq_a"], mode), lw["q_norm"])
    q = _mm("bte,ef->btf", c_q, lw["wq_b"], mode).reshape(B, T, H, dn + dr)
    q = q.transpose(2, 0, 1, 3)                           # [H, B, T, .]
    q = jnp.concatenate([q[..., :dn], _rotary(cfg, q[..., dn:])], -1)
    ckv = _mm("bte,ef->btf", x, lw["wkv_a"], mode)
    c_kv = _rms(cfg, ckv[..., :kvl], lw["kv_norm"])
    k_pe = _rotary(cfg, ckv[..., kvl:])                   # [B, T, dr]
    kv = _mm("bte,ef->btf", c_kv, lw["wkv_b"], mode).reshape(
        B, T, H, dn + dv).transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def head(args):
        qh, kvh = args
        kh = jnp.concatenate([kvh[..., :dn], k_pe], -1)
        s = _mm("bqd,bkd->bqk", qh, kh, mode) / ((dn + dr) ** 0.5)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("bqk,bkd->bqd", a, kvh[..., dn:], mode)

    o = lax.map(jax.checkpoint(head), (q, kv))            # [H, B, T, dv]
    o = o.transpose(1, 2, 0, 3).reshape(B, T, H * dv)
    return _mm("bte,ef->btf", o, lw["wo"], mode)


def _gated(x, wg, wu, wd, mode):
    g = _mm("...e,ef->...f", x, wg, mode)
    u = _mm("...e,ef->...f", x, wu, mode)
    return _mm("...f,fe->...e", jax.nn.silu(g) * u, wd, mode)


def route(cfg, zt, router, bias, mode="f32"):
    """(selected experts [n, k], weights [n, k]) of tokens zt [n, E]."""
    s = jax.nn.sigmoid(_mm("ne,ex->nx", zt, router, mode))
    _, idx = lax.top_k(lax.stop_gradient(s + bias), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def _moe(cfg, zt, lw, mode):
    """Shared(z) + the held experts' weighted part; also the number of
    (token, expert) pairs that fell in the held range."""
    z = _sizes(cfg)
    idx, w = route(cfg, zt, lw["router"], lw["router_bias"], mode)

    def expert(acc, xs):
        e, wg, wu, wd = xs
        # this expert's weight for each token (nought where not selected)
        we = jnp.where(idx == e + z["first"], w, 0.0).sum(-1)
        return acc + we[:, None] * _gated(zt, wg, wu, wd, mode), None

    routed, _ = lax.scan(
        jax.checkpoint(expert), jnp.zeros_like(zt),
        (jnp.arange(z["held"]), lw["we_g"], lw["we_u"], lw["we_d"]))
    local = idx - z["first"]
    pairs = ((local >= 0) & (local < z["held"])).sum()
    return _gated(zt, lw["ws_g"], lw["ws_u"], lw["ws_d"], mode) + routed, \
        pairs


def _layer(cfg, kind, x, lw, mode):
    h = x + _mla(cfg, _rms(cfg, x, lw["ln1"]), lw, mode)
    zt = _rms(cfg, h, lw["ln2"])
    if kind == "dense":
        return h + _gated(zt, lw["wg"], lw["wu"], lw["wd"], mode), \
            jnp.int32(0)
    B, T, E = zt.shape
    f, pairs = _moe(cfg, zt.reshape(B * T, E), lw, mode)
    return h + f.reshape(B, T, E), pairs


def _segment(cfg, p, prefix, kind, x, mode):
    names = [name for name, _, _ in _layer_layout(cfg, kind)]

    def body(x, lw):
        return _layer(cfg, kind, x, lw, mode)

    x, pairs = lax.scan(jax.checkpoint(body), x,
                        {n: p[prefix + n] for n in names})
    return x, pairs.sum()


def _nll_rows(cfg, p, ln, h, labels, mode):
    """Sum of the negative log likelihood of ``labels`` [n] under the
    shared head on RMS(h) [n, E], the logits made a block of rows at a
    time."""
    n, E = h.shape
    block = min(_LOGIT_ROWS, n)
    pad = (-n) % block
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, E)
    lb = jnp.pad(labels, (0, pad)).reshape(-1, block)
    keep = (jnp.arange(n + pad) < n).reshape(-1, block)

    def rows(args):
        hh, ll, kk = args
        lg = _mm("ne,ev->nv", _rms(cfg, hh, ln), p["unembed"], mode)
        picked = jnp.take_along_axis(lg, ll[:, None], axis=-1)[:, 0]
        return jnp.where(kk, jax.nn.logsumexp(lg, axis=-1) - picked,
                         0.0).sum()

    return lax.map(jax.checkpoint(rows), (hb, lb, keep)).sum()


def forward(cfg, p, tokens, labels, mode="f32"):
    """(sum of the main head's nll over the block's tokens, sum of the
    MTP head's over its positions, pairs routed into the held range)."""
    B, T = tokens.shape
    E = cfg["hidden_size"]
    x = p["embed"][tokens]
    pairs = 0
    for prefix, kind, _ in _segments(cfg):
        if prefix != "mtp.":
            x, n = _segment(cfg, p, prefix, kind, x, mode)
            pairs = pairs + n
    main = _nll_rows(cfg, p, p["ln_f"], x.reshape(B * T, E),
                     labels.reshape(B * T), mode)
    if not cfg["num_nextn_predict_layers"]:
        return main, jnp.float32(0.0), pairs
    u = jnp.concatenate(
        [_rms(cfg, p["embed"][tokens[:, 1:]], p["mtp.ln_e"]),
         _rms(cfg, x[:, :-1], p["mtp.ln_h"])], -1)
    u = _mm("bte,ef->btf", u, p["mtp.eh"], mode)
    u, n = _segment(cfg, p, "mtp.", "moe", u, mode)
    mtp = _nll_rows(cfg, p, p["mtp.ln_f"], u.reshape(B * (T - 1), E),
                    labels[:, 1:].reshape(B * (T - 1)), mode)
    return main, mtp, pairs + n


def _loss_and_pairs(cfg, p, tokens, labels, mode):
    B, T = tokens.shape
    main, mtp, pairs = forward(cfg, p, tokens, labels, mode)
    loss = main / (B * T)
    if cfg["num_nextn_predict_layers"]:
        loss = loss + cfg["mtp_loss_weight"] * mtp / (B * (T - 1))
    return loss, pairs


def loss_fn(cfg, p, tokens, labels, mode="f32"):
    """The batch's loss: mean main nll + lambda * mean MTP nll."""
    return _loss_and_pairs(cfg, p, tokens, labels, mode)[0]


def _grad_and_pairs(cfg, p, tokens, labels, mode):
    """((loss, pairs routed into the held range), gradient) of the
    batch-mean loss."""
    return jax.value_and_grad(
        lambda q: _loss_and_pairs(cfg, q, tokens, labels, mode),
        has_aux=True)(p)


def _grad_of_mean(cfg, p, tokens, labels, rows, mode):
    """Loss and gradient of the batch-mean loss.  ``rows`` is accepted
    for the harness's sake and not used: a second copy of the gradient
    to add blocks of rows into does not fit beside 11 GB of float32
    state, so the whole batch goes through at once and the blocking is
    inside (a layer, a head, a block of logit rows at a time)."""
    del rows
    (loss, _), g = _grad_and_pairs(cfg, p, tokens, labels, mode)
    return loss, g


def leaf_norms(tree, stacked=None):
    """{leaf name: l2 norm}; a leaf in ``stacked`` gives one norm per
    layer, named ``leaf.<layer>``.  ``stacked`` defaults to every leaf
    of more than one axis but the embedding, the head and the MTP
    projection (so that the harness, which knows no configuration
    here, gets the same names)."""
    if stacked is None:
        stacked = [k for k in tree
                   if k not in ("embed", "unembed", "ln_f", "mtp.eh",
                                "mtp.ln_e", "mtp.ln_h", "mtp.ln_f")]
    stacked = set(stacked)

    @jax.jit
    def norms(t):
        out = {}
        for k, v in t.items():
            sq = jnp.square(v.astype(jnp.float32))
            out[k] = jnp.sqrt(sq.reshape(sq.shape[0], -1).sum(-1)) \
                if k in stacked else jnp.sqrt(sq.sum())
        return out

    flat = {}
    for k, v in norms(tree).items():
        if k in stacked:
            for i, x in enumerate(jax.device_get(v)):
                flat["%s.%d" % (k, i)] = float(x)
        else:
            flat[k] = float(v)
    return flat


def train(cfg, opt, params, feed, n_steps, moment_step, mode="f32",
          fault=None):
    """Follow ``n_steps`` of training from ``params`` (bfloat16 values).

    ``feed(i)`` gives step i's host batch ``(tokens int32 [B,T], labels
    int32 [B,T])``.  Returns every step's loss, the per-leaf norm of
    Adam's first moment after ``moment_step`` steps and of the
    parameters' change after all of them, and every step's count of
    (token, expert) pairs routed into the held range, over all expert
    layers (``moe_pairs``).  ``fault="half_batch"`` leaves
    the second half of every batch out and takes the mean over the rest.
    """
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, tokens, labels):
        (loss, pairs), g = _grad_and_pairs(cfg, p, tokens, labels, mode)
        t = t + 1.0
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            new_m[k] = b1 * m[k] + (1.0 - b1) * g[k]
            new_v[k] = b2 * v[k] + (1.0 - b2) * g[k] * g[k]
            delta = lr * (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + eps)
            # the stated storage type: one rounding to bfloat16 per step
            new_p[k] = to_bf16(p[k] - delta)
        return new_p, new_m, new_v, t, loss, pairs

    # the step donates its state, so the start is kept as it was given
    # (on the host: see init_params)
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(jnp.float32),
                               params)
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    t = jnp.float32(0.0)
    losses, pairs, moment_norms = [], [], None
    for i in range(n_steps):
        tokens, labels = feed(i)
        if fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        p, m, v, t, loss, n = step(p, m, v, t,
                                   jnp.asarray(tokens, jnp.int32),
                                   jnp.asarray(labels, jnp.int32))
        losses.append(float(loss))
        pairs.append(float(n))
        if i + 1 == moment_step:
            moment_norms = leaf_norms(m)
    del m, v
    delta = jax.jit(lambda a, b: {
        k: a[k] - b[k].astype(jnp.float32) for k in a},
        donate_argnums=(0,))(p, dict(params))
    return {"losses": losses, "moment_norms": moment_norms,
            "delta_norms": leaf_norms(delta), "moe_pairs": pairs}
