"""Plain reference of one chip's share of Trinity-Mini (``afmoe``, Arcee
Trinity Mini 26B-A3B), float32.

The equations, from the published ``config.json`` and the family's
modelling code (what no key of ``config.json`` states is listed in the
configuration file under ``assumed``).  ``RMS`` is RMSNorm with
``rms_norm_eps``; ``i`` is a layer's PUBLISHED index (``layers_held``).

* embedding: ``h = Emb[t] * sqrt(hidden_size)`` (``mup_enabled``); no
  position table.
* attention, ``x = RMS(h; ln1)``: ``q = x W_q`` as
  ``num_attention_heads`` heads of ``head_dim``, ``k = x W_k`` and ``v =
  x W_v`` as ``num_key_value_heads`` heads, ``g = x W_gate``; ``q`` and
  ``k`` RMS-normed over each head's values (one ``[head_dim]`` scale
  each).  Where ``layer_types[i]`` is ``sliding_attention``: rotary
  positions on q and k over the whole head (rotate-half, base
  ``rope_theta``, positions 0 .. T-1) and a query sees key ``j`` iff
  ``j <= t`` and ``t - j < sliding_window``.  Where it is
  ``full_attention``: NO positions, and every ``j <= t``.  q head ``h``
  meets kv head ``h // (heads / kv heads)``; scores ``q . k /
  sqrt(head_dim)``, softmax in float32; ``y = (o * sigmoid(g)) W_o``;
  ``h <- h + RMS(y; ln1_post)``.
* feed-forward, ``z = RMS(h; ln2)``, ``h <- h + RMS(f(z); ln2_post)``:
  the first ``num_dense_layers`` layers held: ``f = W_d (silu(z W_g) * (z
  W_u))``; the others: ``s = sigmoid(z W_r)``; the
  ``num_experts_per_tok`` largest ``s + b``; weights ``route_scale * s_e
  / (sum of s over ALL the selected + 1e-20)``; ``f = Shared(z) + sum
  over e selected AND held of w_e * Expert_e(z)``.  What absent experts
  would add is left out.  ``b`` is a constant.
* head: ``logits = RMS(h; ln_f) W_head``, the mean cross-entropy.

Straightforward ``jax.numpy`` that shares nothing with the program under
test: the kv heads are a plain ``repeat``, the mask an ``iota`` compare
over ALL T keys (a window layer computes what a full one does and masks
it), one head and one block of query rows at a time (one head's 8192 x
8192 scores are 268 MB a sequence), every held expert runs over every
token and is masked, every product at ``precision`` ``HIGHEST``.  One
layer's activations at a time are kept (``jax.checkpoint``), the logits
are made in blocks of rows.

``mode``: ``"f32"``; ``"bf16"`` rounds every product's operands, forward
and backward (the cotangent too), to bfloat16; ``"int8"`` rounds them to
8-bit integers with one scale per tensor, the control the limits have to
refuse; ``"no_window"`` is float32 with the WINDOW LEFT OUT (every layer
masks causally and no more), the other control they have to refuse.

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
_QUERY_ROWS = 2048      # query rows of one head scored at a time


def _sizes(cfg):
    return dict(
        E=cfg["hidden_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
        NE=cfg["num_experts"], held=cfg["experts_held"],
        first=cfg.get("expert_first", 0),
        Fs=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        V=cfg["vocab_size"], dense=cfg["num_dense_layers"])


def _is_full(cfg, i):
    """Whether the layer of published index ``i`` attends over the whole
    prefix, by the published list."""
    return cfg["layer_types"][i] == "full_attention"


def _segments(cfg):
    """[(prefix, full, ffn, first row, layers)] in the model's order, as
    the program names its stacks: the window layers with experts keep
    the bare leaf names, leading dense layers are ``dense.``, full-
    attention layers ``full.`` behind that.  A run of layers of one
    prefix is a segment and holds rows [first, first + layers) of the
    prefix's stacked leaves."""
    segs, rows = [], {}
    for j, i in enumerate(cfg["layers_held"]):
        lead, full = j < cfg["num_dense_layers"], _is_full(cfg, i)
        prefix = ("dense." if lead else "") + ("full." if full else "")
        if segs and segs[-1][0] == prefix:
            segs[-1][4] += 1
        else:
            segs.append([prefix, full, "dense" if lead else "moe",
                         rows.get(prefix, 0), 1])
        rows[prefix] = rows.get(prefix, 0) + 1
    return [tuple(s) for s in segs]


def _layer_layout(cfg, ffn):
    z = _sizes(cfg)
    E, H, Hkv, d = z["E"], z["H"], z["Hkv"], z["d"]
    out = [("ln1", (E,), None), ("ln2", (E,), None),
           ("ln1_post", (E,), None), ("ln2_post", (E,), None),
           ("wq", (E, H * d), E), ("wk", (E, Hkv * d), E),
           ("wv", (E, Hkv * d), E), ("wo", (H * d, E), H * d),
           ("q_norm", (d,), None), ("k_norm", (d,), None),
           ("w_gate", (E, H * d), E)]
    if ffn == "dense":
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


def _stacks(cfg):
    """{prefix: (ffn, layers)} over all of a prefix's segments."""
    out = {}
    for prefix, _, ffn, _, n in _segments(cfg):
        out[prefix] = (ffn, out.get(prefix, (ffn, 0))[1] + n)
    return out


def layout(cfg):
    """Ordered (name, shape, fan_in or None); a layer leaf is stacked on
    a leading axis over its prefix's layers."""
    z = _sizes(cfg)
    E, V = z["E"], z["V"]
    out = [("embed", (V, E), E), ("ln_f", (E,), None),
           ("unembed", (E, V), E)]
    for prefix, (ffn, n) in _stacks(cfg).items():
        out += [(prefix + name, (n,) + shape, fan_in)
                for name, shape, fan_in in _layer_layout(cfg, ffn)]
    return out


def stacked_leaves(cfg):
    """{leaf: layers} of the leaves that hold one layer per leading
    index."""
    return {prefix + name: n for prefix, (ffn, n) in _stacks(cfg).items()
            for name, _, _ in _layer_layout(cfg, ffn)}


def init_params(cfg, seed):
    """All weights in one jitted call from the seed, in bfloat16 (the
    type the program stores and trains them in): normal with variance
    1/fan_in, unit norm scales.  Returned as HOST arrays: the caller
    keeps the start through the whole comparison."""
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


def _precision(mode):
    """The products' precision of ``mode``: the window left out is a
    float32 run."""
    return "f32" if mode == "no_window" else mode


def _mm(spec, a, b, mode):
    return in_precision(
        lambda x, y: jnp.einsum(spec, x, y, precision=_HI),
        _precision(mode))(a, b)


def _rms(cfg, x, scale):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True)
                         + cfg["rms_norm_eps"]) * scale


def _rotary(cfg, x):
    """R(x) for x [..., T, d] at positions 0..T-1, rotate-half."""
    T, d = x.shape[-2], x.shape[-1]
    half = d // 2
    inv = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg, x, lw, full, mode):
    z = _sizes(cfg)
    B, T, _ = x.shape
    H, Hkv, d = z["H"], z["Hkv"], z["d"]

    def heads(y, n):                            # -> [n, B, T, d]
        return y.reshape(B, T, n, d).transpose(2, 0, 1, 3)

    q = _rms(cfg, heads(_mm("bte,ef->btf", x, lw["wq"], mode), H),
             lw["q_norm"])
    k = _rms(cfg, heads(_mm("bte,ef->btf", x, lw["wk"], mode), Hkv),
             lw["k_norm"])
    v = heads(_mm("bte,ef->btf", x, lw["wv"], mode), Hkv)
    if not full:                # positions on the window layers only
        q, k = _rotary(cfg, q), _rotary(cfg, k)
    k, v = (jnp.repeat(a, H // Hkv, axis=0) for a in (k, v))
    rows = min(_QUERY_ROWS, T)
    pad = (-T) % rows
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        H, B, -1, rows, d).transpose(0, 2, 1, 3, 4)     # [H, blocks, B, ..]
    window = None if full or mode == "no_window" else cfg["sliding_window"]

    def block(args):
        qr, kh, vh, r = args                    # [B, rows, d], [B, T, d]
        s = _mm("bqd,bkd->bqk", qr, kh, mode) / (d ** 0.5)
        qi = r * rows + lax.broadcasted_iota(jnp.int32, (rows, T), 0)
        ki = lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        seen = ki <= qi
        if window is not None:
            seen &= qi - ki < window
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("bqk,bkd->bqd", a, vh, mode)

    def head(args):
        qh, kh, vh = args                       # [blocks, B, rows, d]
        n = qh.shape[0]
        kb, vb = (jnp.broadcast_to(a, (n,) + a.shape) for a in (kh, vh))
        return lax.map(jax.checkpoint(block), (qh, kb, vb, jnp.arange(n)))

    o = lax.map(head, (qb, k, v))               # [H, blocks, B, rows, d]
    o = o.transpose(2, 1, 3, 0, 4).reshape(B, -1, H * d)[:, :T]
    gate = jax.nn.sigmoid(_mm("bte,ef->btf", x, lw["w_gate"], mode))
    return _mm("bte,ef->btf", o * gate, lw["wo"], mode)


def _gated(x, wg, wu, wd, mode):
    g = _mm("...e,ef->...f", x, wg, mode)
    u = _mm("...e,ef->...f", x, wu, mode)
    return _mm("...f,fe->...e", jax.nn.silu(g) * u, wd, mode)


def route(cfg, zt, router, bias, mode="f32"):
    """(selected experts [n, k], weights [n, k]) of tokens zt [n, E]."""
    s = jax.nn.sigmoid(_mm("ne,ex->nx", zt, router, mode))
    _, idx = lax.top_k(lax.stop_gradient(s + bias),
                       cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * cfg["route_scale"]


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


def _layer(cfg, full, ffn, x, lw, mode):
    h = x + _rms(cfg, _attention(cfg, _rms(cfg, x, lw["ln1"]), lw, full,
                                 mode), lw["ln1_post"])
    zt = _rms(cfg, h, lw["ln2"])
    if ffn == "dense":
        f, pairs = _gated(zt, lw["wg"], lw["wu"], lw["wd"], mode), \
            jnp.int32(0)
    else:
        B, T, E = zt.shape
        f, pairs = _moe(cfg, zt.reshape(B * T, E), lw, mode)
        f = f.reshape(B, T, E)
    return h + _rms(cfg, f, lw["ln2_post"]), pairs


def _segment(cfg, p, segment, x, mode):
    prefix, full, ffn, first, n = segment
    names = [name for name, _, _ in _layer_layout(cfg, ffn)]

    def body(x, lw):
        return _layer(cfg, full, ffn, x, lw, mode)

    x, pairs = lax.scan(jax.checkpoint(body), x,
                        {k: p[prefix + k][first:first + n] for k in names})
    return x, pairs.sum()


def _nll_rows(cfg, p, h, labels, mode):
    """Sum of the negative log likelihood of ``labels`` [n] under the
    head on RMS(h) [n, E], the logits made a block of rows at a time."""
    n, E = h.shape
    block = min(_LOGIT_ROWS, n)
    pad = (-n) % block
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, block, E)
    lb = jnp.pad(labels, (0, pad)).reshape(-1, block)
    keep = (jnp.arange(n + pad) < n).reshape(-1, block)

    def rows(args):
        hh, ll, kk = args
        lg = _mm("ne,ev->nv", _rms(cfg, hh, p["ln_f"]), p["unembed"], mode)
        picked = jnp.take_along_axis(lg, ll[:, None], axis=-1)[:, 0]
        return jnp.where(kk, jax.nn.logsumexp(lg, axis=-1) - picked,
                         0.0).sum()

    return lax.map(jax.checkpoint(rows), (hb, lb, keep)).sum()


def forward(cfg, p, tokens, labels, mode="f32"):
    """(sum of the nll over the batch's tokens, pairs routed into the
    held range over all expert layers)."""
    B, T = tokens.shape
    x = p["embed"][tokens] * (cfg["hidden_size"] ** 0.5
                              if cfg["mup_enabled"] else 1.0)
    pairs = 0
    for segment in _segments(cfg):
        x, n = _segment(cfg, p, segment, x, mode)
        pairs = pairs + n
    return _nll_rows(cfg, p, x.reshape(B * T, -1), labels.reshape(B * T),
                     mode), pairs


def _loss_and_pairs(cfg, p, tokens, labels, mode):
    nll, pairs = forward(cfg, p, tokens, labels, mode)
    return nll / tokens.size, pairs


def loss_fn(cfg, p, tokens, labels, mode="f32"):
    """The batch's loss: the mean nll."""
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
    to add blocks of rows into does not fit beside 8 GB of float32
    state, so the whole batch goes through at once and the blocking is
    inside (a layer, a head's block of query rows, a block of logit rows
    at a time)."""
    del rows
    (loss, _), g = _grad_and_pairs(cfg, p, tokens, labels, mode)
    return loss, g


def leaf_norms(tree, stacked=None):
    """{leaf name: l2 norm}; a leaf in ``stacked`` gives one norm per
    layer, named ``leaf.<layer>``.  ``stacked`` defaults to every leaf
    but the embedding, the head and the final norm (so that the harness,
    which knows no configuration here, gets the same names)."""
    if stacked is None:
        stacked = [k for k in tree if k not in ("embed", "unembed", "ln_f")]
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
    layers (``moe_pairs``).  ``fault="half_batch"`` leaves the second
    half of every batch out and takes the mean over the rest.
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
