"""Plain reference of one chip's share of Solar Open 2 (``solar_open2``,
Upstage Solar-Open2-250B), float32.

The equations, from the published ``config.json`` (every choice where a
key is silent is listed in the configuration file under ``assumed``).
``RMS`` is RMSNorm with ``rms_norm_eps``; ``i`` is a layer's PUBLISHED
index (``layers_held``); every mixer and feed-forward is pre-norm
residual; no product has a bias; there are NO positions anywhere
(``use_rope`` false, no position table).  The layer mixes by GQA where
``i`` is in ``gqa_layers`` and by Kimi Delta Attention otherwise; every
layer's feed-forward is the expert layer (``first_k_dense_replace`` 0).

* Kimi Delta Attention (arXiv:2510.26692, the ``KimiDeltaAttention``
  layer of flash-linear-attention), H heads of d (``linear_attn_config``),
  ``x = RMS(h; ln1)``: ``q~, k~, v = silu(conv(x W_q)), silu(conv(x
  W_k)), silu(conv(x W_v))``, ``conv`` causal and depthwise over
  ``short_conv_kernel_size`` taps, the last on the current token; per
  head ``q = q~ / |q~| * d^-1/2``, ``k = k~ / |k~|`` (1e-6 inside the
  root); the log-decay per head AND channel ``g_t = -exp(A_log_h) *
  softplus((x_t F_a) F_b + dt_bias)``, with no lower bound; the write
  strength per head ``beta_t = 2 sigmoid(x_t W_b)`` in (0, 2)
  (``kda_allow_neg_eigval``); the state ``S`` ``[d, d]`` per head from
  nought, TOKEN BY TOKEN: ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
  S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``; out ``W_o [RMS_d(o_t,h;
  o_norm) * sigmoid((x_t G_a) G_b)_h]``, the gate elementwise
  (``kda_use_full_proj`` false: F and G through rank-d bottlenecks).
* GQA (``use_gqa_gate``): ``q = x W_q`` as H heads of ``head_dim``, ``k =
  x W_k`` and ``v = x W_v`` as ``num_key_value_heads`` heads, no norms
  and no positions; q head ``h`` meets kv head ``h // (H / Hkv)``;
  scores ``q . k / sqrt(head_dim)`` for ``j <= t``, softmax in float32;
  ``y = (o * sigmoid(x W_gate)) W_o``, the gate elementwise.
* expert layer, ``z = RMS(h; ln2)``: ``s = sigmoid(z W_r)``; the
  ``num_experts_per_tok`` largest ``s + b`` (no groups); weights
  ``routed_scaling_factor * s_e / (sum of s over ALL the selected +
  1e-20)``; ``f = Shared(z) + sum over e selected AND held of w_e *
  Expert_e(z)``, SwiGLU experts of ``moe_intermediate_size`` and one
  shared expert of ``n_shared_experts`` times that.  What absent experts
  would add is left out.  ``b`` is a constant.
* head: ``logits = RMS(h; ln_f) W_head`` (untied), the mean
  cross-entropy over the vocabulary slice.

Straightforward ``jax.numpy`` that shares nothing with the program under
test: the recurrence is a ``lax.scan`` over the tokens (in blocks of
tokens under ``jax.checkpoint``, which changes no arithmetic), attention
is an ``iota``-masked softmax over a ``repeat`` of the kv heads, one head
and one block of query rows at a time, every held expert runs over every
token and is masked, every product at ``precision`` ``HIGHEST``.  One
layer's activations at a time are kept (``jax.checkpoint``), the logits
are made in blocks of rows.

``mode``: ``"f32"``; ``"bf16"`` rounds every product's operands, forward
and backward (the cotangent too), to bfloat16, and what the recurrence
is handed (q, k, v, beta) likewise; ``"int8"`` rounds them to 8-bit
integers with one scale per tensor, the control the limits have to
refuse; ``"pos_beta"`` is float32 with the NEGATIVE EIGENVALUES LEFT
OUT (``beta = sigmoid(x W_b)`` in (0, 1)), the other control they have
to refuse.

Weights are STORED in bfloat16 with no float32 master copy: the
reference does its arithmetic in float32 and rounds the new weight to
bfloat16 once per Adam step.  Adam as the program states it:
``m = b1*m + (1-b1)*g``, ``v = b2*v + (1-b2)*g*g``,
``w = bf16(w - lr * (m/(1-b1^t)) / (sqrt(v/(1-b2^t)) + eps))``.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .precision import in_precision, round_to, seed_key, to_bf16

_HI = lax.Precision.HIGHEST
_LOGIT_ROWS = 2048      # rows of logits made at a time
_QUERY_ROWS = 1024      # query rows of one head scored at a time
_TOKEN_BLOCK = 64       # tokens of the recurrence under one checkpoint
_WHOLE = ("embed", "ln_f", "unembed")


def _sizes(cfg):
    lin = cfg["linear_attn_config"]
    return dict(
        E=cfg["hidden_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        Hl=lin["num_heads"], dl=lin["head_dim"],
        taps=lin["short_conv_kernel_size"], Fe=cfg["moe_intermediate_size"],
        Fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        NE=cfg["n_routed_experts"], held=cfg["experts_held"],
        first=cfg.get("expert_first", 0), V=cfg["vocab_size"])


def _segments(cfg):
    """[(prefix, mixer, first row, layers)] in the model's order, as the
    program names and stacks them: KDA layers are ``kda.``, GQA layers
    bare; a run of layers of one prefix is a segment and holds rows
    [first, first + layers) of the prefix's stacked leaves."""
    segs, rows = [], {}
    for i in cfg["layers_held"]:
        kda = i not in cfg["gqa_layers"]
        prefix = "kda." if kda else ""
        if segs and segs[-1][0] == prefix:
            segs[-1][3] += 1
        else:
            segs.append([prefix, "kda" if kda else "gqa",
                         rows.get(prefix, 0), 1])
        rows[prefix] = rows.get(prefix, 0) + 1
    return [tuple(s) for s in segs]


def _layer_layout(cfg, mixer):
    z = _sizes(cfg)
    E = z["E"]
    out = [("ln1", (E,), None), ("ln2", (E,), None)]
    if mixer == "kda":
        H, d = z["Hl"], z["dl"]
        for n in ("q", "k", "v"):
            out += [("w" + n, (E, H * d), E),
                    ("conv_" + n, (z["taps"], H * d), z["taps"])]
        out += [("f_a", (E, d), E), ("f_b", (d, H * d), d),
                ("g_a", (E, d), E), ("g_b", (d, H * d), d),
                ("A_log", (H,), "A_log"), ("dt_bias", (H * d,), "dt_bias"),
                ("wb", (E, H), E), ("o_norm", (d,), None),
                ("wo", (H * d, E), H * d)]
    else:
        H, Hkv, d = z["H"], z["Hkv"], z["d"]
        out += [("wq", (E, H * d), E), ("wk", (E, Hkv * d), E),
                ("wv", (E, Hkv * d), E), ("wo", (H * d, E), H * d),
                ("w_gate", (E, H * d), E)]
    Fe, Fs, held = z["Fe"], z["Fs"], z["held"]
    return out + [
        ("router", (E, z["NE"]), E),
        ("router_bias", (z["NE"],), 1e4),       # drawn small, constant
        ("we_g", (held, E, Fe), E), ("we_u", (held, E, Fe), E),
        ("we_d", (held, Fe, E), Fe),
        ("ws_g", (E, Fs), E), ("ws_u", (E, Fs), E), ("ws_d", (Fs, E), Fs)]


def _stacks(cfg):
    """{prefix: (mixer, layers)} over all of a prefix's segments."""
    out = {}
    for prefix, mixer, _, n in _segments(cfg):
        out[prefix] = (mixer, out.get(prefix, (mixer, 0))[1] + n)
    return out


def layout(cfg):
    """Ordered (name, shape, how it is drawn); a layer leaf is stacked on
    a leading axis over the layers of its kind.  Drawn: None a norm scale
    (ones), a number the fan-in of a normal, "A_log" / "dt_bias" the
    decay gate's (``init_params``)."""
    z = _sizes(cfg)
    E, V = z["E"], z["V"]
    out = [("embed", (V, E), E), ("ln_f", (E,), None),
           ("unembed", (E, V), E)]
    for prefix, (mixer, n) in _stacks(cfg).items():
        out += [(prefix + name, (n,) + shape, how)
                for name, shape, how in _layer_layout(cfg, mixer)]
    return out


def stacked_leaves(cfg):
    """{leaf: layers} of the leaves that hold one layer per leading
    index."""
    return {prefix + name: n for prefix, (mixer, n) in _stacks(cfg).items()
            for name, _, _ in _layer_layout(cfg, mixer)}


def init_params(cfg, seed):
    """All weights in one jitted call from the seed, in bfloat16 (the
    type the program stores and trains them in): normal with variance
    1/fan_in (the convolutions' taps 1/4), unit norm scales, ``A_log =
    log U(1, 16)``, ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly from (1e-3, 1e-1).  Returned as HOST arrays: the
    caller keeps the start through the whole comparison."""
    leaves = layout(cfg)

    @jax.jit
    def make(key):
        p = {}
        for i, (name, shape, how) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if how is None:
                a = jnp.ones(shape, jnp.float32)
            elif how == "A_log":
                a = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               1.0, 16.0))
            elif how == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
                a = dt + jnp.log(-jnp.expm1(-dt))
            else:
                a = jax.random.normal(k, shape, jnp.float32) \
                    * (1.0 / how) ** 0.5
            p[name] = a.astype(jnp.bfloat16)
        return p

    return jax.device_get(make(seed_key(seed)))


def _precision(mode):
    """The products' precision of ``mode``: the negative eigenvalues left
    out is a float32 run."""
    return "f32" if mode == "pos_beta" else mode


def _mm(spec, a, b, mode):
    return in_precision(
        lambda x, y: jnp.einsum(spec, x, y, precision=_HI),
        _precision(mode))(a, b)


def _lowp(x, mode):
    """``x`` as ``mode`` holds it, its cotangent rounded likewise: what
    the recurrence is handed where the stated precision is below
    float32."""
    mode = _precision(mode)
    if mode == "f32":
        return x

    @jax.custom_vjp
    def f(a):
        return round_to(a, mode)

    f.defvjp(lambda a: (round_to(a, mode), None),
             lambda _, g: (round_to(g, mode),))
    return f(x)


def _rms(cfg, x, scale):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True)
                         + cfg["rms_norm_eps"]) * scale


def _conv(x, taps):
    """y_t = sum_j taps[j] * x[t - (K - 1) + j]: causal, depthwise, the
    last tap on the current token.  x: [B, T, C]; taps: [K, C]."""
    K, T = taps.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * taps[j] for j in range(K))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule as written, one token at a time.  q, k, v, g:
    [B, T, H, d]; beta: [B, T, H].  Returns o [B, T, H, d]."""
    B, T, H, d = q.shape
    block = _TOKEN_BLOCK if T % _TOKEN_BLOCK == 0 else T

    def token(S, xs):
        qt, kt, vt, gt, bt = xs                         # [B, H, d], [B, H]
        S = jnp.exp(gt)[..., None] * S                  # Diag(alpha) S
        # (I - beta k k^T) S + beta k v^T = S + beta k (v - S^T k)^T
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt,
                                             precision=_HI))
        S = S + kt[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=_HI)

    def tokens(S, xs):
        return lax.scan(token, S, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape((T // block, block)
                                             + a.shape[:1] + a.shape[2:])
               for a in (q, k, v, g, beta))
    _, o = lax.scan(jax.checkpoint(tokens),
                    jnp.zeros((B, H, d, d), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((T, B, H, d)), 0, 1)


def _kda(cfg, x, lw, mode):
    z = _sizes(cfg)
    B, T, _ = x.shape
    H, d = z["Hl"], z["dl"]

    def heads(y):
        return y.reshape(B, T, H, d)

    def low_rank(a, b):
        return _mm("btr,rf->btf", _mm("bte,er->btr", x, lw[a], mode), lw[b],
                   mode)

    q, k, v = (heads(jax.nn.silu(_conv(
        _mm("bte,ef->btf", x, lw["w" + n], mode), lw["conv_" + n])))
        for n in ("q", "k", "v"))
    q = q * lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) * d ** -0.5
    k = k * lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    g = -jnp.exp(lw["A_log"])[:, None] * jax.nn.softplus(
        heads(low_rank("f_a", "f_b") + lw["dt_bias"]))
    beta = jax.nn.sigmoid(_mm("bte,eh->bth", x, lw["wb"], mode))
    if mode != "pos_beta":
        beta = 2.0 * beta
    o = delta_rule(_lowp(q, mode), _lowp(k, mode), _lowp(v, mode), g,
                   _lowp(beta, mode))
    o = _rms(cfg, o, lw["o_norm"]) * jax.nn.sigmoid(heads(
        low_rank("g_a", "g_b")))
    return _mm("bte,ef->btf", o.reshape(B, T, H * d), lw["wo"], mode)


def _attention(cfg, x, lw, mode):
    z = _sizes(cfg)
    B, T, _ = x.shape
    H, Hkv, d = z["H"], z["Hkv"], z["d"]

    def heads(y, n):                            # -> [n, B, T, d]
        return y.reshape(B, T, n, d).transpose(2, 0, 1, 3)

    q = heads(_mm("bte,ef->btf", x, lw["wq"], mode), H)
    k, v = (jnp.repeat(heads(_mm("bte,ef->btf", x, lw[w], mode), Hkv),
                       H // Hkv, axis=0) for w in ("wk", "wv"))
    rows = min(_QUERY_ROWS, T)
    pad = (-T) % rows
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        H, B, -1, rows, d).transpose(0, 2, 1, 3, 4)     # [H, blocks, B, ..]

    def block(args):
        qr, kh, vh, r = args                    # [B, rows, d], [B, T, d]
        s = _mm("bqd,bkd->bqk", qr, kh, mode) / (d ** 0.5)
        qi = r * rows + lax.broadcasted_iota(jnp.int32, (rows, T), 0)
        ki = lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        a = jax.nn.softmax(jnp.where(ki <= qi, s, -jnp.inf), axis=-1)
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


def _layer(cfg, mixer, x, lw, mode):
    mix = _kda if mixer == "kda" else _attention
    h = x + mix(cfg, _rms(cfg, x, lw["ln1"]), lw, mode)
    zt = _rms(cfg, h, lw["ln2"])
    B, T, E = zt.shape
    f, pairs = _moe(cfg, zt.reshape(B * T, E), lw, mode)
    return h + f.reshape(B, T, E), pairs


def _segment(cfg, p, segment, x, mode):
    prefix, mixer, first, n = segment
    names = [name for name, _, _ in _layer_layout(cfg, mixer)]

    def body(x, lw):
        return _layer(cfg, mixer, x, lw, mode)

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
    x = p["embed"][tokens]
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
    to add blocks of rows into does not fit beside the float32 state, so
    the whole batch goes through at once and the blocking is inside (a
    layer, a head's block of query rows, a block of tokens or logit rows
    at a time)."""
    del rows
    (loss, _), g = _grad_and_pairs(cfg, p, tokens, labels, mode)
    return loss, g


def leaf_norms(tree, stacked=None):
    """{leaf name: l2 norm}; a leaf in ``stacked`` gives one norm per
    layer, named ``leaf.<layer>``.  ``stacked`` defaults to every leaf
    but the embedding, the head and the final norm (so that the harness,
    which knows no configuration here, gets the same names).  A leaf at
    a time: the leaves may be host arrays."""
    if stacked is None:
        stacked = [k for k in tree if k not in _WHOLE]
    stacked = set(stacked)

    @functools.partial(jax.jit, static_argnums=(1,))
    def norm(v, by_layer):
        sq = jnp.square(v.astype(jnp.float32))
        return jnp.sqrt(sq.reshape(sq.shape[0], -1).sum(-1)) if by_layer \
            else jnp.sqrt(sq.sum())

    flat = {}
    for k, v in tree.items():
        n = jax.device_get(norm(v, k in stacked))
        if k in stacked:
            for i, x in enumerate(n):
                flat["%s.%d" % (k, i)] = float(x)
        else:
            flat[k] = float(n)
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

    At the cell's size the float32 weights are 4.1e9 bytes, and so are
    the gradient and each of Adam's moments: the weights and the
    gradient live on the device, the gradient is one program and Adam
    another, a LEAF at a time, each call donating the leaf's weight and
    gradient, and BOTH moments wait on the host between steps.
    """
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])

    @jax.jit
    def grad(p, tokens, labels):
        return _grad_and_pairs(cfg, p, tokens, labels, mode)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adam(w, m, v, g, t):
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        # the stated storage type: one rounding to bfloat16 per step
        return to_bf16(w - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)), m, v

    # the start is kept as it was given (on the host: see init_params)
    p = {k: jnp.asarray(a).astype(jnp.float32) for k, a in params.items()}
    m_host = {k: np.zeros(a.shape, np.float32) for k, a in p.items()}
    v_host = {k: np.zeros(a.shape, np.float32) for k, a in p.items()}
    losses, pairs, moment_norms = [], [], None
    for i in range(n_steps):
        tokens, labels = feed(i)
        if fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        (loss, n), g = grad(p, jnp.asarray(tokens, jnp.int32),
                            jnp.asarray(labels, jnp.int32))
        t = jnp.float32(i + 1)
        for k in list(p):
            p[k], m, v = adam(p[k], jnp.asarray(m_host[k]),
                              jnp.asarray(v_host[k]), g.pop(k), t)
            m_host[k], v_host[k] = np.asarray(m), np.asarray(v)
            del m, v
        losses.append(float(loss))
        pairs.append(float(n))
        if i + 1 == moment_step:
            moment_norms = leaf_norms(m_host)
    del m_host, v_host
    delta = {}
    for k in list(p):
        delta[k] = jax.jit(lambda a, b: a - b.astype(jnp.float32),
                           donate_argnums=(0,))(p.pop(k), params[k])
    return {"losses": losses, "moment_norms": moment_norms,
            "delta_norms": leaf_norms(delta), "moe_pairs": pairs}
