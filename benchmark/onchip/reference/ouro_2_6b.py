"""Plain reference of one pipeline stage of Ouro-2.6B (``ouro``, ByteDance's
LoopLM), float32.

The equations, from the published ``config.json``, ``modeling_ouro.py``
and arXiv:2510.25741 section 3 (what no key of ``config.json`` states is
listed in the configuration file under ``assumed``).  ``RMS`` is RMSNorm
with ``rms_norm_eps``; U = ``total_ut_steps``; the held layers' weights
are the same at every pass.

* embedding: ``x_0 = Emb[t]``; no position table, no scale.
* pass t = 1 .. U, from ``h = x_{t-1}``, every held layer in order:
  ``a = RMS(h; ln1)``; ``q, k, v = a W_q, a W_k, a W_v`` as
  ``num_attention_heads`` heads of ``head_dim``; rotary positions on q
  and k over the whole head (rotate-half, base ``rope_theta``, positions
  0 .. T-1 at EVERY pass); causal softmax of ``q . k / sqrt(head_dim)``
  in float32; ``h <- h + RMS(o W_o; ln1_post)``; ``z = RMS(h; ln2)``;
  ``h <- h + RMS(W_d (silu(z W_g) * (z W_u)); ln2_post)``.  Then ``x_t =
  RMS(h; ln_f)``, which pass t + 1 starts from.
* at every pass: ``nll_t`` the cross-entropy of ``x_t W_head``, and for
  t < U the exit gate ``lambda_t = sigmoid(x_t . w + b)``.
* the exit distribution: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
  for t < U, ``p_U = prod_{j<U} (1 - lambda_j)``, its logarithms summed
  from ``log sigmoid(s)`` and ``log sigmoid(-s)``.
* the loss, the stage-1 objective: the mean over tokens of ``sum_t p_t
  nll_t + beta sum_t p_t log p_t`` (``exit_entropy``), no gradient
  stopped.

Straightforward ``jax.numpy`` that shares nothing with the program under
test: the passes are a scan and the exit distribution a running sum,
the mask an ``iota`` compare over ALL T keys, one head and one
block of query rows at a time, every product at ``precision``
``HIGHEST``.  One layer's input a pass is kept (``jax.checkpoint``), the
logits are made in blocks of rows and rebuilt for the backward pass.

``mode``: ``"f32"``; ``"bf16"`` rounds every product's operands, forward
and backward (the cotangent too), to bfloat16; ``"int8"`` rounds them to
8-bit integers with one scale per tensor, the control the limits have to
refuse.

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
_WHOLE = ("embed", "ln_f", "unembed", "exit_gate.w", "exit_gate.b")


def _layer_layout(cfg):
    E, H, d, F = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["head_dim"], cfg["intermediate_size"])
    Hkv = cfg["num_key_value_heads"]
    return [("ln1", (E,), None), ("ln2", (E,), None),
            ("ln1_post", (E,), None), ("ln2_post", (E,), None),
            ("wq", (E, H * d), E), ("wk", (E, Hkv * d), E),
            ("wv", (E, Hkv * d), E), ("wo", (H * d, E), H * d),
            ("wg", (E, F), E), ("wu", (E, F), E), ("wd", (F, E), F)]


def layout(cfg):
    """Ordered (name, shape, fan_in, or None for ones, or "zeros"); a
    layer leaf is stacked on a leading axis over the held layers."""
    E, V, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    return [("embed", (V, E), E), ("ln_f", (E,), None),
            ("unembed", (E, V), E), ("exit_gate.w", (E,), E),
            ("exit_gate.b", (), "zeros")] + [
        (name, (n,) + shape, fan_in)
        for name, shape, fan_in in _layer_layout(cfg)]


def stacked_leaves(cfg):
    """{leaf: layers} of the leaves that hold one layer per leading
    index."""
    return {name: cfg["num_hidden_layers"]
            for name, _, _ in _layer_layout(cfg)}


def init_params(cfg, seed):
    """All weights in one jitted call from the seed, in bfloat16 (the
    type the program stores and trains them in): normal with variance
    1/fan_in, unit norm scales, the gate's bias 0.  Returned as HOST
    arrays: the caller keeps the start through the whole comparison."""
    leaves = layout(cfg)

    @jax.jit
    def make(key):
        p = {}
        for i, (name, shape, fan_in) in enumerate(leaves):
            if fan_in is None:
                p[name] = jnp.ones(shape, jnp.bfloat16)
            elif fan_in == "zeros":
                p[name] = jnp.zeros(shape, jnp.bfloat16)
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
    """R(x) for x [..., T, d] at positions 0..T-1, rotate-half."""
    T, d = x.shape[-2], x.shape[-1]
    half = d // 2
    inv = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(cfg, x, lw, mode):
    B, T, _ = x.shape
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])

    def heads(y, n):                            # -> [n, B, T, d]
        return y.reshape(B, T, n, d).transpose(2, 0, 1, 3)

    q = _rotary(cfg, heads(_mm("bte,ef->btf", x, lw["wq"], mode), H))
    k = _rotary(cfg, heads(_mm("bte,ef->btf", x, lw["wk"], mode), Hkv))
    v = heads(_mm("bte,ef->btf", x, lw["wv"], mode), Hkv)
    k, v = (jnp.repeat(a, H // Hkv, axis=0) for a in (k, v))
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
    return _mm("bte,ef->btf", o, lw["wo"], mode)


def _layer(cfg, x, lw, mode):
    h = x + _rms(cfg, _attention(cfg, _rms(cfg, x, lw["ln1"]), lw, mode),
                 lw["ln1_post"])
    z = _rms(cfg, h, lw["ln2"])
    g = _mm("bte,ef->btf", z, lw["wg"], mode)
    u = _mm("bte,ef->btf", z, lw["wu"], mode)
    f = _mm("btf,fe->bte", jax.nn.silu(g) * u, lw["wd"], mode)
    return h + _rms(cfg, f, lw["ln2_post"])


def _stack(cfg, p, x, mode):
    names = [name for name, _, _ in _layer_layout(cfg)]

    def body(x, lw):
        return _layer(cfg, x, lw, mode), None

    x, _ = lax.scan(jax.checkpoint(body), x, {k: p[k] for k in names})
    return x


def _nll_rows(cfg, p, x, labels, mode):
    """The negative log likelihood of ``labels`` [n] under the head on
    x [n, E] (already normed), per token, the logits made a block of
    rows at a time."""
    n, E = x.shape
    block = min(_LOGIT_ROWS, n)
    pad = (-n) % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, E)
    lb = jnp.pad(labels, (0, pad)).reshape(-1, block)

    def rows(args):
        xx, ll = args
        lg = _mm("ne,ev->nv", xx, p["unembed"], mode)
        picked = jnp.take_along_axis(lg, ll[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(lg, axis=-1) - picked

    return lax.map(jax.checkpoint(rows), (xb, lb)).reshape(-1)[:n]


def passes(cfg, p, tokens, mode="f32"):
    """[U, B, T, E]: each pass's normed output x_t, t = 1 .. U.  The
    passes are a scan, so that the backward pass adds every pass's
    gradient of the held layers into one accumulator."""
    def one(x, _):
        x = _rms(cfg, _stack(cfg, p, x, mode), p["ln_f"])
        return x, x

    _, out = lax.scan(one, p["embed"][tokens], None,
                      length=cfg["total_ut_steps"])
    return out


def logits(cfg, p, tokens, mode="f32"):
    """The last pass's logits [B, T, V] (every pass runs: no early
    exit)."""
    return _mm("bte,ev->btv", passes(cfg, p, tokens, mode)[-1],
               p["unembed"], mode)


def forward(cfg, p, tokens, labels, mode="f32"):
    """(the objective summed over the batch's tokens, {"exit_expect":
    sum over tokens of sum_t t p_t, "exit_last": sum over tokens of p_U,
    "log_p": [U, n] of the exit distribution, "nll": [U, n]})."""
    n = tokens.size
    U, beta = cfg["total_ut_steps"], cfg["exit_entropy"]
    nll, log_p, stayed = [], [], jnp.zeros((n,), jnp.float32)
    xs = passes(cfg, p, tokens, mode)
    for t in range(U):
        x = xs[t].reshape(n, -1)
        nll.append(_nll_rows(cfg, p, x, labels.reshape(n), mode))
        if t == U - 1:
            log_p.append(stayed)
            break
        s = _mm("ne,e->n", x, p["exit_gate.w"], mode) + p["exit_gate.b"]
        log_p.append(jax.nn.log_sigmoid(s) + stayed)
        stayed = stayed + jax.nn.log_sigmoid(-s)
    nll, log_p = jnp.stack(nll), jnp.stack(log_p)
    prob = jnp.exp(log_p)
    total = (prob * nll + beta * prob * log_p).sum()
    at = jnp.arange(1, U + 1, dtype=jnp.float32)[:, None]
    return total, {"exit_expect": (at * prob).sum(),
                   "exit_last": prob[-1].sum(), "log_p": log_p, "nll": nll}


def loss_fn(cfg, p, tokens, labels, mode="f32"):
    """The batch's loss: the objective's mean over tokens."""
    return forward(cfg, p, tokens, labels, mode)[0] / tokens.size


def _grad_of_mean(cfg, p, tokens, labels, rows, mode):
    """Loss and gradient of the batch-mean loss.  ``rows`` is accepted
    for the harness's sake and not used: the whole batch goes through at
    once and the blocking is inside (a layer, a head's block of query
    rows, a block of logit rows at a time)."""
    del rows
    return jax.value_and_grad(
        lambda q: loss_fn(cfg, q, tokens, labels, mode))(p)


def leaf_norms(tree, stacked=None):
    """{leaf name: l2 norm}; a leaf in ``stacked`` gives one norm per
    layer, named ``leaf.<layer>``.  ``stacked`` defaults to every leaf
    but the embedding, the head, the final norm and the exit gate (so
    that the harness, which knows no configuration here, gets the same
    names)."""
    if stacked is None:
        stacked = [k for k in tree if k not in _WHOLE]
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
    parameters' change after all of them.  ``fault="half_batch"`` leaves
    the second half of every batch out and takes the mean over the rest.
    """
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, tokens, labels):
        loss, g = _grad_of_mean(cfg, p, tokens, labels, None, mode)
        t = t + 1.0
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        new_p, new_m, new_v = {}, {}, {}
        for k in p:
            new_m[k] = b1 * m[k] + (1.0 - b1) * g[k]
            new_v[k] = b2 * v[k] + (1.0 - b2) * g[k] * g[k]
            delta = lr * (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + eps)
            # the stated storage type: one rounding to bfloat16 per step
            new_p[k] = to_bf16(p[k] - delta)
        return new_p, new_m, new_v, t, loss

    # the step donates its state, so the start is kept as it was given
    # (on the host: see init_params)
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(jnp.float32),
                               params)
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    t = jnp.float32(0.0)
    losses, moment_norms = [], None
    for i in range(n_steps):
        tokens, labels = feed(i)
        if fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        p, m, v, t, loss = step(p, m, v, t, jnp.asarray(tokens, jnp.int32),
                                jnp.asarray(labels, jnp.int32))
        losses.append(float(loss))
        if i + 1 == moment_step:
            moment_norms = leaf_norms(m)
    del m, v
    delta = jax.jit(lambda a, b: {
        k: a[k] - b[k].astype(jnp.float32) for k in a},
        donate_argnums=(0,))(p, dict(params))
    return {"losses": losses, "moment_norms": moment_norms,
            "delta_norms": leaf_norms(delta)}
