"""Plain reference of the decoder-only language model, float32.

GPT-2 medium's published sizes (Radford et al. 2019; ``gpt2-medium``
``config.json``) in the block as THIS repository's
``mxtpu.parallel.transformer`` defines it.  Its departures from GPT-2,
which the configuration file lists under ``assumed``, are kept here
because the reference has to compute what the program claims to compute:

* RMSNorm (``x / sqrt(mean(x^2) + 1e-6) * scale``) where GPT-2 has
  LayerNorm, and no bias anywhere;
* the output projection ``unembed`` is a matrix of its own, not the
  transposed embedding;
* GELU in its tanh form (GPT-2's ``gelu_new``);
* weights are STORED in bfloat16 with no float32 master copy: after each
  Adam step the new weight is rounded to bfloat16.  The reference does
  its arithmetic in float32 and applies that one rounding, so a scale
  at 1.0 moves here exactly when it can move in the stated storage type.

Straightforward ``jax.numpy``: attention materializes its [T, T] scores,
nothing is imported from the program under test, every product runs at
``precision`` ``HIGHEST``.  The batch is walked in blocks of rows
(gradients of a mean loss add over rows), and one layer's activations at
a time are kept, so that it fits beside nothing else on one chip.

``mode``: ``"f32"``; ``"bf16"`` rounds every product's operands, forward
and backward (the cotangent too), to bfloat16; ``"int8"`` rounds them to
8-bit integers with one scale per tensor, the control the limits have
to refuse.

Adam as the program states it: ``m = b1*m + (1-b1)*g``,
``v = b2*v + (1-b2)*g*g``,
``w = bf16(w - lr * (m/(1-b1^t)) / (sqrt(v/(1-b2^t)) + eps))``.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .precision import in_precision, seed_key, to_bf16

_HI = lax.Precision.HIGHEST
_LAYER_LEAVES = ("wq", "wk", "wv", "wo", "ln1", "ln2", "w1", "w2")


def layout(cfg):
    """Ordered (name, shape, fan_in or None); per-layer leaves are
    stacked on a leading layer axis."""
    E, F, V = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    L, T = cfg["n_layer"], cfg["n_positions"]
    return [
        ("embed", (V, E), E), ("pos", (T, E), E), ("ln_f", (E,), None),
        ("unembed", (E, V), E),
        ("wq", (L, E, E), E), ("wk", (L, E, E), E), ("wv", (L, E, E), E),
        ("wo", (L, E, E), E), ("ln1", (L, E), None), ("ln2", (L, E), None),
        ("w1", (L, E, F), E), ("w2", (L, F, E), F),
    ]


def init_params(cfg, seed):
    """All weights in one jitted call from the seed, in bfloat16 (the
    type the program stores and trains them in): normal with variance
    1/fan_in, unit norm scales."""
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

    return make(seed_key(seed))


def _mm(spec, a, b, mode):
    return in_precision(
        lambda x, y: jnp.einsum(spec, x, y, precision=_HI), mode)(a, b)


def _rms(x, scale):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + 1e-6) * scale


def _layer(cfg, x, lw, mode):
    B, T, E = x.shape
    H = cfg["n_head"]
    D = E // H

    def heads(h):
        return h.reshape(B, T, H, D).transpose(0, 2, 1, 3)

    z = _rms(x, lw["ln1"])
    q, k, v = (heads(_mm("bte,ef->btf", z, lw[n], mode))
               for n in ("wq", "wk", "wv"))
    s = _mm("bhqd,bhkd->bhqk", q, k, mode) / (D ** 0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bhkd->bhqd", a, v, mode)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, E)
    h = x + _mm("bte,ef->btf", o, lw["wo"], mode)
    z = _rms(h, lw["ln2"])
    f = jax.nn.gelu(_mm("bte,ef->btf", z, lw["w1"], mode), approximate=True)
    return h + _mm("btf,fe->bte", f, lw["w2"], mode)


def nll_sum(cfg, p, tokens, labels, mode="f32"):
    """Sum over the block's tokens of the negative log likelihood."""
    T = tokens.shape[1]
    x = p["embed"][tokens] + p["pos"][:T][None]
    stacked = {n: p[n] for n in _LAYER_LEAVES}

    def body(x, lw):
        return _layer(cfg, x, lw, mode), None

    x, _ = lax.scan(jax.checkpoint(body), x, stacked)
    lg = _mm("bte,ev->btv", _rms(x, p["ln_f"]), p["unembed"], mode)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return (lse - picked).sum()


def _grad_of_mean(cfg, p, tokens, labels, rows, mode):
    """Loss and gradient of the batch-mean loss, ``rows`` rows at a
    time."""
    B, T = tokens.shape
    n = B // rows
    tk = tokens.reshape(n, rows, T)
    lb = labels.reshape(n, rows, T)

    def block(carry, xs):
        loss, g = jax.value_and_grad(
            lambda q: nll_sum(cfg, q, xs[0], xs[1], mode))(p)
        acc_l, acc_g = carry
        return (acc_l + loss, jax.tree_util.tree_map(jnp.add, acc_g, g)), \
            None

    zero = jax.tree_util.tree_map(jnp.zeros_like, p)
    (loss, g), _ = lax.scan(block, (jnp.float32(0.0), zero), (tk, lb))
    denom = float(B * T)
    return loss / denom, jax.tree_util.tree_map(lambda a: a / denom, g)


def leaf_norms(tree):
    """{leaf name: l2 norm}; a stacked leaf gives one norm per layer,
    named ``leaf.<layer>``."""
    stacked = set(_LAYER_LEAVES)

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
          fault=None, rows=2):
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
        r = min(rows, tokens.shape[0])
        loss, g = _grad_of_mean(cfg, p, tokens, labels, r, mode)
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

    def as_f32(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)

    # the step donates its state, so the start is kept as it was given
    p = as_f32(params)
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
    delta = jax.jit(lambda a, b: {
        k: a[k] - b[k].astype(jnp.float32) for k in a})(p, params)
    return {"losses": losses, "moment_norms": moment_norms,
            "delta_norms": leaf_norms(delta)}
