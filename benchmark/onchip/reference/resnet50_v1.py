"""Plain reference of ResNet v1 (bottleneck) training, float32.

He et al. 2015 (arXiv:1512.03385), table 1, as MXNet Gluon's
``resnet50_v1`` lays it out: the stride of a down-sampling block sits in
its first 1x1 convolution, the 1x1 convolutions carry a bias (which the
BatchNorm that follows cancels), the 3x3 and projection convolutions do
not.  BatchNorm uses the batch's own mean and biased variance; moving
statistics take no part in a training step and are not kept here.

Straightforward ``jax.numpy``: no kernels, no mixed precision, nothing
imported from the program under test.  Every product runs at
``precision`` ``HIGHEST`` in float32.  ``mode`` selects the controls the
benchmark's ``correct`` is calibrated against: ``"bf16"`` rounds the
operands of every convolution and matrix product, forward and backward
(the cotangent too), to bfloat16, which the configuration states the
program may do; ``"int8"`` rounds them to 8-bit integers with one scale
per tensor: the precision below it, the one the limits have to refuse.

The optimizer is MXNet's SGD with momentum:
``m = momentum*m - lr*(g + wd*w); w = w + m``, weight decay on weights
and BatchNorm scales only, ``g`` the gradient of the batch-mean loss.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .precision import in_precision, seed_key

_HI = lax.Precision.HIGHEST
_BN_EPS = 1e-5


def layout(cfg):
    """Ordered (name, shape, kind) of every parameter leaf."""
    out = []
    ch = cfg["channels"]
    out.append(("stem.conv.w", (ch[0], 3, 7, 7), "conv_w"))
    out += _bn_leaves("stem.bn", ch[0])
    cin = ch[0]
    for s, (n_blocks, cout) in enumerate(zip(cfg["layers"], ch[1:]), 1):
        mid = cout // 4
        for b in range(n_blocks):
            p = "s%d.b%d" % (s, b)
            out.append((p + ".c1.w", (mid, cin, 1, 1), "conv_w"))
            out.append((p + ".c1.b", (mid,), "bias"))
            out += _bn_leaves(p + ".n1", mid)
            out.append((p + ".c2.w", (mid, mid, 3, 3), "conv_w"))
            out += _bn_leaves(p + ".n2", mid)
            out.append((p + ".c3.w", (cout, mid, 1, 1), "conv_w"))
            out.append((p + ".c3.b", (cout,), "bias"))
            out += _bn_leaves(p + ".n3", cout)
            if b == 0 and cin != cout:
                out.append((p + ".ds.w", (cout, cin, 1, 1), "conv_w"))
                out += _bn_leaves(p + ".dn", cout)
            cin = cout
    out.append(("fc.w", (cfg["classes"], ch[-1]), "fc_w"))
    out.append(("fc.b", (cfg["classes"],), "bias"))
    return out


def _bn_leaves(prefix, c):
    return [(prefix + ".g", (c,), "gamma"), (prefix + ".b", (c,), "beta")]


def init_params(cfg, seed):
    """All weights in one jitted call from the seed, on the default
    device, float32 (the type the program keeps its master weights in).
    He-normal convolutions, unit scales, zero shifts and biases."""
    leaves = layout(cfg)

    @jax.jit
    def make(key):
        p = {}
        for i, (name, shape, kind) in enumerate(leaves):
            if kind in ("conv_w", "fc_w"):
                fan_in = int(np.prod(shape[1:]))
                std = (2.0 / fan_in) ** 0.5 if kind == "conv_w" \
                    else (1.0 / fan_in) ** 0.5
                p[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            elif kind == "gamma":
                p[name] = jnp.ones(shape, jnp.float32)
            else:
                p[name] = jnp.zeros(shape, jnp.float32)
        return p

    return make(seed_key(seed))


def _conv(x, w, stride, pad, mode):
    def product(a, b):
        return lax.conv_general_dilated(
            a, b, window_strides=(stride, stride),
            padding=[(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=_HI)

    return in_precision(product, mode)(x, w)


def _bn(x, g, b):
    mean = x.mean((0, 2, 3), keepdims=True)
    var = jnp.square(x - mean).mean((0, 2, 3), keepdims=True)
    return (x - mean) / jnp.sqrt(var + _BN_EPS) * g.reshape(1, -1, 1, 1) \
        + b.reshape(1, -1, 1, 1)


def _block(p, prefix, x, stride, project, mode):
    def bias(name):
        return p[prefix + name].reshape(1, -1, 1, 1)

    y = _conv(x, p[prefix + ".c1.w"], stride, 0, mode) + bias(".c1.b")
    y = jax.nn.relu(_bn(y, p[prefix + ".n1.g"], p[prefix + ".n1.b"]))
    y = _conv(y, p[prefix + ".c2.w"], 1, 1, mode)
    y = jax.nn.relu(_bn(y, p[prefix + ".n2.g"], p[prefix + ".n2.b"]))
    y = _conv(y, p[prefix + ".c3.w"], 1, 0, mode) + bias(".c3.b")
    y = _bn(y, p[prefix + ".n3.g"], p[prefix + ".n3.b"])
    if project:
        x = _conv(x, p[prefix + ".ds.w"], stride, 0, mode)
        x = _bn(x, p[prefix + ".dn.g"], p[prefix + ".dn.b"])
    return jax.nn.relu(y + x)


def logits(cfg, p, x, mode="f32"):
    """x: float32 [N, 3, H, W] -> [N, classes]."""
    y = _conv(x, p["stem.conv.w"], 2, 3, mode)
    y = jax.nn.relu(_bn(y, p["stem.bn.g"], p["stem.bn.b"]))
    y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    ch = cfg["channels"]
    cin = ch[0]
    for s, (n_blocks, cout) in enumerate(zip(cfg["layers"], ch[1:]), 1):
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 1) else 1
            project = b == 0 and cin != cout
            # one block's activations at a time are kept for the backward
            # pass; the rest is recomputed (memory, not arithmetic)
            blk = jax.checkpoint(functools.partial(
                _block, prefix="s%d.b%d" % (s, b), stride=stride,
                project=project, mode=mode))
            y = blk(p, x=y)
            cin = cout
    y = y.mean((2, 3))
    dense = in_precision(
        lambda a, b: jnp.matmul(a, b.T, precision=_HI), mode)
    return dense(y, p["fc.w"]) + p["fc.b"]


def loss_fn(cfg, p, x, labels, mode="f32"):
    """Mean cross-entropy over the rows of the batch."""
    lg = logits(cfg, p, x, mode)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None].astype(jnp.int32),
                                 axis=1)[:, 0]
    return (lse - picked).mean()


# ---------------------------------------------------------------------------
# training


def _decayed(kind):
    return kind in ("conv_w", "fc_w", "gamma")


def leaf_norms(tree):
    """{leaf name: l2 norm}, computed on the device, read as floats."""
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)
    return {k: float(v) for k, v in norms.items()}


def train(cfg, opt, params, feed, n_steps, moment_step, mode="f32",
          fault=None):
    """Follow ``n_steps`` of training from ``params``.

    ``feed(i)`` gives step i's host batch ``(x float32 [B,3,H,W],
    labels [B])``.  Returns the loss of every step, the per-leaf norm of
    the momentum after ``moment_step`` steps (after one step that is the
    first gradient times ``-lr``) and of the parameters' change after
    all of them.  ``fault="half_batch"`` leaves the second half of every
    batch out and takes the mean over the rest.
    """
    kinds = {name: kind for name, _, kind in layout(cfg)}
    lr, mom, wd = opt["learning_rate"], opt["momentum"], opt["wd"]

    @jax.jit
    def step(p, m, x, labels):
        loss, g = jax.value_and_grad(
            lambda q: loss_fn(cfg, q, x, labels, mode))(p)
        new_p, new_m = {}, {}
        for k in p:
            decay = wd if _decayed(kinds[k]) else 0.0
            new_m[k] = mom * m[k] - lr * (g[k] + decay * p[k])
            new_p[k] = p[k] + new_m[k]
        return new_p, new_m, loss

    p0 = params
    p = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, moment_norms = [], None
    for i in range(n_steps):
        x, labels = feed(i)
        if fault == "half_batch":
            half = x.shape[0] // 2
            x, labels = x[:half], labels[:half]
        p, m, loss = step(p, m, jnp.asarray(x, jnp.float32),
                          jnp.asarray(labels, jnp.int32))
        losses.append(float(loss))
        if i + 1 == moment_step:
            moment_norms = leaf_norms(m)
    delta = jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(p, p0)
    return {"losses": losses, "moment_norms": moment_norms,
            "delta_norms": leaf_norms(delta)}
