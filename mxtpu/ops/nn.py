"""Neural-network ops.

Covers the reference's `src/operator/nn/*` (Convolution, Deconvolution,
FullyConnected, Pooling, BatchNorm, LayerNorm, LRN, Softmax family,
Activation, Dropout, UpSampling, CTCLoss), the legacy top-level layer ops
(InstanceNorm, L2Normalization, LeakyReLU, Sequence*), and the output/loss
heads (SoftmaxOutput & regression outputs — which in the reference have
*custom backward semantics* independent of the head gradient; reproduced
here with `jax.custom_vjp`, the analog of FGradient overrides).

TPU notes: conv/matmul funnel into `lax.conv_general_dilated` / `dot` so
XLA tiles them onto the MXU; elementwise pre/post ops fuse into those
kernels.  Layout follows the reference's NCHW semantics at the API level —
XLA relayouts internally for the TPU (NHWC-preferring) conv engine.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from ..base import MXNetError, np_dtype
from .registry import register


def _jax():
    import jax

    return jax


def _jnp():
    import jax.numpy as jnp

    return jnp


# ---------------------------------------------------------------------------
# FullyConnected — reference `src/operator/nn/fully_connected.cc`
# ---------------------------------------------------------------------------

@register("FullyConnected")
def _fully_connected(data, weight, *maybe_bias, num_hidden=0, no_bias=False,
                     flatten=True):
    jnp = _jnp()
    x = data
    if flatten:
        x = x.reshape(x.shape[0], -1)
        out = x @ weight.T
    else:
        out = jnp.tensordot(x, weight.T, axes=([x.ndim - 1], [0]))
    if not no_bias and maybe_bias:
        out = out + maybe_bias[0]
    return out


# ---------------------------------------------------------------------------
# Convolution — reference `src/operator/nn/convolution.cc` (NCHW/OIHW)
# ---------------------------------------------------------------------------

_SPATIAL = {1: "W", 2: "HW", 3: "DHW"}


def _conv_dnums(nspatial: int):
    sp = _SPATIAL[nspatial]
    return ("NC" + sp, "OI" + sp, "NC" + sp)


def _conv_dnums_cl(nspatial: int):
    sp = _SPATIAL[nspatial]
    return ("N" + sp + "C", sp + "IO", "N" + sp + "C")


def _norm_tuple(v, n, default):
    if not v:
        return (default,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@register("Convolution", aliases=("Convolution_v1",))
def _convolution(data, weight, *maybe_bias, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=0, num_group=1, no_bias=False,
                 workspace=1024, layout=None, cudnn_tune=None, cudnn_off=False):
    """``layout="NHWC"`` runs channels-last: data/output are NHWC
    while the weight stays OIHW (this build's gluon blocks always
    allocate OIHW)."""
    lax = _jax().lax
    ns = len(kernel)
    stride = _norm_tuple(stride, ns, 1)
    dilate = _norm_tuple(dilate, ns, 1)
    pad = _norm_tuple(pad, ns, 0)
    cl = str(layout or "").upper() == "N" + _SPATIAL[ns] + "C"
    if cl:
        rhs = weight.transpose(tuple(range(2, 2 + ns)) + (1, 0))  # spIO
        dn = lax.conv_dimension_numbers(data.shape, rhs.shape,
                                        _conv_dnums_cl(ns))
    else:
        rhs = weight
        dn = lax.conv_dimension_numbers(data.shape, rhs.shape,
                                        _conv_dnums(ns))
    out = lax.conv_general_dilated(
        data, rhs,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        lhs_dilation=(1,) * ns,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if not no_bias and maybe_bias:
        out = out + (maybe_bias[0] if cl
                     else maybe_bias[0].reshape((1, -1) + (1,) * ns))
    return out


@register("Deconvolution")
def _deconvolution(data, weight, *maybe_bias, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), target_shape=(), num_filter=0, num_group=1,
                   no_bias=True, workspace=1024, layout=None, cudnn_tune=None,
                   cudnn_off=False):
    """Transposed convolution via input dilation (gradient-of-conv
    formulation, reference `src/operator/nn/deconvolution.cc`)."""
    lax = _jax().lax
    jnp = _jnp()
    ns = len(kernel)
    stride = _norm_tuple(stride, ns, 1)
    dilate = _norm_tuple(dilate, ns, 1)
    pad = _norm_tuple(pad, ns, 0)
    adj = _norm_tuple(adj, ns, 0)
    if target_shape:
        # adj derived from requested output size
        adj = tuple(
            (target_shape[i] + 2 * pad[i] - ((kernel[i] - 1) * dilate[i] + 1))
            % stride[i]
            for i in range(ns)
        )
    # weight layout (C_in, num_filter/num_group, *kernel)
    w = jnp.flip(weight, axis=tuple(range(2, 2 + ns)))
    if num_group > 1:
        ci, co_g = weight.shape[0], weight.shape[1]
        w = w.reshape((num_group, ci // num_group, co_g) + kernel)
        w = jnp.swapaxes(w, 1, 2)  # (g, co_g, ci_g, *k)
        w = w.reshape((num_group * co_g, ci // num_group) + kernel)
    else:
        w = jnp.swapaxes(w, 0, 1)  # (O, I, *k)
    eff_k = tuple((kernel[i] - 1) * dilate[i] + 1 for i in range(ns))
    padding = [(eff_k[i] - 1 - pad[i], eff_k[i] - 1 - pad[i] + adj[i])
               for i in range(ns)]
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _conv_dnums(ns))
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * ns,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if not no_bias and maybe_bias:
        out = out + maybe_bias[0].reshape((1, -1) + (1,) * ns)
    return out


# ---------------------------------------------------------------------------
# Pooling — reference `src/operator/nn/pooling.cc`
# ---------------------------------------------------------------------------

def _pool_pads(in_sz, k, s, p, convention):
    """Return (lo, hi) padding per spatial dim for valid/full conventions."""
    if convention == "full":
        out = int(np.ceil((in_sz + 2 * p - k) / s)) + 1
    else:  # valid / same handled by caller
        out = (in_sz + 2 * p - k) // s + 1
    needed = (out - 1) * s + k - in_sz - p
    return (p, max(needed, p))


@register("Pooling", aliases=("Pooling_v1",))
def _pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(),
             pad=(), pooling_convention="valid", count_include_pad=True,
             p_value=2, cudnn_off=False, layout=None):
    """``layout`` ending in ``C`` (NHWC/NWC/NDHWC) pools channels-last;
    the NCHW-family values gluon always sends select the default path."""
    lax = _jax().lax
    jnp = _jnp()
    nd = data.ndim
    ns = nd - 2
    cl = bool(layout) and str(layout).upper() == \
        "N" + _SPATIAL.get(ns, "?") + "C"
    if global_pool:
        axes = tuple(range(1, nd - 1)) if cl else tuple(range(2, nd))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type in ("avg", "sum"):
            r = jnp.sum(data, axis=axes, keepdims=True)
            if pool_type == "avg":
                r = r / np.prod([data.shape[a] for a in axes])
            return r
        if pool_type == "lp":
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value),
                                     axis=axes, keepdims=True), 1.0 / p_value)
    kernel = tuple(kernel)
    stride = _norm_tuple(stride, ns, 1)
    pad = _norm_tuple(pad, ns, 0)
    # only where the channel dim sits differs between the layouts
    sp0 = 1 if cl else 2  # first spatial dim position
    spatial_pads = [
        _pool_pads(data.shape[sp0 + i], kernel[i], stride[i], pad[i],
                   pooling_convention)
        for i in range(ns)
    ]
    if cl:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = [(0, 0)] + spatial_pads + [(0, 0)]
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        pads = [(0, 0), (0, 0)] + spatial_pads
    # NOTE: init values must be python scalars so lax.reduce_window
    # specializes to reduce_window_max/add primitives (which carry the
    # autodiff rules); a traced init array kills differentiability.
    if pool_type == "max":
        # jnp's lattice knows extension floats (bfloat16 has numpy kind
        # 'V', so np.issubdtype would misroute it to iinfo)
        init = -np.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            int(np.iinfo(np.dtype(data.dtype)).min)
        return lax.reduce_window(data, np.dtype(data.dtype).type(init), lax.max,
                                 window, strides, pads)
    zero = np.dtype(data.dtype).type(0)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, zero, lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            return s / np.prod(kernel)
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, zero, lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        s = lax.reduce_window(jnp.power(jnp.abs(data), p_value), zero,
                              lax.add, window, strides, pads)
        return jnp.power(s, 1.0 / p_value)
    raise MXNetError("unknown pool_type %r" % pool_type)


@register("_contrib_AdaptiveAvgPooling2D")
def _adaptive_avg_pool2d(data, output_size=(1, 1)):
    jnp = _jnp()
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    if not output_size:
        output_size = (1, 1)
    n, c, h, w = data.shape
    oh, ow = output_size
    # reduce via reshape when divisible (common case), else interpolate
    if h % oh == 0 and w % ow == 0:
        x = data.reshape(n, c, oh, h // oh, ow, w // ow)
        return x.mean(axis=(3, 5))
    import jax

    return jax.image.resize(data, (n, c, oh, ow), method="linear")


@register("_contrib_BilinearResize2D")
def _bilinear_resize2d(data, height=0, width=0, scale_height=None,
                       scale_width=None, mode="size"):
    import jax

    n, c, h, w = data.shape
    if scale_height is not None:
        height = int(round(h * scale_height))
        width = int(round(w * scale_width))
    return jax.image.resize(data, (n, c, int(height), int(width)), method="linear")


@register("UpSampling")
def _upsampling(*args, scale=1, sample_type="nearest", num_args=1,
                num_filter=0, multi_input_mode="concat", workspace=512):
    jnp = _jnp()
    data = args[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        return out
    import jax

    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="linear")


# ---------------------------------------------------------------------------
# Normalization layers
# ---------------------------------------------------------------------------

def _single_pass_stats(jnp, x, axes, keepdims=False, force=False):
    """Mean and variance for normalization layers.

    Low-precision inputs (bf16/f16) — or force=True — use the
    single-pass E[x]/E[x^2] form: ONE fused reduction sweep in f32
    accumulators (jnp.var re-subtracts the mean, forcing a second
    sequential HBM pass before the normalize pass; what that read costs
    on the chip: not measured).  The
    E[x^2]-E[x]^2 cancellation is bounded by the input precision: a
    bf16 tensor with |mean|/std beyond ~2^8 cannot represent the
    variation in the first place, so f32 accumulators lose nothing.

    float32+ inputs keep the numerically stable two-pass jnp.var —
    there a mean-dominated input (|mean|/std ~ 2^12) genuinely carries
    variance the one-pass formula would cancel away."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=axes, keepdims=keepdims)
    if force or jnp.dtype(x.dtype).itemsize < 4:
        meansq = jnp.mean(jnp.square(x32), axis=axes, keepdims=keepdims)
        return mean, jnp.maximum(meansq - jnp.square(mean), 0.0)
    return mean, jnp.var(x32, axis=axes, keepdims=keepdims)


@register("BatchNorm", num_outputs=3, train_aware=True,
          aliases=("BatchNorm_v1",),
          visible_outputs=lambda attrs: 3 if attrs.get("output_mean_var")
          else 1)
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False, is_train=False):
    """Returns (out, mean, var).  The imperative/Gluon layer updates the
    moving stats outside (reference mutates aux states in place —
    `src/operator/nn/batch_norm.cc`)."""
    jnp = _jnp()
    axes = tuple(i for i in range(data.ndim) if i != axis % data.ndim)
    bshape = [1] * data.ndim
    bshape[axis % data.ndim] = data.shape[axis % data.ndim]
    # statistics accumulate in float32 even for bf16/fp16 activations
    # (reference accumulates in AccReal=float, batch_norm-inl.h); the
    # normalized output returns in the input dtype so AMP graphs stay
    # low-precision end to end
    x32 = data.astype(jnp.float32)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if is_train and not use_global_stats:
        # force=True: batch stats over post-conv activations are
        # zero-mean-ish, so the one-pass cancellation is benign even in
        # fp32 (same accumulate-in-AccReal choice as the reference,
        # `src/operator/nn/batch_norm-inl.h`) — and BN dominates the
        # memory-bound CNN train step where the pass matters most
        mean, var = _single_pass_stats(jnp, data, axes, force=True)
    else:
        mean, var = (moving_mean.astype(jnp.float32),
                     moving_var.astype(jnp.float32))
    inv = g.astype(jnp.float32).reshape(bshape) / \
        jnp.sqrt(var.reshape(bshape) + eps)
    out = (x32 - mean.reshape(bshape)) * inv + \
        beta.astype(jnp.float32).reshape(bshape)
    return out.astype(data.dtype), mean, var


@register("LayerNorm", num_outputs=3,
          visible_outputs=lambda attrs: 3 if attrs.get("output_mean_var")
          else 1)
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    jnp = _jnp()
    ax = axis % data.ndim
    mean, var = _single_pass_stats(jnp, data, ax, keepdims=True)
    std = jnp.sqrt(var + eps)
    norm = ((data.astype(jnp.float32) - mean) / std).astype(data.dtype)
    bshape = [1] * data.ndim
    bshape[ax] = data.shape[ax]
    out = norm * gamma.reshape(bshape) + beta.reshape(bshape)
    return (out, jnp.squeeze(mean, ax).astype(data.dtype),
            jnp.squeeze(std, ax).astype(data.dtype))


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3):
    jnp = _jnp()
    axes = tuple(range(2, data.ndim))
    mean, var = _single_pass_stats(jnp, data, axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data.astype(jnp.float32) - mean) / jnp.sqrt(var + eps) \
        * gamma.reshape(bshape).astype(jnp.float32) + \
        beta.reshape(bshape).astype(jnp.float32)
    return out.astype(data.dtype)


@register("L2Normalization")
def _l2_normalization(data, eps=1e-10, mode="instance"):
    jnp = _jnp()
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
        keep = True
    elif mode == "channel":
        axes = (1,)
        keep = True
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
        keep = True
    else:
        raise MXNetError("unknown L2Normalization mode %r" % mode)
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=keep) + eps)
    return data / norm


@register("LRN")
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    jnp = _jnp()
    sq = jnp.square(data)
    half = nsize // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2)
    sq_p = jnp.pad(sq, pad)
    acc = sum(sq_p[:, i:i + data.shape[1]] for i in range(nsize))
    return data / jnp.power(knorm + (alpha / nsize) * acc, beta)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

@register("Activation")
def _activation(data, act_type="relu"):
    jax = _jax()
    jnp = _jnp()
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise MXNetError("unknown act_type %r" % act_type)


@register("relu")
def _relu(x):
    return _jax().nn.relu(x)


@register("sigmoid")
def _sigmoid(x):
    return _jax().nn.sigmoid(x)


@register("hard_sigmoid")
def _hard_sigmoid(x, alpha=0.2, beta=0.5):
    return _jnp().clip(alpha * x + beta, 0.0, 1.0)


@register("softsign")
def _softsign(x):
    return _jax().nn.soft_sign(x)


@register("LeakyReLU")
def _leaky_relu(data, *maybe_gamma, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334):
    jax = _jax()
    jnp = _jnp()
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * (jnp.exp(data) - 1.0))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "selu":
        return jax.nn.selu(data)
    if act_type == "prelu":
        g = maybe_gamma[0]
        bshape = [1] * data.ndim
        if g.ndim == 1 and data.ndim > 1:
            bshape[1] = g.shape[0]
            g = g.reshape(bshape)
        return jnp.where(data > 0, data, g * data)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, mid * data)
    raise MXNetError("unknown LeakyReLU act_type %r" % act_type)


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------

@register("softmax")
def _softmax(data, axis=-1, temperature=None, dtype=None, length=None):
    jax = _jax()
    x = data / temperature if temperature else data
    out = jax.nn.softmax(x, axis=axis)
    return out.astype(np_dtype(dtype)) if dtype else out


@register("softmin")
def _softmin(data, axis=-1, temperature=None, dtype=None):
    jax = _jax()
    x = -data / temperature if temperature else -data
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None, dtype=None):
    jax = _jax()
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance"):
    jax = _jax()
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(
        data.shape)


# ---------------------------------------------------------------------------
# Dropout — needs rng + train gating
# ---------------------------------------------------------------------------

@register("Dropout", needs_rng=True, train_aware=True)
def _dropout(key, data, p=0.5, mode="training", axes=(), cudnn_off=False,
             is_train=False):
    jax = _jax()
    jnp = _jnp()
    active = (mode == "always") or is_train
    if not active or p <= 0.0:
        return jnp.asarray(data)
    shape = list(data.shape)
    if axes:
        for a in axes:
            shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ---------------------------------------------------------------------------
# Output heads with custom backward (reference: SoftmaxOutput etc. define
# their own gradient regardless of the incoming head grad)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _softmax_output_core(grad_scale, ignore_label, multi_output, use_ignore,
                         preserve_shape, normalization, smooth_alpha):
    import jax
    import jax.numpy as jnp

    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def f(data, label):
        return jax.nn.softmax(data, axis=axis)

    def fwd(data, label):
        p = jax.nn.softmax(data, axis=axis)
        return p, (p, label)

    def bwd(res, g):
        p, label = res
        n_class = p.shape[axis]
        lab = label.astype(jnp.int32)
        if multi_output:
            oh = jax.nn.one_hot(lab, n_class, axis=1, dtype=p.dtype)
        else:
            oh = jax.nn.one_hot(lab.reshape(p.shape[:-1]), n_class, dtype=p.dtype)
        if smooth_alpha:
            oh = oh * (1.0 - smooth_alpha) + smooth_alpha / n_class
        grad = p - oh
        valid = None
        if use_ignore:
            mask = (lab != int(ignore_label)).astype(p.dtype)
            if multi_output:
                grad = grad * jnp.expand_dims(mask, 1)
            else:
                grad = grad * jnp.expand_dims(mask.reshape(p.shape[:-1]), -1)
            valid = jnp.maximum(mask.sum(), 1.0)
        scale = grad_scale
        if normalization == "batch":
            scale = scale / p.shape[0]
        elif normalization == "valid" and valid is not None:
            scale = scale / valid
        elif normalization == "valid":
            scale = scale / p.shape[0]
        grad = grad * scale
        return grad, jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@register("SoftmaxOutput", aliases=("Softmax",))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    f = _softmax_output_core(float(grad_scale), float(ignore_label),
                             bool(multi_output), bool(use_ignore),
                             bool(preserve_shape), str(normalization),
                             float(smooth_alpha))
    return f(data, label.astype(data.dtype))


def _regression_core(grad_fn_name, grad_scale):
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def f(data, label):
        if grad_fn_name == "logistic":
            return jax.nn.sigmoid(data)
        return data

    def fwd(data, label):
        out = f(data, label)
        return out, (data, label)

    def bwd(res, g):
        data, label = res
        num = np.prod(data.shape[1:]) if data.ndim > 1 else 1
        if grad_fn_name == "linear":
            grad = (data - label)
        elif grad_fn_name == "mae":
            grad = jnp.sign(data - label)
        elif grad_fn_name == "logistic":
            grad = jax.nn.sigmoid(data) - label
        grad = grad * (grad_scale / num)
        return grad, jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@functools.lru_cache(maxsize=64)
def _regression_cached(kind, grad_scale):
    return _regression_core(kind, grad_scale)


@register("LinearRegressionOutput")
def _linear_regression_output(data, label, grad_scale=1.0):
    return _regression_cached("linear", float(grad_scale))(data, label)


@register("MAERegressionOutput")
def _mae_regression_output(data, label, grad_scale=1.0):
    return _regression_cached("mae", float(grad_scale))(data, label)


@register("LogisticRegressionOutput")
def _logistic_regression_output(data, label, grad_scale=1.0):
    return _regression_cached("logistic", float(grad_scale))(data, label)


@functools.lru_cache(maxsize=64)
def _svm_core(margin, regularization_coefficient, use_linear):
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def f(data, label):
        return data

    def fwd(data, label):
        return data, (data, label)

    def bwd(res, g):
        data, label = res
        n_class = data.shape[1]
        oh = jax.nn.one_hot(label.astype(jnp.int32), n_class, dtype=data.dtype)
        score_correct = jnp.sum(data * oh, axis=1, keepdims=True)
        if use_linear:
            viol = ((margin - (2 * oh - 1) * data) > 0).astype(data.dtype)
            grad = -(2 * oh - 1) * viol * regularization_coefficient
        else:
            dist = margin - (2 * oh - 1) * data
            viol = (dist > 0).astype(data.dtype)
            grad = -2 * (2 * oh - 1) * dist * viol * regularization_coefficient
        return grad, jnp.zeros_like(label)

    f.defvjp(fwd, bwd)
    return f


@register("SVMOutput")
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False):
    return _svm_core(float(margin), float(regularization_coefficient),
                     bool(use_linear))(data, label.astype(data.dtype))


@functools.lru_cache(maxsize=64)
def _make_loss_core(grad_scale, normalization):
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def f(data):
        return data

    def fwd(data):
        return data, data

    def bwd(res, g):
        scale = grad_scale
        if normalization == "batch":
            scale = scale / res.shape[0]
        return (jnp.full_like(res, scale),)

    f.defvjp(fwd, bwd)
    return f


@register("MakeLoss")
def _make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    return _make_loss_core(float(grad_scale), str(normalization))(data)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    jax = _jax()
    jnp = _jnp()
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
    return -jnp.sum(logp * oh)


@register("CTCLoss", aliases=("ctc_loss", "_contrib_CTCLoss", "_contrib_ctc_loss"))
def _ctc_loss(data, label, *lengths, blank_label="first",
              use_data_lengths=False, use_label_lengths=False):
    """CTC loss (reference `src/operator/nn/ctc_loss.cc`).  data: (T, N, C),
    label: (N, L) padded with 0 (blank at class 0, 'first' convention).
    Optional extra inputs in order: data_lengths (N,), label_lengths (N,)
    when the corresponding use_*_lengths flag is set."""
    import optax

    jnp = _jnp()
    t, n, c = data.shape
    logits = jnp.transpose(data, (1, 0, 2))  # (N, T, C)
    li = 0
    if use_data_lengths:
        dlen = lengths[li].astype(np.int32)
        li += 1
        logit_pad = (jnp.arange(t)[None, :] >= dlen[:, None]).astype(data.dtype)
    else:
        logit_pad = jnp.zeros((n, t), dtype=data.dtype)
    labels = label.astype(np.int32)
    if use_label_lengths:
        llen = lengths[li].astype(np.int32)
        label_pad = (jnp.arange(label.shape[1])[None, :] >=
                     llen[:, None]).astype(data.dtype)
    elif blank_label == "first":
        label_pad = (labels <= 0).astype(data.dtype)
    else:
        label_pad = (labels >= c - 1).astype(data.dtype)
    blank_id = 0 if blank_label == "first" else c - 1
    loss = optax.ctc_loss(logits, logit_pad, labels, label_pad,
                          blank_id=blank_id)
    return loss


# ---------------------------------------------------------------------------
# Sequence ops — reference `src/operator/sequence_*.cc`
# ---------------------------------------------------------------------------

@register("SequenceMask")
def _sequence_mask(data, *maybe_len, use_sequence_length=False, value=0.0,
                   axis=0):
    jnp = _jnp()
    if not use_sequence_length or not maybe_len:
        return jnp.asarray(data)
    seqlen = maybe_len[0]
    t = data.shape[axis]
    pos = jnp.arange(t)
    if axis == 0:
        bshape = (t,) + (1,) * (data.ndim - 1)
        lshape = (1, -1) + (1,) * (data.ndim - 2)
    else:
        bshape = (1, t) + (1,) * (data.ndim - 2)
        lshape = (-1, 1) + (1,) * (data.ndim - 2)
    mask = pos.reshape(bshape) < seqlen.reshape(lshape)
    return jnp.where(mask, data, jnp.asarray(value, dtype=data.dtype))


@register("SequenceLast")
def _sequence_last(data, *maybe_len, use_sequence_length=False, axis=0):
    jnp = _jnp()
    if not use_sequence_length or not maybe_len:
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    seqlen = maybe_len[0].astype(np.int32) - 1
    if axis == 0:
        idx = jnp.clip(seqlen, 0, data.shape[0] - 1)
        return jnp.take_along_axis(
            data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0
        ).squeeze(0)
    idx = jnp.clip(seqlen, 0, data.shape[1] - 1)
    return jnp.take_along_axis(
        data, idx.reshape((-1, 1) + (1,) * (data.ndim - 2)), axis=1
    ).squeeze(1)


@register("SequenceReverse")
def _sequence_reverse(data, *maybe_len, use_sequence_length=False, axis=0):
    jnp = _jnp()
    if not use_sequence_length or not maybe_len:
        return jnp.flip(data, axis=0)
    seqlen = maybe_len[0].astype(np.int32)
    t = data.shape[0]
    pos = jnp.arange(t)[:, None]  # (T,1)
    lens = seqlen[None, :]  # (1,N)
    src = jnp.where(pos < lens, lens - 1 - pos, pos)  # reverse within length
    src = src.reshape((t, -1) + (1,) * (data.ndim - 2))
    return jnp.take_along_axis(data, src, axis=0)


@register("_contrib_div_sqrt_dim")
def _div_sqrt_dim(data):
    jnp = _jnp()
    return data / np.sqrt(data.shape[-1])


@register("_contrib_quadratic")
def _quadratic(data, a=0.0, b=0.0, c=0.0):
    return a * data * data + b * data + c


@register("IdentityAttachKLSparseReg")
def _identity_attach_kl(data, sparseness_target=0.1, penalty=0.001,
                        momentum=0.9):
    return _jnp().asarray(data)
