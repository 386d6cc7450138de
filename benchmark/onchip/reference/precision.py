"""What the references share and the program has no part in: a PRNG key
from any seed, and the rounding by which a reference is computed in a
precision below float32 (the controls of ``correct``)."""
import jax
import jax.numpy as jnp
from jax import lax


def seed_key(seed):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def to_bf16(x):
    """Float32 values rounded to what bfloat16 holds.  Not a pair of
    ``astype``: XLA is allowed to drop a convert to a narrower type and
    back (``xla_allow_excess_precision``), and on the chip it does."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def round_to(x, mode):
    """``x`` as the lower precision holds it: bfloat16, or 8-bit integers
    with one scale for the tensor."""
    if mode == "bf16":
        return to_bf16(x)
    if mode == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    raise ValueError("unknown mode %r" % (mode,))


def in_precision(product, mode):
    """``product(a, b)`` with its operands rounded to ``mode`` in the
    forward pass AND in the backward pass: the two products that give the
    gradients take the rounded operands and the rounded cotangent, as a
    step computed in that precision does.  ``"f32"`` is the product
    itself."""
    if mode == "f32":
        return product

    @jax.custom_vjp
    def lowp(a, b):
        return product(round_to(a, mode), round_to(b, mode))

    def fwd(a, b):
        return lowp(a, b), (a, b)

    def bwd(res, dy):
        _, vjp = jax.vjp(product, round_to(res[0], mode),
                         round_to(res[1], mode))
        return vjp(round_to(dy, mode))

    lowp.defvjp(fwd, bwd)
    return lowp
