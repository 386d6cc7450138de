"""What the drivers share: reading the program's state under the
reference's leaf names WITHOUT putting a copy of it on the device (the
memory reading after the window has to be the program's, not the
check's), and the loss of softmax outputs."""
import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("stacked", "layers"))
def _norms(tree, stacked, layers):
    out = {}
    for k, v in tree.items():
        sq = jnp.square(v.astype(jnp.float32))
        out[k] = jnp.sqrt(sq.reshape(layers, -1).sum(-1)) if k in stacked \
            else jnp.sqrt(sq.sum())
    return out


def leaf_norms(tree, stacked=(), layers=1):
    """{reference leaf: l2 norm} of the program's arrays ``{leaf:
    array}``, reduced on the device in one program with scalars out.  A
    leaf in ``stacked`` holds ``layers`` layers on its leading axes and
    gives one norm per layer, ``leaf.<layer>`` as the reference names
    them."""
    flat = {}
    for k, v in jax.device_get(_norms(tree, tuple(stacked), layers)).items():
        if k in stacked:
            for i, x in enumerate(v):
                flat["%s.%d" % (k, i)] = float(x)
        else:
            flat[k] = float(v)
    return flat


def to_host(tree, shapes=None):
    """The program's arrays as host arrays (no device copy is made),
    reshaped to the reference's ``shapes`` where those differ."""
    host = jax.device_get(tree)
    if shapes:
        host = {k: v.reshape(shapes[k]) for k, v in host.items()}
    return host


@jax.jit
def xent_of_probs(probs, labels):
    """Mean cross-entropy of softmax outputs [..., B, C] against integer
    labels [..., B]: one loss per leading index."""
    p = jnp.take_along_axis(probs.astype(jnp.float32),
                            labels[..., None].astype(jnp.int32), axis=-1)
    return -jnp.log(jnp.maximum(p[..., 0], 1e-30)).mean(-1)
