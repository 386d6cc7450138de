"""Input kind ``image``: float32 uniform [0, 1) NCHW images and uniform
integer labels, from the configuration's ``input`` (``shape``,
``classes``)."""
import numpy as np

from traffic import uniform_float32


def draw(spec, k, b, small, big):
    """One host stack of ``k`` batches of ``b`` rows: ``small`` seeds
    the labels' generator, ``big`` the chunked fill of the images."""
    c, h, w = spec["shape"]
    rng = np.random.default_rng(small)
    return {"data": uniform_float32((k, b, c, h, w), big),
            "label": rng.integers(0, spec["classes"], (k, b),
                                  dtype=np.int32)}


def samples_per_row(spec):
    return 1                    # a row is one image
