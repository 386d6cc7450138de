"""Input kind ``tokens``: uniform int32 token ids and labels, from the
configuration's ``input`` (``length``, ``vocab``)."""
import numpy as np


def draw(spec, k, b, small, big):
    """One host stack of ``k`` batches of ``b`` sequences."""
    t, v = spec["length"], spec["vocab"]
    rng = np.random.default_rng(small)
    return {"data": rng.integers(0, v, (k, b, t), dtype=np.int32),
            "label": rng.integers(0, v, (k, b, t), dtype=np.int32)}


def samples_per_row(spec):
    return int(spec["length"])  # a row is one sequence of tokens
