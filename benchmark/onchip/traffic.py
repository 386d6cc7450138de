"""The one general generator of training traffic.

A traffic mix is a data file: which loop of the program is driven (its
``driver``), how many steps one program holds, the batch, how many staged
programs are kept ahead and how long the host ring is.  This module turns
such a file, the configuration's ``input`` and the seed into the host
ring: ``ring`` stacks, each ``steps_per_program`` batches whose rows all
differ, the same for the same seed.  A new mix is a new data file; what
one batch of an input kind is made of is ``inputs/<kind>.py``.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np


_CHUNK = 1 << 24        # elements a worker fills from a stream of its own
_WORKERS = 8


def uniform_float32(shape, seeds):
    """float32 uniform [0, 1) of ``shape``, filled in fixed chunks by a
    few threads (numpy's generators release the interpreter lock), each
    chunk from its own child of ``seeds``: the same seed gives the same
    array however the threads are scheduled."""
    out = np.empty(shape, np.float32)
    flat = out.reshape(-1)
    starts = range(0, flat.size, _CHUNK)
    streams = seeds.spawn(len(starts))

    def fill(job):
        start, stream = job
        np.random.default_rng(stream).random(
            out=flat[start:start + _CHUNK], dtype=np.float32)

    with ThreadPoolExecutor(max_workers=_WORKERS) as pool:
        list(pool.map(fill, zip(starts, streams)))
    return out


def _input(config):
    import harness

    return harness.load_module("inputs", config["input"]["kind"])


def host_ring(config, traffic, seed):
    """List of stacks; a stack is a dict of host arrays with a leading
    [steps_per_program, batch] shape."""
    spec, kind = config["input"], _input(config)
    k, b = int(traffic["steps_per_program"]), int(traffic["batch"])
    return [kind.draw(spec, k, b, *seeds.spawn(2))
            for seeds in np.random.SeedSequence(int(seed)).spawn(
                int(traffic["ring"]))]


def samples_per_program(config, traffic):
    """What one program adds to the cell's rate: images, or tokens."""
    return int(traffic["steps_per_program"]) * int(traffic["batch"]) \
        * _input(config).samples_per_row(config["input"])


def step_feed(ring, steps_per_program):
    """``feed(i)``: the batch of the i-th step the stager serves from
    the start, for a reference to follow."""
    def feed(i):
        stack = ring[(i // steps_per_program) % len(ring)]
        j = i % steps_per_program
        return stack["data"][j], stack["label"][j]
    return feed
