"""The router's selection (`transformer._top_k_by_rounds`, `_route`)
against `jax.lax.top_k`, at the expert cells' widths with a few hundred
tokens, on the CPU.  The rounds have to give `lax.top_k`'s values and
indices to the bit: the same experts, in the same order, on every input,
ties, -inf and NaN included.  On the TPU `lax.top_k` is a full sort of
every row; the described compile of the cells' programs
(`tests/test_chip_compile.py`) holds that no such sort is left in them."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.parallel import transformer as tf

ONCHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "onchip")
if ONCHIP not in sys.path:
    sys.path.insert(0, ONCHIP)

N = 300     # tokens: the cells' 4096 - 16 384 cut to a few hundred

# (row shape past the tokens, k): each selection the expert cells make,
# ling's three (512 experts; its 8 groups of 64; the 8 groups' scores),
# solar's, trinity's and glm's; and k = 1
SELECTIONS = [((512,), 8), ((8, 64), 2), ((8,), 4), ((320,), 8),
              ((128,), 8), ((64,), 4), ((64,), 1), ((512,), 1)]


def _rows(kind, shape, seed=0):
    """[N, *shape] float32 of one kind: plain normal draws; a few values
    repeated (ties everywhere); whole rows of one value; -inf over most
    of a row; rows with fewer finite entries than any k here (one); NaN
    of both signs and zeros of both signs among finite values."""
    rng = np.random.RandomState(seed)
    x = rng.randn(N, *shape).astype(np.float32)
    if kind == "ties":
        x = np.round(x * 2) / 2
    elif kind == "equal_rows":
        x = np.broadcast_to(
            rng.randint(0, 3, (N,) + (1,) * len(shape)).astype(np.float32),
            x.shape).copy()
    elif kind == "neg_inf":
        x[rng.rand(*x.shape) < 0.7] = -np.inf
    elif kind == "fewer_finite":
        first = x.reshape(N, -1)[:, :1].copy()
        x[...] = -np.inf
        x.reshape(N, -1)[:, -1:] = first
    elif kind == "nan_and_zeros":
        u = rng.rand(*x.shape)
        x[u < 0.1] = np.nan
        x[(u >= 0.1) & (u < 0.15)] = -np.nan
        x[(u >= 0.2) & (u < 0.4)] = 0.0
        x[(u >= 0.4) & (u < 0.6)] = -0.0
    return jnp.asarray(x)


def _primitives(f, *args):
    """The names of the primitives f's jaxpr holds, inner jaxprs'
    included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return set(walk(jax.make_jaxpr(f)(*args).jaxpr))


@pytest.mark.parametrize("kind", ["normal", "ties", "equal_rows", "neg_inf",
                                  "fewer_finite", "nan_and_zeros"])
@pytest.mark.parametrize("shape,k", SELECTIONS,
                         ids=["x".join(map(str, s)) + "-k%d" % k
                              for s, k in SELECTIONS])
def test_rounds_give_lax_top_k_to_the_bit(shape, k, kind):
    x = _rows(kind, shape)
    got_v, got_i = tf._top_k_by_rounds(x, k)
    want_v, want_i = jax.lax.top_k(x, k)
    assert got_i.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    # the values' bits: -0 against 0 and each NaN's sign count
    np.testing.assert_array_equal(np.asarray(got_v).view(np.int32),
                                  np.asarray(want_v).view(np.int32))


@pytest.mark.parametrize("k", [1, 8, 16])
def test_rounds_trace_no_sort(k):
    x = _rows("normal", (512,))
    used = _primitives(lambda x: tf._top_k_by_rounds(x, k), x)
    assert not used & {"sort", "top_k"}, used
    got = tf._top_k_by_rounds(x, k)
    want = jax.lax.top_k(x, k)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ---------------------------------------------------------------------------
# `_route` at the expert cells' configurations against the sort-based
# selection it had


CELLS = {"glm": ("lm_glm_fused", "glm_4_7_flash_ep8"),
         "ling": ("lm_ling_fused", "ling_3_0_flash_vl_ep64"),
         "trinity": ("lm_trinity_fused", "trinity_mini_ep16"),
         "solar": ("lm_solar_fused", "solar_open2_ep40")}


def _cell_config(cell):
    driver, name = CELLS[cell]
    with open(os.path.join(ONCHIP, "configs", name + ".json")) as f:
        config = json.load(f)
    return importlib.import_module("drivers." + driver).transformer_config(
        config)


def _route_by_sort(cfg, flat, router, bias):
    """`transformer._route` as it stood with `lax.top_k` (a full sort of
    every row on the TPU) and the kept groups set by a scatter."""
    logits = tf._kept(jnp.einsum("ne,ex->nx", flat, router,
                                 preferred_element_type=jnp.float32))
    scores = jax.nn.softmax(logits, axis=-1) \
        if cfg.moe_score == "softmax" else jax.nn.sigmoid(logits)
    select = scores if bias is None else \
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    select = jax.lax.stop_gradient(select)
    if cfg.n_group > 1:
        n, per = select.shape[0], cfg.n_experts // cfg.n_group
        by_group = select.reshape(n, cfg.n_group, per)
        _, best = jax.lax.top_k(
            jax.lax.top_k(by_group, 2)[0].sum(-1), cfg.topk_group)
        kept = jnp.zeros((n, cfg.n_group), bool).at[
            jnp.arange(n)[:, None], best].set(True)
        select = jnp.where(kept[..., None], by_group,
                           -jnp.inf).reshape(select.shape)
    _, idx = jax.lax.top_k(select, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg.moe_norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if cfg.moe_scale != 1.0:
        w = w * cfg.moe_scale
    if cfg.n_group > 1:
        return idx.astype(jnp.int32), w, kept
    return idx.astype(jnp.int32), w


@pytest.mark.parametrize("tied", [False, True], ids=["drawn", "tied"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_route_selects_what_the_sort_did(cell, tied):
    """The same experts, weights and kept groups to the bit, and the
    same gradient into the router; `tied` repeats the router's columns
    and the bias in pairs, so every score has an equal twin.  The
    selection traces no sort."""
    cfg = _cell_config(cell)
    rng = np.random.RandomState(7)
    e, ne = cfg.d_model, cfg.n_experts
    flat = jnp.asarray(rng.randn(N, e), jnp.float32)
    cols = ne // 2 if tied else ne
    router = rng.randn(e, cols) / np.sqrt(e)
    bias = rng.randn(cols) * 0.05
    if tied:
        router, bias = np.repeat(router, 2, 1), np.repeat(bias, 2)
    router = jnp.asarray(router, jnp.float32)
    bias = jnp.asarray(bias, jnp.float32)
    got = tf._route(cfg, flat, router, bias)
    want = _route_by_sort(cfg, flat, router, bias)
    assert len(got) == len(want) == (3 if cfg.n_group > 1 else 2)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if tied:    # the ties bind: some token selects both of a pair
        pairs = np.sort(np.asarray(got[0]) // 2, 1)
        assert (pairs[:, 1:] == pairs[:, :-1]).any()

    def loss(route, router):
        w = route(cfg, flat, router, bias)[1]
        return (w * jnp.arange(1, cfg.top_k + 1)).sum()

    np.testing.assert_array_equal(
        np.asarray(jax.grad(lambda r: loss(tf._route, r))(router)),
        np.asarray(jax.grad(lambda r: loss(_route_by_sort, r))(router)))
    used = _primitives(lambda f, r, b: tf._route(cfg, f, r, b),
                       flat, router, bias)
    assert not used & {"sort", "top_k", "scatter"}, used
