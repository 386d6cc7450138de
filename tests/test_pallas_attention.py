"""Pallas flash-attention kernel (`mxtpu/ops/pallas_attention.py`).

Runs the kernel in Pallas interpreter mode on CPU (the driver's real
TPU run exercises the compiled path); numeric gold is the standard
softmax attention.
"""
import numpy as np
import pytest

import mxtpu as mx


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


def _naive(q, k, v, scale, causal):
    s = np.einsum("bqd,bkd->bqk", q, k).astype(np.float64) * scale
    if causal:
        tq, tk = s.shape[-2:]
        mask = np.arange(tq)[:, None] >= np.arange(tk)[None, :]
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bqk,bkd->bqd", p, v.astype(np.float64))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 256, 64), (1, 384, 128)])
def test_flash_matches_naive(causal, shape):
    from mxtpu.ops.pallas_attention import flash_attention

    rng = np.random.RandomState(0)
    q, k, v = (rng.normal(0, 1, shape).astype(np.float32)
               for _ in range(3))
    import jax.numpy as jnp

    out = np.asarray(flash_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=causal,
                                     block_q=128, block_k=128))
    gold = _naive(q, k, v, 1.0 / np.sqrt(shape[-1]), causal)
    np.testing.assert_allclose(out, gold, rtol=2e-4, atol=2e-5)


def test_flash_4d_and_op_registration():
    from mxtpu import nd

    rng = np.random.RandomState(1)
    q, k, v = (rng.normal(0, 1, (2, 3, 128, 32)).astype(np.float32)
               for _ in range(3))
    out = nd.contrib.flash_attention(nd.array(q), nd.array(k),
                                     nd.array(v), causal=True)
    assert out.shape == (2, 3, 128, 32)
    gold = _naive(q.reshape(6, 128, 32), k.reshape(6, 128, 32),
                  v.reshape(6, 128, 32), 1.0 / np.sqrt(32), True)
    np.testing.assert_allclose(out.asnumpy().reshape(6, 128, 32), gold,
                               rtol=2e-4, atol=2e-5)


def test_flash_gradients_match_reference():
    """custom_vjp backward (recompute formulation) vs autodiff through
    the plain softmax attention."""
    import jax
    import jax.numpy as jnp

    from mxtpu.ops.pallas_attention import (_reference_attention,
                                            flash_attention)

    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 128, 32))
                           .astype(np.float32)) for _ in range(3))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_reference_attention(q, k, v, 1.0 / np.sqrt(32),
                                     True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4,
                                   err_msg="d%s" % name)


def test_blockwise_attention_pallas_route():
    """The kernel route must match the jnp blockwise path's numerics —
    forced via explicit use_pallas args so the baseline stays the jnp
    loop whatever the ambient routing default resolves to."""
    import jax.numpy as jnp

    from mxtpu.parallel import blockwise_attention

    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (1, 2, 256, 32))
                           .astype(np.float32)) for _ in range(3))
    base = np.asarray(blockwise_attention(q, k, v, causal=True,
                                          block_size=128,
                                          use_pallas=False))
    got = np.asarray(blockwise_attention(q, k, v, causal=True,
                                         block_size=128,
                                         use_pallas=True))
    np.testing.assert_allclose(got, base, rtol=2e-4, atol=2e-5)


def test_flash_ragged_lengths_fall_back():
    """Sequence lengths that don't divide the block fall back to the
    fused reference path (still correct, no padding hazards)."""
    import jax.numpy as jnp

    from mxtpu.ops.pallas_attention import flash_attention

    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 100, 32))
                           .astype(np.float32)) for _ in range(3))
    out = np.asarray(flash_attention(q, k, v, causal=False,
                                     block_q=64, block_k=64))
    gold = _naive(np.asarray(q), np.asarray(k), np.asarray(v),
                  1.0 / np.sqrt(32), False)
    np.testing.assert_allclose(out, gold, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_multiblock(causal):
    """The blocked backward with several q/k blocks (nq=nk=4) matches
    autodiff through plain attention — the multi-block accumulation
    paths, causal block masking, and LSE reassembly all engage."""
    import jax
    import jax.numpy as jnp

    from mxtpu.ops.pallas_attention import (_reference_attention,
                                            flash_attention)

    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 256, 32))
                           .astype(np.float32)) for _ in range(3))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=64,
                                block_k=64) ** 2).sum()

    def loss_ref(q, k, v):
        return (_reference_attention(q, k, v, 1.0 / np.sqrt(32),
                                     causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)


def test_flash_gradients_ragged_multiblock():
    """Ragged Tq/Tk (padding paths in the blocked backward)."""
    import jax
    import jax.numpy as jnp

    from mxtpu.ops.pallas_attention import (_reference_attention,
                                            flash_attention)

    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.normal(0, 1, (1, 100, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, 90, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, 90, 16)).astype(np.float32))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=False, block_q=32,
                                block_k=32) ** 2).sum()

    def loss_ref(q, k, v):
        return (_reference_attention(q, k, v, 0.25, False) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)


def test_flash_bfloat16_roundtrip():
    """bf16 inputs: internal math is fp32, output returns bf16; values
    track the fp32 reference within bf16 tolerance."""
    import jax.numpy as jnp

    from mxtpu.ops.pallas_attention import flash_attention

    rng = np.random.RandomState(7)
    qf, kf, vf = (rng.normal(0, 1, (2, 128, 64)).astype(np.float32)
                  for _ in range(3))
    q, k, v = (jnp.asarray(x, dtype=jnp.bfloat16) for x in (qf, kf, vf))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    gold = _naive(qf, kf, vf, 1.0 / np.sqrt(64), True)
    np.testing.assert_allclose(np.asarray(out).astype(np.float32), gold,
                               rtol=0.05, atol=0.05)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_kernels_match_jnp_sweeps(causal, monkeypatch):
    """The dq / dk-dv Pallas kernels (interpret mode) against the jnp
    blocked sweeps, called directly — proves the kernel path itself,
    not just the end-to-end gradient."""
    import jax.numpy as jnp

    from mxtpu.ops import pallas_attention as fa

    rng = np.random.RandomState(8)
    q, k, v, g = (jnp.asarray(rng.normal(0, 1, (2, 256, 32))
                              .astype(np.float32)) for _ in range(4))
    scale = 1.0 / np.sqrt(32)
    out, lse = fa._reference_attention_lse(q, k, v, scale, causal)
    delta = (out * g).sum(-1)
    got = fa._flash_backward_pallas(q, k, v, g, out, lse, scale,
                                    causal, 64, 64)
    # jnp sweeps: disable the pallas route for the direct comparison
    monkeypatch.setenv("MXTPU_NO_PALLAS", "1")
    monkeypatch.delenv("MXTPU_PALLAS_INTERPRET", raising=False)
    ref = fa._flash_bwd_sweeps(q, k, v, g, delta, lse, scale, causal,
                               64, 64)
    for a, b, name in zip(got, ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=name)


# (bh, tq, tk, d, block_q, block_k, causal): every class of the causal
# walk -- skipped, unmasked, diagonal in sub-tiles -- and what stays
# outside it
_WALK_CASES = {
    "skip+unmasked+subtiled": (2, 1024, 1024, 64, 512, 512, True),
    "one_diagonal_block": (2, 512, 512, 64, 512, 512, True),
    "blocks256_d128": (1, 1024, 1024, 128, 256, 256, True),
    "fallback_bq>bk": (1, 1024, 1024, 64, 512, 256, True),
    "fallback_bq<bk": (1, 1024, 1024, 64, 256, 512, True),
    "blocks_fall_to_128": (1, 640, 640, 64, None, None, True),
    "ragged_q_padded_rows": (1, 900, 1024, 64, 512, 512, True),
    "not_causal": (1, 1024, 1024, 64, 512, 512, False),
}


@pytest.mark.parametrize("case", list(_WALK_CASES), ids=list(_WALK_CASES))
def test_causal_walk_matches_reference(case):
    """Forward and all three gradients against `_reference_attention`
    over block shapes that reach every class of the causal walk."""
    import jax
    import jax.numpy as jnp

    from mxtpu import profiler
    from mxtpu.ops.pallas_attention import (_reference_attention,
                                            flash_attention)

    bh, tq, tk, d, block_q, block_k, causal = _WALK_CASES[case]
    blocks = {} if block_q is None else dict(block_q=block_q,
                                             block_k=block_k)
    rng = np.random.RandomState(31)
    q = jnp.asarray(rng.normal(0, 1, (bh, tq, d)).astype(np.float32))
    k, v = (jnp.asarray(rng.normal(0, 1, (bh, tk, d)).astype(np.float32))
            for _ in range(2))
    w = jnp.asarray(rng.normal(0, 1, (bh, tq, d)).astype(np.float32))
    scale = 1.0 / np.sqrt(d)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, **blocks)

    def ref(q, k, v):
        return _reference_attention(q, k, v, scale, causal)

    before = dict(profiler.stats())
    got, vjp = jax.vjp(flash, q, k, v)
    grads = vjp(w)
    # forward and backward took the kernels; a ragged q takes the
    # forward kernel on padded rows and the jnp sweeps backward
    took = {p: profiler.get_stat("flash_attention_" + p)
            - before.get("flash_attention_" + p, 0)
            for p in ("pallas", "reference")}
    assert took == ({"pallas": 1, "reference": 1} if tq != tk
                    else {"pallas": 2, "reference": 0}), took
    gold, vjp_ref = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gold),
                               rtol=2e-4, atol=2e-5)
    for a, b, name in zip(grads, vjp_ref(w), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)


@pytest.mark.parametrize("causal,blocks,window,want", [
    (True, (512, 512), None, (64, 36, 8)),    # the walk: 128-wide sub-tiles
    (True, (512, 256), None, (8, 6, 4)),      # whole blocks: the fallback
    (False, (512, 512), None, (64, 64, 0)),
    # T = 1024 in 2 x 2 blocks of 4 x 4 tiles under a window of 512: the
    # two diagonal blocks in sub-tiles (10 visited, 4 masked each) and
    # block (1, 0), which the band's left edge crosses (query 512 + r
    # sees key c iff c > r), whole under the mask: 16 and 16
    (True, (512, 512), 512, (64, 36, 24)),
    # ... of 256 in 4 x 4 blocks of 2 x 2 tiles: four diagonal blocks (3
    # visited, 2 masked), three edge blocks (i, i - 1) (4 and 4); the
    # three blocks (i, i - 2) and (3, 0) lie wholly left of the band
    (True, (256, 256), 256, (64, 24, 20)),
    # ... of 100, narrower than a 128 block: the diagonal blocks are
    # edge blocks too (whole, masked), and so are the 7 blocks (i, i - 1)
    (True, (128, 128), 100, (64, 15, 15)),
    (True, (512, 512), 1024, (64, 36, 8)),    # reaches every key: causal
], ids=["walk", "fallback", "not_causal", "band_512", "band_256",
        "band_narrower_than_a_block", "window_reaches_every_key"])
def test_flash_tiles_stats(causal, blocks, window, want):
    """`flash_tiles_{total,visited,masked}`: what one traced call adds
    per head at gpt2-medium's shape (T=1024, d=64), against a count by
    hand.  The parent's kernels visited and masked 48 of 64."""
    import jax
    import jax.numpy as jnp

    from mxtpu import profiler
    from mxtpu.ops.pallas_attention import flash_attention_bthd

    names = ["flash_tiles_" + n for n in ("total", "visited", "masked")]
    x = jax.ShapeDtypeStruct((2, 1024, 64), jnp.float32)
    before = [profiler.get_stat(n) for n in names]
    jax.eval_shape(lambda q, k, v: flash_attention_bthd(
        q[:, :, None], k[:, :, None], v[:, :, None], causal=causal,
        block_q=blocks[0], block_k=blocks[1], window=window), x, x, x)
    got = tuple(profiler.get_stat(n) - b for n, b in zip(names, before))
    assert got == want


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("d", [64, 128, 256])
def test_bthd_entry_matches_reference(d, causal):
    """`flash_attention_bthd`, the entry on the activations' layout (q,
    k, v [B, T, H, D] -> [B, T, H * D]): forward, the log-sums its
    `custom_vjp` keeps, and all three gradients against
    `_reference_attention_lse`; the residuals are q, k, v AS GIVEN, the
    merged output and the log-sums."""
    import jax
    import jax.numpy as jnp

    from mxtpu.ops import pallas_attention as fa

    b, t, h = 2, 256, 2
    rng = np.random.RandomState(d)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (b, t, h, d))
                           .astype(np.float32)) for _ in range(3))
    w = jnp.asarray(rng.normal(0, 1, (b, t, h * d)).astype(np.float32))
    scale = 1.0 / np.sqrt(d)

    def split(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    def ref(q, k, v):
        out, lse = fa._reference_attention_lse(split(q), split(k),
                                               split(v), scale, causal)
        return out.reshape(b, h, t, d).transpose(0, 2, 1, 3) \
            .reshape(b, t, h * d), lse

    (gold, gold_lse), vjp_ref = jax.vjp(ref, q, k, v)
    got, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_bthd(
        q, k, v, causal=causal, block_q=128, block_k=128), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gold),
                               rtol=2e-4, atol=2e-5)
    for a, g, name in zip(vjp(w), vjp_ref((w, jnp.zeros_like(gold_lse))),
                          "qkv"):
        assert a.shape == (b, t, h, d)
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)
    out, res = fa._flash_fwd(q, k, v, scale, causal, 128, 128, None)
    assert res[0] is q and res[1] is k and res[2] is v
    assert res[3].shape == (b, t, h * d) and res[4].shape == (b * h, t)
    np.testing.assert_allclose(np.asarray(res[4]), np.asarray(gold_lse),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("grad", [False, True], ids=["plain", "for_grad"])
def test_flash_fwd_stats(grad):
    """`flash_fwd_traced` / `flash_fwd_named`: a forward kernel traced
    outside differentiation is not named; differentiating traces the
    `custom_vjp`'s forward rule, whose output and log-sums go out under
    `FLASH_OUT` / `FLASH_LSE`."""
    import jax
    import jax.numpy as jnp

    from mxtpu import profiler
    from mxtpu.ops import pallas_attention as fa

    x = jnp.ones((1, 128, 2, 64), jnp.float32)

    def f(q):
        return fa.flash_attention_bthd(q, x, x, causal=True).sum()

    names = ("flash_fwd_traced", "flash_fwd_named")
    before = [profiler.get_stat(n) for n in names]
    jaxpr = jax.make_jaxpr(jax.grad(f) if grad else f)(x)
    got = tuple(profiler.get_stat(n) - b for n, b in zip(names, before))
    assert got == ((1, 1) if grad else (1, 0))
    named = {e.params["name"] for e in jaxpr.eqns
             if e.primitive.name == "name"}
    assert named == ({fa.FLASH_OUT, fa.FLASH_LSE} if grad else set())


# (heads, head width) -> (heads to a lane block, or 0: a split copy):
# how `_lane_plan` has the kernels read [B, T, H * D]; None stands for
# the (bh, t, d) entry, which arrives as one head
_LAYOUT_CASES = {
    "two_heads_to_a_block": ((16, 64), 2),
    "head_is_a_block_128": ((4, 128), 1),
    "head_is_a_block_256": ((20, 256), 1),
    "does_not_tile_split": ((3, 64), 0),
    "bh_t_d_entry": (None, 1),
}


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("case", list(_LAYOUT_CASES), ids=list(_LAYOUT_CASES))
def test_kernels_read_the_activations_layout_in_place(case, causal):
    """The kernels on [B, T, H * D] as it is (a head, or the heads that
    share a 128-lane vreg, picked by the lane block of the index maps):
    forward and all three gradients against `_reference_attention`, and
    against the same numbers through the (bh, t, d) entry -- today's
    program before PR 35 -- to float32 rounding; and what the three
    stats of the layout read for each case."""
    import jax
    import jax.numpy as jnp

    from mxtpu import profiler
    from mxtpu.ops import pallas_attention as fa

    heads_width, per_block = _LAYOUT_CASES[case]
    h, d = heads_width or (2, 64)
    b, t, block = 1, 512, 256       # 2 x 2 blocks of 2 x 2 sub-tiles
    rng = np.random.RandomState(h * d)
    q, k, v, w = (jnp.asarray(rng.normal(0, 1, (b, t, h, d))
                              .astype(np.float32)) for _ in range(4))
    scale = 1.0 / np.sqrt(d)

    def split(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    def merge(x):
        return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    def by_entry(q, k, v):          # one head a batch row
        return merge(fa.flash_attention(split(q), split(k), split(v),
                                        causal=causal, block_q=block,
                                        block_k=block))

    def in_place(q, k, v):
        return fa.flash_attention_bthd(
            q, k, v, causal=causal, block_q=block,
            block_k=block).reshape(b, t, h, d)

    def ref(q, k, v):
        return merge(fa._reference_attention(split(q), split(k), split(v),
                                             scale, causal))

    names = ("flash_calls_in_place", "flash_calls_split")
    before = [profiler.get_stat(n) for n in names]
    profiler.set_stat("flash_heads_per_block", 0)
    got, vjp = jax.vjp(by_entry if heads_width is None else in_place,
                       q, k, v)
    grads = vjp(w)
    calls = tuple(profiler.get_stat(n) - x for n, x in zip(names, before))
    # a forward launch and the backward pass's two
    assert calls == ((3, 0) if per_block else (0, 3)), calls
    assert profiler.get_stat("flash_heads_per_block") == max(per_block, 1)

    gold, vjp_ref = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gold),
                               rtol=2e-4, atol=2e-5)
    for a, g, name in zip(grads, vjp_ref(w), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)
    if heads_width is None:
        return
    same, vjp_same = jax.vjp(by_entry, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(same),
                               rtol=1e-5, atol=1e-6)
    for a, g, name in zip(grads, vjp_same(w), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   rtol=1e-5, atol=2e-5,
                                   err_msg="d%s against the entry" % name)


@pytest.mark.parametrize("fits", [True, False], ids=["a_slot_a_q_block",
                                                     "taken_every_step"])
def test_dkv_sweep_keeps_the_q_side_where_it_fits(fits, monkeypatch):
    """The dkv sweep takes a q block's columns (log-sums, delta) while it
    sweeps the first k block and keeps them in a slot per q block; where
    the sequence is too long for that it takes them every step.  Both
    ways give the reference's dk and dv (two heads to a lane block, 4 x
    4 blocks, causal)."""
    import jax
    import jax.numpy as jnp

    from mxtpu.ops import pallas_attention as fa

    if not fits:
        monkeypatch.setattr(fa, "_Q_SIDE_BYTES", 0)
    b, t, h, d = 1, 512, 2, 64
    rng = np.random.RandomState(35)
    q, k, v, w = (jnp.asarray(rng.normal(0, 1, (b, t, h, d))
                              .astype(np.float32)) for _ in range(4))

    def split(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    def ref(q, k, v):
        out = fa._reference_attention(split(q), split(k), split(v),
                                      1.0 / np.sqrt(d), True)
        return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    _, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_bthd(
        q, k, v, causal=True, block_q=128, block_k=128)
        .reshape(b, t, h, d), q, k, v)
    _, vjp_ref = jax.vjp(ref, q, k, v)
    for a, g, name in zip(vjp(w), vjp_ref(w), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)


# (q heads, kv heads, head width, T, block, window) -> launches fed an
# expanded k / v (a forward and the backward pass's two, or none)
_BAND_CASES = {
    "group_2": ((4, 2, 128, 512, 128, None), 0),
    "group_8_window_under_a_block": ((8, 1, 128, 512, 128, 100), 0),
    "group_2_window_no_multiple_of_the_block": ((4, 2, 128, 512, 128, 200),
                                                0),
    "group_1_window_of_two_blocks": ((2, 2, 128, 512, 128, 256), 0),
    "group_2_T_no_multiple_of_the_window": ((2, 1, 128, 640, 256, 300), 0),
    "blocks_of_256_under_a_window_of_one": ((2, 1, 128, 1024, 256, 256), 0),
    "two_heads_to_a_block_window": ((4, 4, 64, 512, 128, 200), 0),
    "narrow_heads_are_expanded": ((4, 2, 64, 512, 128, 100), 3),
}


@pytest.mark.parametrize("case", list(_BAND_CASES), ids=list(_BAND_CASES))
def test_grouped_kv_heads_and_the_band_match_reference(case):
    """Fewer kv heads than q heads read in place (q head h meets kv head
    h // group; dk and dv summed over a group's q heads inside the dkv
    sweep) and a static window (blocks left of the band skipped, the
    sweeps' inner axes the band's length): forward, dq, dk, dv against
    attention written out with a plain `repeat` and an index compare;
    and what the three stats read."""
    import jax
    import jax.numpy as jnp

    from mxtpu import profiler
    from mxtpu.ops import pallas_attention as fa

    (h, hkv, d, t, block, window), expanded = _BAND_CASES[case]
    rng = np.random.RandomState(t + h)
    q, w = (jnp.asarray(rng.normal(0, 1, (1, t, h, d)).astype(np.float32))
            for _ in range(2))
    k, v = (jnp.asarray(rng.normal(0, 1, (1, t, hkv, d)).astype(np.float32))
            for _ in range(2))

    def ref(q, k, v):
        k, v = (jnp.repeat(a, h // hkv, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None]
        seen = (ki <= qi) if window is None \
            else (ki <= qi) & (qi - ki < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    names = ("flash_kv_expanded", "flash_attention_pallas")
    before = [profiler.get_stat(n) for n in names]
    for n in ("flash_kv_group", "flash_window"):
        profiler.set_stat(n, 0)
    got, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_bthd(
        q, k, v, causal=True, block_q=block, block_k=block,
        window=window).reshape(1, t, h, d), q, k, v)
    grads = vjp(w)
    assert [profiler.get_stat(n) - x for n, x in zip(names, before)] \
        == [expanded, 2]
    assert profiler.get_stat("flash_kv_group") == h // hkv
    assert profiler.get_stat("flash_window") == (window or 0)
    gold, vjp_ref = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gold),
                               rtol=2e-4, atol=2e-5)
    for a, g, name in zip(grads, vjp_ref(w), "qkv"):
        assert a.shape == g.shape       # dk, dv at k's own head count
        np.testing.assert_allclose(np.asarray(a), np.asarray(g),
                                   rtol=2e-3, atol=2e-4,
                                   err_msg="d%s" % name)


def _pallas_calls(jaxpr, out):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(str(e))
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, out)
    return out


# sha256 of the three kernels' `pallas_call` equations as the tree
# before grouped kv heads and windows (commit f5a4f79) traced them, for
# [1, 512, heads, width] in blocks of 256, causal, under jax 0.9.0
_PARENT_KERNELS = {
    (2, 128): ["4d2bf2f11736fa88", "490dc42760557ec3", "19ea362712b96f69"],
    (4, 64): ["a5a1723675d97074", "3354b9b9b46fd719", "1a080e4a4815c1c1"],
}


@pytest.mark.parametrize("heads,width", list(_PARENT_KERNELS))
def test_equal_heads_and_a_window_that_reaches_every_key_trace_the_parent(
        heads, width):
    """With equal head counts, no window or one that reaches every key,
    the forward, dq and dkv kernels trace equation for equation what
    they traced before they knew grouped kv heads or a band: the
    accepted cells' programs hold the kernels they held."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from mxtpu.ops import pallas_attention as fa

    x = jnp.ones((1, 512, heads, width), jnp.float32)

    def traced(window):
        def f(q, k, v):
            return fa.flash_attention_bthd(
                q, k, v, causal=True, block_q=256, block_k=256,
                window=window).sum()

        return _pallas_calls(
            jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(x, x, x).jaxpr,
            [])

    plain = traced(None)
    assert len(plain) == 3
    assert traced(512) == plain and traced(4096) == plain
    assert traced(511) != plain
    if jax.__version__ == "0.9.0":      # the record's own version
        assert [hashlib.sha256(c.encode()).hexdigest()[:16]
                for c in plain] == _PARENT_KERNELS[(heads, width)]
