"""The `glm4_moe_lite` layer kinds of `mxtpu.parallel.transformer`
(latent attention, SiLU-gated feed-forward, a leading dense layer, the
dropless sigmoid top-k expert layer over a held range of the experts
with a shared expert, the multi-token-prediction block) against the
plain float32 reference beside this file, at tiny widths on the CPU.

Tolerances.  Program and reference are both float32 here and differ in
the order of their sums (the flash recurrence against a whole softmax,
grouped products against masked dense ones): agreement is ~1e-6 of a
leaf's largest entry.  `TOL` = 2e-4 leaves two orders of room and is
still fifty times under what bfloat16 arithmetic gives (~1e-2), which
`test_the_tolerance_refuses_bfloat16` shows.
"""
import dataclasses
import importlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import create_mesh

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.join(os.path.dirname(HERE), "benchmark", "onchip")
sys.path[:0] = [HERE, ONCHIP]
import glm_moe_lite_reference as ref  # noqa: E402
from drivers.lm_glm_fused import transformer_config  # noqa: E402

TOL = 2e-4

# the published config's keys, at the tiny sizes of the benchmark
# config's `rehearse` block: 8 experts, top-2, this "chip" holds 2
HF = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=16,
          kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
          v_head_dim=16, rope_theta=1e6, rms_norm_eps=1e-5,
          intermediate_size=96, moe_intermediate_size=32,
          n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
          routed_scaling_factor=1.8, norm_topk_prob=True,
          first_k_dense_replace=1, num_hidden_layers=3,
          num_nextn_predict_layers=1, vocab_size=64, experts_held=2,
          expert_first=2, mtp_loss_weight=0.3)
B, T = 2, 64


def program_config(hf, dtype="float32", remat="none", **over):
    """The `TransformerConfig` the benchmark's driver builds from these
    keys (so the mapping the cell runs is the one tested here)."""
    return dataclasses.replace(
        transformer_config(dict(hf, param_dtype=dtype, remat=remat)), **over)


@pytest.fixture(scope="module")
def mesh():
    return create_mesh({"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1},
                       devices=jax.devices()[:1])


def _weights(cfg, mesh, seed=0, bias=None):
    """(program's params, the same under the reference's shapes)."""
    params = tf.init_params(cfg, mesh, seed)
    if bias is not None:
        for k in list(params):
            if k.endswith("router_bias"):
                params[k] = jnp.broadcast_to(
                    jnp.asarray(bias, params[k].dtype), params[k].shape)
    whole = ("embed", "ln_f", "unembed", "mtp.eh", "mtp.ln_e", "mtp.ln_h",
             "mtp.ln_f")        # the rest are [pp=1, layers, ...] stacks
    flat = {k: np.asarray(v, np.float32)[() if k in whole else 0]
            for k, v in params.items()}
    return params, flat


def _batch(seed=0, vocab=64):
    rng = np.random.RandomState(seed)
    return (rng.randint(0, vocab, (B, T)).astype(np.int32),
            rng.randint(0, vocab, (B, T)).astype(np.int32))


def _program_loss_and_grads(cfg, mesh, params, tokens, labels):
    """One Adam step from a zero state: the first moment is (1 - b1)
    times the gradient the optimizer was given."""
    step, sh = tf.make_train_step(cfg, mesh, lr=1e-3, optimizer="adam",
                                  betas=(0.9, 0.999))
    params = jax.tree_util.tree_map(jnp.copy, params)
    out = step(params, tf.init_opt_state(cfg, mesh),
               jax.device_put(tokens, sh["data"]),
               jax.device_put(labels, sh["data"]))
    grads = {k: np.asarray(v, np.float32) / 0.1
             for k, v in out[1]["m"].items()}
    return float(out[2]), grads, (out[3] if len(out) > 3 else None)


def _worst_gap(grads, want):
    """Largest |g - g_ref| over a leaf's largest |g_ref|, by leaf."""
    worst, where = 0.0, None
    for k, w in want.items():
        w = np.asarray(w, np.float32)
        g = grads[k].reshape(w.shape)
        scale = np.abs(w).max()
        if scale == 0.0:                  # the selection bias: no gradient
            assert np.abs(g).max() == 0.0, k
            continue
        gap = np.abs(g - w).max() / scale
        if gap > worst:
            worst, where = gap, k
    return worst, where


@pytest.mark.parametrize("remat", ["none", "dots"])
def test_program_matches_reference_loss_and_every_gradient(mesh, remat):
    cfg = program_config(HF, remat=remat)
    params, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    loss, grads, stats = _program_loss_and_grads(cfg, mesh, params, tokens,
                                                 labels)
    want_loss, want = jax.value_and_grad(
        lambda p: ref.loss(HF, p, tokens, labels))(
            {k: jnp.asarray(v) for k, v in flat.items()})
    assert abs(loss - float(want_loss)) <= TOL * float(want_loss)
    assert set(want) == set(grads)
    gap, where = _worst_gap(grads, want)
    assert gap <= TOL, (where, gap)
    assert 0 < float(stats["moe_pairs"]) <= float(stats["moe_tokens"]) * 2
    assert float(stats["moe_tokens"]) == 3 * B * T


def test_the_tolerance_refuses_bfloat16(mesh):
    """The same comparison with the program in bfloat16 (weights rounded
    to it on both sides, so only the arithmetic differs) fails by the
    tolerance the float32 program passes."""
    cfg = program_config(HF, dtype="bfloat16")
    params, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    _, grads, _ = _program_loss_and_grads(cfg, mesh, params, tokens, labels)
    want = jax.grad(lambda p: ref.loss(HF, p, tokens, labels))(
        {k: jnp.asarray(v) for k, v in flat.items()})
    assert _worst_gap(grads, want)[0] > 10 * TOL


def _on_mesh(mesh, fn, *args):
    """fn(*args) inside shard_map on the one-device mesh: the layer
    functions name the mesh's axes in their collectives."""
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(P() for _ in args), out_specs=P(),
        check_vma=False))(*args)


@pytest.mark.parametrize("path", ["reference_path", "pallas_interpreted"])
def test_mla_alone_matches_reference(mesh, monkeypatch, path):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET",
                       "1" if path == "pallas_interpreted" else "0")
    cfg = program_config(HF)
    rng = np.random.RandomState(1)
    lw = {k: jnp.asarray(rng.randn(*shape) * (1.0 / (fan or 1)) ** 0.5
                         + (fan is None), jnp.float32)
          for k, (shape, _, fan) in tf._layer_leaves(cfg, "dense").items()}
    x = jnp.asarray(rng.randn(B, 128, HF["hidden_size"]), jnp.float32)

    def run(x, lw):
        return tf._mla(cfg, x, lw, 1, tf._rotary_table(cfg, jnp.arange(128)))

    got = np.asarray(_on_mesh(mesh, run, x, lw))
    want = np.asarray(ref.mla(HF, x, lw))
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()
    from mxtpu import profiler
    if path == "pallas_interpreted":
        assert profiler.stats().get("flash_attention_pallas", 0) > 0


def _route_case(**over):
    hf = dict(HF, **over)
    cfg = program_config(hf)
    rng = np.random.RandomState(2)
    z = jnp.asarray(rng.randn(96, 64), jnp.float32)
    router = jnp.asarray(rng.randn(64, 8) / 8.0, jnp.float32)
    return hf, cfg, z, router


@pytest.mark.parametrize("case", ["bias_moves_selection_not_weights",
                                  "renormalised_over_all_selected",
                                  "scaled", "matches_reference"])
def test_router(case):
    hf, cfg, z, router = _route_case()
    scores = np.asarray(jax.nn.sigmoid(z @ router))
    zero = jnp.zeros(8)
    idx0, w0 = tf._route(cfg, z, router, zero)
    if case == "bias_moves_selection_not_weights":
        bias = jnp.asarray([5.0, 0, 0, 0, 0, 0, 0, -5.0])
        idx, w = tf._route(cfg, z, router, bias)
        idx, w = np.asarray(idx), np.asarray(w)
        assert (idx == 0).any(1).all() and not (idx == 7).any()
        assert not (np.asarray(idx0) == 0).any(1).all()
        # the weights are the UNBIASED scores of what was selected
        picked = np.take_along_axis(scores, idx, 1)
        np.testing.assert_allclose(
            w, 1.8 * picked / picked.sum(1, keepdims=True), rtol=1e-6)
    elif case == "renormalised_over_all_selected":
        # each token's weights sum to the scale whatever range is held,
        # and without the renormalisation they are the bare scores
        np.testing.assert_allclose(np.asarray(w0).sum(1), 1.8, rtol=1e-6)
        _, cfg_raw, _, _ = _route_case(norm_topk_prob=False)
        _, w_raw = tf._route(cfg_raw, z, router, zero)
        np.testing.assert_allclose(
            np.asarray(w_raw),
            1.8 * np.take_along_axis(scores, np.asarray(idx0), 1), rtol=1e-6)
    elif case == "scaled":
        _, cfg1, _, _ = _route_case(routed_scaling_factor=1.0)
        _, w1 = tf._route(cfg1, z, router, zero)
        np.testing.assert_allclose(np.asarray(w0), 1.8 * np.asarray(w1),
                                   rtol=1e-6)
    else:
        bias = jnp.asarray(np.random.RandomState(3).randn(8) * 0.01,
                           jnp.float32)
        idx, w = tf._route(cfg, z, router, bias)
        ridx, rw = ref.route(hf, z, router, bias)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-6)


def _expert_layer_weights(cfg, rng, held):
    leaves = tf._layer_leaves(
        dataclasses.replace(cfg, experts_held=held, expert_first=0), "moe")
    return {k: jnp.asarray(rng.randn(*shape) * (1.0 / fan) ** 0.5,
                           jnp.float32)
            for k, (shape, _, fan) in leaves.items() if fan is not None}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_shares_add_up_to_the_uncut_layer(mesh, side):
    """Four chips' shares of eight experts: the held ranges' routed
    parts, plus the shared expert counted once, are the whole layer as
    the uncut reference computes it."""
    cfg = program_config(HF)
    rng = np.random.RandomState(4)
    whole = _expert_layer_weights(cfg, rng, 8)
    z = jnp.asarray(rng.randn(B * T, 64), jnp.float32)
    want = np.asarray(ref.routed_part(HF, z, whole, 0, 8)
                      + ref.shared_part(z, whole))
    total = np.asarray(ref.shared_part(z, whole))
    pairs = 0.0
    for first in range(0, 8, 2):
        share = dict(whole, **{k: whole[k][first:first + 2]
                               for k in ("we_g", "we_u", "we_d")})
        if side == "reference":
            part = ref.routed_part(HF, z, share, first, 2)
        else:
            c = dataclasses.replace(cfg, expert_first=first)

            def run(z, share):
                idx, w = tf._route(c, z, share["router"],
                                   share["router_bias"])
                return tf._experts_grouped(c, z, idx, w, share)

            part, stats = _on_mesh(mesh, run, z, share)
            pairs += float(stats["moe_pairs"])
        total = total + np.asarray(part)
    assert np.abs(total - want).max() <= TOL * np.abs(want).max()
    if side == "program":           # every pair computed on some share
        assert pairs == B * T * 2


@pytest.mark.parametrize("held,first", [(2, 2), (1, 5), (8, 0)])
def test_no_token_is_dropped_when_every_pair_falls_in_the_held_range(
        mesh, held, first):
    """A router biased to send every token's top_k into the held range
    fills the static row bound exactly: every pair is computed and the
    result is the reference's."""
    hf = dict(HF, experts_held=held, expert_first=first)
    cfg = program_config(hf)
    rng = np.random.RandomState(5)
    lw = _expert_layer_weights(cfg, rng, held)
    bias = np.full(8, -10.0, np.float32)
    bias[first:first + held] = 10.0
    lw["router_bias"] = jnp.asarray(bias)
    z = jnp.asarray(rng.randn(B * T, 64), jnp.float32)

    def run(z, lw):
        idx, w = tf._route(cfg, z, lw["router"], lw["router_bias"])
        return tf._experts_grouped(cfg, z, idx, w, lw)

    got, stats = _on_mesh(mesh, run, z, lw)
    n_pairs = B * T * min(2, held)
    assert float(stats["moe_pairs"]) == n_pairs
    want = np.asarray(ref.routed_part(hf, z, lw, first, held))
    assert np.abs(np.asarray(got) - want).max() <= TOL * np.abs(want).max()


# pairs the two held experts get, of 1024 tokens at top-2 over 32 experts:
# the walk's block is C = 512 rows of the 2048 no routing can overflow
WALKS = {"no_pair_held": (0, 0), "exactly_one_block": (200, 312),
         "one_pair_past_a_block": (200, 313),
         "a_group_across_two_block_edges": (300, 900),
         "every_pair_held": (1024, 1024)}
# the meshes the walk runs on.  A rank of "dp" has 1024 tokens of its
# own, so its own pairs and its own number of blocks, and holds the same
# experts as the other; the ranks of "tp" share the tokens and split the
# experts' hidden width
SPLITS = {"one_device": {}, "dp2": {"dp": 2}, "tp2": {"tp": 2}}


@pytest.mark.parametrize("split", sorted(SPLITS))
@pytest.mark.parametrize("case", sorted(WALKS))
def test_the_walk_takes_the_blocks_the_pairs_need(case, split):
    """The dispatch walks ceil(pairs / C) blocks of the sorted pairs,
    none where no pair is held and rows / C where every pair is: result
    and gradients (tokens, weights, the three expert matrices) are a
    plain loop's over the experts on one device, under `jax.checkpoint`
    inside a `lax.scan` over layers as the LM runs it, and
    `moe_rows_walked` says how many rows each layer walked.  The second
    rank of "dp" routes as the NEXT case does, so the two walk
    different numbers of blocks."""
    n, first, layers = 1024, 2, 2
    cfg = dataclasses.replace(program_config(HF), n_experts=32,
                              expert_first=first)
    rows, C = tf._dispatch_block(cfg, n)
    assert (rows, C) == (2048, 512)
    axes = dict({"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1},
                **SPLITS[split])
    dp, tp = axes["dp"], axes["tp"]
    mesh = create_mesh(axes, devices=jax.devices()[:dp * tp])
    names = sorted(WALKS)
    counts = [WALKS[names[(names.index(case) + r) % len(names)]]
              for r in range(dp)]
    rng = np.random.RandomState(10)
    lw = {k: jnp.stack([v, v[::-1]]) for k, v in
          _expert_layer_weights(cfg, rng, 2).items() if k.startswith("we_")}
    # slot s of a token names held expert s for the first counts[r][s]
    # tokens of a shuffle of its own, and an expert held elsewhere for
    # the rest
    idx = np.empty((layers, dp, n, 2), np.int32)
    for layer, r in np.ndindex(layers, dp):
        for slot, count in enumerate(counts[r]):
            idx[layer, r, rng.permutation(n), slot] = np.where(
                np.arange(n) < count, first + slot, 8 + slot)
    idx = jnp.asarray(idx.reshape(layers, dp * n, 2))
    w = jnp.asarray(rng.rand(layers, dp * n, 2) + 0.5, jnp.float32)
    z = jnp.asarray(rng.randn(dp * n, 64), jnp.float32)
    aim = jnp.asarray(rng.randn(dp * n, 64), jnp.float32)

    def plain(cfg, x, idx, w, lw):
        out = 0.0
        for e in range(2):
            y = (jax.nn.silu(x @ lw["we_g"][e]) * (x @ lw["we_u"][e])) \
                @ lw["we_d"][e]
            share = jnp.where(idx == first + e, w, 0.0).sum(1)
            out = out + share[:, None] * y
        return out, {"moe_rows_walked": jnp.zeros(())}

    def run(experts, own, whole, z, aim, idx, w, lw):
        """`own` marks a rank's inputs its own as the LM's activations
        are (varying over every axis); `whole` is one copy of what the
        ranks that share tokens all hold, summed over the named axes
        besides."""
        def loss(z, w, lw):
            @jax.checkpoint
            def layer(x, per_layer):
                idx, w, lw = per_layer
                f, stats = experts(cfg, x, idx, w, lw)
                return x + f, (f, stats["moe_rows_walked"])

            x, (f, walked) = jax.lax.scan(layer, own(z),
                                          (own(idx), own(w), lw))
            return whole((x * own(aim)).sum(), "dp"), (
                whole(f), whole(walked)[None])

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            z, w, lw)

    def whole(v, *summed):
        return jax.lax.psum(v, ("pp", "tp", "sp", "ep") + summed) / tp

    rank, pair = P("dp"), P(None, "dp")
    lw_spec = {"we_g": P(None, None, None, "tp"),
               "we_u": P(None, None, None, "tp"), "we_d": P(None, None, "tp")}
    (_, (f, walked)), got = jax.jit(jax.shard_map(
        lambda *a: run(tf._experts_grouped, tf._pvary_all, whole, *a),
        mesh=mesh, in_specs=(rank, rank, pair, pair, lw_spec),
        out_specs=((P(), (pair, rank)), (rank, pair, lw_spec))))(
            z, aim, idx, w, lw)
    (_, (f_want, _)), want = jax.jit(
        lambda *a: run(plain, lambda v: v, lambda v, *_: v, *a))(
            z, aim, idx, w, lw)
    np.testing.assert_array_equal(
        np.asarray(walked),
        [[-(-sum(c) // C) * C] * layers for c in counts])
    for a, b in zip(jax.tree_util.tree_leaves((f, got)),
                    jax.tree_util.tree_leaves((f_want, want))):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= TOL * np.abs(b).max()
    for r, c in enumerate(counts):
        if not sum(c):      # nothing but the residual path's gradient
            own = slice(r * n, (r + 1) * n)
            assert not np.asarray(f)[:, own].any()
            assert not np.asarray(got[1])[:, own].any()
    if not any(map(sum, counts)):
        assert not any(np.asarray(a).any()
                       for a in jax.tree_util.tree_leaves(got[2]))


@pytest.mark.parametrize("optimizer", ["adam", "sgd"])
def test_fused_k_steps_equal_k_sequential_steps(mesh, optimizer):
    cfg = program_config(HF)
    K = 2
    rng = np.random.RandomState(6)
    toks = rng.randint(0, 64, (K, B, T)).astype(np.int32)
    labs = rng.randint(0, 64, (K, B, T)).astype(np.int32)
    adam = optimizer == "adam"

    def state():
        p = tf.init_params(cfg, mesh, 7)
        return (p, tf.init_opt_state(cfg, mesh)) if adam else (p,)

    fused, sh = tf.make_fused_train_steps(cfg, mesh, K, lr=1e-2,
                                          optimizer=optimizer)
    n = len(state())
    out = fused(*state(), jax.device_put(toks, sh["data"]),
                jax.device_put(labs, sh["data"]))
    one, sh1 = tf.make_train_step(cfg, mesh, lr=1e-2, optimizer=optimizer)
    st, losses, pairs = state(), [], []
    for i in range(K):
        o = one(*st, jax.device_put(toks[i], sh1["data"]),
                jax.device_put(labs[i], sh1["data"]))
        st = o[:n]
        losses.append(float(o[n]))
        pairs.append(float(o[n + 1]["moe_pairs"]))
    np.testing.assert_allclose(np.asarray(out[n]), losses, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[n + 1]["moe_pairs"]), pairs)
    for k in st[0]:
        np.testing.assert_allclose(np.asarray(out[0][k]),
                                   np.asarray(st[0][k]), rtol=1e-5,
                                   atol=1e-7, err_msg=k)


def test_the_two_references_agree(mesh):
    """`tests/glm_moe_lite_reference.py` and the benchmark's copy
    (`benchmark/onchip/reference/glm_4_7_flash.py`, computed in blocks)
    give the same loss and the same gradients."""
    bench = importlib.import_module("reference.glm_4_7_flash")
    p = {k: v.astype(jnp.float32)
         for k, v in bench.init_params(HF, 11).items()}
    assert {k: v.shape for k, v in p.items()} == \
        {n: tuple(s) for n, s, _ in bench.layout(HF)}
    tokens, labels = _batch(8)
    l0, g0 = jax.value_and_grad(lambda q: ref.loss(HF, q, tokens, labels))(p)
    l1, g1 = bench._grad_of_mean(HF, p, jnp.asarray(tokens),
                                 jnp.asarray(labels), 1, "f32")
    assert abs(float(l0) - float(l1)) <= 1e-6 * float(l0)
    gap, where = _worst_gap({k: np.asarray(v) for k, v in g1.items()}, g0)
    assert gap <= 2e-5, (where, gap)


@pytest.mark.parametrize("bad", [
    dict(attention="mla"), dict(ffn="relu"), dict(n_dense_layers=1),
    dict(n_experts=4, top_k=2), dict(n_experts=4, experts_held=2),
    dict(n_experts=4, n_shared_experts=1), dict(n_experts=4, top_k=5),
    dict(mtp_depth=2), dict(n_experts=4, ffn="swiglu", expert_first=3,
                            experts_held=2)])
def test_config_refuses_what_is_not_built(bad):
    from mxtpu.base import MXNetError

    with pytest.raises(MXNetError):
        tf.TransformerConfig(**bad)


def test_rows_the_grouped_product_does_not_write_are_never_read(
        mesh, monkeypatch):
    """On the TPU `jax.lax.ragged_dot` leaves the rows past its last
    group unwritten, forward and backward (the CPU's gives zeros there).
    With a grouped product that fills those rows with 1e30, in its
    result and in the cotangent of its left operand, the expert layer's
    result and gradients are what they are without (PERF.md, PR 30:
    unmasked, such rows were scattered into the tokens' gradients)."""
    cfg = program_config(HF)
    rng = np.random.RandomState(9)
    lw = _expert_layer_weights(cfg, rng, 2)
    z = jnp.asarray(rng.randn(B * T, 64), jnp.float32)
    real = jax.lax.ragged_dot

    def poison(x, sizes):
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.where(rows < sizes.sum(), x, 1e30)

    @jax.custom_vjp
    def dirty(a, b, sizes):
        return poison(real(a, b, sizes), sizes)

    def fwd(a, b, sizes):
        return dirty(a, b, sizes), (a, b, sizes)

    def bwd(res, g):
        a, b, sizes = res
        da, db = jax.vjp(lambda a, b: real(a, b, sizes), a, b)[1](
            jnp.where(jnp.arange(g.shape[0])[:, None] < sizes.sum(), g, 0))
        return poison(da, sizes), db, None

    dirty.defvjp(fwd, bwd)

    def run(z, lw):
        def f(z, lw):
            idx, w = tf._route(cfg, z, lw["router"], lw["router_bias"])
            return (tf._experts_grouped(cfg, z, idx, w, lw)[0] ** 2).sum()
        return jax.value_and_grad(f, argnums=(0, 1))(z, lw)

    want = _on_mesh(mesh, run, z, lw)
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        lambda a, b, sizes, preferred_element_type=None: dirty(a, b, sizes))
    got = _on_mesh(mesh, run, z, lw)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
