"""Solar Open 2's layer kinds in `mxtpu.parallel.transformer` (a stack of
NoPE gated GQA as the first layer of each period and Kimi Delta
Attention in the others, with an unbounded softplus decay, rank-d gates
and write strengths in (0, 2); sigmoid top-k routing over the held
experts with a shared expert) against plain float32 references, at tiny
widths on the CPU.

Tolerances.  Program and reference are both float32 here and differ in
the order of their sums (the chunked form's products and triangular
solve against a recurrence over tokens; the flash recurrence against a
whole softmax; grouped products against masked dense ones).  `TOL` =
2e-4 of a leaf's largest entry is what the glm and ling tests hold; it
is refused both by the program in bfloat16 and by the reference with
the negative eigenvalues left out (`pos_beta`), which
`test_the_tolerance_refuses` shows.  The mixers alone against numpy
float64, a query or a token at a time, are held to `MIXER_TOL` = 2e-5:
the chunked core reads 1e-6 to 8e-6 of the recurrence's largest entry
at per-step log-decays from -1e-3 to -1e3 (tests/test_ling_hybrid.py).
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.base import MXNetError
from mxtpu.parallel import transformer as tf

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.join(os.path.dirname(HERE), "benchmark", "onchip")
sys.path[:0] = [HERE, ONCHIP]
import solar_open2_reference as plain  # noqa: E402
from drivers.lm_solar_fused import transformer_config  # noqa: E402
from ling_hybrid_reference import expert_layer_uncut  # noqa: E402
from trinity_mini_reference import route_top_k  # noqa: E402
# the one-device mesh, a function run inside shard_map on it, the worst
# leaf's gradient gap, and the program's loss and first gradient: as the
# glm and ling tests have them
from test_glm_moe_lite import _on_mesh, _worst_gap, mesh  # noqa: E402,F401
from test_ling_hybrid import (_batch, _program_loss_and_grads,  # noqa: E402
                              _weights, B, T)
from reference import solar_open2 as ref  # noqa: E402

TOL = 2e-4
MIXER_TOL = 2e-5

# the published config's keys at tiny sizes: a period of 4 (GQA where i %
# 4 == 0), published layers 2..5 held (KDA, KDA, GQA, KDA: the KDA stack
# in two segments), 4 q heads on 2 kv heads of 16, 4 KDA heads of 16,
# chunks of 8, 8 experts top-2, this "chip" holds 2
HF = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, linear_attn_config=dict(
              short_conv_kernel_size=4, head_dim=16, num_heads=4,
              num_kv_heads=None),
          intermediate_size=96, moe_intermediate_size=32, rms_norm_eps=1e-5,
          rope_theta=10000, first_k_dense_replace=0, use_rope=False,
          gqa_interval=3, gqa_layers=[0, 4, 8], use_gqa_gate=True,
          kda_use_full_proj=False, kda_allow_neg_eigval=True,
          n_routed_experts=8, n_shared_experts=1, norm_topk_prob=True,
          routed_scaling_factor=1, num_experts_per_tok=2,
          score_function="sigmoid", experts_held=2, expert_first=2,
          num_hidden_layers=4, layers_held=[2, 3, 4, 5], kda_chunk=8,
          vocab_size=64, published=dict(num_hidden_layers=12))


def program_config(hf, dtype="float32", remat="none", **over):
    """The `TransformerConfig` the benchmark's driver builds from these
    keys (so the mapping the cell runs is the one tested here)."""
    return dataclasses.replace(
        transformer_config(dict(hf, param_dtype=dtype, remat=remat)), **over)


def _leaves(cfg, kind, seed=1, gate_shift=0.0):
    """Random leaves of one layer of `kind`: normal with variance
    1/fan_in, the norm scales about 1, `A_log` of U(1, 16), `dt_bias`
    the family's slow decays moved by `gate_shift`."""
    rng = np.random.RandomState(seed)
    out = {}
    for k, (shape, _, fan) in tf._layer_leaves(cfg, kind).items():
        if fan == "A_log":
            a = np.log(rng.uniform(1.0, 16.0, shape))
        elif fan == "dt_bias":
            step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            a = step + np.log(-np.expm1(-step)) + gate_shift
        else:
            a = rng.randn(*shape) * (1.0 / (fan or 25.0)) ** 0.5 \
                + (fan is None)
        out[k] = jnp.asarray(a, jnp.float32)
    return out


# ---------------------------------------------------------------------------
# the two mixers alone


@pytest.mark.parametrize("gate_shift", [0.0, 8.0],
                         ids=["slow_decays", "hundreds_of_nats_a_chunk"])
def test_kda_mixer_matches_a_token_at_a_time(mesh, gate_shift):
    """`_kda` with the softplus decay, the rank-16 gates and beta in (0,
    2) against numpy, a token at a time; with the gate's bias moved up
    by 8, one step's log-decay reaches -exp(A_log) * 8 (up to -128) and
    a chunk of 8 hundreds of nats: no floor, no clamp, and finite."""
    cfg = program_config(HF)
    lw = _leaves(cfg, "kda+moe", gate_shift=gate_shift)
    x = jnp.asarray(np.random.RandomState(2).randn(1, 40, 64), jnp.float32)
    y, stats = _on_mesh(mesh, lambda x, lw: tf._kda(cfg, x, lw), x, lw)
    want, g, beta = plain.kda_mixer(x[0], lw, 4, 16, 1e-5)
    y = np.asarray(y)[0]
    assert np.isfinite(y).all()
    assert np.abs(y - want).max() <= MIXER_TOL * np.abs(want).max()
    assert float(stats["kda_neg_beta"]) == (beta > 1.0).sum()
    chunk_sums = -g.reshape(5, 8, 4, 16).sum(1)     # 40 tokens, chunks of 8
    assert float(stats["kda_decay_span_max"]) == pytest.approx(
        chunk_sums.max(), rel=1e-5)
    if gate_shift:
        assert chunk_sums.max() > 88.7      # past float32's exp() cliff


def test_gqa_mixer_has_no_positions_and_matches_a_query_at_a_time(mesh):
    """`_gqa` as `_layer_fn` calls it in this stack (no rotary table: the
    model has no positions; every layer attends over the whole prefix)
    against numpy a query at a time; and, with no positions, the last
    query's output is a function of the SET of keys: reversing the
    tokens before it moves nothing."""
    cfg = program_config(HF)
    assert not tf._rotary(cfg) and "pos" not in tf.param_shapes(cfg, 1)
    lw = _leaves(cfg, "moe")
    x = jnp.asarray(np.random.RandomState(3).randn(1, 40, 64), jnp.float32)

    def run(x, lw):
        return tf._gqa(cfg, x, lw, 1, None, None)

    got = np.asarray(_on_mesh(mesh, run, x, lw))[0]
    want = plain.gqa_block(x[0], lw, 4, 2, 16)
    assert np.abs(got - want).max() <= MIXER_TOL * np.abs(want).max()
    reversed_keys = jnp.concatenate([x[:, -2::-1], x[:, -1:]], axis=1)
    last = np.asarray(_on_mesh(mesh, run, reversed_keys, lw))[0, -1]
    assert np.abs(last - got[-1]).max() <= 1e-5 * np.abs(got[-1]).max()


# ---------------------------------------------------------------------------
# the whole stack against the benchmark's reference


@pytest.mark.parametrize("remat,path", [
    ("none", "reference_path"), ("dots", "reference_path"),
    ("dots", "pallas_interpreted")])
def test_program_matches_reference_loss_and_every_gradient(
        mesh, monkeypatch, remat, path):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET",
                       "1" if path == "pallas_interpreted" else "0")
    cfg = program_config(HF, remat=remat)
    params, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    loss, grads, stats = _program_loss_and_grads(cfg, mesh, params, tokens,
                                                 labels)
    (want_loss, pairs), want = ref._grad_and_pairs(
        HF, {k: jnp.asarray(v) for k, v in flat.items()},
        jnp.asarray(tokens), jnp.asarray(labels), "f32")
    assert abs(loss - float(want_loss)) <= TOL * float(want_loss)
    assert set(want) == set(grads)
    gap, where = _worst_gap(grads, want)
    assert gap <= TOL, (where, gap)
    assert float(stats["moe_pairs"]) == float(pairs)
    assert float(stats["moe_tokens"]) == 4 * B * T
    assert float(stats["kda_tokens"]) == 3 * B * T
    assert float(stats["kda_chunks"]) == 3 * B * -(-T // 8)
    assert 0 < float(stats["kda_neg_beta"]) < 3 * B * T * 4
    assert float(stats["kda_decay_span_max"]) > 0
    if path == "pallas_interpreted":
        from mxtpu import profiler
        assert profiler.stats().get("flash_attention_pallas", 0) > 0


@pytest.mark.parametrize("control", ["bfloat16", "pos_beta"])
def test_the_tolerance_refuses(mesh, control):
    """The comparison above fails by its tolerance with the program in
    bfloat16 (weights rounded to it on both sides, so only the
    arithmetic differs), and with the reference's negative eigenvalues
    left out (beta = sigmoid, in (0, 1)) against the float32 program."""
    cfg = program_config(HF, dtype="bfloat16" if control == "bfloat16"
                         else "float32")
    params, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    _, grads, _ = _program_loss_and_grads(cfg, mesh, params, tokens, labels)
    mode = "pos_beta" if control == "pos_beta" else "f32"
    want = jax.grad(lambda p: ref.loss_fn(HF, p, jnp.asarray(tokens),
                                          jnp.asarray(labels), mode))(
        {k: jnp.asarray(v) for k, v in flat.items()})
    assert _worst_gap(grads, want)[0] > 10 * TOL


def test_fused_k_steps_return_every_counter_per_step(mesh):
    cfg = program_config(HF)
    params, _ = _weights(cfg, mesh)
    step, sh = tf.make_fused_train_steps(cfg, mesh, 2, lr=1e-3)
    toks = jax.device_put(np.stack([_batch(s)[0] for s in (1, 2)]),
                          sh["data"])
    out = step(params, tf.init_opt_state(cfg, mesh), toks, toks)
    assert set(out[3]) == set(tf.MOE_STATS + tf.KDA_STATS
                              + tf.KDA_NEG_STATS)
    assert all(v.shape == (2,) for v in out[3].values())
    added = tf.publish_moe_stats(out[3])
    assert added["kda_tokens"] == 2 * 3 * B * T
    assert added["kda_neg_beta"] == float(
        np.asarray(out[3]["kda_neg_beta"]).sum())


# ---------------------------------------------------------------------------
# the chip's share


def _halves(lw, kind, H, Hkv, d):
    """The two halves of a mixer's leaves by head: columns of the head-
    wise products and per-head vectors, rows of the out product; what
    every head shares (the low-rank gates' down products, the norms'
    scales) whole."""
    def part(a, heads, width, half):
        n = heads // 2 * width
        return a[..., half * n:(half + 1) * n]

    by_head = {"gqa": {"wq": (H, d), "w_gate": (H, d), "wk": (Hkv, d),
                       "wv": (Hkv, d)},
               "kda": dict({k: (H, d) for k in (
                   "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_b",
                   "g_b", "dt_bias")}, A_log=(H, 1), wb=(H, 1))}[kind]
    return [dict(lw, wo=part(lw["wo"].T, H, d, half).T,
                 **{k: part(lw[k], *hw, half) for k, hw in by_head.items()})
            for half in range(2)]


def test_the_shares_add_up_to_the_uncut_layer(mesh):
    """Guide, section 4: over BOTH halves of the heads the mixers' parts
    (partial sums of a row-split out product) add up to the uncut mixer,
    and over ALL 40 held ranges (40 chips' shares of 320 experts, top-8,
    8 a chip) the routed parts, with the shared expert counted once, add
    up to the expert layer with every expert present; every pair is
    computed on exactly one share."""
    x = jnp.asarray(np.random.RandomState(5).randn(1, 24, 64), jnp.float32)
    for kind, mixer in (("kda+moe", tf._kda), ("moe", tf._gqa)):
        whole = program_config(HF)
        half = dataclasses.replace(whole, n_heads=2, n_kv_heads=1)
        lw = _leaves(whole, kind, seed=6)
        if kind == "kda+moe":
            want = plain.kda_mixer(x[0], lw, 4, 16, 1e-5)[0]

            def run(x, lw):
                return mixer(half, x, lw)[0]
        else:
            want = plain.gqa_block(x[0], lw, 4, 2, 16)

            def run(x, lw):
                return mixer(half, x, lw, 1, None, None)
        total = sum(np.asarray(_on_mesh(mesh, run, x, share), np.float64)[0]
                    for share in _halves(lw, "kda" if mixer is tf._kda
                                         else "gqa", 4, 2, 16))
        assert np.abs(total - want).max() <= MIXER_TOL * np.abs(want).max()

    hf = dict(HF, n_routed_experts=320, num_experts_per_tok=8,
              experts_held=8, expert_first=0)
    cfg = program_config(hf)
    rng = np.random.RandomState(2)
    z = jnp.asarray(rng.randn(48, 64), jnp.float32)
    router = jnp.asarray(rng.randn(64, 320) / 8.0, jnp.float32)
    bias = jnp.asarray(rng.randn(320) * 0.05, jnp.float32)
    leaves = tf._layer_leaves(dataclasses.replace(cfg, experts_held=320),
                              "moe")
    whole = {k: jnp.asarray(rng.randn(*leaves[k][0])
                            * (1.0 / leaves[k][2]) ** 0.5, jnp.float32)
             for k in ("we_g", "we_u", "we_d", "ws_g", "ws_u", "ws_d")}
    whole["router"], whole["router_bias"] = router, bias
    ids, w = route_top_k(np.asarray(jax.nn.sigmoid(z @ router)), bias, 8, 1.0)
    want = expert_layer_uncut(z, whole, ids, w)

    def shared(z, lw):
        return tf._gated_ffn(z, lw["ws_g"], lw["ws_u"], lw["ws_d"])

    total = np.asarray(_on_mesh(mesh, shared, z, whole), np.float64)
    pairs = 0.0
    for first in range(0, 320, 8):
        share = dict(whole, **{k: whole[k][first:first + 8]
                               for k in ("we_g", "we_u", "we_d")})
        c = dataclasses.replace(cfg, expert_first=first)

        def run(z, share):
            idx, w = tf._route(c, z, share["router"], share["router_bias"])
            return tf._experts_grouped(c, z, idx, w, share)

        part, stats = _on_mesh(mesh, run, z, share)
        total = total + np.asarray(part, np.float64)
        pairs += float(stats["moe_pairs"])
    assert np.abs(total - want).max() <= TOL * np.abs(want).max()
    assert pairs == z.shape[0] * 8


# ---------------------------------------------------------------------------
# layouts and what is refused


def test_the_published_48_layers_build_in_their_order():
    """`param_shapes` of the uncut depth (shapes only): 12 GQA layers at
    i % 4 == 0 and 36 KDA layers, in the published order (one GQA layer,
    then three KDA ones, twelve times), no leading dense layer and no
    position table; the reference lays the same leaves out under the
    same names."""
    hf = dict(HF, num_hidden_layers=48, layers_held=list(range(48)),
              gqa_layers=list(range(0, 48, 4)),
              published=dict(num_hidden_layers=48))
    cfg = program_config(hf)
    segs = tf._segments(cfg)
    order = [kind for _, kind, _, n in segs for _ in range(n)]
    assert order == ["moe", "kda+moe", "kda+moe", "kda+moe"] * 12
    shapes = tf.param_shapes(cfg, 1)
    assert shapes["wq"] == (1, 12, 64, 64) and shapes["wk"][:2] == (1, 12)
    assert shapes["kda.f_a"] == (1, 36, 64, 16)
    assert shapes["kda.f_b"] == (1, 36, 16, 64)
    assert "kda.wa" not in shapes and "kda.w_gate" not in shapes
    assert "pos" not in shapes and "dense.wq" not in shapes
    assert [first for p, _, first, _ in segs if p == ""] == list(range(12))
    assert set(ref.stacked_leaves(hf)) | {"embed", "ln_f", "unembed"} \
        == set(shapes)
    assert {k: (1,) + tuple(s) if k in ref.stacked_leaves(hf) else tuple(s)
            for k, s, _ in ref.layout(hf)} == shapes


@pytest.mark.parametrize("bad", [
    dict(kda_chunk=12),                 # the halves need a power of two
    dict(kda_gate="sigmoid"), dict(kda_gate_rank=-1),
    dict(window=16),                    # a window beside a kda_period
    dict(kda_period=0),                 # the KDA fields without a period
    dict(kda_period=1)])
def test_config_refuses_what_is_not_built(bad):
    with pytest.raises(MXNetError):
        program_config(HF, **bad)


def test_driver_refuses_a_published_layout_it_does_not_place():
    with pytest.raises(ValueError):
        program_config(dict(HF, gqa_layers=[0, 4, 9]))
