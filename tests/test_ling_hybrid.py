"""Ling-3.0-flash's layer kinds in `mxtpu.parallel.transformer` (a stack
of two mixer kinds; Kimi Delta Attention in its chunked form; latent
attention with q from one matrix, q.k wider than v, per-head norms and a
head gate; group-limited routing) against plain float32 references, at
tiny widths on the CPU.

Tolerances.  Program and reference are both float32 here and differ in
the order of their sums (the chunked form's products and triangular
solve against a recurrence over tokens; the flash recurrence against a
whole softmax; grouped products against masked dense ones).  `TOL` =
2e-4 of a leaf's largest entry is what the glm tests hold and is fifty
times under what bfloat16 arithmetic gives, which
`test_the_tolerance_refuses_bfloat16` shows.  The chunked core alone is
held to `CORE_TOL` = 2e-5: float32 round-off through a 64-row solve and
`exp` of up to +-40 nats (which carries its argument's rounding 40-fold)
reads 1e-6 to 4e-6 here, every decay at the floor included.  (Based at
a span's start, where a factor reaches e^-80, the floor case read 3e-4
on k's gradient: products with a cotangent flushed to nought.)
"""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import create_mesh

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.join(os.path.dirname(HERE), "benchmark", "onchip")
sys.path[:0] = [HERE, ONCHIP]
import ling_hybrid_reference as plain  # noqa: E402
from drivers.lm_ling_fused import transformer_config  # noqa: E402
# the one-device mesh, a function run inside shard_map on it, and the
# worst leaf's gradient gap: as the glm tests have them
from test_glm_moe_lite import _on_mesh, _worst_gap, mesh  # noqa: E402,F401
from reference import ling_3_0_flash as ref  # noqa: E402

TOL = 2e-4
CORE_TOL = 2e-5

# the published config's keys at tiny sizes: a period of 3 (published
# layers 0, 3, 4, 5: KDA + dense, KDA + moe twice, MLA + moe), 8 experts
# in 2 groups, top-2 inside the best group, this "chip" holds 2
HF = dict(hidden_size=64, num_attention_heads=4, head_dim=16,
          q_lora_rank=None, kv_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8, rope_theta=6e6,
          rms_norm_eps=1e-6, use_qk_norm=True, intermediate_size=96,
          moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
          num_experts=8, num_experts_per_tok=2, n_group=2, topk_group=1,
          routed_scaling_factor=2.5, norm_topk_prob=True,
          score_function="sigmoid", moe_router_enable_expert_bias=True,
          first_k_dense_replace=1, num_hidden_layers=4, layer_group_size=3,
          layers_held=[0, 3, 4, 5], short_conv_kernel_size=4,
          kda_lower_bound=-5, kda_chunk=8, kda_rebase=4, vocab_size=64,
          experts_held=2, expert_first=2)
B, T = 2, 44        # T is no multiple of the chunk or of its re-basing


def program_config(hf, dtype="float32", remat="none", **over):
    """The `TransformerConfig` the benchmark's driver builds from these
    keys (so the mapping the cell runs is the one tested here)."""
    return dataclasses.replace(
        transformer_config(dict(hf, param_dtype=dtype, remat=remat)), **over)


def _weights(cfg, mesh, seed=0):
    """(program's params, the same under the reference's shapes)."""
    params = tf.init_params(cfg, mesh, seed)
    whole = ("embed", "ln_f", "unembed")    # the rest: [pp=1, layers, ...]
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
    return float(out[2]), grads, out[3]


# ---------------------------------------------------------------------------
# the chunked core against the recurrence


def _core_inputs(t, d, g_kind, seed=7):
    """q, k, v, g, beta.  `g_kind` a number: the softplus gate's
    unbounded decays about that log-decay a step (each entry the number
    times one of U(0.5, 1.5)), with write strengths in (0, 2)."""
    rng = np.random.RandomState(seed)
    q, k, v = (rng.randn(2, t, 3, d).astype(np.float32) for _ in range(3))
    if g_kind == "aligned_slow":        # 0.9 of each key one direction
        k = 0.9 * k[:, :1] / np.linalg.norm(k[:, :1], axis=-1,
                                            keepdims=True) \
            + 0.1 * k / d ** 0.5
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    if not isinstance(g_kind, str):     # no floor: any decay at all
        g = g_kind * rng.uniform(0.5, 1.5, (2, t, 3, d))
        beta = 2.0 / (1.0 + np.exp(-rng.randn(2, t, 3)))
        return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))
    if g_kind == "at_the_floor":        # every step's log-decay -5
        g = np.full((2, t, 3, d), -5.0, np.float32)
    elif g_kind == "aligned_slow":      # log-decays in (-0.01, 0)
        g = rng.uniform(-0.01, 0.0, (2, t, 3, d))
    else:
        g = -5.0 / (1.0 + np.exp(-3.0 * rng.randn(2, t, 3, d)))
    beta = 1.0 / (1.0 + np.exp(-rng.randn(2, t, 3)))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("t,chunk,rebase,g_kind,against", [
    (64, 16, 4, "mixed", "by_matrices"),
    (37, 16, 4, "mixed", "by_matrices"),        # no multiple of 16 or of 4
    (70, 64, 16, "at_the_floor", "by_matrices"),    # exp(80) in a span
    (150, 64, 16, "mixed", "by_matrices"),
    (37, 16, 4, "mixed", "benchmark_reference"),
    (150, 64, 16, "aligned_slow", "by_matrices"),   # a series would fail
    # the softplus gate, no floor: scored by halves (rebase None)
    (150, 64, None, -1e-3, "by_matrices"),
    (150, 64, None, -5.0, "by_matrices"),
    (150, 64, None, -50.0, "by_matrices"),
    (150, 64, None, -1e3, "by_matrices"),
    (37, 16, None, -5.0, "benchmark_reference"),
], ids=["whole_chunks", "ragged_length", "decays_at_the_floor",
        "cell_chunking", "benchmark_reference", "aligned_slow",
        "softplus_step_1e-3", "softplus_step_5", "softplus_step_50",
        "softplus_step_1e3", "softplus_benchmark_reference"])
def test_chunked_kda_matches_the_recurrence(mesh, t, chunk, rebase, g_kind,
                                            against):
    """Forward and every input's gradient of `_kda_chunked` against the
    recurrence run one token at a time: re-based every `rebase` steps
    under the floor gate, or with `rebase` None scored by halves under
    the softplus gate's decays of any size (a chunk of 64 at -1e3 a step
    sums to -64 000 nats), finite with no NaN in any gradient."""
    d = 8
    args = _core_inputs(t, d, g_kind)
    recurrence = plain.delta_rule_by_matrices \
        if against == "by_matrices" else ref.delta_rule
    cot = jnp.asarray(np.random.RandomState(8).randn(2, t, 3, d),
                      jnp.float32)

    def chunked(*a):
        o, span = tf._kda_chunked(*a, chunk, rebase, jnp.float32)
        return (o * cot).sum(), (o, span)

    def stepwise(*a):
        o = recurrence(*a)
        return (o * cot).sum(), o

    (_, (o, span)), grads = _on_mesh(
        mesh, jax.value_and_grad(chunked, argnums=(0, 1, 2, 3, 4),
                                 has_aux=True), *args)
    (_, want_o), want = jax.value_and_grad(
        stepwise, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    assert np.isfinite(np.asarray(o)).all()
    scale = np.abs(np.asarray(want_o)).max()
    assert np.abs(np.asarray(o) - np.asarray(want_o)).max() \
        <= CORE_TOL * scale
    # a gradient is held to its own largest entry, or to a hundredth of
    # the largest of the five where it is smaller than that (at the
    # floor g's gradient is 1e-3 of q's: sums of O(1) terms that cancel)
    largest = max(np.abs(np.asarray(w)).max() for w in want)
    for name, got, w in zip("q k v g beta".split(), grads, want):
        got, w = np.asarray(got), np.asarray(w)
        assert np.isfinite(got).all(), name
        assert np.abs(got - w).max() <= CORE_TOL * max(
            np.abs(w).max(), 1e-2 * largest), name
    if rebase is None:      # the watermark: the largest chunk's sum
        g = np.pad(np.asarray(args[3]), ((0, 0), (0, -t % chunk), (0, 0),
                                         (0, 0)))
        assert float(span) == pytest.approx(
            -g.reshape(2, -1, chunk, 3, d).sum(2).min(), rel=1e-5)
        return
    # the watermark: the largest re-based span's summed |log-decay|
    assert 0 < float(span) <= rebase * 5.0 * (1 + 1e-6)
    if g_kind == "at_the_floor":
        assert float(span) == pytest.approx(rebase * 5.0)


def _chunk_system(c, case, seed=3, n=3, d=32):
    """(A, rhs) as `_kda_chunked` builds them for one chunk of c rows:
    beta 0.99, unit keys, per-channel decays.  `case`: independent keys
    under fast decays; keys half or 0.9 along one direction under slow
    ones; one key repeated with no decay; keys 0.9 along one direction
    under slow decays with beta 1.99 (negative eigenvalues: A's entries
    up to twice as large)."""
    rng = np.random.RandomState(seed)
    k = rng.randn(n, c, d)
    share, low = {"independent": (0.0, -5.0), "half_aligned": (0.5, -0.05),
                  "aligned_slow": (0.9, -0.01),
                  "identical_no_decay": (1.0, 0.0),
                  "aligned_beta_near_2": (0.9, -0.01)}[case]
    beta = 1.99 if case == "aligned_beta_near_2" else 0.99
    u = rng.randn(n, 1, d)
    k = share * u / np.linalg.norm(u, axis=-1, keepdims=True) \
        + (1.0 - share) * k / d ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    G = np.cumsum(rng.uniform(low, 0.0, (n, c, d)), 1)
    A = np.tril(beta * np.einsum("nrd,nid->nri", k * np.exp(G),
                                 k * np.exp(-G)), -1)
    v = rng.randn(n, c, d)
    rhs = beta * np.concatenate([k * np.exp(G), v], -1)
    return A, rhs


@pytest.mark.parametrize("case", ["independent", "half_aligned",
                                  "aligned_slow", "identical_no_decay",
                                  "aligned_beta_near_2"])
@pytest.mark.parametrize("c", [16, 64, 33])    # 33: padded to 4 x 9 rows
def test_unit_lower_solve_matches_the_triangular_solve(c, case):
    """`_unit_lower_solve` (the blocked inverse by products) against
    `solve_triangular` and float64 `numpy.linalg.solve`, and both its
    gradients against jax's own VJP of `solve_triangular`, on systems
    whose keys align under slow decay (where a series in A cancels terms
    of up to C(63, 31) in float32)."""
    from mxtpu import profiler

    A64, rhs64 = _chunk_system(c, case)
    want = np.linalg.solve(np.eye(c) + A64, rhs64)
    A, rhs = (jnp.asarray(a, jnp.float32) for a in (A64, rhs64))
    cot = jnp.asarray(np.random.RandomState(5).randn(*rhs.shape),
                      jnp.float32)

    def triangular(A, rhs):
        return jax.scipy.linalg.solve_triangular(
            A + jnp.eye(c, dtype=A.dtype), rhs, lower=True,
            unit_diagonal=True)

    def value_and_grads(solve):
        return jax.jit(jax.value_and_grad(
            lambda A, rhs: (solve(A, rhs) * cot).sum(), argnums=(0, 1)))

    # beta near 2 with aligned keys: (I + A) has a condition number of
    # ~2800 at C = 64 (70 with beta 0.99), and an inverse applied by a
    # product keeps less of it than a substitution does: 1.5e-5 of the
    # largest entry, solve_triangular's own float32 1.5e-6.  Still 250
    # times under a bfloat16 rounding of the products' operands
    tol = 3e-5 if case == "aligned_beta_near_2" else 1e-5
    before = profiler.get_stat("kda_solves_blocked")
    got = np.asarray(jax.jit(lambda *a: tf._unit_lower_solve(*a))(A, rhs))
    assert profiler.get_stat("kda_solves_blocked") == before + 1
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= tol * scale
    assert np.abs(got - np.asarray(triangular(A, rhs))).max() <= tol * scale

    _, (dA, drhs) = value_and_grads(tf._unit_lower_solve)(A, rhs)
    _, (want_dA, want_drhs) = value_and_grads(triangular)(A, rhs)
    below = np.tril(np.ones((c, c), bool), -1)
    assert not np.asarray(dA)[..., ~below].any()
    for got, w in ((np.asarray(dA)[..., below],
                    np.asarray(want_dA)[..., below]),
                   (np.asarray(drhs), np.asarray(want_drhs))):
        assert np.abs(got - w).max() <= tol * np.abs(w).max()


# ---------------------------------------------------------------------------
# the mixed stack against the benchmark's reference


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
    assert float(stats["moe_tokens"]) == 3 * B * T
    assert float(stats["kda_tokens"]) == 3 * B * T
    assert float(stats["kda_chunks"]) == 3 * B * -(-T // 8)
    assert 0 < float(stats["kda_decay_span_max"]) <= 20.0 * (1 + 1e-6)
    assert 0 <= float(stats["moe_groups_kept_here"]) <= 3 * B * T
    from mxtpu import profiler
    assert profiler.stats().get("kda_traced", 0) > 0
    if path == "pallas_interpreted":        # q.k 16 wide, v 8: padded
        assert profiler.stats().get("mla_padded_width") == 128
        assert profiler.stats().get("flash_attention_pallas", 0) > 0


def test_the_tolerance_refuses_bfloat16(mesh):
    """The same comparison with the program in bfloat16 (weights rounded
    to it on both sides, so only the arithmetic differs) fails by the
    tolerance the float32 program passes."""
    cfg = program_config(HF, dtype="bfloat16")
    params, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    _, grads, _ = _program_loss_and_grads(cfg, mesh, params, tokens, labels)
    want = jax.grad(lambda p: ref.loss_fn(HF, p, jnp.asarray(tokens),
                                          jnp.asarray(labels)))(
        {k: jnp.asarray(v) for k, v in flat.items()})
    assert _worst_gap(grads, want)[0] > 10 * TOL


def test_fused_k_steps_return_every_counter_per_step(mesh):
    cfg = program_config(HF)
    params, _ = _weights(cfg, mesh)
    step, sh = tf.make_fused_train_steps(cfg, mesh, 2, lr=1e-3)
    toks = jax.device_put(np.stack([_batch(s)[0] for s in (1, 2)]),
                          sh["data"])
    out = step(params, tf.init_opt_state(cfg, mesh), toks, toks)
    assert set(out[3]) == set(tf.MOE_STATS + tf.GROUP_STATS + tf.KDA_STATS)
    assert all(v.shape == (2,) for v in out[3].values())
    added = tf.publish_moe_stats(out[3])
    assert added["kda_tokens"] == 2 * 3 * B * T
    assert added["kda_decay_span_max"] == float(
        np.asarray(out[3]["kda_decay_span_max"]).max())


# ---------------------------------------------------------------------------
# the router


def _route_case(**over):
    hf = dict(HF, num_experts=16, n_group=4, topk_group=2,
              num_experts_per_tok=3, experts_held=2, **over)
    cfg = program_config(hf)
    rng = np.random.RandomState(2)
    z = jnp.asarray(rng.randn(96, 64), jnp.float32)
    router = jnp.asarray(rng.randn(64, 16) / 8.0, jnp.float32)
    bias = jnp.asarray(rng.randn(16) * 0.05, jnp.float32)
    return hf, cfg, z, router, bias


@pytest.mark.parametrize("case", ["matches_plain", "matches_reference",
                                  "one_group_traces_the_parent",
                                  "all_groups_kept_is_plain_top_k"])
def test_group_limited_router(case):
    hf, cfg, z, router, bias = _route_case()
    scores = np.asarray(jax.nn.sigmoid(z @ router))
    if case == "matches_plain":
        idx, w, kept = tf._route(cfg, z, router, bias)
        ids, want_w, want_kept = plain.route_by_groups(
            scores, bias, 4, 2, 3, 2.5)
        np.testing.assert_array_equal(np.asarray(idx), ids)
        np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(kept), want_kept)
        # a selection that is NOT the plain top 3 on some token: the
        # groups bind
        _, plain_ids = jax.lax.top_k(jnp.asarray(scores) + bias, 3)
        assert (np.asarray(plain_ids) != ids).any()
    elif case == "matches_reference":
        idx, w, kept = tf._route(cfg, z, router, bias)
        ridx, rw, rkept = ref.route(hf, z, router, bias)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_allclose(np.asarray(w), np.asarray(rw), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(kept), np.asarray(rkept))
    elif case == "one_group_traces_the_parent":
        one = dataclasses.replace(cfg, n_group=1, topk_group=1)
        got = jax.make_jaxpr(lambda z, r, b: tf._route(one, z, r, b))(
            z, router, bias)
        want = jax.make_jaxpr(
            lambda z, r, b: plain.route_before_groups(one, z, r, b))(
                z, router, bias)
        assert str(got) == str(want)
    else:
        every = dataclasses.replace(cfg, topk_group=4)
        idx, w, kept = tf._route(every, z, router, bias)
        one = dataclasses.replace(cfg, n_group=1, topk_group=1)
        idx1, w1 = tf._route(one, z, router, bias)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx1))
        np.testing.assert_allclose(np.asarray(w), np.asarray(w1))
        assert np.asarray(kept).all()


def test_the_shares_add_up_to_the_uncut_layer(mesh):
    """Guide, section 4: over ALL the held ranges (8 chips' shares of 16
    experts in 4 groups, top-3 inside 2 groups) the routed parts, with
    the shared expert counted once, add up to the layer with every
    expert present, and every pair is computed on exactly one share."""
    hf, cfg, z, router, bias = _route_case()
    rng = np.random.RandomState(4)
    leaves = tf._layer_leaves(
        dataclasses.replace(cfg, experts_held=16, expert_first=0), "moe")
    whole = {k: jnp.asarray(rng.randn(*shape) * (1.0 / fan) ** 0.5,
                            jnp.float32)
             for k, (shape, _, fan) in leaves.items() if fan is not None}
    whole["router"], whole["router_bias"] = router, bias
    ids, w, _ = plain.route_by_groups(
        np.asarray(jax.nn.sigmoid(z @ router)), bias, 4, 2, 3, 2.5)
    want = plain.expert_layer_uncut(z, whole, ids, w)

    def shared(z, lw):
        return tf._gated_ffn(z, lw["ws_g"], lw["ws_u"], lw["ws_d"])

    total = np.asarray(_on_mesh(mesh, shared, z, whole), np.float64)
    pairs = kept_here = 0.0
    for first in range(0, 16, 2):
        share = dict(whole, **{k: whole[k][first:first + 2]
                               for k in ("we_g", "we_u", "we_d")})
        c = dataclasses.replace(cfg, expert_first=first)

        def run(z, share):
            idx, w, kept = tf._route(c, z, share["router"],
                                     share["router_bias"])
            return tf._experts_grouped(c, z, idx, w, share)

        part, stats = _on_mesh(mesh, run, z, share)
        total = total + np.asarray(part, np.float64)
        pairs += float(stats["moe_pairs"])
    assert np.abs(total - want).max() <= TOL * np.abs(want).max()
    assert pairs == z.shape[0] * 3


# ---------------------------------------------------------------------------
# layouts and what is refused


def test_the_published_42_layers_build_in_their_order():
    """`param_shapes` of the uncut depth (shapes only): 35 KDA layers and
    7 latent-attention layers, two leading dense ones, in the published
    order: five KDA then one MLA, seven times."""
    hf = dict(HF, num_hidden_layers=42, first_k_dense_replace=2,
              layer_group_size=6, layers_held=list(range(42)))
    cfg = program_config(hf)
    segs = tf._segments(cfg)
    order = [kind for _, kind, _, n in segs for _ in range(n)]
    assert order == (["kda+dense"] * 2 + ["kda+moe"] * 3 + ["moe"]
                     + (["kda+moe"] * 5 + ["moe"]) * 6)
    shapes = tf.param_shapes(cfg, 1)
    assert shapes["dense.kda.wa"][:2] == (1, 2)
    assert shapes["kda.wa"][:2] == (1, 33)
    assert shapes["wkv_b"][:2] == (1, 7) and shapes["wq"][:2] == (1, 7)
    assert "kda.router" in shapes and "dense.kda.router" not in shapes
    # each MLA segment takes its own row of the 7-deep stack
    assert [first for p, _, first, _ in segs if p == ""] == list(range(7))
    assert set(ref.stacked_leaves(hf)) | {"embed", "ln_f", "unembed"} \
        == set(shapes)
    assert {k: (1,) + tuple(s) if k in ref.stacked_leaves(hf) else tuple(s)
            for k, s, _ in ref.layout(hf)} == shapes


@pytest.mark.parametrize("bad", [
    dict(kda_period=1), dict(kda_chunk=10, kda_rebase=4),
    dict(kda_rebase=32, kda_chunk=64),      # 32 x 5 = 160 nats: overflows
    dict(layer_ids=(0, 1, 2)), dict(layer_ids=(3, 0, 4, 5)),
    dict(n_group=3), dict(topk_group=3), dict(n_group=8),
    dict(n_group=4, topk_group=1, top_k=3)])
def test_config_refuses_what_is_not_built(bad):
    from mxtpu.base import MXNetError

    with pytest.raises(MXNetError):
        program_config(HF, **bad)


@pytest.mark.parametrize("axis", ["sp", "tp"])
def test_kda_refuses_a_sharded_sequence_or_sharded_heads(axis):
    from mxtpu.base import MXNetError

    axes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}
    axes[axis] = 2
    mesh = create_mesh(axes, devices=jax.devices()[:2])
    with pytest.raises(MXNetError):
        tf.make_train_step(program_config(HF), mesh)
