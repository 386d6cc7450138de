"""Trinity-Mini's block in `mxtpu.parallel.transformer` (grouped kv
heads; window and full attention layers mixed by a period, rotary
positions on the window layers only; per-head q / k norms; an
elementwise output gate; four norms a layer; sigmoid top-k experts over
a held range with a shared expert) against plain float32 references, at
tiny widths on the CPU.

Tolerances.  Program and reference are both float32 here and differ in
the order of their sums (the flash recurrence, or the fused reference
path, against a whole softmax over row blocks; grouped products against
masked dense ones).  `TOL` = 2e-4 of a leaf's largest entry is what the
glm and ling tests hold; bfloat16 arithmetic reads fifty times that and
the window left out a thousand times, which
`test_the_tolerance_refuses` shows.
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
from mxtpu.parallel.mesh import create_mesh

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.join(os.path.dirname(HERE), "benchmark", "onchip")
sys.path[:0] = [HERE, ONCHIP]
import trinity_mini_reference as plain  # noqa: E402
from ling_hybrid_reference import expert_layer_uncut  # noqa: E402
from drivers.lm_trinity_fused import transformer_config  # noqa: E402
# the one-device mesh, a function run inside shard_map on it, and the
# worst leaf's gradient gap: as the glm tests have them
from test_glm_moe_lite import _on_mesh, _worst_gap, mesh  # noqa: E402,F401
from reference import trinity_mini as ref  # noqa: E402

TOL = 2e-4

# the published config's keys at tiny sizes: a period of 4 (published
# layers 0, 4, 5, 6, 7: sliding + dense; sliding, sliding, sliding, full,
# each + experts), 4 q heads on 2 kv heads of 16, a window of 24 in
# sequences of 88 (no multiple of it), 8 experts top-2, this "chip" holds 2
PERIOD = 4
HF = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, rope_theta=10000, rms_norm_eps=1e-5,
          sliding_window=24, global_attn_every_n_layers=PERIOD,
          layer_types=["full_attention" if (i + 1) % PERIOD == 0
                       else "sliding_attention" for i in range(32)],
          mup_enabled=True, intermediate_size=96, moe_intermediate_size=32,
          num_shared_experts=1, num_experts=8, num_experts_per_tok=2,
          n_group=1, topk_group=1, route_scale=2.826, route_norm=True,
          score_func="sigmoid", num_dense_layers=1, num_hidden_layers=5,
          layers_held=[0, 4, 5, 6, 7], vocab_size=64, experts_held=2,
          expert_first=2)
B, T = 2, 88


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


def _reference_grads(flat, tokens, labels, mode="f32"):
    return ref._grad_and_pairs(
        HF, {k: jnp.asarray(v) for k, v in flat.items()},
        jnp.asarray(tokens), jnp.asarray(labels), mode)


# ---------------------------------------------------------------------------
# the whole stack against the benchmark's reference


@pytest.mark.parametrize("remat,path", [
    ("none", "reference_path"), ("dots", "reference_path"),
    ("dots", "pallas_interpreted")])
def test_program_matches_reference_loss_and_every_gradient(
        mesh, monkeypatch, remat, path):
    from mxtpu import profiler

    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET",
                       "1" if path == "pallas_interpreted" else "0")
    cfg = program_config(HF, remat=remat)
    params, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    before = profiler.get_stat("flash_attention_pallas")
    loss, grads, stats = _program_loss_and_grads(cfg, mesh, params, tokens,
                                                 labels)
    (want_loss, pairs), want = _reference_grads(flat, tokens, labels)
    assert abs(loss - float(want_loss)) <= TOL * float(want_loss)
    assert set(want) == set(grads)
    gap, where = _worst_gap(grads, want)
    assert gap <= TOL, (where, gap)
    assert float(stats["moe_pairs"]) == float(pairs)
    assert float(stats["moe_tokens"]) == 4 * B * T
    took = profiler.get_stat("flash_attention_pallas") - before
    assert (took > 0) == (path == "pallas_interpreted")
    if took:        # 4 q heads on 2 kv heads, the window layers' band
        assert profiler.get_stat("flash_kv_group") >= 2
        assert profiler.get_stat("flash_window") >= HF["sliding_window"]


@pytest.mark.parametrize("what", ["bfloat16", "the_window_left_out"])
def test_the_tolerance_refuses(mesh, what):
    """The same comparison fails by the tolerance the float32 program
    passes: with the program in bfloat16 (weights rounded to it on both
    sides, so only the arithmetic differs), and with the reference's
    window layers masked causally and no more."""
    cfg = program_config(HF, dtype="bfloat16" if what == "bfloat16"
                         else "float32")
    params, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    _, grads, _ = _program_loss_and_grads(cfg, mesh, params, tokens, labels)
    _, want = _reference_grads(
        flat, tokens, labels, "f32" if what == "bfloat16" else "no_window")
    assert _worst_gap(grads, want)[0] > 10 * TOL


def test_fused_k_steps_return_every_counter_per_step(mesh):
    cfg = program_config(HF)
    params, _ = _weights(cfg, mesh)
    step, sh = tf.make_fused_train_steps(cfg, mesh, 2, lr=1e-3)
    toks = jax.device_put(np.stack([_batch(s)[0] for s in (1, 2)]),
                          sh["data"])
    out = step(params, tf.init_opt_state(cfg, mesh), toks, toks)
    assert set(out[3]) == set(tf.MOE_STATS)
    assert all(v.shape == (2,) for v in out[3].values())
    added = tf.publish_moe_stats(out[3])
    assert added["moe_tokens"] == 2 * 4 * B * T
    assert added["moe_pairs"] == float(np.asarray(out[3]["moe_pairs"]).sum())


# ---------------------------------------------------------------------------
# the attention block alone


def _block(cfg, seed=1, t=40):
    rng = np.random.RandomState(seed)
    lw = {k: jnp.asarray(rng.randn(*shape) * (1.0 / (fan or 1)) ** 0.5
                         + (fan is None), jnp.float32)
          for k, (shape, _, fan) in tf._layer_leaves(cfg, "dense").items()}
    return lw, jnp.asarray(rng.randn(1, t, HF["hidden_size"]), jnp.float32)


def _gqa(cfg, mesh, x, lw, kind):
    """`_gqa` as `_layer_fn` calls it for a layer of `kind`."""
    full = tf._is_full(kind)

    def run(x, lw):
        rope = tf._rotary_table(cfg, jnp.arange(x.shape[1]))
        return tf._gqa(cfg, x, lw, 1,
                       rope if cfg.rope_full or not full else None,
                       None if full else cfg.window or None)

    return np.asarray(_on_mesh(mesh, run, x, lw))


@pytest.mark.parametrize("kind", ["dense", "full+dense"],
                         ids=["window_layer", "full_layer"])
@pytest.mark.parametrize("path", ["reference_path", "pallas_interpreted"])
def test_attention_block_matches_a_query_at_a_time(mesh, monkeypatch, path,
                                                   kind):
    """Grouped kv heads, the per-head norms, the band's edge (a query
    sees `window` keys with its own), positions on the window layer and
    none on the full one, the elementwise gate: against numpy, a query
    at a time."""
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET",
                       "1" if path == "pallas_interpreted" else "0")
    cfg = program_config(HF)
    lw, x = _block(cfg)
    full = tf._is_full(kind)
    want = plain.attention_block(
        x[0], lw, 4, 2, 16, window=None if full else HF["sliding_window"],
        theta=None if full else 10000.0)
    got = _gqa(cfg, mesh, x, lw, kind)[0]
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


def test_a_full_layer_carries_no_positions_and_a_window_layer_does(mesh):
    """Reverse the order of the tokens before the last, with the mask
    set aside (a window wider than the sequence, and only the last query
    read, which sees every key either way): a full layer's last output
    does not move, for without positions attention is a function of the
    SET of keys; a window layer's does."""
    cfg = program_config(HF, window=10 ** 6)
    lw, x = _block(cfg)
    # keys 0 .. T-2 in reverse order, the last query kept last
    keys_reversed = jnp.concatenate([x[:, -2::-1], x[:, -1:]], axis=1)
    for kind, same in (("full+dense", True), ("dense", False)):
        a = _gqa(cfg, mesh, x, lw, kind)[0, -1]
        b = _gqa(cfg, mesh, keys_reversed, lw, kind)[0, -1]
        close = np.abs(a - b).max() <= 1e-5 * np.abs(a).max()
        assert close == same, (kind, np.abs(a - b).max())


# ---------------------------------------------------------------------------
# the router and the shares


def test_the_shares_add_up_to_the_uncut_layer(mesh):
    """Guide, section 4: over ALL 16 held ranges (16 chips' shares of 32
    experts, top-3) the routed parts, with the shared expert counted
    once, add up to the layer with every expert present, and every pair
    is computed on exactly one share."""
    hf = dict(HF, num_experts=32, num_experts_per_tok=3, experts_held=2)
    cfg = program_config(hf)
    rng = np.random.RandomState(2)
    z = jnp.asarray(rng.randn(96, 64), jnp.float32)
    router = jnp.asarray(rng.randn(64, 32) / 8.0, jnp.float32)
    bias = jnp.asarray(rng.randn(32) * 0.05, jnp.float32)
    leaves = tf._layer_leaves(
        dataclasses.replace(cfg, experts_held=32, expert_first=0), "moe")
    whole = {k: jnp.asarray(rng.randn(*shape) * (1.0 / fan) ** 0.5,
                            jnp.float32)
             for k, (shape, _, fan) in leaves.items() if fan is not None}
    whole["router"], whole["router_bias"] = router, bias
    ids, w = plain.route_top_k(np.asarray(jax.nn.sigmoid(z @ router)), bias,
                               3, 2.826)
    idx, got_w = tf._route(cfg, z, router, bias)
    np.testing.assert_array_equal(np.asarray(idx), ids)
    np.testing.assert_allclose(np.asarray(got_w), w, rtol=1e-5)
    want = expert_layer_uncut(z, whole, ids, w)

    def shared(z, lw):
        return tf._gated_ffn(z, lw["ws_g"], lw["ws_u"], lw["ws_d"])

    total = np.asarray(_on_mesh(mesh, shared, z, whole), np.float64)
    pairs = 0.0
    for first in range(0, 32, 2):
        share = dict(whole, **{k: whole[k][first:first + 2]
                               for k in ("we_g", "we_u", "we_d")})
        c = dataclasses.replace(cfg, expert_first=first)

        def run(z, share):
            idx, w = tf._route(c, z, share["router"], share["router_bias"])
            return tf._experts_grouped(c, z, idx, w, share)

        part, stats = _on_mesh(mesh, run, z, share)
        total = total + np.asarray(part, np.float64)
        pairs += float(stats["moe_pairs"])
    assert np.abs(total - want).max() <= TOL * np.abs(want).max()
    assert pairs == z.shape[0] * 3


# ---------------------------------------------------------------------------
# layouts and what is refused


def test_the_published_32_layers_build_in_their_order():
    """`param_shapes` of the uncut depth (shapes only): 24 window layers
    and 8 full ones in the published order (three window layers, then a
    full one, eight times), two leading dense ones; the reference lays
    the same leaves out under the same names."""
    hf = dict(HF, num_hidden_layers=32, num_dense_layers=2,
              layers_held=list(range(32)))
    cfg = program_config(hf)
    segs = tf._segments(cfg)
    order = [kind for _, kind, _, n in segs for _ in range(n)]
    assert order == ["dense", "dense", "moe", "full+moe"] \
        + (["moe"] * 3 + ["full+moe"]) * 7
    assert [tf._is_full(k) for k in order] \
        == [t == "full_attention" for t in hf["layer_types"]]
    shapes = tf.param_shapes(cfg, 1)
    assert shapes["dense.wq"] == (1, 2, 64, 64)
    assert shapes["wk"] == (1, 22, 64, 32) and shapes["w_gate"][:2] == (1, 22)
    assert shapes["full.wq"][:2] == (1, 8) and "full.router" in shapes
    assert "dense.router" not in shapes and "pos" not in shapes
    assert shapes["ln1_post"] == (1, 22, 64) == shapes["ln2_post"]
    # each full segment takes its own row of the 8-deep stack
    assert [first for p, _, first, _ in segs if p == "full."] \
        == list(range(8))
    assert set(ref.stacked_leaves(hf)) | {"embed", "ln_f", "unembed"} \
        == set(shapes)
    assert {k: (1,) + tuple(s) if k in ref.stacked_leaves(hf) else tuple(s)
            for k, s, _ in ref.layout(hf)} == shapes


@pytest.mark.parametrize("bad", [
    dict(n_kv_heads=3), dict(head_dim=15), dict(full_period=1),
    dict(window=0), dict(full_period=0),    # no positions on NO layer
    dict(window=-1), dict(kda_period=3), dict(attention="mha"),
    dict(attention="mla"), dict(head_gate=True)])
def test_config_refuses_what_is_not_built(bad):
    with pytest.raises(MXNetError):
        program_config(HF, **bad)


def test_a_config_that_sets_none_of_it_builds_what_it_built():
    """The new fields at their defaults leave `mha`'s leaves, segments
    and position table as they were."""
    cfg = tf.TransformerConfig()
    assert set(tf.param_shapes(cfg, 1)) == {
        "embed", "ln_f", "unembed", "pos", "ln1", "ln2", "wq", "wk", "wv",
        "wo", "w1", "w2"}
    assert tf._segments(cfg) == [("", "dense", 0, 4)]


@pytest.mark.parametrize("axis", ["sp", "tp4"])
def test_gqa_refuses_the_ring_and_heads_it_cannot_split(axis):
    """sp > 1 runs the ring, which knows neither a window nor grouped kv
    heads; tp has to divide the kv heads (2 here)."""
    axes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}
    axes[axis[:2]] = int(axis[2:] or 2)
    mesh = create_mesh(axes, devices=jax.devices()[:axes[axis[:2]]])
    cfg = program_config(HF)
    with pytest.raises(MXNetError):
        tf.make_train_step(cfg, mesh)
