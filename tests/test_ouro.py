"""Ouro's looped stack in `mxtpu.parallel.transformer` (`loop_passes`:
the same layers run several times, a norm, an exit gate and a loss at
every pass, combined by the exit distribution into the stage-1
objective) against plain float32 references, at tiny widths on the CPU.

Tolerances.  Program and reference are both float32 here and differ in
the order of their sums (the flash recurrence, or the fused reference
path, against a whole softmax over row blocks; the exit distribution
by a cumulative sum against a running one).  `TOL` = 2e-4 of a leaf's
largest entry is what the glm, ling and trinity tests hold; the three
passes compound the rounding of one pass, and still read under a tenth
of it.  The controls read a hundred to ten thousand times it, which
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
import ouro_reference as plain  # noqa: E402
from drivers.lm_ouro_fused import transformer_config  # noqa: E402
# the one-device mesh and the worst leaf's gradient gap: as the glm
# tests have them
from test_glm_moe_lite import _worst_gap, mesh  # noqa: E402,F401
from reference import ouro_2_6b as ref  # noqa: E402

TOL = 2e-4

# the published config's keys at tiny sizes: 2 held layers run 3 times,
# 4 heads of 16, sequences of 64
HF = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
          head_dim=16, intermediate_size=96, num_hidden_layers=2,
          vocab_size=64, rope_theta=1000000, rms_norm_eps=1e-6,
          layer_types=["full_attention"] * 48, sliding_window=None,
          use_sliding_window=False, early_exit_threshold=1,
          total_ut_steps=3, exit_entropy=0.1)
B, T = 2, 64


def program_config(hf, dtype="float32", remat="none", **over):
    """The `TransformerConfig` the benchmark's driver builds from these
    keys (so the mapping the cell runs is the one tested here)."""
    return dataclasses.replace(
        transformer_config(dict(hf, param_dtype=dtype, remat=remat)), **over)


def _weights(cfg, mesh, seed=0):
    """(program's params, the same under the reference's shapes).  The
    gate's bias is moved off its starting 0 and its weight scaled up, so
    that the exit distribution is uneven and differs token by token."""
    params = tf.init_params(cfg, mesh, seed)
    if "exit_gate.b" in params:
        params["exit_gate.b"] = params["exit_gate.b"] - 0.7
        params["exit_gate.w"] = params["exit_gate.w"] * 8.0
    flat = {k: np.asarray(v, np.float32)[() if k in ref._WHOLE else 0]
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
    return float(out[2]), grads, (out[3] if len(out) > 3 else {})


def _reference(hf, flat, tokens, labels, mode="f32"):
    """((loss, the forward's record), gradient) of the reference."""
    n = tokens.size

    def loss(p):
        total, aux = ref.forward(hf, p, jnp.asarray(tokens),
                                 jnp.asarray(labels), mode)
        return total / n, aux

    return jax.value_and_grad(loss, has_aux=True)(
        {k: jnp.asarray(v) for k, v in flat.items()})


@pytest.fixture(scope="module")
def plain_run(mesh):
    """The float32 program's first step (`remat="full"`, the cell's
    policy; the flash kernels' jnp path) and the reference's, on one
    batch: what several tests below hold against each other."""
    cfg = program_config(HF, remat="full")
    params, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    loss, grads, stats = _program_loss_and_grads(cfg, mesh, params, tokens,
                                                 labels)
    (want_loss, aux), want = _reference(HF, flat, tokens, labels)
    return dict(cfg=cfg, params=params, flat=flat, loss=loss, grads=grads,
                stats=stats, want_loss=float(want_loss), aux=aux, want=want)


# ---------------------------------------------------------------------------
# the whole looped stack against the benchmark's reference


@pytest.mark.parametrize("path", ["reference_path", "pallas_interpreted"])
def test_program_matches_reference_loss_and_every_gradient(
        mesh, monkeypatch, plain_run, path):
    from mxtpu import profiler

    run = plain_run
    loss, grads = run["loss"], run["grads"]
    if path == "pallas_interpreted":
        monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
        before = profiler.get_stat("flash_attention_pallas")
        loss, grads, _ = _program_loss_and_grads(
            run["cfg"], mesh, run["params"], *_batch())
        assert profiler.get_stat("flash_attention_pallas") > before
    want = run["want"]
    assert abs(loss - run["want_loss"]) <= TOL * run["want_loss"]
    assert set(want) == set(grads)
    assert set(want) >= {"exit_gate.w", "exit_gate.b"}
    gap, where = _worst_gap(grads, want)
    assert gap <= TOL, (where, gap)


def _flash_kernel_sites(cfg, mesh, params, tokens, labels):
    """{kernel name: `pallas_call`s in the train step's jaxpr} (a call in
    a scan's body is one)."""
    step, _ = tf.make_train_step(cfg, mesh, lr=1e-3, optimizer="adam")
    sites = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                sites[name] = sites.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(step)(params, tf.init_opt_state(cfg, mesh), tokens,
                              labels).jaxpr)
    return sites


def test_later_passes_keep_the_flash_results_and_change_no_number(
        mesh, monkeypatch, plain_run):
    """Under `remat="full"` every pass but the first keeps the flash
    kernel's output and log-sums: the backward pass rebuilds the forward
    kernel in the first pass's layer scan alone, the trace-time stat
    `loop_flash_kept` reads (U - 1) x N, and the loss and every leaf's
    gradient are those of the same program with nothing kept, to float32
    rounding (interpreted Pallas kernels)."""
    from mxtpu import profiler

    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")
    cfg, params = plain_run["cfg"], plain_run["params"]
    passes, layers = HF["total_ut_steps"], HF["num_hidden_layers"]
    tokens, labels = _batch()
    before = profiler.get_stat("loop_flash_kept")
    loss, grads, _ = _program_loss_and_grads(cfg, mesh, params, tokens,
                                             labels)
    assert profiler.get_stat("loop_flash_kept") - before \
        == (passes - 1) * layers
    kept = _flash_kernel_sites(cfg, mesh, params, tokens, labels)

    run_stack = tf._run_stack
    monkeypatch.setattr(
        tf, "_run_stack",
        lambda *args, keep_flash=False: run_stack(*args))
    want_loss, want, _ = _program_loss_and_grads(cfg, mesh, params, tokens,
                                                 labels)
    rebuilt = _flash_kernel_sites(cfg, mesh, params, tokens, labels)
    # a forward and a backward layer scan a pass; the forward kernel
    # rebuilt in every backward one, or in the first pass's alone
    assert rebuilt == {"mx_flash_fwd": 2 * passes, "mx_flash_dq": passes,
                       "mx_flash_dkv": passes}, rebuilt
    assert kept == dict(rebuilt, mx_flash_fwd=passes + 1), kept
    assert abs(loss - want_loss) <= 1e-6 * want_loss
    gap, where = _worst_gap(grads, want)
    assert gap <= 1e-5, (where, gap)


@pytest.mark.parametrize("what", ["no_entropy", "one_pass_fewer",
                                  "bfloat16"])
def test_the_tolerance_refuses(mesh, plain_run, what):
    """The same comparison fails by the tolerance the float32 program
    passes: against the reference with the entropy term dropped, with
    one pass fewer, and with the program in bfloat16 (weights rounded to
    it on both sides, so only the arithmetic differs)."""
    tokens, labels = _batch()
    grads, flat = plain_run["grads"], plain_run["flat"]
    if what == "bfloat16":
        cfg = program_config(HF, dtype="bfloat16", remat="full")
        params, flat = _weights(cfg, mesh)
        _, grads, _ = _program_loss_and_grads(cfg, mesh, params, tokens,
                                              labels)
    hf = dict(HF, **{"no_entropy": {"exit_entropy": 0.0},
                     "one_pass_fewer": {"total_ut_steps": 2},
                     "bfloat16": {}}[what])
    _, want = _reference(hf, flat, tokens, labels)
    assert _worst_gap(grads, want)[0] > 10 * TOL


def test_one_pass_is_the_plain_stack_to_the_bit(mesh):
    """`loop_passes` = 1 is the stack as it was: no gate among the
    leaves, no counters, and the lowered program the text of a config
    that never names the loop (whatever `exit_entropy` says); the
    reference's objective with one pass is its plain cross-entropy to
    the bit (p_1 = 1, log p_1 = 0), and the program's loss is the
    reference's."""
    cfg = program_config(HF, loop_passes=1, exit_entropy=0.5)
    base = dataclasses.replace(cfg, loop_passes=1, exit_entropy=0.1)
    assert tf._stat_names(cfg) == ()
    assert set(tf.param_shapes(cfg, 1)) == set(tf.param_shapes(base, 1))
    assert not any(k.startswith("exit_gate") for k in tf.param_shapes(cfg, 1))
    tokens, labels = _batch()
    texts = []
    for c in (cfg, base):
        step, sh = tf.make_train_step(c, mesh, lr=1e-3, optimizer="adam")
        params = tf.init_params(c, mesh, 0)
        texts.append(step.lower(params, tf.init_opt_state(c, mesh),
                                jax.device_put(tokens, sh["data"]),
                                jax.device_put(labels, sh["data"])).as_text())
    assert texts[0] == texts[1]
    params, flat = _weights(cfg, mesh)
    loss, _, stats = _program_loss_and_grads(cfg, mesh, params, tokens,
                                             labels)
    assert stats == {}
    hf = dict(HF, total_ut_steps=1)
    total, aux = ref.forward(hf, {k: jnp.asarray(v) for k, v in flat.items()},
                             jnp.asarray(tokens), jnp.asarray(labels))
    assert float(total) == float(aux["nll"][0].sum())
    assert np.all(np.asarray(aux["log_p"]) == 0.0)
    assert abs(loss - float(total) / tokens.size) <= TOL * loss


@pytest.mark.parametrize("passes", [2, 3, 4])
def test_exit_distribution_sums_to_one_and_matches_its_closed_form(passes):
    """The program's log-probabilities (`_exit_log_probs`) against the
    product of probabilities in float64, with gates that saturate both
    ways: exp of them sums to 1 and no entry is -inf or NaN where a
    probability rounds to 0 (|s| = 120)."""
    rng = np.random.RandomState(passes)
    s = rng.randn(passes - 1, 50) * 4.0
    s[:, :4] = [120.0, -120.0, 30.0, -30.0]
    got = np.asarray(tf._exit_log_probs(jnp.asarray(s, jnp.float32)),
                     np.float64)
    assert got.shape == (passes, 50) and np.all(np.isfinite(got))
    want = plain.exit_distribution(s)
    np.testing.assert_allclose(np.exp(got).sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.exp(got), want, rtol=1e-5, atol=1e-7)
    ok = want > 1e-30
    np.testing.assert_allclose(got[ok], np.log(want[ok]), rtol=1e-5,
                               atol=1e-6)


def test_reference_objective_is_the_closed_form(mesh):
    """The benchmark's reference combines its per-pass losses by the
    exit distribution as the float64 closed form does."""
    cfg = program_config(HF)
    _, flat = _weights(cfg, mesh)
    tokens, labels = _batch()
    p = {k: jnp.asarray(v) for k, v in flat.items()}
    total, aux = ref.forward(HF, p, jnp.asarray(tokens), jnp.asarray(labels))
    xs = ref.passes(HF, p, jnp.asarray(tokens))
    s = np.stack([np.asarray(x, np.float64).reshape(tokens.size, -1)
                  @ flat["exit_gate.w"] + flat["exit_gate.b"]
                  for x in xs[:-1]])
    want = plain.objective(np.asarray(aux["nll"]), s, HF["exit_entropy"])
    assert float(total) == pytest.approx(want.sum(), rel=1e-5)
    # the gate, moved off its start, spreads the exits unevenly
    assert 0.05 < np.exp(np.asarray(aux["log_p"]))[-1].mean() < 0.95


def test_counters_are_the_references_own_sums(mesh, plain_run):
    """The step's counters against the reference's sums: passes,
    tokens, the expected exit pass and the last pass's mass; a fused
    program returns them per step and `publish_moe_stats` adds them up
    (to the nearest whole number)."""
    from mxtpu import profiler

    stats, aux = plain_run["stats"], plain_run["aux"]
    assert set(stats) == set(tf.LOOP_STATS)
    assert float(stats["loop_passes"]) == HF["total_ut_steps"]
    assert float(stats["loop_tokens"]) == B * T
    for name, key in (("loop_exit_expect", "exit_expect"),
                      ("loop_exit_last", "exit_last")):
        assert float(stats[name]) == pytest.approx(float(aux[key]),
                                                   rel=1e-4)
    assert 1.0 < float(stats["loop_exit_expect"]) / (B * T) \
        < HF["total_ut_steps"]

    cfg = plain_run["cfg"]
    step, sh = tf.make_fused_train_steps(cfg, mesh, 2, lr=1e-3)
    toks = jax.device_put(np.stack([_batch(s)[0] for s in (1, 2)]),
                          sh["data"])
    params = jax.tree_util.tree_map(jnp.copy, plain_run["params"])
    out = step(params, tf.init_opt_state(cfg, mesh), toks, toks)
    assert set(out[3]) == set(tf.LOOP_STATS)
    assert all(v.shape == (2,) for v in out[3].values())
    before = profiler.get_stat("loop_passes")
    added = tf.publish_moe_stats(out[3])
    assert added["loop_passes"] == 2 * HF["total_ut_steps"]
    assert profiler.get_stat("loop_passes") - before == 6
    assert added["loop_tokens"] == 2 * B * T


def test_forward_runs_every_pass_and_gives_the_last_ones_logits(mesh):
    cfg = program_config(HF)
    params, flat = _weights(cfg, mesh)
    tokens, _ = _batch()
    fwd = tf.make_forward(cfg, mesh)
    got = np.asarray(fwd(params, tokens), np.float32)
    want = np.asarray(ref.logits(HF, {k: jnp.asarray(v)
                                      for k, v in flat.items()},
                                 jnp.asarray(tokens)))
    assert got.shape == (B, T, HF["vocab_size"])
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()
    # ... and not the logits of a pass before it
    early = np.asarray(ref.logits(dict(HF, total_ut_steps=2),
                                  {k: jnp.asarray(v)
                                   for k, v in flat.items()},
                                  jnp.asarray(tokens)))
    assert np.abs(got - early).max() > 100 * TOL * np.abs(want).max()


def test_a_data_and_tensor_parallel_mesh_gives_the_same_loss(plain_run):
    """dp = 2, tp = 2: the heads, the feed-forward and the vocabulary
    split over tp, the batch over dp; the gate's logits are the same on
    every tp rank, so the loss and the counters are the one-device
    program's."""
    cfg = plain_run["cfg"]
    wide = create_mesh({"dp": 2, "pp": 1, "tp": 2, "sp": 1, "ep": 1},
                       devices=jax.devices()[:4])
    spec = tf.param_specs(cfg)
    placed = {k: jax.device_put(np.asarray(v),
                                jax.sharding.NamedSharding(wide, spec[k]))
              for k, v in plain_run["params"].items()}
    got, _, stats = _program_loss_and_grads(cfg, wide, placed, *_batch())
    assert got == pytest.approx(plain_run["loss"], rel=1e-5)
    for k in tf.LOOP_STATS:
        assert float(stats[k]) == pytest.approx(
            float(plain_run["stats"][k]), rel=1e-5)


# ---------------------------------------------------------------------------
# what is not built is refused


def test_config_refuses_what_is_not_built():
    with pytest.raises(MXNetError, match="loop_passes"):
        tf.TransformerConfig(loop_passes=0)
    with pytest.raises(MXNetError, match="multi-token-prediction"):
        tf.TransformerConfig(loop_passes=2, mtp_depth=1)


def test_a_pipeline_or_microbatches_are_refused():
    cfg = program_config(HF)
    two = create_mesh({"dp": 1, "pp": 2, "tp": 1, "sp": 1, "ep": 1},
                      devices=jax.devices()[:2])
    with pytest.raises(MXNetError, match="pp = 1"):
        tf.make_train_step(cfg, two, optimizer="adam")
    with pytest.raises(MXNetError, match="pp = 1"):
        tf.make_forward(cfg, two)
    one = create_mesh({"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1},
                      devices=jax.devices()[:1])
    step, sh = tf.make_train_step(cfg, one, n_micro=2, optimizer="adam")
    tokens, labels = _batch()
    with pytest.raises(MXNetError, match="one stage and one microbatch"):
        step.lower(tf.init_params(cfg, one, 0), tf.init_opt_state(cfg, one),
                   jax.device_put(tokens, sh["data"]),
                   jax.device_put(labels, sh["data"]))
