"""What `remat="dots"` keeps of an LM layer (`executor.apply_remat`,
`parallel/transformer._layer_fn`): the products the blocks name, the
flash kernel's merged output and log-sums, and NOT the one product each
attention block gives back; and that the policy changes no number: one
fused step's loss and every gradient leaf agree across "none", "dots"
and "full".  Tiny widths, float32, the kernels in interpreter mode.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import saved_residuals
from jax.sharding import PartitionSpec as P

from mxtpu.parallel import transformer as tf
from mxtpu.parallel.mesh import create_mesh

from test_glm_moe_lite import HF, program_config

B, T = 2, 64


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


@pytest.fixture(scope="module")
def mesh():
    return create_mesh({"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1},
                       devices=jax.devices()[:1])


def _config(attention, remat):
    if attention == "mla":      # the glm cell's layer kinds, tiny
        return program_config(HF, remat=remat)
    return tf.TransformerConfig(vocab=64, d_model=64, n_heads=4,
                                n_layers=2, d_ff=128, max_len=T,
                                dtype="float32", remat=remat)


def _one_fused_step(cfg, mesh):
    """(loss, gradient by leaf) of one fused Adam step from a zero
    state: the first moment is (1 - b1) times the gradient."""
    step, sh = tf.make_fused_train_steps(cfg, mesh, 1, lr=1e-3,
                                         optimizer="adam",
                                         betas=(0.9, 0.999))
    rng = np.random.RandomState(0)
    tokens, labels = (jax.device_put(
        rng.randint(0, cfg.vocab, (1, B, T)).astype(np.int32), sh["data"])
        for _ in range(2))
    out = step(tf.init_params(cfg, mesh, 0), tf.init_opt_state(cfg, mesh),
               tokens, labels)
    return float(out[2][0]), {k: np.asarray(v, np.float32) / 0.1
                              for k, v in out[1]["m"].items()}


_BASE = {}


@pytest.mark.parametrize("remat", ["none", "dots", "full"])
@pytest.mark.parametrize("attention", ["mha", "mla"])
def test_one_fused_step_is_the_same_under_every_policy(mesh, monkeypatch,
                                                       attention, remat):
    """Against the step with nothing rematerialised and the kernels OFF
    (the ring's own path in [B, h, T, D]): the policy, the kernels'
    entry on the activations' layout and the rebuilt product change the
    loss and every gradient leaf by float32 rounding alone."""
    from mxtpu import profiler

    if attention not in _BASE:
        with monkeypatch.context() as m:
            m.setenv("MXTPU_NO_PALLAS", "1")
            _BASE[attention] = _one_fused_step(_config(attention, "none"),
                                               mesh)
    want_loss, want = _BASE[attention]
    before = profiler.get_stat("flash_fwd_named")
    loss, grads = _one_fused_step(_config(attention, remat), mesh)
    assert profiler.get_stat("flash_fwd_named") > before    # the kernels
    assert abs(loss - want_loss) <= 1e-5 * abs(want_loss)
    assert set(grads) == set(want)
    for k, w in want.items():
        scale = np.abs(w).max()
        assert np.abs(grads[k] - w).max() <= 2e-5 * scale + 1e-30, k


@pytest.mark.parametrize("attention,kind", [("mha", "dense"),
                                            ("mla", "dense"),
                                            ("mla", "moe")])
def test_dots_keeps_the_kernels_results_and_gives_one_product_back(
        mesh, attention, kind):
    """The saved residuals of one "dots" layer: the flash kernel's
    merged output and its log-sums (by name), every product but the one
    the attention block gives back (`o @ wo` in `_attention`, `c_q @
    wq_b` in `_mla`), and nothing a block computes besides."""
    cfg = _config(attention, "dots")
    rng = np.random.RandomState(0)
    lw = {k: jnp.asarray(rng.randn(*shape) * 0.05, jnp.float32)
          for k, (shape, _, _) in tf._layer_leaves(cfg, kind).items()}
    x = jnp.asarray(rng.randn(B, T, cfg.d_model), jnp.float32)
    rope = tf._rotary_table(cfg, jnp.arange(T)) \
        if attention == "mla" else None
    seen = []

    def probe(x, lw):
        layer = tf._layer_fn(cfg, kind, 1, 1, rope)
        seen.extend(saved_residuals(lambda x, lw: layer(x, lw)[0], x, lw))
        return x

    jax.jit(jax.shard_map(probe, mesh=mesh, in_specs=(P(), P()),
                          out_specs=P(), check_vma=False))(x, lw)
    kept = [(aval.shape, src) for aval, src in seen
            if "from the argument" not in src and "constant" not in src]
    heads = cfg.n_heads
    merged = (B, T, heads * (cfg.v_head_dim or cfg.d_model // heads))
    # the log-sums by name, [B * heads, T]; the merged output comes out
    # of the kernels' entry (jax lists the rounding barrier it puts
    # behind a kept name, at the name's source line)
    assert [s for s, src in kept if "named 'flash_lse'" in src] \
        == [(B * heads, T)]
    assert [s for s, src in kept if "flash_attention_bthd" in src
            and "flash_lse" not in src] == [merged]
    # everything else kept is a product a block named (`_kept`; in
    # float32 jax may list a gated product as `silu`'s saved input)
    rest = [(s, src) for s, src in kept
            if "flash_attention_bthd" not in src]
    assert all("(_kept)" in src or "'silu'" in src for _, src in rest), rest
    # of the merged output's shape the parent's "dots" kept, beside it,
    # mha: q, k, v and o @ wo; mla: c_q @ wq_b and o @ wo.  One is gone.
    assert len([s for s, _ in rest if s == merged]) \
        == (3 if attention == "mha" else 1), rest


def test_dots_without_names_keeps_every_product(mesh):
    """The symbolic executor's graphs name nothing: `apply_remat(fn,
    "dots")` keeps every product's output as before, and the kernel's
    two results besides."""
    from mxtpu.executor import apply_remat
    from mxtpu.ops.pallas_attention import flash_attention_bthd

    def fn(x, w):
        q = (x @ w).reshape(B, T, 2, 32)
        return jnp.tanh(flash_attention_bthd(q, q, q, causal=True) @ w)

    x = jnp.ones((B, T, 64), jnp.float32)
    w = jnp.ones((64, 64), jnp.float32) * 0.01
    kept = [(a.shape, src) for a, src in saved_residuals(
        apply_remat(fn, "dots"), x, w) if "from the argument" not in src]
    # (jax lists a kept value as the rounding barrier behind it, at the
    # source line of what made it: the two products are `fn`'s own)
    assert sum(".fn)" in src for _, src in kept) == 2, kept
    assert sum("flash_attention_bthd" in src for _, src in kept) == 2, kept
    assert len(kept) == 4, kept
