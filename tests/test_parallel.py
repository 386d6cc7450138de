"""Parallel subsystem tests on the virtual 8-device CPU mesh.

Analog of the reference's single-process multi-device kvstore/consistency
tests (`tests/python/unittest/test_kvstore.py`, gpu `check_consistency`):
the ground truth for every sharded computation is the same computation on
a 1-device mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu.parallel as par
from mxtpu.parallel import transformer as tfm
from mxtpu.parallel.mesh import (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP,
                                 AXIS_EP)


def _mesh(dp=1, pp=1, tp=1, sp=1, ep=1):
    n = dp * pp * tp * sp * ep
    return par.create_mesh({AXIS_DP: dp, AXIS_PP: pp, AXIS_TP: tp,
                            AXIS_SP: sp, AXIS_EP: ep},
                           devices=jax.devices()[:n])


def _data(cfg, B, T, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, cfg.vocab, (B, T)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab, (B, T)).astype(np.int32)
    return tokens, labels


def _run_forward(cfg, mesh, tokens):
    params = tfm.init_params(cfg, mesh, seed=3)
    fwd = tfm.make_forward(cfg, mesh)
    return np.asarray(jax.device_get(fwd(params, tokens)))


CFG = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=4, n_layers=2,
                            d_ff=32, n_experts=0, max_len=64,
                            dtype="float32")


class TestShardedForwardConsistency:
    def _check(self, **mesh_kw):
        tokens, _ = _data(CFG, 4, 16)
        ref = _run_forward(CFG, _mesh(), tokens)
        got = _run_forward(CFG, _mesh(**mesh_kw), tokens)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_dp(self):
        self._check(dp=4)

    def test_tp(self):
        self._check(tp=4)

    def test_sp(self):
        self._check(sp=4)

    def test_pp(self):
        self._check(pp=2)

    def test_all_axes(self):
        self._check(dp=2, pp=2, tp=2)

    def test_tp_sp(self):
        self._check(tp=2, sp=2)


class TestShardedTrainConsistency:
    def _loss(self, cfg, mesh, n_micro=2):
        tokens, labels = _data(cfg, 8, 16, seed=1)
        params = tfm.init_params(cfg, mesh, seed=3)
        step, sh = tfm.make_train_step(cfg, mesh, n_micro=n_micro,
                                       lr=1e-2)
        t = jax.device_put(tokens, sh["data"])
        l = jax.device_put(labels, sh["data"])
        losses = []
        for _ in range(3):
            params, loss = step(params, t, l)
            losses.append(float(jax.device_get(loss)))
        return losses

    def test_train_matches_single_device(self):
        ref = self._loss(CFG, _mesh())
        got = self._loss(CFG, _mesh(dp=2, pp=2, tp=2))
        np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)
        assert ref[-1] < ref[0]  # it actually learns

    def test_train_sp_ring(self):
        ref = self._loss(CFG, _mesh())
        got = self._loss(CFG, _mesh(sp=4))
        np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)

    def test_train_moe_ep(self):
        cfg = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=4,
                                    n_layers=2, d_ff=32, n_experts=4,
                                    max_len=64, dtype="float32")
        ref = self._loss(cfg, _mesh())
        got = self._loss(cfg, _mesh(ep=4))
        np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-4)


class TestRingAttention:
    def _naive(self, q, k, v, causal):
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            T = q.shape[2]
            mask = np.triu(np.ones((T, T), bool), 1)
            s = np.where(mask, -1e30, s)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        return np.einsum("bhqk,bhkd->bhqd", p, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_blockwise_matches_naive(self, causal):
        rng = np.random.RandomState(0)
        q, k, v = (rng.randn(2, 2, 33, 8).astype(np.float32)
                   for _ in range(3))
        out = np.asarray(par.blockwise_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            block_size=8, causal=causal))
        np.testing.assert_allclose(out, self._naive(q, k, v, causal),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_matches_naive(self, causal):
        from jax.sharding import PartitionSpec as P

        mesh = _mesh(sp=4)
        rng = np.random.RandomState(1)
        q, k, v = (rng.randn(2, 2, 32, 8).astype(np.float32)
                   for _ in range(3))

        def f(q, k, v):
            return par.ring_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), axis_name=AXIS_SP,
                                      causal=causal)

        spec = P(None, None, AXIS_SP, None)
        sm = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
        out = np.asarray(jax.device_get(sm(q, k, v)))
        np.testing.assert_allclose(out, self._naive(q, k, v, causal),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_custom_vjp_gradients_match_dense(self, causal):
        """The recompute backward (second ring pass vs AD-through-loop)
        must reproduce dense-attention gradients for q, k AND v —
        including the cross-shard dk/dv hops riding the ring home."""
        from jax.sharding import PartitionSpec as P

        mesh = _mesh(sp=4)
        rng = np.random.RandomState(3)
        q, k, v = (rng.randn(2, 2, 32, 8).astype(np.float32)
                   for _ in range(3))

        def ring_loss(q, k, v):
            o = par.ring_attention(q, k, v, axis_name=AXIS_SP,
                                   causal=causal)
            return (jnp.sin(o) * o).sum()  # non-uniform cotangent

        spec = P(None, None, AXIS_SP, None)
        grads_ring = jax.jit(jax.shard_map(
            lambda q, k, v: jax.grad(ring_loss, argnums=(0, 1, 2))(
                q, k, v),
            mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=(spec, spec, spec)))(q, k, v)

        def dense_loss(q, k, v):
            d = q.shape[-1]
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
            if causal:
                t = q.shape[2]
                mask = np.tril(np.ones((t, t), bool))
                s = jnp.where(mask[None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
            return (jnp.sin(o) * o).sum()

        grads_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for gr, gd, name in zip(grads_ring, grads_dense, "qkv"):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(gr)), np.asarray(gd),
                rtol=2e-4, atol=2e-5, err_msg="d" + name)


class TestCollectives:
    def test_all_reduce(self):
        mesh = _mesh(dp=8)
        x = np.arange(16, dtype=np.float32).reshape(8, 2)
        out = par.all_reduce(x, axis=AXIS_DP, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), x.sum(0,
                                                          keepdims=True))

    def test_all_gather(self):
        mesh = _mesh(dp=8)
        x = np.arange(8, dtype=np.float32)
        out = par.all_gather(x, axis=AXIS_DP, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), x)

    def test_reduce_scatter(self):
        mesh = _mesh(dp=8)
        # 8 stacked per-shard contributions of length 8: output is the
        # elementwise sum, distributed one element per device
        x = np.arange(64, dtype=np.float32).reshape(8, 8)
        out = par.reduce_scatter(x.reshape(-1), axis=AXIS_DP, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), x.sum(0))

    def test_collective_permute(self):
        mesh = _mesh(dp=8)
        x = np.arange(8, dtype=np.float32)
        perm = [(i, (i + 1) % 8) for i in range(8)]
        out = par.collective_permute(x, perm, axis=AXIS_DP, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.roll(x, 1))

    def test_psum_scalar(self):
        mesh = _mesh(dp=8)
        assert par.psum_scalar(2.5, axis=AXIS_DP, mesh=mesh) == 20.0


class TestMesh:
    def test_default_shape(self):
        s = par.default_mesh_shape(8, tp=2)
        assert s == {"dp": 4, "pp": 1, "tp": 2, "sp": 1, "ep": 1}

    def test_bad_factor(self):
        from mxtpu.base import MXNetError

        with pytest.raises(MXNetError):
            par.default_mesh_shape(8, tp=3)

    def test_mesh_context(self):
        mesh = _mesh(dp=8)
        assert par.current_mesh() is None
        with par.MeshContext(mesh):
            assert par.current_mesh() is mesh
        assert par.current_mesh() is None


def test_zero1_adam_matches_unsharded_and_shards_memory():
    """ZeRO-1 sharded Adam (arxiv 2004.13336): dp=2 chunked update must
    match the dp=1 (unsharded) trajectory exactly — Adam is
    elementwise, so slicing moments across replicas changes memory, not
    math — and each replica must hold 1/dp of every moment."""
    import jax
    import jax.numpy as jnp

    from mxtpu import parallel
    from mxtpu.parallel import transformer as T

    cfg = T.TransformerConfig(vocab=64, d_model=64, n_heads=2,
                              n_layers=2, d_ff=128, max_len=32,
                              dtype="float32")
    rng = np.random.RandomState(0)
    tok_np = rng.randint(0, 64, (4, 32)).astype(np.int32)
    lab_np = rng.randint(0, 64, (4, 32)).astype(np.int32)

    def run(axes, steps=4):
        import numpy as _np

        n = int(_np.prod(list(axes.values())))
        mesh = parallel.create_mesh(axes, devices=jax.devices()[:n])
        params = T.init_params(cfg, mesh, seed=0)
        step, sh = T.make_train_step(cfg, mesh, n_micro=2, lr=1e-2,
                                     optimizer="adam")
        opt = T.init_opt_state(cfg, mesh)
        tok = jax.device_put(jnp.asarray(tok_np), sh["data"])
        lab = jax.device_put(jnp.asarray(lab_np), sh["data"])
        losses = []
        for _ in range(steps):
            params, opt, loss = step(params, opt, tok, lab)
            losses.append(float(loss))
        return losses, params, opt, mesh

    base, _, _, _ = run({"dp": 1, "pp": 1, "tp": 2, "sp": 2, "ep": 1})
    sharded, params, opt, mesh = run(
        {"dp": 2, "pp": 1, "tp": 2, "sp": 2, "ep": 1})
    np.testing.assert_allclose(sharded, base, rtol=2e-4, atol=2e-4)
    assert sharded[-1] < sharded[0]  # it actually optimizes
    # memory: local moment shard is 1/(dp*tp) of the global wq moment
    m = opt["m"]["wq"]
    local = np.prod(m.addressable_shards[0].data.shape)
    assert local * 4 == np.prod(m.shape)
    # each dp rank owns a DISTINCT moment slice: two shards covering
    # different index ranges hold different data after training
    shards = {s.index: np.asarray(s.data) for s in m.addressable_shards}
    assert len(shards) == 4  # dp x tp distinct blocks
    vals = list(shards.values())
    assert any(not np.allclose(vals[0], v) for v in vals[1:])
    # tiny params (LayerNorm vectors) keep replicated state
    assert T._zero1_dims(cfg, mesh)["ln_f"] is None


def _run_remat_losses(remat, axes=None, n_experts=0, T_len=64):
    """Shared harness for the remat parity tests: 3 Adam steps of the
    tiny TransformerLM under the given mesh axes, returns losses."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxtpu import parallel
    from mxtpu.parallel import transformer as T

    axes = axes or {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}
    rng = np.random.RandomState(3)
    tok_np = rng.randint(0, 64, (4, T_len)).astype(np.int32)
    lab_np = rng.randint(0, 64, (4, T_len)).astype(np.int32)
    cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, max_len=T_len,
                              dtype="float32", n_experts=n_experts,
                              remat=remat)
    n = int(np.prod(list(axes.values())))
    mesh = parallel.create_mesh(axes, devices=jax.devices()[:n])
    params = T.init_params(cfg, mesh, seed=0)
    opt = T.init_opt_state(cfg, mesh)
    step, sh = T.make_train_step(cfg, mesh, lr=1e-2, optimizer="adam")
    tok = jax.device_put(jnp.asarray(tok_np), sh["data"])
    lab = jax.device_put(jnp.asarray(lab_np), sh["data"])
    losses = []
    for _ in range(3):
        params, opt, loss = step(params, opt, tok, lab)
        losses.append(float(loss))
    return losses


def test_remat_matches_none_and_rejects_unknown():
    """remat='full'/'dots' must be numerically identical to 'none'
    (same step math, only backward memory strategy differs)."""
    import numpy as np
    import pytest

    from mxtpu.base import MXNetError

    base = _run_remat_losses("none")
    np.testing.assert_allclose(_run_remat_losses("full"), base,
                               rtol=1e-5)
    np.testing.assert_allclose(_run_remat_losses("dots"), base,
                               rtol=1e-5)
    with pytest.raises(MXNetError):
        _run_remat_losses("mirror")


def test_remat_sharded_and_moe_parity():
    """remat must compose with shard_map collectives (tp psums, sp ring,
    ep all_to_all) — jax.checkpoint wraps the scan body INSIDE the
    per-device program, so the recompute replays collectives too."""
    import numpy as np

    axes = {"dp": 2, "pp": 1, "tp": 2, "sp": 2, "ep": 1}
    np.testing.assert_allclose(_run_remat_losses("full", axes),
                               _run_remat_losses("none", axes),
                               rtol=1e-5)
    moe = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 2}
    np.testing.assert_allclose(
        _run_remat_losses("full", moe, n_experts=2),
        _run_remat_losses("none", moe, n_experts=2), rtol=1e-5)


def test_one_step_schedule_matches_the_loop():
    """One stage with one microbatch calls the stage once, with no
    pipeline loop (`_build_loss_fn`); with two microbatches the loop
    runs.  The two paths are the same function of the batch: the same
    losses and the same weights after two SGD steps, with and without
    remat, on one device and on a dp x tp mesh.  (One test, not a case
    each: xdist hands out files by their test counts, largest first,
    and more cases would move this long file up that order and re-deal
    the suite over its workers.)"""
    def run(cfg, mesh, tokens, labels, n_micro):
        params = tfm.init_params(cfg, mesh, seed=3)
        step, sh = tfm.make_train_step(cfg, mesh, n_micro=n_micro,
                                       lr=1e-2)
        t = jax.device_put(tokens, sh["data"])
        l = jax.device_put(labels, sh["data"])
        losses = []
        for _ in range(2):
            params, loss = step(params, t, l)
            losses.append(float(loss))
        return losses, {k: np.asarray(v) for k, v in params.items()}

    for remat, axes in (("none", {}), ("dots", {}),
                        ("dots", {"dp": 2, "tp": 2})):
        cfg = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=4,
                                    n_layers=2, d_ff=32, max_len=64,
                                    dtype="float32", remat=remat)
        mesh = _mesh(**axes)
        tokens, labels = _data(cfg, 8, 16, seed=1)
        straight, w_straight = run(cfg, mesh, tokens, labels, 1)
        looped, w_looped = run(cfg, mesh, tokens, labels, 2)
        np.testing.assert_allclose(straight, looped, rtol=1e-5,
                                   err_msg=str((remat, axes)))
        for k in w_looped:
            np.testing.assert_allclose(
                w_straight[k], w_looped[k], rtol=1e-4, atol=1e-5,
                err_msg=str((remat, axes, k)))


@pytest.mark.parametrize("optimizer", ["adam", "sgd"])
@pytest.mark.parametrize("remat", ["none", "dots", "full"])
def test_fused_train_steps_matches_sequential(remat, optimizer):
    """make_fused_train_steps: K lax.scan-fused steps must produce the
    SAME losses and final params as K sequential make_train_step calls
    (the FusedTrainLoop principle applied to the SPMD transformer),
    under each remat policy and both optimizers (`gpt2m_fused_k8` runs
    "dots" with Adam).  n_micro is 1 and pp is 1 here, so both sides
    take the loss's straight path (no pipeline loop): this is also that
    path's fused against sequential check."""
    from mxtpu import parallel
    from mxtpu.parallel import transformer as T

    K = 3
    rng = np.random.RandomState(5)
    toks_np = rng.randint(0, 64, (K, 4, 32)).astype(np.int32)
    labs_np = rng.randint(0, 64, (K, 4, 32)).astype(np.int32)
    axes = {"dp": 2, "pp": 1, "tp": 2, "sp": 2, "ep": 1}
    cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, max_len=32,
                              dtype="float32", remat=remat)
    mesh = parallel.create_mesh(axes)

    def fresh_state():
        # what a step carries beside the data: the weights, and Adam's
        # moments where the optimizer has any
        state = [T.init_params(cfg, mesh, seed=0)]
        if optimizer == "adam":
            state.append(T.init_opt_state(cfg, mesh))
        return state

    state = fresh_state()
    step, sh = T.make_train_step(cfg, mesh, lr=1e-2, optimizer=optimizer)
    seq = []
    for k in range(K):
        tok = jax.device_put(jnp.asarray(toks_np[k]), sh["data"])
        lab = jax.device_put(jnp.asarray(labs_np[k]), sh["data"])
        *state, loss = step(*state, tok, lab)
        seq.append(float(loss))

    fstep, fsh = T.make_fused_train_steps(cfg, mesh, K, lr=1e-2,
                                          optimizer=optimizer)
    *state2, losses = fstep(
        *fresh_state(),
        jax.device_put(jnp.asarray(toks_np), fsh["data"]),
        jax.device_put(jnp.asarray(labs_np), fsh["data"]))
    np.testing.assert_allclose([float(l) for l in np.asarray(losses)],
                               seq, rtol=1e-5)
    for k in state[0]:
        np.testing.assert_allclose(np.asarray(state2[0][k]),
                                   np.asarray(state[0][k]),
                                   rtol=1e-4, atol=1e-5)
