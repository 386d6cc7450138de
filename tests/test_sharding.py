"""mx.shard: the sharding-aware distributed backbone.

ZeRO-1 contract (arXiv 2004.13336): sharding the optimizer state and
update across data-parallel replicas changes MEMORY, not math — every
trajectory here must match its replicated twin (bitwise on the
host-replica engine, float-noise on the GSPMD carry), while each
replica holds ~1/N of the state bytes.  Reshard (arXiv 2112.01075)
moves params/state between two plans' layouts.  The end-to-end 50-step
guard is `tools/check_sharding.py` (tier-1, see tests/test_tools.py).
"""
import contextlib
import os
import tempfile

import numpy as np
import pytest

import jax

import mxtpu as mx
from mxtpu import sym
from mxtpu.io.io import DataBatch, NDArrayIter
from mxtpu.sharding import ShardingPlan, ZeRO1Updater, zero1 as z1


def _mlp():
    x = sym.Variable("data")
    h = sym.FullyConnected(data=x, num_hidden=64, name="fc1")
    h = sym.Activation(data=h, act_type="relu")
    h = sym.FullyConnected(data=h, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(data=h, label=sym.Variable("softmax_label"),
                             name="softmax")


def _blobs(n=128, d=32, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, d).astype("float32"),
            rng.randint(0, 4, n).astype("float32"))


def _train_module(plan, n_ctx, steps=6, optimizer="adam", kvstore="device",
                  seed=7, net=None, checkpoint=None):
    """Train a Module for `steps` epochs over the blob set; returns
    (params dict, module)."""
    x, y = _blobs()
    scope = plan.activate() if plan is not None \
        else contextlib.nullcontext()
    with scope:
        it = NDArrayIter(x, y, batch_size=32, label_name="softmax_label")
        mod = mx.mod.Module(net or _mlp(),
                            context=[mx.cpu(i) for i in range(n_ctx)])
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mx.random.seed(seed)
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                           optimizer_params={"learning_rate": 0.01})
        for _ in range(steps):
            it.reset()
            for b in it:
                mod.forward(b, is_train=True)
                mod.backward()
                mod.update()
        p, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in p.items()}, mod


# ---------------------------------------------------------------------------
# ShardingPlan API
# ---------------------------------------------------------------------------

class TestPlan:
    def test_shard_dim_first_free_divisible(self):
        plan = ShardingPlan(num_shards=4, min_shard_elems=16)
        assert plan.shard_dim("w", (64, 32)) == 0
        assert plan.shard_dim("w", (5, 32)) == 1   # 5 % 4 != 0
        assert plan.shard_dim("w", (5, 7)) is None
        assert plan.shard_dim("tiny", (8,)) is None  # < min elems

    def test_shard_dim_respects_model_spec(self):
        from jax.sharding import PartitionSpec as P

        plan = ShardingPlan(num_shards=4, min_shard_elems=16,
                            param_specs={"w": P("tp", None)})
        # dim 0 is claimed by tensor parallelism -> state shards dim 1
        assert plan.shard_dim("w", (64, 32)) == 1

    def test_shard_slice_partitions_exactly(self):
        plan = ShardingPlan(num_shards=4)
        rows = [plan.shard_slice((8, 3), 0, r)[0] for r in range(4)]
        assert [(s.start, s.stop) for s in rows] == \
            [(0, 2), (2, 4), (4, 6), (6, 8)]

    def test_opt_state_spec_adds_data_axis(self):
        plan = ShardingPlan(num_shards=4, min_shard_elems=16)
        spec = plan.opt_state_spec("w", (64, 32))
        assert tuple(spec) == ("dp", None)
        assert tuple(plan.opt_state_spec("tiny", (8,))) == (None,)

    def test_resolved_pins_and_conflicts(self):
        plan = ShardingPlan()
        assert not plan.resolved_explicitly
        p4 = plan.resolved(4)
        assert p4.num_shards == 4
        with pytest.raises(mx.MXNetError):
            p4.resolved(2)

    def test_scope_stack_and_env(self, monkeypatch):
        from mxtpu.sharding import current_plan, plan_scope

        assert current_plan() is None
        plan = ShardingPlan(num_shards=2)
        with plan.activate():
            assert current_plan() is plan
            with plan_scope(None):
                assert current_plan() is None
            assert current_plan() is plan
        assert current_plan() is None
        monkeypatch.setenv("MXTPU_SHARD", "zero1")
        env_plan = current_plan()
        assert env_plan is not None and not env_plan.resolved_explicitly

    def test_describe_mentions_mode_and_n(self):
        d = ShardingPlan(num_shards=4).describe()
        assert "zero1" in d and "n=4" in d


# ---------------------------------------------------------------------------
# ZeRO-1 host-replica engine (Module path)
# ---------------------------------------------------------------------------

class TestModuleZeRO1:
    def test_bitwise_parity_and_state_fraction(self):
        pr, mr = _train_module(None, 4)
        plan = ShardingPlan(min_shard_elems=64)
        ps, ms = _train_module(plan, 4)
        for k in pr:
            np.testing.assert_array_equal(pr[k], ps[k], err_msg=k)
        upd = ms._updater
        assert isinstance(upd, ZeRO1Updater)
        # fc weights shard (dim 0), fc2_bias (4 elems) stays replicated
        assert upd.shard_dims[0] == 0
        assert None in upd.shard_dims.values()
        full = z1.tree_nbytes(upd._gather_full())
        per_replica = upd.per_replica_state_nbytes()
        assert per_replica < full / 4 * 1.35
        assert per_replica >= full / 4 * 0.95

    def test_counters_and_provenance(self):
        from mxtpu import profiler, telemetry

        before_ag = profiler.get_stat("allgather_bytes")
        before_rs = profiler.get_stat("reduce_scatter_bytes")
        plan = ShardingPlan(min_shard_elems=64)
        _, ms = _train_module(plan, 4, steps=2)
        assert profiler.get_stat("allgather_bytes") > before_ag
        assert profiler.get_stat("reduce_scatter_bytes") > before_rs
        # the plan is visible on the bound program's inspect record
        rec = ms._exec_group.execs[0]._insp
        assert rec.sharding and "zero1:n=4" in rec.sharding
        assert rec.pass_report is not None
        shard_entries = [p for p in rec.pass_report["passes"]
                         if p["pass"] == "shard"]
        # bind resolved the ambient (unpinned) plan to the 4 replicas
        assert shard_entries and "n=4" in shard_entries[0]["plan"]
        d = rec.as_dict(analyze=False)
        assert "zero1:n=4" in d["sharding"]
        # ... and on the telemetry compile events
        evs = [e for e in telemetry.events("compile")
               if e.get("sharding")]
        assert any("zero1:n=4" in e["sharding"] for e in evs)

    def test_sgd_momentum_parity(self):
        pr, _ = _train_module(None, 4, optimizer="sgd")
        ps, ms = _train_module(ShardingPlan(min_shard_elems=64), 4,
                               optimizer="sgd")
        for k in pr:
            np.testing.assert_array_equal(pr[k], ps[k], err_msg=k)

    def test_incompatible_optimizer_keeps_replicated_path(self):
        plan = ShardingPlan(min_shard_elems=64)
        _, mod = _train_module(plan, 2, steps=1, optimizer="nadam")
        assert not isinstance(mod._updater, ZeRO1Updater)

    def test_single_context_keeps_plain_updater(self):
        plan = ShardingPlan(min_shard_elems=64)
        _, mod = _train_module(plan, 1, steps=1)
        assert not isinstance(mod._updater, ZeRO1Updater)


def test_dense_then_sparse_grad_regathers_state():
    """A row_sparse grad arriving AFTER dense steps sharded a param's
    state must re-gather the shards and continue replicated — not hand
    the optimizer a shard list (review regression)."""
    from mxtpu import optimizer as opt_mod
    from mxtpu.ndarray import sparse as sp

    plan = ShardingPlan(num_shards=4, min_shard_elems=16)
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    upd = ZeRO1Updater(opt, plan, idx2name={0: "emb_weight"})
    w = mx.nd.array(np.ones((8, 16), "float32"))
    dense = mx.nd.array(np.full((8, 16), 0.5, "float32"))
    upd.update_replicas([(0, [dense], [w])])
    assert upd.shard_dims[0] == 0 and isinstance(upd.states[0], list)
    rsp = sp.row_sparse_array(
        (np.ones((2, 16), "float32"), np.array([1, 5])), shape=(8, 16))
    upd.update_replicas([(0, [rsp], [w])])   # must not raise
    assert upd.shard_dims[0] is None
    assert not isinstance(upd.states[0], list)


def test_batched_rank_update_bitwise_matches_per_param(monkeypatch):
    """The fused one-XLA-call-per-rank ZeRO-1 update (optimizer
    `fused_update_multi` over every batchable param's slices at once)
    must be BITWISE identical to the eager per-(param,rank) slice path
    it replaced — and must actually engage on the adam/dense path
    (the `zero1_fused_rank_updates` counter ticks)."""
    from mxtpu import profiler

    plan = ShardingPlan(min_shard_elems=64)
    before = profiler.get_stat("zero1_fused_rank_updates")
    p_batched, ms = _train_module(plan, 4, steps=3)
    assert isinstance(ms._updater, ZeRO1Updater)
    assert profiler.get_stat("zero1_fused_rank_updates") > before

    # force the pre-existing per-param fallback and retrain identically
    monkeypatch.setattr(ZeRO1Updater, "_update_batched",
                        lambda self, items, prof: False)
    p_fallback, _ = _train_module(plan, 4, steps=3)
    for k in p_batched:
        np.testing.assert_array_equal(p_batched[k], p_fallback[k],
                                      err_msg=k)


# ---------------------------------------------------------------------------
# checkpoint round-trip (sharded state across replica counts)
# ---------------------------------------------------------------------------

class TestCheckpointRoundTrip:
    def _resume(self, prefix, n_ctx, steps):
        """load_latest under a fresh plan on `n_ctx` replicas, train
        `steps` more epochs; returns params."""
        x, y = _blobs()
        plan = ShardingPlan(min_shard_elems=64)
        with plan.activate():
            found = mx.mod.Module.load_latest(
                prefix, load_optimizer_states=True,
                context=[mx.cpu(i) for i in range(n_ctx)])
            assert found is not None
            mod, _epoch = found
            it = NDArrayIter(x, y, batch_size=32,
                             label_name="softmax_label")
            mod.bind(data_shapes=it.provide_data,
                     label_shapes=it.provide_label)
            mod.init_optimizer(kvstore="device", optimizer="adam",
                               optimizer_params={"learning_rate": 0.01})
            for _ in range(steps):
                it.reset()
                for b in it:
                    mod.forward(b, is_train=True)
                    mod.backward()
                    mod.update()
            p, _ = mod.get_params()
            return {k: v.asnumpy() for k, v in p.items()}

    def test_sharded_save_resumes_across_replica_counts(self):
        """Save sharded 4-replica optimizer state; resuming on 2 (and
        1) replicas must continue the EXACT trajectory — states are
        gathered at save and re-sharded at load."""
        x, y = _blobs()
        plan = ShardingPlan(min_shard_elems=64)
        _, mod = _train_module(plan, 4, steps=3)
        with tempfile.TemporaryDirectory() as td:
            prefix = os.path.join(td, "ckpt")
            with plan.activate():
                mod.save_checkpoint(prefix, 3, save_optimizer_states=True)
            got2 = self._resume(prefix, 2, steps=3)
            got1 = self._resume(prefix, 1, steps=3)
        # ground truth: the uninterrupted 6-epoch sharded run
        ref, _ = _train_module(ShardingPlan(min_shard_elems=64), 4,
                               steps=6)
        for k in ref:
            np.testing.assert_allclose(got2[k], ref[k], rtol=1e-6,
                                       atol=1e-7, err_msg=k + " n=2")
            # n=1 computes each batch grad in ONE reduction where the
            # 4-replica runs summed 4 partials — reassociation noise
            # only, the optimizer state/counters carried over exactly
            np.testing.assert_allclose(got1[k], ref[k], rtol=1e-4,
                                       atol=1e-5, err_msg=k + " n=1")

    def test_wire_format_loads_into_plain_updater(self):
        """A ZeRO1Updater states blob is the plain Updater wire format
        (gathered full states) — interchangeable both ways."""
        from mxtpu import optimizer as opt_mod

        plan = ShardingPlan(min_shard_elems=64)
        _, mod = _train_module(plan, 4, steps=2)
        blob = mod._updater.get_states()
        plain = opt_mod.get_updater(
            opt_mod.create("adam", learning_rate=0.01))
        plain.set_states(blob)
        assert set(plain.states) == set(mod._updater.states)
        # and back: plain -> sharded re-shards
        z = ZeRO1Updater(opt_mod.create("adam", learning_rate=0.01),
                         plan.resolved(4),
                         idx2name=dict(mod._updater.idx2name))
        z.set_states(plain.get_states())
        g1 = mod._updater._gather_full()
        g2 = z._gather_full()
        for idx in g1:
            if g1[idx] is None:
                continue
            for a, b in zip(g1[idx], g2[idx]):
                np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())


# ---------------------------------------------------------------------------
# gluon Trainer path
# ---------------------------------------------------------------------------

class TestTrainerZeRO1:
    def _run(self, plan, n_ctx, steps=6):
        from mxtpu import autograd, gluon
        from mxtpu.gluon import nn

        rng = np.random.RandomState(1)
        X = rng.rand(64, 16).astype("float32")
        Y = rng.rand(64, 1).astype("float32")
        ctxs = [mx.cpu(i) for i in range(n_ctx)]
        net = nn.Dense(1, in_units=16)
        mx.random.seed(3)
        net.initialize(ctx=ctxs)
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01},
                           sharding_plan=plan)
        loss = gluon.loss.L2Loss()
        bs = 64 // n_ctx
        for _ in range(steps):
            with autograd.record():
                for k, c in enumerate(ctxs):
                    xb = mx.nd.array(X[k * bs:(k + 1) * bs], ctx=c)
                    yb = mx.nd.array(Y[k * bs:(k + 1) * bs], ctx=c)
                    loss(net(xb), yb).backward()
            tr.step(64)
        return ([v.data(ctxs[0]).asnumpy()
                 for _, v in sorted(net.collect_params().items())],
                tr)

    def test_matches_single_device_semantics(self):
        """Sharded multi-replica Trainer reproduces the single-device
        trajectory (one count bump per wall step) to float-sum noise —
        the grad merge is the only reassociation."""
        p1, _ = self._run(None, 1)
        ps, tr = self._run(ShardingPlan(min_shard_elems=8), 4)
        assert tr._zero1 is not None
        for a, b in zip(p1, ps):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)

    def test_trainer_state_roundtrip(self, tmp_path):
        _, tr = self._run(ShardingPlan(min_shard_elems=8), 4, steps=2)
        f = str(tmp_path / "trainer.states")
        tr.save_states(f)
        _, tr2 = self._run(ShardingPlan(min_shard_elems=8), 2, steps=0)
        tr2.load_states(f)
        g1 = tr._zero1._gather_full()
        g2 = tr2._zero1._gather_full()
        for idx in g1:
            for a, b in zip(g1[idx], g2[idx]):
                np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())

    def test_explicit_plan_argument_wins(self):
        from mxtpu import gluon
        from mxtpu.gluon import nn

        net = nn.Dense(1, in_units=4)
        net.initialize(ctx=[mx.cpu(0), mx.cpu(1)])
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 0.01},
                           sharding_plan=ShardingPlan(min_shard_elems=1))
        tr._init_kvstore()
        assert tr._zero1 is not None and tr._zero1.n == 2


# ---------------------------------------------------------------------------
# FusedTrainLoop sharded scanned carry (GSPMD)
# ---------------------------------------------------------------------------

class TestFusedCarry:
    def _run(self, plan):
        from mxtpu.fused_train import FusedTrainLoop

        rng = np.random.RandomState(5)
        batches = [DataBatch(
            data=[mx.nd.array(rng.rand(8, 32).astype("float32"))],
            label=[mx.nd.array(rng.randint(0, 4, 8).astype("float32"))])
            for _ in range(4)]
        scope = plan.activate() if plan is not None \
            else contextlib.nullcontext()
        with scope:
            mod = mx.mod.Module(_mlp(), data_names=("data",),
                                label_names=("softmax_label",))
            mod.bind(data_shapes=[("data", (8, 32))],
                     label_shapes=[("softmax_label", (8,))])
            mx.random.seed(11)
            mod.init_params(initializer=mx.init.Xavier())
            mod.init_optimizer(kvstore=None, optimizer="adam",
                               optimizer_params={"learning_rate": 0.01})
            loop = FusedTrainLoop(mod, steps_per_program=2)
            for i in (0, 2):
                loop.run(batches[i:i + 2])
            loop.finalize()
            p, _ = mod.get_params()
            return ({k: v.asnumpy() for k, v in p.items()},
                    loop.sharding_info())

    def test_sharded_carry_parity_and_memory(self):
        from mxtpu import parallel

        pr, info_r = self._run(None)
        assert info_r is None
        mesh = parallel.create_mesh({"dp": 4},
                                    devices=jax.devices()[:4])
        ps, info = self._run(ShardingPlan(mesh=mesh, min_shard_elems=64))
        # float tolerance, not bitwise: GSPMD's sharded program sums
        # in another order than the unsharded one on XLA:CPU (jax
        # 0.9.0), and Adam's m/sqrt(v) turns a last-bit gradient
        # difference into ~1e-6 of a weight within these four steps
        for k in pr:
            np.testing.assert_allclose(pr[k], ps[k], rtol=1e-5,
                                       atol=1e-5, err_msg=k)
        assert info is not None and "zero1:n=4" in info["plan"]
        per_dev = list(info["state_bytes_per_device"].values())
        assert len(per_dev) == 4
        total = info["state_total_bytes"]
        # every device holds ~1/4 (sharded moments) + tiny replicated
        for b in per_dev:
            assert b < total / 4 * 1.35


# ---------------------------------------------------------------------------
# reshard primitive
# ---------------------------------------------------------------------------

class TestReshard:
    def test_values_preserved_and_counters(self):
        from mxtpu import parallel, profiler, telemetry
        from mxtpu.sharding import reshard

        mesh = parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
        train_plan = ShardingPlan(mesh=mesh, min_shard_elems=16)
        serve_plan = ShardingPlan(num_shards=1)  # one-host serving
        rng = np.random.RandomState(0)
        tree = {"w": jax.numpy.asarray(rng.rand(64, 32)
                                       .astype("float32")),
                "b": jax.numpy.asarray(rng.rand(8).astype("float32"))}
        before = profiler.get_stat("reshard_bytes")
        # host -> ZeRO-1 opt-state layout on the mesh
        sharded = reshard(tree, train_plan, kind="opt_state",
                          label="test")
        assert len(sharded["w"].addressable_shards) == 4
        local = sharded["w"].addressable_shards[0].data
        assert int(np.prod(local.shape)) * 4 == sharded["w"].nbytes // 4
        # ... and back to the serve layout
        back = reshard(sharded, serve_plan, plan_a=train_plan,
                       label="test")
        for k in tree:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]))
        assert profiler.get_stat("reshard_bytes") > before
        evs = telemetry.events("reshard")
        assert evs and evs[-1]["plan_to"] == serve_plan.describe()
        rec = mx.inspect.find("reshard:test")
        assert rec is not None and rec.compiles >= 1


# ---------------------------------------------------------------------------
# kvstore=tpu rides the plan
# ---------------------------------------------------------------------------

class TestKVStorePlan:
    def test_tpu_kvstore_resolves_mesh_and_axis_from_plan(self):
        from mxtpu import kvstore, parallel

        mesh = parallel.create_mesh({"dp": 4}, devices=jax.devices()[:4])
        plan = ShardingPlan(mesh=mesh)
        kv = kvstore.create("tpu")
        vals = [mx.nd.array(np.full((4,), float(i + 1), "float32"),
                            ctx=mx.cpu(i)) for i in range(4)]
        kv.init("w", vals[0])
        with plan.activate():   # no MeshContext: the plan supplies it
            kv.push("w", vals)
        assert kv.last_reduce_path == "psum"
        out = mx.nd.zeros((4,))
        kv.pull("w", out=out)
        np.testing.assert_allclose(out.asnumpy(), np.full((4,), 10.0))
