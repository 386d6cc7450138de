"""Compile-lifecycle subsystem (`mxtpu/compile_cache.py`): persistent
XLA cache, shape-bucketed dispatch, AOT warmup, and donated executor
buffers.  See docs/compile_cache.md for the serving recipe under test.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import autograd, compile_cache, profiler, sym
from mxtpu.gluon import nn
from mxtpu.io.io import DataBatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def pow2_buckets():
    mx.set_bucket_policy("pow2")
    yield
    mx.set_bucket_policy(None)


def _mlp(seed=0):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.initializer.Xavier(rnd_type="uniform"))
    net.hybridize()
    return net


def _convnet():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(4, kernel_size=3, padding=1, activation="relu"),
                nn.BatchNorm(),
                nn.GlobalAvgPool2D(),
                nn.Dense(3))
    net.initialize(mx.initializer.Xavier(rnd_type="uniform"))
    net.hybridize()
    return net


# -- bucket policy math ----------------------------------------------------

def test_bucket_policies():
    assert [compile_cache.bucket_batch(n, "pow2") for n in (1, 2, 3, 5, 9)] \
        == [1, 2, 4, 8, 16]
    assert [compile_cache.bucket_batch(n, "mult:4") for n in (1, 4, 5, 9)] \
        == [4, 4, 8, 12]
    assert [compile_cache.bucket_batch(n, "fixed:2,8") for n in (1, 3, 8, 9)] \
        == [2, 8, 8, 9]  # above the largest fixed bucket: run exact
    assert compile_cache.bucket_batch(5, None) == 5
    with pytest.raises(mx.MXNetError):
        compile_cache.bucket_batch(2, "bogus")


def test_policy_env_and_override(monkeypatch):
    monkeypatch.setenv("MXTPU_SHAPE_BUCKETS", "1")
    assert compile_cache.get_bucket_policy() == "pow2"
    monkeypatch.setenv("MXTPU_SHAPE_BUCKETS", "mult:8")
    assert compile_cache.get_bucket_policy() == "mult:8"
    mx.set_bucket_policy("off")
    assert compile_cache.get_bucket_policy() is None
    mx.set_bucket_policy(None)
    assert compile_cache.get_bucket_policy() == "mult:8"


# -- bucketed dispatch: correctness + program count ------------------------

@pytest.mark.parametrize("make_net,shape", [
    (_mlp, (10,)),
    (_convnet, (3, 8, 8)),
])
def test_bucketed_outputs_match_unbucketed(pow2_buckets, make_net, shape):
    """Padded-and-sliced outputs must match the exact-shape path for
    every ragged batch size (per-sample inference math is unaffected
    by pad rows) — to a few float32 ulps, not bitwise: XLA:CPU (jax
    0.9.0) does not vectorize two batch sizes of one conv identically,
    so the same row can differ in its last bit."""
    net = make_net()
    for b in (1, 2, 3, 5, 7, 8):
        x = mx.nd.array(np.random.RandomState(b).rand(b, *shape)
                        .astype("float32"))
        out = net(x)
        mx.set_bucket_policy("off")
        ref = net(x)
        mx.set_bucket_policy("pow2")
        assert out.shape == ref.shape
        np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                                   rtol=1e-6, atol=1e-7)


def test_bucketing_bounds_program_count(pow2_buckets):
    """Ragged sizes 1..8 compile at most log2 buckets with bucketing on
    (vs one program per distinct size off)."""
    net = _mlp()
    for b in range(1, 9):
        net(mx.nd.array(np.ones((b, 10), "float32")))
    assert net._cached_op._jit_infer._cache_size() <= 4  # 1,2,4,8

    mx.set_bucket_policy("off")
    net2 = _mlp()
    for b in range(1, 9):
        net2(mx.nd.array(np.ones((b, 10), "float32")))
    assert net2._cached_op._jit_infer._cache_size() == 8


def test_bucket_hit_does_not_retrace(pow2_buckets):
    """A new shape inside an existing bucket is a hit, not a trace."""
    net = _mlp()
    net(mx.nd.array(np.ones((5, 10), "float32")))  # traces bucket 8
    n_progs = net._cached_op._jit_infer._cache_size()
    trace0 = profiler.get_stat("cachedop_infer_trace")
    pads0 = profiler.get_stat("cachedop_bucket_pad")
    for b in (6, 7, 8, 5):
        net(mx.nd.array(np.ones((b, 10), "float32")))
    assert net._cached_op._jit_infer._cache_size() == n_progs
    assert profiler.get_stat("cachedop_infer_trace") == trace0
    assert profiler.get_stat("cachedop_bucket_pad") == pads0 + 3  # 6,7,5


def test_per_op_bucket_flag(monkeypatch):
    """hybridize(shape_buckets=...) enables bucketing for one block
    without the global knob."""
    monkeypatch.delenv("MXTPU_SHAPE_BUCKETS", raising=False)
    net = _mlp()
    net.hybridize(shape_buckets="pow2")
    for b in (3, 4, 7, 8):
        out = net(mx.nd.array(np.ones((b, 10), "float32")))
        assert out.shape == (b, 4)
    assert net._cached_op._jit_infer._cache_size() <= 2  # buckets 4, 8


# -- AOT warmup ------------------------------------------------------------

def test_warmup_then_call_compiles_zero_programs():
    net = _mlp()
    net.warmup([(4, 10)])
    assert net._cached_op._jit_infer._cache_size() == 0
    x = mx.nd.array(np.random.RandomState(0).rand(4, 10).astype("float32"))
    aot0 = profiler.get_stat("cachedop_aot_hit")
    out = net(x)
    assert out.shape == (4, 4)
    assert np.isfinite(out.asnumpy()).all()
    # the call dispatched to the warmed executable: the jit's own
    # trace/compile cache was never touched
    assert net._cached_op._jit_infer._cache_size() == 0
    assert profiler.get_stat("cachedop_aot_hit") == aot0 + 1


def test_warmup_matches_jit_path_outputs():
    x = mx.nd.array(np.random.RandomState(1).rand(4, 10).astype("float32"))
    net = _mlp()
    ref = net(x).asnumpy()  # jit path
    net2 = nn.HybridSequential()
    with net2.name_scope():
        net2.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net2.initialize()
    net2.hybridize()
    # copy params so the two nets are identical
    for (n1, p1), (n2, p2) in zip(net.collect_params().items(),
                                  net2.collect_params().items()):
        p2.set_data(p1.data())
    net2.warmup([(4, 10)])
    np.testing.assert_array_equal(net2(x).asnumpy(), ref)


def test_warmup_bucket_set_serves_all_sizes(pow2_buckets):
    """Warm the whole pow2 bucket set, then ragged traffic 1..8 runs
    with ZERO jit compiles — every call is an AOT or bucket hit."""
    net = _mlp()
    net.warmup([[(b, 10)] for b in (1, 2, 4, 8)])
    assert len(net._cached_op._aot_infer) == 4
    for b in range(1, 9):
        out = net(mx.nd.array(np.ones((b, 10), "float32")))
        assert out.shape == (b, 4)
    assert net._cached_op._jit_infer._cache_size() == 0


def test_executor_warmup_and_forward():
    data = sym.Variable("data")
    s = sym.FullyConnected(data=data, num_hidden=8, name="fc")
    s = sym.SoftmaxOutput(data=s, label=sym.Variable("label"), name="sm")
    ex = s.simple_bind(ctx=mx.cpu(), data=(4, 6), label=(4,))
    ex.warmup()
    assert ex._aot_infer is not None and ex._aot_step is not None
    aot0 = profiler.get_stat("executor_aot_hit")
    ex.forward(is_train=False, data=np.ones((4, 6), "float32"))
    assert ex.outputs[0].shape == (4, 8)
    ex.forward(is_train=True, data=np.ones((4, 6), "float32"),
               label=np.zeros(4, "float32"))
    ex.backward()
    assert profiler.get_stat("executor_aot_hit") == aot0 + 2
    g = ex.grad_dict["fc_weight"].asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


# -- executor/module bucketed serving --------------------------------------

def _softmax_net():
    data = sym.Variable("data")
    s = sym.FullyConnected(data=data, num_hidden=8, name="fc")
    s = sym.BatchNorm(data=s, name="bn")
    s = sym.SoftmaxOutput(data=s, label=sym.Variable("label"), name="sm")
    return s


def test_executor_bucketed_forward_matches_exact(pow2_buckets):
    s = _softmax_net()
    ex = s.simple_bind(ctx=mx.cpu(), data=(8, 6), label=(8,))
    rng = np.random.RandomState(0)
    for name in ("fc_weight", "fc_bias", "bn_gamma", "bn_beta"):
        ex.arg_dict[name][:] = rng.rand(*ex.arg_dict[name].shape) \
            .astype("float32")
    for b in (1, 3, 5, 8):
        x = rng.rand(b, 6).astype("float32")
        ex.forward(is_train=False, data=x)
        out = ex.outputs[0]
        assert out.shape == (b, 8)
        # reference: an executor bound EXACTLY at b
        ex_ref = ex.reshape(data=(b, 6), label=(b,))
        ex_ref.forward(is_train=False, data=x)
        np.testing.assert_array_equal(out.asnumpy(),
                                      ex_ref.outputs[0].asnumpy())


def test_module_ragged_serving_skips_rebind(pow2_buckets):
    mod = mx.mod.Module(_softmax_net(), data_names=("data",),
                        label_names=("label",), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 6))], label_shapes=[("label", (8,))])
    mod.init_params()
    first_exec = mod._exec_group.execs[0]
    for b in (3, 5, 8, 2, 7):
        mod.forward(DataBatch(data=[mx.nd.array(np.ones((b, 6), "float32"))],
                              label=None), is_train=False)
        assert mod.get_outputs()[0].shape[0] == b
    assert mod._exec_group.execs[0] is first_exec, \
        "ragged inference batch forced a rebind"


def test_module_ragged_off_still_rebinds():
    mx.set_bucket_policy("off")
    try:
        mod = mx.mod.Module(_softmax_net(), data_names=("data",),
                            label_names=("label",), context=mx.cpu())
        mod.bind(data_shapes=[("data", (8, 6))],
                 label_shapes=[("label", (8,))])
        mod.init_params()
        first_exec = mod._exec_group.execs[0]
        mod.forward(DataBatch(data=[mx.nd.array(np.ones((3, 6), "float32"))],
                              label=None), is_train=False)
        assert mod.get_outputs()[0].shape[0] == 3
        assert mod._exec_group.execs[0] is not first_exec
    finally:
        mx.set_bucket_policy(None)


def test_ragged_serving_uses_this_batchs_labels(pow2_buckets):
    """A label-consuming graph served ragged must see THIS batch's
    labels (padded alongside the data), never the stale bound ones."""
    data, label = sym.Variable("data"), sym.Variable("label")
    loss_s = sym.MakeLoss(sym.square(
        sym.FullyConnected(data=data, num_hidden=1, name="fc")
        - label.reshape((-1, 1))))
    mod = mx.mod.Module(loss_s, data_names=("data",),
                        label_names=("label",), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 4))], label_shapes=[("label", (8,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    for b in (10, 5, 3):
        X = np.random.RandomState(b).rand(b, 4).astype("float32")
        Y = np.full(b, 0.5, "float32")
        mod.forward(DataBatch(data=[mx.nd.array(X)],
                              label=[mx.nd.array(Y)]), is_train=False)
        got = mod.get_outputs()[0].asnumpy()
        mx.set_bucket_policy("off")
        ref = mx.mod.Module(loss_s, data_names=("data",),
                            label_names=("label",), context=mx.cpu())
        ref.bind(data_shapes=[("data", (b, 4))],
                 label_shapes=[("label", (b,))])
        arg_p, aux_p = mod.get_params()
        ref.init_params(arg_params=arg_p, aux_params=aux_p)
        ref.forward(DataBatch(data=[mx.nd.array(X)],
                              label=[mx.nd.array(Y)]), is_train=False)
        mx.set_bucket_policy("pow2")
        np.testing.assert_array_equal(got, ref.get_outputs()[0].asnumpy())


def test_non_batch_major_output_falls_back_exact(pow2_buckets):
    """An output that does NOT carry the batch dim (here: transposed)
    must never be pad-sliced — such shapes run exact instead (decided
    by shape inference, counted as *_bucket_fallback)."""

    class T(nn.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.d = nn.Dense(16)

        def hybrid_forward(self, F, x):
            return F.transpose(self.d(x))

    net = T()
    net.initialize()
    net.hybridize()
    net(mx.nd.array(np.ones((16, 4), "float32")))  # trace
    fb0 = profiler.get_stat("cachedop_bucket_fallback")
    for b in (10, 6):
        x = np.random.RandomState(b).rand(b, 4).astype("float32")
        out = net(mx.nd.array(x))
        assert out.shape == (16, b)
        mx.set_bucket_policy("off")
        ref = net(mx.nd.array(x))
        mx.set_bucket_policy("pow2")
        np.testing.assert_array_equal(out.asnumpy(), ref.asnumpy())
    assert profiler.get_stat("cachedop_bucket_fallback") == fb0 + 2


def test_mixed_leading_dims_rebind_not_ragged(pow2_buckets):
    """Multi-input batches whose inputs disagree on the leading dim
    must take the rebind path, not the ragged dispatch."""
    d0, d1 = sym.Variable("d0"), sym.Variable("d1")
    s = sym.FullyConnected(data=d0 + d1, num_hidden=2, name="fc")
    mod = mx.mod.Module(s, data_names=("d0", "d1"), label_names=(),
                        context=mx.cpu())
    mod.bind(data_shapes=[("d0", (8, 4)), ("d1", (8, 4))],
             label_shapes=None, for_training=False)
    mod.init_params()
    batch = DataBatch(data=[mx.nd.array(np.ones((10, 4), "float32")),
                            mx.nd.array(np.ones((8, 4), "float32"))],
                      label=None)
    assert not mod._exec_group.can_forward_ragged(batch)


# -- buffer donation -------------------------------------------------------

def _train_trajectory(monkeypatch, donate):
    """N fused-executor train steps; returns (grads, aux, outputs)."""
    monkeypatch.setenv("MXTPU_DONATE", "1" if donate else "0")
    ex = _softmax_net().simple_bind(ctx=mx.cpu(), data=(4, 6), label=(4,))
    rng = np.random.RandomState(7)
    for name in ("fc_weight", "fc_bias", "bn_gamma", "bn_beta"):
        ex.arg_dict[name][:] = rng.rand(*ex.arg_dict[name].shape) \
            .astype("float32")
    assert ex._donate == donate
    outs = []
    for i in range(4):
        ex.forward(is_train=True,
                   data=np.random.RandomState(i).rand(4, 6)
                   .astype("float32"),
                   label=np.zeros(4, "float32"))
        ex.backward()
        outs.append(ex.outputs[0].asnumpy())
    grads = {n: g.asnumpy() for n, g in ex.grad_dict.items()
             if g is not None}
    aux = {n: a.asnumpy() for n, a in ex.aux_dict.items()}
    return grads, aux, outs


def test_executor_donation_no_correctness_drift(monkeypatch):
    """Donated aux buffers: gradients, running stats and outputs are
    bit-identical to the non-donated path over multiple steps."""
    g1, a1, o1 = _train_trajectory(monkeypatch, donate=True)
    g0, a0, o0 = _train_trajectory(monkeypatch, donate=False)
    assert set(g1) == set(g0) and set(a1) == set(a0)
    for n in g0:
        np.testing.assert_array_equal(g1[n], g0[n])
    for n in a0:
        np.testing.assert_array_equal(a1[n], a0[n])
    for x, y in zip(o0, o1):
        np.testing.assert_array_equal(x, y)
    # the BN stats really moved (write-back observed the updates)
    assert np.abs(a1["bn_moving_mean"]).sum() > 0


def test_explicit_ograd_backward_after_donated_forward():
    """backward(out_grads) after a default donated forward: the one-time
    vjp rebuild must not read the donated (deleted) aux buffers."""
    ex = _softmax_net().simple_bind(ctx=mx.cpu(), data=(4, 6), label=(4,))
    assert len(ex.aux_arrays) > 0
    ex.forward(is_train=True, data=np.ones((4, 6), "float32"),
               label=np.zeros(4, "float32"))
    og = mx.nd.array(np.ones((4, 8), "float32"))
    ex.backward(out_grads=[og])
    g = ex.grad_dict["fc_weight"].asnumpy()
    assert np.isfinite(g).all()
    # subsequent steps run in split fwd/vjp mode
    ex.forward(is_train=True, data=np.ones((4, 6), "float32"),
               label=np.zeros(4, "float32"))
    ex.backward(out_grads=[og])
    assert np.isfinite(ex.grad_dict["fc_weight"].asnumpy()).all()
    aux = ex.aux_dict["bn_moving_mean"].asnumpy()
    assert np.isfinite(aux).all()


def test_cachedop_train_donation_aux_writeback(monkeypatch):
    """CachedOp._jit_train donation: the non-recording training path
    still publishes updated BN running stats, identically to the
    non-donated path."""

    def run(donate):
        monkeypatch.setenv("MXTPU_DONATE", "1" if donate else "0")
        np.random.seed(0)  # identical init for the two nets under compare
        mx.random.seed(0)
        net = _convnet()
        x = mx.nd.array(np.random.RandomState(3).rand(2, 3, 8, 8)
                        .astype("float32"))
        with autograd.train_mode():
            for _ in range(3):
                net(x)
        # key by suffix: the two nets get distinct auto-prefixes
        stats = {n.split("_", 1)[1]: p.data().asnumpy() for n, p in
                 net.collect_params(".*running.*|.*moving.*").items()}
        assert stats, "convnet has no BN running stats?"
        return stats

    s1 = run(True)
    s0 = run(False)
    for n in s0:
        assert np.abs(s0[n]).sum() > 0  # stats actually updated
        np.testing.assert_allclose(s1[n], s0[n], rtol=0, atol=0)


# -- persistent compile cache ----------------------------------------------

# One process that compiles through every cache-touching path (a warmed
# hybridized net, then a diagnostic mx.inspect compile) and reports where
# the cache sits and every jax.config.update made on the way.
_CACHE_SCRIPT = r"""
import json, os
import jax
updates = []
_update = jax.config.update
def spy(name, val):
    updates.append(name)
    return _update(name, val)
jax.config.update = spy
import numpy as np
import mxtpu as mx
from mxtpu import compile_cache
from mxtpu.gluon import nn
mx.random.seed(0)
net = nn.HybridSequential()
with net.name_scope():
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
net.initialize()
net.hybridize()
net.warmup([(4, 16)])
out = net(mx.nd.array(np.ones((4, 16), "float32")))
rec = mx.inspect.find(net._cached_op._insp.name)
assert "dense" in rec.latest_sig().hlo_text()      # diagnostic compile
assert compile_cache.persistent_cache_dir() == \
    jax.config.jax_compilation_cache_dir
print(json.dumps({"dir": compile_cache.persistent_cache_dir(),
                  "updates": updates,
                  "sum": float(out.asnumpy().sum())}))
"""


def _run_cache_script(**env_over):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("MXTPU_COMPILE_CACHE", None)
    env.update(env_over)
    r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_goes_where_jax_compilation_cache_dir_says(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: whoever runs the program placed
    the cache, so that directory is used — populated by a first process,
    read by a second — and NO code path sets a directory of its own
    (neither `import mxtpu` nor the inspect-time uncached compile)."""
    cache = str(tmp_path / "placed")
    r1 = _run_cache_script(JAX_COMPILATION_CACHE_DIR=cache)
    assert r1["dir"] == cache
    assert "jax_compilation_cache_dir" not in r1["updates"]
    assert os.listdir(cache), "persistent cache wrote no entries"
    r2 = _run_cache_script(JAX_COMPILATION_CACHE_DIR=cache)
    assert r2["sum"] == r1["sum"]


def test_cache_defaults_to_the_checkout_and_can_be_switched_off():
    """Variable unset: <checkout>/.jax_cache, derived from the package
    path (the child's cwd is elsewhere).  MXTPU_COMPILE_CACHE=0 is the
    off switch."""
    r = _run_cache_script()
    assert r["dir"] == os.path.join(REPO, ".jax_cache")
    assert _run_cache_script(MXTPU_COMPILE_CACHE="0")["dir"] is None


def test_bypass_scope_keeps_the_directory():
    """`persistent_cache_bypassed` turns the cache off for diagnostic
    compiles and back on, and never touches the directory."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.persistent_cache_dir() == before
    with compile_cache.persistent_cache_bypassed():
        assert compile_cache.persistent_cache_dir() is None
        assert jax.config.jax_compilation_cache_dir == before
    assert compile_cache.persistent_cache_dir() == before


def test_persistent_cache_writes_are_atomic(tmp_path):
    """`import mxtpu` patches jax's LRUCache.put to write temp +
    os.replace: jax 0.9.0 still writes entries with a bare write_bytes,
    and a torn entry (concurrent reader, or SIGKILL mid-write) is never
    overwritten, so it would stay a miss for good.  Readers must only
    ever observe a complete entry."""
    from jax._src import lru_cache as _lru

    assert getattr(_lru.LRUCache.put, "_mxtpu_atomic", False), \
        "atomic-write patch did not install on this jax"
    probe = _lru.LRUCache(str(tmp_path / "probe"), max_size=-1)
    val = b"v" * (1 << 20)
    import threading

    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            for i in range(8):
                got = probe.get("k%d" % i)
                if got is not None and got != val:
                    torn.append((i, len(got)))

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    for i in range(8):
        probe.put("k%d" % i, val)
    stop.set()
    t.join(5)
    assert not t.is_alive()
    assert not torn, "reader observed torn cache entries: %s" % torn
    # no .tmp litter left behind on the happy path
    assert not [f for f in os.listdir(str(tmp_path / "probe"))
                if f.endswith(".tmp")]


# -- thread safety (serving workers share executables) ---------------------

def test_cachedop_threaded_dispatch_bitwise_zero_extra_retraces(
        pow2_buckets):
    """N serving threads hammering ONE CachedOp concurrently: outputs
    stay bitwise-identical to a serial dispatch, and the retrace
    counters show EXACTLY one trace per bucket — a check-then-act race
    on the seen-signature set (two threads both claiming a brand-new
    bucket signature) would inflate them and trip
    tools/check_retrace.py on a healthy server."""
    import threading

    net = _mlp(seed=4)
    op = net._cached_op  # not built until first call/trace
    x0 = mx.nd.array(np.zeros((1, 10), "float32"))
    net(x0)  # build the cache; bucket-1 program traced here
    op = net._cached_op
    t0 = profiler.get_stat("cachedop_infer_trace")
    rng = np.random.RandomState(0)
    xs = {n: rng.rand(n, 10).astype("float32") for n in range(1, 9)}
    expected = {}  # serial reference AFTER threads (order-free check)

    barrier = threading.Barrier(8)
    failures = []

    def worker(tid):
        barrier.wait()  # maximize signature-race pressure
        for it in range(12):
            n = 1 + (tid + it) % 8
            out = net(mx.nd.array(xs[n])).asnumpy()
            with lock:
                got.setdefault(n, []).append(out)

    lock = threading.Lock()
    got = {}
    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for n, x in xs.items():
        expected[n] = net(mx.nd.array(x)).asnumpy()
    for n, outs in got.items():
        for out in outs:
            if not np.array_equal(out, expected[n]):
                failures.append(n)
    assert not failures, "non-deterministic outputs for sizes %s" \
        % sorted(set(failures))
    # pow2 buckets for 1..8 = {1, 2, 4, 8}; bucket 1 traced before the
    # threads started, so AT MOST 3 new traces — and not one more
    traces = profiler.get_stat("cachedop_infer_trace") - t0
    assert traces <= 3, ("concurrent dispatch inflated retraces: %d "
                         "new traces for 3 new buckets" % traces)
    # registry bookkeeping reconciles too (inspect.track_compile under
    # the signature lock): hits + traces == dispatches
    rec = op._insp
    dispatches = 8 * 12 + 1 + len(xs)  # threads + build + reference
    assert rec.compiles + rec.hits == dispatches
