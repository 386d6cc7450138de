"""mx.passes: symbol-level graph-rewrite pass framework.

Every pass must be output-identical against the unoptimized graph —
bitwise on deterministic graphs (including RNG-consuming ones: the
stable per-node ``__rng_id__`` means DCE/CSE cannot reseed dropout) —
across the Executor, CachedOp and FusedTrainLoop dispatch paths,
with provenance recorded on `mx.inspect` program records and
telemetry ``compile`` events.  The end-to-end train-trajectory guard
lives in `tools/check_passes.py` (see tests/test_tools.py)."""
import numpy as np
import pytest

import mxtpu as mx
import mxtpu.passes as P
from mxtpu import autograd, control_flow as cf, sym
from mxtpu.symbol.symbol import _topo_order


def _nodes(s):
    return _topo_order(s._outputs)


def _op_names(s):
    return [n.op.name for n in _nodes(s) if not n.is_variable]


# ---------------------------------------------------------------------------
# spec parsing / config
# ---------------------------------------------------------------------------

def test_parse_spec_grammar():
    assert P.parse_spec("default") == ("dce", "fold", "cse", "fuse")
    assert P.parse_spec("off") == ()
    assert P.parse_spec("0") == ()
    # canonical order enforced regardless of spelling order
    assert P.parse_spec("fuse,dce") == ("dce", "fuse")
    assert P.parse_spec("default,-fuse") == ("dce", "fold", "cse")
    assert P.parse_spec(["cse", "dce"]) == ("dce", "cse")


def test_parse_spec_unknown_pass_raises():
    with pytest.raises(mx.MXNetError, match="unknown graph pass"):
        P.parse_spec("dce,flod")


def test_scope_overrides_env(monkeypatch):
    monkeypatch.setenv("MXTPU_PASSES", "dce")
    assert P.current_spec() == ("dce",)
    with P.scope("off"):
        assert P.current_spec() == ()
    assert P.current_spec() == ("dce",)


# ---------------------------------------------------------------------------
# individual passes
# ---------------------------------------------------------------------------

def test_dce_removes_interior_identity_keeps_head():
    x = sym.Variable("data")
    h = sym.identity(x * 2.0, name="mid")
    out = sym.identity(h + 1.0, name="head")
    opt, rep = out.optimize(passes="dce", return_report=True)
    assert rep["passes"][0]["identity_removed"] == 1
    assert "_copy" in _op_names(opt)  # the head copy survives
    assert sum(1 for n in _op_names(opt) if n == "_copy") == 1
    assert opt.list_outputs() == out.list_outputs()


def test_cse_merges_duplicate_subexpressions():
    x = sym.Variable("data")
    a = sym.exp(x * 0.5)
    b = sym.exp(x * 0.5)
    out = a + b
    opt, rep = out.optimize(passes="cse", return_report=True)
    assert rep["passes"][0]["cse_merged"] == 2  # _mul_scalar and exp
    assert _op_names(opt).count("exp") == 1


def test_cse_and_fold_preserve_head_output_names():
    """A head that duplicates an interior expression (cse) or is
    constant (fold) must keep its name — Symbol.optimize users read
    list_outputs()."""
    x = sym.Variable("data")
    a = sym.exp(x, name="inner")
    dup_head = sym.exp(x, name="dup_head")
    const_head = sym._arange(start=0, stop=4, name="const_head") * 2.0
    g = sym.Group([a + dup_head, dup_head, const_head])
    opt = g.optimize(passes="default")
    assert opt.list_outputs() == g.list_outputs()


def test_cse_never_merges_rng_ops():
    x = sym.Variable("data")
    out = sym.Dropout(x, p=0.5, name="d1") + sym.Dropout(x, p=0.5,
                                                         name="d2")
    opt, _ = out.optimize(passes="cse", return_report=True)
    assert _op_names(opt).count("Dropout") == 2


def test_fold_evaluates_constant_subgraph():
    x = sym.Variable("data")
    c = sym._arange(start=0, stop=4, name="ar") * 2.0 + 1.0
    out = sym.broadcast_add(x, c)
    opt, rep = out.optimize(passes="fold", return_report=True)
    assert rep["passes"][0]["folded"] == 1
    names = _op_names(opt)
    assert "_arange" not in names and "_mul_scalar" not in names
    assert "_pass_const" in names
    ex = opt.bind(mx.cpu(), {"data": mx.nd.zeros((2, 4))})
    np.testing.assert_array_equal(ex.forward()[0].asnumpy(),
                                  [[1, 3, 5, 7], [1, 3, 5, 7]])


def test_fold_respects_size_cap(monkeypatch):
    monkeypatch.setenv("MXTPU_FOLD_MAX_BYTES", "8")
    x = sym.Variable("data")
    out = sym.broadcast_add(x, sym._arange(start=0, stop=64, name="ar"))
    opt, rep = out.optimize(passes="fold", return_report=True)
    assert rep["passes"][0]["folded"] == 0
    assert "_arange" in _op_names(opt)


def test_folded_constants_cse_by_value():
    x = sym.Variable("data")
    out = sym.broadcast_add(
        sym.broadcast_add(x, sym._arange(start=0, stop=4, name="a1")),
        sym._arange(start=0, stop=4, name="a2"))
    opt, _ = out.optimize(passes="fold,cse", return_report=True)
    assert _op_names(opt).count("_pass_const") == 1


def test_fuse_groups_elementwise_chain():
    x = sym.Variable("data")
    w = sym.Variable("w")
    h = sym.FullyConnected(data=x, weight=w, no_bias=True,
                           num_hidden=4, name="fc")
    out = sym.tanh(sym.exp(h * 0.5) + 1.0, name="tail")
    opt, rep = out.optimize(passes="fuse", return_report=True)
    st = rep["passes"][0]
    assert st["chains"] == 1 and st["nodes_fused"] == 3
    names = _op_names(opt)
    assert names.count("_fused_elemwise") == 1
    assert "exp" not in names and "tanh" not in names
    # attribution: the fused node takes the chain's terminal name and
    # lists its members
    (fused,) = [n for n in _nodes(opt)
                if not n.is_variable and n.op.name == "_fused_elemwise"]
    assert fused.name == "tail"
    assert "tail" in fused.ext_attrs["__fused__"]


def test_fuse_stops_at_multi_consumer():
    x = sym.Variable("data")
    e = sym.exp(x)              # consumed twice -> not an intermediate
    out = sym.tanh(e) + sym.sin(e)
    opt, _ = out.optimize(passes="fuse", return_report=True)
    assert "exp" in _op_names(opt)


# ---------------------------------------------------------------------------
# parity across dispatch paths (bitwise, incl. RNG + BN aux)
# ---------------------------------------------------------------------------

def _probe_net():
    x = sym.Variable("data")
    h = sym.FullyConnected(data=x, num_hidden=8, name="fc1")
    h = sym.BatchNorm(data=h, name="bn1")
    h = sym.Activation(data=h, act_type="relu", name="r1")
    h = sym.Dropout(data=h, p=0.5, name="do1")
    h = sym.exp(h * 0.1) + sym.exp(h * 0.1)  # cse + fuse fodder
    h = sym.broadcast_add(h, sym._arange(start=0, stop=8, name="ar")
                          * 0.01)  # fold fodder
    return sym.FullyConnected(data=h, num_hidden=4, name="fc2")


def _fill_args(ex, seed=3):
    rng = np.random.RandomState(seed)
    for k, a in sorted(ex.arg_dict.items()):
        if k != "data":
            a[:] = mx.nd.array(rng.rand(*a.shape).astype("float32"))


def test_executor_train_parity_bitwise():
    net = _probe_net()
    res = {}
    for spec in ("off", "default"):
        with P.scope(spec):
            ex = net.simple_bind(mx.cpu(), data=(8, 16), grad_req="write")
        _fill_args(ex)
        x = mx.nd.array(np.random.RandomState(0).rand(8, 16)
                        .astype("float32"))
        mx.random.seed(42)
        out = ex.forward(is_train=True, data=x)[0].asnumpy()
        ex.backward()
        res[spec] = (out, ex.grad_dict["fc1_weight"].asnumpy(),
                     ex.aux_dict["bn1_moving_mean"].asnumpy())
    for a, b in zip(res["off"], res["default"]):
        np.testing.assert_array_equal(a, b)


def test_rng_parity_is_regression_guarded():
    """DCE/CSE remove/merge nodes AROUND dropout; the stochastic output
    must stay bitwise identical (stable __rng_id__, not topo rank)."""
    x = sym.Variable("data")
    dead = sym.identity(x)  # removed by dce
    h = sym.Dropout(dead * 1.0, p=0.5, name="do1")
    h = h + (x * 0.0)
    out = sym.Dropout(h, p=0.5, name="do2")
    res = {}
    for spec in ("off", "default"):
        with P.scope(spec):
            ex = out.simple_bind(mx.cpu(), data=(16, 8), grad_req="null")
        mx.random.seed(9)
        x_in = mx.nd.array(np.ones((16, 8), "float32"))
        res[spec] = ex.forward(is_train=True, data=x_in)[0].asnumpy()
    np.testing.assert_array_equal(res["off"], res["default"])
    # and the ids really are pinned on the original nodes
    assert [n.ext_attrs["__rng_id__"] for n in _nodes(out)
            if not n.is_variable and n.op.needs_rng] == ["0", "1"]


def test_cachedop_parity_bitwise():
    net = _probe_net()
    args = net.list_arguments()
    shapes, _, aux_shapes = net.infer_shape(data=(8, 16))
    rng = np.random.RandomState(3)
    vals = [rng.rand(*s).astype("float32") for s in shapes]
    res = {}
    for spec in ("off", "default"):
        with P.scope(spec):
            co = mx.CachedOp(net)
        nd_in = [mx.nd.array(v) for v in vals]
        for a in nd_in:
            a.attach_grad()
        aux = [mx.nd.ones(s) for s in aux_shapes]
        mx.random.seed(7)
        with autograd.record():
            out = co(nd_in, aux)[0]
        out.backward()
        res[spec] = (out.asnumpy(),
                     nd_in[args.index("fc1_weight")].grad.asnumpy(),
                     [a.asnumpy() for a in aux])
    np.testing.assert_array_equal(res["off"][0], res["default"][0])
    np.testing.assert_array_equal(res["off"][1], res["default"][1])
    for a, b in zip(res["off"][2], res["default"][2]):
        np.testing.assert_array_equal(a, b)


def test_fused_train_loop_parity_bitwise():
    from mxtpu.fused_train import FusedTrainLoop
    from mxtpu.io.io import DataBatch

    def run(spec):
        with P.scope(spec):
            net = sym.SoftmaxOutput(
                data=_probe_net(), label=sym.Variable("softmax_label"),
                name="softmax")
            mod = mx.mod.Module(net, data_names=("data",),
                                label_names=("softmax_label",))
            mod.bind(data_shapes=[("data", (8, 16))],
                     label_shapes=[("softmax_label", (8,))])
            mx.random.seed(11)
            mod.init_params(initializer=mx.init.Xavier())
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.1})
            loop = FusedTrainLoop(mod, steps_per_program=2)
            rng = np.random.RandomState(5)
            batches = [DataBatch(
                data=[mx.nd.array(rng.rand(8, 16).astype("float32"))],
                label=[mx.nd.array(rng.randint(0, 4, 8)
                                   .astype("float32"))])
                for _ in range(2)]
            mx.random.seed(13)
            loop.run(batches)
            loop.finalize()
            p, a = mod.get_params()
            return ({k: v.asnumpy() for k, v in p.items()},
                    {k: v.asnumpy() for k, v in a.items()})

    pa, aa = run("off")
    pb, ab = run("default")
    for k in pa:
        np.testing.assert_array_equal(pa[k], pb[k])
    for k in aa:
        np.testing.assert_array_equal(aa[k], ab[k])


def test_control_flow_sub_aux_parity():
    """Passes apply to control-flow SUBGRAPHS too (they lower through
    the same _build_graph_fn); BatchNorm aux write-back from inside a
    foreach body must stay bitwise identical."""
    def build():
        x = sym.var("x")
        st = sym.var("st")

        def body(xt, s):
            h = sym.BatchNorm(data=xt, name="bn", fix_gamma=False)
            h = sym.tanh(sym.exp(h * 0.5))  # fusable chain in the body
            return h, s + 1

        o, _ = cf.foreach(body, x, st)
        return o

    res = {}
    for spec in ("off", "default"):
        with P.scope(spec):
            ex = build().simple_bind(ctx=mx.cpu(), x=(4, 2, 3), st=(1,))
        rng = np.random.RandomState(0)
        xv = (rng.randn(4, 2, 3) * 3 + 5).astype(np.float32)
        out = ex.forward(is_train=True, x=xv,
                         st=np.zeros(1, np.float32))[0].asnumpy()
        res[spec] = (out, ex.aux_dict["bn_moving_mean"].asnumpy(),
                     ex.aux_dict["bn_moving_var"].asnumpy())
    for a, b in zip(res["off"], res["default"]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# provenance + caching + API
# ---------------------------------------------------------------------------

def test_symbol_optimize_leaves_original_untouched():
    net = _probe_net()
    before = len(_nodes(net))
    opt, rep = net.optimize(return_report=True)
    assert len(_nodes(net)) == before
    assert rep["nodes_after"] < rep["nodes_before"] == before
    assert [p["pass"] for p in rep["passes"]] == list(rep["spec"]
                                                     .split(","))


def test_optimize_cached_per_graph_and_spec():
    from mxtpu import profiler

    net = _probe_net()
    with P.scope("default"):
        before = profiler.get_stat("pass_runs::dce")
        # executor bind builds infer AND train graph fns -> one optimize
        net.simple_bind(mx.cpu(), data=(4, 16), grad_req="write")
        assert profiler.get_stat("pass_runs::dce") == before + 1


def test_provenance_on_inspect_and_telemetry():
    from mxtpu import telemetry

    net = _probe_net()
    with P.scope("default"):
        ex = net.simple_bind(mx.cpu(), data=(4, 16), grad_req="null")
    ex.forward(is_train=False,
               data=mx.nd.ones((4, 16)))
    rec = ex._insp
    assert rec.pass_report is not None
    assert rec.pass_report["nodes_after"] < \
        rec.pass_report["nodes_before"]
    d = rec.as_dict(analyze=False)
    assert "passes" in d and "->" in d["passes"]
    evs = [e for e in telemetry.events("compile")
           if e.get("program") == rec.name]
    assert evs and any("->" in e.get("passes", "") for e in evs)
    # full report rides on inspect.report()
    rep = mx.inspect.report(rec)
    assert rep["pass_report"]["spec"] == d["passes"].split(":")[0]


def test_pass_timings_in_profiler_stats():
    from mxtpu import profiler

    _probe_net().optimize(passes="default")
    stats = profiler.stats()
    for name in ("dce", "fold", "cse", "fuse"):
        assert stats.get("pass_runs::%s" % name, 0) >= 1
        assert "pass_wall_us::%s" % name in stats


def test_shard_pass_joins_default_only_with_plan_and_orders_last():
    """`shard` composes with dce/fold/cse/fuse in canonical order (it
    registers LAST) and joins the default set only under an active
    ShardingPlan — mirroring layout's opt-in discipline."""
    assert P.parse_spec("shard,dce") == ("dce", "shard")
    assert P.parse_spec("fuse,shard,fold") == ("fold", "fuse", "shard")
    assert "shard" not in P.parse_spec("default")
    with mx.shard.ShardingPlan(num_shards=4).activate():
        spec = P.parse_spec("default")
        assert spec[-1] == "shard"
        net = _probe_net()
        _, rep = net.optimize(return_report=True)
        assert [p["pass"] for p in rep["passes"]] == \
            ["dce", "fold", "cse", "fuse", "shard"]


def test_shard_pass_noop_on_single_shard_bitwise():
    """On a 1-shard plan the pass must be a STRICT no-op: zero
    annotations, zero node delta, bitwise-identical execution."""
    net = _probe_net()
    with mx.shard.ShardingPlan(num_shards=1).activate():
        opt, rep = net.optimize(passes="shard", return_report=True)
        st = rep["passes"][0]
        assert st["annotated"] == 0 and st["plan"] is None
        assert st["nodes_before"] == st["nodes_after"]
        assert not any("__shard_spec__" in n.ext_attrs
                       for n in _nodes(opt))
        res = {}
        for spec in ("off", "default"):
            with P.scope(spec):
                ex = net.simple_bind(mx.cpu(), data=(8, 16),
                                     grad_req="write")
            _fill_args(ex)
            x = mx.nd.array(np.random.RandomState(0).rand(8, 16)
                            .astype("float32"))
            mx.random.seed(42)
            res[spec] = ex.forward(is_train=True, data=x)[0].asnumpy()
        np.testing.assert_array_equal(res["off"], res["default"])


def test_shard_pass_annotates_variables_only():
    with mx.shard.ShardingPlan(num_shards=4,
                               min_shard_elems=16).activate():
        w = sym.Variable("w", shape=(64, 32))
        out = sym.FullyConnected(data=sym.Variable("data"), weight=w,
                                 no_bias=True, num_hidden=32)
        opt, rep = out.optimize(passes="shard", return_report=True)
        st = rep["passes"][0]
        assert st["annotated"] == 2 and st["state_sharded"] == 1
        assert "zero1:n=4" in st["plan"]
        for n in _nodes(opt):
            if n.is_variable:
                assert "__shard_spec__" in n.ext_attrs
                if n.name == "w":
                    assert n.ext_attrs["__shard_state_dim__"] == "0"
            else:
                assert "__shard_spec__" not in n.ext_attrs
        # the ORIGINAL graph is untouched (passes clone)
        assert not any("__shard_spec__" in n.ext_attrs
                       for n in _nodes(out))


def test_shard_pass_never_touches_rng_ids():
    """Annotation under a live multi-shard plan must leave the stable
    `__rng_id__` untouched and the stochastic output bitwise identical
    passes-on vs passes-off."""
    x = sym.Variable("data")
    h = sym.Dropout(sym.identity(x) * 1.0, p=0.5, name="do1")
    out = sym.Dropout(h + (x * 0.0), p=0.5, name="do2")
    P.ensure_rng_ids(out)
    ids_before = [n.ext_attrs["__rng_id__"] for n in _nodes(out)
                  if not n.is_variable and n.op.needs_rng]
    with mx.shard.ShardingPlan(num_shards=4).activate():
        res = {}
        for spec in ("off", "default"):
            with P.scope(spec):
                ex = out.simple_bind(mx.cpu(), data=(16, 8),
                                     grad_req="null")
            mx.random.seed(9)
            x_in = mx.nd.array(np.ones((16, 8), "float32"))
            res[spec] = ex.forward(is_train=True,
                                   data=x_in)[0].asnumpy()
        opt = out.optimize(passes="default")
        ids_after = [n.ext_attrs["__rng_id__"] for n in _nodes(out)
                     if not n.is_variable and n.op.needs_rng]
        opt_ids = [n.ext_attrs["__rng_id__"] for n in _nodes(opt)
                   if not n.is_variable and n.op.needs_rng]
    np.testing.assert_array_equal(res["off"], res["default"])
    assert ids_after == ids_before
    assert set(opt_ids) <= set(ids_before)


def test_stablehlo_histogram_parses_lowered_text():
    txt = """\
module @jit_f {
  func.func public @main(%arg0: tensor<2x3x4x4xf32>) -> tensor<2x4x4x3xf32> {
    %0 = stablehlo.transpose %arg0, dims = [0, 2, 3, 1] : (tensor<2x3x4x4xf32>) -> tensor<2x4x4x3xf32>
    %1 = stablehlo.tanh %0 : tensor<2x4x4x3xf32>
    return %1 : tensor<2x4x4x3xf32>
  }
}
"""
    h = mx.inspect.hlo_histogram(txt)
    assert h["dialect"] == "stablehlo"
    assert h["n_transposes_surviving"] == 1
    assert h["op_histogram_top"]["tanh"] == 1
