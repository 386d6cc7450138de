"""FusedTrainLoop (K steps per dispatch) must match the per-step path.

The reference amortizes per-op scheduling with engine bulking
(`src/engine/threaded_engine.h:411-426`); the TPU analog scans K whole
train steps into one donated XLA program (`mxtpu/fused_train.py`).
Semantic equivalence — params, optimizer state, BN moving stats, lr
schedule advance — is the contract these tests pin down.
"""
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import sym
from mxtpu.io.io import DataBatch


def _mlp(data):
    # no_bias before BatchNorm: a bias feeding BN has ~zero true
    # gradient, and with the reference's wd_mult=0-for-biases now
    # seeded, its adam trajectory is pure fp-noise amplification —
    # a degenerate parameter no real network carries
    x = sym.FullyConnected(data=data, num_hidden=16, no_bias=True,
                           name="fc1")
    x = sym.BatchNorm(data=x, name="bn1")
    x = sym.Activation(data=x, act_type="relu")
    return sym.FullyConnected(data=x, num_hidden=4, name="fc2")


def _conv_bn(x, name, num_filter, stride=1):
    x = sym.Convolution(data=x, kernel=(3, 3), num_filter=num_filter,
                        stride=(stride, stride), pad=(1, 1), no_bias=True,
                        name="c" + name)
    return sym.BatchNorm(data=x, name="bn" + name)


def _convnet(data):
    """`resnet50_fused_k16`'s operator mix at toy widths: 3x3
    convolutions each under a BatchNorm, relu, a residual add across
    two of them, global average pooling, a dense head."""
    x = sym.Activation(_conv_bn(data, "0", 4), act_type="relu")
    x = sym.Activation(_conv_bn(x, "1", 8, stride=2), act_type="relu")
    y = sym.Activation(_conv_bn(x, "2", 8), act_type="relu")
    x = sym.Activation(x + _conv_bn(y, "3", 8), act_type="relu")
    x = sym.Pooling(data=x, global_pool=True, pool_type="avg",
                    kernel=(1, 1))
    return sym.FullyConnected(data=sym.Flatten(x), num_hidden=4, name="fc")


_NETS = {"mlp": (_mlp, (10,)), "conv": (_convnet, (3, 8, 8))}


def _make_module(seed, optimizer="sgd", opt_params=None, batch=8,
                 net="mlp", amp=None):
    build, shape = _NETS[net]
    # bf16 AMP over fp32 master weights, set up as
    # `benchmark/onchip/drivers/module_fused.py` does: the scope holds
    # while the symbol is built and bound
    with mx.amp.scope(amp):
        out = sym.SoftmaxOutput(data=build(sym.Variable("data")),
                                label=sym.Variable("softmax_label"),
                                name="softmax")
        mod = mx.mod.Module(out, data_names=("data",),
                            label_names=("softmax_label",),
                            context=mx.cpu())
        mod.bind(data_shapes=[("data", (batch,) + shape)],
                 label_shapes=[("softmax_label", (batch,))])
    mod.init_params(initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                                      magnitude=2.0),
                    force_init=True)
    # deterministic identical init across modules
    rng = np.random.RandomState(seed)
    args, auxs = mod.get_params()
    new_args = {k: mx.nd.array(rng.randn(*v.shape).astype(np.float32) * 0.1)
                for k, v in sorted(args.items())}
    mod.set_params(new_args, auxs, force_init=True)
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params=dict(opt_params or
                                             {"learning_rate": 0.05}))
    return mod


def _batches(n, batch=8, seed=3, net="mlp"):
    rng = np.random.RandomState(seed)
    shape = _NETS[net][1]
    out = []
    for _ in range(n):
        d = mx.nd.array(rng.randn(batch, *shape).astype(np.float32))
        l = mx.nd.array(rng.randint(0, 4, (batch,)).astype(np.float32))
        out.append(DataBatch(data=[d], label=[l]))
    return out


def _run_per_step(mod, batches):
    for b in batches:
        mod.forward(b, is_train=True)
        mod.backward()
        mod.update()


def _training_state(mod):
    """{name: array} of all a train step carries over: the weights,
    BatchNorm's moving statistics, and the optimizer's state (none, one
    array, or a tuple such as Adam's two moments per weight)."""
    args, aux = mod.get_params()
    named = dict(args, **aux)
    for idx, state in mod._updater.states.items():
        leaves = state if isinstance(state, (tuple, list)) else [state]
        for j, leaf in enumerate(leaves):
            if leaf is not None:
                named["optimizer state %s.%d" % (idx, j)] = leaf
    return args, named


def _assert_fused_matches_per_step(net, amp, optimizer, opt_params, K, tol):
    batches = _batches(2 * K, net=net)
    mod_a = _make_module(7, optimizer, opt_params, net=net, amp=amp)
    mod_b = _make_module(7, optimizer, opt_params, net=net, amp=amp)

    _run_per_step(mod_a, batches)

    loop = mx.FusedTrainLoop(mod_b, steps_per_program=K)
    loop.run(batches[:K])
    loop.run(batches[K:])

    _, state_a = _training_state(mod_a)
    args_b, state_b = _training_state(mod_b)
    # the master weights stay float32 whatever the compute type
    assert all(v.dtype == np.float32 for v in args_b.values())
    # the moving statistics advance per scanned step, not once per
    # chunk, and the optimizer's state is handed back with the weights
    assert set(state_a) == set(state_b)
    for name in state_a:
        np.testing.assert_allclose(state_a[name].asnumpy(),
                                   state_b[name].asnumpy(),
                                   rtol=tol, atol=tol, err_msg=name)


_OPTIMIZERS = [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
]


def _tol(amp, optimizer):
    """rtol = atol between the K-step program and the per-step one."""
    if optimizer != "adam":
        # under bf16 AMP both programs cast the same values at the same
        # places, so SGD agrees as closely as in fp32 (1.5e-8 measured,
        # CPU); a loop that lost the policy would be 1e-4 (MLP) to 8e-4
        # (conv net) away, the distance between the two precisions
        return 2e-5
    if amp is None:
        # Adam divides by sqrt(v)+eps with v near zero early in
        # training, so fp reassociation between the scanned and per-step
        # XLA programs compounds faster (a single step matches to ~1e-7)
        return 2e-4
    # bf16 AMP: master weights 1.5e-7 apart after two steps fall on
    # either side of a bf16 rounding, activations move by 2^-8, and Adam
    # turns a gradient element near zero into a step of +-lr whatever
    # its size: 3.3e-3 to 5.1e-3 measured on the conv net over three
    # data seeds (CPU), all of it from the third step on.  Two steps of
    # lr = 0.01, a third of what a weight travels in the six steps
    return 2e-2


@pytest.mark.parametrize("optimizer,opt_params", _OPTIMIZERS,
                         ids=["sgd", "sgd_momentum_wd", "adam"])
@pytest.mark.parametrize("amp", [None, "bfloat16"], ids=["fp32", "bf16"])
@pytest.mark.parametrize("net", ["mlp", "conv"])
def test_fused_matches_per_step(net, amp, optimizer, opt_params):
    _assert_fused_matches_per_step(net, amp, optimizer, opt_params, 3,
                                   _tol(amp, optimizer))


@pytest.mark.parametrize("K", [1, 4, 16])
def test_fused_matches_per_step_at_k(K):
    """The measured cell's mix (conv net, bf16 AMP, momentum + weight
    decay) at the program lengths either side of the K=3 above, and at
    `resnet50_fused_k16`'s own."""
    optimizer, opt_params = _OPTIMIZERS[1]
    _assert_fused_matches_per_step("conv", "bfloat16", optimizer,
                                   opt_params, K,
                                   _tol("bfloat16", optimizer))


def test_fused_lr_schedule_advances_per_step():
    """The scheduler must see every scanned step, not one per program."""
    from mxtpu.lr_scheduler import FactorScheduler

    K = 4
    # FactorScheduler is stateful — each module needs its own instance
    def opt_params():
        return {"learning_rate": 0.1,
                "lr_scheduler": FactorScheduler(step=2, factor=0.5)}
    batches = _batches(K)
    mod_a = _make_module(11, "sgd", opt_params())
    mod_b = _make_module(11, "sgd", opt_params())

    _run_per_step(mod_a, batches)
    mx.FusedTrainLoop(mod_b, steps_per_program=K).run(batches)

    args_a, _ = mod_a.get_params()
    args_b, _ = mod_b.get_params()
    for name in args_a:
        np.testing.assert_allclose(args_a[name].asnumpy(),
                                   args_b[name].asnumpy(),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
    assert mod_a._optimizer.num_update == mod_b._optimizer.num_update


@pytest.mark.parametrize("net,amp", [("mlp", None), ("conv", "bfloat16")])
def test_fused_outputs_stacked_and_switchable(net, amp):
    """Collected outputs are (K, ...) stacks matching per-step outputs
    (what the benchmark's `correct` reads its losses from), and per-step
    training continues seamlessly after a fused chunk."""
    K = 2
    batches = _batches(K + 1, net=net)
    mod_a = _make_module(5, net=net, amp=amp)
    mod_b = _make_module(5, net=net, amp=amp)

    outs_a = []
    for b in batches[:K]:
        mod_a.forward(b, is_train=True)
        outs_a.append(mod_a.get_outputs()[0].asnumpy())
        mod_a.backward()
        mod_a.update()

    loop = mx.FusedTrainLoop(mod_b, steps_per_program=K)
    stacked = loop.run(batches[:K])
    assert stacked[0].shape == (K,) + outs_a[0].shape
    for k in range(K):
        np.testing.assert_allclose(stacked[0].asnumpy()[k], outs_a[k],
                                   rtol=2e-5, atol=2e-5)

    # hand the module back to the per-step path: states must be current
    _run_per_step(mod_a, batches[K:])
    _run_per_step(mod_b, batches[K:])
    args_a, _ = mod_a.get_params()
    args_b, _ = mod_b.get_params()
    for name in args_a:
        np.testing.assert_allclose(args_a[name].asnumpy(),
                                   args_b[name].asnumpy(),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


def test_fused_rejects_unsupported():
    mod = _make_module(1)
    with pytest.raises(mx.MXNetError):
        mx.FusedTrainLoop(mod, steps_per_program=0)
    mod2 = _make_module(1, optimizer="rmsprop",
                        opt_params={"learning_rate": 0.01})
    with pytest.raises(mx.MXNetError):
        mx.FusedTrainLoop(mod2)


def test_backward_do_mirror_remat_equivalence(monkeypatch):
    """MXTPU_BACKWARD_DO_MIRROR=1 gradient-checkpoints the fused step
    (reference MXNET_BACKWARD_DO_MIRROR mirror pass,
    graph_executor.cc:134-283): numerics must match the non-remat path
    exactly — only the backward's memory/compute schedule changes."""
    import numpy as np

    import mxtpu as mx
    from mxtpu import sym

    def run():
        data = sym.Variable("data")
        h = sym.Convolution(data, kernel=(3, 3), num_filter=4,
                            pad=(1, 1), name="c1")
        h = sym.Activation(h, act_type="relu")
        h = sym.FullyConnected(sym.Flatten(h), num_hidden=8, name="f1")
        out = sym.SoftmaxOutput(h, sym.Variable("softmax_label"),
                                name="softmax")
        exe = out.simple_bind(ctx=mx.cpu(), grad_req="write",
                              data=(2, 3, 8, 8), softmax_label=(2,))
        rng = np.random.RandomState(0)
        for name, arr in exe.arg_dict.items():
            if name not in ("data", "softmax_label"):
                arr._set_jax(mx.nd.array(
                    rng.uniform(-0.5, 0.5, arr.shape)
                    .astype(np.float32))._data)
        x = rng.uniform(-1, 1, (2, 3, 8, 8)).astype(np.float32)
        y = np.array([1.0, 3.0], np.float32)
        outs = exe.forward(is_train=True, data=mx.nd.array(x),
                           softmax_label=mx.nd.array(y))
        exe.backward()
        return (outs[0].asnumpy(),
                {k: v.asnumpy() for k, v in exe.grad_dict.items()
                 if v is not None})

    # the baseline must really be the non-remat path even if the shell
    # exports the mirror flag
    for var in ("MXTPU_BACKWARD_DO_MIRROR", "MXNET_BACKWARD_DO_MIRROR",
                "MXTPU_REMAT_POLICY"):
        monkeypatch.delenv(var, raising=False)
    base_out, base_grads = run()
    monkeypatch.setenv("MXTPU_BACKWARD_DO_MIRROR", "1")
    for policy in ("full", "dots"):
        monkeypatch.setenv("MXTPU_REMAT_POLICY", policy)
        got_out, got_grads = run()
        np.testing.assert_allclose(got_out, base_out, rtol=1e-6,
                                   atol=1e-7)
        for k in base_grads:
            np.testing.assert_allclose(got_grads[k], base_grads[k],
                                       rtol=1e-5, atol=1e-6,
                                       err_msg="%s/%s" % (policy, k))
