"""Drives ``mxtpu.FusedTrainLoop.run_stacked``: K train steps (forward,
backward, SGD update) of a Module in one device program.

The Module is built the way ``chip_smoke.py`` (phase 1) and
``examples/image-classification`` do: the model-zoo net traced to a
Symbol plus ``SoftmaxOutput`` under the bf16 AMP scope, bound through
``Module``, SGD with momentum.  The weights are the benchmark's own, made
from the seed by the reference's ``init_params`` and handed over by
name."""
import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import profiler, sym
from mxtpu.gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1
from mxtpu.ndarray.ndarray import NDArray

from . import common


def _context(rehearse):
    return mx.cpu(0) if rehearse else mx.tpu(0)


def program_names(config, prefix="resnetv10_"):
    """{reference leaf: the Module's parameter name} for Gluon's
    ``resnet<N>_v1``: convolutions and BatchNorms are numbered per stage
    in the order the blocks create them.  Gluon numbers the nets of a
    process: ``prefix`` is the one this net was given."""
    names = {"stem.conv.w": prefix + "conv2d0_weight",
             "stem.bn.g": prefix + "batchnorm0_gamma",
             "stem.bn.b": prefix + "batchnorm0_beta",
             "fc.w": prefix + "dense0_weight", "fc.b": prefix + "dense0_bias"}
    ch = config["channels"]
    cin = ch[0]
    for s, (n_blocks, cout) in enumerate(zip(config["layers"], ch[1:]), 1):
        conv = bn = 0
        st = "%sstage%d_" % (prefix, s)
        for b in range(n_blocks):
            p = "s%d.b%d" % (s, b)
            parts = [("c1", "n1", True), ("c2", "n2", False),
                     ("c3", "n3", True)]
            if b == 0 and cin != cout:
                parts.append(("ds", "dn", False))
            for c, n, has_bias in parts:
                names["%s.%s.w" % (p, c)] = "%sconv2d%d_weight" % (st, conv)
                if has_bias:
                    names["%s.%s.b" % (p, c)] = "%sconv2d%d_bias" % (st, conv)
                names["%s.%s.g" % (p, n)] = "%sbatchnorm%d_gamma" % (st, bn)
                names["%s.%s.b" % (p, n)] = "%sbatchnorm%d_beta" % (st, bn)
                conv += 1
                bn += 1
            cin = cout
    return names


def _build_module(config, batch, ctx, weights):
    """The bound Module with ``weights`` ({reference leaf: float32
    array}) in place and its optimizer ready."""
    c, h, w = config["input"]["shape"]
    shape = (batch, c, h, w)
    with mx.amp.scope(config["amp_dtype"]):
        # what vision.resnet50_v1() builds, from the configuration's sizes
        net = ResNetV1(BottleneckV1, config["layers"], config["channels"],
                       classes=config["classes"])
        net.initialize(ctx=ctx)
        out_sym, _, _ = net._trace_symbol(mx.nd.zeros(shape, ctx=ctx))
        softmax = sym.SoftmaxOutput(data=out_sym,
                                    label=sym.Variable("softmax_label"),
                                    name="softmax")
        mod = mx.mod.Module(softmax, data_names=("data0",),
                            label_names=("softmax_label",), context=[ctx])
        mod.bind(data_shapes=[("data0", shape)],
                 label_shapes=[("softmax_label", (batch,))])
    names = program_names(config, net.prefix)
    want = set(mod._exec_group.param_names)
    if set(names.values()) != want:
        raise ValueError("the Module's parameters are not the reference's: "
                         "%s" % sorted(want ^ set(names.values()))[:6])
    args = {names[k]: NDArray(v, ctx=ctx, _committed=True)
            for k, v in weights.items()}
    aux = {}
    for name, arrs in zip(mod._exec_group.aux_names,
                          mod._exec_group.aux_arrays):
        fill = jnp.ones if name.endswith("running_var") else jnp.zeros
        aux[name] = NDArray(fill(arrs[0].shape, jnp.float32), ctx=ctx,
                            _committed=True)
    mod.init_params(initializer=None, arg_params=args, aux_params=aux)
    opt = config["optimizer"]
    if opt["name"] != "sgd":
        raise ValueError("this driver runs the recipe's SGD, not %r"
                         % (opt["name"],))
    mod.init_optimizer(kvstore=None, optimizer="sgd", optimizer_params={
        "learning_rate": opt["learning_rate"], "momentum": opt["momentum"],
        "wd": opt["wd"]})
    return mod, names


def _put_image_stack(stack, dev):
    """One host stack's arrays on the device, labels as the float32 the
    Module binds them as."""
    data, label = stack["data"], stack["label"].astype("float32")
    return jax.device_put(data, dev), jax.device_put(label, dev)


class Driver(object):
    def __init__(self, cell, seed, ref):
        self.cell, self.seed, self.ref = cell, seed, ref
        self.k = int(cell.traffic["steps_per_program"])

    def setup(self):
        cell = self.cell
        self.ctx = _context(cell.rehearse)
        weights = self.ref.init_params(cell.config, self.seed)
        self.mod, self.names = _build_module(
            cell.config, int(cell.traffic["batch"]), self.ctx, weights)
        del weights
        self.loop = mx.FusedTrainLoop(self.mod, steps_per_program=self.k,
                                      collect_outputs=True)
        slots = [self.loop._arg_names[i] for i in self.loop._data_idx]
        if slots != ["data0", "softmax_label"]:
            raise ValueError("the fused loop's data slots are %s" % slots)
        self.dev = self.ctx.jax_device

    def put(self, stack):
        return list(_put_image_stack(stack, self.dev))

    def call(self, staged):
        outs = self.loop.run_stacked(staged)
        return outs[0]._data

    def sync(self):
        mx.nd.waitall()

    def _state(self):
        """({leaf: weight}, {leaf: momentum}) under the reference's names,
        as the loop holds them now."""
        back = {v: k for k, v in self.names.items()}
        order = [back[self.loop._arg_names[i]] for i in self.loop._diff_idx]
        return (dict(zip(order, self.loop._p_vals)),
                dict(zip(order, self.loop._s_tree)))

    def observe(self, probs, ring):
        """What the first program left behind: its steps' losses, the
        momentum as per-leaf norms, the master weights as host arrays."""
        losses = common.xent_of_probs(probs, ring[0]["label"])
        weights, moments = self._state()
        return {"losses": [float(x) for x in losses],
                "moment_norms": common.leaf_norms(moments),
                "weights": common.to_host(weights)}

    def snapshot(self):
        """A copy of the state, for the fault that puts it back."""
        return jax.tree_util.tree_map(
            jnp.copy, (self.loop._p_vals, self.loop._s_tree,
                       self.loop._aux_vals))

    def restore(self, state):
        self.loop._p_vals, self.loop._s_tree, self.loop._aux_vals = state

    def counters(self):
        return dict(profiler.stats())

    def release(self):
        self.loop.finalize()
        self.loop = self.mod = None
