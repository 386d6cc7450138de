"""Flagship sharded TransformerLM: a manual-SPMD training step over the
full mesh (dp × pp × tp × sp × ep).

The reference has no transformer, no TP/PP/SP/EP (SURVEY.md §2.4 marks
all four absent; its only model parallelism is manual `group2ctx` op
placement, `src/executor/graph_executor.cc:1594`).  This module is the
TPU-first replacement: one `shard_map`-wrapped train step where

  * dp — batch sharded; gradient psum over "dp" replaces KVStore
         push/pull (`src/kvstore/kvstore_local.h:173`).
  * pp — layers stacked per stage, microbatches rotate through stages
         with `ppermute` (GPipe-style collective pipeline).
  * tp — Megatron-style column/row parallel attention + FFN: QKV/W1
         column-sharded, WO/W2 row-sharded with psum; vocab-sharded
         embedding/unembedding with a psum-based softmax-xent.
  * sp — sequence sharded; ring attention (`ring_attention.py`) streams
         K/V shards over ICI neighbors.
  * ep — mixture-of-experts FFN with top-1 (switch) routing; token
         buckets exchanged via all_to_all over "ep".

Everything is pure-functional jax under one jit: params in, (params,
metrics) out, with donated params for in-place HBM update.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

from ..base import MXNetError
from .mesh import create_mesh, AXIS_DP, AXIS_TP, AXIS_PP, AXIS_SP, AXIS_EP
from .ring_attention import ring_attention, _match_vma

__all__ = ["TransformerConfig", "init_params", "param_specs",
           "make_train_step", "make_fused_train_steps", "make_forward",
           "dryrun", "init_opt_state", "param_shapes"]

_NEG_INF = -1e30
# params below this element count keep replicated optimizer state
# (ZeRO-sharding a LayerNorm vector costs a collective, saves nothing)
_ZERO1_MIN_ELEMS = 4096


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 4          # total; must divide by pp stages
    d_ff: int = 128
    n_experts: int = 0         # 0 = dense FFN; >0 = MoE every layer
    capacity_factor: float = 2.0
    max_len: int = 128
    dtype: Any = "bfloat16"
    remat: str = "none"        # "none" or an executor remat policy
    # ("full" | "dots" | "dots_no_batch"): per-layer rematerialization
    # in the backward pass.  "full" recomputes each layer's internals
    # from its input (activation memory drops from O(layers * T *
    # d_ff) to O(layers * T * d_model) — what makes T>=8k trainable
    # on one chip); "dots" saves matmul outputs and recomputes
    # elementwise only.  Analog of the reference's
    # MXNET_BACKWARD_DO_MIRROR (docs/faq/env_var.md) which this
    # repo's symbolic executor exposes as MXTPU_BACKWARD_DO_MIRROR;
    # same policy vocabulary (`executor.apply_remat`).

    def __post_init__(self):
        from ..executor import _REMAT_POLICIES

        if self.remat != "none" and self.remat not in _REMAT_POLICIES:
            raise MXNetError(
                "TransformerConfig.remat must be 'none' or one of %s "
                "(got %r)" % (sorted(_REMAT_POLICIES), self.remat))


# ---------------------------------------------------------------------------
# parameters


def init_params(cfg: TransformerConfig, mesh, seed: int = 0):
    """Initialize the stacked-parameter pytree, laid out for the mesh:
    leading axis of every per-layer tensor is [pp, layers_per_stage].
    Returns committed, sharded jax arrays (NamedSharding from
    `param_specs`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    pp = mesh.shape[AXIS_PP]
    shapes = param_shapes(cfg, pp)  # single shape source (+div check)
    E, F = cfg.d_model, cfg.d_ff
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 16)
    dt = jnp.dtype(cfg.dtype)

    def norm(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (1.0 / fan_in) ** 0.5).astype(dt)

    # fan-in per param; ones-initialized norms have no fan-in entry
    fan_in = {"embed": E, "pos": E, "unembed": E, "wq": E, "wk": E,
              "wv": E, "wo": E, "router": E, "we1": E, "we2": F,
              "w1": E, "w2": F}
    p = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        if name in ("ln_f", "ln1", "ln2"):
            p[name] = jnp.ones(shape, dt)
        else:
            p[name] = norm(ks[i], shape, fan_in[name])

    specs = param_specs(cfg)
    out = {}
    for name, arr in p.items():
        out[name] = jax.device_put(
            arr, NamedSharding(mesh, specs[name]))
    return out


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec per parameter (Megatron layout on tp, stage-stacked
    on pp, experts on ep)."""
    from jax.sharding import PartitionSpec as P

    specs = {
        "embed": P(AXIS_TP, None),       # vocab-sharded embedding
        "pos": P(None, None),
        "ln_f": P(None),
        "unembed": P(None, AXIS_TP),     # vocab-sharded unembedding
        "wq": P(AXIS_PP, None, None, AXIS_TP),   # column parallel
        "wk": P(AXIS_PP, None, None, AXIS_TP),
        "wv": P(AXIS_PP, None, None, AXIS_TP),
        "wo": P(AXIS_PP, None, AXIS_TP, None),   # row parallel
        "ln1": P(AXIS_PP, None, None),
        "ln2": P(AXIS_PP, None, None),
    }
    if cfg.n_experts:
        specs["router"] = P(AXIS_PP, None, None, None)
        specs["we1"] = P(AXIS_PP, None, AXIS_EP, None, AXIS_TP)
        specs["we2"] = P(AXIS_PP, None, AXIS_EP, AXIS_TP, None)
    else:
        specs["w1"] = P(AXIS_PP, None, None, AXIS_TP)
        specs["w2"] = P(AXIS_PP, None, AXIS_TP, None)
    return specs


def param_shapes(cfg: TransformerConfig, pp: int) -> Dict[str, Tuple]:
    """Global parameter shapes — the single source init_params and the
    optimizer-state builders share."""
    if cfg.n_layers % pp:
        raise MXNetError("n_layers=%d not divisible by pp=%d"
                         % (cfg.n_layers, pp))
    lps = cfg.n_layers // pp
    E, F, V = cfg.d_model, cfg.d_ff, cfg.vocab
    shapes = {
        "embed": (V, E), "pos": (cfg.max_len, E), "ln_f": (E,),
        "unembed": (E, V),
        "wq": (pp, lps, E, E), "wk": (pp, lps, E, E),
        "wv": (pp, lps, E, E), "wo": (pp, lps, E, E),
        "ln1": (pp, lps, E), "ln2": (pp, lps, E),
    }
    if cfg.n_experts:
        NE = cfg.n_experts
        shapes["router"] = (pp, lps, E, NE)
        shapes["we1"] = (pp, lps, NE, E, F)
        shapes["we2"] = (pp, lps, NE, F, E)
    else:
        shapes["w1"] = (pp, lps, E, F)
        shapes["w2"] = (pp, lps, F, E)
    return shapes


def _plan_for_mesh(cfg: TransformerConfig, mesh):
    """The transformer stack's ShardingPlan: Megatron model specs plus
    ZeRO-1 state sharding over dp — re-based onto the `mx.shard`
    backbone so the placement logic lives in ONE place
    (`ShardingPlan.shard_dim` / `opt_state_spec`)."""
    from ..sharding.plan import ShardingPlan

    return ShardingPlan(mesh=mesh, data_axis=AXIS_DP,
                        model_axis=AXIS_TP,
                        param_specs=param_specs(cfg),
                        shard_optimizer_state=True,
                        min_shard_elems=_ZERO1_MIN_ELEMS,
                        name="transformer")


def _zero1_dims(cfg: TransformerConfig, mesh) -> Dict[str, Any]:
    """ZeRO-1 placement (arxiv 2004.13336, automatic cross-replica
    sharding of the weight update): per parameter, the dimension to
    shard optimizer state over the dp axis — the first spec-unsharded
    dim whose size divides dp (`ShardingPlan.shard_dim`).  None =
    state stays replicated (tiny params not worth a collective)."""
    plan = _plan_for_mesh(cfg, mesh)
    shapes = param_shapes(cfg, mesh.shape[AXIS_PP])
    return {name: plan.shard_dim(name, shape)
            for name, shape in shapes.items()}


def _opt_state_specs(cfg: TransformerConfig, mesh):
    """PartitionSpecs for the ZeRO-sharded Adam moments: the param's
    spec with AXIS_DP added on the chosen dim
    (`ShardingPlan.opt_state_spec`)."""
    plan = _plan_for_mesh(cfg, mesh)
    shapes = param_shapes(cfg, mesh.shape[AXIS_PP])
    return {name: plan.opt_state_spec(name, shape)
            for name, shape in shapes.items()}


def init_opt_state(cfg: TransformerConfig, mesh):
    """Sharded-zero Adam state: per-param m/v in fp32, each replica
    holding 1/dp of every moment (the ZeRO-1 memory win), plus the
    step counter."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    shapes = param_shapes(cfg, mesh.shape[AXIS_PP])
    ospecs = _opt_state_specs(cfg, mesh)
    state = {"m": {}, "v": {}}
    for name, shape in shapes.items():
        sh = NamedSharding(mesh, ospecs[name])
        state["m"][name] = jax.device_put(
            jnp.zeros(shape, jnp.float32), sh)
        state["v"][name] = jax.device_put(
            jnp.zeros(shape, jnp.float32), sh)
    state["t"] = jax.device_put(
        jnp.zeros((), jnp.float32),
        NamedSharding(mesh, jax.sharding.PartitionSpec()))
    return state


def _grad_psum_axes(cfg: TransformerConfig) -> Dict[str, Tuple[str, ...]]:
    """Axes each gradient must be psum-ed over = mesh axes the param is
    REPLICATED on (data/sequence always; pp/tp/ep when not sharded)."""
    specs = param_specs(cfg)
    axes = {}
    for name, spec in specs.items():
        sharded = {a for dim in spec for a in
                   ((dim,) if isinstance(dim, str) else (dim or ()))}
        rep = [a for a in (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP, AXIS_EP)
               if a not in sharded]
        axes[name] = tuple(rep)
    return axes


# ---------------------------------------------------------------------------
# model (runs INSIDE shard_map: arrays are per-device shards)


def _rms_norm(x, scale):
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    var = (x32 * x32).mean(-1, keepdims=True)
    return (x32 * jnp.reciprocal(jnp.sqrt(var + 1e-6))).astype(x.dtype) \
        * scale


def _attention(cfg, x, wq, wk, wv, wo, tp_size):
    """TP column/row-parallel attention with ring-sharded sequence.
    x: [B, T_loc, E]; wq/wk/wv: [E, E/tp] (local shard), wo: [E/tp, E]."""
    import jax
    import jax.numpy as jnp

    B, T, E = x.shape
    h_loc = cfg.n_heads // tp_size
    D = E // cfg.n_heads

    def split(h):
        return h.reshape(B, T, h_loc, D).transpose(0, 2, 1, 3)

    q, k, v = split(x @ wq), split(x @ wk), split(x @ wv)
    o = ring_attention(q, k, v, axis_name=AXIS_SP, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, h_loc * D)
    out = o @ wo
    # row-parallel output projection: partial sums over tp
    return jax.lax.psum(out, AXIS_TP)


def _dense_ffn(x, w1, w2):
    import jax
    import jax.numpy as jnp

    h = jax.nn.gelu(jnp.einsum(
        "bte,ef->btf", x, w1,
        preferred_element_type=jnp.float32)).astype(x.dtype)
    return jax.lax.psum(h @ w2, AXIS_TP)


def _moe_ffn(cfg, x, router, we1, we2, ep_size):
    """Switch-style top-1 MoE with all_to_all dispatch over "ep".

    x: [B, T, E] local tokens; we1: [NE/ep, E, F/tp] local expert shard.
    Tokens are bucketed by destination expert (capacity-dropped),
    exchanged over the ep ring, processed by the local experts, and sent
    back.  With ep=1 the all_to_all is the identity and this reduces to
    single-host switch routing.
    """
    import jax
    import jax.numpy as jnp

    B, T, E = x.shape
    NE = cfg.n_experts
    ne_loc = NE // ep_size
    n_tok = B * T
    cap = max(1, int(cfg.capacity_factor * n_tok / NE))

    flat = x.reshape(n_tok, E)
    logits = (flat @ router).astype(jnp.float32)          # [n_tok, NE]
    gates = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(gates, axis=-1)                    # [n_tok]
    gate = jnp.take_along_axis(gates, expert[:, None], 1)[:, 0]

    # position of each token within its expert bucket; drop overflow
    onehot = jax.nn.one_hot(expert, NE, dtype=jnp.int32)   # [n_tok, NE]
    pos_in_exp = jnp.cumsum(onehot, axis=0) * onehot       # 1-based
    pos = pos_in_exp.max(axis=-1) - 1                      # [n_tok]
    keep = (pos >= 0) & (pos < cap)
    gate = jnp.where(keep, gate, 0.0)

    # scatter tokens into [NE, cap, E] buckets
    buckets = jnp.zeros((NE, cap, E), flat.dtype)
    safe_pos = jnp.clip(pos, 0, cap - 1)
    buckets = buckets.at[expert, safe_pos].add(
        jnp.where(keep[:, None], flat, 0.0))

    # all_to_all: [NE, cap, E] -> every ep rank gets its ne_loc experts'
    # buckets from all peers: [ep*ne_loc? ] reshape to route over ep
    if ep_size > 1:
        b = buckets.reshape(ep_size, ne_loc, cap, E)
        # split over ep peers, receive their buckets for MY experts:
        # [ne_loc, ep, cap, E]
        b = jax.lax.all_to_all(b, AXIS_EP, split_axis=0, concat_axis=1,
                               tiled=False)
        b = b.reshape(ne_loc, ep_size * cap, E)
    else:
        b = buckets.reshape(ne_loc, cap, E)

    # native-dtype operands on the MXU, f32 accumulate + f32 gelu
    # (upcasting b/we1 would force the multi-pass f32 matmul path)
    h = jax.nn.gelu(jnp.einsum(
        "nce,nef->ncf", b, we1,
        preferred_element_type=jnp.float32)).astype(x.dtype)
    y = jnp.einsum("ncf,nfe->nce", h, we2)
    y = jax.lax.psum(y, AXIS_TP)                           # row-parallel

    if ep_size > 1:
        y = y.reshape(ne_loc, ep_size, cap, E)
        y = jax.lax.all_to_all(y, AXIS_EP, split_axis=1, concat_axis=0,
                               tiled=False)
        y = y.reshape(NE, cap, E)
    else:
        y = y.reshape(NE, cap, E)

    out = y[expert, safe_pos] * gate[:, None].astype(x.dtype)
    return out.reshape(B, T, E)


def _pvary_all(x):
    """Mark x varying over every mesh axis (stabilizes lax.scan carry
    types when branches differ in collective use); no-op outside
    shard_map."""
    import jax

    try:
        have = set(jax.typeof(x).vma)
    except (AttributeError, TypeError):
        return x
    want = {AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP, AXIS_EP} - have
    if want:
        x = jax.lax.pcast(x, tuple(want), to="varying")
    return x


def _stage_fn(cfg, params_stage, x, tp_size, ep_size):
    """Run this pipeline stage's layers_per_stage layers over x via
    lax.scan (weights stacked on the layer axis)."""
    import jax

    x = _pvary_all(x)

    # the named scopes (here, and `embed` / `loss` / `adam` below) are
    # metadata on the device program's instructions: a trace's device
    # time can be summed by them (PERF.md)
    def layer(x, lw):
        with jax.named_scope("attn"):
            h = x + _attention(cfg, _rms_norm(x, lw["ln1"]),
                               lw["wq"], lw["wk"], lw["wv"], lw["wo"],
                               tp_size)
        with jax.named_scope("ffn"):
            z = _rms_norm(h, lw["ln2"])
            if cfg.n_experts:
                f = _moe_ffn(cfg, z, lw["router"], lw["we1"], lw["we2"],
                             ep_size)
            else:
                f = _dense_ffn(z, lw["w1"], lw["w2"])
            return h + f, None

    if cfg.remat != "none":
        from ..executor import apply_remat

        layer = apply_remat(layer, cfg.remat, prevent_cse=False)

    out, _ = jax.lax.scan(layer, x, params_stage)
    return out


def _sharded_xent(logits_loc, labels, vocab_shard_size):
    """Softmax cross-entropy with vocab sharded over tp: psum-based
    logsumexp; the label's logit found via global-index masking."""
    import jax
    import jax.numpy as jnp

    tp_idx = jax.lax.axis_index(AXIS_TP)
    lg = logits_loc.astype(jnp.float32)                  # [N, V/tp]
    # max is only for numerical stability: stop-gradient before the
    # collective (pmax has no AD rule)
    local_max = jax.lax.stop_gradient(lg.max(-1))
    gmax = jax.lax.pmax(local_max, AXIS_TP)
    lse = jnp.log(jax.lax.psum(
        jnp.exp(lg - gmax[:, None]).sum(-1), AXIS_TP)) + gmax
    local_label = labels - tp_idx * vocab_shard_size
    in_shard = (local_label >= 0) & (local_label < vocab_shard_size)
    label_logit = jax.lax.psum(
        jnp.where(in_shard,
                  jnp.take_along_axis(
                      lg, jnp.clip(local_label, 0,
                                   vocab_shard_size - 1)[:, None],
                      1)[:, 0],
                  0.0), AXIS_TP)
    return lse - label_logit                              # [N]


# ---------------------------------------------------------------------------
# full per-device train step (inside shard_map)


def _build_loss_fn(cfg: TransformerConfig, mesh, n_micro: int):
    """The per-device loss (inside shard_map): embed, this rank's stage
    of layers under the pipeline schedule, final norm, unembed, sharded
    cross-entropy.

    The schedule is GPipe's: n_micro + pp - 1 steps of a `fori_loop`,
    each running the stage on one microbatch and handing its output to
    the next rank by `ppermute`.  The loop exists only where it has more
    than one step; one stage with one microbatch (every single-chip
    run, any dp / tp / sp / ep mesh with pp = 1 and n_micro = 1) calls
    the stage once.  A one-trip loop is still differentiated as a scan,
    whose partial evaluation hoists what depends on loop constants only
    and so splits the layer scan into a second n_layers-trip loop that
    re-stacks the layer weights before every forward pass: gpt2-medium
    at B=8, T=1024 on a v5e spent 43.9 ms of its 277 ms step there
    (PERF.md, PR 27).  With more than one step that hoisted loop is
    still there (PERF.md, open questions)."""
    import jax
    import jax.numpy as jnp

    pp = mesh.shape[AXIS_PP]
    tp = mesh.shape[AXIS_TP]
    sp = mesh.shape[AXIS_SP]
    ep = mesh.shape[AXIS_EP]
    V_loc = cfg.vocab // tp
    grad_axes = _grad_psum_axes(cfg)

    def loss_fn(params, tokens, labels):
        """tokens/labels: local shard [B_loc, T_loc] (dp × sp)."""
        pp_idx = jax.lax.axis_index(AXIS_PP)
        sp_idx = jax.lax.axis_index(AXIS_SP)
        tp_idx = jax.lax.axis_index(AXIS_TP)
        B, T = tokens.shape
        if B % n_micro:
            raise MXNetError("local batch %d %% n_micro %d" % (B, n_micro))

        # vocab-sharded embedding lookup: local rows + psum over tp
        with jax.named_scope("embed"):
            local_tok = tokens - tp_idx * V_loc
            in_shard = (local_tok >= 0) & (local_tok < V_loc)
            emb = jnp.where(
                in_shard[..., None],
                params["embed"][jnp.clip(local_tok, 0, V_loc - 1)], 0.0)
            # exactly one tp shard contributes a non-zero row per token
            # (vocab-sharded one-hot), so a native-dtype psum is exact
            # and halves the ICI bytes vs upcasting to f32 first
            emb = jax.lax.psum(emb, AXIS_TP)
            pos_global = sp_idx * T + jnp.arange(T)
            x = (emb + params["pos"][pos_global][None]).astype(
                jnp.dtype(cfg.dtype))                     # [B, T, E]

        # my stage's layer stack: params["wq"][pp_idx] etc (leading pp
        # axis is sharded, so inside shard_map it has extent 1)
        stage_params = {}
        for name in ("wq", "wk", "wv", "wo", "ln1", "ln2", "w1", "w2",
                     "router", "we1", "we2"):
            if name in params:
                stage_params[name] = params[name][0]      # [lps, ...]

        is_last = (pp_idx == pp - 1)

        def run_stage(state):
            return _stage_fn(cfg, stage_params, state, tp, ep)

        n_steps = n_micro + pp - 1
        if n_steps == 1:
            # one stage, one microbatch: one call, and never a loop of
            # one trip (the docstring above says what that costs)
            h = run_stage(x)
        else:
            mb, E = B // n_micro, cfg.d_model
            x_mb = x.reshape(n_micro, mb, T, E)
            perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
            is_first = (pp_idx == 0)
            out_buf = _pvary_all(jnp.zeros((n_micro, mb, T, E), x.dtype))

            def step(s, carry):
                state, out_buf = carry
                feed = x_mb[jnp.clip(s, 0, n_micro - 1)]
                inp = jnp.where(is_first, feed, state)
                out = run_stage(inp)
                slot = jnp.clip(s - (pp - 1), 0, n_micro - 1)
                out_buf = out_buf.at[slot].set(
                    jnp.where(is_last, out, out_buf[slot]))
                state = jax.lax.ppermute(out, AXIS_PP, perm_fwd) \
                    if pp > 1 else out
                return state, out_buf

            state0 = _pvary_all(jnp.zeros((mb, T, E), x.dtype))
            _, out_buf = jax.lax.fori_loop(0, n_steps, step,
                                           (state0, out_buf))
            h = out_buf.reshape(B, T, E)

        # only the last stage's h is the real model output; psum the
        # masked loss over pp so every rank agrees (others contribute 0)
        with jax.named_scope("loss"):
            h = _rms_norm(h, params["ln_f"])
            logits = h @ params["unembed"]                # [B, T, V/tp]
            nll = _sharded_xent(logits.reshape(B * T, V_loc),
                                labels.reshape(B * T), V_loc)
            local_loss = nll.mean() * jnp.where(is_last, 1.0, 0.0)
            # mean over dp × sp shards; sum over pp picks the last
            # stage; ep ranks hold identical copies, so psum/ep is exact
            # (and makes the per-path gradient normalization come out
            # right for both ep-sharded expert weights and replicated
            # params)
            loss = jax.lax.psum(local_loss,
                                (AXIS_PP, AXIS_DP, AXIS_SP, AXIS_EP)) \
                / (mesh.shape[AXIS_DP] * sp * ep)
        return loss

    return loss_fn


def _build_device_step(cfg: TransformerConfig, mesh, n_micro: int,
                       lr: float):
    import jax
    import jax.numpy as jnp

    loss_fn = _build_loss_fn(cfg, mesh, n_micro)

    def device_step(params, tokens, labels):
        # shard_map AD auto-psums the cotangent of every input that is
        # replicated (invariant) along a mesh axis, so `grads` already
        # carry the cross-replica reduction — the explicit KVStore-style
        # allreduce of the reference (`kvstore_local.h:173`) is folded
        # into the transpose here.
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        new_params = {}
        for name, g in grads.items():
            new_params[name] = (params[name].astype(jnp.float32)
                                - lr * g.astype(jnp.float32)).astype(
                params[name].dtype)
        return new_params, loss

    return device_step


def _gather_delta(delta_my, full_shape, dp_idx, chunk, dim):
    """Reassemble the per-rank weight-update slices over dp.

    Preferred path: all_gather_invariant — half the wire bytes of an
    allreduce and the vma checker knows the result is replicated.  The
    public all_gather keeps the 'dp-varying' mark (a checker
    limitation), so when the invariant form is unavailable fall back to
    scatter + psum: correct, but allreduce-cost."""
    import jax.numpy as jnp
    from jax import lax

    try:
        from jax._src.lax.parallel import all_gather_invariant

        return all_gather_invariant(delta_my, AXIS_DP, axis=dim,
                                    tiled=True)
    except ImportError:
        full = jnp.zeros(full_shape, jnp.float32)
        full = lax.dynamic_update_slice_in_dim(full, delta_my,
                                               dp_idx * chunk, dim)
        return lax.psum(full, AXIS_DP)


def _build_adam_zero1_step(cfg: TransformerConfig, mesh, n_micro: int,
                           lr: float, betas=(0.9, 0.999), eps=1e-8):
    """ZeRO-1 sharded Adam (arxiv 2004.13336, 'automatic cross-replica
    sharding of the weight update'): each dp replica owns 1/dp of every
    Adam moment along the param's ZeRO dim, updates only its slice, and
    the weight DELTA is all-gathered over dp — moment memory shrinks by
    dp and the gather moves the same bytes an allreduce's second half
    would have."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    loss_fn = _build_loss_fn(cfg, mesh, n_micro)
    dp = mesh.shape[AXIS_DP]
    zdims = _zero1_dims(cfg, mesh)
    b1, b2 = betas

    def device_step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
        with jax.named_scope("adam"):
            return _adam(params, opt_state, grads, loss)

    def _adam(params, opt_state, grads, loss):
        dp_idx = lax.axis_index(AXIS_DP)
        t = opt_state["t"] + 1.0
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t
        new_p, new_m, new_v = {}, {}, {}
        for name, g in grads.items():
            p = params[name]
            g32 = g.astype(jnp.float32)
            m = opt_state["m"][name]
            v = opt_state["v"][name]
            dim = zdims[name]
            if dim is not None and dp > 1:
                chunk = p.shape[dim] // dp
                g_my = lax.dynamic_slice_in_dim(g32, dp_idx * chunk,
                                                chunk, dim)
            else:
                g_my = g32
            m = b1 * m + (1.0 - b1) * g_my
            v = b2 * v + (1.0 - b2) * g_my * g_my
            delta_my = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if dim is not None and dp > 1:
                delta = _gather_delta(delta_my, g32.shape, dp_idx,
                                      chunk, dim)
            else:
                delta = delta_my
            new_p[name] = (p.astype(jnp.float32) - delta).astype(p.dtype)
            new_m[name] = m
            new_v[name] = v
        return new_p, {"m": new_m, "v": new_v, "t": t}, loss

    return device_step


def _make_step_common(cfg, mesh, n_micro, lr, optimizer, betas, eps,
                      k_steps):
    """Shared plumbing for make_train_step / make_fused_train_steps:
    builds the per-device step (wrapped in a k_steps lax.scan when
    k_steps is not None), shard_maps + jits it with donation, and
    returns (step, shardings).  ONE copy of the spec/sharding layout so
    the fused and per-step paths cannot drift."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P, NamedSharding

    specs = param_specs(cfg)
    pspecs = {k: specs[k] for k in specs}
    data_spec = P(AXIS_DP, AXIS_SP) if k_steps is None \
        else P(None, AXIS_DP, AXIS_SP)
    shardings = {
        "params": {k: NamedSharding(mesh, v) for k, v in specs.items()},
        "data": NamedSharding(mesh, data_spec),
    }
    if optimizer not in ("sgd", "adam"):
        raise MXNetError("optimizer must be 'sgd' or 'adam' (got %r)"
                         % (optimizer,))
    if optimizer == "sgd":
        device_step = _build_device_step(cfg, mesh, n_micro, lr)
        if k_steps is None:
            device_fn = device_step
        else:
            def device_fn(params, toks_stack, labs_stack):
                def body(p, batch):
                    return device_step(p, batch[0], batch[1])

                return lax.scan(body, params, (toks_stack, labs_stack),
                                length=k_steps)

        sm = jax.shard_map(device_fn, mesh=mesh,
                           in_specs=(pspecs, data_spec, data_spec),
                           out_specs=(pspecs, P()))
        return jax.jit(sm, donate_argnums=(0,)), shardings

    device_step = _build_adam_zero1_step(cfg, mesh, n_micro, lr,
                                         betas=betas, eps=eps)
    if k_steps is None:
        device_fn = device_step
    else:
        def device_fn(params, opt_state, toks_stack, labs_stack):
            def body(carry, batch):
                p, o, loss = device_step(carry[0], carry[1],
                                         batch[0], batch[1])
                return (p, o), loss

            (params, opt_state), losses = lax.scan(
                body, (params, opt_state), (toks_stack, labs_stack),
                length=k_steps)
            return params, opt_state, losses

    ospecs = _opt_state_specs(cfg, mesh)
    ostate_specs = {"m": dict(ospecs), "v": dict(ospecs), "t": P()}
    sm = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(pspecs, ostate_specs, data_spec,
                                 data_spec),
                       out_specs=(pspecs, ostate_specs, P()))
    step = jax.jit(sm, donate_argnums=(0, 1))
    shardings["opt_state"] = {
        "m": {k: NamedSharding(mesh, v) for k, v in ospecs.items()},
        "v": {k: NamedSharding(mesh, v) for k, v in ospecs.items()},
        "t": NamedSharding(mesh, P()),
    }
    return step, shardings


def make_train_step(cfg: TransformerConfig, mesh, n_micro: int = 1,
                    lr: float = 1e-2, optimizer: str = "sgd",
                    betas=(0.9, 0.999), eps: float = 1e-8):
    """Jitted SPMD train step.

    optimizer="sgd" (default): (params, tokens, labels) ->
    (new_params, loss).

    optimizer="adam": ZeRO-1 sharded Adam —
    (params, opt_state, tokens, labels) ->
    (new_params, new_opt_state, loss), with `init_opt_state(cfg, mesh)`
    building the dp-sharded moments.  tokens/labels are globally
    [B, T], sharded (dp, sp) by the returned in-shardings."""
    return _make_step_common(cfg, mesh, n_micro, lr, optimizer, betas,
                             eps, k_steps=None)


def make_fused_train_steps(cfg: TransformerConfig, mesh, k_steps: int,
                           n_micro: int = 1, lr: float = 1e-2,
                           optimizer: str = "adam", betas=(0.9, 0.999),
                           eps: float = 1e-8):
    """K train steps lax.scan-fused into ONE compiled program — the
    transformer analog of `mxtpu.fused_train.FusedTrainLoop`
    (dispatch-latency amortization; one launch per K steps instead of
    K; its worth on a locally attached chip is not measured yet).
    Data arrives stacked: tokens/labels are [K, B, T], sharded
    (None, dp, sp).

    adam: (params, opt_state, toks_stack, labs_stack) ->
    (new_params, new_opt_state, losses[K]).
    sgd:  (params, toks_stack, labs_stack) -> (new_params, losses[K]).
    """
    k_steps = int(k_steps)
    if k_steps < 1:
        raise MXNetError("make_fused_train_steps: k_steps must be >= 1 "
                         "(got %d) — a zero-length scan would silently "
                         "train nothing" % k_steps)
    return _make_step_common(cfg, mesh, n_micro, lr, optimizer, betas,
                             eps, k_steps=k_steps)


def make_forward(cfg: TransformerConfig, mesh):
    """Jitted SPMD forward (logits) for inference/eval."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[AXIS_TP]
    V_loc = cfg.vocab // tp
    specs = param_specs(cfg)

    def fwd(params, tokens):
        # single-microbatch pipeline forward, then gather vocab shards
        pp_idx = jax.lax.axis_index(AXIS_PP)
        sp_idx = jax.lax.axis_index(AXIS_SP)
        tp_idx = jax.lax.axis_index(AXIS_TP)
        B, T = tokens.shape
        local_tok = tokens - tp_idx * V_loc
        in_shard = (local_tok >= 0) & (local_tok < V_loc)
        emb = jnp.where(in_shard[..., None],
                        params["embed"][jnp.clip(local_tok, 0,
                                                 V_loc - 1)], 0.0)
        # exactly one tp shard contributes a non-zero row per token
        # (vocab-sharded one-hot), so a native-dtype psum is exact
        # and halves the ICI bytes vs upcasting to f32 first
        emb = jax.lax.psum(emb, AXIS_TP)
        pos_global = sp_idx * T + jnp.arange(T)
        x = (emb + params["pos"][pos_global][None]).astype(
            jnp.dtype(cfg.dtype))
        stage_params = {k: params[k][0] for k in params
                        if params[k].ndim >= 3 and k not in
                        ("embed", "pos", "unembed")}
        pp = mesh.shape[AXIS_PP]
        state = x
        for s in range(pp):  # unrolled: stage s runs everywhere, keep
            out = _stage_fn(cfg, stage_params, state, tp,
                            mesh.shape[AXIS_EP])
            state = jnp.where(pp_idx == s, out, state)
            if pp > 1 and s < pp - 1:
                state = jax.lax.ppermute(
                    state, AXIS_PP,
                    [(i, (i + 1) % pp) for i in range(pp)])
        h = _rms_norm(state, params["ln_f"])
        logits = h @ params["unembed"]
        # only the last stage holds the real output: mask + psum to
        # replicate over pp; ep ranks are identical copies so psum/ep
        # replicates exactly.  The vocab dim stays tp-sharded — the out
        # spec reassembles it (no all_gather needed).
        ep = mesh.shape[AXIS_EP]
        logits = jax.lax.psum(
            jnp.where(pp_idx == pp - 1, logits, 0.0) / ep,
            (AXIS_PP, AXIS_EP))
        return logits

    sm = jax.shard_map(fwd, mesh=mesh,
                       in_specs=({k: v for k, v in specs.items()},
                                 P(AXIS_DP, AXIS_SP)),
                       out_specs=P(AXIS_DP, AXIS_SP, AXIS_TP))
    return jax.jit(sm)


# ---------------------------------------------------------------------------
# driver entry


def _dryrun_axis_configs(n_devices: int):
    """Axis-assignment rotation for `dryrun`: between them the configs
    exercise EVERY parallel axis (dp, pp, tp, sp, ep) at >=2 when the
    device count allows, instead of a single greedy split that leaves
    ep at 1."""
    def greedy(order, n):
        remaining = n
        out = {AXIS_DP: 1, AXIS_PP: 1, AXIS_TP: 1, AXIS_SP: 1, AXIS_EP: 1}
        for ax in order:
            if remaining % 2 == 0 and remaining >= 2:
                out[ax] = 2
                remaining //= 2
        out[AXIS_DP] *= remaining
        return out

    if n_devices == 1:
        return [greedy((), 1)]
    # config A: pipeline/tensor/sequence focus; config B: expert focus
    cfgs = [greedy((AXIS_PP, AXIS_TP, AXIS_SP), n_devices),
            greedy((AXIS_EP, AXIS_TP, AXIS_PP), n_devices)]
    if cfgs[1] == cfgs[0]:   # odd device counts: both collapse to pure dp
        cfgs.pop()
    return cfgs


def dryrun(n_devices: int, devices=None) -> None:
    """Compile + run ONE sharded train step on tiny shapes per axis
    config, rotating so every parallel axis (incl. ep) is exercised at
    >=2 where the device count allows.  Used by
    __graft_entry__.dryrun_multichip."""
    import numpy as np
    import jax

    for axes in _dryrun_axis_configs(n_devices):
        dp, pp, tp, sp, ep = (axes[AXIS_DP], axes[AXIS_PP], axes[AXIS_TP],
                              axes[AXIS_SP], axes[AXIS_EP])
        mesh = create_mesh(axes, devices=devices)
        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                n_layers=2 * pp, d_ff=64, n_experts=2,
                                max_len=16, dtype="float32")
        params = init_params(cfg, mesh, seed=0)
        step, sh = make_train_step(cfg, mesh, n_micro=2, lr=1e-2)
        B = 4 * dp
        T = 8 * sp
        rng = np.random.RandomState(0)
        tokens = jax.device_put(
            rng.randint(0, cfg.vocab, (B, T)).astype(np.int32),
            sh["data"])
        labels = jax.device_put(
            rng.randint(0, cfg.vocab, (B, T)).astype(np.int32),
            sh["data"])
        params, loss = step(params, tokens, labels)
        loss_val = float(jax.device_get(loss))
        if not np.isfinite(loss_val):
            raise MXNetError(
                "dryrun produced non-finite loss (axes=%r)" % (axes,))
        jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])

    if n_devices >= 2 and n_devices % 2 == 0:
        # ZeRO-1 sharded-Adam path needs dp>=2 (the rotation above
        # spends its factors on pp/tp/sp/ep): one dedicated config with
        # dp-sharded moments and the gathered weight delta
        rest = n_devices // 2
        tp2 = 2 if rest % 2 == 0 else 1
        sp2 = rest // tp2
        axes = {AXIS_DP: 2, AXIS_PP: 1, AXIS_TP: tp2, AXIS_SP: sp2,
                AXIS_EP: 1}
        mesh = create_mesh(axes, devices=devices)
        cfg = TransformerConfig(vocab=64, d_model=64, n_heads=4,
                                n_layers=2, d_ff=128, max_len=16,
                                dtype="float32")
        params = init_params(cfg, mesh, seed=0)
        astep, ash = make_train_step(cfg, mesh, n_micro=2, lr=1e-2,
                                     optimizer="adam")
        opt = init_opt_state(cfg, mesh)
        rng = np.random.RandomState(1)
        B, T = 4 * 2, 8 * sp2
        tokens = jax.device_put(
            rng.randint(0, cfg.vocab, (B, T)).astype(np.int32),
            ash["data"])
        labels = jax.device_put(
            rng.randint(0, cfg.vocab, (B, T)).astype(np.int32),
            ash["data"])
        params, opt, aloss = astep(params, opt, tokens, labels)
        if not np.isfinite(float(jax.device_get(aloss))):
            raise MXNetError("dryrun ZeRO-1 adam produced non-finite "
                             "loss (axes=%r)" % (axes,))


def dryrun_parity(n_devices: int, devices=None, rtol: float = 2e-4,
                  full: bool = True):
    """Per-axis loss-parity sweep (VERDICT r4 next #6): the SAME model,
    init seed, and global batch must produce the SAME first-step loss
    no matter which mesh axis the devices are spent on — dp / tp / sp /
    ep each compared against the single-axis gold, and the GPipe
    microbatch count must be loss-invariant at fixed global batch.

    Catches the class of sharding bug the single-shape dryrun can't:
    a wrong PartitionSpec or a missed psum produces a *finite but
    different* loss.  Returns {config_name: loss} for reporting."""
    import numpy as np
    import jax

    if devices is None:
        devices = jax.devices()

    def one_loss(axes, n_micro=1, seed=0):
        mesh = create_mesh(axes, devices=devices[:int(
            np.prod(list(axes.values())))])
        cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                n_layers=2 * axes[AXIS_PP], d_ff=64,
                                n_experts=2, max_len=16,
                                dtype="float32")
        params = init_params(cfg, mesh, seed=seed)
        step, sh = make_train_step(cfg, mesh, n_micro=n_micro, lr=1e-2)
        rng = np.random.RandomState(42)
        B, T = 8, 16
        tokens = jax.device_put(
            rng.randint(0, cfg.vocab, (B, T)).astype(np.int32),
            sh["data"])
        labels = jax.device_put(
            rng.randint(0, cfg.vocab, (B, T)).astype(np.int32),
            sh["data"])
        _, loss = step(params, tokens, labels)
        return float(jax.device_get(loss))

    base = {AXIS_DP: 1, AXIS_PP: 1, AXIS_TP: 1, AXIS_SP: 1, AXIS_EP: 1}
    losses = {"gold_1dev": one_loss(dict(base))}

    def run(name, **over):
        axes = dict(base)
        axes.update(over)
        need = int(np.prod(list(axes.values())))
        if need > n_devices:
            return
        losses[name] = one_loss(axes)
        if not np.isclose(losses[name], losses["gold_1dev"], rtol=rtol):
            raise MXNetError(
                "loss parity violation on %s: %.6f vs gold %.6f"
                % (name, losses[name], losses["gold_1dev"]))

    # core (every axis + one composite) runs in tier-1; `full` adds the
    # larger-factor and triple-composite configs that re-exercise the
    # same partition rules (tp4 = tp2's rule at factor 4, dp2_sp2_ep2
    # composes pairwise-proven axes) — nightly/slow tier only
    run("dp%d" % min(n_devices, 8), **{AXIS_DP: min(n_devices, 8)})
    run("tp2", **{AXIS_TP: 2})
    if full:
        run("tp4", **{AXIS_TP: 4})
    run("sp2", **{AXIS_SP: 2})
    run("ep2", **{AXIS_EP: 2})
    run("dp2_tp2", **{AXIS_DP: 2, AXIS_TP: 2})
    if full:
        run("dp2_sp2_ep2" if n_devices >= 8 else "dp2_sp2",
            **({AXIS_DP: 2, AXIS_SP: 2, AXIS_EP: 2} if n_devices >= 8
               else {AXIS_DP: 2, AXIS_SP: 2}))

    # pipeline group: init layout depends on pp, so pp configs compare
    # against a pp=2 gold — dp-extension and the GPipe microbatch count
    # must both be loss-neutral
    if n_devices >= 2:
        pp_axes = dict(base)
        pp_axes[AXIS_PP] = 2
        gold_pp = one_loss(pp_axes, n_micro=1)
        losses["gold_pp2_m1"] = gold_pp
        for n_micro in ((2, 4) if full else (2,)):
            l = one_loss(pp_axes, n_micro=n_micro)
            losses["pp2_m%d" % n_micro] = l
            if not np.isclose(l, gold_pp, rtol=rtol):
                raise MXNetError(
                    "microbatch parity violation: pp2 n_micro=%d "
                    "%.6f vs %.6f" % (n_micro, l, gold_pp))
        if n_devices >= 4:
            pd = dict(pp_axes)
            pd[AXIS_DP] = 2
            l = one_loss(pd, n_micro=2)
            losses["pp2_dp2_m2"] = l
            if not np.isclose(l, gold_pp, rtol=rtol):
                raise MXNetError(
                    "loss parity violation on pp2_dp2: %.6f vs %.6f"
                    % (l, gold_pp))
    return losses
