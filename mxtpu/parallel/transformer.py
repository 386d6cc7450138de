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

from .. import profiler as _prof
from ..base import MXNetError
from .mesh import create_mesh, AXIS_DP, AXIS_TP, AXIS_PP, AXIS_SP, AXIS_EP
from .ring_attention import ring_attention, _match_vma, _pallas_enabled

__all__ = ["TransformerConfig", "init_params", "param_specs",
           "make_train_step", "make_fused_train_steps", "make_forward",
           "dryrun", "init_opt_state", "param_shapes", "MOE_STATS",
           "GROUP_STATS", "KDA_STATS", "KDA_NEG_STATS", "LOOP_STATS",
           "publish_moe_stats"]

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
    # on one chip); "dots" saves the products' outputs (the blocks
    # name them: `_kept`) and the flash kernel's merged output and
    # log-sums, and recomputes the elementwise chains between them
    # and ONE product per attention block, the cheapest to rebuild
    # per byte, which pays for the kernel's output: `o @ wo` in
    # `_attention`, `c_q @ wq_b` in `_mla` (`c_kv @ wkv_b` where q has
    # no bottleneck), `x @ w_gate` in `_gqa` (q's where it has no
    # gate).  So the backward pass
    # runs no second forward kernel.  Analog of the reference's
    # MXNET_BACKWARD_DO_MIRROR (docs/faq/env_var.md) which this
    # repo's symbolic executor exposes as MXTPU_BACKWARD_DO_MIRROR;
    # same policy vocabulary (`executor.apply_remat`).

    # ---- layer kinds beyond the square-attention GELU block.  A config
    # that sets none of the fields below builds the program it built
    # before they existed.
    norm_eps: float = 1e-6
    attention: str = "mha"     # "mha": q / k / v of width d_model //
    # n_heads, a learned position table.  "gqa": n_heads q heads on
    # n_kv_heads kv heads of head_dim (q head h meets kv head h //
    # (n_heads / n_kv_heads)), rotary positions over the whole head
    # (rope_theta, rotate-half), no position table; `qk_norm`,
    # `out_gate`, `window` / `full_period` are its.  "mla": latent
    # attention —
    # low-rank q with a norm between (q_lora_rank), one compressed kv
    # (kv_lora_rank, normed) plus one rotary key shared by the heads,
    # decompressed to per-head k_nope (qk_nope_dim) and v (v_head_dim);
    # rotary positions (rope_theta, rotate-half) on the qk_rope_dim
    # parts, and no position table.  The attention kernels take one
    # width for q.k and v: qk_nope_dim + qk_rope_dim == v_head_dim.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    rope_theta: float = 10000.0
    ffn: str = "gelu"          # "gelu": a pair (w1, w2).  "swiglu":
    # silu(x Wg) * (x Wu) -> Wd — dense layers, routed experts and the
    # shared expert alike.  It also states the routed experts' dispatch:
    # GELU experts go through the capacity-bucketed top-1 exchange
    # (`_experts_bucketed`, any ep; DROPS over capacity_factor), gated
    # ones through grouped products over the held experts
    # (`_experts_grouped`: no token dropped, no capacity_factor; ep = 1:
    # the sorted pairs are walked in blocks of a size derived from the
    # stated load, as many as the step's own routing needs, up to all
    # n_tok * top_k pairs)
    n_dense_layers: int = 0    # leading dense layers (width d_ff) in
    # front of the expert layers: a segment of the stack with its own
    # scan and its own parameter names ("dense.<leaf>")
    d_expert: int = 0          # a routed expert's width; 0 = d_ff
    top_k: int = 1             # experts per token
    moe_score: str = "softmax"  # or "sigmoid"
    moe_select_bias: bool = False   # a per-expert bias ("router_bias")
    # added to the scores for SELECTION only; no gradient reaches it
    moe_norm_topk: bool = False     # weights / their sum over the top_k
    moe_scale: float = 1.0          # ... times this
    n_shared_experts: int = 0  # a shared expert of width n * d_expert,
    # added for every token
    expert_first: int = 0      # the range of experts this layer holds:
    experts_held: int = 0      # [first, first + held); 0 = all.  The
    # router still scores all n_experts; the layer computes its own
    # experts' part of the result (one chip's share of an expert-
    # parallel deployment, run without the exchange)
    mtp_depth: int = 0         # 1: a multi-token-prediction block
    # (DeepSeek-V3's form) after the stack, on the shared embedding and
    # head: params "mtp.<leaf>"; its loss is added times mtp_weight
    mtp_weight: float = 0.3
    n_group: int = 1           # group-limited selection (DeepSeek-V3's
    topk_group: int = 1        # `noaux_tc`): the experts lie in n_group
    # groups of consecutive ids, a group's score is the sum of its two
    # best selection scores, and top_k is taken inside the topk_group
    # best groups.  n_group = 1 is the plain top_k, traced as before

    # ---- a stack of TWO mixer kinds (Kimi Linear's hybrid): with
    # kda_period = p > 0 the layer of published index i runs `attention`
    # where i % p == kda_attn_at % p and Kimi Delta Attention (`_kda`)
    # otherwise.
    # Each run of layers of one (mixer, ffn) kind is a segment; layers of
    # one kind share stacked leaves: KDA layers are "kda.<leaf>" (leading
    # dense ones "dense.kda.<leaf>")
    kda_period: int = 0
    layer_ids: Tuple[int, ...] = ()     # the published indices of the
    # n_layers held here, ascending (a pipeline stage's share of a deeper
    # model; the first n_dense_layers of them are the dense ones);
    # () = 0 .. n_layers - 1
    kda_head_dim: int = 0      # d_k = d_v of a KDA head; 0 = d_model //
    # n_heads
    kda_conv: int = 4          # taps of the causal depthwise convolutions
    kda_gate_floor: float = -5.0    # the log-decay of one step lies in
    # (floor, 0): floor * sigmoid(exp(A_log) * (x Wa + dt_bias))
    kda_chunk: int = 64        # tokens a chunk of the chunked form holds
    kda_rebase: int = 16       # ... and the steps after which the
    # cumulative log-decay is re-based: a span's rebase * -floor nats
    # (80) are split about its middle, so `exp` sees +-40 at most
    kda_attn_at: int = -1      # the attention layer's place in a period:
    # -1 its last (Kimi Linear's, Ling's), 0 its first (Solar Open 2's)
    kda_gate: str = "floor"    # the log-decay's form.  "floor": above,
    # bounded below by kda_gate_floor, the chunks re-based every
    # kda_rebase steps.  "softplus": -exp(A_log) * softplus(logit +
    # dt_bias), with NO lower bound: the chunks' scores are based at the
    # boundaries of halves (`_scores_by_halves`), exact for any decay;
    # kda_chunk a power of two, kda_gate_floor and kda_rebase unused
    kda_gate_rank: int = 0     # 0: the decay's logits from one full
    # matrix ("wa"), the output gated per head ("w_gate").  r > 0: both
    # through a rank-r bottleneck ("f_a" / "f_b", "g_a" / "g_b"), the
    # output gate elementwise over each head's values
    kda_neg_eigval: bool = False    # the write strength 2 sigmoid(x Wb)
    # in (0, 2): transitions I - beta k k^T with eigenvalues down to -1
    qk_norm: bool = False      # mla: q_nope and k_nope RMS-normed per
    # head, one [qk_nope_dim] scale each; gqa: q and k, one [head_dim]
    # scale each
    head_gate: bool = False    # mla: a sigmoid gate per head on the
    # attention's output, from x (KDA always has one)

    # ---- grouped kv heads, and window and full layers mixed by a
    # period ("gqa"): the layer of published index i attends over the
    # whole causal prefix where (i + 1) % full_period == 0 and over the
    # last `window` keys (the query's own among them) otherwise.  A full
    # layer's leaves are "full.<leaf>": each run of layers of one kind is
    # a segment, as with kda_period
    n_kv_heads: int = 0        # 0 = n_heads
    head_dim: int = 0          # a q / k / v head's width; 0 = d_model //
    # n_heads
    window: int = 0            # 0: every layer attends over the whole
    # prefix
    full_period: int = 0       # 0 with a window: every layer slides
    rope_full: bool = True     # False: the full layers carry NO
    # positions (the window layers' rotary ones give the order); with
    # no window every layer is a full one and the model has no
    # positions at all (NoPE)
    out_gate: bool = False     # gqa: the merged attention output times
    # sigmoid(x W_gate), elementwise, before the out product
    post_norms: bool = False   # an RMSNorm AFTER each sub-block too
    # ("ln1_post", "ln2_post"), on what it adds to the residual: four
    # norms a layer
    embed_scale: float = 1.0   # the embedding's rows times this

    # ---- a looped stack (Ouro's LoopLM): the whole stack runs
    # loop_passes times on the same weights.  Each pass ends in ln_f, whose
    # output is fed to the next pass, a head and a cross-entropy, and a
    # gate ("exit_gate.w" / ".b": lambda_t = sigmoid(x_t . w + b)); the
    # passes are combined by the exit distribution p_t = lambda_t prod_{j<t}
    # (1 - lambda_j) (the last pass takes what is left) into the expected
    # loss less exit_entropy times the distribution's entropy.  1: the
    # plain stack, one head, no gate
    loop_passes: int = 1
    exit_entropy: float = 0.1

    def __post_init__(self):
        from ..executor import _REMAT_POLICIES

        if self.remat != "none" and self.remat not in _REMAT_POLICIES:
            raise MXNetError(
                "TransformerConfig.remat must be 'none' or one of %s "
                "(got %r)" % (sorted(_REMAT_POLICIES), self.remat))
        bad = None
        if self.attention not in ("mha", "mla", "gqa"):
            bad = "attention must be 'mha', 'mla' or 'gqa'"
        elif self.attention != "gqa" and (
                self.n_kv_heads or self.head_dim or self.window
                or self.full_period or self.out_gate or self.post_norms
                or not self.rope_full):
            bad = ("n_kv_heads, head_dim, window, full_period, rope_full, "
                   "out_gate and post_norms are attention='gqa's")
        elif self.attention == "gqa" and (
                self.n_kv_heads < 0
                or self.n_heads % (self.n_kv_heads or self.n_heads)
                or (self.head_dim or self.d_model // self.n_heads) % 2
                or self.window < 0 or self.full_period < 0
                or (self.full_period and not self.window)
                or self.full_period == 1
                or (not self.rope_full and self.window
                    and not self.full_period)
                or (self.kda_period and self.window)):
            bad = ("attention='gqa' needs n_kv_heads dividing n_heads, an "
                   "even head_dim, full_period >= 2 only with a window, "
                   "rope_full=False beside a window only with a "
                   "full_period, and no window beside a kda_period")
        elif self.attention == "mla" and (
                min(self.kv_lora_rank, self.qk_nope_dim,
                    self.v_head_dim) < 1 or self.q_lora_rank < 0
                or self.qk_rope_dim < 2 or self.qk_rope_dim % 2):
            bad = ("attention='mla' needs kv_lora_rank, qk_nope_dim, "
                   "v_head_dim and an even qk_rope_dim (q_lora_rank 0 "
                   "is q from one matrix)")
        elif self.head_gate and self.attention != "mla" or (
                self.qk_norm and self.attention == "mha"):
            bad = ("head_gate is latent attention's, qk_norm latent "
                   "attention's and 'gqa's")
        elif self.kda_period and (
                self.kda_period < 2 or self.kda_chunk < 1
                or self.kda_gate not in ("floor", "softplus")
                or (self.kda_gate == "floor" and (
                    self.kda_rebase < 1 or self.kda_chunk % self.kda_rebase
                    or not 0 < -self.kda_gate_floor * self.kda_rebase
                    <= 80))
                or (self.kda_gate == "softplus"
                    and self.kda_chunk & (self.kda_chunk - 1))
                or self.kda_gate_rank < 0 or self.kda_conv < 1
                or (self.kda_head_dim or self.d_model // self.n_heads) < 1):
            bad = ("kda_period needs a period >= 2 and kda_gate 'floor' "
                   "or 'softplus'; 'floor' needs kda_chunk a multiple of "
                   "kda_rebase and -kda_gate_floor * kda_rebase in (0, "
                   "80] (exp() of a re-based span must stay finite in "
                   "float32), 'softplus' a kda_chunk that is a power of "
                   "two")
        elif not self.kda_period and (
                self.kda_attn_at != -1 or self.kda_gate != "floor"
                or self.kda_gate_rank or self.kda_neg_eigval):
            bad = ("kda_attn_at, kda_gate, kda_gate_rank and "
                   "kda_neg_eigval are a kda_period's")
        elif self.layer_ids and (
                len(self.layer_ids) != self.n_layers
                or list(self.layer_ids) != sorted(set(self.layer_ids))
                or self.layer_ids[0] < 0):
            bad = ("layer_ids are the n_layers published indices held "
                   "here, ascending")
        elif self.ffn not in ("gelu", "swiglu"):
            bad = "ffn must be 'gelu' or 'swiglu'"
        elif self.moe_score not in ("softmax", "sigmoid"):
            bad = "moe_score must be 'softmax' or 'sigmoid'"
        elif self.mtp_depth not in (0, 1):
            bad = "mtp_depth must be 0 or 1"
        elif self.loop_passes < 1 or (self.loop_passes > 1
                                      and self.mtp_depth):
            bad = ("loop_passes must be >= 1, and a looped stack has a head "
                   "at every pass and no multi-token-prediction block")
        elif self.n_dense_layers and not (
                self.n_experts and 0 < self.n_dense_layers < self.n_layers):
            bad = ("n_dense_layers are the layers in front of the expert "
                   "layers: it needs n_experts and fewer than n_layers")
        elif self.n_experts:
            held = self.experts_held or self.n_experts
            if not 1 <= self.top_k <= self.n_experts:
                bad = "top_k must be in 1..n_experts"
            elif self.expert_first < 0 or \
                    self.expert_first + held > self.n_experts:
                bad = "the held experts lie outside 0..n_experts"
            elif self.n_group < 1 or self.n_experts % self.n_group \
                    or not 1 <= self.topk_group <= self.n_group \
                    or (self.n_group > 1 and (
                        self.n_experts // self.n_group < 2 or self.top_k
                        > self.topk_group * (self.n_experts
                                             // self.n_group))):
                bad = ("n_group must divide n_experts into groups of at "
                       "least 2, topk_group lie in 1..n_group, and top_k "
                       "fit inside the kept groups")
            elif self.ffn == "gelu" and (
                    self.top_k != 1 or self.experts_held
                    or self.n_shared_experts):
                bad = ("GELU experts run the capacity-bucketed exchange, "
                       "which is top-1 over all experts; top_k > 1, a held "
                       "range and a shared expert need ffn='swiglu' (the "
                       "grouped, dropless dispatch)")
        if bad:
            raise MXNetError("TransformerConfig: " + bad)


def _kind_parts(cfg: TransformerConfig, kind: str) -> Tuple[str, str]:
    """(mixer, feed-forward) of a segment's kind (see `_segments`): the
    mixer "kda", or the config's `attention` ("full+" in front marks its
    full-attention layers in a stack that mixes them with window layers:
    `_is_full`); "dense" or "moe"."""
    tag, _, ffn = kind.rpartition("+")
    return ("kda" if tag == "kda" else cfg.attention), ffn


def _is_full(kind: str) -> bool:
    """Whether a segment's kind is the FULL-attention layers' of a stack
    that has window layers too (`full_period`)."""
    return kind.startswith("full+")


def _segments(cfg: TransformerConfig):
    """The stack in segments, each a homogeneous run of layers with a
    `lax.scan` of its own, in the model's order: [(parameter-name
    prefix, kind, first, layers)].  `kind` is "dense" or "moe" (the
    feed-forward), with "kda+" in front where the mixer is Kimi Delta
    Attention and not the config's `attention`, or "full+" where the
    layer is a full-attention one among window layers (`full_period`).
    Layers of one prefix
    share stacked leaves and a segment holds rows [first, first +
    layers) of them (a stack of two mixer kinds comes back to a kind
    once per period).  The model's repeated layer kind keeps the bare
    leaf names; leading dense layers are "dense.<leaf>", KDA layers
    "kda.<leaf>" and full-attention layers among window layers
    "full.<leaf>" behind that, the multi-token-prediction block's layer
    "mtp.<leaf>"."""
    ffn = "moe" if cfg.n_experts else "dense"
    ids = cfg.layer_ids or range(cfg.n_layers)
    segs, rows = [], {}
    for j, i in enumerate(ids):
        lead = j < cfg.n_dense_layers
        kda = bool(cfg.kda_period) and \
            (i - cfg.kda_attn_at) % cfg.kda_period != 0
        full = bool(cfg.full_period) and (i + 1) % cfg.full_period == 0
        tag = "kda" if kda else "full" if full else ""
        prefix = ("dense." if lead else "") + (tag and tag + ".")
        kind = (tag and tag + "+") + ("dense" if lead else ffn)
        if segs and segs[-1][0] == prefix:
            segs[-1][3] += 1
        else:
            segs.append([prefix, kind, rows.get(prefix, 0), 1])
        rows[prefix] = rows.get(prefix, 0) + 1
    if cfg.mtp_depth:
        segs.append(["mtp.", ffn, 0, cfg.mtp_depth])
    return [tuple(seg) for seg in segs]


def _stacks(cfg: TransformerConfig):
    """{prefix: (kind, layers)}: the stacked leaves' depth by prefix,
    over all of the prefix's segments."""
    out = {}
    for prefix, kind, _, n in _segments(cfg):
        out[prefix] = (kind, out.get(prefix, (kind, 0))[1] + n)
    return out


def _layer_leaves(cfg: TransformerConfig, kind: str):
    """{leaf: (shape, spec, fan_in)} of ONE layer of `kind` (see
    `_segments`): Megatron layout on tp (columns of the first product,
    rows of the last), experts on ep.  fan_in None marks a norm scale
    (ones), "A_log" the KDA heads' decay rates (log of U(1, 16)),
    "dt_bias" the decay gate's bias (the inverse softplus of a step
    drawn log-uniformly from (1e-3, 1e-1): slow decays at the start)."""
    E, H = cfg.d_model, cfg.n_heads
    col, row = (None, AXIS_TP), (AXIS_TP, None)
    out = {"ln1": ((E,), (None,), None), "ln2": ((E,), (None,), None)}
    if cfg.post_norms:
        out.update({"ln1_post": ((E,), (None,), None),
                    "ln2_post": ((E,), (None,), None)})
    mixer, kind = _kind_parts(cfg, kind)
    if mixer == "kda":
        d = cfg.kda_head_dim or E // H
        for n in ("q", "k", "v"):
            out["w" + n] = ((E, H * d), col, E)
            out["conv_" + n] = ((cfg.kda_conv, H * d), col, cfg.kda_conv)
        r = cfg.kda_gate_rank
        if r:       # the decay's logits and the output gate, low-rank
            for n in ("f", "g"):
                out[n + "_a"] = ((E, r), (None, None), E)
                out[n + "_b"] = ((r, H * d), col, r)
        else:
            out["wa"] = ((E, H * d), col, E)     # the decay gate, full
        out.update({
            "A_log": ((H,), (AXIS_TP,), "A_log"),
            "dt_bias": ((H * d,), (AXIS_TP,), "dt_bias"),
            "wb": ((E, H), col, E)})             # write strength
        if not r:
            out["w_gate"] = ((E, H), col, E)     # the output's head gate
        out.update({"o_norm": ((d,), (None,), None),
                    "wo": ((H * d, E), row, H * d)})
    elif mixer == "mla":
        ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        if ql:
            out.update({
                "wq_a": ((E, ql), (None, None), E),
                "q_norm": ((ql,), (None,), None),
                "wq_b": ((ql, H * (dn + dr)), col, ql)})
        else:
            out["wq"] = ((E, H * (dn + dr)), col, E)
        out.update({
            "wkv_a": ((E, kvl + dr), (None, None), E),
            "kv_norm": ((kvl,), (None,), None),
            "wkv_b": ((kvl, H * (dn + dv)), col, kvl),
            "wo": ((H * dv, E), row, H * dv)})
        if cfg.qk_norm:
            out["q_nope_norm"] = ((dn,), (None,), None)
            out["k_nope_norm"] = ((dn,), (None,), None)
        if cfg.head_gate:
            out["w_gate"] = ((E, H), col, E)
    elif mixer == "gqa":
        d, Hkv = cfg.head_dim or E // H, cfg.n_kv_heads or H
        out.update({"wq": ((E, H * d), col, E),
                    "wk": ((E, Hkv * d), col, E),
                    "wv": ((E, Hkv * d), col, E),
                    "wo": ((H * d, E), row, H * d)})
        if cfg.qk_norm:
            out["q_norm"] = ((d,), (None,), None)
            out["k_norm"] = ((d,), (None,), None)
        if cfg.out_gate:
            out["w_gate"] = ((E, H * d), col, E)
    else:
        out.update({n: ((E, E), col, E) for n in ("wq", "wk", "wv")})
        out["wo"] = ((E, E), row, E)

    def ffn(names, width, lead=(), lead_spec=()):
        first, last = (names[:-1], names[-1])
        for n in first:
            out[n] = (lead + (E, width), lead_spec + col, E)
        out[last] = (lead + (width, E), lead_spec + row, width)

    pair = cfg.ffn == "gelu"
    if kind == "dense":
        ffn(("w1", "w2") if pair else ("wg", "wu", "wd"), cfg.d_ff)
        return out
    NE, Fe = cfg.n_experts, cfg.d_expert or cfg.d_ff
    out["router"] = ((E, NE), (None, None), E)
    if cfg.moe_select_bias:
        out["router_bias"] = ((NE,), (None,), 1e4)    # drawn small
    ffn(("we1", "we2") if pair else ("we_g", "we_u", "we_d"), Fe,
        (cfg.experts_held or NE,), (AXIS_EP,))
    if cfg.n_shared_experts:
        ffn(("ws_g", "ws_u", "ws_d"), cfg.n_shared_experts * Fe)
    return out


def _leaves(cfg: TransformerConfig, pp: int):
    """{parameter: (global shape, PartitionSpec, fan_in)}: the single
    source `param_shapes`, `param_specs` and `init_params` share.  A
    layer leaf is stacked [pp, layers per stage, ...]; a stack in more
    than one segment lives on one stage."""
    from jax.sharding import PartitionSpec as P

    if cfg.n_layers % pp:
        raise MXNetError("n_layers=%d not divisible by pp=%d"
                         % (cfg.n_layers, pp))
    stacks = _stacks(cfg)
    if len(stacks) > 1 and pp > 1:
        raise MXNetError("a stack in segments (leading dense layers, two "
                         "mixer kinds, a multi-token-prediction block) "
                         "needs pp = 1")
    E, V = cfg.d_model, cfg.vocab
    out = {"embed": ((V, E), P(AXIS_TP, None), E),   # vocab-sharded
           "ln_f": ((E,), P(None), None),
           "unembed": ((E, V), P(None, AXIS_TP), E)}
    if cfg.attention == "mha":                     # rotary: no table
        out["pos"] = ((cfg.max_len, E), P(None, None), E)
    for prefix, (kind, n) in stacks.items():
        for leaf, (shape, spec, fan_in) in _layer_leaves(cfg,
                                                         kind).items():
            out[prefix + leaf] = ((pp, n // pp) + shape,
                                  P(AXIS_PP, None, *spec), fan_in)
    if cfg.mtp_depth:
        out["mtp.eh"] = ((2 * E, E), P(None, None), 2 * E)
        for leaf in ("mtp.ln_e", "mtp.ln_h", "mtp.ln_f"):
            out[leaf] = ((E,), P(None), None)
    if cfg.loop_passes > 1:         # the exit gate, its bias starting at 0
        out["exit_gate.w"] = ((E,), P(None), E)
        out["exit_gate.b"] = ((), P(), "zeros")
    return out


# per step, over every expert layer (the MTP block's too): token-expert
# pairs the router put in the held range; tokens routed (tokens x expert
# layers); the fullest held expert's pairs in any one layer; rows the
# dispatch walked (blocks x the block's rows: `_experts_grouped`)
MOE_STATS = ("moe_pairs", "moe_tokens", "moe_load_max", "moe_rows_walked")
# with group-limited selection: tokens x expert layers whose kept groups
# include the group of the first expert held here
GROUP_STATS = ("moe_groups_kept_here",)
# per step, over every KDA layer: tokens mixed; chunks scanned; the
# largest |cumulative log-decay| of any re-based span, in nats (what
# `exp` is given: 80 is the config's bound, 88.7 float32's cliff), or
# with the unbounded gate of any chunk (how far past 88.7 the chunks went)
KDA_STATS = ("kda_tokens", "kda_chunks", "kda_decay_span_max")
# with negative eigenvalues allowed: (token, head) write strengths over 1
KDA_NEG_STATS = ("kda_neg_beta",)
# per step of a looped stack (`loop_passes` > 1): the passes run; the
# tokens; over the tokens, the pass each is expected to exit at under the
# exit distribution (sum_t t p_t) and the mass left to the last pass (p_U)
LOOP_STATS = ("loop_passes", "loop_tokens", "loop_exit_expect",
              "loop_exit_last")
_WATERMARKS = ("moe_load_max", "kda_decay_span_max")    # max, not sum
_COPIES = ("loop_passes",)      # the same on every shard: not summed


def _stat_names(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The per-step counters the step returns beside its loss: the
    routed experts' where the expert layers run the grouped, dropless
    dispatch (`_experts_grouped`: gated experts), the KDA layers' where
    the stack has them, the loop's where the stack runs more than once.
    () for every other config, whose program's outputs are as they
    were."""
    names = ()
    if cfg.n_experts and cfg.ffn == "swiglu":
        names += MOE_STATS + (GROUP_STATS if cfg.n_group > 1 else ())
    if any(_kind_parts(cfg, kind)[0] == "kda"
           for _, kind, _, _ in _segments(cfg)):
        names += KDA_STATS + (KDA_NEG_STATS if cfg.kda_neg_eigval else ())
    if cfg.loop_passes > 1:
        names += LOOP_STATS
    return names


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

    leaves = _leaves(cfg, mesh.shape[AXIS_PP])  # single source (+div check)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 16)
    dt = jnp.dtype(cfg.dtype)
    out = {}
    for i, (name, (shape, spec, fan_in)) in enumerate(
            sorted(leaves.items())):
        k = ks[i] if i < 16 else jax.random.fold_in(key, i)
        if fan_in is None:                # norm scales start at one
            arr = jnp.ones(shape, dt)
        elif fan_in == "zeros":
            arr = jnp.zeros(shape, dt)
        elif fan_in == "A_log":           # decay rates in (1, 16)
            arr = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                             16.0)).astype(dt)
        elif fan_in == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            arr = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        else:
            arr = (jax.random.normal(k, shape, jnp.float32)
                   * (1.0 / fan_in) ** 0.5).astype(dt)
        out[name] = jax.device_put(arr, NamedSharding(mesh, spec))
    return out


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpec per parameter (Megatron layout on tp, stage-stacked
    on pp, experts on ep)."""
    return {name: spec for name, (_, spec, _) in _leaves(cfg, 1).items()}


def param_shapes(cfg: TransformerConfig, pp: int) -> Dict[str, Tuple]:
    """Global parameter shapes — the single source init_params and the
    optimizer-state builders share."""
    return {name: shape for name, (shape, _, _) in _leaves(cfg,
                                                           pp).items()}


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


def _rms_norm(x, scale, eps=1e-6):
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    var = (x32 * x32).mean(-1, keepdims=True)
    return (x32 * jnp.reciprocal(jnp.sqrt(var + eps))).astype(x.dtype) \
        * scale


def _kept(y):
    """Name a product's output for `remat="dots"` to keep
    (`executor.apply_remat`).  Every product of a layer goes through
    here but the one its attention block gives back; under any other
    policy, and outside `jax.checkpoint`, the name is a no-op."""
    from jax.ad_checkpoint import checkpoint_name

    from ..executor import REMAT_DOT

    return checkpoint_name(y, REMAT_DOT)


def _causal_attention(q, k, v, scale=None, window=None):
    """Causal attention over the ring-sharded sequence.  q, k, v: [B,
    T_loc, h, D], as the projections leave them (k and v may have fewer
    heads than q: grouped kv heads); returns [B, T_loc, h * D], what
    the out-projection reads.  `scale` multiplies the scores (None: D **
    -0.5); with a `window` a query sees the last `window` keys, its own
    among them.  Grouped kv heads and a window are the flash entry's
    alone (its kernels, or off a TPU its jnp reference): the ring knows
    neither, and `_check_mesh` refuses them with sp > 1.  On one sequence shard with the
    kernel available this is the flash kernels' entry that keeps this
    layout (its `custom_vjp` holds the merged output and the log-sums
    under names `remat="dots"` keeps); the sp > 1 ring, and a host
    without the kernel, take the ring's own path in [B, h, T, D]."""
    import jax

    if jax.lax.axis_size(AXIS_SP) == 1 and (
            _pallas_enabled() or window or k.shape[2] != q.shape[2]):
        from ..ops.pallas_attention import flash_attention_bthd

        return flash_attention_bthd(q, k, v, sm_scale=scale, causal=True,
                                    window=window)
    B, T, h, D = q.shape
    o = ring_attention(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                       axis_name=AXIS_SP, causal=True, scale=scale)
    return o.transpose(0, 2, 1, 3).reshape(B, T, h * D)


def _padded_attention(q, k, v):
    """`_causal_attention` where q.k and v differ in width (q, k: [B, T,
    h, dqk]; v: [B, T, h, dv]; returns [B, T, h * dv]).  The flash
    kernels take ONE width: q, k and v are padded with zero columns to
    the next lane multiple that holds both and the output's first dv
    columns are taken.  Exact: a zero column adds nothing to a score,
    and a zero column of v gives a zero column of output; the scores'
    scale stays dqk ** -0.5.  `mla_padded_width` (`mx.profiler` stats,
    set when traced) holds the padded width."""
    import jax.numpy as jnp

    from .. import profiler

    B, T, h, dqk = q.shape
    dv = v.shape[-1]
    wide = -(-max(dqk, dv) // 128) * 128
    profiler.max_stat("mla_padded_width", wide)

    def padded(a):
        return jnp.pad(a, ((0, 0),) * 3 + ((0, wide - a.shape[-1]),))

    o = _causal_attention(padded(q), padded(k), padded(v),
                          scale=dqk ** -0.5)
    return o.reshape(B, T, h, wide)[..., :dv].reshape(B, T, h * dv)


def _attention(cfg, x, wq, wk, wv, wo, tp_size):
    """TP column/row-parallel attention with ring-sharded sequence.
    x: [B, T_loc, E]; wq/wk/wv: [E, E/tp] (local shard), wo: [E/tp, E]."""
    import jax

    B, T, E = x.shape
    h_loc = cfg.n_heads // tp_size
    D = E // cfg.n_heads
    q, k, v = (_kept(x @ w).reshape(B, T, h_loc, D) for w in (wq, wk, wv))
    o = _causal_attention(q, k, v)
    # not `_kept`: the attention's merged output IS kept under "dots",
    # and this product is rebuilt from it (the cheapest per byte)
    out = o @ wo
    # row-parallel output projection: partial sums over tp
    return jax.lax.psum(out, AXIS_TP)


def _gqa(cfg, x, lw, tp_size, rope, window):
    """Attention with grouped kv heads.  x: [B, T_loc, E].  q as
    n_heads heads of head_dim, k and v as n_kv_heads (q head h meets kv
    head h // group: the flash kernels read the kv heads in place);
    `qk_norm` RMS-norms q and k over each head's values, one [head_dim]
    scale each; `rope` (None: no positions) rotates q and k over the
    whole head; `window` (None: the whole causal prefix) is the
    kernels' band; `out_gate` multiplies the merged output by sigmoid(x
    W_gate), elementwise.  Heads are column-sharded over tp in wq / wk /
    wv / w_gate and row-sharded in wo.  Device scopes `qk_norm`, `rope`,
    `core`, `gate`.

    Under "dots" the block gives back (does not `_kept`) the gate's
    product, or q's where there is no gate: with `o @ wo`'s the
    cheapest to rebuild, and twice its bytes (head_dim * n_heads wide
    against d_model); that pays for the attention's merged output,
    which IS kept."""
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    h = cfg.n_heads // tp_size
    hkv = (cfg.n_kv_heads or cfg.n_heads) // tp_size
    d = cfg.head_dim or cfg.d_model // cfg.n_heads
    q = x @ lw["wq"]
    q = (_kept(q) if cfg.out_gate else q).reshape(B, T, h, d)
    k, v = (_kept(x @ lw[n]).reshape(B, T, hkv, d) for n in ("wk", "wv"))
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q = _rms_norm(q, lw["q_norm"], cfg.norm_eps)
            k = _rms_norm(k, lw["k_norm"], cfg.norm_eps)
    if rope is not None:
        with jax.named_scope("rope"):
            rope_h = tuple(r[:, None] for r in rope)    # [T, 1, d / 2]
            q, k = _rotate(q, rope_h), _rotate(k, rope_h)
    with jax.named_scope("core"):
        o = _causal_attention(q, k, v, window=window)
    if cfg.out_gate:
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid((x @ lw["w_gate"]).astype(jnp.float32))
            o = (o * gate).astype(x.dtype)
    return jax.lax.psum(_kept(o @ lw["wo"]), AXIS_TP)


def _rotary(cfg):
    """Whether any layer rotates: latent attention's rope dims always,
    "gqa"'s heads on its window layers and, with `rope_full`, its full
    ones; "mha" has a position table."""
    return cfg.attention == "mla" or (
        cfg.attention == "gqa" and (cfg.rope_full or bool(cfg.window)))


def _rotary_table(cfg, positions):
    """(cos, sin), float32 [T, width / 2], of the global `positions`:
    angle = position * rope_theta ** (-2i / width), the width being
    latent attention's qk_rope_dim or "gqa"'s whole head."""
    import jax.numpy as jnp

    half = (cfg.qk_rope_dim if cfg.attention == "mla"
            else cfg.head_dim or cfg.d_model // cfg.n_heads) // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, rope):
    """Rotary embedding of x [..., T, d], rotate-half pairing: dim i is
    paired with dim i + d/2.  `rope`: (cos, sin) of [T, d / 2], or of any
    shape that broadcasts against x's halves ([T, 1, d / 2] for x [B, T,
    heads, d])."""
    import jax.numpy as jnp

    cos, sin = rope
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _mla(cfg, x, lw, tp_size, rope):
    """Latent attention in its decompressed (training) form.  x: [B,
    T_loc, E].  q goes through a rank-q_lora_rank bottleneck with a norm
    in it, or with q_lora_rank = 0 comes from ONE matrix; keys and
    values come from ONE compressed row per token (kv_lora_rank wide,
    normed) plus one rotary key of qk_rope_dim that all heads share.
    Heads are column-sharded over tp in wq_b / wkv_b and row-sharded in
    wo; the compressions are replicated.  `qk_norm` RMS-norms q_nope and
    k_nope per head; `head_gate` multiplies each head's output by a
    sigmoid gate from x.

    The flash kernels take ONE width for q.k and v.  Where qk_nope_dim +
    qk_rope_dim == v_head_dim they serve as they are; where the widths
    differ (192 and 128) q, k and v go to them padded
    (`_padded_attention`)."""
    import jax
    import jax.numpy as jnp

    B, T, _ = x.shape
    h = cfg.n_heads // tp_size
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvl = cfg.kv_lora_rank

    def heads(y, d):
        return y.reshape(B, T, h, d)

    rope_h = tuple(r[:, None] for r in rope)    # [T, 1, dr / 2]: per head
    # under "dots" each block gives back (does not `_kept`) the ONE
    # product that is cheapest to rebuild per byte, which pays for the
    # attention's merged output, which IS kept: with a q bottleneck that
    # is `c_q @ wq_b`, without one `c_kv @ wkv_b`
    if cfg.q_lora_rank:
        c_q = _rms_norm(_kept(x @ lw["wq_a"]), lw["q_norm"], cfg.norm_eps)
        q = heads(c_q @ lw["wq_b"], dn + dr)
    else:
        q = heads(_kept(x @ lw["wq"]), dn + dr)
    q_nope = q[..., :dn]
    if cfg.qk_norm:
        q_nope = _rms_norm(q_nope, lw["q_nope_norm"], cfg.norm_eps)
    q = jnp.concatenate([q_nope, _rotate(q[..., dn:], rope_h)], -1)
    ckv = _kept(x @ lw["wkv_a"])
    c_kv = _rms_norm(ckv[..., :kvl], lw["kv_norm"], cfg.norm_eps)
    k_pe = _rotate(ckv[..., kvl:], rope)[:, :, None]      # [B, T, 1, dr]
    kv = c_kv @ lw["wkv_b"]
    kv = heads(_kept(kv) if cfg.q_lora_rank else kv, dn + dv)
    k_nope = kv[..., :dn]
    if cfg.qk_norm:
        k_nope = _rms_norm(k_nope, lw["k_nope_norm"], cfg.norm_eps)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, T, h, dr))], -1)
    v = kv[..., dn:]
    o = _causal_attention(q, k, v) if dn + dr == dv \
        else _padded_attention(q, k, v)
    if cfg.head_gate:
        gate = jax.nn.sigmoid(_kept(jnp.einsum(
            "bte,eh->bth", x, lw["w_gate"],
            preferred_element_type=jnp.float32)))
        o = (heads(o, dv) * gate[..., None]).astype(x.dtype).reshape(
            B, T, h * dv)
    return jax.lax.psum(_kept(o @ lw["wo"]), AXIS_TP)


def _short_conv(x, taps):
    """Causal depthwise convolution over the sequence, no bias.  x: [B,
    T, C]; taps: [K, C], the LAST tap on the current token: y_t = sum_j
    taps[j] * x[t - (K - 1) + j], nought before the sequence's start.
    Float32 out."""
    import jax.numpy as jnp

    K, T = taps.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    return sum(xp[:, j:j + T] * taps[j] for j in range(K))


# the rows of a diagonal block of the chunk's triangular system that
# `_unit_lower_inverse` inverts by forward substitution before it merges
# blocks by halves.  At 2 x 32 heads x 32 chunks of 64 rows on a TPU
# v5e, 16 takes 1.75 ms a forward (2.75 with the backward pass), 8 takes
# 2.11 (2.93): its merge products are narrower
_SOLVE_LEAF = 16


def _unit_lower_inverse(A):
    """(I + A)^-1 for A [..., C, C] float32, strictly lower triangular
    (nought on and above its diagonal), by matrix products.  The C rows
    are cut into 2^k diagonal blocks of at most `_SOLVE_LEAF` rows (C
    padded with identity rows to a multiple of 2^k where it is none);
    each block is inverted by forward substitution unrolled over its
    rows, all blocks at once, then neighbours are merged by halves,

        [[L11, 0], [L21, L22]]^-1 = [[L11^-1, 0], [-L22^-1 L21 L11^-1,
                                                    L22^-1]],

    every level's blocks in one batched product (`_f32_dot`)."""
    import jax.numpy as jnp

    C = A.shape[-1]
    levels = 0
    while _SOLVE_LEAF << levels < C:
        levels += 1
    s = -(-C >> levels)                 # a leaf's rows
    P = s << levels
    lead = [(0, 0)] * (A.ndim - 2)
    A = jnp.pad(A, lead + [(0, P - C)] * 2)

    def blocks(s, below):   # A's s x s blocks `below` rows under the
        step = s + below    # diagonal, one every s + below rows
        return jnp.stack([A[..., i + below:i + step, i:i + s]
                          for i in range(0, P, step)], -3)

    # the leaves, [..., 2^k, s, s]: row r of the inverse is e_r less
    # A's row r times the rows above it (A's row r is nought from r on)
    L = blocks(s, 0)
    M = jnp.broadcast_to(jnp.eye(s, dtype=A.dtype), L.shape)
    for r in range(1, s):
        M = M.at[..., r, :].add(-(L[..., r, :, None] * M).sum(-2))

    mul = "...ij,...jk->...ik"
    while s < P:
        pairs = M.reshape(M.shape[:-3] + (-1, 2, s, s))
        M11, M22 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        M21 = -_f32_dot(mul, M22, _f32_dot(mul, blocks(s, s), M11))
        M = jnp.concatenate([
            jnp.concatenate([M11, jnp.zeros_like(M11)], -1),
            jnp.concatenate([M21, M22], -1)], -2)
        s *= 2
    return M[..., 0, :C, :C]


def _unit_lower_solve(A, rhs):
    """X = (I + A)^-1 rhs, A [..., C, C] float32 strictly lower
    triangular, rhs [..., C, m] float32.  The inverse is
    `_unit_lower_inverse`'s and is applied by one product; every product
    here and in the backward pass is float32 at `Precision.HIGHEST`.

    A `jax.custom_vjp`: it keeps (inverse, X), and the backward pass
    applies the inverse's transpose, d_rhs = (I + A)^-T dX and dA =
    -strict_lower(d_rhs X^T): jax's own rule for `triangular_solve`,
    with the kept inverse in place of a second solve.  Differentiated
    through its products, the inversion would keep every level's
    blocks."""
    import jax
    import jax.numpy as jnp

    from .. import profiler

    profiler.inc_stat("kda_solves_blocked")

    def solve_fwd(A, rhs):
        inv = _unit_lower_inverse(A)
        X = _f32_dot("...ij,...jm->...im", inv, rhs)
        return X, (inv, X)

    def solve_bwd(res, dX):
        inv, X = res
        d_rhs = _f32_dot("...ji,...jm->...im", inv, dX)
        C = X.shape[-2]
        below = jnp.arange(C)[:, None] > jnp.arange(C)[None]
        return jnp.where(below, -_f32_dot("...im,...jm->...ij", d_rhs, X),
                         0.0), d_rhs

    solve = jax.custom_vjp(lambda A, rhs: solve_fwd(A, rhs)[0])
    solve.defvjp(solve_fwd, solve_bwd)
    return solve(A, rhs)


def _f32_dot(spec, a, b):
    """`jnp.einsum` of float32 operands at `Precision.HIGHEST`, float32
    out: the products of the chunk's triangular solve."""
    import jax
    import jax.numpy as jnp

    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _scores_rebased(k, q, G, rebase, einsum):
    """The chunk's scores [..., 2, C, C] float32, (k_r . k_i, q_r . k_i)
    under exp(G_r - G_i) per channel, for a log-decay bounded below
    (`kda_gate` "floor"), and the largest |log-decay| summed over a
    re-based span.  k, q, G: [..., C, d], G the cumulative log-decay.
    exp(G_r - G_i) is a product of a row factor and a column factor, and
    exp(-G_i) alone passes float32 within a chunk (e^320 at 64 steps of
    -5), so the cumulative decay is RE-BASED every `rebase` steps: rows
    of sub-block a take exp(G_r - R_a) and columns exp(R_a - G_i), R_a
    being G at the middle of the span the sub-block covers: with a span
    of at most 80 nats both lie within e^-40 .. e^40 inside the
    sub-block, and a column before it under e^-40.  Entries above the
    diagonal are left for the caller to mask."""
    import jax
    import jax.numpy as jnp

    C, d = G.shape[-2:]
    R = rebase
    n = C // R
    Gs = G.reshape(G.shape[:-2] + (n, R, d))
    # the base of sub-block a, [..., n, d]: the MIDDLE of the span its
    # rows' G covers, so that a row factor and a column factor of the
    # sub-block each lie within e^-40 .. e^40 and no product of one with
    # a cotangent leaves float32's normal range (based at the span's
    # start, a row factor of e^-80 times a cotangent flushed to nought in
    # the backward pass).  No gradient through it: exp(G_r - R_a)
    # exp(R_a - G_i) does not depend on R_a
    start = jnp.concatenate(
        [jnp.zeros_like(Gs[..., :1, 0, :]), Gs[..., :-1, R - 1, :]], -2)
    span = jax.lax.stop_gradient(start - Gs[..., R - 1, :])
    base = jax.lax.stop_gradient(start) - 0.5 * span
    span = span.max()
    rows = jnp.exp(Gs - base[..., None, :])     # exp(G_r - R_a)
    # columns against sub-block a's base, up to a's own end; a later
    # column is never under a row of a: its factor is nought
    seen = jnp.arange(C)[None] < (jnp.arange(n)[:, None] + 1) * R
    cols = jnp.exp(jnp.where(
        seen[..., None], base[..., None, :] - G[..., None, :, :],
        -jnp.inf))                              # [..., n, C, d]
    left = jnp.stack([k.reshape(Gs.shape) * rows,
                      q.reshape(Gs.shape) * rows], -3)
    scores = einsum("zbhasrd,zbhacd->zbhsarc", left,
                    k[..., None, :, :] * cols)
    return scores.reshape(G.shape[:-2] + (2, C, C)), span


def _scores_by_halves(k, q, G, einsum):
    """`_scores_rebased`'s scores for a log-decay with NO lower bound
    (`kda_gate` "softplus"), exact and finite in float32 for any decay,
    and the largest |log-decay| summed over a chunk.  C a power of two.

    Every exponent is at or below 0.  The chunk is cut in halves,
    quarters, ... down to single rows: the block of a pair of halves of
    m rows each below the diagonal (rows in the lower half, columns in
    the upper) is based at G of the upper half's LAST row, b: rows take
    exp(G_r - G_b) and columns exp(G_b - G_i), each at most 1, their
    product exp(G_r - G_i) (a factor that falls under float32's range
    makes the product 0, which is what the true value rounds to).  A
    level's blocks are one batched product, and the levels are merged by
    halves as `_unit_lower_inverse` merges: [[D1, 0], [off, D2]].  The
    diagonal is (k_r . k_r, q_r . k_r) itself; above it, nought."""
    import jax
    import jax.numpy as jnp

    C, d = G.shape[-2:]
    lead = G.shape[:-2]
    M = (jnp.stack([k, q], -3) * k[..., None, :, :]).sum(-1)[
        ..., None, None]                        # [..., 2, C, 1, 1]
    m = 1
    while m < C:
        def halves(a):      # [..., C, d] -> [..., C / 2m, 2, m, d]
            return a.reshape(lead + (C // (2 * m), 2, m, d))

        Gp, kp, qp = halves(G), halves(k), halves(q)
        base = jax.lax.stop_gradient(Gp[..., 0, m - 1:, :])
        rows = jnp.exp(Gp[..., 1, :, :] - base)
        cols = jnp.exp(base - Gp[..., 0, :, :])
        off = einsum("zbhsjrd,zbhjid->zbhsjri",
                     jnp.stack([kp[..., 1, :, :] * rows,
                                qp[..., 1, :, :] * rows], -4),
                     kp[..., 0, :, :] * cols)   # [..., 2, C / 2m, m, m]
        pairs = M.reshape(M.shape[:-3] + (C // (2 * m), 2, m, m))
        D1, D2 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        M = jnp.concatenate([
            jnp.concatenate([D1, jnp.zeros_like(D1)], -1),
            jnp.concatenate([off, D2], -1)], -2)
        m *= 2
    return M[..., 0, :, :], jax.lax.stop_gradient(-G[..., -1, :]).max()


def _kda_chunked(q, k, v, g, beta, chunk, rebase, dtype):
    """The gated delta rule with a per-channel decay (Kimi Delta
    Attention, arXiv:2510.26692), in its CHUNKED form.  Per head, with
    the state S [d_k, d_v] in float32 from nought:

        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
              + beta_t k_t v_t^T,          o_t = S_t^T q_t.

    q, k, v, g: [B, T, h, d] float32 (q and k as the recurrence takes
    them, g the log-decay, <= 0); beta: [B, T, h] (in (0, 2) where the
    model allows negative eigenvalues).  Returns (o [B, T, h, d]
    float32, the largest |log-decay| summed over a re-based span, or
    with `rebase` None over a chunk).

    A chunk is C = `chunk` tokens.  With G the cumulative log-decay
    inside the chunk and S_0 the state the chunk is handed, the rows u_r
    = beta_r (v_r - S_{r-1}^T Diag(exp(g_r)) k_r) solve the unit lower
    triangular system (the WY form)

        (I + A) U = beta (V - (exp(G) K) S_0),
        A_ri = beta_r sum_c k_rc k_ic exp(G_rc - G_ic)   (i < r),

    so U = Ut - W S_0 with [W | Ut] = (I + A)^-1 [beta exp(G) K | beta
    V], and with B_ri = sum_c q_rc k_ic exp(G_rc - G_ic) (i <= r):

        O = (exp(G) Q) S_0 + B U,
        S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) K)^T U.

    Everything before S_0 is made for all chunks at once (scope
    `chunk`); a `lax.scan` hands the state from chunk to chunk (scope
    `state`).  Of these only the scores exp(G_r - G_i) need a base:
    every other factor's exponent is at most 0.  With a `rebase` (a
    decay bounded below) they are re-based every `rebase` steps
    (`_scores_rebased`); with None (no bound) based at the boundaries
    of halves (`_scores_by_halves`, C a power of two).

    The system is solved by (I + A)^-1 built in matrix products
    (`_unit_lower_solve`): diagonal blocks inverted by forward
    substitution, merged by halves, all float32 at HIGHEST precision.
    Not by a series in A ((I - A)(I + A^2)(I + A^4)...): its partial
    sums cancel terms of up to C(63, 31) ~ 1e18 at C = 64 where keys
    align under slow decay, which the gates' slow-decay initialisation
    gives, and float32 keeps nothing of the inverse there.  The
    decays, the solve and the state are float32; the operands of the
    products are `dtype`, accumulated in float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    B, T, h, d = q.shape
    C = chunk
    pad = (-T) % C
    N = (T + pad) // C

    def blocks(a):          # [B, T, h, x] -> [N, B, h, C, x], zero tail
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return a.reshape(B, N, C, h, a.shape[-1]).transpose(1, 0, 3, 2, 4)

    def einsum(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          preferred_element_type=f32)

    q, k, v, g, beta = (blocks(a) for a in (q, k, v, g, beta[..., None]))
    with jax.named_scope("chunk"):
        G = jnp.cumsum(g, axis=3)                   # [N, B, h, C, d], <= 0
        scores, span = _scores_rebased(k, q, G, rebase, einsum) if rebase \
            else _scores_by_halves(k, q, G, einsum)
        below = jnp.arange(C)[:, None] - jnp.arange(C)[None]
        A = jnp.where(below > 0, scores[..., 0, :, :], 0.0) * beta
        Bm = jnp.where(below >= 0, scores[..., 1, :, :], 0.0)
        decay = jnp.exp(G)
        sol = _unit_lower_solve(
            A, jnp.concatenate([k * decay * beta, v * beta], -1))
        # the products' operands, in `dtype` once for the whole scan
        W, Qd, Bm, Ke = (a.astype(dtype) for a in (
            sol[..., :d], q * decay, Bm,
            k * jnp.exp(G[..., -1:, :] - G)))       # k decayed to the end
        Ut = sol[..., d:]
        end = decay[..., -1, :]                     # [N, B, h, d]

    with jax.named_scope("state"):
        def step(S, xs):
            W, Ut, Qd, Bm, Ke, end = xs
            U = Ut - einsum("bhck,bhkv->bhcv", W, S)
            o = einsum("bhck,bhkv->bhcv", Qd, S) \
                + einsum("bhcj,bhjv->bhcv", Bm, U)
            S = end[..., None] * S + einsum("bhck,bhcv->bhkv", Ke, U)
            return S, o

        _, o = jax.lax.scan(step, _pvary_all(jnp.zeros((B, h, d, d), f32)),
                            (W, Ut, Qd, Bm, Ke, end))
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, N * C, h, d)[:, :T]
    return o, span


def _low_rank(x, a, b):
    """(x A) B, float32 [B, T, n]: a gate's logits through a rank-r
    bottleneck, each product on x's type accumulated in float32.  Under
    "dots" the bottleneck's r-wide output is kept and the up product
    rebuilt from it."""
    import jax.numpy as jnp

    f32 = jnp.float32
    mid = _kept(jnp.einsum("bte,er->btr", x, a, preferred_element_type=f32))
    return jnp.einsum("btr,rn->btn", mid.astype(x.dtype), b,
                      preferred_element_type=f32)


def _kda(cfg, x, lw):
    """Kimi Delta Attention (arXiv:2510.26692) as one mixer.  x: [B, T,
    E] (sp = tp = 1).  q, k, v each through a product, a causal
    depthwise convolution of `kda_conv` taps and SiLU; q and k
    L2-normed per head (q times d ** -0.5 besides); a log-decay per
    head AND channel, `kda_gate` "floor": kda_gate_floor * sigmoid(
    exp(A_log) * (logit + dt_bias)), so one step's decay lies in (e^
    floor, 1), or "softplus": -exp(A_log) * softplus(logit + dt_bias),
    unbounded; the logits from one full matrix, or through a rank-
    `kda_gate_rank` bottleneck; a write strength per head, sigmoid(x
    Wb), twice that with `kda_neg_eigval`; the gated delta rule in its
    chunked form (`_kda_chunked`); then per head an RMSNorm over its d
    values and a sigmoid gate from x, one a head or, through the
    bottleneck, one a value; and the out product.  Device scopes `conv`,
    `gate`, `chunk`, `state`, `out`.  Returns (y [B, T, E], stats:
    `KDA_STATS`, and `KDA_NEG_STATS` with `kda_neg_eigval`)."""
    import jax
    import jax.numpy as jnp

    from .. import profiler

    profiler.inc_stat("kda_traced")
    f32 = jnp.float32
    B, T, E = x.shape
    h = cfg.n_heads
    d = cfg.kda_head_dim or E // h

    def heads(y):
        return y.reshape(B, T, h, d)

    def per_head(w):        # a [B, T, h] gate's logits, float32
        return _kept(jnp.einsum("bte,eh->bth", x, lw[w],
                                preferred_element_type=f32))

    with jax.named_scope("conv"):
        q, k, v = (heads(jax.nn.silu(_short_conv(_kept(x @ lw["w" + n]),
                                                 lw["conv_" + n])))
                   for n in ("q", "k", "v"))
        q, k = (a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)
                for a in (q, k))
        q = q * d ** -0.5
    with jax.named_scope("gate"):
        a = _low_rank(x, lw["f_a"], lw["f_b"]) if cfg.kda_gate_rank else \
            _kept(jnp.einsum("bte,ef->btf", x, lw["wa"],
                             preferred_element_type=f32))
        rate = jnp.exp(lw["A_log"].astype(f32))[:, None]      # [h, 1]
        z = heads(a + lw["dt_bias"].astype(f32))
        g = -rate * jax.nn.softplus(z) if cfg.kda_gate == "softplus" \
            else cfg.kda_gate_floor * jax.nn.sigmoid(rate * z)
        beta = jax.nn.sigmoid(per_head("wb"))
        if cfg.kda_neg_eigval:
            beta = 2.0 * beta
    o, span = _kda_chunked(q, k, v, g, beta, cfg.kda_chunk,
                           cfg.kda_rebase if cfg.kda_gate == "floor"
                           else None, x.dtype)
    with jax.named_scope("out"):
        o = _rms_norm(o, lw["o_norm"].astype(f32), cfg.norm_eps)
        o = o * (jax.nn.sigmoid(heads(_low_rank(x, lw["g_a"], lw["g_b"])))
                 if cfg.kda_gate_rank
                 else jax.nn.sigmoid(per_head("w_gate"))[..., None])
        y = _kept(o.astype(x.dtype).reshape(B, T, h * d) @ lw["wo"])
    stats = {"kda_tokens": _pvary_all(jnp.asarray(B * T, f32)),
             "kda_chunks": _pvary_all(jnp.asarray(
                 B * -(-T // cfg.kda_chunk), f32)),
             "kda_decay_span_max": span}
    if cfg.kda_neg_eigval:
        stats["kda_neg_beta"] = (beta > 1.0).sum().astype(f32)
    return jax.lax.psum(y, AXIS_TP), stats


def _dense_ffn(x, w1, w2):
    import jax
    import jax.numpy as jnp

    h = jax.nn.gelu(_kept(jnp.einsum(
        "bte,ef->btf", x, w1,
        preferred_element_type=jnp.float32))).astype(x.dtype)
    return jax.lax.psum(_kept(h @ w2), AXIS_TP)


def _gated_ffn(x, wg, wu, wd):
    """SiLU-gated feed-forward: (silu(x Wg) * (x Wu)) Wd.  The two
    products come out in the activations' type (what `remat="dots"`
    keeps of them is half of what float32 outputs would be: 0.6 GB of
    the glm cell's step) and the gate is taken in float32."""
    import jax
    import jax.numpy as jnp

    g = _kept(x @ wg).astype(jnp.float32)
    u = _kept(x @ wu).astype(jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jax.lax.psum(_kept(h @ wd), AXIS_TP)


def _top_k_by_rounds(x, k):
    """(values [..., k], indices [..., k] int32) of the k largest entries
    of x (float32) along its last axis, as `jax.lax.top_k` gives them:
    descending, the lower index first among equal values, in the total
    order -NaN < -inf < -0 < 0 < inf < NaN, the indices distinct.  It
    takes k rounds of "the row's largest untaken key, the first untaken
    index that holds it" over int32 keys that order as the floats do,
    and no sort: on the TPU `lax.top_k` is a full sort of every row."""
    import jax
    import jax.numpy as jnp

    def ordered(bits):
        # a negative float's bits order backwards: flip all but the
        # sign (its own inverse)
        return bits ^ ((bits >> 31) & 0x7FFFFFFF)

    key = ordered(jax.lax.bitcast_convert_type(x, jnp.int32))
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    low = jnp.iinfo(jnp.int32).min
    taken = jnp.zeros(x.shape, bool)
    best, idx = [], []
    for _ in range(k):
        top = jnp.where(taken, low, key).max(-1, keepdims=True)
        at = jnp.where(~taken & (key == top), pos, x.shape[-1]).min(
            -1, keepdims=True)
        taken = taken | (pos == at)
        best.append(top)
        idx.append(at)
    values = jax.lax.bitcast_convert_type(
        ordered(jnp.concatenate(best, -1)), jnp.float32)
    return values, jnp.concatenate(idx, -1)


def _route(cfg, flat, router, bias=None):
    """Scores, selection and weights of the routed experts: ONE piece of
    code for every mesh and both dispatches.  flat: [n_tok, E].  Returns
    (expert ids [n_tok, top_k] int32 over ALL n_experts, weights [n_tok,
    top_k] float32).  Scores are float32 (float32 accumulation of the
    stored operands): softmax or sigmoid over all experts.  `bias` is
    added for the selection alone: the weights are the unbiased scores
    of the selected, divided by their sum over all top_k (held here or
    not) under `moe_norm_topk`, times `moe_scale`.  Gradients reach the
    router through the weights only.  With `n_group` > 1 the selection
    is group-limited: the experts lie in n_group groups of consecutive
    ids, a group's score is the sum of its two largest selection
    scores, and the top_k are taken inside the `topk_group` best groups;
    a third value is then returned, the kept groups [n_tok, n_group]
    bool."""
    import jax
    import jax.numpy as jnp

    logits = _kept(jnp.einsum("ne,ex->nx", flat, router,
                               preferred_element_type=jnp.float32))
    scores = jax.nn.softmax(logits, axis=-1) \
        if cfg.moe_score == "softmax" else jax.nn.sigmoid(logits)
    select = scores if bias is None else \
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    select = jax.lax.stop_gradient(select)
    if cfg.n_group > 1:
        with jax.named_scope("groups"):
            n, per = select.shape[0], cfg.n_experts // cfg.n_group
            by_group = select.reshape(n, cfg.n_group, per)
            _, best = _top_k_by_rounds(
                _top_k_by_rounds(by_group, 2)[0].sum(-1), cfg.topk_group)
            kept = (best[..., None] == jnp.arange(cfg.n_group)).any(-2)
            select = jnp.where(kept[..., None], by_group,
                               -jnp.inf).reshape(select.shape)
    _, idx = _top_k_by_rounds(select, cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if cfg.moe_norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if cfg.moe_scale != 1.0:
        w = w * cfg.moe_scale
    if cfg.n_group > 1:
        return idx.astype(jnp.int32), w, kept
    return idx.astype(jnp.int32), w


# A block of the dispatch's walk holds this many times the load the
# configuration states for the held experts (n_tok * top_k * held /
# n_experts pairs a layer).  Not an option: the walk takes as many blocks
# as the step's pairs need, whatever this is
_BLOCK_LOADS = 2


def _dispatch_block(cfg, n):
    """(rows, C): the static bound on a layer's held pairs over n tokens
    and the rows one block of `_experts_grouped`'s walk holds, from the
    configuration alone: `_BLOCK_LOADS` times the stated load, to the
    next 512; `rows` where one chip holds every expert."""
    held = cfg.experts_held or cfg.n_experts
    rows = n * min(cfg.top_k, held)
    loads = _BLOCK_LOADS * n * cfg.top_k * held
    return rows, min(rows, -(-loads // (512 * cfg.n_experts)) * 512)


def _walk_pairs(flat, wf, experts, order, sizes, blocks, k, C):
    """The held experts' products over the sorted pairs, a block of C
    rows at a time, `blocks` (a device scalar) of them.  flat [n, E];
    wf [n * k] float32, a pair's weight; experts (we_g, we_u, we_d);
    order: the pairs' indices into wf, held pairs first, by expert;
    sizes [held]: pairs an expert got.  Returns [n, E] float32.

    Reverse mode cannot cross a loop of traced length, so this is a
    `jax.custom_vjp`: it keeps its own arguments and the backward rule
    walks the same blocks, rebuilds a block's forward and takes its
    `jax.vjp`.  Nothing of `rows` rows exists in either pass."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def zeros(x):
        return _match_vma(jnp.zeros(x.shape, f32), x)

    def gather(i, flat, wf, order, sizes):
        """Block i: its rows' pairs and tokens, which rows are pairs at
        all, the block's share of each expert's group, and the rows'
        tokens' activations and pairs' weights."""
        r0 = i * C
        ends = jnp.cumsum(sizes)
        share = jnp.clip(ends - r0, 0, C) - jnp.clip(ends - sizes - r0, 0, C)
        with jax.named_scope("dispatch"):
            pair = jax.lax.dynamic_slice(order, (r0,), (C,))
            tok = pair // k
            used = (r0 + jnp.arange(C) < ends[-1])[:, None]
            return pair, tok, used, share, flat[tok], wf[pair]

    def rows_of(xs, ws, experts, used, share):
        """[C, E] float32: each row's expert applied to it, weighted."""
        def grouped(a, b):
            return jax.lax.ragged_dot(a, b, share,
                                      preferred_element_type=f32)

        # rows past the last group belong to no expert.  The TPU's
        # grouped product does not WRITE them, in either pass: what it
        # leaves there is whatever the memory held.  So a block masks
        # what it feeds (the transpose of this `where` then masks the
        # cotangent that comes back for those rows, before it is
        # scattered onto tokens) and what it takes out
        # (silu of unwritten rows in between is harmless: those rows
        # meet no expert's matrix in the next grouped product either)
        with jax.named_scope("experts"):
            we_g, we_u, we_d = experts
            xs = jnp.where(used, xs, 0)
            hid = (jax.nn.silu(grouped(xs, we_g))
                   * grouped(xs, we_u)).astype(xs.dtype)
            y = jax.lax.psum(jnp.where(used, grouped(hid, we_d), 0.0),
                             AXIS_TP)                       # row-parallel
            return y * jnp.where(used, ws[:, None], 0.0)

    @jax.custom_vjp
    def walk(flat, wf, experts, order, sizes, blocks):
        def block(i, out):
            _, tok, used, share, xs, ws = gather(i, flat, wf, order, sizes)
            y = rows_of(xs, ws, experts, used, share)
            with jax.named_scope("combine"):
                return out.at[tok].add(y)

        return jax.lax.fori_loop(0, blocks, block, zeros(flat))

    def walk_fwd(*args):
        return walk(*args), args

    def walk_bwd(args, g):
        flat, wf, experts, order, sizes, blocks = args

        def block(i, grads):
            d_flat, d_wf, d_experts = grads
            pair, tok, used, share, xs, ws = gather(i, flat, wf, order,
                                                    sizes)
            with jax.named_scope("combine"):
                g_rows = g[tok]
            _, pull = jax.vjp(
                lambda xs, ws, experts: rows_of(xs, ws, experts, used,
                                                share), xs, ws, experts)
            d_xs, d_ws, d_block = pull(g_rows)
            with jax.named_scope("dispatch"):
                d_flat = d_flat.at[tok].add(d_xs.astype(f32))
                d_wf = d_wf.at[pair].add(d_ws)
            return d_flat, d_wf, jax.tree_util.tree_map(
                lambda a, b: a + b.astype(f32), d_experts, d_block)

        d_flat, d_wf, d_experts = jax.lax.fori_loop(
            0, blocks, block,
            (zeros(flat), zeros(wf), jax.tree_util.tree_map(zeros, experts)))
        return (d_flat.astype(flat.dtype), d_wf.astype(wf.dtype),
                jax.tree_util.tree_map(lambda d, a: d.astype(a.dtype),
                                       d_experts, experts),
                None, None, None)

    walk.defvjp(walk_fwd, walk_bwd)
    # ranks of one mesh walk different numbers of blocks (each its own
    # tokens' pairs), so no collective over an axis the tokens are split
    # on may run INSIDE a loop.  What the mesh replicates over such an
    # axis (the experts' matrices over dp and sp) is cast to the tokens'
    # axes here: the loops then carry each rank's own gradient, and the
    # cast's transpose sums them over the replicas once, after the walk
    wf, experts = jax.tree_util.tree_map(lambda a: _match_vma(a, flat),
                                         (wf, experts))
    return walk(flat, wf, experts, order, sizes, blocks)


def _experts_grouped(cfg, flat, idx, w, lw):
    """The held experts' part of the routed result, with no token
    dropped (ep = 1).  The (token, expert) pairs that fall in the held
    range [expert_first, expert_first + held) are sorted by expert, held
    pairs first, and WALKED in blocks of C rows (`_dispatch_block`: about
    twice the stated load, from the configuration alone), ceil(pairs / C)
    of them, the count read on the device from the step's own routing: a
    block gathers its tokens, runs the experts' products as grouped
    products over its share of each expert's group (`jax.lax.ragged_dot`:
    rows of one group meet one expert's matrix) and scatter-adds each
    row onto its token, weighted (`_walk_pairs`).  The pairs are at most
    n_tok * min(top_k, held) (a token's top_k experts are distinct), that
    is rows / C blocks, and the walk takes as many as there are pairs:
    that is what makes it dropless by construction, with no capacity to
    set; what experts held elsewhere would add is left out.  we_*:
    [held, E, F/tp].  Returns ([n_tok, E], stats)."""
    import jax
    import jax.numpy as jnp

    n, k = flat.shape[0], cfg.top_k
    held = cfg.experts_held or cfg.n_experts
    rows, C = _dispatch_block(cfg, n)
    with jax.named_scope("dispatch"):
        local = idx - cfg.expert_first
        here = (local >= 0) & (local < held)
        # pairs held elsewhere sort behind every group and get no weight
        key = jnp.where(here, local, held).reshape(n * k)
        # whole blocks: the rows past `rows` are no pair's (masked)
        order = jnp.pad(jnp.argsort(key, stable=True)[:rows],
                        (0, -rows % C))
        sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        blocks = (sizes.sum() + C - 1) // C
    out = _kept(_walk_pairs(flat, w.reshape(n * k),
                            (lw["we_g"], lw["we_u"], lw["we_d"]), order,
                            sizes, blocks, k, C).astype(flat.dtype))
    f32 = jnp.float32
    stats = {"moe_pairs": here.sum().astype(f32),
             "moe_tokens": _pvary_all(jnp.asarray(n, f32)),
             "moe_load_max": sizes.max().astype(f32),
             "moe_rows_walked": (blocks * C).astype(f32)}
    return out, stats


def _experts_bucketed(cfg, flat, expert, gate, we1, we2, ep_size):
    """Switch-style top-1 dispatch with all_to_all over "ep": the
    exchange for ep > 1 (static exchange shapes), and the top-1 path on
    every mesh.  It DROPS: each expert takes at most capacity_factor *
    n_tok / n_experts tokens and the overflow contributes nothing.

    flat: [n_tok, E] local tokens; expert, gate: [n_tok] from `_route`;
    we1: [NE/ep, E, F/tp] local expert shard.  Tokens are bucketed by
    destination expert, exchanged over the ep ring, processed by the
    local experts, and sent back.  With ep=1 the all_to_all is the
    identity and this reduces to single-host switch routing.
    """
    import jax
    import jax.numpy as jnp

    n_tok, E = flat.shape
    NE = cfg.n_experts
    ne_loc = NE // ep_size
    cap = max(1, int(cfg.capacity_factor * n_tok / NE))

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
    h = jax.nn.gelu(_kept(jnp.einsum(
        "nce,nef->ncf", b, we1,
        preferred_element_type=jnp.float32))).astype(flat.dtype)
    y = _kept(jnp.einsum("ncf,nfe->nce", h, we2))
    y = jax.lax.psum(y, AXIS_TP)                           # row-parallel

    if ep_size > 1:
        y = y.reshape(ne_loc, ep_size, cap, E)
        y = jax.lax.all_to_all(y, AXIS_EP, split_axis=1, concat_axis=0,
                               tiled=False)
        y = y.reshape(NE, cap, E)
    else:
        y = y.reshape(NE, cap, E)

    return y[expert, safe_pos] * gate[:, None].astype(flat.dtype)


def _moe_ffn(cfg, x, lw, ep_size):
    """The expert layer's feed-forward: route (`_route`), the held
    routed experts' part (grouped and dropless, or the capacity-bucketed
    exchange), and the shared expert for every token.  x: [B, T, E].
    Returns (f [B, T, E], stats: `MOE_STATS` or {})."""
    import jax

    B, T, E = x.shape
    flat = x.reshape(B * T, E)
    with jax.named_scope("router"):
        idx, w, *kept = _route(cfg, flat, lw["router"],
                               lw.get("router_bias"))
    if cfg.ffn == "swiglu":
        f, stats = _experts_grouped(cfg, flat, idx, w, lw)
        if kept:        # tokens whose kept groups hold the experts here
            here = cfg.expert_first // (cfg.n_experts // cfg.n_group)
            stats["moe_groups_kept_here"] = \
                kept[0][:, here].sum().astype("float32")
    else:
        f, stats = _experts_bucketed(cfg, flat, idx[:, 0], w[:, 0],
                                     lw["we1"], lw["we2"], ep_size), {}
    if cfg.n_shared_experts:
        with jax.named_scope("shared_expert"):
            f = f + _gated_ffn(x, lw["ws_g"], lw["ws_u"],
                               lw["ws_d"]).reshape(B * T, E)
    return f.reshape(B, T, E), stats


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


def _merge_stats(a, b):
    """Two sets of per-step counters as one: counts add, a watermark is
    the higher; a counter only one side has is kept."""
    import jax.numpy as jnp

    out = dict(a)
    for k, v in b.items():
        out[k] = v if k not in out else (
            jnp.maximum(out[k], v) if k in _WATERMARKS else out[k] + v)
    return out


def _layer_fn(cfg, kind, tp_size, ep_size, rope=None, keep_flash=False):
    """One layer of a segment as the scan's body, (x, lw) -> (x, stats),
    under the config's remat policy, which with `keep_flash` keeps the
    flash kernel's output and log-sums besides (`_looped_loss`).
    `kind` is a segment's (see `_segments`): the feed-forward "dense" or
    "moe", "kda+" in front where the mixer is Kimi Delta Attention."""
    import jax

    mixer, ffn = _kind_parts(cfg, kind)
    # the named scopes (here, `conv` .. `out` in `kda`, `router` ..
    # `combine` in the expert layer, and `embed` / `mtp` / `loss` /
    # `adam` below) are metadata on the device program's instructions: a
    # trace's device time can be summed by them (PERF.md)
    def layer(x, lw):
        stats = {}
        if mixer == "kda":
            with jax.named_scope("kda"):
                m, stats = _kda(cfg, _rms_norm(x, lw["ln1"], cfg.norm_eps),
                                lw)
                h = x + m
        elif mixer == "mla":
            with jax.named_scope("mla"):
                h = x + _mla(cfg, _rms_norm(x, lw["ln1"], cfg.norm_eps),
                             lw, tp_size, rope)
        elif mixer == "gqa":
            # mask and positions are the LAYER's: a full-attention layer
            # among window layers has no window, and no positions where
            # the model gives it none (with no window every layer is full)
            full = _is_full(kind) or not cfg.window
            with jax.named_scope("attn"):
                m = _gqa(cfg, _rms_norm(x, lw["ln1"], cfg.norm_eps), lw,
                         tp_size,
                         rope if cfg.rope_full or not full else None,
                         None if full else cfg.window or None)
                if cfg.post_norms:
                    m = _rms_norm(m, lw["ln1_post"], cfg.norm_eps)
                h = x + m
        else:
            with jax.named_scope("attn"):
                h = x + _attention(cfg, _rms_norm(x, lw["ln1"],
                                                  cfg.norm_eps),
                                   lw["wq"], lw["wk"], lw["wv"], lw["wo"],
                                   tp_size)
        with jax.named_scope("ffn"):
            z = _rms_norm(h, lw["ln2"], cfg.norm_eps)
            if ffn == "moe":
                f, st = _moe_ffn(cfg, z, lw, ep_size)
                stats = dict(stats, **st)
            elif cfg.ffn == "gelu":
                f = _dense_ffn(z, lw["w1"], lw["w2"])
            else:
                f = _gated_ffn(z, lw["wg"], lw["wu"], lw["wd"])
            if cfg.post_norms:
                f = _rms_norm(f, lw["ln2_post"], cfg.norm_eps)
            return h + f, stats

    if cfg.remat == "none":
        return layer
    from ..executor import apply_remat
    from ..ops.pallas_attention import FLASH_OUT, FLASH_LSE

    # the blocks say by name what "dots" keeps of them (`_kept`)
    return apply_remat(layer, cfg.remat, prevent_cse=False, named=True,
                       keep=(FLASH_OUT, FLASH_LSE) if keep_flash else ())


def _stage_fn(cfg, kind, params_stage, x, tp_size, ep_size, rope=None,
              keep_flash=False):
    """Run one segment's layers (this pipeline stage's share of them)
    over x via lax.scan (weights stacked on the layer axis).  `kind` is
    the segment's; `rope` the rotary table where positions are rotary;
    `keep_flash` as `_layer_fn`'s.  Returns (x, the segment's per-step
    counters or {})."""
    import jax

    x = _pvary_all(x)
    layer = _layer_fn(cfg, kind, tp_size, ep_size, rope, keep_flash)
    out, per_layer = jax.lax.scan(layer, x, params_stage)
    if not per_layer:
        return out, {}
    return out, {k: (v.max() if k in _WATERMARKS else v.sum())
                 for k, v in per_layer.items()}


def _segment_params(cfg, params, segment):
    """One segment's stacked layer weights, [layers, ...] each: the
    leading pp axis is sharded, so inside shard_map it has extent 1;
    where the segment's prefix has more segments (a stack of two mixer
    kinds) the segment's rows of the stack."""
    prefix, kind, first, n = segment
    whole = _stacks(cfg)[prefix][1] == n
    return {leaf: (params[prefix + leaf][0] if whole
                   else params[prefix + leaf][0, first:first + n])
            for leaf in _layer_leaves(cfg, kind)}


def _run_stack(cfg, params, x, tp_size, ep_size, rope, keep_flash=False):
    """The model's layers in order, segment by segment (the multi-token-
    prediction block is not one of them); `keep_flash` as `_layer_fn`'s,
    the trace-time stat `loop_flash_kept` counting the attention layers
    it is set for.  Returns (x, stats)."""
    from .. import profiler

    stats = {}
    for segment in _segments(cfg):
        if segment[0] != "mtp.":
            x, st = _stage_fn(cfg, segment[1],
                              _segment_params(cfg, params, segment),
                              x, tp_size, ep_size, rope, keep_flash)
            stats = _merge_stats(stats, st)
            if keep_flash and _kind_parts(cfg, segment[1])[0] != "kda":
                profiler.inc_stat("loop_flash_kept", segment[3])
    return x, stats


def _embed(cfg, params, tokens, tp_idx, V_loc, positions):
    """Vocab-sharded embedding lookup (local rows + psum over tp), times
    `embed_scale`, plus the learned positions where the model has a
    table."""
    import jax
    import jax.numpy as jnp

    local_tok = tokens - tp_idx * V_loc
    in_shard = (local_tok >= 0) & (local_tok < V_loc)
    emb = jnp.where(
        in_shard[..., None],
        params["embed"][jnp.clip(local_tok, 0, V_loc - 1)], 0.0)
    # exactly one tp shard contributes a non-zero row per token
    # (vocab-sharded one-hot), so a native-dtype psum is exact
    # and halves the ICI bytes vs upcasting to f32 first
    emb = jax.lax.psum(emb, AXIS_TP)
    if cfg.embed_scale != 1.0:
        emb = emb.astype(jnp.float32) * cfg.embed_scale
    if "pos" in params:
        emb = emb + params["pos"][positions][None]
    return emb.astype(jnp.dtype(cfg.dtype))               # [B, T, E]


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


def _exit_log_probs(s):
    """log p_t [U, N], float32, of the exit distribution from the gate's
    logits s [U - 1, N] at every pass but the last: log p_t = log
    lambda_t + sum_{j<t} log(1 - lambda_j) for t < U and log p_U =
    sum_{j<U} log(1 - lambda_j), lambda = sigmoid(s), each term a
    log-sigmoid, so that no probability that rounds to 0 is logged."""
    import jax
    import jax.numpy as jnp

    none = jnp.zeros_like(s[:1])
    stayed = jnp.concatenate([none, jnp.cumsum(jax.nn.log_sigmoid(-s), 0)])
    return stayed + jnp.concatenate([jax.nn.log_sigmoid(s), none])


def _looped_loss(cfg, params, x, labels, tp_size, ep_size, rope):
    """The stage-1 objective of a looped stack (`loop_passes` = U > 1),
    inside shard_map on one pipeline stage: the stack runs U times on
    the same weights, pass t + 1 fed x_t = RMS(pass t's output; ln_f);
    each x_t meets the head (nll_t, a token's cross-entropy) and every
    x_t but the last the exit gate (s_t = x_t . w + b).  Per token, sum_t
    p_t (nll_t + exit_entropy log p_t) under the exit distribution
    (`_exit_log_probs`): the expected loss less exit_entropy times the
    entropy, with no gradient stopped.  The heads are not rebuilt in the
    backward pass: `jax.checkpoint` around each cost the Ouro cell 3.2%
    of its rate on a v5e and saved no memory, the compiled program
    holding one pass's logits at a time either way (PERF.md).  Under
    `remat="full"` every pass but the first keeps the flash kernel's
    output and log-sums (`_layer_fn`'s `keep_flash`), so that its
    backward pass does not run the forward kernel again; the first pass
    keeps nothing beyond its layer inputs, because its backward pass
    runs last, beside what the later passes' backward passes left
    behind, where the program's memory peaks (keeping them there cost the Ouro cell's
    described program +0.25 GiB, the later passes' 33 MB).  Device
    scopes `loop`, with `pass` around each pass's stack and `exit`
    around the norm, gate, head and combination.  Returns (mean over the
    local tokens, the step's counters: the stack's, summed over the
    passes, and `LOOP_STATS`)."""
    import jax
    import jax.numpy as jnp

    B, T = labels.shape
    f32 = jnp.float32
    V_loc = params["unembed"].shape[-1]
    flat = labels.reshape(B * T)

    def head(x, unembed):
        return _sharded_xent((x @ unembed).reshape(B * T, V_loc), flat,
                             V_loc)

    U, nll, gates, stats = cfg.loop_passes, [], [], {}
    with jax.named_scope("loop"):
        for t in range(U):
            with jax.named_scope("pass"):
                h, st = _run_stack(cfg, params, x, tp_size, ep_size, rope,
                                   keep_flash=t > 0 and cfg.remat == "full")
            stats = _merge_stats(stats, st)
            with jax.named_scope("exit"):
                x = _rms_norm(h, params["ln_f"], cfg.norm_eps)
                nll.append(head(x, params["unembed"]))
                if t < U - 1:
                    # tp ranks hold the same x: the mean of their equal
                    # logits is exact, and the same on every rank
                    gates.append(jax.lax.psum(
                        x.reshape(B * T, -1).astype(f32)
                        @ params["exit_gate.w"].astype(f32), AXIS_TP)
                        / tp_size + params["exit_gate.b"].astype(f32))
        with jax.named_scope("exit"):
            logp = _exit_log_probs(jnp.stack(gates))
            p = jnp.exp(logp)
            per_token = (p * (jnp.stack(nll) + cfg.exit_entropy * logp)
                         ).sum(0)
            # marked varying everywhere, as the stack's counters are,
            # for the one reduction over the mesh they all go through
            stats.update({k: _pvary_all(v) for k, v in dict(
                loop_passes=f32(U), loop_tokens=f32(B * T),
                loop_exit_expect=(jnp.arange(1, U + 1, dtype=f32)[:, None]
                                  * p).sum(),
                loop_exit_last=p[-1].sum()).items()})
    return per_token.mean(), stats


# ---------------------------------------------------------------------------
# full per-device train step (inside shard_map)


def _build_loss_fn(cfg: TransformerConfig, mesh, n_micro: int):
    """The per-device loss (inside shard_map): embed, this rank's stage
    of layers under the pipeline schedule, final norm, unembed, sharded
    cross-entropy; a looped stack's (`loop_passes` > 1, one stage, one
    microbatch) is `_looped_loss`.

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
        """tokens/labels: local shard [B_loc, T_loc] (dp × sp).  Returns
        (loss, stats): the step's counters (`_stat_names(cfg)`), or {}."""
        pp_idx = jax.lax.axis_index(AXIS_PP)
        sp_idx = jax.lax.axis_index(AXIS_SP)
        tp_idx = jax.lax.axis_index(AXIS_TP)
        B, T = tokens.shape
        if B % n_micro:
            raise MXNetError("local batch %d %% n_micro %d" % (B, n_micro))

        pos_global = sp_idx * T + jnp.arange(T)
        rope = _rotary_table(cfg, pos_global) if _rotary(cfg) else None
        with jax.named_scope("embed"):
            x = _embed(cfg, params, tokens, tp_idx, V_loc, pos_global)

        is_last = (pp_idx == pp - 1)

        def run_stage(state):
            return _run_stack(cfg, params, state, tp, ep, rope)

        n_steps = n_micro + pp - 1
        if n_steps > 1 and _stat_names(cfg):
            raise MXNetError("the step's counters (routed experts, KDA "
                             "layers, a looped stack) need one stage and "
                             "one microbatch")
        looped = None
        if cfg.loop_passes > 1:
            looped, stats = _looped_loss(cfg, params, x, labels, tp, ep,
                                         rope)
        elif n_steps == 1:
            # one stage, one microbatch: one call, and never a loop of
            # one trip (the docstring above says what that costs)
            h, stats = run_stage(x)
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
                out, _ = run_stage(inp)
                slot = jnp.clip(s - (pp - 1), 0, n_micro - 1)
                out_buf = out_buf.at[slot].set(
                    jnp.where(is_last, out, out_buf[slot]))
                state = jax.lax.ppermute(out, AXIS_PP, perm_fwd) \
                    if pp > 1 else out
                return state, out_buf

            state0 = _pvary_all(jnp.zeros((mb, T, E), x.dtype))
            _, out_buf = jax.lax.fori_loop(0, n_steps, step,
                                           (state0, out_buf))
            h, stats = out_buf.reshape(B, T, E), {}

        mtp_loss = None
        if cfg.mtp_depth:
            # predict token i+2 from the stack's output at i and the
            # embedding of token i+1: one more layer of the model's kind
            # on [norm(emb) | norm(h)] W_eh, then the SHARED head.  The
            # sequence keeps its length (the kernels' tiles): the last
            # position has no next token, is fed the first one's
            # embedding, stays causal-invisible to the others and is
            # left out of the mean
            if sp > 1:
                raise MXNetError("the multi-token-prediction block "
                                 "shifts along the sequence: sp = 1")
            with jax.named_scope("mtp"):
                eps = cfg.norm_eps
                nxt = _embed(cfg, params, jnp.roll(tokens, -1, axis=1),
                             tp_idx, V_loc, pos_global)
                u = jnp.concatenate(
                    [_rms_norm(nxt, params["mtp.ln_e"], eps),
                     _rms_norm(h, params["mtp.ln_h"], eps)], -1) \
                    @ params["mtp.eh"]
                segment = _segments(cfg)[-1]
                u, st = _stage_fn(
                    cfg, segment[1], _segment_params(cfg, params, segment),
                    u, tp, ep, rope)
                stats = _merge_stats(stats, st)

                def head(u, ln, unembed, labels):
                    nll = _sharded_xent(
                        (_rms_norm(u, ln, eps) @ unembed).reshape(
                            B * T, V_loc),
                        jnp.roll(labels, -1, axis=1).reshape(B * T), V_loc)
                    valid = (jnp.arange(T) < T - 1).astype(nll.dtype)
                    return (nll.reshape(B, T) * valid).sum() \
                        / (B * (T - 1))

                # a second set of logits: kept for the backward pass it
                # would lie beside the main head's at the step's memory
                # peak, so it is made again there (one product more)
                mtp_loss = jax.checkpoint(head)(
                    u, params["mtp.ln_f"], params["unembed"], labels)

        # only the last stage's h is the real model output; psum the
        # masked loss over pp so every rank agrees (others contribute 0)
        with jax.named_scope("loss"):
            if looped is not None:
                local_loss = looped
            else:
                h = _rms_norm(h, params["ln_f"], cfg.norm_eps)
                logits = h @ params["unembed"]            # [B, T, V/tp]
                nll = _sharded_xent(logits.reshape(B * T, V_loc),
                                    labels.reshape(B * T), V_loc)
                local_loss = nll.mean()
            if mtp_loss is not None:
                local_loss = local_loss + cfg.mtp_weight * mtp_loss
            local_loss = local_loss * jnp.where(is_last, 1.0, 0.0)
            # mean over dp × sp shards; sum over pp picks the last
            # stage; ep ranks hold identical copies, so psum/ep is exact
            # (and makes the per-path gradient normalization come out
            # right for both ep-sharded expert weights and replicated
            # params)
            loss = jax.lax.psum(local_loss,
                                (AXIS_PP, AXIS_DP, AXIS_SP, AXIS_EP)) \
                / (mesh.shape[AXIS_DP] * sp * ep)
        if stats:
            # one value for the whole mesh: counts add over the data
            # shards (dp, sp); the other axes hold copies
            every = (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP, AXIS_EP)
            stats = jax.lax.stop_gradient({
                k: (jax.lax.pmax(v, every)
                    if k in _WATERMARKS + _COPIES else
                    jax.lax.psum(v, every) / float(pp * tp * ep))
                for k, v in stats.items()})
        return loss, stats

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
        (loss, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, labels)
        new_params = {}
        for name, g in grads.items():
            new_params[name] = (params[name].astype(jnp.float32)
                                - lr * g.astype(jnp.float32)).astype(
                params[name].dtype)
        return (new_params, loss) + ((stats,) if stats else ())

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
        (loss, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, labels)
        with jax.named_scope("adam"):
            return _adam(params, opt_state, grads, loss) \
                + ((stats,) if stats else ())

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


def _check_mesh(cfg, mesh):
    if cfg.n_experts and cfg.ffn == "swiglu" and mesh.shape[AXIS_EP] > 1:
        raise MXNetError(
            "gated (swiglu) experts run the held experts' grouped "
            "products on ep = 1; the exchange over ep > 1 is the "
            "capacity-bucketed one (GELU experts), which drops")
    if cfg.attention == "gqa":
        kv = cfg.n_kv_heads or cfg.n_heads
        if mesh.shape[AXIS_SP] > 1 and (cfg.window or kv != cfg.n_heads):
            raise MXNetError(
                "a window and grouped kv heads are the flash kernels': "
                "the sp > 1 ring knows neither: sp = 1")
        if kv % mesh.shape[AXIS_TP]:
            raise MXNetError("n_kv_heads=%d not divisible by tp=%d"
                             % (kv, mesh.shape[AXIS_TP]))
    if cfg.loop_passes > 1 and mesh.shape[AXIS_PP] > 1:
        raise MXNetError(
            "a looped stack runs on one pipeline stage: a loop that goes "
            "round a ring of stages is not built: pp = 1")
    if KDA_STATS[0] in _stat_names(cfg) and (mesh.shape[AXIS_SP] > 1
                                             or mesh.shape[AXIS_TP] > 1):
        raise MXNetError(
            "Kimi Delta Attention carries a state along the sequence and "
            "convolves over it: sp = 1; its heads are not sharded: tp = 1")


class _LMStep(object):
    """The LM's step call: the jitted program, bracketed by the
    program's spans while `mx.profiler.armed()` (docs/observability.md):
    ``mx:step`` (``step`` = the LM programs the process called before
    this one, read off ``lm_programs``; ``k``,
    ``site=lm``) around ``mx:host_dispatch`` (``n_args`` = the array
    leaves passed, reckoned once from the shardings).  The counters
    ``lm_programs`` / ``lm_steps`` count every call.  Un-armed a call is
    one check, the two bumps and the jit's own call.  Every other
    attribute (``lower``, ``trace``, ...) is the jit's."""

    __slots__ = ("_jit", "_k", "_n_args")

    def __init__(self, jitted, k_steps, shardings):
        import jax

        self._jit = jitted
        self._k = k_steps or 1
        # every array of the shardings' tree, "data" twice (toks, labs)
        self._n_args = len(jax.tree_util.tree_leaves(shardings)) + 1

    def __getattr__(self, name):
        if name == "_jit":          # unset slot (copy, pickle): no loop
            raise AttributeError(name)
        return getattr(self._jit, name)

    def __call__(self, *args):
        programs = _prof.inc_stat("lm_programs")
        _prof.inc_stat("lm_steps", self._k)
        if not _prof.armed():
            return self._jit(*args)
        with _prof.span("mx:step", "loop", step=programs - 1, k=self._k,
                        site="lm"), \
                _prof.span("mx:host_dispatch", "loop", n_args=self._n_args):
            return self._jit(*args)


def _make_step_common(cfg, mesh, n_micro, lr, optimizer, betas, eps,
                      k_steps):
    """Shared plumbing for make_train_step / make_fused_train_steps:
    builds the per-device step (wrapped in a k_steps lax.scan when
    k_steps is not None), shard_maps + jits it with donation, and
    returns (step, shardings): the step an `_LMStep` around the jit.
    ONE copy of the spec/sharding layout so the fused and per-step
    paths cannot drift."""
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
    _check_mesh(cfg, mesh)
    # the step's counters ride beside the loss where the config has them
    # (`_stat_names`); no other program's outputs change
    names = _stat_names(cfg)
    extra = ({k: P() for k in names},) if names else ()
    if optimizer == "sgd":
        device_step = _build_device_step(cfg, mesh, n_micro, lr)
        if k_steps is None:
            device_fn = device_step
        else:
            def device_fn(params, toks_stack, labs_stack):
                def body(p, batch):
                    out = device_step(p, batch[0], batch[1])
                    return out[0], out[1:]

                params, per_step = lax.scan(
                    body, params, (toks_stack, labs_stack), length=k_steps)
                return (params,) + per_step

        sm = jax.shard_map(device_fn, mesh=mesh,
                           in_specs=(pspecs, data_spec, data_spec),
                           out_specs=(pspecs, P()) + extra)
        return _LMStep(jax.jit(sm, donate_argnums=(0,)), k_steps,
                       shardings), shardings

    device_step = _build_adam_zero1_step(cfg, mesh, n_micro, lr,
                                         betas=betas, eps=eps)
    if k_steps is None:
        device_fn = device_step
    else:
        def device_fn(params, opt_state, toks_stack, labs_stack):
            def body(carry, batch):
                out = device_step(carry[0], carry[1], batch[0], batch[1])
                return out[:2], out[2:]

            (params, opt_state), per_step = lax.scan(
                body, (params, opt_state), (toks_stack, labs_stack),
                length=k_steps)
            return (params, opt_state) + per_step

    ospecs = _opt_state_specs(cfg, mesh)
    ostate_specs = {"m": dict(ospecs), "v": dict(ospecs), "t": P()}
    sm = jax.shard_map(device_fn, mesh=mesh,
                       in_specs=(pspecs, ostate_specs, data_spec,
                                 data_spec),
                       out_specs=(pspecs, ostate_specs, P()) + extra)
    shardings["opt_state"] = {
        "m": {k: NamedSharding(mesh, v) for k, v in ospecs.items()},
        "v": {k: NamedSharding(mesh, v) for k, v in ospecs.items()},
        "t": NamedSharding(mesh, P()),
    }
    return _LMStep(jax.jit(sm, donate_argnums=(0, 1)), k_steps,
                   shardings), shardings


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


def publish_moe_stats(stats) -> Dict[str, float]:
    """Add the per-step counters a step (or a fused program: [K] arrays)
    returned to `mx.profiler`'s stats, the routed experts' (`MOE_STATS`,
    `GROUP_STATS`), the KDA layers' (`KDA_STATS`) and the loop's
    (`LOOP_STATS`) alike: counts add up, to the nearest whole number,
    `moe_load_max` and `kda_decay_span_max` are watermarks (whole
    numbers: the span's nats to the nearest) (docs/observability.md).
    One host read of a few small arrays: call it where the host may
    wait for the program (a sampled step, the end of a window), not
    after every step.  Returns what it added."""
    import jax
    import numpy as np

    from .. import profiler

    host = {k: np.asarray(v) for k, v in jax.device_get(stats).items()}
    out = {}
    for k in MOE_STATS + GROUP_STATS + KDA_STATS + KDA_NEG_STATS \
            + LOOP_STATS:
        if k not in host:
            continue
        if k in _WATERMARKS:
            out[k] = float(host[k].max())
            profiler.max_stat(k, int(round(out[k])))
        else:
            out[k] = float(host[k].sum())
            profiler.inc_stat(k, int(round(out[k])))
    return out


def make_forward(cfg: TransformerConfig, mesh):
    """Jitted SPMD forward (logits) for inference/eval."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    _check_mesh(cfg, mesh)
    tp = mesh.shape[AXIS_TP]
    V_loc = cfg.vocab // tp
    specs = param_specs(cfg)

    def fwd(params, tokens):
        # single-microbatch pipeline forward, then gather vocab shards
        pp_idx = jax.lax.axis_index(AXIS_PP)
        sp_idx = jax.lax.axis_index(AXIS_SP)
        tp_idx = jax.lax.axis_index(AXIS_TP)
        B, T = tokens.shape
        pos_global = sp_idx * T + jnp.arange(T)
        rope = _rotary_table(cfg, pos_global) if _rotary(cfg) else None
        x = _embed(cfg, params, tokens, tp_idx, V_loc, pos_global)
        pp = mesh.shape[AXIS_PP]
        state = x
        for s in range(pp):  # unrolled: stage s runs everywhere, keep
            out, _ = _run_stack(cfg, params, state, tp,
                                mesh.shape[AXIS_EP], rope)
            state = jnp.where(pp_idx == s, out, state)
            if pp > 1 and s < pp - 1:
                state = jax.lax.ppermute(
                    state, AXIS_PP,
                    [(i, (i + 1) % pp) for i in range(pp)])
        h = _rms_norm(state, params["ln_f"], cfg.norm_eps)
        # a looped stack runs every pass (no early exit; pp = 1): the
        # last pass's logits
        for _ in range(cfg.loop_passes - 1):
            out, _ = _run_stack(cfg, params, h, tp, mesh.shape[AXIS_EP],
                                rope)
            h = _rms_norm(out, params["ln_f"], cfg.norm_eps)
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
