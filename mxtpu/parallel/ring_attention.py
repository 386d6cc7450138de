"""Ring attention: sequence/context parallelism over the "sp" mesh axis.

Absent from the reference (SURVEY.md §2.4/§5 — long sequences are handled
only by BucketingModule bucketing); table stakes for a TPU framework, so
built first-class here.

Design: the sequence dim is sharded over "sp".  Each device holds its Q
block and streams K/V blocks around the ring with `jax.lax.ppermute`
(nearest-neighbor ICI hops), accumulating attention online with the
numerically-stable log-sum-exp rescaling of flash attention.  Compute on
the current block overlaps the permute of the next: XLA schedules the
ppermute concurrently with the matmuls inside the `lax.fori_loop` body.

`blockwise_attention` is the single-device building block (blocked
softmax accumulation — the same math, looping over local K/V blocks);
`ring_attention` composes it across the ring.  Both are jit-traceable
and differentiable: blockwise via JAX AD of the loop, ring via a
custom recompute backward (a second ring pass against the saved
log-sum-exp) that keeps residual memory O(local shard) — AD through
the forward loop would stash every visiting K/V block, i.e. the full
sequence per device.
"""
from __future__ import annotations

import functools
from typing import Optional

__all__ = ["ring_attention", "blockwise_attention", "ring_self_attention"]

_NEG_INF = -1e30


def _pallas_enabled() -> bool:
    """Shared routing default — exactly flash_attention's own
    kernel-availability predicate, so the router can never send work to
    a kernel that won't engage (which would land in the dense jnp
    reference and materialize the T×T score matrix).  Force the route
    explicitly with ``use_pallas=True`` where needed (tests)."""
    from ..ops.pallas_attention import _use_pallas

    return _use_pallas()


def _match_vma(x, like):
    """Mark `x` as varying over the manual mesh axes `like` varies over
    (lax loop carries need it under shard_map's vma tracking); no-op
    outside shard_map."""
    import jax

    want = set(jax.typeof(like).vma) - set(jax.typeof(x).vma)
    if want:
        x = jax.lax.pcast(x, tuple(want), to="varying")
    return x


def _online_block(q, k, v, acc, row_max, row_sum, mask_bias, scale):
    """One flash-attention accumulation step.

    q: [B, H, Tq, D]; k, v: [B, H, Tk, D]; acc: [B, H, Tq, D];
    row_max/row_sum: [B, H, Tq].  Returns updated (acc, row_max, row_sum).
    """
    import jax.numpy as jnp

    # q/k/v stay in their native (possibly bf16) dtype: the MXU runs
    # single-pass low-precision multiplies with f32 accumulation via
    # preferred_element_type; an f32 operand (upcast q or v) would
    # force the multi-pass f32 matmul path.  The probability block
    # re-enters the MXU in v's dtype (flash-attention standard).
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask_bias is not None:
        scores = scores + mask_bias
    new_max = jnp.maximum(row_max, scores.max(axis=-1))
    correction = jnp.exp(row_max - new_max)
    p = jnp.exp(scores - new_max[..., None])
    new_sum = row_sum * correction + p.sum(axis=-1)
    new_acc = acc * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return new_acc, new_max, new_sum


def blockwise_attention(q, k, v, block_size: int = 512,
                        causal: bool = False, scale: Optional[float] = None,
                        use_pallas: Optional[bool] = None):
    """Memory-efficient attention via blocked online softmax.

    q, k, v: [B, H, T, D] (q may have different T than k/v).  Never
    materializes the full [T, T] score matrix: peak memory is
    O(T * block_size) per head, which is what lets a single chip run
    sequence lengths the reference could not.

    `use_pallas` selects the Pallas flash kernel for the square
    self-attention case; when None it auto-enables exactly where the
    kernel backend exists (TPU, or ``MXTPU_PALLAS_INTERPRET=1``;
    ``MXTPU_NO_PALLAS=1`` is the kill switch) — the same predicate
    ``flash_attention`` itself gates on.  Both paths accumulate in
    float32 and return ``q.dtype``.  NOTE: the routing decision is
    STATIC — under ``jit`` it is resolved once at trace time, so
    flipping the env vars after the first compiled call has no effect
    on cached executables (pass ``use_pallas`` explicitly, or set the
    env before tracing).
    """
    import jax
    import jax.numpy as jnp

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    # Pallas kernel for the square self-attention case (the kernel's
    # causal mask assumes aligned q/k positions; the decode and
    # shard_map-collective paths keep the jnp formulation)
    if use_pallas is None:
        use_pallas = _pallas_enabled()
    if Tq == Tk and use_pallas:
        from ..ops.pallas_attention import flash_attention

        # pass BOTH blocks so the kernel's q tiling follows the
        # caller's block_size too — a default bigger than the local
        # shard would pad q and trip the backward's divisibility gate
        return flash_attention(q, k, v, sm_scale=scale, causal=causal,
                               block_q=block_size, block_k=block_size)
    block_size = min(block_size, Tk)
    n_blocks = (Tk + block_size - 1) // block_size
    pad = n_blocks * block_size - Tk
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        kp, vp = k, v

    acc0 = _match_vma(jnp.zeros((B, H, Tq, D), jnp.float32), q)
    max0 = _match_vma(jnp.full((B, H, Tq), _NEG_INF, jnp.float32), q)
    sum0 = _match_vma(jnp.zeros((B, H, Tq), jnp.float32), q)

    # decode-style alignment: when Tq < Tk the queries are the LAST Tq
    # positions of the key sequence (standard causal cross/decode case)
    q_pos = (Tk - Tq) + jnp.arange(Tq)

    def body(i, carry):
        acc, m, s = carry
        kb = jax.lax.dynamic_slice_in_dim(kp, i * block_size, block_size, 2)
        vb = jax.lax.dynamic_slice_in_dim(vp, i * block_size, block_size, 2)
        k_pos = i * block_size + jnp.arange(block_size)
        bias = jnp.where(k_pos[None, :] >= Tk, _NEG_INF, 0.0)
        if causal:
            bias = bias + jnp.where(k_pos[None, :] > q_pos[:, None],
                                    _NEG_INF, 0.0)
        bias = bias[None, None]  # [1,1,Tq,block]
        return _online_block(q, kb, vb, acc, m, s, bias, scale)

    acc, m, s = jax.lax.fori_loop(0, n_blocks, body, (acc0, max0, sum0))
    out = acc / jnp.maximum(s, 1e-30)[..., None]
    return out.astype(q.dtype)


def _ring_causal_bias(causal, src, my_idx, T):
    import jax.numpy as jnp

    if not causal:
        return None
    q_pos = my_idx * T + jnp.arange(T)
    k_pos = src * T + jnp.arange(T)
    return jnp.where(k_pos[None, :] > q_pos[:, None],
                     _NEG_INF, 0.0)[None, None]


def _ring_forward(q, k, v, axis_name, causal, scale):
    """Forward ring pass; returns (out, lse) with lse = m + log(s) —
    the per-row log-sum-exp the recompute backward needs."""
    import jax
    import jax.numpy as jnp

    sp_size = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, T, D = q.shape
    perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]

    acc0 = _match_vma(jnp.zeros((B, H, T, D), jnp.float32), q)
    max0 = _match_vma(jnp.full((B, H, T), _NEG_INF, jnp.float32), q)
    sum0 = _match_vma(jnp.zeros((B, H, T), jnp.float32), q)

    def body(step, carry):
        acc, m, s, kb, vb = carry
        # the K/V shard visiting at `step` originated on rank
        # (my_idx - step) mod sp
        src = (my_idx - step) % sp_size
        bias = _ring_causal_bias(causal, src, my_idx, T)
        acc, m, s = _online_block(q, kb, vb, acc, m, s, bias, scale)
        # rotate for next step (XLA overlaps this with the block math);
        # K/V ride the ring in their NATIVE dtype — for bf16 inputs
        # that halves the per-hop ppermute bytes on ICI
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return acc, m, s, kb, vb

    acc, m, s, _, _ = jax.lax.fori_loop(
        0, sp_size, body, (acc0, max0, sum0, k, v))
    s = jnp.maximum(s, 1e-30)
    out = acc / s[..., None]
    return out.astype(q.dtype), m + jnp.log(s)


def _ring_backward(q, k, v, out, lse, g, axis_name, causal, scale):
    """Recompute backward: a SECOND ring pass rebuilds each visiting
    block's probabilities from the saved LSE (flash attention §3.1
    applied across the ring).  The visiting shard's dk/dv accumulators
    ride the ring WITH it, so after sp_size hops every shard is home
    with contributions from every rank.  Residual memory is O(local
    shard) — JAX AD of the forward loop would instead stash the
    visiting K/V of every step (sp_size x local, i.e. the full
    sequence per device, defeating sequence parallelism's memory win).
    """
    import jax
    import jax.numpy as jnp

    sp_size = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, T, D = q.shape
    perm = [(i, (i + 1) % sp_size) for i in range(sp_size)]

    g32 = g.astype(jnp.float32)
    delta = (out.astype(jnp.float32) * g32).sum(-1)     # [B,H,T]
    dq0 = _match_vma(jnp.zeros((B, H, T, D), jnp.float32), q)
    dk0 = _match_vma(jnp.zeros((B, H, T, D), jnp.float32), q)
    dv0 = _match_vma(jnp.zeros((B, H, T, D), jnp.float32), q)

    def body(step, carry):
        dq, dkb, dvb, kb, vb = carry
        src = (my_idx - step) % sp_size
        bias = _ring_causal_bias(causal, src, my_idx, T)
        s_blk = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                           preferred_element_type=jnp.float32) * scale
        if bias is not None:
            s_blk = s_blk + bias
        p = jnp.exp(s_blk - lse[..., None])              # [B,H,Tq,Tk]
        dp = jnp.einsum("bhqd,bhkd->bhqk", g, vb,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        # p/ds re-enter the MXU in the activation dtype (_dot_f32
        # convention in ops/pallas_attention.py); accumulators stay f32
        ds_lp = ds.astype(q.dtype)
        p_lp = p.astype(q.dtype)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds_lp, kb,
                             preferred_element_type=jnp.float32)
        dkb = dkb + jnp.einsum("bhqk,bhqd->bhkd", ds_lp, q,
                               preferred_element_type=jnp.float32)
        dvb = dvb + jnp.einsum("bhqk,bhqd->bhkd", p_lp, g,
                               preferred_element_type=jnp.float32)
        # rotate the visiting shard AND its gradient accumulators
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        dkb = jax.lax.ppermute(dkb, axis_name, perm)
        dvb = jax.lax.ppermute(dvb, axis_name, perm)
        return dq, dkb, dvb, kb, vb

    dq, dk, dv, _, _ = jax.lax.fori_loop(
        0, sp_size, body, (dq0, dk0, dv0, k, v))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _ring_fwd_rule(q, k, v, axis_name, causal, scale):
    out, lse = _ring_forward(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_bwd_rule(axis_name, causal, scale, res, g):
    q, k, v, out, lse = res
    return _ring_backward(q, k, v, out, lse, g, axis_name, causal,
                          scale)


_RING = None


def _get_ring():
    """Build the custom_vjp wrapper on first use — decorating at import
    would need a module-level jax import, breaking the package's
    lazy-jax convention."""
    global _RING
    if _RING is None:
        import jax

        ring = jax.custom_vjp(
            lambda q, k, v, axis_name, causal, scale:
            _ring_forward(q, k, v, axis_name, causal, scale)[0],
            nondiff_argnums=(3, 4, 5))
        ring.defvjp(_ring_fwd_rule, _ring_bwd_rule)
        _RING = ring
    return _RING


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention inside shard_map: q/k/v are the LOCAL sequence
    shards [B, H, T_local, D]; the full sequence is T_local * sp_size.

    K/V rotate around the "sp" ring; each step attends the local Q
    against the visiting K/V shard with online-softmax accumulation.
    Causal masking uses global positions derived from the ring index.
    Differentiation uses a custom recompute backward (second ring pass
    against the saved log-sum-exp) so residuals stay O(local shard)
    instead of AD stashing every visiting K/V block.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    import jax

    # degenerate ring (sp=1, e.g. a single chip or an sp-less mesh):
    # no rotation to do — route square attention through the Pallas
    # flash kernel (fwd + recompute bwd) when it is actually enabled.
    # WITHOUT the kernel, stay on the custom-vjp ring (valid at
    # sp_size=1: one step, identity permute): blockwise's jnp path is
    # differentiated by JAX AD through its block loop, which stashes
    # O(T^2/block) probability residuals — exactly the memory blowup
    # this module's recompute backward exists to avoid.
    if jax.lax.axis_size(axis_name) == 1 and _pallas_enabled() \
            and q.shape[2] == k.shape[2]:
        return blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   use_pallas=True)
    return _get_ring()(q, k, v, axis_name, bool(causal), float(scale))


def ring_self_attention(x, wq, wk, wv, wo, n_heads: int,
                        axis_name: str = "sp", causal: bool = True):
    """Full self-attention layer with ring-sharded sequence: x is the
    local shard [B, T_local, E]; weights replicated (or tp-sharded by
    the caller)."""
    import jax.numpy as jnp

    B, T, E = x.shape
    D = wq.shape[1] // n_heads

    def split(h):
        return h.reshape(B, T, n_heads, D).transpose(0, 2, 1, 3)

    q = split(x @ wq)
    k = split(x @ wk)
    v = split(x @ wv)
    o = ring_attention(q, k, v, axis_name=axis_name, causal=causal)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, n_heads * D)
    return o @ wo
