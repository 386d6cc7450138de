"""Flash attention as a Pallas TPU kernel.

The framework's hottest non-conv op.  XLA's generic softmax-attention
materializes the (T, T) score matrix in HBM; this kernel streams K/V
blocks through VMEM with the online log-sum-exp rescaling of flash
attention (Dao et al. 2022), so HBM traffic is O(T·d) instead of
O(T²).  The grid is (batch·lane blocks, q_blocks, k_blocks) with the k
axis innermost — TPU grids execute sequentially, so VMEM scratch
(accumulator + running max/sum) carries state across the k sweep and
the output block is written once on the last k step.  A lane block is
one head, or the heads that share a 128-lane vreg (`_lane_plan`): the
kernels read q, k, v and write their results in [batch, seq, heads *
head_dim], the layout the projections leave and read, and pick heads
by the lane block of their index maps.

`flash_attention` is the public entry: it pads ragged query lengths
to the block size and runs the kernel compiled on a TPU, or in
interpreter mode off one when `MXTPU_PALLAS_INTERPRET=1` (CPU tests);
elsewhere, and for shapes the kernel does not tile, a fused jnp
reference implementation runs, and `profiler.stats()` counts which
path each trace took (`flash_attention_{pallas,reference}`).  The
backward pass is a `jax.custom_vjp` with the BLOCKED recompute formulation (paper §3.1):
scores are rebuilt block by block against the LSE the forward saved
(the kernel emits it as a second output), in two sweeps (dq; dk/dv)
— backward memory is O(T·d + block²) like the forward; the T×T matrix
is never materialized in either direction.  Under `causal=True` all
three kernels walk the score matrix by block class (`_causal_walk`):
blocks above the diagonal are skipped, blocks below it take no mask,
and a block the diagonal crosses is cut into static 128-wide
sub-tiles of which only those on or below the diagonal are computed;
`profiler.stats()` counts the tiles (`flash_tiles_{total,visited,
masked}`).  The sweeps themselves are Pallas
kernels when shapes divide the blocks (`_flash_bwd_dq_kernel`,
`_flash_bwd_dkv_kernel`), with equivalent jnp loops as the ragged /
non-TPU fallback.

Registered as `_contrib_flash_attention` (q, k, v of shape
(batch, heads, seq, head_dim)).  `mxtpu.parallel`'s blockwise /
ring attention routes its local-chunk compute here automatically
wherever the kernel backend exists (see `_use_pallas`).

`flash_attention_bthd` is the entry on the activations' own layout
([batch, seq, heads, head_dim] in, [batch, seq, heads * head_dim]
out): no head split or merge copy in either pass (a shape whose lane
blocks are not whole heads takes one; `profiler.stats()` counts
`flash_calls_in_place` / `flash_calls_split` and the watermark
`flash_heads_per_block`).  ONE `custom_vjp`, whose residuals are q, k,
v as given, the output and the log-sums, the last two under the names
`FLASH_OUT` / `FLASH_LSE` so that a remat policy can keep them
(`executor.apply_remat`'s "dots" does; the LM's blocks call this
entry).  `delta = rowsum(out * g)` is taken inside the two backward
kernels from the tiles they hold.
"""
from __future__ import annotations

import functools
import os

import jax
import numpy as np

from ..base import MXNetError
from .registry import register

_NEG_INF = -1e30
_LANES = 128
_SUB = 128     # side of the causal walk's sub-tiles (see _causal_walk)


def _vma_union(likes):
    """Union of the varying-manual-axes of `likes` (empty outside
    shard_map)."""
    import jax

    out = set()
    for like in likes:
        out |= set(jax.typeof(like).vma)
    return out


def _vma_like(x, *likes):
    """Mark `x` as varying over every manual mesh axis ANY of `likes`
    varies over (loop carries under shard_map need it — and a carry fed
    by q, k, v and g must cover all four, they can shard differently);
    no-op outside shard_map.  Twin of
    parallel.ring_attention._match_vma, duplicated here to keep the ops
    package import-independent of parallel."""
    import jax

    want = _vma_union(likes) - set(jax.typeof(x).vma)
    if want:
        x = jax.lax.pcast(x, tuple(want), to="varying")
    return x


def _sds(shape, dtype, *likes):
    """ShapeDtypeStruct for a pallas_call output; inside shard_map the
    struct must declare its varying-manual-axes (check_vma) — the
    UNION of the operands', since an output varies wherever any input
    does.  Pass vma even when empty: a None-vma struct is rejected
    outright under check_vma, and a replicated operand legitimately
    varies over no axes."""
    import jax

    return jax.ShapeDtypeStruct(shape, dtype,
                                vma=frozenset(_vma_union(likes)))


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


def _interpret():
    """Interpreter mode is for hosts with no TPU (CPU tests); on a TPU
    the kernel is always compiled, so a compile failure is an error
    instead of a silent switch to the interpreter."""
    if os.environ.get("MXTPU_PALLAS_INTERPRET", "0") != "1":
        return False
    if _on_tpu():
        raise MXNetError(
            "MXTPU_PALLAS_INTERPRET=1 on a TPU: interpret mode is for "
            "hosts without one; unset it to run the compiled kernel")
    return True


def _use_pallas():
    """THE authoritative kernel-availability predicate — the flash
    entry, blockwise_attention's routing default, and ring_attention's
    sp=1 shortcut all share it, so route and kernel can never disagree.
    Precedence: MXTPU_NO_PALLAS=1 (kill switch) > a TPU (compiled) >
    interpret mode."""
    if os.environ.get("MXTPU_NO_PALLAS", "0") == "1":
        return False
    return _on_tpu() or _interpret()


def _tiles(block_q, block_k, d):
    """Whether the kernel takes these blocks.  The interpreter takes
    any; compiled, only the tilings that have been on the chip: score
    tiles of whole 128-lane vregs, and a head width that is a lane
    multiple or fits inside one (chip_smoke.py checks d=64, d=128 and,
    for latent attention's 192 + 64, d=256 at T=4096).
    Anything else, a T < 128 included, takes the reference path."""
    if _interpret():
        return True
    return block_q % _LANES == 0 and block_k % _LANES == 0 and \
        (d % _LANES == 0 or d < _LANES)


def _count_path(path):
    """Count, per trace, which implementation a flash_attention call
    took (`flash_attention_pallas` / `flash_attention_reference`), so
    a caller that expects the kernel can check it got it."""
    from .. import profiler as _prof

    _prof.inc_stat("flash_attention_" + path)


def _count_fwd(named):
    """Count, per trace, the forward kernels (`flash_fwd_traced`) and
    those of them traced for differentiation, whose output and log-sums
    go out under `FLASH_OUT` / `FLASH_LSE` (`flash_fwd_named`).  A
    recomputation under `jax.checkpoint` replays traced equations and
    is no trace, so no trace-time count sees it; the compiled programs'
    call sites are held by tests/test_chip_compile.py."""
    from .. import profiler as _prof

    _prof.inc_stat("flash_fwd_traced")
    if named:
        _prof.inc_stat("flash_fwd_named")


def _causal_mask(i, j, block_q, block_k, window=None):
    """(block_q, block_k) mask of the (i, j) score block: the whole-block
    form, for a block on the diagonal where `block_q != block_k`; a
    2-D iota, the form the Pallas TPU guide asks for.  With a `window`
    also the key no more than `window` - 1 behind the query (`k > q -
    window`), for a block the band's LEFT edge crosses: the edge's
    offset inside a block follows `i - j` (two values where the window
    is no multiple of the block), so no static sub-tiling serves every
    window."""
    from jax import lax
    import jax.numpy as jnp

    shape = (block_q, block_k)
    q_idx = lax.broadcasted_iota(jnp.int32, shape, 0) + i * block_q
    k_idx = lax.broadcasted_iota(jnp.int32, shape, 1) + j * block_k
    if window is None:
        return q_idx >= k_idx
    return jnp.logical_and(q_idx >= k_idx, k_idx > q_idx - window)


def _sub_tile(block_q, block_k):
    """(rows, columns) of the tiles the causal walk tells apart: square
    `_SUB`-wide sub-tiles of equal blocks (the whole block where `_SUB`
    does not divide it), whole blocks where `block_q != block_k`."""
    if block_q == block_k and block_q % _SUB == 0:
        return _SUB, _SUB
    return block_q, block_k


def _mask_corner(s, tri, axis):
    """`s` with the ONE sub-tile the diagonal crosses masked by the
    local lower-triangular `tri`: the last columns of a row chunk's
    column prefix (axis=1), or the first rows of a column chunk's row
    suffix (axis=0).  The rest of `s` lies below the diagonal and is
    not touched."""
    import jax.numpy as jnp

    c = tri.shape[0]
    if s.shape == tri.shape:
        return jnp.where(tri, s, _NEG_INF)
    if axis == 1:
        w = s.shape[1] - c
        return jnp.concatenate(
            [s[:, :w], jnp.where(tri, s[:, w:], _NEG_INF)], axis=1)
    return jnp.concatenate(
        [jnp.where(tri, s[:c], _NEG_INF), s[c:]], axis=0)


def _block_classes(i, j, block_q, block_k, window=None, inside=None):
    """(visited, below, edge) of the (i, j) score block under the causal
    mask and, with `window`, the band `q - window < k <= q`; traced
    booleans (`edge` None without a window).  `visited`: not above the
    diagonal, not wholly left of the band, and `inside` the score matrix
    (a band sweep's last steps can stand past its end).  `below`: the
    diagonal does not cross it.  `edge`: the band's left edge does."""
    import jax.numpy as jnp

    visited = j * block_k <= (i + 1) * block_q - 1
    below = (j + 1) * block_k - 1 <= i * block_q
    if inside is not None:
        visited = jnp.logical_and(visited, inside)
    if window is None:
        return visited, below, None
    left = (j + 1) * block_k - 1 <= i * block_q - window
    visited = jnp.logical_and(visited, jnp.logical_not(left))
    edge = j * block_k <= (i + 1) * block_q - 1 - window
    return visited, below, edge


def _causal_walk(step, i, j, block_q, block_k, by_rows, window=None,
                 inside=None):
    """THE causal walk, one copy for the forward and both backward
    sweeps: which class the (i, j) block is follows from the block
    indices and the static block shape alone, and only that class's
    work is emitted.  `step(rows, cols, mask)` handles q rows `rows`
    against k rows `cols` of the block (static slices); `mask` maps
    the f32 scores to masked scores, or is None.

    1. above the diagonal, or with a static `window` wholly left of the
       band (`_block_classes`): skipped;
    2. strictly below the diagonal (and right of the band's left edge):
       ONE unmasked step over the whole block (no iota, no compare, no
       select);
    3. crossed by the diagonal: with equal blocks, static `_SUB`-wide
       chunks -- per q row chunk one step over its k column prefix
       (`by_rows`: forward and dq, whose state is per q row), or per k
       column chunk one step over its q row suffix (dkv, whose state is
       per k row); sub-tiles above the diagonal are never emitted, and
       the one sub-tile on it takes a LOCAL triangular mask with no
       `program_id` in it.  With unequal blocks, the whole block under
       `_causal_mask`;
    4. crossed by the band's left edge (the diagonal may cross it too,
       where the window is narrower than a block): the whole block
       under `_causal_mask`'s band form.  A row of it that sees none of its keys
       leaves the forward's state as a later block finds it: its
       running max stays at the floor, so the next block's rescale is
       by `exp(floor - max) = 0`."""
    from jax import lax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    full = slice(None)
    visited, below, edge = _block_classes(i, j, block_q, block_k, window,
                                          inside)
    if edge is not None:
        off_edge = jnp.logical_and(visited, jnp.logical_not(edge))

        @pl.when(jnp.logical_and(visited, edge))
        def _edge():
            mask = _causal_mask(i, j, block_q, block_k, window)
            step(full, full, lambda s: jnp.where(mask, s, _NEG_INF))
    else:
        off_edge = visited

    @pl.when(below if edge is None and inside is None
             else jnp.logical_and(off_edge, below))
    def _unmasked():
        step(full, full, None)

    @pl.when(jnp.logical_and(off_edge, jnp.logical_not(below)))
    def _diagonal():
        if block_q != block_k:
            mask = _causal_mask(i, j, block_q, block_k)
            step(full, full, lambda s: jnp.where(mask, s, _NEG_INF))
            return
        c = _sub_tile(block_q, block_k)[0]
        tri = lax.broadcasted_iota(jnp.int32, (c, c), 0) \
            >= lax.broadcasted_iota(jnp.int32, (c, c), 1)
        for lo in range(0, block_q, c):
            hi = lo + c
            if by_rows:
                step(slice(lo, hi), slice(0, hi),
                     lambda s: _mask_corner(s, tri, 1))
            else:
                step(slice(lo, block_q), slice(lo, hi),
                     lambda s: _mask_corner(s, tri, 0))


def _visited(i, j, block_q, block_k, causal, window=None, inside=None):
    """Whether a sweep computes anything of the (i, j) score block:
    always, or under `causal` unless it lies above the diagonal (or
    left of the band, or past the matrix's end: `_block_classes`).  A
    traced value either way: what a kernel does to its refs it does
    under `pl.when`, where the interpreter does not hold it against the
    varying axes of a `shard_map`."""
    if not causal:
        return j >= 0 if inside is None else inside
    if window is None and inside is None:
        return j * block_k <= (i + 1) * block_q - 1
    return _block_classes(i, j, block_q, block_k, window, inside)[0]


def _walk(step, i, j, block_q, block_k, causal, by_rows, window=None,
          inside=None):
    """One (i, j) score block of a sweep: the causal walk, or ONE
    unmasked step over the whole block."""
    from jax.experimental import pallas as pl

    if causal:
        return _causal_walk(step, i, j, block_q, block_k, by_rows, window,
                            inside)
    pl.when(_visited(i, j, block_q, block_k, causal, None, inside))(
        lambda: step(slice(None), slice(None), None))


def _count_tiles(tq, tk, block_q, block_k, causal, window=None):
    """Per traced kernel call and per head, in tiles of `_sub_tile`:
    how many the score matrix has (`flash_tiles_total`), how many the
    kernels compute (`flash_tiles_visited`) and how many of those they
    mask (`flash_tiles_masked`), so a caller can see the walk engaged.
    With a `window` a block wholly left of the band is not visited, and
    every tile of a block the band's left edge crosses is computed
    under the whole block's mask."""
    from .. import profiler as _prof

    cq, ck = _sub_tile(block_q, block_k)
    a = np.arange(-(-tq // cq))[:, None]
    b = np.arange(tk // ck)[None, :]
    visited = np.ones((a.size, b.size), bool)
    masked = ~visited
    if causal:      # the same two tests as _causal_walk's, per tile
        visited = b * ck <= (a + 1) * cq - 1
        masked = visited & ((b + 1) * ck - 1 > a * cq)
    if window is not None:      # ... and its two of the band, per BLOCK
        i, j = a * cq // block_q, b * ck // block_k
        left = (j + 1) * block_k - 1 <= i * block_q - window
        edge = ~left & (j * block_k <= (i + 1) * block_q - 1 - window)
        visited = ~left & (edge | visited)
        masked = ~left & (edge | masked)
    _prof.inc_stat("flash_tiles_total", int(visited.size))
    _prof.inc_stat("flash_tiles_visited", int(visited.sum()))
    _prof.inc_stat("flash_tiles_masked", int(masked.sum()))


def _band_steps(nq, nk, block_q, block_k, causal, window):
    """(k blocks the longest sweep of a q block visits, q blocks the
    longest sweep of a k block visits): the lengths of the sweeps'
    inner grid axes in the band form, where a sweep starts at its first
    visited block (`_first_k_block`, `_first_q_block`) and not at block
    0, so a window layer at T = 8192 in blocks of 512 walks 5 steps a q
    block and not 16."""
    i, j = np.arange(nq), np.arange(nk)
    j_hi = np.minimum(((i + 1) * block_q - 1) // block_k, nk - 1) \
        if causal else np.full(nq, nk - 1)
    j_lo = 0 if window is None else \
        _first_k_block(i, block_q, block_k, window)
    i_lo = np.minimum(_first_q_block(j, block_q, block_k, causal), nq - 1)
    i_hi = np.minimum(_last_q_block(j, block_q, block_k, window, nq), nq - 1)
    return int((j_hi - j_lo + 1).max()), int((i_hi - i_lo + 1).max())


def _first_k_block(i, block_q, block_k, window):
    """The first k block q block `i` sees under `window`: the one that
    holds key `i * block_q - window + 1`.  `i`: a traced index, or
    numpy's (`_band_steps`: static, inside a trace)."""
    import jax.numpy as jnp

    xp = np if isinstance(i, np.ndarray) else jnp
    return xp.maximum(i * block_q - window + 1, 0) // block_k


def _first_q_block(j, block_q, block_k, causal):
    """The first q block that sees k block `j`: the diagonal's."""
    return (j * block_k) // block_q if causal else 0


def _last_q_block(j, block_q, block_k, window, nq):
    """The last q block that sees k block `j` under `window` (the one
    that holds query `(j + 1) * block_k + window - 2`), not held to the
    `nq` blocks there are; without a window the last there is."""
    if window is None:
        return nq - 1
    return ((j + 1) * block_k + window - 2) // block_q


def _lane_block(b, heads):
    """(batch row, lane block) of grid step `b` over [N, T, heads * w]:
    the grid's first axis walks the `heads` lane blocks of a row
    innermost."""
    if heads == 1:
        return b, 0
    return b // heads, b % heads


def _k_index_map(causal, block_q, block_k, heads=1, group=1, window=None,
                 nk=None):
    """Index map of a k or v tile in a k-innermost sweep (grid b, i,
    j): block j of lane block `b % heads`, but a causal step above the
    diagonal (skipped in the kernel) names the last visited block
    again, so no tile is fetched for it.  With `group` q heads to a kv
    head the tile is lane block `(b % heads) // group` of k's own
    narrower array; with a `window` step j stands on block
    `_first_k_block(i) + j` (the band form: `nk` is the number of k
    blocks there are)."""
    import jax.numpy as jnp

    def index(b, i, j):
        n, lane = _lane_block(b, heads)
        if window is not None:
            j = jnp.minimum(
                _first_k_block(i, block_q, block_k, window) + j,
                jnp.minimum(((i + 1) * block_q - 1) // block_k, nk - 1))
        elif causal:
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        if group > 1:
            lane = lane // group
        return n, j, lane

    return index


def _q_index_map(causal, block_q, block_k, nq, heads=1):
    """The same for a q-side tile in the q-innermost dkv sweep (grid
    b, j, i): a skipped step names the first q block that sees k
    block j."""
    import jax.numpy as jnp

    def index(b, j, i):
        n, lane = _lane_block(b, heads)
        if causal:
            i = jnp.maximum(i, jnp.minimum((j * block_k) // block_q,
                                           nq - 1))
        return n, i, lane

    return index


def _band_q_index_map(causal, block_q, block_k, nq, kv_heads, group, steps,
                      window):
    """A q-side tile in the BAND form of the dkv sweep: grid (b over
    the kv lane blocks, j, x), where the inner axis runs over the
    `group` q heads that share kv head b and, for each, over the
    `steps` q blocks from the first that sees k block j
    (`_first_q_block`): x = head * steps + block.  Steps past the last
    q block that sees k block j (skipped in the kernel) name that block
    again, so no tile is fetched for them."""
    import jax.numpy as jnp

    def index(b, j, x):
        n, lane = _lane_block(b, kv_heads)
        i = _first_q_block(j, block_q, block_k, causal) + x % steps
        last = jnp.minimum(
            _last_q_block(j, block_q, block_k, window, nq), nq - 1)
        return n, jnp.minimum(i, last), lane * group + x // steps

    return index


def _outer_index_map(heads=1):
    """Index map of the tile a sweep holds fixed (grid b, outer, inner):
    block `outer` of lane block `b % heads`."""
    def index(b, outer, inner):
        n, lane = _lane_block(b, heads)
        return n, outer, lane

    return index


def _row_index_map(qmap, heads=None):
    """Index map of the (1, rows, block_q) tile of per-row numbers
    (`_ROWS` sublanes of [N * heads, _ROWS, T]) that goes with the q
    tile `qmap` names: the same q block of grid step b's own row, or
    where the grid's b does not walk the q side's lane blocks (the band
    form of the dkv sweep: give `heads`, the q side's) of the row of the
    lane block `qmap` names."""
    if heads is None:
        return lambda b, x, y: (b, 0, qmap(b, x, y)[1])

    def index(b, x, y):
        n, i, lane = qmap(b, x, y)
        return n * heads + lane, 0, i

    return index


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _dot_f32(a, b, contract=((1,), (1,))):
    """MXU-friendly matmul: operands stay in their native (possibly
    bf16) dtype so the systolic array runs single-pass multiplies, with
    float32 accumulation via preferred_element_type.  Mixed f32 x bf16
    pairs cast the f32 side DOWN (flash-attention standard: the
    probability / dscore blocks re-enter the MXU in the activation
    dtype; an f32 operand would force the multi-pass f32 matmul path).
    Same-dtype f32 inputs are untouched — full-precision tests see
    identical math."""
    from jax import lax
    import jax.numpy as jnp

    if a.dtype != b.dtype:
        if a.dtype == jnp.float32:
            a = a.astype(b.dtype)
        elif b.dtype == jnp.float32:
            b = b.astype(a.dtype)
    return lax.dot_general(a, b, (contract, ((), ())),
                           preferred_element_type=jnp.float32)


# Sublanes of the log-sums' tile, a lane block's heads in the first of
# them: one float32 vreg row group, so the tile is whole vregs and T lies
# along the LANES -- 8 x T x 4 bytes a lane block, where a (T, 1) column
# a head is padded to 128 x T x 4 on the chip.
_ROWS = 8
# VMEM the dkv sweep may spend on keeping every q block's columns
# (log-sums and deltas, 512 bytes a row and quantity) over its k blocks:
# T = 4096 at one head to a lane block, 2730 at two
_Q_SIDE_BYTES = 4 * 2 ** 20


def _head_lanes(per_block, width):
    """One (1, width) lane mask per head of a lane block that holds
    `per_block` heads side by side; [None] where a block is one head."""
    from jax import lax
    import jax.numpy as jnp

    if per_block == 1:
        return [None]
    d = width // per_block
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [jnp.logical_and(lane >= r * d, lane < (r + 1) * d)
            for r in range(per_block)]


def _own_lanes(lane, new, old):
    """`new` in the lanes of the head `lane` masks, `old` elsewhere (a
    product against a tile that holds several heads is right in the
    lanes of the head whose scores went in, and another head's
    elsewhere)."""
    import jax.numpy as jnp

    return new if lane is None else jnp.where(lane, new, old)


def _keep_head_tiles(dst_ref, src_ref, lanes):
    """dst_ref[r] = the tile src_ref[0] with every head's lanes but head
    r's zeroed: a product that contracts over the block's whole lane
    width then adds nothing from the other heads.  Once per outer block,
    into VMEM; no lane moves."""
    import jax.numpy as jnp

    tile = src_ref[0]
    for r, lane in enumerate(lanes):
        dst_ref[r] = jnp.where(lane, tile, jnp.zeros_like(tile))


def _columns_to_rows(cols):
    """Per-row numbers from column form -- a list of (block_q, 1), one
    per head -- to the (`_ROWS`, block_q) tile the log-sums leave the
    forward kernel in: row r (and r + heads, ...) is head r's."""
    from jax import lax
    import jax.numpy as jnp

    n = cols[0].shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) % len(cols)
    wide = jnp.broadcast_to(cols[-1], (n, _LANES))
    for r, col in enumerate(cols[:-1]):
        wide = jnp.where(lane == r, col, wide)
    return wide.T[:_ROWS]


def _rows_to_columns(rows):
    """The (`_ROWS`, block_q) tile of per-row numbers to (block_q,
    128): lane c holds the tile's row c % `_ROWS` as a column, the form
    that subtracts from a (block_q, block_k) score tile."""
    import jax.numpy as jnp

    return jnp.tile(rows, (_LANES // _ROWS, 1)).T


def _keep_q_side(lse_ref, dlt_ref, slot, row_ref, o_ref, g_ref, lanes):
    """What a backward sweep needs of a q block beside its tiles, into
    slot `slot` of VMEM scratch: lse_ref[slot] = the log-sums' rows as
    columns (`_rows_to_columns`), dlt_ref[r, slot] = head r's `delta =
    rowsum(out * g)`, float32, as a lane-replicated column (the running
    max's form).  The output and its cotangent are in VMEM as the
    sweeps' q-side tiles, so the row sum costs one pass over them and
    no array of its own."""
    import jax.numpy as jnp

    lse_ref[slot] = _rows_to_columns(row_ref[0])
    prod = o_ref[0].astype(jnp.float32) * g_ref[0].astype(jnp.float32)
    for r, lane in enumerate(lanes):
        own = prod if lane is None else jnp.where(lane, prod, 0.0)
        dlt_ref[r, slot] = jnp.broadcast_to(
            jnp.sum(own, axis=1, keepdims=True), dlt_ref.shape[2:])


def _k_sweep_step(block_q, block_k, window, nk):
    """Where a k-innermost sweep (forward, dq) stands: (q block i, step
    of the sweep, the k block j it is on, whether j is a block of the
    matrix or None where it always is).  Without a `window` step and
    block are one; with one the sweep starts at `_first_k_block(i)`."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    step = pl.program_id(2)       # innermost, sequential
    if window is None:
        return i, step, step, None
    j = _first_k_block(i, block_q, block_k, window) + step
    return i, step, j, j < nk


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale, causal,
                  block_q, block_k, want_lse, per_block, window=None,
                  nk=None):
    rest = list(rest)
    lse_ref = rest.pop(0) if want_lse else None
    acc_ref, m_ref, l_ref = rest[:3]
    qh_ref = rest[3] if per_block > 1 else None
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i, at, j, inside = _k_sweep_step(block_q, block_k, window, nk)
    lanes = _head_lanes(per_block, q_ref.shape[2])

    @pl.when(at == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        if qh_ref is not None:
            _keep_head_tiles(qh_ref, q_ref, lanes)

    def _step(rows, cols, mask):
        for r, lane in enumerate(lanes):
            # native-dtype operands on the MXU, f32 accumulate; the
            # softmax scale applies to the f32 scores (not the bf16 q,
            # which would round it into the inputs)
            q = q_ref[0, rows] if qh_ref is None else qh_ref[r, rows]
            s = _dot_f32(q, k_ref[0, cols]) * sm_scale
            if mask is not None:
                s = mask(s)
            m_prev = m_ref[r, rows, 0:1]                  # (rows, 1)
            l_prev = l_ref[r, rows, 0:1]
            m_cur = jnp.max(s, axis=1, keepdims=True)     # (rows, 1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                        # (rows, cols)
            alpha = jnp.exp(m_prev - m_new)               # rescale old state
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc = acc_ref[rows]
            acc_ref[rows] = _own_lanes(
                lane, acc * alpha + _dot_f32(p, v_ref[0, cols],
                                             ((1,), (0,))), acc)
            wide = (m_new.shape[0], m_ref.shape[2])
            m_ref[r, rows] = jnp.broadcast_to(m_new, wide)
            l_ref[r, rows] = jnp.broadcast_to(l_new, wide)

    _walk(_step, i, j, block_q, block_k, causal, True, window, inside)

    @pl.when(at == pl.num_programs(2) - 1)
    def _finish():
        out, lses = None, []
        for r, lane in enumerate(lanes):
            l = jnp.maximum(l_ref[r, :, 0:1], 1e-30)
            out = acc_ref[:] / l if out is None \
                else jnp.where(lane, acc_ref[:] / l, out)
            lses.append(m_ref[r, :, 0:1] + jnp.log(l))
        o_ref[0] = out.astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row log-sum-exp, saved for the backward
            lse_ref[0] = _columns_to_rows(lses)


def _flash_forward_pallas(q, k, v, sm_scale, causal, block_q, block_k,
                          want_lse, heads=1, per_block=1, group=1,
                          window=None):
    """Runs the kernel on q of [N, T, heads * w], a lane block of width
    w holding `per_block` heads side by side, and k, v of [N, T, heads
    // group * w] (`group` q heads read one kv head, in place: the index
    map picks its lane block); returns (out in q's layout, log-sums (N *
    heads * per_block, T) or None).  With a `window` the k axis of the
    grid is the band's length (`_band_steps`).  The LSE
    output is built only when requested — pallas_call is an opaque
    custom call, so an unused output would still be written to HBM."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, tq, width = q.shape
    w = width // heads
    tk = k.shape[1]
    nq, nk = pl.cdiv(tq, block_q), pl.cdiv(tk, block_k)
    band = {}
    if window is not None:
        band = dict(window=window, nk=nk)
        nk = _band_steps(nq, nk, block_q, block_k, causal, window)[0]
    grid = (n * heads, nq, nk)
    kmap = _k_index_map(causal, block_q, block_k, heads, group, **band)
    qmap = _outer_index_map(heads)
    kernel = functools.partial(_flash_kernel, sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, want_lse=want_lse,
                               per_block=per_block, **band)
    out_shape = [_sds((n, tq, width), q.dtype, q, k, v)]
    out_specs = [pl.BlockSpec((1, block_q, w), qmap)]
    if want_lse:
        out_shape.append(
            _sds((n * heads, _ROWS, tq), jnp.float32, q, k, v))
        out_specs.append(pl.BlockSpec((1, _ROWS, block_q),
                                      _row_index_map(qmap)))
    scratch = [
        pltpu.VMEM((block_q, w), jnp.float32),                 # acc
        pltpu.VMEM((per_block, block_q, 128), jnp.float32),    # running max
        pltpu.VMEM((per_block, block_q, 128), jnp.float32),    # running sum
    ]
    if per_block > 1:
        scratch.append(pltpu.VMEM((per_block, block_q, w), q.dtype))
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, w), qmap),
            pl.BlockSpec((1, block_k, w), kmap),
            pl.BlockSpec((1, block_k, w), kmap),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        interpret=_interpret(),
        name="mx_flash_fwd",
    )(q, k, v)
    if want_lse:
        return outs[0], outs[1][:, :per_block].reshape(-1, tq)
    return outs[0], None


def _bwd_p_ds(q, k, v, g, lse, dlt, mask, sm_scale):
    """Shared backward math of one head's q rows (q, its cotangent g,
    its log-sums and delta as (rows, 1)) against k rows (k, v): rebuild
    the scores against the saved LSE and return (p, ds) -- ONE copy of
    the ds formula for both sweeps; `mask` is the causal walk's (None
    below the diagonal)."""
    import jax.numpy as jnp

    s = _dot_f32(q, k) * sm_scale
    if mask is not None:
        s = mask(s)
    p = jnp.exp(s - lse)
    dp = _dot_f32(g, v)
    ds = p * (dp - dlt) * sm_scale
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, row_ref,
                         dq_ref, acc_ref, lse_ref, dlt_ref, *kept,
                         sm_scale, causal, block_q, block_k, per_block,
                         window=None, nk=None):
    """dq sweep: grid (n * heads, nq, nk), k innermost; accumulates
    ds·K into VMEM scratch and writes the q block's dq once.  The q
    side is fixed over the sweep: its log-sums are turned to columns,
    its delta is taken, and with several heads to a lane block q and g
    are cut to each head's lanes, once per q block."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i, at, j, inside = _k_sweep_step(block_q, block_k, window, nk)
    lanes = _head_lanes(per_block, q_ref.shape[2])

    @pl.when(at == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        _keep_q_side(lse_ref, dlt_ref, 0, row_ref, o_ref, g_ref, lanes)
        if kept:
            _keep_head_tiles(kept[0], q_ref, lanes)
            _keep_head_tiles(kept[1], g_ref, lanes)

    def _step(rows, cols, mask):
        k = k_ref[0, cols]                 # native dtype (see _dot_f32)
        for r, lane in enumerate(lanes):
            q, g = (kept[0][r, rows], kept[1][r, rows]) if kept \
                else (q_ref[0, rows], g_ref[0, rows])
            _, ds = _bwd_p_ds(q, k, v_ref[0, cols], g,
                              lse_ref[0, rows, r:r + 1],
                              dlt_ref[r, 0, rows, 0:1], mask, sm_scale)
            acc = acc_ref[rows]
            acc_ref[rows] = _own_lanes(
                lane, acc + _dot_f32(ds, k, ((1,), (0,))), acc)

    _walk(_step, i, j, block_q, block_k, causal, True, window, inside)

    @pl.when(at == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, row_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, lse_ref,
                          dlt_ref, *kept, sm_scale, causal, block_q,
                          block_k, per_block, window=None, band=None):
    """dk/dv sweep: grid (n * heads, nk, nq), q innermost.  The k side
    is fixed over the sweep (with several heads to a lane block k and v
    are cut to each head's lanes once per k block).  What the sweep
    needs of a q block beside its tiles (`_keep_q_side`) is taken while
    the first k block is swept, which every q block sees, and kept for
    the later ones in a slot per q block; where T is too long for that
    (`lse_ref` has one slot) it is taken every step.

    In the BAND form (`band` = (steps, nq): grouped kv heads, or a
    `window`) the grid is (n * kv heads, nk, group * steps): the inner
    axis runs over the q heads that share this kv head and, for each,
    over the `steps` q blocks from the diagonal on (`_band_q_index_map`),
    and dk, dv are summed over all of them in VMEM: no dk or dv a q head
    is ever written.  The q side is taken every step there."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    at = pl.program_id(2)
    if band is None:
        i, inside = at, None
    else:
        i = _first_q_block(j, block_q, block_k, causal) + at % band[0]
        inside = i < band[1]
    lanes = _head_lanes(per_block, q_ref.shape[2])

    @pl.when(at == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if kept:
            _keep_head_tiles(kept[0], k_ref, lanes)
            _keep_head_tiles(kept[1], v_ref, lanes)

    slots = lse_ref.shape[0]
    slot = i if slots > 1 else 0

    @pl.when(j == 0 if slots > 1
             else _visited(i, j, block_q, block_k, causal, window, inside))
    def _q_side():
        _keep_q_side(lse_ref, dlt_ref, slot, row_ref, o_ref, g_ref, lanes)

    def _step(rows, cols, mask):
        q = q_ref[0, rows]
        g = g_ref[0, rows]
        for r, lane in enumerate(lanes):
            k, v = (kept[0][r, cols], kept[1][r, cols]) if kept \
                else (k_ref[0, cols], v_ref[0, cols])
            p, ds = _bwd_p_ds(q, k, v, g, lse_ref[slot, rows, r:r + 1],
                              dlt_ref[r, slot, rows, 0:1], mask,
                              sm_scale)
            dv = dv_acc[cols]
            dv_acc[cols] = _own_lanes(
                lane, dv + _dot_f32(p, g, ((0,), (0,))), dv)
            dk = dk_acc[cols]
            dk_acc[cols] = _own_lanes(
                lane, dk + _dot_f32(ds, q, ((0,), (0,))), dk)

    _walk(_step, i, j, block_q, block_k, causal, False, window, inside)

    @pl.when(at == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward_pallas(q, k, v, g, out, lse, sm_scale, causal,
                           block_q, block_k, heads=1, per_block=1, group=1,
                           window=None):
    """Pallas backward: two kernel launches (dq; dk/dv) over the saved
    output and LSE — the TPU-kernel analog of the jnp blocked sweeps
    below.  q, the cotangent g, `out` and dq: [N, T, heads * w], a lane
    block of width w holding `per_block` heads; k, v, dk and dv: [N, T,
    heads // group * w] (`group` q heads to a kv head, read and written
    in place: dk and dv are summed over the group's q heads inside the
    dkv sweep); `lse`: (N * heads * per_block, T).  `delta = rowsum(out
    * g)` is taken inside both sweeps from the tiles they hold
    (`_keep_q_side`): as an XLA reduction it cost two whole-array
    transposing copies a call, whichever layout it was asked in
    (PERF.md, PR 35).  With a `window`, and for grouped kv heads, the
    sweeps' inner grid axes take the band form (`_band_steps`)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, tq, width = q.shape
    w = width // heads
    tk = k.shape[1]
    nq = tq // block_q
    nk = tk // block_k
    # the log-sums travel with T along the LANES, a lane block's heads
    # in the first of `_ROWS` sublanes (a (T, 1) column per head is
    # padded 128-fold on the chip)
    rows = jnp.pad(lse.reshape(n * heads, per_block, tq),
                   ((0, 0), (0, _ROWS - per_block), (0, 0)))
    band = {}
    k_steps, q_steps = nk, nq
    if window is not None:
        band = dict(window=window, nk=nk)
        k_steps, q_steps = _band_steps(nq, nk, block_q, block_k, causal,
                                       window)

    qmap = _outer_index_map(heads)
    qspec = pl.BlockSpec((1, block_q, w), qmap)
    kspec = pl.BlockSpec((1, block_k, w),
                         _k_index_map(causal, block_q, block_k, heads,
                                      group, **band))
    rspec = pl.BlockSpec((1, _ROWS, block_q), _row_index_map(qmap))

    def q_side(slots):      # `_keep_q_side`'s scratch: log-sums, delta
        return [pltpu.VMEM((slots, block_q, _LANES), jnp.float32),
                pltpu.VMEM((per_block, slots, block_q, _LANES),
                           jnp.float32)]

    def kept(block, dtype):
        return [pltpu.VMEM((per_block, block, w), dtype)] * 2 \
            if per_block > 1 else []

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, per_block=per_block, **band),
        out_shape=_sds((n, tq, width), q.dtype, q, k, v, g),
        grid=(n * heads, nq, k_steps),
        in_specs=[qspec, kspec, kspec, qspec, qspec, rspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, w), jnp.float32)] + q_side(1)
        + kept(block_q, q.dtype),
        interpret=_interpret(),
        name="mx_flash_dq",
    )(q, k, v, g, out, rows)

    kv_heads = heads // group
    if group == 1 and window is None:
        # dkv grid: (n * heads, nk, nq) — q innermost; index maps swap
        # (i, j)
        qmap2 = _q_index_map(causal, block_q, block_k, nq, heads)
        rmap2 = _row_index_map(qmap2)
        # a slot per q block where the whole sequence's columns fit
        slots = nq if (1 + per_block) * tq * _LANES * 4 <= _Q_SIDE_BYTES \
            else 1
        form = {}
    else:
        # the band form: (n * kv heads, nk, group * q_steps); the q side
        # is taken every step (a slot per q head and block is group * T
        # KiB of VMEM: 64 MiB at 8 heads a group and T = 8192)
        qmap2 = _band_q_index_map(causal, block_q, block_k, nq, kv_heads,
                                  group, q_steps, window)
        rmap2 = _row_index_map(qmap2, heads)
        slots = 1
        form = dict(window=window, band=(q_steps, nq))
        q_steps *= group
    qspec2 = pl.BlockSpec((1, block_q, w), qmap2)
    kspec2 = pl.BlockSpec((1, block_k, w), _outer_index_map(kv_heads))
    rspec2 = pl.BlockSpec((1, _ROWS, block_q), rmap2)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q,
                          block_k=block_k, per_block=per_block, **form),
        out_shape=(_sds(k.shape, k.dtype, q, k, v, g),
                   _sds(v.shape, v.dtype, q, k, v, g)),
        grid=(n * kv_heads, nk, q_steps),
        in_specs=[qspec2, kspec2, kspec2, qspec2, qspec2, rspec2],
        out_specs=(kspec2, kspec2),
        scratch_shapes=[pltpu.VMEM((block_k, w), jnp.float32),
                        pltpu.VMEM((block_k, w), jnp.float32)]
        + q_side(slots) + kept(block_k, k.dtype),
        interpret=_interpret(),
        name="mx_flash_dkv",
    )(q, k, v, g, out, rows)
    return dq, dk, dv


def _reference_attention_lse(q, k, v, sm_scale, causal, window=None):
    """Fused jnp reference; returns (out, per-row log-sum-exp).  With a
    `window` a query sees the keys no more than `window` - 1 behind it."""
    import jax.numpy as jnp

    # native-dtype operands + f32 accumulation (MXU single-pass for
    # bf16; identical math for f32 inputs) — see _dot_f32
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        tq, tk = s.shape[-2:]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        if window is not None:
            mask &= jnp.arange(tk)[None, :] > jnp.arange(tq)[:, None] - window
        s = jnp.where(mask, s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32) \
        .astype(q.dtype)
    return out, lse


def _reference_attention(q, k, v, sm_scale, causal, window=None):
    """Fused jnp reference (also the CPU/GPU fallback path)."""
    return _reference_attention_lse(q, k, v, sm_scale, causal, window)[0]


# The names the differentiated forward gives its two results.  A
# `jax.checkpoint` policy that lists them (`executor.apply_remat`'s
# "dots") keeps them, and the backward pass then does not run the
# forward kernel a second time to have them; outside `jax.checkpoint`
# a name is a no-op.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"


def _split_heads(x):
    """[B, T, H, D], the activations' layout, -> (B*H, T, D): what the
    reference path computes on, and the kernels where [B, T, H * D] has
    no lane block that is whole heads."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _merge_heads(x, b):
    """(B*H, T, D) -> [B, T, H, D]."""
    bh, t, d = x.shape
    return x.reshape(b, bh // b, t, d).transpose(0, 2, 1, 3)


def _lane_plan(h, d):
    """How the kernels read [B, T, h * d]: (heads to a lane block, or 0
    where no lane block is whole heads and the kernels take a split
    copy).  A head that is whole 128-lane vregs wide is a block of its
    own; narrower heads that fill one vreg share it (the kernels walk
    them inside one grid step; `_ROWS` at most: their log-sums share
    that tile's sublanes); one head is the whole last dimension,
    whatever its width."""
    if h == 1 or d % _LANES == 0:
        return 1
    per_block = _LANES // d
    if _LANES % d == 0 and h % per_block == 0 and per_block <= _ROWS:
        return per_block
    return 0


def _expand_kv(x, group):
    """k or v [B, T, Hkv, D] with every head `group` times, at q's head
    count: what the jnp paths compute on, and the kernels where they
    cannot read the kv heads in place."""
    import jax.numpy as jnp

    return x if group == 1 else jnp.repeat(x, group, axis=2)


def _sum_group(x, group):
    """dk or dv at q's head count [B, T, H, D] summed over the q heads
    of each kv head: [B, T, H // group, D]."""
    if group == 1:
        return x
    b, t, h, d = x.shape
    return x.reshape(b, t, h // group, group, d).sum(axis=3)


def _kernel_layout(q_side, kv_side, launches, window=None):
    """([B, T, H, D] arrays of q's side and [B, T, Hkv, D] arrays of
    k's in the kernels' [N, T, lane blocks * w], q's lane blocks, heads
    to a lane block, q heads to a kv head as the kernels will see it): a
    free reshape in place, `_split_heads` where `_lane_plan` finds no
    lane block of whole heads.  Fewer kv heads than q heads are read in
    place where a head is a lane block of its own; elsewhere k and v go
    in expanded to q's head count (`_expand_kv`) and the group comes
    back as 1.  Counts the `launches` that will read them
    (`flash_calls_in_place` / `flash_calls_split`, and
    `flash_kv_expanded` those fed an expanded k / v), the heads to a
    block (`flash_heads_per_block`) and, as watermarks too, the q heads
    to a kv head (`flash_kv_group`) and the `window` (`flash_window`; 0:
    none)."""
    from .. import profiler as _prof

    b, t, h, d = q_side[0].shape
    group = h // kv_side[0].shape[2]
    per_block = _lane_plan(h, d)
    _prof.inc_stat("flash_calls_in_place" if per_block
                   else "flash_calls_split", launches)
    _prof.max_stat("flash_heads_per_block", max(per_block, 1))
    _prof.max_stat("flash_kv_group", group)
    _prof.max_stat("flash_window", window or 0)
    if group > 1 and per_block != 1:
        _prof.inc_stat("flash_kv_expanded", launches)
        kv_side = [_expand_kv(x, group) for x in kv_side]
        group = 1
    xs = list(q_side) + list(kv_side)
    if per_block:
        return [x.reshape(x.shape[0], x.shape[1], -1) for x in xs], \
            h // per_block, per_block, group
    return [_split_heads(x) for x in xs], 1, 1, group


def _from_kernel_layout(x, shape):
    """A kernel's result back in [B, T, H, D]: the reshape again, or
    the merge of a split copy (whose rows are B * H)."""
    return x.reshape(shape) if x.shape[0] == shape[0] \
        else _merge_heads(x, shape[0])


def _takes_kernel(tq, tk, block_q, block_k, d, ragged_q):
    """Whether these lengths go to the kernels.  INVARIANT: the kernel
    never sees padded KEY positions (a padded key would need
    per-position masking inside the kernel); ragged K lengths take the
    fused reference path.  Ragged Q is safe in the forward pass —
    padded query rows are sliced off — and takes the jnp sweeps in the
    backward pass."""
    return _use_pallas() and tk % block_k == 0 \
        and (ragged_q or tq % block_q == 0) \
        and _tiles(block_q, block_k, d)


def _flash_merged(q, k, v, sm_scale, causal, block_q, block_k, window,
                  want_lse):
    """The forward on q [B, T, H, D] and k, v [B, T, Hkv, D].  Returns
    ([B, T, H * D], log-sums (B*H, T) or None).  The LSE is produced
    only for the differentiated path: the pallas kernel writes it as a
    real second output (not prunable), while the jnp reference's unused
    copy is ordinary dead code."""
    b, tq, h, d = q.shape
    group = h // k.shape[2]
    if not _takes_kernel(tq, k.shape[1], block_q, block_k, d, True):
        _count_path("reference")
        out, lse = _reference_attention_lse(
            _split_heads(q), _split_heads(_expand_kv(k, group)),
            _split_heads(_expand_kv(v, group)), sm_scale, causal, window)
        return _merge_heads(out, b).reshape(b, tq, h * d), lse
    _count_path("pallas")
    _count_fwd(want_lse)
    _count_tiles(tq, k.shape[1], block_q, block_k, causal, window)
    (q, k, v), heads, per_block, group = _kernel_layout([q], [k, v], 1,
                                                        window)
    pq = (-tq) % block_q
    if pq:
        import jax.numpy as jnp

        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    out, lse = _flash_forward_pallas(q, k, v, sm_scale, causal, block_q,
                                     block_k, want_lse, heads, per_block,
                                     group, window)
    if pq:
        out, lse = out[:, :tq], (lse[:, :tq] if want_lse else None)
    return _from_kernel_layout(out, (b, tq, h, d)).reshape(
        b, tq, h * d), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, window):
    """q: [B, T, H, D]; k, v: [B, T, Hkv, D], Hkv dividing H; returns [B,
    T, H * D].  The kernels read
    q, k, v (and in the backward pass the cotangent) as [B, T, H * D], a
    free reshape, and pick a head, or the heads that share a 128-lane
    vreg, by the lane block of their index maps; they write the output
    and dq, dk, dv the same way (`_lane_plan`; a shape whose lane blocks
    are not whole heads takes a split copy, `_split_heads`).  What the
    `custom_vjp` keeps for the backward pass is q, k, v as given, the
    output as returned and the log-sums, (B*H, T) float32."""
    return _flash_merged(q, k, v, sm_scale, causal, block_q, block_k,
                         window, want_lse=False)[0]


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, window):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_merged(q, k, v, sm_scale, causal, block_q, block_k,
                             window, want_lse=True)
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _block_mask(causal, q0, k0, bq, bk, window=None):
    import jax.numpy as jnp

    if not causal:
        return None
    q_idx = q0 + jnp.arange(bq)[:, None]
    k_idx = k0 + jnp.arange(bk)[None, :]
    if window is None:
        return q_idx >= k_idx
    return (q_idx >= k_idx) & (k_idx > q_idx - window)


def _flash_bwd(sm_scale, causal, block_q, block_k, window, res, g):
    """The backward rule, on the activations' layout: the two sweeps
    read q, k, v, the cotangent and the saved output and write dq, dk,
    dv in the layout the forward kernel read (`_kernel_layout`); dk and
    dv come out at k's own head count."""
    import jax.numpy as jnp

    q, k, v, out, lse = res
    b, tq, h, d = q.shape
    group = h // k.shape[2]
    g = g.reshape(q.shape)
    out = out.reshape(q.shape)
    if not _takes_kernel(tq, k.shape[1], block_q, block_k, d, False):
        _count_path("reference")
        delta = (out.astype(jnp.float32) * g.astype(jnp.float32)) \
            .sum(axis=-1).transpose(0, 2, 1).reshape(b * h, tq)
        dq, dk, dv = _flash_bwd_sweeps(
            _split_heads(q), _split_heads(_expand_kv(k, group)),
            _split_heads(_expand_kv(v, group)), _split_heads(g), delta,
            lse, sm_scale, causal, block_q, block_k, window)
        return (_merge_heads(dq, b),) + tuple(
            _sum_group(_merge_heads(x, b), group) for x in (dk, dv))
    # kernel path (same math as the jnp sweeps, on the MXU)
    _count_path("pallas")
    _count_tiles(tq, k.shape[1], block_q, block_k, causal, window)
    (q3, g3, o3, k3, v3), heads, per_block, read_as = _kernel_layout(
        [q, g, out], [k, v], 2, window)
    dq, dk, dv = _flash_backward_pallas(
        q3, k3, v3, g3, o3, lse, sm_scale, causal, block_q, block_k,
        heads, per_block, read_as, window)
    if read_as == group:
        return tuple(_from_kernel_layout(x, a.shape)
                     for x, a in zip((dq, dk, dv), (q, k, v)))
    # k and v went in expanded: dk, dv come back a q head, XLA sums
    return (_from_kernel_layout(dq, q.shape),) + tuple(
        _sum_group(_from_kernel_layout(x, q.shape), group)
        for x in (dk, dv))


def _flash_bwd_sweeps(q, k, v, g, delta, lse_saved, sm_scale, causal,
                      block_q, block_k, window=None):
    """Blocked recompute backward (flash attention paper §3.1) in jnp,
    where the kernels do not serve: scores are rebuilt block by block
    against the LSE saved by the forward, so backward memory stays
    O(T·d + block²) — the T×T matrix is never materialized.  Two sweeps
    (dq; dk/dv), with fully-masked causal blocks skipped via loop
    bounds (those left of a `window`'s band too).  All of (B*H, T, D);
    `delta` and `lse_saved` (B*H, T)."""
    import jax.numpy as jnp
    from jax import lax

    B, Tq, D = q.shape
    Tk = k.shape[1]
    # blocks arrive pre-clamped by flash_attention_bthd (the only entry)
    bq, bk = block_q, block_k
    # pad to block multiples; padded K columns are masked by giving
    # them -inf scores via the padded-position test below.  Padded Q
    # rows get lse 0 (finite): their head-gradient rows are zero, so
    # every term they touch is zero — but exp() must stay finite.
    pq = (-Tq) % bq
    pk = (-Tk) % bk
    q32 = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, pq), (0, 0)))
    k32 = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, pk), (0, 0)))
    v32 = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pk), (0, 0)))
    g32 = jnp.pad(g.astype(jnp.float32), ((0, 0), (0, pq), (0, 0)))
    lse = jnp.pad(lse_saved, ((0, 0), (0, pq)))
    delta = jnp.pad(delta, ((0, 0), (0, pq)))           # (B, Tq+pq)
    nq = (Tq + pq) // bq
    nk = (Tk + pk) // bk
    k_valid = jnp.arange(Tk + pk) < Tk  # padded keys never attend

    def scores(qi, i, j):
        kj = lax.dynamic_slice_in_dim(k32, j * bk, bk, 1)
        s = jnp.einsum("bqd,bkd->bqk", qi, kj) * sm_scale
        mask = _block_mask(causal, i * bq, j * bk, bq, bk, window)
        kv = lax.dynamic_slice_in_dim(k_valid, j * bk, bk, 0)
        s = jnp.where(kv[None, None, :], s, _NEG_INF)
        if mask is not None:
            s = jnp.where(mask[None], s, _NEG_INF)
        return s, kj

    # pass 1: dq, one q block at a time (the forward saved the LSE, so
    # only the standard two recompute sweeps remain)
    def dq_for_block(_, i):
        qi = lax.dynamic_slice_in_dim(q32, i * bq, bq, 1)
        gi = lax.dynamic_slice_in_dim(g32, i * bq, bq, 1)
        li = lax.dynamic_slice_in_dim(lse, i * bq, bq, 1)
        di = lax.dynamic_slice_in_dim(delta, i * bq, bq, 1)

        def body(j, acc):
            s, kj = scores(qi, i, j)
            p = jnp.exp(s - li[..., None])
            vj = lax.dynamic_slice_in_dim(v32, j * bk, bk, 1)
            dp = jnp.einsum("bqd,bkd->bqk", gi, vj)
            ds = p * (dp - di[..., None]) * sm_scale
            return acc + jnp.einsum("bqk,bkd->bqd", ds, kj)

        # causal: k blocks past this q block's diagonal are all-masked
        nk_i = jnp.minimum((i * bq + bq - 1) // bk + 1, nk) \
            if causal else nk
        # inside shard_map the carry must carry the same varying-
        # manual-axes marking the body output has (see
        # parallel.ring_attention._match_vma)
        acc0 = _vma_like(jnp.zeros((B, bq, D), jnp.float32),
                         q32, k32, v32, g32)
        first = 0 if window is None else jnp.minimum(
            _first_k_block(i, bq, bk, window), nk_i)
        return _, lax.fori_loop(first, nk_i, body, acc0)

    _, dq_blocks = lax.scan(dq_for_block, None, jnp.arange(nq))
    dq = dq_blocks.transpose(1, 0, 2, 3).reshape(B, nq * bq, D)[:, :Tq]

    # pass 2: dk/dv, one k block at a time
    def dkv_for_block(_, j):
        vj = lax.dynamic_slice_in_dim(v32, j * bk, bk, 1)

        def body(i, carry):
            dk_acc, dv_acc = carry
            qi = lax.dynamic_slice_in_dim(q32, i * bq, bq, 1)
            gi = lax.dynamic_slice_in_dim(g32, i * bq, bq, 1)
            li = lax.dynamic_slice_in_dim(lse, i * bq, bq, 1)
            di = lax.dynamic_slice_in_dim(delta, i * bq, bq, 1)
            s, _ = scores(qi, i, j)
            p = jnp.exp(s - li[..., None])
            dv_acc = dv_acc + jnp.einsum("bqk,bqd->bkd", p, gi)
            dp = jnp.einsum("bqd,bkd->bqk", gi, vj)
            ds = p * (dp - di[..., None]) * sm_scale
            dk_acc = dk_acc + jnp.einsum("bqk,bqd->bkd", ds, qi)
            return dk_acc, dv_acc

        # causal: q blocks before this k block's diagonal see none of it
        i0 = jnp.minimum((j * bk) // bq, nq) if causal else 0
        z = _vma_like(jnp.zeros((B, bk, D), jnp.float32),
                      q32, k32, v32, g32)
        # ... and with a window those past the band's left edge
        i1 = jnp.clip(_last_q_block(j, bq, bk, window, nq) + 1, i0, nq)
        return _, lax.fori_loop(i0, i1, body, (z, z))

    _, (dk_blocks, dv_blocks) = lax.scan(dkv_for_block, None,
                                         jnp.arange(nk))
    dk = dk_blocks.transpose(1, 0, 2, 3).reshape(B, nk * bk, D)[:, :Tk]
    dv = dv_blocks.transpose(1, 0, 2, 3).reshape(B, nk * bk, D)[:, :Tk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, sm_scale=None, causal=False, block_q=512,
                    block_k=512):
    """Multi-head attention, flash-style.

    q/k/v: (batch, heads, seq, head_dim) or (batch*heads, seq,
    head_dim).  Returns the same layout as the input.  A caller that
    holds q, k, v as its products leave them, [batch, seq, heads,
    head_dim], takes `flash_attention_bthd`: the same kernels behind
    the same `custom_vjp`, reading that layout in place (this entry is
    that one with every (batch, head) pair as a batch row of one head,
    whose lane block is the whole head width: no copy either).

    Default 512x512 blocks, walked by class when `causal` (see
    `_causal_walk`).  Measured on a v5e (PERF.md section 5, PR 31;
    bf16, causal, the kernels alone, ms a call, parent -> walk):
    [128, 1024, 64] fwd 0.974 -> 0.796, dq 0.709 -> 0.628, dkv 0.841
    -> 0.759; [40, 4096, 256] fwd 4.377 -> 3.977, dq 4.651 -> 4.015,
    dkv 5.866 -> 4.433; read in place (PR 35, in the cells' traces) [8,
    1024, 16 x 64] 0.825 / 0.544 / 0.694, [2, 4096, 20 x 256] 4.13 /
    4.20 / 4.74.  Half the forward's time at d=64 is its two
    row reductions; the mask, the scale and the cast are free, and
    the matrix unit is not what binds.  No block sweep per (T, d) is
    on record.  Blocks
    are clamped to the sequence lengths below, so short-sequence and
    unit-test shapes are unaffected.
    """
    shape = q.shape
    if q.ndim == 4:
        q, k, v = (x.reshape((-1,) + x.shape[2:]) for x in (q, k, v))
    out = flash_attention_bthd(q[:, :, None], k[:, :, None],
                               v[:, :, None], sm_scale=sm_scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k)
    return out.reshape(shape)


def flash_attention_bthd(q, k, v, sm_scale=None, causal=False,
                         block_q=512, block_k=512, window=None):
    """`flash_attention` on the activations' own layout: q of [batch,
    seq, heads, head_dim] (a reshape of the projections' [batch, seq,
    heads * head_dim]), k and v the same or of FEWER heads (grouped kv
    heads: their count divides q's, read from the shapes; q head h
    meets kv head h // group, read in place by the lane block of the
    index maps, and dk, dv are summed over a group's q heads inside the
    dkv sweep); returns [batch, seq, heads * head_dim], what the
    out-projection reads.  A static `window` (with `causal`) lets a
    query see the keys no more than `window` - 1 behind it: a block
    wholly left of that band is skipped as a block above the diagonal
    is, the grid's inner axis is the band's length and not the
    sequence's, and a `window` that reaches every key traces the causal
    program.  The three kernels read
    and write that layout in place (`_lane_plan`: a head that is whole
    128-lane vregs wide is a lane block of its own, narrower heads that
    fill a vreg share one; any other shape takes a split copy), inside
    ONE `custom_vjp` (`_flash`), whose residuals are q, k, v as given,
    the output and the log-sums; the last two carry the names
    `FLASH_OUT` / `FLASH_LSE` (`jax.ad_checkpoint.checkpoint_name`), so
    a remat policy that lists them keeps the forward kernel from
    running twice."""
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    # fit blocks to the sequence lengths: clamp, then halve (512 ->
    # 256 -> 128) until the block divides the sequence — a seq like
    # 640 or 6784 must keep the kernel at a smaller block rather than
    # silently falling to the materializing reference path (whose
    # (T, T) score tensor is exactly what flash exists to avoid)
    def _fit(block, t):
        b = int(min(block, t))
        while b > 128 and t % b:
            b //= 2
        return b

    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise MXNetError("flash_attention_bthd: k and v need one shape "
                         "and a head count that divides q's (%r, %r, %r)"
                         % (q.shape, k.shape, v.shape))
    if window is not None:
        if not causal or int(window) < 1:
            raise MXNetError("flash_attention_bthd: a window is a causal "
                             "mask's, and at least 1 (got %r)" % (window,))
        window = None if window >= k.shape[1] else int(window)
    return _flash(q, k, v, float(sm_scale), bool(causal),
                  _fit(block_q, q.shape[1]), _fit(block_k, k.shape[1]),
                  window)


@register("_contrib_flash_attention")
def _contrib_flash_attention(q, k, v, sm_scale=None, causal=False,
                             block_q=512, block_k=512):
    """Flash attention op over (batch, heads, seq, head_dim) inputs
    (kernel above; reference has no analog — attention in MXNet 1.5 is
    composed from batch_dot/softmax, which materializes the score
    matrix)."""
    if q.ndim != 4:
        raise MXNetError("_contrib_flash_attention expects "
                         "(batch, heads, seq, head_dim)")
    return flash_attention(q, k, v, sm_scale=sm_scale, causal=causal,
                           block_q=block_q, block_k=block_k)
