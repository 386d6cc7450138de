"""Compiles for a DESCRIBED chip (TPU v5e, not attached), at real widths,
at no chip time: the one file for such tests (ROADMAP C11; the
`on-chip-measurement` guide, section 2).

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU's library, and every xdist
worker imports every test file.  Nothing here runs a program, so nothing
here is a device metric; what a compile can show is what the compiler
built: which loops, which copies, whether the Mosaic kernels are in.
"""
import os
import re

import pytest

# gpt2-medium as `gpt2m_fused_k8` runs it (benchmark/onchip/configs/
# gpt2_medium.json, traffic/fused_k8_tokens.json)
WIDTHS = dict(vocab=50257, d_model=1024, n_heads=16, n_layers=24,
              d_ff=4096, max_len=1024, dtype="bfloat16")
BATCH, SEQ, K_STEPS = 8, 1024, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # The TPU compiler is left every core it sees (4-5 of 8 for ten
    # seconds a program; the file then takes ~150 s of one worker).  It
    # was held to ONE core while a host-timing ratchet's tests ran
    # beside it on other workers; they are gone (PR 32), and with the
    # compiler free the whole of tier-1 passed on six workers twice.
    # The guards that still hold a host path to microseconds or rank
    # ops by measured time (`check_health`, `check_inspect`,
    # `check_hbm`, `check_xprof`) are the ones to look at first if a
    # timing test fails beside this file: see `ling_compiled` below.
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another holds it
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without the chip: keep these
    # out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip_mesh(topo):
    import numpy as np
    from jax.sharding import Mesh

    from mxtpu.parallel.mesh import (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP,
                                     AXIS_EP)

    return Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1, 1, 1),
                (AXIS_DP, AXIS_PP, AXIS_TP, AXIS_SP, AXIS_EP))


def _lm_program(mesh, remat, k_steps):
    """The LM's Adam train program compiled for `mesh`: K steps fused
    (`k_steps`), or one step (None)."""
    import jax
    import jax.numpy as jnp

    from mxtpu.parallel import transformer as tf

    cfg = tf.TransformerConfig(remat=remat, **WIDTHS)
    if k_steps is None:
        step, sh = tf.make_train_step(cfg, mesh, lr=3e-4, optimizer="adam")
        data_shape = (BATCH, SEQ)
    else:
        step, sh = tf.make_fused_train_steps(cfg, mesh, k_steps, lr=3e-4,
                                             optimizer="adam")
        data_shape = (k_steps, BATCH, SEQ)
    shapes = tf.param_shapes(cfg, 1)
    params = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                      sharding=sh["params"][n])
              for n, s in shapes.items()}
    moments = {n: jax.ShapeDtypeStruct(s, jnp.float32,
                                       sharding=sh["opt_state"]["m"][n])
               for n, s in shapes.items()}
    opt = {"m": moments, "v": dict(moments),
           "t": jax.ShapeDtypeStruct((), jnp.float32,
                                     sharding=sh["opt_state"]["t"])}
    data = jax.ShapeDtypeStruct(data_shape, jnp.int32, sharding=sh["data"])
    return step.lower(params, opt, data, data).compile()


_FLASH_CALL = re.compile(
    r"%(mx_flash_\w+?)[.\d]* = .*?\[(\d+),(\d+),(\d+)\].*custom-call\(")


def _flash_kernels(text):
    """The flash kernels in a compiled program's text, by name, with
    the [b, t, h * d] each returns first."""
    kernels = {}
    for name, b, t, hd in _FLASH_CALL.findall(text):
        kernels.setdefault(name, set()).add((int(b), int(t), int(hd)))
    return kernels


def _mosaic_call_sites(text):
    """How many Mosaic kernel calls the compiled text holds (a call in a
    loop's body is one site)."""
    return text.count('custom_call_target="tpu_custom_call"')


def _described_bytes(compiled):
    """Arguments + temporaries of a program compiled for the described
    chip: what the compiler planned, not a device reading."""
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_WHILE = re.compile(r"\bwhile\(.*\bbody=%?([\w.\-]+)")
_COPY = re.compile(r"= \w+\[([\d,]*)\][^ ]* (?:copy|transpose)\(")


def _whiles_and_stack_copies(text, n_layers, split=()):
    """What the compiled text says of its loops: a list of (body's name,
    whether the `while` sits in the ENTRY computation), one per `while`,
    and by computation name the `copy` / `transpose` instructions in it
    whose result has `n_layers` as its leading dimension (a leading 1
    set aside); and the same for those whose result is one of the
    shapes `split`."""
    whiles, copies, splits, name, entry = [], {}, {}, None, False
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            entry, name = bool(m.group(1)), m.group(2)
            continue
        m = _WHILE.search(line)
        if m:
            whiles.append((m.group(1), entry))
        m = _COPY.search(line)
        if m:
            dims = [int(d) for d in m.group(1).split(",") if d]
            while dims[:1] == [1]:
                dims = dims[1:]
            for found, hit in ((copies, dims[:1] == [n_layers]),
                               (splits, tuple(dims) in split)):
                if hit:
                    found.setdefault(name, []).append(line.strip()[:120])
    return whiles, copies, splits


def _row_bound_arrays(text, rows, widths):
    """The arrays of the compiled text with `rows` leading and one of
    `widths` behind it: what the expert dispatch gathered, multiplied
    and scattered a layer while it worked on the static bound of n_tok
    * top_k rows.  Since PR 37 it walks the step's pairs in blocks
    (`transformer._walk_pairs`) and no such array is left."""
    return sorted(set(re.findall(
        r"\w+\[%d,(?:%s)\]" % (rows, "|".join(str(w) for w in widths)),
        text)))


_SORT = re.compile(r"^\s*%?[\w.\-]+ = (.*?) sort\(")


def _row_sorts(text):
    """The `sort` instructions of the compiled text that sort rows: by
    the shape of each result of more than one dimension (a sort of
    several operands gives each its own), how many results have it.
    Where the router's selection ran `lax.top_k`, the TPU sorted every
    token's whole row of scores: [tokens, experts], [tokens, groups,
    experts a group], [tokens, groups].  Since `_top_k_by_rounds` none
    is left; the dispatch's argsort of the pair keys and the scatters'
    index sorts are 1-D."""
    found = {}
    for line in text.splitlines():
        m = _SORT.match(line)
        if m:
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1)):
                shape = tuple(int(d) for d in dims.split(",") if d)
                if len(shape) > 1:
                    found[shape] = found.get(shape, 0) + 1
    return found


def _split_shapes(b, t, h, d):
    """The shapes a head split or merge copy of [b, t, h * d] results
    in: the kernels read that layout in place since PR 35, and before
    it every attention call made eleven such copies (seventeen of them
    in `gpt2m_fused_k8`'s two layer loops)."""
    return {(b * h, t, d), (b, h, t, d), (b, t, h, d), (b * h, t, 1)}


@pytest.mark.parametrize("k_steps,remat", [
    (K_STEPS, "dots"), (K_STEPS, "full"), (None, "dots"), (None, "full")],
    ids=["fused_k8-dots", "fused_k8-full", "per_step-dots",
         "per_step-full"])
def test_lm_step_keeps_layer_stacks_in_place(one_chip_mesh, monkeypatch,
                                             k_steps, remat):
    """On one chip with one microbatch the layer weights reach the
    backward pass as the arrays the step was given: no loop over the
    layers copies a whole [n_layers, ...] stack in its body, and the
    only loops are the forward and the backward layer scan (and the K
    loop around them).
    A one-trip pipeline loop in the loss broke that (PERF.md, PR 27:
    a fourth, hoisted 24-trip loop, six whole-stack copies a trip)."""
    from mxtpu.ops import pallas_attention as pa

    # the program asks jax.devices() whether it is on a TPU, and here
    # that is the CPU: steer it in the test, not by an option of the
    # program, so that the Pallas kernels go through Mosaic
    from mxtpu import profiler

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    split = profiler.get_stat("flash_calls_split")
    profiler.set_stat("flash_heads_per_block", 0)
    compiled = _lm_program(one_chip_mesh, remat, k_steps)
    # every launch traced reads [8, 1024, 16 x 64] in place, two heads
    # to a 128-lane block (the ling cell's thread traces beside this
    # one: it adds no split either, and one head a block)
    assert profiler.get_stat("flash_calls_split") == split
    assert profiler.get_stat("flash_heads_per_block") == 2
    text = compiled.as_text()
    # remat="dots" keeps the forward kernel's merged output and log-sums
    # (PR 33), so the backward pass holds dq and dkv and NO second call
    # of the forward: 3 sites where the parent had 4, as "full" still has
    assert _mosaic_call_sites(text) == (3 if remat == "dots" else 4)
    if (k_steps, remat) == (K_STEPS, "dots"):
        # ... and pays for them with `o @ wo`, which is rebuilt: the
        # parent's described 13.2965 GiB + 0.0594.  A later change that
        # re-grows the saved set (the naive way costs +0.43 to +0.76
        # GiB) fails here before `peak_hbm_gib` refuses it on the chip
        assert _described_bytes(compiled) <= (13.2965 + 0.08) * 2 ** 30, \
            _described_bytes(compiled)
    # the three flash kernels, by name, on the activations' own [8,
    # 1024, 16 x 64] (two 64-wide heads to a 128-lane block): Mosaic
    # lowered the causal walk's sub-tiled diagonal, the lane-block index
    # maps and the in-kernel transposes of the log-sums for the
    # described chip
    want = {(BATCH, SEQ, WIDTHS["d_model"])}
    kernels = _flash_kernels(text)
    assert kernels == {"mx_flash_fwd": want, "mx_flash_dq": want,
                       "mx_flash_dkv": want}, kernels
    # ... and no head split / merge copy is left in a layer loop
    whiles, copies, splits = _whiles_and_stack_copies(
        text, WIDTHS["n_layers"],
        _split_shapes(BATCH, SEQ, WIDTHS["n_heads"],
                      WIDTHS["d_model"] // WIDTHS["n_heads"]))
    # the loops that run once per layer: every `while` of the per-step
    # program; in the fused one, those inside the K loop, which is the
    # `while` of the ENTRY computation (a copy in ITS body runs once per
    # step: remat="full" keeps one weight stack in a second layout so)
    layer_loops = [body for body, in_entry in whiles
                   if k_steps is None or not in_entry]
    found = [c for body in layer_loops for c in copies.get(body, [])]
    assert not found, ("whole-stack copies once per layer:\n  "
                       + "\n  ".join(found))
    found = [c for body in layer_loops for c in splits.get(body, [])]
    assert not found, ("head split / merge copies once per layer:\n  "
                       + "\n  ".join(found))
    assert len(layer_loops) == 2, whiles
    assert len(whiles) == (2 if k_steps is None else 3), whiles


# ---------------------------------------------------------------------------
# `glm47f_ep8_fused_k4`: one chip's share of GLM-4.7-Flash at published
# widths (benchmark/onchip/configs/glm_4_7_flash_ep8.json, traffic/
# fused_k4_tokens_2x4k.json), through the config the cell's driver builds

_CHIP_BYTES = 16.9e9        # a v5e chip's `bytes_limit` (PERF.md, PR 21)


def _glm_cell():
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    onchip = os.path.join(root, "benchmark", "onchip")
    if onchip not in sys.path:
        sys.path.insert(0, onchip)
    from drivers.lm_glm_fused import transformer_config

    with open(os.path.join(onchip, "configs",
                           "glm_4_7_flash_ep8.json")) as f:
        config = json.load(f)
    with open(os.path.join(onchip, "traffic",
                           "fused_k4_tokens_2x4k.json")) as f:
        traffic = json.load(f)
    return transformer_config(config), config, traffic


def test_glm_cell_program_fits_one_chip_and_keeps_its_kernels(
        one_chip_mesh, monkeypatch):
    """The cell's K=4 program at 2 x 4096 tokens a step, compiled for a
    described v5e: its arguments and temporaries fit the chip; the three
    flash kernels are in it on [2, 4096, 20 x 256] (a 256-wide head is
    a lane block of its own; q.k 192 + 64 = v 256), and no head split
    or merge copy anywhere; the grouped expert products went to XLA's own
    Mosaic kernel; no loop over the layers copies a whole weight stack
    in its body; and the loops are the K loop, the forward and backward
    scan of the four expert layers, and a forward and a backward walk
    of the dispatch's blocks in each of them and in the MTP block; no
    array of the dispatch's static row bound is left."""
    import jax
    import jax.numpy as jnp

    from mxtpu.ops import pallas_attention as pa
    from mxtpu.parallel import transformer as tf

    from mxtpu import profiler

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    split = profiler.get_stat("flash_calls_split")
    profiler.set_stat("flash_heads_per_block", 0)
    cfg, config, traffic = _glm_cell()
    k, b = traffic["steps_per_program"], traffic["batch"]
    step, sh = tf.make_fused_train_steps(cfg, one_chip_mesh, k, lr=3e-4,
                                         optimizer="adam")
    shapes = tf.param_shapes(cfg, 1)
    assert sum(int(jnp.prod(jnp.array(s))) for s in shapes.values()) \
        == 706518848            # the issue's count of what this chip holds
    params = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                      sharding=sh["params"][n])
              for n, s in shapes.items()}
    moments = {n: jax.ShapeDtypeStruct(s, jnp.float32,
                                       sharding=sh["opt_state"]["m"][n])
               for n, s in shapes.items()}
    opt = {"m": moments, "v": dict(moments),
           "t": jax.ShapeDtypeStruct((), jnp.float32,
                                     sharding=sh["opt_state"]["t"])}
    data = jax.ShapeDtypeStruct((k, b, config["input"]["length"]),
                                jnp.int32, sharding=sh["data"])
    compiled = step.lower(params, opt, data, data).compile()
    # in place, a 256-wide head a lane block (the ling cell's too)
    assert profiler.get_stat("flash_calls_split") == split
    assert profiler.get_stat("flash_heads_per_block") == 1
    assert pa._lane_plan(32, 256) == 1
    need = _described_bytes(compiled)
    assert need < _CHIP_BYTES, "arguments + temporaries %.3e bytes" % need
    # a full chip, as a training job's is (PERF.md section 4)
    assert need > 0.75 * _CHIP_BYTES, need
    # recorded at PR 37: 13 817 757 696 described bytes (7 065 million
    # of arguments), where PR 33 had 16 027 537 920: the expert
    # dispatch's [32 768, 2048] and [32 768, 1536] buffers went (a block
    # of its walk is 8 192 rows)
    assert need <= 13817757696 + 0.05 * 2 ** 30, need

    text = compiled.as_text()
    heads, width = config["num_attention_heads"], config["v_head_dim"]
    length = config["input"]["length"]
    want = {(b, length, heads * width)}
    kernels = _flash_kernels(text)
    assert kernels == {"mx_flash_fwd": want, "mx_flash_dq": want,
                       "mx_flash_dkv": want}, kernels
    assert "ragged-dot" in text, "the grouped products left Mosaic"
    # one forward call site fewer than before PR 33 (36): the expert
    # layers' backward scan no longer runs the forward kernel again;
    # PR 37 (35 -> 39): the grouped products sit in the walks' loops,
    # and a backward walk rebuilds its block's three
    assert _mosaic_call_sites(text) == 39
    rows = b * length * config["num_experts_per_tok"]
    assert tf._dispatch_block(cfg, b * length) == (rows, rows // 4)
    found = _row_bound_arrays(text, rows, (config["hidden_size"],
                                           config["moe_intermediate_size"]))
    assert not found, found
    found = _row_sorts(text)
    assert not found, found

    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    whiles, copies, splits = _whiles_and_stack_copies(
        text, layers, _split_shapes(b, length, heads, width))
    layer_loops = [body for body, in_entry in whiles if not in_entry]
    found = [c for body in layer_loops for c in copies.get(body, [])]
    assert not found, ("whole-stack copies once per layer:\n  "
                       + "\n  ".join(found))
    # the K loop's body holds the one-layer segments (the dense layer,
    # the MTP block): head split / merge copies are looked for there too
    found = [c for body, _ in whiles for c in splits.get(body, [])]
    assert not found, ("head split / merge copies once per layer:\n  "
                       + "\n  ".join(found))
    # recorded: 3 whiles (PR 30); 7 since PR 37: the K loop, the two
    # layer scans, and the dispatch's walk forward and backward in the
    # scans' bodies and, for the MTP block, in the K loop's
    assert len(layer_loops) == 6 and len(whiles) == 7, whiles


# ---------------------------------------------------------------------------
# `ling3fvl_ep64_fused_k4`: one chip's share of Ling-3.0-flash's language
# model at published widths (benchmark/onchip/configs/
# ling_3_0_flash_vl_ep64.json, traffic/fused_k4_tokens_2x2k.json), through
# the config the cell's driver builds


# arguments + temporaries: 18 143 063 040 at PR 34, 18 056 681 472 since
# PR 37 (the dispatch's [32 768, 2560] buffers went; the KDA layers'
# temporaries set this program's peak), 17 731 018 240 since the chunk's
# triangular systems are solved by a blocked inverse that the backward
# pass applies transposed (`transformer._unit_lower_solve`)
LING_DESCRIBED = 17731018240
LING_MOSAIC_SITES = 33      # 3 flash kernels + XLA's grouped products
# the K loop, the five KDA + expert layers' two scans, the chunk scans
# of the KDA core (forward, rebuilt, backward) and, since PR 37 (9
# before), the dispatch's walk forward and backward in the scanned
# segment and in the one-layer MLA segment
LING_WHILES = 13


def _ling_cell():
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    onchip = os.path.join(root, "benchmark", "onchip")
    if onchip not in sys.path:
        sys.path.insert(0, onchip)
    from drivers.lm_ling_fused import transformer_config

    with open(os.path.join(onchip, "configs",
                           "ling_3_0_flash_vl_ep64.json")) as f:
        config = json.load(f)
    with open(os.path.join(onchip, "traffic",
                           "fused_k4_tokens_2x2k.json")) as f:
        traffic = json.load(f)
    return transformer_config(config), config, traffic


def _cell_program(mesh, cell):
    """(compiled program, its config file, its traffic file, the
    parameters it holds) of a cell's fused program at the traffic's K
    steps and batch, for `mesh`; `cell` is (TransformerConfig, config
    file, traffic file)."""
    import jax
    import jax.numpy as jnp

    from mxtpu.parallel import transformer as tf

    cfg, config, traffic = cell
    k, b = traffic["steps_per_program"], traffic["batch"]
    step, sh = tf.make_fused_train_steps(cfg, mesh, k, lr=3e-4,
                                         optimizer="adam")
    shapes = tf.param_shapes(cfg, 1)
    params = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                      sharding=sh["params"][n])
              for n, s in shapes.items()}
    moments = {n: jax.ShapeDtypeStruct(s, jnp.float32,
                                       sharding=sh["opt_state"]["m"][n])
               for n, s in shapes.items()}
    opt = {"m": moments, "v": dict(moments),
           "t": jax.ShapeDtypeStruct((), jnp.float32,
                                     sharding=sh["opt_state"]["t"])}
    data = jax.ShapeDtypeStruct((k, b, config["input"]["length"]),
                                jnp.int32, sharding=sh["data"])
    return step.lower(params, opt, data, data).compile(), config, traffic, \
        sum(int(jnp.prod(jnp.array(s))) for s in shapes.values())


@pytest.fixture(scope="module", autouse=True)
def ling_compiled(one_chip_mesh):
    """The ling cell's program, traced and compiled in a thread of its
    own from the moment the file's first test starts, BESIDE the other
    cases and not after them.  As a sixth compile at the file's end (65
    s) it ran beside `test_tools.py::test_check_xprof_guard` on another
    worker, which then read false in four whole runs of five (its
    replay and xplane paths ranked different ops first; the schedule of
    six workers is the same every run), also with the compiler held to
    half the cores or to nice 19; the parent's tree, whose last compile
    ends before that guard starts, passed.  The steer of `_on_tpu` that
    each test makes for itself holds for the whole file here, since
    this thread traces while they come and go."""
    from concurrent.futures import ThreadPoolExecutor

    from mxtpu.ops import pallas_attention as pa

    steer, pa._on_tpu = pa._on_tpu, lambda: True
    pool = ThreadPoolExecutor(max_workers=1)
    future = pool.submit(_cell_program, one_chip_mesh, _ling_cell())
    yield future
    pool.shutdown(wait=True)
    pa._on_tpu = steer


def test_ling_cell_program_compiles_for_one_chip_and_keeps_its_kernels(
        ling_compiled):
    """The cell's K=4 program at 2 x 2048 tokens a step, compiled for a
    described v5e: the three flash kernels are in it on [2, 2048, 32 x
    256] (q.k 192 and v 128 padded to the kernels' one width, a lane
    block a head); the grouped expert products went to XLA's own Mosaic
    kernel; the described bytes are bounded.  The chip reserves about
    three quarters of the described temporaries (PERF.md section 7), so
    the bound here is above the chip's 16.9e9."""
    compiled, config, traffic, n_params = ling_compiled.result()
    assert n_params == 822036672    # the issue's count of what this chip holds
    need = _described_bytes(compiled)
    # recorded at PR 34: 18 143 million described bytes (8 221 of
    # arguments); a change that re-grows what the KDA layers keep fails
    # here before `peak_hbm_gib` refuses it on the chip
    assert 0.75 * _CHIP_BYTES < need <= LING_DESCRIBED + 0.05 * 2 ** 30, need

    text = compiled.as_text()
    want = {(traffic["batch"], config["input"]["length"],
             config["num_attention_heads"] * 256)}
    kernels = _flash_kernels(text)
    assert kernels == {"mx_flash_fwd": want, "mx_flash_dq": want,
                       "mx_flash_dkv": want}, kernels
    assert "ragged-dot" in text, "the grouped products left Mosaic"
    assert _mosaic_call_sites(text) == LING_MOSAIC_SITES
    assert len(_whiles_and_stack_copies(text, 5)[0]) == LING_WHILES
    # the KDA chunk's triangular systems are solved by products: no
    # call of the TPU's sequential triangular inversion is left
    assert "InvertDiagBlocksLowerTriangular" not in text
    tokens = traffic["batch"] * config["input"]["length"]
    rows = tokens * config["num_experts_per_tok"]
    from mxtpu.parallel import transformer as tf

    assert tf._dispatch_block(_ling_cell()[0], tokens) == (rows, rows // 32)
    found = _row_bound_arrays(text, rows, (config["hidden_size"],
                                           config["moe_intermediate_size"]))
    assert not found, found
    # the router selects by rounds: no sort of [4096, 512], [4096, 8,
    # 64] or [4096, 8], four of each before (`lax.top_k` on the TPU)
    found = _row_sorts(text)
    assert not found, found


# ---------------------------------------------------------------------------
# `trinitym_ep16_fused_k4`: one chip's share of Trinity-Mini at published
# widths (benchmark/onchip/configs/trinity_mini_ep16.json, traffic/
# fused_k4_tokens_2x8k.json), through the config the cell's driver builds

# arguments 5.042e9 + temporaries: 18.322e9 at PR 36, 14 522 442 752 since
# PR 37: the dispatch's [131 072, 2048] and [131 072, 1024] buffers went (a
# block is 16 384 rows), and the walk's result is kept for the post-norm
TRI_DESCRIBED = 14522442752
# 3 segments x 3 flash kernels + grouped products (35 at PR 36; since PR
# 37 they sit in the walks' loops, a backward walk rebuilding its block's)
TRI_MOSAIC_SITES = 39
_FLASH_OPERANDS = re.compile(r"%(mx_flash_\w+?)[.\d]* = .*"
                             r"operand_layout_constraints=\{(.*?)frontend")


def _tri_cell():
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    onchip = os.path.join(root, "benchmark", "onchip")
    if onchip not in sys.path:
        sys.path.insert(0, onchip)
    from drivers.lm_trinity_fused import transformer_config

    with open(os.path.join(onchip, "configs",
                           "trinity_mini_ep16.json")) as f:
        config = json.load(f)
    with open(os.path.join(onchip, "traffic",
                           "fused_k4_tokens_2x8k.json")) as f:
        traffic = json.load(f)
    return transformer_config(config), config, traffic


def test_trinity_cell_program_reads_its_kv_heads_in_place(one_chip_mesh,
                                                          monkeypatch):
    """The cell's K=4 program at 2 x 8192 tokens a step, compiled for a
    described v5e: the three flash kernels are in it by name, the
    forward and dq on q's `[2, 8192, 32 x 128]` and dk/dv on k's own
    `[2, 8192, 4 x 128]`; EVERY flash call is handed k and v as `[2,
    8192, 512]` (no array of k or v at q's 32 heads exists to hand it:
    `flash_kv_expanded` stays 0), under a window of 2048 on the window
    layers' segments; the grouped expert products went to XLA's own
    Mosaic kernel; the described bytes are bounded (the chip reserves
    about three quarters of the described temporaries, PERF.md section
    7, so the bound is above the chip's 16.9e9)."""
    import jax
    import jax.numpy as jnp

    from mxtpu import profiler
    from mxtpu.ops import pallas_attention as pa
    from mxtpu.parallel import transformer as tf

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    expanded = profiler.get_stat("flash_kv_expanded")
    split = profiler.get_stat("flash_calls_split")
    cfg, config, traffic = _tri_cell()
    k, b = traffic["steps_per_program"], traffic["batch"]
    step, sh = tf.make_fused_train_steps(cfg, one_chip_mesh, k, lr=3e-4,
                                         optimizer="adam")
    shapes = tf.param_shapes(cfg, 1)
    assert sum(int(jnp.prod(jnp.array(s))) for s in shapes.values()) \
        == 504147712            # the issue's count of what this chip holds
    params = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                      sharding=sh["params"][n])
              for n, s in shapes.items()}
    moments = {n: jax.ShapeDtypeStruct(s, jnp.float32,
                                       sharding=sh["opt_state"]["m"][n])
               for n, s in shapes.items()}
    opt = {"m": moments, "v": dict(moments),
           "t": jax.ShapeDtypeStruct((), jnp.float32,
                                     sharding=sh["opt_state"]["t"])}
    length = config["input"]["length"]
    data = jax.ShapeDtypeStruct((k, b, length), jnp.int32,
                                sharding=sh["data"])
    compiled = step.lower(params, opt, data, data).compile()
    assert profiler.get_stat("flash_kv_expanded") == expanded
    assert profiler.get_stat("flash_calls_split") == split
    assert profiler.get_stat("flash_kv_group") == 8
    assert profiler.get_stat("flash_window") == config["sliding_window"]
    need = _described_bytes(compiled)
    assert 0.75 * _CHIP_BYTES < need <= TRI_DESCRIBED + 0.05 * 2 ** 30, need

    text = compiled.as_text()
    rows = b * length * config["num_experts_per_tok"]
    assert tf._dispatch_block(cfg, b * length) == (rows, rows // 8)
    found = _row_bound_arrays(text, rows, (config["hidden_size"],
                                           config["moe_intermediate_size"]))
    assert not found, found
    found = _row_sorts(text)
    assert not found, found
    heads, kv, width = (config["num_attention_heads"],
                        config["num_key_value_heads"], config["head_dim"])
    at_q, at_kv = (b, length, heads * width), (b, length, kv * width)
    kernels = _flash_kernels(text)
    assert kernels == {"mx_flash_fwd": {at_q}, "mx_flash_dq": {at_q},
                       "mx_flash_dkv": {at_kv}}, kernels
    calls = _FLASH_OPERANDS.findall(text)
    assert len(calls) == 9      # 3 segments (dense., window, full.) x 3
    for name, operands in calls:
        operand_shapes = [tuple(int(x) for x in dims.split(","))
                          for dims in re.findall(r"\[([\d,]+)\]", operands)]
        assert operand_shapes[:3] == [at_q, at_kv, at_kv], (name,
                                                            operand_shapes)
    assert "ragged-dot" in text, "the grouped products left Mosaic"
    assert _mosaic_call_sites(text) == TRI_MOSAIC_SITES

    # the loops: the K loop, the forward and backward scan of the three
    # window expert layers, and since PR 37 the dispatch's walk forward
    # and backward in the scans' bodies and, for the one full layer, in
    # the K loop's (3 whiles at PR 36, 7 now).  What
    # is copied at q's size in them is q's own: XLA lays the 4-D `[2,
    # 8192, 32, 128]` the rotary step works on out head-major, so q is
    # re-laid out after it (forward, and rebuilt in the backward pass),
    # and a saved stack element and dq once each: 4 copies recorded, none
    # of them k's or v's, which the kernels' operands above show
    whiles, copies, splits = _whiles_and_stack_copies(
        text, 3, _split_shapes(b, length, heads, width) | {at_q})
    layer_loops = [body for body, in_entry in whiles if not in_entry]
    assert len(layer_loops) == 6 and len(whiles) == 7, whiles
    found = [c for body in layer_loops for c in copies.get(body, [])]
    assert not found, ("whole-stack copies once per layer:\n  "
                       + "\n  ".join(found))
    found = [c for body, _ in whiles for c in splits.get(body, [])]
    assert len(found) <= 4, "\n  ".join(found)


# ---------------------------------------------------------------------------
# `solar2_ep40_fused_k4`: one chip's share of Solar Open 2 at published
# widths (benchmark/onchip/configs/solar_open2_ep40.json, traffic/
# fused_k4_tokens_2x2k_solar.json), through the config the cell's driver
# builds

# arguments 10 355 540 992 + temporaries 7 552 296 960
SOLAR_DESCRIBED = 17907837952
SOLAR_MOSAIC_SITES = 33     # 3 flash kernels + XLA's grouped products
# the K loop, the KDA segment's two scans, the chunk scans of the KDA core
# (forward, rebuilt, backward), the dispatch's walk forward and backward
# in the scanned segment and in the one-layer GQA segment
SOLAR_WHILES = 10


def _solar_cell():
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    onchip = os.path.join(root, "benchmark", "onchip")
    if onchip not in sys.path:
        sys.path.insert(0, onchip)
    from drivers.lm_solar_fused import transformer_config

    with open(os.path.join(onchip, "configs", "solar_open2_ep40.json")) as f:
        config = json.load(f)
    with open(os.path.join(onchip, "traffic",
                           "fused_k4_tokens_2x2k_solar.json")) as f:
        traffic = json.load(f)
    return transformer_config(config), config, traffic


@pytest.fixture(scope="module", autouse=True)
def solar_compiled(one_chip_mesh, ling_compiled):
    """The solar cell's program, traced and compiled in a thread of its
    own from the file's first test on, as the ling cell's is (and under
    the steer of `_on_tpu` that `ling_compiled` holds for the file)."""
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=1)
    future = pool.submit(_cell_program, one_chip_mesh, _solar_cell())
    yield future
    pool.shutdown(wait=True)


def test_solar_cell_program_compiles_for_one_chip_and_keeps_its_kernels(
        solar_compiled):
    """The cell's K=4 program at 2 x 2048 tokens a step, compiled for a
    described v5e: the three flash kernels are in it by name, the
    forward and dq on q's `[2, 2048, 32 x 128]` and dk/dv on k's own
    `[2, 2048, 4 x 128]` (the NoPE GQA layer's 8 q heads a kv head read
    in place); the grouped expert products went to XLA's own Mosaic
    kernel; the KDA core's triangular systems are solved by products;
    the described bytes are bounded (the chip reserves about three
    quarters of the described temporaries, PERF.md section 7, so the
    bound is above the chip's 16.9e9)."""
    from mxtpu.parallel import transformer as tf

    compiled, config, traffic, n_params = solar_compiled.result()
    assert n_params == 1035536096   # what the configuration holds here
    need = _described_bytes(compiled)
    assert 0.75 * _CHIP_BYTES < need <= SOLAR_DESCRIBED + 0.05 * 2 ** 30, need

    text = compiled.as_text()
    b, length = traffic["batch"], config["input"]["length"]
    width = config["head_dim"]
    at_q = (b, length, config["num_attention_heads"] * width)
    at_kv = (b, length, config["num_key_value_heads"] * width)
    kernels = _flash_kernels(text)
    assert kernels == {"mx_flash_fwd": {at_q}, "mx_flash_dq": {at_q},
                       "mx_flash_dkv": {at_kv}}, kernels
    assert "ragged-dot" in text, "the grouped products left Mosaic"
    assert _mosaic_call_sites(text) == SOLAR_MOSAIC_SITES
    assert len(_whiles_and_stack_copies(text, 3)[0]) == SOLAR_WHILES
    assert "InvertDiagBlocksLowerTriangular" not in text
    tokens = b * length
    rows = tokens * config["num_experts_per_tok"]
    assert tf._dispatch_block(_solar_cell()[0], tokens) == (rows, rows // 16)
    found = _row_bound_arrays(text, rows, (config["hidden_size"],
                                           config["moe_intermediate_size"]))
    assert not found, found
    found = _row_sorts(text)
    assert not found, found


# ---------------------------------------------------------------------------
# `ouro26b_l8_fused_k4`: one pipeline stage of Ouro-2.6B at published
# widths, its 8 held layers run 4 times a step (benchmark/onchip/configs/
# ouro_2_6b_l8.json, traffic/fused_k4_tokens_2x4k_loop.json), through the
# config the cell's driver builds

# arguments 6 124 644 352 + temporaries 10 641 344 000: 66 MB more than
# when every pass rebuilt its flash forward, for the kernel's output and
# log-sums that the 24 layer applications of passes 2..4 keep; the heads
# rebuilt in the backward pass (`jax.checkpoint`) read 11 314 172 928
OURO_DESCRIBED = 16765988352


def _ouro_cell():
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    onchip = os.path.join(root, "benchmark", "onchip")
    if onchip not in sys.path:
        sys.path.insert(0, onchip)
    from drivers.lm_ouro_fused import transformer_config

    with open(os.path.join(onchip, "configs", "ouro_2_6b_l8.json")) as f:
        config = json.load(f)
    with open(os.path.join(onchip, "traffic",
                           "fused_k4_tokens_2x4k_loop.json")) as f:
        traffic = json.load(f)
    return transformer_config(config), config, traffic


@pytest.fixture(scope="module", autouse=True)
def ouro_compiled(one_chip_mesh, solar_compiled):
    """The ouro cell's program, traced and compiled in a thread of its
    own from the file's first test on, as the ling and solar cells' are
    (and under the steer of `_on_tpu` that `ling_compiled` holds for the
    file)."""
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=1)
    future = pool.submit(_cell_program, one_chip_mesh, _ouro_cell())
    yield future
    pool.shutdown(wait=True)


def _sites_by_computation(text):
    """{flash kernel: [the computation each call site is in]}."""
    sites, name = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(2)
            continue
        m = _FLASH_CALL.search(line)
        if m:
            sites.setdefault(m.group(1), []).append(name)
    return sites


def test_ouro_cell_program_runs_the_stack_four_times_without_restacking(
        ouro_compiled):
    """The cell's K=4 program at 2 x 4096 tokens a step, compiled for a
    described v5e: the three flash kernels by name on the activations'
    own `[2, 4096, 16 x 128]`, read in place; the loops are the K loop
    and, for each of the 4 passes, a forward and a backward scan of the
    8 held layers, with no loop that re-stacks the layer weights (the
    hoisted loop a one-trip pipeline loop once brought, which a scan
    over the passes brings back once a step) and no whole-stack copy in
    a layer loop's body; each backward layer loop holds one dq and one
    dk/dv site, and one of them alone (the first pass's) a rebuilt
    forward (`remat="full"`; the later passes keep the kernel's output
    and log-sums): 32 launches of dq and dk/dv a step and 40 of the
    forward; the described bytes are bounded, under what one more
    pass's float32 logits alive at once (1.61e9) would add (the chip
    reserves the compile's preallocated temporary buffer, 8.08e9 of the
    described 10.64e9 temporaries here, PERF.md section 5, so the bound
    is above the chip's 16.9e9)."""
    compiled, config, traffic, n_params = ouro_compiled.result()
    assert n_params == 612438017    # the issue's count of what this chip holds
    need = _described_bytes(compiled)
    assert 0.75 * _CHIP_BYTES < need <= OURO_DESCRIBED + 0.05 * 2 ** 30, need

    text = compiled.as_text()
    b, length = traffic["batch"], config["input"]["length"]
    heads, width = config["num_attention_heads"], config["head_dim"]
    want = {(b, length, heads * width)}
    assert _flash_kernels(text) == {"mx_flash_fwd": want, "mx_flash_dq": want,
                                    "mx_flash_dkv": want}
    passes, layers = config["total_ut_steps"], config["num_hidden_layers"]
    whiles, copies, splits = _whiles_and_stack_copies(
        text, layers, _split_shapes(b, length, heads, width))
    layer_loops = [body for body, in_entry in whiles if not in_entry]
    assert len(whiles) == 1 + 2 * passes and \
        len(layer_loops) == 2 * passes, whiles
    found = [c for body in layer_loops for c in copies.get(body, [])]
    assert not found, ("whole-stack copies once per layer:\n  "
                       + "\n  ".join(found))
    found = [c for body, _ in whiles for c in splits.get(body, [])]
    assert not found, ("head split / merge copies:\n  " + "\n  ".join(found))
    sites = _sites_by_computation(text)
    assert {k: len(v) for k, v in sites.items()} == {
        "mx_flash_fwd": passes + 1, "mx_flash_dq": passes,
        "mx_flash_dkv": passes}, sites
    assert all(len(set(v)) == len(v) and set(v) <= set(layer_loops)
               for v in sites.values()), sites
    backward = set(sites["mx_flash_dq"])
    assert backward == set(sites["mx_flash_dkv"]), sites
    assert len(backward & set(sites["mx_flash_fwd"])) == 1, sites
    assert _mosaic_call_sites(text) == 3 * passes + 1
