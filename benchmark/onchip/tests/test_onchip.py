"""Tests of the benchmark's yardstick, at sizes a CPU test run can hold.

    JAX_PLATFORMS=cpu python -m pytest benchmark/onchip/tests -q

* the control (the reference in int8, put in the program's place) comes
  out NOT correct in every cell, and the reference in the configuration's
  own bfloat16 comes out correct;
* a whole run of the harness (``run.py --rehearse``: everything but the
  look for a chip) comes out correct, and NOT correct with the timed path
  broken underneath: a state left unchanged, half of the batch left out;
* the trace reducer on a recorded TPU trace; the FLOP and byte counts
  against hand counts.

They say nothing about the chip: every timing in them is a CPU's.
"""
import argparse
import io
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(ONCHIP))
for p in (ROOT, ONCHIP):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as run_mod  # noqa: E402

run_mod._environment(True)

import compare  # noqa: E402
import flops  # noqa: E402
import harness  # noqa: E402
import readers  # noqa: E402
import traffic  # noqa: E402
import trace_reduce  # noqa: E402

CELLS = [w["name"] for w in
         harness.load_json(ROOT, "BENCHMARK.json")["workloads"]]


def _run(cell, seed, fault=None, trace=0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=0.5,
                              trace=trace, rehearse=True)
    out, err = io.StringIO(), io.StringIO()
    rc = run_mod.run_cell(args, fault=fault, out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    last = out.getvalue().strip().splitlines()[-1]
    return json.loads(last), err.getvalue()


@pytest.mark.parametrize("cell", CELLS)
def test_run_is_correct_and_well_formed(cell):
    result, err = _run(cell, seed=2 ** 31 + 77)     # seeds pass 32 bits
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["device"]["platform"] == "cpu"    # never a chip's number
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    # the rate goes under the one name the cell's traffic gives it
    c = harness.Cell(harness.load_json(ROOT, "BENCHMARK.json"), cell)
    rates = {harness.load_json(ONCHIP, "traffic", f)["rate_metric"]
             for f in os.listdir(os.path.join(ONCHIP, "traffic"))}
    # (which name that is, test_benchmark_json.py holds: glm's line has
    # ``tok_per_s_routed`` and no ``tok_per_s``)
    assert rates & set(result["metrics"]) == {c.traffic["rate_metric"]}
    # (the CPU client reports no memory, so that one metric reads 0 here)
    assert all(v["value"] > 0 for k, v in result["metrics"].items()
               if k != "peak_hbm_gib")
    for name, c in result["compared"].items():
        assert "compared %s" % name in err


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault):
    result, err = _run(cell, seed=41, fault=fault)
    assert result["correct"] is False, result["compared"]
    assert "FAILS" in err


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct_and_bf16_is(cell):
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    c = harness.Cell(bench, cell, rehearse=True)
    ref = harness.load_module("reference", c.config["reference"])
    cmp = harness.load_module("comparisons", c.traffic["comparison"])
    for seed in (51, 52, 53):
        ring = traffic.host_ring(c.config, c.traffic, seed)
        want = cmp.follow(c, ref, ring, seed)
        ok, rows = compare.verdict(
            cmp.numbers(cmp.follow(c, ref, ring, seed, mode="int8"),
                        want)[0], c.limits)
        assert not ok, (seed, rows)
        ok, rows = compare.verdict(
            cmp.numbers(cmp.follow(c, ref, ring, seed, mode="bf16"),
                        want)[0], c.limits)
        assert ok, (seed, rows)


@pytest.mark.parametrize("cell", CELLS)
def test_chip_readings_stand_on_the_right_side_of_the_limits(cell):
    """``limits/<cell>.json`` keeps what calibrate.py and ``run.py
    --fault`` read on the chip at the cell's own size.  Held to the
    committed limits, every sound seed's numbers pass, and every seed of
    the control and of each fault fails."""
    doc = harness.load_json(ONCHIP, "limits", cell + ".json")
    chip = doc["chip_readings"]
    assert len(chip["program"]) >= 12
    for row in chip["program"]:
        assert compare.verdict(row["numbers"], doc)[0], row
    for kind in ("control_int8", "fault_half_batch",
                 "fault_half_batch_in_program",
                 "fault_state_unchanged_in_program"):
        assert chip[kind], kind
        for row in chip[kind]:
            assert not compare.verdict(row["numbers"], doc)[0], (kind, row)
    for row in chip["bf16"]:
        assert compare.verdict(row["numbers"], doc)[0], row


@pytest.mark.parametrize("cell,block,blocks", [
    ("glm47f_ep8_fused_k4", 8192, 0.75),
    ("ling3fvl_ep64_fused_k4", 1024, 1.0),
    ("trinitym_ep16_fused_k4", 16384, 1.25)])
def test_blocks_per_layer_step_from_the_programs_counters(
        cell, block, blocks, monkeypatch):
    """``moe_rows_walked`` is blocks x the rows of a block; the reader
    takes the block from the program's own rule at the cell's real
    sizes (PERF.md has the three); without the counter it is silent."""
    import moe_readers

    c = harness.Cell(harness.load_json(ROOT, "BENCHMARK.json"), cell)
    name, = [m["name"] for m in c.per_layer
             if m["name"].startswith("moe_blocks_per_layer_step.")]
    read = harness.load_module("metrics", name).read
    n_tok = int(c.traffic["batch"]) * c.config["input"]["length"]
    layer_steps = 5 * 36
    stats = {"moe_tokens": n_tok * layer_steps,
             "moe_rows_walked": int(blocks * layer_steps) * block}
    monkeypatch.setattr(moe_readers, "_stats", lambda: stats)
    assert read({"cell": c}) == pytest.approx(blocks)
    del stats["moe_rows_walked"]
    assert read({"cell": c}) is None


def test_traced_run_reports_per_layer_metrics():
    result, _ = _run(CELLS[0], seed=5, trace=1)
    assert result["correct"] is True
    # on a CPU there is no device plane: readers that need one are silent
    assert "window_compiles" in result["metrics"]
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert not any("roofline" in k or "idle" in k for k in result["metrics"])


# ---------------------------------------------------------------------------
# the trace reducer on a recorded TPU v5e trace (fixtures/README.md)

FIXTURE = os.path.join(ONCHIP, "fixtures", "lm_tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load(FIXTURE))


def test_reducer_busy_union_and_idle(reduced):
    expect = harness.load_json(ONCHIP, "fixtures", "lm_tiny_v5e.expect.json")
    assert reduced["programs"] == expect["programs"]
    assert reduced["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert reduced["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # container events (while) are not busy time of their own: the union
    # of the ops is shorter than the programs' extent
    assert reduced["busy_s"] <= expect["module_s"]


def test_reducer_counts_steps_and_mfu_from_the_trace_alone(reduced):
    main = reduced["main"]
    assert main["runs"] == 3 and main["name"].startswith("jit_")
    # three runs of ~0.93 ms, the last 22 ms after the second
    assert main["seconds"] == pytest.approx(0.002802137, rel=1e-6)
    assert main["seconds"] < main["extent_s"] < reduced["window_s"]
    cell = argparse.Namespace(
        config={"family": "decoder_lm", "n_embd": 1024, "n_inner": 4096,
                "n_layer": 2, "n_head": 8, "vocab_size": 512,
                "input": {"length": 1024}},
        traffic={"steps_per_program": 2, "batch": 1}, chips=1)
    run = {"cell": cell, "trace": reduced, "steps": 10 ** 6,
           "elapsed_s": 1e-9, "peaks": {"flops_bf16": 197e12}}
    family = harness.load_module("families", "decoder_lm")
    want = 100 * family.train_step_flops(cell.config, 1) * 6 \
        / (main["extent_s"] * 197e12)
    # the host's step count and clock are not in it
    assert readers.mfu_pct(run) == pytest.approx(want, rel=1e-12)
    assert 0 < readers.mfu_pct(run) < 100
    assert readers.programs_per_step(run) == 0.5
    assert readers.mfu_pct(dict(run, trace=None)) is None


def test_reducer_finds_the_three_flash_kernels(reduced):
    k = reduced["kernels"]
    assert set(k) == {"fwd", "dq", "dkv"}
    assert all(v["shape"] == (4, 1024, 128) for v in k.values())
    # remat="dots" runs the forward kernel again in the backward pass
    assert k["fwd"]["calls"] == 2 * k["dq"]["calls"] == 2 * k["dkv"]["calls"]


def test_reducer_attributes_gaps(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) >= {"device:inside_program"}
    total = sum(gaps.values())
    assert total == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                  rel=1e-6)
    assert any(k.startswith("bench:") for k in gaps)


def test_union_and_labels():
    total, merged = trace_reduce.union_seconds(
        [(0, 2), (1, 3), (5, 6), (5.5, 5.8)])
    assert total == 4 and merged == [[0, 3], [5, 6]]
    assert trace_reduce.op_label(
        "%convert_reduce_fusion.12 = bf16[8]{0} fusion(%p), kind=kOutput, "
        "calls=%f") == "convert_reduce_fusion/kOutput"
    assert trace_reduce.op_label("%copy-done.4 = bf16[2]{0} copy-done(%c)") \
        == "copy-done"
    assert trace_reduce.pallas_kind("%fusion.1 = f32[2]{0} fusion(%p)") \
        == (None, None)


# ---------------------------------------------------------------------------
# FLOPs and bytes against hand counts


def test_conv_flops_by_hand():
    # ResNet-50's stem on one image: 112*112 outputs x 64 filters x
    # 3*7*7 multiply-adds, two operations each
    assert flops.conv2d(1, 3, 64, 112, 112, 7, 7) == \
        2 * 112 * 112 * 64 * 147 == 236027904
    cfg = harness.load_json(ONCHIP, "configs", "resnet50_v1.json")
    resnet = harness.load_module("families", cfg["family"])
    fwd, stem = resnet.forward_flops(cfg, 1)
    assert stem == 236027904
    # table 1's 3.8e9 multiply-adds of the 50-layer column, twice
    assert 7.6e9 < fwd < 8.4e9
    assert resnet.train_step_flops(cfg, 128) == 128 * (3 * fwd - stem)


def test_transformer_layer_and_attention_flops_by_hand():
    # one sequence of 4 tokens, E=8, F=16, 2 heads of 4, causal:
    # products 2*4*(4*64 + 2*128) = 4096; attention 2 products x 2 ops x
    # 2 heads x 10 pairs x 4 = 320
    assert flops.attention_forward(2, 4, 4) == 320
    assert flops.transformer_layer_forward(1, 4, 8, 16, 2) == 4096 + 320
    f, b = flops.attention_kernel("fwd", 64, 1024, 128)
    assert f == 2 * 2 * 64 * (1024 * 1025 / 2) * 128
    assert b == 4 * 64 * 1024 * 128 * 2 + 64 * 1024 * 4
    assert flops.attention_kernel("dq", 64, 1024, 128)[0] == 1.5 * f
    assert flops.attention_kernel("dkv", 64, 1024, 128)[0] == 2 * f
    cfg = harness.load_json(ONCHIP, "configs", "gpt2_medium.json")
    lm = harness.load_module("families", cfg["family"])
    per_token = lm.train_step_flops(cfg, 8) / (8 * 1024)
    assert 2.2e9 < per_token < 2.35e9        # ~6 x 354M + attention
