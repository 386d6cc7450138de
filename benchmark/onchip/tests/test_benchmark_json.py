"""``BENCHMARK.json`` and the traffic files agree on which end-to-end
metric a cell's rate goes under, and every per-layer metric moves a
metric that its cells report.  Needs no JAX.

    python -m pytest benchmark/onchip/tests/test_benchmark_json.py -q
"""
import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ONCHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(ONCHIP))


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCH = _load(ROOT, "BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}


def _reports(metric, cell):
    return cell in END_TO_END[metric].get("workloads", list(CELLS))


def test_every_cells_rate_metric_is_an_end_to_end_entry_that_names_it():
    rates, rated = set(), []
    for name, w in CELLS.items():
        rate = _load(ONCHIP, "traffic",
                     w["traffic"] + ".json").get("rate_metric")
        if rate is None:        # a cell that reports no rate
            continue
        assert rate in END_TO_END, (name, rate)
        assert name in END_TO_END[rate].get("workloads", []), (name, rate)
        rates.add(rate)
        rated.append(name)
    # a rate metric lists its cells, and no cell is in two of them
    listed = [c for r in rates for c in END_TO_END[r]["workloads"]]
    assert sorted(listed) == sorted(rated)


def test_every_traffic_file_is_some_cells():
    used = {w["traffic"] for w in CELLS.values()}
    on_disk = {os.path.basename(p)[:-len(".json")]
               for p in glob.glob(os.path.join(ONCHIP, "traffic", "*.json"))}
    assert used == on_disk


def test_a_per_layer_metric_moves_what_each_of_its_cells_reports():
    for m in BENCH["per_layer"]:
        assert m["moves"] in END_TO_END, m["name"]
        for cell in m.get("workloads", list(CELLS)):
            assert cell in CELLS, (m["name"], cell)
            assert _reports(m["moves"], cell), (m["name"], cell)
        assert os.path.exists(os.path.join(
            ONCHIP, "metrics", m["name"] + ".py")), m["name"]


def test_a_routed_cells_rate_has_a_name_and_a_bound_of_its_own():
    plain = END_TO_END["tok_per_s"]
    assert plain["bound"] == 0.01
    for name, cell, low, high in (
            ("tok_per_s_routed", "glm47f_ep8_fused_k4", 0.04, 0.1),
            ("tok_per_s_routed.tri", "trinitym_ep16_fused_k4", 0.015, 0.04)):
        routed = END_TO_END[name]
        assert routed["workloads"] == [cell]
        assert cell not in plain["workloads"]
        assert (routed["unit"], routed["better"], routed["source"]) \
            == (plain["unit"], plain["better"], plain["source"])
        assert low <= routed["bound"] <= high
        for m in BENCH["per_layer"]:
            if m.get("workloads") == [cell]:
                assert m["moves"] in (name, "setup_s"), m["name"]
