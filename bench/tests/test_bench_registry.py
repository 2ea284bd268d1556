"""Cells, configurations, traffic mixes and metrics are found by name,
so a later change adds them as new files only."""

import json
import os

import registry
from conftest import ROOT, TINY, make_checkout


def test_every_cell_of_the_benchmark_resolves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = registry.Cell(ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.entry_module().Entry
        assert cell.end_to_end() and cell.per_layer()
        for m in cell.end_to_end():
            assert callable(cell.metric_module("e2e", m["name"]).read)
        for m in cell.per_layer():
            assert callable(cell.metric_module("metrics", m["name"]).read)
            assert m["moves"] in {e["name"] for e in cell.end_to_end()}
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_new_files_are_picked_up_without_editing_old_ones(tmp_path):
    bench = make_checkout(str(tmp_path))
    # a new configuration, a new traffic mix and a new per-layer metric,
    # each a file of its own, and one BENCHMARK.json entry per piece
    with open(os.path.join(bench, "configs", "tiny_cnn-8.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny_cnn-16"
    cfg["workload_kw"] = {"res": 16}
    with open(os.path.join(bench, "configs", "tiny_cnn-16.json"), "w") as f:
        json.dump(cfg, f)
    os.unlink(os.path.join(bench, "traffic"))
    os.makedirs(os.path.join(bench, "traffic"))
    with open(os.path.join(bench, "traffic", "sweep8.json"), "w") as f:
        json.dump({"entry": "sweep", "points_per_request": 8,
                   "draws": {"router_latency": [1, 2, 3, 4],
                             "scalar_alu_latency": [1, 2]},
                   "check_sample": 2,
                   "limits": {"cycles_gap": 0, "energy_gap": 0}}, f)
    os.unlink(os.path.join(bench, "metrics"))
    os.makedirs(os.path.join(bench, "metrics"))
    with open(os.path.join(bench, "metrics", "requests.sweep8.py"),
              "w") as f:
        f.write("def read(run):\n    return float(len(run.requests))\n")
    spec_path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny_cnn-16.sweep8",
                              "config": "tiny_cnn-16", "traffic": "sweep8",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "requests.sweep8", "unit": "1",
                              "better": "higher", "source":
                              "program_counter", "layer": "harness",
                              "moves": "points_per_s",
                              "workloads": ["tiny_cnn-16.sweep8"]})
    spec["end_to_end"][0]["workloads"].append("tiny_cnn-16.sweep8")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    cell = registry.Cell(str(tmp_path), "tiny_cnn-16.sweep8", bench)
    assert cell.config["workload_kw"] == {"res": 16}
    assert cell.traffic["points_per_request"] == 8
    assert cell.entry_module().__doc__.startswith("Timing sweeps")
    assert [m["name"] for m in cell.end_to_end()] == ["points_per_s",
                                                       "setup_s"]
    assert [m["name"] for m in cell.per_layer()] == ["requests.sweep8"]
    mod = cell.metric_module("metrics", "requests.sweep8")

    class _Run:
        requests = [1, 2, 3]
    assert mod.read(_Run()) == 3.0


def test_a_metric_without_a_cell_list_follows_what_it_moves(tmp_path):
    make_checkout(str(tmp_path))
    spec_path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["per_layer"].append({"name": "any", "unit": "%", "better": "lower",
                              "source": "device_trace", "layer": "device",
                              "moves": "point_p90_ms"})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    bench = os.path.join(str(tmp_path), "bench")
    point = registry.Cell(str(tmp_path), f"{TINY}.point", bench)
    sweep = registry.Cell(str(tmp_path), f"{TINY}.sweep64", bench)
    assert "any" in [m["name"] for m in point.per_layer()]
    assert "any" not in [m["name"] for m in sweep.per_layer()]
