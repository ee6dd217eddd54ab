"""Configurations, mixes and metrics are found by name from files alone."""
import json
import os

import pytest

from harness import spec

import tiny


def test_every_cell_resolves():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert cell["config"]["chips"] == w["chips"]
        assert cell["per_layer"], w["name"]
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        for m in cell["per_layer"]:
            assert callable(spec.reader(m["name"]))


def test_adding_a_cell_is_adding_files(tmp_path):
    root = tiny.root(tmp_path)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "msmarco-tasb-int8-m384.json")
              ) as f:
        cfg = json.load(f)
    cfg.update(name="new-config", m=192, cutoff=0.75)
    with open(os.path.join(bench, "configs", "new-config.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "trickle.json"), "w") as f:
        json.dump(dict(loop="open", rate_qps=50.0, arrival_seed=1,
                       query_pool=64), f)
    with open(os.path.join(bench, "metrics", "answered.trickle.py"), "w") as f:
        f.write("def read(facts):\n    return 7.0\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append(dict(name="new-config", source="test",
                             file="bench/configs/new-config.json",
                             reduced=[], why="test"))
    b["workloads"].append(dict(name="new-config.trickle", config="new-config",
                               traffic="trickle", chips=1, why="test"))
    b["end_to_end"][1]["workloads"].append("new-config.trickle")
    b["per_layer"].append(dict(name="answered.trickle", unit="%",
                               better="higher", source="host_clock",
                               layer="load generator", moves="p50_ms",
                               workloads=["new-config.trickle"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    cell = spec.cell("new-config.trickle", root)
    assert cell["config"]["m"] == 192
    assert cell["traffic"]["rate_qps"] == 50.0
    assert [m["name"] for m in cell["per_layer"]] == ["answered.trickle"]
    assert spec.reader("answered.trickle", root)({}) == 7.0
    assert {m["name"] for m in cell["end_to_end"]} == {
        "p50_ms", "peak_hbm_gib", "setup_s"}


def test_unknown_names_are_refused():
    with pytest.raises(SystemExit):
        spec.cell("no-such-cell")
    with pytest.raises(SystemExit):
        spec.peaks("TPU v0 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
