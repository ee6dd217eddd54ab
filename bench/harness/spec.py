"""What a run measures, found by name in files alone.

``BENCHMARK.json`` at the checkout's root pairs a configuration with a
traffic mix under a cell name. Each configuration is the JSON file its
entry names, each traffic mix is ``bench/traffic/<traffic>.json``, and each
per-layer metric is a reader ``bench/metrics/<metric>.py`` with a function
``read(facts)``. Adding a cell, a mix or a metric is adding files and
entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str, root: str = ROOT) -> dict:
    """Everything one run needs: the cell, its configuration, its traffic
    mix, and the metrics it reports with and without a trace."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           f"{w['traffic']}.json")) as f:
        traffic = json.load(f)

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in reported and mine(m)]
    return dict(name=name, workload=w, config=config, traffic=traffic,
                end_to_end=end_to_end, per_layer=per_layer,
                run_seconds=bench["run_seconds"], root=root)


def reader(metric: str, root: str = ROOT):
    """``read(facts) -> float | None`` of one per-layer metric."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> dict:
    """Published peaks of one chip, by ``device_kind``. A kind that is not
    in the table is an error, never a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["kinds"]:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json ({sorted(table['kinds'])}); "
                         f"nothing was measured")
    return table["kinds"][kind]
