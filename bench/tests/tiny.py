"""A copy of the benchmark at a size a CPU test can hold."""
import json
import os
import shutil

from harness import spec

ROWS = 20011          # not a multiple of the block: the last block is ragged
BLOCK = 4096
# configurations kept for cells not proved on the chip yet; the copy gives
# them cells so that their paths (float32 rows, four shards) are exercised
UNPROVED = {"msmarco-tasb-f32-m384": 1, "msmarco-tasb-f32-d768": 4}
TEST_LIMITS = dict(score_err=1e-4, rank_gap=1e-4)


def root(tmp_path, **traffic) -> str:
    """A checkout holding ``BENCHMARK.json`` and ``bench/`` with every
    configuration cut to ``ROWS`` rows; ``traffic`` overrides mix keys."""
    out = str(tmp_path / "checkout")
    shutil.copytree(spec.BENCH, os.path.join(out, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for d, edit in (("configs", dict(rows=ROWS, block_rows=BLOCK)),
                    ("traffic", traffic)):
        folder = os.path.join(out, "bench", d)
        for f in os.listdir(folder):
            path = os.path.join(folder, f)
            with open(path) as fh:
                c = json.load(fh)
            c.update(edit)
            if d == "configs" and c.get("limits") is None:
                c["limits"] = TEST_LIMITS
            with open(path, "w") as fh:
                json.dump(c, fh)
    bench = spec.load_benchmark()
    have = {c["name"] for c in bench["configs"]}
    for name, chips in UNPROVED.items():
        if name in have:
            continue
        bench["configs"].append(dict(name=name, source="test",
                                     file=f"bench/configs/{name}.json",
                                     reduced=[], why="test"))
        cell = f"{name}.batch"
        bench["workloads"].append(dict(name=cell, config=name,
                                       traffic="batch", chips=chips,
                                       why="test"))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"] in ("qps", "dispatch_ms.batch",
                             "scan_roofline.batch", "device_idle.batch"):
                m["workloads"].append(cell)
        if chips == 4 and not any(m["name"] == "collective_ms.4chip"
                                  for m in bench["per_layer"]):
            bench["per_layer"].append(dict(
                name="collective_ms.4chip", unit="ms", better="lower",
                source="device_trace", layer="cross-chip merge",
                moves="qps", workloads=[cell]))
    with open(os.path.join(out, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return out
