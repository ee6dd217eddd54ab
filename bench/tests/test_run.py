"""A whole run at a tiny size on the CPU, with the look for a chip
skipped: the last line, the refusals, and faults of the timed path that
``correct`` has to catch."""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import control
import run
import tiny

INT8 = "msmarco-tasb-int8-m384"
D768 = "msmarco-tasb-f32-d768"
SEED = 3_000_000_019     # wider than 32 signed bits


@pytest.fixture
def cpu_chips(monkeypatch):
    monkeypatch.setattr(run, "chips", lambda n: jax.devices()[:n])


def _run(root, cell, capsys, seconds=1.0):
    res = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                    str(seconds), "--trace", "0"], root=root)
    out = capsys.readouterr()
    assert json.loads(out.out.strip().splitlines()[-1]) == res
    return res, out.err


@pytest.mark.parametrize("cell", [f"{INT8}.batch", f"{INT8}.poisson80",
                                  "msmarco-tasb-f32-m384.batch",
                                  f"{D768}.batch"])
def test_last_line(tmp_path, cpu_chips, capsys, cell):
    root = tiny.root(tmp_path, rate_qps=400.0, query_pool=256)
    res, err = _run(root, cell, capsys)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    names = {m["name"] for m in run.spec.cell(cell, root)["end_to_end"]}
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values()
               if m["unit"] != "GiB")   # the CPU reports no memory
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # the numbers compared close standard error, each beside its limit
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert [line.split()[0] for line in tail] == list(res["checks"])
    assert "compilations in the window: 0" in err


def test_off_a_chip_no_result(tmp_path, capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", f"{INT8}.batch", "--seed", "1", "--seconds",
                  "1", "--trace", "0"], root=tiny.root(tmp_path))
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def test_without_the_program_no_result(tmp_path):
    """A directory with only BENCHMARK.json and bench/ cannot run."""
    root = tiny.root(tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        f"{INT8}.batch", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, capture_output=True,
                       text=True, env=dict(os.environ, JAX_PLATFORMS="cpu",
                                           PYTHONPATH=""), timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_fault_answer_altered(tmp_path, cpu_chips, capsys, monkeypatch):
    """One reply's ids replaced where the dispatch produces them."""
    import repro.core.index as index
    real = index._dense_search_projected

    def altered(*a, **kw):
        s, i = real(*a, **kw)
        return s, i.at[0].set((i[0] + 1) % tiny.ROWS)

    monkeypatch.setattr(index, "_dense_search_projected", altered)
    res, _ = _run(tiny.root(tmp_path), f"{INT8}.batch", capsys)
    assert res["correct"] is False


def test_fault_exchange_left_out(tmp_path, cpu_chips, capsys, monkeypatch):
    """The all-gather merge skipped: each chip keeps its own top-k."""
    import repro.core.index as index
    assert jax.device_count() >= 4
    monkeypatch.setattr(index, "_staged_topk_merge",
                        lambda s, ids, k, stages: (s, ids))
    res, _ = _run(tiny.root(tmp_path), f"{D768}.batch", capsys)
    assert res["correct"] is False
    assert res["checks"]["rank_gap"]["value"] > res["checks"]["rank_gap"][
        "limit"]


def test_control_fails(tmp_path, cpu_chips, capsys):
    """The reference one precision step down (int4 rows for int8 rows), in
    the program's place, reads above a limit that sound runs stay under."""
    cell = f"{INT8}.batch"
    root = tiny.root(tmp_path, query_pool=256)
    cfg = run.spec.cell(cell, root)["config"]
    [line] = control.main(["--workload", cell, "--seeds", str(SEED),
                           "--seconds", "0.5"], root=root)
    for key in ("score_err", "rank_gap"):
        assert line["sound"][key] <= cfg["limits"][key]
    assert any(line["control"][cfg["control"]][key] > cfg["limits"][key]
               for key in ("score_err", "rank_gap"))
    assert line["sound"]["bad_ids"] == 0 and line["failed"] == 0
    # three bf16 passes, written out, read well above float32 at HIGHEST
    assert (line["control"]["high"]["score_err"]
            > 3 * line["sound"]["score_err"])
