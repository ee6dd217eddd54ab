"""The four-chip cell: its control on the tiny copy, and its per-layer
readers on a recorded trace of the cell on a v5e 2x2 host."""
import os

import jax
import pytest

import control
import control_onepass
import run
import tiny
from harness import spec, xtrace

CELL = "msmarco-tasb-f32-d768.batch"
SEED = 3_000_000_019     # wider than 32 signed bits


def test_control_fails_f32(tmp_path, monkeypatch):
    """Float32 at ``highest`` over four shards reads under the limits; the
    reference one precision step down (three bf16 passes) in the
    program's place reads above one of them."""
    monkeypatch.setattr(run, "chips", lambda n: jax.devices()[:n])
    root = tiny.root(tmp_path, query_pool=256)
    cfg = spec.cell(CELL, root)["config"]
    assert cfg["control"] == "high" and cfg["chips"] == 4
    [line] = control.main(["--workload", CELL, "--seeds", str(SEED),
                           "--seconds", "0.5"], root=root)
    assert line["failed"] == 0 and line["sound"]["bad_ids"] == 0
    for key in ("score_err", "rank_gap"):
        assert line["sound"][key] <= cfg["limits"][key]
    assert any(line["control"]["high"][key] > cfg["limits"][key]
               for key in ("score_err", "rank_gap"))


def test_onepass_reads_above_both_limits_f32(tmp_path, monkeypatch):
    """The reference in one bf16 pass reads above both limits of the f32
    configuration: ``rank_gap`` too, which the ``high`` control leaves at
    0 on the chip, so its limit lies between two readings."""
    monkeypatch.setattr(run, "chips", lambda n: jax.devices()[:n])
    root = tiny.root(tmp_path, query_pool=256)
    cfg = spec.cell(CELL, root)["config"]
    [line] = control_onepass.main(["--workload", CELL, "--seeds", str(SEED)],
                                  root=root)
    assert line["onepass"]["bad_ids"] == 0
    for key in ("score_err", "rank_gap"):
        assert line["onepass"][key] > cfg["limits"][key]


TRACE = os.path.join(spec.BENCH, "testdata", "v5e_f32_d768_batch.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """About 37 ms of the closed-loop window of the four-chip cell on a
    v5e 2x2 host (three dispatches a chip), with the program's device
    scopes; stats that name source files taken out."""
    return xtrace.reduce(TRACE)


def test_recorded_four_chip_trace(recorded):
    from harness import spans
    devs = recorded["devices"]
    assert [d["plane"] for d in devs] == [f"/device:TPU:{i}" for i in
                                          range(4)]
    for d in devs:
        # one program a batch on every chip: the batch is staged on all
        # four, so no chip reshapes or forwards it
        assert {m.split("(")[0]: n for m, n in d["modules"].items()} == {
            "jit_shard_fn": 3}
        assert 0 < d["collective_ns"] < d["busy_ns"] / 1000
    scopes = spans.reduce(TRACE, recorded["window"])["scopes"]
    assert len(scopes) == 4
    for s in scopes:
        assert set(s) == {"score", "select", "merge"}
        assert s["merge"] < s["select"] < s["score"]


def test_four_chip_readers_on_the_recorded_trace(recorded):
    facts = dict(trace=recorded, batches=[None] * 3)
    coll = spec.reader("collective_ms.4chip")(facts)
    assert coll == pytest.approx((21271 + 19758 + 19136 + 17929) / 4 / 3
                                 / 1e6)
    skew = spec.reader("chip_skew.4chip")(facts)
    own = [d["busy_ns"] - d["collective_ns"] for d in recorded["devices"]]
    assert skew == pytest.approx(100 * (max(own) - min(own)) / max(own))
    assert 0 < skew < 0.01


def test_chip_skew_hand_written():
    read = spec.reader("chip_skew.4chip")

    def facts(*busy_coll):
        return dict(trace=dict(devices=[dict(busy_ns=b, collective_ns=c)
                                        for b, c in busy_coll]))

    # chip 0 works 1 ms more; the others wait that long in the all-gather,
    # which their busy time alone would count as work
    assert read(facts((11e6, 0.1e6), (11e6, 1.1e6), (11e6, 1.1e6),
                      (11e6, 1.1e6))) == pytest.approx(100 * 1 / 10.9)
    assert read(facts((5e6, 1e6), (5e6, 1e6))) == 0
    assert read(facts((5e6, 0))) is None
    assert read(dict(trace=dict(devices=[]))) is None
