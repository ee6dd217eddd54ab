"""The program's spans and device scopes read from a trace, and the
readers of the server's batch records."""
import os

import pytest
from jax.profiler import ProfileData

from harness import spans, spec
from repro.launch.serve import BatchRecord

US = 1_000_000   # picoseconds per microsecond

# device ops (us from 1000 ns): while.1 [0,40) holding fusion.1 (score)
# [0,10) and [20,30), cond.1 (select) [10,20) holding custom-call (select)
# [12,18); fusion.2 (project) [1100,1110). Host spans of the stager:
# wait_empty [40,1000), stage [1000,1100) (batch 1), hold_full [1100,3000);
# completer: d2h [1100,1150), resolve [1150,1160).
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: %(40)d }
    events { metadata_id: 2 offset_ps: 0 duration_ps: %(10)d }
    events { metadata_id: 3 offset_ps: %(10)d duration_ps: %(10)d }
    events { metadata_id: 4 offset_ps: %(12)d duration_ps: %(6)d }
    events { metadata_id: 2 offset_ps: %(20)d duration_ps: %(10)d }
    events { metadata_id: 5 offset_ps: %(1100)d duration_ps: %(10)d } }
  event_metadata { key: 1 value { id: 1 name: "while.1"
    stats { metadata_id: 9 str_value: "jit(s)/jit(_scan_topk)/while" } } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1"
    stats { metadata_id: 9 str_value: "jit(s)/while/body/score/dot_general" }
  } }
  event_metadata { key: 3 value { id: 3 name: "cond.1"
    stats { metadata_id: 9 ref_value: 10 } } }
  event_metadata { key: 4 value { id: 4 name: "custom-call"
    stats { metadata_id: 9 str_value: "jit(s)/while/body/select/cond/top_k" }
  } }
  event_metadata { key: 5 value { id: 5 name: "fusion.2"
    stats { metadata_id: 9 str_value: "jit(s)/project/dot_general" } } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
  stat_metadata { key: 10 value { id: 10 name: "jit(s)/while/body/select/cond" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: %(40)d duration_ps: %(960)d
             stats { metadata_id: 1 int64_value: 1 } }
    events { metadata_id: 2 offset_ps: %(1000)d duration_ps: %(100)d
             stats { metadata_id: 1 int64_value: 1 } }
    events { metadata_id: 3 offset_ps: %(1100)d duration_ps: %(1900)d
             stats { metadata_id: 1 int64_value: 2 } } }
  lines { id: 2 name: "python" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: %(1100)d duration_ps: %(50)d
             stats { metadata_id: 1 int64_value: 1 } }
    events { metadata_id: 5 offset_ps: %(1150)d duration_ps: %(10)d
             stats { metadata_id: 1 int64_value: 1 } }
    events { metadata_id: 6 offset_ps: %(1150)d duration_ps: %(5)d } }
  event_metadata { key: 1 value { id: 1 name: "serve.wait_empty" } }
  event_metadata { key: 2 value { id: 2 name: "serve.stage" } }
  event_metadata { key: 3 value { id: 3 name: "serve.hold_full" } }
  event_metadata { key: 4 value { id: 4 name: "serve.d2h" } }
  event_metadata { key: 5 value { id: 5 name: "serve.resolve" } }
  event_metadata { key: 6 value { id: 6 name: "np.asarray" } }
  stat_metadata { key: 1 value { id: 1 name: "batch" } }
}
""" % {str(v): v * US for v in (5, 6, 10, 12, 20, 40, 50, 100, 960, 1000,
                                 1100, 1150, 1900)}


@pytest.fixture
def trace(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(path)


def test_reduce_hand_written(trace):
    r = spans.reduce(trace, window=(1000, 1000 + 3000 * 1000))
    assert r["spans"] == {
        "serve.wait_empty": [(41_000, 1_001_000, 1)],
        "serve.stage": [(1_001_000, 1_101_000, 1)],
        "serve.hold_full": [(1_101_000, 3_001_000, 2)],
        "serve.d2h": [(1_101_000, 1_151_000, 1)],
        "serve.resolve": [(1_151_000, 1_161_000, 1)]}
    # a string kept by reference names its scope as well as a string does
    assert r["scopes"] == [dict(score=20_000, select=10_000, project=10_000)]
    # busy [0,40) and [1100,1110): idle [40,1100) and [1110,3000)
    assert r["idle_ns"] == 1_060_000 + 1_890_000
    assert r["idle_by_span"] == {"serve.wait_empty": 960_000,
                                 "serve.stage": 100_000,
                                 "serve.hold_full": 1_890_000}
    assert r["stager_ns"] == 3_000_000 - 40_000
    # the first gap spans two stager spans, the second lies in one
    assert r["long_gaps"] == [(41_000, 1_101_000, None, 0),
                              (1_111_000, 3_001_000, "serve.hold_full", 0)]


def test_reduce_clips_to_window(trace):
    r = spans.reduce(trace, window=(1000 + 15_000, 1000 + 1_105_000))
    assert r["scopes"] == [dict(score=10_000, select=5_000, project=5_000)]
    assert r["spans"]["serve.hold_full"] == [(1_101_000, 1_106_000, 2)]
    assert r["idle_by_span"] == {"serve.wait_empty": 960_000,
                                 "serve.stage": 100_000}
    assert r["long_gaps"] == [(41_000, 1_101_000, None, 0)]


def _facts(batches, program=None, window_ns=3 * 10**9):
    f = dict(batches=batches, trace=dict(window_ns=window_ns, devices=[]))
    if program is not None:
        f["program"] = program
    return f


RECORDS = [BatchRecord(32, 0.0, 0.020, 0, 0.32, "full", 0.0004, 0.0001),
           BatchRecord(16, 0.021, 0.040, 1, 0.08, "idle", 0.0002, 0.0003)]
PARENT = [tuple(b[:3]) for b in RECORDS]


@pytest.mark.parametrize("name,value", [
    ("queue_wait_ms.poisson", 1e3 * 0.40 / 48),
    ("stage_ms.poisson", 0.3),
    ("resolve_ms.poisson", 0.2)])
def test_program_counter_readers(name, value):
    read = spec.reader(name)
    assert read(_facts(RECORDS)) == pytest.approx(value)
    assert read(_facts(PARENT)) is None          # a parent's log rows
    assert read(_facts([])) is None


def test_trace_helpers(trace):
    prog = spans.reduce(trace, window=(1000, 1000 + 3000 * 1000))
    facts = _facts(RECORDS, prog, window_ns=3_000_000)
    assert spans.idle_host(facts) == pytest.approx(
        100 * (2_950_000 - 960_000) / 3_000_000)
    assert spans.scope_ms(facts, "select") == pytest.approx(0.01 / 2)
    assert spans.scope_ms(facts, "score") == pytest.approx(0.02 / 2)
    assert spans.scope_ms(facts, "merge") is None
    # a parent: no program key, or a trace with neither spans nor scopes
    for f in (_facts(PARENT), _facts(PARENT, dict(
            prog, spans={}, scopes=[{}]))):
        assert spans.idle_host(f) is None
        assert spans.scope_ms(f, "select") is None


def test_every_reader_is_entered():
    """The program counters read records the server writes: each new
    reader has an entry, in the open-loop cell, and a file."""
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("queue_wait_ms.poisson", "stage_ms.poisson",
                 "resolve_ms.poisson"):
        m = entries[name]
        assert m["source"] == "program_counter" and m["moves"] == "p50_ms"
        assert m["workloads"] == ["msmarco-tasb-int8-m384.poisson80"]
        assert os.path.exists(os.path.join(spec.BENCH, "metrics",
                                           f"{name}.py"))


def test_records_of_a_live_server():
    """The readers on rows a real server logged."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import DenseIndex
    from repro.launch.serve import RetrievalServer
    D = np.random.default_rng(0).standard_normal((64, 16)).astype(np.float32)
    server = RetrievalServer(DenseIndex.build(jnp.asarray(D)), None, k=3,
                             max_batch=4)
    replies = [server.submit(D[i]) for i in range(10)]
    for r in replies:
        r.get(timeout=30)
    server.close()
    facts = _facts(list(server.batch_log))
    for name in ("queue_wait_ms.poisson", "stage_ms.poisson",
                 "resolve_ms.poisson"):
        assert spec.reader(name)(facts) >= 0


def test_reduce_recorded_chip_trace():
    """About 100 ms of an open-loop window on one TPU v5e (int8 m=384
    index, 0.8 x capacity), with the program's spans and device scopes
    (the stats that name source files taken out): the names, stats and
    planes the reduction keys on."""
    from harness import xtrace
    path = os.path.join(spec.BENCH, "testdata",
                        "v5e_int8_poisson_spans.xplane.pb")
    red = xtrace.reduce(path)
    r = spans.reduce(path, red["window"])
    assert set(r["spans"]) == set(spans.STAGER + spans.COMPLETER)
    batches = {name: {b for _, _, b in v} for name, v in r["spans"].items()}
    assert batches["serve.stage"] == {131, 132, 133, 134, 135}
    # a batch's stager and completer spans carry the same number
    assert batches["serve.stage"] <= batches["serve.d2h"]
    assert {131, 132, 133, 134} <= batches["serve.resolve"]
    # the op_name of each operation is the `tf_op` stat of its metadata
    [scopes] = r["scopes"]
    assert set(scopes) == {"project", "score", "select"}
    assert scopes["score"] == pytest.approx(38_138_118, abs=1)
    assert scopes["select"] == pytest.approx(45_090_428, abs=1)
    assert scopes["score"] + scopes["select"] < red["devices"][0]["busy_ns"]
    # the device idles while the stager holds out for a full batch and
    # while it stages the next one; the gaps run from one span into the
    # next, with microseconds outside both
    assert set(r["idle_by_span"]) == {"serve.hold_full", "serve.stage",
                                      "serve.wait_deadline", "none"}
    assert r["idle_by_span"]["serve.hold_full"] > r["idle_by_span"][
        "serve.stage"] > 50 * r["idle_by_span"]["none"]
    assert len(r["long_gaps"]) == 5
    assert all(h is None and u < 20_000 for _, _, h, u in r["long_gaps"])
    assert r["stager_ns"] > 0.99 * red["window_ns"]
