"""The trace reduction on a hand-written trace and on a recorded one."""
import os

from jax.profiler import ProfileData

from harness import xtrace

US = 1_000_000   # picoseconds per microsecond

# device ops (us): fusion.1 [0,10), fusion.2 [5,15), all-gather.1 [20,30),
# fusion.1 [40,45); host: PjitFunction [15,20), sleep [30,40)
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: %(10)d }
    events { metadata_id: 2 offset_ps: %(5)d duration_ps: %(10)d }
    events { metadata_id: 3 offset_ps: %(20)d duration_ps: %(10)d }
    events { metadata_id: 1 offset_ps: %(40)d duration_ps: %(5)d } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: %(30)d }
    events { metadata_id: 4 offset_ps: %(40)d duration_ps: %(5)d } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "all-gather.1" } }
  event_metadata { key: 4 value { id: 4 name: "jit_search" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: %(15)d duration_ps: %(5)d }
    events { metadata_id: 2 offset_ps: %(30)d duration_ps: %(10)d } }
  event_metadata { key: 1 value { id: 1 name: "PjitFunction" } }
  event_metadata { key: 2 value { id: 2 name: "sleep" } }
}
""" % {str(v): v * US for v in (5, 10, 15, 20, 30, 40)}


def _write(tmp_path):
    data = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(data)
    return str(path)


def test_reduce_hand_written(tmp_path):
    r = xtrace.reduce(_write(tmp_path), window=(1000, 1000 + 50_000))
    [d] = r["devices"]
    assert r["window_ns"] == 50_000
    assert d["busy_ns"] == 30_000            # [0,15) + [20,30) + [40,45)
    assert d["collective_ns"] == 10_000      # the all-gather
    assert d["modules"] == {"jit_search": 2}
    assert d["ops"] == {"fusion.1": 15_000, "fusion.2": 10_000,
                        "all-gather.1": 10_000}
    assert [(g, label) for g, _, _, label in d["gaps"]] == [
        (10_000, "sleep"), (5_000, "host idle"), (5_000, "PjitFunction")]
    assert xtrace.top_gaps(r, 1) == [["sleep", 1e-5]]
    assert xtrace.top_ops(r, 1) == [["fusion.1", 1.5e-5]]
    r = xtrace.reduce(_write(tmp_path), seconds=20e-6)
    assert r["window"] == (1000, 21_000) and r["devices"][0]["busy_ns"] == 15_000


def test_reduce_clips_to_window(tmp_path):
    r = xtrace.reduce(_write(tmp_path), window=(1000 + 8_000, 1000 + 25_000))
    [d] = r["devices"]
    assert d["busy_ns"] == 7_000 + 5_000     # [8,15) + [20,25)
    assert d["collective_ns"] == 5_000
    assert d["modules"] == {"jit_search": 1}  # midpoints 15 us, 42.5 us


def test_reduce_no_device_plane(tmp_path):
    data = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 2 name: "/host:CPU" }')
    path = tmp_path / "h.xplane.pb"
    path.write_bytes(data)
    assert xtrace.reduce(str(path))["devices"] == []


def test_reduce_recorded_chip_trace():
    """The first dispatches of a closed-loop window on one TPU v5e
    (int8 m=384 index): planes, lines and names as the reduction keys on."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata", "v5e_int8_batch.xplane.pb")
    r = xtrace.reduce(path)
    [d] = r["devices"]
    assert d["plane"] == "/device:TPU:0"
    runs = {name.split("(")[0]: n for name, n in d["modules"].items()}
    assert runs == {"jit__dense_search_projected": 4, "jit_atleast_2d": 4}
    assert r["window_ns"] == 82_787_284
    assert d["busy_ns"] == 81_746_172
    assert d["collective_ns"] == 0
    ops = [op for op, _ in xtrace.top_ops(r, 4)]
    # the scan's while loop holds the per-strip top-k (custom-call) and the
    # int8 score fusion
    assert ops[0].startswith("%while") and "%custom-call" in ops
    assert len(xtrace.top_gaps(r)) == 10
