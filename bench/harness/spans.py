"""The program's own marks in a profiler trace (``.xplane.pb``).

Host spans: ``RetrievalServer`` writes ``serve.*`` spans
(``jax.profiler.TraceAnnotation``) on ``/host:CPU``, each with a ``batch``
stat. The stager's spans follow each other with no gap, so every moment
the chips idle falls in one of them, or in none where the program has no
spans (a parent without them).

Device scopes: ``jax.named_scope`` names the search's layers; each XLA
operation carries them in its ``op_name`` (``.../score/dot_general``),
which the trace keeps as a stat of the operation's event metadata. A
scope's busy time is the union of the intervals of the operations whose
``op_name`` holds ``/<scope>/``.

``jax.profiler.ProfileData`` does not show event-metadata stats, so the
file is read with a protobuf description of the XSpace fields used here
(field numbers as in XLA's ``xplane.proto``).
"""
from __future__ import annotations

import bisect
import collections
import functools

from harness.xtrace import DEVICE_PREFIX, HOST_PLANE, OPS_LINE, _clip, _union

STAGER = ("serve.slot_wait", "serve.wait_empty", "serve.hold_full",
          "serve.wait_deadline", "serve.stage")
COMPLETER = ("serve.d2h", "serve.resolve")
SCOPES = ("project", "score", "select", "merge")
OP_NAME_STAT = "tf_op"     # the stat that holds an XLA operation's op_name
LONG_GAP_NS = 1_000_000    # an idle gap worth naming


@functools.cache
def _xspace_class():
    """A message class for XSpace with the fields this module reads."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                           package="bench_xplane",
                                           syntax="proto3")
    msgs = {
        "XSpace": [("planes", 1, "XPlane")],
        "XPlane": [("name", 2, F.TYPE_STRING), ("lines", 3, "XLine"),
                   ("event_metadata", 4, "EventMetadataEntry"),
                   ("stat_metadata", 5, "StatMetadataEntry")],
        # map<int64, ...> entries, on the wire
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64),
                               ("value", 2, "XEventMetadata?")],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64),
                              ("value", 2, "XStatMetadata?")],
        "XLine": [("name", 2, F.TYPE_STRING),
                  ("timestamp_ns", 3, F.TYPE_INT64),
                  ("events", 4, "XEvent")],
        "XEvent": [("metadata_id", 1, F.TYPE_INT64),
                   ("offset_ps", 2, F.TYPE_INT64),
                   ("duration_ps", 3, F.TYPE_INT64),
                   ("stats", 4, "XStat")],
        "XStat": [("metadata_id", 1, F.TYPE_INT64),
                  ("uint64_value", 3, F.TYPE_UINT64),
                  ("int64_value", 4, F.TYPE_INT64),
                  ("str_value", 5, F.TYPE_STRING),
                  ("ref_value", 7, F.TYPE_UINT64)],
        "XEventMetadata": [("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_STRING),
                           ("stats", 5, "XStat")],
        "XStatMetadata": [("id", 1, F.TYPE_INT64), ("name", 2, F.TYPE_STRING)],
    }
    for name, fields in msgs.items():
        m = f.message_type.add(name=name)
        for fname, number, kind in fields:
            if isinstance(kind, str):     # a message; "?" marks one
                m.field.add(name=fname, number=number, type=F.TYPE_MESSAGE,
                            label=(F.LABEL_OPTIONAL if kind.endswith("?")
                                   else F.LABEL_REPEATED),
                            type_name=f".bench_xplane.{kind.rstrip('?')}")
            else:
                m.field.add(name=fname, number=number, type=kind,
                            label=F.LABEL_OPTIONAL)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _stats(stats, names: dict) -> dict:
    """{stat name: value}; a string kept by reference is looked up."""
    out = {}
    for s in stats:
        out[names.get(s.metadata_id, "")] = (
            s.str_value or (names.get(s.ref_value, "") if s.ref_value
                            else s.int64_value or s.uint64_value))
    return out


def _events(line):
    """(event, start, end), in ns on the trace's clock."""
    t0 = line.timestamp_ns
    for e in line.events:
        a = t0 + e.offset_ps / 1000
        yield e, a, a + e.duration_ps / 1000


def load(path: str):
    with open(path, "rb") as f:
        return _xspace_class().FromString(f.read())


def reduce(path: str, window: tuple[float, float]) -> dict:
    """The program's marks in the trace at ``path`` within ``window`` (ns
    on the trace's clock, as ``xtrace.reduce`` gives it).

    ``spans``: {span name: [(start, end, batch)]}, clipped to the window;
    ``scopes``: per chip, {scope: busy ns}; ``idle_ns``: time in which no
    chip ran an operation; ``idle_by_span``: that idle time split by the
    stager span it fell in (``"none"`` outside any); ``stager_ns``: time
    covered by stager spans; ``long_gaps``: idle gaps longer than 1 ms as
    (start, end, the stager span that holds all of it or ``None``, ns of
    it outside every stager span).
    """
    lo, hi = window
    space = load(path)
    spans = collections.defaultdict(list)
    busy_all, scopes = [], []
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        if plane.name.startswith(DEVICE_PREFIX):
            meta = {e.key: _stats(e.value.stats, names).get(OP_NAME_STAT, "")
                    for e in plane.event_metadata}
            per_scope = collections.defaultdict(list)
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e, a, b in _events(line):
                    busy_all.append((a, b))
                    op = meta.get(e.metadata_id, "") or _stats(
                        e.stats, names).get(OP_NAME_STAT, "")
                    for scope in SCOPES:
                        if f"/{scope}/" in op:
                            per_scope[scope].append((a, b))
            scopes.append((int(plane.name[len(DEVICE_PREFIX):]), {
                s: sum(b - a for a, b in _union(_clip(iv, lo, hi)))
                for s, iv in per_scope.items()}))
        elif plane.name == HOST_PLANE:
            meta = {e.key: e.value.name for e in plane.event_metadata}
            for line in plane.lines:
                for e, a, b in _events(line):
                    name = meta.get(e.metadata_id, "")
                    if name.startswith("serve.") and b > lo and a < hi:
                        spans[name].append((max(a, lo), min(b, hi),
                                            _stats(e.stats, names).get(
                                                "batch")))
    busy = _union(_clip(busy_all, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    # one thread writes the stager's spans: they do not overlap, so their
    # ends are sorted too
    stager = sorted((a, b, name) for name in STAGER
                    for a, b, _ in spans.get(name, ()))
    ends = [b for _, b, _ in stager]
    by_span: dict = collections.defaultdict(float)
    long_gaps = []
    for a, b in idle:
        left, holder = b - a, None
        for j in range(bisect.bisect_right(ends, a), len(stager)):
            x, y, name = stager[j]
            if x >= b:
                break
            cover = min(b, y) - max(a, x)
            by_span[name] += cover
            left -= cover
            if x <= a and b <= y:
                holder = name
        left = left if left > 0.5 else 0   # ns outside every stager span
        if left:
            by_span["none"] += left
        if b - a > LONG_GAP_NS:
            long_gaps.append((a, b, holder, left))
    return dict(
        window=(lo, hi), spans=dict(spans),
        scopes=[s for _, s in sorted(scopes)],
        idle_ns=sum(b - a for a, b in idle), idle_by_span=dict(by_span),
        stager_ns=sum(b - a for a, b in _union([(a, b)
                                                 for a, b, _ in stager])),
        long_gaps=long_gaps)


def idle_host(facts: dict) -> float | None:
    """% of the traced window in which every chip idled while the stager
    was not waiting for a first request: idle the host caused, not the
    traffic. ``None`` without the program's spans."""
    prog = facts.get("program")
    if not prog or not prog["spans"] or not facts["trace"]["window_ns"]:
        return None
    own = prog["idle_ns"] - prog["idle_by_span"].get("serve.wait_empty", 0)
    return 100.0 * own / facts["trace"]["window_ns"]


def scope_ms(facts: dict, scope: str) -> float | None:
    """Busy ms of the operations under one device scope, on the chip where
    it is largest, per dispatch completed in the window. ``None`` without
    the scope."""
    prog = facts.get("program")
    if not prog or not facts["batches"]:
        return None
    busy = max((s.get(scope, 0) for s in prog["scopes"]), default=0)
    return busy / 1e6 / len(facts["batches"]) if busy else None
