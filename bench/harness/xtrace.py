"""Reduction of a profiler trace (``.xplane.pb``) to device facts.

Keys, as read from a TPU v5e trace of the served path: one plane per chip
named ``/device:TPU:<i>``; on it the line ``XLA Ops`` holds one event per
executed HLO operation (nested: a ``while`` event holds its body's ops),
and ``XLA Modules`` one per executed program. Host threads are on
``/host:CPU``. Timestamps of all planes share one clock, counted in ns
from the start of the trace.

From those: the device-busy union (the time in which any operation ran),
the gaps between busy intervals, collective time (operations whose name
names a collective), and program executions by name.
"""
from __future__ import annotations

import collections
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def reduce(path: str, window: tuple[int, int] | None = None,
           gaps: int = 10, seconds: float | None = None) -> dict:
    """Device facts of the trace at ``path``, within ``window`` (ns on the
    trace's clock). By default the window opens at the first device event
    and lasts ``seconds``, or closes at the last device event."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, e.start_ns, e.start_ns
                                + e.duration_ns) for e in line.events]
            devices.append((plane.name, ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    devices.sort(key=lambda d: int(d[0][len(DEVICE_PREFIX):]))
    if not any(ops for _, ops, _ in devices):
        return dict(devices=[], window_ns=0)
    if window is None:
        lo = min(a for _, ops, _ in devices for _, a, _ in ops)
        hi = (lo + int(seconds * 1e9) if seconds is not None else
              max(b for _, ops, _ in devices for _, _, b in ops))
        window = (lo, hi)
    lo, hi = window
    out = []
    for name, ops, modules in devices:
        busy = _union(_clip([(a, b) for _, a, b in ops], lo, hi))
        per_op: dict = collections.defaultdict(int)
        coll = []
        for op, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            per_op[op.split(" = ")[0]] += b - a
            if any(c in op for c in COLLECTIVES):
                coll.append((a, b))
        runs = collections.Counter(m for m, a, b in modules
                                   if lo <= (a + b) // 2 < hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]), reverse=True)
        out.append(dict(
            plane=name,
            busy_ns=sum(b - a for a, b in busy),
            collective_ns=sum(b - a for a, b in _union(coll)),
            ops=dict(per_op), modules=dict(runs),
            gaps=[(g, a, b, _host_at(host, a, b)) for g, a, b in
                  idle[:gaps]]))
    return dict(devices=out, window_ns=hi - lo, window=(lo, hi))


def _host_at(host, a: int, b: int) -> str:
    """What the host was doing in [a, b): the name of the host event that
    covers most of it, the shortest on a tie."""
    best, best_key = "host idle", (0, 0)
    for name, x, y in host:
        cover = min(b, y) - max(a, x)
        if cover > 0:
            key = (cover, -(y - x))
            if key > best_key:
                best, best_key = name, key
    return best


def top_ops(reduced: dict, count: int = 10) -> list[list]:
    """The device operations that took most time, by HLO name, summed over
    chips and averaged per chip. Nested ops count in their parent too."""
    total: dict = collections.defaultdict(int)
    for d in reduced["devices"]:
        for op, ns in d["ops"].items():
            total[op] += ns
    n = max(len(reduced["devices"]), 1)
    return [[op, ns / n / 1e9] for op, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:count]]


def top_gaps(reduced: dict, count: int = 10) -> list[list]:
    gaps = [(g, label) for d in reduced["devices"] for g, _, _, label
            in d["gaps"]]
    return [[label, g / 1e9] for g, label in sorted(gaps, reverse=True)[
        :count]]

