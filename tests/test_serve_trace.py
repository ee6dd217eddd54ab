"""The served path's own instrumentation: host spans on the profiler's
trace, ``BatchRecord`` counters in the bounded ``batch_log``, and the
device scopes in the compiled search's HLO metadata.

A slow device is simulated by results whose transfer to the host takes
``SLOW_S``: while a batch is in flight the stager holds out for a full
batch, so every stager phase and every flush reason occurs.
"""
import collections
import glob
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import DenseIndex, ShardedDenseIndex, StaticPruner
from repro.launch import serve
from repro.launch.serve import FLUSHES, BatchRecord, RetrievalServer

SLOW_S = 0.05
DEADLINE_S = 0.2
STAGER = ("serve.slot_wait", "serve.wait_empty", "serve.hold_full",
          "serve.wait_deadline", "serve.stage")
COMPLETER = ("serve.d2h", "serve.resolve")


@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(11)
    D = rng.standard_normal((96, 64)).astype(np.float32)
    pruner = StaticPruner(cutoff=0.25).fit(jnp.asarray(D))
    index = DenseIndex.build(pruner.prune_index(jnp.asarray(D)))
    return D, pruner, index


class _Slow:
    """A result whose transfer to the host takes ``SLOW_S``."""

    def __init__(self, a):
        self.a = a

    def __array__(self, dtype=None, copy=None):
        time.sleep(SLOW_S)
        return np.asarray(self.a, dtype=dtype)


def _slow_server(index, pruner, **kw):
    server = RetrievalServer(index, pruner, k=3, max_batch=4, **kw)
    real = server._dispatch

    def dispatch(vecs):
        s, i = real(vecs)
        return _Slow(s), _Slow(i)

    server._dispatch = dispatch
    # a deadline far above any scheduling delay keeps the script's batches
    # apart on a loaded machine
    server.batcher.deadline = DEADLINE_S
    return server


def _script(server, D) -> list:
    """One batch of each flush reason: a partial batch at its deadline; a
    partial batch held while that one is in flight, closed when the device
    drains; a full batch; a partial batch pending at close."""
    replies = [server.submit(D[0])]
    t = time.perf_counter() + 10
    while server.batcher.next_seq == 0 and time.perf_counter() < t:
        time.sleep(0.001)                    # deadline flush, now in flight
    replies.append(server.submit(D[1]))      # held: the device is busy
    replies[0].get(timeout=10)
    replies[1].get(timeout=10)               # idle flush once it drained
    replies += [server.submit(D[i]) for i in range(2, 6)]   # full
    for r in replies[2:]:
        r.get(timeout=10)
    replies.append(server.submit(D[6]))      # drained by close
    server.close()
    replies[-1].get(timeout=10)
    return replies


def _host_spans(trace_dir) -> list:
    [path] = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):   # a line per thread
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, dict(e.stats).get("batch"), thread))
    return out


def test_spans_on_the_profiler_trace(served, tmp_path):
    D, pruner, index = served
    server = _slow_server(index, pruner)
    with jax.profiler.trace(str(tmp_path)):
        _script(server, D)
    spans = _host_spans(tmp_path)
    assert {name for name, _, _ in spans} == set(STAGER + COMPLETER)
    assert all(isinstance(b, int) for _, b, _ in spans)
    # a batch's stager and completer spans share its number, and each
    # thread writes its own spans
    by_batch = collections.defaultdict(set)
    threads = collections.defaultdict(set)
    for name, b, thread in spans:
        by_batch[b].add(name)
        threads[name in STAGER].add(thread)
    for rec in server.batch_log:
        assert {"serve.stage", "serve.d2h", "serve.resolve"} <= \
            by_batch[rec.seq]
    assert not threads[True] & threads[False]


def test_sync_loop_same_span_names(served, tmp_path):
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=3, max_batch=4,
                             pipeline_depth=1)
    with jax.profiler.trace(str(tmp_path)):
        for i in range(3):
            server.query(D[i])
        server.close()
    names = {name for name, _, _ in _host_spans(tmp_path)}
    assert names == {"serve.wait_empty", "serve.wait_deadline",
                     "serve.stage", "serve.d2h", "serve.resolve"}


def test_batch_records(served):
    D, pruner, index = served
    server = _slow_server(index, pruner)
    replies = _script(server, D)
    log = list(server.batch_log)
    assert all(isinstance(b, BatchRecord) for b in log)
    assert sum(b.size for b in log) == len(replies)
    assert [b.seq for b in log] == list(range(len(log)))
    for b in log:
        assert b.wait_s >= 0 and b.stage_s >= 0 and b.resolve_s >= 0
        assert b.t_dispatch + b.stage_s <= b.t_done
    assert [b.flush for b in log] == ["deadline", "idle", "full", "drain"]
    stats = server.worker_stats()
    assert sum(stats["flushes"].values()) == stats["batches"] == len(log)
    assert set(stats["flushes"]) == set(FLUSHES)
    # the held request waited about one slow transfer for its batch
    assert log[1].wait_s >= 0.5 * SLOW_S
    assert stats["d2h_ms"] >= 1e3 * SLOW_S
    for key in ("wait_ms", "stage_ms", "resolve_ms"):
        assert stats[key] >= 0


def test_batch_log_stays_at_its_bound(served, monkeypatch):
    D, pruner, index = served
    monkeypatch.setattr(serve, "BATCH_LOG_BOUND", 3)
    server = RetrievalServer(index, pruner, k=3, max_batch=4,
                             pipeline_depth=1)
    try:
        for i in range(7):
            server.query(D[i])
    finally:
        server.close()
    assert len(server.batch_log) == 3
    assert [b.seq for b in server.batch_log] == [4, 5, 6]
    assert server.worker_stats()["batches"] == 3


def test_records_under_concurrent_clients(served):
    """More submitting threads than cores and a reader polling the stats,
    with a short switch interval: every request is counted once, in order,
    and no record loses its resolve time to the update in place."""
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=3, max_batch=8)
    per, errs = 20, []

    def client(i):
        try:
            for j in range(per):
                server.query(D[(i + j) % len(D)], timeout=30)
        except BaseException as e:  # noqa: BLE001 — must fail the test
            errs.append(e)

    def reader():
        while not done.is_set():
            server.worker_stats()

    done = threading.Event()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        clients = [threading.Thread(target=client, args=(i,))
                   for i in range(16)]
        poll = threading.Thread(target=reader)
        for t in clients + [poll]:
            t.start()
        for t in clients:
            t.join(timeout=60)
        done.set()
        poll.join(timeout=10)
    finally:
        sys.setswitchinterval(switch)
        server.close()
    assert not errs and not any(t.is_alive() for t in clients + [poll])
    log = list(server.batch_log)
    assert sum(b.size for b in log) == 16 * per
    assert [b.seq for b in log] == list(range(len(log)))
    assert all(b.resolve_s >= 0 for b in log)     # nan fails


@pytest.mark.parametrize("rows", ["records", "3-tuples"])
def test_worker_stats_reads_rows_by_index(rows):
    t = [(0.0, 0.010), (0.005, 0.020), (0.030, 0.035)]
    log = [BatchRecord(4, a, b, i, 0.002 * 4, "full", 0.001, 0.0005)
           for i, (a, b) in enumerate(t)]
    if rows == "3-tuples":
        log = [tuple(b[:3]) for b in log]
    owner = type("Owner", (), dict(batch_log=log, max_batch=4,
                                   _log_lock=threading.Lock()))()
    stats = RetrievalServer.worker_stats(owner)
    assert stats["batches"] == 3 and stats["mean_batch"] == 4.0
    assert stats["worker_qps"] == pytest.approx(12 / 0.035)
    if rows == "3-tuples":
        assert set(stats) == {"batches", "mean_batch", "occupancy",
                              "worker_qps", "service_qps"}
    else:
        assert stats["wait_ms"] == pytest.approx(2.0)
        assert stats["stage_ms"] == pytest.approx(1.0)
        assert stats["resolve_ms"] == pytest.approx(0.5)
        assert stats["d2h_ms"] == pytest.approx(1e3 * (0.030 / 3) - 1.0)
        assert stats["flushes"] == dict(full=3, deadline=0, idle=0, drain=0)


def _scopes(hlo: str) -> set:
    names = re.findall(r'op_name="([^"]*)"', hlo)
    return {s for s in ("project", "score", "select", "merge")
            if any(f"/{s}/" in n for n in names)}


def test_device_scopes_in_compiled_hlo():
    rng = np.random.default_rng(5)
    D = jnp.asarray(rng.standard_normal((3000, 16)), jnp.float32)
    W = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    Q = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
    index = DenseIndex.build(D, quantize_int8=True)
    from repro.core.index import _dense_search_projected
    hlo = _dense_search_projected.lower(
        index.vectors, index.scale, W, None, Q, k=5, block=1024,
        backend="jnp").compile().as_text()
    assert _scopes(hlo) == {"project", "score", "select"}
    # one strip: no scan, the same scopes
    hlo = _dense_search_projected.lower(
        index.vectors, index.scale, W, None, Q, k=5, block=None,
        backend="jnp").compile().as_text()
    assert _scopes(hlo) == {"project", "score", "select"}

    assert jax.device_count() >= 4
    mesh = jax.make_mesh((4,), ("data",))
    sharded = ShardedDenseIndex.build(D, mesh, quantize_int8=True)
    sharded.search_projected(Q, W, k=5)
    [fn] = [f for key, f in sharded._jit_cache.items()
            if key[0] == "projected"]
    hlo = fn.lower(sharded.vectors, W, sharded.scale, None,
                   Q).compile().as_text()
    assert _scopes(hlo) == {"project", "score", "select", "merge"}


# the scan's loop and its set-up (the empty running list, the strip
# starts): the loop's time is its body's, which sits in score and select
SCAN_PLUMBING = {"while", "broadcast_in_dim", "iota", "mul"}


@pytest.mark.parametrize("merge", ["flat", "hierarchical"])
def test_sharded_search_ops_all_scoped(merge):
    """Every operation of the unpruned sharded search sits in ``score``,
    ``select`` or ``merge``: the shard-local id offset, the pad mask and
    the pad cut (n = 3 mod 4, so one padding row) included, which the
    cross-chip merge waits on."""
    rng = np.random.default_rng(6)
    D = jnp.asarray(rng.standard_normal((3003, 16)), jnp.float32)
    shape = (4,) if merge == "flat" else (2, 2)
    mesh = jax.make_mesh(shape, ("data",) if merge == "flat"
                         else ("row", "col"))
    index = ShardedDenseIndex.build(D, mesh, merge=merge)
    q = jax.device_put(np.zeros((8, 16), np.float32), index.query_sharding)
    hlo = jax.jit(index._search_fn(5, merge, 1024)).lower(
        index.vectors, q).compile().as_text()
    ops = [n for n in re.findall(r'op_name="([^"]*)"', hlo)
           if n.startswith("jit(shard_fn)/shard_map/")]
    assert ops
    unscoped = {n for n in ops
                if not any(f"/{s}/" in n for s in ("score", "select",
                                                   "merge"))}
    assert {n.rsplit("/", 1)[1] for n in unscoped} <= SCAN_PLUMBING
    assert all("/jit(_scan_topk)/" in n for n in unscoped)
    assert _scopes(hlo) == {"score", "select", "merge"}
