"""Pipelined serving: reply/request integrity under concurrency, drain-on-
close, sync/pipelined bit-identity, and the no-busy-wait batching queue.

The server's correctness contract is scheduling-independent: whatever the
batch composition, in-flight depth, or arrival order, every reply must
carry exactly the submitting query's (scores, ids), and closing the server
must flush — never drop — accepted work.
"""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DenseIndex, StaticPruner
from repro.launch.serve import BatchingQueue, RetrievalServer, _drive_open

RNG = np.random.default_rng(7)


def _unit_corpus(n=96, d=64):
    """Rows ~unit-norm and well separated: query = row i retrieves id i."""
    D = RNG.standard_normal((n, d)).astype(np.float32)
    D /= np.linalg.norm(D, axis=1, keepdims=True)
    return D


@pytest.fixture(scope="module")
def served():
    D = _unit_corpus()
    pruner = StaticPruner(cutoff=0.25).fit(jnp.asarray(D))
    index = DenseIndex.build(pruner.prune_index(jnp.asarray(D)))
    return D, pruner, index


# ---------------------------------------------------------------------------
# BatchingQueue
# ---------------------------------------------------------------------------


def test_batching_queue_coalesces_backlog():
    bq = BatchingQueue(max_batch=4, deadline_ms=50.0)
    replies = [bq.submit(np.full((3,), float(i), np.float32))
               for i in range(6)]
    vecs, reps = bq.next_batch(timeout=1.0)
    assert vecs.shape == (4, 3)               # capped at max_batch
    assert reps == replies[:4]                # FIFO order preserved
    vecs, reps = bq.next_batch(timeout=1.0)   # remainder flushes at deadline
    assert vecs.shape == (2, 3)
    assert (vecs[:, 0] == [4.0, 5.0]).all()


def test_batching_queue_deadline_flushes_partial():
    bq = BatchingQueue(max_batch=32, deadline_ms=5.0)
    bq.submit(np.zeros((2,), np.float32))
    t0 = time.perf_counter()
    item = bq.next_batch(timeout=1.0)
    took = time.perf_counter() - t0
    assert item is not None and item[0].shape == (1, 2)
    assert took < 0.5                         # deadline, not the full timeout


def test_batching_queue_want_full_holds_then_kick_releases():
    bq = BatchingQueue(max_batch=8, deadline_ms=1.0)
    busy = threading.Event()
    busy.set()
    bq.submit(np.zeros((2,), np.float32))
    got = []

    def collect():
        got.append(bq.next_batch(timeout=5.0, want_full=busy.is_set))

    th = threading.Thread(target=collect)
    th.start()
    time.sleep(0.15)
    assert not got                            # held: device "busy", not full
    busy.clear()
    bq.kick()                                 # device idle -> partial flushes
    th.join(timeout=5.0)
    assert got and got[0][0].shape == (1, 2)


def test_idle_server_burns_no_cpu(served):
    """Blocking condition-variable waits: an idle server must not spin.
    The old queue slept in 200 µs increments while collecting and woke
    every 0.5 s at idle; process CPU over an idle window must stay a small
    fraction of wall time."""
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=5, max_batch=8)
    try:
        server.query(D[0])                    # warm: compile outside window
        wall = 0.6
        c0 = time.process_time()
        time.sleep(wall)
        cpu = time.process_time() - c0
        assert cpu < 0.5 * wall, f"idle server used {cpu:.3f}s CPU in {wall}s"
    finally:
        server.close()


# ---------------------------------------------------------------------------
# RetrievalServer pipeline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 3])
def test_replies_map_to_requests_under_concurrent_pressure(served, depth):
    """Many clients, shuffled arrival, batches interleaving in flight:
    reply r must answer query r (self-retrieval: query == doc row)."""
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=1, max_batch=8,
                             pipeline_depth=depth)
    n = len(D)
    order = RNG.permutation(np.arange(n).repeat(3))     # 288 requests
    hits = np.zeros(len(order), dtype=bool)

    def client(slot, doc_id):
        _, ids = server.query(D[doc_id], timeout=30.0)
        hits[slot] = (ids[0] == doc_id)

    try:
        threads = [threading.Thread(target=client, args=(s, int(i)))
                   for s, i in enumerate(order)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert hits.all(), f"{(~hits).sum()} replies answered the wrong query"
    finally:
        server.close()


def test_close_drains_inflight_without_dropping(served):
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=1, max_batch=8,
                             pipeline_depth=3)
    server.query(D[0])                        # compile before the burst
    replies = [server.submit(D[i % len(D)]) for i in range(100)]
    server.close()                            # must flush, not drop
    for i, r in enumerate(replies):
        _, ids = r.get(timeout=5.0)
        assert ids[0] == i % len(D)


def test_sync_and_pipelined_results_bit_identical(served):
    """Same queries through depth=1 and depth=3 servers (arbitrary batch
    compositions): every (scores, ids) reply must agree bit-exactly —
    scheduling may change throughput, never results."""
    D, pruner, index = served
    Q = np.repeat(D, 2, axis=0)
    outs = []
    for depth in (1, 3):
        server = RetrievalServer(index, pruner, k=5, max_batch=8,
                                 pipeline_depth=depth)
        try:
            res = _drive_open(server, Q, rate=4000.0, collect=True)
        finally:
            server.close()
        outs.append(res["results"])
    for (s0, i0), (s1, i1) in zip(*outs):
        assert (np.asarray(i0) == np.asarray(i1)).all()
        assert (np.asarray(s0) == np.asarray(s1)).all()


def test_open_loop_driver_reports(served):
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=3, max_batch=8)
    try:
        res = _drive_open(server, D[:48], rate=2000.0)
    finally:
        server.close()
    assert res["n"] == 48
    assert res["achieved_qps"] > 0
    assert res["p50_ms"] <= res["p95_ms"] <= res["p99_ms"]
    stats = server.worker_stats()
    assert stats["batches"] >= 1
    assert 0 < stats["occupancy"] <= 1.0


def test_bucketed_batches_map_and_results(served):
    """Batch-shape bucketing: partial batches pad to the next bucket in
    {8, 16, ..., max_batch}, and the returned ids match the pad-to-max
    server exactly (pad rows are inert; only the compiled shape differs)."""
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=5, max_batch=32,
                             pipeline_depth=1, bucket_batches=True)
    ref = RetrievalServer(index, pruner, k=5, max_batch=32,
                          pipeline_depth=1, bucket_batches=False)
    try:
        assert server._buckets == (8, 16, 32)
        assert [server._bucket_for(b) for b in (1, 8, 9, 16, 17, 32)] \
            == [8, 8, 16, 16, 32, 32]
        server.warmup()                        # compiles every bucket shape
        for i in range(24):
            _, ids_b = server.query(D[i])
            _, ids_r = ref.query(D[i])
            assert (np.asarray(ids_b) == np.asarray(ids_r)).all()
    finally:
        server.close()
        ref.close()


def test_pipeline_overlaps_batches_in_flight(served):
    """Under a saturating open-loop burst the stager must run ahead of the
    completer: with depth 3 the worker log shows batches whose dispatch
    happened before the previous batch finished."""
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=1, max_batch=4,
                             pipeline_depth=3)
    try:
        _drive_open(server, np.repeat(D, 2, axis=0), rate=1e5)
        log = sorted(server.batch_log, key=lambda b: b[1])
        overlapped = sum(1 for a, b in zip(log, log[1:]) if b[1] < a[2])
        assert len(log) >= 2
        assert overlapped > 0, "no batch was staged while another ran"
    finally:
        server.close()


# ---------------------------------------------------------------------------
# Lock-discipline regressions (crop of `python -m repro.analysis` findings:
# unguarded index/_proj snapshots, unlocked batch_log, bare _items read)
# ---------------------------------------------------------------------------


def test_reset_stats_clears_worker_log(served):
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=1, max_batch=4)
    try:
        server.query(D[0])
        assert server.worker_stats()["batches"] >= 1
        server.reset_stats()
        assert server.worker_stats()["batches"] == 0
    finally:
        server.close()


def test_worker_stats_safe_while_completer_appends(served):
    """worker_stats() snapshots batch_log under its lock: polling it from
    another thread mid-drive must never raise or observe a torn log row
    (the completer appends concurrently)."""
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=1, max_batch=4,
                             pipeline_depth=3)
    errs, counts = [], []
    stop = threading.Event()

    def poll():
        try:
            while not stop.is_set():
                s = server.worker_stats()
                assert s["batches"] >= 0 and s["mean_batch"] >= 0.0
                counts.append(s["batches"])
        except BaseException as e:  # noqa: BLE001 — must fail the test
            errs.append(e)

    th = threading.Thread(target=poll)
    th.start()
    try:
        _drive_open(server, np.repeat(D, 2, axis=0), rate=1e5)
    finally:
        stop.set()
        th.join(timeout=10.0)
        server.close()
    assert not errs
    # the log only grows between resets (the drive resets once, after its
    # warmup query): any drop must be a reset to empty
    assert all(b >= a or b == 0 for a, b in zip(counts, counts[1:]))


def test_submit_validation_tracks_index_swap(served):
    """submit() reads (index, projection) as ONE locked snapshot: after a
    swap that drops the pruner, validation must follow the new state."""
    D, pruner, index = served
    d_raw = D.shape[1]
    m = index.dim
    assert m < d_raw
    server = RetrievalServer(index, pruner, k=1, max_batch=4)
    try:
        server.query(D[0])                       # raw-dim queries accepted
        server.swap_index(index, pruner=None)    # now serves projected dim
        with pytest.raises(ValueError, match=str(m)):
            server.submit(D[0])
        scores, ids = server.query(np.zeros((m,), np.float32))
        assert ids.shape == (1,)
    finally:
        server.close()


def test_batching_queue_empty_tracks_submit_and_drain():
    bq = BatchingQueue(max_batch=4)
    assert bq.empty()
    bq.submit(np.zeros((2,), np.float32))
    assert not bq.empty()
    assert len(bq.drain()) == 1
    assert bq.empty()


# ---------------------------------------------------------------------------
# deadlines: completer-side expiry of overdue queued work
# ---------------------------------------------------------------------------


def test_reply_resolve_first_writer_wins():
    """A reply racing its own expiry must deliver exactly one payload:
    later writers are no-ops and the completion stamp is the winner's."""
    from repro.launch.serve import Reply, TimedOut
    r = Reply(deadline=None)
    assert r.resolve(("scores", "ids"), 1.5)
    assert not r.resolve(TimedOut("late expiry"), 9.9)
    assert r.get(timeout=1.0) == ("scores", "ids")
    assert r.completed_at == 1.5
    assert r.empty()                  # exactly one payload ever posted


def test_deadline_noop_on_fast_path(served):
    """A generous deadline must not perturb a healthy request."""
    D, pruner, index = served
    server = RetrievalServer(index, pruner, k=1, max_batch=4)
    try:
        scores, ids = server.query(D[3], deadline=30.0)
        assert int(np.asarray(ids)[0]) == 3
    finally:
        server.close()


def test_deadline_expires_overdue_work_behind_hung_dispatch(served):
    """A hung dispatch must NOT park deadline-carrying clients forever:
    the completer sweep resolves them with an explicit TimedOut while the
    batch is still stuck, and the server recovers once the hang clears."""
    from repro.launch.serve import TimedOut
    from repro.serving.fleet import FaultableIndex

    D, pruner, index = served
    faultable = FaultableIndex(index)
    server = RetrievalServer(faultable, pruner, k=1, max_batch=4)
    try:
        server.query(D[0])                       # warm/compile first
        faultable.state.inject("hang")
        t0 = time.perf_counter()
        reply = server.submit(D[1], deadline=0.3)
        out = reply.get(timeout=30.0)
        took = time.perf_counter() - t0
        assert isinstance(out, TimedOut)
        assert took < 5.0, f"expiry took {took:.1f}s for a 0.3s deadline"
        # un-hang: the stuck batch completes, its late result is a no-op
        # (first-writer-wins), and fresh queries serve normally again
        faultable.state.clear()
        scores, ids = server.query(D[2], timeout=30.0)
        assert int(np.asarray(ids)[0]) == 2
        assert server.error is None
    finally:
        faultable.state.clear()
        server.close()


def test_deadline_expired_before_batch_never_wastes_dispatch(served):
    """Already-expired work must resolve TimedOut without requiring the
    worker to execute it (queued behind a hang, deadline long past)."""
    from repro.launch.serve import TimedOut
    from repro.serving.fleet import FaultableIndex

    D, pruner, index = served
    faultable = FaultableIndex(index)
    server = RetrievalServer(faultable, pruner, k=1, max_batch=2)
    try:
        server.query(D[0])
        faultable.state.inject("hang")
        server.submit(D[1])                      # wedges the worker
        replies = [server.submit(D[i], deadline=0.2) for i in (2, 3, 4)]
        outs = [r.get(timeout=30.0) for r in replies]
        assert all(isinstance(o, TimedOut) for o in outs)
    finally:
        faultable.state.clear()
        server.close()
