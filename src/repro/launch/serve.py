"""Retrieval serving driver: batched queries against a PCA-pruned index.

The paper's online path, end to end:
  1. load the offline artefacts (PCA transform W_m + pruned index D̂)
  2. batch incoming queries (micro-batching queue with a latency deadline)
  3. one fused dispatch: q̂ = W_mᵀ q, int8 scale fold, score+top-k scan
     (``search_projected`` — projection never leaves the compiled graph)
  4. return doc ids + scores

The worker is a two-thread pipeline (``pipeline_depth`` >= 2, the
default): a *stager* assembles batches and enqueues the fused search —
JAX dispatch is asynchronous, so this returns before the device finishes —
and a *completer* blocks only on the *oldest* in-flight batch's
device-to-host transfer and posts replies. Up to ``pipeline_depth``
batches are in flight, so batch N+1's assembly, H2D transfer and dispatch
overlap batch N's compute instead of serialising behind its D2H.
``pipeline_depth<=1`` is the old synchronous loop (same math, same
compiled fn — kept for the sync-vs-pipelined benchmark rows).

``--compare-full`` serves the unpruned index side by side and reports the
measured speedup vs the O(d/m) prediction.

``--sharded`` row-shards the pruned index over a mesh of every visible
device and serves through ``ShardedDenseIndex`` (local fused scan + tiny
global top-k merge). On a CPU-only host, ``--host-devices N`` forces an
N-way mesh via ``XLA_FLAGS=--xla_force_host_platform_device_count`` —
the same code path a TPU pod takes, minus the speed. ``--backend pallas``
selects the fused score-and-select kernel for the (per-shard) scan.
``--merge hierarchical`` factors the device count into a 2-D mesh and
merges per-shard candidates in two all-gather stages (k·(a+b) candidates
per device instead of k·a·b).

``--save-index DIR`` persists the offline artifact (PCA state + pruned
vectors + int8 scale) through ``repro.core.store``; ``--load-index DIR``
serves from it — no PCA refit, no index rebuild, and the index is
host-streamed onto the device(s) (per-shard when ``--sharded``). The
cold-start time (open store -> first answered query) is printed.

``--live-append R`` serves through a ``PagedIndex`` and appends synthetic
documents at R rows/s WHILE serving: each append builds a new index (rows
in the open delta extent, with its own int8 scale) and installs it into
the running server atomically between batches (``swap_index``), then a
final compaction promotes every delta and drains its pages into the pool
mid-serve — the live-index lifecycle under traffic, zero steady-state
recompiles.
``--bucket-batches`` pads partial batches to the next bucket in
{8, 16, …, max_batch} instead of always max_batch (less pad compute at
low load for a handful of extra compiles).

The index is paged (``PagedIndex``) when ``--live-append``, ``--page-pool``
or a loaded artifact's ``paged`` manifest block asks for it: it lives in
fixed-size pages behind an int32 indirection table, so live appends,
delta promotion, compaction and eviction are page-pointer swaps — no
array rebuilds, no recompiles. ``--page-rows R`` sets the page geometry
(rows per page) and ``--page-pool P`` caps the device-resident pool at P
pages: an index larger than the pool keeps its overflow pages host-side
and streams them through the double-buffered DMA pipeline on demand
(oversubscription). Composes with ``--cascade`` (both resolutions page)
and ``--save-index`` / ``--load-index`` (the page map rides the
manifest). ``--sharded`` is refused with ``--live-append`` and
``--page-pool`` (paged per-shard pools do not exist yet).

``--cascade M:N`` serves through a two-resolution ``CascadeIndex``:
a coarse scan over the first M PCA dims (int8) keeps N·k candidates per
query, then one small exact rescore at full m picks the final top-k —
bit-identical to the single-resolution search whenever N·k >= n, ~24x
fewer scanned bytes otherwise. Composes with ``--live-append`` (both
resolutions grow and swap as one object) and ``--save-index`` /
``--load-index`` (the coarse view rides the same store as a
``resolutions`` manifest entry); ``--sharded`` is not supported.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --n-docs 50000 --dim 256 \
      --cutoff 0.5 --queries 256 --batch 32
  PYTHONPATH=src python -m repro.launch.serve --sharded --host-devices 4 \
      --backend pallas --merge hierarchical
  PYTHONPATH=src python -m repro.launch.serve --pipeline-depth 4 \
      --open-loop 200            # Poisson arrivals at 200 qps, p50/p95/p99
  PYTHONPATH=src python -m repro.launch.serve --pipeline-depth 1 \
      --open-loop 200            # same load through the synchronous loop
  PYTHONPATH=src python -m repro.launch.serve --n-docs 50000 \
      --quantize-int8 --save-index /tmp/idx
  PYTHONPATH=src python -m repro.launch.serve --load-index /tmp/idx --sharded
  PYTHONPATH=src python -m repro.launch.serve --live-append 300 \
      --open-loop 200            # paged index: append while serving,
                                 # atomic swaps, final mid-serve compaction
  PYTHONPATH=src python -m repro.launch.serve --bucket-batches \
      --open-loop 50             # low load: pad to {8,16,32}, not max_batch
  PYTHONPATH=src python -m repro.launch.serve --n-docs 100000 --dim 768 \
      --cascade 64:8             # coarse m=64 int8 scan -> exact rescore
  PYTHONPATH=src python -m repro.launch.serve --cascade 64:8 \
      --live-append 300          # cascade + live appends: both resolutions
                                 # grow and swap atomically as one object
  PYTHONPATH=src python -m repro.launch.serve --load-index /tmp/idx \
      --page-pool 96             # oversubscribed: pool capped at 96
                                 # pages, the rest stream from host
"""
from __future__ import annotations

import argparse
import queue
import threading
import time
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import CascadeIndex, DenseIndex, IndexStore, ShardedDenseIndex, StaticPruner
from repro.core.paged import PagedIndex
from repro.core.store import save_index
from repro.data.synthetic import make_dataset
from repro.util import compile_cache_dir, force_host_device_count


class TimedOut(RuntimeError):
    """A reply's deadline expired before its batch posted. Delivered as the
    reply payload (and re-raised by ``query``) — an explicit timeout, never
    a silently dropped request."""


#: why a batch closed: it filled, its deadline passed, the device drained
#: after the batch was held for a full one (``kick``), or the server closed
FLUSHES = ("full", "deadline", "idle", "drain")
#: batches ``RetrievalServer.batch_log`` keeps: about 23 minutes of full
#: batches at 1,500 queries/s
BATCH_LOG_BOUND = 1 << 16


class BatchRecord(NamedTuple):
    """One executed batch, as ``RetrievalServer.batch_log`` keeps it. The
    first three fields are the log's original row; readers that index rows
    (``b[0]``, ``b[2]``) read the same numbers from either."""

    size: int            # real queries (pad rows excluded)
    t_dispatch: float    # perf_counter at the start of ``serve.stage``
    t_done: float        # perf_counter when the results reached the host
    seq: int             # per-server batch number, the spans' ``batch``
    wait_s: float        # sum over its requests of formation - submit time
    flush: str           # one of ``FLUSHES``
    stage_s: float       # end of ``serve.stage`` minus ``t_dispatch``
    resolve_s: float     # length of ``serve.resolve`` (nan while it runs)


class Formed(NamedTuple):
    """A batch as the batcher closed it, before staging."""

    vecs: list
    replies: list
    seq: int
    flush: str
    wait_s: float


class Reply(queue.Queue):
    """Single-slot reply future for one submitted query.

    ``completed_at`` is stamped by the completer (``perf_counter``) the
    instant the batch's results post — BEFORE the client is woken. Latency
    accounting reads the stamp instead of the collector's own clock, so it
    no longer assumes replies complete in submission (FIFO) order: a
    multi-priority scheduler, a mid-drain index swap, or a slow collector
    can reorder/delay observation without corrupting the measurement.

    ``deadline`` (absolute ``perf_counter`` time, or None) lets the server
    expire the reply with a ``TimedOut`` payload if its batch has not
    posted in time. Because a reply can then race its own expiry, all
    delivery goes through ``resolve``: first writer wins, later writers
    are no-ops — a posted result never overwrites a timeout or vice versa,
    and nobody ever blocks on the single reply slot.

    ``submitted_at`` (``perf_counter``, stamped by ``BatchingQueue.submit``)
    is where the request's queue wait starts.
    """

    def __init__(self, deadline: float | None = None,
                 submitted_at: float | None = None):
        super().__init__(maxsize=1)
        self.completed_at: float | None = None
        self.submitted_at = submitted_at
        self.deadline = deadline
        self.done = False
        self._claim = threading.Lock()

    def resolve(self, payload, t: float | None = None) -> bool:
        """Deliver ``payload`` exactly once; returns False if a prior
        resolution (result, timeout, or worker crash) already won."""
        with self._claim:
            if self.done:
                return False
            self.done = True
            self.completed_at = t
        self.put_nowait(payload)
        return True


class BatchingQueue:
    """Micro-batching: collect up to ``max_batch`` requests, flush at a
    latency deadline — the standard online-serving pattern.

    All waits park on one condition variable: the old implementation spun
    ``get_nowait`` + 200 µs sleeps for the whole deadline window on every
    batch and woke every 0.5 s at idle, burning CPU for nothing. An idle
    server now costs ~zero CPU (pinned by tests/test_serve_pipeline.py).

    ``next_batch(want_full=...)`` is the pipelined scheduler's hook: while
    the predicate holds (the device is still chewing on earlier batches),
    the collector waits for a *full* batch instead of flushing at the
    deadline — queued requests lose no latency (the device couldn't start
    them anyway) and the batch that is dispatched ahead carries no padding.
    The moment the predicate flips (device idle — see ``kick()``), the
    deadline policy resumes and a partial batch flushes immediately.

    ``form`` is ``next_batch`` for the server: it leaves the vectors
    unstacked (stacking is staging work) and says which batch it closed
    (``seq``), why (``flush``), and how long its requests queued
    (``wait_s``). Its waits are the stager spans ``serve.wait_empty``,
    ``serve.hold_full`` and ``serve.wait_deadline``.
    """

    def __init__(self, max_batch: int = 32, deadline_ms: float = 2.0):
        self.max_batch = max_batch
        self.deadline = deadline_ms / 1e3
        self._items: deque = deque()
        self._cv = threading.Condition()
        self._seq = 0     # number of the next batch to close

    def submit(self, qvec: np.ndarray,
               deadline: float | None = None) -> "Reply":
        reply = Reply(deadline=deadline, submitted_at=time.perf_counter())
        with self._cv:
            self._items.append((qvec, reply))
            self._cv.notify_all()
        return reply

    def kick(self) -> None:
        """Wake every waiter so it re-evaluates its predicate (called on
        server close and whenever the device drains to idle)."""
        with self._cv:
            self._cv.notify_all()

    def drain(self) -> list:
        """Remove and return every pending (vec, reply) pair — used to
        fail-fast outstanding requests when a worker thread dies."""
        with self._cv:
            items = list(self._items)
            self._items.clear()
        return items

    def empty(self) -> bool:
        with self._cv:
            return not self._items

    @property
    def next_seq(self) -> int:
        """Number the next batch will close under."""
        with self._cv:
            return self._seq

    def next_batch(self, timeout: float = 30.0,
                   stop: threading.Event | None = None,
                   want_full=None) -> tuple[np.ndarray, list] | None:
        formed = self.form(timeout, stop, want_full)
        if formed is None:
            return None
        return np.stack(formed.vecs), formed.replies

    def form(self, timeout: float = 30.0,
             stop: threading.Event | None = None,
             want_full=None) -> Formed | None:
        """``next_batch``, with the batch's number, flush reason and
        queue wait, and its vectors left unstacked."""
        with self._cv:
            seq = self._seq
            with TraceAnnotation("serve.wait_empty", batch=seq):
                ready = self._cv.wait_for(
                    lambda: self._items or (stop is not None
                                            and stop.is_set()),
                    timeout=timeout)
            if not ready or not self._items:
                return None
            flush_at = time.monotonic() + self.deadline
            flush, held, phase, span = "full", False, None, None
            try:
                while len(self._items) < self.max_batch:
                    hold = want_full is not None and want_full()
                    name = ("serve.hold_full" if hold
                            else "serve.wait_deadline")
                    if name != phase:
                        # one span per phase of the batch, not per wakeup
                        if span is not None:
                            span.__exit__(None, None, None)
                        phase, span = name, TraceAnnotation(name, batch=seq)
                        span.__enter__()
                    if hold:
                        # device busy: hold out for a full batch; a kick()
                        # or new submit re-evaluates (1 s backstop vs lost
                        # wakeups)
                        held = True
                        self._cv.wait(timeout=1.0)
                        continue
                    rem = flush_at - time.monotonic()
                    if rem <= 0 or not self._cv.wait(timeout=rem):
                        flush = ("drain" if stop is not None
                                 and stop.is_set()
                                 else "idle" if held else "deadline")
                        break
                items = [self._items.popleft()
                         for _ in range(min(self.max_batch,
                                            len(self._items)))]
                self._seq += 1
                t = time.perf_counter()
                replies = [x[1] for x in items]
                wait_s = sum(t - r.submitted_at for r in replies
                             if r.submitted_at is not None)
                return Formed([x[0] for x in items], replies, seq, flush,
                              wait_s)
            finally:
                # the phase's span covers the batch's close too: the
                # stager's spans leave no gap but the call into staging
                if span is not None:
                    span.__exit__(None, None, None)


class RetrievalServer:
    """Batched query server over a DenseIndex or ShardedDenseIndex.

    Both index types expose ``search``/``search_projected``; the sharded
    one fans the batch out over the mesh and merges per-shard top-k, so the
    server loop is layout-agnostic. With a pruner attached, every batch is
    one fused dispatch (``search_projected``: projection + scale fold +
    scan); without one it falls back to plain ``search``.

    ``pipeline_depth`` >= 2 (default 3) runs the stager/completer pipeline
    with that many batches in flight; <= 1 is the synchronous loop. Every
    executed batch is logged in ``batch_log`` as a ``BatchRecord``: its
    first fields ``(size, t_dispatch, t_done)`` give occupancy and
    worker-side throughput — ``worker_qps`` (queries / busy-span wall time —
    the honest pipelined number, overlap counted once) and ``service_qps``
    (queries / summed per-batch service time — matches the old sync metric,
    but double-counts overlapped seconds when pipelined) — and the rest its
    batch number, queue wait, flush reason, staging and resolve times.
    ``batch_log`` is a deque bounded at ``BATCH_LOG_BOUND`` records (the
    newest kept), so a long-running server does not grow with every batch.

    Spans (``jax.profiler.TraceAnnotation``; they land on the profiler's
    host plane, on the device ops' clock, and cost about a microsecond
    each when no profiler runs). Each carries ``batch=<seq>``, which links
    a batch's stager and completer spans. The stager's spans follow each
    other with no gap, so any moment the device idles falls in one:

    * ``serve.slot_wait`` — waiting for an in-flight slot (pipelined only);
    * ``serve.wait_empty`` — batcher empty, waiting for a first request;
    * ``serve.hold_full`` — device busy, holding out for a full batch;
    * ``serve.wait_deadline`` — collecting until the deadline flush;
    * ``serve.stage`` — stack, pad, host-to-device copy, enqueue of the
      fused search;

    and the completer's (the same thread in the synchronous loop):

    * ``serve.d2h`` — device finish plus the transfer of scores and ids;
    * ``serve.resolve`` — ``batch_log`` append, replies, deadline sweep.

    ``bucket_batches=True`` pads partial batches to the next bucket in
    {8, 16, 32, …, max_batch} instead of always ``max_batch`` — a handful
    of compiled shapes traded for up to 4x less pad compute at low load
    (call ``warmup()`` to pre-compile every bucket).

    ``swap_index`` installs a NEW index (a fresh ``PagedIndex`` after a
    live append or compaction) atomically *between* batches: the worker
    snapshots (index, projection) under a lock per dispatch, so every batch
    runs entirely against one index, and in-flight batches keep the old
    one's arrays alive until their replies post — no reply is dropped or
    computed against a half-swapped state.
    """

    _KEEP = object()   # swap_index sentinel: leave the projection alone

    def __init__(self, index: DenseIndex | ShardedDenseIndex,
                 pruner: StaticPruner | None,
                 k: int = 10, max_batch: int = 32,
                 pipeline_depth: int = 3,
                 bucket_batches: bool = False):
        self.index = index
        self.pruner = pruner
        self.k = k
        self.max_batch = max_batch
        self.bucket_batches = bucket_batches
        caps, c = [], min(8, max_batch)
        while c < max_batch:
            caps.append(c)
            c *= 2
        caps.append(max_batch)
        self._buckets = tuple(caps)
        self.pipeline_depth = max(1, pipeline_depth)
        self.batcher = BatchingQueue(max_batch=max_batch)
        # one BatchRecord per executed batch; appended by the completer,
        # snapshotted by stats readers on other threads
        self.batch_log: deque[BatchRecord] = deque(maxlen=BATCH_LOG_BOUND)
        self._log_lock = threading.Lock()
        self._index_lock = threading.Lock()
        self.swap_count = 0
        self._proj = None
        if pruner is not None:
            W, mean = pruner.projection()
            self._proj = (jnp.asarray(W),
                          None if mean is None else jnp.asarray(mean))
        self._stop = threading.Event()
        self.error: BaseException | None = None   # first worker-thread crash
        # replies submitted with a deadline, swept by the completer: an
        # overdue queued request gets an explicit TimedOut payload instead
        # of parking its client forever behind a hung dispatch
        self._pending_dl: list[Reply] = []
        self._dl_lock = threading.Lock()
        if self.pipeline_depth >= 2:
            # bounded in-flight window. The semaphore gates batch ASSEMBLY,
            # not just dispatch: while every slot is busy, requests keep
            # accumulating in the batcher, so the next batch assembles full
            # instead of greedily draining the queue into padded fragments
            # (which burns compute on pad rows and sinks occupancy).
            self._slots = threading.Semaphore(self.pipeline_depth)
            self._inflight: queue.Queue = queue.Queue()
            self._inflight_n = 0
            self._inflight_lock = threading.Lock()
            self._threads = [
                threading.Thread(target=self._guard, args=(self._stage_loop,),
                                 daemon=True),
                threading.Thread(target=self._guard,
                                 args=(self._complete_loop,), daemon=True)]
        else:
            self._threads = [threading.Thread(target=self._guard,
                                              args=(self._loop,), daemon=True)]
        for t in self._threads:
            t.start()

    def _guard(self, loop):
        """Worker-thread crashes must be loud: record the exception, stop
        the server (so clients' reply timeouts fire instead of hanging
        forever), and unblock the sibling thread."""
        try:
            loop()
        except BaseException as e:   # noqa: BLE001 — survives for reporting
            import traceback
            self.error = e
            self._stop.set()
            self.batcher.kick()
            if self.pipeline_depth >= 2:
                # fail-fast dispatched-but-unposted batches too: their
                # replies would otherwise wait out their full timeout
                while True:
                    try:
                        it = self._inflight.get_nowait()
                    except queue.Empty:
                        break
                    if it is not None:
                        for r in it[2]:
                            r.resolve(e)
                self._inflight.put(None)   # release a blocked completer
            # fail-fast every queued request: clients get the exception
            # immediately instead of waiting out their reply timeout
            for _, reply in self.batcher.drain():
                reply.resolve(e)
            traceback.print_exc()

    def _bucket_for(self, b: int) -> int:
        if not self.bucket_batches:
            return self.max_batch
        for cap in self._buckets:
            if cap >= b:
                return cap
        return self.max_batch

    def _dispatch(self, vecs: np.ndarray):
        """Enqueue one batch's fused search; returns device arrays
        immediately (JAX async dispatch) — the caller decides when to
        block on the transfer back.

        Batches are zero-padded to a FIXED set of compiled shapes — always
        ``max_batch``, or the next bucket in {8, 16, …, max_batch} with
        ``bucket_batches`` — so a novel partial-batch size never
        jit-compiles a fresh full-index scan mid-serve (hundreds of ms of
        compile stampeding the worker exactly when load is ragged). Pad
        rows cost compute but are sliced off before reply; exact-search
        results are row-independent, so real rows are bit-identical to an
        unpadded dispatch.

        The (index, projection) pair is snapshotted under the swap lock:
        the whole batch runs against one consistent index even if
        ``swap_index`` lands mid-flight.

        The padded batch goes from the host straight to where the index
        takes it: every device of a ``ShardedDenseIndex``'s mesh (its
        ``query_sharding``), so no device receives it first and copies it
        on to the rest; any other index gets it on the default device.
        """
        with self._index_lock:
            index, proj = self.index, self._proj
        b = len(vecs)
        cap = self._bucket_for(b)
        if b < cap:
            vecs = np.concatenate(
                [vecs, np.zeros((cap - b, vecs.shape[1]), vecs.dtype)])
        q = jax.device_put(vecs, getattr(index, "query_sharding", None))
        if proj is not None:
            W, mean = proj
            return index.search_projected(q, W, k=self.k, mean=mean)
        return index.search(q, k=self.k)

    def _stage(self, formed: Formed):
        """Stack, pad and enqueue one formed batch inside ``serve.stage``:
        (scores, ids, replies, t_dispatch, record), the record still
        without its completion time."""
        t0 = time.perf_counter()
        with TraceAnnotation("serve.stage", batch=formed.seq):
            scores, ids = self._dispatch_guarded(formed.vecs, formed.replies)
            rec = BatchRecord(len(formed.replies), t0, t0, formed.seq,
                              formed.wait_s, formed.flush,
                              time.perf_counter() - t0, float("nan"))
            return scores, ids, formed.replies, t0, rec

    def _post(self, scores, ids, replies, t0, rec: BatchRecord):
        try:
            with TraceAnnotation("serve.d2h", batch=rec.seq):
                scores = np.asarray(scores)   # blocks on this batch's D2H
                ids = np.asarray(ids)         # (both BEFORE any lock)
        except BaseException as e:
            # a poisoned device result must fail ITS batch's clients, not
            # strand them: resolve in-hand replies before the crash
            # propagates to _guard
            t = time.perf_counter()
            for r in replies:
                r.resolve(e, t)
            raise
        t1 = time.perf_counter()
        with TraceAnnotation("serve.resolve", batch=rec.seq):
            # logged before the replies, so a client that saw its reply
            # sees its batch; the resolve time is filled in once known
            rec = rec._replace(t_done=t1)
            with self._log_lock:
                self.batch_log.append(rec)
            for i, r in enumerate(replies):
                # first-writer-wins: an already-expired reply keeps its
                # TimedOut (and the single slot is never double-filled)
                r.resolve((scores[i], ids[i]), t1)
            self._expire_overdue()
            done = rec._replace(resolve_s=time.perf_counter() - t1)
            with self._log_lock:
                if self.batch_log and self.batch_log[-1] is rec:
                    self.batch_log[-1] = done

    # -- deadline expiry ----------------------------------------------------
    def _dl_poll(self) -> float:
        """Completer wait quantum: fine-grained while deadlines are
        pending, coarse (but bounded — a hung stager must not be able to
        park the sweep forever) when none are."""
        with self._dl_lock:
            pending = bool(self._pending_dl)
        return 0.05 if pending else 0.5

    def _expire_overdue(self) -> None:
        """Resolve every overdue pending reply with TimedOut. Replies are
        collected under the deadline lock but resolved OUTSIDE it — reply
        delivery never runs under a server lock."""
        now = time.perf_counter()
        with self._dl_lock:
            live = [r for r in self._pending_dl if not r.done]
            due = [r for r in live if r.deadline <= now]
            self._pending_dl = [r for r in live if r.deadline > now]
        for r in due:
            r.resolve(TimedOut(
                f"reply deadline exceeded ({now - r.deadline:.3f}s overdue) "
                f"— batch never posted"), now)

    def swap_index(self, index, pruner=_KEEP) -> None:
        """Atomically install a new index for future batches.

        Runs between batches by construction: ``_dispatch`` snapshots
        (index, projection) under the same lock, in-flight batches hold
        references to the old arrays, and the completer drains them
        normally — accepted work is never dropped and no batch ever sees a
        half-swapped state. Pass ``pruner`` to atomically replace the
        query projection too (a refit changed ``W_m``); by default the
        existing projection is kept (appends/compaction never change it).
        """
        proj = None
        if pruner is not self._KEEP and pruner is not None:
            W, mean = pruner.projection()
            # device transfers stay OUTSIDE the lock: a dispatch snapshot
            # must never wait on an H2D copy
            proj = (jnp.asarray(W),
                    None if mean is None else jnp.asarray(mean))
        with self._index_lock:
            if pruner is self._KEEP:
                proj = self._proj
            self.index = index
            self._proj = proj
            self.swap_count += 1

    def warmup(self) -> None:
        """Compile every dispatch shape (each bucket) before taking load —
        without this, the first partial batch of each bucket size pays its
        compile mid-serve."""
        d = self._query_dim()
        caps = self._buckets if self.bucket_batches else (self.max_batch,)
        for cap in caps:
            jax.block_until_ready(
                self._dispatch(np.zeros((cap, d), np.float32)))

    # -- synchronous worker (pipeline_depth <= 1) ---------------------------
    def _loop(self):
        # deadline expiry here is opportunistic (between batches): with one
        # thread, a dispatch that hangs also hangs the sweep — prompt
        # in-hang expiry needs pipeline_depth >= 2 (completer-side sweep)
        while not (self._stop.is_set() and self.batcher.empty()):
            formed = self.batcher.form(stop=self._stop,
                                       timeout=self._dl_poll())
            if formed is None:
                self._expire_overdue()     # _post sweeps after a batch
                continue
            self._post(*self._stage(formed))

    def _dispatch_guarded(self, vecs, replies):
        """_dispatch of the stacked ``vecs``, but a crash resolves the
        in-hand batch's replies with the exception before propagating to
        _guard — the batch being assembled is accepted work, and accepted
        work never silently strands its clients."""
        try:
            return self._dispatch(np.stack(vecs))
        except BaseException as e:
            t = time.perf_counter()
            for r in replies:
                r.resolve(e, t)
            raise

    # -- pipelined worker (stager + completer) ------------------------------
    def _busy(self) -> bool:
        """True while earlier batches are still in flight (and we are not
        draining): the stager should then hold out for a full batch."""
        return self._inflight_n > 0 and not self._stop.is_set()

    def _stage_loop(self):
        while not ((self._stop.is_set() and self.batcher.empty())
                   or self.error is not None):
            with TraceAnnotation("serve.slot_wait",
                                 batch=self.batcher.next_seq):
                got = self._slots.acquire(timeout=0.2)
            if not got:
                continue                           # re-check stop, try again
            formed = self.batcher.form(stop=self._stop, want_full=self._busy)
            if formed is None:
                self._slots.release()
                continue
            staged = self._stage(formed)           # async — does not block
            with self._inflight_lock:
                self._inflight_n += 1
            self._inflight.put(staged)
        self._inflight.put(None)                   # drain sentinel

    def _complete_loop(self):
        while True:
            try:
                item = self._inflight.get(timeout=self._dl_poll())
            except queue.Empty:
                # nothing posted within the quantum: sweep overdue
                # deadlines — this is what un-wedges clients of a HUNG
                # dispatch (the stager is parked inside the device call,
                # but their deadlines still fire here)
                self._expire_overdue()
                continue
            if item is None:
                return
            self._post(*item)
            with self._inflight_lock:
                self._inflight_n -= 1
                idle = self._inflight_n == 0
            self._slots.release()
            if idle:
                self.batcher.kick()   # device drained: flush partial batches

    def _query_dim(self) -> int:
        """Expected query dimensionality, from a CONSISTENT (index, proj)
        snapshot: a concurrent ``swap_index(..., pruner=...)`` must not be
        observed half-applied (old projection, new index)."""
        with self._index_lock:
            index, proj = self.index, self._proj
        return proj[0].shape[0] if proj is not None else index.dim

    # -- client API ---------------------------------------------------------
    def submit(self, qvec: np.ndarray,
               deadline: float | None = None) -> "Reply":
        """Open-loop entry: enqueue a query, return its reply queue.

        The shape is validated here, synchronously: a malformed vector must
        fail its submitter, not poison a whole batch inside the worker.
        ``deadline`` (relative seconds) arms completer-side expiry: if the
        batch has not posted by then, the reply resolves to ``TimedOut``
        instead of parking its client behind a hung dispatch. Submitting to
        an already-crashed server raises immediately.
        """
        qvec = np.asarray(qvec)
        if self.error is not None:
            raise RuntimeError("server worker failed") from self.error
        want = self._query_dim()
        if qvec.shape != (want,):
            raise ValueError(f"query must have shape ({want},), "
                             f"got {qvec.shape}")
        abs_dl = (None if deadline is None
                  else time.perf_counter() + deadline)
        reply = self.batcher.submit(qvec, deadline=abs_dl)
        if abs_dl is not None:
            with self._dl_lock:
                self._pending_dl.append(reply)
        if self.error is not None:
            # the worker died between the check above and the enqueue: the
            # batcher drain already ran, so fail this reply directly
            reply.resolve(self.error)
        return reply

    def query(self, qvec: np.ndarray, timeout: float = 10.0,
              deadline: float | None = None):
        out = self.submit(qvec, deadline=deadline).get(timeout=timeout)
        if isinstance(out, TimedOut):
            raise out
        if isinstance(out, BaseException):
            raise RuntimeError("server worker failed") from out
        return out

    def reset_stats(self) -> None:
        """Drop the batch log (e.g. after a warmup query) so stats reflect
        steady state only."""
        with self._log_lock:
            self.batch_log.clear()

    def worker_stats(self) -> dict:
        """Occupancy + worker-side throughput from the executed batches,
        and from ``BatchRecord`` rows: mean queue wait per request
        (``wait_ms``), mean staging, in-flight (end of staging to results
        on the host: ``d2h_ms``) and resolve ms per batch, and the count of
        each flush reason. Rows are read by index: a plain
        ``(size, t_dispatch, t_done)`` log gets the first five keys."""
        with self._log_lock:
            log = list(self.batch_log)
        if not log:
            return dict(batches=0, mean_batch=0.0, occupancy=0.0,
                        worker_qps=0.0, service_qps=0.0, wait_ms=0.0,
                        stage_ms=0.0, d2h_ms=0.0, resolve_ms=0.0,
                        flushes=dict.fromkeys(FLUSHES, 0))
        sizes = np.array([b[0] for b in log], dtype=np.float64)
        t0s = np.array([b[1] for b in log], dtype=np.float64)
        t1s = np.array([b[2] for b in log], dtype=np.float64)
        span = float(t1s.max() - t0s.min())
        busy = float((t1s - t0s).sum())
        out = dict(batches=len(log),
                   mean_batch=float(sizes.mean()),
                   occupancy=float(sizes.mean() / self.max_batch),
                   worker_qps=float(sizes.sum() / max(span, 1e-9)),
                   service_qps=float(sizes.sum() / max(busy, 1e-9)))
        if len(log[0]) > 3:
            stage = np.array([b[6] for b in log])
            out.update(
                wait_ms=1e3 * sum(b[4] for b in log) / sizes.sum(),
                stage_ms=1e3 * float(stage.mean()),
                d2h_ms=1e3 * float((t1s - t0s - stage).mean()),
                # the newest record's resolve time may still be running
                resolve_ms=1e3 * float(np.mean(
                    [b[7] for b in log if not np.isnan(b[7])] or [0.0])),
                flushes={f: sum(b[5] == f for b in log) for f in FLUSHES})
        return out

    def close(self):
        """Stop accepting work *after* draining: every already-submitted
        request is batched, executed, and replied to before the threads
        exit (pinned by tests/test_serve_pipeline.py)."""
        self._stop.set()
        self.batcher.kick()
        for t in self._threads:
            t.join(timeout=60.0)


def _worker_times(stats: dict) -> str:
    """The ``[serve] worker:`` line of where a batch's time went."""
    flushes = " ".join(f"{f}={n}" for f, n in stats["flushes"].items())
    return (f"[serve] worker: queue wait {stats['wait_ms']:.2f} ms/query, "
            f"stage {stats['stage_ms']:.2f} ms, d2h "
            f"{stats['d2h_ms']:.2f} ms, resolve {stats['resolve_ms']:.2f} "
            f"ms per batch; flushes {flushes}")


def _serve_mesh(ndev: int, merge: str):
    """1-D mesh for the flat merge; the squarest 2-D factoring for the
    hierarchical one (a 1-long second axis degenerates to flat anyway)."""
    if merge == "hierarchical":
        a = next(d for d in range(int(ndev ** 0.5), 0, -1) if ndev % d == 0)
        if a > 1:
            return jax.make_mesh((a, ndev // a), ("row", "col"))
    return jax.make_mesh((ndev,), ("data",))


def _drive(server: RetrievalServer, Q: np.ndarray) -> tuple[float, np.ndarray]:
    """Issue every query in array order; (wall seconds, per-query latency s).

    Both sides of ``--compare-full`` go through this, so the query order,
    count, and batching pattern are identical — speedups are apples to
    apples. One untimed warmup query absorbs compilation; its batch is
    dropped from the worker log so occupancy/worker-qps reflect steady
    state, matching the client-side numbers.
    """
    server.query(Q[0])
    server.reset_stats()
    lat = np.empty(len(Q))
    t0 = time.perf_counter()
    for i in range(len(Q)):
        t = time.perf_counter()
        server.query(Q[i])
        lat[i] = time.perf_counter() - t
    return time.perf_counter() - t0, lat


def _lat_summary(lat_s: np.ndarray) -> dict:
    ms = np.asarray(lat_s) * 1e3
    return dict(p50_ms=float(np.percentile(ms, 50)),
                p95_ms=float(np.percentile(ms, 95)),
                p99_ms=float(np.percentile(ms, 99)),
                mean_ms=float(ms.mean()))


def _drive_open(server: RetrievalServer, Q: np.ndarray, rate: float,
                seed: int = 0, collect: bool = False,
                tolerate_errors: bool = False,
                deadline: float | None = None) -> dict:
    """Open-loop load: Poisson arrivals at ``rate`` qps, independent of
    completions.

    A closed loop (``_drive``) can never overrun the server — each query
    waits for the last — so it measures latency at trivial concurrency. An
    open loop submits on the arrival process a real fleet generates,
    exposing queueing and letting the pipeline actually fill. Latency is
    measured from each query's *scheduled* arrival (not the submit call),
    so submitter lag counts against the server, never for it (no
    coordinated omission), and ends at the reply's ``completed_at`` stamp
    posted by the completer — not at the collector's own clock — so
    out-of-FIFO completions (priorities, swaps) measure correctly. One
    warmup query absorbs compilation.

    Returns achieved/offered qps, p50/p95/p99 latency, and — with
    ``collect`` — the per-query (scores, ids) in submission order, used by
    the bench's sync-vs-pipelined bit-identity check.

    ``tolerate_errors`` is the fault-injection mode (the fleet soak): an
    exception payload (Shed, TimedOut, a replica crash) or a submit-time
    rejection counts in ``errors`` instead of failing the drive, and
    latency percentiles cover the successful replies only —
    ``n_ok``/``errors`` make the split explicit. ``deadline`` (relative
    seconds) is forwarded to every submit. Any target duck-typing
    ``submit``/``query``/``reset_stats`` (a ``Router``) drives the same
    way a single server does.
    """
    rng = np.random.default_rng(seed)
    server.query(Q[0])
    server.reset_stats()
    n = len(Q)
    gaps = rng.exponential(1.0 / rate, size=n)
    lat = np.full(n, np.nan)
    results: list = [None] * n if collect else None
    handoff: queue.Queue = queue.Queue()
    done = threading.Event()
    errors: list = []
    fails: list = []

    def collector():
        # per-reply timeout: a dead worker thread must fail this drive
        # loudly (CI would otherwise hang to its job timeout), not wedge it
        try:
            for _ in range(n):
                i, reply, t_arr = handoff.get()
                if isinstance(reply, BaseException):   # rejected at submit
                    fails.append((i, reply))
                    continue
                out = reply.get(timeout=120.0)
                if isinstance(out, BaseException):
                    if tolerate_errors:
                        fails.append((i, out))
                        continue
                    raise out
                t_done = getattr(reply, "completed_at", None)
                lat[i] = (t_done if t_done is not None
                          else time.perf_counter()) - t_arr
                if collect:
                    results[i] = out
        except BaseException as e:   # noqa: BLE001 — must reach the driver
            errors.append(e)
        finally:
            done.set()

    th = threading.Thread(target=collector, daemon=True)
    th.start()
    t_start = time.perf_counter()
    t_next = t_start
    for i in range(n):
        t_next += gaps[i]
        delay = t_next - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        try:
            reply = server.submit(Q[i], deadline=deadline) \
                if deadline is not None else server.submit(Q[i])
        except Exception as e:
            if not tolerate_errors:
                done.set()
                raise
            reply = e
        handoff.put((i, reply, t_next))
    done.wait()
    if errors:
        raise RuntimeError(
            "open-loop drive failed: a reply never arrived (worker thread "
            "dead?)") from errors[0]
    wall = time.perf_counter() - t_start
    ok = lat[~np.isnan(lat)]
    out = dict(offered_qps=float(rate), achieved_qps=float(n / wall),
               wall_s=float(wall), n=int(n), n_ok=int(ok.size),
               errors=len(fails),
               **_lat_summary(ok if ok.size else np.array([np.inf])))
    if collect:
        out["results"] = results
    return out


def _serve_fleet(args) -> None:
    """--fleet path: R replicas behind a Router, driven open-loop; with
    --fleet-kill, a kill/restart fault plan runs mid-drive and the
    droplessness/misroute invariants are reported."""
    import tempfile

    # deferred: repro.serving.fleet imports this module
    from repro.serving.fleet import FaultEvent, FaultPlan, ReplicaSet

    if args.load_index:
        store_path, ctx = args.load_index, None
        src_d = int(IndexStore.open(store_path).meta.get("source_dim",
                                                         args.dim))
        if src_d != args.dim:
            print(f"[serve] store was fit at d={src_d}; overriding --dim")
            args.dim = src_d
    else:
        ctx = None if args.save_index else tempfile.TemporaryDirectory()
        store_path = args.save_index or (ctx.name + "/fleet-store")
        print(f"[serve] building corpus n={args.n_docs} d={args.dim}")
        ds = make_dataset("tasb", n_docs=args.n_docs, d=args.dim,
                          query_sets=("dl19",))
        pruner = StaticPruner(cutoff=args.cutoff).fit(jnp.asarray(ds.docs))
        st = save_index(store_path, pruner.build_index(jnp.asarray(ds.docs)),
                        pruner=pruner)
        print(f"[serve] artifact: {store_path} "
              f"({st.nbytes/2**20:.1f} MiB, n={st.n})")
    ds = make_dataset("tasb", n_docs=256, d=args.dim, query_sets=("dl19",))
    Q = np.asarray(ds.queries["dl19"])
    Q = np.tile(Q, (max(1, args.queries // len(Q) + 1), 1))[:args.queries]

    rate = args.open_loop if args.open_loop > 0 else 200.0
    fleet = ReplicaSet(store_path, replicas=args.fleet, k=args.k,
                       max_batch=args.batch,
                       pipeline_depth=args.pipeline_depth,
                       backend=args.backend, probe_queries=Q[:16])
    try:
        print(f"[serve] fleet: {args.fleet} replicas, open loop @ "
              f"{rate:.0f} qps, {len(Q)} queries")
        if args.fleet_kill > 0:
            FaultPlan([FaultEvent(args.fleet_kill, "kill", "r1"),
                       FaultEvent(args.fleet_kill + 2.0, "restart", "r1")]
                      ).start(fleet)
            print(f"[serve] fault plan: kill r1 @ {args.fleet_kill:.1f}s, "
                  f"restart @ {args.fleet_kill + 2.0:.1f}s")
        res = _drive_open(fleet, Q, rate=rate, tolerate_errors=True,
                          deadline=2.0)
        stats = fleet.stats()
        health = fleet.health()
        print(f"[serve] fleet drive: {res['achieved_qps']:.1f} qps achieved "
              f"({res['n_ok']}/{res['n']} ok)  p50={res['p50_ms']:.2f}ms "
              f"p95={res['p95_ms']:.2f}ms p99={res['p99_ms']:.2f}ms")
        print(f"[serve] fleet accounting: accepted={stats['accepted']} "
              f"completed={stats['completed']} shed={stats['shed']} "
              f"timed_out={stats['timed_out']} failed={stats['failed']} "
              f"failovers={stats['failovers']} "
              f"lost_accepted={stats['lost_accepted']}")
        states = ", ".join(f"{name}={rep['state']}"
                           for name, rep in health["replicas"].items())
        print(f"[serve] fleet health: "
              f"{'ok' if health['ok'] else 'DEGRADED'} ({states})")
    finally:
        fleet.close()
        if ctx is not None:
            ctx.cleanup()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-docs", type=int, default=50000)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--cutoff", type=float, default=0.5)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--pipeline-depth", type=int, default=3,
                    help="max batches in flight (stager/completer overlap); "
                         "<=1 runs the legacy synchronous worker loop")
    ap.add_argument("--bucket-batches", action="store_true",
                    help="pad partial batches to the next bucket in "
                         "{8,16,...,max_batch} instead of always max_batch "
                         "(less pad compute at low load, a few more "
                         "compiles)")
    ap.add_argument("--live-append", type=float, default=0.0,
                    metavar="ROWS_PER_S",
                    help="serve through a PagedIndex and append "
                         "synthetic documents at this rate during the "
                         "drive — every append swaps a fresh index into "
                         "the running server (then compacts at the end)")
    ap.add_argument("--delta-capacity", type=int, default=4096,
                    help="rows at which a paged delta extent seals (the "
                         "bound on a scale-widening requantisation)")
    ap.add_argument("--open-loop", type=float, default=0.0, metavar="QPS",
                    help="additionally drive Poisson arrivals at QPS "
                         "(open loop: submissions never wait on replies) "
                         "and report p50/p95/p99 under that load")
    ap.add_argument("--compare-full", action="store_true")
    ap.add_argument("--sharded", action="store_true",
                    help="row-shard the index over a mesh of every device")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force an N-way host-platform mesh via XLA_FLAGS "
                         "(default: 4 when --sharded; no-op on non-CPU "
                         "platforms or once JAX is initialised)")
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp",
                    help="scan backend for the (per-shard) score+top-k")
    ap.add_argument("--merge", choices=("flat", "hierarchical"),
                    default="flat",
                    help="sharded candidate merge: one all-gather over "
                         "every device, or two stages over a factored mesh")
    ap.add_argument("--quantize-int8", action="store_true")
    ap.add_argument("--page-rows", type=int, default=0, metavar="R",
                    help="rows per page (default: 256, or the artifact's "
                         "page geometry under --load-index)")
    ap.add_argument("--page-pool", type=int, default=0, metavar="P",
                    help="serve through a PagedIndex whose "
                         "device-resident page pool is capped at P pages; "
                         "overflow pages stay host-side and stream "
                         "through the DMA pipeline on demand "
                         "(default: everything resident)")
    ap.add_argument("--cascade", default=None, metavar="M:N",
                    help="serve a two-resolution cascade: coarse scan over "
                         "the first M PCA dims (int8) keeps N*k candidates "
                         "per query, then one exact full-m rescore of the "
                         "shortlist (e.g. 64:8)")
    ap.add_argument("--fleet", type=int, default=0, metavar="R",
                    help="serve through a replicated fleet of R servers "
                         "behind a load-aware router (admission control, "
                         "retry-with-failover, health-gated maintenance) "
                         "instead of one bare server")
    ap.add_argument("--fleet-kill", type=float, default=0.0, metavar="SEC",
                    help="with --fleet: kill replica r1 SEC seconds into "
                         "the drive and restart it 2s later — prints the "
                         "droplessness/misroute accounting the chaos soak "
                         "asserts")
    ap.add_argument("--save-index", default=None, metavar="DIR",
                    help="persist the built artifact (PCA state + pruned "
                         "vectors + int8 scale) to DIR for later "
                         "--load-index restarts")
    ap.add_argument("--load-index", default=None, metavar="DIR",
                    help="serve from an on-disk artifact: skips the PCA "
                         "refit and index rebuild entirely (the paper's "
                         "offline/online split, made real)")
    args = ap.parse_args()
    if args.save_index and args.load_index:
        ap.error("--save-index and --load-index are mutually exclusive")
    for flag, on in (("--live-append", args.live_append > 0),
                     ("--page-pool", args.page_pool > 0)):
        if on and args.sharded:
            ap.error(f"{flag} does not compose with --sharded yet "
                     f"(paged per-shard pools: see ROADMAP)")
    paged = args.live_append > 0 or args.page_pool > 0
    page_rows = args.page_rows or 256
    pool_pages = args.page_pool or None
    cascade_mn = None
    if args.cascade:
        if args.sharded:
            ap.error("--cascade does not compose with --sharded yet "
                     "(sharded base rescore: see ROADMAP)")
        try:
            mc_s, nf_s = args.cascade.split(":")
            cascade_mn = (int(mc_s), int(nf_s))
        except ValueError:
            ap.error(f"--cascade wants M:N (e.g. 64:8), got {args.cascade!r}")
        if cascade_mn[0] < 1 or cascade_mn[1] < 1:
            ap.error("--cascade M and N must both be >= 1")

    force_host_device_count(args.host_devices or (4 if args.sharded else 0))
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())

    if args.fleet > 0:
        if args.sharded or args.cascade or paged:
            ap.error("--fleet composes with the single-node flat index only "
                     "(sharded/cascade fleet replicas: see ROADMAP)")
        _serve_fleet(args)
        return

    if args.load_index:
        # peek at the artifact for the query dimensionality, synthesise the
        # query stream, then time the restart proper: open+validate, load,
        # first answered query — the same span as the perf sweep's
        # cold_start row (the peek costs one extra validate, ~ms)
        src_d = int(IndexStore.open(args.load_index).meta.get("source_dim",
                                                             args.dim))
        if src_d != args.dim:
            print(f"[serve] store was fit at d={src_d}; overriding --dim")
            args.dim = src_d
        # a tiny corpus is enough to synthesise the query stream — the
        # served docs come from the artifact, not from here
        ds = make_dataset("tasb", n_docs=256, d=args.dim,
                          query_sets=("dl19",))
        Q = np.asarray(ds.queries["dl19"])
        Q = np.tile(Q, (max(1, args.queries // len(Q) + 1), 1))[:args.queries]

        t_cold = time.perf_counter()
        store = IndexStore.open(args.load_index)
        pruner = store.load_pruner()
        if args.sharded:
            mesh = _serve_mesh(jax.device_count(), args.merge)
            index = ShardedDenseIndex.load(store, mesh,
                                           backend=args.backend,
                                           merge=args.merge)
            print(f"[serve] loaded sharded index: {index.n} x {index.dim} "
                  f"over mesh "
                  f"{dict(zip(mesh.axis_names, mesh.devices.shape))} "
                  f"({index.nbytes/2**20:.1f} MiB, backend={args.backend}, "
                  f"merge={args.merge})")
        elif cascade_mn:
            index = CascadeIndex.load(store, m_coarse=cascade_mn[0],
                                      n_factor=cascade_mn[1],
                                      backend=args.backend,
                                      page_rows=args.page_rows or None,
                                      pool_pages=pool_pages)
            if paged and isinstance(index.full, DenseIndex):
                index = index.paged(page_rows=page_rows,
                                    pool_pages=pool_pages,
                                    seal_rows=args.delta_capacity)
            tag = ", paged" if isinstance(index.full, PagedIndex) else ""
            print(f"[serve] loaded cascade: {index.n} x {index.dim} "
                  f"(+ coarse m={index.m_coarse}, shortlist "
                  f"{index.n_factor}*k, {index.nbytes/2**20:.1f} MiB{tag})")
        elif paged or "paged" in store.manifest:
            index = PagedIndex.load(store, backend=args.backend,
                                    page_rows=args.page_rows or None,
                                    pool_pages=pool_pages,
                                    seal_rows=args.delta_capacity)
            stg = index.storage
            print(f"[serve] loaded paged index: {index.n} x {index.dim} "
                  f"({index.nbytes/2**20:.1f} MiB, {stg.n_slots} pages "
                  f"x {stg.page_rows} rows, {stg.n_host_pages} host-tier)")
        else:
            index = DenseIndex.load(store, backend=args.backend)
            print(f"[serve] loaded index: {index.n} x {index.dim} "
                  f"({index.nbytes/2**20:.1f} MiB, "
                  f"dtype={index.vectors.dtype})")
        server = RetrievalServer(index, pruner, k=args.k,
                                 max_batch=args.batch,
                                 pipeline_depth=args.pipeline_depth,
                                 bucket_batches=args.bucket_batches)
        server.query(Q[0])   # first answered query closes the cold start
        print(f"[serve] cold start (open store -> first query): "
              f"{(time.perf_counter() - t_cold)*1e3:.1f}ms")
        server.reset_stats()
    else:
        print(f"[serve] building corpus n={args.n_docs} d={args.dim}")
        ds = make_dataset("tasb", n_docs=args.n_docs, d=args.dim,
                          query_sets=("dl19",))
        D = jnp.asarray(ds.docs)
        Q = np.asarray(ds.queries["dl19"])
        Q = np.tile(Q, (max(1, args.queries // len(Q) + 1), 1))[:args.queries]

        pruner = StaticPruner(cutoff=args.cutoff).fit(D)
        pruned = pruner.prune_index(D)
        if args.sharded:
            ndev = jax.device_count()
            mesh = _serve_mesh(ndev, args.merge)
            index = ShardedDenseIndex.build(pruned, mesh,
                                            quantize_int8=args.quantize_int8,
                                            backend=args.backend,
                                            merge=args.merge)
            print(f"[serve] sharded index: {index.n} x {index.dim} over "
                  f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
                  f"({index.nbytes/2**20:.1f} MiB, backend={args.backend}, "
                  f"merge={args.merge})")
        elif cascade_mn:
            index = CascadeIndex.build(pruned, m_coarse=cascade_mn[0],
                                       n_factor=cascade_mn[1],
                                       quantize_int8=args.quantize_int8,
                                       backend=args.backend)
            if paged:
                index = index.paged(page_rows=page_rows,
                                    pool_pages=pool_pages,
                                    seal_rows=args.delta_capacity)
            print(f"[serve] cascade index: {index.n} x {index.dim} "
                  f"(+ coarse m={index.m_coarse} int8, shortlist "
                  f"{index.n_factor}*k, {index.nbytes/2**20:.1f} MiB"
                  f"{', paged' if paged else ''})")
        elif paged:
            base = DenseIndex.build(pruned, quantize_int8=args.quantize_int8)
            index = PagedIndex.from_index(base, page_rows=page_rows,
                                          pool_pages=pool_pages,
                                          seal_rows=args.delta_capacity,
                                          backend=args.backend)
            stg = index.storage
            print(f"[serve] paged index: {index.n} x {index.dim} "
                  f"({index.nbytes/2**20:.1f} MiB, {stg.n_slots} pages "
                  f"x {stg.page_rows} rows, {stg.n_host_pages} host-tier)")
        else:
            index = DenseIndex.build(pruned, quantize_int8=args.quantize_int8,
                                     backend=args.backend)
            print(f"[serve] pruned index: {index.n} x {index.dim} "
                  f"({index.nbytes/2**20:.1f} MiB)")
        if args.save_index:
            st = save_index(args.save_index, index, pruner=pruner)
            print(f"[serve] saved artifact: {args.save_index} "
                  f"({st.nbytes/2**20:.1f} MiB on disk, n={st.n})")

        server = RetrievalServer(index, pruner, k=args.k, max_batch=args.batch,
                                 pipeline_depth=args.pipeline_depth,
                                 bucket_batches=args.bucket_batches)

    updater = None
    cascade_app = None
    append_stop = threading.Event()
    appender = None
    if args.live_append > 0 and cascade_mn:
        # CascadeIndex is copy-on-write: append grows BOTH resolutions and
        # swap_index installs the consistent pair atomically. IndexUpdater
        # holds one PagedIndex, so the cascade drives the same
        # swap-between-batches discipline directly; only this thread ever
        # rebinds the local, so no extra lock is needed. (The index is
        # already paged: --live-append selects paged storage.)
        rng_app = np.random.default_rng(123)
        app_block = 64
        cascade_app = {"rows": 0, "index": index}

        def _appender():
            cas = cascade_app["index"]
            while not append_stop.is_set():
                t0 = time.perf_counter()
                block = jnp.asarray(
                    rng_app.standard_normal((app_block, args.dim))
                    .astype(np.float32))
                cas = cas.append(pruner.prune_index(block))
                server.swap_index(cas)
                cascade_app["rows"] += app_block
                cascade_app["index"] = cas
                delay = (app_block / args.live_append
                         - (time.perf_counter() - t0))
                if delay > 0:
                    append_stop.wait(delay)

        appender = threading.Thread(target=_appender, daemon=True)
        print(f"[serve] live-append (cascade): {args.live_append:.0f} "
              f"rows/s (blocks of {app_block}, seal rows "
              f"{args.delta_capacity})")
        appender.start()
    elif args.live_append > 0:
        from repro.core.maintenance import IndexUpdater
        updater = IndexUpdater(pruner=pruner, index=index, server=server,
                               delta_capacity=args.delta_capacity)
        rng_app = np.random.default_rng(123)
        app_block = 64

        def _appender():
            while not append_stop.is_set():
                t0 = time.perf_counter()
                updater.add_documents(jnp.asarray(
                    rng_app.standard_normal((app_block, args.dim))
                    .astype(np.float32)))
                delay = (app_block / args.live_append
                         - (time.perf_counter() - t0))
                if delay > 0:
                    append_stop.wait(delay)

        appender = threading.Thread(target=_appender, daemon=True)
        print(f"[serve] live-append: {args.live_append:.0f} rows/s "
              f"(blocks of {app_block}, seal rows "
              f"{args.delta_capacity})")
        appender.start()

    if args.bucket_batches:
        # pre-compile every bucket shape: without this the first partial
        # batch of each size pays its compile mid-drive — the exact
        # stampede bucketing exists to avoid
        server.warmup()

    wall, lat = _drive(server, Q)
    stats = server.worker_stats()
    lat_ms = lat * 1e3
    mode = ("pipelined" if args.pipeline_depth >= 2 else "sync")
    print(f"[serve] pruned ({mode}): {args.queries / wall:.1f} qps  "
          f"p50={np.percentile(lat_ms, 50):.2f}ms "
          f"p99={np.percentile(lat_ms, 99):.2f}ms")
    print(f"[serve] worker: {stats['worker_qps']:.1f} qps span "
          f"({stats['service_qps']:.1f} qps service) over "
          f"{stats['batches']} batches, mean batch "
          f"{stats['mean_batch']:.1f}/{args.batch} "
          f"({stats['occupancy']*100:.0f}% occupancy)")
    print(_worker_times(stats))

    if args.open_loop > 0:
        res = _drive_open(server, Q, rate=args.open_loop)
        ostats = server.worker_stats()
        print(f"[serve] open-loop @ {args.open_loop:.0f} qps offered: "
              f"{res['achieved_qps']:.1f} qps achieved  "
              f"p50={res['p50_ms']:.2f}ms p95={res['p95_ms']:.2f}ms "
              f"p99={res['p99_ms']:.2f}ms  "
              f"worker={ostats['worker_qps']:.1f} qps "
              f"({ostats['occupancy']*100:.0f}% occupancy)")
        print(_worker_times(ostats))

    def _delta_units(idx) -> str:
        n_ext = sum(1 for e in idx.storage.extents if e.kind == "delta")
        return f"{idx.storage.delta_pages} delta page(s), {n_ext} extent(s)"

    if cascade_app is not None:
        append_stop.set()
        appender.join(timeout=30.0)
        cas = cascade_app["index"]
        print(f"[serve] live-append (cascade): +{cascade_app['rows']} rows "
              f"in {_delta_units(cas.full)} per resolution, "
              f"{server.swap_count} atomic swaps; index now {cas.n} rows "
              f"(both resolutions)")
    if updater is not None:
        append_stop.set()
        appender.join(timeout=30.0)
        print(f"[serve] live-append: +{updater.appended_rows} rows in "
              f"{_delta_units(updater.index)}, "
              f"{server.swap_count} atomic swaps; index now "
              f"{updater.index.n} rows")
        t0 = time.perf_counter()
        updater.compact()
        dt_ms = (time.perf_counter() - t0) * 1e3
        lc = updater.last_compaction
        print(f"[serve] compaction: {lc['pages_moved']} pages moved, "
              f"{lc['pages_freed']} freed, {lc['pages_host']} host-tier, "
              f"in {dt_ms:.0f}ms — pointer swaps, no rebuild; server "
              f"swapped mid-serve (swap #{server.swap_count})")
    server.close()

    if args.compare_full and args.load_index:
        print("[serve] --compare-full needs the raw corpus; skipped under "
              "--load-index")
    elif args.compare_full:
        full = DenseIndex.build(D)
        server2 = RetrievalServer(full, None, k=args.k, max_batch=args.batch,
                                  pipeline_depth=args.pipeline_depth)
        wall_full, _ = _drive(server2, Q)   # identical query order/batching
        server2.close()
        print(f"[serve] full:   {args.queries / wall_full:.1f} qps  "
              f"speedup={wall_full / wall:.2f}x "
              f"(O(d/m) predicts {args.dim / pruner.kept_dims:.2f}x)")


if __name__ == "__main__":
    main()
