"""Perf — the paper's §2 complexity claims, measured, plus the serving sweep.

  * query scoring time O(dn) -> O(dm + mn): wall-clock speedup vs d/m
  * index bytes O(dn) -> O(mn) (+ md for W_m)
  * serving sweep {backend x dtype x layout x merge}: us/call, qps, bytes,
    recall@10 per config — the trajectory ``BENCH_perf.json`` tracks PR
    over PR (written by ``benchmarks.run``)
  * select-path A/B: the two-stage + block-skip ``_scan_topk`` against the
    legacy concat-and-full-top_k select on the same corpus
  * paged: paged serve qps with and without live appends, the Pallas
    DMA pipeline depth sweep (resident vs oversubscribed, host-tier
    streaming), the zero-recompile page-count lifecycle, and the per-row
    block-skip guard A/B
  * serve_pipeline: sync vs pipelined RetrievalServer under open-loop
    (Poisson) load — worker qps, p50/p95/p99 latency, occupancy, and a
    bit-identity check between the two workers per config
  * beyond-paper: int8 index on top of PCA (bytes /4, recall preserved)

Emits ``name,us_per_call,derived`` CSV rows like every other bench and
returns a JSON-ready dict.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DenseIndex, ShardedDenseIndex, StaticPruner
from repro.core.index import _scan_topk, _topk_merge
from repro.core.store import IndexStore, save_index


def _paged_jit_cache_size() -> int:
    """Compiled variants of every jit a paged search or lifecycle touches."""
    from repro.core import paged
    return sum(paged._jit_cache_sizes().values())


def _search_jit_cache_size() -> int:
    """Compiled variants of every search jit a served drive can touch."""
    from repro.core import cascade
    from repro.core.index import _dense_search_projected, _scan_topk
    return (_paged_jit_cache_size()
            + sum(cascade._jit_cache_sizes().values())
            + _dense_search_projected._cache_size()
            + _scan_topk._cache_size())

N_DOCS = 100_000
DIM = 768
N_QUERIES = 16
K = 10
ITERS = 3
# interpret-mode Pallas pays a huge per-op interpreter tax off-TPU; cap its
# corpus so the sweep stays tractable (the config records its own n)
PALLAS_MAX_DOCS = 20_000
# serve_pipeline section: open-loop queries per drive, in-flight window
N_SERVE = 192
SERVE_DEPTH = 3
SERVE_BATCH = 32
# live_index section: queries per drive, append block, seal rows (one
# open delta extent absorbs the whole drive's appends)
N_LIVE = 160
LIVE_APPEND_BLOCK = 128
LIVE_DELTA_CAP = 16384
LIVE_APPEND_RATES = {"append_low": 256.0, "append_high": 2048.0}  # rows/s
# paged section: page geometry, DMA pipeline depths, and the append blocks
# (in pruned m-dim rows) that walk the page count up during the
# zero-recompile sweep
PAGED_PAGE_ROWS = 256
PAGED_DEPTHS = (1, 2, 4)
PAGED_SWEEP_ROWS = (64, 128, 192, 256, 320)
PAGED_SWEEP_PAGE_ROWS = 64
PAGED_SWEEP_SEAL_ROWS = 128
# cascade section: coarse widths x shortlist depths (N*k candidates per
# query) x full-resolution dtypes; the coarse pass is always int8 and the
# rows serve through the jnp backend (interpret-mode pallas pays an
# intractable per-candidate tax at these shortlist depths off-TPU)
CASCADE_M_COARSE = (32, 64, 128, 192)
CASCADE_N_FACTORS = (4, 8, 16, 32, 64)
# fleet section: replicas, offered rate, and drive lengths. The corpus is
# unit-norm with self-retrieval queries (query i IS row i) so every
# successful reply's top-1 id is exactly checkable — "misrouted" is a
# measured count, not an inference. The kill drive is long enough that the
# handful of failover-delayed replies around the kill cannot dominate p99.
FLEET_REPLICAS = 3
FLEET_RATE = 150.0
FLEET_N_DOCS = 4096
FLEET_DIM = 64
N_FLEET_HEALTHY = 512
N_FLEET_KILL = 1536
N_FLEET_ROLLOUT = 512


def _bench(fn, *args, iters: int = ITERS) -> float:
    """Median us/call. Blocks on the result inside the timed region each
    iteration — with JAX's async dispatch, timing a loop of un-blocked
    calls measures enqueue rate, not latency."""
    jax.block_until_ready(fn(*args))   # compile + warmup
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


def _recall(ids_ref: np.ndarray, ids: np.ndarray, k: int) -> float:
    return float(np.mean([
        len(set(ids_ref[i].tolist()) & set(ids[i].tolist())) / k
        for i in range(ids_ref.shape[0])]))


# ---------------------------------------------------------------------------
# legacy select path (pre two-stage/block-skip) — kept only for the A/B row
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("k", "block"))
def _scan_topk_concat(D, Q, k, block=65536):
    """The old select: concat running + full strip, one big top_k per strip."""
    n, d = D.shape
    B = Q.shape[0]
    block = min(block, n)
    nblocks = -(-n // block)
    pad = nblocks * block - n
    Dp = jnp.pad(D, ((0, pad), (0, 0))) if pad else D
    blocks = Dp.reshape(nblocks, block, d)
    Qf = Q.astype(jnp.float32)

    def body(carry, inp):
        bs, bi = carry
        blk, start = inp
        s = Qf @ blk.T.astype(jnp.float32)
        ids = start + jnp.arange(block, dtype=jnp.int32)[None, :]
        s = jnp.where(ids < n, s, -jnp.inf)
        cs = jnp.concatenate([bs, s], axis=1)
        ci = jnp.concatenate([bi, jnp.broadcast_to(ids, (B, block))], axis=1)
        return _topk_merge(cs, ci, k), None

    init = (jnp.full((B, k), -jnp.inf, jnp.float32),
            jnp.full((B, k), -1, jnp.int32))
    starts = jnp.arange(nblocks, dtype=jnp.int32) * block
    (scores, ids), _ = jax.lax.scan(body, init, (blocks, starts))
    return scores, ids


# ---------------------------------------------------------------------------
# serving sweep
# ---------------------------------------------------------------------------


def _build_index(D, dtype: str, backend: str, layout: str, mesh):
    if layout == "dense":
        if dtype == "int8":
            return DenseIndex.build(D, quantize_int8=True, backend=backend)
        v = D.astype(jnp.bfloat16) if dtype == "bf16" else D
        return DenseIndex.build(v, backend=backend)
    merge = "hierarchical" if layout == "sharded-hier" else "flat"
    if dtype == "int8":
        return ShardedDenseIndex.build(D, mesh, quantize_int8=True,
                                       backend=backend, merge=merge)
    v = D.astype(jnp.bfloat16) if dtype == "bf16" else D
    return ShardedDenseIndex.build(v, mesh, backend=backend, merge=merge)


def _sweep(D, Q, ids_ref, emit) -> dict:
    """{backend x dtype x layout(+merge)} serving grid on the pruned index."""
    from repro.launch.serve import _serve_mesh
    ndev = jax.device_count()
    layouts = ["dense"]
    meshes = {}
    if ndev > 1:
        # flat merges over a 1-D mesh; hierarchical needs the factored 2-D
        # mesh (on 1-D it degenerates to the same single stage — measuring
        # that would just duplicate the flat row)
        meshes["sharded-flat"] = _serve_mesh(ndev, "flat")
        meshes["sharded-hier"] = _serve_mesh(ndev, "hierarchical")
        layouts += ["sharded-flat", "sharded-hier"]
    else:
        emit("# sweep: single device — sharded configs skipped")
    out = {}
    B = Q.shape[0]
    for backend in ("jnp", "pallas"):
        n_cap = min(D.shape[0], PALLAS_MAX_DOCS) if backend == "pallas" \
            else D.shape[0]
        Dc = D[:n_cap]
        if n_cap == D.shape[0]:
            ref_c = ids_ref
        else:   # exact f32 ranking on the capped corpus
            _, rid = DenseIndex.build(Dc).search(Q, k=K)
            ref_c = np.asarray(rid)
        for dtype in ("f32", "bf16", "int8"):
            for layout in layouts:
                name = f"{backend}_{dtype}_{layout}"
                mesh = meshes.get(layout)
                idx = _build_index(Dc, dtype, backend, layout, mesh)
                us = _bench(lambda q: idx.search(q, k=K), Q)
                _, ids = idx.search(Q, k=K)
                rec = _recall(ref_c, np.asarray(ids), K)
                qps = B / (us / 1e6)
                out[name] = dict(us=us, qps=qps, nbytes=int(idx.nbytes),
                                 recall=rec, n=n_cap, dim=int(D.shape[1]),
                                 mesh=(list(mesh.devices.shape)
                                       if mesh is not None else None))
                emit(f"sweep_{name},{us:.0f},qps={qps:.1f} "
                     f"bytes={idx.nbytes} recall@10={rec:.3f} n={n_cap}")
    return out


class _LegacySyncServer:
    """The pre-PR synchronous serving loop, faithfully reproduced for the
    sync row of the serve_pipeline bench (the ``_scan_topk_concat`` of the
    serving layer).

    One worker thread that (a) sleep-polls the request queue while
    assembling a batch, (b) dispatches projection (``transform_queries``)
    and search as separate computations, (c) blocks on ``np.asarray`` for
    the full D2H round-trip before assembling the next batch, and (d)
    dispatches whatever batch size arrived — so under ragged open-loop
    load every novel size jit-compiles a fresh full-index scan mid-serve.
    The pipelined server exists to delete exactly these four behaviours.
    """

    def __init__(self, index, pruner, k=10, max_batch=32):
        import queue as _q
        self.index, self.pruner, self.k = index, pruner, k
        self.max_batch = max_batch
        self.q: "queue.Queue" = _q.Queue()
        self.batch_log: list = []   # (size, t0, t1) — same shape as the new log
        self._log_lock = threading.Lock()   # worker_stats is borrowed from
        self._stop = threading.Event()      # RetrievalServer and locks it
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    def reset_stats(self):
        # single worker thread appends; same drive-side contract as
        # RetrievalServer.reset_stats
        self.batch_log.clear()

    def _next_batch(self):
        import queue as _q
        try:
            first = self.q.get(timeout=0.5)
        except _q.Empty:
            return None
        items = [first]
        t0 = time.time()
        while len(items) < self.max_batch and (time.time() - t0) < 0.002:
            try:
                items.append(self.q.get_nowait())
            except _q.Empty:
                time.sleep(0.0002)
        return np.stack([x[0] for x in items]), [x[1] for x in items]

    def _loop(self):
        while not self._stop.is_set():
            item = self._next_batch()
            if item is None:
                continue
            vecs, replies = item
            t0 = time.perf_counter()
            q = jnp.asarray(vecs)
            if self.pruner is not None:
                q = self.pruner.transform_queries(q)      # separate dispatch
            scores, ids = self.index.search(q, k=self.k)  # second dispatch
            scores = np.asarray(scores)                   # full D2H block
            ids = np.asarray(ids)
            self.batch_log.append((len(replies), t0, time.perf_counter()))
            for i, r in enumerate(replies):
                r.put((scores[i], ids[i]))

    def submit(self, qvec):
        import queue as _q
        reply: "queue.Queue" = _q.Queue(maxsize=1)
        self.q.put((qvec, reply))
        return reply

    def query(self, qvec, timeout: float = 10.0):
        return self.submit(qvec).get(timeout=timeout)

    def worker_stats(self):
        from repro.launch.serve import RetrievalServer
        return RetrievalServer.worker_stats(self)

    def close(self):
        self._stop.set()
        self._worker.join(timeout=60.0)


def _serve_mode_row(res: dict, stats: dict) -> dict:
    return dict(qps=res["achieved_qps"], p50_ms=res["p50_ms"],
                p95_ms=res["p95_ms"], p99_ms=res["p99_ms"],
                worker_qps=stats["worker_qps"],
                service_qps=stats["service_qps"],
                occupancy=stats["occupancy"], batches=stats["batches"])


def _serve_pipeline(Dh, pruner, Q_raw, emit) -> dict:
    """Sync vs pipelined serving under open-loop (Poisson) load.

    Per config {dense, sharded} x {f32, int8}, three servers run the same
    Poisson arrival tape at 1.5x the fused batched capacity:

      * ``sync``       — the pre-PR synchronous loop (``_LegacySyncServer``:
                         separate projection dispatch, ragged batch shapes
                         that recompile mid-serve, D2H-blocking before the
                         next batch is even assembled);
      * ``sync_fused`` — the new worker at pipeline_depth=1: fused
                         ``search_projected`` + fixed-shape padded batches,
                         but still one batch in flight (attribution row —
                         how much of the win is fusion vs pipelining);
      * ``pipelined``  — the new stager/completer worker at depth 3.

    Every query's (scores, ids) is collected from all three; the two
    new-architecture workers (same compiled fn, same padded shape) are
    compared bit-exactly — the pipeline must change throughput, never
    results. The legacy worker's ids agreement is reported alongside (its
    ragged batch shapes hit different matmul kernels, ~1e-7 score jitter).
    """
    from repro.launch.serve import RetrievalServer, _drive_open, _serve_mesh
    ndev = jax.device_count()
    layouts = [("dense", None)]
    if ndev > 1:
        layouts.append(("sharded", _serve_mesh(ndev, "flat")))
    else:
        emit("# serve_pipeline: single device — sharded configs skipped")
    Q = np.asarray(Q_raw)
    Qs = np.tile(Q, (N_SERVE // len(Q) + 1, 1))[:N_SERVE]
    W, mean = pruner.projection()
    configs = {}
    for layout, mesh in layouts:
        for dtype in ("f32", "int8"):
            quant = dtype == "int8"
            if mesh is None:
                idx = DenseIndex.build(Dh, quantize_int8=quant)
            else:
                idx = ShardedDenseIndex.build(Dh, mesh, quantize_int8=quant)
            name = f"{layout}_{dtype}"

            # offered rate: 1.5x the fused full-batch capacity, so every
            # worker saturates and worker-side qps is the comparison
            tb = _bench(lambda q: idx.search_projected(q, W, k=K, mean=mean),
                        jnp.asarray(Qs[:SERVE_BATCH])) / 1e6
            rate = 1.5 * SERVE_BATCH / tb

            rows, outs = {}, {}
            servers = (
                ("sync", lambda: _LegacySyncServer(
                    idx, pruner, k=K, max_batch=SERVE_BATCH)),
                ("sync_fused", lambda: RetrievalServer(
                    idx, pruner, k=K, max_batch=SERVE_BATCH,
                    pipeline_depth=1)),
                ("pipelined", lambda: RetrievalServer(
                    idx, pruner, k=K, max_batch=SERVE_BATCH,
                    pipeline_depth=SERVE_DEPTH)),
            )
            for mode, make in servers:
                srv = make()
                res = _drive_open(srv, Qs, rate=rate, collect=True)
                stats = srv.worker_stats()
                srv.close()
                outs[mode] = res.pop("results")
                rows[mode] = _serve_mode_row(res, stats)
            # scheduling must never change results: depth-1 and depth-3 run
            # the same compiled fn on the same padded shape, so (scores,
            # ids) are required bit-identical. The legacy worker dispatches
            # ragged unpadded shapes whose small-B matmul kernels round
            # differently at ~1e-7 — its ids agreement is reported, not
            # asserted bitwise.
            match = all(
                (np.asarray(a[0]) == np.asarray(b[0])).all()
                and (np.asarray(a[1]) == np.asarray(b[1])).all()
                for a, b in zip(outs["sync_fused"], outs["pipelined"]))
            legacy_ids = float(np.mean([
                (np.asarray(a[1]) == np.asarray(b[1])).all()
                for a, b in zip(outs["sync"], outs["pipelined"])]))
            configs[name] = dict(
                n=int(Dh.shape[0]), dim=int(Dh.shape[1]),
                nbytes=int(idx.nbytes), rate_qps=float(rate),
                match=bool(match), legacy_ids_equal=legacy_ids, **rows)
            emit(f"serve_pipeline_{name},{rows['pipelined']['p50_ms']*1e3:.0f},"
                 f"sync={rows['sync']['worker_qps']:.1f}qps "
                 f"fused={rows['sync_fused']['worker_qps']:.1f}qps "
                 f"piped={rows['pipelined']['worker_qps']:.1f}qps "
                 f"(offered {rate:.1f}) "
                 f"p99 {rows['sync']['p99_ms']:.0f}->"
                 f"{rows['pipelined']['p99_ms']:.0f}ms match={match}")
    return dict(meta=dict(depth=int(SERVE_DEPTH), max_batch=int(SERVE_BATCH),
                          n_queries=int(N_SERVE),
                          rate_policy="1.5x fused batched capacity",
                          sync_row="pre-PR synchronous worker "
                                   "(_LegacySyncServer)"),
                configs=configs)


def _live_index(Dh, pruner, Q_raw, emit) -> dict:
    """Serve QPS under concurrent live appends vs the static baseline.

    Per dtype {f32, int8}, the same Poisson tape drives four servers at
    ~0.8x the fused batched capacity:

      * ``static``      — monolithic ``DenseIndex`` (appends would
                          require a rebuild);
      * ``append_low`` / ``append_high`` — a ``PagedIndex`` server while a
                          background ``IndexUpdater`` appends raw
                          documents at that rate; every append swaps a
                          fresh index into the server atomically.

    Each append row also records the number of paged jit compilations
    during the timed drive (``recompiles_steady``) — the acceptance bar is
    ZERO: page-table slot bounds are traced operands, so corpus growth
    never stalls serving on a compile. ``benchmarks/run.py`` schema-checks
    all of this before BENCH_perf.json is written.
    """
    from repro.core.maintenance import IndexUpdater
    from repro.core.paged import PagedIndex
    from repro.launch.serve import RetrievalServer, _drive_open
    d_raw = int(pruner.state.d)
    Q = np.asarray(Q_raw)
    Qs = np.tile(Q, (N_LIVE // len(Q) + 1, 1))[:N_LIVE]
    W, mean = pruner.projection()
    rng = np.random.default_rng(42)
    configs = {}
    for dtype in ("f32", "int8"):
        quant = dtype == "int8"
        idx = DenseIndex.build(Dh, quantize_int8=quant)
        tb = _bench(lambda q: idx.search_projected(q, W, k=K, mean=mean),
                    jnp.asarray(Qs[:SERVE_BATCH])) / 1e6
        rate = 0.8 * SERVE_BATCH / tb

        rows = {}
        srv = RetrievalServer(idx, pruner, k=K, max_batch=SERVE_BATCH,
                              pipeline_depth=SERVE_DEPTH)
        res = _drive_open(srv, Qs, rate=rate)
        rows["static"] = _serve_mode_row(res, srv.worker_stats())
        srv.close()

        for name, arate in LIVE_APPEND_RATES.items():
            live = PagedIndex.from_index(idx, page_rows=PAGED_PAGE_ROWS,
                                         seal_rows=LIVE_DELTA_CAP)
            srv = RetrievalServer(live, pruner, k=K, max_batch=SERVE_BATCH,
                                  pipeline_depth=SERVE_DEPTH)
            up = IndexUpdater(pruner=pruner, index=live, server=srv,
                              delta_capacity=LIVE_DELTA_CAP)
            # warm appends (open + a NON-widening extend at the live block
            # size: 0.5x rows already present) + query: compile the page
            # walk and the append-side projection BEFORE the timed drive —
            # everything after this is steady state
            warm = rng.standard_normal(
                (LIVE_APPEND_BLOCK, d_raw)).astype(np.float32)
            up.add_documents(jnp.asarray(warm))
            up.add_documents(jnp.asarray(0.5 * warm))
            srv.query(Qs[0])
            jit0 = _paged_jit_cache_size()
            n0 = up.index.n
            stop = threading.Event()

            def appender(arate=arate):
                while not stop.is_set():
                    t0 = time.perf_counter()
                    up.add_documents(jnp.asarray(
                        rng.standard_normal((LIVE_APPEND_BLOCK, d_raw))
                        .astype(np.float32)))
                    lag = (LIVE_APPEND_BLOCK / arate
                           - (time.perf_counter() - t0))
                    if lag > 0:
                        stop.wait(lag)

            th = None
            if arate > 0:
                th = threading.Thread(target=appender, daemon=True)
                th.start()
            res = _drive_open(srv, Qs, rate=rate)
            if th is not None:
                stop.set()
                th.join(timeout=30.0)
            recompiles = _paged_jit_cache_size() - jit0
            rows[name] = dict(_serve_mode_row(res, srv.worker_stats()),
                              appended_rows=int(up.index.n - n0),
                              swaps=int(srv.swap_count),
                              recompiles_steady=int(recompiles))
            srv.close()
        configs[f"dense_{dtype}"] = dict(
            n=int(Dh.shape[0]), dim=int(Dh.shape[1]), rate_qps=float(rate),
            **rows)
        emit(f"live_index_dense_{dtype},{rows['append_high']['p50_ms']*1e3:.0f},"
             f"static={rows['static']['worker_qps']:.1f}qps "
             f"low={rows['append_low']['worker_qps']:.1f}qps"
             f"(+{rows['append_low']['appended_rows']}) "
             f"high={rows['append_high']['worker_qps']:.1f}qps"
             f"(+{rows['append_high']['appended_rows']}r/"
             f"{rows['append_high']['swaps']}sw) "
             f"recompiles={rows['append_high']['recompiles_steady']}")
    return dict(meta=dict(n_queries=int(N_LIVE),
                          append_block=int(LIVE_APPEND_BLOCK),
                          delta_capacity=int(LIVE_DELTA_CAP),
                          append_rates_rows_per_s={
                              k: float(v)
                              for k, v in LIVE_APPEND_RATES.items()},
                          rate_policy="0.8x fused batched capacity"),
                configs=configs)


def _paged(Dh, pruner, Q_raw, emit) -> dict:
    """Paged index memory: the four tracked claims, one subsection each.

      * ``configs`` — paged serve qps at append rates {0, high} on the
        live-append harness: per dtype, the same Poisson tape at the same
        offered rate (0.8x the dense fused capacity) drives two servers —
        ``paged_append_0`` and ``paged_append_high`` — with a background
        ``IndexUpdater`` supplying the appends.  Every row reports the steady-state jit-compile count;
        the schema gate pins it to ZERO (paged appends are page-pointer
        swaps at fixed dispatch shapes — growth must never stall serving
        on a compile).
      * ``depth_sweep`` — DMA/compute overlap through the interpreted
        Pallas paged kernel at pipeline depth {1, 2, 4}, fully resident
        vs oversubscribed (pool capped at half the index, overflow on the
        host tier), with a bit-identity check between the two residencies
        at each depth (streaming must change throughput, never results).
      * ``oversubscription`` — the headline row: best depth>=2
        oversubscribed qps as a fraction of fully resident.  The schema
        floor is 0.80 — host-tier staging has to hide behind compute once
        the pipeline is at least double-buffered.
      * ``page_count_sweep`` — full lifecycle (append -> search ->
        promote -> compact -> search) at growing page counts, ``jnp``
        backend: the page count is data ([lo, hi) slot bounds are
        traced), so the compiled-variant count must not move.
      * ``guard_ab`` — the per-row block-skip guard (masked merge) vs the
        legacy whole-batch guard on the same blocked scan, asserted
        bit-identical (the guard is an optimisation, never a result).
    """
    from repro.core.maintenance import IndexUpdater
    from repro.core.paged import PagedIndex
    from repro.launch.serve import RetrievalServer, _drive_open
    d_raw = int(pruner.state.d)
    Q = np.asarray(Q_raw)
    Qs = np.tile(Q, (N_LIVE // len(Q) + 1, 1))[:N_LIVE]
    W, mean = pruner.projection()
    rng = np.random.default_rng(17)

    # -- paged serve with and without live appends --------------------------
    configs = {}
    for dtype in ("f32", "int8"):
        quant = dtype == "int8"
        idx = DenseIndex.build(Dh, quantize_int8=quant)
        tb = _bench(lambda q: idx.search_projected(q, W, k=K, mean=mean),
                    jnp.asarray(Qs[:SERVE_BATCH])) / 1e6
        rate = 0.8 * SERVE_BATCH / tb
        rows = {}
        for name, arate in (("append_0", 0.0),
                            ("append_high",
                             LIVE_APPEND_RATES["append_high"])):
            live = PagedIndex.from_index(idx, page_rows=PAGED_PAGE_ROWS,
                                         seal_rows=LIVE_DELTA_CAP)
            srv = RetrievalServer(live, pruner, k=K, max_batch=SERVE_BATCH,
                                  pipeline_depth=SERVE_DEPTH)
            up = IndexUpdater(pruner=pruner, index=live, server=srv,
                              delta_capacity=LIVE_DELTA_CAP)
            # same warmup contract as live_index: open + non-widening
            # extend + query compile every steady-state path up front
            warm = rng.standard_normal(
                (LIVE_APPEND_BLOCK, d_raw)).astype(np.float32)
            up.add_documents(jnp.asarray(warm))
            up.add_documents(jnp.asarray(0.5 * warm))
            srv.query(Qs[0])
            jit0 = _paged_jit_cache_size()
            n0 = up.index.n
            stop = threading.Event()

            def appender(arate=arate):
                while not stop.is_set():
                    t0 = time.perf_counter()
                    up.add_documents(jnp.asarray(
                        rng.standard_normal((LIVE_APPEND_BLOCK, d_raw))
                        .astype(np.float32)))
                    lag = (LIVE_APPEND_BLOCK / arate
                           - (time.perf_counter() - t0))
                    if lag > 0:
                        stop.wait(lag)

            th = None
            if arate > 0:
                th = threading.Thread(target=appender, daemon=True)
                th.start()
            res = _drive_open(srv, Qs, rate=rate)
            if th is not None:
                stop.set()
                th.join(timeout=30.0)
            recompiles = _paged_jit_cache_size() - jit0
            rows[f"paged_{name}"] = dict(
                _serve_mode_row(res, srv.worker_stats()),
                appended_rows=int(up.index.n - n0),
                swaps=int(srv.swap_count),
                recompiles_steady=int(recompiles))
            srv.close()
        configs[f"dense_{dtype}"] = dict(
            n=int(Dh.shape[0]), dim=int(Dh.shape[1]), rate_qps=float(rate),
            **rows)
        emit(f"paged_live_dense_{dtype},"
             f"{rows['paged_append_high']['p50_ms']*1e3:.0f},"
             f"pg0={rows['paged_append_0']['worker_qps']:.1f}qps "
             f"pgH={rows['paged_append_high']['worker_qps']:.1f}qps"
             f"(+{rows['paged_append_high']['appended_rows']}r/"
             f"{rows['paged_append_high']['swaps']}sw) "
             f"recompiles={rows['paged_append_high']['recompiles_steady']}")

    # -- DMA/compute overlap: depth sweep, resident vs oversubscribed -------
    n_cap = min(Dh.shape[0], PALLAS_MAX_DOCS)
    Dc = Dh[:n_cap]
    base8 = DenseIndex.build(Dc, quantize_int8=True)
    npages = -(-n_cap // PAGED_PAGE_ROWS)
    pool = max(npages // 2, 1)
    Qb = jnp.asarray(Qs[:SERVE_BATCH])
    depth_rows = {}
    for depth in PAGED_DEPTHS:
        row = {}
        outs = {}
        for mode, pp in (("resident", None), ("oversubscribed", pool)):
            pg = PagedIndex.from_index(base8, page_rows=PAGED_PAGE_ROWS,
                                       pool_pages=pp, backend="pallas",
                                       depth=depth)
            us = _bench(
                lambda q: pg.search_projected(q, W, k=K, mean=mean), Qb)
            outs[mode] = pg.search_projected(Qb, W, k=K, mean=mean)
            row[mode] = dict(us=us, qps=SERVE_BATCH / (us / 1e6),
                             host_pages=int(pg.storage.n_host_pages))
        row["match"] = bool(
            (np.asarray(outs["resident"][0])
             == np.asarray(outs["oversubscribed"][0])).all()
            and (np.asarray(outs["resident"][1])
                 == np.asarray(outs["oversubscribed"][1])).all())
        row["overlap_ratio"] = (row["oversubscribed"]["qps"]
                                / row["resident"]["qps"])
        depth_rows[f"depth_{depth}"] = row
        emit(f"paged_depth_{depth},{row['resident']['us']:.0f},"
             f"resident={row['resident']['qps']:.1f}qps "
             f"oversub={row['oversubscribed']['qps']:.1f}qps "
             f"({row['overlap_ratio']:.2f}x, "
             f"{row['oversubscribed']['host_pages']} host pages) "
             f"match={row['match']}")
    best_depth, best_ratio = max(
        ((d, depth_rows[f"depth_{d}"]["overlap_ratio"])
         for d in PAGED_DEPTHS if d >= 2), key=lambda t: t[1])
    oversub = dict(
        n=int(n_cap), page_rows=int(PAGED_PAGE_ROWS),
        total_pages=int(npages), pool_pages=int(pool),
        host_pages=int(npages - pool), depth=int(best_depth),
        resident_qps=depth_rows[f"depth_{best_depth}"]["resident"]["qps"],
        oversub_qps=depth_rows[f"depth_{best_depth}"]["oversubscribed"]["qps"],
        ratio=float(best_ratio))
    emit(f"paged_oversubscription,{oversub['resident_qps']:.0f},"
         f"ratio={oversub['ratio']:.2f} at depth={best_depth} "
         f"({oversub['host_pages']}/{npages} pages on host)")

    # -- page-count sweep: full lifecycle, zero steady-state recompiles -----
    # deliberately oversubscribed (pool of 18 against a growing index) so
    # the measured sweep crosses NOTHING for the first time: warmup runs
    # the lifecycle until the jit-variant set is a fixed point with the
    # host tier already live, then five more lifecycles grow the page
    # count (and the host tier) with the cache pinned
    rngp = np.random.default_rng(23)
    m = int(Dh.shape[1])
    pg = PagedIndex.from_index(
        DenseIndex.build(Dh[:1024], quantize_int8=True),
        page_rows=PAGED_SWEEP_PAGE_ROWS, pool_pages=18,
        seal_rows=PAGED_SWEEP_SEAL_ROWS, wave_pages=2)

    def lifecycle(pg, rows):
        pg = pg.append(jnp.asarray(
            rngp.standard_normal((rows, m)).astype(np.float32)))
        pg.search_projected(Qb, W, k=K, mean=mean)
        pg, _ = pg.promote()
        pg, _ = pg.compact_pages()
        jax.block_until_ready(pg.search_projected(Qb, W, k=K, mean=mean))
        return pg

    warmups, prev = 0, -1
    while warmups < 10:
        pg = lifecycle(pg, 192)
        warmups += 1
        cur = _paged_jit_cache_size()
        if cur == prev and pg.storage.n_host_pages > 0:
            break
        prev = cur
    jit0 = _paged_jit_cache_size()
    page_counts = [int(pg.total_pages)]
    host_counts = [int(pg.storage.n_host_pages)]
    for rows in PAGED_SWEEP_ROWS:
        pg = lifecycle(pg, rows)
        page_counts.append(int(pg.total_pages))
        host_counts.append(int(pg.storage.n_host_pages))
    sweep = dict(page_rows=int(PAGED_SWEEP_PAGE_ROWS),
                 seal_rows=int(PAGED_SWEEP_SEAL_ROWS),
                 pool_pages=18, warmup_lifecycles=int(warmups),
                 append_rows=[int(r) for r in PAGED_SWEEP_ROWS],
                 page_counts=page_counts, host_pages=host_counts,
                 recompiles_steady=int(_paged_jit_cache_size() - jit0))
    emit(f"paged_page_count_sweep,0,pages={page_counts} "
         f"host={host_counts} recompiles={sweep['recompiles_steady']}")

    # -- guard A/B: per-row masked merge vs legacy whole-batch guard --------
    qh = pruner.transform_queries(jnp.asarray(Q))
    blk = min(512, Dh.shape[0])
    t_row = _bench(lambda q: _scan_topk(Dh, q, K, block=blk), qh)
    t_batch = _bench(
        lambda q: _scan_topk(Dh, q, K, block=blk, guard="batch"), qh)
    out_r = _scan_topk(Dh, qh, K, block=blk)
    out_b = _scan_topk(Dh, qh, K, block=blk, guard="batch")
    identical = bool(
        (np.asarray(out_r[0]) == np.asarray(out_b[0])).all()
        and (np.asarray(out_r[1]) == np.asarray(out_b[1])).all())
    guard_ab = dict(row_us=t_row, batch_us=t_batch,
                    speedup=t_batch / t_row, block=int(blk),
                    bitwise_identical=identical)
    emit(f"paged_guard_ab,{t_row:.0f},row-vs-batch={t_batch/t_row:.2f}x "
         f"identical={identical}")

    return dict(meta=dict(page_rows=int(PAGED_PAGE_ROWS),
                          depths=[int(d) for d in PAGED_DEPTHS],
                          n_queries=int(N_LIVE),
                          append_block=int(LIVE_APPEND_BLOCK),
                          seal_rows=int(LIVE_DELTA_CAP),
                          rate_policy="0.8x fused batched capacity",
                          depth_backend="pallas (interpret off-TPU)"),
                configs=configs, depth_sweep=depth_rows,
                oversubscription=oversub, page_count_sweep=sweep,
                guard_ab=guard_ab)


def _serve_bucketing(Dh, pruner, Q_raw, emit) -> dict:
    """Pad-to-max vs batch-shape bucketing at LOW load (0.2x capacity):
    partial batches dominate there, so padding every one of them to
    ``max_batch`` burns up to 4x the needed scan compute — bucketing pads
    to the next of {8, 16, 32} instead, for a handful of extra compiles
    (absorbed by ``warmup()``, not paid mid-serve)."""
    from repro.launch.serve import RetrievalServer, _drive_open
    Q = np.asarray(Q_raw)
    Qs = np.tile(Q, (N_LIVE // len(Q) + 1, 1))[:N_LIVE]
    W, mean = pruner.projection()
    idx = DenseIndex.build(Dh)
    tb = _bench(lambda q: idx.search_projected(q, W, k=K, mean=mean),
                jnp.asarray(Qs[:SERVE_BATCH])) / 1e6
    rate = 0.2 * SERVE_BATCH / tb
    out = {"rate_qps": float(rate), "n": int(Dh.shape[0])}
    for mode, bucketed in (("pad_to_max", False), ("bucketed", True)):
        srv = RetrievalServer(idx, pruner, k=K, max_batch=SERVE_BATCH,
                              pipeline_depth=SERVE_DEPTH,
                              bucket_batches=bucketed)
        srv.warmup()
        res = _drive_open(srv, Qs, rate=rate)
        out[mode] = _serve_mode_row(res, srv.worker_stats())
        srv.close()
    emit(f"serve_bucketing,{out['bucketed']['p50_ms']*1e3:.0f},"
         f"@{rate:.1f}qps p50 {out['pad_to_max']['p50_ms']:.2f}->"
         f"{out['bucketed']['p50_ms']:.2f}ms p99 "
         f"{out['pad_to_max']['p99_ms']:.2f}->"
         f"{out['bucketed']['p99_ms']:.2f}ms")
    return out


def _cascade(Dh, pruner, Q_raw, emit) -> dict:
    """Cascade Pareto sweep: recall@10 vs saturated worker qps across
    {m_coarse x N x full dtype}, against the single-resolution full-m
    worker on the same open-loop harness.

    Every row (baselines included) drives the same query tape at 1.5x its
    OWN fused batched capacity — each worker saturates, so ``worker_qps``
    is the capacity comparison — and reports recall@10 against the exact
    full-m f32 oracle plus the steady-state jit-compile count (the
    cascade's zero-recompile contract under fixed nk)."""
    from repro.core import CascadeIndex
    from repro.launch.serve import RetrievalServer, _drive_open
    Q = np.asarray(Q_raw)
    Qs = np.tile(Q, (N_SERVE // len(Q) + 1, 1))[:N_SERVE]
    W, mean = pruner.projection()
    n, m = int(Dh.shape[0]), int(Dh.shape[1])
    _, ids_o = DenseIndex.build(Dh).search_projected(
        jnp.asarray(Qs), W, k=K, mean=mean)
    ids_o = np.asarray(ids_o)

    def drive(idx):
        tb = _bench(lambda q: idx.search_projected(q, W, k=K, mean=mean),
                    jnp.asarray(Qs[:SERVE_BATCH])) / 1e6
        rate = 1.5 * SERVE_BATCH / tb
        srv = RetrievalServer(idx, pruner, k=K, max_batch=SERVE_BATCH,
                              pipeline_depth=SERVE_DEPTH)
        srv.query(Qs[0])            # compile the padded batch shape
        jit0 = _search_jit_cache_size()
        srv.reset_stats()
        res = _drive_open(srv, Qs, rate=rate, collect=True)
        outs = res.pop("results")
        stats = srv.worker_stats()
        recompiles = _search_jit_cache_size() - jit0
        srv.close()
        ids = np.stack([np.asarray(i) for _, i in outs])
        return dict(_serve_mode_row(res, stats), rate_qps=float(rate),
                    recall_at_10=_recall(ids_o, ids, K),
                    recompiles_steady=int(recompiles))

    rows = {}
    for dtype in ("f32", "int8"):
        base = DenseIndex.build(Dh, quantize_int8=dtype == "int8")
        brow = dict(drive(base), dtype=dtype, m_coarse=None, n_factor=None,
                    baseline=True, nbytes=int(base.nbytes))
        rows[f"baseline_{dtype}"] = brow
        emit(f"cascade_baseline_{dtype},{brow['p50_ms']*1e3:.0f},"
             f"worker={brow['worker_qps']:.1f}qps "
             f"recall@10={brow['recall_at_10']:.3f}")
        for mc in CASCADE_M_COARSE:
            if mc >= base.dim:   # coarse view must strictly nest (fast mode)
                continue
            for nf in CASCADE_N_FACTORS:
                cas = CascadeIndex.from_index(base, m_coarse=mc,
                                              n_factor=nf)
                crow = dict(drive(cas), dtype=dtype, m_coarse=int(mc),
                            n_factor=int(nf), baseline=False,
                            nbytes=int(cas.nbytes))
                crow["speedup_vs_baseline"] = (crow["worker_qps"]
                                               / brow["worker_qps"])
                rows[f"{dtype}_m{mc}_N{nf}"] = crow
                emit(f"cascade_{dtype}_m{mc}_N{nf},"
                     f"{crow['p50_ms']*1e3:.0f},"
                     f"worker={crow['worker_qps']:.1f}qps "
                     f"({crow['speedup_vs_baseline']:.2f}x baseline) "
                     f"recall@10={crow['recall_at_10']:.3f} "
                     f"recompiles={crow['recompiles_steady']}")
    return dict(meta=dict(n=n, m=m, n_queries=int(N_SERVE), k=int(K),
                          max_batch=int(SERVE_BATCH),
                          depth=int(SERVE_DEPTH), backend="jnp",
                          coarse_dtype="int8",
                          rate_policy="1.5x own fused batched capacity",
                          oracle="exact full-m f32 search_projected"),
                rows=rows)


def _fleet(emit) -> dict:
    """Replicated fleet under chaos: the three tracked drives.

      * ``healthy``      — R replicas, open-loop Poisson, no faults: the
                           p99 baseline the fault drives are held against.
      * ``kill_restart`` — replica r1 is crash-injected mid-drive and
                           restarted later; the schema gate requires zero
                           lost accepted replies, zero misrouted replies,
                           and p99 within 2x the healthy baseline (the
                           failover cohort is a fixed handful of requests,
                           so a long enough drive keeps it out of p99).
      * ``bad_rollout``  — a recall-regressing artifact (same corpus,
                           shuffled row ids) is rolled out mid-drive; the
                           health gate must roll the whole fleet back and
                           no live reply may ever have been served by the
                           bad index (top-1 self-retrieval makes every
                           reply checkable).
    """
    import threading as _threading

    from repro.launch.serve import _drive_open
    from repro.serving.fleet import FaultEvent, FaultPlan
    from repro.serving.soak import _unit_corpus, build_fleet

    def drive_row(fleet, D, n, *, seed, plan=None, rollout_to=None):
        rng = np.random.default_rng(seed)
        qids = rng.integers(0, len(D), size=n)
        rollout_result = {}
        threads = []
        if plan is not None:
            threads.append(plan.start(fleet))
        if rollout_to is not None:
            def _roll():
                time.sleep(1.0)
                rollout_result.update(fleet.rollout(rollout_to))
            th = _threading.Thread(target=_roll, daemon=True)
            th.start()
            threads.append(th)
        res = _drive_open(fleet, D[qids], rate=FLEET_RATE, seed=seed,
                          collect=True, tolerate_errors=True, deadline=2.0)
        for th in threads:
            th.join(timeout=60.0)
        misrouted = sum(
            1 for i, out in enumerate(res.pop("results"))
            if isinstance(out, tuple)
            and int(np.asarray(out[1])[0]) != int(qids[i]))
        stats = fleet.stats()
        row = dict(n=res["n"], n_ok=res["n_ok"], errors=res["errors"],
                   achieved_qps=res["achieved_qps"],
                   p50_ms=res["p50_ms"], p95_ms=res["p95_ms"],
                   p99_ms=res["p99_ms"], misrouted=misrouted,
                   accepted=stats["accepted"], shed=stats["shed"],
                   timed_out=stats["timed_out"], failed=stats["failed"],
                   failovers=stats["failovers"],
                   lost_accepted=stats["lost_accepted"],
                   health_ok=bool(fleet.health()["ok"]))
        if rollout_result:
            row["rolled_back"] = bool(rollout_result.get("rolled_back"))
        return row

    tmpdir = tempfile.mkdtemp(prefix="bench_fleet_")
    try:
        fleet, D = build_fleet(os.path.join(tmpdir, "v1"),
                               n_docs=FLEET_N_DOCS, dim=FLEET_DIM,
                               replicas=FLEET_REPLICAS)
        try:
            out = {"replicas": FLEET_REPLICAS, "rate": FLEET_RATE}
            out["healthy"] = drive_row(fleet, D, N_FLEET_HEALTHY, seed=1)
            emit(f"fleet_healthy,{out['healthy']['p99_ms']*1e3:.0f},"
                 f"qps={out['healthy']['achieved_qps']:.1f} "
                 f"ok={out['healthy']['n_ok']}/{out['healthy']['n']}")

            t_kill = 0.3 * (N_FLEET_KILL / FLEET_RATE)
            plan = FaultPlan([FaultEvent(t_kill, "kill", "r1"),
                              FaultEvent(2.0 * t_kill, "restart", "r1")])
            out["kill_restart"] = drive_row(fleet, D, N_FLEET_KILL,
                                            seed=2, plan=plan)
            kr = out["kill_restart"]
            emit(f"fleet_kill_restart,{kr['p99_ms']*1e3:.0f},"
                 f"lost={kr['lost_accepted']} misrouted={kr['misrouted']} "
                 f"failovers={kr['failovers']} ok={kr['n_ok']}/{kr['n']}")

            # recall-regressing artifact: identical rows, shuffled order —
            # every id the bad index would return is wrong
            from repro.core import StaticPruner as _SP
            perm = np.random.default_rng(3).permutation(len(D))
            prb = _SP(cutoff=0.5).fit(jnp.asarray(D[perm]))
            save_index(os.path.join(tmpdir, "v_bad"),
                       prb.build_index(jnp.asarray(D[perm])), pruner=prb)
            out["bad_rollout"] = drive_row(
                fleet, D, N_FLEET_ROLLOUT, seed=4,
                rollout_to=os.path.join(tmpdir, "v_bad"))
            br = out["bad_rollout"]
            emit(f"fleet_bad_rollout,{br['p99_ms']*1e3:.0f},"
                 f"rolled_back={br.get('rolled_back')} "
                 f"misrouted={br['misrouted']} lost={br['lost_accepted']}")
            return out
        finally:
            fleet.close()
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)


def run(emit=print) -> dict:
    # structured corpus (trained-encoder spectral regime) — recall under
    # pruning is meaningless on isotropic gaussians
    from repro.data.synthetic import make_corpus
    rng = np.random.default_rng(0)
    D_np, _ = make_corpus("tasb", n_docs=N_DOCS, d=DIM, seed=0)
    D = jnp.asarray(D_np)
    q_idx = rng.choice(N_DOCS, N_QUERIES, replace=False)
    Q = jnp.asarray(D_np[q_idx] + 0.05 * rng.standard_normal((N_QUERIES, DIM))
                    .astype(np.float32))

    results = {"meta": dict(n_docs=int(N_DOCS), dim=int(DIM),
                            n_queries=int(N_QUERIES), k=int(K),
                            iters=int(ITERS),
                            device_count=int(jax.device_count()),
                            backend=jax.default_backend(),
                            jax_version=jax.__version__)}
    full = DenseIndex.build(D)
    t_full = _bench(lambda q: full.search(q, k=K), Q)
    emit(f"search_full_d{DIM},{t_full:.0f},bytes={full.nbytes}")
    results["full"] = dict(us=t_full, qps=N_QUERIES / (t_full / 1e6),
                           nbytes=int(full.nbytes), recall=1.0)
    _, ids_full = full.search(Q, k=K)
    ids_full = np.asarray(ids_full)

    for c in (0.25, 0.5, 0.75):
        pruner = StaticPruner(cutoff=c).fit(D)
        m = pruner.kept_dims
        idx = DenseIndex.build(pruner.prune_index(D))
        qh = pruner.transform_queries(Q)
        t = _bench(lambda q: idx.search(q, k=K), qh)
        _, ids_p = idx.search(qh, k=K)
        rec = _recall(ids_full, np.asarray(ids_p), K)
        emit(f"search_pca_m{m},{t:.0f},speedup={t_full/t:.2f}x "
             f"predicted={DIM/m:.2f}x bytes={idx.nbytes} recall@10={rec:.3f}")
        results[f"pca_{c}"] = dict(us=t, qps=N_QUERIES / (t / 1e6), m=int(m),
                                   speedup=t_full / t, predicted=DIM / m,
                                   nbytes=int(idx.nbytes), recall=rec)

    # beyond paper: PCA(50%) + int8
    pruner = StaticPruner(cutoff=0.5).fit(D)
    idx8 = pruner.build_index(D, quantize_int8=True)
    qh = pruner.transform_queries(Q)
    t8 = _bench(lambda q: idx8.search(q, k=K), qh)
    _, ids_8 = idx8.search(qh, k=K)
    rec8 = _recall(ids_full, np.asarray(ids_8), K)
    emit(f"search_pca50_int8,{t8:.0f},bytes={idx8.nbytes} "
         f"compression={full.nbytes/idx8.nbytes:.1f}x recall@10={rec8:.3f}")
    results["pca50_int8"] = dict(us=t8, qps=N_QUERIES / (t8 / 1e6),
                                 nbytes=int(idx8.nbytes), recall=rec8)

    # serving sweep on the pruned index (the paper's serve-time artefact);
    # recall reference = exact f32 ranking on the same pruned space
    Dh = pruner.prune_index(D)
    _, ids_ref_pruned = DenseIndex.build(Dh).search(qh, k=K)
    results["sweep"] = _sweep(Dh, qh, np.asarray(ids_ref_pruned), emit)

    # end-to-end serving: sync vs pipelined workers under open-loop load,
    # raw d-dim queries through the fused search_projected hot path
    results["serve_pipeline"] = _serve_pipeline(Dh, pruner, np.asarray(Q),
                                                emit)

    # live paged index: serve QPS while a background updater appends
    # (zero steady-state recompiles asserted by the schema check), plus the
    # batch-shape bucketing A/B at low load
    results["live_index"] = _live_index(Dh, pruner, np.asarray(Q), emit)
    results["serve_bucketing"] = _serve_bucketing(Dh, pruner, np.asarray(Q),
                                                  emit)

    # paged index memory: paged live serve, DMA depth sweep
    # with the oversubscription headline row, zero-recompile page-count
    # lifecycle, and the per-row vs whole-batch guard A/B
    results["paged"] = _paged(Dh, pruner, np.asarray(Q), emit)

    # cascade Pareto: two-stage coarse scan -> exact shortlist rescore vs
    # the single-resolution full-m worker, same open-loop harness
    results["cascade"] = _cascade(Dh, pruner, np.asarray(Q), emit)

    # replicated fleet under chaos: healthy baseline, kill/restart, and a
    # recall-regressing rollout — droplessness/misroute/rollback invariants
    # enforced by benchmarks.run's schema gate before BENCH_perf.json lands
    results["fleet"] = _fleet(emit)

    # cold start: committed on-disk artifact -> first answered query — the
    # restart path ``serve.py --load-index`` takes. One-shot by nature
    # (page cache + jit compile are part of the cost being measured).
    tmpdir = tempfile.mkdtemp(prefix="bench_store_")
    try:
        store_path = os.path.join(tmpdir, "idx")
        save_index(store_path, DenseIndex.build(Dh), pruner=pruner)
        t0 = time.perf_counter()
        st = IndexStore.open(store_path)
        idx_cold = DenseIndex.load(st)
        jax.block_until_ready(
            idx_cold.search(st.load_pruner().transform_queries(Q), k=K))
        cold_dense = (time.perf_counter() - t0) * 1e6
        emit(f"cold_start_dense,{cold_dense:.0f},n={st.n} bytes={st.nbytes}")
        results["cold_start"] = dict(dense_us=cold_dense, n=int(st.n),
                                     nbytes=int(st.nbytes))
        if jax.device_count() > 1:
            mesh = jax.make_mesh((jax.device_count(),), ("data",))
            t0 = time.perf_counter()
            st = IndexStore.open(store_path)
            sidx_cold = ShardedDenseIndex.load(st, mesh)
            jax.block_until_ready(sidx_cold.search(qh, k=K))
            cold_sh = (time.perf_counter() - t0) * 1e6
            emit(f"cold_start_sharded,{cold_sh:.0f},"
                 f"ndev={jax.device_count()}")
            results["cold_start"]["sharded_us"] = cold_sh
            results["cold_start"]["ndev"] = int(jax.device_count())
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)

    # select-path A/B: two-stage + block-skip scan vs legacy concat select.
    # Same arrays, same block size — isolates the selection machinery.
    blk = min(65536, Dh.shape[0])
    t_new = _bench(lambda q: _scan_topk(Dh, q, K, block=blk), qh)
    t_old = _bench(lambda q: _scan_topk_concat(Dh, q, K, block=blk), qh)
    emit(f"scan_select_new,{t_new:.0f},vs_old={t_old/t_new:.2f}x")
    emit(f"scan_select_old,{t_old:.0f},")
    results["scan_select"] = dict(new_us=t_new, old_us=t_old,
                                  speedup=t_old / t_new)

    # offline build cost: gram + projection
    t_gram = _bench(lambda d: jnp.asarray(np.asarray(d)).T @ d, D, iters=2)
    results["gram_naive_us"] = t_gram
    return results


def main():
    run()


if __name__ == "__main__":
    main()
