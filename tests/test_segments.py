"""Live growth on the paged index: extents, scales, store segments, swaps.

The acceptance surface of live growth (``PagedIndex`` extents mirrored to
``IndexStore`` segments):
  * with mixed per-extent scales, ids/ordering match a float64 oracle over
    the per-extent dequantised rows;
  * appends roll over into fresh extents at ``seal_rows`` with global ids
    in order, never clip (per-extent scales widen) and never recompile in
    steady state (jit-cache-size pinned);
  * a pre-segment artifact opens as a single base extent holding the
    stored bytes, and a grown artifact round-trips losslessly;
  * ``IndexUpdater`` mirrors every append to the store bit for bit,
    compacts by pointer swaps (racing appends included), and
    ``RetrievalServer.swap_index`` under live append+query load drops no
    reply and never serves from a half-swapped index.
"""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_oracle import assert_matches_oracle
from repro.core import (
    DenseIndex,
    IndexStore,
    IndexStoreError,
    ShardedDenseIndex,
    StaticPruner,
    save_index,
)
from repro.core.maintenance import IndexUpdater
from repro.core.paged import PagedIndex, _jit_cache_sizes
from repro.core.quantization import quantize_int8_per_dim

RNG = np.random.default_rng(17)


def _corpus(n=1003, d=48, seed=3, domain_seed=None):
    from repro.data.synthetic import make_corpus
    D, _ = make_corpus("tasb", n_docs=n, d=d, seed=seed,
                       domain_seed=domain_seed)
    return np.asarray(D, np.float32)


def _queries(d=48, nq=7):
    return jnp.asarray(RNG.standard_normal((nq, d)), jnp.float32)


def _mesh(ndev):
    if jax.device_count() < ndev:
        pytest.skip(f"needs {ndev} devices, have {jax.device_count()}")
    return jax.make_mesh((ndev,), ("data",))


def _delta_extents(index):
    return [e for e in index.storage.extents if e.kind == "delta"]


# ---------------------------------------------------------------------------
# mixed scales: the oracle over per-extent dequantised rows
# ---------------------------------------------------------------------------


def test_mixed_scale_ids_match_f32_oracle():
    """Per-extent scales (an OOD append widened the delta's): ids and
    ordering must exactly match the float64 oracle over the per-extent
    DEQUANTISED rows — strict correctness, not best-effort."""
    D = _corpus(600, 32)
    base8, base_scale = quantize_int8_per_dim(jnp.asarray(D))
    base = DenseIndex(vectors=base8, scale=base_scale)
    pg = PagedIndex.from_index(base, page_rows=64, seal_rows=128)
    ood = np.concatenate([_corpus(80, 32, seed=9) * 12.0,
                          _corpus(40, 32, seed=11)])
    pg = pg.append(ood)
    deltas = _delta_extents(pg)
    assert len(deltas) == 1
    assert not np.array_equal(deltas[0].scale, np.asarray(base_scale))
    Q = _queries(32)
    assert_matches_oracle(pg, Q, pg.search(Q, k=10), 10)


# ---------------------------------------------------------------------------
# growth: rollover, widening, no clipping, no recompiles
# ---------------------------------------------------------------------------


def test_append_rollover_and_global_ids():
    D = _corpus(500, 24)
    pg = PagedIndex.from_index(DenseIndex.build(jnp.asarray(D)),
                               page_rows=32, seal_rows=100)
    extra = _corpus(750, 24, seed=5)[500:]
    pg = pg.append(extra)
    assert pg.n == 750
    deltas = _delta_extents(pg)
    assert [e.n_rows for e in deltas] == [100, 100, 50]   # seal, roll over
    assert [e.row_offset for e in deltas] == [500, 600, 700]
    assert [e.sealed for e in deltas] == [True, True, False]
    for gid in (500, 601, 749):
        _, ids = pg.search(jnp.asarray(extra[gid - 500][None, :]), k=5)
        assert gid in np.asarray(ids)[0].tolist()


def test_ood_append_widens_scale_never_clips():
    """The frozen-scale clip problem, killed at the root: a 50x OOD append
    lands with a widened per-delta scale; every stored value round-trips
    within half an LSB of its f32 source — nothing saturates."""
    D = _corpus(400, 24)
    up = IndexUpdater.build(jnp.asarray(D), cutoff=0.5, quantize_int8=True,
                            delta_capacity=256)
    in_dom = _corpus(500, 24, domain_seed=5)[400:480]   # same encoder basis
    up.add_documents(jnp.asarray(in_dom))
    scale0 = _delta_extents(up.index)[0].scale
    up.add_documents(50.0 * jnp.asarray(in_dom[:40]))
    st = up.index.storage
    ei = st.extents.index(_delta_extents(up.index)[0])
    d = st.extents[ei]
    scale1 = d.scale
    assert (scale1 >= scale0).all() and (scale1 > scale0).any()
    stored = st.extent_rows(ei).astype(np.float32)
    err = np.abs(stored * scale1[None, :] - d.raw)
    assert (err <= scale1[None, :] / 2 + 1e-7).all(), \
        "a stored value clipped instead of the scale widening"
    assert up.clip_fraction == 0.0
    assert up.scale_divergence() > 4.0
    assert up.needs_refit(jnp.asarray(in_dom))    # scale policy trips
    # drift alone would not have caught it (energy ratio is scale-invariant)
    assert up.drift_score(50.0 * jnp.asarray(in_dom[:40])) > 0.8


def test_steady_state_appends_do_not_recompile():
    """Fixed-shape dispatch contract: once the page walk is warm, appends
    (any live count, widening or not) add ZERO jit cache entries."""
    D = _corpus(300, 24)
    pruner = StaticPruner(cutoff=0.5).fit(jnp.asarray(D))
    pg = PagedIndex.from_index(
        pruner.build_index(jnp.asarray(D), quantize_int8=True),
        page_rows=64, seal_rows=512)
    W, mean = pruner.projection()
    # warm: open the delta, then extend once (appends themselves are
    # host-side writes re-pushed at fixed shapes)
    warm = np.asarray(pruner.prune_index(
        jnp.asarray(_corpus(20, 24, seed=7))), np.float32)
    pg = pg.append(warm)
    pg = pg.append(0.5 * warm[:15])
    jax.block_until_ready(
        pg.search_projected(jnp.asarray(_queries(24, 4)), W, k=5,
                            mean=mean))
    j0 = _jit_cache_sizes()
    for i in range(6):
        pg = pg.append(np.asarray(pruner.prune_index(
            jnp.asarray(_corpus(15, 24, seed=20 + i))), np.float32))
        jax.block_until_ready(
            pg.search_projected(jnp.asarray(_queries(24, 4)), W, k=5,
                                mean=mean))
    assert _jit_cache_sizes() == j0, \
        "an append recompiled the steady-state search path"


# ---------------------------------------------------------------------------
# store layout: backward compat + segmented round trip
# ---------------------------------------------------------------------------


def test_pre_segment_artifact_opens_as_single_base(tmp_path):
    """Backward compat: an artifact written before segments exist reads as
    one base segment, and PagedIndex.load pages exactly its stored bytes
    (and scale) as one base extent."""
    D = _corpus(500, 32)
    pruner = StaticPruner(cutoff=0.5).fit(jnp.asarray(D))
    idx = pruner.build_index(jnp.asarray(D), quantize_int8=True)
    store = save_index(str(tmp_path / "st"), idx, pruner=pruner)
    assert not store.is_segmented
    views = store.segments()
    assert len(views) == 1 and views[0].kind == "base"
    assert views[0].n == store.n and views[0].offset == 0
    pg = PagedIndex.load(store)
    assert [(e.kind, e.sealed) for e in pg.storage.extents] == [("base",
                                                                True)]
    np.testing.assert_array_equal(pg.storage.extent_rows(0),
                                  store.read_rows(0, store.n))
    np.testing.assert_array_equal(pg.storage.extents[0].scale,
                                  store.scale())
    Q = _queries(32)
    qh = pruner.transform_queries(Q)
    assert_matches_oracle(pg, qh, pg.search(qh, k=10), 10)


@pytest.mark.parametrize("quantize", [False, True])
def test_segmented_store_round_trip(tmp_path, quantize):
    """save -> open -> load: per-segment scales, capacities, and search
    results survive; the manifest's global view stays validation-clean."""
    D = _corpus(600, 32)
    pruner = StaticPruner(cutoff=0.5).fit(jnp.asarray(D))
    pg = PagedIndex.from_index(
        pruner.build_index(jnp.asarray(D), quantize_int8=quantize),
        page_rows=64, seal_rows=128)
    extra = _corpus(800, 32, seed=5)[600:]
    pg = pg.append(np.asarray(pruner.prune_index(jnp.asarray(extra)),
                              np.float32))
    store = save_index(str(tmp_path / "st"), pg, pruner=pruner)
    re = IndexStore.open(store.path)            # fresh open: full validation
    assert re.is_segmented and re.n == 800
    assert [v.kind for v in re.segments()] == ["base", "delta", "delta"]
    assert re.segments()[1].capacity == 128
    loaded = PagedIndex.load(re)
    assert loaded.n == pg.n
    Q = _queries(32)
    qh = pruner.transform_queries(Q)
    s0, i0 = pg.search(qh, k=10)
    s1, i1 = loaded.search(qh, k=10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_mixed_scale_store_refuses_flat_load(tmp_path):
    D = _corpus(300, 24)
    up = IndexUpdater.build(jnp.asarray(D), cutoff=0.5, quantize_int8=True,
                            store_path=str(tmp_path / "st"),
                            delta_capacity=128)
    up.add_documents(9.0 * jnp.asarray(_corpus(40, 24, seed=5)))
    st = IndexStore.open(str(tmp_path / "st"))
    assert not st.flat_loadable
    with pytest.raises(IndexStoreError, match="PagedIndex.load"):
        DenseIndex.load(st)
    mesh = _mesh(1)
    with pytest.raises(IndexStoreError, match="PagedIndex.load"):
        ShardedDenseIndex.load(st, mesh)


def test_updater_store_mirror_is_bit_identical(tmp_path):
    """Disk and memory never diverge: after appends (including a widening
    rewrite), the stored delta bytes equal the served delta bytes."""
    D = _corpus(400, 24)
    up = IndexUpdater.build(jnp.asarray(D), cutoff=0.5, quantize_int8=True,
                            store_path=str(tmp_path / "st"),
                            delta_capacity=256)
    up.add_documents(jnp.asarray(_corpus(60, 24, seed=5)))
    up.add_documents(30.0 * jnp.asarray(_corpus(30, 24, seed=6)))  # widen
    up.add_documents(jnp.asarray(_corpus(20, 24, seed=7)))
    st = IndexStore.open(str(tmp_path / "st"))
    views = st.segments()
    pst = up.index.storage
    assert len(views) == len(pst.extents) > 1
    for i, (v, e) in enumerate(zip(views, pst.extents)):
        np.testing.assert_array_equal(v.read_rows(0, v.n),
                                      pst.extent_rows(i))
        np.testing.assert_array_equal(v.scale(), e.scale)
    # and a cold start reproduces the exact same search results
    up2 = IndexUpdater.from_store(str(tmp_path / "st"))
    Q = _queries(24)
    s0, i0 = up.search(Q, k=10)
    s1, i1 = up2.search(Q, k=10)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_store_append_migrating_widen(tmp_path):
    """Store-level scale migration (no f32 staging available): an append
    that would clip widens the segment scale and requantises its chunks —
    bounded by the segment, within half an old LSB of exact."""
    D = _corpus(300, 16)
    pruner = StaticPruner(cutoff=0.5).fit(jnp.asarray(D))
    idx = pruner.build_index(jnp.asarray(D), quantize_int8=True)
    st = save_index(str(tmp_path / "st"), idx, pruner=pruner)
    name = st.add_delta(scale=np.full((8,), 0.01, np.float32), capacity=4096)
    st.append_migrating(np.full((4, 8), 0.5, np.float32))       # fits
    before = IndexStore.open(st.path).segments()[1].scale()
    widened = st.append_migrating(np.full((3, 8), 7.0, np.float32))
    assert widened
    re = IndexStore.open(st.path)
    v = re.segments()[1]
    assert v.n == 7
    after = v.scale()
    assert (after >= before).all() and (after > before).any()
    vals = v.read_rows(0, 7).astype(np.float32) * after[None, :]
    np.testing.assert_allclose(vals[:4], 0.5, atol=float(after.max()))
    np.testing.assert_allclose(vals[4:], 7.0, atol=float(after.max()) / 2)
    # base untouched by the delta migration
    np.testing.assert_array_equal(re.segments()[0].scale(),
                                  np.asarray(idx.scale))


def test_store_append_migrating_base_segment(tmp_path):
    """Regression: widening the BASE segment's scale (pre-segment store,
    the unbounded-rewrite case segmenting exists to avoid) must keep the
    top-level manifest's scale_file in sync with the base entry — the old
    blob is deleted by the rewrite, and a stale pointer would make the
    store permanently unopenable."""
    D = _corpus(300, 16)
    pruner = StaticPruner(cutoff=0.5).fit(jnp.asarray(D))
    idx = pruner.build_index(jnp.asarray(D), quantize_int8=True)
    st = save_index(str(tmp_path / "st"), idx, pruner=pruner)
    scale0 = np.asarray(idx.scale)
    widened = st.append_migrating(
        50.0 * np.asarray(pruner.prune_index(jnp.asarray(D[:5])), np.float32))
    assert widened
    re = IndexStore.open(st.path)          # must validate cleanly
    assert re.n == 305
    base = re.segments()[0]
    assert (base.scale() >= scale0).all() and (base.scale() > scale0).any()
    np.testing.assert_array_equal(np.load(
        os.path.join(re.path, re.manifest["scale_file"])), base.scale())
    # still servable end to end
    loaded = PagedIndex.load(re)
    _, ids = loaded.search(pruner.transform_queries(_queries(16)), k=5)
    assert np.asarray(ids).max() < 305


def test_replace_segment_crash_orphans_ignored(tmp_path):
    D = _corpus(200, 16)
    st = save_index(str(tmp_path / "st"), DenseIndex.build(jnp.asarray(D)))
    name = st.add_delta(capacity=64)
    st.append(np.ones((4, 16), np.float32), segment=name)
    # orphan blobs from a crashed replace (blob written, manifest not
    # swapped) must not invalidate the store
    np.save(os.path.join(st.path, "vectors_999998.npy"),
            np.zeros((2, 16), np.float32))
    re = IndexStore.open(st.path)
    assert re.n == 204
    st.replace_segment(name, [np.full((6, 16), 2.0, np.float32)])
    re = IndexStore.open(st.path)
    assert re.n == 206
    np.testing.assert_array_equal(re.segments()[1].read_rows(0, 6),
                                  np.full((6, 16), 2.0, np.float32))


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------


def test_compact_merges_to_single_fresh_base(tmp_path):
    """Compaction folds every delta extent into the base by pointer swaps:
    the pages move to the pool, each extent keeps its bytes and scale, and
    on disk only the lifecycle block changes."""
    D = _corpus(500, 24)
    up = IndexUpdater.build(jnp.asarray(D), cutoff=0.5, quantize_int8=True,
                            store_path=str(tmp_path / "st"),
                            delta_capacity=128, page_rows=32)
    extra = _corpus(700, 24, seed=5)[500:]
    up.add_documents(jnp.asarray(extra))
    assert up.delta_fraction > 0
    before = up.index.storage
    up.compact()
    st = up.index.storage
    assert up.index.delta_pages == 0 and up.index.n == 700
    assert up.compactions == 1 and up.delta_fraction == 0.0
    assert up.last_compaction["pages_moved"] > 0
    for i, e in enumerate(st.extents):
        assert e.kind == "base" and e.sealed
        np.testing.assert_array_equal(st.extent_rows(i),
                                      before.extent_rows(i))
        np.testing.assert_array_equal(e.scale, before.extents[i].scale)
    re = IndexStore.open(str(tmp_path / "st"))
    assert re.n == 700
    assert all(e["kind"] == "base" for e in re.manifest["paged"]["extents"])
    assert not os.path.exists(str(tmp_path / "st") + ".tmp")
    # every doc still retrievable
    _, ids = up.search(jnp.asarray(D[123][None, :]), k=3)
    assert 123 in np.asarray(ids)[0].tolist()
    # further appends land on the compacted base's store
    up.add_documents(jnp.asarray(_corpus(30, 24, seed=9)))
    assert IndexStore.open(str(tmp_path / "st")).n == 730


def test_compact_reconciles_racing_appends():
    """Appends that race a background compaction must survive it: the
    compaction and the append serialise on the updater lock, so whichever
    lands second builds on the first and no row is lost."""
    D = _corpus(400, 24)
    up = IndexUpdater.build(jnp.asarray(D), cutoff=0.5, delta_capacity=256)
    up.add_documents(jnp.asarray(_corpus(50, 24, seed=5)))
    racing = _corpus(30, 24, seed=6)
    th = up.compact_async()
    up.add_documents(jnp.asarray(racing))        # races the compaction
    th.join(timeout=60.0)
    assert not th.is_alive()
    assert up.index.n == 480
    assert up.compactions == 1
    # the racing rows answer exactly as in a brute-force search over all
    # 480 rows under the post-compaction projection, and they are found
    _, ids = up.search(jnp.asarray(racing), k=5)
    ids = np.asarray(ids)
    rows = np.asarray(up.pruner.prune_index(jnp.asarray(
        np.concatenate([D, _corpus(50, 24, seed=5), racing]))))
    q = np.asarray(up.pruner.transform_queries(jnp.asarray(racing)))
    exact = np.argsort(-(q @ rows.T), axis=1, kind="stable")[:, :5]
    assert [set(r) for r in ids.tolist()] == [set(r) for r in exact.tolist()]
    assert sum(450 + i in ids[i] for i in range(len(racing))) >= len(racing) // 2


# ---------------------------------------------------------------------------
# serving: atomic swap under live traffic
# ---------------------------------------------------------------------------


def _unit_corpus(n, d=64, seed=77):
    rng = np.random.default_rng(seed)
    D = rng.standard_normal((n, d)).astype(np.float32)
    D /= np.linalg.norm(D, axis=1, keepdims=True)
    return D


def test_swap_under_load_soak():
    """Live appends + swaps while concurrent clients hammer the server:
    every reply must answer its own query (self-retrieval) — a dropped
    reply would hang its client's timeout, a half-swapped segment set
    would misroute ids — and the steady-state jit cache must not grow
    (appends never stall serving on a compile)."""
    from repro.launch.serve import RetrievalServer
    D = _unit_corpus(96)
    extra = _unit_corpus(200, seed=78)
    pruner = StaticPruner(cutoff=0.25).fit(jnp.asarray(D))
    base = DenseIndex.build(pruner.prune_index(jnp.asarray(D)))
    pg = PagedIndex.from_index(base, seal_rows=4096)
    server = RetrievalServer(pg, pruner, k=1, max_batch=8, pipeline_depth=3)
    up = IndexUpdater(pruner=pruner, index=pg, server=server,
                      delta_capacity=4096)
    try:
        # warm the steady state: open the delta, extend it once, and
        # answer a query. Those 8 scaled rows get ids 104..111; the
        # clients below never query them.
        up.add_documents(jnp.asarray(extra[:8]))
        up.add_documents(jnp.asarray(0.5 * extra[:8]))
        server.query(D[0])
        j0 = _jit_cache_sizes()
        swaps0 = server.swap_count
        n_known = 96 + 8                     # rows safe to self-retrieve

        stop = threading.Event()
        failures: list = []

        def appender():
            i = 16
            while not stop.is_set() and i + 8 <= len(extra):
                up.add_documents(jnp.asarray(extra[i:i + 8]))
                i += 8
                stop.wait(0.002)

        def client(cid):
            rng = np.random.default_rng(cid)
            try:
                for _ in range(40):
                    doc = int(rng.integers(0, n_known))
                    q = D[doc] if doc < 96 else extra[doc - 96]
                    _, ids = server.query(q, timeout=30.0)
                    if int(ids[0]) != doc:
                        failures.append((cid, doc, int(ids[0])))
            except BaseException as e:       # noqa: BLE001
                failures.append((cid, "exception", repr(e)))

        app = threading.Thread(target=appender, daemon=True)
        clients = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(8)]
        app.start()
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=120.0)
        stop.set()
        app.join(timeout=30.0)
        assert not failures, f"misrouted/dropped replies: {failures[:5]}"
        assert server.swap_count > swaps0, "appends never swapped the index"
        assert _jit_cache_sizes() == j0, \
            "live appends recompiled the serving path"
        # every appended doc is now retrievable through the server
        n_final = up.index.n
        for gid in (100, n_final - 1):
            _, ids = server.query(extra[gid - 96])
            assert int(ids[0]) == gid
        # close() drains — zero dropped replies at shutdown too
        replies = [server.submit(D[i % 96]) for i in range(50)]
        server.close()
        for i, r in enumerate(replies):
            _, ids = r.get(timeout=5.0)
            assert int(ids[0]) == i % 96
    finally:
        server.close()


def test_swap_during_compaction_under_traffic():
    """Background compaction finishes and swaps mid-serve; queries before,
    during, and after must all self-retrieve."""
    from repro.launch.serve import RetrievalServer
    D = _unit_corpus(96)
    extra = _unit_corpus(64, seed=79)
    pruner = StaticPruner(cutoff=0.25).fit(jnp.asarray(D))
    base = DenseIndex.build(pruner.prune_index(jnp.asarray(D)),
                            quantize_int8=True)
    pg = PagedIndex.from_index(base, seal_rows=1024)
    server = RetrievalServer(pg, pruner, k=1, max_batch=8, pipeline_depth=3)
    up = IndexUpdater(pruner=pruner, index=pg, server=server,
                      delta_capacity=1024)
    try:
        up.add_documents(jnp.asarray(extra))
        swaps_before = server.swap_count
        th = up.compact_async()
        ok = 0
        while th.is_alive():
            doc = int(RNG.integers(0, 160))
            q = D[doc] if doc < 96 else extra[doc - 96]
            _, ids = server.query(q, timeout=30.0)
            assert int(ids[0]) == doc
            ok += 1
        th.join(timeout=60.0)
        assert server.swap_count == swaps_before + 1
        assert up.index.delta_pages == 0
        for doc in (0, 95, 96, 159):
            q = D[doc] if doc < 96 else extra[doc - 96]
            _, ids = server.query(q, timeout=30.0)
            assert int(ids[0]) == doc
    finally:
        server.close()


def test_reply_carries_completion_timestamp():
    from repro.launch.serve import RetrievalServer
    D = _unit_corpus(32)
    pruner = StaticPruner(cutoff=0.25).fit(jnp.asarray(D))
    index = DenseIndex.build(pruner.prune_index(jnp.asarray(D)))
    server = RetrievalServer(index, pruner, k=1, max_batch=8)
    try:
        import time
        t0 = time.perf_counter()
        reply = server.submit(D[3])
        _, ids = reply.get(timeout=10.0)
        assert reply.completed_at is not None
        assert t0 < reply.completed_at <= time.perf_counter()
    finally:
        server.close()


# ---------------------------------------------------------------------------
# serve CLI: paged storage has no sharded form yet
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("flag,value", [("--live-append", "100"),
                                        ("--page-pool", "4")])
def test_paged_flags_refuse_sharded(monkeypatch, capsys, flag, value):
    """``--live-append`` and ``--page-pool`` select the paged index, which
    has no per-shard pools: with ``--sharded`` the CLI refuses before it
    builds anything."""
    import sys
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", ["serve", flag, value, "--sharded"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 2
    assert (f"{flag} does not compose with --sharded yet"
            in capsys.readouterr().err)
