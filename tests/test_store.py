"""On-disk index artifact store: round-trips, streaming build, durability.

Covers the acceptance surface of the artifact subsystem:
  * save -> load parity (scores AND ids) against the in-memory build path,
    dense and sharded, fp32 and int8, on 1- and 4-device meshes, with row
    counts not divisible by the device count;
  * the streaming build path's peak host memory stays O(block_rows · d) —
    the full corpus array never materialises (tracemalloc-verified);
  * corrupted / partially-written directories are rejected loudly;
  * ``IndexUpdater`` appends persist: append -> reload preserves n and
    search results.
"""
import json
import os
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DenseIndex,
    IndexStore,
    IndexStoreError,
    ShardedDenseIndex,
    StaticPruner,
    save_index,
)
from repro.core.maintenance import IndexUpdater
from repro.core.store import IndexStoreWriter

RNG = np.random.default_rng(11)


def _corpus(n=1003, d=64):
    from repro.data.synthetic import make_corpus
    D, _ = make_corpus("tasb", n_docs=n, d=d, seed=3)
    return jnp.asarray(D)


def _queries(d=64, nq=6):
    return jnp.asarray(RNG.standard_normal((nq, d)), jnp.float32)


def _mesh(ndev):
    if jax.device_count() < ndev:
        pytest.skip(f"needs {ndev} devices, have {jax.device_count()}")
    return jax.make_mesh((ndev,), ("data",))


def _batches(D, rows=200):
    D = np.asarray(D)

    def gen():
        for i in range(0, len(D), rows):
            yield D[i:i + rows]
    return gen


# ---------------------------------------------------------------------------
# round trips: saved artifact == served index, all dtypes / layouts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantize", [False, True])
def test_saved_index_serves_identical_topk_dense(tmp_path, quantize):
    """Acceptance: load path returns identical scores and ids to the
    in-memory build it was saved from (fp32 and int8)."""
    D, Q = _corpus(), _queries()
    pruner = StaticPruner(cutoff=0.5).fit(D)
    idx = pruner.build_index(D, quantize_int8=quantize)
    store = save_index(str(tmp_path / "st"), idx, pruner=pruner)

    loaded = DenseIndex.load(store)
    qh = store.load_pruner().transform_queries(Q)
    s0, i0 = idx.search(pruner.transform_queries(Q), k=10)
    s1, i1 = loaded.search(qh, k=10)
    assert (np.asarray(i0) == np.asarray(i1)).all()
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    # the stored bytes are the served bytes
    disk = np.concatenate([np.array(c) for c in store.iter_chunks()])
    np.testing.assert_array_equal(disk, np.asarray(idx.vectors))


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("quantize", [False, True])
def test_sharded_load_matches_dense_uneven_rows(tmp_path, ndev, quantize):
    """1003 % 4 != 0: load-time device padding must never surface."""
    mesh = _mesh(ndev)
    D, Q = _corpus(1003, 32), _queries(32)
    pruner = StaticPruner(cutoff=0.5).fit(D)
    idx = pruner.build_index(D, quantize_int8=quantize)
    store = save_index(str(tmp_path / "st"), idx, pruner=pruner)

    sidx = ShardedDenseIndex.load(store, mesh)
    assert sidx.n == store.n == 1003
    qh = pruner.transform_queries(Q)
    s0, i0 = idx.search(qh, k=10)
    s1, i1 = sidx.search(qh, k=10)
    assert int(np.asarray(i1).max()) < 1003
    assert (np.asarray(i0) == np.asarray(i1)).all()
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-5, atol=1e-5)


def test_sharded_load_shard_entirely_padding(tmp_path):
    """n=5 on a 4-device mesh: the last shard is 100% device padding —
    the load must synthesise it rather than crash on an out-of-range
    read, and search must still match the dense oracle."""
    mesh = _mesh(4)
    D = jnp.asarray(RNG.standard_normal((5, 8)), jnp.float32)
    Q = _queries(8, nq=3)
    store = save_index(str(tmp_path / "st"), DenseIndex.build(D))
    sidx = ShardedDenseIndex.load(store, mesh)
    assert sidx.n == 5
    s0, i0 = DenseIndex.build(D).search(Q, k=3)
    s1, i1 = sidx.search(Q, k=3)
    assert (np.asarray(i0) == np.asarray(i1)).all()
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-5, atol=1e-5)


def test_store_replacement_at_same_path(tmp_path):
    """Re-committing to an existing path (IndexUpdater.refit) swaps via
    rename-aside — the new store wins and no .tmp/.old residue is left."""
    D1 = _corpus(300, 16)
    D2 = _corpus(421, 16)
    path = str(tmp_path / "st")
    save_index(path, DenseIndex.build(D1))
    # a leftover .old from a previous crashed replacement must not block
    os.makedirs(path + ".old", exist_ok=True)
    save_index(path, DenseIndex.build(D2))
    st = IndexStore.open(path)
    assert st.n == 421
    assert not os.path.exists(path + ".tmp")
    assert not os.path.exists(path + ".old")


def test_bf16_round_trip(tmp_path):
    """bf16 has no native .npy encoding — stored as uint16 views, loaded
    back as logical bf16, bit-identical."""
    D, Q = _corpus(500, 32), _queries(32)
    idx = DenseIndex.build(D, dtype=jnp.bfloat16)
    store = save_index(str(tmp_path / "st"), idx)
    assert store.manifest["dtype"] == "bfloat16"
    loaded = DenseIndex.load(store)
    assert loaded.vectors.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(loaded.vectors).view(np.uint16),
        np.asarray(idx.vectors).view(np.uint16))
    s0, i0 = idx.search(Q, k=10)
    s1, i1 = loaded.search(Q, k=10)
    assert (np.asarray(i0) == np.asarray(i1)).all()


def test_multi_chunk_read_rows(tmp_path):
    """read_rows assembles across chunk boundaries without touching
    chunks outside the range."""
    writer = IndexStore.create(str(tmp_path / "st"))
    parts = [RNG.standard_normal((r, 8)).astype(np.float32)
             for r in (10, 7, 13)]
    for p in parts:
        writer.append(p)
    store = writer.commit()
    full = np.concatenate(parts)
    np.testing.assert_array_equal(store.read_rows(5, 25), full[5:25])
    np.testing.assert_array_equal(store.read_rows(0, 30), full)
    with pytest.raises(ValueError):
        store.read_rows(0, 31)


# ---------------------------------------------------------------------------
# streaming build: memory stays O(block), multi-pass contract enforced
# ---------------------------------------------------------------------------


def test_streaming_build_matches_in_memory(tmp_path):
    D, Q = _corpus(), _queries()
    st = StaticPruner(cutoff=0.5).build_index_to(
        str(tmp_path / "st"), _batches(D))
    assert st.n == D.shape[0]
    assert st.meta["kept_dims"] == st.dim
    mem = StaticPruner(cutoff=0.5).fit(D)
    qh = mem.transform_queries(Q)
    _, i0 = mem.build_index(D).search(qh, k=10)
    _, i1 = DenseIndex.load(st).search(
        st.load_pruner().transform_queries(Q), k=10)
    assert (np.asarray(i0) == np.asarray(i1)).all()


def test_streaming_build_int8_matches_in_memory(tmp_path):
    D, Q = _corpus(), _queries()
    st = StaticPruner(cutoff=0.5).build_index_to(
        str(tmp_path / "st"), _batches(D), quantize_int8=True)
    assert st.dtype == np.int8
    assert st.scale() is not None
    mem = StaticPruner(cutoff=0.5).fit(D)
    qh = mem.transform_queries(Q)
    _, i0 = mem.build_index(D, quantize_int8=True).search(qh, k=10)
    _, i1 = DenseIndex.load(st).search(qh, k=10)
    assert (np.asarray(i0) == np.asarray(i1)).all()


def test_streaming_build_peak_memory_is_o_block(tmp_path):
    """Build a 30000x128 (~15 MiB fp32) index from 1000-row batches that
    are generated on the fly — host peak must stay a small multiple of one
    block (~0.5 MiB), nowhere near the full corpus."""
    n, d, rows = 30000, 128, 1000
    full_bytes = n * d * 4

    def gen():
        rng = np.random.default_rng(0)    # fresh per pass: identical blocks
        for _ in range(n // rows):
            yield rng.standard_normal((rows, d)).astype(np.float32)

    tracemalloc.start()
    tracemalloc.reset_peak()
    st = StaticPruner(cutoff=0.5).build_index_to(str(tmp_path / "st"), gen)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert st.n == n
    assert peak < full_bytes / 4, \
        f"peak host memory {peak} bytes is not O(block) vs corpus {full_bytes}"


def test_streaming_int8_build_two_corpus_passes(tmp_path):
    """The int8 build's absmax piggybacks on the write pass (projected
    blocks spill to disk while the scale accumulates), so when the scale
    stabilises in the first block the corpus is read exactly twice: once
    for the Gram fit, once to project+write. Counted via generator
    restarts. (A corpus whose absmax keeps growing pays one extra bounded
    re-read pass for the stale blocks — see the spill test below.)"""
    D = np.asarray(_corpus(900, 48))
    # first block dominates the dynamic range per-dim: the provisional
    # scale equals the final scale from block 0, so no block goes stale
    blocks = [3.0 * D[:300], np.asarray(D[300:600]), np.asarray(D[600:])]
    blocks = [np.asarray(b, np.float32) for b in blocks]
    calls = {"n": 0}

    def gen():
        calls["n"] += 1
        yield from blocks

    st = StaticPruner(cutoff=0.5).build_index_to(
        str(tmp_path / "st"), gen, quantize_int8=True)
    assert calls["n"] == 2, f"expected 2 corpus passes, got {calls['n']}"
    assert st.n == 900 and st.dtype == np.int8
    assert st.meta["requant_blocks"] == 0

    # an already-fitted pruner needs only the write pass
    pre = StaticPruner(cutoff=0.5)
    pre.fit_streaming(blocks)
    calls["n"] = 0
    st2 = pre.build_index_to(str(tmp_path / "st2"), gen, quantize_int8=True)
    assert calls["n"] == 1
    # identical artifact either way: same scale, same quantised rows
    np.testing.assert_array_equal(st.scale(), st2.scale())
    np.testing.assert_array_equal(st.read_rows(0, 900), st2.read_rows(0, 900))


def test_streaming_int8_spill_is_int8_and_bit_identical(tmp_path):
    """The spill is int8 (4x fewer bytes than the old f32 spill), blocks
    whose provisional scale went stale are re-projected in one bounded
    re-read pass, and the committed artifact is BIT-IDENTICAL to
    quantising exact f32 projections under the final corpus-wide scale."""
    from repro.core import pca as _pca
    D = np.asarray(_corpus(900, 48))
    blocks = [np.asarray(D[i:i + 300], np.float32) for i in range(0, 900, 300)]
    calls = {"n": 0}

    def gen():
        calls["n"] += 1
        yield from blocks

    st = StaticPruner(cutoff=0.5).build_index_to(
        str(tmp_path / "st"), gen, quantize_int8=True)
    # generic corpus: absmax keeps growing -> fit + write + bounded re-read
    assert calls["n"] <= 3
    m = st.meta["kept_dims"]
    assert st.meta["spill_dtype"] == "int8"
    assert st.meta["spill_bytes"] == 900 * m          # int8: one byte/value
    assert 0 <= st.meta["requant_blocks"] <= len(blocks)

    # oracle: exact f32 projections quantised under the final scale
    pre = StaticPruner(cutoff=0.5)
    pre.fit_streaming(blocks)
    proj = np.concatenate([
        np.asarray(_pca.transform(jnp.asarray(b), pre.state, m), np.float32)
        for b in blocks])
    scale = (np.maximum(np.abs(proj).max(axis=0), 1e-12) / 127.0) \
        .astype(np.float32)
    want = np.clip(np.round(proj / scale[None, :]), -127, 127).astype(np.int8)
    np.testing.assert_array_equal(st.scale(), scale)
    np.testing.assert_array_equal(st.read_rows(0, 900), want)


def test_streaming_int8_build_peak_memory_is_o_block(tmp_path):
    """The absmax fusion spills projected blocks to disk — host peak must
    stay O(block) for the int8 path too, not grow to the corpus."""
    n, d, rows = 30000, 128, 1000
    full_bytes = n * d * 4

    def gen():
        rng = np.random.default_rng(0)    # fresh per pass: identical blocks
        for _ in range(n // rows):
            yield rng.standard_normal((rows, d)).astype(np.float32)

    # one untraced build first: a process's first build also allocates
    # one-time state (lazy imports, jit compiles) whose size depends on
    # what ran before in the process, not on the corpus
    StaticPruner(cutoff=0.5).build_index_to(str(tmp_path / "warm"), gen,
                                            quantize_int8=True)
    tracemalloc.start()
    tracemalloc.reset_peak()
    st = StaticPruner(cutoff=0.5).build_index_to(str(tmp_path / "st"), gen,
                                                 quantize_int8=True)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert st.n == n and st.dtype == np.int8
    assert peak < full_bytes / 4, \
        f"peak host memory {peak} bytes is not O(block) vs corpus {full_bytes}"


def test_streaming_build_rejects_one_shot_generator(tmp_path):
    D = _corpus(400, 16)
    gen = iter([np.asarray(D[:200]), np.asarray(D[200:])])
    with pytest.raises(TypeError, match="multiple passes"):
        StaticPruner(cutoff=0.5).build_index_to(str(tmp_path / "st"), gen)


def test_writer_rejects_mismatched_chunks(tmp_path):
    w = IndexStoreWriter(str(tmp_path / "st"))
    w.append(np.zeros((4, 8), np.float32))
    with pytest.raises(ValueError, match="chunk mismatch"):
        w.append(np.zeros((4, 9), np.float32))
    with pytest.raises(ValueError, match="chunk mismatch"):
        w.append(np.zeros((4, 8), np.int8))
    w.abort()


# ---------------------------------------------------------------------------
# durability: partial writes and corruption rejected loudly
# ---------------------------------------------------------------------------


def test_uncommitted_tmp_dir_rejected(tmp_path):
    """A crash mid-build leaves only <dir>.tmp — open() must refuse both
    the missing final dir and the tmp dir itself."""
    w = IndexStoreWriter(str(tmp_path / "st"))
    w.append(np.zeros((4, 8), np.float32))
    # no commit: simulate the crash
    with pytest.raises(IndexStoreError, match="not a committed"):
        IndexStore.open(str(tmp_path / "st"))
    assert not os.path.exists(str(tmp_path / "st"))
    assert os.path.exists(str(tmp_path / "st.tmp"))


def test_missing_chunk_rejected(tmp_path):
    D = _corpus(300, 16)
    st = save_index(str(tmp_path / "st"), DenseIndex.build(D))
    os.remove(os.path.join(st.path, st.manifest["chunks"][0]["file"]))
    with pytest.raises(IndexStoreError, match="missing chunk"):
        IndexStore.open(st.path)


def test_wrong_shape_chunk_rejected(tmp_path):
    D = _corpus(300, 16)
    st = save_index(str(tmp_path / "st"), DenseIndex.build(D))
    f = os.path.join(st.path, st.manifest["chunks"][0]["file"])
    np.save(f, np.zeros((7, 16), np.float32))
    with pytest.raises(IndexStoreError, match="shape"):
        IndexStore.open(st.path)


def test_row_count_mismatch_rejected(tmp_path):
    D = _corpus(300, 16)
    st = save_index(str(tmp_path / "st"), DenseIndex.build(D))
    mpath = os.path.join(st.path, "manifest.json")
    with open(mpath) as f:
        m = json.load(f)
    m["n"] = 9999
    with open(mpath, "w") as f:
        json.dump(m, f)
    with pytest.raises(IndexStoreError, match="manifest n"):
        IndexStore.open(st.path)


def test_unsupported_version_rejected(tmp_path):
    D = _corpus(300, 16)
    st = save_index(str(tmp_path / "st"), DenseIndex.build(D))
    mpath = os.path.join(st.path, "manifest.json")
    with open(mpath) as f:
        m = json.load(f)
    m["format_version"] = 99
    with open(mpath, "w") as f:
        json.dump(m, f)
    with pytest.raises(IndexStoreError, match="format_version"):
        IndexStore.open(st.path)


# ---------------------------------------------------------------------------
# incremental growth through the store
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantize", [False, True])
def test_updater_append_persists_across_reload(tmp_path, quantize):
    D, Q = _corpus(800, 48), _queries(48)
    up = IndexUpdater.build(D, cutoff=0.5, quantize_int8=quantize,
                            store_path=str(tmp_path / "st"))
    new = _corpus(900, 48)[800:870]
    up.add_documents(new)
    assert up.index.n == 870

    # reload from disk: same n, identical search results
    up2 = IndexUpdater.from_store(str(tmp_path / "st"))
    assert up2.index.n == 870
    s0, i0 = up.search(Q, k=10)
    s1, i1 = up2.search(Q, k=10)
    assert (np.asarray(i0) == np.asarray(i1)).all()
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-5, atol=1e-5)
    # and a freshly appended doc is findable after reload
    _, ids = up2.search(new[3][None, :], k=5)
    assert 803 in np.asarray(ids)[0].tolist()


def test_updater_append_sharded_reload(tmp_path):
    """Append on the dense updater, reload the grown artifact sharded."""
    mesh = _mesh(4)
    D, Q = _corpus(801, 32), _queries(32)
    up = IndexUpdater.build(D, cutoff=0.5, store_path=str(tmp_path / "st"))
    up.add_documents(_corpus(900, 32)[801:850])
    sidx = ShardedDenseIndex.load(str(tmp_path / "st"), mesh)
    assert sidx.n == 850
    qh = up.pruner.transform_queries(Q)
    _, i0 = up.index.search(qh, k=10)
    _, i1 = sidx.search(qh, k=10)
    assert (np.asarray(i0) == np.asarray(i1)).all()


# ---------------------------------------------------------------------------
# serve-path parity: the restart really serves what the build served
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("quantize", [False, True])
def test_served_topk_identical_after_reload(tmp_path, sharded, quantize):
    """The serve.py restart path end to end: build+save, then serve from
    the artifact through the same RetrievalServer — identical scores and
    ids per query, dense and sharded, fp32 and int8."""
    from repro.launch.serve import RetrievalServer
    if sharded and jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    D, Q = _corpus(1003, 32), np.asarray(_queries(32, nq=8))
    pruner = StaticPruner(cutoff=0.5).fit(D)
    idx = pruner.build_index(D, quantize_int8=quantize)
    store = save_index(str(tmp_path / "st"), idx, pruner=pruner)

    if sharded:
        mesh = _mesh(4)
        served = ShardedDenseIndex.load(store, mesh)
    else:
        served = DenseIndex.load(store)
    s_build = RetrievalServer(idx, pruner, k=10, max_batch=4)
    s_load = RetrievalServer(served, store.load_pruner(), k=10, max_batch=4)
    try:
        for q in Q:
            sb, ib = s_build.query(q)
            sl, il = s_load.query(q)
            np.testing.assert_array_equal(ib, il)
            np.testing.assert_allclose(sb, sl, rtol=1e-5, atol=1e-5)
    finally:
        s_build.close()
        s_load.close()


def test_append_crash_window_leaves_valid_store(tmp_path):
    """An orphan chunk blob without a manifest swap (crash between the two
    append steps) must not invalidate the store."""
    D = _corpus(300, 16)
    st = save_index(str(tmp_path / "st"), DenseIndex.build(D))
    np.save(os.path.join(st.path, "vectors_999999.npy"),
            np.zeros((5, 16), np.float32))
    re = IndexStore.open(st.path)   # orphan blob ignored
    assert re.n == 300


def test_truncated_chunk_rejected(tmp_path):
    """A torn write (crash mid-rollout/copy: npy header intact, payload
    short) must be rejected by open() as an IndexStoreError diagnosis,
    not surface as a raw mmap failure."""
    D = _corpus(300, 16)
    st = save_index(str(tmp_path / "st"), DenseIndex.build(D))
    f = os.path.join(st.path, st.manifest["chunks"][0]["file"])
    with open(f, "r+b") as fh:
        fh.truncate(os.path.getsize(f) // 2)
    with pytest.raises(IndexStoreError, match="truncated"):
        IndexStore.open(st.path)
