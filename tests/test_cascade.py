"""Cascade retrieval: oracle parity, monotonicity, multi-resolution store.

Covers the acceptance surface of the two-stage cascade:
  * with N·k >= n the dense cascade is BIT-identical (scores AND ids) to
    the full-m exact ``search_projected`` — f32 and int8 full resolution,
    jnp and pallas backends — and a paged cascade (base + live deltas in
    both resolutions) returns the float64 oracle's exact top-k;
  * recall@10 against the full-m oracle is non-decreasing in the
    shortlist depth N (a superset shortlist rescored exactly can only
    keep or add true top-k members) and reaches 1.0 at N·k >= n;
  * a stored coarse resolution round-trips bit-identically and corrupted
    multi-resolution manifests are rejected loudly (row mismatch,
    non-nested m, duplicate m, missing blobs);
  * ``CascadeIndex`` validates row alignment and nesting, and a paged
    cascade grows BOTH resolutions in lockstep with zero steady-state
    recompiles.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paged_oracle import assert_matches_oracle
from repro.core import (
    CascadeIndex,
    DenseIndex,
    IndexStore,
    IndexStoreError,
    StaticPruner,
    save_index,
)

RNG = np.random.default_rng(17)


def _fixture(n=500, d=64, nq=5, seed=3):
    from repro.data.synthetic import make_corpus
    D, _ = make_corpus("tasb", n_docs=n, d=d, seed=seed)
    pruner = StaticPruner(cutoff=0.5).fit(jnp.asarray(D))
    pruned = pruner.prune_index(jnp.asarray(D))
    W, mean = pruner.projection()
    Q = jnp.asarray(RNG.standard_normal((nq, d)), jnp.float32)
    return pruned, W, mean, Q


def _full_nf(n, k):
    """n_factor making the shortlist cover the corpus: N·k >= n."""
    return -(-n // k)


# ---------------------------------------------------------------------------
# oracle parity: shortlist covering the corpus == full-m exact search
# ---------------------------------------------------------------------------


# interpret-mode pallas unrolls nk extraction passes per strip, so its
# parity configs run on a deliberately tiny corpus (same code path, same
# geometry family — just tractable off-TPU)
@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("backend,n", [("jnp", 500), ("pallas", 64)])
def test_cascade_bitwise_oracle_parity_dense(quant, backend, n):
    """Acceptance: N·k >= n makes the cascade bit-identical — scores AND
    ids — to the single-resolution full-m search, because the exact
    rescore sees every row and shares the oracle's dot shape family."""
    k = 8
    pruned, W, mean, Q = _fixture(n=n)
    cas = CascadeIndex.build(pruned, m_coarse=max(2, pruned.shape[1] // 2),
                             n_factor=_full_nf(n, k), quantize_int8=quant,
                             backend=backend)
    oracle = DenseIndex.build(pruned, quantize_int8=quant, backend=backend)
    s0, i0 = oracle.search_projected(Q, W, k=k, mean=mean)
    s1, i1 = cas.search_projected(Q, W, k=k, mean=mean)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


@pytest.mark.parametrize("quant", [False, True])
def test_cascade_bitwise_oracle_parity_segmented(quant):
    """Paged cascade (base + live deltas in both resolutions) at covering
    depth: its answer, like the paged full-m search's, is the float64
    oracle's exact top-k over the full resolution."""
    k, n = 8, 400
    pruned, W, mean, Q = _fixture(n=n)
    extra = RNG.standard_normal((90, pruned.shape[1])).astype(np.float32)
    cas = CascadeIndex.build(pruned, m_coarse=max(2, pruned.shape[1] // 2),
                             n_factor=_full_nf(n + 90, k),
                             quantize_int8=quant
                             ).paged(page_rows=64, seal_rows=64)
    cas = cas.append(extra)
    assert cas.n == n + 90 and cas.coarse.n == cas.full.n
    assert cas.full.delta_pages > 0
    assert_matches_oracle(cas.full, Q, cas.search_projected(Q, W, k=k,
                                                            mean=mean),
                          k, W=W, mean=mean, msg="cascade")
    assert_matches_oracle(cas.full, Q, cas.full.search_projected(
        Q, W, k=k, mean=mean), k, W=W, mean=mean, msg="full")


def test_cascade_recall_monotone_in_shortlist_depth():
    """recall@10 vs the full-m oracle is non-decreasing in N: a deeper
    shortlist is a superset, and an exact rescore over a superset can
    displace a true top-k member only with another true top-k member."""
    k, n = 10, 1200
    pruned, W, mean, Q = _fixture(n=n, nq=8)
    oracle = DenseIndex.build(pruned)
    _, i0 = oracle.search_projected(Q, W, k=k, mean=mean)
    i0 = np.asarray(i0)
    recalls = []
    for nf in (1, 2, 4, 8, 16, _full_nf(n, k)):
        cas = CascadeIndex.from_index(oracle, m_coarse=pruned.shape[1] // 4,
                                      n_factor=nf)
        _, ids = cas.search_projected(Q, W, k=k, mean=mean)
        ids = np.asarray(ids)
        recalls.append(np.mean([
            len(set(i0[q]) & set(ids[q])) / k for q in range(len(i0))]))
    assert all(b >= a for a, b in zip(recalls, recalls[1:])), recalls
    assert recalls[-1] == 1.0


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------


def test_cascade_rejects_row_count_mismatch():
    pruned, _, _, _ = _fixture(n=120)
    full = DenseIndex.build(pruned)
    coarse = DenseIndex.build(pruned[:100, :4], quantize_int8=True)
    with pytest.raises(ValueError, match="disagree on row count"):
        CascadeIndex(coarse=coarse, full=full)


def test_cascade_rejects_non_nested_m():
    pruned, _, _, _ = _fixture(n=120)
    full = DenseIndex.build(pruned)
    with pytest.raises(ValueError, match="does not nest"):
        CascadeIndex(coarse=DenseIndex.build(pruned), full=full)
    with pytest.raises(ValueError, match="n_factor"):
        CascadeIndex.build(pruned, m_coarse=4, n_factor=0)


def test_cascade_append_requires_segmented_resolutions():
    pruned, _, _, _ = _fixture(n=120)
    cas = CascadeIndex.build(pruned, m_coarse=4)
    with pytest.raises(TypeError, match="paged resolutions"):
        cas.append(np.zeros((3, pruned.shape[1]), np.float32))


def test_cascade_append_zero_steady_state_recompiles():
    """Fixed-shape appends + searches after warmup must not grow any jit
    cache — nk is fixed and both page walks take their slot bounds as
    traced operands."""
    from repro.core import cascade, paged

    def cache_size():
        return (sum(paged._jit_cache_sizes().values())
                + sum(cascade._jit_cache_sizes().values()))

    k, n = 5, 300
    pruned, W, mean, Q = _fixture(n=n)
    cas = CascadeIndex.build(pruned, m_coarse=pruned.shape[1] // 2,
                             n_factor=2, quantize_int8=True
                             ).paged(page_rows=64, seal_rows=128)
    block = RNG.standard_normal((8, pruned.shape[1])).astype(np.float32)
    cas = cas.append(block)            # opens both deltas, widest scale
    cas = cas.append(0.5 * block)      # non-widening extend
    cas.search_projected(Q, W, k=k, mean=mean)
    before = cache_size()
    for frac in (0.4, 0.3, 0.2):       # shrinking rows: never re-widen
        cas = cas.append(frac * block)
        cas.search_projected(Q, W, k=k, mean=mean)
    assert cache_size() == before
    assert cas.coarse.n == cas.full.n == n + 5 * 8


# ---------------------------------------------------------------------------
# multi-resolution store: round trips + corruption rejection
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quant", [False, True])
def test_cascade_store_roundtrip_dense(tmp_path, quant):
    """save_index(CascadeIndex) persists the coarse view as a manifest
    resolution; the load must search bit-identically."""
    k, n = 8, 300
    pruned, W, mean, Q = _fixture(n=n)
    cas = CascadeIndex.build(pruned, m_coarse=pruned.shape[1] // 2,
                             n_factor=3, quantize_int8=quant)
    store = save_index(str(tmp_path / "st"), cas)
    loaded = CascadeIndex.load(store, m_coarse=cas.m_coarse, n_factor=3)
    assert (loaded.n, loaded.m_coarse) == (cas.n, cas.m_coarse)
    s0, i0 = cas.search_projected(Q, W, k=k, mean=mean)
    s1, i1 = loaded.search_projected(Q, W, k=k, mean=mean)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_cascade_store_roundtrip_segmented(tmp_path):
    """A grown cascade persists through the store: the stored resolution
    covers the base rows, coarse deltas rehydrate from their PERSISTED
    segments (exact quantised bytes — no requantisation on load), the
    pair stays row-aligned, and the reload serves the same bits."""
    k, n = 8, 300
    pruned, W, mean, Q = _fixture(n=n)
    cas = CascadeIndex.build(pruned, m_coarse=pruned.shape[1] // 2,
                             n_factor=_full_nf(n + 40, k),
                             quantize_int8=True
                             ).paged(page_rows=64, seal_rows=64)
    cas = cas.append(RNG.standard_normal((40, pruned.shape[1]))
                     .astype(np.float32))
    store = save_index(str(tmp_path / "st"), cas)
    loaded = CascadeIndex.load(store, m_coarse=cas.m_coarse,
                               n_factor=cas.n_factor)
    assert loaded.n == cas.n and loaded.coarse.n == loaded.full.n
    assert loaded.full.storage.seal_rows == 64
    s0, i0 = cas.search_projected(Q, W, k=k, mean=mean)
    s1, i1 = loaded.search_projected(Q, W, k=k, mean=mean)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    # at covering depth the shortlist spans every row: the exact top-k
    assert_matches_oracle(loaded.full, Q, (s1, i1), k, W=W, mean=mean)


def _cascade_store(tmp_path, n=200):
    pruned, W, mean, Q = _fixture(n=n)
    cas = CascadeIndex.build(pruned, m_coarse=pruned.shape[1] // 2,
                             n_factor=2, quantize_int8=True)
    return save_index(str(tmp_path / "st"), cas), pruned


def test_store_rejects_resolution_row_mismatch(tmp_path):
    store, pruned = _cascade_store(tmp_path)
    with pytest.raises(IndexStoreError, match="rows"):
        store.add_resolution(np.zeros((5, 3), np.float32))
    mpath = os.path.join(store.path, "manifest.json")
    with open(mpath) as f:
        m = json.load(f)
    m["resolutions"][0]["chunks"][0]["rows"] -= 1
    with open(mpath, "w") as f:
        json.dump(m, f)
    with pytest.raises(IndexStoreError, match="shape|same corpus"):
        IndexStore.open(store.path)


def test_store_rejects_non_nested_resolution_m(tmp_path):
    store, pruned = _cascade_store(tmp_path)
    n, m = pruned.shape
    with pytest.raises(IndexStoreError, match="nest"):
        store.add_resolution(np.zeros((n, m), np.float32))
    mpath = os.path.join(store.path, "manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    man["resolutions"][0]["m"] = man["dim"] + 4
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(IndexStoreError, match="does not nest"):
        IndexStore.open(store.path)


def test_store_rejects_duplicate_resolution_m(tmp_path):
    store, pruned = _cascade_store(tmp_path)
    mc = int(store.manifest["resolutions"][0]["m"])
    with pytest.raises(IndexStoreError, match="already present"):
        store.add_resolution(
            np.asarray(pruned[:, :mc], np.float32))


def test_store_rejects_missing_resolution_blob(tmp_path):
    store, _ = _cascade_store(tmp_path)
    entry = store.manifest["resolutions"][0]
    os.remove(os.path.join(store.path, entry["chunks"][0]["file"]))
    with pytest.raises(IndexStoreError, match="missing chunk"):
        IndexStore.open(store.path)


def test_store_rejects_missing_resolution_scale(tmp_path):
    store, _ = _cascade_store(tmp_path)
    entry = store.manifest["resolutions"][0]
    assert entry["scale_file"] is not None   # int8 coarse ships its scale
    os.remove(os.path.join(store.path, entry["scale_file"]))
    with pytest.raises(IndexStoreError, match="scale"):
        IndexStore.open(store.path)


def test_cascade_load_requires_matching_resolution(tmp_path):
    pruned, W, mean, Q = _fixture(n=150)
    plain = save_index(str(tmp_path / "plain"),
                       DenseIndex.build(pruned))
    with pytest.raises(IndexStoreError, match="no coarse resolutions"):
        CascadeIndex.load(plain)
    store, _ = _cascade_store(tmp_path)
    with pytest.raises(IndexStoreError, match="no m="):
        CascadeIndex.load(store, m_coarse=3)


def test_cascade_store_persists_coarse_deltas_bit_parity(tmp_path):
    """Satellite regression: a paged cascade's coarse delta extents
    persist in the store as exact quantised bytes + per-extent scales,
    and a load adopts them BIT-identically — no requantisation from the
    full deltas on the load path."""
    k, n = 8, 300
    pruned, W, mean, Q = _fixture(n=n)
    cas = CascadeIndex.build(pruned, m_coarse=pruned.shape[1] // 2,
                             n_factor=_full_nf(n + 48, k),
                             quantize_int8=True
                             ).paged(page_rows=64, seal_rows=64)
    for seed in (1, 2):
        cas = cas.append(np.random.default_rng(seed)
                         .standard_normal((24, pruned.shape[1]))
                         .astype(np.float32))
    store = save_index(str(tmp_path / "st"), cas)
    name = store.manifest["resolutions"][0]["name"]
    dviews = store.resolution_deltas(name)
    assert dviews, "a paged save must persist the coarse delta extents"
    mem_st = cas.coarse.storage
    assert [v.n for v in dviews] == [e.n_rows for e in mem_st.extents[1:]]
    loaded = CascadeIndex.load(store, m_coarse=cas.m_coarse,
                               n_factor=cas.n_factor)
    got_st = loaded.coarse.storage
    assert len(got_st.extents) == len(mem_st.extents)
    for i in range(1, len(mem_st.extents)):
        np.testing.assert_array_equal(mem_st.extent_rows(i),
                                      got_st.extent_rows(i))
        mem, got = mem_st.extents[i], got_st.extents[i]
        assert (mem.scale is None) == (got.scale is None)
        if mem.scale is not None:
            np.testing.assert_array_equal(mem.scale, got.scale)
    s0, i0 = cas.search_projected(Q, W, k=k, mean=mean)
    s1, i1 = loaded.search_projected(Q, W, k=k, mean=mean)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_store_rejects_misaligned_resolution_deltas(tmp_path):
    """Coarse delta rows must mirror the main delta segments one-for-one
    — otherwise the two views would describe different docs."""
    pruned, W, mean, Q = _fixture(n=200)
    cas = CascadeIndex.build(pruned, m_coarse=pruned.shape[1] // 2,
                             n_factor=2, quantize_int8=True
                             ).paged(page_rows=64, seal_rows=64)
    cas = cas.append(RNG.standard_normal((16, pruned.shape[1]))
                     .astype(np.float32))
    store = save_index(str(tmp_path / "full-only"), cas.full)
    mc = cas.m_coarse
    base = cas.coarse.storage.extent_rows(0)
    scale = cas.coarse.storage.extents[0].scale
    with pytest.raises(IndexStoreError, match="mirror"):
        store.add_resolution(base, scale=scale, deltas=[
            {"rows": np.zeros((3, mc), np.int8), "scale": None,
             "capacity": 64}])
    with pytest.raises(IndexStoreError, match="no resolution"):
        store.resolution_deltas("m999")
