"""Paged index memory: kernel, parity, lifecycle, store, serving.

The acceptance surface of the paged architecture:
  * ``topk_score_paged_pallas`` walks a scrambled two-tier page table
    (pool + tail) bit-identically to the contiguous fused kernel — f32
    and int8 (per-page scales folded into the query), partial last page
    masked by ``n_valid``, run-split carry chaining, ``ids_pool`` rescore
    mode, any pipeline depth;
  * ``PagedIndex`` search matches a plain float64 oracle over its
    dequantised extents (``tests/paged_oracle.py``: ids exact, scores
    within an argued f32 bound) — {f32, int8} x {jnp, pallas}, fresh,
    after appends (a scale-widening block among them), after promotion +
    compaction, oversubscribed, and through the cascade rescore path;
  * promotion / compaction / eviction are page-pointer swaps: results
    never move by a bit, jnp and pallas agree bit for bit, and a full
    lifecycle never grows the jit cache once every variant is warm;
  * paged artifacts round-trip through ``IndexStore`` page-granularly
    (chunk boundaries page-aligned, host-tier pages included, bytes
    identical from either residency), reject corruption/truncation and a
    paged block that LEADS the segments, and accept a lagging block (the
    crash window);
  * ``RetrievalServer`` under live append+promote+compact traffic and
    under eviction/readmission swaps drops no reply and misroutes none;
  * ``IndexUpdater`` telemetry is page-based on a paged index:
    ``delta_fraction`` counts pages and ``last_compaction`` reports pages
    moved/freed/host — not rows copied.
"""
import dataclasses
import json
import os
import shutil
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_oracle import (assert_matches_oracle, dequantised_rows,
                          exact_topk, score_bound)
from repro.core import DenseIndex, StaticPruner
from repro.core.maintenance import IndexUpdater
from repro.core.paged import PagedIndex, PagedIndexStorage, _jit_cache_sizes
from repro.core.store import (
    IndexStore,
    IndexStoreError,
    save_index,
    save_paged_index,
)
from repro.kernels.topk_score import (query_terms, score_rows,
                                     topk_score_paged_pallas, topk_score_pallas)

RNG = np.random.default_rng(170)


def _assert_same(a, b, msg=""):
    assert jnp.array_equal(a[0], b[0]), f"scores diverged {msg}"
    assert jnp.array_equal(a[1], b[1]), f"ids diverged {msg}"


def _jit_cache_size() -> int:
    return sum(_jit_cache_sizes().values())


# ---------------------------------------------------------------------------
# kernel: two-tier paged walk vs the contiguous fused kernel
# ---------------------------------------------------------------------------


def _two_tier_fixture(dtype=np.float32, seed=0):
    """Corpus scattered over a scrambled pool+tail page layout: logical
    slot j lives at physical page perm[j], the last page is partial."""
    rng = np.random.default_rng(seed)
    R, m, B, k = 8, 32, 5, 7
    npages, n_last = 11, 3
    n = (npages - 1) * R + n_last
    D = rng.standard_normal((n, m)).astype(np.float32)
    Q = rng.standard_normal((B, m)).astype(np.float32)
    pool_pages, tail_pages, table_cap = 7, 6, 16
    perm = rng.permutation(npages)
    pt = np.full(table_cap, -1, np.int32)
    pt[:npages] = perm
    nv = np.zeros(table_cap, np.int32)
    nv[:npages] = R
    nv[npages - 1] = n_last
    off = np.zeros(table_cap, np.int32)
    off[:npages] = np.arange(npages) * R
    pool = np.zeros((pool_pages, R, m), dtype)
    tail = np.zeros((tail_pages, R, m), dtype)
    return (R, m, B, k, npages, n, D, Q, pool_pages, table_cap, pt, nv, off,
            pool, tail)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_paged_kernel_two_tier_partial_last_page(depth):
    (R, m, B, k, npages, n, D, Q, pool_pages, _tc, pt, nv, off, pool,
     tail) = _two_tier_fixture()
    for j in range(npages):
        phys = pt[j]
        buf, idx = (pool, phys) if phys < pool_pages \
            else (tail, phys - pool_pages)
        buf[idx, :nv[j]] = D[j * R: j * R + nv[j]]
    ref = topk_score_pallas(jnp.asarray(D), jnp.asarray(Q), k=k,
                            block_n=R * npages, interpret=True)
    got = topk_score_paged_pallas(
        jnp.asarray(pool), jnp.asarray(pt), jnp.asarray(nv),
        jnp.asarray(off), jnp.int32(0), jnp.int32(npages), jnp.asarray(Q),
        k=k, tail=jnp.asarray(tail), depth=depth, interpret=True)
    _assert_same(got, ref, f"depth={depth}")


def test_paged_kernel_run_split_carry_matches_single_pass():
    (R, m, B, k, npages, n, D, Q, pool_pages, _tc, pt, nv, off, pool,
     tail) = _two_tier_fixture(seed=1)
    for j in range(npages):
        phys = pt[j]
        buf, idx = (pool, phys) if phys < pool_pages \
            else (tail, phys - pool_pages)
        buf[idx, :nv[j]] = D[j * R: j * R + nv[j]]
    args = (jnp.asarray(pool), jnp.asarray(pt), jnp.asarray(nv),
            jnp.asarray(off))
    ref = topk_score_paged_pallas(*args, jnp.int32(0), jnp.int32(npages),
                                  jnp.asarray(Q), k=k,
                                  tail=jnp.asarray(tail), depth=2,
                                  interpret=True)
    part = topk_score_paged_pallas(*args, jnp.int32(0), jnp.int32(4),
                                   jnp.asarray(Q), k=k,
                                   tail=jnp.asarray(tail), depth=2,
                                   finalize=False, interpret=True)
    got = topk_score_paged_pallas(*args, jnp.int32(4), jnp.int32(npages),
                                  jnp.asarray(Q), k=k,
                                  tail=jnp.asarray(tail), depth=2,
                                  carry=part, interpret=True)
    _assert_same(got, ref, "run-split carry")


def test_paged_kernel_int8_per_page_scale():
    (R, m, B, k, npages, n, D, Q, pool_pages, table_cap, pt, nv, off, _p,
     _t) = _two_tier_fixture(seed=2)
    scale = np.stack([
        np.abs(D[j * R:(j + 1) * R]).max(axis=0).clip(1e-12) / 127.0
        for j in range(npages)]).astype(np.float32)
    pool8 = np.zeros((pool_pages, R, m), np.int8)
    tail8 = np.zeros((6, R, m), np.int8)
    D8 = np.zeros_like(D, np.int8)
    for j in range(npages):
        rows = D[j * R: j * R + nv[j]]
        q8 = np.clip(np.round(rows / scale[j][None, :]), -127,
                     127).astype(np.int8)
        D8[j * R: j * R + nv[j]] = q8
        phys = pt[j]
        buf, idx = (pool8, phys) if phys < pool_pages \
            else (tail8, phys - pool_pages)
        buf[idx, :nv[j]] = q8
    ps = np.zeros((table_cap, 1, m), np.float32)   # kernel's DMA layout
    ps[:npages, 0] = scale
    # reference: per-page scale folded into the query, then the jnp scan's
    # scoring of int8 rows (three-term bf16 split of the query) per page
    parts_s, parts_i = [], []
    for j in range(npages):
        qf = jnp.asarray(Q) * jnp.asarray(scale[j])[None, :]
        rows8 = jnp.asarray(D8[j * R: j * R + nv[j]])
        sj = score_rows(query_terms(qf, rows8.dtype), rows8)
        parts_s.append(sj)
        parts_i.append(jnp.asarray(np.arange(
            j * R, j * R + nv[j], dtype=np.int32)[None, :].repeat(B, 0)))
    cat_s = jnp.concatenate(parts_s, axis=1)
    cat_i = jnp.concatenate(parts_i, axis=1)
    rs, ridx = jax.lax.top_k(cat_s, k)
    ref = (rs, jnp.take_along_axis(cat_i, ridx, axis=1))
    got = topk_score_paged_pallas(
        jnp.asarray(pool8), jnp.asarray(pt), jnp.asarray(nv),
        jnp.asarray(off), jnp.int32(0), jnp.int32(npages), jnp.asarray(Q),
        k=k, tail=jnp.asarray(tail8), page_scale=jnp.asarray(ps), depth=2,
        interpret=True)
    _assert_same(got, ref, "int8 per-page scale")


def test_paged_kernel_ids_pool_rescore_mode():
    (R, m, B, k, npages, n, D, Q, pool_pages, table_cap, pt, nv, off, pool,
     tail) = _two_tier_fixture(seed=3)
    for j in range(npages):
        phys = pt[j]
        buf, idx = (pool, phys) if phys < pool_pages \
            else (tail, phys - pool_pages)
        buf[idx, :nv[j]] = D[j * R: j * R + nv[j]]
    ids_pool = np.full((table_cap, 1, R), -1, np.int32)   # kernel's DMA layout
    for j in range(npages):
        ids_pool[j, 0, :nv[j]] = np.arange(j * R, j * R + nv[j],
                                           dtype=np.int32)
    ref = topk_score_pallas(
        jnp.asarray(D), jnp.asarray(Q), k=k, block_n=R * npages,
        row_ids=jnp.asarray(np.arange(n, dtype=np.int32)), interpret=True)
    got = topk_score_paged_pallas(
        jnp.asarray(pool), jnp.asarray(pt), jnp.asarray(nv),
        jnp.asarray(off), jnp.int32(0), jnp.int32(npages), jnp.asarray(Q),
        k=k, tail=jnp.asarray(tail), ids_pool=jnp.asarray(ids_pool),
        depth=2, interpret=True)
    _assert_same(got, ref, "ids_pool rescore")


# ---------------------------------------------------------------------------
# PagedIndex vs the float64 oracle; bit parity across lifecycle steps
# ---------------------------------------------------------------------------


def _oracle_case(quant, backend, state):
    rng = np.random.default_rng(7)
    n, d, m = 300, 48, 24
    X = rng.standard_normal((n, m)).astype(np.float32)
    base = DenseIndex.build(jnp.asarray(X), quantize_int8=quant)
    pool = 5 if state == "oversubscribed" else None
    pg = PagedIndex.from_index(base, page_rows=32, pool_pages=pool,
                               seal_rows=64, backend=backend)
    if state in ("appended", "compacted"):
        # the second block's magnitude widens the open extent's int8 scale
        for bl in (rng.standard_normal((37, m)),
                   rng.standard_normal((20, m)) * 9.0,
                   rng.standard_normal((50, m))):
            pg = pg.append(bl.astype(np.float32))
    if state == "compacted":
        pg, _ = pg.promote()
        pg, _ = pg.compact_pages()
    W = rng.standard_normal((d, m)).astype(np.float32) * 0.2
    mean = rng.standard_normal(d).astype(np.float32) * 0.1
    Qraw = rng.standard_normal((6, d)).astype(np.float32)
    Qm = rng.standard_normal((6, m)).astype(np.float32)
    return pg, W, mean, Qraw, Qm


@pytest.mark.parametrize("state", ["fresh", "appended", "compacted",
                                   "oversubscribed"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_paged_matches_oracle(quant, backend, state):
    pg, W, mean, Qraw, Qm = _oracle_case(quant, backend, state)
    if state == "appended":
        assert pg.delta_pages > 0
        if quant:
            scales = [e.scale for e in pg.storage.extents]
            assert not np.array_equal(scales[0], scales[1])
    if state == "oversubscribed":
        assert pg.storage.n_host_pages > 0
    k = 9
    assert_matches_oracle(pg, Qm, pg.search(jnp.asarray(Qm), k), k,
                          msg="search")
    assert_matches_oracle(
        pg, Qraw, pg.search_projected(jnp.asarray(Qraw), jnp.asarray(W), k,
                                      mean=jnp.asarray(mean)),
        k, W=W, mean=mean, msg="search_projected")


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_paged_parity_lifecycle(quant, backend):
    rng = np.random.default_rng(1)
    n, d, m, B, k = 500, 48, 24, 6, 9
    X = rng.standard_normal((n, m)).astype(np.float32)
    W = jnp.asarray(rng.standard_normal((d, m)).astype(np.float32) * 0.2)
    mean = jnp.asarray(rng.standard_normal(d).astype(np.float32) * 0.1)
    Qraw = jnp.asarray(rng.standard_normal((B, d)).astype(np.float32))
    Qm = jnp.asarray(rng.standard_normal((B, m)).astype(np.float32))
    base = DenseIndex.build(jnp.asarray(X), quantize_int8=quant,
                            backend=backend)
    pg = PagedIndex.from_index(base, page_rows=32, seal_rows=96,
                               backend=backend)

    def oracle(ix, msg):
        assert_matches_oracle(ix, Qm, ix.search(Qm, k), k, msg=msg)
        assert_matches_oracle(ix, Qraw, ix.search_projected(Qraw, W, k,
                                                            mean=mean),
                              k, W=W, mean=mean, msg=msg + " projected")

    oracle(pg, "base")
    # appends, including a big-magnitude block that widens the int8 scale
    blocks = [rng.standard_normal((37, m)).astype(np.float32),
              (rng.standard_normal((20, m)) * 9.0).astype(np.float32),
              rng.standard_normal((150, m)).astype(np.float32)]
    for bl in blocks:
        pg = pg.append(bl)
    oracle(pg, "after appends")
    # promotion and compaction are pointer swaps: results must not move
    ref = pg.search(Qm, k)
    ref_p = pg.search_projected(Qraw, W, k, mean=mean)
    pg, _ = pg.promote()
    _assert_same(pg.search(Qm, k), ref, "after promote")
    pg, stats = pg.compact_pages()
    _assert_same(pg.search(Qm, k), ref, "after compact")
    assert pg.delta_pages == 0
    # eviction: same contents, host-tier streaming, same bits
    pg, nev = pg.evict(7)
    assert pg.storage.n_host_pages >= 7, nev
    _assert_same(pg.search(Qm, k), ref, "oversubscribed")
    _assert_same(pg.search_projected(Qraw, W, k, mean=mean), ref_p,
                 "oversubscribed projected")
    # append while oversubscribed. Compaction SEALED the open delta, so a
    # post-compact int8 append opens a fresh extent with a fresh scale;
    # the other backend must agree bit for bit
    pg = pg.append(blocks[0])
    oracle(pg, "oversub append")
    other = dataclasses.replace(
        pg, backend="pallas" if backend == "jnp" else "jnp")
    _assert_same(pg.search(Qm, k), other.search(Qm, k),
                 "oversub append xbackend")


def test_paged_construction_oversubscription_parity():
    rng = np.random.default_rng(2)
    X = rng.standard_normal((500, 24)).astype(np.float32)
    Qm = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
    base = DenseIndex.build(jnp.asarray(X), quantize_int8=True,
                            backend="pallas")
    pg = PagedIndex.from_index(base, page_rows=32, pool_pages=6,
                               seal_rows=96, backend="pallas")
    assert pg.storage.n_host_pages > 0
    assert_matches_oracle(pg, Qm, pg.search(Qm, 8), 8,
                          msg="construction oversub")
    resident = PagedIndex.from_index(base, page_rows=32, seal_rows=96,
                                     backend="pallas")
    _assert_same(pg.search(Qm, 8), resident.search(Qm, 8),
                 "oversubscribed vs resident")


def test_paged_load_adopts_segment_bytes(tmp_path):
    """A store load adopts every segment's stored bytes and scale as they
    are, the open delta's requant staging included: continued appends on
    the loaded index stay bit for bit with the index that was saved,
    through a scale-widening block."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((400, 24)).astype(np.float32)
    Qm = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
    pg = PagedIndex.from_index(
        DenseIndex.build(jnp.asarray(X), quantize_int8=True),
        page_rows=32, seal_rows=96)
    pg = pg.append(rng.standard_normal((130, 24)).astype(np.float32))
    save_paged_index(str(tmp_path / "idx"), pg)
    got = PagedIndex.load(str(tmp_path / "idx"))
    st, st2 = pg.storage, got.storage
    assert len(st2.extents) == len(st.extents) == 3
    for i, (e, e2) in enumerate(zip(st.extents, st2.extents)):
        np.testing.assert_array_equal(st.extent_rows(i), st2.extent_rows(i))
        np.testing.assert_array_equal(e.scale, e2.scale)
        assert (e.kind, e.sealed) == (e2.kind, e2.sealed)
    _assert_same(got.search(Qm, 8), pg.search(Qm, 8), "loaded")
    # continued appends stay in lockstep, including a widening block
    bl = (rng.standard_normal((25, 24)) * 8.0).astype(np.float32)
    _assert_same(got.append(bl).search(Qm, 8), pg.append(bl).search(Qm, 8),
                 "continued append + widen")


def test_paged_cascade_rescore_parity():
    from repro.core.cascade import _cascade_select
    rng = np.random.default_rng(4)
    X = rng.standard_normal((400, 24)).astype(np.float32)
    qf = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
    pg = PagedIndex.from_index(
        DenseIndex.build(jnp.asarray(X), quantize_int8=True),
        page_rows=32, seal_rows=96)
    pg = pg.append(rng.standard_normal((130, 24)).astype(np.float32))
    uids = jnp.sort(jnp.asarray(
        rng.choice(pg.n, size=40, replace=False).astype(np.int32)))
    acc = pg.rescore(qf, uids)
    # every shortlist uid scores its own exact row within the f32 bound
    rows = dequantised_rows(pg)
    q = np.asarray(qf, np.float64)
    u = np.asarray(uids)
    ids = np.broadcast_to(u[None, :], (q.shape[0], u.shape[0]))
    exact = q @ rows[u].T
    err = np.abs(np.asarray(acc, np.float64) - exact)
    assert (err <= score_bound(rows, q, np.zeros_like(q), ids)).all()
    # the selected top-k is the oracle's top-k over the shortlist
    ref = _cascade_select(acc, uids, 8)
    ref_s, ref_i = exact_topk(rows[u], q, 8)
    np.testing.assert_array_equal(np.asarray(ref[1]), u[ref_i])
    # rescore with host-tier pages streams waves, same bits
    pgo, _ = pg.evict(9)
    _assert_same(_cascade_select(pgo.rescore(qf, uids), uids, 8), ref,
                 "paged rescore oversubscribed")


def test_paged_k_exceeding_n_clamps_to_n():
    rng = np.random.default_rng(5)
    X = rng.standard_normal((5, 24)).astype(np.float32)
    Qm = jnp.asarray(rng.standard_normal((3, 24)).astype(np.float32))
    pg = PagedIndex.from_index(DenseIndex.build(jnp.asarray(X)),
                               page_rows=32)
    out = pg.search(Qm, 50)
    assert out[1].shape == (3, 5)
    assert_matches_oracle(pg, Qm, out, 50, msg="k>n")


def test_paged_lifecycle_zero_recompiles_across_page_counts():
    """Append -> search -> promote -> compact -> search at growing page
    counts: the page count is data ([lo,hi) is traced), so once every
    variant is warm the jit cache must not move."""
    rng = np.random.default_rng(6)
    m = 24
    X = rng.standard_normal((256, m)).astype(np.float32)
    Qm = jnp.asarray(rng.standard_normal((4, m)).astype(np.float32))
    pg = PagedIndex.from_index(
        DenseIndex.build(jnp.asarray(X), quantize_int8=True),
        page_rows=32, seal_rows=64)

    def lifecycle(pg, rows):
        pg = pg.append(rng.standard_normal((rows, m)).astype(np.float32))
        pg.search(Qm, 6)
        pg, _ = pg.promote()
        pg, _ = pg.compact_pages()
        jax.block_until_ready(pg.search(Qm, 6)[0])
        return pg

    pg = lifecycle(pg, 48)           # warmup: compile every resident path
    j0 = _jit_cache_size()
    counts = set()
    for rows in (32, 48, 80, 96):
        pg = lifecycle(pg, rows)
        counts.add(pg.total_pages)
    assert len(counts) > 1, "page count never changed — sweep is vacuous"
    assert _jit_cache_size() == j0, \
        "page-count growth leaked into a static jit key"


# ---------------------------------------------------------------------------
# store: page-granular round-trip, corruption, crash-window manifests
# ---------------------------------------------------------------------------


def _grown_paged(quant, seed=30):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((400, 24)).astype(np.float32)
    pg = PagedIndex.from_index(
        DenseIndex.build(jnp.asarray(X), quantize_int8=quant),
        page_rows=32, seal_rows=96)
    pg = pg.append(rng.standard_normal((50, 24)).astype(np.float32))
    pg = pg.append((rng.standard_normal((60, 24)) * 6).astype(np.float32))
    return pg


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_paged_store_roundtrip_page_granular(tmp_path, quant):
    rng = np.random.default_rng(31)
    Qm = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
    pg = _grown_paged(quant)
    p = str(tmp_path / "idx")
    st = save_index(p, pg)                       # isinstance dispatch branch
    assert "paged" in st.manifest
    pg2 = PagedIndex.load(IndexStore.open(p))
    _assert_same(pg2.search(Qm, 8), pg.search(Qm, 8), "roundtrip")
    # geometry and lifecycle state survive the round-trip
    assert pg2.storage.page_rows == 32 and pg2.storage.seal_rows == 96
    assert ([(e.kind, e.sealed) for e in pg2.storage.extents]
            == [(e.kind, e.sealed) for e in pg.storage.extents])
    # every non-final chunk boundary is page-aligned
    for s in st.manifest["segments"]:
        for c in s["chunks"][:-1]:
            assert c["rows"] % 32 == 0, c


def test_paged_store_host_tier_pages_roundtrip(tmp_path):
    """Saving from an oversubscribed (host-tier) storage writes the same
    bytes as saving the fully resident equivalent."""
    rng = np.random.default_rng(32)
    Qm = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
    pg = _grown_paged(True)
    pr, po = str(tmp_path / "resident"), str(tmp_path / "oversub")
    save_paged_index(pr, pg)
    pg4 = PagedIndex.load(IndexStore.open(pr), pool_pages=5)
    assert pg4.storage.n_host_pages > 0
    _assert_same(pg4.search(Qm, 8), pg.search(Qm, 8), "oversubscribed load")
    save_paged_index(po, pg4)
    pg5 = PagedIndex.load(IndexStore.open(po))
    _assert_same(pg5.search(Qm, 8), pg.search(Qm, 8), "host-tier roundtrip")
    a = sorted(f for f in os.listdir(pr) if f.startswith("vectors"))
    b = sorted(f for f in os.listdir(po) if f.startswith("vectors"))
    assert a == b
    for x in a:
        assert np.array_equal(np.load(os.path.join(pr, x)),
                              np.load(os.path.join(po, x))), x


def test_paged_store_rejects_truncated_blob(tmp_path):
    pg = _grown_paged(True)
    p = str(tmp_path / "idx")
    save_paged_index(p, pg)
    blob = sorted(f for f in os.listdir(p) if f.startswith("vectors"))[0]
    path = os.path.join(p, blob)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    with pytest.raises(IndexStoreError):
        IndexStore.open(p)


def test_paged_store_rejects_leading_manifest_block(tmp_path):
    """A paged block claiming MORE rows than the segments hold means the
    metadata committed ahead of the data — never recoverable, reject."""
    pg = _grown_paged(True)
    p = str(tmp_path / "idx")
    save_paged_index(p, pg)
    mpath = os.path.join(p, "manifest.json")
    man = json.load(open(mpath))
    man["paged"]["extents"][0]["n"] += 1
    json.dump(man, open(mpath, "w"))
    with pytest.raises(IndexStoreError):
        IndexStore.open(p)


def test_paged_store_accepts_lagging_manifest_block(tmp_path):
    """A paged block missing the newest extent is the crash window
    (data committed, metadata not yet): reload reconstructs it."""
    rng = np.random.default_rng(33)
    Qm = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
    pg = _grown_paged(True)
    p = str(tmp_path / "idx")
    save_paged_index(p, pg)
    mpath = os.path.join(p, "manifest.json")
    man = json.load(open(mpath))
    man["paged"]["extents"] = man["paged"]["extents"][:-1]
    json.dump(man, open(mpath, "w"))
    pgl = PagedIndex.load(IndexStore.open(p))
    _assert_same(pgl.search(Qm, 8), pg.search(Qm, 8), "lagging block")


def test_paged_store_append_reload_bit_parity(tmp_path):
    """Page-granular append -> save -> reload -> append: the reloaded
    index continues bit-for-bit (cross-backend self-parity — the reload
    must not perturb quantised bytes or extent scales)."""
    rng = np.random.default_rng(34)
    Qm = jnp.asarray(rng.standard_normal((5, 24)).astype(np.float32))
    pg = _grown_paged(True)
    p = str(tmp_path / "idx")
    save_paged_index(p, pg)
    pg2 = PagedIndex.load(IndexStore.open(p))
    bl = rng.standard_normal((30, 24)).astype(np.float32)
    a, b = pg.append(bl), pg2.append(bl)
    _assert_same(a.search(Qm, 8), b.search(Qm, 8), "append after reload")
    other = dataclasses.replace(b, backend="pallas")
    _assert_same(b.search(Qm, 8), other.search(Qm, 8), "xbackend")


def test_paged_store_empty_grown_index_roundtrip(tmp_path):
    """An index grown purely from appends (0-row base) round-trips with
    its open delta intact and keeps accepting appends."""
    rng = np.random.default_rng(35)
    m = 24
    Qm = jnp.asarray(rng.standard_normal((5, m)).astype(np.float32))
    st0 = PagedIndexStorage.from_rows(np.zeros((0, m), np.int8),
                                      np.ones(m, np.float32),
                                      page_rows=32, seal_rows=96)
    pg = PagedIndex(storage=st0)
    pg = pg.append(rng.standard_normal((40, m)).astype(np.float32))
    p = str(tmp_path / "idx")
    save_paged_index(p, pg)
    pgr = PagedIndex.load(IndexStore.open(p))
    _assert_same(pgr.search(Qm, 8), pg.search(Qm, 8), "empty-grown")
    assert pgr.storage.extents[0].kind == "delta"
    assert not pgr.storage.extents[0].sealed
    pgr.append(rng.standard_normal((20, m)).astype(np.float32))


# ---------------------------------------------------------------------------
# maintenance: page-based telemetry, durable mirror, refit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_updater_paged_telemetry_and_mirror(tmp_path, quant):
    rng = np.random.default_rng(40)
    n, d = 600, 48
    corpus = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    Qd = jnp.asarray(rng.standard_normal((4, d)).astype(np.float32))
    sp = str(tmp_path / "store")
    u = IndexUpdater.build(corpus, cutoff=0.5, quantize_int8=quant,
                           store_path=sp, delta_capacity=96, page_rows=32)
    assert isinstance(u.index, PagedIndex)
    assert u.delta_fraction == 0.0

    def srch(upd):
        W, mean = upd.pruner.projection()
        return upd.index.search_projected(
            Qd, jnp.asarray(W), k=6,
            mean=None if mean is None else jnp.asarray(mean))

    u.add_documents(jnp.asarray(
        rng.standard_normal((50, d)).astype(np.float32)))
    u.add_documents(jnp.asarray(
        (rng.standard_normal((70, d)) * 5).astype(np.float32)))  # widens
    # delta_fraction counts PAGES on a paged index, not rows
    st = u.index.storage
    assert u.delta_fraction == pytest.approx(st.delta_pages / st.n_slots)
    # the durable mirror reloads to the same bits
    u2 = IndexUpdater.from_store(sp)
    assert isinstance(u2.index, PagedIndex)
    _assert_same(srch(u2), srch(u), "mirror reload")
    # compaction telemetry reports pages moved/freed/host — not rows
    assert u.health()["last_compaction"] is None
    u.compact()
    assert set(u.last_compaction) == {"pages_moved", "pages_freed",
                                      "pages_host"}
    assert u.compactions == 1 and u.delta_fraction == 0.0
    assert all(e.kind == "base" for e in u.index.storage.extents)
    # post-compact appends keep mirroring page-granularly
    u.add_documents(jnp.asarray(
        rng.standard_normal((40, d)).astype(np.float32)))
    _assert_same(srch(IndexUpdater.from_store(sp)), srch(u),
                 "post-compact append reload")
    # refit rebuilds in place and stays paged
    u.refit(corpus)
    assert isinstance(u.index, PagedIndex)


# ---------------------------------------------------------------------------
# serving: promotion/compaction and eviction swaps under live traffic
# ---------------------------------------------------------------------------


def _unit_corpus(n, d=64, seed=77):
    rng = np.random.default_rng(seed)
    D = rng.standard_normal((n, d)).astype(np.float32)
    D /= np.linalg.norm(D, axis=1, keepdims=True)
    return D


def test_paged_swap_soak_append_promote_compact():
    """Live appends (sealing + promoting pages) plus a mid-soak compaction
    while concurrent clients self-retrieve: every reply must answer its
    own query — a dropped reply hangs its client, a half-swapped page
    table misroutes ids."""
    from repro.launch.serve import RetrievalServer
    D = _unit_corpus(96)
    extra = _unit_corpus(200, seed=78)
    pruner = StaticPruner(cutoff=0.25).fit(jnp.asarray(D))
    base = DenseIndex.build(pruner.prune_index(jnp.asarray(D)))
    pg = PagedIndex.from_index(base, page_rows=32, seal_rows=64)
    server = RetrievalServer(pg, pruner, k=1, max_batch=8, pipeline_depth=3)
    up = IndexUpdater(pruner=pruner, index=pg, server=server)
    try:
        assert isinstance(up.index, PagedIndex)
        up.add_documents(jnp.asarray(extra[:8]))
        up.add_documents(jnp.asarray(0.5 * extra[:8]))
        server.query(D[0])
        swaps0 = server.swap_count
        n_known = 96 + 8

        stop = threading.Event()
        failures: list = []

        def appender():
            i = 16
            while not stop.is_set() and i + 8 <= len(extra):
                up.add_documents(jnp.asarray(extra[i:i + 8]))
                if i == 96:               # pointer-swap compaction mid-soak
                    up.compact()
                i += 8
                stop.wait(0.002)

        def client(cid):
            rng = np.random.default_rng(cid)
            try:
                for _ in range(30):
                    doc = int(rng.integers(0, n_known))
                    q = D[doc] if doc < 96 else extra[doc - 96]
                    _, ids = server.query(q, timeout=30.0)
                    if int(ids[0]) != doc:
                        failures.append((cid, doc, int(ids[0])))
            except BaseException as e:    # noqa: BLE001
                failures.append((cid, "exception", repr(e)))

        app = threading.Thread(target=appender, daemon=True)
        clients = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(6)]
        app.start()
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=120.0)
        stop.set()
        app.join(timeout=60.0)
        assert not failures, f"misrouted/dropped replies: {failures[:5]}"
        assert server.swap_count > swaps0, "appends never swapped the index"
        assert up.compactions >= 1
        # every appended doc is retrievable through the server afterwards
        n_final = up.index.n
        for gid in (100, n_final - 1):
            _, ids = server.query(extra[gid - 96])
            assert int(ids[0]) == gid
    finally:
        server.close()


def test_paged_eviction_swaps_under_live_traffic():
    """Residency changes (evict to host tier / readmit) swapped into a
    live server must never change results: clients self-retrieve while a
    maintenance thread flips the same contents between fully resident and
    oversubscribed."""
    from repro.launch.serve import RetrievalServer
    D = _unit_corpus(192)
    pruner = StaticPruner(cutoff=0.25).fit(jnp.asarray(D))
    base = DenseIndex.build(pruner.prune_index(jnp.asarray(D)))
    resident = PagedIndex.from_index(base, page_rows=32, seal_rows=64)
    evicted, nev = resident.evict(3)
    assert nev == 3 and evicted.storage.n_host_pages == 3
    server = RetrievalServer(resident, pruner, k=1, max_batch=8,
                             pipeline_depth=3)
    try:
        server.query(D[0])
        stop = threading.Event()
        failures: list = []

        def flipper():
            flip = 0
            while not stop.is_set():
                server.swap_index((evicted, resident)[flip % 2])
                flip += 1
                stop.wait(0.001)

        def client(cid):
            rng = np.random.default_rng(1000 + cid)
            try:
                for _ in range(40):
                    doc = int(rng.integers(0, len(D)))
                    _, ids = server.query(D[doc], timeout=30.0)
                    if int(ids[0]) != doc:
                        failures.append((cid, doc, int(ids[0])))
            except BaseException as e:    # noqa: BLE001
                failures.append((cid, "exception", repr(e)))

        fl = threading.Thread(target=flipper, daemon=True)
        clients = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(6)]
        fl.start()
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=120.0)
        stop.set()
        fl.join(timeout=30.0)
        assert not failures, f"misrouted/dropped replies: {failures[:5]}"
    finally:
        server.close()
