"""Sharded/serial parity: every distributed path against its single-device
oracle, on 1- and 4-device CPU meshes (conftest forces 4 host devices).

Oracles: ``_scan_topk`` / ``DenseIndex.search`` for ``ShardedDenseIndex``,
``fit_pca`` for ``fit_pca_distributed`` / ``StaticPruner.fit_distributed``.
Covers int8 quantisation and row counts not divisible by the device count
(device-padding rows must never surface in results).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DenseIndex, ShardedDenseIndex, StaticPruner, fit_pca, fit_pca_distributed
from repro.par import compat

RNG = np.random.default_rng(42)


def _mesh(ndev):
    if jax.device_count() < ndev:
        pytest.skip(f"needs {ndev} devices, have {jax.device_count()}")
    return jax.make_mesh((ndev,), ("data",))


def _data(n, d, nq=6):
    D = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    Q = jnp.asarray(RNG.standard_normal((nq, d)), jnp.float32)
    return D, Q


@pytest.mark.parametrize("ndev", [1, 4])
def test_sharded_search_matches_dense(ndev):
    mesh = _mesh(ndev)
    D, Q = _data(2048, 32)
    s, ids = ShardedDenseIndex.build(D, mesh).search(Q, k=10)
    ws, wids = DenseIndex.build(D).search(Q, k=10)
    assert (np.asarray(ids) == np.asarray(wids)).all()
    np.testing.assert_allclose(np.asarray(s), np.asarray(ws),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ndev", [1, 4])
def test_sharded_search_uneven_rows(ndev):
    """1003 % 4 != 0: device-padding rows score exactly 0.0, so with every
    real score forced negative an unmasked pad row would always win the
    local top-k — ids must stay < n and match the unpadded oracle."""
    mesh = _mesh(ndev)
    D, Q = _data(1003, 16)
    D, Q = jnp.abs(D), -jnp.abs(Q)        # all real scores < 0
    sidx = ShardedDenseIndex.build(D, mesh)
    assert sidx.n == 1003
    s, ids = sidx.search(Q, k=10)
    _, wids = DenseIndex.build(D).search(Q, k=10)
    assert int(ids.max()) < 1003
    assert float(s.max()) < 0.0
    assert (np.asarray(ids) == np.asarray(wids)).all()


@pytest.mark.parametrize("ndev", [1, 4])
def test_sharded_search_int8_matches_dense_int8(ndev):
    mesh = _mesh(ndev)
    D, Q = _data(1000, 32)
    s, ids = ShardedDenseIndex.build(D, mesh, quantize_int8=True).search(Q, k=10)
    ws, wids = DenseIndex.build(D, quantize_int8=True).search(Q, k=10)
    assert (np.asarray(ids) == np.asarray(wids)).all()
    np.testing.assert_allclose(np.asarray(s), np.asarray(ws),
                               rtol=1e-4, atol=1e-4)


def test_sharded_search_int8_uneven_rows_4dev():
    mesh = _mesh(4)
    D, Q = _data(1001, 16)
    D, Q = jnp.abs(D), -jnp.abs(Q)        # all real scores < 0 (see above)
    sidx = ShardedDenseIndex.build(D, mesh, quantize_int8=True)
    s, ids = sidx.search(Q, k=7)
    _, wids = DenseIndex.build(D, quantize_int8=True).search(Q, k=7)
    assert int(ids.max()) < 1001
    assert float(s.max()) < 0.0
    assert (np.asarray(ids) == np.asarray(wids)).all()


def test_sharded_pallas_backend_matches_jnp_4dev():
    mesh = _mesh(4)
    D, Q = _data(512, 32)
    _, a = ShardedDenseIndex.build(D, mesh, backend="pallas").search(Q, k=10)
    _, b = ShardedDenseIndex.build(D, mesh, backend="jnp").search(Q, k=10)
    for row in range(Q.shape[0]):
        assert set(np.asarray(a)[row].tolist()) == set(np.asarray(b)[row].tolist())


@pytest.mark.parametrize("ndev", [1, 4])
def test_sharded_hierarchical_matches_flat_1d(ndev):
    """merge='hierarchical' on a 1-axis mesh degenerates to the flat single
    stage — results must be bit-identical, and match the dense oracle."""
    mesh = _mesh(ndev)
    D, Q = _data(2048, 32)
    idx = ShardedDenseIndex.build(D, mesh)
    sf, if_ = idx.search(Q, k=10, merge="flat")
    sh, ih = idx.search(Q, k=10, merge="hierarchical")
    assert (np.asarray(sf) == np.asarray(sh)).all()
    assert (np.asarray(if_) == np.asarray(ih)).all()
    _, wids = DenseIndex.build(D).search(Q, k=10)
    assert (np.asarray(ih) == np.asarray(wids)).all()


def test_sharded_hierarchical_matches_flat_2d_mesh():
    """2x2 mesh: the hierarchical merge really runs two all-gather stages
    (within 'col', then across 'row') — bit-identical to the flat merge,
    tied scores included."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = jax.make_mesh((2, 2), ("row", "col"))
    D, Q = _data(1003, 16)          # uneven rows: device padding in play
    # duplicate a row across shards so the merges must tie-break identically
    D = D.at[900].set(D[5])
    idx = ShardedDenseIndex.build(D, mesh, merge="hierarchical")
    sh, ih = idx.search(Q, k=10)    # build-time default: hierarchical
    sf, if_ = idx.search(Q, k=10, merge="flat")
    assert (np.asarray(sf) == np.asarray(sh)).all()
    assert (np.asarray(if_) == np.asarray(ih)).all()
    _, wids = DenseIndex.build(D).search(Q, k=10)
    assert (np.asarray(ih) == np.asarray(wids)).all()


def test_sharded_hierarchical_int8_2d_mesh():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = jax.make_mesh((2, 2), ("row", "col"))
    D, Q = _data(1001, 16)
    D, Q = jnp.abs(D), -jnp.abs(Q)  # all real scores < 0 (pad-row trap)
    idx = ShardedDenseIndex.build(D, mesh, quantize_int8=True,
                                  merge="hierarchical")
    s, ids = idx.search(Q, k=7)
    _, wids = DenseIndex.build(D, quantize_int8=True).search(Q, k=7)
    assert int(ids.max()) < 1001
    assert float(s.max()) < 0.0
    assert (np.asarray(ids) == np.asarray(wids)).all()


def test_sharded_pad_rows_cannot_displace_real_candidates():
    """Device-padding rows score 0.0 — above every real score here — and
    would win the padded shard's local top-k before any post-hoc mask. The
    shard-local select must over-fetch (k+pad) so the shard's true top-k
    real rows survive. Regression: the global top-k is concentrated in the
    padded (last) shard."""
    mesh = _mesh(4)
    n, k = 29, 4                     # 29 % 4 = 1 -> 3 pad rows, last shard
    D = np.abs(RNG.standard_normal((n, 8))).astype(np.float32)
    D[-k:] *= 0.01                   # last shard holds the least-negative rows
    D, Q = jnp.asarray(D), -jnp.abs(
        jnp.asarray(RNG.standard_normal((3, 8)), jnp.float32))
    for merge in ("flat", "hierarchical"):
        s, ids = ShardedDenseIndex.build(D, mesh).search(Q, k=k, merge=merge)
        ws, wids = DenseIndex.build(D).search(Q, k=k)
        assert (np.asarray(ids) == np.asarray(wids)).all()
        np.testing.assert_allclose(np.asarray(s), np.asarray(ws),
                                   rtol=1e-5, atol=1e-5)


def test_sharded_k_exceeds_shard_rows():
    """k larger than any single shard's row count: the per-shard scan pads
    with sentinels and the global merge must still match the dense oracle."""
    mesh = _mesh(4)
    D, Q = _data(20, 8)             # 5 rows per shard < k=10
    s, ids = ShardedDenseIndex.build(D, mesh).search(Q, k=10)
    ws, wids = DenseIndex.build(D).search(Q, k=10)
    assert (np.asarray(ids) == np.asarray(wids)).all()
    np.testing.assert_allclose(np.asarray(s), np.asarray(ws),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ndev", [1, 4])
def test_fit_pca_distributed_matches_serial(ndev):
    mesh = _mesh(ndev)
    D, _ = _data(1003, 24)   # uneven rows: gram_distributed zero-pads
    s1 = fit_pca(D)
    s2 = fit_pca_distributed(D, mesh)
    np.testing.assert_allclose(np.asarray(s1.eigenvalues),
                               np.asarray(s2.eigenvalues),
                               rtol=1e-3, atol=1e-3)
    assert int(s2.n_samples) == 1003
    # eigenvectors match up to sign on the well-separated top components
    dots = np.abs(np.sum(np.asarray(s1.components) * np.asarray(s2.components),
                         axis=0))
    assert (dots[:8] > 0.99).all()


def test_static_pruner_fit_distributed_end_to_end():
    """Paper pipeline on a 4-device mesh: distributed fit -> sharded pruned
    index -> search matches the all-serial pipeline."""
    mesh = _mesh(4)
    D, Q = _data(1200, 32)
    serial = StaticPruner(cutoff=0.5).fit(D)
    dist = StaticPruner(cutoff=0.5).fit_distributed(D, mesh)
    assert dist.kept_dims == serial.kept_dims

    sidx = dist.build_index(D, mesh=mesh)
    assert isinstance(sidx, ShardedDenseIndex)
    _, ids = sidx.search(dist.transform_queries(Q), k=10)
    _, wids = serial.build_index(D).search(serial.transform_queries(Q), k=10)
    # same rotation up to column sign; scores in the rotated space agree
    assert (np.asarray(ids) == np.asarray(wids)).all()


# ---------------------------------------------------------------------------
# fused projection parity: search_projected(raw q) must be bit-identical to
# transform_queries(q) -> search on every layout x backend x dtype
# ---------------------------------------------------------------------------


def _fused_vs_two_step(idx, pruner, Qraw, k=10):
    W, mean = pruner.projection()
    qh = pruner.transform_queries(Qraw)
    s0, i0 = idx.search(qh, k=k)
    s1, i1 = idx.search_projected(Qraw, W, k=k, mean=mean)
    assert (np.asarray(i0) == np.asarray(i1)).all()
    assert (np.asarray(s0) == np.asarray(s1)).all()   # bit-identical


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_search_projected_matches_two_step_dense(backend, dtype):
    D, Q = _data(700, 32)
    pruner = StaticPruner(cutoff=0.5).fit(D)
    Dh = pruner.prune_index(D)
    if dtype == "int8":
        idx = DenseIndex.build(Dh, quantize_int8=True, backend=backend)
    else:
        idx = DenseIndex.build(
            Dh.astype(jnp.bfloat16) if dtype == "bf16" else Dh,
            backend=backend)
    _fused_vs_two_step(idx, pruner, Q)


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("ndev", [1, 4])
def test_search_projected_matches_two_step_sharded(ndev, backend, dtype):
    """Uneven shard rows on purpose: 1003 % 4 != 0, so the fused path must
    agree under device padding too."""
    mesh = _mesh(ndev)
    D, Q = _data(1003, 32)
    pruner = StaticPruner(cutoff=0.5).fit(D)
    Dh = pruner.prune_index(D)
    if dtype == "int8":
        idx = ShardedDenseIndex.build(Dh, mesh, quantize_int8=True,
                                      backend=backend)
    else:
        idx = ShardedDenseIndex.build(
            Dh.astype(jnp.bfloat16) if dtype == "bf16" else Dh,
            mesh, backend=backend)
    _fused_vs_two_step(idx, pruner, Q)


def test_search_projected_centered_pruner_dense_and_sharded():
    """center=True exercises the mean-subtraction branch of the fused jit."""
    mesh = _mesh(4)
    D, Q = _data(900, 24)
    pruner = StaticPruner(cutoff=0.5, center=True).fit(D)
    Dh = pruner.prune_index(D)
    _fused_vs_two_step(DenseIndex.build(Dh), pruner, Q)
    _fused_vs_two_step(ShardedDenseIndex.build(Dh, mesh), pruner, Q)


def test_search_projected_hierarchical_2d_mesh_int8():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = jax.make_mesh((2, 2), ("row", "col"))
    D, Q = _data(1001, 16)
    pruner = StaticPruner(cutoff=0.5).fit(D)
    Dh = pruner.prune_index(D)
    idx = ShardedDenseIndex.build(Dh, mesh, quantize_int8=True,
                                  merge="hierarchical")
    _fused_vs_two_step(idx, pruner, Q, k=7)


def test_search_projected_is_single_dispatch_dense():
    """The fused path must stay ONE compiled computation: the d->m
    projection matmul traces into the same jit as the top-k scan instead
    of running as its own dispatch on the hot path."""
    import repro.core.index as index_mod
    D, Q = _data(600, 32)
    pruner = StaticPruner(cutoff=0.5).fit(D)
    Dh = pruner.prune_index(D)
    W, _ = pruner.projection()
    jaxpr = jax.make_jaxpr(
        lambda d, w, q: index_mod._dense_search_projected(
            d, None, w, None, q, 10, None, "jnp"))(Dh, W, Q)
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    # the (B, d) @ (d, m) projection is a dot_general at this level; the
    # scan carries the streamed top-k — both inside one traced computation
    assert "dot_general" in prims or "jit" in prims
    flat = jaxpr.pretty_print(use_color=False)
    assert "dot_general" in flat and ("scan" in flat or "top_k" in flat)


def test_compat_abstract_mesh_roundtrip():
    am = compat.abstract_mesh((2, 4), ("data", "model"))
    assert tuple(am.axis_names) == ("data", "model")
    assert dict(am.shape) == {"data": 2, "model": 4}
    with pytest.raises(ValueError):
        compat.abstract_mesh((2, 4), ("data",))


def test_compat_axis_size_inside_shard_map():
    from jax.sharding import PartitionSpec as P
    mesh = _mesh(4)
    out = compat.shard_map(lambda: jnp.asarray(compat.axis_size("data")),
                           mesh=mesh, in_specs=(), out_specs=P(),
                           check_vma=False)()
    assert int(out) == 4


SERVED_N, SERVED_D, SERVED_K = 20_011, 768, 10   # n = 3 mod 4: one pad row
# f32 accumulation of a 768-term dot product, in any order, errs by at most
# gamma_d * sum|x_i q_i| <= gamma_d * |x| |q| with gamma_d = d u / (1 - d u),
# u = 2**-24; rows and queries are unit vectors, so about 4.6e-5
GAMMA_D = SERVED_D * 2.0 ** -24 / (1 - SERVED_D * 2.0 ** -24)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@pytest.fixture(scope="module")
def served_unpruned():
    """A ``RetrievalServer`` over a four-device ``ShardedDenseIndex`` of
    unit 768-d f32 rows, raw queries (no pruner), warmed up; the float64
    rows and their unit queries."""
    from functools import partial

    from repro.launch.serve import RetrievalServer
    rng = np.random.default_rng(15)
    D = rng.standard_normal((SERVED_N, SERVED_D)).astype(np.float32)
    D /= np.linalg.norm(D, axis=1, keepdims=True)
    Q = rng.standard_normal((32, SERVED_D)).astype(np.float32)
    Q /= np.linalg.norm(Q, axis=1, keepdims=True)
    index = ShardedDenseIndex.build(jnp.asarray(D), _mesh(4))
    assert index.vectors.shape[0] == SERVED_N + 1
    # 5,003 rows a shard in strips of 4,096: the second strip is ragged
    index.search = partial(index.search, block=4096)
    server = RetrievalServer(index, None, k=SERVED_K)
    server.warmup()
    yield server, D.astype(np.float64), Q
    server.close()


@pytest.mark.parametrize("nq", [1, 7, 32])
def test_served_unpruned_sharded_matches_exact(served_unpruned, nq):
    """The served raw-query path over four shards against a float64 exact
    top-10: ids wherever the 10th and 11th exact scores lie apart, scores
    within the f32 accumulation bound, and nothing compiled after
    ``warmup()``."""
    server, D64, Q = served_unpruned
    compiles = []

    def listen(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        replies = [server.submit(q) for q in Q[:nq]]
        got = [r.get(timeout=120) for r in replies]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    exact = Q[:nq].astype(np.float64) @ D64.T                 # (nq, n)
    order = np.argsort(-exact, axis=1, kind="stable")
    norms = np.linalg.norm(D64, axis=1)
    tol = GAMMA_D * norms.max() * np.linalg.norm(Q[:nq].astype(np.float64),
                                                 axis=1).max()
    for i, (scores, ids) in enumerate(got):
        ids = np.asarray(ids, np.int64)
        assert ids.shape == (SERVED_K,) and len(set(ids.tolist())) == SERVED_K
        assert ((ids >= 0) & (ids < SERVED_N)).all()
        assert (np.diff(scores) <= 0).all()
        np.testing.assert_allclose(scores, exact[i, ids], rtol=0, atol=tol)
        kth, next_ = exact[i, order[i, SERVED_K - 1]], exact[i, order[i,
                                                                    SERVED_K]]
        if kth - next_ > 2 * tol:
            assert set(ids.tolist()) == set(order[i, :SERVED_K].tolist())
