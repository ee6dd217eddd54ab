"""DenseIndex / ShardedDenseIndex / int8 quantisation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DenseIndex, ShardedDenseIndex
from repro.core.quantization import dequantize_int8, quantization_error, quantize_int8_per_dim

RNG = np.random.default_rng(7)


def _data(n=2000, d=64):
    D = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    Q = jnp.asarray(RNG.standard_normal((9, d)), jnp.float32)
    return D, Q


def test_exact_search_matches_bruteforce():
    D, Q = _data()
    idx = DenseIndex.build(D)
    s, ids = idx.search(Q, k=10, block=300)
    brute = np.asarray(Q) @ np.asarray(D).T
    want_ids = np.argsort(-brute, axis=1)[:, :10]
    assert (np.asarray(ids) == want_ids).all()
    np.testing.assert_allclose(np.asarray(s),
                               np.take_along_axis(brute, want_ids, 1),
                               rtol=1e-4, atol=1e-4)


def test_block_size_invariance():
    D, Q = _data(777)
    a = DenseIndex.build(D).search(Q, k=7, block=100)
    b = DenseIndex.build(D).search(Q, k=7, block=7777)
    assert (np.asarray(a[1]) == np.asarray(b[1])).all()


def test_pallas_backend_matches_jnp():
    D, Q = _data(500, 32)
    a = DenseIndex.build(D, backend="jnp").search(Q, k=10)
    b = DenseIndex.build(D, backend="pallas").search(Q, k=10)
    for x in range(Q.shape[0]):
        assert set(np.asarray(a[1])[x].tolist()) == set(np.asarray(b[1])[x].tolist())


def test_pallas_backend_honours_block():
    """``block`` used to be silently dropped on the pallas backend — a
    non-default block must reach the kernel and preserve exact results."""
    D, Q = _data(700, 32)
    idx = DenseIndex.build(D, backend="pallas")
    s_def, i_def = idx.search(Q, k=10)
    s_blk, i_blk = idx.search(Q, k=10, block=256)   # non-default block_n
    assert (np.asarray(i_def) == np.asarray(i_blk)).all()
    np.testing.assert_allclose(np.asarray(s_def), np.asarray(s_blk),
                               rtol=1e-5, atol=1e-5)
    # and both match the jnp oracle at another non-default block
    _, want = DenseIndex.build(D).search(Q, k=10, block=130)
    assert (np.asarray(i_blk) == np.asarray(want)).all()


def test_int8_index_recall():
    D, Q = _data(3000, 64)
    full = DenseIndex.build(D)
    q8 = DenseIndex.build(D, quantize_int8=True)
    assert q8.nbytes < full.nbytes / 3.5
    _, ids_f = full.search(Q, k=10)
    _, ids_q = q8.search(Q, k=10)
    # int8 keeps high top-10 overlap
    overlap = np.mean([len(set(np.asarray(ids_f)[i]) & set(np.asarray(ids_q)[i])) / 10
                       for i in range(Q.shape[0])])
    assert overlap > 0.8


def test_quantization_roundtrip_error_small():
    D, _ = _data(1000, 32)
    assert float(quantization_error(D)) < 0.01
    q, s = quantize_int8_per_dim(D)
    assert q.dtype == jnp.int8
    rec = dequantize_int8(q, s)
    assert float(jnp.abs(rec - D).max()) < float(jnp.abs(D).max()) * 0.02


def test_sharded_index_single_device_mesh():
    # 1-device mesh exercises the shard_map merge path end to end
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    D, Q = _data(1024, 32)
    sidx = ShardedDenseIndex.build(D, mesh)
    s, ids = sidx.search(Q, k=10)
    _, want = DenseIndex.build(D).search(Q, k=10)
    assert (np.asarray(ids) == np.asarray(want)).all()


def test_sharded_index_pads_uneven_rows():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    D, Q = _data(1000, 16)   # 1000 rows, any padding must not surface
    sidx = ShardedDenseIndex.build(D, mesh)
    s, ids = sidx.search(Q, k=5)
    assert int(ids.max()) < 1000


# ---------------------------------------------------------------------------
# the scan's bucketed select against its direct per-strip top_k
# ---------------------------------------------------------------------------

def _direct_scan(monkeypatch, *args, **kw):
    """``_scan_topk`` traced afresh with the bucketed select off: today's
    per-strip ``top_k``, the oracle of the bucketed path."""
    from repro.core import index as ix
    with monkeypatch.context() as mp:
        mp.setattr(ix, "_select_plan", lambda k, block: 0)
        scan = jax.jit(ix._scan_topk.__wrapped__,
                       static_argnames=("k", "block", "vma_axes", "guard"))
        return scan(*args, **kw)


def _tied_data(n, d, B, dtype, rng):
    """Integer-valued rows, a quarter of them copies of others, and zero
    rows (+0 and -0), so that equal scores fall in many buckets and strips;
    the first query is all negative against non-negative rows, so its best
    scores are zeros of both signs."""
    D = rng.integers(0, 3, (n, d)).astype(np.float32)
    D[rng.integers(0, n, n // 4)] = D[rng.integers(0, n, n // 4)]
    D[rng.integers(0, n, n // 20)] = 0.0
    D[rng.integers(0, n, n // 20)] = -0.0
    Q = rng.integers(-1, 2, (B, d)).astype(np.float32)
    Q[0] = -rng.integers(1, 3, d)
    D = D.astype(np.int8) if dtype == "int8" else D
    return jnp.asarray(D), jnp.asarray(Q)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("B", [1, 32])
@pytest.mark.parametrize("guard", ["row", "batch"])
@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_bucketed_select_bitwise_matches_direct(monkeypatch, dtype, guard, B):
    """k=8 over 8,192-row strips engages the bucketed select; 3 strips and
    a sliding masked last one. Scores (to the bit) and ids, ties included,
    equal the direct path's."""
    from repro.core.index import BUCKET, _scan_topk, _select_plan
    assert _select_plan(8, 8192) == BUCKET
    D, Q = _tied_data(3 * 8192 + 1000, 16, B, dtype,
                      np.random.default_rng(B))
    got = _scan_topk(D, Q, 8, block=8192, guard=guard)
    want = _direct_scan(monkeypatch, D, Q, k=8, block=8192, guard=guard)
    for g, w in zip(got, want):
        assert (_bits(g) == _bits(w)).all()
    assert (np.asarray(want[0])[0] == 0).all()   # the zeros case is hit


def test_bucketed_select_sharded_one_device(monkeypatch):
    """The per-shard scan under shard_map (``vma_axes``) takes the bucketed
    select too, with the direct path's bits."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    D, Q = _tied_data(3 * 8192 + 1000, 16, 32, "f32",
                      np.random.default_rng(3))
    got = ShardedDenseIndex.build(D, mesh).search(Q, k=8, block=8192)
    want = _direct_scan(monkeypatch, D, Q, k=8, block=8192)
    for g, w in zip(got, want):
        assert (_bits(g) == _bits(w)).all()


@pytest.mark.parametrize("k,block,engaged", [
    (10, 65536, True),       # the served shape
    (8, 8192, True),
    (640, 65536, False),     # a cascade coarse scan, N·k = 64·10
    (10, 65536 + 64, False),  # not a whole number of buckets
    (10, 256, False),        # a small test strip
])
def test_select_plan(k, block, engaged):
    from repro.core.index import BUCKET, _select_plan
    assert _select_plan(k, block) == (BUCKET if engaged else 0)


def test_bucketed_select_scopes_in_hlo():
    """When the path engages, the compiled scan's op_names nest
    ``/select/bucket/`` and ``/select/refine/`` under ``select``; the
    direct path has neither."""
    import re
    from repro.core.index import _scan_topk
    D, Q = _data(3 * 8192 + 1000, 16)

    def names(block):
        hlo = _scan_topk.lower(D, Q, 8, block=block).compile().as_text()
        return re.findall(r'op_name="([^"]*)"', hlo)

    bucketed = names(8192)
    assert any("/select/bucket/" in n for n in bucketed)
    assert any("/select/refine/" in n for n in bucketed)
    direct = names(256)
    assert any("/select/" in n for n in direct)
    assert not any("/bucket/" in n or "/refine/" in n for n in direct)
