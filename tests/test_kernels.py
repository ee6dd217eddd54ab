"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_lints import iter_all_eqns
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


# ---------------------------------------------------------------------------
# gram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [(64, 16), (257, 64), (1000, 96), (1024, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_matches_oracle(n, d, dtype):
    D = _rand((n, d), dtype)
    got = ops.gram(D, block_rows=128, interpret=True)
    want = ref.gram_ref(D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 2e-3,
                               atol=1e-2)


def test_gram_block_size_invariance():
    D = _rand((500, 32), jnp.float32)
    a = ops.gram(D, block_rows=64, interpret=True)
    b = ops.gram(D, block_rows=500, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-3)


def test_gram_psd():
    D = _rand((300, 24), jnp.float32)
    G = np.asarray(ops.gram(D, interpret=True))
    evals = np.linalg.eigvalsh(G)
    assert evals.min() > -1e-3
    np.testing.assert_allclose(G, G.T, atol=1e-5)


# ---------------------------------------------------------------------------
# topk_score
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,B,k,bn", [
    (128, 16, 1, 5, 64),
    (1000, 64, 8, 10, 256),
    (555, 48, 4, 13, 128),     # non-divisible block
    (2048, 128, 16, 100, 512), # k large
])
def test_topk_matches_oracle(n, m, B, k, bn):
    D = _rand((n, m), jnp.float32)
    Q = _rand((B, m), jnp.float32)
    s1, i1 = ops.topk_score(D, Q, k=k, block_n=bn, interpret=True)
    s2, i2 = ref.topk_score_ref(D, Q, k=k)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)
    # discrete-boundary check: sets must match even if tie order differs
    for b in range(B):
        assert set(np.asarray(i1)[b].tolist()) == set(np.asarray(i2)[b].tolist())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_topk_dtypes(dtype):
    D = _rand((400, 32), dtype)
    Q = _rand((4, 32), jnp.float32)
    s1, i1 = ops.topk_score(D, Q, k=10, block_n=128, interpret=True)
    s2, i2 = ref.topk_score_ref(D, Q, k=10)
    assert (np.asarray(i1) == np.asarray(i2)).mean() > 0.95


def test_topk_k_exceeding_block():
    # k larger than one block's rows: merge must span blocks correctly.
    D = _rand((96, 8), jnp.float32)
    Q = _rand((2, 8), jnp.float32)
    s1, i1 = ops.topk_score(D, Q, k=40, block_n=32, interpret=True)
    s2, i2 = ref.topk_score_ref(D, Q, k=40)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)
    assert (np.asarray(i1) == np.asarray(i2)).all()


def test_topk_sorted_descending():
    D = _rand((300, 16), jnp.float32)
    Q = _rand((3, 16), jnp.float32)
    s, _ = ops.topk_score(D, Q, k=20, block_n=64, interpret=True)
    s = np.asarray(s)
    assert (np.diff(s, axis=-1) <= 1e-6).all()


def test_topk_duplicate_scores_tiebreak():
    # identical rows => tied scores; ids must be the smallest ones (top_k semantics)
    row = RNG.standard_normal(16).astype(np.float32)
    D = jnp.asarray(np.tile(row, (64, 1)))
    Q = jnp.asarray(row[None, :])
    _, ids = ops.topk_score(D, Q, k=8, block_n=16, interpret=True)
    assert set(np.asarray(ids)[0].tolist()) == set(range(8))


def _quantized(D):
    from repro.core.quantization import quantize_int8_per_dim
    return quantize_int8_per_dim(D)


@pytest.mark.parametrize("B", [1, 8, 64])
@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
def test_topk_parity_scan_topk(dtype, B):
    """Kernel vs the jnp oracle on every index dtype, with block_b=16 so
    B=64 crosses the batch-tile boundary (and B=1 exercises tile padding)."""
    from repro.core.index import _scan_topk
    D = _rand((1000, 64), jnp.float32)
    Q = _rand((B, 64), jnp.float32)
    if dtype == "int8":
        D, scale = _quantized(D)
        Q = Q * scale[None, :]
    elif dtype == "bf16":
        D = D.astype(jnp.bfloat16)
    s1, i1 = ops.topk_score(D, Q, k=10, block_n=256, block_b=16,
                            interpret=True)
    s2, i2 = _scan_topk(D, Q, 10, block=256)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


def test_topk_batch_not_multiple_of_tile():
    from repro.core.index import _scan_topk
    D = _rand((500, 32), jnp.float32)
    Q = _rand((10, 32), jnp.float32)
    s1, i1 = ops.topk_score(D, Q, k=7, block_n=128, block_b=8, interpret=True)
    s2, i2 = _scan_topk(D, Q, 7, block=128)
    assert (np.asarray(i1) == np.asarray(i2)).all()


def test_topk_int8_streams_native():
    """The array handed to pallas_call must keep the index dtype — an int8
    corpus streams as int8, with no fp32 shadow copy at any size."""
    D, scale = _quantized(_rand((300, 32), jnp.float32))
    Q = _rand((4, 32), jnp.float32) * scale[None, :]

    jaxpr = jax.make_jaxpr(
        lambda d, q: ops.topk_score(d, q, k=5, interpret=True))(D, Q)
    eqn = next((e for e in iter_all_eqns(jaxpr.jaxpr)
                if e.primitive.name == "pallas_call"), None)
    assert eqn is not None
    in_dtypes = {str(v.aval.dtype) for v in eqn.invars}
    assert "int8" in in_dtypes
    # and no fp32 operand the size of the corpus anywhere in the trace
    corpus_elems = D.shape[0] * D.shape[1]
    for v in eqn.invars:
        if str(v.aval.dtype) == "float32":
            assert np.prod(v.aval.shape) < corpus_elems


def test_topk_all_tied_across_strips():
    """Every score identical over multiple strips: min-id tie-break must
    match jax.lax.top_k first-occurrence order exactly."""
    row = RNG.standard_normal(16).astype(np.float32)
    D = jnp.asarray(np.tile(row, (300, 1)))
    Q = jnp.asarray(np.stack([row, 2 * row]))
    s, ids = ops.topk_score(D, Q, k=9, block_n=64, interpret=True)
    _, want = ref.topk_score_ref(D, Q, k=9)
    assert (np.asarray(ids) == np.asarray(want)).all()
    assert (np.asarray(ids) == np.arange(9)[None, :]).all()


def test_topk_k_equals_n():
    D = _rand((96, 16), jnp.float32)
    Q = _rand((3, 16), jnp.float32)
    s1, i1 = ops.topk_score(D, Q, k=96, block_n=32, interpret=True)
    s2, i2 = ref.topk_score_ref(D, Q, k=96)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


def test_topk_strip_entirely_padding():
    """n_valid cuts the corpus mid-array: the second strip is 100% masked
    (its max is -inf) and must be skipped without corrupting the running
    list; no id >= n_valid may surface."""
    from repro.core.index import _scan_topk
    D = _rand((128, 16), jnp.float32)
    Q = _rand((4, 16), jnp.float32)
    s1, i1 = ops.topk_score(D, Q, k=5, block_n=64, n_valid=64, interpret=True)
    s2, i2 = _scan_topk(D[:64], Q, 5, block=64)
    assert int(np.asarray(i1).max()) < 64
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


def test_topk_every_strip_skippable():
    """n_valid=0 masks every strip to -inf: the merge never runs and the
    finish step must still write the init state (-inf scores, -1 ids)."""
    D = _rand((256, 16), jnp.float32)
    Q = _rand((3, 16), jnp.float32)
    s, ids = ops.topk_score(D, Q, k=4, block_n=64, n_valid=0, interpret=True)
    assert (np.asarray(ids) == -1).all()
    assert np.isneginf(np.asarray(s)).all()


def test_topk_block_skip_guard_parity():
    """Top-k concentrated in the first strip: every later strip fails the
    guard (strip max < kth best) yet the result must equal the oracle —
    including when a later strip ties the kth best exactly (ascending id
    order means the tie loses anyway)."""
    base = RNG.standard_normal((256, 16)).astype(np.float32)
    base[:8] *= 100.0          # first strip dominates
    base[200] = base[7]        # exact tie with a kept row, larger id
    D = jnp.asarray(base)
    Q = jnp.asarray(base[:4] + 0.01 * RNG.standard_normal((4, 16))
                    .astype(np.float32))
    s1, i1 = ops.topk_score(D, Q, k=8, block_n=64, interpret=True)
    s2, i2 = ref.topk_score_ref(D, Q, k=8)
    assert (np.asarray(i1) == np.asarray(i2)).all()
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


def test_topk_rescore_nonascending_ids_tiebreak():
    """Regression (ROADMAP follow-up (a)): rescore mode with a deliberately
    NON-ascending shortlist. A tied score in a later strip carries a
    SMALLER doc id; the old skip-on-equality guard never merged that strip,
    surfacing the larger id and breaking the min-id tie-break. The guard
    now merges on equality whenever row_ids is present."""
    m = 16
    D = np.zeros((16, m), np.float32)
    D[:, 0] = np.linspace(0.5, 2.0, 16)   # background, all < 5
    D[3, 0] = 5.0     # strip 1 (rows 0-7): tied max, LARGER id
    D[11, 0] = 5.0    # strip 2 (rows 8-15): tied max, SMALLER id
    row_ids = np.asarray([20, 21, 22, 10, 24, 25, 26, 27,
                          28, 29, 30, 7, 32, 33, 34, 35], np.int32)
    Q = np.zeros((1, m), np.float32)
    Q[0, 0] = 1.0
    s, ids = ops.topk_score(jnp.asarray(D), jnp.asarray(Q), k=1, block_n=8,
                            interpret=True, row_ids=jnp.asarray(row_ids))
    assert float(np.asarray(s)[0, 0]) == 5.0
    assert int(np.asarray(ids)[0, 0]) == 7    # min id among the tied max


def test_topk_rescore_ascending_ids_unchanged():
    """The guard change must be invisible for the ascending shortlists the
    cascade actually emits: rescore-mode results still match the oracle."""
    D = _rand((200, 32), jnp.float32)
    Q = _rand((3, 32), jnp.float32)
    ids = jnp.arange(200, dtype=jnp.int32) + 1000       # ascending, offset
    s1, i1 = ops.topk_score(D, Q, k=7, block_n=64, interpret=True,
                            row_ids=ids)
    s2, i2 = ref.topk_score_ref(D, Q, k=7)
    assert (np.asarray(i1) == np.asarray(i2) + 1000).all()
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# pca_project (+ quant epilogue)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,m", [(100, 32, 8), (513, 96, 48), (1024, 128, 64)])
def test_project_matches_oracle(n, d, m):
    D = _rand((n, d), jnp.float32)
    W = _rand((d, m), jnp.float32)
    got = ops.pca_project(D, W, block_rows=128, interpret=True)
    want = ref.pca_project_ref(D, W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_project_quant_matches_oracle():
    D = _rand((500, 64), jnp.float32)
    W = _rand((64, 32), jnp.float32)
    t = np.asarray(ref.pca_project_ref(D, W))
    scale = jnp.asarray(np.abs(t).max(0) / 127.0)
    got = np.asarray(ops.pca_project_quant(D, W, scale, block_rows=128, interpret=True))
    want = np.asarray(ref.pca_project_quant_ref(D, W, scale))
    # rounding boundaries may flip +-1 ulp of int8 on a tiny fraction
    assert (got == want).mean() > 0.999
    assert np.abs(got.astype(np.int32) - want.astype(np.int32)).max() <= 1
    assert got.dtype == np.int8


def test_project_quant_roundtrip_error_bounded():
    D = _rand((400, 48), jnp.float32)
    W = np.linalg.qr(RNG.standard_normal((48, 48)))[0].astype(np.float32)
    W = jnp.asarray(W[:, :24])
    t = np.asarray(ref.pca_project_ref(D, W))
    scale = jnp.asarray(np.abs(t).max(0) / 127.0)
    q = np.asarray(ops.pca_project_quant(D, W, scale, interpret=True))
    rec = q.astype(np.float32) * np.asarray(scale)[None, :]
    rel = np.linalg.norm(rec - t) / np.linalg.norm(t)
    assert rel < 0.01  # int8 symmetric ~ <1% Frobenius error
