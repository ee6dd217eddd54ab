"""A plain float64 oracle for ``PagedIndex`` searches.

It shares nothing with the program's search: each extent of the index is
dequantised on the host (``storage.extent_rows(i)`` times that extent's
scale), every row is scored in float64, and the exact top-k is taken with
the lowest-id tie-break (a stable sort on descending score).

A result is judged as ``bench/harness/reference.py`` judges ``correct``:

  * ids exact — the returned ids equal the oracle's, rank for rank. The one
    exception is a tie the rounding cannot resolve: a rank where the two
    ids differ passes only if their exact scores lie within twice the
    bound below, and ``rank_gap`` still holds;
  * ``score_err`` — a returned score against the exact score of its id;
  * ``rank_gap`` — how far a returned id's exact score lies below the
    exact k-th best of its query.

The bound is argued from float32 accumulation over m terms. A score is
``sum_j (q_j * s_j) * r_j`` (the extent scale ``s`` folds into the query,
``r`` the stored row). With unit roundoff ``u = 2**-24`` a recursive or
blocked f32 dot product of m terms errs by at most ``gamma_m * sum_j
|q_j s_j r_j|`` with ``gamma_m = m u / (1 - m u)`` (Higham, Accuracy and
Stability of Numerical Algorithms, 3.1), in any summation order. The fold
``q_j * s_j`` adds one rounding per term; int8 and bf16 rows meet the
query as three bf16 terms whose products are exact in f32 and whose sum
``hi + (mid + lo)`` adds two more; f32 rows are multiplied exactly at
``HIGHEST``. So the scoring error is at most ``(m + 4) u / (1 - (m + 4) u)
* A`` with ``A = sum_j |q_j| |s_j r_j|``. A raw query is first projected,
``q = (x - mean) @ W``, an f32 dot product over d terms plus the centring
rounding: ``|dq_j| <= gamma_(d+1) * sum_i |x_i - mean_i| |W_ij|``, which
reaches the score as ``sum_j |dq_j| |s_j r_j|``. The bound is twice the
sum of both terms — the factor two covers ``1 / (1 - n u)`` and the final
rounding of the f32 score.
"""
import numpy as np

U = 2.0 ** -24


def dequantised_rows(index) -> np.ndarray:
    """Every row of a ``PagedIndex`` in global-id order, float64."""
    st = index.storage
    parts = [np.zeros((0, st.dim))]
    for i, e in enumerate(st.extents):
        rows = st.extent_rows(i).astype(np.float64)
        if e.scale is not None:
            rows = rows * np.asarray(e.scale, np.float64)[None, :]
        parts.append(rows)
    return np.concatenate(parts)


def project(queries, W=None, mean=None):
    """(exact projected queries, per-dim bound on the f32 projection's
    error) — the bound is zero for queries the index scores as given."""
    x = np.atleast_2d(np.asarray(queries, np.float64))
    if W is None:
        return x, np.zeros_like(x)
    W = np.asarray(W, np.float64)
    if mean is not None:
        x = x - np.asarray(mean, np.float64)[None, :]
    d = W.shape[0]
    return x @ W, (d + 1) * U * (np.abs(x) @ np.abs(W))


def exact_topk(rows: np.ndarray, q: np.ndarray, k: int):
    """Exact float64 (scores, ids) of the top-min(k, n), lowest id first
    among equal scores."""
    exact = q @ rows.T
    ids = np.argsort(-exact, axis=1, kind="stable")[:, :min(k, rows.shape[0])]
    return np.take_along_axis(exact, ids, axis=1), ids


def score_bound(rows: np.ndarray, q: np.ndarray, dq: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """Per returned (query, id) bound on |f32 score - exact score|."""
    m = rows.shape[1]
    r = np.abs(rows[ids])                                   # (B, k, m)
    a = np.einsum("bm,bkm->bk", np.abs(q), r)
    p = np.einsum("bm,bkm->bk", dq, r)
    return 2.0 * ((m + 4) * U * a + p)


def assert_matches_oracle(index, queries, result, k: int, *, W=None,
                          mean=None, msg: str = "") -> None:
    """Judge a search ``result`` = (scores, ids) of ``queries`` against the
    oracle over ``index``'s rows. Pass ``W`` (and ``mean``) when the
    queries are raw, as ``search_projected`` takes them."""
    rows = dequantised_rows(index)
    q, dq = project(queries, W, mean)
    ref_s, ref_i = exact_topk(rows, q, k)
    got_s = np.asarray(result[0], np.float64)
    got_i = np.asarray(result[1], np.int64)
    assert got_i.shape == ref_i.shape, (msg, got_i.shape, ref_i.shape)
    assert ((got_i >= 0) & (got_i < rows.shape[0])).all(), (msg, got_i)
    assert all(len(set(r)) == len(r) for r in got_i.tolist()), (msg, got_i)
    got_exact = np.take_along_axis(q @ rows.T, got_i, axis=1)
    bound = score_bound(rows, q, dq, got_i)
    score_err = np.abs(got_s - got_exact)
    assert (score_err <= bound).all(), \
        f"{msg}: score_err {score_err.max():.3g} over bound {bound.min():.3g}"
    rank_gap = ref_s[:, -1:] - got_exact
    assert (rank_gap <= bound).all(), \
        f"{msg}: rank_gap {rank_gap.max():.3g} over bound {bound.min():.3g}"
    tie = np.abs(got_exact - ref_s) <= 2.0 * bound
    assert ((got_i == ref_i) | tie).all(), \
        f"{msg}: ids differ from the oracle's\n{got_i}\n{ref_i}"
