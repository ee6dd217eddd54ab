"""The exact reference and the comparison that decides ``correct``.

Imports nothing of the program. For a sample of the requests the window
answered, the reference finds the exact top-k over the corpus the index
was built from:

  1. The projected query ``q_hat = (q @ W) * scale`` in float64 on the
     host (``W`` and ``scale`` are the benchmark's own; a configuration
     without them scores raw queries).
  2. Candidates on the device: every stored block is regenerated by the
     program that made it (``Corpus.rows``), scored against ``q_hat`` at
     ``Precision.HIGHEST`` and cut to its best ``CANDIDATES`` per query.
  3. Exact scores in float64 on the host, for the candidates and for every
     returned id, from the stored rows gathered in a second pass.

Each result set is then read three ways:

  * ``bad_ids``: rows with an id outside the corpus, a repeated id, or a
    score that is not finite (limit 0);
  * ``score_err``: the widest gap between a returned score and the exact
    score of the id it came with;
  * ``rank_gap``: the widest gap by which a returned id's exact score lies
    below the exact k-th best of its query.

``control`` runs the same scan in the precision one step below what the
configuration states, in the program's place, and reads it the same way.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CANDIDATES = 32


def _dot(a, b, precision: str):
    """``a @ b`` in float32 at ``highest``; at ``high``, three bf16 passes
    written out, so that the control computes the same on every backend:
    each operand is split into a bf16 head and a bf16 tail (by
    ``reduce_precision``, which the compiler may not elide as it may a
    round trip through bfloat16), the tail x tail term is dropped, and the
    three products of bf16 values are exact in float32."""
    exact = partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return exact(a, b)
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    ah, bh = bf16(a), bf16(b)
    al, bl = bf16(a - ah), bf16(b - bh)
    return exact(ah, bh) + (exact(ah, bl) + exact(al, bh))


@partial(jax.jit, static_argnames=("c", "n", "precision"))
def _block_top(q, rows, base, c: int, n: int, precision: str):
    s = _dot(q, rows.astype(jnp.float32).T, precision)
    ids = base + jnp.arange(rows.shape[0], dtype=jnp.int32)[None, :]
    s = jnp.where(ids < n, s, -jnp.inf)
    top, at = jax.lax.top_k(s, c)
    return top, base + at.astype(jnp.int32)


@partial(jax.jit, static_argnames=("precision",))
def _project(q, W, scale, precision: str):
    if W is not None:
        q = _dot(q, W, precision)
    return q if scale is None else q * scale[None, :]


def _merge(best, new, c: int):
    s = np.concatenate([best[0], new[0]], axis=1)
    i = np.concatenate([best[1], new[1]], axis=1)
    at = np.argsort(-s, axis=1, kind="stable")[:, :c]
    return np.take_along_axis(s, at, 1), np.take_along_axis(i, at, 1)


def _scan(corpus, device, q, c: int, precision: str, rows_of):
    """Top-c (scores, ids) of ``q`` over every block ``rows_of(b)``."""
    qd = jax.device_put(np.asarray(q, np.float32), device)
    best = (np.full((len(q), 0), -np.inf, np.float32),
            np.zeros((len(q), 0), np.int32))
    for b in range(corpus.nblocks):
        s, i = _block_top(qd, rows_of(b), np.int32(b * corpus.block), c=c,
                          n=corpus.n, precision=precision)
        best = _merge(best, (np.asarray(s), np.asarray(i)), c)
    return best


def _gather(corpus, device, ids: np.ndarray) -> dict:
    """{id: stored row as float64} for every id in ``ids``."""
    ids = np.unique(ids[(ids >= 0) & (ids < corpus.n)])
    out = {}
    by_block = ids // corpus.block
    for b in np.unique(by_block):
        want = ids[by_block == b]
        pad = 1 << int(np.ceil(np.log2(len(want))))
        local = np.zeros(pad, np.int32)
        local[:len(want)] = want - b * corpus.block
        rows = np.asarray(jnp.take(corpus.rows(int(b), device),
                                   jax.device_put(local, device), axis=0))
        for j, i in enumerate(want):
            out[int(i)] = rows[j].astype(np.float64)
    return out


def q_hat(corpus, Q: np.ndarray) -> np.ndarray:
    q = np.asarray(Q, np.float64)
    if corpus.W is not None:
        q = q @ corpus.W.astype(np.float64)
    if corpus.scale is not None:
        q = q * corpus.scale.astype(np.float64)[None, :]
    return q


class Exact:
    """Exact top-k of a sample of queries, and readings of result sets."""

    def __init__(self, corpus, device, Q: np.ndarray, k: int):
        self.corpus, self.device, self.k = corpus, device, k
        self.q = q_hat(corpus, Q)
        self.cand_s, self.cand_i = _scan(
            corpus, device, self.q.astype(np.float32), CANDIDATES, "highest",
            lambda b: corpus.rows(b, device))
        self._rows: dict = {}

    def _fetch(self, ids: np.ndarray) -> None:
        new = np.setdiff1d(ids, np.fromiter(self._rows, np.int64,
                                            len(self._rows)))
        if len(new):
            self._rows.update(_gather(self.corpus, self.device, new))

    def scores(self, ids: np.ndarray) -> np.ndarray:
        """Exact float64 scores of (S, j) ids; NaN where an id is not in
        the corpus."""
        self._fetch(ids.ravel())
        m = self.q.shape[1]
        rows = np.stack([self._rows.get(int(i), np.full(m, np.nan))
                         for i in ids.ravel()]).reshape(*ids.shape, m)
        return np.einsum("sm,sjm->sj", self.q, rows)

    @functools.cached_property
    def kth(self) -> tuple[np.ndarray, int]:
        """Exact k-th best score per query, and how many queries' top-k
        the candidates could not be shown to hold."""
        exact = self.scores(self.cand_i)
        srt = -np.sort(-exact, axis=1)
        kth = srt[:, self.k - 1]
        # an id outside the candidates scored at most the last candidate's
        # float32 score, which lies within err of its exact score
        err = np.nanmax(np.abs(exact - self.cand_s))
        uncovered = int((self.cand_s[:, -1] + 2 * err >= kth).sum())
        return kth, uncovered

    def read(self, scores: np.ndarray, ids: np.ndarray) -> dict:
        scores = np.asarray(scores, np.float64)
        ids = np.asarray(ids, np.int64)
        bad = ~np.isfinite(scores).all(axis=1)
        bad |= ((ids < 0) | (ids >= self.corpus.n)).any(axis=1)
        bad |= np.array([len(set(r)) < len(r) for r in ids.tolist()])
        exact = self.scores(ids)
        kth, uncovered = self.kth
        good = ~bad
        err = np.abs(scores - exact)[good]
        gap = (kth[:, None] - exact)[good]
        return dict(bad_ids=int(bad.sum()),
                    score_err=float(err.max()) if err.size else 0.0,
                    rank_gap=float(max(gap.max(), 0.0)) if gap.size else 0.0,
                    uncovered=uncovered)


def control(exact: Exact, Q: np.ndarray, kind: str) -> dict:
    """The reference in the program's place, one precision step down:
    ``high`` (three bf16 passes) for float32 at ``highest``, or ``int4``
    rows for int8 rows. Read like a served result."""
    corpus, device, k = exact.corpus, exact.device, exact.k
    if kind == "high":
        q = _project(jax.device_put(np.asarray(Q, np.float32), device),
                     None if corpus.W is None else jax.device_put(
                         corpus.W, device),
                     None if corpus.scale is None else jax.device_put(
                         corpus.scale, device), precision="high")
        s, i = _scan(corpus, device, np.asarray(q), k, "high",
                     lambda b: corpus.rows(b, device))
    elif kind == "int4":
        scale4 = (corpus.scale * (127.0 / 7.0)).astype(np.float32)
        q = np.asarray(Q, np.float64) @ corpus.W.astype(np.float64)
        q = (q * scale4.astype(np.float64)[None, :]).astype(np.float32)
        s, i = _scan(corpus, device, q, k, "highest",
                     lambda b: corpus.rows(b, device, scale=scale4, qmax=7))
    else:
        raise ValueError(f"unknown control {kind!r}")
    return exact.read(s, i)
