"""PCA decomposition of dense-retrieval embedding indexes.

Implements the paper's core machinery (Siciliano et al., 2024):

    D^T D = W Λ W^T              (uncentered Gram eigendecomposition)
    T     = D W                  (rotated index, variance-sorted columns)
    D̂    = T_m = D W_m           (pruned index at cutoff c = (d-m)/d)
    q̂    = W_m^T q               (query transform, applied online)

The paper eigendecomposes the *uncentered* Gram matrix D^T D (not the
mean-centred covariance); we default to that for faithfulness and expose
``center=True`` as an option (classical PCA).

Three Gram paths, one math:
  * ``gram(D)``                — single-device blocked jnp (reference).
  * ``gram_streaming(batches)``— host-side accumulation over an iterator of
                                 row blocks; the index never needs to be
                                 resident (production offline path).
  * ``gram_distributed(D, mesh)`` — rows sharded over every mesh device,
                                 local Gram + psum (multi-pod offline path).
A Pallas kernel path (``repro.kernels.gram_ops``) is selected automatically
for large blocks when available.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.topk_score import PRECISION
from repro.par import compat


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PCAState:
    """Result of fitting PCA on an embedding matrix.

    Attributes:
      components: ``W`` — (d, d) orthonormal eigenvector matrix, columns
        sorted by decreasing eigenvalue.
      eigenvalues: (d,) eigenvalues of the (un)centered Gram/covariance,
        descending, clipped at >= 0.
      mean: (d,) mean row of the fitted corpus (zeros when ``center=False``
        — kept so transform code is branch-free).
      n_samples: number of embedding rows used for the fit.
      centered: static flag — whether ``mean`` was subtracted before the
        eigendecomposition.
    """

    components: jax.Array
    eigenvalues: jax.Array
    mean: jax.Array
    n_samples: jax.Array
    centered: bool = dataclasses.field(metadata=dict(static=True), default=False)

    @property
    def d(self) -> int:
        return self.components.shape[0]


# ---------------------------------------------------------------------------
# Gram computation
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("block_rows",))
def gram(D: jax.Array, block_rows: int = 8192) -> jax.Array:
    """Blocked ``D^T D`` in fp32, streaming row blocks through a scan.

    Blocking bounds the transient working set to ``block_rows × d`` while the
    (d, d) accumulator stays live — the structure the Pallas kernel mirrors
    on TPU (strip streams HBM→VMEM, accumulator is VMEM-resident).
    """
    n, d = D.shape
    nblocks = max(1, -(-n // block_rows))
    pad = nblocks * block_rows - n
    Dp = jnp.pad(D, ((0, pad), (0, 0))) if pad else D
    blocks = Dp.reshape(nblocks, block_rows, d)

    def body(acc, blk):
        blk = blk.astype(jnp.float32)
        return acc + jnp.dot(blk.T, blk, precision=PRECISION), None

    acc0 = jnp.zeros((d, d), jnp.float32)
    out, _ = jax.lax.scan(body, acc0, blocks)
    return out


def gram_streaming(batches: Iterable[np.ndarray | jax.Array]) -> tuple[jax.Array, jax.Array, int]:
    """Accumulate Gram + column sums over an iterator of row blocks.

    Returns ``(G, colsum, n)`` so the caller can optionally centre:
    ``cov = G/n − mean meanᵀ``. The corpus never needs to fit in memory.
    """
    G = None
    colsum = None
    n = 0
    def _step(g, s, b):
        b = b.astype(jnp.float32)
        return g + jnp.dot(b.T, b, precision=PRECISION), s + b.sum(0)

    step = jax.jit(_step)
    for b in batches:
        b = jnp.asarray(b)
        if G is None:
            d = b.shape[1]
            G = jnp.zeros((d, d), jnp.float32)
            colsum = jnp.zeros((d,), jnp.float32)
        G, colsum = step(G, colsum, b)
        n += int(b.shape[0])
    if G is None:
        raise ValueError("gram_streaming received an empty iterator")
    return G, colsum, n


def gram_distributed(D: jax.Array, mesh: Mesh) -> jax.Array:
    """Gram of a row-sharded index: local strip Gram + psum over all axes.

    ``D`` is (n, d) sharded ``P(mesh.axis_names, None)`` (rows over every
    device). Each device computes its strip's Gram and a single all-reduce
    of (d, d) fp32 — d ≤ 4096 ⇒ ≤ 64 MiB, negligible next to streaming D.
    Row counts not divisible by the device count are zero-padded: zero rows
    are Gram-neutral, so the result is exact.
    """
    axes = tuple(mesh.axis_names)
    spec = P(axes, None)
    ndev = int(np.prod(mesh.devices.shape))
    pad = (-D.shape[0]) % ndev
    if pad:
        D = jnp.pad(D, ((0, pad), (0, 0)))

    def local_gram(strip):
        strip = strip.astype(jnp.float32)
        return jax.lax.psum(jnp.dot(strip.T, strip, precision=PRECISION),
                            axes)

    fn = compat.shard_map(local_gram, mesh=mesh, in_specs=(spec,),
                          out_specs=P(None, None))
    return jax.jit(fn)(D)


# ---------------------------------------------------------------------------
# Fit
# ---------------------------------------------------------------------------


def _eig_from_gram(G: jax.Array, colsum: jax.Array, n: int, center: bool) -> PCAState:
    d = G.shape[0]
    mean = colsum / jnp.maximum(n, 1)
    if center:
        M = G / jnp.maximum(n, 1) - jnp.outer(mean, mean)
    else:
        M = G
        mean = jnp.zeros((d,), jnp.float32)
    # eigh returns ascending eigenvalues; the paper wants descending.
    evals, evecs = jnp.linalg.eigh(M.astype(jnp.float64) if jax.config.jax_enable_x64 else M)
    order = jnp.argsort(evals)[::-1]
    evals = jnp.clip(evals[order], 0.0, None).astype(jnp.float32)
    evecs = evecs[:, order].astype(jnp.float32)
    return PCAState(components=evecs, eigenvalues=evals, mean=mean,
                    n_samples=jnp.asarray(n, jnp.int32), centered=center)


def fit_pca(D: jax.Array, *, center: bool = False, block_rows: int = 8192) -> PCAState:
    """Fit PCA on an in-memory embedding matrix (paper default: uncentered)."""
    D = jnp.asarray(D)
    n, d = D.shape
    G = gram(D, block_rows=min(block_rows, max(1, n)))
    colsum = D.sum(0, dtype=jnp.float32)
    return _eig_from_gram(G, colsum, n, center)


def fit_pca_streaming(batches: Iterable[np.ndarray | jax.Array], *, center: bool = False) -> PCAState:
    """Fit PCA over an iterator of row blocks (out-of-core offline path)."""
    G, colsum, n = gram_streaming(batches)
    return _eig_from_gram(G, colsum, n, center)


def fit_pca_distributed(D: jax.Array, mesh: Mesh, *, center: bool = False) -> PCAState:
    """Fit PCA on a row-sharded index across a mesh (multi-pod offline path)."""
    G = gram_distributed(D, mesh)
    colsum = D.sum(0, dtype=jnp.float32)
    n = D.shape[0]
    return _eig_from_gram(G, colsum, n, center)


# ---------------------------------------------------------------------------
# Transforms
# ---------------------------------------------------------------------------


def m_from_cutoff(d: int, cutoff: float) -> int:
    """Paper's cutoff c = (d - m)/d ⇒ m = round(d · (1 - c)). c in [0, 1)."""
    if not 0.0 <= cutoff < 1.0:
        raise ValueError(f"cutoff must be in [0, 1), got {cutoff}")
    return max(1, int(round(d * (1.0 - cutoff))))


def cutoff_from_m(d: int, m: int) -> float:
    return (d - m) / d


def transform(X: jax.Array, state: PCAState, m: int | None = None) -> jax.Array:
    """Project rows of X onto the first m principal components: X @ W_m."""
    W = state.components
    if m is not None:
        W = W[:, :m]
    Xc = X - state.mean if state.centered else X
    return jnp.dot(Xc, W, precision=PRECISION).astype(X.dtype)


def transform_query(q: jax.Array, state: PCAState, m: int | None = None) -> jax.Array:
    """q̂ = W_m^T q for a single query (d,) or a batch (B, d)."""
    return transform(jnp.atleast_2d(q), state, m).reshape(
        (*q.shape[:-1], m if m is not None else state.d))


def projection_operands(state: PCAState, m: int | None = None
                        ) -> tuple[jax.Array, jax.Array | None]:
    """``(W_m, mean-or-None)`` — the operands a fused search needs.

    ``DenseIndex.search_projected`` / ``ShardedDenseIndex.search_projected``
    trace ``transform_query`` inline (projection + int8 scale fold + top-k
    in one jit); they take these raw arrays instead of a ``PCAState`` so
    the hot path carries no pytree and the compiled cache keys stay flat.
    ``mean`` is ``None`` for the paper's uncentered fit — the fused path
    then skips the subtract entirely rather than adding zeros.
    """
    W = state.components if m is None else state.components[:, :m]
    return W, (state.mean if state.centered else None)


def inverse_transform(T: jax.Array, state: PCAState) -> jax.Array:
    """Reconstruct from an m-dim projection (lossy for m < d): T @ W_m^T."""
    m = T.shape[-1]
    X = T @ state.components[:, :m].T
    return X + state.mean if state.centered else X


def explained_variance_ratio(state: PCAState) -> jax.Array:
    tot = jnp.maximum(state.eigenvalues.sum(), 1e-30)
    return state.eigenvalues / tot


def m_for_variance(state: PCAState, target: float) -> int:
    """Smallest m whose leading eigenvalues explain >= target of total.

    Clamped to [1, d]: with ``target=1.0`` fp32 rounding can leave
    ``cumsum.max() < target``, where searchsorted would point past the
    last component.
    """
    csum = jnp.cumsum(explained_variance_ratio(state))
    m = int(jnp.searchsorted(csum, jnp.float32(target)) + 1)
    return max(1, min(m, state.d))


# ---------------------------------------------------------------------------
# Serialization (offline artefact: W, Λ, mean)
# ---------------------------------------------------------------------------


def save_pca(path: str, state: PCAState) -> None:
    np.savez(path,
             components=np.asarray(state.components),
             eigenvalues=np.asarray(state.eigenvalues),
             mean=np.asarray(state.mean),
             n_samples=np.asarray(state.n_samples),
             centered=np.asarray(state.centered))


def load_pca(path: str) -> PCAState:
    z = np.load(path)
    return PCAState(components=jnp.asarray(z["components"]),
                    eigenvalues=jnp.asarray(z["eigenvalues"]),
                    mean=jnp.asarray(z["mean"]),
                    n_samples=jnp.asarray(z["n_samples"]),
                    centered=bool(z["centered"]))
