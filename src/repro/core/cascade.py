"""Cascade retrieval: coarse scan at m_coarse → exact shortlist rescore.

The paper's static pruning picks ONE operating point on the m-vs-quality
curve. Related work (query embedding pruning, arXiv 2108.10341; conditional
dimension reduction, arXiv 2205.03284) shows adaptive per-query pruning
beats any single static cutoff — at a per-query decision cost. The cascade
captures that win query-independently, offline:

  1. **coarse scan** — the first pass scans an aggressively pruned index
     (the first ``m_coarse`` PCA dims, int8) for the top-(N·k) per query.
     PCA dims *nest*: the coarse matrix is literally the full pruned
     matrix's leading columns re-quantised, and the coarse query is a
     column slice of the one shared projected query. At m=64 int8 vs
     m=384 f32 the first pass streams ~24x fewer bytes than a full scan.
  2. **shortlist rescore** — the per-query shortlists are flattened into
     one batch-shared shortlist (sorted ascending, duplicates marked -1),
     the U = B·N·k full-resolution rows are gathered in storage dtype, and
     a single small (B, m)×(m, U) matmul rescores them EXACTLY at full m
     before the final top-k. Sharing the shortlist across the batch cuts
     the gather B-fold and keeps the rescore in the same
     batch-by-contraction dot shape family as the full scan — which is
     what makes the cascade *bit-identical* to the full-m search whenever
     the shortlist covers the corpus (N·k ≥ n), the oracle-parity anchor
     the tests pin.

Both stages trace into ONE jit for a dense×dense cascade (projection +
coarse scan + shortlist + gather + rescore + select — the serving hot path
stays one dispatch per batch). A paged cascade (``CascadeIndex.paged``,
the form that takes appends) runs one shared projection, the coarse page
walk, the shortlist, the paged rescore and the select: five dispatches,
whatever the page or extent count, with page-table slot bounds as *traced*
operands, so steady-state appends never recompile.

Tie-breaks: the shortlist is sorted ascending, so ``lax.top_k``'s
first-occurrence rule reproduces the monolithic scan's lowest-doc-id
tie-break; the Pallas rescore kernel's min-id-among-ties extract gives the
same result independent of gather order.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import (
    Backend,
    DenseIndex,
    _project_nofold,
    _scan_topk,
    _topk_merge,
    project_queries,
)
from repro.core.paged import PagedIndex
from repro.kernels.topk_score import query_terms, score_rows


def _shortlist(cids: jax.Array) -> jax.Array:
    """Batch-shared shortlist from per-query coarse ids.

    Flattens (B, nk) coarse top-ids to one (B·nk,) candidate row, sorts
    ascending (so -1 pads lead and ``top_k`` ties resolve to the lowest
    doc id) and marks duplicates as -1 — each surviving slot holds a
    distinct doc id scored once for the whole batch.
    """
    flat = jnp.sort(cids.reshape(-1))
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool), flat[1:] == flat[:-1]])
    return jnp.where(dup, jnp.int32(-1), flat)


_cascade_shortlist = jax.jit(_shortlist)


@partial(jax.jit, static_argnames=("k", "nk", "block", "backend"))
def _cascade_dense_projected(Dc, scale_c, Df, scale_f, W, mean, Q, k: int,
                             nk: int, block: int | None, backend: Backend):
    """One compiled dispatch: projection + coarse scan + shortlist +
    gather + exact rescore + final top-k (dense×dense cascade).

    The U = B·nk shortlist rows gather from ``Df`` in storage dtype — the
    (U, m) conversion inside the rescore matmul/kernel IS the second
    stage's dequant unit (mirroring the scan's per-strip one).
    """
    qf = project_queries(Q, W, scale=None, mean=mean)
    mc = Dc.shape[1]
    qc = qf[:, :mc]
    if scale_c is not None:
        qc = qc * scale_c[None, :]
    if backend == "pallas":
        from repro.kernels import ops as kops
        kw = {} if block is None else {"block_n": block}
        _, cids = kops.topk_score(Dc, qc, k=nk, **kw)
        uids = _shortlist(cids)
        q = qf if scale_f is None else qf * scale_f[None, :]
        rows = jnp.take(Df, jnp.maximum(uids, 0), axis=0)
        return kops.topk_score(rows, q, k=k, row_ids=uids, **kw)
    _, cids = _scan_topk(Dc, qc, nk, block=65536 if block is None else block)
    uids = _shortlist(cids)
    q = qf if scale_f is None else qf * scale_f[None, :]
    rows = jnp.take(Df, jnp.maximum(uids, 0), axis=0)
    s = score_rows(query_terms(q, rows.dtype), rows)
    s = jnp.where(uids[None, :] >= 0, s, -jnp.inf)
    return _topk_merge(s, jnp.broadcast_to(uids[None, :], s.shape), k)


@partial(jax.jit, static_argnames=("k",))
def _cascade_select(s, uids, k: int):
    """Final top-k of the (B, U) shortlist scores."""
    return _topk_merge(s, jnp.broadcast_to(uids[None, :], s.shape), k)


def _jit_cache_sizes() -> dict:
    """Compiled-variant counts of every cascade jit, for recompile soaks
    (merge with ``repro.core.paged._jit_cache_sizes`` for a paged
    cascade)."""
    return {fn.__wrapped__.__name__: fn._cache_size()
            for fn in (_cascade_dense_projected, _cascade_shortlist,
                       _cascade_select)}


def _coarse_rows(full) -> np.ndarray:
    """Dequantised f32 leading-column source rows of an existing index."""
    v = np.asarray(full.vectors[:full.n], np.float32)
    if full.scale is not None:
        v = v * np.asarray(full.scale, np.float32)[None, :]
    return v


@dataclasses.dataclass(frozen=True)
class CascadeIndex:
    """Two-resolution cascade over one corpus: coarse scan → exact rescore.

    ``coarse`` holds the first ``m_coarse`` PCA dims (int8 by default),
    ``full`` the complete pruned representation; both views index the SAME
    rows in the same order (validated), so a shortlist id from the first
    pass addresses the rescore row directly. ``n_factor`` sets the
    shortlist depth: the coarse pass keeps N·k candidates per query.

    Mutations are copy-on-write like the underlying indexes: ``append``
    returns a new ``CascadeIndex`` with BOTH resolutions grown, so a
    serving swap installs a consistent pair atomically.
    """

    coarse: DenseIndex | PagedIndex
    full: DenseIndex | PagedIndex
    n_factor: int = 8

    def __post_init__(self):
        if self.coarse.n != self.full.n:
            raise ValueError(
                f"cascade resolutions disagree on row count: coarse has "
                f"{self.coarse.n} rows, full has {self.full.n}")
        if not 0 < self.coarse.dim < self.full.dim:
            raise ValueError(
                f"coarse m={self.coarse.dim} does not nest inside full "
                f"m={self.full.dim} (need 0 < m_coarse < m)")
        if self.n_factor < 1:
            raise ValueError(f"n_factor must be >= 1, got {self.n_factor}")

    # -- construction -------------------------------------------------------
    @classmethod
    def build(cls, pruned, *, m_coarse: int, n_factor: int = 8,
              quantize_int8: bool = False, coarse_int8: bool = True,
              backend: Backend = "jnp") -> "CascadeIndex":
        """Build both resolutions from one (n, m) pruned f32 matrix.

        The coarse index is the leading ``m_coarse`` columns — PCA dims
        nest, so no second projection exists — re-quantised with its OWN
        per-dim scale (``coarse_int8``); ``quantize_int8`` controls the
        full-resolution storage as usual.
        """
        v = jnp.asarray(pruned)
        full = DenseIndex.build(v, quantize_int8=quantize_int8,
                                backend=backend)
        coarse = DenseIndex.build(v[:, :m_coarse],
                                  quantize_int8=coarse_int8, backend=backend)
        return cls(coarse=coarse, full=full, n_factor=n_factor)

    @classmethod
    def from_index(cls, full: DenseIndex, *, m_coarse: int,
                   n_factor: int = 8, coarse_int8: bool = True
                   ) -> "CascadeIndex":
        """Derive the coarse resolution from an existing full index (via
        its dequantised rows — exact for f32 storage, reconstruction for
        int8)."""
        coarse = DenseIndex.build(
            jnp.asarray(_coarse_rows(full)[:, :m_coarse]),
            quantize_int8=coarse_int8, backend=full.backend)
        return cls(coarse=coarse, full=full, n_factor=n_factor)

    @classmethod
    def load(cls, store, *, m_coarse: int | None = None, n_factor: int = 8,
             backend: Backend = "jnp", page_rows: int | None = None,
             pool_pages: int | None = None) -> "CascadeIndex":
        """Load a multi-resolution artifact: the main segments become the
        full resolution, the ``m_coarse`` resolution entry the coarse one
        (``m_coarse=None`` picks the widest stored resolution).

        A store that has grown (a ``paged`` block or delta segments) loads
        both resolutions paged (``PagedIndex.load`` for the full side):
        the coarse extents adopt the resolution's PERSISTED delta segments
        when the store carries them (``save_index`` on a paged cascade
        writes the exact quantised bytes + per-extent scales, so the
        reload is bit-identical to what was serving). A store without
        them — or one whose main segments have grown past the persisted
        coarse view — re-derives (requantises) the coarse deltas from the
        full deltas' dequantised rows, so the pair stays row-aligned
        however far the store has grown. A flat store loads dense; call
        ``paged()`` on it to take appends.
        """
        from repro.core.store import IndexStore, IndexStoreError
        if isinstance(store, (str, os.PathLike)):
            store = IndexStore.open(store)
        views = store.resolutions()
        if not views:
            raise IndexStoreError(
                f"{store.path}: no coarse resolutions in manifest — write "
                f"one with IndexStore.add_resolution before loading a "
                f"cascade")
        if m_coarse is None:
            view = max(views, key=lambda v: v.dim)
        else:
            by_m = {v.dim: v for v in views}
            if m_coarse not in by_m:
                raise IndexStoreError(
                    f"{store.path}: no m={m_coarse} resolution (stored: "
                    f"{sorted(by_m)})")
            view = by_m[m_coarse]
        if "paged" not in store.manifest and len(store.segments()) == 1:
            return cls(coarse=DenseIndex.load(view, backend=backend),
                       full=DenseIndex.load(store, backend=backend),
                       n_factor=n_factor)
        full = PagedIndex.load(store, page_rows=page_rows,
                               pool_pages=pool_pages, backend=backend)
        fst = full.storage
        coarse = PagedIndex.from_index(
            DenseIndex.load(view, backend=backend), page_rows=fst.page_rows,
            tail_pages=fst.tail_pages, seal_rows=fst.seal_rows)
        dviews = store.resolution_deltas(view.name)
        if dviews and ([v.n for v in dviews]
                       == [e.n_rows for e in fst.extents[1:]]):
            # persisted coarse deltas mirror the main ones row-for-row:
            # adopt the exact quantised bytes (no requantisation), with
            # each main extent's lifecycle state
            st = coarse.storage
            for v in dviews:
                vs = v.scale()
                st = st._adopt_extent(
                    v.read_rows(0, v.n),
                    None if vs is None else np.asarray(vs, np.float32))
            coarse = dataclasses.replace(coarse, storage=st.with_lifecycle(
                [(e.kind, e.sealed) for e in fst.extents]))
        else:
            # an artifact without persisted coarse deltas (or the main store
            # grew past the persisted view): re-derive them from the full
            # extents' dequantised rows
            for i in range(1, len(fst.extents)):
                rows = fst.extent_f32(i)
                if rows.shape[0]:
                    coarse = coarse.append(rows[:, :coarse.dim])
        return cls(coarse=coarse, full=full, n_factor=n_factor)

    def paged(self, *, page_rows: int = 256, pool_pages: int | None = None,
              coarse_pool_pages: int | None = None, seal_rows: int = 4096,
              depth: int = 2, wave_pages: int = 8) -> "CascadeIndex":
        """Re-home both dense resolutions on paged storage (byte-for-byte):
        the coarse scan and the exact rescore then both stream through the
        page tables — appends, promotion, compaction and eviction become
        pointer swaps on BOTH sides of the cascade, and either side may
        oversubscribe device memory independently (``pool_pages`` /
        ``coarse_pool_pages``)."""
        def conv(ix, pool):
            return PagedIndex.from_index(
                ix, page_rows=page_rows, pool_pages=pool,
                seal_rows=seal_rows, depth=depth, wave_pages=wave_pages)

        return dataclasses.replace(self, full=conv(self.full, pool_pages),
                                   coarse=conv(self.coarse,
                                               coarse_pool_pages))

    # -- shape --------------------------------------------------------------
    @property
    def n(self) -> int:
        return self.full.n

    @property
    def dim(self) -> int:
        """Search dim = the FULL resolution's width (the projection's m)."""
        return self.full.dim

    @property
    def m_coarse(self) -> int:
        return self.coarse.dim

    @property
    def nbytes(self) -> int:
        return self.coarse.nbytes + self.full.nbytes

    # -- growth (copy-on-write) --------------------------------------------
    def append(self, rows) -> "CascadeIndex":
        """Append pruned f32 rows (full m) to BOTH resolutions — the coarse
        side takes the leading columns. Requires paged resolutions."""
        if not isinstance(self.full, PagedIndex):
            raise TypeError("append needs paged resolutions — wrap with "
                            "CascadeIndex.paged() first")
        rows = np.atleast_2d(np.asarray(rows, np.float32))
        return dataclasses.replace(
            self, full=self.full.append(rows),
            coarse=self.coarse.append(rows[:, :self.m_coarse]))

    # -- search -------------------------------------------------------------
    def search_projected(self, queries: jax.Array, components: jax.Array,
                         k: int = 10, *, mean: jax.Array | None = None,
                         block: int | None = None
                         ) -> tuple[jax.Array, jax.Array]:
        """Cascaded raw-query search. Same signature and same contract as
        the single-resolution ``search_projected``: with N·k >= n the
        result is bit-identical to the full-m exact search."""
        k = min(k, max(self.n, 1))
        nk = min(self.n_factor * k, max(self.n, 1))
        Q = jnp.atleast_2d(queries)
        W = jnp.asarray(components)
        if isinstance(self.full, PagedIndex):
            return self._paged_search(Q, W, mean, k, nk)
        return _cascade_dense_projected(
            self.coarse.vectors, self.coarse.scale, self.full.vectors,
            self.full.scale, W, mean, Q, k, nk, block, self.full.backend)

    def _paged_search(self, Q, W, mean, k: int, nk: int):
        """Paged cascade: shared projection, the coarse page walk, then an
        exact rescore of the shared shortlist by the full side's page
        walk (``PagedIndex.rescore``). Page-table slot bounds are traced
        operands throughout — zero recompiles as the index grows."""
        qf = _project_nofold(Q, W, mean)
        _, cids = self.coarse._search_qf(qf[:, :self.m_coarse], nk)
        uids = _cascade_shortlist(cids)
        return _cascade_select(self.full.rescore(qf, uids), uids, k)
