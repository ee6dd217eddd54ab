"""Paged index memory: the mutable index, in fixed-size pages behind an
int32 indirection table.

``PagedIndexStorage`` stores the rows as fixed ``page_rows``-row pages
addressed through a page table, in three tiers:

  * ``pool``  — the stable device tier (promoted/compacted pages). Written
                only by the one-dispatch compaction drain; searches stream
                straight out of it.
  * ``tail``  — a small device write arena absorbing live appends. O(tail)
                copy-on-write per append — never O(index).
  * host tier — pages whose table entry is -1 live as host ``np`` arrays
                and stream on demand in bounded waves, so the index may
                exceed device memory (oversubscription).

Logical slots are contiguous per extent (base extents first, then deltas,
ascending global-id order). Each extent carries its own int8 scale: an
extent opens with a fresh per-dim scale fitted to its first rows, and an
append that would clip widens it (``max(old, need)``) and requantises the
extent from its exact f32 staging — bounded by ``seal_rows``, so nothing
ever clips. Every lifecycle step is a page-pointer swap:

  append   — write rows into tail pages, grow the open delta extent;
  seal     — the open extent freezes at ``seal_rows`` rows (metadata);
  promote  — sealed delta extents become base extents (metadata only);
  compact  — promoted tail pages drain into free pool slots in ONE fused
             gather dispatch (``_pool_drain``) + a pointer swap — no
             requantisation, no index rebuild (``IndexUpdater.refit``
             requantises);
  evict    — device pages move to the host tier (pointer swap + host copy).

Search walks slots ``[lo, hi)`` with *traced* bounds over fixed-shape
arrays, so appends/seals/promotions/compactions/evictions never recompile.
An oversubscribed index splits into device/host runs chained through a
top-k carry; visit order stays ascending-slot, so ties resolve to the
lowest global id and the skip-on-equality guard stays exact. Backends:
'jnp' (``lax.scan`` page walk) or 'pallas' (``topk_score_paged_pallas`` —
double-buffered ``make_async_copy`` DMA pipeline prefetching page
i+depth-1 while scoring page i). Both fold each page's extent scale into
the query and score it with ``score_rows``, so they agree bit for bit, and
promotion, compaction, eviction and a store round trip never move a
result (pinned by tests/test_paged.py).
"""
from __future__ import annotations

import dataclasses
from collections import deque
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import (Backend, DenseIndex, _project_nofold,
                              _topk_merge, project_queries)
from repro.kernels.topk_score import query_terms, score_rows


class PageExtent(NamedTuple):
    """One logical row range: ``n_rows`` rows in ``n_pages`` contiguous
    slots starting at ``start_slot``; global ids ``[row_offset,
    row_offset + n_rows)``. ``scale`` is the extent's int8 dequant scale
    (replicated into the per-page scale rows); ``raw`` is the exact f32
    staging kept only while a delta extent is open (the requant source
    when an append widens the scale; after a load, the dequantised
    reconstruction — the best source that survives a restart).
    """

    kind: str                    # "base" | "delta"
    sealed: bool
    start_slot: int
    n_pages: int
    n_rows: int
    row_offset: int
    scale: np.ndarray | None
    raw: np.ndarray | None


@jax.jit
def _pool_drain(pool, tail, sel):
    """One fused compaction dispatch: pool slot p takes tail page
    ``sel[p]`` when ``sel[p] >= 0``, else keeps its page. A gather + a
    select — one O(pool) pass, deterministic, no scatter aliasing."""
    take = jnp.clip(sel, 0, tail.shape[0] - 1)
    return jnp.where((sel >= 0)[:, None, None], tail[take], pool)


def _paged_core(pool, tail, pt, scale, nv, off, lo, hi, Qf, k: int,
                guard: str, carry, finalize: bool):
    """Traced jnp page walk: running top-k over slots [lo, hi).

    Mirrors ``_scan_topk``'s merge structure (strip ``top_k``, running
    list first in the concat, per-row guard with masked merge) page by
    page, and the Pallas kernel's pad semantics (unique negative init ids,
    clamped to -1 at ``finalize``) so device/host runs chain through the
    carry bitwise-consistently on both backends.
    """
    pool_pages, R, m = pool.shape
    table_cap = pt.shape[0]
    B = Qf.shape[0]
    kk = min(k, R)
    if carry is None:
        bs = jnp.full((B, k), -jnp.inf, jnp.float32)
        bi = -(jnp.broadcast_to(
            jnp.arange(k, dtype=jnp.int32)[None, :], (B, k)) + 2)
    else:
        bs = carry[0].astype(jnp.float32)
        bi = carry[1].astype(jnp.int32)

    def body(c, t):
        bs, bi = c
        live = (t >= lo) & (t < hi)
        phys = pt[t]
        pg = pool[jnp.clip(phys, 0, pool_pages - 1)]
        if tail is not None:
            pgt = tail[jnp.clip(phys - pool_pages, 0, tail.shape[0] - 1)]
            pg = jnp.where(phys >= pool_pages, pgt, pg)
        q = Qf if scale is None else Qf * scale[t]
        s = score_rows(query_terms(q, pg.dtype), pg)             # (B, R)
        iota = jnp.arange(R, dtype=jnp.int32)[None, :]
        gids = jnp.broadcast_to(off[t] + iota, s.shape)
        s = jnp.where((iota < nv[t]) & live, s, -jnp.inf)
        imp = jnp.max(s, axis=1) > jnp.min(bs, axis=1)           # (B,)

        def merge(cin):
            bs0, bi0 = cin
            ss, si = jax.lax.top_k(s, kk)
            gi = jnp.take_along_axis(gids, si, axis=1)
            cs = jnp.concatenate([bs0, ss], axis=1)
            ci = jnp.concatenate([bi0, gi], axis=1)
            ms, mi = _topk_merge(cs, ci, k)
            if guard == "row":
                ms = jnp.where(imp[:, None], ms, bs0)
                mi = jnp.where(imp[:, None], mi, bi0)
            return ms, mi

        if guard == "row":
            can = jnp.any(imp)
        else:
            can = jnp.max(s) > jnp.min(bs)
        return jax.lax.cond(can, merge, lambda x: x, (bs, bi)), None

    (bs, bi), _ = jax.lax.scan(body, (bs, bi),
                               jnp.arange(table_cap, dtype=jnp.int32))
    if finalize:
        bi = jnp.maximum(bi, -1)
    return bs, bi


def _dispatch_topk(pool, tail, pt, scale, nv, off, lo, hi, q, k, backend,
                   depth, guard, carry, finalize):
    if backend == "pallas":
        from repro.kernels import ops as kops
        return kops.topk_score_paged(pool, pt, nv, off, lo, hi, q, k=k,
                                     tail=tail, page_scale=scale, carry=carry,
                                     depth=depth, guard=guard,
                                     finalize=finalize)
    return _paged_core(pool, tail, pt, scale, nv, off, lo, hi, q, k,
                       guard, carry, finalize)


@partial(jax.jit, static_argnames=("k", "backend", "depth", "guard",
                                   "finalize"))
def _paged_topk(pool, tail, pt, scale, nv, off, lo, hi, Q, *, k: int,
                backend: Backend, depth: int, guard: str = "row",
                carry=None, finalize: bool = True):
    """One compiled paged-search dispatch over slots [lo, hi) (traced) —
    pre-projected queries; every lifecycle mutation reuses this shape."""
    q = jnp.atleast_2d(Q).astype(jnp.float32)
    return _dispatch_topk(pool, tail, pt, scale, nv, off, lo, hi, q, k,
                          backend, depth, guard, carry, finalize)


@partial(jax.jit, static_argnames=("k", "backend", "depth", "guard"))
def _paged_search_projected(pool, tail, pt, scale, nv, off, lo, hi, W, mean,
                            Q, *, k: int, backend: Backend, depth: int,
                            guard: str = "row"):
    """The serving hot path: projection + page walk in ONE dispatch (the
    paged analogue of ``_dense_search_projected``). No scale fold at
    projection — each page's extent scale folds inside the walk."""
    q = project_queries(jnp.atleast_2d(Q), W, scale=None, mean=mean)
    return _dispatch_topk(pool, tail, pt, scale, nv, off, lo, hi, q, k,
                          backend, depth, guard, None, True)


@jax.jit
def _paged_rescore(pool, tail, pt, scale, nv, off, lo, hi, qf, uids, acc):
    """Cascade rescore over pages: max-combine each page's contribution to
    the shared shortlist into ``acc`` (B, U).

    Per page: fold the extent scale into q, gather shortlist rows in
    storage dtype, one (B,m)×(m,U) matmul, -inf outside the page. Each
    live uid falls in exactly one page, so the elementwise max is that
    uid's one exact score.
    """
    pool_pages, R, m = pool.shape
    table_cap = pt.shape[0]

    def body(acc, t):
        live = (t >= lo) & (t < hi)
        phys = pt[t]
        pg = pool[jnp.clip(phys, 0, pool_pages - 1)]
        if tail is not None:
            pgt = tail[jnp.clip(phys - pool_pages, 0, tail.shape[0] - 1)]
            pg = jnp.where(phys >= pool_pages, pgt, pg)
        q = qf if scale is None else qf * scale[t]
        local = uids - off[t]
        valid = (uids >= 0) & (local >= 0) & (local < nv[t]) & live
        rows = jnp.take(pg, jnp.clip(local, 0, R - 1), axis=0)   # (U, m)
        s = score_rows(query_terms(q, rows.dtype), rows)         # (B, U)
        return jnp.maximum(acc, jnp.where(valid[None, :], s, -jnp.inf)), None

    acc, _ = jax.lax.scan(body, acc, jnp.arange(table_cap, dtype=jnp.int32))
    # re-assert the shortlist sentinel OUTSIDE the scan: every dead uid is
    # already -inf from each page's in-scan mask, but the carry hides that
    # from the invariant interpreter — the contract (-1 slots never compete
    # in the final top-k) must be provable at the jaxpr top level
    return jnp.where(uids[None, :] >= 0, acc, -jnp.inf)


def _jit_cache_sizes() -> dict:
    """Compiled-variant counts of every jit a paged search or lifecycle
    step can touch — the recompile soaks pin these to zero growth (a
    failure names the function that recompiled)."""
    from repro.kernels.topk_score import topk_score_paged_pallas
    sizes = {fn.__wrapped__.__name__: fn._cache_size()
             for fn in (_paged_topk, _paged_search_projected, _paged_rescore,
                        _pool_drain, _project_nofold)}
    sizes["topk_score_paged_pallas"] = topk_score_paged_pallas._cache_size()
    return sizes


def _device_scale(sc: np.ndarray | None) -> jax.Array | None:
    """Per-page scale rows on device as (table_cap, 1, m): the paged
    kernel DMAs one page's row over the untiled leading axis."""
    return None if sc is None else jnp.asarray(sc[:, None, :])


class _Mut:
    """Scratch copy-on-write view of a storage's host-side state: every
    mutation edits a private copy, then ``freeze`` pushes the metadata to
    device in one ``asarray`` batch (fixed shapes — no recompiles)."""

    def __init__(self, st: "PagedIndexStorage"):
        self.st = st
        self.pt = st.pt_host.copy()
        self.nv = st.nvalid_host.copy()
        self.off = st.offset_host.copy()
        self.sc = None if st.scale_host is None else st.scale_host.copy()
        self.tail_host = st.tail_host
        self._tail_copied = False
        self.host_pages = dict(st.host_pages)
        self.extents = list(st.extents)
        self.free_pool = list(st.free_pool)
        self.free_tail = list(st.free_tail)
        self.table_grows = st.table_grows
        self.pool = st.pool

    @property
    def R(self) -> int:
        return self.st.page_rows

    def _tail(self) -> np.ndarray:
        if not self._tail_copied:
            self.tail_host = self.tail_host.copy()
            self._tail_copied = True
        return self.tail_host

    def ensure_slots(self, n_needed: int) -> None:
        cap = self.pt.shape[0]
        if n_needed <= cap:
            return
        new_cap = cap
        while new_cap < n_needed:
            new_cap *= 2
        grow = new_cap - cap
        self.pt = np.concatenate([self.pt, np.full(grow, -1, np.int32)])
        self.nv = np.concatenate([self.nv, np.zeros(grow, np.int32)])
        self.off = np.concatenate([self.off, np.zeros(grow, np.int32)])
        if self.sc is not None:
            self.sc = np.concatenate(
                [self.sc, np.zeros((grow, self.sc.shape[1]), np.float32)])
        self.table_grows += 1          # shape change: a counted recompile

    def alloc_page(self, slot: int, offset: int) -> None:
        """Back a fresh logical slot: tail tier while arena slots remain,
        host tier once the arena is full (append never fails)."""
        self.ensure_slots(slot + 1)
        if self.free_tail:
            local = self.free_tail.pop(0)
            self.pt[slot] = self.st.pool_pages + local
        else:
            self.pt[slot] = -1
            self.host_pages[slot] = np.zeros(
                (self.R, self.st.dim), self.st.np_dtype)
        self.nv[slot] = 0
        self.off[slot] = offset

    def write_rows(self, slot: int, row0: int, rows: np.ndarray) -> None:
        """Write ``rows`` (storage dtype) into a page at in-page ``row0``."""
        phys = int(self.pt[slot])
        if phys >= 0:
            local = phys - self.st.pool_pages
            if local < 0:
                raise AssertionError("writes only target tail/host pages")
            t = self._tail()
            t[local, row0:row0 + rows.shape[0]] = rows
        else:
            page = self.host_pages[slot].copy()   # COW: readers keep theirs
            page[row0:row0 + rows.shape[0]] = rows
            self.host_pages[slot] = page
        self.nv[slot] = max(int(self.nv[slot]), row0 + rows.shape[0])

    def set_scale(self, slot: int, scale: np.ndarray) -> None:
        if self.sc is not None:
            self.sc[slot] = scale

    def freeze(self, pool=None) -> "PagedIndexStorage":
        tail_dev = (jnp.asarray(self.tail_host) if self._tail_copied
                    else self.st.tail)
        return dataclasses.replace(
            self.st,
            pool=self.st.pool if pool is None else pool,
            tail=tail_dev,
            page_table=jnp.asarray(self.pt),
            page_scale=_device_scale(self.sc),
            page_nvalid=jnp.asarray(self.nv),
            page_offset=jnp.asarray(self.off),
            pt_host=self.pt, nvalid_host=self.nv, offset_host=self.off,
            scale_host=self.sc, tail_host=self.tail_host,
            host_pages=self.host_pages, extents=tuple(self.extents),
            free_pool=tuple(self.free_pool), free_tail=tuple(self.free_tail),
            table_grows=self.table_grows)


@dataclasses.dataclass(frozen=True, eq=False)
class PagedIndexStorage:
    """Two device page tiers + a host tier behind one indirection table.

    Immutable: every mutation returns a NEW storage sharing untouched
    arrays (the ``RetrievalServer`` swap discipline — in-flight searches
    keep the old table/pools alive until their replies post). The host
    ``*_host`` mirrors are authoritative; the device copies are re-pushed
    whole per mutation (fixed shapes, tiny for metadata, O(tail) for the
    write arena — the same cost class as a delta's update slice).
    """

    pool: jax.Array                    # (pool_pages, R, m) stable tier
    tail: jax.Array                    # (tail_pages, R, m) write arena
    page_table: jax.Array              # (table_cap,) int32; -1 = host tier
    page_scale: jax.Array | None       # (table_cap, 1, m) f32 (int8 pools)
    page_nvalid: jax.Array             # (table_cap,) int32 live rows/page
    page_offset: jax.Array             # (table_cap,) int32 first global id
    pt_host: np.ndarray
    nvalid_host: np.ndarray
    offset_host: np.ndarray
    scale_host: np.ndarray | None
    tail_host: np.ndarray              # host staging of the write arena
    host_pages: dict                   # slot -> (R, m) np page (host tier)
    extents: tuple
    free_pool: tuple
    free_tail: tuple
    page_rows: int
    seal_rows: int
    table_grows: int = 0

    # -- shape ---------------------------------------------------------------
    @property
    def pool_pages(self) -> int:
        return self.pool.shape[0]

    @property
    def tail_pages(self) -> int:
        return self.tail.shape[0]

    @property
    def table_cap(self) -> int:
        return self.pt_host.shape[0]

    @property
    def dim(self) -> int:
        return self.pool.shape[2]

    @property
    def np_dtype(self):
        return np.dtype(self.pool.dtype)

    @property
    def quantized(self) -> bool:
        return self.scale_host is not None

    @property
    def n_slots(self) -> int:
        return sum(e.n_pages for e in self.extents)

    @property
    def n_rows(self) -> int:
        return sum(e.n_rows for e in self.extents)

    @property
    def delta_pages(self) -> int:
        return sum(e.n_pages for e in self.extents if e.kind == "delta")

    @property
    def delta_rows(self) -> int:
        return sum(e.n_rows for e in self.extents if e.kind == "delta")

    @property
    def n_host_pages(self) -> int:
        return len(self.host_pages)

    @property
    def nbytes(self) -> int:
        b = self.pool.size * self.pool.dtype.itemsize
        b += self.tail.size * self.tail.dtype.itemsize
        b += self.page_table.size * 4 + self.page_nvalid.size * 4
        b += self.page_offset.size * 4
        if self.page_scale is not None:
            b += self.page_scale.size * 4
        return b

    # -- construction --------------------------------------------------------
    @classmethod
    def from_index(cls, base: DenseIndex, **kw) -> "PagedIndexStorage":
        """Page an immutable base index (see ``from_rows``)."""
        return cls.from_rows(np.asarray(base.vectors),
                             None if base.scale is None
                             else np.asarray(base.scale, np.float32), **kw)

    @classmethod
    def from_rows(cls, vec: np.ndarray, scale: np.ndarray | None, *,
                  page_rows: int = 256, pool_pages: int | None = None,
                  tail_pages: int | None = None,
                  table_cap: int | None = None,
                  seal_rows: int = 4096) -> "PagedIndexStorage":
        """Page stored rows (storage dtype, ``scale`` their int8 dequant
        scale or None) as one base extent. ``pool_pages`` below the rows'
        page count oversubscribes at construction: the overflow suffix
        lives on the host tier and streams at search time."""
        R = page_rows
        n, m = vec.shape
        npages = -(-n // R) if n else 0
        if tail_pages is None:
            tail_pages = max(2 * (-(-seal_rows // R)), 2)
        if pool_pages is None:
            pool_pages = npages + max(tail_pages, 8)
        pool_pages = max(pool_pages, 1)
        if table_cap is None:
            table_cap = max(2 * (npages + tail_pages) + 8, 16)
        table_cap = max(table_cap, npages + 1)

        pt = np.full(table_cap, -1, np.int32)
        nv = np.zeros(table_cap, np.int32)
        off = np.zeros(table_cap, np.int32)
        sc = (np.zeros((table_cap, m), np.float32)
              if scale is not None else None)
        pool_np = np.zeros((pool_pages, R, m), vec.dtype)
        host_pages: dict = {}
        for j in range(npages):
            rows = vec[j * R:(j + 1) * R]
            nv[j] = rows.shape[0]
            off[j] = j * R
            if sc is not None:
                sc[j] = scale
            if j < pool_pages:
                pool_np[j, :rows.shape[0]] = rows
                pt[j] = j
            else:
                page = np.zeros((R, m), vec.dtype)
                page[:rows.shape[0]] = rows
                host_pages[j] = page
        extents = ((PageExtent("base", True, 0, npages, n, 0, scale, None),)
                   if n else ())
        return cls(
            pool=jnp.asarray(pool_np), tail=jnp.asarray(
                np.zeros((tail_pages, R, m), vec.dtype)),
            page_table=jnp.asarray(pt),
            page_scale=_device_scale(sc),
            page_nvalid=jnp.asarray(nv), page_offset=jnp.asarray(off),
            pt_host=pt, nvalid_host=nv, offset_host=off, scale_host=sc,
            tail_host=np.zeros((tail_pages, R, m), vec.dtype),
            host_pages=host_pages, extents=extents,
            free_pool=tuple(range(min(npages, pool_pages), pool_pages)),
            free_tail=tuple(range(tail_pages)), page_rows=R,
            seal_rows=seal_rows)

    def _adopt_extent(self, stored: np.ndarray, scale: np.ndarray | None
                      ) -> "PagedIndexStorage":
        """Append a whole pre-quantised extent (a stored segment, on load)
        into the writable tiers, with its bytes and scale as they are, as
        a sealed delta (``with_lifecycle`` sets what the store recorded)."""
        mut = _Mut(self)
        R = self.page_rows
        start_slot = self.n_slots
        row_offset = self.n_rows
        n = stored.shape[0]
        npages = -(-n // R) if n else 0
        for pi in range(npages):
            slot = start_slot + pi
            mut.alloc_page(slot, row_offset + pi * R)
            mut.write_rows(slot, 0, stored[pi * R:(pi + 1) * R])
            if scale is not None:
                mut.set_scale(slot, scale)
        mut.extents.append(PageExtent("delta", True, start_slot, npages,
                                      n, row_offset, scale, None))
        return mut.freeze()

    def with_lifecycle(self, states) -> "PagedIndexStorage":
        """Set each extent's ``(kind, sealed)``; an unsealed extent gets its
        dequantised bytes as requant staging ``raw`` — the best source
        that survives a restart."""
        exts = tuple(
            e._replace(kind=kind, sealed=sealed,
                       raw=None if sealed else self.extent_f32(i))
            for i, (e, (kind, sealed)) in enumerate(zip(self.extents,
                                                        states)))
        return dataclasses.replace(self, extents=exts)

    def extent_f32(self, ei: int) -> np.ndarray:
        """One extent's rows dequantised to f32 (its bytes times its
        scale)."""
        rows = self.extent_rows(ei).astype(np.float32)
        sc = self.extents[ei].scale
        return rows if sc is None else rows * sc[None, :].astype(np.float32)

    def extent_rows(self, ei: int) -> np.ndarray:
        """One extent's stored bytes in global-id order, gathered off
        whatever tier each page lives on (pool/tail/host) — the
        persistence source (``save_paged_index``) and the requant-staging
        rehydration source on load."""
        e = self.extents[ei]
        R = self.page_rows
        out = np.empty((e.n_rows, self.dim), self.np_dtype)
        pool_np = None
        for pi in range(e.n_pages):
            slot = e.start_slot + pi
            phys = int(self.pt_host[slot])
            if phys < 0:
                page = self.host_pages[slot]
            elif phys >= self.pool_pages:
                page = self.tail_host[phys - self.pool_pages]
            else:
                if pool_np is None:       # one device pull, not per page
                    pool_np = np.asarray(self.pool)
                page = pool_np[phys]
            lo = pi * R
            take = min(R, e.n_rows - lo)
            out[lo:lo + take] = page[:take]
        return out

    # -- growth (copy-on-write) ---------------------------------------------
    def append_with_ops(self, rows) -> tuple["PagedIndexStorage", list]:
        """Append f32 rows; page-pointer swaps only — no array rebuilds.

        Rows land in the open delta extent (tail-tier pages; host-tier
        once the arena is full) which seals at ``seal_rows``. Scales: a
        fresh per-dim scale per extent, widen = ``max(old, need)`` +
        requantise the extent from its exact f32 staging. Returns the op
        stream a durable mirror replays (``IndexUpdater._mirror_ops``), in
        order:
          ("open",   di, stored_rows, scale)  — new delta with first rows
          ("extend", di, stored_rows)         — rows appended, scale kept
          ("widen",  di, stored_all,  scale)  — scale widened: the delta's
                                                full requantised contents
        ``di`` is the delta's ordinal among delta extents; ``stored_*``
        are in storage dtype, exactly the bytes served — disk and memory
        stay bit-identical.
        """
        from repro.core.quantization import quantize_with_scale, scale_for
        rows = np.atleast_2d(np.asarray(rows, np.float32))
        if rows.shape[1] != self.dim:
            raise ValueError(f"append expects (rows, {self.dim}), got "
                             f"{tuple(rows.shape)}")
        st = self
        ops: list = []
        pos = 0
        while pos < rows.shape[0]:
            mut = _Mut(st)
            open_ei = None
            if (mut.extents and mut.extents[-1].kind == "delta"
                    and not mut.extents[-1].sealed):
                open_ei = len(mut.extents) - 1
            ordinal = sum(1 for e in mut.extents if e.kind == "delta")
            if open_ei is not None:
                ext = mut.extents[open_ei]
                ordinal -= 1                      # this delta's own ordinal
                take = min(rows.shape[0] - pos, st.seal_rows - ext.n_rows)
                block = rows[pos:pos + take]
                raw = np.concatenate([ext.raw, block])
                if ext.scale is not None:
                    need = scale_for(block)
                    scale = np.maximum(ext.scale, need).astype(np.float32)
                    if bool((scale > ext.scale).any()):
                        stored_all = quantize_with_scale(raw, scale)
                        st = st._widen_extent(mut, open_ei, raw, stored_all,
                                              scale)
                        ops.append(("widen", ordinal, stored_all, scale))
                        pos += take
                        continue
                    stored = quantize_with_scale(block, ext.scale)
                else:
                    stored = block.astype(st.np_dtype)
                st = st._extend_extent(mut, open_ei, raw, stored)
                ops.append(("extend", ordinal, stored))
            else:
                take = min(rows.shape[0] - pos, st.seal_rows)
                block = rows[pos:pos + take]
                if st.quantized:
                    scale = scale_for(block)
                    stored = quantize_with_scale(block, scale)
                else:
                    scale = None
                    stored = block.astype(st.np_dtype)
                st = st._open_extent(mut, stored, scale, block)
                ops.append(("open", ordinal, stored, scale))
            pos += take
        return st, ops

    def append(self, rows) -> "PagedIndexStorage":
        return self.append_with_ops(rows)[0]

    def _open_extent(self, mut: "_Mut", stored: np.ndarray,
                     scale: np.ndarray | None,
                     raw: np.ndarray) -> "PagedIndexStorage":
        R = self.page_rows
        start_slot = self.n_slots
        row_offset = self.n_rows
        n = stored.shape[0]
        npages = -(-n // R)
        for pi in range(npages):
            slot = start_slot + pi
            mut.alloc_page(slot, row_offset + pi * R)
            mut.write_rows(slot, 0, stored[pi * R:(pi + 1) * R])
            if scale is not None:
                mut.set_scale(slot, scale)
        sealed = n >= self.seal_rows
        mut.extents.append(PageExtent(
            "delta", sealed, start_slot, npages, n, row_offset, scale,
            None if sealed else np.ascontiguousarray(raw)))
        return mut.freeze()

    def _extend_extent(self, mut: "_Mut", ei: int, raw: np.ndarray,
                       stored: np.ndarray) -> "PagedIndexStorage":
        R = self.page_rows
        ext = mut.extents[ei]
        r = ext.n_rows                     # extent-local first new row
        pos = 0
        n_pages = ext.n_pages
        while pos < stored.shape[0]:
            pi = r // R
            slot = ext.start_slot + pi
            if pi >= n_pages:              # grow the (last) open extent
                mut.alloc_page(slot, ext.row_offset + pi * R)
                if ext.scale is not None:
                    mut.set_scale(slot, ext.scale)
                n_pages = pi + 1
            in_page = r - pi * R
            chunk = min(stored.shape[0] - pos, R - in_page)
            mut.write_rows(slot, in_page, stored[pos:pos + chunk])
            pos += chunk
            r += chunk
        n = ext.n_rows + stored.shape[0]
        sealed = n >= self.seal_rows
        mut.extents[ei] = ext._replace(
            n_pages=n_pages, n_rows=n, sealed=sealed,
            raw=None if sealed else raw)
        return mut.freeze()

    def _widen_extent(self, mut: "_Mut", ei: int, raw: np.ndarray,
                      stored_all: np.ndarray,
                      scale: np.ndarray) -> "PagedIndexStorage":
        """Scale widened: requantise the whole extent from exact f32
        staging and rewrite its pages in place — bounded by ``seal_rows``
        (the tractability argument for per-extent scales)."""
        R = self.page_rows
        ext = mut.extents[ei]
        n = stored_all.shape[0]
        npages = -(-n // R)
        for pi in range(npages):
            slot = ext.start_slot + pi
            if pi >= ext.n_pages:
                mut.alloc_page(slot, ext.row_offset + pi * R)
            mut.write_rows(slot, 0, stored_all[pi * R:(pi + 1) * R])
            mut.set_scale(slot, scale)
        sealed = n >= self.seal_rows
        mut.extents[ei] = ext._replace(
            n_pages=npages, n_rows=n, sealed=sealed, scale=scale,
            raw=None if sealed else raw)
        return mut.freeze()

    # -- lifecycle: pointer swaps -------------------------------------------
    def promote(self) -> tuple["PagedIndexStorage", int]:
        """Sealed delta extents become base extents — metadata only, zero
        page bytes move. Returns (new storage, extents promoted)."""
        promoted = 0
        extents = []
        for e in self.extents:
            if e.kind == "delta" and e.sealed:
                extents.append(e._replace(kind="base", scale=e.scale))
                promoted += 1
            else:
                extents.append(e)
        if not promoted:
            return self, 0
        return dataclasses.replace(self, extents=tuple(extents)), promoted

    def compact(self) -> tuple["PagedIndexStorage", dict]:
        """Seal + promote every delta extent, then drain its tail-tier
        pages into free pool slots with ONE fused gather dispatch — the
        pointer-swap compaction. No requantisation, no rebuild; telemetry
        counts pages, not rows (stale-signal fix for the fleet's
        auto-compaction controller)."""
        mut = _Mut(self)
        for ei, e in enumerate(mut.extents):
            if e.kind == "delta":
                mut.extents[ei] = e._replace(kind="base", sealed=True,
                                             raw=None)
        sel = np.full(self.pool_pages, -1, np.int32)
        moved = 0
        for e in mut.extents:
            for pi in range(e.n_pages):
                slot = e.start_slot + pi
                phys = int(mut.pt[slot])
                if phys >= self.pool_pages and mut.free_pool:
                    dst = mut.free_pool.pop(0)
                    sel[dst] = phys - self.pool_pages
                    mut.pt[slot] = dst
                    mut.free_tail.append(phys - self.pool_pages)
                    moved += 1
        pool = _pool_drain(self.pool, self.tail, jnp.asarray(sel)) \
            if moved else None
        stats = {"pages_moved": moved, "pages_freed": moved,
                 "pages_host": len(mut.host_pages)}
        return mut.freeze(pool=pool), stats

    def evict(self, n_pages: int) -> tuple["PagedIndexStorage", int]:
        """Move the highest-slot pool-tier pages to the host tier (pointer
        swap + one host copy per page). Suffix-of-the-pool policy keeps
        the slot visit order ascending, so the skip-on-equality guard and
        lowest-id tie-breaks stay exact under oversubscription."""
        mut = _Mut(self)
        ns = self.n_slots
        cands = [s for s in range(ns)
                 if 0 <= mut.pt[s] < self.pool_pages][::-1][:n_pages]
        for slot in cands:
            phys = int(mut.pt[slot])
            mut.host_pages[slot] = np.asarray(self.pool[phys])
            mut.free_pool.append(phys)
            mut.pt[slot] = -1
        return mut.freeze(), len(cands)


@dataclasses.dataclass(frozen=True, eq=False)
class PagedIndex:
    """Search facade over ``PagedIndexStorage`` — the mutable index the
    server swaps. Every mutation (``append``, ``promote``,
    ``compact_pages``, ``evict``) returns a NEW index sharing untouched
    arrays: the running ``RetrievalServer`` swaps whole indexes between
    batches, and in-flight batches keep the old one alive until their
    replies post.

    ``depth`` is the DMA pipeline depth (pallas: page i+depth-1 prefetches
    while page i scores; jnp: host-wave staging lookahead). ``wave_pages``
    bounds the host-tier staging buffer — oversubscribed searches stream
    host pages in fixed-shape waves chained through the top-k carry.
    """

    storage: PagedIndexStorage
    backend: Backend = "jnp"
    depth: int = 2
    guard: str = "row"
    wave_pages: int = 8

    # -- shape ---------------------------------------------------------------
    @property
    def n(self) -> int:
        return self.storage.n_rows

    @property
    def dim(self) -> int:
        return self.storage.dim

    @property
    def nbytes(self) -> int:
        return self.storage.nbytes

    @property
    def quantized(self) -> bool:
        return self.storage.quantized

    @property
    def storage_dtype(self):
        return self.storage.pool.dtype

    @property
    def delta_pages(self) -> int:
        return self.storage.delta_pages

    @property
    def total_pages(self) -> int:
        return self.storage.n_slots

    # -- construction --------------------------------------------------------
    @classmethod
    def from_index(cls, base: DenseIndex, *, page_rows: int = 256,
                   pool_pages: int | None = None,
                   tail_pages: int | None = None,
                   table_cap: int | None = None, seal_rows: int = 4096,
                   backend: Backend | None = None, depth: int = 2,
                   wave_pages: int = 8) -> "PagedIndex":
        st = PagedIndexStorage.from_index(
            base, page_rows=page_rows, pool_pages=pool_pages,
            tail_pages=tail_pages, table_cap=table_cap, seal_rows=seal_rows)
        return cls(storage=st,
                   backend=base.backend if backend is None else backend,
                   depth=depth, wave_pages=wave_pages)

    # -- persistence ---------------------------------------------------------
    @classmethod
    def load(cls, store, *, page_rows: int | None = None,
             pool_pages: int | None = None, tail_pages: int | None = None,
             table_cap: int | None = None, seal_rows: int = 4096,
             backend: Backend = "jnp", depth: int = 2,
             wave_pages: int = 8) -> "PagedIndex":
        """Rehydrate from an on-disk artifact bit-for-bit.

        Extent i is store segment i, adopted with its stored bytes and
        scale as they are: segment 0 pages into the pool as the base
        extent, every later segment into the writable tiers (tail, then
        host). When the ``paged`` manifest block records extent 0 as a
        delta (an index grown from empty), segment 0 is adopted like the
        rest — pool pages reject writes, so an open extent must never
        land there. The block (page geometry + extent lifecycle) may LAG
        the segments (crash between the append mirror's two manifest
        swaps): missing trailing extents reload as deltas, and sealed-ness
        is reconstructed conservatively (every non-last extent is sealed;
        the last one by row count or the fresh block entry). Without a
        block (a flat artifact, or bare delta segments) a delta is sealed
        once it holds its stored capacity. An unsealed extent's requant
        staging ``raw`` is the dequantised reconstruction of its bytes.
        ``pool_pages`` below the resident page count oversubscribes: the
        overflow streams from the host tier at search time.

        The seal size is the store's: the ``paged`` block's ``seal_rows``,
        else the last delta segment's recorded capacity, so the open
        extent never takes more rows than its segment may hold.
        ``seal_rows`` applies only to a flat store.
        """
        import os
        from repro.core.store import IndexStore
        if isinstance(store, (str, os.PathLike)):
            store = IndexStore.open(store)
        pb = store.manifest.get("paged")
        views = store.segments()
        caps = [v.capacity for v in views if v.kind == "delta"]
        if pb is not None:
            S = int(pb["seal_rows"])
        elif caps and caps[-1]:
            S = int(caps[-1])
        else:
            S = seal_rows
        if page_rows is None:
            R = 256 if pb is None else int(pb["page_rows"])
        else:
            R = page_rows
        pbe = None if pb is None else pb["extents"]
        first = 1 if not pbe or pbe[0]["kind"] != "delta" else 0
        adopt = views[first:]
        if tail_pages is None:
            seal_pages = -(-S // R)
            need = sum(-(-(v.capacity or max(S, v.n)) // R) for v in adopt)
            tail_pages = max(2 * seal_pages, need + seal_pages, 2)

        def scale_of(v):
            sc = v.scale()
            return None if sc is None else np.asarray(sc, np.float32)

        base = views[0].read_rows(0, views[0].n) if first else \
            np.zeros((0, store.dim), store.dtype)
        st = PagedIndexStorage.from_rows(
            base, scale_of(views[0]), page_rows=R, pool_pages=pool_pages,
            tail_pages=tail_pages, table_cap=table_cap, seal_rows=S)
        states = [("base", True)] * len(st.extents)
        for v in adopt:
            st = st._adopt_extent(v.read_rows(0, v.n), scale_of(v))
            states.append(("delta", v.n >= (v.capacity or max(S, v.n))))
        if pbe is not None:
            last = len(states) - 1
            states = [
                (pbe[i]["kind"] if i < len(pbe) else "delta",
                 i < last or e.n_rows >= S
                 or (i < len(pbe) and int(pbe[i]["n"]) == e.n_rows
                     and bool(pbe[i]["sealed"])))
                for i, e in enumerate(st.extents)]
        return cls(storage=st.with_lifecycle(states), backend=backend,
                   depth=depth, wave_pages=wave_pages)

    def save(self, path: str, *, pruner=None, meta: dict | None = None
             ) -> "object":
        """Persist page-granularly (see ``save_paged_index``)."""
        from repro.core.store import save_paged_index
        return save_paged_index(path, self, pruner=pruner, meta=meta)

    # -- growth --------------------------------------------------------------
    def append_with_ops(self, rows) -> tuple["PagedIndex", list]:
        st, ops = self.storage.append_with_ops(rows)
        return dataclasses.replace(self, storage=st), ops

    def append(self, rows) -> "PagedIndex":
        return self.append_with_ops(rows)[0]

    def promote(self) -> tuple["PagedIndex", int]:
        st, n = self.storage.promote()
        return dataclasses.replace(self, storage=st), n

    def compact_pages(self) -> tuple["PagedIndex", dict]:
        st, stats = self.storage.compact()
        return dataclasses.replace(self, storage=st), stats

    def evict(self, n_pages: int) -> tuple["PagedIndex", int]:
        st, n = self.storage.evict(n_pages)
        return dataclasses.replace(self, storage=st), n

    # -- search --------------------------------------------------------------
    def _runs(self) -> list:
        """Maximal contiguous slot ranges per tier, ascending — device
        runs dispatch straight off the pools, host runs stream waves."""
        pt = self.storage.pt_host
        ns = self.storage.n_slots
        runs = []
        i = 0
        while i < ns:
            dev = pt[i] >= 0
            j = i
            while j < ns and (pt[j] >= 0) == dev:
                j += 1
            runs.append((i, j, bool(dev)))
            i = j
        return runs

    def _device_args(self):
        st = self.storage
        return (st.pool, st.tail, st.page_table, st.page_scale,
                st.page_nvalid, st.page_offset)

    def _search_qf(self, qf: jax.Array, k: int):
        runs = self._runs()
        B = qf.shape[0]
        if not runs:
            return (jnp.full((B, k), -jnp.inf, jnp.float32),
                    jnp.full((B, k), -1, jnp.int32))
        out = None
        for idx, (lo, hi, dev) in enumerate(runs):
            last = idx == len(runs) - 1
            if dev:
                out = _paged_topk(*self._device_args(), jnp.int32(lo),
                                  jnp.int32(hi), qf, k=k,
                                  backend=self.backend, depth=self.depth,
                                  guard=self.guard, carry=out, finalize=last)
            else:
                out = self._host_run_topk(qf, k, lo, hi, out, finalize=last)
        return out

    def _stage_wave(self, slots: list):
        """Host pages -> one fixed-shape device wave (pool-of-its-own)."""
        st = self.storage
        W, R, m = self.wave_pages, st.page_rows, st.dim
        buf = np.zeros((W, R, m), st.np_dtype)
        nv = np.zeros(W, np.int32)
        off = np.zeros(W, np.int32)
        sc = (np.zeros((W, m), np.float32) if st.scale_host is not None
              else None)
        for i, s in enumerate(slots):
            buf[i] = st.host_pages[s]
            nv[i] = st.nvalid_host[s]
            off[i] = st.offset_host[s]
            if sc is not None:
                sc[i] = st.scale_host[s]
        pt = np.full(W, -1, np.int32)
        pt[:len(slots)] = np.arange(len(slots), dtype=np.int32)
        return (jnp.asarray(buf), jnp.asarray(pt),
                _device_scale(sc), jnp.asarray(nv),
                jnp.asarray(off), len(slots))

    def _waves(self, lo: int, hi: int) -> list:
        slots = list(range(lo, hi))
        W = self.wave_pages
        return [slots[i:i + W] for i in range(0, len(slots), W)]

    def _host_run_topk(self, qf, k, lo, hi, carry, *, finalize):
        """Stream a host run in waves; ``depth-1`` waves stage ahead of
        the one being scored, so host->device transfer overlaps compute
        (async dispatch) just as page DMA overlaps inside the kernel."""
        waves = self._waves(lo, hi)
        staged: deque = deque()
        nxt = 0
        out = carry
        for wi, _ in enumerate(waves):
            while nxt < len(waves) and nxt <= wi + max(self.depth - 1, 0):
                staged.append(self._stage_wave(waves[nxt]))
                nxt += 1
            buf, pt, sc, nv, off, cnt = staged.popleft()
            out = _paged_topk(buf, None, pt, sc, nv, off, jnp.int32(0),
                              jnp.int32(cnt), qf, k=k, backend=self.backend,
                              depth=self.depth, guard=self.guard, carry=out,
                              finalize=finalize and wi == len(waves) - 1)
        return out

    def search(self, queries: jax.Array, k: int = 10
               ) -> tuple[jax.Array, jax.Array]:
        q = jnp.atleast_2d(queries).astype(jnp.float32)
        k = min(k, max(self.n, 1))
        return self._search_qf(q, k)

    def search_projected(self, queries: jax.Array, components: jax.Array,
                         k: int = 10, *, mean: jax.Array | None = None
                         ) -> tuple[jax.Array, jax.Array]:
        """Raw-query search. Fully device-resident index: ONE dispatch
        (projection + page walk fused). Oversubscribed: one shared
        projection dispatch, then device/host runs chained by carry."""
        k = min(k, max(self.n, 1))
        runs = self._runs()
        if len(runs) == 1 and runs[0][2]:
            lo, hi, _ = runs[0]
            return _paged_search_projected(
                *self._device_args(), jnp.int32(lo), jnp.int32(hi),
                jnp.asarray(components), mean, jnp.atleast_2d(queries),
                k=k, backend=self.backend, depth=self.depth,
                guard=self.guard)
        q = _project_nofold(jnp.atleast_2d(queries),
                            jnp.asarray(components), mean)
        return self._search_qf(q, k)

    # -- cascade rescore -----------------------------------------------------
    def rescore(self, qf: jax.Array, uids: jax.Array) -> jax.Array:
        """(B, U) exact shortlist scores (cascade second stage): device
        runs rescore off the pools, host runs stream waves; max-combined
        per page."""
        acc = jnp.full((qf.shape[0], uids.shape[0]), -jnp.inf, jnp.float32)
        for lo, hi, dev in self._runs():
            if dev:
                acc = _paged_rescore(*self._device_args(), jnp.int32(lo),
                                     jnp.int32(hi), qf, uids, acc)
            else:
                for slots in self._waves(lo, hi):
                    buf, pt, sc, nv, off, cnt = self._stage_wave(slots)
                    acc = _paged_rescore(buf, None, pt, sc, nv, off,
                                         jnp.int32(0), jnp.int32(cnt), qf,
                                         uids, acc)
        return acc
