"""Dense embedding index with exact (brute-force) top-k search.

This is the FAISS-flat role in the paper's pipeline, built TPU-native:

  * ``DenseIndex``        — single-logical-array index, matmul + top-k.
                            Backend 'jnp' (XLA) or 'pallas' (fused
                            score-and-select scan; see repro.kernels).
  * ``ShardedDenseIndex`` — rows sharded over every mesh device; each shard
                            scans locally, then a tiny global merge over the
                            per-shard top-k (k·chips candidates).
  * The mutable (appendable) index is ``repro.core.paged.PagedIndex``.
  * int8 symmetric quantisation (beyond-paper) composes with PCA pruning:
    index bytes drop by 4x on top of the m/d PCA reduction.

Scores are always accumulated in fp32 regardless of index dtype.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.topk_score import PRECISION, query_terms, score_rows
from repro.par import compat


Backend = Literal["jnp", "pallas"]
Merge = Literal["flat", "hierarchical"]


def project_queries(q: jax.Array, W: jax.Array,
                    scale: jax.Array | None = None,
                    mean: jax.Array | None = None) -> jax.Array:
    """q̂ = ((q − mean) @ W_m) ⊙ scale — the full raw-query-to-search-query
    transform (PCA projection + int8 dequant fold), written to be traced
    inline inside the fused ``search_projected`` jits.

    Operation order deliberately mirrors the two-step path
    (``transform_query`` then ``_dequeries``) — cast to f32, center,
    project, then fold the scale — so for f32 raw queries (the serving
    input) the fused dispatch is bit-identical to the separate-dispatch
    path (pinned by tests/test_sharded_parity.py). Lower-precision raw
    queries upcast here, whereas ``transform`` casts its result back to
    the input dtype — feed f32 when exact parity matters.

    Its ops carry the device scope ``project`` (see ``_scan_topk``).
    """
    with jax.named_scope("project"):
        q = jnp.atleast_2d(q).astype(jnp.float32)
        if mean is not None:
            q = q - mean[None, :]
        q = jnp.dot(q, W, precision=PRECISION)
        if scale is not None:
            q = q * scale[None, :]
        return q


@partial(jax.jit, static_argnames=("k", "block", "backend"))
def _dense_search_projected(D, scale, W, mean, Q, k: int,
                            block: int | None, backend: Backend):
    """One compiled dispatch: projection + scale fold + fused top-k scan."""
    q = project_queries(Q, W, scale=scale, mean=mean)
    if backend == "pallas":
        from repro.kernels import ops as kops
        if block is None:
            return kops.topk_score(D, q, k=k)
        return kops.topk_score(D, q, k=k, block_n=block)
    return _scan_topk(D, q, k, block=65536 if block is None else block)


def _check_flat_loadable(store) -> None:
    """Refuse to flatten a segmented store whose segments disagree on the
    int8 scale — a flat load would dequantise delta rows with the base's
    scale. ``SegmentView``s (single segment by construction) pass."""
    if getattr(store, "flat_loadable", True):
        return
    from repro.core.store import IndexStoreError
    raise IndexStoreError(
        f"{store.path}: store has delta segments with per-segment scales — "
        f"load it with PagedIndex.load, not a flat index loader")


def _topk_merge(scores: jax.Array, ids: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k of (B, C) candidate scores, returning (B, k) scores + gathered ids."""
    s, idx = jax.lax.top_k(scores, k)
    return s, jnp.take_along_axis(ids, idx, axis=-1)


def _staged_topk_merge(s: jax.Array, ids: jax.Array, k: int,
                       stages) -> tuple[jax.Array, jax.Array]:
    """Merge per-shard (B, k) top-k across the mesh in all-gather stages.

    ``stages`` is a sequence of axis-name tuples; each stage all-gathers the
    surviving candidates over its axes and re-selects top-k. One stage over
    every axis is the flat merge (k·ndev candidates per device); splitting
    into two stages shrinks the per-device gather volume to
    k·(|stage1| + |stage2|) — k·2√ndev on a square mesh. Exactness is
    preserved: a global top-k entry is a top-k entry of every intermediate
    device group it belongs to, so it survives each stage. Gather order is
    row-major by mesh position in both layouts, so tie-breaks (and thus the
    selected ids) are bit-identical between flat and staged merges. Its
    ops carry the device scope ``merge``.
    """
    with jax.named_scope("merge"):
        for stage in stages:
            stage = tuple(stage)
            if not stage:
                continue
            s_all = jax.lax.all_gather(s, stage, axis=1, tiled=True)
            i_all = jax.lax.all_gather(ids, stage, axis=1, tiled=True)
            s, ids = _topk_merge(s_all, i_all, k)
        return s, ids


BUCKET = 128   # rows per select bucket: one lane width


def _select_plan(k: int, block: int) -> int:
    """Bucket width for ``_scan_topk``'s deferred select, 0 for a direct
    ``top_k`` over the strip.

    Bucketing pays when the k buckets it keeps are a small share of the
    strip (k·W ≤ block / 8): the served k=10 over 65,536-row strips keeps
    1,280 of 65,536 columns. A large k (the cascade's coarse scan) or a
    strip that is not a whole number of buckets selects directly.
    """
    if block % BUCKET == 0 and 8 * k * BUCKET <= block:
        return BUCKET
    return 0


def _score_strip(D: jax.Array, qt: jax.Array, start: jax.Array, block: int
                 ) -> tuple[jax.Array, jax.Array]:
    """The (B, block) scores of the strip that starts at row ``start``, and
    the row it is read from. The last strip slides back to end at row n;
    rows below ``start``, which the previous strip scored, read -inf."""
    n = D.shape[0]
    with jax.named_scope("score"):
        at = jnp.minimum(start, n - block)
        blk = jax.lax.dynamic_slice_in_dim(D, at, block, axis=0)
        s = score_rows(qt, blk)                                  # (B, block)
        ids = at + jnp.arange(block, dtype=jnp.int32)[None, :]
        return jnp.where(ids >= start, s, -jnp.inf), at


def _deferred_select(D: jax.Array, qt: jax.Array, k: int, block: int,
                     W: int, starts: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """The bucketed scan: each strip leaves only its bucket maxima, and the
    select runs once, after the last strip.

    ``select/bucket``: the strip's (B, block / W) maxima over buckets of W
    contiguous rows, stacked strip by strip. ``select/refine``: one
    ``top_k`` over every maximum of the scan picks k buckets, which are
    rescored slot by slot (B buckets of W rows at a time, by the strip's
    own ``score_rows``) and masked as their strip was; one ``top_k`` over
    the (B, k·W) candidates is the result.

    Exact: an entry outside the kept buckets is preceded, in (score desc,
    id asc) order, by the max of each kept one. ``top_k`` breaks equal
    maxima towards the lower bucket, and buckets in strip-major order hold
    ascending ids (the sliding last strip's rows at or above its start
    exceed every earlier strip's), so the kept buckets, put in ascending
    order, give ``top_k`` its candidates as the scan saw them.
    """
    n, d = D.shape
    nb = block // W

    def strip(_, start):
        s, _ = _score_strip(D, qt, start, block)
        with jax.named_scope("select"), jax.named_scope("bucket"):
            return None, s.reshape(-1, nb, W).max(axis=2)        # (B, nb)

    _, bmax = jax.lax.scan(strip, None, starts)         # (nblocks, B, nb)

    with jax.named_scope("select"), jax.named_scope("refine"):
        B = bmax.shape[1]
        bmax = jnp.moveaxis(bmax, 0, 1).reshape(B, -1)
        _, top = jax.lax.top_k(bmax, k)                          # (B, k)
        top = jnp.sort(top, axis=1)
        start = (top // nb) * block
        row0 = jnp.minimum(start, n - block) + (top % nb) * W
        rows = jnp.arange(W, dtype=jnp.int32)
        diag = jnp.arange(B)

        def rescore(_, slot):
            r0, st = slot                                        # (B,), (B,)
            ids = r0[:, None] + rows                             # (B, W)
            blk = D.at[ids.reshape(-1)].get(mode="promise_in_bounds")
            s = score_rows(qt, blk)                              # (B, B·W)
            s = s.reshape(B, B, W)[diag, diag]                   # (B, W)
            return None, (jnp.where(ids >= st[:, None], s, -jnp.inf), ids)

        _, (cs, ci) = jax.lax.scan(rescore, None, (row0.T, start.T))
        cs = jnp.moveaxis(cs, 0, 1).reshape(B, k * W)
        ci = jnp.moveaxis(ci, 0, 1).reshape(B, k * W)
        return _topk_merge(cs, ci, k)


@partial(jax.jit, static_argnames=("k", "block", "vma_axes", "guard"))
def _scan_topk(D: jax.Array, Q: jax.Array, k: int, block: int = 65536,
               vma_axes: tuple[str, ...] | None = None, guard: str = "row"
               ) -> tuple[jax.Array, jax.Array]:
    """Blocked exact search: stream row blocks of D, select the top-k.

    Never materialises the full (B, n) score matrix — the jnp analogue of
    the Pallas fused kernel, and the oracle it is tested against:

      * the index blocks keep their storage dtype (int8 stays int8 in the
        scan's xs) and each is scored by ``score_rows`` as the rows' dtype
        selects: f32 rows by an f32 matmul at ``HIGHEST``; int8 or bf16
        rows, which bf16 holds exactly, convert to bf16 and meet the
        query's three-term bf16 split (``query_terms``, made once before
        the loop) in one matmul with f32 accumulation — no full-index
        shadow copy, no strip in f32;
      * deferred bucketed select (``_select_plan``: k·W ≤ block / 8 with
        W = 128, over two strips or more): each strip keeps only its
        per-bucket maxima, and the select runs once per call after the
        loop (``_deferred_select``) — no per-strip ``top_k``, sort or
        ``lax.cond``. ``guard`` selects nothing there;
      * otherwise, a direct select: ``top_k`` over each strip, then a tiny
        (B, 2k) merge with a running list, behind a block-skip guard: a
        strip that cannot improve the running top-k skips selection under
        ``lax.cond``. ``guard="row"`` (default): row b improves iff
        ``max(s[b]) > min(run_s[b])``; the strip is skipped iff *no* row
        improves (a strictly weaker skip condition than the legacy
        ``guard="batch"`` global compare, so mixed batches skip more
        often, never less) and the merge writes back only improving rows.
        Results are bit-identical either way: for a non-improving row the
        merge is already a no-op — strict guard, and ascending-id strips
        lose first-occurrence ties. Skipping on equality is exact for the
        same ascending-id reason.

    Both selects give the same scores and ids, ties included.

    The index is never padded or copied: when ``block`` does not divide
    n, the last strip slides back to end at row n and masks the rows the
    previous strip already scored.

    ``vma_axes``: when called inside shard_map over those axes, the direct
    path's scan carry must be marked varying (compat.mark_varying) to
    typecheck.

    Device scopes (``jax.named_scope``: each op's HLO ``op_name`` holds
    ``/score/`` or ``/select/``, names a profile can follow across
    refactors): ``score`` is the query's split (once, before the loop)
    and the strip's slice, conversion, matmul and mask. On the direct
    path ``select`` is the improvement test and the whole guarded merge,
    per-strip ``top_k`` included. On the deferred path ``select`` nests
    ``bucket`` (the strip's maxima, in the loop) and ``refine`` (once,
    after it: the ``top_k`` of the maxima, the sort, the rescore of the
    kept buckets and the final ``top_k``).
    ``project_queries`` adds ``project``; ``ShardedDenseIndex``'s id
    offset, pad mask and cut and ``_staged_topk_merge`` add ``merge``.
    """
    n, d = D.shape
    B = Q.shape[0]
    block = min(block, n)
    nblocks = -(-n // block)
    kk = min(k, block)   # strip-local candidate count
    W = _select_plan(k, block)
    with jax.named_scope("score"):
        qt = query_terms(Q.astype(jnp.float32), D.dtype)

    if nblocks == 1:
        # single strip (block == n): the running list is empty, a guard can
        # never fire, and the two-stage detour just adds a second sort —
        # select directly
        with jax.named_scope("score"):
            s = score_rows(qt, D)
        with jax.named_scope("select"):
            ids = jnp.broadcast_to(
                jnp.arange(block, dtype=jnp.int32)[None, :], (B, block))
            if k > block:
                # fewer rows than k: sentinels first so they win -inf
                # ties, matching the scan init and the Pallas kernel's -1
                # pads
                s = jnp.concatenate(
                    [jnp.full((B, k), -jnp.inf, jnp.float32), s], axis=1)
                ids = jnp.concatenate(
                    [jnp.full((B, k), -1, jnp.int32), ids], axis=1)
            return _topk_merge(s, ids, k)

    starts = jnp.arange(nblocks, dtype=jnp.int32) * block
    if W:
        return _deferred_select(D, qt, k, block, W, starts)

    def body(carry, start):
        bs, bi = carry
        s, at = _score_strip(D, qt, start, block)

        with jax.named_scope("select"):
            imp = jnp.max(s, axis=1) > jnp.min(bs, axis=1)       # (B,)

            def merge(carry_in):
                bs0, bi0 = carry_in
                ss, si = jax.lax.top_k(s, kk)                    # (B, kk)
                # running list first: at -inf ties its (-1) pads win the
                # first-occurrence tie-break, matching the kernel's pads
                cs = jnp.concatenate([bs0, ss], axis=1)
                ci = jnp.concatenate([bi0, at + si.astype(jnp.int32)],
                                     axis=1)
                ms, mi = _topk_merge(cs, ci, k)
                if guard == "row":
                    # masked merge: non-improving rows keep their list
                    # bitwise
                    ms = jnp.where(imp[:, None], ms, bs0)
                    mi = jnp.where(imp[:, None], mi, bi0)
                return ms, mi

            if guard == "row":
                can_improve = jnp.any(imp)
            else:
                can_improve = jnp.max(s) > jnp.min(bs)
            return jax.lax.cond(can_improve, merge, lambda c: c,
                                (bs, bi)), None

    init = (jnp.full((B, k), -jnp.inf, jnp.float32), jnp.full((B, k), -1, jnp.int32))
    if vma_axes:
        init = compat.mark_varying(init, vma_axes)
    (scores, ids), _ = jax.lax.scan(body, init, starts)
    return scores, ids


@partial(jax.jit, donate_argnums=0)
def _fill_rows(D, block, start):
    """Write ``block`` into ``D`` at row ``start``. ``D`` is donated (a
    load's buffer no one else holds), so the rows land in place instead
    of in a copy; ``start`` is traced, so one chunk shape compiles once."""
    return jax.lax.dynamic_update_slice(D, block, (start, 0))


@dataclasses.dataclass
class DenseIndex:
    """Flat exact-search index over document embeddings.

    ``vectors``: (n, m) document matrix (possibly PCA-pruned and/or int8).
    ``scale``:   per-dim dequant scale when vectors are int8, else None.
    """

    vectors: jax.Array
    scale: jax.Array | None = None
    backend: Backend = "jnp"

    @property
    def n(self) -> int:
        return self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def nbytes(self) -> int:
        b = self.vectors.size * self.vectors.dtype.itemsize
        if self.scale is not None:
            b += self.scale.size * self.scale.dtype.itemsize
        return b

    @classmethod
    def build(cls, vectors: jax.Array, *, dtype: jnp.dtype | None = None,
              quantize_int8: bool = False, backend: Backend = "jnp") -> "DenseIndex":
        v = jnp.asarray(vectors)
        if quantize_int8:
            from repro.core.quantization import quantize_int8_per_dim
            q, scale = quantize_int8_per_dim(v)
            return cls(vectors=q, scale=scale, backend=backend)
        if dtype is not None:
            v = v.astype(dtype)
        return cls(vectors=v, scale=None, backend=backend)

    @classmethod
    def load(cls, store, *, backend: Backend = "jnp") -> "DenseIndex":
        """Load from an on-disk ``IndexStore`` (path or open handle).

        Chunks are memory-mapped and copied to device one at a time — the
        host never holds a full-index copy beyond the OS page cache — and
        written in place into one preallocated buffer, so the device holds
        the index plus one chunk, never two copies of the index.
        """
        from repro.core.store import IndexStore
        if isinstance(store, (str, os.PathLike)):
            store = IndexStore.open(store)
        _check_flat_loadable(store)
        chunks = list(store.iter_chunks())          # memory maps, not reads
        vectors = jnp.zeros((sum(map(len, chunks)), chunks[0].shape[1]),
                            chunks[0].dtype)
        start = 0
        for c in chunks:
            vectors = _fill_rows(vectors, jnp.asarray(np.ascontiguousarray(c)),
                                 np.int32(start))
            # dispatch is asynchronous: without the wait every chunk's
            # upload is queued ahead of its fill, and the queued chunks add
            # up to a second copy of the index on the device
            vectors.block_until_ready()
            start += len(c)
        s = store.scale()
        return cls(vectors=vectors,
                   scale=None if s is None else jnp.asarray(s),
                   backend=backend)

    def _dequeries(self, queries: jax.Array) -> jax.Array:
        """Fold the int8 scale into the query side: (Dq) = (D_int8)(s ⊙ q)."""
        q = jnp.atleast_2d(queries)
        if self.scale is not None:
            q = q * self.scale[None, :]
        return q

    def search(self, queries: jax.Array, k: int = 10,
               block: int | None = None) -> tuple[jax.Array, jax.Array]:
        """Exact top-k. Returns (scores (B,k) fp32, ids (B,k) int32).

        ``block`` is the scan strip size. ``None`` picks the backend
        default (65536 rows for the jnp scan, the kernel's ``block_n`` for
        pallas); an explicit value is honoured on *both* backends — it used
        to be silently dropped on pallas, so serve-time tuning did nothing.
        """
        q = self._dequeries(queries)
        k = min(k, self.n)
        if self.backend == "pallas":
            from repro.kernels import ops as kops
            if block is None:
                return kops.topk_score(self.vectors, q, k=k)
            return kops.topk_score(self.vectors, q, k=k, block_n=block)
        return _scan_topk(self.vectors, q, k,
                          block=65536 if block is None else block)

    def search_projected(self, queries: jax.Array, components: jax.Array,
                         k: int = 10, *, mean: jax.Array | None = None,
                         block: int | None = None
                         ) -> tuple[jax.Array, jax.Array]:
        """Fused raw-query search: one dispatch from d-dim query to top-k.

        ``queries`` are raw (B, d) vectors; ``components`` is the (d, m)
        PCA projection ``W_m`` (``StaticPruner.projection()`` /
        ``pca.projection_operands``); ``mean`` the optional centering row.
        Projection, the int8 scale fold, and the top-k scan all trace into
        a single jit — no separate projection dispatch, no intermediate
        q̂ round-trip. For f32 raw queries (the serving input) results are
        bit-identical to ``transform_queries`` → ``search``.
        """
        k = min(k, self.n)
        return _dense_search_projected(self.vectors, self.scale,
                                       jnp.asarray(components), mean,
                                       jnp.atleast_2d(queries), k, block,
                                       self.backend)


def _addressable_shard_ranges(sharding, shape: tuple[int, int], n: int
                              ) -> list[tuple]:
    """Row ranges of the shards THIS PROCESS must materialise.

    One ``(device, start, stop, lo, hi)`` tuple per shard in
    ``sharding.addressable_devices_indices_map`` — i.e. per local device
    only, so a multi-host load reads 1/num_hosts of the store and never
    touches rows another process owns. ``[start, stop)`` is the shard's
    padded-global row window; ``[lo, hi)`` is its clamp to the ``n`` real
    rows (a shard may be partly — or, when ``n < (ndev-1)·rows_per``,
    entirely — device padding the caller synthesises as zeros).
    """
    n_padded = shape[0]
    out = []
    for device, index in sharding.addressable_devices_indices_map(
            shape).items():
        rows = index[0]
        start = rows.start or 0
        stop = rows.stop if rows.stop is not None else n_padded
        out.append((device, start, stop, min(start, n), min(stop, n)))
    return out


@dataclasses.dataclass
class ShardedDenseIndex:
    """Index with rows sharded across every device of a mesh.

    Serve-time layout of the paper's index at pod scale: each chip owns
    n/num_devices contiguous rows. Search = local blocked scan per shard
    followed by a global merge of per-shard top-k — the only collective is
    an all-gather of (B, k) scores + ids per shard (k·chips ≪ n).

    ``backend`` selects the per-shard scan: 'jnp' (blocked XLA scan) or
    'pallas' (fused score-and-select kernel).
    ``merge`` selects the global candidate merge: 'flat' (one all-gather
    over every axis, k·ndev candidates per query) or 'hierarchical' (one
    stage per mesh dimension — within the minor axis, then across the
    rest — shrinking the collective to k·(minor + rest) candidates; on a
    1-axis mesh the two are the same single stage).
    """

    vectors: jax.Array          # (n_padded, m) sharded P(axes, None)
    mesh: Mesh
    scale: jax.Array | None = None
    backend: Backend = "jnp"
    merge: Merge = "flat"
    n_real: int | None = None   # logical row count before device padding
    # compiled search per (B, k, merge) — rebuilding the shard_map closure
    # per call would recompile per batch and cap serving at trace speed
    _jit_cache: dict = dataclasses.field(default_factory=dict, repr=False,
                                         compare=False)

    @classmethod
    def build(cls, vectors: jax.Array, mesh: Mesh, *,
              quantize_int8: bool = False,
              backend: Backend = "jnp",
              merge: Merge = "flat") -> "ShardedDenseIndex":
        axes = tuple(mesh.axis_names)
        scale = None
        v = jnp.asarray(vectors)
        if quantize_int8:
            from repro.core.quantization import quantize_int8_per_dim
            v, scale = quantize_int8_per_dim(v)
        sharding = NamedSharding(mesh, P(axes, None))
        n = v.shape[0]
        ndev = int(np.prod(mesh.devices.shape))
        pad = (-n) % ndev
        if pad:
            v = jnp.pad(v, ((0, pad), (0, 0)))
        v = jax.device_put(v, sharding)
        return cls(vectors=v, mesh=mesh, scale=scale, backend=backend,
                   merge=merge, n_real=n)

    @classmethod
    def load(cls, store, mesh: Mesh, *,
             backend: Backend = "jnp",
             merge: Merge = "flat") -> "ShardedDenseIndex":
        """Host-streamed sharded load from an on-disk ``IndexStore``.

        Each device's row range is sliced out of the memory-mapped chunks
        (host memory O(shard), one shard live at a time), placed on that
        device, and the global array assembled with
        ``jax.make_array_from_single_device_arrays`` — no full-index host
        copy and no single-device ``device_put`` ever materialises, so the
        index may exceed one host's RAM. Device-padding rows for n not
        divisible by the device count are synthesised at load.
        """
        from repro.core.store import IndexStore
        if isinstance(store, (str, os.PathLike)):
            store = IndexStore.open(store)
        _check_flat_loadable(store)
        axes = tuple(mesh.axis_names)
        n, m = store.n, store.dim
        ndev = int(np.prod(mesh.devices.shape))
        pad = (-n) % ndev
        n_padded = n + pad
        sharding = NamedSharding(mesh, P(axes, None))
        shape = (n_padded, m)
        shards = []
        for device, start, stop, lo, hi in _addressable_shard_ranges(
                sharding, shape, n):
            local = store.read_rows(lo, hi)
            if stop - start > hi - lo:   # synthesise this shard's padding rows
                local = np.concatenate(
                    [local, np.zeros(((stop - start) - (hi - lo), m),
                                     store.dtype)], axis=0)
            shards.append(jax.device_put(local, device))
            del local
        vectors = jax.make_array_from_single_device_arrays(shape, sharding, shards)
        s = store.scale()
        return cls(vectors=vectors, mesh=mesh,
                   scale=None if s is None else jnp.asarray(s),
                   backend=backend, merge=merge, n_real=n)

    @property
    def n(self) -> int:
        """Logical (unpadded) row count."""
        return self.n_real if self.n_real is not None else self.vectors.shape[0]

    @property
    def dim(self) -> int:
        return self.vectors.shape[1]

    @property
    def nbytes(self) -> int:
        b = self.vectors.size * self.vectors.dtype.itemsize
        if self.scale is not None:
            b += self.scale.size * self.scale.dtype.itemsize
        return b

    @property
    def query_sharding(self) -> NamedSharding:
        """Where a server stages a batch of queries: replicated on every
        device of the mesh, the layout the search takes them in, so no
        device receives them first and copies them on."""
        return NamedSharding(self.mesh, P())

    def search(self, queries: jax.Array, k: int = 10,
               merge: Merge | None = None,
               block: int | None = None) -> tuple[jax.Array, jax.Array]:
        # a (B, d) batch goes in as it is: ``atleast_2d`` would run as a
        # program of its own on every device, once a batch
        q = queries if np.ndim(queries) == 2 else jnp.atleast_2d(queries)
        q = q.astype(jnp.float32)
        if self.scale is not None:
            q = q * self.scale[None, :]
        k = min(k, self.n)
        merge = self.merge if merge is None else merge
        key = (q.shape[0], k, merge, block)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = self._jit_cache[key] = jax.jit(
                self._search_fn(k, merge, block))
        return fn(self.vectors, q)

    def search_projected(self, queries: jax.Array, components: jax.Array,
                         k: int = 10, *, mean: jax.Array | None = None,
                         merge: Merge | None = None,
                         block: int | None = None
                         ) -> tuple[jax.Array, jax.Array]:
        """Fused raw-query search over the sharded index (one dispatch).

        The PCA projection + int8 scale fold run on the replicated query
        inside the same jit as the shard_map'd scan+merge, so the serving
        hot path issues exactly one compiled computation per batch. For
        f32 raw queries, bit-identical to ``transform_queries`` →
        ``search`` (parity-tested).
        """
        q = jnp.atleast_2d(queries)
        k = min(k, self.n)
        merge = self.merge if merge is None else merge
        key = ("projected", q.shape[0], q.shape[1], k, merge, block,
               self.scale is not None, mean is not None)
        fn = self._jit_cache.get(key)
        if fn is None:
            search = self._search_fn(k, merge, block)

            def projected(vectors, W, scale, mean_, q_):
                return search(vectors,
                              project_queries(q_, W, scale=scale, mean=mean_))

            fn = self._jit_cache[key] = jax.jit(projected)
        return fn(self.vectors, jnp.asarray(components), self.scale, mean, q)

    def _search_fn(self, k: int, merge: Merge, block: int | None = None):
        axes = tuple(self.mesh.axis_names)
        n_real = self.n
        ndev = int(np.prod(self.mesh.devices.shape))
        rows_per = self.vectors.shape[0] // ndev
        backend = self.backend
        # Device-padding rows score like real zero vectors and can *win* the
        # shard-local top-k (every real score may be negative), displacing
        # real candidates before any post-hoc mask runs. All ``pad`` padding
        # rows live in the last shard, so a local top-(k+pad) provably
        # retains the shard's true top-k real rows; the pad entries are then
        # masked and cut back to k before the gather.
        pad = self.vectors.shape[0] - n_real
        kp = k + pad
        if merge == "hierarchical" and len(axes) > 1:
            stages = ((axes[-1],), tuple(axes[:-1]))   # minor axis first
        else:
            stages = (axes,)

        def shard_fn(D_local, q_rep):
            if backend == "pallas":
                from repro.kernels import ops as kops
                if block is None:
                    s, ids = kops.topk_score(D_local, q_rep, k=kp)
                else:
                    s, ids = kops.topk_score(D_local, q_rep, k=kp,
                                             block_n=block)
            else:
                s, ids = _scan_topk(D_local, q_rep, kp,
                                    block=65536 if block is None else block,
                                    vma_axes=axes)
            with jax.named_scope("merge"):
                # shard-local ids to global ones (flat linear shard index
                # over the mesh axes), then the pad mask and cut
                base = compat.axis_index(axes) * rows_per
                ids = jnp.where(ids >= 0, ids + base, -1)
                padded = ids >= n_real
                s = jnp.where(padded, -jnp.inf, s)
                ids = jnp.where(padded, -1, ids)
                if pad:
                    s, ids = _topk_merge(s, ids, k)
            # Gather every shard's candidates and merge (1 or 2 stages).
            return _staged_topk_merge(s, ids, k, stages)

        # merged result is replicated by construction; not statically provable
        return compat.shard_map(shard_fn, mesh=self.mesh,
                                in_specs=(P(axes, None), P(None, None)),
                                out_specs=(P(None, None), P(None, None)),
                                check_vma=False)


@jax.jit
def _project_nofold(Q, W, mean):
    """Raw-query projection WITHOUT the scale fold, shared by searches whose
    rows do not agree on one scale (``PagedIndex`` extents, the cascade's
    two resolutions): each folds its own scale inside its own dispatch."""
    return project_queries(Q, W, scale=None, mean=mean)
