"""Dense embedding index with exact (brute-force) top-k search.

This is the FAISS-flat role in the paper's pipeline, built TPU-native:

  * ``DenseIndex``        — single-logical-array index, matmul + top-k.
                            Backend 'jnp' (XLA) or 'pallas' (fused
                            score-and-select scan; see repro.kernels).
  * ``ShardedDenseIndex`` — rows sharded over every mesh device; each shard
                            scans locally, then a tiny global merge over the
                            per-shard top-k (k·chips candidates).
  * ``SegmentedIndex``    — an immutable base segment (dense or sharded)
                            plus growable fixed-capacity ``DeltaSegment``s,
                            each delta with its OWN int8 scale; searched by
                            a cross-segment top-k merge with global doc-id
                            offsets. Appends are copy-on-write and dispatch
                            at the delta's fixed padded capacity (live row
                            count and id offset are traced operands), so a
                            growing index never recompiles in steady state.
  * int8 symmetric quantisation (beyond-paper) composes with PCA pruning:
    index bytes drop by 4x on top of the m/d PCA reduction.

Scores are always accumulated in fp32 regardless of index dtype.
"""
from __future__ import annotations

import contextlib
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
        f"load it with SegmentedIndex.load, not a flat index loader")


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
    """Bucket width for ``_scan_topk``'s per-strip select, 0 for a direct
    ``top_k`` over the strip.

    Bucketing pays when the k buckets it keeps are a small share of the
    strip (k·W ≤ block / 8): the served k=10 over 65,536-row strips keeps
    1,280 of 65,536 columns. A large k (the cascade's coarse scan) or a
    strip that is not a whole number of buckets selects directly.
    """
    if block % BUCKET == 0 and 8 * k * BUCKET <= block:
        return BUCKET
    return 0


def _bucket_candidates(s3: jax.Array, bmax: jax.Array, at: jax.Array,
                       k: int) -> tuple[jax.Array, jax.Array]:
    """The (B, k·W) scores and ids of the k buckets with the largest maxima.

    ``s3`` is the strip as (B, block / W, W) buckets of W contiguous rows,
    ``bmax`` each bucket's max. A strip entry outside the kept buckets is
    preceded, in (score desc, id asc) order, by the max of each of the k
    kept ones (``top_k`` breaks equal maxima towards the lower bucket), so
    the strip's top-k lies in them. Kept buckets go in ascending order, so
    candidate ids ascend and ``top_k``'s first-occurrence tie-break sees
    them as it sees the strip.
    """
    B, _, W = s3.shape
    _, top = jax.lax.top_k(bmax, k)                            # (B, k)
    top = jnp.sort(top, axis=1)
    cs = jnp.take_along_axis(s3, top[:, :, None], axis=1)      # (B, k, W)
    ci = at + top[:, :, None] * W + jnp.arange(W, dtype=jnp.int32)
    return cs.reshape(B, k * W), ci.reshape(B, k * W)


@partial(jax.jit, static_argnames=("k", "block", "vma_axes", "guard"))
def _scan_topk(D: jax.Array, Q: jax.Array, k: int, block: int = 65536,
               vma_axes: tuple[str, ...] | None = None, guard: str = "row"
               ) -> tuple[jax.Array, jax.Array]:
    """Blocked exact search: stream row blocks of D, keep a running top-k.

    Never materialises the full (B, n) score matrix — the jnp analogue of
    the Pallas fused kernel, and the oracle it is tested against. Mirrors
    the kernel's structure:

      * the index blocks keep their storage dtype (int8 stays int8 in the
        scan carry's xs) and each is scored by ``score_rows`` as the
        rows' dtype selects: f32 rows by an f32 matmul at ``HIGHEST``;
        int8 or bf16 rows, which bf16 holds exactly, convert to bf16 and
        meet the query's three-term bf16 split (``query_terms``, made
        once before the loop) in one matmul with f32 accumulation — no
        full-index shadow copy, no strip in f32;
      * exact bucketed select (``_select_plan``: k·W ≤ block / 8 with
        W = 128): the strip's per-bucket maxima (B, block / W) pick the k
        buckets that must hold its top-k; one ``top_k`` over the running
        list followed by those buckets' (B, k·W) scores merges them —
        never a sort over the whole strip. Otherwise ``top_k`` over the
        strip alone, then a tiny (B, 2k) merge with the running list.
        Both give the same scores and ids, ties included;
      * block-skip guard: a strip that cannot improve the running top-k
        skips selection entirely under ``lax.cond``; the strip's max is
        the max of its bucket maxima, read once. ``guard="row"``
        (default): row b improves iff ``max(s[b]) > min(run_s[b])``; the
        strip is skipped iff *no* row improves (a strictly weaker skip
        condition than the legacy ``guard="batch"`` global compare, so
        mixed batches skip more often, never less) and the merge writes
        back only improving rows. Results are bit-identical either way:
        for a non-improving row the merge is already a no-op — strict
        guard, and ascending-id strips lose first-occurrence ties.
        Skipping on equality is exact for the same ascending-id reason.

    The index is never padded or copied: when ``block`` does not divide
    n, the last strip slides back to end at row n and masks the rows the
    previous strip already scored.

    ``vma_axes``: when called inside shard_map over those axes, the scan
    carry must be marked varying (compat.mark_varying) to typecheck.

    Device scopes (``jax.named_scope``: each op's HLO ``op_name`` holds
    ``/score/`` or ``/select/``, names a profile can follow across
    refactors): ``score`` is the query's split (once, before the loop)
    and the strip's slice, conversion, matmul and mask;
    ``select`` the improvement test and the whole guarded merge, per-strip
    ``top_k`` included. On the bucketed path ``select`` nests ``bucket``
    (the bucket maxima) and ``refine`` (the guarded merge: bucket
    ``top_k``, gather, merge).
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

    def body(carry, start):
        bs, bi = carry
        with jax.named_scope("score"):
            at = jnp.minimum(start, n - block)
            blk = jax.lax.dynamic_slice_in_dim(D, at, block, axis=0)
            s = score_rows(qt, blk)                              # (B, block)
            ids = at + jnp.arange(block, dtype=jnp.int32)[None, :]
            s = jnp.where(ids >= start, s, -jnp.inf)

        with jax.named_scope("select"):
            if W:
                with jax.named_scope("bucket"):
                    # one (B, block / W, W) view for the maxima and the
                    # gather: the TPU relayouts the strip once
                    s = s.reshape(B, block // W, W)
                    smax = s.max(axis=2)                         # (B, nb)
            else:
                smax = s
            imp = jnp.max(smax, axis=1) > jnp.min(bs, axis=1)    # (B,)

            def merge(carry_in):
                bs0, bi0 = carry_in
                if W:
                    ss, gi = _bucket_candidates(s, smax, at, k)
                else:
                    ss, si = jax.lax.top_k(s, kk)                # (B, kk)
                    gi = at + si.astype(jnp.int32)
                # running list first: at -inf ties its (-1) pads win the
                # first-occurrence tie-break, matching the kernel's pads
                cs = jnp.concatenate([bs0, ss], axis=1)
                ci = jnp.concatenate([bi0, gi], axis=1)
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
                can_improve = jnp.max(smax) > jnp.min(bs)
            with (jax.named_scope("refine") if W
                  else contextlib.nullcontext()):
                return jax.lax.cond(can_improve, merge, lambda c: c,
                                    (bs, bi)), None

    init = (jnp.full((B, k), -jnp.inf, jnp.float32), jnp.full((B, k), -1, jnp.int32))
    if vma_axes:
        init = compat.mark_varying(init, vma_axes)
    starts = jnp.arange(nblocks, dtype=jnp.int32) * block
    (scores, ids), _ = jax.lax.scan(body, init, starts)
    return scores, ids


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


# ---------------------------------------------------------------------------
# Segmented live index: immutable base + growable delta segments
# ---------------------------------------------------------------------------


@jax.jit
def _project_nofold(Q, W, mean):
    """Shared raw-query projection for segmented search: center + project,
    WITHOUT any scale fold — per-segment scales fold inside each segment's
    own dispatch (the segments no longer agree on one scale)."""
    return project_queries(Q, W, scale=None, mean=mean)


@partial(jax.jit, static_argnames=("k",))
def _delta_topk(D, scale, Q, n_valid, offset, k: int):
    """Top-k over one fixed-capacity delta segment, in one compiled shape.

    ``D`` is the (capacity, m) segment in its storage dtype — rows at and
    beyond the live count are zero padding. ``n_valid`` (live rows) and
    ``offset`` (this segment's global doc-id base) are *traced* operands,
    so appends that grow the live count never trigger a recompile: the
    serving hot path dispatches the same compiled computation whether the
    delta holds 1 row or its full capacity. Padding rows are masked to
    (-inf, -1) before selection, exactly like the scan's init sentinels.
    """
    q = jnp.atleast_2d(Q).astype(jnp.float32)
    if scale is not None:
        q = q * scale[None, :]
    cap = D.shape[0]
    s = score_rows(query_terms(q, D.dtype), D)               # (B, cap) f32
    ids = jnp.arange(cap, dtype=jnp.int32)[None, :]
    live = ids < n_valid
    s = jnp.where(live, s, -jnp.inf)
    gids = jnp.broadcast_to(jnp.where(live, ids + offset, -1), s.shape)
    ss, si = jax.lax.top_k(s, min(k, cap))
    return ss, jnp.take_along_axis(gids, si, axis=-1)


@jax.jit
def _delta_update(D, block, start):
    """Patch appended rows into a delta's fixed-capacity buffer — O(rows)
    per append instead of re-uploading the whole capacity. ``start`` is
    traced, so steady-state appends of one block size compile once."""
    return jax.lax.dynamic_update_slice(D, block, (start, 0))


@partial(jax.jit, donate_argnums=0)
def _fill_rows(D, block, start):
    """``_delta_update`` into a buffer no one else holds (a load's): ``D``
    is donated, so the rows land in place instead of in a copy."""
    return jax.lax.dynamic_update_slice(D, block, (start, 0))


@partial(jax.jit, static_argnames=("k",))
def _concat_topk(parts_s, parts_i, k: int):
    s = jnp.concatenate(parts_s, axis=1)
    ids = jnp.concatenate(parts_i, axis=1)
    return _topk_merge(s, ids, k)


def merge_segment_topk(candidates, k: int):
    """Merge per-segment (B, k_i) top-k candidate lists (global ids already
    applied) into the global (B, k) top-k.

    Segments must be passed in ascending id-offset order (base first, then
    deltas): ``lax.top_k`` keeps the *first* occurrence among equal scores,
    so concatenation order reproduces the monolithic index's lowest-id
    tie-break — the same invariant ``_staged_topk_merge`` relies on for its
    row-major shard gather, which makes the segmented search bit-identical
    to a monolithic scan over the concatenated corpus.
    """
    parts_s = tuple(s for s, _ in candidates)
    parts_i = tuple(i for _, i in candidates)
    if len(parts_s) == 1:
        return parts_s[0], parts_i[0]
    return _concat_topk(parts_s, parts_i, k)


def segment_jit_cache_sizes() -> dict:
    """Per-jit compiled-variant counts for every jit the segmented search
    path can touch — the diagnosable form of ``segment_jit_cache_size``
    (a failure names the function that recompiled)."""
    from repro.core import cascade, paged  # lazy: both import this module
    sizes = {fn.__wrapped__.__name__: fn._cache_size()
             for fn in (_delta_topk, _concat_topk, _project_nofold,
                        _scan_topk, _dense_search_projected, _delta_update)}
    sizes.update(cascade._jit_cache_sizes())
    sizes.update(paged._jit_cache_sizes())
    return sizes


def segment_jit_cache_size() -> int:
    """Total compiled-variant count across every jit the segmented search
    path can touch — the soak tests pin this to ZERO growth during
    steady-state appends (the whole point of fixed-capacity deltas)."""
    return sum(segment_jit_cache_sizes().values())


@dataclasses.dataclass(frozen=True)
class DeltaSegment:
    """One growable segment: fixed-capacity storage + its own scale.

    ``vectors`` always has ``capacity`` rows (zeros beyond ``n_real``) so
    every search dispatches one compiled shape. ``raw`` keeps the exact f32
    rows appended so far — the requantisation source when an append widens
    the scale (re-quantising from f32 is exact; from int8 it would drift by
    up to half an old LSB). After a cold start from disk ``raw`` is the
    dequantised reconstruction — the best source that survives a restart.
    """

    vectors: jax.Array                 # (capacity, m), storage dtype
    n_real: int
    scale: jax.Array | None            # per-dim dequant scale (int8 deltas)
    raw: np.ndarray                    # (n_real, m) f32 requant source

    @property
    def capacity(self) -> int:
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

    @staticmethod
    def quantise(raw: np.ndarray, scale: np.ndarray) -> np.ndarray:
        from repro.core.quantization import quantize_with_scale
        return quantize_with_scale(raw, scale)

    @classmethod
    def build(cls, rows: np.ndarray, capacity: int, *, quantize: bool,
              dtype) -> "DeltaSegment":
        """Open a delta from its first f32 rows; int8 deltas get a FRESH
        per-dim scale fitted to these rows — never the base's frozen one."""
        from repro.core.quantization import scale_for
        raw = np.ascontiguousarray(np.asarray(rows, np.float32))
        if raw.shape[0] > capacity:
            raise ValueError(f"{raw.shape[0]} rows exceed delta capacity "
                             f"{capacity}")
        if quantize:
            scale = scale_for(raw)
            stored = cls.quantise(raw, scale)
        else:
            scale = None
            stored = raw.astype(np.dtype(dtype))
        pad = capacity - stored.shape[0]
        if pad:
            stored = np.concatenate(
                [stored, np.zeros((pad, stored.shape[1]), stored.dtype)])
        return cls(vectors=jnp.asarray(stored), n_real=raw.shape[0],
                   scale=None if scale is None else jnp.asarray(scale),
                   raw=raw)

    def extend(self, rows: np.ndarray
               ) -> tuple["DeltaSegment", bool, np.ndarray]:
        """Copy-on-write append of f32 rows.

        Returns ``(new segment, widened, stored)`` where ``stored`` is the
        host copy of what changed in storage dtype — just the new rows in
        the common case, the whole requantised segment when the scale
        widened (the durable mirror appends/rewrites exactly those bytes).

        int8 deltas widen their per-dim scale whenever a new row's absmax
        exceeds the representable range — the whole segment requantises
        from its exact f32 staging, so nothing ever clips. That rewrite is
        bounded by the segment's capacity (the reason the scale problem is
        tractable per segment and was not on the monolithic index). The
        common non-widened append touches only O(rows): the new rows
        quantise under the unchanged scale and patch into the existing
        device buffer with a ``dynamic_update_slice`` (a new immutable
        array — in-flight searches keep the old one).
        """
        rows = np.ascontiguousarray(np.asarray(rows, np.float32))
        if self.n_real + rows.shape[0] > self.capacity:
            raise ValueError("extend beyond delta capacity — seal and open "
                             "a new delta instead")
        from repro.core.quantization import scale_for
        raw = np.concatenate([self.raw, rows])
        if self.scale is not None:
            old = np.asarray(self.scale)
            need = scale_for(rows)
            scale = np.maximum(old, need).astype(np.float32)
            if bool((scale > old).any()):          # widen: bounded rewrite
                stored = self.quantise(raw, scale)
                full = np.concatenate(
                    [stored, np.zeros((self.capacity - stored.shape[0],
                                       stored.shape[1]), stored.dtype)]) \
                    if stored.shape[0] < self.capacity else stored
                return dataclasses.replace(
                    self, vectors=jnp.asarray(full), n_real=raw.shape[0],
                    scale=jnp.asarray(scale), raw=raw), True, stored
            new_rows = self.quantise(rows, old)
        else:
            new_rows = rows.astype(self.vectors.dtype)
        vectors = _delta_update(self.vectors, jnp.asarray(new_rows),
                                jnp.int32(self.n_real))
        return dataclasses.replace(
            self, vectors=vectors, n_real=raw.shape[0],
            raw=raw), False, new_rows


def rehydrate_delta(view, delta_capacity: int) -> DeltaSegment:
    """Rebuild one ``DeltaSegment`` from a store view (a main delta OR a
    persisted coarse-resolution delta): the stored quantised bytes become
    the served bytes bit-for-bit, padded to the stored capacity; ``raw``
    is the dequantised reconstruction — the best requant source that
    survives a restart."""
    rows = view.read_rows(0, view.n)
    s = view.scale()
    if s is not None:
        raw = rows.astype(np.float32) * s[None, :].astype(np.float32)
    else:
        raw = rows.astype(np.float32)
    cap = int(view.capacity) if view.capacity else max(delta_capacity,
                                                       view.n)
    stored = np.zeros((cap, view.dim), rows.dtype)
    stored[:view.n] = rows
    return DeltaSegment(vectors=jnp.asarray(stored), n_real=view.n,
                        scale=None if s is None else jnp.asarray(s),
                        raw=np.ascontiguousarray(raw))


@dataclasses.dataclass(frozen=True)
class SegmentedIndex:
    """Immutable segment set: [base] + deltas, searched as one index.

    The base is a committed ``DenseIndex`` or ``ShardedDenseIndex`` (the
    offline PCA-pruned artifact); deltas absorb live corpus growth. Every
    mutation (``append``) returns a NEW ``SegmentedIndex`` sharing the
    untouched segments — the running ``RetrievalServer`` swaps whole
    segment sets atomically between batches, and in-flight batches keep
    the old set alive until their replies post.

    Search = per-segment top-k (each segment folds its OWN scale) merged by
    ``merge_segment_topk`` with global id offsets (base rows first, deltas
    in open order). When every segment shares one scale the result is
    bit-identical to a monolithic index over the concatenated corpus; with
    mixed scales, ids/ordering are exactly the top-k of the per-segment
    dequantised scores.
    """

    base: DenseIndex | ShardedDenseIndex
    deltas: tuple[DeltaSegment, ...] = ()
    delta_capacity: int = 4096

    # -- construction -------------------------------------------------------
    @classmethod
    def from_index(cls, base, *, delta_capacity: int = 4096
                   ) -> "SegmentedIndex":
        return cls(base=base, deltas=(), delta_capacity=delta_capacity)

    @classmethod
    def load(cls, store, *, mesh: Mesh | None = None,
             backend: Backend = "jnp", merge: Merge = "flat",
             delta_capacity: int = 4096) -> "SegmentedIndex":
        """Load a (possibly segmented) artifact: segment 0 becomes the base
        (sharded over ``mesh`` when given), every delta segment is
        rehydrated at its stored capacity with its own scale. A pre-segment
        artifact loads as a single base — full backward compatibility."""
        from repro.core.store import IndexStore
        if isinstance(store, (str, os.PathLike)):
            store = IndexStore.open(store)
        views = store.segments()
        base_view = views[0]
        if mesh is not None:
            base = ShardedDenseIndex.load(base_view, mesh, backend=backend,
                                          merge=merge)
        else:
            base = DenseIndex.load(base_view, backend=backend)
        deltas = [rehydrate_delta(v, delta_capacity) for v in views[1:]]
        return cls(base=base, deltas=tuple(deltas),
                   delta_capacity=delta_capacity)

    # -- shape --------------------------------------------------------------
    @property
    def n(self) -> int:
        return self.base.n + sum(d.n_real for d in self.deltas)

    @property
    def dim(self) -> int:
        return self.base.dim

    @property
    def nbytes(self) -> int:
        return self.base.nbytes + sum(d.nbytes for d in self.deltas)

    @property
    def quantized(self) -> bool:
        return self.base.scale is not None

    @property
    def delta_rows(self) -> int:
        return sum(d.n_real for d in self.deltas)

    @property
    def storage_dtype(self):
        return self.base.vectors.dtype

    # -- growth (copy-on-write) --------------------------------------------
    def append(self, rows) -> "SegmentedIndex":
        new, _ = self.append_with_ops(rows)
        return new

    def append_with_ops(self, rows) -> tuple["SegmentedIndex", list]:
        """Append f32 rows (already PCA-pruned to this index's dim).

        Returns ``(new_index, ops)`` where ``ops`` records what changed for
        a durable mirror (``IndexStore``), in order:
          ("open",   di, stored_rows, scale)  — new delta with first rows
          ("extend", di, stored_rows)         — rows appended, scale kept
          ("widen",  di, stored_all,  scale)  — scale widened: the delta's
                                                full requantised contents
        ``stored_*`` are in storage dtype (int8 already quantised), exactly
        the bytes the in-memory index serves — disk and memory stay
        bit-identical.
        """
        rows = np.atleast_2d(np.asarray(rows, np.float32))
        if rows.shape[1] != self.dim:
            raise ValueError(f"append expects (rows, {self.dim}), got "
                             f"{tuple(rows.shape)}")
        quantize = self.quantized
        deltas = list(self.deltas)
        ops: list = []
        pos = 0
        while pos < rows.shape[0]:
            if deltas and deltas[-1].n_real < deltas[-1].capacity:
                di = len(deltas) - 1
                seg = deltas[di]
                take = min(rows.shape[0] - pos, seg.capacity - seg.n_real)
                block = rows[pos:pos + take]
                seg, widened, stored = seg.extend(block)
                deltas[di] = seg
                if widened:
                    ops.append(("widen", di, stored, np.asarray(seg.scale)))
                else:
                    ops.append(("extend", di, stored))
            else:
                di = len(deltas)
                take = min(rows.shape[0] - pos, self.delta_capacity)
                block = rows[pos:pos + take]
                seg = DeltaSegment.build(block, self.delta_capacity,
                                         quantize=quantize,
                                         dtype=self.storage_dtype)
                deltas.append(seg)
                ops.append(("open", di, np.asarray(seg.vectors[:seg.n_real]),
                            None if seg.scale is None
                            else np.asarray(seg.scale)))
            pos += take
        return dataclasses.replace(self, deltas=tuple(deltas)), ops

    # -- search -------------------------------------------------------------
    def _merged_topk(self, q: jax.Array, k: int):
        k = min(k, max(self.n, 1))
        parts = [self.base.search(q, k=k)]
        off = self.base.n
        for d in self.deltas:
            parts.append(_delta_topk(d.vectors, d.scale, q,
                                     jnp.int32(d.n_real), jnp.int32(off), k))
            off += d.n_real
        return merge_segment_topk(parts, k)

    def search(self, queries: jax.Array, k: int = 10
               ) -> tuple[jax.Array, jax.Array]:
        q = jnp.atleast_2d(queries).astype(jnp.float32)
        return self._merged_topk(q, k)

    def search_projected(self, queries: jax.Array, components: jax.Array,
                         k: int = 10, *, mean: jax.Array | None = None
                         ) -> tuple[jax.Array, jax.Array]:
        """Raw-query search: one shared projection dispatch (no scale fold —
        the segments don't share one), then per-segment fold+scan+merge."""
        q = _project_nofold(jnp.atleast_2d(queries),
                            jnp.asarray(components), mean)
        return self._merged_topk(q, k)
