"""Index maintenance: live growth, compaction and drift-triggered refit.

Beyond-paper production plumbing for the pruned index. The paper shows the
transform is robust out-of-domain (RQ2) and to small fit samples (RQ3) —
this module turns those findings into operational policy over the mutable
index, ``repro.core.paged.PagedIndex``:

  * ``IndexUpdater.add_documents`` — new documents are rotated with the
    EXISTING ``W_m`` and appended to the open delta extent (no refit, no
    reindex of old docs). Each extent carries its OWN int8 scale, widened
    per append block when needed, so nothing ever clips against the base's
    frozen scale. With a ``store`` attached, every append mirrors durably
    to disk (the quantised bytes on disk are the bytes being served); with
    a ``server`` attached, every append installs the new index atomically
    between in-flight batches (``RetrievalServer.swap_index``).
  * ``drift_score`` — fraction of new-batch embedding energy captured by
    the kept subspace, ``||X W_m||² / ||X||²``, compared to the energy the
    subspace captured at fit time. A ratio near 1 ⇒ the rotation still
    fits (paper RQ2 regime); a falling ratio quantifies when the corpus
    distribution has moved enough to warrant an offline refit.
  * ``scale_divergence`` / ``delta_fraction`` — how far the delta scales
    have widened past the base's, and how many pages live outside the
    base. Either climbing is the compaction signal.
  * ``needs_refit`` — thresholded policy over all three signals.
  * ``compact()`` — seal + promote every delta extent and drain its tail
    pages into the pool: page-pointer swaps plus one fused gather
    dispatch, and on disk one lifecycle-block manifest swap. It keeps
    every extent's scale; ``refit`` is what requantises.
  * ``refit()`` — a fresh PCA fit and a fresh index (fresh corpus-wide
    scale) on the current corpus, replacing the store atomically.
"""
from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import DenseIndex
from repro.core.paged import PagedIndex
from repro.core.pruning import StaticPruner


def _new_rlock():
    """Call-time ``threading.RLock`` lookup for the dataclass default.

    ``default_factory=threading.RLock`` freezes the lock class at module
    import; an instrumented ``threading.RLock`` (see
    ``repro.analysis.lock_sanitizer``) installed later would be ignored
    for every new updater. Resolving at call time keeps construction
    late-bound.
    """
    return threading.RLock()


def _eigval_energy(pruner: StaticPruner) -> float:
    """Reference captured energy from the fitted state alone.

    ``captured_energy`` is an *uncentered* ratio. Uncentered fit:
    ``||D W_m||²/||D||² = Σ_{i≤m} λ_i / Σ λ_i`` (mean is zeros, the
    correction terms vanish). Centered fit: the Gram is ``n·(C + μμᵀ)``,
    so the same ratio gains the mean's energy —
    ``(Σ_{i≤m} λ_i + ||W_mᵀμ||²) / (Σ λ_i + ||μ||²)``. Both exact.
    """
    state = pruner.state
    m = pruner.kept_dims
    lam = np.asarray(state.eigenvalues, np.float64)
    mu = np.asarray(state.mean, np.float64)
    W = np.asarray(state.components, np.float64)[:, :m]
    num = float(lam[:m].sum()) + float(np.sum((W.T @ mu) ** 2))
    den = float(lam.sum()) + float(np.sum(mu ** 2))
    return num / max(den, 1e-30)


def captured_energy(X: jax.Array, pruner: StaticPruner) -> float:
    """||X W_m||^2 / ||X||^2 — energy the kept subspace explains on X."""
    W = pruner.state.components[:, :pruner.kept_dims]
    Xf = X.astype(jnp.float32)
    num = jnp.sum((Xf @ W) ** 2)
    den = jnp.maximum(jnp.sum(Xf ** 2), 1e-30)
    return float(num / den)


@dataclasses.dataclass
class IndexUpdater:
    """Paged pruned index + transform with live growth and compaction.

    ``index`` may be handed in as a bare ``DenseIndex`` (it is paged as
    one base extent, sealing deltas at ``delta_capacity`` rows) or as a
    ``PagedIndex``.

    ``fit_energy`` may be left unset (a directly-constructed updater): the
    reference energy is then derived lazily from the fitted state — for an
    uncentered fit ``||D W_m||²/||D||² = Σ_{i≤m} λ_i / Σ λ_i``, and a
    centered fit adds the mean's energy on both sides (see
    ``_reference_energy``) — exact either way, so no fit-corpus pass is
    needed.

    ``store``: an optional ``IndexStore`` (or path) the updater appends
    through — every delta mutation lands durably so the on-disk artifact
    tracks the in-memory extents bit-for-bit. ``server``: an optional
    ``RetrievalServer`` that receives the new index via ``swap_index``
    after every mutation.
    """

    pruner: StaticPruner
    index: PagedIndex
    fit_energy: float | None = None  # energy on the fit corpus (reference)
    store: object | None = None      # IndexStore | str | None
    server: object | None = None     # RetrievalServer | None
    delta_capacity: int = 4096
    # telemetry
    appended_rows: int = 0
    compactions: int = 0
    # last compaction's cost receipt: {"pages_moved", "pages_freed",
    # "pages_host"} — pointer swaps, the true cost unit (a rows-copied
    # number would claim O(corpus) work compaction never does)
    last_compaction: dict | None = None
    # background-thread failures (compact_async and any future maintenance
    # thread): a swallowed exception is an operational lie — the fleet
    # health check reads this list, so a dead compaction surfaces instead
    # of silently leaving the deltas to grow forever
    background_errors: list = dataclasses.field(default_factory=list)
    _lock: threading.RLock = dataclasses.field(default_factory=_new_rlock,
                                               repr=False, compare=False)

    def __post_init__(self):
        from repro.core.store import IndexStore
        if isinstance(self.store, (str, bytes)) or hasattr(self.store,
                                                           "__fspath__"):
            self.store = IndexStore.open(self.store)
        if isinstance(self.index, DenseIndex):
            self.index = PagedIndex.from_index(
                self.index, seal_rows=self.delta_capacity)
        elif not isinstance(self.index, PagedIndex):
            raise TypeError(
                f"IndexUpdater needs a DenseIndex or PagedIndex, got "
                f"{type(self.index).__name__}; appends over a sharded base "
                f"need paged x sharded storage (ROADMAP queue 2 item 5)")

    @classmethod
    def build(cls, corpus: jax.Array, *, cutoff: float = 0.5,
              quantize_int8: bool = False,
              store_path: str | None = None,
              delta_capacity: int = 4096, page_rows: int = 256,
              pool_pages: int | None = None) -> "IndexUpdater":
        """Fit + build in memory; with ``store_path``, also persist the
        artifact and attach the committed store for durable appends.
        ``pool_pages`` below the corpus page count oversubscribes device
        memory."""
        pruner = StaticPruner(cutoff=cutoff).fit(corpus)
        base = pruner.build_index(corpus, quantize_int8=quantize_int8)
        index = PagedIndex.from_index(base, page_rows=page_rows,
                                      pool_pages=pool_pages,
                                      seal_rows=delta_capacity)
        store = None
        if store_path is not None:
            from repro.core.store import save_index
            store = save_index(store_path, index, pruner=pruner)
        return cls(pruner=pruner, index=index,
                   fit_energy=captured_energy(corpus, pruner), store=store,
                   delta_capacity=delta_capacity)

    @classmethod
    def from_store(cls, store, *, backend: str = "jnp",
                   delta_capacity: int = 4096,
                   pool_pages: int | None = None) -> "IndexUpdater":
        """Rehydrate updater state from a committed artifact (cold start) —
        base AND delta extents, each with its own scale
        (``PagedIndex.load``).

        ``fit_energy`` stays lazy — the fit corpus is not in the store, and
        the eigenvalue identity gives the same reference.
        """
        from repro.core.store import IndexStore
        if not isinstance(store, IndexStore):
            store = IndexStore.open(store)
        index = PagedIndex.load(store, backend=backend,
                                pool_pages=pool_pages,
                                seal_rows=delta_capacity)
        return cls(pruner=store.load_pruner(), index=index,
                   store=store, delta_capacity=delta_capacity)

    # -- incremental growth ------------------------------------------------
    def add_documents(self, new_embs: jax.Array) -> int:
        """Rotate with the existing W_m and append to the open delta.

        Copy-on-write: a NEW index is built, mirrored to the store
        (open/extend/widen ops with the exact quantised bytes), then
        installed into the attached server atomically. Nothing ever clips:
        an int8 delta's scale widens per-dim to fit every appended block
        (requantised from the exact f32 staging — the rewrite is bounded by
        ``seal_rows``). Returns the number of rows appended.
        """
        with self._lock:
            pruner = self.pruner
        # the rotation runs OUTSIDE the lock (device work must not block
        # concurrent telemetry); the append below re-takes it
        pruned = np.asarray(pruner.prune_index(new_embs), np.float32)
        with self._lock:
            new_index, ops = self.index.append_with_ops(pruned)
            self._mirror_ops(ops, new_index)
            self.index = new_index
            self.appended_rows += pruned.shape[0]
            # swap INSIDE the lock: a preempted thread must not install an
            # index an already-completed append/compaction superseded
            if self.server is not None:
                self.server.swap_index(new_index)
        return int(pruned.shape[0])

    def _mirror_ops(self, ops, new_index) -> None:
        """Replay append ops durably. Extents are store segments
        positionally, with base extents a prefix — delta ordinal di is
        extent/segment ``n_base + di``. The mirror finishes with the
        lifecycle-block swap, which may lag the segment ops across a crash
        (the loader reconstructs; ``IndexStore._validate_paged``)."""
        if self.store is None:
            return
        from repro.core.store import paged_manifest_block
        st = new_index.storage
        base_idx = sum(1 for e in st.extents if e.kind == "base")
        names = [v.name for v in self.store.segments()]
        for op in ops:
            kind, di = op[0], op[1]
            if kind == "open":
                _, _, stored, scale = op
                name = self.store.add_delta(scale=scale,
                                            capacity=st.seal_rows)
                names.append(name)
                if stored.shape[0]:
                    self.store.append(stored, segment=name)
            elif kind == "extend":
                _, _, stored = op
                self.store.append(stored, segment=names[base_idx + di])
            else:                                   # widen: bounded rewrite
                _, _, stored, scale = op
                self.store.replace_segment(names[base_idx + di], [stored],
                                           scale=scale)
        self.store.set_paged_state(paged_manifest_block(st))

    # -- telemetry ---------------------------------------------------------
    @property
    def clip_fraction(self) -> float:
        """Always 0.0: per-delta scales widen instead of clipping. Kept as
        an explicit invariant (and for dashboards that tracked it when the
        monolithic updater could only report the damage)."""
        return 0.0

    @property
    def delta_fraction(self) -> float:
        """Fraction of the index living outside the compacted base, counted
        in PAGES (``delta_pages / total_pages``): the unit compaction pays
        in — pointer swaps per page. A rows-over-corpus ratio undercounts
        a delta of many part-filled pages (the fleet auto-compaction
        controller then waits too long), and a capacity-based ratio would
        overcount sealed-but-short extents that cost nothing to promote."""
        with self._lock:
            index = self.index
        pages = index.total_pages
        return index.delta_pages / pages if pages else 0.0

    def scale_divergence(self) -> float:
        """max over deltas of max-dim ratio (delta scale / base scale) —
        how far live data has outgrown the base's quantisation regime.
        1.0 when unquantised or no deltas have widened past the base."""
        with self._lock:
            index = self.index
        exts = index.storage.extents
        base_scale = exts[0].scale if exts else None
        dscales = [e.scale for e in exts if e.kind == "delta"]
        if base_scale is None or not dscales:
            return 1.0
        b = np.asarray(base_scale, np.float64)
        worst = 1.0
        for s in dscales:
            if s is not None:
                worst = max(worst, float(np.max(np.asarray(s,
                                                           np.float64) / b)))
        return worst

    # -- drift policy ------------------------------------------------------
    def _reference_energy(self) -> float:
        with self._lock:
            if self.fit_energy is not None:
                return self.fit_energy
            pruner = self.pruner
        # the device->host transfers inside the eigenvalue identity run
        # UNLOCKED; only the cache fill re-takes the lock (and discards
        # the result if a refit swapped the pruner meanwhile)
        ref = _eigval_energy(pruner)
        with self._lock:
            if self.fit_energy is None and self.pruner is pruner:
                self.fit_energy = ref
            return self.fit_energy if self.fit_energy is not None else ref

    def drift_score(self, new_embs: jax.Array) -> float:
        """1.0 = no drift; < 1.0 = kept subspace explains less energy on the
        new batch than it did on the fit corpus."""
        with self._lock:
            pruner = self.pruner
        ref = self._reference_energy()
        return captured_energy(new_embs, pruner) / max(ref, 1e-12)

    def needs_refit(self, new_embs: jax.Array, threshold: float = 0.9,
                    delta_threshold: float = 0.5,
                    scale_threshold: float = 4.0) -> bool:
        """Compact/refit when the subspace drifted, the deltas hold more
        than ``delta_threshold`` of the corpus, *or* a delta scale has
        widened more than ``scale_threshold``x past the base's — widened
        scales never clip, but they do coarsen the quantisation grid for
        everything in that delta."""
        if self.delta_fraction > delta_threshold:
            return True
        if self.scale_divergence() > scale_threshold:
            return True
        return self.drift_score(new_embs) < threshold

    # -- compaction --------------------------------------------------------
    def compact(self) -> None:
        """Seal + promote every delta extent and drain tail pages into free
        pool slots — pointer swaps plus ONE fused gather dispatch, never a
        corpus rebuild, and every extent keeps its scale (``refit``
        requantises). Cheap enough to run entirely under the lock, so no
        racing append can interleave; on disk it is a single
        lifecycle-block manifest swap (the page bytes already mirrored at
        append time). The attached server receives the new index between
        batches."""
        from repro.core.store import paged_manifest_block
        with self._lock:
            new_index, stats = self.index.compact_pages()
            if self.store is not None:
                self.store.set_paged_state(
                    paged_manifest_block(new_index.storage))
            self.index = new_index
            self.compactions += 1
            self.last_compaction = dict(stats)
            if self.server is not None:
                self.server.swap_index(new_index)

    def compact_async(self) -> threading.Thread:
        """Run ``compact`` off-thread: the serving path keeps dispatching
        against the old index until the compacted one swaps in.

        A crash in the background thread is RECORDED, not swallowed: the
        exception lands in ``background_errors`` (read by ``health()`` and
        the fleet's rollout/auto-compaction health checks), so a dead
        compaction can fail a health probe instead of leaving the deltas
        to grow unboundedly with nobody the wiser."""
        def _run():
            import time as _time
            try:
                self.compact()
            except BaseException as e:   # noqa: BLE001 — recorded, re-raised
                with self._lock:
                    self.background_errors.append(
                        {"op": "compact", "error": repr(e),
                         "time": _time.time()})
                raise
        th = threading.Thread(target=_run, daemon=True)
        th.start()
        return th

    def health(self) -> dict:
        """Maintenance health snapshot: ok iff no background thread has
        died. ``background_errors`` is a copy — callers can't tear it."""
        with self._lock:
            errs = list(self.background_errors)
            compactions = self.compactions
            appended = self.appended_rows
            last = (None if self.last_compaction is None
                    else dict(self.last_compaction))
        return {"ok": not errs, "background_errors": errs,
                "compactions": compactions, "appended_rows": appended,
                "last_compaction": last}

    def refit(self, corpus: jax.Array) -> None:
        """Full offline refit (new rotation) on the current corpus
        distribution — unlike ``compact``, this re-fits ``W_m`` itself and
        requantises under one fresh corpus-wide scale. The new index keeps
        the old one's page geometry, backend and pipeline depth."""
        with self._lock:
            old_index, old_pruner = self.index, self.pruner
        pruner = StaticPruner(cutoff=old_pruner.effective_cutoff).fit(corpus)
        base = pruner.build_index(corpus, quantize_int8=old_index.quantized,
                                  backend=old_index.backend)
        new_index = PagedIndex.from_index(
            base, page_rows=old_index.storage.page_rows,
            seal_rows=old_index.storage.seal_rows,
            backend=old_index.backend, depth=old_index.depth,
            wave_pages=old_index.wave_pages)
        energy = captured_energy(corpus, pruner)
        with self._lock:
            self.pruner, self.index, self.fit_energy = (pruner, new_index,
                                                        energy)
            self.appended_rows = 0
            if self.store is not None:
                # the old artifact is invalid under the new rotation —
                # replace it atomically at the same path
                from repro.core.store import save_index
                self.store = save_index(self.store.path, self.index,
                                        pruner=self.pruner)
            if self.server is not None:
                self.server.swap_index(self.index, pruner=self.pruner)

    def search(self, queries: jax.Array, k: int = 10):
        with self._lock:
            index, pruner = self.index, self.pruner
        return index.search(pruner.transform_queries(queries), k=k)
