"""On-disk index artifact store: the paper's offline object, made durable.

The whole point of static pruning is that it is *query independent and
executed offline* — the deliverable is a reusable artifact, not a warm
process. This module gives that artifact a versioned on-disk layout:

    <dir>/
      manifest.json          # version, n, dim, logical dtype, chunk list,
                             # pca/scale file names, free-form meta
      pca.npz                # PCAState (W, Λ, mean) — save_pca format
      scale.npy              # per-dim int8 dequant scale (int8 stores only)
      vectors_000000.npy     # row chunk 0
      vectors_000001.npy     # row chunk 1 ...

Durability reuses the checkpoint module's commit protocol: everything is
written into ``<dir>.tmp`` with every blob fsynced, then the directory is
atomically renamed into place and the parent fsynced — a crashed build can
never be mistaken for a committed artifact, and ``IndexStore.open``
validates the manifest against the blobs it names (version, chunk
presence, per-chunk shape, row-count sum) so a tampered or partially
copied directory is rejected loudly.

Appends to a *committed* store (incremental corpus growth through
``IndexUpdater``) use a blob-then-manifest protocol: the new chunk is
written and fsynced first, then the manifest is atomically replaced
(``os.replace`` + dir fsync). A crash between the two leaves an orphan
blob the manifest never names — still a valid store.

**Segments.** A live store may carry a ``segments`` list in its manifest:
segment 0 is the immutable base (the offline PCA-pruned artifact), later
entries are growable *delta* segments, each with the chunked-blob format
above plus its OWN ``scale_file`` (per-segment int8 scale — the fix for
the frozen-scale clip problem) and a ``capacity`` (the rows at which the
delta seals). A ``PagedIndex`` store maps extent i to segment i and adds
a ``paged`` block (page geometry + extent lifecycle). The top-level ``n``/``chunks``/``scale_file`` fields
are always the derived global view (total rows, all chunks in id order,
the base's scale), so a pre-segment manifest IS a valid single-base
segmented store — ``IndexStore.open`` on an old artifact exposes exactly
one base segment, and old artifacts round-trip untouched. Segment
mutations (``add_delta`` / ``append`` / ``replace_segment``) all follow
the blob-then-manifest-swap protocol; whole-store replacement (a refit
re-saving at the same path) reuses ``checkpoint.manager.commit_dir``.

**Resolutions.** A manifest may also carry a ``resolutions`` list: extra
*coarse* views of the SAME rows at a smaller width — the leading
``m < dim`` PCA columns (dims nest, so no second projection state exists),
usually re-quantised int8 with their own scale. Each entry reuses the
chunked-blob layout (``chunks`` + optional ``scale_file`` + its own
``dtype``) and covers exactly the immutable BASE segment's rows. A
paged cascade's coarse DELTA extents may ride along in the entry's
``deltas`` list (same per-delta layout as the main segments: exact
quantised rows + own scale + capacity), so a reload serves the very bytes
that were serving before instead of requantising from the full deltas;
a store whose main deltas outgrow the persisted coarse view falls back to
re-derivation at load. ``open`` refuses a resolution whose row count
disagrees with the base or whose m does not nest strictly inside ``dim``
— a mismatched pair would silently rescore the wrong rows.

Reads are host-streamed: chunks are memory-mapped (``np.load(mmap_mode=
'r')``), so assembling a device-resident index never needs a second full
host copy — ``DenseIndex.load`` copies one chunk at a time to device, and
``ShardedDenseIndex.load`` materialises one *shard* at a time on its
target device and assembles the global array with
``jax.make_array_from_single_device_arrays``.

bfloat16 has no native ``.npy`` encoding; bf16 chunks are stored as raw
``uint16`` views and re-viewed on load (the manifest keeps the logical
dtype).
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Iterator, Sequence

import numpy as np

from repro.checkpoint.manager import commit_dir, fsync_dir, fsync_file, write_json_fsync

FORMAT_VERSION = 1
MANIFEST = "manifest.json"
PCA_FILE = "pca.npz"
SCALE_FILE = "scale.npy"

# logical dtypes with no native .npy encoding -> raw storage view
_STORAGE_VIEW = {"bfloat16": np.uint16}


class IndexStoreError(RuntimeError):
    """A store directory is missing, corrupted, or inconsistent."""


def save_index(path: str, index, *, pruner=None, meta: dict | None = None,
               chunk_rows: int = 262144) -> "IndexStore":
    """Persist an already-built ``DenseIndex``/``ShardedDenseIndex``.

    Rows are copied device→host one ``chunk_rows`` slice at a time, so the
    host transient is O(chunk); only the logical ``index.n`` rows are
    written (a sharded index's device-padding rows are dropped — the load
    path re-synthesises them for whatever mesh it targets). Pass the fitted
    ``pruner`` to persist the PCA state alongside (required for
    ``IndexStore.load_pruner`` / ``serve --load-index`` to transform
    queries).
    """
    import numpy as _np
    from repro.core.cascade import CascadeIndex
    from repro.core.paged import PagedIndex
    if isinstance(index, PagedIndex):
        return save_paged_index(path, index, pruner=pruner, meta=meta,
                                chunk_rows=chunk_rows)
    if isinstance(index, CascadeIndex):
        # full resolution commits through the normal (possibly paged)
        # path; the coarse base rides along as a `resolutions` entry, so
        # one artifact round-trips the whole cascade via CascadeIndex.load.
        # A paged coarse side persists its delta extents too (exact
        # quantised bytes + per-extent scales), so a load adopts them
        # bit-for-bit instead of requantising from the full deltas.
        store = save_index(path, index.full, pruner=pruner, meta=meta,
                           chunk_rows=chunk_rows)
        coarse = index.coarse
        if hasattr(coarse, "storage"):
            # paged coarse side: extent 0 is the resolution base, later
            # extents persist as resolution deltas — bytes gathered
            # straight off the page tiers
            cst = coarse.storage
            exts = cst.extents
            base_rows = (cst.extent_rows(0) if exts
                         else _np.zeros((0, cst.dim), cst.np_dtype))
            base_scale = exts[0].scale if exts else None
            coarse_deltas = [
                {"rows": cst.extent_rows(i),
                 "scale": None if e.scale is None else _np.asarray(e.scale),
                 "capacity": cst.seal_rows}
                for i, e in enumerate(exts) if i > 0]
        else:
            base_rows = _np.asarray(coarse.vectors[:coarse.n])
            base_scale = coarse.scale
            coarse_deltas = []
        store.add_resolution(
            base_rows,
            scale=None if base_scale is None else _np.asarray(base_scale),
            chunk_rows=chunk_rows, deltas=coarse_deltas)
        return store
    writer = IndexStoreWriter(path)
    with writer:
        if pruner is not None:
            writer.put_pca(pruner.state)
        if index.scale is not None:
            writer.set_scale(_np.asarray(index.scale))
        v = index.vectors
        n = index.n   # logical rows: excludes sharded device padding
        for start in range(0, n, chunk_rows):
            writer.append(_np.asarray(v[start:min(start + chunk_rows, n)]))
        info = {} if pruner is None else dict(
            kept_dims=int(pruner.kept_dims),
            source_dim=int(pruner.state.d),
            cutoff=float(pruner.effective_cutoff),
            centered=bool(pruner.state.centered))
        info["quantize_int8"] = index.scale is not None
        info.update(meta or {})
        return writer.commit(meta=info)


def paged_manifest_block(storage) -> dict:
    """The ``paged`` manifest entry for a ``PagedIndexStorage``: page
    geometry plus per-extent lifecycle state (kind/sealed). The page map
    itself is positional — extent i's rows are store segment i's rows,
    paged into ``page_rows``-row pages ascending — so the block stays tiny
    and every byte is validated through the existing segment machinery."""
    return {"page_rows": int(storage.page_rows),
            "seal_rows": int(storage.seal_rows),
            "extents": [{"kind": e.kind, "sealed": bool(e.sealed),
                         "n": int(e.n_rows)} for e in storage.extents]}


def save_paged_index(path: str, index, *, pruner=None,
                     meta: dict | None = None,
                     chunk_rows: int = 262144) -> "IndexStore":
    """Persist a ``PagedIndex``: one store segment per extent (page-granular
    chunks — every blob boundary is page-aligned) plus the ``paged``
    manifest block. Bytes are gathered straight off the page tiers
    (pool/tail/host alike), so the artifact is bit-identical to what was
    serving; the final ``set_paged_state`` manifest swap is the commit
    point for the lifecycle metadata."""
    import numpy as _np
    st = index.storage
    R = st.page_rows
    # page-align the chunking: whole pages per blob, never a split page
    chunk_rows = max(chunk_rows // R, 1) * R
    exts = st.extents
    writer = IndexStoreWriter(path)
    with writer:
        if pruner is not None:
            writer.put_pca(pruner.state)
        base_scale = exts[0].scale if exts else None
        if base_scale is not None:
            writer.set_scale(_np.asarray(base_scale))
        if exts:
            rows = st.extent_rows(0)
            for s in range(0, rows.shape[0], chunk_rows):
                writer.append(rows[s:s + chunk_rows])
        info = {} if pruner is None else dict(
            kept_dims=int(pruner.kept_dims),
            source_dim=int(pruner.state.d),
            cutoff=float(pruner.effective_cutoff),
            centered=bool(pruner.state.centered))
        info["quantize_int8"] = st.quantized
        info.update(meta or {})
        store = writer.commit(meta=info)
    for ei in range(1, len(exts)):
        e = exts[ei]
        name = store.add_delta(
            scale=None if e.scale is None else _np.asarray(e.scale),
            capacity=st.seal_rows)
        rows = st.extent_rows(ei)
        for s in range(0, rows.shape[0], chunk_rows):
            store.append(rows[s:s + chunk_rows], segment=name)
    store.set_paged_state(paged_manifest_block(st))
    return store


def _as_numpy_dtype(logical: str):
    if logical in _STORAGE_VIEW:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, logical))
    return np.dtype(logical)


def _logical_dtype_name(arr: np.ndarray) -> str:
    return arr.dtype.name


def _write_chunk(path: str, arr: np.ndarray) -> None:
    view = _STORAGE_VIEW.get(arr.dtype.name)
    np.save(path, arr.view(view) if view is not None else arr)
    fsync_file(path)


def _read_chunk(path: str, logical: str, mmap: bool = True) -> np.ndarray:
    arr = np.load(path, mmap_mode="r" if mmap else None)
    view = _STORAGE_VIEW.get(logical)
    return arr.view(_as_numpy_dtype(logical)) if view is not None else arr


def _read_chunk_validated(store_path: str, fpath: str,
                          logical: str) -> np.ndarray:
    """``_read_chunk`` for validate(): a blob whose payload is shorter
    than its npy header promises (a torn write — crash mid-rollout or
    mid-copy) must surface as an IndexStoreError diagnosis, not a raw
    mmap/np.load failure."""
    try:
        return _read_chunk(fpath, logical)
    except IndexStoreError:
        raise
    except Exception as e:
        raise IndexStoreError(
            f"{store_path}: chunk {os.path.basename(fpath)} is truncated "
            f"or unreadable ({e}) — partial artifact rejected") from e


def _read_rows_from_chunks(path: str, chunks: list, logical: str, dim: int,
                           total: int, start: int, stop: int) -> np.ndarray:
    """Materialise rows [start, stop) of a chunk list — host O(stop-start)."""
    if not 0 <= start <= stop <= total:
        raise ValueError(f"row range [{start}, {stop}) outside [0, {total})")
    out = np.empty((stop - start, dim), _as_numpy_dtype(logical))
    pos = 0          # global row index at the current chunk's head
    filled = 0
    for c in chunks:
        rows = c["rows"]
        lo, hi = max(start, pos), min(stop, pos + rows)
        if lo < hi:
            chunk = _read_chunk(os.path.join(path, c["file"]), logical)
            out[filled:filled + (hi - lo)] = chunk[lo - pos:hi - pos]
            filled += hi - lo
        pos += rows
        if pos >= stop:
            break
    return out


@dataclasses.dataclass
class SegmentView:
    """Read handle on one segment of a (possibly pre-segment) store.

    Duck-types the slice of the ``IndexStore`` read API the index loaders
    use (``n``/``dim``/``dtype``/``iter_chunks``/``read_rows``/``scale``),
    so ``DenseIndex.load`` / ``ShardedDenseIndex.load`` work unchanged on a
    single segment (a cascade's coarse resolution loads this way). Row
    indices are segment-local; ``offset`` is the segment's
    global doc-id base.
    """

    store_path: str
    name: str
    kind: str                      # "base" | "delta"
    entry: dict                    # manifest segment entry (shared ref)
    offset: int                    # global row offset of this segment
    dim: int
    dtype_name: str

    @property
    def n(self) -> int:
        return int(self.entry["n"])

    @property
    def dtype(self) -> np.dtype:
        return _as_numpy_dtype(self.dtype_name)

    @property
    def capacity(self) -> int | None:
        c = self.entry.get("capacity")
        return None if c is None else int(c)

    def iter_chunks(self, mmap: bool = True) -> Iterator[np.ndarray]:
        for c in self.entry["chunks"]:
            yield _read_chunk(os.path.join(self.store_path, c["file"]),
                              self.dtype_name, mmap=mmap)

    def read_rows(self, start: int, stop: int) -> np.ndarray:
        return _read_rows_from_chunks(self.store_path, self.entry["chunks"],
                                      self.dtype_name, self.dim, self.n,
                                      start, stop)

    def scale(self) -> np.ndarray | None:
        f = self.entry.get("scale_file")
        if f is None:
            return None
        return np.load(os.path.join(self.store_path, f))


class IndexStoreWriter:
    """Streaming writer: append row chunks, then commit atomically.

    Peak host memory is one chunk — nothing is buffered across ``append``
    calls. ``dim``/``dtype`` are inferred from the first chunk and enforced
    thereafter. Usable as a context manager (aborts on exception).
    """

    def __init__(self, path: str):
        self.path = str(path)
        self.tmp = self.path + ".tmp"
        if os.path.exists(self.tmp):
            shutil.rmtree(self.tmp)
        os.makedirs(self.tmp)
        self._chunks: list[dict] = []
        self._n = 0
        self._dim: int | None = None
        self._dtype: str | None = None
        self._has_pca = False
        self._has_scale = False
        self._committed = False

    # -- content -----------------------------------------------------------
    def put_pca(self, state) -> None:
        """Persist the fitted PCAState alongside the vectors."""
        from repro.core import pca as _pca
        _pca.save_pca(os.path.join(self.tmp, PCA_FILE), state)
        fsync_file(os.path.join(self.tmp, PCA_FILE))
        self._has_pca = True

    def set_scale(self, scale: np.ndarray) -> None:
        """Per-dim dequant scale for int8 stores."""
        scale = np.asarray(scale, np.float32)
        path = os.path.join(self.tmp, SCALE_FILE)
        np.save(path, scale)
        fsync_file(path)
        self._has_scale = True

    def append(self, block: np.ndarray) -> None:
        block = np.asarray(block)
        if block.ndim != 2 or block.shape[0] == 0:
            raise ValueError(f"append expects a non-empty (rows, dim) block, "
                             f"got shape {block.shape}")
        if self._dim is None:
            self._dim = int(block.shape[1])
            self._dtype = _logical_dtype_name(block)
        if block.shape[1] != self._dim or block.dtype.name != self._dtype:
            raise ValueError(
                f"chunk mismatch: got ({block.shape[1]}, {block.dtype.name}), "
                f"store is ({self._dim}, {self._dtype})")
        fname = f"vectors_{len(self._chunks):06d}.npy"
        _write_chunk(os.path.join(self.tmp, fname), block)
        self._chunks.append({"file": fname, "rows": int(block.shape[0])})
        self._n += int(block.shape[0])

    # -- commit ------------------------------------------------------------
    def commit(self, meta: dict | None = None) -> "IndexStore":
        if self._committed:
            raise IndexStoreError("writer already committed")
        if not self._chunks:
            raise IndexStoreError("commit on an empty store (no chunks)")
        manifest = {
            "format_version": FORMAT_VERSION,
            "kind": "dense_index",
            "n": self._n,
            "dim": self._dim,
            "dtype": self._dtype,
            "chunks": self._chunks,
            "pca_file": PCA_FILE if self._has_pca else None,
            "scale_file": SCALE_FILE if self._has_scale else None,
            "meta": meta or {},
        }
        write_json_fsync(os.path.join(self.tmp, MANIFEST), manifest)
        commit_dir(self.tmp, self.path)
        self._committed = True
        return IndexStore.open(self.path)

    def abort(self) -> None:
        if not self._committed and os.path.exists(self.tmp):
            shutil.rmtree(self.tmp)

    def __enter__(self) -> "IndexStoreWriter":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.abort()


@dataclasses.dataclass
class IndexStore:
    """Read/append handle on a committed artifact directory."""

    path: str
    manifest: dict

    # -- open / validate ---------------------------------------------------
    @classmethod
    def create(cls, path: str) -> IndexStoreWriter:
        return IndexStoreWriter(path)

    @classmethod
    def open(cls, path: str) -> "IndexStore":
        path = str(path)
        mpath = os.path.join(path, MANIFEST)
        if not os.path.isfile(mpath):
            raise IndexStoreError(
                f"{path}: not a committed index store (no {MANIFEST} — "
                f"a crashed build leaves only a .tmp directory)")
        try:
            with open(mpath) as f:
                manifest = json.load(f)
        except json.JSONDecodeError as e:
            raise IndexStoreError(f"{path}: unreadable manifest: {e}") from e
        store = cls(path=path, manifest=manifest)
        store.validate()
        return store

    def validate(self) -> None:
        m = self.manifest
        if m.get("format_version") != FORMAT_VERSION:
            raise IndexStoreError(
                f"{self.path}: format_version {m.get('format_version')!r} "
                f"!= supported {FORMAT_VERSION}")
        for key in ("n", "dim", "dtype", "chunks"):
            if key not in m:
                raise IndexStoreError(f"{self.path}: manifest missing {key!r}")
        rows = 0
        for c in m["chunks"]:
            fpath = os.path.join(self.path, c["file"])
            if not os.path.isfile(fpath):
                raise IndexStoreError(f"{self.path}: missing chunk {c['file']}")
            arr = _read_chunk_validated(self.path, fpath, m["dtype"])
            if arr.ndim != 2 or arr.shape != (c["rows"], m["dim"]):
                raise IndexStoreError(
                    f"{self.path}: chunk {c['file']} has shape "
                    f"{tuple(arr.shape)}, manifest says ({c['rows']}, {m['dim']})")
            rows += c["rows"]
        if rows != m["n"]:
            raise IndexStoreError(
                f"{self.path}: chunk rows sum to {rows}, manifest n={m['n']}")
        for key in ("pca_file", "scale_file"):
            f = m.get(key)
            if f is not None and not os.path.isfile(os.path.join(self.path, f)):
                raise IndexStoreError(f"{self.path}: missing {key} blob {f}")
        segs = m.get("segments")
        if segs is not None:
            if not segs or segs[0].get("kind") != "base":
                raise IndexStoreError(
                    f"{self.path}: segments must start with a base segment")
            if sum(int(s["n"]) for s in segs) != m["n"]:
                raise IndexStoreError(
                    f"{self.path}: segment rows sum "
                    f"{sum(int(s['n']) for s in segs)} != manifest n={m['n']}")
            seg_files = [c["file"] for s in segs for c in s["chunks"]]
            if seg_files != [c["file"] for c in m["chunks"]]:
                raise IndexStoreError(
                    f"{self.path}: top-level chunks are not the "
                    f"concatenation of the segment chunk lists")
            for s in segs:
                f = s.get("scale_file")
                if f is not None and not os.path.isfile(
                        os.path.join(self.path, f)):
                    raise IndexStoreError(
                        f"{self.path}: segment {s['name']} missing scale "
                        f"blob {f}")
                cap = s.get("capacity")
                if cap is not None and int(s["n"]) > int(cap):
                    raise IndexStoreError(
                        f"{self.path}: segment {s['name']} holds {s['n']} "
                        f"rows over its capacity {cap}")
        self._validate_resolutions()
        self._validate_paged()

    def _validate_resolutions(self) -> None:
        """A coarse resolution must be a nested, row-aligned view of the
        base: same rows in the same order at a strictly smaller m. A
        mismatch would make cascade shortlist ids address the wrong
        rescore rows, so open() refuses loudly."""
        m = self.manifest
        base_n = int(self._segment_entries()[0]["n"])
        seen_m: set[int] = set()
        for r in m.get("resolutions", ()):
            for key in ("name", "m", "dtype", "chunks"):
                if key not in r:
                    raise IndexStoreError(
                        f"{self.path}: resolution entry missing {key!r}")
            rm = int(r["m"])
            if not 0 < rm < m["dim"]:
                raise IndexStoreError(
                    f"{self.path}: resolution {r['name']} has m={rm}, which "
                    f"does not nest inside the store's dim={m['dim']} "
                    f"(need 0 < m < dim — PCA leading columns)")
            if rm in seen_m:
                raise IndexStoreError(
                    f"{self.path}: duplicate resolution m={rm}")
            seen_m.add(rm)
            rows = 0
            for c in r["chunks"]:
                fpath = os.path.join(self.path, c["file"])
                if not os.path.isfile(fpath):
                    raise IndexStoreError(
                        f"{self.path}: resolution {r['name']} missing chunk "
                        f"{c['file']}")
                arr = _read_chunk_validated(self.path, fpath, r["dtype"])
                if arr.ndim != 2 or arr.shape != (c["rows"], rm):
                    raise IndexStoreError(
                        f"{self.path}: resolution chunk {c['file']} has "
                        f"shape {tuple(arr.shape)}, manifest says "
                        f"({c['rows']}, {rm})")
                rows += c["rows"]
            if rows != base_n:
                raise IndexStoreError(
                    f"{self.path}: resolution {r['name']} holds {rows} "
                    f"rows, base segment has {base_n} — the views no "
                    f"longer describe the same corpus")
            f = r.get("scale_file")
            if f is not None and not os.path.isfile(
                    os.path.join(self.path, f)):
                raise IndexStoreError(
                    f"{self.path}: resolution {r['name']} missing scale "
                    f"blob {f}")
            for d in r.get("deltas", ()):
                for key in ("name", "n", "capacity", "dtype", "chunks"):
                    if key not in d:
                        raise IndexStoreError(
                            f"{self.path}: resolution delta entry missing "
                            f"{key!r}")
                if int(d["n"]) > int(d["capacity"]):
                    raise IndexStoreError(
                        f"{self.path}: resolution delta {d['name']} holds "
                        f"{d['n']} rows over its capacity {d['capacity']}")
                drows = 0
                for c in d["chunks"]:
                    fpath = os.path.join(self.path, c["file"])
                    if not os.path.isfile(fpath):
                        raise IndexStoreError(
                            f"{self.path}: resolution delta {d['name']} "
                            f"missing chunk {c['file']}")
                    arr = _read_chunk_validated(self.path, fpath, d["dtype"])
                    if arr.ndim != 2 or arr.shape != (c["rows"], rm):
                        raise IndexStoreError(
                            f"{self.path}: resolution delta chunk "
                            f"{c['file']} has shape {tuple(arr.shape)}, "
                            f"manifest says ({c['rows']}, {rm})")
                    drows += c["rows"]
                if drows != int(d["n"]):
                    raise IndexStoreError(
                        f"{self.path}: resolution delta {d['name']} chunk "
                        f"rows sum to {drows}, manifest n={d['n']}")
                sf = d.get("scale_file")
                if sf is not None and not os.path.isfile(
                        os.path.join(self.path, sf)):
                    raise IndexStoreError(
                        f"{self.path}: resolution delta {d['name']} "
                        f"missing scale blob {sf}")

    def _validate_paged(self) -> None:
        """The ``paged`` block must describe the segment list it rides on.

        Append mirroring is two swaps (segment op, then lifecycle block),
        so the block may LAG the segments after a crash between them —
        fewer extents than segments, or a stale smaller row count — and
        the loader reconstructs the missing state conservatively. It must
        never LEAD: an extent claiming rows (or a whole extent) the
        segments don't hold is a torn artifact and is rejected."""
        pb = self.manifest.get("paged")
        if pb is None:
            return
        for key in ("page_rows", "seal_rows", "extents"):
            if key not in pb:
                raise IndexStoreError(
                    f"{self.path}: paged block missing {key!r}")
        if int(pb["page_rows"]) <= 0 or int(pb["seal_rows"]) <= 0:
            raise IndexStoreError(
                f"{self.path}: paged block needs positive page_rows/"
                f"seal_rows, got {pb['page_rows']}/{pb['seal_rows']}")
        exts = pb["extents"]
        entries = self._segment_entries() if int(self.manifest["n"]) else []
        if len(exts) > len(entries):
            raise IndexStoreError(
                f"{self.path}: paged block lists {len(exts)} extents but "
                f"the store holds {len(entries)} segments")
        for i, e in enumerate(exts):
            if e.get("kind") not in ("base", "delta"):
                raise IndexStoreError(
                    f"{self.path}: paged extent {i} has kind "
                    f"{e.get('kind')!r} (need base|delta)")
            if int(e["n"]) > int(entries[i]["n"]):
                raise IndexStoreError(
                    f"{self.path}: paged extent {i} claims {e['n']} rows, "
                    f"segment {entries[i]['name']} holds {entries[i]['n']}")
            if not e.get("sealed", True) and (i != len(exts) - 1
                                              or e["kind"] != "delta"):
                raise IndexStoreError(
                    f"{self.path}: paged extent {i} is unsealed but only "
                    f"the last delta extent may be open")

    # -- shape -------------------------------------------------------------
    @property
    def n(self) -> int:
        return int(self.manifest["n"])

    @property
    def dim(self) -> int:
        return int(self.manifest["dim"])

    @property
    def dtype(self) -> np.dtype:
        return _as_numpy_dtype(self.manifest["dtype"])

    @property
    def meta(self) -> dict:
        return self.manifest.get("meta", {})

    @property
    def nbytes(self) -> int:
        b = self.n * self.dim * self.dtype.itemsize
        if self.manifest.get("scale_file"):
            b += self.dim * 4
        return b

    # -- reads (host-streamed) --------------------------------------------
    def iter_chunks(self, mmap: bool = True) -> Iterator[np.ndarray]:
        """Yield row chunks in order, memory-mapped by default."""
        for c in self.manifest["chunks"]:
            yield _read_chunk(os.path.join(self.path, c["file"]),
                              self.manifest["dtype"], mmap=mmap)

    def read_rows(self, start: int, stop: int) -> np.ndarray:
        """Materialise rows [start, stop) — host memory O(stop - start).

        Chunks outside the range are never touched (mmap slicing), which is
        what lets a sharded load pull one device's rows at a time.
        """
        return _read_rows_from_chunks(self.path, self.manifest["chunks"],
                                      self.manifest["dtype"], self.dim,
                                      self.n, start, stop)

    def scale(self) -> np.ndarray | None:
        f = self.manifest.get("scale_file")
        if f is None:
            return None
        return np.load(os.path.join(self.path, f))

    def load_pca(self):
        """PCAState persisted at build time (None file -> error)."""
        f = self.manifest.get("pca_file")
        if f is None:
            raise IndexStoreError(f"{self.path}: store has no PCA state")
        from repro.core import pca as _pca
        return _pca.load_pca(os.path.join(self.path, f))

    def load_pruner(self):
        """Rebuild the StaticPruner this store was pruned with."""
        from repro.core.pruning import StaticPruner
        state = self.load_pca()
        m = self.meta.get("kept_dims", self.dim)
        pruner = StaticPruner(m=int(m), center=state.centered)
        pruner.state = state
        return pruner

    # -- segments ----------------------------------------------------------
    @property
    def is_segmented(self) -> bool:
        return "segments" in self.manifest

    def _segment_entries(self) -> list[dict]:
        """Manifest segment list, synthesising the single-base view for a
        pre-segment artifact (the backward-compat normalisation)."""
        segs = self.manifest.get("segments")
        if segs is not None:
            return segs
        return [{"name": "base", "kind": "base", "n": self.manifest["n"],
                 "chunks": self.manifest["chunks"],
                 "scale_file": self.manifest.get("scale_file")}]

    def segments(self) -> list[SegmentView]:
        """Read handles on every segment, base first, with global offsets."""
        views, offset = [], 0
        for s in self._segment_entries():
            views.append(SegmentView(store_path=self.path, name=s["name"],
                                     kind=s["kind"], entry=s, offset=offset,
                                     dim=self.dim,
                                     dtype_name=self.manifest["dtype"]))
            offset += int(s["n"])
        return views

    # -- resolutions (multi-resolution cascade artifact) -------------------
    def resolutions(self) -> list[SegmentView]:
        """Read handles on every coarse resolution (row-aligned with the
        base segment; ``dim`` is the resolution's m, ``dtype`` its own
        storage dtype). ``DenseIndex.load`` works on a view unchanged."""
        return [SegmentView(store_path=self.path, name=r["name"],
                            kind="resolution", entry=r, offset=0,
                            dim=int(r["m"]), dtype_name=r["dtype"])
                for r in self.manifest.get("resolutions", ())]

    def add_resolution(self, vectors: np.ndarray, *,
                       scale: np.ndarray | None = None,
                       chunk_rows: int = 262144,
                       deltas: "Sequence[dict]" = ()) -> str:
        """Durably attach a coarse resolution: the (base_n, m) leading-
        column view of the base rows in its storage dtype (int8 rows with
        their own per-dim ``scale``, or f32). Blob-then-manifest-swap like
        every other segment mutation; refuses a duplicate m, a non-nested
        m, or a row count that disagrees with the base segment.

        ``deltas`` persists a paged cascade's COARSE delta extents so a
        load adopts them bit-for-bit instead of re-deriving (requantising)
        from the full deltas: each dict carries ``rows`` (the live rows in
        storage dtype — exactly the bytes served), ``scale`` (per-dim
        dequant scale or None) and ``capacity`` (the seal rows). Their row counts must mirror the main
        delta segments one-for-one — the two views describe the same docs.
        """
        vectors = np.asarray(vectors)
        if vectors.ndim != 2:
            raise ValueError(f"add_resolution expects (rows, m), got shape "
                             f"{tuple(vectors.shape)}")
        seg_entries = self._segment_entries()
        base_n = int(seg_entries[0]["n"])
        n, m = vectors.shape
        if n != base_n:
            raise IndexStoreError(
                f"{self.path}: resolution has {n} rows, base segment has "
                f"{base_n}")
        if not 0 < m < self.dim:
            raise IndexStoreError(
                f"{self.path}: resolution m={m} does not nest inside "
                f"dim={self.dim}")
        deltas = list(deltas)
        main_delta_n = [int(s["n"]) for s in seg_entries[1:]]
        if deltas and [int(np.asarray(d["rows"]).shape[0])
                       for d in deltas] != main_delta_n:
            raise IndexStoreError(
                f"{self.path}: resolution delta rows "
                f"{[int(np.asarray(d['rows']).shape[0]) for d in deltas]} "
                f"do not mirror the main delta segments {main_delta_n} — "
                f"the views would describe different docs")
        manifest = json.loads(json.dumps(self.manifest))   # deep copy
        if any(int(r["m"]) == m for r in manifest.get("resolutions", ())):
            raise IndexStoreError(
                f"{self.path}: resolution m={m} already present")
        name = f"m{m}"
        entry = {"name": name, "m": m,
                 "dtype": _logical_dtype_name(vectors), "chunks": [],
                 "scale_file": None}
        for start in range(0, n, chunk_rows):
            fname, seq = self._next_blob(f"res_{name}")
            manifest["blob_seq"] = seq
            self.manifest["blob_seq"] = seq    # keep the counter monotonic
            block = vectors[start:min(start + chunk_rows, n)]
            _write_chunk(os.path.join(self.path, fname), block)
            entry["chunks"].append({"file": fname,
                                    "rows": int(block.shape[0])})
        if scale is not None:
            fname, seq = self._next_blob(f"scale_{name}")
            manifest["blob_seq"] = seq
            self.manifest["blob_seq"] = seq
            np.save(os.path.join(self.path, fname),
                    np.asarray(scale, np.float32))
            fsync_file(os.path.join(self.path, fname))
            entry["scale_file"] = fname
        if deltas:
            entry["deltas"] = []
            for di, d in enumerate(deltas):
                rows = np.asarray(d["rows"])
                if rows.ndim != 2 or rows.shape[1] != m:
                    raise ValueError(
                        f"resolution delta {di} expects (rows, {m}), got "
                        f"{tuple(rows.shape)}")
                dname = f"{name}-delta-{di:03d}"
                dent = {"name": dname, "n": int(rows.shape[0]),
                        "capacity": int(d["capacity"]),
                        "dtype": _logical_dtype_name(rows), "chunks": [],
                        "scale_file": None}
                if dent["n"] > dent["capacity"]:
                    raise IndexStoreError(
                        f"{self.path}: resolution delta {dname} holds "
                        f"{dent['n']} rows over its capacity "
                        f"{dent['capacity']}")
                if rows.shape[0]:
                    fname, seq = self._next_blob(f"res_{dname}")
                    manifest["blob_seq"] = seq
                    self.manifest["blob_seq"] = seq
                    _write_chunk(os.path.join(self.path, fname), rows)
                    dent["chunks"].append({"file": fname,
                                           "rows": int(rows.shape[0])})
                ds = d.get("scale")
                if ds is not None:
                    fname, seq = self._next_blob(f"scale_{dname}")
                    manifest["blob_seq"] = seq
                    self.manifest["blob_seq"] = seq
                    np.save(os.path.join(self.path, fname),
                            np.asarray(ds, np.float32))
                    fsync_file(os.path.join(self.path, fname))
                    dent["scale_file"] = fname
                entry["deltas"].append(dent)
        manifest.setdefault("resolutions", []).append(entry)
        self._swap_manifest(manifest)
        return name

    def resolution_deltas(self, name: str) -> list[SegmentView]:
        """Read handles on a resolution's persisted coarse delta segments
        (empty for a base-only resolution). ``dim`` is the resolution's m;
        offsets continue from the base rows in delta order, mirroring the
        main segment layout."""
        for r in self.manifest.get("resolutions", ()):
            if r["name"] == name:
                views, offset = [], int(self._segment_entries()[0]["n"])
                for d in r.get("deltas", ()):
                    views.append(SegmentView(
                        store_path=self.path, name=d["name"],
                        kind="resolution-delta", entry=d, offset=offset,
                        dim=int(r["m"]), dtype_name=d["dtype"]))
                    offset += int(d["n"])
                return views
        raise IndexStoreError(f"{self.path}: no resolution {name!r}")

    @property
    def flat_loadable(self) -> bool:
        """Whether the global chunk list is a coherent single index: one
        segment, no scales at all, or every segment sharing one scale —
        mixed per-segment scales need ``PagedIndex.load``."""
        segs = self._segment_entries()
        if len(segs) == 1:
            return True
        scales = [SegmentView(self.path, s["name"], s["kind"], s, 0,
                              self.dim, self.manifest["dtype"]).scale()
                  for s in segs]
        if all(s is None for s in scales):
            return True
        if any(s is None for s in scales):
            return False
        return all(np.array_equal(scales[0], s) for s in scales[1:])

    # -- append / segment mutation (incremental growth) --------------------
    def _next_blob(self, prefix: str = "vectors") -> str:
        """Unique blob name: a monotonically increasing sequence survives
        segment rewrites that delete earlier blobs (names never reused)."""
        seq = int(self.manifest.get("blob_seq",
                                    len(self.manifest["chunks"])))
        return f"{prefix}_{seq:06d}.npy", seq + 1

    def _swap_manifest(self, manifest: dict) -> None:
        """Atomic manifest replacement — the commit point of every segment
        mutation (all blobs must already be fsynced)."""
        tmp_manifest = os.path.join(self.path, MANIFEST + ".tmp")
        write_json_fsync(tmp_manifest, manifest)
        os.replace(tmp_manifest, os.path.join(self.path, MANIFEST))
        fsync_dir(self.path)
        self.manifest = manifest

    def _rebuild_global(self, manifest: dict) -> dict:
        """Re-derive the top-level n/chunks/scale_file from the segment
        list, keeping pre-segment readers (and validation) working on the
        global view. The top-level scale_file must track the BASE
        segment's: a base rewrite (``append_migrating`` widening the base)
        replaces and deletes the old scale blob, and a stale top-level
        pointer would fail validation forever after."""
        segs = manifest["segments"]
        manifest["chunks"] = [c for s in segs for c in s["chunks"]]
        manifest["n"] = sum(int(s["n"]) for s in segs)
        manifest["scale_file"] = segs[0].get("scale_file")
        return manifest

    def set_paged_state(self, block: dict) -> None:
        """Install/replace the ``paged`` lifecycle block in one manifest
        swap. Page bytes never move: promote and compact are pointer swaps
        in memory and exactly this metadata swap on disk."""
        manifest = json.loads(json.dumps(self.manifest))   # deep copy
        manifest["paged"] = block
        self._swap_manifest(manifest)

    def add_delta(self, scale: np.ndarray | None = None,
                  capacity: int | None = None) -> str:
        """Open a new (empty) delta segment with its own scale; returns its
        name. Converts a pre-segment manifest to the segmented layout (the
        existing vectors become the base segment, bit-untouched)."""
        manifest = json.loads(json.dumps(self.manifest))   # deep copy
        segs = manifest.setdefault("segments", self._segment_entries())
        name = f"delta-{len(segs):03d}"
        entry = {"name": name, "kind": "delta", "n": 0, "chunks": [],
                 "scale_file": None}
        if capacity is not None:
            entry["capacity"] = int(capacity)
        if scale is not None:
            fname, seq = self._next_blob(f"scale_{name}")
            np.save(os.path.join(self.path, fname), np.asarray(scale,
                                                               np.float32))
            fsync_file(os.path.join(self.path, fname))
            entry["scale_file"] = fname
            manifest["blob_seq"] = seq
        segs.append(entry)
        self._swap_manifest(self._rebuild_global(manifest))
        return name

    def _find_segment(self, manifest: dict, segment: str | None) -> dict:
        segs = manifest.get("segments")
        if segs is None:
            if segment not in (None, "base"):
                raise IndexStoreError(
                    f"{self.path}: no segment {segment!r} (pre-segment store)")
            return manifest                     # legacy: top-level IS the base
        if segment is None:
            return segs[-1]                     # the open (last) segment
        for s in segs:
            if s["name"] == segment:
                return s
        raise IndexStoreError(f"{self.path}: no segment {segment!r}")

    def append(self, block: np.ndarray, *, segment: str | None = None) -> None:
        """Durably append a row chunk (storage dtype) to a segment.

        ``segment=None`` targets the open (last) segment — the base on a
        pre-segment store, the newest delta on a segmented one. Protocol:
        chunk blob fsynced first, then the manifest atomically replaced
        (``os.replace``) and the directory fsynced — the manifest swap is
        the commit point.
        """
        block = np.asarray(block)
        if block.ndim != 2 or block.shape[1] != self.dim:
            raise ValueError(f"append expects (rows, {self.dim}), got "
                             f"{tuple(block.shape)}")
        if block.dtype.name != self.manifest["dtype"]:
            raise ValueError(f"append dtype {block.dtype.name} != store dtype "
                             f"{self.manifest['dtype']}")
        fname, seq = self._next_blob()
        _write_chunk(os.path.join(self.path, fname), block)
        manifest = json.loads(json.dumps(self.manifest))
        target = self._find_segment(manifest, segment)
        target["chunks"] = target["chunks"] + [
            {"file": fname, "rows": int(block.shape[0])}]
        target["n"] = int(target["n"]) + int(block.shape[0])
        manifest["blob_seq"] = seq
        if "segments" in manifest:
            manifest = self._rebuild_global(manifest)
        self._swap_manifest(manifest)

    def replace_segment(self, segment: str, blocks, *,
                        scale: np.ndarray | None = None) -> None:
        """Atomically rewrite one segment's contents (and scale).

        Used when a delta's int8 scale widens: the requantised rows replace
        the old chunks in one manifest swap. New blobs are written and
        fsynced first; the old blobs are deleted only after the swap, so a
        crash leaves either the old or the new segment — orphan blobs from
        the crash window are ignored by ``open`` (never named by the
        manifest). The rewrite cost is bounded by the segment's size.
        """
        manifest = json.loads(json.dumps(self.manifest))
        if "segments" not in manifest:
            manifest["segments"] = self._segment_entries()
        target = self._find_segment(manifest, segment)
        old_files = [c["file"] for c in target["chunks"]]
        old_scale = target.get("scale_file")
        chunks, total = [], 0
        for block in blocks:
            block = np.asarray(block)
            if block.dtype.name != self.manifest["dtype"]:
                raise ValueError(
                    f"replace dtype {block.dtype.name} != store dtype "
                    f"{self.manifest['dtype']}")
            fname, seq = self._next_blob()
            manifest["blob_seq"] = seq
            self.manifest["blob_seq"] = seq    # keep the counter monotonic
            _write_chunk(os.path.join(self.path, fname), block)
            chunks.append({"file": fname, "rows": int(block.shape[0])})
            total += int(block.shape[0])
        if scale is not None:
            fname, seq = self._next_blob(f"scale_{segment}")
            manifest["blob_seq"] = seq
            self.manifest["blob_seq"] = seq
            np.save(os.path.join(self.path, fname),
                    np.asarray(scale, np.float32))
            fsync_file(os.path.join(self.path, fname))
            target["scale_file"] = fname
        target["chunks"] = chunks
        target["n"] = total
        self._swap_manifest(self._rebuild_global(manifest))
        for f in old_files + ([old_scale] if scale is not None and old_scale
                              else []):
            try:
                os.remove(os.path.join(self.path, f))
            except OSError:
                pass

    def append_migrating(self, block: np.ndarray, *,
                         segment: str | None = None) -> bool:
        """Append f32 rows to an int8 segment, widening its scale instead
        of clipping (the scale-migration path, scoped per segment).

        If any value of ``block`` falls outside ±127 under the segment's
        current scale, the scale widens per-dim to fit and the segment's
        existing chunks requantise under it (dequantise with the old scale,
        requantise with the new — within half an old LSB of exact; callers
        holding the exact f32 rows should use ``replace_segment``
        directly). Returns True when the scale widened. On float stores
        this is a plain cast-and-append.
        """
        block = np.atleast_2d(np.asarray(block, np.float32))
        views = {v.name: v for v in self.segments()}
        target = self._find_segment(self.manifest, segment)
        name = target.get("name", "base")
        view = views.get(name, self.segments()[0])
        if self.dtype != np.int8:
            self.append(block.astype(self.dtype), segment=segment)
            return False
        from repro.core.quantization import quantize_with_scale, scale_for
        old = view.scale()
        if old is None:
            raise IndexStoreError(
                f"{self.path}: segment {name} is int8 but has no scale")
        need = scale_for(block)
        widened = bool((need > old).any())
        if not widened:
            self.append(quantize_with_scale(block, old), segment=segment)
            return False
        new_scale = np.maximum(old, need).astype(np.float32)
        requant = [
            quantize_with_scale(c.astype(np.float32) * old[None, :],
                                new_scale)
            for c in view.iter_chunks()]
        requant.append(quantize_with_scale(block, new_scale))
        if "segments" not in self.manifest:
            # pre-segment store: the rewrite touches the whole (base)
            # artifact — exactly the unbounded cost segmenting avoids
            self.manifest["segments"] = self._segment_entries()
        self.replace_segment(name, requant, scale=new_scale)
        return True
