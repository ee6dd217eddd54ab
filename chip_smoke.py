"""Smoke test of the served retrieval path on a TPU, end to end.

    python chip_smoke.py               # one chip: dense f32/int8 on both
                                       # backends, paged int8 (pallas)
    python chip_smoke.py --four-chip   # every chip: ShardedDenseIndex, flat
                                       # and hierarchical merge, both backends

One process, data from ``--seed``. A TAS-B-profile synthetic corpus (d=768)
is generated block by block and pruned at cutoff 0.5 (m=384) by
``StaticPruner.build_index_to`` into a store under ``.smoke/``; the int8
store is minted from it through the same build path. Each phase loads a
store through the normal loaders and answers ``--queries`` raw queries
through ``RetrievalServer``.

Every phase is checked against an exact reference that shares no code with
the path under test: numpy float64 over the stored (dequantised) vectors
and the stored projection. Both sides hold the same vectors, so the only
legitimate difference is f32 rounding on the chip: the matmuls run at
``Precision.HIGHEST``, i.e. unit roundoff u = 2**-24. Each projected query
entry sums d products and each score m more, so by the probabilistic
rounding bound (Higham & Mary, 2019: |error| <= lam*sqrt(n)*u*sum|x_i y_i|
except with probability 2*exp(-lam**2/2), 3e-8 at lam = 6)

    tol = 6 * u * (sqrt(d*m) + sqrt(m) + 1) * |q| * max_row_norm

(the sqrt(m) in the first term carries the m projected entries into the
score). A returned score must lie within ``tol`` of the exact score of the
same id, the k returned ids must be distinct and none may score more than
``2 * tol`` below the exact k-th, and the returned id set must equal the
exact top-k wherever the exact k-th and (k+1)-th scores are more than
``2 * tol`` apart. The chip's
default single bf16 pass (u = 2**-9) would miss this by orders of magnitude.

The pallas phases must compile to a Mosaic kernel (``tpu_custom_call``).
Anything else, or a platform other than ``tpu``, exits non-zero; the last
stdout line is a JSON object naming the device only when every phase
passed.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.util import compile_cache_dir  # noqa: E402  (needs the path above)

K = 10
D_IN = 768
CUTOFF = 0.5


def _device():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform is "
                         f"{dev.platform!r}); nothing was measured")
    return dev


def build_stores(n_docs: int, seed: int, *, int8: bool = True,
                 block_rows: int = 65536):
    """f32 store from the generator; with ``int8``, an int8 store
    re-quantised from it (else None)."""
    from repro.core import IndexStore, StaticPruner
    from repro.data.synthetic import corpus_blocks

    work = os.path.join(ROOT, ".smoke")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    t0 = time.perf_counter()
    pruner = StaticPruner(cutoff=CUTOFF)
    f32 = pruner.build_index_to(
        os.path.join(work, "f32"),
        corpus_blocks("tasb", n_docs=n_docs, d=D_IN, seed=seed,
                      block_rows=block_rows))
    t1 = time.perf_counter()
    print(f"[build] {n_docs} x {D_IN} -> m={f32.dim}: f32 store "
          f"{t1 - t0:.1f}s", flush=True)
    if not int8:
        return f32, None
    src = IndexStore.open(f32.path)
    q8 = pruner.build_index_to(os.path.join(work, "int8"),
                               lambda: src.iter_chunks(),
                               quantize_int8=True, already_projected=True)
    print(f"[build] int8 store {time.perf_counter() - t1:.1f}s", flush=True)
    return f32, q8


class Reference:
    """Exact float64 top-(k+1) over a store's vectors, numpy only."""

    def __init__(self, store, Q: np.ndarray):
        pca = store.load_pca()
        W = np.asarray(pca.components, np.float64)[:, :store.dim]
        q = Q.astype(np.float64)
        if pca.centered:
            q = q - np.asarray(pca.mean, np.float64)[None, :]
        q_norm = np.linalg.norm(q, axis=1)
        scale = store.scale()
        deq = 1.0 if scale is None else scale.astype(np.float64)
        self.q = (q @ W) * deq
        self.rows = np.concatenate(list(store.iter_chunks(mmap=False)))
        row_norm = max(np.linalg.norm(c.astype(np.float64) * deq, axis=1).max()
                       for c in np.array_split(self.rows, 64))
        # see the module docstring; W's columns are orthonormal
        m = store.dim
        self.tol = (6 * 2.0 ** -24 * (np.sqrt(D_IN * m) + np.sqrt(m) + 1)
                    * q_norm * row_norm)
        self.s, self.i = self._topk(K + 1)

    def _topk(self, k: int):
        best_s = np.empty((len(self.q), 0))
        best_i = np.empty((len(self.q), 0), np.int64)
        for lo in range(0, len(self.rows), 65536):
            s = self.q @ self.rows[lo:lo + 65536].astype(np.float64).T
            cs = np.concatenate([best_s, s], axis=1)
            ci = np.concatenate(
                [best_i, np.broadcast_to(lo + np.arange(s.shape[1]), s.shape)],
                axis=1)
            part = np.argpartition(-cs, k - 1, axis=1)[:, :k]
            best_s = np.take_along_axis(cs, part, 1)
            best_i = np.take_along_axis(ci, part, 1)
        order = np.lexsort((best_i, -best_s), axis=1)
        return (np.take_along_axis(best_s, order, 1),
                np.take_along_axis(best_i, order, 1))

    def check(self, name: str, scores: np.ndarray, ids: np.ndarray) -> dict:
        """Agreement of one phase's (B, K) results with the exact top-K."""
        exact = np.einsum("bm,bkm->bk", self.q,
                          self.rows[ids].astype(np.float64))
        err = np.abs(scores.astype(np.float64) - exact)
        gap = self.s[:, K - 1] - self.s[:, K]
        decided = gap > 2 * self.tol
        same = np.array([set(a) == set(b) for a, b in
                         zip(ids.tolist(), self.i[:, :K].tolist())])
        recall = np.mean([len(set(a) & set(b)) / K for a, b in
                          zip(ids.tolist(), self.i[:, :K].tolist())])
        # on every query, undecided ones too: K distinct ids, none of them
        # clearly below the exact k-th score
        outside = exact < (self.s[:, K - 1] - 2 * self.tol)[:, None]
        repeats = sum(len(set(r)) < K for r in ids.tolist())
        res = dict(phase=name, recall_at_10=float(recall),
                   max_score_err=float(err.max()),
                   max_tol=float(self.tol.max()),
                   queries_decided=int(decided.sum()),
                   id_mismatch_decided=int((~same & decided).sum()),
                   ids_outside_topk=int(outside.sum()),
                   queries_with_repeats=int(repeats))
        print(f"[check] {json.dumps(res)}", flush=True)
        if ((err > self.tol[:, None]).any() or res["id_mismatch_decided"]
                or res["ids_outside_topk"] or repeats):
            raise SystemExit(f"chip_smoke: phase {name} disagrees with the "
                             f"exact reference: {res}")
        return res


def serve(index, pruner, Q: np.ndarray, *, batch: int = 32):
    """Answer every query through a RetrievalServer; (scores, ids, compile s)."""
    from repro.launch.serve import RetrievalServer

    server = RetrievalServer(index, pruner, k=K, max_batch=batch)
    try:
        t0 = time.perf_counter()
        server.warmup()                  # compiles the one served shape
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        replies = [server.submit(q) for q in Q]
        out = [r.get(timeout=300) for r in replies]
        wall = time.perf_counter() - t0
    finally:
        server.close()
    for o in out:
        if isinstance(o, BaseException):
            raise o
    scores = np.stack([o[0] for o in out])
    ids = np.stack([o[1] for o in out])
    return scores, ids, t_compile, wall


def assert_kernel(index, pruner, Q: np.ndarray) -> None:
    """The served dispatch must compile to a Mosaic kernel on the chip.

    Lowers the jitted function ``search_projected`` dispatches, with the
    index arrays as arguments: a jit closing over them would bake the whole
    index into the program as a constant.
    """
    import jax.numpy as jnp
    from repro.core.index import _dense_search_projected
    from repro.core.paged import PagedIndex, _paged_search_projected

    W, mean = pruner.projection()
    q = jnp.asarray(Q[:32])
    if isinstance(index, PagedIndex):
        [(lo, hi, on_device)] = index._runs()
        if not on_device:
            raise SystemExit("chip_smoke: paged index is not device-resident")
        lowered = _paged_search_projected.lower(
            *index._device_args(), jnp.int32(lo), jnp.int32(hi), W, mean, q,
            k=K, backend=index.backend, depth=index.depth, guard=index.guard)
    else:
        lowered = _dense_search_projected.lower(
            index.vectors, index.scale, W, mean, q, K, None, index.backend)
    hlo = lowered.compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise SystemExit("chip_smoke: pallas dispatch has no tpu_custom_call")


def one_chip(args, dev) -> None:
    from repro.core import DenseIndex
    from repro.core.paged import PagedIndex
    from repro.data.synthetic import corpus_queries

    f32, int8 = build_stores(args.n_docs, args.seed)
    # peak_bytes_in_use only grows: this is the build's, and a later phase's
    # peak above it is that phase's own
    mem = dev.memory_stats()
    print(f"[build] bytes_in_use {mem.get('bytes_in_use', -1)}, "
          f"peak_bytes_in_use {mem.get('peak_bytes_in_use', -1)}", flush=True)
    Q = corpus_queries("tasb", n_queries=args.queries, d=D_IN, seed=args.seed)
    pruner = f32.load_pruner()
    refs = {}
    phases = [("dense-f32-jnp", f32, "jnp", DenseIndex),
              ("dense-f32-pallas", f32, "pallas", DenseIndex),
              ("dense-int8-jnp", int8, "jnp", DenseIndex),
              ("dense-int8-pallas", int8, "pallas", DenseIndex),
              ("paged-int8-pallas", int8, "pallas", PagedIndex)]
    for name, store, backend, cls in phases:
        if store.path not in refs:
            t0 = time.perf_counter()
            refs[store.path] = Reference(store, Q)
            print(f"[ref] {os.path.basename(store.path)}: exact top-{K + 1} "
                  f"in {time.perf_counter() - t0:.1f}s", flush=True)
        t0 = time.perf_counter()
        index = cls.load(store, backend=backend)
        t_load = time.perf_counter() - t0
        t0 = time.perf_counter()
        if backend == "pallas":   # compiles the served dispatch
            assert_kernel(index, pruner, Q)
        t_kernel = time.perf_counter() - t0
        scores, ids, t_compile, wall = serve(index, pruner, Q)
        refs[store.path].check(name, scores, ids)
        mem = dev.memory_stats()
        print(f"[phase] {name}: load {t_load:.1f}s, kernel check "
              f"{t_kernel:.2f}s, compile+first batch {t_compile:.2f}s, "
              f"{len(Q)} queries in {wall:.3f}s, index {index.nbytes} B, "
              f"bytes_in_use {mem.get('bytes_in_use', -1)}, "
              f"peak_bytes_in_use {mem.get('peak_bytes_in_use', -1)}",
              flush=True)
        del index


def four_chip(args) -> None:
    """ShardedDenseIndex over every chip vs the same store on one device."""
    import jax
    from repro.core import DenseIndex, ShardedDenseIndex
    from repro.data.synthetic import corpus_queries
    from repro.launch.serve import _serve_mesh

    ndev = jax.device_count()
    if ndev != 4:
        raise SystemExit(f"chip_smoke --four-chip: {ndev} devices, need 4")
    f32, _ = build_stores(args.n_docs, args.seed, int8=False)
    Q = corpus_queries("tasb", n_queries=args.queries, d=D_IN, seed=args.seed)
    pruner = f32.load_pruner()
    ref = Reference(f32, Q)
    for backend in ("jnp", "pallas"):
        single = DenseIndex.load(f32, backend=backend)
        s1, i1, _, _ = serve(single, pruner, Q)
        ref.check(f"single-{backend}", s1, i1)
        del single
        for merge in ("flat", "hierarchical"):
            mesh = _serve_mesh(ndev, merge)
            index = ShardedDenseIndex.load(f32, mesh, backend=backend,
                                           merge=merge)
            held = {s.device for s in index.vectors.addressable_shards}
            if len(held) != 4:
                raise SystemExit(f"chip_smoke: shards on {len(held)} devices")
            s, i, t_compile, wall = serve(index, pruner, Q)
            ref.check(f"sharded-{backend}-{merge}", s, i)
            same = np.array([set(a) == set(b) for a, b in
                             zip(i.tolist(), i1.tolist())])
            if (~same & (ref.s[:, K - 1] - ref.s[:, K] > 2 * ref.tol)).any():
                raise SystemExit(f"chip_smoke: sharded {backend}/{merge} ids "
                                 f"differ from one device's")
            print(f"[sharded] {backend}/{merge} on mesh "
                  f"{dict(mesh.shape)}: ids equal to one device for "
                  f"{int(same.sum())}/{len(Q)} queries, max |score diff| "
                  f"{float(np.abs(s - s1).max()):.3g}, compile+first batch "
                  f"{t_compile:.2f}s, {len(Q)} queries in {wall:.3f}s",
                  flush=True)
            del index


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded path over all 4 chips")
    ap.add_argument("--n-docs", type=int, default=None,
                    help="corpus rows (default 1,000,000; 250,001 with "
                         "--four-chip)")
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.n_docs is None:
        args.n_docs = 250_001 if args.four_chip else 1_000_000

    import jax
    dev = _device()
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    print(f"[device] {dev.platform} {dev.device_kind} x {jax.device_count()}"
          f", jax {jax.__version__}, compile cache {compile_cache_dir()}",
          flush=True)
    if args.four_chip:
        four_chip(args)
    else:
        one_chip(args, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
