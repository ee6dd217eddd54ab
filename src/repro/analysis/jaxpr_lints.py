"""Jaxpr invariant lints over the serving entry points.

Each serving dispatch path is traced (``jax.make_jaxpr`` — no device
execution, no traffic) on a tiny synthetic corpus shaped to exercise the
invariant, and the resulting jaxpr is walked statically:

  * **fused-dispatch** — the top level of a traced entry point must contain
    exactly the expected number of *compute dispatches* (jit eqns whose
    inner jaxpr does real work: dot_general / scan / top_k / pallas_call /
    collectives). ``DenseIndex.search_projected`` and
    ``ShardedDenseIndex.search_projected`` and a device-resident
    ``PagedIndex`` must be ONE; a paged cascade is five by design. A
    stray extra dispatch (a projection that escaped the jit, a
    device round-trip) is the regression this lint exists to catch.
  * **storage-dtype streaming** — with an int8/bf16 index, no
    ``convert_element_type`` may upcast an operand larger than one scan
    strip (the in-register dequant unit), and the array handed to
    ``pallas_call`` must keep the storage dtype: the whole bandwidth win
    is streaming n·m·1 bytes, not a 4x fp32 shadow copy.
  * **no host callbacks** — ``pure_callback``/``io_callback``/debug
    prints/infeed inside the traced hot path serialise the device behind
    the host; none may appear anywhere in the trace.
  * **jit-cache stability** — appending to a paged index (and a paged
    cascade) and searching it across a sweep of live counts, and the full
    page lifecycle, must not grow any jit cache
    (``repro.core.paged._jit_cache_sizes``): page-table slot bounds are
    traced operands by contract, so an append never recompiles.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.analysis import Finding

# primitives that mark a jit eqn as a real compute dispatch (vs a trivial
# jnp wrapper like atleast_2d, which also traces as a named jit)
_COMPUTE_PRIMS = frozenset({
    "dot_general", "scan", "while", "pallas_call", "top_k", "sort",
    "all_gather", "all_reduce", "psum", "reduce_sum", "reduce_max",
    "argmax", "shard_map",
})
_DISPATCH_PRIMS = frozenset({"jit", "pallas_call"})
# host round-trips that must never appear inside a traced hot path
_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback", "outside_call", "infeed", "outfeed",
})
_WIDTH = {"int8": 1, "bfloat16": 2, "float16": 2, "float32": 4,
          "float64": 8}


def iter_all_eqns(jaxpr) -> Iterable:
    """Every eqn of ``jaxpr`` and (recursively) of every sub-jaxpr in eqn
    params — scan bodies, cond branches, jit/pallas inner jaxprs."""
    for j in _walk(jaxpr):
        yield from j.eqns


def _contains_compute(eqn) -> bool:
    if eqn.primitive.name == "pallas_call":
        return True
    for j in _eqn_subjaxprs(eqn):
        for sub in _walk(j):
            for e in sub.eqns:
                if e.primitive.name in _COMPUTE_PRIMS:
                    return True
    return False


def _eqn_subjaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x


def _walk(jaxpr):
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        yield j
        for eqn in j.eqns:
            stack.extend(_eqn_subjaxprs(eqn))


def compute_dispatches(fn: Callable, *args) -> list:
    """Top-level compute-dispatch eqns of ``fn`` traced on ``args``."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name not in _DISPATCH_PRIMS:
            continue
        if _contains_compute(eqn):
            out.append(eqn)
    return out


def dispatch_name(eqn) -> str:
    name = eqn.params.get("name")
    return str(name) if name else eqn.primitive.name


def check_dispatch_count(label: str, fn: Callable, args: Sequence,
                         expected: int) -> list[Finding]:
    got = compute_dispatches(fn, *args)
    if len(got) == expected:
        return []
    names = [dispatch_name(e) for e in got]
    return [Finding(
        check="jaxpr.extra-dispatch", where=label,
        message=(f"{label}: {len(got)} compute dispatches on the hot path "
                 f"({names}), contract says exactly {expected} — a "
                 f"projection or merge escaped the fused jit"))]


def check_storage_dtype_stream(label: str, fn: Callable, args: Sequence,
                               corpus_shape: tuple[int, int],
                               storage_dtype: str,
                               strip_rows: int) -> list[Finding]:
    """No upcast larger than ONE scan strip anywhere in the trace; pallas
    operands keep the storage dtype.

    Per-strip upcasts (the in-register dequant, ``strip_rows`` × m) are the
    design; anything strictly larger is a shadow copy of multiple strips —
    in the limit the whole corpus — and defeats the storage-dtype
    streaming win. Callers must trace a config whose strip is smaller than
    the corpus, or the check is vacuous by construction."""
    findings: list[Finding] = []
    width = _WIDTH.get(storage_dtype)
    if width is None or width >= 4:
        return findings          # f32 storage: nothing to shadow-copy
    n, m = corpus_shape
    corpus_elems = n * m
    strip_elems = min(strip_rows, n) * m
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    for eqn in iter_all_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "convert_element_type":
            (src,), (dst,) = eqn.invars, eqn.outvars
            src_elems = int(np.prod(src.aval.shape)) if src.aval.shape else 1
            if (str(src.aval.dtype) == storage_dtype
                    and src_elems > strip_elems
                    and _WIDTH.get(str(dst.aval.dtype), 8) > width):
                findings.append(Finding(
                    check="jaxpr.upcast", where=f"{label}:{src.aval.shape}",
                    message=(f"{label}: convert_element_type upcasts a "
                             f"{storage_dtype} operand "
                             f"{tuple(src.aval.shape)} (> one "
                             f"{strip_rows}-row strip) to "
                             f"{dst.aval.dtype} — a multi-strip shadow "
                             f"copy defeats storage-dtype streaming")))
        elif name == "pallas_call":
            dtypes = {str(v.aval.dtype) for v in eqn.invars}
            if storage_dtype not in dtypes:
                findings.append(Finding(
                    check="jaxpr.upcast", where=f"{label}:pallas_call",
                    message=(f"{label}: no {storage_dtype} operand reaches "
                             f"pallas_call (got {sorted(dtypes)}) — the "
                             f"index was upcast before the kernel instead "
                             f"of dequantising in-register")))
            for v in eqn.invars:
                if (str(v.aval.dtype) not in (storage_dtype,)
                        and int(np.prod(v.aval.shape or (1,)))
                        >= corpus_elems):
                    findings.append(Finding(
                        check="jaxpr.upcast",
                        where=f"{label}:pallas_call:{v.aval.shape}",
                        message=(f"{label}: corpus-sized "
                                 f"{v.aval.dtype} operand "
                                 f"{tuple(v.aval.shape)} handed to "
                                 f"pallas_call alongside the "
                                 f"{storage_dtype} index")))
    return findings


def check_no_callbacks(label: str, fn: Callable, args: Sequence
                       ) -> list[Finding]:
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    hits = sorted({e.primitive.name for e in iter_all_eqns(jaxpr)
                   if e.primitive.name in _CALLBACK_PRIMS})
    return [Finding(
        check="jaxpr.host-callback", where=f"{label}:{h}",
        message=(f"{label}: host callback primitive '{h}' inside the "
                 f"traced hot path — every dispatch would synchronise the "
                 f"device behind the host")) for h in hits]


def check_recompile_stability(dispatch: Callable[[int, int], None],
                              cache_sizes: Callable[[], dict],
                              sweep: Sequence[tuple[int, int]],
                              label: str) -> list[Finding]:
    """Drive ``dispatch(live_count, offset)`` across ``sweep`` after one
    warmup call; any jit-cache growth means a cache key depends on a value
    that must stay a traced operand."""
    lo, off = sweep[0]
    dispatch(lo, off)                       # warmup compiles once
    before = cache_sizes()
    for live, offset in sweep[1:]:
        dispatch(live, offset)
    after = cache_sizes()
    grew = {name: (before.get(name, 0), n) for name, n in after.items()
            if n > before.get(name, 0)}
    return [Finding(
        check="jaxpr.recompile", where=f"{label}:{name}",
        message=(f"{label}: jit cache of '{name}' grew {b} -> {a} across a "
                 f"live-count/offset sweep — an index quantity leaked "
                 f"into a static cache key, so appends recompile under "
                 f"live traffic")) for name, (b, a) in sorted(grew.items())]


# ---------------------------------------------------------------------------
# The repo's real entry points, on tiny traced corpora
# ---------------------------------------------------------------------------


def _tiny(n=600, d=32, B=4, seed=0):
    rng = np.random.default_rng(seed)
    D = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    Q = jnp.asarray(rng.standard_normal((B, d)).astype(np.float32))
    return D, Q


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One traced serving entry point — the shared registry row consumed by
    the jaxpr lints, the cost model, and the invariant checker.

    ``fn(*args)`` is trace-ready (``jax.make_jaxpr``-safe, tiny corpus, no
    device traffic implied). ``storage_dtype``/``strip_rows`` are None when
    the storage-dtype streaming check does not apply (f32 storage, or the
    deltas' whole-capacity dequant-by-design). ``bench_key`` names the
    ``BENCH_perf.json`` serve_pipeline config this entry models, when one
    exists. ``family`` ∈ dense/cascade/sharded/paged/cascade-paged."""

    label: str
    fn: Callable
    args: tuple
    expected_dispatches: int
    corpus_shape: tuple[int, int]
    family: str
    backend: str
    storage_dtype: str | None = None
    strip_rows: int | None = None
    bench_key: str | None = None
    batch: int = 4


def serving_entry_points() -> tuple[EntryPoint, ...]:
    """Build every serving entry point on the tiny synthetic corpus."""
    from repro.core import (CascadeIndex, DenseIndex, ShardedDenseIndex,
                            StaticPruner)

    D, Q = _tiny()
    pruner = StaticPruner(cutoff=0.5).fit(D)
    Dh = pruner.prune_index(D)
    W, mean = pruner.projection()
    n, m = Dh.shape
    B = int(Q.shape[0])
    entries: list[EntryPoint] = []

    # -- dense: fused path is ONE dispatch, streams storage dtype ----------
    for quant, backend, block in ((False, "jnp", None), (True, "jnp", 128),
                                  (True, "pallas", 128)):
        idx = DenseIndex.build(Dh, quantize_int8=quant, backend=backend)
        label = f"DenseIndex.search_projected[{backend}" \
                f"{',int8' if quant else ''}]"
        entry = (lambda i, blk: lambda q: i.search_projected(
            q, W, k=10, mean=mean, block=blk))(idx, block)
        bench = None
        if backend == "jnp":          # serve_pipeline rows run jnp backend
            bench = "dense_int8" if quant else "dense_f32"
        entries.append(EntryPoint(
            label=label, fn=entry, args=(Q,), expected_dispatches=1,
            corpus_shape=(n, m), family="dense", backend=backend,
            storage_dtype=str(idx.vectors.dtype) if quant else None,
            strip_rows=block if quant else None, bench_key=bench, batch=B))

    # -- cascade (dense x dense): coarse scan + shortlist + gather +
    # exact rescore all trace into the SAME single fused dispatch ----------
    for quant, backend, block in ((False, "jnp", None), (True, "jnp", 128),
                                  (True, "pallas", 128)):
        cas = CascadeIndex.build(Dh, m_coarse=max(2, m // 2), n_factor=2,
                                 quantize_int8=quant, backend=backend)
        label = f"CascadeIndex.search_projected[{backend}" \
                f"{',int8' if quant else ''}]"
        entry = (lambda c, blk: lambda q: c.search_projected(
            q, W, k=10, mean=mean, block=blk))(cas, block)
        # the (U, m) = (B*nk, m) int8->f32 upcast of the gathered
        # shortlist IS the rescore stage's dequant unit (one matmul
        # operand, not a corpus shadow copy) — price the strip as the
        # larger of the coarse scan strip and the whole shortlist
        nk = min(cas.n_factor * 10, cas.n)
        entries.append(EntryPoint(
            label=label, fn=entry, args=(Q,), expected_dispatches=1,
            corpus_shape=(n, m), family="cascade", backend=backend,
            storage_dtype=(str(cas.full.vectors.dtype) if quant else None),
            strip_rows=max(block, B * nk) if quant else None, batch=B))

    # -- sharded: one dispatch wrapping shard_map + merge ------------------
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    for quant in (False, True):
        sidx = ShardedDenseIndex.build(Dh, mesh, quantize_int8=quant)
        label = f"ShardedDenseIndex.search_projected" \
                f"[{'int8' if quant else 'f32'}]"
        entry = (lambda i: lambda q: i.search_projected(
            q, W, k=10, mean=mean, block=128))(sidx)
        entries.append(EntryPoint(
            label=label, fn=entry, args=(Q,), expected_dispatches=1,
            corpus_shape=(n, m), family="sharded", backend="jnp",
            storage_dtype=str(sidx.vectors.dtype) if quant else None,
            strip_rows=128 if quant else None,
            bench_key="sharded_int8" if quant else "sharded_f32", batch=B))

    # -- paged: a device-resident paged index is ONE fused dispatch — the
    # projection, page-table walk, per-page in-register dequant and top-k
    # all trace into ``_paged_search_projected``. Appends land in the tail
    # tier at contiguous logical slots, so a grown index stays single-run
    # (the [lo, hi) bounds are traced operands, never static shapes) ------
    from repro.core.paged import PagedIndex
    rng_p = np.random.default_rng(11)
    for quant, backend in ((False, "jnp"), (True, "jnp"), (True, "pallas")):
        pidx = PagedIndex.from_index(
            DenseIndex.build(Dh, quantize_int8=quant), page_rows=64,
            seal_rows=128, backend=backend)
        pidx = pidx.append(rng_p.standard_normal((70, m))
                           .astype(np.float32))
        label = f"PagedIndex.search_projected[{backend}" \
                f"{',int8' if quant else ''}]"
        entries.append(EntryPoint(
            label=label,
            fn=(lambda i: lambda q: i.search_projected(q, W, k=10,
                                                       mean=mean))(pidx),
            args=(Q,), expected_dispatches=1, corpus_shape=(n, m),
            family="paged", backend=backend,
            storage_dtype="int8" if quant else None,
            strip_rows=64 if quant else None, batch=B))

    # -- paged cascade: projection + paged coarse walk + shortlist +
    # paged rescore + select = 5 dispatches, independent of page or
    # extent count ---------------------------------------------------------
    rng_pc = np.random.default_rng(13)
    pcas = CascadeIndex.build(Dh, m_coarse=max(2, m // 2), n_factor=2,
                              quantize_int8=True
                              ).paged(page_rows=64, seal_rows=128)
    pcas = pcas.append(rng_pc.standard_normal((70, m)).astype(np.float32))
    entries.append(EntryPoint(
        label="CascadeIndex.search_projected[paged,int8]",
        fn=(lambda c: lambda q: c.search_projected(q, W, k=10,
                                                   mean=mean))(pcas),
        args=(Q,), expected_dispatches=5, corpus_shape=(n, m),
        family="cascade-paged", backend="jnp", batch=B))

    return tuple(entries)


def run() -> list[Finding]:
    """Lint every serving entry point; returns the combined findings."""
    from repro.core import CascadeIndex, DenseIndex, StaticPruner
    from repro.core import cascade, paged
    from repro.core.pca import transform

    findings: list[Finding] = []
    for ep in serving_entry_points():
        findings += check_dispatch_count(ep.label, ep.fn, ep.args,
                                         expected=ep.expected_dispatches)
        findings += check_no_callbacks(ep.label, ep.fn, ep.args)
        if ep.storage_dtype is not None:
            findings += check_storage_dtype_stream(
                ep.label, ep.fn, ep.args, ep.corpus_shape, ep.storage_dtype,
                strip_rows=ep.strip_rows)

    D, Q = _tiny()
    pruner = StaticPruner(cutoff=0.5).fit(D)
    Dh = pruner.prune_index(D)
    W, mean = pruner.projection()
    m = Dh.shape[1]

    # -- compaction streaming: the per-block projection is one dispatch ----
    rng = np.random.default_rng(3)
    label = "pca.transform[compaction-block]"
    block = jnp.asarray(rng.standard_normal((64, D.shape[1]))
                        .astype(np.float32))
    entry = lambda b: transform(b, pruner.state, pruner.kept_dims)  # noqa: E731
    findings += check_no_callbacks(label, entry, (block,))

    # -- paged cascade recompile stability: appends grow BOTH resolutions;
    # the coarse walk and the rescore take page-table slot bounds as traced
    # operands and nk = n_factor*k stays fixed, so no jit may recompile
    # (appends themselves are host-side writes re-pushed at fixed shapes).
    sweep = [(1, 0), (2, 0), (3, 0), (5, 0), (1, 0)]
    rng_c = np.random.default_rng(7)
    cas = CascadeIndex.build(Dh, m_coarse=max(2, m // 2), n_factor=2,
                             quantize_int8=True
                             ).paged(page_rows=64, seal_rows=128)
    cstate = {"cas": cas.append(rng_c.standard_normal((70, m))
                                .astype(np.float32))}

    def cdispatch(live_rows: int, _offset: int) -> None:
        cstate["cas"] = cstate["cas"].append(
            rng_c.standard_normal((live_rows, m)).astype(np.float32))
        cstate["cas"].search_projected(Q, W, k=5, mean=mean)

    def cascade_cache_sizes() -> dict:
        return {**paged._jit_cache_sizes(), **cascade._jit_cache_sizes()}

    findings += check_recompile_stability(
        cdispatch, cascade_cache_sizes, sweep,
        "CascadeIndex.append+search_projected[paged]")

    # -- paged lifecycle recompile stability: the FULL page lifecycle —
    # append -> search -> promote -> compact -> search — at varying live
    # counts must reuse every jit. All page metadata (table, nvalid,
    # offsets, scales) is host-authoritative and re-pushed at fixed
    # shapes; [lo, hi) slot bounds are traced operands; compaction is the
    # one fused ``_pool_drain`` gather. Any cache growth here means a page
    # count or extent boundary leaked into a static key.
    rng_p = np.random.default_rng(11)
    pstate = {"pg": paged.PagedIndex.from_index(
        DenseIndex.build(Dh, quantize_int8=True), page_rows=64,
        seal_rows=128)}

    def pdispatch(live_rows: int, _offset: int) -> None:
        pg = pstate["pg"].append(
            rng_p.standard_normal((live_rows, m)).astype(np.float32))
        pg.search_projected(Q, W, k=5, mean=mean)
        pg, _ = pg.promote()
        pg, _ = pg.compact_pages()
        pg.search_projected(Q, W, k=5, mean=mean)
        pstate["pg"] = pg

    findings += check_recompile_stability(
        pdispatch, paged._jit_cache_sizes, sweep, "PagedIndex.lifecycle")
    return findings
