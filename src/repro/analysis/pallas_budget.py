"""Static VMEM/grid budget checker for the repo's Pallas kernels.

TPU cores have ~16 MiB of VMEM; a ``pallas_call`` whose resident working
set exceeds it fails at *compile* time on hardware — but this repo's CI
runs the kernels in interpret mode, where any geometry "works". This
checker closes that gap statically, with no TPU and no execution:

  * **VMEM estimate** — resident bytes for a ``topk_score_pallas`` /
    ``pca_project`` config, derived from the kernels' own shared geometry
    helpers (``topk_geometry`` / ``project_geometry``), so the checker
    prices exactly the dispatch the wrapper would launch: streamed inputs
    double-buffered, outputs double-buffered, scratch and the kernel's
    in-register intermediates single-buffered.
  * **grid/padding invariants** — the clamp/pad/fold arithmetic must tile
    exactly (no dropped or double-visited rows): ``nblocks·block_n =
    n + pad_rows`` with ``pad_rows < block_n``, batch and fold likewise.
  * **traced index-map bounds** — best-effort introspection of the traced
    ``pallas_call``: every BlockSpec index map is evaluated at the grid
    corners and every block window must start inside its operand (the
    last window may overhang: Pallas clips that edge block and the kernels
    mask its rows). Guarded per JAX version; introspection failure
    degrades to a warn, never a crash.
  * **alignment warnings** — lane (128) / sublane (8) misalignment wastes
    VMEM and MXU occupancy without being wrong; reported at warn severity.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import numpy as np

from repro.analysis import Finding
from repro.analysis.jaxpr_lints import iter_all_eqns
from repro.kernels.pca_project import project_geometry
from repro.kernels.topk_score import (PagedTopKGeometry, TopKGeometry,
                                      exact_in_bf16, paged_topk_geometry,
                                      topk_geometry)

#: per-core VMEM on current TPU generations; the checker budget defaults to
#: this minus a safety margin for compiler-managed temporaries.
VMEM_PER_CORE = 16 * 2 ** 20
DEFAULT_BUDGET = int(VMEM_PER_CORE * 0.9)

_WIDTH = {"int8": 1, "bfloat16": 2, "float16": 2, "float32": 4,
          "int32": 4, "float64": 8}

LANE = 128
SUBLANE = 8


def _width(dtype: str) -> int:
    return _WIDTH.get(str(dtype), 4)


def estimate_topk_vmem(g: TopKGeometry, dtype: str,
                       with_ids: bool = False) -> dict[str, int]:
    """Resident-bytes breakdown of one ``topk_score_pallas`` dispatch.

    Inputs/outputs are priced double-buffered (the Pallas pipeline keeps
    the next block in flight while the kernel runs on the current one);
    scratch is persistent single-buffered; the kernel's largest live
    intermediates — the (block_b, block_n) f32 score strip, its int32 id
    strip, the fold buffers and the (k + fold_w) candidate rows — are
    priced once. ``with_ids`` adds the cascade rescore's explicit
    ``row_ids`` strip: a double-buffered (1, block_n) int32 input (the
    broadcast gids buffer replaces the plain mode's iota — same bytes,
    already priced as ``gids``).
    """
    w = _width(dtype)
    # rows bf16 holds exactly meet the query's three bf16 terms: a (3bb, m)
    # bf16 tile, the strip in bf16 and a (3bb, block_n) f32 product
    terms = 3 if exact_in_bf16(dtype) else 1
    q_tile = 2 * terms * g.block_b * g.m * (2 if terms == 3 else 4)
    d_strip = 2 * g.block_n * g.m * w                 # storage-dtype strip
    ids_strip = 2 * g.block_n * 4 if with_ids else 0  # row_ids int32 strip
    outs = 2 * g.block_b * g.k * (4 + 4)              # scores + ids
    scratch = g.block_b * g.k * (4 + 4)               # running top-k
    scores = terms * g.block_b * g.block_n * 4        # S_blk f32
    gids = g.block_b * g.block_n * 4                  # iota/broadcast int32
    dequant = g.block_n * g.m * (2 if terms == 3 else 4) if w < 4 else 0
    fold = g.block_b * g.fold_r * g.fold_w * (4 + 4)  # fs + fi
    cand = g.block_b * (g.k + g.fold_w) * (4 + 4)     # merge buffer
    parts = dict(q_tile=q_tile, d_strip=d_strip, ids_strip=ids_strip,
                 dequant=dequant, scores=scores, gids=gids, fold=fold,
                 cand=cand, scratch=scratch, outputs=outs)
    parts["total"] = sum(parts.values())
    return parts


def estimate_paged_topk_vmem(g: PagedTopKGeometry, dtype: str,
                             with_scale: bool = False,
                             with_ids: bool = False,
                             with_carry: bool = False) -> dict[str, int]:
    """Resident-bytes breakdown of one ``topk_score_paged_pallas`` dispatch.

    The page pool and tail live in HBM (``ANY`` memory space) — only the
    DMA landing window is VMEM-resident, and it is priced ``depth`` times:
    at pipeline depth D, D page buffers (plus their per-page scale and id
    strips in the rescore mode) are in flight at once. That is the whole
    point of the estimate — doubling ``depth`` buys copy/compute overlap
    by doubling exactly these rows. Everything else mirrors the flat
    kernel: the f32 query tile streams per batch tile, the running top-k
    scratch persists, and the per-page score/fold/candidate intermediates
    are priced once (the loop reuses them each page).
    """
    w = _width(dtype)
    R = g.page_rows
    # rows bf16 holds exactly: the query tile splits per page into three
    # bf16 terms, the page converts to bf16, the product is (3bb, R) f32
    terms = 3 if exact_in_bf16(dtype) else 1
    parts = dict(
        q_tile=2 * g.block_b * g.m * 4,               # f32 query tile
        q_terms=g.block_b * g.m * 2 * 3 if terms == 3 else 0,
        page_window=g.depth * R * g.m * w,            # DMA buffers x depth
        scale_window=g.depth * g.m * 4 if with_scale else 0,
        ids_window=g.depth * R * 4 if with_ids else 0,
        dequant=R * g.m * (2 if terms == 3 else 4) if w < 4 else 0,
        scores=terms * g.block_b * R * 4,             # per-page strip
        gids=g.block_b * R * 4,
        fold=g.block_b * g.fold_r * g.fold_w * (4 + 4),
        cand=g.block_b * (g.k + g.fold_w) * (4 + 4),
        scratch=g.block_b * g.k * (4 + 4),            # running top-k
        carry=2 * g.block_b * g.k * (4 + 4) if with_carry else 0,
        outputs=2 * g.block_b * g.k * (4 + 4),
    )
    parts["total"] = sum(parts.values())
    return parts


def estimate_paged_hbm_reads(g: PagedTopKGeometry, dtype: str,
                             live_pages: int, with_scale: bool = False,
                             with_ids: bool = False) -> dict[str, int]:
    """HBM read-bytes of one paged dispatch: every live page is DMA'd once
    per batch tile, the int32 page table / n_valid / offset arrays ride
    along (they are small but they are real reads the flat kernel does
    not pay), and the query tiles stream once."""
    w = _width(dtype)
    R = g.page_rows
    parts = dict(
        pages=g.nbt * live_pages * R * g.m * w,
        page_table=3 * g.table_cap * 4 + 8,           # pt/nvalid/offset+lohi
        scales=g.nbt * live_pages * g.m * 4 if with_scale else 0,
        ids=g.nbt * live_pages * R * 4 if with_ids else 0,
        queries=g.b_pad * g.m * 4,
    )
    parts["total"] = sum(parts.values())
    return parts


def check_paged_topk_config(table_cap: int, pool_pages: int, page_rows: int,
                            m: int, B: int, k: int, *, depth: int = 2,
                            block_b: int = 128, dtype: str = "float32",
                            with_scale: bool = False, with_ids: bool = False,
                            budget: int = DEFAULT_BUDGET) -> list[Finding]:
    """Budget + tiling-invariant findings for one paged-scan config."""
    g = paged_topk_geometry(table_cap, pool_pages, page_rows, m, B, k,
                            depth=depth, block_b=block_b)
    label = (f"topk_score_paged[R={page_rows},m={m},k={k},d={depth},"
             f"bb={g.block_b},{dtype}"
             f"{',scale' if with_scale else ''}{',ids' if with_ids else ''}]")
    findings: list[Finding] = []

    est = estimate_paged_topk_vmem(g, dtype, with_scale=with_scale,
                                   with_ids=with_ids)
    if est["total"] > budget:
        top = sorted((v, c) for c, v in est.items() if c != "total")[-2:]
        hot = ", ".join(f"{c}={v // 1024}KiB" for v, c in reversed(top))
        findings.append(Finding(
            check="pallas.vmem-budget", where=label,
            message=(f"{label}: resident VMEM estimate "
                     f"{est['total'] / 2 ** 20:.1f} MiB exceeds the "
                     f"{budget / 2 ** 20:.1f} MiB budget ({hot}) — shrink "
                     f"page_rows or the pipeline depth")))

    bad = []
    if g.nbt * g.block_b != g.b_pad or g.b_pad < g.B:
        bad.append(f"batch tiles: {g.nbt}x{g.block_b} vs B={g.B}"
                   f" pad->{g.b_pad}")
    if g.fold_r * g.fold_w != g.page_rows + g.pad_w or g.pad_w >= g.fold_w:
        bad.append(f"fold: {g.fold_r}x{g.fold_w} vs page_rows="
                   f"{g.page_rows}+pad{g.pad_w}")
    if depth < 1:
        bad.append(f"depth: {depth} < 1 — no DMA buffer in flight")
    for b in bad:
        findings.append(Finding(
            check="pallas.grid", where=f"{label}:{b.split(':')[0]}",
            message=(f"{label}: tiling invariant violated — {b}; rows "
                     f"would be dropped or double-visited")))

    if table_cap < pool_pages:
        findings.append(Finding(
            check="pallas.grid", where=f"{label}:table",
            message=(f"{label}: table_cap={table_cap} < pool_pages="
                     f"{pool_pages} — pool slots exist that no page-table "
                     f"entry can ever address")))
    if g.fold_w % LANE:
        findings.append(Finding(
            check="pallas.alignment", where=f"{label}:fold_w",
            severity="warn",
            message=(f"{label}: fold_w={g.fold_w} is not lane-aligned "
                     f"({LANE}); cross-lane reductions pad internally")))
    if page_rows % SUBLANE:
        findings.append(Finding(
            check="pallas.alignment", where=f"{label}:page_rows",
            severity="warn",
            message=(f"{label}: page_rows={page_rows} is not "
                     f"sublane-aligned ({SUBLANE}); every page DMA pads "
                     f"internally")))
    return findings


def estimate_project_vmem(n: int, d: int, m: int, *, block_rows: int,
                          in_dtype: str = "float32",
                          out_dtype: str = "float32") -> dict[str, int]:
    """Resident-bytes breakdown of one ``pca_project`` dispatch: the
    VMEM-resident ``W``, a double-buffered input strip, the f32 accumulator
    and the double-buffered output strip (+ the broadcast scale row when
    the quant epilogue is fused)."""
    block_rows, _, _ = project_geometry(n, block_rows)
    parts = dict(
        w_resident=d * m * 4,
        x_strip=2 * block_rows * d * _width(in_dtype),
        accum=block_rows * m * 4,
        out_strip=2 * block_rows * m * _width(out_dtype),
        scale=m * 4 if out_dtype == "int8" else 0,
    )
    parts["total"] = sum(parts.values())
    return parts


def check_topk_config(n: int, m: int, B: int, k: int, *,
                      block_n: int = 1024, block_b: int = 128,
                      dtype: str = "float32", with_ids: bool = False,
                      budget: int = DEFAULT_BUDGET) -> list[Finding]:
    """Budget + tiling-invariant findings for one top-k scan config."""
    g = topk_geometry(n, m, B, k, block_n=block_n, block_b=block_b)
    label = (f"topk_score[m={m},k={k},bn={g.block_n},bb={g.block_b},"
             f"{dtype}{',ids' if with_ids else ''}]")
    findings: list[Finding] = []

    est = estimate_topk_vmem(g, dtype, with_ids=with_ids)
    if est["total"] > budget:
        top = sorted((v, c) for c, v in est.items() if c != "total")[-2:]
        hot = ", ".join(f"{c}={v // 1024}KiB" for v, c in reversed(top))
        findings.append(Finding(
            check="pallas.vmem-budget", where=label,
            message=(f"{label}: resident VMEM estimate "
                     f"{est['total'] / 2 ** 20:.1f} MiB exceeds the "
                     f"{budget / 2 ** 20:.1f} MiB budget ({hot}) — this "
                     f"config compiles in interpret mode but cannot "
                     f"launch on a real core")))

    # tiling must cover every row exactly once
    bad = []
    if g.nblocks * g.block_n != g.n + g.pad_rows or g.pad_rows >= g.block_n:
        bad.append(f"index strips: {g.nblocks}x{g.block_n} vs n={g.n}"
                   f"+pad{g.pad_rows}")
    if g.nbt * g.block_b != g.b_pad or g.b_pad < g.B:
        bad.append(f"batch tiles: {g.nbt}x{g.block_b} vs B={g.B}"
                   f" pad->{g.b_pad}")
    if g.fold_r * g.fold_w != g.block_n + g.pad_w or g.pad_w >= g.fold_w:
        bad.append(f"fold: {g.fold_r}x{g.fold_w} vs block_n={g.block_n}"
                   f"+pad{g.pad_w}")
    # (fold_w < k is fine: a strip smaller than k contributes what it has;
    # the running-list merge keeps earlier strips' survivors)
    for b in bad:
        findings.append(Finding(
            check="pallas.grid", where=f"{label}:{b.split(':')[0]}",
            message=(f"{label}: tiling invariant violated — {b}; rows "
                     f"would be dropped or double-visited")))

    if g.fold_w % LANE:
        findings.append(Finding(
            check="pallas.alignment", where=f"{label}:fold_w",
            severity="warn",
            message=(f"{label}: fold_w={g.fold_w} is not lane-aligned "
                     f"({LANE}); cross-lane reductions pad internally")))
    if g.block_b % SUBLANE and g.block_b != g.B:
        findings.append(Finding(
            check="pallas.alignment", where=f"{label}:block_b",
            severity="warn",
            message=(f"{label}: block_b={g.block_b} is not sublane-aligned "
                     f"({SUBLANE}); the query tile pads internally")))
    return findings


def check_project_config(n: int, d: int, m: int, *, block_rows: int = 1024,
                         quant: bool = False,
                         budget: int = DEFAULT_BUDGET) -> list[Finding]:
    label = (f"pca_project[d={d},m={m},rows={block_rows}"
             f"{',int8' if quant else ''}]")
    est = estimate_project_vmem(n, d, m, block_rows=block_rows,
                                out_dtype="int8" if quant else "float32")
    findings: list[Finding] = []
    if est["total"] > budget:
        findings.append(Finding(
            check="pallas.vmem-budget", where=label,
            message=(f"{label}: resident VMEM estimate "
                     f"{est['total'] / 2 ** 20:.1f} MiB exceeds the "
                     f"{budget / 2 ** 20:.1f} MiB budget — shrink "
                     f"block_rows or m")))
    br, nblocks, pad = project_geometry(n, block_rows)
    if nblocks * br != n + pad or pad >= br:
        findings.append(Finding(
            check="pallas.grid", where=f"{label}:rows",
            message=(f"{label}: tiling invariant violated — {nblocks}x{br} "
                     f"vs n={n}+pad{pad}")))
    return findings


# ---------------------------------------------------------------------------
# Traced index-map bounds (best-effort, JAX-version-sensitive)
# ---------------------------------------------------------------------------


def _grid_corners(grid: Sequence[int]):
    """All 2^len(grid) corner index tuples (first/last step per dim)."""
    corners = [()]
    for size in grid:
        ends = (0,) if size <= 1 else (0, size - 1)
        corners = [c + (e,) for c in corners for e in ends]
    return corners


def check_traced_index_maps(label: str, fn: Callable, args: Sequence
                            ) -> list[Finding]:
    """Trace ``fn``, locate its ``pallas_call`` eqns and evaluate every
    BlockSpec index map at the grid corners: each block window must start
    inside its operand (only the edge block may overhang). Introspection
    details vary across JAX versions, so any failure to introspect
    degrades to a warn finding rather than an error or a crash."""
    findings: list[Finding] = []
    try:
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        calls = [e for e in iter_all_eqns(jaxpr)
                 if e.primitive.name == "pallas_call"]
        if not calls:
            return [Finding(
                check="pallas.index-map", where=f"{label}:no-pallas-call",
                severity="warn",
                message=f"{label}: traced entry contains no pallas_call")]
        for eqn in calls:
            gm = eqn.params["grid_mapping"]
            grid = tuple(int(s) for s in gm.grid)
            operands = list(eqn.invars) + list(eqn.outvars)
            mappings = list(gm.block_mappings)
            # index/scalar-prefetch operands precede the mapped ones
            operands = operands[len(operands) - len(mappings):] \
                if len(operands) >= len(mappings) else operands
            for bm, var in zip(mappings, operands):
                shape = tuple(var.aval.shape)
                block = tuple(bm.block_shape)
                imap = bm.index_map_jaxpr
                for corner in _grid_corners(grid):
                    idx = jax.core.eval_jaxpr(
                        imap.jaxpr, imap.consts,
                        *(np.int32(c) for c in corner))
                    for ax, (bi, bs) in enumerate(zip(idx, block)):
                        bs = getattr(bs, "block_size", bs)   # Blocked(n)
                        if bs is None or not isinstance(bs, int):
                            continue
                        start = int(bi) * bs
                        if start < 0 or start >= shape[ax]:
                            findings.append(Finding(
                                check="pallas.index-map",
                                where=f"{label}:axis{ax}",
                                message=(
                                    f"{label}: index map sends grid "
                                    f"{corner} to block start {start} "
                                    f"(+{bs}) outside operand dim "
                                    f"{shape[ax]} on axis {ax} — "
                                    f"out-of-bounds window")))
    except Exception as exc:  # noqa: BLE001 — version-sensitive introspection
        findings.append(Finding(
            check="pallas.index-map", where=f"{label}:introspection",
            severity="warn",
            message=(f"{label}: pallas_call introspection unavailable on "
                     f"this JAX version ({type(exc).__name__}: {exc})")))
    return findings


# ---------------------------------------------------------------------------
# The repo's real kernel configs
# ---------------------------------------------------------------------------

#: (n, m, B, k, block_n, block_b, dtype) — the serving configs BENCH_perf
#: exercises plus the defaults the wrappers ship with.
SERVING_TOPK_CONFIGS = (
    (1_000_000, 128, 128, 10, 1024, 128, "int8"),
    (1_000_000, 128, 128, 10, 1024, 128, "float32"),
    (1_000_000, 256, 64, 100, 1024, 128, "float32"),
    # bn=4096 at k=100 busts the budget (17.0 MiB: the three-term score
    # product + fold strips);
    # 2048 is the largest power-of-two strip that fits with headroom
    (10_000_000, 256, 256, 100, 2048, 128, "int8"),
)

SERVING_PROJECT_CONFIGS = (
    (1_000_000, 1024, 256, 1024, False),
    (1_000_000, 1024, 256, 1024, True),
    (1_000_000, 768, 128, 2048, True),
)

#: cascade geometries — the coarse first pass keeps N·k candidates per
#: query over the narrow int8 view, then the rescore scans the U = B·N·k
#: gathered full-m rows with an explicit ``row_ids`` strip.
CASCADE_COARSE_CONFIGS = (
    # n, m_coarse, B, N*k, block_n, block_b, dtype — deepest shortlist N=64
    (1_000_000, 192, 32, 640, 1024, 32, "int8"),
    (1_000_000, 128, 32, 320, 1024, 32, "int8"),
    (1_000_000, 64, 32, 160, 1024, 32, "int8"),
    (1_000_000, 32, 32, 80, 1024, 32, "int8"),
)
#: paged streaming geometries — the bench's paged serve rows plus the
#: oversubscription and rescore shapes. Layout: (table_cap, pool_pages,
#: page_rows, m, B, k, depth, block_b, dtype, with_scale, with_ids).
#: depth counts DMA page buffers in flight, so the f32 depth-4 row prices
#: the deepest overlap the bench sweeps; the pool_pages<live row is the
#: oversubscribed config (same kernel, tail/host pages DMA through the
#: identical buffer window).
PAGED_TOPK_CONFIGS = (
    (8192, 8192, 512, 128, 128, 10, 2, 128, "int8", True, False),
    (8192, 8192, 512, 128, 128, 10, 2, 128, "float32", False, False),
    (8192, 8192, 512, 128, 128, 10, 4, 128, "float32", False, False),
    (4096, 1024, 1024, 256, 64, 100, 2, 64, "int8", True, False),
    (4096, 4096, 512, 384, 32, 10, 2, 32, "int8", True, True),
)

CASCADE_RESCORE_CONFIGS = (
    # U = B*N*k rows at full m, final k — the BENCH_perf cascade grid
    (1_280, 384, 32, 10, 1024, 32, "float32"),    # N=4
    (2_560, 384, 32, 10, 1024, 32, "int8"),       # N=8
    (5_120, 384, 32, 10, 1024, 32, "int8"),       # N=16
    (10_240, 384, 32, 10, 1024, 32, "float32"),   # N=32
    (20_480, 384, 32, 10, 1024, 32, "float32"),   # N=64
)


def run(budget: int = DEFAULT_BUDGET) -> list[Finding]:
    """Budget-check the repo's shipped kernel configs and bounds-check the
    traced dispatches."""
    from repro.kernels.pca_project import (pca_project_pallas,
                                           pca_project_quant_pallas)
    from repro.kernels.topk_score import topk_score_pallas

    findings: list[Finding] = []
    for n, m, B, k, bn, bb, dt in SERVING_TOPK_CONFIGS:
        findings += check_topk_config(n, m, B, k, block_n=bn, block_b=bb,
                                      dtype=dt, budget=budget)
    for n, m, B, k, bn, bb, dt in CASCADE_COARSE_CONFIGS:
        findings += check_topk_config(n, m, B, k, block_n=bn, block_b=bb,
                                      dtype=dt, budget=budget)
    for n, m, B, k, bn, bb, dt in CASCADE_RESCORE_CONFIGS:
        findings += check_topk_config(n, m, B, k, block_n=bn, block_b=bb,
                                      dtype=dt, with_ids=True, budget=budget)
    for tc, pp, R, m, B, k, dep, bb, dt, sc, ids in PAGED_TOPK_CONFIGS:
        findings += check_paged_topk_config(tc, pp, R, m, B, k, depth=dep,
                                            block_b=bb, dtype=dt,
                                            with_scale=sc, with_ids=ids,
                                            budget=budget)
    for n, d, m, rows, quant in SERVING_PROJECT_CONFIGS:
        findings += check_project_config(n, d, m, block_rows=rows,
                                         quant=quant, budget=budget)

    # traced bounds on representative tiny dispatches (nontrivial padding:
    # 600 % 128 != 0 exercises the pad window at the last grid step)
    rng = np.random.default_rng(0)
    D = rng.standard_normal((600, 128)).astype(np.float32)
    Q = rng.standard_normal((4, 128)).astype(np.float32)
    findings += check_traced_index_maps(
        "topk_score_pallas[600x128]",
        functools.partial(topk_score_pallas, k=10, block_n=128, block_b=8),
        (D, Q))
    # cascade rescore mode: the extra (1, n) row_ids operand gets its own
    # BlockSpec — its windows must stay inside the padded ids row too
    ids = np.arange(600, dtype=np.int32)
    findings += check_traced_index_maps(
        "topk_score_pallas[600x128,ids]",
        functools.partial(topk_score_pallas, k=10, block_n=128, block_b=8,
                          row_ids=ids),
        (D, Q))
    # paged mode: the query/carry tiles are the only windowed operands
    # (tables ride SMEM, pools ride ANY) — their windows must stay inside
    # the padded batch, including the partial last page (nvalid < R)
    from repro.kernels.topk_score import topk_score_paged_pallas
    R, npg, mD = 64, 4, 32
    pool = rng.standard_normal((npg, R, mD)).astype(np.float32)
    nv = np.full(npg, R, np.int32)
    nv[-1] = 40
    findings += check_traced_index_maps(
        "topk_score_paged_pallas[4x64p]",
        functools.partial(topk_score_paged_pallas, k=10, depth=2,
                          block_b=8),
        (pool, np.arange(npg, dtype=np.int32), nv,
         np.arange(npg, dtype=np.int32) * R, np.int32(0), np.int32(npg),
         rng.standard_normal((4, mD)).astype(np.float32)))
    X = rng.standard_normal((600, 64)).astype(np.float32)
    W = rng.standard_normal((64, 32)).astype(np.float32)
    findings += check_traced_index_maps(
        "pca_project_pallas[600x64->32]",
        functools.partial(pca_project_pallas, block_rows=128), (X, W))
    scale = np.full((32,), 0.1, np.float32)
    findings += check_traced_index_maps(
        "pca_project_quant_pallas[600x64->32]",
        functools.partial(pca_project_quant_pallas, block_rows=128),
        (X, W, scale))
    return findings
