"""Pallas TPU kernel: fused dense-retrieval scan — score + running top-k.

The paper's query-time hot path is ``s = D̂ q̂`` followed by top-k selection.
A naive implementation materialises the n-length score vector in HBM (write
n·4 bytes, re-read for selection). FAISS-GPU fuses selection into the scan
using warp-shuffle k-heaps — a mechanism with no TPU analogue. TPU-native
adaptation:

  * the index streams HBM→VMEM in **its storage dtype** (f32/bf16/int8) —
    int8 is dequantised in-register (the per-dim scale is folded into the
    query before the kernel), so a pruned+quantised index really moves
    n·m·1 bytes, not a 4x-inflated fp32 shadow copy;
  * a (block_b, m) query tile stays VMEM-resident while (block_n, m) strips
    of the index hit the MXU: ``S_blk = Q · D_blkᵀ`` (for int8/bf16 strips
    the tile is the query's three bf16 terms, ``score_terms``, so the
    kernel scores as the jnp scan does). The grid is
    (batch tiles, index strips) with strips minor, so arbitrarily large B
    works — each batch tile re-streams the index once;
  * a running top-k candidate list (scores + global ids) lives in VMEM
    scratch across grid steps;
  * selection is a **two-stage select**: the strip is partial-reduced by a
    lane fold — (block_b, block_n) reshaped to (block_b, R, W) and maxed
    over the R sub-strips — into a W-wide candidate buffer (W ≈ 2k), which
    is then merged with the running top-k by k unrolled max-extract passes.
    Per pass, only the masking of the extracted id and the lane-fold repair
    touch the full strip, and those are element-wise / sublane reductions;
    every cross-lane (last-axis) reduction is W+k wide instead of block_n
    wide. The merge with the running list is fused into the same k passes
    (no separate 2k extraction stage);
  * a **block-skip guard** (FAISS's "thermometer" trick, TPU-flavoured):
    if a strip cannot improve the running top-k, the merge is skipped
    entirely under ``pl.when`` — for well-shuffled indexes the merge runs
    O(few) times instead of O(n/block_n). The guard is **per-row** by
    default (``guard="row"``): row b improves iff ``max(s[b]) >
    min(run_s[b])``, the strip is skipped iff *no* row improves, and the
    merge writes back only the improving rows (masked merge) — a mixed
    batch where one hot query keeps finding candidates no longer drags
    every other query's merge along. ``guard="batch"`` restores the
    batch-global compare (``max(s) > min(run_s)``) for A/B measurement;
    both produce bit-identical results (for a non-improving row the merge
    is a no-op by construction, since its strict guard plus ascending-id
    tie-breaks would preserve the running list anyway). In plain mode the
    skip fires on equality too, which is exact because strips are visited
    in ascending id order (a later tied score loses the min-id tie-break
    anyway); rescore mode merges on equality — see below.

HBM traffic ≈ bytes(D̂) streamed exactly once per batch tile ⇒ the kernel
is memory-bound at the index-read roofline, which is the paper's O(mn)
term made optimal: pruning d→m (and int8) cuts exactly the streamed bytes.

Outputs are sorted descending; ties break toward the smaller doc id
(matching ``jax.lax.top_k`` first-occurrence semantics).

**Shortlist rescore mode** (``row_ids``): the cascade's second stage scans
a *gathered* shortlist — rows plucked from the full-resolution index — so
row position no longer equals doc id. ``row_ids`` streams a (1, U) int32
id row alongside the strips: the kernel scores position ``j`` but reports
``row_ids[j]``, and masks ``row_ids[j] < 0`` (dedup/pad sentinels) to
-inf instead of the ``n_valid`` iota mask. The min-id-among-ties extract
makes the result independent of gather order, and the block-skip guard
merges (rather than skips) on score equality in this mode, so exactness
holds for *arbitrary* ``row_ids`` order: a tied candidate in a later
strip may carry a smaller id and must get its shot at the tie-break.
(The cascade's ``_shortlist`` still emits ascending ids, which maximises
how often the strict-improvement skip fires; correctness no longer
depends on it.)

**Paged mode** (``topk_score_paged_pallas``): the index lives in a fixed
page pool ``(pool_pages, page_rows, m)`` addressed through an int32 page
table — the layout `PagedIndexStorage` maintains so appends, promotions,
compaction and eviction are pointer swaps. The kernel walks the table's
live slots with a **multi-buffered DMA pipeline**: ``depth`` VMEM page
buffers + DMA semaphores, ``make_async_copy`` of page ``i+depth-1``
started before page ``i`` is scored, so the HBM (or host-tier) stream
overlaps the MXU. The pool stays in its storage dtype end-to-end (int8
pages dequantise in-register); each page's per-page dequant scale row is
DMA'd alongside and folded into the *query* (``q * scale``, the same
fold order as the jnp page walk, so scores are bit-identical). Dead
table slots (``slot >= n_slots``) are masked, never DMA'd. The page
count is a *traced* scalar — growing or shrinking the index never
recompiles. A ``(table_cap, 1, page_rows)`` ``ids_pool`` switches to the
rescore mode (report gathered ids, mask negatives, merge-on-equality
guard), and an optional ``carry`` seeds the running top-k so an
oversubscribed index can stream through a small pool in waves.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = float("-inf")
# f32 scores at full f32 precision on the MXU (several bf16 passes): the
# chip's default single bf16 pass rounds the query to 8 mantissa bits, which
# reorders near-tied candidates against an exact f32 reference. On CPU this
# is a no-op, so interpret-mode results are unchanged.
PRECISION = jax.lax.Precision.HIGHEST
_BIG = jnp.iinfo(jnp.int32).max


def exact_in_bf16(dtype) -> bool:
    """Every value of ``dtype`` is a bf16 value: bf16 itself, and integers
    of one byte (bf16 has 8 significant bits)."""
    dtype = jnp.dtype(dtype)
    return dtype == jnp.bfloat16 or (jnp.issubdtype(dtype, jnp.integer)
                                     and dtype.itemsize == 1)


def _bf16_head(x: jax.Array) -> jax.Array:
    """``x`` with its f32 significand cut to bf16's 8 bits, toward zero.
    Cut on the bits: a compiler that keeps bf16 values at f32 precision
    (XLA's excess precision) may drop an f32 -> bf16 -> f32 round trip,
    which would leave nothing for the lower terms."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def query_terms(Qf: jax.Array, rows_dtype) -> jax.Array:
    """The (B, m) f32 queries as ``score_rows`` takes them against rows of
    ``rows_dtype``: ``Qf`` itself unless bf16 holds every row value
    (``exact_in_bf16``), else its split into three bf16 terms stacked as
    (3B, m), hi then mid then lo.

    hi = the top 8 significant bits of q, mid the next 8, lo the last 8:
    hi + mid + lo == q exactly, each term is a bf16 value, and all three
    share q's sign. A query scaled by an int8 fold splits after the fold.
    """
    if not exact_in_bf16(rows_dtype):
        return Qf
    hi = _bf16_head(Qf)
    mid = _bf16_head(Qf - hi)
    # stacked in f32, whose 8-row tiles any B that is a multiple of 8
    # keeps aligned, then cast: every value is a bf16 value already
    return jnp.concatenate([hi, mid, Qf - hi - mid]).astype(jnp.bfloat16)


def score_terms(q_terms: jax.Array, rows: jax.Array) -> jax.Array:
    """(B, n) f32 scores of ``rows`` (n, m), which bf16 holds exactly,
    against the (3B, m) bf16 terms of ``query_terms``: the rows convert
    straight to bf16, never to f32, and meet the three terms in one
    (3, B, m) x (m, n) matmul with f32 accumulation, summed hi + (mid +
    lo). A product of two bf16 values is exact in f32, so the score is
    the f32 inner product of the same f32 query with the same rows; only
    the order of rounding in the sum differs from an f32 matmul at
    ``PRECISION``.
    """
    B, m = q_terms.shape[0] // 3, q_terms.shape[1]
    s = jax.lax.dot_general(q_terms.reshape(3, B, m),
                            rows.astype(jnp.bfloat16),
                            (((2,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (3, B, n)
    return s[0] + (s[1] + s[2])


def score_rows(q_terms: jax.Array, rows: jax.Array) -> jax.Array:
    """(B, n) f32 scores of ``rows`` (n, m), in their storage dtype,
    against queries prepared by ``query_terms``: ``score_terms`` for rows
    bf16 holds exactly, else one f32 matmul at ``PRECISION``."""
    if q_terms.dtype == jnp.float32:
        return jnp.dot(q_terms, rows.T.astype(jnp.float32),
                       precision=PRECISION)
    return score_terms(q_terms, rows)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


class TopKGeometry(NamedTuple):
    """Grid/padding/fold geometry of one ``topk_score_pallas`` dispatch.

    Single source of truth shared by the kernel wrapper below and the
    static VMEM/grid budget checker (``repro.analysis.pallas_budget``) —
    the checker must reject exactly the configs the kernel would launch,
    so both derive every derived quantity from here.
    """

    n: int            # corpus rows (pre-padding)
    m: int            # index width
    B: int            # query batch (pre-padding)
    k: int
    block_n: int      # index strip rows (clamped)
    block_b: int      # query tile rows (clamped)
    nblocks: int      # index strips in the grid
    pad_rows: int     # rows the last strip overhangs n (masked, not padded)
    b_pad: int        # padded batch
    nbt: int          # batch tiles in the grid
    fold_w: int       # stage-1 candidate-lane width (~2k, lane-aligned)
    fold_r: int       # sub-strips folded per lane
    pad_w: int        # strip padding for the (fold_r, fold_w) reshape

    @property
    def grid(self) -> tuple[int, int]:
        return (self.nbt, self.nblocks)


def topk_geometry(n: int, m: int, B: int, k: int, *, block_n: int = 1024,
                  block_b: int = 128) -> TopKGeometry:
    """Clamp/derive the full dispatch geometry for a (n, m) × (B,) call."""
    block_n = min(block_n, max(8, n))
    nblocks = -(-n // block_n)
    pad_rows = nblocks * block_n - n
    block_b = max(1, min(block_b, _round_up(B, 8)))
    b_pad = _round_up(B, block_b)
    nbt = b_pad // block_b
    # two-stage select geometry: W-wide candidate lanes (~2k, lane-aligned),
    # R sub-strips folded per lane
    fold_w = min(block_n, _round_up(2 * k, 128))
    fold_r = -(-block_n // fold_w)
    pad_w = fold_r * fold_w - block_n
    return TopKGeometry(n=n, m=m, B=B, k=k, block_n=block_n, block_b=block_b,
                        nblocks=nblocks, pad_rows=pad_rows, b_pad=b_pad,
                        nbt=nbt, fold_w=fold_w, fold_r=fold_r, pad_w=pad_w)


def _select_merge(s, gids, rs, ri, k: int, fold_w: int, fold_r: int,
                  pad_w: int):
    """Two-stage select over (running list ∪ strip), as plain values.

    ``s``/``gids``: (bb, strip) scores and global ids; ``rs``/``ri``:
    (bb, k) running list. Returns the merged (bb, k) list, sorted
    descending, ties toward the smaller id. Shared by the flat and paged
    kernels so their tie-break semantics cannot drift apart.
    """
    bb = s.shape[0]
    if pad_w:
        s = jnp.concatenate(
            [s, jnp.full((bb, pad_w), _NEG, jnp.float32)], axis=-1)
        gids = jnp.concatenate(
            [gids, jnp.full((bb, pad_w), _BIG, jnp.int32)], axis=-1)
    fs = s.reshape(bb, fold_r, fold_w)
    fi = gids.reshape(bb, fold_r, fold_w)
    out_s, out_i = [], []
    for _ in range(k):
        # stage 1 — partial reduce: lane fold over the R sub-strips
        # (sublane-axis max; min id among in-lane ties)
        lane_s = jnp.max(fs, axis=1)                     # (bb, W)
        lane_i = jnp.min(
            jnp.where(fs >= lane_s[:, None, :], fi, _BIG), axis=1)
        # stage 2 — merge: extract the global max of the (bb, k+W)
        # candidate buffer = running list ∪ lane maxes. Each lane
        # max is the max of its unextracted elements, so the buffer
        # max is the true max of (running ∪ strip remainder).
        cs = jnp.concatenate([rs, lane_s], axis=-1)
        ci = jnp.concatenate([ri, lane_i], axis=-1)
        m = jnp.max(cs, axis=-1)                         # (bb,)
        sel = jnp.min(
            jnp.where(cs >= m[:, None], ci, _BIG), axis=-1)
        out_s.append(m)
        out_i.append(sel)
        # id-keyed removal (element-wise); next pass's lane fold
        # repairs the affected lane's max
        fs = jnp.where(fi == sel[:, None, None], _NEG, fs)
        rs = jnp.where(ri == sel[:, None], _NEG, rs)
    return jnp.stack(out_s, axis=-1), jnp.stack(out_i, axis=-1)


def _guard_and_merge(s, gids, run_s_ref, run_i_ref, k: int, fold_w: int,
                     fold_r: int, pad_w: int, *, guard: str,
                     merge_on_eq: bool):
    """Block-skip guard + (masked) merge into the running-list refs.

    ``guard="row"``: row b improves iff its strip max beats its own k-th
    best; skip the whole strip iff no row improves (a strictly weaker skip
    condition than the batch-global compare, so it never merges less) and
    write back only improving rows. ``guard="batch"``: the legacy
    batch-global compare. ``merge_on_eq`` selects >= (rescore mode —
    arbitrary id order means a later tie may win the min-id tie-break)
    vs > (ascending-id strips, where a later tie always loses).
    """
    rs0 = run_s_ref[...]
    ri0 = run_i_ref[...]
    row_max = jnp.max(s, axis=-1)                        # (bb,)
    row_kth = jnp.min(rs0, axis=-1)                      # (bb,)
    imp = row_max >= row_kth if merge_on_eq else row_max > row_kth
    if guard == "row":
        can_improve = jnp.any(imp)
    else:
        blk_max = jnp.max(s)
        kth_best = jnp.min(rs0)
        can_improve = blk_max >= kth_best if merge_on_eq else blk_max > kth_best

    @pl.when(can_improve)
    def _merge():
        new_s, new_i = _select_merge(s, gids, rs0, ri0, k, fold_w, fold_r,
                                     pad_w)
        if guard == "row":
            run_s_ref[...] = jnp.where(imp[:, None], new_s, rs0)
            run_i_ref[...] = jnp.where(imp[:, None], new_i, ri0)
        else:
            run_s_ref[...] = new_s
            run_i_ref[...] = new_i


def _make_kernel(k: int, n_valid: int, block_n: int, nblocks: int,
                 fold_w: int, fold_r: int, with_ids: bool = False,
                 guard: str = "row"):
    pad_w = fold_r * fold_w - block_n

    def kernel(q_ref, d_ref, *refs):
        if with_ids:
            ids_ref, out_s_ref, out_i_ref, run_s_ref, run_i_ref = refs
        else:
            out_s_ref, out_i_ref, run_s_ref, run_i_ref = refs
        i = pl.program_id(1)   # index strip (minor); program_id(0) = batch tile

        @pl.when(i == 0)
        def _init():
            run_s_ref[...] = jnp.full_like(run_s_ref, _NEG)
            # unique negative ids so id-keyed masking never collides (more
            # negative than the -1 shortlist sentinels, which DO collide —
            # but only among themselves, at -inf, where it cannot matter)
            bb = run_i_ref.shape[0]
            neg = -(jax.lax.broadcasted_iota(jnp.int32, (bb, k), 1) + 2)
            run_i_ref[...] = neg

        q = q_ref[...]
        if q.dtype == jnp.bfloat16:
            # the tile's (3bb, m) query terms; rows exact in bf16
            s = score_terms(q, d_ref[...])                    # (bb, block_n)
        else:
            blk = d_ref[...].astype(jnp.float32)  # dequant/upcast in-register
            s = jax.lax.dot_general(
                q, blk, dimension_numbers=(((1,), (1,)), ((), ())),
                precision=PRECISION,
                preferred_element_type=jnp.float32)           # (bb, block_n)
        if with_ids:
            # rescore mode: report the gathered rows' true doc ids; negative
            # ids mark dedup/pad slots and never surface
            gids = jnp.broadcast_to(ids_ref[...], s.shape)
            s = jnp.where(gids >= 0, s, _NEG)
        else:
            # also masks the ragged last strip: rows past the array end are
            # unspecified (possibly NaN), and ``where`` discards them
            gids = i * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                          1)
            s = jnp.where(gids < n_valid, s, _NEG)

        # Block-skip guard + masked merge (see _guard_and_merge). Plain mode
        # skips on equality: strips are visited in ascending id order (iota
        # ids), so a later tied score loses the min-id tie-break anyway.
        # Rescore mode must MERGE on equality: row_ids carry arbitrary
        # gathered order, so a tied candidate in a later strip may hold a
        # smaller id and win the tie-break.
        _guard_and_merge(s, gids, run_s_ref, run_i_ref, k, fold_w, fold_r,
                         pad_w, guard=guard, merge_on_eq=with_ids)

        @pl.when(i == nblocks - 1)
        def _finish():
            out_s_ref[...] = run_s_ref[...]
            out_i_ref[...] = jnp.maximum(run_i_ref[...], -1)  # pad ids -> -1

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "block_n", "block_b",
                                             "n_valid", "interpret", "guard"))
def topk_score_pallas(D: jax.Array, Q: jax.Array, *, k: int,
                      block_n: int = 1024, block_b: int = 128,
                      n_valid: int | None = None, interpret: bool = False,
                      row_ids: jax.Array | None = None, guard: str = "row"
                      ) -> tuple[jax.Array, jax.Array]:
    """Fused exact search: top-k of ``Q @ D^T`` per query row.

    D: (n, m) index, streamed in its own dtype (f32/bf16/int8 — int8 scale
       must be pre-folded into Q; the strip is dequantised in-register).
    Q: (B, m) queries. B is tiled into ``block_b``-row grid steps, so B may
       exceed what fits VMEM-resident alongside an index strip.
    ``n_valid``: logical row count; rows with id >= n_valid (e.g. device
       padding in a sharded index) never surface in results.
    ``row_ids``: optional (n,) int32 true doc id per row — rescore mode for
       a gathered shortlist, in any order. Rows with a negative id
       (dedup/pad sentinels) are masked out and ``n_valid`` is ignored.
    ``guard``: "row" (default) per-row block-skip guard with masked merges;
       "batch" the legacy batch-global compare. Bit-identical results.
    Returns (scores (B, k) f32 sorted desc, ids (B, k) int32; -1 pads).
    """
    n, m = D.shape
    B = Q.shape[0]
    nv = n if n_valid is None else min(n_valid, n)
    g = topk_geometry(n, m, B, k, block_n=block_n, block_b=block_b)
    # D is never padded here: the grid's last strip may run past row n, and
    # the kernel masks those rows, so a dispatch never copies the index
    Qf = Q.astype(jnp.float32)
    if g.b_pad != B:
        Qf = jnp.pad(Qf, ((0, g.b_pad - B), (0, 0)))
    Qf = query_terms(Qf, D.dtype)
    # a batch tile's query rows: its own (3 block_b, m) terms where split
    rows_b = g.block_b * (Qf.shape[0] // g.b_pad)
    if rows_b != g.block_b:
        Qf = Qf.reshape(3, g.nbt, g.block_b, m).swapaxes(0, 1).reshape(-1, m)

    kernel = _make_kernel(k, nv, g.block_n, g.nblocks, g.fold_w, g.fold_r,
                          with_ids=row_ids is not None, guard=guard)
    in_specs = [
        pl.BlockSpec((rows_b, m), lambda b, i: (b, 0)),     # Q resident
        pl.BlockSpec((g.block_n, m), lambda b, i: (i, 0)),  # D streams
    ]
    operands = [Qf, D]
    if row_ids is not None:
        ids = row_ids.astype(jnp.int32).reshape(1, n)
        if g.pad_rows:
            ids = jnp.pad(ids, ((0, 0), (0, g.pad_rows)),
                          constant_values=-1)
        in_specs.append(
            pl.BlockSpec((1, g.block_n), lambda b, i: (0, i)))  # ids stream
        operands.append(ids)
    out_s, out_i = pl.pallas_call(
        kernel,
        grid=g.grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((g.block_b, k), lambda b, i: (b, 0)),
            pl.BlockSpec((g.block_b, k), lambda b, i: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g.b_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((g.b_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            _scratch((g.block_b, k), jnp.float32),
            _scratch((g.block_b, k), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return out_s[:B], out_i[:B]


def _scratch(shape, dtype):
    """VMEM scratch allocation (TPU memory space; plain SMEM-free buffer)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


class PagedTopKGeometry(NamedTuple):
    """Grid/fold/buffer geometry of one ``topk_score_paged_pallas`` dispatch.

    Single source of truth shared with ``repro.analysis.pallas_budget``:
    the budget checker prices exactly the buffers this geometry allocates
    (``depth`` DMA page buffers count ``depth`` times in VMEM residency;
    the page table's bytes join the HBM read estimate).
    """

    table_cap: int    # page-table capacity (live slots are traced, <= cap)
    pool_pages: int   # physical page-pool slots
    page_rows: int    # rows per page (R)
    m: int            # index width
    B: int            # query batch (pre-padding)
    k: int
    depth: int        # DMA pipeline depth (page buffers in flight)
    block_b: int      # query tile rows
    b_pad: int
    nbt: int          # batch tiles in the grid
    fold_w: int       # stage-1 candidate-lane width
    fold_r: int       # sub-strips folded per lane
    pad_w: int

    @property
    def grid(self) -> tuple[int]:
        return (self.nbt,)


def paged_topk_geometry(table_cap: int, pool_pages: int, page_rows: int,
                        m: int, B: int, k: int, *, depth: int = 2,
                        block_b: int = 128) -> PagedTopKGeometry:
    block_b = max(1, min(block_b, _round_up(B, 8)))
    b_pad = _round_up(B, block_b)
    nbt = b_pad // block_b
    fold_w = min(page_rows, _round_up(2 * k, 128))
    fold_r = -(-page_rows // fold_w)
    pad_w = fold_r * fold_w - page_rows
    return PagedTopKGeometry(table_cap=table_cap, pool_pages=pool_pages,
                             page_rows=page_rows, m=m, B=B, k=k, depth=depth,
                             block_b=block_b, b_pad=b_pad, nbt=nbt,
                             fold_w=fold_w, fold_r=fold_r, pad_w=pad_w)


def _make_paged_kernel(k: int, table_cap: int, page_rows: int,
                       pool_pages: int, depth: int, fold_w: int, fold_r: int,
                       pad_w: int, *, guard: str, with_tail: bool,
                       with_scale: bool, with_ids: bool, with_carry: bool,
                       finalize: bool):
    from jax.experimental.pallas import tpu as pltpu

    # prefetch distance: page i+dist is started while page i is scored, so
    # depth buffers hold the in-flight window. depth=1 is the serial
    # baseline (start, wait, compute — no overlap).
    dist = depth - 1

    def kernel(*refs):
        bounds_ref, pt_ref, nv_ref, off_ref, q_ref = refs[:5]
        pos = 5
        if with_carry:
            cs_ref, ci_ref = refs[pos:pos + 2]
            pos += 2
        pool_ref = refs[pos]
        pos += 1
        if with_tail:
            tail_ref = refs[pos]
            pos += 1
        if with_scale:
            scale_ref = refs[pos]
            pos += 1
        if with_ids:
            idsp_ref = refs[pos]
            pos += 1
        out_s_ref, out_i_ref, run_s_ref, run_i_ref = refs[pos:pos + 4]

        lo = bounds_ref[0]
        hi = bounds_ref[1]
        bb = q_ref.shape[0]
        if with_carry:
            # wave mode: seed from the previous wave's (un-clamped) list so
            # the unique-negative pad ids survive across waves
            run_s_ref[...] = cs_ref[...]
            run_i_ref[...] = ci_ref[...]
        else:
            run_s_ref[...] = jnp.full((bb, k), _NEG, jnp.float32)
            run_i_ref[...] = -(
                jax.lax.broadcasted_iota(jnp.int32, (bb, k), 1) + 2)

        def body(pbuf, psem, sbuf=None, ssem=None, ibuf=None, isem=None):
            def page_copy(j, slot):
                """DMA descriptor(s) for logical slot j's page: the page
                table picks the physical tier — [0, pool_pages) = stable
                pool, beyond = append tail. Exactly one branch fires."""
                phys = pt_ref[j]
                if with_tail:
                    def run(op):
                        @pl.when(phys < pool_pages)
                        def _pool():
                            op(pltpu.make_async_copy(
                                pool_ref.at[phys], pbuf.at[slot],
                                psem.at[slot]))

                        @pl.when(phys >= pool_pages)
                        def _tail():
                            op(pltpu.make_async_copy(
                                tail_ref.at[phys - pool_pages], pbuf.at[slot],
                                psem.at[slot]))
                else:
                    def run(op):
                        op(pltpu.make_async_copy(pool_ref.at[phys],
                                                 pbuf.at[slot],
                                                 psem.at[slot]))
                return run

            def start(j):
                slot = j % depth
                page_copy(j, slot)(lambda c: c.start())
                if with_scale:
                    pltpu.make_async_copy(scale_ref.at[j],
                                          sbuf.at[slot], ssem.at[slot]).start()
                if with_ids:
                    pltpu.make_async_copy(idsp_ref.at[j],
                                          ibuf.at[slot], isem.at[slot]).start()

            def wait(j):
                slot = j % depth
                page_copy(j, slot)(lambda c: c.wait())
                if with_scale:
                    pltpu.make_async_copy(scale_ref.at[j],
                                          sbuf.at[slot], ssem.at[slot]).wait()
                if with_ids:
                    pltpu.make_async_copy(idsp_ref.at[j],
                                          ibuf.at[slot], isem.at[slot]).wait()

            # warm-up: fill the prefetch window (dead slots never DMA)
            for j in range(min(dist, table_cap)):
                @pl.when(lo + j < hi)
                def _warm(j=j):
                    start(lo + j)

            def step(i, carry):
                if dist:
                    @pl.when(i + dist < hi)
                    def _prefetch():
                        start(i + dist)
                else:
                    start(i)
                wait(i)

                slot = i % depth
                q = q_ref[...]
                if with_scale:
                    # per-page dequant scale folds into the QUERY — the same
                    # fold order as the jnp page walk, so bitwise-equal
                    q = q * sbuf[slot]                  # (bb, m) * (1, m)
                q = query_terms(q, pbuf.dtype)
                if q.dtype == jnp.bfloat16:
                    s = score_terms(q, pbuf[slot])           # (bb, R)
                else:
                    page = pbuf[slot].astype(jnp.float32)  # in-register
                    s = jax.lax.dot_general(
                        q, page, dimension_numbers=(((1,), (1,)), ((), ())),
                        precision=PRECISION,
                        preferred_element_type=jnp.float32)  # (bb, R)
                iota = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                if with_ids:
                    gids = jnp.broadcast_to(ibuf[slot], s.shape)
                    mask = gids >= 0
                else:
                    gids = off_ref[i] + iota
                    mask = iota < nv_ref[i]
                s = jnp.where(mask, s, _NEG)
                _guard_and_merge(s, gids, run_s_ref, run_i_ref, k, fold_w,
                                 fold_r, pad_w, guard=guard,
                                 merge_on_eq=with_ids)
                return carry

            jax.lax.fori_loop(lo, hi, step, 0)

        m = q_ref.shape[1]
        scoped = dict(pbuf=pltpu.VMEM((depth, page_rows, m), pool_ref.dtype),
                      psem=pltpu.SemaphoreType.DMA((depth,)))
        if with_scale:
            scoped.update(sbuf=pltpu.VMEM((depth, 1, m), jnp.float32),
                          ssem=pltpu.SemaphoreType.DMA((depth,)))
        if with_ids:
            scoped.update(ibuf=pltpu.VMEM((depth, 1, page_rows), jnp.int32),
                          isem=pltpu.SemaphoreType.DMA((depth,)))
        pl.run_scoped(body, **scoped)
        out_s_ref[...] = run_s_ref[...]
        if finalize:
            out_i_ref[...] = jnp.maximum(run_i_ref[...], -1)  # pad ids -> -1
        else:
            out_i_ref[...] = run_i_ref[...]

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "depth", "block_b",
                                             "guard", "finalize", "interpret"))
def topk_score_paged_pallas(pool: jax.Array, page_table: jax.Array,
                            page_nvalid: jax.Array, page_offset: jax.Array,
                            lo: jax.Array, hi: jax.Array, Q: jax.Array, *,
                            k: int, tail: jax.Array | None = None,
                            page_scale: jax.Array | None = None,
                            ids_pool: jax.Array | None = None,
                            carry: tuple[jax.Array, jax.Array] | None = None,
                            depth: int = 2, block_b: int = 128,
                            guard: str = "row", finalize: bool = True,
                            interpret: bool = False
                            ) -> tuple[jax.Array, jax.Array]:
    """Fused exact search over a paged index: top-k of ``Q @ pages^T``.

    pool:        (pool_pages, R, m) stable page pool in its storage dtype;
                 pages stream pool→VMEM through ``depth`` DMA buffers.
    tail:        optional (tail_pages, R, m) append-tier pool; page-table
                 entries ``>= pool_pages`` address ``tail[phys-pool_pages]``.
    page_table:  (table_cap,) int32, logical slot -> physical page slot.
    page_nvalid: (table_cap,) int32 live rows per page (partial pages).
    page_offset: (table_cap,) int32 global id of each page's first row.
    lo, hi:      *traced* scalar slot bounds — the kernel walks logical
                 slots [lo, hi), so index growth/shrink never recompiles
                 and an oversubscribed walk splits into device/host runs.
    page_scale:  optional (table_cap, 1, m) f32 per-page dequant scales,
                 folded into Q per page (int8 pools).
    ids_pool:    optional (table_cap, 1, R) int32 true doc ids per page
                 row — rescore mode (negative = masked sentinel).
                 Both keep a unit axis before the tiled one so a page's
                 row is a DMA over the untiled leading axis: Mosaic
                 refuses a one-row slice of a tiled (8-row) axis.
    carry:       optional (B, k) scores/ids seeding the running list —
                 chain runs/waves. Pass the *un-clamped* ids of a
                 ``finalize=False`` call back in.
    Returns (scores (B, k) f32 sorted desc, ids (B, k) int32; -1 pads
    once ``finalize``) — identical semantics to ``topk_score_pallas``.
    """
    from jax.experimental.pallas import tpu as pltpu
    pool_pages, R, m = pool.shape
    table_cap = page_table.shape[0]
    B = Q.shape[0]
    g = paged_topk_geometry(table_cap, pool_pages, R, m, B, k, depth=depth,
                            block_b=block_b)
    Qf = Q.astype(jnp.float32)
    if g.b_pad != B:
        Qf = jnp.pad(Qf, ((0, g.b_pad - B), (0, 0)))

    kernel = _make_paged_kernel(
        k, table_cap, R, pool_pages, depth, g.fold_w, g.fold_r, g.pad_w,
        guard=guard, with_tail=tail is not None,
        with_scale=page_scale is not None, with_ids=ids_pool is not None,
        with_carry=carry is not None, finalize=finalize)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    anyspace = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [smem, smem, smem, smem,
                pl.BlockSpec((g.block_b, m), lambda b: (b, 0))]
    bounds = jnp.stack([jnp.asarray(lo, jnp.int32).reshape(()),
                        jnp.asarray(hi, jnp.int32).reshape(())])
    operands = [bounds,
                page_table.astype(jnp.int32),
                page_nvalid.astype(jnp.int32),
                page_offset.astype(jnp.int32), Qf]
    if carry is not None:
        cs, ci = carry
        cs = cs.astype(jnp.float32)
        ci = ci.astype(jnp.int32)
        if g.b_pad != B:
            cs = jnp.pad(cs, ((0, g.b_pad - B), (0, 0)),
                         constant_values=_NEG)
            ci = jnp.pad(ci, ((0, g.b_pad - B), (0, 0)), constant_values=-1)
        in_specs += [pl.BlockSpec((g.block_b, k), lambda b: (b, 0)),
                     pl.BlockSpec((g.block_b, k), lambda b: (b, 0))]
        operands += [cs, ci]
    in_specs.append(anyspace)
    operands.append(pool)
    if tail is not None:
        in_specs.append(anyspace)
        operands.append(tail)
    if page_scale is not None:
        in_specs.append(anyspace)
        operands.append(page_scale.astype(jnp.float32))
    if ids_pool is not None:
        in_specs.append(anyspace)
        operands.append(ids_pool.astype(jnp.int32))
    out_s, out_i = pl.pallas_call(
        kernel,
        grid=g.grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((g.block_b, k), lambda b: (b, 0)),
            pl.BlockSpec((g.block_b, k), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g.b_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((g.b_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            _scratch((g.block_b, k), jnp.float32),
            _scratch((g.block_b, k), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return out_s[:B], out_i[:B]
