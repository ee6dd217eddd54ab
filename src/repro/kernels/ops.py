"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` picks by backend: the compiled Mosaic kernels on a TPU,
the Pallas interpreter elsewhere (the CPU test suite). The kernels
themselves default to compiled.
"""
from __future__ import annotations

import jax

from repro.kernels.gram import gram_pallas
from repro.kernels.pca_project import pca_project_pallas, pca_project_quant_pallas
from repro.kernels.topk_score import topk_score_paged_pallas, topk_score_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def gram(D: jax.Array, *, block_rows: int = 1024,
         interpret: bool | None = None) -> jax.Array:
    """Blocked ``D^T D`` (fp32)."""
    if interpret is None:
        interpret = _interpret_default()
    return gram_pallas(D, block_rows=block_rows, interpret=interpret)


def topk_score(D: jax.Array, Q: jax.Array, *, k: int, block_n: int = 1024,
               block_b: int = 128, n_valid: int | None = None,
               interpret: bool | None = None,
               row_ids: jax.Array | None = None, guard: str = "row"
               ) -> tuple[jax.Array, jax.Array]:
    """Fused score + top-k over a document index shard.

    The index streams in its storage dtype (int8 stays int8 — the dequant
    scale must be folded into ``Q``); ``block_b`` tiles the query batch;
    ``n_valid`` masks trailing padding rows out of the results.
    ``row_ids`` switches to shortlist-rescore mode: each row reports its
    gathered true doc id (any order; negative sentinels masked out).
    ``guard`` selects the per-row (default) vs batch-global block-skip.
    """
    if interpret is None:
        interpret = _interpret_default()
    return topk_score_pallas(D, Q, k=k, block_n=block_n, block_b=block_b,
                             n_valid=n_valid, interpret=interpret,
                             row_ids=row_ids, guard=guard)


def topk_score_paged(pool: jax.Array, page_table: jax.Array,
                     page_nvalid: jax.Array, page_offset: jax.Array,
                     lo, hi, Q: jax.Array, *, k: int,
                     tail: jax.Array | None = None,
                     page_scale: jax.Array | None = None,
                     ids_pool: jax.Array | None = None,
                     carry: tuple[jax.Array, jax.Array] | None = None,
                     depth: int = 2, block_b: int = 128, guard: str = "row",
                     finalize: bool = True, interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Fused score + top-k over a paged index (DMA-pipelined page walk).

    Pages stream from the two-tier pool (stable ``pool`` + append ``tail``)
    in their storage dtype through ``depth`` double-buffered async copies;
    the slot bounds ``[lo, hi)`` are traced scalars, so appends /
    promotions / compactions / evictions (all page-pointer swaps) never
    recompile. ``page_scale`` folds per-page int8 dequant scales into the
    query; ``ids_pool`` enables the rescore mode; ``carry`` /
    ``finalize=False`` chain runs and host-tier waves for indexes larger
    than the device pools.
    """
    if interpret is None:
        interpret = _interpret_default()
    return topk_score_paged_pallas(pool, page_table, page_nvalid, page_offset,
                                   lo, hi, Q, k=k, tail=tail,
                                   page_scale=page_scale,
                                   ids_pool=ids_pool, carry=carry, depth=depth,
                                   block_b=block_b, guard=guard,
                                   finalize=finalize, interpret=interpret)


def pca_project(D: jax.Array, W: jax.Array, *, block_rows: int = 1024,
                interpret: bool | None = None) -> jax.Array:
    """Blocked ``D @ W_m`` index build."""
    if interpret is None:
        interpret = _interpret_default()
    return pca_project_pallas(D, W, block_rows=block_rows, interpret=interpret)


def pca_project_quant(D: jax.Array, W: jax.Array, scale: jax.Array, *,
                      block_rows: int = 1024, interpret: bool | None = None
                      ) -> jax.Array:
    """Blocked ``D @ W_m`` with fused int8 quantisation epilogue."""
    if interpret is None:
        interpret = _interpret_default()
    return pca_project_quant_pallas(D, W, scale, block_rows=block_rows,
                                    interpret=interpret)
