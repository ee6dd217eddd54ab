"""Pallas TPU kernel: blocked PCA projection D·W_m with fused quant epilogue.

The offline index-build hot loop: a tall-skinny GEMM ``(n, d) @ (d, m)``
where n is millions-to-billions and d, m ≤ 1024. TPU adaptation:

  * ``W_m`` (d·m ≤ 4 MiB fp32) is VMEM-resident for the whole grid;
  * ``(block_n, d)`` strips of ``D`` stream HBM→VMEM once, hit the MXU, and
    the projected strip goes straight back out — optionally **quantised to
    int8 in-register** (fused epilogue) so the expensive fp32 intermediate
    index never exists in HBM at all. PCA⊕int8 composition writes
    ``m/d × 1/4`` of the baseline index bytes.

Per-dimension scales for the epilogue are supplied by the wrapper (derived
from eigenvalues or a calibration strip) because a per-column max over the
full index would need a second pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def project_geometry(n: int, block_rows: int = 1024) -> tuple[int, int, int]:
    """``(block_rows, nblocks, pad)`` for one projection dispatch — the
    clamp/padding math shared by both wrappers below and the static budget
    checker (``repro.analysis.pallas_budget``)."""
    block_rows = min(block_rows, max(8, n))
    nblocks = -(-n // block_rows)
    pad = nblocks * block_rows - n
    return block_rows, nblocks, pad


def _project_kernel(x_ref, w_ref, out_ref):
    out_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_ref.dtype)


def _project_quant_kernel(x_ref, w_ref, scale_ref, out_ref):
    t = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    inv = 1.0 / jnp.maximum(scale_ref[...], 1e-12)               # (1, m)
    q = jnp.clip(jnp.round(t * inv), -127.0, 127.0)
    out_ref[...] = q.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def pca_project_pallas(D: jax.Array, W: jax.Array, *, block_rows: int = 1024,
                       interpret: bool = False) -> jax.Array:
    """``D @ W`` (fp32 accumulate), blocked over rows."""
    n, d = D.shape
    d2, m = W.shape
    assert d == d2
    block_rows, nblocks, pad = project_geometry(n, block_rows)
    Dp = jnp.pad(D, ((0, pad), (0, 0))) if pad else D
    out = pl.pallas_call(
        _project_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks * block_rows, m), D.dtype),
        interpret=interpret,
    )(D if not pad else Dp, W)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def pca_project_quant_pallas(D: jax.Array, W: jax.Array, scale: jax.Array, *,
                             block_rows: int = 1024, interpret: bool = False
                             ) -> jax.Array:
    """``int8(round((D @ W) / scale))`` with the quantisation fused in VMEM."""
    n, d = D.shape
    m = W.shape[1]
    block_rows, nblocks, pad = project_geometry(n, block_rows)
    Dp = jnp.pad(D, ((0, pad), (0, 0))) if pad else D
    out = pl.pallas_call(
        _project_quant_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d, m), lambda i: (0, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks * block_rows, m), jnp.int8),
        interpret=interpret,
    )(Dp, W, scale.reshape(1, m).astype(jnp.float32))
    return out[:n]
