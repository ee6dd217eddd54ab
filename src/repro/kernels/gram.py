"""Pallas TPU kernel: blocked Gram matrix D^T D accumulation.

The offline hot loop of PCA fitting. TPU adaptation (vs a GPU cuBLAS syrk):
a ``(block_n, d)`` strip of ``D`` streams HBM→VMEM once per grid step and is
contracted on the MXU; the ``(d, d)`` fp32 accumulator stays VMEM-resident
across the whole grid (d ≤ 1024 for every bi-encoder we target ⇒ ≤ 4 MiB,
well inside v5e's ~128 MiB VMEM). Arithmetic intensity per strip is
``2·block_n·d² / (block_n·d·bytes)`` = ``2d/bytes`` — with d = 768 and bf16
input that is ~768 FLOP/byte, far above the v5e ridge (~240), i.e. the
kernel is compute-bound and MXU-saturating by construction.

Grid: 1-D over row strips. Accumulation pattern: the output BlockSpec maps
every grid step to the same (d, d) block; the accumulator is zeroed at step
0 and revisited thereafter (standard Pallas reduction idiom).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gram_kernel(d_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = d_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        blk, blk,
        dimension_numbers=(((0,), (0,)), ((), ())),   # contract rows: blk^T @ blk
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gram_pallas(D: jax.Array, *, block_rows: int = 1024,
                interpret: bool = False) -> jax.Array:
    """``D^T D`` in fp32 via the blocked Pallas kernel.

    ``D``: (n, d), any float dtype. Rows are zero-padded to a multiple of
    ``block_rows`` (zero rows contribute nothing to the Gram).
    """
    n, d = D.shape
    block_rows = min(block_rows, max(8, n))
    nblocks = -(-n // block_rows)
    pad = nblocks * block_rows - n
    if pad:
        D = jnp.pad(D, ((0, pad), (0, 0)))

    return pl.pallas_call(
        _gram_kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((d, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, d), jnp.float32),
        interpret=interpret,
    )(D)
