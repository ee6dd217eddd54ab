"""Invariant-checker fixtures: each entry breaks exactly one value
contract the abstract interpreter proves on the live pipeline."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.topk_score import topk_score_pallas


def unsorted_rescore(D: jax.Array, q: jax.Array, cids: jax.Array, k: int = 5):
    """Skips ``_shortlist`` entirely: the gathered ids reach the rescore
    kernel in raw coarse-scan order — never sorted, never deduplicated.
    Must trip exactly ``inv.rowids-order``."""
    uids = cids.reshape(-1)
    rows = D[jnp.maximum(uids, 0)]
    return topk_score_pallas(rows, q, k=k, block_n=16, interpret=True,
                             row_ids=uids)


def swapped_dedup_rescore(D: jax.Array, q: jax.Array, cids: jax.Array,
                          k: int = 5):
    """The dedup select with its branches swapped: keeps the *duplicates*
    and sentinels the first occurrences — sorted, but the lowest-id
    keep-first contract is gone. Must trip exactly
    ``inv.dedup-tiebreak``."""
    flat = jnp.sort(cids.reshape(-1))
    dup = jnp.concatenate([jnp.zeros((1,), bool), flat[1:] == flat[:-1]])
    uids = jnp.where(dup, flat, jnp.int32(-1))          # branches swapped
    rows = D[jnp.maximum(uids, 0)]
    return topk_score_pallas(rows, q, k=k, block_n=16, interpret=True,
                             row_ids=uids)


def unmasked_rescore_jnp(D: jax.Array, q: jax.Array, cids: jax.Array,
                         k: int = 5):
    """A correct shortlist whose -1 sentinel slots are never masked to
    -inf before the final top-k: a dedup slot's score competes as a real
    document. Must trip exactly ``inv.sentinel-mask``."""
    flat = jnp.sort(cids.reshape(-1))
    dup = jnp.concatenate([jnp.zeros((1,), bool), flat[1:] == flat[:-1]])
    uids = jnp.where(dup, jnp.int32(-1), flat)          # correct dedup
    rows = D[jnp.maximum(uids, 0)].astype(jnp.float32)
    s = q @ rows.T                                      # missing the mask
    top_s, idx = jax.lax.top_k(s, k)
    ids = jnp.take_along_axis(jnp.broadcast_to(uids[None, :], s.shape),
                              idx, axis=-1)
    return top_s, ids

