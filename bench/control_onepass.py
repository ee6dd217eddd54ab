"""A reading that moves ``rank_gap`` where the ``high`` control does not.

    python3 bench/control_onepass.py --workload <cell> --seeds 1,2,3

``bench/control.py`` reads the reference one precision step below the
configuration's in the program's place. For float32 rows at ``highest``
that is ``high`` (three bf16 passes), whose error swaps no neighbours at
the corpus's score gaps: its ``rank_gap`` reads 0, as the program's does.
This reads the reference two steps down, in one bf16 pass (every operand
rounded to bf16, products summed in float32, as a TPU's default precision
does), over a seeded sample of the cell's query pool, against the same
exact reference. One JSON line per seed, then the smallest reading of
each number. It needs no program and runs on one chip: the corpus of a
seed is the same on one chip or four.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402  (sets the import path of the program too)
from harness import spec  # noqa: E402


def _bf16(x):
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def onepass(exact, Q):
    """The reference in one bf16 pass (projection, if any, and scan), read
    like a served result."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import reference

    corpus, device, k = exact.corpus, exact.device, exact.k
    dot = partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)

    @partial(jax.jit, static_argnames=("c", "n"))
    def block_top(q, rows, base, c: int, n: int):
        s = dot(q, _bf16(rows.astype(jnp.float32)).T)
        ids = base + jnp.arange(rows.shape[0], dtype=jnp.int32)[None, :]
        top, at = jax.lax.top_k(jnp.where(ids < n, s, -jnp.inf), c)
        return top, base + at.astype(jnp.int32)

    q = jax.device_put(np.asarray(Q, np.float32), device)
    if corpus.W is not None:
        q = dot(_bf16(q), _bf16(jax.device_put(corpus.W, device)))
    if corpus.scale is not None:
        q = q * jax.device_put(corpus.scale, device)[None, :]
    q = _bf16(q)
    best = (np.full((len(Q), 0), -np.inf, np.float32),
            np.zeros((len(Q), 0), np.int32))
    for b in range(corpus.nblocks):
        s, i = block_top(q, corpus.rows(b, device), np.int32(b * corpus.block),
                         c=k, n=corpus.n)
        best = reference._merge(best, (np.asarray(s), np.asarray(i)), k)
    return exact.read(*best)


def main(argv=None, root: str = spec.ROOT) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, root)
    cfg, mix = cell["config"], cell["traffic"]

    run.configure_jax()
    [dev] = run.chips(1)
    import numpy as np

    from harness import reference
    from harness.corpus import Corpus

    lines = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        corpus = Corpus(cfg, seed)
        corpus.fit(dev)
        Q = corpus.queries(mix["query_pool"], dev)
        pick = np.sort(np.random.default_rng(seed).choice(
            len(Q), size=min(run.SAMPLE, len(Q)), replace=False))
        exact = reference.Exact(corpus, dev, Q[pick], cfg["k"])
        line = dict(seed=seed, onepass=onepass(exact, Q[pick]),
                    seconds=time.perf_counter() - t)
        print(json.dumps(line), flush=True)
        lines.append(line)
    for key in ("score_err", "rank_gap"):
        print(json.dumps(dict(number=key, onepass_min=min(
            x["onepass"][key] for x in lines))), flush=True)
    return lines


if __name__ == "__main__":
    main()
