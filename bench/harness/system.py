"""The system under test: the program's index and server over the corpus.

Everything the window drives is the program's own: ``DenseIndex`` or
``ShardedDenseIndex`` over the rows the benchmark generated,
``StaticPruner`` carrying the cut, and ``RetrievalServer`` with its
defaults (``max_batch=32``, ``pipeline_depth=3``, the jnp scan).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def build(config: dict, corpus, devices) -> tuple:
    """(index, pruner) with the index filled in place on ``devices``."""
    from repro.core import DenseIndex, ShardedDenseIndex, StaticPruner
    from repro.core.pca import PCAState
    from repro.launch.serve import _serve_mesh

    n, width, dtype = corpus.n, corpus.width, corpus.dtype
    corpus.fit(devices[0])
    pruner = None
    if corpus.m is not None:
        pruner = StaticPruner(cutoff=config["cutoff"])
        pruner.state = PCAState(
            components=jnp.asarray(corpus.components),
            eigenvalues=jnp.asarray(corpus.eigenvalues),
            mean=jnp.zeros((corpus.d,), jnp.float32),
            n_samples=jnp.asarray(n, jnp.int32), centered=False)
        if pruner.kept_dims != width:
            raise SystemExit(f"bench: cutoff {config['cutoff']} keeps "
                             f"{pruner.kept_dims} dims, the config says "
                             f"{width}")
    scale = None if corpus.scale is None else jnp.asarray(corpus.scale)
    if len(devices) == 1:
        dev = devices[0]
        buf = jnp.zeros((n, width), dtype, device=dev)
        [vectors] = corpus.fill([(dev, buf, 0, n)])
        return DenseIndex(vectors=vectors, scale=scale, backend="jnp"), pruner
    mesh = _serve_mesh(len(devices), "flat")
    n_padded = n + (-n) % len(devices)
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names), None))
    bufs = []
    for dev, idx in sharding.addressable_devices_indices_map(
            (n_padded, width)).items():
        lo, hi = idx[0].start or 0, idx[0].stop or n_padded
        # made on its own device: a zero shard made on the default device
        # and then moved would double that device's set-up peak
        bufs.append((dev, jnp.zeros((hi - lo, width), dtype, device=dev),
                     lo, hi))
    shards = corpus.fill(bufs)
    vectors = jax.make_array_from_single_device_arrays(
        (n_padded, width), sharding, shards)
    return ShardedDenseIndex(vectors=vectors, mesh=mesh, scale=scale,
                             backend="jnp", merge="flat", n_real=n), pruner


def serve(index, pruner, k: int):
    """The program's server with its defaults, its one dispatch shape
    compiled."""
    from repro.launch.serve import RetrievalServer
    server = RetrievalServer(index, pruner, k=k)
    server.warmup()
    return server


def index_bytes(index) -> int:
    """Index bytes held by the busiest device."""
    v = index.vectors
    per = max(np.prod(s.data.shape) for s in v.addressable_shards)
    return int(per * v.dtype.itemsize)
