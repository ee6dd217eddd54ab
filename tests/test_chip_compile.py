"""Compile the served path for a described TPU v5e chip (nothing runs).

Interpret mode accepts any slice and any footprint; Mosaic and the chip's
HBM do not. These compiles catch a kernel the chip's compiler refuses and a
dispatch that does not fit one chip's 16 GB, at deployment widths: m=384
(768-d embeddings pruned at cutoff 0.5) and MS MARCO passage v1's
8,841,823 rows, on one chip and row-sharded over the 2x2 host. The
topology is described inside a fixture, never at import: only one process
may load the TPU library, and every test worker imports this module.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from hlo_text import loop_instructions
from repro.core.index import ShardedDenseIndex, _dense_search_projected, _fill_rows, project_queries
from repro.kernels import ops
from repro.kernels.topk_score import topk_score_paged_pallas, topk_score_pallas

M, D_IN, K, B = 384, 768, 10, 32
MSMARCO_ROWS = 8_841_823
HBM_TEMP_LIMIT = 256 * 2 ** 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # needs the TPU compiler (libtpu, the ``jax[tpu]`` extra), not a chip
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a described chip cannot read cache entries back; keep compiles silent
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The described chip is not the default backend: steer the kernel
    wrappers off the CPU interpreter so the Mosaic kernel is compiled."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_topk_kernel_compiles_on_v5e(one_chip, dtype):
    # a ragged last strip (n % 1024 != 0): the kernel masks it, never pads
    c = jax.jit(lambda d, q: topk_score_pallas(d, q, k=K)).lower(
        _spec(one_chip, (100_003, M), dtype),
        _spec(one_chip, (B, M), jnp.float32)).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < HBM_TEMP_LIMIT


@pytest.mark.parametrize("mode", ["page_scale", "ids_pool"])
def test_paged_kernel_compiles_on_v5e(one_chip, mode):
    table_cap, R, pool_pages = 64, 256, 40
    pool_dtype = jnp.int8 if mode == "page_scale" else jnp.float32
    extra = {"page_scale": (table_cap, 1, M), "ids_pool": (table_cap, 1, R)}
    extra_dtype = jnp.float32 if mode == "page_scale" else jnp.int32
    i32 = jnp.int32
    args = (_spec(one_chip, (pool_pages, R, M), pool_dtype),
            _spec(one_chip, (table_cap,), i32),
            _spec(one_chip, (table_cap,), i32),
            _spec(one_chip, (table_cap,), i32),
            _spec(one_chip, (), i32), _spec(one_chip, (), i32),
            _spec(one_chip, (B, M), jnp.float32),
            _spec(one_chip, extra[mode], extra_dtype))

    def paged(pool, pt, nv, off, lo, hi, q, x):
        return topk_score_paged_pallas(pool, pt, nv, off, lo, hi, q, k=K,
                                       **{mode: x})

    c = jax.jit(paged).lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()


def _score_dots(hlo: str) -> list[tuple[str, list[str]]]:
    """(line, operand types) of each compiled dot under scope ``score``."""
    types = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+\[[\d,]*\])",
                            hlo, re.M))
    out = []
    for line in hlo.splitlines():
        mt = re.search(r"= \S+ convolution\(([^)]*)\)", line)
        if mt and "/score/dot_general" in line:
            out.append((line, [types[a.strip()]
                               for a in mt.group(1).split(",")]))
    return out


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_served_dispatch_fits_one_chip_at_msmarco_scale(one_chip, compiled_kernels,
                                                        dtype, backend):
    """The fused dispatch at 8.8M x 384 fits 16 GB: f32 is 13.6 GB of index,
    so the scan may not copy it (no per-dispatch pad) or upcast it.

    The jnp scan scores each int8 strip against the query's three bf16
    terms, (3, 32, 384): the dot reads bf16 terms and the strip in its
    int8 storage, never at ``HIGHEST``, and the split is cut on the bits
    (two ``and``s under ``score``), which the compiler cannot fold away
    as it may a bf16 round trip. The f32 ``HIGHEST`` dot on rows upcast
    to f32, which this replaced, compiled to 485,888 temp bytes (JAX
    0.9); a (96, 65,536) f32 product or a tripled strip in HBM would add
    at least 25,165,824 B to that. f32 rows keep the f32 dot at
    ``HIGHEST``. The select runs once after the strip loop: no
    conditional is left in it."""
    scale = _spec(one_chip, (M,), jnp.float32) if dtype == jnp.int8 else None
    c = jax.jit(lambda d, s, w, q: _dense_search_projected(
        d, s, w, None, q, K, None, backend)).lower(
        _spec(one_chip, (MSMARCO_ROWS, M), dtype), scale,
        _spec(one_chip, (D_IN, M), jnp.float32),
        _spec(one_chip, (B, D_IN), jnp.float32)).compile()
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < HBM_TEMP_LIMIT
    hlo = c.as_text()
    assert ("tpu_custom_call" in hlo) == (backend == "pallas")
    if backend == "pallas":
        return
    [(line, operands)] = _score_dots(hlo)
    loop = loop_instructions(hlo, "/score/dot_general")
    assert "conditional" not in {op for op, _ in loop}
    if dtype == jnp.int8:
        assert temp < 485_888 + 96 * 65536 * 4
        assert operands == [f"bf16[3,{B},{M}]", f"s8[65536,{M},1]"]
        assert "highest" not in line
        cuts = re.findall(rf"= u32\[{B},{M}\]\S* and\(.*/score/and", hlo)
        assert len(cuts) == 2
    else:
        assert operands == [f"f32[{B},{M}]", f"f32[65536,{M}]"]
        assert "operand_precision={highest,highest}" in line


def test_index_load_fills_in_place_at_msmarco_scale(one_chip):
    """``DenseIndex.load`` writes each chunk into the index buffer in place:
    two copies of a 13.6 GB f32 index would not fit one chip."""
    c = _fill_rows.lower(_spec(one_chip, (MSMARCO_ROWS, M), jnp.float32),
                         _spec(one_chip, (65536, M), jnp.float32),
                         _spec(one_chip, (), jnp.int32)).compile()
    mem = c.memory_analysis()
    assert mem.alias_size_in_bytes >= MSMARCO_ROWS * M * 4   # tile-padded
    assert mem.temp_size_in_bytes < HBM_TEMP_LIMIT


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("merge", ["flat", "hierarchical"])
def test_sharded_dispatch_compiles_on_v5e_2x2(topo, compiled_kernels, merge,
                                               backend):
    """``ShardedDenseIndex.search_projected``'s program over the four chips
    of one host: a quarter of the f32 index per chip, merged by all-gather."""
    devs = np.array(topo.devices)
    mesh = (Mesh(devs.reshape(4), ("data",)) if merge == "flat"
            else Mesh(devs.reshape(2, 2), ("row", "col")))
    n_pad = MSMARCO_ROWS + (-MSMARCO_ROWS) % 4
    rows = NamedSharding(mesh, P(mesh.axis_names, None))
    vectors = jax.ShapeDtypeStruct((n_pad, M), jnp.float32, sharding=rows)
    index = ShardedDenseIndex(vectors=vectors, mesh=mesh, backend=backend,
                              merge=merge, n_real=MSMARCO_ROWS)
    search = index._search_fn(K, merge)
    replicated = NamedSharding(mesh, P())
    c = jax.jit(lambda v, w, q: search(v, project_queries(q, w))).lower(
        vectors, _spec(replicated, (D_IN, M), jnp.float32),
        _spec(replicated, (B, D_IN), jnp.float32)).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes < n_pad * M * 4 // 4 + HBM_TEMP_LIMIT
    assert mem.temp_size_in_bytes < HBM_TEMP_LIMIT
    hlo = c.as_text()
    assert "all-gather" in hlo
    assert ("tpu_custom_call" in hlo) == (backend == "pallas")


def test_unpruned_sharded_search_at_published_widths_on_v5e_2x2(topo):
    """The unpruned baseline as served over the four chips of one host:
    MS MARCO v1 at 768-d f32, 27.2 GB, row-sharded (8,841,824 rows with
    one padding row, 6.79 GB a chip), raw (32, 768) queries replicated,
    ``ShardedDenseIndex._search_fn``'s program."""
    devs = np.array(topo.devices)
    mesh = Mesh(devs.reshape(4), ("data",))
    n_pad = MSMARCO_ROWS + (-MSMARCO_ROWS) % 4
    assert n_pad - MSMARCO_ROWS == 1
    rows = NamedSharding(mesh, P(mesh.axis_names, None))
    vectors = jax.ShapeDtypeStruct((n_pad, D_IN), jnp.float32, sharding=rows)
    index = ShardedDenseIndex(vectors=vectors, mesh=mesh,
                              n_real=MSMARCO_ROWS)
    c = jax.jit(index._search_fn(K, "flat")).lower(
        vectors, _spec(index.query_sharding, (B, D_IN),
                       jnp.float32)).compile()
    mem = c.memory_analysis()
    shard_bytes = n_pad // 4 * D_IN * 4
    assert mem.argument_size_in_bytes <= shard_bytes + B * D_IN * 4
    # the scan scores each 65,536 x 768 f32 strip where it lies: a copy of
    # one strip (201,326,592 B, an upcast or a materialised slice) would
    # reach this limit; the scores and the select's buffers stay far below
    # it (518,144 B with JAX 0.9)
    strip = 65536 * D_IN * 4
    assert mem.temp_size_in_bytes < strip
    hlo = c.as_text()
    assert "all-gather" in hlo
    # the select runs once after the strip loop
    loop = loop_instructions(hlo, "/score/dot_general")
    assert "conditional" not in {op for op, _ in loop}
