"""DenseIndex / ShardedDenseIndex / int8 quantisation."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DenseIndex, ShardedDenseIndex
from repro.core.quantization import dequantize_int8, quantization_error, quantize_int8_per_dim

RNG = np.random.default_rng(7)


def _data(n=2000, d=64):
    D = jnp.asarray(RNG.standard_normal((n, d)), jnp.float32)
    Q = jnp.asarray(RNG.standard_normal((9, d)), jnp.float32)
    return D, Q


def test_exact_search_matches_bruteforce():
    D, Q = _data()
    idx = DenseIndex.build(D)
    s, ids = idx.search(Q, k=10, block=300)
    brute = np.asarray(Q) @ np.asarray(D).T
    want_ids = np.argsort(-brute, axis=1)[:, :10]
    assert (np.asarray(ids) == want_ids).all()
    np.testing.assert_allclose(np.asarray(s),
                               np.take_along_axis(brute, want_ids, 1),
                               rtol=1e-4, atol=1e-4)


def test_block_size_invariance():
    D, Q = _data(777)
    a = DenseIndex.build(D).search(Q, k=7, block=100)
    b = DenseIndex.build(D).search(Q, k=7, block=7777)
    assert (np.asarray(a[1]) == np.asarray(b[1])).all()


def test_pallas_backend_matches_jnp():
    D, Q = _data(500, 32)
    a = DenseIndex.build(D, backend="jnp").search(Q, k=10)
    b = DenseIndex.build(D, backend="pallas").search(Q, k=10)
    for x in range(Q.shape[0]):
        assert set(np.asarray(a[1])[x].tolist()) == set(np.asarray(b[1])[x].tolist())


def test_pallas_backend_honours_block():
    """``block`` used to be silently dropped on the pallas backend — a
    non-default block must reach the kernel and preserve exact results."""
    D, Q = _data(700, 32)
    idx = DenseIndex.build(D, backend="pallas")
    s_def, i_def = idx.search(Q, k=10)
    s_blk, i_blk = idx.search(Q, k=10, block=256)   # non-default block_n
    assert (np.asarray(i_def) == np.asarray(i_blk)).all()
    np.testing.assert_allclose(np.asarray(s_def), np.asarray(s_blk),
                               rtol=1e-5, atol=1e-5)
    # and both match the jnp oracle at another non-default block
    _, want = DenseIndex.build(D).search(Q, k=10, block=130)
    assert (np.asarray(i_blk) == np.asarray(want)).all()


def test_int8_index_recall():
    D, Q = _data(3000, 64)
    full = DenseIndex.build(D)
    q8 = DenseIndex.build(D, quantize_int8=True)
    assert q8.nbytes < full.nbytes / 3.5
    _, ids_f = full.search(Q, k=10)
    _, ids_q = q8.search(Q, k=10)
    # int8 keeps high top-10 overlap
    overlap = np.mean([len(set(np.asarray(ids_f)[i]) & set(np.asarray(ids_q)[i])) / 10
                       for i in range(Q.shape[0])])
    assert overlap > 0.8


def test_quantization_roundtrip_error_small():
    D, _ = _data(1000, 32)
    assert float(quantization_error(D)) < 0.01
    q, s = quantize_int8_per_dim(D)
    assert q.dtype == jnp.int8
    rec = dequantize_int8(q, s)
    assert float(jnp.abs(rec - D).max()) < float(jnp.abs(D).max()) * 0.02


def test_sharded_index_single_device_mesh():
    # 1-device mesh exercises the shard_map merge path end to end
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    D, Q = _data(1024, 32)
    sidx = ShardedDenseIndex.build(D, mesh)
    s, ids = sidx.search(Q, k=10)
    _, want = DenseIndex.build(D).search(Q, k=10)
    assert (np.asarray(ids) == np.asarray(want)).all()


def test_sharded_index_pads_uneven_rows():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    D, Q = _data(1000, 16)   # 1000 rows, any padding must not surface
    sidx = ShardedDenseIndex.build(D, mesh)
    s, ids = sidx.search(Q, k=5)
    assert int(ids.max()) < 1000


# ---------------------------------------------------------------------------
# the scan's bucketed select against its direct per-strip top_k
# ---------------------------------------------------------------------------

def _direct_scan(monkeypatch, *args, **kw):
    """``_scan_topk`` traced afresh with the bucketed select off: today's
    per-strip ``top_k``, the oracle of the bucketed path."""
    from repro.core import index as ix
    with monkeypatch.context() as mp:
        mp.setattr(ix, "_select_plan", lambda k, block: 0)
        scan = jax.jit(ix._scan_topk.__wrapped__,
                       static_argnames=("k", "block", "vma_axes", "guard"))
        return scan(*args, **kw)


def _tied_data(n, d, B, dtype, rng):
    """Integer-valued rows, a quarter of them copies of others, and zero
    rows (+0 and -0), so that equal scores fall in many buckets and strips;
    the first query is all negative against non-negative rows, so its best
    scores are zeros of both signs."""
    D = rng.integers(0, 3, (n, d)).astype(np.float32)
    D[rng.integers(0, n, n // 4)] = D[rng.integers(0, n, n // 4)]
    D[rng.integers(0, n, n // 20)] = 0.0
    D[rng.integers(0, n, n // 20)] = -0.0
    Q = rng.integers(-1, 2, (B, d)).astype(np.float32)
    Q[0] = -rng.integers(1, 3, d)
    D = D.astype(np.int8) if dtype == "int8" else D
    return jnp.asarray(D), jnp.asarray(Q)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("B", [1, 32])
@pytest.mark.parametrize("guard", ["row", "batch"])
@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_bucketed_select_bitwise_matches_direct(monkeypatch, dtype, guard, B):
    """k=8 over 8,192-row strips engages the bucketed select; 3 strips and
    a sliding masked last one. Scores (to the bit) and ids, ties included,
    equal the direct path's."""
    from repro.core.index import BUCKET, _scan_topk, _select_plan
    assert _select_plan(8, 8192) == BUCKET
    D, Q = _tied_data(3 * 8192 + 1000, 16, B, dtype,
                      np.random.default_rng(B))
    got = _scan_topk(D, Q, 8, block=8192, guard=guard)
    want = _direct_scan(monkeypatch, D, Q, k=8, block=8192, guard=guard)
    for g, w in zip(got, want):
        assert (_bits(g) == _bits(w)).all()
    assert (np.asarray(want[0])[0] == 0).all()   # the zeros case is hit


def test_bucketed_select_sharded_one_device(monkeypatch):
    """The per-shard scan under shard_map (``vma_axes``) takes the bucketed
    select too, with the direct path's bits."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    D, Q = _tied_data(3 * 8192 + 1000, 16, 32, "f32",
                      np.random.default_rng(3))
    got = ShardedDenseIndex.build(D, mesh).search(Q, k=8, block=8192)
    want = _direct_scan(monkeypatch, D, Q, k=8, block=8192)
    for g, w in zip(got, want):
        assert (_bits(g) == _bits(w)).all()


def _strips_n(nstrips, block=8192):
    """Rows for ``nstrips`` strips of ``block``: the last one slides back
    and masks all but 1,000 of its rows."""
    return (nstrips - 1) * block + 1000


def _layout_data(layout, nstrips, B, dtype, rng, block=8192):
    """``_tied_data`` over ``nstrips`` strips, where ``layout`` places the
    best rows of every query (every layout but ``"tied"`` draws positive
    queries, so that a row of larger values scores higher):

    * ``"tied"``: as drawn, ties everywhere;
    * ``"straddle"``: the k=8 best rows, tied, in the live part of the last
      strip's straddling bucket (the bucket that holds rows the last strip
      masks and rows it scores), the next best in its masked part;
    * ``"straddle_masked"``: the 6 best rows there, and the 7th best in
      its masked part, so a rescore that dropped the mask would return
      that row twice;
    * ``"shared_max"``: one best row copied into five buckets of every
      strip, so more than k buckets across strips share one maximum.

    Returns (D, Q, the expected leading ids of every query or None)."""
    n = _strips_n(nstrips, block)
    D, Q = _tied_data(n, 16, B, dtype, rng)
    if layout == "tied":
        return D, Q, None
    from repro.core.index import BUCKET
    D = np.array(D)
    Q = jnp.asarray(rng.integers(1, 3, (B, 16)).astype(np.float32))
    start, at = (nstrips - 1) * block, n - block
    assert (start - at) % BUCKET == 24         # masked rows open the bucket
    if layout == "straddle":
        best = start + np.arange(8)
        assert len({(r - at) // BUCKET for r in best}) == 1
        D[start - 24:start] = 3
        D[best] = 4
    elif layout == "straddle_masked":
        best = np.append(start + np.arange(6), start - 1)
        D[best[:6]] = 4
        D[start - 1] = 3
    else:
        best = np.sort([j * block + off for j in range(nstrips)
                        for off in (100, 300, 500, 700, 900)])[:8]
        D[best] = 4
    return jnp.asarray(D), Q, best


@pytest.mark.parametrize("B", [1, 32])
@pytest.mark.parametrize("dtype", ["f32", "int8"])
@pytest.mark.parametrize("nstrips", [2, 5])
@pytest.mark.parametrize("layout", ["tied", "straddle", "straddle_masked",
                                    "shared_max"])
def test_deferred_select_bitwise_matches_direct(monkeypatch, layout, nstrips,
                                                dtype, B):
    """The select deferred to once a call returns the direct path's scores
    (to the bit) and ids, ties included: two strips (one full, the last
    sliding and masked) and five, with the top-k in the straddling bucket
    of the last strip, with a row of its masked part among the top-k, and
    with the k best buckets sharing one maximum across strips."""
    from repro.core.index import BUCKET, _scan_topk, _select_plan
    assert _select_plan(8, 8192) == BUCKET
    D, Q, best = _layout_data(layout, nstrips, B, dtype,
                              np.random.default_rng(nstrips * 100 + B))
    got = _scan_topk(D, Q, 8, block=8192)
    want = _direct_scan(monkeypatch, D, Q, k=8, block=8192)
    for g, w in zip(got, want):
        assert (_bits(g) == _bits(w)).all()
    if best is not None:
        assert (np.asarray(got[1])[:, :len(best)] == best[None, :]).all()


@pytest.mark.parametrize("layout", ["tied", "straddle", "straddle_masked",
                                    "shared_max"])
def test_deferred_select_sharded_one_device(monkeypatch, layout):
    """``ShardedDenseIndex``'s per-shard scan (under shard_map, no carry
    left to mark varying) defers the select too, with the direct path's
    bits."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    D, Q, _ = _layout_data(layout, 2, 32, "f32", np.random.default_rng(4))
    got = ShardedDenseIndex.build(D, mesh).search(Q, k=8, block=8192)
    want = _direct_scan(monkeypatch, D, Q, k=8, block=8192)
    for g, w in zip(got, want):
        assert (_bits(g) == _bits(w)).all()


@pytest.mark.parametrize("nstrips", [2, 5])
def test_deferred_select_untied_within_f32_bound(monkeypatch, nstrips):
    """On untied float data the deferred select returns the direct path's
    ids, with every score within the f32 accumulation bound of its
    direct score."""
    from repro.core.index import _scan_topk
    rng = np.random.default_rng(15)
    D = rng.standard_normal((_strips_n(nstrips), 32)).astype(np.float32)
    Q = rng.standard_normal((32, 32)).astype(np.float32)
    s1, i1 = _scan_topk(jnp.asarray(D), jnp.asarray(Q), 10, block=8192)
    s0, i0 = _direct_scan(monkeypatch, jnp.asarray(D), jnp.asarray(Q),
                          k=10, block=8192)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    bound = _f32_bound(Q.astype(np.float64), D.astype(np.float64))
    gap = np.abs(np.asarray(s1, np.float64) - np.asarray(s0, np.float64))
    assert (gap <= np.take_along_axis(bound, np.asarray(i0), 1)).all()


@pytest.mark.parametrize("k,block,engaged", [
    (10, 65536, True),       # the served shape
    (8, 8192, True),
    (640, 65536, False),     # a cascade coarse scan, N·k = 64·10
    (10, 65536 + 64, False),  # not a whole number of buckets
    (10, 256, False),        # a small test strip
])
def test_select_plan(k, block, engaged):
    from repro.core.index import BUCKET, _select_plan
    assert _select_plan(k, block) == (BUCKET if engaged else 0)


def test_bucketed_select_scopes_in_hlo():
    """On the bucketed path the compiled strip loop scores the strip and
    takes its ``/select/bucket/`` maxima, and holds no conditional, no
    sort and no TopK; every ``/select/refine/`` op lies outside it. The
    direct path keeps its guarded conditional and its ``/select/`` ops in
    the loop, and has neither nested scope."""
    from hlo_text import loop_instructions
    from repro.core.index import _scan_topk
    D, Q = _data(3 * 8192 + 1000, 16)

    def compiled(block):
        return _scan_topk.lower(D, Q, 8, block=block).compile().as_text()

    bucketed = compiled(8192)
    loop = loop_instructions(bucketed, "/select/bucket/")
    assert any("/score/" in line for _, line in loop)
    assert not {"conditional", "sort", "topk"} & {op for op, _ in loop}
    assert not any("TopK" in line or "/select/refine/" in line
                   for _, line in loop)
    assert "/select/refine/" in bucketed
    direct = compiled(256)
    loop = loop_instructions(direct, "/score/")
    assert "conditional" in {op for op, _ in loop}
    assert any("/select/" in line for _, line in loop)
    names = re.findall(r'op_name="([^"]*)"', direct)
    assert not any("/bucket/" in n or "/refine/" in n for n in names)


# ---------------------------------------------------------------------------
# scoring rows bf16 holds exactly: the three-term bf16 split of the query
# ---------------------------------------------------------------------------

U = 2.0 ** -24   # unit roundoff of f32


@pytest.mark.parametrize("magnitude", [1e-6, 1e-3, 1.0, 1e3, 1e6])
def test_query_terms_reproduce_query(magnitude):
    """hi + mid + lo == q in float64 across the range a scale-folded int8
    query spans (a 768-d query projected and scaled by |x|max / 127 per
    dimension); bf16 and int8 rows split alike, f32 rows get q itself."""
    from repro.kernels.topk_score import query_terms
    q = (RNG.standard_normal((32, 384)) * magnitude).astype(np.float32)
    for dtype in (jnp.int8, jnp.bfloat16):
        t = query_terms(jnp.asarray(q), dtype)
        assert t.dtype == jnp.bfloat16 and t.shape == (96, 384)
        t = np.asarray(t.astype(jnp.float32), np.float64)
        np.testing.assert_array_equal(t[:32] + t[32:64] + t[64:], q)
    assert query_terms(jnp.asarray(q), jnp.float32).dtype == jnp.float32


def test_query_terms_reproduce_special_values():
    """Zeros of both signs, values near the ends of the range the terms
    hold, and every pattern of the 16 mantissa bits below hi's in one
    binade."""
    from repro.kernels.topk_score import query_terms
    edge = np.array([0.0, -0.0, 1e-30, -1e-30, 1.17549435e-30, 1e30, -1e30,
                     3e38, -3e38, 1.0 + 2.0 ** -23, -(2.0 - 2.0 ** -23)],
                    np.float32)
    mant = (1.0 + np.arange(2 ** 16, dtype=np.float64) * 2.0 ** -23)
    q = np.concatenate([edge, mant.astype(np.float32),
                        -mant.astype(np.float32)])
    q = np.pad(q, (0, (-q.size) % 8)).reshape(8, -1)
    t = np.asarray(query_terms(jnp.asarray(q), jnp.int8).astype(jnp.float32),
                   np.float64)
    np.testing.assert_array_equal(t[:8] + t[8:16] + t[16:], q)
    # every term shares q's sign: |hi| + |mid| + |lo| == |q|
    assert (np.sign(t.reshape(3, 8, -1)) * np.sign(q) >= 0).all()


def _exact_rows(dtype, n, m, rng):
    """Rows every value of which bf16 holds: int8, or bf16 rows rounded
    from f32 draws."""
    if dtype == "int8":
        return jnp.asarray(rng.integers(-127, 128, (n, m)), jnp.int8)
    return jnp.asarray(rng.standard_normal((n, m)), jnp.bfloat16)


def _f32_bound(Q, D):
    """Forward error bound of an f32 inner product of length m: each
    partial sum rounds once, so |fl(q.r) - q.r| <= m u sum_i |q_i r_i|.
    The split sums three such products, of hi, mid and lo, which share
    q's sign so that their absolute values add to |q|, then rounds twice
    more: (m + 2) u (1 + 2^-7) sum_i |q_i r_i|, the last factor for terms
    of order u^2, covers both it and the f32 matmul of the rows upcast."""
    m = Q.shape[1]
    absdot = np.abs(Q) @ np.abs(D).T
    return (m + 2) * U * (1 + 2.0 ** -7) * absdot


@pytest.mark.parametrize("dtype", ["int8", "bf16"])
def test_split_scan_within_f32_bound_of_float64(dtype):
    """The scan over rows bf16 holds exactly: every returned score lies
    within the f32 accumulation bound of its row's float64 score, the
    same bound the f32 ``HIGHEST`` path of the same rows meets, and the
    ids are the float64 top-k (distinct scores, gaps above the bound)."""
    from repro.core.index import _scan_topk
    rng = np.random.default_rng(11)
    D = _exact_rows(dtype, 3 * 4096 + 333, 384, rng)
    Q = (rng.standard_normal((32, 384)) * 0.02).astype(np.float32)
    Dn = np.asarray(D.astype(jnp.float32), np.float64)
    exact = Q.astype(np.float64) @ Dn.T
    bound = _f32_bound(Q.astype(np.float64), Dn)
    want = np.argsort(-exact, axis=1, kind="stable")[:, :10]
    for rows in (D, D.astype(jnp.float32)):          # split, then HIGHEST
        s, ids = _scan_topk(rows, jnp.asarray(Q), 10, block=4096)
        s, ids = np.asarray(s, np.float64), np.asarray(ids)
        np.testing.assert_array_equal(ids, want)
        err = np.abs(s - np.take_along_axis(exact, ids, 1))
        assert (err <= np.take_along_axis(bound, ids, 1)).all()


@pytest.mark.parametrize("dtype", ["int8", "bf16"])
@pytest.mark.parametrize("block", [4096, 65536])
def test_split_scan_ids_match_f32_path(dtype, block):
    """On untied data the split path returns the ids of the parent's path,
    ``_scan_topk`` over the rows upcast to f32 beforehand (several strips
    and a single strip), with scores within the f32 bound of each other."""
    from repro.core.index import _scan_topk
    rng = np.random.default_rng(12)
    D = _exact_rows(dtype, 3 * 4096 + 777, 96, rng)
    Q = jnp.asarray(rng.standard_normal((9, 96)).astype(np.float32))
    s1, i1 = _scan_topk(D, Q, 10, block=block)
    s0, i0 = _scan_topk(D.astype(jnp.float32), Q, 10, block=block)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    bound = 2 * _f32_bound(np.asarray(Q, np.float64),
                           np.asarray(D.astype(jnp.float32), np.float64))
    gap = np.abs(np.asarray(s1, np.float64) - np.asarray(s0, np.float64))
    assert (gap <= np.take_along_axis(bound, np.asarray(i0), 1)).all()


def _hlo_instructions(hlo: str) -> dict:
    """name -> (dtype, shape, opcode, operand names, line) of every
    instruction of an HLO module's text."""
    out = {}
    pat = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w-]+)\(([^)]*)\)")
    for line in hlo.splitlines():
        mt = pat.match(line)
        if mt:
            name, dtype, dims, op, args = mt.groups()
            shape = tuple(int(d) for d in dims.split(",") if d)
            ops = [a.strip().lstrip("%") for a in args.split(",") if a]
            out[name] = (dtype, shape, op, ops, line)
    return out


@pytest.mark.parametrize("dtype", ["int8", "bf16", "f32"])
def test_score_dot_form_follows_row_dtype_in_hlo(dtype):
    """Engagement, read from the lowered HLO: for rows bf16 holds
    exactly, the one dot under ``score`` multiplies bf16 operands into f32
    and no strip is converted to f32; for f32 rows it is the f32 dot at
    ``HIGHEST``."""
    from repro.core.index import _scan_topk
    rng = np.random.default_rng(13)
    n, block = 3 * 4096 + 5, 4096
    if dtype == "f32":
        D = jnp.asarray(rng.standard_normal((n, 32)), jnp.float32)
    else:
        D = _exact_rows(dtype, n, 32, rng)
    Q = jnp.asarray(rng.standard_normal((8, 32)).astype(np.float32))
    hlo = _scan_topk.lower(D, Q, 8, block=block).as_text(dialect="hlo",
                                                          debug_info=True)
    ins = _hlo_instructions(hlo)
    dots = [v for v in ins.values()
            if v[2] == "dot" and 'op_name="score/' in v[4]]
    assert len(dots) == 1
    out_dtype, out_shape, _, operands, line = dots[0]
    assert out_dtype == "f32"
    kinds = [ins[o][0] for o in operands]
    if dtype == "f32":
        assert kinds == ["f32", "f32"] and out_shape == (8, block)
        assert "operand_precision={highest,highest}" in line
        return
    assert kinds == ["bf16", "bf16"] and out_shape == (3, 8, block)
    assert "highest" not in line
    strips = [v for v in ins.values() if v[2] == "convert" and v[0] == "f32"
              and ins[v[3][0]][1][:1] in ((block,), (n,))]
    assert strips == []
