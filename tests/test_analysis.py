"""Self-tests for the static-analysis suite (``repro.analysis``).

Two directions: every known-bad fixture must trip EXACTLY its expected
finding (the analyzers detect what they claim to), and the live repo code
must produce zero unsuppressed findings (the gate is green at head, so any
future red is a real regression).
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import Finding, concurrency, jaxpr_lints, pallas_budget
from repro.analysis.fixtures import BAD_TOPK_CONFIG, bad_jaxpr
from repro.analysis.report import apply_baseline, format_text, load_baseline, write_report

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "src" / "repro" / "analysis" / "fixtures"

RNG = np.random.default_rng(5)


def _int8_corpus(n=256, m=32):
    D = (RNG.integers(-127, 128, size=(n, m))).astype(np.int8)
    scale = np.full((m,), 0.05, np.float32)
    q = RNG.standard_normal((3, m)).astype(np.float32)
    return jnp.asarray(D), jnp.asarray(scale), jnp.asarray(q)


# ---------------------------------------------------------------------------
# jaxpr lints: bad fixtures
# ---------------------------------------------------------------------------


def test_upcasting_fixture_flagged():
    D, scale, q = _int8_corpus()
    entry = lambda x: bad_jaxpr.upcasting_search(D, scale, x)  # noqa: E731
    fs = jaxpr_lints.check_storage_dtype_stream(
        "fixture.upcast", entry, (q,), tuple(D.shape), "int8",
        strip_rows=64)
    assert [f.check for f in fs] == ["jaxpr.upcast"]
    assert "convert_element_type" in fs[0].message


def test_strip_sized_dequant_not_flagged():
    """The per-strip in-register dequant is the DESIGN — a convert no
    larger than one strip must pass."""
    D, scale, q = _int8_corpus(n=64)             # corpus == one strip
    entry = lambda x: bad_jaxpr.upcasting_search(D, scale, x)  # noqa: E731
    fs = jaxpr_lints.check_storage_dtype_stream(
        "fixture.strip", entry, (q,), tuple(D.shape), "int8",
        strip_rows=64)
    assert fs == []


def test_two_dispatch_fixture_flagged():
    D, _, q = _int8_corpus()
    Df = D.astype(jnp.float32)
    entry = lambda x: bad_jaxpr.two_dispatch_search(Df, x)  # noqa: E731
    fs = jaxpr_lints.check_dispatch_count("fixture.2disp", entry, (q,),
                                          expected=1)
    assert [f.check for f in fs] == ["jaxpr.extra-dispatch"]
    assert "2 compute dispatches" in fs[0].message


def test_callback_fixture_flagged():
    D, _, q = _int8_corpus()
    Df = D.astype(jnp.float32)
    entry = lambda x: bad_jaxpr.chatty_search(Df, x)  # noqa: E731
    fs = jaxpr_lints.check_no_callbacks("fixture.callback", entry, (q,))
    assert len(fs) == 1 and fs[0].check == "jaxpr.host-callback"


def test_recompile_fixture_flagged():
    D, _, q = _int8_corpus(n=64)
    s = bad_jaxpr.RecompilingSearcher(D.astype(jnp.float32))
    fs = jaxpr_lints.check_recompile_stability(
        lambda live, _off: s.search(q, n_valid=live),
        s.cache_sizes, [(4, 0), (5, 0), (6, 0)], "fixture.recompile")
    assert [f.check for f in fs] == ["jaxpr.recompile"]
    assert "grew" in fs[0].message


def test_fused_entry_is_single_dispatch():
    """The repo's own dense fused path is the known-good control."""
    from repro.core import DenseIndex, StaticPruner
    D = jnp.asarray(RNG.standard_normal((200, 32)).astype(np.float32))
    pruner = StaticPruner(cutoff=0.5).fit(D)
    idx = DenseIndex.build(pruner.prune_index(D), quantize_int8=True)
    W, mean = pruner.projection()
    q = jnp.asarray(RNG.standard_normal((2, 32)).astype(np.float32))
    entry = lambda x: idx.search_projected(x, W, k=5, mean=mean)  # noqa: E731
    assert jaxpr_lints.check_dispatch_count("good", entry, (q,), 1) == []
    assert jaxpr_lints.check_no_callbacks("good", entry, (q,)) == []


# ---------------------------------------------------------------------------
# pallas budget
# ---------------------------------------------------------------------------


def test_over_budget_config_rejected():
    fs = pallas_budget.check_topk_config(**BAD_TOPK_CONFIG)
    errors = [f for f in fs if f.severity == "error"]
    assert [f.check for f in errors] == ["pallas.vmem-budget"]
    assert "exceeds" in errors[0].message


def test_budget_scales_with_block_and_dtype():
    small = pallas_budget.estimate_topk_vmem(
        pallas_budget.topk_geometry(10**6, 128, 64, 10, block_n=512), "int8")
    big = pallas_budget.estimate_topk_vmem(
        pallas_budget.topk_geometry(10**6, 128, 64, 10, block_n=4096),
        "float32")
    assert big["total"] > small["total"]
    assert big["d_strip"] == 4 * 8 * small["d_strip"]  # 8x rows, 4x width


def test_geometry_invariants_hold_on_awkward_shapes():
    for n, m, B, k, bn, bb in ((601, 48, 3, 7, 256, 64),
                               (8, 128, 1, 10, 1024, 128),
                               (4096, 64, 129, 100, 1000, 8)):
        assert pallas_budget.check_topk_config(
            n, m, B, k, block_n=bn, block_b=bb, dtype="int8",
            budget=2**40) == [f for f in pallas_budget.check_topk_config(
                n, m, B, k, block_n=bn, block_b=bb, dtype="int8",
                budget=2**40) if f.check == "pallas.alignment"]


def test_traced_index_maps_accept_good_kernel():
    import functools
    from repro.kernels.topk_score import topk_score_pallas
    D = RNG.standard_normal((300, 128)).astype(np.float32)
    Q = RNG.standard_normal((4, 128)).astype(np.float32)
    fs = pallas_budget.check_traced_index_maps(
        "good", functools.partial(topk_score_pallas, k=5, block_n=128,
                                  block_b=8), (D, Q))
    assert fs == []


def test_traced_index_maps_catch_out_of_bounds():
    from jax.experimental import pallas as pl

    def bad(x):
        return pl.pallas_call(
            lambda x_ref, o_ref: o_ref.__setitem__(Ellipsis, x_ref[...]),
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 16), lambda i: (i + 1, 0))],  # skew
            out_specs=pl.BlockSpec((8, 16), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 16), jnp.float32),
            interpret=True)(x)

    x = np.zeros((32, 16), np.float32)
    fs = pallas_budget.check_traced_index_maps("fixture.oob", bad, (x,))
    assert any(f.check == "pallas.index-map" and f.severity == "error"
               for f in fs)


# ---------------------------------------------------------------------------
# concurrency lint
# ---------------------------------------------------------------------------


def test_bad_locks_fixture_findings_exact():
    fs = concurrency.analyze([("fx", FIXTURES / "bad_locks.py")])
    keys = sorted(f.key for f in fs)
    assert "conc.unguarded-field:fx:UnguardedCounter.peek:count" in keys
    assert "conc.unlocked-shared-mutable:fx:NeverLockedLog:log" in keys
    assert "conc.blocking-under-lock:fx:SleepyWriter.publish:np.asarray" \
        in keys
    assert "conc.blocking-under-lock:fx:SleepyWriter.publish:time.sleep" \
        in keys
    cycles = [f for f in fs if f.check == "conc.lock-order"]
    assert len(cycles) == 1
    assert "Left._lock" in cycles[0].message
    assert "Right._lock" in cycles[0].message
    assert len(fs) == 5                       # nothing beyond the five sins


def test_lock_propagation_suppresses_false_positive():
    """A private helper whose every call site holds the lock is analysed
    as locked — the _mirror_ops pattern."""
    src = '''
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.state = []

    def mutate(self, x):
        with self._lock:
            self._apply(x)

    def replace(self, xs):
        with self._lock:
            self.state.clear()
            for x in xs:
                self._apply(x)

    def _apply(self, x):
        self.state.append(x)
'''
    infos = concurrency.analyze_classes(src, "fx")
    assert concurrency.field_findings(infos[0]) == []


def test_real_serving_code_clean_modulo_baseline():
    fs = concurrency.run()
    report = apply_baseline(fs, load_baseline(REPO
                                              / "analysis_baseline.json"),
                            active_analyzers=["conc"])
    assert report.gating == ()
    assert report.stale == ()


# ---------------------------------------------------------------------------
# report / baseline / CLI
# ---------------------------------------------------------------------------


def _f(check="c.x", where="w", sev="error"):
    return Finding(check=check, where=where, message="m", severity=sev)


def test_baseline_roundtrip(tmp_path):
    findings = [_f(where="a"), _f(where="b"), _f(where="w2", sev="warn")]
    base = tmp_path / "b.json"
    base.write_text(json.dumps({"suppressions": [
        {"key": "c.x:a", "reason": "reviewed"},
        {"key": "c.x:gone", "reason": "paid off"}]}))
    report = apply_baseline(findings, load_baseline(base))
    assert [f.where for f in report.findings] == ["b", "w2"]
    assert report.gating == (findings[1],)       # warn does not gate
    assert report.stale == ("c.x:gone",)
    out = tmp_path / "r.json"
    write_report(report, out)
    doc = json.loads(out.read_text())
    assert doc["schema"] == "repro.analysis/v1"
    assert doc["counts"] == {"findings": 2, "gating": 1, "suppressed": 1,
                             "stale_suppressions": 1}
    txt = format_text(report)
    assert "stale-suppression" in txt and "c.x:b" in txt


def test_missing_baseline_is_empty():
    assert load_baseline(None) == {}
    assert load_baseline("/nonexistent/x.json") == {}


def test_duplicate_baseline_key_rejected(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [
        {"key": "k", "reason": "r1"}, {"key": "k", "reason": "r2"}]}))
    with pytest.raises(ValueError, match="duplicate"):
        load_baseline(p)


def test_cli_conc_gate_green_and_red(tmp_path):
    from repro.analysis.__main__ import main
    out = tmp_path / "rep.json"
    rc = main(["--only", "conc", "--json", str(out),
               "--baseline", str(REPO / "analysis_baseline.json"),
               "--fail-on-findings"])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["counts"]["gating"] == 0
    assert doc["counts"]["suppressed"] == 2
    # without the baseline the same findings gate
    rc = main(["--only", "conc", "--json", "",
               "--baseline", str(tmp_path / "missing.json"),
               "--fail-on-findings"])
    assert rc == 1


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def _costs_doc():
    return json.loads((REPO / "analysis_costs.json").read_text())


def test_cost_baseline_schema_valid():
    from repro.analysis import cost_model
    cost_model.check_costs_schema(_costs_doc())     # must not raise


def test_cost_schema_rejects_missing_metric():
    from repro.analysis import cost_model
    doc = _costs_doc()
    label = next(iter(doc["entries"]))
    del doc["entries"][label]["flops_per_query"]
    with pytest.raises(SystemExit, match="flops_per_query"):
        cost_model.check_costs_schema(doc)


def test_cost_shadow_copy_fixture_fails_gate():
    """An f32 shadow copy of the int8 index must blow the per-query HBM
    byte budget of the entry it impersonates."""
    from repro.analysis import cost_model
    from repro.analysis.fixtures import bad_costs
    ep = bad_costs.shadow_copy_entry()
    doc = _costs_doc()
    sub = {"schema": doc["schema"],
           "entries": {ep.label: doc["entries"][ep.label]}}
    fs = cost_model.compare_costs({ep.label: cost_model.measure_entry(ep)},
                                  sub)
    regressed = {f.where.rsplit(":", 1)[-1] for f in fs
                 if f.check == "cost.regression"}
    assert "hbm_read_bytes_per_query" in regressed
    assert "dispatches" not in regressed         # same dispatch count


def test_cost_extra_dispatch_fixture_fails_gate():
    from repro.analysis import cost_model
    from repro.analysis.fixtures import bad_costs
    ep = bad_costs.extra_dispatch_entry()
    doc = _costs_doc()
    sub = {"schema": doc["schema"],
           "entries": {ep.label: doc["entries"][ep.label]}}
    fs = cost_model.compare_costs({ep.label: cost_model.measure_entry(ep)},
                                  sub)
    assert any(f.check == "cost.regression"
               and f.where.endswith(":dispatches") for f in fs)


def test_cost_bench_crosscheck_flags_inverted_ordering():
    from repro.analysis import cost_model
    entries = {
        "A": {"family": "dense", "bench_key": "ka",
              "hbm_read_bytes_per_query": 100.0,
              "hbm_write_bytes_per_query": 0.0},
        "B": {"family": "dense", "bench_key": "kb",
              "hbm_read_bytes_per_query": 900.0,
              "hbm_write_bytes_per_query": 0.0},
    }
    bench = {"serve_pipeline": {"configs": {
        "ka": {"pipelined": {"worker_qps": 10.0}},
        "kb": {"pipelined": {"worker_qps": 50.0}},
    }}}
    fs = cost_model.bench_crosscheck(entries, bench)
    assert [f.check for f in fs] == ["cost.bench-mismatch"]
    assert fs[0].severity == "warn"
    bench["serve_pipeline"]["configs"]["kb"]["pipelined"]["worker_qps"] = 5.0
    assert cost_model.bench_crosscheck(entries, bench) == []


def test_cli_cost_gate_green_and_red(tmp_path):
    from repro.analysis.__main__ import main
    out = tmp_path / "rep.json"
    rc = main(["--only", "cost", "--json", str(out),
               "--baseline", str(REPO / "analysis_baseline.json"),
               "--costs", str(REPO / "analysis_costs.json"),
               "--bench", str(REPO / "BENCH_perf.json"),
               "--fail-on-findings"])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["counts"]["gating"] == 0
    # doctor one entry's byte budget far below what the code spends: the
    # same gate must go red
    costs = _costs_doc()
    label = "DenseIndex.search_projected[jnp]"
    costs["entries"][label]["hbm_read_bytes_per_query"] /= 4.0
    doctored = tmp_path / "costs.json"
    doctored.write_text(json.dumps(costs))
    rc = main(["--only", "cost", "--json", "",
               "--baseline", str(REPO / "analysis_baseline.json"),
               "--costs", str(doctored),
               "--bench", str(REPO / "BENCH_perf.json"),
               "--fail-on-findings"])
    assert rc == 1


def test_cost_write_baseline_roundtrips(tmp_path):
    from repro.analysis import cost_model
    from repro.analysis.jaxpr_lints import serving_entry_points
    eps = [ep for ep in serving_entry_points() if ep.family == "dense"]
    measured = cost_model.measure_all(eps)
    path = tmp_path / "costs.json"
    cost_model.write_baseline(path, measured)
    doc = json.loads(path.read_text())
    assert cost_model.compare_costs(measured, doc) == []


# ---------------------------------------------------------------------------
# dataflow invariants
# ---------------------------------------------------------------------------


def test_invariants_clean_on_live_entry_points():
    from repro.analysis import invariants
    assert invariants.run() == []


def _inv_fixture_args():
    D = jnp.asarray(RNG.standard_normal((64, 16)).astype(np.float32))
    q = jnp.asarray(RNG.standard_normal((2, 16)).astype(np.float32))
    cids = jnp.asarray(RNG.integers(0, 64, size=(2, 12)).astype(np.int32))
    return D, q, cids


@pytest.mark.parametrize("fn_name,expect", [
    ("unsorted_rescore", "inv.rowids-order"),
    ("swapped_dedup_rescore", "inv.dedup-tiebreak"),
    ("unmasked_rescore_jnp", "inv.sentinel-mask"),
])
def test_invariant_fixtures_trip_exactly_their_finding(fn_name, expect):
    from repro.analysis import invariants
    from repro.analysis.fixtures import bad_invariants
    fs = invariants.check_entry(f"fixture.{fn_name}",
                                getattr(bad_invariants, fn_name),
                                _inv_fixture_args())
    assert [f.check for f in fs] == [expect]
    assert all(f.severity == "error" for f in fs)


# ---------------------------------------------------------------------------
# lock sanitizer
# ---------------------------------------------------------------------------


def test_handoff_fixture_flagged_exactly():
    from repro.analysis import lock_sanitizer
    infos = concurrency.analyze_classes(
        (FIXTURES / "bad_handoff.py").read_text(), "fx")
    fs = lock_sanitizer.handoff_findings(infos)
    assert [f.key for f in fs] == \
        ["locks.handoff-deadlock:fx:StalledPipeline.consume:_q"]
    # and the lock-order pass sees nothing: no cycle exists
    assert concurrency.lock_order_findings(infos) == []


def test_handoff_clean_on_live_tree():
    from repro.analysis import lock_sanitizer
    assert lock_sanitizer.run() == []


def test_static_lock_graph_contents():
    from repro.analysis import lock_sanitizer
    g = lock_sanitizer.static_lock_graph()
    assert g["schema"] == lock_sanitizer.LOCKGRAPH_SCHEMA
    assert {"BatchingQueue._cv", "IndexUpdater._lock",
            "RetrievalServer._index_lock",
            "RetrievalServer._inflight_lock",
            "RetrievalServer._log_lock"} <= set(g["nodes"])
    assert ["IndexUpdater._lock", "RetrievalServer._index_lock"] \
        in g["edges"]
    assert g["handoffs"] == []


def test_crosscheck_divergence_and_unknown_lock():
    from repro.analysis import lock_sanitizer
    static = {"schema": lock_sanitizer.LOCKGRAPH_SCHEMA,
              "nodes": ["A.x", "B.y", "C.z"],
              "edges": [["A.x", "B.y"], ["B.y", "C.z"]]}
    ok = {"schema": lock_sanitizer.LOCKGRAPH_SCHEMA,
          "nodes": ["A.x", "C.z"],
          "edges": [["A.x", "C.z"]]}       # in the transitive closure
    assert lock_sanitizer.crosscheck(ok, static) == []
    bad = {"schema": lock_sanitizer.LOCKGRAPH_SCHEMA,
           "nodes": ["A.x", "B.y", "D.w"],
           "edges": [["B.y", "A.x"]]}      # reversed + unknown node
    fs = lock_sanitizer.crosscheck(bad, static)
    keys = sorted(f.key for f in fs)
    assert keys == ["locks.graph-divergence:B.y->A.x",
                    "locks.unknown-lock:D.w"]
    sev = {f.key: f.severity for f in fs}
    assert sev["locks.unknown-lock:D.w"] == "warn"
    assert sev["locks.graph-divergence:B.y->A.x"] == "error"


def test_lock_graph_schema_rejected(tmp_path):
    from repro.analysis import lock_sanitizer
    p = tmp_path / "g.json"
    p.write_text(json.dumps({"schema": "nope", "nodes": [], "edges": []}))
    with pytest.raises(SystemExit, match="lockgraph"):
        lock_sanitizer.run(lock_graph_path=str(p))


def test_runtime_lock_graph_embeds_in_static():
    """Drive a real updater+server through query/append/swap under a
    fresh monitor: every runtime acquisition order must embed in the
    static graph (the CI cross-check, in miniature)."""
    from repro.analysis import lock_sanitizer
    mon = lock_sanitizer.LockMonitor()
    originals = lock_sanitizer.instrument(mon)
    try:
        from repro.core.maintenance import IndexUpdater
        from repro.launch.serve import RetrievalServer
        corpus = jnp.asarray(RNG.standard_normal((96, 32))
                             .astype(np.float32))
        upd = IndexUpdater.build(corpus, cutoff=0.5, quantize_int8=True,
                                 delta_capacity=16)
        assert type(upd._lock).__name__ == "_TrackedLock"  # late-bound
        srv = RetrievalServer(upd.index, upd.pruner, max_batch=4)
        upd.server = srv
        try:
            srv.query(np.asarray(corpus[0]))
            upd.add_documents(jnp.asarray(
                RNG.standard_normal((8, 32)).astype(np.float32)))
            srv.query(np.asarray(corpus[0]))
        finally:
            srv.close()
    finally:
        lock_sanitizer.uninstrument(originals)
    observed = mon.to_doc()
    # the append path's cross-class order was actually exercised
    assert ["IndexUpdater._lock", "RetrievalServer._index_lock"] \
        in observed["edges"]
    assert lock_sanitizer.crosscheck(
        observed, lock_sanitizer.static_lock_graph()) == []


def test_stale_suppressions_scoped_to_ran_analyzers():
    findings = [_f(check="conc.x", where="a")]
    baseline = {"conc.x:a": "reviewed", "cost.regression:gone": "reviewed",
                "mystery.key:z": "reviewed"}
    # cost analyzer did not run: its unmatched key is NOT stale; an
    # unrecognised prefix always is
    rep = apply_baseline(findings, baseline, active_analyzers=["conc"])
    assert rep.stale == ("mystery.key:z",)
    # with every analyzer active (None) the cost key is genuinely stale
    rep = apply_baseline(findings, baseline, active_analyzers=None)
    assert sorted(rep.stale) == ["cost.regression:gone", "mystery.key:z"]
