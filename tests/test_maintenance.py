"""Index maintenance: incremental adds + drift-triggered refit policy."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.maintenance import IndexUpdater, captured_energy
from repro.core.pruning import StaticPruner
from repro.data.synthetic import make_corpus


def _corpus(seed=0, n=2000, domain_seed=None):
    D, _ = make_corpus("tasb", n_docs=n, d=96, seed=seed,
                       domain_seed=domain_seed)
    return jnp.asarray(D)


def test_add_documents_searchable():
    D = _corpus()
    up = IndexUpdater.build(D, cutoff=0.5)
    n0 = up.index.n
    new = _corpus(seed=0, n=200, domain_seed=1)[:100]
    up.add_documents(new)
    assert up.index.n == n0 + 100
    # a newly added doc retrieves itself
    _, ids = up.search(new[3][None, :], k=5)
    assert n0 + 3 in np.asarray(ids)[0].tolist()


def test_updater_refuses_sharded_index_at_construction():
    """Appends over a sharded base are not supported: the updater says so
    when it is built, not at the first append."""
    import jax
    from repro.core.index import ShardedDenseIndex
    D = _corpus(n=256)
    pruner = StaticPruner(cutoff=0.5).fit(D)
    mesh = jax.make_mesh((1,), ("data",))
    sidx = ShardedDenseIndex.build(pruner.prune_index(D), mesh)
    with pytest.raises(TypeError, match="queue 2 item 5"):
        IndexUpdater(pruner=pruner, index=sidx)


def test_add_documents_int8_path():
    D = _corpus()
    up = IndexUpdater.build(D, cutoff=0.5, quantize_int8=True)
    up.add_documents(_corpus(seed=0, n=120, domain_seed=2)[:50])
    st = up.index.storage
    assert up.index.storage_dtype == jnp.int8
    assert st.extent_rows(1).dtype == np.int8
    assert st.extents[1].kind == "delta"
    assert st.extents[1].scale is not None          # its OWN scale
    assert not np.array_equal(st.extents[1].scale, st.extents[0].scale)
    s, ids = up.search(D[:2], k=5)
    assert np.isfinite(np.asarray(s)).all()


def test_drift_low_in_domain_high_out_of_domain():
    D = _corpus()
    up = IndexUpdater.build(D, cutoff=0.5)
    in_dom = _corpus(seed=0, n=500, domain_seed=3)  # same encoder basis
    assert up.drift_score(in_dom) > 0.85
    # totally different basis (different encoder seed => rotated space)
    ood, _ = make_corpus("tasb", n_docs=500, d=96, seed=99)
    assert up.drift_score(jnp.asarray(ood)) < up.drift_score(in_dom)


def test_refit_restores_energy():
    D = _corpus()
    up = IndexUpdater.build(D, cutoff=0.5)
    shifted, _ = make_corpus("tasb", n_docs=2000, d=96, seed=99)
    shifted = jnp.asarray(shifted)
    before = up.drift_score(shifted)
    up.refit(shifted)
    after = up.drift_score(shifted)
    assert after > before
    assert abs(up.drift_score(shifted) - 1.0) < 0.05


def test_drift_score_without_fit_energy():
    """Directly-constructed updater (dataclass default fit_energy=None)
    used to raise TypeError in drift_score; the reference energy is now
    derived lazily from the eigenvalues — and matches the corpus-measured
    one on the fit corpus itself."""
    D = _corpus()
    pruner = StaticPruner(cutoff=0.5).fit(D)
    up = IndexUpdater(pruner=pruner, index=pruner.build_index(D))
    assert up.fit_energy is None
    score = up.drift_score(D[:500])        # must not raise
    assert 0.5 < score < 1.5
    # lazy reference == measured reference (uncentered Gram identity)
    measured = captured_energy(D, pruner)
    assert abs(up._reference_energy() - measured) < 2e-3


def test_drift_reference_centered_fit():
    """The lazy reference must also be exact for center=True fits, where
    captured_energy's uncentered ratio picks up the mean's energy."""
    D = _corpus() + 3.0                    # nonzero mean: centering matters
    pruner = StaticPruner(cutoff=0.5, center=True).fit(D)
    up = IndexUpdater(pruner=pruner, index=pruner.build_index(D))
    measured = captured_energy(D, pruner)
    assert abs(up._reference_energy() - measured) < 2e-3
    assert abs(up.drift_score(D) - 1.0) < 5e-3


def test_ood_append_scale_policy_trips_refit():
    """The frozen-scale regression, inverted: an out-of-distribution append
    used to clip silently under the base's scale. Per-delta scales now
    widen instead (clip_fraction is structurally zero), and the policy
    signal is the scale DIVERGENCE between delta and base."""
    D = _corpus()
    up = IndexUpdater.build(D, cutoff=0.5, quantize_int8=True)
    # in-distribution append: delta scale stays near the base's
    in_dom = _corpus(seed=0, n=200, domain_seed=4)[:100]
    up.add_documents(in_dom)
    assert up.clip_fraction == 0.0
    assert up.scale_divergence() < 4.0
    assert not up.needs_refit(in_dom)
    # OOD magnitudes: same subspace (drift blind), 50x the dynamic range —
    # nothing clips, but the delta's widened scale flags the divergence
    up.add_documents(50.0 * in_dom)
    assert up.clip_fraction == 0.0
    assert up.scale_divergence() > 4.0
    # drift_score can't see it (same subspace, energy ratio unchanged)...
    assert up.drift_score(50.0 * in_dom) > 0.9
    # ...but the scale policy trips the refit
    assert up.needs_refit(50.0 * in_dom)


def test_clip_fraction_zero_on_float_index():
    D = _corpus()
    up = IndexUpdater.build(D, cutoff=0.5)
    up.add_documents(1e6 * _corpus(seed=0, n=120, domain_seed=5)[:40])
    assert up.clip_fraction == 0.0
    assert up.scale_divergence() == 1.0             # unquantised: no scales


def test_delta_fraction_trips_refit():
    """Compaction pressure: once the delta pages are most of the index,
    the policy asks for a compaction even with zero drift."""
    D = _corpus(n=400)
    up = IndexUpdater.build(D, cutoff=0.5, page_rows=64)
    in_dom = _corpus(seed=0, n=900, domain_seed=7)[400:]
    up.add_documents(in_dom)
    assert up.delta_fraction > 0.5
    # threshold=0 disables the drift leg: delta_fraction alone must trip
    assert up.needs_refit(in_dom[:100], threshold=0.0)
    up.compact()
    assert up.delta_fraction == 0.0
    assert not up.needs_refit(in_dom[:100], threshold=0.0)


def test_delta_fraction_counts_pages_at_default_page_size():
    """At the default 256-row pages the threshold is on pages, not rows:
    500 delta rows over a 400-row base are 2 of 4 pages, exactly 0.5, so
    the policy holds (5/9 of the rows would have tripped it); 13 more
    rows open a third delta page, 3 of 5, and it trips."""
    D = _corpus(n=400)
    up = IndexUpdater.build(D, cutoff=0.5)
    assert up.index.storage.page_rows == 256
    in_dom = _corpus(seed=0, n=913, domain_seed=7)[400:]
    up.add_documents(in_dom[:500])
    assert up.delta_fraction == 0.5
    assert not up.needs_refit(in_dom[:100], threshold=0.0)
    up.add_documents(in_dom[500:])
    assert up.delta_fraction == 0.6
    assert up.needs_refit(in_dom[:100], threshold=0.0)


def test_refit_resets_segments_and_telemetry():
    D = _corpus()
    up = IndexUpdater.build(D, cutoff=0.5, quantize_int8=True)
    up.add_documents(50.0 * _corpus(seed=0, n=120, domain_seed=6)[:40])
    assert up.scale_divergence() > 1.0
    assert up.index.delta_pages > 0
    up.refit(D)
    assert up.scale_divergence() == 1.0
    assert up.index.delta_pages == 0
    assert up.appended_rows == 0


def test_captured_energy_bounds():
    D = _corpus()
    up = IndexUpdater.build(D, cutoff=0.5)
    e = captured_energy(D, up.pruner)
    assert 0.0 < e <= 1.0


# ---------------------------------------------------------------------------
# Lock-discipline regressions (crop of `python -m repro.analysis` findings:
# telemetry read index/pruner without the updater lock, _reference_energy
# wrote its cache bare and did D2H transfers under the lock)
# ---------------------------------------------------------------------------


def test_telemetry_safe_under_concurrent_appends():
    """delta_fraction/scale_divergence/drift_score/search snapshot
    (index, pruner) under the lock: hammering them while another thread
    appends must never raise (previously they could observe a half-swapped
    segment set)."""
    import threading

    D = _corpus(n=600)
    up = IndexUpdater.build(D, cutoff=0.5, quantize_int8=True,
                            delta_capacity=64)
    probe = _corpus(seed=2, n=64, domain_seed=3)
    errs = []
    done = threading.Event()

    def appender():
        try:
            for i in range(30):
                up.add_documents(_corpus(seed=i + 10, n=40,
                                         domain_seed=4)[:37])
        finally:
            done.set()

    th = threading.Thread(target=appender)
    th.start()
    try:
        while not done.is_set():
            try:
                assert 0.0 <= up.delta_fraction <= 1.0
                assert up.scale_divergence() >= 1.0
                assert up.drift_score(probe) > 0.0
                up.needs_refit(probe)
                up.search(probe[:2], k=3)
            except BaseException as e:  # noqa: BLE001 — must fail the test
                errs.append(e)
                break
    finally:
        th.join(timeout=60.0)
    assert not errs
    assert up.appended_rows == 30 * 37
    st = up.index.storage
    assert abs(up.delta_fraction - st.delta_pages / st.n_slots) < 1e-9
    assert st.delta_rows == 30 * 37


def test_reference_energy_cached_once_and_refit_coherent():
    """The lazy fit_energy fill happens outside the lock but commits under
    it, and a refit that swaps the pruner mid-derivation must not be
    clobbered by the stale value."""
    D = _corpus(n=400)
    pruner = StaticPruner(cutoff=0.5).fit(D)
    up = IndexUpdater(pruner=pruner, index=pruner.build_index(D))
    assert up.fit_energy is None
    ref = up._reference_energy()
    assert up.fit_energy == ref                  # cached under the lock
    assert ref == up._reference_energy()         # stable on re-read
    D2 = _corpus(seed=9, n=400, domain_seed=7)
    up.refit(D2)
    assert up.fit_energy is not None and up.fit_energy != ref
    assert abs(up.drift_score(D2) - captured_energy(D2, up.pruner)
               / up.fit_energy) < 1e-9


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_background_compaction_failure_surfaces_in_health(monkeypatch):
    """A compact_async thread that dies must be RECORDED, not swallowed:
    health() flips to not-ok and carries the error, so a fleet health
    probe can see the dead maintenance thread. (The background re-raise is
    part of the loud-death contract — the thread warning is expected.)"""
    D = _corpus(n=400)
    up = IndexUpdater.build(D, cutoff=0.5)
    up.add_documents(_corpus(seed=3, n=80, domain_seed=4)[:40])
    assert up.health()["ok"]

    def boom(**kw):
        raise RuntimeError("disk full mid-compaction")

    monkeypatch.setattr(up, "compact", boom)
    th = up.compact_async()
    th.join(timeout=60.0)
    health = up.health()
    assert not health["ok"]
    assert health["background_errors"][0]["op"] == "compact"
    assert "disk full" in health["background_errors"][0]["error"]
    # serving-path reads still work: the failure is visible, not fatal
    _, ids = up.search(D[:2], k=3)
    assert np.asarray(ids).shape == (2, 3)
