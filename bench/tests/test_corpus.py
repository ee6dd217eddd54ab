"""The on-device corpus: one corpus per seed, whatever the layout, drawn
from the TAS-B profile of ``repro.data.synthetic``."""
import jax
import jax.numpy as jnp
import numpy as np

from harness.corpus import Corpus

CFG = dict(rows=10_007, d=768, m=384, cutoff=0.5, dtype="int8",
           block_rows=2048, profile=dict(alpha=0.78, sigma=0.42))


def _fill(corpus, cuts):
    dev = jax.devices()[0]
    bufs = [(dev, jnp.zeros((hi - lo, corpus.width), corpus.dtype), lo, hi)
            for lo, hi in zip(cuts[:-1], cuts[1:])]
    return np.concatenate([np.asarray(b) for b in corpus.fill(bufs)])


def test_same_seed_same_corpus_other_seed_other():
    dev = jax.devices()[0]
    a, b, c = (Corpus(CFG, s) for s in (2**31 + 5, 2**31 + 5, 17))
    for x in (a, b, c):
        x.fit(dev)
    np.testing.assert_array_equal(a.W, b.W)
    np.testing.assert_array_equal(a.scale, b.scale)
    np.testing.assert_array_equal(np.asarray(a.rows(3, dev)),
                                  np.asarray(b.rows(3, dev)))
    np.testing.assert_array_equal(a.queries(16, dev), b.queries(16, dev))
    assert not np.array_equal(np.asarray(a.raw(3, dev)),
                              np.asarray(c.raw(3, dev)))
    assert not np.array_equal(a.queries(16, dev), np.asarray(
        a.raw(0, dev))[:16])


def test_layout_does_not_change_the_corpus():
    corpus = Corpus(CFG, 3)
    corpus.fit(jax.devices()[0])
    one = _fill(corpus, [0, CFG["rows"]])
    # four shards cut off the block grid, the last one padded past n
    n4 = CFG["rows"] + (-CFG["rows"]) % 4
    four = _fill(corpus, [0, 2501, 5003, 7505, n4])
    np.testing.assert_array_equal(one, four[:CFG["rows"]])
    assert not four[CFG["rows"]:].any()
    assert one.dtype == np.int8 and np.abs(one).max() == 127


def test_distribution_matches_the_program_generator():
    from repro.data.synthetic import corpus_blocks
    dev = jax.devices()[0]
    cfg = dict(CFG, m=None, dtype="float32", rows=16384, block_rows=16384)
    mine = np.asarray(Corpus(cfg, 11).raw(0, dev), np.float64)
    theirs = next(corpus_blocks("tasb", n_docs=16384, d=768, seed=11,
                                block_rows=16384)()).astype(np.float64)
    np.testing.assert_allclose(np.linalg.norm(mine, axis=1), 1, atol=1e-5)

    def spectrum(x):
        return np.sort(np.linalg.eigvalsh(x.T @ x / len(x)))[::-1]

    s_mine, s_theirs = spectrum(mine), spectrum(theirs)
    # the leading eigenvalues follow lam_j = j**-0.78 over a noise floor
    np.testing.assert_allclose(s_mine[:64], s_theirs[:64], rtol=0.1)
    np.testing.assert_allclose(s_mine.sum(), 1.0, rtol=1e-4)
    lam = np.arange(1, 769) ** -0.78
    lam /= lam.sum()
    noise = 0.42 ** 2 / 768
    model = (lam + noise) / (1 + 0.42 ** 2)
    np.testing.assert_allclose(s_mine[:32], model[:32], rtol=0.15)
