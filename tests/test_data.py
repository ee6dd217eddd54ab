"""Data pipelines: determinism, prefetch, graph sampling, corpus structure."""
import hashlib
import os
import subprocess
import sys

import numpy as np

from repro.data.graph import CSRGraph, NeighborSampler, batched_molecules, random_graph
from repro.data.recsys import ctr_batch, two_tower_batch
from repro.data.synthetic import (ENCODER_PROFILES, corpus_blocks, corpus_queries, make_corpus,
                                  make_dataset)
from repro.data.tokens import Prefetcher, token_batch


def test_token_batch_deterministic():
    a = token_batch(7, 42, batch=4, seq_len=16, vocab=100)
    b = token_batch(7, 42, batch=4, seq_len=16, vocab=100)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = token_batch(7, 43, batch=4, seq_len=16, vocab=100)
    assert (a["tokens"] != c["tokens"]).any()


def test_token_labels_are_shifted():
    b = token_batch(0, 0, batch=2, seq_len=8, vocab=50)
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert (b["labels"][:, -1] == -1).all()


def test_prefetcher_orders_steps():
    pf = Prefetcher(lambda t: {"t": t}, start_step=5, depth=2)
    steps = [next(pf)[0] for _ in range(4)]
    pf.close()
    assert steps == [5, 6, 7, 8]


def test_ctr_batch_bounds():
    b = ctr_batch(0, 0, batch=128, vocab_sizes=(100, 50, 10), n_dense=5)
    assert b["sparse"].shape == (128, 3)
    assert (b["sparse"] >= 0).all()
    assert (b["sparse"].max(0) < np.array([100, 50, 10])).all()
    assert b["dense"].shape == (128, 5)


def test_two_tower_batch_logq():
    b = two_tower_batch(0, 0, batch=64, user_vocab=1000, item_vocab=500)
    assert np.isfinite(b["item_logq"]).all()
    assert (b["item_ids"] < 500).all()


def test_csr_and_sampler_shapes():
    ei = random_graph(200, avg_degree=8, seed=0)
    g = CSRGraph.from_edge_index(ei, 200)
    assert g.indptr[-1] == ei.shape[1]
    s = NeighborSampler(g, fanouts=(3, 2), batch_nodes=16, seed=0)
    sub = s.sample()
    assert sub["node_ids"].shape == (s.max_nodes,)
    assert sub["edge_index"].shape == (2, s.max_edges)
    assert sub["seed_mask"].sum() == 16
    # sampled edges reference only in-subgraph local ids
    n_real = int(sub["node_mask"].sum())
    assert sub["edge_index"].max() < max(n_real, 1)


def test_sampler_handles_isolated_nodes():
    ei = np.array([[0, 1], [1, 0]], dtype=np.int32)   # nodes 2.. isolated
    g = CSRGraph.from_edge_index(ei, 50)
    s = NeighborSampler(g, fanouts=(2,), batch_nodes=8, seed=1)
    sub = s.sample()
    assert np.isfinite(sub["node_mask"]).all()


def test_molecule_batch_block_diagonal():
    b = batched_molecules(batch=4, n_nodes=5, n_edges=7, d_feat=3, d_edge=2)
    assert b["nodes"].shape == (20, 3)
    assert b["edge_index"].shape == (2, 28)
    # graph g's edges stay within its node block
    for gidx in range(4):
        seg = b["edge_index"][:, gidx * 7:(gidx + 1) * 7]
        assert (seg >= gidx * 5).all() and (seg < (gidx + 1) * 5).all()


def test_corpus_spectra_ordered_by_profile():
    """Effective rank: ance < tasb < contriever (the paper's robustness order)."""
    ranks = {}
    for enc in ENCODER_PROFILES:
        D, _ = make_corpus(enc, n_docs=2000, d=64, seed=0)
        s = np.linalg.svd(D, compute_uv=False)
        p = s**2 / (s**2).sum()
        ranks[enc] = float(np.exp(-(p * np.log(p + 1e-12)).sum()))
    assert ranks["ance"] < ranks["tasb"] < ranks["contriever"]


def test_dataset_has_queries_and_graded_qrels():
    ds = make_dataset("tasb", n_docs=500, d=32, query_sets=("dl19", "devsmall"))
    assert ds.queries["dl19"].shape[0] == 43
    grades = {g for q in ds.qrels["dl19"].values() for g in q.values()}
    assert 3 in grades          # graded judgments
    grades_dev = {g for q in ds.qrels["devsmall"].values() for g in q.values()}
    assert grades_dev == {1}    # binary shallow judgments


# sha256 of the first (32, 16) block of corpus_blocks("tasb", seed=0), rounded
# to 4 decimals so BLAS summation order cannot flip it
_BLOCK0_SHA256 = "f05de8e091929bd74aa1269c95a6328206efb18c7fc6f72887accae270ab1741"
_BLOCK0_SNIPPET = (
    "import hashlib, numpy as np\n"
    "from repro.data.synthetic import corpus_blocks\n"
    "b = next(iter(corpus_blocks(n_docs=64, d=16, seed=0, block_rows=32)()))\n"
    "print(hashlib.sha256(np.round(b, 4).tobytes()).hexdigest())\n")


def test_corpus_block_checksum_is_pinned_across_processes():
    """Seeds must not depend on the per-process salt of ``hash(str)``: the
    same block comes out in a process with another PYTHONHASHSEED."""
    b = next(iter(corpus_blocks(n_docs=64, d=16, seed=0, block_rows=32)()))
    assert hashlib.sha256(np.round(b, 4).tobytes()).hexdigest() == _BLOCK0_SHA256
    env = dict(os.environ, PYTHONHASHSEED="12345",
               PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run([sys.executable, "-c", _BLOCK0_SNIPPET], env=env,
                         capture_output=True, text=True, timeout=120, check=True)
    assert out.stdout.strip() == _BLOCK0_SHA256
    a, _ = make_corpus("tasb", n_docs=32, d=16, seed=3)
    code = ("import hashlib, numpy as np\n"
            "from repro.data.synthetic import make_corpus\n"
            "a, _ = make_corpus('tasb', n_docs=32, d=16, seed=3)\n"
            "print(hashlib.sha256(np.round(a, 4).tobytes()).hexdigest())\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120, check=True)
    assert out.stdout.strip() == hashlib.sha256(np.round(a, 4).tobytes()).hexdigest()


def test_corpus_blocks_repeat_per_pass_and_cover_n_docs():
    gen = corpus_blocks("tasb", n_docs=100, d=16, seed=1, block_rows=32)
    first, second = list(gen()), list(gen())
    assert [b.shape for b in first] == [(32, 16)] * 3 + [(4, 16)]
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(np.linalg.norm(np.concatenate(first), axis=1),
                               1.0, rtol=1e-5)
    q = corpus_queries("tasb", n_queries=8, d=16, seed=1)
    assert q.shape == (8, 16) and q.dtype == np.float32
    assert not any(np.allclose(q[:4], b[:4]) for b in first)
