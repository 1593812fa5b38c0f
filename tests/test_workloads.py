"""Workload-generator guards: the locality properties the paper tests and
the navigability precondition (a corpus no graph method can navigate
would silently invalidate every benchmark — this bit us once)."""
from __future__ import annotations

import numpy as np
import pytest

from repro.data import workloads as W


def test_medrag_zipf_is_skewed():
    wl = W.make_medrag_zipf(n=2000, n_queries=1024)
    # many queries share near-duplicate neighborhoods: pairwise-close pairs
    q = wl.queries
    d = ((q[:256, None, :] - q[None, :256, :]) ** 2).sum(-1)
    np.fill_diagonal(d, np.inf)
    near = (d.min(1) < 0.5 * np.median(d)).mean()
    assert near > 0.5, "zipf workload must contain near-duplicate clusters"


def test_tripclick_sessions_are_bursty():
    wl = W.make_tripclick(n=2000, n_queries=512, session_len=8)
    q = wl.queries
    seq_d = ((q[1:] - q[:-1]) ** 2).sum(-1)
    rng = np.random.default_rng(0)
    perm = q[rng.permutation(len(q))]
    rand_d = ((perm[1:] - perm[:-1]) ** 2).sum(-1)
    assert np.median(seq_d) < 0.3 * np.median(rand_d), \
        "consecutive queries must be far closer than shuffled ones"


def test_uniform_has_no_locality():
    wl = W.make_uniform(n=2000, n_queries=512)
    q = wl.queries
    seq_d = np.median(((q[1:] - q[:-1]) ** 2).sum(-1))
    rng = np.random.default_rng(0)
    perm = q[rng.permutation(len(q))]
    rand_d = np.median(((perm[1:] - perm[:-1]) ** 2).sum(-1))
    assert 0.5 < seq_d / rand_d < 2.0


def test_papers_labels_cover_queries():
    wl = W.make_papers(n=2000, n_queries=256)
    assert wl.labels is not None and wl.filter_labels is not None
    for fl in np.unique(wl.filter_labels):
        assert (wl.labels == fl).sum() > 0, f"label {fl} has no documents"


@pytest.mark.parametrize("maker", [W.make_tripclick, W.make_medrag_zipf])
def test_corpora_are_navigable(maker):
    """Greedy-search self-recall must stay high — the precondition for
    every benchmark (distance concentration at high ambient d breaks it;
    see the module docstring's dimensionality note)."""
    import jax.numpy as jnp
    from repro.core import brute_force_knn
    from repro.core.beam_search import SearchSpec, beam_search_l2
    from repro.core.vamana import VamanaParams, build_vamana

    wl = maker(n=3000, n_queries=32)
    adj, med = build_vamana(wl.corpus, VamanaParams(max_degree=20,
                                                    build_beam=40,
                                                    batch=1024))
    rng = np.random.default_rng(3)
    qs = (wl.corpus[rng.integers(0, 3000, 48)]
          + 0.01 * rng.normal(size=(48, wl.corpus.shape[1]))
          ).astype(np.float32)
    truth = brute_force_knn(wl.corpus, qs, 1)
    spec = SearchSpec(beam_width=16, k=1, max_iters=128)
    res = beam_search_l2(jnp.asarray(adj), jnp.asarray(wl.corpus),
                         jnp.asarray(qs),
                         jnp.full((48, 1), med, jnp.int32), spec)
    hit = (np.asarray(res.ids[:, 0]) == truth[:, 0]).mean()
    # 0.8 at this deliberately small scale (3k pts, beam 16); the broken
    # regime this guards against measures ~0.0 (see module docstring)
    assert hit > 0.8, f"self-recall {hit}: corpus not navigable"


def test_lift_keeps_the_neighbor_structure():
    """Lifting to deployment width keeps who is whose neighbor: the map
    is orthonormal and the ambient noise is small."""
    from repro.core import brute_force_knn, recall_at_k
    wl = W.make_medrag_zipf(n=600, n_queries=64)
    up = W.lift(wl, 768, seed=5)
    assert up.corpus.shape == (600, 768) and up.queries.shape == (64, 768)
    assert up.corpus.dtype == np.float32
    truth = brute_force_knn(wl.corpus, wl.queries, 10)
    assert recall_at_k(brute_force_knn(up.corpus, up.queries, 10),
                       truth) > 0.9
    np.testing.assert_array_equal(W.lift(wl, 768, seed=5).corpus,
                                  up.corpus)
    with pytest.raises(ValueError):
        W.lift(wl, 8)
