"""Baseline fidelity: LSH-APG entry points, Proximity cache, PQ path."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (VamanaParams, VectorSearchEngine, brute_force_knn,
                        recall_at_k)
from repro.core import proximity_cache as pc
from repro.core import pq as pq_mod
from tests.conftest import make_clustered

VP = VamanaParams(max_degree=16, build_beam=32, batch=512)


def test_lsh_apg_entry_points_beat_medoid(corpus, queries):
    eng_apg = VectorSearchEngine(mode="lsh_apg", vamana=VP).build(corpus[0])
    eng_dsk = VectorSearchEngine(mode="diskann", vamana=VP).build(corpus[0])
    _, _, st_apg = eng_apg.search(queries, k=1, beam_width=4)
    _, _, st_dsk = eng_dsk.search(queries, k=1, beam_width=4)
    # data-side LSH entries start closer than the medoid on clustered data
    assert st_apg.hops.mean() <= st_dsk.hops.mean()


def test_lsh_apg_is_workload_oblivious(corpus, queries):
    """Replaying queries must NOT change LSH-APG behaviour (static index)."""
    eng = VectorSearchEngine(mode="lsh_apg", vamana=VP).build(corpus[0])
    _, _, st1 = eng.search(queries, k=1, beam_width=4)
    _, _, st2 = eng.search(queries, k=1, beam_width=4)
    np.testing.assert_array_equal(st1.hops, st2.hops)


def test_proximity_cache_hit_miss():
    state = pc.make_cache(capacity=8, dim=4, k=3)
    q = jnp.asarray(np.eye(4, dtype=np.float32))
    ids = jnp.arange(12, dtype=jnp.int32).reshape(4, 3)
    state = pc.cache_insert(state, q, ids, jnp.ones(4, bool))
    hit = pc.cache_probe(state, q + 0.001, jnp.float32(0.1))
    assert np.all(np.asarray(hit.hit))
    np.testing.assert_array_equal(np.asarray(hit.ids), np.asarray(ids))
    miss = pc.cache_probe(state, q + 10.0, jnp.float32(0.1))
    assert not np.any(np.asarray(miss.hit))


def test_proximity_cache_staleness_under_insertion():
    """Fig. 2: cached results go stale when the database changes."""
    data, centers, _ = make_clustered(600, 8, 4, seed=51)
    eng = VectorSearchEngine(mode="diskann", vamana=VP, capacity=900).build(data)
    rng = np.random.default_rng(52)
    q = (centers[1] + 0.1 * rng.normal(size=(32, 8))).astype(np.float32)
    state = pc.make_cache(capacity=64, dim=8, k=3)
    ids, _, _ = eng.search(q, k=3, beam_width=16)
    state = pc.cache_insert(state, jnp.asarray(q), jnp.asarray(ids),
                            jnp.ones(32, bool))
    # insert better vectors right at the query cluster
    better = (centers[1] + 0.01 * rng.normal(size=(60, 8))).astype(np.float32)
    eng.insert(better)
    truth = brute_force_knn(eng._vec_np[: eng.n_active], q, 3)
    hit = pc.cache_probe(state, jnp.asarray(q), jnp.float32(1e3))
    stale_recall = recall_at_k(np.asarray(hit.ids), truth)
    fresh_ids, _, _ = eng.search(q, k=3, beam_width=16)
    fresh_recall = recall_at_k(fresh_ids, truth)
    assert stale_recall < 0.5 < fresh_recall


def test_pq_adc_preserves_neighbor_ordering():
    rng = np.random.default_rng(61)
    vecs = rng.normal(size=(256, 32)).astype(np.float32)
    cb = pq_mod.train_pq(jax.random.PRNGKey(0), jnp.asarray(vecs), 8,
                         n_centroids=32)
    codes = pq_mod.encode(cb, jnp.asarray(vecs))
    q = jnp.asarray(vecs[0] + 0.01)
    approx = np.asarray(pq_mod.adc_dist_fn(cb, codes)(
        q, jnp.arange(256, dtype=jnp.int32)))
    exact = ((vecs - np.asarray(q)) ** 2).sum(1)
    # top-1 by ADC should be within exact top-10
    assert approx.argmin() in np.argsort(exact)[:10]


def test_pq_engine_recall_with_rerank(corpus, queries, ground_truth):
    eng = VectorSearchEngine(mode="diskann", vamana=VP,
                             pq_subspaces=4).build(corpus[0])
    ids, _, _ = eng.search(queries, k=10, beam_width=32)
    assert recall_at_k(ids, ground_truth) > 0.8


def test_brute_force_chunking_does_not_change_results(monkeypatch):
    """Chunked exact kNN equals one plain pass over all query rows, with
    the filter and exclusion masks applied."""
    from repro.core import engine
    rng = np.random.default_rng(4)
    v = rng.normal(size=(300, 12)).astype(np.float32)
    q = rng.normal(size=(50, 12)).astype(np.float32)
    labels = rng.integers(0, 3, 300).astype(np.int32)
    fl = rng.integers(-1, 3, 50).astype(np.int32)
    excl = np.array([1, 5, 7])
    d = ((q[:, None, :] - v[None, :, :]) ** 2).sum(-1)
    d[:, excl] = np.inf
    d[(labels[None, :] != fl[:, None]) & (fl[:, None] >= 0)] = np.inf
    want = np.argsort(d, axis=1)[:, :5]
    monkeypatch.setattr(engine, "_BRUTE_CHUNK_ELEMS", 300 * 12 * 3)
    got = engine.brute_force_knn(v, q, 5, labels=labels, filter_labels=fl,
                                 exclude=excl)
    np.testing.assert_array_equal(got, want)
