"""Disk-resident store: layout round-trip, cache accounting, disk engine."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (VectorSearchEngine, brute_force_knn, recall_at_k)
from repro.core.vamana import build_vamana
from repro.store import layout
from repro.store.cache import NodeCache
from repro.store.io_engine import DiskVectorSearchEngine

from conftest import SMALL, VPARAMS, make_clustered


@pytest.fixture(scope="module")
def prebuilt(corpus):
    return build_vamana(corpus[0], VPARAMS)


@pytest.fixture(scope="module")
def tmp_store_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("stores")


# ---------------------------------------------------------------- layout

def test_layout_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    n, d, r = 64, 12, 8
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, r)).astype(np.int32)
    labels = rng.integers(0, 4, n).astype(np.int32)
    path = str(tmp_path / "idx.ctpl")

    store = layout.write_store(path, vecs, adj, medoid=7, labels=labels)
    store.close()
    re = layout.open_store(path)
    assert re.header.version == layout.VERSION
    assert re.n_active == n and re.medoid == 7 and re.header.has_labels
    np.testing.assert_array_equal(np.asarray(re.vectors[:n]), vecs)
    np.testing.assert_array_equal(np.asarray(re.adjacency[:n]), adj)
    np.testing.assert_array_equal(np.asarray(re.labels[:n]), labels)


def test_layout_blocks_are_sector_aligned(tmp_path):
    import os
    path = str(tmp_path / "idx.ctpl")
    store = layout.create_store(path, capacity=10, dim=24, degree=24)
    bsz = store.header.block_size
    assert bsz % layout.SECTOR == 0
    assert bsz >= 4 * 24 + 4 * 24 + 4
    store.flush()
    assert os.path.getsize(path) == layout.HEADER_SIZE + 10 * bsz


def test_layout_rejects_corrupt_header(tmp_path):
    path = str(tmp_path / "idx.ctpl")
    layout.create_store(path, capacity=4, dim=8, degree=4).flush()
    with open(path, "r+b") as f:
        f.write(b"JUNK")
    with pytest.raises(layout.StoreFormatError):
        layout.open_store(path)


# ---------------------------------------------------------------- cache

def _tiny_store(tmp_path, n=32, d=4, r=4):
    rng = np.random.default_rng(1)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(0, n, size=(n, r)).astype(np.int32)
    return layout.write_store(str(tmp_path / "tiny.ctpl"), vecs, adj,
                              medoid=0), vecs, adj


def test_cache_counts_and_contents(tmp_path):
    store, vecs, adj = _tiny_store(tmp_path)
    cache = NodeCache(store, capacity=8)
    got_v, got_a, hits, misses = cache.fetch([3, 5, 3])
    assert (hits, misses) == (1, 2)            # duplicate in-call -> hit
    np.testing.assert_array_equal(got_v, vecs[[3, 5, 3]])
    np.testing.assert_array_equal(got_a, adj[[3, 5, 3]])
    _, _, hits, misses = cache.fetch([3, 5])
    assert (hits, misses) == (2, 0)
    assert cache.block_reads == 2
    assert cache.hits + cache.misses == 5


def test_cache_evicts_under_pressure_but_not_pins(tmp_path):
    store, _, _ = _tiny_store(tmp_path)
    cache = NodeCache(store, capacity=4)
    cache.pin(0)
    # stream far more nodes than frames: node 0 must survive throughout
    for lo in range(1, 29, 4):
        cache.fetch(np.arange(lo, lo + 4))
    _, _, hits, misses = cache.fetch([0])
    assert (hits, misses) == (1, 0), "pinned medoid was evicted"
    assert cache.resident <= 4


def test_cache_rotating_pins_bounded(tmp_path):
    store, _, _ = _tiny_store(tmp_path)
    cache = NodeCache(store, capacity=8, pin_budget=2)
    cache.pin_rotating([1, 2, 3, 4])           # budget 2: only 3,4 stay
    assert int(cache.pinned.sum()) == 2
    cache.invalidate()
    assert cache.resident == 0 and int(cache.pinned.sum()) == 0


def test_fetch_batch_contents_and_attribution(tmp_path):
    store, vecs, adj = _tiny_store(tmp_path)
    cache = NodeCache(store, capacity=8)
    lanes = [np.array([3, 5]), np.array([5, 3, 7]), np.array([], np.int64)]
    out = cache.fetch_batch(lanes)
    assert len(out) == 3
    for lane, (v, a, _, _) in zip(lanes, out):
        np.testing.assert_array_equal(v, vecs[lane])
        np.testing.assert_array_equal(a, adj[lane])
    # misses charged once, to the first lane wanting each node
    assert (out[0][2], out[0][3]) == (0, 2)     # lane 0: 3, 5 both cold
    assert (out[1][2], out[1][3]) == (2, 1)     # lane 1: 5, 3 shared; 7 cold
    assert (out[2][2], out[2][3]) == (0, 0)
    assert cache.stats.prefetch_batches == 1
    assert cache.stats.batched_reads == 3       # deduplicated: {3, 5, 7}
    assert cache.stats.block_reads == 3


def test_fetch_batch_dedup_beats_naive_under_pressure(tmp_path):
    """The prefetcher's claim: one deduplicated multi-node fetch issues
    no more reads than the per-lane loop — strictly fewer when lanes
    share blocks and the frame pool thrashes between lanes."""
    store, vecs, _ = _tiny_store(tmp_path)
    rng = np.random.default_rng(9)
    # overlapping lanes over a 12-node hot set, 4-frame cache: the naive
    # loop re-reads nodes evicted between lanes
    lanes = [np.sort(rng.choice(12, 6, replace=False)) for _ in range(8)]

    naive_cache = NodeCache(store, capacity=4)
    naive = sum(naive_cache.fetch(lane)[3] for lane in lanes)

    batch_cache = NodeCache(store, capacity=4)
    out = batch_cache.fetch_batch(lanes)
    batched = sum(m for _, _, _, m in out)
    assert batched == batch_cache.stats.batched_reads
    assert batched == len({int(x) for lane in lanes for x in lane})
    assert batched < naive, (batched, naive)
    # contents stay correct even though the pool is smaller than the batch
    for lane, (v, _, _, _) in zip(lanes, out):
        np.testing.assert_array_equal(v, vecs[lane])


# ---------------------------------------------------------------- disk engine

@pytest.mark.parametrize("dim,m", [(768, 192), (128, 32), (36, 9),
                                   (24, 8), (16, 8), (12, 4), (6, 2),
                                   (7, 1)])
def test_default_pq_subspaces(dim, m):
    """About 4 dimensions per subspace at embedding widths, and the
    largest of {8, 4, 2} below 36 dimensions."""
    from repro.store.io_engine import default_pq_subspaces
    assert default_pq_subspaces(dim) == m
    assert dim % m == 0


def test_disk_engine_recall_parity_with_ram(tmp_store_dir, corpus, queries,
                                            ground_truth, prebuilt):
    """Acceptance: ±0.01 recall@10 vs the in-RAM engine, same graph."""
    ram = VectorSearchEngine(mode="diskann", vamana=VPARAMS).build(
        corpus[0], prebuilt=prebuilt)
    ids_r, _, _ = ram.search(queries, k=10)
    disk = DiskVectorSearchEngine(
        mode="diskann", vamana=VPARAMS, cache_frames=256,
        store_path=str(tmp_store_dir / "parity.ctpl")).build(
        corpus[0], prebuilt=prebuilt)
    ids_d, _, st = disk.search(queries, k=10)
    r_ram = recall_at_k(ids_r, ground_truth)
    r_disk = recall_at_k(ids_d, ground_truth)
    assert r_disk >= r_ram - 0.01, (r_ram, r_disk)
    # I/O accounting invariants
    assert st.block_reads is not None and st.cache_hits is not None
    assert (st.block_reads + st.cache_hits > 0).all()
    assert st.block_reads.sum() <= disk.cache.block_reads


def test_disk_engine_persist_reopen_identical(tmp_store_dir, corpus, queries,
                                              prebuilt):
    path = str(tmp_store_dir / "reopen.ctpl")
    disk = DiskVectorSearchEngine(
        mode="diskann", vamana=VPARAMS, cache_frames=256,
        store_path=path).build(corpus[0], prebuilt=prebuilt)
    ids_a, d_a, _ = disk.search(queries, k=10)
    disk.store.flush()

    re = DiskVectorSearchEngine.load(path, mode="diskann", vamana=VPARAMS,
                                     cache_frames=256)
    assert re.n_active == disk.n_active and re.medoid == disk.medoid
    ids_b, d_b, _ = re.search(queries, k=10)
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_allclose(d_a, d_b, rtol=1e-6)


def test_disk_engine_cache_hits_on_biased_stream(tmp_store_dir, corpus,
                                                 queries, prebuilt):
    """Repeated (biased) queries must turn block reads into cache hits."""
    # frames sized to the replay's working set: leftover misses are then
    # compulsory (first touch), not capacity evictions
    disk = DiskVectorSearchEngine(
        mode="catapult", vamana=VPARAMS, cache_frames=2048,
        store_path=str(tmp_store_dir / "biased.ctpl")).build(
        corpus[0], prebuilt=prebuilt)
    _, _, st1 = disk.search(queries, k=10)
    _, _, st2 = disk.search(queries, k=10)    # identical batch replayed
    assert st2.block_reads.mean() < 0.3 * max(st1.block_reads.mean(), 1.0)
    hit_rate2 = st2.cache_hits.sum() / max(
        (st2.cache_hits + st2.block_reads).sum(), 1)
    assert hit_rate2 > 0.7


def test_disk_engine_insert_then_persist(tmp_store_dir):
    data, _, _ = make_clustered(n=600, d=16, n_clusters=8, seed=3)
    base, extra = data[:500], data[500:] + 8.0   # shifted: distinctive
    path = str(tmp_store_dir / "insert.ctpl")
    disk = DiskVectorSearchEngine(
        mode="diskann", vamana=VPARAMS, capacity=600, cache_frames=128,
        store_path=path).build(base)
    disk.insert(extra)
    assert disk.n_active == 600
    q = extra[:8] + 0.01
    ids, _, _ = disk.search(q, k=5)
    assert (ids >= 500).any(), "inserted region unreachable"

    re = DiskVectorSearchEngine.load(path, mode="diskann", vamana=VPARAMS,
                                     cache_frames=128)
    assert re.n_active == 600
    np.testing.assert_allclose(np.asarray(re.store.vectors[500:600]),
                               extra, rtol=1e-6)
    ids2, _, _ = re.search(q, k=5)
    np.testing.assert_array_equal(ids, ids2)


def test_disk_engine_pq_persisted_byte_identical_after_insert(tmp_store_dir):
    """CTPL v2: the build-time codebook rides in the file, so a reopen
    after post-build inserts traverses with byte-identical ADC state
    (codebook, codes, hence hops) — the FORMAT.md 'Not persisted' fix."""
    data, _, _ = make_clustered(n=700, d=16, n_clusters=8, seed=5)
    base, extra = data[:600], data[600:] + 6.0
    path = str(tmp_store_dir / "pq_persist.ctpl")
    disk = DiskVectorSearchEngine(
        mode="diskann", vamana=VPARAMS, capacity=700, cache_frames=128,
        store_path=path).build(base)
    disk.insert(extra)
    q = data[:16] + 0.01
    ids_a, d_a, st_a = disk.search(q, k=5)

    re = DiskVectorSearchEngine.load(path, mode="diskann", vamana=VPARAMS,
                                     cache_frames=128)
    np.testing.assert_array_equal(np.asarray(re._pq.centroids),
                                  np.asarray(disk._pq.centroids))
    np.testing.assert_array_equal(re._codes_np, disk._codes_np)
    ids_b, d_b, st_b = re.search(q, k=5)
    np.testing.assert_array_equal(ids_a, ids_b)
    np.testing.assert_allclose(d_a, d_b, rtol=1e-6)
    # same ADC tables => the PQ-steered walk itself is identical
    np.testing.assert_array_equal(st_a.hops, st_b.hops)


def test_store_v1_file_still_opens(tmp_path):
    """A pre-PQ (v1) header reads back as pq_m == 0 — no codebook section,
    load() falls back to retraining (legacy behaviour)."""
    path = str(tmp_path / "v1.ctpl")
    layout.create_store(path, capacity=4, dim=8, degree=4).flush()
    with open(path, "r+b") as f:
        f.seek(4)
        f.write((1).to_bytes(4, "little"))      # stamp version = 1
    re = layout.open_store(path)
    assert re.header.version == 1 and re.read_pq() is None


def test_disk_engine_rejects_lsh_apg():
    with pytest.raises(ValueError):
        DiskVectorSearchEngine(mode="lsh_apg")


# ------------------------------------------------- two-phase won stat fix

def test_two_phase_threads_catapult_wins(catapult_engine, corpus, queries):
    """search_two_phase must report real phase-1 wins, not hardcoded zeros."""
    eng = catapult_engine
    eng.search_two_phase(queries, k=5)          # populate buckets
    _, _, st = eng.search_two_phase(queries, k=5)
    assert st.won.shape == (queries.shape[0],)
    assert st.used.any()
    assert st.won.any(), "repeat queries should win via catapult starts"
    assert (~st.won | st.used).all(), "won implies used"
