"""VectorSearchEngine — the deployable facade over the paper's machinery.

One engine object = one index + one acceleration mode:

* ``mode='diskann'``   — vanilla Vamana beam search from the medoid
                         (the paper's primary baseline),
* ``mode='catapult'``  — CatapultDB: LSH-bucketed shortcut layer
                         (the paper's contribution),
* ``mode='lsh_apg'``   — static data-side LSH entry points (baseline).

Orthogonal features, all composable with every mode exactly as Table 1
of the paper demands of CatapultDB:

* ``filtered=True``    — FilteredVamana stitched graph + per-label entry
                         points + predicate-constrained traversal,
* ``pq_subspaces=M``   — DiskANN-style PQ traversal distances with
                         full-precision rerank of the final beam,
* ``insert``/``delete``— FreshVamana online updates (tombstones),
* sharding             — see ``repro.core.sharded`` for the scatter-gather
                         multi-device engine used by the dry-run.

The device-side search path is functional and jit-cached per batch shape;
the host keeps numpy mirrors for graph surgery (build/insert).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import buckets as bk
from repro.core import catapult as cat
from repro.core import filters as flt
from repro.core import insert as ins
from repro.core import lsh_apg as apg
from repro.core import pq as pq_mod
from repro.core.beam_search import (SearchSpec, beam_search, beam_search_l2,
                                    l2_dist_fn)
from repro.core.vamana import VamanaParams, build_vamana, medoid_index
from repro.obs.profiler import span
from repro.obs.trace import stages


class SearchStats(NamedTuple):
    hops: np.ndarray          # (B,) node expansions
    ndists: np.ndarray        # (B,) distance computations
    used: np.ndarray          # (B,) bool catapult used (catapult mode only)
    won: np.ndarray           # (B,) bool catapult beat fallback
    # disk-backed engines only (None on the RAM path):
    block_reads: Optional[np.ndarray] = None   # (B,) node blocks read from disk
    cache_hits: Optional[np.ndarray] = None    # (B,) node cache hits
    # catapult mode under a trace (explain) only: elsewhere the read
    # back would cost every dispatch a device->host round trip
    lanes_published: Optional[int] = None      # lanes that published
    publish_rounds: Optional[int] = None       # rounds the publish ran


def publish_counts(pub, trace) -> dict:
    """``SearchStats`` keywords for the publish counts that ``_dispatch``
    returned: read back under a trace (explain) only, else none."""
    if trace is None or pub is None:
        return {}
    lanes, rounds = jax.device_get(pub)
    return dict(lanes_published=int(lanes), publish_rounds=int(rounds))


# ---------------------------------------------------------------------------
# Storage backends — build()/search()/insert() are backend-agnostic: the
# engine holds its host-side vector/adjacency mirrors as views supplied by a
# NodeStore, so the same graph surgery runs against RAM arrays or memmap'd
# disk blocks (repro.store.layout).
# ---------------------------------------------------------------------------

class RamStore:
    """Device-memory-scale backend: plain numpy arrays (seed behaviour)."""

    def __init__(self, vectors: np.ndarray, adjacency: np.ndarray):
        self.vectors = vectors        # (capacity, d) float32
        self.adjacency = adjacency    # (capacity, R) int32, -1 padded

    @classmethod
    def allocate(cls, capacity: int, dim: int, degree: int) -> 'RamStore':
        return cls(np.zeros((capacity, dim), np.float32),
                   np.full((capacity, degree), -1, np.int32))

    def flush(self) -> None:          # RAM is always "durable enough"
        pass

    def close(self) -> None:
        pass


class DiskStore:
    """Disk-resident backend: views into a block-aligned store file.

    ``vectors``/``adjacency`` are strided memmap views into per-node
    blocks (repro.store.layout), so insert-time graph surgery writes
    disk pages in place; ``flush`` persists them plus header metadata.
    """

    def __init__(self, block_store):
        self.block_store = block_store
        self.vectors = block_store.vectors
        self.adjacency = block_store.adjacency

    @classmethod
    def create(cls, path: str, capacity: int, dim: int, degree: int,
               has_labels: bool = False) -> 'DiskStore':
        from repro.store import layout   # lazy: breaks the import cycle
        return cls(layout.create_store(path, capacity=capacity, dim=dim,
                                       degree=degree, has_labels=has_labels))

    @classmethod
    def open(cls, path: str, mode: str = 'r+') -> 'DiskStore':
        from repro.store import layout
        return cls(layout.open_store(path, mode=mode))

    def flush(self, **header_updates) -> None:
        self.block_store.flush(**header_updates)

    def close(self) -> None:
        self.block_store.close()


_BRUTE_CHUNK_ELEMS = 1 << 24    # floats per brute-force temporary


def brute_force_knn(vectors: np.ndarray, queries: np.ndarray, k: int,
                    labels: np.ndarray | None = None,
                    filter_labels: np.ndarray | None = None,
                    exclude: np.ndarray | None = None) -> np.ndarray:
    """Exact ground truth.

    Query rows are scored in chunks that bound each temporary to
    ``_BRUTE_CHUNK_ELEMS`` floats (at most 256 rows): at deployment
    width a 256-row chunk would need gigabytes.  Each row's distances
    and ordering do not depend on the chunking."""
    n, d = vectors.shape
    rows = int(np.clip(_BRUTE_CHUNK_ELEMS // max(n * d, 1), 1, 256))
    out = np.zeros((queries.shape[0], k), np.int32)
    for lo in range(0, queries.shape[0], rows):
        q = queries[lo: lo + rows]
        dist = ((q[:, None, :] - vectors[None, :, :]) ** 2).sum(-1)
        if exclude is not None:
            dist[:, exclude] = np.inf
        if filter_labels is not None and labels is not None:
            fl = filter_labels[lo: lo + rows]
            mism = (labels[None, :] != fl[:, None]) & (fl[:, None] >= 0)
            dist[mism] = np.inf
        out[lo: lo + rows] = np.argsort(dist, axis=1)[:, :k]
    return out


def recall_at_k(found: np.ndarray, truth: np.ndarray) -> float:
    """Fraction of true k-NN present in the returned k (paper's metric)."""
    k = truth.shape[1]
    hits = sum(len(set(f[:k].tolist()) & set(t.tolist())) for f, t in
               zip(found, truth))
    return hits / (truth.shape[0] * k)


@dataclasses.dataclass
class VectorSearchEngine:
    mode: str = 'catapult'
    vamana: VamanaParams = dataclasses.field(default_factory=VamanaParams)
    n_bits: int = 8                 # L (paper default)
    bucket_capacity: int = 40       # b (paper default)
    apg_entries: int = 8
    pq_subspaces: Optional[int] = None
    seed: int = 0
    capacity: Optional[int] = None  # adjacency row preallocation for inserts
    store: Optional[object] = None  # NodeStore backend; default RamStore
    # workload-adaptation hooks (repro.adapt): the utility gate routes
    # catapult-mode dispatch through the plain diskann path when the
    # maintainer decides shortcuts stopped paying off — a gated-off
    # engine runs the very same jit'd search a diskann-mode engine does,
    # so uniform workloads pay ~zero catapult overhead.
    # ``catapult_enabled`` is the PERSISTENT gate verdict (saved by the
    # disk tiers); ``catapult_override`` is the maintainer's transient
    # one-batch dispatch override for shadow-baseline/probe batches and
    # is never persisted — keeping them separate means a save() landing
    # mid-shadow cannot permanently gate a reopened engine off.
    # ``adapt_state`` is the maintainer's per-engine telemetry.
    catapult_enabled: bool = True
    catapult_override: Optional[bool] = None
    adapt_state: Optional[object] = None
    # traversal hop implementation: "unfused" (composed jnp/vmap hop) or
    # "fused" (one Pallas dispatch per hop, kernels.fused_hop).  Results
    # are bit-identical; filtered searches always use the composed path.
    hop_backend: str = 'unfused'

    @property
    def catapult_active(self) -> bool:
        """Effective dispatch switch: the transient override when one is
        armed, else the persistent gate."""
        return (self.catapult_override if self.catapult_override is not None
                else self.catapult_enabled)

    # populated by build()
    n_active: int = 0
    medoid: int = 0
    n_labels: int = 0
    filtered: bool = False

    def build(self, vectors: np.ndarray, labels: np.ndarray | None = None,
              n_labels: int | None = None,
              prebuilt=None) -> 'VectorSearchEngine':
        """prebuilt: optional (adjacency, medoid[, label_entries]) — share
        one Vamana build across engines (the paper's unified-codebase
        control: systems differ only in entry-point selection)."""
        vectors = np.ascontiguousarray(vectors, np.float32)
        n, d = vectors.shape
        cap = self.capacity or n
        self.filtered = labels is not None

        if self.filtered:
            assert n_labels is not None
            if prebuilt is not None:
                adj, med, entries = prebuilt
            else:
                adj, med, entries = flt.build_stitched_graph(
                    vectors, labels, n_labels, self.vamana)
            self.n_labels = n_labels
            self._label_entry = jnp.asarray(entries)
            self._labels_np = np.zeros(cap, np.int32)
            self._labels_np[:n] = labels.astype(np.int32)
        else:
            if prebuilt is not None:
                adj, med = prebuilt[0], prebuilt[1]
            else:
                adj, med = build_vamana(vectors, self.vamana, capacity=cap)
            self._label_entry = None
            self._labels_np = None
        # Copy graph + vectors into the storage backend; the engine's host
        # mirrors are backend-owned views from here on (a prebuilt graph is
        # therefore never shared by reference — engines insert independently).
        if self.store is None:
            self.store = self._make_store(cap, d, adj.shape[1])
        sv, sa = self.store.vectors, self.store.adjacency
        assert sv.shape == (cap, d) and sa.shape == (cap, adj.shape[1]), (
            "store geometry mismatch", sv.shape, sa.shape, (cap, d))
        rows = min(adj.shape[0], cap)
        sa[:rows] = adj[:rows]
        sa[rows:] = -1
        sv[:n] = vectors
        sv[n:] = 0.0
        self._adj_np = sa
        self._vec_np = sv
        self._tomb_np = np.zeros(cap, bool)
        # rows >= n are tombstoned until inserted
        self._tomb_np[n:] = True
        self.n_active, self.medoid = n, med
        self.capacity = cap

        self._init_aux(vectors)
        self._sync_device()
        return self

    def _make_store(self, capacity: int, dim: int, degree: int):
        """Backend factory — subclasses swap RAM for disk here."""
        return RamStore.allocate(capacity, dim, degree)

    def _init_aux(self, vectors: np.ndarray,
                  pq_codebook: np.ndarray | None = None) -> None:
        """(Re)derive the mode's auxiliary state from the active vectors:
        catapult LSH + buckets, LSH-APG entries, PQ codebook + codes.

        Deterministic in (seed, vectors), so a reopened disk store
        retrains to bit-identical state without persisting codebooks.
        ``pq_codebook`` short-circuits the PQ retrain with a persisted
        codebook (repro.store CTPL v2) — codes re-encode from it, so the
        reopened ADC distances are byte-identical to the live engine's
        even when the stored vectors include post-build inserts the
        original training never saw.
        """
        n, d = vectors.shape
        cap = self._vec_np.shape[0]
        key = jax.random.PRNGKey(self.seed)
        k_lsh, k_apg, k_pq = jax.random.split(key, 3)
        if self.mode == 'catapult':
            self._cat = cat.make_catapult_state(
                k_lsh, d, self.n_bits, self.bucket_capacity)
        elif self.mode == 'lsh_apg':
            self._apg = apg.build_lsh_apg(vectors, k_apg, self.n_bits,
                                          self.apg_entries)
        if self.pq_subspaces:
            if pq_codebook is not None:
                assert pq_codebook.shape[0] == self.pq_subspaces, (
                    pq_codebook.shape, self.pq_subspaces)
                self._pq = pq_mod.PQCodebook(
                    centroids=jnp.asarray(pq_codebook, jnp.float32))
            else:
                self._pq = pq_mod.train_pq(k_pq, jnp.asarray(vectors),
                                           self.pq_subspaces)
            codes = np.zeros((cap, self.pq_subspaces), np.int32)
            codes[:n] = np.asarray(pq_mod.encode(self._pq, jnp.asarray(vectors)))
            self._codes_np = codes

    # ---------------------------------------------------------------- device
    def _sync_device(self) -> None:
        self._adj = jnp.asarray(self._adj_np)
        self._vec = jnp.asarray(self._vec_np)
        self._tomb = jnp.asarray(self._tomb_np)
        self._labels = (jnp.asarray(self._labels_np)
                        if self._labels_np is not None else None)
        if self.pq_subspaces:
            self._codes = jnp.asarray(self._codes_np)

    def _dist_fn(self):
        if self.pq_subspaces:
            return pq_mod.adc_dist_fn(self._pq, self._codes)
        return l2_dist_fn(self._vec)

    @property
    def cache_stats(self):
        """Uniform across tiers: the RAM engine has no block cache, so
        its record is all-zero rather than absent — callers never need
        hasattr/None special-casing to scrape one shape of counters."""
        from repro.store.cache import CacheStats   # lazy: import cycle
        return CacheStats(hits=0, misses=0, block_reads=0,
                          prefetch_batches=0, batched_reads=0)

    def io_stats(self, reset: bool = False):
        """Tier-uniform typed I/O record; the RAM engine does no block
        I/O, so the record is all-zero (and ``reset`` a no-op) rather
        than the method being absent."""
        from repro.store.cache import ZERO_IO_STATS   # lazy: import cycle
        return ZERO_IO_STATS

    def tombstone_fraction(self) -> float:
        """Dead-row share of the active range — the maintainer's
        background-consolidate trigger signal."""
        n = int(self.n_active)
        return float(self._tomb_np[:n].sum()) / n if n else 0.0

    # ---------------------------------------------------------------- search
    def search(self, queries: np.ndarray, k: int,
               beam_width: int | None = None,
               filter_labels: np.ndarray | None = None,
               max_iters: int | None = None,
               publish_mask: np.ndarray | None = None,
               trace=None
               ) -> tuple[np.ndarray, np.ndarray, SearchStats]:
        """Batched k-NN search.  Returns (ids (B,k), dists (B,k), stats).

        ``publish_mask`` ((B,) bool) opts lanes out of the catapult
        bucket publish and usage stats — the serving frontend masks its
        padded lanes, and a frozen-catapult baseline passes all-False.
        ``trace`` is an optional ``repro.obs.TraceRecorder``: when
        supplied, the route/rerank stages are timed into it (each stage
        syncs the device, so pass one only on explain queries).
        """
        with span("catapultdb.engine.upload"):
            queries = jnp.asarray(queries, jnp.float32)
        b = queries.shape[0]
        l = beam_width or max(2 * k, 16)
        # PQ mode reranks the *entire* final beam at full precision
        # (DiskANN's SSD fetch of the candidate list), so ask the search
        # for the whole beam, not just k PQ-approximate winners.
        # max_iters is a SAFETY bound, not a budget: Algorithm 1 terminates
        # when the beam converges, and the medoid->neighborhood walk can be
        # long at small beam widths (the whole point of catapults), so the
        # cap must stay far above typical path lengths.
        spec = SearchSpec(beam_width=l, k=(l if self.pq_subspaces else k),
                          max_iters=max_iters or (4 * l + 64),
                          hop_backend=self.hop_backend)
        flabels = (jnp.asarray(filter_labels, jnp.int32)
                   if filter_labels is not None
                   else jnp.full((b,), -1, jnp.int32))

        stage = stages(trace)
        with stage("route", "catapultdb.engine.route"):
            res, used, won, pub = self._dispatch(queries, flabels, spec,
                                                 publish_mask=publish_mask)
            if trace is not None:
                jax.block_until_ready(res.ids)

        # explain's "rerank" stage is the whole readback: the PQ rerank
        # runs between the beam's and the counts' host reads
        with stage("rerank", "catapultdb.engine.readback"):
            used, won = np.asarray(used), np.asarray(won)
            ids, dists = np.asarray(res.ids), np.asarray(res.dists)
            if self.pq_subspaces:  # full-precision rerank (DiskANN final fetch)
                with span("catapultdb.engine.rerank"):
                    rr = jax.vmap(partial(pq_mod.rerank, self._vec, k=k))(
                        queries, res.ids)
                    ids, dists = np.asarray(rr[0]), np.asarray(rr[1])
            stats = SearchStats(hops=np.asarray(res.hops),
                                ndists=np.asarray(res.ndists),
                                used=used, won=won,
                                **publish_counts(pub, trace))
        return ids, dists, stats

    def _dispatch(self, queries: jax.Array, flabels: jax.Array,
                  spec: 'SearchSpec', publish_mask=None):
        """Run the mode's jit'd traversal; returns (raw result, used, won,
        publish counts).

        Shared by the RAM search above and the disk engine's I/O-counted
        rerank path (repro.store.io_engine), which consumes the raw
        expansion trace instead of the device-side rerank.  A gated-off
        catapult engine (``catapult_enabled=False``) falls through to
        the diskann dispatch — identical jit cache entry, zero shortcut
        overhead.  Nothing here waits for the device: ``used``/``won``
        are device arrays in catapult mode, which the caller reads back,
        and the publish counts the device scalars
        ``(lanes_published, publish_rounds)`` (None in other modes),
        which only ``publish_counts`` under a trace reads.
        """
        b = queries.shape[0]
        if self.mode == 'catapult' and self.catapult_active:
            pm = (None if publish_mask is None
                  else jnp.asarray(publish_mask, bool))
            new_cat, res, st = _search_catapult(
                self._cat, self._adj, self._vec, self._tomb, self._labels,
                self._label_entry, queries, flabels, jnp.int32(self.medoid),
                spec, self.pq_subspaces or 0,
                self._pq if self.pq_subspaces else None,
                self._codes if self.pq_subspaces else None, pm)
            self._cat = new_cat
            return res, st.used, st.won, (st.lanes_published,
                                          st.publish_rounds)
        if self.mode == 'lsh_apg':
            res = _search_apg(self._apg, self._adj, self._vec, self._tomb,
                              self._labels, queries, flabels,
                              jnp.int32(self.medoid), spec)
        else:
            res = _search_diskann(self._adj, self._vec, self._tomb,
                                  self._labels, self._label_entry, queries,
                                  flabels, jnp.int32(self.medoid), spec,
                                  self.pq_subspaces or 0,
                                  self._pq if self.pq_subspaces else None,
                                  self._codes if self.pq_subspaces else None)
        z = np.zeros(b, bool)
        return res, z, z, None

    def search_two_phase(self, queries: np.ndarray, k: int,
                         beam_width: int | None = None,
                         phase1_iters: int = 8
                         ) -> tuple[np.ndarray, np.ndarray, SearchStats]:
        """Convergence-compacted search (beyond-paper optimization).

        A lockstep batch pays max(hops) while catapults cut the *mean*:
        fast lanes idle behind stragglers.  Phase 1 runs a short iteration
        budget for the whole batch; unconverged lanes are compacted
        host-side (padded to a power of two for jit-cache reuse) and
        phase 2 warm-restarts ONLY them from their phase-1 beams.  Total
        work ≈ B·M1 + |stragglers|·rest instead of B·max(hops).
        """
        queries = np.ascontiguousarray(queries, np.float32)
        b = queries.shape[0]
        l = beam_width or max(2 * k, 16)
        spec1 = SearchSpec(beam_width=l, k=l, max_iters=phase1_iters,
                           hop_backend=self.hop_backend)
        if self.mode == 'catapult' and self.catapult_active:
            new_cat, res, st = _search_catapult(
                self._cat, self._adj, self._vec, self._tomb, None, None,
                jnp.asarray(queries), jnp.full((b,), -1, jnp.int32),
                jnp.int32(self.medoid), spec1, 0, None, None)
            self._cat = new_cat
            used, won = np.asarray(st.used), np.asarray(st.won)
        else:
            res = _search_diskann(self._adj, self._vec, self._tomb, None,
                                  None, jnp.asarray(queries),
                                  jnp.full((b,), -1, jnp.int32),
                                  jnp.int32(self.medoid), spec1, 0, None,
                                  None)
            used = won = np.zeros(b, bool)
        ids = np.array(res.ids)
        dists = np.array(res.dists)
        hops = np.array(res.hops)
        ndists = np.array(res.ndists)
        conv = np.asarray(res.converged)

        if not conv.all():
            idx = np.nonzero(~conv)[0]
            # fixed phase-2 chunk => exactly one extra jit signature; the
            # straggler fraction rarely needs more than one chunk
            chunk = max(b // 4, 32)
            spec2 = SearchSpec(beam_width=l, k=l, max_iters=4 * l + 64,
                               hop_backend=self.hop_backend)
            for lo in range(0, idx.size, chunk):
                part = idx[lo: lo + chunk]
                sel = np.resize(part, chunk)   # pad by repetition
                res2 = beam_search_l2(self._adj, self._vec,
                                      jnp.asarray(queries[sel]),
                                      jnp.asarray(ids[sel], jnp.int32),
                                      spec2)
                ids[part] = np.asarray(res2.ids)[: part.size]
                dists[part] = np.asarray(res2.dists)[: part.size]
                hops[part] += np.asarray(res2.hops)[: part.size]
                ndists[part] += np.asarray(res2.ndists)[: part.size]
        order = np.argsort(dists, axis=1)[:, :k]
        # `won` is a phase-1 property: catapult starts either beat the
        # medoid at entry or they don't — phase-2 warm restarts reuse the
        # phase-1 beam, so the phase-1 CatapultStats carry through intact.
        stats = SearchStats(hops=hops, ndists=ndists, used=used, won=won)
        return (np.take_along_axis(ids, order, 1),
                np.take_along_axis(dists, order, 1), stats)

    # ---------------------------------------------------------------- updates
    def insert(self, new_vectors: np.ndarray,
               labels: np.ndarray | None = None) -> np.ndarray:
        """FreshVamana batch insert; returns the assigned node ids."""
        b = new_vectors.shape[0]
        start = self.n_active
        self.n_active = ins.insert_batch(
            self._adj_np, self._vec_np, self.n_active,
            np.ascontiguousarray(new_vectors, np.float32), self.medoid,
            self.vamana)
        self._tomb_np[start: self.n_active] = False
        if self._labels_np is not None:
            self._labels_np[start: self.n_active] = (
                labels if labels is not None else 0)
        if self.pq_subspaces:
            self._codes_np[start: self.n_active] = np.asarray(
                pq_mod.encode(self._pq, jnp.asarray(self._vec_np[start: self.n_active])))
        self._sync_device()
        return np.arange(start, self.n_active, dtype=np.int64)

    def insert_batch(self, new_vectors: np.ndarray,
                     labels: np.ndarray | None = None) -> np.ndarray:
        """Alias for :meth:`insert` — the mutable-tier spelling every
        backend (RAM / disk / sharded-disk) exposes uniformly."""
        return self.insert(new_vectors, labels)

    def delete(self, ids: np.ndarray) -> None:
        """Tombstone ``ids`` and repair every structure that could still
        steer a query onto them: catapult buckets are flushed of the dead
        destinations (a stale shortcut is a wasted beam start — and a
        wasted block read on disk), and a tombstoned medoid / label entry
        point is re-elected among the surviving nodes."""
        ids = np.atleast_1d(np.asarray(ids, np.int64)).ravel()
        ids = ids[ids >= 0]     # tolerate search()'s -1 padding lanes
        if ids.size == 0:
            return
        self._tomb_np = ins.delete(self._tomb_np, ids)
        self._tomb = jnp.asarray(self._tomb_np)
        if self.mode == 'catapult':
            self._cat = dataclasses.replace(
                self._cat,
                buckets=bk.evict_ids(self._cat.buckets,
                                     jnp.asarray(ids, jnp.int32)))
        if self._tomb_np[self.medoid]:
            self.medoid = self._elect_medoid()
        if self.filtered:
            self._label_entry = jnp.asarray(flt.refresh_label_entries(
                np.asarray(self._label_entry), self._vec_np,
                self._labels_np, self._tomb_np, self.n_active))

    def _elect_medoid(self) -> int:
        """Deterministic medoid re-election over the live rows."""
        live = (~self._tomb_np[: self.n_active]).nonzero()[0]
        if live.size == 0:
            return self.medoid
        return int(live[medoid_index(self._vec_np[live])])

    def consolidate(self) -> int:
        """Splice tombstoned nodes out of the graph (FreshVamana
        compaction): live in-neighbors inherit each deleted node's live
        out-edges under RobustPrune, then the deleted rows disconnect.
        Node ids stay stable; returns the number of repaired rows."""
        repaired = ins.consolidate(self._adj_np, self._vec_np,
                                   self._tomb_np, self.n_active, self.vamana)
        self._sync_device()
        return repaired


# ---------------------------------------------------------------------------
# jit'd search paths (functions of arrays only -> stable cache keys)
# ---------------------------------------------------------------------------

def _mk_dist(vec, pq_sub, pqcb, codes, hop_backend='unfused'):
    if hop_backend == 'fused':
        # fused hop backends ARE dist_fns (same jnp expressions, so
        # catapult entry scoring and filtered fallbacks are identical)
        # that additionally let beam_search run one kernel per hop
        from repro.kernels.fused_hop import FusedL2Hop, FusedPQHop
        if pq_sub:
            return FusedPQHop(pqcb, codes)
        return FusedL2Hop(vec)
    if pq_sub:
        return pq_mod.adc_dist_fn(pqcb, codes)
    return l2_dist_fn(vec)


def _masks(tomb, labels, flabels):
    """Traversal constraints shared by every engine tier (RAM and the
    disk/sharded paths dispatch through the same jit'd searches): the
    predicate mask comes from ``filters.make_filter_mask_fn``, the
    result mask hides tombstoned nodes."""
    def result_mask(ids):
        return ~tomb[jnp.maximum(ids, 0)]

    neighbor_mask = (flt.make_filter_mask_fn(labels, flabels)
                     if labels is not None else None)
    return neighbor_mask, result_mask


@partial(jax.jit, static_argnames=('spec', 'pq_sub'))
def _search_diskann(adj, vec, tomb, labels, label_entry, queries, flabels,
                    medoid, spec, pq_sub, pqcb, codes):
    b = queries.shape[0]
    if label_entry is not None:
        starts = jnp.where(flabels >= 0,
                           label_entry[jnp.maximum(flabels, 0)], medoid)
    else:
        starts = jnp.broadcast_to(medoid, (b,))
    nmask, rmask = _masks(tomb, labels, flabels)
    return beam_search(adj, queries, starts[:, None].astype(jnp.int32), spec,
                       _mk_dist(vec, pq_sub, pqcb, codes, spec.hop_backend),
                       neighbor_mask_fn=nmask, result_mask_fn=rmask)


@partial(jax.jit, static_argnames=('spec',))
def _search_apg(apg_index, adj, vec, tomb, labels, queries, flabels, medoid,
                spec):
    starts = apg.entry_points(apg_index, queries, medoid)
    nmask, rmask = _masks(tomb, labels, flabels)
    return beam_search(adj, queries, starts, spec,
                       _mk_dist(vec, 0, None, None, spec.hop_backend),
                       neighbor_mask_fn=nmask, result_mask_fn=rmask)


@partial(jax.jit, static_argnames=('spec', 'pq_sub'))
def _search_catapult(cat_state, adj, vec, tomb, labels, label_entry, queries,
                     flabels, medoid, spec, pq_sub, pqcb, codes,
                     publish_mask=None):
    nmask, rmask = _masks(tomb, labels, flabels)
    return cat.catapulted_lookup(
        cat_state, adj, queries, spec,
        _mk_dist(vec, pq_sub, pqcb, codes, spec.hop_backend),
        medoid, filter_labels=flabels, node_labels=labels,
        label_entry=label_entry, neighbor_mask_fn=nmask,
        result_mask_fn=rmask, publish_mask=publish_mask)
