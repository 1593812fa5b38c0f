"""ShardedDiskVectorSearchEngine — scatter-gather serving over CTPL shards.

The production shape of the disk tier (ROADMAP "sharded disk stores"):
the corpus is row-sharded into S independent CTPL block files, each
served by its own ``DiskVectorSearchEngine`` — one ``DiskStore``, one
CLOCK ``NodeCache``, and (in catapult mode) one private bucket table per
shard, exactly the paper's one-instance-per-replica deployment that
``core/sharded.py`` models on the device mesh.  This module is the
host/disk counterpart: per-shard searches run concurrently on a thread
pool (overlapping their block fetches the way independent SSD queue
pairs would), local results rebase to global row ids and merge with the
SAME ``rebase_ids``/``merge_topk`` helpers the shard_map path uses — so
the RAM mesh engine is the semantic reference for this one, and the
cross-tier parity test (tests/test_sharded_store.py) holds by
construction rather than by coincidence.

On-disk layout: a directory, not a file —

    <store_dir>/
        manifest.json           multi-shard manifest (FORMAT.md)
        shard_0000.ctpl         CTPL v2 block file, shard 0
        shard_0000.buckets.npz  catapult bucket state, shard 0 (save())
        shard_0001.ctpl         ...

Global ids are contiguous per shard: shard s owns rows
``[offsets[s], offsets[s] + capacity_s)``; at build time with no spare
capacity this makes global ids identical to corpus row order, so
recall measures directly against brute force on the unsharded corpus.

``save()``/``load()`` round-trip the whole index *including each
shard's catapult buckets* — unlike a process restart, a planned
save/restore keeps the workload-adapted hot state, so the first batch
after reopen catapults exactly like the last batch before.

The tier is mutable end-to-end (CTPL v3): ``insert_batch`` routes new
vectors to the least-loaded shard (most free preallocated capacity —
build with ``spare_capacity``), ``delete`` fans tombstones out to the
owning shards (persisted per shard in the v3 bitmap), ``consolidate``
runs every shard's compaction pass, and filtered searches fan out
against each shard's persisted per-label entry points.  Global ids are
capacity-ranged per shard and stable across all of it.
"""
from __future__ import annotations

import dataclasses
import json
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.adapt import stats as adapt_stats
from repro.core import buckets as bk
from repro.core import catapult as cat
from repro.core.engine import SearchStats
from repro.core.sharded import merge_topk, rebase_ids
from repro.core.vamana import VamanaParams
from repro.db.spec import IoSpec
from repro.obs.trace import stages
from repro.store.cache import CacheStats, IoStats
from repro.store.io_engine import DiskVectorSearchEngine

MANIFEST_NAME = "manifest.json"
MANIFEST_FORMAT = "ctpl-sharded"
MANIFEST_VERSION = 1


def _shard_file(s: int) -> str:
    return f"shard_{s:04d}.ctpl"


def _bucket_file(s: int) -> str:
    return f"shard_{s:04d}.buckets.npz"


def _summed_publish_counts(stats: list[SearchStats]) -> dict:
    """The shards' explain-only publish counts, summed; none when the
    shards reported none (no trace, or no catapult layer)."""
    counted = [st for st in stats if st.publish_rounds is not None]
    if not counted:
        return {}
    return dict(lanes_published=sum(st.lanes_published for st in counted),
                publish_rounds=sum(st.publish_rounds for st in counted))


@dataclasses.dataclass
class ShardedDiskVectorSearchEngine:
    """Scatter-gather facade over S disk-resident shard engines."""

    store_dir: str = "index.ctpl.d"
    n_shards: int = 2
    mode: str = "catapult"
    vamana: VamanaParams = dataclasses.field(default_factory=VamanaParams)
    n_bits: int = 8
    bucket_capacity: int = 40
    pq_subspaces: Optional[int] = None
    seed: int = 0
    cache_frames: int = 2048          # frames PER SHARD
    pin_catapult_destinations: bool = True
    max_workers: Optional[int] = None  # shard-fetch overlap; default = S
    # I/O engine config, applied PER SHARD (each shard engine owns its
    # cache + pipeline); None = manifest value on load / sync default
    io: Optional[IoSpec] = None
    # traversal hop implementation, applied PER SHARD ("unfused"/"fused")
    hop_backend: str = "unfused"

    # populated by build()/load()
    shards: list = dataclasses.field(default_factory=list)
    offsets: Optional[np.ndarray] = None   # (S+1,) global row offsets
    n_active: int = 0
    dim: int = 0
    filtered: bool = False
    n_labels: int = 0
    # durable caller-owned manifest entries (e.g. the ingest subsystem's
    # "ingest" spec + "keys" sidecar pointer): _write_manifest regenerates
    # the manifest from scratch on EVERY insert/save, so anything that
    # must survive those rewrites lives here and is merged in each time
    manifest_extra: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        if self.n_shards < 1:
            raise ValueError(f"need >= 1 shard, got {self.n_shards}")
        if self.mode not in ("catapult", "diskann"):
            raise ValueError(f"sharded disk engine supports catapult/diskann "
                             f"modes, got {self.mode!r}")
        self._pool = None

    # ---------------------------------------------------------------- build
    def build(self, vectors: np.ndarray, labels: np.ndarray | None = None,
              n_labels: int | None = None,
              spare_capacity: int = 0) -> "ShardedDiskVectorSearchEngine":
        """Row-shard ``vectors`` into S contiguous slices and build each
        shard's graph + store independently (per-shard seed = seed + s,
        matching ``core.sharded.build_sharded_state``) — build memory
        scales with the largest shard, not the corpus.

        ``labels``/``n_labels`` build each shard filtered (stitched
        graph + per-label entry points over the shard's slice).
        ``spare_capacity`` preallocates that many EXTRA rows in total,
        split evenly over the shards, so ``insert_batch`` has block
        space to route into.  Global ids are capacity-ranged: shard
        ``s`` owns ``[offsets[s], offsets[s] + capacity_s)``; with no
        spare this reduces to corpus row order.
        """
        vectors = np.ascontiguousarray(vectors, np.float32)
        n, d = vectors.shape
        self.filtered = labels is not None
        if self.filtered:
            assert n_labels is not None
            self.n_labels = int(n_labels)
        # resolve once so the manifest and every shard agree on the
        # I/O engine config (each shard gets its own cache + pipeline)
        self.io = self.io or IoSpec()
        os.makedirs(self.store_dir, exist_ok=True)
        bounds = np.linspace(0, n, self.n_shards + 1).astype(np.int64)
        # every requested spare slot materializes: the first
        # (spare_capacity mod S) shards absorb the remainder
        spare = np.full(self.n_shards, spare_capacity // self.n_shards,
                        np.int64)
        spare[: spare_capacity % self.n_shards] += 1
        self.offsets = np.zeros(self.n_shards + 1, np.int64)
        self.shards = []
        for s in range(self.n_shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            cap = hi - lo + int(spare[s])
            self.offsets[s + 1] = self.offsets[s] + cap
            eng = DiskVectorSearchEngine(
                mode=self.mode,
                vamana=dataclasses.replace(self.vamana, seed=self.seed + s),
                n_bits=self.n_bits, bucket_capacity=self.bucket_capacity,
                pq_subspaces=self.pq_subspaces, seed=self.seed + s,
                cache_frames=self.cache_frames, capacity=cap,
                pin_catapult_destinations=self.pin_catapult_destinations,
                io=self.io, hop_backend=self.hop_backend,
                store_path=os.path.join(self.store_dir, _shard_file(s)))
            if self.filtered:
                eng.build(vectors[lo:hi], labels=labels[lo:hi],
                          n_labels=self.n_labels)
            else:
                eng.build(vectors[lo:hi])
            self.shards.append(eng)
        self.n_active, self.dim = n, d
        self._write_manifest()
        return self

    def _write_manifest(self) -> None:
        manifest = {
            "format": MANIFEST_FORMAT,
            "version": MANIFEST_VERSION,
            "n_shards": self.n_shards,
            "dim": self.dim,
            "mode": self.mode,
            "seed": self.seed,
            "n_bits": self.n_bits,
            "bucket_capacity": self.bucket_capacity,
            "filtered": self.filtered,
            "n_labels": self.n_labels,
            # the sharded tier's IoSpec home is the manifest (the
            # per-shard .io.json sidecars exist but the manifest wins),
            # so open() resumes the pipeline/admission setup tier-wide
            "io": (self.io or IoSpec()).to_dict(),
            "offsets": [int(o) for o in self.offsets],
            "shards": [{
                "file": _shard_file(s),
                "n_active": int(eng.n_active),
                "capacity": int(eng.capacity or eng.n_active),
                # the adapt layer's utility gate survives a reopen: a
                # gated-off replica must not pay catapult overhead on
                # its first post-restart batches either
                "catapult_enabled": bool(eng.catapult_enabled),
            } for s, eng in enumerate(self.shards)],
        }
        manifest.update(self.manifest_extra)
        tmp = os.path.join(self.store_dir, MANIFEST_NAME + ".tmp")
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, os.path.join(self.store_dir, MANIFEST_NAME))

    # ------------------------------------------------------------ adaptation
    @property
    def catapult_enabled(self) -> bool:
        """The adapt layer's utility gate, fanned out over the shards."""
        return all(eng.catapult_enabled for eng in self.shards)

    @catapult_enabled.setter
    def catapult_enabled(self, flag: bool) -> None:
        for eng in self.shards:
            eng.catapult_enabled = bool(flag)

    @property
    def catapult_active(self) -> bool:
        """Effective dispatch switch (gate + any transient shadow/probe
        override), true only when every shard would catapult."""
        return all(eng.catapult_active for eng in self.shards)

    # ---------------------------------------------------------------- search
    def _executor(self) -> ThreadPoolExecutor:
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.max_workers or self.n_shards)
        return self._pool

    def search(self, queries: np.ndarray, k: int,
               beam_width: int | None = None,
               filter_labels: np.ndarray | None = None,
               max_iters: int | None = None,
               publish_mask: np.ndarray | None = None,
               trace=None
               ) -> tuple[np.ndarray, np.ndarray, SearchStats]:
        """Scatter the batch to every shard, gather + merge global top-k.

        Shard searches run concurrently on the thread pool, so block
        fetches overlap across shards.  The requested beam is SPLIT
        across shards (floored at k): every shard still returns k
        candidates, so the merged pool is S·k ≥ the single-store pool,
        but the per-shard traversal narrows as S grows — aggregate
        block reads stay in the single-store regime instead of
        multiplying by S.  Per-lane stats aggregate over shards:
        hops/ndists/block_reads/cache_hits sum (total work the query
        cost the system), used/won OR (any shard's catapult fired); the
        explain-only publish counts sum (every shard publishes into its
        own buckets).

        Filtered queries (``filter_labels``, -1 = unfiltered lane) fan
        out unchanged: every shard constrains its own traversal via its
        per-label entry points, and the merge keeps the global top-k of
        the predicate-satisfying union.

        ``trace`` (optional ``repro.obs.TraceRecorder``): the whole
        fan-out is timed as one ``scatter`` span and the merge as
        ``merge``; each shard fills its own child recorder, and the
        top-level ``route``/``fetch``/``rerank`` spans are the MAXIMUM
        over shards — the critical path through the overlapped pool,
        not a sum that double-counts concurrency.
        """
        if not self.shards:
            raise RuntimeError("build() or load() first")
        stage = stages(trace)
        # mirror the single-store default (L ≈ 3k, io_engine.search),
        # then divide it over the scatter width
        beam = beam_width or max(3 * k, 24)
        per_shard_beam = max(k, -(-beam // self.n_shards))
        kids = ([trace.child(f"shard_{s}") for s in range(self.n_shards)]
                if trace is not None else [None] * self.n_shards)

        def one(arg):
            eng, kid = arg
            return eng.search(queries, k, beam_width=per_shard_beam,
                              filter_labels=filter_labels,
                              max_iters=max_iters,
                              publish_mask=publish_mask, trace=kid)

        with stage("scatter", "catapultdb.sharded.scatter"):
            results = list(self._executor().map(one, zip(self.shards, kids)))
        with stage("merge", "catapultdb.sharded.merge"):
            all_ids = np.stack([
                np.asarray(rebase_ids(ids, int(self.offsets[s])))
                for s, (ids, _, _) in enumerate(results)])        # (S, B, k)
            all_d = np.stack([d for _, d, _ in results])           # (S, B, k)
            merged_ids, merged_d = merge_topk(jnp.asarray(all_ids),
                                              jnp.asarray(all_d), k)
            merged_ids = np.asarray(merged_ids)
            merged_d = np.asarray(merged_d)
        if trace is not None:
            for name in ("route", "fetch", "speculate", "rerank"):
                trace.add_stage(name, max(kid.stage_ms(name)
                                          for kid in kids))
        stats = SearchStats(
            hops=np.sum([st.hops for _, _, st in results], axis=0),
            ndists=np.sum([st.ndists for _, _, st in results], axis=0),
            used=np.any([st.used for _, _, st in results], axis=0),
            won=np.any([st.won for _, _, st in results], axis=0),
            block_reads=np.sum([st.block_reads for _, _, st in results],
                               axis=0),
            cache_hits=np.sum([st.cache_hits for _, _, st in results],
                              axis=0),
            **_summed_publish_counts([st for _, _, st in results]))
        return merged_ids, merged_d, stats

    # ---------------------------------------------------------------- updates
    def _shard_of(self, global_ids: np.ndarray) -> np.ndarray:
        return (np.searchsorted(self.offsets, global_ids, side="right")
                - 1).astype(np.int64)

    def insert_batch(self, new_vectors: np.ndarray,
                     labels: np.ndarray | None = None) -> np.ndarray:
        """Route inserts to the least-loaded shard; returns global ids.

        "Least-loaded" = most free preallocated block capacity, so a
        stream of inserts levels the shards instead of piling onto one.
        A batch larger than any single shard's headroom splits greedily
        across shards in input order.  Build with ``spare_capacity`` (or
        per-shard ``capacity``) to have headroom at all.
        """
        vectors = np.ascontiguousarray(new_vectors, np.float32)
        b = vectors.shape[0]
        out = np.empty(b, np.int64)
        pos = 0
        while pos < b:
            free = np.array([(e.capacity or e.n_active) - e.n_active
                             for e in self.shards])
            s = int(np.argmax(free))
            if free[s] <= 0:
                raise RuntimeError(
                    "every shard is at capacity; rebuild with spare_capacity")
            take = min(int(free[s]), b - pos)
            chunk_labels = (labels[pos: pos + take]
                            if labels is not None else None)
            local = self.shards[s].insert_batch(vectors[pos: pos + take],
                                                chunk_labels)
            out[pos: pos + take] = local + int(self.offsets[s])
            pos += take
        self.n_active += b
        self._write_manifest()
        return out

    def delete(self, global_ids: np.ndarray) -> None:
        """Fan tombstone deletes out to the owning shards."""
        gids = np.atleast_1d(np.asarray(global_ids, np.int64)).ravel()
        gids = gids[gids >= 0]  # tolerate search()'s -1 padding lanes
        shard_of = self._shard_of(gids)
        for s in np.unique(shard_of):
            self.shards[int(s)].delete(gids[shard_of == s]
                                       - int(self.offsets[int(s)]))

    def consolidate(self) -> int:
        """Run every shard's compaction pass; returns total repaired rows."""
        return sum(eng.consolidate() for eng in self.shards)

    # ---------------------------------------------------------------- I/O
    @property
    def cache_stats(self) -> CacheStats:
        """Aggregate cache counters over every shard's node cache."""
        per = [eng.cache.stats for eng in self.shards]
        return CacheStats(*[sum(s[i] for s in per) for i in range(5)])

    def io_stats(self, reset: bool = False) -> IoStats:
        """Tier-wide I/O record: each shard's counters summed exactly
        once (every block read/hit/prefetch belongs to one shard's cache,
        so the sum never double-counts the overlapped fan-out)."""
        per = [eng.io_stats(reset=reset) for eng in self.shards]
        return IoStats(*[sum(s[i] for s in per)
                         for i in range(len(IoStats._fields))])

    def reset_io(self) -> None:
        for eng in self.shards:
            eng.reset_io()

    def tombstone_fraction(self) -> float:
        """Dead-row share across every shard (maintainer's background-
        consolidate trigger)."""
        dead = sum(int(eng._tomb_np[:eng.n_active].sum())
                   for eng in self.shards)
        n = sum(int(eng.n_active) for eng in self.shards)
        return dead / n if n else 0.0

    # ---------------------------------------------------------------- persist
    def save(self) -> None:
        """Flush every shard + manifest, and snapshot catapult buckets.

        Bucket state is workload state, but a *planned* save/restore
        (maintenance restart, replica clone) wants it back: the first
        batch after ``load()`` then catapults exactly like the last
        batch before ``save()``.
        """
        for s, eng in enumerate(self.shards):
            # header + tombstone bitmap + label entries; adapt state is
            # the SHARDED layer's to persist (below + manifest), not the
            # per-shard engine sidecar's
            eng.save(include_adapt=False)
            if self.mode == "catapult":
                # adapt telemetry rides in the same sidecar: a reopened
                # index resumes mid-drift (histograms, win EWMA and all)
                # instead of relearning the workload from zero
                extra = (adapt_stats.telemetry_to_arrays(eng.adapt_state)
                         if eng.adapt_state is not None else {})
                np.savez(os.path.join(self.store_dir, _bucket_file(s)),
                         **bk.to_arrays(eng._cat.buckets), **extra)
        self._write_manifest()

    @classmethod
    def load(cls, store_dir: str, mode: str | None = None,
             **engine_kwargs) -> "ShardedDiskVectorSearchEngine":
        """Reopen a sharded index from its manifest directory.

        Each shard reopens through ``DiskVectorSearchEngine.load`` (PQ
        codebook from the CTPL v2 section, graph via memmap) and, when a
        bucket snapshot exists, restores its catapult table — full
        round-trip of the serving state.
        """
        with open(os.path.join(store_dir, MANIFEST_NAME)) as f:
            manifest = json.load(f)
        if manifest.get("format") != MANIFEST_FORMAT:
            raise ValueError(f"not a sharded CTPL manifest: "
                             f"{manifest.get('format')!r}")
        if int(manifest.get("version", 0)) != MANIFEST_VERSION:
            raise ValueError(f"unsupported manifest version "
                             f"{manifest.get('version')}")
        mode = mode or manifest["mode"]
        self = cls(store_dir=store_dir, n_shards=int(manifest["n_shards"]),
                   mode=mode, seed=int(manifest["seed"]),
                   n_bits=int(manifest["n_bits"]),
                   bucket_capacity=int(manifest["bucket_capacity"]),
                   **engine_kwargs)
        self.offsets = np.asarray(manifest["offsets"], np.int64)
        self.dim = int(manifest["dim"])
        self.filtered = bool(manifest.get("filtered", False))
        self.n_labels = int(manifest.get("n_labels", 0))
        # keep caller-owned entries durable across future rewrites
        self.manifest_extra = {key: manifest[key]
                               for key in ("ingest", "keys")
                               if key in manifest}
        if self.io is None and "io" in manifest:
            # no caller preference: resume the I/O engine config the
            # index was tuned with (pre-io manifests fall through to
            # the synchronous default below)
            self.io = IoSpec.from_dict(manifest["io"])
        self.io = self.io or IoSpec()
        self.shards = []
        for s, meta in enumerate(manifest["shards"]):
            eng = DiskVectorSearchEngine.load(
                os.path.join(store_dir, meta["file"]), mode=mode,
                vamana=dataclasses.replace(self.vamana, seed=self.seed + s),
                n_bits=self.n_bits, bucket_capacity=self.bucket_capacity,
                seed=self.seed + s, cache_frames=self.cache_frames,
                pin_catapult_destinations=self.pin_catapult_destinations,
                io=self.io, hop_backend=self.hop_backend)
            bpath = os.path.join(store_dir, _bucket_file(s))
            if mode == "catapult" and os.path.exists(bpath):
                with np.load(bpath) as z:
                    buckets = bk.from_arrays(z)
                    eng.adapt_state = adapt_stats.telemetry_from_arrays(z)
                eng._cat = cat.CatapultState(lsh=eng._cat.lsh,
                                             buckets=buckets)
            eng.catapult_enabled = bool(meta.get("catapult_enabled", True))
            self.shards.append(eng)
        self.n_active = sum(eng.n_active for eng in self.shards)
        return self

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        for eng in self.shards:
            eng.close()
