"""Batched serving engines: LM continuous batching + vector-search routing.

Two front doors live here:

* ``ServingEngine`` — slot-based continuous batching for LM decode
  (vLLM-style, sized down): a fixed pool of B decode slots; finished
  sequences free their slot and the next queued request is prefilled
  into it.  All steps are jit'd once per shape; the scheduler is
  host-side.
* ``VectorSearchFrontend`` — micro-batching router for retrieval: single
  queries coalesce into fixed-shape batches and dispatch to ANY search
  backend — the RAM ``VectorSearchEngine``, the single-store
  ``DiskVectorSearchEngine``, or the scatter-gather
  ``ShardedDiskVectorSearchEngine`` — so the disk tier serves the same
  traffic shape the paper's RAG deployment (§1) generates: many
  independent callers, one batched index.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.engine import SearchStats
from repro.models import model as M
from repro.obs import NULL_INSTRUMENT, RollingWindow, span


class VectorSearchFrontend:
    """Coalesce single search requests into fixed-shape backend batches.

    The backend's jit cache is keyed on batch shape, so the frontend
    always dispatches full ``max_batch``-row batches, padding by
    repeating the last real query.  Padded lanes are masked out of the
    catapult bucket publish and out of the returned stats
    (``publish_mask``): an unmasked pad would double-publish the last
    real query's destination — skewing the bucket LRU toward
    batch-boundary traffic — and double-count it in the adapt layer's
    win-rate/drift telemetry.  ``submit`` returns a ticket; ``flush``
    services every pending ticket in ONE backend search per chunk and
    returns ``{ticket: (ids, dists)}``.  ``search`` is the
    batch-in/batch-out convenience used by bulk callers (it also
    returns the per-chunk SearchStats for I/O attribution, real lanes
    only).

    ``k``/``beam_width`` are per-request: ``submit(q, k=...,
    beam_width=...)`` overrides the construction-time defaults for that
    ticket only.  ``flush`` groups pending tickets by their effective
    (k, beam) pair — requests sharing a pair batch together, so the
    backend's jit cache stays bounded by the number of distinct pairs
    in flight, never by request interleaving order — and each ticket
    gets back ids/dists shaped by ITS k.

    ``maintainer`` (a ``repro.adapt.CatapultMaintainer``) hooks the
    workload-adaptation loop into the serving path: every dispatched
    chunk is observed (real lanes only), and maintenance ticks ride
    the flush cadence.

    Serving telemetry: ``window`` (a ``repro.obs.RollingWindow``) keeps
    a bounded rolling readout — QPS, mean batch occupancy, flush
    latency percentiles — recorded once per ``flush()``/bulk
    ``search()`` call (one deque append; always on).  ``metrics`` (an
    optional ``repro.obs.MetricsRegistry``) additionally publishes
    flush counts and a full-history flush-latency histogram;
    ``Database.serve()`` passes its own registry here.

    Each ``flush()``/``search()`` call is one host span
    (``catapultdb.frontend.flush`` / ``.search``) whose arguments count
    its ``tickets`` and ``chunks`` and sum and max the waits, in ms,
    from each ticket's ``submit()`` (a bulk search's call) to the
    dispatch of its chunk.  Inside it, per chunk, ``pack`` (group,
    stack, pad, mask), ``dispatch`` (the backend search, argument
    ``dispatch``: the batch's index), ``unpack`` (trim stats, hand out
    answers); then ``ingest`` around the ingest pump.
    """

    def __init__(self, backend, *, k: int = 10, max_batch: int = 64,
                 beam_width: Optional[int] = None, maintainer=None,
                 metrics=None, ingest=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.backend = backend
        self.k, self.max_batch, self.beam_width = k, max_batch, beam_width
        self.maintainer = maintainer
        # an attached repro.ingest.IngestQueue is pumped once per
        # flush()/bulk search() — writes interleave with serving at
        # flush granularity instead of competing for the backend
        self.ingest = ingest
        # ticket queue entries: (ticket, query, k, beam_width, submit
        # time) with the per-request overrides already resolved against
        # the defaults
        self._queue: list[tuple[int, np.ndarray, int, Optional[int],
                                float]] = []
        self._next_ticket = 0
        self.batches_dispatched = 0
        self.window = RollingWindow()
        self._m_flushes = (metrics.counter("catapultdb_serve_flushes_total")
                           if metrics is not None else NULL_INSTRUMENT)
        self._m_flush_ms = (metrics.histogram("catapultdb_serve_flush_ms")
                            if metrics is not None else NULL_INSTRUMENT)

    def submit(self, query: np.ndarray, k: Optional[int] = None,
               beam_width: Optional[int] = None) -> int:
        """Queue one query; ``k``/``beam_width`` override the frontend
        defaults for this ticket only."""
        q = np.ascontiguousarray(query, np.float32).ravel()
        ticket = self._next_ticket
        self._next_ticket += 1
        self._queue.append((ticket, q, k or self.k,
                            beam_width or self.beam_width,
                            time.perf_counter()))
        return ticket

    @property
    def pending(self) -> int:
        return len(self._queue)

    def _dispatch_chunk(self, qs: np.ndarray, k: int,
                        beam_width: Optional[int] = None):
        """Pad to the fixed batch shape, search with padded lanes masked
        out of publishes, and return (ids, dists, stats) trimmed to the
        real lanes, and when the search was dispatched; feeds the
        maintainer when one is attached."""
        with span("catapultdb.frontend.pack"):
            real = qs.shape[0]
            pad = self.max_batch - real
            if pad:
                qs = np.concatenate([qs, np.repeat(qs[-1:], pad, axis=0)])
            mask = np.zeros(self.max_batch, bool)
            mask[:real] = True
        t_dispatch = time.perf_counter()
        with span("catapultdb.frontend.dispatch",
                  dispatch=self.batches_dispatched):
            ids, dists, stats = self.backend.search(
                qs, k=k, beam_width=beam_width, publish_mask=mask)
            self.batches_dispatched += 1
            if self.maintainer is not None:
                # full padded shape + real_mask, NOT the trimmed views:
                # the telemetry fold is jit'd on array shape, and one
                # fixed (max_batch,) signature is the whole point of the
                # padding
                self.maintainer.observe(qs, stats, real_mask=mask)
        with span("catapultdb.frontend.unpack"):
            stats = SearchStats(
                hops=np.asarray(stats.hops)[:real],
                ndists=np.asarray(stats.ndists)[:real],
                used=np.asarray(stats.used)[:real],
                won=np.asarray(stats.won)[:real],
                block_reads=(None if stats.block_reads is None
                             else np.asarray(stats.block_reads)[:real]),
                cache_hits=(None if stats.cache_hits is None
                            else np.asarray(stats.cache_hits)[:real]))
            ids, dists = np.asarray(ids[:real]), np.asarray(dists[:real])
        return ids, dists, stats, t_dispatch

    def _pump_ingest(self) -> None:
        if self.ingest is not None:
            with span("catapultdb.frontend.ingest"):
                self.ingest.pump()

    def flush(self) -> dict[int, tuple[np.ndarray, np.ndarray]]:
        """Serve every queued request; returns {ticket: (ids, dists)}.

        Tickets group by their effective (k, beam) pair — submission
        order is preserved within a pair, and each pair dispatches its
        own fixed-shape chunks, so mixed-k traffic costs one jit
        signature per distinct pair, not one per flush pattern."""
        with span("catapultdb.frontend.flush") as sp:
            out: dict[int, tuple[np.ndarray, np.ndarray]] = {}
            with span("catapultdb.frontend.pack"):
                groups: dict[tuple, list] = {}
                for entry in self._queue:
                    groups.setdefault((entry[2], entry[3]), []).append(entry)
                self._queue = []
            t0 = time.perf_counter()
            served = 0
            occupancy: list[float] = []
            wait_sum = wait_max = 0.0
            for (k, beam), entries in groups.items():
                for lo in range(0, len(entries), self.max_batch):
                    chunk = entries[lo: lo + self.max_batch]
                    with span("catapultdb.frontend.pack"):
                        qs = np.stack([e[1] for e in chunk])
                    ids, dists, _, t_dispatch = self._dispatch_chunk(
                        qs, k, beam)
                    served += len(chunk)
                    occupancy.append(len(chunk) / self.max_batch)
                    with span("catapultdb.frontend.unpack"):
                        for row, entry in enumerate(chunk):
                            out[entry[0]] = (ids[row], dists[row])
                    waits = t_dispatch - np.array([e[4] for e in chunk])
                    wait_sum += float(waits.sum())
                    wait_max = max(wait_max, float(waits.max()))
            if served:
                ms = (time.perf_counter() - t0) * 1e3
                self.window.record_flush(
                    queries=served, occupancy=float(np.mean(occupancy)),
                    ms=ms)
                self._m_flushes.inc()
                self._m_flush_ms.observe(ms)
            self._pump_ingest()
            sp.set_metadata(tickets=served, chunks=len(occupancy),
                            wait_ms_sum=wait_sum * 1e3,
                            wait_ms_max=wait_max * 1e3)
        return out

    def search(self, queries: np.ndarray, k: Optional[int] = None,
               beam_width: Optional[int] = None):
        """Bulk path: chunk a (Q, d) batch through the backend and
        reassemble — same route the ticketed path takes, minus the queue."""
        k = k or self.k
        beam_width = beam_width or self.beam_width
        queries = np.ascontiguousarray(queries, np.float32)
        if queries.shape[0] == 0:
            return (np.empty((0, k), np.int32),
                    np.empty((0, k), np.float32), [])
        with span("catapultdb.frontend.search") as sp:
            all_ids, all_d, all_stats = [], [], []
            t0 = time.perf_counter()
            occupancy: list[float] = []
            wait_sum = wait_max = 0.0
            for lo in range(0, queries.shape[0], self.max_batch):
                ids, dists, stats, t_dispatch = self._dispatch_chunk(
                    queries[lo: lo + self.max_batch], k, beam_width)
                occupancy.append(ids.shape[0] / self.max_batch)
                all_ids.append(ids)
                all_d.append(dists)
                all_stats.append(stats)
                wait_sum += (t_dispatch - t0) * ids.shape[0]
                wait_max = t_dispatch - t0
            ms = (time.perf_counter() - t0) * 1e3
            self.window.record_flush(queries=int(queries.shape[0]),
                                     occupancy=float(np.mean(occupancy)),
                                     ms=ms)
            self._m_flushes.inc()
            self._m_flush_ms.observe(ms)
            self._pump_ingest()
            sp.set_metadata(tickets=int(queries.shape[0]),
                            chunks=len(occupancy),
                            wait_ms_sum=wait_sum * 1e3,
                            wait_ms_max=wait_max * 1e3)
        return (np.concatenate(all_ids), np.concatenate(all_d), all_stats)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    out: Optional[np.ndarray] = None


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_len: int = 128, eos_id: int = 1):
        self.cfg, self.params = cfg, params
        self.slots, self.max_len, self.eos = slots, max_len, eos_id
        self.cache = M.init_cache(cfg, slots, max_len)
        self.pos = np.zeros(slots, np.int64)       # next write offset
        self.budget = np.zeros(slots, np.int64)    # remaining new tokens
        self.active: list[Optional[Request]] = [None] * slots
        self.last_tok = np.zeros(slots, np.int64)

        self._decode = jax.jit(
            lambda p, t, c, pos: M.decode_step(cfg, p, t, c, pos))

    def _prefill_into_slot(self, slot: int, req: Request) -> None:
        """Feed the prompt token-by-token through decode (slot-local
        prefill; a production system would batch this with paged caches)."""
        toks = req.prompt.astype(np.int64)
        for i, t in enumerate(toks):
            tok = jnp.full((self.slots, 1), 0, jnp.int32).at[slot, 0].set(
                int(t))
            logits, self.cache = self._decode(
                self.params, tok, self.cache, jnp.int32(self.pos[slot]))
            self.pos[slot] += 1
        nxt = int(jnp.argmax(logits[slot, -1]))
        self.last_tok[slot] = nxt
        self.budget[slot] = req.max_new_tokens
        req.out = np.asarray([nxt], np.int64)
        self.active[slot] = req

    def run(self, requests: list[Request]) -> list[Request]:
        """Serve all requests to completion; returns them with .out filled."""
        pending = list(requests)
        done: list[Request] = []
        while pending or any(a is not None for a in self.active):
            # admit
            for s in range(self.slots):
                if self.active[s] is None and pending:
                    self.pos[s] = 0
                    self._prefill_into_slot(s, pending.pop(0))
            # one decode step for every active slot (single batched call)
            toks = jnp.asarray(self.last_tok, jnp.int32)[:, None]
            # NOTE: slots may be at different positions; per-slot positions
            # via the max — correctness is kept by masking: slots write at
            # their own offset.  We step each slot with its own call when
            # offsets diverge (host scheduler keeps them aligned per wave).
            groups: dict[int, list[int]] = {}
            for s in range(self.slots):
                if self.active[s] is not None:
                    groups.setdefault(int(self.pos[s]), []).append(s)
            for off, ss in groups.items():
                logits, self.cache = self._decode(
                    self.params, toks, self.cache, jnp.int32(off))
                for s in ss:
                    nxt = int(jnp.argmax(logits[s, -1]))
                    req = self.active[s]
                    req.out = np.append(req.out, nxt)
                    self.pos[s] += 1
                    self.budget[s] -= 1
                    self.last_tok[s] = nxt
                    if (nxt == self.eos or self.budget[s] <= 0
                            or self.pos[s] >= self.max_len - 1):
                        done.append(req)
                        self.active[s] = None
        return done
