"""Graph-based beam search — Algorithm 1 of the paper, TPU-native.

The paper's Algorithm 1 (DiskANN-style best-first beam search) is a per-query
pointer-chasing loop on CPU.  Here it is re-derived for TPU:

* a *batch* of queries runs in lockstep inside one ``lax.while_loop`` —
  each lane holds a fixed-size beam (ids / dists / expanded flags) and
  expands its closest unexpanded entry per iteration; converged lanes
  mask their updates to no-ops,
* neighbor fetch is a vectorized gather (the HBM analogue of DiskANN's
  SSD read; the overlapped Pallas version is ``kernels.gather_distance``),
* distances are computed with a pluggable ``dist_fn`` so the engine can
  swap full-precision, PQ-approximate (DiskANN's in-memory path), or the
  Pallas MXU kernels without touching the traversal,
* the visited set is the beam itself: a candidate already present in the
  beam is deduplicated by id-matching (L×R comparisons), mirroring
  Algorithm 1's `V` check, and distance-computation counts exclude dupes.

Starting points are an *array* (padded with -1), which is precisely the
hook the catapult layer uses (paper §3.1: "queries are simply routed to a
better starting point"): the traversal below never knows whether its
starts came from the medoid, a per-label entry point, or a catapult.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distance import sq_l2

INVALID = jnp.int32(-1)
INF = jnp.float32(jnp.inf)


class BeamState(NamedTuple):
    ids: jax.Array        # (B, L) int32, -1 = empty slot
    dists: jax.Array      # (B, L) f32, +inf for empty slots
    expanded: jax.Array   # (B, L) bool, True for empty slots (never selected)
    hops: jax.Array       # (B,) int32 — number of node expansions ("nodes visited")
    ndists: jax.Array     # (B,) int32 — distance computations performed
    trace: jax.Array      # (B, max_iters) int32 — expansion order (Vamana build needs it)
    scored: jax.Array     # (B, max_iters, R) int32 — ALL neighbors whose
                          # distance was computed (RobustPrune's V set), or
                          # a (B, 1, 1) dummy when not requested
    it: jax.Array         # () int32 — global iteration counter


class SearchResult(NamedTuple):
    ids: jax.Array       # (B, k)
    dists: jax.Array     # (B, k)
    hops: jax.Array      # (B,)
    ndists: jax.Array    # (B,)
    trace: jax.Array     # (B, max_iters) expanded node ids, -1 padded
    scored: jax.Array    # (B, max_iters, R) scored-neighbor ids (build only)
    converged: jax.Array # (B,) bool — beam fully expanded (vs. iter cap)


def l2_dist_fn(vectors: jax.Array) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Default distance: full-precision squared L2 against a vector table."""

    def dist(q: jax.Array, ids: jax.Array) -> jax.Array:
        x = vectors[jnp.maximum(ids, 0)]
        return jnp.where(ids < 0, INF, sq_l2(x, q[None, :]))

    return dist


def _dedup_candidates(cand_ids: jax.Array, cand_dists: jax.Array,
                      beam_ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Mask candidates already in the beam or duplicated among themselves."""
    in_beam = jnp.any(
        (cand_ids[:, None] == beam_ids[None, :]) & (beam_ids[None, :] >= 0), axis=1)
    c = cand_ids.shape[0]
    earlier = (cand_ids[:, None] == cand_ids[None, :]) & (
        jnp.arange(c)[None, :] < jnp.arange(c)[:, None])
    dup = in_beam | jnp.any(earlier, axis=1)
    fresh = ~dup & (cand_ids >= 0)
    cand_dists = jnp.where(fresh, cand_dists, INF)
    return cand_dists, fresh


def _merge(beam_ids, beam_dists, beam_exp, cand_ids, cand_dists):
    """Merge candidates into the fixed-size beam, keeping the L closest."""
    l = beam_ids.shape[0]
    cand_dists, fresh = _dedup_candidates(cand_ids, cand_dists, beam_ids)
    ids = jnp.concatenate([beam_ids, cand_ids])
    dists = jnp.concatenate([beam_dists, cand_dists])
    exp = jnp.concatenate([beam_exp, jnp.zeros(cand_ids.shape, bool)])
    order = jnp.argsort(dists)[:l]
    ids, dists, exp = ids[order], dists[order], exp[order]
    invalid = ~jnp.isfinite(dists)
    ids = jnp.where(invalid, INVALID, ids)
    exp = exp | invalid
    return ids, dists, exp, jnp.sum(fresh).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class SearchSpec:
    """Static configuration of a beam search (hashable; closed over by jit)."""
    beam_width: int
    k: int
    max_iters: int
    # record every scored neighbor (Vamana build needs RobustPrune's full
    # visited set V — the expansion path alone lacks the long-range
    # diversity that keeps clustered corpora navigable)
    record_scored: bool = False
    # "unfused" = composed jnp/vmap hop; "fused" = single Pallas dispatch
    # per hop (kernels.fused_hop) when the dist_fn is a fused hop backend.
    # Results are bit-identical either way; this is purely a speed knob.
    hop_backend: str = "unfused"


def beam_search(
    adjacency: jax.Array,           # (N, R) int32, -1 padded
    queries: jax.Array,             # (B, d)
    start_ids: jax.Array,           # (B, S) int32, -1 padded
    spec: SearchSpec,
    dist_fn: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    neighbor_mask_fn: Optional[Callable[[jax.Array, jax.Array], jax.Array]] = None,
    result_mask_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
) -> SearchResult:
    """Batched Algorithm 1.

    Args:
      adjacency: out-edges of the proximity graph, -1 padded to max degree R.
      queries: query batch.
      start_ids: per-query starting points (medoid / label entry / catapults).
      spec: beam width L, result count k, iteration bound.
      dist_fn: (q:(d,), ids:(m,)) -> (m,) distances (+inf for id<0 is the
        caller's duty for exotic dist_fns; the default helpers handle it).
      neighbor_mask_fn: (lane_aux, ids) -> bool — False excludes a node from
        the beam entirely (FilteredVamana traversal constraint).  lane_aux is
        the per-lane query index, letting filters differ across the batch.
      result_mask_fn: ids -> bool — False excludes a node from *results* only
        (tombstoned nodes remain traversable, FreshVamana-style).

    Returns a SearchResult; `trace` records expansion order for graph build.
    """
    b, _ = queries.shape
    l, max_iters = spec.beam_width, spec.max_iters
    # Fused hop path: dist_fn doubles as a hop backend (kernels.fused_hop)
    # carrying the gather table; one Pallas dispatch covers gather +
    # distance + merge for the whole batch.  Filtered traversal masks
    # distances per neighbor, which the kernel does not model — those
    # searches stay on the composed path (results are identical; the
    # fused path is purely a speed knob).
    use_fused = (getattr(dist_fn, "is_fused_hop", False)
                 and neighbor_mask_fn is None)

    def lane_init(q, sp, lane_idx):
        d0 = dist_fn(q, sp)
        if neighbor_mask_fn is not None:
            d0 = jnp.where(neighbor_mask_fn(lane_idx, sp), d0, INF)
        d0 = jnp.where(sp < 0, INF, d0)
        ids0 = jnp.full((l,), INVALID, jnp.int32)
        dists0 = jnp.full((l,), INF)
        exp0 = jnp.ones((l,), bool)
        ids, dists, exp, n = _merge(ids0, dists0, exp0, sp, d0)
        return ids, dists, exp, n

    lane_idx = jnp.arange(b, dtype=jnp.int32)
    if use_fused:
        # init is a fused hop into an empty beam: candidates = start ids
        ids, dists, exp, n0 = dist_fn.hop_batch(
            queries, start_ids,
            jnp.full((b, l), INVALID, jnp.int32),
            jnp.full((b, l), INF),
            jnp.ones((b, l), bool))
    else:
        ids, dists, exp, n0 = jax.vmap(lane_init)(queries, start_ids, lane_idx)
    r = adjacency.shape[1]
    scored0 = (jnp.full((b, max_iters, r), INVALID, jnp.int32)
               if spec.record_scored
               else jnp.full((b, 1, 1), INVALID, jnp.int32))
    state = BeamState(
        ids=ids, dists=dists, expanded=exp,
        hops=jnp.zeros((b,), jnp.int32), ndists=n0,
        trace=jnp.full((b, max_iters), INVALID, jnp.int32),
        scored=scored0, it=jnp.int32(0))

    def lane_step(q, lane, ids, dists, exp, hops, ndists, trace_row,
                  scored_row, it):
        active = jnp.any((ids >= 0) & ~exp)
        sel = jnp.argmin(jnp.where(exp | (ids < 0), INF, dists))
        node = ids[sel]
        # a mask, not a scatter: on a TPU at 4096 lanes the vmapped
        # scatter lost writes, and lanes re-expanded one node until
        # max_iters
        exp2 = exp | (jnp.arange(l) == sel)
        nbrs = jnp.where(node < 0, INVALID, adjacency[jnp.maximum(node, 0)])
        nd = dist_fn(q, nbrs)
        nd = jnp.where(nbrs < 0, INF, nd)
        if neighbor_mask_fn is not None:
            nd = jnp.where(neighbor_mask_fn(lane, nbrs), nd, INF)
        nids, ndsts, nexp, nfresh = _merge(ids, dists, exp2, nbrs, nd)
        ids = jnp.where(active, nids, ids)
        dists = jnp.where(active, ndsts, dists)
        exp = jnp.where(active, nexp, exp)
        hops = hops + active.astype(jnp.int32)
        ndists = ndists + jnp.where(active, nfresh, 0)
        trace_row = trace_row.at[it].set(jnp.where(active, node, INVALID))
        if spec.record_scored:
            scored_row = scored_row.at[it].set(
                jnp.where(active, nbrs, INVALID))
        return ids, dists, exp, hops, ndists, trace_row, scored_row

    def cond(s: BeamState):
        any_active = jnp.any((s.ids >= 0) & ~s.expanded)
        return any_active & (s.it < max_iters)

    def body(s: BeamState):
        ids, dists, exp, hops, ndists, trace, scored = jax.vmap(
            lane_step, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, None))(
            queries, lane_idx, s.ids, s.dists, s.expanded, s.hops, s.ndists,
            s.trace, s.scored, s.it)
        return BeamState(ids, dists, exp, hops, ndists, trace, scored,
                         s.it + 1)

    def fused_body(s: BeamState):
        # Same semantics as `body`, but the gather/distance/merge of all
        # B lanes is one kernel dispatch.  Converged lanes feed all-(-1)
        # neighbor rows (the kernel skips their DMAs) and their outputs
        # are discarded below, exactly like the composed path.
        active = jnp.any((s.ids >= 0) & ~s.expanded, axis=1)        # (B,)
        sel = jnp.argmin(
            jnp.where(s.expanded | (s.ids < 0), INF, s.dists), axis=1)
        node = jnp.take_along_axis(s.ids, sel[:, None], axis=1)[:, 0]
        exp2 = s.expanded | (jnp.arange(l)[None, :] == sel[:, None])
        nbrs = jnp.where(((node < 0) | ~active)[:, None], INVALID,
                         adjacency[jnp.maximum(node, 0)])         # (B, R)
        nids, ndsts, nexp, nfresh = dist_fn.hop_batch(
            queries, nbrs, s.ids, s.dists, exp2)
        act = active[:, None]
        ids = jnp.where(act, nids, s.ids)
        dists = jnp.where(act, ndsts, s.dists)
        exp = jnp.where(act, nexp, s.expanded)
        hops = s.hops + active.astype(jnp.int32)
        ndists = s.ndists + jnp.where(active, nfresh, 0)
        trace = s.trace.at[:, s.it].set(jnp.where(active, node, INVALID))
        scored = s.scored
        if spec.record_scored:
            scored = scored.at[:, s.it].set(jnp.where(act, nbrs, INVALID))
        return BeamState(ids, dists, exp, hops, ndists, trace, scored,
                         s.it + 1)

    final = jax.lax.while_loop(cond, fused_body if use_fused else body, state)

    res_dists = final.dists
    if result_mask_fn is not None:
        keep = jax.vmap(result_mask_fn)(final.ids)
        res_dists = jnp.where(keep & (final.ids >= 0), res_dists, INF)
    # Beam is sorted ascending by construction; re-sort because result
    # masking may have disturbed the order.
    order = jnp.argsort(res_dists, axis=1)[:, : spec.k]
    top_ids = jnp.take_along_axis(final.ids, order, axis=1)
    top_d = jnp.take_along_axis(res_dists, order, axis=1)
    top_ids = jnp.where(jnp.isfinite(top_d), top_ids, INVALID)
    converged = jnp.all(final.expanded | (final.ids < 0), axis=1)
    return SearchResult(ids=top_ids, dists=top_d, hops=final.hops,
                        ndists=final.ndists, trace=final.trace,
                        scored=final.scored, converged=converged)


@partial(jax.jit, static_argnames=("spec",))
def beam_search_l2(adjacency: jax.Array, vectors: jax.Array, queries: jax.Array,
                   start_ids: jax.Array, spec: SearchSpec) -> SearchResult:
    """Convenience jit entry point: full-precision L2 search, no filters."""
    if spec.hop_backend == "fused":
        from repro.kernels.fused_hop import FusedL2Hop  # lazy: core↛kernels
        return beam_search(adjacency, queries, start_ids, spec,
                           FusedL2Hop(vectors))
    return beam_search(adjacency, queries, start_ids, spec, l2_dist_fn(vectors))
