"""CATAPULTED_LOOKUP — Algorithm 2 of the paper, batched and functional.

The catapult layer wraps any index exposing a starting-point hook
(Algorithm 1 here).  Per query batch:

  1. hash queries with random-hyperplane LSH -> bucket indices,
  2. gather each bucket's catapult destinations; append the graph medoid
     (fallback guaranteeing the unmodified-DiskANN baseline, §3.2
     "Competitive recall"),
  3. filtered queries drop destinations that fail the predicate (§3.4) —
     the search then falls back to the per-label entry point,
  4. run the *unchanged* beam search with that starting set,
  5. publish each query's best neighbor back to its bucket (LRU evict),
     tagged with the active filter.

Usage statistics mirror the paper's Fig. 6(d): a query "uses" catapults
when its bucket supplied at least one valid destination; we additionally
track "won" = the best starting point was a catapult rather than the
medoid, a stricter measure.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import buckets as bk
from repro.core import lsh as lsh_mod
from repro.core.beam_search import SearchResult, SearchSpec, beam_search

INVALID = jnp.int32(-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CatapultState:
    lsh: lsh_mod.LSHParams
    buckets: bk.BucketState


def make_catapult_state(key: jax.Array, dim: int, n_bits: int = 8,
                        capacity: int = 40) -> CatapultState:
    """Defaults b=40, L=8 — the paper's tuned optimum (§4.5)."""
    return CatapultState(
        lsh=lsh_mod.make_lsh(key, n_bits, dim),
        buckets=bk.make_buckets(2 ** n_bits, capacity))


class CatapultStats(NamedTuple):
    used: jax.Array   # (B,) bool — bucket supplied >=1 valid destination
    won: jax.Array    # (B,) bool — best start was a catapult, not the medoid
    hops: jax.Array
    ndists: jax.Array


def catapulted_lookup(
    state: CatapultState,
    adjacency: jax.Array,
    queries: jax.Array,                 # (B, d)
    spec: SearchSpec,
    dist_fn,
    medoid: jax.Array,                  # () int32 — or per-label entry when filtered
    *,
    filter_labels: Optional[jax.Array] = None,   # (B,) int32, -1 = unfiltered
    node_labels: Optional[jax.Array] = None,     # (N,) int32
    label_entry: Optional[jax.Array] = None,     # (n_labels,) per-label entry points
    neighbor_mask_fn=None,
    result_mask_fn=None,
    publish_mask: Optional[jax.Array] = None,    # (B,) bool, False = don't publish
) -> tuple[CatapultState, SearchResult, CatapultStats]:
    """One batch of Algorithm 2.  Returns (new state, results, stats)."""
    b = queries.shape[0]
    with jax.named_scope("catapult/lsh"):
        hashes = lsh_mod.hash_codes(state.lsh, queries)      # (B,)
    cat_ids, cat_tags = bk.lookup(state.buckets, hashes)     # (B, cap)

    if filter_labels is None:
        filter_labels = jnp.full((b,), INVALID, jnp.int32)
    flt = filter_labels

    # Validity of a catapult destination (paper §3.4): the landing node must
    # satisfy the active predicate.  Unfiltered queries accept everything.
    valid = cat_ids >= 0
    if node_labels is not None:
        dest_label = jnp.where(cat_ids >= 0, node_labels[jnp.maximum(cat_ids, 0)],
                               INVALID)
        valid &= (flt[:, None] < 0) | (dest_label == flt[:, None])
    cat_sp = jnp.where(valid, cat_ids, INVALID)

    # Fallback entry: the global medoid, or the per-label entry point
    # (FilteredVamana) for filtered lanes.
    if label_entry is not None:
        fallback = jnp.where(flt >= 0, label_entry[jnp.maximum(flt, 0)],
                             medoid)
    else:
        fallback = jnp.broadcast_to(medoid, (b,))
    starts = jnp.concatenate([cat_sp, fallback[:, None].astype(jnp.int32)], axis=1)

    result = beam_search(adjacency, queries, starts, spec, dist_fn,
                         neighbor_mask_fn=neighbor_mask_fn,
                         result_mask_fn=result_mask_fn)

    with jax.named_scope("catapult/lookup"):
        used = jnp.any(cat_sp >= 0, axis=1)
        # "won": some catapult start is strictly closer to q than the
        # fallback.
        d_start = jax.vmap(dist_fn)(queries, cat_sp)
        d_fb = jax.vmap(lambda q, m: dist_fn(q, m[None]))(
            queries, fallback)[:, 0]
        won = used & (jnp.min(jnp.where(cat_sp >= 0, d_start, jnp.inf),
                              axis=1) < d_fb)

    # Masked lanes (batch padding, frozen replicas) neither publish nor
    # report usage: a padded lane repeats a real query, so letting it
    # through would double-publish the destination (skewing the bucket
    # LRU toward batch-boundary queries) and double-count in any
    # telemetry derived from used/won.
    best = result.ids[:, 0]
    if publish_mask is not None:
        pm = jnp.asarray(publish_mask, bool)
        best = jnp.where(pm, best, INVALID)
        used &= pm
        won &= pm
    new_buckets = bk.publish(state.buckets, hashes, best, flt)
    new_state = CatapultState(lsh=state.lsh, buckets=new_buckets)
    stats = CatapultStats(used=used, won=won, hops=result.hops,
                          ndists=result.ndists)
    return new_state, result, stats
