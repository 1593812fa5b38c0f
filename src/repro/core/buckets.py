"""Catapult buckets — the paper's auxiliary shortcut-edge layer (§3.2).

State is a dense ``(2**L, b)`` table of destination node ids plus LRU
stamps and filter tags.  The paper guards each bucket with a
reader-writer lock; on TPU the same protocol becomes *batch-synchronous
functional update*:

* ``lookup``: one pure gather — the whole query batch reads the pre-batch
  bucket state (the paper's read-locked section),
* ``publish``: completed queries append their best neighbor one at a time
  inside a ``lax.fori_loop`` — a deterministic serialization of the
  paper's write-locked appends, preserving LRU semantics exactly even
  when many queries in a batch hash to the same hot bucket.

LRU detail: the paper evicts the least-recently-used entry.  We stamp
entries on insert and *refresh* the stamp when a published destination is
already present (the common case in a burst), evicting the minimum stamp
when full.  Memory cost matches the paper's accounting: b·2^L int32 ids
(40 KiB at b=40, L=8) plus equal-sized stamp/tag arrays.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INVALID = jnp.int32(-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BucketState:
    ids: jax.Array     # (n_buckets, b) int32 destination node ids, -1 empty
    stamp: jax.Array   # (n_buckets, b) int32 LRU stamps, -1 empty
    tag: jax.Array     # (n_buckets, b) int32 filter label of the query that
                       # published the entry, -1 = unfiltered
    step: jax.Array    # () int32 monotone insertion clock

    @property
    def capacity(self) -> int:
        return self.ids.shape[1]


def make_buckets(n_buckets: int, capacity: int) -> BucketState:
    shape = (n_buckets, capacity)
    return BucketState(
        ids=jnp.full(shape, INVALID, jnp.int32),
        stamp=jnp.full(shape, INVALID, jnp.int32),
        tag=jnp.full(shape, INVALID, jnp.int32),
        step=jnp.int32(0))


def lookup(state: BucketState, bucket_idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Read catapult destinations for a batch of bucket indices.

    Returns (ids (B, b), tags (B, b)).  Pure gather — the read-locked
    critical section of the paper costs one HBM fetch here.
    """
    with jax.named_scope("catapult/lookup"):
        return state.ids[bucket_idx], state.tag[bucket_idx]


@jax.jit
def publish(state: BucketState, bucket_idx: jax.Array, dest: jax.Array,
            tags: jax.Array) -> BucketState:
    """Append each (bucket, destination) pair with LRU eviction.

    Args:
      bucket_idx: (B,) int32 bucket per completed query.
      dest: (B,) int32 best-neighbor node id per query (-1 skips the lane —
        e.g. a failed/filtered-out search publishes nothing).
      tags: (B,) int32 filter label of each query (-1 unfiltered).
    """

    def one(i, carry):
        ids, stamp, tag, step = carry
        h, d, t = bucket_idx[i], dest[i], tags[i]
        row_ids, row_stamp, row_tag = ids[h], stamp[h], tag[h]
        present = (row_ids == d) & (row_tag == t)
        hit = jnp.any(present) & (d >= 0)
        # refresh stamp on hit, else evict min-stamp slot (-1 empty wins)
        slot = jnp.where(hit, jnp.argmax(present), jnp.argmin(row_stamp))
        do = d >= 0
        row_ids = jnp.where(do, row_ids.at[slot].set(d), row_ids)
        row_stamp = jnp.where(do, row_stamp.at[slot].set(step), row_stamp)
        row_tag = jnp.where(do, row_tag.at[slot].set(t), row_tag)
        return (ids.at[h].set(row_ids), stamp.at[h].set(row_stamp),
                tag.at[h].set(row_tag), step + do.astype(jnp.int32))

    with jax.named_scope("catapult/publish"):
        ids, stamp, tag, step = jax.lax.fori_loop(
            0, bucket_idx.shape[0], one,
            (state.ids, state.stamp, state.tag, state.step))
    return BucketState(ids=ids, stamp=stamp, tag=tag, step=step)


def evict_where(state: BucketState, mask: jax.Array) -> BucketState:
    """Clear every occupied entry selected by ``mask`` ((n_buckets, b) bool).

    The one invalidation primitive every flush path shares: ids, stamps
    AND tags all reset to INVALID together — a cleared slot that kept
    its tag would let a later filtered lookup match a ghost label, and a
    kept stamp would make the empty slot lose LRU-eviction priority.
    """
    bad = mask & (state.ids >= 0)
    return BucketState(ids=jnp.where(bad, INVALID, state.ids),
                       stamp=jnp.where(bad, INVALID, state.stamp),
                       tag=jnp.where(bad, INVALID, state.tag),
                       step=state.step)


def evict_ids(state: BucketState, dead: jax.Array) -> BucketState:
    """Clear every bucket entry whose destination is in ``dead``.

    Tombstone deletion's invalidation hook: the LRU refresh would age
    stale shortcuts out *eventually*, but until then every query hashing
    to the bucket pays a beam start on a node that can never be a result
    — and on the disk tier that start is a wasted block read.  One dense
    ``isin`` sweep drops them immediately (the paper's passive-refresh
    story is about insertions; deletions get the active flush).
    """
    dead = jnp.asarray(dead, jnp.int32).ravel()
    return evict_where(state, jnp.isin(state.ids, dead))


def evict_buckets(state: BucketState, bucket_mask: jax.Array) -> BucketState:
    """Flush whole bucket rows (``bucket_mask``: (n_buckets,) bool).

    The adapt layer's drift-flush unit: when a query region shifts, the
    shortcuts published under the old regime steer beams into the stale
    hot set — clearing the region's rows costs a handful of cold starts
    and stops the misdirection immediately.
    """
    return evict_where(state, jnp.asarray(bucket_mask, bool)[:, None])


def to_arrays(state: BucketState) -> dict[str, np.ndarray]:
    """Field-name -> ndarray snapshot — THE sidecar schema every persist
    path shares (single-store ``.adapt.npz``, sharded ``.buckets.npz``),
    so the writers cannot drift apart."""
    return {f.name: np.asarray(getattr(state, f.name))
            for f in dataclasses.fields(BucketState)}


def from_arrays(arrays) -> BucketState:
    """Rebuild a state from ``to_arrays`` output (e.g. an open npz)."""
    return BucketState(**{f.name: jnp.asarray(arrays[f.name])
                          for f in dataclasses.fields(BucketState)})


def evict_stale(state: BucketState, max_age: jax.Array) -> BucketState:
    """TTL eviction: clear entries whose stamp is older than
    ``step - max_age`` on the bucket layer's publish clock.

    Ages in publish *events*, not wall time — a bucket that stopped
    receiving traffic stops refreshing its stamps while the global clock
    keeps advancing, so its entries expire exactly when the workload
    moved away."""
    cutoff = state.step - jnp.asarray(max_age, jnp.int32)
    return evict_where(state, (state.stamp >= 0) & (state.stamp < cutoff))
