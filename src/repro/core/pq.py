"""Product quantization — DiskANN's in-memory compressed vectors (§4.1.2).

DiskANN keeps PQ-compressed vectors in DRAM for traversal-time distance
estimates and fetches full-precision vectors from SSD only for final
rerank.  The TPU mapping (DESIGN.md §3): PQ codes live in HBM (bf16/int8
budget), the per-query lookup table (LUT) fits VMEM, and asymmetric
distance computation (ADC) is a gather-sum executed by the
``kernels.pq_adc`` Pallas kernel — this module is its jnp oracle and the
codebook trainer.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.distance import ordered_sum


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PQCodebook:
    centroids: jax.Array   # (M, K, ds) — M subspaces, K centroids, ds = d/M

    @property
    def n_subspaces(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_centroids(self) -> int:
        return self.centroids.shape[1]


def train_pq(key: jax.Array, vectors: jax.Array, n_subspaces: int,
             n_centroids: int = 256, iters: int = 8) -> PQCodebook:
    """Per-subspace k-means (Lloyd's, k-means++-free random init)."""
    n, d = vectors.shape
    assert d % n_subspaces == 0, (d, n_subspaces)
    ds = d // n_subspaces
    sub = vectors.reshape(n, n_subspaces, ds).transpose(1, 0, 2)  # (M, N, ds)
    init = jax.random.choice(key, n, (n_subspaces, n_centroids), replace=True)
    cents = jnp.take_along_axis(sub, init[:, :, None], axis=1)    # (M, K, ds)

    def step(cents, _):
        d2 = jnp.sum((sub[:, :, None, :] - cents[:, None, :, :]) ** 2, axis=-1)
        assign = jnp.argmin(d2, axis=-1)                          # (M, N)
        onehot = jax.nn.one_hot(assign, cents.shape[1], dtype=vectors.dtype)
        counts = onehot.sum(axis=1)                               # (M, K)
        sums = jnp.einsum('mnk,mnd->mkd', onehot, sub)
        new = jnp.where(counts[:, :, None] > 0,
                        sums / jnp.maximum(counts[:, :, None], 1), cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return PQCodebook(centroids=cents)


@jax.jit
def encode(cb: PQCodebook, vectors: jax.Array) -> jax.Array:
    """(N, d) -> (N, M) uint8/int32 codes."""
    n, d = vectors.shape
    m, k, ds = cb.centroids.shape
    sub = vectors.reshape(n, m, ds)
    d2 = jnp.sum((sub[:, :, None, :] - cb.centroids[None]) ** 2, axis=-1)
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)              # (N, M)


def query_lut(cb: PQCodebook, q: jax.Array) -> jax.Array:
    """Per-query ADC lookup table: (M, K) of squared subspace distances."""
    m, k, ds = cb.centroids.shape
    qs = q.reshape(m, ds)
    d2 = jnp.square(cb.centroids - qs[:, None, :])     # (M, K, ds)
    # coordinate by coordinate: one order inside and outside the search
    # loop (the unfused and fused hops build their LUTs in each)
    return ordered_sum(d2[..., i] for i in range(ds))


def adc_dist_fn(cb: PQCodebook, codes: jax.Array):
    """dist_fn(q, ids) for beam_search: PQ-approximate distances."""

    def dist(q: jax.Array, ids: jax.Array) -> jax.Array:
        lut = query_lut(cb, q)                          # (M, K)
        with jax.named_scope("hop/gather"):
            c = codes[jnp.maximum(ids, 0)]              # (m_ids, M)
        g = jnp.take_along_axis(lut[None], c[:, :, None], axis=2)[:, :, 0]
        # subspace by subspace, as the fused PQ hop kernel sums
        d = ordered_sum(g[:, j] for j in range(g.shape[1]))
        return jnp.where(ids < 0, jnp.inf, d)

    return dist


def rerank(vectors: jax.Array, q: jax.Array, ids: jax.Array, k: int):
    """Full-precision rerank of the final beam (DiskANN's SSD fetch)."""
    with jax.named_scope("rerank"):
        x = vectors[jnp.maximum(ids, 0)]
        d = jnp.sum((x - q[None]) ** 2, axis=-1)
        d = jnp.where(ids < 0, jnp.inf, d)
        order = jnp.argsort(d)[:k]
        return ids[order], d[order]
