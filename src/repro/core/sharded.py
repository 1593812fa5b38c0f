"""Distributed CatapultDB: scatter-gather shard search on the production mesh.

How sharded vector databases actually scale (Milvus/Weaviate segments,
DiskANN replica groups), expressed with shard_map + lax collectives:

  * the corpus is row-sharded over the `model` axis — each shard holds an
    independent Vamana subgraph over its rows (block-diagonal adjacency,
    local ids) with its own medoid and its own catapult buckets,
  * the query stream is sharded over `data` (× `pod`),
  * every device runs the *unchanged* batched beam search (Algorithm 1)
    on (its query shard × its corpus shard) — catapult layer included
    (Algorithm 2 state is per-device, exactly the paper's
    one-instance-per-replica deployment),
  * results merge with an all_gather over `model` + local top-k: the
    scatter-gather pattern.  Local ids are rebased to global with the
    shard offset.

The per-device search is embarrassingly parallel; the single collective
is the (Q_local × shards × k) result gather — bytes counted in §Roofline.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import buckets as bk
from repro.core import catapult as cat
from repro.core import lsh as lsh_mod
from repro.core.beam_search import SearchSpec, beam_search, l2_dist_fn


# ---------------------------------------------------------------------------
# Scatter-gather primitives — shared by the shard_map RAM path below and
# the disk-backed scatter-gather engine (repro.store.sharded_store), so
# both tiers merge shard results with the exact same semantics.
# ---------------------------------------------------------------------------

def rebase_ids(local_ids, offset):
    """Shard-local row ids -> global row ids; invalid lanes stay -1."""
    return jnp.where(local_ids >= 0, local_ids + offset, -1)


def merge_topk(all_ids, all_dists, k):
    """Merge per-shard candidate lists: (S, Q, k') -> global top-k (Q, k).

    Stable in distance order; -1 ids carry +inf distances by convention
    (per-shard searches mask invalid lanes that way), so they sink.
    """
    s, q, kk = all_ids.shape
    flat_ids = jnp.transpose(all_ids, (1, 0, 2)).reshape(q, s * kk)
    flat_d = jnp.transpose(all_dists, (1, 0, 2)).reshape(q, s * kk)
    top = jnp.argsort(flat_d, axis=1)[:, :k]
    return (jnp.take_along_axis(flat_ids, top, axis=1),
            jnp.take_along_axis(flat_d, top, axis=1))


class ShardedEngineState(NamedTuple):
    """Corpus arrays shard over `model`; catapult buckets are per-DEVICE
    (each data-parallel replica keeps its own, the paper's one-instance-
    per-replica deployment), so they shard over ALL mesh axes."""
    vectors: jax.Array      # (S*N, d)       P("model", None)
    adjacency: jax.Array    # (S*N, R)       P("model", None)   local ids
    medoids: jax.Array      # (S,)           P("model")
    hyperplanes: jax.Array  # (L, d)         replicated
    bucket_ids: jax.Array   # (DEV*2^L, b)   P(all_axes, None)
    bucket_stamp: jax.Array # (DEV*2^L, b)   P(all_axes, None)
    bucket_step: jax.Array  # (DEV,)         P(all_axes)


def engine_state_specs(mesh, n_per_shard: int, dim: int,
                       max_degree: int, lsh_bits: int, bucket_cap: int):
    """ShapeDtypeStructs + pspecs for the dry-run (no allocation)."""
    f32, i32 = jnp.float32, jnp.int32
    n_shards = mesh.shape["model"]
    n_dev = mesh.size
    all_axes = tuple(mesh.axis_names)
    sds = ShardedEngineState(
        vectors=jax.ShapeDtypeStruct((n_shards * n_per_shard, dim), f32),
        adjacency=jax.ShapeDtypeStruct((n_shards * n_per_shard, max_degree),
                                       i32),
        medoids=jax.ShapeDtypeStruct((n_shards,), i32),
        hyperplanes=jax.ShapeDtypeStruct((lsh_bits, dim), f32),
        bucket_ids=jax.ShapeDtypeStruct((n_dev * 2 ** lsh_bits,
                                         bucket_cap), i32),
        bucket_stamp=jax.ShapeDtypeStruct((n_dev * 2 ** lsh_bits,
                                           bucket_cap), i32),
        bucket_step=jax.ShapeDtypeStruct((n_dev,), i32),
    )
    specs = ShardedEngineState(
        vectors=P("model", None), adjacency=P("model", None),
        medoids=P("model"), hyperplanes=P(),
        bucket_ids=P(all_axes, None), bucket_stamp=P(all_axes, None),
        bucket_step=P(all_axes),
    )
    return sds, specs


def make_sharded_search(mesh, spec: SearchSpec, n_per_shard: int,
                        lsh_bits: int):
    """Builds the shard_map'd search step.

    step(state, queries (Q, d)) ->
        (new_state, ids (Q, k) global, dists (Q, k))
    queries sharded over the batch axes; state over `model`.
    """
    qaxes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    all_axes = tuple(mesh.axis_names)

    def local_step(vectors, adjacency, medoid, hyper, b_ids, b_stamp,
                   b_step, queries):
        # everything here is per-device: queries (Ql, d), corpus (N, d)
        medoid = medoid[0]
        lsh = lsh_mod.LSHParams(hyperplanes=hyper)
        buckets = bk.BucketState(ids=b_ids, stamp=b_stamp,
                                 tag=jnp.full_like(b_ids, -1),
                                 step=b_step[0])
        state = cat.CatapultState(lsh=lsh, buckets=buckets)
        new_state, result, stats = cat.catapulted_lookup(
            state, adjacency, queries, spec, l2_dist_fn(vectors), medoid)

        # rebase local ids -> global row ids using this shard's position
        shard = jax.lax.axis_index("model")
        gids = rebase_ids(result.ids, shard * n_per_shard)

        # scatter-gather merge over the corpus shards
        all_ids = jax.lax.all_gather(gids, "model")          # (S, Ql, k)
        all_d = jax.lax.all_gather(result.dists, "model")    # (S, Ql, k)
        merged_ids, merged_d = merge_topk(all_ids, all_d,
                                          k=all_ids.shape[-1])

        nb = new_state.buckets
        return (nb.ids, nb.stamp, nb.step[None], merged_ids, merged_d)

    in_specs = (P("model", None), P("model", None), P("model"), P(),
                P(all_axes, None), P(all_axes, None), P(all_axes),
                P(qaxes, None))
    out_specs = (P(all_axes, None), P(all_axes, None), P(all_axes),
                 P(qaxes, None), P(qaxes, None))

    smapped = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)

    def step(state: ShardedEngineState, queries):
        b_ids, b_stamp, b_step, ids, dists = smapped(
            state.vectors, state.adjacency, state.medoids,
            state.hyperplanes, state.bucket_ids, state.bucket_stamp,
            state.bucket_step, queries)
        new_state = state._replace(bucket_ids=b_ids, bucket_stamp=b_stamp,
                                   bucket_step=b_step)
        return new_state, ids, dists

    return step


def build_sharded_state(workload_vectors, n_shards, *, n_devices=None,
                        max_degree=16, lsh_bits=8, bucket_cap=40,
                        build_beam=32, seed=0):
    """Host-side build of a real (small) sharded engine — used by the
    integration test on a CPU mesh; the dry-run uses specs only."""
    import numpy as np
    from repro.core.vamana import VamanaParams, build_vamana

    n_devices = n_devices or n_shards
    n_total, dim = workload_vectors.shape
    assert n_total % n_shards == 0
    n = n_total // n_shards
    adj = np.zeros((n_total, max_degree), np.int32)
    medoids = np.zeros(n_shards, np.int32)
    for s in range(n_shards):
        block = workload_vectors[s * n: (s + 1) * n]
        a, m = build_vamana(block, VamanaParams(max_degree=max_degree,
                                                build_beam=build_beam,
                                                seed=seed + s))
        adj[s * n: (s + 1) * n] = a
        medoids[s] = m
    lsh = lsh_mod.make_lsh(jax.random.PRNGKey(seed), lsh_bits, dim)
    nb = 2 ** lsh_bits
    return ShardedEngineState(
        vectors=jnp.asarray(workload_vectors),
        adjacency=jnp.asarray(adj),
        medoids=jnp.asarray(medoids),
        hyperplanes=lsh.hyperplanes,
        bucket_ids=jnp.full((n_devices * nb, bucket_cap), -1, jnp.int32),
        bucket_stamp=jnp.full((n_devices * nb, bucket_cap), -1, jnp.int32),
        bucket_step=jnp.zeros((n_devices,), jnp.int32),
    )
