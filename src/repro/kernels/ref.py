"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is tested against
(tests/test_kernels_*.py sweep shapes/dtypes and assert_allclose).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.distance import sq_l2


def l2_distance_ref(queries: jnp.ndarray, points: jnp.ndarray) -> jnp.ndarray:
    """(B, d), (C, d) -> (B, C) squared L2 distances."""
    return jnp.sum(
        jnp.square(queries[:, None, :].astype(jnp.float32)
                   - points[None, :, :].astype(jnp.float32)), axis=-1)


def gather_distance_ref(vectors: jnp.ndarray, ids: jnp.ndarray,
                        query: jnp.ndarray) -> jnp.ndarray:
    """(N, d), (M,), (d,) -> (M,) squared L2 distance to each gathered row.

    Invalid ids (< 0) produce +inf, matching beam-search conventions.
    """
    d = sq_l2(vectors[jnp.maximum(ids, 0)], query[None, :])
    return jnp.where(ids < 0, jnp.inf, d)


def lsh_hash_ref(queries: jnp.ndarray, hyperplanes: jnp.ndarray) -> jnp.ndarray:
    """(B, d), (L, d) -> (B,) int32 bucket codes (bit i = sign of proj i)."""
    bits = (queries.astype(jnp.float32) @ hyperplanes.T.astype(jnp.float32)
            >= 0).astype(jnp.int32)
    weights = 2 ** jnp.arange(hyperplanes.shape[0], dtype=jnp.int32)
    return jnp.sum(bits * weights, axis=-1).astype(jnp.int32)


def pq_adc_ref(lut: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """(M, K) LUT, (C, M) codes -> (C,) summed asymmetric distances."""
    g = jnp.take_along_axis(lut[None, :, :].astype(jnp.float32),
                            codes[:, :, None], axis=2)[:, :, 0]
    return g.sum(axis=-1)


def _merge_ref(cand_ids, cand_d, beam_ids, beam_d, beam_exp):
    """One lane's beam merge: dedup then stable top-L (self-contained
    mirror of ``core.beam_search._merge``'s semantics)."""
    l = beam_ids.shape[0]
    c = cand_ids.shape[0]
    in_beam = jnp.any((cand_ids[:, None] == beam_ids[None, :])
                      & (beam_ids[None, :] >= 0), axis=1)
    earlier = (cand_ids[:, None] == cand_ids[None, :]) & (
        jnp.arange(c)[None, :] < jnp.arange(c)[:, None])
    fresh = ~(in_beam | jnp.any(earlier, axis=1)) & (cand_ids >= 0)
    cand_d = jnp.where(fresh, cand_d, jnp.inf)
    ids = jnp.concatenate([beam_ids, cand_ids])
    dists = jnp.concatenate([beam_d, cand_d])
    exp = jnp.concatenate([beam_exp, jnp.zeros((c,), bool)])
    order = jnp.argsort(dists)[:l]
    ids, dists, exp = ids[order], dists[order], exp[order]
    invalid = ~jnp.isfinite(dists)
    ids = jnp.where(invalid, -1, ids)
    exp = exp | invalid
    return ids, dists, exp, jnp.sum(fresh).astype(jnp.int32)


def fused_hop_ref(vectors, cand_ids, queries, beam_ids, beam_dists, beam_exp):
    """Oracle for ``fused_hop_l2``: batched gather + L2 + beam merge.

    (N, d) table, (B, C) candidate ids, (B, d) queries, (B, L) beam
    state -> (new_ids, new_dists, new_exp, n_fresh), all batched.
    """
    import jax

    def lane(cids, q, bids, bd, bexp):
        d = gather_distance_ref(vectors, cids, q)
        return _merge_ref(cids, d, bids, bd, bexp)

    return jax.vmap(lane)(cand_ids, queries, beam_ids, beam_dists, beam_exp)


def fused_hop_pq_ref(luts, codes, cand_ids, beam_ids, beam_dists, beam_exp):
    """Oracle for ``fused_hop_pq``: batched code gather + ADC + merge.

    (B, M, K) per-query LUTs, (N, M) code table, (B, C) candidate ids,
    (B, L) beam state -> (new_ids, new_dists, new_exp, n_fresh).
    """
    import jax

    def lane(lut, cids, bids, bd, bexp):
        d = pq_adc_ref(lut, codes[jnp.maximum(cids, 0)])
        d = jnp.where(cids < 0, jnp.inf, d)
        return _merge_ref(cids, d, bids, bd, bexp)

    return jax.vmap(lane)(luts, cand_ids, beam_ids, beam_dists, beam_exp)
