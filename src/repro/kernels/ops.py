"""Public jit'd entry points for the Pallas kernels.

Each op pads ragged inputs to kernel block multiples, dispatches to the
Pallas kernel (compiled on TPU, ``interpret=True`` elsewhere so CPU CI
executes the same kernel bodies), and slices the result.  The pure-jnp
oracles live in ``ref.py``; tests assert op == oracle across shape/dtype
sweeps.

Profiling: each public op wraps its jit'd dispatch in
``repro.obs.annotate`` — with ``REPRO_PROFILE=1`` (or
``repro.obs.enable_profiling()``) a ``jax.profiler`` capture shows
named host spans per kernel instead of anonymous dispatches.  The
annotation sits OUTSIDE the jit boundary (a host context manager can't
live inside a traced function) and is one shared no-op when profiling
is off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fused_hop import fused_hop_l2 as _fused_hop_l2
from repro.kernels.fused_hop import fused_hop_pq as _fused_hop_pq
from repro.kernels.gather_distance import gather_distance as _gather_distance
from repro.kernels.l2_distance import l2_distance as _l2_distance
from repro.kernels.lsh_hash import lsh_hash as _lsh_hash
from repro.kernels.pq_adc import pq_adc as _pq_adc
from repro.kernels.platform import interpret_mode
from repro.obs.profiler import annotate


def _pad_rows(x: jax.Array, mult: int, value=0) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=value)


@functools.partial(jax.jit, static_argnames=("block_q", "block_c"))
def _l2_distance_jit(queries: jax.Array, points: jax.Array, *,
                     block_q: int = 128, block_c: int = 128) -> jax.Array:
    b, c = queries.shape[0], points.shape[0]
    bq, bc = min(block_q, max(b, 8)), min(block_c, max(c, 8))
    qp = _pad_rows(queries, bq)
    pp = _pad_rows(points, bc)
    out = _l2_distance(qp, pp, block_q=bq, block_c=bc,
                       interpret=interpret_mode())
    return out[:b, :c]


def l2_distance(queries: jax.Array, points: jax.Array, *,
                block_q: int = 128, block_c: int = 128) -> jax.Array:
    """(B, d) × (C, d) -> (B, C) squared L2, any B/C (padded internally)."""
    with annotate("repro.kernels.l2_distance"):
        return _l2_distance_jit(queries, points, block_q=block_q,
                                block_c=block_c)


@jax.jit
def _gather_distance_jit(vectors: jax.Array, ids: jax.Array,
                         query: jax.Array) -> jax.Array:
    return _gather_distance(vectors, ids, query, interpret=interpret_mode())


def gather_distance(vectors: jax.Array, ids: jax.Array,
                    query: jax.Array) -> jax.Array:
    """(N, d), (M,) ids, (d,) -> (M,) distances; ids<0 -> +inf."""
    with annotate("repro.kernels.gather_distance"):
        return _gather_distance_jit(vectors, ids, query)


@functools.partial(jax.jit, static_argnames=("block_q",))
def _lsh_hash_jit(queries: jax.Array, hyperplanes: jax.Array, *,
                  block_q: int = 128) -> jax.Array:
    b = queries.shape[0]
    bq = min(block_q, max(b, 8))
    qp = _pad_rows(queries, bq)
    out = _lsh_hash(qp, hyperplanes, block_q=bq, interpret=interpret_mode())
    return out[:b]


def lsh_hash(queries: jax.Array, hyperplanes: jax.Array, *,
             block_q: int = 128) -> jax.Array:
    """(B, d) × (L, d) -> (B,) int32 bucket codes, any B."""
    with annotate("repro.kernels.lsh_hash"):
        return _lsh_hash_jit(queries, hyperplanes, block_q=block_q)


@functools.partial(jax.jit, static_argnames=("block_c",))
def _pq_adc_jit(lut: jax.Array, codes: jax.Array, *,
                block_c: int = 128) -> jax.Array:
    c = codes.shape[0]
    bc = min(block_c, max(c, 8))
    cp = _pad_rows(codes, bc)
    out = _pq_adc(lut, cp, block_c=bc, interpret=interpret_mode())
    return out[:c]


def pq_adc(lut: jax.Array, codes: jax.Array, *, block_c: int = 128) -> jax.Array:
    """(M, K) LUT × (C, M) codes -> (C,) ADC distances, any C."""
    with annotate("repro.kernels.pq_adc"):
        return _pq_adc_jit(lut, codes, block_c=block_c)


def fused_hop_l2(vectors, cand_ids, queries, beam_ids, beam_dists, beam_exp):
    """One fused L2 hop (gather + distance + beam merge) for a batch.

    (N, d) table, (B, C) candidate ids, (B, d) queries, (B, L) beam ->
    (new_ids, new_dists, new_exp, n_fresh), for any B/C/L (the kernel
    pads the batch to whole lane tiles itself).
    """
    with annotate("repro.kernels.fused_hop_l2"):
        return _fused_hop_l2(vectors, cand_ids, queries, beam_ids,
                             beam_dists, beam_exp, interpret=interpret_mode())


def fused_hop_pq(luts, codes, cand_ids, beam_ids, beam_dists, beam_exp):
    """One fused PQ-ADC hop: (B, M, K) LUTs, (N, M) codes, (B, C) ids,
    (B, L) beam -> (new_ids, new_dists, new_exp, n_fresh)."""
    with annotate("repro.kernels.fused_hop_pq"):
        return _fused_hop_pq(luts, codes, cand_ids, beam_ids,
                             beam_dists, beam_exp, interpret=interpret_mode())


# re-export oracles for convenience in tests/benchmarks
l2_distance_ref = ref.l2_distance_ref
gather_distance_ref = ref.gather_distance_ref
lsh_hash_ref = ref.lsh_hash_ref
pq_adc_ref = ref.pq_adc_ref
fused_hop_ref = ref.fused_hop_ref
fused_hop_pq_ref = ref.fused_hop_pq_ref
