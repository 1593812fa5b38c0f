"""PQ asymmetric-distance (ADC) kernel — DiskANN's in-memory distances.

DiskANN estimates traversal distances from PQ codes + a per-query lookup
table.  A scalar gather per (candidate, subspace) is the CPU idiom; on
TPU scattered VMEM reads serialize badly, so the kernel re-expresses the
LUT gather as a one-hot contraction on the MXU:

    dist[c] = sum_m LUT[m, code[c, m]]
            = sum_{m,k} onehot(code)[c, m, k] * LUT[m, k]

The (bc, M*K) one-hot tile and the flattened (M*K, 1) LUT turn into a
single ``dot`` — gathers become a matmul, the canonical TPU adaptation
(DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _adc_kernel(lut_ref, codes_ref, o_ref, *, n_centroids: int):
    lut = lut_ref[...].astype(jnp.float32)        # (M*K, 1) flattened LUT
    codes = codes_ref[...]                        # (bc, M) int32
    m, k = codes.shape[1], n_centroids
    # one-hot over (subspace, centroid), built directly in its flattened
    # (bc, M*K) layout: column p holds subspace p // K, centroid p % K
    col = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], m * k), 1)
    onehot = jnp.zeros(col.shape, bool)
    for j in range(m):
        onehot = onehot | ((col // k == j)
                           & (codes[:, j:j + 1] == col % k))
    # HIGHEST: the LUT entries keep f32 precision on the MXU
    o_ref[...] = jax.lax.dot_general(
        onehot.astype(jnp.float32), lut,
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def pq_adc(lut: jax.Array, codes: jax.Array, *, block_c: int = 128,
           interpret: bool = False) -> jax.Array:
    """(M, K) LUT × (C, M) codes -> (C,) distances.  C must divide block_c."""
    m, k = lut.shape
    c, _ = codes.shape
    assert c % block_c == 0, (c, block_c)
    return pl.pallas_call(
        functools.partial(_adc_kernel, n_centroids=k),
        grid=(c // block_c,),
        in_specs=[
            pl.BlockSpec((m * k, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_c, m), lambda i: (i, 0)),
        ],
        # distances leave as a (C, 1) column: the layout the chip tiles
        # a per-row result in (a 1-D output is tiled by 1024, not by block)
        out_specs=pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 1), jnp.float32),
        interpret=interpret,
    )(lut.reshape(m * k, 1), codes)[:, 0]
