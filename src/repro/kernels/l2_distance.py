"""Blocked squared-L2 distance kernel — the AVX hot loop, moved to the MXU.

The paper's distance computations dominate query cost (Fig. 6c);
its CPU artifact uses AVX SIMD.  On TPU the same computation is a
matmul-shaped kernel:

    ||q - x||^2 = ||q||^2 + ||x||^2 - 2 q.x

so the (B, C) distance tile is one MXU ``dot_general`` plus two rank-1
norm broadcasts.  Tiles are VMEM-resident: (bq, d) queries × (bc, d)
candidates -> (bq, bc) output, with the grid covering B/bq × C/bc.
Block sizes default to 128 (MXU-aligned); callers pad via ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _l2_kernel(q_ref, x_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)            # (bq, d)
    x = x_ref[...].astype(jnp.float32)            # (bc, d)
    qn = jnp.sum(q * q, axis=1, keepdims=True)    # (bq, 1)
    xn = jnp.sum(x * x, axis=1, keepdims=True).T  # (1, bc)
    # HIGHEST: f32 passes on the MXU; the default truncates the operands
    # to bf16, three digits short of an f32 distance
    cross = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)       # (bq, bc) on the MXU
    o_ref[...] = qn + xn - 2.0 * cross


@functools.partial(jax.jit, static_argnames=("block_q", "block_c", "interpret"))
def l2_distance(queries: jax.Array, points: jax.Array, *,
                block_q: int = 128, block_c: int = 128,
                interpret: bool = False) -> jax.Array:
    """(B, d) × (C, d) -> (B, C) squared L2.  B, C must divide the blocks."""
    b, d = queries.shape
    c, _ = points.shape
    assert b % block_q == 0 and c % block_c == 0, (b, c, block_q, block_c)
    grid = (b // block_q, c // block_c)
    return pl.pallas_call(
        _l2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        interpret=interpret,
    )(queries, points)
