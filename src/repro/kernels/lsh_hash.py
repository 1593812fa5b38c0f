"""Random-hyperplane LSH hashing kernel (paper §2.2, §3.2).

Every query is hashed on the way in (Algorithm 2 line 2), so hashing sits
on the latency path of every lookup.  One MXU matmul projects a (bq, d)
query tile onto all L hyperplanes at once; the sign bits are packed into
a bucket index with a power-of-two weighted reduction — no per-bit loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lsh_kernel(q_ref, h_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)            # (bq, d)
    h = h_ref[...].astype(jnp.float32)            # (L, d)
    proj = jax.lax.dot_general(
        q, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (bq, L)
    bits = (proj >= 0.0).astype(jnp.int32)
    weights = 2 ** jax.lax.broadcasted_iota(jnp.int32, proj.shape, 1)
    o_ref[...] = jnp.sum(bits * weights, axis=1, keepdims=True)  # (bq, 1)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def lsh_hash(queries: jax.Array, hyperplanes: jax.Array, *,
             block_q: int = 128, interpret: bool = False) -> jax.Array:
    """(B, d) × (L, d) -> (B,) int32 bucket codes.  B must divide block_q."""
    b, d = queries.shape
    l, _ = hyperplanes.shape
    assert b % block_q == 0, (b, block_q)
    return pl.pallas_call(
        _lsh_kernel,
        grid=(b // block_q,),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
            pl.BlockSpec((l, d), lambda i: (0, 0)),
        ],
        # codes leave as a (B, 1) column: the layout the chip tiles a
        # per-row result in (a 1-D output is tiled by 1024, not by block)
        out_specs=pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        interpret=interpret,
    )(queries, hyperplanes)[:, 0]
