"""Row gather + distance — DiskANN's SSD read, TPU-native.

DiskANN's inner loop reads a node's neighbor vectors from SSD and
overlaps the read with distance computation on the previous node.  The
TPU analogue keeps the vector table in HBM and gathers with in-kernel
async copies: each grid step takes a block of ids as an SMEM block, puts
one row copy per id in flight (HBM -> VMEM, the same gather
``fused_hop`` uses), and computes the block's squared distances against
the VMEM-resident query once the copies land.

Invalid ids (< 0, adjacency padding) fetch row 0 and are masked to +inf;
a block of only invalid ids issues no copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.distance import sq_l2
from repro.kernels.fused_hop import gather_rows, row_table

MAX_BLOCK = 128   # ids per grid step


def _gather_kernel(ids_smem, ids_ref, q_ref, rows_ref, o_ref, xs_ref, sem,
                   *, block):
    gather_rows(ids_smem, rows_ref, xs_ref, sem, tile=1, c=block,
                first=pl.program_id(0))
    # row and query zero-padded to W' lanes: the same sum as sq_l2 of
    # the unpadded ones
    d = sq_l2(xs_ref[...][0, :, 0, :], q_ref[...])[:, None]   # (block, 1)
    o_ref[...] = jnp.where(ids_ref[...] < 0, jnp.inf, d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_distance(vectors: jax.Array, ids: jax.Array, query: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """(N, d) table, (M,) int32 ids, (d,) query -> (M,) squared distances."""
    d = vectors.shape[1]
    m = ids.shape[0]
    block = min(MAX_BLOCK, -(-m // 8) * 8)
    mp = -(-m // block) * block
    ids_p = jnp.pad(ids, (0, mp - m), constant_values=-1)
    rows = row_table(vectors)
    query = jnp.pad(query, (0, rows.shape[-1] - d))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block=block),
        grid=(mp // block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),       # DMA addresses
            pl.BlockSpec((block, 1), lambda i: (i, 0)),  # ids, for the mask
            pl.BlockSpec((1, query.shape[0]), lambda i: (0, 0)),  # query
            pl.BlockSpec(memory_space=pl.ANY),           # row table
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block) + rows.shape[1:], rows.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(ids_p.reshape(mp // block, block), ids_p[:, None], query[None, :],
      rows)
    return out[:m, 0]
