"""Distance sums in one fixed order, shared by XLA code and kernels.

A search computes distances in two places: XLA code (the unfused hop,
catapult entry scoring) and Pallas kernels (the fused hop,
``gather_distance``).  An f32 sum rounds according to its order, and on
a TPU the two compilers reduce an axis in different orders, so one
distance could come out as two floats a few ulps apart, and a beam
merge decided by that last bit would send the two backends down
different paths.  Both sides therefore sum with the functions here.

``lane_sum`` (squared L2 over a vector's width):

* wider than one 128-lane tile: zero-pad to whole tiles, add the tiles
  one after another, left to right, then reduce the one 128-lane row
  that is left.  The tile adds are element-wise; the last reduction is
  left to the compilers, and on a TPU v5e XLA and the kernel compiler
  reduce a full 128-lane row to the same float.  Left to themselves,
  they add the tiles of a 768-wide row in different orders, and about
  6 in 10 distances differ in their last bits.
* one tile or less: zero-pad to a power of two and add the upper half
  to the lower half until one value is left.  A reduction is not left
  to the compilers here, because XLA drops zero padding before a
  reduction and then sums the narrower row in its own order.

Zero padding adds exact zeros, so a kernel may sum its rows padded to
whole tiles (the row table's width) and XLA the unpadded ones: both get
the same float.  ``chip_smoke.py`` and ``tests/test_kernels.py``, run on
the chip, hold the fused search to the unfused one bit for bit.

``ordered_sum`` adds a short list of arrays left to right (a PQ
distance's per-subspace terms).
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

LANES = 128


def lane_sum(s: jax.Array) -> jax.Array:
    """Sum of ``s`` (non-negative terms) over its last axis, in the
    order above."""
    n = s.shape[-1]
    width = (-(-n // LANES) * LANES if n > LANES
             else 1 << max(n - 1, 0).bit_length())
    if width != n:
        s = jnp.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, width - n)])
    if width <= LANES:
        while s.shape[-1] > 1:
            half = s.shape[-1] // 2
            s = s[..., :half] + s[..., half:]
        return s[..., 0]
    acc = s[..., :LANES]
    for i in range(1, width // LANES):
        acc = acc + s[..., i * LANES:(i + 1) * LANES]
    return jnp.sum(acc, axis=-1)


def sq_l2(x: jax.Array, q: jax.Array) -> jax.Array:
    """Squared L2 distance over the last axis (operands broadcast)."""
    return lane_sum(jnp.square(x.astype(jnp.float32) - q.astype(jnp.float32)))


def ordered_sum(terms):
    """``terms[0] + terms[1] + ...``, left to right."""
    return functools.reduce(operator.add, terms)
