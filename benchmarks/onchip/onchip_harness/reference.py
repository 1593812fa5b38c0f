"""The plain reference: exact k-NN by squared L2, on the device in blocks.

Independent of the program under test: it takes the corpus rows and the
queries as the benchmark made them and nothing the program built.  Run
only once the measured window has closed and the program's state is
freed, so that it neither slows the window nor sets the memory peak.

``exact_knn`` ranks every row for a block of queries with one matmul and
``lax.top_k``: float32 at HIGHEST precision, or with ``dtype=bfloat16``
the control, the same ranking one precision below (bfloat16 operands,
float32 sums: what a float32 matmul at the TPU's default precision does).
``row_dists`` gives the exact squared distance of given rows, as a sum
of squared differences, to check the distances an answer reports.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 2048


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_block(x, xn, q, *, k):
    q = q.astype(x.dtype)
    qn = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=1, keepdims=True)
    d = qn + xn[None, :] - 2.0 * jnp.dot(q, x.T, precision=HIGHEST,
                                         preferred_element_type=jnp.float32)
    neg, ids = jax.lax.top_k(-d, k)
    return ids.astype(jnp.int32), -neg


@jax.jit
def _row_dists_block(x, q, ids):
    diff = x[jnp.clip(ids, 0, x.shape[0] - 1)] - q[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def _blocks(n: int, block: int):
    for lo in range(0, n, block):
        yield lo, min(lo + block, n)


def _pad(a: np.ndarray, rows: int) -> np.ndarray:
    """Pad a block to ``rows`` rows so that every block shares one shape."""
    if a.shape[0] == rows:
        return a
    return np.concatenate([a, np.repeat(a[-1:], rows - a.shape[0], axis=0)])


def exact_knn(vectors, queries: np.ndarray, k: int, *, dtype=jnp.float32,
              block: int = BLOCK) -> tuple[np.ndarray, np.ndarray]:
    """(ids (Q, k) int32, squared distances (Q, k) float32), nearest
    first, of every query against every row of ``vectors``."""
    x = jnp.asarray(vectors, jnp.float32).astype(dtype)
    xn = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=1)
    ids = np.empty((queries.shape[0], k), np.int32)
    dists = np.empty((queries.shape[0], k), np.float32)
    for lo, hi in _blocks(queries.shape[0], block):
        bi, bd = _knn_block(x, xn, jnp.asarray(_pad(queries[lo:hi], block)),
                            k=k)
        ids[lo:hi] = np.asarray(bi)[: hi - lo]
        dists[lo:hi] = np.asarray(bd)[: hi - lo]
    return ids, dists


def row_dists(vectors, queries: np.ndarray, ids: np.ndarray,
              block: int = BLOCK) -> np.ndarray:
    """Exact squared distance from each query to each of its ``ids``
    (ids out of range read row 0 or the last row; callers check them)."""
    x = jnp.asarray(vectors, jnp.float32)
    out = np.empty(ids.shape, np.float32)
    for lo, hi in _blocks(queries.shape[0], block):
        d = _row_dists_block(x, jnp.asarray(_pad(queries[lo:hi], block)),
                             jnp.asarray(_pad(ids[lo:hi], block)))
        out[lo:hi] = np.asarray(d)[: hi - lo]
    return out
