"""The benchmark's corpus: rows at an intrinsic width, lifted.

A configuration's ``corpus.generator`` names a module
``generators/<name>.py`` whose ``make(params)`` returns a ``Corpus``.
The lift is a copy of ``repro.data.workloads.lift``, kept here so that
the yardstick does not move when the program's own generator does.  Two
things differ from the original, and both only make the output
reproducible bit for bit on any x86 host, so that a stored graph can be
checked against a fresh corpus by its hash:

* the lift's orthonormal basis comes from modified Gram-Schmidt written
  with elementwise operations and 1-d sums, not from LAPACK's QR;
* the lift itself is a fixed-order sum of elementwise products, not a
  BLAS matmul, whose summation order depends on the CPU it runs on.
"""
from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

# Per-dimension scale of the noise the lift adds (repro.data.workloads).
LIFT_NOISE = 0.02


@dataclasses.dataclass(frozen=True)
class Corpus:
    vectors: np.ndarray        # (rows, dim) float32, lifted
    basis: np.ndarray          # (intrinsic, dim) float32, orthonormal rows
    centers: np.ndarray        # (topics, intrinsic) float32
    by_rank: np.ndarray        # (topics,) popularity rank -> topic
    extra: dict = dataclasses.field(default_factory=dict)  # generator's own


def orthonormal_rows(rng, d: int, dim: int) -> np.ndarray:
    """``d`` orthonormal rows in ``dim`` dimensions, by modified
    Gram-Schmidt in float64 on Gaussian rows."""
    rows = rng.normal(size=(d, dim))
    out = []
    for v in rows:
        v = v.copy()
        for u in out:
            v -= np.sum(v * u) * u
        out.append(v / np.sqrt(np.sum(v * v)))
    return np.stack(out).astype(np.float32)


def lift_rows(x: np.ndarray, basis: np.ndarray) -> np.ndarray:
    """``x @ basis`` as a left-to-right sum of float32 products."""
    y = np.zeros((x.shape[0], basis.shape[1]), np.float32)
    for j in range(basis.shape[0]):
        y += x[:, j:j + 1] * basis[j][None, :]
    return y


def make_corpus(params: dict) -> Corpus:
    """The configuration's corpus, from its ``corpus`` parameters, by the
    generator they name (``generators/<generator>.py``)."""
    from onchip_harness import registry
    return registry.load_module("generators", params["generator"]).make(
        params)


def digest(vectors: np.ndarray) -> str:
    """sha256 of the corpus rows as little-endian float32."""
    arr = np.ascontiguousarray(vectors, "<f4")
    return hashlib.sha256(arr.tobytes()).hexdigest()
