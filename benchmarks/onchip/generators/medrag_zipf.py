"""MedRAG-like corpus: topic clusters in a continuous background, at an
intrinsic width, lifted (a copy of ``repro.data.workloads``'s
``_clustered_corpus`` and the corpus half of ``make_medrag_zipf``).

Parameters (a configuration's ``corpus``): ``rows``, ``dim``,
``intrinsic_dim``, ``clusters``, ``seed`` and ``lift_seed``.  The
popularity ranking of the topics and the cluster centers are drawn from
the corpus seed, so every query seed sees the same popular topics.
"""
from __future__ import annotations

import numpy as np

from onchip_harness.corpus import (LIFT_NOISE, Corpus, lift_rows,
                                   orthonormal_rows)


def clustered(n, d, n_clusters, rng, spread=1.0, sep=1.5, background=0.15):
    """Topic clusters embedded in a continuous background."""
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32) * sep
    assign = rng.integers(0, n_clusters, n)
    pts = centers[assign] + spread * rng.normal(size=(n, d)).astype(np.float32)
    nb = int(n * background)
    if nb:
        scale = float(np.abs(centers).max() * 1.2)
        pts[:nb] = rng.normal(size=(nb, d)).astype(np.float32) * scale * 0.6
        assign[:nb] = -1
    return pts.astype(np.float32), centers, assign


def make(params: dict) -> Corpus:
    n, d, dim = params["rows"], params["intrinsic_dim"], params["dim"]
    rng = np.random.default_rng(params["seed"])
    pts, centers, _ = clustered(n, d, params["clusters"], rng)
    by_rank = rng.permutation(params["clusters"])
    lift_rng = np.random.default_rng(params["lift_seed"])
    basis = orthonormal_rows(lift_rng, d, dim)
    vectors = lift_rows(pts, basis)
    vectors += LIFT_NOISE * lift_rng.standard_normal(vectors.shape, np.float32)
    return Corpus(vectors=vectors, basis=basis, centers=centers,
                  by_rank=by_rank)
