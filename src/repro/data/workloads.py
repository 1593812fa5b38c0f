"""The paper's four evaluation workloads (§4.1.1), distribution-matched.

TripClick/PubMed/MedCPT embeddings and the arXiv corpus are not available
offline, so each workload is synthesized to preserve the property the
paper tests (DESIGN.md §8):

  tripclick    — session random-walk over topic clusters: real user
                 traffic's *temporal* locality (bursts of related queries)
                 replayed in order.
  medrag_zipf  — clusters sampled by Zipf(0.8) + paraphrase jitter:
                 the heavy-tailed *frequency* skew of search logs.
  uniform      — queries uniform in [-1,1]^d: the no-locality worst case.
  papers       — labeled corpus (arXiv-like primary categories); filtered
                 queries ask for neighbors within the query's category.

Corpora are Gaussian cluster mixtures (embedding models map topically
similar text to nearby vectors; clusters model topics).

Dimensionality note: ambient d defaults to 24, matching the INTRINSIC
dimension regime of real text embeddings (768-d MedCPT vectors
concentrate on a ~10–30-d manifold).  Isotropic Gaussians at ambient
d≈64+ are *harder* than real embeddings — distance concentration stops
RobustPrune's coverage rule from ever firing, so every graph method
(including reference DiskANN) degrades into cluster islands; measured in
EXPERIMENTS.md §Repro notes.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Workload:
    name: str
    corpus: np.ndarray                 # (N, d)
    queries: np.ndarray                # (Q, d), replayed in order
    labels: np.ndarray | None = None   # (N,) corpus labels (papers)
    filter_labels: np.ndarray | None = None  # (Q,) query predicates
    meta: dict | None = None           # generator annotations (shift points
                                       # etc.) consumed by the adapt benches


def _clustered_corpus(n, d, n_clusters, rng, spread=1.0, sep=1.5,
                      background=0.15):
    """Topic clusters embedded in a continuous manifold.

    Real text-embedding clouds are density *modes* on a connected
    manifold, not isolated islands: with isolated Gaussian islands
    (large sep, no background) even reference DiskANN's greedy descent
    dead-ends at inter-cluster voids — a geometry no embedding model
    produces.  A background fraction + moderate separation keeps the
    corpus greedy-navigable while preserving the locality structure the
    paper's workloads test.
    """
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32) * sep
    assign = rng.integers(0, n_clusters, n)
    pts = centers[assign] + spread * rng.normal(size=(n, d)).astype(np.float32)
    nb = int(n * background)
    if nb:
        scale = float(np.abs(centers).max() * 1.2)
        pts[:nb] = rng.normal(size=(nb, d)).astype(np.float32) * scale * 0.6
        assign[:nb] = -1
    return pts.astype(np.float32), centers, assign


def make_tripclick(n=20_000, d=24, n_clusters=64, n_queries=4_096, seed=0,
                   session_len=16, hot_frac=0.2):
    """Temporal locality: sessions orbit a *document* of a popular topic
    (real users query about existing content — anchoring sessions on
    corpus points keeps queries on-manifold; abstract topic centroids
    can fall in low-density voids where no graph method navigates).
    Popularity is heavy-tailed ('asthma pregnancy'-style heads)."""
    rng = np.random.default_rng(seed)
    corpus, centers, assign = _clustered_corpus(n, d, n_clusters, rng)
    n_hot = max(1, int(n_clusters * hot_frac))
    popular = rng.permutation(n_clusters)[:n_hot]
    by_topic = [np.nonzero(assign == t)[0] for t in range(n_clusters)]
    qs = []
    while len(qs) < n_queries:
        topic = popular[rng.integers(0, n_hot)] if rng.random() < 0.8 \
            else rng.integers(0, n_clusters)
        docs = by_topic[topic]
        if docs.size == 0:
            continue
        anchor = corpus[docs[rng.integers(0, docs.size)]]
        for _ in range(session_len):
            qs.append(anchor + 0.25 * rng.normal(size=d))
            if len(qs) >= n_queries:
                break
    return Workload("tripclick", corpus,
                    np.asarray(qs, np.float32))


def make_medrag_zipf(n=20_000, d=24, n_clusters=256, n_queries=4_096,
                     seed=1, zipf_a=1.8, paraphrase=0.15):
    """Zipf-sampled paraphrase clusters (the paper's Zipf(0.8) over ranked
    clusters; numpy's one-parameter zipf uses a>1, the rank skew matches)."""
    rng = np.random.default_rng(seed)
    corpus, centers, _ = _clustered_corpus(n, d, n_clusters, rng)
    ranks = rng.zipf(zipf_a, size=n_queries) % n_clusters
    base = rng.permutation(n_clusters)[ranks]
    qs = centers[base] + paraphrase * rng.normal(size=(n_queries, d))
    return Workload("medrag_zipf", corpus, qs.astype(np.float32))


def make_shifted_zipf(n=20_000, d=24, n_clusters=256, n_queries=4_096,
                      seed=1, zipf_a=1.8, paraphrase=0.15, kind="sudden",
                      period=None):
    """medrag_zipf with a mid-stream workload shift (the paper's Fig. 7
    adaptation scenarios).

    Two independent rank→cluster popularity maps A and B over the SAME
    corpus; each query draws its Zipf rank as usual, then resolves it
    through A or B depending on stream position:

      sudden    — A for the first half, B for the second: the hot set
                  swaps instantly (a trending-topic event),
      gradual   — P(B) ramps linearly from 0 to 1 over the middle half
                  of the stream: slow audience migration,
      flipflop  — A/B alternate every ``period`` queries (default Q/8):
                  periodic traffic (time zones, weekday/weekend).

    ``meta['shift_point']`` marks where post-shift measurement starts:
    the swap for sudden, the end of the ramp for gradual, the last flip
    for flipflop.
    """
    rng = np.random.default_rng(seed)
    corpus, centers, _ = _clustered_corpus(n, d, n_clusters, rng)
    ranks = rng.zipf(zipf_a, size=n_queries) % n_clusters
    perm_a = rng.permutation(n_clusters)
    perm_b = rng.permutation(n_clusters)
    i = np.arange(n_queries)
    if kind == "sudden":
        shift = n_queries // 2
        use_b = i >= shift
    elif kind == "gradual":
        ramp = np.clip((i - n_queries // 4) / max(n_queries // 2, 1), 0., 1.)
        use_b = rng.random(n_queries) < ramp
        shift = 3 * n_queries // 4
    elif kind == "flipflop":
        period = period or max(n_queries // 8, 1)
        use_b = (i // period) % 2 == 1
        shift = (n_queries // period) * period - period
    else:
        raise ValueError(f"unknown shift kind {kind!r}")
    cluster = np.where(use_b, perm_b[ranks], perm_a[ranks])
    qs = centers[cluster] + paraphrase * rng.normal(size=(n_queries, d))
    return Workload(f"shifted_zipf_{kind}", corpus, qs.astype(np.float32),
                    meta={"kind": kind, "shift_point": int(shift),
                          "period": int(period or 0)})


def make_uniform(n=20_000, d=24, n_queries=4_096, seed=2):
    rng = np.random.default_rng(seed)
    corpus, _, _ = _clustered_corpus(n, d, 64, rng)
    qs = rng.uniform(-1, 1, size=(n_queries, d)).astype(np.float32) * 4.0
    return Workload("uniform", corpus, qs)


def make_papers(n=20_000, d=24, n_labels=16, n_queries=2_048, seed=3):
    """Labeled corpus; every query carries its own category predicate."""
    rng = np.random.default_rng(seed)
    # no background mass: every paper carries a category label
    corpus, centers, assign = _clustered_corpus(n, d, n_labels, rng,
                                                background=0.0)
    labels = assign.astype(np.int32)       # cluster == arXiv category
    qi = rng.integers(0, n_labels, n_queries)
    qs = centers[qi] + 0.5 * rng.normal(size=(n_queries, d))
    return Workload("papers", corpus, qs.astype(np.float32),
                    labels=labels, filter_labels=qi.astype(np.int32))


WORKLOADS = {
    "tripclick": make_tripclick,
    "medrag_zipf": make_medrag_zipf,
    "shifted_zipf": make_shifted_zipf,
    "uniform": make_uniform,
    "papers": make_papers,
}


# Per-dimension scale of the noise ``lift`` adds.  Summed over 768
# dimensions it carries about 768 * 0.02**2 = 0.31 of squared norm,
# small against the ~24 of a point's intrinsic spread, so near
# neighbors stay near while no ambient direction is left empty.
_LIFT_NOISE = 0.02


def lift(wl: Workload, dim: int, *, seed: int = 0) -> Workload:
    """Embed a workload in ``dim`` ambient dimensions.

    Real text embeddings are hundreds of dimensions wide but concentrate
    on a low-dimensional manifold (the module note above).  A fixed
    random map with orthonormal columns carries corpus and queries from
    the generator's intrinsic dimension into ``dim`` without changing
    any distance; isotropic Gaussian noise of scale ``_LIFT_NOISE`` then
    fills the ambient space, as the residual of an embedding model
    does.  Distances between points change by about the same amount
    everywhere, so the neighbor structure the workload tests survives
    and the graph stays navigable at deployment width."""
    d = wl.corpus.shape[1]
    if dim < d:
        raise ValueError(f"cannot lift {d}-d data into {dim} dimensions")
    rng = np.random.default_rng(seed)
    basis, _ = np.linalg.qr(rng.normal(size=(dim, d)))     # (dim, d)
    basis = basis.T.astype(np.float32)

    def up(x: np.ndarray) -> np.ndarray:
        y = x @ basis
        y += _LIFT_NOISE * rng.standard_normal(y.shape, np.float32)
        return y

    return dataclasses.replace(wl, corpus=up(wl.corpus),
                               queries=up(wl.queries))
