"""Zipf-ranked topics, paraphrased: the paper's MedRAG queries.

A query picks a popularity rank r in 1..T over the corpus's T topics
with probability proportional to r ** -zipf_s (the paper's Zipf(0.8)
over ranked clusters: ``zipf_s`` 0.8), takes that topic's center and
adds Gaussian paraphrase noise of scale ``paraphrase`` at the intrinsic
width (``repro.data.workloads.make_medrag_zipf``'s 0.15).
"""
from __future__ import annotations

import numpy as np


def rank_probabilities(topics: int, s: float) -> np.ndarray:
    p = np.arange(1, topics + 1, dtype=np.float64) ** -float(s)
    return p / p.sum()


def draw(rng: np.random.Generator, n: int, traffic: dict,
         corpus) -> np.ndarray:
    topics, d = corpus.centers.shape
    ranks = rng.choice(topics, size=n,
                       p=rank_probabilities(topics, traffic["zipf_s"]))
    q = corpus.centers[corpus.by_rank[ranks]] + traffic["paraphrase"] * \
        rng.normal(size=(n, d))
    return q.astype(np.float32)
