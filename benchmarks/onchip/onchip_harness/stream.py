"""Query streams, drawn from a run's seed and a traffic mix's parameters.

One general generator for every mix: a mix file (``traffic/<mix>.json``)
names a query kind (``queries``) and its parameters; the kind's module
``queries/<kind>.py`` draws queries at the corpus's intrinsic width
from the corpus's topics.  They are lifted into the corpus's width by
its basis, plus the lift's ambient noise.  The noise comes from a pool
of rows drawn once per run, one row per query chosen at random, so that
a batch costs a small gather and not tens of milliseconds of Gaussian
draws.  Batch ``i`` has a
generator of its own, seeded by (seed, i), so any batch can be made
without the ones before it.
"""
from __future__ import annotations

import numpy as np

from onchip_harness import registry
from onchip_harness.corpus import LIFT_NOISE, Corpus

NOISE_POOL_ROWS = 8192
_POOL, _BATCH, _ARRIVALS = 0, 1, 2      # seed-sequence namespaces


class QueryStream:
    def __init__(self, corpus: Corpus, traffic: dict, seed: int):
        self.corpus, self.traffic, self.seed = corpus, traffic, int(seed)
        self.kind = traffic["queries"]
        self._draw = registry.load_module("queries", self.kind).draw
        pool_rng = np.random.default_rng((self.seed, _POOL))
        dim = corpus.basis.shape[1]
        self.noise = LIFT_NOISE * pool_rng.standard_normal(
            (NOISE_POOL_ROWS, dim), np.float32)

    def intrinsic(self, rng: np.random.Generator, n: int) -> np.ndarray:
        return self._draw(rng, n, self.traffic, self.corpus)

    def batch(self, i: int, n: int) -> np.ndarray:
        """Batch ``i`` of ``n`` queries, (n, dim) float32."""
        rng = np.random.default_rng((self.seed, _BATCH, int(i)))
        q = self.intrinsic(rng, n) @ self.corpus.basis
        q += self.noise[rng.integers(0, NOISE_POOL_ROWS, n)]
        return q

    def arrivals(self, rate: float, seconds: float) -> np.ndarray:
        """Poisson arrival times (s) at ``rate`` per second over
        ``seconds``, ascending, all below ``seconds``."""
        rng = np.random.default_rng((self.seed, _ARRIVALS))
        n = int(rate * seconds * 1.2 + 64)
        while True:
            t = np.cumsum(rng.exponential(1.0 / rate, size=n))
            if t[-1] >= seconds:
                return t[t < seconds]
            n *= 2
