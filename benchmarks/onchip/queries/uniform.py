"""Uniform in the box [-box, box] at the intrinsic width: the
no-locality worst case (``repro.data.workloads.make_uniform``, box 4)."""
from __future__ import annotations

import numpy as np


def draw(rng: np.random.Generator, n: int, traffic: dict,
         corpus) -> np.ndarray:
    d = corpus.basis.shape[0]
    return (rng.uniform(-1, 1, size=(n, d)) * traffic["box"]).astype(
        np.float32)
