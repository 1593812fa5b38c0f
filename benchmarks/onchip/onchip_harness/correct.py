"""The comparison that decides ``correct``.

Every query due in the window is compared with the plain reference
(``reference.py``), over exactly the ids and distances the timed path
returned, a block of queries at a time.  Three numbers, each held to a
limit of its own (a cell's ``limits/<cell>.json``; how each limit was
set is in PERF.md):

* ``unanswered``: due queries with no answer, with an id outside the
  corpus, or with an id twice.  Exact: the limit is 0.
* ``miss_rate``: 1 - recall@10 against exact k-NN, over all due queries.
  The search is approximate by design, so each cell's limit lies
  between what sound runs read and what a search cut short reads; a
  search that walks nowhere, or answers one query with another's
  neighbors, misses most of them.
* ``dist_err``: the widest relative gap between a distance the answer
  reports and the exact squared distance of the row it names.  It
  catches an answer altered after it was produced, and distances
  computed in a lower precision.
"""
from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np

from onchip_harness import reference

NAMES = ("unanswered", "miss_rate", "dist_err")


def numbers(vectors, blocks, ids: np.ndarray, dists: np.ndarray,
            answered: np.ndarray, k: int, knn=reference.exact_knn) -> dict:
    """The compared numbers for the answers the timed path gave
    (``ids``/``dists`` (Q, k); ``answered`` (Q,) bool) to the queries
    that ``blocks()`` yields as (lo, hi, queries[lo:hi]).  ``knn`` is
    the reference; the control puts another in its place."""
    vectors = jnp.asarray(vectors, jnp.float32)     # on the device once
    n = vectors.shape[0]
    bad = hits = 0
    gap_max = 0.0
    for lo, hi, q in blocks():
        bi, bd = ids[lo:hi], dists[lo:hi]
        truth, _ = knn(vectors, q, k)
        valid = answered[lo:hi] & (bi >= 0).all(axis=1) & (bi < n).all(
            axis=1)
        srt = np.sort(bi, axis=1)
        valid &= ~(srt[:, 1:] == srt[:, :-1]).any(axis=1)
        h = (bi[:, :, None] == truth[:, None, :]).any(axis=2).sum(axis=1)
        hits += int(np.where(valid, h, 0).sum())
        bad += int((~valid).sum())
        exact = reference.row_dists(vectors, q, bi)
        gap = np.abs(bd.astype(np.float64) - exact) / np.maximum(exact, 1e-6)
        gap = np.where(valid[:, None], gap, 0.0)
        if gap.size:
            gap_max = max(gap_max, float(gap.max()))
    total = k * ids.shape[0]
    return {
        "unanswered": bad,
        "miss_rate": float(1.0 - hits / total) if total else 0.0,
        "dist_err": gap_max,
        "recall_at_10": float(hits / total) if total else 0.0,
    }


def load_limits(cell: str, root: str) -> dict:
    """``limits/<cell>.json``: {name: {"limit": x, ...}} for each of
    ``NAMES``."""
    path = os.path.join(root, "limits", f"{cell}.json")
    with open(path) as f:
        limits = json.load(f)
    missing = [n for n in NAMES if n not in limits]
    if missing:
        raise KeyError(f"{path} gives no limit for {missing}")
    return limits


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under
    its limit."""
    checks = {n: {"value": found[n], "limit": limits[n]["limit"]}
              for n in NAMES}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
