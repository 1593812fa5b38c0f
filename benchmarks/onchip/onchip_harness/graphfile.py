"""The stored graph: adjacency as uint16 (65535 = empty slot), medoid,
the corpus hash it was built on, and the generator parameters."""
from __future__ import annotations

import json

import numpy as np

EMPTY = np.iinfo(np.uint16).max


class GraphMismatch(RuntimeError):
    """The stored graph was built on another corpus than this one."""


def save(path: str, adj: np.ndarray, medoid: int, sha256: str,
         corpus_params: dict, build_s: float) -> None:
    if adj.shape[0] >= EMPTY:
        raise ValueError(f"{adj.shape[0]} rows do not fit uint16 ids")
    packed = np.where(adj < 0, EMPTY, adj).astype(np.uint16)
    np.savez_compressed(path, adjacency=packed, medoid=np.int64(medoid),
                        corpus_sha256=np.str_(sha256),
                        corpus_params=np.str_(json.dumps(corpus_params,
                                                         sort_keys=True)),
                        build_s=np.float64(build_s))


def load(path: str, sha256: str) -> tuple[np.ndarray, int]:
    """(adjacency int32 with -1 padding, medoid); raises ``GraphMismatch``
    unless the file was built on the corpus whose hash is ``sha256``."""
    with np.load(path) as f:
        stored = str(f["corpus_sha256"])
        if stored != sha256:
            raise GraphMismatch(
                f"{path} was built on corpus {stored}, this corpus is "
                f"{sha256}: rebuild it with build_graph.py")
        packed = f["adjacency"]
        medoid = int(f["medoid"])
    adj = packed.astype(np.int32)
    adj[packed == EMPTY] = -1
    return adj, medoid
