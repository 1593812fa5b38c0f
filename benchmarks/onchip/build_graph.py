"""Build a configuration's Vamana graph once and store it beside the benchmark.

    python3 benchmarks/onchip/build_graph.py medrag-768-ram [--out PATH]

A deployment builds its index offline and loads it; the benchmark does
the same.  This script makes the configuration's corpus
(``onchip_harness.corpus``), builds the graph with the system's own
``repro.core.vamana.build_vamana`` at the configuration's degree, alpha,
build beam and seed, and writes the adjacency (uint16, 65535 for an
empty slot), the medoid, the corpus hash and the generator parameters
to the configuration's ``graph`` file.  ``run.py`` checks a fresh
corpus against that hash and loads the graph; it never builds one.
It runs on any backend; on a CPU the build of 60,000 rows at 768-d
takes about two hours, nearly all of it in the host-side prune.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

from onchip_harness import corpus, graphfile, registry  # noqa: E402


def build(config: dict) -> tuple[np.ndarray, int, float]:
    from repro.core.vamana import VamanaParams, build_vamana
    spec = config["spec"]
    data = corpus.make_corpus(config["corpus"])
    params = VamanaParams(max_degree=spec["degree"], alpha=spec["alpha"],
                          build_beam=spec["build_beam"],
                          batch=spec["build_batch"], seed=spec["seed"])
    t0 = time.perf_counter()
    adj, medoid = build_vamana(data.vectors, params)
    return adj, medoid, time.perf_counter() - t0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("config", help="configuration name (configs/<name>.json)")
    p.add_argument("--out", help="output path (default: the config's graph)")
    args = p.parse_args(argv)
    config = registry.load_config(args.config)
    adj, medoid, seconds = build(config)
    data = corpus.make_corpus(config["corpus"])
    out = args.out or os.path.join(HERE, config["graph"])
    graphfile.save(out, adj, medoid, corpus.digest(data.vectors),
                   config["corpus"], seconds)
    print(json.dumps({"graph": out, "rows": int(adj.shape[0]),
                      "medoid": int(medoid), "build_s": seconds,
                      "rows_per_s": adj.shape[0] / seconds,
                      "corpus_sha256": corpus.digest(data.vectors)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
