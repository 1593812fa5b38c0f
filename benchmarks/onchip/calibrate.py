"""Readings that a cell's limits are set from (limits/<cell>.json).

    python3 benchmarks/onchip/calibrate.py --workload <cell> \\
        --seeds 11,12,... --control 3 --seconds <s>

On the chip, in one process (set-up once).  One JSON line per reading:

* ``program``: for each ``--seeds`` seed, the cell's window at its own
  load for ``--seconds``, then the compared numbers of
  ``onchip_harness.correct`` for what the timed path answered.  The
  largest over the seeds, with those of the benchmark's own runs, is a
  limit's lower reading.
* ``control``: over the queries of each of the first ``--control``
  program windows, the reference put in the program's place, one
  precision below the float32 the configuration states: exact k-NN
  over bfloat16 operands (what a float32 matmul at the TPU's default
  precision computes).
* ``narrow_beam``: the timed path with its beam cut to ``k``, for each
  of the first ``--control`` seeds: a search faster at lower recall,
  the step that would tempt a later change.
* ``half_rows``: over the same queries, exact k-NN over a random half
  of the rows put in the program's place: half of the index lost.

The upper reading of a number is the least that the control and the
planted faults give where that is three times its lower reading or more.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def put_in_place(knn, vectors, win, k: int):
    """The answers ``knn`` gives to the window's queries, judged as the
    program's answers are."""
    import numpy as np
    from onchip_harness import correct
    ids = np.empty((win.n_queries, k), np.int32)
    dists = np.empty((win.n_queries, k), np.float32)
    for lo, hi, q in win.blocks():
        ids[lo:hi], dists[lo:hi] = knn(vectors, q, k)
    return correct.numbers(vectors, win.blocks, ids, dists,
                           np.ones(win.n_queries, bool), k)


def bfloat16_knn(vectors, q, k):
    import jax.numpy as jnp
    from onchip_harness import reference
    return reference.exact_knn(vectors, q, k, dtype=jnp.bfloat16)


def half_rows_knn(seed: int):
    import numpy as np
    from onchip_harness import reference
    keep = {}

    def knn(vectors, q, k):
        if "rows" not in keep:
            n = vectors.shape[0]
            keep["rows"] = np.sort(
                np.random.default_rng(seed).permutation(n)[: n // 2])
            keep["x"] = np.asarray(vectors)[keep["rows"]]
        ids, dists = reference.exact_knn(keep["x"], q, k)
        return keep["rows"][ids].astype(np.int32), dists
    return knn


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]

    import run
    from onchip_harness import cell, correct, registry
    from onchip_harness.stream import QueryStream
    entry = registry.cell(args.workload, registry.benchmark())
    run.check_platform(entry["chips"])
    run.enable_compile_cache()
    config = registry.load_config(entry["config"])
    traffic = registry.load_traffic(entry["traffic"])

    def emit(kind, seed, win, found, **kw):
        print(json.dumps({"kind": kind, "seed": seed,
                          "queries": win.n_queries, **kw, **found}),
              flush=True)

    db, fe, data, _, k = cell.setup(config, traffic, seeds[0])
    wins = []
    for seed in seeds:
        win = cell.window(fe, QueryStream(data, traffic, seed), traffic,
                          args.seconds, k)
        t0 = time.perf_counter()
        found = correct.numbers(data.vectors, win.blocks, win.ids,
                                win.dists, win.answered, k)
        emit("program", seed, win, found, window_s=win.seconds,
             check_s=time.perf_counter() - t0)
        if len(wins) < args.control:
            wins.append((seed, win))

    batch = traffic["batch"]
    db.warm((batch,), beam_width=k)
    narrow = db.serve(max_batch=batch, k=k, beam_width=k)
    for seed, _ in wins:
        stream = QueryStream(data, traffic, seed)
        cell.loop(traffic).warm(narrow, stream, traffic)
        win = cell.window(narrow, stream, traffic, args.seconds, k)
        found = correct.numbers(data.vectors, win.blocks, win.ids,
                                win.dists, win.answered, k)
        emit("narrow_beam", seed, win, found, beam_width=k)
    db.close()

    for seed, win in wins:
        emit("control", seed, win, put_in_place(bfloat16_knn, data.vectors,
                                                win, k))
        emit("half_rows", seed, win, put_in_place(half_rows_knn(seed),
                                                  data.vectors, win, k))
    return 0


if __name__ == "__main__":
    sys.exit(main())
