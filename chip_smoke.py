"""Chip smoke: the vector-search main path, once, on one TPU at 768-d.

Run from the root of a checkout on a machine whose JAX sees a TPU:

    python chip_smoke.py [--rows N] [--seed S]

Any other backend is refused: the script exits non-zero and names the
platform it found.  On the chip it drives the public entry points
(``catapultdb.create`` -> ``warm`` -> ``serve`` -> frontend batches) at
the deployment geometry of ``src/repro/configs/catapultdb.py`` (768-d,
degree 64, 8 LSH bits, buckets of 40, beam 16, k 10, 4096-query
batches).  Only the row count is cut, because the graph build runs on
the host.  Data is the ``medrag_zipf`` workload (Zipf-popular topic
clusters) generated from ``--seed`` at intrinsic dimension 24 and
lifted to 768-d (``repro.data.workloads.lift``).

Phases, each of which raises on a failed check:

  a. RAM tier, catapult mode.  Recall@10 against ``brute_force_knn``
     on the first batch must be no more than 0.01 below DiskANN mode on
     the same graph and queries; replaying a batch must take fewer
     hops, with catapults used by (nearly) every query.
  b. The fused Pallas hop: ``hop_backend="fused"`` over the same
     batches returns the unfused ids, distances and hop counts in every
     lane, bit for bit (both sum distances in ``repro.distance``'s
     order); the lanes that differ are counted, and must be none.
  c. Disk tier over the same vectors, with its default PQ: a batch
     reads blocks, and its recall is no more than 0.01 below phase a's
     on the same batch.

The last line of output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK_DIR = os.path.join(ROOT, ".chip_smoke")     # disk-tier index files

# deployment geometry: src/repro/configs/catapultdb.py
DIM = 768
DEGREE = 64
N_BITS = 8
BUCKET_CAPACITY = 40
BEAM = 16
K = 10
BATCH = 4096

INTRINSIC_DIM = 24          # the generator's dimension before the lift
DEFAULT_ROWS = 5_000        # host graph build bound (see CHANGES.md)
RECALL_SLACK = 0.01
MIN_REPLAY_USED = 0.99


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Served:
    """One frontend batch: results plus its host wall time."""
    ids: np.ndarray
    dists: np.ndarray
    hops: np.ndarray
    used: np.ndarray
    block_reads: np.ndarray | None
    seconds: float


def serve_batches(db, batches, batch: int) -> list[Served]:
    """Send each query batch through ``db.serve()``'s frontend, timing
    each until its results are on the host."""
    import jax

    fe = db.serve(max_batch=batch, k=K, beam_width=BEAM)
    out = []
    for q in batches:
        t0 = time.perf_counter()
        ids, dists, stats = jax.block_until_ready(fe.search(q))
        seconds = time.perf_counter() - t0
        reads = [s.block_reads for s in stats if s.block_reads is not None]
        out.append(Served(
            ids=ids, dists=dists,
            hops=np.concatenate([s.hops for s in stats]),
            used=np.concatenate([s.used for s in stats]),
            block_reads=np.concatenate(reads) if reads else None,
            seconds=seconds))
    return out


def recall(served: list[Served], truth: np.ndarray) -> float:
    from repro.core import recall_at_k
    return recall_at_k(np.concatenate([s.ids for s in served]), truth)


def device_bytes(stat: str) -> int:
    import jax
    return int(jax.devices()[0].memory_stats()[stat])


def make_data(rows: int, n_queries: int, seed: int):
    from repro.data.workloads import lift, make_medrag_zipf
    wl = make_medrag_zipf(n=rows, d=INTRINSIC_DIM, n_queries=n_queries,
                          seed=seed)
    wl = lift(wl, DIM, seed=seed)
    return wl.corpus, wl.queries


def phase_a(spec, vectors, graph, batches, truth, batch):
    """RAM tier, catapult mode, against DiskANN mode on the same graph."""
    from repro import db as catapultdb

    db = catapultdb.create(spec, vectors, prebuilt=graph)
    log(f"a: device bytes in use after create: "
        f"{device_bytes('bytes_in_use')}")
    warm_s = db.warm((batch,)) / 1e3
    log(f"a: warm (compile) seconds: {warm_s:.3f}")
    first, replay, steady = serve_batches(db, batches, batch)
    log(f"a: first batch seconds: {first.seconds:.3f}, replay "
        f"{replay.seconds:.3f}, steady batch seconds: {steady.seconds:.3f}")
    db.close()

    diskann = catapultdb.create(dataclasses.replace(spec, mode="diskann"),
                                vectors, prebuilt=graph)
    d_first = serve_batches(diskann, batches, batch)[0]
    diskann.close()

    r_cat = recall([first], truth)
    r_dsk = recall([d_first], truth)
    log(f"a: recall@10 catapult {r_cat:.4f} diskann {r_dsk:.4f}")
    check(r_cat >= r_dsk - RECALL_SLACK,
          f"catapult recall {r_cat:.4f} is more than {RECALL_SLACK} below "
          f"diskann {r_dsk:.4f}")
    h0, h1 = float(first.hops.mean()), float(replay.hops.mean())
    used = float(replay.used.mean())
    log(f"a: mean hops first {h0:.3f} replay {h1:.3f}; "
        f"catapults used on replay {used:.4f}")
    check(h1 < h0, f"replay hops {h1:.3f} not below first pass {h0:.3f}")
    check(used >= MIN_REPLAY_USED,
          f"catapults used by {used:.4f} of replayed queries")
    return [first, replay, steady], r_cat


def phase_b(spec, vectors, graph, batches, reference, batch):
    """Fused hop kernel against the unfused hop, end to end: every lane
    of every batch must return the same ids, distances and hops."""
    from repro import db as catapultdb

    db = catapultdb.create(dataclasses.replace(spec, hop_backend="fused"),
                           vectors, prebuilt=graph)
    warm_s = db.warm((batch,)) / 1e3
    fused = serve_batches(db, batches, batch)
    db.close()
    lanes = sum(int(((got.ids != want.ids).any(axis=1)
                     | (got.dists != want.dists).any(axis=1)
                     | (got.hops != want.hops)).sum())
                for got, want in zip(fused, reference))
    log(f"b: fused warm (compile) seconds {warm_s:.3f}; steady batch "
        f"seconds {fused[-1].seconds:.3f}; lanes differing from unfused "
        f"(ids, dists or hops): {lanes} of {batch * len(batches)}")
    check(lanes == 0, f"{lanes} fused lanes differ from the unfused search")


def phase_c(spec, vectors, graph, queries, truth, ram_recall, batch):
    """Disk tier with its default PQ: block reads happen, recall holds."""
    from repro import db as catapultdb

    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    try:
        disk_spec = dataclasses.replace(
            spec, tier="disk", path=os.path.join(WORK_DIR, "index.ctpl"))
        db = catapultdb.create(disk_spec, vectors, prebuilt=graph)
        warm_s = db.warm((batch,)) / 1e3
        (served,) = serve_batches(db, [queries], batch)
        db.close()
    finally:
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    reads = int(served.block_reads.sum())
    r = recall([served], truth)
    log(f"c: disk warm (compile) seconds {warm_s:.3f}; batch seconds "
        f"{served.seconds:.3f}; block reads {reads}; recall@10 {r:.4f} "
        f"(ram {ram_recall:.4f})")
    check(reads > 0, "disk tier read no blocks")
    check(r >= ram_recall - RECALL_SLACK,
          f"disk recall {r:.4f} is more than {RECALL_SLACK} below ram "
          f"{ram_recall:.4f}")


def run(rows: int, seed: int, batch: int = BATCH) -> None:
    """All three phases at ``rows`` corpus rows and ``batch``-query
    batches (two distinct batches, the first one replayed)."""
    from repro.core import brute_force_knn
    from repro.core.vamana import build_vamana
    from repro.db import IndexSpec

    vectors, queries = make_data(rows, 2 * batch, seed)
    q_a, q_b = queries[:batch], queries[batch:]
    spec = IndexSpec(tier="ram", mode="catapult", dim=DIM, degree=DEGREE,
                     n_bits=N_BITS, bucket_capacity=BUCKET_CAPACITY,
                     beam_width=BEAM, k=K, seed=seed)
    log(f"rows N={rows} d={DIM} queries per batch={batch}")
    t0 = time.perf_counter()
    graph = build_vamana(vectors, spec.vamana())
    build_s = time.perf_counter() - t0
    log(f"build seconds {build_s:.3f}; build rows/s {rows / build_s:.1f}")
    t0 = time.perf_counter()
    truth = brute_force_knn(vectors, q_a, K)     # recall is read on q_a
    log(f"brute-force truth seconds {time.perf_counter() - t0:.3f}")

    batches = [q_a, q_a, q_b]
    served, ram_recall = phase_a(spec, vectors, graph, batches, truth,
                                 batch)
    phase_b(spec, vectors, graph, batches, served, batch)
    phase_c(spec, vectors, graph, q_a, truth, ram_recall, batch)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--rows", type=int, default=DEFAULT_ROWS,
                   help="corpus rows (the only cut from the deployment)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable
    log(f"compile cache: {enable()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    t0 = time.perf_counter()
    run(args.rows, args.seed)
    log(f"peak_bytes_in_use {device_bytes('peak_bytes_in_use')}; "
        f"total seconds {time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
