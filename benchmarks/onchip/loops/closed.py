"""Closed loop: one caller hands the frontend a batch of ``batch`` queries
with ``search()`` and hands it the next once the results are on the
host.  A query's latency runs from the hand-off of its batch to its
results.  Warm-up: ``warmup_batches`` batches the same way; the window
goes on from the batch after them."""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from onchip_harness.drive import NoHooks, Prefetcher, Window, stream_blocks


def warm(fe, stream, traffic: dict) -> None:
    for i in range(traffic["warmup_batches"]):
        fe.search(stream.batch(i, traffic["batch"]))


def run(fe, stream, traffic: dict, seconds: float, k: int,
        hooks=NoHooks()) -> Window:
    """Hand batches to ``fe.search`` until ``seconds`` have passed."""
    batch, first = traffic["batch"], traffic["warmup_batches"]
    pre = Prefetcher(lambda i: stream.batch(i, batch), start=first)
    ids, dists, lat, stats, calls = [], [], [], [], []
    dispatched = fe.batches_dispatched
    try:
        pre.fill()
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("onchip.next_batch"):
                q = pre.get()
            hooks.tick(time.perf_counter() - t0, len(stats))
            ts = time.perf_counter()
            with TraceAnnotation("onchip.fe_search"):
                i, d, st = fe.search(q)
            te = time.perf_counter()
            with TraceAnnotation("onchip.record"):
                ids.append(i)
                dists.append(d)
                lat.append(np.full(q.shape[0], te - ts))
                stats.extend(st)
                calls.append((ts - t0, te - ts, q.shape[0]))
            del q
            if te - t0 >= seconds:
                break
        hooks.end()
    finally:
        pre.close()
    n = sum(len(a) for a in ids)
    return Window(blocks=stream_blocks(stream, first, batch, n),
                  ids=np.concatenate(ids), dists=np.concatenate(dists),
                  answered=np.ones(n, bool),
                  latency_s=np.concatenate(lat), seconds=te - t0,
                  stats=stats, batches=fe.batches_dispatched - dispatched,
                  max_batch=fe.max_batch,
                  calls=np.array(calls).reshape(-1, 3))
