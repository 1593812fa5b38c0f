"""Open loop: queries arrive one by one at Poisson times at ``rate`` per
second; whenever the previous ``flush()`` has returned, every query
that has come due is ``submit()``-ted and flushed.  A query's latency
runs from its due time, so a stall counts against every query that
waits behind it.  Queries are made ``CHUNK`` at a time in the
background.  Warm-up: ``warmup_batches`` batches of ``batch`` queries,
each submitted and flushed at once; the window's chunks go on from the
chunk with that index."""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from onchip_harness.drive import NoHooks, Prefetcher, Window, stream_blocks

CHUNK = 4096            # queries made per background batch


def warm(fe, stream, traffic: dict) -> None:
    for i in range(traffic["warmup_batches"]):
        for row in stream.batch(i, traffic["batch"]):
            fe.submit(row)
        fe.flush()


def run(fe, stream, traffic: dict, seconds: float, k: int,
        hooks=NoHooks()) -> Window:
    """Flush whatever is due whenever the previous flush has returned,
    until every query that came due in the window is answered."""
    first = traffic["warmup_batches"]
    due = stream.arrivals(traffic["rate"], seconds)
    n = due.size
    pre = Prefetcher(lambda c: stream.batch(c, CHUNK), start=first)
    chunks: dict[int, np.ndarray] = {}      # made and not yet submitted
    made = 0
    ids = np.full((n, k), -1, np.int32)
    dists = np.full((n, k), np.inf, np.float32)
    answered = np.zeros(n, bool)
    lat = np.zeros(n)
    wait = np.zeros(n)
    calls = []
    dispatched = fe.batches_dispatched
    nxt = 0
    try:
        pre.fill()
        te = t0 = time.perf_counter()
        while nxt < n:
            now = time.perf_counter() - t0
            end = int(np.searchsorted(due, now, side="right"))
            if end == nxt:
                with TraceAnnotation("onchip.wait_arrival"):
                    time.sleep(max(due[nxt] - now, 0.0))
                continue
            hooks.tick(now, fe.batches_dispatched - dispatched)
            with TraceAnnotation("onchip.submit"):
                while made * CHUNK < end:
                    chunks[made] = pre.get()
                    made += 1
                tickets = [fe.submit(chunks[j // CHUNK][j % CHUNK])
                           for j in range(nxt, end)]
                for c in range(nxt // CHUNK, end // CHUNK):
                    chunks.pop(c, None)
            tf = time.perf_counter()
            with TraceAnnotation("onchip.fe_flush"):
                out = fe.flush()
            te = time.perf_counter()
            with TraceAnnotation("onchip.record"):
                for j, t in zip(range(nxt, end), tickets):
                    got = out.get(t)
                    if got is not None:
                        ids[j], dists[j] = got
                        answered[j] = True
                lat[nxt:end] = te - t0 - due[nxt:end]
                wait[nxt:end] = tf - t0 - due[nxt:end]
                calls.append((tf - t0, te - tf, end - nxt))
            nxt = end
        hooks.end()
    finally:
        pre.close()
    return Window(blocks=stream_blocks(stream, first, CHUNK, n), ids=ids,
                  dists=dists, answered=answered, latency_s=lat,
                  seconds=te - t0, stats=[],
                  batches=fe.batches_dispatched - dispatched,
                  max_batch=fe.max_batch,
                  calls=np.array(calls).reshape(-1, 3),
                  queue_wait_s=wait)
