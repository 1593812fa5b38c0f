"""What every loop (``loops/<loop>.py``) shares: the record of a window,
a background maker of query batches, and the hooks a traced run uses.

Query batches are made by one background thread, a few batches ahead,
so that making them costs the window nothing but a queue hand-off.  A
window keeps no queries: every batch of a stream can be made again
from its index, so the check makes them again once the window has
closed (``Window.blocks``), and the window's host memory is its answers
alone.  The host spans the profiler records
(``jax.profiler.TraceAnnotation``) name what the harness was doing, for
the trace reduction's idle gaps.  A loop calls ``hooks.tick(elapsed_s,
dispatches_so_far)`` before each dispatch, where a traced run starts its
profiler, and ``hooks.end()`` once the last answer is in, before it
assembles the window's arrays.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Iterator

import numpy as np


class Prefetcher:
    """Make ``make(i)`` for i = start, start + 1, ... on one thread,
    ``depth`` ahead of the consumer."""

    def __init__(self, make, start: int = 0, depth: int = 4):
        self._make, self._q = make, queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, args=(start,),
                                        daemon=True)
        self._thread.start()

    def _run(self, i: int) -> None:
        try:
            while not self._stop.is_set():
                item = self._make(i)
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                i += 1
        except BaseException as e:      # handed to the consumer in get()
            self._error = e

    def fill(self) -> None:
        """Wait until ``depth`` items are made, so that a window starts
        with the maker idle and not competing with its first dispatch."""
        while not self._q.full():
            if self._error is not None:
                raise self._error
            time.sleep(0.01)

    def get(self):
        while True:
            if self._error is not None:
                raise self._error
            try:
                return self._q.get(timeout=0.1)
            except queue.Empty:
                continue

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            raise RuntimeError("query prefetch thread did not stop")


def stream_blocks(stream, first: int, size: int, n: int
                  ) -> Callable[[], Iterator[tuple[int, int, np.ndarray]]]:
    """The first ``n`` queries of batches ``first``, ``first + 1``, ...
    of ``size`` queries each, made again block by block: an iterator of
    (lo, hi, queries[lo:hi])."""
    def blocks():
        for j, lo in enumerate(range(0, n, size)):
            hi = min(lo + size, n)
            yield lo, hi, stream.batch(first + j, size)[: hi - lo]
    return blocks


@dataclasses.dataclass
class Window:
    """What the window served: per query and per dispatch."""
    blocks: Callable           # () -> iterator of (lo, hi, queries), every
                               # query due in the window, made again
    ids: np.ndarray            # (Q, k) answers, -1 where none came
    dists: np.ndarray          # (Q, k)
    answered: np.ndarray       # (Q,) bool
    latency_s: np.ndarray      # (Q,)
    seconds: float             # window start to the last answer
    stats: list                # per dispatch: SearchStats of real lanes
    batches: int               # the frontend's dispatches in the window
    max_batch: int
    calls: np.ndarray          # (calls, 3): start (s into the window),
                               # seconds and queries of each frontend call
    queue_wait_s: np.ndarray | None = None   # open loop: due -> flush

    @property
    def n_queries(self) -> int:
        return int(self.ids.shape[0])


class NoHooks:
    def tick(self, elapsed: float, dispatched: int) -> None:
        pass

    def end(self) -> None:
        pass
