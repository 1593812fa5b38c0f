"""One run of one cell: set-up, the measured window, then the check.

Set-up regenerates the configuration's corpus, checks it against the
stored graph's hash, loads the graph, calls ``catapultdb.create(spec,
vectors, prebuilt=graph)`` and ``warm()`` for the cell's batch shape,
and serves a warm-up prefix of the stream through ``db.serve()`` (it
fills the catapult buckets and, on the disk tier, the block cache).  The
window then drives the traffic for ``seconds``.  Only once it has closed
and the device's peak memory is read is the program's state freed and
the exact reference run.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from onchip_harness import correct, corpus, drive, graphfile, registry
from onchip_harness.stream import QueryStream

WORK = os.path.join(registry.ROOT, ".onchip_work")
STORE = os.path.join(WORK, "store")      # the disk tier's block file
TRACE = os.path.join(WORK, "trace")      # the traced run's profile
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 4.0     # a traced run profiles the window's last seconds,
TRACE_LEAD = 1.0        # with the profiler started this much earlier
TRACED_SPAN = "onchip.traced"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, counted
    apart for set-up and for the window."""

    def __init__(self):
        self.phase = "setup"
        self.counts = {"setup": 0, "window": 0, "after": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.counts[self.phase] += 1


class Tracer:
    """The profiler of a traced run.  The profiler starts ``TRACE_LEAD``
    seconds before the traced part of the window, which is the window's
    last ``TRACE_SECONDS`` or so, from the first dispatch after that
    lead: the trace stays small, and the device's events are recorded
    by the time the traced part begins.  The traced part ends with the
    window's last answer; writing the trace out waits until the window
    has closed.  ``first`` is the index of the first dispatch inside the
    traced part.  Passed to a loop as its hooks."""

    def __init__(self, seconds: float):
        self.span_at = max(seconds - TRACE_SECONDS, TRACE_LEAD)
        self.first: int | None = None
        self._started = False
        self._span = None

    def tick(self, elapsed: float, dispatched: int) -> None:
        if not self._started and elapsed >= self.span_at - TRACE_LEAD:
            shutil.rmtree(TRACE, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # no event per Python call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE, profiler_options=opts)
            self._started = True
        elif self._started and self.first is None \
                and elapsed >= self.span_at:
            self._span = TraceAnnotation(TRACED_SPAN)
            self._span.__enter__()
            self.first = dispatched

    def end(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def stop(self) -> None:
        if not self._started:
            return
        self.end()
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace written in {time.perf_counter() - t0:.3f} s")


@dataclasses.dataclass
class Context:
    """What a metric reader reads (``end_to_end/<m>.py``,
    ``layers/<m>.py``: ``read(ctx) -> float | None``)."""
    window: drive.Window
    setup_s: float
    recall: float
    trace: dict | None
    peaks: dict
    config: dict
    traffic: dict
    traced_from: int | None = None

    def lanes(self, field: str, traced: bool = False) -> np.ndarray | None:
        """A ``SearchStats`` field over every real lane of the window
        (with ``traced``, of the dispatches inside the trace), or None
        where the loop kept no stats or the tier has no such count."""
        stats = self.window.stats
        if traced:
            stats = stats[self.traced_from:] if self.trace else []
        parts = [getattr(s, field) for s in stats]
        if not parts or any(p is None for p in parts):
            return None
        return np.concatenate([np.asarray(p) for p in parts])


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    checks: dict
    context: Context
    memory_peak_bytes: int


def setup(config: dict, traffic: dict, seed: int, adj_medoid=None):
    """(db, frontend, corpus, stream, k) ready for the window."""
    from repro import db as catapultdb
    from repro.db import IndexSpec

    data = corpus.make_corpus(config["corpus"])
    sha = corpus.digest(data.vectors)
    if sha != config["corpus_sha256"]:
        raise graphfile.GraphMismatch(
            f"corpus of {config['name']} hashes to {sha}, the configuration "
            f"names {config['corpus_sha256']}")
    if adj_medoid is None:
        adj_medoid = graphfile.load(
            os.path.join(registry.HERE, config["graph"]), sha)
    spec = dict(config["spec"])
    if spec["tier"] != "ram":
        shutil.rmtree(STORE, ignore_errors=True)
        os.makedirs(STORE)
        spec["path"] = os.path.join(STORE, f"{config['name']}.ctpl")
    spec = IndexSpec(**spec)
    db = catapultdb.create(spec, data.vectors, prebuilt=adj_medoid)
    if spec.tier != "ram":
        # a deployment serves a block file that is already on disk: let
        # no write-back of the fresh file fall into the window
        os.sync()
    batch = traffic["batch"]
    db.warm((batch,))
    fe = db.serve(max_batch=batch, k=spec.k, beam_width=spec.beam_width)
    stream = QueryStream(data, traffic, seed)
    loop(traffic).warm(fe, stream, traffic)
    return db, fe, data, stream, spec.k


def loop(traffic: dict):
    """The module of the mix's loop, ``loops/<loop>.py``."""
    return registry.load_module("loops", traffic["loop"])


def window(fe, stream, traffic: dict, seconds: float, k: int,
           hooks=drive.NoHooks()) -> drive.Window:
    return loop(traffic).run(fe, stream, traffic, seconds, k, hooks)


def memory_peak_bytes() -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(cell: str, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, t_start: float, limits: dict, peaks: dict,
        adj_medoid=None) -> Outcome:
    """One run; ``t_start`` is when the process began, so that set-up
    counts start-up, loading and compiling."""
    import trace_reduce

    counter = CompileCounter()
    db, fe, data, stream, k = setup(config, traffic, seed, adj_medoid)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.6f}; programs compiled or loaded in set-up: "
        f"{counter.counts['setup']}")

    tracer = Tracer(seconds) if trace else None
    counter.phase = "window"
    try:
        win = window(fe, stream, traffic, seconds, k,
                     tracer or drive.NoHooks())
    finally:
        counter.phase = "after"
        if tracer:
            tracer.stop()
    log(f"compilations in window: {counter.counts['window']}")
    log(f"window: {win.n_queries} queries in {win.seconds:.6f} s, "
        f"{win.batches} dispatches")
    slow = win.calls[np.argsort(win.calls[:, 1])[::-1][:5]]
    log(f"frontend calls: {len(win.calls)}, median "
        f"{np.median(win.calls[:, 1]) * 1e3:.3f} ms; slowest (start s, ms, "
        f"queries): " + ", ".join(f"({a:.3f}, {b * 1e3:.3f}, {int(c)})"
                                  for a, b, c in slow))
    peak = memory_peak_bytes()
    reduced = None
    if trace:
        t0 = time.perf_counter()
        reduced = trace_reduce.reduce_dir(TRACE)
        log(f"trace reduction seconds {time.perf_counter() - t0:.3f}")

    db.close()
    del fe, db
    gc.collect()
    shutil.rmtree(STORE, ignore_errors=True)

    t0 = time.perf_counter()
    found = correct.numbers(data.vectors, win.blocks, win.ids, win.dists,
                            win.answered, k)
    log(f"reference and check seconds {time.perf_counter() - t0:.3f}")
    ok, checks = correct.judge(found, limits)
    ctx = Context(window=win, setup_s=setup_s, recall=found["recall_at_10"],
                  trace=reduced, peaks=peaks, config=config, traffic=traffic,
                  traced_from=tracer.first if tracer else None)
    return Outcome(correct=ok, attempted=win.n_queries,
                   failed=found["unanswered"], checks=checks, context=ctx,
                   memory_peak_bytes=peak)
