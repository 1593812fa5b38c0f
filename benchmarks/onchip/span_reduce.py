"""Split the traced window's device idle time by the program's spans.

``trace_reduce`` gives the device's busy time and the idle gaps of the
first device, each gap by its middle.  This module reads the same
profile again and keeps what that reduction leaves out: the program's
own host spans (``catapultdb.<layer>.<phase>``, written by
``repro.obs.span`` on the thread that dispatches) and their arguments.

``load(dir)`` reads the newest ``*.xplane.pb`` under ``dir`` into

* ``devices``: per TPU device, its operations and its programs (``XLA
  Ops``, ``XLA Modules``) as (start_ns, end_ns);
* ``host``: the harness's spans (``onchip.*``, among them
  ``onchip.traced``, the traced part of the window) and the program's
  (``catapultdb.*``) as (name, start_ns, end_ns);
* ``args``: each program span that carries arguments, as (name,
  start_ns, end_ns, {argument: number}).

``reduce(events)`` splits the first device's idle time in the window
exactly, by intersecting intervals:

* ``idle_in_program_s``: inside a program's run on the device (an
  ``XLA Modules`` interval), between its own operations: the host does
  not cause it;
* ``idle_by_span``: the rest, given to the innermost host span that
  covers it (``none`` outside them all), with 0.0 for a span that is
  innermost somewhere in the window and covers no idle time there.

The two add up to ``window_s - busy_s``, computed as ``trace_reduce``
computes them.  ``flushes`` holds the arguments of the frontend's calls
(``catapultdb.frontend.flush`` and ``.search``) that start in the
window, each with its ``span`` name.  A reduction that finds no window
or no device operation returns None.  The TPU runtime's copies are not
spans of the dispatching thread: the time they take belongs to the span
that waits for them.
"""
from __future__ import annotations

import functools
import glob
import os

import numpy as np

import trace_reduce

PROGRAM_PREFIX = "catapultdb."
SPAN_PREFIXES = (trace_reduce.HOST_PREFIX, PROGRAM_PREFIX)
FLUSH_SPANS = ("catapultdb.frontend.flush", "catapultdb.frontend.search")


def _number(value) -> float | None:
    try:
        return float(value)
    except (TypeError, ValueError):
        return None


def _newest(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(trace_dir: str) -> dict | None:
    path = _newest(trace_dir)
    return None if path is None else _load_file(path)


def _load_file(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, args = [], [], []
    lines = {trace_reduce.OPS_LINE: "ops",
             trace_reduce.MODULES_LINE: "modules"}
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            dev = {"plane": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = lines.get(line.name)
                if key:
                    dev[key] = [(e.start_ns, e.end_ns) for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIXES):
                        continue
                    host.append((e.name, e.start_ns, e.end_ns))
                    if e.name.startswith(PROGRAM_PREFIX):
                        kv = {k: _number(v) for k, v in e.stats}
                        if kv:
                            args.append((e.name, e.start_ns, e.end_ns, kv))
    return {"devices": devices, "host": host, "args": args}


def _paint(spans):
    """The spans' boundaries ``cuts``, and for each segment [cuts[i],
    cuts[i+1]) the index into ``labels`` of the shortest span (name,
    start, end) over it: 0, "none", where no span covers it."""
    cuts = np.unique([t for _, s, e in spans for t in (s, e)])
    labels = ["none"] + sorted({n for n, _, _ in spans})
    code = {n: i for i, n in enumerate(labels)}
    owner = np.zeros(cuts.size, np.int64)
    for n, s, e in sorted(spans, key=lambda t: t[1] - t[2]):
        # longest first, so that a shorter span paints over a longer one
        owner[np.searchsorted(cuts, s):np.searchsorted(cuts, e)] = code[n]
    return cuts, labels, owner


def _inside(iv: np.ndarray, points: np.ndarray) -> np.ndarray:
    """Whether each point lies in one of the sorted, disjoint intervals
    ``iv`` (an (n, 2) array of [start, end))."""
    if not iv.size:
        return np.zeros(points.size, bool)
    i = np.searchsorted(iv[:, 0], points, side="right") - 1
    return (i >= 0) & (points < iv[np.maximum(i, 0), 1])


def _pairs(events) -> list:
    """(start, end) of each event, whether or not it carries a name."""
    return [(t[-2], t[-1]) for t in events]


def reduce(events: dict | None) -> dict | None:
    if not events:
        return None
    windows = [(s, e) for n, s, e in events["host"]
               if n == trace_reduce.WINDOW]
    devices = [d for d in events["devices"] if d["ops"]]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    busy = [trace_reduce._union(_pairs(d["ops"]), lo, hi) for d in devices]
    busy_s = sum(float((b[:, 1] - b[:, 0]).sum())
                 for b in busy) * 1e-9 / len(busy)
    idle = np.concatenate([[lo], busy[0].ravel(), [hi]]).reshape(-1, 2)
    idle = idle[idle[:, 1] > idle[:, 0]].astype(np.int64)
    programs = trace_reduce._union(_pairs(devices[0]["modules"]), lo, hi)
    spans = [t for t in events["host"]
             if t[0] != trace_reduce.WINDOW and t[0].startswith(SPAN_PREFIXES)]
    cuts, labels, owner = _paint(spans)
    # pieces between consecutive boundaries: each lies wholly inside or
    # outside the idle time, a program's run and each span
    points = np.unique(np.concatenate([
        [lo, hi], idle.ravel(), programs.ravel(),
        np.clip(cuts, lo, hi)]).astype(np.int64))
    start, width = points[:-1], np.diff(points)
    is_idle = _inside(idle, start)
    in_program = _inside(programs, start)
    seg = np.searchsorted(cuts, start, side="right") - 1
    code = np.zeros(start.size, np.int64)
    code[seg >= 0] = owner[seg[seg >= 0]]
    by_span = {labels[c]: 0 for c in np.unique(code)}
    host_idle = is_idle & ~in_program
    for c, ns in zip(code[host_idle], width[host_idle]):
        by_span[labels[c]] += int(ns)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_s,
            "idle_in_program_s": int(width[is_idle & in_program].sum())
            * 1e-9,
            "idle_by_span": {k: v * 1e-9 for k, v in by_span.items()},
            "flushes": [dict(kv, span=n)
                        for n, s, _, kv in events.get("args", [])
                        if n in FLUSH_SPANS and lo <= s < hi]}


def reduce_dir(trace_dir: str) -> dict | None:
    path = _newest(trace_dir)
    return None if path is None else _reduce_file(path,
                                                  os.path.getmtime(path))


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, _mtime: float) -> dict | None:
    """One profile reduced once, however many metrics read it."""
    return reduce(_load_file(path))


def of(ctx) -> dict | None:
    """The split for a reader's context: None unless the run was traced
    and ``trace_reduce`` found a window there."""
    if ctx.trace is None:
        return None
    from onchip_harness import cell
    return reduce_dir(cell.TRACE)


def idle_under(ctx, prefix: str) -> float | None:
    """The device's idle time under the spans named ``prefix*``, in % of
    the traced window; None where the program emits no such span."""
    t = of(ctx)
    if t is None:
        return None
    parts = [v for k, v in t["idle_by_span"].items() if k.startswith(prefix)]
    return 100.0 * sum(parts) / t["window_s"] if parts else None
