"""Reduce a profiler trace of the measured window to the benchmark's numbers.

``load(dir)`` reads the newest ``*.xplane.pb`` under ``dir`` with JAX's
own reader into plain lists of (name, start_ns, end_ns):

* per TPU device: its operations (the ``XLA Ops`` line, each named by its
  HLO instruction and opcode, ``%fusion.93 fusion f32[65536]``) and its
  programs (``XLA Modules``, named by their jit: ``jit__search_catapult``);
* on the host: the harness's own spans (``onchip.*``), among them
  ``onchip.traced``, which bounds the traced part of the window, and the TPU
  runtime's copies between host and device (``runtime.host_to_device``,
  ``runtime.device_to_host``).

``reduce(events)`` turns those lists into the device's busy time and
idle share over the window (busy is the union of operation intervals),
device time by operation and by program, and the idle gaps of the first
device, each given to the innermost host span that covers its middle.
A reduction that finds no window or no device operation returns None:
there is then nothing to read, and no metric is made from it.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PREFIX = "onchip."
RUNTIME_SPANS = {"TransferToDevice": "runtime.host_to_device",
                 "TransferFromDevice": "runtime.device_to_host"}
WINDOW = "onchip.traced"
TOP = 10
# "%fusion.93 = f32[65536]{0:T(1024)} fusion(...)" or, for a tuple
# result, "%while.37 = (s32[4096,16]{...}, ...) while(...)"
_HLO = re.compile(r"^(%[\w.-]+) = (?:(\w+\[[\d,]*\])\S*|\(.*?\)) "
                  r"([a-z][\w-]*)\(")


def op_name(text: str) -> str:
    """An HLO instruction's name, opcode and (single) result shape,
    without layouts or operands; any other text as it is."""
    m = _HLO.match(text)
    if not m:
        return text
    return " ".join(p for p in (m[1], m[3], m[2]) if p)


def _host_span(name: str) -> str | None:
    if name.startswith(HOST_PREFIX):
        return name
    return next((v for k, v in RUNTIME_SPANS.items() if k in name), None)


def load(trace_dir: str) -> dict | None:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, host, names = [], [], {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"plane": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if not key:
                    continue
                for e in line.events:
                    full = e.name
                    short = names.get(full)
                    if short is None:
                        short = names[full] = op_name(full)
                    dev[key].append((short, e.start_ns, e.end_ns))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = _host_span(e.name)
                    if span:
                        host.append((span, e.start_ns, e.end_ns))
    return {"devices": devices, "host": host}


def _union(intervals, lo, hi) -> np.ndarray:
    """Merged intervals clipped to [lo, hi], sorted: an (n, 2) array."""
    iv = np.asarray(list(intervals), np.int64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not iv.size:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    start = np.ones(len(iv), bool)
    start[1:] = iv[1:, 0] > reach[:-1]
    first = np.flatnonzero(start)
    last = np.append(first[1:], len(iv)) - 1
    return np.stack([iv[first, 0], reach[last]], axis=1)


def _innermost(spans, points) -> list:
    """For each point, the name of the shortest span (name, start, end)
    that covers it, or "none"."""
    cuts = np.unique([t for _, s, e in spans for t in (s, e)])
    if not cuts.size:
        return ["none"] * len(points)
    labels = ["none"] + sorted({n for n, _, _ in spans})
    code = {n: i for i, n in enumerate(labels)}
    owner = np.zeros(cuts.size, np.int64)     # segment [cuts[i], cuts[i+1])
    for n, s, e in sorted(spans, key=lambda t: t[1] - t[2]):
        # longest first, so that a shorter span paints over a longer one
        owner[np.searchsorted(cuts, s):np.searchsorted(cuts, e)] = code[n]
    idx = np.searchsorted(cuts, points, side="right") - 1
    return [labels[owner[i]] if i >= 0 else "none" for i in idx]


def _by_name(events, lo, hi, strip=re.compile(r"\(\d+\)$")):
    out: dict[str, float] = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            key = strip.sub("", name)
            out[key] = out.get(key, 0.0) + d * 1e-9
    return out


def _top(d: dict, n: int = TOP) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(events: dict | None) -> dict | None:
    if not events:
        return None
    windows = [(s, e) for n, s, e in events["host"] if n == WINDOW]
    devices = [d for d in events["devices"] if d["ops"]]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    busy = [_union([(s, e) for _, s, e in d["ops"]], lo, hi)
            for d in devices]
    busy_s = sum(float((b[:, 1] - b[:, 0]).sum())
                 for b in busy) * 1e-9 / len(busy)
    op_s, module_s = {}, {}
    for d in devices:
        for k, v in _by_name(d["ops"], lo, hi).items():
            op_s[k] = op_s.get(k, 0.0) + v / len(devices)
        for k, v in _by_name(d["modules"], lo, hi).items():
            module_s[k] = module_s.get(k, 0.0) + v / len(devices)
    edges = np.concatenate([[lo], busy[0].ravel(), [hi]]).reshape(-1, 2)
    edges = edges[edges[:, 1] > edges[:, 0]]
    spans = [t for t in events["host"] if t[0] != WINDOW]
    gaps: dict[str, float] = {}
    owners = _innermost(spans, (edges[:, 0] + edges[:, 1]) // 2)
    for name, (s, e) in zip(owners, edges):
        gaps[name] = gaps.get(name, 0.0) + float(e - s) * 1e-9
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "op_s": op_s, "module_s": module_s,
            "breakdown": {"device_ops": _top(op_s),
                          "idle_gaps": _top(gaps)}}


def reduce_dir(trace_dir: str) -> dict | None:
    return reduce(load(trace_dir))
