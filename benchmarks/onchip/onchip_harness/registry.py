"""Find a cell's parts by name.

Data: BENCHMARK.json at the checkout root, ``configs/<config>.json``,
``traffic/<mix>.json`` and ``limits/<cell>.json``.  Code, one small
module per name beside the harness:

* ``generators/<name>.py``: ``make(params) -> Corpus``, the corpus a
  configuration's ``corpus.generator`` names;
* ``queries/<kind>.py``: ``draw(rng, n, traffic, corpus)``, the
  intrinsic queries of a mix's ``queries`` kind;
* ``loops/<loop>.py``: ``warm(fe, stream, traffic)`` and ``run(fe,
  stream, traffic, seconds, k, hooks) -> Window``, how a mix's ``loop``
  drives the frontend;
* ``end_to_end/<metric>.py`` and ``layers/<metric>.py``: ``read(ctx)``.

A new configuration, mix, query kind, loop or metric is a new file plus
a new entry; no file that is there changes.  An unknown name is an
error that names the file it looked for.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))

_modules: dict[str, object] = {}


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.exists(path):
        raise KeyError(f"no {kind} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    return _json("configs", name)


def load_traffic(name: str) -> dict:
    return _json("traffic", name)


def cell(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_for(cell_name: str, metrics: list[dict]) -> list[dict]:
    """The metrics of ``metrics`` that this cell reports: those that list
    it under ``workloads``, and those that list no cells at all."""
    return [m for m in metrics
            if cell_name in m.get("workloads", [cell_name])]


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py`` beside the harness, loaded once."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if path in _modules:
        return _modules[path]
    if not os.path.exists(path):
        raise KeyError(f"no {kind} module named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"onchip_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _modules[path] = mod
    return mod


def load_reader(kind: str, metric: str):
    """The ``read(ctx)`` function of ``<kind>/<metric>.py``, where kind
    is ``end_to_end`` or ``layers``."""
    return load_module(kind, metric).read
