"""The split of the device's idle time by the program's spans
(``span_reduce``) and the per-layer metrics that read it, on the CPU.

On a recorded excerpt of a chip trace with the program's spans, and by
hand: the idle time inside programs and under each span adds up to the
window less the busy time, a gap that two spans share is split between
them exactly, and each metric reads None without a trace.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402
from onchip_harness import cell, registry  # noqa: E402

DATA = os.path.join(HERE, "tests", "data")
NEW_METRICS = ("idle_frontend", "idle_frontend.open", "idle_engine",
               "idle_engine.open", "idle_disk", "idle_in_program",
               "idle_in_program.open", "submit_wait_ms.open")


def _fixture(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _idle_adds_up(got):
    split = got["idle_in_program_s"] + sum(got["idle_by_span"].values())
    assert split == pytest.approx(got["window_s"] - got["busy_s"],
                                  abs=1e-9, rel=0)


# ---------------------------------------------------------------- by hand
HAND = {
    "devices": [{"plane": "/device:TPU:0",
                 "ops": [("a", 10, 20), ("b", 60, 70)],
                 "modules": [("jit__search_x(1)", 10, 30)]}],
    "host": [("onchip.traced", 0, 100), ("onchip.fe_flush", 0, 100),
             ("catapultdb.frontend.flush", 5, 95),
             ("catapultdb.engine.readback", 25, 45),
             ("catapultdb.frontend.unpack", 45, 55),
             ("catapultdb.frontend.pack", 55, 65)],
    "args": [("catapultdb.frontend.flush", 5, 95,
              {"tickets": 3.0, "chunks": 1.0, "wait_ms_sum": 1.5,
               "wait_ms_max": 0.75}),
             ("catapultdb.frontend.flush", 150, 160,
              {"tickets": 9.0, "chunks": 1.0, "wait_ms_sum": 90.0,
               "wait_ms_max": 10.0})],
}


def test_span_reduce_by_hand():
    got = span_reduce.reduce(HAND)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx(20e-9)
    # 20-30: the program runs, its operations do not: no span's doing
    # (though the readback covers 25-30)
    assert got["idle_in_program_s"] == pytest.approx(10e-9)
    # the gap 20-60 is split exactly over the spans it crosses: where
    # trace_reduce gives all 40 ns to the span over its middle
    assert got["idle_by_span"] == pytest.approx({
        "onchip.fe_flush": 10e-9, "catapultdb.frontend.flush": 30e-9,
        "catapultdb.engine.readback": 15e-9,
        "catapultdb.frontend.unpack": 10e-9,
        "catapultdb.frontend.pack": 5e-9}, abs=1e-15)
    _idle_adds_up(got)
    gaps = dict(trace_reduce.reduce(HAND)["breakdown"]["idle_gaps"])
    assert gaps["catapultdb.engine.readback"] == pytest.approx(40e-9)
    # the flush that starts after the window is left out
    assert got["flushes"] == [{"tickets": 3.0, "chunks": 1.0,
                               "wait_ms_sum": 1.5, "wait_ms_max": 0.75,
                               "span": "catapultdb.frontend.flush"}]
    assert span_reduce.reduce({"devices": [], "host": [], "args": []}) \
        is None
    assert span_reduce.reduce(None) is None


def test_a_span_with_no_idle_time_reads_zero():
    ev = json.loads(json.dumps(HAND))
    ev["host"].append(["catapultdb.disk.fetch", 12, 18])     # device busy
    got = span_reduce.reduce(ev)
    assert got["idle_by_span"]["catapultdb.disk.fetch"] == 0.0
    _idle_adds_up(got)


# ---------------------------------------------------------------- recorded
def test_span_reduce_on_the_recorded_chip_trace():
    """The window, busy time and split as trace_reduce and the
    recorded expectation have them, on the trace that has no program
    spans."""
    rec = _fixture("trace_events.json")
    got = span_reduce.reduce(rec["events"])
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(rec["expect"][key], rel=1e-9)
    _idle_adds_up(got)
    assert got["flushes"] == []
    assert not any(k.startswith(span_reduce.PROGRAM_PREFIX)
                   for k in got["idle_by_span"])


def test_span_reduce_on_a_recorded_trace_with_program_spans():
    rec = _fixture("trace_program_spans.json")
    got = span_reduce.reduce(rec["events"])
    want = rec["expect"]
    for key in ("window_s", "busy_s", "idle_in_program_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-12), key
    assert got["idle_by_span"] == pytest.approx(want["idle_by_span"],
                                                rel=1e-12)
    assert got["flushes"] == want["flushes"]
    assert len(got["flushes"]) == 2
    _idle_adds_up(got)
    base = trace_reduce.reduce(rec["events"])
    assert got["window_s"] == base["window_s"]
    assert got["busy_s"] == base["busy_s"]
    assert any(k.startswith("catapultdb.engine.")
               for k in got["idle_by_span"])
    assert any(k.startswith("catapultdb.frontend.")
               for k in got["idle_by_span"])


def test_load_keeps_the_program_spans_and_their_arguments(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("onchip.traced"):
        with jax.profiler.TraceAnnotation("catapultdb.frontend.flush") as sp:
            jnp.ones(8).block_until_ready()
            time.sleep(0.01)
            sp.set_metadata(tickets=4, wait_ms_sum=2.5)
        with jax.profiler.TraceAnnotation("runtime.not_a_span"):
            pass
    jax.profiler.stop_trace()
    ev = span_reduce.load(str(tmp_path))
    assert [n for n, _, _ in ev["host"]] == ["onchip.traced",
                                             "catapultdb.frontend.flush"]
    (name, s, e, args), = ev["args"]
    assert name == "catapultdb.frontend.flush" and e > s
    assert args == {"tickets": 4.0, "wait_ms_sum": 2.5}
    assert span_reduce.reduce(ev) is None     # the CPU has no TPU plane


# ---------------------------------------------------------------- readers
class _Ctx:
    def __init__(self, trace):
        self.trace = trace


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_read_none_without_a_trace(metric):
    assert registry.load_reader("layers", metric)(_Ctx(None)) is None


def _by_prefix(got, prefix):
    return 100 * sum(v for k, v in got["idle_by_span"].items()
                     if k.startswith(prefix)) / got["window_s"]


@pytest.mark.parametrize("events,want", [
    (HAND, {"idle_frontend": 45.0, "idle_frontend.open": 45.0,
            "idle_engine": 15.0, "idle_engine.open": 15.0,
            "idle_disk": None, "idle_in_program": 10.0,
            "idle_in_program.open": 10.0, "submit_wait_ms.open": 0.5}),
    ("trace_program_spans.json", None),
])
def test_new_metrics_read_the_split(monkeypatch, events, want):
    if isinstance(events, str):
        events = _fixture(events)["events"]
    got = span_reduce.reduce(events)
    if want is None:                  # the recorded excerpt, by hand
        fl = [f for f in got["flushes"]
              if f["span"] == "catapultdb.frontend.flush"]
        front = _by_prefix(got, "catapultdb.frontend.")
        engine = _by_prefix(got, "catapultdb.engine.")
        inside = 100 * got["idle_in_program_s"] / got["window_s"]
        want = {"idle_frontend": front, "idle_frontend.open": front,
                "idle_engine": engine, "idle_engine.open": engine,
                "idle_disk": None, "idle_in_program": inside,
                "idle_in_program.open": inside,
                "submit_wait_ms.open": (
                    sum(f["wait_ms_sum"] for f in fl)
                    / sum(f["tickets"] for f in fl)) if fl else None}
    monkeypatch.setattr(span_reduce, "reduce_dir",
                        lambda d: span_reduce.reduce(events))
    ctx = _Ctx({"window_s": got["window_s"]})
    for metric in NEW_METRICS:
        value = registry.load_reader("layers", metric)(ctx)
        if want[metric] is None:
            assert value is None, metric
        else:
            assert value == pytest.approx(want[metric], rel=1e-12), metric


def test_readers_read_this_runs_trace(monkeypatch):
    seen = []
    monkeypatch.setattr(span_reduce, "reduce_dir",
                        lambda d: seen.append(d) or span_reduce.reduce(HAND))
    registry.load_reader("layers", "idle_engine")(_Ctx({}))
    assert seen == [cell.TRACE]


def test_new_metrics_are_listed_where_they_read():
    bench = registry.benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for metric in NEW_METRICS:
        m = per_layer[metric]
        assert m["workloads"], metric
        moves = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moves["workloads"]), metric
