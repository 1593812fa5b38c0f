"""The on-chip benchmark's harness, at tiny sizes on the CPU.

Discovery by name, generators deterministic in their seed, the stored
graph's hash, the trace reduction on a recorded chip trace, the result
line's schema and the refusal of a CPU.  Then whole runs of a tiny cell
with the chip check skipped: a sound run is correct, the control (the
reference one precision below) is not, and neither is a run whose timed
path is broken underneath in any of the ways a search cell can break.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import run  # noqa: E402
import trace_reduce  # noqa: E402
from onchip_harness import (cell, correct, corpus, drive,  # noqa: E402
                            graphfile, reference, registry)
from onchip_harness.stream import QueryStream  # noqa: E402

BENCH = registry.benchmark()
BIG_SEED = 2**31 + 12345

TINY = {
    "name": "tiny",
    "spec": {"tier": "ram", "mode": "catapult", "dim": 32, "degree": 16,
             "alpha": 1.2, "build_beam": 32, "build_batch": 128,
             "n_bits": 4, "bucket_capacity": 8, "beam_width": 16, "k": 10,
             "seed": 0, "hop_backend": "unfused"},
    "corpus": {"generator": "medrag_zipf", "rows": 800, "dim": 32,
               "intrinsic_dim": 8, "clusters": 16, "seed": 1,
               "lift_seed": 1},
}
# what a tiny run is held to: sound tiny runs read a miss_rate up to
# 0.016 (the disk tier with 8 PQ subspaces) and a dist_err of about 4e-7;
# dist_err's limit is the cells' own
TINY_LIMITS = {"unanswered": {"limit": 0}, "miss_rate": {"limit": 0.05},
               "dist_err": {"limit": 2e-4}}


# ---------------------------------------------------------------- by name
def test_every_part_of_every_cell_is_found_by_name():
    names = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in names
        config = registry.load_config(w["config"])
        assert config["name"] == w["config"]
        traffic = registry.load_traffic(w["traffic"])
        assert callable(cell.loop(traffic).run)
        assert callable(registry.load_module("queries",
                                             traffic["queries"]).draw)
        assert callable(registry.load_module(
            "generators", config["corpus"]["generator"]).make)
        assert set(correct.load_limits(w["name"], HERE)) >= set(
            correct.NAMES)
    for m in BENCH["end_to_end"]:
        assert callable(registry.load_reader("end_to_end", m["name"]))
    for m in BENCH["per_layer"]:
        assert callable(registry.load_reader("layers", m["name"]))
        assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}


def test_a_missing_part_is_an_error_that_names_it():
    with pytest.raises(KeyError, match="no-such-mix"):
        registry.load_traffic("no-such-mix")
    with pytest.raises(KeyError, match="no-such-metric"):
        registry.load_reader("layers", "no-such-metric")
    with pytest.raises(KeyError, match="no-such-cell"):
        registry.cell("no-such-cell", BENCH)


def test_metrics_for_a_cell_follow_their_workloads_lists():
    specs = [{"name": "a", "workloads": ["x"]}, {"name": "b"},
             {"name": "c", "workloads": ["y"]}]
    assert [m["name"] for m in registry.metrics_for("x", specs)] == ["a", "b"]


def test_config_files_match_benchmark_entries():
    for c in BENCH["configs"]:
        config = registry.load_config(c["name"])
        assert c["file"] == f"benchmarks/onchip/configs/{c['name']}.json"
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert config["spec"]["dim"] == config["corpus"]["dim"]
        for cut in config["reduced"].values():      # as it is run
            group, key = cut["at"].split(".")
            assert config[group][key] == cut["used"] != cut["published"]


def test_an_unknown_generator_kind_or_loop_is_an_error():
    with pytest.raises(KeyError, match="no-such-generator"):
        corpus.make_corpus(dict(TINY["corpus"], generator="no-such-generator"))
    with pytest.raises(KeyError, match="no-such-kind"):
        QueryStream(None, {"queries": "no-such-kind"}, 1)
    with pytest.raises(KeyError, match="no-such-loop"):
        cell.loop({"loop": "no-such-loop"})


NEW_KIND = '''
import numpy as np


def draw(rng, n, traffic, corpus):
    d = corpus.basis.shape[0]
    v = rng.normal(size=(n, d))
    return (traffic["radius"] * v / np.linalg.norm(v, axis=1,
                                                   keepdims=True)).astype(
        np.float32)
'''


def test_a_new_kind_and_mix_are_found_without_editing_code(
        tmp_path, monkeypatch, tiny_corpus):
    """A mix of a query kind the harness has never seen: two new files in
    a copy of the benchmark's directory, no file edited."""
    copy = tmp_path / "onchip"
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "data", "tests"))
    (copy / "queries" / "sphere.py").write_text(NEW_KIND)
    (copy / "traffic" / "sphere.json").write_text(json.dumps(
        {"queries": "sphere", "radius": 3.0, "loop": "closed",
         "batch": 64, "warmup_batches": 1}))
    monkeypatch.setattr(registry, "HERE", str(copy))
    traffic = registry.load_traffic("sphere")
    q = QueryStream(tiny_corpus, traffic, BIG_SEED).intrinsic(
        np.random.default_rng(0), 50)
    np.testing.assert_allclose(np.linalg.norm(q, axis=1), 3.0, rtol=1e-5)
    assert cell.loop(traffic) is registry.load_module("loops", "closed")


def test_zipf_ranks_follow_the_papers_exponent(tiny_corpus):
    """Zipf(0.8) over ranked topics: rank r drawn with probability
    proportional to r ** -0.8."""
    kind = registry.load_module("queries", "zipf")
    p = kind.rank_probabilities(256, 0.8)
    assert p[0] == pytest.approx(1 / np.sum(np.arange(1, 257) ** -0.8))
    assert 0.08 < p[0] < 0.10 and p[255] > 0.001
    traffic = registry.load_traffic("zipf")
    assert traffic["zipf_s"] == 0.8
    q = kind.draw(np.random.default_rng(0), 20000, traffic, tiny_corpus)
    nearest = np.argmin(((q[:, None, :] - tiny_corpus.centers[None]) ** 2)
                        .sum(-1), axis=1)
    share = np.bincount(nearest, minlength=16)[tiny_corpus.by_rank]
    np.testing.assert_allclose(share / q.shape[0],
                               kind.rank_probabilities(16, 0.8), atol=0.01)


# ---------------------------------------------------------------- seeds
@pytest.fixture(scope="module")
def tiny_corpus():
    return corpus.make_corpus(TINY["corpus"])


@pytest.mark.parametrize("mix", ["zipf", "uniform", "zipf-open"])
def test_streams_are_deterministic_in_their_seed(tiny_corpus, mix):
    traffic = registry.load_traffic(mix)
    a = QueryStream(tiny_corpus, traffic, BIG_SEED)
    b = QueryStream(tiny_corpus, traffic, BIG_SEED)
    c = QueryStream(tiny_corpus, traffic, BIG_SEED + 1)
    np.testing.assert_array_equal(a.batch(3, 64), b.batch(3, 64))
    assert not np.array_equal(a.batch(3, 64), c.batch(3, 64))
    assert not np.array_equal(a.batch(3, 64), a.batch(4, 64))
    np.testing.assert_array_equal(a.arrivals(500.0, 2.0),
                                  b.arrivals(500.0, 2.0))
    t = a.arrivals(500.0, 2.0)
    assert (np.diff(t) >= 0).all() and t[-1] < 2.0 and 800 < t.size < 1200


def test_corpus_is_deterministic_and_lift_orthonormal(tiny_corpus):
    again = corpus.make_corpus(TINY["corpus"])
    assert corpus.digest(again.vectors) == corpus.digest(tiny_corpus.vectors)
    b = tiny_corpus.basis.astype(np.float64)
    np.testing.assert_allclose(b @ b.T, np.eye(b.shape[0]), atol=1e-6)


def test_stored_graph_was_built_on_a_fresh_corpus():
    for c in BENCH["configs"]:
        config = registry.load_config(c["name"])
        fresh = corpus.digest(corpus.make_corpus(config["corpus"]).vectors)
        assert fresh == config["corpus_sha256"]
        adj, medoid = graphfile.load(
            os.path.join(HERE, config["graph"]), fresh)
        assert adj.shape == (config["corpus"]["rows"],
                             config["spec"]["degree"])
        assert 0 <= medoid < adj.shape[0] and adj.max() < adj.shape[0]
        with pytest.raises(graphfile.GraphMismatch):
            graphfile.load(os.path.join(HERE, config["graph"]), "0" * 64)


# ---------------------------------------------------------------- trace
def test_trace_reduce_on_a_recorded_chip_trace():
    """An excerpt of a traced medrag-ram.zipf window on a TPU v5 lite:
    the device's longest idle gap in mid-window with 1.5 ms of device
    work on each side, and the numbers it reduced to there."""
    with open(os.path.join(HERE, "tests", "data",
                           "trace_events.json")) as f:
        rec = json.load(f)
    got = trace_reduce.reduce(rec["events"])
    for key, want in rec["expect"].items():
        assert got[key] == pytest.approx(want, rel=1e-9), key
    assert dict(got["breakdown"]["idle_gaps"]) == pytest.approx(
        rec["expect_gaps"], rel=1e-9)
    assert got["module_s"] == pytest.approx(rec["expect_modules"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert any(k.startswith("jit__search_") for k in got["module_s"])
    assert len(got["breakdown"]["device_ops"]) <= trace_reduce.TOP


@pytest.mark.parametrize("text,want", [
    ("%fusion.93 = f32[65536]{0:T(1024)S(1)} fusion(f32[4096,80]{1,0:T(8,"
     "128)S(1)} %a, s32[65536]{0:T(1024)S(1)} %b), kind=kCustom",
     "%fusion.93 fusion f32[65536]"),
    ("%while.37 = (s32[4096,16]{1,0:T(8,128)}, pred[4096]{0:T(1024)(128)"
     "(4,1)}) while((s32[4096,16]{1,0:T(8,128)}) %tuple.1), condition=%c",
     "%while.37 while"),
    ("jit__search_catapult(14193684865064782771)",
     "jit__search_catapult(14193684865064782771)"),
])
def test_op_names_keep_instruction_opcode_and_shape(text, want):
    assert trace_reduce.op_name(text) == want


def test_trace_reduce_by_hand():
    ev = {"devices": [{"plane": "/device:TPU:0",
                       "ops": [("a", 10, 20), ("b", 15, 30), ("a", 60, 70)],
                       "modules": [("jit__search_x(3)", 10, 70)]}],
          "host": [("onchip.traced", 0, 100), ("onchip.record", 30, 60),
                   ("onchip.fe_search", 0, 100)]}
    got = trace_reduce.reduce(ev)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx(30e-9)
    assert got["idle_share"] == pytest.approx(0.7)
    assert got["module_s"] == {"jit__search_x": pytest.approx(60e-9)}
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert gaps == {"onchip.fe_search": pytest.approx(40e-9),
                    "onchip.record": pytest.approx(30e-9)}
    ev["host"].append(("runtime.host_to_device", 65, 68))
    ev["devices"][0]["ops"].append(("c", 66, 80))      # overlaps "a"
    got = trace_reduce.reduce(ev)
    assert got["busy_s"] == pytest.approx(40e-9)
    assert dict(got["breakdown"]["idle_gaps"]) == {
        "onchip.fe_search": pytest.approx(30e-9),
        "onchip.record": pytest.approx(30e-9)}
    assert trace_reduce.reduce({"devices": [], "host": []}) is None


def test_trace_load_finds_the_harness_spans(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("onchip.traced"):
        jnp.ones(8).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    ev = trace_reduce.load(str(tmp_path))
    assert [n for n, _, _ in ev["host"]] == ["onchip.traced"]
    assert trace_reduce.reduce(ev) is None      # the CPU has no TPU plane


# ---------------------------------------------------------------- schema
def _fake_outcome(trace):
    win = type("W", (), {})()
    ctx = cell.Context(window=win, setup_s=1.5, recall=0.99,
                       trace=trace, peaks={}, config={}, traffic={})
    return cell.Outcome(correct=True, attempted=10, failed=0,
                        checks={"miss_rate": {"value": 0.0, "limit": 0.1}},
                        context=ctx, memory_peak_bytes=123)


def test_result_line_schema(monkeypatch):
    monkeypatch.setattr(run, "metrics",
                        lambda kind, specs, name, ctx: {"x": {
                            "value": 1.0, "unit": kind}})
    line = run.result_line(_fake_outcome(None), BENCH, "medrag-ram.zipf",
                           trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["metrics"]["x"]["unit"] == "end_to_end"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    red = {"busy_s": 1.0, "window_s": 2.0,
           "breakdown": {"device_ops": [], "idle_gaps": []}}
    line = run.result_line(_fake_outcome(red), BENCH, "medrag-ram.zipf",
                           trace=True)
    assert list(line)[-1] == "checks" and "breakdown" in line
    assert line["device"]["busy_s"] == 1.0
    assert line["device"]["window_s"] == 2.0
    json.dumps(line)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in registry.metrics_for(
            w["name"], BENCH["end_to_end"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.metrics_for(w["name"], BENCH["per_layer"])


# ---------------------------------------------------------------- refusal
def test_run_refuses_a_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    rc = run.main(["--workload", "medrag-ram.zipf", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == run.NO_CHIP and out.out == ""
    assert "no TPU" in out.err and "'cpu'" in out.err


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    p = subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload",
         "medrag-ram.zipf", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0 and p.stdout == ""


# ---------------------------------------------------------------- runs
@pytest.fixture(scope="module")
def tiny_graph(tiny_corpus):
    from repro.core.vamana import VamanaParams, build_vamana
    s = TINY["spec"]
    return build_vamana(tiny_corpus.vectors, VamanaParams(
        max_degree=s["degree"], build_beam=s["build_beam"],
        batch=s["build_batch"], alpha=s["alpha"], seed=s["seed"]))


def _tiny_run(tiny_corpus, tiny_graph, tier="ram", mix="zipf", batch=64,
              seconds=1.0, trace=False):
    config = json.loads(json.dumps(TINY))
    config["corpus_sha256"] = corpus.digest(tiny_corpus.vectors)
    config["spec"]["tier"] = tier
    if tier == "disk":
        config["spec"].update(pq=8, cache_frames=128)
    traffic = registry.load_traffic(mix)
    traffic.update(batch=batch, warmup_batches=2)
    if traffic["loop"] == "open":
        traffic["rate"] = 1000.0
    return cell.run("tiny", config, traffic, BIG_SEED, seconds, trace,
                    time.perf_counter(), TINY_LIMITS, {},
                    adj_medoid=tiny_graph)


@pytest.mark.parametrize("tier,mix", [
    ("ram", "zipf"), ("disk", "zipf-b128"), ("ram", "uniform"),
    ("ram", "zipf-open")])
def test_a_sound_run_is_correct(tiny_corpus, tiny_graph, tier, mix):
    out = _tiny_run(tiny_corpus, tiny_graph, tier, mix)
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 0
    assert out.checks["dist_err"]["value"] < 1e-5
    ctx = out.context
    assert registry.load_reader("end_to_end", "qps")(ctx) > 0
    assert 0.9 < registry.load_reader("end_to_end", "recall_at_10")(ctx) <= 1


def test_a_traced_run_profiles_the_end_of_its_window(tiny_corpus,
                                                     tiny_graph):
    out = _tiny_run(tiny_corpus, tiny_graph, seconds=cell.TRACE_SECONDS + 1,
                    trace=True)
    assert out.correct, out.checks
    ctx = out.context
    assert 0 < ctx.traced_from < len(ctx.window.stats)
    ev = trace_reduce.load(cell.TRACE)
    (lo, hi), = [(s, e) for n, s, e in ev["host"]
                 if n == cell.TRACED_SPAN]
    searches = [s for n, s, _ in ev["host"]
                if n == "onchip.fe_search" and s >= lo]
    assert len(searches) == len(ctx.window.stats) - ctx.traced_from
    assert ctx.trace is None            # the CPU has no TPU plane
    assert registry.load_reader("layers", "hops_mean")(ctx) > 0
    assert registry.load_reader("layers", "device_idle_share")(ctx) is None


def test_the_control_is_not_correct(tiny_corpus):
    stream = QueryStream(tiny_corpus, registry.load_traffic("zipf"),
                         BIG_SEED)
    blocks = drive.stream_blocks(stream, 0, 256, 2048)
    qs = np.concatenate([q for _, _, q in blocks()])
    ids, dists = reference.exact_knn(tiny_corpus.vectors, qs, 10,
                                     dtype=jnp.bfloat16)
    found = correct.numbers(tiny_corpus.vectors, blocks, ids, dists,
                            np.ones(qs.shape[0], bool), 10)
    ok, checks = correct.judge(found, TINY_LIMITS)
    assert not ok and checks["dist_err"]["value"] > 1e-3, checks
    for cell_name in (w["name"] for w in BENCH["workloads"]):
        ok, _ = correct.judge(found, correct.load_limits(cell_name, HERE))
        assert not ok, cell_name


def test_the_window_checks_the_queries_it_served(tiny_corpus, tiny_graph,
                                                 monkeypatch):
    """The window keeps no queries: the check makes them again from the
    stream, and they are the ones the frontend was handed."""
    from repro.serving.engine import VectorSearchFrontend
    served = []
    orig = VectorSearchFrontend.search

    def search(self, queries, *a, **kw):
        served.append(np.array(queries))
        return orig(self, queries, *a, **kw)
    monkeypatch.setattr(VectorSearchFrontend, "search", search)
    config = json.loads(json.dumps(TINY))
    config["corpus_sha256"] = corpus.digest(tiny_corpus.vectors)
    traffic = dict(registry.load_traffic("zipf"), batch=64, warmup_batches=2)
    db, fe, data, stream, k = cell.setup(config, traffic, BIG_SEED,
                                         tiny_graph)
    del served[:]
    win = cell.window(fe, stream, traffic, 0.5, k)
    db.close()
    made = np.concatenate([q for _, _, q in win.blocks()])
    np.testing.assert_array_equal(made, np.concatenate(served))
    assert made.shape[0] == win.n_queries


def _fault_state_unchanged(monkeypatch):
    """The traversal's step leaves its state as it was."""
    monkeypatch.setattr(jax.lax, "while_loop", lambda cond, body, s: s)


def _wrap_search(monkeypatch, tier, alter):
    from repro.core.engine import VectorSearchEngine
    from repro.store.io_engine import DiskVectorSearchEngine
    cls = DiskVectorSearchEngine if tier == "disk" else VectorSearchEngine
    orig = cls.search

    def search(self, queries, *a, publish_mask=None, **kw):
        ids, dists, stats = orig(self, queries, *a,
                                 publish_mask=publish_mask, **kw)
        real = (queries.shape[0] if publish_mask is None
                else int(np.sum(publish_mask)))
        return alter(np.array(ids), np.array(dists), real) + (stats,)
    monkeypatch.setattr(cls, search.__name__, search)


def _fault_half_batch(monkeypatch, tier):
    """Half of each batch's real lanes left out: they get the other
    half's answers."""
    def alter(ids, dists, real):
        h = real // 2
        ids[h:2 * h], dists[h:2 * h] = ids[:h], dists[:h]
        return ids, dists
    _wrap_search(monkeypatch, tier, alter)


def _fault_answer_altered(monkeypatch, tier):
    """One answer of each batch altered where it is produced."""
    def alter(ids, dists, real):
        ids[0, 0] = (ids[0, 0] + 1) % TINY["corpus"]["rows"]
        return ids, dists
    _wrap_search(monkeypatch, tier, alter)


@pytest.mark.parametrize("fault,tier,mix", [
    ("state_unchanged", "ram", "zipf"), ("half_batch", "ram", "zipf"),
    ("half_batch", "disk", "zipf-b128"), ("half_batch", "ram", "zipf-open"),
    ("answer_altered", "ram", "zipf"), ("answer_altered", "disk", "zipf-b128"),
    ("answer_altered", "ram", "uniform")])
def test_a_broken_timed_path_is_not_correct(tiny_corpus, tiny_graph,
                                            monkeypatch, fault, tier, mix):
    jax.clear_caches()
    try:
        if fault == "state_unchanged":
            _fault_state_unchanged(monkeypatch)
        elif fault == "half_batch":
            _fault_half_batch(monkeypatch, tier)
        else:
            _fault_answer_altered(monkeypatch, tier)
        out = _tiny_run(tiny_corpus, tiny_graph, tier, mix)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not out.correct, out.checks
