"""The observability layer (repro.obs): metrics, traces, serving window.

Contracts pinned here:

* the metrics registry: histogram bucket placement + percentile
  estimates on known edges, disabled-mode behaviour (shared no-op
  instrument, no allocation, empty exports), Prometheus text format,
* ``explain=True``: on every tier the returned ``SearchTrace`` carries
  EXACTLY the ids/dists of a plain call on the same frozen state
  (``publish=False`` — observe, never perturb), with the tier's stage
  vocabulary present,
* ``db.metrics()``: facade search counters, the cache collector, the
  warm() per-shape breakdown gauges,
* the frontend rolling window under mixed-k ticketed flushes,
* ``cache_stats`` tier-uniformity (all-zero on RAM, never None).
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from repro import db as catapultdb
from repro.obs import (DEFAULT_MS_EDGES, Histogram, MetricsRegistry,
                       NULL_INSTRUMENT, RollingWindow, TraceRecorder)
from tests.conftest import make_clustered

SPEC = catapultdb.IndexSpec(degree=16, build_beam=32, build_batch=512,
                            seed=0, cache_frames=128)


@pytest.fixture(scope="module")
def data():
    corpus, _, _ = make_clustered(600, 16, 8, seed=3)
    return corpus


@pytest.fixture(scope="module")
def queries(data):
    rng = np.random.default_rng(11)
    return (data[:8] + rng.normal(scale=0.05, size=(8, data.shape[1]))
            ).astype(np.float32)


# ---------------------------------------------------------------- registry
class TestRegistry:
    def test_histogram_bucket_edges(self):
        h = Histogram("h", edges=(1.0, 10.0, 100.0))
        for v in (0.5, 1.0, 5.0, 50.0, 500.0):
            h.observe(v)
        # bucket placement: le=1 gets {0.5, 1.0}, le=10 gets {5.0},
        # le=100 gets {50.0}, overflow gets {500.0}
        assert h.counts == [2, 1, 1, 1]
        assert h.count == 5
        assert h.sum == pytest.approx(556.5)
        # overflow observations report the top edge, not +inf
        assert h.percentile(0.99) == 100.0
        assert 0.0 < h.percentile(0.25) <= 1.0

    def test_histogram_percentile_interpolates(self):
        h = Histogram("h", edges=(10.0, 20.0))
        for _ in range(100):
            h.observe(15.0)          # all in the (10, 20] bucket
        p50 = h.percentile(0.50)
        assert 10.0 < p50 <= 20.0

    def test_histogram_rejects_unsorted_edges(self):
        with pytest.raises(ValueError):
            Histogram("h", edges=(5.0, 1.0))
        with pytest.raises(ValueError):
            Histogram("h", edges=())

    def test_counter_gauge(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        c.inc()
        c.inc(4)
        g = reg.gauge("g")
        g.set(2.5)
        snap = reg.snapshot()
        assert snap["c"] == 5.0 and snap["g"] == 2.5
        # same name resolves to the same instrument
        assert reg.counter("c") is c

    def test_disabled_registry_is_inert(self):
        reg = MetricsRegistry(enabled=False)
        # every instrument is the ONE shared no-op — no allocation
        assert reg.counter("a") is NULL_INSTRUMENT
        assert reg.gauge("b") is NULL_INSTRUMENT
        assert reg.histogram("c") is NULL_INSTRUMENT
        reg.counter("a").inc()
        reg.histogram("c").observe(1.0)
        reg.register_collector(lambda: {"x": 1.0})
        assert reg._counters == {} and reg._histograms == {}
        assert reg._collectors == []
        assert reg.snapshot() == {}
        assert reg.to_prometheus() == ""

    def test_collector_polled_at_snapshot(self):
        reg = MetricsRegistry()
        state = {"v": 1.0}
        reg.register_collector(lambda: {"my_metric": state["v"]})
        assert reg.snapshot()["my_metric"] == 1.0
        state["v"] = 7.0             # pull model: reads current state
        assert reg.snapshot()["my_metric"] == 7.0

    def test_prometheus_text_format(self):
        reg = MetricsRegistry()
        reg.counter("app_reqs_total").inc(3)
        h = reg.histogram("app_ms", edges=(1.0, 10.0))
        h.observe(0.5)
        h.observe(99.0)
        text = reg.to_prometheus()
        assert "# TYPE app_reqs_total counter" in text
        assert "app_reqs_total 3" in text
        assert "# TYPE app_ms histogram" in text
        assert 'app_ms_bucket{le="1"} 1' in text
        # cumulative: the +Inf bucket always equals the total count
        assert 'app_ms_bucket{le="+Inf"} 2' in text
        assert "app_ms_count 2" in text
        # json export round-trips
        assert json.loads(reg.to_json())["app_reqs_total"] == 3.0

    def test_default_edges_sorted(self):
        assert list(DEFAULT_MS_EDGES) == sorted(DEFAULT_MS_EDGES)


# ---------------------------------------------------------------- explain
def _assert_parity_and_stages(db, queries, tier, stages_expected):
    plain = db.search(queries, k=5, publish=False)
    tr = db.search(queries, k=5, publish=False, explain=True)
    # the acceptance criterion: explain OBSERVES the search, identical
    # answer — ids and dists bit-for-bit
    np.testing.assert_array_equal(plain.ids, tr.ids)
    np.testing.assert_array_equal(plain.dists, tr.dists)
    assert tr.tier == tier
    assert tr.batch == queries.shape[0] and tr.k == 5
    seen = {s.name for s in tr.stages}
    assert stages_expected <= seen, (tier, seen)
    assert tr.total_ms > 0.0
    assert all(s.ms >= 0.0 for s in tr.stages)
    # entry vocabulary: every lane classified
    assert set(np.unique(tr.entry)) <= {"catapult", "label_entry", "medoid"}
    assert tr.catapult_used == int(np.asarray(tr.stats.used).sum())
    return tr


class TestExplain:
    def test_ram_parity(self, data, queries):
        db = catapultdb.create(SPEC, data)
        tr = _assert_parity_and_stages(db, queries, "ram",
                                       {"route", "rerank"})
        assert tr.blocks_read is None      # no disk under this tier
        assert tr.shards == []

    def test_disk_parity(self, data, queries, tmp_path):
        spec = dataclasses.replace(SPEC, tier="disk",
                                   path=str(tmp_path / "e.ctpl"))
        db = catapultdb.create(spec, data)
        tr = _assert_parity_and_stages(db, queries, "disk",
                                       {"route", "fetch", "rerank"})
        assert tr.blocks_read is not None
        db.close()

    def test_sharded_parity(self, data, queries, tmp_path):
        spec = dataclasses.replace(SPEC, tier="sharded", n_shards=2,
                                   path=str(tmp_path / "e.d"))
        db = catapultdb.create(spec, data)
        tr = _assert_parity_and_stages(
            db, queries, "sharded",
            {"scatter", "merge", "route", "fetch", "rerank"})
        # each shard contributed its own child span set
        assert len(tr.shards) == 2
        for sh in tr.shards:
            assert {s.name for s in sh["stages"]} >= {"route", "fetch"}
        # top-level route/fetch/rerank are critical-path maxima over the
        # overlapped shards — each must equal SOME shard's stage time
        for name in ("route", "fetch", "rerank"):
            per_shard = [sum(s.ms for s in sh["stages"] if s.name == name)
                         for sh in tr.shards]
            assert tr.stage_ms(name) == pytest.approx(max(per_shard))
        db.close()

    @pytest.mark.parametrize("tier", ["ram", "disk"])
    def test_publish_rounds_count_the_busiest_bucket(self, data, queries,
                                                     tier, tmp_path):
        """``publish_rounds`` is the busiest bucket's publishing lanes,
        counted here from the same hashes; masked lanes (the frontend's
        padding, ``publish=False``) count in no bucket."""
        from repro.core import lsh as lsh_mod
        spec = dataclasses.replace(SPEC, tier=tier,
                                   path=str(tmp_path / "p.ctpl"))
        db = catapultdb.create(spec, data)
        q = np.concatenate([queries, queries[:5], queries[:2]])
        hashes = np.asarray(lsh_mod.hash_codes(db.backend._cat.lsh, q))

        def want(mask):
            return int(np.bincount(hashes[mask]).max(initial=0))

        everyone = np.ones(q.shape[0], bool)
        d = db.search(q, k=5, explain=True).to_dict()
        assert d["lanes_published"] == q.shape[0]
        assert d["publish_rounds"] == want(everyone) >= 3
        d = db.search(q, k=5, publish=False, explain=True).to_dict()
        assert d["lanes_published"] == d["publish_rounds"] == 0
        padded = everyone.copy()
        padded[:2] = padded[-4:] = False
        _, _, st = db.backend.search(q, k=5, publish_mask=padded,
                                     trace=TraceRecorder())
        assert st.lanes_published == padded.sum()
        assert st.publish_rounds == want(padded) < want(everyone)
        # the served path does not read the counts back
        assert db.search(q, k=5).stats.publish_rounds is None
        db.close()

    def test_trace_to_dict_is_json_ready(self, data, queries):
        db = catapultdb.create(SPEC, data)
        tr = db.search(queries, k=3, publish=False, explain=True)
        d = json.loads(json.dumps(tr.to_dict()))
        assert d["tier"] == "ram" and d["k"] == 3
        assert "route" in d["stages_ms"]

    def test_explain_composes_with_search_request(self, data, queries):
        db = catapultdb.create(SPEC, data)
        req = catapultdb.SearchRequest(queries=queries, k=4, publish=False)
        tr = db.search(req, explain=True)    # facade-level, no conflict
        assert tr.k == 4
        # but request-field keywords still conflict with a request
        with pytest.raises(TypeError):
            db.search(req, k=4)


# ---------------------------------------------------------------- metrics()
class TestDatabaseMetrics:
    def test_search_counters_and_cache_collector(self, data, queries,
                                                 tmp_path):
        spec = dataclasses.replace(SPEC, tier="disk",
                                   path=str(tmp_path / "m.ctpl"))
        db = catapultdb.create(spec, data)
        for _ in range(3):
            db.search(queries, k=5)
        snap = db.metrics()
        assert snap["catapultdb_search_requests_total"] == 3.0
        assert snap["catapultdb_search_queries_total"] == 3.0 * len(queries)
        assert snap["catapultdb_search_latency_ms"]["count"] == 3
        assert snap["catapultdb_search_latency_ms"]["p99"] > 0.0
        # the cache collector mirrors the live CacheStats
        cs = db.cache_stats
        assert snap["catapultdb_cache_block_reads"] == float(cs.block_reads)
        assert snap["catapultdb_cache_hits"] == float(cs.hits)
        db.close()

    def test_disabled_spec_empty_and_identical_answers(self, data, queries):
        db_on = catapultdb.create(SPEC, data)
        db_off = catapultdb.create(
            dataclasses.replace(SPEC, metrics=False), data)
        r_on = db_on.search(queries, k=5, publish=False)
        r_off = db_off.search(queries, k=5, publish=False)
        np.testing.assert_array_equal(r_on.ids, r_off.ids)
        assert db_off.metrics() == {}
        assert db_off.metrics("prometheus") == ""
        # explain still works without a registry
        tr = db_off.search(queries, k=5, publish=False, explain=True)
        np.testing.assert_array_equal(tr.ids, r_off.ids)

    def test_warm_breakdown_per_shape(self, data):
        db = catapultdb.create(SPEC, data)
        db.warm((4, 8))
        assert set(db.last_warm_breakdown) == {4, 8}
        assert all(ms > 0.0 for ms in db.last_warm_breakdown.values())
        assert db.last_warm_ms == pytest.approx(
            sum(db.last_warm_breakdown.values()), rel=0.05)
        snap = db.metrics()
        assert snap["catapultdb_warm_ms_shape_4"] > 0.0
        assert snap["catapultdb_warm_ms_shape_8"] > 0.0
        assert snap["catapultdb_warm_total_ms"] == pytest.approx(
            db.last_warm_ms)

    def test_metrics_fmt_validation(self, data):
        db = catapultdb.create(SPEC, data)
        with pytest.raises(ValueError):
            db.metrics("xml")

    def test_cache_stats_uniform_across_tiers(self, data, tmp_path):
        ram = catapultdb.create(SPEC, data)
        st = ram.cache_stats
        assert st is not None
        assert (st.hits, st.misses, st.block_reads) == (0, 0, 0)
        disk = catapultdb.create(
            dataclasses.replace(SPEC, tier="disk",
                                path=str(tmp_path / "u.ctpl")), data)
        disk.search(data[:4], k=3)
        assert disk.cache_stats.block_reads > 0
        assert type(disk.cache_stats) is type(ram.cache_stats)
        disk.close()


# ---------------------------------------------------------------- serving
class TestServingWindow:
    def test_mixed_k_flushes_fill_the_window(self, data, queries):
        db = catapultdb.create(SPEC, data)
        fe = db.serve(max_batch=4)
        for flush in range(3):
            tickets = {}
            for i in range(6):       # 6 tickets, alternating k -> two
                k = 3 if i % 2 == 0 else 5       # (k, beam) groups
                tickets[fe.submit(queries[i % len(queries)], k=k)] = k
            out = fe.flush()
            for t, k in tickets.items():
                assert out[t][0].shape == (k,)
        snap = fe.window.snapshot()
        assert snap["flushes"] == 3
        assert snap["queries"] == 18
        assert snap["qps"] > 0.0
        assert snap["flush_p99_ms"] >= snap["flush_p50_ms"] > 0.0
        # 6 tickets split into (k=3: one 3-real chunk) + (k=5: one
        # 3-real chunk) over max_batch=4 -> mean occupancy 0.75
        assert snap["batch_occupancy"] == pytest.approx(0.75)
        # the window rides into db.metrics() as a collector
        m = db.metrics()
        assert m["catapultdb_serve_flushes"] == 3.0
        assert m["catapultdb_serve_flushes_total"] == 3.0
        assert m["catapultdb_serve_flush_ms"]["count"] == 3

    def test_empty_window_snapshot(self):
        w = RollingWindow()
        snap = w.snapshot()
        assert snap["flushes"] == 0 and snap["qps"] == 0.0

    def test_window_bounded(self):
        w = RollingWindow(limit=4)
        for i in range(10):
            w.record_flush(queries=1, occupancy=1.0, ms=1.0,
                           t_end=float(i))
        assert w.snapshot()["flushes"] == 4      # rolling, not total
        assert w.total_flushes == 10

    def test_bulk_search_records_window(self, data, queries):
        db = catapultdb.create(SPEC, data)
        fe = db.serve(max_batch=4)
        ids, dists, _ = fe.search(queries, k=3)
        assert ids.shape == (len(queries), 3)
        assert fe.window.snapshot()["flushes"] == 1
        assert fe.window.snapshot()["queries"] == len(queries)


# ---------------------------------------------------------------- recorder
class TestTraceRecorder:
    def test_stage_timing_and_children(self):
        rec = TraceRecorder("root")
        with rec.stage("route"):
            pass
        rec.add_stage("route", 2.0)
        assert rec.stage_ms("route") >= 2.0
        assert rec.stage_ms("absent") == 0.0
        kid = rec.child("shard_0")
        kid.add_stage("fetch", 1.0)
        assert rec.children[0].stage_ms("fetch") == 1.0

    def test_engine_accepts_trace_kw(self, data, queries):
        # the engine-level contract the facade builds on
        db = catapultdb.create(SPEC, data)
        rec = TraceRecorder()
        mask = np.zeros(len(queries), bool)
        db.backend.search(queries, k=3, publish_mask=mask, trace=rec)
        assert {s.name for s in rec.spans} == {"route", "rerank"}
