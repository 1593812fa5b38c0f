"""The program's spans and device scopes (repro.obs.span, jax.named_scope).

Contracts pinned here:

* a profiler capture of ``fe.flush()`` and ``fe.search()`` over a RAM
  and a disk database holds the ``catapultdb.*`` spans on one thread,
  nested and in the order the served path runs them, with the flush's
  arguments;
* spans only observe: answers and stats are bit-identical with and
  without a capture running, and explain's ``to_dict()`` keeps its keys;
* the compiled catapult search carries every device scope in its
  ``op_name`` metadata, for both hop backends, with and without PQ.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
import tempfile
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import db as catapultdb
from repro.core import catapult as cat
from repro.core import engine as E
from repro.core import pq as pq_mod
from repro.core.beam_search import SearchSpec
from tests.conftest import make_clustered

SPEC = catapultdb.IndexSpec(degree=16, build_beam=32, build_batch=512,
                            seed=0, cache_frames=128)
SCOPES = ("catapult/lsh", "catapult/lookup", "catapult/publish",
          "hop/gather", "hop/distance", "hop/merge")


@pytest.fixture(scope="module")
def data():
    corpus, _, _ = make_clustered(600, 16, 8, seed=3)
    return corpus


@pytest.fixture(scope="module")
def queries(data):
    rng = np.random.default_rng(5)
    return (data[:20] + rng.normal(scale=0.05, size=(20, data.shape[1]))
            ).astype(np.float32)


@pytest.fixture(params=["ram", "disk"])
def db(request, data, tmp_path):
    spec = SPEC
    if request.param == "disk":
        spec = dataclasses.replace(SPEC, tier="disk",
                                   path=str(tmp_path / "s.ctpl"))
    d = catapultdb.create(spec, data)
    yield d
    d.close()


def _capture(tmp_path, fn):
    """Run ``fn`` under a profiler capture; its result and the
    ``catapultdb.*`` host events as (thread, name, start, end, args)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    where = tempfile.mkdtemp(dir=tmp_path)
    jax.profiler.start_trace(where, profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = pathlib.Path(where).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    events = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("catapultdb."):
                    events.append(((plane.name, line_no), e.name,
                                   e.start_ns, e.end_ns, dict(e.stats)))
    return out, sorted(events, key=lambda t: (t[2], -t[3]))


def _within(inner, outer) -> bool:
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def _flush(fe, queries):
    tickets = [fe.submit(q) for q in queries]
    out = fe.flush()
    return [out[t] for t in tickets]


def test_flush_spans_nest_in_order(db, queries, tmp_path):
    fe = db.serve(max_batch=16, k=5)
    _flush(fe, queries)                          # compile outside
    _, ev = _capture(tmp_path, lambda: _flush(fe, queries))
    assert len({line for line, *_ in ev}) == 1   # the dispatching thread
    flush, = [e for e in ev if e[1] == "catapultdb.frontend.flush"]
    assert all(_within(e, flush) for e in ev)
    args = flush[4]
    assert args["tickets"] == len(queries) and args["chunks"] == 2
    assert 0 < args["wait_ms_max"] <= args["wait_ms_sum"]
    dispatches = [e for e in ev if e[1] == "catapultdb.frontend.dispatch"]
    first = fe.batches_dispatched - 2
    assert [e[4]["dispatch"] for e in dispatches] == [first, first + 1]
    for d in dispatches:
        inner = [e[1] for e in ev if e is not d and _within(e, d)]
        assert inner[:2] == ["catapultdb.engine.upload",
                             "catapultdb.engine.route"]
        assert "catapultdb.engine.readback" in inner
        if db.spec.tier == "disk":
            # the readback finishes the route; the block I/O follows it
            assert inner[2:6] == ["catapultdb.engine.readback",
                                  "catapultdb.disk.plan",
                                  "catapultdb.disk.fetch",
                                  "catapultdb.disk.rerank"]
        else:
            assert inner[2:] == ["catapultdb.engine.readback"]
    names = [e[1].rsplit(".", 1)[1] for e in ev
             if e[1].startswith("catapultdb.frontend.")]
    # group, then per chunk: stack, pad, dispatch, trim, hand out
    assert names == ["flush", "pack"] + 2 * ["pack", "pack", "dispatch",
                                             "unpack", "unpack"]


def test_bulk_search_spans(db, queries, tmp_path):
    fe = db.serve(max_batch=16, k=5)
    fe.search(queries)
    _, ev = _capture(tmp_path, lambda: fe.search(queries))
    search, = [e for e in ev if e[1] == "catapultdb.frontend.search"]
    assert all(_within(e, search) for e in ev)
    assert search[4]["tickets"] == len(queries)
    assert search[4]["chunks"] == 2
    assert [e[1] for e in ev].count("catapultdb.engine.route") == 2


def test_spans_change_no_answer(data, queries, tmp_path):
    """Two databases built alike serve the same flushes, one under a
    capture: the same ids, distances and stats, bucket publishes
    included."""
    a, b = catapultdb.create(SPEC, data), catapultdb.create(SPEC, data)
    fa, fb = a.serve(max_batch=16, k=5), b.serve(max_batch=16, k=5)
    for _ in range(2):
        want = _flush(fa, queries)
        got, _ = _capture(tmp_path, lambda: _flush(fb, queries))
        for (i1, d1), (i2, d2) in zip(want, got):
            np.testing.assert_array_equal(i1, i2)
            np.testing.assert_array_equal(d1, d2)
    _, _, sa = fa.search(queries)
    (_, _, sb), _ = _capture(tmp_path, lambda: fb.search(queries))
    for x, y in zip(sa, sb):
        for f in x._fields:
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))


def test_explain_keys_unchanged(db, queries):
    tr = db.search(queries, k=5, publish=False, explain=True)
    d = tr.to_dict()
    assert list(d) == ["tier", "mode", "batch", "k", "beam_width",
                       "entry_counts", "catapult_used", "catapult_won",
                       "lanes_published", "publish_rounds",
                       "hops_mean", "blocks_read_mean", "stages_ms",
                       "shards", "total_ms"]
    want = ({"route", "fetch", "rerank"} if db.spec.tier == "disk"
            else {"route", "rerank"})
    assert set(d["stages_ms"]) == want


def _op_names(compiled) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _has_scope(names, scope) -> bool:
    """Whether an op's name stack holds ``scope``: as a path of its own,
    or wrapped by a transform, as vmap writes it (``vmap(hop/merge)``)."""
    pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
    return any(pat.search(n) for n in names)


@pytest.mark.parametrize("hop_backend", ["unfused", "fused"])
@pytest.mark.parametrize("pq", [0, 4])
def test_search_program_carries_the_scopes(hop_backend, pq):
    n, d, r, b = 64, 8, 8, 8
    f32, i32 = jnp.float32, jnp.int32
    state = cat.make_catapult_state(jax.random.PRNGKey(0), d, 4, 8)
    pqcb = (pq_mod.PQCodebook(centroids=jnp.zeros((pq, 256, d // pq), f32))
            if pq else None)
    spec = SearchSpec(beam_width=8, k=8 if pq else 4, max_iters=16,
                      hop_backend=hop_backend)
    compiled = E._search_catapult.lower(
        state, jnp.zeros((n, r), i32), jnp.zeros((n, d), f32),
        jnp.zeros(n, bool), None, None, jnp.zeros((b, d), f32),
        jnp.full(b, -1, i32), jnp.int32(0), spec, pq, pqcb,
        jnp.zeros((n, pq), i32) if pq else None,
        jnp.ones(b, bool)).compile()
    names = _op_names(compiled)
    for scope in SCOPES:
        assert _has_scope(names, scope), (scope, hop_backend, pq)


def test_pq_rerank_carries_its_scope():
    vec = jnp.zeros((32, 8), jnp.float32)
    fn = jax.jit(jax.vmap(partial(pq_mod.rerank, vec, k=4)))
    compiled = fn.lower(jnp.zeros((4, 8), jnp.float32),
                        jnp.zeros((4, 8), jnp.int32)).compile()
    assert _has_scope(_op_names(compiled), "rerank")
