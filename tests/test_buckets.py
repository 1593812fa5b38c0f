"""Catapult bucket (LRU shortcut table) semantics — §3.2 of the paper."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:             # optional dep — fall back to the local shim
    from _hypothesis_fallback import given, settings, st

from repro.core import buckets as bk
from repro.core import catapult as cat
from repro.core.beam_search import SearchSpec, l2_dist_fn


@jax.jit
def _publish_sequential(state, bucket_idx, dest, tags):
    """Oracle: the publish as a loop over lanes, one append at a time in
    batch order — what ``bk.publish`` must equal bit for bit."""

    def one(i, carry):
        ids, stamp, tag, step = carry
        h, d, t = bucket_idx[i], dest[i], tags[i]
        row_ids, row_stamp, row_tag = ids[h], stamp[h], tag[h]
        present = (row_ids == d) & (row_tag == t)
        hit = jnp.any(present) & (d >= 0)
        slot = jnp.where(hit, jnp.argmax(present), jnp.argmin(row_stamp))
        do = d >= 0
        row_ids = jnp.where(do, row_ids.at[slot].set(d), row_ids)
        row_stamp = jnp.where(do, row_stamp.at[slot].set(step), row_stamp)
        row_tag = jnp.where(do, row_tag.at[slot].set(t), row_tag)
        return (ids.at[h].set(row_ids), stamp.at[h].set(row_stamp),
                tag.at[h].set(row_tag), step + do.astype(jnp.int32))

    ids, stamp, tag, step = jax.lax.fori_loop(
        0, bucket_idx.shape[0], one,
        (state.ids, state.stamp, state.tag, state.step))
    return bk.BucketState(ids=ids, stamp=stamp, tag=tag, step=step)


def _lanes(rng, b, n_buckets, n_dest, n_tags=1, invalid=0.0):
    """(bucket, dest, tag) int32 lanes; a share ``invalid`` of dests -1."""
    h = rng.integers(0, n_buckets, b)
    d = np.where(rng.random(b) < invalid, -1, rng.integers(0, n_dest, b))
    t = rng.integers(-1, n_tags - 1, b) if n_tags > 1 else np.full(b, -1)
    return tuple(jnp.asarray(x, jnp.int32) for x in (h, d, t))


def _full_old_state(rng, n_buckets, cap, step0):
    """Every slot taken, by distinct ids with distinct stamps below
    ``step0`` in a shuffled order, and a mix of tags."""
    ids = rng.permutation(n_buckets * cap).reshape(n_buckets, cap)
    stamp = np.stack([rng.permutation(step0)[:cap] for _ in range(n_buckets)])
    tag = rng.integers(-1, 2, (n_buckets, cap))
    return bk.BucketState(ids=jnp.asarray(ids, jnp.int32),
                          stamp=jnp.asarray(stamp, jnp.int32),
                          tag=jnp.asarray(tag, jnp.int32),
                          step=jnp.int32(step0))


def _zipf_buckets(rng, b, n_buckets):
    p = 1.0 / np.arange(1, n_buckets + 1) ** 0.8
    return rng.choice(n_buckets, b, p=p / p.sum())


def _case(name, rng):
    """(initial state, batches of (bucket, dest, tag)) for one case."""
    empty = bk.make_buckets(8, 4)
    if name == "random":
        return empty, [_lanes(rng, 64, 8, 40, 3, 0.2) for _ in range(3)]
    if name == "one_bucket":
        h, d, t = _lanes(rng, 50, 1, 12)
        return empty, [(h + 5, d, t), (h + 5, d[::-1], t)]
    if name == "invalid_interleaved":
        h, d, t = _lanes(rng, 48, 4, 9, 2)
        d = jnp.where(jnp.arange(48) % 2 == 0, d, -1)
        return empty, [(h, d, t), (h, jnp.full_like(d, -1), t), (h, d, t)]
    if name == "mixed_tags":
        return empty, [_lanes(rng, 64, 3, 5, 4) for _ in range(3)]
    if name == "full_old_stamps":
        return (_full_old_state(rng, 8, 4, 1000),
                [_lanes(rng, 40, 8, 40, 3, 0.1) for _ in range(2)])
    if name == "repeated_dests":
        h, d, t = _lanes(rng, 64, 2, 1)
        return empty, [(h, d + 6, t), (h, (d + 6) * (jnp.arange(64) % 2), t)]
    assert name == "deployment"            # b = 40, L = 8, 4096 lanes
    batches = []
    for _ in range(2):
        _, d, t = _lanes(rng, 4096, 256, 60_000, 1, 0.05)
        h = jnp.asarray(_zipf_buckets(rng, 4096, 256), jnp.int32)
        batches.append((h, d, t))
    return bk.make_buckets(256, 40), batches


@pytest.mark.parametrize("name", ["random", "one_bucket",
                                  "invalid_interleaved", "mixed_tags",
                                  "full_old_stamps", "repeated_dests",
                                  "deployment"])
def test_publish_equals_sequential_loop(name):
    """Every field, after every batch, bit for bit."""
    want, batches = _case(name, np.random.default_rng(17))
    got = want
    for h, d, t in batches:
        want = _publish_sequential(want, h, d, t)
        got = bk.publish(got, h, d, t)
        for f in ("ids", "stamp", "tag", "step"):
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(getattr(want, f)),
                                          err_msg=f"{name}: {f}")


def test_publish_rounds_is_busiest_bucket():
    h = jnp.asarray([3, 3, 1, 3, 1, 0], jnp.int32)
    d = jnp.asarray([7, -1, 2, 9, 2, -1], jnp.int32)
    state = bk.make_buckets(4, 2)
    assert int(bk.publish_rounds(state, h, d)) == 2     # bucket 3: 7, 9
    assert int(bk.publish_rounds(state, h, jnp.full_like(d, -1))) == 0


def test_catapulted_lookup_matches_sequential_publish(catapult_engine,
                                                      queries, monkeypatch):
    """Two batches in a row, the second starting from the buckets the
    first published, give the same results, stats and state whichever
    publish runs."""
    eng = catapult_engine
    spec = SearchSpec(beam_width=8, k=4, max_iters=96)
    mask = jnp.asarray(np.arange(queries.shape[0]) % 5 != 0)

    def two_batches():
        run = jax.jit(lambda s, q: cat.catapulted_lookup(
            s, eng._adj, q, spec, l2_dist_fn(eng._vec),
            jnp.int32(eng.medoid), publish_mask=mask))
        state = cat.make_catapult_state(jax.random.PRNGKey(0),
                                        queries.shape[1])
        outs = []
        for q in (queries, queries[::-1]):
            state, res, st = run(state, jnp.asarray(q))
            outs.append((res, st))
        return state, outs

    got = two_batches()
    monkeypatch.setattr(bk, "publish", _publish_sequential)
    want = two_batches()
    assert int(want[0].buckets.step) > 0
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)


def test_publish_and_lookup_roundtrip():
    st8 = bk.make_buckets(8, 4)
    h = jnp.asarray([1, 1, 3], jnp.int32)
    d = jnp.asarray([10, 11, 12], jnp.int32)
    t = jnp.full((3,), -1, jnp.int32)
    st8 = bk.publish(st8, h, d, t)
    ids, tags = bk.lookup(st8, jnp.asarray([1, 3, 0], jnp.int32))
    assert set(np.asarray(ids[0])[np.asarray(ids[0]) >= 0].tolist()) == {10, 11}
    assert 12 in np.asarray(ids[1]).tolist()
    assert np.all(np.asarray(ids[2]) == -1)


def test_lru_eviction_order():
    state = bk.make_buckets(2, 3)
    h = jnp.zeros((5,), jnp.int32)
    d = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
    state = bk.publish(state, h, d, jnp.full((5,), -1, jnp.int32))
    ids = np.asarray(bk.lookup(state, jnp.zeros((1,), jnp.int32))[0][0])
    # capacity 3: oldest (1, 2) evicted, {3,4,5} retained
    assert set(ids.tolist()) == {3, 4, 5}


def test_duplicate_publish_refreshes_instead_of_evicting():
    state = bk.make_buckets(2, 3)
    h = jnp.zeros((3,), jnp.int32)
    state = bk.publish(state, h, jnp.asarray([1, 2, 3], jnp.int32),
                       jnp.full((3,), -1, jnp.int32))
    # re-publish 1 (refresh), then add 4 -> 2 is now LRU and must go
    state = bk.publish(state, jnp.zeros((2,), jnp.int32),
                       jnp.asarray([1, 4], jnp.int32),
                       jnp.full((2,), -1, jnp.int32))
    ids = np.asarray(bk.lookup(state, jnp.zeros((1,), jnp.int32))[0][0])
    assert set(ids.tolist()) == {1, 3, 4}


def test_invalid_destination_is_skipped():
    state = bk.make_buckets(2, 2)
    state = bk.publish(state, jnp.zeros((1,), jnp.int32),
                       jnp.asarray([-1], jnp.int32),
                       jnp.full((1,), -1, jnp.int32))
    assert np.all(np.asarray(state.ids) == -1)
    assert int(state.step) == 0


@given(st.lists(st.tuples(st.integers(0, 7), st.integers(0, 99)),
                min_size=1, max_size=64))
@settings(max_examples=30, deadline=None)
def test_matches_reference_lru(ops):
    """Property: the batched publish equals a python dict-of-LRU-lists."""
    cap = 4
    state = bk.make_buckets(8, cap)
    ref: dict[int, list[int]] = {i: [] for i in range(8)}
    h = jnp.asarray([o[0] for o in ops], jnp.int32)
    d = jnp.asarray([o[1] for o in ops], jnp.int32)
    state = bk.publish(state, h, d, jnp.full((len(ops),), -1, jnp.int32))
    for hb, dd in ops:
        row = ref[hb]
        if dd in row:
            row.remove(dd)      # refresh = move to MRU end
        elif len(row) == cap:
            row.pop(0)          # evict LRU
        row.append(dd)
    for b in range(8):
        got = np.asarray(state.ids[b])
        got = set(got[got >= 0].tolist())
        assert got == set(ref[b]), (b, got, ref[b])


def test_memory_cost_matches_paper():
    """b=40, L=8 -> 40 KiB of id data (paper §3.2 'Negligible storage')."""
    state = bk.make_buckets(2 ** 8, 40)
    assert state.ids.size * 4 == 40 * 1024


def test_evict_ids_flushes_dead_destinations():
    """Tombstone-delete invalidation: dead ids vanish from every bucket,
    live entries (and the LRU clock) are untouched."""
    state = bk.make_buckets(4, 3)
    h = jnp.asarray([0, 0, 1, 2], jnp.int32)
    d = jnp.asarray([10, 11, 10, 12], jnp.int32)
    state = bk.publish(state, h, d, jnp.full((4,), -1, jnp.int32))
    step_before = int(state.step)
    state = bk.evict_ids(state, jnp.asarray([10], jnp.int32))
    ids = np.asarray(state.ids)
    assert not (ids == 10).any(), "dead destination survived eviction"
    assert (ids == 11).any() and (ids == 12).any(), "live entry lost"
    assert int(state.step) == step_before
    # stamps of cleared slots are reset so they evict first on reuse
    assert np.asarray(state.stamp)[ids == -1].max(initial=-1) == -1


def test_evict_ids_resets_tag_no_ghost_label_match():
    """Regression: an evicted slot must reset its filter tag too — a
    surviving tag would let a later filtered lookup treat the empty
    slot as a predicate match (a "ghost" of the deleted destination)."""
    state = bk.make_buckets(2, 3)
    state = bk.publish(state, jnp.zeros((2,), jnp.int32),
                       jnp.asarray([10, 11], jnp.int32),
                       jnp.asarray([5, 7], jnp.int32))     # tagged entries
    state = bk.evict_ids(state, jnp.asarray([10], jnp.int32))
    ids = np.asarray(state.ids)
    tags = np.asarray(state.tag)
    assert np.all(tags[ids == -1] == -1), "ghost tag survived eviction"
    assert tags[ids == 11].tolist() == [7], "live tag lost"
    # the filtered-lookup validity rule (catapult.py): a cleared slot
    # must never satisfy "ids >= 0 and tag matches" for ANY label
    cat_ids, cat_tags = bk.lookup(state, jnp.zeros((1,), jnp.int32))
    ghost = (np.asarray(cat_ids)[0] < 0) & (np.asarray(cat_tags)[0] == 5)
    assert not ghost.any()


def test_evict_stale_ttl_clock():
    """evict_stale ages on the publish clock: entries older than
    step - max_age clear in full (id, stamp, tag)."""
    state = bk.make_buckets(2, 8)
    state = bk.publish(state, jnp.zeros((5,), jnp.int32),
                       jnp.asarray([1, 2, 3, 4, 5], jnp.int32),
                       jnp.full((5,), 9, jnp.int32))       # stamps 0..4
    out = bk.evict_stale(state, jnp.int32(3))              # cutoff: < 2
    ids = np.asarray(out.ids)
    assert set(ids[ids >= 0].tolist()) == {3, 4, 5}
    assert np.all(np.asarray(out.tag)[ids == -1] == -1)
    assert int(out.step) == int(state.step)


def test_evict_buckets_row_flush():
    state = bk.make_buckets(4, 2)
    state = bk.publish(state, jnp.asarray([0, 2], jnp.int32),
                       jnp.asarray([7, 8], jnp.int32),
                       jnp.full((2,), -1, jnp.int32))
    out = bk.evict_buckets(state, jnp.asarray([True, False, False, False]))
    ids = np.asarray(out.ids)
    assert np.all(ids[0] == -1), "flushed row survived"
    assert 8 in ids[2].tolist(), "untouched row lost its entry"
