"""Per-kernel verification: shape/dtype sweeps against the pure-jnp oracles."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


@pytest.mark.parametrize("b,c,d", [(8, 8, 16), (37, 203, 64), (128, 256, 128),
                                   (1, 5, 768), (130, 127, 96)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_l2_distance(b, c, d, dtype):
    q, x = _arr((b, d), dtype), _arr((c, d), dtype)
    got = ops.l2_distance(q, x)
    want = ref.l2_distance_ref(q, x)
    tol = 1e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("d", [1, 16, 24, 100, 128, 200, 700])
def test_lane_sum_ignores_zero_padding(d):
    """The kernels sum rows zero-padded to whole 128-lane tiles, XLA the
    unpadded rows: both must give the same float, and the sum itself."""
    from repro.distance import lane_sum
    s = np.random.default_rng(d).random((32, d)).astype(np.float32)
    got = np.asarray(lane_sum(jnp.asarray(s)))
    padded = jnp.pad(jnp.asarray(s), ((0, 0), (0, (-d) % 128)))
    np.testing.assert_array_equal(np.asarray(lane_sum(padded)), got)
    np.testing.assert_allclose(got, s.astype(np.float64).sum(-1), rtol=1e-6)


@pytest.mark.parametrize("n,m,d", [(50, 8, 16), (500, 33, 64), (1000, 64, 128)])
def test_gather_distance(n, m, d):
    x = _arr((n, d))
    ids = jnp.asarray(RNG.integers(-1, n, size=(m,)).astype(np.int32))
    q = _arr((d,))
    got = ops.gather_distance(x, ids, q)
    want = ref.gather_distance_ref(x, ids, q)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert np.all(np.isinf(np.asarray(got)[np.asarray(ids) < 0]))


@pytest.mark.parametrize("b,l,d", [(4, 4, 16), (100, 8, 64), (256, 16, 128)])
def test_lsh_hash(b, l, d):
    q, h = _arr((b, d)), _arr((l, d))
    got = ops.lsh_hash(q, h)
    want = ref.lsh_hash_ref(q, h)
    np.testing.assert_array_equal(got, want)
    assert np.asarray(got).max() < 2 ** l


@pytest.mark.parametrize("m,k,c", [(4, 8, 16), (8, 256, 77), (16, 64, 128)])
def test_pq_adc(m, k, c):
    lut = jnp.asarray((RNG.normal(size=(m, k)) ** 2).astype(np.float32))
    codes = jnp.asarray(RNG.integers(0, k, size=(c, m)).astype(np.int32))
    got = ops.pq_adc(lut, codes)
    want = ref.pq_adc_ref(lut, codes)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_interpret_mode_only_off_tpu(monkeypatch):
    """A TPU backend always gets compiled kernels, never the interpreter."""
    from repro.kernels import platform
    monkeypatch.setattr(platform.jax, "default_backend", lambda: "tpu")
    assert platform.interpret_mode() is False
    monkeypatch.setattr(platform.jax, "default_backend", lambda: "cpu")
    assert platform.interpret_mode() is True


def test_l2_distance_agrees_with_beam_search_metric():
    """Kernel and beam-search default dist_fn must be the same metric."""
    from repro.core.beam_search import l2_dist_fn
    x = _arr((40, 32))
    q = _arr((32,))
    ids = jnp.arange(40, dtype=jnp.int32)
    np.testing.assert_allclose(l2_dist_fn(x)(q, ids),
                               ops.l2_distance(q[None], x)[0],
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused traversal hop — bit-exact parity with the jnp oracle and with the
# composed (unfused) beam-search path, on every tier
# ---------------------------------------------------------------------------

def _hop_state(rng, n, b, c, l):
    """Mid-traversal hop state: sorted beams, -1 holes, a converged lane
    and an interior -1 before valid candidates (catapult start shape)."""
    cand = rng.integers(-1, n, size=(b, c)).astype(np.int32)
    cand[-1] = -1                      # fully-converged lane: no-op hop
    if b > 1 and c > 1:
        cand[0, 0] = -1                # interior hole before valid ids
    bids = rng.integers(-1, n, size=(b, l)).astype(np.int32)
    bd = np.where(bids < 0, np.inf,
                  (rng.random((b, l)) * 10).astype(np.float32))
    bexp = np.where(bids < 0, True, rng.random((b, l)) < 0.5)
    order = np.argsort(bd, axis=1)
    return (jnp.asarray(cand),
            jnp.asarray(np.take_along_axis(bids, order, 1)),
            jnp.asarray(np.take_along_axis(bd, order, 1).astype(np.float32)),
            jnp.asarray(np.take_along_axis(bexp, order, 1)))


def _assert_hop_parity(got, want):
    """ids/exp/nfresh must match EXACTLY; dists get one-ULP slack only —
    the oracle runs un-jitted, so XLA may schedule its d-reduction in a
    different association order than the kernel's.  (The bit-for-bit
    claim is fused-vs-unfused *beam search*, where both paths run in the
    same jit context — test_fused_beam_search_bit_identical and the
    per-tier engine test below hold that to exact equality.)"""
    for g, w, name in zip(got, want, ["ids", "dists", "exp", "nfresh"]):
        g, w = np.asarray(g), np.asarray(w)
        if name == "dists":
            np.testing.assert_array_equal(np.isfinite(g), np.isfinite(w))
            m = np.isfinite(w)
            np.testing.assert_allclose(g[m], w[m], rtol=1e-6, atol=0,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


@pytest.mark.parametrize("n,b,c,l", [(64, 1, 3, 5), (200, 6, 10, 8),
                                     (500, 16, 32, 16), (100, 4, 1, 2)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_fused_hop_l2_matches_oracle(n, b, c, l, dtype):
    rng = np.random.default_rng(n + b + c + l)
    vec = jnp.asarray(rng.normal(size=(n, 24)).astype(dtype))
    q = jnp.asarray(rng.normal(size=(b, 24)).astype(dtype))
    cand, bids, bd, bexp = _hop_state(rng, n, b, c, l)
    got = ops.fused_hop_l2(vec, cand, q, bids, bd, bexp)
    want = ref.fused_hop_ref(vec, cand, q, bids, bd, bexp)
    _assert_hop_parity(got, want)


@pytest.mark.parametrize("n,b,c,l,m,k", [(64, 1, 3, 5, 4, 8),
                                         (200, 6, 10, 8, 8, 16),
                                         (300, 12, 24, 12, 4, 32)])
def test_fused_hop_pq_matches_oracle(n, b, c, l, m, k):
    rng = np.random.default_rng(n + b)
    luts = jnp.asarray((rng.normal(size=(b, m, k)) ** 2).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, k, size=(n, m)).astype(np.int32))
    cand, bids, bd, bexp = _hop_state(rng, n, b, c, l)
    got = ops.fused_hop_pq(luts, codes, cand, bids, bd, bexp)
    want = ref.fused_hop_pq_ref(luts, codes, cand, bids, bd, bexp)
    _assert_hop_parity(got, want)


def test_fused_beam_search_bit_identical():
    """Full traversal: spec.hop_backend='fused' must reproduce the
    composed path bit-for-bit — ids, dists, and every stats counter."""
    from repro.core.beam_search import SearchSpec, beam_search_l2
    rng = np.random.default_rng(3)
    n, d, b = 300, 16, 8
    vec = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    adj = rng.integers(0, n, size=(n, 8)).astype(np.int32)
    adj[rng.random((n, 8)) < 0.2] = -1
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    starts = np.full((b, 3), -1, np.int32)
    starts[:, 1] = rng.integers(0, n, size=b)   # interior -1 first slot
    starts[:, 2] = rng.integers(0, n, size=b)
    ru = beam_search_l2(jnp.asarray(adj), vec, q, jnp.asarray(starts),
                        SearchSpec(beam_width=12, k=5, max_iters=40))
    rf = beam_search_l2(jnp.asarray(adj), vec, q, jnp.asarray(starts),
                        SearchSpec(beam_width=12, k=5, max_iters=40,
                                   hop_backend="fused"))
    for fld in ["ids", "dists", "hops", "ndists", "trace", "converged"]:
        np.testing.assert_array_equal(np.asarray(getattr(ru, fld)),
                                      np.asarray(getattr(rf, fld)),
                                      err_msg=fld)


@pytest.mark.parametrize("tier", ["ram", "disk", "sharded"])
def test_fused_engine_bit_identical(tier, tmp_path):
    """db-facade acceptance: hop_backend='fused' returns bit-identical
    ids/dists/hops/ndists on every tier over several batches."""
    from repro import db as catapultdb
    from repro.db.spec import IndexSpec

    rng = np.random.default_rng(11)
    vec = rng.normal(size=(300, 16)).astype(np.float32)
    qs = rng.normal(size=(8, 16)).astype(np.float32)

    def build(hb):
        path = None
        if tier == "disk":
            path = str(tmp_path / f"{hb}.ctpl")
        elif tier == "sharded":
            path = str(tmp_path / f"{hb}.d")
        spec = IndexSpec(tier=tier, mode="catapult", path=path, degree=8,
                         build_beam=16, bucket_capacity=8, n_shards=2,
                         hop_backend=hb)
        return catapultdb.create(spec, vec)

    du, df = build("unfused"), build("fused")
    assert du.spec.hop_backend == "unfused"
    assert df.spec.hop_backend == "fused"
    for i in range(3):
        ru = du.search(qs + 0.01 * i, k=5)
        rf = df.search(qs + 0.01 * i, k=5)
        np.testing.assert_array_equal(ru.ids, rf.ids)
        np.testing.assert_array_equal(ru.dists, rf.dists)
        np.testing.assert_array_equal(ru.stats.hops, rf.stats.hops)
        np.testing.assert_array_equal(ru.stats.ndists, rf.stats.ndists)
