"""Distributed engine equivalence: the shard_map scatter-gather search must
return the same neighbors as a single-device brute-force/merged reference.

Needs >1 device, so the check runs in a SUBPROCESS with forged host
devices (XLA_FLAGS must precede jax import; never set it in this
process — see launch/dryrun.py header).
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.beam_search import SearchSpec
from repro.core.sharded import build_sharded_state, make_sharded_search
from repro.core import brute_force_knn, recall_at_k

mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
centers = rng.normal(size=(16, 24)).astype(np.float32) * 2
vecs = (centers[rng.integers(0, 16, 1600)]
        + rng.normal(size=(1600, 24))).astype(np.float32)
state = build_sharded_state(vecs, n_shards=4, n_devices=8,
                            max_degree=12, lsh_bits=4, bucket_cap=8)
spec = SearchSpec(beam_width=12, k=5, max_iters=64)
step = make_sharded_search(mesh, spec, 400, 4)

q = (centers[rng.integers(0, 16, 64)]
     + 0.3 * rng.normal(size=(64, 24))).astype(np.float32)
with jax.set_mesh(mesh):
    jq = jax.device_put(jnp.asarray(q), NamedSharding(mesh, P("data", None)))
    st = state
    for rep in range(3):     # repeats exercise the per-device catapults
        st, ids, dists = step(st, jq)
ids = np.asarray(ids)
truth = brute_force_knn(vecs, q, 5)
rec = recall_at_k(ids, truth)
assert ids.shape == (64, 5)
assert rec > 0.9, f"sharded recall {rec}"
d_check = ((vecs[np.maximum(ids, 0)] - q[:, None]) ** 2).sum(-1)
np.testing.assert_allclose(np.asarray(dists), d_check, rtol=1e-3, atol=1e-3)
assert int(jnp.sum(st.bucket_step)) > 0, "catapults must have been published"
print("SHARDED-OK", rec)
"""


@pytest.mark.parametrize("n", [1])
def test_sharded_engine_matches_reference(n, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "SHARDED-OK" in r.stdout
