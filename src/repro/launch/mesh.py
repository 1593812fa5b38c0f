"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state): 16×16 = 256 chips per pod, ×2 pods multi-pod.
The dry-run (launch/dryrun.py) forges 512 host devices via XLA_FLAGS
*before* any jax import; real deployments get the same shapes from the
TPU topology.

``make_local_mesh`` builds whatever grid the live process can support —
the CPU test/benchmark path and the elastic-restart path (ft/elastic.py
picks the shape).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Axes are ``Auto``: the model code places arrays with sharding
    constraints and leaves propagation to the compiler, so array types
    carry no explicit sharding that every op would have to agree on
    (``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    return jax.make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """The mesh axes a global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
