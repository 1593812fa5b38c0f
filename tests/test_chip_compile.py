"""Compile-only checks for a TPU v5e, made without the chip.

Every Pallas kernel under ``repro.kernels`` and the batched beam search
are compiled by the TPU compiler for a described ``v5e:2x2`` topology
(one of its chips) at deployment width: 768-d, 4096-query batches,
degree 64, beam 16, a 1M-row table.  Interpret-mode tests cannot see
what this sees: block shapes and DMA slices that the chip's tiling
refuses, and programs that do not fit its memory.  Nothing runs here, so
nothing here says anything about results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.  Where it cannot be described, the tests skip.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, C, L, D = 4096, 64, 16, 768      # queries, degree, beam, width
N = 1_000_000                       # table rows
STARTS = 41                         # catapult bucket (40) + the medoid
PQ_M, PQ_K = 8, 256                 # PQ subspaces, centroids
LSH_BITS = 8
HBM_BYTES = 16 * 10 ** 9            # TPU v5e: 16 GB of HBM

i32, f32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Shape maker on one described chip, with the persistent compile
    cache off: an entry compiled for a described chip cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=f32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _beam(chip):
    return (chip((B, L), i32), chip((B, L)), chip((B, L), jnp.bool_))


@pytest.mark.parametrize("starts", [C, STARTS])
def test_fused_hop_l2_compiles(chip, starts):
    from repro.kernels.fused_hop import fused_hop_l2
    compiled = _compile(functools.partial(fused_hop_l2, interpret=False),
                        chip((N, D)), chip((B, starts), i32), chip((B, D)),
                        *_beam(chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_hop_pq_compiles(chip):
    from repro.kernels.fused_hop import fused_hop_pq
    compiled = _compile(functools.partial(fused_hop_pq, interpret=False),
                        chip((B, PQ_M, PQ_K)), chip((N, PQ_M), i32),
                        chip((B, C), i32), *_beam(chip))
    assert "tpu_custom_call" in compiled.as_text()


def _l2_distance(chip):
    from repro.kernels.l2_distance import l2_distance
    return (functools.partial(l2_distance, interpret=False),
            chip((B, D)), chip((1024, D)))


def _gather_distance(chip):
    from repro.kernels.gather_distance import gather_distance
    return (functools.partial(gather_distance, interpret=False),
            chip((N, D)), chip((C,), i32), chip((D,)))


def _lsh_hash(chip):
    from repro.kernels.lsh_hash import lsh_hash
    return (functools.partial(lsh_hash, interpret=False),
            chip((B, D)), chip((LSH_BITS, D)))


def _pq_adc(chip):
    from repro.kernels.pq_adc import pq_adc
    return (functools.partial(pq_adc, interpret=False),
            chip((PQ_M, PQ_K)), chip((B, PQ_M), i32))


@pytest.mark.parametrize("case", [_l2_distance, _gather_distance, _lsh_hash,
                                  _pq_adc])
def test_kernel_compiles(chip, case):
    fn, *args = case(chip)
    assert "tpu_custom_call" in _compile(fn, *args).as_text()


@pytest.mark.parametrize("hop_backend", ["unfused", "fused"])
def test_beam_search_compiles_and_fits(chip, hop_backend):
    """The search step at 1M x 768: compiles, and its arguments plus
    temporaries fit the chip's memory."""
    from repro.core.beam_search import SearchSpec, beam_search_l2
    spec = SearchSpec(beam_width=L, k=10, max_iters=64,
                      hop_backend=hop_backend)
    compiled = beam_search_l2.lower(
        chip((N, C), i32), chip((N, D)), chip((B, D)),
        chip((B, STARTS), i32), spec).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= HBM_BYTES, (hop_backend, total)


def test_bucket_publish_compiles_without_scatter(chip):
    """The catapult publish at 4096 lanes over the (256, 40) tables: a
    vmapped scatter lost writes at 4096 lanes on the chip, so the
    compiled publish must hold none."""
    from repro.core import buckets as bk
    table = chip((2 ** LSH_BITS, STARTS - 1), i32)
    state = bk.BucketState(ids=table, stamp=table, tag=table,
                           step=chip((), i32))
    lanes = chip((B,), i32)
    text = bk.publish.lower(state, lanes, lanes, lanes).compile().as_text()
    # instructions only: the metadata's stack frames name this test
    assert " while(" in text and " scatter(" not in text
