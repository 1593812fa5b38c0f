"""Fused traversal-hop kernel — one Pallas dispatch per beam-search hop.

Algorithm 1's inner loop is the hot path every tier shares, and the
unfused implementation pays for it piecewise: a neighbor gather
(a jnp table gather, or ``gather_distance``'s in-kernel row DMAs),
a distance kernel (``l2_distance`` / ``pq_adc``), and jnp top-k merge
glue in ``core/beam_search.py`` — three-plus dispatches and HBM
round-trips per hop.  This kernel fuses the whole hop:

  * **gather** — the grid is one step per tile of ``LANE_TILE`` query
    lanes; each step takes its lanes' candidate ids as an SMEM block
    and puts one async copy per candidate row in flight (HBM -> VMEM
    scratch) before waiting on any, the DMA-overlap structure of
    DiskANN's SSD read.  Rows are gathered from a ``row_table`` view
    of the table, in which every row is a legal DMA source,
  * **distance** — computed on the VMEM-resident rows, either
    full-precision squared L2 against the lane's query or the PQ-ADC
    LUT sum against the lane's per-query lookup table, summed in
    ``repro.distance``'s order, which the unfused hop's XLA code also
    follows, so the two return the same floats on a TPU too,
  * **merge** — the per-lane top-L beam merge (dedup against the beam,
    dedup among candidates, stable ascending selection) runs in the
    same kernel and writes the NEW beam (ids / dists / expanded) plus
    the fresh-distance count, so no jnp ``argsort`` glue remains.

The merge replicates ``core.beam_search._merge`` **bit-exactly**: the
selection loop picks the first minimum each round (= stable argsort
order), +inf slots collapse to (id=-1, expanded=True), and the fresh
count excludes beam duplicates and intra-candidate duplicates — CI
asserts ids/dists equality against the unfused path on every tier.
It is written as masked vector ops over the lane tile (no dynamic
indexing), which is what the chip's compiler can lay out; the block
shapes follow its tiling rule (last two dims multiples of (8, 128) or
whole), so the batch is padded to whole tiles with no-op lanes.

Lane divergence: a lane whose candidate row is all ``-1`` (converged
lanes in a fixed-shape serving batch) skips its gather DMAs entirely
(``pl.when``) and its merge degenerates to re-emitting the sorted beam
— a masked no-op, so batched multi-query traffic rides one kernel at
any divergence.

Off-TPU the public wrappers run with ``interpret=True``
(``platform.interpret_mode``): CPU CI executes the very same kernel
body.  The pure-jnp oracle is ``ref.fused_hop_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.distance import ordered_sum, sq_l2
from repro.kernels.platform import interpret_mode


LANE_TILE = 8   # query lanes per grid step: the f32 sublane count


def _merge_into_beam(cand_ids, cand_d, beam_ids, beam_d, beam_exp, *, c, l):
    """Shared merge tail over a lane tile: dedup + stable top-L selection.

    Every operand is a (tile, ·) array with one query lane per row, and
    the merge is written as masked vector ops (no dynamic indexing) so
    the chip's compiler can lay it out.  ``beam_exp`` carries the
    expanded flags as int32; the jit wrappers cast at the boundary.
    Returns (ids, dists, exp, n_fresh) with n_fresh shaped (tile, 1).
    """
    in_beam = jnp.zeros(cand_ids.shape, bool)
    for j in range(l):
        bj = beam_ids[:, j:j + 1]
        in_beam = in_beam | ((cand_ids == bj) & (bj >= 0))
    pos_c = jax.lax.broadcasted_iota(jnp.int32, cand_ids.shape, 1)
    dup = in_beam
    for j in range(c - 1):
        dup = dup | ((cand_ids == cand_ids[:, j:j + 1]) & (pos_c > j))
    fresh = ~dup & (cand_ids >= 0)
    cand_d = jnp.where(fresh, cand_d, jnp.inf)

    # Stable ascending top-L over the concatenation [beam, candidates]:
    # each round takes the FIRST minimum (beam slots before candidates,
    # lower slots first) and masks it to +inf — exactly stable-argsort
    # order.  All-inf rounds emit (-1, inf, 1), matching _merge's
    # invalid-slot collapse.
    pos_b = jax.lax.broadcasted_iota(jnp.int32, beam_ids.shape, 1)
    big = jnp.int32(l + c)
    work_b, work_c = beam_d, cand_d
    out_ids = jnp.full(beam_ids.shape, -1, jnp.int32)
    out_d = jnp.full(beam_d.shape, jnp.inf, jnp.float32)
    out_exp = jnp.ones(beam_ids.shape, jnp.int32)
    for s in range(l):
        m = jnp.minimum(jnp.min(work_b, axis=1, keepdims=True),
                        jnp.min(work_c, axis=1, keepdims=True))
        ib = jnp.min(jnp.where(work_b == m, pos_b, big), axis=1,
                     keepdims=True)
        ic = jnp.min(jnp.where(work_c == m, pos_c, big), axis=1,
                     keepdims=True)
        from_b = ib < big
        hit_b = from_b & (pos_b == ib)
        hit_c = ~from_b & (pos_c == ic)
        sel_id = (jnp.sum(jnp.where(hit_b, beam_ids, 0), axis=1,
                          keepdims=True)
                  + jnp.sum(jnp.where(hit_c, cand_ids, 0), axis=1,
                            keepdims=True))
        sel_exp = jnp.sum(jnp.where(hit_b, beam_exp, 0), axis=1,
                          keepdims=True)
        invalid = m == jnp.inf
        slot = pos_b == s
        out_ids = jnp.where(slot, jnp.where(invalid, -1, sel_id), out_ids)
        out_d = jnp.where(slot, m, out_d)
        out_exp = jnp.where(slot, jnp.where(invalid, 1, sel_exp), out_exp)
        work_b = jnp.where(hit_b, jnp.inf, work_b)
        work_c = jnp.where(hit_c, jnp.inf, work_c)
    n_fresh = jnp.sum(fresh.astype(jnp.int32), axis=1, keepdims=True)
    return out_ids, out_d, out_exp, n_fresh


def gather_rows(ids_ref, rows_ref, xs_ref, sem, *, tile, c, first=0):
    """Gather every lane's candidate rows (HBM -> VMEM scratch
    ``(tile, c, 1, width)``): one async copy per row, all in flight
    before the first wait.  Lane ``t`` reads its ids from SMEM row
    ``first + t``.  A lane with no valid candidate (a converged lane in
    a divergent batch) issues no copy at all.  Invalid ids fetch row 0;
    their distances are masked to +inf afterwards."""
    def live(t):
        # -1s may sit anywhere (a missed catapult slot precedes valid
        # fallbacks), so scan the whole SMEM row
        return jax.lax.fori_loop(
            0, c, lambda j, hi: jnp.maximum(hi, ids_ref[first + t, j]),
            jnp.int32(-1)) >= 0

    def copy(t, j):
        row = jnp.maximum(ids_ref[first + t, j], 0)
        return pltpu.make_async_copy(rows_ref.at[row], xs_ref.at[t, j], sem)

    def each_copy(op):
        def lane(t, carry):
            @pl.when(live(t))
            def _():
                def one(j, cj):
                    op(copy(t, j))
                    return cj
                jax.lax.fori_loop(0, c, one, 0)
            return carry
        jax.lax.fori_loop(0, tile, lane, 0)

    each_copy(lambda cp: cp.start())
    each_copy(lambda cp: cp.wait())


def _write(outs, oids_ref, odists_ref, oexp_ref, onf_ref):
    ids, dists, exp, n_fresh = outs
    oids_ref[...] = ids
    odists_ref[...] = dists
    oexp_ref[...] = exp
    onf_ref[...] = n_fresh


def _l2_hop_kernel(ids_smem, cand_ref, q_ref, bids_ref, bdists_ref,
                   bexp_ref, vec_ref, oids_ref, odists_ref, oexp_ref,
                   onf_ref, xs_ref, sem, *, tile, c, l):
    gather_rows(ids_smem, vec_ref, xs_ref, sem, tile=tile, c=c)
    # rows and queries are zero-padded to whole 128-lane tiles, which
    # leaves sq_l2's sum (the unfused hop's order) unchanged
    x = xs_ref[...][:, :, 0, :]                       # (tile, c, W')
    cand_ids = cand_ref[...]
    cand_d = sq_l2(x, q_ref[...][:, None, :])
    cand_d = jnp.where(cand_ids < 0, jnp.inf, cand_d)
    _write(_merge_into_beam(cand_ids, cand_d, bids_ref[...],
                            bdists_ref[...], bexp_ref[...], c=c, l=l),
           oids_ref, odists_ref, oexp_ref, onf_ref)


def _pq_hop_kernel(ids_smem, cand_ref, lut_ref, bids_ref, bdists_ref,
                   bexp_ref, codes_ref, oids_ref, odists_ref, oexp_ref,
                   onf_ref, xs_ref, sem, *, tile, c, l, width):
    gather_rows(ids_smem, codes_ref, xs_ref, sem, tile=tile, c=c)
    codes = xs_ref[...][:, :, 0, :width]               # (tile, c, M) int32
    lut = lut_ref[...].astype(jnp.float32)             # (tile, M, K)
    m, k = lut.shape[1:]
    cand_ids = cand_ref[...]
    # LUT gather as a one-hot select-sum per subspace (exact: one
    # nonzero term), then the same sum over subspaces as pq.adc_dist_fn
    centroid = jax.lax.broadcasted_iota(jnp.int32, (1, 1, k), 2)
    cand_d = ordered_sum(
        jnp.sum(jnp.where(codes[:, :, j:j + 1] == centroid,
                          lut[:, j:j + 1, :], 0.0), axis=-1)
        for j in range(m))
    cand_d = jnp.where(cand_ids < 0, jnp.inf, cand_d)
    _write(_merge_into_beam(cand_ids, cand_d, bids_ref[...],
                            bdists_ref[...], bexp_ref[...], c=c, l=l),
           oids_ref, odists_ref, oexp_ref, onf_ref)


def _pad_lanes(b, cand_ids, beam_ids, beam_dists, beam_exp, *per_lane):
    """Pad the batch to a whole number of lane tiles with no-op lanes
    (all ``-1`` candidates, an empty beam)."""
    pad = (-b) % LANE_TILE
    if pad == 0:
        return (cand_ids, beam_ids, beam_dists, beam_exp.astype(jnp.int32),
                *per_lane)

    def grow(x, value):
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=value)

    return (grow(cand_ids, -1), grow(beam_ids, -1),
            grow(beam_dists, jnp.inf), grow(beam_exp.astype(jnp.int32), 1),
            *(grow(x, 0) for x in per_lane))


def row_table(table: jax.Array) -> jax.Array:
    """(N, W) table -> (N, 1, W') row table the hop kernels gather from.

    The chip lays a 2-D f32 table out in (8, 128) tiles, so one row is
    not a legal DMA source; as (N, 1, W') every row is its own tile
    row.  W' rounds W up to whole 128-lane tiles (a DMA moves whole
    tiles).  Building it copies the table once: callers that hop many
    times build it once, outside their loop."""
    n, w = table.shape
    pad = (-w) % 128
    if pad:
        table = jnp.pad(table, ((0, 0), (0, pad)))
    return table.reshape(n, 1, w + pad)


def _hop_call(kernel, rows, lane_arr, cand_ids, beam_ids, beam_dists,
              beam_exp, *, interpret):
    """Run one fused hop kernel over lane tiles of ``LANE_TILE`` lanes.

    ``rows`` is a ``row_table``; ``lane_arr`` is the per-lane operand
    ((B, W') queries or (B, M, K) LUTs), one block of lanes per step.
    Candidate ids ride twice: as an SMEM block (DMA addresses) and as a
    VMEM block (dedup and merge)."""
    b, c = cand_ids.shape
    l = beam_ids.shape[1]
    cand_p, bids_p, bd_p, bexp_p, lane_p = _pad_lanes(
        b, cand_ids, beam_ids, beam_dists, beam_exp, lane_arr)
    bp = cand_p.shape[0]
    tile = LANE_TILE
    lane_block = lane_arr.shape[1:]
    zeros = (0,) * len(lane_block)
    row = lambda w: pl.BlockSpec((tile, w), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(kernel, tile=tile, c=c, l=l),
        grid=(bp // tile,),
        in_specs=[
            pl.BlockSpec((tile, c), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),           # DMA addresses
            row(c),                                          # candidate ids
            pl.BlockSpec((tile,) + lane_block,
                         lambda i: (i,) + zeros),            # query / LUT
            row(l), row(l), row(l),                          # beam state
            pl.BlockSpec(memory_space=pl.ANY),              # row table
        ],
        out_specs=[row(l), row(l), row(l), row(1)],
        out_shape=[
            jax.ShapeDtypeStruct((bp, l), jnp.int32),    # new beam ids
            jax.ShapeDtypeStruct((bp, l), jnp.float32),  # new beam dists
            jax.ShapeDtypeStruct((bp, l), jnp.int32),    # new expanded flags
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),    # fresh-distance counts
        ],
        scratch_shapes=[pltpu.VMEM((tile, c) + rows.shape[1:], rows.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
    )(cand_p, cand_p, lane_p, bids_p, bd_p, bexp_p, rows)
    return out[0][:b], out[1][:b], out[2][:b].astype(bool), out[3][:b, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_hop_l2_rows(rows, queries, cand_ids, beam_ids, beam_dists,
                       beam_exp, *, interpret):
    pad = rows.shape[-1] - queries.shape[1]       # to the rows' width W'
    queries = jnp.pad(queries, ((0, 0), (0, pad)))
    return _hop_call(_l2_hop_kernel, rows, queries, cand_ids, beam_ids,
                     beam_dists, beam_exp, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _fused_hop_pq_rows(rows, luts, cand_ids, beam_ids, beam_dists,
                       beam_exp, *, width, interpret):
    return _hop_call(functools.partial(_pq_hop_kernel, width=width), rows,
                     luts, cand_ids, beam_ids, beam_dists, beam_exp,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_hop_l2(vectors: jax.Array, cand_ids: jax.Array, queries: jax.Array,
                 beam_ids: jax.Array, beam_dists: jax.Array,
                 beam_exp: jax.Array, *, interpret: bool = False):
    """One fused L2 hop for a whole batch.

    Args:
      vectors: (N, d) float table, stays in HBM (ANY memory space).
      cand_ids: (B, C) int32 candidate ids (a lane's adjacency row, or
        its start-point set), -1 padded; an all-``-1`` lane no-ops.
      queries: (B, d) query batch.
      beam_ids / beam_dists / beam_exp: (B, L) current beam state.

    Returns (new_ids, new_dists, new_exp, n_fresh) matching
    ``_merge`` applied per lane with ``l2_dist_fn`` distances.
    """
    return _fused_hop_l2_rows(row_table(vectors), queries, cand_ids,
                              beam_ids, beam_dists, beam_exp,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_hop_pq(luts: jax.Array, codes: jax.Array, cand_ids: jax.Array,
                 beam_ids: jax.Array, beam_dists: jax.Array,
                 beam_exp: jax.Array, *, interpret: bool = False):
    """One fused PQ-ADC hop for a whole batch.

    Args:
      luts: (B, M, K) per-query ADC lookup tables (``pq.query_lut``).
      codes: (N, M) int32 PQ code table, stays in HBM.
      cand_ids / beam_*: as in :func:`fused_hop_l2`.
    """
    return _fused_hop_pq_rows(row_table(codes), luts, cand_ids, beam_ids,
                              beam_dists, beam_exp, width=codes.shape[1],
                              interpret=interpret)


# ---------------------------------------------------------------------------
# dist_fn-level hop backends — the plug core.beam_search dispatches on.
#
# A backend IS a dist_fn (callable (q, ids) -> dists, so catapult
# entry-point scoring and any unfused fallback behave identically) that
# additionally carries the table state the fused kernel gathers from and
# exposes ``hop_batch`` — the whole-batch fused hop.  ``beam_search``
# duck-types on ``is_fused_hop`` so core never imports kernels.
# ---------------------------------------------------------------------------

class FusedL2Hop:
    """Full-precision L2 hop backend over an HBM vector table."""

    is_fused_hop = True

    def __init__(self, vectors: jax.Array):
        self.vectors = vectors
        self.rows = row_table(vectors)     # once per search, not per hop

    def __call__(self, q: jax.Array, ids: jax.Array) -> jax.Array:
        d = sq_l2(self.vectors[jnp.maximum(ids, 0)], q[None, :])
        return jnp.where(ids < 0, jnp.inf, d)

    def hop_batch(self, queries, cand_ids, beam_ids, beam_dists, beam_exp):
        return _fused_hop_l2_rows(self.rows, queries, cand_ids, beam_ids,
                                  beam_dists, beam_exp,
                                  interpret=interpret_mode())


class FusedPQHop:
    """PQ-ADC hop backend over an HBM code table + per-query LUTs."""

    is_fused_hop = True

    def __init__(self, codebook, codes: jax.Array):
        self.codebook = codebook
        self.codes = codes
        self.rows = row_table(codes)       # once per search, not per hop

    def __call__(self, q: jax.Array, ids: jax.Array) -> jax.Array:
        from repro.core.pq import adc_dist_fn    # lazy: kernels stay leaf-like
        return adc_dist_fn(self.codebook, self.codes)(q, ids)

    def hop_batch(self, queries, cand_ids, beam_ids, beam_dists, beam_exp):
        from repro.core.pq import query_lut
        luts = jax.vmap(query_lut, in_axes=(None, 0))(self.codebook, queries)
        return _fused_hop_pq_rows(self.rows, luts, cand_ids, beam_ids,
                                  beam_dists, beam_exp,
                                  width=self.codes.shape[1],
                                  interpret=interpret_mode())
