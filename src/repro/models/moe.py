"""Mixture-of-Experts layer: top-k routing, sort-based dispatch, EP sharding.

Covers both assigned MoE archs:
  * arctic-480b      — 128 experts, top-2, dense residual MLP in parallel
  * deepseek-moe-16b — 64 routed experts top-6 + 2 shared experts,
                       leading dense layer(s)

Dispatch is *sort-based* (argsort by expert id + capacity cutoff), not
the dense GShard one-hot einsum: at 1M tokens × 128 experts the dense
dispatch tensor is O(T·E·C) — petabytes — while the sort is O(T·K log).
Tokens beyond an expert's capacity are dropped (standard capacity-factor
semantics); combine weights renormalize over the surviving experts.

Experts are sharded over the `model` mesh axis (EP).  Under pjit the
(E, C, D) dispatch scatter crosses shards and XLA inserts the
all-to-all; the shard_map variant with explicit collectives is a
recorded hillclimb lever.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import decl, gated_mlp, maybe_shard


def moe_decl(cfg):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    # Expert weights shard over `model` (EP) AND `data` (FSDP/ZeRO-3):
    # at arctic-480b scale the experts are 60 GiB/chip under EP alone.
    # The per-layer shard_map regathers the data-sharded slice just-in-
    # time inside the layer scan (one layer live at a time).
    out = {
        "router": decl((d, e), P(None, None), 1.0),
        "wi": decl((e, d, 2 * f), P("model", None, ("data",)), 1.0),
        "wo": decl((e, f, d), P("model", ("data",), None), 1.0),
    }
    if cfg.n_shared_experts:
        out["shared"] = {
            "wi": decl((d, 2 * f * cfg.n_shared_experts), P(None, "model"), 1.0),
            "wo": decl((f * cfg.n_shared_experts, d), P("model", None), 1.0),
        }
    if cfg.dense_residual:
        out["dense"] = {
            "wi": decl((d, 2 * cfg.d_ff), P(None, "model"), 1.0),
            "wo": decl((cfg.d_ff, d), P("model", None), 1.0),
        }
    return out


def _capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(n_tokens * top_k * factor / n_experts)
    # multiple of 512 so the capacity axis shards over data×(pod) too —
    # the (E, C, D) buffers carry GLOBAL capacity and would otherwise
    # replicate per chip (hundreds of GiB at 1M tokens × 128 experts)
    mult = 512 if c >= 512 else 8
    return max(8, -(-c // mult) * mult)


def _route(xt, router, e, k, cap, *, expert_lo=0, expert_hi=None):
    """Top-k routing + capacity positions for experts in [lo, hi).

    Returns (flat_e, pos, keep, tok_idx, gate_vals, probs) with `keep`
    false for slots outside [lo, hi) or beyond capacity.  Positions are
    computed per GLOBAL expert (stable sort), so every shard agrees.
    """
    t = xt.shape[0]
    expert_hi = e if expert_hi is None else expert_hi
    logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    flat_e = gate_idx.reshape(t * k)
    order = jnp.argsort(flat_e)
    pos_sorted = jnp.cumsum(jnp.ones_like(flat_e)) - 1
    seg_start = jnp.searchsorted(flat_e[order], jnp.arange(e), side="left")
    pos_sorted = pos_sorted - seg_start[flat_e[order]]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    keep = (pos < cap) & (flat_e >= expert_lo) & (flat_e < expert_hi)
    tok_idx = jnp.arange(t * k) // k
    return flat_e, pos, keep, tok_idx, gate_vals, probs


def _expert_ffn(buf, wi, wo, mlp_kind):
    h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(buf.dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    act = jax.nn.silu(gate) if mlp_kind == "swiglu" \
        else jax.nn.gelu(gate, approximate=True)
    return jnp.einsum("ecf,efd->ecd", act * up, wo.astype(buf.dtype))


def _moe_local(params, xt, cfg, mlp_kind, e_lo, e_local, cap):
    """Dispatch/compute/combine for experts [e_lo, e_lo + e_local).

    e_lo may be traced (shard offset); e_local is static (buffer shape).
    Returns (partial y, aux) — y covers only these experts' contribution.

    Dispatch is *slot-compacted*: routed slots are keyed by
    (expert · cap + position); an argsort brings this shard's ≤
    e_local·cap slots to the front, so every (T·K, D)-sized gather /
    scatter collapses to (e_local·cap, D) — 10–20× smaller at arctic
    scale, and the backward scatter-adds shrink with it.
    """
    e, k = cfg.n_experts, cfg.top_k
    t, d = xt.shape
    flat_e, pos, keep, tok_idx, gate_vals, probs = _route(
        xt, params["router"], e, k, cap, expert_lo=e_lo,
        expert_hi=e_lo + e_local)
    n_slots = e_local * cap
    big = jnp.int32(2 ** 30)
    # keys are contiguous per expert (positions are cumsum ranks), so the
    # first n_slots sorted entries are exactly this shard's buffer slots.
    keys = jnp.where(keep, flat_e * cap + pos, big)
    order = jnp.argsort(keys)[:n_slots]                     # (n_slots,)
    k_sel = keys[order]
    valid = k_sel < big
    slot = jnp.where(valid, k_sel - e_lo * cap, n_slots)    # OOB drops
    src_tok = tok_idx[order]                                # (n_slots,)
    buf = jnp.zeros((n_slots, d), xt.dtype)
    buf = buf.at[slot].set(xt[src_tok], mode="drop")
    out = _expert_ffn(buf.reshape(e_local, cap, d), params["wi"],
                      params["wo"], mlp_kind).reshape(n_slots, d)
    # combine: scatter each slot's output back to its token, weighted
    w_slot = gate_vals.reshape(t * k)[order].astype(xt.dtype)
    contrib = out[jnp.where(valid, slot, 0)] * w_slot[:, None]
    contrib = jnp.where(valid[:, None], contrib, 0)
    y = jnp.zeros((t, d), xt.dtype).at[
        jnp.where(valid, src_tok, t)].add(contrib, mode="drop")
    # Switch-style load-balance aux (identical on every shard: global stats)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,)).at[flat_e].add(
        (pos < cap).astype(jnp.float32)) / t
    aux = e * jnp.sum(me * ce) / k
    return y, aux


def moe_layer(params, x, cfg, *, mlp_kind="swiglu"):
    """x: (B, S, D) -> (B, S, D).  Returns (y, load-balance aux loss).

    Two execution paths:
      * no mesh / model axis absent -> single-device dispatch (smoke tests);
      * mesh with `model` -> shard_map EP+TP: tokens replicate within each
        model group, every shard dispatches ONLY its E/model_size experts
        locally (local capacity — the (E, C, D) buffers stay per-shard
        sized) and computes the shared/dense MLPs on its tensor-parallel
        slice; a single psum over `model` combines everything.  No global
        (E, C_global, D) buffer ever exists, which is what lets
        arctic-480b's 128-expert layers fit at 1M-token steps.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    mesh = jax.sharding.get_abstract_mesh()
    use_smap = (not mesh.empty and "model" in mesh.axis_names
                and e % mesh.shape["model"] == 0
                and mesh.shape["model"] > 1)

    if not use_smap:
        xt = x.reshape(b * s, d)
        cap = _capacity(b * s, e, k, cfg.capacity_factor)
        y, aux = _moe_local(params, xt, cfg, mlp_kind, 0, e, cap)
        if "shared" in params:
            y = y + gated_mlp(params["shared"], xt, mlp_kind)
        if "dense" in params:
            y = y + gated_mlp(params["dense"], xt, mlp_kind)
        return y.reshape(b, s, d), aux

    n_ep = mesh.shape["model"]
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    t_loc = (b // max(1, _axes_size(mesh, ba))) * s
    cap = _capacity(t_loc, e, k, cfg.capacity_factor)

    def local(router, wi, wo, shared, dense, x_loc):
        bl, sl, _ = x_loc.shape
        xt = x_loc.reshape(bl * sl, d)
        me = jax.lax.axis_index("model")
        e_loc = e // n_ep
        p_loc = {"router": router, "wi": wi, "wo": wo}
        y, aux = _moe_local(p_loc, xt, cfg, mlp_kind, me * e_loc, e_loc,
                            cap)
        # TP slices of the shared experts / dense residual join the psum
        if shared is not None:
            y = y + gated_mlp(shared, xt, mlp_kind)
        if dense is not None:
            y = y + gated_mlp(dense, xt, mlp_kind)
        y = jax.lax.psum(y, "model")
        aux = aux  # identical on all model shards (global routing stats)
        return y.reshape(bl, sl, d), aux

    pspec = {"router": P(None, None), "wi": P("model", None, None),
             "wo": P("model", None, None)}
    shared_spec = ({"wi": P(None, "model"), "wo": P("model", None)}
                   if "shared" in params else None)
    dense_spec = ({"wi": P(None, "model"), "wo": P("model", None)}
                  if "dense" in params else None)
    y, aux = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(pspec["router"], pspec["wi"], pspec["wo"], shared_spec,
                  dense_spec, P(ba, None, None)),
        out_specs=(P(ba, None, None), P()),
    )(params["router"], params["wi"], params["wo"],
      params.get("shared"), params.get("dense"), x)
    return y, aux


def _axes_size(mesh, axes) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size
