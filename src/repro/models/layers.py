"""Shared building blocks: norms, embeddings, rotary, gated MLPs.

Hand-rolled functional JAX (params = pytrees of arrays) so that layer
stacking, scan-over-layers, and pjit sharding annotations stay fully
explicit.  Initializers return (params, partition-spec) pairs built from
the same shape description, keeping dry-run specs and smoke-test arrays
in lockstep.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Any  # nested dict of arrays (or ShapeDtypeStructs in dry-run)


# --------------------------------------------------------------------------
# Param declaration: each leaf is (shape, pspec, init_scale)
# --------------------------------------------------------------------------

def decl(shape, pspec, scale=None, dtype=None, init=None):
    """Param/state declaration.  init: 'normal' (scale != None default),
    'ones' (scale None default — norm gammas), or 'zeros' (caches)."""
    if init is None:
        init = "ones" if scale is None else "normal"
    return {"__leaf__": True, "shape": tuple(shape), "pspec": pspec,
            "scale": scale, "dtype": dtype, "init": init}


def is_leaf_decl(x):
    return isinstance(x, dict) and x.get("__leaf__", False)


def init_from_decl(tree, key, dtype):
    """Materialize real arrays (smoke tests / examples)."""
    leaves = [p for p in jax.tree_util.tree_leaves(
        tree, is_leaf=is_leaf_decl) if is_leaf_decl(p)]
    keys = jax.random.split(key, max(len(leaves), 1))
    it = iter(keys)

    def make(d):
        k = next(it)
        shape = d["shape"]
        dt = d.get("dtype") or dtype
        kind = d.get("init", "ones" if d["scale"] is None else "normal")
        if kind == "zeros":
            return jnp.zeros(shape, dt)
        if kind == "ones":
            return jnp.ones(shape, dt)
        fan_in = shape[0] if len(shape) >= 2 else 1
        s = d["scale"] / (fan_in ** 0.5)
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    return jax.tree_util.tree_map(make, tree, is_leaf=is_leaf_decl)


def specs_from_decl(tree, dtype):
    """ShapeDtypeStructs (dry-run) — no allocation."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d["shape"], d.get("dtype") or dtype),
        tree, is_leaf=is_leaf_decl)


def pspecs_from_decl(tree):
    return jax.tree_util.tree_map(lambda d: d["pspec"], tree,
                                  is_leaf=is_leaf_decl)


def stack_decl(tree, n):
    """Prepend a layer axis (scan-over-layers stacking) to every leaf."""
    def bump(d):
        spec = d["pspec"]
        return decl((n,) + d["shape"], P(*((None,) + tuple(spec))),
                    d["scale"])
    return jax.tree_util.tree_map(bump, tree, is_leaf=is_leaf_decl)


# --------------------------------------------------------------------------
# Ops
# --------------------------------------------------------------------------

def maybe_shard(x, spec):
    """Best-effort with_sharding_constraint.

    Per-dimension, axes missing from the active mesh are dropped and axes
    whose product does not divide the dimension are dropped — so the same
    model code runs under pjit on any production mesh and on the single
    bare CPU device in smoke tests.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    fixed = []
    for dim, entry in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if entry is None:
            fixed.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        axes = tuple(a for a in axes if a in names)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if not axes or size <= 1 or dim % size != 0:
            fixed.append(None)
        else:
            fixed.append(axes if len(axes) > 1 else axes[0])
    if all(f is None for f in fixed):
        return x
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def shard_residual(x):
    """Sequence-parallel sharding of the residual stream (B, S, D).

    Between blocks, activations need not be replicated across the tensor-
    parallel axis: sharding the sequence over `model` (Megatron-LM SP)
    divides the per-layer scan-carry stash — the dominant train-time
    memory term — by the TP degree.  GSPMD inserts the all-gather /
    reduce-scatter pair at each block boundary.  No-op off-mesh or when
    dims don't divide (decode S=1, batch=1).
    """
    if x.ndim != 3:
        return x
    return maybe_shard(x, P(("pod", "data"), "model", None))


def rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * gamma


def softcap(x, cap):
    return jnp.tanh(x / cap) * cap if cap else x


def rope(x, positions, theta):
    """Rotary embedding.  x: (..., S, H, Dh); positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    ang = ang[..., None, :]                                # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def gated_mlp_decl(d_model, d_ff):
    return {
        "wi": decl((d_model, 2 * d_ff), P(None, "model"), 1.0),
        "wo": decl((d_ff, d_model), P("model", None), 1.0),
    }


def gated_mlp(params, x, kind="swiglu"):
    h = x @ params["wi"]
    gate, up = jnp.split(h, 2, axis=-1)
    act = jax.nn.gelu(gate, approximate=True) if kind == "geglu" \
        else jax.nn.silu(gate)
    return (act * up) @ params["wo"]


def padded_vocab(vocab: int) -> int:
    """Pad the vocab to a multiple of 256 so the embedding table shards
    over any TP degree up to 256 (MaxText-style vocab padding)."""
    return -(-vocab // 256) * 256


def embed_decl(vocab, d_model):
    return {"table": decl((padded_vocab(vocab), d_model),
                          P("model", None), 1.0)}


def embed_lookup(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x, *, cap=None, vocab=None):
    """x @ E^T with softcap; padded vocab columns masked to -1e9 (after the
    cap — they must stay out of every softmax/argmax/logsumexp)."""
    logits = softcap(x @ params["table"].T, cap)
    vpad = params["table"].shape[0]
    if vocab is not None and vocab != vpad:
        mask = jnp.arange(vpad) < vocab
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    return logits
