"""FedADP baseline [6]: adaptive pruning with the *neuron* as pruning unit.

Each client uploads only its most-changed neurons (rows of weight matrices /
conv output channels); the server aggregates element-wise over the uploaded
entries. This is the finer-granularity comparison point the paper contrasts
with FedLDF's layer-granularity selection (paper §III, pruning ratio chosen
for equal communication overhead).

Implemented for the stacked (vmap-client) layout used by the CIFAR-scale
experiments.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any


def _neuron_axis_scores(delta: jnp.ndarray) -> jnp.ndarray:
    """Importance per output-neuron (last axis) = L2 over all other axes."""
    if delta.ndim == 1:
        return jnp.abs(delta)
    axes = tuple(range(delta.ndim - 1))
    return jnp.sqrt(jnp.sum(delta.astype(jnp.float32) ** 2, axis=axes))


def neuron_masks(client_params: Pytree, global_params: Pytree,
                 keep_frac: float) -> Pytree:
    """Per-leaf {0,1} masks keeping the top ``keep_frac`` of output neurons
    by update magnitude. client_params leaves have NO client axis here
    (call under vmap)."""

    def mask_leaf(theta, g):
        delta = theta.astype(jnp.float32) - g.astype(jnp.float32)
        scores = _neuron_axis_scores(delta)          # (out,)
        out = scores.shape[0]
        n_keep = max(1, int(round(keep_frac * out)))
        _, idx = jax.lax.top_k(scores, n_keep)
        kept = jax.nn.one_hot(idx, out, dtype=jnp.float32).sum(axis=0)
        return jnp.broadcast_to(kept, theta.shape)

    return jax.tree.map(mask_leaf, client_params, global_params)


def fedadp_psum_parts(stacked_params: Pytree, global_params: Pytree,
                      data_sizes: jnp.ndarray,
                      keep_frac: float) -> tuple[Pytree, Pytree]:
    """Element-wise masked aggregation over the client axis, first half:
    masked numerators ``Σ_k θ·m·w`` and element-wise denominators
    ``Σ_k m·w`` over the stacked clients (leaves ``(K, ...)``; this
    device's clients on a mesh). Both are additive over the client axis,
    so a mesh psums the per-device partials before
    :func:`fedadp_psum_finalize`, and matches one device up to fp32
    reduction order. The denominator is a param-structured tree — the
    engine shards it alongside the numerators on 2-D meshes."""
    masks = jax.vmap(lambda p: neuron_masks(p, global_params, keep_frac))(
        stacked_params)
    w = data_sizes.astype(jnp.float32)

    def wx_for(theta):
        return w.reshape((-1,) + (1,) * (theta.ndim - 1))

    numer = jax.tree.map(
        lambda theta, m: jnp.sum(theta.astype(jnp.float32) * m
                                 * wx_for(theta), axis=0),
        stacked_params, masks)
    denom = jax.tree.map(
        lambda theta, m: jnp.sum(m * wx_for(theta), axis=0),
        stacked_params, masks)
    return numer, denom


def fedadp_psum_finalize(numer: Pytree, denom: Pytree,
                         global_params: Pytree) -> Pytree:
    """Second half: element-wise division with fallback to the previous
    global value where no client uploaded an entry. Element-wise,
    so it is shard-safe (runs on 1/M 'model'-axis slices unchanged)."""

    def combine(n, d, g):
        agg = jnp.where(d > 0, n / jnp.where(d > 0, d, 1.0),
                        g.astype(jnp.float32))
        return agg.astype(g.dtype)

    return jax.tree.map(combine, numer, denom, global_params)


def comm_bytes(global_params: Pytree, num_clients: int,
               keep_frac: float) -> float:
    """Modeled uplink bytes per round: kept neurons + per-neuron index
    overhead (4 B each, standard sparse-upload encoding)."""
    total = 0.0
    for leaf in jax.tree.leaves(global_params):
        out = leaf.shape[-1] if leaf.ndim >= 1 else 1
        n_keep = max(1, int(round(keep_frac * out)))
        per_neuron = leaf.size // out * leaf.dtype.itemsize
        total += n_keep * (per_neuron + 4)
    return num_clients * total
