"""Model aggregation (paper Eqs. 1, 5, 6).

Two execution layouts are supported:

- **stacked** (``vmap`` client mode): all K client models are materialised
  with a leading client axis; aggregation is a masked weighted mean over that
  axis (small models — the paper's own VGG-9 regime).
- **streaming** (``scan`` client mode): clients are visited sequentially and
  added into a float32 accumulator with per-unit weights (large models; a
  strategy that selects from every client's score trains each client
  again for it).
- **top-n slots** (``scan`` client mode, FedLDF): as clients are visited,
  each unit keeps its n most divergent locals so far (Eq. 4's top-n), and
  Eq. 5 reduces the slots once selection is known, so no client trains
  twice.

The stacked layout aggregates in two halves, additive partials
(:func:`stacked_psum_parts`) and their epilogue
(:func:`stacked_psum_finalize`), so a *client-sharded* round puts one
``psum`` between them: each device pre-reduces its local clients, then
numerators and denominators are ``psum``'d across the mesh so every device
holds the same new global model.

All layouts compute Eq. 5
``Ĝ_u = Σ_k s[k,u]·w_k·Θ_{k,u} / Σ_m s[m,u]·w_m``.

With ``s ≡ 1`` this is exactly FedAvg (Eq. 1) — tested as the n=K degeneracy
of Theorem 1.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.units import UnitMap, tree_cast, tree_zeros_like

Pytree = Any


def unit_weights(selection: jnp.ndarray,
                 data_sizes: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(client, unit) aggregation weights and per-unit denominators.

    selection: (K, U) ∈ {0,1}; data_sizes: (K,) |D_k|.
    Returns (numer_w: (K, U), denom: (U,)) with
    ``numer_w[k,u] = s[k,u]·|D_k|`` and ``denom[u] = Σ_m s[m,u]·|D_m|``.
    """
    w = selection * data_sizes[:, None].astype(jnp.float32)
    return w, w.sum(axis=0)


def aggregate_stacked(stacked_params: Pytree, umap: UnitMap,
                      selection: jnp.ndarray, data_sizes: jnp.ndarray,
                      fallback: Pytree | None = None,
                      axis_name: str | None = None) -> Pytree:
    """Eq. 5 over client-stacked params (every leaf has leading K).

    ``fallback`` (usually the previous global model) is used for any unit
    whose denominator is zero (cannot happen with top-n selection, which
    guarantees n ≥ 1 clients per unit, but can with dropout-style policies).

    It is :func:`stacked_psum_parts` then :func:`stacked_psum_finalize`,
    the two halves every round aggregates through. ``axis_name`` puts a
    ``psum`` between them: the cross-device reduction of a client-sharded
    round (``shard_map`` over a ``'clients'`` mesh axis), where the inputs
    are the *local* shard — ``selection``/``data_sizes`` rows and stacked
    leaves for this device's K/D clients. The numerators of every leaf
    AND the Eq. 5 denominator then travel in **one fused psum** (a pytree
    collective) — one cross-device rendezvous per round instead of one
    per parameter leaf. The division by Σ_m s·w_m happens after the
    collective, so the sharded result matches the unsharded call up to
    fp32 summation order.
    """
    parts, denom = stacked_psum_parts(stacked_params, umap, selection,
                                      data_sizes)
    if axis_name is not None:
        parts, denom = jax.lax.psum((parts, denom), axis_name)
    return stacked_psum_finalize(parts, denom, umap, stacked_params,
                                 fallback)


def stacked_psum_parts(stacked_params: Pytree, umap: UnitMap,
                       selection: jnp.ndarray, data_sizes: jnp.ndarray
                       ) -> tuple[Pytree, jnp.ndarray]:
    """First half of Eq. 5: unnormalised numerators (Σ_k s·w_k·Θ_k per
    leaf, fp32) and the stacked rows' denominator contribution (U,). Both
    are *additive* over clients, so on a mesh the caller can fold them —
    together with any other additive per-round stats (loss sums, tap
    partials) — into one fused ``psum``, then call
    :func:`stacked_psum_finalize` on the reduced values. On a 2-D
    ('clients', 'model') mesh the caller may slice each numerator leaf down
    to its 'model'-axis shard *before* the psum (the reduction runs over
    'clients' only, per model column) — the unit-axis bookkeeping below
    never touches the sharded leaf dims, so parts/finalize work unchanged
    on 1/M slices."""
    w, denom_loc = unit_weights(selection, data_sizes)      # local (K,U),(U,)
    k = selection.shape[0]

    def partial_one(key: str):
        off, n = umap.spans[key]
        seg = jax.lax.dynamic_slice(w, (0, off), (k, n))     # (K, n)

        def num(leaf):
            if n > 1:
                wx = seg.reshape((k, n) + (1,) * (leaf.ndim - 2))
            else:
                wx = seg.reshape((k,) + (1,) * (leaf.ndim - 1))
            return jnp.sum(leaf.astype(jnp.float32) * wx, axis=0)

        return jax.tree.map(num, stacked_params[key])

    return ({key: partial_one(key) for key in stacked_params}, denom_loc)


def stacked_psum_finalize(partials: Pytree, denom: jnp.ndarray,
                          umap: UnitMap, stacked_params: Pytree,
                          fallback: Pytree | None) -> Pytree:
    """Second half of Eq. 5 (replicated on a mesh): divide the summed
    numerators by the global denominator, fall back to the previous global
    model for dead units, and cast back to the parameter dtype.
    ``stacked_params`` is only consulted for leaf dtypes (its leaves need
    not carry the stacked client axis — the sharded round passes its local
    param shards, whose dtypes match, and whose leaves/fallback may be 1/M
    'model'-axis slices aligned with the sliced numerators)."""
    safe = jnp.where(denom > 0, denom, 1.0)

    def finalize_one(key: str):
        off, n = umap.spans[key]
        seg_d = jax.lax.dynamic_slice(denom, (off,), (n,))
        seg_s = jax.lax.dynamic_slice(safe, (off,), (n,))

        def fin(p, leaf, fb):
            if n > 1:
                out = p / seg_s.reshape((n,) + (1,) * (p.ndim - 1))
                alive = (seg_d > 0).reshape((n,) + (1,) * (p.ndim - 1))
            else:
                out = p / seg_s[0]
                alive = seg_d[0] > 0
            if fb is not None:
                out = jnp.where(alive, out, fb.astype(jnp.float32))
            return out.astype(leaf.dtype)

        fsub = fallback[key] if fallback is not None else None
        if fsub is None:
            return jax.tree.map(lambda p, leaf: fin(p, leaf, None),
                                partials[key], stacked_params[key])
        return jax.tree.map(fin, partials[key], stacked_params[key], fsub)

    return {key: finalize_one(key) for key in stacked_params}


def hierarchical_psum(tree: Pytree, axis_name: str, axis_size: int,
                      group_size: int) -> Pytree:
    """Two-tier all-reduce over a named mesh axis (population-scale rounds).

    Tier 1: ``psum`` restricted to groups of ``group_size`` consecutive
    axis positions (``axis_index_groups`` — XLA keeps the collective on
    intra-group links, e.g. intra-host NVLink/ICI when the mesh is built
    host-contiguous). Tier 2: a ring all-reduce across the groups via
    ``lax.ppermute`` rotations by ``group_size`` — each step every position
    receives the previous group's running partial and accumulates it, so
    after ``num_groups - 1`` rotations every device holds the global sum
    without any single root absorbing all ``D`` partials. Cross-group
    traffic per device is O(num_groups) payloads instead of the flat
    reduce's O(D) at the root — server/root bandwidth stops being the
    ceiling (RingFed, arXiv:2107.08873).

    ``group_size == axis_size`` (one group) degenerates to a flat psum;
    ``group_size == 1`` is a pure ring all-reduce over all devices. The
    result equals ``jax.lax.psum(tree, axis_name)`` up to fp32 summation
    order (the equivalence tests use the usual fp32 tolerance).
    """
    if axis_size % group_size:
        raise ValueError(
            f"hierarchical_psum: group_size={group_size} must divide the "
            f"axis size {axis_size}")
    num_groups = axis_size // group_size
    if num_groups <= 1:
        return jax.lax.psum(tree, axis_name)
    if group_size > 1:
        groups = [[g * group_size + i for i in range(group_size)]
                  for g in range(num_groups)]
        tree = jax.lax.psum(tree, axis_name, axis_index_groups=groups)
    perm = [(i, (i + group_size) % axis_size) for i in range(axis_size)]
    acc, rot = tree, tree
    for _ in range(num_groups - 1):
        rot = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), rot)
        acc = jax.tree.map(jnp.add, acc, rot)
    return acc


def fedavg_stacked(stacked_params: Pytree, data_sizes: jnp.ndarray) -> Pytree:
    """Eq. 1 — plain FedAvg over client-stacked params."""
    w = data_sizes.astype(jnp.float32)
    frac = w / w.sum()

    def combine(leaf):
        wx = frac.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(leaf.astype(jnp.float32) * wx, axis=0).astype(leaf.dtype)

    return jax.tree.map(combine, stacked_params)


# ----------------------------------------------------------------------
# Streaming layout (scan over clients) — same math, O(1)-client memory.
# ----------------------------------------------------------------------
def streaming_init(global_params: Pytree) -> Pytree:
    """Float32 accumulator for Eq. 5 numerators."""
    return tree_zeros_like(global_params, dtype=jnp.float32)


def streaming_add(acc: Pytree, client_params: Pytree, umap: UnitMap,
                  client_frac: jnp.ndarray) -> Pytree:
    """acc += client_frac[u] * Θ_k  (client_frac = w[k]/denom, shape (U,))."""
    return umap.accumulate(acc, client_params, client_frac)


def streaming_finalize(acc: Pytree, umap: UnitMap, denom: jnp.ndarray,
                       fallback: Pytree) -> Pytree:
    """Replace zero-denominator units with the previous global model and cast
    back to the parameter dtype."""
    alive = (denom > 0).astype(jnp.float32)
    kept = umap.scale_by_unit(acc, alive)
    fb = umap.scale_by_unit(fallback, 1.0 - alive)
    return jax.tree.map(lambda a, b, g: (a + b.astype(jnp.float32)).astype(g.dtype),
                        kept, fb, fallback)


# ----------------------------------------------------------------------
# Top-n slots (scan over clients) — Eq. 4's per-unit top-n kept as the
# clients train, so Eq. 5 needs no second training pass.
# ----------------------------------------------------------------------
def topn_insert(slots: list, score: jnp.ndarray, who: jnp.ndarray,
                local: Pytree, div: jnp.ndarray, client: jnp.ndarray,
                umap: UnitMap) -> tuple[list, jnp.ndarray, jnp.ndarray]:
    """Insert one client's ``local`` into each unit's n best so far.

    ``slots`` is a list of n parameter trees at the parameter dtype,
    ``score`` (n, U) their Eq. 3 divergences, descending per unit (−inf
    where a slot is empty), ``who`` (n, U) the client each slot holds.
    Per unit, the slots from the first whose score is strictly lower than
    ``div`` shift down one and the local takes that slot. The comparison
    is strict, so a tie keeps the earlier client, as ``lax.top_k`` does:
    after every client has been inserted in index order, ``who`` holds
    exactly the clients ``selection.topn_divergence`` picks. Stacked
    leaves take the select row by row, one row per unit.
    """
    n = score.shape[0]
    pos = jnp.sum(score >= div[None, :], axis=0)          # (U,)
    j = jnp.arange(n)[:, None]
    keep, put = j < pos, j == pos                           # (n, U)

    def shift(a, new):
        prev = jnp.concatenate([a[:1], a[:-1]])             # slot j - 1
        return jnp.where(keep, a, jnp.where(put, new[None], prev))

    def pick(mask, a, b):
        m = umap.expand_to_leaves(a, mask.astype(jnp.float32))
        return jax.tree.map(lambda m_, x, y: jnp.where(m_ != 0, x, y),
                            m, a, b)

    new_slots = [pick(keep[i], slots[i],
                      local if i == 0 else pick(put[i], local, slots[i - 1]))
                 for i in range(n)]
    return (new_slots, shift(score, div),
            shift(who, jnp.full(div.shape, client, who.dtype)))


def topn_aggregate(slots: list, who: jnp.ndarray, umap: UnitMap,
                   selection: jnp.ndarray, data_sizes: jnp.ndarray,
                   fallback: Pytree) -> Pytree:
    """Eq. 5 over the kept slots: ``Σ_j w[who_j,u]·slot_j / denom[u]``
    with ``w, denom = unit_weights(selection, data_sizes)``, summed in
    float32 and finished as :func:`streaming_finalize` does (dead units
    from ``fallback``, cast back to the parameter dtype). ``who`` must
    hold the selected clients (:func:`topn_insert` over every client)."""
    w, denom = unit_weights(selection, data_sizes)
    frac = (jnp.take_along_axis(w, who, axis=0)
            / jnp.where(denom > 0, denom, 1.0)[None, :])      # (n, U)
    parts = [umap.scale_by_unit(tree_cast(s, jnp.float32), f)
             for s, f in zip(slots, frac)]
    acc = jax.tree.map(lambda *xs: sum(xs[1:], xs[0]), *parts)
    return streaming_finalize(acc, umap, denom, fallback)
