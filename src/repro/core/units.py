"""Layer-unit abstraction for FedLDF.

The paper (Eq. 3) computes one divergence scalar per *layer*. For VGG-9 a
layer is a conv/FC module; for the transformer zoo a natural unit is a block
depth (parameters are stacked ``(L, ...)`` under ``lax.scan``), plus separate
units for embedding / final norm / LM head.

A :class:`UnitMap` assigns every parameter leaf to one or more units:

- a *plain* top-level subtree (e.g. ``params['embed']``) is one unit;
- a *stacked* top-level subtree (e.g. ``params['blocks']`` whose leaves all
  share a leading depth dim ``L``) contributes ``L`` units, one per depth.

All reductions below are pure JAX and jit-safe; static structure (names,
sizes) is computed from shapes at trace time.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

# Top-level keys whose leaves carry a leading stacked-depth dimension.
DEFAULT_STACKED_KEYS = ("blocks", "enc_blocks", "dec_blocks", "experts")


def _is_stacked(key: str, stacked_keys: Sequence[str]) -> bool:
    return key in stacked_keys


@dataclasses.dataclass(frozen=True)
class UnitMap:
    """Static description of layer units for a parameter pytree."""

    # Ordered unit names, e.g. ["blocks/0", ..., "blocks/L-1", "embed", ...].
    names: tuple[str, ...]
    # top-level key -> (unit offset, n_units). n_units > 1 means stacked.
    spans: dict[str, tuple[int, int]]
    # bytes per unit (static, from shapes/dtypes).
    unit_bytes: tuple[int, ...]
    # parameter count per unit.
    unit_params: tuple[int, ...]

    @property
    def num_units(self) -> int:
        return len(self.names)

    @property
    def total_bytes(self) -> int:
        return int(sum(self.unit_bytes))

    @property
    def total_params(self) -> int:
        return int(sum(self.unit_params))

    # ------------------------------------------------------------------
    @staticmethod
    def build(params: Pytree,
              stacked_keys: Sequence[str] = DEFAULT_STACKED_KEYS) -> "UnitMap":
        if not isinstance(params, dict):
            raise TypeError("UnitMap.build expects a top-level dict pytree")
        names: list[str] = []
        spans: dict[str, tuple[int, int]] = {}
        nbytes: list[int] = []
        nparams: list[int] = []
        for key in sorted(params.keys()):
            sub = params[key]
            leaves = jax.tree.leaves(sub)
            if not leaves:
                continue
            if _is_stacked(key, stacked_keys):
                depth = leaves[0].shape[0]
                for leaf in leaves:
                    if leaf.ndim < 1 or leaf.shape[0] != depth:
                        raise ValueError(
                            f"stacked subtree {key!r} has inconsistent leading "
                            f"dims: {leaf.shape} vs depth {depth}")
                spans[key] = (len(names), depth)
                per_depth_bytes = sum(
                    int(np.prod(l.shape[1:])) * l.dtype.itemsize for l in leaves)
                per_depth_params = sum(
                    int(np.prod(l.shape[1:])) for l in leaves)
                for d in range(depth):
                    names.append(f"{key}/{d}")
                    nbytes.append(per_depth_bytes)
                    nparams.append(per_depth_params)
            else:
                spans[key] = (len(names), 1)
                names.append(key)
                nbytes.append(sum(int(np.prod(l.shape)) * l.dtype.itemsize
                                  for l in leaves))
                nparams.append(sum(int(np.prod(l.shape)) for l in leaves))
        return UnitMap(names=tuple(names), spans=spans,
                       unit_bytes=tuple(nbytes), unit_params=tuple(nparams))

    # ------------------------------------------------------------------
    def unit_bytes_array(self) -> jnp.ndarray:
        return jnp.asarray(self.unit_bytes, dtype=jnp.float32)

    # ------------------------------------------------------------------
    def divergence_batched(self, locals_: Pytree,
                           ref: Pytree) -> jnp.ndarray:
        """Eq. 3 for K stacked local models (leading client axis on every
        leaf): per-unit L2 norm of (local − ref), ``(K, U)`` fp32.

        Each leaf is one kernel call for all K clients
        (``kernels.ops.sqdiff_units``), which reads ``ref`` once."""
        from repro.kernels import ops as kops  # local import; no cycle
        parts = []
        for key, (_, n) in sorted(self.spans.items(), key=lambda s: s[1][0]):
            parts.append(sum(kops.sqdiff_units(a, b, n) for a, b in zip(
                jax.tree.leaves(locals_[key]), jax.tree.leaves(ref[key]))))
        return jnp.sqrt(jnp.concatenate(parts, axis=1))

    def divergence(self, params: Pytree, ref: Pytree) -> jnp.ndarray:
        """Eq. 3: per-unit L2 norm of (params − ref), shape ``(U,)``: one
        client of :meth:`divergence_batched`. Under ``jax.vmap`` the mapped
        axis becomes the kernel's client axis, one call per leaf."""
        one = jax.tree.map(lambda l: l[None], params)
        return self.divergence_batched(one, ref)[0]

    def divergence_plan(self, params: Pytree) -> list:
        """Eq. 3's static plan, from shapes: ``(leaf path, LeafView)`` per
        leaf in the order the divergence reads them. The view says whether
        the kernel reads the leaf in place or folded, and ``view.nbytes``
        the bytes one client's leaf reads."""
        from repro.kernels.divergence import leaf_view
        return [(key + jax.tree_util.keystr(path),
                 leaf_view(leaf.shape, leaf.dtype, n))
                for key, (_, n) in self.spans.items()
                for path, leaf in jax.tree_util.tree_leaves_with_path(
                    params[key])]

    def in_place_share(self, params: Pytree) -> float:
        """Share of Eq. 3's bytes that the kernel reads in place."""
        plan = self.divergence_plan(params)
        total = sum(v.nbytes for _, v in plan)
        return sum(v.nbytes for _, v in plan if v.in_place) / max(total, 1)

    # ------------------------------------------------------------------
    def scale_by_unit(self, tree: Pytree, per_unit: jnp.ndarray) -> Pytree:
        """Multiply each leaf by its unit's scalar (stacked: per-depth)."""
        out = {}
        for key in tree:
            off, n = self.spans[key]
            seg = jax.lax.dynamic_slice(per_unit, (off,), (n,))
            if n > 1:
                def mul(l, seg=seg):
                    return l * seg.astype(l.dtype).reshape((n,) + (1,) * (l.ndim - 1))
            else:
                def mul(l, seg=seg):
                    return l * seg[0].astype(l.dtype)
            out[key] = jax.tree.map(mul, tree[key])
        return out

    def accumulate(self, acc: Pytree, tree: Pytree, per_unit: jnp.ndarray,
                   masked_accumulate: Callable | None = None) -> Pytree:
        """``acc += per_unit[u(leaf)] * tree`` — the Eq. 5 inner accumulation.

        ``masked_accumulate(acc2d, x2d, w_rows) -> acc2d`` may route through
        the Pallas kernel; defaults to pure jnp.
        """
        from repro.kernels import ops as kops
        macc = masked_accumulate or kops.masked_accumulate
        out = {}
        for key in tree:
            off, n = self.spans[key]
            seg = jax.lax.dynamic_slice(per_unit, (off,), (n,))

            def upd(a, x, seg=seg, n=n):
                a2 = a.reshape(n, -1) if n > 1 else a.reshape(1, -1)
                x2 = x.reshape(n, -1) if n > 1 else x.reshape(1, -1)
                w = seg if n > 1 else seg[:1]
                return macc(a2, x2, w).reshape(a.shape)

            out[key] = jax.tree.map(upd, acc[key], tree[key])
        return out

    # ------------------------------------------------------------------
    def expand_to_leaves(self, tree: Pytree, per_unit: jnp.ndarray) -> Pytree:
        """Return a pytree matching ``tree`` whose leaves hold the unit value
        broadcast to the leaf shape (useful for elementwise algorithms)."""
        out = {}
        for key in tree:
            off, n = self.spans[key]
            seg = jax.lax.dynamic_slice(per_unit, (off,), (n,))
            if n > 1:
                def mk(l, seg=seg):
                    return jnp.broadcast_to(
                        seg.astype(l.dtype).reshape((n,) + (1,) * (l.ndim - 1)),
                        l.shape)
            else:
                def mk(l, seg=seg):
                    return jnp.broadcast_to(seg[0].astype(l.dtype), l.shape)
            out[key] = jax.tree.map(mk, tree[key])
        return out


# ----------------------------------------------------------------------
# Generic pytree helpers used across the framework.
# ----------------------------------------------------------------------
def tree_zeros_like(tree: Pytree, dtype=None) -> Pytree:
    return jax.tree.map(
        lambda l: jnp.zeros(l.shape, dtype or l.dtype), tree)


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: Pytree, s) -> Pytree:
    return jax.tree.map(lambda l: l * jnp.asarray(s, dtype=l.dtype), tree)


def tree_axpy(a: Pytree, x: Pytree, alpha) -> Pytree:
    """a + alpha * x"""
    return jax.tree.map(
        lambda u, v: u + jnp.asarray(alpha, u.dtype) * v, a, x)


def tree_dot(a: Pytree, b: Pytree) -> jnp.ndarray:
    parts = jax.tree.map(
        lambda u, v: jnp.sum(u.astype(jnp.float32) * v.astype(jnp.float32)),
        a, b)
    return sum(jax.tree.leaves(parts), jnp.float32(0.0))


def tree_sq_norm(tree: Pytree) -> jnp.ndarray:
    return tree_dot(tree, tree)


def tree_bytes(tree: Pytree) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


def tree_params(tree: Pytree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


def tree_cast(tree: Pytree, dtype) -> Pytree:
    return jax.tree.map(lambda l: l.astype(dtype), tree)


def tree_stack_index(tree: Pytree, i) -> Pytree:
    """Index leading (client) axis of a stacked pytree."""
    return jax.tree.map(lambda l: l[i], tree)
