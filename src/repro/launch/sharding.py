"""Auto-sharding policy (divisibility-driven, Megatron/FSDP-style defaults).

For every parameter/cache leaf we assign:
- the largest divisible non-leading dim -> 'model' (tensor parallel),
- the next largest divisible dim       -> the data/FSDP axis product
  ('data', or ('pod','data') multi-pod),
- everything else replicated.

Leaves under stacked top-level keys (blocks/enc_blocks/dense) skip their leading
depth dim (it is scanned, never sharded). 1-D leaves (norm scales, biases)
are replicated. When a dim does not divide the axis size the policy falls
back rather than failing — this is what lets 25-head/28-head architectures
lower cleanly with MLP-only tensor parallelism (DESIGN.md §5).

``overrides`` allows per-path-regex PartitionSpec pinning — the hillclimb
lever used in §Perf.
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import data_axes

Pytree = Any

STACKED_TOPKEYS = ("blocks", "enc_blocks", "dec_blocks", "dense")


def _axis_size(mesh: Mesh, axis) -> int:
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def auto_spec(shape: tuple[int, ...], mesh: Mesh, *,
              skip_leading: bool = False,
              model_axis: str = "model",
              model_only: bool = False) -> P:
    """Generic two-level sharding of one array shape.

    ``model_only=True`` assigns the 'model' (tensor/FSDP) axis only and
    leaves every other dim replicated — the FL round engine uses this so a
    ('clients', 'model') mesh never shards parameter leaves over 'clients'
    (that axis carries stacked *clients*, not parameter blocks).
    """
    start = 1 if skip_leading else 0
    dims = list(range(start, len(shape)))
    spec: list = [None] * len(shape)

    def pick(axis, exclude: set[int]) -> Optional[int]:
        size = _axis_size(mesh, axis)
        cands = [d for d in dims if d not in exclude
                 and shape[d] >= size and shape[d] % size == 0]
        if not cands:
            return None
        return max(cands, key=lambda d: (shape[d], d))

    dm = pick(model_axis, set())
    if dm is not None:
        spec[dm] = model_axis
    if not model_only:
        daxes = data_axes(mesh)
        daxis = daxes if len(daxes) > 1 else daxes[0]
        dd = pick(daxis, {dm} if dm is not None else set())
        if dd is not None:
            spec[dd] = daxis
    return P(*spec)


def _iter_paths(tree: Pytree, prefix: str = ""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _iter_paths(tree[k], f"{prefix}{k}/")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _iter_paths(v, f"{prefix}#{i}/")
    else:
        yield prefix.rstrip("/"), tree


def param_specs(params_shape: Pytree, mesh: Mesh,
                overrides: Optional[dict[str, P]] = None,
                model_only: bool = False,
                stacked_keys: tuple[str, ...] = STACKED_TOPKEYS) -> Pytree:
    """PartitionSpec pytree for a parameter (or cache) shape tree.

    ``params_shape`` leaves: ShapeDtypeStruct or arrays.
    ``overrides``: {path-regex: PartitionSpec} applied first-match.
    ``model_only``: see :func:`auto_spec` — 'model'-axis shards only.
    ``stacked_keys``: top-level keys whose leading dim is a stacked depth
    (never sharded); callers whose depth dim doubles as a *unit* axis (the
    FL engine) must list every such key or the unit bookkeeping breaks.
    """
    overrides = overrides or {}

    def assign(path: str, leaf) -> P:
        for pat, spec in overrides.items():
            if re.search(pat, path):
                return spec
        shape = leaf.shape
        if len(shape) <= 1:
            return P()
        top = path.split("/", 1)[0]
        skip = top in stacked_keys
        if len(shape) - (1 if skip else 0) < 1:
            return P()
        return auto_spec(shape, mesh, skip_leading=skip,
                         model_only=model_only)

    flat = dict(_iter_paths(params_shape))
    specs = {path: assign(path, leaf) for path, leaf in flat.items()}

    def rebuild(tree: Pytree, prefix: str = "") -> Pytree:
        if isinstance(tree, dict):
            return {k: rebuild(v, f"{prefix}{k}/") for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            t = type(tree)
            return t(rebuild(v, f"{prefix}#{i}/") for i, v in enumerate(tree))
        return specs[prefix.rstrip("/")]

    return rebuild(params_shape)


def to_named(spec_tree: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------------------
# FL round engine ('clients' × 'model' mesh) — FSDP-style param policy and
# the shard_map-side reassembly/slicing that goes with it.
# ----------------------------------------------------------------------
def fl_param_specs(params_shape: Pytree, mesh: Mesh,
                   model_axis: str = "model") -> Pytree:
    """Model-axis-only PartitionSpecs for the federated round engine.

    Every parameter leaf gets its largest divisible dim (skipping the
    stacked depth dim for ``STACKED_TOPKEYS`` subtrees) assigned to the
    mesh's 'model' axis and everything else replicated — FSDP-style 1/M
    per-device shards with no 'clients'-axis factor (that axis carries
    stacked clients, never parameter blocks). On a mesh without a 'model'
    axis (or with ``model=1``) the whole tree is replicated (``P()``),
    which keeps 1-D client meshes byte-identical to the pre-model-axis
    engine. Indivisible leaves fall back to replication per ``auto_spec``.
    """
    names = getattr(mesh, "axis_names", ())
    if model_axis not in names or int(mesh.shape[model_axis]) <= 1:
        return jax.tree.map(lambda _: P(), params_shape)
    # the FL engine's unit bookkeeping (core/units.DEFAULT_STACKED_KEYS)
    # treats these leading depth dims as the *unit* axis — sharding one
    # would break the per-unit aggregation epilogue on 1/M slices, so they
    # must all be skip_leading here ('experts' is stacked for units but
    # not in the dry-run policy's STACKED_TOPKEYS).
    from repro.core.units import DEFAULT_STACKED_KEYS
    return param_specs(params_shape, mesh, model_only=True,
                       stacked_keys=tuple(set(STACKED_TOPKEYS)
                                          | set(DEFAULT_STACKED_KEYS)))


def residual_store_specs(params_shape: Pytree, mesh: Mesh) -> Pytree:
    """PartitionSpecs for an ``(N, ...)`` per-client store (EF residuals,
    control variates, any strategy client-state entry): the client-id axis
    is replicated (any client can be sampled onto any device), while each
    leaf's trailing dims carry the same 'model'-axis sharding as the
    corresponding parameter leaf (:func:`fl_param_specs`). All-replicated
    on meshes without a 'model' axis."""
    pspecs = fl_param_specs(params_shape, mesh)
    return jax.tree.map(lambda s: P(None, *s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def init_residual_store(params: Pytree, num_clients: int,
                        mesh: Optional[Mesh] = None) -> Pytree:
    """Per-client error-feedback residual store: every leaf gets a leading
    ``(N,)`` client axis, zero-initialised **in the leaf's own dtype** (a
    hard-coded float32 store silently upcast EF arithmetic — and doubled
    the store's memory — for bf16/fp16 models). Rows for the round's
    participants are gathered before the round and scattered back after —
    residuals belong to *clients*, not to sampling slots. At N × model
    size this store is the first memory cliff; under a 2-D
    ('clients', 'model') mesh pass ``mesh`` so it is held 'model'-axis
    sharded (:func:`residual_store_specs`), 1/M per device — and *created*
    sharded: the zeros are jitted with sharded out_shardings, so the full
    replicated store never materialises on any single device (allocating
    it first and resharding after would reintroduce, at init time, exactly
    the cliff the sharding removes)."""
    import jax.numpy as jnp

    def build():
        return jax.tree.map(
            lambda l: jnp.zeros((num_clients,) + l.shape, l.dtype), params)

    if mesh is None:
        return build()
    shardings = to_named(residual_store_specs(params, mesh), mesh)
    return jax.jit(build, out_shardings=shardings)()


def _model_dim(spec: P, axis_name: str) -> Optional[int]:
    for i, s in enumerate(spec):
        if s == axis_name:
            return i
    return None


def tree_all_gather(tree: Pytree, spec_tree: Pytree,
                    axis_name: str = "model", offset: int = 0) -> Pytree:
    """Reassemble full leaves from per-device 'model'-axis shards.

    Only callable inside ``shard_map``. ``spec_tree`` is the
    :func:`fl_param_specs` tree of the *unprefixed* leaves; ``offset``
    shifts every spec dim right (e.g. ``offset=1`` for error-feedback rows
    whose leaves carry a leading client axis the spec does not mention).
    Leaves whose spec has no 'model' entry are already full — returned
    untouched, so a replicated tree makes this a no-op.
    """
    def gather(x, spec):
        d = _model_dim(spec, axis_name)
        if d is None:
            return x
        return jax.lax.all_gather(x, axis_name, axis=d + offset, tiled=True)

    return jax.tree.map(gather, tree, spec_tree)


def tree_shard_slice(tree: Pytree, spec_tree: Pytree, axis_size: int,
                     axis_name: str = "model", offset: int = 0) -> Pytree:
    """Slice full leaves down to this device's 'model'-axis shard — the
    inverse of :func:`tree_all_gather`, same calling convention. Exact
    (pure data movement): gather-then-slice round-trips bit-identically.
    """
    def shard(x, spec):
        d = _model_dim(spec, axis_name)
        if d is None:
            return x
        dim = d + offset
        size = x.shape[dim] // axis_size
        start = jax.lax.axis_index(axis_name) * size
        return jax.lax.dynamic_slice_in_dim(x, start, size, axis=dim)

    return jax.tree.map(shard, tree, spec_tree)


def batch_specs(batch_shape: Pytree, mesh: Mesh, *,
                client_leading: bool = False) -> Pytree:
    """Shard the batch dim over the data axes.

    Leaves: (K, b, ...) when client_leading (FL round batch; the per-client
    batch dim b is sharded) or (b, ...) otherwise. Falls back to replication
    when b does not divide the axis product (e.g. long_500k's batch=1).
    """
    daxes = data_axes(mesh)
    daxis = daxes if len(daxes) > 1 else daxes[0]
    dsize = _axis_size(mesh, daxis)
    bdim = 1 if client_leading else 0

    def assign(leaf) -> P:
        shape = leaf.shape
        if len(shape) <= bdim or shape[bdim] % dsize or shape[bdim] < dsize:
            return P()
        spec: list = [None] * len(shape)
        spec[bdim] = daxis
        return P(*spec)

    return jax.tree.map(assign, batch_shape)
