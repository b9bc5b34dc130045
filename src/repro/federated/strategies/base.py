"""FLStrategy protocol + registry: pluggable FL algorithms for one engine.

An :class:`FLStrategy` packages everything algorithm-specific about a
federated round behind a fixed set of **jit-safe hooks**, so the two
round builders in :mod:`repro.federated.server` (stacked clients under
``vmap``, on one device or a mesh, and sequential clients under ``scan``)
share one protocol instead of re-implementing per-algorithm branches.

Hook contract (every hook is traced under ``jax.jit`` — no Python control
flow on traced values, no host callbacks, static shapes only):

- ``select(divs, key, k, u, n) -> (K, U) float32 selection matrix`` —
  which (client, layer-unit) pairs are uploaded and aggregated (Eq. 4 /
  the baselines' policies). ``divs`` is the (K, U) divergence matrix when
  :attr:`needs_divergence` is set, else ``None``. ``key`` is this round's
  algorithm PRNG key (same stream in every engine, so vmap/scan/sharded
  trajectories agree).
- ``transform_upload(local, global_params, umap, residual)
  -> (upload, candidate_residual)`` — per-client payload transform
  (identity by default; the quantize+error-feedback wrapper compresses
  here). Called under ``jax.vmap`` over the client axis; only consulted
  when :attr:`transforms_upload` is set.
- ``update_residual(cand_res, old_res, sel_row, umap, global_params)`` —
  per-client error-feedback residual update, gated on the selection row
  (residuals advance only where a layer actually shipped). Only consulted
  when :attr:`tracks_residuals` is set.
- ``psum_parts`` / ``psum_finalize`` — the server-side aggregation, in
  two halves: additive partials over the client-stacked uploads, then
  the epilogue on their sums. Every stacked-clients round calls them,
  with the mesh's fused ``psum`` between them (an identity off a mesh).
  The defaults implement the paper's Eq. 5 masked weighted mean
  (:func:`repro.core.aggregation.stacked_psum_parts` /
  :func:`~repro.core.aggregation.stacked_psum_finalize`). A strategy with
  other math (FedADP's element-wise neuron masks) overrides both halves,
  keeps its partials additive over clients, and sets
  ``eq5_weighted = False``.
- ``comm_profile(selection, umap, param_bytes_override=None,
  unit_bytes_override=None) -> dict`` — per-round communication
  accounting. Must preserve the ledger invariant
  ``uplink_payload + uplink_feedback == uplink_total`` (tested for every
  registered strategy). Every round calls it once, on the replicated
  (K, U) selection. ``unit_bytes_override`` carries the packed wire
  format's per-unit byte vector (``PackedPayload.unit_wire_bytes``) and
  takes precedence over the legacy uniform repricing.
- ``uplink_psum_parts`` — the packed-uplink path, consulted only when
  :attr:`packed_upload` is set: the strategy turns the stacked client
  locals directly into a packed wire payload (``core/wire``) and reduces
  it through the fused dequant+EF+Eq. 5 kernel (``kernels/uplink``),
  never materialising per-client fp32 reconstructions. It returns
  additive partials for the round's reduce, finalized by
  ``psum_finalize``.

**Cross-round state seam** (optional; every round threads it):

- ``init_state(params, num_clients, mesh=None) -> state | None`` — declare
  cross-round state once before round 0. ``None`` (the default) keeps the
  strategy stateless and adds **zero** carry leaves to the engines. A
  stateful strategy returns ``{"client": {name: store}, "global":
  {name: tree}}``: each *client* entry is a per-client store whose leaves
  carry a leading ``(num_clients,)`` axis (rows for the round's
  participants are gathered before the round and scattered back after —
  exactly the error-feedback residual treatment, which is itself declared
  through this hook by the quantize wrapper); each *global* entry is a
  replicated pytree updated wholesale every round.
- ``select_with_state(state, divs, key, k, u, n)`` — state-aware selection;
  the engines always call this, and the default delegates to ``select``
  (so existing strategies are untouched). ``state`` is the *round-local*
  view: client entries hold the participants' ``(K, ...)`` rows.
- ``update_state(state, selection, divs, umap, key=None) -> state`` — the
  per-round state transition, called once per round after aggregation with
  the same replicated ``selection``/``divs`` every engine computed.
  Default: identity. Must be jit-safe and shape-preserving (the scan
  engine carries state through ``lax.scan``; changing a leaf's
  shape/dtype across rounds will fail to trace).
- ``state_specs(params, state, mesh) -> specs`` — mesh placement for state
  entries on a 2-D ('clients', 'model') mesh, mirroring
  ``residual_store_specs``: a same-structure dict of PartitionSpec trees
  for each entry's *trailing* dims (no client axis — the engine prepends
  the 'clients' axis for client rows itself). The default shards any
  param-shaped client entry like the parameters (``fl_param_specs``) and
  replicates everything else, which is right for residual/control-variate
  stores and for small global vectors alike.

In the mesh engine, global entries are replicated and may drive selection;
client entries enter hooks as the device-local rows (like EF residual
rows), so ``select_with_state``/``update_state`` must touch client entries
only element-wise per-row when ``supports_mesh`` is declared.

Capability flags (class attributes, read by ``FLConfig`` validation and
the engines):

- ``needs_divergence`` — the engine computes the (K, U) Eq. 3 divergence
  matrix (and accounts its feedback uplink) before calling ``select``.
- ``supports_scan`` — the strategy can run under ``mode="scan"``.
  Strategies with ``eq5_weighted`` stream clients through an O(1)-client
  accumulator; others have their sequentially-trained locals stacked by
  the scan and fed to the same ``psum_parts``/``psum_finalize`` halves
  (O(K) param memory, still O(1) activation memory).
- ``supports_mesh`` — the strategy can run client-sharded over a device
  mesh (its ``psum_parts`` must be additive over clients).
- ``supports_quantize`` — the quantize(+EF) wrapper may be composed on
  top (``FLConfig(compression=CompressionConfig(...))``).
- ``eq5_weighted`` — aggregation is exactly Eq. 5 over the selection
  matrix, so the engines may execute it as a streaming accumulation
  (scan). Set it to ``False`` whenever ``psum_parts``/``psum_finalize``
  are overridden with different math.
- ``selects_topn_divergence`` — an engine-dispatch flag, not a user
  knob: the selection is exactly Eq. 4's per-unit top-n of the Eq. 3
  scores (``selection.topn_divergence``, ties to the lower client). With
  ``needs_divergence`` and ``eq5_weighted`` it lets the scan engine keep
  each unit's n most divergent locals as the clients train and train
  each client once (``server.scan_client_passes``). A subclass that
  overrides :meth:`select` or :meth:`select_with_state` must clear it.

Register with :func:`register_strategy`; ``FLConfig(algo=<name>)`` then
resolves through the registry, and the name shows up in
``repro.federated.ALGOS`` and ``benchmarks/fl_comparison.py``
automatically.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import aggregation as agg
from repro.core import comm as comm_mod
from repro.core.units import UnitMap

Pytree = Any


class FLStrategy:
    """Base strategy: Eq. 5 aggregation over a subclass-chosen selection."""

    # registry name; filled in by @register_strategy
    name: str = "?"
    # per-strategy options dataclass accepted via FLConfig(algo_options=...)
    # (None = the strategy has no knobs beyond the shared FLConfig fields)
    options_cls: Optional[type] = None
    # ---- capability flags (see module docstring) ----
    needs_divergence: bool = False
    supports_scan: bool = True
    supports_mesh: bool = True
    supports_quantize: bool = True
    eq5_weighted: bool = True
    # ---- engine dispatch flags ----
    # select() is Eq. 4's per-unit top-n of the divergences (see the
    # module docstring; clear it when overriding select)
    selects_topn_divergence: bool = False
    transforms_upload: bool = False
    tracks_residuals: bool = False
    # packed wire-format uplink: the round routes the whole
    # locals→payload→aggregate reduction through uplink_psum_parts
    # instead of transform_upload + psum_parts
    packed_upload: bool = False

    def __init__(self, cfg):
        self.cfg = cfg   # the FLConfig (duck-typed; strategies read knobs)
        self.opts = self.resolve_options(cfg)

    @classmethod
    def resolve_options(cls, cfg):
        """The strategy's options instance for ``cfg``.

        ``FLConfig`` normalizes ``algo_options`` in ``__post_init__`` (flat
        deprecated knobs are folded in there), so this usually just reads
        ``cfg.algo_options``. Duck-typed cfgs without the field fall back
        to the options defaults. Returns ``None`` when the strategy
        declares no :attr:`options_cls`.
        """
        if cls.options_cls is None:
            return None
        opts = getattr(cfg, "algo_options", None)
        if opts is None:
            return cls.options_cls()
        if not isinstance(opts, cls.options_cls):
            raise TypeError(
                f"algo_options for strategy {cls.name!r} must be "
                f"{cls.options_cls.__name__}, got {type(opts).__name__}")
        return opts

    # ---- cross-round state seam (see module docstring) ----
    def init_state(self, params: Pytree, num_clients: int,
                   mesh=None) -> Optional[dict]:
        """Declare cross-round state; ``None`` (default) = stateless, and
        the engines add no carry leaves at all."""
        return None

    def state_specs(self, params: Pytree, state: dict, mesh) -> dict:
        """Mesh placement of state entries: a same-structure dict of
        PartitionSpec trees for each entry's *trailing* dims. Default:
        param-shaped client entries inherit the parameters' 'model'-axis
        sharding (``fl_param_specs`` — the residual-store treatment),
        everything else is replicated."""
        from repro.launch.sharding import fl_param_specs
        pspecs = fl_param_specs(params, mesh)
        pdef = jax.tree.structure(params)
        pshapes = [l.shape for l in jax.tree.leaves(params)]

        def entry_specs(entry, client: bool):
            if client and jax.tree.structure(entry) == pdef and \
                    [l.shape[1:] for l in jax.tree.leaves(entry)] == pshapes:
                return pspecs
            return jax.tree.map(lambda _: P(), entry)

        return {kind: {name: entry_specs(e, kind == "client")
                       for name, e in (state.get(kind) or {}).items()}
                for kind in ("client", "global")}

    def select_with_state(self, state: Optional[dict],
                          divs: Optional[jnp.ndarray], key, k: int, u: int,
                          n: int) -> jnp.ndarray:
        """State-aware selection — the engines' actual entry point. The
        default ignores ``state`` and delegates to :meth:`select`."""
        return self.select(divs, key, k, u, n)

    def update_state(self, state: dict, selection: jnp.ndarray,
                     divs: Optional[jnp.ndarray], umap: UnitMap,
                     key=None) -> dict:
        """Per-round state transition (identity by default). Runs once per
        round, after aggregation, with replicated inputs; must be jit-safe
        and preserve every leaf's shape/dtype."""
        return state

    # ------------------------------------------------------------------
    def select(self, divs: Optional[jnp.ndarray], key, k: int, u: int,
               n: int) -> jnp.ndarray:
        raise NotImplementedError

    def transform_upload(self, local: Pytree, global_params: Pytree,
                         umap: UnitMap, residual: Optional[Pytree]
                         ) -> tuple[Pytree, Optional[Pytree]]:
        return local, None

    def update_residual(self, cand_res: Pytree, old_res: Optional[Pytree],
                        sel_row: jnp.ndarray, umap: UnitMap,
                        global_params: Pytree) -> Pytree:
        raise NotImplementedError

    # ---- aggregation, in two halves (the round's reduce between) ----
    def psum_parts(self, uploads: Pytree, umap: UnitMap,
                   sel_loc: jnp.ndarray, data_sizes: jnp.ndarray,
                   global_params: Optional[Pytree] = None
                   ) -> tuple[Pytree, Pytree]:
        """Additive partials over the stacked ``uploads`` (this device's
        clients on a mesh, all K off it), summed by the round's reduce.
        The returned ``parts`` must be param-structured; ``denom`` may be a
        single ``(U,)`` array (Eq. 5) *or* a param-structured tree of
        element-wise denominators (FedADP) — the engine slices a
        param-structured denom to 'model'-axis shards alongside ``parts``.
        ``global_params`` is the (fully gathered) global model, for
        strategies whose partials depend on it (e.g. FedADP's masks)."""
        return agg.stacked_psum_parts(uploads, umap, sel_loc, data_sizes)

    def psum_finalize(self, parts: Pytree, denom: jnp.ndarray,
                      umap: UnitMap, params_shard: Pytree,
                      fallback: Pytree) -> Pytree:
        return agg.stacked_psum_finalize(parts, denom, umap, params_shard,
                                         fallback)

    # ---- packed-uplink path (only when packed_upload is set) ----
    def uplink_psum_parts(self, locals_: Pytree, global_params: Pytree,
                          umap: UnitMap, sel_loc: jnp.ndarray,
                          divs: Optional[jnp.ndarray],
                          data_sizes: jnp.ndarray,
                          res_rows: Optional[Pytree]
                          ) -> tuple[Pytree, jnp.ndarray,
                                     Optional[Pytree], dict]:
        """Stacked client ``locals_`` → ``(parts, denom, new_residual_rows,
        wire)``: additive Eq. 5 numerator partials and denominator (for
        the round's reduce, then :meth:`psum_finalize`), the updated
        residual rows, and ``wire`` = ``{"unit_bytes": (U,), "bits": (U,),
        "nbytes": int}``, the packed payload's accounting, which prices
        :meth:`comm_profile` via ``unit_bytes_override``."""
        raise NotImplementedError(
            f"{type(self).__name__} sets packed_upload but does not "
            "implement uplink_psum_parts")

    # ------------------------------------------------------------------
    def comm_profile(self, selection: jnp.ndarray, umap: UnitMap,
                     param_bytes_override: float | None = None,
                     unit_bytes_override: jnp.ndarray | None = None) -> dict:
        return comm_mod.round_comm(
            selection, umap, divergence_feedback=self.needs_divergence,
            param_bytes_override=param_bytes_override,
            unit_bytes_override=unit_bytes_override)

    # ---- telemetry taps (observability; jit-safe like every hook) ----
    # global-state entries at most this many elements are passed through
    # verbatim (FedLAMA's (U,) interval/ttl vectors); larger entries are
    # summarised by their Frobenius norm instead.
    tap_passthrough_max: int = 256

    def telemetry_taps(self, state: Optional[dict],
                       selection: jnp.ndarray,
                       divs: Optional[jnp.ndarray],
                       umap: UnitMap) -> dict:
        """Per-round observability dict for the telemetry subsystem
        (``FLConfig(telemetry=TelemetryConfig(taps=True))``): a flat
        ``{name: array}`` of small summaries recorded into the round
        ledger. Called once per round inside the compiled round function
        with the same REPLICATED inputs on every engine — ``selection``
        is the (K, U) matrix, ``divs`` the (K, U) Eq. 3 divergence matrix
        (or None), and ``state`` holds only the *global* entries (client
        rows are device-local under a mesh; the engines tap their norms
        separately). Must be jit-safe with a static key set.

        Default: per-unit selection counts, per-unit divergence
        mean/max, and each global state entry — verbatim when it is a
        single small array (≤ :attr:`tap_passthrough_max` elements, e.g.
        FedLAMA's (U,) interval/ttl vectors), by norm otherwise.
        """
        taps = {"sel_count": jnp.sum(selection, axis=0)}
        if divs is not None:
            taps["div_mean"] = jnp.mean(divs, axis=0)
            taps["div_max"] = jnp.max(divs, axis=0)
        if state and state.get("global"):
            for name, entry in state["global"].items():
                leaves = jax.tree.leaves(entry)
                if len(leaves) == 1 and leaves[0].ndim <= 1 and \
                        leaves[0].size <= self.tap_passthrough_max:
                    taps[f"state_{name}"] = leaves[0]
                else:
                    sq = sum((jnp.sum(jnp.square(l.astype(jnp.float32)))
                              for l in leaves), jnp.float32(0.0))
                    taps[f"state_{name}_norm"] = jnp.sqrt(sq)
        return taps


# ======================================================================
# Registry
# ======================================================================
_REGISTRY: dict[str, type[FLStrategy]] = {}


def register_strategy(name: str, *, override: bool = False):
    """Class decorator: make ``FLConfig(algo=name)`` resolve to this
    strategy (and list it in ``ALGOS`` / the comparison bench).

    Registering a name that is already taken by a *different* class raises
    (a plugin silently replacing e.g. the ``fedavg`` baseline would corrupt
    every savings-vs-fedavg comparison with no signal); pass
    ``override=True`` to replace intentionally. Re-registering the same
    class under the same name is an idempotent no-op (module re-imports).
    """

    def deco(cls: type[FLStrategy]) -> type[FLStrategy]:
        if not (isinstance(cls, type) and issubclass(cls, FLStrategy)):
            raise TypeError(f"{cls!r} is not an FLStrategy subclass")
        existing = _REGISTRY.get(name)
        if existing is not None and existing is not cls and not override:
            raise ValueError(
                f"strategy name {name!r} is already registered to "
                f"{existing.__name__}; pass register_strategy(name, "
                "override=True) to replace it")
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def unregister_strategy(name: str) -> None:
    """Remove a registered strategy (tests / plugin teardown)."""
    _REGISTRY.pop(name, None)


def registered_algos() -> tuple[str, ...]:
    """Registered algorithm names, in registration order."""
    return tuple(_REGISTRY)


def strategy_registry() -> dict[str, type[FLStrategy]]:
    return dict(_REGISTRY)


def get_strategy_cls(name: str) -> type[FLStrategy]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown FL algorithm {name!r}; registered strategies: "
            f"{', '.join(sorted(_REGISTRY)) or '(none)'}") from None
