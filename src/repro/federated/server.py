"""ServerExecute (paper Algorithm 1) — round function builders + drivers.

Two per-round execution modes produce the same aggregation semantics
(tested):

- ``vmap`` (:func:`build_round_vmap`): all K clients train in parallel
  and their models are materialised stacked — the paper's own regime
  (small models, many clients). One body serves one device and a mesh:
  with ``FLConfig(mesh=make_client_mesh(...))`` it runs under
  ``shard_map`` (each device trains K/D clients; 2-D meshes also shard
  every leaf 1/M over 'model'), and off a mesh its three collectives —
  the divergence all-gather, the local-rows slice and the fused psum —
  are identities. Mesh and single-device trajectories agree to fp32
  tolerance (tests/test_shard_engine.py, tests/test_model_axis.py).
- ``scan`` (:func:`build_round_scan`): clients train sequentially —
  O(1)-client activation memory for LLM-scale FL. A FedLDF client trains
  once (each unit keeps its top-n locals as the clients train); FedLAMA,
  which selects from every client's score, trains each client twice
  (:func:`scan_client_passes`).

Every round aggregates through the strategy's ``psum_parts`` /
``psum_finalize`` halves (``uplink_psum_parts`` for a packed uplink) and
ends in one shared tail: comm accounting, the state transition, the
telemetry taps.

Two *multi-round* drivers share those round functions:

- :func:`run_training` — the host-loop reference oracle: one Python
  iteration per round (host RNG or JAX-RNG sampling, per-round
  host↔device batch transfer, per-round ``CommMeter`` pulls).
- :func:`run_training_scan` — the device-resident engine: the whole FL
  schedule is one jitted ``jax.lax.scan`` over rounds. Client sampling is
  ``jax.random.choice`` on device, round batches are gathered from
  device-resident :class:`~repro.data.ClientShards`, communication totals
  accumulate in the scan carry (one device→host pull per eval block), the
  carry buffers (params, error-feedback residuals, comm accumulator) are
  donated between blocks, and error-feedback residuals are threaded
  through rounds via a per-client store — ``run_training(sampler="jax")``
  and ``run_training_scan`` produce identical trajectories for the same
  seed (tested to fp32 tolerance; see benchmarks/round_engine_bench.py for
  the rounds/sec comparison).

Algorithms are **strategy plugins** (:mod:`repro.federated.strategies`):
the engines above are thin execution shells around the jit-safe
:class:`~repro.federated.strategies.FLStrategy` hooks (``select``,
``transform_upload``, ``psum_parts``/``psum_finalize``,
``comm_profile``, …), and ``FLConfig.algo`` resolves through the strategy
registry — built-ins are fedldf (paper), fedavg (Eq. 1), random
(per-layer random-n), hdfl (client dropout [7]), fedadp (neuron pruning
[6]), fedlp (layer-wise probabilistic pruning, arXiv:2303.06360) and
fedlama (layer-wise adaptive intervals, arXiv:2110.10302);
``register_strategy`` adds user-defined schemes without touching this
module. Per-strategy capability flags (``supports_scan`` /
``supports_mesh`` / ``supports_quantize``) replace engine-side special
cases and are validated at ``FLConfig`` construction.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import aggregation as agg
from repro.core import comm as comm_mod
from repro.core.partition import ParamPartition, partition_counts
from repro.core.units import UnitMap, tree_zeros_like
from repro.core.wire import CompressionConfig
from repro.data.device import ClientShards
from repro.federated.client import make_local_update
from repro.federated.sampling import (local_rows, round_keys, sample_clients,
                                      sample_clients_grouped,
                                      sample_clients_jax)
from repro.federated.strategies import (FedADPOptions, FedLAMAOptions,
                                        FedLPOptions, get_strategy_cls,
                                        make_strategy, registered_algos)
from repro.launch.mesh import (CLIENT_AXIS, MODEL_AXIS, client_mesh_size,
                               model_mesh_size, replicated_rng,
                               shard_map_norep)
from repro.launch.sharding import (fl_param_specs, to_named,
                                   tree_all_gather, tree_shard_slice)
from repro.optim import sgd
from repro.optim.opt import Optimizer
from repro.telemetry import ProgressSink, RoundLedger, TelemetryConfig
from repro.telemetry import profiling as prof_mod
from repro.telemetry import taps as taps_mod

Pytree = Any


def __getattr__(name):   # PEP 562: ALGOS is a live view of the registry
    if name == "ALGOS":
        return registered_algos()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# deprecated flat FLConfig fields → (owning algo, options field); the
# normalization shim in FLConfig.__post_init__ folds non-default values
# into algo_options and mirrors the normalized options back, so old
# readers of the flat names keep seeing the effective values.
_DEPRECATED_ALGO_FIELDS = (
    ("fedadp_keep", "fedadp", "keep"),
    ("fedlp_p", "fedlp", "p"),
    ("fedlama_tau", "fedlama", "tau"),
    ("fedlama_lam", "fedlama", "lam"),
)

# Raised when compression=CompressionConfig(...) meets the sequential-client
# scan engine (asserted verbatim in tests/test_wire.py — keep in sync).
_SCAN_COMPRESSION_MSG = (
    "compression=CompressionConfig(...) is not supported by the "
    "sequential-client scan engine (mode='scan'): the packed quantized "
    "uplink reduces a stacked client axis. Supported drivers: mode='vmap' "
    "on a single device, the mesh-sharded round (FLConfig(mesh=...)), and "
    "both multi-round drivers (run_training / run_training_scan) on top of "
    "them.")


@dataclasses.dataclass(frozen=True)
class FLConfig:
    algo: str = "fedldf"
    num_clients: int = 50          # N
    clients_per_round: int = 20    # K
    top_n: int = 4                 # n (per-layer uploads)
    local_steps: int = 1
    lr: float = 0.05
    mode: str = "vmap"             # vmap | scan
    # per-strategy knobs: FedADPOptions | FedLPOptions | FedLAMAOptions |
    # a plugin strategy's declared options_cls. None resolves to the
    # strategy's defaults (or to the deprecated flat fields below).
    algo_options: Optional[Any] = None
    # uplink compression policy (repro.core.wire.CompressionConfig):
    # packed wire-format quantized uploads + optional error feedback +
    # divergence-driven bit allocation (bits="auto"). None = fp32 uploads.
    compression: Optional[CompressionConfig] = None
    # trainable/frozen split (repro.core.partition.ParamPartition): only
    # the trainable sub-pytree is trained, divergence-scored, communicated,
    # and aggregated; the frozen base stays device-resident and is closed
    # over by local training (adapter fine-tuning). None = every leaf
    # trainable, bit-identical to the pre-partition engine.
    partition: Optional[ParamPartition] = None
    batch_per_client: int = 32
    # remat local-training steps (jax.checkpoint): caps activation memory
    # when K stacked clients run inside the scan engine
    remat: bool = False
    # ---- deprecated flat knobs (warn + fold into algo_options /
    # compression; kept as mirrors of the normalized values) ----
    fedadp_keep: float = 0.2       # FedADP keep fraction (equal-comm setting)
    fedlp_p: float = 0.5           # FedLP per-layer keep probability
    fedlama_tau: int = 2           # FedLAMA base aggregation interval τ'
    fedlama_lam: int = 2           # FedLAMA long-interval multiplier λ
    quantize_bits: int = 0         # quantized delta upload (0 = off)
    error_feedback: bool = False
    # multi-device: shard the stacked client axis over this mesh's 'clients'
    # axis; a 2-D ('clients', 'model') mesh (make_client_mesh(model=M))
    # additionally FSDP-shards param leaves + the EF residual store 1/M per
    # device. None = single-device round, unchanged.
    mesh: Optional[Mesh] = None
    # hierarchical two-tier aggregation (mesh only): the round's fused
    # reduce becomes a group-local psum over blocks of agg_group_size
    # consecutive 'clients'-axis devices followed by a ring all-reduce
    # across group leaders (lax.ppermute rotations; see
    # repro.core.aggregation.hierarchical_psum). 0 (default) keeps the
    # single flat psum — the compiled round is byte-identical to the
    # pre-tier engine. 1 = pure ring all-reduce over all devices.
    agg_group_size: int = 0
    # sample-axis sharding (mesh only): the drivers place ClientShards
    # with shard_samples=True — samples are permuted into per-device
    # blocks by the static client→device affinity, the cohort is drawn
    # per affinity group, and the round-batch gather reads device-local
    # rows only. At-rest dataset bytes/device drop ~1/D.
    shard_samples: bool = False
    # observability: in-jit metric taps + JSONL round ledger + profiling
    # hooks (see repro.telemetry). None (default) is the zero-cost path:
    # compiled rounds, scan carries, and fixed-seed trajectories are
    # bit-identical to a config without telemetry.
    telemetry: Optional[TelemetryConfig] = None

    # ------------------------------------------------------------------
    def _normalize_algo_options(self, scls):
        """Fold the deprecated flat per-algo knobs into ``algo_options``
        (validating through the owning options classes) and mirror the
        normalized options back onto the flat names, so equivalent
        spellings compare (and jit-cache) equal."""
        defaults = {f.name: f.default
                    for f in dataclasses.fields(type(self))}
        flat_set = [name for name, _, _ in _DEPRECATED_ALGO_FIELDS
                    if getattr(self, name) != defaults[name]]
        # validation of the flat values is unconditional (as it was when
        # FLConfig owned these checks), algo match or not: constructing
        # the options classes raises ValueError on bad values.
        legacy = {
            "fedadp": FedADPOptions(keep=self.fedadp_keep),
            "fedlp": FedLPOptions(p=self.fedlp_p),
            "fedlama": FedLAMAOptions(tau=self.fedlama_tau,
                                      lam=self.fedlama_lam),
        }
        opts = self.algo_options
        if opts is not None:
            ocls = getattr(scls, "options_cls", None)
            if ocls is None:
                raise TypeError(
                    f"strategy {self.algo!r} declares no options class; "
                    f"got algo_options={opts!r}")
            if not isinstance(opts, ocls):
                raise TypeError(
                    f"algo_options for strategy {self.algo!r} must be "
                    f"{ocls.__name__}, got {type(opts).__name__}")
            # a flat field that disagrees with the options instance is a
            # conflict; agreeing values (the mirrors dataclasses.replace
            # round-trips) are fine.
            for name, algo, field in _DEPRECATED_ALGO_FIELDS:
                if algo != self.algo or name not in flat_set:
                    continue
                if getattr(self, name) != getattr(opts, field):
                    raise ValueError(
                        f"FLConfig.{name}={getattr(self, name)} conflicts "
                        f"with algo_options.{field}="
                        f"{getattr(opts, field)}; pass one spelling, "
                        "not both")
        else:
            if flat_set:
                warnings.warn(
                    f"FLConfig fields {flat_set} are deprecated; pass "
                    "algo_options=FedADPOptions/FedLPOptions/"
                    "FedLAMAOptions(...) instead",
                    DeprecationWarning, stacklevel=3)
            opts = legacy.get(self.algo)
            if opts is None and getattr(scls, "options_cls", None):
                opts = scls.options_cls()
            object.__setattr__(self, "algo_options", opts)
        # mirror the normalized options back onto the flat names
        for name, algo, field in _DEPRECATED_ALGO_FIELDS:
            if algo == self.algo and opts is not None:
                object.__setattr__(self, name, getattr(opts, field))

    def _normalize_compression(self, scls):
        """Fold the deprecated ``quantize_bits``/``error_feedback`` flats
        into ``compression`` and mirror back."""
        comp = self.compression
        if comp is not None:
            if not isinstance(comp, CompressionConfig):
                raise TypeError(
                    "FLConfig.compression must be a repro.core.wire."
                    f"CompressionConfig or None, got {type(comp)}")
            # disagreement (not mere presence) is the conflict, so the
            # mirrored flats survive dataclasses.replace round-trips
            mirror_qb = 0 if comp.is_auto else int(comp.bits)
            if self.quantize_bits not in (0, mirror_qb) or \
                    (self.error_feedback
                     and not comp.error_feedback):
                raise ValueError(
                    "FLConfig.quantize_bits/error_feedback conflict with "
                    "compression=CompressionConfig(...); pass one "
                    "spelling, not both")
        else:
            if self.error_feedback:
                assert self.quantize_bits > 0, \
                    "error feedback needs quantization"
            if self.quantize_bits:
                warnings.warn(
                    "FLConfig(quantize_bits=..., error_feedback=...) is "
                    "deprecated; pass compression=CompressionConfig("
                    "bits=..., error_feedback=...) instead",
                    DeprecationWarning, stacklevel=3)
                comp = CompressionConfig(
                    bits=int(self.quantize_bits),
                    error_feedback=self.error_feedback)
                object.__setattr__(self, "compression", comp)
        if comp is not None:
            # mirror: flat ints keep showing the effective width (0 for
            # the adaptive allocator, whose width is per-round)
            object.__setattr__(self, "quantize_bits",
                               0 if comp.is_auto else int(comp.bits))
            object.__setattr__(self, "error_feedback", comp.error_feedback)
        if comp is not None and not scls.supports_quantize:
            raise ValueError(
                f"strategy {self.algo!r} declares supports_quantize=False "
                "(fedadp aggregates pruned neurons, not quantized deltas)")

    def __post_init__(self):
        # resolve through the strategy registry: unknown algos raise a
        # ValueError listing every registered name, and per-strategy
        # capability flags replace engine special-cases.
        scls = get_strategy_cls(self.algo)
        assert self.mode in ("vmap", "scan")
        assert 1 <= self.top_n <= self.clients_per_round
        self._normalize_algo_options(scls)
        self._normalize_compression(scls)
        if self.mode == "scan":
            if not scls.supports_scan:
                raise ValueError(
                    f"strategy {self.algo!r} declares supports_scan=False")
            if self.compression is not None:
                raise NotImplementedError(_SCAN_COMPRESSION_MSG)
        if self.partition is not None and \
                not isinstance(self.partition, ParamPartition):
            raise TypeError(
                "FLConfig.partition must be a repro.core.partition."
                f"ParamPartition or None, got {type(self.partition)}")
        if self.mesh is not None:
            assert self.mode == "vmap", \
                "client-axis sharding needs stacked clients (mode='vmap')"
            if not scls.supports_mesh:
                raise ValueError(
                    f"strategy {self.algo!r} declares supports_mesh=False "
                    "(a declared capability — see "
                    "repro.federated.strategies)")
            d = client_mesh_size(self.mesh)
            assert self.clients_per_round % d == 0, \
                f"K={self.clients_per_round} must divide over {d} devices"
            if self.agg_group_size:
                gs = self.agg_group_size
                if not (1 <= gs <= d and d % gs == 0):
                    raise ValueError(
                        f"FLConfig.agg_group_size={gs} must be in [1, {d}] "
                        f"and divide the 'clients' axis size {d}")
            if self.shard_samples and self.num_clients % d:
                raise ValueError(
                    f"FLConfig.shard_samples needs N={self.num_clients} "
                    f"divisible by the {d} 'clients'-axis devices (the "
                    "static client→device affinity assigns N/D clients "
                    "per device)")
        else:
            if self.agg_group_size:
                raise ValueError(
                    "FLConfig.agg_group_size is a mesh-round knob; pass "
                    "mesh=make_client_mesh(...) too")
            if self.shard_samples:
                raise ValueError(
                    "FLConfig.shard_samples is a mesh-round knob; pass "
                    "mesh=make_client_mesh(...) too")
        if self.telemetry is not None and \
                not isinstance(self.telemetry, TelemetryConfig):
            raise TypeError(
                "FLConfig.telemetry must be a repro.telemetry."
                f"TelemetryConfig or None, got {type(self.telemetry)}")


# ======================================================================
# Cross-round strategy state: shared plumbing
# ======================================================================
# Strategy state is ``{"client": {name: (N, ...) store}, "global":
# {name: tree}}`` or None (see FLStrategy.init_state). The helpers below
# are the *only* state plumbing the engines/drivers need — there is no
# per-strategy special-casing here; the EF residual store is just the
# client entry named "residual" declared by the quantize wrapper.
_IS_SPEC = lambda x: isinstance(x, P)     # noqa: E731  (tree_map is_leaf)


def _state_round_view(state: Optional[dict], clients) -> Optional[dict]:
    """Round-local view of the state: client stores are replaced by the
    participants' gathered ``(K, ...)`` rows; global entries pass through."""
    if not state or not state.get("client"):
        return state
    return {**state, "client": {n_: _gather_rows(s, clients)
                                for n_, s in state["client"].items()}}


def _state_scatter(state: Optional[dict], new_state: dict,
                   clients) -> Optional[dict]:
    """Persist a round's updated state: client rows are scattered back into
    the ``(N, ...)`` stores, global entries are replaced wholesale."""
    if state is None:
        return None
    out = dict(new_state)
    if state.get("client"):
        out["client"] = {n_: _scatter_rows(state["client"][n_], clients, r)
                         for n_, r in new_state["client"].items()}
    return out


def _state_shard_specs(state: dict, sspecs: dict, ax: Optional[str]) -> dict:
    """shard_map in/out specs for the round-local state: client rows get a
    leading 'clients' axis over their entry's trailing-dim specs
    (``residual_store_specs``-style placement), global entries use their
    specs as-is (replicated by default)."""
    out = {}
    if "client" in state:
        out["client"] = {
            n_: jax.tree.map(lambda s: P(ax, *s), sspecs["client"][n_],
                             is_leaf=_IS_SPEC)
            for n_ in state["client"]}
    if "global" in state:
        out["global"] = {n_: sspecs["global"][n_] for n_ in state["global"]}
    return out


def _state_model_gather(state: dict, sspecs: dict) -> dict:
    """Inside shard_map on a 2-D mesh: reassemble full state leaves from
    'model'-axis shards (client rows carry a leading client axis the specs
    do not mention, hence offset=1). No-op for replicated entries."""
    out = dict(state)
    for kind, off in (("client", 1), ("global", 0)):
        if state.get(kind):
            out[kind] = {n_: tree_all_gather(e, sspecs[kind][n_],
                                             MODEL_AXIS, offset=off)
                         for n_, e in state[kind].items()}
    return out


def _state_model_slice(state: dict, sspecs: dict, m: int) -> dict:
    """Inverse of :func:`_state_model_gather` (exact data movement)."""
    out = dict(state)
    for kind, off in (("client", 1), ("global", 0)):
        if state.get(kind):
            out[kind] = {n_: tree_shard_slice(e, sspecs[kind][n_], m,
                                              MODEL_AXIS, offset=off)
                         for n_, e in state[kind].items()}
    return out


def _place_state(state: dict, params, strategy, mesh) -> dict:
    """Device-put a (possibly host/numpy) state onto the mesh: client
    stores replicated over the client-id axis with 'model'-axis-sharded
    trailing dims, global entries per their declared specs."""
    sspecs = strategy.state_specs(params, state, mesh)
    out = dict(state)
    if state.get("client"):
        out["client"] = {
            n_: jax.device_put(e, to_named(jax.tree.map(
                lambda s: P(None, *s), sspecs["client"][n_],
                is_leaf=_IS_SPEC), mesh))
            for n_, e in state["client"].items()}
    if state.get("global"):
        out["global"] = {
            n_: jax.device_put(e, to_named(sspecs["global"][n_], mesh))
            for n_, e in state["global"].items()}
    return out


def _probe_sums(probe, partition, params, frozen, batch) -> dict:
    """The loss's own counters (``loss_fn.probe``, e.g. an MoE model's
    tokens per expert) summed over the stacked clients of ``batch``, one
    forward pass each at the round's global model (with one local step,
    the routing local training saw); {} without a probe."""
    if probe is None:
        return {}
    full = params if frozen is None else partition.merge(params, frozen)
    per = jax.lax.map(lambda b: probe(full, b), batch)
    return jax.tree.map(lambda x: x.sum(axis=0), per)


# ======================================================================
# Round builders
# ======================================================================
def _round_tail(strategy, umap: UnitMap, taps_on: bool, loss, selection,
                divs, state, key, wire=None, tap_sums=None) -> dict:
    """Every round's tail, on replicated inputs: the comm accounting
    (priced at the packed payload's per-unit wire bytes when ``wire`` is
    set), the strategy's state transition, and the telemetry taps over
    the reduced ``tap_sums`` (client-state sums of squares, probe
    counters). Returns the round's metrics dict."""
    with prof_mod.phase("fl.comm"):
        kw = ({} if wire is None
              else {"unit_bytes_override": wire["unit_bytes"]})
        comm = strategy.comm_profile(selection, umap, **kw)
    metrics = {"loss": loss, "comm": comm, "selection": selection}
    if state is not None:
        with prof_mod.phase("fl.state"):
            state = metrics["state"] = strategy.update_state(
                state, selection, divs, umap, key=key)
    if taps_on:
        tap_sums = tap_sums or {}
        with prof_mod.phase("fl.taps"):
            extra = taps_mod.probe_taps(tap_sums.get("probe", {}))
            if wire is not None:
                extra.update(wire_unit_bytes=wire["unit_bytes"],
                             wire_bits=wire["bits"])
            # a None client_sq has collect() derive the client-state
            # norms from the rows in ``state`` itself
            metrics["taps"] = taps_mod.collect(
                strategy, state, selection, divs, umap,
                client_sq=tap_sums.get("client_sq"), extra=extra)
    return metrics


def build_round_vmap(loss_fn, umap: UnitMap, flcfg: FLConfig,
                     opt: Optimizer | None = None):
    """Round function with stacked clients: one ``vmap`` trains all K.

    One body serves one device and a mesh. Off a mesh (``flcfg.mesh`` is
    None) it runs as it stands, and its three collectives are
    identities. With a mesh it runs under ``shard_map`` over the
    ('clients'[, 'model']) axes: every device trains its K/D clients,
    and the round is stitched back together with collectives:

    - FedLDF divergence feedback: per-device (K/D, U) divergence blocks are
      ``all_gather``'d into the full (K, U) matrix so the top-n selection —
      which needs *all* clients' divergences (Eq. 4) — is computed
      replicated on every device; each device then slices back its own rows.
    - Aggregation (Eq. 5), the loss sum and the taps' additive partials
      travel in ONE fused ``psum``: the strategy's additive local parts
      (:meth:`FLStrategy.psum_parts`, or ``uplink_psum_parts`` for a
      packed uplink), one collective, then the replicated epilogue
      (:meth:`FLStrategy.psum_finalize`) — a single cross-device
      rendezvous per round instead of one per parameter leaf. Comm
      accounting runs on the replicated selection and needs no
      collective. (:func:`~repro.core.aggregation.aggregate_stacked`
      with ``axis_name`` offers the same reduction as a standalone call.)
    - Strategy state (the cross-round seam): global entries enter and
      leave replicated — selection and ``update_state`` run on identical
      replicated inputs on every device, so the state trajectory matches
      the single-device round. Client entries (e.g. the EF residual
      store's rows) stay device-local (spec P('clients', ...) rows); the
      driver's store scatter handles the store update.

    On a 2-D ('clients', 'model') mesh the round is additionally
    FSDP-sharded: parameter leaves (and EF residual rows) enter and leave
    the body as 1/M 'model'-axis shards per :func:`fl_param_specs`. The
    full model is reassembled *transiently* for local training
    (``tree_all_gather``), and the Eq. 5 numerators are sliced back to this
    device's shard (``tree_shard_slice``) **before** the fused psum — which
    reduces over 'clients' only, so each model column reduces its own 1/M
    slice and the at-rest params/store replication cliff disappears along
    with 1/M of the collective payload. Gather/slice are exact data
    movement, so a 2-D trajectory matches the 1-D mesh bit-for-bit and the
    single-device round to the usual fp32 psum-order tolerance.

    Outputs are replicated (per model column) by construction
    (psum/all_gather/replicated inputs); replication *checking* is
    disabled — see :func:`repro.launch.mesh.shard_map_norep` — and covered
    by the equivalence tests instead (tests/test_shard_engine.py,
    tests/test_model_axis.py).
    """
    opt = opt or sgd(flcfg.lr)
    local_update = make_local_update(loss_fn, opt, flcfg.local_steps,
                                     remat=flcfg.remat,
                                     partition=flcfg.partition)
    strategy = make_strategy(flcfg)
    probe = getattr(loss_fn, "probe", None)
    mesh, ax = flcfg.mesh, CLIENT_AXIS
    d = 1 if mesh is None else client_mesh_size(mesh)
    m = 1 if mesh is None else model_mesh_size(mesh)
    k = flcfg.clients_per_round
    taps_on = flcfg.telemetry is not None and flcfg.telemetry.taps
    # hierarchical two-tier reduce: group-local psum + group-leader ring.
    # gs == 0 (default) or gs == d keeps the single flat psum — reduce_
    # lowers to exactly the pre-tier collective, byte-identical rounds.
    gs = flcfg.agg_group_size
    hier = bool(gs) and gs < d

    # the round's three collectives; off a mesh each is the identity
    if mesh is None:
        gather_ = rows_ = reduce_ = lambda x: x     # noqa: E731
    else:
        def gather_(divs_loc):
            return jax.lax.all_gather(divs_loc, ax, axis=0, tiled=True)

        def rows_(selection):
            return local_rows(selection, ax, k // d)

        def reduce_(vals):
            if hier:
                return agg.hierarchical_psum(vals, ax, axis_size=d,
                                             group_size=gs)
            return jax.lax.psum(vals, ax)

    def body(pspecs, sspecs, fspecs, params, batch, data_sizes, key,
             state=None, frozen=None):
        # on a mesh everything in here sees the LOCAL shard: K/D clients
        # per device, and (2-D mesh) 1/M 'model'-axis blocks of each
        # param/state leaf. With a partition, ``params`` is the TRAINABLE
        # sub-pytree — the frozen base broadcasts into every client's
        # local step and never touches the reduce or the outputs.
        params_shard = params
        if m > 1:
            with prof_mod.phase("fl.collective"):
                params = tree_all_gather(params, pspecs, MODEL_AXIS)
                if frozen is not None:
                    frozen = tree_all_gather(frozen, fspecs, MODEL_AXIS)
                if state is not None:
                    state = _state_model_gather(state, sspecs)
        lu = (local_update if frozen is None
              else lambda p, b: local_update(p, b, frozen))
        with prof_mod.phase("fl.local"):
            locals_, losses = jax.vmap(lu, in_axes=(None, 0))(params, batch)

        # divergence feedback (Eq. 3) is computed on the TRUE local model —
        # upload transforms (e.g. quantization) below only affect the
        # uploaded payload.
        divs = None
        if strategy.needs_divergence:
            with prof_mod.phase("fl.eq3"):
                divs = umap.divergence_batched(locals_, params)
            with prof_mod.phase("fl.collective"):
                divs = gather_(divs)
        # selection is replicated: divs are all-gathered and global state
        # entries enter replicated (client state rows are device-local and
        # must not drive selection under a mesh — see FLStrategy docs)
        with prof_mod.phase("fl.eq4"):
            selection = strategy.select_with_state(
                state, divs, key, k, umap.num_units,
                flcfg.top_n)                                   # (K, U), repl.
            sel_loc = rows_(selection)

        # the strategy's additive Eq. 5 parts over this device's clients;
        # they ride the fused reduce below (one rendezvous per round)
        wire = None
        res_rows = (state["client"]["residual"]
                    if strategy.tracks_residuals else None)
        if strategy.packed_upload:
            # packed wire-format uplink: quantize the local client deltas
            # into PackedPayload buffers and reduce them through the fused
            # dequant+EF+accumulate kernel (its accumulate half is scoped
            # fl.eq5 inside the strategy)
            with prof_mod.phase("fl.uplink"):
                parts, denom_loc, new_rows, wire = \
                    strategy.uplink_psum_parts(locals_, params, umap,
                                               sel_loc, divs, data_sizes,
                                               res_rows)
        else:
            uploads = locals_
            if strategy.transforms_upload:
                # e.g. quantized deltas: the server reconstructs
                # Ĝ + dequant(Q(Δ + e)) for uploaded layers; error
                # feedback residuals update only where a layer was
                # actually uploaded (s[k,u] = 1)
                with prof_mod.phase("fl.uplink"):
                    uploads, cand_res = jax.vmap(
                        lambda loc, res: strategy.transform_upload(
                            loc, params, umap, res),
                        in_axes=(0, 0 if res_rows is not None else None),
                    )(locals_, res_rows)
                    if strategy.tracks_residuals:
                        new_rows = jax.vmap(
                            lambda cand, old, s: strategy.update_residual(
                                cand, old, s, umap, params),
                            in_axes=(0, 0, 0))(cand_res, res_rows, sel_loc)
            with prof_mod.phase("fl.eq5"):
                parts, denom_loc = strategy.psum_parts(
                    uploads, umap, sel_loc, data_sizes,
                    global_params=params)
        if strategy.tracks_residuals:
            # the residual rows ride the state seam as the client entry
            # named "residual" (see FLStrategy.init_state)
            state = {**state, "client": {**state["client"],
                                         "residual": new_rows}}
        if m > 1:
            with prof_mod.phase("fl.collective"):
                parts = tree_shard_slice(parts, pspecs, m, MODEL_AXIS)
                # a param-structured denominator (element-wise
                # aggregation, e.g. FedADP's mask counts) shards with the
                # numerators; the Eq. 5 (U,) unit denominator stays
                # replicated
                if jax.tree.structure(denom_loc) == \
                        jax.tree.structure(parts):
                    denom_loc = tree_shard_slice(denom_loc, pspecs, m,
                                                 MODEL_AXIS)
        # telemetry partials (client-state sums of squares — EF residual
        # rows are device-local — and probe counters) ride the SAME
        # reduce: taps must not add a second rendezvous. Disabled
        # telemetry adds no leaves, so the compiled round is unchanged.
        tap_sums = {}
        if taps_on:
            with prof_mod.phase("fl.taps"):
                if state is not None and state.get("client"):
                    tap_sums["client_sq"] = taps_mod.client_sqsums(
                        state["client"])
                if probe is not None:
                    tap_sums["probe"] = _probe_sums(
                        probe, flcfg.partition, params, frozen, batch)
        with prof_mod.phase("fl.collective"):
            (parts, denom), loss_sum, tap_sums = reduce_(
                ((parts, denom_loc), losses.sum(), tap_sums))
        with prof_mod.phase("fl.eq5"):
            new_params = strategy.psum_finalize(parts, denom, umap,
                                                params_shard, params_shard)
        metrics = _round_tail(strategy, umap, taps_on, loss_sum / k,
                              selection, divs, state, key, wire, tap_sums)
        if mesh is not None:
            # per-tier aggregation-traffic split: static topology ×
            # payload arithmetic (this device's Eq. 5 numerator tree, a
            # 1/M slice on a 2-D mesh), not riding the reduce
            with prof_mod.phase("fl.comm"):
                for n_, v in comm_mod.agg_tier_bytes(
                        umap.total_bytes / m, d, gs if hier else 0).items():
                    metrics["comm"][n_] = jnp.float32(v)
        if m > 1 and state is not None:
            # client rows go back to this device's 1/M store-row shard
            with prof_mod.phase("fl.collective"):
                metrics["state"] = _state_model_slice(metrics["state"],
                                                      sspecs, m)
        return new_params, metrics

    if mesh is None:
        return functools.partial(body, None, None, None)   # no specs

    out_metrics_spec = {"loss": P(), "comm": P(), "selection": P()}
    if taps_on:
        out_metrics_spec["taps"] = P()

    def round_fn(params, batch, data_sizes, key, state=None, frozen=None):
        # specs are pure shape logic, computed at trace time (the drivers
        # jit round_fn, so this runs once per compiled configuration).
        # State and frozen-base arguments are optional; both presences are
        # static per configuration, so the arg list is assembled once.
        pspecs = fl_param_specs(params, mesh)
        fspecs = None if frozen is None else fl_param_specs(frozen, mesh)
        sspecs = None
        in_specs = [pspecs, P(ax), P(ax), P()]
        args = [params, batch, data_sizes, key]
        out_metrics = dict(out_metrics_spec)
        if state is not None:
            sspecs = strategy.state_specs(params, state, mesh)
            st_specs = _state_shard_specs(state, sspecs, ax)
            in_specs.append(st_specs)
            args.append(state)
            out_metrics["state"] = st_specs
        if frozen is not None:
            # the frozen base enters model-sharded like the params and is
            # consumed inside the body (all-gathered transiently on a 2-D
            # mesh); it is never part of the outputs
            in_specs.append(fspecs)
            args.append(frozen)
        names = [n_ for n_, a in (("state", state), ("frozen", frozen))
                 if a is not None]

        def call(p, b, s, key_, *rest):
            return body(pspecs, sspecs, fspecs, p, b, s, key_,
                        **dict(zip(names, rest)))

        sharded = shard_map_norep(call, mesh, in_specs=tuple(in_specs),
                                  out_specs=(pspecs, out_metrics))
        return sharded(*args)

    return round_fn


def scan_client_passes(strategy) -> int:
    """How many times the scan round trains each client: twice where the
    strategy needs every client's Eq. 3 scores before it selects and its
    selection is not the per-unit top-n the round can keep as the clients
    train (Eq. 5 then recomputes each local), else once."""
    if strategy.needs_divergence and not (
            strategy.selects_topn_divergence and strategy.eq5_weighted):
        return 2
    return 1


def build_round_scan(loss_fn, umap: UnitMap, flcfg: FLConfig,
                     opt: Optimizer | None = None):
    """Round function with sequential clients.

    Each client trains once (:func:`scan_client_passes`), except where the
    strategy needs every client's Eq. 3 scores before it selects and its
    selection is not the per-unit top-n: then phase 1 trains each client
    for Eq. 3 and phase 2 trains it again for Eq. 5 (FedLAMA).

    Memory, ``eq5_weighted`` strategies: O(global + 1 local + 1 float32
    accumulator) models, independent of K — selected layers are streamed
    into the Eq. 5 accumulator as each client trains. A top-n strategy
    (``selects_topn_divergence``, FedLDF) holds n locals at the parameter
    dtype in place of the accumulator: each unit keeps its n most
    divergent locals as the clients train
    (:func:`repro.core.aggregation.topn_insert`), and Eq. 5 reduces them
    after selection — at top-2 over bf16 adapters the same bytes as the
    float32 accumulator. Strategies whose aggregation is not an Eq. 5
    weighted mean (e.g. FedADP's element-wise neuron masks) instead have
    their sequentially-trained locals *stacked* by the scan and aggregated
    through the same :meth:`FLStrategy.psum_parts` /
    :meth:`FLStrategy.psum_finalize` halves as the vmap round — O(K)
    parameter memory, but still O(1) activation memory, which is the scan
    engine's binding constraint for deep models.
    """
    if getattr(flcfg, "compression", None) is not None or \
            getattr(flcfg, "quantize_bits", 0):
        raise NotImplementedError(_SCAN_COMPRESSION_MSG)
    strategy = make_strategy(flcfg)
    if not strategy.supports_scan:
        raise NotImplementedError(
            f"strategy {strategy.name!r} declares supports_scan=False")
    opt = opt or sgd(flcfg.lr)
    local_update = make_local_update(loss_fn, opt, flcfg.local_steps,
                                     remat=flcfg.remat,
                                     partition=flcfg.partition)
    k = flcfg.clients_per_round
    taps_on = flcfg.telemetry is not None and flcfg.telemetry.taps
    probe = getattr(loss_fn, "probe", None)
    # Eq. 4 is the per-unit top-n of the Eq. 3 scores: keep those locals
    # as the clients train instead of training every client again
    topn = strategy.needs_divergence and scan_client_passes(strategy) == 1

    def round_fn(params: Pytree, batch: dict, data_sizes: jnp.ndarray,
                 key: jax.Array, state: Optional[dict] = None,
                 frozen: Optional[Pytree] = None):
        lu = (local_update if frozen is None
              else lambda p, b: local_update(p, b, frozen))
        # each client-loop scan is scoped fl.local as a whole (its slicing
        # and stacking included); the Eq. 3 / Eq. 5 work inside the body
        # carries its own, inner phase
        if topn:
            # ---- one pass: Eq. 3, and each unit's top-n locals kept
            def one_pass(carry, inp):
                batch_k, client = inp
                slots, score, who = carry
                local, loss = lu(params, batch_k)
                with prof_mod.phase("fl.eq3"):
                    div = umap.divergence(local, params)
                with prof_mod.phase("fl.eq5"):
                    carry = agg.topn_insert(slots, score, who, local, div,
                                            client, umap)
                return carry, (div, loss)

            with prof_mod.phase("fl.eq5"):
                shape = (flcfg.top_n, umap.num_units)
                slots0 = ([tree_zeros_like(params)] * flcfg.top_n,
                          jnp.full(shape, -jnp.inf, jnp.float32),
                          jnp.zeros(shape, jnp.int32))
            with prof_mod.phase("fl.local"):
                (slots, _, who), (divs, losses1) = jax.lax.scan(
                    one_pass, slots0, (batch, jnp.arange(k)))
        # ---- phase 1: divergence feedback (only if the policy needs it)
        elif strategy.needs_divergence:
            def phase1(carry, batch_k):
                local, loss = lu(params, batch_k)
                with prof_mod.phase("fl.eq3"):
                    div = umap.divergence(local, params)
                return carry, (div, loss)

            with prof_mod.phase("fl.local"):
                _, (divs, losses1) = jax.lax.scan(phase1, None, batch)
        else:
            divs, losses1 = None, None

        with prof_mod.phase("fl.eq4"):
            selection = strategy.select_with_state(
                state, divs, key, k, umap.num_units, flcfg.top_n)

        if topn:
            with prof_mod.phase("fl.eq5"):
                new_params = agg.topn_aggregate(slots, who, umap, selection,
                                                data_sizes, params)
        elif strategy.eq5_weighted:
            with prof_mod.phase("fl.eq5"):
                w, denom = agg.unit_weights(selection, data_sizes)
                frac = w / jnp.where(denom > 0, denom, 1.0)[None, :]  # (K,U)

            # ---- phase 2: recompute local training, stream layers in
            def phase2(acc, inp):
                batch_k, frac_k = inp
                local, loss = lu(params, batch_k)
                with prof_mod.phase("fl.eq5"):
                    acc = agg.streaming_add(acc, local, umap, frac_k)
                return acc, loss

            with prof_mod.phase("fl.eq5"):
                acc0 = agg.streaming_init(params)
            with prof_mod.phase("fl.local"):
                acc, losses2 = jax.lax.scan(phase2, acc0, (batch, frac))
            with prof_mod.phase("fl.eq5"):
                new_params = agg.streaming_finalize(acc, umap, denom, params)
        else:
            # ---- phase 2 (non-Eq.5 aggregation, e.g. FedADP): train
            # sequentially, let the scan stack the locals, and aggregate
            # them through the strategy's two halves as the vmap round
            # does (with no reduce between them).
            def phase2_stack(carry, batch_k):
                return carry, lu(params, batch_k)

            with prof_mod.phase("fl.local"):
                _, (stacked, losses2) = jax.lax.scan(phase2_stack, None,
                                                     batch)
            with prof_mod.phase("fl.eq5"):
                parts, denom = strategy.psum_parts(
                    stacked, umap, selection, data_sizes,
                    global_params=params)
                new_params = strategy.psum_finalize(parts, denom, umap,
                                                    params, params)

        loss = (losses1 if losses1 is not None else losses2).mean()
        tap_sums = None
        if taps_on:
            with prof_mod.phase("fl.taps"):
                tap_sums = {"probe": _probe_sums(probe, flcfg.partition,
                                                 params, frozen, batch)}
        return new_params, _round_tail(strategy, umap, taps_on, loss,
                                       selection, divs, state, key,
                                       tap_sums=tap_sums)

    return round_fn


def build_round_fn(loss_fn, umap: UnitMap, flcfg: FLConfig,
                   opt: Optimizer | None = None):
    if flcfg.mode == "vmap":
        return build_round_vmap(loss_fn, umap, flcfg, opt)
    return build_round_scan(loss_fn, umap, flcfg, opt)


# ----------------------------------------------------------------------
# Compiled-callable cache. Both drivers build their jitted functions from
# (loss_fn, umap, flcfg) alone; rebuilding a fresh ``jax.jit`` object per
# driver call would force a full retrace + XLA recompile every time
# ``run_training``/``run_training_scan`` is invoked (the jit cache is keyed
# on function identity). The cache keeps one compiled callable per distinct
# configuration, so repeated runs — benchmark repetitions, sweeps, tests —
# pay compilation once.
# ----------------------------------------------------------------------
_JIT_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_JIT_CACHE_MAX = 64   # LRU bound: evicts one cold entry, never the hot set


def _umap_cache_key(umap: UnitMap) -> tuple:
    return (umap.names, tuple(sorted(umap.spans.items())), umap.unit_bytes)


def _trace_flcfg(flcfg: FLConfig) -> FLConfig:
    """Cache-key view of the config: telemetry is reduced to its
    trace-relevant subset (taps on/off, full-selection on/off), so two runs
    differing only in host-side observability — ledger path, run id,
    verbosity, profiler window — share one compiled round instead of
    forcing a retrace."""
    if flcfg.telemetry is None:
        return flcfg
    return dataclasses.replace(flcfg,
                               telemetry=flcfg.telemetry.trace_key())


def _cached(kind: str, loss_fn, umap: UnitMap, flcfg: FLConfig, build):
    """NOTE: keyed on ``loss_fn`` *identity* — pass a stable function (module
    function, bound method, or a lambda created once) to hit the cache;
    a lambda re-created per call misses every time. The key also carries
    the *class* currently registered under ``flcfg.algo``: the registry is
    mutable (unregister + re-register is the iterate-on-a-plugin flow), so
    an equal FLConfig must not reuse a round compiled for a previously
    registered strategy class.

    Every lookup is reported to the telemetry retrace counters
    (:func:`repro.telemetry.profiling.note_engine_cache`): a nonzero
    ``<kind>_builds`` delta across identical driver calls is the retrace
    regression tests/test_telemetry.py pins."""
    key = (kind, loss_fn, _umap_cache_key(umap), _trace_flcfg(flcfg),
           get_strategy_cls(flcfg.algo))
    try:
        fn = _JIT_CACHE.get(key)
    except TypeError:       # unhashable loss_fn — skip caching
        prof_mod.note_engine_cache(kind, hit=False)
        return build()
    if fn is None:
        prof_mod.note_engine_cache(kind, hit=False)
        while len(_JIT_CACHE) >= _JIT_CACHE_MAX:
            _JIT_CACHE.popitem(last=False)
        fn = _JIT_CACHE[key] = build()
    else:
        prof_mod.note_engine_cache(kind, hit=True)
        _JIT_CACHE.move_to_end(key)
    return fn


# ======================================================================
# Multi-round drivers
# ======================================================================
def _run_meta(flcfg: FLConfig, *, driver: str, umap: UnitMap, params: Pytree,
              seed: int, sampler: str, start_round: int, rounds: int,
              run_id: str, partition_info: Optional[dict] = None) -> dict:
    """Ledger run-header metadata: everything a consumer needs to label a
    segment without rebuilding the model (notably the layer-unit names,
    which index every per-layer tap vector — under a partition those are
    the *trainable* units, e.g. per-adapter-layer ``blocks/<d>`` labels,
    and ``partition`` carries the trainable/frozen param+byte totals)."""
    mesh = flcfg.mesh
    agg_meta = None
    if mesh is not None:
        d = client_mesh_size(mesh)
        gs = flcfg.agg_group_size if (
            flcfg.agg_group_size and flcfg.agg_group_size < d) else d
        agg_meta = {"group_size": int(gs), "num_groups": int(d // gs),
                    "tiers": 1 if gs == d else 2}
    return {"run_id": run_id, "driver": driver, "algo": flcfg.algo,
            "agg": agg_meta, "shard_samples": bool(flcfg.shard_samples),
            "partition": partition_info,
            "mode": flcfg.mode, "sampler": sampler, "seed": seed,
            "start_round": start_round, "rounds": rounds,
            "num_clients": flcfg.num_clients,
            "clients_per_round": flcfg.clients_per_round,
            "top_n": flcfg.top_n,
            "quantize_bits": flcfg.quantize_bits,
            "compression": (None if flcfg.compression is None else
                            {"bits": flcfg.compression.bits,
                             "error_feedback":
                                 flcfg.compression.error_feedback,
                             "fused": flcfg.compression.fused}),
            "mesh": (dict(mesh.shape) if mesh is not None else None),
            "units": list(umap.names),
            # share of Eq. 3's bytes the divergence kernel reads in place
            "eq3_in_place_share": umap.in_place_share(params),
            # times the scan round trains each client (None: vmap round)
            "scan_client_passes": (
                None if flcfg.mode == "vmap"
                else scan_client_passes(make_strategy(flcfg))),
            "unit_bytes": [float(b) for b in np.asarray(umap.unit_bytes)]}


@dataclasses.dataclass
class TrainLog:
    rounds: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)
    test_errors: list = dataclasses.field(default_factory=list)
    uplink_mb: list = dataclasses.field(default_factory=list)
    meter: comm_mod.CommMeter = dataclasses.field(
        default_factory=comm_mod.CommMeter)
    # strategy state after the last round (None for stateless strategies);
    # feed it back as run_training*(server_state=...) with
    # start_round=<rounds done> to continue a run bit-identically
    # (checkpoint via repro.checkpoint.save_server_state)
    final_state: Optional[dict] = None


def _gather_rows(store: Pytree, clients: jnp.ndarray) -> Pytree:
    return jax.tree.map(lambda l: l[clients], store)


def _scatter_rows(store: Pytree, clients: jnp.ndarray,
                  rows: Pytree) -> Pytree:
    # explicit cast: EF update arithmetic runs fp32, the store keeps each
    # leaf's own dtype (jax refuses an implicit fp32->bf16 scatter cast)
    return jax.tree.map(
        lambda full, r: full.at[clients].set(r.astype(full.dtype)),
        store, rows)


def run_training(params: Pytree, loss_fn, fldata, flcfg: FLConfig,
                 rounds: int, eval_fn: Optional[Callable[[Pytree], float]] = None,
                 eval_every: int = 10, seed: int = 0,
                 verbose: bool = False,
                 sampler: str = "host",
                 start_round: int = 0,
                 server_state: Optional[dict] = None
                 ) -> tuple[Pytree, TrainLog]:
    """Full FL training loop (paper Algorithm 1 ServerExecute), host-driven.

    One Python iteration per round — the reference oracle for
    :func:`run_training_scan`. ``sampler`` picks the RNG stream:

    - ``"host"`` (default): numpy client sampling + numpy batch gathering,
      byte-compatible with the original seed driver;
    - ``"jax"``: the engine's key schedule (:func:`round_keys` +
      :func:`sample_clients_jax` + :meth:`ClientShards.gather`), so a fixed
      seed yields the *same trajectory* as ``run_training_scan``.

    Strategy cross-round state (the EF residual store, FedLAMA's interval
    accumulators, any :meth:`FLStrategy.init_state` schema) is threaded
    through rounds generically: client-entry rows are gathered/scattered
    per round, the final state lands in ``log.final_state``. To resume a
    checkpointed run, pass ``start_round=<rounds already done>`` and
    ``server_state=<saved state>`` — with ``sampler="jax"`` the per-round
    keys are a pure function of (seed, absolute round index), so the
    continuation is bit-identical to the uninterrupted run (the "host"
    sampler's sequential numpy stream is not resumable).
    """
    assert sampler in ("host", "jax"), sampler
    partition, frozen, pinfo = flcfg.partition, None, None
    if partition is not None:
        # split ONCE: everything downstream — unit map, strategy state,
        # round functions, comm accounting — sees the trainable sub-pytree;
        # the frozen base rides along as an untouched round input
        pinfo = partition_counts(partition, params)
        params, frozen = partition.split(params)
    umap = UnitMap.build(params)
    strategy = make_strategy(flcfg)
    round_fn = _cached("round", loss_fn, umap, flcfg,
                       lambda: jax.jit(build_round_fn(loss_fn, umap, flcfg)))
    log = TrainLog()
    tele = flcfg.telemetry
    sink = ProgressSink.for_run(tele, verbose)
    sample_sys = tele is not None and tele.sample_system
    win = prof_mod.ProfileWindow.from_config(tele)
    ledger = None
    if tele is not None and tele.wants_ledger:
        ledger = RoundLedger(tele.ledger_path, meta=_run_meta(
            flcfg, driver="host", umap=umap, params=params, seed=seed,
            sampler=sampler, start_round=start_round, rounds=rounds,
            run_id=tele.run_id, partition_info=pinfo))
    if flcfg.mesh is not None:
        # place the global model over the mesh: replicated across 'clients'
        # so the sharded round starts from device-local copies everywhere,
        # and (2-D mesh) FSDP-sharded 1/M per device along the 'model' axis.
        # The frozen base gets the same policy: big base leaves land
        # model-sharded, small (indivisible) adapters replicate.
        params = jax.device_put(
            params, to_named(fl_param_specs(params, flcfg.mesh), flcfg.mesh))
        if frozen is not None:
            frozen = jax.device_put(
                frozen,
                to_named(fl_param_specs(frozen, flcfg.mesh), flcfg.mesh))
    merged = ((lambda p: p) if partition is None
              else (lambda p: partition.merge(p, frozen)))
    if server_state is not None:
        # checkpoint-loaded states arrive as numpy; the row scatter below
        # needs jax arrays (and a mesh needs explicit placement)
        state = (_place_state(server_state, params, strategy, flcfg.mesh)
                 if flcfg.mesh is not None
                 else jax.tree.map(jnp.asarray, server_state))
    else:
        state = strategy.init_state(params, flcfg.num_clients, flcfg.mesh)
    if sampler == "jax":
        shards = (fldata if isinstance(fldata, ClientShards)
                  else ClientShards.from_federated(fldata))
        if flcfg.mesh is not None:
            shards = shards.place(flcfg.mesh,
                                  shard_samples=flcfg.shard_samples)
        all_sizes_dev = shards.data_sizes()
        base_key = jax.random.PRNGKey(seed)
    elif flcfg.shard_samples:
        raise ValueError(
            "FLConfig.shard_samples needs sampler='jax' (the host sampler "
            "never builds device-resident ClientShards)")
    else:
        rng = np.random.default_rng(seed)
        all_sizes = fldata.data_sizes()
        # per-round algorithm keys: fold the round index into one base key.
        # (The old ``PRNGKey(seed * 100003 + t)`` schedule degenerated to
        # ``key = t`` at seed=0 and let nearby seeds replay each other's
        # round keys once t crossed the stride.)
        host_base = jax.random.PRNGKey(seed)

    try:
        for t in range(start_round, start_round + rounds):
            win.round_begin(t)
            with prof_mod.span("fl.host", round=t):
                wall0 = time.perf_counter() if sample_sys else None
                if sampler == "jax":
                    with prof_mod.span("fl.host.sample"):
                        ck, bk, key = round_keys(base_key, t)
                        # affinity-laid-out shards (num_groups > 1) switch
                        # the cohort draw to per-group sampling, matching
                        # the scan engine's trajectory on the same shards
                        clients = sample_clients_grouped(
                            ck, flcfg.num_clients, flcfg.clients_per_round,
                            shards.num_groups)
                    with prof_mod.span("fl.host.gather"):
                        batch = shards.gather(clients,
                                              flcfg.batch_per_client, bk)
                        sizes = all_sizes_dev[clients]
                else:
                    with prof_mod.span("fl.host.sample"):
                        clients = sample_clients(rng, flcfg.num_clients,
                                                 flcfg.clients_per_round)
                    with prof_mod.span("fl.host.gather"):
                        batch = fldata.round_batch(
                            clients, flcfg.batch_per_client, rng)
                        batch = {k: jnp.asarray(v) for k, v in batch.items()}
                        sizes = jnp.asarray(all_sizes[clients])
                        key = jax.random.fold_in(host_base, t)
                        clients = jnp.asarray(clients)
                with prof_mod.span("fl.host.dispatch"):
                    kw = {} if frozen is None else {"frozen": frozen}
                    if state is not None:
                        st_rows = _state_round_view(state, clients)
                        params, metrics = round_fn(params, batch, sizes, key,
                                                   st_rows, **kw)
                        state = _state_scatter(state, metrics["state"],
                                               clients)
                    else:
                        params, metrics = round_fn(params, batch, sizes, key,
                                                   **kw)
                with prof_mod.span("fl.host.pull"):
                    log.meter.update(metrics["comm"])
                    log.rounds.append(t)
                    loss_t = float(metrics["loss"])     # device sync
                    log.losses.append(loss_t)
                    log.uplink_mb.append(log.meter.uplink_bytes / 1e6)
                do_eval = eval_fn is not None and (
                    t % eval_every == 0 or t == start_round + rounds - 1)
                with prof_mod.span("fl.host.log"):
                    if ledger is not None:
                        # the float() pull above synced the round, so
                        # wall_s is real compute time, not dispatch time
                        wall_s = (time.perf_counter() - wall0
                                  if wall0 is not None else None)
                        mem = (prof_mod.device_memory_peak() if sample_sys
                               else None)
                        ledger.round(
                            t, loss_t, jax.device_get(metrics["comm"]),
                            log.meter.uplink_bytes,
                            taps=(jax.device_get(metrics["taps"])
                                  if "taps" in metrics else None),
                            selection=(metrics["selection"]
                                       if tele.full_selection else None),
                            wall_s=wall_s, mem_peak_bytes=mem)
                    if not do_eval and sink.enabled and t % 10 == 0:
                        sink.round(t, loss_t)
                if do_eval:
                    with prof_mod.span("fl.host.eval"):
                        err = float(eval_fn(merged(params)))
                        log.test_errors.append(
                            (t, err, log.meter.uplink_bytes))
                        if ledger is not None:
                            ledger.eval(t, err, log.meter.uplink_bytes)
                        sink.round(t, loss_t, test_error=err,
                                   uplink_bytes=log.meter.uplink_bytes)
            win.round_end(t)
    finally:
        win.close()
        if ledger is not None:
            ledger.close()
    log.final_state = state
    return merged(params), log


# ======================================================================
# Device-resident multi-round engine
# ======================================================================
def _eval_cuts(rounds: int, eval_every: int, do_eval: bool) -> list[int]:
    """Block boundaries: cut after round t iff the host driver would eval
    there (t % eval_every == 0 or t == rounds-1); one block when not
    evaluating."""
    if not do_eval:
        return [rounds]
    return sorted({t + 1 for t in range(rounds)
                   if t % eval_every == 0 or t == rounds - 1})


def _build_block_fn(loss_fn, umap: UnitMap, flcfg: FLConfig):
    """Compiled multi-round block: ``lax.scan`` of the round function.

    ``run_block(carry, shards, all_sizes, base_key, t0, num)`` advances the
    carry (params, strategy state, comm accumulator) by ``num`` rounds
    starting at round index ``t0``, entirely on device. ``t0`` is a traced
    scalar so eval blocks of equal length share one executable. A
    stateless strategy carries ``None`` — zero extra carry leaves.
    """
    round_fn = build_round_fn(loss_fn, umap, flcfg)
    strategy = make_strategy(flcfg)
    mesh = flcfg.mesh
    # sharded engine: pin the gathered round batch (and client-state rows)
    # to the 'clients' axis so XLA partitions the gather itself — each
    # device materialises only its own K/D clients' samples, never the
    # full batch. Client-state rows additionally keep their leaves'
    # 'model'-axis sharding, and the scattered store is pinned back to its
    # (replicated-N, 'model') layout so the scan carry's sharding stays
    # fixed across rounds.
    client_spec = (NamedSharding(mesh, P(CLIENT_AXIS))
                   if mesh is not None else None)

    def constrain_state(st, params, *, rows: bool):
        """Pin a round-local state view (rows=True) or the full store
        (rows=False) to its mesh layout; no-op off-mesh / stateless."""
        if mesh is None or st is None or not st.get("client"):
            return st
        sspecs = strategy.state_specs(params, st, mesh)
        lead = CLIENT_AXIS if rows else None
        out = dict(st)
        out["client"] = {
            n_: jax.lax.with_sharding_constraint(
                e, jax.tree.map(
                    lambda s: NamedSharding(mesh, P(lead, *s)),
                    sspecs["client"][n_], is_leaf=_IS_SPEC))
            for n_, e in st["client"].items()}
        return out

    def one_round(carry, t, shards, all_sizes, base_key, frozen):
        params, state, acc = carry

        # shards.num_groups is static pytree aux: affinity-laid-out shards
        # flip the cohort draw to per-group sampling at trace time (a
        # num_groups of 1 lowers to exactly sample_clients_jax).
        def sample(k_):
            return sample_clients_grouped(k_, flcfg.num_clients,
                                          flcfg.clients_per_round,
                                          shards.num_groups)

        with prof_mod.phase("fl.sample"):
            ck, bk, ak = round_keys(base_key, t)
            if mesh is not None:
                # run the RNG draws replicated inside shard_map: the
                # non-partitionable threefry lowering changes values when
                # XLA shards it (see ClientShards.gather / replicated_rng)
                # — the participant draw gets the same treatment as the
                # batch draw.
                clients = replicated_rng(sample, mesh)(ck)
            else:
                clients = sample(ck)
            batch = shards.gather(clients, flcfg.batch_per_client, bk,
                                  mesh=mesh)
            sizes = all_sizes[clients]
            if client_spec is not None:
                batch = jax.lax.with_sharding_constraint(batch, client_spec)
                sizes = jax.lax.with_sharding_constraint(sizes, client_spec)
        kw = {} if frozen is None else {"frozen": frozen}
        if state is not None:
            with prof_mod.phase("fl.state"):
                st_rows = constrain_state(_state_round_view(state, clients),
                                          params, rows=True)
            params, metrics = round_fn(params, batch, sizes, ak, st_rows,
                                       **kw)
            with prof_mod.phase("fl.state"):
                state = constrain_state(
                    _state_scatter(state, metrics.pop("state"), clients),
                    params, rows=False)
        else:
            params, metrics = round_fn(params, batch, sizes, ak, **kw)
        with prof_mod.phase("fl.comm"):
            acc = comm_mod.comm_acc_update(acc, metrics["comm"])
        per_round = {"loss": metrics["loss"],
                     "uplink_bytes": acc["uplink_bytes"]}
        # telemetry widens the stacked per-round OUTPUTS (scan ys), never
        # the carry — disabled telemetry leaves zero extra carry leaves
        # and the per_round dict exactly as above (bit-identical blocks).
        tele = flcfg.telemetry
        if tele is not None:
            per_round["comm"] = metrics["comm"]
            if tele.taps:
                per_round["taps"] = metrics["taps"]
            if tele.full_selection:
                per_round["selection"] = metrics["selection"]
        return (params, state, acc), per_round

    # carry buffers are donated so XLA reuses them across eval blocks
    @functools.partial(jax.jit, static_argnames=("num",),
                       donate_argnums=(0,))
    def run_block(carry, shards, all_sizes, base_key, t0, num, frozen=None):
        # ``frozen`` is a real (pytree) argument, not a closure: closed-over
        # arrays would be baked into the jaxpr as constants and re-staged
        # per driver call. It is never donated — it outlives every block.
        body = functools.partial(one_round, shards=shards,
                                 all_sizes=all_sizes, base_key=base_key,
                                 frozen=frozen)
        return jax.lax.scan(body, carry, t0 + jnp.arange(num))

    return run_block


@jax.jit
def _copy_carry(params, state):
    """The scan carry's first value as one program: copies of ``params``
    and of a resumed ``state`` (``None`` passes through), and a zeroed
    comm accumulator. ``run_block`` donates its carry, so every output is
    a buffer of its own and the caller's params and state survive the
    call. Not donated; the outputs keep the inputs' shardings."""
    return (jax.tree.map(jnp.copy, params), jax.tree.map(jnp.copy, state),
            comm_mod.comm_acc_init())


def run_training_scan(params: Pytree, loss_fn, fldata, flcfg: FLConfig,
                      rounds: int,
                      eval_fn: Optional[Callable[[Pytree], float]] = None,
                      eval_every: int = 10, seed: int = 0,
                      verbose: bool = False,
                      start_round: int = 0,
                      server_state: Optional[dict] = None
                      ) -> tuple[Pytree, TrainLog]:
    """Device-resident FL training: ``jax.lax.scan`` over rounds.

    The whole schedule — client sampling (``jax.random.choice``), round-batch
    gathering from device-resident shards, local training, selection,
    aggregation, communication accounting, and strategy cross-round state
    updates (EF residuals, FedLAMA intervals, …) — runs inside one jitted
    scan per eval block, with the carry (params, strategy state, comm
    accumulator) donated between blocks. Host↔device traffic is one
    stacked (losses, uplink) pull per block instead of several scalar
    syncs per round.

    ``fldata`` may be a :class:`~repro.data.FederatedData` (uploaded once)
    or a prebuilt :class:`~repro.data.ClientShards`. Same seed ⇒ same
    trajectory as ``run_training(sampler="jax")`` (fp32 tolerance).

    Resume: the per-round keys are ``fold_in(PRNGKey(seed), t)`` with
    ``t`` the *absolute* round index, so
    ``start_round=<rounds done>, server_state=<log.final_state or a loaded
    checkpoint>`` continues a run bit-identically to one that never
    stopped (regression-tested in tests/test_state_seam.py).
    """
    with prof_mod.span("fl.scan", start_round=start_round, rounds=rounds):
        return _run_training_scan(params, loss_fn, fldata, flcfg, rounds,
                                  eval_fn, eval_every, seed, verbose,
                                  start_round, server_state)


def _run_training_scan(params, loss_fn, fldata, flcfg, rounds, eval_fn,
                       eval_every, seed, verbose, start_round, server_state):
    """:func:`run_training_scan`'s body, one host span per phase."""
    with prof_mod.span("fl.scan.prepare"):
        partition, frozen, pinfo = flcfg.partition, None, None
        if partition is not None:
            pinfo = partition_counts(partition, params)
            params, frozen = partition.split(params)
        umap = UnitMap.build(params)
        shards = (fldata if isinstance(fldata, ClientShards)
                  else ClientShards.from_federated(fldata))
        strategy = make_strategy(flcfg)
        run_block = _cached("block", loss_fn, umap, flcfg,
                            lambda: _build_block_fn(loss_fn, umap, flcfg))
        if flcfg.mesh is not None:
            # replicated over 'clients', FSDP-sharded over 'model' (2-D
            # mesh); the frozen base follows the same placement policy
            params = jax.device_put(
                params,
                to_named(fl_param_specs(params, flcfg.mesh), flcfg.mesh))
            if frozen is not None:
                frozen = jax.device_put(
                    frozen,
                    to_named(fl_param_specs(frozen, flcfg.mesh), flcfg.mesh))
            shards = shards.place(flcfg.mesh,
                                  shard_samples=flcfg.shard_samples)
        merged = ((lambda p: p) if partition is None
                  else (lambda p: partition.merge(p, frozen)))
    with prof_mod.span("fl.scan.copy_carry"):
        # a fresh state is new buffers already: only the caller's are copied
        if server_state is not None and flcfg.mesh is not None:
            server_state = _place_state(server_state, params, strategy,
                                        flcfg.mesh)
        params, state0, acc = _copy_carry(params, server_state)
        if server_state is None:
            state0 = strategy.init_state(params, flcfg.num_clients,
                                         flcfg.mesh)
        carry = (params, state0, acc)
    log = TrainLog()
    tele = flcfg.telemetry
    sink = ProgressSink.for_run(tele, verbose)
    sample_sys = tele is not None and tele.sample_system
    win = prof_mod.ProfileWindow.from_config(tele)
    ledger = None
    if tele is not None and tele.wants_ledger:
        ledger = RoundLedger(tele.ledger_path, meta=_run_meta(
            flcfg, driver="scan", umap=umap, params=params, seed=seed,
            sampler="jax", start_round=start_round, rounds=rounds,
            run_id=tele.run_id, partition_info=pinfo))
    run_kw = {} if frozen is None else {"frozen": frozen}
    all_sizes = base_key = None
    t0 = 0
    try:
        for cut in _eval_cuts(rounds, eval_every, eval_fn is not None):
            num = cut - t0
            win.block_begin(start_round + t0, start_round + cut)
            wall0 = time.perf_counter() if sample_sys else None
            with prof_mod.span("fl.scan.dispatch"):
                if base_key is None:
                    all_sizes = shards.data_sizes()
                    base_key = jax.random.PRNGKey(seed)
                carry, per_round = run_block(
                    carry, shards, all_sizes, base_key,
                    jnp.int32(start_round + t0), num, **run_kw)
            with prof_mod.span("fl.scan.pull"):
                losses = np.asarray(per_round["loss"])
                uplink = np.asarray(per_round["uplink_bytes"])
            # the np.asarray pulls above synced the block, so block wall
            # time is real compute; per-round wall is the amortised share
            block_wall = (time.perf_counter() - wall0
                          if wall0 is not None else None)
            t_last = start_round + cut - 1
            with prof_mod.span("fl.scan.log"):
                log.rounds.extend(range(start_round + t0, start_round + cut))
                log.losses.extend(float(l) for l in losses)
                log.uplink_mb.extend(float(u) / 1e6 for u in uplink)
                if ledger is not None:
                    wall_each = (block_wall / num
                                 if block_wall is not None else None)
                    mem = (prof_mod.device_memory_peak() if sample_sys
                           else None)
                    comm_stack = jax.device_get(per_round["comm"])
                    taps_stack = (jax.device_get(per_round["taps"])
                                  if "taps" in per_round else None)
                    sel_stack = (np.asarray(per_round["selection"])
                                 if "selection" in per_round else None)
                    for i in range(num):
                        ledger.round(
                            start_round + t0 + i, losses[i],
                            jax.tree.map(lambda a, i=i: a[i], comm_stack),
                            uplink[i],
                            taps=(jax.tree.map(lambda a, i=i: a[i],
                                               taps_stack)
                                  if taps_stack is not None else None),
                            selection=(sel_stack[i] if sel_stack is not None
                                       else None),
                            wall_s=wall_each, mem_peak_bytes=mem)
                if eval_fn is None and sink.enabled:
                    sink.round(t_last, float(losses[-1]))
            if eval_fn is not None:
                with prof_mod.span("fl.scan.eval"):
                    err = float(eval_fn(merged(carry[0])))
                    log.test_errors.append((t_last, err, float(uplink[-1])))
                    if ledger is not None:
                        ledger.eval(t_last, err, float(uplink[-1]))
                    sink.round(t_last, float(losses[-1]), test_error=err,
                               uplink_bytes=float(uplink[-1]))
            win.block_end(start_round + cut)
            t0 = cut
    finally:
        win.close()
        if ledger is not None:
            ledger.close()
    with prof_mod.span("fl.scan.finish"):
        params, final_state, acc = carry
        log.meter = comm_mod.CommMeter.from_accumulator(acc)
        log.final_state = final_state
        return merged(params), log
