"""Built-in strategies: the paper's FedLDF, its baselines, and FedLP.

Each class ports one branch of the pre-refactor ``federated/server.py``
``if flcfg.algo == ...`` ladder; the engines now only see the hook surface
of :class:`~repro.federated.strategies.base.FLStrategy`. Trajectories are
bit-identical to the branch code they replace (same ops, same RNG stream —
pinned by the fixed-seed equivalence tests).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core import comm as comm_mod
from repro.core import fedadp as fedadp_mod
from repro.core import selection as sel
from repro.federated.strategies.base import FLStrategy, register_strategy


# ----------------------------------------------------------------------
# Per-strategy options (``FLConfig(algo_options=...)``). Validation lives
# here, next to the knob's owner, instead of in FLConfig.__post_init__;
# the deprecated flat FLConfig fields (fedadp_keep, fedlp_p, ...) are
# folded into these by FLConfig's normalization shim.
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FedADPOptions:
    """FedADP knobs: ``keep`` — the neuron keep fraction (equal-comm
    setting vs FedLDF's n/K)."""
    keep: float = 0.2

    def __post_init__(self):
        if not 0.0 < self.keep <= 1.0:
            raise ValueError(
                f"fedadp keep fraction must be in (0, 1], got {self.keep}")


@dataclasses.dataclass(frozen=True)
class FedLPOptions:
    """FedLP knobs: ``p`` — per-layer keep probability."""
    p: float = 0.5

    def __post_init__(self):
        if not 0.0 < self.p <= 1.0:
            raise ValueError(
                f"fedlp_p must be in (0, 1], got {self.p}")


@register_strategy("fedldf")
class FedLDF(FLStrategy):
    """The paper's algorithm: top-n clients per layer-unit by divergence
    (Eq. 4), Eq. 5 aggregation, divergence-feedback uplink accounted."""

    needs_divergence = True
    selects_topn_divergence = True   # subclasses overriding select clear it

    def select(self, divs, key, k, u, n):
        return sel.topn_divergence(divs, n)


@register_strategy("fedavg")
class FedAvg(FLStrategy):
    """Eq. 1: full participation, everything uploaded."""

    def select(self, divs, key, k, u, n):
        return sel.full_participation(k, u)


@register_strategy("random")
class RandomPerLayer(FLStrategy):
    """Random baseline: per unit, n uniform clients upload."""

    def select(self, divs, key, k, u, n):
        return sel.random_per_layer(key, k, u, n)


@register_strategy("hdfl")
class HDFL(FLStrategy):
    """HDFL [7]: n whole clients participate, uploading all units."""

    def select(self, divs, key, k, u, n):
        return sel.client_dropout(key, k, u, n)


@register_strategy("fedadp")
class FedADP(FLStrategy):
    """FedADP [6]: per-client neuron-granularity pruning with element-wise
    masked aggregation — not an Eq. 5 selection scheme, so it overrides
    both aggregation halves. Its masked numerators ``Σ_k θ·m·w`` and
    element-wise denominators ``Σ_k m·w`` are additive over clients, so
    :meth:`psum_parts`/:meth:`psum_finalize` serve ``vmap`` mode, ``scan``
    mode (the engine stacks the sequentially-trained locals) and the
    mesh's fused per-round psum alike — the denominator is a
    param-structured tree (not the Eq. 5 ``(U,)`` vector), which the
    engine 'model'-axis shards alongside the numerators on 2-D meshes."""

    options_cls = FedADPOptions
    eq5_weighted = False        # element-wise masks, not unit weights
    supports_quantize = False   # aggregates pruned neurons, not deltas

    def select(self, divs, key, k, u, n):
        # selection is accounting-only for FedADP: pruning happens at
        # neuron granularity inside psum_parts()
        return sel.full_participation(k, u)

    # ---- aggregation halves: per-leaf additive masked partials ----
    def psum_parts(self, uploads, umap, sel_loc, data_sizes,
                   global_params=None):
        assert global_params is not None, \
            "fedadp psum_parts needs the global model for its masks"
        return fedadp_mod.fedadp_psum_parts(uploads, global_params,
                                            data_sizes,
                                            self.opts.keep)

    def psum_finalize(self, parts, denom, umap, params_shard, fallback):
        return fedadp_mod.fedadp_psum_finalize(parts, denom, fallback)

    def comm_profile(self, selection, umap, param_bytes_override=None,
                     unit_bytes_override=None):
        comm = comm_mod.round_comm(selection, umap,
                                   divergence_feedback=False)
        # overwrite with FedADP's own accounting. The payload must be
        # recomputed alongside the total, or the metrics dict goes
        # internally inconsistent (payload + feedback != total).
        comm["uplink_total"] = jnp.float32(0.0) + comm["fedavg_uplink"] \
            * self.opts.keep
        comm["uplink_payload"] = comm["uplink_total"] \
            - comm["uplink_feedback"]
        comm["savings_frac"] = 1.0 - self.opts.keep
        return comm


@register_strategy("fedlp")
class FedLP(FLStrategy):
    """FedLP (Zhu et al., arXiv:2303.06360): layer-wise probabilistic
    participation. Each client independently keeps (uploads) each
    layer-unit with probability ``FedLPOptions.p``; the server runs the
    usual Eq. 5 weighted mean over whatever arrived, falling back to the
    previous global value for units nobody kept. Expected uplink is
    ``p × FedAvg`` with zero feedback traffic — the comm profile adds only
    the per-client keep-mask header (U bits/client) the server needs to
    know which layers are present.

    Eq. 5 aggregation + replicated-key selection ⇒ full engine support:
    vmap, scan (streaming), mesh-sharded, and quantized uploads all work.
    """

    options_cls = FedLPOptions

    def select(self, divs, key, k, u, n):
        return sel.bernoulli_per_layer(key, k, u, self.opts.p)

    def comm_profile(self, selection, umap, param_bytes_override=None,
                     unit_bytes_override=None):
        stats = comm_mod.round_comm(
            selection, umap, divergence_feedback=False,
            param_bytes_override=param_bytes_override,
            unit_bytes_override=unit_bytes_override)
        # keep-mask header: U bits per participating client, byte-padded
        mask_bytes = jnp.float32(selection.shape[0]
                                 * ((umap.num_units + 7) // 8))
        stats["uplink_feedback"] = stats["uplink_feedback"] + mask_bytes
        stats["uplink_total"] = stats["uplink_total"] + mask_bytes
        stats["savings_frac"] = (1.0 - stats["uplink_total"]
                                 / stats["fedavg_uplink"])
        return stats
