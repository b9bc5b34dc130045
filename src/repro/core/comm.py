"""Communication accounting (the quantity the paper optimises).

The paper's headline result is an ~80 % reduction in *uplink* bytes: with
top-n-per-layer selection only ``n/K`` of the layer payloads travel from
clients to the server, plus a negligible divergence-feedback vector
(K · U float32 scalars per round).

`round_comm` is a pure jit-safe function of the selection matrix; the
:class:`CommMeter` accumulates totals across rounds on the host.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.units import UnitMap

DIVERGENCE_SCALAR_BYTES = 4  # float32 feedback scalars


def round_comm(selection: jnp.ndarray, umap: UnitMap, *,
               divergence_feedback: bool = True,
               param_bytes_override: float | None = None,
               unit_bytes_override: jnp.ndarray | None = None,
               axis_name: str | None = None) -> dict:
    """Per-round communication in bytes.

    selection: (K, U) ∈ {0,1}. When the round runs client-sharded
    (``shard_map`` over a ``'clients'`` mesh axis), pass the *local* rows
    plus ``axis_name``: the payload sum and client count are ``psum``'d
    across the axis, so every device returns the identical global totals —
    no all-gather of the selection matrix is needed for accounting.

    ``param_bytes_override`` reprices every parameter uniformly (legacy
    quantized pricing, e.g. 1.0 for int8).  ``unit_bytes_override`` — a
    (U,) per-unit byte vector, usually ``PackedPayload.unit_wire_bytes`` —
    takes precedence and is the packed wire format's source of truth
    (header + ceil(params·bits/8) per unit, possibly traced per round).

    Returns dict with jnp scalars:
      uplink_payload   — Σ_{k,u} s[k,u]·bytes(u)        (selected layers)
      uplink_feedback  — K·U·4 if divergence feedback is on (FedLDF only)
      uplink_total
      downlink         — K·total_model_bytes (server broadcast, unchanged
                         vs FedAvg; the paper optimises uplink)
      fedavg_uplink    — K·total_model_bytes (reference)
      savings_frac     — 1 − uplink_total/fedavg_uplink
    """
    k = selection.shape[0]
    if axis_name is not None:
        k = k * jax.lax.psum(1, axis_name)   # global K across the mesh
    if unit_bytes_override is not None:
        unit_bytes = jnp.asarray(unit_bytes_override, jnp.float32)
    else:
        scale = (1.0 if param_bytes_override is None
                 else param_bytes_override / 4.0)
        unit_bytes = umap.unit_bytes_array() * scale
    payload = jnp.sum(selection * unit_bytes[None, :])
    if axis_name is not None:
        payload = jax.lax.psum(payload, axis_name)
    feedback = jnp.float32(
        k * umap.num_units * DIVERGENCE_SCALAR_BYTES if divergence_feedback
        else 0.0)
    # reference = uncompressed FedAvg (full model, fp32 wire format)
    fedavg_up = jnp.float32(k) * jnp.float32(umap.total_bytes)
    uplink = payload + feedback
    return {
        "uplink_payload": payload,
        "uplink_feedback": feedback,
        "uplink_total": uplink,
        "downlink": fedavg_up,
        "fedavg_uplink": fedavg_up,
        "savings_frac": 1.0 - uplink / fedavg_up,
    }


def agg_tier_bytes(payload_bytes: float, axis_size: int,
                   group_size: int = 0) -> dict:
    """Per-round aggregation-traffic split for the (optionally two-tier)
    cross-device reduce (:func:`repro.core.aggregation.hierarchical_psum`).

    ``payload_bytes`` is ONE device's reduce payload P (the Eq. 5
    numerator tree riding the fused psum — on a 2-D mesh the 1/M
    'model'-axis slice). ``group_size`` of 0 or ``axis_size`` means the
    flat single psum. All values are static per configuration (pure
    topology × payload arithmetic, deliberately NOT riding the psum so the
    flat path's compiled round stays byte-identical):

      agg_payload_bytes        — P
      agg_intra_bytes          — total bytes/round on intra-group links
                                 (tier-1: non-leader partials funnel to a
                                 group leader; 0 for the flat reduce)
      agg_cross_bytes          — total bytes/round crossing group
                                 boundaries (flat: all D−1 partials funnel
                                 to the root; hier: the leaders' ring
                                 moves G·(G−1) payloads)
      agg_cross_bytes_per_host — the busiest participant's share of the
                                 cross-tier traffic, send+receive (flat:
                                 the root takes 2·(D−1)·P; hier: every
                                 ring member moves 2·(G−1)·P) — the
                                 "server bandwidth is no longer the
                                 ceiling" number
      agg_groups               — number of tier-1 groups G
      agg_tiers                — 1 (flat) or 2 (hierarchical)
    """
    d = int(axis_size)
    gs = int(group_size) or d
    if d % gs:
        raise ValueError(f"agg_tier_bytes: group_size={gs} must divide "
                         f"axis_size={d}")
    p = float(payload_bytes)
    num_groups = d // gs
    if num_groups <= 1:     # flat: one rendezvous, root absorbs everything
        return {"agg_payload_bytes": p,
                "agg_intra_bytes": 0.0,
                "agg_cross_bytes": (d - 1) * p,
                "agg_cross_bytes_per_host": 2.0 * (d - 1) * p,
                "agg_groups": 1.0, "agg_tiers": 1.0}
    return {"agg_payload_bytes": p,
            "agg_intra_bytes": float(d - num_groups) * p,
            "agg_cross_bytes": float(num_groups * (num_groups - 1)) * p,
            "agg_cross_bytes_per_host": 2.0 * (num_groups - 1) * p,
            "agg_groups": float(num_groups), "agg_tiers": 2.0}


# ----------------------------------------------------------------------
# Device-side accumulator (scan engine): a dict of float32 scalars that
# lives in the lax.scan carry, so no per-round device→host pull is needed.
# ----------------------------------------------------------------------
def comm_acc_init() -> dict:
    """Zeroed jit-safe accumulator matching :class:`CommMeter`'s totals.

    One buffer per entry: the scan driver donates its carry, and a buffer
    that appears twice in a donated argument is refused."""
    return {name: jnp.float32(0.0) for name in
            ("uplink_bytes", "downlink_bytes", "fedavg_uplink_bytes",
             "rounds")}


def comm_acc_update(acc: dict, round_stats: dict) -> dict:
    """Pure functional accumulate of one round's :func:`round_comm` stats."""
    return {
        "uplink_bytes": acc["uplink_bytes"] + round_stats["uplink_total"],
        "downlink_bytes": acc["downlink_bytes"] + round_stats["downlink"],
        "fedavg_uplink_bytes": (acc["fedavg_uplink_bytes"]
                                + round_stats["fedavg_uplink"]),
        "rounds": acc["rounds"] + 1.0,
    }


@dataclasses.dataclass
class CommMeter:
    """Host-side cumulative communication meter."""

    uplink_bytes: float = 0.0
    downlink_bytes: float = 0.0
    fedavg_uplink_bytes: float = 0.0
    rounds: int = 0

    def update(self, round_stats: dict) -> None:
        self.uplink_bytes += float(round_stats["uplink_total"])
        self.downlink_bytes += float(round_stats["downlink"])
        self.fedavg_uplink_bytes += float(round_stats["fedavg_uplink"])
        self.rounds += 1

    @classmethod
    def from_accumulator(cls, acc: dict) -> "CommMeter":
        """One device→host pull at the end of a scanned training run."""
        return cls(uplink_bytes=float(acc["uplink_bytes"]),
                   downlink_bytes=float(acc["downlink_bytes"]),
                   fedavg_uplink_bytes=float(acc["fedavg_uplink_bytes"]),
                   rounds=int(acc["rounds"]))

    @property
    def savings_frac(self) -> float:
        if self.fedavg_uplink_bytes == 0:
            return 0.0
        return 1.0 - self.uplink_bytes / self.fedavg_uplink_bytes

    def summary(self) -> dict:
        return {
            "rounds": self.rounds,
            "uplink_MB": self.uplink_bytes / 1e6,
            "downlink_MB": self.downlink_bytes / 1e6,
            "fedavg_uplink_MB": self.fedavg_uplink_bytes / 1e6,
            "uplink_savings_frac": self.savings_frac,
        }
