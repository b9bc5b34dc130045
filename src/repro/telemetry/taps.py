"""In-jit tap collection: the engine side of the telemetry taps.

The strategy side is :meth:`FLStrategy.telemetry_taps` — a jit-safe hook
whose default derives per-layer selection counts, divergence statistics
(the Eq. 4 inputs), and summaries of the *global* state entries from the
hooks every strategy already implements. The helpers here add what only
the engines can see:

- :func:`client_sqsums` — squared-norm partials of the round's *client*
  state rows (e.g. the participants' error-feedback residuals). The
  stacked-clients round computes these partials over its rows (device-local
  on a mesh) and rides them on the round's single fused ``psum`` (no
  extra rendezvous, no host sync); the scan round sums the same quantity
  over all K rows in :func:`collect`, so the tapped value is
  driver-independent.
- :func:`probe_taps` — the loss's own counters (``loss_fn.probe``),
  summed over the round's clients by the engine; an MoE model's
  ``expert_tokens`` (tokens routed to each expert, per MoE layer) gains
  ``expert_load_ratio``, the busiest expert's tokens over the mean, per
  layer.
- :func:`collect` — assemble the final per-round tap dict: the strategy
  hook on replicated inputs (selection/divergence/global state) plus
  ``state_<name>_norm`` entries from the client-row partials.

Client-entry norms are sampled *after* the upload transform updated them
(the EF residual treatment) and before :meth:`FLStrategy.update_state`;
global-entry summaries reflect the post-``update_state`` value — i.e.
taps describe the state the next round will start from.

Everything here is traced under ``jax.jit`` — static structure, no host
callbacks.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def client_sqsums(client: dict) -> dict:
    """Per-entry sum of squares over every leaf of the round's client-state
    rows: ``{name: f32 scalar}``. Additive over the client axis, so the
    stacked-clients round can psum per-device partials on a mesh."""
    out = {}
    for name, rows in client.items():
        parts = [jnp.sum(jnp.square(l.astype(jnp.float32)))
                 for l in jax.tree.leaves(rows)]
        out[name] = sum(parts, jnp.float32(0.0))
    return out


def probe_taps(sums: dict) -> dict:
    """The round's summed probe counters, plus derived load ratios."""
    out = dict(sums)
    if "expert_tokens" in sums:
        t = sums["expert_tokens"]
        out["expert_load_ratio"] = t.max(axis=-1) / jnp.maximum(
            t.mean(axis=-1), 1e-9)
    return out


def collect(strategy, state: Optional[dict], selection, divs, umap,
            client_sq: Optional[dict] = None,
            extra: Optional[dict] = None) -> dict:
    """Build one round's tap dict (see module docstring).

    ``state`` is the round-local post-``update_state`` view (client rows
    included off-mesh). ``client_sq`` carries pre-reduced client partials
    when the caller already reduced them (the stacked round); ``None`` means
    compute them here from ``state['client']``. ``extra`` merges
    engine-side taps that no hook can see — e.g. the packed uplink's
    per-unit wire bytes and bit-width allocation (replicated values; keys
    must be static across rounds like every tap).
    """
    gview = None
    if state and state.get("global"):
        gview = {"global": state["global"]}
    taps = dict(strategy.telemetry_taps(gview, selection, divs, umap))
    if client_sq is None and state and state.get("client"):
        client_sq = client_sqsums(state["client"])
    if client_sq:
        for name, sq in client_sq.items():
            taps[f"state_{name}_norm"] = jnp.sqrt(sq)
    if extra:
        taps.update(extra)
    return taps
