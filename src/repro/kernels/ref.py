"""Pure-jnp oracles for the Pallas kernels.

These are the ground-truth implementations used (a) by tests to validate the
kernels and (b) as the CPU fast path (interpret-mode Pallas is slow).
"""
from __future__ import annotations

import jax.numpy as jnp


def sqdiff_rowsum(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-row sum of squared differences.

    a, b: (R, C) same shape/dtype. Returns (R,) float32.
    This is the inner reduction of the paper's Eq. 3 layer divergence.
    """
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sum(d * d, axis=1)


def sqdiff_units(a: jnp.ndarray, b: jnp.ndarray, rows: int = 1) -> jnp.ndarray:
    """Per-unit sum of squared differences for K clients of one leaf.

    a: (K,) + b.shape; b holds ``rows`` unit rows (its leading dimension
    when ``rows > 1``). Returns (K, rows) float32.
    """
    d = (a.astype(jnp.float32).reshape(a.shape[0], rows, -1)
         - b.astype(jnp.float32).reshape(1, rows, -1))
    return jnp.sum(d * d, axis=2)


def masked_accumulate(acc: jnp.ndarray, x: jnp.ndarray,
                      w: jnp.ndarray) -> jnp.ndarray:
    """acc + w[:, None] * x — the Eq. 5 per-layer weighted accumulation.

    acc: (R, C) float32 accumulator; x: (R, C) any float dtype;
    w: (R,) per-row (per layer-unit) weight. Returns (R, C) float32.
    """
    return acc + w.astype(jnp.float32)[:, None] * x.astype(jnp.float32)


def fused_uplink(levels: jnp.ndarray, scales: jnp.ndarray,
                 w: jnp.ndarray) -> jnp.ndarray:
    """Σ_k w[k,r]·scales[k,r]·levels[k,r,:] — dequant + Eq. 5 numerator.

    levels: (K, R, C) int levels; scales, w: (K, R). Returns (R, C) float32.
    """
    recon = levels.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]
    return jnp.einsum("kr,krc->rc", w.astype(jnp.float32), recon)


def fused_uplink_ef(levels: jnp.ndarray, scales: jnp.ndarray,
                    w: jnp.ndarray, gate: jnp.ndarray, v: jnp.ndarray,
                    e_old: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused dequant + Eq. 5 numerator + error-feedback residual update.

    levels: (K, R, C); scales, w, gate: (K, R); v (=Δ+e), e_old: (K, R, C).
    Returns (num (R, C), new_res (K, R, C)) float32 with
    ``new_res = gate·(v − recon) + (1−gate)·e_old``.
    """
    recon = levels.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]
    num = jnp.einsum("kr,krc->rc", w.astype(jnp.float32), recon)
    g = gate.astype(jnp.float32)[..., None]
    res = (g * (v.astype(jnp.float32) - recon)
           + (1.0 - g) * e_old.astype(jnp.float32))
    return num, res
