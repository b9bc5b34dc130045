"""Dispatching wrappers for the FedLDF kernels.

On TPU the Pallas kernels run compiled; on CPU (this container) the pure-jnp
reference is both the oracle and the fast path (interpret-mode Pallas
executes the kernel body in Python and is only used for validation).

The kernel entry points (``sqdiff_units``, ``masked_accumulate``,
``flash_attention``) default to ``interpret=None``, which resolves through
:func:`_interpret` here — so TPU callers get compiled Pallas without opting
in, and CPU callers get interpret mode.

Set ``REPRO_FORCE_PALLAS=1`` to route through the Pallas kernels in
interpret mode everywhere (used by tests/CI to exercise the kernel path).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import aggregate as _aggregate
from repro.kernels import divergence as _divergence
from repro.kernels import ref as _ref
from repro.kernels import uplink as _uplink


def _use_pallas() -> bool:
    if os.environ.get("REPRO_FORCE_PALLAS", "0") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def sqdiff_units(a: jnp.ndarray, b: jnp.ndarray, rows: int = 1) -> jnp.ndarray:
    """(K,) + leaf, leaf of ``rows`` unit rows -> (K, rows) float32
    per-unit Σ(a−b)² (Eq. 3 for one leaf and K clients)."""
    if _use_pallas():
        return _divergence.sqdiff_units(a, b, rows=rows,
                                        interpret=_interpret())
    return _ref.sqdiff_units(a, b, rows)


def sqdiff_rowsum(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(R, C), (R, C) -> (R,) float32 per-row Σ(a−b)²."""
    if _use_pallas():
        return _divergence.sqdiff_rowsum(a, b, interpret=_interpret())
    return _ref.sqdiff_rowsum(a, b)


def masked_accumulate(acc: jnp.ndarray, x: jnp.ndarray,
                      w: jnp.ndarray) -> jnp.ndarray:
    """(R, C), (R, C), (R,) -> (R, C) float32: acc + w[:,None]*x."""
    if _use_pallas():
        return _aggregate.masked_accumulate(acc, x, w, interpret=_interpret())
    return _ref.masked_accumulate(acc, x, w)


def fused_uplink(levels: jnp.ndarray, scales: jnp.ndarray,
                 w: jnp.ndarray) -> jnp.ndarray:
    """(K,R,C) int levels, (K,R), (K,R) -> (R,C) f32 Eq. 5 numerator."""
    if _use_pallas():
        return _uplink.fused_uplink(levels, scales, w,
                                    interpret=_interpret())
    return _ref.fused_uplink(levels, scales, w)


def fused_uplink_ef(levels: jnp.ndarray, scales: jnp.ndarray,
                    w: jnp.ndarray, gate: jnp.ndarray, v: jnp.ndarray,
                    e_old: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused dequant + Eq. 5 numerator + EF residual update.
    -> (num (R,C), new_res (K,R,C)) f32."""
    if _use_pallas():
        return _uplink.fused_uplink_ef(levels, scales, w, gate, v, e_old,
                                       interpret=_interpret())
    return _ref.fused_uplink_ef(levels, scales, w, gate, v, e_old)
