"""Pallas TPU kernel: fused packed-uplink dequant + EF update + Eq. 5 accumulate.

The per-round hot loop used to run as separate XLA ops over fp32 buffers:
dequantize each client's levels, rebuild Θ̂, update the error-feedback
residual, then weighted-accumulate into the Eq. 5 numerator — four full
HBM passes over K × (model size).  This kernel consumes the **packed wire
format directly** (int8 level buffers from ``core/wire``) and does all of
it in one pass per (Rb, Cb) tile:

    recon      = levels[k] · scale[k]                  (dequant, in VMEM)
    num       += w[k] · recon                          (Eq. 5 numerator)
    res'[k]    = gate[k]·(v[k] − recon) + (1−gate[k])·e[k]   (EF update)

Client axis K is the **minor-most grid dimension**, so the (Rb, Cb)
numerator block is revisited across consecutive k steps and accumulated
in-place (the ``divergence.py`` reduction idiom); the residual output block
is written exactly once per (k, i, j).

Blocks default to (32, 2048): int8 operands need (32, 128)-aligned tiles
(fp32 only needs (8, 128)), and one int8 + four fp32 blocks ≈ 0.6 MiB —
comfortable in the ~16 MiB VMEM budget.

Leaves arrive with few unit rows (an unstacked leaf is one row of C
elements), so the wrappers never pad rows up to the block: each unit row
is folded into ``f = block_r / gcd(r, block_r)`` lane-dense sub-rows of
``C / f`` columns (:func:`_fold`), making ``r·f`` a block multiple, and
the per-unit scale, weight and gate are repeated per sub-row. Only C pads,
up to a multiple of ``f·128``; the arithmetic per element is unchanged.

``interpret=None`` resolves via the backend check in ``kernels/ops``
(compiled on TPU, interpret elsewhere); ``kernels/ref.py`` holds the
pure-jnp oracle that doubles as the CPU fast path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_R = 32
DEFAULT_BLOCK_C = 2048


def _uplink_kernel(lvl_ref, s_ref, w_ref, num_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)

    recon = lvl_ref[0].astype(jnp.float32) * s_ref[0]  # (Rb,Cb)·(Rb,1)
    num_ref[...] += w_ref[0] * recon


def _uplink_ef_kernel(lvl_ref, s_ref, w_ref, g_ref, v_ref, e_ref,
                      num_ref, res_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)

    recon = lvl_ref[0].astype(jnp.float32) * s_ref[0]
    num_ref[...] += w_ref[0] * recon
    g = g_ref[0]
    res_ref[0] = (g * (v_ref[0].astype(jnp.float32) - recon)
                  + (1.0 - g) * e_ref[0].astype(jnp.float32))


def _fold(levels, rowvecs, mats, block_r, block_c):
    """Fold (K, r, C) operands into (K, r·f, Cf) lane-dense rows.

    ``f = block_r / gcd(r, block_r)`` makes the row count a block
    multiple without padding rows; C zero-pads to ``f·Cf`` with ``Cf`` a
    multiple of 128, and the column block is the largest multiple of 128
    up to ``block_c`` that divides ``Cf``. (K, r) row vectors repeat per
    folded row. Zero pads are exact: zero levels add nothing to num, and
    the residual's pad columns are sliced off by :func:`_unfold`.
    Returns ``(levels, rowvecs, mats, block_c)``.
    """
    k, r, c = levels.shape
    f = block_r // math.gcd(r, block_r)
    lanes = pl.cdiv(pl.cdiv(c, f), 128)           # Cf in 128-lane units
    cf = lanes * 128
    bc = 128 * max(d for d in range(1, max(1, block_c // 128) + 1)
                   if lanes % d == 0)
    if f * cf != c:
        levels = jnp.pad(levels, ((0, 0), (0, 0), (0, f * cf - c)))
        mats = [jnp.pad(m, ((0, 0), (0, 0), (0, f * cf - c))) for m in mats]
    levels = levels.reshape(k, r * f, cf)
    mats = [m.reshape(k, r * f, cf) for m in mats]
    rowvecs = [jnp.repeat(v, f, axis=1).reshape(k, r * f, 1)
               for v in rowvecs]
    return levels, rowvecs, mats, bc


def _unfold(x, r, c):
    """(..., r·f, Cf) → (..., r, C): undo :func:`_fold`'s row split."""
    return x.reshape(x.shape[:-2] + (r, -1))[..., :c]


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_c", "interpret"))
def fused_uplink(levels: jnp.ndarray, scales: jnp.ndarray, w: jnp.ndarray, *,
                 block_r: int = DEFAULT_BLOCK_R,
                 block_c: int = DEFAULT_BLOCK_C,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Σ_k w[k,r]·scales[k,r]·levels[k,r,:] in one pass over packed levels.

    levels: (K, R, C) int levels; scales, w: (K, R) → num (R, C) f32.
    """
    if interpret is None:
        from repro.kernels import ops
        interpret = ops._interpret()
    kk, r, c = levels.shape
    assert scales.shape == (kk, r) and w.shape == (kk, r)
    levels, (s2, w2), _, block_c = _fold(levels, [scales, w], [],
                                         block_r, block_c)
    _, rf, cf = levels.shape
    grid = (rf // block_r, cf // block_c, kk)
    num = pl.pallas_call(
        _uplink_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r, block_c), lambda i, j, k: (k, i, j)),
            pl.BlockSpec((1, block_r, 1), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda i, j, k: (k, i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rf, cf), jnp.float32),
        interpret=interpret,
    )(levels, s2, w2)
    return _unfold(num, r, c)


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_c", "interpret"))
def fused_uplink_ef(levels: jnp.ndarray, scales: jnp.ndarray,
                    w: jnp.ndarray, gate: jnp.ndarray, v: jnp.ndarray,
                    e_old: jnp.ndarray, *,
                    block_r: int = DEFAULT_BLOCK_R,
                    block_c: int = DEFAULT_BLOCK_C,
                    interpret: bool | None = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused dequant + Eq. 5 accumulate + error-feedback residual update.

    levels: (K, R, C); scales, w, gate: (K, R); v (=Δ+e) and e_old: (K, R, C)
    → (num (R, C) f32, new_res (K, R, C) f32) where
    ``new_res = gate·(v − recon) + (1−gate)·e_old``.
    """
    if interpret is None:
        from repro.kernels import ops
        interpret = ops._interpret()
    kk, r, c = levels.shape
    assert scales.shape == (kk, r) and w.shape == (kk, r)
    assert gate.shape == (kk, r) and v.shape == (kk, r, c)
    assert e_old.shape == (kk, r, c)
    levels, (s2, w2, g2), (v_, e_), block_c = _fold(
        levels, [scales, w, gate], [v, e_old], block_r, block_c)
    _, rf, cf = levels.shape
    grid = (rf // block_r, cf // block_c, kk)
    num, res = pl.pallas_call(
        _uplink_ef_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_r, block_c), lambda i, j, k: (k, i, j)),
            pl.BlockSpec((1, block_r, 1), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda i, j, k: (k, i, 0)),
            pl.BlockSpec((1, block_r, block_c), lambda i, j, k: (k, i, j)),
            pl.BlockSpec((1, block_r, block_c), lambda i, j, k: (k, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, j, k: (i, j)),
            pl.BlockSpec((1, block_r, block_c), lambda i, j, k: (k, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rf, cf), jnp.float32),
            jax.ShapeDtypeStruct((kk, rf, cf), jnp.float32),
        ],
        interpret=interpret,
    )(levels, s2, w2, g2, v_, e_)
    return _unfold(num, r, c), _unfold(res, r, c)
