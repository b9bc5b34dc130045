"""Attention substrate: GQA, RoPE / M-RoPE / YaRN, chunked-flash, sliding
window.

Design notes (TPU adaptation):
- ``attend`` is a single entry point. For short KV it issues one masked
  einsum (MXU-friendly); for long KV it runs an online-softmax scan over KV
  chunks (pure-JAX flash) so 32k-token prefill lowers with O(chunk) score
  memory instead of O(S²).
- GQA is computed in grouped layout (B, S, KV, G, hd) — no materialised
  head-repeat, which matters when kv_heads ≪ heads (e.g. qwen2-vl 12H/2KV).
- Sliding-window masking makes every full-attention architecture eligible
  for the ``long_500k`` decode shape via a ring-buffer KV cache.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ----------------------------------------------------------------------
# Rotary embeddings
# ----------------------------------------------------------------------
def _rope_angles(positions: jnp.ndarray, hd: int, theta: float):
    """positions (..., S) -> cos/sin (..., S, hd//2) in float32."""
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (B, S, H, hd); positions: (B, S)."""
    b, s, h, hd = x.shape
    cos, sin = _rope_angles(positions, hd, theta)       # (B, S, hd/2)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray,
                sections: tuple[int, ...], theta: float):
    """Qwen2-VL multimodal RoPE. positions: (3, B, S) = (t, h, w) ids.

    The hd/2 rotary frequency slots are partitioned into ``sections``
    (Σ sections = hd//2); each section rotates by its own position stream.
    """
    b, s, h, hd = x.shape
    assert sum(sections) == hd // 2, (sections, hd)
    cos_parts, sin_parts = [], []
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    off = 0
    for axis, sec in enumerate(sections):
        ang = positions[axis].astype(jnp.float32)[..., None] * inv[off:off + sec]
        cos_parts.append(jnp.cos(ang))
        sin_parts.append(jnp.sin(ang))
        off += sec
    cos = jnp.concatenate(cos_parts, axis=-1)[:, :, None, :]  # (B,S,1,hd/2)
    sin = jnp.concatenate(sin_parts, axis=-1)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# YaRN (arXiv:2309.00071) as DeepSeek-V2 runs it: frequency blend and
# attention scale
# ----------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float):
    """(low, high): the rotary dims between which YaRN ramps from the
    extrapolated to the interpolated frequency."""
    def at(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(at(beta_fast))
    high = math.ceil(at(beta_slow))
    return max(low, 0), min(high, dim - 1)


def rope_inv_freq(dim: int, theta: float, factor: float = 1.0,
                  original: int = 0, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jnp.ndarray:
    """(dim/2,) float32 inverse frequencies; with ``factor > 1`` the YaRN
    blend of ``theta^(-2i/dim)`` and the same divided by ``factor``."""
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if factor <= 1.0:
        return extra
    low, high = yarn_correction_range(dim, theta, original, beta_fast,
                                      beta_slow)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                  # 1: extrapolate, 0: interpolate
    return extra / factor * (1.0 - keep) + extra * keep


def apply_rope_pairs(x: jnp.ndarray, positions: jnp.ndarray,
                     inv_freq: jnp.ndarray, mscale: float = 1.0):
    """DeepSeek's rotary layout. x: (B, S, H, d); positions: (B, S).

    The d dims are de-interleaved (pairs ``(2i, 2i+1)`` to halves) and
    then rotated half against half; the output keeps the de-interleaved
    order, which queries and keys share."""
    b, s, h, d = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, h, d // 2, 2)
    x1, x2 = xf[..., 0], xf[..., 1]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq   # (B,S,d/2)
    cos = (jnp.cos(ang) * mscale)[:, :, None, :]
    sin = (jnp.sin(ang) * mscale)[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def text_mrope_positions(batch: int, seq: int) -> jnp.ndarray:
    """Text-only M-RoPE positions: t = h = w = arange (matches HF)."""
    p = jnp.broadcast_to(jnp.arange(seq)[None, :], (batch, seq))
    return jnp.stack([p, p, p], axis=0)


# ----------------------------------------------------------------------
# Masked single-block attention (short KV path)
# ----------------------------------------------------------------------
def _mask_bias(q_pos, kv_pos, *, causal: bool, window: int,
               kv_valid: Optional[jnp.ndarray] = None):
    """Additive bias (..., Sq, Skv) from position constraints (float32)."""
    ok = jnp.ones(q_pos.shape[-1:] + kv_pos.shape[-1:], dtype=bool)
    if causal:
        ok &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= kv_pos[None, :] > q_pos[:, None] - window
    bias = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
    if kv_valid is not None:  # (B, Skv) bool
        bias = bias[None] + jnp.where(kv_valid, 0.0, NEG_INF)[:, None, :]
    return bias


def _attend_block(q, k, v, bias, scale=None):
    """q: (B,Sq,KV,G,hd); k: (B,Skv,KV,hd); v: (B,Skv,KV,hd_v);
    bias: (B?,Sq,Skv) fp32."""
    if scale is None:
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bckd->bkgqc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias.ndim == 2:
        bias = bias[None]
    s = s + bias[:, None, None, :, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bckd->bqkgd", p, v.astype(jnp.float32))
    return o


# ----------------------------------------------------------------------
# Chunked-flash attention (long KV path)
# ----------------------------------------------------------------------
def _attend_flash(q, k, v, q_pos, kv_pos, *, causal, window, chunk,
                  kv_valid=None, probs_bf16=False, scale=None):
    b, sq, kvh, g, hd = q.shape
    hd_v = v.shape[-1]
    skv = k.shape[1]
    nchunks = -(-skv // chunk)
    pad = nchunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=2**30)
        if kv_valid is not None:
            kv_valid = jnp.pad(kv_valid, ((0, 0), (0, pad)))
    if kv_valid is None:
        kv_valid = jnp.ones((b, nchunks * chunk), dtype=bool)
    kv_valid &= kv_pos[None, :] < 2**30

    kc = k.reshape(b, nchunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunks, chunk, kvh, hd_v).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(nchunks, chunk)
    valc = kv_valid.reshape(b, nchunks, chunk).transpose(1, 0, 2)

    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qf = q.astype(jnp.float32)

    def body(carry, xs):
        m, l, o = carry
        kb, vb, pb, valb = xs
        s = jnp.einsum("bqkgd,bckd->bkgqc", qf, kb.astype(jnp.float32)) * scale
        ok = jnp.ones((sq, chunk), dtype=bool)
        if causal:
            ok &= pb[None, :] <= q_pos[:, None]
        if window > 0:
            ok &= pb[None, :] > q_pos[:, None] - window
        bias = jnp.where(ok, 0.0, NEG_INF)
        bias = bias[None] + jnp.where(valb, 0.0, NEG_INF)[:, None, :]
        s = s + bias[:, None, None, :, :]
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Guard fully-masked blocks: with m == s == NEG_INF, exp(s - m) would
        # be exp(0) = 1; force those probabilities (and the correction) to 0/1
        # explicitly.
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_new))
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
        l_new = l * corr + p.sum(axis=-1)
        if probs_bf16:
            # §Perf lever: the probability tensor dominates flash HBM
            # traffic under XLA lowering; bf16 halves it (fp32 accumulate).
            pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(jnp.bfloat16),
                            vb.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bkgqc,bckd->bkgqd", p, vb.astype(jnp.float32))
        o_new = o * corr[..., None] + pv
        return (m_new, l_new, o_new), None

    m0 = jnp.full((b, kvh, g, sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), dtype=jnp.float32)
    o0 = jnp.zeros((b, kvh, g, sq, hd_v), dtype=jnp.float32)
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), (kc, vc, pc, valc))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 3, 1, 2, 4)  # (B,Sq,KV,G,hd)


# ----------------------------------------------------------------------
# Public entry point
# ----------------------------------------------------------------------
def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
           q_pos: jnp.ndarray, kv_pos: jnp.ndarray,
           causal: bool = True, window: int = 0,
           kv_valid: Optional[jnp.ndarray] = None,
           chunk: int = 1024, flash_threshold: int = 2048,
           probs_bf16: bool = False,
           scale: Optional[float] = None) -> jnp.ndarray:
    """Grouped-query attention.

    q: (B, Sq, H, hd); k: (B, Skv, KV, hd); v: (B, Skv, KV, hd_v);
    H = KV·G. q_pos: (Sq,) absolute positions of queries; kv_pos: (Skv,).
    kv_valid: optional (B, Skv) bool (cache occupancy for decode).
    ``scale`` multiplies the scores (default ``hd**-0.5``).
    Returns (B, Sq, H, hd_v) in q.dtype.
    """
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    assert h % kvh == 0, (h, kvh)
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd)
    skv = k.shape[1]
    if skv <= flash_threshold:
        bias = _mask_bias(q_pos, kv_pos, causal=causal, window=window,
                          kv_valid=kv_valid)
        o = _attend_block(qg, k, v, bias, scale)
    else:
        o = _attend_flash(qg, k, v, q_pos, kv_pos, causal=causal,
                          window=window, chunk=chunk, kv_valid=kv_valid,
                          probs_bf16=probs_bf16, scale=scale)
    return o.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)
