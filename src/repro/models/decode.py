"""Serving path: prefill + single-token decode with ring-buffer KV cache.

- ``init_cache``  — allocate the per-family cache pytree (attention KV ring
  buffers, SSM recurrent state, enc-dec cross-K/V).
- ``prefill``     — full forward that also materialises the cache.
- ``decode_step`` — ONE new token against the cache (the program lowered for
  the ``decode_32k`` / ``long_500k`` input shapes).

MLA models cache each head's expanded key (nope + rope) and value; the
leading dense layers of an MoE model (``params["dense"]``) hold the first
rows of every cache leaf and run before the MoE blocks.

Ring buffer: the KV buffer has ``W`` slots; token at absolute position ``p``
writes slot ``p mod W``. With ``W = sliding_window`` this *is* sliding-window
attention (what makes dense architectures eligible for ``long_500k``); with
``W = seq_len`` it is an ordinary full cache. Keys are stored post-RoPE, so
decode attention needs only an occupancy mask, not stored positions.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig, dtype_of
from repro.models.layers import mlp_fwd, rms_norm
from repro.models.transformer import (_embed_tokens, _enc_kv_all, _encode,
                                      _mla_qkv, _qkv, block_kind,
                                      mla_softmax_scale)

Pytree = Any


def cache_window(cfg: ModelConfig, seq_len: int) -> int:
    return min(cfg.sliding_window, seq_len) if cfg.sliding_window else seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               enc_len: int = 0) -> Pytree:
    """Empty cache for ``seq_len`` context. Leaves stacked over layers."""
    dt = dtype_of(cfg.compute_dtype)
    l, hd, kvh = cfg.num_layers, cfg.hd, cfg.num_kv_heads
    hd_v = hd
    if cfg.is_mla:
        kvh, hd, hd_v = cfg.num_heads, cfg.qk_head_dim, cfg.v_head_dim
    w = cache_window(cfg, seq_len)
    kind = block_kind(cfg)
    cache: Pytree = {"pos": jnp.zeros((), jnp.int32)}
    if kind in ("dense", "moe", "hybrid", "dec"):
        cache["k"] = jnp.zeros((l, batch, w, kvh, hd), dt)
        cache["v"] = jnp.zeros((l, batch, w, kvh, hd_v), dt)
    if kind in ("ssm", "hybrid"):
        sc = ssm_mod.init_ssm_cache(cfg, batch, dt)
        cache["ssm_conv"] = jnp.broadcast_to(
            sc["conv"][None], (l,) + sc["conv"].shape).astype(dt)
        cache["ssm_state"] = jnp.broadcast_to(
            sc["state"][None], (l,) + sc["state"].shape)
    if cfg.is_encdec:
        cache["cross_k"] = jnp.zeros((l, batch, enc_len, kvh, hd), dt)
        cache["cross_v"] = jnp.zeros((l, batch, enc_len, kvh, hd), dt)
    return cache


def _attn_qkv(p, cfg: ModelConfig, h, positions):
    return (_mla_qkv if cfg.is_mla else _qkv)(p, cfg, h, positions)


def _scan_layers(params, cfg: ModelConfig, body, x, xs):
    """``body(kind)`` scanned over ``(layer params, xs)``: the leading
    dense layers, if any, and then the blocks. ``xs`` leaves hold every
    layer, dense ones first; so do the returned ys."""
    kind = block_kind(cfg)
    if "dense" not in params:
        return jax.lax.scan(body(kind), x, (params["blocks"], xs))
    n = cfg.first_dense_layers
    x, ys_d = jax.lax.scan(body("dense"), x, (
        params["dense"], jax.tree.map(lambda a: a[:n], xs)))
    x, ys = jax.lax.scan(body(kind), x, (
        params["blocks"], jax.tree.map(lambda a: a[n:], xs)))
    return x, jax.tree.map(lambda a, b: jnp.concatenate([a, b]), ys_d, ys)


# ----------------------------------------------------------------------
# Prefill
# ----------------------------------------------------------------------
def prefill(params: Pytree, cfg: ModelConfig, tokens: jnp.ndarray,
            enc_inputs: Optional[jnp.ndarray] = None,
            embeddings: Optional[jnp.ndarray] = None,
            max_len: Optional[int] = None):
    """Forward over the prompt; returns (last-position logits, cache).

    ``max_len`` sets cache capacity (≥ prompt length); when omitted the
    cache is exactly prompt-sized and subsequent decode steps roll the ring
    buffer (oldest entry evicted).
    """
    b, s = tokens.shape
    w = cache_window(cfg, max_len or s)
    scale = mla_softmax_scale(cfg) if cfg.is_mla else None
    x = _embed_tokens(params, cfg, tokens, embeddings)
    if cfg.mrope:
        positions = attn.text_mrope_positions(b, s)
        pos1d = positions[0, 0]
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        pos1d = positions[0]

    enc_kv = None
    if cfg.is_encdec:
        enc_out = _encode(params, cfg, enc_inputs)
        enc_kv = _enc_kv_all(params, cfg, enc_out)

    def body(kind):
        return lambda x, xs: prefill_layer(x, xs, kind)

    def prefill_layer(x, xs, kind):
        blk, ekv = xs
        ys = {}
        h = rms_norm(x, blk["ln1"])
        if kind in ("dense", "moe", "hybrid", "dec"):
            q, k, v = _attn_qkv(blk["attn"], cfg, h, positions)
            o = attn.attend(q, k, v, q_pos=pos1d, kv_pos=pos1d, causal=True,
                            window=cfg.sliding_window, scale=scale)
            o = jnp.einsum("bsf,fd->bsd", o.reshape(b, s, -1),
                           blk["attn"]["wo"])
            # keep the last min(s, w) (post-RoPE) keys/values, ring-aligned
            # so that absolute position p sits in slot p mod w.
            if w >= s:
                kw = jnp.pad(k, ((0, 0), (0, w - s), (0, 0), (0, 0)))
                vw = jnp.pad(v, ((0, 0), (0, w - s), (0, 0), (0, 0)))
            else:
                kw = jax.lax.dynamic_slice_in_dim(k, s - w, w, axis=1)
                vw = jax.lax.dynamic_slice_in_dim(v, s - w, w, axis=1)
                shift = (s - w) % w
                kw = jnp.roll(kw, shift=shift, axis=1)
                vw = jnp.roll(vw, shift=shift, axis=1)
            ys["k"], ys["v"] = kw, vw
            if kind == "hybrid":
                o2, sc = ssm_mod.ssd_fwd(blk["ssm"], h, cfg, return_cache=True)
                ys["ssm_conv"], ys["ssm_state"] = sc["conv"], sc["state"]
                o = 0.5 * (o + o2)
            x = x + o
        else:  # pure ssm
            o, sc = ssm_mod.ssd_fwd(blk["ssm"], h, cfg, return_cache=True)
            ys["ssm_conv"], ys["ssm_state"] = sc["conv"], sc["state"]
            x = x + o
            h2 = rms_norm(x, blk["ln2"]) if "ln2" in blk else None
            if h2 is not None:
                x = x + mlp_fwd(blk["mlp"], h2)
            return x, ys
        if kind == "dec" and ekv is not None:
            from repro.models.transformer import _cross_attn
            x = x + _cross_attn(blk["cross"], cfg,
                                rms_norm(x, blk["ln_cross"]), ekv)
            ys["cross_k"], ys["cross_v"] = ekv
        h2 = rms_norm(x, blk["ln2"])
        if kind == "moe":
            out, _, _ = moe_mod.moe_fwd(blk["moe"], h2, cfg)
            x = x + out
        else:
            x = x + mlp_fwd(blk["mlp"], h2)
        return x, ys

    x, ys = _scan_layers(params, cfg, body, x,
                         tuple(enc_kv) if cfg.is_encdec else None)

    x = rms_norm(x, params["final"]["norm"])
    head = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["final"]["head"])
    logits = jnp.einsum("bd,dv->bv", x[:, -1, :], head.astype(x.dtype))

    cache = init_cache(cfg, b, max_len or s, enc_len=enc_inputs.shape[1]
                       if enc_inputs is not None else 0)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    for key in ("k", "v", "ssm_conv", "ssm_state", "cross_k", "cross_v"):
        if key in ys:
            cache[key] = ys[key].astype(cache[key].dtype)
    return logits, cache


# ----------------------------------------------------------------------
# Decode step
# ----------------------------------------------------------------------
def decode_step(params: Pytree, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: Pytree):
    """One token. tokens: (B, 1) int32. Returns (logits (B, V), cache')."""
    b = tokens.shape[0]
    pos = cache["pos"]
    scale = mla_softmax_scale(cfg) if cfg.is_mla else None
    x = _embed_tokens(params, cfg, tokens)
    if cfg.mrope:
        positions = jnp.broadcast_to(pos, (3, b, 1))
    else:
        positions = jnp.broadcast_to(pos, (b, 1))

    has_kv = "k" in cache
    if has_kv:
        w = cache["k"].shape[2]
        slot = pos % w
        n_valid = jnp.minimum(pos + 1, w)
        kv_valid = jnp.broadcast_to(jnp.arange(w)[None, :] < n_valid, (b, w))

    def body(kind):
        return lambda x, xs: decode_layer(x, xs, kind)

    def decode_layer(x, xs, kind):
        blk, xs = xs
        ys = {}
        h = rms_norm(x, blk["ln1"])
        if kind in ("dense", "moe", "hybrid", "dec"):
            q, k, v = _attn_qkv(blk["attn"], cfg, h, positions)
            ck = jax.lax.dynamic_update_slice_in_dim(
                xs["k"], k.astype(xs["k"].dtype), slot, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                xs["v"], v.astype(xs["v"].dtype), slot, axis=1)
            ys["k"], ys["v"] = ck, cv
            o = attn.attend(q, ck, cv,
                            q_pos=jnp.full((1,), pos, jnp.int32),
                            kv_pos=jnp.zeros((w,), jnp.int32),
                            causal=False, window=0, kv_valid=kv_valid,
                            scale=scale)
            o = jnp.einsum("bsf,fd->bsd", o.reshape(b, 1, -1),
                           blk["attn"]["wo"])
            if kind == "hybrid":
                o2, sc = ssm_mod.ssd_step(
                    blk["ssm"], h,
                    {"conv": xs["ssm_conv"], "state": xs["ssm_state"]}, cfg)
                ys["ssm_conv"], ys["ssm_state"] = sc["conv"], sc["state"]
                o = 0.5 * (o + o2)
            x = x + o
        else:  # pure ssm
            o, sc = ssm_mod.ssd_step(
                blk["ssm"], h,
                {"conv": xs["ssm_conv"], "state": xs["ssm_state"]}, cfg)
            ys["ssm_conv"], ys["ssm_state"] = sc["conv"], sc["state"]
            x = x + o
            return x, ys
        if kind == "dec":
            from repro.models.transformer import _cross_attn
            x = x + _cross_attn(blk["cross"], cfg,
                                rms_norm(x, blk["ln_cross"]),
                                (xs["cross_k"], xs["cross_v"]))
            ys["cross_k"], ys["cross_v"] = xs["cross_k"], xs["cross_v"]
        h2 = rms_norm(x, blk["ln2"])
        if kind == "moe":
            out, _, _ = moe_mod.moe_fwd(blk["moe"], h2, cfg)
            x = x + out
        else:
            x = x + mlp_fwd(blk["mlp"], h2)
        return x, ys

    xs = {key: cache[key] for key in ("k", "v", "ssm_conv", "ssm_state",
                                      "cross_k", "cross_v") if key in cache}
    x, ys = _scan_layers(params, cfg, body, x, xs)

    x = rms_norm(x, params["final"]["norm"])
    head = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["final"]["head"])
    logits = jnp.einsum("bd,dv->bv", x[:, 0, :], head.astype(x.dtype))

    new_cache = dict(cache)
    new_cache["pos"] = pos + 1
    for key, val in ys.items():
        new_cache[key] = val
    return logits, new_cache
