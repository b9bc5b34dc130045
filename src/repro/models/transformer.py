"""Composable transformer LM covering the assigned families.

Parameters are organised for FedLDF layer-units (see core/units.py):

    params = {
      "embed":      {"tok": (V, D)}                       # unit "embed"
      "dense":      {...stacked (first_dense_layers, ...)}  # unit "dense"
      "blocks":     {...leaves stacked (L, ...)}          # units blocks/0..L-1
      "enc_blocks": {...}            (enc-dec only)       # units enc_blocks/*
      "enc_embed":  {...}            (audio/vlm frontends)
      "final":      {"norm": (D,) [, "head": (D, V)]}     # unit "final"
    }

An MoE model with ``first_dense_layers`` keeps those leading dense layers
(DeepSeekMoE's layer 0) under ``dense``, run before the MoE ``blocks``;
the FedLDF engine counts the ``dense`` subtree as one layer unit.

With ``kv_lora_rank > 0`` attention is DeepSeek-V2's multi-head latent
attention (:func:`_mla_qkv`), trained with the latent expanded.

Blocks execute under ``lax.scan`` (stacked leaves), which keeps HLO size
O(1) in depth — essential for compiling 48-62 layer configs on the dry-run
host — and makes per-depth divergence a batched row-reduction (the Pallas
kernel's layout).

Decode uses a ring-buffer KV cache; ``sliding_window`` caps the buffer so
full-attention architectures stay sub-quadratic-memory on ``long_500k``.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig, dtype_of
from repro.models.layers import (init_dense, init_embed, init_mlp,
                                 lora_dense, mlp_fwd, rms_norm)

Pytree = Any


# ======================================================================
# Init
# ======================================================================
def _init_mla(key, cfg: ModelConfig):
    dt = dtype_of(cfg.param_dtype)
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], d, h * cfg.qk_head_dim, dt),
        "wkv_a": init_dense(ks[1], d, r + cfg.qk_rope_head_dim, dt),
        "kv_norm": jnp.ones((r,), dt),
        "wkv_b": init_dense(ks[2], r,
                            h * (cfg.qk_nope_head_dim + cfg.v_head_dim), dt),
        "wo": init_dense(ks[3], h * cfg.v_head_dim, d, dt),
    }


def _init_attn(key, cfg: ModelConfig, cross: bool = False):
    if cfg.is_mla and not cross:
        return _init_mla(key, cfg)
    dt = dtype_of(cfg.param_dtype)
    d, hd = cfg.d_model, cfg.hd
    qdim, kvdim = cfg.num_heads * hd, cfg.num_kv_heads * hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], d, qdim, dt),
        "wk": init_dense(ks[1], d, kvdim, dt),
        "wv": init_dense(ks[2], d, kvdim, dt),
        "wo": init_dense(ks[3], qdim, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qdim,), dt)
        p["bk"] = jnp.zeros((kvdim,), dt)
        p["bv"] = jnp.zeros((kvdim,), dt)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _init_block(key, cfg: ModelConfig, kind: str, d_ff: int | None = None):
    """kind: dense | moe | ssm | hybrid | enc | dec"""
    ks = jax.random.split(key, 6)
    dt = dtype_of(cfg.param_dtype)
    p: dict = {"ln1": jnp.ones((cfg.d_model,), dt)}
    if kind == "ssm":
        p["ssm"] = ssm_mod.init_ssm(ks[0], cfg)
        return p
    if kind in ("dense", "moe", "enc", "dec", "hybrid"):
        p["attn"] = _init_attn(ks[0], cfg)
    if kind == "hybrid":
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
    if kind == "dec":
        p["ln_cross"] = jnp.ones((cfg.d_model,), dt)
        p["cross"] = _init_attn(ks[2], cfg, cross=True)
    p["ln2"] = jnp.ones((cfg.d_model,), dt)
    if kind == "moe":
        p["moe"] = moe_mod.init_moe(ks[3], cfg)
    else:
        p["mlp"] = init_mlp(ks[4], cfg, d_ff=d_ff)
    return p


def _stack_blocks(key, cfg: ModelConfig, kind: str, depth: int,
                  d_ff: int | None = None):
    keys = jax.random.split(key, depth)
    return jax.vmap(lambda k: _init_block(k, cfg, kind, d_ff))(keys)


def block_kind(cfg: ModelConfig) -> str:
    return {"dense": "dense", "vlm": "dense", "moe": "moe",
            "ssm": "ssm", "hybrid": "hybrid", "audio": "dec"}[cfg.family]


def init_params(key, cfg: ModelConfig) -> Pytree:
    ks = jax.random.split(key, 5)
    dt = dtype_of(cfg.param_dtype)
    n_dense = cfg.first_dense_layers
    params: Pytree = {
        "embed": {"tok": init_embed(ks[0], cfg.vocab_size, cfg.d_model, dt)},
        "blocks": _stack_blocks(ks[1], cfg, block_kind(cfg),
                                cfg.num_layers - n_dense),
        "final": {"norm": jnp.ones((cfg.d_model,), dt)},
    }
    if n_dense:
        params["dense"] = _stack_blocks(jax.random.fold_in(key, 5), cfg,
                                        "dense", n_dense,
                                        d_ff=cfg.dense_d_ff or None)
    if not cfg.tie_embeddings:
        params["final"]["head"] = init_dense(ks[2], cfg.d_model,
                                             cfg.vocab_size, dt)
    if cfg.is_encdec:
        params["enc_blocks"] = _stack_blocks(ks[3], cfg, "enc",
                                             cfg.encoder_layers)
        params["enc_embed"] = {
            "proj": init_dense(ks[4], cfg.frontend_dim or cfg.d_model,
                               cfg.d_model, dt),
            "norm": jnp.ones((cfg.d_model,), dt),
        }
    elif cfg.family == "vlm" and cfg.frontend_dim:
        params["enc_embed"] = {
            "proj": init_dense(ks[4], cfg.frontend_dim, cfg.d_model, dt),
            "norm": jnp.ones((cfg.d_model,), dt),
        }
    return params


# ======================================================================
# Attention wrapper (projection + rope + attend)
# ======================================================================
def _qkv(p, cfg: ModelConfig, x, positions):
    b, s, d = x.shape
    hd = cfg.hd
    lora = p.get("lora")
    q = lora_dense(x, p["wq"], lora, "wq")
    k = lora_dense(x, p["wk"], lora, "wk")
    v = lora_dense(x, p["wv"], lora, "wv")
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if positions is not None:
        if cfg.mrope:
            q = attn.apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
            k = attn.apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = attn.apply_rope(q, positions, cfg.rope_theta)
            k = attn.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``qk_head_dim**-0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim)**2`` when the rotary embedding is YaRN-scaled."""
    scale = cfg.qk_head_dim ** -0.5
    if cfg.rope_factor > 1.0 and cfg.yarn_mscale_all_dim:
        m = attn.yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim)
        scale = scale * m * m
    return scale


def _mla_rope(cfg: ModelConfig, x, positions):
    inv = attn.rope_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                             cfg.rope_factor, cfg.rope_original_max_positions,
                             cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    mscale = (attn.yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
              / attn.yarn_mscale(cfg.rope_factor,
                                 cfg.yarn_mscale_all_dim or 1.0))
    return attn.apply_rope_pairs(x, positions, inv, mscale)


def _mla_qkv(p, cfg: ModelConfig, x, positions):
    """Multi-head latent attention's q (B,S,H,nope+rope), k (same) and
    v (B,S,H,v_head_dim), the latent expanded (no absorbed weights).

    ``[c, k_pe] = x Wkv_a``; ``c`` is RMS-normed and expanded by
    ``Wkv_b`` into per-head ``[k_nope, v]``; the single rotary key
    ``k_pe`` is shared by every head."""
    b, s, _ = x.shape
    h, nope, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    lora = p.get("lora")
    q = lora_dense(x, p["wq"], lora, "wq").reshape(b, s, h, cfg.qk_head_dim)
    kv_a = lora_dense(x, p["wkv_a"], lora, "wkv_a")
    c = rms_norm(kv_a[..., :r], p["kv_norm"])
    kv = lora_dense(c, p["wkv_b"], lora, "wkv_b").reshape(
        b, s, h, nope + cfg.v_head_dim)
    k_pe = _mla_rope(cfg, kv_a[..., None, r:], positions)
    q = jnp.concatenate([q[..., :nope], _mla_rope(cfg, q[..., nope:],
                                                  positions)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe, (b, s, h, cfg.qk_rope_head_dim))], axis=-1)
    return q, k, kv[..., nope:]


def _self_attn(p, cfg: ModelConfig, x, positions, *, causal=True):
    s = x.shape[1]
    if cfg.is_mla:
        with jax.named_scope("attn.mla"):
            q, k, v = _mla_qkv(p, cfg, x, positions)
            o = attn.attend(q, k, v, q_pos=positions[0], kv_pos=positions[0],
                            causal=causal, window=cfg.sliding_window,
                            chunk=cfg.attn_chunk,
                            probs_bf16=cfg.attn_probs_bf16,
                            scale=mla_softmax_scale(cfg))
            return lora_dense(o.reshape(x.shape[0], s, -1), p["wo"],
                              p.get("lora"), "wo")
    q, k, v = _qkv(p, cfg, x, positions)
    pos1d = positions[0, 0] if cfg.mrope else positions[0]
    o = attn.attend(q, k, v, q_pos=pos1d, kv_pos=pos1d, causal=causal,
                    window=cfg.sliding_window, chunk=cfg.attn_chunk,
                    probs_bf16=cfg.attn_probs_bf16)
    return lora_dense(o.reshape(x.shape[0], s, -1), p["wo"],
                      p.get("lora"), "wo")


def _cross_attn(p, cfg: ModelConfig, x, enc_kv):
    b, s, _ = x.shape
    hd = cfg.hd
    q = jnp.einsum("bsd,df->bsf", x, p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k, v = enc_kv  # precomputed (B, Senc, KV, hd)
    o = attn.attend(q, k, v,
                    q_pos=jnp.zeros((s,), jnp.int32),
                    kv_pos=jnp.zeros((k.shape[1],), jnp.int32),
                    causal=False, window=0)
    return jnp.einsum("bsf,fd->bsd", o.reshape(b, s, -1), p["wo"])


# ======================================================================
# Block forward (full sequence)
# ======================================================================
def _block_fwd(blk, cfg: ModelConfig, x, positions, kind: str,
               enc_kv=None):
    """(x, aux, tokens routed to each expert (E,) or None)."""
    aux = jnp.float32(0.0)
    h = rms_norm(x, blk["ln1"])
    if kind == "ssm":
        return x + ssm_mod.ssd_fwd(blk["ssm"], h, cfg), aux, None
    if kind == "hybrid":
        mix = 0.5 * (_self_attn(blk["attn"], cfg, h, positions)
                     + ssm_mod.ssd_fwd(blk["ssm"], h, cfg))
        x = x + mix
    else:
        causal = kind != "enc"
        x = x + _self_attn(blk["attn"], cfg, h, positions, causal=causal)
    if kind == "dec" and enc_kv is not None:
        x = x + _cross_attn(blk["cross"], cfg,
                            rms_norm(x, blk["ln_cross"]), enc_kv)
    h2 = rms_norm(x, blk["ln2"])
    load = None
    if kind == "moe":
        out, aux, load = moe_mod.moe_fwd(blk["moe"], h2, cfg)
        x = x + out
    else:
        x = x + mlp_fwd(blk["mlp"], h2)
    return x, aux, load


def _run_stack(blocks, cfg: ModelConfig, x, positions, kind: str,
               enc_kv=None):
    """(x, aux, per-layer expert loads (L, E) or None).

    enc_kv: optional per-layer stacked (L, B, Se, KV, hd) K/V pair —
    scanned alongside the blocks so each decoder layer sees its own slice."""

    def body(carry, xs):
        x, aux = carry
        if enc_kv is not None:
            blk, ek, ev = xs
            x, a, load = _block_fwd(blk, cfg, x, positions, kind, (ek, ev))
        else:
            x, a, load = _block_fwd(blk := xs, cfg, x, positions, kind, None)
        return (x, aux + a), load

    if cfg.remat_blocks:
        # activation checkpointing: store only block boundaries, recompute
        # internals in the backward pass (the §Perf memory-term lever).
        body = jax.checkpoint(body)

    xs = (blocks, enc_kv[0], enc_kv[1]) if enc_kv is not None else blocks
    (x, aux), loads = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
    return x, aux, loads


# ======================================================================
# Full forward passes
# ======================================================================
def _positions_for(cfg: ModelConfig, batch: int, seq: int, offset=0):
    if cfg.mrope:
        return attn.text_mrope_positions(batch, seq) + offset
    return jnp.broadcast_to(jnp.arange(seq)[None, :], (batch, seq)) + offset


def _encode(params, cfg: ModelConfig, enc_inputs):
    """Audio/VLM frontend stub output -> encoder stack -> (B, Senc, D)."""
    x = jnp.einsum("bsf,fd->bsd", enc_inputs, params["enc_embed"]["proj"])
    x = rms_norm(x, params["enc_embed"]["norm"])
    pos = _positions_for(cfg, x.shape[0], x.shape[1])
    x, _, _ = _run_stack(params["enc_blocks"], cfg, x, pos, "enc")
    return x


def _embed_tokens(params, cfg: ModelConfig, tokens, embeddings=None):
    x = params["embed"]["tok"][tokens]
    if embeddings is not None and cfg.family == "vlm":
        # VLM early-fusion stub: add projected patch embeddings to the first
        # S_vis token slots (precomputed by the (stubbed) vision tower).
        proj = jnp.einsum("bsf,fd->bsd", embeddings,
                          params["enc_embed"]["proj"])
        proj = rms_norm(proj, params["enc_embed"]["norm"])
        svis = proj.shape[1]
        x = x.at[:, :svis, :].add(proj.astype(x.dtype))
    return x.astype(dtype_of(cfg.compute_dtype))


def _decoder(params: Pytree, cfg: ModelConfig, tokens, enc_inputs=None,
             embeddings=None):
    """Embedding and every layer: (hidden (B, S, D), aux, loads)."""
    b, s = tokens.shape
    x = _embed_tokens(params, cfg, tokens, embeddings)
    pos = _positions_for(cfg, b, s)
    enc_kv = None
    if cfg.is_encdec:
        assert enc_inputs is not None, "enc-dec model needs enc_inputs"
        enc_out = _encode(params, cfg, enc_inputs)
        enc_kv = _enc_kv_all(params, cfg, enc_out)
    aux0 = None
    if "dense" in params:
        x, aux0, _ = _run_stack(params["dense"], cfg, x, pos, "dense")
    x, aux, loads = _run_stack(params["blocks"], cfg, x, pos,
                               block_kind(cfg), enc_kv)
    if aux0 is not None:
        aux = aux + aux0
    return x, aux, loads


def forward(params: Pytree, cfg: ModelConfig, tokens: jnp.ndarray,
            enc_inputs: Optional[jnp.ndarray] = None,
            embeddings: Optional[jnp.ndarray] = None):
    """Training forward. tokens: (B, S) int32 -> logits (B, S, V), aux."""
    x, aux, _ = _decoder(params, cfg, tokens, enc_inputs, embeddings)
    x = rms_norm(x, params["final"]["norm"])
    head = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["final"]["head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    return logits, aux


def _enc_kv_all(params, cfg: ModelConfig, enc_out):
    """Precompute per-layer cross-attention K/V from encoder output.

    Returns stacked (L, B, Senc, KV, hd) pair consumed inside the decoder
    scan (the xs argument), so cross-K/V is computed once, not per step.
    """
    b, se, _ = enc_out.shape
    hd = cfg.hd

    def per_layer(blk):
        k = jnp.einsum("bsd,df->bsf", enc_out, blk["cross"]["wk"])
        v = jnp.einsum("bsd,df->bsf", enc_out, blk["cross"]["wv"])
        return (k.reshape(b, se, cfg.num_kv_heads, hd),
                v.reshape(b, se, cfg.num_kv_heads, hd))

    return jax.vmap(per_layer)(params["blocks"])


# ======================================================================
# Loss
# ======================================================================
def lm_loss(params: Pytree, cfg: ModelConfig, batch: dict) -> jnp.ndarray:
    """Next-token cross-entropy (+ MoE aux). batch: tokens, labels[, enc]."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          enc_inputs=batch.get("enc_inputs"),
                          embeddings=batch.get("embeddings"))
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)
    if cfg.aux_loss_coef:
        loss = loss + cfg.aux_loss_coef * aux
    return loss


def expert_load(params: Pytree, cfg: ModelConfig, batch: dict) -> dict:
    """``{"expert_tokens": (MoE layers, E) f32}``: the (token, choice)
    rows each expert receives over ``batch``, one forward pass."""
    _, _, loads = _decoder(params, cfg, batch["tokens"],
                           batch.get("enc_inputs"), batch.get("embeddings"))
    return {"expert_tokens": loads}


def make_lm_loss(cfg: ModelConfig):
    """A ``loss_fn(params, batch)`` closure over ``cfg`` for the FL drivers.

    The drivers key their jit cache on loss_fn identity — build this once
    per run and reuse the same object across rounds and drivers.
    An MoE model's loss carries ``loss_fn.probe`` (:func:`expert_load`), which the
    round engines sum over a round's clients into the ``expert_tokens``
    telemetry tap.
    """
    def loss_fn(params: Pytree, batch: dict) -> jnp.ndarray:
        return lm_loss(params, cfg, batch)

    if cfg.num_experts:
        loss_fn.probe = lambda params, batch: expert_load(params, cfg, batch)
    return loss_fn
