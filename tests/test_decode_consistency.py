"""Serving-path correctness: prefill + incremental decode must reproduce the
full-forward logits for every model family (incl. sliding window, SSM state,
MoE routing, latent attention with YaRN and a leading dense layer, M-RoPE,
enc-dec cross attention)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import decode as dec
from repro.models import transformer as tf
from repro.models.config import ModelConfig


def mk(family, **kw):
    base = dict(name="t-" + family, family=family, num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                vocab_size=97)
    base.update(kw)
    return ModelConfig(**base)


CASES = [
    mk("dense"),
    mk("dense", sliding_window=8),
    mk("dense", qk_norm=True, qkv_bias=True),
    mk("moe", num_experts=4, moe_top_k=2, moe_d_ff=32, num_shared_experts=1,
       d_ff=0),
    mk("moe", name="t-mla-moe", num_experts=4, moe_top_k=2, moe_d_ff=32,
       num_shared_experts=1, d_ff=0, kv_lora_rank=16, qk_nope_head_dim=8,
       qk_rope_head_dim=8, v_head_dim=12, rope_factor=4.0,
       rope_original_max_positions=8, yarn_mscale=0.707,
       yarn_mscale_all_dim=0.707, norm_topk_prob=False,
       first_dense_layers=1, dense_d_ff=48),
    mk("ssm", ssm_state=8, ssm_head_dim=16, ssm_chunk=8),
    mk("hybrid", ssm_state=8, ssm_head_dim=16, ssm_chunk=8),
    mk("vlm", mrope=True, mrope_sections=(4, 2, 2)),
    mk("audio", encoder_layers=2, frontend_dim=24),
]


@pytest.mark.parametrize("cfg", CASES, ids=lambda c: f"{c.name}-w{c.sliding_window}")
def test_decode_matches_forward(cfg):
    key = jax.random.PRNGKey(0)
    params = tf.init_params(key, cfg)
    s, steps = 13, 4           # deliberately not a chunk multiple
    toks = jax.random.randint(key, (2, s + steps), 0, cfg.vocab_size)
    enc = (jax.random.normal(key, (2, 13, 24))
           if cfg.is_encdec else None)
    logits_full, _ = tf.forward(params, cfg, toks, enc_inputs=enc)

    lg, cache = dec.prefill(params, cfg, toks[:, :s], enc_inputs=enc,
                            max_len=s + steps)
    np.testing.assert_allclose(lg, logits_full[:, s - 1], rtol=1e-4,
                               atol=1e-4)
    for t in range(steps):
        lg, cache = dec.decode_step(params, cfg, toks[:, s + t:s + t + 1],
                                    cache)
        np.testing.assert_allclose(lg, logits_full[:, s + t], rtol=1e-4,
                                   atol=1e-4)


def test_ring_buffer_eviction_matches_window():
    """With a full ring buffer, decode == forward restricted to the window."""
    cfg = mk("dense", sliding_window=6)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 20), 0, 97)
    logits_full, _ = tf.forward(params, cfg, toks)
    lg, cache = dec.prefill(params, cfg, toks[:, :10])
    for t in range(10, 20):
        lg, cache = dec.decode_step(params, cfg, toks[:, t:t + 1], cache)
        np.testing.assert_allclose(lg, logits_full[:, t], rtol=1e-4,
                                   atol=1e-4)


def test_flash_path_matches_block_path():
    """Chunked-flash attention (long KV) == single-block attention."""
    from repro.models import attention as attn
    key = jax.random.PRNGKey(0)
    b, sq, h, kvh, hd = 2, 64, 4, 2, 16
    q = jax.random.normal(key, (b, sq, h, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sq, kvh, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sq, kvh, hd))
    pos = jnp.arange(sq)
    block = attn.attend(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                        flash_threshold=10_000)
    flash = attn.attend(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                        flash_threshold=1, chunk=16)
    np.testing.assert_allclose(block, flash, rtol=2e-4, atol=2e-5)


def test_flash_path_sliding_window():
    from repro.models import attention as attn
    key = jax.random.PRNGKey(0)
    b, sq, h, kvh, hd = 1, 48, 2, 2, 8
    q = jax.random.normal(key, (b, sq, h, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sq, kvh, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sq, kvh, hd))
    pos = jnp.arange(sq)
    block = attn.attend(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                        window=7, flash_threshold=10_000)
    flash = attn.attend(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                        window=7, flash_threshold=1, chunk=16)
    np.testing.assert_allclose(block, flash, rtol=2e-4, atol=2e-5)
