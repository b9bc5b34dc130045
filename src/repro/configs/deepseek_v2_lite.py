"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, multi-head latent
attention (kv_lora_rank 512, qk 128 nope + 64 rope, v 128), YaRN RoPE
(theta 1e4, factor 40 over 4,096 positions), layer 0 dense (SwiGLU
10,944), layers 1-26 DeepSeekMoE: 2 shared + 64 routed experts of width
1,408, top-6 softmax without renormalisation; vocab 102,400, untied.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]

Departure: the published sequence-level balance loss (alpha 0.001) is a
pre-training loss; ``aux_loss_coef`` is 0 here, for fine-tuning with a
frozen router.
"""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        d_ff=1408,
        vocab_size=102400,
        rope_theta=10_000.0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_factor=40.0,
        rope_original_max_positions=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        num_experts=64,
        num_shared_experts=2,
        moe_top_k=6,
        moe_d_ff=1408,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
        first_dense_layers=1,
        dense_d_ff=10944,
        aux_loss_coef=0.0,
        param_dtype="bfloat16",
        compute_dtype="bfloat16",
        source="hf:deepseek-ai/DeepSeek-V2-Lite (config.json); "
               "arXiv:2405.04434",
    )
