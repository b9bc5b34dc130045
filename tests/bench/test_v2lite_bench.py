"""The DeepSeek-V2-Lite cell's own files, on the CPU: the configuration
states its cut and keeps every number of the published config.json but
the depth; the cost functions against hand counts; the reference's YaRN
constants and routing; the grouped-matmul roofline reader."""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run, tracereduce  # noqa: E402
from bench.costs import deepseek_v2_lite as costs  # noqa: E402

CONFIG = "deepseek-v2-lite-6of27"

# the published config.json's numbers (huggingface.co/deepseek-ai/
# DeepSeek-V2-Lite), as the model-configs catalog holds them
PUBLISHED = {
    "first_k_dense_replace": 1, "hidden_size": 2048,
    "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1, "topk_group": 1,
    "v_head_dim": 128, "vocab_size": 102400, "tie_word_embeddings": False}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return load("BENCHMARK.json")


@pytest.fixture(scope="module")
def cfg(spec):
    entry = {c["name"]: c for c in spec["configs"]}[CONFIG]
    return load(entry["file"])


def test_config_file_states_its_cut(spec, cfg):
    entry = {c["name"]: c for c in spec["configs"]}[CONFIG]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    for key in ("source", "precision", "deployment", "assumed",
                "departures"):
        assert cfg[key]
    assert cfg["published"]["num_hidden_layers"] == 27
    assert cfg["num_hidden_layers"] == 6
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key


def test_costs_match_published_counts(cfg):
    """706.2 M frozen parameters a token passes through, 56.5 M adapter
    parameters, 13.3 TFLOP a 4,096-token client step."""
    assert costs.base_params_active(cfg) == pytest.approx(706.2e6, rel=1e-4)
    assert costs.adapter_params(cfg) == pytest.approx(56.45e6, rel=1e-3)
    assert costs.train_flops_per_sequence(cfg, 4096) == pytest.approx(
        13.32e12, rel=1e-3)


TINY = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 3, "kv_lora_rank": 5,
        "intermediate_size": 12, "moe_intermediate_size": 6,
        "n_shared_experts": 1, "n_routed_experts": 4,
        "num_experts_per_tok": 2, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "vocab_size": 10,
        "lora": {"rank": 2, "targets": {"attn": ["wq", "wo"],
                                        "mlp": ["w_down"],
                                        "moe": ["w_gate"],
                                        "moe/shared": ["w_up"]}}}


def test_costs_match_hand_count():
    # attention 96 + 56 + 70 + 48 = 270 a layer; the dense SwiGLU 288;
    # a MoE layer 2 x 144 routed + 144 shared + 32 router; the head 80
    assert costs.base_params_active(TINY) == 558 + 2 * 734 + 80
    # attention 40 + 28 a layer, the dense w_down 40, a MoE layer's
    # w_gate 28 (x 2 routed, x 4 held) and shared w_up 28
    assert costs.adapter_params_active(TINY) == 3 * 68 + 40 + 2 * 84
    assert costs.adapter_params(TINY) == 3 * 68 + 40 + 2 * 140
    t = 5
    attn = 3 * t * t * 2 * (6 + 3)
    assert costs.train_flops_per_sequence(TINY, t) == (
        4 * 2106 * t + 6 * 412 * t + 3 * attn)
    traffic = {"algo": "fedldf", "clients_per_round": 3,
               "batch_per_client": 1, "local_steps": 1,
               "dataset": {"seq_len": t + 1}}
    assert costs.useful_flops_per_round(TINY, traffic) == \
        3 * costs.train_flops_per_sequence(TINY, t)
    # grouped matmuls, rows = 5 x 2 = 10: w_up and w_down (no adapters)
    # 2,880 FLOPs and 1,992 bytes a pass each; w_gate with its adapters
    # 5,120 FLOPs and 4,328 bytes; 2 passes x 3 clients x 2 MoE layers
    got = costs.expert_gmm(TINY, traffic)
    assert got["flops"] == 12 * (2880 + 2880 + 5120)
    assert got["bytes"] == 12 * (1992 + 1992 + 4328)


def test_expert_gmm_roofline_reads_gmm_events(cfg):
    traffic = load("bench/traffic/lora_fedldf_domains_4k.json")
    trace = {"device": {"/device:TPU:0": [
        ["gmm.3", "gmm.3", 0.0, 4e8],
        ["tgmm.1", "tgmm.1", 4e8, 1e8],
        ["fusion.1", "fusion.1", 5e8, 5e8]]},
        "host": [["bench.call", 0.0, 1e9]]}
    peak = run.peak_of("TPU v5 lite",
                       os.path.join(ROOT, "bench", "peaks.json"))
    ctx = {"trace": tracereduce.reduce(trace), "trace_rounds": 1,
           "config": cfg, "traffic": traffic, "peak": peak}
    cost = costs.expert_gmm(cfg, traffic)
    least = max(cost["flops"] / peak["bf16_flops_per_s"],
                cost["bytes"] / peak["hbm_bytes_per_s"])
    assert run.read_metric("expert_gmm_roofline", ctx) == pytest.approx(
        100.0 * least / 0.5)
    coder = load("bench/configs/deepseek-coder-33b-4of62.json")
    assert run.read_metric("expert_gmm_roofline",
                           dict(ctx, config=coder)) is None
    assert run.read_metric("expert_gmm_roofline", dict(ctx, trace=None)) \
        is None


def test_reference_yarn_and_routing(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.models import deepseek_v2_lite as model
    inv, cos_factor, scale = model.yarn(cfg)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], base[:11])
    np.testing.assert_allclose(inv[23:], base[23:] / 40)
    assert cos_factor == 1.0
    assert scale == pytest.approx(0.114721, abs=1e-6)
    h = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    router = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    w = np.asarray(model.route_weights(jax, h, router, cfg))
    probs = np.asarray(jax.nn.softmax(jnp.dot(
        h, router, precision=jax.lax.Precision.HIGHEST), axis=-1))
    assert ((w > 0).sum(-1) == 6).all()
    np.testing.assert_allclose(w.sum(-1), np.sort(probs, -1)[:, -6:].sum(-1),
                               rtol=1e-6)
