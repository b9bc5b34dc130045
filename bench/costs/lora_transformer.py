"""Useful FLOPs of LoRA local training on a frozen decoder.

Per token and local step, with ``N`` the frozen matmul parameters the
token passes through (every layer's attention and MLP projections plus
the untied head; the embedding is a lookup):

- forward through the base: ``2 N``;
- backward through the base, input gradients only (the base is frozen,
  so it has no weight gradients): ``2 N``;
- the adapters: ``2 P`` forward, ``4 P`` backward (input and weight
  gradients), ``P`` the adapter parameters;
- causal attention per layer: ``2 H hd T`` forward for the scores and the
  weighted values over the causal half, twice that backward.

The scan engine trains every client twice per round when the strategy
needs divergence (once to score it, once to aggregate); the second pass
is recompute and is not counted, nor is per-block rematerialisation.
"""
from __future__ import annotations


def base_params(cfg: dict) -> float:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return float(cfg["num_hidden_layers"] * per_layer
                 + d * cfg["vocab_size"])


def adapter_params(cfg: dict) -> float:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
              "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    r = cfg["lora"]["rank"]
    names = [n for mod in cfg["lora"]["targets"].values() for n in mod]
    return float(cfg["num_hidden_layers"]
                 * sum(r * (shapes[n][0] + shapes[n][1]) for n in names))


def train_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    t = float(seq_len)
    attn_fwd = (cfg["num_hidden_layers"] * 2.0 * cfg["num_attention_heads"]
                * cfg["head_dim"] * t * t)
    return (4.0 * base_params(cfg) * t + 6.0 * adapter_params(cfg) * t
            + 3.0 * attn_fwd)


def useful_flops_per_round(cfg: dict, traffic: dict) -> float:
    seq = traffic["dataset"]["seq_len"] - 1
    return (traffic["clients_per_round"] * traffic["batch_per_client"]
            * traffic["local_steps"] * train_flops_per_sequence(cfg, seq))
