"""Useful FLOPs of LoRA local training on a frozen DeepSeek-V2-Lite share,
and the FLOPs and bytes of its grouped expert matmuls.

Per token and local step, with ``N`` the frozen matmul parameters one
token passes through (every layer's latent-attention projections, the
dense layer's SwiGLU, the router, the ``k`` experts it is routed to and
the shared experts in each MoE layer, and the untied head; the embedding
is a lookup) and ``P`` the adapter parameters it passes through (those of
the same projections, routed experts counted ``k`` times):

- the frozen base: ``2 N`` forward and ``2 N`` backward (input gradients
  only; the base has no weight gradients);
- the adapters: ``2 P`` forward, ``4 P`` backward;
- causal attention per layer: ``T H (qk + v)`` forward for the scores and
  the weighted values over the causal half (``qk = 192``, ``v = 128``),
  twice that backward.

The scan engine trains every client twice per round when the strategy
needs divergence; the second pass is recompute and is not counted, nor is
per-layer rematerialisation.

``expert_gmm`` counts what the grouped matmuls of the MoE layers execute
in a round, recompute included, since the roofline share divides by
their whole device time: per client and MoE layer, both scan passes, in
each the forward, the checkpointed layer's forward again in the backward
pass, and the backward products. Per adapted expert projection
``x (rows, d_in) -> (rows, d_out)``, ``rows = T k``, the forward runs the
base product and the adapter's two (``x a``, ``z b``); the backward runs
the base's input gradient and four adapter products (``dy b^T``,
``dz a^T``, ``z^T dy`` and ``x^T dz`` per expert). A call's FLOPs are
``2 rows d_in d_out`` for its own widths; its bytes are its bfloat16
operands and result, the row operands read once, each expert's weight
(or, for a per-expert result, that result) once.
"""
from __future__ import annotations

BF16 = 2


def _dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return {"d": cfg["hidden_size"], "h": h, "qk": qk,
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "c": cfg["kv_lora_rank"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "L": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"], "V": cfg["vocab_size"],
            "r": cfg["lora"]["rank"]}


def _projections(cfg: dict) -> dict:
    """{module path: {name: (d_in, d_out)}} per layer kind."""
    m = _dims(cfg)

    def swiglu(f):
        return {"w_gate": (m["d"], f), "w_up": (m["d"], f),
                "w_down": (f, m["d"])}

    attn = {"wq": (m["d"], m["h"] * m["qk"]),
            "wkv_a": (m["d"], m["c"] + m["rope"]),
            "wkv_b": (m["c"], m["h"] * (m["nope"] + m["vd"])),
            "wo": (m["h"] * m["vd"], m["d"])}
    return {"dense": {"attn": attn, "mlp": swiglu(m["f"])},
            "moe": {"attn": attn, "moe": swiglu(m["fe"]),
                    "moe/shared": swiglu(m["fs"])}}


def _per_token(cfg: dict, adapters: bool) -> float:
    """Parameters a token passes through: frozen (``adapters=False``) or
    adapter (True), routed experts ``k`` times."""
    m = _dims(cfg)
    targets = cfg["lora"]["targets"]
    total = 0.0
    for kind, depth in (("dense", m["dense"]), ("moe", m["L"] - m["dense"])):
        layer = 0.0
        for path, projs in _projections(cfg)[kind].items():
            times = m["k"] if path == "moe" else 1
            for name, (din, dout) in projs.items():
                if not adapters:
                    layer += times * din * dout
                elif name in targets.get(path, ()):
                    layer += times * m["r"] * (din + dout)
        if kind == "moe" and not adapters:
            layer += m["d"] * m["E"]                       # the router
        total += depth * layer
    if not adapters:
        total += m["d"] * m["V"]                           # the head
    return total


def base_params_active(cfg: dict) -> float:
    return _per_token(cfg, adapters=False)


def adapter_params_active(cfg: dict) -> float:
    return _per_token(cfg, adapters=True)


def adapter_params(cfg: dict) -> float:
    """Every adapter parameter (each routed expert once per expert)."""
    m = _dims(cfg)
    targets = cfg["lora"]["targets"]
    total = 0.0
    for kind, depth in (("dense", m["dense"]), ("moe", m["L"] - m["dense"])):
        for path, projs in _projections(cfg)[kind].items():
            copies = m["E"] if path == "moe" else 1
            total += depth * copies * sum(
                m["r"] * (din + dout) for name, (din, dout) in projs.items()
                if name in targets.get(path, ()))
    return total


def train_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    m = _dims(cfg)
    t = float(seq_len)
    attn_fwd = m["L"] * t * t * m["h"] * (m["qk"] + m["vd"])
    return (4.0 * base_params_active(cfg) * t
            + 6.0 * adapter_params_active(cfg) * t + 3.0 * attn_fwd)


def useful_flops_per_round(cfg: dict, traffic: dict) -> float:
    seq = traffic["dataset"]["seq_len"] - 1
    return (traffic["clients_per_round"] * traffic["batch_per_client"]
            * traffic["local_steps"] * train_flops_per_sequence(cfg, seq))


def _call(rows: float, din: int, dout: int, experts: int,
          grouped_out: bool = False) -> tuple:
    """(FLOPs, bytes) of one grouped product; ``grouped_out`` for a
    per-expert result (E, d_in, d_out) made from two row operands."""
    flops = 2.0 * rows * din * dout
    if grouped_out:
        byts = (rows * (din + dout) + experts * din * dout) * BF16
    else:
        byts = (rows * din + rows * dout + experts * din * dout) * BF16
    return flops, byts


def expert_gmm(cfg: dict, traffic: dict) -> dict:
    """Per-round FLOPs and bytes of the grouped expert matmuls."""
    m = _dims(cfg)
    rows = float((traffic["dataset"]["seq_len"] - 1)
                 * traffic["batch_per_client"] * m["k"])
    targets = cfg["lora"]["targets"].get("moe", ())
    e, r = m["E"], m["r"]
    flops = byts = 0.0
    for name, (din, dout) in _projections(cfg)["moe"]["moe"].items():
        fwd = [_call(rows, din, dout, e)]
        bwd = [_call(rows, dout, din, e)]
        if name in targets:
            fwd += [_call(rows, din, r, e), _call(rows, r, dout, e)]
            bwd += [_call(rows, dout, r, e), _call(rows, r, din, e),
                    _call(rows, r, dout, e, grouped_out=True),
                    _call(rows, din, r, e, grouped_out=True)]
        # forward, its recompute inside the checkpointed layer, backward
        for f, b in 2 * fwd + bwd:
            flops += f
            byts += b
    passes = 2 if traffic["algo"] == "fedldf" else 1
    n = (passes * traffic["clients_per_round"] * traffic["local_steps"]
         * (m["L"] - m["dense"]))
    return {"flops": n * flops, "bytes": n * byts}
