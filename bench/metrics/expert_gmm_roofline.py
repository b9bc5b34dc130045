"""Roofline share of the MoE layers' grouped expert matmuls
(``repro.models.moe.grouped_matmul``: on a TPU the megablox kernels,
whose events the trace names ``gmm.*`` and ``tgmm.*``); their FLOPs and
bytes per round are ``bench/costs/deepseek_v2_lite.expert_gmm``'s. A
program or cell without them reads nothing."""
from bench.costs import deepseek_v2_lite
from bench.metrics._roofline import share


def read(ctx):
    cfg = ctx["config"]
    if "n_routed_experts" not in cfg:
        return None
    return share(ctx, deepseek_v2_lite.expert_gmm(cfg, ctx["traffic"]),
                 "gmm")
