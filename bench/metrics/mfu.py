"""Useful FLOPs of local training per round (``bench/costs``), times the
rounds completed in the traced window, over the window and the chips'
bf16 peak (``bench/peaks.json``). Recomputed work (remat, the scan
engine's second training pass) is not counted."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx["trace_rounds"]:
        return None
    flops = ctx["costs"]["flops_per_round"] * ctx["trace_rounds"]
    return 100.0 * flops / (tr["window_s"] * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])
