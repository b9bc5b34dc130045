"""A kernel's roofline share: the least time the chip could take for the
kernel's work in the traced window, the larger of FLOPs over the bf16
peak and logical bytes over the HBM bandwidth, over the device time of
the trace's events whose label holds ``match``.

``cost`` is the kernel's per-round ``{"flops", "bytes"}``, which the
metric's own file computes from the run's trainable shapes and traffic
(``bench/costs/``). A run whose trace holds no such event reads
nothing."""
from bench import tracereduce


def share(ctx, cost: dict, match: str):
    tr = ctx.get("trace")
    if not tr or not ctx["trace_rounds"]:
        return None
    secs = tracereduce.kernel_seconds(tr, match)
    if secs <= 0:
        return None
    peak = ctx["peak"]
    least = max(cost["flops"] / peak["bf16_flops_per_s"],
                cost["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least * ctx["trace_rounds"] / secs
