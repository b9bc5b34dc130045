"""Roofline share of the packed-uplink error-feedback kernel
(kernels/uplink.py), whose events the trace names ``uplink_ef``."""
from bench.costs import kernels
from bench.metrics._roofline import share


def read(ctx):
    return share(ctx, kernels.fused_uplink_ef(
        ctx["trainable"], ctx["traffic"]["clients_per_round"]), "uplink_ef")
