"""Roofline share of the Eq. 3 kernel (kernels/divergence.py), whose
events the trace names ``sqdiff``."""
from bench.costs import kernels
from bench.metrics._roofline import share


def read(ctx):
    tr = ctx["traffic"]
    return share(ctx, kernels.sqdiff_rowsum(
        ctx["trainable"], tr["clients_per_round"], tr["mode"]), "sqdiff")
