"""Device time a round spends on Eq. 3, the layer divergences: ops under
the ``fl.eq3`` phase, the kernel and the padding and reshapes around it
(``sqdiff_rowsum_roofline`` is the kernel's own share)."""
from bench.metrics._phases import ms_per_round


def read(ctx):
    return ms_per_round(ctx, ("fl.eq3",))
