"""Device time a round spends drawing the cohort and gathering its
batches: ops under the ``fl.sample`` phase."""
from bench.metrics._phases import ms_per_round


def read(ctx):
    return ms_per_round(ctx, ("fl.sample",))
