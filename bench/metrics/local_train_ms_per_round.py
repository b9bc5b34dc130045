"""Device time a round spends in the clients' local training: ops under
the ``fl.local`` phase (in scan mode, both client loops)."""
from bench.metrics._phases import ms_per_round


def read(ctx):
    return ms_per_round(ctx, ("fl.local",))
