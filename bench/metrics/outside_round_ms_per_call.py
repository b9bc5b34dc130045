"""Device time a driver call spends outside every round phase: copies of
the carry, relayouts of the call's arguments, key and size set-up, the
block's loop itself."""
from bench.metrics._phases import outside_ms_per_call


def read(ctx):
    return outside_ms_per_call(ctx)
