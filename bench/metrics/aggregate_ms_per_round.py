"""Device time a round spends from selection to the new model: ops under
the ``fl.eq4``, ``fl.uplink``, ``fl.eq5``, ``fl.state``, ``fl.comm``,
``fl.taps`` and ``fl.collective`` phases."""
from bench.metrics._phases import ms_per_round

PHASES = ("fl.eq4", "fl.uplink", "fl.eq5", "fl.state", "fl.comm",
          "fl.taps", "fl.collective")


def read(ctx):
    return ms_per_round(ctx, PHASES)
