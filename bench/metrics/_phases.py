"""Device self time of the round's phases (``bench/spantrace.py``: the
innermost ``fl.*`` scope each device op's label names), per round or per
call of the traced window. A trace in which no op names a phase (a
program without them) reads nothing."""
from bench import spantrace


def ms_per_round(ctx, phases: tuple):
    secs = _phase_seconds(ctx)
    if secs is None or not ctx["trace_rounds"]:
        return None
    return 1e3 * sum(secs.get(p, 0.0) for p in phases) / ctx["trace_rounds"]


def outside_ms_per_call(ctx):
    """Device time under no phase, per driver call."""
    secs = _phase_seconds(ctx)
    if secs is None or not ctx["window"]["calls"]:
        return None
    return 1e3 * secs.get(None, 0.0) / ctx["window"]["calls"]


def _phase_seconds(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return spantrace.phase_seconds(tr) or None
