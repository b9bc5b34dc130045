"""Uplink wire bytes per round, as the program counts them (its comm
accounting), averaged over the window's rounds. The correctness check
holds the same count against the reference's own."""


def read(ctx):
    per_round = ctx.get("uplink_per_round") or []
    if not per_round:
        return None
    return sum(per_round) / len(per_round)
