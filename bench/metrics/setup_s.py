"""Process start to the first timed round: imports, dataset, weights,
compilation or compile-cache loads, and the first three calls that the
correctness check reads (host clock)."""


def read(ctx):
    return ctx["timings"]["setup_s"]
