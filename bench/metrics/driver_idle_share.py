"""Share of the traced window in which the device idles while the host
is inside one of the program's driver spans (``fl.*``), from
``bench/spantrace.py``'s ``idle_by_span``. A reduction without host
spans (``tracereduce.reduce``) or a program without them reads
nothing."""
from bench.spantrace import PROGRAM_PREFIX


def read(ctx):
    tr = ctx.get("trace")
    by_span = (tr or {}).get("idle_by_span") or {}
    mine = [v for k, v in by_span.items() if k.startswith(PROGRAM_PREFIX)]
    if not mine:
        return None
    return 100.0 * sum(mine) / tr["window_s"]
