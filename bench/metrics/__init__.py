"""One reader per metric, found by the metric's name in BENCHMARK.json.

``<name>.py`` defines ``read(ctx) -> float | None``. ``ctx`` is what one
run of ``bench/run.py`` measured and what it ran (see ``run.py``:
``window``, ``timings``, ``memory_peak_bytes``, ``costs`` (the model's
useful FLOPs per round), ``config``, ``traffic``, ``trainable`` (the
trainable leaves' shapes and dtypes), ``peak``, ``uplink_per_round`` and,
in a traced run, ``trace`` (``tracereduce.reduce``'s result) and
``trace_rounds``). So a new per-layer metric, a kernel's roofline among
them, is one new file here, with its counts in a new file under
``bench/costs/`` where it needs them. A reader that finds nothing to
read returns None and the harness leaves the metric out of the result
line.
"""
