"""The device's peak memory after the window, read before the plain
reference runs: the allocator's ``peak_bytes_in_use`` plus the
``peak_bytes_reserved`` that holds the loaded programs' scratch."""


def read(ctx):
    return ctx["memory_peak_bytes"]
