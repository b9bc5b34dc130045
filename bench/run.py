#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the repository
root: the cell's configuration file, ``bench/traffic/<traffic>.json``,
the model module ``bench/models/<config "model">.py``, the check's limits
``bench/limits/<cell>.json`` and one reader ``bench/metrics/<metric>.py``
per metric.

A run: refuse anything but a TPU with the chips the cell asks for; build
the dataset and weights from ``--seed``; make the first three calls of
the program's driver (the first compiles, or loads from the compile
cache kept under the checkout); measure ``--seconds`` of further calls
(traced with ``--trace 1``); read the device's memory peak; free the
program's state; run the plain reference over the first three calls'
rounds and compare. The last line of stdout is one JSON object; the
numbers compared, each with its limit, are the last lines of stderr and
the last key of that object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_spec(workload: str) -> tuple[dict, dict, dict, dict]:
    """(spec, cell, configuration file, traffic file)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return spec, cell, config, traffic


def cell_metrics(spec: dict, workload: str, kind: str) -> list[dict]:
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


def read_metric(name: str, ctx: dict):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path under the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` already names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info(chips: int, peaks_file: str):
    """The accelerator's description and its peaks; exits 1 with no
    result on anything but a TPU with at least ``chips`` chips."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        say(f"run.py: no TPU (JAX reports platform {d.platform!r}); this "
            "benchmark does not run elsewhere")
        sys.exit(1)
    if len(devs) < chips:
        say(f"run.py: the cell needs {chips} chips, JAX reports "
            f"{len(devs)}")
        sys.exit(1)
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devs)}, peak_of(d.device_kind, peaks_file))


def peak_of(kind: str, peaks_file: str) -> dict:
    with open(peaks_file) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {peaks_file}")
    return table[kind]


class CompileCounter:
    """Counts JAX compilations (backend compiles and cache loads)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.count += 1


def measure(cell, seconds: float, annotate=None) -> dict:
    """Call the driver until ``seconds`` have passed; every call ends in
    a sync, so the last call's end closes the window."""
    import contextlib
    span = annotate or (lambda name: contextlib.nullcontext())
    ends = []
    t0 = time.perf_counter()
    while True:
        with span("bench.call"):
            cell.step()
        ends.append(time.perf_counter())
        if ends[-1] - t0 >= seconds:
            break
    with span("bench.sync"):
        cell.sync()
    calls = len(ends)
    return {"calls": calls, "rounds": calls * cell.rounds_per_call,
            "seconds": time.perf_counter() - t0,
            "call_s": [b - a for a, b in zip([t0] + ends, ends)]}


def run_cell(args, spec, cell_entry, config, traffic, *, fault=None,
             check_device=True) -> dict:
    """One run; returns the result object (without printing it).

    ``check_device=False`` lets the benchmark's own tests drive a run on
    the CPU at a small size; the peaks are then the v5e's."""
    import jax
    timings = {}
    chips = int(cell_entry["chips"])
    if check_device:
        device, peak = device_info(chips, os.path.join(BENCH, "peaks.json"))
        say(f"[device] {device} compile cache {enable_compile_cache()}")
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
        peak = peak_of("TPU v5 lite", os.path.join(BENCH, "peaks.json"))
    model = importlib.import_module("bench.models." + config["model"])
    timings["imports_s"] = time.perf_counter() - T_START
    cell = model.Workload(config, traffic, args.seed, fault=fault)
    cell.build()
    cell.first_steps()
    timings.update(cell.timings)
    timings["setup_s"] = time.perf_counter() - T_START
    say("[setup] " + " ".join(f"{k}={v:.3f}" for k, v in timings.items()))

    counter = CompileCounter()
    trace = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(trace_dir)
            window = measure(cell, args.seconds, jax.profiler.TraceAnnotation)
            jax.profiler.stop_trace()
            from bench import tracereduce
            trace = tracereduce.reduce(
                tracereduce.extract(tracereduce.find_xplane(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        window = measure(cell, args.seconds)
    compiles = counter.count
    n_window = window["rounds"]
    window_losses = cell.run.losses[-n_window:]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    stats = jax.devices()[0].memory_stats() or {}
    # a TPU reserves each loaded program's scratch apart from the
    # allocator's buffers: the chip's peak holds both
    mem_peak = (stats["peak_bytes_in_use"]
                + stats.get("peak_bytes_reserved", 0)
                if "peak_bytes_in_use" in stats else None)
    say(f"[memory] {stats}")
    device["memory_peak_bytes"] = mem_peak
    call_q = statistics.quantiles(window["call_s"], n=10) \
        if window["calls"] > 1 else window["call_s"] * 9
    say(f"[window] calls={window['calls']} rounds={n_window} "
        f"seconds={window['seconds']:.4f} compiles_in_window={compiles} "
        f"memory_peak_bytes={mem_peak} "
        f"call_s: min={min(window['call_s']):.4f} "
        f"p10={call_q[0]:.4f} p50={call_q[4]:.4f} p90={call_q[8]:.4f} "
        f"max={max(window['call_s']):.4f}")
    uplink = cell.run.uplink[-n_window:]

    trainable = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                             cell.split(cell.params0)[0])
    ctx = {"timings": timings, "window": window, "chips": chips,
           "memory_peak_bytes": mem_peak, "costs": cell.costs(),
           "config": config, "traffic": traffic, "trainable": trainable,
           "peak": peak, "uplink_per_round": uplink, "trace": trace,
           "trace_rounds": n_window if trace else 0}
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(spec, args.workload, kind):
        value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    cell.release()
    t = time.perf_counter()
    from bench import check
    ref = cell.reference_record(
        observed=cell.prog["observed"],
        tie_margin=check.load_tie_margin(BENCH, args.workload))
    say(f"[reference] seconds={time.perf_counter() - t:.3f}")
    values = check.numbers(cell.prog, ref)
    correct, table = check.judge(values,
                                 check.load_limits(BENCH, args.workload))
    say("[readings] " + " ".join(f"{k}={v!r}" for k, v in values.items()
                                 if k not in table))
    out = {"correct": correct and compiles == 0, "attempted": n_window,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    table["compiles_in_window"] = {"value": compiles, "limit": 0}
    out["checks"] = table
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        say(f"run.py: the program's sources are missing "
            f"({os.path.join(ROOT, 'src', 'repro')})")
        return 2
    # bench/ itself must not shadow standard modules
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    spec, cell_entry, config, traffic = load_spec(args.workload)
    out = run_cell(args, spec, cell_entry, config, traffic)
    for name, row in out["checks"].items():
        say(f"check {name}: {row['value']!r} limit {row['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
