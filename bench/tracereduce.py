"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes (with
``jax.profiler.ProfileData``, nothing else) into a small dict:

- ``device``: {plane name: [[name, label, start_ns, dur_ns], ...]}, the
  events of each accelerator's ``XLA Ops`` line (one per operation run on
  the device); ``name`` is the HLO instruction's name, ``label`` the
  event's whole text with its string stats (on a TPU the instruction with
  its shapes and, for a Pallas kernel, the kernel's jitted name), which
  is what a kernel is matched by;
- ``host``: [[name, start_ns, dur_ns], ...], the benchmark's own host
  spans (names starting ``bench.``), on the same clock.

``reduce`` turns that dict into busy and window seconds, per-op self
seconds (less the ops nested inside), device seconds per event label
(``kernel_seconds`` sums those of one kernel by a substring of its
label), and the idle gaps labelled by the host span the host was in.
All three are pure, so
``tests/bench`` checks them on a small recorded trace
(``bench/fixtures/trace_small.json``).
"""
from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
HOST_LABELS = {"bench.call": "inside run_training_scan call",
               "bench.sync": "block_until_ready after window"}
BETWEEN = "host between calls"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def extract(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"device": {}, "host": []}
    for plane in data.planes:
        name = plane.name
        if name.startswith(DEVICE_PLANE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    strs = [str(v) for _, v in ev.stats
                            if isinstance(v, str)]
                    evs.append([op_name(ev.name),
                                " ".join([ev.name] + strs),
                                float(ev.start_ns), float(ev.duration_ns)])
            out["device"][name] = evs
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        out["host"].append([ev.name, float(ev.start_ns),
                                            float(ev.duration_ns)])
    return out


def op_name(text: str) -> str:
    """The HLO instruction's name from an event name that may hold the
    whole instruction (``%fusion.12 = f32[...] fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(evs: list) -> list:
    """Each event's duration less the events nested inside it (a
    ``while`` op's event spans the ops of its body, which have events of
    their own). One line runs one op at a time, so an event that starts
    inside another is nested in it, even where the trace's clock has it
    end a little after its parent."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][2], -evs[i][3]))
    own = [float(e[3]) for e in evs]
    stack = []
    for i in order:
        s, e = evs[i][2], evs[i][2] + evs[i][3]
        while stack and evs[stack[-1]][2] + evs[stack[-1]][3] <= s:
            stack.pop()
        if stack:
            parent_end = evs[stack[-1]][2] + evs[stack[-1]][3]
            own[stack[-1]] -= min(e, parent_end) - s
        stack.append(i)
    return [max(o, 0.0) for o in own]


def merge(intervals: list) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def window_of(trace: dict) -> tuple[float, float]:
    """The traced window: from the first ``bench.call`` span's start to
    the end of the last host span."""
    spans = [(s, s + d) for n, s, d in trace["host"]]
    calls = [s for n, s, d in trace["host"] if n == "bench.call"]
    if not calls:
        raise RuntimeError("the trace holds no bench.call span")
    return min(calls), max(e for _, e in spans)


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and window seconds, op and label seconds, idle gaps.

    Busy seconds are averaged over the device planes that ran anything.
    """
    lo, hi = window_of(trace)
    window_s = (hi - lo) * 1e-9
    planes = {p: evs for p, evs in trace["device"].items() if evs}
    if not planes:
        raise RuntimeError("the trace holds no device operation")
    busy, ops, labels = [], {}, {}
    gaps = []
    for evs in planes.values():
        iv = merge(clip([[s, s + d] for _, _, s, d in evs], lo, hi))
        busy.append(sum(e - s for s, e in iv) * 1e-9)
        for (name, label, s, d), own in zip(evs, self_times(evs)):
            ov = max(0.0, min(s + d, hi) - max(s, lo)) * 1e-9
            if ov <= 0:
                continue
            ops[name] = ops.get(name, 0.0) + own * 1e-9 * ov / (d * 1e-9)
            labels[label] = labels.get(label, 0.0) + ov
        edges = [lo] + [x for se in iv for x in se] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    totals, single = {}, []
    for s, e in gaps:
        pieces = _split_by_host(trace["host"], s, e)
        for label, sec in pieces.items():
            totals[label] = totals.get(label, 0.0) + sec
        single.append((max(pieces, key=pieces.get), (e - s) * 1e-9))
    longest = sorted(single, key=lambda x: -x[1])
    idle = ([[f"all gaps: {k}", v] for k, v in
             sorted(totals.items(), key=lambda x: -x[1])]
            + [[f"one gap: {k}", v] for k, v in longest])[:top]
    return {"window_s": window_s,
            "busy_s": sum(busy) / len(busy),
            "ops": ops,
            "label_s": labels,
            "device_ops": [[n, v] for n, v in
                           sorted(ops.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": idle}


def kernel_seconds(reduced: dict, match: str) -> float:
    """Device seconds, inside the window, of the events whose label
    holds ``match``."""
    return sum(v for label, v in reduced["label_s"].items() if match in label)


def _split_by_host(host: list, s: float, e: float) -> dict:
    """Seconds of the gap [s, e) under each host span's label; the rest
    is time the host spent between the benchmark's spans."""
    out = {}
    covered = 0.0
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov > 0:
            label = HOST_LABELS.get(name, name)
            out[label] = out.get(label, 0.0) + ov * 1e-9
            covered += ov
    if e - s - covered > 0:
        out[BETWEEN] = out.get(BETWEEN, 0.0) + (e - s - covered) * 1e-9
    return out
