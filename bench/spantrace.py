"""The program's own names in a profiler trace: driver spans and round
phases (``repro.telemetry.profiling``), on top of ``tracereduce``.

- Host spans. The round drivers open ``fl.scan``/``fl.scan.*`` and
  ``fl.host``/``fl.host.*`` spans (``TraceAnnotation``s) on the host, on
  the device ops' clock. :func:`extract` keeps them beside the
  benchmark's ``bench.*`` spans, each as ``[name, start_ns, dur_ns,
  stats]``.
- Round phases. The compiled round names its parts with
  ``jax.named_scope`` (``fl.sample``, ``fl.local``, ``fl.eq3``, ...); the
  name lands in each compiled op's ``op_name`` metadata, and
  :func:`phase_of` reads the innermost one back from an event's label.
  A TPU's op events do not carry that metadata, so :func:`join_scopes`
  appends it: an event that runs inside one of the block's intervals on
  the device's ``XLA Modules`` line (which :func:`extract` keeps as
  ``modules``) takes the ``op_name`` of the same-named instruction in the
  block's compiled text (:func:`scopes_from_hlo`).

:func:`reduce` is ``tracereduce.reduce`` with every existing output as
it was, except that each idle instant of ``idle_gaps`` is put down to the
innermost host span that covers it, and two more outputs:

- ``idle_by_span``: idle seconds by that innermost span's name (every
  span seen in the window is a key, with 0 where it covers no idle), as
  an average over the device planes, as ``busy_s`` is; a program span
  wins over ``bench.sync``, which wins over ``bench.call``;
- ``label_self_s``: device seconds by event label with the events nested
  inside subtracted, summed over the planes as ``ops`` and ``label_s``
  are (on one chip, that chip's seconds).

The per-layer readers under ``bench/metrics/`` need only
``tracereduce.reduce``'s outputs for the round phases, once the labels
name them (:func:`label_self_s` rebuilds self time from ``ops`` and
``label_s``); ``idle_by_span`` needs the host spans that only
:func:`extract` keeps. On a TPU a run reads the phases as
``reduce(join_scopes(extract(path), scopes_from_hlo(block_text)))``,
with ``block_text`` the block's compiled text.
"""
from __future__ import annotations

import bisect
import re

from bench import tracereduce

PROGRAM_PREFIX = "fl."
#: the round phases ``repro.telemetry.profiling`` names
PHASES = ("fl.sample", "fl.local", "fl.eq3", "fl.eq4", "fl.uplink",
          "fl.eq5", "fl.state", "fl.comm", "fl.taps", "fl.collective")
#: a phase as one component of an op's scope path (innermost last)
PHASE_RE = re.compile(r"(?<![\w.])(" + "|".join(
    re.escape(p) for p in PHASES) + r")(?![\w.])")
CALL_LABEL = "benchmark code inside bench.call"
MODULES_LINE = "XLA Modules"
#: the jitted name of ``run_training_scan``'s compiled block
BLOCK_MODULE = "jit_run_block"
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = .*?op_name="([^"]*)"',
                     re.M)


def extract(path: str) -> dict:
    """``tracereduce.extract`` plus the program's host spans, with their
    stats (``start_round``, ``round``, ...), and ``modules``: {plane:
    [[module, start_ns, dur_ns], ...]} from each device plane's ``XLA
    Modules`` line."""
    from jax.profiler import ProfileData
    out = tracereduce.extract(path)
    out["modules"] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tracereduce.DEVICE_PLANE_PREFIX):
            out["modules"][plane.name] = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for line in plane.lines if line.name == MODULES_LINE
                for ev in line.events]
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out["host"].append(
                        [ev.name, float(ev.start_ns), float(ev.duration_ns),
                         {k: v for k, v in ev.stats
                          if isinstance(v, (int, float, str))}])
    return out


def scopes_from_hlo(text: str) -> dict:
    """{instruction name: ``op_name``} of a compiled module's text
    (``jax.stages.Compiled.as_text()`` or an XLA dump after
    optimizations); instructions without one are left out."""
    out = {}
    for m in _HLO_OP.finditer(text):
        out.setdefault(m.group(1), m.group(2))
    return out


def join_scopes(trace: dict, scopes: dict,
                module: str = BLOCK_MODULE) -> dict:
    """``trace`` with each device event that runs inside an interval of
    ``module`` (by ``modules``, ``module`` or ``module(<id>)``) labelled
    ``op_name=<path>`` from ``scopes``; events of other programs, whose
    instruction names may repeat the block's, keep their labels."""
    device = {}
    for plane, evs in trace["device"].items():
        spans = sorted((s, s + d) for name, s, d
                       in trace.get("modules", {}).get(plane, [])
                       if name.split("(", 1)[0] == module)
        starts = [s for s, _ in spans]
        out = []
        for name, label, s, d in evs:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < spans[i][1] and name in scopes:
                label = f"{label} op_name={scopes[name]}"
            out.append([name, label, s, d])
        device[plane] = out
    return dict(trace, device=device)


def phase_of(label: str):
    """The innermost round phase an event label names, or None."""
    found = PHASE_RE.findall(label)
    return found[-1] if found else None


def label_self_s(reduced: dict) -> dict:
    """Self seconds by label from ``tracereduce.reduce``'s outputs.

    ``ops`` holds self seconds by op name and ``label_s`` whole seconds
    by label; a label's op name is the head of its text. Where one name
    has several labels (one op name in several programs), its self
    seconds are shared in proportion to their whole seconds: exact
    unless the op nests others (a loop) in more than one program.
    """
    by_name = {}
    for label, sec in reduced["label_s"].items():
        name = label.split(" ", 1)[0].lstrip("%")
        by_name.setdefault(name, []).append((label, sec))
    out = {}
    for name, items in by_name.items():
        total = sum(sec for _, sec in items)
        own = reduced["ops"].get(name, 0.0)
        for label, sec in items:
            out[label] = own * sec / total if total > 0 else 0.0
    return out


def phase_seconds(reduced: dict) -> dict:
    """Device self seconds by innermost round phase; key None holds what
    ran under no phase. Empty where no event names a phase (a program
    without them)."""
    selfs = reduced.get("label_self_s") or label_self_s(reduced)
    out = {}
    for label, sec in selfs.items():
        p = phase_of(label)
        out[p] = out.get(p, 0.0) + sec
    return out if any(p is not None for p in out) else {}


def reduce(trace: dict, top: int = 10) -> dict:
    """``tracereduce.reduce`` with innermost-span idle attribution,
    ``idle_by_span`` and ``label_self_s``."""
    bench_only = dict(trace, host=[h[:3] for h in trace["host"]
                                   if not h[0].startswith(PROGRAM_PREFIX)])
    out = tracereduce.reduce(bench_only, top=top)
    lo, hi = tracereduce.window_of(bench_only)
    planes = [evs for evs in trace["device"].values() if evs]
    segments = _innermost_segments(trace["host"], lo, hi)
    totals, by_span, single = {}, {}, []
    for evs in planes:
        iv = tracereduce.merge(tracereduce.clip(
            [[s, s + d] for _, _, s, d in evs], lo, hi))
        edges = [lo] + [x for se in iv for x in se] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for (s, e), pieces in zip(gaps, _split(gaps, segments)):
            for (span, label), sec in pieces.items():
                totals[label] = totals.get(label, 0.0) + sec
                by_span[span] = by_span.get(span, 0.0) + sec
            if pieces:
                single.append((max(pieces, key=pieces.get)[1],
                               (e - s) * 1e-9))
    longest = sorted(single, key=lambda x: -x[1])
    out["idle_gaps"] = ([[f"all gaps: {k}", v] for k, v in
                         sorted(totals.items(), key=lambda x: -x[1])]
                        + [[f"one gap: {k}", v] for k, v in longest])[:top]
    names = {h[0] for h in trace["host"]
             if h[1] < hi and h[1] + h[2] > lo}
    out["idle_by_span"] = {n: by_span.get(n, 0.0) / len(planes)
                           for n in names | set(by_span)}
    selfs = {}
    for evs in planes:
        for (_, label, s, d), own in zip(evs, tracereduce.self_times(evs)):
            ov = max(0.0, min(s + d, hi) - max(s, lo))
            if ov > 0:
                selfs[label] = selfs.get(label, 0.0) + own * 1e-9 * ov / d
    out["label_self_s"] = selfs
    return out


def _rank(span) -> tuple:
    """Which of two covering spans owns an instant: a program span over
    ``bench.sync`` over ``bench.call``; among equals the later start (the
    inner one of a nest), then the shorter."""
    name, s, d = span[:3]
    kind = (2 if name.startswith(PROGRAM_PREFIX)
            else 1 if name == "bench.sync" else 0)
    return kind, s, -d


def _label(name):
    if name is None:
        return tracereduce.BETWEEN
    if name == "bench.call":
        return CALL_LABEL
    return tracereduce.HOST_LABELS.get(name, name)


def _innermost_segments(host: list, lo: float, hi: float) -> list:
    """[start, end, (span name, label)] covering [lo, hi] with the
    innermost span of each stretch (``tracereduce.BETWEEN`` for none)."""
    spans = [h for h in host if h[2] > 0 and h[1] < hi and h[1] + h[2] > lo]
    cuts = sorted({lo, hi} | {min(max(x, lo), hi) for h in spans
                              for x in (h[1], h[1] + h[2])})
    opens = sorted(spans, key=lambda h: h[1])
    active, out, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(opens) and opens[j][1] <= a:
            active.append(opens[j])
            j += 1
        active = [h for h in active if h[1] + h[2] > a]
        top = max(active, key=_rank)[0] if active else None
        key = (top or tracereduce.BETWEEN, _label(top))
        if out and out[-1][2] == key:
            out[-1][1] = b
        else:
            out.append([a, b, key])
    return out


def _split(gaps: list, segments: list) -> list:
    """For each gap (sorted, disjoint), {(span, label): seconds} of the
    segments it overlaps."""
    out, j = [], 0
    for s, e in gaps:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        pieces, k = {}, j
        while k < len(segments) and segments[k][0] < e:
            ov = min(e, segments[k][1]) - max(s, segments[k][0])
            if ov > 0:
                key = segments[k][2]
                pieces[key] = pieces.get(key, 0.0) + ov * 1e-9
            k += 1
        out.append(pieces)
    return out
