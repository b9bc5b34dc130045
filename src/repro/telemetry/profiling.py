"""Profiling hooks: trace windows, driver spans, round phases, retrace
counters, system sampling.

Independent facilities the round drivers wire in:

- :class:`ProfileWindow` — a ``jax.profiler`` trace over a configurable
  absolute-round range (``TelemetryConfig.profile_rounds``). The host
  driver opens/closes it exactly at the window bounds; the scan driver
  snaps to eval-block boundaries (a jitted ``lax.scan`` cannot be split
  mid-block). Profiler failures degrade to a one-time warning — tracing
  is best-effort observability, never a correctness dependency. Its
  traces hold the driver spans and round phases below.
- :func:`span` / :func:`phase` — the names a trace carries, always on
  and with no option. A span is a host ``TraceAnnotation``: inert unless
  a profiler trace is open, then recorded on the device ops' clock, so
  each device-idle gap falls inside a named span. The scan driver
  (``run_training_scan``) opens ``fl.scan`` per call (stats
  ``start_round``, ``rounds``) around ``fl.scan.prepare`` (unit map,
  strategy, engine-cache lookup, mesh placement),
  ``fl.scan.copy_carry`` (one program copies params and a resumed state
  before donation and zeroes the comm accumulator; a fresh run's
  ``init_state`` after it), and per eval
  block ``fl.scan.dispatch`` (sizes, base key, the block call: a compile
  shows here), ``fl.scan.pull``, ``fl.scan.log`` and, with an eval
  function, ``fl.scan.eval``; then ``fl.scan.finish``. The host driver
  (``run_training``) opens ``fl.host`` per round (stat ``round``) around
  ``fl.host.sample``, ``.gather``, ``.dispatch``, ``.pull``, ``.log``
  and, on eval rounds, ``.eval``. ProfileWindow's windows start and
  stop outside these spans, so a window holds whole rounds or blocks.
  A phase is a ``jax.named_scope`` on a part of the compiled round: it
  lands in each op's ``op_name`` metadata and changes nothing else.
  ``fl.sample`` (cohort draw, batch gather, sizes), ``fl.local`` (local
  training; in scan mode the client loops), ``fl.eq3`` (divergence),
  ``fl.eq4`` (selection), ``fl.uplink`` (upload transform, quantize/pack,
  EF residual update), ``fl.eq5`` (aggregation, the fused
  dequant+EF+accumulate), ``fl.state`` (state rows, scatter, strategy
  transition), ``fl.comm`` (comm accounting, round loss), ``fl.taps``
  and, on a mesh, ``fl.collective`` (all-gathers, the fused psum). Where
  phases nest, the innermost owns an op. ``bench/spantrace.py`` reads
  both.
- **engine-cache retrace counters** — ``repro.federated.server``'s
  compiled-callable cache reports every build/hit here, so "did this
  config recompile?" is a queryable fact instead of a wall-clock guess:
  :func:`engine_cache_stats` after two identical ``run_training_scan``
  calls must show zero new builds (regression-tested).
- :func:`device_memory_peak` / wall-clock sampling — best-effort
  ``memory_stats()`` peak bytes for the ledger's per-round system fields
  (returns ``None`` on backends that don't report, e.g. CPU).
"""
from __future__ import annotations

import collections
import sys
from typing import Optional

# ----------------------------------------------------------------------
# Driver spans and round phases
# ----------------------------------------------------------------------
def span(name: str, **stats):
    """Host span ``name`` with integer/string ``stats`` (a
    ``jax.profiler.TraceAnnotation``; about a microsecond when no trace
    is open)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **stats)


def phase(name: str):
    """Phase ``name`` of the compiled round (a ``jax.named_scope``): it
    names the ops traced under it and changes nothing they compute."""
    import jax
    return jax.named_scope(name)


# ----------------------------------------------------------------------
# Engine-cache retrace counters
# ----------------------------------------------------------------------
_CACHE_EVENTS: "collections.Counter[str]" = collections.Counter()


def note_engine_cache(kind: str, *, hit: bool) -> None:
    """Called by the round-engine compiled-callable cache on every lookup:
    ``kind`` is the cache's entry kind ('round' for the host driver's
    jitted round, 'block' for the scan driver's block fn)."""
    _CACHE_EVENTS[f"{kind}_{'hits' if hit else 'builds'}"] += 1


def engine_cache_stats() -> dict:
    """Cumulative build/hit counts per engine kind since the last reset.
    ``<kind>_builds`` counts fresh traces+compiles (a nonzero delta across
    two identical driver calls means the compiled-callable cache missed —
    the retrace regression the telemetry subsystem pins)."""
    return dict(_CACHE_EVENTS)


def reset_engine_cache_stats() -> None:
    _CACHE_EVENTS.clear()


# ----------------------------------------------------------------------
# System sampling
# ----------------------------------------------------------------------
def device_memory_peak() -> Optional[int]:
    """Peak device-memory bytes of device 0, or None when the backend
    does not report memory stats (CPU) or the query fails."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use") or stats.get("bytes_in_use")
    return int(peak) if peak else None


# ----------------------------------------------------------------------
# jax.profiler trace windows
# ----------------------------------------------------------------------
class ProfileWindow:
    """Start/stop a ``jax.profiler`` trace over a round range.

    Host driver: ``round_begin(t)`` / ``round_end(t)`` bracket each round
    — the trace starts when ``t`` hits the window's first round and stops
    after its last. Scan driver: ``block_begin(t0, t1)`` /
    ``block_end(t1)`` bracket each eval block with absolute round bounds
    ``[t0, t1)`` — the trace covers every block overlapping the window
    (the window is snapped outward to block boundaries).
    """

    def __init__(self, rounds: Optional[tuple[int, int]], trace_dir: str):
        self.lo, self.hi = rounds if rounds is not None else (None, None)
        self.trace_dir = trace_dir
        self.active = False
        self._warned = False

    @classmethod
    def from_config(cls, telemetry) -> "ProfileWindow":
        if telemetry is None:
            return cls(None, "")
        return cls(telemetry.profile_rounds, telemetry.profile_dir)

    # ------------------------------------------------------------------
    def _start(self) -> None:
        try:
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self.active = True
        except Exception as e:   # profiling is best-effort
            if not self._warned:
                print(f"telemetry: profiler trace unavailable ({e})",
                      file=sys.stderr)
                self._warned = True
            self.lo = None       # don't retry every round

    def _stop(self) -> None:
        if not self.active:
            return
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:
            if not self._warned:
                print(f"telemetry: profiler stop failed ({e})",
                      file=sys.stderr)
                self._warned = True
        self.active = False

    # ---- host driver: exact round bounds ----
    def round_begin(self, t: int) -> None:
        if self.lo is not None and not self.active and self.lo <= t <= self.hi:
            self._start()

    def round_end(self, t: int) -> None:
        if self.active and t >= self.hi:
            self._stop()

    # ---- scan driver: eval-block granularity ----
    def block_begin(self, t0: int, t1: int) -> None:
        """Block covers absolute rounds [t0, t1)."""
        if self.lo is not None and not self.active and \
                t0 <= self.hi and t1 > self.lo:
            self._start()

    def block_end(self, t1: int) -> None:
        if self.active and t1 > self.hi:
            self._stop()

    def close(self) -> None:
        """Stop an open trace at end of run (window past the last round)."""
        self._stop()
