"""The reduction of the program's spans and phases (``bench/spantrace.py``)
and the per-layer readers built on it, on the CPU.

- on a small synthetic trace with nested ``fl.`` spans each idle instant
  is counted once, under its innermost span, and the ``all gaps``
  totals sum to window - busy;
- on the recorded one-call trace (``bench/fixtures/trace_small.json``,
  a program without spans) every output of ``tracereduce.reduce`` and
  every existing metric reads as before, and the new readers read
  nothing;
- on a traced call of the program with its spans, recorded on a TPU v5e
  (``bench/fixtures/trace_spans.json``), the driver spans own the idle
  time and ``driver_idle_share`` reads under ``device_idle_share``; the
  chip's op events carry no ``op_name``, so the phase readers read
  nothing there until ``join_scopes`` labels the block's ops from its
  compiled text, and then every device second has one owner.
"""
from __future__ import annotations

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run, spantrace, tracereduce  # noqa: E402

NEW_READERS = ("driver_idle_share", "outside_round_ms_per_call",
               "sample_ms_per_round", "local_train_ms_per_round",
               "divergence_ms_per_round", "aggregate_ms_per_round")
PHASE_READERS = NEW_READERS[1:]


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


# ----------------------------------------------------------------------
# a synthetic trace
# ----------------------------------------------------------------------
def _op(label, s, d):
    return [label.split(" ", 1)[0], label, float(s), float(d)]


@pytest.fixture
def nested():
    """Two calls; the first has a driver span with three children, the
    second none (a program without spans inside its call)."""
    dev = [_op("fusion.1 jit(run_block)/while/body/fl.local/conv", 12, 3),
           _op("fusion.2 jit(run_block)/while/body/fl.eq3/sqdiff", 15, 5),
           _op("while.9 jit(run_block)/while", 30, 30),
           _op("fusion.3 jit(run_block)/while/body/fl.local/fl.eq3/x",
               35, 10),
           _op("copy.1 jit(copy)/copy", 62, 4),
           _op("fusion.4 jit(f)/fl.eq5/add", 120, 20)]
    host = [["bench.call", 0, 100],
            ["fl.scan", 5, 90, {"start_round": 0, "rounds": 1}],
            ["fl.scan.copy_carry", 8, 10, {}],
            ["fl.scan.dispatch", 25, 10, {}],
            ["fl.scan.pull", 40, 50, {}],
            ["bench.call", 110, 50],
            ["bench.sync", 165, 15]]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_every_gap_counted_once_under_innermost_span(nested):
    out = spantrace.reduce(nested)
    # window 0..180; busy [12,20] [30,60] [62,66] [120,140] = 62
    assert out["window_s"] == pytest.approx(180e-9)
    assert out["busy_s"] == pytest.approx(62e-9)
    by = out["idle_by_span"]
    # idle [0,12] [20,30] [60,62] [66,120] [140,180], by innermost span
    want = {"bench.call": (5 + 5 + 10 + 20) * 1e-9,  # 0-5 95-100 110-120
                                                      # 140-160
            "fl.scan": (3 + 5 + 5) * 1e-9,            # 5-8 20-25 90-95
            "fl.scan.copy_carry": 4e-9,               # 8-12
            "fl.scan.dispatch": 5e-9,                 # 25-30
            "fl.scan.pull": (2 + 24) * 1e-9,          # 60-62 66-90
            "bench.sync": 15e-9,                      # 165-180
            tracereduce.BETWEEN: (10 + 5) * 1e-9}     # 100-110 160-165
    assert by.keys() == want.keys()
    for k, v in want.items():
        assert by[k] == pytest.approx(v), k
    totals = {k[len("all gaps: "):]: v for k, v in out["idle_gaps"]
              if k.startswith("all gaps: ")}
    assert sum(totals.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert totals[spantrace.CALL_LABEL] == pytest.approx(want["bench.call"])


def test_innermost_phase_owns_an_op(nested):
    out = spantrace.reduce(nested)
    secs = spantrace.phase_seconds(out)
    assert secs["fl.local"] == pytest.approx(3e-9)
    assert secs["fl.eq3"] == pytest.approx(15e-9)    # the nested one too
    assert secs["fl.eq5"] == pytest.approx(20e-9)
    # the loop less its body, and the copy
    assert secs[None] == pytest.approx(20e-9 + 4e-9)
    assert sum(secs.values()) == pytest.approx(out["busy_s"])


def test_self_time_rebuilt_from_ops_and_labels(nested):
    out = spantrace.reduce(nested)
    rebuilt = spantrace.label_self_s(out)
    assert rebuilt.keys() == out["label_self_s"].keys()
    for k, v in out["label_self_s"].items():
        assert rebuilt[k] == pytest.approx(v), k


def test_phase_names_are_whole_path_components():
    assert spantrace.phase_of("a/fl.local/b/fl.eq3/mul") == "fl.eq3"
    assert spantrace.phase_of('x tf_op="jit(f)/fl.comm/add"') == "fl.comm"
    assert spantrace.phase_of("a/fl.localx/b") is None
    assert spantrace.phase_of("a/fl.scan.pull/b") is None
    assert spantrace.phase_of("%fusion.1 = f32[2] fusion()") is None


def test_driver_idle_share_reads_program_spans_only(nested):
    out = spantrace.reduce(nested)
    ctx = {"trace": out}
    got = run.read_metric("driver_idle_share", ctx)
    assert got == pytest.approx(100.0 * 48e-9 / 180e-9)
    assert run.read_metric("driver_idle_share",
                           {"trace": tracereduce.reduce(
                               dict(nested, host=[h[:3] for h in
                                                  nested["host"]
                                                  if h[0][:6] == "bench."]))
                            }) is None


# ----------------------------------------------------------------------
# the recorded trace of a program without spans
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    return load("bench/fixtures/trace_small.json")


def _ctx(reduced, rounds=4, calls=1):
    import jax
    import numpy as np
    return {"trace": reduced, "trace_rounds": rounds,
            "window": {"calls": calls}, "chips": 1,
            "traffic": {"clients_per_round": 2, "mode": "vmap"},
            "trainable": {"a": {"w": jax.ShapeDtypeStruct((3, 5),
                                                          np.float32)}},
            "costs": {"flops_per_round": 1e12},
            "peak": run.peak_of("TPU v5 lite",
                                os.path.join(ROOT, "bench", "peaks.json"))}


def test_existing_outputs_and_metrics_unchanged(recorded):
    old = tracereduce.reduce(recorded)
    new = spantrace.reduce(recorded)
    for k in ("window_s", "busy_s", "ops", "label_s", "device_ops"):
        assert new[k] == old[k], k
    for name in ("device_idle_share", "mfu", "sqdiff_rowsum_roofline"):
        assert run.read_metric(name, _ctx(new)) == \
            run.read_metric(name, _ctx(old)), name


def test_new_readers_read_nothing_without_spans(recorded):
    for reduced in (tracereduce.reduce(recorded),
                    spantrace.reduce(recorded)):
        for name in NEW_READERS:
            assert run.read_metric(name, _ctx(reduced)) is None, name


def test_idle_gaps_of_a_program_without_spans(recorded):
    """Without program spans the gaps are those of ``tracereduce``, the
    call's now named as the benchmark's own code."""
    relabel = {f"all gaps: {tracereduce.HOST_LABELS['bench.call']}":
               f"all gaps: {spantrace.CALL_LABEL}"}
    want = {relabel.get(k, k): v
            for k, v in tracereduce.reduce(recorded)["idle_gaps"]
            if k.startswith("all gaps")}
    got = {k: v for k, v in spantrace.reduce(recorded)["idle_gaps"]
           if k.startswith("all gaps")}
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k]), k


# ----------------------------------------------------------------------
# a call of the program with its spans, traced on a TPU v5e
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def spans_trace():
    return load("bench/fixtures/trace_spans.json")


def test_recorded_spans_own_the_idle_time(spans_trace):
    out = spantrace.reduce(spans_trace)
    by = out["idle_by_span"]
    for name in ("fl.scan", "fl.scan.prepare", "fl.scan.copy_carry",
                 "fl.scan.dispatch", "fl.scan.pull", "fl.scan.log",
                 "fl.scan.finish"):
        assert name in by, name
    assert sum(by.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert max(by, key=by.get) == "fl.scan.copy_carry"
    ctx = _ctx(out, rounds=1, calls=1)
    driver = run.read_metric("driver_idle_share", ctx)
    device = run.read_metric("device_idle_share", ctx)
    assert 0.0 < driver <= device
    named = [k for k, _ in out["idle_gaps"]]
    assert "all gaps: fl.scan.copy_carry" in named


@pytest.mark.parametrize("name", ["trace_small", "trace_spans"])
def test_recorded_self_time_rebuilt(name):
    out = spantrace.reduce(load(f"bench/fixtures/{name}.json"))
    rebuilt = spantrace.label_self_s(out)
    assert sum(rebuilt.values()) == pytest.approx(out["busy_s"], rel=1e-6)
    for k, v in out["label_self_s"].items():
        assert rebuilt[k] == pytest.approx(v, rel=1e-9, abs=1e-15), k


def test_phase_readers_on_a_tpu_trace(spans_trace):
    """A TPU trace's ``XLA Ops`` events carry no ``op_name``, so no
    label names a phase and the phase readers read nothing: they are
    not listed in ``BENCHMARK.json``."""
    for reduced in (tracereduce.reduce(dict(
            spans_trace, host=[h[:3] for h in spans_trace["host"]
                               if h[0].startswith("bench.")])),
            spantrace.reduce(spans_trace)):
        assert spantrace.phase_seconds(reduced) == {}
        for name in PHASE_READERS:
            assert run.read_metric(name, _ctx(reduced)) is None, name


def test_phase_readers_on_a_joined_tpu_trace(spans_trace):
    """Joined with the block's scopes, the five phase readers read the
    recorded call, and the phases times the rounds plus the outside time
    times the calls are the device's busy time."""
    joined = spantrace.join_scopes(spans_trace, spans_trace["scopes"])
    ctx = _ctx(spantrace.reduce(joined), rounds=1, calls=1)
    got = {name: run.read_metric(name, ctx) for name in PHASE_READERS}
    want = {"outside_round_ms_per_call": 11.537, "sample_ms_per_round": 0.116,
            "local_train_ms_per_round": 50.431,
            "divergence_ms_per_round": 19.630,
            "aggregate_ms_per_round": 9.606}
    for name, value in want.items():
        assert got[name] == pytest.approx(value, abs=1e-3), name
    assert sum(got.values()) * 1e-3 == pytest.approx(ctx["trace"]["busy_s"],
                                                     rel=1e-9)


def test_join_scopes_labels_only_the_block():
    """An event takes its instruction's scope only inside an interval of
    the block's module; another program's same-named op keeps its
    label, as does an op the block's text does not name."""
    trace = {"device": {"/device:TPU:0": [
                 ["copy.1", "%copy.1 = f32[4] copy()", 10.0, 2.0],
                 ["copy.1", "%copy.1 = f32[4] copy()", 30.0, 2.0],
                 ["fusion.7", "%fusion.7 = f32[4] fusion()", 34.0, 3.0]]},
             "host": [["bench.call", 0.0, 50.0]],
             "modules": {"/device:TPU:0": [["jit_copy(17)", 9.0, 4.0],
                                           ["jit_run_block(3)", 29.0, 10.0]]}}
    joined = spantrace.join_scopes(
        trace, {"copy.1": "jit(run_block)/while/body/fl.state/copy"})
    labels = [e[1] for e in joined["device"]["/device:TPU:0"]]
    assert [spantrace.phase_of(x) for x in labels] == [None, "fl.state",
                                                        None]
    assert trace["device"]["/device:TPU:0"][1][1] == "%copy.1 = f32[4] copy()"


def test_scopes_from_compiled_text():
    """Each instruction's ``op_name`` as the compiled text gives it, the
    phases as ``jax.named_scope`` left them."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("fl.local"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("fl.eq3"):
            return (y * y).sum()

    text = jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()
    scopes = spantrace.scopes_from_hlo(text)
    phases = {spantrace.phase_of(v) for v in scopes.values()}
    assert {"fl.local", "fl.eq3"} <= phases, scopes
    for name in scopes:
        assert re.search(r"%" + re.escape(name) + r" = ", text), name


def test_extract_is_tracereduce_extract_plus_program_spans(tmp_path):
    """One load gives ``tracereduce.extract``'s dict, the program's spans
    with their stats, and a module list for each device plane."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1)
    x = f(jnp.ones(4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                with jax.profiler.TraceAnnotation("fl.scan", start_round=i,
                                                  rounds=1):
                    x = f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = tracereduce.find_xplane(str(tmp_path))
    old, new = tracereduce.extract(path), spantrace.extract(path)
    assert new["device"] == old["device"]
    assert [h for h in new["host"] if h[0].startswith("bench.")] \
        == old["host"]
    assert len(old["host"]) == 3
    assert [h[3] for h in new["host"] if h[0] == "fl.scan"] == \
        [{"start_round": i, "rounds": 1} for i in range(3)]
    assert new["modules"].keys() == new["device"].keys()
