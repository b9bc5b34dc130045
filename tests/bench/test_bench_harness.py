"""Tests of the on-chip benchmark under ``bench/``, on the CPU at small sizes.

- ``BENCHMARK.json`` finds every file it names: configuration, traffic,
  limits, and one reader per metric;
- the trace reducer on a small trace recorded on a TPU v5e
  (``bench/fixtures/trace_small.json``): busy union, idle share, kernel
  time by name, window;
- the FLOP functions against hand counts, and kernel bytes from unpadded
  shapes;
- the harness refuses a non-TPU platform, an unknown device kind, and a
  checkout that holds only the benchmark;
- each cell's plain reference against the program at small sizes, the
  control (one precision down) failing the cell's limits, and a run with
  each planted fault coming out not correct.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, run, tracereduce  # noqa: E402
from bench.costs import kernels, lora_transformer, vgg9  # noqa: E402

SPEC_PATH = os.path.join(ROOT, "BENCHMARK.json")


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return load("BENCHMARK.json")


# ----------------------------------------------------------------------
# the benchmark's own files
# ----------------------------------------------------------------------
def test_every_named_file_exists(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        model = load(c["file"])["model"]
        assert os.path.isfile(os.path.join(ROOT, "bench", "models",
                                           model + ".py"))
    for w in spec["workloads"]:
        assert w["config"] in configs
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "traffic", w["traffic"] + ".json"))
        limits = check.load_limits(os.path.join(ROOT, "bench"), w["name"])
        assert limits["uplink"] == 0.0
        assert any(n.startswith("delta") for n in limits)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py")), m["name"]


def test_every_cell_has_its_metrics(spec):
    for w in spec["workloads"]:
        e2e = run.cell_metrics(spec, w["name"], "end_to_end")
        per_layer = run.cell_metrics(spec, w["name"], "per_layer")
        assert "setup_s" in [m["name"] for m in e2e]
        assert len(e2e) >= 2 and per_layer


@pytest.mark.parametrize("name", ["vgg9-cifar10",
                                  "deepseek-coder-33b-4of62"])
def test_config_files_state_their_cut(spec, name):
    entry = {c["name"]: c for c in spec["configs"]}[name]
    cfg = load(entry["file"])
    assert cfg["reduced"] == entry["reduced"]
    for key in ("source", "precision", "deployment", "assumed"):
        assert cfg[key]


# ----------------------------------------------------------------------
# trace reduction
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    return load("bench/fixtures/trace_small.json")


def _brute_busy(events, lo, hi, step=1000.0):
    """Busy nanoseconds by sampling a fine time grid (independent of the
    interval union)."""
    import numpy as np
    t = np.arange(lo, hi, step)
    busy = np.zeros_like(t, dtype=bool)
    for _, _, s, d in events:
        busy |= (t >= s) & (t < s + d)
    return busy.sum() * step


def test_reducer_on_recorded_trace(recorded):
    out = tracereduce.reduce(recorded)
    lo, hi = tracereduce.window_of(recorded)
    (plane, evs), = [(p, e) for p, e in recorded["device"].items() if e]
    brute = _brute_busy(evs, lo, hi) * 1e-9
    assert out["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert out["busy_s"] == pytest.approx(brute, rel=1e-2)
    assert 0.0 < out["busy_s"] < out["window_s"]
    want = sum(min(s + d, hi) - max(s, lo) for _, lab, s, d in evs
               if "sqdiff" in lab and s + d > lo and s < hi) * 1e-9
    assert tracereduce.kernel_seconds(out, "sqdiff") == pytest.approx(want)
    assert want > 0
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    gap_total = sum(v for k, v in out["idle_gaps"] if k.startswith("all"))
    assert gap_total == pytest.approx(out["window_s"] - out["busy_s"],
                                      rel=1e-6)


def test_reducer_union_and_labels():
    trace = {"device": {"/device:TPU:0": [
        ["a", "a", 0.0, 10.0], ["b", "b sqdiff", 5.0, 10.0],
        ["c", "c", 40.0, 10.0]]},
        "host": [["bench.call", 0.0, 30.0], ["bench.call", 35.0, 65.0]]}
    out = tracereduce.reduce(trace)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(25e-9)
    assert tracereduce.kernel_seconds(out, "sqdiff") == pytest.approx(10e-9)
    assert tracereduce.kernel_seconds(out, "uplink_ef") == 0.0
    labels = dict(out["idle_gaps"])
    assert labels["all gaps: inside run_training_scan call"] == \
        pytest.approx(70e-9)
    assert labels["all gaps: host between calls"] == pytest.approx(5e-9)


def test_self_times_of_nested_loops():
    # an outer loop holding an inner loop whose last op ends 1 ns after
    # both loops, then a sibling op after the outer loop
    evs = [["outer", "", 0.0, 100.0], ["inner", "", 10.0, 80.0],
           ["a", "", 10.0, 30.0], ["b", "", 50.0, 41.0],
           ["c", "", 120.0, 5.0]]
    own = dict(zip([e[0] for e in evs], tracereduce.self_times(evs)))
    assert own == {"outer": 20.0, "inner": 10.0, "a": 30.0, "b": 41.0,
                   "c": 5.0}


# ----------------------------------------------------------------------
# costs
# ----------------------------------------------------------------------
def test_vgg9_forward_flops_match_hand_count():
    cfg = load("bench/configs/vgg9-cifar10.json")
    assert vgg9.forward_flops_per_image(cfg) == pytest.approx(418.816e6)
    tr = load("bench/traffic/fedldf_iid.json")
    per_image = 3 * 418.816e6 - 2 * 32 * 32 * 9 * 3 * 64
    assert vgg9.useful_flops_per_round(cfg, tr) == pytest.approx(
        20 * 32 * per_image)


def test_lora_transformer_flops_match_hand_count():
    cfg = {"hidden_size": 8, "intermediate_size": 16,
           "num_hidden_layers": 2, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 10,
           "lora": {"rank": 2, "targets": {"attn": ["wq"],
                                           "mlp": ["w_down"]}}}
    base = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16) + 8 * 10
    adapters = 2 * (2 * (8 + 8) + 2 * (16 + 8))
    t = 6
    attn = 2 * 2 * 2 * 4 * t * t
    want = 2 * base * t + 2 * base * t + 6 * adapters * t + 3 * attn
    assert lora_transformer.base_params(cfg) == base
    assert lora_transformer.adapter_params(cfg) == adapters
    assert lora_transformer.train_flops_per_sequence(cfg, t) == want


def test_kernel_bytes_come_from_unpadded_shapes():
    import numpy as np
    tree = {"a": {"w": np.zeros((3, 5), np.float32)},
            "blocks": {"x": np.zeros((4, 7), np.float16)}}
    k = 3
    got = kernels.sqdiff_rowsum(tree, k, "vmap")
    want = (k + 1) * 15 * 4 + k * 1 * 4 + (k + 1) * 28 * 2 + k * 4 * 4
    assert got["bytes"] == want
    scan = kernels.sqdiff_rowsum(tree, k, "scan")
    assert scan["bytes"] == 2 * k * 15 * 4 + k * 4 + 2 * k * 28 * 2 + 16 * k
    ef = kernels.fused_uplink_ef({"a": {"w": np.zeros((2, 3), np.float32)}},
                                 k)
    assert ef["bytes"] == k * 6 * 13 + 6 * 4 + 3 * k * 4


def test_roofline_metrics_read_from_their_own_files(recorded):
    """A kernel's roofline reader takes its counts from the run's
    trainable shapes and traffic and its time from the trace by label,
    so a new kernel needs no edit of the harness; a trace without the
    kernel reads nothing."""
    import jax
    import numpy as np
    tree = {"a": {"w": jax.ShapeDtypeStruct((3, 5), np.float32)}}
    traffic = {"clients_per_round": 2, "mode": "vmap"}
    ctx = {"trace": tracereduce.reduce(recorded), "trace_rounds": 4,
           "trainable": tree, "traffic": traffic,
           "peak": run.peak_of("TPU v5 lite", os.path.join(
               ROOT, "bench", "peaks.json"))}
    cost = kernels.sqdiff_rowsum(tree, 2, "vmap")
    secs = tracereduce.kernel_seconds(ctx["trace"], "sqdiff")
    want = 100.0 * 4 * max(cost["flops"] / 197e12, cost["bytes"] / 819e9) \
        / secs
    assert run.read_metric("sqdiff_rowsum_roofline", ctx) == \
        pytest.approx(want)
    assert run.read_metric("fused_uplink_ef_roofline", ctx) is None


# ----------------------------------------------------------------------
# refusals
# ----------------------------------------------------------------------
def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        run.peak_of("TPU v99", os.path.join(ROOT, "bench", "peaks.json"))
    assert run.peak_of("TPU v5 lite", os.path.join(
        ROOT, "bench", "peaks.json"))["hbm_bytes_per_s"] == 819e9


def test_non_tpu_platform_is_refused(capsys):
    with pytest.raises(SystemExit) as e:
        run.device_info(1, os.path.join(ROOT, "bench", "peaks.json"))
    assert e.value.code == 1
    assert capsys.readouterr().out == ""


def test_benchmark_alone_exits_without_result(tmp_path):
    shutil.copy(SPEC_PATH, tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg9.fedldf.iid",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# ----------------------------------------------------------------------
# the check, end to end at small sizes
# ----------------------------------------------------------------------
def small_cell(workload: str):
    spec, entry, config, traffic = run.load_spec(workload)
    if config["model"] == "vgg9":
        config = dict(config, channels=[8, 8, 16, 16], pool_after=[1, 3])
        traffic = dict(traffic, num_clients=6, clients_per_round=4,
                       top_n=2, batch_per_client=8,
                       dataset=dict(traffic["dataset"], num_samples=240))
    else:
        config = dict(config, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=256,
                      lora=dict(config["lora"], rank=4))
        traffic = dict(traffic, num_clients=4, clients_per_round=2,
                       top_n=1, dataset=dict(traffic["dataset"],
                                             sequences_per_client=4,
                                             seq_len=33))
    return spec, entry, config, traffic


def cell_numbers(workload: str, seed: int, control: bool,
                 tie_margin: float = 0.0):
    import importlib
    _, _, config, traffic = small_cell(workload)
    model = importlib.import_module("bench.models." + config["model"])
    cell = model.Workload(config, traffic, seed)
    cell.build()
    cell.first_steps()
    cell.release()
    prog = cell.reference_record(control=True) if control else cell.prog
    ref = cell.reference_record(observed=prog["observed"],
                                tie_margin=tie_margin)
    return check.numbers(prog, ref)


def limits_of(workload: str) -> dict:
    return check.load_limits(os.path.join(ROOT, "bench"), workload)


def workloads():
    with open(SPEC_PATH) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", workloads())
def test_reference_agrees_with_program(workload):
    values = cell_numbers(workload, 3, control=False)
    ok, table = check.judge(values, limits_of(workload))
    assert ok, table
    assert values["uplink"] == 0.0


@pytest.mark.parametrize("workload", workloads())
def test_control_fails_the_check(workload):
    values = cell_numbers(workload, 3, control=True)
    ok, table = check.judge(values, limits_of(workload))
    assert not ok, table


def small_run(workload: str, seed: int, fault=None) -> dict:
    """One whole run of the harness at a small size on the CPU, with the
    timed path broken underneath when ``fault`` names a fault."""
    import argparse
    spec, entry, config, traffic = small_cell(workload)
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.2,
                              trace=0)
    return run.run_cell(args, spec, entry, config, traffic, fault=fault,
                        check_device=False)


@pytest.mark.parametrize("workload", workloads())
def test_sound_run_is_correct(workload):
    out = small_run(workload, 2 ** 33 + 5)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0


@pytest.mark.parametrize("workload", workloads())
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(fault, workload):
    out = small_run(workload, 5, fault)
    assert out["correct"] is False, out["checks"]


# ----------------------------------------------------------------------
# near ties
# ----------------------------------------------------------------------
def test_swap_takes_the_next_client():
    import jax
    import jax.numpy as jnp
    from bench import fedref
    glob = {"a": {"w": jnp.zeros((2,))}, "b": {"w": jnp.zeros((1,))}}
    # divergences by client: unit a 3, 2, 1; unit b 4, 0.5, 5
    locals_ = {"a": {"w": jnp.array([[3.0, 0], [2.0, 0], [1.0, 0]])},
               "b": {"w": jnp.array([[4.0], [0.5], [5.0]])}}
    sizes = jnp.ones((3,), jnp.int32)
    new, _, sel, margins = fedref.aggregate(jax, glob, locals_, sizes, 1)
    assert sel.tolist() == [[1, 0], [0, 0], [0, 1]]
    assert margins.tolist() == pytest.approx([1 / 3, 0.2])
    swapped, _, sel2, _ = fedref.aggregate(
        jax, glob, locals_, sizes, 1, swap=jnp.array([False, True]))
    assert sel2.tolist() == [[1, 1], [0, 0], [0, 0]]
    picked = fedref.pick_units(new, swapped, jnp.array([0.0, 1.0]), jnp)
    assert picked["a"]["w"].tolist() == [3.0, 0.0]
    assert picked["b"]["w"].tolist() == [4.0]
    assert fedref.unit_distances(new, swapped, jnp).tolist() == \
        pytest.approx([0.0, 1.0])


def test_floor_on_units_compared():
    """A norm that has lost more than half of the units reads infinite."""
    rows = {"blocks/w": [1.0, 2.0, 3.0, 4.0]}
    ref = {"loss": [1.0, 1.0, 1.0], "uplink": [1.0] * 3, "delta1": rows,
           "delta3": rows, "units": [f"blocks/{i}" for i in range(4)],
           "delta1_rounds": 1, "resolved": [],
           "excluded": {"blocks/0": 1, "blocks/1": 2}}
    prog = dict(ref, loss=[1.0, 1.0, 2.0])
    out = check.numbers(prog, ref)
    assert out["delta1"] == 0.0 and out["delta3"] == 0.0
    assert out["loss"] == 0.0 and out["excluded_units"] == 2
    ref["excluded"]["blocks/2"] = 2
    out = check.numbers(prog, ref)
    assert out["delta1"] == 0.0 and out["delta3"] == float("inf")


@pytest.mark.parametrize("workload", ["vgg9.fedldf.iid",
                                      "coder33b.lora.fedldf"])
def test_ties_resolved_where_observed(workload):
    """With every unit counted as a near tie, the reference follows the
    program's side after the first and third calls, so the first call
    still agrees; the unobserved second call leaves out every unit, so
    the three-call norms fail by the floor."""
    values = cell_numbers(workload, 3, control=False, tie_margin=2.0)
    limits = limits_of(workload)
    assert values["resolved_ties"] > 0
    assert values["delta1_median"] <= limits["delta1_median"]
    assert values["delta3_median"] == float("inf")
