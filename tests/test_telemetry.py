"""Round telemetry subsystem: taps, ledger, sink, profiling counters.

Pins the subsystem's three contracts:

- **zero-cost disabled path** — with ``FLConfig.telemetry=None`` (the
  default) the per-round metrics carry no tap keys and fixed-seed
  trajectories are bit-identical to telemetry-enabled runs across the
  host-vmap, jitted-scan, and mesh-sharded drivers (taps are pure extra
  outputs, never inputs);
- **driver-independent ledger schema** — both drivers emit round/eval
  records with exactly the same key set, absolute contiguous round
  indices, and a resumed (save → load → continue) run's ledger matches an
  uninterrupted run's indices gap-free, for a stateful (fedlama) and a
  stateless (fedavg) strategy;
- **no retraces across identical runs** — the compiled-callable cache
  reports zero new builds for a repeated identical ``run_training_scan``,
  and host-only telemetry knobs (ledger path, run id) don't change the
  cache key.
"""
import dataclasses
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_server_state, save_server_state
from repro.core.units import UnitMap
from repro.data import FederatedData, iid_partition, make_image_dataset
from repro.federated import (FLConfig, TelemetryConfig, build_round_fn,
                             run_training, run_training_scan)
from repro.federated.server import _trace_flcfg
from repro.launch import monitor
from repro.launch.mesh import make_client_mesh
from repro.telemetry import (LEDGER_SCHEMA, ProgressSink, RoundLedger,
                             read_ledger, split_runs)
from repro.telemetry.profiling import (engine_cache_stats,
                                       reset_engine_cache_stats)

N_CLIENTS, K = 8, 4

needs_devices = [
    pytest.param(d, marks=pytest.mark.skipif(
        len(jax.devices()) < d,
        reason=f"needs {d} devices; set REPRO_TEST_DEVICES=8"))
    for d in (2,)
]


def _mlp_params(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    return {
        "l1": {"w": jax.random.normal(ks[0], (3072, 16)) * 0.02,
               "b": jnp.zeros((16,))},
        "head": {"w": jax.random.normal(ks[1], (16, 10)) * 0.1,
                 "b": jnp.zeros((10,))},
    }


def _loss(params, batch):
    x = batch["images"].reshape(batch["images"].shape[0], -1)
    h = jax.nn.relu(x @ params["l1"]["w"] + params["l1"]["b"])
    logits = h @ params["head"]["w"] + params["head"]["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, batch["labels"][:, None],
                                axis=-1).mean()


@pytest.fixture(scope="module")
def task():
    train, _ = make_image_dataset(num_train=320, num_test=16, seed=1)
    parts = iid_partition(train.ys, N_CLIENTS, seed=0)
    data = FederatedData(train.xs, train.ys, parts)
    return _mlp_params(), data


def _cfg(algo="fedldf", mode="vmap", **kw):
    return FLConfig(algo=algo, num_clients=N_CLIENTS, clients_per_round=K,
                    top_n=2, mode=mode, batch_per_client=8, **kw)


def _assert_bit_identical(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ======================================================================
# TelemetryConfig
# ======================================================================
def test_config_validation():
    with pytest.raises(ValueError, match="verbosity"):
        TelemetryConfig(verbosity="loud")
    with pytest.raises(ValueError, match="profile_rounds"):
        TelemetryConfig(profile_rounds=(5, 2))
    with pytest.raises(TypeError, match="telemetry"):
        _cfg(telemetry="yes")
    t = TelemetryConfig(profile_rounds=(1.0, 3.0))
    assert t.profile_rounds == (1, 3)
    assert isinstance(hash(t), int)    # jit-cache key material


def test_trace_key_drops_host_only_fields():
    a = _cfg(telemetry=TelemetryConfig(ledger_path="/tmp/a.jsonl",
                                       run_id="a", verbosity="quiet",
                                       profile_rounds=(0, 1)))
    b = _cfg(telemetry=TelemetryConfig(ledger_path="/tmp/b.jsonl",
                                       run_id="b", verbosity="human"))
    assert _trace_flcfg(a) == _trace_flcfg(b)     # no retrace between them
    c = _cfg(telemetry=TelemetryConfig(taps=False))
    assert _trace_flcfg(a) != _trace_flcfg(c)     # taps change the graph
    assert _trace_flcfg(_cfg()) == _cfg()          # None passes through


# ======================================================================
# Zero-cost disabled path / taps structure
# ======================================================================
def test_metrics_tap_keys_follow_config(task):
    params, _ = task
    batch = {"images": jnp.zeros((K, 8, 32, 32, 3)),
             "labels": jnp.zeros((K, 8), jnp.int32)}
    fn = build_round_fn(_loss, UnitMap.build(params), _cfg())
    _, metrics = fn(params, batch, jnp.ones((K,)), jax.random.PRNGKey(0))
    assert set(metrics) == {"loss", "comm", "selection"}

    fn_t = build_round_fn(_loss, UnitMap.build(params),
                          _cfg(telemetry=TelemetryConfig()))
    _, metrics_t = fn_t(params, batch, jnp.ones((K,)),
                        jax.random.PRNGKey(0))
    assert set(metrics_t) == {"loss", "comm", "selection", "taps"}
    assert {"div_mean", "div_max", "sel_count"} <= set(metrics_t["taps"])
    assert metrics_t["taps"]["div_mean"].shape == \
        (UnitMap.build(params).num_units,)


@pytest.mark.parametrize("algo", ["fedldf", "fedlama"])
def test_bit_identical_trajectories_host_and_scan(task, tmp_path, algo):
    params, data = task
    tele = TelemetryConfig(ledger_path=str(tmp_path / "l.jsonl"))
    for driver in ("host", "scan", "scan_mode"):
        if driver == "host":
            p0, _ = run_training(params, _loss, data, _cfg(algo), rounds=3,
                                 seed=0, sampler="jax")
            p1, _ = run_training(params, _loss, data,
                                 _cfg(algo, telemetry=tele), rounds=3,
                                 seed=0, sampler="jax")
        elif driver == "scan":
            p0, _ = run_training_scan(params, _loss, data, _cfg(algo),
                                      rounds=3, seed=0)
            p1, _ = run_training_scan(params, _loss, data,
                                      _cfg(algo, telemetry=tele),
                                      rounds=3, seed=0)
        else:
            p0, _ = run_training(params, _loss, data,
                                 _cfg(algo, mode="scan"), rounds=3,
                                 seed=0, sampler="jax")
            p1, _ = run_training(params, _loss, data,
                                 _cfg(algo, mode="scan", telemetry=tele),
                                 rounds=3, seed=0, sampler="jax")
        _assert_bit_identical(p0, p1)


@pytest.mark.parametrize("d", needs_devices)
def test_mesh_taps_bit_identical_and_residual_norm_matches(task, tmp_path,
                                                           d):
    """Mesh-sharded round with EF residual state: telemetry leaves the
    trajectory bit-identical, and the psum'd client-state norm tap equals
    the unsharded engine's value."""
    params, data = task
    mesh = make_client_mesh(d)
    lp_mesh, lp_flat = str(tmp_path / "mesh.jsonl"), str(tmp_path / "f.jsonl")
    kw = dict(quantize_bits=8, error_feedback=True)
    p0, _ = run_training(params, _loss, data, _cfg(mesh=mesh, **kw),
                         rounds=3, seed=0, sampler="jax")
    p1, _ = run_training(
        params, _loss, data,
        _cfg(mesh=mesh, telemetry=TelemetryConfig(ledger_path=lp_mesh),
             **kw), rounds=3, seed=0, sampler="jax")
    _assert_bit_identical(p0, p1)
    run_training(params, _loss, data,
                 _cfg(telemetry=TelemetryConfig(ledger_path=lp_flat), **kw),
                 rounds=3, seed=0, sampler="jax")
    rm = split_runs(read_ledger(lp_mesh))[0]["rounds"]
    rf = split_runs(read_ledger(lp_flat))[0]["rounds"]
    for a, b in zip(rm, rf):
        np.testing.assert_allclose(a["taps"]["state_residual_norm"],
                                   b["taps"]["state_residual_norm"],
                                   rtol=1e-4)


# ======================================================================
# Ledger: cross-driver schema equality + resume contiguity
# ======================================================================
def test_cross_driver_ledger_schema_equality(task, tmp_path):
    params, data = task
    eval_fn = lambda p: 0.5   # noqa: E731
    paths = {}
    for driver, runner in (("host", run_training),
                           ("scan", run_training_scan)):
        lp = str(tmp_path / f"{driver}.jsonl")
        kw = {"sampler": "jax"} if driver == "host" else {}
        runner(params, _loss, data,
               _cfg(telemetry=TelemetryConfig(ledger_path=lp)),
               rounds=5, eval_fn=eval_fn, eval_every=2, seed=0, **kw)
        paths[driver] = lp
    segs = {d: split_runs(read_ledger(p))[0] for d, p in paths.items()}
    # identical record key sets, tap key sets, and round indices
    assert [sorted(r) for r in segs["host"]["rounds"]] == \
        [sorted(r) for r in segs["scan"]["rounds"]]
    assert [sorted(r["taps"]) for r in segs["host"]["rounds"]] == \
        [sorted(r["taps"]) for r in segs["scan"]["rounds"]]
    assert [r["round"] for r in segs["host"]["rounds"]] == \
        [r["round"] for r in segs["scan"]["rounds"]] == list(range(5))
    # eval cadence (t % eval_every == 0 or last round) matches too
    assert [e["round"] for e in segs["host"]["evals"]] == \
        [e["round"] for e in segs["scan"]["evals"]] == [0, 2, 4]
    assert [sorted(e) for e in segs["host"]["evals"]] == \
        [sorted(e) for e in segs["scan"]["evals"]]
    # and the same comm-profile fields round for round
    assert [sorted(r["comm"]) for r in segs["host"]["rounds"]] == \
        [sorted(r["comm"]) for r in segs["scan"]["rounds"]]


@pytest.mark.parametrize("driver", ["host", "scan"])
def test_run_meta_records_eq3_in_place_share(task, tmp_path, driver):
    """The run header carries the share of Eq. 3's bytes the divergence
    kernel reads in place: here l1.w (3072, 128) in place, the vectors
    and the (128, 10) head folded."""
    _, data = task
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    params = {"l1": {"w": jax.random.normal(ks[0], (3072, 128)) * 0.02,
                     "b": jnp.zeros((128,))},
              "head": {"w": jax.random.normal(ks[1], (128, 10)) * 0.1,
                       "b": jnp.zeros((10,))}}
    lp = str(tmp_path / "ledger.jsonl")
    runner = run_training if driver == "host" else run_training_scan
    kw = {"sampler": "jax"} if driver == "host" else {}
    runner(params, _loss, data,
           _cfg(telemetry=TelemetryConfig(ledger_path=lp)),
           rounds=1, seed=0, **kw)
    meta = split_runs(read_ledger(lp))[0]["meta"]
    # bytes per client as read: 3072·128 in place; 128, 10·128 and 128
    # (the 10 head biases padded to a lane) folded
    want = 3072 * 128 / (3072 * 128 + 128 + 10 * 128 + 128)
    assert meta["eq3_in_place_share"] == pytest.approx(want)


@pytest.mark.parametrize("algo,mode,want", [("fedldf", "scan", 1),
                                             ("fedlama", "scan", 2),
                                             ("fedldf", "vmap", None)])
def test_run_meta_records_scan_client_passes(task, tmp_path, algo, mode,
                                             want):
    """The run header says how many times the scan round trains each
    client: once for FedLDF (each unit's top-n kept as clients train),
    twice for FedLAMA (Eq. 5 recomputes each local), null in vmap mode."""
    params, data = task
    lp = str(tmp_path / "ledger.jsonl")
    run_training_scan(params, _loss, data,
                      _cfg(algo, mode=mode,
                           telemetry=TelemetryConfig(ledger_path=lp)),
                      rounds=1, seed=0)
    meta = split_runs(read_ledger(lp))[0]["meta"]
    assert meta["scan_client_passes"] == want


@pytest.mark.parametrize("algo", ["fedlama", "fedavg"])
@pytest.mark.parametrize("driver", ["host", "scan"])
def test_ledger_resume_contiguous(task, tmp_path, algo, driver):
    """save -> load -> continue appends a ledger whose round indices are
    gap-free and identical to an uninterrupted run's."""
    params0, data = task

    def go(params, cfg, rounds, start_round=0, server_state=None):
        if driver == "host":
            return run_training(params, _loss, data, cfg, rounds=rounds,
                                seed=0, sampler="jax",
                                start_round=start_round,
                                server_state=server_state)
        return run_training_scan(params, _loss, data, cfg, rounds=rounds,
                                 seed=0, start_round=start_round,
                                 server_state=server_state)

    lp_full = str(tmp_path / "full.jsonl")
    pf, _ = go(params0,
               _cfg(algo, telemetry=TelemetryConfig(ledger_path=lp_full)),
               rounds=6)
    lp_res = str(tmp_path / "resumed.jsonl")
    cfg_res = _cfg(algo, telemetry=TelemetryConfig(ledger_path=lp_res))
    p1, log1 = go(params0, cfg_res, rounds=3)
    ckpt = str(tmp_path / "server.npz")
    save_server_state(ckpt, p1, log1.final_state)
    p_loaded, state_loaded = load_server_state(ckpt)
    p2, _ = go(p_loaded, cfg_res, rounds=3, start_round=3,
               server_state=state_loaded)
    _assert_bit_identical(pf, p2)

    full = split_runs(read_ledger(lp_full))
    res = split_runs(read_ledger(lp_res))
    assert len(full) == 1 and len(res) == 2    # one file, two segments
    full_rounds = [r["round"] for r in full[0]["rounds"]]
    res_rounds = [r["round"] for seg in res for r in seg["rounds"]]
    assert res_rounds == full_rounds == list(range(6))   # gap-free
    assert res[1]["meta"]["start_round"] == 3
    full_losses = [r["loss"] for r in full[0]["rounds"]]
    res_losses = [r["loss"] for seg in res for r in seg["rounds"]]
    np.testing.assert_array_equal(full_losses, res_losses)


def test_reader_skips_corrupt_and_newer_schema(tmp_path):
    lp = str(tmp_path / "l.jsonl")
    with RoundLedger(lp, meta={"run_id": "x"}) as led:
        led.round(0, 1.0, {"uplink_total": 1.0, "fedavg_uplink": 2.0}, 1.0)
    with open(lp, "a") as f:
        f.write("{torn json\n")
        f.write(json.dumps({"schema": LEDGER_SCHEMA + 1,
                            "kind": "round", "round": 9}) + "\n")
    recs = read_ledger(lp)
    assert [r["kind"] for r in recs] == ["run", "round"]
    # headerless files still split into a meta=None segment
    segs = split_runs([{"kind": "round", "round": 0}])
    assert len(segs) == 1 and segs[0]["meta"] is None


# ======================================================================
# Progress sink (verbosity satellite)
# ======================================================================
def test_sink_modes():
    buf = io.StringIO()
    ProgressSink("human", stream=buf).round(7, 0.5, test_error=0.25,
                                            uplink_bytes=2e6)
    ProgressSink("human", stream=buf).round(7, 0.5)
    assert buf.getvalue() == ("round    7 loss 0.5000 test_err 0.2500 "
                              "uplink 2.0MB\nround    7 loss 0.5000\n")
    buf = io.StringIO()
    ProgressSink("structured", stream=buf).round(7, 0.5, test_error=0.25)
    rec = json.loads(buf.getvalue())
    assert rec == {"kind": "progress", "round": 7, "loss": 0.5,
                   "test_error": 0.25}
    buf = io.StringIO()
    sink = ProgressSink("quiet", stream=buf)
    sink.round(7, 0.5, test_error=0.25)
    assert buf.getvalue() == "" and not sink.enabled
    # resolution: explicit verbosity beats the driver's verbose flag
    assert ProgressSink.for_run(None, True).mode == "human"
    assert ProgressSink.for_run(None, False).mode == "quiet"
    assert ProgressSink.for_run(TelemetryConfig(verbosity="structured"),
                                False).mode == "structured"
    assert ProgressSink.for_run(TelemetryConfig(verbosity="quiet"),
                                True).mode == "quiet"


def test_verbose_output_format_unchanged(task, capsys):
    """The legacy verbose=True one-liners survive the sink refactor
    byte-for-byte (humans grep these)."""
    params, data = task
    run_training(params, _loss, data, _cfg(), rounds=1,
                 eval_fn=lambda p: 0.25, eval_every=1, seed=0,
                 sampler="jax", verbose=True)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith("round    0 loss ")
    assert "test_err 0.2500 uplink " in line and line.endswith("MB")


# ======================================================================
# Retrace counters (regression satellite)
# ======================================================================
def test_scan_rerun_zero_recompiles(task, tmp_path):
    params, data = task
    cfg = _cfg(telemetry=TelemetryConfig(
        ledger_path=str(tmp_path / "a.jsonl")))
    run_training_scan(params, _loss, data, cfg, rounds=2, seed=0)
    reset_engine_cache_stats()
    run_training_scan(params, _loss, data, cfg, rounds=2, seed=0)
    # a config differing only in host-side fields must also hit the cache
    cfg2 = dataclasses.replace(cfg, telemetry=TelemetryConfig(
        ledger_path=str(tmp_path / "b.jsonl"), run_id="other"))
    run_training_scan(params, _loss, data, cfg2, rounds=2, seed=0)
    stats = engine_cache_stats()
    assert stats.get("block_builds", 0) == 0, stats
    assert stats.get("block_hits", 0) == 2, stats


# ======================================================================
# Monitor (consumer smoke)
# ======================================================================
def test_monitor_renders_ledger(task, tmp_path):
    params, data = task
    lp = str(tmp_path / "m.jsonl")
    run_training(params, _loss, data,
                 _cfg("fedlama",
                      telemetry=TelemetryConfig(ledger_path=lp,
                                                run_id="mon")),
                 rounds=4, eval_fn=lambda p: 0.5, eval_every=2, seed=0,
                 sampler="jax")
    buf = io.StringIO()
    assert monitor.render(lp, out=buf) == 1
    text = buf.getvalue()
    assert "run mon" in text
    assert "per-layer mean divergence" in text
    assert "per-layer uploads" in text
    assert "state_interval" in text            # fedlama global-state tap
    assert "bytes/round" in text and "eval @ round" in text
    # sparkline/binning helpers are total functions on edge inputs
    assert monitor.sparkline([]) == ""
    assert len(monitor.bin_series(np.arange(100), 10)) == 10
