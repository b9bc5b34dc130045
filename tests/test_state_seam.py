"""Cross-round strategy state seam.

Covers the seam itself (a toy rotating-selection strategy whose trajectory
depends on its state must agree across the host-vmap, jitted-scan, and
mesh-sharded drivers; stateless strategies must pay zero carry overhead),
the EF residual store re-expressed as declared client state, server-state
checkpoint round-trips (save → load → continue bit-identically), the
scan driver's first carry (one copy program that leaves the caller's
params and state live, on one device and on a mesh; one-round calls equal
one run), and the FedLAMA proof strategy (round-0 full sync, interval
adaptation, driver agreement, uplink below FedAvg)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_server_state, save_server_state
from repro.core.wire import CompressionConfig
from repro.data import FederatedData, iid_partition, make_image_dataset
from repro.federated import (FLConfig, FLStrategy, build_round_fn,
                             make_strategy, register_strategy, run_training,
                             run_training_scan, unregister_strategy)
from repro.launch.mesh import make_client_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_CLIENTS, K = 8, 4
STATELESS = ("fedldf", "fedavg", "random", "hdfl", "fedadp", "fedlp")

needs_devices = [
    pytest.param(d, marks=pytest.mark.skipif(
        len(jax.devices()) < d,
        reason=f"needs {d} devices; set REPRO_TEST_DEVICES=8"))
    for d in (1, 2)
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


def _assert_trees_equal(a, b, atol=0.0):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=atol)


# ----------------------------------------------------------------------
# The seam itself: a toy strategy whose selection depends on its state
# ----------------------------------------------------------------------
class RotatingClient(FLStrategy):
    """Round t: only participant slot (t mod K) uploads — the selection is
    a pure function of the cross-round counter, so any driver that drops,
    duplicates, or reorders a state update changes the whole trajectory."""

    def init_state(self, params, num_clients, mesh=None):
        return {"global": {"rounds": jnp.float32(0.0),
                           "sel_mass": jnp.float32(0.0)}}

    def select(self, divs, key, k, u, n):
        raise NotImplementedError("state-driven; engines use "
                                  "select_with_state")

    def select_with_state(self, state, divs, key, k, u, n):
        t = state["global"]["rounds"].astype(jnp.int32)
        row = (jnp.arange(k) == t % k).astype(jnp.float32)
        return jnp.broadcast_to(row[:, None], (k, u))

    def update_state(self, state, selection, divs, umap, key=None):
        g = state["global"]
        return {**state, "global": {
            "rounds": g["rounds"] + 1.0,
            "sel_mass": g["sel_mass"] + selection.sum()}}


@pytest.fixture()
def rotating():
    register_strategy("rotating")(RotatingClient)
    yield "rotating"
    unregister_strategy("rotating")


def test_state_trajectory_same_across_drivers(task, rotating):
    """vmap host driver, scan engine, and scan-client mode all observe the
    same state trajectory (and hence the same params)."""
    params, data = task
    rounds = 5
    ph, lh = run_training(params, _loss, data, _cfg(rotating), rounds=rounds,
                          seed=0, sampler="jax")
    ps, ls = run_training_scan(params, _loss, data, _cfg(rotating),
                               rounds=rounds, seed=0)
    pm, lm = run_training(params, _loss, data, _cfg(rotating, mode="scan"),
                          rounds=rounds, seed=0, sampler="jax")
    for log in (lh, ls, lm):
        g = jax.tree.map(float, log.final_state)["global"]
        assert g["rounds"] == rounds
    _assert_trees_equal(lh.final_state, ls.final_state)
    _assert_trees_equal(lh.final_state, lm.final_state)
    _assert_trees_equal(ph, ps, atol=2e-5)
    _assert_trees_equal(ph, pm, atol=2e-5)


@pytest.mark.parametrize("mesh_size", needs_devices)
def test_state_trajectory_under_mesh(task, rotating, mesh_size):
    """The shard_map driver threads the same state trajectory: global
    state enters replicated, leaves replicated, and the resulting
    trajectory matches the unsharded engine."""
    params, data = task
    p0, l0 = run_training_scan(params, _loss, data, _cfg(rotating),
                               rounds=4, seed=3)
    p1, l1 = run_training_scan(params, _loss, data,
                               _cfg(rotating, mesh=make_client_mesh(mesh_size)),
                               rounds=4, seed=3)
    _assert_trees_equal(l0.final_state, l1.final_state)
    _assert_trees_equal(p0, p1, atol=2e-5)


# ----------------------------------------------------------------------
# Stateless strategies: zero carry overhead
# ----------------------------------------------------------------------
@pytest.mark.parametrize("algo", STATELESS)
def test_stateless_strategies_have_no_state(algo):
    params = _mlp_params()
    fl = FLConfig(algo=algo, num_clients=N_CLIENTS, clients_per_round=K,
                  top_n=2)
    assert make_strategy(fl).init_state(params, N_CLIENTS) is None


def test_stateless_round_metrics_carry_no_state(task):
    """The compiled round of a stateless strategy must not grow any state
    output (no new scan-carry leaves vs the pre-seam engine)."""
    from repro.core.units import UnitMap
    params, _ = task
    umap = UnitMap.build(params)
    k = K
    key = jax.random.PRNGKey(0)
    batch = {"images": jax.random.normal(key, (k, 8, 32, 32, 3)),
             "labels": jax.random.randint(key, (k, 8), 0, 10)}
    sizes = jnp.full((k,), 10.0)
    fl = _cfg("fedavg")
    _, metrics = jax.jit(build_round_fn(_loss, umap, fl))(params, batch,
                                                          sizes, key)
    assert "state" not in metrics and "residuals" not in metrics
    _, ls = run_training_scan(params, _loss,
                              FederatedData(
                                  *_tiny_data()), fl, rounds=1, seed=0)
    assert ls.final_state is None


def _tiny_data():
    train, _ = make_image_dataset(num_train=160, num_test=8, seed=1)
    return train.xs, train.ys, iid_partition(train.ys, N_CLIENTS, seed=0)


# ----------------------------------------------------------------------
# EF residual store as declared client state
# ----------------------------------------------------------------------
def test_ef_store_is_client_state(task):
    params, data = task
    fl = _cfg(quantize_bits=4, error_feedback=True)
    state = make_strategy(fl).init_state(params, N_CLIENTS)
    store = state["client"]["residual"]
    for leaf, row in zip(jax.tree.leaves(params), jax.tree.leaves(store)):
        assert row.shape == (N_CLIENTS,) + leaf.shape
        assert row.dtype == leaf.dtype
        assert float(jnp.abs(row).max()) == 0.0
    # ... and the driver threads it: after training, some rows are nonzero
    _, log = run_training_scan(params, _loss, data, fl, rounds=3, seed=0)
    final = log.final_state["client"]["residual"]
    assert max(float(jnp.abs(l).max()) for l in jax.tree.leaves(final)) > 0


# ----------------------------------------------------------------------
# Checkpoint round-trip + resume
# ----------------------------------------------------------------------
@pytest.mark.parametrize("algo,kw", [
    ("fedlama", {}),                                      # global state
    ("fedldf", dict(quantize_bits=4, error_feedback=True)),  # client state
    ("fedavg", {}),                                       # stateless
])
def test_save_load_continue_matches_uninterrupted(task, tmp_path, algo, kw):
    """3 rounds + save → load + 3 more rounds == 6 uninterrupted rounds,
    bit-identically (same driver, same device, same key schedule)."""
    params, data = task
    fl = _cfg(algo, **kw)
    p_full, l_full = run_training_scan(params, _loss, data, fl, rounds=6,
                                       seed=0)
    p_half, l_half = run_training_scan(params, _loss, data, fl, rounds=3,
                                       seed=0)
    path = str(tmp_path / "server.npz")
    save_server_state(path, p_half, l_half.final_state)
    p_loaded, state_loaded = load_server_state(path)
    _assert_trees_equal(p_loaded, p_half)
    p_res, l_res = run_training_scan(p_loaded, _loss, data, fl, rounds=3,
                                     seed=0, start_round=3,
                                     server_state=state_loaded)
    _assert_trees_equal(p_full, p_res)
    if l_full.final_state is None:
        assert l_res.final_state is None and state_loaded is None
    else:
        _assert_trees_equal(l_full.final_state, l_res.final_state)


def test_host_driver_resume(task, tmp_path):
    """The host-loop driver (jax sampler) supports the same resume seam."""
    params, data = task
    fl = _cfg("fedlama")
    p_full, _ = run_training(params, _loss, data, fl, rounds=4, seed=0,
                             sampler="jax")
    p_half, l_half = run_training(params, _loss, data, fl, rounds=2, seed=0,
                                  sampler="jax")
    path = str(tmp_path / "server.npz")
    save_server_state(path, p_half, l_half.final_state)
    p_loaded, state_loaded = load_server_state(path)
    p_res, _ = run_training(p_loaded, _loss, data, fl, rounds=2, seed=0,
                            sampler="jax", start_round=2,
                            server_state=state_loaded)
    _assert_trees_equal(p_full, p_res)


def test_save_load_stateless_round_trip(tmp_path):
    params = _mlp_params()
    path = str(tmp_path / "plain.npz")
    save_server_state(path, params)
    p2, state = load_server_state(path)
    _assert_trees_equal(params, p2)
    assert state is None


# ----------------------------------------------------------------------
# The scan driver's first carry: one program, the caller's buffers kept
# ----------------------------------------------------------------------
Q8EF = dict(compression=CompressionConfig(bits=8, error_feedback=True))


def _snapshot(tree):
    return jax.tree.map(np.array, tree)


def _assert_survived(tree, snap):
    """Every leaf of ``tree`` is still live and holds ``snap``'s values."""
    for leaf, old in zip(jax.tree.leaves(tree), jax.tree.leaves(snap)):
        assert not leaf.is_deleted()
        np.testing.assert_array_equal(np.asarray(leaf), old)


@pytest.mark.parametrize("kw", [{}, Q8EF],
                         ids=["fedldf", "q8ef_resumed"])
def test_scan_call_keeps_caller_buffers(task, kw):
    """``run_block`` donates its carry; the call copies first, so the
    caller's params and resumed server state are neither deleted nor
    changed, over a call of two eval blocks."""
    params, data = task
    fl = _cfg(**kw)
    state = None
    if kw:
        params, log = run_training_scan(params, _loss, data, fl, rounds=1,
                                        seed=0)
        state = log.final_state
        assert state is not None
    snap_p, snap_s = _snapshot(params), _snapshot(state)
    eval_fn = jax.jit(lambda p: jnp.float32(0.0))
    p_new, _ = run_training_scan(params, _loss, data, fl, rounds=2, seed=0,
                                 start_round=1, server_state=state,
                                 eval_fn=eval_fn, eval_every=1)
    _assert_survived(params, snap_p)
    _assert_survived(state, snap_s)
    assert any(not np.array_equal(np.asarray(a), b) for a, b in
               zip(jax.tree.leaves(p_new), jax.tree.leaves(snap_p)))


def test_scan_carry_copy_traces_once(task, monkeypatch):
    """Calls with the same tree structure reuse one carry-copy program:
    ``comm_acc_init`` runs only while ``_copy_carry`` traces."""
    from repro.core import comm as comm_mod
    from repro.federated import server
    params, data = task
    fl = _cfg(**Q8EF)
    traces = []
    init = comm_mod.comm_acc_init
    monkeypatch.setattr(comm_mod, "comm_acc_init",
                        lambda: traces.append(1) or init())
    server._copy_carry.clear_cache()
    p, state = params, None
    for t in range(4):
        p, log = run_training_scan(p, _loss, data, fl, rounds=1, seed=0,
                                   start_round=t, server_state=state)
        state = log.final_state
        # the fresh first call traces once, the resumed calls once more
        assert len(traces) == min(t + 1, 2), t


def _per_round_bytes(logs):
    """Integer uplink bytes of each round, from one or more calls' logs
    (``uplink_mb`` is cumulative within a call)."""
    out = []
    for log in logs:
        cum = np.rint(np.asarray(log.uplink_mb, np.float64) * 1e6)
        out.extend(np.diff(np.concatenate(([0.0], cum))).tolist())
    return out


@pytest.mark.parametrize("kw", [{}, Q8EF],
                         ids=["fedldf", "q8ef"])
def test_one_round_calls_match_one_run(task, kw):
    """Four one-round calls chained through ``start_round`` and
    ``server_state`` (how a benchmark or a checkpointing user drives the
    engine) equal one four-round call bit for bit: params, losses, uplink
    bytes and the final state."""
    params, data = task
    fl = _cfg(**kw)
    p_full, l_full = run_training_scan(params, _loss, data, fl, rounds=4,
                                       seed=0)
    p, state, logs = params, None, []
    for t in range(4):
        p, log = run_training_scan(p, _loss, data, fl, rounds=1, seed=0,
                                   start_round=t, server_state=state)
        state = log.final_state
        logs.append(log)
    _assert_trees_equal(p_full, p)
    assert l_full.losses == [x for log in logs for x in log.losses]
    assert _per_round_bytes([l_full]) == _per_round_bytes(logs)
    assert min(_per_round_bytes(logs)) > 0
    if kw:
        _assert_trees_equal(l_full.final_state, state)
    else:
        assert l_full.final_state is None and state is None


MESH_CARRY_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.wire import CompressionConfig
    from repro.data import FederatedData, iid_partition, make_image_dataset
    from repro.federated import FLConfig, run_training_scan, server
    from repro.launch.mesh import make_client_mesh
    from repro.launch.sharding import fl_param_specs, to_named

    def loss(p, b):
        x = b["images"].reshape(b["images"].shape[0], -1)
        return ((x @ p["w"] + p["b"]) ** 2).mean()

    train, _ = make_image_dataset(num_train=128, num_test=8, seed=1)
    data = FederatedData(train.xs, train.ys, iid_partition(train.ys, 8, 0))
    mesh = make_client_mesh(2, model=2)
    fl = FLConfig(num_clients=8, clients_per_round=4, top_n=1,
                  batch_per_client=4, mesh=mesh,
                  compression=CompressionConfig(bits=8, error_feedback=True))
    params = {"w": jnp.ones((3072, 4)) * 0.01, "b": jnp.zeros((4,))}
    params = jax.device_put(params, to_named(fl_param_specs(params, mesh),
                                             mesh))
    params, log = run_training_scan(params, loss, data, fl, rounds=1)
    state = log.final_state
    leaves = jax.tree.leaves((params, state))
    snap = [np.array(x) for x in leaves]
    shardings = [x.sharding for x in leaves]
    assert any(not s.is_fully_replicated for s in shardings), shardings
    copied = server._copy_carry(params, state)
    assert [x.sharding for x in jax.tree.leaves(copied[:2])] == shardings
    assert all(a is not b for a, b in zip(jax.tree.leaves(copied[:2]),
                                          leaves))
    p2, log2 = run_training_scan(params, loss, data, fl, rounds=2,
                                 start_round=1, server_state=state)
    for x, old in zip(leaves, snap):
        assert not x.is_deleted()
        np.testing.assert_array_equal(np.asarray(x), old)
    out = [x.sharding for x in jax.tree.leaves((p2, log2.final_state))]
    assert out == shardings, (out, shardings)
    print("ok")
""")


def test_scan_call_keeps_caller_buffers_on_mesh():
    """On a 2-device ('clients' 1, 'model' 2) mesh the carry copy keeps
    each input's ``NamedSharding`` (the scan carry's sharding stays
    fixed), and the caller's placed params and EF state survive the call.
    Own process: the suite runs on one CPU device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           ROOT]))
    out = subprocess.run([sys.executable, "-c", MESH_CARRY_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "ok"


# ----------------------------------------------------------------------
# FedLAMA
# ----------------------------------------------------------------------
def test_fedlama_round0_full_sync_then_intervals_adapt(task):
    params, data = task
    fl = _cfg("fedlama", fedlama_tau=2, fedlama_lam=3)
    _, log = run_training(params, _loss, data, fl, rounds=5, seed=0,
                          sampler="jax")
    g = log.final_state["global"]
    intervals = np.asarray(g["interval"])
    tau, lam = 2.0, 3.0
    assert set(np.unique(intervals)) <= {tau, tau * lam}
    assert (intervals == tau * lam).any(), \
        "no unit was demoted to the long interval"
    disc = np.asarray(g["disc"])
    assert (disc > 0).all(), "discrepancy estimate never bootstrapped"
    # uplink stays below FedAvg: only expired units travel + feedback
    assert log.meter.savings_frac > 0.2


def test_fedlama_first_round_selection_is_full(task):
    from repro.core.units import UnitMap
    params, _ = task
    umap = UnitMap.build(params)
    fl = _cfg("fedlama")
    key = jax.random.PRNGKey(0)
    batch = {"images": jax.random.normal(key, (K, 8, 32, 32, 3)),
             "labels": jax.random.randint(key, (K, 8), 0, 10)}
    sizes = jnp.full((K,), 10.0)
    strat = make_strategy(fl)
    state = strat.init_state(params, N_CLIENTS)
    _, metrics = jax.jit(build_round_fn(_loss, umap, fl))(
        params, batch, sizes, key, state)
    assert float(np.asarray(metrics["selection"]).min()) == 1.0
    # ttl advanced: nothing should sync again next round with tau >= 2
    ttl = np.asarray(metrics["state"]["global"]["ttl"])
    assert (ttl > 0).all()


def test_fedlama_drivers_agree(task):
    params, data = task
    kw = dict(fedlama_tau=2, fedlama_lam=2)
    ph, lh = run_training(params, _loss, data, _cfg("fedlama", **kw),
                          rounds=4, seed=0, sampler="jax")
    ps, ls = run_training_scan(params, _loss, data, _cfg("fedlama", **kw),
                               rounds=4, seed=0)
    pm, lm = run_training(params, _loss, data,
                          _cfg("fedlama", mode="scan", **kw),
                          rounds=4, seed=0, sampler="jax")
    _assert_trees_equal(ph, ps, atol=2e-5)
    _assert_trees_equal(ph, pm, atol=2e-5)
    _assert_trees_equal(lh.final_state, ls.final_state, atol=1e-6)
    _assert_trees_equal(lh.final_state, lm.final_state, atol=1e-6)
    assert lh.meter.uplink_bytes == pytest.approx(ls.meter.uplink_bytes,
                                                  rel=1e-6)


def test_fedlama_scan_round_keeps_its_trajectory(task):
    """FedLAMA selects from every client's score and its interval state,
    so the scan round still trains each client twice (Eq. 3, then Eq. 5):
    the trajectory is the one recorded before the FedLDF scan round took
    one pass (losses, each leaf's absolute sum, uplink bytes)."""
    params, data = task
    p, log = run_training_scan(params, _loss, data,
                               _cfg("fedlama", mode="scan", fedlama_tau=2,
                                    fedlama_lam=2), rounds=4, seed=0)
    np.testing.assert_allclose(
        log.losses, [2.3273391723632812, 2.0734736919403076,
                     1.9525597095489502, 2.060070037841797], rtol=1e-6)
    abs_sums = {"head": {"b": 0.019118035037536174, "w": 12.728678405052051},
                "l1": {"b": 0.008434971852693707, "w": 789.0554684412755}}
    for got, want in zip(jax.tree.leaves(p), jax.tree.leaves(abs_sums)):
        assert float(np.abs(np.asarray(got, np.float64)).sum()) == \
            pytest.approx(want, rel=1e-6)
    assert log.meter.uplink_bytes == 792256.0


def test_fedlama_quantized_composition(task):
    """FedLAMA under the quantize wrapper: interval state and the upload
    transform compose (state flows through QuantizedUpload delegation)."""
    params, data = task
    fl = _cfg("fedlama", quantize_bits=8)
    _, log = run_training_scan(params, _loss, data, fl, rounds=3, seed=0)
    assert log.final_state is not None
    assert float(log.final_state["global"]["rounds"]
                 if "rounds" in log.final_state["global"]
                 else log.final_state["global"]["disc"].sum()) >= 0.0
    assert all(np.isfinite(l) for l in log.losses)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
