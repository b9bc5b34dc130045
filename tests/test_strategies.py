"""Strategy-plugin seams: registry round-trip, capability flags, FedADP
vmap-vs-scan equivalence, FedLP end-to-end, and the per-strategy
comm_profile ledger invariant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.federated as fed
from repro.core import selection as sel
from repro.core.units import UnitMap
from repro.data import FederatedData, iid_partition, make_image_dataset
from repro.federated import (FLConfig, FLStrategy, build_round_fn,
                             make_strategy, register_strategy,
                             registered_algos, run_training,
                             run_training_scan, unregister_strategy)
from repro.models import cnn

CFG = cnn.VGGConfig().reduced()
BUILTINS = ("fedldf", "fedavg", "random", "hdfl", "fedadp", "fedlp")
ALL_ALGOS = BUILTINS + ("fedlama",)


def _loss(params, batch):
    return cnn.classify_loss(params, CFG, batch)


@pytest.fixture(scope="module")
def setup():
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    umap = UnitMap.build(params)
    k = 6
    key = jax.random.PRNGKey(3)
    batch = {"images": jax.random.normal(key, (k, 8, 32, 32, 3)),
             "labels": jax.random.randint(key, (k, 8), 0, 10)}
    sizes = jnp.array([10.0, 20.0, 30.0, 10.0, 15.0, 25.0])
    return params, umap, batch, sizes, key, k


@pytest.fixture(scope="module")
def fed_data():
    train, _ = make_image_dataset(num_train=400, num_test=40, seed=1)
    parts = iid_partition(train.ys, 8, seed=0)
    return FederatedData(train.xs, train.ys, parts)


# ----------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------
def test_builtins_registered_in_order():
    algos = registered_algos()
    assert algos[:len(BUILTINS)] == BUILTINS
    assert fed.ALGOS == algos          # live module-level view


def test_unknown_algo_lists_registered_names():
    with pytest.raises(ValueError) as ei:
        FLConfig(algo="definitely-not-registered")
    msg = str(ei.value)
    for name in BUILTINS:
        assert name in msg


def test_register_round_trip(fed_data):
    """register → FLConfig resolves → a real training run → unregister."""

    @register_strategy("first_n")
    class FirstN(FLStrategy):
        """Deterministic toy policy: clients 0..n-1 upload everything."""

        def select(self, divs, key, k, u, n):
            rows = (jnp.arange(k) < n).astype(jnp.float32)
            return jnp.broadcast_to(rows[:, None], (k, u))

    try:
        assert "first_n" in fed.ALGOS
        fl = FLConfig(algo="first_n", num_clients=8, clients_per_round=4,
                      top_n=2, lr=0.05, batch_per_client=8)
        params = cnn.init_params(jax.random.PRNGKey(0), CFG)
        params, log = run_training(params, _loss, fed_data, fl, rounds=2,
                                   seed=0)
        assert all(np.isfinite(l) for l in log.losses)
        # n/K = 1/2 of the payload, no divergence feedback
        assert log.meter.savings_frac == pytest.approx(0.5, abs=1e-6)
    finally:
        unregister_strategy("first_n")
    assert "first_n" not in fed.ALGOS
    with pytest.raises(ValueError):
        FLConfig(algo="first_n")


def test_register_duplicate_name_guarded():
    """A plugin can't silently replace a builtin (or another plugin)."""
    with pytest.raises(ValueError, match="already registered"):
        @register_strategy("fedavg")
        class Impostor(FLStrategy):
            def select(self, divs, key, k, u, n):
                return jnp.zeros((k, u))
    from repro.federated.strategies import get_strategy_cls
    fedavg_cls = get_strategy_cls("fedavg")
    # same class, same name: idempotent (module re-import)
    assert register_strategy("fedavg")(fedavg_cls) is fedavg_cls
    # explicit override is allowed — and restorable
    try:
        @register_strategy("fedavg", override=True)
        class Replacement(FLStrategy):
            def select(self, divs, key, k, u, n):
                return jnp.ones((k, u))
        assert get_strategy_cls("fedavg") is Replacement
    finally:
        register_strategy("fedavg", override=True)(fedavg_cls)
    assert get_strategy_cls("fedavg") is fedavg_cls


def test_reregistered_strategy_misses_stale_jit_cache(fed_data):
    """The driver's compiled-callable cache must not hand a re-registered
    name the round compiled for the previously registered class."""
    p0 = cnn.init_params(jax.random.PRNGKey(0), CFG)

    def run_once():
        fl = FLConfig(algo="tmpstrat", num_clients=8, clients_per_round=4,
                      top_n=2, lr=0.05, batch_per_client=8)
        _, log = run_training(p0, _loss, fed_data, fl, rounds=1, seed=0)
        return log.meter.savings_frac

    @register_strategy("tmpstrat")
    class AllLayers(FLStrategy):
        def select(self, divs, key, k, u, n):
            return jnp.ones((k, u), jnp.float32)

    try:
        assert run_once() == pytest.approx(0.0, abs=1e-6)
        unregister_strategy("tmpstrat")

        @register_strategy("tmpstrat")
        class HalfClients(FLStrategy):
            def select(self, divs, key, k, u, n):
                rows = (jnp.arange(k) < k // 2).astype(jnp.float32)
                return jnp.broadcast_to(rows[:, None], (k, u))

        # identical FLConfig: a stale cache would reproduce 0.0 savings
        assert run_once() == pytest.approx(0.5, abs=1e-6)
    finally:
        unregister_strategy("tmpstrat")


# ----------------------------------------------------------------------
# Capability flags
# ----------------------------------------------------------------------
def test_capability_flags_validated():
    with pytest.raises(ValueError, match="supports_quantize"):
        FLConfig(algo="fedadp", quantize_bits=8)
    with pytest.raises(NotImplementedError):
        FLConfig(algo="fedldf", mode="scan", quantize_bits=8)
    # fedadp in scan mode is now a declared capability, not an assert
    assert FLConfig(algo="fedadp", mode="scan").algo == "fedadp"


@pytest.mark.skipif(len(jax.devices()) < 1, reason="needs a device")
def test_fedadp_mesh_capability_flipped():
    """fedadp now ships psum_parts/psum_finalize overrides, so a mesh
    config validates (the equivalence matrix lives in
    tests/test_shard_engine.py)."""
    from repro.launch.mesh import make_client_mesh
    mesh = make_client_mesh(1)
    fl = FLConfig(algo="fedadp", clients_per_round=4, top_n=2, mesh=mesh)
    assert type(make_strategy(fl)).supports_mesh


# ----------------------------------------------------------------------
# FedADP scan mode (unlocked by the refactor)
# ----------------------------------------------------------------------
def test_fedadp_vmap_scan_trajectory_equivalence(fed_data):
    """Multi-round driver equivalence on a fixed seed: the scan engine
    stacks sequentially-trained locals into the same psum_parts /
    psum_finalize halves."""
    kw = dict(algo="fedadp", num_clients=8, clients_per_round=4, top_n=2,
              lr=0.05, batch_per_client=8, fedadp_keep=0.3)
    p0 = cnn.init_params(jax.random.PRNGKey(0), CFG)
    pv, lv = run_training(p0, _loss, fed_data,
                          FLConfig(mode="vmap", **kw), rounds=3, seed=0,
                          sampler="jax")
    ps, ls = run_training(p0, _loss, fed_data,
                          FLConfig(mode="scan", **kw), rounds=3, seed=0,
                          sampler="jax")
    for a, b in zip(jax.tree.leaves(pv), jax.tree.leaves(ps)):
        np.testing.assert_allclose(a, b, atol=3e-5)
    np.testing.assert_allclose(lv.losses, ls.losses, atol=1e-4)
    assert lv.meter.uplink_bytes == pytest.approx(ls.meter.uplink_bytes)


# ----------------------------------------------------------------------
# FedLP
# ----------------------------------------------------------------------
def test_fedlp_selection_is_bernoulli(setup):
    params, umap, batch, sizes, key, k = setup
    s = sel.bernoulli_per_layer(key, 50, umap.num_units, 0.5)
    assert s.shape == (50, umap.num_units)
    assert set(np.unique(np.asarray(s))) <= {0.0, 1.0}
    assert 0.3 < float(s.mean()) < 0.7
    with pytest.raises(ValueError):
        sel.bernoulli_per_layer(key, 4, 3, 0.0)


def test_fedlp_round_and_comm(setup):
    """One fedlp round: Eq. 5 over the Bernoulli mask; uplink ≈ p·FedAvg
    plus the keep-mask header, and the ledger invariant holds."""
    params, umap, batch, sizes, key, k = setup
    fl = FLConfig(algo="fedlp", clients_per_round=k, top_n=2, fedlp_p=0.5)
    p, m = jax.jit(build_round_fn(_loss, umap, fl))(params, batch, sizes,
                                                    key)
    assert np.isfinite(float(m["loss"]))
    c = m["comm"]
    assert float(c["uplink_payload"]) + float(c["uplink_feedback"]) == \
        pytest.approx(float(c["uplink_total"]))
    sel_frac = float(np.asarray(m["selection"]).mean())
    assert float(c["uplink_payload"]) <= float(c["fedavg_uplink"])
    # payload tracks the realised keep mask (unit sizes vary, so compare
    # against the mask-weighted bytes, not the raw fraction)
    expect = float((np.asarray(m["selection"])
                    * np.asarray(umap.unit_bytes_array())[None, :]).sum())
    assert float(c["uplink_payload"]) == pytest.approx(expect)
    mask_hdr = k * ((umap.num_units + 7) // 8)
    assert float(c["uplink_feedback"]) == pytest.approx(mask_hdr)
    assert 0.0 < sel_frac < 1.0


def test_fedlp_trains_end_to_end(fed_data):
    """FLConfig(algo='fedlp') through both multi-round drivers."""
    fl = FLConfig(algo="fedlp", num_clients=8, clients_per_round=4,
                  top_n=2, lr=0.05, batch_per_client=8, fedlp_p=0.5)
    p0 = cnn.init_params(jax.random.PRNGKey(0), CFG)
    ph, lh = run_training(p0, _loss, fed_data, fl, rounds=3, seed=0,
                          sampler="jax")
    ps, lscan = run_training_scan(p0, _loss, fed_data, fl, rounds=3, seed=0)
    assert all(np.isfinite(l) for l in lh.losses)
    for a, b in zip(jax.tree.leaves(ph), jax.tree.leaves(ps)):
        np.testing.assert_allclose(a, b, atol=2e-6)
    # ~p of FedAvg uplink (+ tiny mask header), Bernoulli-noisy
    assert 0.2 < lh.meter.savings_frac < 0.8


# ----------------------------------------------------------------------
# comm_profile ledger invariant — every registered strategy
# ----------------------------------------------------------------------
def _config_for(algo):
    return FLConfig(algo=algo, num_clients=50, clients_per_round=6,
                    top_n=2, fedadp_keep=0.3, fedlp_p=0.4)


@pytest.mark.parametrize("algo", ALL_ALGOS)
@pytest.mark.parametrize("quantized", [False, True])
def test_comm_profile_invariant(setup, algo, quantized):
    """payload + feedback == total, and savings_frac is consistent, for
    every registered strategy — bare and under the quantize wrapper.
    Selection goes through select_with_state (the engines' entry point),
    which exercises the stateless-delegation default and lets the
    stateful fedlama participate."""
    params, umap, batch, sizes, key, k = setup
    fl = _config_for(algo)
    if quantized:
        if not type(make_strategy(fl)).supports_quantize:
            pytest.skip(f"{algo} declares supports_quantize=False")
        fl = FLConfig(algo=algo, num_clients=50, clients_per_round=6,
                      top_n=2, fedadp_keep=0.3, fedlp_p=0.4,
                      quantize_bits=8)
    strat = make_strategy(fl)
    divs = (jax.random.uniform(key, (k, umap.num_units))
            if strat.needs_divergence else None)
    state = strat.init_state(params, fl.num_clients)
    s = strat.select_with_state(state, divs, key, k, umap.num_units,
                                fl.top_n)
    c = strat.comm_profile(s, umap)
    payload, feedback = float(c["uplink_payload"]), float(c["uplink_feedback"])
    total, ref = float(c["uplink_total"]), float(c["fedavg_uplink"])
    assert payload + feedback == pytest.approx(total), strat.name
    # abs tolerance: savings_frac is computed on-device in fp32, and for
    # near-zero savings (fedlama's round-0 full sync + feedback) the
    # default relative approx is tighter than fp32 resolution
    assert float(c["savings_frac"]) == pytest.approx(1.0 - total / ref,
                                                     abs=1e-6)
    assert float(c["downlink"]) == pytest.approx(ref)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
