"""Packed wire format: roundtrip exactness, byte accounting, fused kernel
equivalence, divergence-driven bit allocation, and the FLConfig shims."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm, wire
from repro.core.compress import compress_upload, quantize_unit_symmetric
from repro.core.units import UnitMap
from repro.core.wire import (UNIT_HEADER_BYTES, CompressionConfig,
                             PackedPayload, allocate_bits)
from repro.federated import FLConfig, build_round_fn
from repro.federated.strategies import make_strategy
from repro.kernels import ref
from repro.models import cnn

CFG = cnn.VGGConfig().reduced()


def _loss(p, b):
    return cnn.classify_loss(p, CFG, b)


def _tree_max_abs_diff(a, b):
    return max(float(jnp.abs(x.astype(jnp.float32)
                             - y.astype(jnp.float32)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.fixture(scope="module")
def setup():
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    umap = UnitMap.build(params)
    local = jax.tree.map(
        lambda l: l + 0.01 * jax.random.normal(jax.random.PRNGKey(1),
                                               l.shape), params)
    return params, umap, local


# ----------------------------------------------------------------------
# roundtrip: pack → unpack/dequantize against the pre-wire fp32 chain
# ----------------------------------------------------------------------
def test_pack_roundtrip_int8_matches_legacy_exactly(setup):
    g, umap, local = setup
    delta = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32), local, g)
    bits = jnp.full((umap.num_units,), 8.0, jnp.float32)
    payload = wire.pack(delta, umap, bits, storage_bits=8)
    recon = wire.dequantize(payload, umap, delta)

    # int8 storage is lossless for 8-bit levels: the wire path must agree
    # with the legacy fp32 chain bit-for-bit (compare at the Θ̂ level so
    # both sides use the same op order — Ĝ + recon)
    theta_hat, _ = compress_upload(local, g, umap, 8)
    theta_wire = jax.tree.map(
        lambda gg, r: (gg.astype(jnp.float32) + r).astype(gg.dtype),
        g, recon)
    assert _tree_max_abs_diff(theta_wire, theta_hat) == 0.0

    levels, scales = quantize_unit_symmetric(delta, umap, 8)
    np.testing.assert_array_equal(np.asarray(payload.scales),
                                  np.asarray(scales))
    for a, b in zip(jax.tree.leaves(payload.levels),
                    jax.tree.leaves(levels)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b).astype(np.int8))


def test_pack_roundtrip_int4_nibbles(setup):
    g, umap, local = setup
    delta = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32), local, g)
    bits = jnp.full((umap.num_units,), 4.0, jnp.float32)
    levels, _ = wire.quantize_units(delta, umap, bits)
    payload = wire.pack(delta, umap, bits, storage_bits=4)
    # nibble packing halves the last axis (rounded up)
    for lv, pk in zip(jax.tree.leaves(levels), jax.tree.leaves(payload.levels)):
        assert pk.dtype == jnp.int8
        assert pk.shape[-1] == (lv.shape[-1] + 1) // 2
    # and unpacks losslessly — 4-bit levels live in [-7, 7]
    unpacked = wire.unpack_levels(payload, delta)
    for lv, up in zip(jax.tree.leaves(levels), jax.tree.leaves(unpacked)):
        np.testing.assert_array_equal(np.asarray(lv).astype(np.int8),
                                      np.asarray(up))
    recon = wire.dequantize(payload, umap, delta)
    tol = 0.12 * _tree_max_abs_diff(delta, jax.tree.map(jnp.zeros_like,
                                                        delta))
    assert _tree_max_abs_diff(recon, delta) <= tol


def test_pack4_odd_tail():
    x = jnp.arange(-7, 8, dtype=jnp.int8).reshape(3, 5)  # odd last dim
    out = wire._unpack4(wire._pack4(x), 5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


# ----------------------------------------------------------------------
# byte accounting: nbytes / unit_wire_bytes / round_comm form one ledger
# ----------------------------------------------------------------------
def test_nbytes_matches_unit_wire_bytes_int8(setup):
    g, umap, local = setup
    delta = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32), local, g)
    bits = jnp.full((umap.num_units,), 8.0, jnp.float32)
    payload = wire.pack(delta, umap, bits, storage_bits=8)
    # at 8 bits the logical wire cost (ceil(p·8/8) + header per unit) is
    # exactly the physical packed size: levels + fp32 scale + width byte
    logical = float(jnp.sum(payload.unit_wire_bytes(umap)))
    assert logical == float(payload.nbytes)
    assert payload.nbytes == (umap.total_params
                              + (4 + 1) * umap.num_units)
    assert UNIT_HEADER_BYTES == 5


def test_nbytes_int4_padding_slack_bounded(setup):
    g, umap, local = setup
    delta = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32), local, g)
    bits = jnp.full((umap.num_units,), 4.0, jnp.float32)
    payload = wire.pack(delta, umap, bits, storage_bits=4)
    logical = float(jnp.sum(payload.unit_wire_bytes(umap)))
    # physical nibble packing pads odd last-dims per *leaf row*; the
    # logical per-unit ceil can only be under it, and the slack is at most
    # one byte per packed row
    rows = sum(int(np.prod(l.shape[:-1]))
               for l in jax.tree.leaves(payload.levels))
    assert payload.nbytes >= logical - rows
    assert payload.nbytes <= logical + rows


def test_comm_profile_prices_packed_bytes(setup):
    g, umap, local = setup
    delta = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32), local, g)
    bits = jnp.full((umap.num_units,), 8.0, jnp.float32)
    payload = wire.pack(delta, umap, bits, storage_bits=8)
    unit_bytes = payload.unit_wire_bytes(umap)

    k, u = 4, umap.num_units
    sel = (jax.random.uniform(jax.random.PRNGKey(2), (k, u)) < 0.5
           ).astype(jnp.float32)
    flcfg = FLConfig(algo="fedldf", clients_per_round=k, mode="vmap",
                     compression=CompressionConfig(bits=8))
    strat = make_strategy(flcfg)
    prof = strat.comm_profile(sel, umap, unit_bytes_override=unit_bytes)

    # the invariant: payload bytes == Σ selection · per-unit wire bytes,
    # and payload + feedback == total
    expect = float(jnp.sum(sel * unit_bytes[None, :]))
    assert float(prof["uplink_payload"]) == pytest.approx(expect, rel=1e-6)
    assert float(prof["uplink_total"]) == pytest.approx(
        float(prof["uplink_payload"]) + float(prof["uplink_feedback"]),
        rel=1e-6)
    # and it agrees with core.comm directly
    ref_prof = comm.round_comm(sel, umap, unit_bytes_override=unit_bytes)
    assert float(prof["uplink_total"]) == pytest.approx(
        float(ref_prof["uplink_total"]), rel=1e-6)


def test_comm_profile_static_fallback_prices_headers(setup):
    _, umap, _ = setup
    k, u = 4, umap.num_units
    sel = jnp.ones((k, u), jnp.float32)
    flcfg = FLConfig(algo="fedldf", clients_per_round=k, mode="vmap",
                     compression=CompressionConfig(bits=8))
    strat = make_strategy(flcfg)
    prof = strat.comm_profile(sel, umap)   # no per-round wire vector
    p = np.asarray(umap.unit_params, np.float64)
    expect = k * float((np.ceil(p * 8 / 8) + UNIT_HEADER_BYTES).sum())
    assert float(prof["uplink_payload"]) == pytest.approx(expect, rel=1e-6)


# ----------------------------------------------------------------------
# fused uplink kernel (interpret-mode Pallas) vs the jnp oracle
# ----------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1, 1, 1), (3, 7, 129), (4, 16, 2048),
                                   (5, 33, 2049)])
def test_fused_uplink_pallas_matches_ref(monkeypatch, shape):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    from repro.kernels import ops as kops
    k_, r, c = shape
    key = jax.random.PRNGKey(r * c)
    ks = jax.random.split(key, 3)
    levels = jax.random.randint(ks[0], shape, -127, 128).astype(jnp.int8)
    scales = jax.random.uniform(ks[1], (k_, r), minval=1e-4)
    w = jax.random.uniform(ks[2], (k_, r))
    out = kops.fused_uplink(levels, scales, w)
    exp = ref.fused_uplink(levels, scales, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=3e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 5, 64), (4, 16, 2048), (3, 9, 515)])
def test_fused_uplink_ef_pallas_matches_ref(monkeypatch, shape):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    from repro.kernels import ops as kops
    k_, r, c = shape
    ks = jax.random.split(jax.random.PRNGKey(c), 5)
    levels = jax.random.randint(ks[0], shape, -127, 128).astype(jnp.int8)
    scales = jax.random.uniform(ks[1], (k_, r), minval=1e-4)
    w = jax.random.uniform(ks[2], (k_, r))
    gate = (jax.random.uniform(ks[3], (k_, r)) < 0.5).astype(jnp.float32)
    v = jax.random.normal(ks[4], shape)
    e_old = jax.random.normal(ks[0], shape)
    num, res = kops.fused_uplink_ef(levels, scales, w, gate, v, e_old)
    enum, eres = ref.fused_uplink_ef(levels, scales, w, gate, v, e_old)
    np.testing.assert_allclose(np.asarray(num), np.asarray(enum),
                               rtol=3e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res), np.asarray(eres),
                               rtol=3e-5, atol=1e-5)
    # EF residual gating: unselected rows keep e_old exactly
    off = np.asarray(gate) == 0.0
    np.testing.assert_array_equal(np.asarray(res)[off],
                                  np.asarray(e_old)[off])


# folded layout: a leaf with fewer unit rows than the block folds into
# lane-dense sub-rows instead of padding rows up to 32
@pytest.mark.parametrize("ef", [False, True], ids=["noef", "ef"])
@pytest.mark.parametrize("shape", [(3, 1, 4096), (2, 3, 4096), (4, 1, 1000),
                                   (3, 3, 777)],
                         ids=["r1", "r3", "r1-c1000", "r3-c777"])
def test_fused_uplink_folded_layout_matches_ref(monkeypatch, shape, ef):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    from repro.kernels import ops as kops
    k_, r, c = shape
    ks = jax.random.split(jax.random.PRNGKey(r * 1000 + c), 6)
    levels = jax.random.randint(ks[0], shape, -127, 128).astype(jnp.int8)
    scales = jax.random.uniform(ks[1], (k_, r), minval=1e-4)
    w = jax.random.uniform(ks[2], (k_, r))
    if not ef:
        np.testing.assert_allclose(
            np.asarray(kops.fused_uplink(levels, scales, w)),
            np.asarray(ref.fused_uplink(levels, scales, w)),
            rtol=3e-5, atol=1e-5)
        return
    gate = (jax.random.uniform(ks[3], (k_, r)) < 0.5).astype(jnp.float32)
    v = jax.random.normal(ks[4], shape)
    e_old = jax.random.normal(ks[5], shape)
    out = kops.fused_uplink_ef(levels, scales, w, gate, v, e_old)
    exp = ref.fused_uplink_ef(levels, scales, w, gate, v, e_old)
    for o, x in zip(out, exp):
        assert o.shape == x.shape
        np.testing.assert_allclose(np.asarray(o), np.asarray(x),
                                   rtol=3e-5, atol=1e-5)


@pytest.mark.parametrize("r,c,rows,cols,block_c", [
    (1, 2359296, 32, 73728, 2048),   # VGG-9 conv7: no padding at all
    (1, 512, 32, 128, 128),          # a bias: C pads to 32·128
    (3, 777, 96, 128, 128),          # C pads up to f·128 only
    (64, 4096, 64, 4096, 2048),      # already a block multiple: no fold
])
def test_fold_pads_columns_not_rows(r, c, rows, cols, block_c):
    from repro.kernels import uplink
    k_ = 20
    lv = jax.ShapeDtypeStruct((k_, r, c), jnp.int8)
    vec = jax.ShapeDtypeStruct((k_, r), jnp.float32)
    mat = jax.ShapeDtypeStruct((k_, r, c), jnp.float32)
    out = {}

    def fold(lv_, vec_, mat_):
        levels, rowvecs, mats, bc = uplink._fold(
            lv_, [vec_], [mat_], uplink.DEFAULT_BLOCK_R,
            uplink.DEFAULT_BLOCK_C)
        out["block_c"] = bc
        return levels, rowvecs, mats

    levels, (vec_f,), (mat_f,) = jax.eval_shape(fold, lv, vec, mat)
    assert levels.shape == mat_f.shape == (k_, rows, cols)
    assert vec_f.shape == (k_, rows, 1)
    assert out["block_c"] == block_c and cols % block_c == 0


# ----------------------------------------------------------------------
# end-to-end: fused packed path vs the legacy unfused chain, fixed seed
# ----------------------------------------------------------------------
def _one_round(flcfg, params, umap, rng, state=None):
    k = flcfg.clients_per_round
    batch = {"images": jax.random.normal(rng, (k, 8, 32, 32, 3)),
             "labels": jax.random.randint(rng, (k, 8), 0, 10)}
    sizes = jnp.ones((k,))
    fn = jax.jit(build_round_fn(_loss, umap, flcfg))
    return fn(params, batch, sizes, rng, state)


@pytest.mark.parametrize("ef", [False, True], ids=["noef", "ef"])
def test_fused_trajectory_matches_legacy(ef):
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    umap = UnitMap.build(params)
    mk = lambda fused: FLConfig(
        algo="fedldf", num_clients=4, clients_per_round=4, top_n=2,
        mode="vmap",
        compression=CompressionConfig(bits=8, error_feedback=ef,
                                      fused=fused))
    cf, cl = mk(True), mk(False)
    # EF residual rows ride the strategy-state seam, as in the drivers
    sf = make_strategy(cf).init_state(params, 4)
    sl = make_strategy(cl).init_state(params, 4)
    pf, pl = params, params
    for r in range(3):
        rng = jax.random.PRNGKey(100 + r)
        pf, mf = _one_round(cf, pf, umap, rng, sf)
        pl, ml = _one_round(cl, pl, umap, rng, sl)
        sf, sl = mf.get("state", sf), ml.get("state", sl)
        # same math, different fp32 summation order (the fused path adds
        # denom·Ĝ once instead of accumulating Ĝ per client), so the
        # trajectories agree to fp32 tolerance, not bit-for-bit
        num = sum(float(jnp.sum((x - y) ** 2))
                  for x, y in zip(jax.tree.leaves(pf), jax.tree.leaves(pl)))
        den = sum(float(jnp.sum(x ** 2)) for x in jax.tree.leaves(pf))
        assert (num / den) ** 0.5 < 1e-4
        np.testing.assert_array_equal(np.asarray(mf["selection"]),
                                      np.asarray(ml["selection"]))
    # packed pricing adds only the per-unit header vs legacy b/8 pricing
    assert float(mf["comm"]["savings_frac"]) == pytest.approx(
        float(ml["comm"]["savings_frac"]), abs=0.01)


# ----------------------------------------------------------------------
# divergence-driven bit allocation
# ----------------------------------------------------------------------
def test_allocate_bits_budget_and_bounds(setup):
    _, umap, _ = setup
    u = umap.num_units
    divs = jax.random.uniform(jax.random.PRNGKey(5), (6, u), minval=0.1)
    b = allocate_bits(divs, umap, avg_bits=4.0, min_bits=2, max_bits=8)
    bn = np.asarray(b)
    assert bn.shape == (u,)
    np.testing.assert_array_equal(bn, np.round(bn))  # integer widths
    assert (bn >= 2).all() and (bn <= 8).all()
    p = np.asarray(umap.unit_params, np.float64)
    assert (p * bn).sum() / p.sum() <= 4.0 + 1e-6    # respects the budget


def test_allocate_bits_uniform_energy_hits_budget(setup):
    _, umap, _ = setup
    # per-parameter divergence energy identical across units → every unit
    # sits at the budget
    p = jnp.asarray(umap.unit_params, jnp.float32)
    divs = jnp.sqrt(p)[None, :]
    b = np.asarray(allocate_bits(divs, umap, avg_bits=4.0))
    np.testing.assert_array_equal(b, np.full_like(b, 4.0))


def test_allocate_bits_monotone_in_divergence(setup):
    _, umap, _ = setup
    u = umap.num_units
    p = jnp.asarray(umap.unit_params, jnp.float32)
    # unit 0 diverges 100× more per parameter than the rest
    energy = jnp.ones((u,)).at[0].set(100.0)
    divs = jnp.sqrt(energy * p)[None, :]
    b = np.asarray(allocate_bits(divs, umap, avg_bits=4.0))
    assert b[0] > b[1:].max()


def test_auto_bits_trains_and_saves_more_than_8bit():
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    umap = UnitMap.build(params)
    rng = jax.random.PRNGKey(11)
    auto = FLConfig(algo="fedldf", clients_per_round=4, top_n=2,
                    mode="vmap",
                    compression=CompressionConfig(bits="auto", avg_bits=4.0))
    fixed = FLConfig(algo="fedldf", clients_per_round=4, top_n=2,
                     mode="vmap", compression=CompressionConfig(bits=8))
    pa, ma = _one_round(auto, params, umap, rng)
    _, mf = _one_round(fixed, params, umap, rng)
    assert np.isfinite(float(ma["loss"]))
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(pa))
    # ≤4-bit average beats uniform 8-bit on the wire
    assert float(ma["comm"]["uplink_total"]) < float(mf["comm"]["uplink_total"])


def test_auto_requires_divergence_stats(setup):
    _, umap, _ = setup
    with pytest.raises(ValueError, match="divergence"):
        CompressionConfig(bits="auto").bits_vector(umap, None)


# ----------------------------------------------------------------------
# CompressionConfig validation + FLConfig deprecation shims
# ----------------------------------------------------------------------
def test_compression_config_validation():
    with pytest.raises(ValueError, match=r"\[2, 8\]"):
        CompressionConfig(bits=1)
    with pytest.raises(ValueError, match=r"\[2, 8\]"):
        CompressionConfig(bits=9)
    with pytest.raises(ValueError, match="auto"):
        CompressionConfig(bits="adaptive")
    with pytest.raises(ValueError, match="waterfill"):
        CompressionConfig(allocation="greedy")
    with pytest.raises(ValueError, match="avg_bits"):
        CompressionConfig(bits="auto", avg_bits=10.0)
    with pytest.raises(ValueError, match="fused"):
        CompressionConfig(bits="auto", fused=False)
    assert CompressionConfig(bits=4).storage_bits == 4
    assert CompressionConfig(bits=5).storage_bits == 8
    assert CompressionConfig(bits="auto", max_bits=4).storage_bits == 4


def test_flcfg_quantize_shim_warns_and_normalizes():
    with pytest.warns(DeprecationWarning, match="CompressionConfig"):
        old = FLConfig(algo="fedldf", clients_per_round=4, mode="vmap",
                       quantize_bits=8, error_feedback=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # new spelling must not warn
        new = FLConfig(algo="fedldf", clients_per_round=4, mode="vmap",
                       compression=CompressionConfig(bits=8,
                                                     error_feedback=True))
    assert old == new and hash(old) == hash(new)
    assert old.compression == CompressionConfig(bits=8, error_feedback=True)
    assert new.quantize_bits == 8 and new.error_feedback  # mirrored back


def test_flcfg_quantize_shim_conflict_raises():
    with pytest.raises(ValueError):
        FLConfig(algo="fedldf", clients_per_round=4, mode="vmap",
                 quantize_bits=4,
                 compression=CompressionConfig(bits=8))


def test_flcfg_algo_options_shim():
    from repro.federated import FedLPOptions
    with pytest.warns(DeprecationWarning, match="algo_options"):
        old = FLConfig(algo="fedlp", clients_per_round=4, mode="vmap",
                       fedlp_p=0.25)
    new = FLConfig(algo="fedlp", clients_per_round=4, mode="vmap",
                   algo_options=FedLPOptions(p=0.25))
    assert old == new
    assert new.fedlp_p == 0.25          # mirrored back for old readers
    with pytest.raises(ValueError):
        FLConfig(algo="fedlp", clients_per_round=4, mode="vmap",
                 fedlp_p=0.75, algo_options=FedLPOptions(p=0.25))


def test_flcfg_equivalent_spellings_share_strategy_behaviour():
    import dataclasses as dc
    cfg = FLConfig(algo="fedldf", clients_per_round=4, mode="vmap",
                   compression=CompressionConfig(bits=8))
    again = dc.replace(cfg)             # normalized configs must round-trip
    assert cfg == again
    strat = make_strategy(cfg)
    assert strat.packed_upload and not strat.transforms_upload
    legacy = make_strategy(dc.replace(
        cfg, compression=CompressionConfig(bits=8, fused=False)))
    assert legacy.transforms_upload and not legacy.packed_upload


def test_scan_compression_error_names_config_and_drivers():
    """The scan-engine refusal must tell the user what to reach for: the
    config class spelling and every driver that does support the packed
    uplink."""
    with pytest.raises(NotImplementedError) as ei:
        FLConfig(algo="fedldf", mode="scan",
                 compression=CompressionConfig(bits=8))
    msg = str(ei.value)
    for needle in ("CompressionConfig", "mode='vmap'", "mesh",
                   "run_training", "run_training_scan"):
        assert needle in msg, needle
    # the direct build_round_scan entry point refuses with the same message
    from repro.federated import build_round_scan
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    umap = UnitMap.build(params)
    fl = FLConfig(algo="fedldf", clients_per_round=4,
                  compression=CompressionConfig(bits=8))
    with pytest.raises(NotImplementedError) as ei2:
        build_round_scan(_loss, umap, fl)
    assert str(ei2.value) == msg
