"""Core FedLDF: unit map, divergence (Eq. 3), selection (Eq. 4),
aggregation (Eq. 5/6), communication accounting, convergence bound."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional [test] extra — deterministic fallbacks below
    HAVE_HYPOTHESIS = False

from repro.core import (BoundParams, UnitMap, aggregate_stacked,
                        asymptotic_gap, contraction_A, fedavg_stacked,
                        round_comm, selection as sel, streaming_add,
                        streaming_finalize, streaming_init, unit_weights)
from repro.core import convergence as conv


def _params(key=0):
    k = jax.random.PRNGKey(key)
    ks = jax.random.split(k, 4)
    return {
        "embed": {"w": jax.random.normal(ks[0], (32, 8))},
        "blocks": {"a": jax.random.normal(ks[1], (3, 8, 8)),
                   "b": jax.random.normal(ks[2], (3, 8))},
        "final": {"n": jax.random.normal(ks[3], (8,))},
    }


def _np_divergence(p, r, umap):
    out = np.zeros(umap.num_units)
    for key, (off, n) in umap.spans.items():
        for a, b in zip(jax.tree.leaves(p[key]), jax.tree.leaves(r[key])):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            if n > 1:
                out[off:off + n] += ((a - b) ** 2).reshape(n, -1).sum(1)
            else:
                out[off] += ((a - b) ** 2).sum()
    return np.sqrt(out)


# ----------------------------------------------------------------------
class TestUnitMap:
    def test_build(self):
        umap = UnitMap.build(_params())
        assert umap.names == ("blocks/0", "blocks/1", "blocks/2", "embed",
                              "final")
        assert umap.unit_bytes[0] == (8 * 8 + 8) * 4
        assert umap.unit_bytes[3] == 32 * 8 * 4
        assert umap.total_params == 3 * 72 + 256 + 8

    def test_divergence_matches_numpy(self):
        p, r = _params(0), _params(1)
        umap = UnitMap.build(p)
        np.testing.assert_allclose(umap.divergence(p, r),
                                   _np_divergence(p, r, umap), rtol=1e-5)

    def test_divergence_zero_for_identical(self):
        p = _params()
        umap = UnitMap.build(p)
        np.testing.assert_allclose(umap.divergence(p, p), 0.0, atol=1e-7)

    def test_scale_by_unit(self):
        p = _params()
        umap = UnitMap.build(p)
        scale = jnp.arange(umap.num_units, dtype=jnp.float32)
        out = umap.scale_by_unit(p, scale)
        np.testing.assert_allclose(out["blocks"]["a"][1],
                                   np.asarray(p["blocks"]["a"][1]) * 1.0)
        np.testing.assert_allclose(out["blocks"]["a"][2],
                                   np.asarray(p["blocks"]["a"][2]) * 2.0)
        np.testing.assert_allclose(out["embed"]["w"],
                                   np.asarray(p["embed"]["w"]) * 3.0)

    def test_jit_and_scan_safe(self):
        p, r = _params(0), _params(1)
        umap = UnitMap.build(p)
        d1 = jax.jit(umap.divergence)(p, r)
        np.testing.assert_allclose(d1, umap.divergence(p, r), rtol=1e-6)

    @pytest.mark.parametrize("pallas", [False, True], ids=["jnp", "pallas"])
    @pytest.mark.parametrize("path", ["vmap", "scan", "jax_vmap"])
    def test_divergence_paths_match_numpy(self, monkeypatch, path, pallas):
        """Eq. 3 per unit as each round calls it: the vmap round scores
        the stacked locals in one call per leaf, the scan round one client
        at a time, and an outer ``jax.vmap`` batches the per-client call;
        on the jnp path and through the Pallas kernel (interpret mode),
        with in-place and folded leaves alike."""
        monkeypatch.setenv("REPRO_FORCE_PALLAS", "1" if pallas else "0")
        k = 3
        keys = jax.random.split(jax.random.PRNGKey(5), 4)
        g = dict(_params(0), conv={
            "w": jax.random.normal(keys[0], (3, 3, 8, 128)),
            "b": jax.random.normal(keys[1], (128,))})
        g["blocks"]["lora_b"] = jax.random.normal(keys[2], (3, 8, 256))
        locals_ = jax.tree.map(
            lambda l: l + jax.random.normal(keys[3], (k,) + l.shape),
            g)
        umap = UnitMap.build(g)
        exp = np.stack([_np_divergence(jax.tree.map(lambda l: l[i],
                                                    locals_), g, umap)
                        for i in range(k)])
        if path == "vmap":
            got = umap.divergence_batched(locals_, g)
        elif path == "scan":
            got = jnp.stack([umap.divergence(
                jax.tree.map(lambda l: l[i], locals_), g) for i in range(k)])
        else:
            got = jax.vmap(lambda p: umap.divergence(p, g))(locals_)
        np.testing.assert_allclose(got, exp, rtol=1e-5)


# ----------------------------------------------------------------------
class TestSelection:
    def test_topn_exact(self):
        divs = jnp.array([[3.0, 0.0], [1.0, 2.0], [2.0, 1.0]])  # (K=3, U=2)
        s = sel.topn_divergence(divs, 2)
        np.testing.assert_array_equal(s, [[1, 0], [0, 1], [1, 1]])

    @staticmethod
    def _check_topn_properties(k, u, n, seed):
        n = min(n, k)
        divs = jax.random.uniform(jax.random.PRNGKey(seed), (k, u))
        s = np.asarray(sel.topn_divergence(divs, n))
        assert set(np.unique(s)) <= {0.0, 1.0}
        np.testing.assert_array_equal(s.sum(0), np.full(u, n))
        # selected divergences dominate unselected, per column
        for col in range(u):
            chosen = np.asarray(divs)[:, col][s[:, col] == 1]
            rest = np.asarray(divs)[:, col][s[:, col] == 0]
            if len(rest):
                assert chosen.min() >= rest.max() - 1e-6

    # deterministic fallback grid — covers the invariant without hypothesis
    @pytest.mark.parametrize("k,u,n,seed", [
        (2, 1, 1, 0), (3, 4, 2, 1), (12, 9, 12, 7), (5, 3, 5, 42),
        (7, 6, 3, 123), (9, 1, 4, 999983), (4, 2, 1, 31337),
    ])
    def test_topn_properties_cases(self, k, u, n, seed):
        self._check_topn_properties(k, u, n, seed)

    if HAVE_HYPOTHESIS:
        @settings(max_examples=30, deadline=None)
        @given(k=st.integers(2, 12), u=st.integers(1, 9),
               n=st.integers(1, 12), seed=st.integers(0, 10**6))
        def test_topn_properties(self, k, u, n, seed):
            self._check_topn_properties(k, u, n, seed)

    def test_random_per_layer_counts(self):
        s = np.asarray(sel.random_per_layer(jax.random.PRNGKey(0), 10, 7, 3))
        np.testing.assert_array_equal(s.sum(0), np.full(7, 3))

    def test_client_dropout_rows(self):
        s = np.asarray(sel.client_dropout(jax.random.PRNGKey(0), 10, 7, 4))
        # whole-row selection: every row all-ones or all-zeros
        assert set(s.sum(1)) <= {0.0, 7.0}
        assert s.sum() == 4 * 7

    def test_full(self):
        assert np.asarray(sel.full_participation(3, 2)).sum() == 6


# ----------------------------------------------------------------------
class TestAggregation:
    def _stacked(self, k=4):
        base = _params()
        return jax.tree.map(
            lambda l: jnp.stack([l * (i + 1.0) for i in range(k)]), base)

    def test_eq5_manual(self):
        """Eq. 5 against a hand-computed single-unit case."""
        g = _params()
        umap = UnitMap.build(g)
        sp = self._stacked(2)
        selection = jnp.zeros((2, umap.num_units)).at[0, 3].set(1.0) \
            .at[1, 3].set(1.0).at[0, 0].set(1.0).at[1, 4].set(1.0)
        sizes = jnp.array([1.0, 3.0])
        out = aggregate_stacked(sp, umap, selection, sizes, fallback=g)
        # unit 3 = embed: (1·1·θ + 3·2·θ)/(1+3)
        np.testing.assert_allclose(
            out["embed"]["w"],
            np.asarray(g["embed"]["w"]) * (1 * 1 + 3 * 2) / 4, rtol=1e-5)
        # unit 0 = blocks/0 only client 0: θ·1
        np.testing.assert_allclose(out["blocks"]["a"][0],
                                   np.asarray(g["blocks"]["a"][0]), rtol=1e-5)
        # blocks/1, blocks/2 unselected -> fallback to g
        np.testing.assert_allclose(out["blocks"]["a"][1],
                                   np.asarray(g["blocks"]["a"][1]), rtol=1e-5)
        # unit 4 = final only client 1 (×2)
        np.testing.assert_allclose(out["final"]["n"],
                                   np.asarray(g["final"]["n"]) * 2, rtol=1e-5)

    def test_full_selection_equals_fedavg(self):
        g = _params()
        umap = UnitMap.build(g)
        sp = self._stacked(3)
        sizes = jnp.array([2.0, 5.0, 3.0])
        s = sel.full_participation(3, umap.num_units)
        a = aggregate_stacked(sp, umap, s, sizes, fallback=g)
        b = fedavg_stacked(sp, sizes)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(x, y, rtol=1e-5)

    def test_streaming_equals_stacked(self):
        g = _params()
        umap = UnitMap.build(g)
        k = 4
        sp = self._stacked(k)
        sizes = jnp.array([1.0, 2.0, 3.0, 4.0])
        divs = jax.vmap(lambda p: umap.divergence(p, g))(sp)
        s = sel.topn_divergence(divs, 2)
        stacked = aggregate_stacked(sp, umap, s, sizes, fallback=g)
        w, denom = unit_weights(s, sizes)
        frac = w / jnp.where(denom > 0, denom, 1.0)[None, :]
        acc = streaming_init(g)
        for i in range(k):
            ci = jax.tree.map(lambda l: l[i], sp)
            acc = streaming_add(acc, ci, umap, frac[i])
        out = streaming_finalize(acc, umap, denom, g)
        for x, y in zip(jax.tree.leaves(stacked), jax.tree.leaves(out)):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
class TestComm:
    """Uses the paper's real VGG-9 (4.7M params) so the divergence-feedback
    vector is, as in the paper, negligible against layer payloads."""

    @pytest.fixture(scope="class")
    def vgg_umap(self):
        from repro.models import cnn
        params = cnn.init_params(jax.random.PRNGKey(0), cnn.VGGConfig())
        return UnitMap.build(params)

    def test_80_percent_savings(self, vgg_umap):
        """Paper headline: n/K = 0.2 -> ~80 % uplink reduction."""
        umap = vgg_umap
        k, n = 20, 4
        s = sel.topn_divergence(
            jax.random.uniform(jax.random.PRNGKey(0), (k, umap.num_units)), n)
        stats = round_comm(s, umap)
        assert abs(float(stats["savings_frac"]) - 0.8) < 0.01
        assert float(stats["uplink_payload"]) == pytest.approx(
            n * umap.total_bytes)

    def test_feedback_overhead_is_small(self, vgg_umap):
        umap = vgg_umap
        s = sel.full_participation(20, umap.num_units)
        stats = round_comm(s, umap, divergence_feedback=True)
        assert float(stats["uplink_feedback"]) == 20 * umap.num_units * 4
        assert float(stats["uplink_feedback"]) < 0.01 * float(
            stats["uplink_payload"])

    def test_payload_plus_feedback_is_total(self, vgg_umap):
        """The accounting invariant every consumer of the metrics dict
        relies on: uplink_payload + uplink_feedback == uplink_total."""
        umap = vgg_umap
        s = sel.topn_divergence(
            jax.random.uniform(jax.random.PRNGKey(1), (20, umap.num_units)),
            4)
        for fb in (False, True):
            stats = round_comm(s, umap, divergence_feedback=fb)
            assert float(stats["uplink_payload"]) \
                + float(stats["uplink_feedback"]) \
                == pytest.approx(float(stats["uplink_total"]))
            assert float(stats["savings_frac"]) == pytest.approx(
                1.0 - float(stats["uplink_total"])
                / float(stats["fedavg_uplink"]))


# ----------------------------------------------------------------------
class TestConvergenceBound:
    P = BoundParams(beta=1.0, xi1=0.1, xi2=0.05, grad_bound=1.0,
                    eta=0.05, num_layers=9, n=4, k=20)

    def test_n_equals_k_vanishes(self):
        p = conv.BoundParams(**{**self.P.__dict__, "n": 20})
        assert contraction_A(p) == 0.0
        assert asymptotic_gap(p) == 0.0

    def test_gap_decreases_in_n(self):
        gaps = [asymptotic_gap(conv.BoundParams(
            **{**self.P.__dict__, "n": n})) for n in range(1, 21)]
        assert all(g1 >= g2 - 1e-12 for g1, g2 in zip(gaps, gaps[1:]))

    def test_condition(self):
        assert conv.converges(self.P)
        bad = conv.BoundParams(**{**self.P.__dict__, "xi2": 1e6})
        assert not conv.converges(bad)

    def test_recursion_matches_closed_form(self):
        p, gap0 = self.P, 0.3
        a, b = contraction_A(p), conv.offset_B(p)
        gap = gap0
        for t in range(1, 6):
            gap = a * gap + b
            assert conv.gap_bound(p, t, gap0) == pytest.approx(gap, rel=1e-9)
