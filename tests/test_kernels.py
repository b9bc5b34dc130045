"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional [test] extra — deterministic fallbacks below
    HAVE_HYPOTHESIS = False

from repro.kernels import aggregate as ka
from repro.kernels import divergence as kd
from repro.kernels import ref

SHAPES = [(1, 1), (1, 37), (4, 1000), (8, 2048), (9, 2049), (48, 5000),
          (3, 16384), (62, 33)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_sqdiff_rowsum_matches_ref(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(hash(shape) % 2**31))
    a = jax.random.normal(k1, shape, dtype=dtype)
    b = jax.random.normal(k2, shape, dtype=dtype)
    out = kd.sqdiff_rowsum(a, b, interpret=True)
    exp = ref.sqdiff_rowsum(a, b)
    assert out.shape == (shape[0],)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, exp, rtol=3e-3, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_masked_accumulate_matches_ref(shape, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    acc = jax.random.normal(k1, shape, dtype=jnp.float32)
    x = jax.random.normal(k2, shape, dtype=dtype)
    w = jax.random.normal(k3, (shape[0],))
    out = ka.masked_accumulate(acc, x, w, interpret=True)
    exp = ref.masked_accumulate(acc, x, w)
    np.testing.assert_allclose(out, exp, rtol=3e-3, atol=1e-5)


@pytest.mark.parametrize("block_r,block_c", [(8, 128), (8, 2048), (16, 512)])
def test_sqdiff_block_shape_invariance(block_r, block_c):
    """Result must not depend on the block size: ``block_bytes`` of one
    (block_r, block_c) f32 block, from many small steps to a whole unit,
    with ragged last blocks where the rows do not divide."""
    block_bytes = block_r * block_c * 4
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(3), 4)
    a = jax.random.normal(k1, (21, 3000))
    b = jax.random.normal(k2, (21, 3000))
    out = kd.sqdiff_rowsum(a, b, block_bytes=block_bytes, interpret=True)
    np.testing.assert_allclose(out, ref.sqdiff_rowsum(a, b), rtol=1e-5)
    # in place, 216 rows of 128: 27 exact, 2 or 4 ragged blocks
    la = jax.random.normal(k3, (3, 3, 3, 24, 128))
    lb = jax.random.normal(k4, (3, 3, 24, 128))
    out = kd.sqdiff_units(la, lb, block_bytes=block_bytes, interpret=True)
    np.testing.assert_allclose(out, ref.sqdiff_units(la, lb), rtol=1e-5)


# Eq. 3 as the round calls it: one leaf, K clients, ``rows`` unit rows.
# (leaf shape, rows, dtype, in place)
UNIT_LEAVES = [
    ((3, 3, 64, 128), 1, jnp.float32, True),     # VGG-9 conv2
    ((3, 3, 3, 64), 1, jnp.float32, False),      # VGG-9 conv0
    ((2048, 10), 1, jnp.float32, False),         # VGG-9 fc.w
    ((64,), 1, jnp.float32, False),              # VGG-9 bias / scale
    ((2, 16, 256), 2, jnp.bfloat16, True),       # stacked LoRA B
    ((2, 256, 16), 2, jnp.bfloat16, False),      # stacked LoRA A
    ((5, 129), 5, jnp.float32, False),           # ragged tail per row
    ((3, 7, 5, 1000), 3, jnp.float32, False),    # odd sizes
    ((2, 24, 384), 2, jnp.bfloat16, False),      # rows off the bf16 tile
]


@pytest.mark.parametrize("k", [1, 3, 20])
@pytest.mark.parametrize("shape,rows,dtype,in_place", UNIT_LEAVES,
                         ids=lambda v: str(v))
def test_sqdiff_units_matches_ref(k, shape, rows, dtype, in_place):
    k1, k2 = jax.random.split(jax.random.PRNGKey(len(shape) * 31 + k))
    a = jax.random.normal(k1, (k,) + shape, dtype=dtype)
    b = jax.random.normal(k2, shape, dtype=dtype)
    assert kd.leaf_view(shape, dtype, rows).in_place == in_place
    out = kd.sqdiff_units(a, b, rows=rows, interpret=True)
    exp = np.stack([ref.sqdiff_rowsum(a[i].reshape(rows, -1),
                                      b.reshape(rows, -1))
                    for i in range(k)])
    assert out.shape == (k, rows) and out.dtype == jnp.float32
    np.testing.assert_allclose(out, exp,
                               rtol=1e-5 if dtype == jnp.float32 else 3e-3)
    np.testing.assert_allclose(ref.sqdiff_units(a, b, rows), exp, rtol=1e-5)


@pytest.mark.parametrize("in_axes", [(0, None), (0, 0), (None, 0)],
                         ids=["locals", "both", "global"])
@pytest.mark.parametrize("shape,rows", [((3, 3, 16, 128), 1),
                                        ((4, 16, 256), 4), ((2048, 10), 1),
                                        ((64,), 1)], ids=lambda v: str(v))
def test_sqdiff_units_under_vmap(shape, rows, in_axes):
    """The batching rule: a mapped locals axis joins the clients (one call),
    a mapped global makes one call per entry; both equal the oracle."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(rows + len(shape)))
    a = jax.random.normal(k1, (4, 3) + shape)
    b = jax.random.normal(k2, (4,) + shape)
    a_in = a if in_axes[0] == 0 else a[0]
    b_in = b if in_axes[1] == 0 else b[0]
    out = jax.vmap(lambda x, y: kd.sqdiff_units(x, y, rows=rows,
                                                interpret=True),
                   in_axes=in_axes)(a_in, b_in)
    exp = jax.vmap(lambda x, y: ref.sqdiff_units(x, y, rows),
                   in_axes=in_axes)(a_in, b_in)
    assert out.shape == (4, 3, rows)
    np.testing.assert_allclose(out, exp, rtol=1e-5)
    calls = str(jax.make_jaxpr(jax.vmap(
        lambda x, y: kd.sqdiff_units(x, y, rows=rows, interpret=True),
        in_axes=in_axes))(a_in, b_in)).count("pallas_call")
    assert calls == 1


@pytest.mark.parametrize("shape,rows,dtype,in_place", [
    ((3, 3, 512, 512), 1, jnp.float32, True),
    ((4, 16, 7168), 4, jnp.bfloat16, True),
    ((2048, 10), 1, jnp.float32, False),
    ((4, 7168, 16), 4, jnp.bfloat16, False),
    ((3, 3, 3, 64), 1, jnp.float32, False),
    ((512,), 1, jnp.float32, False),
], ids=lambda v: str(v))
def test_leaf_view_plan(shape, rows, dtype, in_place):
    """In place where the unit's minor dim is lane-aligned and its
    second-minor a sublane-tile multiple; otherwise 128-lane folds that
    pad only the tail, never rows up to a tile."""
    v = kd.leaf_view(shape, dtype, rows)
    assert v.in_place == in_place and v.rows == rows
    unit = int(np.prod(shape)) // rows
    if in_place:
        assert (v.m, v.n) == (unit // shape[-1], shape[-1])
        assert v.nbytes == int(np.prod(shape)) * np.dtype(dtype).itemsize
    else:
        assert v.n == kd.LANES and v.m == -(-unit // kd.LANES)


def test_vgg9_in_place_share():
    """conv2-conv7 (4,644,864 of 4,709,706 parameters) read in place."""
    from repro.core import UnitMap
    from repro.models import cnn
    params = jax.eval_shape(lambda: cnn.init_params(jax.random.PRNGKey(0),
                                                    cnn.VGGConfig()))
    umap = UnitMap.build(params)
    plan = dict(umap.divergence_plan(params))
    assert {p for p, v in plan.items() if v.in_place} == {
        f"conv{i}['w']" for i in range(2, 8)}
    share = umap.in_place_share(params)
    assert share >= 0.98
    assert share == pytest.approx(4644864 / sum(
        v.nbytes // 4 for v in plan.values()))


def _check_sqdiff_rowsum_property(r, c, seed):
    """∀ shapes: kernel == Σ(a−b)² per row; zero diff → zero."""
    k = jax.random.PRNGKey(seed)
    a = jax.random.normal(k, (r, c))
    out = kd.sqdiff_rowsum(a, a, interpret=True)
    np.testing.assert_allclose(out, np.zeros(r), atol=1e-6)
    b = a + 1.0
    out2 = kd.sqdiff_rowsum(a, b, interpret=True)
    np.testing.assert_allclose(out2, np.full(r, float(c)), rtol=1e-4)


# deterministic fallback grid — covers the invariant without hypothesis
@pytest.mark.parametrize("r,c,seed", [
    (1, 1, 0), (1, 300, 1), (17, 1, 2), (5, 129, 3), (8, 257, 12345),
])
def test_sqdiff_rowsum_property_cases(r, c, seed):
    _check_sqdiff_rowsum_property(r, c, seed)


def _check_masked_accumulate_property(r, c, w0, seed):
    """w = 0 rows leave acc unchanged; w scales linearly."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    acc = jax.random.normal(k1, (r, c))
    x = jax.random.normal(k2, (r, c))
    w = jnp.full((r,), w0, dtype=jnp.float32)
    out = ka.masked_accumulate(acc, x, w, interpret=True)
    np.testing.assert_allclose(out, np.asarray(acc) + w0 * np.asarray(x),
                               rtol=1e-4, atol=1e-5)
    zero = ka.masked_accumulate(acc, x, jnp.zeros((r,)), interpret=True)
    np.testing.assert_allclose(zero, acc, atol=1e-6)


@pytest.mark.parametrize("r,c,w0,seed", [
    (1, 1, -2.0, 0), (1, 200, 0.5, 1), (9, 1, 2.0, 2), (4, 100, -0.75, 77),
    (7, 63, 1.0, 31337),
])
def test_masked_accumulate_property_cases(r, c, w0, seed):
    _check_masked_accumulate_property(r, c, w0, seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(r=st.integers(1, 17), c=st.integers(1, 300),
           seed=st.integers(0, 2**31 - 1))
    def test_sqdiff_rowsum_property(r, c, seed):
        _check_sqdiff_rowsum_property(r, c, seed)

    @settings(max_examples=20, deadline=None)
    @given(r=st.integers(1, 9), c=st.integers(1, 200),
           w0=st.floats(-2, 2), seed=st.integers(0, 2**31 - 1))
    def test_masked_accumulate_property(r, c, w0, seed):
        _check_masked_accumulate_property(r, c, w0, seed)


def test_ops_dispatch_forced_pallas(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    a = jnp.ones((3, 100))
    b = jnp.zeros((3, 100))
    np.testing.assert_allclose(ops.sqdiff_rowsum(a, b), np.full(3, 100.0))
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "0")
    np.testing.assert_allclose(ops.sqdiff_rowsum(a, b), np.full(3, 100.0))
