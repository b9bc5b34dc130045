"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs here. The TPU compiler that ships with jax compiles each
kernel for a v5e chip that is described, not attached, and refuses what
the chip would refuse: tiles not aligned to its layout, more VMEM than a
kernel may use, a program that does not fit in HBM. Each kernel is fed
as the round engine feeds it: VGG-9's largest leaf, conv7's
(3, 3, 512, 512) weight, reshaped to one unit row of 2,359,296, stacked
over K=20 clients where the engine stacks them.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports every test file.

The last tests check where ``repro.launch.compile_cache`` puts the
persistent compilation cache.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import aggregate, divergence, uplink

K = 20
CONV7 = (3, 3, 512, 512)
HBM_BUDGET = 4e9   # arguments + temp of the EF uplink (was 18.56G)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for_chip(one_chip):
    """Compile ``fn`` at abstract shapes on one described v5e chip, with
    the persistent compilation cache off (a described chip's entries
    cannot be read back). Compiled programs are kept per name."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    done = {}

    def compile_(name, fn, *shapes):
        if name not in done:
            args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                    for s, d in shapes]
            done[name] = jax.jit(fn).lower(*args).compile()
        return done[name]

    yield compile_
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _rows(x):
    return x.reshape(K, 1, -1)


f32, i8 = jnp.float32, jnp.int8
KERNELS = {
    # the vmap round scores every client's leaf: vmap over K of (1, C)
    "sqdiff_rowsum": (
        jax.vmap(lambda a, b: divergence.sqdiff_rowsum(
            a.reshape(1, -1), b.reshape(1, -1), interpret=False)),
        [((K,) + CONV7, f32), ((K,) + CONV7, f32)]),
    # the scan round accumulates one client's (1, C) row at a time
    "masked_accumulate": (
        lambda a, x, w: aggregate.masked_accumulate(
            a.reshape(1, -1), x.reshape(1, -1), w, interpret=False),
        [(CONV7, f32), (CONV7, f32), ((1,), f32)]),
    "fused_uplink": (
        lambda l, s, w: uplink.fused_uplink(_rows(l), s, w,
                                            interpret=False),
        [((K,) + CONV7, i8), ((K, 1), f32), ((K, 1), f32)]),
    "fused_uplink_ef": (
        lambda l, s, w, g, v, e: uplink.fused_uplink_ef(
            _rows(l), s, w, g, _rows(v), _rows(e), interpret=False),
        [((K,) + CONV7, i8), ((K, 1), f32), ((K, 1), f32), ((K, 1), f32),
         ((K,) + CONV7, f32), ((K,) + CONV7, f32)]),
    # a stacked bf16 leaf: 28 unit rows of 4,194,304
    "sqdiff_rowsum_bf16": (
        lambda a, b: divergence.sqdiff_rowsum(a, b, interpret=False),
        [((28, 4194304), jnp.bfloat16), ((28, 4194304), jnp.bfloat16)]),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(compile_for_chip, name):
    fn, shapes = KERNELS[name]
    compiled = compile_for_chip(name, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_uplink_ef_fits_in_hbm(compile_for_chip):
    """The uplink no longer pads a one-row leaf to 32 rows: arguments plus
    temp of the EF kernel at conv7 with K=20 stay far below one chip's
    HBM (row padding made them 18.56G, refused on a 15.75G v5e)."""
    fn, shapes = KERNELS["fused_uplink_ef"]
    mem = compile_for_chip("fused_uplink_ef", fn, *shapes).memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BUDGET, used


# ----------------------------------------------------------------------
# the persistent compilation cache: a fixed path, or the one JAX was given
# ----------------------------------------------------------------------
@pytest.fixture
def cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_defaults_to_checkout(monkeypatch, tmp_path,
                                            cache_config):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache(str(tmp_path))
    assert path == str(tmp_path / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_leaves_env_dir_alone(monkeypatch, tmp_path,
                                            cache_config):
    from repro.launch.compile_cache import enable_compile_cache
    env = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(str(tmp_path)) == env
    assert jax.config.jax_compilation_cache_dir == before
