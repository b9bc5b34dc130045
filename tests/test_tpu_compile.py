"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs here. The TPU compiler that ships with jax compiles each
kernel for a v5e chip that is described, not attached, and refuses what
the chip would refuse: tiles not aligned to its layout, more VMEM than a
kernel may use, a program that does not fit in HBM. Each kernel is fed
as the round engine feeds it: VGG-9's largest leaf, conv7's
(3, 3, 512, 512) weight, reshaped to one unit row of 2,359,296, stacked
over K=20 clients where the engine stacks them. The MoE layer's grouped
matmul (megablox) is compiled at DeepSeek-V2-Lite's widths.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports every test file.

The last tests check where ``repro.launch.compile_cache`` puts the
persistent compilation cache.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import aggregate, divergence, uplink
from repro.models import moe

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
    # the vmap round scores the K stacked locals of a leaf in one call,
    # each leaf in its own shape
    "sqdiff_rowsum": (
        lambda a, b: divergence.sqdiff_units(a, b, interpret=False),
        [((K,) + CONV7, f32), (CONV7, f32)]),
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
    # a stacked bf16 leaf: 28 unit rows of 4,194,304, one client at a time
    # as the scan round feeds it
    "sqdiff_rowsum_bf16": (
        lambda a, b: divergence.sqdiff_units(a, b, rows=28,
                                             interpret=False),
        [((1, 28, 2048, 2048), jnp.bfloat16),
         ((28, 2048, 2048), jnp.bfloat16)]),
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


def _pads(text):
    """(output elements, padding config) of each pad in compiled text."""
    out = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* pad\(.*?padding=(\S+?),",
                         text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        out.append((math.prod(dims), m.group(2)))
    return out


def test_sqdiff_rowsum_reads_conv7_in_place(compile_for_chip):
    """K=20 stacked conv7 locals reach the kernel as bitcasts: no pad
    larger than the leaf (row padding wrote f32[20,8,2359296]), and temp
    within 5 % of the arguments (it was 426 % for all of VGG-9)."""
    fn, shapes = KERNELS["sqdiff_rowsum"]
    compiled = compile_for_chip("sqdiff_rowsum", fn, *shapes)
    assert all(n <= math.prod(CONV7) for n, _ in _pads(compiled.as_text()))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 0.05 * mem.argument_size_in_bytes


def test_vgg9_eq3_has_no_row_padding(compile_for_chip, monkeypatch):
    """The whole of VGG-9's Eq. 3 at K=20, through the kernel as the vmap
    round calls it: the only pads fill a unit row's tail up to a lane
    tile, and temp stays within 5 % of the 399 MB of arguments."""
    from repro.core import UnitMap
    from repro.kernels import ops
    from repro.models import cnn
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    params = jax.eval_shape(lambda: cnn.init_params(jax.random.PRNGKey(0),
                                                    cnn.VGGConfig()))
    umap = UnitMap.build(params)
    leaves, tdef = jax.tree.flatten(params)
    n = len(leaves)

    def eq3(*xs):
        return umap.divergence_batched(jax.tree.unflatten(tdef, xs[:n]),
                                       jax.tree.unflatten(tdef, xs[n:]))

    compiled = compile_for_chip(
        "vgg9_eq3", eq3, *[((K,) + l.shape, l.dtype) for l in leaves],
        *[(l.shape, l.dtype) for l in leaves])
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= n
    for _, cfg in _pads(text):
        *major, minor = cfg.split("x")
        assert all(d == "0_0" for d in major), cfg
        lo, hi = (int(v) for v in minor.split("_")[:2])
        assert lo == 0 and hi < 128, cfg
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 0.05 * mem.argument_size_in_bytes


def test_vmapped_divergence_is_one_call_per_leaf(compile_for_chip,
                                                 monkeypatch):
    """``jax.vmap`` over the one-client ``UnitMap.divergence`` (as
    ``examples/quickstart.py`` calls it) compiles for the chip with the
    kernel's HBM constraint, and the mapped axis becomes the kernel's
    client axis: one call per leaf, no row padding, temp within 5 %."""
    from repro.core import UnitMap
    from repro.kernels import ops
    from repro.models import cnn
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    params = jax.eval_shape(lambda: cnn.init_params(jax.random.PRNGKey(0),
                                                    cnn.VGGConfig()))
    umap = UnitMap.build(params)
    leaves, tdef = jax.tree.flatten(params)
    n = len(leaves)

    def eq3(*xs):
        ref = jax.tree.unflatten(tdef, xs[n:])
        return jax.vmap(lambda p: umap.divergence(p, ref))(
            jax.tree.unflatten(tdef, xs[:n]))

    compiled = compile_for_chip(
        "vgg9_eq3_vmap", eq3, *[((K,) + l.shape, l.dtype) for l in leaves],
        *[(l.shape, l.dtype) for l in leaves])
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n
    for _, cfg in _pads(text):
        assert all(d == "0_0" for d in cfg.split("x")[:-1]), cfg
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 0.05 * mem.argument_size_in_bytes


def test_sqdiff_operands_stay_in_hbm(compile_for_chip):
    """Small stacked LoRA leaves made by a fusion, as local training makes
    them: XLA would write them to VMEM (``S(1)``) and the kernel's HBM
    reads would happen outside it; the kernel keeps both operands in HBM,
    so its time holds the work its roofline counts."""
    bf16 = jnp.bfloat16

    def eq3(a, g, b, a2, g2, b2):
        return (divergence.sqdiff_units((a - 0.1 * g).astype(bf16), b,
                                        rows=4, interpret=False),
                divergence.sqdiff_units((a2 - 0.1 * g2).astype(bf16), b2,
                                        rows=4, interpret=False))

    lora_b, lora_a = (1, 4, 16, 19200), (1, 4, 7168, 16)
    text = compile_for_chip(
        "sqdiff_lora", eq3, (lora_b, bf16), (lora_b, bf16),
        (lora_b[1:], bf16), (lora_a, bf16), (lora_a, bf16),
        (lora_a[1:], bf16)).as_text()
    defs = dict(re.findall(r"(%[\w.\-]+) = (\S+) ", text))
    calls = re.findall(r"= \S+ custom-call\(([^)]*)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert len(calls) == 2
    for ops in calls:
        for op in ops.split(", "):
            assert "S(1)" not in defs[op], (op, defs[op])


def _lora_expert_grads(x, w, a, b, gs, c):
    """Gradients w.r.t. the rows and the LoRA factors of one expert
    projection as the MoE layer makes it (the base ``w`` is frozen)."""
    gm = lambda u, v: moe.grouped_matmul(u, v, gs, kernel="megablox")
    loss = lambda x, a, b: jnp.sum(
        (c * (gm(x, w) + gm(gm(x, a), b))).astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(x, a, b)


@pytest.mark.parametrize("clients", [0, 2])
def test_grouped_matmul_compiles_for_v5e(compile_for_chip, clients):
    """The MoE layer's megablox path at DeepSeek-V2-Lite's widths (4,096
    tokens x top-6 rows, 64 experts, 2048 -> 1408, rank 16), alone and
    vmapped over stacked clients (the batching rule's ``lax.map``). Six
    kernels either way: the rank-16 forward product the gradients need,
    three row gradients (``gmm``) and the two factors' (``tgmm``); the
    frozen base takes no weight gradient and no kernel reads it twice."""
    bf16, e, d, f, r = jnp.bfloat16, 64, 2048, 1408, 16
    rows = 4096 * 6
    lead = (clients,) if clients else ()
    fn = (jax.vmap(_lora_expert_grads, in_axes=(0, None, None, None, 0, 0))
          if clients else _lora_expert_grads)
    text = compile_for_chip(
        f"grouped_matmul_{clients}", fn, (lead + (rows, d), bf16),
        ((e, d, f), bf16), ((e, d, r), bf16), ((e, r, f), bf16),
        (lead + (e,), jnp.int32), (lead + (rows, f), bf16)).as_text()
    kernels = re.findall(r"%(\S+) = \S+ custom-call\([^)]*\)[^\n]*"
                         r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(kernels) == 6, kernels
    assert sum("tgmm" in k for k in kernels) == 2, kernels
    assert "ragged" not in text


def _v2lite_scan_round(compile_for_chip, monkeypatch, algo):
    """The LoRA scan round at DeepSeek-V2-Lite's widths, as the benchmark
    cell runs it (K=4, top-2, one sequence a client, rank 16 on the
    cell's projections, checkpointed layers), cut to the dense layer and
    one MoE layer and to 512 tokens: the MoE layers are one scanned body,
    so the round's text holds each client pass's kernels once at any
    depth. Returns the compiled text."""
    import dataclasses

    from repro.configs import deepseek_v2_lite
    from repro.core import UnitMap
    from repro.federated import FLConfig, make_strategy
    from repro.federated.server import build_round_scan
    from repro.kernels import ops
    from repro.models import lora, transformer
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    # the grouped matmul takes megablox where the backend is a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(deepseek_v2_lite.config(), num_layers=2,
                              remat_blocks=True)
    key = jax.random.PRNGKey(0)
    full = jax.eval_shape(lambda: lora.inject_lora(
        key, transformer.init_params(key, cfg), rank=16))
    part = lora.lora_partition(full)
    train, frozen = part.split(full)
    k, t = 4, 512
    fl = FLConfig(algo=algo, num_clients=20, clients_per_round=k, top_n=2,
                  mode="scan", partition=part)
    round_fn = build_round_scan(transformer.make_lm_loss(cfg),
                                UnitMap.build(train), fl)
    state = jax.eval_shape(lambda: make_strategy(fl).init_state(train, 20))
    toks = jax.ShapeDtypeStruct((k, 1, t), jnp.int32)
    args = (train, {"tokens": toks, "labels": toks},
            jax.ShapeDtypeStruct((k,), f32),
            jax.ShapeDtypeStruct((2,), jnp.uint32), state, frozen)
    leaves, tdef = jax.tree.flatten(args)
    return compile_for_chip(
        f"v2lite_scan_round_{algo}",
        lambda *xs: round_fn(*jax.tree.unflatten(tdef, xs)),
        *[(l.shape, l.dtype) for l in leaves]).as_text()


def _grouped_kernels(text):
    return [k for k in re.findall(r"%(\S+) = \S+ custom-call\([^)]*\)"
                                  r"[^\n]*custom_call_target="
                                  r"\"tpu_custom_call\"", text)
            if "gmm" in k]


def test_v2lite_fedldf_scan_round_trains_each_client_once(compile_for_chip,
                                                         monkeypatch):
    """FedLDF's scan round keeps each unit's top-2 locals as the clients
    train: one client loop, 33 grouped-matmul kernels (the parent's
    recompute pass made 66); FedLAMA, which selects from every client's
    score, keeps its two loops and twice the kernels."""
    once = _grouped_kernels(_v2lite_scan_round(compile_for_chip,
                                               monkeypatch, "fedldf"))
    twice = _grouped_kernels(_v2lite_scan_round(compile_for_chip,
                                                monkeypatch, "fedlama"))
    assert len(once) == 33, once
    assert len(twice) == 2 * len(once), twice


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
