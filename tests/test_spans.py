"""Driver spans and round phases (``repro.telemetry.profiling.span`` /
``phase``), on the CPU at tiny sizes.

- a traced ``run_training_scan`` call holds each ``fl.scan.*`` span once,
  inside its ``fl.scan`` parent, which carries ``start_round`` and
  ``rounds``; a traced ``run_training`` holds each ``fl.host.*`` span once
  a round, inside the ``fl.host`` span carrying that ``round``;
- the compiled block's HLO ``op_name`` metadata names every phase: vmap
  FedLDF, vmap 8-bit packed EF (with taps), scan mode, and the mesh
  engine on 4 virtual CPU devices (``fl.collective``);
- the phases change nothing else: with them turned off the compiled
  block is the same text less its metadata, and gives bit-identical
  results.
"""
from __future__ import annotations

import contextlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spantrace, tracereduce
from repro.core import comm as comm_mod
from repro.core.units import UnitMap
from repro.core.wire import CompressionConfig
from repro.data import (ClientShards, FederatedData, iid_partition,
                        make_image_dataset)
from repro.federated import FLConfig, run_training, run_training_scan, server
from repro.federated.strategies import make_strategy
from repro.telemetry import TelemetryConfig
from repro.telemetry import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_CLIENTS, K = 8, 4
ROUND_PHASES = {"fl.sample", "fl.local", "fl.eq3", "fl.eq4", "fl.eq5",
                "fl.comm"}
SCAN_SPANS = ("fl.scan.prepare", "fl.scan.copy_carry", "fl.scan.dispatch",
              "fl.scan.pull", "fl.scan.log", "fl.scan.finish")
HOST_SPANS = ("fl.host.sample", "fl.host.gather", "fl.host.dispatch",
              "fl.host.pull", "fl.host.log", "fl.host.eval")


def _params(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    return {"l1": {"w": jax.random.normal(ks[0], (3072, 16)) * 0.02,
                   "b": jnp.zeros((16,))},
            "head": {"w": jax.random.normal(ks[1], (16, 10)) * 0.1,
                     "b": jnp.zeros((10,))}}


def _loss(params, batch):
    x = batch["images"].reshape(batch["images"].shape[0], -1)
    h = jax.nn.relu(x @ params["l1"]["w"] + params["l1"]["b"])
    logp = jax.nn.log_softmax(h @ params["head"]["w"] + params["head"]["b"])
    return -jnp.take_along_axis(logp, batch["labels"][:, None],
                                axis=-1).mean()


@pytest.fixture(scope="module")
def task():
    train, _ = make_image_dataset(num_train=320, num_test=16, seed=1)
    parts = iid_partition(train.ys, N_CLIENTS, seed=0)
    return _params(), FederatedData(train.xs, train.ys, parts)


def _fl(**kw):
    base = dict(algo="fedldf", num_clients=N_CLIENTS, clients_per_round=K,
                top_n=2, batch_per_client=8)
    base.update(kw)
    return FLConfig(**base)


def _traced(fn, tmp_path):
    """Host spans of the program recorded while ``fn()`` runs."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    trace = spantrace.extract(tracereduce.find_xplane(str(tmp_path)))
    return [h for h in trace["host"] if h[0].startswith("fl.")]


def _inside(child, parent):
    return (parent[1] <= child[1]
            and child[1] + child[2] <= parent[1] + parent[2])


# ----------------------------------------------------------------------
# host spans
# ----------------------------------------------------------------------
def test_scan_driver_spans_once_per_call(task, tmp_path):
    params, data = task
    fl = _fl()
    run_training_scan(params, _loss, data, fl, rounds=2, seed=0)  # compile
    spans = _traced(lambda: run_training_scan(
        params, _loss, data, fl, rounds=2, seed=0, start_round=5),
        tmp_path)
    names = [s[0] for s in spans]
    assert names.count("fl.scan") == 1
    parent = next(s for s in spans if s[0] == "fl.scan")
    assert parent[3] == {"start_round": 5, "rounds": 2}
    for name in SCAN_SPANS:
        assert names.count(name) == 1, name
        assert _inside(next(s for s in spans if s[0] == name), parent)
    assert "fl.scan.eval" not in names       # no eval function


def test_scan_driver_eval_span_per_block(task, tmp_path):
    params, data = task
    eval_fn = jax.jit(lambda p: jnp.float32(0.5))
    spans = _traced(lambda: run_training_scan(
        params, _loss, data, _fl(), rounds=3, seed=0, eval_fn=eval_fn,
        eval_every=2), tmp_path)
    names = [s[0] for s in spans]
    # blocks end after rounds 0 and 2: two dispatches, two evals
    assert names.count("fl.scan.dispatch") == names.count("fl.scan.eval") \
        == 2
    assert names.count("fl.scan") == names.count("fl.scan.prepare") == 1


@pytest.mark.parametrize("sampler", ["jax", "host"])
def test_host_driver_spans_once_per_round(task, tmp_path, sampler):
    params, data = task
    eval_fn = jax.jit(lambda p: jnp.float32(0.5))
    run_training(params, _loss, data, _fl(), rounds=1, seed=0,
                 sampler=sampler, eval_fn=eval_fn, eval_every=1)
    spans = _traced(lambda: run_training(
        params, _loss, data, _fl(), rounds=3, seed=0, sampler=sampler,
        eval_fn=eval_fn, eval_every=1, start_round=2), tmp_path)
    rounds = [s for s in spans if s[0] == "fl.host"]
    assert [s[3]["round"] for s in rounds] == [2, 3, 4]
    for name in HOST_SPANS:
        kids = [s for s in spans if s[0] == name]
        assert len(kids) == 3, name
        for kid, parent in zip(kids, rounds):
            assert _inside(kid, parent), name


def test_host_driver_eval_span_only_on_eval_rounds(task, tmp_path):
    params, data = task
    eval_fn = jax.jit(lambda p: jnp.float32(0.5))
    spans = _traced(lambda: run_training(
        params, _loss, data, _fl(), rounds=4, seed=0, sampler="jax",
        eval_fn=eval_fn, eval_every=3), tmp_path)
    names = [s[0] for s in spans]
    assert names.count("fl.host") == names.count("fl.host.log") == 4
    assert names.count("fl.host.eval") == 2     # rounds 0 and 3


def test_span_is_inert_without_a_trace():
    with profiling.span("fl.test", round=1):
        pass


# ----------------------------------------------------------------------
# device phases
# ----------------------------------------------------------------------
def _block_args(params, data, fl):
    umap = UnitMap.build(params)
    shards = ClientShards.from_federated(data)
    state = make_strategy(fl).init_state(params, fl.num_clients)
    carry = (jax.tree.map(jnp.copy, params), state,
             comm_mod.comm_acc_init())
    return umap, (carry, shards, shards.data_sizes(),
                  jax.random.PRNGKey(0), jnp.int32(0))


def _compiled_block(params, data, fl):
    umap, args = _block_args(params, data, fl)
    block = server._build_block_fn(_loss, umap, fl)
    return block.lower(*args, 2).compile().as_text()


def _phases_in(hlo: str) -> set:
    return {m for path in re.findall(r'op_name="([^"]*)"', hlo)
            for m in re.findall(r"(?:^|/)(fl\.[a-z0-9_]+)", path)}


@pytest.mark.parametrize("case,fl_kw,want", [
    ("vmap_fedldf", dict(mode="vmap"), ROUND_PHASES),
    ("vmap_q8_packed_ef",
     dict(mode="vmap",
          compression=CompressionConfig(bits=8, error_feedback=True),
          telemetry=TelemetryConfig(taps=True)),
     ROUND_PHASES | {"fl.uplink", "fl.state", "fl.taps"}),
    ("scan", dict(mode="scan"), ROUND_PHASES),
])
def test_compiled_block_names_every_phase(task, case, fl_kw, want):
    params, data = task
    got = _phases_in(_compiled_block(params, data, _fl(**fl_kw)))
    assert want <= got, sorted(want - got)


def test_phases_change_nothing_but_metadata(task, monkeypatch):
    params, data = task
    fl = _fl(compression=CompressionConfig(bits=8, error_feedback=True))
    umap, args = _block_args(params, data, fl)
    scoped = server._build_block_fn(_loss, umap, fl)
    hlo_scoped = scoped.lower(*args, 2).compile().as_text()
    out_scoped = scoped(*_block_args(params, data, fl)[1], 2)
    monkeypatch.setattr(profiling, "phase",
                        lambda name: contextlib.nullcontext())
    bare = server._build_block_fn(_loss, umap, fl)
    hlo_bare = bare.lower(*args, 2).compile().as_text()
    out_bare = bare(*_block_args(params, data, fl)[1], 2)
    assert "fl.eq3" in hlo_scoped and "fl.eq3" not in hlo_bare

    def strip(text):
        # the module line and the computations less their metadata,
        # without the tables of source locations between them
        lines = text.splitlines()
        first = next(i for i, line in enumerate(lines)
                     if line.startswith(("%", "ENTRY")))
        body = "\n".join(lines[:1] + lines[first:])
        assert "fusion" in body and "ENTRY" in body
        return re.sub(r", metadata=\{[^}]*\}", "", body)

    assert strip(hlo_scoped) == strip(hlo_bare)
    for a, b in zip(jax.tree.leaves(out_scoped), jax.tree.leaves(out_bare)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


MESH_SCRIPT = textwrap.dedent("""
    import re
    import jax, jax.numpy as jnp
    from repro.core import comm as comm_mod
    from repro.core.units import UnitMap
    from repro.data import (ClientShards, FederatedData, iid_partition,
                            make_image_dataset)
    from repro.federated import FLConfig, server
    from repro.federated.strategies import make_strategy
    from repro.launch.mesh import make_client_mesh
    from repro.launch.sharding import fl_param_specs, to_named

    def loss(p, b):
        x = b["images"].reshape(b["images"].shape[0], -1)
        return ((x @ p["w"]) ** 2).mean()

    train, _ = make_image_dataset(num_train=128, num_test=8, seed=1)
    data = FederatedData(train.xs, train.ys, iid_partition(train.ys, 8, 0))
    mesh = make_client_mesh(4)
    fl = FLConfig(num_clients=8, clients_per_round=4, top_n=2,
                  batch_per_client=4, mesh=mesh)
    params = {"w": jnp.ones((3072, 4)) * 0.01}
    params = jax.device_put(params, to_named(fl_param_specs(params, mesh),
                                             mesh))
    shards = ClientShards.from_federated(data).place(mesh)
    block = server._build_block_fn(loss, UnitMap.build(params), fl)
    carry = (params, make_strategy(fl).init_state(params, 8, mesh),
             comm_mod.comm_acc_init())
    hlo = block.lower(carry, shards, shards.data_sizes(),
                      jax.random.PRNGKey(0), jnp.int32(0),
                      1).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', hlo)
    print(sorted({m for p in paths
                  for m in re.findall(r"(?:^|/)(fl\\.[a-z0-9_]+)", p)}))
""")


def test_mesh_block_names_collectives():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           ROOT]))
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = set(eval(out.stdout.strip().splitlines()[-1]))
    assert {"fl.collective", "fl.local", "fl.eq3", "fl.eq4",
            "fl.eq5"} <= got, got
