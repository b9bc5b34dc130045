"""Client-axis sharding benchmark: rounds/sec vs device-mesh size.

    PYTHONPATH=src python -m benchmarks.shard_engine_bench
        [--devices 8] [--rounds N] [--reps R] [--clients N] [--json PATH]

Measures :func:`repro.federated.run_training_scan` on a client-heavy FedLDF
workload (N=K=64 clients by default) with the stacked client axis sharded
over a 'clients' mesh of 1, 2, 4, ... devices, against the unsharded
``mesh=None`` single-device engine. On CPU the devices are forced virtual
ones (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — the same
flag CI uses — so the scaling path is measurable in any container; each
virtual device executes on its own thread, so the ceiling is the physical
core count, not 8.

The workload uses ``local_steps=2``: after the first local step every
client's weights have diverged, so the remaining local-training matmuls are
per-client batched ops that XLA cannot collapse into one device-wide GEMM —
exactly the regime where the client axis is the scaling dimension (and the
regime of real FL, where clients run many local steps). With
``local_steps=1`` a single device can fuse the whole cohort's forward pass
into one multithreaded GEMM and sharding has nothing left to win on CPU.

On the CPU, when the current process lacks the requested device count
(e.g. invoked from benchmarks/run.py after JAX already initialised the
single real CPU device), the benchmark re-executes itself in a subprocess
with XLA_FLAGS set, streams its output, and returns the parsed results.
On an accelerator it never starts a child (the parent holds the chips):
it runs in-process on the devices that exist.

Also measures one 2-D ('clients', 'model') mesh point — the FSDP
configuration where params (and the EF residual store) live 1/M per device
— reporting both rounds/sec and the at-rest per-device param bytes, and
re-checks sharded-vs-unsharded trajectory equivalence on a fixed seed
(fp32 tolerance — reduction order differs across mesh sizes) including the
2-D mesh, the hierarchical two-tier reduce (``FLConfig(agg_group_size=...)``
at group sizes 2 and 4), and the sample-sharded placement
(``shard_samples=True`` vs replicated placement of the same affinity
layout, grouped cohort in both).

**Population scale** (``population_run`` / ``--pop-clients``): an
N=1e6-client, K=4096-cohort synthetic round on the widest mesh with
sample-axis sharding + client→device affinity and the hierarchical
aggregation tier, reporting per-round wall-clock, per-tier bytes/host
(intra-group vs cross-group — the flat reduce funnels all D−1 payloads
through one root, the two-tier reduce caps any host at 2·(G−1) ring
payloads), and at-rest dataset bytes/device (~1/D shrink vs replicated
placement, asserted).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from benchmarks.round_engine_bench import EQUIV_TOL  # single source

# paper-motivated, client-heavy: full participation of a 64-client cohort
D_IN, HIDDEN, N_CLASSES = 3072, 64, 10
LOCAL_STEPS = 2


def _mlp_params(key):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(key, 2)
    return {"l1": {"w": jax.random.normal(ks[0], (D_IN, HIDDEN)) * 0.02,
                   "b": jnp.zeros((HIDDEN,))},
            "head": {"w": jax.random.normal(ks[1], (HIDDEN, N_CLASSES)) * 0.1,
                     "b": jnp.zeros((N_CLASSES,))}}


def _mlp_loss(params, batch):
    import jax
    import jax.numpy as jnp
    x = batch["images"].reshape(batch["images"].shape[0], -1)
    h = jax.nn.relu(x @ params["l1"]["w"] + params["l1"]["b"])
    logits = h @ params["head"]["w"] + params["head"]["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, batch["labels"][:, None],
                                axis=-1).mean()


def _make_task(num_clients: int, batch: int, seed: int = 0):
    import jax
    from repro.data import (ClientShards, FederatedData, iid_partition,
                            make_image_dataset)
    from repro.federated import FLConfig
    train, _ = make_image_dataset(num_train=num_clients * 50, num_test=16,
                                  seed=1)
    parts = iid_partition(train.ys, num_clients, seed=seed)
    shards = ClientShards.from_federated(
        FederatedData(train.xs, train.ys, parts))
    params = _mlp_params(jax.random.PRNGKey(seed))

    def flcfg(mesh, **kw):
        return FLConfig(algo="fedldf", num_clients=num_clients,
                        clients_per_round=num_clients, top_n=4,
                        local_steps=LOCAL_STEPS, batch_per_client=batch,
                        mesh=mesh, **kw)

    return params, _mlp_loss, shards, flcfg


def _best_rates(fns: list, rounds: int, reps: int) -> list[float]:
    """Best-of-``reps`` rounds/sec for every candidate, measured
    *interleaved* (one rep of each per sweep) so ambient-load drift on a
    shared box biases all candidates equally instead of whichever ran
    last; first call per candidate warms the jit cache outside timing."""
    for fn in fns:
        fn()
    best = [float("inf")] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return [rounds / b for b in best]


def _mesh_sizes(limit: int) -> list[int]:
    sizes, d = [], 1
    while d <= limit:
        sizes.append(d)
        d *= 2
    return sizes


def run_local(devices: int = 8, rounds: int = 30, reps: int = 5,
              clients: int = 64, batch: int = 16,
              pop_clients: int = 1_000_000, pop_cohort: int = 4096,
              pop_rounds: int = 3, out=sys.stdout) -> dict:
    """Run in-process (requires >= ``devices`` JAX devices)."""
    import jax
    from repro.federated import run_training_scan
    from repro.launch.mesh import make_client_mesh

    params, loss, shards, flcfg = _make_task(clients, batch)
    print(f"clients={clients} (full participation) B={batch} "
          f"local_steps={LOCAL_STEPS} rounds={rounds} "
          f"devices={len(jax.devices())} backend={jax.default_backend()}",
          file=out)

    results = {"clients": clients, "batch": batch, "rounds": rounds,
               "devices": len(jax.devices()), "mesh": {}}
    sizes = _mesh_sizes(min(devices, len(jax.devices())))

    def runner(mesh, **kw):
        return lambda: run_training_scan(params, loss, shards,
                                         flcfg(mesh, **kw),
                                         rounds=rounds, seed=0)

    rates = _best_rates(
        [runner(None)] + [runner(make_client_mesh(d)) for d in sizes],
        rounds, reps)
    rate_un, mesh_rates = rates[0], rates[1:]
    results["unsharded"] = rate_un
    print(f"mesh=None (single-device engine): {rate_un:8.1f} rounds/s",
          file=out)
    for d, rate in zip(sizes, mesh_rates):
        results["mesh"][str(d)] = rate
        print(f"mesh={d} sharded engine         : {rate:8.1f} rounds/s "
              f"({rate / rate_un:.2f}x vs unsharded)", file=out)

    # headline: widest mesh vs the FASTER single-device variant (mesh=1 runs
    # the same shard_map machinery on one device; mesh=None is the plain
    # engine — comparing against the better of the two keeps us honest)
    widest = max(int(s) for s in results["mesh"])
    base = max(rate_un, results["mesh"]["1"])
    results["speedup"] = results["mesh"][str(widest)] / base
    print(f"speedup: {results['speedup']:.2f}x at {widest} devices vs best "
          f"1-device engine (ceiling = physical cores, "
          f"os.cpu_count()={os.cpu_count()})", file=out)

    # 2-D ('clients', 'model') mesh: the FSDP point — same round math, but
    # params (and the EF store, when on) live 1/M per device. Rate is
    # expected at-or-below the pure clients-split (training all-gathers the
    # model transiently); the per-device at-rest bytes are the win.
    total = min(devices, len(jax.devices()))
    model = 2
    # skip (don't crash) when the 2-D factorisation doesn't fit: model must
    # divide the device count and K (= clients, full participation) must
    # divide the resulting clients axis
    if total % model == 0 and clients % (total // model) == 0:
        mesh2d = make_client_mesh(total, model=model)
        results["model_mesh"] = {
            "model": model, "clients_axis": total // model,
            "rate": _best_rates([runner(mesh2d)], rounds, reps)[0]}
        p2d, _ = run_training_scan(params, loss, shards, flcfg(mesh2d),
                                   rounds=1, seed=0)
        dev_b = sum(x.addressable_shards[0].data.nbytes
                    for x in jax.tree.leaves(p2d))
        tot_b = sum(x.nbytes for x in jax.tree.leaves(p2d))
        results["model_mesh"]["param_bytes_per_device"] = dev_b
        results["model_mesh"]["param_bytes_total"] = tot_b
        print(f"mesh=({total // model}x{model}) clients x model   : "
              f"{results['model_mesh']['rate']:8.1f} rounds/s; at-rest "
              f"param bytes/device {dev_b} vs {tot_b} replicated "
              f"({dev_b / tot_b:.2f}x)", file=out)

    # hierarchical two-tier reduce at the widest mesh (group-local psum +
    # group-leader ppermute ring; FLConfig(agg_group_size=...)). On forced
    # CPU devices the rate should track the flat psum — the win the tier
    # buys (per-HOST cross-group traffic capped at O(G) instead of the
    # root's O(D)) is reported by the population run's byte split below.
    if widest > 1:
        gs = max(1, widest // 4)
        wide_mesh = make_client_mesh(widest)
        results["hier_rate"] = _best_rates(
            [runner(wide_mesh, agg_group_size=gs)], rounds, reps)[0]
        results["hier"] = {"group_size": gs, "devices": widest,
                           "rate": results["hier_rate"]}
        print(f"mesh={widest} two-tier (group={gs})  : "
              f"{results['hier_rate']:8.1f} rounds/s "
              f"({results['hier_rate'] / results['mesh'][str(widest)]:.2f}x "
              "vs flat psum)", file=out)

    results["equiv_max_diff"] = equivalence_check(out=out)
    results["equiv_ok"] = results["equiv_max_diff"] < EQUIV_TOL

    if pop_clients:
        results["population"] = population_run(
            devices=devices, clients=pop_clients, cohort=pop_cohort,
            rounds=pop_rounds, out=out)
    return results


def equivalence_check(rounds: int = 3, out=sys.stdout) -> float:
    """Sharded (every power-of-2 mesh) vs unsharded trajectories, fixed
    seed. Fp32 tolerance: cross-device psum changes fp reduction order.
    Also pins the hierarchical two-tier reduce (group sizes 2/4 at the
    widest mesh) against the same unsharded reference, and the
    sample-sharded placement against replicated placement of the same
    affinity layout (grouped cohort in both — same participants, so the
    trajectories must agree bit-for-bit up to fp32 gather order)."""
    import jax
    import jax.numpy as jnp
    from repro.federated import run_training_scan
    from repro.launch.mesh import make_client_mesh

    def tree_diff(a, b):
        return max(float(jnp.abs(x - y).max()) for x, y in
                   zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    params, loss, shards, flcfg = _make_task(16, 8)
    params_ref, _ = run_training_scan(params, loss, shards, flcfg(None),
                                      rounds=rounds, seed=0)
    worst = 0.0
    ndev = len(jax.devices())
    meshes = [(d, 1, 0) for d in _mesh_sizes(ndev)]
    # 2-D ('clients', 'model') FSDP point (K=16 clients above)
    if ndev % 2 == 0 and 16 % (ndev // 2) == 0:
        meshes.append((ndev, 2, 0))
    # hierarchical two-tier reduce at the widest mesh
    meshes.extend((ndev, 1, gs) for gs in (1, 2, 4)
                  if gs < ndev and ndev % gs == 0)
    for d, model, gs in meshes:
        ps, _ = run_training_scan(
            params, loss, shards,
            flcfg(make_client_mesh(d, model=model), agg_group_size=gs),
            rounds=rounds, seed=0)
        diff = tree_diff(params_ref, ps)
        worst = max(worst, diff)
        status = "OK" if diff < EQUIV_TOL else "FAIL"
        label = f"{d}" if model == 1 else f"{d // model}x{model}"
        if gs:
            label += f" group={gs}"
        print(f"equivalence mesh={label}: max|sharded-unsharded| = "
              f"{diff:.2e}  [{status}]", file=out)

    # sample-axis sharding: sharded vs replicated placement of the SAME
    # affinity layout (the drivers draw the cohort per group for both, so
    # the participant trajectory is identical — only data placement moves)
    if ndev > 1 and 16 % ndev == 0:
        mesh = make_client_mesh(ndev)
        aff = shards.with_affinity(ndev)
        p_rep, _ = run_training_scan(params, loss, aff.place(mesh),
                                     flcfg(mesh), rounds=rounds, seed=0)
        p_shd, _ = run_training_scan(params, loss, aff,
                                     flcfg(mesh, shard_samples=True),
                                     rounds=rounds, seed=0)
        diff = tree_diff(p_rep, p_shd)
        worst = max(worst, diff)
        status = "OK" if diff < EQUIV_TOL else "FAIL"
        print(f"equivalence mesh={ndev} sample-sharded vs replicated "
              f"placement: max diff = {diff:.2e}  [{status}]", file=out)
    return worst


def population_run(devices: int = 8, clients: int = 1_000_000,
                   cohort: int = 4096, rounds: int = 3,
                   out=sys.stdout) -> dict:
    """Population-scale synthetic round: N≈1e6 clients, K≈4096 cohort.

    One sample per client (16 features), tiny MLP — the point is the
    *round machinery* at population N, not the model: vectorized shard
    construction, per-group cohort draw, sample-sharded placement with
    client→device affinity, device-local gather, and the two-tier reduce.
    Reports per-round wall-clock (flat vs hierarchical reduce), at-rest
    dataset bytes/device (~1/D shrink vs replicated placement — enforced),
    and the static per-tier aggregation-traffic split per round.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import agg_tier_bytes
    from repro.data import ClientShards, FederatedData
    from repro.federated import FLConfig, run_training_scan
    from repro.launch.mesh import make_client_mesh

    d = min(devices, len(jax.devices()))
    clients -= clients % d          # N % D (affinity groups, FLConfig)
    cohort -= cohort % d            # K % G (per-group cohort draw)
    d_in, hidden = 16, 8
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((clients, d_in), dtype=np.float32)
    ys = rng.integers(0, N_CLASSES, size=clients).astype(np.int32)
    print(f"[population] N={clients:,} clients, K={cohort:,} cohort, "
          f"{d} devices, {rounds} rounds", file=out)

    t0 = time.perf_counter()
    parts = list(np.arange(clients, dtype=np.int64).reshape(clients, 1))
    shards = ClientShards.from_federated(FederatedData(xs, ys, parts))
    build_s = time.perf_counter() - t0
    print(f"[population] ClientShards.from_federated: {build_s:.2f}s "
          f"(vectorized; the per-client loop was O(N*S))", file=out)

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    params = {"l1": {"w": jax.random.normal(ks[0], (d_in, hidden)) * 0.1,
                     "b": jnp.zeros((hidden,))},
              "head": {"w": jax.random.normal(ks[1],
                                              (hidden, N_CLASSES)) * 0.1,
                       "b": jnp.zeros((N_CLASSES,))}}
    mesh = make_client_mesh(d)
    gs = max(1, d // 4)             # stand-in for devices-per-host

    # at-rest dataset footprint: replicated vs sample-sharded placement
    rep_b = shards.place(mesh).bytes_per_device()
    shd = shards.place(mesh, shard_samples=True)
    shd_b = shd.bytes_per_device()
    shrink = rep_b / shd_b
    print(f"[population] at-rest dataset bytes/device: {shd_b:,} sharded "
          f"vs {rep_b:,} replicated ({shrink:.1f}x shrink, D={d})",
          file=out)
    if d > 1 and shrink < 0.9 * d:
        raise RuntimeError(
            f"sample-axis sharding shrank at-rest bytes only {shrink:.2f}x "
            f"on {d} devices (expected ~{d}x)")

    def tr(**kw):
        cfg = FLConfig(algo="fedldf", num_clients=clients,
                       clients_per_round=cohort, top_n=2, local_steps=1,
                       batch_per_client=1, mesh=mesh, shard_samples=True,
                       **kw)
        return lambda: run_training_scan(params, _mlp_loss, shd, cfg,
                                         rounds=rounds, seed=0)

    flat_rate, hier_rate = _best_rates(
        [tr(), tr(agg_group_size=gs)], rounds, reps=2)
    print(f"[population] flat reduce      : {1 / flat_rate:8.3f} s/round",
          file=out)
    print(f"[population] two-tier (g={gs})  : {1 / hier_rate:8.3f} s/round",
          file=out)

    # static per-round aggregation-traffic split (payload = param bytes,
    # the Eq. 5 numerator tree riding the fused reduce)
    pbytes = float(sum(np.asarray(x).nbytes
                       for x in jax.tree.leaves(params)))
    tiers = {"flat": agg_tier_bytes(pbytes, d, 0),
             "hier": agg_tier_bytes(pbytes, d, gs)}
    for name, t in tiers.items():
        print(f"[population] {name} bytes/round: "
              f"intra={t['agg_intra_bytes']:,.0f} "
              f"cross={t['agg_cross_bytes']:,.0f} "
              f"busiest-host cross={t['agg_cross_bytes_per_host']:,.0f}",
              file=out)
    ratio = (tiers["hier"]["agg_cross_bytes_per_host"]
             / max(tiers["flat"]["agg_cross_bytes_per_host"], 1.0))
    print(f"[population] busiest-host cross-tier traffic: {ratio:.2f}x "
          "of flat (lower = the root is no longer the ceiling)", file=out)
    return {"clients": clients, "cohort": cohort, "devices": d,
            "group_size": gs, "rounds": rounds, "build_s": build_s,
            "rate": hier_rate, "flat_rate": flat_rate,
            "sec_per_round": 1.0 / hier_rate,
            "at_rest_bytes_per_device": shd_b,
            "at_rest_bytes_replicated": rep_b,
            "at_rest_shrink": shrink,
            "tier_bytes": tiers,
            "cross_host_ratio": ratio}


def run(devices: int = 8, rounds: int = 30, reps: int = 5,
        clients: int = 64, batch: int = 16,
        pop_clients: int = 1_000_000, pop_cohort: int = 4096,
        pop_rounds: int = 3, out=sys.stdout) -> dict:
    """Entry point for benchmarks/run.py.

    On an accelerator the benchmark runs in this process over the devices
    that exist, with mesh sizes capped at ``jax.device_count()``: a chip
    belongs to one process, and this one already holds it. On the CPU,
    when this process sees fewer than ``devices`` devices, it re-executes
    itself with forced host devices (the device count is fixed at JAX's
    first import; only a fresh process can change it)."""
    import jax
    if jax.default_backend() != "cpu":
        devices = min(devices, jax.device_count())
    if jax.device_count() >= devices:
        return run_local(devices, rounds, reps, clients, batch,
                         pop_clients=pop_clients, pop_cohort=pop_cohort,
                         pop_rounds=pop_rounds, out=out)

    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "--xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (f"{flags} --xla_force_host_platform_device_count="
                        f"{devices}").strip()
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    with_json = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "..", f".shard_bench_{os.getpid()}.json")
    cmd = [sys.executable, "-m", "benchmarks.shard_engine_bench",
           "--devices", str(devices), "--rounds", str(rounds),
           "--reps", str(reps), "--clients", str(clients),
           "--batch", str(batch), "--pop-clients", str(pop_clients),
           "--pop-cohort", str(pop_cohort),
           "--pop-rounds", str(pop_rounds), "--json", with_json]
    print(f"# re-exec with XLA_FLAGS={env['XLA_FLAGS']!r}", file=out)
    proc = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    print(proc.stdout, end="", file=out)
    try:
        with open(with_json) as f:
            return json.load(f)
    except OSError:
        raise SystemExit(
            f"[shard] subprocess failed (exit {proc.returncode})")
    finally:
        try:
            os.remove(with_json)
        except OSError:
            pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--pop-clients", type=int, default=1_000_000,
                    help="population-scale run size (0 disables)")
    ap.add_argument("--pop-cohort", type=int, default=4096)
    ap.add_argument("--pop-rounds", type=int, default=3)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    results = run(devices=args.devices, rounds=args.rounds, reps=args.reps,
                  clients=args.clients, batch=args.batch,
                  pop_clients=args.pop_clients, pop_cohort=args.pop_cohort,
                  pop_rounds=args.pop_rounds)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
    return 0 if results.get("equiv_ok") else 1


if __name__ == "__main__":
    sys.exit(main())
