#!/usr/bin/env python3
"""On-chip smoke run of the FedLDF round engine at the paper's VGG-9 scale.

    python chip_smoke.py              # one TPU chip: phases 1-5
    python chip_smoke.py --chips 4    # four TPU chips: the mesh engine only

The workload is the paper's protocol (§III-A) at full widths: VGG-9
(8 conv + 1 FC, 4.7 M parameters) on 50,000 synthetic CIFAR-10-shaped
training images split IID over N=50 clients, K=20 clients per round,
FedLDF top-n=4, B=32, one local step. Weights and data come from --seed.

Phases run in one process, in order. Each prints its result, its seconds
and the device-memory peak:

1. device: a TPU must be present; anything else exits 1 with no result.
2. kernels: the four Pallas kernels at VGG-9 leaf shapes (K=20 for the
   uplink and for Eq. 3 as the vmap round calls it), against
   ``kernels/ref.py``, each compiled program holding a ``tpu_custom_call``.
3. engine: ``run_training_scan`` for 3 rounds with an eval after each,
   cold and then warm, against ``run_training(sampler="jax")``.
4. sequential clients: ``mode="scan"`` (FedLDF's one client pass, each
   unit's top-n locals kept as the clients train) for 2 rounds, against
   the stacked-client engine's first 2 rounds.
5. packed uplink with error feedback: ``run_training`` with
   ``CompressionConfig(bits=8, error_feedback=True)`` for 3 rounds.

``--chips 4`` runs phase 1 and then the mesh engine only:
``run_training_scan`` on ``make_client_mesh(4)`` (5 clients per chip),
with the flat psum and with ``agg_group_size=2``, each against the
one-device engine in the same process.

Every engine comparison runs at the default matmul precision, where its
gap is printed, and again at "highest", where the gap must be within
``EQUIV_TOL``. Beside it is printed what rounding alone does over the
same rounds (see ``Smoke.compare``).

On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Any failed
check raises and exits non-zero. The seconds printed are smoke timings
(compilation included where marked), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

K, TOP_N, N_CLIENTS, BATCH = 20, 4, 50, 32
N_TRAIN, N_TEST, EVAL_CHUNKS = 50_000, 10_000, 10
ROUNDS = 3
# Pallas kernel vs kernels/ref.py: max |out - ref| over max |ref|. The
# reference runs at "highest" matmul precision, so both sides are f32
# and differ only in summation order.
KERNEL_TOL = 1e-5
# what a compiled Pallas TPU kernel leaves in the compiled program text;
# interpret mode and the jnp reference leave no such call
KERNEL_MARK = "tpu_custom_call"
# Engine agreement at "highest" precision: max |param diff| after the
# phase's rounds. The CPU tests hold the same comparisons to 2e-5
# (benchmarks/round_engine_bench.EQUIV_TOL) at small widths. At full
# widths on TPU v5e the gaps are 9.716e-05 (phase 3, 3 rounds),
# 2.162e-04 (phase 4, 2 rounds) and 2.697e-03 (four-chip mesh, 3
# rounds), the same in every run: the paper's lr=0.05 makes the round
# losses climb 3 -> 13 -> 14, and that trajectory grows each
# summation-order difference. Nudging the start params by one ulp moves
# the one-device engine itself by 2.310e-03 over the same 3 rounds.
EQUIV_TOL = 3e-3


def _say(*parts) -> None:
    print(*parts, flush=True)


class Smoke:
    """Shared state of one smoke run: device, workload, phase clock."""

    def __init__(self, seed: int):
        self.seed = seed
        # agreement checks that failed: every gap is printed before the
        # run exits non-zero, so one run shows all of them
        self.failures: list[str] = []

    def compare(self, name: str, run_a, run_b, a, b) -> None:
        """Agreement of two engines run on the same seed.

        ``a`` and ``b`` are their final params at the default precision,
        where the TPU runs an f32 convolution or matmul as one bf16 pass:
        two differently compiled programs round differently there, and
        the trajectory grows the difference, so that gap is printed only.
        ``run_a`` and ``run_b`` map start params to final params; both run
        again from ``params0`` at "highest" precision, where both are f32
        and differ in summation order only, and that gap must be within
        ``EQUIV_TOL``. Beside it is printed the gap that rounding alone
        makes: ``run_b`` from ``params0`` against ``run_b`` from
        ``params0`` nudged up by one ulp.
        """
        import jax
        import jax.numpy as jnp
        _say(f"  {name}: max|param diff| = {tree_gap(a, b):.3e} at the "
             "default precision")
        nudged = jax.tree.map(lambda x: jnp.nextafter(x, jnp.inf),
                              self.params0)
        with jax.default_matmul_precision("highest"):
            ref = run_b(self.params0)
            gap = tree_gap(run_a(self.params0), ref)
            floor = tree_gap(run_b(nudged), ref)
        _say(f"  {name}: max|param diff| = {gap:.3e} at 'highest' "
             f"(tolerance {EQUIV_TOL:.1e}); a one-ulp nudge of the start "
             f"moves the second by {floor:.3e}")
        if not gap <= EQUIV_TOL:
            self.failures.append(f"{name}: gap {gap:.3e} exceeds "
                                 f"{EQUIV_TOL:.1e}")

    # ------------------------------------------------------------------
    @staticmethod
    def phase(name: str, fn):
        """Run one phase; print its result, seconds and memory peak."""
        from repro.telemetry.profiling import device_memory_peak
        _say(f"[{name}] start")
        t0 = time.perf_counter()
        result = fn()
        secs = time.perf_counter() - t0
        peak = device_memory_peak()
        if peak is None:
            raise RuntimeError(f"[{name}] device_memory_peak() is None")
        _say(f"[{name}] ok {result} seconds={secs:.3f} "
             f"mem_peak_bytes={peak}")
        return result

    # ------------------------------------------------------------------
    def build_workload(self) -> str:
        import jax
        import jax.numpy as jnp
        from repro.configs import vgg9
        from repro.configs.vgg9_cifar10 import fl_config
        from repro.data import (ClientShards, FederatedData, iid_partition,
                                make_image_dataset)
        from repro.models import cnn

        cfg = vgg9()
        train, test = make_image_dataset(num_train=N_TRAIN, num_test=N_TEST,
                                         seed=self.seed)
        parts = iid_partition(train.ys, N_CLIENTS, seed=self.seed)
        self.shards = ClientShards.from_federated(
            FederatedData(train.xs, train.ys, parts))
        self.fl = fl_config("fedldf")
        if (self.fl.num_clients, self.fl.clients_per_round, self.fl.top_n,
                self.fl.batch_per_client) != (N_CLIENTS, K, TOP_N, BATCH):
            raise RuntimeError(f"vgg9_cifar10 is no longer the paper's "
                               f"protocol: {self.fl}")
        self.params0 = cnn.init_params(jax.random.PRNGKey(self.seed), cfg)

        def loss(p, b):
            return cnn.classify_loss(p, cfg, b)

        self.loss = loss
        xs = jnp.asarray(test.xs).reshape((EVAL_CHUNKS, -1) + test.xs.shape[1:])
        ys = jnp.asarray(test.ys).reshape(EVAL_CHUNKS, -1)

        @jax.jit
        def test_error(p, xs, ys):
            acc = jax.lax.map(
                lambda b: cnn.accuracy(p, cfg, {"images": b[0],
                                                "labels": b[1]}),
                (xs, ys))
            return 1.0 - acc.mean()

        self.eval_fn = lambda p: test_error(p, xs, ys)
        n_params = sum(x.size for x in jax.tree.leaves(self.params0))
        return (f"params={n_params} train={N_TRAIN} test={N_TEST} "
                f"N={N_CLIENTS} K={K} n={TOP_N} B={BATCH}")


def tree_gap(a, b) -> float:
    import jax
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def check_finite(name: str, values) -> None:
    import numpy as np
    arr = np.asarray(values, dtype=np.float64)
    if arr.size == 0 or not np.all(np.isfinite(arr)):
        raise RuntimeError(f"{name}: non-finite or empty values {arr}")


# ======================================================================
# Phase 1: device
# ======================================================================
def phase_device(want: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    _say(f"[device] platform={d.platform} kind={d.device_kind} "
         f"count={len(devs)} jax={jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX reports platform "
                         f"{d.platform!r}); this check does not run on "
                         "the CPU")
    if len(devs) < want:
        raise SystemExit(f"chip_smoke: --chips {want} needs {want} TPU "
                         f"devices, JAX reports {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ======================================================================
# Phase 2: kernels at real shapes
# ======================================================================
def phase_kernels(smoke: Smoke) -> str:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.kernels import ref

    # VGG-9 leaves as the engine reshapes them: one unit row each
    leaves = {"conv7.w": (3, 3, 512, 512), "conv7.b": (512,),
              "fc.w": (2048, 10)}
    key = jax.random.PRNGKey(smoke.seed + 1)
    worst = 0.0
    for name, shape in leaves.items():
        ks = jax.random.split(jax.random.fold_in(key, len(shape)), 8)
        a = jax.random.normal(ks[0], shape)
        b = jax.random.normal(ks[1], shape)
        w1 = jax.random.uniform(ks[2], (1,))
        kshape = (K,) + shape
        levels = jax.random.randint(ks[3], kshape, -127, 128).astype(jnp.int8)
        scales = jax.random.uniform(ks[4], (K, 1), minval=1e-4, maxval=1e-2)
        wk = jax.random.uniform(ks[5], (K, 1)) / K
        gate = (jax.random.uniform(ks[6], (K, 1)) < 0.5).astype(jnp.float32)
        v = jax.random.normal(ks[7], kshape)
        e = jax.random.normal(ks[0], kshape) * 0.1

        def row(x):
            return x.reshape(1, -1)

        def rows(x):
            return x.reshape(K, 1, -1)

        cases = [
            ("sqdiff_rowsum",
             lambda a, b: kops.sqdiff_rowsum(row(a), row(b)),
             lambda a, b: ref.sqdiff_rowsum(row(a), row(b)), (a, b)),
            # Eq. 3 as the vmap round calls it: K stacked locals, one call
            ("sqdiff_units", kops.sqdiff_units, ref.sqdiff_units, (v, b)),
            ("masked_accumulate",
             lambda a, b, w: kops.masked_accumulate(row(a), row(b), w),
             lambda a, b, w: ref.masked_accumulate(row(a), row(b), w),
             (a, b, w1)),
            ("fused_uplink",
             lambda l, s, w: kops.fused_uplink(rows(l), s, w),
             lambda l, s, w: ref.fused_uplink(rows(l), s, w),
             (levels, scales, wk)),
            ("fused_uplink_ef",
             lambda l, s, w, g, v, e: kops.fused_uplink_ef(
                 rows(l), s, w, g, rows(v), rows(e)),
             lambda l, s, w, g, v, e: ref.fused_uplink_ef(
                 rows(l), s, w, g, rows(v), rows(e)),
             (levels, scales, wk, gate, v, e)),
        ]
        for kname, fn, ref_fn, args in cases:
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*args).compile()
            compile_s = time.perf_counter() - t0
            if KERNEL_MARK not in compiled.as_text():
                raise RuntimeError(f"{kname} {name}: compiled program has "
                                   f"no {KERNEL_MARK}")
            out = compiled(*args)
            with jax.default_matmul_precision("highest"):
                exp = jax.jit(ref_fn)(*args)
            err = max(float(jnp.max(jnp.abs(o - x))
                            / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
                      for o, x in zip(jax.tree.leaves(out),
                                      jax.tree.leaves(exp)))
            _say(f"  {kname:17s} {name:8s} {str(shape):18s} "
                 f"rel_err={err:.2e} compile_s={compile_s:.1f} "
                 f"tpu_custom_call=yes")
            if not err <= KERNEL_TOL:
                raise RuntimeError(f"{kname} {name}: relative error "
                                   f"{err:.2e} exceeds {KERNEL_TOL:.0e}")
            worst = max(worst, err)
    return (f"cases={len(cases)} leaves={len(leaves)} "
            f"worst_rel_err={worst:.2e}")


# ======================================================================
# Phase 3: device-resident engine vs host driver
# ======================================================================
def phase_engine(smoke: Smoke) -> str:
    import jax
    from repro.federated import run_training, run_training_scan

    def scan_run(params):
        t0 = time.perf_counter()
        p, log = run_training_scan(params, smoke.loss, smoke.shards,
                                   smoke.fl, rounds=ROUNDS,
                                   eval_fn=smoke.eval_fn, eval_every=1,
                                   seed=smoke.seed)
        jax.block_until_ready(p)
        return p, log, time.perf_counter() - t0

    p_scan, log, cold = scan_run(smoke.params0)
    _, log_warm, warm = scan_run(smoke.params0)
    _say(f"  run_training_scan cold call (compile included) = {cold:.3f} s, "
         f"warm call = {warm:.3f} s")
    check_finite("scan losses", log.losses)
    check_finite("scan test errors", [e for _, e, _ in log.test_errors])
    if log_warm.losses != log.losses:
        raise RuntimeError(f"warm call changed losses: {log.losses} vs "
                           f"{log_warm.losses}")

    def host_run(params):
        return run_training(params, smoke.loss, smoke.shards, smoke.fl,
                            rounds=ROUNDS, seed=smoke.seed, sampler="jax")

    p_host, log_h = host_run(smoke.params0)
    check_finite("host losses", log_h.losses)
    smoke.compare("run_training(sampler='jax') vs run_training_scan",
                  lambda p: host_run(p)[0], lambda p: scan_run(p)[0],
                  p_host, p_scan)
    smoke.uplink_fp32 = log.uplink_mb[0] * 1e6
    return (f"losses={[round(x, 6) for x in log.losses]} "
            f"test_error={log.test_errors[-1][1]:.4f} "
            f"cold_s={cold:.3f} warm_s={warm:.3f}")


# ======================================================================
# Phase 4: sequential clients (one pass, each unit's top-n locals kept)
# ======================================================================
def phase_sequential(smoke: Smoke) -> str:
    from repro.federated import run_training_scan

    def run(mode, params):
        return run_training_scan(
            params, smoke.loss, smoke.shards,
            dataclasses.replace(smoke.fl, mode=mode), rounds=2,
            eval_fn=smoke.eval_fn, eval_every=1, seed=smoke.seed)

    p_seq, log = run("scan", smoke.params0)
    check_finite("sequential losses", log.losses)
    smoke.compare("mode='scan' vs mode='vmap'",
                  lambda p: run("scan", p)[0], lambda p: run("vmap", p)[0],
                  p_seq, run("vmap", smoke.params0)[0])
    return f"losses={[round(x, 6) for x in log.losses]}"


# ======================================================================
# Phase 5: packed uplink with error feedback
# ======================================================================
def phase_packed_ef(smoke: Smoke) -> str:
    import jax
    import numpy as np
    from repro.core.wire import CompressionConfig
    from repro.federated import run_training

    comp = dataclasses.replace(
        smoke.fl, compression=CompressionConfig(bits=8, error_feedback=True))
    _, log = run_training(smoke.params0, smoke.loss, smoke.shards, comp,
                          rounds=ROUNDS, seed=smoke.seed, sampler="jax")
    check_finite("packed EF losses", log.losses)
    per_round = np.rint(np.diff(np.concatenate(([0.0], log.uplink_mb))) * 1e6)
    _say(f"  wire bytes per round = {per_round.tolist()} "
         f"(fp32 uplink in phase 3: {smoke.uplink_fp32:.0f})")
    ratio = float(per_round.max()) / smoke.uplink_fp32
    # int8 levels are a quarter of fp32, plus per-unit headers
    if not 0.0 < ratio < 0.3:
        raise RuntimeError(f"packed wire bytes are {ratio:.3f} of fp32")
    res = jax.tree.leaves(log.final_state["client"]["residual"])
    sq = sum(float((x.astype("float32") ** 2).sum()) for x in res)
    check_finite("EF residual store", [sq])
    if sq <= 0.0:
        raise RuntimeError("EF residual store stayed zero")
    return (f"losses={[round(x, 6) for x in log.losses]} "
            f"wire_bytes_per_round={per_round[-1]:.0f} "
            f"ratio_to_fp32={ratio:.4f} residual_sqnorm={sq:.4e}")


# ======================================================================
# --chips 4: the mesh engine
# ======================================================================
def phase_mesh(smoke: Smoke, chips: int) -> str:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.units import UnitMap
    from repro.federated import build_round_fn, run_training_scan
    from repro.launch.mesh import CLIENT_AXIS, make_client_mesh

    mesh = make_client_mesh(chips)

    def run(cfg, params):
        return run_training_scan(params, smoke.loss, smoke.shards, cfg,
                                 rounds=ROUNDS, seed=smoke.seed)

    p_one, log_one = run(smoke.fl, smoke.params0)
    check_finite("one-device losses", log_one.losses)
    out = []
    for gs in (0, 2):
        cfg = dataclasses.replace(smoke.fl, mesh=mesh, agg_group_size=gs)
        p, log = run(cfg, smoke.params0)
        check_finite(f"mesh group={gs} losses", log.losses)
        smoke.compare(f"mesh={chips} agg_group_size={gs} vs one device",
                      lambda p, c=cfg: run(c, p)[0],
                      lambda p: run(smoke.fl, p)[0], p, p_one)
        out.append(f"group{gs}_losses={[round(x, 6) for x in log.losses]}")

    # where the mesh round keeps its client stack: let the compiler place
    # the round batch (no input sharding given) and read what it chose
    cfg = dataclasses.replace(smoke.fl, mesh=mesh)
    umap = UnitMap.build(smoke.params0)
    batch = {"images": jax.ShapeDtypeStruct((K, BATCH, 32, 32, 3),
                                            smoke.shards.xs.dtype),
             "labels": jax.ShapeDtypeStruct((K, BATCH),
                                            smoke.shards.ys.dtype)}
    compiled = jax.jit(build_round_fn(smoke.loss, umap, cfg)).lower(
        smoke.params0, batch, jax.ShapeDtypeStruct((K,), "float32"),
        jax.random.PRNGKey(0)).compile()
    in_batch = compiled.input_shardings[0][1]
    labels = jax.device_put(smoke.shards.ys[:K * BATCH].reshape(K, BATCH),
                            in_batch["labels"])
    jax.debug.visualize_array_sharding(labels)
    per_dev = sorted((s.device.id, s.data.shape[0])
                     for s in labels.addressable_shards)
    _say(f"  round client stack: {in_batch['labels']}; rows per device "
         f"{per_dev}")
    want = NamedSharding(mesh, P(CLIENT_AXIS))
    if not in_batch["images"].is_equivalent_to(want, 5) or \
            len({d for d, _ in per_dev}) != chips or \
            any(n != K // chips for _, n in per_dev):
        raise RuntimeError(f"client stack not split over {chips} devices: "
                           f"{in_batch}")
    out.append(f"clients_per_device={K // chips}")
    return " ".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh engine over four chips only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repository's sources are not next to this "
              f"script ({SRC} is missing)", file=sys.stderr)
        return 2
    sys.path[:0] = [SRC, ROOT]
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache(ROOT)

    smoke = Smoke(args.seed)
    device = smoke.phase("device", lambda: phase_device(args.chips))
    _say(f"[device] compile cache = {cache}")
    smoke.phase("workload", smoke.build_workload)
    if args.chips == 1:
        smoke.phase("kernels", lambda: phase_kernels(smoke))
        smoke.phase("engine", lambda: phase_engine(smoke))
        smoke.phase("sequential", lambda: phase_sequential(smoke))
        smoke.phase("packed_ef", lambda: phase_packed_ef(smoke))
    else:
        smoke.phase("mesh", lambda: phase_mesh(smoke, args.chips))
    if smoke.failures:
        print("chip_smoke: FAILED\n  " + "\n  ".join(smoke.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
