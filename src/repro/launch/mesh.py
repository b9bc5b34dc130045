"""Production mesh builders.

Functions, not module constants: importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS before any JAX import).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis joins the
data/FSDP product so cross-pod traffic is gradient/param-aggregation only.

FL round engine: :func:`make_client_mesh` builds the mesh the federated
drivers shard over (``FLConfig(mesh=...)``; see federated/server.py):

- ``make_client_mesh(D)`` — 1-D ``'clients'`` mesh: the stacked client axis
  of every round is split D ways (data parallelism over clients).
- ``make_client_mesh(D, model=M)`` — 2-D ``('clients', 'model')`` mesh of
  D total devices (D/M × M): in addition to the client split, every
  parameter leaf and every row of the error-feedback residual store is
  FSDP-sharded 1/M per device along its largest divisible dim
  (:func:`repro.launch.sharding.fl_param_specs`), so the at-rest memory
  cliffs — the N × model-size residual store first — shrink by M.

On CPU hosts, forced virtual devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) make the same code
path testable without accelerators.
"""
from __future__ import annotations

import jax
import numpy as np

CLIENT_AXIS = "clients"
MODEL_AXIS = "model"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Axes forming the batch/FSDP product ('pod' included when present)."""
    names = mesh.axis_names
    return tuple(a for a in names if a != "model")


def make_host_mesh(data: int = 2, model: int = 2):
    """Tiny mesh over host devices for CI-scale distribution tests."""
    return jax.make_mesh((data, model), ("data", "model"))


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None,
                     local_device_ids=None) -> dict:
    """Idempotent ``jax.distributed.initialize`` wrapper.

    Call ONCE per process, before any other JAX use, to let the 'clients'
    mesh axis span hosts (``make_client_mesh(processes=...)``). Arguments
    left ``None`` fall back to jax's own environment autodetection
    (``JAX_COORDINATOR_ADDRESS`` etc. / cluster plugins). Already
    initialized (``jax.process_count() > 1`` or a repeated call) is a
    no-op, so drivers and benchmarks can call it unconditionally.

    Returns ``{"process_id", "process_count", "device_count"}`` for
    logging. Raises ``RuntimeError`` on backends where multi-process init
    is unsupported — callers that only *prefer* distributed mode (e.g.
    ``benchmarks/dist_smoke.py``) catch it and fall back to single-process.
    """
    try:
        # probe WITHOUT touching the backend: jax.process_count() would
        # initialize XLA, after which jax.distributed.initialize refuses
        # to run ("must be called before any JAX computations")
        from jax._src.distributed import global_state
        already = global_state.client is not None
    except ImportError:        # private module moved: just attempt init
        already = False
    if not already:
        kwargs = {k: v for k, v in
                  (("coordinator_address", coordinator_address),
                   ("num_processes", num_processes),
                   ("process_id", process_id),
                   ("local_device_ids", local_device_ids))
                  if v is not None}
        try:
            jax.distributed.initialize(**kwargs)
        except RuntimeError as e:
            # a repeated initialize is the one benign failure
            if "already" not in str(e).lower():
                raise
    return {"process_id": jax.process_index(),
            "process_count": jax.process_count(),
            "device_count": len(jax.devices())}


def make_client_mesh(num_devices: int | None = None, model: int = 1,
                     processes: int | None = None):
    """Device mesh for the FL round engine.

    ``num_devices`` counts the TOTAL devices used (``None`` = every visible
    device; an explicit count takes the first ``num_devices``, so
    equivalence tests can build submeshes inside one forced-8-device
    process). With ``model=1`` (default) the mesh is the original 1-D
    ``'clients'`` mesh — the stacked client axis is the embarrassingly
    parallel dimension of every round. With ``model=M > 1`` the devices are
    folded into a 2-D ``('clients', 'model')`` mesh of shape
    ``(num_devices // M, M)``: the 'clients' factor still splits the round's
    client stack, while the 'model' factor FSDP-shards parameter leaves and
    the error-feedback residual store (see federated/server.py).

    ``processes``: multi-host mode. After :func:`init_distributed`,
    ``jax.devices()`` is the GLOBAL device list; passing the expected
    process count builds the mesh with ``jax.make_mesh`` over all global
    devices, whose device ordering keeps each host's local devices
    contiguous on the 'clients' axis — so a hierarchical aggregation tier
    with ``group_size = devices_per_host``
    (``FLConfig(agg_group_size=...)``) reduces intra-host first and only
    group leaders' ring traffic crosses the network. ``processes=None``/1
    keeps the original single-process construction byte-identical.
    """
    devs = jax.devices()
    n = len(devs) if num_devices is None else num_devices
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"make_client_mesh: asked for {n} devices, have {len(devs)} "
            "(on CPU, force more with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    multi = processes is not None and processes > 1
    if multi and jax.process_count() != processes:
        raise ValueError(
            f"make_client_mesh: processes={processes} but "
            f"jax.process_count()={jax.process_count()} — call "
            "repro.launch.mesh.init_distributed() in every process first")
    if model <= 1:
        if multi and n == len(devs):
            return jax.make_mesh((n,), (CLIENT_AXIS,))
        return jax.sharding.Mesh(np.asarray(devs[:n]), (CLIENT_AXIS,))
    if n % model:
        raise ValueError(
            f"make_client_mesh: model={model} must divide the total device "
            f"count {n} (mesh shape is (clients={n}//{model}, model={model}))")
    if multi and n == len(devs):
        return jax.make_mesh((n // model, model), (CLIENT_AXIS, MODEL_AXIS))
    grid = np.asarray(devs[:n]).reshape(n // model, model)
    return jax.sharding.Mesh(grid, (CLIENT_AXIS, MODEL_AXIS))


def client_mesh_size(mesh) -> int:
    """Devices on the ``'clients'`` axis (validates the axis exists)."""
    if CLIENT_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh has axes {mesh.axis_names}; FL client sharding needs a "
            f"{CLIENT_AXIS!r} axis (see make_client_mesh)")
    return int(mesh.shape[CLIENT_AXIS])


def model_mesh_size(mesh) -> int:
    """Devices on the ``'model'`` axis; 1 when the mesh has no such axis
    (1-D client meshes keep params fully replicated)."""
    if MODEL_AXIS not in mesh.axis_names:
        return 1
    return int(mesh.shape[MODEL_AXIS])


def replicated_rng(fn, mesh):
    """Wrap an RNG-consuming computation so its drawn values are
    bit-identical to the single-device lowering on any ``mesh``.

    Under the default non-partitionable threefry
    (``jax_threefry_partitionable=False``), XLA's SPMD partitioner is free
    to shard a random op's lowering across devices — which silently
    *changes* (and can bias) the drawn values, because the counter
    assignment is rewritten per shard; an output
    ``with_sharding_constraint`` does not stop it from computing the bits
    sharded first. Running the draw inside a ``shard_map`` whose in/out
    specs are fully replicated leaves the partitioner nothing to split:
    every device executes the exact single-device program. Inputs and
    outputs must be small and wanted replicated (participant ids, batch
    indices — the FL engine's case).
    """
    from jax.sharding import PartitionSpec
    return shard_map_norep(fn, mesh, in_specs=PartitionSpec(),
                           out_specs=PartitionSpec())


def shard_map_norep(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off.

    The static checker cannot follow the axis_index-based row slicing the
    sharded FL round uses; output replication is instead covered by
    equivalence tests (tests/test_shard_engine.py).
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
