"""Device-mesh construction and multi-host initialization.

The reference's distributed layer is an NCCL/torch.distributed shim
(``/root/reference/VAR_models/dist.py:20-49``) that is, in practice, only a
device-selection helper — no ES code communicates across processes
(SURVEY.md §5.8). The TPU-native framework makes distribution first-class
instead: a named :class:`jax.sharding.Mesh` whose axes carry the parallelism
strategy, with XLA inserting ICI/DCN collectives from sharding annotations.

Axis conventions used throughout the framework:

- ``"pop"`` — the ES population axis. Population parallelism is the natural
  data-parallelism of ES training (SURVEY.md §2.2): each device evaluates a
  slice of the population, and only tiny score vectors / factored-noise
  contractions cross the interconnect.
- ``"data"`` — the intra-member image batch axis (prompts × repeats), for
  sharding one member's generation across chips when the population is small.
- ``"tp"`` — tensor parallelism over model hidden dims, for generators too
  large for one chip's HBM.

Meshes are constructed so that the fastest-varying (innermost, ICI-adjacent)
axis is the one with the heaviest traffic — ``tp`` innermost, then ``data``,
``pop`` outermost (its collectives are per-epoch and tiny, so they can ride
DCN across slices in multi-host deployments).
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

POP_AXIS = "pop"
DATA_AXIS = "data"
TP_AXIS = "tp"


def initialize_multihost() -> bool:
    """Initialize JAX's multi-controller runtime when launched as one process
    per host (the TPU-pod equivalent of the reference's env-var ``RANK`` NCCL
    init, ``VAR_models/dist.py:20-49``).

    Gracefully degrades to single-process when no coordinator is configured —
    mirroring ``dist.py:25-29`` ("fallback to single-GPU"). Returns True when
    a multi-host runtime was initialized.
    """
    # Check the env vars BEFORE any backend-touching jax call:
    # jax.distributed.initialize() must run before XLA backend init, and even
    # jax.process_count() initializes the backends.
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    num = os.environ.get("JAX_NUM_PROCESSES") or os.environ.get("NUM_PROCESSES")
    pid = os.environ.get("JAX_PROCESS_ID") or os.environ.get("PROCESS_ID")
    if not (coord and num and pid is not None):
        # Not a coordinator-configured launch; report whether a runtime is
        # already up (e.g. initialized by the launcher before importing us).
        return jax.process_count() > 1
    from jax._src import distributed as _dist

    if _dist.global_state.client is not None:
        return True  # already initialized
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(num),
        process_id=int(pid),
    )
    return True


# the framework's bodies return all_gather'ed values under replicated
# out_specs, which the varying-manual-axes check cannot infer
shard_map = functools.partial(jax.shard_map, check_vma=False)


def make_mesh(
    axes: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named mesh. ``axes`` maps axis name → size; a single ``-1``
    entry absorbs all remaining devices (like a reshape wildcard).

    ``make_mesh()`` with no arguments returns the default 1-D population mesh
    over every addressable-or-global device — the right default for ES, where
    population parallelism is the scaling story (SURVEY.md §2.2).
    """
    devs = list(devices) if devices is not None else jax.devices()
    if not axes:
        axes = {POP_AXIS: len(devs)}
    names = list(axes.keys())
    sizes = list(axes.values())
    n_wild = sum(1 for s in sizes if s == -1)
    if n_wild > 1:
        raise ValueError("at most one mesh axis may be -1")
    fixed = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    if n_wild:
        if len(devs) % fixed:
            raise ValueError(f"{len(devs)} devices not divisible by {fixed}")
        sizes = [len(devs) // fixed if s == -1 else s for s in sizes]
    total = int(np.prod(sizes))
    if total > len(devs):
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, have {len(devs)}")
    grid = np.asarray(devs[:total], dtype=object).reshape(sizes)
    return Mesh(grid, axis_names=tuple(names))


def gcd_pop_data_mesh(
    pop_size: int, n_devices: int, *, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """The bench's slice-filling default mesh at a device count: the pop
    axis takes ``gcd(pop, n)`` devices and the remainder shards each
    member's image batch over the data axis (pop_eval pads both axes as
    needed). ONE definition on purpose: ``bench.run_rung`` times this mesh
    and ``preflight --devices`` analyzes it — a drift between the two would
    silently void the 'analyzed program = timed program' contract."""
    import math

    n_pop = math.gcd(pop_size, n_devices)
    return make_mesh(
        {POP_AXIS: n_pop, DATA_AXIS: n_devices // n_pop}, devices=devices
    )


def pop_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a [pop, ...] leading-axis array."""
    return NamedSharding(mesh, P(POP_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def local_pop(mesh: Mesh, pop_size: int) -> int:
    """Per-shard population slice size; population must tile the pop axis."""
    n = mesh.shape[POP_AXIS]
    if pop_size % n:
        raise ValueError(f"pop_size={pop_size} not divisible by pop-axis size {n}")
    return pop_size // n


def host_slices(pop_size: int, n_hosts: int) -> "list[tuple[int, int]]":
    """Contiguous per-host member slices ``[(lo, n), ...]`` for a population
    split over ``n_hosts`` processes — THE reshard-plan math of elastic
    topology (ISSUE 15): member slices are keyed by *global* member ids and
    the ES update is replicated, so re-splitting the same ``pop_size`` over
    a different host count is bit-exactly well-defined. The cover identity
    (slices are disjoint, contiguous, and union to ``[0, pop_size)`` for any
    host count that tiles the population) is what makes a 2→1 or 1→2 resume
    replay the SAME members — unit-tested in tests/test_elastic.py.

    Raises (naming both numbers) when the population does not tile the host
    count — the same refusal the trainer makes at launch."""
    pop_size, n_hosts = int(pop_size), int(n_hosts)
    if n_hosts < 1:
        raise ValueError(f"n_hosts must be >= 1, got {n_hosts}")
    if pop_size % n_hosts:
        raise ValueError(
            f"host-sharded population needs pop_size divisible by the host "
            f"count: pop_size={pop_size}, hosts={n_hosts}"
        )
    lpop = pop_size // n_hosts
    return [(i * lpop, lpop) for i in range(n_hosts)]


def pop_slice_plan(mesh: Mesh, pop_size: int) -> Dict[str, object]:
    """Describe how the population lands on the mesh — which contiguous
    member slice each pop-axis shard evaluates and which *process* owns it.

    This is the pod's work assignment made explicit: the trainer logs it at
    setup (an operator debugging a slow host needs to know which members that
    host was evaluating) and records its geometry in the checkpoint manifest
    so a resume into a different topology is refused loudly
    (``resilience/checkpoints.py`` TopologyMismatch) instead of silently
    replaying a wrong population split.

    Returns ``{"n_pop", "lpop" (padded slice size, pop_eval padding rules),
    "pop_size", "process_count", "shards": [{"shard", "members": [lo, hi),
    "processes": [...]}, ...]}``.
    """
    n_pop = mesh.shape.get(POP_AXIS, 1)
    pop_pad = -(-pop_size // n_pop) * n_pop
    lpop = pop_pad // n_pop
    axis = list(mesh.axis_names).index(POP_AXIS) if POP_AXIS in mesh.axis_names else None
    shards = []
    for p in range(n_pop):
        if axis is None:
            devs = mesh.devices.ravel()
        else:
            # [p] on a 1-D object grid yields a bare Device — re-wrap so the
            # shard-owner scan below is rank-agnostic
            devs = np.asarray(np.moveaxis(mesh.devices, axis, 0)[p], dtype=object).ravel()
        shards.append({
            "shard": p,
            # padded slots wrap onto existing members (pop_eval: arange % pop)
            "members": [p * lpop, min((p + 1) * lpop, pop_pad)],
            "processes": sorted({int(d.process_index) for d in devs}),
        })
    return {
        "n_pop": int(n_pop),
        "lpop": int(lpop),
        "pop_size": int(pop_size),
        "process_count": int(jax.process_count()),
        "shards": shards,
    }


def replicate_to_mesh(tree, mesh: Mesh):
    """Stage a host-local pytree fully replicated over ``mesh``, including
    meshes that span processes (multi-controller pods): plain
    ``jax.device_put`` handles single-process meshes (arrays already on one
    of the mesh's devices keep that buffer as their shard there); cross-process
    meshes go through ``multihost_utils.host_local_array_to_global_array``.
    Every process must pass the same values (they do: θ init and checkpoint
    restores are seed/file-deterministic)."""
    if jax.process_count() <= 1 or all(
        d.process_index == jax.process_index() for d in mesh.devices.ravel()
    ):
        return jax.device_put(tree, replicated(mesh))
    from jax.experimental import multihost_utils

    # leaves may be device arrays (θ', epoch keys); the converter wants host
    # local data it can place per addressable device
    host_tree = jax.tree_util.tree_map(
        lambda x: np.asarray(jax.device_get(x)), tree
    )
    return multihost_utils.host_local_array_to_global_array(host_tree, mesh, P())


def mesh_spans_processes(mesh: Optional[Mesh]) -> bool:
    """True when the mesh places shards on more than one process — the case
    where every jit input must be staged as a *global* array up front
    (``replicate_to_mesh``): host-local arrays fed to a multi-controller
    computation are a placement error, not an implicit broadcast."""
    if mesh is None:
        return False
    procs = {d.process_index for d in mesh.devices.ravel()}
    return len(procs) > 1
