"""Device mesh construction.

The reference sizes its worker grid from ``PATHWAY_THREADS`` ×
``PATHWAY_PROCESSES`` (``src/engine/dataflow/config.rs:88-120``).  Here the
grid is a ``jax.sharding.Mesh``; one chip plays the role of one worker
(BASELINE north star).  ``make_mesh`` factors the device count into
``(data, model)`` with a modest tensor-parallel degree — encoder weights
are small enough that dp should dominate.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("data", "model")

_DISTRIBUTED = False


def initialize_distributed(
    *,
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Join the multi-host device runtime so ``jax.devices()`` spans hosts.

    The reference sizes its worker grid from the ``PATHWAY_*`` env
    (``src/engine/dataflow/config.rs:88-120``) and its ``spawn`` CLI forks
    processes with those variables set (``python/pathway/cli.py:53-110``);
    here the same env powers ``jax.distributed.initialize`` so ``make_mesh``
    returns a GLOBAL mesh and XLA collectives ride DCN between hosts (ICI
    within one).  Resolution order per field: explicit argument →
    ``PATHWAY_DEVICE_COORDINATOR`` env → derived from the worker-cluster
    config (first peer host, ``first_port + 1000`` — off the TCP-mesh port
    range).  Returns False (no-op) for single-process runs; idempotent.
    """
    global _DISTRIBUTED
    if _DISTRIBUTED:
        return True
    from pathway_tpu.internals.config import get_config

    cfg = get_config()
    nproc = cfg.processes if num_processes is None else num_processes
    pid = cfg.process_id if process_id is None else process_id
    if nproc <= 1:
        return False
    if coordinator_address is None:
        from pathway_tpu.internals.config import env_str

        coordinator_address = env_str("PATHWAY_DEVICE_COORDINATOR")
    if coordinator_address is None:
        host = (cfg.peer_hosts[0] if cfg.peer_hosts else "127.0.0.1")
        # supervised restarts (engine/supervisor.py) offset the derived
        # coordinator port by the restart attempt: the previous attempt's
        # coordinator may linger in FIN_WAIT/teardown for seconds after
        # SIGKILL, and jax.distributed.initialize fails hard on a port that
        # is merely slow to free — a fresh port per attempt sidesteps it
        from pathway_tpu.engine.faults import restart_attempt

        coordinator_address = (
            f"{host}:{cfg.first_port + 1000 + restart_attempt()}"
        )
    # multi-process CPU meshes need a cross-process collectives backend:
    # XLA:CPU's default ("none") hard-fails any computation spanning
    # processes ("Multiprocess computations aren't implemented on the CPU
    # backend").  jaxlib ships gloo TCP collectives; select them before
    # the backend initializes.  Only when CPU is the explicitly requested
    # platform — on TPU the collectives ride ICI/DCN and this flag is
    # irrelevant.
    platforms = (
        getattr(jax.config, "jax_platforms", None)
        or os.environ.get("JAX_PLATFORMS", "")
        or ""
    )
    if "cpu" in platforms.lower():
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=nproc,
        process_id=pid,
    )
    _DISTRIBUTED = True
    return True


def put_global(arr: np.ndarray, sharding) -> jax.Array:
    """``device_put`` that also works when the mesh spans hosts.

    Multi-host: every process holds the full host-side array (the SPMD
    "every worker builds the same data" invariant) and each device reads
    its own slice via ``make_array_from_callback`` — ``jax.device_put``
    alone cannot target non-addressable devices.
    """
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


def mesh_shape_for(n_devices: int, max_model: int = 2) -> tuple[int, int]:
    """Factor ``n_devices`` into (data, model).

    Tensor parallelism is capped at ``max_model`` — MiniLM/BGE-class
    encoders saturate a chip long before weight memory is a constraint, so
    extra chips are worth more as data parallelism.
    """
    model = 1
    for cand in range(min(max_model, n_devices), 0, -1):
        if n_devices % cand == 0:
            model = cand
            break
    return n_devices // model, model


def make_mesh(
    n_devices: int | None = None,
    *,
    devices: list | None = None,
    max_model: int = 2,
) -> Mesh:
    """An ``("data", "model")`` mesh over the first ``n_devices`` devices."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    data, model = mesh_shape_for(len(devices), max_model)
    grid = np.asarray(devices).reshape(data, model)
    return Mesh(grid, AXES)


def flat_axes(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes — for state sharded over every chip (the index)."""
    return tuple(mesh.axis_names)


# Process-wide default mesh for device-resident indexes.  When set, every
# BruteForceKnn/USearchKnn index (and the DocumentStore/VectorStore built on
# them) shards its corpus matrix over this mesh and answers queries through
# the shard_map top-k — the analog of the reference attaching its external
# index to every SPMD worker (src/engine/dataflow.rs:2694).
_DEFAULT_INDEX_MESH: Mesh | None = None


def set_default_index_mesh(mesh: Mesh | None) -> None:
    """Route all subsequently-built device indexes over ``mesh``."""
    global _DEFAULT_INDEX_MESH
    _DEFAULT_INDEX_MESH = mesh


def get_default_index_mesh() -> Mesh | None:
    return _DEFAULT_INDEX_MESH
