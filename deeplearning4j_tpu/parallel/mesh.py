"""Device mesh utilities (TPU-native replacement for the reference's
CudaAffinityManager device assignment + MeshOrganizer topology,
SURVEY.md §2.10, §2.30 — here the 'mesh' is jax.sharding.Mesh and the
topology is XLA's problem).

Axis convention (scaling-book style):
- 'data'  — batch sharding (DP)
- 'model' — tensor parallel (TP) sharding of weight matrices
Sequence parallelism reuses 'model' for the token axis in attention
blocks (Ulysses-style all-to-all is expressed as resharding).
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401  (re-exported to the call sites)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("deeplearning4j_tpu")

_dist_initialized = False


def maybe_init_distributed(env: Optional[dict] = None) -> bool:
    """Join a multi-host ``jax.distributed`` job when the environment
    says there is one; no-op otherwise. Threaded through ShardedTrainer
    mesh construction so a multi-host data-parallel run needs only the
    standard three env vars (or a TPU pod's auto-detection), not a
    hand-written bootstrap:

    - ``DL4J_TPU_COORDINATOR``   — coordinator ``host:port``
    - ``DL4J_TPU_NUM_PROCESSES`` — world size
    - ``DL4J_TPU_PROCESS_ID``    — this process's rank

    Must run BEFORE the XLA backend initializes (jax requirement); a
    backend already up without these vars is the normal single-process
    case and returns False. Idempotent across trainers."""
    global _dist_initialized
    e = env if env is not None else os.environ
    coord = e.get("DL4J_TPU_COORDINATOR")
    if not coord or _dist_initialized:
        return _dist_initialized
    try:
        nproc = int(e.get("DL4J_TPU_NUM_PROCESSES", "1"))
        pid = int(e.get("DL4J_TPU_PROCESS_ID", "0"))
    except ValueError:
        log.warning("maybe_init_distributed: non-integer "
                    "DL4J_TPU_NUM_PROCESSES/DL4J_TPU_PROCESS_ID — "
                    "staying single-process")
        return False
    if nproc <= 1:
        log.warning(
            "maybe_init_distributed: DL4J_TPU_COORDINATOR=%s is set "
            "but DL4J_TPU_NUM_PROCESSES=%s — staying single-process "
            "(set the world size to join the multi-host job)",
            coord, e.get("DL4J_TPU_NUM_PROCESSES"))
        return False
    try:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
        _dist_initialized = True
        log.warning("jax.distributed initialized: process %d/%d via %s "
                    "(%d global devices)", pid, nproc, coord,
                    len(jax.devices()))
    except RuntimeError as exc:
        # already initialized by the caller (DistributedBackend) is
        # fine; anything else is a real bootstrap failure
        if "already initialized" in str(exc).lower():
            _dist_initialized = True
        elif "before any JAX computations" in str(exc):
            raise RuntimeError(
                "DL4J_TPU_COORDINATOR is set but the XLA backend is "
                "already up: jax.distributed must initialize before "
                "any jax computation. Construct the ShardedTrainer (or "
                "call maybe_init_distributed()) BEFORE model.init() — "
                "trainer-before-init is supported — or initialize "
                "DistributedBackend at program start.") from exc
        else:
            raise
    return _dist_initialized


def worker_env(coordinator: str, num_processes: int,
               process_id: int) -> dict:
    """The env-var bundle a supervisor injects into a spawned worker
    process so ``maybe_init_distributed()`` joins it to the multi-host
    job — the one place the ``jax.distributed`` bootstrap contract is
    spelled out (``WorkerSupervisor(coordinator=...)`` uses this per
    worker, rank = the worker's index)."""
    return {"DL4J_TPU_COORDINATOR": str(coordinator),
            "DL4J_TPU_NUM_PROCESSES": str(int(num_processes)),
            "DL4J_TPU_PROCESS_ID": str(int(process_id))}


def put_replicated(tree, mesh: Mesh):
    """Replicate a host pytree across the mesh, multi-host safe
    (``make_array_from_callback`` materializes only addressable shards;
    plain ``device_put`` to a sharding with non-addressable devices is
    a single-process-only operation)."""
    spec = NamedSharding(mesh, P())

    def one(a):
        host = np.asarray(a)
        return jax.make_array_from_callback(
            host.shape, spec, lambda idx: host[idx])

    if jax.process_count() == 1:
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, spec), tree)
    return jax.tree_util.tree_map(one, tree)


def build_mesh(num_data: Optional[int] = None, num_model: int = 1,
               devices: Optional[Sequence] = None) -> Mesh:
    """Build a ('data', 'model') mesh over available devices.

    Defaults: all devices on the data axis (pure DP) — the reference's
    ParallelWrapper default of one worker per GPU. In a multi-host job
    (``maybe_init_distributed``) ``jax.devices()`` is the GLOBAL device
    list, so the default mesh spans every host's chips.
    """
    devs = list(devices if devices is not None else jax.devices())
    if num_data is None:
        num_data = len(devs) // num_model
    if num_data * num_model != len(devs):
        raise ValueError(
            f"mesh {num_data}x{num_model} != {len(devs)} devices")
    arr = np.asarray(devs).reshape(num_data, num_model)
    return Mesh(arr, axis_names=("data", "model"))


def data_parallel_spec(mesh: Mesh, x) -> NamedSharding:
    """Shard leading (batch) dim over 'data', replicate the rest."""
    ndim = getattr(x, "ndim", None) or len(x.shape)
    return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, *arrays):
    """Place host arrays sharded over the data axis."""
    out = [jax.device_put(a, data_parallel_spec(mesh, a)) for a in arrays]
    return out[0] if len(out) == 1 else out
