"""Multi-host (multi-trainer) runtime bootstrap.

TPU-native analog of the reference's nccl2 multi-trainer mode:
- `gen_nccl_id` exchanged an ncclUniqueId over its own gRPC server
  (reference: paddle/fluid/operators/distributed_ops/gen_nccl_id_op.cc:31,78)
  → here `jax.distributed.initialize` against a coordinator endpoint.
- `ParallelExecutor` then built comms with `num_trainers * ndev` ranks
  (reference: paddle/fluid/framework/parallel_executor.cc:254;
  python knobs `num_trainers`/`trainer_id` in parallel_executor.py)
  → here a hybrid mesh whose outer axes span hosts (DCN) and inner axes
  span the chips of each host (ICI); GSPMD routes collectives over the
  right fabric automatically.
- Cluster env variables keep the reference's names
  (reference: benchmark/fluid/fluid_benchmark.py:63-110 —
  PADDLE_TRAINER_ID, PADDLE_TRAINERS, PADDLE_CURRENT_ENDPOINT,
  PADDLE_TRAINER_ENDPOINTS).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import numpy as np


def init_distributed(trainer_id: Optional[int] = None,
                     num_trainers: Optional[int] = None,
                     coordinator: Optional[str] = None,
                     local_device_ids=None, health: bool = True):
    """Bootstrap the multi-host runtime (gen_nccl_id analog).

    Arguments default to the reference's cluster env vars:
    PADDLE_TRAINER_ID, PADDLE_TRAINERS, PADDLE_COORDINATOR (or the first
    entry of PADDLE_TRAINER_ENDPOINTS, matching how the reference used
    trainer 0's endpoint as the NCCLID broadcast root).

    When `num_trainers > 1` the distributed HEALTH PLANE
    (resilience/health.py: heartbeats + peer-loss monitor + the gang
    poison key) starts automatically on the same KV store — existing
    multi-trainer callers inherit bounded-time failure detection for
    free; pass `health=False` to opt out (the reference's pserver
    heartbeat analog, so a dead rank becomes a structured
    PeerLostError instead of a hang in the next collective).

    Safe to call when num_trainers == 1 (no-op).  Returns
    (trainer_id, num_trainers).
    """
    import jax

    if trainer_id is None:
        trainer_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if num_trainers is None:
        num_trainers = int(os.environ.get("PADDLE_TRAINERS", "1"))
    if num_trainers <= 1:
        return trainer_id, num_trainers
    if coordinator is None:
        coordinator = os.environ.get("PADDLE_COORDINATOR")
    if coordinator is None:
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        coordinator = eps.split(",")[0].strip() if eps else None
    if coordinator is None:
        raise ValueError(
            "multi-trainer bootstrap needs a coordinator endpoint: pass "
            "coordinator= or set PADDLE_COORDINATOR / "
            "PADDLE_TRAINER_ENDPOINTS")
    from ..flags import FLAGS

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_trainers,
        process_id=trainer_id,
        local_device_ids=local_device_ids,
        # bound the bootstrap wait (reference FLAGS_rpc_deadline guarded
        # the gRPC client the same way; ms → s)
        initialization_timeout=max(1, int(FLAGS.rpc_deadline / 1000)),
    )
    if health:
        from ..resilience import health as _health

        _health.start_health_plane(rank=trainer_id,
                                   num_ranks=num_trainers)
    return trainer_id, num_trainers


def shutdown_distributed():
    """Tear down the multi-host runtime.  Idempotent: safe to call
    twice, and safe when init_distributed never ran (or no-op'd at
    num_trainers == 1) — teardown paths (atexit hooks, finally blocks,
    test fixtures) must never crash on a not-running runtime.  Also
    stops the health plane first so its threads don't race a dying KV
    client."""
    import jax

    from ..resilience import health as _health

    _health.stop_health_plane()
    if not jax.distributed.is_initialized():
        return  # never initialized (or already shut down)
    try:
        jax.distributed.shutdown()
    except RuntimeError:
        pass  # raced another teardown path: already down


def make_multihost_mesh(ici_axes: Dict[str, int],
                        dcn_axes: Optional[Dict[str, int]] = None):
    """Hybrid DCN×ICI mesh: outer `dcn_axes` span hosts/slices (slow
    fabric), inner `ici_axes` span each host's chips (fast fabric).

    Typical data-parallel-across-hosts layout:
        make_multihost_mesh({"mp": 4}, {"dp": num_hosts})
    Axis names may repeat across the two dicts ONLY if disjoint; repeated
    names are rejected — use distinct axes and reshape shardings instead.

    Replaces the reference's flat `num_trainers * ndev` NCCL rank space
    (parallel_executor.cc:254) with a topology-aware mesh.
    """
    import jax
    from jax.sharding import Mesh

    dcn_axes = dict(dcn_axes or {})
    overlap = set(dcn_axes) & set(ici_axes)
    if overlap:
        raise ValueError(f"axes {sorted(overlap)} appear in both dcn and "
                         f"ici dicts; use distinct axis names")
    if not dcn_axes:
        from .mesh import make_mesh

        return make_mesh(ici_axes)
    devs = jax.devices()
    n = int(np.prod(list(dcn_axes.values()))
            * np.prod(list(ici_axes.values())))
    if n != len(devs):
        raise ValueError(
            f"hybrid mesh axes {dcn_axes}×{ici_axes} need exactly "
            f"{n} devices, have {len(devs)}")
    if all(getattr(d, "slice_index", None) is not None for d in devs):
        # Real multi-slice topology: let mesh_utils order devices so the
        # dcn axes land on slice boundaries; config errors propagate.
        from jax.experimental import mesh_utils

        dev_mesh = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=tuple(ici_axes.values()),
            dcn_mesh_shape=tuple(dcn_axes.values()),
            devices=devs,
        )
    else:
        # CPU/virtual meshes have no slice metadata: processes enumerate
        # devices in order, so the outer (dcn) dims reshape directly.
        dev_mesh = np.asarray(devs).reshape(
            tuple(dcn_axes.values()) + tuple(ici_axes.values()))
    return Mesh(dev_mesh, tuple(dcn_axes.keys()) + tuple(ici_axes.keys()))


def global_batch(mesh, value, axis: str = "dp"):
    """Assemble a global batch array from this process's local shard.

    Every trainer passes its LOCAL numpy batch; the result is a global
    jax.Array sharded over `axis` whose global dim 0 is
    local_batch * processes-along-axis.  Feed it to Executor.run like a
    numpy array.  (Replaces the reference pattern where each trainer fed
    its own Scope and NCCL all-reduce merged gradients.)
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    value = np.asarray(value)
    spec = P(axis, *([None] * (value.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_process_local_data(sharding, value)
