"""Device mesh construction.

Replaces the reference's device topology handling (NCCLContextMap over
places, platform/nccl_helper.h:86; multi-trainer ranks at
parallel_executor.cc:254).  A Mesh names the parallelism axes; shardings
reference axes by name and XLA routes collectives over ICI (fast, within
slice) vs DCN (across slices) according to mesh layout.

Conventional axis names: "dp" (data), "mp" (tensor/model), "sp"
(sequence/context), "pp" (pipeline), "ep" (expert).
"""

from __future__ import annotations

import contextvars
from typing import Dict, NamedTuple, Optional, Sequence

import numpy as np


def make_mesh(axes: Dict[str, int], devices=None):
    """Build a jax.sharding.Mesh with named axes, e.g.
    make_mesh({"dp": 4, "mp": 2})."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = int(np.prod(list(axes.values())))
    if n > len(devices):
        raise ValueError(
            f"mesh needs {n} devices, only {len(devices)} available")
    arr = np.asarray(devices[:n]).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


_default_mesh = None


def set_default_mesh(mesh):
    global _default_mesh
    _default_mesh = mesh


class ExecContext(NamedTuple):
    """What a CompiledProgram trace exposes to mesh-aware op impls:
    the mesh, the name of the mesh axis the batch dim is sharded over
    (so sp/pp shard_maps keep dp-sharded activations sharded instead of
    assuming the axis is literally called "dp"), the pipeline
    microbatch count (0 = pipelining off), and the placement's
    ShardingRules (the explicit grad_sync body asks them for the data
    axes and for each param's spec)."""

    mesh: object
    batch_axis: str = "dp"
    pipeline_microbatches: int = 0
    rules: object = None


# ContextVar, not a module global: two CompiledPrograms tracing
# concurrently (threads, or a nested trace) must not cross-contaminate
# the mesh seen by mesh-aware op impls.
_exec_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_exec_ctx", default=None)


class executing_mesh:
    """Trace-time marker: the mesh a CompiledProgram is being traced
    under.  Mesh-aware op impls (sequence-parallel flash attention, the
    pipeline engine) read it via get_executing_mesh() /
    get_exec_context() to route onto shard_map collectives; it is set
    only while the executor traces a step that has a placement."""

    def __init__(self, mesh, batch_axis: str = "dp",
                 pipeline_microbatches: int = 0, rules=None):
        self._ctx = ExecContext(mesh, batch_axis, pipeline_microbatches,
                                rules)

    def __enter__(self):
        self._token = _exec_ctx.set(self._ctx)
        return self._ctx.mesh

    def __exit__(self, *exc):
        _exec_ctx.reset(self._token)
        return False


def get_executing_mesh():
    ctx = _exec_ctx.get()
    return None if ctx is None else ctx.mesh


def get_exec_context() -> Optional[ExecContext]:
    return _exec_ctx.get()


def get_default_mesh(create_dp: bool = True):
    """The process-wide mesh; lazily a pure-DP mesh over all devices."""
    global _default_mesh
    if _default_mesh is None and create_dp:
        import jax

        _default_mesh = make_mesh({"dp": len(jax.devices())})
    return _default_mesh
