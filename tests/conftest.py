"""Test harness config: virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): op tests run against
the CPU interpreter; multi-device tests use a virtual 8-device host mesh
(xla_force_host_platform_device_count) standing in for an ICI slice.
Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402  (after env setup)

# Numeric comparisons against float64 numpy references need full-precision
# matmuls; the framework itself keeps the fast TPU default.
jax.config.update("jax_default_matmul_precision", "highest")

# Event-kind registry enforcement (ISSUE 15): under tests an
# unregistered serving_/fleet_/gang_ event kind RAISES instead of
# warning — a typo'd kind silently drops off every dashboard filter,
# and warn-only rot is exactly what the registries exist to stop.
from paddle_tpu.observe import events as _observe_events  # noqa: E402

_observe_events.set_strict_kinds(True)

import pytest  # noqa: E402

from paddle_tpu.observe.monitoring import runtime_stats  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _cold_runs_of_this_module_alone():
    """`runtime_stats.cold_runs()` is the PROCESS's record, and a test
    that picks "the start-up runs before my step" out of it
    (`benchmarks/setup_anatomy.pick`) takes a start-up run that the
    module before it left last for its own.  Which module that is
    depends on which xdist worker was free: a module starts with none."""
    runtime_stats._cold_runs.clear()


# -- the chip's compiler for a DESCRIBED v5e (tests/chip_compile.py) --------
# Described inside a module-scoped fixture (never at import: only one
# process may hold the TPU library, and every xdist worker imports
# every test file), in the test's own process, with the persistent
# compilation cache off.

@pytest.fixture(scope="module")
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the library raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topology):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def dp4_mesh(topology):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topology.devices).reshape(4), ("dp",))
