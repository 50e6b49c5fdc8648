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
