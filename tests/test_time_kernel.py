"""`tools/time_kernel.py` and its registry `tools/kernel_cases.py` on the
CPU: every family's every cell is a workload of `BENCHMARK.json`, every
way of it traces at that cell's shape, forward and as a VJP
(`jax.eval_shape`: no compile, no interpreter), and `--sweep` refuses
what an entry does not list.  The times themselves are the chip's.  A
kernel PR that changes an entry's signature fails here until the
family's builder follows.
"""

import importlib
import inspect
import json
import os
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import time_kernel  # noqa: E402
from kernel_cases import FAMILIES  # noqa: E402

CASES = [(family, cell) for family, entry in FAMILIES.items()
         for cell in entry.cells]


def test_the_registry_holds_every_family_the_scripts_timed():
    assert sorted(FAMILIES) == [
        "channel_delta", "flash_block_diffusion", "flash_gqa", "flash_segment",
        "flash_window",
        "gated_delta", "head_lanes", "head_norm", "rope", "selective_scan", "share_rows",
        "short_conv", "ssd_scan"]
    for entry in FAMILIES.values():
        ways = list(entry.ways)
        assert ways[0] == "kernel" and ways[1] in ("xla", "view"), ways


@pytest.fixture(scope="module")
def workloads():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return {w["name"] for w in json.load(f)["workloads"]}


@pytest.mark.parametrize("family, cell", CASES,
                         ids=[f"{f}-{c}" for f, c in CASES])
def test_every_way_traces_at_the_cells_shape(family, cell, workloads):
    assert cell in workloads
    entry = FAMILIES[family]
    shape = entry.cells[cell]
    mod = importlib.import_module(time_kernel.PALLAS + entry.module)
    xs, aux = jax.eval_shape(lambda: entry.operands(shape, 0))
    assert len(xs) == len(entry.names)
    results = {}
    for name, way in {**entry.ways, **entry.composites}.items():
        def forward(xs, aux, way=way):
            return way(mod, shape, aux)(*xs)

        y = results[name] = jax.eval_shape(forward, xs, aux)

        def backward(ct, xs, aux, way=way):
            return jax.vjp(way(mod, shape, aux), *xs)[1](ct)

        grads = jax.eval_shape(backward, y, xs, aux)
        assert [(g.shape, g.dtype) for g in grads] == [
            (x.shape, x.dtype) for x in xs], name
    # (a result may be several arrays: `head_lanes`' q, k and v)
    assert len({tuple((y.shape, y.dtype) for y in jax.tree.leaves(r))
                for r in results.values()}) == 1, results


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_sweep_takes_what_the_entry_lists_and_nothing_else(family):
    entry = FAMILIES[family]
    mod = importlib.import_module(time_kernel.PALLAS + entry.module)
    for name in entry.sweepable:
        assert time_kernel.parse_sweep(family, f"{name}=256,512x1024") == (
            name, [256, (512, 1024)])
        # a constant is the kernel file's, a keyword the builder's
        assert hasattr(mod, name) if name.isupper() else (
            name in inspect.signature(entry.ways["kernel"]).parameters), name
    for text in ("NO_SUCH_TILE=8", "rows=64", (entry.sweepable or ("x",))[0]):
        with pytest.raises(ValueError, match="sweeps"):
            time_kernel.parse_sweep(family, text)


def test_an_operand_in_parts_is_all_of_its_parts():
    """`parts` (gated_delta's QKV: q, k, v) name operands the entry has
    and tile each one's lanes, so no lane's gradient goes unread."""
    assert [f for f, e in FAMILIES.items()
            if e.parts(next(iter(e.cells.values())))] == ["gated_delta"]
    entry = FAMILIES["gated_delta"]
    shape = entry.cells["qwen3next-16k"]
    xs, _ = jax.eval_shape(lambda: entry.operands(shape, 0))
    for name, cuts in entry.parts(shape).items():
        edges = sorted(cuts.values())
        assert [first for first, _ in edges] + [
            xs[entry.names.index(name)].shape[-1]] == [0] + [
            end for _, end in edges], name
    import numpy as np

    values = [np.arange(6.0).reshape(1, 6) + i for i in range(3)]
    got = time_kernel.by_part(("qkv", "g"), {"qkv": {"q": (0, 2),
                                                     "v": (2, 6)}}, values)
    assert list(got) == ["y", "dq", "dv", "dg"]
    assert got["dv"].tolist() == [[3., 4., 5., 6.]] and got["dg"] is not None
    assert got["dg"].shape == (1, 6)


def test_off_a_tpu_the_tool_times_nothing(capsys):
    assert time_kernel.main(["rope"]) == 1
    assert json.loads(capsys.readouterr().out) == {"error": "cpu is no TPU"}
