"""The step is built, cached, placed, called and written back in one
place, core/executor.py; a mesh is a placement that path takes
(parallel/compiler.py CompiledProgram).  These tests hold the two sides
of that seam to each other: same results, same state, same counters,
and no code of the one in the file of the other."""

import glob
import os
import pathlib
import re

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.parallel import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[16], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        h = layers.fc(x, size=32, act="relu")
        loss = layers.mean(layers.square_error_cost(layers.fc(h, size=1),
                                                    y))
        fluid.optimizer.AdamOptimizer(learning_rate=0.01).minimize(loss)
    return main, startup, scope, loss


def _batches(n, rows=8, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.randn(rows, 16).astype(np.float32),
             "y": rng.randn(rows, 1).astype(np.float32)} for _ in range(n)]


def _place(main, loss, placement):
    """What the executor is handed to run: the Program itself, a bare
    CompiledProgram, or the Program wrapped over a mesh."""
    if placement is None:
        return main
    if placement == "bare":
        return fluid.CompiledProgram(main)
    fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, mesh=make_mesh(placement))
    return main


def _train(placement, steps=3):
    from paddle_tpu.observe.monitoring import STEP_PHASES

    main, startup, scope, loss = _mlp()
    observe.enable_telemetry(main)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        target = _place(main, loss, placement)
        snap = observe.runtime_stats.snapshot()
        losses = [exe.run(target, feed=b, fetch_list=[loss])[0].item()
                  for b in _batches(steps)]
        d = observe.runtime_stats.delta(snap)
        tel = observe.fetch_telemetry(scope)
    return {"losses": losses,
            "state_names": sorted(scope.local_var_names()),
            "telemetry": (tel.steps, tel.loss_mean, tel.grad_norm_mean,
                          tel.update_norm_mean),
            "counts": {k: d[k] for k in
                       ["builds", "retraces", "dispatches"]
                       + [p + "_count" for p in STEP_PHASES]}}


@pytest.mark.parametrize("placement", ["bare", {"dp": 1}, {"dp": 4}],
                         ids=["bare", "dp1", "dp4"])
def test_one_device_and_every_placement_run_the_same_step(placement):
    one, placed = _train(None), _train(placement)
    np.testing.assert_allclose(placed["losses"], one["losses"],
                               rtol=1e-5, atol=1e-7)
    assert placed["state_names"] == one["state_names"]
    assert placed["telemetry"][0] == one["telemetry"][0] == 3
    np.testing.assert_allclose(placed["telemetry"][1:],
                               one["telemetry"][1:], rtol=1e-5)
    assert placed["counts"] == one["counts"]
    assert one["counts"]["builds"] == 1 and one["counts"]["retraces"] == 0


def test_gspmd_partial_batch_recompiles_once_replicated():
    """The default (implicit all-reduce) path on a final batch that no
    longer divides dp: a second build whose feeds are replicated, the
    sharded step kept for the next full batch, the loss that of one
    device."""
    full, tail = _batches(2, rows=8), _batches(1, rows=6, seed=9)
    batches = [full[0], tail[0], full[1], tail[0]]
    ref_main, ref_startup, ref_scope, ref_loss = _mlp()
    with fluid.scope_guard(ref_scope):
        ref_exe = fluid.Executor()
        ref_exe.run(ref_startup)
        ref = [ref_exe.run(ref_main, feed=b,
                           fetch_list=[ref_loss])[0].item()
               for b in batches]
    main, startup, scope, loss = _mlp()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        _place(main, loss, {"dp": 4})
        wrapper = main._compiled_wrapper
        assert wrapper._feed_sharding("x", full[0]["x"]).spec[0] == "dp"
        assert not any(wrapper._feed_sharding("x", tail[0]["x"]).spec)
        snap = observe.runtime_stats.snapshot()
        got = [exe.run(main, feed=b, fetch_list=[loss])[0].item()
               for b in batches]
        d = observe.runtime_stats.delta(snap)
    # one build for the sharded feeds, one for the replicated ones; the
    # second visit of each is a cache hit and no retrace
    assert d["builds"] == 2 and d["retraces"] == 0
    assert len(wrapper._cache) == 2
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("placement", [None, {"dp": 2}], ids=["one", "dp2"])
def test_compiled_step_compiles_once_and_names_its_arguments(placement):
    main, startup, scope, loss = _mlp()
    feed = _batches(1)[0]
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        _place(main, loss, placement)

        def compiled_step():
            if placement is None:
                return exe.compiled_step(main, feed=feed, fetch_list=[loss],
                                         scope=scope, with_names=True)
            return main._compiled_wrapper.compiled_step(
                feed, [loss.name], scope, with_names=True)

        snap = observe.runtime_stats.snapshot()
        first, names = compiled_step()
        after_first = observe.runtime_stats.delta(snap)["compiles"]
        second, names_again = compiled_step()
        d = observe.runtime_stats.delta(snap)
        # the step fn run() uses is the one that was lowered
        exe.run(main, feed=feed, fetch_list=[loss])
        assert observe.runtime_stats.delta(snap)["builds"] == 1
    assert second is first and names_again is names
    assert after_first >= 1 and d["compiles"] == after_first
    # jax's pytree order: the state dict's sorted keys, then the feeds'
    state = sorted(n for kind, n in names if kind == "state")
    assert names == [("state", n) for n in state] \
        + [("feed", "x"), ("feed", "y")]
    assert fluid.core.executor.RNG_STATE_VAR in state
    assert "as_text" in dir(first)


def test_exec_context_hands_grad_sync_the_data_axes(monkeypatch):
    """The explicit grad_sync body learns the dp x fsdp axes from the
    trace-time context alone: the program carries no wrapper."""
    import jax

    from paddle_tpu.core import executor as core_executor
    from paddle_tpu.parallel.mesh import executing_mesh, get_exec_context
    from paddle_tpu.parallel.strategies import (GradSyncConfig,
                                                ShardingRules)

    main, startup, scope, loss = _mlp()
    main._grad_sync = GradSyncConfig.normalize("bf16")
    assert not hasattr(main, "_compiled_wrapper")
    seen = {}
    real = core_executor._dp_sync_value_and_grad

    def spy(*args):
        seen["data_axes"] = args[-1]
        seen["rules"] = get_exec_context().rules
        return real(*args)

    monkeypatch.setattr(core_executor, "_dp_sync_value_and_grad", spy)
    rules = ShardingRules()
    mesh = make_mesh({"dp": 2, "fsdp": 2})
    feed = _batches(1)[0]
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
        env = {n: scope.find_var(n) for n in scope.local_var_names()
               if n != core_executor.RNG_STATE_VAR}
        env.update(feed)

        def trace(env):
            with executing_mesh(mesh, "dp", rules=rules):
                return core_executor.interpret_program(
                    main, dict(env), jax.random.PRNGKey(0),
                    fetch_names=[loss.name],
                    feed_names=("x", "y"))[loss.name]

        jax.eval_shape(trace, env)
    assert seen == {"data_axes": ("dp", "fsdp"), "rules": rules}


@pytest.mark.parametrize("files, pattern, allowed", [
    # core/ learns of a placement in one helper and nowhere else
    ("paddle_tpu/core/*.py", r"_compiled_wrapper", 1),
    ("paddle_tpu/core/*.py", r"\._rules\b", 0),
    # the placement builds, jits, lowers and memoizes nothing
    ("paddle_tpu/parallel/compiler.py", r"interpret_program\(", 0),
    ("paddle_tpu/parallel/compiler.py", r"jax\.jit\(|\.lower\(", 0),
    ("paddle_tpu/parallel/compiler.py",
     r"init_telemetry_for|ensure_numerics_fields|_cache\[|_cache\.get", 0),
], ids=["core-wrapper", "core-rules", "placement-interpret",
        "placement-jit", "placement-caches"])
def test_the_seam_between_step_and_placement(files, pattern, allowed):
    paths = sorted(glob.glob(os.path.join(REPO, files)))
    assert paths
    hits = [(os.path.relpath(p, REPO), m.group(0))
            for p in paths
            for m in re.finditer(pattern, pathlib.Path(p).read_text())]
    assert len(hits) == allowed, hits
    if allowed:
        import inspect

        from paddle_tpu.core import executor as core_executor

        assert re.search(pattern, inspect.getsource(
            core_executor._resolve_placement))
