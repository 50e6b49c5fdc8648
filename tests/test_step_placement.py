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


# -- `place` moves only what does not lie where the step wants it ---------

def _place_as_the_parent_did(values, shardings):
    """`place` before PR 29: every array through `jax.device_put`, a
    host feed by way of the default device.  The reference the cases
    below are held to, bit for bit."""
    import jax
    import jax.numpy as jnp

    return {n: jax.device_put(v if isinstance(v, dict) else jnp.asarray(v),
                              shardings[n])
            for n, v in values.items()}


def _classifier(seed=5):
    """Feeds of both kinds a host hands over in 64 bits."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[16], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="int64")
        logits = layers.fc(layers.fc(x, size=32, act="relu"), size=4)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.AdamOptimizer(learning_rate=0.01).minimize(loss)
    return main, startup, scope, loss


def _placed_steps(mesh=None, rules=None, rows=(8, 8, 8, 8), feed_as="host",
                  stray=None, guard_from=None, telemetry=False):
    """Train the MLP over a mesh, one `runtime_stats` delta a step.
    `feed_as`: numpy, a jax.Array on one device, or one that already
    lies as the step wants it; `stray` names a state array moved to one
    device before the third step; from step `guard_from` on, explicit
    chip-to-chip transfers raise."""
    import contextlib

    import jax
    import jax.numpy as jnp

    main, startup, scope, loss = _mlp()
    if telemetry:
        observe.enable_telemetry(main)
    losses, deltas = [], []
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        bs = fluid.BuildStrategy()
        if rules:
            from paddle_tpu.parallel.strategies import ShardingRules

            bs.sharding_rules = ShardingRules(rules=rules)
        wrapper = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, build_strategy=bs,
            mesh=make_mesh(mesh or {"dp": 4}))
        for i, n_rows in enumerate(rows):
            feed = _batches(1, rows=n_rows, seed=i)[0]
            if feed_as == "device":
                feed = {n: jnp.asarray(v) for n, v in feed.items()}
            elif feed_as == "sharded":
                feed = {n: jax.device_put(v, wrapper._feed_sharding(n, v))
                        for n, v in feed.items()}
            if stray and i == 2:
                scope.set_var(stray, jax.device_put(
                    np.asarray(scope.find_var(stray)), jax.devices()[0]))
            guarded = guard_from is not None and i >= guard_from
            snap = observe.runtime_stats.snapshot()
            with (jax.transfer_guard_device_to_device("disallow_explicit")
                  if guarded else contextlib.nullcontext()):
                losses.append(exe.run(main, feed=feed,
                                      fetch_list=[loss])[0].item())
            deltas.append(observe.runtime_stats.delta(snap))
        prepared = exe._prepare(main, dict(feed), [loss.name], scope, 1,
                                True, 1, wrapper)
    return {"losses": losses, "deltas": deltas, "scope": scope,
            "prepared": prepared,
            "counts": [(d["place_puts"], d["place_skips"]) for d in deltas]}


def _steady(first, later=None, third=None):
    """(puts, skips) of the four steps, as functions of S, the number
    of state arrays (the MLP feeds 2 arrays a step)."""
    later = later or (lambda S: (2, S))
    return lambda S: [first(S), later(S), (third or later)(S), later(S)]


# the start-up program left the state on one device: step one puts all
_HOST = _steady(lambda S: (S + 2, 0))
_PLACE_CASES = {
    "host-feed": (dict(guard_from=1), _HOST),
    "device-feed": (dict(feed_as="device"), _HOST),
    "sharded-feed": (dict(feed_as="sharded", guard_from=1),
                     _steady(lambda S: (S, 2), lambda S: (0, S + 2))),
    # 6 rows do not divide dp=4: replicated feeds, a second step fn
    # whose state shardings are new objects, equal to the arrays'
    "partial-batch": (dict(rows=(8, 6, 8, 6), guard_from=1), _HOST),
    "dp2-mp2-column": (dict(mesh={"dp": 2, "mp": 2}, guard_from=1,
                            rules=[(r"fc_0\.w_0", (None, "mp"))]), _HOST),
    "stray-state": (dict(stray="fc_0.w_0"),
                    _steady(lambda S: (S + 2, 0),
                            third=lambda S: (3, S - 1))),
    "telemetry": (dict(telemetry=True, guard_from=1), _HOST),
}


@pytest.mark.parametrize("case", sorted(_PLACE_CASES))
def test_place_moves_only_what_is_not_in_place(case, monkeypatch):
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.core import executor as core_executor

    kwargs, expected = _PLACE_CASES[case]
    got = _placed_steps(**kwargs)
    fn, state, feeds = got["prepared"]
    assert got["counts"] == expected(len(state))
    # after step one the state `place` hands the step is the scope's own
    for name, value in state.items():
        assert value is got["scope"].find_var(name), name
    if case == "dp2-mp2-column":
        assert state["fc_0.w_0"].sharding.spec == P(None, "mp")
        assert len(state["fc_0.w_0"].addressable_shards[0].data[0]) == 16
    if case == "telemetry":
        assert isinstance(state[observe.metrics.TELEMETRY_VAR], dict)
    # nothing compiles after step one, but the replicated-feed step
    for i, d in enumerate(got["deltas"][1:], 1):
        assert d["compiles"] == 0 or (case, i) == ("partial-batch", 1)
        assert d["retraces"] == 0

    # the parent's `place` under the same traffic: the same numbers
    monkeypatch.setattr(core_executor, "_place", _place_as_the_parent_did)
    kwargs = dict(kwargs, guard_from=None)
    ref = _placed_steps(**kwargs)
    assert got["losses"] == ref["losses"]


def test_the_parent_place_copied_chip_to_chip(monkeypatch):
    """What the guard in the cases above would have caught."""
    from paddle_tpu.core import executor as core_executor

    monkeypatch.setattr(core_executor, "_place", _place_as_the_parent_did)
    with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
        _placed_steps(guard_from=1)


@pytest.mark.parametrize("wide", [True, False], ids=["64bit", "32bit"])
def test_host_feeds_reach_the_step_in_the_dtypes_asarray_gave(wide):
    """int64 / float64 host feeds are int32 / float32 at the step, as
    `jnp.asarray` made them: one trace, one compile, then neither,
    whichever width the host sends next."""
    main, startup, scope, loss = _classifier()
    rng = np.random.RandomState(1)

    def batch(wide):
        return {"x": rng.randn(8, 16).astype(
                    np.float64 if wide else np.float32),
                "y": rng.randint(0, 4, (8, 1)).astype(
                    np.int64 if wide else np.int32)}

    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, mesh=make_mesh({"dp": 4}))
        first = exe.run(main, feed=batch(wide), fetch_list=[loss])[0]
        snap = observe.runtime_stats.snapshot()
        for w in (wide, not wide, wide):
            out = exe.run(main, feed=batch(w), fetch_list=[loss])[0]
        d = observe.runtime_stats.delta(snap)
        fn, state, feeds = exe._prepare(
            main, batch(wide), [loss.name], scope, 1, True, 1,
            main._compiled_wrapper)
    assert np.isfinite(first) and np.isfinite(out)
    assert (d["builds"], d["retraces"], d["compiles"]) == (0, 0, 0)
    assert (d["place_puts"], d["place_skips"]) == (6, 3 * len(state))
    assert str(feeds["x"].dtype) == "float32"
    assert str(feeds["y"].dtype) == "int32"
    assert feeds["x"].sharding.spec[0] == "dp"


@pytest.mark.parametrize("value, dtype, weak", [
    ([1, 2, 3, 4], "int32", False), ([0.5, 1.5], "float32", False),
    (3, "int32", True), (2.5, "float32", True),
    (np.float32(2), "float32", False), (np.int64(7), "int32", False),
], ids=["int-list", "float-list", "int", "float", "np-f32", "np-i64"])
def test_one_array_keeps_what_asarray_made_of_lists_and_scalars(
        value, dtype, weak):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core.executor import _one_array

    sharding = NamedSharding(make_mesh({"dp": 4}), P())
    got = jax.device_put(_one_array(value), sharding)
    ref = jnp.asarray(value)
    assert isinstance(got, jax.Array)
    assert (got.shape, str(got.dtype), got.weak_type) == \
        (ref.shape, dtype, weak) == (ref.shape, str(ref.dtype),
                                     ref.weak_type)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
