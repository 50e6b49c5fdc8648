"""Set-up seen from inside (ISSUE 34): `runtime_stats.heard`, the record
of every cold `Executor.run`, the cache counters and the build stage.

- a run during which a step fn was built, a feed signature was new or
  jax traced, lowered, compiled or read its cache leaves ONE record; a
  warm run leaves none and reads `heard` twice, nothing more,
- the persistent cache's hits and misses are heard apart from compiles,
- an AOT compile (`compiled_step`) does not pass `Executor.run` and
  leaves no record,
- `stage("build_program")` nests without double counting, and the three
  builders the benchmark's cells use enter it.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observe import monitoring
from paddle_tpu.observe.monitoring import RuntimeStats, runtime_stats
from tests.test_observe import _feed, _linreg_program, _wrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORD_KEYS = {
    "program", "ops", "state_arrays", "feed_arrays", "fetches",
    "placement", "new_signature", "t_entry", "prepare_s", "place_s",
    "call_s", "writeback_s", "trace_s", "lower_s", "backend_compile_s",
    "compiles", "cache_hits", "cache_misses", "cache_read_s"}


def _new_records(count_before):
    """The records appended since `snapshot()["cold_runs"]` read
    `count_before`."""
    grown = runtime_stats.snapshot()["cold_runs"] - count_before
    return runtime_stats.cold_runs()[-grown:] if grown else []


@pytest.mark.parametrize("mesh", [None, {"dp": 2}])
def test_first_run_leaves_one_record_and_a_warm_run_none(mesh):
    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        n0 = runtime_stats.snapshot()["cold_runs"]
        t_before = time.perf_counter()
        exe.run(startup)
        (start,) = _new_records(n0)
        _wrap(main, loss, mesh)
        n1 = runtime_stats.snapshot()["cold_runs"]
        snap = runtime_stats.snapshot()
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
        first = runtime_stats.delta(snap)
        step_records = _new_records(n1)
        heard = runtime_stats.heard
        n2 = runtime_stats.snapshot()["cold_runs"]
        for _ in range(3):
            exe.run(main, feed=_feed(rng), fetch_list=[loss])
        # over a mesh jax may lower the step once more for the state
        # the first step left committed: a cold run, and it says so
        late = _new_records(n2)
    # the start-up program: no feed, no fetch, its own op count
    assert set(start) == RECORD_KEYS
    assert (start["feed_arrays"], start["fetches"]) == (0, 0)
    assert start["program"] == startup._uid
    assert start["ops"] == len(startup.global_block().ops)
    assert start["new_signature"] and not start["placement"]
    assert t_before <= start["t_entry"] <= time.perf_counter()
    # the step: x and y fed, the loss fetched, the weights, the
    # learning rate and the RNG key as state
    step = step_records[0]
    assert len(step_records) == 1
    assert (step["feed_arrays"], step["fetches"]) == (2, 1)
    assert step["program"] == main._uid
    assert step["ops"] == len(main.global_block().ops)
    assert step["state_arrays"] >= 3
    assert step["new_signature"]
    assert step["placement"] == (mesh is not None)
    assert step["compiles"] >= 1 and step["backend_compile_s"] > 0.0
    assert step["trace_s"] > 0.0 and step["lower_s"] > 0.0
    # the deltas of the record are the deltas of the counters
    assert step["compiles"] == first["compiles"]
    assert step["backend_compile_s"] == pytest.approx(
        first["compile_time_s"])
    assert step["trace_s"] + step["lower_s"] == pytest.approx(
        first["trace_time_s"])
    # jax's work lies inside the phases that hold it
    assert step["trace_s"] + step["lower_s"] + step["backend_compile_s"] \
        <= 1.1 * (step["prepare_s"] + step["place_s"] + step["call_s"])
    assert first["cold_runs"] == 1
    for r in late:
        assert not r["new_signature"], r
    if not late:
        # three warm runs replaced nothing
        assert runtime_stats.heard is heard


def test_new_feed_shape_leaves_a_record_with_the_mark_and_a_retrace():
    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=_feed(rng), fetch_list=[loss])
        snap = runtime_stats.snapshot()
        exe.run(main, feed=_feed(rng, n=12), fetch_list=[loss])
        d = runtime_stats.delta(snap)
        (r,) = _new_records(snap["cold_runs"])
    assert (d["retraces"], d["builds"], d["cold_runs"]) == (1, 0, 1)
    assert r["new_signature"] and r["program"] == main._uid
    assert r["compiles"] >= 1


def test_compiled_step_leaves_no_record():
    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    feed = _feed(rng)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        snap = runtime_stats.snapshot()
        exe.compiled_step(main, feed=feed, fetch_list=[loss], scope=scope)
        d = runtime_stats.delta(snap)
        assert d["compiles"] >= 1 and d["builds"] == 1
        assert d["cold_runs"] == 0
        # the run after it is cold for jax (the AOT executable is not
        # the jitted call's) and its deltas hold its own work only
        exe.run(main, feed=feed, fetch_list=[loss])
        (r,) = _new_records(snap["cold_runs"])
    assert not r["new_signature"]
    assert r["compiles"] == runtime_stats.delta(snap)["compiles"] \
        - d["compiles"]


def test_warm_run_reads_heard_and_appends_nothing():
    main, startup, scope, loss = _linreg_program()
    rng = np.random.RandomState(0)
    feed = _feed(rng)
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        heard = runtime_stats.heard
        snap = runtime_stats.snapshot()
        for _ in range(5):
            exe.run(main, feed=feed, fetch_list=[loss])
    assert runtime_stats.heard is heard
    d = runtime_stats.delta(snap)
    assert d["cold_runs"] == 0 and d["dispatches"] == 5
    assert all(d[f] == 0 for f in monitoring.Heard._fields[1:])


def test_heard_is_replaced_not_mutated_and_trace_time_is_derived():
    stats = RuntimeStats()
    h0 = stats.heard
    assert h0 == (0,) * len(monitoring.Heard._fields)
    stats.hear(jaxpr_trace_time_s=0.25)
    stats.hear(lower_time_s=0.5)
    stats.hear(compile_time_s=2.0, compiles=1)
    stats.record_build()
    stats.record_retrace()
    assert h0 == (0,) * len(h0) and stats.heard is not h0
    assert stats.heard.events == 5
    # one accumulator path: the sum is read off the two
    assert stats.trace_time_s == 0.75
    assert not hasattr(stats, "record_trace")
    assert "trace_time_s" not in stats.__dict__
    s = stats.snapshot()
    assert (s["compiles"], s["compile_time_s"], s["trace_time_s"],
            s["jaxpr_trace_time_s"], s["lower_time_s"], s["builds"],
            s["retraces"]) == (1, 2.0, 0.75, 0.25, 0.5, 1, 1)
    assert stats.compiles == 1 and stats.builds == 1
    with pytest.raises(AttributeError):
        stats.no_such_counter


def test_a_function_traced_inside_another_counts_once():
    """jax reports a nested jitted function's tracing on its own, inside
    the outer one's: only the outermost span is wall time."""
    import jax
    from jax._src import monitoring as jax_monitoring

    monitoring.install()
    trace_event = "/jax/core/compile/jaxpr_trace_duration"
    raw = []

    def listen(event, duration, **_kw):
        if event == trace_event:
            raw.append(duration)

    inner = jax.jit(lambda x: x * 2.0 + 1.0)
    outer = jax.jit(lambda x: inner(x) + inner(x[::-1]))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        snap = runtime_stats.snapshot()
        outer(np.arange(4, dtype=np.float32)).block_until_ready()
        d = runtime_stats.delta(snap)
    finally:
        jax_monitoring.unregister_event_duration_listener(listen)
    assert len(raw) >= 2            # the outer and, inside it, the inner
    assert d["jaxpr_trace_time_s"] == pytest.approx(max(raw))
    assert d["jaxpr_trace_time_s"] < sum(raw)
    assert d["lower_time_s"] > 0.0 and d["compiles"] == 1
    assert d["trace_time_s"] == pytest.approx(
        d["jaxpr_trace_time_s"] + d["lower_time_s"])


def test_snapshot_and_delta_carry_the_new_fields():
    new = {"cache_hits", "cache_misses", "cache_read_time_s",
           "jaxpr_trace_time_s", "lower_time_s", "cold_runs",
           "build_program_time_s", "build_program_count"}
    stats = RuntimeStats()
    before = stats.snapshot()
    assert new <= set(before)
    assert all(before[f] == 0 for f in new)
    stats.hear(cache_hits=1)
    stats.hear(cache_read_time_s=0.125)
    stats.hear(cache_misses=1)
    with stats.stage("build_program"):
        pass
    for phase in monitoring.STEP_PHASES:
        stats._record_phase(phase, 0.5)
    stats.record_cold_run(before=monitoring.Heard(*(0,) * 10),
                          program=7, ops=3)
    d = stats.delta(before)
    assert (d["cache_hits"], d["cache_misses"], d["cache_read_time_s"],
            d["cold_runs"], d["build_program_count"]) == (1, 1, 0.125, 1, 1)
    assert d["build_program_time_s"] > 0.0
    (r,) = stats.cold_runs()
    assert (r["program"], r["ops"], r["cache_hits"], r["cache_misses"],
            r["cache_read_s"], r["call_s"]) == (7, 3, 1, 1, 0.125, 0.5)
    line = monitoring.format_cold_run(dict(
        r, state_arrays=4, feed_arrays=2, fetches=1, placement=True))
    assert "\n" not in line
    assert line.startswith("program 7 (3 ops, 4 state / 2 feed / 1 fetch, "
                           "placed, signature seen before): 2.00 s = ")
    assert "cache 1 hit / 1 miss, read 0.12" in line
    # the list is a copy, bounded at the newest 256
    r["program"] = None
    assert stats.cold_runs()[0]["program"] == 7
    for i in range(300):
        stats.record_cold_run(before=stats.heard, program=i)
    assert len(stats.cold_runs()) == 256
    assert stats.cold_runs()[-1]["program"] == 299
    assert stats.snapshot()["cold_runs"] == 301


def test_build_stage_nests_without_double_counting():
    stats = RuntimeStats()

    @stats.stage("build_program")
    def inner():
        time.sleep(0.01)
        return "built"

    @stats.stage("build_program")
    def outer():
        time.sleep(0.01)
        return inner()

    assert outer() == "built"
    s = stats.snapshot()
    assert s["build_program_count"] == 1
    assert 0.02 <= s["build_program_time_s"] < 0.5
    assert inner() == "built"
    assert stats.snapshot()["build_program_count"] == 2
    # an error passes, and the stage is left
    with pytest.raises(KeyError):
        with stats.stage("build_program"):
            raise KeyError("x")
    assert stats.snapshot()["build_program_count"] == 3
    assert stats._stage_depth["build_program"] == 0


def _tiny_transformer():
    from paddle_tpu.models import transformer

    return transformer.build_model(
        src_vocab_size=32, trg_vocab_size=32, max_length=8, n_layer=1,
        n_head=2, d_model=16, d_inner_hid=32)


def _tiny_resnet():
    from paddle_tpu.models import resnet

    return resnet.build_model(dataset="cifar10")


def _tiny_decoder():
    from paddle_tpu.models import decoder

    return decoder.build_model(
        max_length=8, vocab_size=32, hidden_size=16,
        intermediate_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=2, num_experts=4,
        num_experts_per_tok=2, norm_topk_prob=False, rope_theta=10000.0,
        rms_norm_eps=1e-5)


@pytest.mark.parametrize("build", [_tiny_transformer, _tiny_resnet,
                                   _tiny_decoder])
def test_the_builders_enter_the_build_stage_once(build):
    main, startup = fluid.Program(), fluid.Program()
    snap = runtime_stats.snapshot()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        t0 = time.perf_counter()
        build()
        took = time.perf_counter() - t0
    d = runtime_stats.delta(snap)
    assert d["build_program_count"] == 1
    # the builder's whole body: forward, backward and optimizer ops
    assert 0.5 * took <= d["build_program_time_s"] <= took
    assert main.global_block().ops


_CACHE_SCRIPT = r"""
import json, sys
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observe.monitoring import runtime_stats

def build_and_run():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, size=3))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])

snap = runtime_stats.snapshot()
build_and_run()
first = runtime_stats.delta(snap)
n = len(runtime_stats.cold_runs())
jax.clear_caches()
snap = runtime_stats.snapshot()
build_and_run()
print(json.dumps({"first": first, "second": runtime_stats.delta(snap),
                  "first_records": runtime_stats.cold_runs()[:n],
                  "second_records": runtime_stats.cold_runs()[n:]}))
"""


def test_cache_misses_then_hits_are_heard_apart_from_compiles(tmp_path):
    """A first build with an empty cache directory counts misses; the
    same build with jax's in-memory caches dropped reads the cache:
    hits, a read time, and a `backend_compile_s` that is the read."""
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT, str(tmp_path / "cache")],
        capture_output=True, text=True, cwd=REPO, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    first, second = got["first"], got["second"]
    assert first["cache_misses"] >= 2 and first["cache_hits"] == 0
    assert first["cache_read_time_s"] == 0
    assert second["cache_hits"] >= 2 and second["cache_misses"] == 0
    assert second["cache_read_time_s"] > 0.0
    # a hit is a `compile` to backend_compile_duration: the counters
    # that tell them apart are the new ones
    assert second["compiles"] >= second["cache_hits"]
    # the records carry them run by run: start-up and step, both times
    for records, hit in ((got["first_records"], False),
                         (got["second_records"], True)):
        assert [r["feed_arrays"] for r in records] == [0, 1]
        for r in records:
            assert (r["cache_hits"] > 0) == hit
            assert (r["cache_misses"] > 0) == (not hit)
            assert (r["cache_read_s"] > 0.0) == hit
    assert sum(r["cache_misses"] for r in got["first_records"]) \
        == first["cache_misses"]
    assert sum(r["cache_hits"] for r in got["second_records"]) \
        == second["cache_hits"]
