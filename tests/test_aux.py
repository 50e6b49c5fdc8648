"""Aux subsystems: flags, nan-check, profiler annotations, debugger,
iteration batching (incl. compiled path).
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.flags import FLAGS


def test_flags_env_bridge(monkeypatch):
    import paddle_tpu.flags as flags_mod

    monkeypatch.setenv("FLAGS_check_nan_inf", "true")
    flags_mod.init_from_env()
    assert FLAGS.check_nan_inf is True
    FLAGS.check_nan_inf = False
    with pytest.raises(AttributeError):
        FLAGS.no_such_flag
    with pytest.raises(AttributeError):
        FLAGS.another_unknown = 1


def test_nan_check_raises():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data(name="x", shape=[2], dtype="float32")
        y = layers.log(x)  # log(-1) = nan
        exe = fluid.Executor()
        exe.run(startup)
        FLAGS.check_nan_inf = True
        try:
            with pytest.raises(FloatingPointError):
                exe.run(main, feed={"x": -np.ones((1, 2), np.float32)},
                        fetch_list=[y])
        finally:
            FLAGS.check_nan_inf = False


def test_iterations_single_device():
    """K iterations in one dispatch == K separate dispatches."""

    def build():
        x = layers.data(name="x", shape=[2], dtype="float32")
        y = layers.fc(x, size=1, bias_attr=False)
        loss = layers.mean(y)
        fluid.optimizer.SGDOptimizer(0.5).minimize(loss)
        return loss

    def run(iters):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 5
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope):
            loss = build()
            exe = fluid.Executor()
            exe.run(startup)
            feed = {"x": np.ones((4, 2), np.float32)}
            if iters == 1:
                for _ in range(4):
                    (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            else:
                (lv,) = exe.run(main, feed=feed, fetch_list=[loss],
                                iterations=4)
        return float(np.asarray(lv).reshape(-1)[0])

    np.testing.assert_allclose(run(1), run(4), rtol=1e-5)


def test_iterations_compiled_path():
    """CompiledProgram honors iterations (not silently 1)."""
    from paddle_tpu.parallel import make_mesh

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        x = layers.data(name="x", shape=[2], dtype="float32")
        y = layers.fc(x, size=1, bias_attr=False,
                      param_attr=fluid.ParamAttr(name="w"))
        loss = layers.mean(y)
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        w0 = np.asarray(scope.find_var("w")).copy()
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, mesh=make_mesh({"dp": 8}))
        feed = {"x": np.ones((8, 2), np.float32)}
        exe.run(cp, feed=feed, fetch_list=[loss], iterations=3)
        w3 = np.asarray(scope.find_var("w"))
        # loss = mean(x @ w) with x all-ones ⇒ dloss/dw_i = 1;
        # 3 iterations of SGD lr 0.1 ⇒ w - 0.3
        np.testing.assert_allclose(w3, w0 - 3 * 0.1, rtol=1e-5)


def test_profiler_record_event_and_timer():
    from paddle_tpu import profiler

    with profiler.record_event("unit-test-region"):
        pass
    t = profiler.Timer()
    t.start()
    t.pause()
    assert t.elapsed >= 0.0


def test_debugger_outputs():
    from paddle_tpu import debugger

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[2], dtype="float32")
        y = layers.fc(x, size=3, act="relu")
    text = debugger.pprint_program_codes(main)
    assert "mul" in text and "relu" in text
    dot = debugger.draw_block_graphviz(main.global_block())
    assert dot.startswith("digraph") and '"x"' in dot


def test_print_op_passthrough_and_py_func():
    """print → jax.debug.print passthrough; py_func → pure_callback
    (reference print_op.cc, py_func_op.cc).  Host callbacks need a
    backend with send/recv support (CPU here; TPU runtimes have it)."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], append_batch_size=False)
        y = layers.Print(layers.scale(x, 2.0), message="dbg")
        o = main.global_block().create_var(name="pyout", shape=(4,),
                                           dtype="float32")
        layers.py_func(lambda a: a + 1.0, y, o)
        o2 = main.global_block().create_var(name="pyout2", shape=(1,),
                                            dtype="float32")
        layers.py_func(lambda a: a.sum(keepdims=True), x, o2)
    exe = fluid.Executor()
    r1, r2 = exe.run(main, feed={"x": np.arange(4, dtype=np.float32)},
                     fetch_list=[o, o2])
    np.testing.assert_allclose(r1, np.arange(4) * 2 + 1)
    np.testing.assert_allclose(r2, [6.0])


def test_reader_queue_speed_test_mode_flag():
    """FLAGS.reader_queue_speed_test_mode serves the first batch forever
    (reference reader-throughput test mode)."""
    import numpy as np

    from paddle_tpu.data.pipeline import DeviceFeeder
    from paddle_tpu.flags import FLAGS

    def reader():
        for i in range(3):
            yield {"x": np.full((2,), i, np.float32)}

    FLAGS.reader_queue_speed_test_mode = True
    try:
        feeder = iter(DeviceFeeder(reader, capacity=2).start())
        got = [float(np.asarray(next(feeder)["x"])[0]) for _ in range(6)]
        assert got == [0.0] * 6  # first batch repeated, never consumed
    finally:
        FLAGS.reader_queue_speed_test_mode = False
        feeder_obj = feeder
        feeder_obj.reset()
    # normal mode still consumes in order
    feeder = iter(DeviceFeeder(reader, capacity=2).start())
    got = [float(np.asarray(b["x"])[0]) for b in feeder]
    assert got == [0.0, 1.0, 2.0]


def test_flag_registry_breadth():
    from paddle_tpu.flags import FLAGS

    d = FLAGS.to_dict()
    for name in ["check_nan_inf", "benchmark", "paddle_num_threads",
                 "rpc_deadline", "cudnn_deterministic",
                 "reader_queue_speed_test_mode",
                 "fraction_of_tpu_memory_to_use"]:
        assert name in d
