"""A counted sub-block (`layers.StaticRNN(trip_count=R)`, `lax.scan`
with no step input) over shared parameters (ISSUE 36): a parameter
read inside it gets the SUM of its trips' gradients, a
`recompute_scope` inside it changes no value and lowers the saved
bytes, AMP's casts reach its ops, and the Program's op count is
independent of the trip count."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observe.monitoring import runtime_stats

N, D = 8, 32


def build(trips, recompute=False, depth=3):
    """x -> `trips` x [`depth` x tanh(fc)] over ONE set of weights;
    loss = mean over trips and rows of the state's mean."""
    x = layers.data(name="x", shape=[D], dtype="float32")
    loop = layers.StaticRNN(trip_count=trips)
    with loop.step():
        h = loop.memory(init=x)
        y = h
        for _ in range(depth):
            if recompute:
                with fluid.recompute_scope():
                    y = layers.fc(y, size=4 * D, bias_attr=False, act="tanh")
                    y = layers.fc(y, size=D, bias_attr=False, act="tanh")
            else:
                y = layers.fc(y, size=4 * D, bias_attr=False, act="tanh")
                y = layers.fc(y, size=D, bias_attr=False, act="tanh")
        loop.update_memory(h, y)
        loop.step_output(layers.reduce_mean(y, dim=[1]))
    per_trip = loop()
    return layers.mean(per_trip), per_trip


def run(trips, recompute=False, amp=False, weights=None, seed=3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss, per_trip = build(trips, recompute)
        if amp:
            main._amp_lists = fluid.amp.AutoMixedPrecisionLists()
        grads = [g for _, g in fluid.append_backward(loss)]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        names = [p.name for p in main.all_parameters()]
        for name, w in zip(names, weights or ()):
            scope.set_var(name, w)
        weights = [np.asarray(scope.find_var(n)) for n in names]
        feed = {"x": np.random.default_rng(0).normal(
            size=(N, D)).astype(np.float32)}
        before = runtime_stats.snapshot()
        fetched = exe.run(main, feed=feed, scope=scope,
                          fetch_list=[loss, per_trip] + grads)
        counted = runtime_stats.delta(before)["loop_trips"]
        step, state, feeds = exe._prepare(
            main, {k: jnp.asarray(v) for k, v in feed.items()},
            [loss.name] + [g.name for g in grads], scope, 1, True)
        text = step.lower(state, feeds).as_text()
    return dict(loss=fetched[0], per_trip=fetched[1], grads=fetched[2:],
                weights=weights, feed=feed, text=text, trips=counted,
                ops=[len(b.ops) for b in main.blocks], names=names)


def reference_grads(weights, x, trips, untied=False):
    """jax.grad of the same mathematics in a Python `for`; `untied`
    gives each trip leaves of its own (equal values): the per-trip
    parts of a shared leaf's gradient."""
    def f(copies):
        h, means = x, []
        for r in range(trips):
            ws = copies[r] if untied else copies
            for a, b in zip(ws[::2], ws[1::2]):
                h = jnp.tanh(jnp.tanh(h @ a) @ b)
            means.append(jnp.mean(h, axis=1))
        return jnp.mean(jnp.stack(means))

    ws = [jnp.asarray(w) for w in weights]
    with jax.default_matmul_precision("highest"):
        return jax.grad(f)([ws] * trips if untied else ws)


def test_a_shared_parameters_gradient_is_the_sum_of_its_trips():
    got = run(4)
    assert got["ops"][0] > 0 and len(got["ops"]) == 2
    assert got["per_trip"].shape == (4, N)
    assert got["trips"] == 4            # the counter reads the Program's
    want = reference_grads(got["weights"], got["feed"]["x"], 4)
    parts = reference_grads(got["weights"], got["feed"]["x"], 4, untied=True)
    for i, (g, w) in enumerate(zip(got["grads"], want)):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-7)
        each = [np.asarray(parts[r][i]) for r in range(4)]
        np.testing.assert_allclose(g, sum(each), rtol=2e-5, atol=1e-7)
        for part in each:       # no single trip's part would pass
            assert np.abs(g - part).max() > 1e-2 * np.abs(g).max()


def test_the_op_count_is_independent_of_the_trip_count():
    two, six = run(2), run(6)
    assert two["ops"] == six["ops"]
    assert two["names"] == six["names"]
    assert six["per_trip"].shape == (6, N)
    # the first trips do not know how many follow
    six_from_two = run(6, weights=two["weights"])
    np.testing.assert_array_equal(six_from_two["per_trip"][:2],
                                  two["per_trip"])


def _saved_bytes(trips, recompute, weights):
    """Bytes the scan's forward pass hands its backward pass: the
    stacked residuals of `jax.linearize`'s jaxpr are the (trips, ...)
    outputs of the forward `scan` beyond the per-trip means."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss, _ = build(trips, recompute)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        names = [p.name for p in main.all_parameters()]
        for name, w in zip(names, weights):
            scope.set_var(name, w)
        from paddle_tpu.core.executor import run_ops

        ops = main.global_block().ops

        def forward(params, x):
            env = dict(zip(names, params))
            env["x"] = x
            run_ops(ops, env, None, program=main,
                    keep_names={loss.name})
            return jnp.squeeze(env[loss.name])

        params = [jnp.asarray(w) for w in weights]
        x = jnp.ones((N, D), jnp.float32)
        _, vjp = jax.vjp(forward, params, x)
        leaves = jax.tree.leaves(vjp)
    return sum(leaf.size * leaf.dtype.itemsize for leaf in leaves
               if hasattr(leaf, "size") and leaf.ndim
               and leaf.shape[0] == trips)


def test_recompute_inside_the_sub_block_keeps_values_and_saves_less():
    plain = run(4)
    remat = run(4, recompute=True, weights=plain["weights"])
    np.testing.assert_array_equal(remat["loss"], plain["loss"])
    np.testing.assert_array_equal(remat["per_trip"], plain["per_trip"])
    for g, w in zip(remat["grads"], plain["grads"]):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-8)
    kept = _saved_bytes(4, False, plain["weights"])
    kept_remat = _saved_bytes(4, True, plain["weights"])
    # plain: every layer's pre-activations and products; recomputed:
    # each segment's input (N x D a segment a trip)
    assert kept_remat <= 4 * 3 * N * D * 4 * 1.5
    assert kept_remat < 0.35 * kept, (kept_remat, kept)


def test_amp_casts_reach_the_sub_blocks_ops():
    plain = run(3)
    amp = run(3, amp=True, weights=plain["weights"])
    assert "bf16" not in plain["text"]
    # the products inside the body are bfloat16 ...
    body = amp["text"]
    assert "xbf16>" in body and "stablehlo.while" in body
    dots = [ln for ln in body.splitlines() if "dot_general" in ln]
    assert dots and all("bf16" in ln for ln in dots)
    # ... and the numbers are bfloat16's, not float32's
    err = np.abs(amp["per_trip"] - plain["per_trip"]).max()
    assert 1e-5 < err < 5e-2


def test_a_counted_loop_refuses_a_step_input_and_a_trip_count_of_zero():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[D], dtype="float32")
        seq = layers.data(name="seq", shape=[N, D], dtype="float32")
        loop = layers.StaticRNN(trip_count=3)
        with loop.step():
            with pytest.raises(RuntimeError, match="no step input"):
                loop.step_input(seq)
            h = loop.memory(init=x)
            y = layers.scale(h, scale=2.0)
            loop.update_memory(h, y)
            loop.step_output(y)
        stacked = loop()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ones = np.ones((N, D), np.float32)
        s, = exe.run(main, feed={"x": ones,
                                 "seq": np.ones((2, N, D), np.float32)},
                     fetch_list=[stacked], scope=scope)
    np.testing.assert_array_equal(s[:, 0, 0], [2.0, 4.0, 8.0])
    with pytest.raises(ValueError, match="positive"):
        layers.StaticRNN(trip_count=0)
