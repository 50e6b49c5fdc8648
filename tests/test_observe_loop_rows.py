"""A counted `while`'s body in the cost rows and in the trace join
(ISSUE 36): the body's instructions get rows of their own (bucket,
FLOPs and bytes per call, fluid op, name scope), the `while` row keeps
no cost that a body row carries, `join_events` buckets body events as
it does entry events and counts `calls` = trips; a body whose trip
count is unknown still lands in `loop`."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.program import name_scope
from paddle_tpu.observe import cost, trace

TRIPS, N, D = 5, 16, 64


def _looped_program(trips=TRIPS):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[D], dtype="float32")
        with name_scope("ut_loop"):
            loop = layers.StaticRNN(trip_count=trips)
            with loop.step():
                h = loop.memory(init=x)
                y = layers.fc(h, size=D, bias_attr=False, act="tanh")
                loop.update_memory(h, y)
                loop.step_output(layers.reduce_mean(y, dim=[1]))
            per_trip = loop()
        loss = layers.mean(per_trip)
        fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.ones((N, D), np.float32)}
        compiled = exe.compiled_step(main, feed=feed, fetch_list=[loss],
                                     scope=scope)
    return cost.compiled_hlo_proto(compiled)


@pytest.fixture(scope="module")
def proto():
    return _looped_program()


def test_a_counted_loops_body_has_rows_of_its_own(proto):
    rows = cost.instruction_costs(proto)
    loops = [r for r in rows if r["opcode"] == "while"]
    assert len(loops) == 2              # the forward scan and its transpose
    for loop in loops:
        assert loop["trip_count"] == TRIPS and loop["bucket"] == "loop"
        assert loop["flops"] == loop["bytes"] == 0
        assert loop["loop_of"] is None and loop["trips"] == 1
    inside = [r for r in rows if r["loop_of"]]
    assert {r["loop_of"] for r in inside} == {r["name"] for r in loops}
    assert all(r["trips"] == TRIPS for r in inside)
    dots = [r for r in inside if r["bucket"] == "matmul"]
    # one forward product and two backward ones a trip, PER CALL
    assert sorted(r["flops"] for r in dots) == [2.0 * N * D * D] * 3
    for r in dots:
        assert r["op_type"] == "mul" and r["bytes"] > 0
    # every instruction of every computation the loops call is a row
    # once, under its own name
    assert len({r["name"] for r in rows}) == len(rows)


def test_loop_row_and_body_rows_carry_each_flop_once(proto):
    rows = cost.instruction_costs(proto)
    total = cost.total_costs(proto)["flops"]
    assert total == sum(cost.per_step(r, "flops") for r in rows)
    for loop in (r for r in rows if r["opcode"] == "while"):
        body = sum(cost.per_step(r, "flops") for r in rows
                   if r["loop_of"] == loop["name"])
        assert body == loop["body_flops"] > 0
    matmul = sum(cost.per_step(r, "flops") for r in rows
                 if r["bucket"] == "matmul")
    assert matmul == TRIPS * 3 * 2.0 * N * D * D
    assert matmul <= total < 1.2 * matmul
    assert cost.total_costs(proto)["bucket_flops"]["matmul"] == matmul
    # op_cost_table sums a step, not a call
    table = cost.op_cost_table(proto=proto)
    assert sum(g["flops"] for g in table if g["bucket"] == "matmul") \
        == matmul


def test_the_program_map_gives_body_instructions_buckets_and_scopes(proto):
    pmap = trace.program_map(proto)
    rows = [r for r in cost.instruction_costs(proto, every_branch=True)
            if r["loop_of"]]
    assert rows
    for r in rows:
        assert pmap[r["name"]]["bucket"] == r["bucket"] is not None
        assert pmap[r["name"]]["flops"] == r["flops"]
    dots = [r["name"] for r in rows if r["bucket"] == "matmul"]
    for name in dots:
        op_name = pmap[name]["op_name"]
        assert trace.fluid_op_of(op_name) == "mul"
        assert "ut_loop" in trace.name_scope_of(op_name).split("/")


def test_the_op_count_does_not_depend_on_the_trip_count():
    a = cost.HloModule(_looped_program(3))
    b = cost.HloModule(_looped_program(7))
    assert len(a.computations) == len(b.computations)


def test_a_loop_without_a_known_trip_count_keeps_its_cost_and_no_rows():
    def f(x):
        w = jnp.eye(8) * 1.01

        def cond(c):
            return jnp.sum(c[0]) < 100.0

        def body(c):
            return (c[0] @ w, c[1] + 1)

        return lax.while_loop(cond, body, (x, 0))

    compiled = jax.jit(f).lower(jnp.ones((8, 8), jnp.float32)).compile()
    proto = cost.compiled_hlo_proto(compiled)
    rows = cost.instruction_costs(proto)
    (loop,) = [r for r in rows if r["opcode"] == "while"]
    assert loop["trip_count"] is None and loop["bucket"] == "[loop?]"
    assert loop["flops"] >= 2.0 * 8 ** 3            # the body, once
    assert not [r for r in rows if r["loop_of"]]
    # ... so a trace's body events find no bucket and land in `loop`
    pmap = trace.program_map(proto)
    module = cost.HloModule(proto)
    entry = {i.name for i in module.entry.instructions}
    body = [n for n, info in pmap.items() if n not in entry]
    assert body and all(pmap[n]["bucket"] is None for n in body)
    (row,) = trace.join_events([(body[0], 1.0, 0.5, "jit_f")], [],
                               {"jit_f": pmap})
    assert row["bucket"] == trace.BODY_BUCKET and row["joined"]


STEP = "jit_step(42)"
WHILE = ("%while.7 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
         "condition=%cond, body=%body")


def _info(op_name, bucket, flops=0.0, nbytes=0.0, kernel=None):
    return {"op_name": op_name, "bucket": bucket, "flops": flops,
            "bytes": nbytes, "kernel": kernel}


def test_join_events_buckets_a_counted_bodys_events_and_counts_trips():
    """A synthetic nested line: one step's `while` of 4 trips, each a
    matmul fusion, a Mosaic kernel and an event in no map.  The body
    rows carry their own buckets and per-call FLOPs, `calls` counts the
    trips, the `while` keeps the time no body event covers, and the
    rows sum to the line's busy time."""
    body = "jit(step)/jvp(ut_loop/static_rnn:9)/while/body/ut_loop/"
    programs = {STEP: {
        "while.7": _info("jit(step)/jvp(ut_loop/static_rnn:9)/while",
                         "loop"),
        "fusion.21": _info(body + "mul:3/dot_general", "matmul", 4e9, 2e6),
        "custom-call.5": _info(
            body + "flash_attention:7/pallas_flash_fwd", "custom_call",
            8e9, 1e6, kernel="flash_fwd"),
        "add.1": _info("jit(step)/adam:40/add", "elementwise", 10.0, 4.0)}}
    ops = [(WHILE, 1.0, 4.0)]
    for trip in range(4):
        t = 1.0 + trip
        ops += [("%fusion.21 = f32[8]{0} fusion(f32[8]{0} %x)", t + 0.125,
                 0.25),
                ("%custom-call.5 = f32[8]{0} custom-call(f32[8]{0} %x)",
                 t + 0.375, 0.375),
                ("%mystery.3 = f32[8]{0} add(f32[8]{0} %x)", t + 0.75,
                 0.125)]
    ops.append(("%add.1 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)", 5.0,
                0.5))
    rows = {r["instruction"]: r for r in trace.join_events(
        ops, [(STEP, 0.9, 5.0)], programs)}
    assert rows["fusion.21"]["bucket"] == "matmul"
    assert rows["fusion.21"]["calls"] == 4
    assert rows["fusion.21"]["flops"] == 4e9            # per call
    assert rows["fusion.21"]["self_s"] == pytest.approx(1.0)
    assert rows["fusion.21"]["op_type"] == "mul"
    assert rows["fusion.21"]["phase"] == "forward"
    assert "ut_loop" in rows["fusion.21"]["name_scope"].split("/")
    assert rows["custom-call.5"]["kernel"] == "flash_fwd"
    assert rows["custom-call.5"]["calls"] == 4
    assert rows["mystery.3"]["bucket"] == trace.UNJOINED_BUCKET
    assert rows["while.7"]["bucket"] == "loop"
    assert rows["while.7"]["calls"] == 1
    assert rows["while.7"]["total_s"] == pytest.approx(4.0)
    assert rows["while.7"]["self_s"] == pytest.approx(4.0 - 4 * 0.75)
    assert sum(r["self_s"] for r in rows.values()) == pytest.approx(4.5)
    # the MXU's share over the loop's matmuls: FLOPs per call x calls
    mxu = [r for r in rows.values() if r["bucket"] == "matmul"]
    assert sum(r["flops"] * r["calls"] for r in mxu) == 16e9


def _plain_module(f, *args):
    """The module BEFORE optimisation: no `known_trip_count` on its
    loops, which are in their plain form, as the TPU compiler leaves
    them."""
    hlo = jax.jit(f).lower(*args).compiler_ir(dialect="hlo")
    return cost.HloModule(hlo.as_serialized_hlo_module_proto())


def _whiles(module):
    return [(comp, i) for comp in module.computations.values()
            for i in comp.instructions if i.opcode == "while"]


@pytest.mark.parametrize("lo, hi, trips", [(0, 7, 7), (2, 11, 9),
                                           (3, 4, 1)])
def test_a_trip_count_is_read_off_the_loops_plain_form(lo, hi, trips):
    """Without XLA:CPU's annotation (the TPU compiler leaves none) the
    count is read off the instructions: the condition's constant, the
    body's step, the carry's start."""
    def f(x):
        return lax.fori_loop(lo, hi, lambda i, c: c * 1.5 + 1.0, x)

    module = _plain_module(f, jnp.ones((4,), jnp.float32))
    ((comp, loop),) = _whiles(module)
    assert not loop.backend_config
    assert cost.while_trip_count(module, comp, loop) == trips


def test_a_scans_plain_form_counts_and_a_data_dependent_loop_does_not():
    def scanned(x):
        return lax.scan(lambda c, _: (jnp.tanh(c), jnp.sum(c)), x, None,
                        length=5)

    module = _plain_module(scanned, jnp.ones((4,), jnp.float32))
    ((comp, loop),) = _whiles(module)
    assert cost.while_trip_count(module, comp, loop) == 5

    def decode(x):
        return lax.while_loop(lambda c: jnp.sum(c[0]) < 100.0,
                              lambda c: (c[0] * 2.0, c[1] + 1), (x, 0))

    module = _plain_module(decode, jnp.ones((4,), jnp.float32))
    ((comp, loop),) = _whiles(module)
    assert cost.while_trip_count(module, comp, loop) is None
