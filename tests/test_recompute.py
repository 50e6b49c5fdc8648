"""Rematerialization (fluid.recompute_scope -> jax.checkpoint):
marked segments recompute activations in the backward; math is
IDENTICAL with and without the scope, and the remat primitive actually
appears in the traced step.
"""

from __future__ import annotations

import contextlib
import functools
import re

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops import pallas as pallas_tier
from chip_compile import _state_by_shape


def _build(use_recompute):
    x = layers.data("x", shape=[16])
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=32, act="relu", name="pre")
    if use_recompute:
        with fluid.recompute_scope():
            h = layers.fc(h, size=32, act="relu", name="mid1")
            h = layers.fc(h, size=32, act="tanh", name="mid2")
    else:
        h = layers.fc(h, size=32, act="relu", name="mid1")
        h = layers.fc(h, size=32, act="tanh", name="mid2")
    logits = layers.fc(h, size=4, name="post")
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
    fluid.optimizer.MomentumOptimizer(learning_rate=0.1,
                                      momentum=0.9).minimize(loss)
    return loss


def _run(use_recompute, steps=4):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    scope = fluid.Scope()
    losses = []
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss = _build(use_recompute)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(2)
        xv = rng.randn(32, 16).astype(np.float32)
        yv = rng.randint(0, 4, (32, 1)).astype(np.int64)
        for _ in range(steps):
            lv, = exe.run(main, feed={"x": xv, "y": yv},
                          fetch_list=[loss])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    return main, losses


def test_recompute_scope_matches_plain_training():
    _, plain = _run(False)
    main_r, remat = _run(True)
    np.testing.assert_allclose(remat, plain, rtol=1e-6, atol=1e-7)
    assert remat[-1] < remat[0]
    # the scope actually stamped the ops
    tagged = [op.desc.type for op in main_r.global_block().ops
              if op.desc.attrs.get("__recompute__") is not None]
    assert "mul" in tagged and len(tagged) >= 4


def test_recompute_emits_remat_primitive():
    """The traced step of a recompute program contains the checkpoint
    primitive; the plain program does not."""
    from paddle_tpu.core.executor import interpret_program

    def jaxpr_of(use_recompute):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 5
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), fluid.unique_name.guard():
            loss = _build(use_recompute)
            exe = fluid.Executor()
            exe.run(startup)
            gs = fluid.global_scope()
            state = {k: v for k, v in gs.vars.items() if v is not None
                     and not k.startswith("__")}
            feeds = {"x": np.zeros((8, 16), np.float32),
                     "y": np.zeros((8, 1), np.int64)}

            def step(st, fd):
                env = dict(st)
                env.update(fd)
                env = interpret_program(main, env,
                                        jax.random.PRNGKey(0),
                                        fetch_names=(loss.name,))
                return env[loss.name]

            return str(jax.make_jaxpr(step)(state, feeds))

    with_r = jaxpr_of(True)
    without = jaxpr_of(False)
    assert "remat" in with_r or "checkpoint" in with_r
    assert "remat" not in without and "checkpoint" not in without


def test_recompute_scope_nests_and_restores():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[4])
        layers.fc(x, size=4)                       # untagged
        with fluid.recompute_scope():
            layers.fc(x, size=4)                   # tagged
        layers.fc(x, size=4)                       # untagged again
    tags = [op.desc.attrs.get("__recompute__")
            for op in main.global_block().ops]
    assert any(t is not None for t in tags)
    assert tags[0] is None and tags[-1] is None


def test_transformer_recompute_option_parity():
    """build_model(recompute=True) wraps each encoder/decoder layer in
    a remat scope; trajectory identical to the plain build."""
    from paddle_tpu.models import transformer

    def run(rc):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 4
        scope = fluid.Scope()
        losses = []
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), fluid.unique_name.guard():
            m = transformer.build_model(
                src_vocab_size=64, trg_vocab_size=64, max_length=8,
                n_layer=2, n_head=2, d_model=16, d_inner_hid=32,
                dropout=0.0, recompute=rc)
            exe = fluid.Executor()
            exe.run(startup)
            feed = transformer.make_fake_batch(4, 8, 60, 60)
            for _ in range(3):
                lv, = exe.run(main, feed=feed, fetch_list=[m["loss"]])
                losses.append(float(np.asarray(lv).reshape(-1)[0]))
        if rc:
            tagged = sum(
                1 for op in main.global_block().ops
                if op.desc.attrs.get("__recompute__") is not None)
            assert tagged > 20  # both stacks tagged
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6)


# -- a segment keeps its attention kernel's residuals ------------------------
#
# A recompute segment keeps its inputs AND the two residuals a flash
# forward rule names (`ops/pallas keep_residuals`: output, logsumexp):
# its backward pass rebuilds q, k, v from the projections and does not
# run the forward kernel a second time.  Every attention family, alone
# and inside a counted `static_rnn` body, float32 and bf16 AMP, against
# the SAME build with the policy taken away (`jax.checkpoint`'s default:
# the inputs alone, which is what ran before).

T, HID = 128, 64
# family -> (forward kernel's name, d_head, heads, key/value heads, window)
FAMILIES = {
    "flash": ("flash_fwd", 128, 2, 2, None),
    "flash_band_window": ("flash_window_fwd", 128, 2, 1, 32),
    "flash_band_full": ("flash_fwd", 128, 2, 1, None),
    "flash_gqa": ("flash_gqa_fwd", 64, 4, 2, None),     # heads in pairs
    "flash_mla": ("flash_mla_fwd", 128, 2, 2, None),
}
TRIPS = 3


def _layers(family):
    """Two layers where "a call a LAYER" is the mechanism: the plain
    family's second segment keeps its own residuals beside the first's
    (and every cell's whole step counts a call a layer by its trace,
    tests/test_chip_compile_cells.py).  One layer for the other
    families, whose mechanism is that THEIR forward rule's names reach
    the policy: a second layer builds and compiles every interpreted
    kernel twice and proves what the plain family's does."""
    return 2 if family == "flash" else 1


def _proj(x, width, name):
    return layers.fc(x, size=width, num_flatten_dims=2, bias_attr=False,
                     name=name)


def _attention_layer(h, geometry, tag, recompute=True):
    kernel, d, heads, kv_heads, window = geometry
    scope = fluid.recompute_scope() if recompute else contextlib.nullcontext()
    with scope:
        q = _proj(h, heads * d, f"q{tag}")
        k = _proj(h, kv_heads * d, f"k{tag}")
        v = _proj(h, kv_heads * d, f"v{tag}")
        if kernel == "flash_mla_fwd":
            o = layers.latent_attention(
                q, _proj(h, heads * 64, f"qr{tag}"), k,
                _proj(h, 64, f"kr{tag}"), v, heads)
        else:
            o = layers.flash_attention(
                q, k, v, causal=True, use_pallas=True, layout="nthd",
                n_head=heads, n_kv_head=kv_heads, window=window)
        return layers.elementwise_add(h, _proj(o, h.shape[-1], f"o{tag}"))


def attention_stack(geometry, t, hidden, depth, trips=0, recompute=True):
    """`depth` attention layers over x (1, t, hidden), each a recompute
    segment (projections, the flash call, the output projection, the
    residual add), in a straight stack or, with `trips`, as the body of
    a counted loop; returns the loss."""
    x = layers.data("x", shape=[t, hidden], dtype="float32")

    def stack(y):
        for i in range(depth):
            y = _attention_layer(y, geometry, i, recompute)
        return y

    if not trips:
        return layers.mean(stack(x))
    loop = layers.StaticRNN(trip_count=trips)
    with loop.step():
        h = loop.memory(init=x)
        y = stack(h)
        loop.update_memory(h, y)
        loop.step_output(layers.reduce_mean(y, dim=[1, 2]))
    return layers.mean(loop())


def _pallas_calls(jaxpr, found=None):
    """Kernel names of the `pallas_call` equations of a jaxpr and of
    every jaxpr nested in it, kernels' own bodies left out, and the
    number of `dot_general`s beside them."""
    found = {"kernels": [], "dots": 0} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found["kernels"].append(re.search(
                r"pallas_(\w+)", str(eqn.source_info.name_stack)).group(1))
            continue
        found["dots"] += eqn.primitive.name == "dot_general"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


@functools.lru_cache(maxsize=None)
def _built(family, looped, amp, policy=True, recompute=True):
    """One build: `_layers(family)` attention layers, each a recompute
    segment, in a straight stack or as the body of a counted loop of TRIPS trips;
    loss and the gradient of every parameter, the kernels of the step's
    jaxpr and the counters around the build."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard(), pytest.MonkeyPatch.context() as patch:
        if not policy:
            patch.setattr(pallas_tier, "segment_policy", lambda: None)
        loss = attention_stack(FAMILIES[family], T, HID, _layers(family),
                               TRIPS if looped else 0, recompute)
        if amp:
            main._amp_lists = fluid.amp.AutoMixedPrecisionLists()
        grads = [g for _, g in fluid.append_backward(loss)]
        exe = fluid.Executor(fluid.CPUPlace())
        if recompute:
            exe.run(startup)
        else:       # traced and never run
            _state_by_shape(main, scope)
        feed = {"x": np.random.default_rng(5).normal(
            size=(1, T, HID)).astype(np.float32)}
        names = [loss.name] + [g.name for g in grads]
        before = runtime_stats.snapshot()
        # the build with no segment is read for its jaxpr and for the
        # counters around a trace: it is traced once, below, and not run
        fetched = exe.run(main, feed=feed, scope=scope,
                          fetch_list=names) if recompute else []
        counted = runtime_stats.delta(before)
        step, state, feeds = exe._prepare(
            main, {k: jax.numpy.asarray(v) for k, v in feed.items()}, names,
            scope, 1, True)
        found = _pallas_calls(jax.make_jaxpr(step)(state, feeds).jaxpr)
        if not recompute:
            counted = runtime_stats.delta(before)
    return dict(fetched=[np.asarray(f) for f in fetched],
                kept=(counted["recompute_kept_residuals"],
                      counted["recompute_kept_bytes"]), **found)


CASES = [pytest.param(family, looped, amp,
                      id=f"{family}-{'loop' if looped else 'stack'}-"
                         f"{'bf16' if amp else 'f32'}")
         for family in FAMILIES for looped in (False, True)
         for amp in (False, True)]


@pytest.mark.parametrize("family, looped, amp", CASES)
def test_a_segments_backward_runs_no_forward_kernel_again(family, looped,
                                                          amp):
    """(a) The step holds each layer's forward kernel ONCE; with the
    policy taken away, twice: the names reach the policy, and without
    it the segment keeps its inputs alone, as before."""
    forward, layers = FAMILIES[family][0], _layers(family)
    kept = _built(family, looped, amp)["kernels"]
    alone = _built(family, looped, amp, policy=False)["kernels"]
    assert kept.count(forward) == layers
    assert alone.count(forward) == 2 * layers
    # the backward kernels are the same ones
    assert sorted(k for k in kept if k != forward) \
        == sorted(k for k in alone if k != forward)
    assert len(kept) == len(alone) - layers


@pytest.mark.parametrize("family, looped, amp", CASES)
def test_kept_residuals_change_no_bit(family, looped, amp):
    """(b) Loss and every gradient leaf are the no-policy build's bit
    for bit: the same kernels on the same operands in the same order."""
    kept = _built(family, looped, amp)["fetched"]
    alone = _built(family, looped, amp, policy=False)["fetched"]
    assert len(kept) == len(alone) >= 1 + 4 * _layers(family)
    assert np.isfinite(kept[0]).all() and any(np.abs(g).max() > 0
                                              for g in kept[1:])
    for got, want in zip(kept, alone):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("family, looped, amp", CASES)
def test_the_projections_before_the_kernel_are_still_recomputed(
        family, looped, amp):
    """(c) The segment did not turn into "save everything": the step
    multiplies as often as the no-policy build (q, k, v rebuilt from
    the segment's input), and more often than the same layers with no
    segment around them."""
    kept = _built(family, looped, amp)["dots"]
    alone = _built(family, looped, amp, policy=False)["dots"]
    plain = _built(family, looped, amp, recompute=False)
    assert kept == alone
    projections = 5 if family == "flash_mla" else 3
    assert kept >= plain["dots"] + projections * _layers(family)
    assert plain["kernels"].count(FAMILIES[family][0]) == _layers(family)


@pytest.mark.parametrize("family, looped, amp", CASES)
def test_the_counters_read_the_kept_calls_and_their_bytes(family, looped,
                                                          amp):
    """(e) `recompute_kept_residuals` / `_bytes` around a step build: a
    call a layer (a loop's body once, as traced), the output in the
    operands' dtype + 8 float32 sublanes of logsumexp a head; 0 where
    no segment is open."""
    calls, nbytes = _built(family, looped, amp)["kept"]
    layers = _layers(family)
    assert calls == layers
    _, d, heads, _, _ = FAMILIES[family]
    assert nbytes == layers * (T * heads * d * (2 if amp else 4)
                               + heads * 8 * T * 4)
    assert _built(family, looped, amp, recompute=False)["kept"] == (0, 0)
    # the policy is not what counts: the segment's trace is
    assert _built(family, looped, amp, policy=False)["kept"][0] == layers


@pytest.mark.parametrize("looped", [False, True], ids=["stack", "loop"])
def test_a_segment_with_no_flash_call_lowers_to_the_same_text(
        looped, monkeypatch):
    """(d) Where a segment names nothing the policy keeps nothing: the
    lowered step is the text the default policy gives."""
    from test_control_flow_shared_params import run as run_loop

    def text():
        if looped:
            return run_loop(3, recompute=True)["text"]
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 5
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), fluid.unique_name.guard():
            loss = _build(True)
            exe = fluid.Executor()
            exe.run(startup)
            step, state, feeds = exe._prepare(
                main, {"x": np.zeros((8, 16), np.float32),
                       "y": np.zeros((8, 1), np.int64)},
                [loss.name], scope, 1, True)
            return step.lower(state, feeds).as_text()

    before = runtime_stats.snapshot()
    kept = text()
    assert runtime_stats.delta(before)["recompute_kept_residuals"] == 0
    monkeypatch.setattr(pallas_tier, "segment_policy", lambda: None)
    assert text() == kept
    assert "optimization_barrier" in kept     # a segment WAS there
