"""dp-mesh training + explicit (quantized) gradient synchronization
(ISSUE 10, docs/DIST.md).

Acceptance pins:
- dp=8 loss trajectory matches single-device at a FIXED global batch
  within a pinned tolerance (the GSPMD implicit path);
- the explicit bf16 exchange matches the implicit path (control arm);
- int8 quantized grad sync trains to a trajectory within the
  documented tolerance of bf16 dp (the EQuARX correctness A/B the
  virtual mesh can record; wall clock is a chip question);
- SparseGrad stays sparse through the exchange: the embedding-table
  gradient is never routed into the quantized dense path, and
  untouched table rows stay bit-identical (the lazy-update property);
- designed loud errors: composed meshes, gradient accumulation.

Tolerances are measured-then-pinned (see comments), not aspirational.
All models here are deliberately tiny: 8 virtual devices share one
host core, so every compile/dispatch is serialized.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.parallel import GradSyncConfig, make_mesh
from paddle_tpu.parallel.strategies import ShardingRules

N_DEV = 8
STEPS = 6


@pytest.fixture(scope="module", autouse=True)
def _need_devices():
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"needs {N_DEV} devices")


def _batches(n=STEPS, b=64, din=32, seed=0):
    rng = np.random.RandomState(seed)
    return [{"x": rng.randn(b, din).astype(np.float32),
             "y": rng.randn(b, 1).astype(np.float32)}
            for _ in range(n)]


def _build_mlp():
    # dropout-free on purpose: the explicit exchange folds the rank
    # index into the RNG key (per-rank dropout streams), so EXACT
    # parity claims are only meaningful for deterministic programs
    x = layers.data("x", shape=[32], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    h = layers.fc(x, size=128, act="relu")
    h = layers.fc(h, size=128, act="relu")
    pred = layers.fc(h, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _run(grad_sync, mesh_axes, batches=None, build=_build_mlp,
         accumulation_steps=1):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        loss = build()
        exe = fluid.Executor()
        exe.run(startup)
        if mesh_axes:
            bs = fluid.BuildStrategy()
            bs.grad_sync = grad_sync
            fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, build_strategy=bs,
                mesh=make_mesh(mesh_axes))
        losses = []
        for b in (batches or _batches()):
            (lv,) = exe.run(main, feed=b, fetch_list=[loss],
                            accumulation_steps=accumulation_steps)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    return np.asarray(losses), scope


def test_dp_loss_parity_vs_single_device():
    """ACCEPTANCE: dp=8 (global batch fixed) vs single device.  jit
    value semantics make the partitioned step numerically equivalent
    up to reduction-order float drift; measured 7e-8 max relative over
    6 steps on this backend — pinned at 1e-5."""
    single, _ = _run(None, None)
    dp, _ = _run(None, {"dp": N_DEV})
    np.testing.assert_allclose(dp, single, rtol=1e-5, atol=1e-7)


def test_explicit_bf16_matches_implicit_dp():
    """The explicit shard_map exchange is the same math as the GSPMD
    all-reduce (psum of local-mean grads + pmean loss) — the control
    arm that isolates quantization in the int8 A/B."""
    implicit, _ = _run(None, {"dp": N_DEV})
    explicit, _ = _run("bf16", {"dp": N_DEV})
    np.testing.assert_allclose(explicit, implicit, rtol=1e-5,
                               atol=1e-7)


def test_int8_trajectory_within_documented_tolerance():
    """ACCEPTANCE: int8 quantized grad sync vs bf16 dp.  The
    documented tolerance (docs/DIST.md): per-step relative loss
    deviation under 1e-2 on this model class over 6 steps, and the
    trajectory must actually DESCEND (quantization noise must not
    masquerade as training).  Measured here: ~1e-4 after 6 steps —
    pinned with margin at 1e-2."""
    bf16, _ = _run("bf16", {"dp": N_DEV})
    int8, _ = _run(GradSyncConfig("int8"), {"dp": N_DEV})
    rel = np.abs(int8 - bf16) / np.maximum(np.abs(bf16), 1e-6)
    assert rel.max() < 1e-2, f"int8 trajectory off by {rel.max():.2e}"
    assert int8[-1] < int8[0], "int8 run did not descend"
    assert np.isfinite(int8).all()


def test_int8_quantization_is_actually_active():
    """The int8 trajectory must DIFFER from bf16 at the bit level on a
    model with above-floor tensors — otherwise the A/B would be
    comparing the exchange to itself (a floor set too high silently
    turns the feature off)."""
    bf16, _ = _run("bf16", {"dp": N_DEV})
    int8, _ = _run(GradSyncConfig("int8", min_quant_numel=1),
                   {"dp": N_DEV})
    assert not np.array_equal(int8, bf16)


def test_int8_run_is_deterministic():
    """Same seed + same feeds -> bitwise-identical trajectory: the
    quantized exchange introduces error, never nondeterminism."""
    a, _ = _run(GradSyncConfig("int8"), {"dp": N_DEV})
    b, _ = _run(GradSyncConfig("int8"), {"dp": N_DEV})
    assert np.array_equal(a, b)


# -- sparse path -----------------------------------------------------------

V, D, B, F = 64, 16, 32, 4


def _build_sparse():
    ids = layers.data("ids", shape=[B, F], dtype="int64",
                      append_batch_size=False)
    y = layers.data("y", shape=[B, 1], append_batch_size=False)
    emb = layers.embedding(
        ids, size=[V, D], is_sparse=True,
        param_attr=fluid.ParamAttr(
            name="tbl", initializer=fluid.initializer.Constant(0.05)))
    s = layers.reduce_sum(emb, dim=1)
    h = layers.fc(s, size=256, act="relu")
    p = layers.fc(h, size=1)
    loss = layers.reduce_mean(layers.square_error_cost(p, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _sparse_batches(n=4):
    rng = np.random.RandomState(1)
    # ids drawn from the LOWER half of the vocab only: the upper half
    # must come through training untouched (the sparsity proof)
    return [{"ids": rng.randint(0, V // 2, (B, F)).astype(np.int64),
             "y": rng.rand(B, 1).astype(np.float32)}
            for _ in range(n)]


def test_sparse_grads_stay_sparse_under_int8(monkeypatch):
    """SparseGrad never enters the quantized dense exchange (ids+rows
    all_gather keeps it O(touched)), and untouched embedding rows are
    bit-identical after training — the lazy sparse-update contract,
    now across the dp exchange."""
    from paddle_tpu.parallel import collectives

    seen_shapes = []
    real = collectives.quantized_all_reduce_local

    def spy(g, *a, **kw):
        seen_shapes.append(tuple(g.shape))
        return real(g, *a, **kw)

    monkeypatch.setattr(collectives, "quantized_all_reduce_local", spy)
    batches = _sparse_batches()
    int8, scope = _run(GradSyncConfig("int8", min_quant_numel=1),
                       {"dp": N_DEV}, batches=batches,
                       build=_build_sparse)
    assert np.isfinite(int8).all() and int8[-1] < int8[0]
    # the (V, D) table gradient must never be densified into the
    # quantized path...
    assert (V, D) not in seen_shapes, seen_shapes
    # ...while the dense fc weights DO go through it
    assert any(len(s) == 2 and s[0] * s[1] >= 256 for s in seen_shapes), \
        seen_shapes
    # untouched rows: ids only ever hit [0, V/2)
    table = np.asarray(scope.find_var("tbl"))
    np.testing.assert_array_equal(
        table[V // 2:], np.full((V - V // 2, D), 0.05, np.float32))
    assert not np.allclose(table[:V // 2], 0.05)

    # and the sparse trajectory stays within the documented tolerance
    # of the bf16 exchange (same sparse handling both sides)
    bf16, _ = _run("bf16", {"dp": N_DEV}, batches=batches,
                   build=_build_sparse)
    rel = np.abs(int8 - bf16) / np.maximum(np.abs(bf16), 1e-6)
    assert rel.max() < 1e-2, rel


# -- designed errors -------------------------------------------------------

def test_grad_sync_partial_batch_falls_back_exact():
    """A final batch that does not divide dp must TRAIN (replicated
    feeds, exact grads — the feed_spec_for replicate-on-indivisible
    rule), not crash the epoch tail.  Found by driving the surface."""
    rng = np.random.RandomState(3)
    batches = _batches(3) + [
        {"x": rng.randn(13, 32).astype(np.float32),
         "y": rng.randn(13, 1).astype(np.float32)}]
    int8, _ = _run(GradSyncConfig("int8"), {"dp": N_DEV},
                   batches=batches)
    assert np.isfinite(int8).all() and len(int8) == 4


def test_grad_sync_rejects_params_sharded_over_data_axis():
    """ISSUE 13 moved the composition line: dp×mp / dp×fsdp meshes now
    TRAIN under explicit grad sync (tests/test_hybrid_parallel.py); the
    one remaining designed error is ZeRO-3-style param sharding over a
    DATA axis — the replicated param entry would silently all-gather
    the model every step."""

    def run_zero3():
        main, startup = fluid.Program(), fluid.Program()
        scope = fluid.Scope()
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), fluid.unique_name.guard():
            loss = _build_mlp()
            exe = fluid.Executor()
            exe.run(startup)
            bs = fluid.BuildStrategy()
            bs.grad_sync = "int8"
            # params sharded over the batch axis (the Reduce strategy)
            bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
            fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, build_strategy=bs,
                mesh=make_mesh({"dp": N_DEV}))
            exe.run(main, feed=_batches(1)[0], fetch_list=[loss])

    with pytest.raises(ValueError, match="sharded over the data ax"):
        run_zero3()


def test_grad_sync_rejects_gradient_accumulation():
    with pytest.raises(ValueError, match="accumulation"):
        _run("int8", {"dp": N_DEV}, accumulation_steps=2)


def test_grad_sync_config_normalize():
    assert GradSyncConfig.normalize(None) is None
    cfg = GradSyncConfig.normalize("int8")
    assert cfg.mode == "int8" and cfg.block_size == 256
    assert GradSyncConfig.normalize(cfg) is cfg
    with pytest.raises(ValueError, match="not in"):
        GradSyncConfig.normalize("fp4")


# -- feed sharding rule ----------------------------------------------------

def test_feed_spec_for_data_axis():
    mesh = make_mesh({"dp": N_DEV})
    rules = ShardingRules()
    assert rules.feed_spec_for("x", (64, 32), mesh) == ("dp", None)
    # non-divisible batch replicates (final partial batch stays correct)
    assert rules.feed_spec_for("x", (3, 32), mesh) == (None, None)
    assert rules.feed_spec_for("s", (), mesh) == ()
    # an explicit rule wins over the data-axis default
    rules = ShardingRules(rules=[("special", (None, "dp"))])
    assert rules.feed_spec_for("special_in", (64, 32), mesh) == \
        (None, "dp")


def test_feed_spec_for_mesh_without_batch_axis():
    mesh = make_mesh({"sp": N_DEV})
    assert ShardingRules().feed_spec_for("x", (64, 32), mesh) == \
        (None, None)


# -- Trainer surface -------------------------------------------------------

def test_trainer_trains_on_dp_mesh_with_int8_sync():
    from paddle_tpu.contrib import Trainer

    def train_func():
        x = layers.data(name="x", shape=[16], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(layers.fc(x, size=64, act="relu"), size=1)
        return layers.mean(layers.square_error_cost(pred, y))

    bs = fluid.BuildStrategy()
    bs.grad_sync = "int8"
    t = Trainer(train_func,
                lambda: fluid.optimizer.SGD(learning_rate=0.05),
                mesh=make_mesh({"dp": N_DEV}), build_strategy=bs)
    assert t.train_program._compiled_wrapper is not None
    assert t.train_program._grad_sync.mode == "int8"

    rng = np.random.RandomState(0)
    losses = []

    def reader():
        for _ in range(4):
            yield {"x": rng.rand(32, 16).astype(np.float32),
                   "y": rng.rand(32, 1).astype(np.float32)}

    t.train(num_epochs=1, reader=reader,
            event_handler=lambda e: losses.append(
                float(np.asarray(e.metrics[0]).reshape(-1)[0]))
            if hasattr(e, "metrics") else None)
    t.stop()
    assert len(losses) == 4 and np.isfinite(losses).all()
