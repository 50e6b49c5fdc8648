"""Cross-platform TPU lowering of the Pallas kernels.

The CPU test suite exercises these kernels through the Pallas
INTERPRETER, which proves numerics but not that the kernel IR lowers
for the real TPU target (r4 finding: interpreter != Mosaic).
jax.export with platforms=["tpu"] runs the actual Pallas->Mosaic
lowering rules on any host, so block-spec/primitive errors surface
here instead of on the first chip contact.  (The Mosaic->LLO compile
itself still happens on hardware — this pins everything before it.)
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.export  # a submodule: not auto-imported
import jax.numpy as jnp

from paddle_tpu.ops.pallas import force_mosaic_lowering


def _export_tpu(fn, *args):
    """Export for the TPU target with the interpret gate overridden —
    otherwise the CPU host would serialize the INTERPRETER path and
    the check would be vacuous."""

    with force_mosaic_lowering():
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    # prove the Mosaic custom call is actually in the artifact
    mlir = exp.mlir_module()
    assert "tpu_custom_call" in mlir, \
        "export did not contain the Mosaic kernel (interpreter path?)"
    return exp


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(2, 4, 256, 64), jnp.float32)
    return mk(), mk(), mk()


def test_flash_attention_fwd_lowers_for_tpu(qkv):
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    q, k, v = qkv
    exp = _export_tpu(
        lambda q, k, v: pallas_flash_attention(q, k, v, None, 0.125,
                                               True), q, k, v)
    assert len(exp.mlir_module_serialized) > 0
    assert "tpu" in exp.platforms


def test_flash_attention_bwd_lowers_for_tpu(qkv):
    from paddle_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    q, k, v = qkv

    def loss(q, k, v):
        return jnp.sum(
            pallas_flash_attention(q, k, v, None, 0.125, True) ** 2)

    exp = _export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert len(exp.mlir_module_serialized) > 0


def test_vocab_ce_fwd_and_bwd_lower_for_tpu():
    from paddle_tpu.ops.pallas.vocab_ce import fused_vocab_ce

    rng = np.random.RandomState(1)
    h = jnp.asarray(rng.randn(8, 128, 256), jnp.float32)
    w = jnp.asarray(rng.randn(256, 4096) * 0.02, jnp.float32)
    lbl = jnp.asarray(rng.randint(0, 4096, (8, 128)), jnp.int32)

    def loss(h, w):
        return jnp.sum(fused_vocab_ce(h, w, lbl, 0.1, 1024, 2048))

    assert len(_export_tpu(loss, h, w).mlir_module_serialized) > 0
    assert len(_export_tpu(jax.grad(loss, argnums=(0, 1)), h,
                           w).mlir_module_serialized) > 0


def test_ring_attention_pallas_lowers_for_tpu():
    """Ring attention with the Pallas chunk kernel (SMEM offset
    scalars) inside shard_map over an sp mesh: fwd+bwd lower for the
    TPU target."""
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.ring_attention import ring_attention

    mesh = make_mesh({"sp": 8})
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(2, 2, 8 * 128, 64), jnp.float32)
    q, k, v = mk(), mk(), mk()

    def sp_loss(q, k, v):
        return jnp.mean(ring_attention(q, k, v, mesh, axis="sp",
                                       causal=True,
                                       use_pallas=True) ** 2)

    _export_tpu(jax.grad(sp_loss, argnums=(0, 1, 2)), q, k, v)


def test_full_longctx_train_step_lowers_for_tpu():
    """The COMPLETE fluid training step with every Pallas feature
    active — flash self+cross attention, fused vocab-CE, per-layer
    recompute, Adam — lowers for the TPU target (the longctx bench
    configuration's program shape)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import (RNG_STATE_VAR,
                                          interpret_program)
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        model = transformer.build_model(
            src_vocab_size=512, trg_vocab_size=512, max_length=128,
            n_layer=2, n_head=2, d_model=128, d_inner_hid=256,
            dropout=0.1, with_optimizer=True, use_flash=True,
            use_fused_ce=True, flash_pallas=True, recompute=True,
            flash_cross=True)
        exe = fluid.Executor()
        exe.run(startup)

    loss_name = model["loss"].name
    state = {k: v for k, v in scope.vars.items() if v is not None}
    batch = transformer.make_fake_batch(2, max_length=128,
                                        src_vocab=512, trg_vocab=512)
    feeds = {k: jnp.asarray(v) for k, v in batch.items()}

    def step(st, feeds):
        rng = st[RNG_STATE_VAR]
        env = {k: v for k, v in st.items() if k != RNG_STATE_VAR}
        env.update(feeds)
        env = interpret_program(main, env, rng,
                                fetch_names=(loss_name,))
        return env[loss_name]

    exp = _export_tpu(step, state, feeds)
    # flash fwd+bwd (self + cross, enc + dec) and vocab-CE fwd+bwd all
    # reach Mosaic
    assert exp.mlir_module().count("tpu_custom_call") >= 5


def test_paged_attention_lowers_for_tpu():
    """The ragged paged-attention decode kernel (ISSUE 12) lowers to
    Mosaic for the TPU target — scalar-prefetched page-table block
    index maps included — and its module carries ZERO
    stablehlo.transpose (the head-major from-birth boundary proof,
    chip-free)."""
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention

    s, h, d, p, page, maxp = 8, 4, 64, 32, 16, 8
    q = jnp.zeros((s, h * d), jnp.float32)
    kc = jnp.zeros((p, page, h * d), jnp.bfloat16)
    pt = jnp.zeros((s, maxp), jnp.int32)
    ln = jnp.ones((s,), jnp.int32)
    exp = _export_tpu(
        lambda q, kc, vc, pt, ln: ragged_paged_attention(
            q, kc, vc, pt, ln, n_head=h), q, kc, kc, pt, ln)
    mlir = exp.mlir_module()
    assert "stablehlo.transpose" not in mlir, \
        "transpose at the paged-attention kernel boundary"


def test_paged_attention_int8_lowers_for_tpu():
    """The int8-pool variant (per-row scale sidecars) also lowers."""
    from paddle_tpu.ops.pallas.paged_attention import \
        ragged_paged_attention

    s, h, d, p, page, maxp = 4, 2, 64, 16, 16, 4
    q = jnp.zeros((s, h * d), jnp.float32)
    kc = jnp.zeros((p, page, h * d), jnp.int8)
    sc = jnp.ones((p, page, 1), jnp.float32)
    pt = jnp.zeros((s, maxp), jnp.int32)
    ln = jnp.ones((s,), jnp.int32)
    exp = _export_tpu(
        lambda q, kc, vc, ks, vs, pt, ln: ragged_paged_attention(
            q, kc, vc, pt, ln, n_head=h, k_scales=ks, v_scales=vs),
        q, kc, kc, sc, sc, pt, ln)
    assert "stablehlo.transpose" not in exp.mlir_module()


def test_fused_lstm_fwd_lowers_for_tpu():
    from paddle_tpu.ops.pallas.recurrence import fused_lstm

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 16, 4 * 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 4 * 128), jnp.float32)
    sl = jnp.asarray(np.full(8, 16, np.int32))
    exp = _export_tpu(
        lambda x, w, sl: fused_lstm(x, w, seq_len=sl)[0], x, w, sl)
    assert len(exp.mlir_module_serialized) > 0


def test_fused_lstm_bwd_lowers_for_tpu():
    from paddle_tpu.ops.pallas.recurrence import fused_lstm

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 16, 4 * 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 4 * 128), jnp.float32)

    def loss(x, w):
        hs, cs, hl, cl = fused_lstm(x, w, is_reverse=True)
        return hs.sum() + cs.sum()

    exp = _export_tpu(
        lambda x, w: jax.grad(loss, argnums=(0, 1))(x, w), x, w)
    # fwd kernel (residual recompute path) + bwd kernel both reach
    # Mosaic
    assert exp.mlir_module().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("p", [0.1, 0.5])
@pytest.mark.parametrize("shape", [(64, 256, 512), (64, 8, 256, 256)],
                         ids=["residual", "attention_weights"])
def test_dropout_mask_lowers_for_tpu(shape, p):
    """The keep-mask kernel at the Transformer step's two mask shapes,
    through the `dropout` op itself: under the Mosaic gate the op draws
    its mask with the kernel and the export holds no threefry."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    def op(key, x):
        return get_op_impl("dropout")(
            OpContext(key, 3), {"X": [x]},
            {"dropout_prob": p,
             "dropout_implementation": "upscale_in_train"})["Out"][0]

    exp = _export_tpu(op, jax.random.PRNGKey(0),
                      jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    mlir = exp.mlir_module()
    # the op's own scalar fold_in is threefry's one call; a sampler
    # would add `_bernoulli` / `_uniform`
    assert "_bernoulli" not in mlir and "_uniform" not in mlir
    assert exp.out_avals[0].shape == shape
