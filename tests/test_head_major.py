"""Head-major attention layouts end-to-end (ISSUE 8).

The contract under test: with head_major=True the transformer keeps
every attention activation in the flash kernels' head-major
head-grouped (N, T, H*D) convention from the attn_qkv projections
through flash/base attention into attn_out — numerics identical to the
baseline (N, H, T, D) round-trip, ZERO transpose ops in the program,
zero stablehlo.transpose in the TPU-lowered kernel module, and the
NAMED-layer mp sharding (ShardingRules regexes, one allreduce per
block) byte-for-byte unchanged.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers  # noqa: F401  (program-building convention)


def _to_grouped(x4):
    """(N, H, T, D) -> the head-grouped (N, T, H*D) contract."""
    n, h, t, d = x4.shape
    return jnp.moveaxis(x4, 1, 2).reshape(n, t, h * d)


# -- kernel-level parity ----------------------------------------------------

@pytest.mark.parametrize("causal,with_bias",
                         [(False, False), (True, False), (False, True),
                          (True, True)])
def test_pallas_nthd_matches_nhtd_fwd(causal, with_bias):
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(0)
    n, h, t, d = 2, 4, 96, 16
    mk = lambda: jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.4
    q, k, v = mk(), mk(), mk()
    bias = None
    if with_bias:
        b = np.zeros((n, 1, 1, t), np.float32)
        b[:, :, :, t - 17:] = -1e9
        bias = jnp.asarray(b)
    want = fa.pallas_flash_attention(q, k, v, bias=bias, causal=causal,
                                     block_q=32, block_k=64)
    got = fa.pallas_flash_attention(
        _to_grouped(q), _to_grouped(k), _to_grouped(v), bias=bias,
        causal=causal, block_q=32, block_k=64, layout="nthd", n_head=h)
    np.testing.assert_allclose(np.asarray(_to_grouped(want)),
                               np.asarray(got), rtol=2e-3, atol=2e-3)


def test_pallas_nthd_grad_matches_nhtd():
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(1)
    n, h, t, d = 2, 4, 96, 16
    mk = lambda: jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.3
    q, k, v = mk(), mk(), mk()
    b = np.zeros((n, 1, 1, t), np.float32)
    b[:, :, :, t - 9:] = -1e9
    bias = jnp.asarray(b)

    def loss4(q, k, v, b):
        o = fa.pallas_flash_attention(q, k, v, bias=b, causal=True,
                                      block_q=32, block_k=64)
        return jnp.sum(o ** 2)

    def lossg(q, k, v, b):
        o = fa.pallas_flash_attention(q, k, v, bias=b, causal=True,
                                      block_q=32, block_k=64,
                                      layout="nthd", n_head=h)
        return jnp.sum(o ** 2)

    g4 = jax.grad(loss4, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gg = jax.grad(lossg, argnums=(0, 1, 2, 3))(
        _to_grouped(q), _to_grouped(k), _to_grouped(v), bias)
    for name, a, g in zip("qkv", g4[:3], gg[:3]):
        np.testing.assert_allclose(np.asarray(_to_grouped(a)),
                                   np.asarray(g), rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")
    # bias grad sums over heads OUTSIDE the kernel in both layouts
    np.testing.assert_allclose(np.asarray(g4[3]), np.asarray(gg[3]),
                               rtol=5e-3, atol=5e-3)


def test_nthd_return_lse_matches():
    """The ring-attention statistic: nthd lse rides (N, T, H) so it
    broadcasts against the grouped output; values match the (N, H, T)
    form transposed."""
    import paddle_tpu.ops.pallas.flash_attention as fa

    rng = np.random.RandomState(2)
    n, h, t, d = 2, 2, 64, 16
    mk = lambda: jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.4
    q, k, v = mk(), mk(), mk()
    _, lse4 = fa.pallas_flash_attention(q, k, v, causal=True,
                                        block_q=32, block_k=32,
                                        return_lse=True)
    _, lseg = fa.pallas_flash_attention(
        _to_grouped(q), _to_grouped(k), _to_grouped(v), causal=True,
        block_q=32, block_k=32, return_lse=True, layout="nthd",
        n_head=h)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(lse4, 1, 2)),
                               np.asarray(lseg), rtol=2e-3, atol=2e-3)


def test_nthd_validates_n_head():
    import paddle_tpu.ops.pallas.flash_attention as fa

    x = jnp.zeros((1, 8, 32), jnp.float32)
    with pytest.raises(ValueError, match="n_head"):
        fa.pallas_flash_attention(x, x, x, layout="nthd")
    with pytest.raises(ValueError, match="divisible"):
        fa.pallas_flash_attention(x, x, x, layout="nthd", n_head=5)


# -- ring / ulysses ---------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_nthd_matches_nhtd(causal):
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.ring_attention import ring_attention

    rng = np.random.RandomState(3)
    n, h, t, d = 2, 8, 64, 16
    mk = lambda: jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.5
    q, k, v = mk(), mk(), mk()
    mesh = make_mesh({"sp": 8})
    want = ring_attention(q, k, v, mesh, axis="sp", causal=causal)
    got = ring_attention(_to_grouped(q), _to_grouped(k), _to_grouped(v),
                         mesh, axis="sp", causal=causal, layout="nthd",
                         n_head=h)
    np.testing.assert_allclose(np.asarray(_to_grouped(want)),
                               np.asarray(got), rtol=2e-4, atol=2e-5)


def test_ulysses_nthd_matches_nhtd():
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.ring_attention import ulysses_attention

    rng = np.random.RandomState(4)
    n, h, t, d = 2, 8, 64, 16
    mk = lambda: jnp.asarray(rng.randn(n, h, t, d), jnp.float32) * 0.5
    q, k, v = mk(), mk(), mk()
    mesh = make_mesh({"sp": 8})
    want = ulysses_attention(q, k, v, mesh, axis="sp", causal=True)
    got = ulysses_attention(_to_grouped(q), _to_grouped(k),
                            _to_grouped(v), mesh, axis="sp", causal=True,
                            layout="nthd", n_head=h)
    np.testing.assert_allclose(np.asarray(_to_grouped(want)),
                               np.asarray(got), rtol=2e-4, atol=2e-5)


# -- model-level parity -----------------------------------------------------

def _run_transformer(head_major, flash_pallas=None, fused_qkv=False,
                     use_flash=True, collect_program=False):
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    scope = fluid.Scope()
    losses = []
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = transformer.build_model(
            src_vocab_size=64, trg_vocab_size=64, max_length=8,
            n_layer=1, n_head=2, d_model=16, d_inner_hid=32,
            dropout=0.0, use_flash=use_flash, flash_pallas=flash_pallas,
            fused_qkv=fused_qkv, head_major=head_major)
        exe = fluid.Executor()
        exe.run(startup)
        feed = transformer.make_fake_batch(4, 8, 60, 60)
        for _ in range(3):
            lv, = exe.run(main, feed=feed, fetch_list=[m["loss"]])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    if collect_program:
        return losses, main
    return losses


def test_transformer_head_major_matches_baseline():
    """XLA flash path: the head-major program is the SAME math reordered
    — trajectories match the baseline layout tightly."""
    base, base_prog = _run_transformer(False, collect_program=True)
    hm, hm_prog = _run_transformer(True, collect_program=True)
    assert hm[-1] < hm[0]
    np.testing.assert_allclose(hm, base, rtol=2e-4, atol=1e-5)
    # the tentpole structural claim: the baseline layout round-trips
    # through transpose at every kernel boundary; head-major has NONE
    n_base = sum(1 for op in base_prog.global_block().ops
                 if op.type == "transpose")
    n_hm = sum(1 for op in hm_prog.global_block().ops
               if op.type == "transpose")
    assert n_base > 0 and n_hm == 0, (n_base, n_hm)


def test_transformer_head_major_pallas_matches_baseline():
    base = _run_transformer(False)
    hm = _run_transformer(True, flash_pallas=True)
    np.testing.assert_allclose(hm, base, rtol=2e-3, atol=2e-4)


def test_transformer_head_major_fused_qkv_matches():
    base = _run_transformer(False, fused_qkv=True)
    hm = _run_transformer(True, fused_qkv=True)
    np.testing.assert_allclose(hm, base, rtol=2e-4, atol=1e-5)


def test_head_major_requires_flash():
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        with pytest.raises(ValueError, match="use_flash"):
            transformer.build_model(
                src_vocab_size=64, trg_vocab_size=64, max_length=8,
                n_layer=1, n_head=2, d_model=16, d_inner_hid=32,
                use_flash=False, head_major=True)


def test_bert_head_major_matches_baseline():
    from paddle_tpu.models import bert

    def run(head_major):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 5
        scope = fluid.Scope()
        losses = []
        with fluid.program_guard(main, startup), \
                fluid.scope_guard(scope), fluid.unique_name.guard():
            m = bert.build_model(vocab_size=64, max_len=16, n_layer=1,
                                 n_head=2, d_model=16, d_inner=32,
                                 max_predictions=4, dropout=0.0,
                                 use_flash=True, head_major=head_major)
            exe = fluid.Executor()
            exe.run(startup)
            feed = bert.make_fake_batch(4, 16, 64, 4)
            for _ in range(3):
                lv, = exe.run(main, feed=feed, fetch_list=[m["loss"]])
                losses.append(float(np.asarray(lv).reshape(-1)[0]))
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=2e-4,
                               atol=1e-5)


# -- sharding: named layers / mp pairing survive ----------------------------

def _mp_run(head_major):
    """Tiny transformer under a dp2 x mp2 mesh with the Megatron rules:
    (losses, {persistable name -> spec}, compiled HLO text)."""
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.strategies import megatron_transformer_rules

    mesh = make_mesh({"dp": 2, "mp": 2})
    rules = megatron_transformer_rules()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 13
    scope = fluid.Scope()
    losses = []
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = transformer.build_model(
            src_vocab_size=64, trg_vocab_size=64, max_length=8,
            n_layer=1, n_head=2, d_model=16, d_inner_hid=32,
            dropout=0.0, use_flash=True, head_major=head_major)
        exe = fluid.Executor()
        exe.run(startup)
        bs = fluid.BuildStrategy()
        bs.sharding_rules = rules
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=m["loss"].name, build_strategy=bs, mesh=mesh)
        feed = transformer.make_fake_batch(4, 8, 60, 60)
        for _ in range(3):
            lv, = exe.run(prog, feed=feed, fetch_list=[m["loss"]])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
        specs = {
            v.name: rules.spec_for(v.name, v.shape, mesh)
            for v in main.list_vars()
            if v.persistable and ("attn_" in v.name or "ffn_" in v.name)
        }
        hlo = prog.compiled_hlo_text(feed, [m["loss"].name], scope)
    return losses, specs, hlo


def test_head_major_mp_sharding_unchanged():
    """The head-major refactor must not move a single PartitionSpec:
    the NAMED layers still match the ShardingRules regexes with the
    same specs, the Megatron row/col pairing's one-allreduce-per-block
    property survives (identical all-reduce count in the compiled
    HLO), and the sharded trajectory still matches the baseline
    layout's."""
    base_losses, base_specs, base_hlo = _mp_run(False)
    hm_losses, hm_specs, hm_hlo = _mp_run(True)
    np.testing.assert_allclose(hm_losses, base_losses, rtol=2e-4,
                               atol=1e-5)

    assert base_specs == hm_specs, (
        "PartitionSpecs moved under head_major:\n"
        f"base={base_specs}\nhm={hm_specs}")
    # the column/row pairing itself (regex sanity, not just equality):
    qkv = {n: s for n, s in hm_specs.items() if "attn_qkv" in n}
    out = {n: s for n, s in hm_specs.items() if "attn_out" in n}
    assert qkv and all(s == (None, "mp") for n, s in qkv.items()
                       if n.endswith(".w_0")), qkv
    assert out and all(s == ("mp", None) for n, s in out.items()
                       if n.endswith(".w_0")), out

    n_ar_base = len(re.findall(r"all-reduce", base_hlo))
    n_ar_hm = len(re.findall(r"all-reduce", hm_hlo))
    assert n_ar_hm == n_ar_base, (
        f"allreduce count changed under head_major: "
        f"{n_ar_base} -> {n_ar_hm}")


# -- the boundary proof -----------------------------------------------------

def test_nthd_tpu_export_has_zero_transposes():
    """Chip-free HLO-level proof: the head-major flash fwd+bwd lowered
    for the REAL TPU target (Mosaic custom calls, not the interpreter)
    contains zero stablehlo.transpose — the operands reach the kernels
    and the gradients leave them in the model's layout."""
    import paddle_tpu.ops.pallas.flash_attention as fa
    import jax.export

    from paddle_tpu.ops.pallas import force_mosaic_lowering

    n, h, t, d = 1, 2, 256, 128
    q = jnp.zeros((n, t, h * d), jnp.float32)
    bias = jnp.zeros((n, 1, 1, t), jnp.float32)

    def step(q, k, v, b):
        def loss(q, k, v, b):
            o = fa.pallas_flash_attention(q, k, v, bias=b, causal=True,
                                          layout="nthd", n_head=h)
            return jnp.sum(o ** 2)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(q, k, v, b)

    with force_mosaic_lowering():
        exp = jax.export.export(jax.jit(step), platforms=["tpu"])(
            q, q, q, bias)
    mlir = exp.mlir_module()
    assert mlir.count("tpu_custom_call") >= 3, \
        "expected fwd+dkv+dq Mosaic custom calls"
    assert "stablehlo.transpose" not in mlir, \
        "head-major lowering emitted a transpose at a kernel boundary"


# the smallest head-major program, and the long-context stack at a
# CPU's size: Pallas flash for self AND cross attention, the fused
# vocabulary cross-entropy, dropout on
@pytest.mark.parametrize("stack", [
    dict(max_length=8, n_layer=1, n_head=2, d_model=16, d_inner_hid=32,
         dropout=0.0),
    dict(max_length=128, n_layer=1, n_head=4, d_model=64, d_inner_hid=128,
         dropout=0.1, flash_cross=True, use_fused_ce=True)],
    ids=["self_attention", "longctx_stack"])
def test_flash_boundary_layout_audit(stack):
    """The program built head-major holds NO `transpose` fluid op (the
    baseline layout has one at every kernel boundary), and the
    observe.cost boundary audit over its compiled step reports zero
    copy/transpose neighbors at flash custom calls (vacuously on CPU
    where Pallas interprets — the audit is the on-chip CI check — but
    the plumbing is exercised end-to-end), and layout_byte_share
    yields a sane fraction."""
    from paddle_tpu.models import transformer
    from paddle_tpu.observe import cost as obs_cost

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    scope = fluid.Scope()
    t = stack["max_length"]
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = transformer.build_model(
            src_vocab_size=64, trg_vocab_size=64, use_flash=True,
            flash_pallas=True, head_major=True, **stack)
        assert not [op for op in main.global_block().ops
                    if op.type == "transpose"]
        exe = fluid.Executor()
        exe.run(startup)
        feed = {k: jnp.asarray(v) for k, v in
                transformer.make_fake_batch(2, t, 60, 60).items()}
        compiled = exe.compiled_step(main, feed=feed,
                                     fetch_list=[m["loss"]])
        proto = obs_cost.compiled_hlo_proto(compiled)
    assert obs_cost.flash_boundary_layout(proto) == []
    share = obs_cost.layout_byte_share(proto)
    assert 0.0 <= share < 1.0
    # no instruction in the whole entry computation is attributed to a
    # `transpose` fluid op — the op type does not exist in the program
    assert obs_cost.copyish_instructions(proto,
                                         op_types={"transpose"}) == []
