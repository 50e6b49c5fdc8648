"""The ops a modern decoder block adds (`ops/decoder.py`: rms_norm,
rope, swiglu; `ops/moe_dropless.py`: the dropless routed-expert op),
decoupled weight decay on `adam`, and what `observe/cost.py` says of a
grouped matmul: each alone, against a form written another way.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observe import cost, trace

from op_test import run_op


def R(seed):
    return np.random.default_rng(seed)


# --------------------------------------------------------------------------
# rms_norm, rope, swiglu
# --------------------------------------------------------------------------

def test_rms_norm_matches_its_definition_and_keeps_the_dtype():
    x = R(0).normal(size=(2, 5, 16)).astype(np.float32) * 3.0
    w = R(1).uniform(0.5, 1.5, size=16).astype(np.float32)
    got = run_op("rms_norm", {"X": x, "Scale": w},
                 {"epsilon": 1e-5, "begin_norm_axis": 2}, "Y")
    want = x / np.sqrt((x.astype(np.float64) ** 2).mean(-1, keepdims=True)
                       + 1e-5) * w
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # rows of unit mean square before the scale
    np.testing.assert_allclose(((got / w) ** 2).mean(-1), 1.0, rtol=1e-4)
    half = run_op("rms_norm", {"X": x.astype(jnp.bfloat16), "Scale": w},
                  {"begin_norm_axis": -1}, "Y")
    assert half.dtype == jnp.bfloat16
    np.testing.assert_allclose(half.astype(np.float32), want, rtol=2e-2,
                               atol=2e-2)


@pytest.mark.parametrize("offset", [None, 7])
def test_rope_is_a_complex_rotation_of_each_half_pair(offset):
    n, t, heads, d, theta = 2, 6, 3, 8, 10000.0
    x = R(2).normal(size=(n, t, heads * d)).astype(np.float32)
    ins = {"X": x}
    if offset is not None:
        ins["Offset"] = np.array([offset], np.int32)
    got = run_op("rope", ins, {"n_head": heads, "theta": theta})
    # (x[i] + 1j x[i + d/2]) * exp(1j * pos * theta^(-2i/d))
    x4 = x.reshape(n, t, heads, d).astype(np.float64)
    z = x4[..., :d // 2] + 1j * x4[..., d // 2:]
    pos = np.arange(t) + (offset or 0)
    ang = pos[:, None] * theta ** (-np.arange(0, d, 2) / d)[None, :]
    z = z * np.exp(1j * ang)[None, :, None, :]
    want = np.concatenate([z.real, z.imag], -1).reshape(n, t, heads * d)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a rotation: every head keeps its norm; position 0 is unchanged
    np.testing.assert_allclose(
        np.linalg.norm(got.reshape(n, t, heads, d), axis=-1),
        np.linalg.norm(x4, axis=-1), rtol=1e-5)
    if offset is None:
        np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)


def test_rope_scores_depend_on_the_distance_only():
    heads, d = 1, 16
    q = np.tile(R(3).normal(size=(1, 1, d)), (1, 12, 1)).astype(np.float32)
    k = np.tile(R(4).normal(size=(1, 1, d)), (1, 12, 1)).astype(np.float32)
    rq = run_op("rope", {"X": q}, {"n_head": heads})[0]
    rk = run_op("rope", {"X": k}, {"n_head": heads})[0]
    scores = rq @ rk.T
    for dist in (1, 4):
        diag = np.diagonal(scores, -dist)
        np.testing.assert_allclose(diag, diag[0], rtol=1e-4, atol=1e-4)


PUBLISHED_YARN = dict(rope_type="yarn", rope_theta=500000, factor=16,
                      original_max_position_embeddings=8192, beta_fast=32,
                      beta_slow=1, attention_factor=1.2772588722239782)


def test_rope_with_yarn_attributes_is_the_float64_rotation_at_16383():
    """`inv_freq` and `attention_factor` arrive as the op's attributes
    (host constants).  At the last of 16384 positions the rotation is
    numpy float64's of the angle the op holds, float32(pos * inv_freq),
    to 1e-6 of the scale: the frequencies are exact, which the chip's
    own float32 `pow` would not give (PERF.md, PR 26).  Against the
    angle in float64 it is good to 2e-3: a float32 angle of 16383 rad
    is rounded to 1e-3, under the default frequencies alike (the
    reference holds the same float32 angle)."""
    from paddle_tpu.ops.decoder import rope_frequencies

    heads, d, last = 2, 128, 16383
    inv_freq, factor = rope_frequencies(d, **PUBLISHED_YARN)
    assert inv_freq.dtype == np.float64 and inv_freq.shape == (64,)
    x = R(4).normal(size=(1, 1, heads * d)).astype(np.float32)
    got = run_op("rope", {"X": x, "Offset": np.array([last], np.int32)},
                 {"n_head": heads, "theta": 5e5, "inv_freq": list(inv_freq),
                  "attention_factor": factor})
    # the frequencies as float32 holds them: a checkpoint's buffer
    held = inv_freq.astype(np.float32).astype(np.float64)
    x4 = x.reshape(1, 1, heads, d).astype(np.float64)
    def rotated(angle):
        z = (x4[..., :d // 2] + 1j * x4[..., d // 2:]) * factor \
            * np.exp(1j * angle)
        return np.concatenate([z.real, z.imag], -1).reshape(1, 1, heads * d)

    angle32 = (np.float32(last) * inv_freq.astype(np.float32)).astype(
        np.float64)
    scale = np.abs(x).max() * factor
    np.testing.assert_allclose(got, rotated(angle32), rtol=0,
                               atol=1e-6 * scale)
    np.testing.assert_allclose(got, rotated(last * held), rtol=0,
                               atol=2e-3 * scale)
    # ... and it is YaRN's: the slow frequencies turn 16 x less
    plain = run_op("rope", {"X": x, "Offset": np.array([last], np.int32)},
                   {"n_head": heads, "theta": 5e5})
    assert np.abs(got / factor - plain)[..., 40:64].max() > 1e-2
    np.testing.assert_allclose((got / factor)[..., :18], plain[..., :18],
                               atol=3e-3)


def test_rope_parameters_flat_or_a_layer_type_and_what_still_raises():
    """One flat group (every layer's), or one a layer type; `yarn` is
    built, any other type still raises; a `sliding_attention` layer
    without `sliding_window` raises; `head_dim` sizes the projections."""
    from paddle_tpu.models import decoder

    base = dict(hidden_size=48, num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=64, num_experts=0,
                num_experts_per_tok=0, norm_topk_prob=False,
                num_dense_layers=2, vocab_size=32, max_length=16,
                rms_norm_eps=1e-6, qk_norm="head")
    small_yarn = dict(PUBLISHED_YARN, original_max_position_embeddings=8)

    def build(**over):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()), \
                fluid.unique_name.guard():
            decoder.decoder(**dict(base, **over))
        return main

    def ropes(main):
        return [op.attrs for op in main.global_block().ops
                if op.type == "rope"]

    flat = ropes(build(rope_parameters={"rope_theta": 1e4,
                                        "rope_type": "default"}))
    assert len(flat) == 4 and all("inv_freq" not in a for a in flat)
    scaled = ropes(build(rope_parameters=small_yarn, head_dim=16))
    assert all(len(a["inv_freq"]) == 8 for a in scaled)
    assert all(a["attention_factor"] == PUBLISHED_YARN["attention_factor"]
               for a in scaled)
    mixed = build(
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=4, head_dim=16,
        rope_parameters={"full_attention": small_yarn,
                         "sliding_attention": {"rope_type": "default",
                                               "rope_theta": 1e4}})
    attrs = ropes(mixed)
    assert ["inv_freq" in a for a in attrs] == [False, False, True, True]
    windows = [op.attrs.get("window") for op in mixed.global_block().ops
               if op.type == "flash_attention"]
    assert windows == [4, None]
    # head_dim 16 x 4 heads = 64 beside hidden_size 48
    shapes = sorted(tuple(p.shape) for p in mixed.all_parameters()
                    if "attn" in p.name)
    assert shapes == [(48, 32)] * 4 + [(48, 64)] * 2 + [(64, 48)] * 2
    with pytest.raises(NotImplementedError, match="rope_type 'llama3'"):
        build(rope_parameters={"rope_theta": 1e4, "rope_type": "llama3"})
    with pytest.raises(NotImplementedError, match="rope_type 'linear'"):
        build(rope_parameters={
            "full_attention": {"rope_theta": 1e4, "rope_type": "linear"}})
    with pytest.raises(ValueError, match="needs sliding_window"):
        build(layer_types=["sliding_attention", "full_attention"],
              rope_theta=1e4)
    with pytest.raises(ValueError, match="rope_theta or rope_parameters"):
        build()


@pytest.mark.parametrize("qk_norm", ["head", "projection", None])
def test_a_norm_a_head_rides_in_the_rope_op(qk_norm):
    """`qk_norm` "head": q and k are ONE `rope` op each, with the norm's
    Scale (d_head,), epsilon and zero-centring in it, and the program
    holds no `rms_norm` with a `group_size`; "projection" and None keep
    `rope` bare.  Parameter names, order and shapes are what the two
    ops gave."""
    from paddle_tpu.models import decoder

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        decoder.decoder(
            hidden_size=48, num_hidden_layers=1, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=64, num_experts=0,
            num_experts_per_tok=0, norm_topk_prob=False, num_dense_layers=1,
            vocab_size=32, max_length=16, rms_norm_eps=1e-6, rope_theta=1e4,
            qk_norm=qk_norm, zero_centered_norm=qk_norm == "head")
    ops = main.global_block().ops
    ropes = [op for op in ops if op.type == "rope"]
    norms = [op for op in ops if op.type == "rms_norm"]
    assert len(ropes) == 2
    assert not any(op.attrs.get("group_size") for op in norms)
    names = [p.name for p in main.all_parameters()]
    if qk_norm == "head":
        assert len(norms) == 3              # two hidden norms, the final
        scales = [op.input("Scale")[0] for op in ropes]
        assert scales == ["rms_norm_1.w_0", "rms_norm_2.w_0"]
        for op in ropes:
            assert op.attrs["epsilon"] == 1e-6 and op.attrs["zero_centered"]
        assert [tuple(main.global_block().var(n).shape) for n in scales] \
            == [(12,), (12,)]
    else:
        assert len(norms) == (5 if qk_norm else 3)
        assert not any(op.input("Scale") for op in ropes)
    # x-norm, q projection, its norm, k projection, its norm, v
    start = names.index("rms_norm_0.w_0")
    kinds = [n.split("_")[0] for n in names[start:start + 6]]
    assert kinds == (["rms", "attn", "attn", "attn", "attn", "rms"] if qk_norm is None
                     else ["rms", "attn", "rms", "attn", "rms", "attn"])


def test_the_embedding_table_takes_a_range_of_its_own():
    """`embedding_init_range` is the table's std alone; every matrix
    keeps `initializer_range`; absent, the table has it too."""
    from paddle_tpu.models import decoder

    def stds(**over):
        startup = fluid.Program()
        with fluid.program_guard(fluid.Program(), startup), \
                fluid.unique_name.guard():
            decoder.decoder(
                hidden_size=32, num_hidden_layers=1, num_attention_heads=2,
                num_key_value_heads=2, intermediate_size=64, num_experts=0,
                num_experts_per_tok=0, norm_topk_prob=False,
                num_dense_layers=1, vocab_size=48, max_length=8,
                rms_norm_eps=1e-6, rope_theta=1e4, **over)
        return {op.output("Out")[0]: op.attrs["std"]
                for op in startup.global_block().ops
                if op.type == "gaussian_random"}

    both = stds(initializer_range=0.002, embedding_init_range=1.0)
    assert both.pop("tok_embedding.w") == 1.0
    assert set(both.values()) == {0.002} and len(both) == 8
    assert set(stds(initializer_range=0.002).values()) == {0.002}


def test_swiglu_is_silu_times_gate():
    a = R(5).normal(size=(3, 7)).astype(np.float32)
    b = R(6).normal(size=(3, 7)).astype(np.float32)
    got = run_op("swiglu", {"X": a, "Y": b})
    np.testing.assert_allclose(got, a / (1 + np.exp(-a)) * b, rtol=1e-5,
                               atol=1e-6)


def test_layers_infer_shapes_and_name_their_parameters():
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[12, 32], dtype="float32")
        y = layers.rms_norm(x)
        assert y.shape == (-1, 12, 32)
        assert layers.rope(y, n_head=4).shape == (-1, 12, 32)
        assert layers.swiglu(y, y).shape == (-1, 12, 32)
        out, aux, z, counts, experts = layers.dropless_moe(
            y, num_experts=8, d_inner=16, top_k=2)
        assert out.shape == (-1, 12, 32)
        assert (aux.shape, z.shape, counts.shape) == ((1,), (1,), (8,))
        block = fluid.default_main_program().global_block()
        names = sorted(p.name for p in block.all_parameters())
        # the prefixes the ep sharding rules and numerics groups key on
        assert names == ["moe_expert_0.w_0", "moe_expert_0.w_1",
                         "moe_expert_0.w_2", "moe_gate_0.w_0",
                         "rms_norm_0.w_0"]
        assert block.var("moe_expert_0.w_0").shape == (8, 32, 16)
        assert block.var("moe_expert_0.w_1").shape == (8, 16, 32)
        state = block.var("moe_expert_0.w_0.token_count")
        assert state.persistable and state.shape == (8,)


def test_ep_sharding_rules_still_match_the_expert_weights():
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel.strategies import megatron_transformer_rules

    mesh = make_mesh({"dp": 2, "mp": 2, "ep": 2})
    rules = megatron_transformer_rules(moe_axis="ep")
    # W1 / W3 (E, D, H): experts over ep; W1's hidden over mp.
    # W2 (E, H, D): experts over ep, hidden over mp.
    assert rules.spec_for("moe_expert_0.w_0", (8, 32, 16), mesh) == \
        ("ep", None, "mp")
    assert rules.spec_for("moe_expert_0.w_1", (8, 16, 32), mesh) == \
        ("ep", "mp", None)
    assert rules.spec_for("moe_expert_0.w_2", (8, 32, 16), mesh) == \
        ("ep", None, None)
    assert rules.spec_for("moe_gate_0.w_0", (32, 8), mesh) == (None, None)
    assert rules.spec_for("attn_qkv.w_1", (32, 32), mesh) == (None, "mp")


# --------------------------------------------------------------------------
# the dropless routed-expert op
# --------------------------------------------------------------------------

def moe_inputs(t=24, d=16, h=8, e=8, seed=10, scale=0.5):
    r = R(seed)
    return {"X": r.normal(size=(t, d)).astype(np.float32),
            "GateW": r.normal(size=(d, e)).astype(np.float32),
            "W1": r.normal(size=(e, d, h)).astype(np.float32) * scale,
            "W3": r.normal(size=(e, d, h)).astype(np.float32) * scale,
            "W2": r.normal(size=(e, h, d)).astype(np.float32) * scale}


def dense_experts(ins, k, norm=False):
    """Every expert on every token, weighted by the router where the
    expert is among the token's top k: float64 numpy, no sort."""
    x, g = ins["X"].astype(np.float64), ins["GateW"].astype(np.float64)
    logits = x @ g
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1, kind="stable")[:, :k]
    gate = np.zeros_like(p)
    np.put_along_axis(gate, top, np.take_along_axis(p, top, -1), -1)
    if norm:
        gate /= gate.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    for i in range(g.shape[1]):
        a, b = x @ ins["W1"][i], x @ ins["W3"][i]
        y += gate[:, i:i + 1] * ((a / (1 + np.exp(-a)) * b) @ ins["W2"][i])
    counts = (gate > 0).sum(0)
    aux = g.shape[1] * ((counts / (x.shape[0] * k)) * p.mean(0)).sum()
    lse = np.log(np.exp(logits).sum(-1))
    return y, aux, (lse ** 2).mean(), counts


@pytest.mark.parametrize("e,k,norm", [(8, 1, False), (8, 2, False),
                                      (8, 3, True), (64, 8, False)])
def test_dropless_moe_matches_the_dense_loop(e, k, norm):
    ins = moe_inputs(e=e, seed=11 + e + k)
    attrs = {"top_k": k, "norm_topk_prob": norm}
    y, aux, z, counts = dense_experts(ins, k, norm)
    np.testing.assert_allclose(run_op("moe_dropless", ins, attrs), y,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        run_op("moe_dropless", ins, attrs, "AuxLoss")[0], aux, rtol=1e-5)
    np.testing.assert_allclose(
        run_op("moe_dropless", ins, attrs, "ZLoss")[0], z, rtol=1e-5)
    got = run_op("moe_dropless", ins, attrs, "Counts")
    np.testing.assert_array_equal(got, counts)
    assert got.sum() == ins["X"].shape[0] * k
    experts = run_op("moe_dropless", ins, attrs, "Experts")
    assert experts.shape == (ins["X"].shape[0], k)
    assert all(len(set(row)) == k for row in experts)


def test_every_token_to_one_expert_and_none_is_dropped():
    ins = moe_inputs(e=8, seed=20)
    # a router that sends everything to expert 5 first, whatever X is
    ins["GateW"] = np.zeros_like(ins["GateW"])
    ins["X"][:, 0] = 1.0
    ins["GateW"][0, 5] = 30.0
    counts = run_op("moe_dropless", ins, {"top_k": 1}, "Counts")
    assert counts.tolist() == [0, 0, 0, 0, 0, 24, 0, 0]
    y = run_op("moe_dropless", ins, {"top_k": 1})
    x = ins["X"].astype(np.float64)
    a, b = x @ ins["W1"][5], x @ ins["W3"][5]
    want = (a / (1 + np.exp(-a)) * b) @ ins["W2"][5]      # p(5) = 1
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    assert np.abs(y).min(axis=-1).max() > 0               # every row


def test_routing_changes_values_never_shapes_or_the_compiled_step():
    from paddle_tpu.core.registry import OpContext, get_op_impl

    impl = get_op_impl("moe_dropless")
    traces = []

    @jax.jit
    def f(ins):
        traces.append(1)
        outs = impl(OpContext(jax.random.PRNGKey(0), 0),
                    {k: [v] for k, v in ins.items()}, {"top_k": 2})
        return outs["Out"][0], outs["Counts"][0]

    a = {k: jnp.asarray(v) for k, v in moe_inputs(seed=30).items()}
    b = dict(a, GateW=jnp.asarray(moe_inputs(seed=31)["GateW"]) * 5.0)
    (ya, ca), (yb, cb) = f(a), f(b)
    assert len(traces) == 1 and ya.shape == yb.shape
    assert ca.tolist() != cb.tolist() and ca.sum() == cb.sum() == 48


def test_gradients_of_the_sorted_form_match_the_dense_form():
    from paddle_tpu.core.registry import OpContext, get_op_impl
    from paddle_tpu.models import decoder_reference as ref

    ins = {k: jnp.asarray(v) for k, v in moe_inputs(seed=40).items()}
    impl = get_op_impl("moe_dropless")
    cfg = {"num_experts": 8, "num_experts_per_tok": 2,
           "norm_topk_prob": False}

    def system(ins):
        o = impl(OpContext(jax.random.PRNGKey(0), 0),
                 {k: [v] for k, v in ins.items()}, {"top_k": 2})
        return (jnp.sum(jnp.sin(o["Out"][0])) + o["AuxLoss"][0][0]
                + o["ZLoss"][0][0])

    def dense(ins):
        layer = {"router": ins["GateW"], "w1": ins["W1"], "w3": ins["W3"],
                 "w2": ins["W2"]}
        y, aux, z, _, _ = ref.experts(ins["X"], layer, cfg)
        return jnp.sum(jnp.sin(y)) + aux + z

    got, want = jax.jit(jax.grad(system))(ins), jax.jit(jax.grad(dense))(ins)
    for name in ins:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-5,
                                   atol=2e-5, err_msg=name)


def test_top_k_outside_the_experts_is_an_error():
    with pytest.raises(ValueError, match="top_k 9 outside"):
        run_op("moe_dropless", moe_inputs(), {"top_k": 9})


# --------------------------------------------------------------------------
# decoupled weight decay
# --------------------------------------------------------------------------

@pytest.mark.parametrize("decay", [0.0, 0.1])
def test_adam_with_decoupled_decay_is_a_hand_rolled_adamw(decay):
    lr, b1, b2, eps = 0.01, 0.9, 0.95, 1e-8
    w0 = R(50).normal(size=(4, 3)).astype(np.float32)
    xs = R(51).normal(size=(5, 4, 3)).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4, 3], dtype="float32",
                        append_batch_size=False)
        w = fluid.layer_helper.LayerHelper("w").create_parameter(
            fluid.ParamAttr(
                name="w",
                initializer=fluid.initializer.NumpyArrayInitializer(w0)),
            shape=[4, 3], dtype="float32")
        loss = layers.reduce_sum(layers.elementwise_mul(
            layers.elementwise_mul(w, w), x))
        fluid.optimizer.AdamOptimizer(
            lr, beta1=b1, beta2=b2, epsilon=eps,
            weight_decay=decay).minimize(loss)
        op = [o for o in main.global_block().ops if o.type == "adam"][0]
        assert op.desc.attrs["weight_decay"] == decay
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for x_t in xs:
            exe.run(main, feed={"x": x_t}, scope=scope)
        got = np.asarray(scope.find_var("w"))
    p = w0.astype(np.float64)
    m = v = np.zeros_like(p)
    for t, x_t in enumerate(xs, 1):
        g = 2 * p * x_t
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        # this repo's Adam: bias correction folded into the step size,
        # eps beside the uncorrected sqrt(v) (reference adam_op.h)
        step = lr * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        p = p - step * m / (np.sqrt(v) + eps) - lr * decay * p
    np.testing.assert_allclose(got, p, rtol=2e-5, atol=1e-6)


def test_decay_zero_leaves_the_adam_trace_as_it_was():
    from paddle_tpu.core.registry import OpContext, get_op_impl

    impl = get_op_impl("adam")
    ins = {"Param": [jnp.ones((3,))], "Grad": [jnp.ones((3,))],
           "Moment1": [jnp.zeros((3,))], "Moment2": [jnp.zeros((3,))],
           "Beta1Pow": [jnp.full((1,), 0.9)],
           "Beta2Pow": [jnp.full((1,), 0.95)],
           "LearningRate": [jnp.full((1,), 0.1)]}

    def text(attrs):
        return str(jax.make_jaxpr(
            lambda i: impl(OpContext(jax.random.PRNGKey(0), 0), i,
                           attrs))(ins))

    assert text({"weight_decay": 0.0}) == text({})
    assert text({"weight_decay": 0.1}) != text({})


# --------------------------------------------------------------------------
# observe/cost.py and observe/trace.py on a grouped matmul
# --------------------------------------------------------------------------

def test_ragged_dot_cost_counts_the_rows_not_the_experts():
    m, k, n, g = 131072, 2048, 1024, 64
    meta = [((1,), 4), ((65,), 4), ((319,), 4), ((319,), 4), ((1,), 4)]
    fwd = cost.ragged_dot_cost(
        meta + [((m, k), 2), ((g, k, n), 2)], [((m, n), 2)])
    assert fwd[0] == 2.0 * m * k * n == 549755813888.0     # not x 64
    assert fwd[1] == 2 * (m * k + g * k * n + m * n)
    dx = cost.ragged_dot_cost(
        meta + [((m, n), 2), ((g, k, n), 2)], [((m, k), 2)])
    dw = cost.ragged_dot_cost(
        meta + [((m, k), 2), ((m, n), 2)], [((g, k, n), 2)])
    assert dx[0] == dw[0] == fwd[0]
    assert dw[1] == fwd[1]


def test_xla_kernels_are_named_from_the_op_name_the_compiler_stamps():
    assert cost._xla_kernel_of("ragged-dot-none") == "ragged_dot"
    assert cost._xla_kernel_of("ragged-dot-metadata") == \
        "ragged_dot_metadata"
    assert cost._xla_kernel_of("jit(step)/mul:3/dot_general") is None
    assert cost._xla_kernel_of("") is None


def test_join_rows_carry_the_kernel_name():
    programs = {"jit_step(1)": {
        "custom-call.7": {"op_name": "jit(step)/flash_attention:9/"
                          "pallas_flash_fwd", "bucket": "custom_call",
                          "flops": 5.0, "bytes": 2.0,
                          "kernel": "flash_fwd"},
        "fusion.1": {"op_name": "jit(step)/mul:3/dot_general",
                     "bucket": "matmul", "flops": 1.0, "bytes": 1.0,
                     "kernel": None},
        # a map written before rows had kernels
        "copy.2": {"op_name": "", "bucket": "layout", "flops": 0.0,
                   "bytes": 1.0}}}
    ops = [("%custom-call.7 = bf16[4] custom-call()", 0.0, 2.0),
           ("%fusion.1 = bf16[4] fusion()", 2.0, 1.0),
           ("%copy.2 = bf16[4] copy()", 3.0, 1.0)]
    rows = {r["instruction"]: r for r in trace.join_events(
        ops, [("jit_step(1)", 0.0, 5.0)], programs)}
    assert rows["custom-call.7"]["kernel"] == "flash_fwd"
    assert rows["custom-call.7"]["op_type"] == "flash_attention"
    assert rows["fusion.1"]["kernel"] is None
    assert rows["copy.2"]["kernel"] is None
