"""The lane-decayed linear-attention MoE decoder on the normal path
(`models/decoder.py` with "channel_delta_attention" layers: the
`channel_delta_rule` op, the low-rank gate pairs, the norm a head under
a sigmoid gate; latent attention with ONE direct query projection and
nothing rotated; sigmoid-routed experts beside a shared one) against its
plain float32 reference (`benchmarks/reference_kimi_linear.py`) on the
CPU at a small size, seeded random weights: logits, the loss, every
routed layer's counts and experts, the gradient of every parameter.

The preset: hidden 64, one layer of each kind (a delta layer whose FFN
is dense, as the published layer 1's, and a latent layer with routed
experts: the shallowest toy that has both mixers and both FFNs; the
published pattern of layers 1-5 is the same mechanisms at twice the
build, and `benchmarks/kimi_linear_parity.py` runs it at the published
widths on the chip), 2 delta heads
of 16 x 16 under 4 taps, latent attention at 16 / 8 / 16 lanes out of a
latent of 24, 16 experts of width 32, 3 a token, at length 80 (two
chunks of 64, the second padded).  Every parameter that starts constant
(norm scales) and every selection bias is redrawn after start-up.

Also here: the chunked scan (its XLA lowering at heads of 16, the five
kernels through the interpreter at heads of 128) against the
position-by-position recurrence under mild, strong and lane-uneven
decay with every gradient; a decay constant over the lanes against
`gated_delta.py`; the shares' sum; what raises.

Tolerance.  Float32: both sides are float32 with matmuls at "highest"
and differ in summation order only (the chunks against the positions):
5e-6 absolute-or-relative on logits and loss, as the other families; a
gradient passes the scan's exponentials of cumulative sums twice and
takes 3e-5 of its largest entry (largest seen 6e-6).
"""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
sys.path.insert(0, os.path.dirname(__file__))
import reference_kimi_linear as ref  # noqa: E402
from models import kimi_linear as family  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import Family, close, reference, system  # noqa: E402

from paddle_tpu.observe.monitoring import runtime_stats  # noqa: E402
from paddle_tpu.ops.pallas import channel_delta, gated_delta  # noqa: E402

TOL, GRAD_TOL = 5e-6, 3e-5
LENGTH = 80
SHARES = {"whole-layer": dict(num_experts=16),
          "rank-1-of-4": dict(num_experts=4, expert_parallel_size=4,
                              expert_parallel_rank=1)}


def config(**over):
    cfg = dict(
        first_k_dense_replace=1, head_dim=12, hidden_act="silu",
        hidden_size=64, intermediate_size=96, kv_lora_rank=24,
        linear_attn_config={"full_attn_layers": [2], "head_dim": 16,
                            "kda_layers": [1], "num_heads": 2,
                            "short_conv_kernel_size": 4},
        mla_use_nope=True, moe_intermediate_size=32, moe_layer_freq=1,
        moe_renormalize=True, moe_router_activation_func="sigmoid",
        num_attention_heads=2, num_expert_group=1, num_experts=16,
        num_experts_per_token=3, num_hidden_layers=2, num_key_value_heads=2,
        num_nextn_predict_layers=0, num_shared_experts=1, q_lora_rank=None,
        qk_nope_head_dim=16, qk_rope_head_dim=8, rms_norm_eps=1e-5,
        rope_scaling=None, rope_theta=10000, routed_scaling_factor=2.446,
        tie_word_embeddings=False, topk_group=1, use_grouped_topk=True,
        v_head_dim=16, vocab_size=96, expert_parallel_size=1,
        expert_parallel_rank=0)
    cfg.update(over)
    return cfg


def arguments(cfg, **build):
    return dict(family.architecture(cfg), aux_loss_weight=0.0,
                z_loss_weight=0.0, **build)


def off_the_constants(main, scope, seed):
    """Parameters that start at a constant are moved off it and the
    selection biases drawn, so that the comparison sees them; returns
    the biases."""
    rng = np.random.default_rng(seed + 1)
    for p in main.all_parameters():
        value = np.asarray(scope.find_var(p.name))
        if value.std() == 0:
            scope.set_var(p.name, jnp.asarray(
                value + 0.1 * rng.normal(size=value.shape)
                .astype(np.float32)))
    return harness.draw_expert_biases(main, scope, seed)


FAMILY = Family(ref.params_from_list, ref.loss_and_grads, ref.grads_to_list)
batch = functools.partial(harness.batch, length=LENGTH)


# -- (a) the builder's program against the reference ------------------------

@pytest.mark.parametrize("share, recompute", [
    ("whole-layer", None), ("whole-layer", "layer"),
    ("rank-1-of-4", None), ("rank-1-of-4", "layer")])
def test_program_matches_the_float32_reference(share, recompute):
    cfg = config(**SHARES[share])
    feed = batch(cfg)
    got, params = system(arguments(cfg, recompute=recompute), feed,
                         after_startup=off_the_constants)
    total, parts, grads = reference(FAMILY, cfg, feed, params,
                                    drawn=got["drawn"])
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    assert len(got["counts"]) == len(parts["counts"]) == 1  # the sparse layer
    np.testing.assert_array_equal(got["counts"][0],
                                  np.asarray(parts["counts"][0]))
    np.testing.assert_array_equal(
        np.sort(got["experts"][0], axis=-1),
        np.sort(np.asarray(parts["experts"][0]), axis=-1))
    names = ref.system_names(cfg)
    assert len(got["grads"]) == len(grads) == len(params) == len(names)
    for name, g, w in zip(names, got["grads"], grads):
        # no vacuous match, but for a share's router (held constant by
        # the builder on both sides: no exchange sums the ranks')
        routerless = share != "whole-layer" and name.endswith(".router")
        assert (np.abs(np.asarray(w)).max() > 0) != routerless, name
        close(g, w, f"gradient of {name}", GRAD_TOL,
              scale=np.abs(np.asarray(w)).max())
    shapes = {n: p.shape for n, p in zip(names, params)}
    # the delta mixer: one q, k, v projection and filter, two low-rank
    # pairs at the head size's rank, a rate a head and a bias a lane
    assert [shapes[f"layer0.{k}"] for k in ref.DELTA_KEYS] == [
        (64,), (64, 96), (96, 4), (64, 16), (16, 32), (64, 2), (2,), (32,),
        (64, 16), (16, 32), (16,), (32, 64)]
    # latent attention: ONE direct q projection (two column blocks), no
    # query latent and no norm of one
    assert [shapes[f"layer1.{k}"] for k in ref.LATENT_KEYS] == [
        (64,), (64, 32), (64, 16), (64, 24), (24,), (64, 8), (24, 32),
        (24, 32), (32, 64)]
    held = SHARES[share]["num_experts"]
    assert shapes["layer1.router"] == (64, 16)
    assert shapes["layer1.w1"] == (held, 64, 32)
    took = got["took"]
    # heads of 16: the XLA lowering, and no other family's scan
    assert took["channel_delta_calls"] == 0
    assert took["channel_delta_operand_calls"] == 0
    assert took["gated_delta_calls"] == took["ropes_kernel"] \
        == took["ropes_xla"] == 0


def test_the_scopes_are_the_documented_ones():
    cfg = config()
    got, _ = system(arguments(cfg), batch(cfg),
                    after_startup=off_the_constants)
    ops = got["main"].global_block().ops
    by_scope = {}
    for op in ops:
        by_scope.setdefault(op.type, set()).add(
            op.desc.attrs.get("__name_scope__", ""))
    assert by_scope["channel_delta_rule"] == {"channel_delta_attention"}
    assert by_scope["short_conv"] == {"channel_delta_attention"}
    assert by_scope["latent_attention"] == {"latent_attention"}
    assert "rope" not in by_scope               # nothing is rotated
    gated = [op for op in ops if op.type == "rms_norm"
             and op.desc.attrs.get("gate_activation") == "sigmoid"]
    assert len(gated) == 1
    assert all(op.desc.attrs["group_size"] == 16 for op in gated)


def test_the_reference_in_runs_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/kimi_linear_parity.py` runs on the chip so that
    8192 positions fit: scores `q_block` rows at a time, the recurrence
    in recomputed runs of `q_block` positions, every layer recomputed in
    its backward pass.  Same numbers."""
    cfg = config()
    feed = batch(cfg)
    got, params = system(arguments(cfg), feed,
                         after_startup=off_the_constants)
    plain, _, want = reference(FAMILY, cfg, feed, params, drawn=got["drawn"])
    blocked, _, grads = reference(FAMILY, cfg, feed, params,
                                  drawn=got["drawn"], q_block=16)
    close(blocked, plain, "loss")
    for w, g in zip(want, grads):
        close(g, w, "gradient", scale=np.abs(np.asarray(w)).max())


# -- (b) the chunked scan against the recurrence ----------------------------

def sequential(q, k, v, g, beta):
    n, t, h = beta.shape
    heads = lambda x: x.astype(jnp.float32).reshape(  # noqa: E731
        n, t, h, x.shape[2] // h)
    with jax.default_matmul_precision("highest"):
        o = ref.delta_rule(heads(q), heads(k), heads(v), heads(g),
                           beta.astype(jnp.float32))
    return o.reshape(n, t, -1)


def scan_case(t, decay, h=2, d=16, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)

    def unit(x):
        x = x.reshape(1, t, h, d)
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).reshape(
            1, t, h * d)

    q = unit(r.normal(size=(1, t, h * d))) * d ** -0.5
    k, v = unit(r.normal(size=(1, t, h * d))), r.normal(size=(1, t, h * d))
    rate = {"mild": np.full(h * d, 0.01), "strong": np.full(h * d, 4.0),
            # a lane's own: from almost none to e^-12 a position
            "uneven": np.exp(np.linspace(np.log(1e-4), np.log(12.0),
                                         h * d))}[decay]
    g = -rate * np.log1p(np.exp(r.normal(size=(1, t, h * d))))
    beta = 1 / (1 + np.exp(-r.normal(size=(1, t, h))))
    return [jnp.asarray(x, kind) for x, kind in (
        (q, dtype), (k, dtype), (v, dtype), (g, jnp.float32),
        (beta, jnp.float32))]


@pytest.mark.parametrize("decay", ["mild", "strong", "uneven"])
@pytest.mark.parametrize("lowering", ["xla", "kernel"])
def test_the_chunked_scan_is_the_sequential_recurrence(lowering, decay):
    """o and the gradients of q, k, v, g and beta under a random
    cotangent: the XLA lowering at heads of 16 over 160 positions (two
    chunks and a padded third), the five kernels through the interpreter
    at heads of 128 over 128 positions; the counters say which ran."""
    kernel = lowering == "kernel"
    args = scan_case(128, decay, d=128) if kernel else scan_case(160, decay)
    ct = jnp.asarray(np.random.default_rng(9).normal(size=args[2].shape),
                     jnp.float32)

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a) * ct)

    def chunked(*a):
        return channel_delta.channel_delta_rule(*a, use_kernel=kernel)

    # (the XLA lowering and the recurrence as compiled functions: op by
    # op they compile every primitive alone; the kernels' calls stay op
    # by op, where a case finds the interpreter's programs of the case
    # before it)
    compiled = (lambda fn: fn) if kernel else jax.jit
    before = runtime_stats.snapshot()
    with jax.default_matmul_precision("highest"):
        got = compiled(chunked)(*args)
        got_grads = compiled(jax.grad(scalar(chunked),
                                      argnums=range(5)))(*args)
        want, *want_grads = jax.jit(lambda *a: (sequential(*a),) + jax.grad(
            scalar(sequential), argnums=range(5))(*a))(*args)
    took = runtime_stats.delta(before)
    close(got, want, "o", scale=float(jnp.abs(want).max()))
    for name, g, w in zip(("dq", "dk", "dv", "dg", "dbeta"), got_grads,
                          want_grads):
        close(g, w, name, GRAD_TOL, scale=float(jnp.abs(w).max()))
    # forward, then the forward rule's and the backward's: 2 chunks x 2
    # heads a call; the inverse kernel is a chunk-local call too
    assert (took["channel_delta_calls"], took["channel_delta_chunks"]) == (
        (3, 12) if kernel else (0, 0))
    assert (took["channel_delta_operand_calls"],
            took["channel_delta_operand_chunks"]) == (
        (5, 20) if kernel else (0, 0))


def products_case(decay):
    """One head's float32 chunk: the rows kb and q, k, gamma (C, 128),
    and cotangents of A (strictly lower) and P (lower)."""
    c, d = channel_delta.CHUNK, channel_delta.HEAD_DIM
    if decay == "cliff":
        # -80 a position on a quarter of the lanes: exp(gamma) alone is
        # 0 from the second position on, a pair two apart underflows
        g = np.where(np.arange(d) % 4 == 0, -80.0, 0.0) * np.ones((c, 1))
    else:
        g = np.asarray(scan_case(c, decay, h=1, d=d)[3])[0]
    r = np.random.default_rng(3)
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    kb, q, k = (f32(r.normal(size=(c, d))) for _ in range(3))
    cts = [f32(np.tril(r.normal(size=(c, c)), -lower)) for lower in (1, 0)]
    return [kb, q], k, f32(np.cumsum(g, axis=0)), cts


def dense_products(rows, k, gamma):
    """What `chunk_operands` writes out: the (C, C, Dk) decay tensor."""
    decay = jnp.exp(jnp.where(
        gated_delta._lower(channel_delta.CHUNK)[..., None],
        gamma[:, None, :] - gamma[None, :, :], -jnp.inf))
    return [jnp.einsum("id,jd,ijd->ij", x, k, decay) for x in rows]


@pytest.mark.parametrize("decay", ["mild", "strong", "uneven", "cliff"])
def test_the_decayed_products_are_the_dense_decay_einsum(decay):
    """`decayed_products` (what `channel_delta_inverse` runs a head and
    chunk: the levels' MXU products) for the rows (kb, q) together; the
    pairs across a cliff come out 0 or tiny, never inf - inf."""
    rows, k, gamma, _ = products_case(decay)
    got = channel_delta.decayed_products(rows, k, gamma, jnp.float32)
    for name, g, w in zip(("a", "p"), got, dense_products(rows, k, gamma)):
        assert np.isfinite(np.asarray(g)).all(), name
        assert not np.triu(np.asarray(g), 1).any(), name
        close(g, w, name, scale=float(jnp.abs(w).max()))


@pytest.mark.parametrize("decay", ["mild", "strong", "uneven", "cliff"])
def test_the_decayed_products_gradients_are_the_dense_einsums(decay):
    """`decayed_products_bwd` (`channel_delta_operands_bwd`'s) against
    `jax.vjp` of the dense form: dkb, dq and dk."""
    rows, k, gamma, cts = products_case(decay)
    dxs, dk = channel_delta.decayed_products_bwd(rows, cts, k, gamma,
                                                 jnp.float32)
    _, vjp = jax.vjp(lambda kb, q, k: dense_products([kb, q], k, gamma),
                     *rows, k)
    for name, g, w in zip(("dkb", "dq", "dk"), dxs + [dk], vjp(cts)):
        assert np.isfinite(np.asarray(g)).all(), name
        close(g, w, name, scale=float(jnp.abs(w).max()))


def raw_case(dtype, t=128, h=4, d=128, seed=11):
    """QKV (1, T, 3 h d) as a projection writes it (rows of any norm),
    g, beta, and what `channel_delta_rule` makes of them before its
    chunk-local kernels each way: (q, k, kb) with `raw` = the l2norm
    INSIDE the kernels (QKV twice as it lies, kb = beta x the raw k) and
    with None = `head_norm_xla` first, then today's kernels (unit q and
    k, kb = beta x the unit k); vb is the same."""
    from paddle_tpu.ops.pallas import head_norm

    r = np.random.default_rng(seed)
    f32 = jnp.float32
    qkv = jnp.asarray(r.normal(size=(1, t, 3 * h * d))
                      * np.exp(r.normal(size=(1, t, 1))), dtype)
    g = jnp.asarray(-0.1 * np.log1p(np.exp(r.normal(size=(1, t, h * d)))),
                    f32)
    beta = jnp.asarray(1 / (1 + np.exp(-r.normal(size=(1, t, h)))), f32)
    raw = channel_delta.RawQK(q=0, k=h * d, heads=h, dim=d)

    def times_beta(x, beta):
        return (x.astype(f32) * channel_delta.head_spread(beta, d)).astype(
            x.dtype)

    def operands(qkv, beta, inside):
        if inside:
            q = k = qkv
            kb = times_beta(qkv[..., h * d:2 * h * d], beta)
        else:
            form = head_norm.Form(0, h * d)
            q = head_norm.head_norm_xla(
                qkv[..., :h * d], None, None,
                form._replace(constant=d ** -0.5), d)
            k = head_norm.head_norm_xla(qkv[..., h * d:2 * h * d], None,
                                        None, form, d)
            kb = times_beta(k, beta)
        return q, k, kb, times_beta(qkv[..., 2 * h * d:], beta)

    return qkv, g, beta, raw, operands


INSIDE_TOL = {"float32": 1e-6, "bfloat16": 0.02}


@pytest.mark.parametrize("dtype", sorted(INSIDE_TOL))
@pytest.mark.parametrize("kernel", ["inverse", "operands_fwd",
                                    "operands_bwd"])
def test_the_l2norm_inside_a_chunk_local_kernel_is_head_norm_before_it(
        kernel, dtype):
    """Each chunk-local kernel (interpret mode) on QKV as it lies, the
    l2norm of q and k taken inside (`RawQK`), against `head_norm_xla`
    followed by the same kernel on unit q and k: float32 to 1e-6 of the
    largest entry, bfloat16 within the scan's own 2 %.  The backward
    kernel through `operands_kernel`'s VJP: the gradients of the RAW
    QKV (q's and k's lanes through the l2norm's rule, kb's riding k's),
    g and beta, under one cotangent of the five results."""
    qkv, g, beta, raw, operands = raw_case(jnp.dtype(dtype))
    tol = INSIDE_TOL[dtype]

    def same(got, want, names):
        for name, a, b in zip(names, got, want):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            close(a.astype(jnp.float32), b.astype(jnp.float32), name, tol,
                  scale=float(jnp.abs(b.astype(jnp.float32)).max()))

    unit = operands(qkv, beta, False)
    inside = operands(qkv, beta, True)
    kept = channel_delta._inverse_call(*unit[:3], g, interpreted=True)
    if kernel == "inverse":
        same(channel_delta._inverse_call(*inside[:3], g, raw=raw,
                                         interpreted=True), kept, "mp")
        return
    if kernel == "operands_fwd":
        same(channel_delta._operands_fwd_call(*inside, g, kept[0], raw=raw,
                                              interpreted=True),
             channel_delta._operands_fwd_call(*unit, g, kept[0],
                                              interpreted=True),
             ("w", "u", "qg", "kd"))
        return
    cts = [jnp.asarray(np.random.default_rng(5 + i).normal(size=shape), dtype)
           for i, shape in enumerate(
               [(4, 128, 128)] * 4 + [(4, 128, channel_delta.CHUNK)])]

    def results(way):
        def fn(qkv, g, beta):
            return channel_delta.operands_kernel(
                *operands(qkv, beta, way is not None), g, *kept, way)
        return jax.vjp(fn, qkv, g, beta)[1](tuple(cts))

    before = runtime_stats.snapshot()
    got = results(raw)
    # the forward rule's kernel and the backward kernel, 2 chunks x 4 heads
    assert runtime_stats.delta(before)["channel_delta_operand_chunks"] == 16
    same(got, results(None), ("dqkv", "dg", "dbeta"))
    third = qkv.shape[2] // 3
    for i, name in enumerate(("raw q", "raw k")):
        assert float(jnp.abs(got[0][..., i * third:(i + 1) * third]
                             .astype(jnp.float32)).max()) > 0, name


def test_a_decay_constant_over_the_lanes_gives_gated_deltas_numbers():
    """Gated DeltaNet is the case g_t constant over the lanes: the same
    o, to float32's order of summation, from `gated_delta.py`'s chunks
    on g a head and from these on g broadcast over the head's lanes."""
    q, k, v, _, beta = scan_case(192, "mild")
    g = -jnp.asarray(np.random.default_rng(4).uniform(0.01, 2.0,
                                                      size=beta.shape),
                     jnp.float32)
    heads = lambda x: x.reshape(1, 192, 2, 16)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = gated_delta.gated_delta_rule(heads(q), heads(k), heads(v), g,
                                            beta)
        got = channel_delta.channel_delta_rule(
            q, k, v, jnp.repeat(g, 16, axis=-1), beta)
    close(got, want.reshape(1, 192, 32), "o",
          scale=float(jnp.abs(want).max()))


def test_the_scan_in_bfloat16_misses_the_float32_tolerance():
    """The operands' dtype is the dots' (and the state's as a dot reads
    it): bfloat16 operands (AMP) stay within 2% of the sequential
    recurrence and miss TOL by far, on either lowering."""
    for kernel in (False, True):
        args = scan_case(128, "mild", d=128 if kernel else 16)
        want = sequential(*args)
        low = [x.astype(jnp.bfloat16) for x in args[:3]] + args[3:]
        got = channel_delta.channel_delta_rule(*low, use_kernel=kernel)
        err = float(jnp.abs(got.astype(jnp.float32) - want).max()
                    / jnp.abs(want).max())
        assert 100 * TOL < err < 0.02, err


def test_the_kernels_take_pairs_of_heads_of_128_in_blocks_of_8_chunks():
    takes = channel_delta.kernel_takes
    assert takes(32, 128, 128, 8192) and takes(2, 128, 128, 100)
    assert not takes(3, 128, 128, 8192)         # an odd head
    assert not takes(32, 64, 128, 8192) and not takes(32, 128, 64, 8192)
    assert not takes(32, 128, 128, 64 * 12)     # 12 chunks: no block of 8
    args = scan_case(64, "mild", d=16)
    with pytest.raises(NotImplementedError, match="even number of heads"):
        channel_delta.channel_delta_rule(*args, use_kernel=True)
    with pytest.raises(ValueError, match="a decay a key lane"):
        channel_delta.channel_delta_rule(*args[:3], args[4], args[4])


# -- (c) the share, the gated norm, what raises -----------------------------

E_ALL, RANKS, K, D, H, T = 16, 4, 3, 64, 32, 40
HELD = E_ALL // RANKS


def test_all_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The `model-configs` guide's tie of the share to the model: the
    routed parts of all 16 / 4 = 4 shares of the preset (through the op
    the builder appends) plus the shared expert COUNTED ONCE are the
    uncut reference's routed FFN."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    r = np.random.default_rng(0)
    draw = lambda *shape, scale=0.3: jnp.asarray(  # noqa: E731
        r.normal(size=shape).astype(np.float32) * scale)
    p = {"x": draw(T, D, scale=1.0), "router": draw(D, E_ALL, scale=0.25),
         "bias": draw(E_ALL, scale=0.05), "w1": draw(E_ALL, D, H),
         "w3": draw(E_ALL, D, H), "w2": draw(E_ALL, H, D),
         "shared_w1": draw(D, H), "shared_w3": draw(D, H),
         "shared_w2": draw(H, D)}
    cfg = {"num_experts_per_token": K, "moe_renormalize": True,
           "routed_scaling_factor": 2.446}
    with jax.default_matmul_precision("highest"):
        want, counts, _ = ref.experts(p["x"], p, cfg)
        shared = ref.swiglu(p["x"], p["shared_w1"], p["shared_w3"],
                            p["shared_w2"])
    parts = []
    for rank in range(RANKS):
        lo = rank * HELD
        o = get_op_impl("moe_dropless")(
            OpContext(jax.random.PRNGKey(0), 0),
            {"X": [p["x"]], "GateW": [p["router"]],
             "Bias": [p["bias"]],
             **{k.upper(): [p[k][lo:lo + HELD]] for k in ("w1", "w3", "w2")}},
            {"routing": "sigmoid", "norm_topk_prob": True, "top_k": K,
             "norm_topk_eps": 1e-20, "routed_scaling_factor": 2.446,
             "experts_held": [lo, HELD]})
        parts.append((o["Out"][0], o["Counts"][0]))
    total = sum(np.asarray(y, np.float64) for y, _ in parts) \
        + np.asarray(shared, np.float64)
    np.testing.assert_allclose(total, np.asarray(want + shared), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c) for _, c in parts]),
        np.asarray(counts))
    assert sum(int(c.sum()) for _, c in parts) == T * K
    assert np.abs(np.asarray(shared)).max() > 0.1


def test_a_norm_a_head_under_a_sigmoid_gate_is_one_op():
    from op_test import run_op

    r = np.random.default_rng(5)
    x = r.normal(size=(2, 6, 32)).astype(np.float32)
    w = (1 + 0.2 * r.normal(size=(16,))).astype(np.float32)
    gate = r.normal(size=(2, 6, 32)).astype(np.float32)
    heads = x.reshape(2, 6, 2, 16)
    normed = heads / np.sqrt((heads ** 2).mean(-1, keepdims=True) + 1e-5) * w
    attrs = {"epsilon": 1e-5, "group_size": 16}
    ins = {"X": x, "Scale": w, "Gate": gate}
    got = run_op("rms_norm", ins, dict(attrs, gate_activation="sigmoid"),
                 out_slot="Y")
    np.testing.assert_allclose(
        got, normed.reshape(x.shape) / (1 + np.exp(-gate)), rtol=2e-6,
        atol=2e-6)
    silu = run_op("rms_norm", ins, attrs, out_slot="Y")
    np.testing.assert_allclose(silu, got * gate, rtol=2e-6, atol=2e-6)
    with pytest.raises(NotImplementedError, match="gate_activation"):
        run_op("rms_norm", ins, dict(attrs, gate_activation="tanh"),
               out_slot="Y")


@pytest.mark.parametrize("over, error, match", [
    (dict(num_expert_group=2), NotImplementedError, "num_expert_group 2"),
    (dict(topk_group=4), NotImplementedError, "topk_group 4"),
    (dict(num_nextn_predict_layers=1), NotImplementedError,
     "channel_delta_attention layer beside a prediction module"),
    (dict(total_ut_steps=2, exit_gate="sigmoid", num_dense_layers=5),
     NotImplementedError, "beside a looped stack"),
    (dict(num_attention_heads_per_layer=[2] * 5), NotImplementedError,
     "num_attention_heads_per_layer"),
    (dict(objective="block_diffusion", block_length=4), NotImplementedError,
     "block_diffusion"),
    (dict(linear_attn_config={"num_heads": 2}), ValueError,
     "needs linear_attn_config with num_heads, head_dim"),
    (dict(kv_lora_rank=None, qk_nope_head_dim=None, qk_rope_head_dim=None,
          v_head_dim=None, rope_theta=100.0), ValueError,
     "mla_use_nope without kv_lora_rank"),
    (dict(qk_rope_head_dim=None), ValueError, "latent attention needs"),
    (dict(attention_gate="sigmoid"), NotImplementedError,
     "gate on latent attention")])
def test_unbuilt_values_of_the_new_keys_raise(over, error, match):
    cfg = config()
    build = dict(arguments(cfg), **over)
    with pytest.raises(error, match=match):
        system(build, batch(cfg))


@pytest.mark.parametrize("key, value", [
    ("hidden_act", "gelu"), ("moe_layer_freq", 2),
    ("rope_scaling", {"type": "yarn"}), ("use_grouped_topk", False),
    ("moe_router_activation_func", "softmax")])
def test_the_family_raises_on_what_is_not_built(key, value):
    with pytest.raises(NotImplementedError, match=key):
        family.architecture(config(**{key: value}))


def test_the_layer_lists_must_name_every_layer_once():
    group = dict(config()["linear_attn_config"], full_attn_layers=[1, 2])
    with pytest.raises(ValueError, match="once each"):
        family.architecture(config(linear_attn_config=group))
