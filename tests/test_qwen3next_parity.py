"""The hybrid linear-attention MoE decoder on the normal path
(`models/decoder.py` with `layer_types` holding `linear_attention`, the
`linear_*` keys, `partial_rotary_factor`, `zero_centered_norm`,
`attention_gate`, `shared_expert_intermediate_size` and
`shared_expert_gate`) against its plain float32 reference
(`benchmarks/reference_qwen3next.py`, whose recurrence is a scan over
POSITIONS) on the CPU at a small size, seeded random weights; the
chunked scan (`ops/pallas/gated_delta.py`), kernels in interpret mode
and XLA lowering alike, against the sequential recurrence; the share
test of the `model-configs` guide; the norm's zero-centred scale; and
every new key's unbuilt values.

Sizes of the preset: d 64, one layer of each kind (linear, full: the
shallowest toy that has both mixers; the published period, linear x 3
then full, is the same two mechanisms at twice the build, and
`benchmarks/qwen3next_parity.py` runs it at the published widths on the
chip), 2 key / 4 value linear heads of 16, 2 query heads over 1
key/value head of 32 with 8 rotated lanes, 16 experts of which 4 are
held (rank 1 of 4), 3 a token, a shared expert of 24 with its gate, T 80
(a chunk and a quarter: the padded tail).

Tolerance.  Float32: both sides are float32 with matmuls at "highest"
and differ in summation order only (chunks against positions, the
flash kernel's online soft-max, the sorted expert rows): 5e-6
absolute-or-relative, as tests/test_mellum_parity.py (largest seen
here 4e-8 on a gradient, 2e-6 on the logits).
"""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import decoder
from paddle_tpu.ops.pallas import gated_delta

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
sys.path.insert(0, os.path.dirname(__file__))
import reference_qwen3next as ref  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import (Family, build_and_run, close,  # noqa: E402
                            reference, system)

TOL = 5e-6
NO_AUX = dict(aux_loss_weight=0.0, z_loss_weight=0.0)
EQUATIONS = dict(qk_norm="head", router="softmax", zero_centered_norm=True,
                 attention_gate="sigmoid", shared_expert_gate="sigmoid")
SHARES = {"whole-layer": dict(num_experts=16),
          "rank-1-of-4": dict(num_experts=4, expert_parallel_size=4,
                              expert_parallel_rank=1)}
KINDS = ["linear_attention", "full_attention"]


def config(**over):
    cfg = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=2,
               num_key_value_heads=1, head_dim=32,
               partial_rotary_factor=0.25, rope_theta=100.0,
               intermediate_size=96, moe_intermediate_size=32,
               shared_expert_intermediate_size=24, num_experts=16,
               num_experts_per_tok=3, norm_topk_prob=True,
               rms_norm_eps=1e-6, vocab_size=96, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=16, linear_conv_kernel_dim=4,
               layer_types=list(KINDS), **EQUATIONS)
    cfg.update(over)
    return cfg


def reference_config(cfg):
    return dict(cfg, full_attention_interval=len(cfg["layer_types"]))


def arguments(cfg, **build):
    return dict(cfg, **NO_AUX, **build)


def off_the_constants(main, scope, seed):
    """Parameters that start at a constant (the norms' scales,
    `dt_bias`) are moved off it first, so that a scale of 1 + w with
    w = 0 is not all that is compared."""
    rng = np.random.default_rng(seed + 1)
    for p in main.all_parameters():
        value = np.asarray(scope.find_var(p.name))
        if value.std() == 0:
            scope.set_var(p.name, jnp.asarray(
                value + 0.1 * rng.normal(size=value.shape)
                .astype(np.float32)))


FAMILY = Family(ref.params_from_list, ref.loss_and_grads,
                lambda grads, cfg: ref.flat_leaves(grads))
batch = functools.partial(harness.batch, length=80)


# -- (a) the builder's program against the reference ------------------------

@pytest.mark.parametrize("recompute", [None, "layer"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_program_matches_the_float32_reference(share, recompute):
    cfg = config(**SHARES[share])
    feed = batch(cfg)
    got, params = system(arguments(cfg, recompute=recompute), feed,
                         after_startup=off_the_constants)
    total, parts, grads = reference(FAMILY, reference_config(cfg), feed,
                                    params)
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    assert len(got["counts"]) == 2
    for i in range(2):
        np.testing.assert_array_equal(got["counts"][i],
                                      np.asarray(parts["counts"][i]))
        np.testing.assert_array_equal(
            np.sort(got["experts"][i], axis=-1),
            np.sort(np.asarray(parts["experts"][i]), axis=-1))
    names = ref.leaf_names(reference_config(cfg))
    assert len(got["grads"]) == len(grads) == len(params) == len(names)
    for name, g, w in zip(names, got["grads"], grads):
        # no vacuous match, but for a share's router (held constant
        # by the builder on both sides: no exchange sums the ranks')
        routerless = share != "whole-layer" and name.endswith(".router")
        assert (np.abs(np.asarray(w)).max() > 0) != routerless, name
        close(g, w, f"gradient of {name}")
    # the mixers' parameters, in creation order, by shape
    held = SHARES[share]["num_experts"]
    linear = [p.shape for p in params[1:10]]
    assert linear == [(64,), (64, 128), (128, 4), (64, 64), (64, 8), (4,),
                      (4,), (16,), (64, 64)]
    full = [p.shape for p in params[-11 - 8:-11]]
    assert full == [(64,), (64, 64), (32,), (64, 32), (32,), (64, 32),
                    (64, 64), (64, 64)]
    sparse = [p.shape for p in params[-11:-2]]
    assert sparse[:2] == [(64,), (64, 16)]
    assert sparse[2] == (held, 64, 32)
    assert sparse[5:] == [(64, 24), (64, 24), (24, 64), (64, 1)]


def test_the_reference_in_runs_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/qwen3next_parity.py` runs on the chip so that
    16384 positions fit: scores `q_block` rows at a time, the recurrence
    in recomputed runs of `q_block` positions (one state kept a run),
    every layer recomputed in its backward pass.  Same numbers."""
    cfg = config(**SHARES["rank-1-of-4"])
    feed = batch(cfg)
    _, params = system(arguments(cfg), feed, after_startup=off_the_constants)
    plain, _, want = reference(FAMILY, reference_config(cfg), feed, params)
    blocked, _, got = reference(FAMILY, reference_config(cfg), feed, params,
                                q_block=16)
    close(blocked, plain, "loss")
    for w, g in zip(want, got):
        close(g, w, "gradient")


def test_the_two_kinds_of_layer_lower_under_scopes_of_their_own():
    """`linear_attention` / `gated_attention` / `shared_expert` name
    scopes around the mixers and the shared expert's ops; at heads of
    16 the scan is the XLA lowering and counts no kernel call."""
    from paddle_tpu.observe.monitoring import runtime_stats

    cfg = config()
    before = runtime_stats.snapshot()
    got, _ = build_and_run(arguments(cfg), batch(cfg, n=1),
                           after_startup=off_the_constants)
    took = runtime_stats.delta(before)
    assert (took["gated_delta_calls"], took["gated_delta_chunks"]) == (0, 0)
    assert (took["gated_delta_operand_calls"],
            took["gated_delta_operand_chunks"]) == (0, 0)
    found = [op.attrs.get("__name_scope__", "") for b in got["main"].blocks
             for op in b.ops]
    by_type = {s: [op.type for b in got["main"].blocks for op in b.ops
                   if op.attrs.get("__name_scope__", "") == s]
               for s in set(found)}
    assert by_type["linear_attention"].count("gated_delta_rule") == 1
    assert by_type["linear_attention"].count("short_conv") == 1
    assert by_type["gated_attention"].count("flash_attention") == 1
    assert by_type["gated_attention"].count("sigmoid") == 1
    assert by_type["shared_expert"].count("sigmoid") == 2
    assert "full_attention" not in by_type


def test_a_step_counts_three_kernel_calls_a_linear_layer_and_a_build_none():
    """At heads of 128 x 128 the scan is the Pallas kernels' (interpret
    mode here): building the Program traces no call (the op is not
    shape-inferred at the stand-in batch), the first step's build a
    layer's forward, recomputed forward and backward, a second step
    none.  What `gated_delta_chunks_per_step` reads off a process."""
    from paddle_tpu.observe.monitoring import runtime_stats

    cfg = config(linear_num_key_heads=1, linear_num_value_heads=2,
                 linear_key_head_dim=128, linear_value_head_dim=128)
    feed = batch(cfg, n=1, length=128)
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    marks = [runtime_stats.snapshot()]
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = decoder.build_model(max_length=128, warmup_steps=2,
                                recompute="layer", **NO_AUX, **cfg)
        marks.append(runtime_stats.snapshot())
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed=feed, scope=scope, fetch_list=[m["loss"]])
            marks.append(runtime_stats.snapshot())
    for kind in ("gated_delta", "gated_delta_operand"):
        # the scan's kernels, and the chunk-operand kernels before them
        took = [(b[f"{kind}_calls"] - a[f"{kind}_calls"],
                 b[f"{kind}_chunks"] - a[f"{kind}_chunks"])
                for a, b in zip(marks, marks[1:])]
        assert took == [(0, 0), (3, 3 * 2 * 2), (0, 0)], kind
    # every one of the six on the op's own arrays: the scan's o and dO
    # by lane block, the chunk-local kernels' q, k, v and dQKV on QKV
    assert [b["gated_delta_flat_calls"] - a["gated_delta_flat_calls"]
            for a, b in zip(marks, marks[1:])] == [0, 6, 0]
    # the inverse kernel is traced with the layer, once, and the layer's
    # segment keeps what it wrote, (1 key head, 128, 2 x 64) float32,
    # as the full layer's keeps its flash call's output (128 x 2 heads
    # of 32, bfloat16) and logsumexp (8 float32 sublanes a head)
    attention = 128 * 64 * 2 + 2 * 8 * 128 * 4
    for name, step in (("gated_delta_inverse_calls", 1),
                       ("recompute_kept_residuals", 2),
                       ("recompute_kept_bytes", 128 * 128 * 4 + attention)):
        assert [b[name] - a[name] for a, b in zip(marks, marks[1:])] == [
            0, step, 0], name


# -- (b) the chunked scan against the sequential recurrence -----------------

def sequential(q, k, v, g, beta):
    """The recurrence as it is written, a position at a time."""
    r = v.shape[2] // k.shape[2]
    return ref.delta_rule(jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2),
                          v, g, beta)


def scan_case(t, decay, seed=0, hk=1, hv=2, d=gated_delta.HEAD_DIM,
              repeat=False, beta_scale=1.0):
    """q, k unit vectors a head (q over sqrt(d)), v N(0, 1), beta in
    (0, 1); `decay` "far": g drawn so that a chunk's exp(gamma_C) passes
    1e-6; "none": g = 0, the plain delta rule; else mild.  `repeat`:
    positions 10-70 hold ONE key (a chunk's A is then all of one sign
    and size, where the product form of the inverse lost its digits);
    `beta_scale` 12 puts beta within 1e-5 of 0 or 1."""
    r = np.random.default_rng(seed)
    q, k = r.normal(size=(2, 1, t, hk, d))
    if repeat:
        k[:, 10:70] = k[:, 10:11]
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(1, t, hv, d))
    g = -np.abs(r.normal(size=(1, t, hv))) \
        * {"far": 0.44, "none": 0.0, "mild": 0.05}[decay]
    beta = 1 / (1 + np.exp(-beta_scale * r.normal(size=(1, t, hv))))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


@functools.cache
def sequential_reference(t, decay):
    """The recurrence's output and its gradients of q, k, v, g and beta
    under the scan tests' weight on `scan_case(t, decay)`: ONE compiled
    function, once for both lowerings."""
    weight = jnp.asarray(np.random.default_rng(9).normal(
        size=(1, t, 2, gated_delta.HEAD_DIM)), jnp.float32)
    return jax.jit(lambda *a: (sequential(*a),) + jax.grad(
        lambda *b: jnp.sum(sequential(*b) * weight), argnums=range(5))(*a))(
        *scan_case(t, decay))


@pytest.mark.parametrize("lowering", ["xla", "kernel"])
@pytest.mark.parametrize("t,decay", [(64, "mild"), (256, "far"),
                                     (256, "none"), (200, "mild")],
                         ids=["T=C", "T=4C-far-decay", "T=4C-no-decay",
                              "T=200-padded-tail"])
def test_the_chunked_scan_is_the_sequential_recurrence(lowering, t, decay):
    """Forward and the gradients of q, k, v, g and beta, the Pallas
    kernels (interpret mode) and the XLA lowering of the same chunks."""
    from paddle_tpu.observe.monitoring import runtime_stats

    args = scan_case(t, decay)
    if decay == "far":
        per_chunk = jnp.exp(args[3].reshape(1, -1, 64, 2).sum(axis=2))
        assert float(per_chunk.min()) < 1e-6
    weight = jnp.asarray(np.random.default_rng(9).normal(
        size=(1, t, 2, gated_delta.HEAD_DIM)), jnp.float32)

    def chunked(*a):
        return gated_delta.gated_delta_rule(*a,
                                            use_kernel=lowering == "kernel")

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    # (the XLA lowering as a compiled function: op by op it compiles
    # every primitive alone; the kernels' calls stay op by op, where a
    # case finds the interpreter's programs of the case before it)
    compiled = jax.jit if lowering == "xla" else (lambda fn: fn)
    before = runtime_stats.snapshot()
    got = compiled(chunked)(*args)
    got_grads = compiled(jax.grad(scalar(chunked), argnums=range(5)))(*args)
    took = runtime_stats.delta(before)
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                          (got,) + got_grads, sequential_reference(t, decay)):
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=TOL * scale, err_msg=name)
    chunks = 2 * -(-t // 64)            # heads x chunks, the tail padded
    # a forward call, then the forward rule's and the backward kernel
    for kind in ("gated_delta", "gated_delta_operand"):
        assert (took[f"{kind}_calls"], took[f"{kind}_chunks"]) == (
            (3, 3 * chunks) if lowering == "kernel" else (0, 0)), kind
    # the inverse: the forward call's and the differentiated call's; no
    # segment is open, so nothing counts as kept
    assert took["gated_delta_inverse_calls"] == 2 * (lowering == "kernel")
    # the scan kernels write o as the op lays it; q, k and v apart are
    # no QKV, and a fall-back counts nothing
    assert took["gated_delta_flat_calls"] == 3 * (lowering == "kernel")
    assert took["recompute_kept_residuals"] == 0


def test_the_scan_in_bfloat16_misses_the_float32_tolerance():
    """The operands' dtype is the dots': bfloat16 operands (AMP) stay
    within 2% of the sequential recurrence and miss TOL by far."""
    args = scan_case(256, "mild")
    want = sequential(*args)
    low = [x.astype(jnp.bfloat16) for x in args[:3]] + args[3:]
    for use_kernel in (False, True):
        got = gated_delta.gated_delta_rule(*low, use_kernel=use_kernel)
        err = float(jnp.abs(got.astype(jnp.float32) - want).max()
                    / jnp.abs(want).max())
        assert 100 * TOL < err < 0.02, err


OPERAND_CASES = {
    # T and what `scan_case` makes of it, two value heads a key head
    "weak-decay": (128, dict(decay="mild")),
    "strong-decay": (128, dict(decay="far")),
    "repeated-keys": (128, dict(decay="mild", repeat=True)),
    "beta-near-0-and-1": (128, dict(decay="mild", beta_scale=12.0)),
    "ten-chunks-two-blocks-two-key-heads": (
        640, dict(decay="mild", hk=2, hv=4)),
}


@pytest.mark.parametrize("case", sorted(OPERAND_CASES))
def test_the_operand_kernels_are_chunk_operands(case):
    """The two chunk-operand kernels (interpret mode) against the XLA
    lowering of the same chunks and ITS gradient: W, U, Qg, Kd, P and
    exp(gamma_C), and dq, dk, dv, dg, dbeta under a random cotangent
    of each."""
    t, kind = OPERAND_CASES[case]
    args = scan_case(t, seed=3, **kind)
    want = jax.jit(gated_delta.chunk_operands)(*args)
    weights = [jnp.asarray(np.random.default_rng(5 + i).normal(size=w.shape),
                           jnp.float32) for i, w in enumerate(want)]

    def scalar(fn):
        return lambda *a: sum(jnp.sum(o * w)
                              for o, w in zip(fn(*a), weights))

    got = gated_delta.chunk_operands_kernel(*args)
    got_grads = jax.grad(scalar(gated_delta.chunk_operands_kernel),
                         argnums=range(5))(*args)
    want_grads = jax.jit(jax.grad(scalar(gated_delta.chunk_operands),
                                  argnums=range(5)))(*args)
    names = ("w", "u", "qg", "kd", "p", "dec", "dq", "dk", "dv", "dg",
             "dbeta")
    for name, a, b in zip(names, got + got_grads, want + want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=TOL * scale, err_msg=name)


def _flat_operands(q, k, v, g, beta):
    """What `chunk_operands_kernel` hands its kernels: q, k, v by lane
    block and the row tiles."""
    n, t, hk, d = k.shape
    x, _ = gated_delta._row_tiles(g, beta, hk)
    return (q.reshape(n, t, hk * d), k.reshape(n, t, hk * d),
            v.reshape(n, t, -1), x)


@pytest.mark.parametrize("case", sorted(OPERAND_CASES))
def test_the_inverse_kernel_is_every_chunks_inverse(case):
    """`gated_delta_inverse` (interpret mode) against numpy's float64
    inverse of I + strict_lower(diag(beta) (K K^T * G)), two heads side
    by side a key head; and the forward kernel GIVEN that inverse
    returns what the op's forward returns (it solves nothing)."""
    t, kind = OPERAND_CASES[case]
    args = scan_case(t, seed=3, **kind)
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in args)
    hk, hv, nc = k.shape[2], v.shape[2], t // 64
    flat = _flat_operands(*args)
    m = gated_delta.chunk_inverses(flat[1], flat[3])
    assert m.shape == (hk, t, 128) and m.dtype == jnp.float32
    got = np.asarray(m).reshape(hk, nc, 64, 2, 64)
    for h in range(hv):
        for c in range(nc):
            rows = slice(c * 64, (c + 1) * 64)
            gamma = np.cumsum(g[0, rows, h])
            kc = k[0, rows, h // 2]
            a = np.tril(beta[0, rows, h, None] * (kc @ kc.T)
                        * np.exp(gamma[:, None] - gamma[None, :]), -1)
            want = np.linalg.inv(np.eye(64) + a)
            np.testing.assert_allclose(
                got[h // 2, c, :, h % 2], want, rtol=0,
                atol=TOL * np.abs(want).max(), err_msg=f"head {h} chunk {c}")
    given = gated_delta._operands_fwd_call(*flat, m, interpreted=True)
    for a, b in zip(given, gated_delta.chunk_operands_kernel(*args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def raw_case(dtype, t=128, hk=2, hv=4, d=gated_delta.HEAD_DIM, seed=11):
    """QKV (1, T, (2 hk + hv) d) as a projection writes it (rows of any
    norm), the row tiles of a g and a beta, and what `gated_delta_rule`
    hands its chunk-operand kernels each way, `operands(qkv, raw)`:
    QKV alone where `raw` says where q, k AND v lie (what the op hands
    them), (QKV, QKV, v) where it leaves v out (the l2norm INSIDE the
    kernels, v cut out by XLA), and with None `head_norm_xla` first,
    then the kernels on unit q and k."""
    from paddle_tpu.ops.pallas import head_norm

    r = np.random.default_rng(seed)
    qkv = jnp.asarray(r.normal(size=(1, t, (2 * hk + hv) * d))
                      * np.exp(r.normal(size=(1, t, 1))), dtype)
    g = jnp.asarray(-0.05 * np.abs(r.normal(size=(1, t, hv))), jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-r.normal(size=(1, t, hv)))),
                       jnp.float32)
    raw = gated_delta.RawQK(q=0, k=hk * d, heads=hk, dim=d)

    def operands(qkv, raw):
        if raw is not None and raw.v is not None:
            return qkv
        v = qkv[..., 2 * hk * d:]
        if raw:
            return qkv, qkv, v
        form = head_norm.Form(0, hk * d)
        return (head_norm.head_norm_xla(
                    qkv[..., :hk * d], None, None,
                    form._replace(constant=d ** -0.5), d),
                head_norm.head_norm_xla(qkv[..., hk * d:2 * hk * d], None,
                                        None, form, d), v)

    return qkv, gated_delta._row_tiles(g, beta, hk)[0], raw, operands


INSIDE_TOL = {"float32": 1e-6, "bfloat16": 0.02}


@pytest.mark.parametrize("v", ["apart", "in_qkv"])
@pytest.mark.parametrize("dtype", sorted(INSIDE_TOL))
@pytest.mark.parametrize("kernel", ["inverse", "operands_fwd",
                                    "operands_bwd"])
def test_the_l2norm_inside_a_chunk_operand_kernel_is_head_norm_before_it(
        kernel, dtype, v):
    """Each chunk-operand kernel (interpret mode) on QKV as it lies, the
    l2norm of q and k taken inside (`RawQK`), against `head_norm_xla`
    followed by the same kernel on unit q and k: float32 to 1e-6 of the
    largest entry, bfloat16 within the scan's own 2 %.  The backward
    kernel through `operands_kernel`'s VJP: the gradients of the RAW QKV
    (q's and k's lanes through the l2norm's rule) and of the row tiles,
    under one cotangent of the five results.  `v`: cut out of QKV by
    XLA and handed over apart, or blocked out of QKV's lanes by the
    kernels (what the op does since PR 72: QKV is the one operand).
    Then the five results are the same TO THE BIT, and so is the one
    dQKV the backward kernel writes, each lane once, to the three
    gradients padded to QKV's width and added (that case is held to
    those alone: they are held to the unit operands' beside it);
    `gated_delta_flat_calls` counts the calls that took QKV whole and
    no other."""
    from paddle_tpu.observe.monitoring import runtime_stats

    qkv, x, apart, operands = raw_case(jnp.dtype(dtype))
    raw = apart if v == "apart" else apart._replace(v=2 * apart.k)
    tol = INSIDE_TOL[dtype]

    def same(got, want, names, tol=tol):
        for name, a, b in zip(names, got, want):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            a, b = (np.asarray(y.astype(jnp.float32)) for y in (a, b))
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=tol * np.abs(b).max(),
                                       err_msg=name)

    def three(raw):     # q's, k's and v's arrays, as a kernel call takes them
        given = operands(qkv, raw)
        return given if isinstance(given, tuple) else (given,) * 3

    unit = three(None)
    kept = gated_delta._inverse_call(unit[1], x, interpreted=True)
    if kernel == "inverse":
        same([gated_delta._inverse_call(three(raw)[1], x, raw=raw,
                                        interpreted=True)], [kept], "m")
        return
    if kernel == "operands_fwd":
        got = gated_delta._operands_fwd_call(*three(raw), x, kept, raw=raw,
                                             interpreted=True)
        names = ("w", "u", "qg", "kd", "p")
        if raw is apart:
            same(got, gated_delta._operands_fwd_call(
                *unit, x, kept, interpreted=True), names)
        else:
            same(got, gated_delta._operands_fwd_call(
                *three(apart), x, kept, raw=apart, interpreted=True),
                names, 0)
        return
    cts = [jnp.asarray(np.random.default_rng(5 + i).normal(size=shape), dtype)
           for i, shape in enumerate(
               [(4, 128, 128)] * 4 + [(4, 128, gated_delta.CHUNK)])]

    def results(way):
        def fn(qkv, x):
            return gated_delta.operands_kernel(operands(qkv, way), x, kept,
                                               way)
        return jax.vjp(fn, qkv, x)[1](tuple(cts))

    before = runtime_stats.snapshot()
    got = results(raw)
    took = runtime_stats.delta(before)
    # the forward rule's kernel and the backward kernel, 2 chunks x 4 heads
    assert took["gated_delta_operand_chunks"] == 16
    assert took["gated_delta_flat_calls"] == 2 * (v == "in_qkv")
    if raw is apart:
        same(got, results(None), ("dqkv", "dx"))
    else:
        same(got, results(apart), ("dqkv", "dx"), 0)
    for i, name in enumerate(("raw q", "raw k", "v", "v")):
        assert np.abs(np.asarray(got[0][..., i * 256:(i + 1) * 256]
                                 .astype(jnp.float32))).max() > 0, name


def test_the_scan_kernels_write_o_and_read_do_as_the_op_lays_them():
    """`scan_kernel` (interpret mode) returns o (N, T, Hv x 128), a
    value head's lanes a grid step, and takes its cotangent so: equal
    to `scan_xla`'s head-major (N Hv, T, 128) moved, forward and the six
    gradients, over two blocks of chunks and four heads of two batch
    rows."""
    n, t, hv, d = 2, 640, 2, gated_delta.HEAD_DIM
    q, k, v, g, beta = scan_case(t, "mild", seed=6, hk=1, hv=hv)
    both = lambda x: jnp.concatenate([x, x[:, ::-1]])  # noqa: E731
    operands = gated_delta.chunk_operands(*map(both, (q, k, v, g, beta)))
    ct = jnp.asarray(np.random.default_rng(8).normal(size=(n, t, hv * d)),
                     jnp.float32)

    def moved(*a):
        o = gated_delta.scan_xla(*a).reshape(n, hv, t, d)
        return jnp.moveaxis(o, 1, 2).reshape(n, t, hv * d)

    got, pull = jax.vjp(lambda *a: gated_delta.scan_kernel(*a, hv), *operands)
    want, pull_xla = jax.vjp(moved, *operands)
    assert got.shape == (n, t, hv * d)
    for name, a, b in zip(("o", "dw", "du", "dqg", "dkd", "dp", "ddec"),
                          (got,) + pull(ct), (want,) + pull_xla(ct)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=TOL * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("case", ["weak-decay", "repeated-keys",
                                  "ten-chunks-two-blocks-two-key-heads"])
def test_a_segment_keeps_the_inverse_and_gives_the_same_gradients(case):
    """The op inside a recompute segment (`jax.checkpoint` under the
    executor's `segment_policy`, traced as the executor traces a
    segment): loss and every gradient bit-equal to the op with no
    segment; one traced forward + backward records ONE inverse call,
    three chunk-operand calls (two forward: the segment's and the
    forward rule's; one backward) and the inverse's bytes among the
    kept residuals; and the differentiated segment holds the inverse
    kernel ONCE and `gated_delta_operands_fwd` twice, where
    `jax.checkpoint`'s default (the inputs alone) solves twice."""
    from test_recompute import _pallas_calls

    from paddle_tpu.observe.monitoring import runtime_stats
    from paddle_tpu.ops import pallas as pallas_tier

    t, kind = OPERAND_CASES[case]
    args = scan_case(t, seed=4, **kind)
    hk, hv = args[1].shape[2], args[2].shape[2]
    weight = jnp.asarray(np.random.default_rng(9).normal(
        size=(1, t, hv, gated_delta.HEAD_DIM)), jnp.float32)

    def loss(*a):
        return jnp.sum(gated_delta.gated_delta_rule(*a, use_kernel=True)
                       * weight)

    def in_segment(policy):
        def segment(*a):
            with pallas_tier.tracing_segment():
                return loss(*a)
        return jax.checkpoint(segment, policy=policy)

    both = lambda fn: jax.value_and_grad(fn, argnums=range(5))  # noqa: E731
    want = both(loss)(*args)
    before = runtime_stats.snapshot()
    kept = both(in_segment(pallas_tier.segment_policy()))
    got = kept(*args)
    took = runtime_stats.delta(before)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert took["gated_delta_inverse_calls"] == 1
    assert (took["gated_delta_operand_calls"],
            took["gated_delta_operand_chunks"]) == (3, 3 * hv * t // 64)
    assert (took["recompute_kept_residuals"],
            took["recompute_kept_bytes"]) == (1, hk * t * 128 * 4)

    def kernels(fn):
        found = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)["kernels"]
        return {name: found.count(name) for name in set(found)}

    assert kernels(kept) == {
        "gated_delta_inverse": 1, "gated_delta_operands_fwd": 2,
        "gated_delta_operands_bwd": 1, "gated_delta_fwd": 2,
        "gated_delta_bwd": 1}
    assert kernels(both(in_segment(None)))["gated_delta_inverse"] == 2


@pytest.mark.parametrize("diagonal", [8, 32, 64])
def test_the_substitution_at_every_size_of_its_diagonal_blocks(diagonal):
    """`_inverse_side_by_side` against numpy's inverse, two heads side
    by side: rows alone (64), and rows then one to three doublings."""
    r = np.random.default_rng(diagonal)
    a = np.tril(r.normal(size=(2, 64, 64)) * 0.3, -1).astype(np.float32)
    a[1, 20:50, :20] = 0.9          # repeated keys: a block of one value
    want = np.linalg.inv(np.eye(64) + a.astype(np.float64))
    got = gated_delta._inverse_side_by_side(
        jnp.asarray(np.concatenate(list(a), axis=1)),
        gated_delta._tile_iotas(), diagonal)
    np.testing.assert_allclose(got, np.concatenate(list(want), axis=1),
                               rtol=0, atol=2e-5 * np.abs(want).max())


def test_the_op_with_its_gates_is_the_sequential_recurrence():
    """The `gated_delta_rule` op as the cell runs it (heads of 128, two
    value heads a key head: all four kernels, interpret mode) against
    the recurrence a position at a time: the output and the gradient
    of every input, `ALog` and `DtBias` among them; T = 200 is three
    chunks and a padded tail."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    t, hk, hv, d = 200, 1, 2, gated_delta.HEAD_DIM
    r = np.random.default_rng(11)
    args = [jnp.asarray(x, jnp.float32) for x in (
        r.normal(size=(1, t, (2 * hk + hv) * d)),
        r.normal(size=(1, t, 2 * hv)), np.log(r.uniform(0.05, 2.0, size=hv)),
        r.normal(size=hv))]
    weight = jnp.asarray(r.normal(size=(1, t, hv * d)), jnp.float32)
    impl = get_op_impl("gated_delta_rule")

    def op(qkv, ba, a_log, dt_bias):
        return impl(OpContext(jax.random.PRNGKey(0), 0),
                    {"QKV": [qkv], "BA": [ba], "ALog": [a_log],
                     "DtBias": [dt_bias]},
                    {"n_key_head": hk, "n_value_head": hv, "key_dim": d,
                     "value_dim": d})["Out"][0]

    def recurrence(qkv, ba, a_log, dt_bias):
        def l2norm(x):
            x = x.reshape(1, t, hk, d)
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

        q = l2norm(qkv[..., :hk * d]) * d ** -0.5
        k = l2norm(qkv[..., hk * d:2 * hk * d])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        return sequential(q, k, qkv[..., 2 * hk * d:].reshape(1, t, hv, d),
                          g, jax.nn.sigmoid(ba[..., :hv])).reshape(weight.shape)

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    got = (op(*args),) + jax.grad(scalar(op), argnums=range(4))(*args)
    want = jax.jit(lambda *a: (recurrence(*a),) + jax.grad(
        scalar(recurrence), argnums=range(4))(*a))(*args)
    for name, a, b in zip(("o", "dqkv", "dba", "dA_log", "ddt_bias"),
                          got, want):
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=TOL * scale, err_msg=name)


def test_other_heads_keep_chunk_operands():
    """Heads of 128 with ONE or THREE value heads a key head run the
    scan's kernels on XLA's `chunk_operands`: the choice is the shape's
    (`operand_kernels_take`), and the counters say which ran."""
    from paddle_tpu.observe.monitoring import runtime_stats

    assert gated_delta.operand_kernels_take(16, 32, 128, 128)
    assert not gated_delta.operand_kernels_take(16, 16, 128, 128)
    assert not gated_delta.operand_kernels_take(16, 32, 64, 64)
    args = scan_case(128, "mild", hk=1, hv=1)
    before = runtime_stats.snapshot()
    got = gated_delta.gated_delta_rule(*args, use_kernel=True)
    took = runtime_stats.delta(before)
    assert (took["gated_delta_calls"], took["gated_delta_chunks"]) == (1, 2)
    assert (took["gated_delta_operand_calls"],
            took["gated_delta_operand_chunks"],
            took["gated_delta_inverse_calls"]) == (0, 0, 0)
    want = sequential(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=TOL * float(jnp.abs(want).max()))


def test_the_inverse_and_its_own_gradient():
    """(I + A)^-1 against numpy's, and its VJP (-M^T dM M^T) against
    the gradient of the series sum_k (-A)^k it stands for."""
    r = np.random.default_rng(2)
    a = np.tril(r.normal(size=(3, 64, 64)) * 0.2, -1).astype(np.float32)
    want = np.linalg.inv(np.eye(64) + a.astype(np.float64))
    got = gated_delta.unit_lower_inverse(jnp.asarray(a))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    ct = jnp.asarray(r.normal(size=a.shape), jnp.float32)

    def series(x):              # A is nilpotent: 64 terms are all
        term = total = jnp.broadcast_to(jnp.eye(64, dtype=x.dtype), x.shape)
        for _ in range(63):
            term = -term @ x
            total = total + term
        return total

    own = jax.grad(lambda x: jnp.sum(
        gated_delta.unit_lower_inverse(x) * ct))(jnp.asarray(a))
    plain = jax.grad(lambda x: jnp.sum(series(x) * ct))(jnp.asarray(a))
    np.testing.assert_allclose(own, plain, rtol=1e-4, atol=1e-4)


def test_the_kernels_take_heads_of_128_only():
    assert gated_delta.kernel_takes(128, 128)
    assert not gated_delta.kernel_takes(16, 16)
    with pytest.raises(NotImplementedError, match="heads of 128"):
        gated_delta.gated_delta_rule(*scan_case(64, "mild", d=16),
                                     use_kernel=True)
    with pytest.raises(ValueError, match="key heads"):
        gated_delta.gated_delta_rule(*scan_case(64, "mild", hk=2, hv=3))


# -- (c) the share test -----------------------------------------------------

E, RANKS, K, D, H, HS, T = 16, 4, 3, 64, 32, 24, 40
HELD = E // RANKS


def whole_block(seed=0):
    r = np.random.default_rng(seed)

    def draw(*shape, scale=0.3):
        return jnp.asarray(r.normal(size=shape).astype(np.float32) * scale)

    return {"x": draw(T, D, scale=1.0), "router": draw(D, E, scale=0.25),
            "w1": draw(E, D, H), "w3": draw(E, D, H), "w2": draw(E, H, D),
            "shared_w1": draw(D, HS), "shared_w3": draw(D, HS),
            "shared_w2": draw(HS, D), "shared_gate": draw(D, 1)}


def routed_part(p, rank):
    """One rank's routed part, through the op the builder appends."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    lo = rank * HELD
    o = get_op_impl("moe_dropless")(
        OpContext(jax.random.PRNGKey(0), 0),
        {"X": [p["x"]], "GateW": [p["router"]],
         **{k.upper(): [p[k][lo:lo + HELD]] for k in ("w1", "w3", "w2")}},
        {"routing": "softmax", "norm_topk_prob": True, "top_k": K,
         "experts_held": [lo, HELD]})
    return o["Out"][0], o["Counts"][0]


def gated_shared_expert(p):
    """The shared expert as the builder composes it: `mul`, `mul`,
    `swiglu`, `mul`, times `sigmoid` of a 1-wide `mul`."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    ctx = OpContext(jax.random.PRNGKey(0), 0)

    def op(kind, **ins):
        return get_op_impl(kind)(ctx, {k: [v] for k, v in ins.items()},
                                 {"axis": -1})["Out"][0]

    hidden = op("swiglu", X=op("mul", X=p["x"], Y=p["shared_w1"]),
                Y=op("mul", X=p["x"], Y=p["shared_w3"]))
    gate = op("sigmoid", X=op("mul", X=p["x"], Y=p["shared_gate"]))
    return op("elementwise_mul", X=op("mul", X=hidden, Y=p["shared_w2"]),
              Y=gate)


def test_all_shares_and_the_shared_expert_once_add_up_to_the_uncut_block():
    """The `model-configs` guide's tie of the share to the model: the
    routed parts of all 16 / 4 = 4 shares of the preset plus the gated
    shared expert COUNTED ONCE are the uncut reference's sparse block;
    summed as each rank adds it, the shared expert counts four times."""
    p = whole_block()
    cfg = {"num_experts_per_tok": K, "norm_topk_prob": True}
    with jax.default_matmul_precision("highest"):
        want, counts, _ = ref.experts(p["x"], p, cfg)
    parts = [routed_part(p, r) for r in range(RANKS)]
    shared = gated_shared_expert(p)
    total = sum(np.asarray(y, np.float64) for y, _ in parts) \
        + np.asarray(shared, np.float64)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c) for _, c in parts]),
        np.asarray(counts))
    assert sum(int(c.sum()) for _, c in parts) == T * K
    streams = sum(np.asarray(y + shared, np.float64) for y, _ in parts)
    np.testing.assert_allclose(
        streams, np.asarray(want) + (RANKS - 1) * np.asarray(shared),
        rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(shared)).max() > 0.1
    # one rank's share of the reference is that rank's part and the
    # shared expert whole
    held = dict(p, **{k: p[k][HELD:2 * HELD] for k in ("w1", "w3", "w2")})
    with jax.default_matmul_precision("highest"):
        one, _, _ = ref.experts(p["x"], held,
                                dict(cfg, expert_parallel_rank=1))
    np.testing.assert_allclose(np.asarray(parts[1][0] + shared), one,
                               rtol=2e-5, atol=2e-5)


# -- (d) the zero-centred norm ----------------------------------------------

def test_a_zero_centred_scale_is_rms_norm_with_one_plus_w():
    from op_test import run_op

    r = np.random.default_rng(5)
    x = r.normal(size=(2, 6, 32)).astype(np.float32)
    w = (0.2 * r.normal(size=(32,))).astype(np.float32)
    gate = r.normal(size=(2, 6, 32)).astype(np.float32)
    plain = run_op("rms_norm", {"X": x, "Scale": 1 + w}, {"epsilon": 1e-6},
                   out_slot="Y")
    zero = run_op("rms_norm", {"X": x, "Scale": w},
                  {"epsilon": 1e-6, "zero_centered": True}, out_slot="Y")
    np.testing.assert_allclose(zero, plain, rtol=1e-6, atol=1e-6)
    # a head at a time (16 lanes under one (16,) scale), gated
    heads = run_op("rms_norm", {"X": x, "Scale": w[:16], "Gate": gate},
                   {"epsilon": 1e-6, "group_size": 16,
                    "zero_centered": True}, out_slot="Y")
    x4 = x.reshape(2, 6, 2, 16)
    want = (x4 / np.sqrt((x4 ** 2).mean(-1, keepdims=True) + 1e-6)
            * (1 + w[:16])).reshape(x.shape) * gate / (1 + np.exp(-gate))
    np.testing.assert_allclose(heads, want, rtol=2e-6, atol=2e-6)


def test_weight_decay_pulls_a_zero_centred_scale_to_zero_and_the_norm_to_one():
    """The layer's parameter starts at 0, and a step of pure decay
    (no gradient reaches it: its output is not in the loss) moves it
    TOWARD 0: the scale it stands for goes to 1, where a plain scale
    would go to 0."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.rms_norm(x, epsilon=1e-6, zero_centered=True)
        loss = fluid.layers.mean(fluid.layers.scale(y, scale=0.0))
        fluid.optimizer.AdamOptimizer(learning_rate=0.1,
                                      weight_decay=0.5).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (name,) = [p.name for p in main.all_parameters()]
        assert not np.asarray(scope.find_var(name)).any()     # from 0
        start = np.linspace(-0.4, 0.4, 8).astype(np.float32)
        scope.set_var(name, jnp.asarray(start))
        exe.run(main, feed={"x": np.ones((2, 8), np.float32)}, scope=scope,
                fetch_list=[loss])
        after = np.asarray(scope.find_var(name))
    np.testing.assert_allclose(after, start * (1 - 0.1 * 0.5), rtol=1e-5,
                               atol=1e-7)
    assert (np.abs(after) <= np.abs(start)).all()


def test_rope_over_a_part_of_the_head_leaves_the_rest():
    from op_test import run_op

    x = np.random.default_rng(3).normal(size=(1, 6, 64)).astype(np.float32)
    part = run_op("rope", {"X": x}, {"n_head": 2, "theta": 100.0,
                                     "rotary_dim": 8})
    x4, p4 = x.reshape(1, 6, 2, 32), np.asarray(part).reshape(1, 6, 2, 32)
    np.testing.assert_array_equal(p4[..., 8:], x4[..., 8:])
    small = run_op("rope", {"X": x4[..., :8].reshape(1, 6, 16)},
                   {"n_head": 2, "theta": 100.0})
    np.testing.assert_allclose(p4[..., :8].reshape(1, 6, 16), small,
                               rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="rotary_dim"):
        run_op("rope", {"X": x}, {"n_head": 2, "rotary_dim": 7})


def test_the_ungated_convolution_is_the_gated_ones_convolution():
    from op_test import run_op

    r = np.random.default_rng(4)
    x = r.normal(size=(2, 9, 6)).astype(np.float32)
    w = r.normal(size=(6, 4)).astype(np.float32)
    got = run_op("short_conv", {"X": x, "Filter": w}, {"activation": "silu"})
    want = np.asarray(ref.causal_conv(jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_allclose(got, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-6)
    ones = np.ones_like(x)
    gated = run_op("short_conv",
                   {"X": np.concatenate([ones, ones, x], -1), "Filter": w}, {})
    np.testing.assert_allclose(gated, want, rtol=1e-5, atol=1e-6)
    with pytest.raises(NotImplementedError, match="activation"):
        run_op("short_conv", {"X": x, "Filter": w}, {"activation": "gelu"})


# -- (e) every new key's unbuilt values raise -------------------------------

@pytest.mark.parametrize("over,error,match", [
    (dict(attention_gate="tanh"), NotImplementedError, "attention_gate"),
    (dict(shared_expert_gate="softmax"), NotImplementedError,
     "shared_expert_gate"),
    (dict(n_shared_experts=1), ValueError, "given twice"),
    (dict(partial_rotary_factor=0.0), ValueError, "partial_rotary_factor"),
    (dict(partial_rotary_factor=1.5), ValueError, "partial_rotary_factor"),
    (dict(partial_rotary_factor=0.25, head_dim=20), ValueError,
     "whole number of pairs"),
    # (YaRN over a part of the head is built since PR 51; a scaling
    # `ops/decoder.py rope_frequencies` does not know still raises)
    (dict(rope_parameters={"rope_type": "llama3", "rope_theta": 100.0,
                           "factor": 4.0,
                           "original_max_position_embeddings": 16}),
     NotImplementedError, "llama3"),
    (dict(linear_conv_kernel_dim=None), ValueError, "linear_conv_kernel_dim"),
    (dict(linear_num_value_heads=3), ValueError, "multiple"),
    (dict(layer_types=["linear_attention", "state_space"]),
     NotImplementedError, "state_space"),
    (dict(attention_gate="sigmoid", kv_lora_rank=8, q_lora_rank=8,
          qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
          num_key_value_heads=2), NotImplementedError, "latent"),
], ids=["attention_gate", "shared_expert_gate", "shared-width-twice",
        "rotary-0", "rotary-over-1", "rotary-odd", "rotary-part-llama3",
        "linear-keys-missing", "linear-heads", "layer-type", "gate-on-latent"])
def test_unbuilt_values_of_the_new_keys_raise(over, error, match):
    cfg = config(**over)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        with pytest.raises(error, match=match):
            decoder.build_model(max_length=16, with_optimizer=False, **NO_AUX,
                                **cfg)
