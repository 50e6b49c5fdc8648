"""The window / full attention MoE decoder on the normal path
(`models/decoder.py` with `head_dim` a key of its own, `layer_types`
holding `sliding_attention`, `rope_parameters` by layer type with YaRN
on the full layers, `qk_norm="head"`, the soft-max router with
`norm_topk_prob` over a held share; the Pallas band kernels in
interpret mode) against its plain float32 reference
(`benchmarks/reference_mellum.py`) on the CPU at a small size, seeded
random weights: logits, the loss, every token's experts, the held
experts' counts and the gradient of every parameter.

Sizes: d 64, 4 query heads over 2 key/value heads of 16 (so
`head_dim` x heads = 64 is the hidden size only by accident of the
numbers: q is 64 -> 64, k and v 64 -> 32, and `hidden_size // heads`
is never read), W 8, T 48, 8 experts 2 a token, one layer of each kind
(sliding, full): the shallowest toy that has both.  The builder takes
the kinds as a list and computes no period, so the published pattern
(sliding x 3, full) is the same two mechanisms at twice the build; the
eight-layer cut at the published widths is `benchmarks/mellum_parity.py`'s,
on the chip.

Tolerance.  Float32: both sides are float32 with matmuls at "highest"
and differ in summation order only (the flash kernels' online
soft-max, the sorted expert rows): 5e-6 absolute-or-relative, as
tests/test_lfm2_parity.py (largest seen here 1.1e-6).  bf16 AMP: logits
within 0.05 of the reference (seen 0.012; they are O(1)) and a gradient
leaf within 0.15 of its norm (seen 0.03-0.06) on a case whose routing
does not flip, and it must MISS the float32 tolerance by 20 x.
"""

import functools
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import decoder
from paddle_tpu.ops.decoder import rope_frequencies

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import reference_mellum as ref  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import (Family, build_and_run, close,  # noqa: E402
                            reference, system)

TOL = 5e-6
NO_AUX = dict(aux_loss_weight=0.0, z_loss_weight=0.0)
PUBLISHED_ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                       "factor": 16,
                       "original_max_position_embeddings": 8192,
                       "beta_fast": 32, "beta_slow": 1,
                       "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
SHARES = {"whole-layer": dict(num_experts=8),
          "rank-1-of-4": dict(num_experts=2, expert_parallel_size=4,
                              expert_parallel_rank=1)}
def config(**over):
    cfg = dict(qk_norm="head", router="softmax", hidden_size=64,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, norm_topk_prob=True,
               layer_types=["sliding_attention", "full_attention"],
               sliding_window=8, rms_norm_eps=1e-6,
               # YaRN at a size where it does something within 48
               # positions: the ramp runs over dimensions 1..4 of 8
               rope_parameters={
                   "full_attention": {
                       "rope_type": "yarn", "rope_theta": 100.0,
                       "factor": 4.0,
                       "original_max_position_embeddings": 16,
                       "beta_fast": 2.0, "beta_slow": 0.25,
                       "attention_factor": 1.2},
                   "sliding_attention": {"rope_type": "default",
                                         "rope_theta": 100.0}},
               vocab_size=96)
    cfg.update(over)
    return cfg


def arguments(cfg, **build):
    return dict(cfg, **NO_AUX, **build)


FAMILY = Family(ref.params_from_list, ref.loss_and_grads,
                lambda grads, cfg: ref.flat_leaves(grads))
batch = functools.partial(harness.batch, length=48)


@pytest.mark.parametrize("recompute", [None, "layer"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_program_matches_the_float32_reference(share, recompute):
    cfg = config(**SHARES[share])
    feed = batch(cfg)
    got, params = system(arguments(cfg, recompute=recompute), feed)
    total, parts, grads = reference(FAMILY, cfg, feed, params)
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    assert len(got["counts"]) == 2
    for i in range(2):
        np.testing.assert_array_equal(got["counts"][i],
                                      np.asarray(parts["counts"][i]))
        np.testing.assert_array_equal(
            np.sort(got["experts"][i], axis=-1),
            np.sort(np.asarray(parts["experts"][i]), axis=-1))
    names = ref.leaf_names(cfg)
    assert len(got["grads"]) == len(grads) == len(params) == len(names)
    for name, g, w in zip(names, got["grads"], grads):
        # no vacuous match, but for a share's router (held constant
        # by the builder on both sides: no exchange sums the ranks')
        routerless = share != "whole-layer" and name.endswith(".router")
        assert (np.abs(np.asarray(w)).max() > 0) != routerless, name
        close(g, w, f"gradient of {name}")
    # head_dim x heads is what sizes the projections, not hidden_size
    shapes = [p.shape for p in params[2:8]]
    assert shapes == [(64, 64), (16,), (64, 32), (16,), (64, 32), (64, 64)]


def test_head_dim_beside_hidden_size_sizes_the_four_projections():
    """`head_dim` 24 x 4 heads = 96 beside `hidden_size` 64: q 64 -> 96,
    k, v 64 -> 48, o 96 -> 64; and the numbers still match."""
    cfg = config(head_dim=24)
    feed = batch(cfg, n=1)
    got, params = system(arguments(cfg), feed)
    assert [p.shape for p in params[2:8]] == [
        (64, 96), (24,), (64, 48), (24,), (64, 48), (96, 64)]
    total, parts, grads = reference(FAMILY, cfg, feed, params)
    close(got["logits"], parts["logits"], "logits")
    for name, g, w in zip(ref.leaf_names(cfg), got["grads"], grads):
        close(g, w, f"gradient of {name}")


def test_a_window_that_holds_every_key_is_full_attention_bit_for_bit():
    """T <= W: a `sliding_attention` layer IS a `full_attention` layer
    (under the same RoPE): the same kernels, the same bits."""
    same_rope = {"rope_type": "default", "rope_theta": 100.0}
    rope = {"full_attention": same_rope, "sliding_attention": same_rope}
    feed = batch(config(), length=32)
    a, _ = system(arguments(config(sliding_window=32, rope_parameters=rope)),
                  feed)
    b, _ = system(arguments(config(rope_parameters=rope,
                                   layer_types=["full_attention"] * 2)), feed)
    c, _ = system(arguments(config(sliding_window=31, rope_parameters=rope)),
                  feed)
    np.testing.assert_array_equal(a["logits"], b["logits"])
    for g, w in zip(a["grads"], b["grads"]):
        np.testing.assert_array_equal(g, w)
    assert (np.asarray(a["logits"]) != np.asarray(c["logits"])).any()


@pytest.mark.parametrize("window", [5, 16, 20])
def test_a_window_that_is_not_a_multiple_of_the_block(window):
    """Blocks of 16 x 16 over 48 positions: a window under a block, of
    one block, and of one and a quarter."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    cfg = config(sliding_window=window)
    feed = batch(cfg, n=1)
    blocks = (fa.DEFAULT_BAND_BLOCK_Q, fa.DEFAULT_BAND_BLOCK_K,
              fa.DEFAULT_WINDOW_BWD_BLOCK_Q, fa.DEFAULT_WINDOW_BWD_BLOCK_K)
    fa.DEFAULT_BAND_BLOCK_Q = fa.DEFAULT_BAND_BLOCK_K = 16
    fa.DEFAULT_WINDOW_BWD_BLOCK_Q = fa.DEFAULT_WINDOW_BWD_BLOCK_K = 16
    try:            # constants of a module are no part of a key
        got, params = build_and_run(arguments(cfg), feed)
    finally:
        (fa.DEFAULT_BAND_BLOCK_Q, fa.DEFAULT_BAND_BLOCK_K,
         fa.DEFAULT_WINDOW_BWD_BLOCK_Q, fa.DEFAULT_WINDOW_BWD_BLOCK_K) = blocks
    total, parts, grads = reference(FAMILY, cfg, feed, params)
    close(got["logits"], parts["logits"], "logits")
    for name, g, w in zip(ref.leaf_names(cfg), got["grads"], grads):
        close(g, w, f"gradient of {name}")


def test_the_reference_in_blocks_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/mellum_parity.py` runs on the chip so that 16384
    positions fit: scores `q_block` rows at a time (a window layer's
    block reading only the keys that can be allowed), every layer
    recomputed in its backward pass.  Same numbers."""
    cfg = config(**SHARES["rank-1-of-4"])
    feed = batch(cfg)
    _, params = system(arguments(cfg), feed)
    plain, _, want = reference(FAMILY, cfg, feed, params)
    blocked, _, got = reference(FAMILY, cfg, feed, params, q_block=12)
    close(blocked, plain, "loss")
    for w, g in zip(want, got):
        close(g, w, "gradient")


def test_yarn_constants_at_the_published_keys():
    """`low` 18 and `high` 35 of 64 frequencies at theta 5e5, 8192
    original positions, beta 32 / 1; the frequencies at 0 and 18 are
    theta^(-2i/128) as they are, at 35 and 63 divided by 16, between
    blended; the builder's and the reference's constants are the same
    numbers, each computed on its own."""
    group = PUBLISHED_ROPE["full_attention"]
    assert ref.yarn_range(group, 128) == (18, 35)
    # c(r) = D ln(orig / (2 pi r)) / (2 ln theta), by hand
    c = lambda r: 128 * np.log(8192 / (2 * np.pi * r)) / (2 * np.log(5e5))
    assert abs(c(32) - 18.08) < 0.01 and abs(c(1) - 34.98) < 0.01
    inv_freq, scale = ref.rope_inv_freq(group, 128)
    extra = lambda i: 5e5 ** (-2.0 * i / 128)
    np.testing.assert_allclose(
        inv_freq[[0, 18, 35, 63]],
        [1.0, extra(18), extra(35) / 16, extra(63) / 16], rtol=1e-12)
    # dimension 26: ramp (26 - 18) / 17
    r = 8 / 17
    np.testing.assert_allclose(inv_freq[26],
                               extra(26) / 16 * r + extra(26) * (1 - r),
                               rtol=1e-12)
    assert scale == 1.2772588722239782
    assert abs(scale - (0.1 * np.log(16) + 1)) < 1e-12
    built, built_scale = rope_frequencies(128, **group)
    np.testing.assert_allclose(built, inv_freq, rtol=1e-12)
    assert built_scale == scale
    plain, one = rope_frequencies(
        128, **PUBLISHED_ROPE["sliding_attention"])
    np.testing.assert_allclose(plain, [extra(i) for i in range(64)],
                               rtol=1e-12)
    assert one == 1.0


def test_a_score_carries_the_attention_factor_squared():
    """cos and sin of q AND k are scaled, so q . k grows by its square
    and the rotation is otherwise the unscaled one's."""
    from op_test import run_op

    x = np.random.default_rng(3).normal(size=(1, 6, 32)).astype(np.float32)
    inv_freq, _ = rope_frequencies(16, rope_theta=100.0)
    plain = run_op("rope", {"X": x}, {"n_head": 2, "theta": 100.0})
    scaled = run_op("rope", {"X": x},
                    {"n_head": 2, "inv_freq": list(inv_freq),
                     "attention_factor": 1.2})
    np.testing.assert_allclose(scaled, 1.2 * plain, rtol=1e-6, atol=1e-6)
    score = lambda r: np.einsum("ntd,nsd->nts", r[..., :16], r[..., :16])
    np.testing.assert_allclose(score(scaled), 1.44 * score(plain),
                               rtol=1e-5, atol=1e-5)


def test_bf16_amp_stays_in_its_band_and_fails_the_float32_tolerance():
    cfg = config(**SHARES["rank-1-of-4"])
    feed = batch(cfg)
    got, params = system(arguments(cfg, recompute="layer"), feed,
                         use_amp=True)
    _, parts, grads = reference(FAMILY, cfg, feed, params)
    same = all(
        (np.sort(e, axis=-1) == np.sort(np.asarray(w), axis=-1)).all(-1).all()
        for e, w in zip(got["experts"], parts["experts"]))
    err = np.abs(np.asarray(got["logits"], np.float32)
                 - np.asarray(parts["logits"]))
    assert err.max() > 20 * TOL, err.max()
    if same:       # a flipped choice is another function, not an error
        assert err.max() < 0.05, err.max()
        for name, g, w in zip(ref.leaf_names(cfg), got["grads"], grads):
            w = np.asarray(w)
            if np.abs(w).max() == 0:
                continue
            rel = (np.linalg.norm(np.asarray(g, np.float32).reshape(w.shape)
                                  - w) / np.linalg.norm(w))
            assert rel < 0.15, (name, rel)


def test_one_adamw_step_is_the_hand_rolled_one():
    """The whole training Program (AdamW with decoupled decay, clip,
    schedule; AMP off) moves every leaf as the reference's gradient
    says: p - lr (m / (sqrt(v) + eps) + decay p) after one step."""
    cfg = config(**SHARES["rank-1-of-4"])
    feed = batch(cfg)
    lr, b1, b2, eps, decay, clip = 3e-3, 0.9, 0.95, 1e-8, 0.1, 1.0
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = decoder.build_model(max_length=48, learning_rate=lr,
                                warmup_steps=1, use_amp=False, **NO_AUX,
                                **cfg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        names = [p.name for p in main.all_parameters()]
        before = [np.asarray(scope.find_var(n)).copy() for n in names]
        exe.run(main, feed=feed, scope=scope, fetch_list=[m["loss"]])
        after = [np.asarray(scope.find_var(n)) for n in names]
    _, _, grads = reference(FAMILY, cfg, feed, before)
    grads = [np.asarray(g) for g in grads]
    norm = np.sqrt(sum(float((g.astype(np.float64) ** 2).sum())
                       for g in grads))
    # the schedule's first step: warm-up 1 step, cosine barely begun
    lr_now = lr
    step = lr_now * np.sqrt(1 - b2) / (1 - b1)
    for name, p, q, g in zip(ref.leaf_names(cfg), before, after, grads):
        g = g.reshape(p.shape) * clip / max(norm, clip)
        want = (p - step * (1 - b1) * g / (np.sqrt((1 - b2) * g * g) + eps)
                - lr_now * decay * p)
        # a first Adam step is lr * g / (|g| + eps'): where |g| is
        # eps' itself a float32 rounding of g moves it
        firm = np.abs(g) > 1e-5
        assert firm.any() or name.endswith("router"), name
        np.testing.assert_allclose(q[firm], want[firm], rtol=2e-5,
                                   atol=2e-7, err_msg=name)
        np.testing.assert_allclose(q, want, atol=1.01 * lr_now,
                                   err_msg=name)


def test_the_two_kinds_of_layer_lower_under_scopes_of_their_own():
    """`sliding_attention` / `full_attention` name scopes around a
    layer's attention operator, ONLY in a program that has a window
    layer; the backward pass counts a single kernel a layer."""
    from paddle_tpu.observe.monitoring import runtime_stats

    cfg = config()
    feed = batch(cfg, n=1)
    before = runtime_stats.snapshot()
    got, _ = build_and_run(arguments(cfg), feed)
    took = runtime_stats.delta(before)
    assert took["flash_attention_backward_fused"] == 2
    assert took["flash_attention_backward_split"] == 0
    assert took["flash_window_blocks_visited"] \
        >= took["flash_window_blocks_allowed"] > 0
    def scopes(main):
        return [op.attrs.get("__name_scope__", "") for b in main.blocks
                for op in b.ops]

    found = scopes(got["main"])
    assert sum(s == "sliding_attention" for s in found) \
        == sum(s == "full_attention" for s in found) > 0
    plain, _ = system(arguments(config(layer_types=["full_attention"] * 2)),
                      feed)
    assert "full_attention" not in scopes(plain["main"])
