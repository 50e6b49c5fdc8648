"""The window / full attention MoE decoder whose head count follows the
layer type, on the normal path (`models/decoder.py` with
`num_attention_heads_per_layer`, a `partial_rotary_factor` a
`rope_parameters` group with YaRN over a part of the head,
`attention_gate="head"`, `mlp_layer_types` with a leading dense layer,
a shared expert beside a held share of soft-max-routed experts whose
sum carries `routed_scaling_factor`; the Pallas band kernels in
interpret mode) against its plain float32 reference
(`benchmarks/reference_laguna.py`) on the CPU at a small size, seeded
random weights: logits, the loss, every token's experts, the held
experts' counts, the gradient of every parameter and one AdamW step.

The preset has every mechanism at the shallowest depth that holds them:
2 layers [full-dense, sliding-sparse] (the cell's [full-dense, sliding x
3, full] is the same mechanisms at more than twice the build;
`benchmarks/laguna_parity.py` runs it at the published widths on the
chip), query heads [6, 8] over 2 key/value heads of 16 (groups
of 3 and of 4), a window of 8 at length 64, YaRN over HALF the head on
the full layers (8 of 16 lanes: 4 frequencies, the ramp over
dimensions 0..3) and a plain RoPE over the whole head on the sliding
ones, the head gate, 16 experts of which 4 are held, 2 a token, the
routed sum x 2.5, a shared expert.

Tolerance.  Float32: both sides are float32 with matmuls at "highest"
and differ in summation order only (the flash kernels' online
soft-max, the sorted expert rows): 5e-6 absolute-or-relative, as
tests/test_mellum_parity.py (largest seen here 2e-8 on a gradient,
1e-6 on a logit).
"""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.models import decoder
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.decoder import rope_frequencies
from paddle_tpu.ops.pallas import flash_attention as fa

from op_test import run_op

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import reference_laguna as ref  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import Family, close, reference, system  # noqa: E402

TOL = 5e-6
NO_AUX = dict(aux_loss_weight=0.0, z_loss_weight=0.0)
# the builder's arguments no key spells (benchmarks/models/laguna.py)
EQUATIONS = dict(qk_norm="head", router="softmax", norm_topk_prob=True,
                 attention_gate="head")
PUBLISHED_ROPE = {
    "full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                       "factor": 64,
                       "original_max_position_embeddings": 4096,
                       "beta_slow": 1, "beta_fast": 64,
                       "attention_factor": 1.4158883083359672,
                       "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
    "original_max_position_embeddings": 4096}
SHARES = {"whole-layer": dict(num_experts=16),
          "rank-1-of-4": dict(num_experts=4, expert_parallel_size=4,
                              expert_parallel_rank=1)}
LENGTH = 64


def config(**over):
    """The configuration's own keys, as the reference reads them."""
    cfg = dict(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=6,
        num_attention_heads_per_layer=[6, 8],
        num_key_value_heads=2, head_dim=16,
        layer_types=["full_attention", "sliding_attention"],
        mlp_layer_types=["dense", "sparse"], sliding_window=8,
        partial_rotary_factor=0.5, intermediate_size=96,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=16, num_experts_per_tok=2,
        moe_routed_scaling_factor=2.5, rms_norm_eps=1e-6, vocab_size=96,
        # YaRN at a size where it does something within 64 positions:
        # over 8 rotary lanes the ramp runs over dimensions 0..3 of 4
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 100.0, "factor": 4.0,
                "original_max_position_embeddings": 16, "beta_fast": 2.0,
                "beta_slow": 0.25, "attention_factor": 1.2,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 50.0,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 16})
    cfg.update(over)
    return cfg


def arguments(cfg):
    """The builder's arguments of a configuration."""
    args = dict(cfg, **EQUATIONS)
    args["routed_scaling_factor"] = args.pop("moe_routed_scaling_factor")
    return args


def build_arguments(cfg, **build):
    return dict(arguments(cfg), **NO_AUX, **build)


FAMILY = Family(ref.params_from_list, ref.loss_and_grads, ref.flat_leaves)
batch = functools.partial(harness.batch, length=LENGTH)


# -- (a) the program against the reference ---------------------------------

@pytest.mark.parametrize("recompute", [None, "layer"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_program_matches_the_float32_reference(share, recompute):
    cfg = config(**SHARES[share])
    feed = batch(cfg)
    got, params = system(build_arguments(cfg, recompute=recompute), feed)
    took = got["took"]
    total, parts, grads = reference(FAMILY, cfg, feed, params)
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    assert len(got["counts"]) == 1                  # the sparse layer
    np.testing.assert_array_equal(got["counts"][0],
                                  np.asarray(parts["counts"][0]))
    np.testing.assert_array_equal(
        np.sort(got["experts"][0], axis=-1),
        np.sort(np.asarray(parts["experts"][0]), axis=-1))
    names = ref.leaf_names(cfg)
    assert len(got["grads"]) == len(grads) == len(params) == len(names)
    for name, g, w in zip(names, got["grads"], grads):
        # no vacuous match, but for a share's router (held constant
        # by the builder on both sides: no exchange sums the ranks')
        routerless = share != "whole-layer" and name.endswith(".router")
        assert (np.abs(np.asarray(w)).max() > 0) != routerless, name
        close(g, w, f"gradient of {name}")
    # q, o and the gate take the LAYER's heads: 6 x 16 on the full
    # layer, 8 x 16 on the sliding one, over the same 2 x 16 of k, v
    shapes = {n: p.shape for n, p in zip(names, params)}
    for i, heads in enumerate(cfg["num_attention_heads_per_layer"]):
        assert shapes[f"layer{i}.wq"] == (64, heads * 16)
        assert shapes[f"layer{i}.wk"] == shapes[f"layer{i}.wv"] == (64, 32)
        assert shapes[f"layer{i}.wg"] == (64, heads)
        assert shapes[f"layer{i}.wo"] == (heads * 16, 64)
    assert shapes["layer0.w1"] == (64, 96)          # the dense layer
    assert shapes["layer1.shared_w1"] == (64, 32)
    assert shapes["layer1.router"] == (64, 16)
    # one gate a layer; the window layer's and the full layer's forward
    # kernels, traced at the build's shape inference and in the step
    assert took["attention_head_gate_calls"] == 2
    assert took["flash_window_calls"] == took["flash_grouped_calls"] > 0
    assert took["flash_attention_backward_fused"] == 2


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
        m = decoder.build_model(max_length=LENGTH, learning_rate=lr,
                                warmup_steps=1, use_amp=False, **NO_AUX,
                                **arguments(cfg))
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
    step = lr * np.sqrt(1 - b2) / (1 - b1)
    for name, p, q, g in zip(ref.leaf_names(cfg), before, after, grads):
        g = g.reshape(p.shape) * clip / max(norm, clip)
        want = (p - step * (1 - b1) * g / (np.sqrt((1 - b2) * g * g) + eps)
                - lr * decay * p)
        # a first Adam step is lr * g / (|g| + eps'): where |g| is
        # eps' itself a float32 rounding of g moves it
        firm = np.abs(g) > 1e-5
        assert firm.any() or name.endswith("router"), name
        np.testing.assert_allclose(q[firm], want[firm], rtol=2e-5,
                                   atol=2e-7, err_msg=name)
        np.testing.assert_allclose(q, want, atol=1.01 * lr, err_msg=name)


def test_the_reference_in_blocks_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/laguna_parity.py` runs on the chip so that 16384
    positions fit: scores `q_block` rows at a time, every layer
    recomputed in its backward pass.  Same numbers."""
    cfg = config(**SHARES["rank-1-of-4"])
    feed = batch(cfg)
    _, params = system(build_arguments(cfg), feed)
    plain, _, want = reference(FAMILY, cfg, feed, params)
    blocked, _, got = reference(FAMILY, cfg, feed, params, q_block=16)
    close(blocked, plain, "loss")
    for w, g in zip(want, got):
        close(g, w, "gradient")


def test_the_gate_and_the_scopes_are_in_the_program():
    """`attention_head_gate` inside `sliding_attention` /
    `full_attention`, one a layer; without the gate no such scope and
    no gate parameter."""
    cfg = config()
    feed = batch(cfg, n=1)
    got, params = system(build_arguments(cfg), feed)

    def scopes(main):
        return [op.attrs.get("__name_scope__", "") for b in main.blocks
                for op in b.ops]

    found = scopes(got["main"])
    gated = [s for s in found if s.endswith("attention_head_gate")]
    assert {s.split("/")[0] for s in gated} == {"sliding_attention",
                                                "full_attention"}
    assert sum(s.startswith("sliding_attention") for s in gated) \
        == sum(s.startswith("full_attention") for s in gated)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        decoder.build_model(max_length=LENGTH, with_optimizer=False,
                            **NO_AUX, **dict(arguments(cfg),
                                             attention_gate=None))
    assert not [s for s in scopes(main) if "attention_head_gate" in s]
    assert len(main.all_parameters()) == len(params) - 2   # a gate a layer


def test_bf16_amp_stays_in_its_band_and_fails_the_float32_tolerance():
    cfg = config(**SHARES["rank-1-of-4"])
    feed = batch(cfg)
    got, params = system(build_arguments(cfg, recompute="layer"), feed,
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
            assert rel < 0.2, (name, rel)


# -- (b) the band kernels at the two geometries -----------------------------

T, D, HKV, BLOCK = 64, 8, 8, 16


def _dense(q, k, v, heads, window):
    """Soft-max attention under the mask written out, key/value heads
    repeated."""
    n, t, _ = q.shape
    q4 = q.reshape(n, t, heads, D)
    k4 = jnp.repeat(k.reshape(n, t, HKV, D), heads // HKV, axis=2)
    v4 = jnp.repeat(v.reshape(n, t, HKV, D), heads // HKV, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q4, k4) * D ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("nhqk,nkhd->nqhd", p, v4).reshape(n, t, heads * D)


@pytest.mark.parametrize("window", [BLOCK // 2, BLOCK, None],
                         ids=["half_a_tile", "a_tile", "whole_prefix"])
@pytest.mark.parametrize("group", [6, 8])
def test_band_kernels_at_groups_of_six_and_eight_over_eight_heads(
        group, window):
    """Forward and dq, dk, dv of the band kernels in interpret mode
    against the explicit-mask soft-max at 48 and 64 query heads over 8
    key/value heads, under a window of half a tile and of a whole tile,
    and over the whole prefix; dk, dv stay 8 heads wide."""
    heads = group * HKV
    rng = np.random.default_rng(group)

    def draw(h):
        return jnp.asarray(rng.normal(size=(1, T, h * D)), jnp.float32)

    q, k, v, w = draw(heads), draw(HKV), draw(HKV), draw(heads)

    def flash(q, k, v):
        return fa.pallas_flash_attention(
            q, k, v, None, None, True, layout="nthd", n_head=heads,
            n_kv_head=HKV, window=window, block_q=BLOCK, block_k=BLOCK)

    def grads(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(w * fn(q, k, v)), (0, 1, 2))(q, k, v)

    before = runtime_stats.snapshot()
    out, got = grads(flash)
    took = runtime_stats.delta(before)
    want_out, want = grads(lambda *a: _dense(*a, heads, window))
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5,
                                   err_msg="d" + name)
    assert got[1].shape == (1, T, HKV * D)
    assert (took["flash_window_calls"], took["flash_grouped_calls"]) == (
        (1, 0) if window else (0, 1))
    if window:
        band = fa._Band(T, BLOCK, BLOCK, window)
        assert took["flash_window_pairs_allowed"] == band.pairs() \
            == window * T - window * (window - 1) // 2
        # square tiles within the budget: the whole-band forward, which
        # computes (and masks) the first query tile's clamped key tile
        assert took["flash_window_entries_computed"] \
            == band.nq * band.k_steps * BLOCK * BLOCK
        assert (took["flash_window_forward_whole_band"],
                took["flash_window_forward_tiled"]) == (1, 0)


@pytest.mark.parametrize("window, forward", [
    (512, (512, 512)), (1024, (512, 512)), (2048, (1024, 1024)),
    (700, (512, 512)), (100, (256, 256)), (None, (1024, 1024))])
def test_the_forward_tile_follows_the_window(window, forward):
    """A window's forward tile from the window alone: up to 1025 keys a
    query tile of 512 (the largest power of two a narrower window
    holds, no smaller than 256) against its WHOLE band (PR 60); a wider
    window, and a call without one, keep the online soft-max over 1024 x
    1024 tiles; the backward tiles do not move; a tile given holds."""
    blocks, bwd = fa._band_blocks(16384, None, None, window)
    assert blocks == forward
    assert fa.whole_band_forward_fits(window, *blocks) == (
        window is not None and window <= 1025)
    assert bwd == ((512, 512) if window else (1024, 1024))
    assert fa._band_blocks(16384, 128, 256, window) == ((128, 256),) * 2
    # what a tile's side buys under 512 keys: the fill of the tiles a
    # whole-band step computes (two of 512, three of 256 or of 1024's
    # clamped pair), against the tiled grid's, which skips the first
    # query tile's missing neighbour
    if window == 512:
        def fill(b, whole):
            band = fa._Band(16384, b, b, 512)
            tiles = band.nq * band.k_steps if whole else band.blocks_allowed
            return round(100 * band.pairs() / (tiles * b * b), 1)

        assert [fill(b, False) for b in (1024, 512, 256)] \
            == [25.4, 50.0, 66.7]
        assert [fill(b, True) for b in (512, 256)] == [49.2, 65.6]


# -- (c) YaRN over a part of the head ---------------------------------------

def yarn_float64(group, dim):
    """transformers' `_compute_yarn_parameters` transcribed in float64
    for `dim` rotary lanes, truncate on."""
    base, factor = float(group["rope_theta"]), float(group["factor"])
    orig = group["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * np.log(orig / (rotations * 2 * np.pi))) \
            / (2 * np.log(base))

    low = max(np.floor(correction_dim(group["beta_fast"])), 0)
    high = min(np.ceil(correction_dim(group["beta_slow"])), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    return (interpolation * (1 - extrapolation_factor)
            + extrapolation * extrapolation_factor, int(low), int(high))


def test_yarn_over_half_the_head_at_the_published_keys():
    """dim = 64 of a head of 128: `low` 5 and `high` 16 of 32
    frequencies at theta 5e5, 4096 original positions, beta 64 / 1; the
    frequencies below 5 as they are, those above 16 divided by 64; the
    factor as the key gives it; the builder's, the reference's and the
    transcription's constants are the same numbers."""
    group = PUBLISHED_ROPE["full_attention"]
    want, low, high = yarn_float64(group, 64)
    assert (low, high) == (5, 16) == ref.yarn_range(group, 64)
    c = lambda r: 64 * np.log(4096 / (2 * np.pi * r)) / (2 * np.log(5e5))
    assert abs(c(64) - 5.66) < 0.01 and abs(c(1) - 15.80) < 0.01
    built, scale = rope_frequencies(64, **group)
    assert built.shape == (32,)
    np.testing.assert_allclose(built, want, rtol=1e-12)
    extra = lambda i: 5e5 ** (-2.0 * i / 64)
    np.testing.assert_allclose(
        built[[0, 5, 16, 31]],
        [1.0, extra(5), extra(16) / 64, extra(31) / 64], rtol=1e-12)
    r = (10 - 5) / 11                               # dimension 10
    np.testing.assert_allclose(built[10],
                               extra(10) / 64 * r + extra(10) * (1 - r),
                               rtol=1e-12)
    assert scale == 1.4158883083359672
    assert abs(scale - (0.1 * np.log(64) + 1)) < 1e-12
    mine, mine_scale = ref.rope_inv_freq(group, 64)
    np.testing.assert_allclose(mine, want, rtol=1e-12)
    assert mine_scale == scale
    # the sliding layers: the whole head, theta 1e4, unscaled
    plain, one = rope_frequencies(128, **PUBLISHED_ROPE["sliding_attention"])
    np.testing.assert_allclose(
        plain, [1e4 ** (-2.0 * i / 128) for i in range(64)], rtol=1e-12)
    assert one == 1.0


def test_the_op_turns_half_a_head_by_scaled_frequencies():
    """`rope(inv_freq=, rotary_dim=)`: lanes 0..R-1 turn as a head of R
    under the given frequencies and factor, lanes R.. pass through."""
    x = np.random.default_rng(3).normal(size=(1, 6, 32)).astype(np.float32)
    inv_freq, factor = rope_frequencies(
        8, rope_type="yarn", rope_theta=100.0, factor=4.0,
        original_max_position_embeddings=16, beta_fast=2.0, beta_slow=0.25,
        attention_factor=1.2)
    got = run_op("rope", {"X": x}, {"n_head": 2, "inv_freq": list(inv_freq),
                                    "attention_factor": factor,
                                    "rotary_dim": 8})
    want = ref.rope(jnp.asarray(x).reshape(1, 6, 2, 16), inv_freq, factor)
    np.testing.assert_allclose(got, np.asarray(want).reshape(1, 6, 32),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(got).reshape(1, 6, 2, 16)[..., 8:],
        x.reshape(1, 6, 2, 16)[..., 8:])
    with pytest.raises(ValueError, match="frequencies"):
        run_op("rope", {"X": x}, {"n_head": 2, "inv_freq": list(inv_freq),
                                  "rotary_dim": 16})


# -- (d) the shares add up ---------------------------------------------------

def test_the_shares_of_a_sparse_block_add_up_to_the_uncut_blocks():
    """The test the `model-configs` guide asks for: the 4 shares' parts
    of one sparse block's result (16 experts, 4 a rank; at the cell's
    size 256 / 32 = 8), the routed sum x 2.5 under the soft-max router
    with renormalised weights, plus the shared expert counted ONCE, add
    up to what the uncut reference gives for the whole block."""
    e, k, d, h, t = 16, 2, 16, 8, 48
    r = np.random.default_rng(5)
    f32 = np.float32
    x = r.normal(size=(t, d)).astype(f32)
    whole = {"router": r.normal(size=(d, e)).astype(f32) * 0.25,
             "w1": r.normal(size=(e, d, h)).astype(f32) * 0.3,
             "w3": r.normal(size=(e, d, h)).astype(f32) * 0.3,
             "w2": r.normal(size=(e, h, d)).astype(f32) * 0.3,
             "shared_w1": r.normal(size=(d, h)).astype(f32) * 0.3,
             "shared_w3": r.normal(size=(d, h)).astype(f32) * 0.3,
             "shared_w2": r.normal(size=(h, d)).astype(f32) * 0.3}
    cfg = {"num_experts_per_tok": k, "moe_routed_scaling_factor": 2.5}

    def shared():
        return np.asarray(ref.swiglu_ffn(
            jnp.asarray(x), whole["shared_w1"], whole["shared_w3"],
            whole["shared_w2"]))

    with jax.default_matmul_precision("highest"):
        routed, counts, chosen = ref.experts(jnp.asarray(x), whole, cfg)
        want = np.asarray(routed) + shared()
        total, rows = shared().astype(np.float64), 0     # counted once
        for rank in range(4):
            held = slice(4 * rank, 4 * rank + 4)
            outs = get_op_impl("moe_dropless")(
                OpContext(jax.random.PRNGKey(0), 0),
                {slot: [jnp.asarray(a)] for slot, a in {
                    "X": x, "GateW": whole["router"],
                    "W1": whole["w1"][held], "W3": whole["w3"][held],
                    "W2": whole["w2"][held]}.items()},
                {"top_k": k, "norm_topk_prob": True,
                 "routed_scaling_factor": 2.5,
                 "experts_held": [4 * rank, 4]})
            part = {slot: np.asarray(outs[slot][0])
                    for slot in ("Out", "Counts", "Experts")}
            mine, _, _ = ref.experts(
                jnp.asarray(x), dict(whole, **{n: whole[n][held]
                                               for n in ("w1", "w3", "w2")}),
                dict(cfg, expert_parallel_rank=rank))
            np.testing.assert_allclose(part["Out"], mine, rtol=2e-5,
                                       atol=2e-5)
            np.testing.assert_array_equal(part["Counts"],
                                          np.asarray(counts)[held])
            np.testing.assert_array_equal(
                np.sort(part["Experts"], -1), np.sort(np.asarray(chosen), -1))
            total += part["Out"]
            rows += part["Counts"].sum()
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert rows == t * k
    # the factor is on the routed part alone
    unscaled, _, _ = ref.experts(jnp.asarray(x), whole,
                                 dict(cfg, moe_routed_scaling_factor=1.0))
    np.testing.assert_allclose(want - shared(), 2.5 * np.asarray(unscaled),
                               rtol=2e-5, atol=2e-5)


# -- (e) what is not built raises --------------------------------------------

LATENT = dict(kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16,
              qk_rope_head_dim=8, v_head_dim=16, num_key_value_heads=6,
              num_attention_heads_per_layer=[6] * 2,
              layer_types=["full_attention"] * 2)
LINEAR = dict(layer_types=["linear_attention", "full_attention"],
              linear_num_key_heads=2, linear_num_value_heads=4,
              linear_key_head_dim=16, linear_value_head_dim=16,
              linear_conv_kernel_dim=4)


@pytest.mark.parametrize("over, error, match", [
    (LATENT, NotImplementedError, "latent attention"),
    (LINEAR, NotImplementedError, "linear_attention"),
    (dict(total_ut_steps=2, exit_gate="sigmoid",
          mlp_layer_types=["dense"] * 2), NotImplementedError, "looped"),
    (dict(objective="block_diffusion", block_length=4,
          layer_types=["full_attention"] * 2), NotImplementedError,
     "block_diffusion"),
    (dict(num_nextn_predict_layers=1), NotImplementedError,
     "prediction module"),
    (dict(attention_gate=("head", "sigmoid")), NotImplementedError,
     "attention_gate"),
    (dict(attention_gate="lane"), NotImplementedError, "attention_gate"),
    (dict(num_attention_heads_per_layer=[6]), ValueError, "for 2 layers"),
    (dict(num_attention_heads_per_layer=[6, 7]), ValueError, "multiple"),
    (dict(mlp_layer_types=["sparse", "dense"]), NotImplementedError,
     "leading"),
    (dict(mlp_layer_types=["dense"]), NotImplementedError,
     "one entry a layer"),
    (dict(mlp_layer_types=["dense", "sparse"], num_dense_layers=2),
     ValueError, "num_dense_layers"),
    (dict(rope_parameters={
        "full_attention": {"rope_type": "default", "rope_theta": 100.0,
                           "partial_rotary_factor": 0.45},
        "sliding_attention": {"rope_type": "default", "rope_theta": 50.0}}),
     ValueError, "whole number of pairs"),
    (dict(rope_parameters={
        "full_attention": {"rope_type": "llama3", "rope_theta": 100.0,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 50.0}}),
     NotImplementedError, "llama3"),
])
def test_every_combination_that_is_not_built_raises(over, error, match):
    args = dict(arguments(config(**SHARES["rank-1-of-4"])), **over)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        with pytest.raises(error, match=match):
            decoder.build_model(max_length=LENGTH, with_optimizer=False,
                                **NO_AUX, **args)


def test_a_gate_a_head_beside_latent_attention_raises():
    args = dict(arguments(config(**SHARES["rank-1-of-4"])), **LATENT)
    del args["num_attention_heads_per_layer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        with pytest.raises(NotImplementedError, match="gate on latent"):
            decoder.build_model(max_length=LENGTH, with_optimizer=False,
                                **NO_AUX, **args)


def test_the_scaling_factor_rides_with_the_soft_max_router_too():
    """`routed_scaling_factor` under `routing="softmax"`: the weights
    times the factor (it was the sigmoid router's alone); the selection
    bias and `norm_topk_eps` still are."""
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[8, 16], dtype="float32")
        layers.dropless_moe(x, 8, 8, 2, routed_scaling_factor=2.5)
        ops = [op for op in main.global_block().ops
               if op.type == "moe_dropless"]
        assert ops[0].attrs["routed_scaling_factor"] == 2.5
        layers.dropless_moe(x, 8, 8, 2)
        ops = [op for op in main.global_block().ops
               if op.type == "moe_dropless"]
        assert "routed_scaling_factor" not in ops[1].attrs
        for refused in (dict(use_expert_bias=True),
                        dict(norm_topk_eps=1e-6)):
            with pytest.raises(ValueError, match="sigmoid"):
                layers.dropless_moe(x, 8, 8, 2, **refused)
