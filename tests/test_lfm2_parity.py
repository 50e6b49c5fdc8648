"""LFM2 on the normal path (`models/decoder.py` with `qk_norm="head"`
and `router="sigmoid"`: gated short convolutions beside grouped-query
attention at d_head 64 with per-head QK-norm, a leading dense SwiGLU
layer, experts chosen by sigmoid score + a selection bias, Pallas
kernels in interpret mode) against its plain float32 reference
(`models/decoder_reference.py lfm2_*`) on the CPU at a small size,
seeded random weights, AMP off: logits, the loss, the held experts'
counts, each token's experts and the gradient of every parameter, for
the whole layer and for one expert-parallel rank's share.

Tolerance.  As tests/test_decoder_parity.py: both sides are float32
with matmuls at "highest" and differ in summation order only; 5e-6
absolute-or-relative (largest seen 4.5e-7).  The selection bias is drawn
non-zero (the start-up value is zero), so that "choose on score + bias,
weigh with the score" is what is compared; bfloat16 compute misses the
tolerance by orders of magnitude.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import decoder, decoder_reference as ref

from parity_harness import (Family, batch, close, draw_expert_biases,
                            reference, system)

TOL = 5e-6
NO_AUX = dict(aux_loss_weight=0.0, z_loss_weight=0.0)   # the config has none
SHARES = {"whole-layer": dict(num_experts=8),
          "rank-1-of-4": dict(num_experts=2, expert_parallel_size=4,
                              expert_parallel_rank=1)}


def config(**over):
    cfg = dict(qk_norm="head", router="sigmoid", hidden_size=512,
               num_hidden_layers=3, num_attention_heads=8,
               num_key_value_heads=2, intermediate_size=96,
               moe_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, norm_topk_prob=True,
               use_expert_bias=True, routed_scaling_factor=1.0,
               layer_types=["conv", "full_attention", "conv"],
               num_dense_layers=1, conv_L_cache=3, conv_bias=False,
               norm_eps=1e-5,
               rope_parameters={"rope_theta": 1000000.0,
                                "rope_type": "default"},
               vocab_size=96)
    cfg.update(over)
    return cfg


def arguments(cfg):
    return dict(cfg, **NO_AUX)


def _to_list(grads, cfg):
    flat = [grads["embed"]]
    for i, layer in enumerate(grads["layers"]):
        flat += [layer[k] for k in ref.lfm2_layer_keys(cfg, i)]
    return flat + [grads["final_norm"], grads["head"]]


FAMILY = Family(ref.lfm2_params_from_list, ref.lfm2_loss_and_grads, _to_list)


@pytest.mark.parametrize("share", sorted(SHARES))
def test_program_matches_the_float32_reference(share):
    cfg = config(**SHARES[share])
    feed = batch(cfg)
    got, params = system(arguments(cfg), feed,
                         after_startup=draw_expert_biases)
    total, parts, grads = reference(FAMILY, cfg, feed, params,
                                     drawn=got["drawn"])
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    k, tokens = cfg["num_experts_per_tok"], feed["tokens"].size
    assert len(got["counts"]) == 2          # layer 0 is the dense one
    for i in range(2):
        np.testing.assert_array_equal(got["counts"][i],
                                      np.asarray(parts["counts"][i]))
        assert got["counts"][i].shape == (cfg["num_experts"],)
        if share == "whole-layer":
            assert got["counts"][i].sum() == tokens * k     # dropless
        np.testing.assert_array_equal(
            np.sort(got["experts"][i], axis=-1),
            np.sort(np.asarray(parts["experts"][i]), axis=-1))
    assert len(got["grads"]) == len(grads) == len(params)
    flat_keys = ["embed"] + [k for i in range(3)
                             for k in ref.lfm2_layer_keys(cfg, i)] \
        + ["final_norm", "head"]
    for i, (g, w) in enumerate(zip(got["grads"], grads)):
        # no vacuous match, but for a share's router: the builder runs
        # a share with no exchange and so without the gradient through
        # its routing weights (`router_gradient=False`), as the
        # reference's forward does
        routerless = share != "whole-layer" and flat_keys[i] == "router"
        assert (np.abs(np.asarray(w)).max() > 0) != routerless, i
        close(g, w, f"gradient of parameter {i}")


def test_the_reference_in_blocks_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/lfm2_parity.py` runs on the chip so that 8192
    positions fit: scores `q_block` rows at a time, every layer
    recomputed in its backward pass.  Same numbers."""
    cfg = config(**SHARES["rank-1-of-4"])
    feed = batch(cfg)
    got, params = system(arguments(cfg), feed,
                         after_startup=draw_expert_biases)
    tree = ref.lfm2_params_from_list(params, cfg, got["drawn"])
    args = (jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]), cfg)
    # (each compiled as one function, as the chip's script runs them)
    (plain, _), want = jax.jit(
        lambda t: ref.lfm2_loss_and_grads(t, *args))(tree)
    (blocked, _), got = jax.jit(
        lambda t: ref.lfm2_loss_and_grads(t, *args, q_block=8))(tree)
    close(blocked, plain, "loss")

    leaves, other = jax.tree.leaves(want), jax.tree.leaves(got)
    assert len(leaves) == len(other) > len(params)      # + the biases
    for w, g in zip(leaves, other):
        close(g, w, "gradient")


def test_the_selection_bias_moves_the_choice_and_not_the_weight():
    """With the bias at zero other experts are chosen (so the draw
    tests something), and the program's parameters are the learned
    ones only: no gradient is made for a bias."""
    cfg = config()
    feed = batch(cfg)
    got, params = system(arguments(cfg), feed,
                         after_startup=draw_expert_biases)
    parts = jax.jit(lambda tree: ref.lfm2_forward(
        tree, jnp.asarray(feed["tokens"]), cfg))(
        ref.lfm2_params_from_list(params, cfg, None))
    assert (np.sort(got["experts"][0], axis=-1)
            != np.sort(np.asarray(parts["experts"][0]), axis=-1)).any()
    n_layer_params = sum(len(ref.lfm2_layer_keys(cfg, i)) for i in range(3))
    assert len(params) == 1 + n_layer_params + 2


def test_bf16_compute_fails_the_tolerance():
    cfg = config()
    feed = batch(cfg)
    got, params = system(arguments(cfg), feed, use_amp=True,
                         after_startup=draw_expert_biases)
    _, parts, _ = reference(FAMILY, cfg, feed, params,
                                     drawn=got["drawn"])
    err = np.abs(np.asarray(got["logits"], np.float32)
                 - np.asarray(parts["logits"])).max()
    assert err > 20 * TOL, err


@pytest.mark.parametrize("what, over", [
    ("layer type", dict(layer_types=["conv", "chunked_attention", "conv"])),
    ("qk_norm", dict(qk_norm="layer")),
    ("router", dict(router="tanh")),
    ("conv_bias", dict(conv_bias=True)),
    ("rope_type", dict(rope_parameters={"rope_theta": 1e6,
                                        "rope_type": "llama3"})),
])
def test_a_value_that_is_not_built_is_refused_not_guessed(what, over):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(NotImplementedError, match=what.split()[0]):
            decoder.decoder(max_length=8, **config(**over))


def test_training_step_learns_and_counts_both_sides_of_the_share():
    """The whole training Program of a share (AdamW, clip, schedule,
    bf16 AMP): the loss falls, and the device-side counters of held
    and not-held rows add up to steps x T x k in every routed layer."""
    from paddle_tpu.observe import routing

    cfg = config(**SHARES["rank-1-of-4"])
    feed = batch(cfg)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = decoder.build_model(max_length=32, learning_rate=3e-3,
                                warmup_steps=1, **NO_AUX,
                                expert_bias_update_rate=0.001, **cfg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        params = main.all_parameters()
        before = {p.name: np.asarray(scope.find_var(p.name)).copy()
                  for p in params}
        losses = [float(exe.run(main, feed=feed, scope=scope,
                                fetch_list=[m["loss"]])[0][0])
                  for _ in range(6)]
    assert losses[-1] < losses[0]
    # the builder holds back the gradient through a share's routing
    # weights (no exchange sums the ranks' parts): its router only
    # decays (one factor for every element); everything else learns
    gates = [p for p in params if p.name.startswith("moe_gate")]
    assert len(gates) == 2
    for p in params:
        after = np.asarray(scope.find_var(p.name))
        assert (after != before[p.name]).any(), p.name
        ratio = after / before[p.name]
        assert np.allclose(ratio, ratio.flat[0], rtol=1e-6) == (p in gates)
    held = routing.expert_token_counts(scope)
    off = routing.off_share_counts(scope)
    assert len(held) == len(off) == 2
    rows = 6 * feed["tokens"].size * cfg["num_experts_per_tok"]
    for name, counts in held.items():
        gone = off[name.replace(routing.TOKEN_COUNT_SUFFIX,
                                routing.OFF_SHARE_COUNT_SUFFIX)]
        assert counts.shape == (2,) and counts.sum() + gone.sum() == rows
    share = routing.held_row_share(scope)
    assert 0.0 < share < 1.0
    # the recipe's bias update ran in the step: six moves of +-0.001
    for name in main.global_block().vars:
        if name.endswith(".expert_bias"):
            b = np.asarray(scope.find_var(name))
            assert b.shape == (8,) and np.abs(b).max() > 0
            np.testing.assert_allclose(b / 0.001, np.round(b / 0.001),
                                       atol=0.02)
            assert np.abs(b).max() <= 0.006 + 2e-5   # as rounded above
    assert routing.held_row_share(fluid.Scope()) is None
