"""The vision-language family on the normal path
(`models/vision_tower.py` feeding `models/decoder.py` in ONE Program:
packed patches under rotary positions over two axes, attention inside
an image, a position table read through bicubic taps, the 2 x 2 merger
and projector, the merge into the embedded stream, a direct-q latent
attention WITH rotary lanes, two shared experts, the weighted next-token
loss) against its plain float32 reference
(`benchmarks/reference_kimi_vl.py`) on the CPU at a small size, seeded
random weights: logits, the weighted loss, the image rows, every routed
layer's counts and experts, the gradient of every parameter of tower,
projector and decoder.

The preset: a tower of 2 layers of 2 heads of 72 lanes (hidden 144, MLP
80, patches of 2 x 2 pixels, an 8 x 8 position table) over 512 packed
rows (so that the attention runs the segment kernels, through the
interpreter), a decoder of hidden 64 with one dense and one routed
layer (latent attention at 16 / 8 / 16 lanes out of a latent of 24, 16
experts of width 32, 3 a token, 2 shared) at 128 positions.  Three
images of unlike grids, (8, 12), (16, 16) and (4, 10) in the sequence,
392 patches and a padding tail of 120 rows; 98 image rows among 30 text
tokens.  The collator hands the patches of an image in MERGE order, the
images in the sequence's order; the reference runs an image at a time
in the published row-major order and permutes: the image rows agree,
which is the test that the orders are one model.

Tolerance.  Float32 on both sides with matmuls at "highest": 5e-6
absolute-or-relative on logits, loss and image rows, as the other
families; a gradient 3e-5 of its largest entry (largest seen 4e-6: the
tower's q and k through two soft-maxes).
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
import reference_kimi_vl as ref  # noqa: E402
from models import kimi_vl as family  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import Family, close, reference, system  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import decoder, vision_tower  # noqa: E402

TOL, GRAD_TOL = 5e-6, 3e-5
LENGTH, PATCH_ROWS = 128, 512
GRIDS = ((8, 12), (16, 16), (4, 10))     # in the sequence's order
ROWS = sum(h * w for h, w in GRIDS) // 4
SHARES = {"whole-layer": dict(n_routed_experts=16),
          "rank-1-of-4": dict(n_routed_experts=4, expert_parallel_size=4,
                              expert_parallel_rank=1)}


def config(**over):
    cfg = dict(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=2, num_attention_heads=2,
        num_key_value_heads=2, n_shared_experts=2, n_routed_experts=16,
        routed_scaling_factor=2.446, kv_lora_rank=24, q_lora_rank=None,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
        topk_method="noaux_tc", n_group=1, topk_group=1,
        num_experts_per_tok=3, moe_layer_freq=1, first_k_dense_replace=1,
        norm_topk_prob=True, scoring_func="sigmoid", hidden_act="silu",
        rms_norm_eps=1e-5, rope_theta=800000, rope_scaling=None,
        attention_bias=False, tie_word_embeddings=False,
        rope_interleave=True, media_placeholder_token_id=0,
        in_token_limit=256,
        vision_config=dict(
            hidden_size=144, num_hidden_layers=2, num_attention_heads=2,
            intermediate_size=80, patch_size=2, init_pos_emb_height=8,
            init_pos_emb_width=8, merge_kernel_size=[2, 2]),
        expert_parallel_size=1, expert_parallel_rank=0,
        sequence_length=LENGTH, patch_rows=PATCH_ROWS)
    cfg.update(over)
    return cfg


def build(max_length, with_optimizer, tower, **arguments):
    """The family's Program as `benchmarks/models/kimi_vl.py build`
    makes it: the tower, then the decoder that reads its rows."""
    made = vision_tower.vision_tower(recompute=arguments.get("recompute"),
                                     **tower)
    model = decoder.build_model(
        max_length=max_length, with_optimizer=with_optimizer,
        image_rows=made["image_rows"], **arguments)
    return dict(model, image_rows=made["image_rows"],
                tower_out=made["tower_out"])


def arguments(cfg, **how):
    return dict(family.architecture(cfg),
                tower=family.tower_architecture(cfg), aux_loss_weight=0.0,
                z_loss_weight=0.0, **how)


def batch(cfg, seed=0):
    cell = dict(batch_per_chip=2, chips=1, length=LENGTH, images=[])
    return family.make_batch(cfg, cell, np.random.default_rng(seed),
                             grids=GRIDS)


def off_the_constants(main, scope, seed):
    """Parameters that start at a constant (norm scales and shifts,
    every bias) are moved off it and the selection biases drawn, so that
    the comparison sees them; returns the biases."""
    rng = np.random.default_rng(seed + 1)
    for p in main.all_parameters():
        value = np.asarray(scope.find_var(p.name))
        if value.std() == 0:
            scope.set_var(p.name, jnp.asarray(
                value + 0.1 * rng.normal(size=value.shape)
                .astype(np.float32)))
    return harness.draw_expert_biases(main, scope, seed)


FAMILY = Family(ref.params_from_list, ref.loss_and_grads, ref.grads_to_list)
FETCH = ("loss", "logits", "image_rows", "tower_out")
run = functools.partial(system, after_startup=off_the_constants,
                        builder=build, fetch=FETCH)
grids_of = functools.partial(tuple, (GRIDS, GRIDS))


# -- (a) the builders' program against the reference --------------------------

@pytest.mark.parametrize("share, recompute", [
    ("whole-layer", None), ("whole-layer", "layer"),
    ("rank-1-of-4", None), ("rank-1-of-4", "layer")])
def test_program_matches_the_float32_reference(share, recompute):
    cfg = config(**SHARES[share])
    feed = batch(cfg)
    got, params = run(arguments(cfg, recompute=recompute), feed)
    total, parts, grads = reference(FAMILY, cfg, feed, params,
                                    drawn=got["drawn"], grids=grids_of())
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    # merge-order patches give the published order's image rows
    for n in range(2):
        close(got["image_rows"][n, :ROWS], parts["image_rows"][n],
              "image rows")
        # a padding row's tower output is 0: its rows are one constant
        assert got["image_rows"][n, ROWS:].std(axis=0).max() < 1e-6
    np.testing.assert_array_equal(got["counts"][0],
                                  np.asarray(parts["counts"][0]))
    np.testing.assert_array_equal(
        np.sort(got["experts"][0], axis=-1),
        np.sort(np.asarray(parts["experts"][0]), axis=-1))
    names = ref.system_names(cfg)
    assert len(got["grads"]) == len(grads) == len(params) == len(names)
    for name, g, w in zip(names, got["grads"], grads):
        routerless = share != "whole-layer" and name.endswith(".router")
        assert (np.abs(np.asarray(w)).max() > 0) != routerless, name
        close(g, w, f"gradient of {name}", GRAD_TOL,
              scale=np.abs(np.asarray(w)).max())
    # the attention ran the segment kernels: the op of each of the two
    # layers is traced once, under a segment too
    assert got["took"]["flash_segment_calls"] == 2
    assert got["took"]["flash_segment_xla_calls"] == 0
    # 2 heads of 72 are no whole 128-lane tiles a row: the kernels'
    # layout is XLA's pad here and the op turns q and k through `_rope`
    assert (got["took"]["flash_segment_lane_kernel_calls"],
            got["took"]["flash_segment_lane_xla_calls"]) == (0, 2)
    assert (got["took"]["image_patches"], got["took"]["image_rows"]) \
        == (2 * PATCH_ROWS, 2 * PATCH_ROWS // 4)


def test_the_tower_in_the_published_order_is_the_tower_in_merge_order():
    """The tower's own output, row by row: the system's rows are the
    reference's row-major rows at the merge order's places."""
    cfg = config()
    feed = batch(cfg)
    got, params = run(arguments(cfg), feed)
    _, parts, _ = reference(FAMILY, cfg, feed, params, drawn=got["drawn"],
                            grids=grids_of())
    at = 0
    for h, w in GRIDS:
        yx = family.merge_order(h, w)
        want = np.asarray(parts["tower_out"][0])[at:at + h * w][
            yx[:, 0] * w + yx[:, 1]]
        close(got["tower_out"][0, at:at + h * w], want, f"tower ({h}, {w})")
        at += h * w


def test_the_step_under_amp_misses_the_float32_tolerance():
    """The control: the same Program under bf16 AMP (as the cell runs
    it) stays near the reference and misses the float32 tolerance."""
    cfg = config()
    feed = batch(cfg)
    exact, params = run(arguments(cfg), feed)
    got, _ = run(arguments(cfg), feed, use_amp=True)
    err = np.abs(np.asarray(got["logits"], np.float32)
                 - exact["logits"]).max() / np.abs(exact["logits"]).max()
    assert 100 * TOL < err < 0.1, err
    assert abs(float(got["loss"][0]) - float(exact["loss"][0])) < 0.05


def test_the_scopes_are_the_documented_ones():
    cfg = config()
    got, _ = run(arguments(cfg), batch(cfg))
    scopes = {}
    for op in got["main"].global_block().ops:
        for part in op.desc.attrs.get("__name_scope__", "").split("/"):
            scopes.setdefault(part, set()).add(op.type)
    assert {"segment_attention", "mul"} <= scopes["vision_attention"]
    # the tower's q and k turn inside the attention op, by its Positions
    assert "rope" not in scopes["vision_attention"]
    assert all(op.desc.inputs["Positions"] == ["patch_yx"]
               and op.desc.attrs["theta"] == 10000.0
               for op in got["main"].global_block().ops
               if op.type == "segment_attention")
    assert {"table_interp", "layer_norm", "gelu"} <= scopes["vision_tower"]
    assert {"layer_norm", "gelu", "mul", "reshape2"} & \
        scopes["vision_projector"]
    assert scopes["image_merge"] == {"image_merge"}
    assert "rope" in scopes["latent_attention"]


# -- (b) the shares -----------------------------------------------------------

def test_all_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The `model-configs` guide's tie of the share to the model: the
    routed parts of all 16 / 2 = 8 shares (through the op the builder
    appends) plus the shared expert COUNTED ONCE are the uncut
    reference's routed FFN."""
    from paddle_tpu.core.registry import OpContext, get_op_impl

    t, d, h, e_all, held, k = 48, 32, 16, 16, 2, 3
    r = np.random.default_rng(0)
    draw = lambda *shape, scale=0.3: jnp.asarray(  # noqa: E731
        r.normal(size=shape).astype(np.float32) * scale)
    p = {"x": draw(t, d, scale=1.0), "router": draw(d, e_all, scale=0.25),
         "bias": draw(e_all, scale=0.05), "w1": draw(e_all, d, h),
         "w3": draw(e_all, d, h), "w2": draw(e_all, h, d),
         "shared_w1": draw(d, 2 * h), "shared_w3": draw(d, 2 * h),
         "shared_w2": draw(2 * h, d)}
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True,
           "routed_scaling_factor": 2.446}
    with jax.default_matmul_precision("highest"):
        want, counts, _ = ref.experts(p["x"], p, cfg)
        shared = ref.swiglu(p["x"], p["shared_w1"], p["shared_w3"],
                            p["shared_w2"])
    parts = []
    for rank in range(e_all // held):
        lo = rank * held
        o = get_op_impl("moe_dropless")(
            OpContext(jax.random.PRNGKey(0), 0),
            {"X": [p["x"]], "GateW": [p["router"]], "Bias": [p["bias"]],
             **{key.upper(): [p[key][lo:lo + held]]
                for key in ("w1", "w3", "w2")}},
            {"routing": "sigmoid", "norm_topk_prob": True, "top_k": k,
             "norm_topk_eps": 1e-20, "routed_scaling_factor": 2.446,
             "experts_held": [lo, held]})
        parts.append((o["Out"][0], o["Counts"][0]))
    total = sum(np.asarray(y, np.float64) for y, _ in parts) \
        + np.asarray(shared, np.float64)
    np.testing.assert_allclose(total, np.asarray(want + shared), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c) for _, c in parts]),
        np.asarray(counts))
    assert sum(int(c.sum()) for _, c in parts) == t * k


# -- (c) what raises ----------------------------------------------------------

def _decoder_with(**over):
    cfg = config()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        made = vision_tower.vision_tower(**family.tower_architecture(cfg))
        args = dict(family.architecture(cfg), image_rows=made["image_rows"])
        args.update(over)
        return decoder.build_model(max_length=LENGTH, with_optimizer=False,
                                   **args)


@pytest.mark.parametrize("over, error, match", [
    (dict(media_placeholder_token_id=None), ValueError, "needs both"),
    (dict(image_rows=None), ValueError, "needs both"),
    (dict(objective="block_diffusion", block_length=4), NotImplementedError,
     "block_diffusion"),
    (dict(num_nextn_predict_layers=1), NotImplementedError,
     "prediction module"),
    (dict(total_ut_steps=2, exit_gate="sigmoid"), NotImplementedError,
     "looped stack"),
    (dict(hidden_size=32), ValueError, "not rows of the stream"),
])
def test_unbuilt_combinations_of_the_second_input_raise(over, error, match):
    with pytest.raises(error, match=match):
        _decoder_with(**over)


@pytest.mark.parametrize("over, error, match", [
    (dict(merge_kernel_size=[3, 3]), NotImplementedError, "2 x 2"),
    (dict(hidden_act="gelu"), NotImplementedError, "tanh-GELU"),
    (dict(num_attention_heads=5), ValueError, "whole heads"),
    (dict(hidden_size=140), ValueError, "multiple of 4"),
    (dict(recompute="block"), NotImplementedError, "recompute"),
    (dict(patch_rows=510), ValueError, "4-patch blocks"),
])
def test_unbuilt_values_of_the_tower_raise(over, error, match):
    args = dict(family.tower_architecture(config()), **over)
    with fluid.program_guard(fluid.Program(), fluid.Program()), \
            fluid.unique_name.guard():
        with pytest.raises(error, match=match):
            vision_tower.vision_tower(**args)


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 2), ("moe_layer_freq", 2),
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("scoring_func", "softmax"), ("topk_method", "greedy")])
def test_the_family_raises_on_what_is_not_built(key, value):
    with pytest.raises(NotImplementedError, match=key):
        family.architecture(config(**{key: value}))
