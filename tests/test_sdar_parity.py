"""The block-diffusion MoE decoder on the normal path
(`models/decoder.py` with `objective="block_diffusion"`: the doubled
feed [x_0 ; x_t], RoPE positions that restart, the block-diffusion mask
of the Pallas band kernels in interpret mode, the head over the noised
half, the weighted masked cross-entropy; `qk_norm="head"`, the soft-max
router with `norm_topk_prob` over a held share) against its plain
float32 reference (`benchmarks/reference_sdar.py`) on the CPU at a small
size, seeded random weights: logits, the loss, every row's experts, the
held experts' counts and the gradient of every parameter.

Sizes: d 64, 4 query heads over 2 key/value heads of 16, 8 experts of
which 4 are held (rank 1 of 2), 2 a token, 2 layers, B 4, L 32 (64
rows), vocabulary 96 whose last row is the mask id.

Tolerance.  Float32: both sides are float32 with matmuls at "highest"
and differ in summation order only (the flash kernels' online
soft-max, the sorted expert rows): 5e-6 absolute-or-relative, as
tests/test_mellum_parity.py; a gradient leaf is held to that of ITS
largest entry (the weights reach 1 / t_min).  bf16 AMP must MISS the
float32 tolerance by 20 x.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.data.diffusion import block_diffusion_feeds
from paddle_tpu.models import decoder

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import reference_sdar as ref  # noqa: E402
from parity_harness import Family, close, reference, system  # noqa: E402

TOL = 5e-6
B, L, VOCAB = 4, 32, 96
MASK_ID = VOCAB - 1
NO_AUX = dict(aux_loss_weight=0.0, z_loss_weight=0.0)
SHARES = {"whole-layer": dict(num_experts=8),
          "rank-1-of-2": dict(num_experts=4, expert_parallel_size=2,
                              expert_parallel_rank=1)}


def config(**over):
    cfg = dict(qk_norm="head", router="softmax", objective="block_diffusion",
               block_length=B, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               intermediate_size=96, moe_intermediate_size=32,
               num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
               rms_norm_eps=1e-6, rope_theta=100.0, vocab_size=VOCAB)
    cfg.update(over)
    return cfg


def noised_batch(n=2, length=L, seed=0, block_length=B, t_min=0.05):
    rng = np.random.default_rng(seed)
    x0 = rng.integers(1, MASK_ID, size=(n, length))
    return block_diffusion_feeds(x0, block_length, MASK_ID, rng, t_min=t_min)


def arguments(cfg, **build):
    return dict(cfg, **NO_AUX, **build)


def ref_config(cfg):
    return dict(cfg, **{k: cfg.get(k, v) for k, v in
                        (("expert_parallel_size", 1),
                         ("expert_parallel_rank", 0))})


FAMILY = Family(ref.params_from_list, ref.loss_and_grads,
                lambda grads, cfg: ref.flat_leaves(grads))
FETCH = ("loss", "logits", "masked_share")


def close_grads(cfg, got, want):
    names = ref.leaf_names(ref_config(cfg))
    assert len(got) == len(want) == len(names)
    for name, g, w in zip(names, got, want):
        close(g, w, f"gradient of {name}",
              scale=max(1.0, float(np.abs(np.asarray(w)).max())))


# -- (a) the program against the reference ----------------------------------

@pytest.mark.parametrize("recompute", [None, "layer"])
@pytest.mark.parametrize("share", sorted(SHARES))
def test_program_matches_the_float32_reference(share, recompute):
    cfg = config(**SHARES[share])
    feed = noised_batch()
    got, params = system(arguments(cfg, recompute=recompute), feed,
                         fetch=FETCH)
    took = got["took"]
    total, parts, grads = reference(FAMILY, ref_config(cfg), feed, params)
    assert got["logits"].shape == (2, L, VOCAB)         # the noised half
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    close(got["masked_share"], (feed["loss_weights"] > 0).mean(), "share")
    assert len(got["counts"]) == 2
    for i in range(2):
        np.testing.assert_array_equal(got["counts"][i],
                                      np.asarray(parts["counts"][i]))
        np.testing.assert_array_equal(
            np.sort(got["experts"][i], axis=-1),
            np.sort(np.asarray(parts["experts"][i]), axis=-1))
    names = ref.leaf_names(ref_config(cfg))
    for name, w in zip(names, grads):
        # no vacuous match, but for a share's router (held constant
        # by the builder on both sides: no exchange sums the ranks')
        routerless = share != "whole-layer" and name.endswith(".router")
        assert (np.abs(np.asarray(w)).max() > 0) != routerless, name
    close_grads(cfg, got["grads"], grads)
    # the kernels ran (no fall-back to the explicit mask; a call a
    # trace, the build's shape inference and the step's), a single
    # backward kernel a layer; what is visited is what is allowed
    assert took["flash_block_diffusion_calls"] >= 4
    assert took["flash_attention_backward_fused"] == 2
    assert took["flash_attention_backward_split"] == 0
    assert took["flash_block_diffusion_blocks_visited"] \
        == took["flash_block_diffusion_blocks_allowed"] > 0


def test_the_reference_in_blocks_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/sdar_parity.py` runs on the chip so that 16384
    rows fit: scores `q_block` rows at a time, every layer recomputed
    in its backward pass.  Same numbers."""
    cfg = config(**SHARES["rank-1-of-2"])
    feed = noised_batch()
    _, params = system(arguments(cfg), feed, fetch=FETCH)
    plain, _, want = reference(FAMILY, ref_config(cfg), feed, params)
    blocked, _, got = reference(FAMILY, ref_config(cfg), feed, params,
                                q_block=16)
    close(blocked, plain, "loss")
    for w, g in zip(want, got):
        close(g, w, "gradient", scale=max(1.0, float(np.abs(w).max())))


def test_bf16_amp_fails_the_float32_tolerance():
    cfg = config(**SHARES["rank-1-of-2"])
    feed = noised_batch()
    got, params = system(arguments(cfg, recompute="layer"), feed,
                         use_amp=True, fetch=FETCH)
    _, parts, _ = reference(FAMILY, ref_config(cfg), feed, params)
    err = np.abs(np.asarray(got["logits"], np.float32)
                 - np.asarray(parts["logits"]))
    assert err.max() > 20 * TOL, err.max()
    assert err.max() < 0.25, err.max()


# -- (c) the mask is what it means -------------------------------------------

def _prefix_model(params, cfg, ids, positions):
    """The same network on ONE short sequence under the block-causal
    mask blk(s) <= blk(r), written out: a clean prefix of whole blocks
    and after it one noised block, which reads the prefix and itself,
    both directions."""
    cfg = ref_config(cfg)
    tree = ref.params_from_list(params, cfg)
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    t = len(ids)
    blk = positions // B
    seen = blk[None, :] <= blk[:, None]
    with jax.default_matmul_precision("highest"):
        x = tree["embed"][ids][None]
        for layer in tree["layers"]:
            h = ref.rms_norm(x, layer["op_norm"], eps)
            q = ref.rms_norm((h @ layer["wq"]).reshape(1, t, heads, d),
                             layer["q_norm"], eps)
            k = ref.rms_norm((h @ layer["wk"]).reshape(1, t, kv, d),
                             layer["k_norm"], eps)
            v = (h @ layer["wv"]).reshape(1, t, kv, d)
            q = ref.rope(q, positions, cfg["rope_theta"])
            k = ref.rope(k, positions, cfg["rope_theta"])
            k = jnp.repeat(k, heads // kv, axis=2)
            v = jnp.repeat(v, heads // kv, axis=2)
            s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            x = x + jnp.einsum("nhqk,nkhd->nqhd", p, v).reshape(
                1, t, heads * d) @ layer["wo"]
            h = ref.rms_norm(x, layer["ffn_norm"], eps)
            y, _, _ = ref.experts(h[0], layer, cfg)
            x = x + y[None]
        x = ref.rms_norm(x, tree["final_norm"], eps)
        return (x @ tree["head"])[0]


@pytest.mark.parametrize("block", range(L // B))
def test_a_noised_block_sees_its_clean_prefix_and_itself(block):
    """The logits of the noised rows of block b in the 2 L run are
    those of the model run on [x_0 blocks < b ; x_t block b] ALONE,
    under a causal-prefix mask with the last block bidirectional."""
    cfg = config(**SHARES["rank-1-of-2"])
    feed = noised_batch(n=1, seed=11)
    got, params = system(arguments(cfg), feed, fetch=FETCH)
    lo, hi = block * B, (block + 1) * B
    ids = np.concatenate([feed["tokens"][0, :lo],
                          feed["tokens"][0, L + lo:L + hi]])
    want = jax.jit(lambda ids, positions: _prefix_model(
        params, cfg, ids, positions))(jnp.asarray(ids), jnp.arange(hi))
    close(got["logits"][0, lo:hi], want[lo:hi], f"block {block}", tol=2e-5)


# -- (d) the shares add up ----------------------------------------------------

def test_the_two_shares_of_a_sparse_block_add_up_to_the_uncut_reference():
    """The share test the `model-configs` guide asks for, at the preset:
    8 experts over 2 ranks (the cell: 128 over 8), each rank's part of
    one sparse block's result, and their sum against the uncut
    reference of all 8."""
    rng = np.random.default_rng(5)
    f32 = np.float32
    e, k, d, h, t = 8, 2, 64, 32, 2 * L
    ins = {"X": rng.normal(size=(t, d)).astype(f32),
           "GateW": rng.normal(size=(d, e)).astype(f32) * 0.25,
           "W1": rng.normal(size=(e, d, h)).astype(f32) * 0.3,
           "W3": rng.normal(size=(e, d, h)).astype(f32) * 0.3,
           "W2": rng.normal(size=(e, h, d)).astype(f32) * 0.3}
    # half of the rows one repeated embedding, as the mask id's are
    ins["X"][L::2] = ins["X"][L]
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True}

    def reference_part(rank=0, size=1):
        held = e // size
        layer = {"router": jnp.asarray(ins["GateW"]),
                 **{n.lower(): jnp.asarray(
                     ins[n][rank * held:(rank + 1) * held])
                    for n in ("W1", "W3", "W2")}}
        with jax.default_matmul_precision("highest"):
            return ref.experts(jnp.asarray(ins["X"]), layer,
                               dict(cfg, expert_parallel_rank=rank))

    impl = get_op_impl("moe_dropless")
    want, counts, _ = reference_part()
    total, rows = np.zeros((t, d), np.float64), 0
    for rank in range(2):
        cut = {n: ins[n][4 * rank:4 * rank + 4] for n in ("W1", "W3", "W2")}
        o = impl(OpContext(jax.random.PRNGKey(0), 0),
                 {n: [jnp.asarray(v)] for n, v in dict(ins, **cut).items()},
                 {"routing": "softmax", "norm_topk_prob": True, "top_k": k,
                  "experts_held": [4 * rank, 4]})
        part, c = np.asarray(o["Out"][0]), np.asarray(o["Counts"][0])
        np.testing.assert_allclose(part, reference_part(rank, 2)[0],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(
            c, np.asarray(counts)[4 * rank:4 * rank + 4])
        total += part
        rows += c.sum()
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert rows == t * k


# -- (e) the noising function -------------------------------------------------

def test_the_noising_function_is_seeded_and_weighs_masked_positions_alone():
    n, length, b, t_min = 4, 4096, 4, 1e-3
    x0 = np.random.default_rng(1).integers(1, MASK_ID, size=(n, length))
    a = block_diffusion_feeds(x0, b, MASK_ID, np.random.default_rng(9), t_min)
    again = block_diffusion_feeds(x0, b, MASK_ID, np.random.default_rng(9),
                                  t_min)
    other = block_diffusion_feeds(x0, b, MASK_ID, np.random.default_rng(10),
                                  t_min)
    for key in a:
        np.testing.assert_array_equal(a[key], again[key])
    assert (a["tokens"] != other["tokens"]).any()
    assert a["tokens"].shape == (n, 2 * length) and a["tokens"].dtype == np.int64
    assert a["loss_weights"].dtype == np.float32
    np.testing.assert_array_equal(a["tokens"][:, :length], x0)  # clean half
    np.testing.assert_array_equal(a["labels"], x0)
    noised, w = a["tokens"][:, length:], a["loss_weights"]
    masked = noised == MASK_ID
    np.testing.assert_array_equal(noised[~masked], x0[~masked])
    np.testing.assert_array_equal(w > 0, masked)       # weights there alone
    # ONE t a block: the weights of a block's masked positions agree,
    # and 1 / w = t_b lies in [t_min, 1]
    wb = w.reshape(n, length // b, b)
    top = wb.max(axis=-1, keepdims=True)
    assert ((wb == 0) | (wb == top)).all()
    t_b = 1.0 / w[masked]
    assert t_b.min() >= t_min * (1 - 1e-6) and t_b.max() <= 1.0 + 1e-6
    # the expected masked share is E[t] = (1 + t_min) / 2
    assert abs(masked.mean() - 0.5) < 0.02
    # E[w] = E[t * 1 / t] = 1: the loss's scale is a cross-entropy's
    assert abs(w.mean() - 1.0) < 0.1


def test_positions_restart_at_the_noised_half():
    """`rope(period=L)`: row L + p turns as row p; the decode `Offset`
    still adds after it."""
    from op_test import run_op

    x = np.random.default_rng(3).normal(size=(1, 12, 32)).astype(np.float32)
    twice = np.concatenate([x, x], axis=1)
    plain = run_op("rope", {"X": x}, {"n_head": 2, "theta": 100.0})
    turned = run_op("rope", {"X": twice},
                    {"n_head": 2, "theta": 100.0, "period": 12})
    np.testing.assert_array_equal(turned[:, :12], plain)
    np.testing.assert_array_equal(turned[:, 12:], plain)
    unbroken = run_op("rope", {"X": twice}, {"n_head": 2, "theta": 100.0})
    assert np.abs(np.asarray(unbroken[:, 12:]) - plain).max() > 0.1
    moved = run_op("rope", {"X": twice, "Offset": np.array([5], np.int32)},
                   {"n_head": 2, "theta": 100.0, "period": 12})
    np.testing.assert_array_equal(moved[:, :12], moved[:, 12:])
    with pytest.raises(ValueError, match="no row count"):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            fluid.layers.rope(fluid.layers.data("x", shape=[24, 32]), 2,
                              period=0)


# -- (f) what is not built raises ---------------------------------------------

@pytest.mark.parametrize("what, over, error", [
    ("a window layer", dict(layer_types=["sliding_attention",
                                         "full_attention"],
                            sliding_window=8), NotImplementedError),
    ("a convolution layer", dict(layer_types=["conv", "full_attention"],
                                 conv_L_cache=3), NotImplementedError),
    ("a linear layer", dict(layer_types=["linear_attention",
                                         "full_attention"],
                            linear_num_key_heads=2, linear_num_value_heads=2,
                            linear_key_head_dim=16, linear_value_head_dim=16,
                            linear_conv_kernel_dim=4), NotImplementedError),
    ("latent attention", dict(num_key_value_heads=4, kv_lora_rank=16,
                              q_lora_rank=16, qk_nope_head_dim=16,
                              qk_rope_head_dim=8, v_head_dim=16),
     NotImplementedError),
    ("a prediction module", dict(num_nextn_predict_layers=1),
     NotImplementedError),
    ("a loop", dict(total_ut_steps=2, exit_gate="sigmoid",
                    num_dense_layers=2), NotImplementedError),
    ("a tied head", dict(tie_word_embeddings=True), NotImplementedError),
    ("no block length", dict(block_length=None), ValueError),
    ("a block that does not cut the length", dict(block_length=5),
     ValueError),
    ("another objective", dict(objective="masked_lm"), NotImplementedError),
    ("a block length without the objective",
     dict(objective="next_token"), ValueError),
])
def test_every_unbuilt_combination_raises(what, over, error):
    with pytest.raises(error):
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard():
            decoder.decoder(max_length=L, **config(**over))


def test_the_attention_operator_lowers_under_a_scope_of_its_own():
    cfg = config(**SHARES["rank-1-of-2"])
    got, _ = system(arguments(cfg), noised_batch(n=1, seed=11), fetch=FETCH)
    scopes = [op.attrs.get("__name_scope__", "") for b in got["main"].blocks
              for op in b.ops]
    assert sum(s == "block_diffusion_attention" for s in scopes) > 0
    flash = [op for b in got["main"].blocks for op in b.ops
             if op.type == "flash_attention"]
    assert len(flash) == 2
    for op in flash:
        assert op.attrs["block_diffusion"] == B and not op.attrs["causal"]
        assert op.attrs["__name_scope__"] == "block_diffusion_attention"
    ropes = [op for b in got["main"].blocks for op in b.ops
             if op.type == "rope"]
    assert ropes and all(op.attrs["period"] == L for op in ropes)


def test_the_training_program_tracks_the_loss_and_the_masked_share():
    """`scalar.diffusion_loss` and `scalar.masked_share` in the
    telemetry, and one AdamW step runs through the whole Program."""
    cfg = config(**SHARES["rank-1-of-2"])
    feed = noised_batch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = decoder.build_model(max_length=L, warmup_steps=1, use_amp=False,
                                **NO_AUX, **cfg)
        assert set(main._tracked_scalars) >= {"diffusion_loss",
                                              "masked_share", "ce_loss"}
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        first, = exe.run(main, feed=feed, scope=scope, fetch_list=[m["loss"]])
        for _ in range(3):
            last, = exe.run(main, feed=feed, scope=scope,
                            fetch_list=[m["loss"]])
    assert np.isfinite(first).all()
    assert float(np.asarray(last).reshape(-1)[0]) \
        < float(np.asarray(first).reshape(-1)[0])


def test_a_share_under_block_diffusion_runs_the_expert_op_every_share_has():
    """A quarter of the rows hold the mask id and take the same experts
    (PERF.md, PR 47): that is the deployment's to place, not the op's
    to absorb.  The objective gives the share's expert op nothing of
    its own: the attributes of a next-token share, to the value."""
    def moe_attrs(**over):
        with fluid.program_guard(fluid.Program(), fluid.Program()), \
                fluid.unique_name.guard():
            decoder.decoder(max_length=L, **config(**over))
            main = fluid.default_main_program()
            return [dict(op.attrs) for b in main.blocks for op in b.ops
                    if op.type == "moe_dropless"]

    share = moe_attrs(**SHARES["rank-1-of-2"])
    assert len(share) == 2
    assert share == moe_attrs(objective="next_token", block_length=None,
                              **SHARES["rank-1-of-2"])
    assert not any("row_buffer" in key for a in share for key in a)
