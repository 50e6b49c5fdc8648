"""A latent-attention expert decoder with a prediction module on the
normal path (`models/decoder.py` under the keys `kv_lora_rank` ...,
`n_shared_experts`, `num_nextn_predict_layers`, sigmoid routing with a
selection bias, as one expert-parallel rank's share) against the plain
float32 reference `benchmarks/reference_joyai.py` on the CPU at a small
size, seeded random weights, AMP off: logits of the main model and of
the module, both losses, the held experts' counts, each token's
experts, the gradient of every parameter, one AdamW step and the
selection bias's update.

The reference keeps the published parameter layout (a head's unrotated
and rotary parts, its key and its value side by side); the system's
column blocks are mapped onto it by `params_from_list` and back by
`grads_to_list`.

Tolerance.  As tests/test_lfm2_parity.py: both sides are float32 with
matmuls at "highest" and differ in summation order only; 5e-6
absolute-or-relative.
"""

import functools
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import decoder

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

import reference_joyai as ref  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import (Family, draw_expert_biases,  # noqa: E402
                            expert_bias_names, reference, system)

TOL = 5e-6
LAMBDA = 0.3
# the published keys, as the configuration file spells them (the
# reference reads these) ...
CONFIG = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=4, intermediate_size=96,
              moe_intermediate_size=32, n_routed_experts=2,
              num_experts_per_tok=3, norm_topk_prob=True,
              routed_scaling_factor=2.5, first_k_dense_replace=1,
              rms_norm_eps=1e-6, rope_theta=32000000, rope_interleave=True,
              vocab_size=96, kv_lora_rank=24, q_lora_rank=40,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              n_shared_experts=1, num_nextn_predict_layers=1,
              expert_parallel_size=4, expert_parallel_rank=1)
# ... and what the family file's map makes of the ones the builder
# spells otherwise (benchmarks/models/joyai_llm_flash.py)
RENAMED = {"n_routed_experts": "num_experts",
           "first_k_dense_replace": "num_dense_layers"}
EQUATIONS = dict(router="sigmoid", use_expert_bias=True,
                 norm_topk_eps=1e-20)
NO_AUX = dict(aux_loss_weight=0.0, z_loss_weight=0.0)


def builder_args(cfg):
    return dict({RENAMED.get(k, k): v for k, v in cfg.items()}, **EQUATIONS)


def arguments(cfg):
    return dict(builder_args(cfg), mtp_loss_weight=LAMBDA, **NO_AUX)


FAMILY = Family(ref.params_from_list, ref.loss_and_grads, ref.grads_to_list)
FETCH = ("loss", "ce", "mtp_ce", "logits", "mtp_logits")
batch = functools.partial(harness.batch, length=16, ahead=2)
close = functools.partial(harness.close, tol=TOL)


def float32_run():
    """The float32 run of the system and of the reference on it that
    most tests read (the harness remembers both)."""
    feed = batch(CONFIG)
    got, params = system(arguments(CONFIG), feed, fetch=FETCH,
                         after_startup=draw_expert_biases)
    return feed, got, params, got["drawn"], reference(
        FAMILY, CONFIG, feed, params, mtp_loss_weight=LAMBDA,
        drawn=got["drawn"])


def test_the_builders_creation_order_is_the_references_names():
    """`system_names` is how the reference finds each parameter: the
    system's own names must say the same thing, one for one."""
    _, got, params, _, _ = float32_run()
    names = ref.system_names(CONFIG)
    assert len(names) == len(got["names"]) == len(params)
    marks = {"wq_a": "attn_q_a", "wq_b": "attn_q_b", "wkv_a": "attn_kv_a",
             "wkv_b": "attn_kv_b", "wo": "attn_out", "router": "moe_gate",
             "shared_w1": "shared_expert/ffn_in",
             "shared_w2": "shared_expert/ffn_out", "eh": "mtp_eh",
             "embed": "tok_embedding", "head": "lm_head"}
    for mine, theirs in zip(names, got["names"]):
        assert theirs.startswith("mtp/") == mine.startswith("mtp."), \
            (mine, theirs)
        key = mine.split(".")[-2 if mine.split(".")[-1] in (
            "nope", "rope", "latent", "key", "value") else -1]
        if key in marks:
            assert marks[key] in theirs, (mine, theirs)
    shapes = dict(zip(names, (p.shape for p in params)))
    assert shapes["layer1.wq_b.rope"] == (40, 4 * 8)
    assert shapes["layer1.wkv_a.rope"] == (64, 8)      # ONE rotary key head
    assert shapes["layer1.wkv_b.value"] == (24, 4 * 16)
    assert shapes["layer1.router"] == (64, 8)          # all 8 experts wide
    assert shapes["layer1.w1"] == (2, 64, 32)          # 2 of them held
    assert shapes["mtp.eh"] == (128, 64)


def test_program_matches_the_float32_reference():
    feed, got, params, biases, (total, parts, grads) = float32_run()
    close(got["logits"], parts["logits"], "logits")
    close(got["mtp_logits"], parts["mtp_logits"], "the module's logits")
    close(got["ce"], parts["ce"], "main loss")
    close(got["mtp_ce"], parts["mtp_ce"], "the module's loss")
    close(got["loss"], total, "objective")
    close(got["loss"], got["ce"] + LAMBDA * got["mtp_ce"], "ce + lambda mtp")
    assert len(got["counts"]) == 2      # the routed layer and the module's
    tokens = feed["tokens"].size
    for i in range(2):
        np.testing.assert_array_equal(got["counts"][i],
                                      np.asarray(parts["counts"][i]))
        assert got["counts"][i].shape == (2,)
        assert 0 < got["counts"][i].sum() < tokens * 3      # a share
        np.testing.assert_array_equal(
            np.sort(got["experts"][i], axis=-1),
            np.sort(np.asarray(parts["experts"][i]), axis=-1))
    names = ref.system_names(CONFIG)
    assert len(got["grads"]) == len(grads) == len(names)
    for name, g, w in zip(names, got["grads"], grads):
        # no vacuous match, but for a share's routers (held constant by
        # the builder on both sides: no exchange sums the ranks' parts)
        assert (np.abs(np.asarray(w)).max() > 0) \
            != name.endswith(".router"), name
        close(g, w, f"gradient of {name}")


def test_the_reference_in_blocks_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/joyai_parity.py` runs on the chip so that 8192
    positions fit."""
    feed, _, params, biases, (plain, _, want) = float32_run()
    blocked, _, got = reference(FAMILY, CONFIG, feed, params,
                                mtp_loss_weight=LAMBDA, drawn=biases,
                                q_block=4)
    close(blocked, plain, "loss")
    for w, g in zip(want, got):
        close(g, w, "gradient")


def test_the_mapping_between_the_two_layouts_is_a_permutation():
    """System list -> published layout -> system list gives the list
    back, and a head's parts lie side by side in the published one."""
    _, _, params, biases, _ = float32_run()
    tree = ref.params_from_list(params, CONFIG, biases)
    for a, b in zip(ref.grads_to_list(tree, CONFIG), params):
        np.testing.assert_array_equal(np.asarray(a), b)
    names = ref.system_names(CONFIG)
    by_name = dict(zip(names, params))
    wq_b = np.asarray(tree["layers"][1]["wq_b"]).reshape(40, 4, 24)
    np.testing.assert_array_equal(
        wq_b[:, 2, :16], by_name["layer1.wq_b.nope"][:, 32:48])
    np.testing.assert_array_equal(
        wq_b[:, 2, 16:], by_name["layer1.wq_b.rope"][:, 16:24])
    wkv_b = np.asarray(tree["mtp"]["block"]["wkv_b"]).reshape(24, 4, 32)
    np.testing.assert_array_equal(
        wkv_b[:, 3, 16:], by_name["mtp.block.wkv_b.value"][:, 48:64])


def test_bf16_compute_fails_the_tolerance():
    feed, _, _, _, (_, parts, _) = float32_run()
    got, _ = system(arguments(CONFIG), feed, use_amp=True, fetch=FETCH,
                    after_startup=draw_expert_biases)     # the same seed
    for key in ("logits", "mtp_logits"):
        err = np.abs(np.asarray(got[key], np.float32)
                     - np.asarray(parts[key])).max()
        assert err > 20 * TOL, (key, err)


def test_one_adamw_step_and_the_bias_update_follow_the_reference():
    """The training Program (AMP off, so that the step is compared and
    not bfloat16; the cell's AMP step is rehearsed in
    tests/benchmark/test_joyai_cell.py): after one step every parameter
    is where AdamW puts
    it from the REFERENCE's clipped gradient at the step's own learning
    rate, and every selection bias has moved against its experts' load
    as the reference counts it."""
    lr, b1, b2, eps, decay, clip, rate = 3e-3, 0.9, 0.95, 1e-4, 0.1, 1.0, 1e-3
    feed = batch(CONFIG)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = decoder.build_model(
            max_length=16, learning_rate=lr, beta1=b1, beta2=b2,
            epsilon=eps, weight_decay=decay, clip_norm=clip, warmup_steps=2,
            use_amp=False, mtp_loss_weight=LAMBDA,
            expert_bias_update_rate=rate, **NO_AUX, **builder_args(CONFIG))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        bias_names = expert_bias_names(main)
        biases = draw_expert_biases(main, scope, 5)
        names = [p.name for p in main.all_parameters()]
        before = [np.asarray(scope.find_var(n)).copy() for n in names]
        adam = [o for o in main.global_block().ops if o.type == "adam"][0]
        lr_now, = exe.run(main, feed=feed, scope=scope,
                          fetch_list=[adam.desc.inputs["LearningRate"][0]])
        after = [np.asarray(scope.find_var(n)) for n in names]
        moved = [np.asarray(scope.find_var(n)) for n in bias_names]
        losses = [float(exe.run(main, feed=feed, scope=scope,
                                fetch_list=[m["loss"]])[0][0])
                  for _ in range(4)]
    # the same Program goes on learning, and the module's loss is
    # among the tracked scalars beside the main one
    assert losses[-1] < losses[0]
    assert main._tracked_scalars == {"ce_loss": m["ce"].name,
                                     "mtp_loss": m["mtp_ce"].name}
    lr_now = float(np.asarray(lr_now).reshape(-1)[0])
    assert 0 < lr_now <= lr
    _, parts, grads = reference(FAMILY, CONFIG, feed, before,
                                mtp_loss_weight=LAMBDA, drawn=biases)
    grads = [np.asarray(g, np.float64) for g in grads]
    norm = np.sqrt(sum((g * g).sum() for g in grads))
    assert norm > clip                  # the clip is in the comparison
    step = lr_now * np.sqrt(1 - b2) / (1 - b1)
    for name, p, q, g in zip(ref.system_names(CONFIG), before, after, grads):
        g = g.reshape(p.shape) * clip / max(norm, clip)
        want = (p - step * (1 - b1) * g / (np.sqrt((1 - b2) * g * g) + eps)
                - lr_now * decay * p)
        np.testing.assert_allclose(q, want, rtol=2e-5, atol=2e-7,
                                   err_msg=name)
    assert len(moved) == 2
    for b0, b1_, chosen in zip(biases, moved, parts["experts"]):
        want = ref.bias_update(jnp.asarray(b0), chosen, rate)
        np.testing.assert_allclose(b1_, want, atol=1e-7)
        assert (b1_ != b0).any()


@pytest.mark.parametrize("what, over", [
    ("kv_lora_rank", dict(kv_lora_rank=None)),
    ("num_key_value_heads", dict(num_key_value_heads=2)),
    ("chained", dict(num_nextn_predict_layers=2)),
    ("rope_scaling", dict(rope_scaling={"type": "yarn", "factor": 40})),
])
def test_a_value_that_is_not_built_is_refused_not_guessed(what, over):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises((NotImplementedError, ValueError), match=what):
            decoder.decoder(max_length=8,
                            **builder_args(dict(CONFIG, **over)))
