"""A looped language model on the normal path (`models/decoder.py`
under `total_ut_steps`, `sandwich_norm`, `qk_norm=None`,
`exit_gate="sigmoid"`: one stack of layers run R times over shared
weights as ONE sub-block, `lax.scan`) against the plain float32
reference `benchmarks/reference_ouro.py` (a Python `for`, no scan) on
the CPU at a small size, seeded random weights: the logits of EVERY
trip, the (R, N, T) exit distribution, the loss, the gradient of every
parameter leaf, one AdamW step.

Tolerances.  float32: both sides are float32 with matmuls at "highest"
and differ in summation order; 2e-5
absolute-or-relative, four times tests/test_joyai_parity.py's, because
R x L = 8 layer passes compound it where that model has 3.  bf16 AMP:
the bands of `benchmarks/ouro_parity.py` at this size (stated there);
and float32 is tight enough that bf16 compute fails it by 20 times.
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

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

import reference_ouro as ref  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import Family, reference, system  # noqa: E402

TOL = 2e-5
BETA = 0.1
# the published keys, as the configuration file spells them
CONFIG = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
              num_key_value_heads=4, intermediate_size=96, vocab_size=96,
              rms_norm_eps=1e-6, rope_theta=1000000, total_ut_steps=4)
# what the family file adds (benchmarks/models/ouro.py)
EQUATIONS = dict(qk_norm=None, sandwich_norm=True, exit_gate="sigmoid")


def builder_args(cfg, **over):
    return dict(cfg, num_experts=0, num_experts_per_tok=0,
                norm_topk_prob=False,
                num_dense_layers=cfg["num_hidden_layers"],
                exit_entropy_weight=BETA, **dict(EQUATIONS, **over))


def draw_gates(main, scope, seed):
    """The gate's bias starts at 0; give it a value, so that the
    comparison sees it."""
    (name,) = [n for n in main.global_block().vars
               if n.endswith("exit_gate.b_0")]
    scope.set_var(name, np.random.default_rng(seed).normal(
        0, 0.5, (1,)).astype(np.float32))


FAMILY = Family(
    lambda params, cfg: ref.params_from_list(params,
                                             cfg["num_hidden_layers"]),
    ref.loss_and_grads, lambda grads, cfg: ref.grads_to_list(grads))
FETCH = ("loss", "logits", "exit_p", "ut_ce", "ut_exit_p")
batch = functools.partial(harness.batch, length=16)
close = functools.partial(harness.close, tol=TOL)


def ops(got):
    return [len(b.ops) for b in got["main"].blocks]


def float32_run():
    """The float32 run of the system and of the reference on it that
    most tests read (the harness remembers both)."""
    feed = batch(CONFIG)
    got, params = system(builder_args(CONFIG), feed, fetch=FETCH,
                         after_startup=draw_gates)
    return feed, got, params, reference(FAMILY, CONFIG, feed, params,
                                        beta=BETA)


def test_the_builders_creation_order_is_the_references_keys():
    _, got, params, _ = float32_run()
    marks = {"attn_norm": "rms_norm", "wq": "attn_qkv", "wk": "attn_qkv",
             "wv": "attn_qkv", "wo": "attn_out", "w1": "ffn_in",
             "w3": "ffn_in", "w2": "ffn_out", "ffn_post_norm": "rms_norm"}
    names = got["names"]
    assert names[0] == "tok_embedding.w"
    per = len(ref.LAYER_KEYS)
    assert len(names) == 1 + 2 * per + len(ref.TAIL_KEYS)
    for i, key in enumerate(ref.LAYER_KEYS * 2):
        assert "ut_loop/" in names[1 + i]
        assert marks.get(key, "rms_norm") in names[1 + i], (key, names[1 + i])
    tail = names[-4:]
    assert all("exit_head" in n for n in tail)
    assert "lm_head" in tail[1] and "exit_gate.w" in tail[2] \
        and "exit_gate.b" in tail[3]
    assert params[-2].shape == (64, 1) and params[-1].shape == (1,)


def test_program_matches_the_float32_reference_at_every_trip():
    _, got, _, (total, parts, grads) = float32_run()
    trips = CONFIG["total_ut_steps"]
    assert got["logits"].shape == (trips, 2, 16, 96)
    for r in range(trips):
        close(got["logits"][r], parts["logits"][r], f"logits of trip {r + 1}")
    close(got["exit_p"], parts["p"], "exit distribution")
    np.testing.assert_allclose(got["exit_p"].sum(axis=0), 1.0, atol=1e-6)
    assert got["exit_p"].min() > 0.01       # no trip is idle in the loss
    close(got["ut_ce"], np.asarray(parts["ce"]).mean(axis=(1, 2)),
          "mean cross-entropy of each trip")
    close(got["ut_exit_p"], np.asarray(parts["p"]).mean(axis=(1, 2)),
          "mean exit mass of each trip")
    close(got["loss"], total, "objective")
    assert len(got["grads"]) == len(grads) == len(got["names"])
    for name, g, w in zip(got["names"], got["grads"], grads):
        assert np.abs(np.asarray(w)).max() > 0, name       # no vacuous match
        close(g, w, f"gradient of {name}")


def test_a_shared_leafs_gradient_is_the_sum_over_trips_not_one_trips():
    """The reference with a copy of the stack FOR EACH TRIP (equal
    values, leaves of their own) gives each trip's part of a shared
    leaf's gradient: the system's is their sum, and no single part."""
    feed, got, params, _ = float32_run()
    trips, layers = CONFIG["total_ut_steps"], CONFIG["num_hidden_layers"]
    tree = ref.params_from_list(params, layers)
    tokens, labels = jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"])

    def untied(copies):
        with jax.default_matmul_precision("highest"):
            x = tree["embed"][tokens]
            ce, lam = [], []
            for stack in copies:
                for layer in stack:
                    x = ref.decoder_layer(x, layer, CONFIG)
                s = ref.rms_norm(x, tree["final_norm"],
                                 CONFIG["rms_norm_eps"])
                ce.append(ref.token_ce(s @ tree["head"], labels))
                lam.append(jax.nn.sigmoid((s @ tree["gate_w"])[..., 0]
                                          + tree["gate_b"][0]))
            p = ref.exit_distribution(jnp.stack(lam))
            return jnp.mean(jnp.sum(p * jnp.stack(ce), axis=0)
                            + BETA * jnp.sum(p * jnp.log(p), axis=0))

    parts = jax.jit(jax.grad(untied))([tree["layers"]] * trips)
    per = len(ref.LAYER_KEYS)
    for i, key in enumerate(ref.LAYER_KEYS * layers):
        name = got["names"][1 + i]
        each = [np.asarray(parts[r][i // per][key]) for r in range(trips)]
        mine = got["grads"][1 + i].reshape(each[0].shape)
        close(mine, sum(each), f"{name}: sum over trips")
        scale = np.abs(mine).max()
        for r in range(trips):
            # dropping trip r would be off by that trip's part
            assert np.abs(each[r]).max() > 1e-3 * scale, (name, r)
            assert np.abs(mine - each[r]).max() > 0.01 * scale, (name, r)


def test_the_reference_in_blocks_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/ouro_parity.py` runs on the chip so that 4096
    positions fit."""
    feed, _, params, (plain, _, want) = float32_run()
    blocked, _, got = reference(FAMILY, CONFIG, feed, params, beta=BETA,
                                q_block=4)
    close(blocked, plain, "loss")
    for w, g in zip(want, got):
        close(g, w, "gradient")


def test_recomputed_layer_passes_give_the_same_numbers():
    """`recompute="layer"` (the cell's): a layer pass and a trip's head
    keep their inputs alone; no value moves."""
    feed, want, params, _ = float32_run()
    got, _ = system(builder_args(CONFIG, recompute="layer"), feed,
                    fetch=FETCH, params=params)
    assert ops(got) == ops(want)
    np.testing.assert_array_equal(got["logits"], want["logits"])
    np.testing.assert_array_equal(got["loss"], want["loss"])
    for g, w in zip(got["grads"], want["grads"]):
        close(g, w, "gradient", tol=1e-6)


def test_one_trip_is_the_stack_built_the_old_way_bit_for_bit():
    """`total_ut_steps` 1 through the loop construct (a scan of one
    trip) against the same layers appended to the main block with no
    loop and no gate: the same logits and the same loss, bit for bit
    (one trip takes all the exit mass: the objective is its
    cross-entropy)."""
    feed = batch(CONFIG)
    one = dict(CONFIG, total_ut_steps=1)
    looped, params = system(builder_args(one), feed, fetch=FETCH,
                            after_startup=draw_gates)
    plain, _ = system(builder_args(one, exit_gate=None), feed, fetch=FETCH,
                      params=params[:-2])
    assert len(ops(plain)) == 1 and len(ops(looped)) == 2
    assert plain["names"] == [n.replace("ut_loop/", "").replace(
        "exit_head/", "").replace("rms_norm_0.w_0", "rms_norm_8.w_0")
        if "exit_head" in n else n.replace("ut_loop/", "")
        for n in looped["names"][:-2]]
    np.testing.assert_array_equal(looped["logits"][0], plain["logits"])
    np.testing.assert_array_equal(looped["loss"], plain["loss"])
    np.testing.assert_array_equal(looped["exit_p"], 1.0)
    for g, w in zip(looped["grads"][:-2], plain["grads"]):
        # a scan's backward pass sums in another order
        close(g, w, "gradient", tol=1e-6)
    assert not np.asarray(looped["grads"][-2]).any()    # an idle gate


def test_two_and_four_trips_from_one_set_of_weights_and_one_op_count():
    """The loop count is a hyper-parameter of the SAME weights, and the
    Program is no longer for a larger one."""
    feed, four, params, _ = float32_run()
    two_cfg = dict(CONFIG, total_ut_steps=2)
    two, _ = system(builder_args(two_cfg), feed, fetch=FETCH, params=params)
    assert ops(two) == ops(four)
    assert two["names"] == four["names"]
    total, parts, grads = reference(FAMILY, two_cfg, feed, params, beta=BETA)
    assert two["logits"].shape[0] == 2
    # the first two trips do not know how many follow
    np.testing.assert_array_equal(two["logits"], four["logits"][:2])
    close(two["loss"], total, "objective of two trips")
    assert abs(float(two["loss"][0]) - float(four["loss"][0])) > 1e-3
    for name, g, w in zip(two["names"], two["grads"], grads):
        close(g, w, f"gradient of {name}")


def test_bf16_amp_stays_in_its_bands_and_fails_the_float32_tolerance():
    """bf16 AMP reaches the body's ops (the error is bf16's, not
    float32's) and stays within the bands `benchmarks/ouro_parity.py`
    states for this depth."""
    feed, _, params, (total, parts, grads) = float32_run()
    got, _ = system(builder_args(CONFIG), feed, use_amp=True, fetch=FETCH,
                    params=params)
    for r in range(CONFIG["total_ut_steps"]):
        err = np.abs(np.asarray(got["logits"][r], np.float32)
                     - np.asarray(parts["logits"][r])).max()
        assert 20 * TOL < err < 0.08, (r, err)
    assert abs(float(got["loss"][0]) - float(total)) < 0.02
    for name, g, w in zip(got["names"], got["grads"], grads):
        w = np.asarray(w).reshape(-1)
        err = np.abs(np.asarray(g, np.float32).reshape(-1) - w).max()
        assert err < 0.3 * np.abs(w).max() + 1e-6, (name, err)


def test_one_adamw_step_follows_the_reference():
    """The training Program (AMP off): after one step every parameter,
    the shared ones too, is where AdamW puts it from the REFERENCE's
    clipped gradient; the per-trip scalars are tracked."""
    lr, b1, b2, eps, decay, clip = 3e-3, 0.9, 0.95, 1e-4, 0.1, 0.05
    feed = batch(CONFIG)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = decoder.build_model(
            max_length=16, learning_rate=lr, beta1=b1, beta2=b2,
            epsilon=eps, weight_decay=decay, clip_norm=clip, warmup_steps=2,
            use_amp=False, recompute="layer", **builder_args(CONFIG))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        draw_gates(main, scope, 5)
        names = [p.name for p in main.all_parameters()]
        before = [np.asarray(scope.find_var(n)).copy() for n in names]
        adam = [o for o in main.global_block().ops if o.type == "adam"][0]
        lr_now, = exe.run(main, feed=feed, scope=scope,
                          fetch_list=[adam.desc.inputs["LearningRate"][0]])
        after = [np.asarray(scope.find_var(n)) for n in names]
        losses = [float(exe.run(main, feed=feed, scope=scope,
                                fetch_list=[m["loss"]])[0][0])
                  for _ in range(4)]
    assert losses[-1] < losses[0]
    assert set(main._tracked_scalars) == (
        {"ce_loss", "ut_exit_entropy"}
        | {f"ut_ce_{r}" for r in range(1, 5)}
        | {f"ut_exit_p_{r}" for r in range(1, 5)})
    lr_now = float(np.asarray(lr_now).reshape(-1)[0])
    assert 0 < lr_now <= lr
    _, _, grads = reference(FAMILY, CONFIG, feed, before, beta=BETA)
    grads = [np.asarray(g, np.float64) for g in grads]
    norm = np.sqrt(sum((g * g).sum() for g in grads))
    assert norm > clip                  # the clip is in the comparison
    step = lr_now * np.sqrt(1 - b2) / (1 - b1)
    for name, p, q, g in zip(names, before, after, grads):
        g = g.reshape(p.shape) * clip / max(norm, clip)
        want = (p - step * (1 - b1) * g / (np.sqrt((1 - b2) * g * g) + eps)
                - lr_now * decay * p)
        np.testing.assert_allclose(q, want, rtol=5e-5, atol=5e-7,
                                   err_msg=name)


@pytest.mark.parametrize("what, over", [
    ("exit_gate", dict(exit_gate=None)),
    ("exit_gate", dict(exit_gate="softmax")),
    ("recompute", dict(recompute="everything")),
    ("inside the loop", dict(num_dense_layers=1, num_experts=4,
                             num_experts_per_tok=2)),
    ("inside the loop", dict(tie_word_embeddings=True)),
    ("total_ut_steps", dict(total_ut_steps=0)),
])
def test_a_value_that_is_not_built_is_refused_not_guessed(what, over):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises((NotImplementedError, ValueError), match=what):
            decoder.decoder(max_length=8, **dict(builder_args(CONFIG),
                                                 **over))
