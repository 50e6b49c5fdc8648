"""The decoder-only MoE model (`models/decoder.py`: RoPE, QK-norm,
RMSNorm, dropless top-k SwiGLU experts, Pallas flash attention in
interpret mode) against its plain float32 reference
(`models/decoder_reference.py`) on the CPU at a small size, seeded
random weights, AMP off: logits, the three-term loss, both auxiliary
losses, per-expert counts, each token's experts, and the gradient of
every parameter.

Tolerance.  Both sides compute in float32 with matmuls at "highest"
precision (tests/conftest.py); they differ in summation order only
(online soft-max against a full one, sorted rows against a dense
loop), which at these sizes is a few float32 ulps of values of order
1: 5e-6 absolute-or-relative holds with a twentyfold margin (largest
seen: 2.0e-7).  bfloat16 has an 8-bit mantissa (relative step 4e-3):
`test_bf16_compute_fails_the_tolerance` computes the same step under
AMP and shows it misses the tolerance by orders of magnitude, so the
bound cannot be met by computing in a lower precision.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import decoder, decoder_reference as ref

from parity_harness import Family, batch, close, reference, system

TOL = 5e-6
SIZES = {
    "8-experts-top-2": dict(num_experts=8, num_experts_per_tok=2),
    "64-experts-top-8": dict(num_experts=64, num_experts_per_tok=8),
}
WEIGHTS = dict(aux_loss_weight=0.01, z_loss_weight=0.001)


def config(**over):
    cfg = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=32,
               norm_topk_prob=False, rope_theta=10000.0,
               rms_norm_eps=1e-5, vocab_size=128,
               tie_word_embeddings=False)
    cfg.update(over)
    return cfg


def arguments(cfg):
    return dict(cfg, **WEIGHTS)


def _to_list(grads, cfg):
    flat = [grads["embed"]]
    for layer in grads["layers"]:
        flat += [layer[k] for k in ref.LAYER_KEYS]
    return flat + [grads["final_norm"], grads["head"]]


FAMILY = Family(
    lambda params, cfg: ref.params_from_list(params,
                                             cfg["num_hidden_layers"]),
    ref.loss_and_grads, _to_list)
FETCH = ("loss", "logits", "ce", "aux", "z")


@pytest.mark.parametrize("size", sorted(SIZES))
def test_program_matches_the_float32_reference(size):
    cfg = config(**SIZES[size])
    feed = batch(cfg)
    got, params = system(arguments(cfg), feed, fetch=FETCH)
    total, parts, grads = reference(FAMILY, cfg, feed, params, **WEIGHTS)
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    close(got["ce"], parts["ce"], "cross-entropy")
    close(got["aux"], parts["aux"], "load-balancing loss")
    close(got["z"], parts["z"], "router z-loss")
    k = cfg["num_experts_per_tok"]
    tokens = feed["tokens"].size
    for i in range(cfg["num_hidden_layers"]):
        np.testing.assert_array_equal(got["counts"][i],
                                      np.asarray(parts["counts"][i]))
        assert got["counts"][i].sum() == tokens * k      # dropless
        np.testing.assert_array_equal(
            np.sort(got["experts"][i], axis=-1),
            np.sort(np.asarray(parts["experts"][i]), axis=-1))
    assert len(got["grads"]) == len(grads) == len(params)
    for i, (g, w) in enumerate(zip(got["grads"], grads)):
        assert np.abs(np.asarray(w)).max() > 0, i   # no vacuous match
        close(g, w, f"gradient of parameter {i}")


def test_bf16_compute_fails_the_tolerance():
    cfg = config(**SIZES["8-experts-top-2"])
    feed = batch(cfg)
    got, params = system(arguments(cfg), feed, use_amp=True, fetch=FETCH)
    _, parts, _ = reference(FAMILY, cfg, feed, params, **WEIGHTS)
    err = np.abs(np.asarray(got["logits"], np.float32)
                 - np.asarray(parts["logits"])).max()
    assert err > 20 * TOL, err


def test_tied_head_and_renormalised_top_k_follow_their_keys():
    cfg = config(tie_word_embeddings=True, norm_topk_prob=True,
                 num_hidden_layers=1, **SIZES["8-experts-top-2"])
    feed = batch(cfg, seed=3)
    got, params = system(arguments(cfg), feed, fetch=FETCH)
    # no separate head: the reference reads the embedding transposed
    tree = ref.params_from_list(params + [np.zeros(1)], 1)
    parts = ref.forward(tree, jnp.asarray(feed["tokens"]), cfg)
    close(got["logits"], parts["logits"], "logits")


def test_training_step_learns_and_counts_on_the_device():
    """The whole training Program (AdamW, clip, schedule, bf16 AMP):
    the loss falls, the device-side counters add up to steps x T x k,
    and the auxiliary losses ride the telemetry accumulator."""
    from paddle_tpu.observe import routing
    from paddle_tpu.observe.metrics import enable_telemetry, fetch_telemetry

    cfg = config(**SIZES["8-experts-top-2"])
    feed = batch(cfg, n=4)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = decoder.build_model(max_length=32, learning_rate=1e-2,
                                warmup_steps=5, **cfg)
        enable_telemetry(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [float(exe.run(main, feed=feed, fetch_list=[m["loss"]],
                                scope=scope)[0].reshape(()))
                  for _ in range(8)]
    assert losses[-1] < losses[0] - 0.5
    counts = routing.expert_token_counts(scope)
    assert len(counts) == cfg["num_hidden_layers"]
    for c in counts.values():
        assert c.sum() == 8 * feed["tokens"].size * 2
    ratio = routing.load_max_over_mean(counts)
    assert 1.0 <= ratio <= cfg["num_experts"]
    tel = fetch_telemetry(scope).as_dict()
    assert set(tel["scalars"]) == {"ce_loss", "moe_aux_loss", "moe_z_loss"}
    assert tel["scalars"]["moe_aux_loss"]["mean"] >= 1.0 - 1e-3
    assert tel["scalars"]["ce_loss"]["last"] == pytest.approx(
        losses[-1], abs=0.1)
    # reading with reset starts the counters again
    routing.expert_token_counts(scope, reset=True)
    assert all(c.sum() == 0
               for c in routing.expert_token_counts(scope).values())
