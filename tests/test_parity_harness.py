"""What `tests/parity_harness.py` remembers and what it builds again:
an equal key is the SAME result and no build, a key that differs in one
argument, in the feed, in the seed or in the precision is another build,
and what comes back refuses a write.  One real build of the smallest
dense decoder; the keys on a counting stand-in for the builder.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import parity_harness as harness
from paddle_tpu.observe.monitoring import runtime_stats

ARGUMENTS = dict(hidden_size=32, num_hidden_layers=1, num_attention_heads=2,
                 num_key_value_heads=2, intermediate_size=32, num_experts=0,
                 num_experts_per_tok=0, norm_topk_prob=False,
                 num_dense_layers=1, vocab_size=32, rope_theta=100.0,
                 rms_norm_eps=1e-6)
SQUARES = harness.Family(
    lambda params, cfg: [jnp.asarray(p) for p in params],
    lambda tree, tokens, labels, cfg, scale=1.0: (
        (scale * sum(jnp.sum(p * p) for p in tree), {"tokens": tokens}),
        [2 * scale * p for p in tree]),
    lambda grads, cfg: grads)


def test_an_equal_key_is_the_same_result_and_no_build():
    feed = harness.batch(ARGUMENTS, n=1, length=8)
    got, params = first = harness.system(ARGUMENTS, feed)
    before = runtime_stats.snapshot()
    # a default spelled out and a default left out are one key
    again = harness.system(
        dict(ARGUMENTS, recompute=None, qk_norm="projection"),
        harness.batch(ARGUMENTS, n=1, length=8), use_amp=False, seed=7)
    took = runtime_stats.delta(before)
    assert again is first
    assert (took["builds"], took["compiles"]) == (0, 0)
    assert got["took"]["builds"] == 2           # start-up and the step
    assert len(got["grads"]) == len(params) == len(got["names"])
    for array in [got["loss"], got["logits"], *got["grads"], *params]:
        assert isinstance(array, np.ndarray)
        with pytest.raises(ValueError, match="read-only"):
            array[...] = 0
    # the reference, by its family, its parameters and its keywords
    want = harness.reference(SQUARES, ARGUMENTS, feed, params)
    assert harness.reference(SQUARES, ARGUMENTS, feed, params,
                             scale=None) is want
    other = harness.reference(SQUARES, ARGUMENTS, feed, params, scale=2.0)
    assert other is not want
    np.testing.assert_allclose(other[0], 2 * want[0], rtol=1e-6)
    moved = [p + 1 for p in params]
    assert harness.reference(SQUARES, ARGUMENTS, feed, moved) is not want
    with pytest.raises((TypeError, ValueError)):
        np.asarray(want[2][0])[...] = 0


def test_a_key_that_differs_in_one_place_builds_again(monkeypatch):
    built = []

    def counted(arguments, feed, *rest):
        built.append((dict(arguments), rest))
        return {"loss": np.zeros(1), "n": len(built)}, [np.ones(2)]

    monkeypatch.setattr(harness, "build_and_run", counted)
    monkeypatch.setattr(harness, "_REMEMBERED", {})
    arguments = dict(ARGUMENTS)
    feed = harness.batch(arguments, n=1, length=8)
    first = harness.system(arguments, feed)
    for change in (dict(arguments=dict(arguments, recompute="layer")),
                   dict(arguments=dict(arguments, rope_theta=50.0)),
                   dict(feed=harness.batch(arguments, n=1, length=8, seed=1)),
                   dict(feed=harness.batch(arguments, n=2, length=8)),
                   dict(seed=8), dict(use_amp=True), dict(fetch=("loss",)),
                   dict(params=[np.ones(2)])):
        call = dict(arguments=arguments, feed=feed)
        call.update(change)
        n = len(built)
        other = harness.system(**call)
        assert len(built) == n + 1, change
        assert other is not first
        assert harness.system(**call) is other and len(built) == n + 1
    assert harness.system(arguments, feed) is first
    with pytest.raises(ValueError, match="read-only"):
        first[1][0][0] = 0
