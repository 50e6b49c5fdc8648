"""The tie of a share to the model where the routed layer has a SHARED
expert beside it (`models/decoder.py n_shared_experts`; the
`model-configs` guide, section 4): at a small size, a 16-wide sigmoid
router over 4 ranks of 4 experts, the routed parts of ALL the ranks
plus the shared expert COUNTED ONCE add up to the uncut layer of the
plain reference (`benchmarks/reference_joyai.py`), outputs and
gradients.  Every rank computes the shared expert alike, so summing
what each rank's program adds to its residual stream would count it
four times: that sum is checked too, as what it is.
"""

import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

import reference_joyai as ref  # noqa: E402

E, RANKS, K, D, H, T = 16, 4, 3, 16, 8, 40
HELD = E // RANKS
ATTRS = {"routing": "sigmoid", "norm_topk_prob": True, "top_k": K,
         "routed_scaling_factor": 2.5, "norm_topk_eps": 1e-20}
CFG = {"num_experts_per_tok": K, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5}
NAMES = ("x", "router", "w1", "w3", "w2", "shared_w1", "shared_w3",
         "shared_w2")
CTX = OpContext(jax.random.PRNGKey(0), 0)


def whole_layer(seed=0):
    r = np.random.default_rng(seed)

    def draw(*shape, scale=0.3):
        return jnp.asarray(r.normal(size=shape).astype(np.float32) * scale)

    return {"x": draw(T, D, scale=1.0), "router": draw(D, E, scale=0.25),
            "bias": draw(E, scale=0.1), "w1": draw(E, D, H),
            "w3": draw(E, D, H), "w2": draw(E, H, D),
            "shared_w1": draw(D, H), "shared_w3": draw(D, H),
            "shared_w2": draw(H, D)}


def routed_part(p, rank):
    """One rank's routed part, through the op the builder appends."""
    lo = rank * HELD
    o = get_op_impl("moe_dropless")(
        CTX, {"X": [p["x"]], "GateW": [p["router"]], "Bias": [p["bias"]],
              **{k.upper(): [p[k][lo:lo + HELD]]
                 for k in ("w1", "w3", "w2")}},
        dict(ATTRS, experts_held=[lo, HELD]))
    return o["Out"][0], o["Counts"][0]


def shared_expert(p):
    """The shared expert as the builder composes it: `mul`, `mul`,
    `swiglu`, `mul`."""
    def mul(a, b):
        return get_op_impl("mul")(CTX, {"X": [a], "Y": [b]}, {})["Out"][0]

    gate = get_op_impl("swiglu")(
        CTX, {"X": [mul(p["x"], p["shared_w1"])],
              "Y": [mul(p["x"], p["shared_w3"])]}, {})["Out"][0]
    return mul(gate, p["shared_w2"])


def uncut(p):
    """The whole layer of the reference: all 16 experts and the shared
    one."""
    with jax.default_matmul_precision("highest"):
        y, counts, _ = ref.experts(p["x"], p, CFG)
        return y + ref.swiglu(p["x"], p["shared_w1"], p["shared_w3"],
                              p["shared_w2"]), counts


def test_all_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    p = whole_layer()
    want, counts = uncut(p)
    parts = [routed_part(p, r) for r in range(RANKS)]
    shared = shared_expert(p)
    total = sum(np.asarray(y, np.float64) for y, _ in parts) \
        + np.asarray(shared, np.float64)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c) for _, c in parts]),
        np.asarray(counts))
    assert sum(int(c.sum()) for _, c in parts) == T * K
    # what the ranks' programs each add to their stream, summed: the
    # shared expert four times, which is why it counts ONCE
    streams = sum(np.asarray(y + shared, np.float64) for y, _ in parts)
    np.testing.assert_allclose(
        streams, np.asarray(want) + (RANKS - 1) * np.asarray(shared),
        rtol=2e-5, atol=2e-5)
    assert np.abs(np.asarray(shared)).max() > 0.1


def test_their_gradients_add_up_to_the_uncut_layers_too():
    """The ranks' parts of the gradient of the input and of the router
    plus the shared expert's own (once) are the uncut reference's; each
    rank's expert weights get the uncut gradient of those experts; the
    shared expert's weights get theirs from the one term."""
    p = whole_layer(3)
    ct = jnp.asarray(np.random.default_rng(4).normal(size=(T, D))
                     .astype(np.float32))

    def of(fn):
        def scalar(*vals):
            return jnp.sum(fn(dict(p, **dict(zip(NAMES, vals)))) * ct)

        return dict(zip(NAMES, jax.jit(jax.grad(
            scalar, argnums=range(len(NAMES))))(*[p[k] for k in NAMES])))

    want = of(lambda q: uncut(q)[0])
    once = of(shared_expert)
    summed = {k: np.asarray(once[k], np.float64) for k in NAMES}
    for rank in range(RANKS):
        got = of(lambda q, rank=rank: routed_part(q, rank)[0])
        for k in NAMES:
            summed[k] += np.asarray(got[k], np.float64)
        for k in ("shared_w1", "shared_w3", "shared_w2"):
            assert not np.asarray(got[k]).any()     # no routed part in it
    for k in NAMES:
        assert np.abs(np.asarray(want[k])).max() > 0, k
        np.testing.assert_allclose(summed[k], want[k], rtol=5e-5, atol=5e-5,
                                   err_msg=k)
    # the input's gradient needs both: neither term alone is the whole
    assert np.abs(np.asarray(once["x"]) - np.asarray(want["x"])).max() > 1e-3
