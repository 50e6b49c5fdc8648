"""The expert layer that holds a share (`ops/moe_dropless.py`,
`experts_held=(first, count)`), the sigmoid router with a selection
bias, the gated short convolution (`ops/decoder.py short_conv`) and
grouped-query flash attention at d_head 64
(`ops/pallas/flash_gqa.py`, interpret mode): each alone, against a form
written another way.

The share test is the one the `model-configs` guide (section 4) asks
for: at a small size, the partial results of ranks 0..7, each holding 8
of 64 experts, add up to what the uncut float32 reference gives for
the whole layer.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.models import decoder_reference as ref
from paddle_tpu.ops import moe_dropless

from op_test import run_op, with_pull_back

E, K, D, H, T = 64, 4, 16, 8, 48
ROUTING = {"routing": "sigmoid", "norm_topk_prob": True, "top_k": K}
CFG = {"num_experts_per_tok": K, "norm_topk_prob": True,
       "routed_scaling_factor": 1.0}


def R(seed):
    return np.random.default_rng(seed)


def whole_layer(seed=0):
    r = R(seed)
    f32 = np.float32
    return {"X": r.normal(size=(T, D)).astype(f32),
            "GateW": r.normal(size=(D, E)).astype(f32) * 0.25,
            "Bias": r.normal(0, 0.1, size=(E,)).astype(f32),
            "W1": r.normal(size=(E, D, H)).astype(f32) * 0.3,
            "W3": r.normal(size=(E, D, H)).astype(f32) * 0.3,
            "W2": r.normal(size=(E, H, D)).astype(f32) * 0.3}


def share_of(ins, first, count):
    cut = {k: ins[k][first:first + count] for k in ("W1", "W3", "W2")}
    return dict(ins, **cut)


def reference_layer(ins, rank=0, size=1):
    held = E // size
    layer = {"router": jnp.asarray(ins["GateW"]),
             "bias": jnp.asarray(ins["Bias"]),
             **{k.lower(): jnp.asarray(ins[k][rank * held:(rank + 1) * held])
                for k in ("W1", "W3", "W2")}}
    with jax.default_matmul_precision("highest"):
        return ref.lfm2_experts(jnp.asarray(ins["X"]), layer,
                                dict(CFG, expert_parallel_rank=rank))


def run(ins, attrs, **kw):
    impl = get_op_impl("moe_dropless")
    return impl(OpContext(jax.random.PRNGKey(0), 0),
                {k: [jnp.asarray(v)] for k, v in ins.items()}, attrs, **kw)


# --------------------------------------------------------------------------
# the share
# --------------------------------------------------------------------------

def test_the_shares_of_eight_ranks_add_up_to_the_uncut_reference():
    ins = whole_layer()
    want, counts, chosen = reference_layer(ins)
    total, rows = np.zeros((T, D), np.float64), 0
    for rank in range(8):
        o = run(share_of(ins, 8 * rank, 8),
                dict(ROUTING, experts_held=[8 * rank, 8]))
        part, c = np.asarray(o["Out"][0]), np.asarray(o["Counts"][0])
        # a rank's part is what the reference gives for ITS experts
        np.testing.assert_allclose(
            part, reference_layer(ins, rank, 8)[0], rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(
            c, np.asarray(counts)[8 * rank:8 * rank + 8])
        # every rank routes over all 64 and chooses alike
        np.testing.assert_array_equal(
            np.sort(np.asarray(o["Experts"][0]), -1),
            np.sort(np.asarray(chosen), -1))
        total += part
        rows += c.sum()
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert rows == T * K                      # no pair lost, none twice
    # and the layer that holds everything is the sum in one call
    whole = run(ins, ROUTING)["Out"][0]
    np.testing.assert_allclose(whole, want, rtol=2e-5, atol=2e-5)


def test_the_shares_of_a_softmax_routed_layer_add_up_to_the_uncut_layer():
    """The same under the SOFT-MAX router with renormalised top-k
    weights (`routing="softmax"`, `norm_topk_prob`), the combination a
    window / full attention MoE decoder holds a share under: the parts
    of 8 ranks, each holding 8 of 64 experts, add up to what the plain
    reference of that layer (`benchmarks/reference_mellum.py experts`,
    independent of the op) gives for all 64."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import reference_mellum

    ins = {k: v for k, v in whole_layer(3).items() if k != "Bias"}
    cfg = {"num_experts_per_tok": K, "norm_topk_prob": True}
    routing = {"routing": "softmax", "norm_topk_prob": True, "top_k": K}

    def reference(rank=0, size=1):
        held = E // size
        layer = {"router": jnp.asarray(ins["GateW"]),
                 **{k.lower(): jnp.asarray(
                     ins[k][rank * held:(rank + 1) * held])
                    for k in ("W1", "W3", "W2")}}
        with jax.default_matmul_precision("highest"):
            return reference_mellum.experts(
                jnp.asarray(ins["X"]), layer,
                dict(cfg, expert_parallel_rank=rank))

    want, counts, chosen = reference()
    total, rows = np.zeros((T, D), np.float64), 0
    for rank in range(8):
        o = run(share_of(ins, 8 * rank, 8),
                dict(routing, experts_held=[8 * rank, 8]))
        part, c = np.asarray(o["Out"][0]), np.asarray(o["Counts"][0])
        np.testing.assert_allclose(part, reference(rank, 8)[0], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_array_equal(
            c, np.asarray(counts)[8 * rank:8 * rank + 8])
        np.testing.assert_array_equal(
            np.sort(np.asarray(o["Experts"][0]), -1),
            np.sort(np.asarray(chosen), -1))
        total += part
        rows += c.sum()
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert rows == T * K
    # renormalised: a token's weights sum to 1, so with every expert
    # the identity the shares' sum is the input; unrenormalised it is
    # the chosen experts' soft-max mass, under 1
    whole = run(ins, routing)["Out"][0]
    np.testing.assert_allclose(whole, want, rtol=2e-5, atol=2e-5)
    loose = run(ins, dict(routing, norm_topk_prob=False))["Out"][0]
    assert np.abs(np.asarray(loose) - np.asarray(want)).max() > 1e-3


def test_sigmoid_routing_selects_on_the_bias_and_weighs_without_it():
    ins = whole_layer(1)
    o = run(ins, ROUTING)
    scores = 1 / (1 + np.exp(-(ins["X"].astype(np.float64)
                               @ ins["GateW"].astype(np.float64))))
    want = np.argsort(-(scores + ins["Bias"]), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.sort(np.asarray(o["Experts"][0]), -1),
                                  np.sort(want, -1))
    unbiased = np.argsort(-scores, axis=-1)[:, :K]
    assert (np.sort(want, -1) != np.sort(unbiased, -1)).any()
    # twice the scaling factor is twice the output: the weights are
    # scores / (sum + 1e-6) * factor, and the bias is in neither
    twice = run(ins, dict(ROUTING, routed_scaling_factor=2.0))["Out"][0]
    np.testing.assert_allclose(twice, 2 * np.asarray(o["Out"][0]),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="routing 'topk'"):
        run(ins, dict(ROUTING, routing="topk"))


def test_the_bias_update_moves_every_bias_against_its_experts_load():
    """`bias_update_rate` u: BiasOut = Bias + u * sign(mean load -
    load) over ALL experts' rows, also in a share (which routes over
    all of them); the choice of this step used the old bias; rate 0:
    no output, the bias stays."""
    ins = whole_layer(6)
    share = share_of(ins, 8, 8)
    attrs = dict(ROUTING, experts_held=[8, 8], bias_update_rate=0.01)
    o = run(share, attrs)
    _, counts, chosen = reference_layer(ins)
    np.testing.assert_array_equal(np.sort(np.asarray(o["Experts"][0]), -1),
                                  np.sort(np.asarray(chosen), -1))
    load = np.asarray(counts, np.float64)
    want = ins["Bias"] + 0.01 * np.sign(load.mean() - load)
    np.testing.assert_allclose(o["BiasOut"][0], want, rtol=0, atol=1e-7)
    assert (np.asarray(o["BiasOut"][0]) != ins["Bias"]).sum() >= 50
    assert "BiasOut" not in run(share, dict(ROUTING, experts_held=[8, 8]))
    # repeated, it evens the load: the fullest expert's lead shrinks
    bias, leads = ins["Bias"], []
    for _ in range(40):
        o = run(dict(ins, Bias=bias), dict(ROUTING, bias_update_rate=0.01))
        c = np.asarray(o["Counts"][0])
        leads.append(c.max() / c.mean())
        bias = np.asarray(o["BiasOut"][0])
    assert np.mean(leads[-5:]) < 0.7 * np.mean(leads[:5])


def _steer(ins, expert):
    """Inputs whose router sends every token to `expert` first."""
    gate = np.zeros_like(ins["GateW"])
    bias = np.zeros_like(ins["Bias"])
    bias[expert] = 50.0
    return dict(ins, GateW=gate, Bias=bias)


def test_all_tokens_to_one_held_expert_none_dropped_same_compiled_step():
    ins = share_of(whole_layer(2), 0, 8)
    attrs = dict(ROUTING, experts_held=[0, 8])
    impl = get_op_impl("moe_dropless")
    traces = []

    @jax.jit
    def f(ins, off):
        traces.append(1)
        o = impl(OpContext(jax.random.PRNGKey(0), 0),
                 {**{k: [v] for k, v in ins.items()},
                  "OffShareCount": [off]}, attrs)
        return o["Out"][0], o["Counts"][0], o["OffShareCountOut"][0]

    off = jnp.zeros((1,), jnp.int32)
    a = {k: jnp.asarray(v) for k, v in ins.items()}
    b = {k: jnp.asarray(v) for k, v in _steer(ins, 5).items()}
    (ya, ca, oa), (yb, cb, ob) = f(a, off), f(b, off)
    assert len(traces) == 1 and ya.shape == yb.shape == (T, D)
    # the spread router: some rows here, most elsewhere, all accounted
    assert 0 < int(ca.sum()) < T * K and int(ca.sum() + oa[0]) == T * K
    # every token's first choice is expert 5: T rows there, none lost
    assert int(cb[5]) == T and int(cb.sum() + ob[0]) == T * K
    assert np.abs(np.asarray(yb)).min(axis=-1).max() > 0    # every row


def test_all_tokens_to_experts_not_held_gives_zeros_and_finite_gradients():
    # zero gate, ties broken by the bias: experts 60..63 are chosen
    ins = share_of(whole_layer(3), 0, 8)
    ins = dict(ins, GateW=np.zeros_like(ins["GateW"]),
               Bias=np.arange(E, dtype=np.float32))
    attrs = dict(ROUTING, experts_held=[0, 8])
    o = run(ins, attrs)
    assert int(np.asarray(o["Counts"][0]).sum()) == 0
    assert not np.asarray(o["Out"][0]).any()

    def loss(x, gate, w1, w3, w2):
        o = run(dict(ins, X=x, GateW=gate, W1=w1, W3=w3, W2=w2), attrs)
        return jnp.sum(jnp.sin(o["Out"][0]) + o["Out"][0])

    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
        *[jnp.asarray(ins[k]) for k in ("X", "GateW", "W1", "W3", "W2")])
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert not np.asarray(g).any()        # nothing of it ran here


@pytest.mark.parametrize("router_gradient", [True, False])
def test_gradients_of_a_share_match_the_dense_form(router_gradient):
    """The op's backward is the rank's own part of every gradient, the
    router's among them; `router_gradient=False` (the caller's choice,
    not the share's) holds back what flows through the routing
    weights and nothing else."""
    ins = {k: jnp.asarray(v) for k, v in
           share_of(whole_layer(4), 16, 8).items()}
    attrs = dict(ROUTING, experts_held=[16, 8])
    if not router_gradient:
        attrs["router_gradient"] = False
    cfg = dict(CFG, expert_parallel_rank=2)
    names = ("X", "GateW", "W1", "W3", "W2")

    def system(*vals):
        o = run(dict(ins, **dict(zip(names, vals))), attrs)
        return jnp.sum(jnp.sin(o["Out"][0]))

    def dense(x, gate, w1, w3, w2):
        layer = {"router": gate, "bias": ins["Bias"], "w1": w1, "w3": w3,
                 "w2": w2}
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.sin(ref.lfm2_experts(
                x, layer, cfg, router_gradient=router_gradient)[0]))

    vals = [ins[k] for k in names]
    got = jax.grad(system, argnums=range(5))(*vals)
    want = jax.grad(dense, argnums=range(5))(*vals)
    for name, g, w in zip(names, got, want):
        # no vacuous match; the gate is reached through the routing
        # weights alone
        assert (np.abs(np.asarray(w)).max() > 0) == (
            router_gradient or name != "GateW"), name
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5,
                                   err_msg=name)


def test_the_gradients_of_eight_shares_add_up_to_the_uncut_references():
    """What an `ep` lowering's all-reduce would sum: the ranks' parts of
    the gradient of the layer's input and of the router add up to the
    uncut float32 reference's, and each rank's expert weights get the
    uncut reference's gradient of those experts."""
    ins = {k: jnp.asarray(v) for k, v in whole_layer(6).items()}
    ct = jnp.asarray(R(7).normal(size=(T, D)).astype(np.float32))
    names = ("X", "GateW", "W1", "W3", "W2")

    def uncut(x, gate, w1, w3, w2):
        layer = {"router": gate, "bias": ins["Bias"], "w1": w1, "w3": w3,
                 "w2": w2}
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref.lfm2_experts(x, layer, CFG)[0] * ct)

    # (each gradient compiled as one function: op by op, the 64
    # experts' loop compiles every primitive by itself)
    want = dict(zip(names, jax.jit(jax.grad(uncut, argnums=range(5)))(
        *[ins[k] for k in names])))
    summed = {k: np.zeros(ins[k].shape, np.float64) for k in ("X", "GateW")}
    for rank in range(8):
        mine = {k: jnp.asarray(v) for k, v in
                share_of(ins, 8 * rank, 8).items()}
        attrs = dict(ROUTING, experts_held=[8 * rank, 8])

        def part(*vals):
            o = run(dict(mine, **dict(zip(names, vals))), attrs)
            return jnp.sum(o["Out"][0] * ct)

        got = dict(zip(names, jax.jit(jax.grad(part, argnums=range(5)))(
            *[mine[k] for k in names])))
        for k in summed:
            summed[k] += np.asarray(got[k], np.float64)
        for k in ("W1", "W3", "W2"):
            np.testing.assert_allclose(
                got[k], want[k][8 * rank:8 * rank + 8], rtol=2e-5,
                atol=2e-5, err_msg=f"{k} of rank {rank}")
    for k, total in summed.items():
        assert np.abs(np.asarray(want[k])).max() > 0, k
        np.testing.assert_allclose(total, want[k], rtol=5e-5, atol=5e-5,
                                   err_msg=k)
        # and one rank's part alone is not the whole
        assert np.abs(np.asarray(got[k]) - np.asarray(want[k])).max() > 1e-3


@pytest.mark.parametrize("router_gradient", [True, False])
def test_the_router_learns_unless_the_caller_says_otherwise(router_gradient):
    """`router_gradient` is its own attribute: on the WHOLE layer too
    it decides whether the gate is reached, and its absence is the op
    as it was."""
    ins = {k: jnp.asarray(v) for k, v in whole_layer(5).items()}
    attrs = ROUTING if router_gradient else dict(ROUTING,
                                                 router_gradient=False)

    def loss(gate):
        return jnp.sum(jnp.sin(run(dict(ins, GateW=gate),
                                   attrs)["Out"][0]))

    def dense(gate):
        layer = {"router": gate, "bias": ins["Bias"], "w1": ins["W1"],
                 "w3": ins["W3"], "w2": ins["W2"]}
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.sin(ref.lfm2_experts(
                ins["X"], layer, CFG, router_gradient=router_gradient)[0]))

    got, want = jax.grad(loss)(ins["GateW"]), jax.grad(dense)(ins["GateW"])
    assert (np.abs(np.asarray(want)).max() > 0) == router_gradient
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("held, message", [
    ([60, 8], "outside the 64 experts"), ([0, 4], "weights of 8 experts")])
def test_a_share_that_does_not_fit_is_an_error(held, message):
    with pytest.raises(ValueError, match=message):
        run(share_of(whole_layer(), 0, 8),
            dict(ROUTING, experts_held=held))


# --------------------------------------------------------------------------
# the row buffer a share runs on
# --------------------------------------------------------------------------

TS = 640                  # tokens at which a share has three buffer sizes
R1, R2, R3 = SIZES = moe_dropless.row_buffer_sizes(TS, K, E, 8)
NAMES = ("X", "GateW", "W1", "W3", "W2")


def test_the_row_buffer_sizes_follow_the_expected_rows_in_whole_512s():
    assert SIZES == (512, 1024, TS * K)         # 1.5 x 320 and 3 x 320, up
    assert moe_dropless.row_buffer_sizes(8192, 4, 64, 8) == (
        6144, 12288, 32768)                     # lfm2-8k
    assert moe_dropless.row_buffer_sizes(T, K, E, 8) == (T * K,)
    assert moe_dropless.row_buffer_sizes(512, 4, 64, 24) == (1536, 2048)
    assert moe_dropless.row_buffer_sizes(4096, 8, 64, 64) == (4096 * 8,)


def steered(rows, seed=0, rank=2):
    """A whole layer of TS tokens whose router sends exactly `rows` of
    the TS*K (token, expert) rows to experts rank `rank` of 8 holds:
    feature j of a token says whether it picks held expert first + 2j
    (score 0.95 + a bias of 5 against 0.05 + 5); the places left go to
    experts 60..63 (0.5 + 5), which rank 7 holds.  Every chosen score
    is far from 0 and 1, so the router has a gradient."""
    r = R(seed)
    f32 = np.float32
    first = 8 * rank
    x = r.normal(size=(TS, D)).astype(f32)
    gate = r.normal(size=(D, E)).astype(f32) * 0.02
    picks = rows // TS + (np.arange(TS) < rows % TS)    # held picks a token
    x[:, :K] = np.where(np.arange(K) < picks[:, None], 1.0, -1.0)
    gate[:K] = 0
    gate[np.arange(K), first + 2 * np.arange(K)] = 3.0
    bias = np.zeros(E, f32)
    bias[first:first + 8:2] = bias[60:] = 5.0
    return {"X": x, "GateW": gate, "Bias": bias,
            "W1": r.normal(size=(E, D, H)).astype(f32) * 0.3,
            "W3": r.normal(size=(E, D, H)).astype(f32) * 0.3,
            "W2": r.normal(size=(E, H, D)).astype(f32) * 0.3}


def share_and_gradients(ins, attrs):
    """(out, counts, row-buffer slots, gradients of NAMES) of one jitted
    call of the op on a share's inputs."""
    fixed = {k: jnp.asarray(v) for k, v in ins.items()}

    def f(*vals):
        o = run(dict(fixed, **dict(zip(NAMES, vals)),
                     RowBufferCount=jnp.zeros((3,), jnp.int32)), attrs)
        out = o["Out"][0]
        return jnp.sum(jnp.sin(out)), (out, o["Counts"][0],
                                       o["RowBufferCountOut"][0])

    (_, aux), grads = jax.jit(jax.value_and_grad(
        f, argnums=range(5), has_aux=True))(*[fixed[k] for k in NAMES])
    return aux + (grads,)


@pytest.mark.parametrize("rows, slot", [
    (0, 0), (R1, 0), (R1 + 1, 1), (R2, 1), (R2 + 1, 2), (R3, 2)],
    ids=["no-row", "R1", "R1+1", "R2", "R2+1", "every-row"])
def test_a_share_on_the_smallest_buffer_that_fits_is_the_share(
        rows, slot, monkeypatch):
    """Whatever the routing sends a share, the op takes the smallest
    of its buffer sizes that holds the rows, and output and every
    gradient (the router's too) are those of the section on T*k rows
    and of the float32 reference's share; with every row held (the
    worst case) nothing is dropped."""
    whole = steered(rows, seed=rows)
    ins = share_of(whole, 16, 8)
    attrs = dict(ROUTING, experts_held=[16, 8])
    out, counts, slots, grads = share_and_gradients(ins, attrs)
    assert int(counts.sum()) == rows            # the routing is as forced
    assert np.asarray(slots).tolist() == np.eye(3, dtype=int)[slot].tolist()

    monkeypatch.setattr(moe_dropless, "row_buffer_sizes",
                        lambda t, k, e, count: (t * k,))
    full, _, full_slots, full_grads = share_and_gradients(ins, attrs)
    assert np.asarray(full_slots).tolist() == [1, 0, 0]     # its one size
    np.testing.assert_array_equal(out, full)
    for name, g, w in zip(NAMES, grads, full_grads):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6, err_msg=name)

    cfg = dict(CFG, expert_parallel_rank=2)

    def dense(x, gate, w1, w3, w2):
        layer = {"router": gate, "bias": jnp.asarray(ins["Bias"]),
                 "w1": w1, "w3": w3, "w2": w2}
        with jax.default_matmul_precision("highest"):
            y = ref.lfm2_experts(x, layer, cfg)[0]
        return jnp.sum(jnp.sin(y)), y

    (_, want), want_grads = jax.value_and_grad(
        dense, argnums=range(5), has_aux=True)(
            *[jnp.asarray(ins[k]) for k in NAMES])
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for name, g, w in zip(NAMES, grads, want_grads):
        assert (np.abs(np.asarray(w)).max() > 0) == (rows > 0), name
        np.testing.assert_allclose(g, w, rtol=5e-5, atol=5e-5, err_msg=name)
    if rows == R3:                              # no token went elsewhere
        assert np.abs(np.asarray(out)).min(axis=-1).min() > 0


def test_eight_shares_on_small_buffers_add_up_to_the_uncut_layer():
    """At TS tokens every rank has three sizes; a rank that gets a
    usual load runs on the first, the rank the routing is steered to
    on the last, and the parts still add up to the whole."""
    ins = steered(1600, seed=11)
    want, counts, _ = reference_layer(ins)
    total, taken = np.zeros((TS, D), np.float64), []
    for rank in range(8):
        o = run(dict(share_of(ins, 8 * rank, 8),
                     RowBufferCount=np.zeros(3, np.int32)),
                dict(ROUTING, experts_held=[8 * rank, 8]))
        np.testing.assert_array_equal(
            o["Counts"][0], np.asarray(counts)[8 * rank:8 * rank + 8])
        total += np.asarray(o["Out"][0])
        taken.append(int(np.argmax(o["RowBufferCountOut"][0])))
    assert taken == [0, 0, 2, 0, 0, 0, 0, 1]    # rank 7: the other 960 rows
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)


def test_the_switch_keeps_no_sorted_rows_for_the_backward_pass():
    """What the forward pass of a switched share hands the backward
    pass is its inputs and the size taken: nothing with a sorted-row
    dimension (a differentiated `switch` would keep every branch's
    residuals, zero-filled where the branch did not run)."""
    ins = {k: jnp.asarray(v)
           for k, v in share_of(steered(R1), 16, 8).items()}
    attrs = dict(ROUTING, experts_held=[16, 8])

    def f(*vals):
        return run(dict(ins, **dict(zip(NAMES, vals))), attrs)["Out"][0]

    _, pull = jax.vjp(f, *[ins[k] for k in NAMES])
    kept = {tuple(leaf.shape) for leaf in jax.tree_util.tree_leaves(pull)
            if hasattr(leaf, "shape")}
    assert (TS, D) in kept and (8, D, H) in kept            # its inputs
    assert not any(len(shape) > 1 and shape[0] in SIZES
                   for shape in kept), kept
    # (the router's own gradient scatters over (T, E); it is held back
    # here, as a program that runs a share alone holds it back)
    attrs["router_gradient"] = False
    text = str(jax.make_jaxpr(jax.grad(lambda *v: jnp.sum(f(*v)),
                                       argnums=range(5)))(
        *[ins[k] for k in NAMES]))
    assert text.count("cond[") == 2             # forward, and backward
    assert "scatter" not in text                # gathers and k-sums only


# the lowered text of the op with every expert held: sha256, jax 0.9.0,
# CPU.  Pinned on the parent of the PR that brought the row buffers (PR
# 31); again at PR 40, whose grouped matmul puts a ragged dot at these
# toy widths between two masks (all true here) and changes no more
WHOLE_LAYER_TEXT = (
    "a15f0d8c166f87c03ad22cc05dc15114c10753a90f4320192a99a4bca0b3543a")


def test_without_a_share_the_op_is_the_parents_text_for_text():
    ins = {k: jnp.asarray(v) for k, v in whole_layer().items()}

    def f(*vals):
        o = run(dict(ins, **dict(zip(NAMES, vals))), ROUTING)
        return jnp.sum(jnp.sin(o["Out"][0])) + o["AuxLoss"][0][0]

    step = jax.jit(jax.value_and_grad(f, argnums=range(5)))
    vals = [ins[k] for k in NAMES]
    assert "cond[" not in str(jax.make_jaxpr(step)(*vals))
    text = step.lower(*vals).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == WHOLE_LAYER_TEXT
    # and a share too small for a second size has no switch either
    small = {k: jnp.asarray(v) for k, v in
             share_of(whole_layer(), 0, 8).items()}
    jaxpr = jax.make_jaxpr(lambda x: run(
        dict(small, X=x), dict(ROUTING, experts_held=[0, 8]))["Out"][0])(
            small["X"])
    assert "cond[" not in str(jaxpr)


def test_the_row_buffer_counter_is_state_the_step_carries_on_the_device():
    """`<w_0>.row_buffer_count` beside `off_share_count`: three forced
    routings land in three slots, the step fetches only its loss, and
    `observe.routing.row_buffer_counts` reads the scope afterwards."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.observe import routing

    main, startup, scope = fluid.Program(), fluid.Program(), fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        x = layers.data(name="x", shape=[TS, D], dtype="float32")
        out, *_ = layers.dropless_moe(
            x, E, H, K, norm_topk_prob=True, experts_held=(16, 8),
            routing="sigmoid", use_expert_bias=True)
        loss = layers.mean(out)
        fluid.optimizer.SGDOptimizer(learning_rate=0.0).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        (name,) = [n for n in scope.local_var_names()
                   if n.endswith(routing.ROW_BUFFER_COUNT_SUFFIX)]
        assert name[:-len(routing.ROW_BUFFER_COUNT_SUFFIX)] + \
            routing.OFF_SHARE_COUNT_SUFFIX in scope.local_var_names()
        gate = [n for n in scope.local_var_names()
                if n.startswith("moe_gate") and n.endswith(".w_0")][0]
        seen = []
        for rows in (R1, R1, R3, R2, R1 - 1, R2 + 1):
            forced = steered(rows, seed=rows)
            scope.set_var(gate, forced["GateW"])
            scope.set_var(gate + ".expert_bias", forced["Bias"])
            exe.run(main, feed={"x": forced["X"][None]}, fetch_list=[loss])
            assert isinstance(scope.vars[name], jax.Array)   # never fetched
            seen.append(routing.row_buffer_counts(scope)[name].tolist())
        assert seen[-1] == [3, 1, 2] and seen[0] == [1, 0, 0]
        assert routing.row_buffer_counts(scope, reset=True)[name].sum() == 6
        assert not routing.row_buffer_counts(scope)[name].any()


# the same on the kernel path: widths of whole 128-lane groups over
# whole token tiles, where the section sums a token's rows out of the
# buffer's R rows with `ops/pallas/rows_to_tokens.py` (interpret mode)
# and builds no (T, k, D) array; 4 ranks of 4 of 16 experts, two row
# buffer sizes each
TK, DK, HK, EK, HELD_K = 256, 128, 128, 16, 4


def wide_layer(seed, rows=None):
    """A whole layer at the kernel's widths; with `rows`, steered the
    way `steered` steers: exactly `rows` of the TK*K pairs go to rank
    1's experts."""
    r = R(seed)
    f32 = np.float32
    ins = {"X": r.normal(size=(TK, DK)).astype(f32),
           "GateW": r.normal(size=(DK, EK)).astype(f32) * 0.05,
           "Bias": r.normal(0, 0.1, size=(EK,)).astype(f32),
           "W1": r.normal(size=(EK, DK, HK)).astype(f32) * 0.1,
           "W3": r.normal(size=(EK, DK, HK)).astype(f32) * 0.1,
           "W2": r.normal(size=(EK, HK, DK)).astype(f32) * 0.1}
    if rows is not None:
        picks = rows // TK + (np.arange(TK) < rows % TK)
        ins["X"][:, :K] = np.where(np.arange(K) < picks[:, None], 1.0, -1.0)
        ins["GateW"][:K] = 0
        ins["GateW"][np.arange(K), HELD_K + np.arange(K)] = 3.0
        ins["Bias"][:] = 0
        ins["Bias"][HELD_K:2 * HELD_K] = ins["Bias"][-K:] = 5.0
    return ins


@pytest.mark.parametrize("routing, rows", [
    ("sigmoid", None), ("softmax", None), ("sigmoid", 700), ("sigmoid", 0)],
    ids=["sigmoid", "softmax", "second-buffer", "no-row"])
def test_the_shares_add_up_on_the_kernel_path(routing, rows):
    """Output and EVERY gradient, `GateW`'s included: the four ranks'
    parts add up to the layer that holds all 16 experts (the op's own
    all-experts path, which this kernel is no part of), each rank's
    expert weights get the whole layer's gradient of those experts,
    and every section traced took the kernel."""
    from paddle_tpu.observe.monitoring import runtime_stats

    sizes = moe_dropless.row_buffer_sizes(TK, K, EK, HELD_K)
    assert sizes == (512, TK * K)
    ins = {k: jnp.asarray(v) for k, v in wide_layer(21, rows).items()}
    if routing == "softmax":
        del ins["Bias"]
    attrs = {"routing": routing, "norm_topk_prob": True, "top_k": K}
    ct = jnp.asarray(R(22).normal(size=(TK, DK)).astype(np.float32))

    def layer(held, fixed):
        def f(*vals):
            o = run(dict(fixed, **dict(zip(NAMES, vals)),
                         **({} if held is None else {
                             "RowBufferCount": jnp.zeros((3,), jnp.int32)})),
                    dict(attrs, **({} if held is None
                                   else {"experts_held": held})))
            return jnp.sum(o["Out"][0] * ct), (
                o["Out"][0], o.get("RowBufferCountOut", [None])[0])
        (_, aux), grads = jax.jit(jax.value_and_grad(
            f, argnums=range(5), has_aux=True))(*[fixed[k] for k in NAMES])
        return aux + (dict(zip(NAMES, grads)),)

    want_out, _, want = layer(None, ins)
    # (a branch traced before at these shapes would not be traced, nor
    # counted, again)
    moe_dropless._branch.cache_clear()
    before = runtime_stats.snapshot()
    total = np.zeros((TK, DK), np.float64)
    summed = {k: np.zeros(ins[k].shape, np.float64) for k in ("X", "GateW")}
    taken = []
    for rank in range(EK // HELD_K):
        first = HELD_K * rank
        out, slots, got = layer([first, HELD_K], {
            k: jnp.asarray(v) for k, v in
            share_of(ins, first, HELD_K).items()})
        taken.append(int(np.argmax(slots)))
        total += np.asarray(out)
        for k in summed:
            summed[k] += np.asarray(got[k], np.float64)
        for k in ("W1", "W3", "W2"):
            np.testing.assert_allclose(
                got[k], want[k][first:first + HELD_K], rtol=2e-5, atol=2e-5,
                err_msg=f"{k} of rank {rank}")
    took = runtime_stats.delta(before)
    assert took["share_rows_kernel"] > 0 and took["share_rows_xla"] == 0
    if rows is not None:        # rank 1 got what the routing was forced to
        assert taken[1] == int(rows > sizes[0])
    np.testing.assert_allclose(total, want_out, rtol=2e-5, atol=2e-5)
    for k, part in summed.items():
        assert np.abs(np.asarray(want[k])).max() > 0, k
        np.testing.assert_allclose(part, want[k], rtol=5e-5, atol=5e-5,
                                   err_msg=k)


def test_a_row_buffer_counter_without_a_share_is_an_error():
    with pytest.raises(ValueError, match="only a share chooses"):
        run(dict(whole_layer(), RowBufferCount=np.zeros(3, np.int32)),
            ROUTING)


# --------------------------------------------------------------------------
# short_conv
# --------------------------------------------------------------------------

def conv_inputs(seed=5, n=2, t=9, d=6, taps=3):
    r = R(seed)
    return {"X": r.normal(size=(n, t, 3 * d)).astype(np.float32),
            "Filter": r.normal(size=(d, taps)).astype(np.float32)}


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_short_conv_matches_a_loop_over_positions(taps):
    ins = conv_inputs(taps=taps)
    x, w = ins["X"].astype(np.float64), ins["Filter"].astype(np.float64)
    n, t, d = x.shape[0], x.shape[1], w.shape[0]
    b, c, u = x[..., :d], x[..., d:2 * d], x[..., 2 * d:]
    want = np.zeros((n, t, d))
    for pos in range(t):
        for j in range(taps):
            src = pos - (taps - 1) + j
            if src >= 0:
                want[:, pos] += w[:, j] * b[:, src] * u[:, src]
        want[:, pos] *= c[:, pos]
    got = run_op("short_conv", ins, {})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    half = run_op("short_conv", {"X": ins["X"].astype(jnp.bfloat16),
                                 "Filter": ins["Filter"]}, {})
    assert half.dtype == jnp.bfloat16


def test_short_conv_is_causal_and_depthwise():
    ins = conv_inputs(seed=6)
    base = run_op("short_conv", ins, {})
    moved = ins["X"].copy()
    moved[:, 5] += 1.0                      # every channel of position 5
    got = run_op("short_conv", dict(ins, X=moved), {})
    np.testing.assert_array_equal(got[:, :5], base[:, :5])
    assert (got[:, 5] != base[:, 5]).all()
    assert (got[:, 8] == base[:, 8]).all()  # 3 taps reach back 2
    one = ins["X"].copy()
    one[:, :, 2] += 1.0                     # channel 2 of B only
    got = run_op("short_conv", dict(ins, X=one), {})
    changed = (got != base).any(axis=(0, 1))
    assert changed.tolist() == [False, False, True, False, False, False]


def test_short_conv_refuses_a_filter_of_another_width():
    with pytest.raises(ValueError, match="not .N, T, 3D."):
        run_op("short_conv", dict(conv_inputs(),
                                  Filter=np.ones((5, 3), np.float32)), {})


def test_rms_norm_a_head_is_rms_norm_of_each_group():
    x = R(7).normal(size=(2, 5, 24)).astype(np.float32) * 2.0
    w = R(8).uniform(0.5, 1.5, size=8).astype(np.float32)
    got = run_op("rms_norm", {"X": x, "Scale": w},
                 {"epsilon": 1e-5, "group_size": 8}, "Y")
    want = run_op("rms_norm", {"X": x.reshape(2, 5, 3, 8), "Scale": w},
                  {"epsilon": 1e-5, "begin_norm_axis": -1}, "Y")
    np.testing.assert_allclose(got, want.reshape(2, 5, 24), rtol=1e-6)
    with pytest.raises(ValueError, match="whole groups of 7"):
        run_op("rms_norm", {"X": x, "Scale": w[:7]}, {"group_size": 7}, "Y")


# --------------------------------------------------------------------------
# grouped-query flash attention at d_head 64
# --------------------------------------------------------------------------

def dense_gqa(q, k, v, heads, kv):
    n, t, _ = q.shape
    q4 = q.reshape(n, t, heads, 64)
    k4 = jnp.repeat(k.reshape(n, t, kv, 64), heads // kv, axis=2)
    v4 = jnp.repeat(v.reshape(n, t, kv, 64), heads // kv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q4, k4) / 8.0
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1),
                      v4).reshape(n, t, heads * 64)


GEOMETRIES = {   # heads, key/value heads, T, block_q, block_k
    "4-query-heads-a-kv-head": (8, 2, 64, 16, 32),
    "one-query-head-a-kv-head": (4, 4, 32, 16, 16),
    "2-query-heads-a-kv-head": (8, 4, 48, 16, 16),
    "8-query-heads-a-kv-head-wide-q-block": (16, 2, 32, 32, 8),
}


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_flash_gqa_matches_dense_attention_forward_and_backward(geometry):
    from paddle_tpu.ops.pallas.flash_gqa import flash_gqa

    heads, kv, t, bq, bk = GEOMETRIES[geometry]
    keys = jax.random.split(jax.random.PRNGKey(heads + t), 4)
    q = jax.random.normal(keys[0], (2, t, heads * 64))
    k = jax.random.normal(keys[1], (2, t, kv * 64))
    v = jax.random.normal(keys[2], (2, t, kv * 64))
    w = jax.random.normal(keys[3], (2, t, heads * 64))

    def kernel(q, k, v):
        return flash_gqa(q, k, v, heads, kv, block_q=bq, block_k=bk)

    # one forward pass each, its pull-back called on the weight (the
    # gradients of sum(out * w)); the dense form as ONE compiled function
    out, pull = jax.vjp(kernel, q, k, v)
    ref, *want = with_pull_back(
        lambda *a: dense_gqa(*a, heads, kv), w)(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    got = pull(w)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == r.shape           # dk, dv: kv heads wide
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5,
                                   err_msg="d" + name)


@pytest.mark.parametrize("use_pallas", [True, False])
def test_the_flash_attention_op_reads_grouped_heads_on_both_paths(use_pallas):
    heads, kv, t = 8, 2, 32
    r = R(9)
    q = r.normal(size=(2, t, heads * 64)).astype(np.float32)
    k = r.normal(size=(2, t, kv * 64)).astype(np.float32)
    v = r.normal(size=(2, t, kv * 64)).astype(np.float32)
    got = run_op("flash_attention", {"Q": q, "K": k, "V": v},
                 {"causal": True, "use_pallas": use_pallas,
                  "layout": "nthd", "n_head": heads, "n_kv_head": kv})
    np.testing.assert_allclose(
        got, dense_gqa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                       heads, kv), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("what, attrs, message", [
    ("odd key/value heads", dict(n_head=6, n_kv_head=3), "blocks heads in"),
    ("3 query heads a kv head", dict(n_head=6, n_kv_head=2), "blocks heads"),
    ("not causal", dict(n_head=8, n_kv_head=2, causal=False),
     "causal=False"),
    # causal grouped heads at d_head 128 are built (flash_attention.py's
    # band kernels); what is not causal self-attention still is not
    ("d_head 128", dict(n_head=4, n_kv_head=2, causal=False),
     "causal=False"),
])
def test_a_geometry_the_kernels_do_not_block_is_refused(what, attrs, message):
    heads, kv = attrs["n_head"], attrs["n_kv_head"]
    d = 128 if what == "d_head 128" else 64
    x = np.zeros((1, 16, heads * d), np.float32)
    kvx = np.zeros((1, 16, kv * d), np.float32)
    with pytest.raises(NotImplementedError, match=message):
        run_op("flash_attention", {"Q": x, "K": kvx, "V": kvx},
               {"causal": True, "use_pallas": True, "layout": "nthd",
                **attrs})


def test_k_that_is_not_kv_heads_wide_is_an_error():
    x = np.zeros((1, 16, 512), np.float32)
    with pytest.raises(ValueError, match="is not n_kv_head 2 heads of 64"):
        run_op("flash_attention", {"Q": x, "K": x, "V": x},
               {"causal": True, "layout": "nthd", "n_head": 8,
                "n_kv_head": 2})
