"""The decoder-hybrid-decoder on the normal path (`models/decoder.py`
with "mamba", "gated_memory" and "cross_attention" layers,
`attention="differential"`, `norm="layer_norm"`, projection biases, no
positions, a tied head; the `selective_scan` op, `short_conv` with a
bias, ONE grouped flash call a layer and `diff_combine`) against its
plain float32 reference (`benchmarks/reference_phi4flash.py`) on the
CPU at a small size, seeded random weights: logits, the loss and the
gradient of every parameter.

The preset: hidden 64, 4 query and 2 key/value heads of 16 (2 query
pairs over ONE key/value pair), d_inner 128 x 16 states, dt_rank 4, a
window of 8 at length 32, published indices from 14 (lambda_init reads
them).  Two patterns: the cell's six layers (one of every kind), and
eight with TWO readers of each export.  Every parameter that starts
constant (biases, norm scales, D, the sub-layer norm) is redrawn after
start-up, so that no term is compared at 0 or 1.

Tolerance.  Float32: both sides are float32 with matmuls at "highest"
and differ in summation order only (the flash kernels' online soft-max
over zero-padded lanes, the chunked scan against the position-by-
position one): 5e-6 absolute-or-relative, as tests/test_laguna_parity.py;
the exponentials of the scan and of lambda leave 2e-5 on a gradient
(largest seen 8e-6), so gradients take 3e-5.
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
import reference_phi4flash as ref  # noqa: E402
from models import phi4flash as family  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import Family, close, reference, system  # noqa: E402

TOL, GRAD_TOL = 5e-6, 3e-5
LENGTH = 32
PATTERNS = {
    "six": (["mamba", "sliding_attention", "mamba", "full_attention",
             "gated_memory", "cross_attention"], 2, 3),
    "eight-two-readers": (
        ["mamba", "sliding_attention", "mamba", "full_attention",
         "gated_memory", "cross_attention", "gated_memory",
         "cross_attention"], 2, 3)}


def config(pattern="six", **over):
    kinds, memory, kv = PATTERNS[pattern]
    cfg = dict(
        hidden_size=64, num_hidden_layers=len(kinds), num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=96, layer_norm_eps=1e-5,
        sliding_window=8, vocab_size=96, tie_word_embeddings=True,
        layer_types=kinds, layer_indices=list(range(14, 14 + len(kinds))),
        shared_memory_layer=memory, shared_kv_layer=kv, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4, attention_bias=True)
    cfg.update(over)
    return cfg


def arguments(cfg, **build):
    return dict(family.architecture(cfg), aux_loss_weight=0.0,
                z_loss_weight=0.0, **build)


def off_the_constants(main, scope, seed):
    """A parameter that starts at a constant (a bias, a scale, D) is
    drawn again, so that the comparison sees it."""
    redraw = np.random.default_rng(seed)
    for p in main.all_parameters():
        value = np.asarray(scope.find_var(p.name))
        if np.ptp(value) == 0.0:
            scope.set_var(p.name, jnp.asarray(
                (value + redraw.normal(size=value.shape) * 0.3
                 ).astype(value.dtype)))


FAMILY = Family(ref.params_from_list, ref.loss_and_grads, ref.flat_leaves)
batch = functools.partial(harness.batch, length=LENGTH)


@pytest.mark.parametrize("recompute", [None, "layer"])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_program_matches_the_float32_reference(pattern, recompute):
    cfg = config(pattern)
    feed = batch(cfg)
    got, params = system(arguments(cfg, recompute=recompute), feed,
                         after_startup=off_the_constants)
    took = got["took"]
    total, parts, grads = reference(FAMILY, cfg, feed, params)
    close(got["logits"], parts["logits"], "logits")
    close(got["loss"], total, "loss")
    names = ref.leaf_names(cfg)
    assert len(got["grads"]) == len(grads) == len(params) == len(names)
    for name, g, w in zip(names, got["grads"], grads):
        assert np.abs(np.asarray(w)).max() > 0, name    # no vacuous match
        close(g, w, f"gradient of {name}", GRAD_TOL)
    shapes = {n: p.shape for n, p in zip(names, params)}
    assert shapes["layer0.w_x"] == (128, 4 + 2 * 16)
    assert shapes["layer0.a_log"] == (128, 16)
    assert shapes["layer1.wq"] == (64, 64) and shapes["layer1.wk"] == (64, 32)
    assert shapes["layer5.wq"] == (64, 64) and "layer5.wk" not in shapes
    assert shapes["layer4.w1"] == (64, 128)
    readers = (len(cfg["layer_types"]) - 4) // 2
    assert took["shared_memory_reads"] == took["shared_kv_reads"] == readers
    assert took["differential_attention_calls"] == 2 + readers
    assert took["selective_scans_kernel"] == 0      # T is no whole chunk
    assert took["selective_scans_xla"] > 0
    assert took["short_conv_bias_calls"] > 0


def _one_reader_reference(FAMILY, cfg, feed, params, cut):
    """The reference with the reads of the layers in `cut` held
    constant in the backward pass: what a program would compute that
    let only the OTHER readers' gradients reach the exporter."""
    tree = ref.params_from_list(params, cfg)

    def total(tree):
        with jax.default_matmul_precision("highest"):
            x = tree["embed"][jnp.asarray(feed["tokens"])]
            shared = {}
            for i, layer in enumerate(tree["layers"]):
                reads = jax.lax.stop_gradient(shared) if i in cut else shared
                x, exports = ref.decoder_layer(x, layer, i, cfg, reads)
                shared.update(exports)
            x = ref.layer_norm(x, tree["final_norm_w"], tree["final_norm_b"],
                               cfg["layer_norm_eps"])
            logp = jax.nn.log_softmax(x @ tree["embed"].T, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, jnp.asarray(feed["labels"])[..., None], axis=-1))

    return ref.flat_leaves(jax.jit(jax.grad(total))(tree), cfg)


def test_an_exports_gradient_is_the_sum_over_its_readers():
    """Two readers of each export, every layer a recompute segment: the
    gradients of what only the exports reach (the exporting mamba
    layer's step / B / C projection and A_log, the exporting attention
    layer's key and value projections) are the reference's, which sums
    both readers', and are NOT what one reader alone would give."""
    cfg = config("eight-two-readers")
    feed = batch(cfg)
    got, params = system(arguments(cfg, recompute="layer"), feed,
                         after_startup=off_the_constants)
    names = ref.leaf_names(cfg)
    _, _, both = reference(FAMILY, cfg, feed, params)
    one = _one_reader_reference(FAMILY, cfg, feed, params, cut=(6, 7))
    for leaf in ("layer2.w_x", "layer2.a_log", "layer3.wk", "layer3.wv"):
        at = names.index(leaf)
        close(got["grads"][at], both[at], leaf, GRAD_TOL)
        apart = np.abs(np.asarray(both[at]) - np.asarray(one[at])).max()
        assert apart > 100 * GRAD_TOL * np.abs(np.asarray(both[at])).max(), \
            leaf


@pytest.mark.parametrize("over, match", [
    (dict(layer_types=["gated_memory", "mamba", "sliding_attention",
                       "full_attention", "mamba", "cross_attention"],
          shared_memory_layer=1), "before it is made"),
    (dict(shared_kv_layer=None), "needs shared_kv_layer"),
    (dict(shared_memory_layer=1), "is no mamba layer"),
    (dict(layer_types=["mamba", "sliding_attention", "gated_memory",
                       "full_attention", "mamba", "cross_attention"],
          shared_memory_layer=4), "before it is made")])
def test_a_reader_without_its_exporter_raises_at_build_time(over, match):
    cfg = config(**over)
    with pytest.raises(ValueError, match=match):
        system(arguments(cfg), batch(cfg))


def test_the_reference_in_blocks_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/phi4flash_parity.py` runs on the chip so that
    8192 positions fit: scores `q_block` rows at a time, the scan in
    blocks of `time_block` positions, every layer recomputed in its
    backward pass.  Same numbers."""
    cfg = config()
    feed = batch(cfg)
    _, params = system(arguments(cfg), feed, after_startup=off_the_constants)
    plain, _, want = reference(FAMILY, cfg, feed, params)
    blocked, _, got = reference(FAMILY, cfg, feed, params, q_block=8,
                                time_block=8)
    close(blocked, plain, "loss")
    for w, g in zip(want, got):
        close(g, w, "gradient")


def test_the_scopes_and_the_query_order_are_the_documented_ones():
    """The four name scopes; and the builder's query columns are the
    published ones under `reference_phi4flash.q_columns` (2 query pairs
    over one key/value pair: heads 0, 2 first, then 1, 3)."""
    cfg = config()
    got, _ = system(arguments(cfg), batch(cfg),
                    after_startup=off_the_constants)
    scopes = {op.desc.attrs.get("__name_scope__", "")
              for op in got["main"].global_block().ops}
    for scope in ("state_space", "gated_memory", "cross_attention",
                  "differential_attention/sliding_attention",
                  "differential_attention/full_attention",
                  "differential_attention/full_attention/diff_combine"):
        assert scope in scopes, scope
    heads = ref.q_columns(cfg)[::16] // 16
    assert heads.tolist() == [0, 2, 1, 3]
