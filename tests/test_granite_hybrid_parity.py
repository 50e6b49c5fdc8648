"""The hybrid state-space / attention decoder on the normal path
(`models/decoder.py` with "mamba" layers given by heads: the `ssd_scan`
op, ONE `short_conv` with a bias over x, B and C together, the gated
norm; causal grouped-query attention without positions under a scale of
the configuration's own; the four multipliers; a tied head) against its
plain float32 reference (`benchmarks/reference_granite_hybrid.py`) on
the CPU at a small size, seeded random weights: logits, the loss and
the gradient of every parameter.

The preset: hidden 64, 4 query and 2 key/value heads of 16, 4
state-space heads of 16 x 32 states in chunks of 8 at length 32, the
published multipliers (12, 0.22, 1/64, 8).  Two patterns: the cell's
ten layers (mamba x 5, attention, mamba x 4) and two (one of each
kind).  Every parameter that starts constant (the convolution's bias,
norm scales, D) is redrawn after start-up, so that no term is compared
at 0 or 1.

Tolerance.  Float32: both sides are float32 with matmuls at "highest"
and differ in summation order only (the flash kernels' online soft-max,
the chunked scan against the position-by-position one): 5e-6
absolute-or-relative, as tests/test_phi4flash_parity.py; the scan's
exponentials of cumulative sums leave 2e-5 on a gradient (largest seen
4e-6), so gradients take 3e-5.
"""

import functools
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import reference_granite_hybrid as ref  # noqa: E402
from models import granite_hybrid as family  # noqa: E402
import parity_harness as harness  # noqa: E402
from parity_harness import Family, close, reference, system  # noqa: E402

TOL, GRAD_TOL = 5e-6, 3e-5
LENGTH = 32
PATTERNS = {"ten": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
            "two": ["mamba", "attention"]}
MULTIPLIERS = {"embedding_multiplier": 12, "residual_multiplier": 0.22,
               "attention_multiplier": 0.015625, "logits_scaling": 8}


def config(pattern="two", **over):
    kinds = PATTERNS[pattern]
    cfg = dict(
        hidden_size=64, num_hidden_layers=len(kinds), num_attention_heads=4,
        num_key_value_heads=2, shared_intermediate_size=96,
        intermediate_size=96, rms_norm_eps=1e-5, vocab_size=96,
        tie_word_embeddings=True, attention_bias=False, layer_types=kinds,
        mamba_d_state=32, mamba_d_conv=4, mamba_expand=1, mamba_n_heads=4,
        mamba_d_head=16, mamba_n_groups=1, mamba_chunk_size=8, **MULTIPLIERS)
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


@pytest.mark.parametrize("pattern, recompute", [
    ("ten", "layer"), ("two", None), ("two", "layer")])
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
        close(g, w, f"gradient of {name}", GRAD_TOL,
              scale=np.abs(np.asarray(w)).max())
    shapes = {n: p.shape for n, p in zip(names, params)}
    assert shapes["layer0.w_z"] == (64, 64)
    assert shapes["layer0.w_xbc"] == (64, 64 + 2 * 32)
    assert shapes["layer0.conv_w"] == (64 + 2 * 32, 4)
    assert shapes["layer0.w_dt"] == (64, 4)
    assert shapes["layer0.a_log"] == shapes["layer0.dt_bias"] == (4,)
    assert shapes["layer0.gate_norm_w"] == (64,)
    at = cfg["layer_types"].index("attention")
    assert shapes[f"layer{at}.wq"] == (64, 64)
    assert shapes[f"layer{at}.wk"] == (64, 32)
    mamba = cfg["layer_types"].count("mamba")
    assert took["ssd_scans_kernel"] == 0        # heads of 16: the XLA form
    assert took["ssd_scans_xla"] > 0
    assert took["gated_rms_norm_calls"] >= mamba
    assert took["scaled_attention_calls"] == 1
    assert took["short_conv_bias_calls"] > 0
    assert took["selective_scans_xla"] == took["selective_scans_kernel"] == 0


@pytest.mark.parametrize("left_out", sorted(MULTIPLIERS))
def test_a_multiplier_left_out_misses_the_reference(left_out):
    """The program built WITHOUT one of the four multipliers (the
    builder's default: 1, and d_head^-1/2 = 1/4 inside the soft-max)
    against the reference with all four: the logits miss the tolerance
    a hundred times over.  (12 on the embedding, 0.22 on the branches,
    1/64 against 16^-1/2, 8 under the logits.)  Weights from N(0, 0.3):
    under the preset's N(0, 0.02) a score is so small that no scale
    inside the soft-max shows."""
    cfg = config()
    feed = batch(cfg)
    build = arguments(cfg, initializer_range=0.3)
    _, params = system(build, feed, after_startup=off_the_constants)
    build = dict(build)
    del build[left_out]
    got, _ = system(build, feed, params=params)
    whole, _ = system(arguments(cfg, initializer_range=0.3), feed,
                      params=params)
    _, parts, _ = reference(FAMILY, cfg, feed, params)
    want = np.asarray(parts["logits"])
    close(whole["logits"], want, "logits with all four",
          scale=np.abs(want).max())
    miss = np.abs(np.asarray(got["logits"]) - want).max()
    assert miss > 100 * TOL * max(1.0, np.abs(want).max()), (left_out, miss)
    assert got["took"]["scaled_attention_calls"] == (
        0 if left_out == "attention_multiplier" else 1)


def test_the_reference_in_blocks_and_recomputed_gives_the_same_gradients():
    """What `benchmarks/granite_hybrid_parity.py` runs on the chip so
    that 8192 positions fit: scores `q_block` rows at a time, the scan
    in blocks of `time_block` positions, every layer recomputed in its
    backward pass.  Same numbers."""
    cfg = config()
    feed = batch(cfg)
    _, params = system(arguments(cfg), feed, after_startup=off_the_constants)
    plain, _, want = reference(FAMILY, cfg, feed, params)
    blocked, _, got = reference(FAMILY, cfg, feed, params, q_block=8,
                                time_block=8)
    close(blocked, plain, "loss")
    for w, g in zip(want, got):
        close(g, w, "gradient", scale=np.abs(np.asarray(w)).max())


def test_the_scopes_are_the_documented_ones():
    cfg = config()
    got, _ = system(arguments(cfg), batch(cfg),
                    after_startup=off_the_constants)
    ops = got["main"].global_block().ops
    scopes = {op.desc.attrs.get("__name_scope__", "") for op in ops}
    for scope in ("state_space_duality",
                  "state_space_duality/gated_rms_norm", "full_attention"):
        assert scope in scopes, scope
    assert "state_space" not in scopes
    by_scope = {op.type: op.desc.attrs.get("__name_scope__", "")
                for op in ops}
    assert by_scope["ssd_scan"] == by_scope["short_conv"] \
        == "state_space_duality"
    assert by_scope["gated_rms_norm"] == "state_space_duality/gated_rms_norm"
    assert by_scope["flash_attention"] == "full_attention"


@pytest.mark.parametrize("over, error, match", [
    (dict(mamba_dt_rank=4), ValueError, "given twice.*mamba_dt_rank.*"
     "mamba_chunk_size.*mamba_n_heads"),
    (dict(mamba_n_heads=None, mamba_d_head=None, mamba_n_groups=None,
          mamba_chunk_size=None), ValueError,
     "either \\['mamba_dt_rank'\\] or \\['mamba_chunk_size', "
     "'mamba_d_head', 'mamba_n_groups', 'mamba_n_heads'\\]"),
    (dict(mamba_chunk_size=None), ValueError, "missing "
     "\\['mamba_chunk_size'\\]"),
    (dict(mamba_n_groups=2), NotImplementedError, "several groups"),
    (dict(mamba_n_heads=2), NotImplementedError, "share of a mixer's heads")])
def test_a_mamba_layer_given_twice_or_not_at_all_raises_at_build_time(
        over, error, match):
    """Which state-space mixer a "mamba" layer is follows from the keys:
    both key sets, or neither, raise with the two sets named; several
    groups and a share of the heads are not built."""
    cfg = config()
    build = dict(arguments(cfg), **over)
    with pytest.raises(error, match=match):
        system(build, batch(cfg))


@pytest.mark.parametrize("key, value", [
    ("num_local_experts", 8), ("hidden_act", "gelu"),
    ("normalization_function", "layernorm"),
    ("position_embedding_type", "rope"), ("attention_bias", True),
    ("mamba_n_groups", 8), ("mamba_proj_bias", True)])
def test_the_family_raises_on_what_is_not_built(key, value):
    with pytest.raises(NotImplementedError, match=key):
        family.architecture(config(**{key: value}))
