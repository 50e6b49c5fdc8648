"""Where the `dropout` op draws its keep-mask (ops/pallas/dropout_mask.py).

The kernel uses the TPU's hardware generator, which has no CPU rule, so
nothing here RUNS it: these tests trace the op under the Mosaic gate
and read, from the two counters the op keeps, which path it took; the
kernel's results are checked on the chip (`chip_smoke.py dropout_mask`)
and its compile in tests/test_chip_compile_flash_attention.py.
"""

from __future__ import annotations

import contextlib

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpContext, get_op_impl
from paddle_tpu.observe.monitoring import runtime_stats
from paddle_tpu.ops.pallas import dropout_mask as dm
from paddle_tpu.ops.pallas import force_mosaic_lowering
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.collectives import compat_shard_map
from paddle_tpu.parallel.mesh import executing_mesh

TILEABLE = (8, 256, 512)


def _dropout(key, x, p=0.1):
    return get_op_impl("dropout")(
        OpContext(key, 5), {"X": [x]},
        {"dropout_prob": p, "dropout_implementation": "upscale_in_train"})


def _trace(shape, mesh_axes=None, manual=False, mosaic=True):
    """Trace the op on `shape` and return (kernel, xla) as counted."""
    mesh = make_mesh(mesh_axes) if mesh_axes else None

    def op(key, x):
        if not manual:
            return _dropout(key, x)["Out"][0]
        # as the explicit grad_sync step runs its ops: inside a
        # shard_map over the data axis
        from jax.sharding import PartitionSpec as P

        return compat_shard_map(
            lambda k, v: _dropout(k, v)["Out"][0], mesh,
            (P(), P("dp")), P("dp"))(key, x)

    with contextlib.ExitStack() as stack:
        if mosaic:
            stack.enter_context(force_mosaic_lowering())
        if mesh is not None:
            stack.enter_context(executing_mesh(mesh, "dp"))
        snap = runtime_stats.snapshot()
        jaxpr = jax.make_jaxpr(op)(
            jax.random.PRNGKey(0), jax.ShapeDtypeStruct(shape, jnp.float32))
        d = runtime_stats.delta(snap)
    assert ("pallas_call" in str(jaxpr)) == bool(d["dropout_masks_kernel"])
    return d["dropout_masks_kernel"], d["dropout_masks_xla"]


@pytest.mark.parametrize("shape,kw,kernel", [
    (TILEABLE, {}, True),
    ((64, 8, 256, 256), {}, True),
    ((8, 256, 500), {}, False),                 # last dimension not lanes
    ((3, 5, 512), {}, False),                   # rows not whole tiles
    ((4096,), {}, False),                       # no rows at all
    (TILEABLE, {"mosaic": False}, False),       # the CPU: never interpreted
    (TILEABLE, {"mesh_axes": {"dp": 1, "mp": 1}}, True),
    (TILEABLE, {"mesh_axes": {"dp": 4}}, True),
    (TILEABLE, {"mesh_axes": {"dp": 2, "mp": 2}}, False),
    (TILEABLE, {"mesh_axes": {"mp": 4}}, False),
    ((6, 256, 512), {"mesh_axes": {"dp": 4}}, False),   # 6 % 4
    ((4, 8, 512), {"mesh_axes": {"dp": 4}}, False),     # 8 rows a chip
    (TILEABLE, {"mesh_axes": {"dp": 4}, "manual": True}, False),
], ids=["tileable", "attention_weights", "odd_last_dim", "odd_rows",
        "one_dim", "cpu_gate", "mesh_of_ones", "dp4", "dp2_mp2", "mp4",
        "batch_not_divisible", "local_rows_not_tiles", "grad_sync_body"])
def test_where_the_mask_is_drawn(shape, kw, kernel):
    assert _trace(shape, **kw) == ((1, 0) if kernel else (0, 1))


def test_dp_mesh_maps_the_kernel_over_the_batch_axis():
    """Under a dp mesh the call sits in a shard_map whose result is
    sharded on dimension 0, each rank drawing its local rows."""
    mesh = make_mesh({"dp": 4})
    with force_mosaic_lowering(), executing_mesh(mesh, "dp"):
        jaxpr = jax.make_jaxpr(
            lambda k: dm.dropout_keep_mask(k, 0.1, TILEABLE))(
                jax.random.PRNGKey(0))
    text = str(jaxpr)
    assert "shard_map" in text and "axis_index" in text
    assert "i8[512,512]" in text        # 2 x 256 rows a rank
    assert jaxpr.out_avals[0].shape == TILEABLE
    assert jaxpr.out_avals[0].dtype == jnp.bool_


def test_cpu_jaxpr_is_bernoulli_behind_a_barrier():
    """Off the TPU the op is what it was before the kernel, equation
    for equation."""
    def before(key, x):
        keep = jax.lax.optimization_barrier(jax.random.bernoulli(
            jax.random.fold_in(key, 5), 1.0 - 0.1, x.shape))
        y = jnp.where(keep, x / (1.0 - 0.1), 0.0)
        return y.astype(x.dtype), keep.astype(x.dtype)

    def now(key, x):
        o = _dropout(key, x)
        return o["Out"][0], o["Mask"][0]

    args = (jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct(TILEABLE, jnp.bfloat16))
    assert str(jax.make_jaxpr(now)(*args)) == \
        str(jax.make_jaxpr(before)(*args))


@pytest.mark.parametrize("data", [0, 1, 7, 123456, 2 ** 31 - 1])
def test_block_seed_is_fold_in(data):
    """The scalar threefry the kernel seeds each block with is
    `jax.random.fold_in(key, block)`, word for word."""
    key = jax.random.PRNGKey(20270927)
    words = jax.lax.bitcast_convert_type(key, jnp.int32)
    got = dm.threefry_fold_in(words[0], words[1], jnp.int32(data))
    want = jax.random.fold_in(key, data)
    assert jax.lax.bitcast_convert_type(
        jnp.stack(got), jnp.uint32).tolist() == want.tolist()


@pytest.mark.parametrize("p", [0.1, 0.5, 0.3, 1e-9, 1.0 - 1e-9])
def test_threshold_keeps_one_minus_p_to_2_to_minus_32(p):
    """The signed word the kernel compares uniform signed bits with:
    `threshold + 2**31` of the 2**32 values lie below it."""
    kept = dm._threshold(p) + 2 ** 31
    assert 0 <= kept <= 2 ** 32 - 1
    assert abs(kept / 2.0 ** 32 - (1.0 - p)) <= 2.0 ** -32
    assert -2 ** 31 <= dm._threshold(p) < 2 ** 31      # an int32


@pytest.mark.parametrize("rows,last,want", [
    (64 * 256, 512, (2048, 256)),           # residual dropout, one chip
    (64 * 8 * 256, 256, (4096, 512)),       # attention weights
    (16 * 256, 512, (2048, 256)),           # a dp4 rank's share: as above
    (16 * 8 * 256, 256, (4096, 512)),
    (96, 128, (96, 96)),
    (32, 128, (32, 32)),
    (224, 384, (224, 224)),
    (33, 128, None), (64, 100, None), (0, 128, None), (64, 0, None),
])
def test_tiling(rows, last, want):
    got = dm.tiling(rows, last)
    assert got == want
    if got:
        block, chunk = got
        assert rows % block == 0 and block % chunk == 0 and chunk % 32 == 0
        assert block * last <= max(dm.BLOCK_ELEMS, chunk * last)
