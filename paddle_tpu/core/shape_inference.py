"""Shape/dtype inference by abstract evaluation.

The reference implements a hand-written InferShape per operator
(reference: paddle/fluid/framework/shape_inference.h + each op's
InferShape).  Here we get all of them for free: when an op is appended at
graph-build time, its JAX implementation is abstractly evaluated with
`jax.eval_shape` over ShapeDtypeStructs, and the resulting output
shapes/dtypes are written back into the output VarDescs.

Dynamic batch dims (-1) are represented during abstract evaluation by a
large prime sentinel; output dims divisible by the sentinel are restored
to -1 (a batch dim flowing through reshape/flatten keeps its dynamic
marking).
"""

from __future__ import annotations

from typing import Dict, List

# Large prime sentinel standing in for a dynamic (-1) dimension.
DYNAMIC_DIM_SENTINEL = 1000003


def _encode_shape(shape):
    return tuple(DYNAMIC_DIM_SENTINEL if d == -1 else int(d) for d in shape)


def _decode_dim(d: int) -> int:
    if d >= DYNAMIC_DIM_SENTINEL and d % DYNAMIC_DIM_SENTINEL == 0:
        return -1
    return int(d)


def _decode_shape(shape):
    return tuple(_decode_dim(d) for d in shape)


# Op types that the executor handles specially or whose impls can't be
# abstractly evaluated; their outputs keep declared shapes.  Tensor-array
# ops carry (buffer, length) tuples that ShapeDtypeStructs can't model.
# `gated_delta_rule`'s layer declares its one output itself: tracing a
# 256-chunk scan at the stand-in batch would only add three kernel
# calls of a million sequences to `runtime_stats.gated_delta_*`, which
# a benchmark reader takes for the step's.  `short_conv`'s layer
# declares its output likewise, and its kernels are neither traced nor
# counted (`runtime_stats.short_convs_*`) by a Program build; `rope`'s
# likewise (`runtime_stats.ropes_*`), and `selective_scan`'s, `ssd_scan`'s
# and `gated_rms_norm`'s (their counters count traces of a step), and
# `channel_delta_rule`'s (`runtime_stats.channel_delta_*`).
_SKIP_INFERENCE = {
    "backward_marker", "py_func", "print",
    "create_array", "array_write", "array_read", "array_length",
    "array_to_tensor", "gated_delta_rule", "short_conv", "rope",
    "selective_scan", "ssd_scan", "gated_rms_norm", "channel_delta_rule",
    "segment_attention",
}


# Depth of `infer_op_shapes` calls in flight: an op evaluated for its
# shapes alone traces whatever its lowering traces, at the stand-in
# batch (`rms_norm(group_size=128)` a Pallas pass over a million
# sequences); a counter of what STEPS trace asks `inferring_shapes()`
# and does not count then (`ops/pallas/head_norm.py`).
_inferring = [0]


def inferring_shapes() -> bool:
    return bool(_inferring[0])


def infer_op_shapes(op_desc, block) -> bool:
    """Best-effort shape inference for one appended op.  Returns True when
    output VarDescs were updated."""
    if op_desc.type in _SKIP_INFERENCE:
        return False
    import jax
    import jax.numpy as jnp

    from .registry import OpContext, get_op_impl, has_op

    if not has_op(op_desc.type):
        return False

    ins: Dict[str, List[jax.ShapeDtypeStruct]] = {}
    for slot, names in op_desc.inputs.items():
        specs = []
        for n in names:
            if not block.has_var(n):
                return False
            v = block.var(n)
            specs.append(
                jax.ShapeDtypeStruct(_encode_shape(v.shape), jnp.dtype(v.dtype))
            )
        ins[slot] = specs

    impl = get_op_impl(op_desc.type)

    # the key is abstract too: a concrete one makes every random op
    # RUN inside the trace, at the stand-in batch (a 131 GB dropout
    # mask that the device refuses, an exception swallowed below)
    def absfn(abstract_ins, key):
        ctx = OpContext(key, op_index=0,
                        is_test=bool(op_desc.attrs.get("is_test", False)))
        return impl(ctx, abstract_ins, op_desc.attrs)

    _inferring[0] += 1
    try:
        outs = jax.eval_shape(absfn, ins,
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    except Exception:
        return False  # leave declared shapes; executor will still run it
    finally:
        _inferring[0] -= 1

    for slot, names in op_desc.outputs.items():
        specs = outs.get(slot, [])
        if len(specs) != len(names):
            continue
        for n, spec in zip(names, specs):
            if not block.has_var(n):
                continue
            v = block.var(n)
            v.desc.shape = _decode_shape(spec.shape)
            v.desc.dtype = str(spec.dtype)
    return True
