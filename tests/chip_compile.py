"""What the chip-compile tests share: dtypes, the compile helpers and
the expert cells' shape table.  Not a test file.

Interpret mode and `jax.export` (tests/test_pallas_lowering.py) both
stop before Mosaic compiles: kernels that passed every such test were
refused by the TPU's compiler for a lane slice not aligned to the
tiling (paged attention) and for more scoped VMEM than a kernel may
claim (fused LSTM).  The compiler is installed here and compiles for a
chip that is DESCRIBED, not attached, so each kernel of the main path
is compiled once for one v5e device at the width chip_smoke.py and the
cells run it, and the compiled text must hold the Mosaic custom call.
Nothing runs: this says nothing about results or times.

The fixtures (`topology`, `one_chip`, `dp4_mesh`) are in
tests/conftest.py.  The tests are tests/test_chip_compile_*.py: four
files, not one, because `--dist loadfile` gives a file to ONE worker;
and four, not one a kernel family, because that scheduler starts the
files in the order of their NUMBER OF TESTS, largest first, and hands a
worker its next file when two tests of the last are left, so a file of
two compiles that take two minutes each starts last and the run waits
for it alone.  The rule a reader can check: a file here must START in
the first half of the run, which its number of tests decides, and what
it holds must end well before the run does.  PR 55's reading (the
driver's command, six workers, 8 cores; the run 1065 s, 6,189 s of test
time, 101 files; tests, seconds, place in the starting order, start
and end as the scheduler's rule replays the measured durations):

    _kernels.py          38 tests  350 s  14th  starts  227  ends 578
    _flash.py            33 tests  225 s  16th  starts  243  ends 468
    _cells.py            18 tests  462 s  32nd  starts  438  ends 900
    _flash_attention.py  13 tests  266 s  51st  starts  591  ends 857

(at the parent 358 / 218 / 521 / 301 s, the last two ending at 1118 and
1064 of 1336).  The run ended 34 s after its test time over six, most
of it the workers' start-up: no file of this family is in the tail, and
none was regrouped.  A new heavy case (the four whole-cell steps are
50-100 s each, the latent kernels at their budget's edge 140) goes to
`_kernels.py` or `_flash.py`, which start early; `_flash_attention.py`
takes no more: at 13 tests it is the latest to start.  Within a file,
cases that compile one function at one shape share the compiled object
(`test_chip_compile_flash.py _compiled`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import force_mosaic_lowering

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8

# the sorted-row buffers of the five cells with routed experts: tokens,
# experts a token, experts, experts held (None: all), D, H
EXPERT_CELLS = {
    "mellum2-16k": (16384, 8, 64, 8, 2304, 896),
    "lfm2-8k": (8192, 4, 64, 8, 2048, 1536),
    "joyai-8k": (8192, 8, 256, 8, 2048, 768),
    "olmoe-4k": (4 * 4096, 8, 64, None, 2048, 1024),
    "laguna-16k": (16384, 8, 256, 32, 2048, 512),
}


# the first row buffer of the six cells that hold a share (the sorted
# rows a section runs on nearly always), tokens, experts a token, width
SHARE_CELLS = {
    "mellum2-16k": (24576, 16384, 8, 2304),
    "sdar-8k": (24576, 16384, 8, 2048),
    "qwen3next-16k": (7680, 16384, 10, 2048),
    "lfm2-8k": (6144, 8192, 4, 2048),
    "joyai-8k": (3072, 8192, 8, 2048),
    "laguna-16k": (24576, 16384, 8, 2048),
}


def _compile_args(fn, *args):
    """Compile an already-jittable `fn` for the described chip from
    ShapeDtypeStruct arguments that carry its sharding."""
    # conftest asks for "highest" matmul precision (f64 references);
    # the program runs at the default, and Mosaic refuses an fp32
    # contraction of bf16 operands
    with force_mosaic_lowering(), jax.default_matmul_precision("default"):
        return fn.lower(*args).compile()


def _compile(fn, sharding, *specs):
    """Compile `fn` for the described chip from (shape, dtype) specs
    and return the compiled text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    return _compile_args(jax.jit(fn), *args).as_text()


def _kernels(text):
    return text.count("tpu_custom_call")
