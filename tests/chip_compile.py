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
for it alone.  Two rules a reader can check.

THE PLACE: a file here must START in the first half of the run, which
its number of tests decides, and what it holds must end well before the
run does.  PR 67's reading of the files as they stand in tier-1 (the
driver's command, six workers, 8 cores; the run 1079 s, 6,237 s of test
time, 107 files; tests, seconds, place in the starting order, start and
end as the scheduler's rule replays the measured durations):

    _kernels.py          44 tests  259 s  12th  starts  207  ends 466
    _flash.py            43 tests  368 s  13th  starts  217  ends 585
    _cells.py            21 tests  185 s  32nd  starts  466  ends 651
    _flash_attention.py  13 tests  101 s  49th  starts  627  ends 728

(at the parent, the same command beside the builder's own file runs, 535
/ 534 / 571 / 307 s, the last two ending at 1123 and 1116 of 1381).  The
run ended 39 s after its test time over six, most of it the workers'
start-up: no file of this family is in the tail, and none was regrouped.
The longest test that stays is `_flash_attention.py`'s latent backward
past its budget (44 s), then the `STEP_TEXT` pins (6-37 s each, ~250 s
together: the control every `perf_opt` PR reads).

THE TIER: a tier-1 test takes under 45 s in the driver's six-worker run;
a compile of a whole cell's step for the described chip is `slow` from
the day it is written.  (Tier-1 ran into its 1470 s on PR 66's tree; the
run is bound by the SUM over six workers, and sixteen compiles held a
sixth of it.)  What such a test asserts without the compiled text stays
in tier-1 as a stand-in that stops at the LOWERED text (`_lower_args`,
`_sites`: the Program's parameter count, the counters around the trace,
the kernels' names; 5-9 s a whole step, under 2 s a kernel) and shares
the slow test's build, so the two cannot drift.  Run a slow test with
tier-1's environment, `pytest -m slow <file> -k <word>`; all sixteen
take 7 min on four workers.  The `slow` compiles (seconds in the
parent's six-worker run, PR 67) and what guards each between such runs:

  whole steps: the driver builds and runs the cell's step on the chip for
  every PR (a step Mosaic refuses or that does not fit is a failed cell),
  and the benchmark's `hbm_peak_gb` and `*_calls` / `*_grid_steps` /
  `*_chunks` entries read the plan and the kernels; what WAITS for the
  slow run is the plan's side of a depth / share / length rule BEFORE a
  chip run, and the compiled step's kernel counts and cost rows
    _flash.py  ..channel_delta_cells_step_holds_its_kernels..  205  kimilinear-8k
    _cells.py  ..block_diffusion_cells_step..depth_rule_read    86  sdar-8k
    _cells.py  ..head_count_a_layer_cells_step..share_rule_read 93  laguna-16k
    _cells.py  ..state_space_duality_cells_step..length_read    58  granite4h-8k
    _cells.py  ..linear_attention_cells_step_keeps_its_inverses 100 qwen3next-16k
    _cells.py  ..state_space_cells_step..length_read            49  phi4flash-8k
    _cells.py  lfm2_share_layer_and_short_conv_..               79  lfm2-8k (a layer)
    _cells.py  ..vision_language_cells_step..under_the_plan    170  kimivl-8k
  kernels at a shape no cell runs: NOTHING on the chip guards these
  (float32 at "highest" is a parity script's, which no driver's run
  reaches); a PR that touches the kernel's file runs them
    _flash_attention.py  latent_attention_backward_follows_the_budget[edge]   133
    _flash_attention.py  ..head_major_at_d_head_64..[lfm2_8k_gqa_32_over_8-f32] 73
    _kernels.py  latent_attention_kernels_at_the_published_shapes[f32]        115
    _kernels.py  band_kernels_at_a_head_count_a_layer_type[48h_full-f32]       46
    _kernels.py  grouped_flash_at_head_dim_256_..[f32]                         51
    _kernels.py  segment_attention_kernels_in_float32_at_the_cells_shape       17
  kernels a cell runs in bfloat16 (the cell's chip run guards the compile)
    _kernels.py  latent_attention_kernels_at_the_published_shapes[bf16]        70  joyai-8k
    _kernels.py  flash_gqa_under_a_scale_that_is_a_power_of_two                22  granite4h-8k, lfm2-8k

A new heavy case that stays in tier-1 goes to `_kernels.py` or
`_flash.py`, which start early; `_flash_attention.py` takes no more: it
is the latest to start.  Within a file, cases that compile one function
at one shape share the compiled object
(`test_chip_compile_flash.py _compiled`).
"""

from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp

from paddle_tpu.observe.monitoring import runtime_stats
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


def _precision(dtype):
    """The matmul precision a cell's bfloat16 runs at, and a parity
    script's float32."""
    return "default" if dtype == BF16 else "highest"


def _state_by_shape(main, scope):
    """Every persistable of `main` into `scope` as its shape and dtype:
    a step can be prepared, traced, lowered and compiled, nothing run."""
    import numpy as np

    for var in main.global_block().vars.values():
        if var.persistable and all(int(s) > 0 for s in var.shape):
            scope.set_var(var.name, jax.ShapeDtypeStruct(
                tuple(int(s) for s in var.shape), np.dtype(str(var.dtype))))


def _lower_args(fn, *args, precision="default"):
    """Trace and lower an already-jittable `fn` for the described chip
    from ShapeDtypeStruct arguments that carry its sharding, the Mosaic
    kernels' own lowering included, nothing compiled: (the lowered
    function, the counters around its trace).  This is the half a
    tier-1 stand-in runs (seconds); `.compile()` on the first is the
    TPU compiler's half (a minute or more on a whole step)."""
    # conftest asks for "highest" matmul precision (f64 references);
    # the program runs at the default, and Mosaic refuses an fp32
    # contraction of bf16 operands
    before = runtime_stats.snapshot()
    with force_mosaic_lowering(), jax.default_matmul_precision(precision):
        lowered = fn.lower(*args)
    return lowered, runtime_stats.delta(before)


def _compile_args(fn, *args):
    """Compile an already-jittable `fn` for the described chip from
    ShapeDtypeStruct arguments that carry its sharding."""
    return _lower_args(fn, *args)[0].compile()


def _sites(lowered):
    """The Pallas kernels of a lowered function by name, as its text's
    locations name them: call SITES, so a jitted pass that six layers
    share counts once (the compiled text counts calls)."""
    return collections.Counter(re.findall(
        r'pallas_(\w+?)\)*/pallas_call"', lowered.as_text(debug_info=True)))


def _compile(fn, sharding, *specs):
    """Compile `fn` for the described chip from (shape, dtype) specs
    and return the compiled text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]
    return _compile_args(jax.jit(fn), *args).as_text()


def _kernels(text):
    return text.count("tpu_custom_call")
