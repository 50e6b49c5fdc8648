"""Host-side runtime accounting: compile/retrace counters, compile
wall-time, dispatch latency, device-memory snapshots.

reference analog: the reference tracked per-op host timings through
platform/profiler RecordEvent; on TPU the expensive host-side events
are XLA COMPILES (seconds each) and jit RETRACES (a shape change
silently recompiling the step), which are invisible without hooks.
Compile events come from `jax.monitoring` (the jit/pjit internals emit
`/jax/core/compile/backend_compile_duration` per backend compile, a
duration each for jaxpr tracing and MLIR lowering, and the persistent
compilation cache a hit or a miss per request); retraces are detected
in `Executor._prepare` by input-signature change on an already-built
step fn (jax re-traces per new shape/dtype signature).  The host side of one step is split into four phases,
`prepare`, `place`, `call` and `writeback`, entered through
`runtime_stats.phase(name)` in `Executor.run` and
`CompiledProgram.run`: each is a `paddle_tpu.step.<name>` span in a
profiler trace and a counter here.  `call` is the jitted call alone
(async: device completion is NOT included) and also feeds
`dispatches` / `dispatch_time_s`.

Set-up is seen through the same counters.  Everything that makes a run
of `Executor.run` slow the first time (a step fn built, a feed
signature met, and whatever jax then says it traced, lowered, compiled
or read from its cache) is kept in ONE immutable tuple,
`runtime_stats.heard`, which is replaced on every such event.
`Executor.run` reads it on entry and after the call: the same object
means a warm run and costs nothing more; another means the run was
COLD, and one record of it (`runtime_stats.cold_runs()`) says which
program, how long each phase took and what jax did meanwhile.  Building
a Program is `runtime_stats.stage("build_program")`.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Dict, List, Optional

# What makes a run cold, as `runtime_stats.heard` holds it.  `events`
# counts the replacements; the other fields are counters of their own
# in `snapshot()`.  jaxpr tracing and MLIR lowering are the host-side
# work a cold dispatch pays BEFORE the backend compile (their sum is
# `trace_time_s`, which the goodput ledger folds into "compile");
# both count outermost spans only, so they are wall time.
Heard = collections.namedtuple("Heard", (
    "events", "builds", "retraces", "compiles", "compile_time_s",
    "jaxpr_trace_time_s", "lower_time_s",
    "cache_hits", "cache_misses", "cache_read_time_s"))
# jax.monitoring's names.  backend_compile_duration wraps
# compile_or_get_cached: a persistent-cache hit is counted too, at its
# (short) retrieval time, which the cache's own events tell apart
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SPAN_EVENTS = {        # these nest: see install()
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace_time_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_time_s"}
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses"}

# `cold_runs` (the count) is in the snapshot too: the name is the
# records' method here
_FIELDS = Heard._fields[1:] + (
    "trace_time_s", "dispatches", "dispatch_time_s",
    "place_puts", "place_skips",
    "dropout_masks_kernel", "dropout_masks_xla",
    "flash_mla_backward_fused", "flash_mla_backward_split",
    "flash_gqa_backward_fused", "flash_gqa_backward_split",
    "flash_attention_backward_fused", "flash_attention_backward_split",
    "flash_window_blocks_visited", "flash_window_blocks_allowed",
    "flash_window_calls", "flash_grouped_calls",
    "flash_window_pairs_allowed", "flash_window_entries_computed",
    "flash_window_forward_whole_band", "flash_window_forward_tiled",
    "attention_head_gate_calls",
    "flash_prefix_grid_steps", "flash_prefix_visits_full",
    "flash_prefix_visits_diagonal",
    "flash_block_diffusion_calls", "flash_block_diffusion_blocks_visited",
    "flash_block_diffusion_blocks_allowed",
    "flash_block_diffusion_grid_steps",
    "flash_block_diffusion_pairs_allowed",
    "flash_block_diffusion_entries_computed",
    "gated_delta_calls", "gated_delta_chunks",
    "gated_delta_operand_calls", "gated_delta_operand_chunks",
    "gated_delta_inverse_calls", "gated_delta_flat_calls",
    "channel_delta_calls", "channel_delta_chunks",
    "channel_delta_operand_calls", "channel_delta_operand_chunks",
    "head_norm_calls", "head_norm_rows",
    "recompute_kept_residuals", "recompute_kept_bytes",
    "grouped_matmuls_kernel", "grouped_matmuls_xla",
    "short_convs_kernel", "short_convs_xla", "short_conv_bias_calls",
    "selective_scans_kernel", "selective_scans_xla", "selective_scan_chunks",
    "ssd_scans_kernel", "ssd_scans_xla", "ssd_scan_chunks",
    "gated_rms_norm_calls", "scaled_attention_calls",
    "differential_attention_calls", "shared_memory_reads", "shared_kv_reads",
    "ropes_kernel", "ropes_xla",
    "share_rows_kernel", "share_rows_xla",
    "flash_segment_calls", "flash_segment_xla_calls",
    "flash_segment_tiles_total", "flash_segment_lane_kernel_calls",
    "flash_segment_lane_xla_calls", "image_patches", "image_rows",
    "loop_trips")
# the host phases of one step, in the order a step enters them
STEP_PHASES = ("prepare", "place", "call", "writeback")
SPAN_PREFIX = "paddle_tpu.step."
# what `stage(name)` takes: a model builder appending its ops
SETUP_STAGES = ("build_program",)
SETUP_SPAN_PREFIX = "paddle_tpu.setup."
_RECENT = 4096          # durations kept per phase
_COLD_RUNS = 256        # records kept


class _Phase:
    """One entry of `RuntimeStats.phase`: a profiler span and a timed
    region.  A class, not a generator, because a step enters four."""

    __slots__ = ("_stats", "_name", "_span", "_t0")

    def __init__(self, stats, name):
        import jax

        self._stats, self._name = stats, name
        self._span = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._stats._record_phase(self._name,
                                  time.perf_counter() - self._t0)
        return self._span.__exit__(*exc)


class RuntimeStats:
    """Monotonic counters for the process; use snapshot()/delta() to
    attribute a region (a bench model, a telemetry window)."""

    def __init__(self):
        self._lock = threading.Lock()
        # builds: Executor step fns traced (program-cache miss);
        # retraces: a NEW feed signature on an existing step fn;
        # compiles / compile_time_s: XLA backend compiles and their
        # wall time (jax.monitoring); the rest as `Heard` says
        self.heard = Heard(*(0,) * len(Heard._fields))
        self.dispatches = 0         # step dispatches: the `call` phase
        self.dispatch_time_s = 0.0  # host enqueue time (async; excludes
        #                             device execution)
        # state and feed arrays the `place` phase met under a placement:
        # put to their sharding / passed through because they lay there
        # already (a steady step: the feeds / the whole state)
        self.place_puts = 0
        self.place_skips = 0
        # `dropout` ops traced, by where their keep-mask is drawn: the
        # Pallas kernel on the chip's generator, or jax.random.bernoulli
        # (a step that fell back says so; delta() around a build)
        self.dropout_masks_kernel = 0
        self.dropout_masks_xla = 0
        # backward passes of `ops/pallas/flash_mla.py`, `flash_gqa.py`
        # and `flash_attention.py` traced, by what the call's shape
        # chose: the single kernel, or dk/dv and dq by a kernel each
        # (delta() around a build; `joyai-8k` 6 / 0, `lfm2-8k` 1 / 0,
        # `olmoe-4k` 1 / 0; a loop's body counts once, as traced)
        self.flash_mla_backward_fused = 0
        self.flash_mla_backward_split = 0
        self.flash_gqa_backward_fused = 0
        self.flash_gqa_backward_split = 0
        self.flash_attention_backward_fused = 0
        self.flash_attention_backward_split = 0
        # key blocks the forward grid of the window kernel
        # (`flash_attention.py`, a call with a `window`) visits for
        # one head, over its query blocks, and those of them that hold
        # a score the mask allows, summed over the calls traced: equal
        # where the grid skips every block outside the band, T / window
        # times apart where skipping is lost (delta() around a build)
        self.flash_window_blocks_visited = 0
        self.flash_window_blocks_allowed = 0
        # forward band calls traced, by geometry: under a window
        # (`flash_window_fwd`), and over the whole causal prefix with
        # grouped key/value heads (`flash_fwd` on the band's grid); a
        # step that fell back to the XLA mask reads 0.  And what the
        # blocks' ratio cannot see, a tile that is mostly masked: the
        # score pairs ONE head's mask allows under the window and the
        # score entries its forward grid's visited tiles compute
        # (block_q x block_k each), summed over the window calls
        # traced; their ratio is the tiles' fill (25% at 1024 x 1024
        # tiles under a window of 512, 50% at 512 x 512)
        self.flash_window_calls = 0
        self.flash_grouped_calls = 0
        self.flash_window_pairs_allowed = 0
        self.flash_window_entries_computed = 0
        # the same calls by the forward the call's shape chose
        # (`flash_attention.py whole_band_forward_fits`): a grid step a
        # query tile's WHOLE band, the soft-max in one pass, or the
        # online soft-max over the band's key tiles (delta() around a
        # step build; a window layer in a recompute segment is traced
        # forward and once more for the segment's backward pass)
        self.flash_window_forward_whole_band = 0
        self.flash_window_forward_tiled = 0
        # the band calls over the whole causal prefix (no window:
        # `flash_fwd` / `flash_dkv` / `flash_dq` on a list of visits, PR
        # 63), a head's pass each, forward and backward: the grid steps
        # it takes and its visits by kind.  Steps over visits 1.0 = no
        # step computes nothing (the rectangle: 256 / 136 at 16 x 16
        # tiles); `_full` over the visits = the tiles computed with no
        # mask (120 / 136 there)
        self.flash_prefix_grid_steps = 0
        self.flash_prefix_visits_full = 0
        self.flash_prefix_visits_diagonal = 0
        # per-head output gates `models/decoder.py` built
        # (`attention_gate="head"`), one a layer, at program build time
        self.attention_head_gate_calls = 0
        # the same pair for the forward kernel under the block-diffusion
        # mask (`flash_block_diffusion.py _DiffusionBand`: tiles a head's
        # grid computes, and tiles that hold an allowed pair), and the calls
        # of it traced, forward and recomputed; 0 calls = a step that
        # fell back to the XLA lowering under an explicit mask
        self.flash_block_diffusion_calls = 0
        self.flash_block_diffusion_blocks_visited = 0
        self.flash_block_diffusion_blocks_allowed = 0
        # what the visit table is for (PR 59), a head's pass each: the
        # grid steps it takes (beside `_blocks_allowed`: 1.0 = no step
        # computes nothing), the pairs the mask allows and the score
        # entries the visits compute (their fill)
        self.flash_block_diffusion_grid_steps = 0
        self.flash_block_diffusion_pairs_allowed = 0
        self.flash_block_diffusion_entries_computed = 0
        # calls of the chunked delta-rule scan's Pallas kernels traced
        # (`ops/pallas/gated_delta.py`: a layer's forward, its
        # recomputed forward and its backward are a call each) and their
        # chunks x heads; a step that fell back to the XLA lowering of
        # the scan reads 0 (delta() around a build)
        self.gated_delta_calls = 0
        self.gated_delta_chunks = 0
        # the same for the kernels of the scan's chunk-local part
        # (`gated_delta_operands_fwd` / `_bwd`); 0 where XLA's
        # `chunk_operands` ran
        self.gated_delta_operand_calls = 0
        self.gated_delta_operand_chunks = 0
        # of both kinds, the calls whose blocks address the op's own
        # arrays (PR 72): a scan call, which writes o and reads dO as
        # (N, T, Hv x 128) by lane block (every one: the kernels have
        # no other form), and a chunk-operand call that took q, k AND v
        # on QKV as it lies and handed back one dQKV (`RawQK.v`; 0 for
        # operands apart).  The cell: 9 + 9
        self.gated_delta_flat_calls = 0
        # calls traced of the kernel that solves for the chunks'
        # (I + A)^-1 (`gated_delta_inverse`, before the custom VJP of
        # the chunk-operand kernels: traced where the layer is, once,
        # however often the kernels that read it are)
        self.gated_delta_inverse_calls = 0
        # the same two pairs for the delta rule whose decay is a key
        # lane's own (`ops/pallas/channel_delta.py`): the scan kernels
        # `channel_delta_fwd` / `_bwd`, and the chunk-local kernels
        # `channel_delta_inverse` / `_operands_fwd` / `_operands_bwd`;
        # 0 where the XLA lowering of the chunks ran
        self.channel_delta_calls = 0
        self.channel_delta_chunks = 0
        # segment-confined attention (`ops/pallas/flash_segment.py`): the
        # calls traced on the kernels and on the XLA lowering, and the
        # tiles of the whole rectangle the kernel calls stand for
        # (every head's); how many of them a step VISITS is data, a
        # device counter of the layer (`observe/routing.py
        # segment_tile_visits`); and of the kernel calls, those whose
        # 128-lane layout (and rotary turn) `ops/pallas/head_lanes.py`
        # made in one pass an array and those XLA's pad and slice made.
        # A second tower's step inputs as a step
        # build traces them (`ops/vision.py`): patches on the packed row
        # axis (`table_interp`), and the rows that enter the decoder's
        # stream (`image_merge`)
        self.flash_segment_calls = 0
        self.flash_segment_xla_calls = 0
        self.flash_segment_tiles_total = 0
        self.flash_segment_lane_kernel_calls = 0
        self.flash_segment_lane_xla_calls = 0
        self.image_patches = 0
        self.image_rows = 0
        self.channel_delta_operand_calls = 0
        self.channel_delta_operand_chunks = 0
        # calls traced of the kernels that take a head's lane statistic
        # on the flat tensor (`ops/pallas/head_norm.py`: `head_norm_fwd`
        # / `_bwd`, the l2norm of a delta rule's q and k and the gated
        # norm a head), and the rows they walk; 0 where the composition
        # over a (.., H, group) view ran
        self.head_norm_calls = 0
        self.head_norm_rows = 0
        # calls traced inside a recompute segment that name what the
        # segment keeps (`ops/pallas keep_residuals`): an attention
        # call, whose backward pass therefore keeps the kernel's two
        # residuals and does not run its forward kernel again, or the
        # delta rule's inverses, which the recomputed chunk-operand
        # kernel reads; and the bytes kept, from the shapes (the output
        # in the operands' dtype + 8 float32 sublanes of logsumexp a
        # head; (I + A)^-1 in float32, two heads a tile) (delta()
        # around a build; a loop's body counts once, as traced; 0
        # where no segment holds such a call)
        self.recompute_kept_residuals = 0
        self.recompute_kept_bytes = 0
        # grouped matmuls of the dropless expert op traced
        # (`ops/pallas/grouped_matmul.py`), by what the product's shape
        # chose: the Pallas kernels, or `jax.lax.ragged_dot` where no
        # tile divides a width (delta() around a build; a branch of a
        # share's `switch` counts once, as traced, forward and again
        # where the backward pass recomputes it)
        self.grouped_matmuls_kernel = 0
        self.grouped_matmuls_xla = 0
        # `short_conv` ops traced, by what the shape chose: the Pallas
        # kernels (`ops/pallas/short_conv.py`) or the composition
        # (delta() around a build; a Program build counts nothing)
        self.short_convs_kernel = 0
        self.short_convs_xla = 0
        # those of them that add a bias a channel before the activation
        self.short_conv_bias_calls = 0
        # selective scans (`ops/pallas/selective_scan.py`) traced, by
        # what the shape chose: the Pallas kernels (a layer's forward
        # and its backward are a call each; a recompute segment keeps
        # the forward's results and runs it once) or the XLA lowering,
        # and the chunks x batch the kernels walk, summed over the calls
        # (delta() around a build; a step on the fall-back reads 0
        # chunks)
        self.selective_scans_kernel = 0
        self.selective_scans_xla = 0
        self.selective_scan_chunks = 0
        # scans of the scalar-a-head state-space form
        # (`ops/pallas/ssd_scan.py`) traced, as the three above: the
        # Pallas kernels or the XLA lowering, and the chunks x batch the
        # kernels walk
        self.ssd_scans_kernel = 0
        self.ssd_scans_xla = 0
        self.ssd_scan_chunks = 0
        # `gated_rms_norm` ops traced (the norm of y * silu(z) in one
        # pass), and attention calls BUILT under a scale that a
        # configuration gives (`models/decoder.py`: not d_head^-1/2;
        # delta() around a Program build)
        self.gated_rms_norm_calls = 0
        self.scaled_attention_calls = 0
        # differential attention calls built (`models/decoder.py`: two
        # soft-max maps subtracted, a layer with its own K and V or a
        # reader of another's), and the layers built that READ another
        # layer's work: its scan output (a gated memory unit) or its
        # keys and values (cross-attention).  Build-time counts: delta()
        # around a Program build
        self.differential_attention_calls = 0
        self.shared_memory_reads = 0
        self.shared_kv_reads = 0
        # `rope` ops traced, by what the shape and attrs chose: the
        # Pallas kernels (`ops/pallas/rope.py`) or the composition
        # (delta() around a build; a Program build counts nothing)
        self.ropes_kernel = 0
        self.ropes_xla = 0
        # sorted-row sections of a share-holding `moe_dropless` traced
        # (`_held_rows`, one a row buffer), by what the shape chose for
        # the way back to token order: the kernel of
        # `ops/pallas/rows_to_tokens.py` over the buffer's R rows, or
        # the composition that gathers T x k rows (delta() around a
        # build; a branch of a share's `switch` counts once, as traced,
        # forward and again where the backward pass recomputes it)
        self.share_rows_kernel = 0
        self.share_rows_xla = 0
        # trips of the counted loops traced (`static_rnn` with a
        # `trip_count`): what a step runs of them (delta() around a
        # build: 4 where one stack runs 4 times), and what an early exit
        # would lower
        self.loop_trips = 0
        # per-phase totals and the most recent durations; the stages of
        # set-up beside them (outermost entries only), there from the
        # start so that a snapshot always carries them
        self._phase_time_s: Dict[str, float] = dict.fromkeys(
            SETUP_STAGES, 0.0)
        self._phase_count: Dict[str, int] = dict.fromkeys(SETUP_STAGES, 0)
        self._stage_depth: Dict[str, int] = dict.fromkeys(SETUP_STAGES, 0)
        self._recent: Dict[str, collections.deque] = {}
        self._cold_runs = collections.deque(maxlen=_COLD_RUNS)
        self._cold_run_count = 0

    def hear(self, **added):
        """One event that makes the run it falls in cold: `heard` is
        replaced by a tuple with `added` added and `events` one up."""
        with self._lock:
            h = self.heard
            self.heard = h._replace(
                events=h.events + 1,
                **{f: getattr(h, f) + v for f, v in added.items()})

    def record_build(self):
        self.hear(builds=1)

    def record_retrace(self):
        self.hear(retraces=1)

    def __getattr__(self, name):
        # the counters `heard` holds read as attributes of their own
        if name in Heard._fields:
            return getattr(self.__dict__["heard"], name)
        raise AttributeError(name)

    @property
    def trace_time_s(self) -> float:
        """jaxpr tracing + MLIR lowering wall time."""
        h = self.heard
        return h.jaxpr_trace_time_s + h.lower_time_s

    def record_place(self, puts: int, skips: int):
        with self._lock:
            self.place_puts += puts
            self.place_skips += skips

    def record_dropout_mask(self, kernel: bool):
        with self._lock:
            if kernel:
                self.dropout_masks_kernel += 1
            else:
                self.dropout_masks_xla += 1

    def record_flash_backward(self, family: str, fused: bool):
        """One traced backward pass of kernel family `family`
        ("flash_mla", "flash_gqa", "flash_attention")."""
        field = f"{family}_backward_{'fused' if fused else 'split'}"
        with self._lock:
            setattr(self, field, getattr(self, field) + 1)

    def record_flash_window_blocks(self, visited: int, allowed: int):
        with self._lock:
            self.flash_window_blocks_visited += visited
            self.flash_window_blocks_allowed += allowed

    def record_flash_window_forward(self, whole_band: bool):
        """Which forward a traced call under a window took."""
        with self._lock:
            if whole_band:
                self.flash_window_forward_whole_band += 1
            else:
                self.flash_window_forward_tiled += 1

    def record_flash_window_call(self, pairs: int, entries: int):
        """One traced forward call under a window: a head's allowed
        pairs and the score entries its visited tiles compute."""
        with self._lock:
            self.flash_window_calls += 1
            self.flash_window_pairs_allowed += pairs
            self.flash_window_entries_computed += entries

    def record_flash_grouped_call(self):
        with self._lock:
            self.flash_grouped_calls += 1

    def record_flash_prefix_visits(self, steps: int, full: int,
                                   diagonal: int):
        with self._lock:
            self.flash_prefix_grid_steps += steps
            self.flash_prefix_visits_full += full
            self.flash_prefix_visits_diagonal += diagonal

    def record_attention_head_gate(self):
        with self._lock:
            self.attention_head_gate_calls += 1

    def record_flash_block_diffusion(self, visited: int, allowed: int,
                                     steps: int = 0, pairs: int = 0,
                                     entries: int = 0):
        with self._lock:
            self.flash_block_diffusion_calls += 1
            self.flash_block_diffusion_blocks_visited += visited
            self.flash_block_diffusion_blocks_allowed += allowed
            self.flash_block_diffusion_grid_steps += steps
            self.flash_block_diffusion_pairs_allowed += pairs
            self.flash_block_diffusion_entries_computed += entries

    def record_gated_delta(self, chunks: int):
        with self._lock:
            self.gated_delta_calls += 1
            self.gated_delta_chunks += chunks
            self.gated_delta_flat_calls += 1

    def record_gated_delta_operands(self, chunks: int, flat: bool = False):
        with self._lock:
            self.gated_delta_operand_calls += 1
            self.gated_delta_operand_chunks += chunks
            self.gated_delta_flat_calls += int(flat)

    def record_gated_delta_inverse(self):
        with self._lock:
            self.gated_delta_inverse_calls += 1

    def record_channel_delta(self, chunks: int):
        with self._lock:
            self.channel_delta_calls += 1
            self.channel_delta_chunks += chunks

    def record_channel_delta_operands(self, chunks: int):
        with self._lock:
            self.channel_delta_operand_calls += 1
            self.channel_delta_operand_chunks += chunks

    def record_flash_segment(self, kernel: bool, tiles_total: int = 0,
                             lane_kernels=None):
        with self._lock:
            if kernel:
                self.flash_segment_calls += 1
                self.flash_segment_tiles_total += tiles_total
            else:
                self.flash_segment_xla_calls += 1
            if lane_kernels is not None:
                self.flash_segment_lane_kernel_calls += int(lane_kernels)
                self.flash_segment_lane_xla_calls += int(not lane_kernels)

    def record_image_feed(self, patches: int, rows: int):
        with self._lock:
            self.image_patches += patches
            self.image_rows += rows

    def record_head_norm(self, rows: int):
        with self._lock:
            self.head_norm_calls += 1
            self.head_norm_rows += rows

    def record_kept_residuals(self, nbytes: int):
        with self._lock:
            self.recompute_kept_residuals += 1
            self.recompute_kept_bytes += nbytes

    def record_grouped_matmul(self, kernel: bool):
        with self._lock:
            if kernel:
                self.grouped_matmuls_kernel += 1
            else:
                self.grouped_matmuls_xla += 1

    def record_short_conv(self, kernel: bool, bias: bool = False):
        with self._lock:
            if kernel:
                self.short_convs_kernel += 1
            else:
                self.short_convs_xla += 1
            self.short_conv_bias_calls += bool(bias)

    def record_selective_scan(self, kernel: bool, chunks: int):
        with self._lock:
            if kernel:
                self.selective_scans_kernel += 1
                self.selective_scan_chunks += chunks
            else:
                self.selective_scans_xla += 1

    def record_ssd_scan(self, kernel: bool, chunks: int):
        with self._lock:
            if kernel:
                self.ssd_scans_kernel += 1
                self.ssd_scan_chunks += chunks
            else:
                self.ssd_scans_xla += 1

    def record_gated_rms_norm(self):
        with self._lock:
            self.gated_rms_norm_calls += 1

    def record_scaled_attention(self):
        with self._lock:
            self.scaled_attention_calls += 1

    def record_cross_layer(self, differential=0, memory_reads=0, kv_reads=0):
        with self._lock:
            self.differential_attention_calls += differential
            self.shared_memory_reads += memory_reads
            self.shared_kv_reads += kv_reads

    def record_rope(self, kernel: bool):
        with self._lock:
            if kernel:
                self.ropes_kernel += 1
            else:
                self.ropes_xla += 1

    def record_share_rows(self, kernel: bool):
        with self._lock:
            if kernel:
                self.share_rows_kernel += 1
            else:
                self.share_rows_xla += 1

    def record_loop_trips(self, trips: int):
        with self._lock:
            self.loop_trips += trips

    def phase(self, name: str) -> _Phase:
        """Context manager around one host phase of a step: a
        `paddle_tpu.step.<name>` span on the profiler's host line (the
        device planes' clock), and its `time.perf_counter()` duration
        added to `<name>_time_s` / `<name>_count` and to `recent(name)`.
        `call` is the dispatch: it feeds `dispatches` too."""
        return _Phase(self, name)

    def _record_phase(self, name: str, duration_s: float):
        with self._lock:
            self._phase_time_s[name] = (self._phase_time_s.get(name, 0.0)
                                        + duration_s)
            self._phase_count[name] = self._phase_count.get(name, 0) + 1
            ring = self._recent.get(name)
            if ring is None:
                ring = self._recent[name] = collections.deque(
                    maxlen=_RECENT)
            ring.append(duration_s)
            if name == "call":
                self.dispatches += 1
                self.dispatch_time_s += duration_s

    @contextlib.contextmanager
    def stage(self, name: str):
        """Context manager and decorator around one stage of set-up
        (`build_program`: a model builder appending forward, backward
        and optimizer ops): a `paddle_tpu.setup.<name>` span and
        `<name>_time_s` / `<name>_count` in `snapshot()`.  Re-entrant:
        an entry inside another of the same name adds nothing."""
        import jax

        with jax.profiler.TraceAnnotation(SETUP_SPAN_PREFIX + name):
            with self._lock:
                self._stage_depth[name] += 1
                outermost = self._stage_depth[name] == 1
            t0 = time.perf_counter()
            try:
                yield
            finally:
                duration_s = time.perf_counter() - t0
                with self._lock:
                    self._stage_depth[name] -= 1
                    if outermost:
                        self._phase_time_s[name] += duration_s
                        self._phase_count[name] += 1

    def record_cold_run(self, before: Heard, **what):
        """Append the record of one cold `Executor.run`: `what` (the
        program and its arrays), the run's own phase durations, and
        what was heard since `before`, the `heard` of its entry."""
        now = time.perf_counter()
        with self._lock:
            h = self.heard
            phases = {p: self._recent[p][-1] for p in STEP_PHASES}
            self._cold_run_count += 1
            self._cold_runs.append(dict(
                what,
                # to the microseconds between the phases
                t_entry=now - sum(phases.values()),
                new_signature=(h.builds + h.retraces
                               > before.builds + before.retraces),
                **{p + "_s": phases[p] for p in STEP_PHASES},
                trace_s=h.jaxpr_trace_time_s - before.jaxpr_trace_time_s,
                lower_s=h.lower_time_s - before.lower_time_s,
                backend_compile_s=h.compile_time_s - before.compile_time_s,
                compiles=h.compiles - before.compiles,
                cache_hits=h.cache_hits - before.cache_hits,
                cache_misses=h.cache_misses - before.cache_misses,
                cache_read_s=(h.cache_read_time_s
                              - before.cache_read_time_s)))

    def cold_runs(self) -> List[Dict[str, Any]]:
        """The records of the newest (at most 256) cold runs of
        `Executor.run`, oldest first: every run during which a step fn
        was built, a feed signature was new, or jax traced, lowered,
        compiled or read its cache.  Keys: `program` (its `_uid`),
        `ops`, `state_arrays`, `feed_arrays`, `fetches`, `placement`,
        `new_signature`, `t_entry` (`time.perf_counter()`), the four
        `<phase>_s`, and the deltas heard during the run: `trace_s`,
        `lower_s`, `backend_compile_s` (on a cache hit the read),
        `compiles`, `cache_hits`, `cache_misses`, `cache_read_s`.  A
        cold run whose signature was not new is jax re-lowering a step
        it has seen (arguments committed or laid out otherwise).  An
        event on another thread during a run marks that run too."""
        with self._lock:
            return [dict(r) for r in self._cold_runs]

    def recent(self, name: str) -> List[float]:
        """The last (at most 4096) durations of phase `name`, seconds,
        oldest first."""
        with self._lock:
            return list(self._recent.get(name, ()))

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out = {f: getattr(self, f) for f in _FIELDS}
            out["cold_runs"] = self._cold_run_count
            for name, total in self._phase_time_s.items():
                out[name + "_time_s"] = total
                out[name + "_count"] = self._phase_count[name]
            return out

    def delta(self, since: Dict[str, Any]) -> Dict[str, Any]:
        now = self.snapshot()
        return {f: v - since.get(f, 0) for f, v in now.items()}


runtime_stats = RuntimeStats()


def format_cold_run(r: Dict[str, Any]) -> str:
    """One record of `cold_runs()` on one line, for a log."""
    total = sum(r[p + "_s"] for p in STEP_PHASES)
    phases = " + ".join(f"{p} {r[p + '_s']:.2f}" for p in STEP_PHASES)
    return (
        f"program {r['program']} ({r['ops']} ops, {r['state_arrays']} "
        f"state / {r['feed_arrays']} feed / {r['fetches']} fetch"
        f"{', placed' if r['placement'] else ''}"
        f"{'' if r['new_signature'] else ', signature seen before'}): "
        f"{total:.2f} s = {phases}; jax: trace {r['trace_s']:.2f}, "
        f"lower {r['lower_s']:.2f}, compile or read "
        f"{r['backend_compile_s']:.2f} ({r['compiles']}), cache "
        f"{r['cache_hits']} hit / {r['cache_misses']} miss, read "
        f"{r['cache_read_s']:.2f}")


_installed = [False]


def install():
    """Register the jax.monitoring listeners (idempotent).  Called on
    first Executor use; they stay for the process lifetime.  A
    callback is one tuple replaced, a microsecond or two an event, and
    jax emits events only where it traces, lowers, compiles or reads
    its cache."""
    if _installed[0]:
        return
    import jax.monitoring

    # jax times a jitted function traced inside another, and one traced
    # while a module is lowered, each on its own: only the outermost
    # span of a thread is wall time (`joyai-8k`'s step: 12.2 s of
    # events in an 11.8 s call).  A span's start is a scalar event.
    open_spans = threading.local()

    def _on_scalar(event, _value, **_kw):
        if event in _SPAN_EVENTS:
            open_spans.n = getattr(open_spans, "n", 0) + 1

    def _on_duration(event, duration, **_kw):
        field = _SPAN_EVENTS.get(event)
        if field:
            open_spans.n = max(getattr(open_spans, "n", 0) - 1, 0)
            if not open_spans.n:
                runtime_stats.hear(**{field: float(duration)})
        elif event == _COMPILE_EVENT:
            runtime_stats.hear(compiles=1, compile_time_s=float(duration))
        elif event == _CACHE_READ_EVENT:
            runtime_stats.hear(cache_read_time_s=float(duration))

    def _on_event(event, **_kw):
        field = _COUNT_EVENTS.get(event)
        if field:
            runtime_stats.hear(**{field: 1})

    jax.monitoring.register_scalar_listener(_on_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _installed[0] = True


def device_memory_stats(device=None) -> Dict[str, Any]:
    """One device's allocator stats (keys like bytes_in_use,
    peak_bytes_in_use).  {} on backends that don't report (CPU)."""
    import jax

    d = device if device is not None else jax.local_devices()[0]
    try:
        stats = d.memory_stats()
    except Exception:  # noqa: BLE001 — backend-dependent API
        return {}
    return dict(stats) if stats else {}


def peak_memory_bytes() -> Optional[int]:
    """Max peak_bytes_in_use across local devices, or None when no
    device reports memory stats (the CPU test backend)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = device_memory_stats(d)
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class LatencyHistogram:
    """Fixed log-spaced latency histogram with percentile estimates.

    Serving telemetry needs p50/p95/p99 over unbounded request streams
    without storing samples: log-spaced bins (default 20/decade from
    10 µs to 60 s ≈ 7% relative resolution) hold counts only, so
    record() is O(1), memory is constant, and merged windows stay
    exact.  percentile() returns the upper edge of the bin holding the
    rank — a ≤7% overestimate, never an underestimate (latency SLOs
    should round pessimistically).  Thread-safe.
    """

    def __init__(self, lo_ms: float = 0.01, hi_ms: float = 60000.0,
                 bins_per_decade: int = 20):
        import math

        if not (0 < lo_ms < hi_ms):
            raise ValueError("need 0 < lo_ms < hi_ms")
        self._lo = lo_ms
        self._k = bins_per_decade
        self._nbins = (int(math.ceil(
            math.log10(hi_ms / lo_ms) * bins_per_decade)) + 2)
        # bin 0 catches < lo_ms; the last bin catches >= hi_ms
        self._counts = [0] * self._nbins
        self._lock = threading.Lock()
        self.count = 0
        self.sum_ms = 0.0
        self.max_ms = 0.0

    def _bin(self, ms: float) -> int:
        import math

        if ms < self._lo:
            return 0
        idx = int(math.log10(ms / self._lo) * self._k) + 1
        return min(idx, self._nbins - 1)

    def _edge(self, idx: int) -> float:
        # upper edge of bin idx (bin 0's edge is lo_ms itself)
        return self._lo * 10.0 ** (idx / self._k)

    def record(self, ms: float):
        ms = float(ms)
        with self._lock:
            self._counts[self._bin(ms)] += 1
            self.count += 1
            self.sum_ms += ms
            if ms > self.max_ms:
                self.max_ms = ms

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        """Fold `other`'s counts into this histogram IN PLACE (and
        return self) — the "merged windows stay exact" contract:
        bin-wise count addition loses nothing, so percentiles over the
        merged histogram equal percentiles over one histogram that had
        recorded every sample of both.  Bin configs must match
        (lo/bins-per-decade/bin count); merging histograms with
        different edges would silently mis-bin, so it is rejected."""
        if not isinstance(other, LatencyHistogram):
            raise TypeError(f"cannot merge {type(other).__name__} into "
                            f"LatencyHistogram")
        if (self._lo, self._k, self._nbins) != (other._lo, other._k,
                                                other._nbins):
            raise ValueError(
                f"histogram bin configs differ: "
                f"(lo_ms={self._lo}, bins_per_decade={self._k}, "
                f"nbins={self._nbins}) vs (lo_ms={other._lo}, "
                f"bins_per_decade={other._k}, nbins={other._nbins})")
        # lock ordering: snapshot other first, then fold under our lock
        # (never hold both — merge(a, b) vs merge(b, a) would deadlock)
        with other._lock:
            counts = list(other._counts)
            o_count, o_sum, o_max = other.count, other.sum_ms, other.max_ms
        with self._lock:
            for i, c in enumerate(counts):
                self._counts[i] += c
            self.count += o_count
            self.sum_ms += o_sum
            if o_max > self.max_ms:
                self.max_ms = o_max
        return self

    def cumulative_buckets(self):
        """[(upper_edge_ms, cumulative_count), ...] over the non-empty
        bins — the Prometheus `le` mapping: each log-spaced bin's upper
        edge becomes an `le` value and the counts are exact prefix
        sums, so a scraped histogram reproduces this histogram's
        percentiles to bin resolution (the exposition contract of
        observe.registry; pinned by tests)."""
        with self._lock:
            counts = list(self._counts)
        out = []
        acc = 0
        for i, c in enumerate(counts):
            if c:
                acc += c
                out.append((self._edge(i), acc))
        return out

    def percentile(self, p: float) -> Optional[float]:
        """p in [0, 100] → latency ms (bin upper edge), None if empty."""
        with self._lock:
            if self.count == 0:
                return None
            rank = p / 100.0 * self.count
            acc = 0
            for i, c in enumerate(self._counts):
                acc += c
                if acc >= rank:
                    # never report past the observed max (the top bins
                    # are coarse)
                    return min(self._edge(i), self.max_ms)
            return self.max_ms

    def summary(self) -> Dict[str, Any]:
        """{count, mean_ms, sum_ms, max_ms, p50_ms, p95_ms, p99_ms} —
        the serving_window wire form."""
        with self._lock:
            count, total, mx = self.count, self.sum_ms, self.max_ms
        out: Dict[str, Any] = {"count": count}
        out["sum_ms"] = round(total, 3)
        out["mean_ms"] = round(total / count, 3) if count else None
        out["max_ms"] = round(mx, 3) if count else None
        for p in (50, 95, 99):
            v = self.percentile(p)
            out[f"p{p}_ms"] = round(v, 3) if v is not None else None
        return out
