"""Inference serving: AOT-compiled Predictor + portable export.

TPU-native analog of the reference inference API
(reference: paddle/fluid/inference/api/analysis_predictor.cc:56
AnalysisPredictor — load model, run analysis/fusion passes, serve with a
NaiveExecutor and zero-copy tensors; api/paddle_analysis_config.h
AnalysisConfig; api/paddle_api.h PaddlePredictor ABI).

Mapping:
- the analysis/fusion pass pipeline → XLA compilation (the whole pruned
  program is jitted once; fusion is the compiler's job),
- AnalysisPredictor's warm NaiveExecutor loop → an AOT-compiled
  executable cached per input signature; params stay device-resident
  between calls (the zero-copy contract),
- the `__model__` + params dir → same layout (io.py), plus an optional
  portable serialized artifact (`__model__.export`, jax.export/StableHLO
  bytes) that loads WITHOUT re-tracing the program — the saved-engine
  analog of the reference's TensorRT serialized engines.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .core.executor import (RNG_STATE_VAR, Scope, interpret_program,
                            prune_ops)
from .core.program import Program
from .io import EXPORT_FILENAME, load_inference_model


class AnalysisConfig:
    """reference: api/paddle_analysis_config.h (knobs that map to XLA are
    kept; GPU/MKLDNN/TensorRT switches are parity no-ops on TPU)."""

    def __init__(self, model_dir: Optional[str] = None):
        self.model_dir = model_dir
        self.use_serialized_artifact = True
        self.use_int8 = False
        self._params_file = None
        self._model_file = None

    # -- fluid-style setters (parity) -----------------------------------
    def set_model(self, model_dir: str):
        self.model_dir = model_dir

    def enable_int8(self):
        """Serve with REAL int8 kernels: trained QAT scales freeze into
        quantized_conv2d/quantized_matmul ops (int8 MXU path) at load
        time (quantize.py convert_to_int8).  The model must have been
        exported from a QAT-transpiled program; models without the QAT
        pattern load unchanged.  Reference analog:
        enable_tensorrt_engine(precision=Int8) /
        enable_mkldnn_quantizer() in paddle_analysis_config.h."""
        self.use_int8 = True
        # int8 rewrites happen after load; a serialized float artifact
        # would silently serve fp — disable it for this predictor
        self.use_serialized_artifact = False
        return self

    def disable_gpu(self):
        pass

    def switch_ir_optim(self, _on=True):
        pass  # XLA always optimizes

    def enable_memory_optim(self):
        pass  # XLA buffer liveness


class Predictor:
    """AOT inference engine (reference AnalysisPredictor::Run,
    analysis_predictor.cc:170, ZeroCopyRun :444).

    run(feed) compiles on first use per input signature
    (`.lower().compile()`, no retracing afterwards) and keeps parameters
    device-resident.  When the export dir carries a serialized artifact
    and the input signature matches, the artifact is used directly — no
    tracing at all (cold-start path).
    """

    def __init__(self, config: AnalysisConfig | str):
        if isinstance(config, str):
            config = AnalysisConfig(config)
        self.config = config
        from .core.executor import Executor

        self._scope = Scope()
        from .core.executor import scope_guard

        exe = Executor()
        self.int8_converted: Dict[int, tuple] = {}
        with scope_guard(self._scope):
            self._program, self._feed_names, fetch_vars = \
                load_inference_model(config.model_dir, exe)
            if config.use_int8:
                from .quantize import convert_to_int8

                self.int8_converted = convert_to_int8(self._program,
                                                      self._scope)
        self._fetch_names = [v.name for v in fetch_vars]
        import jax

        # params to device once (zero-copy across run() calls)
        self._params = {
            n: jax.device_put(v) for n, v in self._scope.vars.items()
            if v is not None and n != RNG_STATE_VAR
        }
        self._compiled: Dict[tuple, object] = {}
        self._exported = None
        self._export_sig = None
        path = os.path.join(config.model_dir, EXPORT_FILENAME)
        if config.use_serialized_artifact and os.path.exists(path):
            import json

            from jax import export as jax_export

            with open(path, "rb") as f:
                self._exported = jax_export.deserialize(f.read())
            sig_path = path + ".json"
            if os.path.exists(sig_path):
                # the artifact is tied to the exact __model__ it was
                # exported from; a re-saved model or a malformed/old-
                # format sidecar invalidates it rather than crashing or
                # silently serving the old graph
                try:
                    with open(sig_path) as f:
                        meta = json.load(f)
                    ok = (isinstance(meta, dict)
                          and meta.get("model_hash")
                          == _model_hash(config.model_dir))
                    if ok:
                        self._export_sig = tuple(
                            (n, tuple(s), d)
                            for n, s, d in meta["signature"])
                except (ValueError, KeyError, TypeError, OSError):
                    ok = False
                if not ok:
                    self._exported = None

    # -- introspection (PaddlePredictor parity) -------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    # -- execution ------------------------------------------------------
    def _signature(self, feeds):
        # feeds are jnp arrays by the time this is called: .shape/.dtype
        # are metadata reads, no device→host transfer
        return tuple(sorted((n, tuple(v.shape), str(v.dtype))
                            for n, v in feeds.items()))

    def _exported_matches(self, feeds) -> bool:
        """The artifact serves a request only when the per-input
        (name, shape, dtype) signature recorded at export time matches
        exactly; anything else falls back to the traced path."""
        if self._exported is None or self._export_sig is None:
            return False
        return self._signature(feeds) == self._export_sig

    def compile_signature(self, feed_spec: Dict[str, object],
                          donate_feeds: bool = False):
        """AOT-compile the inference executable for one input signature
        WITHOUT example data (the serving warmup path: feed_spec maps
        input name → jax.ShapeDtypeStruct).  The executable lands in
        the same per-signature cache run() consults, so a later run()
        with feeds of exactly this signature dispatches the precompiled
        executable — serving.ServingEngine precompiles its whole shape-
        bucket ladder through here and then never compiles again.

        donate_feeds=True donates the feed buffers to XLA (outputs may
        reuse input memory — the right call for a serving engine that
        pads a FRESH host batch per dispatch).  Do not enable it on a
        Predictor that is also run() with device-resident feeds reused
        across calls (e.g. benchmark(zero_copy=True)): a donated buffer
        is dead after the call.  Params are never donated.

        Idempotent per signature; returns the compiled executable."""
        import jax

        sig = tuple(sorted(
            (n, tuple(s.shape), str(np.dtype(s.dtype)))
            for n, s in feed_spec.items()))
        entry = self._compiled.get(sig)
        if entry is not None:
            return entry
        program = self._program
        fetch_names = self._fetch_names

        def infer(params, feeds):
            env = dict(params)
            env.update(feeds)
            env = interpret_program(program, env, None,
                                    fetch_names=tuple(fetch_names))
            return [env[n] for n in fetch_names]

        jitted = (jax.jit(infer, donate_argnums=(1,)) if donate_feeds
                  else jax.jit(infer))
        entry = jitted.lower(self._params, dict(feed_spec)).compile()
        self._compiled[sig] = entry
        return entry

    def run(self, feed: Dict[str, np.ndarray] | Sequence[np.ndarray]):
        """Returns fetch arrays (list, fetch order from export)."""
        import jax
        import jax.numpy as jnp

        if not isinstance(feed, dict):
            if len(feed) != len(self._feed_names):
                raise ValueError(
                    f"expected {len(self._feed_names)} inputs "
                    f"({self._feed_names}), got {len(feed)}")
            feed = dict(zip(self._feed_names, feed))
        feeds = {n: jnp.asarray(v) for n, v in feed.items()}

        sig = self._signature(feeds)
        entry = self._compiled.get(sig)
        # an already-compiled executable beats the serialized artifact
        # (the artifact exists to skip TRACING on cold start; its own
        # first .call still pays an XLA compile — a warmed signature,
        # e.g. a serving bucket precompiled via compile_signature, must
        # never fall back to that and recompile post-warmup)
        if entry is None and self._exported_matches(feeds):
            outs = self._exported.call(
                {n: self._params[n] for n in sorted(self._params)},
                {n: feeds[n] for n in sorted(feeds)})
            return [np.asarray(o) for o in outs]

        if entry is None:
            program = self._program
            fetch_names = self._fetch_names

            def infer(params, feeds):
                env = dict(params)
                env.update(feeds)
                env = interpret_program(program, env, None,
                                        fetch_names=tuple(fetch_names))
                return [env[n] for n in fetch_names]

            lowered = jax.jit(infer).lower(self._params, feeds)
            entry = lowered.compile()
            self._compiled[sig] = entry
        return [np.asarray(o) for o in entry(self._params, feeds)]

    def benchmark(self, feed, iters: int = 50, warmup: int = 5,
                  zero_copy: bool = True):
        """Serving latency probe: returns {p50_ms, mean_ms}.

        zero_copy=True places the inputs on device once and times the
        warm executable (the reference's ZeroCopyRun measurement,
        analysis_predictor.cc:444); zero_copy=False times end-to-end
        including host→device input transfer."""
        import jax
        import jax.numpy as jnp

        if zero_copy and isinstance(feed, dict):
            feed = {n: jax.device_put(jnp.asarray(v))
                    for n, v in feed.items()}
            for v in feed.values():
                v.block_until_ready()
        for _ in range(warmup):
            self.run(feed)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            self.run(feed)
            times.append((time.perf_counter() - t0) * 1e3)
        times.sort()
        result = {"p50_ms": times[len(times) // 2],
                  "mean_ms": sum(times) / len(times)}
        result["compute_ms"] = self._chained_latency_ms(feed)
        return result

    def _chained_latency_ms(self, feed, k: int = 20):
        """Per-inference device latency with host dispatch amortized over
        k chained requests (a lax.scan over k stacked copies of the
        input, so the body can't be loop-hoisted).  This is the number
        that matters when a real serving frontend keeps the device queue
        full; p50_ms above includes the host↔device round-trip."""
        import jax
        import jax.numpy as jnp

        feeds = {n: jnp.asarray(v) for n, v in feed.items()}
        program = self._program
        fetch_names = self._fetch_names

        def one(params, f):
            env = dict(params)
            env.update(f)
            env = interpret_program(program, env, None,
                                    fetch_names=tuple(fetch_names))
            return [env[n] for n in fetch_names]

        stacked = {n: jnp.stack([v] * k) for n, v in feeds.items()}

        def chained(params, xs):
            def body(_, f):
                return None, one(params, f)

            _, outs = jax.lax.scan(body, None, xs)
            return [o[-1] for o in outs]

        fn = jax.jit(chained).lower(self._params, stacked).compile()
        [o.block_until_ready() for o in fn(self._params, stacked)]
        t0 = time.perf_counter()
        [o.block_until_ready() for o in fn(self._params, stacked)]
        return (time.perf_counter() - t0) * 1e3 / k


    def clone(self) -> "Predictor":
        """Thread-safe sibling predictor SHARING device-resident weights
        and compiled executables (reference AnalysisPredictor::Clone,
        analysis_predictor.cc:56 — per-thread predictors over one
        parameter scope).  XLA executions are internally thread-safe and
        parameters are immutable at serving time, so clones share
        `_params`, `_compiled`, and the program; each clone only carries
        its own handle.  Typical use: one clone per serving thread."""
        twin = object.__new__(Predictor)
        twin.config = self.config
        twin.int8_converted = self.int8_converted
        twin._scope = self._scope
        twin._program = self._program
        twin._feed_names = self._feed_names
        twin._fetch_names = self._fetch_names
        twin._params = self._params          # shared device weights
        twin._compiled = self._compiled      # shared executable cache
        twin._exported = self._exported
        twin._export_sig = self._export_sig
        return twin


def create_paddle_predictor(config: AnalysisConfig) -> Predictor:
    """reference: CreatePaddlePredictor<AnalysisConfig>
    (analysis_predictor.cc:359)."""
    return Predictor(config)


def export_serialized_model(dirname: str, example_feed: Dict[str, np.ndarray],
                            executor=None):
    """AOT-export the saved inference model as a portable artifact
    (jax.export / StableHLO bytes) for the shapes of `example_feed`.
    Written next to `__model__` as `__model__.export`; Predictor uses it
    when input shapes match, skipping program re-tracing entirely.
    Replaces the reference's serialized-engine path
    (analysis_predictor.cc + tensorrt engine serialization)."""
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export

    from .core.executor import Executor, scope_guard

    scope = Scope()
    exe = executor or Executor()
    with scope_guard(scope):
        program, feed_names, fetch_vars = load_inference_model(dirname, exe)
    fetch_names = [v.name for v in fetch_vars]
    params = {n: v for n, v in scope.vars.items()
              if v is not None and n != RNG_STATE_VAR}
    missing = set(feed_names) - set(example_feed)
    if missing:
        raise ValueError(f"example_feed missing inputs: {sorted(missing)}")

    def infer(params, feeds):
        env = dict(params)
        env.update(feeds)
        env = interpret_program(program, env, None,
                                fetch_names=tuple(fetch_names))
        return [env[n] for n in fetch_names]

    params_spec = {n: jax.ShapeDtypeStruct(np.shape(v),
                                           np.asarray(v).dtype)
                   for n, v in sorted(params.items())}
    feed_spec = {n: jax.ShapeDtypeStruct(np.shape(v),
                                         jnp.asarray(v).dtype)
                 for n, v in sorted(example_feed.items())}
    exported = jax_export.export(jax.jit(infer))(params_spec, feed_spec)
    path = os.path.join(dirname, EXPORT_FILENAME)
    with open(path, "wb") as f:
        f.write(exported.serialize())
    import json

    sig = sorted((n, list(s.shape), str(np.dtype(s.dtype)))
                 for n, s in feed_spec.items())
    with open(path + ".json", "w") as f:
        json.dump({"signature": sig,
                   "model_hash": _model_hash(dirname)}, f)
    return path


def _model_hash(dirname: str) -> str:
    import hashlib

    from .io import MODEL_FILENAME

    h = hashlib.sha256()
    with open(os.path.join(dirname, MODEL_FILENAME), "rb") as f:
        h.update(f.read())
    return h.hexdigest()
