"""Continuous-batching autoregressive decode over a paged KV cache.

The generative half of the serving subsystem (ISSUE 12): where
`engine.ServingEngine` serves single-shot inference over shape
buckets, this engine serves DECODE — requests that produce tokens one
iteration at a time, live for wildly different lengths, and would
waste most of the chip under static batching (a batch is as slow as
its longest member, and a dense per-request KV buffer reserves
worst-case memory for every slot).  The design is Ragged Paged
Attention's (PAPERS.md arxiv 2604.15464):

- **fixed-slot batch, paged KV pool** — `num_slots` decode lanes whose
  K/V lives in fixed-size PAGES of one shared pool, addressed through
  per-slot page tables.  Pages are allocated on admit, extended as a
  slot grows, and returned the moment it finishes — memory follows the
  RAGGED true lengths, not the worst case.
- **iteration-level (continuous) batching** — new requests join an
  open slot BETWEEN decode iterations (prefill-on-join through a
  bucketed prompt ladder), instead of waiting for a full batch.  The
  admission/circuit-breaker plane (`admission.py`) is wired in from
  day one: bounded queue, fast-reject shedding, deadline drops,
  breaker on executor failures.
- **preemption** — when the pool runs dry, the lowest-priority slot is
  evicted (pages returned, request requeued); greedy decode makes the
  regenerated tokens identical, so preemption is invisible to callers
  except in latency (and in the `preemptions` counter).
- **jitted While-based decode** — each dispatch runs up to
  `decode_chunk` iterations as ONE `lax.while_loop` on device (the one
  loop reserved for decode per CLAUDE.md), exiting early the moment
  any slot finishes so its pages free and a queued request can join.
  Chunking amortizes the host's dispatch round trip over many tokens
  (the TTFT/TPOT convention in stats.py).

Every executable has a FIXED shape: the slot batch, the pool, the page
tables, and the chunk bound never change across joins/leaves/
preemptions, so steady state performs ZERO XLA compiles — the same
contract, accounting, and loud-event plumbing as ServingEngine.  The
pool is sized up front with `observe.memory.plan_fit` (two small-pool
probe compiles extrapolate the peak) and impossible configs are
rejected with a structured `DecodeMemoryError` BEFORE warmup, the way
`ServingEngine.start()` rejects bucket ladders.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..observe.events import RunEventLog
from ..observe.monitoring import runtime_stats
from .admission import (AdmissionController, CircuitBreaker,
                        DeadlineExceededError, ExecutorFailureError,
                        ServingClosedError, ServingError,
                        WeightReloadError)
from .engine import BucketConfig
from .stats import DecodeStats


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


class DecodeBucketMissError(ServingError):
    """The request fits no prefill bucket / exceeds the slot length
    budget (structured: carries the offending lengths and ladder)."""

    kind = "decode_bucket_miss"


class DecodeMemoryError(ServingError):
    """The configured slot/pool geometry's PREDICTED peak memory
    exceeds the device budget — raised by start() BEFORE warmup from
    the observe.memory fit planner's small-pool probes."""

    kind = "decode_memory"


class DecodeReplicaFailedError(ServingError):
    """An accepted request was pulled off its replica mid-generation —
    the scheduler died, the request was evacuated for a weight roll,
    or the engine shut down with it unresolved.

    RETRYABLE by construction: greedy decode regenerates
    token-identically from the prompt alone, so the error carries the
    full requeue `descriptor` (prompt, sampling params, priority, the
    committed-token count and the tokens emitted so far) — a router
    resubmits it on a surviving replica and can verify the
    regeneration reproduces the committed prefix exactly.  `reason` is
    one of "scheduler_failed" / "evacuated" / "shutdown"; `cause`
    carries the original failure when one exists."""

    kind = "decode_replica_failed"
    retryable = True


class DecodeConfig:
    """Geometry + scheduling knobs of the decode engine.

    num_slots: fixed decode lanes (the device batch).
    page_size: tokens per KV page.
    max_len: per-slot budget (prompt + generated); sets the page-table
        width `max_pages_per_slot`.
    num_pages: shared pool size.  Default: slots * pages-per-slot (no
        preemption pressure); size it TIGHTER than the worst case to
        trade preemptions for memory — `kv_page_utilization` and
        `preemptions` in the stats tell you where you landed.
    prefill_buckets: ascending prompt-length ladder; one prefill
        executable compiles per bucket at start() (a prompt pads UP to
        the smallest fitting bucket).
    decode_chunk: max While iterations per decode dispatch (early-exits
        when a slot finishes).
    eos_id: optional stop token.
    kv_dtype: pool storage — "float32" (exact parity), "bfloat16"
        (default production), or "int8" (per-row scale sidecars,
        opt-in; default stays bf16, no ledger row on either side).
    """

    def __init__(self, num_slots: int = 8, page_size: int = 16,
                 max_len: int = 256, num_pages: Optional[int] = None,
                 prefill_buckets: Sequence[int] = (32, 64, 128),
                 decode_chunk: int = 8, eos_id: Optional[int] = None,
                 kv_dtype: str = "bfloat16"):
        if num_slots < 1 or page_size < 1 or max_len < 2:
            raise ValueError("num_slots/page_size >= 1, max_len >= 2")
        if decode_chunk < 1:
            raise ValueError("decode_chunk must be >= 1")
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.max_pages_per_slot = _cdiv(self.max_len, self.page_size)
        self.num_pages = int(num_pages) if num_pages is not None else \
            self.num_slots * self.max_pages_per_slot
        self.prefill_buckets = BucketConfig._ladder("prefill_buckets",
                                                    prefill_buckets)
        if self.prefill_buckets[-1] > self.max_len:
            raise ValueError(
                f"largest prefill bucket {self.prefill_buckets[-1]} "
                f"exceeds max_len {self.max_len}")
        if self.num_pages < self.max_pages_per_slot:
            raise ValueError(
                f"num_pages {self.num_pages} below max_pages_per_slot "
                f"{self.max_pages_per_slot}: one max-length request "
                f"could never be served, even alone")
        self.decode_chunk = int(decode_chunk)
        self.eos_id = eos_id
        self.kv_dtype = str(kv_dtype)


class DecodeRequest:
    """One accepted generation request."""

    __slots__ = ("prompt", "max_new_tokens", "priority", "future",
                 "deadline", "t_submit", "preempted", "trace",
                 "handoff")

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 priority: int = 0, deadline: Optional[float] = None,
                 trace=None):
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.priority = int(priority)
        self.future: Future = Future()
        self.deadline = deadline
        self.t_submit = time.monotonic()
        self.preempted = 0
        self.trace = trace  # observe.reqtrace.RequestTrace (or None)
        self.handoff = None  # disagg: imported KV package (decode role)

    def descriptor(self, generated: Optional[List[int]] = None
                   ) -> Dict[str, Any]:
        """The requeue wire form a router resubmits on another replica
        (and verifies token-identity against): everything that defines
        the greedy generation, plus what this replica had already
        committed."""
        gen = [int(t) for t in (generated or [])]
        return {"prompt": [int(t) for t in self.prompt],
                "max_new_tokens": self.max_new_tokens,
                "priority": self.priority,
                "deadline": self.deadline,
                "committed_tokens": len(gen),
                "generated": gen,
                "preempted": self.preempted}


class PagePool:
    """Host-side free-list allocator over the device pool's page
    indices.  Single-threaded (the scheduler owns it)."""

    def __init__(self, num_pages: int):
        self.num_pages = int(num_pages)
        self._free = list(range(num_pages - 1, -1, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_pages - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        got = self._free[-n:][::-1]
        del self._free[-n:]
        return got

    def free(self, pages: List[int]):
        self._free.extend(reversed(pages))


class _Slot:
    """Scheduler-side state of one decode lane."""

    __slots__ = ("req", "pages", "committed", "generated", "cur_tok",
                 "remaining", "version")

    def __init__(self, req: DecodeRequest, pages: List[int],
                 version: int = 0):
        self.req = req
        self.pages = pages
        self.committed = len(req.prompt)   # tokens whose KV is pooled
        self.generated: List[int] = []     # tokens produced so far
        self.cur_tok = 0                   # pending (uncommitted) token
        self.remaining = req.max_new_tokens
        self.version = version             # model_version that serves
        #                                    this whole generation

    @property
    def cap_tokens(self) -> int:
        # the LAST generated token is never committed to KV
        return len(self.req.prompt) + self.req.max_new_tokens - 1

    def importance(self):
        # higher tuple = more important (kept under preemption)
        return (self.req.priority, -self.req.t_submit)


class DecodeEngine:
    """Continuous-batching decode endpoint over a DecoderLM.

        lm = DecoderLM(vocab_size=...)
        engine = DecodeEngine(lm, DecodeConfig(num_slots=8))
        engine.start()                       # plan_fit gate + warmup
        fut = engine.submit(prompt_ids, max_new_tokens=64)
        tokens = fut.result()                # np.int32 generated ids
        engine.close()

    model: a models.decoder_lm.DecoderLM (programs + parameter scope).
    Threading: submit() from any thread; ONE scheduler thread owns
    dispatch, the page pool, and the slot table.
    """

    def __init__(self, model, config: Optional[DecodeConfig] = None,
                 queue_capacity: int = 128,
                 default_deadline_ms: Optional[float] = None,
                 event_log: Optional[RunEventLog] = None,
                 log_path: Optional[str] = None,
                 stats_window: int = 64,
                 breaker: Union[CircuitBreaker, bool, None] = None,
                 memory_budget_bytes: Union[int, bool, None] = None,
                 donate_pools: Optional[bool] = None, tracer=None,
                 role: str = "unified", speculate_k: int = 0,
                 drafter=None):
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be 'unified', 'prefill' or 'decode'; "
                f"got {role!r}")
        # speculative decoding (ISSUE 20, serving/speculate.py): a
        # drafter proposes up to k tokens per slot, ONE fixed-shape
        # verify dispatch (the step program at folded batch S*(k+1))
        # scores them all, greedy longest-accepted-prefix acceptance
        # commits 1..k+1 tokens bit-identical to the sequential engine
        self.speculate_k = int(speculate_k or 0)
        if self.speculate_k < 0:
            raise ValueError(
                f"speculate_k must be >= 0, got {speculate_k}")
        if self.speculate_k and role == "prefill":
            raise ValueError(
                "speculate_k requires a decoding role — a "
                "role='prefill' worker never runs decode steps; put "
                "the drafter on the decode workers (serving/disagg.py)")
        if drafter is not None and not self.speculate_k:
            raise ValueError("drafter given but speculate_k is 0")
        self.drafter = None
        if self.speculate_k:
            from .speculate import NGramDrafter

            self.drafter = (drafter if drafter is not None
                            else NGramDrafter(self.speculate_k))
            if getattr(self.drafter, "k", None) != self.speculate_k:
                raise ValueError(
                    f"drafter.k {getattr(self.drafter, 'k', None)} != "
                    f"speculate_k {self.speculate_k}")
        # disagg phase specialization (serving/disagg.py): a "prefill"
        # engine compiles only the bucket ladder plus a page-EXPORT
        # gather and resolves every request with a KV handoff package;
        # a "decode" engine compiles only the chunk loop plus a
        # fixed-shape page-IMPORT scatter and admits requests through
        # import_handoff().  "unified" is the byte-identical default.
        self.role = role
        self.model = model
        # observe pillar 7: per-request tracing (host spans only —
        # join_wait, per-chunk dispatch, preempt/evacuated markers);
        # None disables, fleet-passed traces ride through regardless
        self.tracer = tracer
        self.config = config or DecodeConfig(kv_dtype=model.kv_dtype)
        if self.config.kv_dtype != model.kv_dtype:
            raise ValueError(
                f"config.kv_dtype {self.config.kv_dtype!r} != model "
                f"kv_dtype {model.kv_dtype!r}")
        self._own_log = None
        if event_log is None and log_path is not None:
            event_log = self._own_log = RunEventLog(
                log_path, meta={"component": "decode_engine"})
        self._event_log = event_log
        self.stats = DecodeStats(event_log=event_log,
                                 window=stats_window)
        if self.speculate_k:
            self.stats.configure_speculation(self.speculate_k)
        if breaker is None:
            breaker = CircuitBreaker(failure_threshold=5, cooldown_s=5.0)
        elif breaker is False:
            breaker = None
        self.admission = AdmissionController(
            queue_capacity, default_deadline_ms=default_deadline_ms,
            breaker=breaker)
        self.memory_budget_bytes = memory_budget_bytes
        self.fit_plan: Optional[Dict[str, Any]] = None
        if donate_pools is None:
            import jax

            donate_pools = jax.default_backend() == "tpu"
        self._donate = bool(donate_pools)

        self.scope = model.init_params()
        import jax
        import jax.numpy as jnp

        from ..core.executor import RNG_STATE_VAR

        self._params = {
            n: jax.device_put(jnp.asarray(v))
            for n, v in self.scope.vars.items()
            if v is not None and n != RNG_STATE_VAR}
        self._cache_names = model.cache_feed_names()
        self._pools: Optional[Dict[str, Any]] = None
        self._decode_exec = None
        self._verify_exec = None   # speculate_k > 0: replaces the
        #                            sequential chunk executable
        self._prefill_execs: Dict[int, Any] = {}
        self._export_exec = None   # role="prefill": page gather
        self._import_exec = None   # role="decode": page scatter
        self.page_pool = PagePool(self.config.num_pages)
        self._page_tables = np.zeros(
            (self.config.num_slots, self.config.max_pages_per_slot),
            np.int32)
        self._slots: List[Optional[_Slot]] = \
            [None] * self.config.num_slots
        self._queue: List[DecodeRequest] = []
        self._unresolved = 0      # accepted requests not yet resolved
        self._cv = threading.Condition()
        self._worker: Optional[threading.Thread] = None
        self._stop = False
        self._started = False
        # fleet surface: replica identity, weight version, and the
        # control requests (evacuation / weight swap) the scheduler
        # services between dispatches
        self.replica_id: Optional[int] = None
        self.model_version = 0
        self._evac_waiters: List[Dict[str, Any]] = []
        self._pending_reload: Optional[Dict[str, Any]] = None

    def set_replica_id(self, replica_id: int) -> None:
        """Name this engine as fleet replica `replica_id` and stamp the
        id on every event it (and its stats) emits — N replicas sharing
        one RunEventLog stay disambiguated (the log's write lock
        already makes the concurrent emits safe; this makes them
        attributable)."""
        self.replica_id = int(replica_id)
        if self._event_log is not None \
                and hasattr(self._event_log, "bind"):
            bound = self._event_log.bind(replica_id=self.replica_id)
            self._event_log = bound
            self.stats._event_log = bound

    # -- jitted executables ---------------------------------------------
    def _feed_env(self, params, pools, **feeds):
        env = dict(params)
        env.update(pools)
        env.update(feeds)
        return env

    def _build_decode_fn(self):
        import jax
        import jax.numpy as jnp

        from ..core.executor import interpret_program

        st = self.model.step
        program = st["main"]
        next_name = st["next_token"]
        cache_outs = st["cache_outs"]
        cache_names = self._cache_names
        fetches = (next_name, *cache_outs)
        chunk = self.config.decode_chunk
        eos = self.config.eos_id

        def chunk_fn(params, tokens, write_pos, active, remaining,
                     page_table, pools):
            outbuf0 = jnp.full((tokens.shape[0], chunk), -1, jnp.int32)

            def cond(c):
                i, _t, _w, act, fin_any, _r, _p, _o = c
                return ((i < chunk) & jnp.logical_not(fin_any)
                        & (jnp.sum(act) > 0))

            def body(c):
                i, tok, wp, act, _fin, rem, pls, outbuf = c
                env = self._feed_env(
                    params, pls, tokens=tok, write_pos=wp,
                    lengths=wp + 1, active=act, page_table=page_table)
                env = interpret_program(program, env, None,
                                        fetch_names=fetches)
                nxt = env[next_name].astype(jnp.int32)
                new_pools = {n: env[o] for n, o in
                             zip(cache_names, cache_outs)}
                produced = act > 0
                outbuf = outbuf.at[:, i].set(jnp.where(produced, nxt,
                                                       -1))
                new_wp = wp + act
                new_rem = rem - act
                fin = produced & (new_rem <= 0)
                if eos is not None:
                    fin = fin | (produced & (nxt == eos))
                new_act = jnp.where(fin, 0, act)
                new_tok = jnp.where(produced, nxt, tok)
                return (i + 1, new_tok, new_wp, new_act, jnp.any(fin),
                        new_rem, new_pools, outbuf)

            init = (jnp.int32(0), tokens, write_pos, active,
                    jnp.bool_(False), remaining, pools, outbuf0)
            (steps, tok, wp, act, _fin, rem, pls, outbuf) = \
                jax.lax.while_loop(cond, body, init)
            return outbuf, steps, tok, wp, act, rem, pls

        return chunk_fn

    def _build_verify_fn(self):
        """Speculative verify: ONE dispatch of the step body at folded
        batch S*(k+1) — row (s, j) scores position committed_s + j,
        staggered lengths make it causal, inactive rows' KV writes
        drop, and greedy acceptance (`speculative_accept`) runs
        in-program.  Returns (accepted (S,), tokens (S, k+1), pools);
        the rejected-tail 'rollback' is the host simply not advancing
        the slot past the accepted position."""
        import jax.numpy as jnp

        from ..core.executor import interpret_program

        ver = self.model.verify(self.speculate_k)
        program = ver["main"]
        acc_name = ver["accepted"]
        tok_name = ver["tokens"]
        cache_outs = ver["cache_outs"]
        cache_names = self._cache_names
        fetches = (acc_name, tok_name, *cache_outs)

        def verify_fn(params, folded, drafts, slot_meta, page_table,
                      pools):
            # folded rows: [tokens, write_pos, lengths, active] at
            # (4, S*(k+1)); slot_meta rows: [draft_len, slot_active]
            # at (2, S).  Packing the small int feeds into two arrays
            # keeps the per-round host->device transfer count low —
            # the verify round races the sequential engine's chunk
            # dispatch, so feed overhead is on the critical path.
            env = self._feed_env(
                params, pools, tokens=folded[0], write_pos=folded[1],
                lengths=folded[2], active=folded[3], drafts=drafts,
                draft_len=slot_meta[0], slot_active=slot_meta[1],
                page_table=page_table)
            env = interpret_program(program, env, None,
                                    fetch_names=fetches)
            new_pools = {n: env[o] for n, o in
                         zip(cache_names, cache_outs)}
            return (env[acc_name].astype(jnp.int32),
                    env[tok_name].astype(jnp.int32), new_pools)

        return verify_fn

    def _build_prefill_fn(self, t_bucket: int):
        import jax.numpy as jnp

        from ..core.executor import interpret_program

        pre = self.model.prefill(t_bucket)
        program = pre["main"]
        next_name = pre["next_token"]
        cache_outs = pre["cache_outs"]
        cache_names = self._cache_names
        fetches = (next_name, *cache_outs)

        def prefill_fn(params, tokens, seq_len, last_idx, page_table,
                       pools):
            env = self._feed_env(
                params, pools, tokens=tokens, seq_len=seq_len,
                last_idx=last_idx, page_table=page_table)
            env = interpret_program(program, env, None,
                                    fetch_names=fetches)
            nxt = env[next_name].astype(jnp.int32)
            return nxt, {n: env[o]
                         for n, o in zip(cache_names, cache_outs)}

        return prefill_fn

    def _build_export_fn(self):
        """role="prefill": gather ONE slot's pool pages into dense
        token-major rows (T_cap, C), T_cap = max_pages_per_slot *
        page_size.  Fixed shape for any slot/prompt — rows past the
        committed length gather whatever the zero page-table padding
        points at and are masked again on import (NumValid)."""

        def export_fn(page_table_row, pools):
            out = {}
            for n, p in pools.items():
                g = p[page_table_row]        # (maxp, page, C)
                out[n] = g.reshape(g.shape[0] * g.shape[1], g.shape[2])
            return out

        return export_fn

    def _build_import_fn(self):
        """role="decode": scatter one handoff's exported rows into this
        worker's OWN pool pages (the receiving slot's page-table row)
        via the drop-mode paged scatter — one fixed shape serves any
        join/handoff/failover pattern, the zero-recompile contract
        across the hop."""
        from ..ops.paged_kv import paged_import_rows

        def import_fn(rows, page_table_row, num_valid, pools):
            return {n: paged_import_rows(pools[n], rows[n],
                                         page_table_row, num_valid)
                    for n in pools}

        return import_fn

    def _specs(self):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        s = cfg.num_slots
        i32 = jnp.int32
        vec = jax.ShapeDtypeStruct((s,), i32)
        pt = jax.ShapeDtypeStruct((s, cfg.max_pages_per_slot), i32)
        pool_specs = self.model.pool_specs(cfg.num_pages,
                                           cfg.page_size)
        params_spec = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                       for n, v in self._params.items()}
        return params_spec, vec, pt, pool_specs

    # -- lifecycle ------------------------------------------------------
    def start(self) -> "DecodeEngine":
        """Validate the geometry (plan_fit memory gate), AOT-compile
        every executable (decode chunk + one prefill per bucket), then
        open for traffic.  Steady state performs zero XLA compiles."""
        import jax

        with self._cv:
            if self._started:
                raise RuntimeError("engine already started")
            self._started = True
        cfg = self.config
        if self._event_log is not None:
            self._event_log.event(
                "serving_decode_start",
                num_slots=cfg.num_slots, page_size=cfg.page_size,
                num_pages=cfg.num_pages, max_len=cfg.max_len,
                prefill_buckets=list(cfg.prefill_buckets),
                decode_chunk=cfg.decode_chunk, kv_dtype=cfg.kv_dtype,
                role=self.role,
                queue_capacity=self.admission.queue_capacity)
        snap = runtime_stats.snapshot()
        t0 = time.perf_counter()
        # memory gate BEFORE any full-size compile OR pool allocation
        # (DecodeMemoryError) — an impossible geometry never touches
        # the device at its configured size
        self._validate_memory_budget()
        self._pools = {n: jax.device_put(v) for n, v in
                       self.model.fresh_pools(cfg.num_pages,
                                              cfg.page_size).items()}
        params_spec, vec, pt, pool_specs = self._specs()
        i32 = jax.numpy.int32
        n_exec = 0
        if self.role != "prefill":
            if self.speculate_k:
                # the verify executable REPLACES the sequential chunk
                # loop: one fixed folded shape serves any accept
                # pattern (ragged drafts ride the draft_len companion)
                k1 = self.speculate_k + 1
                fmat = jax.ShapeDtypeStruct((4, cfg.num_slots * k1),
                                            i32)
                fpt = jax.ShapeDtypeStruct(
                    (cfg.num_slots * k1, cfg.max_pages_per_slot), i32)
                dspec = jax.ShapeDtypeStruct(
                    (cfg.num_slots, self.speculate_k), i32)
                smeta = jax.ShapeDtypeStruct((2, cfg.num_slots), i32)
                donate = (5,) if self._donate else ()
                self._verify_exec = jax.jit(
                    self._build_verify_fn(),
                    donate_argnums=donate).lower(
                        params_spec, fmat, dspec, smeta, fpt,
                        pool_specs).compile()
            else:
                donate = (6,) if self._donate else ()
                self._decode_exec = jax.jit(
                    self._build_decode_fn(),
                    donate_argnums=donate).lower(
                        params_spec, vec, vec, vec, vec, pt,
                        pool_specs).compile()
            n_exec += 1
        if self.role != "decode":
            for t in cfg.prefill_buckets:
                tok = jax.ShapeDtypeStruct((cfg.num_slots, t), i32)
                last = jax.ShapeDtypeStruct((cfg.num_slots, 1), i32)
                donate_p = (5,) if self._donate else ()
                self._prefill_execs[t] = jax.jit(
                    self._build_prefill_fn(t),
                    donate_argnums=donate_p).lower(
                        params_spec, tok, vec, last, pt,
                        pool_specs).compile()
            n_exec += len(cfg.prefill_buckets)
        row = jax.ShapeDtypeStruct((cfg.max_pages_per_slot,), i32)
        if self.role == "prefill":
            # page-export gather: pools NOT donated — the worker keeps
            # serving from them after every export
            self._export_exec = jax.jit(
                self._build_export_fn()).lower(row, pool_specs).compile()
            n_exec += 1
        if self.role == "decode":
            t_cap = cfg.max_pages_per_slot * cfg.page_size
            rows_spec = {
                n: jax.ShapeDtypeStruct((t_cap, spec.shape[2]),
                                        spec.dtype)
                for n, spec in pool_specs.items()}
            nv = jax.ShapeDtypeStruct((), i32)
            donate_i = (3,) if self._donate else ()
            self._import_exec = jax.jit(
                self._build_import_fn(),
                donate_argnums=donate_i).lower(
                    rows_spec, row, nv, pool_specs).compile()
            n_exec += 1
        if self.drafter is not None:
            # drafter compiles land INSIDE the warmup window, so the
            # zero-post-warmup-compile contract covers drafting too
            self.drafter.start(self)
            if self._event_log is not None:
                self._event_log.event(
                    "serving_decode_speculate",
                    speculate_k=self.speculate_k,
                    drafter=type(self.drafter).__name__)
        delta = runtime_stats.delta(snap)
        self.stats.record_warmup(n_exec,
                                 delta["compiles"],
                                 delta["compile_time_s"],
                                 time.perf_counter() - t0)
        self.admission.start()
        self._worker = threading.Thread(target=self._loop,
                                        name="decode-scheduler",
                                        daemon=True)
        self._worker.start()
        return self

    def _validate_memory_budget(self):
        """Predict the decode step's peak HBM at the CONFIGURED pool
        size from two small-pool probe compiles (observe.memory
        plan_fit: peak is affine in the pool page count) and reject an
        impossible geometry BEFORE the full-size warmup."""
        budget = self.memory_budget_bytes
        if budget is False:
            return
        if budget is None or budget is True:
            from ..observe.memory import device_memory_budget

            budget = device_memory_budget()
        if not budget:
            self.fit_plan = {"skipped": "no device budget known",
                             "budget_bytes": None}
            return
        cfg = self.config
        if cfg.num_pages == cfg.num_slots:
            # plan_fit scales EVERY leading dim equal to `batch`; a
            # pool exactly the slot count would scale the slot feeds
            # with it and corrupt the fit
            self.fit_plan = {"skipped": "num_pages == num_slots "
                                        "(ambiguous probe axis)",
                            "budget_bytes": int(budget)}
            return
        import jax

        from ..core.executor import Executor, scope_guard
        from ..observe.memory import plan_fit

        st = self.model.step
        params_spec, vec, pt, pool_specs = self._specs()
        feed = dict(pool_specs)
        i32 = jax.numpy.int32
        feed.update(tokens=vec, write_pos=vec, lengths=vec,
                    active=vec, page_table=pt)
        try:
            with scope_guard(self.scope):
                plan = plan_fit(
                    st["main"], feed,
                    fetch_list=[st["next_token"]] + st["cache_outs"],
                    scope=self.scope, batch=cfg.num_pages,
                    budget_bytes=int(budget))
        except RuntimeError as e:
            self.fit_plan = {"skipped": str(e),
                             "budget_bytes": int(budget)}
            return
        self.fit_plan = plan
        if self._event_log is not None:
            self._event_log.event("serving_decode_memory_plan", **plan)
        if plan["fits"] is False:
            raise DecodeMemoryError(
                f"decode geometry predicted to exceed the device "
                f"memory budget: peak "
                f"{plan['predicted_peak_bytes'] / 1e9:.2f} GB vs "
                f"budget {budget / 1e9:.2f} GB (num_pages="
                f"{cfg.num_pages}, page_size={cfg.page_size}, "
                f"num_slots={cfg.num_slots})",
                plan=plan, budget_bytes=int(budget))

    def drain(self, timeout_s: float = 120.0) -> bool:
        """Stop admission, let every accepted request finish decoding.
        Idempotent."""
        self.admission.begin_drain()
        end = time.monotonic() + timeout_s
        with self._cv:
            self._cv.notify_all()
            while self._unresolved > 0:
                remaining = end - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(min(remaining, 0.05))
        if self._event_log is not None:
            self.stats.emit("serving_decode_drain", drained=True)
        return True

    def close(self, timeout_s: float = 120.0):
        if self.admission.state == "running":
            self.drain(timeout_s)
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._worker is not None:
            self._worker.join(timeout_s)
        # shutdown never strands a future: anything a timed-out drain
        # left behind resolves with the RETRYABLE structured error
        # (requeue descriptor attached) so a router can still finish
        # the request on another replica
        self._pull_all("shutdown")
        self.admission.finish_drain()
        if self._own_log is not None:
            self._own_log.close()

    def __enter__(self) -> "DecodeEngine":
        return self.start() if not self._started else self

    def __exit__(self, *exc):
        self.close()
        return False

    def health(self) -> Dict[str, Any]:
        return self.admission.health(
            active_slots=sum(s is not None for s in self._slots),
            num_slots=self.config.num_slots,
            queue_depth=len(self._queue),
            pages_in_use=self.page_pool.in_use,
            num_pages=self.config.num_pages,
            completed=self.stats.completed,
            replica_id=self.replica_id,
            role=self.role,
            model_version=self.model_version,
            post_warmup_compiles=self.stats.post_warmup_compiles())

    # -- fleet surface: evacuation + hot weight reload ------------------
    def evacuate(self, timeout_s: float = 30.0) -> List[Dict[str, Any]]:
        """Pull every accepted-but-unresolved request off this replica
        and return their requeue descriptors.  Each future resolves
        with the structured, retryable DecodeReplicaFailedError (the
        same wire form `_fail_everything` uses), so a router that
        chained them fails the requests over; the returned descriptors
        are the same data for routers that track requests themselves.
        Runs on the scheduler thread at a batch boundary (inline when
        the scheduler is not running); the engine keeps serving — new
        submits after the evacuation are admitted normally."""
        with self._cv:
            alive = (self._worker is not None and self._worker.is_alive()
                     and not self._stop)
            if alive:
                waiter = {"ev": threading.Event(), "result": None}
                self._evac_waiters.append(waiter)
                self._cv.notify_all()
        if not alive:
            return self._pull_all("evacuated")
        if not waiter["ev"].wait(timeout_s):
            raise WeightReloadError(
                f"evacuation not serviced within {timeout_s:.0f}s "
                f"(scheduler wedged?)", replica_id=self.replica_id,
                timeout_s=timeout_s)
        return waiter["result"]

    def reload(self, source, version: Optional[int] = None,
               timeout_s: float = 60.0) -> Dict[str, Any]:
        """Hot weight reload: materialize `source` (a sharded-
        checkpoint dir via io.load_sharded, or a name→array mapping),
        assert every array matches the live parameter's shape and dtype
        — the same-shape swap is what guarantees the jitted executables
        are reused with ZERO recompiles — and swap at the scheduler's
        next batch boundary.  Refuses while generations are in flight
        (evacuate() first; the fleet roll does).  Returns {"version",
        "pause_ms"}; raises the structured WeightReloadError on any
        violation, leaving the old weights serving."""
        t0 = time.perf_counter()
        params = self._materialize_params(source)
        self._check_reload_shapes(params)
        new_version = (self.model_version + 1 if version is None
                       else int(version))
        with self._cv:
            alive = (self._worker is not None and self._worker.is_alive()
                     and not self._stop)
            if alive:
                if self._pending_reload is not None:
                    raise WeightReloadError(
                        "another reload is already pending",
                        replica_id=self.replica_id)
                pend = {"params": params, "version": new_version,
                        "ev": threading.Event(), "error": None}
                self._pending_reload = pend
                self._cv.notify_all()
        if not alive:
            active = sum(s is not None for s in self._slots)
            if active:
                raise WeightReloadError(
                    f"{active} generation(s) still in flight; "
                    f"evacuate() first", replica_id=self.replica_id)
            self._params = params
            self.model_version = new_version
        else:
            if not pend["ev"].wait(timeout_s):
                raise WeightReloadError(
                    f"reload not applied within {timeout_s:.0f}s "
                    f"(scheduler wedged?)", replica_id=self.replica_id,
                    timeout_s=timeout_s)
            if pend["error"]:
                raise WeightReloadError(
                    f"reload refused: {pend['error']}",
                    replica_id=self.replica_id)
        pause_ms = (time.perf_counter() - t0) * 1e3
        self.stats.record_reload(pause_ms)
        if self._event_log is not None:
            self._event_log.event(
                "serving_decode_reload", version=new_version,
                pause_ms=round(pause_ms, 3),
                source=source if isinstance(source, str) else "arrays")
        return {"version": new_version, "pause_ms": round(pause_ms, 3)}

    def _materialize_params(self, source) -> Dict[str, Any]:
        """Device-resident name→array dict from a sharded checkpoint
        dir (io.load_sharded into this engine's scope) or a mapping."""
        import jax
        import jax.numpy as jnp

        from ..core.executor import RNG_STATE_VAR

        if isinstance(source, str):
            from .. import io as fluid_io
            from ..core.executor import Executor, scope_guard

            with scope_guard(self.scope):
                fluid_io.load_sharded(Executor(), source,
                                      main_program=self.model.step["main"])
            src = {n: v for n, v in self.scope.vars.items()
                   if v is not None and n != RNG_STATE_VAR}
        else:
            src = dict(source)
        return {n: jax.device_put(jnp.asarray(v))
                for n, v in src.items() if n in self._params}

    def _check_reload_shapes(self, params: Dict[str, Any]):
        missing = sorted(set(self._params) - set(params))
        if missing:
            raise WeightReloadError(
                f"reload source missing {len(missing)} parameter(s): "
                f"{missing[:4]}{' ...' if len(missing) > 4 else ''}",
                replica_id=self.replica_id, missing=missing)
        mismatched = [
            {"name": n, "live": [list(self._params[n].shape),
                                 str(self._params[n].dtype)],
             "new": [list(params[n].shape), str(params[n].dtype)]}
            for n in self._params
            if (tuple(params[n].shape) != tuple(self._params[n].shape)
                or params[n].dtype != self._params[n].dtype)]
        if mismatched:
            raise WeightReloadError(
                f"{len(mismatched)} parameter(s) change shape/dtype — "
                f"a same-shape swap is the zero-recompile contract; "
                f"first: {mismatched[0]}",
                replica_id=self.replica_id, mismatched=mismatched)

    # -- request path ---------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32,
               priority: int = 0,
               deadline_ms: Optional[float] = None,
               _trace=None) -> Future:
        """Accept one generation request; returns a Future of the
        generated token ids (np.int32, includes the eos token when one
        stopped it).  Raises DecodeBucketMissError / QueueFullError /
        CircuitOpenError / ServingClosedError synchronously.
        `_trace`: a fleet router's RequestTrace to continue."""
        if self.role == "decode":
            raise ValueError(
                "role='decode' engine admits requests only through "
                "import_handoff() — prompts prefill on a prefill "
                "worker (serving/disagg.py)")
        trace = _trace
        if trace is None and self.tracer is not None:
            trace = self.tracer.new_trace("decode")
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size < 1:
            raise DecodeBucketMissError(
                "prompt must be a non-empty 1-D token array",
                got_shape=list(prompt.shape))
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        cfg = self.config
        plen = int(prompt.size)
        if BucketConfig.pick(cfg.prefill_buckets, plen) is None:
            self.stats.record_bucket_miss()
            raise DecodeBucketMissError(
                f"prompt length {plen} exceeds the largest prefill "
                f"bucket {cfg.prefill_buckets[-1]}",
                prompt_len=plen,
                prefill_buckets=list(cfg.prefill_buckets))
        if plen + max_new_tokens > cfg.max_len:
            self.stats.record_bucket_miss()
            raise DecodeBucketMissError(
                f"prompt {plen} + max_new_tokens {max_new_tokens} "
                f"exceeds the per-slot budget max_len {cfg.max_len}",
                prompt_len=plen, max_new_tokens=int(max_new_tokens),
                max_len=cfg.max_len)
        deadline = self.admission.deadline_for(deadline_ms)
        req = DecodeRequest(prompt.astype(np.int32), max_new_tokens,
                            priority=priority, deadline=deadline,
                            trace=trace)
        try:
            with self._cv:
                self.admission.check(self._unresolved)
                self._queue.append(req)
                self._unresolved += 1
                self._cv.notify_all()
        except ServingError as e:
            if e.kind == "queue_full":
                self.stats.record_shed()
            elif e.kind == "circuit_open":
                self.stats.record_circuit_reject()
            if trace is not None and not trace.fleet_owned \
                    and self.tracer is not None:
                trace.point("rejected", reject=e.kind,
                            replica_id=self.replica_id)
                self.tracer.finish(trace, error=e)
            raise
        self.stats.record_submit()
        return req.future

    def generate(self, prompt, max_new_tokens: int = 32,
                 timeout_s: Optional[float] = None,
                 **kw) -> np.ndarray:
        """Synchronous submit()+result() convenience."""
        return self.submit(prompt, max_new_tokens, **kw).result(
            timeout_s)

    def import_handoff(self, handoff: Dict[str, Any],
                       deadline_ms: Optional[float] = None,
                       _trace=None) -> Future:
        """role="decode" entry: accept a prefill worker's KV handoff
        package (the export of `_export_handoffs`) and continue the
        generation from its first token.  The imported slot is seeded
        to EXACTLY the post-prefill state of the unified engine
        (committed prompt KV, pending first token, remaining budget),
        so greedy decode continues bit-identically — the token-parity
        proof holds across the hop.  Returns a Future of the FULL
        generated ids (first token included)."""
        if self.role != "decode":
            raise ValueError(
                "import_handoff() requires role='decode' "
                f"(this engine is role={self.role!r})")
        trace = _trace
        if trace is None and self.tracer is not None:
            trace = self.tracer.new_trace("decode")
        prompt = np.asarray(handoff["prompt"], np.int32)
        committed = int(handoff["committed"])
        max_new = int(handoff["max_new_tokens"])
        cfg = self.config
        if prompt.ndim != 1 or prompt.size < 1 \
                or committed != prompt.size:
            raise ValueError(
                f"handoff package inconsistent: committed {committed} "
                f"vs prompt length {prompt.size}")
        if handoff.get("rows") is None:
            raise ValueError("handoff package carries no KV rows "
                             "(done=True packages resolve at the "
                             "router, not on a decode worker)")
        if committed + max_new > cfg.max_len:
            self.stats.record_bucket_miss()
            raise DecodeBucketMissError(
                f"handoff prompt {committed} + max_new_tokens "
                f"{max_new} exceeds the per-slot budget max_len "
                f"{cfg.max_len}", prompt_len=committed,
                max_new_tokens=max_new, max_len=cfg.max_len)
        deadline = self.admission.deadline_for(deadline_ms)
        req = DecodeRequest(prompt, max_new,
                            priority=int(handoff.get("priority", 0)),
                            deadline=deadline, trace=trace)
        req.handoff = handoff
        try:
            with self._cv:
                self.admission.check(self._unresolved)
                self._queue.append(req)
                self._unresolved += 1
                self._cv.notify_all()
        except ServingError as e:
            if e.kind == "queue_full":
                self.stats.record_shed()
            elif e.kind == "circuit_open":
                self.stats.record_circuit_reject()
            if trace is not None and not trace.fleet_owned \
                    and self.tracer is not None:
                trace.point("rejected", reject=e.kind,
                            replica_id=self.replica_id)
                self.tracer.finish(trace, error=e)
            raise
        self.stats.record_submit()
        return req.future

    # -- scheduler ------------------------------------------------------
    def _loop(self):
        from ..resilience import chaos

        while True:
            with self._cv:
                while (not self._stop and not self._queue
                       and not any(self._slots)
                       and not self._evac_waiters
                       and self._pending_reload is None):
                    self._cv.wait(0.05)
                if self._stop:
                    return
            try:
                self._service_control()
                if self.replica_id is not None:
                    # fleet chaos points (resilience.chaos.kill_replica
                    # / delay_replica): a kill raises here and drives
                    # the REAL abrupt-death path below — exactly what
                    # an executor crash mid-dispatch does; a delay
                    # models a straggling replica for hedge proofs
                    chaos.delaypoint(f"replica:{self.replica_id}:delay")
                    chaos.failpoint(f"replica:{self.replica_id}:kill")
                self._admit()
                self._decode()
            except BaseException as e:  # noqa: BLE001 — the scheduler
                #                         thread must never die silently
                self._fail_everything(e)
                return
            self.stats.maybe_emit()

    def _service_control(self):
        """Evacuations and weight swaps land HERE, on the scheduler
        thread BETWEEN dispatches — the drain-to-batch-boundary
        contract: a control action never interleaves with a dispatch,
        and a swap never touches a live generation (the reload refuses
        unless the slots are empty; the fleet roll evacuates first)."""
        with self._cv:
            evac = self._evac_waiters
            self._evac_waiters = []
            pend = self._pending_reload
            self._pending_reload = None
        if evac:
            descs = self._pull_all("evacuated")
            for w in evac:
                w["result"] = descs
                w["ev"].set()
        if pend is not None:
            active = sum(s is not None for s in self._slots)
            if active:
                pend["error"] = (f"{active} generation(s) still in "
                                 f"flight; evacuate() first")
            else:
                self._params = pend["params"]
                self.model_version = pend["version"]
            pend["ev"].set()

    def _pull_all(self, reason: str, cause: Optional[str] = None
                  ) -> List[Dict[str, Any]]:
        """Remove EVERY accepted-but-unresolved request (active slots +
        queue), resolve each future with the structured, retryable
        DecodeReplicaFailedError carrying its requeue descriptor, free
        the pages, and return the descriptors.  Only safe on the
        scheduler thread or once the scheduler is stopped/dead (the
        slot table is scheduler-owned)."""
        victims: List[tuple] = []
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            self._slots[i] = None
            self.page_pool.free(slot.pages)
            self._page_tables[i, :] = 0
            victims.append((slot.req, slot.generated))
        with self._cv:
            victims += [(r, []) for r in self._queue]
            self._queue = []
            self._unresolved -= len(victims)
            self._cv.notify_all()
        descs: List[Dict[str, Any]] = []
        if not victims:
            return descs
        self.stats.record_evacuation(len(victims))
        if self._event_log is not None:
            self._event_log.event(
                "serving_decode_evacuate", reason=reason, cause=cause,
                requests=len(victims),
                pages_free_after=self.page_pool.free_pages)
        for req, gen in victims:
            d = req.descriptor(gen)
            descs.append(d)
            err = DecodeReplicaFailedError(
                f"request pulled off replica "
                f"{self.replica_id if self.replica_id is not None else '?'}"
                f" ({reason}) after {len(gen)} committed token(s); "
                f"requeue the descriptor on a surviving replica",
                reason=reason, cause=cause,
                replica_id=self.replica_id, descriptor=d)
            if req.trace is not None:
                # the failover hop itself is the ROUTER's span; the
                # replica marks why the request left it
                req.trace.point("evacuated", reason=reason,
                                replica_id=self.replica_id,
                                committed=len(gen))
                if not req.trace.fleet_owned and self.tracer is not None:
                    self.tracer.finish(req.trace, error=err)
            if not req.future.done():
                req.future.set_exception(err)
        return descs

    def _fail_everything(self, exc: BaseException):
        """The scheduler died: stop accepting, then resolve every
        accepted request with the structured retryable error (requeue
        descriptors attached) instead of a bare exception — the
        router-facing half of the failover contract."""
        cause = f"{type(exc).__name__}: {exc}"
        # a dead scheduler must not keep ACCEPTING: later submits get
        # ServingClosedError instead of queueing forever
        try:
            self.admission.begin_drain()
        except ServingError:
            pass
        self._pull_all("scheduler_failed", cause=cause)
        # control waiters must not hang on a dead scheduler either
        with self._cv:
            evac = self._evac_waiters
            self._evac_waiters = []
            pend = self._pending_reload
            self._pending_reload = None
        for w in evac:
            w["result"] = []
            w["ev"].set()
        if pend is not None:
            pend["error"] = f"scheduler died: {cause}"
            pend["ev"].set()

    def _resolve(self, slot_id: int, error: Optional[BaseException]
                 = None, value=None):
        slot = self._slots[slot_id]
        self._slots[slot_id] = None
        self.page_pool.free(slot.pages)
        self._page_tables[slot_id, :] = 0
        with self._cv:
            self._unresolved -= 1
            self._cv.notify_all()
        tr = slot.req.trace
        own_trace = (tr is not None and not tr.fleet_owned
                     and self.tracer is not None)
        if error is not None:
            if not slot.req.future.done():
                slot.req.future.set_exception(error)
            if own_trace:
                self.tracer.finish(tr, error=error)
            return
        if not slot.req.future.done():
            # which weights produced this generation (a router's
            # response tag for the hot-reload roll)
            slot.req.future.model_version = slot.version
            # `value` overrides the token array for role="prefill":
            # the future resolves with the KV handoff package instead
            slot.req.future.set_result(
                value if value is not None
                else np.asarray(slot.generated, np.int32))
        self.stats.record_done()
        if own_trace:
            self.tracer.finish(tr)

    def _requeue(self, slot_id: int):
        """Preempt: pages returned, request re-enters the queue head
        and will regenerate from the prompt (greedy => identical
        tokens)."""
        slot = self._slots[slot_id]
        self._slots[slot_id] = None
        self.page_pool.free(slot.pages)
        self._page_tables[slot_id, :] = 0
        slot.req.preempted += 1
        if slot.req.trace is not None:
            slot.req.trace.point(
                "preempt", slot=slot_id, replica_id=self.replica_id,
                committed=slot.committed, generated=len(slot.generated))
        with self._cv:
            self._queue.insert(0, slot.req)
        self.stats.record_preemption()
        if self._event_log is not None:
            self._event_log.event(
                "serving_decode_preempt", slot=slot_id,
                priority=slot.req.priority,
                committed=slot.committed,
                generated=len(slot.generated),
                pages_freed=len(slot.pages),
                pages_free_after=self.page_pool.free_pages)

    def _set_pages(self, slot_id: int, pages: List[int]):
        self._page_tables[slot_id, :] = 0
        self._page_tables[slot_id, :len(pages)] = pages

    def _admit(self):
        """Fill open slots from the queue (prefill-on-join): pick
        joiners, allocate prompt pages, run ONE bucket-padded prefill
        dispatch over the whole slot batch (non-joiners masked out by
        seq_len 0)."""
        cfg = self.config
        now = time.monotonic()
        joiners: List[int] = []
        while True:
            free_ids = [i for i, s in enumerate(self._slots)
                        if s is None]
            if not free_ids:
                break
            req = None
            with self._cv:
                # priority first, then FIFO; expired requests drop
                # before any device time is spent on them
                self._queue.sort(key=lambda r: (-r.priority,
                                                r.t_submit))
                while self._queue:
                    cand = self._queue[0]
                    if cand.deadline is not None \
                            and now > cand.deadline:
                        self._queue.pop(0)
                        self._unresolved -= 1
                        self.stats.record_deadline_miss()
                        exc = DeadlineExceededError(
                            "deadline expired before a slot opened",
                            queued_ms=round(
                                (now - cand.t_submit) * 1e3, 3))
                        if cand.trace is not None:
                            cand.trace.add(
                                "join_wait", cand.t_submit, now,
                                replica_id=self.replica_id,
                                expired=True)
                            if not cand.trace.fleet_owned \
                                    and self.tracer is not None:
                                self.tracer.finish(cand.trace,
                                                   error=exc)
                        cand.future.set_exception(exc)
                        continue
                    req = cand
                    break
                if req is not None:
                    need = _cdiv(len(req.prompt), cfg.page_size)
                    pages = self.page_pool.alloc(need)
                    if pages is None:
                        req = None  # pool dry: decode frees pages,
                        #             not admission
                    else:
                        self._queue.pop(0)
            if req is None:
                break
            slot_id = free_ids[0]
            self._slots[slot_id] = _Slot(req, pages,
                                         version=self.model_version)
            self._set_pages(slot_id, pages)
            joiners.append(slot_id)
        if not joiners:
            return
        # disagg: handoff joiners import their prefilled KV pages (one
        # fixed-shape scatter each) instead of prefilling
        imports = [i for i in joiners
                   if self._slots[i].req.handoff is not None]
        prefills = [i for i in joiners
                    if self._slots[i].req.handoff is None]
        for i in imports:
            self._dispatch_import(i)
        if prefills:
            self._dispatch_prefill(prefills)

    def _dispatch_import(self, slot_id: int):
        """Scatter one handoff's exported KV rows into this worker's
        pool at the receiving slot's pages, then seed the slot to the
        unified engine's post-prefill state (pending first token) so
        the next decode chunk continues bit-identically."""
        import jax.numpy as jnp

        cfg = self.config
        slot = self._slots[slot_id]
        h = slot.req.handoff
        t_i0 = time.monotonic()
        tr = slot.req.trace
        if tr is not None:
            tr.add("join_wait", slot.req.t_submit, t_i0,
                   replica_id=self.replica_id, slot=slot_id)
        try:
            rows = {n: jnp.asarray(h["rows"][n]) for n in self._pools}
            pools = self._import_exec(
                rows, jnp.asarray(self._page_tables[slot_id]),
                jnp.asarray(np.int32(h["committed"])), self._pools)
        except BaseException as e:
            self.stats.record_executor_failure()
            self._breaker_result(False, 1)
            err = ExecutorFailureError(
                f"KV-page import dispatch failed: "
                f"{type(e).__name__}: {e}",
                error_type=type(e).__name__, joins=1)
            t_i1 = time.monotonic()
            if tr is not None:
                tr.add("dispatch", t_i0, t_i1, kind="import",
                       replica_id=self.replica_id, slot=slot_id,
                       error=type(e).__name__)
            self._resolve(slot_id, error=err)
            return
        t_i1 = time.monotonic()
        if tr is not None:
            tr.add("dispatch", t_i0, t_i1, kind="import",
                   replica_id=self.replica_id, slot=slot_id,
                   pages=len(slot.pages))
        self._breaker_result(True, 1)
        self._pools = pools
        slot.committed = int(h["committed"])
        slot.cur_tok = int(h["first_token"])
        slot.generated = [int(t) for t in h["generated"]]
        slot.remaining = slot.req.max_new_tokens - len(slot.generated)
        self.stats.record_import()
        if self.drafter is not None:
            # no draft-model KV crossed the wire: re-seed the draft
            # pool from the raw prompt (serving/speculate.py)
            self.drafter.on_import(self, slot_id)
        if slot.remaining <= 0 or (cfg.eos_id is not None
                                   and slot.cur_tok == cfg.eos_id):
            self._resolve(slot_id)

    def _dispatch_prefill(self, joiners: List[int]):
        import jax.numpy as jnp

        cfg = self.config
        bucket = BucketConfig.pick(
            cfg.prefill_buckets,
            max(len(self._slots[i].req.prompt) for i in joiners))
        tokens = np.zeros((cfg.num_slots, bucket), np.int32)
        seq_len = np.zeros((cfg.num_slots,), np.int32)
        last_idx = np.zeros((cfg.num_slots, 1), np.int32)
        for i in joiners:
            p = self._slots[i].req.prompt
            tokens[i, :len(p)] = p
            seq_len[i] = len(p)
            last_idx[i, 0] = len(p) - 1
        exec_ = self._prefill_execs[bucket]
        t_p0 = time.monotonic()  # join_wait ends / prefill begins
        for i in joiners:
            tr = self._slots[i].req.trace
            if tr is not None:
                tr.add("join_wait", self._slots[i].req.t_submit, t_p0,
                       replica_id=self.replica_id, slot=i)
        try:
            nxt, pools = exec_(self._params, jnp.asarray(tokens),
                               jnp.asarray(seq_len),
                               jnp.asarray(last_idx),
                               jnp.asarray(self._page_tables),
                               self._pools)
        except BaseException as e:
            self.stats.record_executor_failure()
            self._breaker_result(False, len(joiners))
            err = ExecutorFailureError(
                f"prefill dispatch failed for {len(joiners)} join(s): "
                f"{type(e).__name__}: {e}",
                error_type=type(e).__name__, joins=len(joiners))
            t_p1 = time.monotonic()
            for i in joiners:
                tr = self._slots[i].req.trace
                if tr is not None:
                    tr.add("dispatch", t_p0, t_p1, kind="prefill",
                           replica_id=self.replica_id, slot=i,
                           error=type(e).__name__)
            for i in joiners:
                self._resolve(i, error=err)
            return
        t_p1 = time.monotonic()
        for i in joiners:
            tr = self._slots[i].req.trace
            if tr is not None:
                tr.add("dispatch", t_p0, t_p1, kind="prefill",
                       bucket=bucket, replica_id=self.replica_id,
                       slot=i)
        self._breaker_result(True, len(joiners))
        self._pools = pools
        nxt = np.asarray(nxt)
        now = time.monotonic()
        ttfts = []
        for i in joiners:
            slot = self._slots[i]
            tok = int(nxt[i])
            slot.cur_tok = tok
            slot.generated.append(tok)
            slot.remaining = slot.req.max_new_tokens - 1
            ttfts.append((now - slot.req.t_submit) * 1e3)
        self.stats.record_prefill(len(joiners), ttfts)
        if self.drafter is not None:
            # mirror the join into the draft pool (same buffers, same
            # page tables — the pools share geometry by construction)
            self.drafter.on_prefill(self, joiners, tokens, seq_len,
                                    last_idx)
        if self.role == "prefill":
            # disagg: every joiner resolves NOW with its KV handoff
            # package — the slot and pages recycle immediately, so the
            # prefill worker's TTFT is decoupled from any decode
            # occupancy (the whole point of the split)
            self._export_handoffs(joiners)
            return
        # a request satisfied by its very first token resolves here
        for i in joiners:
            slot = self._slots[i]
            if slot.remaining <= 0 or (cfg.eos_id is not None
                                       and slot.cur_tok == cfg.eos_id):
                self._resolve(i)

    def _export_handoffs(self, joiners: List[int]):
        """role="prefill": gather each joiner's pool pages to host rows
        and resolve its future with the handoff wire package (PR 14
        descriptor fields + the KV rows; docs/SERVING.md §disagg).
        Rows copy VERBATIM in pool dtype — int8 codes and their scale
        sidecars transfer without requantization, so the hop is
        bitwise."""
        import jax.numpy as jnp

        cfg = self.config
        for i in joiners:
            slot = self._slots[i]
            done = slot.remaining <= 0 or (
                cfg.eos_id is not None and slot.cur_tok == cfg.eos_id)
            t_e0 = time.monotonic()
            rows = None
            nbytes = 0
            if not done:
                exported = self._export_exec(
                    jnp.asarray(self._page_tables[i]), self._pools)
                rows = {n: np.asarray(v) for n, v in exported.items()}
                # valid rows only — padding rows never cross the wire
                # in accounting (they do travel in the fixed buffers)
                nbytes = sum(slot.committed * v.shape[1]
                             * v.dtype.itemsize for v in rows.values())
            t_e1 = time.monotonic()
            tr = slot.req.trace
            if tr is not None and not done:
                tr.add("export", t_e0, t_e1,
                       replica_id=self.replica_id, slot=i,
                       pages=len(slot.pages), bytes=nbytes)
            package = {
                "kind": "handoff",
                "prompt": [int(t) for t in slot.req.prompt],
                "first_token": int(slot.cur_tok),
                "generated": [int(t) for t in slot.generated],
                "committed": int(slot.committed),
                "max_new_tokens": slot.req.max_new_tokens,
                "priority": slot.req.priority,
                "done": bool(done),
                "n_pages": len(slot.pages),
                "rows": rows,
                "bytes": int(nbytes),
                "export_ms": round((t_e1 - t_e0) * 1e3, 3),
                "from_replica": self.replica_id,
                "model_version": slot.version,
            }
            self._resolve(i, value=package)

    def _breaker_result(self, ok: bool, n: int):
        res = self.admission.record_dispatch_result(ok)
        if res and self._event_log is not None:
            self._event_log.event(
                f"serving_breaker_{'open' if res == 'opened' else 'close'}",
                state=self.admission.state, component="decode_engine",
                breaker=self.admission.breaker.snapshot(),
                batch=n)

    def _ensure_decode_pages(self) -> List[int]:
        """Extend every active slot's pages to cover the next chunk,
        preempting the least-important slots when the pool runs dry.
        Returns the slot ids still active afterwards."""
        cfg = self.config
        # speculative rounds commit at most k+1 tokens per dispatch
        # (positions committed..committed+k), the chunk loop at most
        # decode_chunk — the page window follows whichever path runs
        window = (self.speculate_k + 1) if self.speculate_k \
            else cfg.decode_chunk
        order = sorted(
            (i for i, s in enumerate(self._slots) if s is not None),
            key=lambda i: self._slots[i].importance(), reverse=True)
        for i in order:
            slot = self._slots[i]
            if slot is None:
                continue  # preempted as a victim earlier in the loop
            target = _cdiv(min(slot.committed + window,
                               slot.cap_tokens), cfg.page_size)
            while slot is not None and target > len(slot.pages):
                got = self.page_pool.alloc(target - len(slot.pages))
                if got is not None:
                    slot.pages.extend(got)
                    self._set_pages(i, slot.pages)
                    break
                # pool dry: evict the least-important active slot
                # (possibly this one)
                victims = [j for j, sj in enumerate(self._slots)
                           if sj is not None]
                victim = min(victims,
                             key=lambda j: self._slots[j].importance())
                self._requeue(victim)
                slot = self._slots[i]
        return [i for i, s in enumerate(self._slots) if s is not None]

    def _decode(self):
        import jax.numpy as jnp

        if self._verify_exec is not None:
            self._decode_speculative()
            return
        if self._decode_exec is None:
            return  # role="prefill": every slot resolved at export
        cfg = self.config
        active_ids = self._ensure_decode_pages()
        if not active_ids:
            return
        s = cfg.num_slots
        tokens = np.zeros((s,), np.int32)
        write_pos = np.zeros((s,), np.int32)
        active = np.zeros((s,), np.int32)
        remaining = np.zeros((s,), np.int32)
        for i in active_ids:
            slot = self._slots[i]
            tokens[i] = slot.cur_tok
            write_pos[i] = slot.committed
            active[i] = 1
            remaining[i] = slot.remaining
        t0 = time.perf_counter()
        t_d0 = time.monotonic()
        try:
            (outbuf, steps, new_tok, new_wp, new_act, new_rem,
             pools) = self._decode_exec(
                self._params, jnp.asarray(tokens),
                jnp.asarray(write_pos), jnp.asarray(active),
                jnp.asarray(remaining),
                jnp.asarray(self._page_tables), self._pools)
        except BaseException as e:
            self.stats.record_executor_failure()
            self._breaker_result(False, len(active_ids))
            err = ExecutorFailureError(
                f"decode dispatch failed for {len(active_ids)} "
                f"slot(s): {type(e).__name__}: {e}",
                error_type=type(e).__name__, slots=len(active_ids))
            t_d1 = time.monotonic()
            for i in active_ids:
                tr = self._slots[i].req.trace
                if tr is not None:
                    tr.add("dispatch", t_d0, t_d1, kind="decode",
                           replica_id=self.replica_id, slot=i,
                           error=type(e).__name__)
            for i in active_ids:
                self._resolve(i, error=err)
            return
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        t_d1 = time.monotonic()
        for i in active_ids:
            tr = self._slots[i].req.trace
            if tr is not None:
                tr.add("dispatch", t_d0, t_d1, kind="decode",
                       iterations=int(steps),
                       replica_id=self.replica_id, slot=i)
        self._breaker_result(True, len(active_ids))
        self._pools = pools
        outbuf = np.asarray(outbuf)
        steps = int(steps)
        new_wp = np.asarray(new_wp)
        new_act = np.asarray(new_act)
        new_rem = np.asarray(new_rem)
        new_tok = np.asarray(new_tok)
        total_tokens = 0
        for i in active_ids:
            slot = self._slots[i]
            produced = int(new_wp[i]) - slot.committed
            toks = [int(t) for t in outbuf[i, :produced] if t >= 0]
            slot.generated.extend(toks)
            total_tokens += len(toks)
            slot.committed = int(new_wp[i])
            slot.cur_tok = int(new_tok[i])
            slot.remaining = int(new_rem[i])
        self.stats.record_decode(
            steps, len(active_ids), cfg.num_slots, total_tokens,
            self.page_pool.in_use, cfg.num_pages, elapsed_ms)
        for i in active_ids:
            if int(new_act[i]) == 0:
                self._resolve(i)

    def _decode_speculative(self):
        """One verify round: draft on the host, score all drafts in
        ONE folded dispatch, commit the accepted prefix (+1 model
        token) per slot.  Token-identical to `_decode`'s sequential
        chunk by the greedy-acceptance argument in
        ops/paged_kv.py `speculative_accept`; rollback of a rejected
        tail is simply not advancing `committed` — the stale rows sit
        past every length and are overwritten before any attention
        reads them."""
        import jax.numpy as jnp

        cfg = self.config
        k = self.speculate_k
        k1 = k + 1
        active_ids = self._ensure_decode_pages()
        if not active_ids:
            return
        s = cfg.num_slots
        proposals, prop_len = self.drafter.draft(self, active_ids)
        folded = np.zeros((4, s * k1), np.int32)
        tokens, write_pos, lengths, active = folded
        slot_meta = np.zeros((2, s), np.int32)
        draft_len, slot_active = slot_meta
        drafts = np.zeros((s, k), np.int32)
        pt = np.zeros((s * k1, cfg.max_pages_per_slot), np.int32)
        ar = np.arange(k1)
        for i in active_ids:
            slot = self._slots[i]
            # cap so emitted (accepted+1) never exceeds the remaining
            # budget and the last write position stays under
            # cap_tokens (committed + remaining == cap_tokens)
            m = int(min(int(prop_len[i]), k, slot.remaining - 1))
            draft_len[i] = m
            drafts[i, :m] = proposals[i, :m]
            slot_active[i] = 1
            base = i * k1
            live = ar <= m          # row 0 always live (m >= 0)
            # dead rows pin to the slot's current position: their
            # writes drop (active 0) and their predictions are
            # discarded, but their feeds stay in-range
            off = np.where(live, ar, 0)
            tokens[base] = slot.cur_tok
            tokens[base + 1:base + k1] = drafts[i]
            write_pos[base:base + k1] = slot.committed + off
            lengths[base:base + k1] = slot.committed + off + 1
            active[base:base + k1] = live
            pt[base:base + k1] = self._page_tables[i]
        drafted_total = int(draft_len.sum())
        t0 = time.perf_counter()
        t_d0 = time.monotonic()
        try:
            accepted, emitted, pools = self._verify_exec(
                self._params, jnp.asarray(folded),
                jnp.asarray(drafts), jnp.asarray(slot_meta),
                jnp.asarray(pt), self._pools)
        except BaseException as e:
            self.stats.record_executor_failure()
            self._breaker_result(False, len(active_ids))
            err = ExecutorFailureError(
                f"speculative verify dispatch failed for "
                f"{len(active_ids)} slot(s): {type(e).__name__}: {e}",
                error_type=type(e).__name__, slots=len(active_ids))
            t_d1 = time.monotonic()
            for i in active_ids:
                tr = self._slots[i].req.trace
                if tr is not None:
                    tr.add("dispatch", t_d0, t_d1, kind="decode",
                           replica_id=self.replica_id, slot=i,
                           error=type(e).__name__)
            for i in active_ids:
                self._resolve(i, error=err)
            return
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        t_d1 = time.monotonic()
        self._breaker_result(True, len(active_ids))
        self._pools = pools
        accepted = np.asarray(accepted)
        emitted = np.asarray(emitted)
        total_tokens = 0
        accept_counts = []
        finished = []
        for i in active_ids:
            slot = self._slots[i]
            a = int(accepted[i])
            accept_counts.append(a)
            toks = emitted[i, :a + 1].tolist()
            if cfg.eos_id is not None and cfg.eos_id in toks:
                # the sequential engine stops at the FIRST eos; tokens
                # the verify scored past it were never really emitted
                toks = toks[:toks.index(cfg.eos_id) + 1]
            n = len(toks)
            slot.generated.extend(toks)
            total_tokens += n
            slot.committed += n
            slot.cur_tok = toks[-1]
            slot.remaining -= n
            tr = slot.req.trace
            if tr is not None:
                tr.add("dispatch", t_d0, t_d1, kind="decode",
                       iterations=1, replica_id=self.replica_id,
                       slot=i)
                tr.add("speculate", t_d0, t_d1, slot=i,
                       drafted=int(draft_len[i]), accepted=a,
                       emitted=n, replica_id=self.replica_id)
            if slot.remaining <= 0 or (cfg.eos_id is not None
                                       and cfg.eos_id in toks):
                finished.append(i)
        self.stats.record_decode(
            1, len(active_ids), cfg.num_slots, total_tokens,
            self.page_pool.in_use, cfg.num_pages, elapsed_ms)
        self.stats.record_verify(drafted_total, total_tokens,
                                 accept_counts)
        for i in finished:
            self._resolve(i)
