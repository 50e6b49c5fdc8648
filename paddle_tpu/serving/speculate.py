"""Drafting layer for speculative decoding (ISSUE 20).

`DecodeEngine(speculate_k=k)` replaces the one-token-per-iteration
chunk loop with VERIFIED multi-token steps: a drafter proposes up to k
tokens per slot on the host, one fixed-shape verify dispatch (the step
program at folded batch S*(k+1), models/decoder_lm.py `verify`) scores
all of them, and greedy longest-accepted-prefix acceptance commits
1..k+1 tokens — bit-identical to the sequential engine, because the
verify forward IS the sequential forward at every drafted position.

Two interchangeable drafters behind one protocol:

- `NGramDrafter` (the default): host-side prompt-lookup drafting —
  propose the tokens that followed the most recent earlier occurrence
  of the current suffix n-gram in (prompt + generated).  Zero extra
  device cost, deterministic, and highly effective on repetitive
  streams (greedy LMs cycle; code/prose repeat).
- `ModelDrafter`: a small draft `DecoderLM` that shares the serving
  fleet's slot/pool conventions — its OWN KV pools at the ENGINE's
  exact (num_pages, page_size) geometry, addressed by the ENGINE's
  page tables, so join/leave/preempt/import keep both pools aligned
  with zero extra bookkeeping.  One fixed-k jitted chunk produces all
  k drafts in a single dispatch; prefill-on-join and the disagg
  import mirror into the draft pool through the same bucket ladder.

Draft-pool consistency needs NO rollback hook: the accepted-prefix
rows are exactly what a sequential draft run over the committed
stream would have written, and rejected-tail rows sit past every
slot's length — the next draft chunk overwrites them before any
attention can read them (the same rollback-as-no-write argument as
the target pool).

All drafter compiles happen inside `DecodeEngine.start()`'s warmup
window, so the zero-post-warmup-compile contract holds fleet-wide
across ANY accept pattern.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np


def ngram_propose(context, k: int, ngram: int = 3) -> List[int]:
    """Prompt-lookup drafting: find the MOST RECENT earlier occurrence
    of the trailing g-gram of `context` (g = ngram down to 1) and
    propose the <= k tokens that followed it.  Among the occurrences
    of a g-gram, the most recent one with a FULL k-token continuation
    wins over a nearer one truncated by the context end — in a
    short-period cycle the nearest match sits within k tokens of the
    tail and would cap every proposal below k, exactly the streams
    drafting serves best.  Pure and deterministic: same context ->
    same proposal, which is what makes speculative runs reproducible.
    Returns [] when nothing matches."""
    ctx = np.asarray(context, dtype=np.int64).ravel()
    n = int(ctx.size)
    k = int(k)
    if n < 2 or k < 1:
        return []
    for g in range(min(int(ngram), n - 1), 0, -1):
        # vectorized window match: starts 0..n-g-1, window == tail.
        # This scan runs per slot per verify round on the scheduler
        # thread — the numpy form is what keeps host drafting cheap
        # against the dispatch it races.
        tail = ctx[n - g:]
        match = ctx[:n - g] == tail[0]
        for j in range(1, g):
            match &= ctx[j:j + n - g] == tail[j]
        idx = np.nonzero(match)[0]
        if idx.size:
            full = idx[idx + g + k <= n]
            if full.size:
                start = int(full[-1])
            else:
                part = idx[idx + g < n]
                if not part.size:
                    continue
                start = int(part[-1])
            return [int(t) for t in ctx[start + g:start + g + k]]
    return []


class Drafter:
    """Protocol between DecodeEngine and a drafting strategy.

    The engine calls, always on its scheduler thread:
    - `start(engine)` inside the warmup window (compile here);
    - `on_prefill(engine, joiners, tokens, seq_len, last_idx)` after
      every successful prefill-on-join dispatch (same padded host
      buffers the engine dispatched);
    - `on_import(engine, slot_id)` after a disagg KV handoff seeds a
      slot on a decode-role worker;
    - `draft(engine, active_ids) -> (drafts (S, k) int32, draft_len
      (S,) int32)` once per verify round.  Proposals may be shorter
      than k (ragged draft_len) and the ENGINE caps them again to the
      slot's remaining budget — a drafter never worries about caps.
    """

    k: int = 0

    def start(self, engine) -> None:  # pragma: no cover - trivial
        pass

    def on_prefill(self, engine, joiners, tokens, seq_len,
                   last_idx) -> None:  # pragma: no cover - trivial
        pass

    def on_import(self, engine, slot_id) -> None:  # pragma: no cover
        pass

    def draft(self, engine, active_ids
              ) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Host-side prompt-lookup drafting (the default drafter): zero
    extra device cost, zero state — the context IS the slot's
    (prompt + generated) stream the scheduler already holds."""

    def __init__(self, k: int, ngram: int = 3):
        if int(k) < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        if int(ngram) < 1:
            raise ValueError(f"ngram must be >= 1, got {ngram}")
        self.k = int(k)
        self.ngram = int(ngram)

    def draft(self, engine, active_ids):
        s = engine.config.num_slots
        drafts = np.zeros((s, self.k), np.int32)
        draft_len = np.zeros((s,), np.int32)
        for i in active_ids:
            slot = engine._slots[i]
            ctx = np.concatenate([
                np.asarray(slot.req.prompt, np.int64).ravel(),
                np.asarray(slot.generated, np.int64)])
            follow = ngram_propose(ctx, self.k, self.ngram)
            draft_len[i] = len(follow)
            drafts[i, :len(follow)] = follow
        return drafts, draft_len


class ModelDrafter(Drafter):
    """A small draft DecoderLM following the target slot-for-slot.

    `model` is any models.decoder_lm.DecoderLM (its parameter names
    come out of the same `unique_name.guard()` discipline as the
    target's, so checkpoints load with the normal io path).  Pools are
    allocated at the ENGINE's exact page geometry and addressed by the
    ENGINE's page tables — the draft pool is a shadow of the target
    pool, kept aligned for free by every join/preempt/import.

    A draft model with the target's own architecture and seed is the
    ORACLE drafter (every draft accepted) — the test lever that pins
    the accept-rate histogram's top bin.
    """

    def __init__(self, model, k: int):
        if int(k) < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.model = model
        self.k = int(k)
        self._params = None
        self._pools = None
        self._draft_exec = None
        self._prefill_execs = {}
        self._started = False

    # -- lifecycle (inside the engine's warmup window) -----------------
    def start(self, engine) -> None:
        import jax
        import jax.numpy as jnp

        from ..core.executor import RNG_STATE_VAR

        cfg = engine.config
        scope = self.model.init_params()
        self._params = {
            n: jax.device_put(jnp.asarray(v))
            for n, v in scope.vars.items()
            if v is not None and n != RNG_STATE_VAR}
        self._pools = {n: jax.device_put(v) for n, v in
                       self.model.fresh_pools(cfg.num_pages,
                                              cfg.page_size).items()}
        params_spec = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                       for n, v in self._params.items()}
        pool_specs = self.model.pool_specs(cfg.num_pages,
                                           cfg.page_size)
        i32 = jnp.int32
        s = cfg.num_slots
        vec = jax.ShapeDtypeStruct((s,), i32)
        pt = jax.ShapeDtypeStruct((s, cfg.max_pages_per_slot), i32)
        # the pools are the LAST argument of both signatures
        self._draft_exec = jax.jit(
            self._build_draft_fn(),
            donate_argnums=(6,) if engine._donate else ()).lower(
                params_spec, vec, vec, vec, vec, pt,
                pool_specs).compile()
        donate = (5,) if engine._donate else ()
        # the full bucket ladder compiles here even on a decode-role
        # worker (the ENGINE skips its own prefill execs there; the
        # DRAFT pool still needs prompt KV on every import)
        for t in cfg.prefill_buckets:
            tok = jax.ShapeDtypeStruct((s, t), i32)
            last = jax.ShapeDtypeStruct((s, 1), i32)
            self._prefill_execs[t] = jax.jit(
                self._build_prefill_fn(t),
                donate_argnums=donate).lower(
                    params_spec, tok, vec, last, pt,
                    pool_specs).compile()
        self._started = True

    def _build_draft_fn(self):
        """k sequential draft steps as ONE jitted fori_loop: write the
        pending token's K/V, attend, argmax, advance — the engine's
        chunk loop shape with a static trip count (no early exit: a
        draft past the budget is capped by the engine; a step whose
        write position is past the slot's allocated pages (`limit`)
        drops its write — its page-table entry is 0, ANOTHER slot's
        physical page).  The loop runs
        k + 1 steps: the last one only writes the k-th draft's K/V —
        when a round accepts every draft the engine commits k + 1
        tokens, and without that row the draft pool would carry a hole
        at `committed - 1` that every later draft attends over."""
        import jax
        import jax.numpy as jnp

        from ..core.executor import interpret_program

        st = self.model.step
        program = st["main"]
        next_name = st["next_token"]
        cache_outs = st["cache_outs"]
        cache_names = self.model.cache_feed_names()
        fetches = (next_name, *cache_outs)
        k = self.k

        def draft_fn(params, tokens, write_pos, active, limit,
                     page_table, pools):
            buf0 = jnp.zeros((tokens.shape[0], k + 1), jnp.int32)

            def body(j, c):
                tok, wp, pls, buf = c
                env = dict(params)
                env.update(pls)
                env.update(tokens=tok, write_pos=wp, lengths=wp + 1,
                           active=active * (wp < limit),
                           page_table=page_table)
                env = interpret_program(program, env, None,
                                        fetch_names=fetches)
                nxt = env[next_name].astype(jnp.int32)
                new_pools = {n: env[o] for n, o in
                             zip(cache_names, cache_outs)}
                buf = buf.at[:, j].set(nxt)
                new_tok = jnp.where(active > 0, nxt, tok)
                return (new_tok, wp + active, new_pools, buf)

            _tok, _wp, pls, buf = jax.lax.fori_loop(
                0, k + 1, body, (tokens, write_pos, pools, buf0))
            return buf[:, :k], pls

        return draft_fn

    def _build_prefill_fn(self, t_bucket: int):
        import jax.numpy as jnp

        from ..core.executor import interpret_program

        pre = self.model.prefill(t_bucket)
        program = pre["main"]
        cache_outs = pre["cache_outs"]
        cache_names = self.model.cache_feed_names()

        def prefill_fn(params, tokens, seq_len, last_idx, page_table,
                       pools):
            env = dict(params)
            env.update(pools)
            env.update(tokens=tokens, seq_len=seq_len,
                       last_idx=last_idx, page_table=page_table)
            env = interpret_program(program, env, None,
                                    fetch_names=tuple(cache_outs))
            return {n: env[o]
                    for n, o in zip(cache_names, cache_outs)}

        return prefill_fn

    # -- engine hooks ---------------------------------------------------
    def on_prefill(self, engine, joiners, tokens, seq_len,
                   last_idx) -> None:
        """Mirror a prefill-on-join into the draft pool: the SAME
        padded host buffers the engine dispatched, addressed by the
        SAME page tables (geometry is shared by construction)."""
        import jax.numpy as jnp

        exec_ = self._prefill_execs[tokens.shape[1]]
        self._pools = exec_(
            self._params, jnp.asarray(tokens), jnp.asarray(seq_len),
            jnp.asarray(last_idx),
            jnp.asarray(engine._page_tables), self._pools)

    def on_import(self, engine, slot_id) -> None:
        """Disagg decode-role hook: a KV handoff seeded the TARGET
        slot but no draft-model KV crossed the wire — re-prefill the
        raw prompt into the draft pool locally (single joiner, every
        other slot masked out by seq_len 0)."""
        from .engine import BucketConfig

        slot = engine._slots[slot_id]
        prompt = np.asarray(slot.req.prompt)
        plen = int(prompt.size)
        bucket = BucketConfig.pick(engine.config.prefill_buckets, plen)
        if bucket is None:
            raise ValueError(
                f"draft-pool import re-prefill: prompt length {plen} "
                f"fits no prefill bucket "
                f"{list(engine.config.prefill_buckets)}")
        s = engine.config.num_slots
        tokens = np.zeros((s, bucket), np.int32)
        seq_len = np.zeros((s,), np.int32)
        last_idx = np.zeros((s, 1), np.int32)
        tokens[slot_id, :plen] = prompt
        seq_len[slot_id] = plen
        last_idx[slot_id, 0] = plen - 1
        self.on_prefill(engine, [slot_id], tokens, seq_len, last_idx)

    def draft(self, engine, active_ids):
        import jax.numpy as jnp

        s = engine.config.num_slots
        tokens = np.zeros((s,), np.int32)
        wp = np.zeros((s,), np.int32)
        act = np.zeros((s,), np.int32)
        limit = np.zeros((s,), np.int32)
        draft_len = np.zeros((s,), np.int32)
        for i in active_ids:
            slot = engine._slots[i]
            tokens[i] = slot.cur_tok
            wp[i] = slot.committed
            act[i] = 1
            limit[i] = len(slot.pages) * engine.config.page_size
            draft_len[i] = self.k
        buf, pools = self._draft_exec(
            self._params, jnp.asarray(tokens), jnp.asarray(wp),
            jnp.asarray(act), jnp.asarray(limit),
            jnp.asarray(engine._page_tables), self._pools)
        self._pools = pools
        return np.asarray(buf), draft_len
