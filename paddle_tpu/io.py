"""Model/checkpoint IO.

reference: python/paddle/fluid/io.py — save_vars:89, save_params:222,
save_persistables:270, load_vars:313, load_params, load_persistables,
save_inference_model:570, load_inference_model:704.  The reference
implements save/load as `save`/`load_combine` *ops* appended to throwaway
programs; here persistence is host-side (numpy container + JSON manifest
with program-format versioning) since checkpoint IO is not a TPU
computation.  Two tiers:

- save_vars/save_params/save_persistables: combined single-file save
  (gathers; fine for single-host inference export and small models).
- save_sharded/load_sharded: per-process shard files keyed by global
  index, loaded straight into target NamedShardings — the path for
  mp/fsdp-sharded training state (used by contrib.Trainer checkpoints).
"""

from __future__ import annotations

import json
import os
import zlib
from typing import List, Optional, Sequence

import numpy as np

from .core.desc import (PROGRAM_FORMAT_VERSION, dump_program_dict,
                        load_program_dict)
from .core.executor import Executor, Scope, global_scope
from .core.program import Parameter, Program, Variable
from .resilience.errors import (CheckpointBarrierPoisonedError,
                                CheckpointBarrierTimeoutError,
                                CheckpointCorruptError,
                                CheckpointFormatError,
                                CheckpointIncompleteError,
                                CheckpointNotFoundError)

MODEL_FILENAME = "__model__"
MANIFEST = "__manifest__.json"
# serialized AOT inference artifact (written by inference.py)
EXPORT_FILENAME = "__model__.export"


def _read_manifest(dirname: str, name: str) -> dict:
    """Manifest read with the structured CheckpointError contract:
    missing file → CheckpointNotFoundError (a save that died before its
    manifest is *by design* not a checkpoint), unparseable JSON →
    CheckpointCorruptError, newer format → CheckpointFormatError."""
    path = os.path.join(dirname, name)
    try:
        with open(path) as f:
            manifest = json.load(f)
    except FileNotFoundError as e:
        raise CheckpointNotFoundError(
            f"no checkpoint manifest {name!r} in {dirname!r} (missing "
            f"or torn/incomplete save)", dirname=dirname,
            manifest=name) from e
    except (json.JSONDecodeError, UnicodeDecodeError, OSError) as e:
        raise CheckpointCorruptError(
            f"unreadable checkpoint manifest {path!r}: {e}",
            dirname=dirname, manifest=name,
            cause=f"{type(e).__name__}: {e}") from e
    version = manifest.get("version", 0)
    if version > PROGRAM_FORMAT_VERSION:
        raise CheckpointFormatError(
            f"checkpoint in {dirname!r} written by format version "
            f"{version}; this build reads <= {PROGRAM_FORMAT_VERSION}",
            dirname=dirname, manifest=name, version=version,
            supported=PROGRAM_FORMAT_VERSION)
    return manifest


def _short(e: BaseException) -> str:
    """Error summary safe to embed in messages/events (BadZipFile can
    quote kilobytes of raw archive bytes)."""
    s = str(e)
    return f"{type(e).__name__}: {s[:160]}{'…' if len(s) > 160 else ''}"


def _open_container(dirname: str, fname: str, files: dict):
    """np.load a shard/param container with structured errors (cached
    in `files`)."""
    if fname in files:
        return files[fname]
    path = os.path.join(dirname, fname)
    try:
        files[fname] = np.load(path)
    except FileNotFoundError as e:
        raise CheckpointIncompleteError(
            f"checkpoint {dirname!r} manifest references missing file "
            f"{fname!r}", dirname=dirname, file=fname) from e
    except Exception as e:  # noqa: BLE001 — BadZipFile/zlib/ValueError
        raise CheckpointCorruptError(
            f"unreadable checkpoint container {path!r}: {_short(e)}",
            dirname=dirname, file=fname, cause=_short(e)) from e
    return files[fname]


def _read_member(container, dirname: str, fname: str, key: str,
                 want_crc: Optional[int]) -> np.ndarray:
    """One stored array out of a container, CRC32-verified against the
    manifest record when present (older checkpoints without CRCs still
    load)."""
    try:
        piece = container[key]
    except KeyError as e:
        raise CheckpointIncompleteError(
            f"checkpoint container {fname!r} in {dirname!r} is missing "
            f"key {key!r}", dirname=dirname, file=fname, key=key) from e
    except Exception as e:  # noqa: BLE001 — zlib error mid-member
        raise CheckpointCorruptError(
            f"corrupt member {key!r} in checkpoint container {fname!r}:"
            f" {_short(e)}", dirname=dirname, file=fname, key=key,
            cause=_short(e)) from e
    if want_crc is not None:
        got = zlib.crc32(piece.tobytes()) & 0xFFFFFFFF
        if got != want_crc:
            raise CheckpointCorruptError(
                f"CRC mismatch for {key!r} in {fname!r} ({dirname!r}): "
                f"stored {want_crc:#010x}, computed {got:#010x} — the "
                f"shard was corrupted after save", dirname=dirname,
                file=fname, key=key, crc_stored=want_crc, crc_got=got)
    return piece


def _is_parameter(var: Variable) -> bool:
    return isinstance(var, Parameter)


def _reinterpret(piece: np.ndarray, dtype_str: str) -> np.ndarray:
    """np.savez stores custom dtypes (bfloat16/fp8 from ml_dtypes) as raw
    void records ('|V2'); reinterpret them back to the dtype recorded in
    the manifest.  Same-size native dtypes pass through untouched."""
    dt = np.dtype(dtype_str)
    if piece.dtype == dt:
        return piece
    if piece.dtype.kind == "V" and piece.dtype.itemsize == dt.itemsize:
        return piece.view(dt)
    raise RuntimeError(
        f"checkpoint dtype mismatch: stored {piece.dtype} cannot be "
        f"reinterpreted as manifest dtype {dt}")


def _collect(program: Program, predicate) -> List[Variable]:
    return [v for v in program.list_vars() if predicate(v)]


def save_vars(executor: Executor, dirname: str,
              main_program: Optional[Program] = None,
              vars: Optional[Sequence[Variable]] = None,
              predicate=None, filename: Optional[str] = None):
    """Persist variables from the scope (reference io.py:89)."""
    from .core.program import default_main_program

    program = main_program or default_main_program()
    if vars is None:
        vars = _collect(program, predicate or (lambda v: v.persistable))
    scope = global_scope()
    os.makedirs(dirname, exist_ok=True)
    arrays = {}
    names = []
    for v in vars:
        val = scope.find_var(v.name)
        if val is None:
            raise RuntimeError(f"variable {v.name!r} has no value in scope")
        arrays[v.name] = np.asarray(val)
        names.append(v.name)
    fname = filename or "params.npz"
    np.savez(os.path.join(dirname, fname), **arrays)
    manifest = {
        "version": PROGRAM_FORMAT_VERSION,
        "file": fname,
        "vars": names,
        "dtypes": {n: str(arrays[n].dtype) for n in names},
        "crc32": {n: zlib.crc32(arrays[n].tobytes()) & 0xFFFFFFFF
                  for n in names},
    }
    with open(os.path.join(dirname, MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1)


def save_params(executor, dirname, main_program=None, filename=None):
    return save_vars(executor, dirname, main_program,
                     predicate=_is_parameter, filename=filename)


def save_persistables(executor, dirname, main_program=None, filename=None):
    return save_vars(executor, dirname, main_program,
                     predicate=lambda v: v.persistable, filename=filename)


def load_vars(executor: Executor, dirname: str,
              main_program: Optional[Program] = None,
              vars: Optional[Sequence[Variable]] = None,
              predicate=None, filename: Optional[str] = None):
    from .core.program import default_main_program

    program = main_program or default_main_program()
    if vars is None:
        vars = _collect(program, predicate or (lambda v: v.persistable))
    manifest = _read_manifest(dirname, MANIFEST)
    fname = filename or manifest["file"]
    data = _open_container(dirname, fname, {})
    scope = global_scope()
    import jax.numpy as jnp

    for v in vars:
        if v.name not in data:
            raise CheckpointIncompleteError(
                f"checkpoint in {dirname!r} is missing variable "
                f"{v.name!r}", dirname=dirname, var=v.name)
        arr = _read_member(data, dirname, fname, v.name,
                           manifest.get("crc32", {}).get(v.name))
        want = manifest.get("dtypes", {}).get(v.name)
        if want is not None:
            arr = _reinterpret(arr, want)
        if tuple(arr.shape) != tuple(v.shape) and -1 not in v.shape:
            raise RuntimeError(
                f"shape mismatch for {v.name!r}: checkpoint "
                f"{arr.shape} vs program {v.shape}")
        scope.set_var(v.name, jnp.asarray(arr))


def load_params(executor, dirname, main_program=None, filename=None):
    return load_vars(executor, dirname, main_program,
                     predicate=_is_parameter, filename=filename)


def load_persistables(executor, dirname, main_program=None, filename=None):
    return load_vars(executor, dirname, main_program,
                     predicate=lambda v: v.persistable, filename=filename)


# ---------------------------------------------------------------------------
# Sharded checkpointing
# ---------------------------------------------------------------------------
#
# reference analog: the DistributeTranspiler saved per-pserver parameter
# slices instead of one combined file
# (transpiler/distribute_transpiler.py:894 _get_slice_vars_and_attrs).
# The TPU equivalent: every process writes only the array shards it
# holds (jax.Array.addressable_shards), a JSON manifest records each
# shard's global index, and load reassembles directly into the target
# NamedShardings via jax.make_array_from_callback — no host ever
# materializes the full state.

SHARD_MANIFEST = "__shards__.json"


def _shard_entries(value):
    """Global (device, index) map of a value, deduped to unique indices
    with a deterministic owner device (lowest id) per index."""
    import jax

    owners = {}
    for dev, idx in value.sharding.devices_indices_map(
            value.shape).items():
        key = tuple((sl.start or 0,
                     sl.stop if sl.stop is not None else dim)
                    for sl, dim in zip(idx, value.shape))
        if key not in owners or dev.id < owners[key].id:
            owners[key] = dev
    return owners


class ShardedSaveJob:
    """One prepared sharded save, split into its two phases:

    - the BLOCKING snapshot already happened in `prepare_sharded_save`
      (device→host copy of every shard this process owns; that is the
      only part a training step loop must wait for, recorded as
      `snapshot_ms`),
    - `write()` is the deferrable phase: CRC, zip serialization, the
      cross-process barrier, manifest-written-LAST — safe to run on a
      background writer thread (resilience.preempt.SnapshotWriter).

    A barrier timeout inside `write()` cleans up this process's own
    shard files before re-raising, so a dead-peer save leaves neither
    a manifest (torn-checkpoint invariant) nor orphaned shards.
    """

    def __init__(self, dirname: str, proc: int, local_arrays: dict,
                 meta: dict, snapshot_ms: float):
        self.dirname = dirname
        self.proc = proc
        self.local_arrays = local_arrays
        self.meta = meta
        self.snapshot_ms = snapshot_ms
        self.bytes_total = sum(a.nbytes for a in local_arrays.values())
        self.write_ms: Optional[float] = None

    def write(self) -> "ShardedSaveJob":
        import time as _time

        from .resilience.chaos import delaypoint, failpoint

        t0 = _time.perf_counter()
        dirname, proc = self.dirname, self.proc
        # a save whose manifest references ONLY this process's shard
        # file is process-LOCAL (per-rank private checkpoints — e.g. a
        # KV-only gang where each rank trains its own model): no peer
        # participates in this directory, so the cross-process barriers
        # must not couple unrelated saves (restarted ranks resume at
        # different cursors — a gang-wide barrier here would deadlock
        # their drifted save cadences), and THIS process writes the
        # manifest (the proc-0 convention is for gang-wide saves)
        local_only = ({sh["file"] for m in self.meta.values()
                       for sh in m["shards"]}
                      <= {f"shards_p{proc}.npz"})
        # chaos hook: tests arm a delay here to prove a slow write
        # phase does not stall the step loop (async acceptance test)
        delaypoint("ckpt:write")
        try:
            np.savez(os.path.join(dirname, f"shards_p{proc}.npz"),
                     **self.local_arrays)
            # per-shard CRC32 sidecar: each process records checksums
            # for the shards it wrote; proc 0 folds every sidecar into
            # the manifest after the barrier (it cannot checksum bytes
            # it never held)
            crcs = {k: zlib.crc32(a.tobytes()) & 0xFFFFFFFF
                    for k, a in self.local_arrays.items()}
            with open(os.path.join(dirname, f"shards_p{proc}.crc.json"),
                      "w") as f:
                json.dump(crcs, f)
            if not local_only:
                _barrier("save_sharded:shards")
        except CheckpointBarrierTimeoutError:
            self._cleanup_partial()
            raise
        # fault-injection point (resilience/chaos.py): the
        # torn-checkpoint tests simulate preemption exactly here —
        # shards on disk, no manifest yet
        failpoint("ckpt:before_manifest")
        # the manifest is written LAST and only once all processes'
        # shard files exist — its presence marks the checkpoint
        # complete, so a process preempted mid-save can never leave a
        # torn-but-loadable checkpoint behind
        if proc == 0 or local_only:
            all_crcs: dict = {}
            for sfile in {sh["file"] for m in self.meta.values()
                          for sh in m["shards"]}:
                cpath = os.path.join(
                    dirname, sfile.replace(".npz", ".crc.json"))
                try:
                    with open(cpath) as f:
                        all_crcs.update(json.load(f))
                except (OSError, json.JSONDecodeError):
                    pass  # CRC is best-effort at save; load tolerates gaps
            for m in self.meta.values():
                for sh in m["shards"]:
                    if sh["key"] in all_crcs:
                        sh["crc32"] = all_crcs[sh["key"]]
            tmp = os.path.join(dirname, SHARD_MANIFEST + ".tmp")
            with open(tmp, "w") as f:
                json.dump({"version": PROGRAM_FORMAT_VERSION,
                           "vars": self.meta}, f, indent=1)
            os.replace(tmp, os.path.join(dirname, SHARD_MANIFEST))
        try:
            if not local_only:
                _barrier("save_sharded:manifest")
        except CheckpointBarrierTimeoutError:
            # proc 0 already renamed the manifest: the checkpoint is
            # complete and loadable; non-zero procs only lose the sync.
            # Do NOT delete shards the manifest now references.
            raise
        self.write_ms = (_time.perf_counter() - t0) * 1000.0
        return self

    def _cleanup_partial(self) -> None:
        """Best-effort removal of this process's shard files after a
        failed shards-barrier: no manifest exists (or will), so the
        directory must not accumulate orphaned partial shards that a
        later save to the same dir could mix with."""
        for name in (f"shards_p{self.proc}.npz",
                     f"shards_p{self.proc}.crc.json"):
            try:
                os.remove(os.path.join(self.dirname, name))
            except OSError:
                pass


def prepare_sharded_save(executor: Executor, dirname: str,
                         main_program: Optional[Program] = None,
                         vars: Optional[Sequence[Variable]] = None
                         ) -> ShardedSaveJob:
    """The blocking snapshot phase of a sharded save: resolve shard
    ownership and copy every locally-owned shard device→host.  Returns
    a ShardedSaveJob whose `write()` performs the rest (callable
    inline for a synchronous save, or on a background writer)."""
    import time as _time

    import jax

    from .core.program import default_main_program

    t0 = _time.perf_counter()
    program = main_program or default_main_program()
    if vars is None:
        vars = _collect(program, lambda v: v.persistable)
    scope = global_scope()
    os.makedirs(dirname, exist_ok=True)

    proc = jax.process_index()
    local_arrays = {}
    meta = {}
    for v in vars:
        val = scope.find_var(v.name)
        if val is None:
            raise RuntimeError(f"variable {v.name!r} has no value in scope")
        if not hasattr(val, "sharding"):  # host numpy: full single shard
            val = jax.device_put(np.asarray(val))
        owners = _shard_entries(val)
        shards_meta = []
        addressable = {d.id: s for s in val.addressable_shards
                       for d in [s.device]}
        for si, (key, dev) in enumerate(sorted(owners.items())):
            owner_proc = dev.process_index
            shards_meta.append({
                "index": [list(se) for se in key],
                "file": f"shards_p{owner_proc}.npz",
                "key": f"{v.name}::{si}",
            })
            if owner_proc == proc:
                local_arrays[f"{v.name}::{si}"] = np.asarray(
                    addressable[dev.id].data)
        meta[v.name] = {
            "shape": list(val.shape),
            "dtype": str(np.dtype(val.dtype)),
            "shards": shards_meta,
        }
    return ShardedSaveJob(dirname, proc, local_arrays, meta,
                          snapshot_ms=(_time.perf_counter() - t0) * 1000.0)


def save_sharded(executor: Executor, dirname: str,
                 main_program: Optional[Program] = None,
                 vars: Optional[Sequence[Variable]] = None,
                 async_: bool = False, writer=None):
    """Save persistables with every process writing only its own shards
    (no single-host gather).  Layout: `shards_p{proc}.npz` per process +
    a manifest mapping each variable to its shard indices/files.

    With `async_=True` only the device→host snapshot happens on the
    calling thread; the serialization/barrier/manifest phase runs on a
    background SnapshotWriter (the given `writer`, else a process-wide
    default) and the returned `resilience.PendingSave` tracks it —
    write failures surface as structured CheckpointWriteErrors on the
    writer's next submit/wait/close, never silently.  Synchronous saves
    return the completed ShardedSaveJob (timings on it)."""
    job = prepare_sharded_save(executor, dirname,
                               main_program=main_program, vars=vars)
    if not async_:
        return job.write()
    if writer is None:
        from .resilience.preempt import default_writer

        writer = default_writer()
    return writer.submit(job)


# barrier ordinal: appended to the KV-store key namespace so repeated
# barriers with the same tag (every save reuses "save_sharded:shards")
# never collide.  Barriers are collective — every process calls them in
# the same order — so a local counter agrees across processes.
_barrier_seq = 0


def _dist_client():
    """The process's distributed-runtime KV client, when multi-process
    jax was initialized (parallel.init_distributed); else None."""
    # jax 0.9 exposes the KV client only here; a rename must raise,
    # not read as "single process"
    from jax._src import distributed

    return distributed.global_state.client


def barrier_timeout_s() -> float:
    """Checkpoint-barrier timeout (seconds).  Generous default — a
    slow peer flushing a big shard is normal; a dead one should fail
    in minutes, not hang the job forever.  The knob is
    FLAGS.ckpt_barrier_timeout_s (docs/RESILIENCE.md knob table); the
    legacy env PADDLE_TPU_CKPT_BARRIER_TIMEOUT_S still wins when set
    (pre-unification callers keep working)."""
    legacy = os.environ.get("PADDLE_TPU_CKPT_BARRIER_TIMEOUT_S")
    if legacy is not None:
        try:
            return float(legacy)
        except ValueError:
            pass
    from .flags import FLAGS

    return float(FLAGS.ckpt_barrier_timeout_s)


def _barrier(tag: str, timeout_s: Optional[float] = None):
    """Cross-process sync for multi-host checkpointing (no-op
    single-process), with a timeout: a peer that died mid-save raises
    a structured CheckpointBarrierTimeoutError naming the missing
    ranks instead of hanging the survivors forever.

    Implementation: each process publishes an arrival key in the
    distributed KV store, then waits for every peer's key.  On timeout
    the un-published keys identify exactly which ranks never arrived.
    Without a KV client (unusual: process_count > 1 implies
    init_distributed ran) it falls back to sync_global_devices on a
    watchdog thread — same timeout, but missing ranks unknown."""
    import time as _time

    import jax

    if jax.process_count() <= 1:
        return
    if timeout_s is None:
        timeout_s = barrier_timeout_s()
    global _barrier_seq
    seq = _barrier_seq
    _barrier_seq += 1
    client = _dist_client()
    if client is None:
        _barrier_fallback(tag, timeout_s)
        return
    prefix = f"ptpu_ckpt_barrier/{tag}/{seq}/"
    proc = jax.process_index()
    client.key_value_set(prefix + str(proc), "ok")
    peers = [p for p in range(jax.process_count()) if p != proc]
    missing = _wait_barrier_peers(client, prefix, peers, tag, timeout_s)
    if missing:
        raise CheckpointBarrierTimeoutError(
            f"checkpoint barrier {tag!r} timed out after {timeout_s:.0f}s"
            f" waiting for rank(s) {missing} (of "
            f"{jax.process_count()} processes) — peer died or wedged "
            f"inside a sharded save", tag=tag, timeout_s=timeout_s,
            missing_ranks=missing, dirname=None,
            process_count=jax.process_count())


# while a barrier waits, the gang poison key is re-checked this often:
# the bounded-time bridge between "a peer died" and "this save fails"
# (well under the 600 s barrier default)
_BARRIER_POISON_POLL_S = 1.0


def _check_barrier_poison(client, tag: str, elapsed_s: float,
                          timeout_s: float) -> None:
    """Abort a waiting barrier the moment the gang is known broken —
    the survivors stop burning the full barrier timeout on a peer that
    is already known dead.  Two sources, in order: the LOCAL health
    monitor's latched alarm (still works when the KV store died with
    the coordinator — the poison key is unreachable exactly then), and
    the gang poison KEY (a peer's monitor/watchdog declared the break).
    Poison-read failures are swallowed: a dying KV store is the local
    alarm's / plain-timeout path's business."""
    from .resilience import health as _health

    plane = _health.get_health_plane()
    if plane is not None:
        alarm = plane.monitor.alarm()
        if alarm is not None:
            details = getattr(alarm, "details", {})
            raise CheckpointBarrierPoisonedError(
                f"checkpoint barrier {tag!r} aborted after "
                f"{elapsed_s:.1f}s: local health alarm — {alarm}",
                tag=tag, timeout_s=timeout_s,
                poison={"rank": plane.rank, "reason": str(alarm),
                        "kind": getattr(alarm, "kind", "alarm"),
                        "missing_ranks":
                        details.get("missing_ranks",
                                    details.get("stalled_ranks", []))},
                elapsed_s=round(elapsed_s, 3),
                missing_ranks=details.get(
                    "missing_ranks", details.get("stalled_ranks", [])),
                dirname=None)
    try:
        poison = _health.read_poison(client)
    except Exception:  # noqa: BLE001
        return
    if poison is None:
        return
    raise CheckpointBarrierPoisonedError(
        f"checkpoint barrier {tag!r} aborted after {elapsed_s:.1f}s: "
        f"gang poisoned by rank {poison.get('rank')} — "
        f"{poison.get('reason')} (kind={poison.get('kind')})",
        tag=tag, timeout_s=timeout_s, poison=poison,
        elapsed_s=round(elapsed_s, 3),
        missing_ranks=poison.get("missing_ranks", []), dirname=None)


def _wait_barrier_peers(client, prefix: str, peers, tag: str,
                        timeout_s: float,
                        poison_poll_s: float = None) -> list:
    """Wait for every peer's arrival key, checking the gang poison key
    between short blocking-get slices.  Returns the ranks that never
    arrived (empty = all arrived); raises
    CheckpointBarrierPoisonedError on poison.  Factored out of
    _barrier so the poison fast-path is unit-testable with a FakeKv
    (the real thing is proven by the gang_worker chaos harness)."""
    import time as _time

    if poison_poll_s is None:
        poison_poll_s = _BARRIER_POISON_POLL_S
    start = _time.monotonic()
    deadline = start + timeout_s
    _check_barrier_poison(client, tag, 0.0, timeout_s)
    missing = []
    for p in peers:
        arrived = False
        while True:
            remaining = deadline - _time.monotonic()
            # even past the deadline every peer gets one 1 ms look —
            # a rank that arrived while we waited on another must not
            # be reported missing (the pre-slicing semantics)
            slice_ms = max(1, int(min(poison_poll_s,
                                      max(remaining, 0.001)) * 1000))
            try:
                client.blocking_key_value_get(prefix + str(p), slice_ms)
                arrived = True
                break
            except Exception:  # noqa: BLE001 — jaxlib raises XlaRuntimeError
                _check_barrier_poison(
                    client, tag, _time.monotonic() - start, timeout_s)
                if remaining <= 0:
                    break
        if not arrived:
            missing.append(p)
    return missing


def _barrier_fallback(tag: str, timeout_s: float):
    """sync_global_devices with a join-timeout watchdog (no KV client:
    cannot name missing ranks)."""
    import threading

    from jax.experimental import multihost_utils

    err: list = []

    def _sync():
        try:
            multihost_utils.sync_global_devices(tag)
        except Exception as e:  # noqa: BLE001 — re-raised on the caller
            err.append(e)

    t = threading.Thread(target=_sync, name=f"ckpt-barrier-{tag}",
                         daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise CheckpointBarrierTimeoutError(
            f"checkpoint barrier {tag!r} timed out after "
            f"{timeout_s:.0f}s (sync_global_devices fallback — missing "
            f"ranks unknown)", tag=tag, timeout_s=timeout_s,
            missing_ranks=[], dirname=None)
    if err:
        raise err[0]


def _assemble_index(meta, files, dirname, index):
    """Read the sub-array covering `index` (tuple of slices) from the
    saved shards, reading only intersecting shard entries."""
    shape = meta["shape"]
    starts = [sl.start or 0 for sl in index]
    stops = [sl.stop if sl.stop is not None else d
             for sl, d in zip(index, shape)]
    buf = np.empty([b - a for a, b in zip(starts, stops)],
                   np.dtype(meta["dtype"]))
    filled = 0
    for sh in meta["shards"]:
        s_idx = sh["index"]
        inter_a = [max(a, sa) for a, (sa, _) in zip(starts, s_idx)]
        inter_b = [min(b, sb) for b, (_, sb) in zip(stops, s_idx)]
        if any(a >= b for a, b in zip(inter_a, inter_b)):
            continue
        container = _open_container(dirname, sh["file"], files)
        raw = _read_member(container, dirname, sh["file"], sh["key"],
                           sh.get("crc32"))
        piece = _reinterpret(raw, meta["dtype"])
        src = tuple(slice(a - sa, b - sa) for a, b, (sa, _) in
                    zip(inter_a, inter_b, s_idx))
        dst = tuple(slice(a - oa, b - oa) for a, b, oa in
                    zip(inter_a, inter_b, starts))
        buf[dst] = piece[src]
        filled += int(np.prod([b - a for a, b in zip(inter_a, inter_b)]))
    if filled < int(np.prod(buf.shape)):
        raise CheckpointIncompleteError(
            "sharded checkpoint does not cover the requested slice "
            f"(covered {filled} of {int(np.prod(buf.shape))} elements) "
            "— missing shard files?", dirname=dirname,
            covered=filled, needed=int(np.prod(buf.shape)))
    return buf


def _optimizer_state_names(program) -> set:
    """Optimizer-state var names of `program` (the ZeRO-sharded
    population) — same classification as observe.memory's buckets and
    CompiledProgram's state shardings."""
    try:
        from .observe.memory import _program_var_buckets

        _params, opt = _program_var_buckets(program)
        return opt
    except Exception:  # noqa: BLE001 — inference programs have no
        #                optimizer ops; degrade to "nothing is opt state"
        return set()


def load_sharded(executor: Executor, dirname: str,
                 main_program: Optional[Program] = None,
                 vars: Optional[Sequence[Variable]] = None,
                 mesh=None, sharding_rules=None):
    """Load a sharded checkpoint.  With `mesh` (+ optional
    `sharding_rules`, defaulting to the program's CompiledProgram rules)
    each variable is materialized directly INTO its target
    NamedSharding — every device reads only its own slice.  Without a
    mesh, arrays load host-side (small-model fallback).

    Mesh-shape-AGNOSTIC (ISSUE 13, gang elasticity): the manifest
    records each shard's GLOBAL index, and assembly reads whichever
    saved shards intersect the target slice — so state saved on a dp=8
    (or fsdp=8) mesh loads onto dp=4, dp=2×mp=2, or a single device
    with bit-identical logical arrays, re-laid-out under the TARGET
    sharding.  Optimizer-state vars get the ZeRO axis composed into
    their target spec exactly as CompiledProgram shards them
    (state_spec_for), so a shrunken gang's opt-state shards land
    1/N'-sharded, never accidentally replicated."""
    import jax
    import jax.numpy as jnp

    from .core.program import default_main_program

    program = main_program or default_main_program()
    if vars is None:
        vars = _collect(program, lambda v: v.persistable)
    manifest = _read_manifest(dirname, SHARD_MANIFEST)
    metas = manifest["vars"]

    wrapper = getattr(program, "_compiled_wrapper", None)
    spec_fn = None
    if mesh is not None:
        if sharding_rules is not None:
            opt_names = _optimizer_state_names(program)

            def spec_fn(name, shape):
                if name in opt_names:
                    return sharding_rules.opt_state_spec_for(
                        name, shape, mesh)
                return sharding_rules.spec_for(name, shape, mesh)
        elif wrapper is not None and wrapper._mesh is mesh:
            # the wrapper's own spec logic (rules + ZeRO composition)
            spec_fn = wrapper.state_spec_for
        elif wrapper is not None and wrapper._rules is not None:
            # resharding onto a DIFFERENT mesh than the wrapper's:
            # same rules, target mesh
            rules = wrapper._rules
            opt_names = _optimizer_state_names(program)

            def spec_fn(name, shape):
                if name in opt_names:
                    return rules.opt_state_spec_for(name, shape, mesh)
                return rules.spec_for(name, shape, mesh)

    scope = global_scope()
    files: dict = {}
    for v in vars:
        if v.name not in metas:
            raise CheckpointIncompleteError(
                f"sharded checkpoint in {dirname!r} is missing variable "
                f"{v.name!r}", dirname=dirname, var=v.name)
        meta = metas[v.name]
        if tuple(meta["shape"]) != tuple(v.shape) and -1 not in v.shape:
            raise RuntimeError(
                f"shape mismatch for {v.name!r}: checkpoint "
                f"{tuple(meta['shape'])} vs program {tuple(v.shape)}")
        if mesh is None:
            full = _assemble_index(
                meta, files, dirname,
                tuple(slice(0, d) for d in meta["shape"]))
            scope.set_var(v.name, jnp.asarray(full))
            continue
        from jax.sharding import NamedSharding, PartitionSpec as P

        if spec_fn is not None:
            spec = spec_fn(v.name, meta["shape"])
        else:
            spec = (None,) * len(meta["shape"])
        sharding = NamedSharding(mesh, P(*spec))
        arr = jax.make_array_from_callback(
            tuple(meta["shape"]), sharding,
            lambda idx, m=meta: _assemble_index(m, files, dirname, idx))
        scope.set_var(v.name, arr)


# ---------------------------------------------------------------------------
# Inference export
# ---------------------------------------------------------------------------

def save_inference_model(dirname: str, feeded_var_names: Sequence[str],
                         target_vars: Sequence[Variable],
                         executor: Executor,
                         main_program: Optional[Program] = None,
                         model_filename: Optional[str] = None,
                         params_filename: Optional[str] = None):
    """Prune to the inference subgraph and export (reference io.py:570):
    writes `__model__` (serialized program) + params."""
    from .core.executor import prune_ops
    from .core.program import default_main_program

    program = (main_program or default_main_program()).clone(for_test=True)
    fetch_names = [t.name for t in target_vars]

    # prune ops to fetch ancestors, then drop unused vars
    program._backward_info = None
    kept_ops = prune_ops(program, fetch_names)
    block = program.global_block()
    block.ops = list(kept_ops)
    used = set(fetch_names) | set(feeded_var_names)
    for op in block.ops:
        used.update(op.desc.input_names())
        used.update(op.desc.output_names())
    block.vars = {n: v for n, v in block.vars.items() if n in used}

    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, model_filename or MODEL_FILENAME),
              "w") as f:
        d = program.to_dict()
        d["feed_var_names"] = list(feeded_var_names)
        d["fetch_var_names"] = fetch_names
        f.write(dump_program_dict(d))
    # a re-saved model invalidates any serialized AOT artifact exported
    # from the previous one (inference.py also hash-checks as a belt)
    for stale in (EXPORT_FILENAME, EXPORT_FILENAME + ".json"):
        p = os.path.join(dirname, stale)
        if os.path.exists(p):
            os.remove(p)
    params = [v for v in program.list_vars() if v.persistable]
    save_vars(executor, dirname, program, vars=params,
              filename=params_filename)
    return fetch_names


def load_inference_model(dirname: str, executor: Executor,
                         model_filename: Optional[str] = None,
                         params_filename: Optional[str] = None):
    """reference io.py:704 — returns (program, feed_names, fetch_vars)."""
    with open(os.path.join(dirname, model_filename or MODEL_FILENAME)) as f:
        d = load_program_dict(f.read())
    program = Program.from_dict(d)
    load_vars(executor, dirname, program,
              predicate=lambda v: v.persistable, filename=params_filename)
    fetch_vars = [program.global_block().var(n)
                  for n in d.get("fetch_var_names", [])]
    return program, d.get("feed_var_names", []), fetch_vars
