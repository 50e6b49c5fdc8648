"""StepTelemetry: device-side training-health accumulator.

The architecture invariant (CLAUDE.md) is that a training step is ONE
jitted XLA computation with no host round-trips and no host
callbacks — so per-step scalars (loss, grad norm,
update norm, non-finite counts) must ACCUMULATE ON DEVICE as extra
carry state of the jitted step and be fetched every N steps in one
host sync ("device-accumulate, periodic-fetch").  The accumulator is a
flat dict-of-scalars pytree living in the executor state under
`TELEMETRY_VAR`; `core/executor.py` threads it through the step (and
through `chain_iterations`' fori_loop carry, so K chained iterations
accumulate K updates with zero extra dispatches).

reference analog: the reference's per-op NaN scan ran on HOST after
every op (operator.cc:943 FLAGS_check_nan_inf) — affordable on a
stream-per-op runtime, a per-step device->host sync here.  The
host-side `_debug_checks` path still exists for debugging; this module
is the production-telemetry replacement that costs one fetch per
window.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import numpy as np

TELEMETRY_VAR = "__telemetry__"

_F32_FIELDS = ("loss_sum", "loss_last", "grad_norm_sum", "grad_norm_last",
               "update_norm_sum", "update_norm_last")
_I32_FIELDS = ("steps", "nonfinite_grad_steps", "nonfinite_loss_steps",
               "skipped_update_steps")
# update-guard state (resilience/guard.py) rides the same accumulator
# but is NOT a window counter: a telemetry reset must preserve it, or
# the loss-scale schedule would restart every fetch
_PERSISTENT_FIELDS = ("loss_scale", "ls_good_steps", "ls_bad_steps")


# scalars a model asks to see beside the loss (`track_scalars`): two
# accumulator fields each, "<prefix><name>.sum" and "<prefix><name>.last"
SCALAR_PREFIX = "scalar."


def track_scalars(program, **variables) -> None:
    """Name (1,)-shaped variables of `program` (an auxiliary loss, a
    router z-loss) whose value the telemetry accumulator sums and
    latches each step, beside the loss: `fetch_telemetry(...).scalars`
    then holds `{name: {"last", "mean"}}`.  Costs nothing until the
    program opts into telemetry."""
    tracked = dict(getattr(program, "_tracked_scalars", None) or {})
    tracked.update({name: var.name for name, var in variables.items()})
    program._tracked_scalars = tracked
    program._bump()


def enable_telemetry(program) -> None:
    """Opt a Program's compiled step into device-side telemetry.  Must
    be set before the Executor builds/caches the step fn for this
    (program, feeds, fetches) combination — enabling later changes the
    cache key, forcing a rebuild, so it still takes effect (at one
    retrace's cost)."""
    program._telemetry_enabled = True


def telemetry_enabled(program) -> bool:
    return bool(getattr(program, "_telemetry_enabled", False))


def init_telemetry(loss_scale: float = 1.0) -> Dict[str, Any]:
    """Fresh zeroed accumulator (host values; become device arrays on
    first dispatch).  `loss_scale` seeds the dynamic loss-scale scalar
    (resilience update guard); 1.0 = inert."""
    out: Dict[str, Any] = {f: np.float32(0.0) for f in _F32_FIELDS}
    out.update({f: np.int32(0) for f in _I32_FIELDS})
    out["loss_scale"] = np.float32(loss_scale)
    out["ls_good_steps"] = np.int32(0)
    out["ls_bad_steps"] = np.int32(0)
    return out


def init_telemetry_for(program) -> Dict[str, Any]:
    """Accumulator sized for one program: guard loss-scale seed plus,
    when the program opted into numerics observability
    (observe.numerics), the per-group vectors and the latched
    first-nonfinite bitmap (one bit per fluid op)."""
    guard_cfg = getattr(program, "_update_guard", None)
    out = init_telemetry(loss_scale=guard_cfg.init_loss_scale
                         if guard_cfg is not None else 1.0)
    for name in getattr(program, "_tracked_scalars", None) or ():
        out[f"{SCALAR_PREFIX}{name}.sum"] = np.float32(0.0)
        out[f"{SCALAR_PREFIX}{name}.last"] = np.float32(0.0)
    if getattr(program, "_numerics_enabled", False):
        from . import numerics as _numerics

        out.update(_numerics.init_numerics_fields(
            len(program.global_block().ops)))
    return out


def ensure_numerics_fields(program, tel: Dict[str, Any]) -> Dict[str, Any]:
    """Patch an EXISTING scope accumulator when numerics was enabled
    after telemetry already ran (or the program grew ops): merge in
    correctly-sized zeroed numerics fields, preserving every window
    counter and the guard's loss-scale schedule.  Returns `tel`
    unchanged when nothing is missing."""
    if not getattr(program, "_numerics_enabled", False):
        return tel
    from . import numerics as _numerics

    n_ops = len(program.global_block().ops)
    words = tel.get(_numerics.NONFINITE_WORDS)
    if words is not None and \
            np.asarray(words).shape[0] == _numerics.n_bit_words(n_ops):
        return tel
    out = dict(tel)
    out.update(_numerics.init_numerics_fields(n_ops))
    return out


def device_update(tel: Dict[str, Any], loss, grads: Dict[str, Any],
                  params_before: Dict[str, Any],
                  env: Dict[str, Any],
                  tracked: Optional[Dict[str, str]] = None
                  ) -> Dict[str, Any]:
    """One step's accumulation — runs INSIDE the jit trace (pure, no
    callbacks).  grads may contain SparseGrad pytrees (their touched
    rows carry the whole gradient mass, so the norm over rows is the
    true table-grad norm up to duplicate-id merging).  `tracked`:
    `{name: variable name}` of `track_scalars`, read from `env`."""
    import jax.numpy as jnp

    from ..core.selected_rows import SparseGrad

    gsq = jnp.float32(0.0)
    nonfinite = jnp.int32(0)
    for g in grads.values():
        parts = (g.rows,) if isinstance(g, SparseGrad) else (g,)
        for a in parts:
            af = a.astype(jnp.float32)
            gsq = gsq + jnp.sum(af * af)
            nonfinite = nonfinite + (~jnp.isfinite(af)).sum().astype(
                jnp.int32)
    usq = jnp.float32(0.0)
    for pname, old in params_before.items():
        new = env.get(pname)
        if new is None or new is old:
            continue
        d = new.astype(jnp.float32) - old.astype(jnp.float32)
        usq = usq + jnp.sum(d * d)
    gnorm = jnp.sqrt(gsq)
    unorm = jnp.sqrt(usq)
    lf = jnp.asarray(loss).astype(jnp.float32)
    loss_bad = (~jnp.isfinite(lf)).astype(jnp.int32)
    out = dict(tel)  # guard/loss-scale fields pass through untouched
    out.update({
        "steps": tel["steps"] + 1,
        "loss_sum": tel["loss_sum"] + lf,
        "loss_last": lf,
        "grad_norm_sum": tel["grad_norm_sum"] + gnorm,
        "grad_norm_last": gnorm,
        "update_norm_sum": tel["update_norm_sum"] + unorm,
        "update_norm_last": unorm,
        "nonfinite_grad_steps": tel["nonfinite_grad_steps"]
        + (nonfinite > 0).astype(jnp.int32),
        "nonfinite_loss_steps": tel["nonfinite_loss_steps"] + loss_bad,
    })
    for name, var in (tracked or {}).items():
        key = f"{SCALAR_PREFIX}{name}"
        if var in env and key + ".sum" in tel:
            v = jnp.asarray(env[var]).astype(jnp.float32).reshape(())
            out[key + ".sum"] = tel[key + ".sum"] + v
            out[key + ".last"] = v
    return out


@dataclass
class StepTelemetry:
    """Host-side view of one telemetry window (the periodic fetch)."""

    steps: int
    loss_last: float
    loss_mean: float
    grad_norm_last: float
    grad_norm_mean: float
    update_norm_last: float
    update_norm_mean: float
    nonfinite_grad_steps: int
    nonfinite_loss_steps: int
    # resilience update guard (0 / 1.0 when the guard is not enabled)
    skipped_update_steps: int = 0
    loss_scale: float = 1.0
    # numerics observability (observe.numerics; None when the program
    # did not opt in): per-group dynamics + first-nonfinite provenance
    groups: Optional[Dict[str, Dict[str, float]]] = None
    first_nonfinite_op: Optional[Dict[str, Any]] = None
    # `track_scalars` values: {name: {"last", "mean"}} (None: none)
    scalars: Optional[Dict[str, Dict[str, float]]] = None

    def as_dict(self) -> Dict[str, Any]:
        out = {
            "steps": self.steps,
            "loss_last": self.loss_last,
            "loss_mean": self.loss_mean,
            "grad_norm_last": self.grad_norm_last,
            "grad_norm_mean": self.grad_norm_mean,
            "update_norm_last": self.update_norm_last,
            "update_norm_mean": self.update_norm_mean,
            "nonfinite_grad_steps": self.nonfinite_grad_steps,
            "nonfinite_loss_steps": self.nonfinite_loss_steps,
            "skipped_update_steps": self.skipped_update_steps,
            "loss_scale": self.loss_scale,
        }
        if self.groups is not None:
            out["groups"] = self.groups
        if self.first_nonfinite_op is not None:
            out["first_nonfinite_op"] = self.first_nonfinite_op
        if self.scalars is not None:
            out["scalars"] = self.scalars
        return out

    @property
    def healthy(self) -> bool:
        return (self.nonfinite_grad_steps == 0
                and self.nonfinite_loss_steps == 0)


def fetch_telemetry(scope, reset: bool = True,
                    program=None) -> Optional[StepTelemetry]:
    """ONE host sync: pull the device accumulator out of `scope`,
    convert to a window summary, and (by default) re-zero it so the
    next window starts fresh.  Returns None when the scope carries no
    telemetry (program not enabled, or no step ran yet).

    `program`: when given and the window latched a nonfinite bitmap
    (observe.numerics), the first set bit is joined back to the fluid
    op desc — `first_nonfinite_op` then carries op type/index/group,
    not just the index."""
    raw = scope.find_var(TELEMETRY_VAR)
    if raw is None:
        return None
    host: Dict[str, Any] = {}
    for k, v in raw.items():
        a = np.asarray(v)
        host[k] = a.item() if a.ndim == 0 else a
    if reset:
        # re-zero by SHAPE (scalars and numerics vectors alike) so the
        # next window starts fresh whatever fields this program carries
        fresh: Dict[str, Any] = {}
        for k, v in raw.items():
            if k in _PERSISTENT_FIELDS:  # loss-scale schedule survives
                fresh[k] = raw[k]
            else:
                a = np.asarray(v)
                fresh[k] = (np.zeros_like(a) if a.ndim
                            else a.dtype.type(0))
        scope.set_var(TELEMETRY_VAR, fresh)
    groups = first = None
    if "nonfinite_op_words" in host:
        from . import numerics as _numerics

        groups = _numerics.summarize_groups(host)
        if int(host.get(_numerics.NONFINITE_LATCH, 0)):
            first = _numerics.join_first_nonfinite(
                host[_numerics.NONFINITE_WORDS], program=program)
    n = max(int(host["steps"]), 1)
    scalars = {
        k[len(SCALAR_PREFIX):-len(".sum")]: {
            "last": host[k[:-len(".sum")] + ".last"], "mean": v / n}
        for k, v in host.items()
        if k.startswith(SCALAR_PREFIX) and k.endswith(".sum")}
    return StepTelemetry(
        steps=int(host["steps"]),
        loss_last=host["loss_last"],
        loss_mean=host["loss_sum"] / n,
        grad_norm_last=host["grad_norm_last"],
        grad_norm_mean=host["grad_norm_sum"] / n,
        update_norm_last=host["update_norm_last"],
        update_norm_mean=host["update_norm_sum"] / n,
        nonfinite_grad_steps=int(host["nonfinite_grad_steps"]),
        nonfinite_loss_steps=int(host["nonfinite_loss_steps"]),
        skipped_update_steps=int(host.get("skipped_update_steps", 0)),
        loss_scale=float(host.get("loss_scale", 1.0)),
        groups=groups,
        first_nonfinite_op=first,
        scalars=scalars or None,
    )
