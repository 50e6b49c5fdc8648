"""What the CPU parity tests of the decoder families share
(tests/test_*_parity.py): the batch, one build-and-run of the training
Program, one call of a family's float32 reference, the comparison.  Not
a test file.  A family's file keeps what is its own: its `config`, its
`SHARES`, how a configuration becomes the builder's arguments, its
reference module (`Family`), its tolerances.

`system` and `reference` REMEMBER their result for the process, keyed
by everything that determines it, and return arrays that refuse a
write: `--dist loadfile` keeps a file on one worker, so a build that a
neighbouring test already paid for costs nothing.  `build_and_run` is
the function that really builds: a test that reads `runtime_stats`
around a build, patches a module's constants for one, or hands the
start-up a function made on the spot calls it by that name.
"""

from __future__ import annotations

import collections
import hashlib
import inspect
import json

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import decoder
from paddle_tpu.observe.monitoring import runtime_stats

_REMEMBERED = {}


def batch(cfg, n=2, length=32, seed=0, ahead=1):
    """`n` rows of `length` random tokens and the labels one position
    on (`ahead` 2: `next_labels` two on, for a prediction module)."""
    ids = np.random.default_rng(seed).integers(
        1, cfg["vocab_size"], size=(n, length + ahead))
    feed = {"tokens": ids[:, :-ahead], "labels": ids[:, 1:length + 1]}
    if ahead == 2:
        feed["next_labels"] = ids[:, 2:]
    return feed


def close(got, want, what, tol=5e-6, scale=1.0):
    np.testing.assert_allclose(np.asarray(got).reshape(-1),
                               np.asarray(want).reshape(-1),
                               rtol=tol, atol=tol * scale, err_msg=what)


def build_and_run(arguments, feed, use_amp=False, seed=7,
                  fetch=("loss", "logits"), after_startup=None, params=None,
                  builder=None):
    """One forward and backward of `decoder.build_model(**arguments)`
    (no optimizer) on `feed`: what was fetched, by name, and the
    parameters in creation order.  `fetch` names the model's scalars and
    tensors to read (one the model lacks is left out); every routed
    layer's `counts` and `experts`, every parameter's gradient (`grads`),
    the parameters' `names`, the Program (`main`) and what the build and
    the run added to `runtime_stats` (`took`) come with them.
    `after_startup(main, scope, seed)` may move parameters off the
    constants they start at; what it returns is `drawn`.  `params`:
    values to start from instead, in creation order."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    before = runtime_stats.snapshot()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            fluid.unique_name.guard():
        m = (builder or decoder.build_model)(
            max_length=feed["labels"].shape[1], with_optimizer=False,
            **arguments)
        if use_amp:
            main._amp_lists = fluid.amp.AutoMixedPrecisionLists()
        grads = [g for _, g in fluid.append_backward(m["loss"])]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        drawn = after_startup(main, scope, seed) if after_startup else None
        names = [p.name for p in main.all_parameters()]
        for name, value in zip(names, params or ()):
            scope.set_var(name, value)
        params = [np.asarray(scope.find_var(n)) for n in names]
        keys = [k for k in fetch if m.get(k) is not None]
        counts, experts = m.get("counts") or [], m.get("experts") or []
        fetched = exe.run(main, feed=feed, scope=scope,
                          fetch_list=[m[k] for k in keys] + counts + experts
                          + grads)
    out = dict(zip(keys, fetched))
    rest = fetched[len(keys):]
    out.update(counts=rest[:len(counts)],
               experts=rest[len(counts):len(counts) + len(experts)],
               grads=rest[len(counts) + len(experts):], names=names,
               main=main, drawn=drawn, took=runtime_stats.delta(before))
    return out, params


def expert_bias_names(main):
    """The routed layers' selection biases in creation order: the main
    model's, then a prediction module's."""
    return sorted((n for n in main.global_block().vars
                   if n.endswith(".expert_bias")),
                  key=lambda n: (n.startswith("mtp/"), n))


def draw_expert_biases(main, scope, seed):
    """An `after_startup`: the selection biases start at 0; give them
    values, so that the comparison sees them.  Returns them."""
    rng = np.random.default_rng(seed)
    biases = []
    for name in expert_bias_names(main):
        assert not np.asarray(scope.find_var(name)).any()
        biases.append(rng.normal(0, 0.05, scope.find_var(name).shape)
                      .astype(np.float32))
        scope.set_var(name, biases[-1])
    return biases


def system(arguments, feed, use_amp=False, seed=7, fetch=("loss", "logits"),
           after_startup=None, params=None, builder=None):
    """`build_and_run`, remembered."""
    key = ("system", _canonical(arguments), _digest(feed), bool(use_amp),
           seed, tuple(fetch), after_startup, _digest(params), builder)
    if key not in _REMEMBERED:
        _REMEMBERED[key] = _frozen(build_and_run(
            arguments, feed, use_amp, seed, fetch, after_startup, params,
            builder))
    return _REMEMBERED[key]


# A family's float32 reference: `to_tree(params, cfg[, drawn])` makes its
# parameter tree of the system's list, `loss_and_grads(tree, *feeds, cfg,
# **how)` is the module's own, `to_list(grads, cfg)` brings
# the gradient tree back into the system's order.
Family = collections.namedtuple("Family", "to_tree loss_and_grads to_list")
_FEEDS = ("tokens", "labels", "next_labels", "loss_weights", "pixel_values")


def reference(family, cfg, feed, params, drawn=None, **how):
    """(loss, parts, gradients in the system's order) of `family`'s
    reference on the system's parameters, remembered.  `how`: the
    reference's own keywords (a loss weight, `q_block` and its like;
    None is the default left out)."""
    how = {k: v for k, v in how.items() if v is not None}
    key = ("reference", family, _canonical(cfg), _digest(feed),
           _digest(params), _digest(drawn), _canonical(how))
    if key not in _REMEMBERED:
        tree = family.to_tree(params, cfg) if drawn is None \
            else family.to_tree(params, cfg, drawn)
        # compiled as ONE function, as the chip's parity scripts run it
        # (benchmarks/*_parity.py): op by op it compiles every primitive
        # of every shape by itself, two to five times as long
        (total, parts), grads = jax.jit(
            lambda tree, *feeds: family.loss_and_grads(
                tree, *feeds, cfg, **how))(
            tree, *(jnp.asarray(feed[k]) for k in _FEEDS if k in feed))
        _REMEMBERED[key] = total, parts, family.to_list(grads, cfg)
    return _REMEMBERED[key]


_DEFAULTS = {name: p.default
             for fn in (decoder.decoder, decoder.build_model)
             for name, p in inspect.signature(fn).parameters.items()
             if p.default is not inspect.Parameter.empty}


def _canonical(arguments):
    """A dict of plain values as a string: sorted, and an argument at
    the builder's default is the argument left out."""
    return json.dumps({k: v for k, v in arguments.items()
                       if k not in _DEFAULTS or v != _DEFAULTS[k]},
                      sort_keys=True)


def _digest(arrays):
    """The bytes (with shape and dtype) of a dict or list of arrays."""
    h = hashlib.sha1()
    items = sorted(arrays.items()) if isinstance(arrays, dict) \
        else enumerate(arrays or ())
    for name, a in items:
        a = np.ascontiguousarray(a)
        h.update(f"{name}{a.shape}{a.dtype}".encode() + a.tobytes())
    return h.hexdigest()


def _frozen(value):
    if isinstance(value, dict):
        return {k: _frozen(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return type(value)(_frozen(v) for v in value)
    if isinstance(value, np.ndarray):
        value.setflags(write=False)
    return value
