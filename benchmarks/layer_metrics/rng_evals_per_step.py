"""Copies of the random-bit generator that the step program executes
per step on chip 0 in the traced window: for every top-level
instruction executed, the generators in its body, nested fusions
included.  Over the Program's dropout ops it reads as evaluations per
mask: a mask generated once and stored is one, a generator that XLA
cloned into every fusion that reads the mask is one for each of them.
(XLA also merges sibling masks into one multi-output fusion: that is
still one evaluation a mask, which is why copies are counted and not
instructions.)

What counts as a generator is read off the instructions, not their
names: an `rng-bit-generator`, or a whole block of threefry2x32,
which is 20 rounds of add / rotate / xor on u32.  (The installed jax
lowers `threefry2x32` out of line, and the rounds' `op_name`s end in
`jit(_bernoulli)/jit(_uniform)/xor`: they name the sampler and the
primitive, never the generator.)  A 32-bit sampler adds one xor a
block to fold the two words, so up to 19 blocks in one computation
count right.  The step's scalar `fold_in`s sit unfused in the entry
computation, in no instruction's body, and do not count.

The walk uses what the program had before PR 25 (`observe.cost
.HloModule`, `observe.trace.hlo_protos`, the rows of `op_rows`), so it
reads the parent's trace too.
"""

import step_anatomy

META = {"layer": "ops", "unit": "count", "moves": "mfu",
        "source": "device_trace", "cells": None}

ROUNDS = 20     # of one threefry2x32 block, one u32 xor each
U32 = 8         # xla_data.proto PrimitiveType


def generators(comp):
    """Generators among one computation's own instructions."""
    own = sum(i.opcode == "rng-bit-generator" for i in comp.instructions)
    rounds = sum(i.opcode == "xor" and i.shape.element_type == U32
                 for i in comp.instructions)
    return own + rounds // ROUNDS


def rng_instructions(module):
    """`{name: generators}` for the entry computation's instructions
    that generate random bits: as an `rng-bit-generator` of their own,
    or in a computation they call, at any depth."""
    memo = {}

    def held(comp_id):
        if comp_id not in memo:
            comp = module.computations[comp_id]
            memo[comp_id] = generators(comp) + sum(
                held(c) for i in comp.instructions for c in i.called_ids)
        return memo[comp_id]

    counts = {i.name: (i.opcode == "rng-bit-generator")
              + sum(held(c) for c in i.called_ids)
              for i in module.entry.instructions}
    return {name: n for name, n in counts.items() if n}


def compute(run):
    a = step_anatomy.anatomy(run)
    if a is None:
        return None
    from paddle_tpu.observe import cost, trace

    proto = trace.hlo_protos(run["trace"]["path"]).get(a["step_module"])
    if proto is None:
        return None
    held = rng_instructions(cost.HloModule(proto))
    return sum(r["calls"] * held.get(r["instruction"], 0)
               for r in a["step_rows"]) / a["steps"]
