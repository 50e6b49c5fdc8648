"""The benchmark's own counts for the Mosaic kernels a cell runs, and
which rows of a traced step are kernels: for the readers in
`layer_metrics/` that share them.

Operations and bytes are what the ALGORITHM needs for the call, from
the cell's shapes; they do not move when the program's HLO or its cost
registry does.  A kernel's roofline time is the larger of operations
over the chip's bf16 peak and bytes over its HBM bandwidth
(`peaks.json`); its roofline share is that over the kernel's measured
self time, and cannot pass 100%.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import json
import os
import re

import step_anatomy

HERE = os.path.dirname(os.path.abspath(__file__))
FLASH_KERNELS = ("flash_fwd", "flash_dkv", "flash_dq")
RAGGED_DOT = "ragged_dot"
_PALLAS_SCOPE = re.compile(r"pallas_([A-Za-z0-9_]+)")


def kernel_of(row):
    """The Mosaic kernel a row of `step_anatomy.anatomy` ran, or None.
    The program names it (`kernel`, this PR on); for a program that
    does not, the name is read off the row's `op_name`: the
    `pallas_<name>` scope of `ops/pallas`, or the `ragged-dot-<mode>`
    the TPU compiler stamps on its own grouped matmul (its
    `ragged-dot-metadata` helper multiplies nothing and is no
    kernel)."""
    if row["bucket"] != "custom_call":
        return None
    if row.get("kernel"):
        return None if row["kernel"].endswith("_metadata") else row["kernel"]
    op_name = row.get("op_name") or ""
    hit = _PALLAS_SCOPE.search(op_name)
    if hit:
        return hit.group(1)
    if op_name.startswith("ragged-dot") and "metadata" not in op_name:
        return RAGGED_DOT
    return None


def kernel_ms_per_step(run, wanted=None):
    """Self time per step on chip 0 of the step program's Mosaic
    kernels (`wanted`: only those names, by prefix); 0.0 where none
    ran; None without the program's join."""
    a = step_anatomy.anatomy(run)
    if a is None:
        return None
    total = 0.0
    for r in a["step_rows"]:
        name = kernel_of(r)
        if name and (wanted is None or name.startswith(tuple(wanted))):
            total += r["self_s"]
    return 1e3 * total / a["steps"]


def peaks():
    """This device's row of `peaks.json`, or None."""
    import jax

    with open(os.path.join(HERE, "peaks.json")) as f:
        row = json.load(f).get(jax.devices()[0].device_kind)
    return row if isinstance(row, dict) else None


def roofline_ms(flops, nbytes, peak):
    return 1e3 * max(flops / peak["bf16_flops"],
                     nbytes / peak["hbm_bytes_per_s"])


def flash_attention_cost(config, cell):
    """(FLOP, bytes) of one step's causal flash attention, forward and
    backward, over all layers.  Seven matmuls of T x T x d_head a head:
    scores and values forward; scores again, dP, dV, dK, dQ backward
    (the scores are recomputed because that IS the algorithm; the
    second recomputation of the two-kernel backward is not counted).
    Causal: half the blocks, so T*T*d each in place of 2*T*T*d.  Bytes:
    q, k, v, o forward and q, k, v, o, do, dq, dk, dv backward, bf16,
    once each; the soft-max statistics are left out (T/d_head of one
    operand)."""
    n = cell["batch_per_chip"]
    t, d = cell["length"], config["hidden_size"]
    heads = config["num_attention_heads"]
    head_dim = d // heads
    flops = 7.0 * n * heads * t * t * head_dim
    nbytes = 12.0 * n * t * d * 2
    return (config["num_hidden_layers"] * flops,
            config["num_hidden_layers"] * nbytes)


def expert_matmul_cost(config, cell):
    """(FLOP, bytes) of one step's grouped expert matmuls over all
    layers: T*k rows through three matmuls forward and six backward
    (dX and dW of each), 2*rows*D*H each: a row meets ONE expert's
    weights.  Bytes: each matmul reads its two operands and writes its
    result once, bf16."""
    rows = (cell["batch_per_chip"] * cell["length"]
            * config["num_experts_per_tok"])
    d, h, e = (config["hidden_size"], config["intermediate_size"],
               config["num_experts"])
    flops = 9 * 2.0 * rows * d * h
    nbytes = 9 * 2.0 * (rows * d + rows * h + e * d * h)
    return (config["num_hidden_layers"] * flops,
            config["num_hidden_layers"] * nbytes)


def roofline_share(run, wanted, cost):
    """100 x the roofline time of `cost(config, cell)` over the
    measured time of the `wanted` kernels, per step; None where the
    kernels did not run or nothing can be read."""
    ms = kernel_ms_per_step(run, wanted)
    peak = peaks()
    if not ms or not peak:
        return None
    return 100.0 * roofline_ms(*cost(run["config"], run["cell"]), peak) / ms
