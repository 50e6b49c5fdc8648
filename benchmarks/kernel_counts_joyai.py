"""The benchmark's own counts for what the `joyai-8k` cell adds to a
step: latent attention's flash kernels, and which rows of a traced
step belong to the latent attention, the routed feed-forward layers
and the prediction module.  For the readers in `layer_metrics/` that
share them, beside `kernel_counts.py` (whose `kernel_of`, `peaks`,
`roofline_ms` and `roofline_share` they use).

Operations and bytes are what the three kernels EXECUTE for the cell's
shapes, by the algorithm and not by the program's HLO or its cost
registry: they do not move when those do.  A share of a roofline
cannot pass 100%.

The program lowers an op built under a `fluid.name_scope()` as
"<path>/<op_type>:<op_index>" and its trace join gives every row that
path (`name_scope`); a program from before that gives no such key, and
the readers then read nothing.

It sits beside `run.py`, not in `layer_metrics/`, where `run.py` takes
every `*.py` for a reader.
"""

from __future__ import annotations

import kernel_counts
import step_anatomy

FLASH_MLA_KERNELS = ("flash_mla_fwd", "flash_mla_dkv", "flash_mla_dq")
LATENT_ATTENTION = "latent_attention"       # name scopes of the builder
SHARED_EXPERT = "shared_expert"
MTP = "mtp"
EXPERT_OP = "moe_dropless"
BF16 = 2
# matmul lanes a (query, key) pair of one head: forward scores 192 and
# values 128; dk/dv scores again 192, dp 128, dv 128, dk 192; dq scores
# again 192, dp again 128, dq 192
LANES = {"flash_mla_fwd": 320, "flash_mla_dkv": 640, "flash_mla_dq": 512}


def blocks(config):
    """Blocks that run latent attention: the layers and the module."""
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def flash_mla_cost(config, cell):
    """(FLOP, bytes) of one step's three latent-attention kernels over
    all blocks.  FLOP: 2 x `LANES` a score pair of a head, causal at
    half the pairs (the kernels skip the blocks above the diagonal and
    run the ones it crosses whole: they execute a little more).  Bytes,
    bfloat16, once each a kernel: the unrotated queries, keys, the
    values, the output and their gradients at H x 128; the rotary
    queries and their gradient at H x 64; the rotary key and its
    gradient at 64, ONCE, not a head (the kernels never repeat it); the
    soft-max statistics are left out."""
    n, t = cell["batch_per_chip"], cell["length"]
    heads = config["num_attention_heads"]
    wide = n * t * heads * config["v_head_dim"]
    rotary = n * t * heads * config["qk_rope_head_dim"]
    key = n * t * config["qk_rope_head_dim"]
    flops = sum(LANES.values()) * float(n * heads * t * t)
    elements = ((4 * wide + rotary + key)               # forward
                + (7 * wide + rotary + 2 * key)         # dk, dv
                + (6 * wide + 2 * rotary + key))        # dq
    return blocks(config) * flops, blocks(config) * float(BF16 * elements)


def _in_scope(row, segment):
    scope = row.get("name_scope")
    return scope is not None and segment in scope.split("/")


def scope_ms_per_step(run, segment, op_types=(), kernels=()):
    """Self time per step on chip 0 of the step program's rows built
    under the name scope `segment` (anywhere in the path, forward and
    backward), plus those of the fluid ops `op_types` and the kernels
    `kernels` wherever they are scoped; None without the program's
    join or where its rows carry no name scope."""
    a = step_anatomy.anatomy(run)
    if a is None or not any("name_scope" in r for r in a["step_rows"]):
        return None
    seconds = sum(
        r["self_s"] for r in a["step_rows"]
        if _in_scope(r, segment) or r["op_type"] in op_types
        or (kernels and kernel_counts.kernel_of(r) in kernels))
    return 1e3 * seconds / a["steps"]
