"""The ImageNet ResNet family (`paddle_tpu.models.resnet`).

The counts below are the benchmark's own, from the architecture of
He et al. 2015 Table 1 as `benchmark/fluid/models/resnet.py` builds it
(stride on the first 1x1 convolution of a stage's first block).
"""

from __future__ import annotations

import numpy as np

# depth -> (blocks per stage, bottleneck?)
STAGES = {18: ([2, 2, 2, 2], False), 34: ([3, 4, 6, 3], False),
          50: ([3, 4, 6, 3], True), 101: ([3, 4, 23, 3], True),
          152: ([3, 8, 36, 3], True)}


def build(config):
    from paddle_tpu.models import resnet

    return resnet.build_model(**config["builder"])["loss"]


def make_batch(config, cell, rng):
    n = cell["batch_per_chip"] * cell["chips"]
    c, h, w = config["input"]
    # pixels: 8-bit values scaled to [0, 1], as decoded images are
    pixels = rng.integers(0, 256, (n, c, h, w), dtype=np.uint8)
    return {"data": pixels.astype(np.float32) / np.float32(255),
            "label": rng.integers(0, config["builder"]["class_dim"],
                                  (n, 1)).astype(np.int64)}


def forward_macs(config):
    """Multiply-adds of one image's forward pass through every
    convolution and the classifier."""
    blocks, bottleneck = STAGES[config["builder"]["depth"]]
    c_in, size, _ = config["input"]

    def conv(cin, cout, k, out):
        return out * out * cout * cin * k * k

    size //= 2                              # 7x7 stride 2
    macs = conv(c_in, 64, 7, size)
    size //= 2                              # 3x3 max pool stride 2
    cin = 64
    for stage, count in enumerate(blocks):
        ch = 64 * 2 ** stage
        for block in range(count):
            if block == 0 and stage > 0:
                size //= 2
            cout = ch * 4 if bottleneck else ch
            if cin != cout:
                macs += conv(cin, cout, 1, size)        # projection
            if bottleneck:
                macs += (conv(cin, ch, 1, size) + conv(ch, ch, 3, size)
                         + conv(ch, cout, 1, size))
            else:
                macs += conv(cin, ch, 3, size) + conv(ch, ch, 3, size)
            cin = cout
    return macs + cin * config["builder"]["class_dim"]


def train_flops(config, cell):
    """3 x forward convolution and classifier FLOP (2 per
    multiply-add); batch norm, pooling and elementwise work count
    zero."""
    return (3.0 * 2 * forward_macs(config)
            * cell["batch_per_chip"] * cell["chips"])


def units(config, cell):
    return {"images_per_s": {
        "per_step": cell["batch_per_chip"] * cell["chips"],
        "unit": "images/s"}}
