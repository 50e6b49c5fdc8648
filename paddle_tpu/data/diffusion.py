"""Noising for block-diffusion training (Arriola et al.,
arXiv:2503.09573, section 3 and its vectorised training), on the host,
in the reader, as a BERT reader masks its batch: the program draws
nothing, so a batch's loss is a function of the weights alone.

A sequence x_0 of L tokens is cut into L / B blocks.  Block b draws
t_b ~ U(t_min, 1) and each of its positions is masked independently
with probability t_b: x_t[i] is the mask id where masked, x_0[i]
elsewhere.  Under the linear schedule alpha_t = 1 - t the loss weight
-alpha'_t / (1 - alpha_t) is 1 / t_b on a masked position and 0 on
the others.  The program (`models/decoder.py`, `objective=
"block_diffusion"`) reads ONE sequence of 2 L rows, x_0 then x_t.
"""

from __future__ import annotations

import numpy as np


def block_diffusion_feeds(x0, block_length, mask_id, rng, t_min=1e-3):
    """The feeds of one batch: `tokens` (N, 2 L) int64, the clean ids
    and after them the noised ones; `labels` (N, L) int64, x_0 itself
    (position i of the noised half predicts x_0[i], no shift);
    `loss_weights` (N, L) float32, 1 / t_b where position i was masked
    and 0 elsewhere.  `x0` (N, L) integer ids, none of them `mask_id`;
    `rng` a `numpy.random.Generator`: the same generator state gives
    the same feeds."""
    x0 = np.asarray(x0)
    if x0.ndim != 2 or x0.shape[1] % block_length:
        raise ValueError(f"x0 {x0.shape} is not (N, L) with L a whole "
                         f"number of blocks of {block_length}")
    if not 0.0 < t_min <= 1.0:
        raise ValueError(f"t_min {t_min} is not in (0, 1]")
    if (x0 == mask_id).any():
        raise ValueError(f"x0 holds the mask id {mask_id}")
    n, length = x0.shape
    t = rng.uniform(t_min, 1.0, size=(n, length // block_length))
    t = np.repeat(t, block_length, axis=1)              # a position's t_b
    masked = rng.random(size=(n, length)) < t
    xt = np.where(masked, mask_id, x0)
    return {"tokens": np.concatenate([x0, xt], axis=1).astype(np.int64),
            "labels": x0.astype(np.int64),
            "loss_weights": np.where(masked, 1.0 / t, 0.0)
            .astype(np.float32)}
