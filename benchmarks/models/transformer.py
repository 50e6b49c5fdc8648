"""The encoder-decoder Transformer family (`paddle_tpu.models.transformer`).

The counts below are the benchmark's own, from the configuration's
shapes: they do not move when the program's HLO does.
"""

from __future__ import annotations

import numpy as np


def build(config):
    """Build the training graph under the caller's program guard and
    return the loss variable."""
    from paddle_tpu.models import transformer

    return transformer.build_model(**config["builder"])["loss"]


def _token_probs(vocab):
    # ids 1..vocab-1 with Zipf-like frequencies, as words in text have:
    # a step that trains learns them within tens of steps, so the loss
    # falls with margin at any learning rate that does not diverge
    p = 1.0 / (np.arange(1, vocab) + 10.0)
    return p / p.sum()


def make_batch(config, cell, rng):
    """One global batch as the numpy feed of `Executor.run`: `length`
    real positions in every sentence, padded to the program's
    `max_length`; the label is the target shifted by one, as in NMT."""
    b = config["builder"]
    n = cell["batch_per_chip"] * cell["chips"]
    length, max_len = cell["length"], b["max_length"]
    if not 0 < length <= max_len:
        raise ValueError(f"length {length} outside (0, {max_len}]")

    def draw(vocab, width):
        ids = rng.choice(vocab - 1, size=(n, width),
                         p=_token_probs(vocab)) + 1
        out = np.zeros((n, max_len + width - length), np.int64)
        out[:, :width] = ids
        return out

    src = draw(b["src_vocab_size"], length)
    trg = draw(b["trg_vocab_size"], length + 1)
    lens = np.full((n,), length, np.int32)
    return {"src_word": src, "trg_word": trg[:, :-1].copy(),
            "lbl_word": trg[:, 1:].copy(), "src_len": lens,
            "trg_len": lens.copy()}


def train_flops(config, cell):
    """Model FLOP of one training step over the global batch: forward
    and backward = 3 x the forward matmul FLOP (2 per multiply-add);
    causal self-attention at half; embedding lookups, elementwise work,
    softmax, layer norm and recomputation count zero."""
    b = config["builder"]
    d, dff, layers = b["d_model"], b["d_inner_hid"], b["n_layer"]
    s = t = cell["length"]          # source and target positions
    proj = 2 * d * d                # one d x d projection of one token
    ffn = 2 * 2 * d * dff           # both FFN matmuls of one token
    enc = s * (4 * proj + ffn) + 2 * 2 * s * s * d
    dec = (t * (4 * proj + ffn)             # self q,k,v,out + FFN
           + 2 * 2 * t * t * d / 2          # causal scores and values
           + t * 2 * proj + s * 2 * proj    # cross q,out / k,v
           + 2 * 2 * t * s * d)             # cross scores and values
    logits = t * 2 * d * b["trg_vocab_size"]
    forward = layers * (enc + dec) + logits
    return 3.0 * forward * cell["batch_per_chip"] * cell["chips"]


def units(config, cell):
    """What one step completes: target-side tokens that enter the loss
    (the NMT convention), summed over chips."""
    n = cell["batch_per_chip"] * cell["chips"]
    return {"tokens_per_s": {"per_step": n * cell["length"],
                             "unit": "tokens/s"}}
