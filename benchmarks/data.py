"""The one general generator of training data: a traffic mix for a
training cell is the `data` block of `workloads/<cell>.json`
(`rows`, `seq_len`, `skew`), and the configuration gives the vocabulary.

Token ids are drawn row by row from a skewed unigram distribution
(`floor(vocab * u ** skew)`, u uniform: low ids are frequent, as in a
frequency-sorted vocabulary), so every row differs and the embedding's
gradient is sparse the way a real batch makes it. The rows are fed in
order (the cell's loader does not shuffle), so the check knows which
rows each step saw without asking the program.
"""
from __future__ import annotations

import numpy as np


def make_tokens(seed: int, rows: int, seq_len: int, vocab_size: int,
                skew: float = 2.0) -> np.ndarray:
    rng = np.random.Generator(np.random.Philox(key=int(seed)))
    u = rng.random((rows, seq_len), dtype=np.float32)
    return np.minimum((vocab_size * u ** skew).astype(np.int32),
                      vocab_size - 1)


def batch_rows(tokens: np.ndarray, step: int, batch_size: int) -> np.ndarray:
    """The rows step `step` (0-based) is fed: in order, wrapping round."""
    idx = (step * batch_size + np.arange(batch_size)) % len(tokens)
    return tokens[idx]
