"""The training cells' inputs: one batch of token sequences per step, from
the run's seed and the step, in natural order (position 0 first).

The stream is that of the program's synthetic source
(``repro.data.pipeline.SyntheticLM``), copied here so that the benchmark
and its reference make their inputs without the program: runs of 8 equal
tokens drawn uniformly from the vocabulary, each token replaced by a fresh
uniform draw with probability 0.1, and the labels the tokens shifted left
by one (the last label wraps to the first token). Every seed gets the same
shapes; the seed and the step pick the tokens, so every step's rows
differ.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

RUN = 8          # tokens per repeated draw
FLIP = 0.1       # share of tokens replaced by a fresh draw


def batch(seed: int, step: int, global_batch: int, seq_len: int,
          vocab: int) -> Dict[str, np.ndarray]:
    """``tokens`` and ``labels``, each (global_batch, seq_len) int32."""
    rng = np.random.Generator(np.random.Philox(key=seed + step))
    base = rng.integers(0, vocab, size=(global_batch, seq_len // RUN),
                        dtype=np.int64)
    toks = np.repeat(base, RUN, axis=1)
    noise = rng.integers(0, vocab, size=(global_batch, seq_len))
    flip = rng.random((global_batch, seq_len)) < FLIP
    toks = np.where(flip, noise, toks).astype(np.int32)
    labels = np.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
    return {"tokens": toks, "labels": labels}


class Source:
    """A data source for the program's prefetcher (``get_batch(step)``):
    the benchmark's batches, laid out along the sequence by the
    permutation ``perm`` the program's plan asks for."""

    def __init__(self, seed: int, global_batch: int, seq_len: int,
                 vocab: int, perm: np.ndarray):
        self.seed, self.b, self.s, self.vocab = seed, global_batch, seq_len, \
            vocab
        self.perm = np.asarray(perm)

    def get_batch(self, step: int) -> Dict[str, np.ndarray]:
        nat = batch(self.seed, step, self.b, self.s, self.vocab)
        return {k: np.take(v, self.perm, axis=1) for k, v in nat.items()}
