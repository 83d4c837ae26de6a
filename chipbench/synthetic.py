"""Seeded synthetic token batches: the training cells' traffic.

The same Markov process as the program's ``data/pipeline.SyntheticLM``
(each token has ``branch`` successors with Zipf weights, and a share
``reset`` of positions draws a fresh uniform token), kept here so that no
change to the program moves the yardstick.  Rows are drawn together, one
position at a time, so a 4-chip cell's 32 sequences cost milliseconds.
"""

from __future__ import annotations

import numpy as np


class MarkovTokens:
    """Batches ``[micro_steps, rows, seq]`` of tokens, targets and mask."""

    def __init__(self, vocab: int, seq: int, *, seed: int, branch: int,
                 skew: float, reset: float):
        self.vocab, self.seq, self.reset = vocab, seq, reset
        rng = np.random.default_rng([seed, 0])
        self.succ = rng.integers(0, vocab, (vocab, branch), dtype=np.int32)
        w = 1.0 / np.arange(1, branch + 1) ** skew
        self.cdf = np.cumsum(w / w.sum())
        self.seed = seed

    def rows(self, index: int, n: int) -> np.ndarray:
        """``n`` sequences of ``seq + 1`` tokens, the ``index``-th draw."""
        rng = np.random.default_rng([self.seed, 1, index])
        toks = np.empty((n, self.seq + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, n)
        pick = np.minimum(np.searchsorted(self.cdf, rng.random((n, self.seq))),
                          len(self.cdf) - 1)
        fresh = rng.integers(0, self.vocab, (n, self.seq), dtype=np.int32)
        resets = rng.random((n, self.seq)) < self.reset
        for t in range(self.seq):
            nxt = self.succ[toks[:, t], pick[:, t]]
            toks[:, t + 1] = np.where(resets[:, t], fresh[:, t], nxt)
        return toks

    def batch(self, index: int, global_batch: int, micro_steps: int
              ) -> dict[str, np.ndarray]:
        """The ``index``-th global batch; every row of every batch differs."""
        toks = self.rows(index, global_batch).reshape(
            micro_steps, global_batch // micro_steps, self.seq + 1)
        return {
            "tokens": np.ascontiguousarray(toks[..., :-1]),
            "targets": np.ascontiguousarray(toks[..., 1:]),
            "mask": np.ones(toks[..., 1:].shape, np.float32),
        }
