"""The one generator of training traffic: token batches from a seed.

A traffic file (``bench/traffic/<name>.json``) gives the batch per worker,
the sequence length, the number of data-parallel workers and the token
distribution. Token ids follow a Zipf law over a seeded permutation of the
vocabulary; every batch is a pure function of (seed, step), and every step
gets rows of its own.
"""

from __future__ import annotations

import numpy as np


class Generator:
    def __init__(self, traffic: dict, *, vocab: int, seed: int, global_batch: int):
        self.seq = int(traffic["seq_len"])
        self.rows = int(global_batch)
        self.seed = int(seed)
        rng = np.random.default_rng([self.seed, 0x70CE])
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        p = ranks ** -float(traffic["tokens"]["zipf_exponent"])
        self.cdf = np.cumsum(p / p.sum())
        self.perm = rng.permutation(vocab).astype(np.int32)

    def batch(self, step: int) -> dict:
        """{'inputs', 'labels', 'positions'}: int32 [global batch, seq]."""
        rng = np.random.default_rng([self.seed, 0xBA7C, int(step)])
        u = rng.random((self.rows, self.seq + 1))
        idx = np.minimum(np.searchsorted(self.cdf, u), len(self.perm) - 1)
        seq = self.perm[idx]
        positions = np.broadcast_to(np.arange(self.seq, dtype=np.int32),
                                    (self.rows, self.seq)).copy()
        return {"inputs": seq[:, :-1].copy(), "labels": seq[:, 1:].copy(),
                "positions": positions}
