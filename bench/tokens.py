"""The one generator of training traffic, driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) fixes the shape of every
step: global batch, sequence length, mesh, ZeRO++ variant, and the
optimizer the step applies (read by ``bench/run.py`` and the reference).  The token stream is an order-1 Markov chain
with a skewed, seeded transition table (the same construction as the
program's ``data/synthetic.py``, copied here so the yardstick stays fixed):
a learnable distribution whose every row and every step differ.  Each
step's batch is a pure function of (seed, step), so the reference sees
exactly the rows the program was fed.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

SEED_MASK = (1 << 64) - 1


class Tokens:
    """Markov-chain token batches for one run: ``batch(step)`` gives
    ``{"tokens", "targets"}``, int32, of shape (global_batch, seq_len)."""

    def __init__(self, traffic: Dict, vocab: int, seed: int):
        self.batch_size = int(traffic["global_batch"])
        self.seq_len = int(traffic["seq_len"])
        self.vocab = int(vocab)
        self.seed = int(seed) & SEED_MASK
        data = traffic.get("data", {})
        k = min(int(data.get("branching", 4)), self.vocab)
        alpha = float(data.get("alpha", 0.6))
        rng = np.random.default_rng([self.seed, 0])
        self._succ = rng.integers(0, self.vocab, size=(self.vocab, k))
        p = rng.dirichlet(np.full(k, alpha), size=self.vocab)
        self._cum = np.cumsum(p, axis=1)

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, 1, int(step)])
        b, s = self.batch_size, self.seq_len
        out = np.empty((b, s + 1), np.int32)
        state = rng.integers(0, self.vocab, size=b)
        u = rng.random((s + 1, b))
        for t in range(s + 1):
            out[:, t] = state
            nxt = (u[t, :, None] < self._cum[state]).argmax(axis=1)
            state = self._succ[state, nxt]
        return {"tokens": out[:, :-1].copy(), "targets": out[:, 1:].copy()}
