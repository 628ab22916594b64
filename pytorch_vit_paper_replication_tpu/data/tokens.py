"""A seeded packed-token source for the token model (``--model lm``).

There is no corpus here, so the trainer's token stream is made from a
seed, with the two properties of text that a training step can feel:
the ids are Zipf(1.0)-distributed over the vocabulary rows held (a few
rows of the embedding and the head take most of the gradient), and the
stream is predictable — each token's successor is one of ``fanout``
fixed candidates (a first-order successor table, itself drawn
Zipf(1.0)), so the next-token loss falls from ``log V`` towards ``log
fanout`` as the table is learned. Sequences are packed: every position
of every sequence is a real token, with no document boundary and no
padding.

:class:`TokenLoader` has the surface of ``data.DataLoader`` that the
trainer uses (``len``, iteration, ``epoch``, ``skip_next_batches``):
batch ``i`` of epoch ``e`` is a pure function of ``(seed, e, i)``, so a
resumed run sees the batches it would have seen.
"""

from __future__ import annotations

import numpy as np


class TokenSource:
    """Seeded sequences of ``seq_len + 1`` ids over ``vocab_size`` rows."""

    def __init__(self, seed: int, vocab_size: int, seq_len: int,
                 fanout: int = 4):
        self.seed, self.vocab_size, self.seq_len = seed, vocab_size, seq_len
        rng = np.random.default_rng([seed, 0x70C])
        weights = 1.0 / np.arange(1, vocab_size + 1)
        self._cdf = np.cumsum(weights / weights.sum())
        self._rows = rng.permutation(vocab_size).astype(np.int32)
        self._table = self.draw(rng, (vocab_size, fanout))

    def draw(self, rng, shape) -> np.ndarray:
        """Ids Zipf(1.0)-distributed over the rows (rank r has weight
        1/r; which row has which rank is a seeded permutation)."""
        ranks = np.searchsorted(self._cdf, rng.random(shape))
        return self._rows[np.minimum(ranks, self.vocab_size - 1)]

    def batch(self, batch_size: int, *key: int) -> dict:
        """``{"tokens": [B, T], "label": [B, T]}`` int32, ``label[t]`` the
        token after ``tokens[t]``; a pure function of ``key``."""
        rng = np.random.default_rng([self.seed, 0xBA7, *key])
        seq = np.empty((batch_size, self.seq_len + 1), np.int32)
        seq[:, 0] = self.draw(rng, batch_size)
        pick = rng.integers(0, self._table.shape[1],
                            size=(batch_size, self.seq_len))
        for t in range(self.seq_len):
            seq[:, t + 1] = self._table[seq[:, t], pick[:, t]]
        return {"tokens": seq[:, :-1].copy(), "label": seq[:, 1:].copy()}


class TokenLoader:
    """``steps`` batches an epoch from a :class:`TokenSource`."""

    def __init__(self, source: TokenSource, batch_size: int, steps: int,
                 *, stream: int = 0):
        self.source, self.batch_size, self.steps = source, batch_size, steps
        self.stream = stream
        self.epoch = 0
        self.skip_next_batches = 0
        self.dataset = range(steps * batch_size)

    def __len__(self) -> int:
        return self.steps

    def __iter__(self):
        first, self.skip_next_batches = self.skip_next_batches, 0
        epoch, self.epoch = self.epoch, self.epoch + 1
        for i in range(first, self.steps):
            yield self.source.batch(self.batch_size, self.stream, epoch, i)
