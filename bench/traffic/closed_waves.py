"""Closed-loop traffic in waves: ``clients`` callers, each sending its next
request only when its previous one has finished.

Every request has the same prompt length and the same number of new
tokens, so the clients finish together and each new wave of ``clients``
requests arrives when the engine is idle.  A request is due when it is
sent.  Prompt token ids are uniform over the vocabulary, drawn from the
seed; every seed gets the same sizes.

Parameters: ``clients``, ``prompt_len``, ``max_new_tokens``.
"""

from __future__ import annotations

import numpy as np


class Traffic:
    closed = True

    def __init__(self, params: dict, seed: int, vocab: int):
        self.clients = int(params["clients"])
        self.prompt_len = int(params["prompt_len"])
        self.max_new = int(params["max_new_tokens"])
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.uid = 0

    def _wave(self, max_new: int):
        out = []
        for _ in range(self.clients):
            prompt = self.rng.integers(0, self.vocab, self.prompt_len,
                                       dtype=np.int64).astype(np.int32)
            out.append((self.uid, prompt, max_new))
            self.uid += 1
        return out

    def warmup(self, max_batch: int):
        """Waves that use every shape the run will use, with short
        outputs: -> list of waves of (uid, prompt, max_new)."""
        return [self._wave(2)]

    def poll(self, elapsed: float, idle: bool):
        """-> [(uid, prompt, max_new, due_elapsed)] to send now."""
        if not idle:
            return []
        return [r + (elapsed,) for r in self._wave(self.max_new)]

    def next_due(self):
        return None

    def longest(self) -> int:
        """The most tokens a request holds: prompt plus new tokens."""
        return self.prompt_len + self.max_new
