"""Open-loop traffic with bursty arrivals: independent users who send on
their own schedule, whether or not the server keeps up.

Inter-arrival times are Gamma distributed with mean ``1 / rate_rps`` and
coefficient of variation ``cv`` (cv > 1 gives bursts; BurstGPT,
arXiv:2401.17644, fits cv about 2 to conversation traffic).  Prompt and
output lengths are lognormal (``median``, ``sigma``), rounded and
clipped to ``[min, max]``.

The schedule of arrivals and lengths is drawn from ``pool_seed`` alone,
so every run sends the same requests at the same times and the tails
measure the server, not the draw; ``--seed`` draws the prompt token ids.
A request is due at its scheduled arrival.

Parameters: ``rate_rps``, ``cv``, ``prompt`` and ``output`` (each
``{"median", "sigma", "min", "max"}``), ``pool_seed``, ``pool_size``.
"""

from __future__ import annotations

import numpy as np


def _lognormal(rng, spec: dict, n: int):
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Traffic:
    closed = False

    def __init__(self, params: dict, seed: int, vocab: int):
        n = int(params["pool_size"])
        pool = np.random.default_rng(int(params["pool_seed"]))
        k = 1.0 / float(params["cv"]) ** 2
        gaps = pool.gamma(k, 1.0 / (float(params["rate_rps"]) * k), n)
        plen = _lognormal(pool, params["prompt"], n)
        olen = _lognormal(pool, params["output"], n)
        self.rng = np.random.default_rng(seed)
        self.due = np.cumsum(gaps)
        self.plen, self.olen = plen, olen
        self.vocab = vocab
        self.params = params
        self.next = 0

    def _prompt(self, length: int):
        return self.rng.integers(0, self.vocab, int(length),
                                 dtype=np.int64).astype(np.int32)

    def warmup(self, max_batch: int):
        """One wave of mixed lengths, from the shortest to the longest
        prompt, with short outputs."""
        spec = self.params["prompt"]
        lens = np.linspace(spec["min"], spec["max"], max_batch).astype(int)
        return [[(-1 - i, self._prompt(n), 2) for i, n in enumerate(lens)]]

    def poll(self, elapsed: float, idle: bool):
        out = []
        while self.next < len(self.due) and self.due[self.next] <= elapsed:
            i = self.next
            out.append((i, self._prompt(self.plen[i]), int(self.olen[i]),
                        float(self.due[i])))
            self.next += 1
        return out

    def next_due(self):
        if self.next >= len(self.due):
            return None
        return float(self.due[self.next])

    def longest(self) -> int:
        """The most tokens a request can hold: prompt plus new tokens."""
        return int(self.params["prompt"]["max"] + self.params["output"]["max"])
