"""The one traffic generator: a closed loop from a mix's parameters.

A mix file (``chipbench/traffic/<name>.json``) names the public source of
its lengths and gives, for prompt and output lengths each, a log-normal
distribution (``median``, ``sigma``) with the bounds the chip forces
(``min``, ``max``). A block of ``strata`` requests takes the midpoints of
equal-probability strata of each distribution (quantiles (i + 0.5) /
strata), and pairs prompt stratum i with output stratum ``pairing[i]``.
So every seed serves the same set of sizes, in the same proportion in
any stretch of requests: the block repeats in one fixed order, from a
starting point the seed draws. Prompt token ids come from the seed and
the request's index.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Iterator

import numpy as np


def strata(dist: dict[str, Any], k: int) -> list[int]:
    """The midpoints of ``k`` equal-probability strata of a log-normal
    length distribution, rounded and clipped to ``[min, max]``."""
    normal = statistics.NormalDist()
    out = []
    for i in range(k):
        z = normal.inv_cdf((i + 0.5) / k)
        n = round(float(dist["median"]) * math.exp(float(dist["sigma"]) * z))
        out.append(int(min(max(n, int(dist["min"])), int(dist["max"]))))
    return out


class ClosedLoop:
    """One client's requests: (index, prompt tokens (batch, length),
    new tokens)."""

    def __init__(self, mix: dict[str, Any], vocab: int, seed: int) -> None:
        if mix.get("loop") != "closed" or mix.get("clients") != 1:
            raise ValueError("only a closed loop of one client is served: "
                             f"got loop={mix.get('loop')!r}, "
                             f"clients={mix.get('clients')!r}")
        self.batch = int(mix["batch"])
        k = int(mix["strata"])
        prompts, outputs = strata(mix["prompt"], k), strata(mix["output"], k)
        pairing = [int(j) for j in mix["pairing"]]
        if sorted(pairing) != list(range(k)):
            raise ValueError(f"pairing {pairing} is not a permutation of "
                             f"the {k} output strata")
        self.block = [(prompts[i], outputs[pairing[i]]) for i in range(k)]
        if min(n for _, n in self.block) < 2:
            raise ValueError("every request needs 2 new tokens or more: "
                             "the time per output token is taken after "
                             "the first")
        self.vocab = int(vocab)
        self.seed = int(seed)

    @property
    def longest(self) -> tuple[int, int]:
        """The (prompt, new tokens) of the block with the largest cache."""
        return max(self.block, key=lambda r: (r[0] + r[1], r[0]))

    def prompt(self, index: int, length: int, stream: int = 1) -> np.ndarray:
        rng = np.random.default_rng([self.seed, stream, index])
        return rng.integers(0, self.vocab, (self.batch, length), np.int32)

    def schedule(self) -> Iterator[tuple[int, int]]:
        """(prompt length, new tokens) in serving order, without end."""
        i = int(np.random.default_rng([self.seed, 2]).integers(
            len(self.block)))
        while True:
            yield self.block[i % len(self.block)]
            i += 1

    def requests(self) -> Iterator[tuple[int, np.ndarray, int]]:
        for i, (length, new) in enumerate(self.schedule()):
            yield i, self.prompt(i, length), new

    def warmup(self) -> list[tuple[np.ndarray, int]]:
        """One request of each shape of the block, with ids of their own."""
        return [(self.prompt(j, n, stream=3), new)
                for j, (n, new) in enumerate(sorted(self.block))]
