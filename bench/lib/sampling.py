"""Seeded choices the drivers make: which answers get checked."""

from __future__ import annotations

import numpy as np


class Reservoir:
    """A uniform sample of at most ``k`` items of a stream of unknown
    length, drawn from ``rng`` (Algorithm R)."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k = k
        self.rng = rng
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.k:
            self.items[j] = item
