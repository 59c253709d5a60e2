"""A uniform sample, drawn from the seed, of the answers a window produced."""
from __future__ import annotations

import numpy as np


class Reservoir:
    """Keeps `size` of the items offered, each offered item equally likely
    to be kept, with draws from the run's seed (reservoir sampling)."""

    def __init__(self, size: int, seed: int):
        self.size = int(size)
        self.rng = np.random.default_rng(seed)
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.size:
            self.items[j] = item
