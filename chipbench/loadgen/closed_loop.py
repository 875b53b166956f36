"""Closed loop: one producer submits back to back from a pool of distinct
requests drawn from the app's source and resident on the device, held
back only by the server's own backpressure (a full request queue blocks
``submit``).

Traffic keys: ``pool`` (distinct requests), ``check_every`` (one request
in this many is kept for the correctness check).
"""
from __future__ import annotations

import numpy as np


class Load:
    on_host = False

    def __init__(self, traffic: dict, source, seed: int):
        self.pool = source.device_pool(int(traffic["pool"]))
        self.rng = np.random.default_rng([seed, 0])

    def schedule(self, seconds: float):
        """Endless ``(due_s, pool_index)``; a closed loop has no due time.

        Each frame is drawn from the pool at random, not in a cycle: a
        cycle as long as the groups that one replica of a widened stage
        sees would give every replica the same frames again, and a replica
        that served its previous group's result would go unseen.
        """
        while True:
            for p in self.rng.integers(0, len(self.pool), 1024):
                yield None, int(p)

    def frame(self, pool_index: int):
        return self.pool[pool_index]

    def pool_frame(self, pool_index: int) -> np.ndarray:
        return np.asarray(self.pool[pool_index])

    def release(self) -> None:
        self.pool = None
