"""Open loop: ``streams`` independent cameras at ``fps`` frames/s each,
each periodic with a random phase.  A frame (a request of the app's
source) is uploaded from a pool of distinct host frames
(``jax.device_put``) at its due time, as a camera's decoded frame would
be, whether or not the server has caught up.

The phases are drawn from the mix's own ``phase_seed``, not from the
run's seed, so every run sends frames at the same instants: how the
phases bunch sets how requests share batches, and a seed that moved them
would change the work it measures.  The run's seed draws the frame pool
and which pool frame each arrival carries.

Traffic keys: ``streams``, ``fps``, ``phase_seed``, ``pool`` (distinct
host frames), ``check_every`` (one frame in this many is kept for the
check).
"""
from __future__ import annotations

import numpy as np


class Load:
    on_host = True

    def __init__(self, traffic: dict, source, seed: int):
        self.streams = int(traffic["streams"])
        self.fps = float(traffic["fps"])
        self.host_frames = source.host_pool(int(traffic["pool"]))
        self.rng = np.random.default_rng([seed, 1])
        self.phase = np.random.default_rng(int(traffic["phase_seed"])).random(
            self.streams) / self.fps

    def schedule(self, seconds: float) -> list[tuple[float, int]]:
        """``(due_s, pool_index)`` of every frame due in the window, in due
        order: ``streams * fps * seconds`` frames."""
        per = int(round(self.fps * seconds))
        due = (self.phase[:, None]
               + np.arange(per)[None, :] / self.fps).ravel()
        pick = self.rng.integers(0, len(self.host_frames), due.size)
        order = np.argsort(due, kind="stable")
        return [(float(due[i]), int(pick[i])) for i in order]

    def frame(self, pool_index: int):
        import jax

        return jax.device_put(self.host_frames[pool_index])

    def pool_frame(self, pool_index: int) -> np.ndarray:
        return self.host_frames[pool_index]

    def release(self) -> None:
        pass
