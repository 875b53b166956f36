"""Seeded frames: the benchmark's own generator.

Float32 RGB frames with values in [0, 255), drawn on the device from the
seed in one jitted call (the program's demo frames are drawn the same
way: one ``jax.random.uniform`` per split key, times 255).
"""
from __future__ import annotations

import functools

import numpy as np


def key_seed(seed: int) -> int:
    """Any whole number (beyond 32 bits too) folded to a 31-bit PRNG seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _pool_fn(n: int, h: int, w: int):
    import jax

    def draw(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), n)
        return tuple(jax.random.uniform(keys[i], (h, w, 3)) * 255
                     for i in range(n))

    return jax.jit(draw)


def device_pool(n: int, h: int, w: int, seed: int) -> list:
    """``n`` distinct [h, w, 3] float32 frames on the default device."""
    return list(_pool_fn(n, h, w)(key_seed(seed)))


def host_pool(n: int, h: int, w: int, seed: int) -> list:
    """The same frames as :func:`device_pool`, copied to host memory."""
    import jax

    frames = device_pool(n, h, w, seed)
    host = [np.asarray(f) for f in jax.device_get(frames)]
    del frames
    return host
