"""Plain numpy reference of the served app, the benchmark's own copy.

OpenCV's ``cornerHarris_Demo`` on one [H, W, 3] RGB frame: BT.601 gray,
Harris response (Sobel ksize 3, box ``block_size``, ``k``) with the image
edge-padded once by the full stencil reach, min-max normalize to 0-255,
convertScaleAbs.  It imports nothing of the program, so a change to the
program cannot move it.  ``dtype`` is the precision every operation is
rounded to: float32 is the reference; a narrower type (bfloat16) is the
control that a correct run must be told apart from.
"""
from __future__ import annotations

import numpy as np


def harris_demo(img, block_size: int = 2, k: float = 0.04,
                dtype=np.float32) -> np.ndarray:
    """The final 0-255 image of one frame, as float32."""
    t = np.dtype(dtype).type
    x = np.asarray(img, np.float32).astype(dtype)
    gray = t(0.299) * x[..., 0] + t(0.587) * x[..., 1] + t(0.114) * x[..., 2]
    H, W = gray.shape
    halo = 1 + block_size // 2
    g = np.pad(gray, ((halo, halo + block_size - 1),
                      (halo, halo + block_size - 1)), mode="edge")
    h1, w1 = H + block_size - 1, W + block_size - 1

    def sh(dy, dx):
        return g[dy:dy + h1, dx:dx + w1]

    two = t(2)
    dx = (sh(0, 2) + two * sh(1, 2) + sh(2, 2)
          - sh(0, 0) - two * sh(1, 0) - sh(2, 0))
    dy = (sh(2, 0) + two * sh(2, 1) + sh(2, 2)
          - sh(0, 0) - two * sh(0, 1) - sh(0, 2))
    ixx, iyy, ixy = dx * dx, dy * dy, dx * dy

    def box(a):
        out = np.zeros((H, W), dtype)
        for by in range(block_size):
            for bx in range(block_size):
                out = out + a[by:by + H, bx:bx + W]
        return out

    sxx, syy, sxy = box(ixx), box(iyy), box(ixy)
    tr = sxx + syy
    resp = (sxx * syy - sxy * sxy) - t(k) * tr * tr
    lo, hi = resp.min(), resp.max()
    norm = (resp - lo) / np.maximum(hi - lo, t(1e-12)) * t(255)
    out = np.abs(norm).astype(dtype).astype(np.float32)
    return np.clip(out, np.float32(0), np.float32(255))


def max_gap(served, ref) -> float:
    """Widest gap, in gray levels, between a served frame and its
    reference; a served frame of the wrong shape or not finite is inf."""
    got = np.asarray(served, np.float32)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - ref)))
