"""The paper's case-study workload (Sect. IV): cornerHarris_Demo.

OpenCV processing flow on a 1920×1080 frame:

    cvtColor → cornerHarris → normalize → convertScaleAbs

Pure-jnp "software" implementations below are the DB fallbacks (the paper's
"functions run on CPU"); ``repro.kernels.harris`` registers the Pallas
"hardware modules" for cvtColor / cornerHarris / convertScaleAbs — and, as
in the paper, **normalize has no hardware module** and stays in software.

The functions mirror the OpenCV semantics used by the demo:
  * cvtColor: BT.601 RGB→gray
  * cornerHarris(blockSize=2, ksize=3, k=0.04): Sobel gradients, box-filtered
    second-moment matrix, response R = det(M) − k·trace(M)²
  * normalize: NORM_MINMAX to [0, 255]
  * convertScaleAbs: |αx + β| saturated to [0, 255]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import (NodeCost, elementwise_cost, fused_cost,
                                  stencil_cost)
from repro.core.database import ModuleDatabase


# --------------------------------------------------------------------------- #
# software implementations (pure jnp)
# --------------------------------------------------------------------------- #
def cvt_color(img: jax.Array) -> jax.Array:
    """RGB [H, W, 3] → gray [H, W] float32 (BT.601)."""
    w = jnp.asarray([0.299, 0.587, 0.114], jnp.float32)
    # HIGHEST: a TPU otherwise runs this f32 contraction in bf16 passes
    return jnp.einsum("hwc,c->hw", img.astype(jnp.float32), w,
                      precision=jax.lax.Precision.HIGHEST)


def corner_harris(gray: jax.Array, block_size: int = 2, k: float = 0.04) -> jax.Array:
    """Sobel gradients → box-filtered second moments → Harris response.

    Border convention: the image is edge-padded ONCE by the full stencil
    reach (sobel + box), and both stages then run "valid" — identical to
    the Pallas module's halo-block scheme, so kernel vs. ref is exact.
    """
    H, W = gray.shape
    halo = 1 + block_size // 2
    g = jnp.pad(gray, ((halo, halo + block_size - 1),
                       (halo, halo + block_size - 1)),
                mode="edge").astype(jnp.float32)
    h1, w1 = H + 2 * halo - 2, W + 2 * halo - 2

    def sh(dy, dx):
        return g[dy:dy + h1, dx:dx + w1]

    dx = (sh(0, 2) + 2 * sh(1, 2) + sh(2, 2)
          - sh(0, 0) - 2 * sh(1, 0) - sh(2, 0))
    dy = (sh(2, 0) + 2 * sh(2, 1) + sh(2, 2)
          - sh(0, 0) - 2 * sh(0, 1) - sh(0, 2))
    ixx, iyy, ixy = dx * dx, dy * dy, dx * dy

    def box(a):
        out = jnp.zeros((H, W), jnp.float32)
        for by in range(block_size):
            for bx in range(block_size):
                out = out + a[by:by + H, bx:bx + W]
        return out

    sxx, syy, sxy = box(ixx), box(iyy), box(ixy)
    det = sxx * syy - sxy * sxy
    tr = sxx + syy
    return det - k * tr * tr


def normalize(x: jax.Array, alpha: float = 0.0, beta: float = 255.0) -> jax.Array:
    lo, hi = jnp.min(x), jnp.max(x)
    return (x - lo) / jnp.maximum(hi - lo, 1e-12) * (beta - alpha) + alpha


def convert_scale_abs(x: jax.Array, alpha: float = 1.0, beta: float = 0.0) -> jax.Array:
    return jnp.clip(jnp.abs(x * alpha + beta), 0.0, 255.0)


def numpy_reference(img, block_size: int = 2,
                    k: float = 0.04) -> tuple[np.ndarray, np.ndarray]:
    """Host float32 reference of the whole demo on one [H, W, 3] frame:
    ``(Harris response, final 0-255 image)``.

    Plain numpy, independent of the jnp and Pallas implementations, so a
    device run can be held to exact f32 arithmetic.  Same border
    convention and operation order as :func:`corner_harris`.
    """
    f32 = np.float32
    x = np.asarray(img, f32)
    gray = f32(0.299) * x[..., 0] + f32(0.587) * x[..., 1] \
        + f32(0.114) * x[..., 2]
    H, W = gray.shape
    halo = 1 + block_size // 2
    g = np.pad(gray, ((halo, halo + block_size - 1),
                      (halo, halo + block_size - 1)), mode="edge")
    h1, w1 = H + block_size - 1, W + block_size - 1

    def sh(dy, dx):
        return g[dy:dy + h1, dx:dx + w1]

    two = f32(2)
    dx = (sh(0, 2) + two * sh(1, 2) + sh(2, 2)
          - sh(0, 0) - two * sh(1, 0) - sh(2, 0))
    dy = (sh(2, 0) + two * sh(2, 1) + sh(2, 2)
          - sh(0, 0) - two * sh(0, 1) - sh(0, 2))
    ixx, iyy, ixy = dx * dx, dy * dy, dx * dy

    def box(a):
        out = np.zeros((H, W), f32)
        for by in range(block_size):
            for bx in range(block_size):
                out = out + a[by:by + H, bx:bx + W]
        return out

    sxx, syy, sxy = box(ixx), box(iyy), box(ixy)
    tr = sxx + syy
    resp = (sxx * syy - sxy * sxy) - f32(k) * tr * tr
    lo, hi = resp.min(), resp.max()
    norm = (resp - lo) / np.maximum(hi - lo, f32(1e-12)) * f32(255)
    return resp, np.clip(np.abs(norm), f32(0), f32(255))


# --------------------------------------------------------------------------- #
# the unmodified "binary" (paper Fig. 4 flow)
# --------------------------------------------------------------------------- #
def corner_harris_demo(lib):
    """Returns the demo app over an interposable Library — the user's code."""

    def app(img):
        gray = lib.cvtColor(img)
        resp = lib.cornerHarris(gray)
        norm = lib.normalize(resp)
        return lib.convertScaleAbs(norm)

    app.__name__ = "cornerHarris_Demo"
    return app


# --------------------------------------------------------------------------- #
# database registration (cost providers = the synthesis-report analog)
# --------------------------------------------------------------------------- #
def _c_cvt(shapes, dtypes, params) -> NodeCost:
    h, w = shapes[0][:2]
    return elementwise_cost(h * w, flops_per_el=5, bytes_per_el=4, n_operands=4)


def _c_harris(shapes, dtypes, params) -> NodeCost:
    h, w = shapes[0][:2]
    return stencil_cost(h, w, 1, taps=6 * 2 + 4 * 3 + 8)   # sobel+box+response


def _c_norm(shapes, dtypes, params) -> NodeCost:
    h, w = shapes[0][:2]
    return elementwise_cost(h * w, flops_per_el=4, bytes_per_el=4, n_operands=3)


def _c_csa(shapes, dtypes, params) -> NodeCost:
    h, w = shapes[0][:2]
    return elementwise_cost(h * w, flops_per_el=4, bytes_per_el=4, n_operands=2)


def _fused_harris_vmem(w: int, block_size: int = 2) -> int:
    """Scoped VMEM of the fused row-block kernel at its default row block
    (``kernels.harris.fused_vmem_bytes`` — the estimate the kernel's own
    row-block search checks against the limit it is compiled with)."""
    from repro.kernels import harris as hk
    return hk.fused_vmem_bytes(hk.ROW_BLOCK, w, block_size)


def _c_fused_pair(shapes, dtypes, params) -> NodeCost:
    """Synthesis-report analog for the fused cvtColor+cornerHarris module:
    the gray intermediate stays in VMEM, its HBM write+read disappears."""
    h, w = shapes[0][:2]
    bs = (params or {}).get("block_size", 2)
    fe = fused_cost([_c_cvt(shapes, dtypes, params),
                     _c_harris([(h, w)], dtypes, params)],
                    intermediate_bytes=4 * h * w,
                    vmem_required=_fused_harris_vmem(w, bs))
    return fe.cost


def _c_fused_mega(shapes, dtypes, params) -> NodeCost:
    h, w = shapes[0][:2]
    bs = (params or {}).get("block_size", 2)
    fe = fused_cost([_c_cvt(shapes, dtypes, params),
                     _c_harris([(h, w)], dtypes, params),
                     _c_csa([(h, w)], dtypes, params)],
                    intermediate_bytes=2 * (4 * h * w),   # gray + response
                    vmem_required=_fused_harris_vmem(w, bs))
    return fe.cost


def make_harris_db(with_hw: bool = True) -> ModuleDatabase:
    """Build the module database for the case study.

    ``with_hw`` registers the Pallas modules for the three functions the
    paper had HLS modules for; ``normalize`` never gets one (paper Table I).
    """
    db = ModuleDatabase("harris")
    db.register("cvtColor", software=cvt_color, cost_hw=_c_cvt, cost_sw=_c_cvt)
    db.register("cornerHarris", software=corner_harris, cost_hw=_c_harris,
                cost_sw=_c_harris)
    db.register("normalize", software=normalize, cost_sw=_c_norm)  # sw-only!
    db.register("convertScaleAbs", software=convert_scale_abs, cost_hw=_c_csa,
                cost_sw=_c_csa)
    if with_hw:
        # imported here: repro.kernels imports this module for its jnp
        # reference implementations
        from repro.kernels import harris as hk
        db.add_accelerated("cvtColor", hk.cvt_color)
        db.add_accelerated("cornerHarris", hk.corner_harris)
        db.add_accelerated("convertScaleAbs", hk.convert_scale_abs)
        # dedicated fused modules (single-pass mega-kernels): resolved by
        # the backend for fused nodes when the cost model accepts the
        # fusion.  In the demo chain `normalize` (sw-only) sits between
        # cornerHarris and convertScaleAbs, so the fusable run is the pair;
        # the 3-op mega-kernel serves normalize-free variants of the chain.
        db.register_fused(("cvtColor", "cornerHarris"),
                          hk.harris_fused_pair, cost_hw=_c_fused_pair)
        db.register_fused(("cvtColor", "cornerHarris", "convertScaleAbs"),
                          hk.harris_fused, cost_hw=_c_fused_mega)
    return db
