"""Harris case-study kernels — the predefined "hardware modules" (paper §IV).

Three Pallas TPU kernels mirror the three HLS modules the paper's database
held (``hls::cvtColor``, ``hls::cornerHarris``, ``hls::convertScaleAbs``);
``normalize`` deliberately has none, exactly like the paper's Table I.

TPU adaptation of the paper's streaming AXI modules:
  * the paper streams pixels over AXI with per-pixel pipelining; here each
    grid program owns a row-block in VMEM and the 8×128 VPU vectorizes
    across the row — block height plays the role of the AXI burst length.
  * cornerHarris needs a 2-row halo (3×3 Sobel then box filter); the host
    wrapper edge-pads the image and each program DMAs its rows + halo from
    the padded HBM ref into a VMEM scratch tile (``pltpu.sync_copy``),
    writing only its own rows — the analog of the paper's line-buffer BRAMs.
    The padded tile is rounded up to whole (8, 128) tiles so the copy is
    aligned; the stencil reads its shifted windows straight from the tile.
  * every kernel that takes the RGB frame (``cvt_color`` and the fused
    kernels) reads it as three colour planes, ``[..., 3, H, W]``.  XLA
    already stores an ``[..., H, W, 3]`` f32 frame plane-major in HBM, so
    the ``moveaxis`` is a relabelling of the same bytes.  A channel-last
    block would put the 3 channels on the 128 lanes: XLA would first copy
    the frame into a lane-padded channel-minor layout (~42x the frame's
    bytes) and the kernel would read that copy, and a channel-last halo
    window cannot be DMA'd at all (Mosaic refuses the padded slice).

Every kernel is compiled with ``vmem_limit_bytes=VMEM_BYTES``, the same
scoped-VMEM figure the cost model and the row-block search plan with, and
carries its function's name, which is the name of its ``tpu_custom_call``
in the compiled HLO.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import LANE, SUBLANE, VMEM_BYTES

from .autotune import AutotuneCache, autotune
from .backend import compiler_params, interpret_mode

ROW_BLOCK = 8          # rows per program (8 sublanes × 128-lane rows)
_F32 = 4               # element bytes of every tile below


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _gray(r: jax.Array, g: jax.Array, b: jax.Array) -> jax.Array:
    return 0.299 * r + 0.587 * g + 0.114 * b


def _scale_abs(x: jax.Array, alpha: float, beta: float) -> jax.Array:
    return jnp.clip(jnp.abs(x * alpha + beta), 0.0, 255.0)


def _halo_tile(rb: int, W: int, block_size: int) -> tuple[int, int, int]:
    """``(halo, rows, cols)`` of the VMEM tile one halo program reads.

    The stencil reaches ``halo`` pixels up/left and ``halo + block_size - 1``
    down/right; the tile covers ``rb`` output rows plus that reach, rounded
    up to whole (8, 128) tiles so the HBM→VMEM copy is tile-aligned.
    """
    halo = 1 + block_size // 2
    return (halo, _round_up(rb + 2 * halo, SUBLANE),
            _round_up(W + 2 * halo + block_size - 1, LANE))


def _leading_batch(call):
    """Make ``jax.vmap`` of ``call`` add a leading axis to its input.

    ``call`` accepts any leading batch axes and folds them into its grid.
    Pallas' own batching rule cannot lower a ``pl.ANY`` input on TPU, so
    the halo kernels take the batch axis themselves.
    """
    @jax.custom_batching.custom_vmap
    def fn(x):
        return call(x)

    @fn.def_vmap
    def _rule(axis_size, in_batched, x):
        return fn(x), True

    return fn


# --------------------------------------------------------------------------- #
# cvtColor: RGB → gray (elementwise, tiled rows of three colour planes)
# --------------------------------------------------------------------------- #
CVT_BLOCK_BYTES = 8 * 1024**2   # double-buffered in + out blocks of cvt_color


def cvt_row_block(H: int, W: int) -> int:
    """Rows per :func:`cvt_color` program: the largest multiple of 8 that
    divides ``H`` and keeps the double-buffered 3-plane input and 1-plane
    output blocks within ``CVT_BLOCK_BYTES``; ``H`` where no multiple of 8
    divides it.  Row blocks carry no padded lanes, so a larger block only
    saves per-program overhead (120 rows at 1080p, 144 at 720p)."""
    row_bytes = 2 * (3 + 1) * _round_up(W, LANE) * _F32
    fits = [rb for rb in range(SUBLANE, H + 1, SUBLANE)
            if H % rb == 0 and rb * row_bytes <= CVT_BLOCK_BYTES]
    if fits:
        return fits[-1]
    return SUBLANE if H % SUBLANE == 0 else H


def _cvt_kernel(rgb_ref, o_ref):
    rgb = rgb_ref[...].astype(jnp.float32)
    o_ref[...] = _gray(rgb[0], rgb[1], rgb[2])


def cvt_color(img: jax.Array, *, row_block: int | None = None,
              interpret: bool | None = None) -> jax.Array:
    """``[H, W, 3]`` RGB → ``[H, W]`` f32 gray, read as three colour planes
    (see the module docstring); ``row_block=None`` takes
    :func:`cvt_row_block`."""
    H, W, C = img.shape
    rb = cvt_row_block(H, W) if row_block is None else row_block
    rb = rb if H % rb == 0 else H
    planes = jnp.moveaxis(img, -1, -3)
    return pl.pallas_call(
        _cvt_kernel,
        grid=(H // rb,),
        in_specs=[pl.BlockSpec((C, rb, W), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((rb, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
        name="cvt_color",
    )(planes)


# --------------------------------------------------------------------------- #
# cornerHarris: Sobel + box-filtered second moments + response
# --------------------------------------------------------------------------- #
def _harris_response(tile, rb: int, W: int, block_size: int,
                     k: float) -> jax.Array:
    """Harris response of the ``rb × W`` block whose edge-padded
    neighbourhood starts at ``tile[0, 0]`` (a VMEM ref)."""
    h1, w1 = rb + block_size - 1, W + block_size - 1   # Sobel rows/cols used

    def win(dy, dx):                                   # shifted window
        return tile[pl.ds(dy, h1), pl.ds(dx, w1)]

    dx = (win(0, 2) + 2 * win(1, 2) + win(2, 2)
          - win(0, 0) - 2 * win(1, 0) - win(2, 0))
    dy = (win(2, 0) + 2 * win(2, 1) + win(2, 2)
          - win(0, 0) - 2 * win(0, 1) - win(0, 2))
    ixx, iyy, ixy = dx * dx, dy * dy, dx * dy

    def box(a):
        out = jnp.zeros((rb, W), jnp.float32)
        for by in range(block_size):
            for bx in range(block_size):
                out = out + a[by:by + rb, bx:bx + W]
        return out

    sxx, syy, sxy = box(ixx), box(iyy), box(ixy)
    det = sxx * syy - sxy * sxy
    tr = sxx + syy
    return det - k * tr * tr


def _harris_kernel(g_hbm, o_ref, tile, *, rb: int, W: int, block_size: int,
                   k: float):
    b, i = pl.program_id(0), pl.program_id(1)
    rows = pl.ds(pl.multiple_of(i * rb, SUBLANE), tile.shape[0])
    pltpu.sync_copy(g_hbm.at[b, rows], tile)
    o_ref[...] = _harris_response(tile, rb, W, block_size, k)


def _corner_harris(gray: jax.Array, *, block_size: int, k: float,
                   row_block: int, interpret: bool) -> jax.Array:
    *lead, H, W = gray.shape
    B = math.prod(lead)
    rb = row_block if H % row_block == 0 else H
    halo, R, Wt = _halo_tile(rb, W, block_size)
    # edge-pad on the host (the paper's modules see replicated borders too)
    pad = jnp.pad(gray.reshape(B, H, W).astype(jnp.float32),
                  ((0, 0), (halo, R - rb - halo), (halo, Wt - W - halo)),
                  mode="edge")
    kernel = functools.partial(_harris_kernel, rb=rb, W=W,
                               block_size=block_size, k=k)
    out = pl.pallas_call(
        kernel,
        grid=(B, H // rb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, rb, W), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((R, Wt), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="corner_harris",
    )(pad)
    return out.reshape(*lead, H, W)


def corner_harris(gray: jax.Array, block_size: int = 2, k: float = 0.04, *,
                  row_block: int = ROW_BLOCK,
                  interpret: bool | None = None) -> jax.Array:
    return _leading_batch(functools.partial(
        _corner_harris, block_size=block_size, k=k, row_block=row_block,
        interpret=interpret_mode(interpret)))(gray)


# --------------------------------------------------------------------------- #
# convertScaleAbs: |αx + β| saturated (elementwise, tiled rows)
# --------------------------------------------------------------------------- #
def _csa_kernel(x_ref, o_ref, *, alpha: float, beta: float):
    o_ref[...] = _scale_abs(x_ref[...].astype(jnp.float32), alpha, beta)


def convert_scale_abs(x: jax.Array, alpha: float = 1.0, beta: float = 0.0, *,
                      row_block: int = ROW_BLOCK,
                      interpret: bool | None = None) -> jax.Array:
    H, W = x.shape
    rb = row_block if H % row_block == 0 else H
    return pl.pallas_call(
        functools.partial(_csa_kernel, alpha=alpha, beta=beta),
        grid=(H // rb,),
        in_specs=[pl.BlockSpec((rb, W), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), jnp.float32),
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
        name="convert_scale_abs",
    )(x)


# --------------------------------------------------------------------------- #
# Fused mega-kernel: cvtColor → cornerHarris [→ convertScaleAbs] in ONE pass
# --------------------------------------------------------------------------- #
# The unfused chain bounces gray/response through HBM between pallas_calls
# (the paper's "intermediate data ... stored in the external memory").  Here
# each program DMAs its padded RGB row-block into VMEM, converts it to gray
# in a second VMEM scratch tile, runs Sobel + box + response on it, and
# (optionally) the convertScaleAbs epilogue — the gray and response tiles
# never leave VMEM.  On the paper's FPGA the fused cvtColor+cornerHarris
# module was "too slow to use"; on TPU the cost model accepts it because the
# eliminated HBM round-trips dominate (see repro.core.costmodel.fused_cost).
#
# Like cvt_color, the kernel reads the frame as three colour planes (module
# docstring): the moveaxis below relabels the plane-major frame XLA already
# holds, and each program DMAs a plane-aligned halo window of it.

def _fused_harris_kernel(img_hbm, o_ref, rgb_ref, gray_ref, *, rb: int,
                         W: int, block_size: int, k: float, with_csa: bool,
                         alpha: float, beta: float):
    b, i = pl.program_id(0), pl.program_id(1)
    rows = pl.ds(pl.multiple_of(i * rb, SUBLANE), rgb_ref.shape[1])
    pltpu.sync_copy(img_hbm.at[b, :, rows], rgb_ref)
    # cvtColor on the padded block; the gray tile lives in VMEM scratch and
    # is consumed in-place by the stencil below — no HBM round-trip.
    gray_ref[...] = _gray(rgb_ref[0], rgb_ref[1], rgb_ref[2])
    resp = _harris_response(gray_ref, rb, W, block_size, k)
    if with_csa:                                # fused epilogue, still VMEM
        resp = _scale_abs(resp, alpha, beta)
    o_ref[...] = resp


def fused_vmem_bytes(rb: int, W: int, block_size: int = 2) -> int:
    """Scoped VMEM one :func:`harris_fused` program needs: the RGB and gray
    tiles, ~8 stencil temporaries of a tile plane each, and the
    double-buffered output block."""
    _, R, Wt = _halo_tile(rb, W, block_size)
    plane = R * Wt * _F32
    out = 2 * _round_up(rb, SUBLANE) * _round_up(W, LANE) * _F32
    return (3 + 1 + 8) * plane + out


def _roofline_rb_score(rb: int, H: int, W: int, block_size: int) -> float:
    """Lower-is-better analytic score for a fused-kernel row block.

    HBM read amplification from the halo is ``rows / rb``; a small
    per-program launch term rewards larger blocks; blocks whose resident
    tiles would overflow the scoped VMEM limit are infeasible.
    """
    if fused_vmem_bytes(rb, W, block_size) > VMEM_BYTES:
        return float("inf")
    _, rows, _ = _halo_tile(rb, W, block_size)
    return (rows / rb) + 0.25 * (H / rb) / max(H, 1)


def fused_row_block(H: int, W: int, block_size: int = 2, *,
                    cache: AutotuneCache | None = None) -> int:
    """Autotuned row-block for :func:`harris_fused` (memoized on disk)."""
    cands = [rb for rb in (8, 16, 32, 64, 128, 256) if H % rb == 0]
    if not cands:
        return H
    res = autotune("harris_fused",
                   (H, W, "float32", block_size, VMEM_BYTES), cands,
                   lambda rb: _roofline_rb_score(rb, H, W, block_size),
                   cache=cache)
    return int(res.best)


def _harris_fused(img: jax.Array, *, block_size: int, k: float, alpha: float,
                  beta: float, with_csa: bool, row_block: int | None,
                  interpret: bool, cache: AutotuneCache | None) -> jax.Array:
    *lead, H, W, C = img.shape
    B = math.prod(lead)
    rb = (fused_row_block(H, W, block_size, cache=cache)
          if row_block is None else row_block)
    rb = rb if H % rb == 0 else H
    halo, R, Wt = _halo_tile(rb, W, block_size)
    planes = jnp.moveaxis(img.reshape(B, H, W, C).astype(jnp.float32), -1, 1)
    pad = jnp.pad(planes, ((0, 0), (0, 0), (halo, R - rb - halo),
                           (halo, Wt - W - halo)), mode="edge")
    kernel = functools.partial(_fused_harris_kernel, rb=rb, W=W,
                               block_size=block_size, k=k,
                               with_csa=with_csa, alpha=alpha, beta=beta)
    out = pl.pallas_call(
        kernel,
        grid=(B, H // rb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, rb, W), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, R, Wt), jnp.float32),
                        pltpu.VMEM((R, Wt), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="harris_fused" if with_csa else "harris_fused_pair",
    )(pad)
    return out.reshape(*lead, H, W)


def harris_fused(img: jax.Array, block_size: int = 2, k: float = 0.04,
                 alpha: float = 1.0, beta: float = 0.0, *,
                 with_csa: bool = True, row_block: int | None = None,
                 interpret: bool | None = None,
                 cache: AutotuneCache | None = None) -> jax.Array:
    """Single-pass fused Harris: cvtColor → cornerHarris [→ convertScaleAbs].

    One ``pallas_call`` over row blocks; gray and response tiles stay in
    scratch VMEM, with the stencil halo re-loaded from the edge-padded HBM
    input at row-block boundaries (overlapping reads between programs — the
    halo-exchange analog of the paper's line-buffer BRAMs).
    ``row_block=None`` asks the autotuner (persistent cache) for the block.
    """
    return _leading_batch(functools.partial(
        _harris_fused, block_size=block_size, k=k, alpha=alpha, beta=beta,
        with_csa=with_csa, row_block=row_block,
        interpret=interpret_mode(interpret), cache=cache))(img)


def harris_fused_pair(img: jax.Array, block_size: int = 2, k: float = 0.04,
                      **kwargs) -> jax.Array:
    """cvtColor+cornerHarris fused module (no epilogue) — the DB entry for
    the demo chain, where ``normalize`` separates cornerHarris from
    convertScaleAbs and limits the fusable run to two functions."""
    kwargs.pop("alpha", None)
    kwargs.pop("beta", None)
    return harris_fused(img, block_size, k, with_csa=False, **kwargs)
