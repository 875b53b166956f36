"""jit'd public wrappers around the Pallas kernels, and the rmsnorm/matmul
database rows.

The kernels always run: natively on TPU, in interpret mode on the CPU
(:func:`repro.kernels.backend.interpret_mode`).  There is no switch back
to the jnp references here — the module database's software rows are
where the paper's sw-fallback placement lives.
"""
from __future__ import annotations

import functools

import jax

from . import ref
from .harris import harris_fused as _harris_fused_kernel
from .rmsnorm import rmsnorm as _rmsnorm_kernel
from .rmsnorm import rmsnorm_matmul as _rmsnorm_matmul_kernel


@functools.partial(jax.jit, static_argnames=("block_size", "k", "alpha",
                                             "beta"))
def harris_response(img, block_size: int = 2, k: float = 0.04,
                    alpha: float = 1.0, beta: float = 0.0):
    """Single-call fused Harris chain (cvt → harris → csa)."""
    return _harris_fused_kernel(img, block_size, k, alpha, beta, row_block=8)


# --------------------------------------------------------------------------- #
# Database registration — the rmsnorm/matmul module family.  Mirrors the
# Harris registrations in repro.models.harris but for the transformer-side
# epilogue, so the fusion compiler generalizes beyond the paper's demo: the
# fused "rmsnorm+matmul" hw module is a first-class database row the
# backend resolves when the cost model accepts the fusion.
# --------------------------------------------------------------------------- #
def register_rmsnorm_matmul_modules(db) -> None:
    """Register rmsnorm / matmul (+ fused pair) into a ModuleDatabase."""
    from repro.core.costmodel import (NodeCost, elementwise_cost, fused_cost,
                                      matmul_cost)

    def _c_rms(shapes, dtypes, params) -> NodeCost:
        n, d = shapes[0]
        return elementwise_cost(n * d, flops_per_el=4, bytes_per_el=4,
                                n_operands=2)

    def _c_mm(shapes, dtypes, params) -> NodeCost:
        (n, d), (_, dout) = shapes[0], shapes[1]
        return matmul_cost(n, dout, d, bytes_per_el=4)

    def _c_fused(shapes, dtypes, params) -> NodeCost:
        n, d = shapes[0]
        dout = shapes[2][1] if len(shapes) > 2 else d
        inter = 4 * n * d                 # the normalized [n, d] intermediate
        fe = fused_cost([_c_rms([(n, d)], None, None),
                         _c_mm([(n, d), (d, dout)], None, None)],
                        intermediate_bytes=inter,
                        vmem_required=4 * (8 * d + d + d * dout + 8 * dout))
        return fe.cost

    def _sw_rms(x, scale):
        return ref.reference_rmsnorm(x, scale)

    def _sw_mm(x, w):
        import jax.numpy as jnp
        return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                       preferred_element_type=jnp.float32).astype(x.dtype)

    db.register("rmsnorm", software=_sw_rms,
                accelerated=lambda x, scale: _rmsnorm_kernel(x, scale),
                applicable=lambda *s: len(s[0]) == 2,
                cost_hw=_c_rms, cost_sw=_c_rms)
    db.register("matmul", software=_sw_mm,
                accelerated=_sw_mm,        # XLA's MXU matmul IS the hw module
                cost_hw=_c_mm, cost_sw=_c_mm)
    db.register_fused(
        ("rmsnorm", "matmul"),
        accelerated=lambda x, scale, w: _rmsnorm_matmul_kernel(x, scale, w),
        applicable=lambda *s: len(s[0]) == 2,
        cost_hw=_c_fused)
