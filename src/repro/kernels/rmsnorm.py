"""Fused RMSNorm — Pallas kernel (row-tiled, f32 accumulation in VMEM)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import compiler_params, interpret_mode

ROW_BLOCK = 256


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # [rb, d]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * (1.0 + s_ref[...].astype(jnp.float32))
                  ).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6, *,
            row_block: int = ROW_BLOCK,
            interpret: bool | None = None) -> jax.Array:
    """x: [N, d] (flatten leading dims first), scale: [d]."""
    N, d = x.shape
    rb = row_block if N % row_block == 0 else N
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(N // rb,),
        in_specs=[pl.BlockSpec((rb, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
        name="rmsnorm",
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(x, scale)


# --------------------------------------------------------------------------- #
# Fused rmsnorm + matmul epilogue (normalized rows never round-trip to HBM)
# --------------------------------------------------------------------------- #
def _rmsnorm_matmul_kernel(x_ref, s_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # [rb, d]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = (x * jax.lax.rsqrt(var + eps)
         * (1.0 + s_ref[...].astype(jnp.float32)))
    o_ref[...] = jnp.dot(y, w_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def rmsnorm_matmul(x: jax.Array, scale: jax.Array, w: jax.Array,
                   eps: float = 1e-6, *, row_block: int = ROW_BLOCK,
                   interpret: bool | None = None) -> jax.Array:
    """Fused ``rmsnorm(x, scale) @ w``; x: [N, d], scale: [d], w: [d, out].

    The normalized activations are produced and consumed inside one
    ``pallas_call`` per row block — unfused, the [N, d] normalized tensor is
    written to and re-read from HBM between the two ops, which the roofline
    cost model charges as the dominant term for memory-bound d.
    """
    N, d = x.shape
    d2, dout = w.shape
    if d2 != d:
        raise ValueError(f"rmsnorm_matmul: x has d={d} but w has d={d2}")
    rb = row_block if N % row_block == 0 else N
    return pl.pallas_call(
        functools.partial(_rmsnorm_matmul_kernel, eps=eps),
        grid=(N // rb,),
        in_specs=[pl.BlockSpec((rb, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((d, dout), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rb, dout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, dout), x.dtype),
        name="rmsnorm_matmul",
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(x, scale, w)
