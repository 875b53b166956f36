"""Flash attention — Pallas TPU kernel (the DB's "hardware module" for attention).

Design (TPU-native, not a CUDA port):
  * grid = (batch·heads, T/BQ): one program owns a [BQ, hd] query block in
    VMEM and streams K/V blocks of [BK, hd] from the full-sequence refs,
    maintaining the online-softmax running (max, sum, accumulator) in f32
    registers — the HBM→VMEM→VREG hierarchy replaces the CUDA shared-memory
    staging of the original algorithm.
  * block sizes are MXU-aligned (multiples of 128 on the contracting dim,
    8×128 vector lanes); BQ/BK default 512/512 → VMEM working set
    ≈ BQ·hd + 2·BK·hd + BQ·BK f32 ≈ 1.4 MiB at hd=128, far under ~128 MiB.
  * causal + sliding-window masking are fused into the score block; fully
    masked K/V blocks are skipped via the loop bounds (window/causal prune).

Backward uses the standard recompute strategy via ``jax.custom_vjp``:
residuals are (q, k, v, o, lse); dq/dk/dv kernels re-stream blocks and
rebuild probabilities from the saved logsumexp — no [T, M] tensor is ever
materialized in either pass.

Validated against ``ref.reference_attention`` in interpret mode (CPU).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .backend import compiler_params, interpret_mode

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# forward kernel
# --------------------------------------------------------------------------- #
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                bq: int, bk: int, causal: bool, window: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # [bq, hd]
    M = k_ref.shape[1]
    nk = M // bk
    hd = q.shape[-1]

    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)

    def body(j, carry):
        acc, m_i, l_i = carry
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)   # [bk, hd]
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # [bq, bk]
        k_pos = j * bk + jax.lax.iota(jnp.int32, bk)
        d = q_pos[:, None] - k_pos[None, :]
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= d >= 0
        if window > 0:
            mask &= d < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return acc, m_new, l_new

    # causal prune: query block qi only sees k blocks j with
    # j*bk <= qi*bq + bq - 1 (fully-masked trailing blocks are skipped)
    j_hi = (qi * bq + bq - 1) // bk + 1 if causal else nk
    acc0 = (jnp.zeros((bq, hd), jnp.float32),
            jnp.full((bq,), NEG_INF, jnp.float32),
            jnp.zeros((bq,), jnp.float32))
    acc, m_i, l_i = jax.lax.fori_loop(0, j_hi, body, acc0)
    out = acc / jnp.maximum(l_i, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)
    lse_ref[0] = m_i + jnp.log(jnp.maximum(l_i, 1e-30))


def _fwd(q, k, v, *, causal, window, bq, bk, interpret):
    """q: [BH, T, hd], k/v: [BH, M, hd] → (o [BH, T, hd], lse [BH, T])."""
    BH, T, hd = q.shape
    M = k.shape[1]
    bq = min(bq, T)
    bk = min(bk, M)
    assert T % bq == 0 and M % bk == 0, (T, bq, M, bk)
    scale = 1.0 / np.sqrt(hd)
    grid = (BH, T // bq)
    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, M, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, M, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, T), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(q, k, v)


# --------------------------------------------------------------------------- #
# backward kernels (recompute from lse)
# --------------------------------------------------------------------------- #
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   bq: int, bk: int, causal: bool, window: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)                   # [bq, hd]
    lse = lse_ref[0]                                     # [bq]
    delta = delta_ref[0]                                 # [bq]
    M = k_ref.shape[1]
    nk = M // bk
    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        k_pos = j * bk + jax.lax.iota(jnp.int32, bk)
        d = q_pos[:, None] - k_pos[None, :]
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= d >= 0
        if window > 0:
            mask &= d < window
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                     # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))

    j_hi = (qi * bq + bq - 1) // bk + 1 if causal else nk
    dq = jax.lax.fori_loop(0, j_hi, body,
                           jnp.zeros_like(q))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *,
                    bq: int, bk: int, causal: bool, window: int, scale: float):
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                      # [bk, hd]
    v = v_ref[0].astype(jnp.float32)
    T = q_ref.shape[1]
    nq = T // bq
    k_pos = ki * bk + jax.lax.iota(jnp.int32, bk)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(i * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * bq, bq)]
        delta = delta_ref[0, pl.ds(i * bq, bq)]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
        q_pos = i * bq + jax.lax.iota(jnp.int32, bq)
        d = q_pos[:, None] - k_pos[None, :]
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= d >= 0
        if window > 0:
            mask &= d < window
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))
        return dk, dv

    i_lo = (ki * bk) // bq if causal else 0
    dk0 = jnp.zeros_like(k)
    dv0 = jnp.zeros_like(v)
    # q was pre-scaled in the loop body, so dk already carries the 1/sqrt(hd)
    dk, dv = jax.lax.fori_loop(i_lo, nq, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, *, causal, window, bq, bk, interpret):
    BH, T, hd = q.shape
    M = k.shape[1]
    bq = min(bq, T)
    bk = min(bk, M)
    scale = 1.0 / np.sqrt(hd)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, scale=scale),
        grid=(BH, T // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, M, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, M, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq), lambda b, i: (b, i)),
            pl.BlockSpec((1, bq), lambda b, i: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, hd), q.dtype),
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, scale=scale),
        grid=(BH, M // bk),
        in_specs=[
            pl.BlockSpec((1, T, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, T, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, T), lambda b, j: (b, 0)),
            pl.BlockSpec((1, T), lambda b, j: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, M, hd), k.dtype),
            jax.ShapeDtypeStruct((BH, M, hd), v.dtype),
        ],
        compiler_params=compiler_params(),
        interpret=interpret_mode(interpret),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# public entry: [B, T, H, hd] GQA attention with custom VJP
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool | None = None) -> jax.Array:
    """q: [B, T, H, hd]; k/v: [B, M, H, hd] (kv pre-expanded) → [B, T, H, hd].

    ``interpret=None`` follows the platform (:func:`interpret_mode`)."""
    o, _ = _flash_fwd(q, k, v, causal, window, bq, bk, interpret)
    return o


def _flash_fwd(q, k, v, causal, window, bq, bk, interpret):
    B, T, H, hd = q.shape
    M = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, M, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, M, hd)
    o, lse = _fwd(qf, kf, vf, causal=causal, window=window, bq=bq, bk=bk,
                  interpret=interpret)
    out = o.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, bq, bk, interpret, res, g):
    q, k, v, o, lse = res
    B, T, H, hd = q.shape
    M = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, M, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, M, hd)
    of = o.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    gf = g.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    dq, dk, dv = _bwd(qf, kf, vf, of, lse, gf, causal=causal, window=window,
                      bq=bq, bk=bk, interpret=interpret)
    un = lambda x, L: x.reshape(B, H, L, hd).transpose(0, 2, 1, 3)
    return un(dq, T), un(dk, M), un(dv, M)


flash_attention.defvjp(
    lambda q, k, v, causal, window, bq, bk, interpret:
        _flash_fwd(q, k, v, causal, window, bq, bk, interpret),
    _flash_bwd)
