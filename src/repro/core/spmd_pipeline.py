"""SPMD token pipeline — the paper's TBB pipeline at pod scale.

Courier-FPGA's deployed artifact is a *token-based software pipeline*: each
stage (a group of functions, some on CPU, some as FPGA modules) processes
token k while the upstream stage already works on token k+1, intermediate
data moving through external memory.  On a TPU pod the native equivalent is
microbatch pipeline parallelism executed inside ``shard_map``:

    token            = microbatch
    pipeline stage   = contiguous group of model layers (Courier partition)
    TBB thread pool  = mesh devices along the ``stage`` axis
    DDR3 hand-off    = ``jax.lax.ppermute`` over the ICI
    token pool       = microbatches in flight (fill/drain schedule)

The stage boundaries come from the same Pipeline Generator partitioners
(paper policy / optimal DP) used for the host pipeline, so the paper's
balanced-partition idea drives pod-scale layer placement.  Stages may hold
*unequal* layer counts (balanced by cost, not cardinality): per-stage layer
stacks are padded to the maximum and masked with ``lax.cond``.

The whole executor is differentiable — ``jax.grad`` through ``scan`` +
``ppermute`` yields the reverse-permuted backward pipeline automatically,
so the same artifact trains (fwd+bwd) and serves (fwd).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

__all__ = ["stack_stage_params", "stage_apply", "spmd_pipeline_fn",
           "pipeline_microbatches"]


# --------------------------------------------------------------------------- #
# Parameter staging
# --------------------------------------------------------------------------- #
def stack_stage_params(layer_params: Any, boundaries: Sequence[int]) -> tuple[Any, jax.Array]:
    """[L, ...] layer-stacked params → ([S, Lmax, ...] padded, lengths[S]).

    ``boundaries`` are stage start indices, e.g. [0, 3, 8] for L=10 gives
    stages of 3, 5 and 2 layers.  Padding layers are zeros and are skipped
    at run time via the lengths mask.
    """
    bounds = list(boundaries)
    L = jax.tree.leaves(layer_params)[0].shape[0]
    if bounds[0] != 0:
        raise ValueError("boundaries must start at 0")
    ends = bounds[1:] + [L]
    lengths = np.array([e - b for b, e in zip(bounds, ends)], dtype=np.int32)
    if (lengths <= 0).any():
        raise ValueError(f"empty stage in boundaries {bounds} for L={L}")
    lmax = int(lengths.max())

    def stack(x):
        segs = []
        for b, e in zip(bounds, ends):
            seg = x[b:e]
            pad = [(0, lmax - (e - b))] + [(0, 0)] * (x.ndim - 1)
            segs.append(jnp.pad(seg, pad))
        return jnp.stack(segs)          # [S, Lmax, ...]

    return jax.tree.map(stack, layer_params), jnp.asarray(lengths)


# --------------------------------------------------------------------------- #
# One stage = masked scan over its (padded) layers
# --------------------------------------------------------------------------- #
def stage_apply(block_fn: Callable[[Any, jax.Array], jax.Array],
                stage_params: Any, length: jax.Array, x: jax.Array) -> jax.Array:
    """Apply ``length`` layers of the padded [Lmax, ...] stack to x."""
    lmax = jax.tree.leaves(stage_params)[0].shape[0]

    def body(h, inp):
        lp, i = inp
        h2 = jax.lax.cond(i < length, lambda: block_fn(lp, h), lambda: h)
        return h2, None

    h, _ = jax.lax.scan(body, x, (stage_params, jnp.arange(lmax)))
    return h


# --------------------------------------------------------------------------- #
# The pipeline step loop (runs INSIDE shard_map over ``axis_name``)
# --------------------------------------------------------------------------- #
def spmd_pipeline_fn(block_fn: Callable[[Any, jax.Array], jax.Array],
                     n_stages: int, axis_name: str = "stage") -> Callable:
    """Build fn(stage_params, lengths, xs) for use inside shard_map.

    Per-device inputs:
      stage_params — this device's stage stack, leaves [1, Lmax, ...]
      lengths      — [S] per-stage layer counts (replicated)
      xs           — [M, mb, ...] all microbatch tokens (replicated)

    Returns out_buf [M, mb, ...]; only the *last* stage's buffer holds the
    pipeline outputs (use out_specs P(axis) and slice [-1] outside, or wrap
    with :func:`pipeline_microbatches`).
    """

    def fn(stage_params, lengths, xs):
        stage = jax.lax.axis_index(axis_name)
        params = jax.tree.map(lambda a: a[0], stage_params)   # drop stage dim
        my_len = lengths[stage]
        M = xs.shape[0]
        T = M + n_stages - 1
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def step(carry, t):
            recv, out_buf = carry
            # stage 0 admits token t (serial_in_order entry)
            tok = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, M - 1), axis=0, keepdims=False)
            x = jnp.where(stage == 0, tok, recv)
            y = stage_apply(block_fn, params, my_len, x)
            # last stage retires token t-(S-1) (serial_in_order exit)
            oidx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            upd = jax.lax.dynamic_update_slice_in_dim(
                out_buf, y[None], oidx, axis=0)
            emit = (stage == n_stages - 1) & (t >= n_stages - 1)
            out_buf = jnp.where(emit, upd, out_buf)
            # hand token to the next stage over the ICI (the DDR3 analog)
            recv = jax.lax.ppermute(y, axis_name, fwd) if n_stages > 1 else y
            return (recv, out_buf), None

        zero = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        (_, out_buf), _ = jax.lax.scan(step, (zero, out0), jnp.arange(T))
        return out_buf

    return fn


# --------------------------------------------------------------------------- #
# Mesh-level convenience wrapper
# --------------------------------------------------------------------------- #
def pipeline_microbatches(mesh, block_fn: Callable, layer_params: Any,
                          boundaries: Sequence[int], xs: jax.Array,
                          axis_name: str = "stage",
                          batch_axis: str | None = None) -> jax.Array:
    """Run [M, mb, ...] microbatches through the staged pipeline on ``mesh``.

    ``layer_params`` leaves are [L, ...]; ``boundaries`` come from a
    PipelinePlan (stage start layer indices).  Returns [M, mb, ...] outputs.
    When ``batch_axis`` is given, the microbatch dim of ``xs`` is sharded
    over it (data parallel × pipeline parallel).
    """
    n_stages = mesh.shape[axis_name]
    if len(boundaries) != n_stages:
        raise ValueError(f"{len(boundaries)} stage boundaries for "
                         f"{n_stages}-way '{axis_name}' mesh axis")
    staged, lengths = stack_stage_params(layer_params, boundaries)
    fn = spmd_pipeline_fn(block_fn, n_stages, axis_name)

    mb_spec = P(None, batch_axis) if batch_axis else P()
    shmap = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), staged),
                  P(), mb_spec),
        out_specs=P(axis_name), check_vma=False)
    out = shmap(staged, lengths, xs)           # [S*M, mb, ...] stacked by stage
    # every stage contributed an [M, ...] buffer; only the last stage's holds
    # the retired tokens (serial_in_order exit)
    return out.reshape((n_stages, xs.shape[0]) + out.shape[1:])[-1]
