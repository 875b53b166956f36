"""Courier at pod scale: balanced pipeline parallelism via shard_map.

The paper's Pipeline Generator decides *stage boundaries* from per-stage
costs; here those boundaries place transformer layers onto a 4-stage mesh
axis and a microbatch token pipeline (ppermute hand-offs) executes them —
TBB tokens become microbatches.  Layers are deliberately heterogeneous in
cost, so the Courier balanced partition differs from naive equal-count
splitting, and the example quantifies the predicted bottleneck gain.

Runs on 8 virtual host devices (set before jax import).
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import AxisType

from repro.core import (CourierIR, Node, linear_ir, partition_optimal,
                        partition_paper, pipeline_microbatches)
from repro.launch.compile_cache import enable_compile_cache


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def main():
    enable_compile_cache()
    mesh = _mesh((4,), ("stage",))

    # A 12-layer stack whose second half is 4x wider (cost-heterogeneous,
    # like a vlm's cross-attn tail) — naive equal-count splitting is
    # unbalanced here, the Courier partition is not.
    L, d = 12, 32
    widths = [4 * d if i >= 6 else d for i in range(L)]
    key = jax.random.PRNGKey(0)
    Win = jnp.stack([jnp.pad(jax.random.normal(key, (d, w)) * 0.2,
                             ((0, 0), (0, 4 * d - w))) for w in widths])
    Wout = jnp.stack([jnp.pad(jax.random.normal(key, (w, d)) * 0.2,
                              ((0, 4 * d - w), (0, 0))) for w in widths])
    params = {"win": Win, "wout": Wout}

    def block(p, x):
        return x + jnp.tanh(x @ p["win"]) @ p["wout"]

    # Courier: per-layer cost model → balanced boundaries
    cost = [2.0 * d * w * 2 for w in widths]          # matmul flops per layer
    ir = linear_ir("layers", [f"L{i}" for i in range(L)], cost)
    paper_plan = partition_paper(ir, n_threads=3)
    opt_plan = partition_optimal(ir, max_stages=4)
    naive_bottleneck = max(sum(cost[i:i + 3]) for i in range(0, L, 3))
    print("naive equal-count bottleneck :", naive_bottleneck)
    print("paper-policy bottleneck      :", paper_plan.bottleneck_ms)
    print("optimal-DP bottleneck        :", opt_plan.bottleneck_ms)

    boundaries, i = [], 0
    for s in opt_plan.stages:
        boundaries.append(i)
        i += len(s.node_names)
    while len(boundaries) < 4:                        # pad to mesh stages
        boundaries.append(L - 1)
    print("stage boundaries (layer idx) :", boundaries)

    # run the token pipeline and check semantics vs sequential
    M, mb = 6, 4
    xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
    out = pipeline_microbatches(mesh, block, params, boundaries, xs)

    h = xs
    for i in range(L):
        h = block({"win": Win[i], "wout": Wout[i]}, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(h),
                               rtol=2e-4, atol=2e-4)
    print("pipeline output == sequential stack: OK")

    # elasticity: a stage group is lost -> re-plan for 3 stages (Courier
    # re-balance), not job abort
    from repro.runtime import ElasticPlanner
    b3 = ElasticPlanner(ir).boundaries(3)
    mesh3 = _mesh((3,), ("stage",))
    out3 = pipeline_microbatches(mesh3, block, params, b3, xs)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(h),
                               rtol=2e-4, atol=2e-4)
    print(f"elastic re-plan to 3 stages {b3}: OK")


if __name__ == "__main__":
    main()
