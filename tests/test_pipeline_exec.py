"""Pipeline executors: host token pipeline ≡ sequential; SPMD pipeline ≡ stack."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (Frontend, Library, ModuleDatabase, PipelineGenerator)

OPS = {
    "mul2": lambda x: x * 2.0,
    "add1": lambda x: x + 1.0,
    "neg": lambda x: -x,
    "sq": lambda x: x * x,
    "tanh": jnp.tanh,
}


@settings(max_examples=25, deadline=None)
@given(st.lists(st.sampled_from(sorted(OPS)), min_size=1, max_size=8),
       st.integers(min_value=1, max_value=6),
       st.integers(min_value=1, max_value=3),
       st.integers(min_value=2, max_value=8))
def test_pipeline_semantics_random_chains(chain, n_tokens, n_threads, pool):
    db = ModuleDatabase("t")
    for name, fn in OPS.items():
        db.register(name, software=fn,
                    accelerated=fn if name != "add1" else None)
    lib = Library(db)

    def app(x):
        for f in chain:
            x = getattr(lib, f)(x)
        return x

    ir, _ = Frontend(db).trace(app, jnp.arange(4.0), profile=False)
    for n in ir.nodes:                    # synthetic profile (no wall clock)
        n.time_ms = 1.0 + (hash(n.name) % 7)
    pipe = PipelineGenerator(db).generate(ir, n_threads=n_threads)
    pipe.max_in_flight = pool
    toks = [jnp.full((4,), float(i + 1)) for i in range(n_tokens)]
    got = pipe.run_async(toks)
    want = [app(t) for t in toks]
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)


def test_pipeline_nonlinear_graph_liveness():
    """A value consumed across a stage boundary must stay live."""
    db = ModuleDatabase("t")
    db.register("a", software=lambda x: x + 1.0)
    db.register("b", software=lambda x: x * 2.0)
    db.register("c", software=lambda x, y: x + y)   # consumes BOTH a and b
    lib = Library(db)

    def app(x):
        u = lib.a(x)
        v = lib.b(u)
        return lib.c(u, v)

    ir, _ = Frontend(db).trace(app, jnp.arange(3.0), profile=False)
    for n in ir.nodes:
        n.time_ms = 1.0
    pipe = PipelineGenerator(db).generate(ir, n_threads=3)
    x = jnp.arange(3.0)
    np.testing.assert_allclose(pipe(x), app(x))


SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import pipeline_microbatches

    try:                                   # AxisType only exists on jax>=0.5
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
    except ImportError:
        mesh = jax.make_mesh((4,), ("stage",))
    L, d, M, mb = 9, 8, 5, 2
    W = jax.random.normal(jax.random.PRNGKey(0), (L, d, d)) * 0.3
    xs = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
    block = lambda p, x: jnp.tanh(x @ p["w"])

    def ref(xs):
        h = xs
        for i in range(L):
            h = jnp.tanh(h @ W[i])
        return h

    # unequal, cost-balanced boundaries (Courier partition output shape)
    out = pipeline_microbatches(mesh, block, {"w": W}, [0, 2, 5, 7], xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(xs)),
                               rtol=2e-5, atol=2e-5)

    # differentiability: same grads as the stacked reference
    loss = lambda p: jnp.mean(
        pipeline_microbatches(mesh, block, p, [0, 2, 5, 7], xs) ** 2)
    def loss_ref(p):
        h = xs
        for i in range(L):
            h = jnp.tanh(h @ p["w"][i])
        return jnp.mean(h ** 2)
    g = jax.grad(loss)({"w": W})["w"]
    gr = jax.grad(loss_ref)({"w": W})["w"]
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-4, atol=1e-5)
    print("SPMD-OK")
""")


@pytest.mark.slow
def test_spmd_pipeline_multidevice_subprocess():
    """Runs the shard_map/ppermute token pipeline on 8 host devices."""
    # inherit the parent env (esp. JAX_PLATFORMS=cpu — without it jax may
    # probe for accelerator backends at import and hang) and force src/ on
    # the child's path
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", SPMD_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert "SPMD-OK" in r.stdout, r.stderr[-2000:]


# --------------------------------------------------------------------------- #
# SPMD building blocks, tested directly (not just via pipeline_microbatches)
# --------------------------------------------------------------------------- #
def test_stack_stage_params_pads_and_counts():
    from repro.core import stack_stage_params
    L = 5
    params = {"w": jnp.arange(float(L)).reshape(L, 1)}
    staged, lengths = stack_stage_params(params, [0, 3])
    assert staged["w"].shape == (2, 3, 1)          # padded to Lmax=3
    np.testing.assert_array_equal(np.asarray(lengths), [3, 2])
    np.testing.assert_allclose(np.asarray(staged["w"][1, :, 0]),
                               [3.0, 4.0, 0.0])    # zero-padded tail
    with pytest.raises(ValueError, match="start at 0"):
        stack_stage_params(params, [1, 3])
    with pytest.raises(ValueError, match="empty stage"):
        stack_stage_params(params, [0, 5])


def test_stage_apply_masks_padding_layers():
    from repro.core import stage_apply

    def block(p, h):
        return h + p["b"]
    stage_params = {"b": jnp.array([1.0, 10.0, 100.0])}
    assert float(stage_apply(block, stage_params, jnp.int32(3),
                             jnp.zeros(()))) == 111.0
    # the masked tail layer (the 100.0) must not run
    assert float(stage_apply(block, stage_params, jnp.int32(2),
                             jnp.zeros(()))) == 11.0


def test_spmd_pipeline_fn_matches_sequential_under_vmap():
    """Drive the shard_map-interior function with vmap's named axis (one
    stage: the ICI hand-off is skipped, which is exactly what vmap's
    ppermute rule requires): every microbatch retires with all L layers
    applied in order."""
    from repro.core import spmd_pipeline_fn, stack_stage_params
    L, M = 4, 3
    params = {"b": jnp.arange(1.0, L + 1.0)}       # layer i adds i+1
    staged, lengths = stack_stage_params(params, [0])

    def block(p, h):
        return h + p["b"]
    fn = spmd_pipeline_fn(block, 1)
    xs = jnp.arange(float(M * 2)).reshape(M, 2)
    per_dev = jax.tree.map(lambda a: a[:, None], staged)   # [S, 1, Lmax, ...]
    out = jax.vmap(fn, in_axes=(0, None, None),
                   axis_name="stage")(per_dev, lengths, xs)
    assert out.shape == (1, M, 2)
    np.testing.assert_allclose(np.asarray(out[-1]),
                               np.asarray(xs + jnp.sum(params["b"])),
                               rtol=1e-6)
