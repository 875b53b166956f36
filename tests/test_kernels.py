"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.harris import convert_scale_abs, corner_harris, cvt_color
from repro.kernels.rmsnorm import rmsnorm
from repro.models import harris as mh

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("B,T,H,hd,M", [
    (1, 128, 1, 64, 128),
    (2, 256, 4, 64, 256),
    (1, 512, 2, 128, 512),
    (2, 128, 4, 32, 384),         # cross-attn style T != M
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, T, H, hd, M, causal, window, dtype):
    if not causal and T != M:
        pass        # valid: cross attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, M, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, M, H, hd), dtype)
    o = flash_attention(q, k, v, causal, window, 128, 128, True)
    r = ref.reference_attention(q, k, v, causal, window)
    tol = 2.5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


def test_flash_attention_grads():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 256, 2, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))

    def f(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    g1 = jax.grad(f(lambda *a: flash_attention(*a, True, 0, 128, 128, True)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f(lambda *a: ref.reference_attention(*a, True, 0)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_flash_attention_windowed_grads():
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (1, 256, 2, 64)) for kk in ks)
    f1 = lambda q, k, v: jnp.sum(flash_attention(q, k, v, True, 128, 128, 128, True) ** 2)
    f2 = lambda q, k, v: jnp.sum(ref.reference_attention(q, k, v, True, 128) ** 2)
    g1 = jax.grad(f1, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def _cvt_case(H, W, batch=None, row_block=None):
    tag = "" if batch is None else f"batch{batch}-"
    tag += f"{H}-{W}" + ("" if row_block is None else f"-rb{row_block}")
    return pytest.param(H, W, batch, row_block, id=tag)


# (33, 130), (45, 200), (21, 96): no multiple of 8 divides H, W is not a
# multiple of 128; row_block 8 gives a grid of several programs
@pytest.mark.parametrize("H,W,batch,row_block", [
    _cvt_case(8, 128), _cvt_case(64, 256), _cvt_case(33, 130),
    _cvt_case(45, 200), _cvt_case(64, 256, row_block=8),
    _cvt_case(64, 256, batch=2, row_block=8), _cvt_case(33, 130, batch=3),
    _cvt_case(21, 96, batch=2)])
def test_cvt_color_sweep(H, W, batch, row_block):
    shape = (H, W, 3) if batch is None else (batch, H, W, 3)
    img = jax.random.uniform(KEY, shape) * 255
    fn = functools.partial(cvt_color, row_block=row_block)
    want = mh.cvt_color
    if batch is not None:               # the served path vmaps stage 0
        fn, want = jax.vmap(fn), jax.vmap(want)
    np.testing.assert_allclose(np.asarray(fn(img)), np.asarray(want(img)),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("H,W,rb", [(1080, 1920, 120), (720, 1280, 144),
                                    (2160, 3840, 48), (33, 130, 33)])
def test_cvt_row_block_rule(H, W, rb):
    """The largest multiple of 8 dividing H whose double-buffered 3-plane
    input and 1-plane output blocks fit ``CVT_BLOCK_BYTES``; H itself
    where no multiple of 8 divides it."""
    from repro.core.costmodel import VMEM_BYTES
    from repro.kernels.harris import CVT_BLOCK_BYTES, cvt_row_block

    def block_bytes(r):
        return 2 * (3 + 1) * r * W * 4

    got = cvt_row_block(H, W)
    assert got == rb and H % got == 0
    if H % 8 == 0:
        assert got % 8 == 0
        assert all(block_bytes(r) > CVT_BLOCK_BYTES
                   for r in range(got + 8, H + 1, 8) if H % r == 0)
    assert block_bytes(got) <= CVT_BLOCK_BYTES <= VMEM_BYTES


@pytest.mark.parametrize("H,W", [(16, 128), (64, 256), (40, 136)])
@pytest.mark.parametrize("block_size", [2, 3])
def test_corner_harris_sweep(H, W, block_size):
    gray = mh.cvt_color(jax.random.uniform(KEY, (H, W, 3)) * 255)
    got = corner_harris(gray, block_size)
    want = mh.corner_harris(gray, block_size)
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=1e-5)


@pytest.mark.parametrize("alpha,beta", [(1.0, 0.0), (0.01, 5.0), (-2.0, 100.0)])
def test_convert_scale_abs_sweep(alpha, beta):
    x = jax.random.normal(KEY, (32, 128)) * 300
    np.testing.assert_allclose(np.asarray(convert_scale_abs(x, alpha, beta)),
                               np.asarray(mh.convert_scale_abs(x, alpha, beta)),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("N,d", [(256, 128), (512, 384), (100, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(N, d, dtype):
    x = jax.random.normal(KEY, (N, d), dtype)
    s = (jax.random.normal(KEY, (d,)) * 0.2).astype(dtype)
    got = rmsnorm(x, s)
    want = ref.reference_rmsnorm(x, s)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_vmem_working_set_documented():
    """The fwd kernel's per-program VMEM footprint stays under budget."""
    from repro.core.costmodel import VMEM_BYTES
    bq, bk, hd, M = 512, 512, 128, 32768
    # q block + k/v full-seq refs + f32 acc + score block
    working = (bq * hd * 2 + 2 * M * hd * 2 + bq * hd * 4 + bq * bk * 4)
    assert working < VMEM_BYTES


def test_kernel_switch_and_fused_harris_response():
    """The ops layer has no switch back to the jnp references: the
    single-call ``harris_response`` runs the fused kernel (interpreted on
    the CPU, by the platform rule) and matches the three-step reference
    chain."""
    from repro.kernels import ops
    from repro.kernels.backend import interpret_mode

    assert not hasattr(ops, "use_kernels")
    assert interpret_mode() == (jax.default_backend() == "cpu")
    assert interpret_mode(False) is False
    img = jax.random.uniform(KEY, (32, 48, 3)) * 255.0
    got = ops.harris_response(img)
    want = ref.reference_convert_scale_abs(
        ref.reference_corner_harris(ref.reference_cvt_color(img), 2, 0.04),
        1.0, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_every_pallas_call_compiles_under_the_planned_vmem_limit():
    """Every kernel passes ``backend.compiler_params()``, so the scoped VMEM
    Mosaic enforces is the ``VMEM_BYTES`` the planner checks against."""
    import ast
    import pathlib

    import repro.kernels
    from repro.core.costmodel import VMEM_BYTES
    from repro.kernels.backend import compiler_params

    assert compiler_params().vmem_limit_bytes == VMEM_BYTES
    calls = []
    for path in sorted(pathlib.Path(repro.kernels.__file__).parent.glob(
            "*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                kw = {k.arg: ast.unparse(k.value) for k in node.keywords}
                calls.append((path.name, node.lineno,
                              kw.get("compiler_params")))
    assert len(calls) == 9, calls
    assert all(cp == "compiler_params()" for _, _, cp in calls), calls
