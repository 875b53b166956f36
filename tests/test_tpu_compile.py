"""Compile rehearsal: the Harris kernels at the paper's 1920x1080 frame,
and the rmsnorm kernels at a width whose working set exceeds Mosaic's
default 16 MiB scoped VMEM, compiled natively (``interpret=False``) for a
described TPU v5e chip.

Nothing runs; the chip's compiler refuses here what it would refuse on the
chip (unaligned copies, scoped-VMEM overflow, a ``pl.ANY`` input under
``vmap``).  Each case checks that the executable holds the Mosaic kernel.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import harris as hk
from repro.kernels.backend import compiled_kernels
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_matmul

H, W = 1080, 1920

KERNELS = {
    "cvt_color": (hk.cvt_color, (H, W, 3)),
    "corner_harris": (hk.corner_harris, (H, W)),
    "convert_scale_abs": (hk.convert_scale_abs, (H, W)),
    "harris_fused": (hk.harris_fused, (H, W, 3)),
    "harris_fused_pair": (hk.harris_fused_pair, (H, W, 3)),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache here; keep it out for these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("batch", [None, 4], ids=["frame", "batch4"])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_harris_kernel_compiles_for_v5e(name, batch, one_chip,
                                        no_persistent_cache):
    kernel, shape = KERNELS[name]

    def fn(x):
        return kernel(x, interpret=False)

    if batch is not None:                 # the served path vmaps stages
        fn, shape = jax.vmap(fn), (batch,) + shape
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    hlo = jax.jit(fn).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert name in compiled_kernels(hlo)  # the kernel's own pallas_call


# a copy or transpose whose result is an f32 [..., 3] array laid out with the
# channel axis minor (the first minor_to_major entry is the last axis)
_RELAYOUT_RE = re.compile(
    r"= f32\[((?:\d+,)*)3\]\{(\d+)[^}]*\} (?:copy|transpose)\(")


def _channel_minor_relayouts(hlo: str) -> list[str]:
    return [m.group(0) for m in _RELAYOUT_RE.finditer(hlo)
            if int(m.group(2)) == m.group(1).count(",")]


@pytest.mark.parametrize("batch", [None, 4], ids=["frame", "batch4"])
def test_cvt_color_reads_planes_without_relayout(batch, one_chip,
                                                 no_persistent_cache):
    """cvt_color reads the frame XLA holds plane-major as three colour
    planes: no lane-padded channel-minor copy of the frame, and no temp
    buffer of its size (a channel-last block needed 1,061,683,200 bytes a
    1080p frame)."""
    fn = functools.partial(hk.cvt_color, interpret=False)
    shape = (H, W, 3)
    if batch is not None:
        fn, shape = jax.vmap(fn), (batch,) + shape
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(fn).lower(x).compile()
    assert _channel_minor_relayouts(compiled.as_text()) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024**2


# N x d activations, [d] scale, [d, d] weight: rmsnorm_matmul's blocks hold
# the whole 16 MiB weight, which only fits under compiler_params' limit
RMS_N, RMS_D = 512, 2048
RMS_KERNELS = {
    "rmsnorm": (rmsnorm, [(RMS_N, RMS_D), (RMS_D,)]),
    "rmsnorm_matmul": (rmsnorm_matmul,
                       [(RMS_N, RMS_D), (RMS_D,), (RMS_D, RMS_D)]),
}


@pytest.mark.parametrize("name", sorted(RMS_KERNELS))
def test_rmsnorm_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    kernel, shapes = RMS_KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    hlo = jax.jit(lambda *a: kernel(*a, interpret=False)).lower(
        *args).compile().as_text()
    assert name in compiled_kernels(hlo)
