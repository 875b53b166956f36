"""Where Pallas kernels run — the one interpret-mode rule — what they are
compiled with, and how to find them in a compiled TPU executable.

A kernel runs in interpret mode only when JAX's default backend is the
CPU (the test container).  On a TPU every kernel compiles natively; there
is no interpreter or jnp fallback to hide a kernel the chip refuses.
Every ``pallas_call`` in this package passes :func:`compiler_params`, so
the scoped-VMEM limit Mosaic enforces is the ``VMEM_BYTES`` the cost
model, the fusion pass and the plan verifier check working sets against.
"""
from __future__ import annotations

import re

import jax
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import VMEM_BYTES

# a kernel's pallas_call name is its tpu_custom_call's HLO instruction name;
# vmap prefixes "vmap_" and suffixes "_", XLA appends ".N"
_KERNEL_RE = re.compile(r"%(?:vmap_)*([A-Za-z]\w*?)_?(?:\.\d+)? = [^\n]*"
                        r'custom_call_target="tpu_custom_call"')


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` when given explicitly, else True exactly on the CPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


def compiler_params() -> pltpu.CompilerParams:
    """Mosaic parameters of every kernel here: scoped VMEM = ``VMEM_BYTES``."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES)


def compiled_kernels(hlo: str) -> set[str]:
    """Names of the Pallas kernels (``tpu_custom_call``) in compiled TPU
    HLO text (``jax.jit(f).lower(...).compile().as_text()``)."""
    return set(_KERNEL_RE.findall(hlo))
