"""Pallas TPU kernels — the module database's "hardware modules".

Each kernel ships three layers (per task spec):
  <name>.py  — pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py     — jit'd public wrappers and database rows
  ref.py     — pure-jnp oracles (assert_allclose targets)
backend.py decides where kernels run: compiled on a TPU, interpreted only
on the CPU.
"""
from . import ops, ref
from .flash_attention import flash_attention
from .harris import convert_scale_abs, corner_harris, cvt_color
from .rmsnorm import rmsnorm

__all__ = ["ops", "ref", "flash_attention", "convert_scale_abs",
           "corner_harris", "cvt_color", "rmsnorm"]
