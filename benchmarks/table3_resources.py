"""Paper Table III — resource utilization of generated modules.

Zynq resources (BRAM/DSP/FF/LUT) map to the TPU kernel budget: VMEM bytes
per program block, fraction of the ~128 MiB VMEM, grid size, and MXU-tile
alignment of the contracting dims.  Derived from each kernel's BlockSpecs.
"""
from __future__ import annotations

from repro.configs.harris import config as HARRIS
from repro.core.costmodel import LANE, MXU_TILE, SUBLANE, VMEM_BYTES
from repro.kernels.harris import ROW_BLOCK, cvt_row_block


def _row(name: str, vmem_bytes: int, grid: int, note: str):
    return (f"table3.{name}.vmem_block_bytes", vmem_bytes,
            f"{100 * vmem_bytes / VMEM_BYTES:.2f}% of VMEM; grid={grid}; {note}")


def run() -> list[tuple[str, float, str]]:
    H, W = HARRIS.height, HARRIS.width
    rb = ROW_BLOCK
    rows = []
    # cvtColor: in block [3, crb, W] f32 (three colour planes) + out [crb, W]
    crb = cvt_row_block(H, W)
    rows.append(_row("cvtColor", crb * W * 3 * 4 + crb * W * 4, H // crb,
                     f"VPU elementwise, {W}-lane rows"))
    # cornerHarris: halo rows + 3 sobel products + 3 sums + out (f32)
    halo = 2
    work = (rb + 2 * halo) * (W + 2 * halo) * 4 * 3 + rb * W * 4 * 4
    rows.append(_row("cornerHarris", work, H // rb,
                     "stencil halo-blocks (line-buffer analog)"))
    rows.append(("table3.cornerHarris.paper_luts", 17494,
                 "paper: 32% LUT, 23% BRAM for hls::cornerHarris"))
    # convertScaleAbs
    rows.append(_row("convertScaleAbs", rb * W * 4 * 2, H // rb,
                     "VPU elementwise"))
    # flash attention: q block + k/v stream + f32 acc + score block
    bq, bk, hd, M = 512, 512, 128, 32768
    fa = bq * hd * 2 + 2 * M * hd * 2 + bq * hd * 4 + bq * bk * 4
    rows.append(_row("flash_attention", fa, f"BHxT/{bq}",
                     f"MXU {MXU_TILE[0]}x{MXU_TILE[1]}-aligned (hd={hd}, "
                     f"bq%{SUBLANE}==0, bk%{LANE}==0)"))
    # rmsnorm
    rows.append(_row("rmsnorm", 256 * 4096 * 4 * 2, "N/256",
                     "row-tiled, f32 accumulation"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
