"""The control of the correctness check: the reference computed in
bfloat16, the next precision below the float32 the configuration states,
put in the served path's place.

For each seed it draws the cell's frame pool exactly as the cell's load
generator does and reads, over ``--frames`` pool frames, the widest gap
between the bfloat16 reference and the float32 one: the number a run
compares against ``run.GAP_LIMIT``.  The check is sound only while every
seed reads far above that limit.  The benchmark's own runs never run this.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import ml_dtypes

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import reference  # noqa: E402
from chipbench import run as bench  # noqa: E402


def control_gap(cell, seed: int, n_frames: int) -> float:
    cfg = cell.config
    shape = (int(cfg["frame"]["height"]), int(cfg["frame"]["width"]))
    load = bench._module("loadgen", cell.traffic["kind"]).Load(
        cell.traffic, shape, seed)
    h = cfg["harris"]
    gap = 0.0
    for p in range(n_frames):
        frame = load.pool_frame(p)
        ref = reference.harris_demo(frame, h["block_size"], h["k"])
        low = reference.harris_demo(frame, h["block_size"], h["k"],
                                    dtype=ml_dtypes.bfloat16)
        gap = max(gap, reference.max_gap(low, ref))
    load.release()
    return gap


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--frames", type=int, default=8)
    args = ap.parse_args()
    cell = bench.resolve(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        gap = control_gap(cell, seed, args.frames)
        rows.append({"seed": seed, "max_gray_gap": gap,
                     "limit": bench.GAP_LIMIT,
                     "correct": gap <= bench.GAP_LIMIT})
        print(json.dumps(rows[-1]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
