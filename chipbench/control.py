"""The control of a cell's correctness check: the app's reference computed
in the next precision below the one the configuration states, put in the
served path's place (the app's ``control(items, source)``).

For each seed it draws the cell's pool exactly as the cell's load
generator does and judges the control's outputs for ``--items`` pool
items by the app's own ``check``: the numbers a run compares, each beside
its limit.  The check is sound only while every seed fails at least one
of them by far.  The benchmark's own runs never run this.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import run as bench  # noqa: E402


def control_checks(cell, seed: int, n_items: int) -> dict:
    """The app's checks of its control on the first ``n_items`` items of
    the cell's pool under ``seed``."""
    source = cell.app.inputs(cell.config, seed)
    load = bench._module("loadgen", cell.traffic["kind"]).Load(
        cell.traffic, source, seed)
    items = [load.pool_frame(p) for p in range(n_items)]
    checks = cell.app.check(cell.app.control(items, source), items, source)
    load.release()
    return checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--items", type=int, default=8)
    args = ap.parse_args()
    cell = bench.resolve(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control_checks(cell, seed, args.items)
        correct = all(c["value"] is not None and c["value"] <= c["limit"]
                      for c in checks.values())
        print(json.dumps({"seed": seed, "checks": checks,
                          "correct": correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
