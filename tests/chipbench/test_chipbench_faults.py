"""The comparison that decides ``correct``, against a timed path broken
underneath it.

Each test skips the harness's look for a chip and drives the rest of one
run of a cell, on the CPU at a small frame size, with a fault planted in
the served pipeline after it is built: an answer altered where the last
stage produces it, half of each batch served with the other half's
results, and, across chips, a replica that hands on its previous group's
buffer in place of this group's (the exchange between chips left out).
A sound run must come out correct, and every broken one not.

The four-device case runs in a child process, which gets four virtual CPU
devices from ``XLA_FLAGS``.  It drives the four-chip configuration, which
has no cell in ``BENCHMARK.json`` yet, under the backlog's traffic.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402

SMALL = {"height": 64, "width": 96}
SEED = 2**33 + 321
SECONDS = 0.5

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
FOUR_CHIP_CONFIG = os.path.join(ROOT, "chipbench", "configs",
                                "harris-1080p-4chip.json")


def _on_last_stage(ex, change) -> None:
    """Apply ``change(output, group_rows)`` to what the last stage
    program returns, on every path the executor dispatches through."""
    pick = ex._stage_fns_for

    def patched(size):
        fns = list(pick(size))
        last = fns[-1]
        fns[-1] = lambda env: change(last(env), size)
        return fns

    ex._stage_fns_for = patched


def answer_altered(ex) -> None:
    import jax

    _on_last_stage(ex, lambda out, size: jax.tree.map(
        lambda v: v.at[(0,) * v.ndim].add(1.0), out))


def half_batch(ex) -> None:
    import jax
    import jax.numpy as jnp

    def change(out, size):
        if size == 1:
            return out
        return jax.tree.map(lambda v: jnp.concatenate(
            [v[:(v.shape[0] + 1) // 2], v[:v.shape[0] // 2]]), out)

    _on_last_stage(ex, change)


def exchange_stale(ex) -> None:
    run_stage = ex._exec_replicated
    previous = {}

    def patched(si, w, seq, g, dev, ordinal, inj_ord):
        ok = run_stage(si, w, seq, g, dev, ordinal, inj_ord)
        if ex.replicas is not None and ex.replicas[si] > 1 and w > 0:
            fresh = g.env
            g.env = previous.get((si, w), fresh)
            previous[(si, w)] = fresh
        return ok

    ex._exec_replicated = patched


FAULTS = {"answer_altered": answer_altered, "half_batch": half_batch,
          "exchange_stale": exchange_stale}


def four_chip_cell() -> bench.Cell:
    with open(FOUR_CHIP_CONFIG) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic", "backlog.json")) as f:
        traffic = json.load(f)
    return bench.Cell(name="harris-1080p-4chip.backlog", chips=4,
                      config=config, traffic=traffic, end_to_end=[],
                      per_layer=[], app=bench.app_of(config))


def small_run(cell: bench.Cell, fault: str | None, devices: list) -> dict:
    """One run of ``cell`` at a small frame size on ``devices``, with
    ``fault`` planted in the built pipeline; returns the result object."""
    cell.config = dict(cell.config, frame=dict(cell.config["frame"], **SMALL))
    cell.traffic = dict(cell.traffic, check_every=1)
    if "streams" in cell.traffic:
        # enough cameras that most groups hold several frames, so that a
        # fault in how a batch is served shows in every run
        cell.traffic["streams"] = 24
    # a module of this run's own: the planted fault goes with it
    cell.app = bench.app_of(cell.config)
    build = cell.app.build

    def broken(config, source, devices):
        served = build(config, source, devices)
        if fault is not None:
            FAULTS[fault](served.executor)
        return served

    cell.app.build = broken
    return bench.run_cell(cell, SEED, SECONDS, False, devices,
                          {"hbm_bytes_per_s": 1e11},
                          t_start=time.perf_counter(), log=lambda s: None)


@pytest.mark.parametrize("fault", [None, "answer_altered", "half_batch"])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    import jax

    out = small_run(bench.resolve(cell, SPEC), fault, jax.devices()[:1])
    gap = out["checks"]["max_gray_gap"]
    assert out["attempted"] > 0
    if fault is None:
        assert out["correct"] and gap["value"] <= gap["limit"]
        assert out["failed"] == 0
    else:
        assert not out["correct"]
        assert gap["value"] >= 0.5 > gap["limit"]


def test_a_stale_exchange_between_chips_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"), ROOT,
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    sound, stale = (json.loads(line) for line
                    in proc.stdout.strip().splitlines()[-2:])
    assert sound["correct"], sound["checks"]
    assert not stale["correct"], stale["checks"]


if __name__ == "__main__":
    import jax

    for fault in (None, "exchange_stale"):
        print(json.dumps(small_run(four_chip_cell(), fault,
                                   jax.devices()[:4])), flush=True)
