"""A configuration names its app, and the harness serves any app that
provides the interface: here a second app, ``toy_lm_app`` (the model
zoo's traced transformer on prompts of two lengths), handed to
``run_cell`` through a hand-built cell with no edit to the harness.

On the CPU, through both load generators, it must come out correct with
no compile in the window and a size on every record; with one layer's
residual add left out of the served model, or with its bfloat16 control
in the served path's place, it must not.
"""
from __future__ import annotations

import glob
import os
import re
import sys
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import control  # noqa: E402
from chipbench import run as bench  # noqa: E402

import toy_lm_app  # noqa: E402

SEED = 2**33 + 77
SECONDS = 0.5
CONFIG = {"name": "toy-lm", "app": "toy_lm_app",
          "source": "repro.models.zoo.transformer_demo, as "
                    "serve_traced_transformer_demo serves it",
          "model": {"n_layers": 2, "d": 64, "ff": 128, "n_heads": 4,
                    "vocab": 128, "theta": 10000.0},
          "prompt_lengths": [16, 32], "max_batch": 4, "max_wait_ms": 4,
          "chips": 1, "devices": None, "extra_workers": None,
          "assumed": [], "reduced": []}
TRAFFIC = {
    "closed_loop": {"kind": "closed_loop", "pool": 8, "check_every": 1},
    "open_loop": {"kind": "open_loop", "streams": 8, "fps": 30,
                  "phase_seed": 0, "pool": 8, "check_every": 1},
}


class SkipFirstResidual:
    """The library with each forward's first residual add left out:
    ``add(x, a)`` returns ``a``."""

    def __init__(self, lib, adds_per_forward: int):
        self._lib, self._per, self._n = lib, adds_per_forward, 0

    def __getattr__(self, name):
        fn = getattr(self._lib, name)
        if name != "add":
            return fn

        def add(x, y):
            self._n += 1
            return y if self._n % self._per == 1 else fn(x, y)

        return add


def toy_run(traffic: str, monkeypatch) -> tuple[dict, list, list[str]]:
    """One run of the toy app under ``traffic`` on the CPU; the result
    object, the frame records and the log lines."""
    records, lines = [], []
    drive = bench.drive

    def keep_records(*a, **k):
        threads, recs, kept, errors = drive(*a, **k)
        records.append(recs)
        return threads, recs, kept, errors

    monkeypatch.setattr(bench, "drive", keep_records)
    cell = bench.Cell(name=f"toy-lm.{traffic}", chips=1, config=CONFIG,
                      traffic=TRAFFIC[traffic], end_to_end=[], per_layer=[],
                      app=toy_lm_app)
    out = bench.run_cell(cell, SEED, SECONDS, False, jax.devices()[:1],
                         {"hbm_bytes_per_s": 1e11},
                         t_start=time.perf_counter(), log=lines.append)
    (recs,) = records
    return out, recs, lines


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_a_second_app_is_served_and_checked(traffic, monkeypatch):
    out, recs, lines = toy_run(traffic, monkeypatch)
    gap = out["checks"]["max_logit_gap"]
    assert out["correct"] and gap["value"] <= gap["limit"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out["checks"]) == ["max_logit_gap", "max_logit_err",
                                   "frames_lost"]
    (window,) = [s for s in lines if s.startswith("window:")]
    assert re.search(r"compiles in the window 0 \(stage programs 0\)",
                     window), window
    assert recs and {r.size for r in recs} == {16, 32}
    assert all(r.size == (16, 32)[r.pool_index % 2] for r in recs)


def test_a_second_app_with_a_residual_left_out_is_not_correct(monkeypatch):
    model = toy_lm_app.served_model
    n_adds = 2 * CONFIG["model"]["n_layers"]
    monkeypatch.setattr(
        toy_lm_app, "served_model",
        lambda lib, params: model(SkipFirstResidual(lib, n_adds), params))
    out, _, _ = toy_run("closed_loop", monkeypatch)
    gap, err = out["checks"]["max_logit_gap"], out["checks"]["max_logit_err"]
    assert not out["correct"]
    assert gap["value"] >= 0.5 > gap["limit"]
    assert err["value"] >= 0.5 > err["limit"]


@pytest.mark.parametrize("seed", [5, 2**33 + 7, 3200000001])
def test_a_second_app_bfloat16_control_is_not_correct(seed):
    # the reference in bfloat16 in the served path's place, on the first
    # eight prompts of the closed loop's pool: it mostly picks the
    # reference's token, and its logits are off by far more than the limit
    cell = bench.Cell(name="toy-lm.closed_loop", chips=1, config=CONFIG,
                      traffic=TRAFFIC["closed_loop"], end_to_end=[],
                      per_layer=[], app=toy_lm_app)
    checks = control.control_checks(cell, seed, n_items=8)
    err = checks["max_logit_err"]
    assert err["value"] > 3 * err["limit"], checks


def test_no_harness_file_names_an_app():
    harness = [os.path.join(ROOT, "chipbench", f)
               for f in ("run.py", "record.py", "trace_reduce.py")]
    harness += glob.glob(os.path.join(ROOT, "chipbench", "loadgen", "*.py"))
    for path in harness:
        with open(path) as f:
            text = f.read().lower()
        for word in ("toy_lm", "zoo", "transformer", "harris", "reference."):
            assert word not in text, (path, word)
