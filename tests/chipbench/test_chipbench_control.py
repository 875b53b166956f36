"""The control of the correctness check: the reference in bfloat16, the
precision below the float32 every configuration states, must read far
outside the limit that a served frame is held to, on every seed, while the
float32 reference read against itself is exact."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import control, reference  # noqa: E402
from chipbench import run as bench  # noqa: E402

SMALL = {"height": 96, "width": 128}
SEEDS = [5, 6, 2**33 + 7]


def _small(cell_name):
    cell = bench.resolve(cell_name)
    cell.config = dict(cell.config, frame=dict(cell.config["frame"], **SMALL))
    return cell


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", [
    "harris-1080p.backlog", "harris-720p.cameras"])
def test_bfloat16_control_fails_the_limit(cell, seed):
    small = _small(cell)
    gap = control.control_checks(small, seed, n_items=2)["max_gray_gap"]
    assert gap["limit"] == small.app.GAP_LIMIT
    assert gap["value"] > 10 * gap["limit"]


def test_reference_against_itself_and_a_wrong_shape():
    x = np.random.default_rng(0).random((24, 40, 3), np.float32) * 255
    ref = reference.harris_demo(x)
    assert ref.shape == (24, 40) and ref.dtype == np.float32
    assert 0.0 <= ref.min() and ref.max() <= 255.0
    assert reference.max_gap(ref, ref) == 0.0
    assert reference.max_gap(ref[:-1], ref) == float("inf")
    assert reference.max_gap(np.full_like(ref, np.nan), ref) == float("inf")
