"""The load generators, the frame pools, the Harris app's source of them
and the statistics every metric reader shares: deterministic per seed, and
taken over all requests."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import frames  # noqa: E402
from chipbench import run as bench  # noqa: E402
from chipbench.record import FrameRecord, RunData, percentile  # noqa: E402
from chipbench.trace_reduce import Summary  # noqa: E402

BIG_SEED = 2**33 + 12345        # seeds beyond 32 bits must work
CAMERAS = {"kind": "open_loop", "streams": 5, "fps": 30, "phase_seed": 3,
           "pool": 4, "check_every": 8}
BACKLOG = {"kind": "closed_loop", "pool": 4, "check_every": 8}
HARRIS = bench._module("apps", "harris")


def _source(seed, shape=(16, 24)):
    return HARRIS.inputs({"frame": {"height": shape[0], "width": shape[1]},
                          "harris": {"block_size": 2, "k": 0.04}}, seed)


def _load(traffic, seed, shape=(16, 24)):
    return bench._module("loadgen", traffic["kind"]).Load(
        traffic, _source(seed, shape), seed)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_frame_pools_are_deterministic_per_seed(seed):
    a = frames.host_pool(3, 16, 24, seed)
    b = [np.asarray(f) for f in frames.device_pool(3, 16, 24, seed)]
    for x, y in zip(a, b):
        assert x.shape == (16, 24, 3) and x.dtype == np.float32
        np.testing.assert_array_equal(x, y)
        assert 0.0 <= x.min() and x.max() < 255.0
    assert not np.array_equal(a[0], a[1])
    other = frames.host_pool(3, 16, 24, seed + 1)
    assert not np.array_equal(a[0], other[0])


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 5])
def test_harris_source_pools_are_the_frame_pools(seed):
    src = _source(seed, (64, 96))
    for got, want in (
            (src.device_pool(3), frames.device_pool(3, 64, 96, seed)),
            (src.host_pool(3), frames.host_pool(3, 64, 96, seed))):
        assert len(got) == 3
        for x, y in zip(got, want):
            assert type(x) is type(y) and x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    (warm,) = src.warm_items()
    assert warm.shape == (64, 96, 3) and src.size(warm) == 64 * 96
    # the loadgens hold the source's pools
    closed = _load(dict(BACKLOG, pool=3), seed, (64, 96))
    opened = _load(dict(CAMERAS, pool=3), seed, (64, 96))
    for p in range(3):
        np.testing.assert_array_equal(closed.pool_frame(p),
                                      opened.pool_frame(p))


@pytest.mark.parametrize("seed", [1, BIG_SEED])
def test_open_loop_schedule_is_deterministic_per_seed(seed):
    s1 = _load(CAMERAS, seed).schedule(2)
    s2 = _load(CAMERAS, seed).schedule(2)
    assert s1 == s2
    assert len(s1) == 5 * 30 * 2
    dues = [d for d, _ in s1]
    assert dues == sorted(dues) and 0.0 <= dues[0] and dues[-1] < 2.0
    assert {p for _, p in s1} <= set(range(4))


def test_open_loop_seeds_change_frames_not_arrivals():
    a = _load(CAMERAS, 3).schedule(2)
    b = _load(CAMERAS, 4).schedule(2)
    assert a != b and [d for d, _ in a] == [d for d, _ in b]
    # each camera sends every frame period, whatever its phase
    gaps = np.diff(sorted(d for d, _ in a)[::5])
    assert np.allclose(gaps, 1 / 30, atol=1e-9)


def test_closed_loop_schedule_is_deterministic_per_seed():
    take = 12
    s1 = [x for _, x in zip(range(take), _load(BACKLOG, 5).schedule(1))]
    s2 = [x for _, x in zip(range(take), _load(BACKLOG, 5).schedule(1))]
    assert s1 == s2
    assert all(d is None for d, _ in s1)
    assert {p for _, p in s1} <= set(range(4)) and len({p for _, p in s1}) > 1


def test_percentile_matches_numpy_linear():
    rng = np.random.default_rng(0)
    xs = rng.random(1001) * 50
    for q in (0, 50, 95, 99, 100):
        assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert percentile([], 50) is None


def _run(frames_):
    return RunData(config={}, peak={}, seconds=1.0, t0=0.0, t1=1.0, setup_s=0.0,
                   frames=frames_)


def test_latency_percentiles_are_over_every_frame_due_in_the_window():
    # 100 frames due in the window: 98 fast, 2 slow.  The slow ones, and
    # the one due in the window but ready long after it closed, count;
    # the frame due after the window does not.
    fs = [FrameRecord(i, 0, t_due=i / 100, t_submit=i / 100,
                      t_ready=i / 100 + 0.001) for i in range(98)]
    fs += [FrameRecord(98, 0, 0.98, 0.98, t_ready=0.98 + 0.5),
           FrameRecord(99, 0, 0.99, 0.99, t_ready=5.0),
           FrameRecord(100, 0, 1.01, 1.01, t_ready=1.02)]
    run = _run(fs)
    lat = run.latencies_ms()
    assert len(lat) == 100 == len(run.attempted())
    p99 = bench._module("metrics", "latency_p99_ms.cameras").read(run)
    assert p99 == pytest.approx(np.percentile(lat, 99))
    assert p99 > 400.0
    p95 = bench._module("metrics", "latency_p95_ms").read(run)
    assert p95 == pytest.approx(np.percentile(lat, 95))
    p50 = bench._module("metrics", "latency_p50_ms").read(run)
    assert p50 == pytest.approx(1.0)


def test_frames_per_s_counts_results_ready_inside_the_window():
    fs = [FrameRecord(i, 0, None, i / 10, t_ready=i / 10 + 0.05)
          for i in range(12)]
    fs[3].error = "lost"
    run = _run(fs)
    # ready at 0.05 .. 1.15: ten inside [0, 1], one of them lost
    assert bench._module("metrics", "frames_per_s").read(run) == 9.0
    assert len(run.attempted()) == 10
    assert bench._module("metrics", "latency_p50_ms").read(run) is None


def test_hbm_roofline_counts_16_bytes_a_pixel_of_the_frames_completed():
    # 1080p frames, nine ready inside the window (one late, one lost):
    # 16 bytes a pixel of each over busy time at the peak
    fs = [FrameRecord(i, 0, None, i / 10, size=1080 * 1920,
                      t_ready=i / 10 + 0.05) for i in range(11)]
    fs[3].error = "lost"
    run = _run(fs)
    reader = bench._module("metrics", "pipeline_hbm_roofline.backlog")
    assert reader.read(run) is None
    run.peak = {"hbm_bytes_per_s": 8.0e11}
    run.trace = Summary(window_s=1.0, busy_s={"/device:TPU:0": 0.25},
                        device_ops=[], idle_gaps=[])
    want = 100.0 * 9 * 16 * 1080 * 1920 / (0.25 * 8.0e11)
    assert reader.read(run) == pytest.approx(want)
    run.frames = []
    assert reader.read(run) is None
