"""The reduction from a profiler trace to busy time, top device operations
and idle gaps: on a synthetic trace with known intervals, and on a small
trace recorded on a TPU v5e chip."""
from __future__ import annotations

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace_reduce as tr  # noqa: E402

MS = 1e6    # nanoseconds


def _synthetic() -> tr.Trace:
    # window 0..100 ms; device 0 busy 10-30 and 25-40 (overlapping ops)
    # and 90-110 (cut at the window's end); device 1 busy 0-50
    return tr.Trace(
        devices={
            "/device:TPU:0": [("cvt_color", 10 * MS, 20 * MS),
                              ("copy.1", 25 * MS, 15 * MS),
                              ("corner_harris", 90 * MS, 20 * MS)],
            "/device:TPU:1": [("fusion", 0.0, 50 * MS)],
        },
        host=[("window", 0.0, 100 * MS),
              ("batcher_wait", 0.0, 100 * MS),   # covers everything
              ("dispatch", 40 * MS, 45 * MS),    # 40-85: most of TPU:0's gap
              ("upload", 85 * MS, 5 * MS),
              ("retire", 200 * MS, 5 * MS)])     # outside the window


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window():
    s = tr.reduce(_synthetic())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s["/device:TPU:0"] == pytest.approx(0.040)   # 10-40, 90-100
    assert s.busy_s["/device:TPU:1"] == pytest.approx(0.050)
    assert s.mean_busy_s == pytest.approx(0.045)


def test_top_ops_sum_clipped_durations_over_devices():
    s = tr.reduce(_synthetic())
    ops = dict(s.device_ops)
    assert ops == pytest.approx({"fusion": 0.05, "cvt_color": 0.02,
                                 "copy.1": 0.015, "corner_harris": 0.01})
    assert [n for n, _ in s.device_ops][0] == "fusion"


def test_each_idle_instant_goes_to_the_first_host_span_open_then():
    s = tr.reduce(_synthetic())
    gaps = dict(s.idle_gaps)
    # TPU:0 idles 0-10 (batcher_wait alone) and 40-90 (dispatch to 85, then
    # upload); TPU:1 idles 50-100 (dispatch to 85, upload to 90, then
    # batcher_wait).  batcher_wait is open throughout, but comes later in
    # HOST_SPANS than dispatch and upload.
    assert gaps == pytest.approx({"dispatch": 0.080, "batcher_wait": 0.020,
                                  "upload": 0.010})
    assert [n for n, _ in s.idle_gaps] == ["dispatch", "batcher_wait",
                                           "upload"]
    assert sum(gaps.values()) == pytest.approx(
        2 * 0.1 - 0.040 - 0.050)


def test_a_retire_inside_dispatch_takes_its_part_of_the_gap():
    # the batcher retires the oldest group inside its dispatch call
    t = tr.Trace(devices={"/device:TPU:0": [("op", 0.0, 10 * MS)]},
                 host=[("window", 0.0, 20 * MS),
                       ("dispatch", 10 * MS, 10 * MS),
                       ("retire", 10 * MS, 6 * MS)])
    assert dict(tr.reduce(t).idle_gaps) == pytest.approx(
        {"retire": 0.006, "dispatch": 0.004})


def test_op_names_keep_the_instruction_and_its_type():
    hlo = ("%copy.1 = f32[4,1080,1920,3]{3,2,1,0:T(8,128)} copy(f32[4,1080,"
           "1920,3]{2,1,3,0:T(8,128)} %env__d0__.1)")
    assert tr.op_name(hlo) == "copy.1 f32[4,1080,1920,3]{3,2,1,0:T(8,128)}"
    assert tr.op_name("%fusion = (f32[4]{0}, f32[4]{0}) fusion(%a)") \
        == "fusion (f32[4]{0}"
    assert tr.op_name("jit_stage(123)") == "jit_stage(123)"


def test_a_gap_no_span_covers_is_named_so():
    t = tr.Trace(devices={"/device:TPU:0": [("op", 0.0, 10 * MS)]},
                 host=[("window", 0.0, 20 * MS)])
    assert tr.reduce(t).idle_gaps == [(tr.NO_SPAN, pytest.approx(0.010))]


def test_reduce_refuses_a_trace_without_window_or_device():
    with pytest.raises(RuntimeError, match="window"):
        tr.reduce(tr.Trace(devices={"/device:TPU:0": []}))
    with pytest.raises(RuntimeError, match="device"):
        tr.reduce(tr.Trace(host=[("window", 0.0, 1.0)]))


RECORDED = glob.glob(os.path.join(ROOT, "chipbench", "testdata", "**",
                                  "*.xplane.pb"), recursive=True)


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_a_trace_recorded_on_the_chip(path):
    t = tr.load(path)
    assert list(t.devices) == ["/device:TPU:0"]
    names = {n for n, _, _ in t.host}
    assert {"window", "submit", "dispatch", "retire"} <= names
    s = tr.reduce(t)
    assert 0.0 < s.busy_s["/device:TPU:0"] <= s.window_s
    ops = " ".join(n for n, _ in s.device_ops)
    assert "cvt_color" in ops and "corner_harris" in ops
    assert all(sec > 0 for _, sec in s.device_ops + s.idle_gaps)
    total_idle = sum(sec for _, sec in s.idle_gaps)
    assert total_idle == pytest.approx(s.window_s - s.mean_busy_s, rel=1e-6)
