"""The reduction from a profiler trace to busy time, the time of every
device operation and program, top device operations and idle gaps: on a
synthetic trace with known intervals, and on a small trace recorded on a
TPU v5e chip."""
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


def test_every_op_and_program_sums_clipped_durations_over_devices():
    # stage programs as the pipeline names them, after their library calls
    a, b = ("jit_stage_cvtColor_cornerHarris(1)",
            "jit_stage_convertScaleAbs(2)")
    t = _synthetic()
    t.modules = {"/device:TPU:0": [(a, 10 * MS, 30 * MS),
                                   (b, 90 * MS, 20 * MS)],
                 "/device:TPU:1": [(a, -5 * MS, 55 * MS)]}
    s = tr.reduce(t)
    assert s.op_s == pytest.approx(dict(s.device_ops))
    assert s.module_s == pytest.approx({a: 0.080, b: 0.010})


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


# reduce() of the recorded trace, as the reduction read it before it kept
# every op's and program's seconds: those additions must leave it as it was
RECORDED_1080P = {
    "window_s": 0.300144454,
    "busy_s": {"/device:TPU:0": 0.200124661},
    "device_ops": [
        ("vmap_cvt_color_.1 f32[4,1080,1920]{2,1,0:T(8,128)S(1)}",
         0.106122737),
        ("copy.1 f32[4,1080,1920,3]{3,2,1,0:T(8,128)}", 0.072661792),
        ("corner_harris.1 f32[4,1080,1920]{2,1,0:T(8,128)}",
         0.005785083999999999),
        ("pad_maximum_fusion f32[4,1080,1920,3]{2,1,3,0:T(8,128)}",
         0.003686582),
        ("copy.1 f32[1,1080,1920,3]{2,1,3,0:T(8,128)}",
         0.0036167499999999997),
        ("copy-done f32[4,1080,1920]{2,1,0:T(8,128)S(1)}",
         0.0023106319999999995),
        ("vmap_convert_scale_abs_.1 f32[4,1080,1920]{2,1,0:T(8,128)}",
         0.0022600280000000003),
        ("copy.1 f32[1080,1920]{1,0:T(8,128)}", 0.0010628280000000003),
        ("divide_multiply_fusion f32[4,1080,1920]{2,1,0:T(8,128)}",
         0.0005599919999999999),
        ("constant_dynamic-slice_fusion f32[1,1080,1920]{2,1,0:T(8,128)}",
         0.0005534460000000002)],
    "idle_gaps": [("retire", 0.089457991), ("dispatch", 0.004771984),
                  ("no_host_span", 0.004587978),
                  ("batcher_wait", 0.00072301), ("submit", 0.00047883)],
}
RECORDED_1080P_PATH = os.path.join(ROOT, "chipbench", "testdata",
                                   "harris-1080p-backlog-300ms.xplane.pb")


def test_the_recorded_trace_reduces_as_before():
    s = tr.reduce(tr.load(RECORDED_1080P_PATH))
    for key, want in RECORDED_1080P.items():
        assert getattr(s, key) == want, key


def test_the_recorded_trace_keeps_every_op_and_program():
    t = tr.load(RECORDED_1080P_PATH)
    s = tr.reduce(t)
    ((w0, w1),) = [(b, b + d) for n, b, d in t.host if n == tr.WINDOW]
    in_window = {n for n, b, d in t.devices["/device:TPU:0"]
                 if min(b + d, w1) > max(b, w0)}
    assert set(s.op_s) == in_window and len(s.op_s) > tr.TOP
    top = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:tr.TOP]
    assert top == s.device_ops
    assert sum(s.op_s.values()) >= sum(v for _, v in s.device_ops)
    # recorded before the stage programs were named after their calls:
    # all three are ``jit_stage(<fingerprint>)``
    stages = [n for n in s.module_s if n.startswith("jit_stage")]
    assert len(stages) == 3
    assert all(v > 0 for v in s.module_s.values())
    assert sum(s.module_s.values()) <= s.window_s

