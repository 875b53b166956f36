"""Reduce a profiler trace of one measured window to device busy time, the
time of every device operation and every program, and the device's idle
gaps, each gap named by what the host was doing in it.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are ``/device:TPU:<n>``; their operations are the events of the
``XLA Ops`` line, each named by its HLO instruction (a Pallas kernel's
carries its ``pallas_call`` name, as in ``vmap_cvt_color_.1``).  The
``Async XLA Ops`` line (copies that overlap compute) is not busy time of
its own.  The ``XLA Modules`` line holds the programs those ops ran in,
each named by its jitted function and fingerprint (a stage program of the
served pipeline is ``jit_stage_<calls>(<fingerprint>)``).  Host spans are
the ``jax.profiler.TraceAnnotation`` events the benchmark records on its
own threads and around its calls into the server: ``window`` bounds the
measured window, and the names in :data:`HOST_SPANS` say what the host
was doing.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
# what the host can be doing while a device idles.  Each instant of a gap
# goes to the first of these that was open then: work on the host's path
# first (retire before dispatch, since the batcher retires the oldest group
# inside its dispatch call when the token pool is full), then a producer
# held by backpressure, then the batcher waiting for requests, then a
# client waiting for its result.
HOST_SPANS = ("retire", "dispatch", "upload", "submit", "batcher_wait",
              "client_wait")
NO_SPAN = "no_host_span"
TOP = 10


@dataclass
class Trace:
    """Events as ``(name, start_ns, duration_ns)``, on one clock."""

    devices: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    modules: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    host: list[tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Summary:
    window_s: float
    busy_s: dict[str, float]                 # per device plane
    device_ops: list[tuple[str, float]]      # top ops, seconds, all devices
    idle_gaps: list[tuple[str, float]]       # idle seconds by host activity
    # every op's and every program's seconds in the window, all devices
    op_s: dict[str, float] = field(default_factory=dict)
    module_s: dict[str, float] = field(default_factory=dict)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def op_name(hlo: str) -> str:
    """``%copy.1 = f32[4,1080,1920,3]{3,2,1,0:T(8,128)} copy(...)`` ->
    ``copy.1 f32[4,1080,1920,3]{3,2,1,0:T(8,128)}``: the instruction and
    the type it makes, so that one name in two programs stays apart."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo
    return f"{lhs.lstrip('%')} {rhs.split(' ', 1)[0].rstrip(',')}"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    wanted = set(HOST_SPANS) | {WINDOW}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(e.name), float(e.start_ns),
                                float(e.duration_ns)) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in line.events)
            tr.devices[plane.name] = ops
            tr.modules[plane.name] = modules
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((e.name, float(e.start_ns),
                                float(e.duration_ns))
                               for e in line.events if e.name in wanted)
    return tr


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _minus(a: list[tuple[float, float]], b: list[tuple[float, float]]):
    """Intervals of ``a`` not covered by the merged intervals ``b``."""
    out = []
    for s, e in a:
        for b0, b1 in b:
            if b1 <= s or b0 >= e:
                continue
            if b0 > s:
                out.append((s, b0))
            s = max(s, b1)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out


def _clipped_s(events, w0: float, w1: float, into: dict) -> None:
    """Add each event's seconds inside [w0, w1] to ``into[name]``."""
    for name, s, d in events:
        t = max(0.0, min(s + d, w1) - max(s, w0))
        if t > 0:
            into[name] = into.get(name, 0.0) + t / 1e9


def reduce(tr: Trace) -> Summary:
    windows = [(s, s + d) for n, s, d in tr.host if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one '{WINDOW}' span, "
                           f"found {len(windows)}")
    w0, w1 = windows[0]
    if not tr.devices:
        raise RuntimeError("the trace holds no device plane")
    spans = {k: _union([(s, s + d) for n, s, d in tr.host if n == k],
                       w0, w1) for k in HOST_SPANS}
    busy: dict[str, float] = {}
    ops: dict[str, float] = {}
    modules: dict[str, float] = {}
    idle: dict[str, float] = {}
    for dev, events in sorted(tr.devices.items()):
        merged = _union([(s, s + d) for _, s, d in events], w0, w1)
        busy[dev] = sum(e - s for s, e in merged) / 1e9
        _clipped_s(events, w0, w1, ops)
        _clipped_s(tr.modules.get(dev, ()), w0, w1, modules)
        gaps = _minus([(w0, w1)], merged)
        for k in HOST_SPANS:
            rest = _minus(gaps, spans[k])
            took = sum(e - s for s, e in gaps) - sum(e - s for s, e in rest)
            if took > 0:
                idle[k] = idle.get(k, 0.0) + took / 1e9
            gaps = rest
        if gaps:
            idle[NO_SPAN] = idle.get(NO_SPAN, 0.0) + sum(
                e - s for s, e in gaps) / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy, device_ops=top,
                   idle_gaps=gaps, op_s=ops, module_s=modules)
