"""What one run records, what an app hands it, and the arithmetic every
metric reader shares."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any


@dataclass
class FrameRecord:
    """One submitted frame; times are ``time.perf_counter`` seconds."""

    index: int                   # submission order
    pool_index: int              # which pool frame it carried
    t_due: float | None          # open loop: when it was due; closed: None
    t_submit: float              # when the generator began to send it
    size: int | None = None      # its work, in its app's unit (pixels, tokens)
    t_ready: float | None = None  # when the client held its ready result
    queue_ms: float | None = None  # submit to batch pickup (server's clock)
    error: str | None = None


@dataclass
class Served:
    """What an app's ``build`` hands the run: the executor and the
    ``RequestQueueServer`` in front of it, built and warmed on every
    shape the source holds, and the lines that describe its plan."""

    executor: Any
    server: Any
    plan_lines: list[str]


@dataclass
class RunData:
    """Everything a metric reader may read about one run."""

    config: dict
    peak: dict
    seconds: float
    t0: float                     # window start
    t1: float                     # window end
    setup_s: float
    frames: list[FrameRecord] = field(default_factory=list)
    executor: dict | None = None  # ExecutorStats.as_dict() of the window
    trace: Any = None             # trace_reduce.Summary (traced runs)

    @property
    def open_loop(self) -> bool:
        return bool(self.frames) and self.frames[0].t_due is not None

    def attempted(self) -> list[FrameRecord]:
        """Frames due in the window (open loop) or sent in it (closed)."""
        if self.open_loop:
            return [f for f in self.frames if f.t_due < self.t1]
        return [f for f in self.frames if f.t_submit < self.t1]

    def ready_in_window(self) -> list[FrameRecord]:
        return [f for f in self.frames if f.error is None
                and f.t_ready is not None and self.t0 <= f.t_ready <= self.t1]

    def latencies_ms(self) -> list[float]:
        """Due time to ready result, for every frame due in the window."""
        return [(f.t_ready - f.t_due) * 1e3 for f in self.attempted()
                if f.t_ready is not None]

    def per_frame(self, counter: str) -> float | None:
        """A per-stage executor counter summed over stages, per retired
        frame of the window."""
        if not self.executor or not self.executor["tokens_retired"]:
            return None
        total = sum(s[counter] for s in self.executor["per_stage"])
        return total / self.executor["tokens_retired"]


def percentile(values, q: float) -> float | None:
    """Linear-interpolation percentile (numpy's default) over every value;
    None for no values."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return None
    rank = q / 100.0 * (len(vals) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (rank - lo)
