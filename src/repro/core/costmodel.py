"""Cost model — the TPU analog of the paper's processing-time sources.

Courier-FPGA obtains per-function processing times from (a) the Frontend's
runtime profile for software functions and (b) the logic-synthesis tool's
latency report for hardware modules (paper Sect. III-B.4).  On TPU we have
no synthesis report, so the "hardware" estimate is an analytical roofline:

    t = max(flops / PEAK_FLOPS, bytes / HBM_BW)  (+ collective term)

using TPU v5e constants by default: 197 TFLOP/s bf16 per chip, 819 GB/s
HBM bandwidth, ~50 GB/s per ICI link.  Per-device constants are keyed by
the ``device_kind`` JAX reports (:data:`DEVICE_CLASSES`).

Both sources feed the same ``NodeCost`` record so the Pipeline Generator's
balanced partitioning (paper Sect. III-B.4) is agnostic to where a time
came from — exactly as in the paper, where measured SW times and estimated
HW times are mixed in one table.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Sequence

import numpy as np

# ---- TPU v5e hardware constants (per chip) -------------------------------- #
# Published peaks of one TPU v5e chip (Google Cloud documentation, "TPU v5e"):
# 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW_PER_LINK = 50e9          # bytes/s per link (per direction)
HBM_BYTES = 16 * 1024**3        # 16 GiB HBM per chip
# Scoped VMEM every Pallas kernel here is compiled with
# (``repro.kernels.backend.compiler_params``); the fusion model, the plan
# verifier and the fused kernel's row-block search check working sets
# against the same figure.  A v5e core has 128 MiB of VMEM; Mosaic's default scoped
# limit is 16 MiB, too small for cvtColor's 3-on-lanes block at 1920 wide.
VMEM_BYTES = 64 * 1024**2
MXU_TILE = (128, 128)           # systolic array tile
LANE = 128                      # vector lane width
SUBLANE = 8

# Host <-> device (and device <-> device via host) staging bandwidth used to
# charge stage boundaries whose producer and consumer sit on different
# devices — the paper's "communication frequency of intermediate data"
# term, now with a real bandwidth attached (PCIe gen4 x16 ballpark).
HOST_XFER_BW = 16e9             # bytes/s


# --------------------------------------------------------------------------- #
# Device classes — per-device-class roofline constants
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class DeviceClass:
    """Roofline constants for one class of placeable device.

    The paper costs a hardware module against the synthesis report of the
    *target FPGA part*; here every :class:`~repro.core.placement.
    DeviceSpec` maps to a class so a replica assigned to device ``k`` is
    costed against that device's constants instead of a single global
    TPU-v5e table (a CPU-class replica of the same stage is much slower,
    and the planner should know).
    """

    name: str
    peak_flops: float = PEAK_FLOPS_BF16
    hbm_bw: float = HBM_BW
    ici_bw: float = ICI_BW_PER_LINK
    xfer_bw: float = HOST_XFER_BW       # host<->device staging bandwidth
    vmem_bytes: int = VMEM_BYTES


# keyed by ``jax.Device.device_kind``
DEVICE_CLASSES: dict[str, DeviceClass] = {
    # TPU v5e: the published peaks above
    "TPU v5 lite": DeviceClass("TPU v5 lite"),
    # one beefy host core + DDR: the "software filter on a CPU core" class
    "cpu": DeviceClass("cpu", peak_flops=1e11, hbm_bw=3e10, ici_bw=1e10,
                       xfer_bw=30e9, vmem_bytes=32 * 1024**2),
}


def device_class(kind: str) -> DeviceClass:
    """Roofline constants for a ``device_kind``; an unknown kind is an
    error, never a silent default."""
    try:
        return DEVICE_CLASSES[kind]
    except KeyError:
        raise ValueError(
            f"no roofline constants for device kind {kind!r}; add its "
            f"published peaks to DEVICE_CLASSES "
            f"(known: {sorted(DEVICE_CLASSES)})") from None


def transfer_ms(nbytes: float, bw_bytes_per_s: float = HOST_XFER_BW) -> float:
    """Wall ms to move ``nbytes`` across a stage boundary that changes
    device — one staging hop at the slower side's transfer bandwidth."""
    if nbytes <= 0:
        return 0.0
    if bw_bytes_per_s <= 0:
        raise ValueError(f"transfer bandwidth must be > 0 "
                         f"(got {bw_bytes_per_s})")
    return 1e3 * float(nbytes) / float(bw_bytes_per_s)


@dataclass
class NodeCost:
    """Roofline terms for one IR node (or one compiled step)."""

    flops: float = 0.0
    bytes_rw: float = 0.0            # HBM traffic (read+write)
    coll_bytes: float = 0.0          # inter-chip bytes over ICI
    measured_ms: float | None = None  # Frontend profile, wins when present

    def time_ms(self, chips: int = 1, ici_links: int = 1,
                device: DeviceClass | None = None) -> float:
        """Roofline time; ``device`` costs against that device class's
        constants instead of the global TPU-v5e table (measured times
        still win — a profile is of the device that ran it)."""
        if self.measured_ms is not None:
            return self.measured_ms
        peak = device.peak_flops if device is not None else PEAK_FLOPS_BF16
        hbm = device.hbm_bw if device is not None else HBM_BW
        ici = device.ici_bw if device is not None else ICI_BW_PER_LINK
        t_compute = self.flops / (chips * peak)
        t_memory = self.bytes_rw / (chips * hbm)
        t_coll = self.coll_bytes / (chips * ici_links * ici)
        return 1e3 * (max(t_compute, t_memory) + t_coll)

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.bytes_rw, 1.0)

    def dominant(self) -> str:
        t_c = self.flops / PEAK_FLOPS_BF16
        t_m = self.bytes_rw / HBM_BW
        t_x = self.coll_bytes / ICI_BW_PER_LINK
        return ("compute", "memory", "collective")[int(np.argmax([t_c, t_m, t_x]))]

    def __add__(self, other: "NodeCost") -> "NodeCost":
        m = None
        if self.measured_ms is not None or other.measured_ms is not None:
            # mixed measured+estimated sum: the operand without a profile
            # contributes its roofline estimate, not 0 — otherwise a stage
            # holding one profiled and one estimated node underreports.
            m = self.time_ms() + other.time_ms()
        return NodeCost(self.flops + other.flops,
                        self.bytes_rw + other.bytes_rw,
                        self.coll_bytes + other.coll_bytes, m)


# --------------------------------------------------------------------------- #
# Fusion model — VMEM-resident intermediates (the TPU dataflow-fusion analog)
# --------------------------------------------------------------------------- #
@dataclass
class FusionEstimate:
    """Predicted economics of fusing a run of adjacent nodes into one kernel.

    On the paper's FPGA the fused cvtColor+cornerHarris module was *slower*
    than its pipelined parts, so Courier rejected it.  On TPU the economics
    usually invert: a fused kernel keeps the intermediates resident in VMEM,
    so their HBM write+readback traffic disappears — but only while the
    fused working set actually fits VMEM.  This record carries both sides of
    that decision so callers (``fuse_adjacent_hw``) can accept wins and
    reject spills.
    """

    cost: NodeCost                  # the fused kernel's roofline record
    hbm_bytes_saved: float          # intermediate write+read traffic removed
    vmem_required: int              # fused working-set bytes (tiles + halos)
    vmem_bytes: int                 # capacity it was checked against
    unfused_ms: float               # sum of the parts' times (seq. latency)

    @property
    def fits_vmem(self) -> bool:
        return self.vmem_required <= self.vmem_bytes

    @property
    def fused_ms(self) -> float:
        """Predicted fused-kernel time; +inf when the working set spills.

        Returning +inf (rather than a degraded estimate) makes a spilling
        fusion lose against *any* acceptance threshold, which is exactly the
        contract ``fuse_adjacent_hw`` needs.
        """
        if not self.fits_vmem:
            return float("inf")
        return self.cost.time_ms()

    @property
    def wins(self) -> bool:
        return self.fits_vmem and self.fused_ms < self.unfused_ms

    def describe(self) -> str:
        return (f"FusionEstimate(fused={self.fused_ms:.4f} ms, "
                f"unfused={self.unfused_ms:.4f} ms, "
                f"hbm_saved={self.hbm_bytes_saved / 1e6:.2f} MB, "
                f"vmem={self.vmem_required / 1e6:.2f}/"
                f"{self.vmem_bytes / 1e6:.0f} MB, "
                f"{'fits' if self.fits_vmem else 'SPILLS'})")


def fused_cost(parts: "list[NodeCost]", intermediate_bytes: float, *,
               vmem_required: int = 0,
               vmem_bytes: int = VMEM_BYTES) -> FusionEstimate:
    """Model a fused kernel over ``parts`` with VMEM-resident intermediates.

    ``intermediate_bytes`` is the total size of the values flowing *between*
    the fused parts.  Unfused, each such value costs one HBM write (by its
    producer) and one HBM read (by its consumer); fused, it never leaves
    VMEM, so ``2 * intermediate_bytes`` of traffic vanishes.  FLOPs are
    conserved — fusion only moves data, it doesn't remove arithmetic.

    ``vmem_required`` is the fused kernel's resident working set (input +
    intermediate + output tiles incl. halos).  When it exceeds
    ``vmem_bytes`` the fusion would spill and the estimate reports
    ``fused_ms = inf`` so callers reject it.

    Parts' ``measured_ms`` are deliberately ignored for the *fused* record:
    the fused kernel is new code, so only the roofline speaks for it; the
    measured times still make up ``unfused_ms`` (the side we compare with).
    """
    if not parts:
        raise ValueError("fused_cost needs at least one part")
    flops = sum(p.flops for p in parts)
    byts = sum(p.bytes_rw for p in parts)
    coll = sum(p.coll_bytes for p in parts)
    saved = min(2.0 * intermediate_bytes, byts)     # can't save more than all
    cost = NodeCost(flops=flops, bytes_rw=byts - saved, coll_bytes=coll)
    unfused_ms = sum(p.time_ms() for p in parts)
    return FusionEstimate(cost=cost, hbm_bytes_saved=saved,
                          vmem_required=int(vmem_required),
                          vmem_bytes=int(vmem_bytes), unfused_ms=unfused_ms)


# --------------------------------------------------------------------------- #
# Analytical costs for common op families
# --------------------------------------------------------------------------- #
def matmul_cost(m: int, n: int, k: int, bytes_per_el: int = 2,
                batch: int = 1) -> NodeCost:
    flops = 2.0 * batch * m * n * k
    byts = bytes_per_el * batch * (m * k + k * n + m * n)
    return NodeCost(flops=flops, bytes_rw=byts)


def elementwise_cost(numel: int, flops_per_el: float = 1.0,
                     bytes_per_el: int = 2, n_operands: int = 2) -> NodeCost:
    return NodeCost(flops=flops_per_el * numel,
                    bytes_rw=bytes_per_el * numel * n_operands)


def stencil_cost(h: int, w: int, c: int, taps: int,
                 bytes_per_el: int = 4) -> NodeCost:
    """k-tap 2-D stencil (Sobel, box filter ...) — the Harris building block."""
    numel = h * w * c
    return NodeCost(flops=2.0 * taps * numel, bytes_rw=2.0 * bytes_per_el * numel)


def attention_cost(batch: int, q_len: int, kv_len: int, heads: int,  # lint: allow-dead(cost-model API for LM workloads; kept for config-driven planners)
                   head_dim: int, kv_heads: int | None = None,
                   window: int | None = None, bytes_per_el: int = 2) -> NodeCost:
    """QK^T + softmax + PV cost; sliding-window caps kv_len at window."""
    kv_heads = kv_heads or heads
    eff_kv = min(kv_len, window) if window else kv_len
    flops = 2.0 * batch * heads * q_len * eff_kv * head_dim * 2  # QK^T and PV
    flops += 5.0 * batch * heads * q_len * eff_kv                # softmax-ish
    byts = bytes_per_el * batch * (
        heads * q_len * head_dim                      # Q
        + 2 * kv_heads * eff_kv * head_dim            # K, V
        + heads * q_len * head_dim)                   # out
    return NodeCost(flops=flops, bytes_rw=byts)


# --------------------------------------------------------------------------- #
# Stage replication (TBB parallel filters — widen instead of re-balance)
# --------------------------------------------------------------------------- #
def replicated_bottleneck_ms(stage_ms: "Sequence[float]",
                             replicas: "Sequence[int]",
                             speeds: "Sequence[Sequence[float]] | None" = None,
                             ) -> float:
    """Predicted steady-state token period of a replicated pipeline plan.

    A stage whose one-worker service time is ``t`` and which runs ``r``
    parallel workers retires a token every ``t / r`` ms once its replicas
    are saturated (the TBB parallel-filter throughput model), so the
    pipeline period is ``max_k t_k / r_k``.  This is the quantity the
    re-planner compares between "move the boundaries" and "widen the
    bottleneck" candidates; with all replicas 1 it reduces to the plain
    bottleneck.  Host-side hand-off overhead is deliberately folded into
    the measured ``stage_ms`` (the profiler times the whole stage
    invocation), not modeled separately.

    ``speeds`` (optional) carries one relative-throughput factor per
    replica per stage (device-aware planning: a replica pinned to a
    faster device class drains more than ``1/r`` of the stream).  Stage
    ``k``'s aggregate rate is ``sum_j speed_kj / t_k``, so its period is
    ``t_k / sum_j speed_kj`` — equal to ``t_k / r_k`` when every replica
    runs at the class baseline.  An empty per-stage entry means
    "homogeneous at speed 1".
    """
    if len(stage_ms) != len(replicas):
        raise ValueError(f"{len(stage_ms)} stage times vs "
                         f"{len(replicas)} replica counts")
    if speeds is not None and len(speeds) != len(stage_ms):
        raise ValueError(f"{len(stage_ms)} stage times vs "
                         f"{len(speeds)} speed vectors")
    if not stage_ms:
        return 0.0
    period = 0.0
    for k, (t, r) in enumerate(zip(stage_ms, replicas)):
        r = max(int(r), 1)
        sp = list(speeds[k]) if speeds is not None and speeds[k] else None
        if sp is not None:
            if len(sp) != r:
                raise ValueError(f"stage {k}: {len(sp)} replica speeds "
                                 f"for {r} replicas")
            if any(s <= 0 for s in sp):
                raise ValueError(f"stage {k}: replica speeds must be > 0")
            rate = sum(sp)
        else:
            rate = float(r)
        period = max(period, float(t) / rate)
    return period


# --------------------------------------------------------------------------- #
# Measured vs modeled (the online-profile write-back contract)
# --------------------------------------------------------------------------- #
PROFILE_MARGIN = 1.5      # default measured-vs-model contradiction factor


def measured_contradicts(model_ms: float | None, measured_ms: float | None,
                         margin: float = PROFILE_MARGIN) -> bool:
    """True when a measurement deviates from the model by ``margin``x.

    The re-planner's trigger condition: a measured stage/node time that is
    ``>= margin`` times the estimate (or ``<= 1/margin`` of it) means the
    cost table the current plan was balanced on is wrong, so fuse/no-fuse
    and stage-boundary decisions deserve a revisit.  ``None`` on either
    side never contradicts (nothing measured, or nothing modeled).
    """
    if model_ms is None or measured_ms is None:
        return False
    if margin < 1.0:
        raise ValueError(f"margin must be >= 1.0 (got {margin})")
    if model_ms <= 0.0:
        return measured_ms > 0.0
    ratio = measured_ms / model_ms
    return ratio >= margin or ratio <= 1.0 / margin


# --------------------------------------------------------------------------- #
# Measured profiles (the Frontend's profile log)
# --------------------------------------------------------------------------- #
def measure_ms(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Wall-clock a callable (blocks on JAX async dispatch via block_until_ready)."""
    import jax

    def _run():
        out = fn(*args)
        return jax.block_until_ready(out)

    for _ in range(warmup):
        _run()
    t0 = time.perf_counter()
    for _ in range(iters):
        _run()
    return (time.perf_counter() - t0) / iters * 1e3


@dataclass
class CostModel:
    """Per-fn_key cost providers; mixes measured and analytical sources.

    ``measured`` holds per-function EMA wall times fed by the online
    profiler (:meth:`observe`); they *supersede* the analytical providers
    during :meth:`annotate` — the paper's rule that a runtime profile
    outranks a synthesis-report estimate, kept live while serving.
    """

    chips: int = 1
    ici_links: int = 1
    providers: dict[str, Callable[..., NodeCost]] = field(default_factory=dict)
    measured: dict[str, float] = field(default_factory=dict)
    measure_alpha: float = 0.25

    def register(self, fn_key: str, provider: Callable[..., NodeCost]) -> None:
        self.providers[fn_key] = provider

    def observe(self, fn_key: str, ms: float) -> float:
        """Fold one measured wall time into the per-function EMA."""
        prev = self.measured.get(fn_key)
        a = self.measure_alpha
        self.measured[fn_key] = float(ms) if prev is None \
            else (1.0 - a) * prev + a * float(ms)
        return self.measured[fn_key]

    def cost(self, fn_key: str, *args, **kwargs) -> NodeCost:
        if fn_key not in self.providers:
            raise KeyError(f"no cost provider for {fn_key!r}")
        return self.providers[fn_key](*args, **kwargs)

    def annotate(self, ir) -> None:
        """Fill Node.flops / bytes from providers when a node has no profile.

        Measured times (:meth:`observe`) win over both the provider estimate
        and any pre-existing estimate on the node; nodes they touch are
        marked ``time_source="profile"`` so later estimator passes leave
        them alone.
        """
        for n in ir.nodes:
            if n.fn_key in self.providers:
                shapes = [ir.values[i].shape for i in n.inputs]
                dtypes = [ir.values[i].dtype for i in n.inputs]
                try:
                    c = self.providers[n.fn_key](shapes, dtypes, n.params)
                except TypeError:
                    continue
                n.flops, n.bytes_rw = c.flops, c.bytes_rw
                if n.time_ms is None:
                    n.time_ms = c.time_ms(self.chips, self.ici_links)
            m = self.measured.get(n.fn_key)
            if m is not None:
                n.time_ms = m
                n.time_source = "profile"
