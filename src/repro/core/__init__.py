"""Courier-TPU core — the paper's contribution as a composable JAX library.

Paper: "An Automatic Mixed Software Hardware Pipeline Builder for CPU-FPGA
Platforms" (Miyajima, Thomas, Amano, 2014) — re-targeted to TPU pods.

Flow (paper Fig. 1):
  Frontend.trace        Steps 1-5  — runtime trace of an unmodified callable
  (user edit_ir hook)   Steps 6-7  — inspect/modify the Courier IR
  PipelineGenerator     Step 8     — DB lookup, fusion, balanced partition,
                                     mixed sw/hw token pipeline
  courier_offload       Step 9     — deployable wrapper w/ Off-load Switcher
"""
from .costmodel import (CostModel, DeviceClass, DEVICE_CLASSES,
                        FusionEstimate, NodeCost, PEAK_FLOPS_BF16,
                        HBM_BW, HOST_XFER_BW, ICI_BW_PER_LINK, HBM_BYTES,
                        PROFILE_MARGIN, VMEM_BYTES, attention_cost,
                        device_class, elementwise_cost, fused_cost,
                        matmul_cost, measured_contradicts,
                        replicated_bottleneck_ms, stencil_cost, transfer_ms)
from .database import ModuleDatabase, ModuleEntry, default_db
from .executor import (ExecutorClosed, ExecutorStats, PendingToken,
                       PipelineExecutor, StageCounters)
from .ir import CourierIR, Node, Value, linear_ir
from .offloader import OffloadedFunction, OffloadPlan, courier_offload
from .partition import (PipelinePlan, StagePlan, assign_replicas,
                        assign_stage_devices, clear_stage_devices,
                        fuse_adjacent_hw, fused_working_set_bytes,
                        make_model_fused_cost, partition_optimal,
                        partition_paper, split_fused_node,
                        widen_for_deployment)
from .pipeline import (BuiltPipeline, PipelineGenerator, StageFn,
                       assign_placements, make_stage_fns)
from .placement import (AUTO_BUDGET, DeviceInventory, DeviceSpec,
                        InventoryDiff, Placement, default_worker_budget,
                        is_hw, is_sw, placement_kind, resolve_worker_budget)
from .profiler import StageProfiler
from .spmd_pipeline import (pipeline_microbatches, spmd_pipeline_fn,
                            stack_stage_params, stage_apply)
from .tracer import Frontend, Library, deploy

__all__ = [
    "CostModel", "DeviceClass", "DEVICE_CLASSES", "FusionEstimate",
    "NodeCost", "PEAK_FLOPS_BF16", "HBM_BW", "HOST_XFER_BW",
    "ICI_BW_PER_LINK", "HBM_BYTES", "PROFILE_MARGIN", "VMEM_BYTES",
    "attention_cost", "device_class", "elementwise_cost", "fused_cost",
    "matmul_cost", "measured_contradicts",
    "replicated_bottleneck_ms", "stencil_cost", "transfer_ms",
    "ModuleDatabase", "ModuleEntry", "default_db",
    "ExecutorClosed", "ExecutorStats", "PendingToken", "PipelineExecutor",
    "StageCounters",
    "CourierIR", "Node", "Value", "linear_ir",
    "OffloadedFunction", "OffloadPlan", "courier_offload",
    "PipelinePlan", "StagePlan", "assign_replicas", "assign_stage_devices",
    "clear_stage_devices", "fuse_adjacent_hw", "fused_working_set_bytes",
    "make_model_fused_cost", "partition_optimal", "partition_paper",
    "split_fused_node", "widen_for_deployment",
    "BuiltPipeline", "PipelineGenerator", "StageFn", "assign_placements",
    "make_stage_fns",
    "AUTO_BUDGET", "DeviceInventory", "DeviceSpec", "InventoryDiff",
    "Placement", "default_worker_budget", "is_hw", "is_sw",
    "placement_kind", "resolve_worker_budget",
    "StageProfiler",
    "pipeline_microbatches", "spmd_pipeline_fn", "stack_stage_params",
    "stage_apply",
    "Frontend", "Library", "deploy",
]
