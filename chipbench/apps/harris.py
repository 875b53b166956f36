"""The Harris app: OpenCV's ``cornerHarris_Demo`` traced, placed and served
as ``repro.launch.serve.serve_pipeline_demo`` serves it.

A request is one float32 RGB frame ``[H, W, 3]`` (``frame`` in the
configuration); its size is its pixel count.  The check compares each
served frame with the benchmark's own numpy float32 reference of the demo
(``chipbench/reference.py``, block size and ``k`` from ``harris``); its
control is that reference in bfloat16.
"""
from __future__ import annotations

import ml_dtypes

from chipbench import frames, reference
from chipbench.record import Served

REQUIRED = ("frame", "harris")
UNIT = "pixels"
# widest gap, in gray levels, between a served frame and the reference.
# Served frames read 1.5e-5 to 3.1e-5 on a TPU v5e, the bfloat16 control
# 7.7 and more (PERF.md gives the readings)
GAP_LIMIT = 0.1


class Source:
    """Seeded frames of the configuration's size, all of one shape."""

    def __init__(self, config: dict, seed: int):
        f = config["frame"]
        self.shape = (int(f["height"]), int(f["width"]))
        self.harris = config["harris"]
        self.seed = seed

    def device_pool(self, n: int) -> list:
        return frames.device_pool(n, *self.shape, self.seed)

    def host_pool(self, n: int) -> list:
        return frames.host_pool(n, *self.shape, self.seed)

    def warm_items(self) -> list:
        return self.device_pool(1)

    def size(self, item) -> int:
        return int(item.shape[0]) * int(item.shape[1])


def inputs(config: dict, seed: int) -> Source:
    return Source(config, seed)


def build(config: dict, source: Source, devices: list) -> Served:
    """The pipeline as ``repro.launch.serve.serve_pipeline_demo`` builds it,
    warmed on ``source.warm_items()``; deployment settings from
    ``config``.  ``devices`` are the cell's chips; the deployment's own
    ``devices`` setting picks them, as the demo's does."""
    from repro.core import DeviceInventory, courier_offload
    from repro.core.partition import widen_for_deployment
    from repro.core.tracer import Library
    from repro.launch.serve import (RequestQueueServer,
                                    replication_aware_batching)
    from repro.models.harris import corner_harris_demo, make_harris_db

    (warm_frame,) = source.warm_items()
    db = make_harris_db(with_hw=True)
    off = courier_offload(corner_harris_demo(Library(db)), warm_frame, db=db)
    inventory = (DeviceInventory.detect(limit=config["devices"])
                 if config["devices"] else None)
    plan = off.pipeline.plan
    budget = (plan.n_stages + config["extra_workers"]
              if config["extra_workers"] is not None else None)
    replicas, stage_devices = widen_for_deployment(
        plan, off.pipeline.ir, worker_budget=budget, inventory=inventory)
    max_batch, max_wait_ms = config["max_batch"], config["max_wait_ms"]
    if replicas is not None:
        max_batch, max_wait_ms = replication_aware_batching(
            plan, max_batch=max_batch, max_wait_ms=max_wait_ms)
    ex = off.pipeline.executor(microbatch=max_batch, pad_microbatches=True,
                               replicas=replicas, devices=stage_devices,
                               inventory=inventory)
    ex.warmup(warm_frame)
    srv = RequestQueueServer(ex, max_batch=max_batch,
                             max_wait_ms=max_wait_ms)
    return Served(ex, srv, plan_lines(off.pipeline, ex, srv))


def plan_lines(pipe, ex, srv) -> list[str]:
    lines = [f"plan: {pipe.plan.n_stages} stages; server max_batch "
             f"{srv.max_batch}, max_wait_ms {srv.max_wait_ms:g}; executor "
             f"microbatch {ex.microbatch}, pool {ex.pool}"]
    for k, st in enumerate(pipe.plan.stages):
        nodes = ", ".join(f"{pipe.ir.node(n).fn_key}="
                          f"{pipe.ir.node(n).placement.kind}"
                          for n in st.node_names)
        lines.append(f"plan:   stage {k}: {nodes}; replicas {st.replicas}; "
                     f"devices {list(st.devices)}")
    return lines


def control(items: list, source: Source) -> list:
    """The reference in bfloat16, the precision below the float32 the
    configuration states, in the served path's place: its frames for
    ``items``."""
    h = source.harris
    return [reference.harris_demo(x, h["block_size"], h["k"],
                                  dtype=ml_dtypes.bfloat16) for x in items]


def check(outputs: list, items: list, source: Source) -> dict:
    """``max_gray_gap``: the widest gap over the served frames ``outputs``,
    each against the reference of its input frame in ``items``; None
    where nothing was compared.  A pool frame that several outputs carry
    is referenced once."""
    h = source.harris
    refs: dict[int, object] = {}
    gap = None
    for out, item in zip(outputs, items):
        if id(item) not in refs:
            refs[id(item)] = reference.harris_demo(item, h["block_size"],
                                                   h["k"])
        g = reference.max_gap(out, refs[id(item)])
        gap = g if gap is None else max(gap, g)
    return {"max_gray_gap": {"value": gap, "limit": GAP_LIMIT}}
