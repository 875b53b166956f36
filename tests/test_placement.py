"""Structured placement layer: Placement parsing, the device inventory,
the budget governor, device-aware replica assignment + transfer accounting,
replication-aware batching — and the lint gate (repro.analysis.lint) that
keeps raw "hw"/"sw" string literals out of every module except the
back-compat parser."""
import os

import numpy as np
import pytest

from repro.core import (DeviceInventory, DeviceSpec, ModuleDatabase, Node,
                        Placement, PipelinePlan, StagePlan, assign_replicas,
                        assign_stage_devices, default_worker_budget,
                        device_class, is_hw, is_sw, linear_ir,
                        partition_optimal, placement_kind,
                        replicated_bottleneck_ms, resolve_worker_budget,
                        transfer_ms)
from repro.core.ir import CourierIR
from repro.core.placement import AUTO_BUDGET, RESERVED_CORES_ENV

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src", "repro")


# --------------------------------------------------------------------------- #
# Placement: parsing, back-compat, identity
# --------------------------------------------------------------------------- #
def test_placement_parse_backcompat_strings():
    p = Placement.parse("hw")
    assert p.is_hw and not p.is_sw and p.is_assigned
    assert p.device is None and p.replica == 0
    assert Placement.parse("sw").is_sw
    u = Placement.parse("unassigned")
    assert not u.is_assigned and not u.is_hw and not u.is_sw
    assert Placement.parse(None) == Placement.unassigned()
    assert Placement.parse(p) is p                    # idempotent
    with pytest.raises(ValueError, match="unknown placement kind"):
        Placement.parse("fpga")
    with pytest.raises(TypeError):
        Placement.parse(42)


def test_placement_structured_fields_and_rendering():
    p = Placement.hw(device=2, replica=1, mesh_coord=(0, 1))
    assert p.device == 2 and p.replica == 1 and p.mesh_coord == (0, 1)
    assert p.short() == "hw@2.1"
    assert Placement.hw(device=3).short() == "hw@3"
    assert Placement.sw().short() == "sw"
    # with_kind preserves the pinning; on() preserves the kind
    assert p.with_kind("sw").device == 2 and p.with_kind("sw").is_sw
    q = Placement.sw().on(1, replica=2)
    assert q.is_sw and q.device == 1 and q.replica == 2
    # hashable identity for StageFn cache keys
    assert p.key == ("hw", 2, 1)
    assert len({Placement.hw(), Placement.hw(), Placement.sw()}) == 2


def test_placement_helpers_tolerate_legacy_values():
    assert is_hw("hw") and not is_hw("sw") and not is_hw(None)
    assert is_sw("sw") and not is_sw("unassigned")
    assert is_hw(Placement.hw(device=1))
    assert placement_kind("hw") == placement_kind(Placement.hw())


def test_node_placement_parses_strings_and_json_roundtrips():
    n = Node(name="f_0", fn_key="f", placement="hw")
    assert isinstance(n.placement, Placement) and n.placement.is_hw
    ir = linear_ir("t", ["a", "b"], [1.0, 2.0], io_shape=(4,))
    ir.nodes[0].placement = Placement.hw(device=3, replica=2,
                                         mesh_coord=(1, 0))
    ir2 = CourierIR.from_json(ir.to_json())
    p = ir2.nodes[0].placement
    assert isinstance(p, Placement)
    assert (p.kind, p.device, p.replica, p.mesh_coord) == ("hw", 3, 2, (1, 0))
    assert "hw@3.2" in ir2.render()


# --------------------------------------------------------------------------- #
# Lint gate: the AST grep-guard now lives in repro.analysis.lint as the
# `placement-literal` rule (plus the concurrency/style rules); this test
# just asserts the linter reports zero findings over src/.
# --------------------------------------------------------------------------- #
def test_lint_clean_over_src():
    """Every "hw"/"sw" comparison must go through repro.core.placement —
    a raw string literal elsewhere is a refactor leak (docstrings exempt).
    That rule, and the rest of the lint catalog (lock-discipline,
    blocking-in-lock, frozen-dataclass, acquire-without-finally,
    dead-export), must hold across the whole tree."""
    from repro.analysis.lint import lint_paths
    findings = lint_paths([SRC])
    assert not findings, "lint findings over src/:\n  " + \
        "\n  ".join(d.format() for d in findings)


# --------------------------------------------------------------------------- #
# DeviceInventory + budget governor
# --------------------------------------------------------------------------- #
def test_device_inventory_synthetic_and_validation():
    inv = DeviceInventory.host(4)
    assert len(inv) == 4 and inv.homogeneous
    assert inv.spec(2).ordinal == 2 and inv.spec(2).platform == "cpu"
    assert inv.jax_device(1) is None              # planning-only inventory
    assert inv.device_class(0) is device_class("cpu")
    assert "4 devices" in inv.describe()
    with pytest.raises(ValueError, match="at least one"):
        DeviceInventory([])
    with pytest.raises(ValueError, match="dense"):
        DeviceInventory([DeviceSpec(ordinal=1)])
    with pytest.raises(ValueError, match="speed"):
        DeviceSpec(ordinal=0, speed=0.0)


def test_synthetic_tpu_inventory_is_costed_as_its_kind_never_as_cpu():
    tpu = DeviceInventory.host(2, "TPU v5 lite")
    assert tpu.spec(1).platform == "tpu" and tpu.spec(1).kind == "TPU v5 lite"
    assert tpu.device_class(1) is device_class("TPU v5 lite")
    assert tpu.device_class(0) is not device_class("cpu")
    with pytest.raises(ValueError, match="does not match"):
        DeviceSpec(ordinal=0, platform="tpu")     # left with the cpu kind
    with pytest.raises(ValueError, match="does not match"):
        DeviceSpec(ordinal=0, platform="cpu", kind="TPU v5 lite")
    with pytest.raises(ValueError, match="no platform"):
        DeviceInventory.host(2, "tpu")            # a platform is not a kind


def test_device_inventory_detect_matches_jax_devices():
    import jax

    inv = DeviceInventory.detect()
    assert len(inv) == len(jax.devices())
    assert inv.jax_device(0) is jax.devices()[0]
    assert inv.spec(0).platform == jax.devices()[0].platform
    assert inv.spec(0).kind == jax.devices()[0].device_kind
    assert inv.device_class(0).name == jax.devices()[0].device_kind
    with pytest.raises(ValueError, match="limit"):
        DeviceInventory.detect(limit=0)


def test_default_worker_budget_governor(monkeypatch):
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    monkeypatch.delenv(RESERVED_CORES_ENV, raising=False)
    assert default_worker_budget(3) == 7            # 8 cores - 1 reserved
    assert default_worker_budget(3, reserved_cores=4) == 4
    # saturated host: collapses to the one-worker-per-stage floor
    assert default_worker_budget(3, reserved_cores=8) == 3
    monkeypatch.setenv(RESERVED_CORES_ENV, "6")
    assert default_worker_budget(1) == 2            # knob read from the env
    with pytest.raises(ValueError):
        default_worker_budget(0)
    with pytest.raises(ValueError):
        default_worker_budget(1, reserved_cores=-1)


def test_resolve_worker_budget_modes(monkeypatch):
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    monkeypatch.delenv(RESERVED_CORES_ENV, raising=False)
    inv = DeviceInventory.host(4)
    assert resolve_worker_budget(5, 2) == 5                  # explicit wins
    assert resolve_worker_budget(None, 2) is None            # legacy: no widen
    assert resolve_worker_budget(None, 2, inv) == inv.worker_budget(2)
    assert resolve_worker_budget(AUTO_BUDGET, 2) == 7        # the governor
    assert resolve_worker_budget(AUTO_BUDGET, 2, inv) >= 4   # >= one/device
    # a 16-device inventory must be widenable even on a small host
    assert DeviceInventory.host(16).worker_budget(2) >= 16


# --------------------------------------------------------------------------- #
# Device-aware replica assignment + cross-device transfer accounting
# --------------------------------------------------------------------------- #
def _chain_ir(times, io_shape=(256, 256)):
    keys = [f"f{i}" for i in range(len(times))]
    return linear_ir("chain", keys, list(times), io_shape=io_shape)


def test_assign_replicas_pins_each_replica_to_distinct_device():
    ir = _chain_ir([0.5, 6.0, 0.5])
    plan = partition_optimal(ir, max_stages=3)
    inv = DeviceInventory.host(4)
    assign_replicas(plan, ir, worker_budget=6, inventory=inv)
    k = max(range(3), key=lambda i: plan.stages[i].est_time_ms)
    wide = plan.stages[k]
    assert wide.replicas == 4
    assert len(set(wide.devices)) == wide.replicas     # distinct devices
    assert wide.device_speeds == [1.0] * 4
    # every stage got a full per-replica assignment
    for s in plan.stages:
        assert len(s.devices) == s.replicas
    assert plan.stage_devices == [s.devices for s in plan.stages]


def test_assign_replicas_rerun_without_inventory_clears_stale_devices():
    """The mutate-and-rerun API: a later run without an inventory must not
    leave a previous run's per-replica pinnings behind (their lengths
    would no longer match the new replica counts)."""
    ir = _chain_ir([0.5, 6.0, 0.5])
    plan = partition_optimal(ir, max_stages=3)
    assign_replicas(plan, ir, worker_budget=6, inventory=DeviceInventory.host(4))
    assert any(s.devices for s in plan.stages)
    assign_replicas(plan, ir, worker_budget=4)          # no inventory
    assert all(s.devices == [] and s.device_speeds == []
               and s.xfer_in_ms == 0.0 for s in plan.stages)
    assert plan.stage_devices is None
    plan.effective_bottleneck_ms                        # must not raise


def test_assign_stage_devices_picks_earliest_completion_on_heterogeneous():
    """Least-loaded = earliest completion time, not busy-time re-divided
    by speed: a fast-but-busier device must lose to an idle slow one when
    the idle one finishes the share sooner."""
    inv = DeviceInventory([DeviceSpec(ordinal=0, speed=2.0),
                           DeviceSpec(ordinal=1, speed=1.0)])
    # one 60ms stage 1-wide then one 20ms stage 1-wide: the heavy stage
    # takes the fast device (completion 30 < 60); the light stage must
    # take the idle slow device (20 < 30 + 10)
    p = PipelinePlan(stages=[
        StagePlan(node_names=["a"], est_time_ms=60.0),
        StagePlan(node_names=["b"], est_time_ms=20.0)])
    assign_stage_devices(p, inv)
    assert p.stages[0].devices == [0]
    assert p.stages[1].devices == [1]
    assert p.stages[0].device_speeds == [2.0]


def test_assign_replicas_inventory_derives_budget():
    ir = _chain_ir([0.5, 6.0, 0.5])
    plan = partition_optimal(ir, max_stages=3)
    # no worker_budget: the inventory's governor supplies it
    assign_replicas(plan, ir, inventory=DeviceInventory.host(6))
    assert max(plan.replicas) > 1
    with pytest.raises(ValueError, match="worker_budget"):
        assign_replicas(partition_optimal(ir, max_stages=3), ir)


def test_cross_device_boundary_transfer_accounting():
    ir = _chain_ir([2.0, 2.0], io_shape=(512, 512))   # 1 MiB boundaries
    plan = partition_optimal(ir, max_stages=2)
    nbytes = plan.stages[1].comm_in_bytes
    assert nbytes == 512 * 512 * 4
    inv = DeviceInventory.host(2)
    assign_stage_devices(plan, inv)
    if set(plan.stages[0].devices) == set(plan.stages[1].devices):
        assert plan.stages[1].xfer_in_ms == 0.0
    else:
        want = transfer_ms(nbytes, inv.device_class(0).xfer_bw)
        assert plan.stages[1].xfer_in_ms == pytest.approx(want)
        assert want > 0
    # without an ir the graph-input bytes are unknown: stage 0 uncharged
    assert plan.stages[0].xfer_in_ms == 0.0
    # with the ir, a multi-device plan charges stage 0 the graph inputs'
    # host-side staging (the executor device_puts every admitted group)
    plan_ir = partition_optimal(ir, max_stages=2)
    assign_stage_devices(plan_ir, inv, ir=ir)
    if len({d for s in plan_ir.stages for d in s.devices}) > 1:
        in_bytes = sum(ir.values[v].nbytes for v in ir.graph_inputs)
        want0 = transfer_ms(in_bytes, inv.device_class(0).xfer_bw)
        assert plan_ir.stages[0].xfer_in_ms == pytest.approx(want0)
        assert want0 > 0
    # single-device inventory: no transfer anywhere, all ordinals 0 (the
    # executor degrades and pays no staging at all)
    plan1 = partition_optimal(ir, max_stages=2)
    assign_stage_devices(plan1, DeviceInventory.host(1), ir=ir)
    assert all(set(s.devices) == {0} for s in plan1.stages)
    assert all(s.xfer_in_ms == 0.0 for s in plan1.stages)


def test_widen_without_replication_deploys_unpinned_plan():
    """A planner holding an inventory whose widening pass yields no
    replicated stage must deploy a plan with NO device pinnings — the
    executor runs unpinned, so keeping pinnings would charge transfer
    costs never paid and skew later replan gain comparisons."""
    from repro.core import ModuleDatabase
    from repro.runtime import ElasticPlanner

    keys = ["g0", "g1", "g2"]
    db = ModuleDatabase("flat")
    for k in keys:
        def impl(x):
            return x
        impl.__name__ = k
        db.register(k, software=impl)
    ir = linear_ir("flat", keys, [2.0, 2.0, 2.0], io_shape=(512, 512))
    planner = ElasticPlanner(ir, db=db, inventory=DeviceInventory.host(4))
    # budget at the floor: no stage widens
    ex, _ = planner.executor_for(3, jit=False, worker_budget=3)
    plan = planner.current_plan
    assert all(r == 1 for r in plan.replicas)
    assert plan.stage_devices is None
    assert all(s.xfer_in_ms == 0.0 for s in plan.stages)
    assert plan.effective_bottleneck_ms == pytest.approx(plan.bottleneck_ms)
    assert ex.devices is None
    ex.close()


def test_widen_for_deployment_shared_rule():
    """The one deploy-or-degrade helper every site uses: widened plans
    return (replicas, devices); non-widened plans come back unpinned."""
    from repro.core import widen_for_deployment

    ir = _chain_ir([0.5, 6.0, 0.5])
    inv = DeviceInventory.host(4)
    plan = partition_optimal(ir, max_stages=3)
    reps, devs = widen_for_deployment(plan, ir, worker_budget=6,
                                      inventory=inv)
    assert reps == plan.replicas and max(reps) == 4
    assert devs == plan.stage_devices and devs is not None
    # degrade: budget at the floor -> unpinned plan, no stale charges
    plan2 = partition_optimal(ir, max_stages=3)
    reps2, devs2 = widen_for_deployment(plan2, ir, worker_budget=3,
                                        inventory=inv)
    assert reps2 is None and devs2 is None
    assert plan2.stage_devices is None
    assert all(s.xfer_in_ms == 0.0 and s.device_speeds == []
               for s in plan2.stages)
    # no budget, no inventory: legacy no-widen
    plan3 = partition_optimal(ir, max_stages=3)
    assert widen_for_deployment(plan3, ir) == (None, None)
    # the no-budget early return must ALSO clear a previously pinned plan
    plan4 = partition_optimal(ir, max_stages=3)
    assign_replicas(plan4, ir, worker_budget=6, inventory=inv)
    assert plan4.stage_devices is not None
    assert widen_for_deployment(plan4, ir) == (None, None)
    assert plan4.stage_devices is None
    assert all(s.device_speeds == [] and s.xfer_in_ms == 0.0
               for s in plan4.stages)


def test_replan_on_pinned_deployment_does_not_double_charge_xfer():
    """Measured stage times from a device-pinned executor already include
    the staging hop; the replan candidates must not re-add the modeled
    transfer on top."""
    from repro.core import ModuleDatabase, StageProfiler
    from repro.runtime import ElasticPlanner

    keys = ["h0", "h1", "h2"]
    db = ModuleDatabase("pinned")
    for k in keys:
        def impl(x):
            return x
        impl.__name__ = k
        db.register(k, software=impl)
    ir = linear_ir("pinned", keys, [0.5, 6.0, 0.5], io_shape=(512, 512))
    planner = ElasticPlanner(ir, db=db, inventory=DeviceInventory.host(4))
    ex, _ = planner.executor_for(3, jit=False, worker_budget=6)
    assert planner.current_plan.stage_devices is not None  # pinned deploy
    prof = StageProfiler(3, min_samples=1)
    for _ in range(6):
        # the dominant stage drifted 2x: forces a wider replan candidate
        for k, t in enumerate([0.5, 12.0, 0.5]):
            prof.record(k, t)
    d = planner.replan_from_profile(prof, worker_budget=8, jit=False)
    assert d.replanned and d.plan is not None, d.describe()
    # measured-on-device times already reflect staging AND device speed:
    # neither may be re-applied to the candidate's predicted period
    assert all(s.xfer_in_ms == 0.0 and s.device_speeds == []
               for s in d.plan.stages)
    ex.close()
    if d.executor is not None:
        d.executor.close()


def test_warmup_rounds_cover_every_replica_only_when_pinned():
    """A device-pinned executor warms one group per replica ring (groups
    route to replica seq % r, each pinned device building its own
    executable); degraded/unpinned executors keep the single-group
    warmup."""
    from repro.core.executor import PipelineExecutor

    fns = [lambda env: {"y": env["x"] + 1.0}]
    # planning-only inventory -> degraded: one warm group
    ex = PipelineExecutor(fns, ["x"], ["y"], replicas=[3],
                          devices=[[0, 1, 2]],
                          inventory=DeviceInventory.host(3),
                          max_in_flight=6)
    ex.warmup(np.zeros(2))
    assert ex._seq == 1
    ex.close()
    # thread-widened (no devices): also one warm group
    ex2 = PipelineExecutor(fns, ["x"], ["y"], replicas=[3], max_in_flight=6)
    ex2.warmup(np.zeros(2))
    assert ex2._seq == 1
    ex2.close()


def test_device_inventory_rejects_out_of_range_ordinals():
    from repro.core.executor import PipelineExecutor

    inv = DeviceInventory.host(2)
    with pytest.raises(IndexError, match="out of range"):
        inv.spec(-1)
    with pytest.raises(IndexError, match="out of range"):
        inv.jax_device(2)
    with pytest.raises(IndexError, match="out of range"):
        inv.device_class(-1)
    # the executor surfaces a bad devices matrix at construction
    with pytest.raises(IndexError, match="out of range"):
        PipelineExecutor([lambda env: env], ["x"], ["x"], replicas=[1],
                         devices=[[-1]], inventory=inv)


def test_serve_worker_budget_arg_parses_int_auto_and_rejects_garbage():
    import argparse

    from repro.launch.serve import _budget_arg

    assert _budget_arg("8") == 8
    assert _budget_arg("auto") == "auto"
    with pytest.raises(argparse.ArgumentTypeError, match="expected an int"):
        _budget_arg("fast")


def test_effective_bottleneck_includes_xfer_and_speeds():
    p = PipelinePlan(stages=[
        StagePlan(node_names=["a"], est_time_ms=4.0, replicas=2,
                  devices=[0, 1], device_speeds=[1.0, 1.0]),
        StagePlan(node_names=["b"], est_time_ms=1.0, xfer_in_ms=1.5),
    ])
    # stage 0: 4/2 = 2.0; stage 1: 1.0 + 1.5 xfer = 2.5 → bottleneck
    assert p.effective_bottleneck_ms == pytest.approx(2.5)
    # a faster device raises the widened stage's aggregate rate
    p.stages[0].device_speeds = [1.0, 3.0]
    assert replicated_bottleneck_ms([4.0], [2], [[1.0, 3.0]]) == \
        pytest.approx(1.0)
    with pytest.raises(ValueError, match="replica speeds"):
        replicated_bottleneck_ms([4.0], [2], [[1.0]])
    with pytest.raises(ValueError, match="> 0"):
        replicated_bottleneck_ms([4.0], [2], [[1.0, 0.0]])
    with pytest.raises(ValueError, match="speed vectors"):
        replicated_bottleneck_ms([4.0, 1.0], [2, 1], [[1.0, 1.0]])


def test_per_device_class_roofline_costing():
    from repro.core import NodeCost

    c = NodeCost(flops=1e9, bytes_rw=1e6)
    t_tpu = c.time_ms(device=device_class("TPU v5 lite"))
    t_cpu = c.time_ms(device=device_class("cpu"))
    assert t_cpu > t_tpu                      # same op, slower device class
    assert c.time_ms() == pytest.approx(t_tpu)   # default = TPU v5e table
    with pytest.raises(ValueError, match="no roofline constants"):
        device_class("nonsense")              # unknown kind: error, no default
    with pytest.raises(ValueError):
        device_class("tpu")                   # a platform is not a kind
    # measured times win regardless of device class
    m = NodeCost(flops=1e9, bytes_rw=1e6, measured_ms=7.0)
    assert m.time_ms(device=device_class("cpu")) == 7.0
    assert transfer_ms(0) == 0.0
    assert transfer_ms(16e9) == pytest.approx(1000.0)   # 16 GB @ 16 GB/s
    with pytest.raises(ValueError):
        transfer_ms(1.0, 0.0)


# --------------------------------------------------------------------------- #
# Replication-aware batching (serving satellite)
# --------------------------------------------------------------------------- #
def test_replication_aware_batching_scales_by_effective_period():
    from repro.launch.serve import replication_aware_batching

    serial = PipelinePlan(stages=[
        StagePlan(node_names=["a"], est_time_ms=6.0),
        StagePlan(node_names=["b"], est_time_ms=1.0)])
    assert replication_aware_batching(serial, max_batch=4, max_wait_ms=4.0) \
        == (4, 4.0)                                   # ratio 1: unchanged
    widened = PipelinePlan(stages=[
        StagePlan(node_names=["a"], est_time_ms=6.0, replicas=3),
        StagePlan(node_names=["b"], est_time_ms=1.0)])
    mb, wait = replication_aware_batching(widened, max_batch=4,
                                          max_wait_ms=4.0)
    assert mb == 12 and wait == pytest.approx(4.0 / 3.0)   # ratio 3
    # growth clamp + wait floor
    huge = PipelinePlan(stages=[
        StagePlan(node_names=["a"], est_time_ms=64.0, replicas=64),
        StagePlan(node_names=["b"], est_time_ms=1.0)])
    mb, wait = replication_aware_batching(huge, max_batch=4, max_wait_ms=4.0)
    assert mb == 16 and wait == pytest.approx(1.0)         # clamped at 4x
    mb, wait = replication_aware_batching(widened, max_batch=1,
                                          max_wait_ms=0.3)
    assert mb >= 1 and wait >= 0.25
    with pytest.raises(ValueError):
        replication_aware_batching(serial, max_batch=0, max_wait_ms=1.0)


def test_request_queue_server_applies_plan_sizing():
    from repro.core.executor import PipelineExecutor
    from repro.launch.serve import RequestQueueServer

    ex = PipelineExecutor([lambda env: {"y": env["x"]}], ["x"], ["y"])
    plan = PipelinePlan(stages=[
        StagePlan(node_names=["a"], est_time_ms=8.0, replicas=4)])
    srv = RequestQueueServer(ex, max_batch=2, max_wait_ms=4.0, plan=plan)
    assert srv.max_batch == 8 and srv.max_wait_ms == pytest.approx(1.0)
    srv2 = RequestQueueServer(ex, max_batch=2, max_wait_ms=4.0)
    assert srv2.max_batch == 2 and srv2.max_wait_ms == 4.0


# --------------------------------------------------------------------------- #
# Executor device plumbing (single-real-device paths)
# --------------------------------------------------------------------------- #
def test_executor_devices_validation_and_single_device_degrade():
    from repro.core.executor import PipelineExecutor

    fns = [lambda env: {"y": env["x"] + 1.0}]
    with pytest.raises(ValueError, match="requires replicas"):
        PipelineExecutor(fns, ["x"], ["y"], devices=[[0]])
    with pytest.raises(ValueError, match="per replica"):
        PipelineExecutor(fns, ["x"], ["y"], replicas=[2], devices=[[0]])
    # planning-only inventory (no jax devices): degrade, no staging hop
    from repro.core import StageProfiler

    inv = DeviceInventory.host(4)
    prof = StageProfiler(1, min_samples=1)
    ex = PipelineExecutor(fns, ["x"], ["y"], replicas=[2],
                          devices=[[0, 1]], inventory=inv, profiler=prof)
    assert ex._replica_devs is None                  # degraded to threads
    assert ex.stats().per_stage[0].devices == [0, 1]   # config echo only
    out = ex.run([(np.zeros(2),), (np.ones(2),)])
    np.testing.assert_allclose(np.asarray(out[1]), 2.0)
    ex.close()
    # degraded pinning is NOT in effect: samples must not be attributed
    # to device ordinals nothing was staged onto
    assert prof.device_ms(0) == {}
    assert prof.samples(0) == 2 and prof.replica_ms(0) != {}
    # stats dict carries the new per-stage fields
    d = ex.stats().as_dict()["per_stage"][0]
    assert d["devices"] == [0, 1] and "xfer_ms" in d


def test_profiler_per_device_attribution():
    from repro.core import StageProfiler

    p = StageProfiler(2, min_samples=1)
    for _ in range(3):
        p.record(0, 10.0, replica=0, device=2)
        p.record(0, 20.0, replica=1, device=3)
    p.record(1, 5.0)
    assert set(p.device_ms(0)) == {2, 3}
    assert p.device_ms(0)[2] == pytest.approx(10.0)
    assert p.device_ms(1) == {}
    snap = p.snapshot()
    assert snap["per_stage"][0]["devices"]["3"]["samples"] == 3
    assert "devices" not in snap["per_stage"][1]
    p.reset()
    assert p.device_ms(0) == {}
