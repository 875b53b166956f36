"""Multi-device placement: per-replica device pinning, cross-device
transfer accounting, ordered retirement across devices, and the serial →
multi-device hot-swap — all under a forced 4-host-device jax
(``JAX_PLATFORMS=cpu`` + ``XLA_FLAGS=--xla_force_host_platform_device_
count=4``), run in subprocesses because the parent's jax is already
initialized single-device."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_forced(script: str, n_devices: int = 4,
                timeout: float = 600.0) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (flags + " " if flags else "") + \
        f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=ROOT)


PLACEMENT_SCRIPT = textwrap.dedent("""
    import random, threading, time
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import (DeviceInventory, StageProfiler, transfer_ms,
                            linear_ir, partition_optimal, assign_replicas)
    from repro.core.executor import PipelineExecutor

    inv = DeviceInventory.detect()
    assert len(inv) == 4, jax.devices()
    assert inv.jax_device(2) is jax.devices()[2]

    # --- per-replica device pinning: committed results cycle the devices ---
    ex = PipelineExecutor([lambda env: {"y": env["x"] * 2.0}], ["x"], ["y"],
                          replicas=[4], devices=[[0, 1, 2, 3]],
                          inventory=inv, max_in_flight=8)
    hs = ex.submit_many([(jnp.full((8,), float(i)),) for i in range(8)])
    for i, h in enumerate(hs):
        out = h.result()
        np.testing.assert_allclose(np.asarray(out), float(i) * 2.0)
        (dev,) = out.devices()
        assert dev is inv.jax_device(i % 4), (i, dev)
    assert ex.stats().out_of_order_retired == 0
    # per-stage counters carry the pinning
    assert ex.stats().per_stage[0].devices == [0, 1, 2, 3]
    assert ex.stats().per_stage[0].xfer_ms > 0.0
    ex.close()

    # warmup on a pinned executor submits one group per replica ring, so
    # every device builds its executable before traffic (seq coverage)
    exw = PipelineExecutor([lambda env: {"y": env["x"] * 2.0}], ["x"], ["y"],
                           replicas=[4], devices=[[0, 1, 2, 3]],
                           inventory=inv, max_in_flight=8)
    exw.warmup(jnp.zeros((8,)))
    assert exw._seq == 4, exw._seq
    exw.close()

    # --- ordered retirement across devices under randomized jitter ---
    rng = random.Random(7)
    def jittery(env):
        time.sleep(rng.uniform(0.0, 0.004))
        return {"x": env["x"] * 2.0 + 1.0}
    def tail(env):
        time.sleep(rng.uniform(0.0, 0.002))
        return {"y": env["x"] - 5.0}
    prof = StageProfiler(2, min_samples=1)
    rep = PipelineExecutor([jittery, tail], ["x"], ["y"],
                           replicas=[4, 2], devices=[[0, 1, 2, 3], [0, 1]],
                           inventory=inv, max_in_flight=10, profiler=prof)
    toks = [(jnp.full((4,), float(i)),) for i in range(32)]
    got = rep.run(toks)
    st = rep.stats()
    rep.close()
    assert st.out_of_order_retired == 0
    assert st.tokens_retired == 32
    for i, g in enumerate(got):
        np.testing.assert_allclose(np.asarray(g), float(i) * 2.0 - 4.0)
    # per-device attribution landed in the profiler snapshot
    snap = prof.snapshot()
    assert len(snap["per_stage"][0]["devices"]) == 4, snap["per_stage"][0]
    assert set(prof.device_ms(1)) <= {0, 1} and len(prof.device_ms(1)) == 2

    # --- cross-device boundary transfer accounting on a real inventory ---
    ir = linear_ir("x", ["f0", "f1"], [2.0, 2.0], io_shape=(512, 512))
    plan = partition_optimal(ir, max_stages=2)
    assign_replicas(plan, ir, worker_budget=4, inventory=inv)
    nbytes = plan.stages[1].comm_in_bytes
    assert nbytes == 512 * 512 * 4
    if set(plan.stages[0].devices) != set(plan.stages[1].devices):
        want = transfer_ms(nbytes, inv.device_class(0).xfer_bw)
        assert abs(plan.stages[1].xfer_in_ms - want) < 1e-9
        assert plan.stages[1].xfer_in_ms > 0.0
    # multi-device plan + known ir: stage 0 is charged the graph inputs'
    # host-side staging (every admitted group is device_put)
    if len({d for s in plan.stages for d in s.devices}) > 1:
        in_bytes = sum(ir.values[v].nbytes for v in ir.graph_inputs)
        want0 = transfer_ms(in_bytes, inv.device_class(0).xfer_bw)
        assert abs(plan.stages[0].xfer_in_ms - want0) < 1e-9
    print("PLACEMENT-OK")
""")


@pytest.mark.slow
def test_multidevice_pinning_ordering_and_transfer_accounting():
    """Per-replica device pinning (committed ``.devices()`` audit), ordered
    retirement across devices, per-device profiler attribution, and
    cross-device boundary transfer accounting on 4 forced host devices."""
    r = _run_forced(PLACEMENT_SCRIPT)
    assert "PLACEMENT-OK" in r.stdout, r.stderr[-3000:]


HOT_SWAP_SCRIPT = textwrap.dedent("""
    import time
    import jax.numpy as jnp
    import numpy as np
    from repro.core import DeviceInventory, ModuleDatabase, linear_ir
    from repro.launch.serve import RequestQueueServer
    from repro.runtime import ElasticPlanner

    # one dominant stage, so a budget of 6 widens it 4-wide onto 4 devices
    stage_ms = [1.0, 12.0, 1.0]
    keys = [f"f{i}" for i in range(len(stage_ms))]
    db = ModuleDatabase("devices")
    for k, ms in zip(keys, stage_ms):
        def impl(x, _ms=ms):
            time.sleep(_ms / 1e3)
            return jnp.asarray(x) + 1.0
        impl.__name__ = k
        db.register(k, software=impl)
    ir = linear_ir("devices", keys, stage_ms, io_shape=(64,))
    inv = DeviceInventory.detect()
    assert len(inv) == 4
    planner = ElasticPlanner(ir, db=db, inventory=inv)
    ex_serial, _ = planner.executor_for(3, jit=False, worker_budget=3,
                                        max_in_flight=8)
    assert ex_serial.replicas is None and ex_serial.devices is None
    frames = [np.full((64,), float(i), np.float32) for i in range(12)]
    with RequestQueueServer(ex_serial, max_batch=1, max_wait_ms=1.0) as srv:
        reqs = [srv.submit(f) for f in frames[:6]]
        ex_pinned, _ = planner.executor_for(3, jit=False, worker_budget=6,
                                            max_in_flight=14)
        srv.swap_executor(ex_pinned)
        reqs += [srv.submit(f) for f in frames[6:]]
        outs = [r.wait(timeout=120.0) for r in reqs]   # a drop raises here
    for i, out in enumerate(outs):
        np.testing.assert_allclose(np.asarray(out), float(i) + 3.0)
    assert srv.swaps == 1
    st_s, st_p = ex_serial.stats(), ex_pinned.stats()
    assert st_s.tokens_retired + st_p.tokens_retired == 12
    assert st_s.out_of_order_retired == st_p.out_of_order_retired == 0
    wide = ex_pinned.replicas.index(max(ex_pinned.replicas))
    assert ex_pinned.replicas[wide] == 4, ex_pinned.replicas
    assert sorted(ex_pinned.devices[wide]) == [0, 1, 2, 3]
    # the 6 requests submitted after the swap ran on all four devices
    assert len(st_p.per_stage[wide].ran_on) == 4, st_p.per_stage[wide]
    ex_pinned.close()
    ex_serial.close()
    print("HOT-SWAP-OK")
""")


def test_hot_swap_serial_to_device_pinned_mid_stream():
    """A serving loop swaps a serial executor for a device-pinned one
    (the dominant stage 4-wide on 4 devices) mid-stream: every request is
    served, in order, with its own result, and the widened stage ran on
    every device."""
    r = _run_forced(HOT_SWAP_SCRIPT)
    assert "HOT-SWAP-OK" in r.stdout, r.stderr[-3000:]


SERVE_SCRIPT = textwrap.dedent("""
    from repro.launch.serve import serve_pipeline_demo

    stats = serve_pipeline_demo(n_requests=12, max_batch=2, max_wait_ms=2.0,
                                worker_budget="auto", devices=4,
                                size=(48, 64)).stats
    assert stats["requests_served"] == 12, stats
    assert stats["executor"]["out_of_order_retired"] == 0
    print("SERVE-OK", stats["requests_served"])
""")


@pytest.mark.slow
def test_serve_demo_with_devices_and_auto_budget():
    """`--devices`/`--worker-budget auto` path: the serving demo plans
    against the detected inventory and serves every request."""
    r = _run_forced(SERVE_SCRIPT)
    assert "SERVE-OK 12" in r.stdout, r.stderr[-3000:]
