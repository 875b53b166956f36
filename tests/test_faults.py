"""Fault-injection harness, executor retry/quarantine, elastic inventory
recovery, serve-layer shutdown/deadline semantics — and the chaos soak.

The executor-level tests use numpy stage fns (no jit: injection + retries
are scheduler behavior, not compilation behavior); values encode the token
index so any seq/slot mix-up shows up as a wrong result, not just a
counter."""
import os
import threading
import time

import numpy as np
import pytest

from repro.core import DeviceInventory, StageProfiler
from repro.core.executor import ExecutorClosed, PipelineExecutor
from repro.core.executor import _SeqRing
from repro.launch.serve import DeadlineExceeded, RequestQueueServer
from repro.runtime.faults import (DeviceLostError, FaultInjector, FaultPlan,
                                  InjectedFault, _hash_draw, as_injector)


# --------------------------------------------------------------------------- #
# FaultPlan / FaultInjector: deterministic scripting
# --------------------------------------------------------------------------- #
def test_transient_fires_on_scripted_counts_only():
    plan = FaultPlan().transient(0, at_calls=[1, 3])
    inj = plan.build()
    fired = []
    for call in range(5):
        try:
            inj.on_stage_call(0)
        except InjectedFault:
            fired.append(call)
    assert fired == [1, 3]
    assert inj.injected == 2
    assert inj.stage_calls(0) == 5
    inj.on_stage_call(1)                       # other stages unaffected
    # a fresh build of the same plan replays the same schedule
    fired2 = []
    inj2 = plan.build()
    for call in range(5):
        try:
            inj2.on_stage_call(0)
        except InjectedFault:
            fired2.append(call)
    assert fired2 == fired


def test_random_transients_reproducible_and_validated():
    assert 0.0 <= _hash_draw(7, 0, 0) < 1.0
    with pytest.raises(ValueError, match="rate"):
        FaultPlan().random_transients(1.5, seed=1)

    def schedule(inj, n=200):
        out = []
        for call in range(n):
            try:
                inj.on_stage_call(0)
            except InjectedFault:
                out.append(call)
        return out

    plan = FaultPlan().random_transients(0.1, seed=42)
    a = schedule(plan.build())
    b = schedule(plan.build())
    assert a == b and 5 <= len(a) <= 40        # ~10% of 200, seeded
    # stage filter: faults only land on listed stages
    inj = FaultPlan().random_transients(0.5, seed=1, stages=[3]).build()
    for _ in range(50):
        inj.on_stage_call(0)
    assert inj.injected == 0


def test_slowdown_window_sleeps_without_raising():
    inj = FaultPlan().slowdown(0, 5.0, from_call=1, to_call=3).build()
    t0 = time.perf_counter()
    for _ in range(4):
        inj.on_stage_call(0)
    assert (time.perf_counter() - t0) * 1e3 >= 8.0   # calls 1 and 2 slept
    assert inj.slowed == 2
    with pytest.raises(ValueError, match="extra_ms"):
        FaultPlan().slowdown(0, -1.0)


def test_device_loss_triggers_and_derives_survivors():
    inj = FaultPlan().lose_device(2).build()
    inj.on_stage_call(0, device=0)             # other ordinals unaffected
    with pytest.raises(DeviceLostError) as ei:
        inj.on_stage_call(0, replica=1, device=2)
    assert ei.value.ordinal == 2
    with pytest.raises(DeviceLostError):       # permanent, not transient
        inj.on_stage_call(1, device=2)
    assert inj.lost_ordinals() == frozenset({2})
    assert inj.device_faults == 2
    inv = DeviceInventory.host(4)
    assert len(inj.surviving(inv)) == 3
    assert inj.stats()["lost_ordinals"] == [2]


def test_scripted_but_unhit_loss_is_not_observable():
    # like a real chip that died while idle: until a call lands on it,
    # nothing has observed the failure
    inj = FaultPlan().lose_device(1).build()
    inj.on_stage_call(0, device=0)
    assert inj.lost_ordinals() == frozenset()
    inv = DeviceInventory.host(2)
    assert inj.surviving(inv) is inv


def test_live_lose_device_counts_from_now():
    inj = FaultInjector()
    for _ in range(3):
        inj.on_stage_call(0, device=1)
    inj.lose_device(1, after_calls=1)          # one more call survives
    inj.on_stage_call(0, device=1)
    with pytest.raises(DeviceLostError):
        inj.on_stage_call(0, device=1)


def test_remap_devices_follows_survivors():
    inj = FaultPlan().lose_device(1).lose_device(3).build()
    with pytest.raises(DeviceLostError):
        inj.on_stage_call(0, device=1)
    # inventory re-densified after dropping ordinal 1: old->new mapping
    inj.remap_devices({0: 0, 2: 1, 3: 2})
    assert inj.lost_ordinals() == frozenset()  # loss now lives in inventory
    assert inj.plan.device_losses == {2: 0}    # old 3 follows to new 2
    with pytest.raises(DeviceLostError) as ei:
        inj.on_stage_call(0, device=2)
    assert ei.value.ordinal == 2


def test_fail_step_fires_once_and_as_injector_normalizes():
    inj = FaultPlan().fail_step([3]).build()
    inj.on_step(2)
    with pytest.raises(InjectedFault):
        inj.on_step(3)
    inj.on_step(3)                             # replay after restart succeeds
    assert as_injector(None) is None
    assert as_injector(inj) is inj
    assert isinstance(as_injector(FaultPlan()), FaultInjector)
    with pytest.raises(TypeError, match="FaultPlan or FaultInjector"):
        as_injector(lambda s: None)


# --------------------------------------------------------------------------- #
# _SeqRing: residue ownership, adopt/retire hand-off
# --------------------------------------------------------------------------- #
def test_seqring_owns_residue_and_consumes_in_order():
    ring = _SeqRing(stride=2, first_seq=0)
    assert ring.put(2, "g2") and ring.put(0, "g0")   # out-of-order arrival
    assert ring.pop() == (0, "g0")
    assert ring.pop() == (2, "g2")
    ring.close()
    assert ring.pop() is None
    assert ring.put(4, "g4") is False          # closed: caller must fail it


def test_seqring_adopt_resumes_siblings_watermark():
    victim = _SeqRing(stride=2, first_seq=1)
    victim.put(1, "g1")
    assert victim.pop() == (1, "g1")           # watermark advances to 3
    victim.put(3, "g3")
    slots, nxt = victim.retire()
    assert slots == {3: "g3"} and nxt == {1: 3}
    assert victim.put(5, "g5") is False        # retired == closed

    survivor = _SeqRing(stride=2, first_seq=0)
    survivor.adopt(1, nxt[1])
    for s, g in slots.items():
        assert survivor.put(s, g)
    survivor.put(0, "g0")
    assert survivor.pop() == (0, "g0")         # own residue still served
    assert survivor.pop() == (3, "g3")         # adopted residue resumes at 3


# --------------------------------------------------------------------------- #
# executor: retry, quarantine, bounded budgets
# --------------------------------------------------------------------------- #
def _fns():
    def s0(env):
        time.sleep(0.001)
        return {"x": np.asarray(env["x"]) * 2.0}

    def s1(env):
        time.sleep(0.001)
        return {"y": np.asarray(env["x"]) + 1.0}
    return [s0, s1]


def _expect(i):
    return float(i) * 2.0 + 1.0


def test_transient_retries_on_sibling_no_quarantine():
    inj = FaultPlan().transient(0, at_calls=[2]).build()
    ex = PipelineExecutor(_fns(), ["x"], ["y"], replicas=[2, 1],
                          fault_injector=inj, quarantine_after=3)
    got = ex.run([(np.full((2,), float(i)),) for i in range(8)])
    st = ex.stats()
    ex.close()
    for i, g in enumerate(got):
        np.testing.assert_allclose(np.asarray(g), _expect(i))
    assert st.retries == 1 and st.quarantined == 0
    assert st.out_of_order_retired == 0
    assert st.tokens_retired == 8
    assert st.per_stage[0].errors == 1


def test_repeated_errors_quarantine_the_replica():
    # every call placed on replica residue 0 of stage 0 faults until the
    # eviction: quarantine_after=1 evicts on the first error
    inj = FaultPlan().transient(0, at_calls=[0]).build()
    ex = PipelineExecutor(_fns(), ["x"], ["y"], replicas=[3, 1],
                          fault_injector=inj, quarantine_after=1)
    got = ex.run([(np.full((2,), float(i)),) for i in range(9)])
    st = ex.stats()
    healthy = ex.healthy_replicas()
    ex.close()
    for i, g in enumerate(got):
        np.testing.assert_allclose(np.asarray(g), _expect(i))
    assert st.quarantined == 1
    assert st.quarantined_replicas and st.quarantined_replicas[0][0] == 0
    assert healthy[0] == 2 and healthy[1] == 1
    assert st.out_of_order_retired == 0 and st.tokens_retired == 9


def test_jitted_pipeline_transients_retry_bit_identical():
    """Transients on the widened stage of the jitted (buffer-donating)
    Harris pipeline are retried mid-stream, and every result is bitwise
    the fault-free run's: injection fires before the stage body, so a
    retry never re-runs a half-donated buffer."""
    import jax

    from repro.core import assign_replicas, courier_offload
    from repro.core.tracer import Library
    from repro.models.harris import corner_harris_demo, make_harris_db

    db = make_harris_db(with_hw=False)
    app = corner_harris_demo(Library(db))
    frames = [jax.random.uniform(jax.random.PRNGKey(i), (32, 48, 3)) * 255
              for i in range(8)]
    pipe = courier_offload(app, frames[0], db=db, prefer_hw=False).pipeline
    plan = assign_replicas(pipe.plan, pipe.ir, worker_budget=8)
    wide = max(range(plan.n_stages), key=lambda s: plan.replicas[s])
    assert plan.replicas[wide] > 1
    clean = pipe.executor(replicas=plan.replicas)
    clean.warmup(frames[0])
    want = clean.run(frames)
    clean.close()

    inj = FaultPlan().build()          # empty: warmup runs fault-free
    ex = pipe.executor(replicas=plan.replicas, fault_injector=inj,
                       quarantine_after=3)
    ex.warmup(frames[0])
    base = inj.stage_calls(wide)       # script the faults mid-stream
    inj.plan.transient(wide, at_calls=[base + 2, base + 5])
    got = ex.run(frames)
    st = ex.stats()
    ex.close()
    assert inj.injected == 2 and st.retries == 2
    assert st.out_of_order_retired == 0 and st.tokens_retired == 8
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_unreplicated_stage_error_fails_the_group():
    # stage 1 has no sibling: the injected fault errors that group only,
    # in order, and the pool is not leaked
    inj = FaultPlan().transient(1, at_calls=[2]).build()
    ex = PipelineExecutor(_fns(), ["x"], ["y"], replicas=[2, 1],
                          fault_injector=inj, quarantine_after=3)
    handles = ex.submit_many([(np.full((2,), float(i)),) for i in range(6)])
    ok, failed = [], []
    for i, h in enumerate(handles):
        try:
            h.result()
            ok.append(i)
        except InjectedFault:
            failed.append(i)
    st = ex.stats()
    ex.close()
    assert len(failed) == 1 and len(ok) == 5
    assert st.retries == 0 and st.quarantined == 0
    assert st.tokens_admitted == st.tokens_retired == 6
    assert st.out_of_order_retired == 0


def test_max_group_retries_bounds_the_retry_loop():
    # every stage-0 invocation faults; the group burns its retry budget
    # and then fails instead of spinning forever
    inj = FaultPlan().transient(0, at_calls=range(1000)).build()
    ex = PipelineExecutor(_fns(), ["x"], ["y"], replicas=[2, 1],
                          fault_injector=inj, quarantine_after=10_000,
                          max_group_retries=3)
    h = ex.submit(np.full((2,), 1.0))
    with pytest.raises(InjectedFault):
        h.result()
    st = ex.stats()
    ex.close()
    assert st.retries == 3                     # bounded, then failed
    assert st.tokens_retired == 1


def test_retry_budget_ms_zero_disables_retries():
    inj = FaultPlan().transient(0, at_calls=[0]).build()
    ex = PipelineExecutor(_fns(), ["x"], ["y"], replicas=[2, 1],
                          fault_injector=inj, quarantine_after=10_000,
                          retry_budget_ms=0.0)
    h = ex.submit(np.full((2,), 1.0))
    with pytest.raises(InjectedFault):
        h.result()
    st = ex.stats()
    ex.close()
    assert st.retries == 0


def test_device_loss_attributes_errors_to_configured_ordinal():
    inj = FaultPlan().lose_device(1).build()
    ex = PipelineExecutor(_fns(), ["x"], ["y"], replicas=[2, 1],
                          devices=[[0, 1], [2]],
                          inventory=DeviceInventory.host(3),
                          fault_injector=inj, quarantine_after=1)
    got = ex.run([(np.full((2,), float(i)),) for i in range(6)])
    st = ex.stats()
    ex.close()
    for i, g in enumerate(got):
        np.testing.assert_allclose(np.asarray(g), _expect(i))
    assert st.quarantined == 1
    assert st.device_errors.get(1, 0) >= 1     # keyed by CONFIGURED ordinal
    assert st.out_of_order_retired == 0


# --------------------------------------------------------------------------- #
# inventory: structured refresh diff
# --------------------------------------------------------------------------- #
def test_inventory_refresh_diffs_by_identity():
    inv = DeviceInventory.host(4)
    diff = inv.refresh(probe=lambda: inv.drop([0]))
    assert diff.changed
    assert diff.lost == (0,) and diff.gained == ()
    assert diff.survivors == {1: 0, 2: 1, 3: 2}   # identity survives re-dense
    assert "lost" in diff.describe()
    same = inv.refresh(probe=lambda: inv)
    assert not same.changed and same.survivors == {0: 0, 1: 1, 2: 2, 3: 3}


def test_inventory_drop_and_reweighted():
    inv = DeviceInventory.host(3)
    smaller = inv.drop({1})
    assert len(smaller) == 2
    assert [s.ordinal for s in smaller.specs] == [0, 1]     # re-densified
    with pytest.raises(ValueError):
        inv.drop({0, 1, 2})
    slow = inv.reweighted({1: 0.25})
    assert slow.spec(1).speed == pytest.approx(inv.spec(1).speed * 0.25)
    assert slow.spec(0).speed == inv.spec(0).speed


# --------------------------------------------------------------------------- #
# elastic recovery: loss -> quarantine -> refresh -> survivors re-plan
# --------------------------------------------------------------------------- #
DELAYS: dict[str, float] = {}


def _impl(key):
    def sw(x):
        time.sleep(DELAYS[key] / 1e3)
        return np.asarray(x) + 1.0
    sw.__name__ = key
    return sw


def _chain_planner(times=(1.0, 4.0), inventory=None, **kw):
    from repro.core import ModuleDatabase, linear_ir
    from repro.runtime import ElasticPlanner

    keys = [f"f{i}" for i in range(len(times))]
    DELAYS.clear()
    DELAYS.update(dict(zip(keys, times)))
    db = ModuleDatabase("faults-chain")
    for k in keys:
        db.register(k, software=_impl(k))
    ir = linear_ir("faults-chain", keys, list(times), io_shape=(4,))
    return ElasticPlanner(ir, db=db, inventory=inventory, **kw)


def test_replan_on_inventory_change_sheds_lost_device(monkeypatch):
    # every host core reserved: the budget is the inventory's own floor (one
    # worker per device), so one replica per device on any host
    monkeypatch.setenv("REPRO_RESERVED_CORES", str(os.cpu_count() or 1))
    inj = FaultInjector()
    inv = DeviceInventory.host(4)
    planner = _chain_planner(inventory=inv, fault_injector=inj,
                             quarantine_after=1)
    prof = StageProfiler(2, min_samples=2)
    ex, _ = planner.executor_for(2, jit=False, profiler=prof)
    assert max(ex.replicas) > 1                # inventory widened the chain
    wide_si = max(range(2), key=lambda s: ex.replicas[s])
    target = ex.devices[wide_si][0]
    toks = [np.full((4,), float(i)) for i in range(8)]
    ex.run(toks)

    inj.lose_device(target)
    got = ex.run(toks)                         # quarantine absorbs the loss
    for i, g in enumerate(got):
        np.testing.assert_allclose(np.asarray(g), float(i) + 2.0)
    st = ex.stats()
    assert st.quarantined == 1 and st.out_of_order_retired == 0

    diff = inv.refresh(probe=lambda: inj.surviving(inv))
    assert diff.lost == (target,)
    d = planner.replan_on_inventory_change(diff, profiler=prof, stats=st,
                                           jit=False)
    assert d.replanned and d.widened
    assert "lost" in d.reason
    assert sum(d.replicas) <= 3                # only 3 survivors remain
    if d.executor.devices is not None:
        assert all(o < 3 for row in d.executor.devices for o in row)
    got2 = d.executor.run(toks)
    for i, g in enumerate(got2):
        np.testing.assert_allclose(np.asarray(g), float(i) + 2.0)
    st2 = d.executor.stats()
    assert st2.retries == 0 and st2.quarantined == 0   # clean on survivors
    d.executor.close()
    ex.close()


def test_replan_on_inventory_change_keeps_when_unchanged():
    planner = _chain_planner(inventory=DeviceInventory.host(4))
    planner.executor_for(2, jit=False)
    inv = planner.inventory
    diff = inv.refresh(probe=lambda: inv)
    d = planner.replan_on_inventory_change(diff, jit=False)
    assert not d.replanned and d.reason == "inventory unchanged"


# --------------------------------------------------------------------------- #
# serve layer: stop() rejects pending, deadlines bound queue time
# --------------------------------------------------------------------------- #
def _slow_executor(ms=30.0, max_in_flight=2):
    def slow(env):
        time.sleep(ms / 1e3)
        return {"y": np.asarray(env["x"]) * 2.0}
    return PipelineExecutor([slow], ["x"], ["y"], replicas=[1],
                            max_in_flight=max_in_flight)


def test_server_stop_fails_pending_requests_with_executor_closed():
    ex = _slow_executor()
    srv = RequestQueueServer(ex, max_batch=1, max_wait_ms=0.5).start()
    reqs = [srv.submit(np.full((2,), float(i))) for i in range(4)]
    srv.stop()
    served = rejected = 0
    for r in reqs:
        try:
            r.wait(timeout=10.0)
            served += 1
        except ExecutorClosed:
            rejected += 1
    assert served + rejected == 4              # nobody left hanging
    st = srv.stats()
    assert st["rejected"] == rejected
    assert st["queue_depth"] == 0
    # post-stop submissions are rejected immediately, not queued forever
    late = srv.submit(np.zeros(2))
    with pytest.raises(ExecutorClosed):
        late.wait(timeout=1.0)
    ex.close()


def test_deadline_ms_fails_queued_requests_instead_of_serving_late():
    ex = _slow_executor(ms=50.0, max_in_flight=1)
    with RequestQueueServer(ex, max_batch=1, max_wait_ms=0.5,
                            queue_depth=16) as srv:
        head = srv.submit(np.zeros(2))         # occupies the executor
        doomed = [srv.submit(np.zeros(2), deadline_ms=1.0)
                  for _ in range(3)]
        ok = srv.submit(np.zeros(2))           # no deadline: served
        head.wait(timeout=10.0)
        expired = served_late = 0
        for r in doomed:
            try:
                r.wait(timeout=10.0)
                served_late += 1
            except DeadlineExceeded:
                expired += 1
        ok.wait(timeout=10.0)
        # the batcher may have collected the first doomed request before
        # its deadline; everything still queued when it expired must fail
        assert expired >= 2 and expired + served_late == 3
        assert srv.stats()["rejected"] >= expired
    ex.close()


# --------------------------------------------------------------------------- #
# training driver: faults= harness, legacy hook, loss accounting
# --------------------------------------------------------------------------- #
def test_driver_faults_and_fail_hook_are_exclusive(tmp_path):
    from repro.checkpoint import CheckpointStore
    from repro.runtime import FaultTolerantDriver

    store = CheckpointStore(str(tmp_path))
    with pytest.raises(ValueError, match="not both"):
        FaultTolerantDriver(lambda s, b: (s, {"loss": 0.0}), store, None,
                            faults=FaultPlan(), fail_hook=lambda s: None)


def test_driver_replay_does_not_double_count_losses(tmp_path):
    import jax.numpy as jnp

    from repro.checkpoint import CheckpointStore
    from repro.runtime import FaultTolerantDriver

    class Data:
        def batch(self, step):
            return float(step)

    def step_fn(state, batch):
        w = state["w"] - 0.1
        return {"w": w}, {"loss": jnp.sum(w * w)}

    store = CheckpointStore(str(tmp_path))
    drv = FaultTolerantDriver(step_fn, store, Data(), ckpt_every=4,
                              async_ckpt=False,
                              faults=FaultPlan().fail_step([6]))
    state, res = drv.run({"w": jnp.ones(3)}, n_steps=10)
    assert res.restarts == 1 and res.steps_done == 10
    # steps 4 and 5 were replayed after the restart; keyed-by-step
    # accounting keeps exactly one loss per step
    assert len(res.losses) == 10
    np.testing.assert_allclose(np.asarray(state["w"]), np.ones(3) - 1.0,
                               atol=1e-6)


def test_driver_legacy_fail_hook_still_supported(tmp_path):
    import jax.numpy as jnp

    from repro.checkpoint import CheckpointStore
    from repro.runtime import FaultTolerantDriver

    class Data:
        def batch(self, step):
            return float(step)

    def step_fn(state, batch):
        return {"w": state["w"] - 0.1}, {"loss": jnp.zeros(())}

    armed = {"on": True}

    def hook(step):
        if step == 3 and armed["on"]:
            armed["on"] = False
            raise RuntimeError("legacy injected failure")

    store = CheckpointStore(str(tmp_path))
    drv = FaultTolerantDriver(step_fn, store, Data(), ckpt_every=2,
                              async_ckpt=False, fail_hook=hook)
    _, res = drv.run({"w": jnp.ones(2)}, n_steps=6)
    assert res.restarts == 1 and res.steps_done == 6


# --------------------------------------------------------------------------- #
# lint: swallowed-exception rule
# --------------------------------------------------------------------------- #
def _lint_src(tmp_path, src):
    from repro.analysis.lint import lint_paths

    f = tmp_path / "mod.py"
    f.write_text(src)
    return [d for d in lint_paths([str(f)])
            if d.rule == "swallowed-exception"]


def test_lint_flags_swallowed_broad_handlers(tmp_path):
    findings = _lint_src(tmp_path, (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
        "def h():\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        x = 1\n"
    ))
    assert len(findings) == 2
    assert "neither re-raises nor records" in findings[0].message


def test_lint_accepts_reraise_recorded_or_annotated(tmp_path):
    findings = _lint_src(tmp_path, (
        "def a():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        raise\n"
        "def b(log):\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        log.append(e)\n"
        "def c():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:  # lint: allow-swallow(best-effort probe)\n"
        "        pass\n"
        "def d():\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"        # narrow handlers are not its job
        "        pass\n"
    ))
    assert findings == []


# --------------------------------------------------------------------------- #
# chaos soak: randomized transients under concurrent submitters
# --------------------------------------------------------------------------- #
@pytest.mark.slow
def test_chaos_soak_randomized_transients_zero_drops():
    """8 threads x 250 requests against a replicated pipeline under seeded
    random transients: every request retires, in order per thread, with
    results identical to the fault-free pipeline."""
    def s0(env):
        return {"x": np.asarray(env["x"]) * 2.0}

    def s1(env):
        return {"x": np.asarray(env["x"]) + 1.0}

    def s2(env):
        return {"y": np.asarray(env["x"]) * 3.0}

    n_threads, per_thread = 8, 250
    inj = FaultPlan().random_transients(0.02, seed=1234).build()
    ex = PipelineExecutor([s0, s1, s2], ["x"], ["y"], replicas=[2, 3, 2],
                          max_in_flight=16, fault_injector=inj,
                          quarantine_after=10**9)   # pure retries, no evict
    errors: list = []
    results: dict[int, list] = {}

    def worker(tid):
        try:
            hs = ex.submit_many([(np.full((2,), tid * 1000.0 + i),)
                                 for i in range(per_thread)])
            results[tid] = [float(np.asarray(h.result())[0]) for h in hs]
        except BaseException as e:     # pragma: no cover - fail the test
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = ex.stats()
    ex.close()
    assert not errors, errors
    assert st.tokens_admitted == st.tokens_retired == n_threads * per_thread
    assert st.out_of_order_retired == 0
    assert st.retries > 0                      # the soak actually injected
    assert st.quarantined == 0
    for tid in range(n_threads):
        want = [(tid * 1000.0 + i) * 2.0 * 3.0 + 3.0
                for i in range(per_thread)]
        assert results[tid] == want, f"thread {tid} results diverged"
