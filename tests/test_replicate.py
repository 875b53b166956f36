"""Parallel-filter stage replication: planner pass, executor dataflow,
ordered retirement, and the widen-vs-rebalance replan decision."""
import random
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ModuleDatabase, Node, PipelinePlan, StagePlan,
                        StageProfiler, assign_replicas, linear_ir,
                        partition_optimal, replicated_bottleneck_ms)
from repro.core.executor import PipelineExecutor
from repro.runtime import ElasticPlanner


def _plan(times, **kw):
    return PipelinePlan(stages=[StagePlan(node_names=[f"n{i}"],
                                          est_time_ms=float(t), **kw)
                                for i, t in enumerate(times)])


# --------------------------------------------------------------------------- #
# cost model: replication-aware bottleneck
# --------------------------------------------------------------------------- #
def test_replicated_bottleneck_ms():
    assert replicated_bottleneck_ms([2.0, 6.0, 2.0], [1, 1, 1]) == 6.0
    assert replicated_bottleneck_ms([2.0, 6.0, 2.0], [1, 3, 1]) == 2.0
    # widening one stage exposes the next bottleneck
    assert replicated_bottleneck_ms([3.0, 6.0], [1, 4]) == 3.0
    assert replicated_bottleneck_ms([], []) == 0.0
    with pytest.raises(ValueError):
        replicated_bottleneck_ms([1.0], [1, 2])


def test_plan_effective_bottleneck_and_workers():
    p = _plan([2.0, 6.0, 2.0])
    assert p.effective_bottleneck_ms == p.bottleneck_ms == 6.0
    p.stages[1].replicas = 3
    assert p.bottleneck_ms == 6.0                 # service time unchanged
    assert p.effective_bottleneck_ms == 2.0       # throughput widened
    assert p.replicas == [1, 3, 1] and p.total_workers == 5
    assert "x3" in p.describe()


# --------------------------------------------------------------------------- #
# planner pass: assign_replicas
# --------------------------------------------------------------------------- #
def test_assign_replicas_widens_dominant_stage():
    p = _plan([0.5, 6.0, 0.5, 0.5])
    assign_replicas(p, worker_budget=8)
    assert p.total_workers <= 8
    assert p.stages[1].replicas == max(p.replicas)    # bottleneck widest
    assert p.effective_bottleneck_ms <= 6.0 / (p.stages[1].replicas - 1)
    # derived target beat the serial plan substantially
    assert p.effective_bottleneck_ms < 2.0


def test_assign_replicas_explicit_target_is_ceil_rule():
    p = _plan([2.0, 6.0, 3.0])
    assign_replicas(p, worker_budget=16, target_ms=1.0)
    assert p.replicas == [2, 6, 3]                    # ceil(t / target)
    p2 = _plan([2.0, 6.0, 3.0])
    assign_replicas(p2, worker_budget=6, target_ms=1.0)
    assert p2.total_workers <= 6                      # budget clamps the rule
    assert p2.stages[1].replicas >= p2.stages[0].replicas


def test_assign_replicas_budget_floor_and_max_replicas():
    p = _plan([1.0, 1.0])
    with pytest.raises(ValueError, match="worker_budget"):
        assign_replicas(p, worker_budget=1)
    p = _plan([0.5, 8.0])
    assign_replicas(p, worker_budget=12, max_replicas=2)
    assert max(p.replicas) <= 2


def test_assign_replicas_respects_serial_only_nodes():
    ir = linear_ir("s", ["a", "b", "c"], [1.0, 9.0, 1.0])
    ir.nodes[1].serial_only = True                    # the bottleneck is I/O
    plan = partition_optimal(ir, max_stages=3)
    assign_replicas(plan, ir, worker_budget=9)
    k = next(i for i, s in enumerate(plan.stages)
             if "b_1" in s.node_names)
    assert plan.stages[k].replicas == 1               # never widened
    # and the derived target respects the serial floor: no other stage is
    # widened past the point of helping (9 ms stays the period)
    assert plan.effective_bottleneck_ms == pytest.approx(9.0)


def test_serial_only_survives_fuse_and_split():
    from repro.core import fuse_adjacent_hw, split_fused_node

    db = ModuleDatabase("t")
    db.register("f", software=lambda x: x + 1.0, accelerated=lambda x: x + 1.0)
    db.register("g", software=lambda x: x * 2.0, accelerated=lambda x: x * 2.0)
    ir = linear_ir("x", ["f", "g"], [1.0, 1.0], io_shape=(4,))
    ir.nodes[0].serial_only = True
    fused = fuse_adjacent_hw(ir, db, fused_cost_ms=lambda run: 0.5)
    fnode = next(n for n in fused.nodes if n.fused_from)
    assert fnode.serial_only                          # any part marks the fuse
    back = split_fused_node(fused, fnode.name)
    assert all(n.serial_only for n in back.nodes)     # conservative split


# --------------------------------------------------------------------------- #
# executor: replicated dataflow correctness
# --------------------------------------------------------------------------- #
def _jnp_fns():
    def s0(env):
        return {"x": env["x"] + 1.0}

    def s1(env):
        return {"x": jnp.tanh(env["x"]) * 2.0}

    def s2(env):
        return {"y": env["x"] - 3.0}
    return [s0, s1, s2]


def test_replicated_results_match_serial_and_retire_in_order():
    toks = [(jnp.full((4,), float(i)),) for i in range(20)]
    ser = PipelineExecutor(_jnp_fns(), ["x"], ["y"], replicas=[1, 1, 1])
    want = ser.run(toks)
    ser.close()
    rep = PipelineExecutor(_jnp_fns(), ["x"], ["y"], replicas=[2, 3, 2])
    got = rep.run(toks)
    st = rep.stats()
    rep.close()
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
    assert st.out_of_order_retired == 0
    assert st.tokens_admitted == st.tokens_retired == 20
    assert [c.replicas for c in st.per_stage] == [2, 3, 2]


def test_replicated_with_microbatch_groups():
    toks = [(jnp.full((4,), float(i)),) for i in range(13)]   # ragged tail
    ser = PipelineExecutor(_jnp_fns(), ["x"], ["y"])
    want = ser.run(toks)
    rep = PipelineExecutor(_jnp_fns(), ["x"], ["y"], replicas=[1, 3, 1],
                           microbatch=4, max_in_flight=12)
    got = rep.run(toks)
    assert rep.stats().out_of_order_retired == 0
    rep.close()
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)


def test_replicated_validation_and_pool_default():
    fns = _jnp_fns()
    with pytest.raises(ValueError, match="every stage"):
        PipelineExecutor(fns, ["x"], ["y"], replicas=[2, 2])
    with pytest.raises(ValueError, match=">= 1"):
        PipelineExecutor(fns, ["x"], ["y"], replicas=[1, 0, 1])
    ex = PipelineExecutor(fns, ["x"], ["y"], replicas=[1, 3, 1])
    assert ex.pool == 5 + 1            # sum(replicas) + 1 default
    ex.close()
    with pytest.raises(RuntimeError, match="closed"):
        ex.submit(jnp.zeros(4))


def test_replicated_stage_error_propagates_and_frees_pool():
    def boom(env):
        if float(env["x"][0]) == 3.0:
            raise RuntimeError("kaboom")
        return {"x": env["x"]}

    def out(env):
        return {"y": env["x"] * 2.0}

    ex = PipelineExecutor([boom, out], ["x"], ["y"], replicas=[2, 1],
                          max_in_flight=4)
    handles = ex.submit_many([(np.full((2,), float(i)),) for i in range(6)])
    ok, failed = 0, 0
    for h in handles:
        try:
            h.result()
            ok += 1
        except RuntimeError:
            failed += 1
    assert (ok, failed) == (5, 1)
    st = ex.stats()
    assert st.tokens_admitted == st.tokens_retired == 6   # pool not leaked
    assert st.out_of_order_retired == 0                   # errors retire in order
    ex.close()


# --------------------------------------------------------------------------- #
# ordered-retirement determinism stress (randomized per-replica jitter)
# --------------------------------------------------------------------------- #
def test_ordered_retirement_under_random_replica_jitter():
    """Replicated stages with randomized per-call sleeps must retire every
    token in submission order with results identical to the serial
    executor — the reorder buffer, not luck, provides the ordering."""
    rng = random.Random(1234)

    def jittery(env):
        time.sleep(rng.uniform(0.0, 0.004))       # per-replica jitter
        return {"x": np.asarray(env["x"]) * 2.0 + 1.0}

    def tail(env):
        time.sleep(rng.uniform(0.0, 0.002))
        return {"y": np.asarray(env["x"]) - 5.0}

    toks = [(np.full((3,), float(i)),) for i in range(40)]
    ser = PipelineExecutor([jittery, tail], ["x"], ["y"], replicas=[1, 1])
    want = ser.run(toks)
    ser.close()
    rep = PipelineExecutor([jittery, tail], ["x"], ["y"], replicas=[4, 3],
                           max_in_flight=10)
    got = rep.run(toks)
    st = rep.stats()
    rep.close()
    assert st.out_of_order_retired == 0
    assert st.tokens_retired == 40
    for i, (g, w) in enumerate(zip(got, want)):
        # value encodes the token index: any slot/seq mix-up shows here
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   err_msg=f"token {i}")
        np.testing.assert_allclose(np.asarray(g), float(i) * 2.0 - 4.0)


def test_replicated_concurrent_submitters_stay_ordered_per_thread():
    def double(env):
        time.sleep(0.001)
        return {"y": np.asarray(env["x"]) * 2.0}

    ex = PipelineExecutor([double], ["x"], ["y"], replicas=[4],
                          max_in_flight=8)
    results: dict[int, list] = {}

    def worker(tid):
        hs = ex.submit_many([(np.full((2,), tid * 100.0 + i),)
                             for i in range(10)])
        results[tid] = [float(np.asarray(h.result())[0]) for h in hs]

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ex.close()
    assert ex.stats().out_of_order_retired == 0
    for tid, vals in results.items():
        assert vals == [(tid * 100.0 + i) * 2.0 for i in range(10)]


# --------------------------------------------------------------------------- #
# profiler: per-replica attribution
# --------------------------------------------------------------------------- #
def test_profiler_per_replica_attribution():
    p = StageProfiler(2, min_samples=1)
    for _ in range(4):
        p.record(0, 10.0, replica=0)
        p.record(0, 30.0, replica=1)
    p.record(1, 5.0)                        # serial stage: no replica index
    assert p.measured_ms(0) == pytest.approx(20.0)   # aggregate = service
    reps = p.replica_ms(0)
    assert set(reps) == {0, 1}
    assert reps[0] == pytest.approx(10.0) and reps[1] == pytest.approx(30.0)
    assert p.replica_ms(1) == {}
    snap = p.snapshot()
    assert snap["per_stage"][0]["replicas"]["1"]["samples"] == 4
    assert "replicas" not in snap["per_stage"][1]
    p.reset()
    assert p.replica_ms(0) == {}


def test_replicated_executor_feeds_replica_profile():
    def slow(env):
        time.sleep(0.002)
        return {"y": np.asarray(env["x"]) + 1.0}

    prof = StageProfiler(1, min_samples=2)
    ex = PipelineExecutor([slow], ["x"], ["y"], replicas=[3],
                          max_in_flight=6, profiler=prof)
    ex.run([(np.zeros(2),) for _ in range(12)])
    ex.close()
    assert prof.samples(0) == 12
    reps = prof.replica_ms(0)
    assert set(reps) <= {0, 1, 2} and len(reps) == 3   # all replicas worked
    assert all(v >= 1.0 for v in reps.values())


# --------------------------------------------------------------------------- #
# replan integration: widen beats rebalance on a one-dominant-node chain
# --------------------------------------------------------------------------- #
DELAYS: dict[str, float] = {}


def _impl(key):
    def sw(x):
        time.sleep(DELAYS[key] / 1e3)
        return np.asarray(x) + 1.0
    sw.__name__ = key
    return sw


def _dominant_planner(times=(0.5, 6.0, 0.5, 0.5)):
    keys = [f"f{i}" for i in range(len(times))]
    DELAYS.clear()
    DELAYS.update(dict(zip(keys, times)))
    db = ModuleDatabase("wide")
    for k in keys:
        db.register(k, software=_impl(k))
    ir = linear_ir("wide", keys, list(times), io_shape=(4,))
    return ElasticPlanner(ir, db=db)


def test_replan_widen_beats_rebalance_on_dominant_stage():
    planner = _dominant_planner()
    prof = StageProfiler(4, min_samples=4, sample_every=1)
    ex, _ = planner.executor_for(4, max_in_flight=10, jit=False,
                                 profiler=prof)
    boundaries0 = [list(s.node_names) for s in planner.current_plan.stages]
    toks = [np.full((4,), float(i)) for i in range(12)]
    ex.run(toks)
    d = planner.replan_from_profile(prof, worker_budget=8, jit=False)
    assert d.replanned and d.widened, d.describe()
    assert d.replicas is not None and max(d.replicas) > 1
    # boundaries did NOT move: widening reuses every stage identity
    assert [list(s.node_names) for s in d.plan.stages] == boundaries0
    assert d.new_bottleneck_ms < d.old_bottleneck_ms / 1.5
    out = d.executor.run(toks)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.full((4,), 4.0))     # 4 increments
    assert d.executor.stats().out_of_order_retired == 0
    d.executor.close()
    ex.close()


def test_replan_widened_plan_is_stable_not_flapping():
    planner = _dominant_planner()
    prof = StageProfiler(4, min_samples=4, sample_every=1)
    ex, _ = planner.executor_for(4, max_in_flight=10, jit=False,
                                 profiler=prof)
    ex.run([np.full((4,), float(i)) for i in range(12)])
    d = planner.replan_from_profile(prof, worker_budget=8, jit=False)
    assert d.replanned and d.widened
    ex.close()
    # steady state: same measured service times -> same widen decision ->
    # "plan unchanged", call after call
    for trial in range(3):
        prof2 = StageProfiler(d.plan.n_stages, min_samples=4)
        rng = np.random.default_rng(trial)
        for _ in range(8):
            for k, s in enumerate(d.plan.stages):
                prof2.record(k, s.est_time_ms * (1 + 0.1 * rng.uniform(-1, 1)))
        d2 = planner.replan_from_profile(prof2, worker_budget=8, jit=False)
        assert not d2.replanned, f"flapped on trial {trial}: {d2.reason}"
    assert planner.replans == 1
    d.executor.close()


def test_replan_without_budget_keeps_legacy_rebalance_path():
    planner = _dominant_planner((2.0, 2.0, 2.0, 2.0, 2.0, 2.0))
    prof = StageProfiler(3, min_samples=4)
    planner.executor_for(3, jit=False)
    for _ in range(6):
        for k, t in enumerate([4.0, 12.0, 4.0]):
            prof.record(k, t)
    d = planner.replan_from_profile(prof, max_stages=6, jit=False)
    assert d.replanned and not d.widened and d.plan.replicas == \
        [1] * d.plan.n_stages


def test_executor_for_worker_budget_builds_replicated_executor():
    planner = _dominant_planner()
    ex, rebuilt = planner.executor_for(4, jit=False, worker_budget=8,
                                       max_in_flight=10)
    assert rebuilt
    assert any(r > 1 for r in planner.current_plan.replicas)
    assert ex.replicas == planner.current_plan.replicas
    out = ex.run([np.full((4,), 0.0)])
    np.testing.assert_allclose(np.asarray(out[0]), np.full((4,), 4.0))
    # same request again: cached, not rebuilt
    ex2, rebuilt2 = planner.executor_for(4, jit=False, worker_budget=8,
                                         max_in_flight=10)
    assert ex2 is ex and not rebuilt2
    ex.close()


# --------------------------------------------------------------------------- #
# _pad_for: microbatch is the explicit final bucket; no silent new sizes
# --------------------------------------------------------------------------- #
def test_pad_for_buckets_include_microbatch_and_guard():
    def stage(env):
        return {"y": env["x"] * 2.0}

    ex = PipelineExecutor([stage], ["x"], ["y"], max_in_flight=8,
                          microbatch=8, pad_microbatches=True,
                          buckets=(2, 4))
    assert ex.buckets == (2, 4, 8)        # microbatch appended explicitly
    assert ex._pad_for(5) == 3            # -> final bucket 8, a warmed size
    with pytest.raises(RuntimeError, match="exceeds every pad bucket"):
        # only reachable by bypassing the microbatch grouping cap
        ex.buckets = (2, 4)
        ex._pad_for(5)


def test_pad_for_all_buckets_filtered_still_explicit():
    def stage(env):
        return {"y": env["x"]}

    ex = PipelineExecutor([stage], ["x"], ["y"], max_in_flight=8,
                          microbatch=4, pad_microbatches=True,
                          buckets=(16, 32))          # all above microbatch
    assert ex.buckets == (4,)
    assert ex._pad_for(3) == 1
