"""Online profiling, profile-guided re-planning, and executor hot-swap."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Frontend, Library, ModuleDatabase, PipelineGenerator,
                        StageProfiler, fuse_adjacent_hw, linear_ir,
                        measured_contradicts, split_fused_node)
from repro.launch.serve import RequestQueueServer, _percentile
from repro.runtime import ElasticPlanner, ReplanDecision


# --------------------------------------------------------------------------- #
# fixtures: a sleep-backed simulated pipeline (runtime-injectable drift)
# --------------------------------------------------------------------------- #
DELAYS_MS: dict[str, float] = {}


def _impl(key):
    def sw(x):
        time.sleep(DELAYS_MS[key] / 1e3)
        return np.asarray(x) + 1.0
    sw.__name__ = key
    return sw


def _sim_planner(n_nodes=6, base_ms=2.0, **kw):
    keys = [f"f{i}" for i in range(n_nodes)]
    DELAYS_MS.clear()
    DELAYS_MS.update({k: base_ms for k in keys})
    db = ModuleDatabase("sim")
    for k in keys:
        db.register(k, software=_impl(k))
    ir = linear_ir("sim", keys, [base_ms] * n_nodes, io_shape=(4,))
    return ElasticPlanner(ir, db=db, **kw)


def _jit_pipe():
    db = ModuleDatabase("t")
    db.register("mul2", software=lambda x: x * 2.0)
    db.register("add1", software=lambda x: x + 1.0)
    db.register("sq", software=lambda x: x * x)
    db.register("tanh", software=jnp.tanh)
    lib = Library(db)

    def app(x):
        return lib.tanh(lib.sq(lib.add1(lib.mul2(x))))
    ir, _ = Frontend(db).trace(app, jnp.arange(4.0), profile=False)
    for n in ir.nodes:
        n.time_ms = 1.0
    return PipelineGenerator(db).generate(ir, n_threads=3)


# --------------------------------------------------------------------------- #
# StageProfiler: mechanics + accuracy
# --------------------------------------------------------------------------- #
def test_profiler_ema_window_percentiles():
    p = StageProfiler(2, alpha=0.5, window=4, min_samples=2)
    assert p.measured_ms(0) is None and p.ema_ms(0) is None
    for ms in (10.0, 20.0, 30.0, 40.0, 50.0):
        p.record(0, ms)
    # window keeps the last 4 samples; median over [20, 30, 40, 50]
    assert p.percentile_ms(0, 50.0) == pytest.approx(35.0)
    assert p.samples(0) == 5
    assert p.ema_ms(0) == pytest.approx(
        0.5 * 50 + 0.5 * (0.5 * 40 + 0.5 * (0.5 * 30 + 0.5 * (
            0.5 * 20 + 0.5 * 10))))
    assert not p.ready                       # stage 1 has no samples
    p.record(1, 1.0)
    p.record(1, 2.0)
    assert p.ready
    snap = p.snapshot()
    assert snap["per_stage"][0]["samples"] == 5
    assert snap["per_stage"][1]["p50_ms"] == pytest.approx(1.5)
    p.reset()
    assert p.samples(0) == 0 and p.measured_ms(0) is None
    with pytest.raises(IndexError):
        p.record(7, 1.0)
    with pytest.raises(ValueError):
        StageProfiler(0)


def test_profiler_sampling_tick():
    p = StageProfiler(1, sample_every=4)
    ticks = [p.tick() for _ in range(8)]
    assert ticks == [True, False, False, False, True, False, False, False]


def test_profiler_converges_on_injected_stage_times():
    """Measured medians track the injected sleeps (every group sampled)."""
    planner = _sim_planner(n_nodes=6, base_ms=2.0)
    prof = StageProfiler(3, min_samples=4, sample_every=1)
    ex, _ = planner.executor_for(3, max_in_flight=8, jit=False,
                                 profiler=prof)
    toks = [np.full((4,), float(i)) for i in range(12)]
    ex.run(toks)
    for k in range(3):
        m = prof.measured_ms(k)
        # each stage = two 2 ms sleeps; sleep overshoot and scheduler noise
        # only ever push the measurement UP
        assert m is not None and 4.0 <= m <= 12.0, f"stage {k}: {m}"
    # drift one stage 3x and verify the profile follows it
    for nn in planner.current_plan.stages[1].node_names:
        DELAYS_MS[planner.layer_ir.node(nn).fn_key] *= 3.0
    prof.reset()
    ex.run(toks)
    slow, fast = prof.measured_ms(1), prof.measured_ms(0)
    assert slow >= 2.0 * fast, f"slowdown not observed: {slow} vs {fast}"
    ex.close()


def test_profiler_apply_to_ir_writes_measured_costs():
    ir = linear_ir("x", ["a", "b", "c", "d"], [1.0, 3.0, 1.0, 1.0])
    from repro.core import partition_optimal
    plan = partition_optimal(ir, max_stages=2)      # [a b] [c d] or similar
    prof = StageProfiler(plan.n_stages, min_samples=1)
    for k in range(plan.n_stages):
        for _ in range(4):
            prof.record(k, 8.0)
    replaced = prof.apply_to_ir(ir, plan)
    assert replaced                              # something was superseded
    for s in plan.stages:
        nodes = [ir.node(nn) for nn in s.node_names]
        # stage total equals the measurement; split proportional to priors
        assert sum(n.time_ms for n in nodes) == pytest.approx(8.0)
        assert all(n.time_source == "profile" for n in nodes)
    # proportionality: b had 3x a's prior -> keeps 3x after write-back
    sa, sb = ir.node("a_0").time_ms, ir.node("b_1").time_ms
    if "b_1" in [n.name for s in plan.stages for n in
                 [ir.node(nn) for nn in s.node_names]
                 if "a_0" in s.node_names]:
        assert sb == pytest.approx(3.0 * sa)


def test_measured_supersedes_roofline_in_assign_placements():
    """assign_placements must not overwrite a profiled time with cost_hw."""
    from repro.core import NodeCost, assign_placements

    db = ModuleDatabase("t")
    db.register("f", software=lambda x: x, accelerated=lambda x: x,
                cost_hw=lambda shapes, dtypes, params: NodeCost(
                    flops=1e9, bytes_rw=1e9))
    ir = linear_ir("x", ["f"], [123.0], io_shape=(4,))
    ir.nodes[0].time_source = "profile"
    assign_placements(ir, db)
    assert ir.nodes[0].time_ms == pytest.approx(123.0)   # kept the profile
    ir.nodes[0].time_source = "estimate"
    assign_placements(ir, db)
    assert ir.nodes[0].time_ms != pytest.approx(123.0)   # estimate replaced


def test_measured_contradicts_margins():
    assert measured_contradicts(2.0, 6.0, margin=1.5)
    assert measured_contradicts(6.0, 2.0, margin=1.5)    # both directions
    assert not measured_contradicts(2.0, 2.5, margin=1.5)
    assert not measured_contradicts(None, 5.0)
    assert not measured_contradicts(5.0, None)
    assert measured_contradicts(0.0, 1.0)
    with pytest.raises(ValueError):
        measured_contradicts(1.0, 2.0, margin=0.5)


def test_costmodel_observe_supersedes_annotation():
    from repro.core import CostModel, NodeCost

    cm = CostModel()
    cm.register("a", lambda shapes, dtypes, params: NodeCost(flops=1.0,
                                                             bytes_rw=1.0))
    ir = linear_ir("x", ["a"], [1.0], io_shape=(4,))
    ir.nodes[0].time_ms = None
    cm.observe("a", 10.0)
    cm.observe("a", 20.0)                    # EMA: 10 + 0.25 * 10 = 12.5
    cm.annotate(ir)
    assert ir.nodes[0].time_ms == pytest.approx(12.5)
    assert ir.nodes[0].time_source == "profile"


# --------------------------------------------------------------------------- #
# replan trigger: decision rule + hysteresis (no flapping)
# --------------------------------------------------------------------------- #
def _feed(prof, stage_times, n=8, noise=0.0, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        for k, t in enumerate(stage_times):
            prof.record(k, t * (1.0 + noise * rng.uniform(-1.0, 1.0)))


def test_replan_requires_profile_and_prior_plan():
    planner = _sim_planner()
    prof = StageProfiler(3, min_samples=4)
    with pytest.raises(ValueError, match="executor_for"):
        planner.replan_from_profile(prof)
    ex, _ = planner.executor_for(3, jit=False)
    d = planner.replan_from_profile(prof)
    assert not d.replanned and "insufficient" in d.reason
    assert planner.replans == 0 and planner.replan_checks == 1


def test_replan_triggers_on_contradicting_profile_then_stays_stable():
    planner = _sim_planner(n_nodes=6, base_ms=2.0)
    ex, _ = planner.executor_for(3, jit=False)
    assert [len(s.node_names) for s in planner.current_plan.stages] == [2, 2, 2]

    # measured: stage 1 is 3x slower than planned -> re-balance
    prof = StageProfiler(3, min_samples=4)
    _feed(prof, [4.0, 12.0, 4.0])
    d = planner.replan_from_profile(prof, max_stages=6, jit=False)
    assert isinstance(d, ReplanDecision) and d.replanned
    assert d.gain >= 1.5 and d.executor is not None
    assert d.new_bottleneck_ms < d.old_bottleneck_ms
    assert planner.replans == 1
    # measured node times were written back and marked profiled
    assert all(n.time_source == "profile" for n in planner.layer_ir.nodes)

    # steady state: noisy timings around the NEW plan's real stage costs
    # must not flap the plan, call after call
    n_stages = d.plan.n_stages
    stage_ms = [s.est_time_ms for s in d.plan.stages]
    for trial in range(5):
        prof2 = StageProfiler(n_stages, min_samples=4)
        _feed(prof2, stage_ms, noise=0.2, seed=trial)
        d2 = planner.replan_from_profile(prof2, max_stages=6, jit=False)
        assert not d2.replanned, f"flapped on trial {trial}: {d2.reason}"
    assert planner.replans == 1


def test_replan_hysteresis_blocks_marginal_gains():
    planner = _sim_planner(n_nodes=6, base_ms=2.0, min_gain=1.5)
    planner.executor_for(3, jit=False)
    # stage 0 measured mildly slower: best re-balance would win < min_gain
    prof = StageProfiler(3, min_samples=4)
    _feed(prof, [5.2, 4.0, 4.0])
    d = planner.replan_from_profile(prof, max_stages=3, jit=False)
    assert not d.replanned
    assert planner.replans == 0


def test_replan_reuses_stagefns_for_unchanged_boundaries():
    """Bounded recompiles: stages whose boundaries didn't move keep their
    compiled StageFn object across a re-plan."""
    db = ModuleDatabase("t")
    for k, f in (("a", lambda x: x + 1.0), ("b", lambda x: x * 2.0),
                 ("c", lambda x: x - 3.0), ("d", jnp.tanh)):
        db.register(k, software=f)
    ir = linear_ir("x", ["a", "b", "c", "d"], [1.0, 1.0, 1.0, 5.0],
                   io_shape=(4,))
    planner = ElasticPlanner(ir, db=db)
    ex1, _ = planner.executor_for(2)
    assert [s.node_names for s in planner.current_plan.stages] == \
        [["a_0", "b_1", "c_2"], ["d_3"]]
    fns_before = {tuple(s.node_names): f for s, f in
                  zip(planner.current_plan.stages, ex1.stage_fns)}
    x = jnp.arange(4.0)
    ex1.run([x])                                  # compile stage executables

    prof = StageProfiler(2, min_samples=4)
    _feed(prof, [9.0, 5.0])                       # stage 0 is 3x its plan
    d = planner.replan_from_profile(prof, max_stages=3)
    assert d.replanned
    new_stages = [tuple(s.node_names) for s in d.plan.stages]
    assert ("d_3",) in new_stages                 # the [d] stage survived
    reused = d.executor.stage_fns[new_stages.index(("d_3",))]
    assert reused is fns_before[("d_3",)]         # same compiled StageFn
    assert reused.compiles == 1                   # still warm, no recompile
    # and the replanned executor computes the same function
    want = np.asarray(jnp.tanh((x + 1.0) * 2.0 - 3.0))
    np.testing.assert_allclose(np.asarray(d.executor.run([x])[0]), want,
                               rtol=1e-6)


def test_replan_defuses_contradicted_fused_node():
    """A fused node whose measured time breaks the model is split apart."""
    db = ModuleDatabase("t")
    db.register("f", software=lambda x: x + 1.0, accelerated=lambda x: x + 1.0)
    db.register("g", software=lambda x: x * 2.0, accelerated=lambda x: x * 2.0)
    db.register("h", software=lambda x: x - 3.0)
    ir = linear_ir("x", ["f", "g", "h"], [2.0, 2.0, 4.0], io_shape=(4,))
    fused = fuse_adjacent_hw(ir, db, fused_cost_ms=lambda run: 1.0)
    fnode = next(n for n in fused.nodes if n.fused_from)

    planner = ElasticPlanner(fused, db=db)
    planner.executor_for(2)
    plan = planner.current_plan
    # the fused node's stage measured 12 ms against a ~1 ms model ->
    # contradiction -> defuse -> parts can split across stages
    prof = StageProfiler(plan.n_stages, min_samples=4)
    stage_of_fused = next(i for i, s in enumerate(plan.stages)
                          if fnode.name in s.node_names)
    _feed(prof, [12.0 if i == stage_of_fused else 4.0
                 for i in range(plan.n_stages)])
    d = planner.replan_from_profile(prof, max_stages=3)
    assert d.defused == [fnode.name]
    assert all(not n.fused_from for n in planner.layer_ir.nodes)
    names = [n.name for n in planner.layer_ir.nodes]
    assert "f_0" in names and "g_1" in names
    # and the defused pipeline still computes f->g->h
    x = jnp.arange(4.0)
    want = np.asarray((x + 1.0) * 2.0 - 3.0)
    np.testing.assert_allclose(np.asarray(d.executor.run([x])[0]), want,
                               rtol=1e-6)


def test_replan_keep_path_never_commits_a_defuse():
    """A contradicted fused node with a below-threshold gain must NOT
    mutate the planner's IR (the current plan still references it)."""
    db = ModuleDatabase("t")
    db.register("f", software=lambda x: x + 1.0, accelerated=lambda x: x + 1.0)
    db.register("g", software=lambda x: x * 2.0, accelerated=lambda x: x * 2.0)
    ir = linear_ir("x", ["f", "g"], [2.0, 2.0], io_shape=(4,))
    fused = fuse_adjacent_hw(ir, db, fused_cost_ms=lambda run: 1.0)
    fnode = next(n for n in fused.nodes if n.fused_from)
    planner = ElasticPlanner(fused, db=db, min_gain=1e9)   # nothing passes
    planner.executor_for(1)
    prof = StageProfiler(1, min_samples=4)
    _feed(prof, [12.0])                   # contradicts the 1 ms fused model
    d = planner.replan_from_profile(prof, max_stages=2)
    assert not d.replanned
    # the defuse was staged, not committed: the fused node is still there
    assert any(n.name == fnode.name for n in planner.layer_ir.nodes)
    # and a second check against the same plan must not crash on a stale
    # node name (regression: KeyError from apply_to_ir on a defused IR)
    d2 = planner.replan_from_profile(prof, max_stages=2)
    assert not d2.replanned


def test_replan_detects_gradual_drift_against_model_baseline():
    """The contradiction check compares against the MODEL, not against the
    previous measurement — gradual drift can't creep under the margin."""
    db = ModuleDatabase("t")
    db.register("f", software=lambda x: x + 1.0, accelerated=lambda x: x + 1.0)
    db.register("g", software=lambda x: x * 2.0, accelerated=lambda x: x * 2.0)
    db.register("h", software=lambda x: x - 3.0)
    ir = linear_ir("x", ["f", "g", "h"], [2.0, 2.0, 4.0], io_shape=(4,))
    fused = fuse_adjacent_hw(ir, db, fused_cost_ms=lambda run: 1.0)
    fname = next(n for n in fused.nodes if n.fused_from).name
    planner = ElasticPlanner(fused, db=db)
    planner.executor_for(2)
    stage_of_fused = next(i for i, s in enumerate(planner.current_plan.stages)
                          if fname in s.node_names)

    def stage_times(fused_ms):
        return [fused_ms if i == stage_of_fused else 4.0
                for i in range(planner.current_plan.n_stages)]

    # drift step 1: 1.4x the 1.0 ms model — below the 1.5x margin, no defuse
    prof = StageProfiler(planner.current_plan.n_stages, min_samples=4)
    _feed(prof, stage_times(1.4))
    d1 = planner.replan_from_profile(prof, max_stages=3)
    assert not d1.defused
    # drift step 2: 1.9 ms — only 1.36x the PREVIOUS measurement, but 1.9x
    # the model: the contradiction must fire
    prof2 = StageProfiler(planner.current_plan.n_stages, min_samples=4)
    _feed(prof2, stage_times(1.9))
    d2 = planner.replan_from_profile(prof2, max_stages=3)
    assert fname in d2.defused, d2.describe()


def test_executor_for_never_serves_a_closed_executor():
    planner = _sim_planner(n_nodes=4, base_ms=1.0)
    ex, rebuilt = planner.executor_for(2, jit=False)
    assert rebuilt
    ex.run([np.zeros(4)])
    ex.close()
    ex2, rebuilt = planner.executor_for(2, jit=False)
    assert rebuilt and ex2 is not ex          # closed executor not cached out
    out = ex2.run([np.zeros(4)])              # and the fresh one works
    np.testing.assert_allclose(np.asarray(out[0]), np.full(4, 4.0))
    ex2.close()


def test_replan_min_samples_override_can_lower_profiler_floor():
    planner = _sim_planner(n_nodes=6, base_ms=2.0)
    planner.executor_for(3, jit=False)
    prof = StageProfiler(3, min_samples=8)     # profiler's own floor: 8
    _feed(prof, [4.0, 12.0, 4.0], n=3)         # only 3 samples per stage
    assert prof.measured_ms(0) is None         # below the profiler's floor
    d = planner.replan_from_profile(prof, max_stages=6, jit=False,
                                    min_samples=3)
    assert d.replanned                         # caller's floor of 3 decides


def test_split_fused_node_roundtrip():
    db = ModuleDatabase("t")
    db.register("f", software=lambda x: x + 1.0, accelerated=lambda x: x + 1.0)
    db.register("g", software=lambda x: x * 2.0, accelerated=lambda x: x * 2.0)
    ir = linear_ir("x", ["f", "g"], [1.0, 1.0], io_shape=(4,))
    fused = fuse_adjacent_hw(ir, db, fused_cost_ms=lambda run: 0.5)
    fnode = next(n for n in fused.nodes if n.fused_from)
    back = split_fused_node(fused, fnode.name, part_times_ms=[3.0, 5.0])
    assert [n.name for n in back.nodes] == ["f_0", "g_1"]
    assert [n.time_ms for n in back.nodes] == [3.0, 5.0]
    back.validate()
    pipe = PipelineGenerator(db).generate(back, n_threads=1)
    x = jnp.arange(4.0)
    np.testing.assert_allclose(np.asarray(pipe(x)),
                               np.asarray((x + 1.0) * 2.0), rtol=1e-6)
    with pytest.raises(ValueError, match="not a fused node"):
        split_fused_node(back, "f_0")


# --------------------------------------------------------------------------- #
# hot-swap correctness: zero drops, identical results, bounded compiles
# --------------------------------------------------------------------------- #
def test_hot_swap_zero_drops_identical_results_bounded_compiles():
    pipe = _jit_pipe()
    toks = [jnp.full((4,), float(i + 1)) for i in range(24)]
    want = pipe.run_sequential(toks)

    ex_a = pipe.executor(max_in_flight=6, microbatch=2, pad_microbatches=True)
    ex_a.warmup(toks[0])
    compiles_warm = pipe.compile_count()

    with RequestQueueServer(ex_a, max_batch=2, max_wait_ms=2.0) as srv:
        reqs = [srv.submit(t) for t in toks[:12]]
        ex_b = pipe.executor(max_in_flight=4, microbatch=2,
                             pad_microbatches=True)
        old = srv.swap_executor(ex_b, warm_args=(toks[0],))
        assert old is ex_a and srv.executor is ex_b and srv.swaps == 1
        reqs += [srv.submit(t) for t in toks[12:]]
        got = [r.wait(timeout=60.0) for r in reqs]     # zero drops

    for g, w in zip(got, want):                         # identical results
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
    st = srv.stats()
    assert st["requests_served"] == 24 and st["swaps"] == 1
    # both executors' tokens are accounted; nothing lost at the boundary
    assert (ex_a.stats().tokens_retired + ex_b.stats().tokens_retired) == 24
    assert ex_a.stats().tokens_admitted == ex_a.stats().tokens_retired
    assert ex_b.stats().tokens_admitted == ex_b.stats().tokens_retired
    # bounded recompiles: shared compiled stages -> ZERO new executables
    assert pipe.compile_count() == compiles_warm


def test_hot_swap_serial_to_replicated_mid_stream():
    """Swap a serial executor for a REPLICATED one mid-stream: zero drops,
    identical in-order results, zero new compiles (widening keeps every
    stage boundary, so every StageFn and vmapped executable is reused)."""
    pipe = _jit_pipe()
    toks = [jnp.full((4,), float(i + 1)) for i in range(24)]
    want = pipe.run_sequential(toks)

    ex_serial = pipe.executor(max_in_flight=6, microbatch=2,
                              pad_microbatches=True)
    ex_serial.warmup(toks[0])
    compiles_warm = pipe.compile_count()

    with RequestQueueServer(ex_serial, max_batch=2, max_wait_ms=2.0) as srv:
        reqs = [srv.submit(t) for t in toks[:12]]
        ex_rep = pipe.executor(microbatch=2, pad_microbatches=True,
                               replicas=[1, 3, 1, 1][: len(pipe.stage_fns)])
        old = srv.swap_executor(ex_rep, warm_args=(toks[0],))
        assert old is ex_serial and srv.executor is ex_rep
        reqs += [srv.submit(t) for t in toks[12:]]
        got = [r.wait(timeout=60.0) for r in reqs]      # zero drops

    for g, w in zip(got, want):                          # identical results
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
    assert (ex_serial.stats().tokens_retired
            + ex_rep.stats().tokens_retired) == 24
    assert ex_rep.stats().out_of_order_retired == 0      # in-order retirement
    assert pipe.compile_count() == compiles_warm         # zero new executables
    ex_rep.close()


def test_hot_swap_outside_serving_loop_is_immediate():
    pipe = _jit_pipe()
    ex_a = pipe.executor()
    srv = RequestQueueServer(ex_a)        # never started
    ex_b = pipe.executor()
    old = srv.swap_executor(ex_b)
    assert old is ex_a and srv.executor is ex_b and srv.swaps == 1


def test_percentile_is_nan_free_on_tiny_and_dirty_windows():
    assert _percentile([], 95) == 0.0
    assert _percentile([3.0], 95) == pytest.approx(3.0)
    assert _percentile([1.0, float("nan"), 3.0], 50) == pytest.approx(2.0)
    assert _percentile([float("nan")], 50) == 0.0
    assert _percentile([None, 2.0], 50) == pytest.approx(2.0)
    assert np.isfinite(_percentile([float("inf"), 1.0], 50))
