"""Overload-protection tests: priority classes, EDF + starvation credit,
admission control, degradation ladder, end-to-end deadlines, and
exactly-once accounting under racy interleavings.

The serving layer's core claim is an invariant, not a number: every
submitted request resolves **exactly once** into served / shed / expired /
failed, no matter how submit, stop, deadlines, and the batcher interleave.
The property-style test here drives randomized interleavings against that
claim; the unit tests pin the individual mechanisms the invariant is built
from.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import ModuleDatabase, StageProfiler, linear_ir
from repro.core.executor import ExecutorClosed
from repro.launch.serve import (BATCH, BEST_EFFORT, INTERACTIVE,
                                PRIORITY_CLASSES, AdmissionController,
                                DeadlineExceeded, Overloaded, Request,
                                RequestQueueServer, WaitTimeout,
                                _ClassedQueue, _percentile, priority_of)
from repro.runtime import ElasticPlanner, ReplanDecision

DELAYS: dict = {}


def _impl(key):
    def sw(x):
        time.sleep(DELAYS[key] / 1e3)
        return np.asarray(x) + 1.0
    sw.__name__ = key
    return sw


def _chain_planner(times=(1.0, 2.0), **kw):
    keys = [f"f{i}" for i in range(len(times))]
    DELAYS.clear()
    DELAYS.update(dict(zip(keys, times)))
    db = ModuleDatabase("overload-chain")
    for k in keys:
        db.register(k, software=_impl(k))
    ir = linear_ir("overload-chain", keys, list(times), io_shape=(4,))
    return ElasticPlanner(ir, db=db, **kw)


def _executor(times=(1.0, 2.0), **kw):
    planner = _chain_planner(times)
    ex, _ = planner.executor_for(len(times), jit=False, **kw)
    return ex, planner


# --------------------------------------------------------------------------- #
# _percentile: exact linear interpolation + p999 (satellite 3)
# --------------------------------------------------------------------------- #
def test_percentile_matches_numpy_linear_interpolation():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 7, 100, 999):
        xs = list(rng.uniform(0, 50, size=n))
        for q in (0, 50, 95, 99, 99.9, 100):
            assert _percentile(xs, q) == pytest.approx(
                float(np.percentile(np.asarray(xs), q)), rel=1e-12)


def test_percentile_filters_nonfinite_and_none():
    xs = [1.0, None, float("nan"), 3.0, float("inf"), 2.0]
    assert _percentile(xs, 50) == 2.0
    assert _percentile([], 99) == 0.0
    assert _percentile([None, float("nan")], 50) == 0.0


def test_latency_summary_has_tail_quantiles():
    ex, _ = _executor()
    with RequestQueueServer(ex, max_batch=2, max_wait_ms=1.0) as srv:
        for _ in range(4):
            srv.submit(np.ones(4)).wait(timeout=30.0)
    lat = srv.stats()["latency_ms"]
    for k in ("mean", "p50", "p95", "p99", "p999", "max"):
        assert k in lat and lat[k] > 0
    assert lat["p50"] <= lat["p99"] <= lat["p999"] <= lat["max"]
    ex.close()


# --------------------------------------------------------------------------- #
# Request.wait timeout distinguishability (satellite 1)
# --------------------------------------------------------------------------- #
def test_wait_timeout_raises_distinct_timeout_error():
    r = Request(args=(1,), t_submit=time.perf_counter())
    with pytest.raises(WaitTimeout):
        r.wait(timeout=0.01)
    # WaitTimeout (my wait gave up) and DeadlineExceeded (the server
    # failed the request) are both TimeoutError but distinguishable
    assert issubclass(WaitTimeout, TimeoutError)
    assert issubclass(DeadlineExceeded, TimeoutError)
    assert not issubclass(WaitTimeout, DeadlineExceeded)
    assert not issubclass(DeadlineExceeded, WaitTimeout)
    # a later wait still observes a late resolution (nothing was consumed)
    r.result = 42
    r._event.set()
    assert r.wait(timeout=0.01) == 42


def test_priority_of_accepts_names_and_indices():
    assert priority_of("interactive") == INTERACTIVE
    assert priority_of("best-effort") == BEST_EFFORT
    assert priority_of(BATCH) == BATCH
    with pytest.raises(ValueError):
        priority_of("platinum")
    with pytest.raises(ValueError):
        priority_of(3)


# --------------------------------------------------------------------------- #
# _ClassedQueue: EDF within class, strict priority across, starvation credit
# --------------------------------------------------------------------------- #
def _req(priority=INTERACTIVE, deadline_ms=None):
    return Request(args=(), t_submit=time.perf_counter(),
                   deadline_ms=deadline_ms, priority=priority)


def test_classed_queue_edf_within_class():
    q = _ClassedQueue(16)
    late = _req(deadline_ms=500.0)
    soon = _req(deadline_ms=10.0)
    never = _req()                      # no deadline: after every deadlined
    for r in (late, never, soon):
        assert q.put(r) == "ok"
    order = [q.get_first(lambda: False)[0] for _ in range(3)]
    assert order == [soon, late, never]


def test_classed_queue_strict_priority_across_classes():
    q = _ClassedQueue(16)
    be = _req(priority=BEST_EFFORT)
    ia = _req(priority=INTERACTIVE)
    ba = _req(priority=BATCH)
    for r in (be, ba, ia):
        q.put(r)
    got = [q.get_first(lambda: False)[0] for _ in range(3)]
    assert got == [ia, ba, be]


def test_classed_queue_starvation_credit_grants_trickle():
    credit = 3
    q = _ClassedQueue(64, credit=credit)
    q.put(_req(priority=BATCH))
    picks = []
    for _ in range(credit + 1):
        q.put(_req(priority=INTERACTIVE))
        r, override = q.get_first(lambda: False)
        picks.append((r.priority, override))
    # the batch request was passed over `credit` times, then granted a
    # trickle batch (override flag True) ahead of waiting interactive work
    assert picks[:credit] == [(INTERACTIVE, False)] * credit
    assert picks[credit] == (BATCH, True)
    # the interactive request enqueued in the last round is still there
    r, override = q.get_first(lambda: False)
    assert (r.priority, override) == (INTERACTIVE, False)


def test_classed_queue_put_full_and_closed():
    q = _ClassedQueue(1)
    assert q.put(_req()) == "ok"
    assert q.put(_req(), block=False) == "full"
    q.close()
    assert q.put(_req(), block=False) == "closed"
    assert q.put(_req(), block=True) == "closed"   # close unblocks producers


def test_classed_queue_depth_upto_counts_higher_classes():
    q = _ClassedQueue(16)
    q.put(_req(priority=INTERACTIVE))
    q.put(_req(priority=BATCH))
    q.put(_req(priority=BEST_EFFORT))
    assert q.depth_upto(INTERACTIVE) == 1
    assert q.depth_upto(BATCH) == 2
    assert q.depth_upto(BEST_EFFORT) == 3
    assert q.depths() == [1, 1, 1]


# --------------------------------------------------------------------------- #
# AdmissionController
# --------------------------------------------------------------------------- #
def test_admission_predicted_wait_and_deadline_shed():
    adm = AdmissionController(period_ms=10.0, batch_hint=1)
    assert adm.predicted_wait_ms(0) == 0.0
    assert adm.predicted_wait_ms(5) == 50.0
    # infeasible deadline at submit time -> shed with a reason
    reason = adm.admit(priority=INTERACTIVE, deadline_ms=30.0,
                       depth_ahead=5, depth_total=5)
    assert reason is not None and "deadline" in reason
    assert adm.shed[INTERACTIVE] == 1
    assert adm.shed_reasons["deadline"] == 1
    # feasible deadline -> admitted
    assert adm.admit(priority=INTERACTIVE, deadline_ms=80.0,
                     depth_ahead=5, depth_total=5) is None
    assert adm.admitted[INTERACTIVE] == 1


def test_admission_batch_hint_groups_the_wait():
    adm = AdmissionController(period_ms=10.0, batch_hint=4)
    assert adm.predicted_wait_ms(4) == 10.0     # one dispatch group
    assert adm.predicted_wait_ms(5) == 20.0     # spills into a second


def test_admission_ladder_sheds_best_effort_then_degrades_wait():
    adm = AdmissionController(period_ms=10.0, slo_ref_ms=100.0,
                              shed_at=0.5, degrade_at=1.0,
                              degraded_wait_scale=0.5)
    # level 0: everything admitted
    assert adm.admit(priority=BEST_EFFORT, deadline_ms=None,
                     depth_ahead=0, depth_total=4) is None
    assert adm.max_wait_scale() == 1.0
    # level 1 (backlog > 50 ms): best-effort shed, batch still admitted
    reason = adm.admit(priority=BEST_EFFORT, deadline_ms=None,
                       depth_ahead=0, depth_total=6)
    assert reason is not None and "ladder" in reason
    assert adm.admit(priority=BATCH, deadline_ms=None,
                     depth_ahead=0, depth_total=6) is None
    assert adm.max_wait_scale() == 1.0
    # level 2 (backlog > 100 ms): also shrink the batcher's max wait
    assert adm.admit(priority=BEST_EFFORT, deadline_ms=None,
                     depth_ahead=0, depth_total=11) is not None
    assert adm.max_wait_scale() == 0.5
    snap = adm.snapshot()
    assert snap["level"] == 2
    assert snap["shed"]["best_effort"] == 2
    assert snap["shed_reasons"]["ladder"] == 2


def test_admission_from_plan_and_update_period():
    planner = _chain_planner((1.0, 2.0))
    planner.executor_for(2, jit=False)[0].close()
    plan = planner.current_plan
    adm = AdmissionController.from_plan(plan, max_batch=4)
    assert adm.period_ms == pytest.approx(plan.effective_bottleneck_ms)
    assert adm.batch_hint == 4
    adm.update_period(7.5)
    assert adm.period_ms == 7.5
    adm.update_period(0.0)                     # ignored: not a valid period
    assert adm.period_ms == 7.5


def test_profiler_effective_period_feeds_admission():
    prof = StageProfiler(2, min_samples=2)
    assert prof.effective_period_ms() is None  # no samples yet
    for _ in range(3):
        prof.record(0, 2.0)
        prof.record(1, 8.0)
    assert prof.effective_period_ms() == pytest.approx(8.0)
    # replication-aware: the widened bottleneck drains r-wide
    assert prof.effective_period_ms([1, 4]) == pytest.approx(2.0)
    assert prof.effective_period_ms([1, 2, 3]) is None   # wrong shape


# --------------------------------------------------------------------------- #
# Server integration: shedding, priorities, end-to-end deadlines
# --------------------------------------------------------------------------- #
def test_server_sheds_instead_of_blocking_with_admission():
    ex, _ = _executor((1.0, 5.0))
    adm = AdmissionController(period_ms=5.0, batch_hint=1)
    with RequestQueueServer(ex, max_batch=2, max_wait_ms=1.0,
                            admission=adm) as srv:
        r = srv.submit(np.ones(4), deadline_ms=2.0)   # infeasible: depth>0
        ok = srv.submit(np.ones(4))                   # no deadline: admitted
        # the first submit lands before any dispatch: in_flight 0, queue 0
        # -> admitted; pile on until prediction crosses the deadline
        sheds = [srv.submit(np.ones(4), deadline_ms=1.0) for _ in range(8)]
        shed_errors = 0
        for s in sheds:
            try:
                s.wait(timeout=30.0)
            except Overloaded:
                shed_errors += 1
            except DeadlineExceeded:
                pass
        ok.wait(timeout=30.0)
        try:
            r.wait(timeout=30.0)
        except (Overloaded, DeadlineExceeded):
            pass
    st = srv.stats()
    assert shed_errors >= 1                    # fast-fails, not queue waits
    assert st["admission"]["shed_reasons"]["deadline"] >= 1
    assert st["submitted"] == st["requests_served"] + st["shed"] \
        + st["expired"] + st["failed"]
    ex.close()


def test_end_to_end_deadline_fails_at_retirement_not_late():
    ex, _ = _executor((1.0, 30.0))             # slow stage: ~31 ms service
    with RequestQueueServer(ex, max_batch=1, max_wait_ms=0.5) as srv:
        r = srv.submit(np.ones(4), deadline_ms=5.0)   # dispatches, too slow
        with pytest.raises(DeadlineExceeded):
            r.wait(timeout=30.0)
    st = srv.stats()
    assert st["expired"] == 1
    assert st["classes"]["interactive"]["expired"] == 1
    assert st["slo_violation_rate"] == 1.0
    ex.close()


def test_interactive_served_before_batch_backlog():
    ex, _ = _executor((1.0, 4.0))
    with RequestQueueServer(ex, max_batch=2, max_wait_ms=0.5,
                            queue_depth=64) as srv:
        batch = [srv.submit(np.ones(4), priority=BATCH) for _ in range(10)]
        ia = srv.submit(np.ones(4), priority="interactive")
        ia.wait(timeout=30.0)
        done_batch = sum(1 for b in batch if b.t_done is not None)
        # the interactive request overtook most of the earlier batch backlog
        assert done_batch < len(batch)
        for b in batch:
            b.wait(timeout=30.0)
    st = srv.stats()
    assert st["classes"]["interactive"]["served"] == 1
    assert st["classes"]["batch"]["served"] == 10
    ex.close()


def test_stats_backcompat_keys_and_rejected():
    ex, _ = _executor()
    srv = RequestQueueServer(ex, max_batch=2, max_wait_ms=1.0).start()
    srv.submit(np.ones(4)).wait(timeout=30.0)
    srv.stop()
    st = srv.stats()
    for k in ("requests_served", "batches", "mean_batch_size",
              "throughput_rps", "latency_ms", "queue_ms_mean", "queue_depth",
              "rejected", "swaps", "executor", "profile"):
        assert k in st
    assert st["requests_served"] == 1 and st["rejected"] == 0
    assert st["executor"]["tokens_failed"] == 0
    r = srv.submit(np.ones(4))                 # post-stop: shed
    with pytest.raises(ExecutorClosed):
        r.wait(timeout=5.0)
    assert srv.stats()["rejected"] == 1
    ex.close()


# --------------------------------------------------------------------------- #
# Property-style: every request resolves exactly once (satellite 4)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_every_request_resolves_exactly_once_under_races(seed):
    rng = np.random.default_rng(seed)
    ex, _ = _executor((0.5, 1.0))
    adm = AdmissionController(period_ms=1.5, batch_hint=1,
                              slo_ref_ms=60.0) \
        if seed % 2 == 0 else None
    srv = RequestQueueServer(ex, max_batch=3, max_wait_ms=1.0,
                             queue_depth=8, admission=adm).start()
    reqs: list = []
    lock = threading.Lock()

    def submitter(tseed):
        trng = np.random.default_rng(tseed)
        for _ in range(20):
            pri = int(trng.integers(0, 3))
            dl = float(trng.uniform(1.0, 40.0)) \
                if trng.random() < 0.5 else None
            r = srv.submit(np.ones(4), deadline_ms=dl, priority=pri)
            with lock:
                reqs.append(r)
            time.sleep(float(trng.uniform(0, 0.003)))

    threads = [threading.Thread(target=submitter, args=(seed * 10 + i,))
               for i in range(4)]
    for t in threads:
        t.start()
    # stop races the submitters mid-stream on odd seeds
    if seed % 2 == 1:
        time.sleep(float(rng.uniform(0.01, 0.05)))
        srv.stop()
    for t in threads:
        t.join()
    if seed % 2 == 0:
        srv.stop()

    st = srv.stats()
    # exactly-once: the per-class counters account for every submission...
    assert st["submitted"] == len(reqs) == 80
    assert st["submitted"] == st["requests_served"] + st["shed"] \
        + st["expired"] + st["failed"]
    per_class = [st["classes"][name] for name in PRIORITY_CLASSES]
    for c in per_class:
        assert c["submitted"] == c["served"] + c["shed"] + c["expired"] \
            + c["failed"]
    # ...and every request object resolved (event set, outcome visible):
    # nothing is left blocked in wait() forever
    for r in reqs:
        try:
            r.wait(timeout=10.0)
            assert r.error is None and r.t_done is not None
        except WaitTimeout:
            pytest.fail("request never resolved (blocked forever)")
        except (Overloaded, DeadlineExceeded, ExecutorClosed):
            pass
    ex.close()


def test_stop_wakes_idle_batcher_promptly():
    """Satellite 2: no 0.02 s poll — an idle server stops in well under
    one legacy poll interval."""
    ex, _ = _executor()
    srv = RequestQueueServer(ex, max_batch=4, max_wait_ms=50.0).start()
    time.sleep(0.05)                  # batcher parks on the empty queue
    t0 = time.perf_counter()
    srv.stop()
    assert time.perf_counter() - t0 < 0.5
    ex.close()


def test_swap_executor_wakes_idle_batcher():
    ex, planner = _executor()
    ex2, _ = planner.executor_for(2, jit=False, max_in_flight=5)
    with RequestQueueServer(ex, max_batch=2, max_wait_ms=1.0) as srv:
        srv.submit(np.ones(4)).wait(timeout=30.0)
        time.sleep(0.02)              # batcher idle-blocked on the queue
        old = srv.swap_executor(ex2, timeout=10.0)
        assert old is ex and srv.executor is ex2
        srv.submit(np.ones(4)).wait(timeout=30.0)
    assert srv.stats()["swaps"] == 1
    ex.close()
    ex2.close()


# --------------------------------------------------------------------------- #
# SLO feedback into the replanner
# --------------------------------------------------------------------------- #
def test_slo_violation_rate_waives_replan_hysteresis():
    planner = _chain_planner((4.0, 4.0))
    prof = StageProfiler(2, min_samples=2)
    ex, _ = planner.executor_for(2, jit=False, profiler=prof)
    # measured 4.0/4.4 with a 3-worker budget: widening the slow stage to
    # 2 replicas predicts effective max(4.0, 4.4/2) = 4.0 ms — a 1.1x
    # win, below the default 1.15x hysteresis gate
    for _ in range(6):
        prof.record(0, 4.0)
        prof.record(1, 4.4)
    d_calm = planner.replan_from_profile(prof, worker_budget=3,
                                         slo_violation_rate=0.0)
    assert not d_calm.replanned and "hysteresis" in d_calm.reason
    # the same profile under SLO pressure: any predicted win justifies
    # the (zero-drop) swap, so hysteresis is waived
    d_hot = planner.replan_from_profile(prof, worker_budget=3,
                                        slo_violation_rate=0.2)
    assert d_hot.replanned
    assert "SLO pressure" in d_hot.reason
    ex.close()
    if d_hot.executor is not None:
        d_hot.executor.close()


def test_random_transients_from_call_exempts_warmup():
    from repro.runtime.faults import FaultPlan, InjectedFault

    plan = FaultPlan().random_transients(0.9, seed=3, stages=[0],
                                         from_call=50)
    inj = plan.build()
    for _ in range(50):               # warmup window: never faults
        inj.on_stage_call(0)
    assert inj.injected == 0
    with pytest.raises(InjectedFault):
        for _ in range(40):           # post-warmup: rate 0.9 fires fast
            inj.on_stage_call(0)
    assert inj.injected >= 1


# --------------------------------------------------------------------------- #
# sustained-overload autoscale: ladder level-2 streak -> widening replan
# (ISSUE 10 satellite: capacity response instead of shedding forever)
# --------------------------------------------------------------------------- #
def _level2_window(adm):
    """One observation window whose worst admission-time level reached 2
    (backlog 11 x 10 ms > degrade_at x slo_ref_ms)."""
    adm.admit(priority=BEST_EFFORT, deadline_ms=None,
              depth_ahead=0, depth_total=11)
    adm.end_window()


def test_level2_streak_counts_consecutive_windows_only():
    adm = AdmissionController(period_ms=10.0, slo_ref_ms=100.0,
                              shed_at=0.5, degrade_at=1.0)
    _level2_window(adm)
    _level2_window(adm)
    assert adm.level2_streak == 2
    # a milder window (level 0) breaks the streak
    adm.admit(priority=BATCH, deadline_ms=None, depth_ahead=0, depth_total=1)
    adm.end_window()
    assert adm.level2_streak == 0
    _level2_window(adm)
    assert adm.level2_streak == 1
    assert adm.snapshot()["level2_streak"] == 1
    adm.reset_streak()
    assert adm.level2_streak == 0


def test_autoscale_from_ladder_widens_after_sustained_streak():
    planner = _chain_planner((1.0, 4.0))          # f1 is the 4 ms bottleneck
    planner.executor_for(2, jit=False)[0].close()
    prof = StageProfiler(2, min_samples=4)
    for _ in range(6):
        prof.record(0, 1.0)
        prof.record(1, 4.0)
    adm = AdmissionController(period_ms=10.0, slo_ref_ms=100.0,
                              shed_at=0.5, degrade_at=1.0)

    # below the trigger: no replan attempt, the streak keeps accumulating
    for want in (1, 2):
        _level2_window(adm)
        d = planner.autoscale_from_ladder(adm, prof, worker_budget=4,
                                          streak=3, jit=False)
        assert d is None and adm.level2_streak == want
    assert planner.replan_checks == 0             # never reached the planner

    # third consecutive level-2 window trips the widen
    _level2_window(adm)
    d = planner.autoscale_from_ladder(adm, prof, worker_budget=4,
                                      streak=3, jit=False)
    assert isinstance(d, ReplanDecision) and d.replanned
    assert d.plan.replicas is not None and max(d.plan.replicas) > 1
    assert d.new_bottleneck_ms < d.old_bottleneck_ms
    assert adm.level2_streak == 0                 # one burst, one attempt
    if d.executor is not None:
        d.executor.close()

    with pytest.raises(ValueError, match="streak"):
        planner.autoscale_from_ladder(adm, prof, worker_budget=4, streak=0)
