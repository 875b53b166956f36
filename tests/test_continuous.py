"""Continuous batching — the in-flight join seam (ISSUE 10 tentpole).

Executor level: ``try_join`` fills an unsealed group's padding seat (and
only that), ``try_evict`` turns a seat back into a dead row before the
seal, and ``seam_capacity`` reports exactly the free seats.  The tests
pin the seam open deterministically by blocking the single stage-0
worker inside an older group's stage body — everything behind it in the
ring stays unsealed.

Serving level: randomized join/leave stress through the continuous
:class:`RequestQueueServer` over a stateful KV pipeline, checked against
analytically computed outputs (any slot aliasing, double-append, or
out-of-order retirement shows up as a bitwise mismatch), plus the same
stress under injected transient faults, and the exactly-once
``on_finish`` release hook on shed/expired terminal paths.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from repro.core.executor import PipelineExecutor
from repro.launch.serve import (DeadlineExceeded, ExecutorClosed,
                                RequestQueueServer)
from repro.runtime.faults import FaultPlan
from repro.runtime.kvstate import KVSlotPool

IO = 8
MB = 4


def _stage_fns(pool: KVSlotPool, *, stage_ms: float = 1.0,
               gate: threading.Event | None = None,
               entered: threading.Event | None = None) -> list:
    """3-stage decode-shaped host pipeline (pre / stateful kv / post),
    shape-polymorphic over ``[IO]`` and ``[B, IO]``.  When ``gate`` is
    given, the FIRST ``pre`` call signals ``entered`` and blocks on the
    gate — the stage-0 worker is now parked inside a sealed group, so
    every group submitted after it stays unsealed (a deterministic seam).
    """
    first = [True]

    def pre(env):
        if gate is not None and first[0]:
            first[0] = False
            entered.set()
            assert gate.wait(timeout=10.0)
        time.sleep(stage_ms / 1e3)
        x = np.asarray(env["x"], dtype=np.float32)
        return {"x": x + 1.0, "slot": env["slot"]}

    def kv(env):
        x = np.asarray(env["x"], dtype=np.float32)
        x2 = x if x.ndim == 2 else x[None]
        slots = np.atleast_1d(np.asarray(env["slot"])).astype(np.int64)
        y = np.empty_like(x2)
        for i in range(x2.shape[0]):
            sid = int(slots[i])
            hist = pool.read(sid)["k"]
            pool.append(sid, k=x2[i])
            y[i] = x2[i] + hist.sum(axis=0, dtype=np.float32)
        return {"x": y if x.ndim == 2 else y[0]}

    def post(env):
        x = np.asarray(env["x"], dtype=np.float32)
        return {"y": x * 0.5}

    return [pre, kv, post]


def _executor(fns, *, open_groups: bool = True,
              replicas=(1, 1, 1), **kw) -> PipelineExecutor:
    return PipelineExecutor(
        fns, ["x", "slot"], ["y"], max_in_flight=64,
        replicas=list(replicas), microbatch=MB, pad_microbatches=True,
        buckets=(MB,), batched_fns=fns, open_groups=open_groups,
        pad_token=(np.zeros(IO, np.float32), -1), **kw)


def _expected_step(pool_rows: list[np.ndarray], x: np.ndarray) -> np.ndarray:
    """What one decode step must return given the rows already in the
    slot — same float32 ops/order as the kv stage, so bitwise-comparable."""
    row = np.asarray(x, np.float32) + 1.0
    hist = (np.stack(pool_rows) if pool_rows
            else np.zeros((0, IO), np.float32))
    return (row + hist.sum(axis=0, dtype=np.float32)) * 0.5


# --------------------------------------------------------------------------- #
# Executor seam: join / evict / capacity
# --------------------------------------------------------------------------- #
def test_try_join_fills_open_seats_then_refuses():
    pool = KVSlotPool(8, 4, {"k": (IO,)})
    gate, entered = threading.Event(), threading.Event()
    ex = _executor(_stage_fns(pool, gate=gate, entered=entered))
    try:
        blocker = ex.submit(np.zeros(IO, np.float32), -1)
        assert entered.wait(5.0)          # stage-0 worker parked: seam open
        slots = [pool.alloc() for _ in range(4)]
        xs = np.arange(4 * IO, dtype=np.float32).reshape(4, IO)
        hB = ex.submit(xs[0], slots[0])   # 1 real token, 3 padding seats
        assert ex.seam_capacity() == MB - 1
        # signature mismatch never claims a seat
        assert ex.try_join((np.zeros(IO + 1, np.float32), slots[1])) is None
        joins = [ex.try_join((xs[i], slots[i])) for i in (1, 2, 3)]
        assert all(j is not None for j in joins)
        assert ex.seam_capacity() == 0    # group full: seam exhausted
        assert ex.try_join((xs[1], slots[1])) is None
        gate.set()
        np.testing.assert_array_equal(
            np.asarray(blocker.result()), (np.zeros(IO, np.float32) + 1) * 0.5)
        for h, i in zip([hB] + joins, range(4)):
            np.testing.assert_array_equal(np.asarray(h.result()),
                                          _expected_step([], xs[i]))
        st = ex.stats()
        assert st.seam_joins == 3
        assert st.tokens_retired == 5 and st.out_of_order_retired == 0
        # every live row appended exactly once; padding touched nothing
        assert [pool.length(s) for s in slots] == [1, 1, 1, 1]
    finally:
        gate.set()
        ex.close()
    for s in slots:
        pool.free(s)
    pool.check_no_leaks()


def test_try_evict_unsealed_seat_is_dead_row():
    pool = KVSlotPool(4, 4, {"k": (IO,)})
    gate, entered = threading.Event(), threading.Event()
    ex = _executor(_stage_fns(pool, gate=gate, entered=entered))
    try:
        blocker = ex.submit(np.zeros(IO, np.float32), -1)
        assert entered.wait(5.0)
        s_live, s_gone = pool.alloc(), pool.alloc()
        x = np.ones((2, IO), np.float32)
        hB = ex.submit(x[0], s_live)
        hJ = ex.try_join((x[1], s_gone))
        assert hJ is not None
        boom = RuntimeError("client went away")
        assert ex.try_evict(hJ, boom) is True
        assert ex.try_evict(hJ, boom) is True      # idempotent
        gate.set()
        np.testing.assert_array_equal(np.asarray(hB.result()),
                                      _expected_step([], x[0]))
        with pytest.raises(RuntimeError, match="client went away"):
            hJ.result()
        blocker.result()
        # the evicted seat ran as the dead row: its slot was never touched
        assert pool.length(s_gone) == 0 and pool.length(s_live) == 1
        assert ex.stats().seam_evictions == 1
        # once the group sealed and retired, eviction is too late
        assert ex.try_evict(hB) is False
    finally:
        gate.set()
        ex.close()
    pool.free(s_live)
    pool.free(s_gone)
    pool.check_no_leaks()


def test_seam_closed_without_open_groups():
    pool = KVSlotPool(2, 4, {"k": (IO,)})
    ex = _executor(_stage_fns(pool), open_groups=False)
    try:
        assert ex.seam_capacity() == 0
        assert ex.try_join((np.zeros(IO, np.float32), -1)) is None
    finally:
        ex.close()


# --------------------------------------------------------------------------- #
# Serving stress: randomized join/leave, analytic ground truth
# --------------------------------------------------------------------------- #
def _drive_continuous(srv: RequestQueueServer, pool: KVSlotPool,
                      arrivals: np.ndarray, xs: np.ndarray,
                      lengths: np.ndarray) -> list:
    """Sessions of randomized length decode sequentially; the last step
    frees the slot through ``on_finish``.  An arrival that finds every
    slot live waits for a leave, as a front end's admission would, so the
    pool bounds concurrency however slowly the host runs.  Returns
    per-session output lists (None entries on error)."""
    n = len(arrivals)
    outs: list = [[None] * int(lengths[i]) for i in range(n)]
    slots: list = [None] * n
    step = [0] * n
    active: dict = {}
    lock = threading.Lock()

    def _release(sess):
        with lock:
            s, slots[sess] = slots[sess], None
        if s is not None:
            pool.free(s)

    def _submit(sess):
        t = step[sess]
        last = t == lengths[sess] - 1
        active[sess] = srv.submit(
            xs[sess, t], slots[sess],
            priority="interactive" if t == 0 else "batch",
            on_finish=(lambda _r, s=sess: _release(s)) if last else None)

    t0 = time.perf_counter()
    nxt = 0
    while nxt < n or active:
        now = time.perf_counter() - t0
        while (nxt < n and arrivals[nxt] <= now
               and pool.live_count() < pool.n_slots):
            slots[nxt] = pool.alloc()
            _submit(nxt)
            nxt += 1
        progressed = False
        for sess, r in list(active.items()):
            if not r._event.is_set():
                continue
            progressed = True
            del active[sess]
            outs[sess][step[sess]] = np.asarray(r.wait(0))
            step[sess] += 1
            if step[sess] < lengths[sess]:
                _submit(sess)
        if not progressed:
            time.sleep(0.0002)
    return outs


def _stress(fault_injector=None, replicas=(1, 1, 1)) -> None:
    rng = np.random.default_rng(5)
    n = 20
    lengths = rng.integers(1, 5, size=n)          # join/leave at random times
    arrivals = np.cumsum(rng.exponential(1 / 300.0, size=n))  # bursty overlap
    xs = rng.standard_normal((n, 4, IO)).astype(np.float32)
    pool = KVSlotPool(12, 4, {"k": (IO,)})
    kw = {} if fault_injector is None else {
        "fault_injector": fault_injector, "quarantine_after": 2}
    ex = _executor(_stage_fns(pool), replicas=replicas, **kw)
    srv = RequestQueueServer(ex, max_batch=MB, max_wait_ms=2.0,
                             queue_depth=256, continuous=True)
    with srv:
        outs = _drive_continuous(srv, pool, arrivals, xs, lengths)
    st, xst = srv.stats(), ex.stats()
    ex.close()
    pool.check_no_leaks()                          # every leave freed its slot
    for sess in range(n):
        rows: list = []
        for t in range(int(lengths[sess])):
            y = outs[sess][t]
            assert y is not None, f"session {sess} step {t} never resolved"
            np.testing.assert_array_equal(y, _expected_step(rows, xs[sess, t]))
            rows.append(np.asarray(xs[sess, t], np.float32) + 1.0)
    total = int(lengths.sum())
    assert st["submitted"] == total and st["requests_served"] == total
    assert st["shed"] + st["expired"] + st["failed"] == 0
    assert st["release_errors"] == 0
    assert xst.out_of_order_retired == 0
    ps = pool.stats()
    assert ps["allocs"] == n and ps["frees"] == n  # never aliased, never leaked
    assert ps["high_water"] <= pool.n_slots
    return st, xst


def test_randomized_continuous_stress_bitwise_ground_truth():
    _stress()


def test_continuous_stress_survives_transient_faults():
    # transients on the replicated pure front stage retry on the sibling
    # (one quarantine allowed); the serial stateful stage is never faulted,
    # so retries must not double-append and outputs stay bit-exact
    inj = FaultPlan().transient(0, at_calls=[1, 4, 9]).build()
    st, xst = _stress(fault_injector=inj, replicas=(2, 1, 1))
    assert xst.retries + xst.quarantined >= 1      # the chaos actually landed


# --------------------------------------------------------------------------- #
# on_finish: exactly once, on every terminal path
# --------------------------------------------------------------------------- #
def test_on_finish_exactly_once_on_shed_and_expired():
    pool = KVSlotPool(4, 4, {"k": (IO,)})
    ex = _executor(_stage_fns(pool, stage_ms=2.0))
    calls: list = []
    srv = RequestQueueServer(ex, max_batch=MB, max_wait_ms=2.0,
                             queue_depth=64, continuous=True)
    with srv:
        s1 = pool.alloc()
        r1 = srv.submit(np.zeros(IO, np.float32), s1, deadline_ms=0.001,
                        on_finish=lambda r: (calls.append(("r1", r)),
                                             pool.free(s1)))
        with pytest.raises(DeadlineExceeded):
            r1.wait(5.0)
    # stopped server: the shed path still runs the release hook
    s2 = pool.alloc()
    r2 = srv.submit(np.zeros(IO, np.float32), s2,
                    on_finish=lambda r: (calls.append(("r2", r)),
                                         pool.free(s2)))
    with pytest.raises(ExecutorClosed):
        r2.wait(1.0)
    ex.close()
    assert [c[0] for c in calls] == ["r1", "r2"]   # exactly once each
    assert calls[0][1].error is not None and calls[1][1].error is not None
    pool.check_no_leaks()                           # both slots returned
    st = srv.stats()
    assert st["expired"] == 1 and st["shed"] == 1
    assert st["release_errors"] == 0
