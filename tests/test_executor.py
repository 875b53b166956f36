"""Async PipelineExecutor + serving loop + pipeline bugfix regressions."""
import contextlib
import os
import subprocess
import sys
import textwrap
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Frontend, Library, ModuleDatabase, Node,
                        PipelineExecutor, PipelineGenerator, fuse_adjacent_hw,
                        linear_ir)
from repro.core.pipeline import _liveness, make_stage_fns
from repro.launch.serve import RequestQueueServer
from repro.runtime import ElasticPlanner


# --------------------------------------------------------------------------- #
# graph fixtures
# --------------------------------------------------------------------------- #
def _linear_db():
    db = ModuleDatabase("t")
    db.register("mul2", software=lambda x: x * 2.0)
    db.register("add1", software=lambda x: x + 1.0)
    db.register("sq", software=lambda x: x * x)
    db.register("tanh", software=jnp.tanh)
    return db


def _linear_app(lib):
    def app(x):
        return lib.tanh(lib.sq(lib.add1(lib.mul2(x))))
    return app


def _branch_db():
    db = ModuleDatabase("t")
    db.register("a", software=lambda x: x + 1.0)
    db.register("b", software=lambda x: x * 2.0)
    db.register("c", software=lambda x, y: x + y)    # consumes BOTH a and b
    db.register("d", software=lambda x: x - 0.5)
    return db


def _branch_app(lib):
    def app(x):
        u = lib.a(x)
        v = lib.b(u)
        return lib.d(lib.c(u, v))
    return app


def _pipe(db, app, n_threads=3, x=None):
    x = jnp.arange(4.0) if x is None else x
    ir, _ = Frontend(db).trace(app, x, profile=False)
    for n in ir.nodes:
        n.time_ms = 1.0
    return PipelineGenerator(db).generate(ir, n_threads=n_threads)


# --------------------------------------------------------------------------- #
# async run ≡ run_sequential (linear + branching), in order
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mkdb,mkapp", [(_linear_db, _linear_app),
                                        (_branch_db, _branch_app)])
@pytest.mark.parametrize("pool", [1, 2, 5])
def test_async_run_matches_sequential(mkdb, mkapp, pool):
    db = mkdb()
    app = mkapp(Library(db))
    pipe = _pipe(db, app)
    toks = [jnp.full((4,), float(i + 1)) for i in range(7)]
    want = pipe.run_sequential(toks)
    got = pipe.run_async(toks, max_in_flight=pool)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)


def test_async_matches_sync_wavefront_run():
    db = _branch_db()
    app = _branch_app(Library(db))
    pipe = _pipe(db, app)
    toks = [jnp.full((4,), float(i)) for i in range(5)]
    for g, w in zip(pipe.run_async(toks), pipe.run(toks)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)


# --------------------------------------------------------------------------- #
# bounded token pool
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("pool", [1, 2, 3])
def test_bounded_pool_never_exceeded(pool):
    db = _linear_db()
    app = _linear_app(Library(db))
    pipe = _pipe(db, app)
    ex = pipe.executor(max_in_flight=pool)
    ex.run([jnp.full((4,), float(i)) for i in range(9)])
    s = ex.stats()
    assert s.tokens_retired == 9
    assert 1 <= s.max_in_flight_seen <= pool
    assert ex.in_flight == 0


def test_max_in_flight_zero_rejected_everywhere():
    db = _linear_db()
    app = _linear_app(Library(db))
    pipe = _pipe(db, app)
    pipe.max_in_flight = 0
    with pytest.raises(ValueError, match="max_in_flight"):
        pipe.run([jnp.ones(4)])
    with pytest.raises(ValueError, match="max_in_flight"):
        pipe.executor()
    with pytest.raises(ValueError, match="max_in_flight"):
        PipelineExecutor(pipe.stage_fns, pipe.graph_inputs,
                         pipe.graph_outputs, max_in_flight=0)
    with pytest.raises(ValueError, match="max_in_flight"):
        pipe.run_async([jnp.ones(4)], max_in_flight=-2)
    pipe.max_in_flight = None                    # None = default, still fine
    assert len(pipe.run([jnp.ones(4)])) == 1


# --------------------------------------------------------------------------- #
# micro-batching
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mkdb,mkapp", [(_linear_db, _linear_app),
                                        (_branch_db, _branch_app)])
def test_microbatch_path_equivalence(mkdb, mkapp):
    db = mkdb()
    app = mkapp(Library(db))
    pipe = _pipe(db, app)
    toks = [jnp.full((4,), float(i + 1)) for i in range(10)]
    want = pipe.run_sequential(toks)
    ex = pipe.executor(max_in_flight=8, microbatch=4)
    got = ex.run(toks)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
    s = ex.stats()
    assert s.groups_admitted < s.tokens_admitted      # stacking happened
    assert s.max_in_flight_seen <= 8


def test_microbatch_splits_on_shape_mismatch():
    db = _linear_db()
    app = _linear_app(Library(db))
    pipe = _pipe(db, app)
    # shape change mid-stream: groups must split rather than stack
    toks = [jnp.ones(4), jnp.ones(4), jnp.ones(3), jnp.ones(3), jnp.ones(4)]
    ex = pipe.executor(max_in_flight=8, microbatch=4)
    got = ex.run(toks)
    want = pipe.run_sequential(toks)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
    assert ex.stats().groups_admitted == 3            # [4,4], [3,3], [4]


def test_padded_microbatch_equivalence_and_no_ragged_groups():
    db = _branch_db()
    app = _branch_app(Library(db))
    pipe = _pipe(db, app)
    toks = [jnp.full((4,), float(i + 1)) for i in range(7)]   # 7 % 3 != 0
    want = pipe.run_sequential(toks)
    ex = pipe.executor(max_in_flight=6, microbatch=3, pad_microbatches=True)
    got = ex.run(toks)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
    s = ex.stats()
    # padding rows never count as tokens
    assert s.tokens_admitted == s.tokens_retired == 7
    # [3], [3], [1] — the singleton tail is never padded (the per-token
    # executables are always warm, so padding would only waste compute)
    assert s.groups_admitted == 3


def test_submit_many_rejects_bad_arity_before_admitting():
    db = _linear_db()
    app = _linear_app(Library(db))
    pipe = _pipe(db, app)
    ex = pipe.executor(max_in_flight=4)
    with pytest.raises(ValueError, match="token 1"):
        ex.submit_many([(jnp.ones(4),), (jnp.ones(4), jnp.ones(4))])
    # all-or-nothing: the valid token 0 must NOT have been issued
    assert ex.stats().tokens_admitted == 0
    assert ex.in_flight == 0


def test_submit_error_keeps_admitted_prefix():
    from repro.core.executor import SubmitError

    db = ModuleDatabase("t")
    db.register("dot4", software=lambda x: x @ jnp.ones(4))   # needs len-4 axis
    db.register("add1", software=lambda x: x + 1.0)
    lib = Library(db)

    def app(x):
        return lib.add1(lib.dot4(x))
    pipe = _pipe(db, app, n_threads=2)
    ex = pipe.executor(max_in_flight=4)
    ok = jnp.ones(4)
    bad = jnp.ones(3)                  # same arity, dim breaks the matmul
    with pytest.raises(SubmitError) as ei:
        ex.submit_many([ok, bad])
    # token 0 stayed admitted, its handle is usable, nothing was re-issued
    assert len(ei.value.handles) == 1
    want = pipe.run_sequential([ok])[0]
    np.testing.assert_allclose(np.asarray(ei.value.handles[0].result()),
                               np.asarray(want), rtol=1e-6)
    # the failed group unwound its pool reservation
    assert ex.in_flight == 0
    assert ex.stats().tokens_admitted == 1


# --------------------------------------------------------------------------- #
# liveness: stage-boundary envs carry exactly the live set
# --------------------------------------------------------------------------- #
def test_stage_boundaries_carry_exact_live_set():
    db = _branch_db()
    lib = Library(db)
    app = _branch_app(lib)
    ir, _ = Frontend(db).trace(app, jnp.arange(3.0), profile=False)
    for n in ir.nodes:
        n.time_ms = 1.0
    pipe = PipelineGenerator(db).generate(ir, n_threads=4)
    bounds = _liveness(ir, pipe.plan)
    assert len(bounds) == pipe.plan.n_stages + 1
    assert bounds[0] == list(ir.graph_inputs)
    # independently recompute the live set at each boundary
    name_to_stage = {nn: si for si, s in enumerate(pipe.plan.stages)
                     for nn in s.node_names}
    produced = set(ir.graph_inputs)
    for k in range(1, pipe.plan.n_stages + 1):
        for nn in pipe.plan.stages[k - 1].node_names:
            produced.update(ir.node(nn).outputs)
        expect = sorted(
            v for v in produced
            if v in ir.graph_outputs
            or any(name_to_stage.get(c, -1) >= k
                   for c in ir.values[v].consumers))
        assert bounds[k] == expect, f"boundary {k}"
    # final boundary is exactly the graph outputs (nothing dead kept alive)
    assert set(bounds[-1]) == set(ir.graph_outputs)
    # and running the pipeline agrees with the reference app
    x = jnp.arange(3.0)
    np.testing.assert_allclose(np.asarray(pipe(x)), np.asarray(app(x)),
                               rtol=1e-6)


# --------------------------------------------------------------------------- #
# fused-node resolution respects shape-gated hw applicability
# --------------------------------------------------------------------------- #
def test_fused_resolution_threads_part_shapes():
    db = ModuleDatabase("t")
    # hw impls are deliberately WRONG (x1000) so a mis-resolution is visible;
    # "g"'s hw module only supports 2-D inputs, and its traced input is 1-D.
    db.register("f", software=lambda x: x + 1.0,
                accelerated=lambda x: x + 1.0)
    db.register("g", software=lambda x: x * 2.0,
                accelerated=lambda x: x * 1000.0,
                applicable=lambda s: len(s) == 2)
    ir = linear_ir("fused", ["f", "g"], [1.0, 1.0], io_shape=(4,))
    fused_ir = fuse_adjacent_hw(ir, db, fused_cost_ms=lambda run: 0.5)
    # g is shape-gated out for 1-D → no fusable hw run → nothing fused,
    # and the traced shapes were recorded for any fusion that does happen
    assert all(not n.fused_from for n in fused_ir.nodes)

    # now a genuinely fused run whose parts recorded their shapes
    db2 = ModuleDatabase("t2")
    db2.register("f", software=lambda x: x + 1.0,
                 accelerated=lambda x: x + 1.0)
    db2.register("g", software=lambda x: x * 2.0,
                 accelerated=lambda x: x * 2.0,
                 applicable=lambda s: len(s) == 1)
    ir2 = linear_ir("fused2", ["f", "g"], [1.0, 1.0], io_shape=(4,))
    fused2 = fuse_adjacent_hw(ir2, db2, fused_cost_ms=lambda run: 0.5)
    fnode = next(n for n in fused2.nodes if n.fused_from)
    assert fnode.fused_input_shapes == [[(4,)], [(4,)]]

    # hand-built fused node whose part "g" sees a gated-out (1-D) shape:
    # resolution must fall back to g's SOFTWARE impl (x2, not x1000)
    ir3 = linear_ir("fused3", ["f", "g"], [1.0, 1.0], io_shape=(4,))
    merged = Node(name="f_0+g_0", fn_key="f+g", inputs=["d0"], outputs=["d2"],
                  time_ms=0.5, placement="hw", fused_from=["f_0", "g_0"],
                  fused_input_shapes=[[(4,)], [(4,)]])
    ir3.nodes = [merged]
    for v in ir3.values.values():
        v.consumers, v.producer = [], None
    ir3.values["d2"].producer = merged.name
    ir3.values["d0"].consumers = [merged.name]
    pipe = PipelineGenerator(db).generate(ir3, n_threads=1)
    x = jnp.arange(4.0)
    np.testing.assert_allclose(np.asarray(pipe(x)),
                               np.asarray((x + 1.0) * 2.0), rtol=1e-6)


# --------------------------------------------------------------------------- #
# serving loop
# --------------------------------------------------------------------------- #
def test_request_queue_server_smoke():
    db = _linear_db()
    app = _linear_app(Library(db))
    pipe = _pipe(db, app)
    ex = pipe.executor(max_in_flight=6, microbatch=3)
    toks = [jnp.full((4,), float(i + 1)) for i in range(11)]
    want = pipe.run_sequential(toks)
    with RequestQueueServer(ex, max_batch=3, max_wait_ms=3.0) as srv:
        reqs = [srv.submit(t) for t in toks]
        got = [r.wait(timeout=60.0) for r in reqs]
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
    stats = srv.stats()
    assert stats["requests_served"] == 11
    assert stats["batches"] >= 1
    assert stats["latency_ms"]["p50"] > 0.0
    assert stats["latency_ms"]["p95"] >= stats["latency_ms"]["p50"]
    assert stats["executor"]["tokens_retired"] == 11
    # every request has a full latency timeline
    for r in reqs:
        assert r.latency_ms is not None and r.queue_ms is not None
        assert r.latency_ms >= r.queue_ms >= 0.0


def test_request_queue_server_propagates_errors():
    db = ModuleDatabase("t")
    db.register("f", software=lambda x: x + 1.0)
    lib = Library(db)

    def app(x):
        return lib.f(x)
    pipe = _pipe(db, app, n_threads=1)
    ex = pipe.executor()
    with RequestQueueServer(ex, max_batch=2, max_wait_ms=1.0) as srv:
        ok = srv.submit(jnp.ones(4))
        bad = srv.submit(jnp.ones(4), jnp.ones(4))      # wrong arity
        np.testing.assert_allclose(np.asarray(ok.wait(timeout=30.0)),
                                   np.full(4, 2.0))
        with pytest.raises((ValueError, TypeError)):
            bad.wait(timeout=30.0)


# --------------------------------------------------------------------------- #
# elastic re-planning rebuilds the executor only when the plan changes
# --------------------------------------------------------------------------- #
def test_elastic_planner_rebuilds_executor_on_plan_change():
    db = _linear_db()
    app = _linear_app(Library(db))
    ir, _ = Frontend(db).trace(app, jnp.arange(4.0), profile=False)
    for i, n in enumerate(ir.nodes):
        n.time_ms = float(i + 1)
    planner = ElasticPlanner(ir, db=db)

    ex2, rebuilt = planner.executor_for(2)
    assert rebuilt and planner.rebuilds == 1
    # same stage count → same boundaries → cached executor, no rebuild
    ex2b, rebuilt = planner.executor_for(2)
    assert ex2b is ex2 and not rebuilt and planner.rebuilds == 1
    # resource change → different boundaries → fresh executor
    ex4, rebuilt = planner.executor_for(4)
    assert rebuilt and ex4 is not ex2 and planner.rebuilds == 2

    toks = [jnp.full((4,), float(i)) for i in range(5)]
    want = [app(t) for t in toks]
    for ex in (ex2, ex4):
        for g, w in zip(ex.run(toks), want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6)


def test_elastic_planner_without_db_still_plans():
    ir = linear_ir("x", ["a", "b", "c"], [1.0, 2.0, 3.0])
    planner = ElasticPlanner(ir)
    assert planner.boundaries(2) == [0, 2]
    with pytest.raises(ValueError, match="ModuleDatabase"):
        planner.executor_for(2)


# --------------------------------------------------------------------------- #
# retirement: one compiled split a stacked group
# --------------------------------------------------------------------------- #
def _two_out(env):
    return {"y": env["x"] * 2.0 + 1.0,
            "n": (env["x"] > 2.0).astype(jnp.int32)}


def _recorded_outputs(ex: PipelineExecutor) -> list:
    """The outputs each group's retirement splits, in retirement order."""
    seen = []
    out_of = ex._out_of

    def record(env):
        seen.append(out_of(env))
        return seen[-1]
    ex._out_of = record
    return seen


def _assert_bitwise(got, want) -> None:
    """Same pytree, shapes, dtypes, weak types and bytes as ``out[i]``."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype, a.weak_type) == (b.shape, b.dtype,
                                                   b.weak_type)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _eager_rows(out, size: int) -> list:
    """Retirement's per-row indexing before the compiled split."""
    return [jax.tree.map(lambda x: x[i], out) for i in range(size)]


@pytest.mark.parametrize("outputs", [["y"], ["y", "n"]],
                         ids=["array", "tuple"])
@pytest.mark.parametrize("size, buckets, rows", [
    (4, None, 4), (2, None, 4), (3, None, 4),
    (2, (1, 2, 4), 2), (3, (1, 2, 4), 4)])
def test_split_matches_eager_indexing(outputs, size, buckets, rows):
    ex = PipelineExecutor([jax.jit(_two_out)], ["x"], outputs,
                          max_in_flight=4, microbatch=4,
                          pad_microbatches=True, buckets=buckets)
    seen = _recorded_outputs(ex)
    toks = [jnp.arange(6.0).reshape(2, 3) + i for i in range(size)]
    got = ex.run(toks)
    (out,) = seen
    assert jax.tree.leaves(out)[0].shape[0] == rows
    assert len(got) == size
    for g, w in zip(got, _eager_rows(out, size)):
        _assert_bitwise(g, w)
    st = ex.stats()
    assert (st.groups_admitted, st.groups_split) == (1, 1)


@pytest.mark.parametrize("outputs", [["y"], ["y", "n"]],
                         ids=["array", "tuple"])
def test_a_singleton_result_is_the_unsplit_output(outputs):
    ex = PipelineExecutor([jax.jit(_two_out)], ["x"], outputs,
                          max_in_flight=4, microbatch=4,
                          pad_microbatches=True)
    seen = _recorded_outputs(ex)
    got = ex.submit(jnp.ones((2, 3))).result()
    assert got is seen[0]
    assert ex.stats().groups_split == 0


def test_groups_split_counts_stacked_groups_only():
    ex = PipelineExecutor([jax.jit(_two_out)], ["x"], ["y"],
                          max_in_flight=4, microbatch=4,
                          pad_microbatches=True, buckets=(1, 2, 4))
    for n in (1, 2, 1, 3, 4, 1):
        ex.run([jnp.full((2, 3), float(n))] * n)
    st = ex.stats()
    assert (st.groups_admitted, st.groups_split) == (6, 3)
    assert st.as_dict()["groups_split"] == 3


def test_host_outputs_are_indexed_not_split():
    def host(env):
        return {"y": np.asarray(env["x"], np.float64) * 2.0}
    ex = PipelineExecutor([host], ["x"], ["y"], max_in_flight=4,
                          microbatch=4, pad_microbatches=True,
                          batched_fns=[host])
    seen = _recorded_outputs(ex)
    got = ex.run([np.full((3,), float(i)) for i in range(3)])
    for g, w in zip(got, _eager_rows(seen[0], 3)):
        assert isinstance(g, np.ndarray) and g.dtype == np.float64
        assert g.tobytes() == w.tobytes()
    assert ex.stats().groups_split == 0


def test_split_serves_open_group_joins():
    """Joined seats retire with their group through the split; the stage-0
    worker is parked inside an older group so the seam stays open."""
    gate, entered = threading.Event(), threading.Event()
    first = [True]

    def pre(env):
        if first[0]:
            first[0] = False
            entered.set()
            assert gate.wait(timeout=10.0)
        return {"x": env["x"] + 1.0}

    def post(env):
        return {"y": env["x"] * 3.0}

    fns = [pre, post]
    ex = PipelineExecutor(fns, ["x"], ["y"], max_in_flight=16,
                          replicas=[1, 1], microbatch=4,
                          pad_microbatches=True, buckets=(4,),
                          batched_fns=fns, open_groups=True)
    seen = _recorded_outputs(ex)
    try:
        blocker = ex.submit(jnp.zeros(3))
        assert entered.wait(5.0)
        hB = ex.submit(jnp.full((3,), 1.0))
        joins = [ex.try_join((jnp.full((3,), float(i)),)) for i in (2, 3)]
        assert all(j is not None for j in joins)
        gate.set()
        blocker.result()
        got = [h.result() for h in [hB] + joins]
    finally:
        gate.set()
        ex.close()
    assert len(seen) == 2
    for g, w in zip(got, _eager_rows(seen[1], 3)):
        _assert_bitwise(g, w)
    np.testing.assert_array_equal(np.asarray(got[2]), np.full(3, 12.0))
    st = ex.stats()
    assert (st.seam_joins, st.groups_split) == (2, 2)


@contextlib.contextmanager
def _backend_compiles():
    """Times XLA compiled a program while the block ran."""
    seen: list[str] = []

    def on_event(event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _scale(env):
    return {"x": env["x"] * 0.5}


@pytest.mark.parametrize("mode, width", [
    ({}, 7), ({"stage_workers": True}, 8), ({"replicas": [1, 2]}, 9)],
    ids=["async", "threaded", "replicated"])
@pytest.mark.parametrize("buckets", [None, (1, 2, 4)],
                         ids=["pad-to-max", "buckets"])
def test_warmup_compiles_every_split_a_wave_needs(mode, width, buckets):
    ex = PipelineExecutor([jax.jit(_scale), jax.jit(_two_out)], ["x"],
                          ["y", "n"], max_in_flight=8, microbatch=4,
                          pad_microbatches=True, buckets=buckets, **mode)
    # a shape no other case compiles: the split's executables are shared
    # by every executor in the process
    tok = jnp.ones((2, width + (10 if buckets else 0)))
    ex.warmup(tok)
    c0 = ex.compile_count()
    with _backend_compiles() as compiles:
        for n in range(1, 5):
            ex.run([tok] * n)
        ex.run([tok] * 10)                      # groups of 4, 4 and 2
    ex.close()
    assert ex.compile_count() == c0 and compiles == []
    assert ex.stats().groups_split == 3 + 3


FOUR_DEVICES = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import DeviceInventory
    from repro.core.executor import PipelineExecutor

    buckets = eval(sys.argv[1])
    inv = DeviceInventory.detect()
    assert len(inv) == 4, jax.devices()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    def head(env):
        return {"x": env["x"] * 2.0 + 1.0}

    def tail(env):
        return {"y": env["x"] - 0.5, "n": (env["x"] > 4.0).astype(jnp.int32)}

    # widened: each stage on two replicas, the last on chips 2 and 3
    ex = PipelineExecutor([jax.jit(head), jax.jit(tail)], ["x"], ["y", "n"],
                          replicas=[2, 2], devices=[[0, 1], [2, 3]],
                          inventory=inv, max_in_flight=8, microbatch=4,
                          pad_microbatches=True, buckets=buckets)
    sizes = [1, 2, 3, 4, 3, 2, 1, 4]
    toks = [jnp.full((2, 3), float(i)) for i in range(len(sizes))]
    ex.warmup(toks[0])
    c0, n0 = ex.compile_count(), len(compiles)
    seen = []
    out_of = ex._out_of
    ex._out_of = lambda env: seen.append(out_of(env)) or seen[-1]
    got = [ex.run([t] * n) for t, n in zip(toks, sizes)]
    ex.close()
    assert ex.compile_count() == c0, (ex.compile_count(), c0)
    assert len(compiles) == n0, compiles[n0:]
    assert ex.stats().out_of_order_retired == 0
    assert ex.stats().groups_split == sum(n > 1 for n in sizes)
    for n, rows, out in zip(sizes, got, seen):
        (dev,) = out[0].devices()
        assert dev in (inv.jax_device(2), inv.jax_device(3)), dev
        if n == 1:
            assert rows[0] is out
            continue
        for i, r in enumerate(rows):
            for a, b in zip(r, out):
                assert a.devices() == {dev}
                assert a.dtype == b.dtype and a.shape == b.shape[1:]
                assert np.asarray(a).tobytes() == np.asarray(b[i]).tobytes()
    print("SPLIT-4-OK", sorted({d.id for o in seen for d in o[0].devices()}))
""")


@pytest.mark.parametrize("buckets", [None, (1, 2, 4)],
                         ids=["pad-to-max", "buckets"])
def test_split_on_four_devices_with_a_widened_plan(buckets):
    """The split runs where the last stage's replica left the group, and
    warmup compiles it on each of their devices."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    r = subprocess.run([sys.executable, "-c", FOUR_DEVICES, repr(buckets)],
                       capture_output=True, text=True, timeout=300.0,
                       env=env, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SPLIT-4-OK [2, 3]" in r.stdout, r.stdout
