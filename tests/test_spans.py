"""The program's own measurement of the served path: the executor's
counters of waiting and wasted work, its profiler spans and the
server's, the named stage programs, and the benchmark's readers of the
new counters."""
from __future__ import annotations

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import PipelineExecutor, courier_offload
from repro.core.ir import Node
from repro.core.pipeline import batched_body, stage_name
from repro.core.tracer import Library
from repro.launch.serve import RequestQueueServer
from repro.models.harris import corner_harris_demo, make_harris_db

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402
from chipbench import trace_reduce  # noqa: E402
from chipbench.record import RunData  # noqa: E402

NEW_COUNTERS = ("pool_wait_ms", "unstack_ms", "groups_split",
                "rows_dispatched", "rows_padded")


def _double(env):
    return {"y": env["x"] * 2.0}


def _executor(**kw) -> PipelineExecutor:
    return PipelineExecutor([jax.jit(_double)], ["x"], ["y"], **kw)


def _tok(i: float = 1.0):
    return jnp.full((4, 8), i, jnp.float32)


def _slow_stage(env):
    time.sleep(0.03)
    return env


# --------------------------------------------------------------------------- #
# counters
# --------------------------------------------------------------------------- #
def test_groups_of_two_and_three_pad_three_of_eight_rows():
    ex = _executor(max_in_flight=8, microbatch=4, pad_microbatches=True)
    hs = ex.submit_many([_tok(1), _tok(2)])
    hs += ex.submit_many([_tok(3), _tok(4), _tok(5)])
    assert [float(h.result()[0, 0]) for h in hs] == [2, 4, 6, 8, 10]
    st = ex.stats()
    assert (st.rows_dispatched, st.rows_padded) == (8, 3)
    assert st.as_dict()["rows_dispatched"] == 8
    assert st.as_dict()["rows_padded"] == 3


def test_singletons_are_not_padded():
    ex = _executor(max_in_flight=8, microbatch=4, pad_microbatches=True)
    for i in range(3):
        ex.submit(_tok(i)).result()
    st = ex.stats()
    assert (st.rows_dispatched, st.rows_padded) == (3, 0)


def test_pool_wait_grows_only_when_the_pool_is_full():
    roomy = PipelineExecutor([_slow_stage], ["x"], ["x"], max_in_flight=4,
                             stage_workers=True)
    hs = [roomy.submit(_tok(i)) for i in range(3)]
    [h.result() for h in hs]
    assert roomy.stats().pool_wait_ms == 0.0
    roomy.close()

    full = PipelineExecutor([_slow_stage], ["x"], ["x"], max_in_flight=1,
                            stage_workers=True)
    hs = [full.submit(_tok(i)) for i in range(3)]
    [h.result() for h in hs]
    # the 2nd and 3rd admissions each waited for a 30 ms stage
    assert full.stats().pool_wait_ms >= 40.0
    full.close()


def test_unstack_time_accumulates_over_groups():
    ex = _executor(max_in_flight=8, microbatch=4, pad_microbatches=True)
    assert ex.stats().unstack_ms == 0.0
    [h.result() for h in ex.submit_many([_tok(1), _tok(2)])]
    first = ex.stats().unstack_ms
    assert first > 0.0
    [h.result() for h in ex.submit_many([_tok(3), _tok(4), _tok(5)])]
    assert ex.stats().unstack_ms > first


def test_reset_stats_zeroes_the_new_counters():
    ex = _executor(max_in_flight=2, microbatch=2, pad_microbatches=True)
    hs = ex.submit_many([_tok(1), _tok(2)]) + ex.submit_many([_tok(3)])
    [h.result() for h in hs]
    before = ex.stats().as_dict()
    assert before["pool_wait_ms"] > 0 and before["unstack_ms"] > 0
    assert before["rows_dispatched"] == 3
    ex.reset_stats()
    after = ex.stats().as_dict()
    assert {k: after[k] for k in NEW_COUNTERS} == dict.fromkeys(
        NEW_COUNTERS, 0)


def test_counters_hold_under_concurrent_submitters():
    ex = _executor(max_in_flight=4, microbatch=4, pad_microbatches=True)
    ex.warmup(_tok())
    errors: list[BaseException] = []

    def submitter(k: int) -> None:
        try:
            for i in range(20):
                n = 1 + (k + i) % 3
                for h in ex.submit_many([_tok(i)] * n):
                    h.result()
        except BaseException as e:       # reported by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=submitter, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    sizes = [1 + (k + i) % 3 for k in range(8) for i in range(20)]
    st = ex.stats()
    assert st.tokens_admitted == st.tokens_retired == sum(sizes)
    assert st.rows_padded == sum(4 - n for n in sizes if n > 1)
    assert st.rows_dispatched == st.tokens_admitted + st.rows_padded


# --------------------------------------------------------------------------- #
# spans, recorded by jax.profiler on the CPU
# --------------------------------------------------------------------------- #
def _host_events(log_dir: str) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace_reduce.find_xplane(log_dir))
    return [(e.name, dict(e.stats)) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_a_profile_of_a_served_run_holds_the_program_spans(tmp_path):
    ex = _executor(max_in_flight=2, microbatch=2, pad_microbatches=True)
    ex.warmup(_tok())
    srv = RequestQueueServer(ex, max_batch=2, max_wait_ms=2.0).start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            reqs = [srv.submit(_tok(i)) for i in range(6)]
            outs = [r.wait(timeout=30.0) for r in reqs]
            # a pair, then a singleton while the pair holds the pool
            hs = ex.submit_many([_tok(1), _tok(2)])
            hs += ex.submit_many([_tok(3)])
            [h.result() for h in hs]
    finally:
        jax.profiler.stop_trace()
        srv.stop()
    assert [float(o[0, 0]) for o in outs] == [2.0 * i for i in range(6)]

    names = {n for n, _, _ in trace_reduce.load(
        trace_reduce.find_xplane(str(tmp_path))).host}
    assert {"window", "batcher_wait", "dispatch", "retire"} <= names

    events = _host_events(str(tmp_path))
    by_name: dict[str, list[dict]] = {}
    for name, stats in events:
        by_name.setdefault(name, []).append(stats)
    assert {"dispatch.stack", "dispatch.pool_wait", "dispatch.issue",
            "retire.wait", "retire.unstack"} <= set(by_name)
    # one group's spans share its id: the pair's dispatch joins its retire
    for name in ("dispatch.stack", "dispatch.pool_wait", "dispatch.issue",
                 "retire", "retire.wait", "retire.unstack"):
        assert all({"group", "rows"} <= set(s) for s in by_name[name]), name
    stacked = {s["group"] for s in by_name["dispatch.stack"]}
    assert stacked <= {s["group"] for s in by_name["retire.unstack"]}
    assert all(s["rows"] == 2 for s in by_name["dispatch.stack"])


# --------------------------------------------------------------------------- #
# named stage programs
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def harris():
    db = make_harris_db(with_hw=True)
    img = jax.random.uniform(jax.random.PRNGKey(0), (32, 64, 3)) * 255.0
    off = courier_offload(corner_harris_demo(Library(db)), img, db=db)
    return off.pipeline, img


def _keys(pipe, k):
    return [pipe.ir.node(n).fn_key for n in pipe.plan.stages[k].node_names]


def test_stage_programs_are_named_after_their_library_calls(harris):
    pipe, img = harris
    names = [f.__name__ for f in pipe.stage_fns]
    assert names == ["stage_" + "_".join(_keys(pipe, k))
                     for k in range(pipe.plan.n_stages)]
    assert all(n.replace("_", "").isalnum() for n in names)
    calls = [key for k in range(pipe.plan.n_stages) for key in _keys(pipe, k)]
    assert calls == ["cvtColor", "cornerHarris", "normalize",
                     "convertScaleAbs"]


def test_a_fused_call_names_its_stage_with_identifier_characters():
    nodes = [Node(name="a_0+b_0", fn_key="cvtColor+cornerHarris"),
             Node(name="n_0", fn_key="cv2.normalize")]
    assert stage_name(nodes) == "stage_cvtColor_cornerHarris_cv2_normalize"


def test_lowered_stage_programs_carry_names_and_call_scopes(harris):
    pipe, img = harris
    env = {pipe.graph_inputs[0]: img}
    stacked = {pipe.graph_inputs[0]: jnp.stack([img, img])}
    for k, f in enumerate(pipe.stage_fns):
        single = f.lower(env).as_text(debug_info=True)
        group = jax.jit(batched_body(f)).lower(stacked).as_text(
            debug_info=True)
        assert f"@jit_{f.__name__}" in single
        assert f"@jit_{f.__name__}" in group
        for key in _keys(pipe, k):
            assert f"jit({f.__name__})/{key}/" in single
            # each call is vmapped inside its own scope
            assert f"jit({f.__name__})/{key}/vmap(" in group
        env, stacked = f(env), jax.jit(batched_body(f))(stacked)
    (out,) = env.values()
    (group_out,) = stacked.values()
    assert jnp.allclose(group_out[0], out) and jnp.allclose(group_out[1], out)


def test_the_batched_body_matches_a_vmap_of_the_whole_stage(harris):
    pipe, img = harris
    imgs = jnp.stack([img, img[::-1], img * 0.5])
    env_a = env_b = {pipe.graph_inputs[0]: imgs}
    for f in pipe.stage_fns:
        env_a = jax.jit(batched_body(f))(env_a)
        env_b = jax.jit(jax.vmap(f.raw))(env_b)
    for k in env_a:
        assert jnp.array_equal(env_a[k], env_b[k])


# --------------------------------------------------------------------------- #
# the benchmark's readers of the new counters
# --------------------------------------------------------------------------- #
def _run(executor):
    return RunData(config={}, peak={}, seconds=1.0, t0=0.0, t1=1.0,
                   setup_s=0.0, executor=executor)


def _read(name, executor):
    return bench._module("metrics", name).read(_run(executor))


COUNTED = {"tokens_retired": 40, "pool_wait_ms": 200.0, "unstack_ms": 4.0,
           "rows_dispatched": 64, "rows_padded": 24}
# what an executor without the new counters exports
OLDER = {"tokens_retired": 40, "per_stage": []}


@pytest.mark.parametrize("name, want", [
    ("pool_wait_ms_per_frame.backlog", 5.0),
    ("unstack_ms_per_frame.backlog", 0.1),
    ("padded_row_share.cameras", 37.5),
])
def test_metric_reads_the_executor_counters(name, want):
    assert _read(name, COUNTED) == pytest.approx(want)
    assert _read(name, OLDER) is None
    assert _read(name, None) is None
    idle = dict(COUNTED, tokens_retired=0, rows_dispatched=0)
    assert _read(name, idle) is None


def test_a_window_without_padding_reads_zero():
    assert _read("padded_row_share.cameras",
                 dict(COUNTED, rows_padded=0)) == 0.0


def test_counters_reach_the_readers_through_as_dict():
    ex = _executor(max_in_flight=2, microbatch=2, pad_microbatches=True)
    hs = ex.submit_many([_tok(1)]) + ex.submit_many([_tok(2), _tok(3)])
    [h.result() for h in hs]
    st = ex.stats().as_dict()
    assert _read("padded_row_share.cameras", st) == 0.0
    assert _read("pool_wait_ms_per_frame.backlog", st) == pytest.approx(
        st["pool_wait_ms"] / 3)
    assert _read("unstack_ms_per_frame.backlog", st) > 0.0
