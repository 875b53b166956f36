"""End-to-end behaviour of the Courier toolchain (paper Steps 1-9)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CourierIR, Frontend, Library, ModuleDatabase,
                        OffloadPlan, PipelineGenerator, courier_offload,
                        deploy, linear_ir, partition_paper)
from repro.launch.serve import serve_pipeline_demo
from repro.models.harris import (corner_harris_demo, make_harris_db,
                                 numpy_reference)


def _demo_db():
    db = ModuleDatabase("t")
    db.register("f1", software=lambda x: x * 2.0, accelerated=lambda x: x * 2.0)
    db.register("f2", software=lambda x: x + 1.0)                  # sw-only
    db.register("f3", software=lambda x: x * x, accelerated=lambda x: x * x)
    return db


def _app(db):
    lib = Library(db)

    def app(x):
        return lib.f3(lib.f2(lib.f1(x)))
    return app


def test_trace_builds_causal_graph():
    db = _demo_db()
    app = _app(db)
    ir, out = Frontend(db).trace(app, jnp.arange(4.0))
    assert [n.fn_key for n in ir.nodes] == ["f1", "f2", "f3"]
    assert ir.is_linear_chain()
    assert ir.graph_inputs == ["d0"]
    assert len(ir.graph_outputs) == 1
    ir.validate()
    # profile log captured
    assert all(n.time_ms is not None and n.time_ms >= 0 for n in ir.nodes)
    # I/O metadata (the paper's "bit-depth")
    assert ir.values["d0"].shape == (4,)
    assert ir.values["d0"].bit_depth == 32


def test_offloaded_function_matches_original():
    db = _demo_db()
    app = _app(db)
    x = jnp.arange(8.0)
    off = courier_offload(app, x, db=db)
    np.testing.assert_allclose(off(x), app(x))
    # db hit → hw, miss → sw (paper's placement rule); the structured
    # Placement carries the backend kind
    placements = {n.fn_key: n.placement.kind for n in off.ir.nodes}
    assert placements == {"f1": "hw", "f2": "sw", "f3": "hw"}
    assert off.ir.nodes[0].placement.is_hw


def test_token_pipeline_equals_sequential():
    db = _demo_db()
    app = _app(db)
    off = courier_offload(app, jnp.arange(8.0), db=db)
    toks = [jnp.full((8,), float(i)) for i in range(7)]
    got = off.map(toks)
    want = [app(t) for t in toks]
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w)


def test_offload_switcher_falls_back_on_failure():
    db = ModuleDatabase("t")

    def boom(x):
        raise RuntimeError("hw module died")
    db.register("f", software=lambda x: x + 1.0, accelerated=boom)
    lib = Library(db)
    plan = OffloadPlan(decisions={"f": "hw"})
    with deploy(plan):
        out = lib.f(jnp.zeros(3))            # must not raise
    np.testing.assert_allclose(out, np.ones(3))
    assert plan.fallback_log and "hw module died" in plan.fallback_log[0]


def test_switch_to_original_path():
    db = _demo_db()
    app = _app(db)
    off = courier_offload(app, jnp.arange(4.0), db=db)
    off.switch("original")
    np.testing.assert_allclose(off(jnp.arange(4.0)), app(jnp.arange(4.0)))


def test_user_ir_edit_hook():
    """Paper Steps 6-7: the user may pin a node to software."""
    db = _demo_db()
    app = _app(db)

    def edit(ir: CourierIR) -> CourierIR:
        ir.node("f1_0").placement = "sw"
        return ir

    off = courier_offload(app, jnp.arange(4.0), db=db, edit_ir=edit,
                          prefer_hw=False)
    np.testing.assert_allclose(off(jnp.arange(4.0)), app(jnp.arange(4.0)))


# --------------------------------------------------------------------------- #
# Paper reproduction anchors (Table I)
# --------------------------------------------------------------------------- #
PAPER_FNS = ["cvtColor", "cornerHarris", "normalize", "convertScaleAbs"]
PAPER_OFFL = [39.8, 13.6, 80.2, 13.2]       # post-offload stage times [ms]
PAPER_TOTAL_ORIG = 1371.1
PAPER_MEASURED_SPEEDUP = 15.36


def test_paper_policy_reproduces_four_stage_plan():
    ir = linear_ir("harris", PAPER_FNS, PAPER_OFFL)
    plan = partition_paper(ir, n_threads=3)
    assert plan.n_stages == 4                      # paper built 4 stages
    assert plan.bottleneck_ms == pytest.approx(80.2)
    # predicted speedup vs the original binary ≈ paper's measured 15.36x
    pred = PAPER_TOTAL_ORIG / plan.bottleneck_ms
    assert pred == pytest.approx(17.1, abs=0.1)
    assert pred >= PAPER_MEASURED_SPEEDUP          # measured includes overhead
    # stage kinds: serial_in_order endpoints, parallel middle (TBB filters)
    kinds = [s.kind for s in plan.stages]
    assert kinds[0] == kinds[-1] == "serial_in_order"
    assert all(k == "parallel" for k in kinds[1:-1])


def test_harris_app_end_to_end():
    """The paper's own case study through the whole toolchain."""
    db = make_harris_db(with_hw=True)
    lib = Library(db)
    app = corner_harris_demo(lib)
    img = jax.random.uniform(jax.random.PRNGKey(0), (32, 64, 3)) * 255
    off = courier_offload(app, img, db=db, prefer_hw=False)
    np.testing.assert_allclose(off(img), app(img), rtol=1e-5, atol=1e-4)
    # normalize must remain a software function (no hw module, paper Table I)
    placements = {n.fn_key: n.placement for n in off.ir.nodes}
    assert placements["normalize"].is_sw


def test_harris_app_with_hw_kernels():
    db = make_harris_db(with_hw=True)
    lib = Library(db)
    app = corner_harris_demo(lib)
    img = jax.random.uniform(jax.random.PRNGKey(1), (32, 64, 3)) * 255
    off = courier_offload(app, img, db=db, prefer_hw=True)
    hw = {n.fn_key for n in off.ir.nodes if n.placement.is_hw}
    assert hw == {"cvtColor", "cornerHarris", "convertScaleAbs"}
    ref = app(img)
    got = off(img)
    scale = float(jnp.max(jnp.abs(ref)))
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(ref) / scale, atol=1e-4)


@pytest.mark.parametrize("fuse", [False, True], ids=["unfused", "fused"])
def test_smoke_path_serves_hardware_pipeline_at_reference_parity(fuse):
    """The path chip_smoke.py drives on the chip, at a 64x96 frame with
    interpreted kernels: the Pallas modules are placed (the fused pair under
    ``fuse``), nothing falls back or compiles while serving, and every
    served frame, and the Harris response the served stage programs give
    it, match the host numpy float32 reference within the script's
    tolerances."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    run = serve_pipeline_demo(n_requests=6, max_batch=4, size=(64, 96),
                              seed=3, fuse=fuse)
    nodes = run.offloaded.pipeline.ir.nodes
    placements = {n.fn_key: n.placement.kind for n in nodes}
    head = ({"cvtColor+cornerHarris": "hw"} if fuse
            else {"cvtColor": "hw", "cornerHarris": "hw"})
    assert placements == {**head, "normalize": "sw", "convertScaleAbs": "hw"}
    assert [n.fn_key for n in nodes if n.fused_from] == (
        ["cvtColor+cornerHarris"] if fuse else [])
    assert run.offloaded.plan.fallback_log == []
    assert run.offloaded.fallbacks == []
    assert run.compiles_in_window == 0
    assert run.stats["requests_served"] == len(run.results) == 6
    responses = smoke.served_responses(
        run, "cvtColor+cornerHarris" if fuse else "cornerHarris")
    assert len(responses) == 6
    for frame, got, resp in zip(run.frames, run.results, responses):
        ref_resp, want = numpy_reference(np.asarray(frame))
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=smoke.IMAGE_ATOL)
        np.testing.assert_allclose(
            np.asarray(resp), ref_resp, rtol=0,
            atol=smoke.RESPONSE_RTOL * float(np.max(np.abs(ref_resp))))


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_placed_from_env_or_fixed_checkout_path(from_env,
                                                             tmp_path):
    """``enable_compile_cache`` leaves a ``JAX_COMPILATION_CACHE_DIR`` set
    from outside alone, and otherwise points JAX at the one fixed
    directory in the checkout (run in a child: it sets global config)."""
    import os
    import subprocess
    import sys

    from repro.launch.compile_cache import CACHE_DIR

    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"), env.get("PYTHONPATH", "")])
    code = ("import jax; from repro.launch.compile_cache import "
            "enable_compile_cache as e; d = e(); "
            "print(d); print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    want = str(tmp_path) if from_env else CACHE_DIR
    assert out.stdout.split() == [want, want]
    assert os.path.basename(CACHE_DIR) == ".jax_cache"
