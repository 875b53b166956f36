"""Regression tests for the Frontend's causal-graph reconstruction.

Each bug class below shipped in the pre-fix tracer (ISSUE 8) and broke
trace-to-pipeline for real models:

* constant / never-recorded outputs silently dropped from graph_outputs,
* array kwargs losing their keyword name (misbound at stage replay),
* closure-captured weights producing dangling producer-less values that
  failed ``validate()`` instead of becoming captured graph inputs,
* aliasing (a fn returning an operand unchanged) making one value both a
  node's input and its output.

Plus round-trip property tests (trace → pipeline == eager app) over
nested pytrees, kwargs, repeated calls, and passthrough outputs, and a
verify-rule test for the IR-level dangling-value gate.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.analysis import verify_plan
from repro.analysis.diagnostics import ERROR
from repro.core import (CourierIR, Frontend, Library, ModuleDatabase,
                        PipelineGenerator, partition_optimal)
from repro.core.ir import Node
from repro.core.tracer import TraceBindingError


# --------------------------------------------------------------------------- #
# fixtures
# --------------------------------------------------------------------------- #
def _db() -> ModuleDatabase:
    db = ModuleDatabase("t")
    db.register("mul2", software=lambda x: x * 2.0)
    db.register("add", software=lambda x, y: x + y)
    db.register("ident", software=lambda x: x)

    def scale(x, *, w):                     # array only reachable by keyword
        return x * w
    db.register("scale", software=scale)

    def shift(x, k, y):                     # non-array between two arrays
        return x * k + y
    db.register("shift", software=shift)

    def cat(*xs):                           # uninspectable positions
        return jnp.concatenate([jnp.atleast_1d(jnp.asarray(x)) for x in xs])
    db.register("cat", software=cat)
    return db


def _trace_pipe(app, *args, max_stages=2):
    db = app.__self_db__
    ir, out = Frontend(db).trace(app, *args)
    pipe = PipelineGenerator(db).generate(ir, policy="optimal",
                                          max_stages=max_stages)
    return ir, out, pipe


def _app(fn):
    """Bind a user fn to a fresh db + Library, keeping the db reachable."""
    db = _db()
    lib = Library(db)

    def app(*args):
        return fn(lib, *args)
    app.__name__ = getattr(fn, "__name__", "app")
    app.__self_db__ = db
    return app


X = jnp.arange(6.0).reshape(2, 3)
Y = jnp.ones((2, 3), jnp.float32) * 0.5


# --------------------------------------------------------------------------- #
# bug (a): outputs whose id() was never recorded were silently dropped
# --------------------------------------------------------------------------- #
def test_constant_output_is_registered_not_dropped():
    const = jnp.full((2, 3), 7.0)

    def f(lib, x):
        return lib.mul2(x), const            # second output: no call saw it

    ir, out, pipe = _trace_pipe(_app(f), X)
    # pre-fix: graph_outputs had 1 entry and the constant vanished
    assert len(ir.graph_outputs) == 2
    cn = ir.graph_outputs[1]
    assert cn in ir.captured and cn in ir.graph_inputs
    y, c = pipe(X)
    assert jnp.array_equal(y, X * 2.0)
    assert jnp.array_equal(c, const)


def test_passthrough_input_output_round_trips():
    def f(lib, x):
        return lib.mul2(x), x                # plain passthrough of an input

    ir, out, pipe = _trace_pipe(_app(f), X)
    assert len(ir.graph_outputs) == 2
    assert ir.graph_outputs[1] in ir.graph_inputs
    y, x2 = pipe(X)
    assert jnp.array_equal(y, X * 2.0)
    assert jnp.array_equal(x2, X)


# --------------------------------------------------------------------------- #
# bug (b): array kwargs lost their keyword name
# --------------------------------------------------------------------------- #
def test_kwarg_array_keeps_its_keyword():
    def f(lib, x, w):
        return lib.scale(x, w=w)             # software impl is kw-only in w

    ir, out, pipe = _trace_pipe(_app(f), X, Y)
    (node,) = ir.nodes
    assert node.input_kw == [None, "w"]
    # pre-fix: replay appended w positionally -> TypeError in the stage fn
    assert jnp.array_equal(pipe(X, Y), X * Y)


def test_shifted_positionals_rebind_by_name():
    def f(lib, x, y):
        return lib.shift(x, 3.0, y)          # 3.0 folds into params["k"]

    ir, out, pipe = _trace_pipe(_app(f), X, Y)
    (node,) = ir.nodes
    assert node.params == {"k": 3.0}
    # y sat AFTER the folded positional: it must be rebound by name, not
    # replayed at a position that no longer exists
    assert node.input_kw == [None, "y"]
    assert jnp.array_equal(pipe(X, Y), X * 3.0 + Y)


def test_unbindable_positional_raises_trace_binding_error():
    def f(lib, x, y):
        return lib.cat(x, 2.0, y)            # *args: position 2 is unnameable

    app = _app(f)
    with pytest.raises(TraceBindingError):
        Frontend(app.__self_db__).trace(app, X, Y)


# --------------------------------------------------------------------------- #
# bug (c): closure-captured weights -> dangling producer-less values
# --------------------------------------------------------------------------- #
def test_closure_weights_become_captured_graph_inputs():
    w = jnp.linspace(0.1, 1.0, 6).reshape(2, 3)

    def f(lib, x):
        return lib.add(lib.scale(x, w=w), w)     # w first seen mid-trace

    app = _app(f)
    # pre-fix: ir.validate() raised (w's value had no producer and was not
    # a graph input); post-fix the trace succeeds and w is captured
    ir, out, pipe = _trace_pipe(app, X)
    cap_names = [vn for vn in ir.graph_inputs if vn in ir.captured]
    assert len(cap_names) == 1
    assert jnp.array_equal(ir.captured[cap_names[0]], w)
    # captured weights are baked into stages, not per-token traffic
    assert pipe.graph_inputs == [ir.graph_inputs[0]]
    assert jnp.array_equal(pipe(X), X * w + w)


def test_dangling_value_verify_rule():
    ir = CourierIR("dangle")
    ir.add_value("d0", (2, 3), "float32")
    ir.add_value("d1", (2, 3), "float32")                 # no producer
    ir.add_value("d2", (2, 3), "float32", producer="add_0")
    ir.add_node(Node(name="add_0", fn_key="add", inputs=["d0", "d1"],
                     outputs=["d2"], time_ms=1.0))
    ir.graph_inputs = ["d0"]                              # d1 missing
    ir.graph_outputs = ["d2"]
    plan = partition_optimal(ir, max_stages=1)
    diags = [d for d in verify_plan(ir, plan) if d.rule == "dangling-value"]
    assert diags and all(d.severity == ERROR for d in diags)
    # registering d1 as a graph input clears the finding
    ir.graph_inputs = ["d0", "d1"]
    assert not [d for d in verify_plan(ir, plan)
                if d.rule == "dangling-value"]


# --------------------------------------------------------------------------- #
# bug (d): aliasing — fn returns an operand unchanged
# --------------------------------------------------------------------------- #
def test_alias_gets_fresh_value_and_identity_edge():
    def f(lib, x):
        return lib.mul2(lib.ident(x))        # ident aliases its input

    ir, out, pipe = _trace_pipe(_app(f), X)
    for n in ir.nodes:
        assert not set(n.inputs) & set(n.outputs), \
            f"{n.name} consumes and produces the same value"
    gi = ir.graph_inputs[0]
    assert ir.values[gi].producer is None     # input's producer not stomped
    ident = ir.nodes[0]
    assert ident.inputs == [gi] and ident.outputs != [gi]
    assert ir.values[ident.outputs[0]].producer == ident.name
    assert jnp.array_equal(pipe(X), X * 2.0)


def test_pure_identity_app():
    def f(lib, x):
        return lib.ident(x)

    ir, out, pipe = _trace_pipe(_app(f), X)
    assert ir.graph_outputs != ir.graph_inputs     # alias, not the input
    assert jnp.array_equal(pipe(X), X)


def test_repeated_alias_chain():
    def f(lib, x):
        y = lib.ident(x)
        z = lib.ident(y)                      # alias of an alias
        return lib.add(z, x)

    ir, out, pipe = _trace_pipe(_app(f), X)
    names = [v for n in ir.nodes for v in n.outputs]
    assert len(names) == len(set(names))      # every output distinct
    assert jnp.array_equal(pipe(X), X + X)


# --------------------------------------------------------------------------- #
# round-trip property tests: trace -> pipeline == eager app
# --------------------------------------------------------------------------- #
@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=4), st.booleans(), st.booleans())
def test_roundtrip_chain(n_calls, use_kw, passthrough):
    def f(lib, x, w):
        y = x
        for _ in range(n_calls):              # repeated calls to the same fn
            y = lib.scale(y, w=w) if use_kw else lib.mul2(y)
        return (y, x) if passthrough else y

    app = _app(f)
    ir, out, pipe = _trace_pipe(app, X, Y)
    got, want = pipe(X, Y), app(X, Y)
    for g, w_ in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w_, rtol=1e-6)
    # structural to_json round-trip survives kw bindings and aliases
    ir2 = CourierIR.from_json(ir.to_json())
    assert [n.name for n in ir2.nodes] == [n.name for n in ir.nodes]
    assert [n.input_kw for n in ir2.nodes] == [n.input_kw for n in ir.nodes]
    assert ir2.graph_inputs == ir.graph_inputs
    assert ir2.graph_outputs == ir.graph_outputs


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=3))
def test_roundtrip_nested_pytree_inputs(depth):
    def f(lib, tree):
        a, (b, c) = tree["a"], tree["bc"]
        h = lib.add(a, b)
        for _ in range(depth):
            h = lib.mul2(h)
        return {"out": lib.add(h, c), "keep": a}

    db = _db()
    lib = Library(db)

    def app(tree):
        return f(lib, tree)
    tree = {"a": X, "bc": (Y, X + 1.0)}
    ir, out = Frontend(db).trace(app, tree)
    # all three leaves are per-token graph inputs, none captured
    assert len(ir.graph_inputs) == 3 and not ir.captured
    pipe = PipelineGenerator(db).generate(ir, policy="optimal", max_stages=2)
    got = pipe(*jax.tree.leaves(tree))
    want = app(tree)
    # graph_outputs follow jax.tree.leaves order over the output dict:
    # sorted keys -> ("keep", "out")
    keep, out_arr = got
    assert jnp.array_equal(keep, want["keep"])
    np.testing.assert_allclose(out_arr, want["out"], rtol=1e-6)


def test_traced_zoo_transformer_matches_jit_of_untraced():
    """The acceptance parity claim: traced+fused pipeline vs jax.jit(app)."""
    from repro.models.zoo import (init_transformer_params, make_zoo_db,
                                  transformer_demo)

    db = make_zoo_db()
    app = transformer_demo(Library(db), init_transformer_params(
        jax.random.PRNGKey(0), n_layers=1, d=16, ff=32, n_heads=2, vocab=32))
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 16), jnp.float32)
    ir, _ = Frontend(db).trace(app, x)
    pipe = PipelineGenerator(db).generate(ir, policy="optimal", fuse=True,
                                          max_stages=3)
    assert any(n.fused_from for n in pipe.ir.nodes)   # mega-kernel fired
    assert pipe.captured and pipe.graph_inputs == [ir.graph_inputs[0]]
    assert jnp.array_equal(pipe(x), jax.jit(app)(x))


def test_traced_zoo_transformer_executor_stream_matches_jit_of_untraced():
    """A token stream through the async executor over the traced, fused
    two-layer transformer is bit-identical to ``jax.jit`` of the app; the
    rmsnorm+matmul mega-kernel fires and the closure weights are captured,
    leaving one per-token input."""
    from repro.models.zoo import (init_transformer_params, make_zoo_db,
                                  transformer_demo)

    db = make_zoo_db()
    app = transformer_demo(Library(db), init_transformer_params(
        jax.random.PRNGKey(0), n_layers=2, d=32, ff=64, n_heads=2, vocab=64))
    toks = [jax.random.normal(jax.random.PRNGKey(10 + i), (16, 32),
                              jnp.float32) for i in range(8)]
    ir, _ = Frontend(db).trace(app, toks[0])
    pipe = PipelineGenerator(db).generate(ir, policy="optimal", fuse=True,
                                          max_stages=4)
    assert "rmsnorm+matmul" in {n.fn_key for n in pipe.ir.nodes
                                if n.fused_from}
    assert len(pipe.captured) >= 1
    assert pipe.graph_inputs == [ir.graph_inputs[0]]
    ex = pipe.executor(max_in_flight=2 * pipe.plan.n_stages + 1)
    ex.warmup(toks[0])
    got = ex.run(toks)
    ex.close()
    ref = jax.jit(app)
    assert len(got) == len(toks)
    for y, t in zip(got, toks):
        assert jnp.array_equal(y, ref(t))


def test_traced_zoo_recurrent_block_matches_jit_of_untraced():
    """The RWKV-shift + SSM-scan block traces through the same path (a
    different op mix) and stays bit-identical to ``jax.jit`` of the app,
    per token and over a stream through the executor."""
    from repro.models.zoo import (init_recurrent_params, make_zoo_db,
                                  recurrent_demo)

    db = make_zoo_db()
    app = recurrent_demo(Library(db),
                         init_recurrent_params(jax.random.PRNGKey(1), d=32))
    xs = [jax.random.normal(jax.random.PRNGKey(2 + i), (16, 32), jnp.float32)
          for i in range(3)]
    ir, _ = Frontend(db).trace(app, xs[0])
    pipe = PipelineGenerator(db).generate(ir, policy="optimal", fuse=True,
                                          max_stages=2)
    assert {"rwkv_shift", "ssm_scan"} <= {
        k for n in pipe.ir.nodes for k in n.fn_key.split("+")}
    ref = jax.jit(app)
    assert jnp.array_equal(pipe(xs[0]), ref(xs[0]))
    for y, x in zip(pipe.run_async(xs), xs):
        assert jnp.array_equal(y, ref(x))


def test_traced_transformer_served_matches_jit_of_untraced():
    """The request queue over the traced transformer serves results
    bit-identical to ``jax.jit`` of the untraced app (micro-batched
    groups of up to 4 against single-sequence references)."""
    from repro.launch.serve import serve_traced_transformer_demo

    s = serve_traced_transformer_demo(max_batch=4, max_wait_ms=4.0,
                                      n_requests=8, seq_len=16, d=32, ff=64,
                                      n_heads=2, vocab=64)
    assert s["requests_served"] == 8
    assert s["results_match"] is True
