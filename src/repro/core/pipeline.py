"""Pipeline Generator — paper Sect. III: build & run the mixed pipeline.

Given a traced CourierIR and the module database, the generator

1. assigns placements by database lookup (hit → "hw" Pallas module, miss →
   "sw" pure-jnp function) and re-estimates hit nodes with the database's
   cost estimator (the synthesis-report analog),
2. optionally fuses adjacent branch-free hw nodes (``#pragma HLS dataflow``),
3. partitions the chronological node list into balanced contiguous stages
   (paper policy or bottleneck-optimal DP),
4. emits one jitted callable per stage operating on the live-value
   environment at the stage boundary (the paper's "intermediate data ...
   stored in the external memory" — here, stage-boundary arrays in HBM),
5. wraps everything in a :class:`BuiltPipeline` whose ``run`` executes a
   TBB-style token pipeline: a wavefront schedule with a bounded number of
   in-flight tokens (TBB's token pool), first/last stages serial-in-order.

JAX's async dispatch provides the overlap TBB gets from its thread pool:
each stage call on a token returns immediately with futures, so stage s can
be issued for token k+1 while token k is still executing downstream — the
paper's "Task #0 can take the second input while Task #1 is processing".

Two token-stream execution paths are exposed:

* ``BuiltPipeline.run``       — the original synchronous wavefront schedule
  (host steps every in-flight token one stage at a time); kept as the
  paper-faithful baseline.
* ``BuiltPipeline.run_async`` / ``BuiltPipeline.executor()`` — the true
  asynchronous executor (:mod:`repro.core.executor`): eager stage issue,
  bounded token pool, optional per-stage micro-batching, issue, wait and
  occupancy counters.  This is the serving-layer fast path.
"""
from __future__ import annotations

import re
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp

from .costmodel import CostModel
from .database import ModuleDatabase
from .ir import CourierIR, Node
from .partition import (PipelinePlan, StagePlan, fuse_adjacent_hw,
                        partition_optimal, partition_paper)
from .placement import HW, SW, Placement, is_hw

__all__ = ["PipelineGenerator", "BuiltPipeline", "StageFn",
           "assign_placements", "make_stage_fns", "loop_batched",
           "batched_body", "stage_name"]


def loop_batched(fn: Callable) -> Callable:
    """Per-row loop replacement for ``jit(vmap(stage))`` on STATEFUL stages.

    A stage that mutates a host-side slot pool can't be vmapped (vmap
    traces the body once; the per-row pool writes would collapse into
    one) and can't be jitted (the writes would never re-execute).  This
    runs the raw stage body once per leading-axis row and restacks, so
    micro-batched groups still flow through stateful stages — each row's
    slot mutation happens exactly once, in row order.
    """
    def batched(env: dict) -> dict:
        b = jnp.shape(next(iter(env.values())))[0]
        outs = [fn({k: v[i] for k, v in env.items()}) for i in range(b)]
        return {k: jnp.stack([o[k] for o in outs]) for k in outs[0]}
    batched.__name__ = f"loop_batched_{getattr(fn, '__name__', 'stage')}"
    return batched


# --------------------------------------------------------------------------- #
# Step: placement assignment (database lookup)
# --------------------------------------------------------------------------- #
def assign_placements(ir: CourierIR, db: ModuleDatabase,
                      prefer_hw: bool = True) -> None:
    """Paper Fig. 3 'Search corresponding modules from a HW module DB'.

    Marks each node's backend kind (hw = accelerated module, sw = software
    fallback) and, for hw nodes with a cost estimator, replaces the
    measured software time with the estimated accelerated time (the paper
    mixes measured SW times with synthesis-estimated HW times).  Nodes
    whose ``time_ms`` came from the *online* profile (``time_source ==
    "profile"``) keep it — a measurement of the deployed hw module
    outranks the synthesis-report estimate it superseded.  Only the
    placement's *kind* is (re)resolved here: a device/replica pinning set
    by the replica-assignment pass (or a user ``edit_ir`` hook) survives.
    """
    for n in ir.nodes:
        e = db.lookup(n.fn_key)
        shapes = [ir.values[i].shape for i in n.inputs]
        cur = Placement.parse(n.placement)
        if e is not None and prefer_hw and e.has_hw(*shapes):
            n.placement = cur.with_kind(HW)
            if e.cost_hw is not None:
                dtypes = [ir.values[i].dtype for i in n.inputs]
                c = e.cost_hw(shapes, dtypes, n.params)
                n.flops, n.bytes_rw = c.flops, c.bytes_rw
                if n.time_source != "profile":
                    n.time_ms = c.time_ms()
        else:
            n.placement = cur.with_kind(SW)


# --------------------------------------------------------------------------- #
# Stage compilation
# --------------------------------------------------------------------------- #
def _liveness(ir: CourierIR, plan: PipelinePlan) -> list[list[str]]:
    """Live value names at each stage boundary (len = n_stages + 1).

    boundary[0] = graph inputs; boundary[k] = values produced before stage k
    that are still needed by stages >= k or are graph outputs.

    Captured graph inputs (closure-held weights the Frontend registered in
    ``ir.captured``) never cross boundaries — they are per-pipeline
    constants baked into the stage closures, not per-token traffic; shipping
    a weight matrix through every boundary (and stacking it per token under
    micro-batching) would swamp the stream.  The one exception: a captured
    value that *is* a graph output stays live at the final boundary so the
    executor can retire it like any other result.
    """
    name_to_stage: dict[str, int] = {}
    for si, s in enumerate(plan.stages):
        for nn in s.node_names:
            name_to_stage[nn] = si

    cap = set(getattr(ir, "captured", ()))
    boundaries: list[list[str]] = [[v for v in ir.graph_inputs
                                    if v not in cap]]
    produced: set[str] = set(ir.graph_inputs)
    for k in range(1, plan.n_stages + 1):
        for nn in plan.stages[k - 1].node_names:
            produced.update(ir.node(nn).outputs)
        live: list[str] = []
        for v in produced:
            if v in cap and not (k == plan.n_stages
                                 and v in ir.graph_outputs):
                continue
            needed = any(
                name_to_stage.get(c, -1) >= k for c in ir.values[v].consumers
            ) or v in ir.graph_outputs
            if needed:
                live.append(v)
        boundaries.append(sorted(live))
    return boundaries


def _accepts_params(fn: Callable, params: dict) -> bool:
    """True when ``fn(*args, **params)`` cannot fail on a param name.

    A dedicated fused module is only used when it understands *every*
    merged param of the fused run — silently dropping one (or crashing into
    the Off-load Switcher's fallback on every call) would diverge from the
    unfused semantics.  Unknown-signature callables are trusted only for
    empty params.
    """
    if not params:
        return True
    import inspect
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    names = set()
    for p in sig.parameters.values():
        if p.kind == p.VAR_KEYWORD:
            return True
        if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY):
            names.add(p.name)
    return set(params) <= names


def _resolve_impl(node: Node, ir: CourierIR, db: ModuleDatabase) -> Callable:
    if node.fused_from:
        # fused node "a+b": prefer a *dedicated* fused hw module registered
        # in the database under the joined key (the single-pass mega-kernel
        # — see ModuleDatabase.register_fused); fall back to composing the
        # parts' impls, re-checking each part's shape-gated hw applicability
        # against the input shapes it actually sees (recorded at fusion
        # time) — resolving without shapes would pick hw even for shapes the
        # module's `applicable` rejects.
        shapes = [ir.values[i].shape for i in node.inputs]
        e = db.lookup(node.fn_key)
        if (e is not None and e.has_hw(*shapes)
                and _accepts_params(e.accelerated, node.params)):
            return e.accelerated
        keys = node.fn_key.split("+")
        part_shapes = node.fused_input_shapes or [[] for _ in keys]
        part_params = node.fused_params or [{} for _ in keys]
        impls = [db.resolve(k, *ps, prefer_hw=True)[0]
                 for k, ps in zip(keys, part_shapes)]

        if node.fused_part_inputs:
            # route each part exactly the values it consumed pre-fusion:
            # external operands come from the fused node's args, carried
            # intermediates from earlier parts' outputs.  Each part's
            # keyword bindings (fused_part_kw, recorded at fusion time)
            # replay under their trace-time names — a part whose software
            # impl takes arrays keyword-only misbinds otherwise.
            part_kws = (tuple(map(tuple, node.fused_part_kw))
                        if node.fused_part_kw
                        else tuple(tuple([None] * len(ins))
                                   for ins in node.fused_part_inputs))
            routing = tuple(zip(tuple(map(tuple, node.fused_part_inputs)),
                                tuple(map(tuple, node.fused_part_outputs)),
                                part_kws))
            arg_names = tuple(node.inputs)
            out_names = tuple(node.outputs)

            def fused(*args: Any, _impls=tuple(impls),
                      _params=tuple(part_params), **_merged: Any):
                env = dict(zip(arg_names, args))
                for (ins, outs, kws), f, pp in zip(routing, _impls, _params):
                    pos = [env[v] for v, kw in zip(ins, kws) if kw is None]
                    kw = {kw: env[v] for v, kw in zip(ins, kws)
                          if kw is not None}
                    out = f(*pos, **kw, **pp)
                    out_t = out if isinstance(out, (tuple, list)) else (out,)
                    env.update(zip(outs, out_t))
                res = tuple(env[v] for v in out_names)
                return res[0] if len(res) == 1 else res
            return fused

        def fused(*args: Any, **_merged: Any):
            # legacy linear-chain composition (fused nodes built without
            # routing metadata, e.g. hand-constructed in tests)
            out = args
            for f, pp in zip(impls, part_params):
                out = f(*out, **pp)
                if not isinstance(out, (tuple, list)):
                    out = (out,)
            return out[0] if len(out) == 1 else tuple(out)
        return fused
    shapes = [ir.values[i].shape for i in node.inputs]
    fn, _ = db.resolve(node.fn_key, *shapes, prefer_hw=is_hw(node.placement))
    return fn


def stage_name(nodes: Sequence[Node]) -> str:
    """``stage_<call>_<call>...``: a stage program named after the library
    calls it holds (``stage_cvtColor_cornerHarris``), so a profile's
    ``XLA Modules`` line tells the stages apart.  No stage index: a stage
    reused across re-plans keeps its name."""
    return "stage_" + re.sub(r"[^A-Za-z0-9_]", "_",
                             "_".join(n.fn_key for n in nodes))


def _stage_body(nodes: Sequence[Node], impls: Sequence[Callable],
                live_out: Sequence[str], captured: dict, *,
                vmapped: bool) -> Callable:
    """The Python body of one stage, each library call under
    ``jax.named_scope(fn_key)``.

    ``vmapped`` makes the micro-batched body: every call is vmapped over
    the group's leading axis INSIDE its scope.  Vmapping the whole body
    would put the scope under ``vmap(...)`` in the name stack, and the TPU
    compiler names a Pallas custom call after the name-stack entry that
    holds it, so ``vmap_cvt_color_`` would turn into ``cvt_color``; per
    call, the compiled program and its op names match a whole-body vmap.
    """
    nodes, impls, live_out = tuple(nodes), tuple(impls), tuple(live_out)

    def call(node: Node, impl: Callable, ins: dict) -> tuple:
        # captured operands come from the closure (pipeline-held
        # constants), everything else from the live env; keyword-bound
        # arrays (input_kw) replay under their trace-time name
        kws = node.input_kw or [None] * len(node.inputs)
        pos = [ins[v] if v in ins else captured[v]
               for v, kw in zip(node.inputs, kws) if kw is None]
        kw = {kw: ins[v] if v in ins else captured[v]
              for v, kw in zip(node.inputs, kws) if kw is not None}
        out = impl(*pos, **kw, **node.params)
        return out if isinstance(out, (tuple, list)) else (out,)

    def stage(env: dict) -> dict:
        env = dict(env)
        rows = (jax.tree.leaves(env)[0].shape[0]
                if vmapped and env else None)
        for node, impl in zip(nodes, impls):
            ins = {v: env[v] for v in node.inputs if v in env}
            with jax.named_scope(node.fn_key):
                if vmapped:
                    outs = jax.vmap(lambda a, n=node, f=impl: call(n, f, a),
                                    axis_size=rows)(ins)
                else:
                    outs = call(node, impl, ins)
            env.update(zip(node.outputs, outs))
        out = {}
        for k in live_out:
            if k in env:
                out[k] = env[k]
            elif vmapped:        # a captured graph output, one per row
                c = captured[k]
                out[k] = jnp.broadcast_to(c, (rows,) + jnp.shape(c))
            else:
                out[k] = captured[k]
        return out

    stage.__name__ = stage_name(nodes)
    return stage


def batched_body(f: Callable) -> Callable:
    """The micro-batched form of stage ``f`` (a group of tokens stacked on a
    leading axis): a :class:`StageFn`'s per-call vmapped body, else
    ``jax.vmap`` of its raw body."""
    return getattr(f, "vmapped", None) or jax.vmap(getattr(f, "raw", f))


class StageFn:
    """One compiled pipeline stage: ``dict(live-in) -> dict(live-out)``.

    Wraps the raw Python stage body in a *hoisted* ``jax.jit`` that lives for
    the pipeline's lifetime, so steady-state serving re-enters the same
    executable instead of re-tracing — and exposes the XLA compile count
    (``jit``'s signature-cache size) so callers can assert **zero recompiles
    after warmup**.  ``raw`` is kept for transform composition (stateful
    stages loop it per row); ``vmapped`` is the body micro-batched groups
    run (see :func:`batched_body`).

    ``donate`` forwards the env argument's buffers to XLA as donated inputs:
    stage outputs may reuse stage-input memory, killing the per-token
    intermediate copies.  Only safe when the caller hands over ownership of
    the env (true for all boundaries that contain no user-provided graph
    inputs — the generator checks liveness before enabling it).
    """

    __slots__ = ("raw", "vmapped", "jitted", "donated", "stateful", "_fn",
                 "__name__")

    def __init__(self, fn: Callable, *, jit: bool = True,
                 donate: bool = False, vmapped: Callable | None = None):
        self.raw = fn
        self.vmapped = vmapped
        self.jitted = jit
        self.donated = donate and jit
        # stage contains a stateful (slot-pool-mutating) node: never jit
        # or vmap its body — the executor loop-batches it per row instead
        self.stateful = False
        self._fn = (jax.jit(fn, donate_argnums=(0,) if donate else ())
                    if jit else fn)
        self.__name__ = getattr(fn, "__name__", "stage")

    def __call__(self, env: dict) -> dict:
        if self.donated:
            # donation is a silent no-op on backends without it (CPU), but
            # XLA warns at compile time; suppress only around *this* call so
            # the host application's own donation diagnostics stay intact.
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                return self._fn(env)
        return self._fn(env)

    @property
    def compiles(self) -> int:
        """Number of distinct executables compiled for this stage."""
        return self._fn._cache_size() if self.jitted else 0

    def lower(self, env: dict):
        """``jax.jit(...).lower`` of the stage for ``env`` (jitted stages)."""
        return self._fn.lower(env)


def make_stage_fns(ir: CourierIR, db: ModuleDatabase, plan: PipelinePlan,
                   jit: bool = True, donate: bool = True,
                   cache: dict | None = None) -> list[StageFn]:
    """One callable per stage: dict(live-in) -> dict(live-out).

    ``donate``: donate each stage's env buffers when the live-in boundary
    consists purely of pipeline-owned intermediates (never stage 0, whose
    env aliases caller-owned token arrays, and never a boundary where a
    graph input is still live).

    ``cache``: optional dict carried across re-plans (owned by e.g.
    :class:`~repro.runtime.driver.ElasticPlanner`).  A stage whose identity
    — node names, placements, live-in/out boundaries, jit/donate config —
    is unchanged from a previous plan reuses the *same* :class:`StageFn`
    object, so its compiled executables survive the re-plan: hot-swapping
    a re-balanced executor recompiles only the stages whose boundaries
    actually moved.
    """
    boundaries = _liveness(ir, plan)
    fns: list[StageFn] = []
    for k, s in enumerate(plan.stages):
        nodes = [ir.node(nn) for nn in s.node_names]
        live_out = boundaries[k + 1]
        # a stage containing a stateful node runs the raw Python body:
        # its impl mutates a host-side slot pool, which jit would trace
        # once and never re-execute.  Donation is off with it (the env
        # arrays are read host-side, not handed to XLA).
        has_state = any(getattr(n, "state", None) for n in nodes)
        stage_jit = jit and not has_state
        can_donate = (donate and stage_jit and k > 0
                      and not set(boundaries[k]) & set(ir.graph_inputs))
        # key on the nodes' CURRENT placements (what _resolve_impl reads),
        # not the plan's snapshot — a plan computed before assign_placements
        # would otherwise never hit the cache
        key = (tuple(s.node_names),
               tuple(Placement.parse(n.placement).key for n in nodes),
               tuple(boundaries[k]), tuple(live_out), stage_jit, can_donate)
        if cache is not None and key in cache:
            fns.append(cache[key])
            continue
        impls = [_resolve_impl(n, ir, db) for n in nodes]
        captured = dict(getattr(ir, "captured", {}))
        sf = StageFn(
            _stage_body(nodes, impls, live_out, captured, vmapped=False),
            jit=stage_jit, donate=can_donate,
            vmapped=_stage_body(nodes, impls, live_out, captured,
                                vmapped=True))
        sf.stateful = has_state
        if cache is not None:
            cache[key] = sf
        fns.append(sf)
    return fns


# --------------------------------------------------------------------------- #
# The built pipeline (deployable artifact)
# --------------------------------------------------------------------------- #
@dataclass
class BuiltPipeline:
    ir: CourierIR
    plan: PipelinePlan
    stage_fns: list[Callable]
    graph_inputs: list[str]                  # per-token inputs callers feed
    graph_outputs: list[str]
    max_in_flight: int | None = None         # TBB token-pool size
    # captured graph inputs (closure-held weights/constants discovered by the
    # Frontend): bound by the stage closures, never passed per token —
    # ``graph_inputs`` above already excludes them.
    captured: dict[str, Any] = field(default_factory=dict)
    # lazily built jit(vmap(stage)) executables, hoisted here (not on each
    # executor) so every executor over this pipeline shares one compiled set
    # — rebuilding an executor must not recompile in steady state.
    _batched_fns: list[Callable] | None = field(default=None, repr=False)

    # -- single token, through all stages (also the reference semantics) --- #
    def __call__(self, *args: Any):
        env = self._env_of(args)
        for fn in self.stage_fns:
            env = fn(env)
        return self._out_of(env)

    # -- token pipeline (paper Fig. 2) -------------------------------------- #
    def run(self, tokens: Iterable[tuple | Any]) -> list[Any]:
        """Wavefront token pipeline with a bounded token pool.

        Issues stage s for token k at wavefront step s+k; with JAX async
        dispatch, issued stages overlap exactly like TBB's thread pool.
        ``max_in_flight`` bounds live tokens (default: n_stages + 1, the
        double-buffering minimum).
        """
        toks = [t if isinstance(t, tuple) else (t,) for t in tokens]
        n = len(toks)
        S = len(self.stage_fns)
        pool = self._validated_pool()
        envs: dict[int, Any] = {}
        done: dict[int, Any] = {}
        next_tok = 0
        # stage index each in-flight token sits at
        at: dict[int, int] = {}
        while len(done) < n:
            # admit new tokens while the pool has room (serial_in_order entry)
            while next_tok < n and len(envs) < pool:
                envs[next_tok] = self._env_of(toks[next_tok])
                at[next_tok] = 0
                next_tok += 1
            # advance the *oldest* tokens first (keeps in-order completion)
            for k in sorted(envs):
                s = at[k]
                envs[k] = self.stage_fns[s](envs[k])
                at[k] = s + 1
                if at[k] == S:
                    done[k] = self._out_of(envs.pop(k))
                    at.pop(k)
        return [done[k] for k in range(n)]

    def run_sequential(self, tokens: Iterable[tuple | Any]) -> list[Any]:
        """No pipelining — the original binary's behavior (baseline)."""
        return [self(*t) if isinstance(t, tuple) else self(t) for t in tokens]

    # -- async executor (TBB parallel_pipeline analog) ----------------------- #
    def executor(self, *, max_in_flight: int | None = None,
                 microbatch: int = 1,
                 pad_microbatches: bool = False,
                 buckets: "Sequence[int] | None" = None,
                 profiler: Any = None, stage_workers: bool = False,
                 replicas: "Sequence[int] | None" = None,
                 devices: "Sequence[Sequence[int]] | None" = None,
                 inventory: Any = None, fault_injector: Any = None,
                 max_group_retries: int = 3, quarantine_after: int = 1,
                 retry_budget_ms: float | None = None,
                 open_groups: bool = False,
                 pad_token: tuple | None = None,
                 ) -> "PipelineExecutor":
        """Build a :class:`~repro.core.executor.PipelineExecutor` over the
        compiled stages (bounded token pool, eager async issue, optional
        per-stage micro-batching with bucketed ragged-group padding).
        ``max_in_flight`` defaults to this pipeline's own setting; the
        executor validates it (>= 1).  Executors built here share this
        pipeline's compiled (and vmapped) stage executables.  ``profiler``
        attaches a :class:`~repro.core.profiler.StageProfiler` for online
        per-stage times; ``stage_workers`` runs stages on dedicated
        threads (host-bound pipelines); ``replicas`` widens stages to the
        given per-stage worker counts (TBB parallel filters — see
        :func:`repro.core.partition.assign_replicas`); ``devices`` pins
        each replica to a device ordinal of ``inventory`` (the plan's
        :attr:`~repro.core.partition.PipelinePlan.stage_devices`);
        ``fault_injector`` / ``max_group_retries`` / ``quarantine_after``
        / ``retry_budget_ms`` configure the executor's fault-tolerance
        layer (see :mod:`repro.runtime.faults`); ``open_groups`` /
        ``pad_token`` enable continuous batching (in-flight seam
        admission — see :meth:`PipelineExecutor.try_join`)."""
        from .executor import PipelineExecutor
        return PipelineExecutor.from_pipeline(
            self, max_in_flight=max_in_flight, microbatch=microbatch,
            pad_microbatches=pad_microbatches, buckets=buckets,
            profiler=profiler, stage_workers=stage_workers,
            replicas=replicas, devices=devices, inventory=inventory,
            fault_injector=fault_injector,
            max_group_retries=max_group_retries,
            quarantine_after=quarantine_after,
            retry_budget_ms=retry_budget_ms,
            open_groups=open_groups, pad_token=pad_token)

    def run_async(self, tokens: Iterable[tuple | Any], *,
                  max_in_flight: int | None = None,
                  microbatch: int = 1) -> list[Any]:
        """Run a token stream through the asynchronous executor.

        Unlike :meth:`run` (the synchronous wavefront), every stage of an
        admitted token is issued immediately and the host blocks only when
        the token pool is full or at final retirement.  Results arrive in
        submission order, identical to :meth:`run`/:meth:`run_sequential`.
        """
        return self.executor(max_in_flight=max_in_flight,
                             microbatch=microbatch).run(tokens)

    def describe(self) -> str:
        return self.plan.describe()

    # -- compile accounting (zero-recompile steady state) ------------------- #
    def batched_stage_fns(self) -> list[Callable]:
        """Shared ``jit(vmap(stage))`` set for micro-batched execution.

        Built once per pipeline and handed to every executor, so executor
        churn (serving re-plans, pool resizes) never pays a recompile.
        """
        if self._batched_fns is None:
            self._batched_fns = [
                loop_batched(getattr(f, "raw", f))
                if getattr(f, "stateful", False)
                else jax.jit(batched_body(f))
                for f in self.stage_fns]
        return self._batched_fns

    def compile_count(self) -> int:
        """Total executables compiled across all stage fns (incl. vmapped)
        and the executors' retirement split (one per row count and device,
        shared by every executor in the process).

        Steady-state serving must hold this constant: after warmup, token
        waves of already-seen shapes re-enter cached executables only.
        """
        from .executor import split_rows
        total = split_rows._cache_size()
        for f in self.stage_fns:
            total += getattr(f, "compiles", 0)
        if self._batched_fns is not None:
            for f in self._batched_fns:
                try:
                    total += f._cache_size()
                except AttributeError:
                    pass
        return total

    # -- helpers ------------------------------------------------------------ #
    def _validated_pool(self) -> int:
        """Token-pool size; ``max_in_flight=0`` is an error, not "unset"."""
        if self.max_in_flight is not None and self.max_in_flight < 1:
            raise ValueError(
                f"max_in_flight must be >= 1 (got {self.max_in_flight}); "
                "use None for the default pool of n_stages + 1")
        S = len(self.stage_fns)
        return self.max_in_flight if self.max_in_flight is not None else S + 1

    def _env_of(self, args: Sequence[Any]) -> dict:
        if len(args) != len(self.graph_inputs):
            raise ValueError(f"expected {len(self.graph_inputs)} inputs, "
                             f"got {len(args)}")
        return dict(zip(self.graph_inputs, args))

    def _out_of(self, env: dict):
        outs = tuple(env[o] if o in env else self.captured[o]
                     for o in self.graph_outputs)
        return outs[0] if len(outs) == 1 else outs


# --------------------------------------------------------------------------- #
# The generator itself (paper Step 8)
# --------------------------------------------------------------------------- #
class PipelineGenerator:
    """End-to-end: IR + database → BuiltPipeline."""

    def __init__(self, db: ModuleDatabase, cost_model: CostModel | None = None):
        self.db = db
        self.cost_model = cost_model

    def generate(self, ir: CourierIR, n_threads: int = 2,
                 policy: str = "paper", prefer_hw: bool = True,
                 fuse: bool = False,
                 fused_cost_ms: Callable[[list[Node]], float] | None = None,
                 max_stages: int | None = None,
                 comm_bw_bytes_per_ms: float | None = None,
                 jit: bool = True, donate: bool = True,
                 max_in_flight: int | None = None) -> BuiltPipeline:
        if self.cost_model is not None:
            self.cost_model.annotate(ir)
        assign_placements(ir, self.db, prefer_hw=prefer_hw)
        if fuse:
            # with no explicit estimator the *cost model* decides (fusions
            # that keep intermediates VMEM-resident win; spills rejected) —
            # the paper's fixed reject-policy becomes a modeled choice.
            ir = fuse_adjacent_hw(
                ir, self.db,
                fused_cost_ms=fused_cost_ms if fused_cost_ms is not None
                else "model")
            assign_placements(ir, self.db, prefer_hw=prefer_hw)
        if policy == "paper":
            plan = partition_paper(ir, n_threads=n_threads)
        elif policy == "optimal":
            plan = partition_optimal(ir, max_stages=max_stages,
                                     comm_bw_bytes_per_ms=comm_bw_bytes_per_ms)
        else:
            raise ValueError(f"unknown policy {policy!r}")
        # mandatory legality gate (REPRO_VERIFY=off to bypass): a malformed
        # plan must fail here, not as a wrong answer under traffic.  Lazy
        # import — analysis sits above core in the layering.
        from repro.analysis.verify import check_plan
        check_plan(ir, plan, db=self.db, where="PipelineGenerator.generate")
        fns = make_stage_fns(ir, self.db, plan, jit=jit, donate=donate)
        cap = dict(getattr(ir, "captured", {}))
        token_inputs = [g for g in ir.graph_inputs if g not in cap]
        return BuiltPipeline(ir=ir, plan=plan, stage_fns=fns,
                             graph_inputs=token_inputs,
                             graph_outputs=list(ir.graph_outputs),
                             max_in_flight=max_in_flight, captured=cap)
