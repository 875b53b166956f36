"""Courier IR — the coarse-grained dataflow representation (paper Sect. II-B).

The IR mirrors what Courier-FPGA's Frontend extracts from a running binary
(paper Steps 1-5): an *ordered* function-call graph whose nodes are
library-level functions ("not a single x86 assembly code ... but a process
with a certain amount of computation") and whose edges carry the observed
input/output data metadata (shape, dtype == the paper's "bit-depth", byte
size) plus a profile log (processing time, absolute start/end times).

Nodes are kept in chronological (traced) order, exactly like the paper's
Fig. 4 graph; the Pipeline Generator partitions this order into contiguous
stages.  Users may inspect and edit the IR (paper Steps 6-7) before the
Backend builds the pipeline.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field, asdict
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from .placement import Placement


# --------------------------------------------------------------------------- #
# Values (edges)
# --------------------------------------------------------------------------- #
@dataclass
class Value:
    """An edge in the call graph: one observed array in/out of a function.

    ``shape``/``dtype`` correspond to the paper's ``height x width x
    bit-depth x channels`` node annotation; ``nbytes`` is what the Pipeline
    Generator uses for port sizing / communication-cost estimates.
    """

    name: str
    shape: tuple[int, ...]
    dtype: str
    producer: str | None = None          # node name that wrote it (None = graph input)
    consumers: list[str] = field(default_factory=list)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) * np.dtype(self.dtype).itemsize if self.shape else np.dtype(self.dtype).itemsize

    @property
    def bit_depth(self) -> int:
        """Paper's AXI port-width input: bits per element."""
        return np.dtype(self.dtype).itemsize * 8


# --------------------------------------------------------------------------- #
# Nodes (function calls)
# --------------------------------------------------------------------------- #
@dataclass
class Node:
    """One traced library-function call.

    ``fn_key`` is the database lookup key (paper: the function *name* used to
    search the hardware-module database).  ``time_ms`` is the profiled
    processing time from the Frontend; ``placement`` is filled by the Backend
    after database lookup — a structured :class:`~repro.core.placement.
    Placement` (backend kind + device ordinal + replica index).  Legacy
    string placements ("hw"/"sw") are parsed on construction and on
    attribute assignment-free paths via :meth:`Placement.parse`, so seed
    IRs and user ``edit_ir`` hooks that pin placements by string keep
    working.
    """

    name: str                              # unique instance name, e.g. "cvtColor_0"
    fn_key: str                            # database key, e.g. "cvtColor"
    inputs: list[str] = field(default_factory=list)    # Value names
    outputs: list[str] = field(default_factory=list)   # Value names
    # keyword binding per input: parallel to ``inputs``; None = positional,
    # a string = the keyword the array was passed under at trace time.  Stage
    # replay must honor it — a library fn whose software impl takes arrays by
    # keyword (e.g. ``def f(x, *, w)``) misbinds if w is appended positionally.
    # Empty list (the default, and what pre-existing serialized IRs decode to)
    # means all-positional.
    input_kw: list[str | None] = field(default_factory=list)
    params: dict[str, Any] = field(default_factory=dict)  # static call params
    time_ms: float | None = None           # profiled processing time
    # provenance of time_ms: "estimate" (roofline/synthesis-report analog,
    # may be overwritten by better sources) or "profile" (measured online by
    # StageProfiler — supersedes estimates and is never overwritten by one).
    time_source: str = "estimate"
    t_start: float | None = None           # absolute start (profile log)
    t_end: float | None = None             # absolute end   (profile log)
    flops: float | None = None             # analytical cost-model annotations
    bytes_rw: float | None = None
    placement: Placement = field(default_factory=Placement)
    # TBB filter-kind marker: a serial-only function is not side-effect safe
    # (hidden state, ordered I/O, RNG, in-place buffers), so any stage
    # containing it must keep exactly ONE worker — assign_replicas never
    # widens it.  Pure array functions (everything the tracer records from
    # jnp/Pallas modules) default to replicable.
    serial_only: bool = False
    fused_from: list[str] = field(default_factory=list)  # names of fused originals
    # per-part input shapes recorded at fusion time, one list per fused part;
    # lets the backend re-check shape-gated hw applicability per part when it
    # resolves the fused node's implementations (empty for unfused nodes).
    fused_input_shapes: list[list[tuple[int, ...]]] = field(default_factory=list)
    # per-part static call params recorded at fusion time (one dict per fused
    # part), so the composed fallback impl re-binds each part's own params
    # instead of dropping them; ``params`` on a fused node holds the merged
    # view for a dedicated fused hw module.
    fused_params: list[dict[str, Any]] = field(default_factory=list)
    # per-part dataflow routing recorded at fusion time: each part's input /
    # output value names.  A fused node's own ``inputs`` are the run's
    # *external* inputs (anything not produced inside the run — e.g. the
    # weight operand of a fused rmsnorm+matmul); the routing lists let the
    # backend feed every part exactly the values it consumed pre-fusion.
    fused_part_inputs: list[list[str]] = field(default_factory=list)
    fused_part_outputs: list[list[str]] = field(default_factory=list)
    # keyword binding per part input recorded at fusion time (parallel to
    # ``fused_part_inputs``; one list per part, None = positional) so the
    # composed fallback impl replays each part's kw-bound operands exactly
    # as traced — a fused MoE dispatch whose gate weights arrived by
    # keyword misbinds if replayed positionally.
    fused_part_kw: list[list[str | None]] = field(default_factory=list)
    # stateful-slot binding: the name of the mutable per-request state this
    # call reads/writes (e.g. a KV-cache slot pool), or None for pure
    # functions.  A stateful node implies serial_only (one worker observes
    # the slot writes in token order), must stay on the sw path (the state
    # lives host-side), and must never fuse into a composed hw kernel.
    state: str | None = None

    def __post_init__(self) -> None:
        # back-compat: legacy string placements (and JSON dicts) normalize
        # to the structured Placement on construction
        if not isinstance(self.placement, Placement):
            self.placement = Placement.parse(self.placement)


# --------------------------------------------------------------------------- #
# Graph
# --------------------------------------------------------------------------- #
class CourierIR:
    """Ordered function-call graph with I/O data (paper Fig. 4)."""

    def __init__(self, name: str = "trace"):
        self.name = name
        self.nodes: list[Node] = []                 # chronological order
        self.values: dict[str, Value] = {}
        self.graph_inputs: list[str] = []
        self.graph_outputs: list[str] = []
        # value name -> array for graph inputs the Frontend discovered
        # mid-trace (closure-captured weights/constants) rather than as
        # top-level call arguments.  They live in ``graph_inputs`` — the IR
        # treats them as ordinary inputs — but the backend stages their
        # arrays from here so callers only feed the per-token arguments.
        self.captured: dict[str, Any] = {}

    # -- construction ------------------------------------------------------ #
    def add_value(self, name: str, shape: Sequence[int], dtype: Any,
                  producer: str | None = None) -> Value:
        v = Value(name=name, shape=tuple(int(s) for s in shape),
                  dtype=str(np.dtype(dtype)), producer=producer)
        self.values[name] = v
        return v

    def add_node(self, node: Node) -> Node:
        for i in node.inputs:
            if i not in self.values:
                raise KeyError(f"node {node.name}: unknown input value {i!r}")
            self.values[i].consumers.append(node.name)
        for o in node.outputs:
            if o not in self.values:
                raise KeyError(f"node {node.name}: unknown output value {o!r}")
            self.values[o].producer = node.name
        self.nodes.append(node)
        return node

    # -- queries ------------------------------------------------------------ #
    def node(self, name: str) -> Node:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(name)

    def total_time_ms(self) -> float:
        return float(sum(n.time_ms or 0.0 for n in self.nodes))

    def is_linear_chain(self) -> bool:
        """True if every node's outputs feed only the next node / graph output.

        The paper's fusion rule ("if the functions have no branch nor loop")
        and the stage partitioner both operate on linear segments.
        """
        for i, n in enumerate(self.nodes):
            for o in n.outputs:
                cons = self.values[o].consumers
                for c in cons:
                    ci = next(j for j, m in enumerate(self.nodes) if m.name == c)
                    if ci != i + 1:
                        return False
        return True

    def consumers_of(self, node: Node) -> list[Node]:
        out: list[Node] = []
        for o in node.outputs:
            for c in self.values[o].consumers:
                out.append(self.node(c))
        return out

    def validate(self) -> None:
        """Topological sanity: every input is produced before use."""
        produced = set(self.graph_inputs)
        for n in self.nodes:
            for i in n.inputs:
                if i not in produced:
                    raise ValueError(
                        f"IR not causally ordered: {n.name} reads {i!r} "
                        f"before it is produced")
            produced.update(n.outputs)
        for o in self.graph_outputs:
            if o not in produced:
                raise ValueError(f"graph output {o!r} never produced")

    # -- paper Fig.4-style rendering ---------------------------------------- #
    def render(self) -> str:
        """ASCII rendering of the chronological call graph incl. I/O data."""
        lines = [f"CourierIR({self.name})  total={self.total_time_ms():.1f} ms"]
        for vn in self.graph_inputs:
            v = self.values[vn]
            tag = " (captured)" if vn in self.captured else ""
            lines.append(f"  (in)  {vn}: {v.shape} {v.dtype}  [{v.nbytes} B]{tag}")
        for n in self.nodes:
            t = f"{n.time_ms:.1f} ms" if n.time_ms is not None else "?"
            p = Placement.parse(n.placement).short()
            lines.append(f"  [{p:^10s}] {n.name} <{n.fn_key}>  {t}")
            for o in n.outputs:
                v = self.values[o]
                lines.append(f"      -> {o}: {v.shape} {v.dtype}  [{v.nbytes} B]")
        for vn in self.graph_outputs:
            lines.append(f"  (out) {vn}")
        return "\n".join(lines)

    # -- (de)serialization --------------------------------------------------- #
    def to_json(self) -> str:
        return json.dumps({
            "name": self.name,
            "nodes": [asdict(n) for n in self.nodes],
            "values": {k: asdict(v) for k, v in self.values.items()},
            "graph_inputs": self.graph_inputs,
            "graph_outputs": self.graph_outputs,
            # names only — the arrays themselves are runtime state, not IR
            "captured": sorted(self.captured),
        }, indent=2)

    @classmethod
    def from_json(cls, s: str) -> "CourierIR":
        d = json.loads(s)
        ir = cls(d["name"])
        for k, v in d["values"].items():
            v = dict(v)
            v["shape"] = tuple(v["shape"])
            ir.values[k] = Value(**v)
        for n in d["nodes"]:
            ir.nodes.append(Node(**{**n, "inputs": list(n["inputs"]),
                                    "outputs": list(n["outputs"])}))
        ir.graph_inputs = list(d["graph_inputs"])
        ir.graph_outputs = list(d["graph_outputs"])
        return ir


def linear_ir(name: str, fn_keys: Sequence[str], times_ms: Sequence[float],
              io_shape: Sequence[int] = (1,), dtype: str = "float32") -> CourierIR:
    """Convenience builder: a linear chain IR from (fn_key, time) pairs.

    Used by tests to replay the *paper's own profile* (Table I)
    through the Pipeline Generator.
    """
    assert len(fn_keys) == len(times_ms)
    ir = CourierIR(name)
    ir.add_value("d0", io_shape, dtype)
    ir.graph_inputs = ["d0"]
    prev = "d0"
    for i, (k, t) in enumerate(zip(fn_keys, times_ms)):
        out = f"d{i+1}"
        ir.add_value(out, io_shape, dtype)
        ir.add_node(Node(name=f"{k}_{i}", fn_key=k, inputs=[prev],
                         outputs=[out], time_ms=float(t)))
        prev = out
    ir.graph_outputs = [prev]
    ir.validate()
    return ir
