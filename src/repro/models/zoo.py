"""Model-zoo library ops — the general trace→pipeline path.

The paper's headline promise is acceleration *without user intervention*:
trace an unmodified program, recover the causal call graph, and build the
mixed pipeline automatically.  :mod:`repro.models.harris` proves that for
the paper's own vision demo; this module generalizes it to the LM model
zoo.  Every layer-level building block (attention, rmsnorm, matmul/FFN,
MoE dispatch, RWKV token-shift, SSM scan) becomes a ModuleDatabase row
behind the interposable :class:`~repro.core.tracer.Library`, so a
transformer forward pass written against ``lib.*`` — with its weights held
in an ordinary Python closure, exactly like a loaded checkpoint — traces
into a :class:`~repro.core.ir.CourierIR` that the Pipeline Generator can
partition, fuse (the registered rmsnorm+matmul mega-kernel), replicate,
verify, and serve.

All software impls operate on rank-2 ``[T, d]`` activations (one sequence
per pipeline token): that is the granularity the tracer observes, and it
keeps the rmsnorm module's shape gate (``len(shape) == 2``) satisfied so
fusion fires on the traced graph.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import (NodeCost, elementwise_cost, matmul_cost)
from repro.core.database import ModuleDatabase
from repro.kernels.ops import register_rmsnorm_matmul_modules

__all__ = ["make_zoo_db", "transformer_demo", "init_transformer_params",
           "recurrent_demo", "init_recurrent_params",
           "make_decode_attention", "register_decode_modules"]


# --------------------------------------------------------------------------- #
# Software implementations (the "original binary" the Frontend interposes on)
# --------------------------------------------------------------------------- #
def sw_attention(x: jax.Array, wq: jax.Array, wk: jax.Array, wv: jax.Array,
                 wo: jax.Array, *, n_heads: int,
                 theta: float = 10000.0) -> jax.Array:
    """Causal self-attention with RoPE over one sequence. x: [T, d]."""
    T, d = x.shape
    hd = d // n_heads
    q = (x @ wq).reshape(T, n_heads, hd)
    k = (x @ wk).reshape(T, n_heads, hd)
    v = (x @ wv).reshape(T, n_heads, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    s = jnp.einsum("thi,mhi->htm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(hd)
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    y = jnp.einsum("htm,mhi->thi", p, v.astype(jnp.float32))
    return (y.reshape(T, d).astype(x.dtype)) @ wo


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: [T, H, hd]."""
    T, H, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq       # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _rope_at(x: jax.Array, pos: int, theta: float) -> jax.Array:
    """Rotary embedding of ONE token at absolute position ``pos``;
    x: [1, H, hd].  Bit-matches row ``pos`` of :func:`_rope` over the full
    prefix (same fp32 angle math), which is what makes incremental decode
    agree with the re-run-the-prefix baseline."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.float32(pos) * freq                                # [half]
    cos, sin = jnp.cos(ang)[None, None, :], jnp.sin(ang)[None, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def make_decode_attention(pool: Any, *, n_heads: int,
                          theta: float = 10000.0,
                          k_buf: str = "k", v_buf: str = "v") -> Callable:
    """Incremental decode attention over a KV slot pool (STATEFUL).

    Returns ``attn(x, slot, wq, wk, wv, wo) -> [1, d]``: one new token
    ``x: [1, d]`` plus its request's ``slot`` id (scalar; ``-1`` = dead
    row).  The op reads the slot's cached (rotated) keys/values, projects
    and RoPE-rotates the new token at absolute position ``len(slot)``,
    appends its k/v row to the cache, and attends over cache + self — an
    O(prefix) step instead of the O(prefix²) full-prefix re-run, and
    bit-identical to :func:`sw_attention` on the accumulated prefix (the
    per-row unit test asserts it).

    Host-side state: the impl must run UNJITTED and serially — register it
    with ``state=`` (see :func:`register_decode_modules`) so the tracer
    marks the node ``serial_only`` and the backend keeps the stage off the
    jit/vmap/fusion paths.  A dead row (``slot < 0``) appends nothing and
    attends over only itself, so padding/evicted seats in a continuously
    batched group are harmless no-ops on the pool.
    """
    def attention_decode(x: jax.Array, slot: Any, wq: jax.Array,
                         wk: jax.Array, wv: jax.Array,
                         wo: jax.Array) -> jax.Array:
        d = x.shape[-1]
        hd = d // n_heads
        s_id = int(np.asarray(slot))
        pos = pool.length(s_id)
        q = (x @ wq).reshape(1, n_heads, hd)
        k = (x @ wk).reshape(1, n_heads, hd)
        v = (x @ wv).reshape(1, n_heads, hd)
        q, k = _rope_at(q, pos, theta), _rope_at(k, pos, theta)
        cache = pool.read(s_id)
        pool.append(s_id, **{k_buf: np.asarray(k[0]),
                             v_buf: np.asarray(v[0])})
        K = jnp.concatenate(
            [jnp.asarray(cache[k_buf], dtype=x.dtype), k], axis=0)
        V = jnp.concatenate(
            [jnp.asarray(cache[v_buf], dtype=x.dtype), v], axis=0)
        s = jnp.einsum("thi,mhi->htm", q.astype(jnp.float32),
                       K.astype(jnp.float32)) / np.sqrt(hd)
        # causality is structural: the cache holds only positions < pos
        p = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum("htm,mhi->thi", p, V.astype(jnp.float32))
        return (y.reshape(1, d).astype(x.dtype)) @ wo

    attention_decode.__name__ = "attention_decode"
    return attention_decode


def register_decode_modules(db: ModuleDatabase, pool: Any, *,
                            n_heads: int, theta: float = 10000.0,
                            name: str = "attention_decode",
                            state: str = "kv") -> None:
    """Register the stateful incremental-decode attention row.

    ``state=`` marks the row stateful: the tracer threads it onto the
    traced ``Node.state`` (implying ``serial_only``), the backend runs its
    stage unjitted, and fusion/replication/hw placement all refuse it (the
    ``state-slot`` verify rule enforces the same).  Multi-layer models
    register one row per layer, each with its own pool.
    """
    db.register(name, software=make_decode_attention(
        pool, n_heads=n_heads, theta=theta),
        cost_sw=_c_attn, tags=("zoo", "decode"), state=state)


def sw_add(a: jax.Array, b: jax.Array) -> jax.Array:
    """Residual add."""
    return a + b


def sw_swiglu(x: jax.Array, wi: jax.Array, wo: jax.Array) -> jax.Array:
    """SwiGLU FFN. x: [T, d], wi: [d, 2*ff], wo: [ff, d]."""
    h = x @ wi
    g, u = jnp.split(h, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ wo


def sw_moe(x: jax.Array, gate_w: jax.Array, w_in: jax.Array,
           w_out: jax.Array, *, top_k: int = 2) -> jax.Array:
    """Top-k MoE dispatch (dense einsum form). x: [T, d], gate_w: [d, E],
    w_in: [E, d, ff], w_out: [E, ff, d]."""
    logits = (x @ gate_w).astype(jnp.float32)                    # [T, E]
    E = logits.shape[-1]
    kth = jnp.sort(logits, axis=-1)[:, E - top_k][:, None]
    probs = jax.nn.softmax(jnp.where(logits >= kth, logits, -jnp.inf),
                           axis=-1)                              # [T, E]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, w_in))
    y = jnp.einsum("tef,efd->ted", h, w_out)
    return jnp.einsum("te,ted->td", probs, y).astype(x.dtype)


def sw_rwkv_shift(x: jax.Array, mu: jax.Array) -> jax.Array:
    """RWKV token-shift mix: blend each token with its predecessor.
    x: [T, d], mu: [d]."""
    prev = jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)
    return x + (prev - x) * mu


def sw_ssm_scan(x: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array) -> jax.Array:
    """Diagonal linear state-space scan: h_t = a*h + b*x_t; y_t = c*h_t.
    x: [T, d]; a, b, c: [d] with a in (0, 1)."""
    def step(h, x_t):
        h = a * h + b * x_t
        return h, c * h
    _, y = jax.lax.scan(step, jnp.zeros_like(x[0]), x)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Cost providers (the synthesis-report analog for the sw rows)
# --------------------------------------------------------------------------- #
def _c_attn(shapes, dtypes, params) -> NodeCost:
    (T, d) = shapes[0]
    proj = matmul_cost(T, d, d, bytes_per_el=4, batch=4)   # q/k/v/o projections
    mix = matmul_cost(T, T, d, bytes_per_el=4, batch=2)    # QK^T and PV
    return NodeCost(flops=proj.flops + mix.flops,
                    bytes_rw=proj.bytes_rw + mix.bytes_rw)


def _c_add(shapes, dtypes, params) -> NodeCost:
    return elementwise_cost(int(np.prod(shapes[0])), bytes_per_el=4)


def _c_swiglu(shapes, dtypes, params) -> NodeCost:
    (T, d), (_, two_ff) = shapes[0], shapes[1]
    ff = two_ff // 2
    up = matmul_cost(T, two_ff, d, bytes_per_el=4)
    down = matmul_cost(T, d, ff, bytes_per_el=4)
    return NodeCost(flops=up.flops + down.flops,
                    bytes_rw=up.bytes_rw + down.bytes_rw)


def _c_moe(shapes, dtypes, params) -> NodeCost:
    (T, d), (_, E) = shapes[0], shapes[1]
    ff = shapes[2][2]
    expert = matmul_cost(T, ff, d, bytes_per_el=4, batch=2 * E)
    return NodeCost(flops=expert.flops, bytes_rw=expert.bytes_rw)


def _c_scan(shapes, dtypes, params) -> NodeCost:
    return elementwise_cost(int(np.prod(shapes[0])), flops_per_el=4,
                            bytes_per_el=4, n_operands=4)


# --------------------------------------------------------------------------- #
# The zoo database
# --------------------------------------------------------------------------- #
def make_zoo_db() -> ModuleDatabase:
    """ModuleDatabase with every model-zoo layer op registered.

    rmsnorm / matmul / the fused rmsnorm+matmul mega-kernel come from
    :func:`repro.kernels.ops.register_rmsnorm_matmul_modules` — the same
    rows the fusion benchmark exercises, now reachable from a trace.  The
    remaining ops are software rows (database miss → sw placement), which
    is what keeps the traced graph *mixed*: hw islands separated by sw
    nodes, exactly the shape the partitioner and fusion pass must handle.
    """
    db = ModuleDatabase("zoo")
    register_rmsnorm_matmul_modules(db)
    db.register("attention", software=sw_attention, cost_sw=_c_attn,
                tags=("zoo",))
    db.register("add", software=sw_add, cost_sw=_c_add, tags=("zoo",))
    db.register("swiglu", software=sw_swiglu, cost_sw=_c_swiglu,
                tags=("zoo",))
    db.register("moe", software=sw_moe, cost_sw=_c_moe, tags=("zoo",))
    db.register("rwkv_shift", software=sw_rwkv_shift, cost_sw=_c_scan,
                tags=("zoo",))
    db.register("ssm_scan", software=sw_ssm_scan, cost_sw=_c_scan,
                tags=("zoo",))
    return db


# --------------------------------------------------------------------------- #
# Demo apps (unmodified user code over the interposable Library)
# --------------------------------------------------------------------------- #
def init_transformer_params(key: jax.Array, *, n_layers: int = 2,
                            d: int = 128, ff: int = 256, n_heads: int = 4,
                            vocab: int = 512) -> dict:
    """Random checkpoint for :func:`transformer_demo` (float32)."""
    def dense(k, shape):
        fan_in = shape[0]
        return (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5)

    keys = iter(jax.random.split(key, 6 * n_layers + 2))
    layers = []
    for _ in range(n_layers):
        layers.append({
            "ln1": jnp.zeros((d,), jnp.float32),
            "wq": dense(next(keys), (d, d)),
            "wk": dense(next(keys), (d, d)),
            "wv": dense(next(keys), (d, d)),
            "wo": dense(next(keys), (d, d)),
            "ln2": jnp.zeros((d,), jnp.float32),
            "wi": dense(next(keys), (d, 2 * ff)),
            "wo_ffn": dense(next(keys), (ff, d)),
        })
    return {"layers": layers, "n_heads": n_heads, "theta": 10000.0,
            "ln_f": jnp.zeros((d,), jnp.float32),
            "w_out": dense(next(keys), (d, vocab))}


def transformer_demo(lib: Any, params: dict) -> Callable:
    """Pre-norm transformer forward over ``lib.*`` calls; weights closed over.

    The returned ``app(x)`` is the "unmodified binary": it never mentions
    tracing, placement, or pipelines.  Every weight reaches the Frontend as
    a mid-trace first sighting (a captured graph input), and the final
    ``rmsnorm → matmul`` (lm head) pair is the branch-free hw run the
    fusion pass collapses into the registered mega-kernel.
    """
    n_heads = int(params["n_heads"])
    theta = float(params["theta"])

    def app(x: jax.Array) -> jax.Array:          # x: [T, d] embeddings
        for ly in params["layers"]:
            h = lib.rmsnorm(x, ly["ln1"])
            a = lib.attention(h, ly["wq"], ly["wk"], ly["wv"], ly["wo"],
                              n_heads=n_heads, theta=theta)
            x = lib.add(x, a)
            h = lib.rmsnorm(x, ly["ln2"])
            f = lib.swiglu(h, ly["wi"], ly["wo_ffn"])
            x = lib.add(x, f)
        h = lib.rmsnorm(x, params["ln_f"])
        return lib.matmul(h, params["w_out"])    # logits [T, vocab]

    app.__name__ = "transformer"
    return app


def init_recurrent_params(key: jax.Array, *, d: int = 64) -> dict:
    """Random weights for :func:`recurrent_demo` (RWKV shift + SSM scan)."""
    k1, k2 = jax.random.split(key)
    return {"mu": jax.random.uniform(k1, (d,), jnp.float32, 0.1, 0.9),
            "a": jax.random.uniform(k2, (d,), jnp.float32, 0.5, 0.95),
            "b": jnp.ones((d,), jnp.float32),
            "c": jnp.ones((d,), jnp.float32),
            "ln": jnp.zeros((d,), jnp.float32)}


def recurrent_demo(lib: Any, params: dict) -> Callable:
    """Minimal RWKV/SSM-style block: shift-mix → norm → scan → residual."""
    def app(x: jax.Array) -> jax.Array:          # x: [T, d]
        h = lib.rwkv_shift(x, params["mu"])
        h = lib.rmsnorm(h, params["ln"])
        y = lib.ssm_scan(h, params["a"], params["b"], params["c"])
        return lib.add(x, y)

    app.__name__ = "recurrent"
    return app
